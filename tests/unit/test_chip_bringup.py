"""Chip bring-up rules, checked without a chip.

Two kinds of test. (1) REHEARSAL against a compile-only v5e topology: the
installed libtpu can describe a ``v5e:2x2`` slice inside a CPU-pinned
process, and lowering/compiling against its devices runs the real Mosaic
and XLA:TPU compilers — so "does the kernel lower at this shape, does the
sharded step keep the kernel" is answered here, before chip time is spent.
It says nothing about numerics or speed. (2) The RULES the bring-up set:
no process holds a chip and then spawns a child that needs it, no fallback
hides the device, one cache directory placed from outside.
"""

import collections
import contextlib
import dataclasses
import functools
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.models import common
from deepspeed_tpu.models.gpt2 import PRESETS, GPT2Config, GPT2Model
from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.ops.pallas import flash_attention as fa

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ------------------------------------------------------------- rehearsal
@pytest.fixture(scope="module")
def v5e():
    """The four devices of a compile-only v5e 2x2 slice."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu / no topology support here
        pytest.skip(f"get_topology_desc raised: {type(e).__name__}: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def _mesh(devices, **dims):
    from deepspeed_tpu.parallel.topology import ALL_AXES

    shape = [dims.get(a, 1) for a in ALL_AXES]
    return Mesh(np.array(devices[:int(np.prod(shape))]).reshape(shape),
                ALL_AXES)


def _abstract(shape, dtype, mesh, spec=P()):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


@pytest.mark.parametrize("heads,dim", [(16, 96), (12, 64), (16, 128)])
def test_flash_fwd_bwd_compiles_for_v5e(v5e, heads, dim):
    mesh = _mesh(v5e)
    x = _abstract((1, 1024, heads, dim), jnp.bfloat16, mesh)
    loss = lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v).astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    # fwd, and the ONE backward call that gives dq, dk and dv (PR 47)
    assert text.count("tpu_custom_call") == 2
    # each Mosaic call is an HLO instruction named after the kernel's own
    # ``name`` (here inside the transform's: %jvp_flash_fwd_.1): that name
    # is what an op event of a device profile carries
    for kernel in ("flash_fwd", "flash_bwd_dkv"):
        assert len(re.findall(
            rf"%[\w.]*{kernel}[\w.]* = [^\n]*tpu_custom_call", text)) == 1, \
            kernel
    assert "flash_bwd_dq" not in text


# every length a cell runs the causal forward at, heads cut to a few: the
# train cells, doc.c1's awkward 768 and 896, doc4k.c1, openPangu's q.k 192 /
# v 128 at its median prompt, Solar's median and longest
@pytest.mark.parametrize("t,d,dv", [
    (1024, 96, 96), (1024, 64, 64), (768, 64, 64), (896, 64, 64),
    (2048, 128, 128), (4096, 192, 128), (16384, 128, 128),
    (32768, 128, 128)], ids=lambda x: str(x))
def test_causal_flash_forward_compiles_for_v5e_at_the_cells_shapes(v5e, t, d,
                                                                    dv):
    """The one causal forward at ``flash_forward_plan``'s shapes: a span of
    K and V double-buffered in VMEM beside the unrolled walk's score tiles
    is what Mosaic may refuse (a 4,096-key span at 192 / 128 did, under the
    16 MiB default of scoped VMEM), and only a compile for the chip says.
    A prompt length's prefill compiles inside its first request's deadline,
    so the kernel's own compile is held to a few seconds too."""
    mesh = _mesh(v5e)
    qk = _abstract((1, t, 4, d), jnp.bfloat16, mesh)
    v = _abstract((1, t, 4, dv), jnp.bfloat16, mesh)
    text = jax.jit(fa.flash_attention).lower(qk, qk, v).compile().as_text()
    call, = re.findall(r"%[\w.]*flash_fwd[\w.]* = [^\n]*tpu_custom_call", text)
    assert f"(bf16[4,{t},{dv}]" in call and f"f32[4,1,{t}]" in call


# heads / KV heads, q.k / v width, rotary columns, window (+ a sink), block,
# length: MiMo's full and window layers at 16,384, SDAR's block mask at its
# prompt (sub-blocks of 256), Solar's one attention layer, OLMoE
@pytest.mark.parametrize("h,kv,d,dv,r,window,block,t", [
    (64, 4, 192, 128, 64, None, None, 16384),
    (64, 8, 192, 128, 64, 128, None, 16384),
    (32, 4, 128, 128, 128, None, 4, 2304),
    (64, 8, 128, 128, 128, None, None, 32768),
    (16, 16, 128, 128, 128, None, None, 4096)], ids=lambda x: str(x))
def test_flash_prefill_compiles_for_v5e_at_the_cells_shapes(
        v5e, h, kv, d, dv, r, window, block, t):
    """``flash_prefill`` on q, k, v as the projections made them: the roll
    inside ``r`` of a head's lanes, the masked store beside the passed-
    through lanes, the (rows, r) table blocks and the output's row blocks
    of (B, T, H * 128) are what only Mosaic can refuse. The compiled call
    takes K and V at their own heads and writes the output as rows."""
    mesh = _mesh(v5e)
    sink = () if window is None else (_abstract((h,), jnp.float32, mesh),)
    text = jax.jit(lambda q, k, v, cos, sin, *sink: fa.flash_prefill(
        q, k, v, cos, sin, *sink, window=window, block=block)).lower(
            _abstract((1, t, h, d), jnp.bfloat16, mesh),
            _abstract((1, t, kv, d), jnp.bfloat16, mesh),
            _abstract((1, t, kv, dv), jnp.bfloat16, mesh),
            *[_abstract((t, r), jnp.float32, mesh)] * 2, *sink
    ).compile().as_text()
    call, = re.findall(r"%[\w.]*flash_fwd[\w.]* = [^\n]*tpu_custom_call[^\n]*",
                       text)
    assert f"(bf16[1,{t},{h * dv}]" in call
    assert f"bf16[{kv},{t},{d}]" in call and f"bf16[{kv},{t},{dv}]" in call


def test_windowed_flash_fwd_bwd_compiles_for_v5e_at_the_cells_shape(v5e):
    """``trinity-mini.train.z1.s8k``'s window layers: 32 heads x 8,192 x 128
    under a window of 2,048, the forward and the ONE backward kernel, each
    an HLO instruction under the name a trace reads (``flash_*_win``: the
    standing readers' ``flash_fwd`` / ``flash_bwd`` still match)."""
    mesh = _mesh(v5e)
    x = _abstract((1, 8192, 32, 128), jnp.bfloat16, mesh)
    loss = lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, window=2048).astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    for kernel in ("flash_fwd_win", "flash_bwd_dkv_win"):
        assert len(re.findall(
            rf"%[\w.]*{kernel}[\w.]* = [^\n]*tpu_custom_call", text)) == 1, \
            kernel
    # no (T, T) square of scores: the band lives in VMEM
    assert not re.search(r"f32\[(?:\d+,)*8192,8192\]", text)


def _attention_grad_text(attend, mesh, *shapes):
    """The compiled gradient of ``sum(attend(q, k, v))`` for the chip."""
    args = [_abstract(s, jnp.bfloat16, mesh) for s in shapes]
    loss = lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32))
    with mesh:
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *args).compile().as_text()


# 576 = 9 x 64 is a 24 x 24 latent (models/diffusion.py::_mha) and 520 = 65
# x 8: under a whole 128 a q block is not a lane dimension Mosaic takes, so
# these take the einsum path by choice; 640, 384 (one block) and a cross-
# attention over 77 keys keep the kernel
@pytest.mark.parametrize("t_q,t_k,kernel", [
    (576, 576, False), (520, 520, False), (640, 640, True), (384, 384, True),
    (1024, 77, True)])
def test_non_causal_attention_compiles_for_v5e_at_any_length(v5e, t_q, t_k,
                                                             kernel):
    """The log-sum-exp and delta pass between the kernels as (B*H, 1, T) in
    blocks (1, 1, block_q): the q block is a multiple of 128 or the whole
    length. ``flash_supports`` knows, so the dispatcher never hands Mosaic a
    block it refuses (interpret mode, which the CPU tests run the kernels
    in, enforces no tiling: only a compile for the chip sees this)."""
    assert fa.flash_supports(t_q, t_k, False) == kernel
    text = _attention_grad_text(
        functools.partial(common.local_causal_attention, causal=False),
        _mesh(v5e), (2, t_q, 8, 64), (2, t_k, 8, 64), (2, t_k, 8, 64))
    assert text.count("tpu_custom_call") == (3 if kernel else 0)


def test_a_flash_block_under_128_is_refused_before_mosaic_sees_it(v5e):
    """64-row blocks were legal while the log-sum-exp was a (.., T, 1)
    column; as a lane dimension they are not. A config says so when it is
    made; the kernel's wrapper, handed one directly, raises its own error
    and not the compiler's; 128 and 256 compile."""
    from deepspeed_tpu.models.bert import BertConfig

    for make in (GPT2Config, BertConfig):
        with pytest.raises(ValueError, match="multiple of 128"):
            make(flash_block=64)
        assert make(flash_block=256).flash_block == 256
    assert not fa.flash_supports(1024, 1024, True, 64, 64)
    x = _abstract((1, 1024, 8, 64), jnp.bfloat16, _mesh(v5e))
    with pytest.raises(ValueError, match="whole 128s"):
        jax.jit(functools.partial(fa.flash_attention, block_q=64,
                                  block_k=64)).lower(x, x, x)
    for block in (128, 256):
        text = _attention_grad_text(
            functools.partial(common.local_causal_attention,
                              flash_block=block),
            _mesh(v5e), *[(1, 1024, 8, 64)] * 3)
        assert text.count("tpu_custom_call") == 2    # fwd, the one bwd


@pytest.mark.parametrize("block", [16, 64, 128, 512])
def test_sparse_flash_compiles_for_v5e_at_the_layouts_block(v5e, block):
    """The block-sparse kernels' tile is the layout's block (16 is the
    reference's default), so their log-sum-exp is a row a block, (B*H*n, 1,
    block): any block the parent's kernels took still lowers."""
    n = 1024 // block
    layout = np.tril(np.ones((n, n), bool)) & ~np.tril(np.ones((n, n), bool), -3)
    text = _attention_grad_text(
        lambda q, k, v: fa.flash_attention_sparse(q, k, v, layout),
        _mesh(v5e), *[(1, 1024, 4, 64)] * 3)
    for kernel in ("sparse_flash_fwd", "sparse_flash_bwd_dq",
                   "sparse_flash_bwd_dkv"):
        assert len(re.findall(
            rf"%[\w.]*{kernel}[\w.]* = [^\n]*tpu_custom_call", text)) == 1, \
            kernel


# B, S, H, KV, Dh: the serving cells' gpt2-xl rows (25 x 64 in 1664 lanes),
# gpt2-760m's, GQA, and a generate()-sized cache that does not tile
@pytest.mark.parametrize("shape", [(1, 1024, 25, 25, 64), (2, 1024, 16, 16, 96),
                                   (2, 1024, 32, 8, 64), (8, 1001, 16, 16, 96)])
def test_decode_kernel_compiles_for_v5e_uninterpreted(v5e, shape):
    B, S, H, KV, Dh = shape
    mesh = _mesh(v5e)
    q = _abstract((B, H, Dh), jnp.bfloat16, mesh)
    cache = _abstract((2, B, S, common.kv_cache_width(KV, Dh)), jnp.bfloat16,
                      mesh)
    scalar = _abstract((), jnp.int32, mesh)
    text = jax.jit(functools.partial(da.decode_attention, n_kv=KV)).lower(
        q, cache, cache, scalar, scalar).compile().as_text()
    # the kernel's ``name`` is the HLO instruction, hence the op's name in a
    # device profile
    assert len(re.findall(r"%[\w.]*decode_attn[\w.]* = [^\n]*tpu_custom_call",
                          text)) == 1


def _chunk_carry(cache, mesh, batch=1):
    """What a decode chunk takes after the weights: the last token, the
    cache, ``done`` and the key."""
    return (_abstract((batch,), jnp.int32, mesh), cache,
            _abstract((batch,), jnp.bool_, mesh),
            _abstract((2,), jnp.uint32, mesh))


def _token_in_token_out(prefill, chunk, params, cache, mesh, prompt, vocab):
    """The two serving programs hand each other a TOKEN: prefill's outputs
    are the chunk's carry, the chunk's own outputs are its carry again (plus
    the chunk's tokens), and no ``(B, vocab)`` float32 leaf is among them."""
    key = _abstract((2,), jnp.uint32, mesh)
    first = jax.eval_shape(prefill, params,
                           _abstract((1, prompt), jnp.int32, mesh), key)
    carry = _chunk_carry(cache, mesh)
    again = jax.eval_shape(chunk, params, *carry)
    shape = lambda tree: jax.tree.map(lambda x: (x.shape, x.dtype), tree)
    assert shape(first) == shape(carry) == shape(again[:4])
    assert shape(again[4]) == ((1, 16), jnp.int32)
    for leaf in jax.tree.leaves((first, again)):
        assert not (leaf.dtype == jnp.float32 and leaf.shape[-1:] == (vocab,))


def test_decode_chunk_for_v5e_reads_cache_and_weights_in_place(v5e):
    """The serving decode chunk at gpt2-xl widths (2 layers), compiled as
    the chip compiles it — entry layouts the TPU's own (``fc2_w`` K-minor,
    the cache row-major): the kernel is in it, no stacked weight is relaid
    out, and the only cache-shaped copies are at most the two an undonated
    input costs (k and v)."""
    from deepspeed_tpu.inference.engine import build_serving_programs

    mesh = _mesh(v5e)
    model = GPT2Model(dataclasses.replace(PRESETS["gpt2-xl"], n_layer=2))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: _abstract(s.shape, jnp.bfloat16, mesh), shapes)
    prefill, chunk = build_serving_programs(model, 1024, 16, False, 1.0, 0,
                                            1.0, None)
    cache = jax.tree.map(lambda s: _abstract(s.shape, s.dtype, mesh),
                         jax.eval_shape(lambda: model.init_cache(1, 1024)))
    with mesh:
        _token_in_token_out(prefill, chunk, params, cache, mesh, 512, 50257)
        # prefill ends in the first token's argmax, after the flash kernel
        first = jax.jit(prefill).lower(
            params, _abstract((1, 512), jnp.int32, mesh),
            _abstract((2,), jnp.uint32, mesh)).compile().as_text()
        assert re.search(r"%[\w.]*flash_fwd[\w.]* = [^\n]*tpu_custom_call",
                         first)
        text = jax.jit(chunk).lower(
            params, *_chunk_carry(cache, mesh)).compile().as_text()
    assert re.search(r"%[\w.]*decode_attn[\w.]* = [^\n]*tpu_custom_call", text)
    assert re.search(r"bf16\[2,6400,1600\]\{1,2,0", text)   # as stored, K-minor
    copies = re.findall(r"= (\w+\[[\d,]*\])\S* copy\(", text)
    as_hlo = lambda x: "bf16[" + ",".join(map(str, x.shape)) + "]"
    stacked = {as_hlo(x) for x in jax.tree.leaves(shapes["blocks"])}
    assert not stacked & set(copies), copies
    # (the tied wte and wpe, stored V-minor, are still relaid out: PERF.md)
    assert copies.count(as_hlo(cache["k"])) <= 2, copies


def test_decode_chunk_over_tensor_4_keeps_the_kernel(v5e):
    """gpt2-760m served at tp=4 (what chip_smoke.py runs on four chips):
    the cache rows (16 x 96 = 1536, no pad columns) are cut over 'tensor'
    with the heads, and the kernel sits in a shard_map over them."""
    from deepspeed_tpu.inference.engine import build_serving_programs

    mesh = _mesh(v5e, tensor=4)
    named = lambda spec: NamedSharding(mesh, spec)
    model = GPT2Model(dataclasses.replace(PRESETS["gpt2-760m"], n_layer=2))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    from deepspeed_tpu.sharding import ShardingRegistry

    # as InferenceEngine fits them: the 50257-row wte stays whole
    specs = ShardingRegistry(mesh).fit(model.param_partition_specs(), shapes)
    params = jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                             sharding=named(spec)),
        shapes, specs)
    cache_sh = jax.tree.map(named, model.cache_partition_specs(),
                            is_leaf=lambda x: isinstance(x, P))
    assert cache_sh["k"].spec == P(None, None, None, "tensor")
    _, chunk = build_serving_programs(model, 1024, 16, False, 1.0, 0, 1.0,
                                      None, cache_shardings=cache_sh)
    cache = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(lambda: model.init_cache(1, 1024)), cache_sh)
    with mesh:
        text = jax.jit(chunk).lower(
            params, *_chunk_carry(cache, mesh)).compile().as_text()
    assert re.search(r"%[\w.]*decode_attn[\w.]* = [^\n]*tpu_custom_call", text)
    assert "bf16[2,1,1024,384]" in text         # a shard of the cache


@pytest.mark.parametrize("t", [601, 1001])
def test_prefill_at_untileable_prompt_length_compiles(v5e, t):
    """Mosaic refuses a block whose row count is neither a multiple of 8
    nor the whole array; T=601/1001 used to halve down to a 1-row block."""
    mesh = _mesh(v5e)
    model = GPT2Model(dataclasses.replace(PRESETS["gpt2-760m"], n_layer=2))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: _abstract(s.shape, jnp.bfloat16, mesh), shapes)

    def prefill(p, ids):
        return model.prefill(p, ids, model.init_cache(1, 1024))

    with mesh:
        text = jax.jit(prefill).lower(
            params, _abstract((1, t), jnp.int32, mesh)).compile().as_text()
    assert "tpu_custom_call" in text


def test_760m_grad_sharded_over_four_chips_keeps_the_kernel(v5e):
    """Bare GSPMD cannot partition a Mosaic call (jax raises at lowering on
    more than one device): the kernel must sit in a shard_map manual over
    every mesh axis. 16x96 heads, 24 layers, batch over data=4. TWO Mosaic
    calls: the forward and the one backward (PR 47). Remat 'attn' saves the forward's ``o`` and
    log-sum-exp, named inside that shard_map by the custom VJP's forward
    rule, so the recompute holds no second forward (four until PR 32: the
    name sat on the VJP's output, the residuals were thrown away)."""
    mesh = _mesh(v5e, data=4)
    model = GPT2Model(dataclasses.replace(PRESETS["gpt2-760m"], remat="attn"))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: _abstract(s.shape, jnp.bfloat16, mesh), shapes)
    ids = _abstract((16, 1024), jnp.int32, mesh, P("data"))
    with mesh:
        lowered = jax.jit(jax.value_and_grad(
            lambda p, b: model.loss(p, {"input_ids": b}))).lower(params, ids)
    assert lowered.as_text().count("tpu_custom_call") == 2


# ---------------------- gpt2-xl's ZeRO-3 step over four chips (train.z3x4)
HEAD_ELEMS = 50257 * 1600
HBM_PER_CHIP = 16.91e9          # 15.75 GiB: what a v5e chip offers a program


def _compiled_train_step(devices, config, traffic_file, chips):
    """The engine's REAL train step of a train cell (the cell's own
    configuration and traffic files: ZeRO stage, micro-batch a chip,
    accumulation steps, bf16, AdamW, clipping, remat 'attn'), compiled for
    ``chips`` described chips over 'data'. Nothing can be placed on a
    described device, so the two places where the engine materializes state
    hand back shapes instead. -> (the engine, which keeps the program alive,
    the program's record at the door with these shapes as its arguments):
    ``record.compiled()`` is the ONE compile the tests below read."""
    import json
    import types

    import deepspeed_tpu
    from benchmark import families
    from deepspeed_tpu.runtime import engine as engine_mod

    mesh = _mesh(devices, data=chips)
    cfg, traffic = (json.load(open(os.path.join(REPO, "benchmark", d, f)))
                    for d, f in (("configs", config), ("traffic", traffic_file)))
    micro = traffic["engine"]["micro_batch_per_chip"]
    gas = traffic["engine"]["gradient_accumulation_steps"]
    ds = dict(cfg["train"]["ds_config"],
              train_micro_batch_size_per_gpu=micro,
              gradient_accumulation_steps=gas, steps_per_print=0,
              zero_optimization={"stage": traffic["engine"]["zero_stage"]})
    real_jit, real_put = engine_mod.sharded_jit, jax.device_put

    def abstract(shape, sharding):
        return jax.ShapeDtypeStruct(shape.shape, shape.dtype, sharding=sharding)

    def jit_or_shapes(fn, *, label, out_shardings, **kw):
        if label != "engine/init_state":
            return real_jit(fn, label=label, out_shardings=out_shardings, **kw)
        return lambda: jax.tree.map(abstract, jax.eval_shape(fn), out_shardings)

    def put_or_shape(x, sharding=None, **kw):
        if getattr(sharding, "mesh", None) is mesh:
            return abstract(jax.eval_shape(lambda: x), sharding)
        return real_put(x, sharding, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "sharded_jit", jit_or_shapes)
        mp.setattr(jax, "device_put", put_or_shape)
        engine, *_ = deepspeed_tpu.initialize(
            model=families.get(cfg["family"]).build_model(cfg, "train"),
            config=ds, mpu=types.SimpleNamespace(mesh=mesh))
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (gas * chips * micro, traffic["seq_len"]), jnp.int32,
        sharding=engine.sharding.batch_sharding(2))}
    record = engine._get_compiled_train_batch(gas, batch).program_record
    record.abstract_args, record.abstract_kwargs = (engine.state, batch), {}
    return engine, record


@pytest.fixture(scope="module")
def xl_z3_record(v5e):
    """``gpt2-xl.train.z3x4``: ZeRO-3 over data=4, micro-batch 16 a chip."""
    return _compiled_train_step(v5e, "gpt2-xl.json", "train.z3x4.json", 4)


@pytest.fixture(scope="module")
def gas4_record(v5e):
    """``gpt2-760m.train.z1.gas4``: ZeRO-1 on one chip, 4 micro-batches of 6
    accumulated in float32 by the engine's scan."""
    return _compiled_train_step(v5e, "gpt2-760m.json", "train.z1.gas4.json", 1)


@pytest.fixture(scope="module")
def xl_z3_step(xl_z3_record):
    return xl_z3_record[1].compiled()


@pytest.fixture(scope="module")
def gas4_step(gas4_record):
    return gas4_record[1].compiled()


def _computations(text):
    """{computation: its lines} of a compiled module's text, and ``reach``:
    the computations a computation calls, itself included."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name:
            comps[name].append(line)

    def called(keys, lines):
        return [c for l in lines
                for c in re.findall(rf"(?:{keys})=%?([\w.\-]+)", l)]

    def reach(root, keys="to_apply|calls|body|condition"):
        seen, todo = set(), [root]
        while todo:
            c = todo.pop()
            if c in comps and c not in seen:
                seen.add(c)
                todo += called(keys, comps[c])
        return seen

    return comps, called, reach


def _flash_kernels_by_loop(text):
    """[{kernel: Mosaic calls}] for every ``while`` body of the compiled text
    that holds a flash kernel (directly or in what it calls), the smallest
    body first: the layer scans come before a scan that holds them."""
    comps, called, reach = _computations(text)
    found = []
    for body in {c for lines in comps.values() for c in called("body", lines)}:
        inside = reach(body)
        n = {k: sum(bool(re.search(
            rf"%[\w.]*{k}[\w.]* = [^\n]*tpu_custom_call", l))
            for c in inside for l in comps[c])
            for k in ("flash_fwd", "flash_bwd_dkv")}
        if any(n.values()):
            found.append((len(inside), n))
    return [n for _, n in sorted(found, key=lambda x: x[0])]


def _assert_the_forward_kernel_runs_once_a_layer(text):
    """The layer scan of the forward holds ``flash_fwd``; the backward's
    holds ``flash_bwd_dkv``, ONE backward call a layer (PR 47: it gives dq
    too), and NO forward kernel: what remat 'attn' saved (``o``, the
    log-sum-exp) is what the backward reads."""
    loops = _flash_kernels_by_loop(text)
    assert loops[:2] in (
        [{"flash_fwd": 1, "flash_bwd_dkv": 0},
         {"flash_fwd": 0, "flash_bwd_dkv": 1}],
        [{"flash_fwd": 0, "flash_bwd_dkv": 1},
         {"flash_fwd": 1, "flash_bwd_dkv": 0}]), loops
    assert "flash_bwd_dq" not in text
    assert len(re.findall(r"%[\w.]*flash_fwd[\w.]* = [^\n]*tpu_custom_call",
                          text)) == 1


def _head_collectives(text):
    """[(op, dtype, inside a while body?)] of every all-gather / all-reduce /
    reduce-scatter of the compiled text that moves the tied head's 50257 x
    1600 elements: the result of a gather or an all-reduce, four times the
    result of a reduce-scatter. XLA:TPU writes most reduce-scatters as a
    fusion that calls a computation holding the whole all-reduce, which is
    counted there, in a loop if its caller is."""
    comps, called, reach = _computations(text)
    inside = set().union(*(reach(c) for lines in comps.values()
                           for c in called("body|condition", lines)))
    found = []
    for name, lines in comps.items():
        for l in lines:
            m = re.search(r"= (.*?) (all-gather|all-reduce|reduce-scatter)"
                          r"(?:-start)?\(", l)
            for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", m.group(1)) if m else ():
                if math.prod(map(int, dims.split(","))) * (
                        4 if m.group(2) == "reduce-scatter" else 1) == HEAD_ELEMS:
                    found.append((m.group(2), dtype, name in inside))
    return found


def test_xl_z3_step_gathers_the_head_once_and_reduces_its_gradient_once(xl_z3_step):
    """Left to the partitioner the chunked loss gathered the ZeRO-3 head in
    the scan's body: 93 chunks forward and 93 backward, 186 gathers of 160
    MB a step (ledger, PR 27: the largest device op of the cell), and an
    all-reduce of the head's whole gradient a chunk beside them."""
    found = _head_collectives(xl_z3_step.as_text())
    assert [op for op, _, in_loop in found if in_loop] == [], found
    assert 1 <= sum(op == "all-gather" for op, _, _ in found) <= 2, found
    assert sum(op != "all-gather" for op, _, _ in found) == 1, found
    # cast, then gather: 160 MB of bf16 and not 321 MB of float32
    assert {dtype for _, dtype, _ in found} == {"bf16"}, found


def _layer_scan_collectives(text):
    """{"fwd" | "bwd": [(op, result type)]} of the collectives inside the
    layer scan of the forward (the smallest ``while`` body that holds
    ``flash_fwd``) and of the backward (``flash_bwd_dkv``): what runs once a
    layer. A reduce-scatter that XLA:TPU writes as an ``all-reduce-scatter``
    fusion is listed as ``reduce-scatter`` with the FUSION's result."""
    comps, called, reach = _computations(text)
    bodies = sorted({c for lines in comps.values() for c in called("body", lines)},
                    key=lambda b: len(reach(b)))
    found = {}
    for key, kernel in (("fwd", "flash_fwd"), ("bwd", "flash_bwd_dkv")):
        body = next(b for b in bodies if any(
            re.search(rf"%[\w.]*{kernel}[\w.]* = [^\n]*tpu_custom_call", l)
            for c in reach(b) for l in comps[c]))
        scattered = {c for k in reach(body) for c in called("calls", comps[k])
                     if "all-reduce-scatter" in c}
        rows = []
        for c in reach(body):
            for l in comps[c]:
                m = re.search(r"= (.*?) (all-gather|all-reduce|reduce-scatter|"
                              r"all-to-all|collective-permute)(?:-start)?\(", l)
                if m and c not in scattered:
                    rows.append((m.group(2), m.group(1)))
                m = re.search(r"= (.*?) fusion\(.*calls=%?([\w.\-]+)", l)
                if m and m.group(2) in scattered:
                    rows.append(("reduce-scatter", m.group(1)))
        found[key] = rows
    return found


# one layer's four weights: the leaves of a gpt2-xl block over the
# persistence threshold, 61.4 MB in bf16
XL_LAYER_WEIGHTS = ("1600,4800", "1600,1600", "1600,6400", "6400,1600")


def test_xl_z3_step_gathers_a_layers_weights_where_the_block_uses_them(xl_z3_step):
    """ZeRO-3's gather-on-use, stated (runtime/zero/partition.py). Left to
    the partitioner the forward kept ``qkv_w`` sharded and moved ACTIVATIONS
    (``all-gather bf16[64,1024,1600]``, 210 MB a layer, and an ``all-to-all
    bf16[4,16,1024,1200]`` back), and gathered a layer's biases as
    update-slice + ``all-reduce bf16[6400]``: the forward matmuls cost two
    to three times their own re-run in the backward, which gathered the
    weight (PERF.md section 5, PR 35). All three passes now gather the
    weight; the stacked biases, judged a layer, are whole on every chip."""
    found = _layer_scan_collectives(xl_z3_step.as_text())
    for op, result in found["fwd"]:
        assert op in ("all-gather", "all-reduce"), (op, result)
        assert not re.search(r",1024,(1600|1200)\]", result), (op, result)
        assert not re.search(r"bf16\[(6400|4800)\]", result), (op, result)
    for key in ("fwd", "bwd"):
        gathered = " ".join(r for op, r in found[key] if op == "all-gather")
        for dims in XL_LAYER_WEIGHTS:
            assert re.search(rf"bf16\[(1,)?{dims}\]", gathered), (key, dims)
    # the backward's reductions are the parent's: three weight gradients
    # leave as reduce-scatters, fc_w's inside ONE combined all-reduce with
    # the layer's small gradients (its whole (1600, 6400): the parent's
    # program too; ROADMAP S7), and no weight gradient is all-reduced alone
    reduced = [r for op, r in found["bwd"] if op == "all-reduce"]
    for dims in ("1600,4800", "6400,1600", "1600,1600"):
        assert not any(f"[{dims}]" in r for r in reduced), (dims, reduced)
    assert sum("[1600,6400]" in r for r in reduced) <= 1, reduced
    assert len([1 for op, _ in found["bwd"] if op == "reduce-scatter"]) == 3
    assert not [r for op, r in found["bwd"] if op == "all-to-all"]


def test_the_rule_is_in_the_four_chip_trace_and_not_in_the_one_chip_trace(
        xl_z3_record, gas4_record):
    """4 leaves a layer on ``z3x4``; on one chip no DP axis holds more than
    one device, and the step's trace holds nothing of the rule."""
    from deepspeed_tpu.runtime.zero.partition import GATHERED_NAME

    def jaxpr_text(fixture):
        engine, record = fixture
        with engine.mesh:
            return engine, str(record.jitted.trace(
                *record.abstract_args).jaxpr)

    engine, text = jaxpr_text(xl_z3_record)
    assert sorted(engine._layer_gathers.leaves) == [
        "fc2_w", "fc_w", "proj_w", "qkv_w"]
    assert f"name={GATHERED_NAME}" in text
    engine, text = jaxpr_text(gas4_record)
    assert engine._layer_gathers is None
    assert GATHERED_NAME not in text and "custom_vjp" not in text


def test_xl_z3_step_at_micro_batch_16_fits_a_chip(xl_z3_step):
    _, total = _footprint(xl_z3_step)
    assert total < HBM_PER_CHIP, (
        f"gpt2-xl ZeRO-3 step at micro-batch 16: {total / 1e9:.2f} GB of "
        f"{HBM_PER_CHIP / 1e9:.2f} GB a chip")
    # what XLA rematerializes to make a step fit is on no idle share (PR 26)
    assert ".remat" not in xl_z3_step.as_text()


def _stacked(text, dtype, *dims):
    return re.search(rf"{dtype}\[{','.join(map(str, dims))}\]", text)


def test_xl_z3_step_runs_the_flash_forward_once_a_layer(xl_z3_step):
    """Inside the shard_map a mesh puts the kernel in, the names reach the
    policy: the backward's layer scan holds no ``flash_fwd`` (it did until
    PR 32: 202 ms of a 2,433 ms step). Kept a layer: the block's input and
    ``o``, each ONE lane-dense (16, 1024, 1600) bf16, and 400 x 1024 float32
    of log-sum-exp; no lane-padded copy of either (the kernel's own (400,
    1024, 64) pads to 128 lanes: +2.5 GB; a (400, 1024, 1) log-sum-exp x
    128: +10 GB, which is why the kernels pass it as (400, 1, 1024)), which
    ``test_xl_z3_step_at_micro_batch_16_fits_a_chip`` would see too."""
    text = xl_z3_step.as_text()
    _assert_the_forward_kernel_runs_once_a_layer(text)
    assert _stacked(text, "bf16", 48, 16, 1024, 1600)
    assert _stacked(text, "f32", 48, 400, 1024)
    assert not _stacked(text, "bf16", 48, 400, 1024, 64)
    assert not _stacked(text, "bf16", 48, 16, 1024, 25, 64)
    assert not _stacked(text, "f32", 48, 400, 1024, 1)


# what XLA's own rematerialization pass puts into the gas-4 step to make it
# fit: 14 ops before PR 32 (8.59 ms a sequence on the chip, PR 26); 15 with
# o and an XLA-squeezed log-sum-exp among the residuals, which LOST 0.5% on
# the chip (the pass re-ran one more matmul a layer); 10 with the
# log-sum-exp and delta lane-dense from and to the kernels (+2.3%)
GAS4_REMAT_OPS = 10


def test_gas4_step_at_micro_batch_6_compiles_and_runs_the_forward_once(gas4_step):
    """``gpt2-760m.train.z1.gas4`` stands at the compiler's limit (micro-batch
    8 is refused: "Used 15.81G of 15.75G"), so the compiler's verdict on the
    real step is the test: it compiled. What it rematerialized to get there
    is device time on no idle share: a change to what a layer keeps moves
    that count, and is seen here before it is on the chip."""
    text = gas4_step.as_text()
    _assert_the_forward_kernel_runs_once_a_layer(text)
    assert _stacked(text, "bf16", 24, 6, 1024, 1536)
    assert _stacked(text, "f32", 24, 96, 1024)
    assert not _stacked(text, "f32", 24, 96, 1024, 1)
    remat = len(re.findall(r"%[\w.\-]*\.remat[\w.\-]* = ", text))
    assert remat <= GAS4_REMAT_OPS, (
        f"{remat} ops rematerialized by XLA (PR 32: {GAS4_REMAT_OPS})")


# ----- trinity-mini.train.z1.s8k: the routed, windowed step (PR 37)
@pytest.fixture(scope="module")
def trinity_record(v5e):
    """``trinity-mini.train.z1.s8k``: ZeRO-1 on one chip, the cell's own
    micro-batch of 8,192-token sequences."""
    return _compiled_train_step(v5e, "trinity-mini.json",
                                "train.z1.s8k.json", 1)[1]


def test_trinity_step_runs_the_windowed_kernels_and_keeps_no_square(
        trinity_record):
    """The cell's real step for one v5e chip: the window layers' two
    kernels and the full layer's two (the forward ONCE a layer under remat
    'attn', ONE backward call a layer), XLA:TPU's own grouped-matmul kernels for the routed experts'
    ``ragged_dot`` (forward, re-run and both transposes), no (T, T) array of
    scores anywhere, and it fits the chip beside the 9.88 GB of state."""
    from deepspeed_tpu.telemetry.scopes import classify

    text = trinity_record.compiled().as_text()
    own = _own_instructions(text)
    calls = collections.Counter(
        re.sub(r"[.\d]+$", "", n) for n, _, line in own
        if "tpu_custom_call" in line)
    # one dense window layer + a period of [win, attn, win, win]: the
    # period's layers are unrolled in the scan's body
    assert calls["flash_fwd_win"] == calls["flash_bwd_dkv_win"] == 4
    assert calls["flash_fwd"] == calls["flash_bwd_dkv"] == 1
    assert calls["flash_bwd_dq_win"] == calls["flash_bwd_dq"] == 0
    # a routed layer's three products over a chunk of the share's rows:
    # forward, re-run, and in the backward once more before both transposes
    # (the loop over chunks keeps its inputs, PR 39)
    assert calls["ragged-dot-none"] == 4 * (3 + 3 + 9)
    assert not re.search(r"f32\[(?:\d+,)*8192,8192\]", text)
    table = trinity_record.instruction_scopes()
    scopes_of = lambda prefix: {classify(table[n], n)[0] for n, _, line in own
                                if n.startswith(prefix)}
    assert scopes_of("ragged-dot-none") == {"moe/experts"}
    assert scopes_of("flash_") == {"attn/core"}
    assert "optimizer/router_bias" in {classify(v, n)[0]
                                       for n, v in table.items()}
    memory = trinity_record.memory()
    assert memory["argument"] == pytest.approx(9.88e9, rel=0.01)
    assert memory["total"] < 15.75 * 2 ** 30


# what the step's ``memory()`` summed to with row buffers of ALL 131,072
# (token, expert) pairs a routed layer (PR 37; temporaries 5,460,337,664 B)
TRINITY_FULL_SIZE_STEP_BYTES = 15_452_601_344


def test_trinity_step_moves_the_shares_rows_and_not_every_pair(trinity_record):
    """16,384 tokens x top-8 = 131,072 pairs a routed layer, of which this
    chip's 16 experts of 128 hold 16,384 if the routing is even: the row
    buffers around the grouped products have ``share_capacity`` = 32,768
    rows, and no array of a width has more. The products stand in loops of
    as many trips as the call's held pairs fill chunks (one, as a rule), so
    what a call holds beyond the buffer is computed too. And the step needs
    less of the chip than it did."""
    from deepspeed_tpu.moe.dropless import share_capacity

    capacity = share_capacity(2 * 8192 * 8, 16, 128)
    assert capacity == 32768
    text = trinity_record.compiled().as_text()
    rows = {int(n) for n in re.findall(
        r"(?:bf16|f32)\[(\d+),(?:1024|2048)\]", text)}
    assert max(rows) == capacity, sorted(rows)[-4:]
    comps, called, reach = _computations(text)
    kernels = lambda lines: sum(bool(re.search(
        r"%ragged-dot-none[\w.]* = [^\n]*tpu_custom_call", l)) for l in lines)
    in_loops = sorted(
        n for n in (sum(kernels(comps[c]) for c in reach(body))
                    for body in {c for lines in comps.values()
                                 for c in called("body", lines)}) if n)
    # forward and its re-run: 3 products a layer; backward: 3 again + 6
    assert in_loops == [3] * 8 + [9] * 4
    assert kernels(text.splitlines()) == sum(in_loops)
    assert trinity_record.memory()["total"] <= TRINITY_FULL_SIZE_STEP_BYTES


# ----- kimi-linear-48b-a3b.train.z1.s16k: KDA's six kernels in a real step
# what ``memory()`` of the step summed to with ``chunk_operands`` in XLA and
# differentiated by autodiff (PR 41: temporaries 5,861.7 MB), and with the
# q | k | v preparation in XLA (PR 42: temporaries 5,278.5 MB)
KIMI_XLA_OPERANDS_STEP_BYTES = 14_646_600_000
KIMI_XLA_PREPARATION_STEP_BYTES = 13_974_200_000


def test_kimi_step_makes_a_chunks_operands_in_kernels(v5e):
    """The cell's real step of 1 x 16,384 tokens for one v5e chip, three
    KDA layers a period behind a dense one: a KDA layer runs
    ``kda_operands_fwd`` twice (the forward; the segment's re-run under
    ``mix``'s checkpoint, which hands the state pass's backward its
    operands) and ``kda_operands_bwd`` once, around ONE ``kda_chunk_fwd``
    (its outputs and group states are kept) and one ``kda_chunk_bwd``, all
    under ``kda/core``; before them ``kda_prep_fwd`` twice and
    ``kda_prep_bwd`` once under ``kda/qkv`` (the block's re-run under remat
    'attn' needs the projection's last rows for the tail, not q, k, v: a
    kernel call that nothing reads is dropped, where XLA's fusions had run
    a third time); nothing of the ``jnp`` ``chunk_operands`` is left, no
    float32 array the size of the projection's output and no norm factor
    broadcast a channel; and it needs less of the chip than it did."""
    from deepspeed_tpu.telemetry.scopes import classify

    record = _compiled_train_step(v5e, "kimi-linear-48b-a3b.json",
                                  "train.z1.s16k.json", 1)[1]
    text = record.compiled().as_text()
    own = _own_instructions(text)
    calls = collections.Counter(
        re.sub(r"[.\d]+$", "", n) for n, _, line in own
        if "tpu_custom_call" in line)
    assert [calls[k] for k in ("kda_operands_fwd", "kda_chunk_fwd",
                               "kda_chunk_bwd", "kda_operands_bwd",
                               "kda_prep_fwd", "kda_prep_bwd")] \
        == [8, 4, 4, 4, 8, 4], calls
    assert not re.search(r"\[(?:1,)?32,32,(?:64|128),(?:64|128)\]", text)
    assert not re.search(r"f32\[(?:\d+,)*20(?:48|51),12288\]", text)
    table = record.instruction_scopes()
    scopes_of = lambda prefix: {classify(table[n], n)[0] for n, _, line in own
                                if n.startswith(prefix)}
    assert scopes_of("kda_prep") == {"kda/qkv"}
    assert scopes_of("kda_operands") == scopes_of("kda_chunk") == {"kda/core"}
    assert not [n for n, line in re.findall(
        r"%([\w.\-]+) = (f32\[2048,32,128\]\S* broadcast\()", text)
        if classify(table.get(n, ""), n)[0] == "kda/qkv"]
    assert record.memory()["total"] < KIMI_XLA_PREPARATION_STEP_BYTES \
        < KIMI_XLA_OPERANDS_STEP_BYTES


# ------------- the real steps under the program's own names (scopes, PR 35)
@pytest.fixture(params=["xl_z3", "gas4"])
def step_record(request):
    return request.getfixturevalue(f"{request.param}_record")[1]


def _own_instructions(text):
    """[(name, opcode, the line)] of the non-fused computations."""
    comps, _, _ = _computations(text)
    rows = []
    for comp, lines in comps.items():
        if "fused_computation" in comp:
            continue
        for line in lines:
            m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = .*?\s([\w\-]+)\(", line)
            if m:
                rows.append((m.group(1), m.group(2), line))
    return rows


def test_the_flash_kernels_keep_their_instruction_names(step_record):
    """The ``name=`` of a ``pallas_call`` stays the LAST scope of its
    ``op_name``, so the Mosaic calls are still the instructions the
    benchmark's ``train.flash_*`` readers and the ledger's ``breakdown``
    know, and each now says which scope and pass it runs in."""
    from deepspeed_tpu.telemetry.scopes import classify

    table = step_record.instruction_scopes()
    kernels = {n: classify(table[n], n) for n, _, line in _own_instructions(
        step_record.compiled().as_text()) if "tpu_custom_call" in line}
    by_kernel = {re.sub(r"[.\d]+$", "", n): v for n, v in kernels.items()}
    assert by_kernel == {"flash_fwd": ("attn/core", "fwd"),
                         "flash_bwd_dkv": ("attn/core", "bwd")}, kernels


def test_the_steps_device_work_resolves_to_a_scope(step_record):
    """Of the instructions that take device time in a profile — fusions,
    copies, custom calls, collectives of the non-fused computations — at
    least 90% carry one of the program's scopes. Not counted, because they
    are the compiler's own and move no data or wait for a copy it scheduled
    itself: ``AllocateBuffer`` / ``ConcatBitcast`` custom calls and the
    ``copy-start`` / ``copy-done`` / ``slice-start`` / ``slice-done`` pairs
    of its memory-space assignment."""
    from deepspeed_tpu.telemetry.scopes import classify

    table = step_record.instruction_scopes()
    kinds = re.compile(r"^(fusion|copy|custom-call|convolution|all-gather|"
                       r"all-reduce|reduce-scatter|all-to-all|"
                       r"collective-permute)(-start|-done)?$")
    rows = [(n, kind) for n, kind, line in _own_instructions(
        step_record.compiled().as_text())
        if kinds.match(kind) and not kind.startswith("copy-")
        and not re.search(r'custom_call_target="(AllocateBuffer|'
                          r'ConcatBitcast)"', line)]
    scoped = [bool(classify(table[n], n)[0]) for n, _ in rows]
    assert len(rows) > 150 and sum(scoped) / len(rows) >= 0.9, (
        sum(scoped), len(rows),
        collections.Counter(k for (n, k), s in zip(rows, scoped) if not s))


def test_the_steps_names_cover_every_phase(step_record):
    from deepspeed_tpu.telemetry.scopes import classify

    found = collections.defaultdict(set)
    for name, op_name in step_record.instruction_scopes().items():
        scope, pass_ = classify(op_name, name)
        found[scope.split("/")[0]].add(pass_)
    assert {"embed", "attn", "mlp", "head", "layers", "optimizer"} \
        <= set(found)
    for scope in ("attn", "mlp"):
        assert {"fwd", "bwd", "recompute"} <= found[scope], found
    assert ("accumulate" in found) == \
        (step_record.label == "engine/train_batch[gas=4]")


def test_the_doors_memory_is_the_footprint(step_record):
    """``memory()``'s sum is what ``_footprint`` counts plus the program's
    own code: the figure ``train.step_hbm_frac`` divides by 16 GiB."""
    compiled = step_record.compiled()
    arguments, total = _footprint(compiled)
    m = step_record.memory()
    assert m["argument"] == arguments
    assert m["total"] - m["generated_code"] == total
    assert 0 < m["generated_code"] < 64 << 20


def _two_layer_step_text(devices, monkeypatch_scope):
    from deepspeed_tpu.telemetry import scopes

    mesh = _mesh(devices)
    model = GPT2Model(dataclasses.replace(PRESETS["gpt2-760m"], remat="attn",
                                          n_layer=2))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _abstract(s.shape, jnp.bfloat16, mesh),
                          shapes)
    ids = _abstract((8, 1024), jnp.int32, mesh)
    with pytest.MonkeyPatch.context() as mp:
        if monkeypatch_scope:
            mp.setattr(scopes, "_named_scope",
                       lambda name: contextlib.nullcontext())
        with mesh:
            return jax.jit(jax.value_and_grad(
                lambda p, b: model.loss(p, {"input_ids": b}))).lower(
                    params, ids).compile().as_text()


def test_scopes_are_metadata_and_nothing_else(v5e):
    """A two-layer step at ``z1``'s widths compiled for one v5e chip with the
    helper real and with the helper a no-op: the SAME instructions, names,
    shapes, layouts and schedule, once ``metadata={...}`` is cut (and the
    module's tables of file names and stack frames, which are metadata
    too)."""
    def instructions(text):
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        return [l for l in text.splitlines()
                if re.match(r"^(\s+(ROOT )?%|ENTRY |%|\}|HloModule)", l)]

    scoped = _two_layer_step_text(v5e, False)
    plain = _two_layer_step_text(v5e, True)
    assert "attn/core/flash_fwd" in scoped and "attn/core" not in plain
    assert instructions(scoped) == instructions(plain)
    assert len(instructions(scoped)) > 2000


# ------------------------------------ OLMoE-1B-7B at its published widths
GIB = 1 << 30


@pytest.fixture(scope="module")
def olmoe(v5e):
    """(mesh, model, abstract bf16 params, abstract cache, the two serving
    programs) of the whole published model on ONE chip: 16 layers, 64
    experts, top-8, a 4,096-slot cache."""
    from deepspeed_tpu.inference.engine import build_serving_programs
    from deepspeed_tpu.models.llama import PRESETS as LLAMA, LlamaModel

    mesh = _mesh(v5e)
    model = LlamaModel(dataclasses.replace(LLAMA["olmoe-1b-7b"],
                                           param_dtype=jnp.bfloat16))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _abstract(s.shape, s.dtype, mesh), shapes)
    cache = jax.tree.map(lambda s: _abstract(s.shape, s.dtype, mesh),
                         jax.eval_shape(lambda: model.init_cache(1, 4096)))
    return (mesh, model, params, cache) + build_serving_programs(
        model, 4096, 16, False, 1.0, 0, 1.0, None)


def _footprint(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes, m.argument_size_in_bytes
            + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)


def _expert_matrix_moves(text):
    """Results of copy / gather / dynamic-slice that end in an expert's
    (2048, 1024) or (1024, 2048): a per-layer slice of a stacked expert leaf
    (805 MB), a gather of the chosen experts (33 MB), a relayout."""
    return [m for m in re.findall(
        r"= (bf16\[[\d,]*\])\S* (?:copy|gather|dynamic-slice)\(", text)
        if re.search(r"(2048,1024|1024,2048)\]$", m)]


def test_olmoe_weights_are_drawn_in_bf16_with_no_float32_leaf(olmoe):
    """What ``benchmark/systems.py::ServeSystem`` runs: the whole draw in one
    jitted call. 13.84 GB out and next to nothing beside it — a float32
    copy of one expert leaf would be 8.6 GB."""
    mesh, model, params, *_ = olmoe
    with mesh:
        compiled = jax.jit(lambda key: jax.tree.map(
            lambda x: x.astype(jnp.bfloat16), model.init_params(key))).lower(
                _abstract((2,), jnp.uint32, mesh)).compile()
    m = compiled.memory_analysis()
    # (small leaves are padded to tiles: a few hundred bytes over)
    assert 0 <= m.output_size_in_bytes - 2 * model.config.num_params() < 1 << 20
    assert m.temp_size_in_bytes < 0.5 * GIB, m.temp_size_in_bytes


def test_olmoe_decode_chunk_for_v5e_reads_the_chosen_experts_in_place(olmoe):
    mesh, model, params, cache, _, chunk = olmoe
    with mesh:
        compiled = jax.jit(chunk).lower(
            params, *_chunk_carry(cache, mesh)).compile()
    text = compiled.as_text()
    for kernel in ("moe_gmm_swiglu_thin", "moe_gmm_thin", "decode_attn"):
        assert re.search(rf"%{kernel}[\w.]* = [^\n]*tpu_custom_call", text), \
            kernel
    assert not _expert_matrix_moves(text)
    copies = re.findall(r"= (\w+\[[\d,]*\])\S* copy\(", text)
    assert copies.count("bf16[16,1,4096,2048]") <= 2, copies   # undonated k, v
    # the weights counted once (13.84 GB) + the cache in and out: it fits
    args, total = _footprint(compiled)
    assert args < 2 * model.config.num_params() + 0.6 * GIB
    assert total < 15.75 * GIB, total / GIB


def test_olmoe_programs_hand_each_other_a_token(olmoe):
    mesh, model, params, cache, prefill, chunk = olmoe
    with mesh:
        _token_in_token_out(prefill, chunk, params, cache, mesh, 2048, 50304)


def test_olmoe_prefill_for_v5e_runs_ragged_groups_on_the_stacked_leaves(olmoe):
    mesh, model, params, _, prefill, _ = olmoe
    with mesh:
        compiled = jax.jit(prefill).lower(
            params, _abstract((1, 2048), jnp.int32, mesh),
            _abstract((2,), jnp.uint32, mesh)).compile()
    text = compiled.as_text()
    for kernel in ("moe_gmm_swiglu_full", "moe_gmm_full", "flash_fwd"):
        assert re.search(rf"%{kernel}[\w.]* = [^\n]*tpu_custom_call", text), \
            kernel
    assert not _expert_matrix_moves(text)
    # 2048 x 8 rows in at most 128 + 64 tiles of 128
    assert "bf16[24576,2048]" in text
    args, total = _footprint(compiled)
    assert args < 2 * model.config.num_params() + 0.1 * GIB
    assert total < 15.75 * GIB, total / GIB


# --------- openPangu-Ultra-MoE-718B: one chip's share, at the published widths
def test_latent_decode_kernel_compiles_for_v5e_uninterpreted(v5e):
    """128 query rows of 576 columns against 640-lane latent rows, the value
    the first 512 lanes of the same block; the cell's 32,768-slot cache."""
    mesh = _mesh(v5e)
    q = _abstract((1, 128, 576), jnp.bfloat16, mesh)
    cache = _abstract((5, 1, 32768, 640), jnp.bfloat16, mesh)
    scalar = _abstract((), jnp.int32, mesh)
    text = jax.jit(functools.partial(
        da.latent_decode_attention, v_width=512, scale=192 ** -0.5)).lower(
            q, cache, scalar, scalar).compile().as_text()
    assert len(re.findall(
        r"%[\w.]*latent_decode_attn[\w.]* = [^\n]*tpu_custom_call", text)) == 1


def test_flash_fwd_at_192_and_128_columns_compiles_for_v5e(v5e):
    """q.k at 192 columns (1.5 lane tiles), v and the output at 128: the
    forward takes v's width, nothing is padded."""
    mesh = _mesh(v5e)
    qk = _abstract((1, 4096, 8, 192), jnp.bfloat16, mesh)
    v = _abstract((1, 4096, 8, 128), jnp.bfloat16, mesh)
    text = jax.jit(fa.flash_attention).lower(qk, qk, v).compile().as_text()
    call, = re.findall(r"%[\w.]*flash_fwd[\w.]* = [^\n]*tpu_custom_call", text)
    assert "(bf16[8,4096,128]" in call                  # the output: v's width
    assert "bf16[8,4096,192]" in text and "bf16[8,4096,256]" not in text


@pytest.fixture(scope="module")
def pangu(v5e):
    """(mesh, model, abstract bf16 params, abstract cache, the two serving
    programs) of the benchmark's configuration on ONE chip: 1 dense + 4
    routed layers, 16 of 256 experts, a 32,768-slot latent cache."""
    from benchmark import manifest as mf
    from benchmark.families import pangu_ultra_moe as family
    from deepspeed_tpu.inference.engine import build_serving_programs

    mesh = _mesh(v5e)
    model = family.build_model(mf.load_json(
        mf.BENCH_DIR / "configs" / "openpangu-ultra-moe-718b.json"), "serve")
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _abstract(s.shape, s.dtype, mesh), shapes)
    cache = jax.tree.map(lambda s: _abstract(s.shape, s.dtype, mesh),
                         jax.eval_shape(lambda: model.init_cache(1, 32768)))
    return (mesh, model, params, cache) + build_serving_programs(
        model, 32768, 16, False, 1.0, 0, 1.0, None)


def _moves(text, shapes, ops="copy|gather|dynamic-slice|transpose"):
    """Results of ``ops`` with one of ``shapes``."""
    return [m for m in re.findall(
        rf"= (bf16\[[\d,]*\])\S* (?:{ops})\(", text) if m in shapes]


def test_pangu_decode_chunk_for_v5e_reads_the_latent_rows_once(pangu):
    """One ``latent_decode_attn`` in the routed stack's loop and one in the
    dense stack's, the thin grouped matmuls under a conditional (a step with
    no held pair runs none), and nothing cache-shaped or ``W_kvb``-shaped is
    copied, sliced out or relaid: the stacked cache and the two up-projection
    leaves are read in place."""
    mesh, model, params, cache, _, chunk = pangu
    with mesh:
        compiled = jax.jit(chunk).lower(
            params, *_chunk_carry(cache, mesh)).compile()
    text = compiled.as_text()
    for kernel in ("moe_gmm_swiglu_thin", "moe_gmm_thin", "latent_decode_attn"):
        assert re.search(rf"%{kernel}[\w.]* = [^\n]*tpu_custom_call", text), \
            kernel
    assert len(re.findall(
        r"%latent_decode_attn[\w.]* = [^\n]*tpu_custom_call", text)) == 2
    assert "decode_attn" not in re.sub("latent_decode_attn", "", text)
    assert " conditional(" in text
    # the up-projections (4 or 1 layers x 128 heads x (128, 512) / (512, 128))
    # feed XLA's own matmuls, which slice a layer out inside their fusion:
    # no copy, no relayout; an expert's matrices feed a custom call, where a
    # slice would be a copy too
    up = {f"bf16[{lead}128,{a},{b}]" for lead in ("", "1,", "4,")
          for a, b in ((128, 512), (512, 128))}
    assert not _moves(text, up, "copy|transpose")
    assert not _moves(text, {"bf16[7680,2048]", "bf16[2048,7680]",
                             "bf16[16,7680,2048]", "bf16[16,2048,7680]"})
    copies = re.findall(r"= (\w+\[[\d,]*\])\S* copy\(", text)
    assert copies.count("bf16[5,1,32768,640]") <= 1, copies    # undonated
    args, total = _footprint(compiled)
    assert args < 2 * model.config.num_params() + 0.3 * GIB
    assert total < 15.75 * GIB, total / GIB


def test_pangu_programs_hand_each_other_a_token(pangu):
    mesh, model, params, cache, prefill, chunk = pangu
    with mesh:
        _token_in_token_out(prefill, chunk, params, cache, mesh, 2048, 19200)


def test_pangu_prefill_for_v5e_keeps_no_square_of_scores(pangu):
    """Flash at 192 / 128 columns and the full-tile grouped matmuls over the
    stacked share; no (T, T) array anywhere in the program; it fits."""
    mesh, model, params, _, prefill, _ = pangu
    with mesh:
        compiled = jax.jit(prefill).lower(
            params, _abstract((1, 3072), jnp.int32, mesh),
            _abstract((2,), jnp.uint32, mesh)).compile()
    text = compiled.as_text()
    for kernel in ("moe_gmm_swiglu_full", "moe_gmm_full", "flash_fwd"):
        assert re.search(rf"%{kernel}[\w.]* = [^\n]*tpu_custom_call", text), \
            kernel
    assert "bf16[128,3072,192]" in text                 # flash's q, by head
    assert not re.search(r"\[[\d,]*3072,3072\]", text)
    assert not _moves(text, {"bf16[7680,2048]", "bf16[2048,7680]",
                             "bf16[16,7680,2048]", "bf16[16,2048,7680]"})
    args, total = _footprint(compiled)
    assert args < 2 * model.config.num_params() + 0.1 * GIB
    assert total < 15.75 * GIB, total / GIB


# ------ SDAR-30B-A3B: one chip's share of 8, all 48 layers, block diffusion
@pytest.fixture(scope="module")
def sdar(v5e):
    """(mesh, model, abstract bf16 params, abstract cache, the two serving
    programs) of the benchmark's configuration on ONE chip: 48 layers, 16
    of 128 experts, blocks of 4 denoised in 2 passes, an 8,192-slot cache."""
    from benchmark import manifest as mf
    from benchmark.families import sdar_moe as family
    from deepspeed_tpu.inference.engine import build_serving_programs

    mesh = _mesh(v5e)
    model = family.build_model(mf.load_json(
        mf.BENCH_DIR / "configs" / "sdar-30b-a3b-chat.json"), "serve")
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _abstract(s.shape, s.dtype, mesh), shapes)
    cache = jax.tree.map(lambda s: _abstract(s.shape, s.dtype, mesh),
                         jax.eval_shape(lambda: model.init_cache(1, 8192)))
    return (mesh, model, params, cache) + build_serving_programs(
        model, 8192, 16, False, 1.0, 0, 1.0, None)


def test_sdar_decode_chunk_for_v5e_holds_the_layers_twice(sdar):
    """A chunk of 4 block steps holds the model TWICE: a step's first pass,
    which carries the block before it (8 positions x 8 heads a group x 4 KV
    heads = 256 query rows, group-major), and the loop of its other passes
    over its own 4 (128 rows); a pair of thin grouped matmuls under the
    share's conditional in each; no pass only commits, so no head under a
    conditional; it fits."""
    mesh, model, params, cache, _, chunk = sdar
    with mesh:
        compiled = jax.jit(chunk).lower(
            params, *_chunk_carry(cache, mesh)).compile()
    text = compiled.as_text()
    for kernel in ("moe_gmm_swiglu_thin", "moe_gmm_thin", "decode_attn"):
        assert len(re.findall(rf"%{kernel}[\w.]* = [^\n]*tpu_custom_call",
                              text)) == 2, kernel
    carrying, own = re.findall(
        r"%decode_attn[\w.]* = [^\n]*tpu_custom_call", text)
    assert "bf16[1,64,512]" in carrying     # 8 x 8 query rows a KV head
    assert "bf16[1,32,512]" in own          # 4 x 8
    assert text.count(" conditional(") == 2
    assert "head/unmask" in text
    args, total = _footprint(compiled)
    assert args < 2 * model.config.num_params() + 1.0 * GIB
    assert total < 12 * GIB, total / GIB


def test_sdar_prefill_ends_in_the_first_block_under_the_block_causal_mask(
        sdar, v5e):
    """The prefill program hands over the carry and the first block's NEW
    tokens (what the prompt left over of a block opens it), the chunk takes
    the carry; the flash forward lowers under the block-causal mask at the
    cell's longest prompt and head shape, one Mosaic call, no (T, T) array.
    (The whole 4,096-token prefill compiles in ~18 s: benchmark's to run.)"""
    mesh, model, params, cache, prefill, chunk = sdar
    with mesh:
        ids = _abstract((1, 1030), jnp.int32, mesh)
        key = _abstract((2,), jnp.uint32, mesh)
        out = jax.eval_shape(prefill, params, ids, key)
        assert out[0].shape == (1,) and out[4].shape == (1, 2)  # 4 - 1030 % 4
        assert jax.eval_shape(chunk, params, *out[:4])[4].shape == (1, 16)
        qkv = _abstract((1, 4096, 32, 128), jnp.bfloat16, mesh)
        text = jax.jit(functools.partial(fa.flash_attention, block=4)).lower(
            qkv, qkv, qkv).compile().as_text()
    assert len(re.findall(r"%[\w.]*flash_fwd[\w.]* = [^\n]*tpu_custom_call",
                          text)) == 1
    assert not re.search(r"\[[\d,]*4096,4096\]", text)


# ------ Solar-Open2-250B: one chip's share of 8, at the published widths
def test_kda_chunk_kernel_compiles_for_v5e_uninterpreted(v5e):
    """The state pass of the chunked form at 64 heads x 128, a segment of
    2,048 positions and one that is no whole number of chunks: one Mosaic
    call, the state float32 out."""
    from deepspeed_tpu.ops.pallas import kda

    mesh = _mesh(v5e)
    for T in (2048, 64 * 3 + 17):
        qk = _abstract((1, T, 64, 128), jnp.bfloat16, mesh)
        g = _abstract((1, T, 64, 128), jnp.float32, mesh)
        beta = _abstract((1, T, 64), jnp.float32, mesh)
        state = _abstract((1, 64, 128, 128), jnp.float32, mesh)
        text = jax.jit(functools.partial(kda.chunked_kda, kernel=True)).lower(
            qk, qk, qk, g, beta, state).compile().as_text()
        call, = re.findall(
            r"%[\w.]*kda_chunk_fwd[\w.]* = [^\n]*tpu_custom_call", text)
        assert "f32[64,128,128]" in call
        # its operands are a kernel's too: q, k, v, g read as the caller's
        # (1, T', heads x 128) rows, a head's lanes by block index
        made, = re.findall(
            r"%[\w.]*kda_operands_fwd[\w.]* = [^\n]*tpu_custom_call", text)
        rows = -(-T // 512) * 512 if T > 512 else -(-T // 64) * 64
        assert f"bf16[1,{rows},8192]" in text and f"f32[1,{rows},8192]" in text
        assert made.count(f"bf16[64,{rows},128]") == 4       # u, w, qg, kend


def test_kda_state_pass_compiles_for_v5e_forward_and_backward(v5e):
    """The state pass with its own backward at Kimi-Linear's 32 heads x 128
    and a segment of 2,048 positions: ``kda_chunk_fwd`` with the groups'
    end states as a third output and ``kda_chunk_bwd``, one Mosaic call
    each, uninterpreted, between ``kda_operands_fwd`` and
    ``kda_operands_bwd``."""
    from deepspeed_tpu.ops.pallas import kda

    mesh = _mesh(v5e)
    qk = _abstract((1, 2048, 32, 128), jnp.bfloat16, mesh)
    g = _abstract((1, 2048, 32, 128), jnp.float32, mesh)
    beta = _abstract((1, 2048, 32), jnp.float32, mesh)
    state = _abstract((1, 32, 128, 128), jnp.float32, mesh)

    def loss(*args):
        o, s = kda.chunked_kda(*args, kernel=True, vjp=True)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(s)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        qk, qk, qk, g, beta, state).compile().as_text()
    fwd, = re.findall(r"%[\w.]*kda_chunk_fwd[\w.]* = [^\n]*custom-call", text)
    bwd, = re.findall(r"%[\w.]*kda_chunk_bwd[\w.]* = [^\n]*custom-call", text)
    assert "f32[32,4,128,128]" in fwd           # a state a group of 8 chunks
    assert bwd.count("bf16[32,2048,128]") >= 4 and "f32[32,32,128]" in bwd
    # the operands' own pair around them: one forward (nothing re-runs it
    # here), and a backward that writes q, k, v and g's cotangents as the
    # caller's rows and beta's a chunk a row
    made, = re.findall(
        r"%[\w.]*kda_operands_fwd[\w.]* = [^\n]*custom-call", text)
    back, = re.findall(
        r"%[\w.]*kda_operands_bwd[\w.]* = ([^\n]*?) custom-call\(", text)
    assert back.count("bf16[1,2048,4096]") == 3 and "f32[1,2048,4096]" in back
    assert "f32[32,32,64]" in back


@pytest.mark.parametrize("heads", [32, 64])
def test_kda_prep_kernels_compile_for_v5e_uninterpreted(v5e, heads):
    """The q | k | v preparation at Kimi-Linear's 32 and Solar's 64 heads x
    128 over a segment of 2,048 positions, forward and backward: one Mosaic
    call each, uninterpreted; the forward writes q, k, v as the (1, T, H
    dk) rows ``kda_operands_fwd`` reads, the backward the projection's
    cotangent in ONE piece beside the tail's and the taps' float32
    partials, a 128-lane column at a time."""
    from deepspeed_tpu.ops.pallas import kda

    mesh = _mesh(v5e)
    ch = 3 * heads * 128
    p = _abstract((1, 2048, ch), jnp.bfloat16, mesh)
    tail = _abstract((1, 3, ch), jnp.bfloat16, mesh)
    taps = _abstract((4, ch), jnp.float32, mesh)

    def loss(*args):
        made = kda.prepare(*args, heads, 1e-6)
        return sum(jnp.sum(t.astype(jnp.float32)) for t in made), made

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True)).lower(
        p, tail, taps).compile().as_text()
    fwd, = re.findall(r"%[\w.]*kda_prep_fwd[\w.]* = ([^\n]*?) custom-call\(",
                      text)
    bwd, = re.findall(r"%[\w.]*kda_prep_bwd[\w.]* = ([^\n]*?) custom-call\(",
                      text)
    assert fwd.count(f"bf16[1,2048,{ch // 3}]") == 3
    assert f"bf16[1,2048,{ch}]" in bwd and f"f32[1,{ch // 128},8,128]" in bwd \
        and f"f32[1,{ch // 128},32,128]" in bwd
    assert not re.search(rf"f32\[(?:\d+,)*20(?:48|51),{ch}\]", text)


def test_kda_kernels_compile_for_v5e_at_heads_narrower_than_a_lane_tile(v5e):
    """4 heads of 16 (the tests' tiny hybrid): a head's lanes are no whole
    tile, so the operand kernels cannot read ``(B, T, H dk)`` rows by block
    index; the heads go ahead of the positions through XLA and all four
    kernels still compile, forward and backward."""
    from deepspeed_tpu.ops.pallas import kda

    mesh = _mesh(v5e)
    qk = _abstract((2, 1024, 4, 16), jnp.bfloat16, mesh)
    g = _abstract((2, 1024, 4, 16), jnp.float32, mesh)
    beta = _abstract((2, 1024, 4), jnp.float32, mesh)
    state = _abstract((2, 4, 16, 16), jnp.float32, mesh)

    def loss(*args):
        o, s = kda.chunked_kda(*args, kernel=True, vjp=True)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(s)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        qk, qk, qk, g, beta, state).compile().as_text()
    for kernel in ("kda_operands_fwd", "kda_chunk_fwd", "kda_chunk_bwd",
                   "kda_operands_bwd"):
        assert len(re.findall(
            rf"%[\w.]*{kernel}[\w.]* = [^\n]*custom-call", text)) == 1, kernel


@pytest.fixture(scope="module")
def solar(v5e):
    """(mesh, model, abstract bf16 params, abstract cache, the two serving
    programs) of the benchmark's configuration on ONE chip: 1 softmax + 3
    KDA layers, 40 of 320 experts, a 36,864-slot K/V cache of one layer."""
    from benchmark import manifest as mf
    from benchmark.families import solar_open2 as family
    from deepspeed_tpu.inference.engine import build_serving_programs

    mesh = _mesh(v5e)
    model = family.build_model(mf.load_json(
        mf.BENCH_DIR / "configs" / "solar-open2-250b.json"), "serve")
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _abstract(s.shape, s.dtype, mesh), shapes)
    cache = jax.tree.map(lambda s: _abstract(s.shape, s.dtype, mesh),
                         jax.eval_shape(lambda: model.init_cache(1, 36864)))
    return (mesh, model, params, cache) + build_serving_programs(
        model, 36864, 16, False, 1.0, 0, 1.0, None)


def test_solar_decode_chunk_for_v5e_reads_each_layers_weights_in_place(solar):
    """One ``decode_attn`` (the ONE softmax layer), the thin grouped matmuls
    twice each — the softmax layer's, and ONE body for the three KDA layers
    (a loop over the run) — under a conditional, no KDA kernel (one position
    is the recurrence itself), and nothing weight-shaped, state-shaped or
    expert-shaped copied or relaid: no branch runs both mixers, and the
    (4, 40, ...) expert leaves go whole to the kernels. The loop over the
    run indexes the three mixer stacks by layer (a ``dynamic-slice`` a leaf
    in the text; measured on the chip as no copy: PERF.md, PR 33)."""
    mesh, model, params, cache, _, chunk = solar
    with mesh:
        compiled = jax.jit(chunk).lower(
            params, *_chunk_carry(cache, mesh)).compile()
    text = compiled.as_text()
    assert len(re.findall(
        r"%decode_attn[\w.]* = [^\n]*tpu_custom_call", text)) == 1
    for kernel in ("moe_gmm_swiglu_thin", "moe_gmm_thin"):
        assert len(re.findall(
            rf"%{kernel}[\w.]* = [^\n]*tpu_custom_call", text)) == 2, kernel
    assert "kda_chunk_fwd" not in text and "flash_fwd" not in text
    assert " conditional(" in text
    weights = {"bf16[4096,24576]", "bf16[3,4096,24576]", "bf16[8192,4096]",
               "bf16[3,8192,4096]", "bf16[4096,8192]", "bf16[1,4096,8192]",
               "bf16[4096,1280]", "bf16[1280,4096]", "bf16[40,4096,1280]",
               "bf16[40,1280,4096]", "bf16[4,40,4096,1280]"}
    assert not _moves(text, weights, "copy|transpose|gather")
    assert not _moves(text, {"bf16[40,4096,1280]", "bf16[40,1280,4096]",
                             "bf16[4,40,4096,1280]", "bf16[4,40,1280,4096]"})
    copies = re.findall(r"= (\w+\[[\d,]*\])\S* copy\(", text)
    assert copies.count("bf16[1,1,36864,1024]") <= 2, copies    # undonated
    args, total = _footprint(compiled)
    assert args < 2 * model.config.num_params() + 0.3 * GIB
    assert total < 15.75 * GIB, total / GIB


def test_solar_programs_hand_each_other_a_token(solar):
    mesh, model, params, cache, prefill, chunk = solar
    with mesh:
        _token_in_token_out(prefill, chunk, params, cache, mesh, 4096, 24576)


def test_solar_prefill_for_v5e_at_the_longest_prompt(solar):
    """32,768 tokens: ``kda_operands_fwd`` and ``kda_chunk_fwd`` in the segment
    loop, ``flash_fwd`` at
    head_dim 128, the full-tile grouped matmuls over the stacked share; no
    (T, T) array and no per-chunk pairwise (64, 64, 128) array anywhere;
    it fits beside the 6.6 GB of weights."""
    mesh, model, params, _, prefill, _ = solar
    with mesh:
        compiled = jax.jit(prefill).lower(
            params, _abstract((1, 32768), jnp.int32, mesh),
            _abstract((2,), jnp.uint32, mesh)).compile()
    text = compiled.as_text()
    for kernel in ("kda_chunk_fwd", "kda_operands_fwd", "moe_gmm_swiglu_full",
                   "moe_gmm_full", "flash_fwd"):
        assert re.search(rf"%{kernel}[\w.]* = [^\n]*tpu_custom_call", text), \
            kernel
    # a chunk's operands come from a kernel wherever the state pass runs,
    # and nothing of the ``jnp`` ``chunk_operands`` is left: no (heads,
    # chunks, 64 | 128, 64 | 128) array of level-split scores or decays
    calls = lambda kernel: len(re.findall(
        rf"%{kernel}[\w.]* = [^\n]*tpu_custom_call", text))
    assert calls("kda_operands_fwd") == calls("kda_chunk_fwd") > 0
    assert not re.search(r"\[(?:1,)?64,32,(?:64|128),(?:64|128)\]", text)
    assert not re.search(r"\[[\d,]*32768,32768\]", text)
    assert not re.search(r"\[[\d,]*64,64,128\]", text)
    assert not _moves(text, {"bf16[40,4096,1280]", "bf16[40,1280,4096]",
                             "bf16[4,40,4096,1280]", "bf16[4096,1280]"})
    args, total = _footprint(compiled)
    assert args < 2 * model.config.num_params() + 0.1 * GIB
    assert total < 15.75 * GIB, total / GIB


def test_routed_experts_on_a_mesh_of_several_chips_take_the_xla_form(v5e):
    """GSPMD cannot partition a Mosaic call: over tensor=4 the experts run
    as ``ragged_dot`` (no kernel, and it compiles); experts over chips are
    open (ROADMAP R1)."""
    from deepspeed_tpu.models.llama import PRESETS as LLAMA, LlamaModel

    mesh = _mesh(v5e, tensor=4)
    model = LlamaModel(dataclasses.replace(
        LLAMA["olmoe-1b-7b"], n_layer=1, n_experts=8, vocab_size=1024))
    params = jax.tree.map(
        lambda s: _abstract(s.shape, jnp.bfloat16, mesh),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    with mesh:
        text = jax.jit(lambda p, ids: model.prefill(
            p, ids, model.init_cache(1, 512))).lower(
                params, _abstract((1, 256), jnp.int32, mesh)).compile().as_text()
    assert "moe_gmm" not in text


# ------------------------------------------------------- kernel dispatch
@pytest.fixture
def interpreted(monkeypatch):
    """Run the Pallas kernels in interpret mode and make model code believe
    its mesh is a TPU mesh — the kernel path on the CPU test mesh."""
    from jax.experimental import pallas as pl

    call = functools.partial(pl.pallas_call, interpret=True)
    monkeypatch.setattr(fa.pl, "pallas_call", call)
    monkeypatch.setattr(da.pl, "pallas_call", call)
    real = common._kernel_target
    monkeypatch.setattr(common, "_kernel_target", lambda: (real()[0], True))


def _qkv(b, t, h, d, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), jnp.float32) for k in keys)


def test_flash_under_a_mesh_runs_in_shard_map_and_matches_einsum(interpreted):
    """Batch over data=2, heads over tensor=2: the shard_map-wrapped kernel
    equals the einsum path, values and gradients."""
    mesh = _mesh(jax.devices(), data=2, tensor=2)
    q, k, v = _qkv(4, 128, 4, 32)
    sh = NamedSharding(mesh, P("data", None, "tensor", None))
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))

    def loss(use_flash):
        return lambda q, k, v: jnp.sum(jnp.sin(
            common.local_causal_attention(q, k, v, use_flash=use_flash)))

    with mesh:
        ker = jax.jit(jax.value_and_grad(loss(True), argnums=(0, 1, 2)))
        assert "shard_map" in str(jax.make_jaxpr(loss(True))(q, k, v))
        (lk, gk), (le, ge) = ker(q, k, v), jax.jit(jax.value_and_grad(
            loss(False), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(lk), float(le), rtol=1e-4)
    for a, b in zip(gk, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_padded_causal_flash_matches_reference(interpreted):
    """T > one block and not tileable: padded inside the wrapper, pad rows
    sliced off — forward and backward equal the reference at length T."""
    q, k, v = _qkv(1, 601, 2, 32, seed=1)
    # 601 has no usable divisor; 520 = 8 x 65 would tile in 8-row blocks
    assert fa._padded_len(601, 512) == fa._padded_len(520, 512) == 640
    assert fa._padded_len(1024, 512) == 1024 and fa._padded_len(37, 512) == 37
    loss = lambda f: lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))
    out = fa.flash_attention(q, k, v)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(fa.mha_reference(q, k, v)),
                               atol=2e-2, rtol=2e-2)
    gk = jax.grad(loss(fa.flash_attention), argnums=(0, 1, 2))(q, k, v)
    ge = jax.grad(loss(fa.mha_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-2, rtol=5e-2)


def test_untileable_noncausal_takes_einsum_by_choice_and_kernel_refuses():
    assert fa.flash_supports(601, 601, causal=True)
    assert not fa.flash_supports(601, 601, causal=False)
    assert fa.flash_supports(512, 512, causal=False)
    q, k, v = _qkv(1, 601, 2, 32)
    with pytest.raises(ValueError, match="do not tile"):
        fa.flash_attention(q, k, v, causal=False)


def test_kernel_failure_on_tpu_backend_propagates(monkeypatch):
    """On a TPU backend a flash kernel that fails is an error — it must not
    be caught and answered by the einsum path."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def boom(*a, **kw):
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(fa, "flash_attention", boom)
    q, k, v = _qkv(1, 64, 2, 32)
    with pytest.raises(RuntimeError, match="mosaic says no"):
        common.local_causal_attention(q, k, v)
    # a static window is the kernel's too since PR 37 ...
    with pytest.raises(RuntimeError, match="mosaic says no"):
        common.local_causal_attention(q, k, v, window=8)
    # ... and what the kernel does not carry (a bias, a TRACED window) is
    # chosen up front, not on failure
    for kw in ({"alibi": jnp.ones((2,))}, {"window": jnp.int32(8)}):
        out = common.local_causal_attention(q, k, v, **kw)
        assert out.shape == q.shape


def test_decode_kernel_never_interprets_itself():
    """Off-TPU the decode kernel fails to lower instead of quietly running
    in the interpreter; a test that wants the interpreter asks for it."""
    q = jnp.zeros((1, 4, 64))
    cache = jnp.zeros((1, 1, 128, 256))
    with pytest.raises(Exception, match="[Ii]nterpret|TPU|tpu"):
        da.decode_attention(q, cache, cache, jnp.int32(0), jnp.int32(3),
                            n_kv=4)


# ----------------------------------------------------------- engine rules
def test_second_train_batch_does_not_compile_again():
    """The TrainState scalars are committed to the mesh at init: typed like
    the step's own outputs, so step 2 hits the program step 1 compiled."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import synthetic_lm_batch

    cfg = GPT2Config(vocab_size=256, n_positions=32, n_embd=32, n_layer=1,
                     n_head=2)
    engine, *_ = deepspeed_tpu.initialize(model=GPT2Model(cfg), config={
        "train_batch_size": 8, "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1}, "steps_per_print": 0})
    for seed in range(3):
        engine.train_batch(synthetic_lm_batch(8, 16, cfg.vocab_size, seed))
    (prog,) = engine._compiled_train_batch.values()
    assert prog._cache_size() == 1


def test_inference_tp_leaves_an_indivisible_vocab_whole():
    """GPT-2's published vocab (50257) does not divide tp=4; the vocab dim
    stays unsharded instead of failing the placement, and the tokens are
    those of tp=1."""
    import deepspeed_tpu
    from deepspeed_tpu.comm import comm

    cfg = GPT2Config(vocab_size=251, n_positions=32, n_embd=32, n_layer=1,
                     n_head=4)
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = np.arange(8, dtype=np.int32)[None] % cfg.vocab_size
    outs = []
    for tp in (1, 4):
        comm.cdb = None
        eng = deepspeed_tpu.init_inference(
            model, dtype="float32", max_out_tokens=32, params=params,
            tensor_parallel={"tp_size": tp})
        assert eng.mesh.shape["tensor"] == tp
        outs.append(np.asarray(eng.generate(prompt, max_new_tokens=8)))
    assert eng.params["wte"].sharding.spec == P(None, None)
    assert eng.params["blocks"]["qkv_w"].sharding.spec == P(None, None, "tensor")
    np.testing.assert_array_equal(outs[0], outs[1])


def test_explicit_tp_size_is_not_overridden_by_the_training_mesh():
    """init_inference after a data=8 training run, asked for tp_size=2,
    builds the tensor=2 mesh — it does not warn and serve on data=8."""
    import deepspeed_tpu

    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=16, n_layer=1,
                     n_head=2, use_flash_attention=False)
    train, *_ = deepspeed_tpu.initialize(model=GPT2Model(cfg), config={
        "train_batch_size": 8, "steps_per_print": 0,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
    assert train.mesh.shape["data"] == 8
    eng = deepspeed_tpu.init_inference(GPT2Model(cfg), dtype="float32",
                                       tensor_parallel={"tp_size": 2})
    assert eng.mesh.shape["tensor"] == 2 and eng.mesh.shape["data"] == 4
    same = deepspeed_tpu.init_inference(GPT2Model(cfg), dtype="float32")
    assert same.mesh is eng.mesh        # no tp asked: the installed mesh


# --------------------------------------------------------- process rules
def _run(code, env=None, timeout=120):
    e = {k: v for k, v in os.environ.items()
         if k != "JAX_COMPILATION_CACHE_DIR" and not k.startswith("BENCH_")}
    e.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **(env or {}))
    return subprocess.run([sys.executable, "-c", code], env=e, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_without_a_chip_fails_at_the_device_check():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no accelerator" in r.stderr and "'cpu'" in r.stderr
    assert r.stdout.strip() == ""                # no result, no phase ran


_CACHE_PROBE = """
import jax
from deepspeed_tpu.sharding import INHERIT, sharded_jit
sharded_jit(lambda x: x, label="t/probe", in_shardings=INHERIT,
            out_shardings=INHERIT, donate_argnums=())
print(jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_dir_is_placed_from_outside_or_fixed(tmp_path):
    outside = _run(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert outside.stdout.split() == [str(tmp_path)], outside.stderr[-2000:]
    first, second = _run(_CACHE_PROBE), _run(_CACHE_PROBE)
    assert first.stdout.split() == second.stdout.split() \
        == [os.path.join(REPO, ".jax_cache")], first.stderr[-2000:]
    sites = subprocess.run(
        ["grep", "-rln", "--include=*.py", "compilation_cache",
         "deepspeed_tpu"], cwd=REPO,
        capture_output=True, text=True).stdout.split()
    assert sites == ["deepspeed_tpu/sharding/jit.py"]


def test_launcher_parent_counts_no_chips():
    """build_resource_pool with no hostfile must not initialise a jax
    backend: the parent would hold the chip its child needs."""
    r = _run("""
import argparse
from deepspeed_tpu.launcher import runner
pool = runner.build_resource_pool(argparse.Namespace(
    hostfile="/nonexistent", include="", exclude="", num_nodes=-1, num_gpus=-1))
from jax._src import xla_bridge
assert not xla_bridge._backends, list(xla_bridge._backends)
print(dict(pool))
""")
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "{'localhost': []}"


# --- MiMo-V2-Flash: one chip's share of 32, window layers that keep a ring
@pytest.fixture(scope="module")
def mimo(v5e):
    """(mesh, model, abstract bf16 params, abstract cache, the two serving
    programs) of the benchmark's configuration on ONE chip: 13 layers, 8 of
    256 experts, a 28,672-slot cache over the three full layers and a ring
    of 128 slots a window layer."""
    from benchmark import manifest as mf
    from benchmark.families import mimo_v2_flash as family
    from deepspeed_tpu.inference.engine import build_serving_programs

    mesh = _mesh(v5e)
    model = family.build_model(mf.load_json(
        mf.BENCH_DIR / "configs" / "mimo-v2-flash.json"), "serve")
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _abstract(s.shape, s.dtype, mesh), shapes)
    cache = jax.tree.map(lambda s: _abstract(s.shape, s.dtype, mesh),
                         jax.eval_shape(lambda: model.init_cache(1, 28672)))
    return (mesh, model, params, cache) + build_serving_programs(
        model, 28672, 16, False, 1.0, 0, 1.0, None)


def test_mimo_decode_chunk_for_v5e_reads_a_ring_and_a_context(mimo):
    """A chunk of 16 steps holds FOUR ``decode_attn`` calls (the dense full
    layer, the period's run of four window layers as one loop, its full
    layer, its last window layer): the full layers' at 4 KV heads over rows
    of 768 / 512 lanes (16 query heads a group: group-major, 16 rows a
    unit), the rings' at 8 over 1,536 / 1,024 with the sinks as a fifth
    operand; a pair of thin grouped matmuls under the share's conditional in
    each routed body; it fits with room for the 24k prefill's successor."""
    mesh, model, params, cache, _, chunk = mimo
    with mesh:
        compiled = jax.jit(chunk).lower(
            params, *_chunk_carry(cache, mesh)).compile()
    text = compiled.as_text()
    calls = re.findall(r"%decode_attn[\w.]* = [^\n]*tpu_custom_call[^\n]*",
                       text)
    assert len(calls) == 4, calls
    full = [c for c in calls if "bf16[3,1,28672,768]" in c]
    ring = [c for c in calls if "bf16[10,1,128,1536]" in c]
    assert len(full) == 2 and len(ring) == 2
    assert all("bf16[3,1,28672,512]" in c and "bf16[1,16,512]" in c
               for c in full)
    assert all("bf16[10,1,128,1024]" in c and "f32[128,128]" in c
               for c in ring)
    for kernel in ("moe_gmm_swiglu_thin", "moe_gmm_thin"):
        assert len(re.findall(rf"%{kernel}[\w.]* = [^\n]*tpu_custom_call",
                              text)) == 3, kernel
    args, total = _footprint(compiled)
    assert args < 2 * model.config.num_params() + 0.5 * GIB
    assert total < 9 * GIB, total / GIB


def test_mimo_prefill_kernels_lower_at_the_cells_widths(mimo):
    """The two flash forwards of a 24,576-token prefill at 64 heads: the
    causal one at 192 / 128 columns, and the windowed one with a sink at a
    window of 128, planned in sub-blocks of 256 (one Mosaic call each, no
    (T, T) array); the whole 24k prefill compiles in ~34 s here and is the
    benchmark's to run."""
    mesh, model, _, _, _, _ = mimo
    with mesh:
        q = _abstract((1, 24576, 64, 192), jnp.bfloat16, mesh)
        v = _abstract((1, 24576, 64, 128), jnp.bfloat16, mesh)
        sink = _abstract((64,), jnp.float32, mesh)
        win = jax.jit(lambda q, k, v, s: fa.flash_attention(
            q, k, v, window=128, sink=s)).lower(q, q, v, sink).compile()
        full = jax.jit(fa.flash_attention).lower(q, q, v).compile()
    for compiled, name in ((win, "flash_fwd_win"), (full, "flash_fwd")):
        text = compiled.as_text()
        assert len(re.findall(
            rf"%[\w.]*{name}[\w.]* = [^\n]*tpu_custom_call", text)) == 1
        assert not re.search(r"\[[\d,]*24576,24576\]", text)
    assert fa.flash_forward_plan(24576, 192, 128, jnp.bfloat16,
                                 window=128).sub_block == 256
