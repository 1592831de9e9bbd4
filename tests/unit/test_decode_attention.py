"""Decode-attention kernel numerics (single-token KV-cache path).

Runs the Pallas TPU kernel in interpreter mode on the CPU mesh (bit-accurate
to the kernel's math) against the XLA einsum path the models take off-TPU;
lowering for the chip is tests/unit/test_chip_bringup.py's, real-TPU
numerics the benchmark's ``correct`` — see .claude/skills/verify/SKILL.md.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.ops.pallas.decode_attention as da
from deepspeed_tpu.models import common


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    if jax.default_backend() != "tpu":
        from jax.experimental import pallas as pl

        monkeypatch.setattr(da.pl, "pallas_call",
                            functools.partial(pl.pallas_call, interpret=True))
    yield


@pytest.fixture
def as_tpu_program(monkeypatch):
    """Make model code believe its program is for a TPU: the path
    ``cached_decode_attention`` chooses there, run by the interpreter."""
    real = common._kernel_target
    monkeypatch.setattr(common, "_kernel_target", lambda: (real()[0], True))


def _rand(B, S, H, KV, Dh, seed=0, layers=1, dtype=jnp.float32):
    """q (B, H, Dh) and a stacked cache (layers, B, S, W) built the way
    prefill builds it."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, H, Dh), jnp.float32).astype(dtype)
    pack = lambda key: jnp.stack([
        common.kv_cache_rows(t, S) for t in
        jax.random.normal(key, (layers, B, S, KV, Dh), jnp.float32).astype(dtype)])
    return q, pack(kk), pack(kv)


def _einsum(q, k, v, layer, pos, kv):
    """What the models run where the program is not for a TPU."""
    return common.cached_decode_attention(q, k, v, jnp.int32(layer),
                                          jnp.int32(pos), kv)


@pytest.mark.parametrize("kv", [4, 2, 1])          # MHA, GQA, MQA
@pytest.mark.parametrize("pos", [0, 63, 64, 200, 255])
def test_matches_reference(kv, pos):
    B, S, H, Dh = 2, 256, 4, 64
    q, k, v = _rand(B, S, H, kv, Dh, layers=2)
    out = da.decode_attention(q, k, v, jnp.int32(1), jnp.int32(pos), n_kv=kv,
                              block_k=64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_einsum(q, k, v, 1, pos, kv)),
                               atol=2e-5, rtol=2e-5)


# the serving cells' shapes: gpt2-xl's 25 heads x 64 in 1664-wide rows, a
# 1024-slot cache, the default 128-slot block; and GQA 32 on 8. ``pos`` at 0,
# one under / at / one over block edges, and the last slot
@pytest.mark.parametrize("heads,kv", [(25, 25), (32, 8)])
@pytest.mark.parametrize("pos", [0, 127, 128, 129, 255, 256, 257, 511, 512,
                                 513, 895, 896, 897, 1023])
def test_cell_shapes_every_block_edge(heads, kv, pos):
    q, k, v = _rand(1, 1024, heads, kv, 64, seed=pos)
    out = da.decode_attention(q, k, v, jnp.int32(0), jnp.int32(pos), n_kv=kv)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_einsum(q, k, v, 0, pos, kv)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", [(1, 128, 2, 1, 64, 32),      # B S H KV Dh bk
                                   (1, 1024, 25, 25, 64, 128)])
def test_garbage_beyond_pos_ignored(shape):
    """Entries past ``pos`` must not affect the output (the cache holds
    zeros / stale tokens there)."""
    B, S, H, KV, Dh, bk = shape
    q, k, v = _rand(B, S, H, KV, Dh, seed=1)
    pos = 40
    k_dirty = k.at[:, :, pos + 1:].set(1e9)
    v_dirty = v.at[:, :, pos + 1:].set(-1e9)
    out = da.decode_attention(q, k_dirty, v_dirty, jnp.int32(0),
                              jnp.int32(pos), n_kv=KV, block_k=bk)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_einsum(q, k, v, 0, pos, KV)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("pos", [10, 63, 64, 95])
def test_cache_length_that_does_not_tile_reads_a_partial_last_block(pos):
    """generate() sizes the cache T + new: the last block is then partial,
    and what it reads past the array weighs nothing."""
    B, S, H, KV, Dh = 1, 96, 4, 2, 32
    q, k, v = _rand(B, S, H, KV, Dh, seed=2)
    out = da.decode_attention(q, k, v, jnp.int32(0), jnp.int32(pos), n_kv=KV,
                              block_k=64)                     # 96 % 64 != 0
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_einsum(q, k, v, 0, pos, KV)),
                               atol=2e-5, rtol=2e-5)


def test_path_chosen_for_a_tpu_program_equals_the_einsum(as_tpu_program):
    """No option picks the kernel: where the program is for a TPU a whole
    LlamaModel decode_step (GQA cache, RoPE positions) takes it, and gives
    what the einsum path gives; a bias or a window keeps the einsum."""
    from deepspeed_tpu.models.llama import PRESETS, LlamaModel

    cfg = dataclasses.replace(PRESETS["llama-tiny"], dtype=jnp.float32,
                              use_flash_attention=False, remat=False)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(2, 8)), jnp.int32)
    logits, cache = model.prefill(params, ids, model.init_cache(2, 24))
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    assert "decode_attn" in str(jax.make_jaxpr(model.decode_step)(
        params, tok, cache))
    out_k, _ = model.decode_step(params, tok, cache)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(common, "_kernel_target", lambda: (None, False))
        assert "decode_attn" not in str(jax.make_jaxpr(model.decode_step)(
            params, tok, cache))
        out_e, _ = model.decode_step(params, tok, cache)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_e),
                               rtol=2e-4, atol=2e-4)
    q, k, v = _rand(1, 32, 4, 4, 16)
    for kw in ({"alibi": common.alibi_slopes(4)}, {"window": jnp.int32(8)}):
        assert "decode_attn" not in str(jax.make_jaxpr(
            lambda q, k, v: common.cached_decode_attention(
                q, k, v, jnp.int32(0), jnp.int32(5), 4, **kw))(q, k, v))


@pytest.mark.parametrize("heads,kv,S,pos", [(4, 2, 128, 100),
                                            (25, 25, 1024, 700)])
def test_bf16_inputs(heads, kv, S, pos):
    q, k, v = _rand(2, S, heads, kv, 64, seed=3, dtype=jnp.bfloat16)
    out = da.decode_attention(q, k, v, jnp.int32(0), jnp.int32(pos), n_kv=kv)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(_einsum(q, k, v, 0, pos, kv), np.float32),
        atol=3e-2, rtol=3e-2)


# -------------------------------------------------- through a whole model
@pytest.fixture(scope="module")
def tiny_gpt2():
    from deepspeed_tpu.models.gpt2 import PRESETS, GPT2Model

    cfg = dataclasses.replace(PRESETS["gpt2-tiny"], dtype=jnp.float32,
                              n_positions=320, use_flash_attention=False,
                              remat=False)
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(4))
    ids = np.random.default_rng(6).integers(0, cfg.vocab_size, size=250,
                                            dtype=np.int32)
    return model, params, ids


def _teacher_forced(model, params, ids, prompt, cache_len):
    """prefill on ids[:prompt], then one decode_step per further token of
    ``ids``: the logits at every position from prompt - 1 on."""
    logits, cache = jax.jit(model.prefill)(
        params, ids[None, :prompt], model.init_cache(1, cache_len))
    step = jax.jit(model.decode_step)
    rows = [logits[0]]
    for t in ids[prompt:]:
        logits, cache = step(params, jnp.asarray([t], jnp.int32), cache)
        rows.append(logits[0])
    return np.asarray(jnp.stack(rows)), cache


def test_prefill_and_32_decode_steps_kernel_einsum_and_reference(
        tiny_gpt2, as_tpu_program):
    """prefill + 32 decode_steps through the folded cache, the slots
    crossing a block edge (250 -> 281 of 320, blocks of 128): the path a
    TPU program takes equals the einsum path, and both equal the
    benchmark's plain reference (no cache at all)."""
    from benchmark.families import gpt2 as reference

    model, params, ids = tiny_gpt2
    cfg = model.config
    more = np.random.default_rng(7).integers(0, cfg.vocab_size, size=32,
                                             dtype=np.int32)
    full = np.concatenate([ids, more])
    with jax.default_matmul_precision("highest"):
        got_k, cache = _teacher_forced(model, params, full, 250, 320)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(common, "_kernel_target", lambda: (None, False))
            got_e, _ = _teacher_forced(model, params, full, 250, 320)
        want = np.asarray(jax.jit(functools.partial(
            reference.reference_logits,
            cfg={"model": {"n_head": cfg.n_head}}))(params, full))[249:]
    assert cache["k"].shape == (cfg.n_layer, 1, 320,
                                common.kv_cache_width(cfg.n_head, cfg.head_dim))
    assert int(cache["pos"]) == 282
    np.testing.assert_allclose(got_k, got_e, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(got_k, want, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(got_e, want, atol=2e-3, rtol=2e-3)


def test_front_end_on_the_kernel_path_emits_generates_tokens(as_tpu_program):
    """A request served through ``ServingFrontEnd`` (16-token ticks over a
    preallocated cache) emits exactly ``generate()``'s tokens (one fused
    program over a tight cache) with the kernel in both."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import PRESETS, GPT2Model
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.serving import ServingFrontEnd

    model = GPT2Model(dataclasses.replace(
        PRESETS["gpt2-tiny"], use_flash_attention=False, remat=False))
    engine = deepspeed_tpu.init_inference(model, dtype="fp32",
                                          max_out_tokens=320)
    prompt = (np.arange(250)[None, :] % 256).astype(np.int32)
    front = ServingFrontEnd(engine, DeepSpeedConfig({"serving": {}}).serving)
    try:
        req = front.submit(prompt, max_new_tokens=20)
        req.result(timeout=600)
        assert req.status == "completed", req.reason
    finally:
        front.close()
    ref = np.asarray(engine.generate(prompt, max_new_tokens=20))
    assert req.tokens == ref[0, 250:].tolist()


# ------------------------------------- a last valid slot PER POSITION (early)
def _block(B, S, Lb, H, KV, Dh, seed):
    """q (B, Lb, H, Dh) and a two-layer cache, as ``_rand``."""
    q, k, v = _rand(B, S, Lb * H, KV, Dh, seed=seed, layers=2)
    return q.reshape(B, Lb, H, Dh), k, v


# (positions, early, heads, KV heads): SDAR's carried pass (8 x 32 on 4:
# group-major, 4 units of 64 rows) and a row-major plan, each with the later
# limit ``pos`` (the early one is ``Lb - early`` slots before it) so that both
# lie in one 128-slot block, in neighbouring blocks, the early one a block's
# last slot, and in a fresh sequence (slots 0 .. Lb - 1 alone); a group-major
# plan whose unit is padded (6 x 4 heads a group = 24 rows in 32) and a
# second row-major one across a block's edge
@pytest.mark.parametrize("Lb,n,heads,kv,pos", [
    *((8, 4, 32, 4, pos) for pos in ("fresh", 100, 129, 131, 255)),
    *((4, 2, 16, 16, pos) for pos in ("fresh", 100, 129, 255)),
    (6, 2, 8, 2, 130), (2, 1, 4, 4, 128)])
def test_a_limit_per_position_matches_the_einsum(Lb, n, heads, kv, pos):
    B, S, Dh = 2, 256, 32
    pos = Lb - 1 if pos == "fresh" else pos
    last = pos - (Lb - n)
    q, k, v = _block(B, S, Lb, heads, kv, Dh, seed=pos + Lb)
    got = da.decode_attention(q, k, v, jnp.int32(1), jnp.int32(pos), n_kv=kv,
                              early=(n, jnp.int32(last)))
    want = common.cached_decode_attention(
        q, k, v, jnp.int32(1), jnp.int32(pos), kv, early=(n, jnp.int32(last)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    # the twin's early rows are a call of their own through ``last``, the
    # others the call without a limit of their own: two limits, no more
    for rows, upto in ((slice(0, n), last), (slice(n, Lb), pos)):
        alone = common.cached_decode_attention(
            q[:, rows], k, v, jnp.int32(1), jnp.int32(upto), kv)
        np.testing.assert_allclose(np.asarray(want[:, rows]),
                                   np.asarray(alone), atol=2e-6, rtol=2e-6)
    assert np.abs(np.asarray(want[:, :n]) - np.asarray(
        common.cached_decode_attention(q, k, v, jnp.int32(1), jnp.int32(pos),
                                       kv)[:, :n])).max() > 1e-3


def test_a_limit_per_position_far_apart_and_refused(as_tpu_program):
    """Limits more than a block apart (every block between them is an
    edge), through ``cached_decode_attention`` as a TPU program calls it;
    what the argument refuses."""
    q, k, v = _block(1, 512, 8, 32, 4, 32, seed=9)
    call = lambda **kw: common.cached_decode_attention(
        q, k, v, jnp.int32(0), jnp.int32(400), 4, **kw)
    assert "decode_attn" in str(jax.make_jaxpr(
        lambda: call(early=(4, jnp.int32(90))))())
    got = call(early=(4, jnp.int32(90)))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(common, "_kernel_target", lambda: (None, False))
        want = call(early=(4, jnp.int32(90)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    for bad in (0, 8):
        with pytest.raises(ValueError, match="early"):
            da.decode_attention(q, k, v, jnp.int32(0), jnp.int32(400), n_kv=4,
                                early=(bad, jnp.int32(90)))
    with pytest.raises(ValueError, match="early"):
        common.cached_decode_attention(q[:, 0], k, v, jnp.int32(0),
                                       jnp.int32(400), 4,
                                       early=(1, jnp.int32(90)))


# sha256 of the jaxpr (kernel body and all) each call traced to at the commit
# BEFORE ``early`` existed (bbeb614; jax 0.9.0): gpt2-xl's and a GQA model's
# one-position step, and SDAR's 4-row block step. To refresh after a change
# that is MEANT to move them: print ``_digest`` from a checkout of the parent
PARENTS = {((1, 25, 64), 25): "409c2c0e3e7806ca",
           ((1, 32, 64), 8): "b2668f1db1ae068c",
           ((1, 4, 32, 128), 4): "a5b9fa50b57a6c0a"}


def _digest(q_shape, kv, **kw):
    import hashlib

    q = jnp.zeros(q_shape, jnp.float32)
    k = jnp.zeros((2, q_shape[0], 1024, common.kv_cache_width(
        kv, q_shape[-1])), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda q, k, v, layer, pos: da.decode_attention(
            q, k, v, layer, pos, n_kv=kv, **kw))(
                q, k, k, jnp.int32(0), jnp.int32(5)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the digests are of jax 0.9.0's jaxprs")
@pytest.mark.parametrize("q_shape,kv", list(PARENTS))
def test_a_call_without_the_limit_is_the_call_it_was(q_shape, kv, monkeypatch):
    """The standing cells' decode steps trace to the program they traced to
    before a call could carry a limit per position; a call that carries one
    does not."""
    monkeypatch.undo()          # traced as a program for the chip traces it
    assert _digest(q_shape, kv) == PARENTS[q_shape, kv]
    assert _digest(q_shape, kv, early=None) == PARENTS[q_shape, kv]
    if len(q_shape) == 4:
        assert _digest(q_shape, kv, early=(2, jnp.int32(3))) != \
            PARENTS[q_shape, kv]
