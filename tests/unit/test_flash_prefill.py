"""``flash_prefill``: the forward-only attention of a prefill on q, k and v
as the projections made them (ops/pallas/flash_attention.py) against the
definition it replaces — q rotated and K / V repeated in passes around
``local_causal_attention``'s einsum — in interpret mode on the CPU."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from deepspeed_tpu.models import common
from deepspeed_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    if jax.default_backend() != "tpu":
        monkeypatch.setattr(fa.pl, "pallas_call",
                            functools.partial(pl.pallas_call, interpret=True))


# heads / KV heads, q.k / v width, rotary columns, window, sink, block: the
# full and the window layers of MiMo-V2-Flash, SDAR's block mask, OLMoE
GEOMETRIES = {
    "mimo_full": (64, 4, 192, 128, 64, None, False, None),
    "mimo_window": (64, 8, 192, 128, 64, 128, True, None),
    "sdar_block": (32, 4, 128, 128, 128, None, False, 4),
    "olmoe": (16, 16, 128, 128, 128, None, False, None),
}


def _operands(geometry, t, dtype, seed=0):
    h, kv, d, dv, r, window, sink, block = GEOMETRIES[geometry]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (1, t, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (1, t, kv, d), jnp.float32)
    v = jax.random.normal(keys[2], (1, t, kv, dv), jnp.float32)
    cos, sin = common._rope_cos_sin(jnp.arange(t), r, 5e6)
    masks = {"window": window, "block": block,
             "sink": jax.random.normal(keys[3], (h,)) if sink else None}
    return tuple(x.astype(dtype) for x in (q, k, v)), (cos, sin), masks


def _as_the_parent(attention, q, k, v, cos, sin, masks):
    """The parent's passes: q and k rotated (and rounded), K and V repeated
    to q's heads, then ``attention`` on full-head operands."""
    rep = q.shape[2] // k.shape[2]
    q, k = (common.apply_rope_leading(x, cos, sin) for x in (q, k))
    return attention(q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                     **{n: m for n, m in masks.items() if m is not None})


def _prefill(q, k, v, cos, sin, masks):
    return fa.flash_prefill(q, common.apply_rope_leading(k, cos, sin), v,
                            cos, sin, **masks)


@pytest.mark.parametrize("t", [896, 1024])
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_float32_operands_match_the_einsum_on_the_parents_operands(
        geometry, t):
    (q, k, v), (cos, sin), masks = _operands(geometry, t, jnp.float32)
    want = _as_the_parent(common.local_causal_attention, q, k, v, cos, sin,
                          masks)
    got = _prefill(q, k, v, cos, sin, masks)
    assert got.shape == want.shape == q.shape[:3] + v.shape[-1:]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)


@pytest.mark.parametrize("t", [896, 1024])
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_bf16_is_no_further_from_float32_than_the_parents_path(geometry, t):
    """q is rounded once (after rotation and scale, both float32) where the
    parent rounds it after the rotation and again after a scale that is
    itself rounded to bf16."""
    (q, k, v), (cos, sin), masks = _operands(geometry, t, jnp.bfloat16)
    exact = np.asarray(_as_the_parent(
        common.local_causal_attention,
        *(x.astype(jnp.float32) for x in (q, k, v)), cos, sin, masks))
    error = lambda o: np.abs(np.asarray(o.astype(jnp.float32)) - exact)
    parent = error(_as_the_parent(fa.flash_attention, q, k, v, cos, sin,
                                  masks))
    mine = error(_prefill(q, k, v, cos, sin, masks))
    assert mine.mean() <= parent.mean() * 1.01, (mine.mean(), parent.mean())
    assert mine.max() <= parent.max() * 1.25, (mine.max(), parent.max())


def test_without_tables_q_is_left_as_it_was():
    """``cos is None`` (the full layers of a pattern whose window layers
    alone are rotated; a model without positions): q is scaled and nothing
    else, K and V still read at their own heads."""
    (q, k, v), _, _ = _operands("mimo_full", 1024, jnp.float32)
    want = common.local_causal_attention(
        q, jnp.repeat(k, 16, axis=2), jnp.repeat(v, 16, axis=2))
    np.testing.assert_allclose(np.asarray(fa.flash_prefill(q, k, v)),
                               np.asarray(want), atol=1e-5, rtol=0)


def test_the_non_causal_forward_reads_grouped_keys_by_index_map_too():
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (8, 256, 32), jnp.float32)
    k, v = (jax.random.normal(key, (2, 384, 32), jnp.float32)
            for key in keys[1:])
    o, _ = fa._flash_forward(q, k, v, 1.0 / math.sqrt(32), False, 128, 128)
    want = fa.mha_reference(*(x[None].transpose(0, 2, 1, 3) for x in (
        q, jnp.repeat(k, 4, axis=0), jnp.repeat(v, 4, axis=0))), causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(
        want[0].transpose(1, 0, 2)), atol=2e-5, rtol=0)


# ------------------------------------------------------- the model's call
def _rehearsal(family, tiny, kind="serve"):
    from benchmark import families, manifest as mf

    cfg = mf.load_json(mf.ROOT / "tests" / "benchmark" / "data" / f"{tiny}.json")
    return families.get(family).build_model(cfg, kind)


@pytest.fixture
def forms(tmp_path):
    """``kernels/prefill_attn_calls`` by form, read from a telemetry
    session's registry (without a session every counter is the no-op)."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig

    telemetry.configure(TelemetryConfig(enabled=True, trace=False,
                                        output_dir=str(tmp_path)))
    yield lambda: {rec["labels"]["form"]: rec["value"]
                   for rec in telemetry.get_registry().snapshot()
                   if rec["name"] == "kernels/prefill_attn_calls"}
    telemetry.deconfigure()


@pytest.fixture
def tpu_target(monkeypatch):
    monkeypatch.setattr(common, "_kernel_target", lambda: (None, True))


def _mimo(t):
    model = _rehearsal("mimo_v2_flash", "mimo-tiny")
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(1, 2 * t))
    return model, params, jax.ShapeDtypeStruct((1, t), jnp.int32), cache


def test_every_layer_of_mimos_rehearsal_prefill_counts_fused_on_a_tpu_target(
        tpu_target, forms):
    """The counter the mechanism brings, read where a traced run's registry
    dump reads it: one count a softmax layer of the program, by its form."""
    import re

    model, params, ids, cache = _mimo(256)
    program = str(jax.make_jaxpr(model.prefill)(params, ids, cache))
    assert forms() == {"fused": float(model.config.n_layer)}
    # both kinds' kernels, and no K or V written at q's heads before them
    assert set(re.findall(r"name=(flash\w+)", program)) \
        == {"flash_fwd", "flash_fwd_win"}
    # (``jnp.repeat`` of a KV head is a broadcast to (B, T, KV, rep, D))
    assert not re.findall(r"\w+\[1,256,\d+,\d+,\d+\] = broadcast_in_dim",
                          program)


def test_off_a_tpu_every_layer_counts_plain_and_the_program_is_the_einsums(
        forms):
    model, params, ids, cache = _mimo(64)
    program = str(jax.make_jaxpr(model.prefill)(params, ids, cache))
    assert forms() == {"plain": float(model.config.n_layer)}
    assert "pallas_call" not in program


def test_the_fused_prefill_is_the_plain_one(monkeypatch):
    """MiMo's rehearsal model in float32: logits and every cache row of the
    fused form (kernels in interpret mode) against the plain form's einsum
    (partial rotary by kind, a window of 8 with a sink, 2 / 4 KV heads
    under 8 heads, q.k 24 / v 16)."""
    import dataclasses

    model = _rehearsal("mimo_v2_flash", "mimo-tiny")
    model.config = dataclasses.replace(
        model.config, dtype=jnp.float32, param_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(4))
    for stack in ("attn_blocks", "win_blocks", "dense_blocks"):
        params[stack]["q_w"] = params[stack]["q_w"] * 8.0
    ids = jnp.asarray(np.random.default_rng(6).integers(
        0, 250, size=(1, 200), dtype=np.int32))
    cache = model.init_cache(1, 256)
    want_logits, want = model.prefill(params, ids, cache)
    monkeypatch.setattr(common, "_kernel_target", lambda: (None, True))
    logits, got = model.prefill(params, ids, cache)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               atol=2e-4, rtol=0)
    for name in ("k", "v", "win_k", "win_v"):
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), atol=2e-4, rtol=0)


# --------------------------------------- what the mechanism does not touch
# sha256[:16] of ``str(make_jaxpr(...))`` (addresses struck out) at the
# PARENT commit (5e8c79e; jax 0.9.0), the rehearsal sizes of
# tests/benchmark/data: the training trunk keeps ``_repeat_kv`` and the
# differentiable entry (on the CPU and on a TPU target, where the kernels
# are in the program), a decode step, SDAR's carrying block step and
# ``models/gpt2.py``'s prefill the calls they had
PINNED = {
    ("afmoe", "trinity-tiny", "loss", False): "0b3ef1ca2893c2b5",
    ("kimi_linear", "kimi-tiny", "loss", False): "73f24d99e2aeefbe",
    ("afmoe", "trinity-tiny", "loss", True): "4e668ae5d5da36ef",
    ("kimi_linear", "kimi-tiny", "loss", True): "8b556dada3897d3a",
    ("mimo_v2_flash", "mimo-tiny", "decode_step", False): "034155686443bee8",
    ("olmoe", "olmoe-tiny", "decode_step", False): "fd7a5f435e2b43f0",
    ("sdar_moe", "sdar-tiny", "block_step", False): "d405176f3530ac26",
    ("gpt2", None, "prefill", False): "c185c7eecb054393",
    ("gpt2", None, "prefill", True): "493378cf76602a58",
}


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the digests are of jax 0.9.0's jaxprs")
@pytest.mark.parametrize("family,tiny,program,on_tpu", list(PINNED))
def test_the_programs_beside_the_prefill_are_the_parents(
        family, tiny, program, on_tpu, monkeypatch):
    import hashlib
    import re

    monkeypatch.undo()          # traced as a program for the chip traces it
    if on_tpu:
        monkeypatch.setattr(common, "_kernel_target", lambda: (None, True))
    ids = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if family == "gpt2":
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

        model = GPT2Model(GPT2Config(vocab_size=256, n_positions=128,
                                     n_embd=64, n_layer=2, n_head=4))
    else:
        model = _rehearsal(family, tiny,
                           "train" if program == "loss" else "serve")
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(1, 64))
    if program == "loss":
        traced = jax.make_jaxpr(
            lambda p, i: model.loss(p, {"input_ids": i}))(
                params, ids(2, 128 if on_tpu else 64))
    elif program == "block_step":
        traced = jax.make_jaxpr(lambda p, t, c: model.block_step(
            p, t, jnp.ones(t.shape, bool), c, pending=t))(
                params, ids(1, model.config.block_length), cache)
    elif program == "decode_step":
        traced = jax.make_jaxpr(model.decode_step)(params, ids(1), cache)
    else:
        traced = jax.make_jaxpr(model.prefill)(params, ids(1, 32), cache)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(traced))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PINNED[family, tiny, program, on_tpu]
