"""The plain reference against the program's own model at a small size on
the CPU, in float32: two independent writings of GPT-2 must agree to
rounding. On the chip the benchmark makes the same comparison at the
published widths, at set-up (benchmark/systems.py), through the same
family module."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import gpt2 as reference


@pytest.fixture(scope="module")
def tiny():
    from deepspeed_tpu.models.gpt2 import PRESETS, GPT2Model

    cfg = dataclasses.replace(PRESETS["gpt2-tiny"], dtype=jnp.float32,
                              use_flash_attention=False, remat=False)
    model = GPT2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(4))
    # biases and layer-norm offsets are zero at init: make them count
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])
    ids = np.random.default_rng(6).integers(0, cfg.vocab_size, size=48,
                                            dtype=np.int32)
    return model, {"model": {"n_head": cfg.n_head,
                             "vocab_size": cfg.vocab_size}}, params, ids


def test_logits_match_the_programs_model_in_float32(tiny):
    model, cfg, params, ids = tiny
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.apply(params, ids[None])[0])
    got = np.asarray(reference.reference_logits(params, ids, cfg))
    assert got.shape == (48, cfg["model"]["vocab_size"]) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


def test_loss_matches_the_programs_loss(tiny):
    model, cfg, params, ids = tiny
    with jax.default_matmul_precision("highest"):
        want = float(model.loss(params, {"input_ids": ids[None]}))
    got = float(reference.reference_loss(params, ids, cfg))
    assert got == pytest.approx(want, abs=1e-4)


def test_reference_is_causal(tiny):
    _, cfg, params, ids = tiny
    a = np.asarray(reference.reference_logits(params, ids, cfg))
    changed = ids.copy()
    changed[30:] = (changed[30:] + 1) % cfg["model"]["vocab_size"]
    b = np.asarray(reference.reference_logits(params, changed, cfg))
    np.testing.assert_array_equal(a[:30], b[:30])
    assert np.abs(a[30:] - b[30:]).max() > 1e-3


def test_reference_reads_bf16_weights_in_float32(tiny):
    """The benchmark hands it the served bf16 weights: it upcasts them and
    computes in float32, so it must equal itself on the rounded weights."""
    _, cfg, params, ids = tiny
    rounded = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    a = reference.reference_logits(rounded, ids, cfg)
    b = reference.reference_logits(
        jax.tree.map(lambda x: x.astype(jnp.float32), rounded), ids, cfg)
    assert a.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
