"""What a hybrid of softmax and KDA layers brings into the program
(models/llama.py's layer pattern, models/kda.py, ops/pallas/kda.py,
models/common.py's two kinds of cache, the front-end's accounting): the
chunked form and its kernel (interpret mode) against the token-by-token
recurrence, prefill + decode through state and convolution window against
one pass, the counts, the refusals. The family's reference and the
benchmark's side are in tests/benchmark/test_solar_family.py; lowering for
the chip in tests/unit/test_chip_bringup.py."""

import dataclasses
import functools
import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import common
from deepspeed_tpu.models import kda as kda_mixer
from deepspeed_tpu.models.llama import PRESETS, LlamaConfig, LlamaModel
from deepspeed_tpu.ops.pallas import kda

# one period twice over: 1 gated NoPE softmax layer (4 heads of 32 on 2 KV
# heads: head_dim is NOT n_embd / n_head) to 3 KDA layers (4 heads of 16),
# every layer routed (the second of four shares) beside a shared expert
TINY = LlamaConfig(
    vocab_size=512, n_positions=256, n_embd=64, n_layer=8, n_head=4,
    n_kv_head=2, head_dim=32, intermediate_size=32, n_experts=32,
    n_experts_per_tok=4, norm_topk_prob=True, n_shared_experts=1,
    router_scoring="sigmoid", experts_held=(8, 8), use_rope=False,
    attn_gate=True, gqa_layers=(0, 4), kda_heads=4, kda_head_dim=16)
F32 = dict(dtype=jnp.float32, remat=False, use_flash_attention=False)
# float32 on both sides; what is left is the order of the sums (the chunked
# algebra against 64+ rank-1 updates): measured 2e-7 - 3e-6 on outputs of
# size 0.3 - 0.9 and states of size ~1. The naive k / exp(G) form gives inf
# or nan in the strong-decay case; a missing factor 2 on beta moves o by 0.1
TOL = 2e-5


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel run by the Pallas interpreter (the test asks; the kernel
    does not pick it by itself)."""
    from jax.experimental import pallas as pl

    monkeypatch.setattr(kda.pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))


@pytest.fixture
def as_tpu_program(monkeypatch, interpreted):
    real = common._kernel_target
    monkeypatch.setattr(common, "_kernel_target", lambda: (real()[0], True))


def draw(seed, T, B=2, H=3, dk=32, a=None, dt=None, beta_shift=0.0):
    """Inputs as the mixer makes them: unit q (x dk^-1/2) and k, g = -A
    softplus(.) per channel with A ~ U(1, 16) a head and dt ~ logU(1e-3,
    1e-1) a channel (or the given ones), beta = 2 sigmoid(.)."""
    r = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q, k, v = unit(n(B, T, H, dk)) * dk ** -0.5, unit(n(B, T, H, dk)), \
        n(B, T, H, dk)
    a = jnp.asarray(r.uniform(1, 16, (H,)) if a is None
                    else np.full((H,), a), jnp.float32)
    dt = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(1e-1), (H, dk)))
                     if dt is None else np.full((H, dk), dt), jnp.float32)
    g = -a[:, None] * jax.nn.softplus(jnp.log(jnp.expm1(dt))
                                      + 0.3 * n(B, T, H, dk))
    return q, k, v, g, 2 * jax.nn.sigmoid(n(B, T, H) + beta_shift)


CASES = {"whole chunks": dict(T=128),
         "64 k + 17": dict(T=64 * 9 + 17),
         "one short chunk": dict(T=23),
         # the overflow trap: the cumulative log-decay passes -100 inside a
         # chunk; e^88 is float32's limit
         "strong decay": dict(T=192, a=16.0, dt=0.1),
         "beta near 2": dict(T=128, beta_shift=4.0)}


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("case", CASES, ids=CASES.keys())
def test_chunked_form_is_the_recurrence(interpreted, case, kernel):
    args = draw(1, **CASES[case])
    if case == "strong decay":
        assert float(jnp.cumsum(args[3][:, :64], axis=1).min()) < -100
    if case == "beta near 2":
        assert float(jnp.median(args[4])) > 1.9
    want_o, want_s = kda.recurrent_kda(*args)
    o, s = jax.jit(functools.partial(kda.chunked_kda, kernel=kernel))(*args)
    assert o.shape == want_o.shape and s.dtype == jnp.float32
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    assert float(jnp.abs(o - want_o).max()) < TOL
    assert float(jnp.abs(s - want_s).max()) < TOL


def test_a_naive_reciprocal_overflows_where_the_differences_do_not():
    """What the halving avoids: ``k e^-G`` inside one chunk of the
    strong-decay case is not a float32 number."""
    q, k, v, g, beta = draw(1, **CASES["strong decay"])
    G = jnp.cumsum(g[:, :64], axis=1)
    assert not bool(jnp.isfinite(k[:, :64] * jnp.exp(-G)).all())


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
def test_chunked_form_continues_a_state(interpreted, kernel):
    """Two calls, the state handed on (at a cut that is no chunk boundary),
    are one call; a padded tail does not move the state."""
    args = draw(2, T=200)
    want_o, want_s = kda.recurrent_kda(*args)
    run = functools.partial(kda.chunked_kda, kernel=kernel)
    o1, s1 = run(*(t[:, :77] for t in args))
    o2, s2 = run(*(t[:, 77:] for t in args), state=s1)
    assert float(jnp.abs(jnp.concatenate([o1, o2], 1) - want_o).max()) < TOL
    assert float(jnp.abs(s2 - want_s).max()) < TOL


# the state pass's own backward against ``jax.grad`` of the recurrence,
# float32: what is left is the order of the sums. Measured 2e-6 - 2e-5 on
# gradients of size 4 - 38 (q, k, v, g, beta) and 2e-7 on the state's
# (size 0.4 - 2.2); a missing term moves them by their own size
VJP_TOL = 1e-4
VJP_CASES = {**CASES, "segments + chunks + 12": dict(T=64 * 17 + 12)}


def _scalar(form, args, state, weights):
    o, s = form(*args, state)
    return jnp.sum(o.astype(jnp.float32) * weights[0]) \
        + jnp.sum(s * weights[1])


def _vjp_case(seed, **kw):
    args = draw(seed, **kw)
    B, T, H, dk = args[0].shape
    r = np.random.default_rng(seed + 40)
    n = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)
    return args, 0.3 * n(B, H, dk, dk), (n(B, T, H, dk), n(B, H, dk, dk))


@pytest.mark.parametrize("case", VJP_CASES, ids=VJP_CASES.keys())
def test_the_state_pass_backward_is_the_gradient_of_the_recurrence(case):
    """All five operands AND the initial state; lengths that are and are
    not whole chunks and whole groups of chunks; the fast-decaying head."""
    args, state, weights = _vjp_case(1, **VJP_CASES[case])
    want = jax.grad(functools.partial(_scalar, kda.recurrent_kda),
                    argnums=(0, 1))(args, state, weights)
    got = jax.jit(jax.grad(functools.partial(
        _scalar, functools.partial(kda.chunked_kda, vjp=True)),
        argnums=(0, 1)))(args, state, weights)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(g).all())
        assert float(jnp.abs(g - w).max()) < VJP_TOL * max(
            1.0, float(jnp.abs(w).max()))


@pytest.mark.parametrize("T", [64 * 3, 64 * 17 + 5], ids=["a group", "groups"])
def test_both_kernels_are_their_jnp_forms(interpreted, T):
    """``kda_chunk_fwd`` with the groups' end states and ``kda_chunk_bwd``
    (interpret mode) against ``_state_pass_jnp`` / ``_state_pass_bwd_jnp``
    on the same operands: outputs, kept states, every cotangent."""
    args, state, _ = _vjp_case(2, T=T)
    ops = kda.chunk_operands(*args)
    BH = state.shape[0] * state.shape[1]
    state = state.reshape(BH, *state.shape[2:])
    o, last, ends = kda._state_pass_kernel(ops, state, kda.CHUNK, keep=True)
    want = kda._state_pass_jnp(ops, state, kda.CHUNK, keep=True)
    for got, ref in zip((o, last, ends), want):
        assert got.shape == ref.shape
        assert float(jnp.abs(got - ref).max()) < TOL
    groups = -(-T // (64 * kda.CHUNKS_PER_STEP))
    assert ends.shape == (BH, groups, 32, 32)
    assert float(jnp.abs(jnp.swapaxes(ends[:, -1], -1, -2) - last).max()) == 0
    r = np.random.default_rng(9)
    do = jnp.asarray(r.standard_normal(o.shape), jnp.float32)
    ds = jnp.asarray(r.standard_normal((BH, 32, 32)), jnp.float32)
    starts = jnp.concatenate([jnp.swapaxes(state, -1, -2)[:, None],
                              ends[:, :-1]], axis=1)
    got = kda._state_pass_bwd_kernel(ops, starts, do, ds, kda.CHUNK)
    ref = kda._state_pass_bwd_jnp(ops, starts, do, ds, kda.CHUNK)
    assert set(got[0]) == set(ops)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert float(jnp.abs(g - w).max()) < TOL * max(
            1.0, float(jnp.abs(w).max()))


# the operand kernels against the ``jnp`` ``chunk_operands``. float32: what
# is left is the order of the sums (the kernel scans g inside a level's
# halves where the ``jnp`` subtracts cumulative sums), measured 1e-8 - 8e-6
# on operands of size 0.2 - 12. bf16: both round the same products, whose
# float32 values differ in the last place, so a rounding can fall the other
# way: one bf16 place (2^-8) of the operand's size
OPERAND_LENGTHS = {"a whole group": 512, "ragged": 64 * 5 + 17,
                   "shorter than a group": 23}
OPERAND_CASES = {
    **{f"{name}, {H} head{'s'[:H - 1]}": dict(T=T, H=H)
       for name, T in OPERAND_LENGTHS.items() for H in (1, 2)},
    # cumulative log-decay under -100 inside a chunk (the case of
    # ``test_a_naive_reciprocal_overflows_where_the_differences_do_not``)
    "the fast-decay head": dict(T=192, H=2, a=16.0, dt=0.1),
    # a head's lanes are whole tiles, as at the published 128: q, k, v, g
    # are read and their cotangents written IN PLACE, a head by block index
    # (narrower heads go ahead of the positions first)
    "heads of 128 lanes": dict(T=64 * 2 + 5, H=2, dk=128)}


def _to_groups(args, dtype):
    """``draw``'s inputs as ``chunked_kda(kernel=True)`` hands them to the
    operand kernels: q, k, v in ``dtype``, padded to whole groups."""
    q, k, v, g, beta = args
    to = kda._whole_groups(q.shape[1], kda.CHUNK) * kda.CHUNK
    return tuple(kda._padded(t, to) for t in (
        q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("case", OPERAND_CASES, ids=OPERAND_CASES.keys())
def test_the_operand_kernel_is_its_jnp_form(interpreted, case, dtype):
    """``kda_operands_fwd`` (interpret mode) against ``chunk_operands``:
    every operand, in the layout the state pass reads."""
    args = draw(4, **OPERAND_CASES[case])
    if "fast" in case:
        assert float(jnp.cumsum(args[3][:, :64], axis=1).min()) < -100
    padded = _to_groups(args, dtype)
    want = jax.jit(kda.chunk_operands)(*padded)
    got = jax.jit(functools.partial(kda._operands_kernel, chunk=kda.CHUNK))(
        *padded)
    assert set(got) == set(want)
    for name, ref in want.items():
        assert got[name].shape == ref.shape and got[name].dtype == ref.dtype
        assert bool(jnp.isfinite(got[name]).all()), name
        size = max(1.0, float(jnp.abs(ref).max()))
        limit = TOL if ref.dtype == jnp.float32 else 2.0 ** -8
        assert float(jnp.abs(got[name].astype(jnp.float32)
                             - ref.astype(jnp.float32)).max()) \
            <= limit * size, name


def _operand_cotangents(ops, seed, only=None):
    r = np.random.default_rng(seed)
    return {name: jnp.asarray(r.standard_normal(t.shape), jnp.float32)
            * (only in (None, name)) for name, t in ops.items()}


@pytest.mark.parametrize("dtype,only", [
    (jnp.float32, None), (jnp.float32, "decay"), (jnp.bfloat16, None)],
    ids=["float32", "float32, decay alone", "bf16"])
@pytest.mark.parametrize("case", ["ragged, 2 heads", "the fast-decay head",
                                  "heads of 128 lanes"])
def test_the_operand_backward_kernel_is_autodiff_of_the_jnp_form(
        interpreted, case, dtype, only):
    """``kda_operands_bwd`` (interpret mode) against ``jax.vjp`` of
    ``chunk_operands`` in float32, for q, k, v, g and beta; with a cotangent
    on the chunk's total decay ALONE, which only g hears (a rule that
    dropped it would pass every other check: PR 41's control
    ``decay_grad_dropped``); and from bf16 operands and cotangents (the
    inverse at three bf16 passes, the transposed scores on rounded
    cotangents), where the ``jnp`` form's own autodiff in bf16 is 0.4% of a
    gradient's size from the float32 one, and so is the kernel: measured
    0.2 - 0.5%."""
    padded = _to_groups(draw(5, **OPERAND_CASES[case]), dtype)
    shapes = jax.eval_shape(kda.chunk_operands, *padded)
    cts = {name: ct.astype(shapes[name].dtype) for name, ct
           in _operand_cotangents(shapes, 6, only).items()}
    f32 = lambda tree: jax.tree.map(lambda t: t.astype(jnp.float32), tree)
    want = jax.jit(lambda *at: jax.vjp(kda.chunk_operands, *at)[1](f32(cts)))(
        *f32(padded))
    got = jax.jit(functools.partial(kda._operands_bwd_kernel,
                                    chunk=kda.CHUNK))(*padded, cts)
    limit = VJP_TOL if dtype == jnp.float32 else 2.0 ** -6
    for name, at, g, w in zip("q k v g beta".split(), padded, got, want):
        assert g.shape == w.shape and g.dtype == at.dtype
        assert float(jnp.abs(g.astype(jnp.float32) - w).max()) < limit * max(
            1.0, float(jnp.abs(w).max())), name
    if only == "decay":
        # (the fast-decay head's total decay is e^-150: nothing to hear)
        assert "fast" in case or float(jnp.abs(got[3]).max()) > 1e-3
        assert all(float(jnp.abs(got[i]).max()) == 0 for i in (0, 1, 2, 4))


@pytest.mark.parametrize("case", ["ragged", "strong decay", "groups",
                                  "128 lanes"])
def test_both_kernel_pairs_give_the_gradient_of_the_recurrence(
        interpreted, case):
    """``jax.grad`` through ``chunked_kda(kernel=True, vjp=True)`` — the
    operands' rule around the state pass's, all four kernels in interpret
    mode — against ``jax.grad`` of ``recurrent_kda``, float32: q, k, v, g,
    beta and the state the pass starts from."""
    kw = {"ragged": dict(T=64 * 3 + 12, H=2), "groups": dict(T=64 * 8 + 5, H=1),
          "128 lanes": dict(T=64 + 7, H=2, dk=128),
          "strong decay": dict(T=128, H=2, a=16.0, dt=0.1)}[case]
    args, state, weights = _vjp_case(7, **kw)
    want = jax.jit(jax.grad(functools.partial(_scalar, kda.recurrent_kda),
                            argnums=(0, 1)))(args, state, weights)
    got = jax.jit(jax.grad(functools.partial(_scalar, functools.partial(
        kda.chunked_kda, kernel=True, vjp=True)), argnums=(0, 1)))(
            args, state, weights)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(g).all())
        assert float(jnp.abs(g - w).max()) < VJP_TOL * max(
            1.0, float(jnp.abs(w).max()))


def test_a_padded_tail_hears_nothing(interpreted):
    """Positions past the prompt's end (``beta`` = 0, ``g`` = 0, zero rows)
    get cotangents that are exactly zero from both backward kernels, g's
    apart: a pad position's g would move the chunk's total decay, and the
    pad's own transpose cuts it."""
    args, state, weights = _vjp_case(8, T=64 * 2 + 9, H=2)
    T = args[0].shape[1]
    padded = _to_groups(args, jnp.float32)
    assert padded[0].shape[1] == 64 * 3

    def loss(*padded):
        BH = state.shape[0] * state.shape[1]
        o, s = kda.state_pass(kda.operands(*padded, kda.CHUNK),
                              state.reshape(BH, *state.shape[2:]),
                              kda.CHUNK, True)
        o = jnp.moveaxis(o[:, :T].reshape(*state.shape[:2], T, -1), 1, 2)
        return jnp.sum(o * weights[0]) + jnp.sum(s.reshape(state.shape)
                                                 * weights[1])

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*padded)
    for name, g in zip("q k v g beta".split(), grads):
        assert float(jnp.abs(g[:, :T]).max()) > 1e-3, name
        if name != "g":
            assert float(jnp.abs(g[:, T:]).max()) == 0, name


def test_the_forward_rule_names_what_remat_attn_keeps():
    """``remat_wrap('attn')`` keeps the state pass's outputs and its group
    states by name: under it the backward holds no second forward pass."""
    from deepspeed_tpu.ops.pallas import SAVED_KDA_STATES, SAVED_O

    assert {SAVED_O, SAVED_KDA_STATES} <= set(common.SAVED_BY_ATTN)
    args, state, weights = _vjp_case(3, T=200)
    loss = functools.partial(
        _scalar, functools.partial(kda.chunked_kda, vjp=True))
    wrapped = common.remat_wrap(loss, "attn")
    text = str(jax.make_jaxpr(jax.grad(wrapped))(args, state, weights))
    assert f"name={SAVED_KDA_STATES}" in text and f"name={SAVED_O}" in text
    plain = jax.jit(jax.grad(loss))(args, state, weights)
    again = jax.jit(jax.grad(wrapped))(args, state, weights)
    for g, w in zip(jax.tree.leaves(again), jax.tree.leaves(plain)):
        assert float(jnp.abs(g - w).max()) < 1e-5
    # the operands' rule keeps its five INPUTS and nothing it made ...
    padded = _to_groups(args, jnp.float32)
    from jax._src.ad_checkpoint import saved_residuals

    kept = saved_residuals(lambda *a: kda.operands(*a, kda.CHUNK), *padded)
    assert len(kept) == 5 and all("argument" in why for _, why in kept), kept
    # ... so a segment under ``mix``'s checkpoint runs the operands' forward
    # again for the state pass's backward, and no second ``kda_chunk_fwd``
    kernels = functools.partial(
        _scalar, functools.partial(kda.chunked_kda, kernel=True, vjp=True))
    text = str(jax.make_jaxpr(jax.grad(common.remat_wrap(kernels, "attn")))(
        args, state, weights))
    assert [text.count(f"name={name}\n") + text.count(f"name={name} ")
            for name in ("kda_operands_fwd", "kda_chunk_fwd", "kda_chunk_bwd",
                         "kda_operands_bwd")] == [2, 1, 1, 1]


def test_one_step_is_the_recurrence():
    q, k, v, g, beta = draw(3, T=5)
    _, state = kda.recurrent_kda(*(t[:, :4] for t in (q, k, v, g, beta)))
    o, s = kda.kda_step(q[:, 4], k[:, 4], v[:, 4], g[:, 4], beta[:, 4], state)
    want_o, want_s = kda.recurrent_kda(q, k, v, g, beta)
    np.testing.assert_allclose(o, want_o[:, 4], atol=1e-6)
    np.testing.assert_allclose(s, want_s, atol=1e-6)


def test_unit_lower_inverse_is_exact():
    a = jnp.tril(jnp.asarray(np.random.default_rng(0).standard_normal(
        (3, 64, 64)), jnp.float32) * 0.3, -1)
    inv = kda._unit_lower_inverse(a)
    np.testing.assert_allclose(inv @ (jnp.eye(64) + a),
                               jnp.broadcast_to(jnp.eye(64), a.shape),
                               atol=2e-5)


def test_the_kernel_never_interprets_itself():
    """Off the TPU the kernels fail to lower: none runs in the interpreter
    unless a test asks (the ``interpreted`` fixture)."""
    assert "interpret=" not in inspect.getsource(kda)
    padded = _to_groups(draw(1, T=64, H=1), jnp.float32)
    with pytest.raises(Exception, match="[Ii]nterpret|TPU|tpu"):
        kda._operands_kernel(*padded, kda.CHUNK)
    with pytest.raises(Exception, match="[Ii]nterpret|TPU|tpu"):
        kda._operands_bwd_kernel(*padded, _operand_cotangents(
            jax.eval_shape(kda.chunk_operands, *padded), 0), kda.CHUNK)


# ------------------------------------------------------------- the hybrid
def hybrid(**over):
    model = LlamaModel(dataclasses.replace(TINY, **{**F32, **over}))
    return model, model.init_params(jax.random.PRNGKey(0))


def test_the_layer_pattern_and_its_stacks():
    model, params = hybrid()
    c = model.config
    assert c.pattern == ("attn", "kda", "kda", "kda")
    assert (c.n_attn_layers, c.head_dim, c.kv_dim) == (2, 32, 64)
    assert LlamaConfig(n_embd=64, n_layer=2, n_head=4).pattern == ("attn",)
    assert LlamaConfig(n_embd=64, n_layer=6, n_head=4, gqa_layers=(2, 5),
                       kda_heads=2, kda_head_dim=16).pattern == \
        ("kda", "kda", "attn")
    # what every layer has over ALL layers; each mixer over its own
    assert params["blocks"]["expert_up_w"].shape == (8, 8, 64, 32)
    assert params["blocks"]["router_w"].shape == (8, 64, 32)
    assert "q_w" not in params["blocks"]
    assert params["attn_blocks"]["q_w"].shape == (2, 64, 128)
    assert params["attn_blocks"]["attn_gate_w"].shape == (2, 64, 128)
    assert params["attn_blocks"]["o_w"].shape == (2, 128, 64)
    assert params["kda_blocks"]["kda_qkv_w"].shape == (6, 64, 192)
    assert params["kda_blocks"]["kda_conv_w"].shape == (6, 4, 192)
    assert params["kda_blocks"]["o_w"].shape == (6, 64, 64)
    # the draws the comparison depends on: decays that are NOT all 1
    a = np.exp(np.asarray(params["kda_blocks"]["kda_a_log"]))
    dt = np.asarray(jax.nn.softplus(params["kda_blocks"]["kda_dt_bias"]))
    assert 1 <= a.min() and a.max() <= 16
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == c.num_params()
    specs = model.param_partition_specs()
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda x: 0, specs, is_leaf=lambda s: isinstance(
                s, jax.sharding.PartitionSpec)))


def test_the_cache_holds_two_kinds_of_state():
    model, _ = hybrid()
    cache = model.init_cache(2, 100)
    assert cache["k"].shape == cache["v"].shape == (2, 2, 100, 128)
    assert cache["kda_state"].shape == (6, 2, 4, 16, 16)
    assert cache["kda_state"].dtype == jnp.float32
    assert cache["kda_conv"].shape == (6, 2, 3, 192)
    assert cache["expert_tokens"].shape == (8, 8)
    assert set(model.cache_partition_specs()) == set(cache)
    # rows a position: 2 softmax layers x (k + v) x 64 values x 4 B; state a
    # sequence: 6 KDA layers x (4 x 16 x 16 x 4 B + 3 x 192 x 4 B)
    assert common.cache_footprint(cache) == \
        (2 * 2 * 128 * 4, 6 * (4 * 16 * 16 * 4 + 3 * 192 * 4))


@pytest.mark.parametrize("T", [131, 64])
def test_prefill_then_decode_is_one_pass(T):
    """Prefill T then decode through the state and the convolution's window
    = one pass over T + n, on LOGITS, for 1 softmax + 3 KDA layers twice."""
    model, params = hybrid()
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 150)),
                      jnp.int32)
    full = jax.jit(model.apply)(params, ids)
    lg, cache = jax.jit(model.prefill)(params, ids[:, :T],
                                       model.init_cache(2, 200))
    # float32 both ways; what is left is chunked against token-by-token
    assert float(jnp.abs(lg - full[:, T - 1]).max()) < 2e-5
    step = jax.jit(model.decode_step)
    for t in range(T, 150):
        lg, cache = step(params, ids[:, t], cache)
        assert float(jnp.abs(lg - full[:, t]).max()) < 2e-5, t
    assert int(cache["pos"]) == 150
    assert float(jnp.abs(cache["kda_state"]).max()) > 0


def test_a_long_prompt_is_walked_in_segments(monkeypatch):
    """``models/kda.py::mix`` hands window and state from segment to
    segment, and to what is left over."""
    model, params = hybrid()
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 512, (1, 150)),
                      jnp.int32)
    whole, cache = model.prefill(params, ids, model.init_cache(1, 160))
    monkeypatch.setattr(kda_mixer, "SEGMENT", 64)      # 2 segments + 22
    cut, cache_cut = model.prefill(params, ids, model.init_cache(1, 160))
    assert float(jnp.abs(cut - whole).max()) < 2e-5
    for name in ("kda_state", "kda_conv"):
        assert float(jnp.abs(cache_cut[name] - cache[name]).max()) < 2e-5


def test_the_kernel_path_matches_the_jnp_form(as_tpu_program):
    """A program "for a TPU" takes ``kda_operands_fwd`` and ``kda_chunk_fwd``
    in prefill (run by the interpreter here), and the trunk under ``loss``
    takes them with ``kda_chunk_bwd`` and ``kda_operands_bwd``."""
    model, params = hybrid(use_flash_attention=False)
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 512, (1, 70)),
                      jnp.int32)
    real = common._kernel_target
    common._kernel_target = lambda: (None, False)
    try:
        want, _ = model.prefill(params, ids, model.init_cache(1, 80))
    finally:
        common._kernel_target = real
    text = str(jax.make_jaxpr(model.prefill)(params, ids,
                                             model.init_cache(1, 80)))
    assert "kda_chunk_fwd" in text and "kda_operands_fwd" in text
    got, _ = model.prefill(params, ids, model.init_cache(1, 80))
    assert float(jnp.abs(got - want).max()) < 2e-5
    # the trunk under ``loss`` takes both kernels, through the state pass's
    # own backward, and its gradient is the jnp forms'
    text = str(jax.make_jaxpr(jax.grad(model.loss))(params, ids))
    for kernel in ("kda_operands_fwd", "kda_chunk_fwd", "kda_chunk_bwd",
                   "kda_operands_bwd"):
        assert kernel in text, kernel
    got = jax.jit(jax.grad(model.loss))(params, ids)
    common._kernel_target = lambda: (None, False)
    try:
        want = jax.jit(jax.grad(model.loss))(params, ids)
    finally:
        common._kernel_target = real
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(g - w).max()) < 2e-5


def test_every_mechanism_counts():
    """A dropped convolution tap, a missing factor 2 on beta, a dropped gate
    or decay: each moves the logits by far more than the tolerances."""
    model, params = hybrid()
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 512, (1, 96)),
                      jnp.int32)
    base = model.apply(params, ids)
    kb = params["kda_blocks"]
    broken = {
        "a tap": {**kb, "kda_conv_w": kb["kda_conv_w"].at[:, 0].set(0)},
        "beta x 1": {**kb, "kda_b_w": kb["kda_b_w"] * 0 - 1e-9},
        "no decay": {**kb, "kda_a_log": kb["kda_a_log"] - 30},
    }
    for name, leaves in broken.items():
        moved = model.apply({**params, "kda_blocks": leaves}, ids)
        assert float(jnp.abs(moved - base).max()) > 1e-3, name
    ab = params["attn_blocks"]
    ungated = model.apply({**params, "attn_blocks": {
        **ab, "attn_gate_w": ab["attn_gate_w"] * 0}}, ids)
    assert float(jnp.abs(ungated - base).max()) > 1e-3


def test_a_model_with_one_mixer_is_what_it_was():
    """No pattern: one stack with the mixer's leaves in it, head_dim =
    n_embd / n_head, a rotary embedding, no gate; flops as they were."""
    c = PRESETS["llama-tiny"]
    assert (c.head_dim, c.pattern, c.n_attn_layers) == (16, ("attn",), 2)
    params = LlamaModel(c).init_params(jax.random.PRNGKey(0))
    assert set(params) == {"wte", "blocks", "norm_g", "lm_head"}
    assert params["blocks"]["q_w"].shape == (2, 64, 64)
    assert "attn_gate_w" not in params["blocks"]
    assert c.flops_per_token(128) == 6 * c.num_params(active=True) \
        + 12 * 2 * 64 * 128


def test_counts_at_the_published_sizes():
    """ISSUE 33's arithmetic: this chip's share 3.31 B (within 1%), the
    uncut configuration ~250 B with ~15 B met by a token."""
    share = LlamaConfig(
        vocab_size=24576, n_positions=36864, n_embd=4096, n_layer=4,
        n_head=64, n_kv_head=8, head_dim=128, intermediate_size=1280,
        n_experts=320, n_experts_per_tok=8, norm_topk_prob=True,
        n_shared_experts=1, router_scoring="sigmoid",
        experts_held=(120, 40), use_rope=False, attn_gate=True,
        gqa_layers=(0,), kda_heads=64, kda_head_dim=128)
    assert share.num_params() == pytest.approx(3.31e9, rel=0.01)
    assert kda_mixer.num_params(share) == pytest.approx(137.7e6, rel=0.001)
    whole = dataclasses.replace(
        share, n_layer=48, gqa_layers=tuple(range(0, 48, 4)),
        experts_held=None, vocab_size=196608)
    assert whole.pattern == ("attn", "kda", "kda", "kda")
    assert whole.num_params() == pytest.approx(250e9, rel=0.01)
    assert whole.num_params(active=True) == pytest.approx(15e9, rel=0.05)
    # attention over the context: the 12 softmax layers only
    assert whole.flops_per_token(2000) - whole.flops_per_token(1000) == \
        12 * 12 * 64 * 128 * 1000


def test_config_refuses_what_is_not_built():
    base = dict(n_embd=64, n_layer=4, n_head=4)
    with pytest.raises(ValueError, match="gqa_layers"):
        LlamaConfig(**base, gqa_layers=(0,))                    # no KDA sizes
    with pytest.raises(ValueError, match="gqa_layers"):
        LlamaConfig(**base, gqa_layers=(4,), kda_heads=2, kda_head_dim=16)
    with pytest.raises(ValueError, match="layer pattern"):
        LlamaConfig(**base, gqa_layers=(0,), kda_heads=2, kda_head_dim=16,
                    sequence_parallel="ring")
    with pytest.raises(ValueError, match="not of one kind"):
        LlamaConfig(**base, gqa_layers=(0,), kda_heads=2, kda_head_dim=16,
                    n_experts=4, n_experts_per_tok=2, n_dense_layers=2)
    # one kind of leading dense layer goes with a pattern, and so does
    # latent attention without a low-rank q
    c = LlamaConfig(**base, gqa_layers=(2,), kda_heads=2, kda_head_dim=16,
                    n_experts=4, n_experts_per_tok=2, n_dense_layers=1,
                    kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8,
                    v_head_dim=8)
    assert (c.dense_mixer, c.pattern) == ("kda", ("kda", "attn", "kda"))
    with pytest.raises(ValueError, match="attn_gate"):
        LlamaConfig(**base, attn_gate=True, q_lora_rank=8, kv_lora_rank=8,
                    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8)


# ---------------------------------------------------------- the front-end
GQA = LlamaConfig(vocab_size=512, n_positions=128, n_embd=64, n_layer=2,
                  n_head=4, n_kv_head=2, intermediate_size=128)
LATENT = LlamaConfig(vocab_size=512, n_positions=128, n_embd=64, n_layer=3,
                     n_head=4, intermediate_size=64, q_lora_rank=24,
                     kv_lora_rank=32, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16)
FOOTPRINTS = {
    # 2 layers x (k + v) x 32 values in one lane tile x 2 B
    "gqa": (GQA, 2 * 2 * 128 * 2, 0),
    # 3 layers x one latent row of 40 values in one lane tile x 2 B
    "latent": (LATENT, 3 * 128 * 2, 0),
    # 2 softmax layers x (k + v) x 64 values x 2 B a position; 6 KDA layers
    # x (4 x 16 x 16 float32 + 3 rows of 192 bf16) a sequence
    "hybrid": (TINY, 2 * 2 * 128 * 2, 6 * (4 * 16 * 16 * 4 + 3 * 192 * 2)),
}


@pytest.mark.parametrize("kind", FOOTPRINTS, ids=FOOTPRINTS.keys())
def test_the_front_end_tells_rows_a_position_from_state_a_sequence(kind):
    """``init_inference`` -> ``from_ds_config`` -> ``submit``: ``generate()``'s
    tokens; the ``request`` span closes with ``cache_bytes`` for what is
    stored a POSITION and ``state_bytes`` for what is stored a SEQUENCE,
    told apart by the names of the cache's own leaves."""
    from deepspeed_tpu import serving, telemetry
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    config, a_position, a_sequence = FOOTPRINTS[kind]
    model = LlamaModel(dataclasses.replace(config, param_dtype=jnp.bfloat16))
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(model, dtype="bf16", params=params,
                                          max_out_tokens=128)
    front = serving.from_ds_config(engine, DeepSpeedConfig({"serving": {}}))
    try:
        prompt = np.arange(20, dtype=np.int32)
        req = front.submit(prompt, max_new_tokens=20)
        req.result(timeout=300)
        assert req.status == "completed" and len(req.tokens) == 20
        want = np.asarray(engine.generate(prompt[None], max_new_tokens=20))
        assert req.tokens == want[0, 20:].tolist()
        span = [s for s in telemetry.get_tracer().snapshot()
                if s.name == "request" and s.args.get("request") == req.id][0]
        # 20 prompt + two 16-step ticks
        assert span.args["cache_positions"] == 52
        assert span.args["cache_bytes"] == 52 * a_position
        assert span.args["state_bytes"] == a_sequence
        # its three ticks compiled their programs: left out of what the
        # service takes, whole, and the request still feeds the estimate
        assert 0 < req.compile_s <= time.monotonic() - req.started_at
        assert 0 <= front._service_ema < 0.5 * req.compile_s
        again = front.submit(prompt, max_new_tokens=20)
        again.result(timeout=300)
        assert again.compile_s == 0 and front._service_ema > 0
    finally:
        front.begin_drain("shutdown")
        front.drain(timeout=60.0)


def test_a_new_prompt_length_every_request_still_feeds_admission():
    """A server that sees unbucketed prompt lengths compiles a prefill on
    nearly every request: each such request must still update the service
    estimate (less its compiling ticks), or ``deadline_unreachable``
    shedding goes silently off."""
    from deepspeed_tpu import serving
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.serving import ShedError

    model = LlamaModel(dataclasses.replace(GQA, param_dtype=jnp.bfloat16))
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(model, dtype="bf16", params=params,
                                          max_out_tokens=128)
    front = serving.from_ds_config(engine, DeepSpeedConfig({"serving": {}}))
    try:
        seen = []
        for length in (17, 18, 19, 21, 23):
            req = front.submit(np.arange(length, dtype=np.int32),
                               max_new_tokens=40)
            req.result(timeout=300)
            took = time.monotonic() - req.started_at
            assert req.status == "completed" and req.compile_s > 0
            # the prefill compiled every time; past the first request the
            # three decode ticks ran warm and are what the estimate is of
            assert front._service_ema is not None
            seen.append((front._service_ema, took - req.compile_s))
        assert seen[-1][0] > 0 and seen[-1][1] > 0
        assert all(ema != before for (ema, _), (before, _)
                   in zip(seen[1:], seen))
        # so the check it feeds is live: a deadline under the estimate sheds
        with pytest.raises(ShedError) as shed:
            front.submit(np.arange(29, dtype=np.int32), max_new_tokens=40,
                         deadline_s=0.25 * seen[-1][0])
        assert shed.value.reason == "deadline_unreachable"
    finally:
        front.begin_drain("shutdown")
        front.drain(timeout=60.0)
