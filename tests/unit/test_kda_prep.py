"""KDA's q | k | v preparation as a kernel pair (``kda_prep_fwd`` /
``kda_prep_bwd``, ops/pallas/kda.py, chosen by ``models/common.py::kda_qkv``)
against its ``jnp`` form ``prepare_qkv``: the kernels alone in interpret
mode, ``models/kda.py::mix`` across the edges of a block, a segment and a
call, and which callers keep the ``jnp`` form. The chunked form behind it is
tests/unit/test_kda.py's; lowering for the chip is
tests/unit/test_chip_bringup.py's."""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import common
from deepspeed_tpu.models import kda as kda_mixer
from deepspeed_tpu.ops.pallas import kda

H, DK, TAPS, D = 2, 128, 4, 64
CH = 3 * H * DK
# float32 on both sides: what is left is the order of four products and of a
# head's 128 squares (measured 2e-8 - 7e-7 on values up to 3, 1.5e-5 on a
# tap's gradient of size 80); bfloat16: the caller's own last place
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2.0 ** -6}


@pytest.fixture
def interpreted(monkeypatch):
    """The kernels run by the Pallas interpreter (the test asks; no kernel
    picks it by itself)."""
    from jax.experimental import pallas as pl

    monkeypatch.setattr(kda.pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))


@pytest.fixture
def as_tpu_program(monkeypatch):
    real = common._kernel_target
    monkeypatch.setattr(common, "_kernel_target", lambda: (real()[0], True))


def close(got, want, dtype, what):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert got.shape == want.shape, what
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=TOL[dtype] * max(1.0, np.abs(want).max()),
                               err_msg=what)


def draw(seed, T, dtype, B=2, heads=H, dk=DK):
    r = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)
    ch = 3 * heads * dk
    return {"h": n(B, T, D).astype(dtype),
            "qkv_w": (n(D, ch) * D ** -0.5).astype(dtype),
            "bump": jnp.zeros((B, T, ch), dtype),
            "tail": n(B, TAPS - 1, ch).astype(dtype),
            "conv_w": jnp.asarray(r.uniform(-0.5, 0.5, (TAPS, ch)),
                                  jnp.float32),
            "cts": tuple(n(B, T, heads, dk) for _ in range(3))
            + (n(B, TAPS - 1, ch),)}


# T: three blocks of rows the last one short; one short block (of ONE head
# two lane tiles wide); fewer rows than the window keeps
@pytest.mark.parametrize("T,dtype,heads,dk", [
    (154, jnp.float32, H, DK), (154, jnp.bfloat16, H, DK),
    (37, jnp.bfloat16, 1, 2 * DK), (2, jnp.float32, H, DK)],
    ids=lambda v: getattr(v, "__name__", str(v)))
def test_the_kernel_pair_is_its_jnp_form(monkeypatch, interpreted, T, dtype,
                                         heads, dk):
    """``kda_prep_fwd`` and ``kda_prep_bwd`` (interpret mode) against
    ``prepare_qkv`` and autodiff of it: q, k, v and the cotangents of the
    projection's output (``bump``, added to it), of the tail and the taps,
    and through them of the mixer's input and ``kda_qkv_w``."""
    monkeypatch.setattr(kda, "PREP_ROWS", 64)
    drawn = draw(T, T, dtype, heads=heads, dk=dk)
    cts = drawn.pop("cts")

    def through(form):
        def weighed(args):
            p = args["h"] @ args["qkv_w"] + args["bump"]
            made = form(p, args["tail"], args["conv_w"], heads,
                        kda_mixer.L2_EPS)
            return sum(jnp.sum(t.astype(jnp.float32) * ct)
                       for t, ct in zip(made, cts)), made
        return jax.jit(jax.value_and_grad(weighed, has_aux=True))(drawn)

    (_, got), got_grads = through(kda.prepare)
    (_, want), want_grads = through(kda.prepare_qkv)
    for name, a, b in zip(("q", "k", "v", "the new tail"), got, want):
        assert a.dtype == dtype
        close(a, b, dtype, name)
    for name in ("bump", "tail", "conv_w", "h", "qkv_w"):
        assert got_grads[name].dtype == drawn[name].dtype
        close(got_grads[name], want_grads[name], dtype, f"d {name}")


def mixer(dk=DK, heads=H, dtype=jnp.float32):
    c = types.SimpleNamespace(
        n_embd=D, kda_heads=heads, kda_head_dim=dk, kda_conv=TAPS, dtype=dtype,
        param_dtype=jnp.float32, rms_norm_eps=1e-6)
    blk = jax.tree.map(lambda t: t[0], kda_mixer.init_leaves(
        c, jax.random.PRNGKey(3), 1, 0.02))
    return c, blk


@pytest.fixture
def a_product_for_the_chunks(monkeypatch):
    """A product of q, k, v, g, beta and a sum into the state stand in for
    the chunked form behind the preparation (its own tests are
    tests/unit/test_kda.py's, minutes of them): what differs between the
    two programs below is the preparation alone, and every one of its
    outputs is heard."""
    monkeypatch.setattr(
        common, "kda_attention",
        lambda q, k, v, g, beta, state, differentiable=False: (
            (q + k) * v * jnp.exp(g) * beta[..., None],
            state + jnp.einsum("bthk,bthv->bhkv", k, v.astype(jnp.float32))))


def test_mix_hands_the_window_across_blocks_segments_and_calls(
        monkeypatch, interpreted, a_product_for_the_chunks):
    """Two and a half segments of two blocks each behind a non-zero tail
    (what a call before left): outputs, tail, state and the gradients of
    the input, the tail, the projection and the taps equal the ``jnp``
    path's, so the three rows before a block, before a segment and before
    a call are each the right ones, forward and backward."""
    monkeypatch.setattr(kda_mixer, "SEGMENT", 128)
    monkeypatch.setattr(kda, "PREP_ROWS", 64)
    c, blk = mixer(heads=1)
    r = np.random.default_rng(5)
    n = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)
    T = 2 * 128 + 64
    state, ct = 0.1 * n(1, 1, DK, DK), n(1, T, DK)
    args = {"h": n(1, T, D), "tail": 0.3 * n(1, TAPS - 1, 3 * DK),
            "qkv_w": blk["kda_qkv_w"], "conv_w": blk["kda_conv_w"]}

    def weighed(args):
        made = kda_mixer.mix(
            c, args["h"], {**blk, "kda_qkv_w": args["qkv_w"],
                           "kda_conv_w": args["conv_w"]},
            args["tail"], state, differentiable=True)
        out, tail, after = made
        return jnp.sum(out * ct) + jnp.sum(tail) + jnp.sum(after), made

    run = lambda: jax.jit(jax.value_and_grad(weighed, has_aux=True))(args)
    (_, want), want_grads = run()
    monkeypatch.setattr(common, "_kernel_target", lambda: (None, True))
    (_, got), got_grads = run()
    for name, a, b in zip(("out", "tail", "state"), got, want):
        close(a, b, jnp.float32, name)
    for name in args:
        close(got_grads[name], want_grads[name], jnp.float32, f"d {name}")


@pytest.mark.parametrize("case,T,dk,heads,kernel", [
    ("a segment", 64, 128, 2, True),
    ("one position: a decode step", 1, 128, 2, False),
    ("heads narrower than a lane tile", 64, 16, 4, False)])
def test_which_callers_take_the_kernel(as_tpu_program, case, T, dk, heads,
                                       kernel):
    """In a program for a TPU ``mix`` names ``kda_prep_fwd`` (and under the
    gradient ``kda_prep_bwd``) where there is more than one position and a
    head's lanes are whole tiles; a decode step and the tests' 16-wide
    heads keep the ``jnp`` form. Off the TPU nothing names them."""
    c, blk = mixer(dk, heads, jnp.bfloat16)
    h = jax.ShapeDtypeStruct((1, T, D), jnp.bfloat16)
    tail = jax.ShapeDtypeStruct((1, TAPS - 1, 3 * heads * dk), jnp.bfloat16)
    state = jax.ShapeDtypeStruct((1, heads, dk, dk), jnp.float32)

    def lowered(differentiable):
        def weighed(h, tail, state):
            out, tail, state = kda_mixer.mix(c, h, blk, tail, state,
                                             differentiable=differentiable)
            return jnp.sum(out.astype(jnp.float32)) + jnp.sum(state)
        fn = jax.grad(weighed) if differentiable else weighed
        return jax.jit(fn).trace(h, tail, state).lower(
            lowering_platforms=("tpu",)).as_text()

    assert ("kda_prep_fwd" in lowered(False)) == kernel, case
    if T > 1:       # a decode step is never differentiated
        text = lowered(True)
        assert ("kda_prep_fwd" in text) == ("kda_prep_bwd" in text) == kernel


def test_off_the_tpu_the_jnp_form_runs_and_no_kernel_interprets_itself():
    import inspect

    drawn = draw(0, 40, jnp.float32, B=1)
    text = jax.jit(lambda p, tail, w: common.kda_qkv(
        p, tail, w, H, kda_mixer.L2_EPS, True)[:3]).lower(
        drawn["bump"], drawn["tail"], drawn["conv_w"]).as_text()
    assert "kda_prep" not in text and "custom_call" not in text
    assert "interpret=" not in inspect.getsource(kda)
    with pytest.raises(Exception, match="[Ii]nterpret|TPU|tpu"):
        kda.prepare(drawn["bump"], drawn["tail"], drawn["conv_w"], H, 1e-6)
