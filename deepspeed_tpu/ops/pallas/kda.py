"""Kimi Delta Attention (KDA): the gated delta rule with a PER-CHANNEL decay,
as a recurrence (decode), as its chunked form (prefill, training) and as the
Pallas TPU kernels of that form: one that makes every chunk's operands, one
that carries the state from chunk to chunk, one that makes q, k, v from the
mixer's projection, and a backward for each.

One head holds a state ``S`` (dk, dv), float32. A position ``t`` brings a
query and key (dk,), L2-normalised by the caller, a value (dv,), a log-decay
``g_t <= 0`` per channel of dk and a step ``beta_t`` (up to 2: the
eigenvalues of ``I - beta k k^T`` then lie in [-1, 1])::

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T        o_t = S_t^T q_t

``recurrent_kda`` is that, token by token (``lax.scan``): the definition,
what a decode step runs (``kda_step``) and what the chunked form is tested
against. A prompt of 4k-32k tokens is that many dependent steps, so prefill
takes the chunked form (chunks of ``CHUNK`` = 64 positions, the family's
convention: the state pass below is sequential over T / 64 chunks and a
chunk's quadratic part is 64 x 64; 128 would halve the steps and cost 5x
the operations of the in-chunk inverse). With ``G_t`` the cumulative
log-decay inside a chunk and ``S_0`` the state it starts from::

    delta = (I + A)^-1 (beta (V - (K . e^G) S_0))   A_ti = beta_t sum_c k_tc k_ic e^(G_tc - G_ic), i < t
    O = (Q . e^G) S_0 + Aqk delta                   Aqk_ti = sum_c q_tc k_ic e^(G_tc - G_ic), i <= t
    S_C = Diag(e^G_C) S_0 + (K . e^(G_C - G))^T delta

``chunk_operands`` computes everything that does not depend on ``S_0`` for
all chunks at once: ``U = T beta V``, ``W = T beta (K . e^G)`` with ``T = (I
+ A)^-1``, ``Qg``, ``Kend``, ``Aqk`` and the chunk's total decay. It is the
definition, in ``jnp``, and what runs off the TPU. In a program for a TPU
the same comes from ``kda_operands_fwd`` (``_operands_kernel``; its body,
``_one_chunk_operands``, is tested against the ``jnp``): grid parallel over
batch x head and over groups of ``CHUNKS_PER_STEP`` chunks, nothing
carried; q, k, v and g are read as the caller's ``(B, T, H dk)`` rows, a
head's lanes by block index, and a chunk's cumulative decays, level
factors, scores and inverse never leave VMEM (through XLA they were ~45
float32 values a channel a position in HBM). ``kda_chunk_fwd`` (off the TPU
``_state_pass_jnp``, the same three lines in ``jnp``, which the kernel is
tested against) then walks the chunks: grid parallel over batch x head,
sequential over chunks, the state in a float32 VMEM scratch, emitting every
position's output and the state after the last one.

**The backward** (``state_pass``, a ``custom_vjp`` on the state pass;
``chunked_kda(vjp=True)``, what a model's trunk under ``loss`` takes). With
``dO`` and the cotangent ``dS_C`` of the state a chunk leaves::

    d delta = Aqk^T dO + Kend dS_C        dU = d delta        dW = -d delta S_0^T
    dAqk = dO delta^T    dQg = dO S_0^T   dKend = delta dS_C^T
    d decay = sum_v (S_0 . dS_C)
    dS_0 = Diag(decay) dS_C + Qg^T dO - W^T d delta

so a chunk needs the state it STARTED from, and the chunks run in reverse.
The forward rule keeps the state at the end of every GROUP of
``CHUNKS_PER_STEP`` chunks and not of every chunk (32 heads x 128 x 128
float32 x 256 chunks would be 537 MB a layer at 16k positions; a group's
are 67 MB), under the name ``SAVED_KDA_STATES``, beside the outputs under
``SAVED_O``: an activation-checkpoint policy that keeps both
(``models/common.py::remat_wrap``, ``'attn'``) never runs the forward
kernel a second time. ``kda_chunk_bwd`` (off the TPU
``_state_pass_bwd_jnp``) walks the groups last to first, batch x head
parallel: a group's chunk states are made again from its start state into
VMEM, then its chunks run in reverse with ``dS`` float32 in VMEM, the
matmuls' operands rounded as the forward rounds them.

The operands have a rule of their own too (``operands``, a ``custom_vjp``
around ``kda_operands_fwd``) whose residuals are its five INPUTS: a
checkpointed segment's re-run writes nothing for it. ``kda_operands_bwd``
reads q, k, v, g, beta and the cotangents ``kda_chunk_bwd`` wrote, makes a
chunk's intermediates again in VMEM and transposes them there (``jax.vjp``
of the one-chunk forward, traced inside the kernel; the products through
rules that keep their operands' types): ``dT = dU (beta V)^T + dW (beta K
e^G)^T``, ``d(beta Akk) = -T^T dT T^T``, the scores' cotangents back to q, k
and g level by level through the SAME two factors as the forward. The
derivative of ``e^x`` is ``e^x``, so every exponent stays a difference <= 0
and no reciprocal of a decay appears in the backward either. Off the TPU
the gradient of ``chunk_operands`` is autodiff's of the ``jnp``.

**A per-channel decay cannot be factored naively.** ``e^(G_t - G_i)`` as
``e^G_t x e^-G_i`` overflows float32 inside one chunk (the cumulative
log-decay reaches -100 and below over 64 steps where a head decays fast;
``e^88`` is the limit). Every exponent here is a DIFFERENCE of cumulative
log-decays that is <= 0: the pairs (t, i) of a chunk are split by halving
(blocks of 64, 32, ... 2 positions; a pair belongs to the level at which t
falls in the later and i in the earlier half of one block) and a level's
pairs are taken relative to the boundary between its halves, ``e^(G_t - r)
x e^(r - G_i)``, both factors <= 1, as ONE matmul a level. Never a
reciprocal of a decay. (The kernel does not even subtract two cumulative
sums: ``G_t - r`` IS the sum of g from the boundary to t, so a level scans
g inside its halves, a log-step rotate-and-add, and takes one exponential
a position.)

``(I + A)^-1`` is exact in finitely many matmuls, A being strictly lower
triangular: within diagonal blocks of 16 by the product form of the Neumann
series, ``(I - D)(I + D^2)(I + D^4)(I + D^8)``, and across the four blocks
by the same form of the block-nilpotent rest, in float32 (three bf16
passes a matmul on the MXU; the kernel splits the operands into bf16 high
and low parts itself, Mosaic knowing one pass or six, and takes all six
where the caller's own type is float32).

A prompt that is no whole number of chunks is padded at its tail with
``beta = 0``, ``g = 0``: the identity, the state does not move, and a pad
position's cotangents are zero.

**What comes before the chunks** (``prepare_qkv``, the definition in
``jnp``; ``prepare``, the same as a kernel pair under a ``custom_vjp``):
q, k, v from the mixer's q | k | v projection, a causal depthwise
convolution over the last ``taps`` positions, SiLU, q and k L2-normalised a
head. ``kda_prep_fwd`` holds a block of rows x whole heads of each third of
the projection's output in VMEM, float32, and writes q, k, v as the ``(B,
T, H dk)`` rows ``kda_operands_fwd`` reads in place; ``kda_prep_bwd`` walks
the row blocks last to first, makes the pre-activation, SiLU and norms
again and writes the projection's cotangent in one piece, the tail's, and
the taps' as partial sums. Their residuals are the rule's three inputs.
Both are bound by the VPU, so what they spare it decides: the window is
staged a 128-lane column at a time, where a column's rows are contiguous
and the convolution's look back is an unaligned load (no rotate, no
select), and the logistic function is one ``tanh``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import SAVED_KDA_STATES, SAVED_O

CHUNK = 64
INVERSE_BLOCK = 16      # diagonal blocks of the in-chunk inverse
# chunks a grid step of the kernel walks: the per-step overhead is paid once
# for 8 x 64 rows (six operand blocks of <= 128 KB each, twice buffered)
CHUNKS_PER_STEP = 8
# float32 operands on the MXU: three bf16 passes (one is the default)
_PRECISE = jax.lax.Precision.HIGH


# --------------------------------------------------------------- recurrence
def kda_step(q, k, v, g, beta, state):
    """One position of the recurrence for every row and head. q, k, g
    (B, H, dk), v (B, H, dv), beta (B, H), state (B, H, dk, dv) float32 ->
    (o (B, H, dv) float32, the new state). A rank-1 update, in float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    state = state * jnp.exp(g)[..., None]
    delta = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", state, k))
    state = state + k[..., None] * delta[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", state, q), state


def recurrent_kda(q, k, v, g, beta, state=None):
    """The definition, token by token. q, k, g (B, T, H, dk), v (B, T, H,
    dv), beta (B, T, H); ``state`` (B, H, dk, dv) float32, None = zeros.
    -> (o (B, T, H, dv) float32, the state after the last position)."""
    B, T, H, dk = q.shape
    if state is None:
        state = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)

    def step(state, at):
        o, state = kda_step(*at, state)
        return state, o

    state, o = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


# ------------------------------------------------ a chunk without its state
def _decayed_scores(q, k, G, dtype):
    """q, k, G (..., C, dk) float32, G the cumulative log-decay of the chunk
    -> (``sum_c q_tc k_ic e^(G_tc - G_ic)`` for i <= t, the same of k with k
    for i < t; zeros elsewhere), (..., C, C) float32 each. By halving: see
    the module's docstring. The matmuls take their operands in ``dtype``."""
    C = q.shape[-2]
    at = jnp.arange(C)
    rows = jnp.concatenate([q, k], axis=-2)                 # (..., 2C, dk)
    scores = jnp.zeros((*q.shape[:-2], 2 * C, C), jnp.float32)
    half = C // 2
    while half >= 1:
        block = 2 * half
        # the boundary: G at the last position of each block's earlier half
        ref = jnp.repeat(G[..., half - 1::block, :], block, axis=-2)
        late = ((at % block) >= half)[:, None]
        row_decay = jnp.exp(jnp.where(late, G - ref, -jnp.inf))
        col_decay = jnp.exp(jnp.where(late, -jnp.inf, ref - G))
        level = jnp.einsum(
            "...td,...id->...ti",
            (rows * jnp.concatenate([row_decay] * 2, axis=-2)).astype(dtype),
            (k * col_decay).astype(dtype),
            preferred_element_type=jnp.float32)
        same = at[:, None] // block == at[None, :] // block
        scores = scores + jnp.where(jnp.tile(same, (2, 1)), level, 0.0)
        half //= 2
    own = jnp.sum(q.astype(dtype).astype(jnp.float32)
                  * k.astype(dtype).astype(jnp.float32), axis=-1)
    return scores[..., :C, :] + own[..., None] * jnp.eye(C), scores[..., C:, :]


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` (..., C, C) strictly lower triangular, in
    float32 (three bf16 passes a matmul on a TPU: 2^-16, far below the bf16
    operands around it): exact in finitely many matmuls (the module's
    docstring)."""
    C = a.shape[-1]
    mm = functools.partial(jnp.matmul, precision=_PRECISE)
    eye = jnp.eye(C, dtype=jnp.float32)
    at = jnp.arange(C) // min(INVERSE_BLOCK, C)
    diag = jnp.where(at[:, None] == at[None, :], a, 0.0)

    def neumann(x, order):
        # sum_{n < order} (-x)^n = (I - x)(I + x^2)(I + x^4) ... : the
        # squarings are one loop body, compiled once
        steps = max(0, math.ceil(math.log2(order)) - 1)

        def square(_, held):
            inv, power = held
            power = mm(power, power)
            return mm(inv, eye + power), power

        return jax.lax.fori_loop(0, steps, square, (eye - x, x))[0]

    inv_diag = neumann(diag, min(INVERSE_BLOCK, C))
    # I + a = (I + diag)(I + rest), rest block-strictly-lower
    rest = mm(inv_diag, a - diag)
    return mm(neumann(rest, -(-C // INVERSE_BLOCK)), inv_diag)


def _whole_groups(T, chunk):
    """The chunks that hold T positions: whole groups of ``CHUNKS_PER_STEP``
    chunks (what a grid step walks: ``_per_step``), or the chunks of a T
    shorter than one group."""
    n = -(-T // chunk)
    return -(-n // _per_step(n)) * _per_step(n)


def _padded(t, to):
    """(B, T, ...) with zeros after position T - 1, up to ``to`` positions:
    ``beta`` = 0, ``g`` = 0 make identity chunks."""
    return jnp.pad(t, ((0, 0), (0, to - t.shape[1]))
                   + ((0, 0),) * (t.ndim - 2))


def chunk_operands(q, k, v, g, beta, chunk=CHUNK):
    """What the state pass needs of every chunk, computed for all chunks at
    once. q, k, g (B, T, H, dk), v (B, T, H, dv), beta (B, T, H); T is
    padded to whole groups of ``CHUNKS_PER_STEP`` chunks, or to whole chunks
    where it is shorter than one group (``beta`` = 0, ``g`` = 0: identity
    chunks). -> ``u`` (dv), ``w``,
    ``qg``, ``kend`` (dk) as (B * H, T', .) and ``aqk`` (B * H, T', chunk)
    in v's type, ``decay`` (B * H, T' / chunk, dk) float32."""
    B, T, H, dk = q.shape
    dtype, f32 = v.dtype, jnp.float32
    n = _whole_groups(T, chunk)
    pad = lambda t: _padded(t.astype(f32), n * chunk)
    # (B, H, n, chunk, .)
    cut = lambda t: jnp.moveaxis(pad(t), 2, 1).reshape(
        B, H, n, chunk, *t.shape[3:])
    q, k, v, g, beta = (cut(t) for t in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)
    aqk, akk = _decayed_scores(q, k, G, dtype)
    t_inv = _unit_lower_inverse(beta[..., None] * akk)
    grown = jnp.exp(G)
    solved = jnp.matmul(t_inv, jnp.concatenate(
        [beta[..., None] * k * grown, beta[..., None] * v], axis=-1),
        precision=_PRECISE)
    total = G[..., -1:, :]
    flat = lambda t: t.astype(dtype).reshape(B * H, n * chunk, t.shape[-1])
    return {"u": flat(solved[..., dk:]), "w": flat(solved[..., :dk]),
            "qg": flat(q * grown), "kend": flat(k * jnp.exp(total - G)),
            "aqk": flat(aqk),
            "decay": jnp.exp(total[..., 0, :]).reshape(B * H, n, dk)}


# ------------------------------------------- a chunk's operands as kernels
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rows_down(x, by):
    """``x`` (rows, lanes) with row t holding row t - by (circular): a
    sublane rotate in the kernel. Linear, so its transpose is the rotate the
    other way (``pltpu.roll`` has no rule of its own)."""
    return pltpu.roll(x, by, 0)


_rows_down.defvjp(lambda x, by: (pltpu.roll(x, by, 0), None),
                  lambda by, _, ct: (pltpu.roll(ct, ct.shape[0] - by, 0),))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _level_scores(q, k, early, dtype):
    """A level's scores: ``[q; k] early^T`` as ONE matmul (the later rows of
    q and of k against the earlier rows of k, each already times its decay),
    operands rounded to ``dtype``, float32 sums -> (of q, of k), (C, C) each.
    Its transposes take the cotangents rounded the same way (as
    ``_chunk_backward`` does), never a mixed-type product."""
    C = q.shape[0]
    both = jax.lax.dot_general(
        jnp.concatenate([q, k], axis=0).astype(dtype), early.astype(dtype),
        _NT, preferred_element_type=jnp.float32)
    return both[:C], both[C:]


def _level_scores_bwd(dtype, res, cts):
    q, k, early = res
    C = q.shape[0]
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)
    ct = jnp.concatenate(cts, axis=0).astype(dtype)
    rows = dot(ct, early.astype(dtype), _NN)
    return rows[:C], rows[C:], dot(
        ct, jnp.concatenate([q, k], axis=0).astype(dtype), _TN)


_level_scores.defvjp(
    lambda q, k, early, dtype: (_level_scores(q, k, early, dtype),
                                (q, k, early)), _level_scores_bwd)


def _three_passes(a, b, dims):
    """A float32 product as three bf16 passes with float32 sums (what
    ``Precision.HIGH`` is on the MXU, which Mosaic does not take by name):
    each operand split into a bf16 high and low part, the low x low term
    (2^-16 of the product) left out."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    dot = functools.partial(jax.lax.dot_general, dimension_numbers=dims,
                            preferred_element_type=f32)
    a_high, b_high = a.astype(bf16), b.astype(bf16)
    a_low = (a - a_high.astype(f32)).astype(bf16)
    b_low = (b - b_high.astype(f32)).astype(bf16)
    return dot(a_high, b_high) + (dot(a_high, b_low) + dot(a_low, b_high))


@jax.custom_vjp
def _precise_bf16(a, b):
    """``a b`` in float32 at three bf16 passes, forward and transposed."""
    return _three_passes(a, b, _NN)


_precise_bf16.defvjp(
    lambda a, b: (_three_passes(a, b, _NN), (a, b)),
    lambda res, ct: (_three_passes(ct, res[1], _NT),
                     _three_passes(res[0], ct, _TN)))


def _one_chunk_operands(q, k, v, g, beta_row, dtype):
    """``chunk_operands`` for ONE chunk, in the operations a Mosaic kernel
    has (and whose transposes it has: ``kda_operands_bwd`` is ``jax.vjp`` of
    this, traced inside the kernel). q, k (C, dk), v (C, dv) in the caller's
    type, g (C, dk) float32, ``beta_row`` (1, C) float32, C a power of two.
    -> u (C, dv), w, qg, kend (C, dk), aqk (C, C) in ``dtype``, decay (1,
    dk) float32. The same mathematics as the ``jnp`` form: every exponent a
    difference of cumulative log-decays <= 0, by halving; the score matmuls
    on operands rounded to ``dtype``; the inverse and ``T [beta K e^G, beta
    V]`` in float32 at three bf16 passes (``_precise_bf16``: Mosaic refuses
    ``HIGH``) or, for a float32 caller, at ``HIGHEST``.

    No cumulative sum is taken across a level's boundary and subtracted:
    ``G_t - r`` for a position in the later half of a block IS the sum of g
    from the boundary to t, ``r - G_i`` in the earlier half minus the sum
    from i + 1 to the boundary, so each level scans g inside its halves
    (log-step rotate-and-add, one array: later halves look back, earlier
    ones ahead) and takes ONE exponential a position."""
    f32 = jnp.float32
    C, dk = q.shape
    assert C & (C - 1) == 0, C
    q, k, v = (t.astype(f32) for t in (q, k, v))
    row = jax.lax.broadcasted_iota(jnp.int32, (C, dk), 0)
    at = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    to = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = at == to
    beta = jnp.sum(jnp.where(eye, beta_row, 0.0), axis=1, keepdims=True)

    def scanned(half):
        """Later halves of blocks of 2 x half rows: the sum of g from the
        half's first row to this one; earlier halves: from this row to the
        half's last."""
        late, inside = (row & half) != 0, row & (half - 1)
        x, by = g, 1
        while by < half:
            x = x + jnp.where(
                late, jnp.where(inside >= by, _rows_down(x, by), 0.0),
                jnp.where(inside < half - by, _rows_down(x, C - by), 0.0))
            by *= 2
        return late, x

    aqk = jnp.where(eye, jnp.sum(q * k, axis=1, keepdims=True), 0.0)
    akk = jnp.zeros((C, C), f32)
    half = C // 2
    while half >= 1:
        late, x = scanned(half)
        # later rows e^(G_t - r), earlier rows e^(r - G_i): both <= 1
        decay = jnp.exp(jnp.where(late, x, x - g))
        rows, cols = jnp.where(late, decay, 0.0), jnp.where(late, 0.0, decay)
        same = (at & -(2 * half)) == (to & -(2 * half))
        of_q, of_k = _level_scores(q * rows, k * rows, k * cols, dtype)
        aqk = aqk + jnp.where(same, of_q, 0.0)
        akk = akk + jnp.where(same, of_k, 0.0)
        half //= 2
    # the chunk's own cumulative log-decay, every row looking back, and what
    # is left of the chunk's after a row, every row looking ahead (summed,
    # not ``total - G``: two sums of ~-150 would cancel to 1e-5)
    G, left, by = g, g, 1
    while by < C:
        G = G + jnp.where(row >= by, _rows_down(G, by), 0.0)
        left = left + jnp.where(row < C - by, _rows_down(left, C - by), 0.0)
        by *= 2
    grown = jnp.exp(G)

    # float32 products: three bf16 passes beside operands of 8 bits, all
    # six (Mosaic's ``HIGHEST``) where the caller's own type is float32
    mm = _precise_bf16 if jnp.dtype(dtype).itemsize < 4 else functools.partial(
        jnp.dot, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=f32)
    one = eye.astype(f32)
    inner = min(INVERSE_BLOCK, C)
    a = beta * akk
    diag = jnp.where((at & -inner) == (to & -inner), a, 0.0)

    def neumann(x, order):
        inv, power = one - x, x
        for _ in range(max(0, math.ceil(math.log2(order)) - 1)):
            power = mm(power, power)
            inv = mm(inv, one + power)
        return inv

    inv_diag = neumann(diag, inner)
    rest = mm(inv_diag, a - diag)
    t_inv = mm(neumann(rest, -(-C // INVERSE_BLOCK)), inv_diag)
    return (mm(t_inv, beta * v).astype(dtype),
            mm(t_inv, beta * k * grown).astype(dtype),
            (q * grown).astype(dtype),
            (k * jnp.exp(left - g)).astype(dtype), aqk.astype(dtype),
            jnp.exp(jnp.sum(g, axis=0, keepdims=True)))


# chunks of a grid step whose dependent chains of small matmuls the
# scheduler may interleave: they share one loop body
_TOGETHER = 2


def _chunks_in_turn(chunks, chunk, one):
    """``one(rows, at)`` for every chunk of a grid step, ``_TOGETHER`` a
    loop body: ``rows`` the chunk's positions in the step's block, ``at``
    its row in a (chunks, .) block."""
    together = _TOGETHER if chunks % _TOGETHER == 0 else 1

    def body(i, _):
        for c in range(together):
            c = i * together + c
            one(pl.ds(pl.multiple_of(c * chunk, chunk), chunk), pl.ds(c, 1))

    jax.lax.fori_loop(0, chunks // together, body, None)


def _kda_operands_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, u_ref, w_ref,
                         qg_ref, kend_ref, aqk_ref, decay_ref, *, chunk: int,
                         chunks: int):
    def one(rows, at):
        *made, decay = _one_chunk_operands(
            q_ref[0, rows, :], k_ref[0, rows, :], v_ref[0, rows, :],
            g_ref[0, rows, :], beta_ref[0, at, :], u_ref.dtype)
        for ref, value in zip((u_ref, w_ref, qg_ref, kend_ref, aqk_ref),
                              made):
            ref[0, rows, :] = value
        decay_ref[0, at, :] = decay

    _chunks_in_turn(chunks, chunk, one)


def _kda_operands_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, du_ref,
                             dw_ref, dqg_ref, dkend_ref, daqk_ref, ddecay_ref,
                             dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *,
                             chunk: int, chunks: int):
    def one(rows, at):
        # the chunk's intermediates are made again here, in VMEM
        _, transposed = jax.vjp(
            functools.partial(_one_chunk_operands, dtype=du_ref.dtype),
            q_ref[0, rows, :], k_ref[0, rows, :], v_ref[0, rows, :],
            g_ref[0, rows, :], beta_ref[0, at, :])
        *grads, dbeta = transposed(
            (du_ref[0, rows, :], dw_ref[0, rows, :], dqg_ref[0, rows, :],
             dkend_ref[0, rows, :], daqk_ref[0, rows, :],
             ddecay_ref[0, at, :]))
        for ref, grad in zip((dq_ref, dk_ref, dv_ref, dg_ref), grads):
            ref[0, rows, :] = grad
        dbeta_ref[0, at, :] = dbeta

    _chunks_in_turn(chunks, chunk, one)


def _operands_call(kernel, name, q, v, chunk, times, moved):
    """-> (``pallas_call`` bound to the operand kernels' grid, (batch x
    head, groups of chunks), both parallel: nothing is carried; how the
    caller's (B, T', H, width) arrays go in and come out: ``lay``, the
    block spec and the shape by width, ``unlay``; the block specs of the
    state pass's (B * H, T', width) and of a value a position or a chunk,
    (B * H, chunks, width), a chunk a row). The caller's arrays are read
    and written IN PLACE as (B, T', H x width), a head's lanes by block
    index, where a head's lanes are whole tiles (no head moves ahead of
    the positions through HBM); else as (B * H, T', width), moved by XLA.
    The cost: a chunk-head is six levels of (2 chunk, dk) x (dk, chunk)
    scores, ten chunk-sized float32 products of the inverse and ``T [beta
    K e^G, beta V]`` at three or six bf16 passes, eight exponentials a
    channel a position; ``times`` the forward's, ``moved`` bytes a position
    a head."""
    B, Tp, H, dk = q.shape
    dv = v.shape[-1]
    n = Tp // chunk
    per = _per_step(n)
    rows, passes = per * chunk, 3 if v.dtype.itemsize < 4 else 6
    by_head = lambda width: pl.BlockSpec((1, rows, width),
                                         lambda b, j: (b, j, 0))
    a_chunk = lambda width: pl.BlockSpec((1, per, width),
                                         lambda b, j: (b, j, 0))
    if H == 1 or dk % 128 == dv % 128 == 0:
        lay = lambda t: t.reshape(B, Tp, -1)
        rows_of = lambda width: pl.BlockSpec(
            (1, rows, width), lambda b, j: (b // H, j, b % H))
        shape_of = lambda width: (B, Tp, H * width)
        unlay = lambda t: t.reshape(B, Tp, H, -1)
    else:
        lay = lambda t: jnp.moveaxis(t, 2, 1).reshape(B * H, Tp, -1)
        rows_of, shape_of = by_head, lambda width: (B * H, Tp, width)
        unlay = lambda t: jnp.moveaxis(t.reshape(B, H, Tp, -1), 1, 2)
    call = functools.partial(
        pl.pallas_call, functools.partial(kernel, chunk=chunk, chunks=per),
        grid=(B * H, n // per),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=int(times * B * H * n * 2 * chunk * chunk * (
                12 * dk + passes * (10 * chunk + dk + dv))),
            bytes_accessed=int(B * H * Tp * moved),
            transcendentals=int(times * B * H * Tp * 8 * dk)),
        name=name)
    return call, (lay, rows_of, shape_of, unlay), by_head, a_chunk


def _a_chunk_a_row(beta, chunk):
    """(B, T', H) -> (B * H, T' / chunk, chunk)."""
    return jnp.moveaxis(beta, 2, 1).reshape(-1, beta.shape[1] // chunk, chunk)


def _operands_kernel(q, k, v, g, beta, chunk):
    """``kda_operands_fwd``: ``chunk_operands`` of q, k, v (B, T', H, .) in
    the caller's type, g and beta float32, T' ``_whole_groups``, as one
    kernel; a chunk's intermediates never leave VMEM."""
    B, Tp, H, dk = q.shape
    dv, dtype = v.shape[-1], v.dtype
    made = (dv + 3 * dk + chunk) * dtype.itemsize
    call, (lay, rows_of, _, _), by_head, a_chunk = _operands_call(
        _kda_operands_kernel, "kda_operands_fwd", q, v, chunk, 1,
        q.dtype.itemsize * (2 * dk + dv) + 4 * dk + made)
    like = lambda width: jax.ShapeDtypeStruct((B * H, Tp, width), dtype)
    out = call(
        in_specs=[rows_of(dk), rows_of(dk), rows_of(dv), rows_of(dk),
                  a_chunk(chunk)],
        out_specs=[by_head(dv), by_head(dk), by_head(dk), by_head(dk),
                   by_head(chunk), a_chunk(dk)],
        out_shape=[like(dv), like(dk), like(dk), like(dk), like(chunk),
                   jax.ShapeDtypeStruct((B * H, Tp // chunk, dk),
                                        jnp.float32)],
    )(lay(q), lay(k), lay(v), lay(g), _a_chunk_a_row(beta, chunk))
    return dict(zip((*_ROWS, "decay"), out))


def _operands_bwd_kernel(q, k, v, g, beta, grads, chunk):
    """``kda_operands_bwd``: the cotangents of q, k, v, g and beta from
    those of the operands (as ``kda_chunk_bwd`` writes them), on the
    forward's grid; it reads the forward's INPUTS and makes a chunk's
    intermediates again in VMEM."""
    B, Tp, H, dk = q.shape
    dv = v.shape[-1]
    made = (dv + 3 * dk + chunk) * grads["u"].dtype.itemsize
    call, (lay, rows_of, shape_of, unlay), by_head, a_chunk = _operands_call(
        _kda_operands_bwd_kernel, "kda_operands_bwd", q, v, chunk, 3,
        2 * q.dtype.itemsize * (2 * dk + dv) + 8 * dk + made)
    like = lambda t: jax.ShapeDtypeStruct(shape_of(t.shape[-1]), t.dtype)
    *rows, dbeta = call(
        in_specs=[rows_of(dk), rows_of(dk), rows_of(dv), rows_of(dk),
                  a_chunk(chunk), by_head(dv), by_head(dk), by_head(dk),
                  by_head(dk), by_head(chunk), a_chunk(dk)],
        out_specs=[rows_of(dk), rows_of(dk), rows_of(dv), rows_of(dk),
                   a_chunk(chunk)],
        out_shape=[like(q), like(k), like(v), like(g),
                   jax.ShapeDtypeStruct((B * H, Tp // chunk, chunk),
                                        jnp.float32)],
    )(lay(q), lay(k), lay(v), lay(g), _a_chunk_a_row(beta, chunk),
      *(grads[name] for name in _ROWS), grads["decay"])
    return (*(unlay(t) for t in rows),
            jnp.moveaxis(dbeta.reshape(B, H, Tp), 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def operands(q, k, v, g, beta, chunk):
    """``_operands_kernel`` with a backward of its own, ``kda_operands_bwd``:
    what the rule keeps is its five inputs."""
    return _operands_kernel(q, k, v, g, beta, chunk)


operands.defvjp(
    lambda q, k, v, g, beta, chunk: (
        _operands_kernel(q, k, v, g, beta, chunk), (q, k, v, g, beta)),
    lambda chunk, res, grads: _operands_bwd_kernel(*res, grads, chunk))


# ------------------------------------------------------------ the state pass
_NN = (((1,), (0,)), ((), ()))      # a b
_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def _chunk_delta(u, w, held):
    """``u - w S_0`` of one chunk, rounded to the operands' type as the
    matmuls that take it do; ``held`` the transposed state in that type."""
    return (u.astype(jnp.float32) - jax.lax.dot_general(
        w, held, _NT, preferred_element_type=jnp.float32)).astype(w.dtype)


def _chunk_update(u, w, qg, kend, aqk, decay, state_t):
    """One chunk given the state it starts from, TRANSPOSED: ``state_t``
    (dv, dk) float32 (the decay then runs along the lanes). -> (o (chunk,
    dv) float32, the transposed state after the chunk). Matmuls take the
    operands' type (the state rounded to it), sums are float32."""
    f32 = jnp.float32
    held = state_t.astype(w.dtype)
    rounded = _chunk_delta(u, w, held)
    o = jax.lax.dot_general(qg, held, _NT, preferred_element_type=f32) \
        + jnp.dot(aqk, rounded, preferred_element_type=f32)
    state_t = state_t * decay + jax.lax.dot_general(
        rounded, kend, _TN, preferred_element_type=f32)
    return o, state_t


def _chunk_state(u, w, kend, decay, state_t):
    """``_chunk_update`` without the outputs: the state after the chunk."""
    return state_t * decay + jax.lax.dot_general(
        _chunk_delta(u, w, state_t.astype(w.dtype)), kend, _TN,
        preferred_element_type=jnp.float32)


def _chunk_backward(u, w, qg, kend, aqk, decay, state_t, do, dstate_t):
    """The transpose of ``_chunk_update`` (the module's docstring), its
    roundings taken as the identity. ``state_t`` (dv, dk) float32 the
    chunk STARTED from, ``do`` (chunk, dv) in the operands' type,
    ``dstate_t`` (dv, dk) float32 the cotangent of the state it left. ->
    (du, dw, dqg, dkend, daqk, ddecay (1, dk), the cotangent of ``state_t``),
    float32. Matmuls take the operands' type as the forward's do (the state
    and its cotangent rounded to it), sums are float32."""
    f32 = jnp.float32
    dot = functools.partial(jax.lax.dot_general, preferred_element_type=f32)
    held, dheld = state_t.astype(w.dtype), dstate_t.astype(w.dtype)
    delta = _chunk_delta(u, w, held)
    ddelta = dot(aqk, do, _TN) + dot(kend, dheld, _NT)
    rounded = ddelta.astype(w.dtype)
    dstate0 = dstate_t * decay + dot(do, qg, _TN) - dot(rounded, w, _TN)
    return (ddelta, -jnp.dot(rounded, held, preferred_element_type=f32),
            jnp.dot(do, held, preferred_element_type=f32),
            jnp.dot(delta, dheld, preferred_element_type=f32),
            dot(do, delta, _NT),
            jnp.sum(state_t * dstate_t, axis=0, keepdims=True), dstate0)


_ROWS = ("u", "w", "qg", "kend", "aqk")     # the operands a position a row


def _per_step(n):
    """Chunks a grid step walks, = a group whose end state the forward rule
    keeps (``chunk_operands`` padded to whole groups)."""
    return min(CHUNKS_PER_STEP, n)


def _state_pass_jnp(ops, state, chunk, keep=False):
    """``lax.scan`` over the chunks: the plain form of ``kda_chunk_fwd``.
    ``keep``: also the transposed state at the end of every group of
    chunks, (B * H, groups, dv, dk) float32."""
    BH, Tp, _ = ops["u"].shape
    n = Tp // chunk
    chunks = lambda t: jnp.moveaxis(
        t.reshape(BH, n, chunk, t.shape[-1]), 1, 0)
    xs = tuple(chunks(ops[name]) for name in _ROWS) \
        + (jnp.moveaxis(ops["decay"], 1, 0)[:, :, None, :],)

    def step(state_t, at):
        o, state_t = jax.vmap(_chunk_update)(*at, state_t)
        return state_t, (o, state_t) if keep else o

    state_t, out = jax.lax.scan(step, jnp.swapaxes(state, -1, -2), xs)
    o, ends = out if keep else (out, None)
    o = jnp.moveaxis(o, 0, 1).reshape(BH, Tp, -1).astype(ops["u"].dtype)
    if not keep:
        return o, jnp.swapaxes(state_t, -1, -2)
    per = _per_step(n)
    return o, jnp.swapaxes(state_t, -1, -2), \
        jnp.moveaxis(ends[per - 1::per], 0, 1)


def _state_pass_bwd_jnp(ops, starts, do, dstate_t, chunk):
    """The plain form of ``kda_chunk_bwd``: the groups last to first
    (``lax.scan``), a group's chunk states made again from ``starts`` (B * H,
    groups, dv, dk), then its chunks in reverse. ``do`` (B * H, T', dv),
    ``dstate_t`` (B * H, dv, dk) float32 -> (the cotangents of the operands
    as ``ops`` holds them, of the transposed state the pass started from)."""
    BH, Tp, _ = ops["u"].shape
    n = Tp // chunk
    per = _per_step(n)
    groups = lambda t: jnp.moveaxis(
        t.reshape(BH, n // per, per, -1, t.shape[-1]), (1, 2), (0, 1))
    xs = tuple(groups(ops[name]) for name in _ROWS) \
        + (groups(ops["decay"][:, :, None, :]),)
    do = groups(do.astype(ops["u"].dtype))

    def group(dstate_t, at):
        *rows, do, start = at
        u, w, _, kend, _, decay = rows

        def forward(state_t, at):
            return jax.vmap(_chunk_state)(*at, state_t), state_t

        _, held = jax.lax.scan(forward, start, (u, w, kend, decay))

        def backward(dstate_t, at):
            *grads, dstate_t = jax.vmap(_chunk_backward)(*at, dstate_t)
            return dstate_t, tuple(grads)

        return jax.lax.scan(backward, dstate_t, (*rows, held, do),
                            reverse=True)

    dstate_t, grads = jax.lax.scan(
        group, dstate_t, (*xs, do, jnp.moveaxis(starts, 1, 0)), reverse=True)
    flat = lambda t: jnp.moveaxis(t, (0, 1), (1, 2)).reshape(
        BH, -1, t.shape[-1])
    out = {name: flat(g).astype(ops[name].dtype)
           for name, g in zip(_ROWS, grads)}
    out["decay"] = flat(grads[5])
    return out, dstate_t


def _kda_chunk_kernel(u_ref, w_ref, qg_ref, kend_ref, aqk_ref, decay_ref,
                      s0_ref, o_ref, s_ref, *rest, chunk: int, chunks: int):
    j = pl.program_id(1)
    *ends_ref, state_sc = rest      # the group's end state, where kept

    @pl.when(j == 0)
    def _start():
        state_sc[:] = s0_ref[0]

    for c in range(chunks):                 # static: a step's chunks in turn
        rows = pl.ds(c * chunk, chunk)
        o, state_t = _chunk_update(
            u_ref[0, rows, :], w_ref[0, rows, :], qg_ref[0, rows, :],
            kend_ref[0, rows, :], aqk_ref[0, rows, :],
            decay_ref[0, pl.ds(c, 1), :], state_sc[:])
        o_ref[0, rows, :] = o.astype(o_ref.dtype)
        state_sc[:] = state_t

    if ends_ref:
        ends_ref[0][0, 0] = state_sc[:]

    @pl.when(j == pl.num_programs(1) - 1)
    def _end():
        s_ref[0] = state_sc[:]


def _state_pass_kernel(ops, state, chunk, keep=False):
    """``kda_chunk_fwd``: the grid is (batch x head, groups of chunks), the
    second axis sequential; the state lives transposed, (dv, dk) float32, in
    VMEM from a head's first chunk to its last. ``keep``: a third output,
    the transposed state at the end of every group."""
    BH, Tp, dv = ops["u"].shape
    dk = ops["w"].shape[-1]
    n = Tp // chunk
    per = _per_step(n)                      # chunk_operands padded to it
    rows = per * chunk
    block = lambda width: pl.BlockSpec((1, rows, width), lambda b, j: (b, j, 0))
    whole = lambda *shape: pl.BlockSpec((1, *shape), lambda b, j: (b, 0, 0))
    item = ops["u"].dtype.itemsize
    kept = ([pl.BlockSpec((1, 1, dv, dk), lambda b, j: (b, j, 0, 0))],
            [jax.ShapeDtypeStruct((BH, n // per, dv, dk), jnp.float32)]) \
        if keep else ([], [])
    o, state_t, *ends = pl.pallas_call(
        functools.partial(_kda_chunk_kernel, chunk=chunk, chunks=per),
        grid=(BH, n // per),
        in_specs=[block(dv), block(dk), block(dk), block(dk), block(chunk),
                  pl.BlockSpec((1, per, dk), lambda b, j: (b, j, 0)),
                  whole(dv, dk)],
        out_specs=[block(dv), whole(dv, dk)] + kept[0],
        out_shape=[jax.ShapeDtypeStruct((BH, n * chunk, dv), ops["u"].dtype),
                   jax.ShapeDtypeStruct((BH, dv, dk), jnp.float32)] + kept[1],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(BH * n * chunk * (6 * dk * dv + 2 * chunk * dv)),
            bytes_accessed=int(BH * n * chunk * item
                               * (2 * dv + 3 * dk + chunk)),
            transcendentals=0),
        name="kda_chunk_fwd",
    )(ops["u"], ops["w"], ops["qg"], ops["kend"], ops["aqk"], ops["decay"],
      jnp.swapaxes(state, -1, -2))
    return (o, jnp.swapaxes(state_t, -1, -2), *ends)


def _kda_chunk_bwd_kernel(u_ref, w_ref, qg_ref, kend_ref, aqk_ref, decay_ref,
                          start_ref, do_ref, ds_ref, du_ref, dw_ref, dqg_ref,
                          dkend_ref, daqk_ref, ddecay_ref, ds0_ref, held_sc,
                          dstate_sc, *, chunk: int, chunks: int):
    j = pl.program_id(1)                    # the groups, last to first

    @pl.when(j == 0)
    def _start():
        dstate_sc[:] = ds_ref[0]

    at = lambda c: (pl.ds(c * chunk, chunk), pl.ds(c, 1))
    state_t = start_ref[0, 0]
    for c in range(chunks):         # the states the group's chunks start from
        rows, one = at(c)
        held_sc[c] = state_t
        if c + 1 < chunks:
            state_t = _chunk_state(u_ref[0, rows, :], w_ref[0, rows, :],
                                   kend_ref[0, rows, :], decay_ref[0, one, :],
                                   state_t)
    for c in reversed(range(chunks)):
        rows, one = at(c)
        du, dw, dqg, dkend, daqk, ddecay, dstate_t = _chunk_backward(
            u_ref[0, rows, :], w_ref[0, rows, :], qg_ref[0, rows, :],
            kend_ref[0, rows, :], aqk_ref[0, rows, :], decay_ref[0, one, :],
            held_sc[c], do_ref[0, rows, :], dstate_sc[:])
        for ref, grad in ((du_ref, du), (dw_ref, dw), (dqg_ref, dqg),
                          (dkend_ref, dkend), (daqk_ref, daqk)):
            ref[0, rows, :] = grad.astype(ref.dtype)
        ddecay_ref[0, one, :] = ddecay
        dstate_sc[:] = dstate_t

    @pl.when(j == pl.num_programs(1) - 1)
    def _end():
        ds0_ref[0] = dstate_sc[:]


def _state_pass_bwd_kernel(ops, starts, do, dstate_t, chunk):
    """``kda_chunk_bwd``: the grid is (batch x head, groups of chunks LAST
    TO FIRST), the second axis sequential; ``dS`` lives transposed, (dv,
    dk) float32, in VMEM from a head's last chunk to its first, beside the
    states a group's chunks start from, made again from ``starts``."""
    BH, Tp, dv = ops["u"].shape
    dk = ops["w"].shape[-1]
    n = Tp // chunk
    per = _per_step(n)
    rows, steps = per * chunk, n // per
    back = lambda j: steps - 1 - j
    block = lambda width: pl.BlockSpec((1, rows, width),
                                       lambda b, j: (b, back(j), 0))
    whole = pl.BlockSpec((1, dv, dk), lambda b, j: (b, 0, 0))
    decay = pl.BlockSpec((1, per, dk), lambda b, j: (b, back(j), 0))
    dtype = ops["u"].dtype
    like = lambda width: jax.ShapeDtypeStruct((BH, Tp, width), dtype)
    *grads, ddecay, dstate_t = pl.pallas_call(
        functools.partial(_kda_chunk_bwd_kernel, chunk=chunk, chunks=per),
        grid=(BH, steps),
        in_specs=[block(dv), block(dk), block(dk), block(dk), block(chunk),
                  decay,
                  pl.BlockSpec((1, 1, dv, dk),
                               lambda b, j: (b, back(j), 0, 0)),
                  block(dv), whole],
        out_specs=[block(dv), block(dk), block(dk), block(dk), block(chunk),
                   decay, whole],
        out_shape=[like(dv), like(dk), like(dk), like(dk), like(chunk),
                   jax.ShapeDtypeStruct((BH, n, dk), jnp.float32),
                   jax.ShapeDtypeStruct((BH, dv, dk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((per, dv, dk), jnp.float32),
                        pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(BH * n * chunk * (18 * dk * dv + 4 * chunk * dv)),
            bytes_accessed=int(BH * n * chunk * dtype.itemsize
                               * (3 * dv + 6 * dk + 2 * chunk)),
            transcendentals=0),
        name="kda_chunk_bwd",
    )(*(ops[name] for name in _ROWS), ops["decay"], starts,
      do.astype(dtype), dstate_t)
    return {**dict(zip(_ROWS, grads)), "decay": ddecay}, dstate_t


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def state_pass(ops, state, chunk, kernel):
    """The state pass over ``chunk_operands``' ``ops`` from ``state`` (B * H,
    dk, dv) float32 -> (o (B * H, T', dv), the state after the last chunk),
    with a backward of its own (the module's docstring): the kernels where
    ``kernel``, their ``jnp`` forms otherwise."""
    return (_state_pass_kernel if kernel else _state_pass_jnp)(
        ops, state, chunk)


def _state_pass_fwd(ops, state, chunk, kernel):
    o, _, ends = (_state_pass_kernel if kernel else _state_pass_jnp)(
        ops, state, chunk, keep=True)
    # named HERE, the values the backward rule and the caller are handed: a
    # policy that keeps both names keeps all this rule makes, the state the
    # pass ends in being the last group's
    o, ends = checkpoint_name(o, SAVED_O), \
        checkpoint_name(ends, SAVED_KDA_STATES)
    return (o, jnp.swapaxes(ends[:, -1], -1, -2)), (ops, state, ends)


def _state_pass_bwd(chunk, kernel, res, cotangents):
    ops, state, ends = res
    do, dstate = cotangents
    starts = jnp.concatenate(
        [jnp.swapaxes(state, -1, -2)[:, None], ends[:, :-1]], axis=1)
    grads, dstate_t = (_state_pass_bwd_kernel if kernel
                       else _state_pass_bwd_jnp)(
        ops, starts, do, jnp.swapaxes(dstate.astype(jnp.float32), -1, -2),
        chunk)
    return grads, jnp.swapaxes(dstate_t, -1, -2)


state_pass.defvjp(_state_pass_fwd, _state_pass_bwd)


def chunked_kda(q, k, v, g, beta, state=None, chunk=CHUNK, kernel=False,
                vjp=False):
    """The chunked form over T positions. Shapes as ``recurrent_kda``; ->
    (o (B, T, H, dv) in v's type, the state (B, H, dk, dv) float32 after
    position T - 1). ``kernel``: the chunks' operands and the state pass as
    the Pallas kernels (a program for a TPU, or the interpreter in a test)
    or as their ``jnp`` forms (everywhere else). ``vjp``: both with their
    own backward (``operands``, ``state_pass``: what a gradient is taken
    through; without it autodiff walks the ``jnp`` scan and keeps every
    chunk's state). The operands exist for all T positions at once: from
    the kernel 3 dk + dv + chunk values a position a head in v's type
    (1.2 KB at 128 in bf16); from the ``jnp`` form ~45 float32 values a
    channel a position beside them (3 GB at 4,096 positions of 64 heads x
    128). A caller with a long sequence walks it in segments and hands the
    state on (``models/kda.py::mix``)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    state = jnp.zeros((B * H, dk, dv), jnp.float32) if state is None \
        else state.astype(jnp.float32).reshape(B * H, dk, dv)
    if kernel:
        to, f32 = _whole_groups(T, chunk) * chunk, jnp.float32
        ops = (operands if vjp else _operands_kernel)(
            _padded(q, to), _padded(k, to), _padded(v, to),
            _padded(g.astype(f32), to), _padded(beta.astype(f32), to), chunk)
    else:
        ops = chunk_operands(q, k, v, g, beta, chunk)
    if vjp:
        o, state = state_pass(ops, state, chunk, kernel)
    else:
        o, state = (_state_pass_kernel if kernel else _state_pass_jnp)(
            ops, state, chunk)
    return jnp.moveaxis(o[:, :T].reshape(B, H, T, dv), 1, 2), \
        state.reshape(B, H, dk, dv)


# ----------------------------------- q | k | v from the projection's output
# positions and channels (whole heads) a grid step of the preparation holds,
# positions a loop body works on (a head at a time), rows kept of what came
# before a block (one float32 tile; a convolution looks back taps - 1 <= 8)
PREP_ROWS, PREP_LANES, _PREP_CHUNK, _HALO = 256, 512, 32, 8


def prepare_qkv(p, tail, conv_w, heads, eps):
    """q, k, v of the mixer from the q | k | v projection's output ``p`` (B,
    T, 3 H dk), the ``taps - 1`` pre-activation rows before it ``tail`` (B,
    taps - 1, 3 H dk) and the taps ``conv_w`` (taps, 3 H dk): the causal
    depthwise convolution (the last tap on the current position), SiLU, then
    q and k L2-normalised a head and q times ``dk ** -0.5``; float32 inside.
    -> (q, k, v (B, T, H, dk) in p's type, the window's last ``taps - 1``
    rows: the next call's ``tail``). The definition, in ``jnp``: what runs
    off the TPU and what ``kda_prep_fwd`` / ``kda_prep_bwd`` are tested
    against."""
    B, T, _ = p.shape
    f32 = jnp.float32
    window = jnp.concatenate([tail.astype(p.dtype), p], axis=1)
    conv_w = conv_w.astype(f32)
    qkv = jax.nn.silu(sum(conv_w[j] * window[:, j:j + T].astype(f32)
                          for j in range(conv_w.shape[0]))).astype(p.dtype)
    q, k, v = (t.reshape(B, T, heads, -1) for t in jnp.split(qkv, 3, axis=-1))

    def unit(t, scale=1.0):
        t = t.astype(f32)
        return (t * (scale * jax.lax.rsqrt(jnp.sum(
            t * t, axis=-1, keepdims=True) + eps))).astype(p.dtype)

    return unit(q, q.shape[-1] ** -0.5), unit(k), v, window[:, T:]


_LANES = 128      # a vreg's lanes: the staging buffers hold columns of them


def _window_into(buf, x_ref, before_ref, tail_ref, first):
    """``buf`` (columns of 128 lanes, 8 + rows, 128) float32 <- the 8 rows
    before a block of the projection's output, then the block: the rows are
    the segment's ``tail`` (its last rows) before the ``first`` block, else
    the block before's. A column's rows are contiguous in VMEM, so a strip
    of it is a LOAD from any row: the convolution looks back without a
    rotate."""
    f32 = jnp.float32
    columns = [(n, pl.ds(n * _LANES, _LANES)) for n in range(buf.shape[0])]

    @pl.when(first)
    def _of_the_tail():
        buf[:, :_HALO, :] = tail_ref[0]

    @pl.when(jnp.logical_not(first))
    def _of_the_block_before():
        for n, lanes in columns:
            buf[n, :_HALO, :] = before_ref[0, :, lanes].astype(f32)[-_HALO:]

    for n, lanes in columns:
        buf[n, _HALO:, :] = x_ref[0, :, lanes].astype(f32)


def _looked(buf, at, rows, taps, back):
    """Every column of ``buf``, ``rows`` rows from ``at``, looked back (or
    ahead: ``back`` -1) by 0 .. taps - 1 rows: (columns, rows, 128) each."""
    return tuple(buf[:, pl.ds(at - back * s, rows), :] for s in range(taps))


def _under_the_taps(looked, w):
    """``sum_s w[taps - 1 - s] looked[s]``: the convolution (``looked``
    back: the last tap on the strip itself) or its transpose (ahead); ``w``
    (columns, taps, 128)."""
    taps = len(looked)
    out = w[:, taps - 1:] * looked[0]
    for s in range(1, taps):
        out = out + w[:, taps - 1 - s:taps - s] * looked[s]
    return out


def _sigmoid(x):
    """The logistic function through ONE transcendental (the quotient ``1 /
    (1 + e^-x)`` costs the VPU a dozen operations a value around its
    reciprocal)."""
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _head_sum(x):
    """``x`` (heads, columns a head, rows, 128): the sum over a head's
    channels, (heads, 1, rows, 1)."""
    return jnp.sum(jnp.sum(x, axis=-1, keepdims=True), axis=1, keepdims=True)


# A strip's arithmetic, values in and out, under ``jax.jit(inline=True)``:
# no program of its own (it is inlined into the kernel's body and never
# dispatched: the ``sharding/unspecified-jit`` rule is about programs), a
# trace cache: traced ONCE a variant in a process and replayed into every
# kernel body after it. A kernel's trace is Python time that a program pays in set-up
# whatever its compile cache holds (~3 s a program on the chip's host in
# the first form, which walked a head at a time: PERF.md, PR 43).
@functools.partial(jax.jit, static_argnames=("wide", "scale", "eps"),
                   inline=True)
def _strip_forward(back, w, wide, scale, eps):
    """A strip of every column of a block, (columns, rows, 128): SiLU of
    the convolution, L2 normalised over a head (``wide`` columns) and times
    ``scale`` where that is not None."""
    pre = _under_the_taps(back, w)
    a = pre * _sigmoid(pre)
    if scale is None:
        return a
    a = a.reshape(-1, wide, *a.shape[1:])
    return (a * (scale * jax.lax.rsqrt(_head_sum(a * a) + eps))).reshape(
        pre.shape)


@functools.partial(jax.jit, static_argnames=("wide", "scale", "eps"),
                   inline=True)
def _strip_backward(back, w, ct, wide, scale, eps):
    """The transpose of ``_strip_forward`` up to the pre-activation: ``ct``
    the strip's cotangent, float32 -> (the pre-activation's cotangent, the
    taps' as eight partial sums of the rows a tap (columns, 8, 128))."""
    pre = _under_the_taps(back, w)
    sig = _sigmoid(pre)
    if scale is not None:
        a = (pre * sig).reshape(-1, wide, *pre.shape[1:])
        r = jax.lax.rsqrt(_head_sum(a * a) + eps)
        unit, ct = a * r, ct.reshape(a.shape)
        ct = ((scale * r) * (ct - unit * _head_sum(ct * unit))).reshape(
            pre.shape)
    dpre = ct * (sig * (1.0 + pre * (1.0 - sig)))
    taps, rows = len(back), pre.shape[1]
    partial = []
    for tap in range(taps):
        by = dpre * back[taps - 1 - tap]
        partial.append(sum(by[:, r:r + _HALO] for r in range(
            _HALO, rows, _HALO)) + by[:, :_HALO])
    return dpre, tuple(partial)


@functools.partial(jax.jit, inline=True)
def _transposed_taps(ahead, w):
    """``_under_the_taps`` of a strip looked AHEAD: the convolution's
    transpose, traced once."""
    return _under_the_taps(ahead, w)


def _kda_prep_kernel(*refs, chunk: int, dk: int, eps: float):
    """A block of rows x a block of whole heads of each third of the
    projection's output -> q, k, v there, a strip of ``chunk`` rows of
    every column at a time."""
    xs, befores, tails, ws, outs, bufs = (refs[3 * n:3 * n + 3]
                                          for n in range(6))
    rows, lanes = xs[0].shape[1:]
    taps = ws[0].shape[1]

    for n in range(3):
        _window_into(bufs[n], xs[n], befores[n], tails[n],
                     pl.program_id(2) == 0)

    def strips(c, _):
        at = pl.multiple_of(c * chunk, chunk)
        for n, scale in enumerate((dk ** -0.5, 1.0, None)):
            made = _strip_forward(
                _looked(bufs[n], at + _HALO, chunk, taps, 1), ws[n][...],
                dk // _LANES, scale, eps).astype(outs[n].dtype)
            for col in range(lanes // _LANES):
                outs[n][0, pl.ds(at, chunk), pl.ds(col * _LANES, _LANES)] \
                    = made[col]

    jax.lax.fori_loop(0, rows // chunk, strips, None)


def _kda_prep_bwd_kernel(x_ref, before_ref, tail_ref, w_ref, dq_ref, dk_ref,
                         dv_ref, dp_ref, dtail_ref, dw_ref, buf, after, *,
                         chunk: int, dk: int, eps: float, per_third: int):
    """One block of rows x whole heads of the projection's output, the row
    blocks LAST TO FIRST: the pre-activation, SiLU and norms again in VMEM,
    then their transposes. ``after`` (columns, chunk + 8, 128) holds the
    pre-activation's cotangent of a strip and behind it of the first rows
    of the strip (and block) that follows, which the convolution's
    transpose looks ahead to; ``dw_ref`` (columns, 8 taps, 128) gathers
    the taps' cotangent over the row blocks, eight partial sums a tap."""
    f32 = jnp.float32
    j, i = pl.program_id(1), pl.program_id(2)
    first = i == pl.num_programs(2) - 1         # the segment's first rows
    rows, lanes = x_ref.shape[1:]
    taps = w_ref.shape[1]
    columns = [pl.ds(col * _LANES, _LANES) for col in range(lanes // _LANES)]
    _window_into(buf, x_ref, before_ref, tail_ref, first)

    @pl.when(i == 0)
    def _start():
        after[...] = jnp.zeros_like(after)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def ahead(dpre, last=chunk):
        """The projection's cotangent over the ``last`` rows of a strip;
        the strip's ``dpre`` goes ahead of what ``after`` held of the strip
        that follows."""
        after[:, chunk:, :] = after[:, :_HALO, :]
        after[:, :chunk, :] = dpre
        return _transposed_taps(
            _looked(after, chunk - last, last, taps, -1), w_ref[...])

    def third(ct_ref, scale):
        def strips(c, _):
            at = pl.multiple_of((rows // chunk - 1 - c) * chunk, chunk)
            dpre, partial = _strip_backward(
                _looked(buf, at + _HALO, chunk, taps, 1), w_ref[...],
                jnp.stack([ct_ref[0, pl.ds(at, chunk), cols].astype(f32)
                           for cols in columns]),
                dk // _LANES, scale, eps)
            for tap, by in enumerate(partial):
                dw_ref[0, :, pl.ds(_HALO * tap, _HALO), :] += by
            dx = ahead(dpre).astype(dp_ref.dtype)
            for col, cols in enumerate(columns):
                dp_ref[0, pl.ds(at, chunk), cols] = dx[col]

        jax.lax.fori_loop(0, rows // chunk, strips, None)

    for n, (ct_ref, scale) in enumerate(
            ((dq_ref, dk ** -0.5), (dk_ref, 1.0), (dv_ref, None))):
        pl.when(j // per_third == n)(functools.partial(third, ct_ref, scale))

    @pl.when(first)
    def _the_tails_share():
        # the tail's rows have no output of their own: what the segment's
        # first rows hand back
        dtail_ref[0] = ahead(jnp.zeros((len(columns), chunk, _LANES), f32),
                             _HALO)


def _prep_shapes(p, heads):
    """-> (a head's size, the rows and lanes of a block, T padded to whole
    blocks of rows)."""
    B, T, ch = p.shape
    dk = ch // (3 * heads)
    rows = min(PREP_ROWS, -(-T // _PREP_CHUNK) * _PREP_CHUNK)
    lanes = dk * max(n for n in range(1, max(1, PREP_LANES // dk) + 1)
                     if heads % n == 0)
    return dk, rows, lanes, -(-T // rows) * rows


def _by_column(t):
    """(.., n, channels) -> (.., channels / 128, n, 128): what a kernel
    holds a block of as (columns, n, 128)."""
    *lead, n, ch = t.shape
    return jnp.moveaxis(t.reshape(*lead, n, ch // _LANES, _LANES), -2, -3)


def _by_channel(t):
    """``_by_column`` back."""
    *lead, columns, n, _ = t.shape
    return jnp.moveaxis(t, -3, -2).reshape(*lead, n, columns * _LANES)


def _tail_rows(tail):
    """(B, taps - 1, ch) -> (B, ch / 128, 8, 128) float32, the tail its
    LAST rows."""
    return _by_column(jnp.pad(tail.astype(jnp.float32), (
        (0, 0), (_HALO - tail.shape[1], 0), (0, 0))))


def _new_tail(p, tail):
    """The last rows of ``tail`` then ``p``, as many as ``tail`` has."""
    T, keep = p.shape[1], tail.shape[1]
    return p[:, T - keep:] if T >= keep else jnp.concatenate(
        [tail[:, T:], p], axis=1)


def _prep_kernel(p, tail, conv_w, heads, eps):
    """``kda_prep_fwd``: ``prepare_qkv`` as one kernel. The grid is (batch,
    blocks of whole heads, blocks of rows), all parallel; a step reads its
    block of each third of ``p`` IN PLACE (three block specs over the one
    array) and the 16 rows before it (no window of T + taps - 1 rows is
    written), and writes q, k, v as ``(B, T, H dk)`` rows, what
    ``kda_operands_fwd`` reads in place. -> what ``prepare_qkv`` gives."""
    return _prep_call(p, tail, conv_w, heads, eps, *_prep_shapes(p, heads))


@functools.partial(jax.jit, inline=True, static_argnames=(
    "heads", "eps", "dk", "rows", "lanes", "Tp"))
def _prep_call(p, tail, conv_w, heads, eps, dk, rows, lanes, Tp):
    # jitted with ``inline``: one trace of the kernel's body a shape a
    # process (Solar's five prefill programs hold the same segment)
    B, T, ch = p.shape
    per, cols, taps, f32 = ch // 3 // lanes, lanes // _LANES, \
        conv_w.shape[0], jnp.float32
    assert taps - 1 <= _HALO and rows % 16 == 0, (conv_w.shape, rows)
    # a block of each third of the channels: the same rows, ``per`` blocks
    # of lanes apart
    thirds = lambda block, where: [
        pl.BlockSpec(block, lambda b, j, i, n=n: where(b, i, n * per + j))
        for n in range(3)]
    padded = _padded(p, Tp)
    q, k, v = pl.pallas_call(
        functools.partial(_kda_prep_kernel, chunk=_PREP_CHUNK, dk=dk, eps=eps),
        grid=(B, per, Tp // rows),
        in_specs=thirds((1, rows, lanes), lambda b, i, c: (b, i, c))
        + thirds((1, 16, lanes), lambda b, i, c: (
            b, jnp.maximum(i * (rows // 16) - 1, 0), c))
        + thirds((1, cols, _HALO, _LANES), lambda b, i, c: (b, c, 0, 0))
        + thirds((cols, taps, _LANES), lambda b, i, c: (c, 0, 0)),
        out_specs=[pl.BlockSpec((1, rows, lanes), lambda b, j, i: (b, i, j))
                   ] * 3,
        out_shape=[jax.ShapeDtypeStruct((B, Tp, ch // 3), p.dtype)] * 3,
        scratch_shapes=[pltpu.VMEM((cols, _HALO + rows, _LANES), f32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=int(B * Tp * ch * 16), transcendentals=int(B * Tp * ch),
            bytes_accessed=int(B * Tp * ch * 2 * p.dtype.itemsize)),
        name="kda_prep_fwd",
    )(*[padded] * 6, *[_tail_rows(tail)] * 3,
      *[_by_column(conv_w.astype(f32))] * 3)
    return (*(t[:, :T].reshape(B, T, heads, dk) for t in (q, k, v)),
            _new_tail(p, tail))


def _prep_bwd_kernel(p, tail, conv_w, cts, heads, eps):
    """``kda_prep_bwd``: the cotangents of ``p`` (in its type), ``tail`` and
    ``conv_w`` (float32) from those of q, k, v (B, T, H, dk). The grid is
    (batch, blocks of whole heads over ALL 3 H dk channels, blocks of rows
    last to first), the last axis sequential: a step is of one third, reads
    that third's cotangent (the two others' block specs stay where they
    are, so nothing of them moves) and writes d``p`` in place as (B, T, 3 H
    dk); the taps' cotangent comes out as eight partial sums a tap a batch
    row, which the caller adds."""
    return _prep_bwd_call(p, tail, conv_w, tuple(cts), heads, eps,
                          *_prep_shapes(p, heads))


@functools.partial(jax.jit, inline=True, static_argnames=(
    "heads", "eps", "dk", "rows", "lanes", "Tp"))
def _prep_bwd_call(p, tail, conv_w, cts, heads, eps, dk, rows, lanes, Tp):
    B, T, ch = p.shape
    per, steps, taps, cols, f32 = ch // 3 // lanes, Tp // rows, \
        conv_w.shape[0], lanes // _LANES, jnp.float32
    back = lambda i: steps - 1 - i
    padded = _padded(p, Tp)

    def of_third(n):
        mine = lambda j: j // per == n
        return pl.BlockSpec((1, rows, lanes), lambda b, j, i: (
            b, jnp.where(mine(j), back(i), 0), jnp.where(mine(j), j % per, 0)))

    by_column = lambda n: pl.BlockSpec((1, cols, n, _LANES),
                                       lambda b, j, i: (b, j, 0, 0))
    dp, dtail, dw = pl.pallas_call(
        functools.partial(_kda_prep_bwd_kernel, chunk=_PREP_CHUNK, dk=dk,
                          eps=eps, per_third=per),
        grid=(B, 3 * per, steps),
        in_specs=[
            pl.BlockSpec((1, rows, lanes), lambda b, j, i: (b, back(i), j)),
            pl.BlockSpec((1, 16, lanes), lambda b, j, i: (
                b, jnp.maximum(back(i) * (rows // 16) - 1, 0), j)),
            by_column(_HALO),
            pl.BlockSpec((cols, taps, _LANES), lambda b, j, i: (j, 0, 0)),
            of_third(0), of_third(1), of_third(2)],
        out_specs=[
            pl.BlockSpec((1, rows, lanes), lambda b, j, i: (b, back(i), j)),
            by_column(_HALO), by_column(_HALO * taps)],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, ch), p.dtype),
                   jax.ShapeDtypeStruct((B, ch // _LANES, _HALO, _LANES), f32),
                   jax.ShapeDtypeStruct(
                       (B, ch // _LANES, _HALO * taps, _LANES), f32)],
        scratch_shapes=[
            pltpu.VMEM((cols, _HALO + rows, _LANES), f32),
            pltpu.VMEM((cols, _PREP_CHUNK + _HALO, _LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(B * Tp * ch * 48), transcendentals=int(B * Tp * ch),
            bytes_accessed=int(B * Tp * ch * 3 * p.dtype.itemsize)),
        name="kda_prep_bwd",
    )(padded, padded, _tail_rows(tail), _by_column(conv_w.astype(f32)),
      *(_padded(t.reshape(B, T, -1), Tp) for t in cts))
    dw = dw.reshape(B, ch // _LANES, taps, _HALO, _LANES).sum((0, 3))
    return (dp[:, :T],
            _by_channel(dtail)[:, _HALO - tail.shape[1]:].astype(tail.dtype),
            _by_channel(dw).astype(conv_w.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def prepare(p, tail, conv_w, heads, eps):
    """``prepare_qkv`` through ``_prep_kernel``, with a backward of its
    own, ``kda_prep_bwd``: what the rule keeps is its three inputs."""
    return _prep_kernel(p, tail, conv_w, heads, eps)


def _prepare_bwd(heads, eps, res, cts):
    p, tail, conv_w = res
    *cts, dnew = cts
    dp, dtail, dw = _prep_bwd_kernel(p, tail, conv_w, cts, heads, eps)
    # the new tail's cotangent goes to the rows it was cut from, in place
    T, keep = p.shape[1], tail.shape[1]
    if T >= keep:
        return dp.at[:, T - keep:].add(dnew.astype(dp.dtype)), dtail, dw
    return (dp + dnew[:, keep - T:].astype(dp.dtype),
            dtail.at[:, T:].add(dnew[:, :keep - T].astype(dtail.dtype)), dw)


prepare.defvjp(
    lambda p, tail, conv_w, heads, eps: (
        prepare(p, tail, conv_w, heads, eps), (p, tail, conv_w)),
    _prepare_bwd)
