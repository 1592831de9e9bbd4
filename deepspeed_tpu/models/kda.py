"""The KDA mixer of a hybrid block (Kimi Delta Attention: a gated delta rule
with a per-channel decay; ``ops/pallas/kda.py`` has the recurrence, its
chunked form and the kernel). ``models/llama.py`` holds the block around it:
a block whose leaves include ``kda_qkv_w`` mixes with this, one that holds
``q_w`` with softmax attention.

Per position, on the block's normed input ``h`` (d), for ``H`` heads of
``dk = dv = kda_head_dim``:

* ``[q | k | v] = SiLU(conv(h kda_qkv_w))``: one projection d -> 3 H dk (the
  published q, k, v projections side by side: a loader's concatenation),
  then a causal depthwise convolution over the last ``kda_conv`` positions
  (``kda_conv_w`` (taps, 3 H dk), the last tap on the current position, no
  bias); q and k L2-normalised per head, q scaled by ``dk^-1/2``;
* the log-decay PER CHANNEL, ``g = -exp(kda_a_log[head]) * softplus(
  (h kda_f_a_w) kda_f_b_w + kda_dt_bias)`` (low rank d -> dk -> H dk), in
  float32;
* ``beta = 2 sigmoid(h kda_b_w)`` (d -> H): the factor 2 admits negative
  eigenvalues of the state's transition;
* the state's recurrence (``ops/pallas/kda.py``) -> ``o`` (H, dv);
* ``RMSNorm_head(o; kda_o_norm_g) * sigmoid((h kda_g_a_w) kda_g_b_w)``,
  which the block's ``o_w`` (H dv -> d) takes.

What a sequence keeps between calls is NOT a row a position: the state ``S``
(H, dk, dv) float32 and the last ``kda_conv - 1`` pre-activation rows of the
projection (the convolution's window), whatever the length.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

L2_EPS = 1e-6
# positions of a prompt the mixer handles at once (``mix``)
SEGMENT = 2048
# the cache leaves a sequence keeps of its KDA layers (``init_state``)
STATE_LEAVES = ("kda_state", "kda_conv")


def widths(c):
    """(heads, a head's size, channels of the q | k | v projection)."""
    return c.kda_heads, c.kda_head_dim, 3 * c.kda_heads * c.kda_head_dim


def init_leaves(c, key, l: int, proj_scale: float):
    """``l`` KDA mixers, stacked. Projections as ``models/llama.py`` draws
    them (normal, std 0.02; ``o_w`` residual-scaled); the convolution's taps
    U(-1/2, 1/2) (bound 1 / sqrt(taps)); ``kda_a_log`` = log U(1, 16) and
    ``kda_dt_bias`` the inverse softplus of dt ~ logU(1e-3, 1e-1) — the
    gated-delta family's convention, so per-step decays lie in about
    [e^-1.6, e^-0.001] and are NOT all 1."""
    h, dk, ch = widths(c)
    d, s = c.n_embd, 0.02
    keys = jax.random.split(key, 10)
    norm = lambda k, shape, scale=s: \
        jax.random.normal(k, shape, c.param_dtype) * scale
    unif = lambda k, shape, lo, hi: jax.random.uniform(
        k, shape, jnp.float32, lo, hi)
    dt = jnp.exp(unif(keys[8], (l, h * dk), np.log(1e-3), np.log(1e-1)))
    return {
        "kda_qkv_w": norm(keys[0], (l, d, ch)),
        "kda_conv_w": unif(keys[1], (l, c.kda_conv, ch), -0.5, 0.5
                           ).astype(c.param_dtype),
        "kda_f_a_w": norm(keys[2], (l, d, dk)),
        "kda_f_b_w": norm(keys[3], (l, dk, h * dk)),
        "kda_b_w": norm(keys[4], (l, d, h)),
        "kda_g_a_w": norm(keys[5], (l, d, dk)),
        "kda_g_b_w": norm(keys[6], (l, dk, h * dk)),
        "kda_a_log": jnp.log(unif(keys[7], (l, h), 1.0, 16.0)
                             ).astype(c.param_dtype),
        "kda_dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(c.param_dtype),
        "kda_o_norm_g": jnp.ones((l, dk), c.param_dtype),
        "o_w": norm(keys[9], (l, h * dk, d), proj_scale)}


def leaf_specs():
    """Replicated: the state's heads under tensor parallelism are open (the
    projection's q | k | v columns and the convolution's window would have to
    be cut by head together)."""
    rep = lambda rank: P(*([None] * rank))
    return {"kda_qkv_w": rep(3), "kda_conv_w": rep(3), "kda_f_a_w": rep(3),
            "kda_f_b_w": rep(3), "kda_b_w": rep(3), "kda_g_a_w": rep(3),
            "kda_g_b_w": rep(3), "kda_a_log": rep(2), "kda_dt_bias": rep(2),
            "kda_o_norm_g": rep(2), "o_w": rep(3)}


def num_params(c) -> int:
    """One KDA mixer's parameters (137.7 M at d 4096, 64 heads x 128)."""
    h, dk, ch = widths(c)
    d = c.n_embd
    return d * ch + c.kda_conv * ch + 2 * (d * dk + dk * h * dk) + d * h \
        + h + h * dk + dk + h * dk * d


def init_state(c, n_layer: int, batch_size: int):
    """The two leaves a sequence keeps of its KDA layers, whatever its
    length: ``kda_state`` (L, B, H, dk, dv) float32 and ``kda_conv`` (L, B,
    taps - 1, 3 H dk), the projection's last pre-activation rows."""
    h, dk, ch = widths(c)
    return {"kda_state": jnp.zeros((n_layer, batch_size, h, dk, dk),
                                   jnp.float32),
            "kda_conv": jnp.zeros((n_layer, batch_size, c.kda_conv - 1, ch),
                                  c.dtype)}


def state_specs():
    return {"kda_state": P(), "kda_conv": P()}


def mix(c, h, blk, tail, state, differentiable=False):
    """The mixer over h (B, T, d), the block's normed input, continuing a
    sequence whose convolution window is ``tail`` (B, taps - 1, 3 H dk) and
    whose state is ``state`` (B, H, dk, dv) float32 (zeros: a new sequence).
    -> (the gated, normed heads (B, T, H dv) for ``o_w``, the new tail, the
    new state). A sequence longer than ``SEGMENT`` positions is walked a
    segment at a time (``lax.scan``, tail and state handed on, then what is
    left over): the mixer's temporaries — the projection, the gates in
    float32 (q, k, v come from ``kda_prep_fwd`` in the caller's type; off
    the TPU the convolution's float32 passes too) and the chunked form's
    operands (74 KB a position at 64 heads x 128 from
    ``kda_operands_fwd``; ~1 MB through the ``jnp`` form off the TPU) —
    are a segment's and not the sequence's, for one more read of its
    weights a segment. ``differentiable`` (the trunk under
    ``loss``): the operands and the state pass take their own backward
    (``common.kda_attention``) and each segment is a ``jax.checkpoint`` that
    keeps what remat ``'attn'`` keeps of a block, so the backward holds a
    segment's temporaries too: it makes a segment's operands again from the
    segment's input (``kda_operands_fwd`` a second time, which keeps
    nothing but its inputs), never the state pass."""
    B, T, _ = h.shape
    n = T // SEGMENT
    one = functools.partial(_mix_segment, c, differentiable=differentiable)
    if T <= SEGMENT:
        return one(h, blk, tail, state)
    if differentiable:
        from deepspeed_tpu.models.common import SAVED_BY_ATTN

        one = jax.checkpoint(
            one, policy=jax.checkpoint_policies.save_only_these_names(
                *SAVED_BY_ATTN))

    def segment(carry, hs):
        out, *carry = one(hs, blk, *carry)
        return tuple(carry), out

    (tail, state), out = jax.lax.scan(
        segment, (tail, state), jnp.moveaxis(
            h[:, :n * SEGMENT].reshape(B, n, SEGMENT, -1), 1, 0))
    out = jnp.moveaxis(out, 0, 1).reshape(B, n * SEGMENT, -1)
    if T % SEGMENT:
        rest, tail, state = one(h[:, n * SEGMENT:], blk, tail, state)
        out = jnp.concatenate([out, rest], axis=1)
    return out, tail, state


def _mix_segment(c, h, blk, tail, state, differentiable=False):
    """``mix`` over positions that are handled at once. One position
    (decode) runs the recurrence itself; more run the chunked form
    (``common.kda_attention``) behind ``common.kda_qkv``: the kernels in a
    program for a TPU, each with its own backward where
    ``differentiable``."""
    from deepspeed_tpu.models.common import kda_attention, kda_qkv
    from deepspeed_tpu.ops.pallas.kda import kda_step
    from deepspeed_tpu.telemetry.scopes import scope

    H, dk, ch = widths(c)
    B, T, _ = h.shape
    f32 = jnp.float32
    hd = h.astype(c.dtype)
    low = lambda a, b: (hd @ blk[a].astype(c.dtype)) @ blk[b].astype(c.dtype)
    with scope("kda/qkv"):
        q, k, v, tail = kda_qkv(
            hd @ blk["kda_qkv_w"].astype(c.dtype), tail.astype(c.dtype),
            blk["kda_conv_w"].astype(f32), H, L2_EPS, differentiable)
        g = -jnp.exp(blk["kda_a_log"].astype(f32))[:, None] * jax.nn.softplus(
            low("kda_f_a_w", "kda_f_b_w").astype(f32).reshape(B, T, H, dk)
            + blk["kda_dt_bias"].astype(f32).reshape(H, dk))
        beta = 2.0 * jax.nn.sigmoid((hd @ blk["kda_b_w"].astype(c.dtype)
                                     ).astype(f32))
    with scope("kda/core"):
        if T == 1:
            o, state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], state)
            o = o[:, None]
        else:
            o, state = kda_attention(q, k, v, g, beta, state, differentiable)
    with scope("kda/out"):
        o = o.astype(f32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + c.rms_norm_eps) \
            * blk["kda_o_norm_g"].astype(f32)
        gate = jax.nn.sigmoid(low("kda_g_a_w", "kda_g_b_w").astype(f32))
        return (o.reshape(B, T, H * dk) * gate).astype(c.dtype), tail, state
