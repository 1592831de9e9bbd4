"""Flash attention — Pallas TPU kernel (fwd + bwd).

The TPU-native replacement for the reference's fused attention CUDA kernels
(csrc/transformer/softmax_kernels.cu:701 and the inference softmax_context path
csrc/transformer/inference/pt_binding.cpp) and its Triton block-sparse
attention (deepspeed/ops/sparse_attention/): one streaming-softmax kernel that
never materializes the (T, T) score matrix, tiled to the MXU (128-multiple
blocks), with a recompute-based backward.

Algorithm: standard flash attention v2 online softmax —
  m_new = max(m, rowmax(S));  P = exp(S - m_new)
  l = l * exp(m - m_new) + rowsum(P);  acc = acc * exp(m - m_new) + P @ V
Backward recomputes P from the saved logsumexp:
  P = exp(S - lse); dV = Pᵀ dO; dS = P ∘ (dO Vᵀ - Δ); dQ = dS K; dK = dSᵀ Q
with Δ = rowsum(dO ∘ O) computed outside the kernel.

Causal execution (the perf-critical path for LM training):

* **Triangular grid** — when ``block_q == block_k``, the (qi, ki) iteration
  space is the lower block-triangle ONLY, flattened to a 1-D grid whose
  block coordinates are looked up from scalar-prefetch arrays
  (``pltpu.PrefetchScalarGridSpec``). Above-diagonal blocks are never
  fetched or executed, so causal costs ~half of non-causal in both DMA and
  grid steps — a ``pl.when`` skip alone saves neither (the pipeline still
  pays the block DMA).
* **Diagonal-only masking** — interior blocks (entirely below the diagonal)
  run a mask-free softmax block; only blocks crossing the diagonal pay the
  iota/compare/select VPU passes. Flash attention at small head_dim is
  VPU-bound on TPU (softmax ops ~O(T²) on the 8×128 VPU vs matmul flops
  O(T²·D) on the MXU), so shaving VPU passes is worth more than it looks.

Layout: (B, T, H, D) in/out (matches deepspeed_tpu.models); internally
(B·H, T, D). v may have a head size of its own (latent attention: q.k at 192
columns, v at 128): the FORWARD kernels take the value width from v — the
accumulator, the output and the P @ V pass are that wide, nothing is padded.
The backward kernels take one width and refuse another for v: no model
trains through latent attention here yet. The per-row statistics that pass
between the kernels, the log-sum-exp and the backward's delta, are (B·H, 1,
T) float32, lane-dense: a kernel transposes a query block's column in VMEM
(``_row``, ``_col``), since a (B·H, T, 1) array pads the 1 to 128 lanes in
HBM — 128 x the bytes for the kernel to write, for XLA to copy and keep.
So a q block is a multiple of 128 or the whole length (``flash_supports``;
a caller takes its einsum path for another, as ``local_causal_attention``
does). The block-sparse kernels, whose tile is the layout's, keep a row a
block instead: (B·H·n, 1, block).

Every ``pallas_call`` carries a ``name`` (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``; ``sparse_`` before each for the block-sparse kernels).
That name is the last scope of the Mosaic call's ``op_name`` and so the name
of its HLO instruction (``%flash_fwd.3 = ... custom-call(...)``), which is
what an op event in a device profile is called — whatever wraps the call
(``checkpoint``, ``shard_map``).

Under activation checkpointing the forward kernel runs ONCE a call site:
the custom VJP's forward rule (``_attention_vjp``) names the two residuals
only the kernel can produce, ``o`` and the log-sum-exp, and remat ``'attn'``
(``models/common.py::remat_wrap``) saves those names, so the recompute
inside the backward holds q, k, v (the caller's matmuls) and no
``flash_fwd``. ``'full'`` saves nothing and runs the forward again.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import SAVED_LSE, SAVED_O

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


# a q block is also the LANE dimension of the blocks the log-sum-exp and
# delta pass between the kernels in, (1, 1, block) of (B*H, 1, T): Mosaic
# takes a multiple of 128 there, or the whole length. Causal lengths one
# block cannot hold are padded to a multiple of it.
_LANES = 128


def _pick_block(t: int, preferred: int) -> int:
    b = min(preferred, t)
    while t % b:
        b //= 2
    return max(b, 1)


def _tiles(t: int, preferred: int, multiple: int) -> bool:
    """Whether Mosaic takes ``_pick_block``'s block for length ``t``: a
    multiple of ``multiple`` or the whole array. 8 for a k/v block, the rows
    of a (block, D) tile; ``_LANES`` for a q block (above)."""
    b = _pick_block(t, preferred)
    return b == t or b % multiple == 0


def _padded_len(t: int, preferred: int) -> int:
    """The length causal self-attention runs the kernels at: ``t`` while one
    block holds it or it tiles in blocks of whole 128s, else the next
    multiple of 128 — any prompt length lowers, and none degrades to the
    8-row blocks an odd multiple of 8 (T=1000) would halve down to."""
    if _tiles(t, preferred, _LANES):
        return t
    return -(-t // _LANES) * _LANES


def flash_supports(t_q: int, t_k: int, causal: bool,
                   block_q: int = None, block_k: int = None) -> bool:
    """Whether :func:`flash_attention` can tile these lengths. Causal
    self-attention can at the default blocks, whatever the length (it pads;
    see ``_padded_len``); the other forms — which have no mask to hide pad
    keys behind — only at lengths that tile as they are: q in blocks of
    whole 128s (or one block), k in whole 8s. 576 = 9 x 64 does not, nor
    does any longer length in blocks of 64."""
    block_q = block_q or DEFAULT_BLOCK_Q
    block_k = block_k or DEFAULT_BLOCK_K
    if causal and t_q == t_k:
        t_q = t_k = _padded_len(t_q, min(block_q, block_k))
    return _tiles(t_q, block_q, _LANES) and _tiles(t_k, block_k, 8)


def _causal_pairs(nq: int):
    """Lower-triangle block pairs, row-major (ki ascending within each qi)."""
    qi = np.concatenate([np.full(i + 1, i, np.int32) for i in range(nq)])
    ki = np.concatenate([np.arange(i + 1, dtype=np.int32) for i in range(nq)])
    return qi, ki


def _causal_pairs_colmajor(nq: int):
    """Lower-triangle block pairs, column-major (qi ascending within each ki)
    — the dkv iteration order: each ki row accumulates over qi = ki..nq-1."""
    ki = np.concatenate([np.full(nq - i, i, np.int32) for i in range(nq)])
    qi = np.concatenate([np.arange(i, nq, dtype=np.int32) for i in range(nq)])
    return ki, qi


def _online_softmax_block(q, k, v, acc_sc, m_sc, l_sc, scale, mask_rc=None):
    """One FA2 streaming-softmax block update. ``mask_rc`` = (rows, cols)
    global index iotas when the block crosses the diagonal, else None
    (interior blocks skip the mask's VPU passes entirely)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * scale
    if mask_rc is not None:
        rows, cols = mask_rc
        s = jnp.where(rows >= cols, s, NEG_INF)
    m_prev = m_sc[:, :1]                       # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)             # (bq, 1)
    l_sc[:] = jnp.broadcast_to(l_sc[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
                               l_sc.shape)
    acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)


def _block_iotas(block_q, block_k, qi, ki):
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + qi * block_q
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + ki * block_k
    return rows, cols


def _row(x):
    """(n, 128) with every lane of a row equal -> (1, n), lane-dense: how a
    per-row statistic (the log-sum-exp) leaves a kernel. A (n, 1) output
    pads the 1 to 128 lanes in HBM: 128 x the bytes, written by the kernel
    and read by whatever takes it next."""
    return x.T[:1]


def _col(row):
    """(1, n) lane-dense -> (n, 1): a per-row statistic (log-sum-exp, delta)
    as the column a (n, block_k) score block subtracts."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


def _write_out(o_ref, lse_ref, acc_sc, m_sc, l_sc):
    """Write a query block's output and log-sum-exp from the running
    accumulator, max and sum (the last two lane-broadcast, (rows, 128))."""
    l_safe = jnp.where(l_sc[:] == 0.0, 1.0, l_sc[:])
    o_ref[0] = (acc_sc[:] / l_safe[:, :1]).astype(o_ref.dtype)
    lse_ref[0] = _row(m_sc[:] + jnp.log(l_safe))


def _causal_dispatch(qi, ki, block_q, block_k, compute):
    """Rectangular-grid causal dispatch shared by fwd/dq/dkv kernels:
    run ``compute(mask_rc)`` mask-free on blocks fully below the diagonal,
    with the iota mask on blocks the diagonal crosses, and not at all on
    blocks fully above it."""
    interior = ki * block_k + block_k - 1 <= qi * block_q
    crosses = (ki * block_k < (qi + 1) * block_q) & jnp.logical_not(interior)

    @pl.when(interior)
    def _interior():
        compute(None)

    @pl.when(crosses)
    def _diag():
        compute(_block_iotas(block_q, block_k, qi, ki))


# ------------------------------------------------- forward (causal, tri-grid)
def _fwd_tri_kernel(qi_arr, ki_arr, q_ref, k_ref, v_ref, o_ref, lse_ref,
                    acc_sc, m_sc, l_sc, *, scale: float, block: int):
    f = pl.program_id(1)
    qi = qi_arr[f]
    ki = ki_arr[f]

    @pl.when(ki == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    @pl.when(ki < qi)
    def _interior():                               # fully below diagonal
        _online_softmax_block(q_ref[0], k_ref[0], v_ref[0],
                              acc_sc, m_sc, l_sc, scale)

    @pl.when(ki == qi)
    def _diagonal():                               # crosses the diagonal
        _online_softmax_block(q_ref[0], k_ref[0], v_ref[0],
                              acc_sc, m_sc, l_sc, scale,
                              mask_rc=_block_iotas(block, block, qi, ki))
        # last block of this row: write out
        _write_out(o_ref, lse_ref, acc_sc, m_sc, l_sc)


# --------------------------------------------- forward (rectangular fallback)
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc,
                *, scale: float, causal: bool, block_q: int, block_k: int, num_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    if causal:
        _causal_dispatch(qi, ki, block_q, block_k,
                         lambda mask_rc: _online_softmax_block(
                             q_ref[0], k_ref[0], v_ref[0],
                             acc_sc, m_sc, l_sc, scale, mask_rc=mask_rc))
    else:
        _online_softmax_block(q_ref[0], k_ref[0], v_ref[0],
                              acc_sc, m_sc, l_sc, scale)

    @pl.when(ki == num_k - 1)
    def _finalize():
        _write_out(o_ref, lse_ref, acc_sc, m_sc, l_sc)


def _tri_min_blocks() -> int:
    """Min row blocks before the triangular grid pays for its bookkeeping
    (default 4 = 37.5%+ of blocks skipped; DS_TPU_FLASH_TRI_MIN=2 enables
    it at nq=2 for experiments — measured slower on v5e at GPT-2 shapes)."""
    import os

    return int(os.environ.get("DS_TPU_FLASH_TRI_MIN", "4"))


def _use_tri(causal, t_q, t_k, bq, bk) -> bool:
    """The triangular grid skips (nq-1)/2nq of the blocks — worth its
    bookkeeping only with ≥_tri_min_blocks() row blocks. Below that a
    rectangular grid with a double-width k block measures faster (fewer,
    larger cells)."""
    return causal and t_q == t_k and bq == bk and t_q // bq >= _tri_min_blocks()


def _flash_forward(q, k, v, scale, causal, block_q, block_k):
    bh, t_q, d = q.shape
    t_k, dv = k.shape[1], v.shape[2]
    bq = _pick_block(t_q, block_q)
    bk = _pick_block(t_k, block_k)
    if causal and t_q == t_k and bq == bk and t_q // bq < _tri_min_blocks():
        bk = _pick_block(t_k, 2 * bq)       # short-seq rect: wider k blocks
    nq, nk = t_q // bq, t_k // bk

    out_shapes = (jax.ShapeDtypeStruct((bh, t_q, dv), q.dtype),
                  jax.ShapeDtypeStruct((bh, 1, t_q), jnp.float32))
    scratch = [pltpu.VMEM((bq, dv), jnp.float32),
               pltpu.VMEM((bq, 128), jnp.float32),
               pltpu.VMEM((bq, 128), jnp.float32)]

    if _use_tri(causal, t_q, t_k, bq, bk):
        qi_arr, ki_arr = _causal_pairs(nq)
        o, lse = pl.pallas_call(
            functools.partial(_fwd_tri_kernel, scale=scale, block=bq),
            name="flash_fwd",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(bh, len(qi_arr)),
                in_specs=[
                    pl.BlockSpec((1, bq, d), lambda b, f, qa, ka: (b, qa[f], 0)),
                    pl.BlockSpec((1, bk, d), lambda b, f, qa, ka: (b, ka[f], 0)),
                    pl.BlockSpec((1, bk, dv), lambda b, f, qa, ka: (b, ka[f], 0)),
                ],
                out_specs=(
                    pl.BlockSpec((1, bq, dv), lambda b, f, qa, ka: (b, qa[f], 0)),
                    pl.BlockSpec((1, 1, bq), lambda b, f, qa, ka: (b, 0, qa[f])),
                ),
                scratch_shapes=scratch,
            ),
            out_shape=out_shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            cost_estimate=pl.CostEstimate(
                flops=int(bh * t_q * t_k * (d + dv)),   # causal: half the blocks run
                bytes_accessed=int((q.size + k.size + 2 * v.size) * q.dtype.itemsize),
                transcendentals=int(bh * t_q * t_k // 2)),
        )(jnp.asarray(qi_arr), jnp.asarray(ki_arr), q, k, v)
        return o, lse

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, num_k=nk)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ),
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * bh * t_q * t_k * (d + dv) * (0.5 if causal else 1.0)),
            bytes_accessed=int((q.size + k.size + 2 * v.size) * q.dtype.itemsize),
            transcendentals=int(bh * t_q * t_k)),
    )(q, k, v)
    return o, lse


# -------------------------------------------------------------------- backward
def _bwd_p_ds(q, k, v, do, lse, delta, scale, mask_rc=None):
    """Recompute P and dS for one block (shared by dq and dkv kernels).
    ``lse`` and ``delta`` arrive as the query block's lane-dense rows."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * scale
    if mask_rc is not None:
        rows, cols = mask_rc
        s = jnp.where(rows >= cols, s, NEG_INF)
    p = jnp.exp(s - _col(lse))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - _col(delta))
    if scale != 1.0:
        ds = ds * scale
    ds = ds.astype(k.dtype)
    return p, ds


def _bwd_dq_tri_kernel(qi_arr, ki_arr, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dq_ref, dq_sc, *, scale, block):
    f = pl.program_id(1)
    qi = qi_arr[f]
    ki = ki_arr[f]

    @pl.when(ki == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def _acc(mask_rc):
        _, ds = _bwd_p_ds(q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
                          delta_ref[0], scale, mask_rc)
        dq_sc[:] += jax.lax.dot_general(ds, k_ref[0], (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(ki < qi)
    def _interior():
        _acc(None)

    @pl.when(ki == qi)
    def _diagonal():
        _acc(_block_iotas(block, block, qi, ki))
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_dkv_tri_kernel(ki_arr, qi_arr, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                        *, scale, block, num_q):
    f = pl.program_id(1)
    ki = ki_arr[f]
    qi = qi_arr[f]

    @pl.when(qi == ki)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def _acc(mask_rc):
        p, ds = _bwd_p_ds(q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
                          delta_ref[0], scale, mask_rc)
        dv_sc[:] += jax.lax.dot_general(p.astype(do_ref.dtype), do_ref[0],
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        dk_sc[:] += jax.lax.dot_general(ds, q_ref[0], (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(qi == ki)
    def _diagonal():
        _acc(_block_iotas(block, block, qi, ki))

    @pl.when(qi > ki)
    def _interior():
        _acc(None)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc,
                   *, scale, causal, block_q, block_k, num_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def _acc(mask_rc):
        _, ds = _bwd_p_ds(q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
                          delta_ref[0], scale, mask_rc)
        dq_sc[:] += jax.lax.dot_general(ds, k_ref[0], (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    if causal:
        _causal_dispatch(qi, ki, block_q, block_k, _acc)
    else:
        _acc(None)

    @pl.when(ki == num_k - 1)
    def _finalize():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_sc, dv_sc, *, scale, causal, block_q, block_k, num_q):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def _acc(mask_rc):
        p, ds = _bwd_p_ds(q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
                          delta_ref[0], scale, mask_rc)
        dv_sc[:] += jax.lax.dot_general(p.astype(do_ref.dtype), do_ref[0],
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        dk_sc[:] += jax.lax.dot_general(ds, q_ref[0], (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    if causal:
        _causal_dispatch(qi, ki, block_q, block_k, _acc)
    else:
        _acc(None)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _flash_backward(res, g, scale, causal, block_q, block_k):
    q, k, v, o, lse = res
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    bq = _pick_block(t_q, block_q)
    bk = _pick_block(t_k, block_k)
    nq, nk = t_q // bq, t_k // bk
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None]        # (bh, 1, t_q), as lse

    if causal and t_q == t_k and bq == bk and t_q // bq < _tri_min_blocks():
        bk = _pick_block(t_k, 2 * bq)       # mirror the forward's block choice
        nk = t_k // bk
    tri = _use_tri(causal, t_q, t_k, bq, bk)
    if tri:
        qi_arr, ki_arr = _causal_pairs(nq)
        # dq: iterate (qi, ki≤qi) row-major; first prefetch array indexes q/dq
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_tri_kernel, scale=scale, block=bq),
            name="flash_bwd_dq",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(bh, len(qi_arr)),
                in_specs=[
                    pl.BlockSpec((1, bq, d), lambda b, f, qa, ka: (b, qa[f], 0)),
                    pl.BlockSpec((1, bk, d), lambda b, f, qa, ka: (b, ka[f], 0)),
                    pl.BlockSpec((1, bk, d), lambda b, f, qa, ka: (b, ka[f], 0)),
                    pl.BlockSpec((1, bq, d), lambda b, f, qa, ka: (b, qa[f], 0)),
                    pl.BlockSpec((1, 1, bq), lambda b, f, qa, ka: (b, 0, qa[f])),
                    pl.BlockSpec((1, 1, bq), lambda b, f, qa, ka: (b, 0, qa[f])),
                ],
                out_specs=pl.BlockSpec((1, bq, d), lambda b, f, qa, ka: (b, qa[f], 0)),
                scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
        )(jnp.asarray(qi_arr), jnp.asarray(ki_arr), q, k, v, do, lse, delta)

        # dkv: iterate (ki, qi≥ki) — the transposed triangle
        ki2, qi2 = _causal_pairs_colmajor(nq)
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_tri_kernel, scale=scale, block=bq, num_q=nq),
            name="flash_bwd_dkv",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(bh, len(ki2)),
                in_specs=[
                    pl.BlockSpec((1, bq, d), lambda b, f, ka, qa: (b, qa[f], 0)),
                    pl.BlockSpec((1, bk, d), lambda b, f, ka, qa: (b, ka[f], 0)),
                    pl.BlockSpec((1, bk, d), lambda b, f, ka, qa: (b, ka[f], 0)),
                    pl.BlockSpec((1, bq, d), lambda b, f, ka, qa: (b, qa[f], 0)),
                    pl.BlockSpec((1, 1, bq), lambda b, f, ka, qa: (b, 0, qa[f])),
                    pl.BlockSpec((1, 1, bq), lambda b, f, ka, qa: (b, 0, qa[f])),
                ],
                out_specs=(
                    pl.BlockSpec((1, bk, d), lambda b, f, ka, qa: (b, ka[f], 0)),
                    pl.BlockSpec((1, bk, d), lambda b, f, ka, qa: (b, ka[f], 0)),
                ),
                scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                                pltpu.VMEM((bk, d), jnp.float32)],
            ),
            out_shape=(jax.ShapeDtypeStruct((bh, t_k, d), k.dtype),
                       jax.ShapeDtypeStruct((bh, t_k, d), v.dtype)),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
        )(jnp.asarray(ki2), jnp.asarray(qi2), q, k, v, do, lse, delta)
        return dq, dk, dv

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, num_k=nk),
        name="flash_bwd_dq",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, num_q=nq),
        name="flash_bwd_dkv",
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=(
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ),
        out_shape=(jax.ShapeDtypeStruct((bh, t_k, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, t_k, d), v.dtype)),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------------ public api
def _to_bhtd(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _to_bthd(x, b):
    bh, t, d = x.shape
    return x.reshape(b, bh // b, t, d).transpose(0, 2, 1, 3)


def _attention_vjp(forward, backward):
    """``f(q, k, v, static) -> o`` over (B, T, H, D) arrays, differentiable,
    from a kernel pair over (B*H, T, D): ``forward(q, k, v, *static) -> (o,
    lse)`` and ``backward((q, k, v, o, lse), do, *static) -> (dq, dk, dv)``.

    The forward RULE names what it keeps of the kernel's outputs, in the
    form worth keeping: ``o`` as the model's lane-dense (B, T, H*Dv) tensor
    (the kernel's (B*H, T, Dv) pads a 64- or 96-wide head to 128 lanes in
    HBM), the log-sum-exp as the kernels' rows, (B*H, T) float32 ((B*H*n,
    block) from the block-sparse pair). The backward rule takes the
    kernels' views from them. A name on the function's OUTPUT would name
    another variable than the residual, and a policy that saved only that
    would throw ``o`` and ``lse`` away and run the forward kernel again to
    get them."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def attend(q, k, v, static):
        o, _ = forward(_to_bhtd(q), _to_bhtd(k), _to_bhtd(v), *static)
        return _to_bthd(o, q.shape[0])

    def fwd(q, k, v, static):
        b, t, h, _ = q.shape
        o, lse = forward(_to_bhtd(q), _to_bhtd(k), _to_bhtd(v), *static)
        o = checkpoint_name(_to_bthd(o, b).reshape(b, t, -1), SAVED_O)
        lse = checkpoint_name(lse[:, 0], SAVED_LSE)
        return o.reshape(b, t, h, -1), (q, k, v, o, lse)

    def bwd(static, res, g):
        q, k, v, o, lse = res
        b, t, h, d = q.shape
        if v.shape[-1] != d:
            raise NotImplementedError(
                "flash_attention: the backward takes v at the q.k width "
                f"({d}), not {v.shape[-1]}")
        grads = backward(
            (_to_bhtd(q), _to_bhtd(k), _to_bhtd(v),
             _to_bhtd(o.reshape(b, t, h, d)), lse[:, None]),
            _to_bhtd(g), *static)
        return tuple(_to_bthd(x, b) for x in grads)

    attend.defvjp(fwd, bwd)
    return attend


_flash_bthd = _attention_vjp(_flash_forward, _flash_backward)


def flash_attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K):
    """q, k: (B, T, H, D), v: (B, T, H, Dv) → (B, T, H, Dv); Dv <= D, the
    softmax scale is D's. Differentiable; bf16-friendly.

    Causal self-attention at a length the kernels cannot tile is padded at
    the END of the sequence and the pad rows sliced off the output: under
    the causal mask a pad key is visible only to pad queries, so no kept
    row changes, and the pad rows' cotangents are zero. Other forms raise
    on an untileable length (``flash_supports`` tells callers beforehand).
    """
    t, d = q.shape[1], q.shape[-1]
    if not flash_supports(t, k.shape[1], causal, block_q, block_k):
        raise ValueError(
            f"flash_attention: lengths ({t}, {k.shape[1]}) with causal="
            f"{causal} do not tile in blocks ({block_q}, {block_k}): q in "
            "whole 128s or one block, k in whole 8s — only causal "
            "self-attention is padded, to a multiple of 128")
    if causal and t == k.shape[1]:
        pad = _padded_len(t, min(block_q, block_k)) - t
        if pad:
            q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                       for x in (q, k, v))
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # fold the softmax scale into q OUTSIDE the kernels: one multiply over
    # (T, D) instead of a VPU pass over every (T², causal-half) score element
    # in the forward and in both backward kernels; autodiff scales dq back
    q = q * jnp.asarray(scale, q.dtype)
    o = _flash_bthd(q, k, v, (1.0, bool(causal), int(block_q), int(block_k)))
    return o[:, :t]


# ------------------------------------------------------------ block-sparse
def _sparse_pairs(layout: np.ndarray, causal: bool):
    """(row-major pairs, col-major pairs) with first/last flags per run.

    ``layout``: (n, n) bool block map. Causal drops above-diagonal pairs.
    Every query row must keep at least one pair (its diagonal/local block),
    or that row's output would never be written."""
    lay = np.asarray(layout, dtype=bool).copy()
    n = lay.shape[0]
    if causal:
        lay &= np.tril(np.ones((n, n), dtype=bool))
    if not lay.any(axis=1).all():
        empty = np.where(~lay.any(axis=1))[0]
        raise ValueError(f"sparse layout leaves query blocks {empty.tolist()} "
                         "with no key blocks (add a local/diagonal pattern)")

    def runs(primary):                # enumerate grouped by `primary` index
        qi, ki, first, last, valid = [], [], [], [], []
        for p in range(n):
            idx = np.where(lay[p] if primary == "row" else lay[:, p])[0]
            if len(idx) == 0:
                # a key block nobody attends still needs its dk/dv output
                # written (as zeros): emit one no-compute dummy pair
                qi.append(0)
                ki.append(p)
                first.append(1)
                last.append(1)
                valid.append(0)
                continue
            for j, o in enumerate(idx):
                a, b = (p, o) if primary == "row" else (o, p)
                qi.append(a)
                ki.append(b)
                first.append(1 if j == 0 else 0)
                last.append(1 if j == len(idx) - 1 else 0)
                valid.append(1)
        return (np.asarray(qi, np.int32), np.asarray(ki, np.int32),
                np.asarray(first, np.int32), np.asarray(last, np.int32),
                np.asarray(valid, np.int32))

    return runs("row"), runs("col")



def _sparse_dispatch(ok, causal, qi, ki, block, compute):
    """Shared causal/valid pl.when dispatch for the sparse kernels: valid
    diagonal blocks get the iota mask, valid off-diagonal blocks run
    mask-free, non-causal valid blocks always run mask-free."""
    if causal:
        @pl.when(ok & (qi == ki))
        def _diag():
            compute(_block_iotas(block, block, qi, ki))

        @pl.when(ok & (qi != ki))
        def _off():
            compute(None)
    else:
        @pl.when(ok)
        def _all():
            compute(None)


def _sparse_fwd_kernel(qi_arr, ki_arr, first_arr, last_arr, valid_arr,
                       q_ref, k_ref, v_ref, o_ref, lse_ref,
                       acc_sc, m_sc, l_sc, *, scale, block, causal):
    f = pl.program_id(1)
    qi, ki = qi_arr[f], ki_arr[f]
    ok = valid_arr[f] == 1

    @pl.when(first_arr[f] == 1)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    _sparse_dispatch(ok, causal, qi, ki, block,
                     lambda mask_rc: _online_softmax_block(
                         q_ref[0], k_ref[0], v_ref[0],
                         acc_sc, m_sc, l_sc, scale, mask_rc=mask_rc))

    @pl.when(last_arr[f] == 1)
    def _finalize():
        _write_out(o_ref, lse_ref, acc_sc, m_sc, l_sc)


def _sparse_bwd_dq_kernel(qi_arr, ki_arr, first_arr, last_arr, valid_arr,
                          q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, dq_sc, *, scale, block, causal):
    f = pl.program_id(1)
    qi, ki = qi_arr[f], ki_arr[f]
    ok = valid_arr[f] == 1

    @pl.when(first_arr[f] == 1)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def _acc(mask_rc):
        _, ds = _bwd_p_ds(q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
                          delta_ref[0], scale, mask_rc)
        dq_sc[:] += jax.lax.dot_general(ds, k_ref[0], (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    _sparse_dispatch(ok, causal, qi, ki, block, _acc)

    @pl.when(last_arr[f] == 1)
    def _finalize():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _sparse_bwd_dkv_kernel(qi_arr, ki_arr, first_arr, last_arr, valid_arr,
                           q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_sc, dv_sc, *, scale, block, causal):
    f = pl.program_id(1)
    qi, ki = qi_arr[f], ki_arr[f]
    ok = valid_arr[f] == 1

    @pl.when(first_arr[f] == 1)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def _acc(mask_rc):
        p, ds = _bwd_p_ds(q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
                          delta_ref[0], scale, mask_rc)
        dv_sc[:] += jax.lax.dot_general(p.astype(do_ref.dtype), do_ref[0],
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        dk_sc[:] += jax.lax.dot_general(ds, q_ref[0], (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    _sparse_dispatch(ok, causal, qi, ki, block, _acc)

    @pl.when(last_arr[f] == 1)
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _sparse_forward(q, k, v, scale, causal, hlayout):
    """The log-sum-exp (and the backward's delta) are (B*H*n, 1, block), a
    row a query block: the tile is the LAYOUT's block, any multiple of 8,
    and a (1, 1, block) block of a (B*H, 1, T) array would have to be a
    multiple of 128 lanes. From 128 up the bytes are the same, lane-dense."""
    bh, t, d = q.shape
    layout = hlayout.arr
    n = layout.shape[0]
    block = t // n
    row_pairs, _ = _sparse_pairs(layout, causal)
    pf = [jnp.asarray(x) for x in row_pairs]
    o, lse = pl.pallas_call(
        functools.partial(_sparse_fwd_kernel, scale=scale, block=block,
                          causal=causal),
        name="sparse_flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(bh, len(pf[0])),
            in_specs=[
                pl.BlockSpec((1, block, d), lambda b, f, qa, ka, fa, la, va: (b, qa[f], 0)),
                pl.BlockSpec((1, block, d), lambda b, f, qa, ka, fa, la, va: (b, ka[f], 0)),
                pl.BlockSpec((1, block, d), lambda b, f, qa, ka, fa, la, va: (b, ka[f], 0)),
            ],
            out_specs=(
                pl.BlockSpec((1, block, d), lambda b, f, qa, ka, fa, la, va: (b, qa[f], 0)),
                pl.BlockSpec((1, 1, block), lambda b, f, qa, ka, fa, la, va: (b * n + qa[f], 0, 0)),
            ),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                            pltpu.VMEM((block, 128), jnp.float32),
                            pltpu.VMEM((block, 128), jnp.float32)],
        ),
        out_shape=(jax.ShapeDtypeStruct((bh, t, d), q.dtype),
                   jax.ShapeDtypeStruct((bh * n, 1, block), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(*pf, q, k, v)
    return o, lse


def _sparse_backward(res, g, scale, causal, hlayout):
    q, k, v, o, lse = res
    layout = hlayout.arr
    bh, t, d = q.shape
    n = layout.shape[0]
    block = t // n
    row_pairs, col_pairs = _sparse_pairs(layout, causal)
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(lse.shape)

    in_specs = [
        pl.BlockSpec((1, block, d), lambda b, f, qa, ka, fa, la, va: (b, qa[f], 0)),
        pl.BlockSpec((1, block, d), lambda b, f, qa, ka, fa, la, va: (b, ka[f], 0)),
        pl.BlockSpec((1, block, d), lambda b, f, qa, ka, fa, la, va: (b, ka[f], 0)),
        pl.BlockSpec((1, block, d), lambda b, f, qa, ka, fa, la, va: (b, qa[f], 0)),
        pl.BlockSpec((1, 1, block), lambda b, f, qa, ka, fa, la, va: (b * n + qa[f], 0, 0)),
        pl.BlockSpec((1, 1, block), lambda b, f, qa, ka, fa, la, va: (b * n + qa[f], 0, 0)),
    ]
    pf_row = [jnp.asarray(x) for x in row_pairs]
    dq = pl.pallas_call(
        functools.partial(_sparse_bwd_dq_kernel, scale=scale, block=block,
                          causal=causal),
        name="sparse_flash_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(bh, len(pf_row[0])),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block, d),
                                   lambda b, f, qa, ka, fa, la, va: (b, qa[f], 0)),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(*pf_row, q, k, v, do, lse, delta)

    pf_col = [jnp.asarray(x) for x in col_pairs]
    dk, dv = pl.pallas_call(
        functools.partial(_sparse_bwd_dkv_kernel, scale=scale, block=block,
                          causal=causal),
        name="sparse_flash_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(bh, len(pf_col[0])),
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((1, block, d), lambda b, f, qa, ka, fa, la, va: (b, ka[f], 0)),
                pl.BlockSpec((1, block, d), lambda b, f, qa, ka, fa, la, va: (b, ka[f], 0)),
            ),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                            pltpu.VMEM((block, d), jnp.float32)],
        ),
        out_shape=(jax.ShapeDtypeStruct((bh, t, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, t, d), v.dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(*pf_col, q, k, v, do, lse, delta)
    return dq, dk, dv


class _HashableLayout:
    """numpy layout wrapped hashable so it can ride custom_vjp nondiff args."""

    def __init__(self, arr: np.ndarray):
        self.arr = np.asarray(arr, dtype=bool)
        self._key = self.arr.tobytes(), self.arr.shape

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _HashableLayout) and self._key == other._key


_sparse_bthd = _attention_vjp(_sparse_forward, _sparse_backward)


def flash_attention_sparse(q, k, v, layout, causal: bool = True,
                           scale: Optional[float] = None):
    """Block-sparse flash attention: q,k,v (B, T, H, D), ``layout`` an
    (n, n) 0/1 block map with block size T//n (reference ops/sparse_attention
    matmul.py:196 block-sparse sdd/dsd role + softmax.py, fused).

    The kernel tile size IS the layout block size: use layout blocks of
    ≥128 (ideally 256-512) on real TPUs — tiles smaller than the 128-wide
    MXU/VPU waste most of the hardware and multiply grid overhead. The
    reference's Triton default of block=16 is a GPU-warp granularity that
    does not transfer."""
    t, d = q.shape[1], q.shape[-1]
    n = np.asarray(layout).shape[0]
    if t % n:
        raise ValueError(f"seq {t} not divisible by layout blocks {n}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    q = q * jnp.asarray(scale, q.dtype)
    return _sparse_bthd(q, k, v, (1.0, bool(causal), _HashableLayout(layout)))


def sparse_mha_reference(q, k, v, layout, causal: bool = True,
                         scale: Optional[float] = None):
    """Dense attention with the token-level expansion of a block layout —
    the numerics oracle for flash_attention_sparse."""
    b, t, h, d = q.shape
    n = np.asarray(layout).shape[0]
    block = t // n
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    mask = np.kron(np.asarray(layout, dtype=bool),
                   np.ones((block, block), dtype=bool))
    if causal:
        mask &= np.tril(np.ones((t, t), dtype=bool))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    logits = jnp.where(jnp.asarray(mask)[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def mha_reference(q, k, v, causal: bool = True, scale: Optional[float] = None):
    """Plain einsum attention, for numerics tests."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((t_q, t_k), jnp.bool_))
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
