"""Decode attention — Pallas TPU kernel for the single-token KV-cache path.

The TPU-native replacement for the reference's per-token ``softmax_context_``
inference kernel (csrc/transformer/inference/pt_binding.cpp, softmax.cu:562):
one new query token per sequence attends over the KV cache. This step is
HBM-bandwidth bound (the cache read dominates), so the kernel reads from HBM
the K/V of the positions attended, once, and nothing else:

* the cache is the models' stacked ``(L, B, S, W)`` buffer with the KV heads
  FOLDED into lane-dense rows (``W`` = ``KV * Dh`` rounded up to 128 lanes,
  ``models/common.py::init_kv_cache``). The layer is a scalar-prefetched
  block index, so the kernel reads the cache in place — no per-layer slice
  is materialized, and the layout the kernel wants (row-major) is the one a
  TPU gives an array whose minor dimension fills its lanes, so no program
  that hands the cache to another relays it out;
* the k-block index is clamped to the valid length (scalar-prefetched
  ``pos``): blocks past the boundary re-present the boundary block, so the
  pipeline issues NO new DMA for them, and ``pl.when`` skips their compute.
  A cache filled to a third of its allocation reads about a third of it;
* one online softmax over the blocks — no (B, H, S) score tensor goes back
  to HBM;
* all heads ride ONE pair of MXU calls a block: the queries are spread into
  a block-diagonal ``(rows, W)`` matrix (row ``r * KVp + g`` holds query head
  ``g * rep + r`` in the columns of KV head ``g``, zeros elsewhere), so
  ``Q @ K^T`` gives every head's scores against its own columns and
  ``P @ V`` every head's output in its own columns; the block diagonal is
  read off at the end. GQA-native: the cache is read at KV (not H) heads.

``latent_decode_attention`` (``latent_decode_attn``) is the same stream for
latent attention (MLA) in its ABSORBED form: the cache holds one row a
position for all heads, ``[c_kv | k_rope]``; every head's query has been
multiplied into the row's columns, so the H query rows ARE the kernel's
query matrix (no block diagonal), the keys are the rows and the values the
first ``v_width`` lanes of the SAME rows: one cache operand, each row read
from HBM once a step for scores and values alike (handing the cache to
``decode_attention`` as both K and V would stream it twice). At 128 heads
that is 128 x (576 + 512) x 2 FLOPs for 1,152 bytes a position: ~240
FLOP/byte, ON the v5e's ridge, where ``decode_attn`` is bandwidth-bound.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# cache rows a grid step streams: the granularity at which cost follows the
# context. gpt2-xl's 16-token chunk on a v5e, ms at contexts 320 / 768 / 1000
# (PERF.md, PR 25): 128 rows 84.8 / 87.8 / 88.7, 256 rows 85.5 / 88.9 / 88.8,
# 512 rows 85.9 / 89.1 / 89.1
DEFAULT_BLOCK_K = 128
LANES = 128
# rows of one KV-head group in the block-diagonal query matrix: a whole
# number of bf16 sublane tiles
ROW_TILE = 16


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _softmax_block(q, k, v, j, pos, block_k: int, edge: bool, acc_sc, m_sc,
                   l_sc):
    """One block of the streaming softmax: the query rows q (rows, W)
    against block ``j``'s keys k (block_k, W) and values v (block_k, Wv),
    into the running max, sum and (rows, Wv) accumulator. ``edge``: the
    block that crosses the valid length — slots past ``pos`` (stale
    entries, or the rows a partial last block reads past the array) weigh
    nothing and add nothing."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if edge:
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_k
        s = jnp.where(cols <= pos, s, NEG_INF)
        rows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) + j * block_k
        v = jnp.where(rows <= pos, v, jnp.zeros_like(v))
    m_prev = m_sc[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[:] = jnp.broadcast_to(
        l_sc[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True), l_sc.shape)
    acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)


def _decode_kernel(sc_ref, q_ref, k_ref, v_ref, o_ref, qb_sc, acc_sc, m_sc,
                   l_sc, *, block_k: int, num_k: int, rep: int, kvp: int,
                   head_dim: int):
    j = pl.program_id(1)
    pos = sc_ref[0]
    boundary = pos // block_k               # last block with valid entries
    width = qb_sc.shape[1]

    def head_columns():
        # (kvp, W): True where column c belongs to KV head g (= the row)
        g = jax.lax.broadcasted_iota(jnp.int32, (kvp, width), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (kvp, width), 1)
        return (c >= g * head_dim) & (c < (g + 1) * head_dim)

    @pl.when(j == 0)
    def _init():
        own = head_columns()
        for r in range(rep):                # static, small (H // KV)
            # in float32: the 32-bit mask cannot be laid over packed rows
            row = jnp.broadcast_to(q_ref[0, r:r + 1, :].astype(jnp.float32),
                                   (kvp, width))
            qb_sc[r * kvp:(r + 1) * kvp, :] = jnp.where(
                own, row, 0.0).astype(qb_sc.dtype)
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def block_update(edge: bool):
        _softmax_block(qb_sc[:], k_ref[0, 0], v_ref[0, 0], j, pos, block_k,
                       edge, acc_sc, m_sc, l_sc)

    @pl.when(j < boundary)
    def _interior():                        # fully inside the valid prefix
        block_update(edge=False)

    @pl.when(j == boundary)
    def _edge():
        block_update(edge=True)

    @pl.when(j == num_k - 1)
    def _finalize():
        own = head_columns()
        l = l_sc[:, :1]
        out = acc_sc[:] / jnp.where(l == 0.0, 1.0, l)
        for r in range(rep):
            mine = jnp.where(own, out[r * kvp:(r + 1) * kvp], 0.0)
            o_ref[0, r:r + 1, :] = jnp.sum(
                mine, axis=0, keepdims=True).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, layer, pos, *, n_kv: int,
                     block_k: int = DEFAULT_BLOCK_K):
    """q: (B, H, Dh) — the new token's queries; k_cache/v_cache: the stacked
    ``(L, B, S, W)`` cache, ``W >= n_kv * Dh`` with KV head ``g`` in columns
    ``[g * Dh, (g + 1) * Dh)`` and finite values everywhere; ``layer`` and
    ``pos``: traced int32 scalars — the layer attended and the last valid
    slot (valid length = pos + 1). Returns (B, H, Dh).

    ``H % n_kv == 0`` (grouped-query attention; H == n_kv is plain MHA).
    """
    B, H, Dh = q.shape
    S, W = k_cache.shape[2], k_cache.shape[3]
    if H % n_kv:
        raise ValueError(f"query heads {H} not divisible by KV heads {n_kv}")
    C = n_kv * Dh
    if W < C:
        raise ValueError(f"cache rows hold {W} values, {n_kv} x {Dh} asked")
    rep = H // n_kv
    kvp = _round_up(n_kv, ROW_TILE)
    # a block is a multiple of the bf16 sublane tile, or the whole of S; the
    # last block of an S that does not tile is partial and always an edge
    bk = S if S <= block_k else _round_up(block_k, ROW_TILE)
    nk = pl.cdiv(S, bk)

    # row r of the kernel's query input: the r-th query head of every KV
    # group, each in its own group's columns; scale folded in
    qf = (q * jnp.asarray(1.0 / math.sqrt(Dh), q.dtype)).reshape(
        B, n_kv, rep, Dh).transpose(0, 2, 1, 3).reshape(B, rep, C)
    qf = jnp.pad(qf.astype(k_cache.dtype), ((0, 0), (0, 0), (0, W - C)))

    scalars = jnp.stack([jnp.asarray(pos, jnp.int32).reshape(()),
                         jnp.asarray(layer, jnp.int32).reshape(())])
    # blocks past the valid boundary present the boundary block's index again
    # → the pipeline skips their DMA entirely
    # (index-map signature: grid indices first, then the scalar-prefetch refs)
    kmap = lambda b, j, sc: (sc[1], b, jnp.minimum(j, sc[0] // bk), 0)
    qmap = lambda b, j, sc: (b, 0, 0)
    item = k_cache.dtype.itemsize

    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=bk, num_k=nk, rep=rep,
                          kvp=kvp, head_dim=Dh),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nk),
            in_specs=[
                pl.BlockSpec((1, rep, W), qmap),
                pl.BlockSpec((1, 1, bk, W), kmap),
                pl.BlockSpec((1, 1, bk, W), kmap),
            ],
            out_specs=pl.BlockSpec((1, rep, W), qmap),
            scratch_shapes=[pltpu.VMEM((rep * kvp, W), k_cache.dtype),
                            pltpu.VMEM((rep * kvp, W), jnp.float32),
                            pltpu.VMEM((rep * kvp, LANES), jnp.float32),
                            pltpu.VMEM((rep * kvp, LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, rep, W), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        # what a full cache costs; the scheduler has no better number for a
        # length that is data
        cost_estimate=pl.CostEstimate(
            flops=int(4 * B * rep * kvp * S * W),
            bytes_accessed=int(2 * B * S * W * item),
            transcendentals=int(B * rep * kvp * S)),
        name="decode_attn",
    )(scalars, qf, k_cache, v_cache)
    return out[:, :, :C].reshape(B, rep, n_kv, Dh).transpose(
        0, 2, 1, 3).reshape(B, H, Dh)


# rows of the latent cache a grid step streams: the row is narrow (640 lanes
# where a folded K/V row is 1,664-2,048), the allocation long (32,768 slots),
# and a step that streams nothing still costs its ~0.35 us
LATENT_BLOCK_K = 1024


def _latent_kernel(sc_ref, q_ref, kv_ref, o_ref, acc_sc, m_sc, l_sc, *,
                   block_k: int, num_k: int, v_width: int):
    j = pl.program_id(1)
    pos = sc_ref[0]
    boundary = pos // block_k               # last block with valid entries

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def block_update(edge: bool):
        kv = kv_ref[0, 0]                   # (block_k, W): keys AND values
        _softmax_block(q_ref[0], kv, kv[:, :v_width], j, pos, block_k, edge,
                       acc_sc, m_sc, l_sc)

    @pl.when(j < boundary)
    def _interior():
        block_update(edge=False)

    @pl.when(j == boundary)
    def _edge():
        block_update(edge=True)

    @pl.when(j == num_k - 1)
    def _finalize():
        l = l_sc[:, :1]
        o_ref[0] = (acc_sc[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def latent_decode_attention(q, cache, layer, pos, *, v_width: int,
                            scale: float, block_k: int = LATENT_BLOCK_K):
    """q: (B, H, C) — the new token's queries in the latent row's columns
    (absorbed); ``cache``: the stacked ``(L, B, S, W)`` latent cache, ``W >=
    C``, finite values everywhere, a position's value = the first
    ``v_width`` columns of its row (a whole number of lane tiles, or all of
    ``W``); ``layer`` and ``pos``: traced int32 scalars — the layer attended
    and the last valid slot. ``scale`` multiplies the scores. Returns
    (B, H, v_width)."""
    B, H, C = q.shape
    S, W = cache.shape[2], cache.shape[3]
    if W < C or not (v_width == W or (v_width < W and v_width % LANES == 0)):
        raise ValueError(f"latent rows hold {W} values: {C} asked of them, "
                         f"{v_width} as the value")
    bk = S if S <= block_k else _round_up(block_k, ROW_TILE)
    nk = pl.cdiv(S, bk)
    qf = jnp.pad((q * jnp.asarray(scale, q.dtype)).astype(cache.dtype),
                 ((0, 0), (0, 0), (0, W - C)))
    scalars = jnp.stack([jnp.asarray(pos, jnp.int32).reshape(()),
                         jnp.asarray(layer, jnp.int32).reshape(())])
    # blocks past the valid boundary present the boundary block's index again
    # -> the pipeline skips their DMA entirely
    kvmap = lambda b, j, sc: (sc[1], b, jnp.minimum(j, sc[0] // bk), 0)
    qmap = lambda b, j, sc: (b, 0, 0)
    return pl.pallas_call(
        functools.partial(_latent_kernel, block_k=bk, num_k=nk,
                          v_width=v_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nk),
            in_specs=[pl.BlockSpec((1, H, W), qmap),
                      pl.BlockSpec((1, 1, bk, W), kvmap)],
            out_specs=pl.BlockSpec((1, H, v_width), qmap),
            scratch_shapes=[pltpu.VMEM((H, v_width), jnp.float32),
                            pltpu.VMEM((H, LANES), jnp.float32),
                            pltpu.VMEM((H, LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        # what a full cache costs (see decode_attention)
        cost_estimate=pl.CostEstimate(
            flops=int(2 * B * H * S * (W + v_width)),
            bytes_accessed=int(B * S * W * cache.dtype.itemsize),
            transcendentals=int(B * H * S)),
        name="latent_decode_attn",
    )(scalars, qf, cache)
