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
        k = k_ref[0, 0]                     # (block_k, W)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(qb_sc[:], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if edge:
            # the block that crosses the valid length: slots past ``pos``
            # (stale entries, or the rows a partial last block reads past
            # the array) weigh nothing and add nothing
            cols = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                    + j * block_k)
            s = jnp.where(cols <= pos, s, NEG_INF)
            rows = (jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
                    + j * block_k)
            v = jnp.where(rows <= pos, v, jnp.zeros_like(v))
        m_prev = m_sc[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[:] = jnp.broadcast_to(
            l_sc[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
            l_sc.shape)
        acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)

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
