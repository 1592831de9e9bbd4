"""Decode attention — Pallas TPU kernel for the single-token KV-cache path.

The TPU-native replacement for the reference's per-token ``softmax_context_``
inference kernel (csrc/transformer/inference/pt_binding.cpp, softmax.cu:562):
one new query token per sequence attends over the whole KV cache. This step is
HBM-bandwidth bound (the cache read dominates), so the kernel:

* streams the cache ONCE with an online softmax — no (B, H, S) score tensor
  is ever written back to HBM (the einsum path materializes it in fp32);
* is GQA-native: queries arrive grouped per KV head, the cache is read at KV
  (not H) heads — no repeated K/V copies;
* clamps the k-block index to the cache's valid length (scalar-prefetched
  ``pos``): blocks past the boundary re-present the boundary block index, so
  the pipeline issues NO new DMA for them, and ``pl.when`` skips their
  compute. A cache filled to 1/8 of max_len reads ~1/8 of it.

Layout: q (B, H, Dh), k/v cache (B, S, KV, Dh) — exactly the models' cache
layout, so no transposes of the cache are materialized per step. TPU blocks
must keep the cache's trailing (KV, Dh) dims whole, so one grid cell covers
all KV heads of one (batch, k-block) pair and loops the (static, small) KV
groups in-kernel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_K = 512
# sublane-pad the (tiny) per-group query count up to one fp32 tile row count
MIN_Q_ROWS = 8


def _pick_block(t: int, preferred: int) -> int:
    b = min(preferred, t)
    while t % b:
        b //= 2
    return max(b, 1)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_sc, m_sc, l_sc,
                   *, block_k: int, num_k: int, num_kv: int):
    j = pl.program_id(1)
    boundary = pos_ref[0] // block_k        # last block with valid entries

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def block_update(mask_cols: bool):
        cols = None                         # built once, shared by all groups
        for g in range(num_kv):             # static unroll over KV groups
            q = q_ref[0, g]                 # (Rp, Dh), scale pre-folded
            k = k_ref[0, :, g]              # (block_k, Dh)
            v = v_ref[0, :, g]
            # f32 operands: the mixed bf16->f32 dot trips a Mosaic
            # vector.broadcast verification error at Dh=64 (GQA llama
            # shapes); decode is bandwidth-bound so in-VMEM f32 is free
            s = jax.lax.dot_general(q.astype(jnp.float32),
                                    k.astype(jnp.float32),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if mask_cols:
                if cols is None:
                    cols = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                            + j * block_k)
                s = jnp.where(cols <= pos_ref[0], s, NEG_INF)
            m_prev = m_sc[g, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_sc[g] = jnp.broadcast_to(
                l_sc[g, :, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
                l_sc.shape[1:])
            acc_sc[g] = acc_sc[g] * corr + jax.lax.dot_general(
                p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[g] = jnp.broadcast_to(m_new, m_sc.shape[1:])

    @pl.when(j < boundary)
    def _interior():                        # fully inside the valid prefix
        block_update(mask_cols=False)

    @pl.when(j == boundary)
    def _edge():                            # crosses the valid length
        block_update(mask_cols=True)

    @pl.when(j == num_k - 1)
    def _finalize():
        for g in range(num_kv):
            l = l_sc[g, :, :1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, g] = (acc_sc[g] / l_safe).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, pos, block_k: int = DEFAULT_BLOCK_K):
    """q: (B, H, Dh) — the new token's queries; k_cache/v_cache:
    (B, S, KV, Dh) with entries valid through index ``pos`` (a traced int32
    scalar; valid length = pos + 1). Returns (B, H, Dh).

    ``H % KV == 0`` (grouped-query attention; H == KV is plain MHA).
    """
    B, H, Dh = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    if H % KV:
        raise ValueError(f"query heads {H} not divisible by KV heads {KV}")
    rep = H // KV
    bk = _pick_block(S, block_k)
    nk = S // bk

    q = q * jnp.asarray(1.0 / math.sqrt(Dh), q.dtype)
    # (B, KV, rep, Dh), sublane-padded so the per-group matmul has tile-sized
    # rows (pad rows cost nothing: they never touch HBM again after slicing)
    rp = max(rep, MIN_Q_ROWS)
    qg = q.reshape(B, KV, rep, Dh)
    if rp != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rp - rep), (0, 0)))

    pos_arr = jnp.reshape(pos, (1,)).astype(jnp.int32)
    # blocks past the valid boundary present the boundary block's index again
    # → the pipeline skips their DMA entirely
    # (index-map signature: grid indices first, then the scalar-prefetch refs)
    kmap = lambda b, j, pos_ref: (b, jnp.minimum(j, pos_ref[0] // bk), 0, 0)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=bk, num_k=nk, num_kv=KV),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nk),
            in_specs=[
                pl.BlockSpec((1, KV, rp, Dh), lambda b, j, pos_ref: (b, 0, 0, 0)),
                pl.BlockSpec((1, bk, KV, Dh), kmap),
                pl.BlockSpec((1, bk, KV, Dh), kmap),
            ],
            out_specs=pl.BlockSpec((1, KV, rp, Dh),
                                   lambda b, j, pos_ref: (b, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((KV, rp, Dh), jnp.float32),
                            pltpu.VMEM((KV, rp, 128), jnp.float32),
                            pltpu.VMEM((KV, rp, 128), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, rp, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * B * H * S * Dh),
            bytes_accessed=int(k_cache.size + v_cache.size) * k_cache.dtype.itemsize,
            transcendentals=int(B * H * S)),
    )(pos_arr, qg, k_cache, v_cache)
    return out[:, :, :rep].reshape(B, H, Dh)


def decode_reference(q, k_cache, v_cache, pos):
    """Grouped-einsum reference — the exact XLA path the models run without
    ``use_flash_decode`` (one shared implementation in models/common.py, so
    kernel tests compare against what production actually runs)."""
    from deepspeed_tpu.models.common import cached_decode_attention

    return cached_decode_attention(q, k_cache, v_cache, pos,
                                   use_flash_decode=False)
