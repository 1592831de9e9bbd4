"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference (v0.9.3) has NO sequence parallelism (SURVEY §2.2 — its
long-sequence story is sparse attention + curriculum). Later DeepSpeed grew
Ulysses (head-scatter all-to-all); on TPU both long-context schemes are
first-class here:

* **Ulysses** (`ulysses_attention`): tokens arrive sequence-sharded over the
  'seq' mesh axis; one all-to-all re-shards heads instead of sequence, full-
  sequence attention runs locally (flash kernel), a second all-to-all restores
  sequence sharding. Comm volume: 2 a2a of the activation — cheap on ICI.
  Requires n_heads % seq_size == 0.

* **Ring attention** (`ring_attention`): K/V blocks rotate around the 'seq'
  ring via ppermute while each device accumulates its queries' attention with
  streaming-softmax merges (blockwise attention, Liu et al.). Memory O(T/s)
  per device with no head-count constraint; comm overlaps with block compute.
  Causal masking works on global positions; blocks entirely in the future
  contribute nothing.

Both are plain traced code inside a FULLY-MANUAL shard_map — AD transposes
the ppermute/all_to_all into the reverse-direction gradient comms. Fully
manual (every mesh axis, with in_specs naming the batch/seq/head layout the
surrounding GSPMD program already uses) rather than manual-over-'seq'-only:
attention is embarrassingly parallel over batch AND heads, so no cross-dp or
cross-tp collective is needed inside — and the flash kernel Ulysses calls
inside must be manual over every axis anyway (XLA cannot partition a Mosaic
call). Partial-manual mode hard-aborted the SPMD partitioner on jax 0.4.x;
not re-tested on 0.9 (ROADMAP D10).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import (DATA_AXIS, EXPERT_AXIS,
                                             ICI_AXIS, MICS_AXIS, SEQ_AXIS,
                                             TENSOR_AXIS)

NEG_INF = -1e30


def _qkv_spec(mesh, seq_axis: str, n_heads: int,
              head_groups: int = 1) -> P:
    """The (B, T, H, D) layout of the fully-manual attention shard_map:
    batch over the dp axes, tokens over ``seq_axis``, heads over 'tensor'
    when the local head count stays divisible (by ``head_groups`` extra
    ways for Ulysses' in-manual head scatter), head_dim whole. Mirrors the
    placement the surrounding GSPMD program already uses, so the manual
    boundary reshards nothing."""
    batch_axes = tuple(a for a in (DATA_AXIS, MICS_AXIS, ICI_AXIS, EXPERT_AXIS)
                       if mesh.shape.get(a, 1) > 1)
    tp = mesh.shape.get(TENSOR_AXIS, 1)
    heads = TENSOR_AXIS if (tp > 1 and n_heads % (tp * head_groups) == 0) \
        else None
    return P(batch_axes if batch_axes else None, seq_axis, heads, None)


# ------------------------------------------------------------------- ulysses
def ulysses_attention(attn_fn: Callable, q, k, v, mesh, seq_axis: str = SEQ_AXIS):
    """attn_fn(q, k, v) with full sequence per device, heads sharded.

    q/k/v: (B, T, H, D) global arrays, T sharded over `seq_axis`.
    """
    S = mesh.shape[seq_axis]
    if S == 1:
        return attn_fn(q, k, v)

    def inner(q, k, v):
        # local: (B, T/S, H, D) → a2a → (B, T, H/S, D)
        def scatter_heads(x):
            return lax.all_to_all(x, seq_axis, split_axis=2, concat_axis=1, tiled=True)

        def gather_heads(x):
            return lax.all_to_all(x, seq_axis, split_axis=1, concat_axis=2, tiled=True)

        o = attn_fn(scatter_heads(q), scatter_heads(k), scatter_heads(v))
        return gather_heads(o)

    spec = _qkv_spec(mesh, seq_axis, q.shape[2], head_groups=S)
    sm = jax.shard_map(inner, mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec,
                       check_vma=False)
    return sm(q, k, v)


# -------------------------------------------------------------------- ring
def _block_attn(q, k, v, scale, mask_mode, q_off, k_off):
    """One (T_q, T_k) attention block → (out_unnorm, m, l) for streaming merge.

    mask_mode: 0 = full (past block), 1 = causal diagonal, 2 = future (all
    masked). Computed with jnp.where on traced mode id so the ring scan stays
    a single program.
    """
    Tq, Tk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    rows = lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0) + q_off
    cols = lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1) + k_off
    causal_mask = rows >= cols
    keep = jnp.where(mask_mode == 0, True,
                     jnp.where(mask_mode == 1, causal_mask, False))
    s = jnp.where(keep[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # (B, H, Tq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                                   # (B, H, Tq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)   # unnormalized
    return o, m, l


def ring_attention(q, k, v, mesh, causal: bool = True, scale: Optional[float] = None,
                   seq_axis: str = SEQ_AXIS):
    """Blockwise ring attention over the 'seq' mesh axis.

    q/k/v: (B, T, H, D) global, T sharded over seq_axis. Returns same layout.
    """
    S = mesh.shape[seq_axis]
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if S == 1:
        from deepspeed_tpu.ops.pallas.flash_attention import mha_reference

        return mha_reference(q, k, v, causal=causal, scale=scale)

    def inner(q, k, v):
        my = lax.axis_index(seq_axis)
        T_local = q.shape[1]
        q_off = my * T_local

        def ring_step(carry, step):
            kv, acc, m_run, l_run = carry
            k_cur, v_cur = kv
            # rotation sends block i → device i-1, so after `step` rotations
            # device m holds the block that started on device (m + step) % S
            src = jnp.mod(my + step, S)
            k_off = src * T_local
            if causal:
                mode = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
            else:
                mode = jnp.int32(0)
            # remat the block: AD otherwise stores the (B,H,Tq,Tk) score
            # tensor of EVERY ring step — O(S·T²) residuals, precisely the
            # memory blow-up ring attention exists to avoid (Liu et al.'s
            # blockwise recompute)
            o_b, m_b, l_b = jax.checkpoint(
                _block_attn,
                policy=jax.checkpoint_policies.nothing_saveable,
            )(q, k_cur, v_cur, scale, mode, q_off, k_off)
            # streaming-softmax merge
            m_new = jnp.maximum(m_run, m_b)
            c_run = jnp.exp(m_run - m_new)
            c_b = jnp.exp(m_b - m_new)
            l_new = l_run * c_run + l_b * c_b
            acc = acc * c_run.transpose(0, 2, 1)[..., None].astype(acc.dtype) + \
                o_b * c_b.transpose(0, 2, 1)[..., None].astype(acc.dtype)
            # rotate kv to the next device (i receives from i+1: shift -1)
            perm = [(i, (i - 1) % S) for i in range(S)]
            k_nxt = lax.ppermute(k_cur, seq_axis, perm)
            v_nxt = lax.ppermute(v_cur, seq_axis, perm)
            return ((k_nxt, v_nxt), acc, m_new, l_new), None

        B, T_l, H, Dh = q.shape
        acc0 = jnp.zeros((B, T_l, H, Dh), q.dtype)
        m0 = jnp.full((B, H, T_l), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, T_l), jnp.float32)
        (kv, acc, m_run, l_run), _ = lax.scan(
            ring_step, ((k, v), acc0, m0, l0), jnp.arange(S))
        l_safe = jnp.where(l_run == 0.0, 1.0, l_run)
        return (acc / l_safe.transpose(0, 2, 1)[..., None].astype(acc.dtype))

    spec = _qkv_spec(mesh, seq_axis, q.shape[2])
    sm = jax.shard_map(inner, mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec,
                       check_vma=False)
    return sm(q, k, v)
