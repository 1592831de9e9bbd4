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
  read off at the end. GQA-native: the cache is read at KV (not H) heads;
* a step may bring MORE than one query position (``q`` (B, Lb, H, Dh): a
  block-diffusion step's block, all of whose positions see slots ``0 ..
  pos`` alike, no mask among them): the ``Lb x rep`` queries of a KV group
  are that group's rows. Where that is a run of whole sublane tiles the
  matrix is laid out GROUP-major instead (rows ``g * R .. g * R + R - 1``
  hold KV head ``g``'s queries, R = ``Lb x rep`` rounded up to 16) and no
  KV head is padded: 4 positions x 8 heads a group x 4 KV heads are 128
  rows, one MXU tile, where the row-major form would pad the 4 KV heads to
  16 and run 512. ``query_plan`` takes whichever has fewer rows, the
  row-major one on a tie: one position at (25, 1), (16, 1), (8, 8) heads a
  group keeps the plan it had;
* such a call may give its FIRST positions an earlier last slot (``early``:
  a block-diffusion step whose pass carries the block before it: that
  block's positions are blind to the new block's slots). Only the edge
  blocks change: their mask compares a column with its ROW's limit (from a
  row iota and the plan: which rows of the matrix are those positions'),
  every block between the two limits is an edge, and the k-block index is
  clamped by the later one. A call without it traces none of this (the
  standing cells' jaxprs are pinned: tests/unit/test_decode_attention.py);
* K rows and V rows may differ in width (``v_dim``: q.k at 192 columns a
  head, v at 128): the block-diagonal query is over the K row's lanes, the
  accumulator and the output over the V row's, each head's block in its own
  columns of each;
* a learned SINK logit a head (``sink``: it takes mass and carries no
  value) is the INITIAL state of the online softmax, ``m = b_h, l = 1, acc
  = 0``, where a call without one starts from ``m = -inf, l = 0``: one more
  operand, the sinks by row of the query matrix, and no work a block;
* the cache may be a RING (a window layer's: ``window`` slots, written at
  ``pos % window``, K rotated before it is written so slot order means
  nothing to the softmax): nothing here changes, the caller hands ``pos``
  as the last valid slot, ``min(pos, window - 1)``.

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
                   l_sc, row_pos=None):
    """One block of the streaming softmax: the query rows q (rows, W)
    against block ``j``'s keys k (block_k, W) and values v (block_k, Wv),
    into the running max, sum and (rows, Wv) accumulator. ``edge``: the
    block that crosses the valid length — slots past ``pos`` (stale
    entries, or the rows a partial last block reads past the array) weigh
    nothing and add nothing. ``row_pos``: (rows, block_k) int32, each
    row's OWN last slot where some rows stop before ``pos`` (a row has a
    valid slot in an earlier block or this one: its running max is a score's
    by the time a block it sees nothing of comes)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if edge:
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_k
        s = jnp.where(cols <= (pos if row_pos is None else row_pos), s,
                      NEG_INF)
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


def query_plan(n_kv: int, per_group: int):
    """How the block-diagonal query matrix is laid out for ``per_group``
    queries a KV head (positions x heads of a group) -> (group_major, rows a
    unit, units): row-major, a unit is one query of every KV head (``n_kv``
    rounded up to a sublane tile) and there are ``per_group`` of them;
    group-major, a unit is one KV head's queries (``per_group`` rounded up)
    and there are ``n_kv``. The form with fewer rows, row-major on a tie."""
    by_row = per_group * _round_up(n_kv, ROW_TILE)
    by_group = n_kv * _round_up(per_group, ROW_TILE)
    if by_group < by_row:
        return True, _round_up(per_group, ROW_TILE), n_kv
    return False, _round_up(n_kv, ROW_TILE), per_group


def _decode_kernel(sc_ref, q_ref, k_ref, v_ref, o_ref, qb_sc, acc_sc, m_sc,
                   l_sc, *, block_k: int, num_k: int, rep: int, kvp: int,
                   head_dim: int, group_major: bool = False,
                   early_queries: int = 0, v_dim=None, sink_ref=None):
    """``rep`` units of ``kvp`` rows (``query_plan``). Row-major: unit r is
    row r of ``q_ref`` spread over the KV heads' rows, each in its own
    columns. Group-major: unit g is ALL of ``q_ref``'s rows masked to KV
    head g's columns. ``early_queries``: the first that many queries of
    every KV group (whole positions) see slots through ``sc_ref[2]`` only.
    ``v_dim``: a head's columns of the V rows and of the output (None:
    ``head_dim``, the K rows'). ``sink_ref`` (rows, 128): each row's sink
    logit, lane-broadcast: the softmax's initial state."""
    j = pl.program_id(1)
    pos = sc_ref[0]
    boundary = pos // block_k               # last block with valid entries
    # the first block that is an edge for SOME row
    first_edge = sc_ref[2] // block_k if early_queries else boundary

    def row_pos():
        # (rows, block_k): the early queries' rows stop at their own slot:
        # the head of every unit group-major, the first units whole row-major
        row = jax.lax.broadcasted_iota(jnp.int32, (rep * kvp, block_k), 0)
        if group_major:
            early = functools.reduce(jnp.logical_or, (
                (row >= g * kvp) & (row < g * kvp + early_queries)
                for g in range(rep)))
        else:
            early = row < early_queries * kvp
        return jnp.where(early, sc_ref[2], pos)

    def head_columns(g=None, of=qb_sc, dim=head_dim):
        # (kvp, W): True where column c belongs to KV head g (None: the row)
        width = of.shape[1]
        if g is None:
            g = jax.lax.broadcasted_iota(jnp.int32, (kvp, width), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (kvp, width), 1)
        return (c >= g * dim) & (c < (g + 1) * dim)

    # the output's columns: the V rows', a head ``v_dim`` of them
    out_columns = head_columns if v_dim is None else functools.partial(
        head_columns, of=acc_sc, dim=v_dim)

    @pl.when(j == 0)
    def _init():
        own = None if group_major else head_columns()
        # in float32: the 32-bit mask cannot be laid over packed rows
        whole = q_ref[0].astype(jnp.float32) if group_major else None
        for r in range(rep):                # static, small
            if group_major:
                unit = jnp.where(head_columns(r), whole, 0.0)
            else:
                unit = jnp.where(own, jnp.broadcast_to(
                    q_ref[0, r:r + 1, :].astype(jnp.float32),
                    (kvp, qb_sc.shape[1])), 0.0)
            qb_sc[r * kvp:(r + 1) * kvp, :] = unit.astype(qb_sc.dtype)
        acc_sc[:] = jnp.zeros_like(acc_sc)
        if sink_ref is None:
            m_sc[:] = jnp.full_like(m_sc, NEG_INF)
            l_sc[:] = jnp.zeros_like(l_sc)
        else:                   # the sink: mass exp(b_h), no value
            m_sc[:] = sink_ref[:]
            l_sc[:] = jnp.ones_like(l_sc)

    def block_update(edge: bool):
        _softmax_block(qb_sc[:], k_ref[0, 0], v_ref[0, 0], j, pos, block_k,
                       edge, acc_sc, m_sc, l_sc,
                       row_pos() if edge and early_queries else None)

    @pl.when(j < first_edge)
    def _interior():                        # fully inside the valid prefix
        block_update(edge=False)

    @pl.when((j >= first_edge) & (j <= boundary) if early_queries
             else j == boundary)
    def _edge():
        block_update(edge=True)

    @pl.when(j == num_k - 1)
    def _finalize():
        l = l_sc[:, :1]
        out = acc_sc[:] / jnp.where(l == 0.0, 1.0, l)
        if group_major:
            o_ref[0] = sum(
                jnp.where(out_columns(g), out[g * kvp:(g + 1) * kvp], 0.0)
                for g in range(rep)).astype(o_ref.dtype)
            return
        own = out_columns()
        for r in range(rep):
            mine = jnp.where(own, out[r * kvp:(r + 1) * kvp], 0.0)
            o_ref[0, r:r + 1, :] = jnp.sum(
                mine, axis=0, keepdims=True).astype(o_ref.dtype)


def _sink_rows(sink, n_kv: int, positions: int, group_major: bool, kvp: int):
    """A head's sink logit (H,) by ROW of the kernel's block-diagonal query
    matrix, (rows, 128) float32 with every lane of a row equal; 0 in the
    rows no query has (they hold zeros and are dropped)."""
    per_group = jnp.tile(sink.astype(jnp.float32).reshape(n_kv, -1),
                         (1, positions))          # (KV, positions x heads)
    rows = jnp.pad(per_group, ((0, 0), (0, kvp - per_group.shape[1]))) \
        if group_major else jnp.pad(per_group.T, ((0, 0), (0, kvp - n_kv)))
    return jnp.broadcast_to(rows.reshape(-1, 1), (rows.size, LANES))


def decode_attention(q, k_cache, v_cache, layer, pos, *, n_kv: int,
                     block_k: int = DEFAULT_BLOCK_K, early=None,
                     v_dim=None, sink=None):
    """q: (B, H, Dh) — the new token's queries — or (B, Lb, H, Dh): ``Lb``
    positions' queries, each of which sees every valid slot (a
    block-diffusion step: its block lies in the last ``Lb`` of them);
    k_cache/v_cache: the stacked ``(L, B, S, W)`` cache, ``W >= n_kv * Dh``
    with KV head ``g`` in columns ``[g * Dh, (g + 1) * Dh)`` and finite
    values everywhere; ``layer`` and ``pos``: traced int32 scalars — the
    layer attended and the last valid slot (valid length = pos + 1).
    ``early``: ``(n, last)``, a last valid slot PER POSITION in two values:
    the first ``n`` (static, 0 < n < Lb) of the ``Lb`` positions see slots
    ``0 .. last`` (traced, 0 <= last <= pos), the others ``0 .. pos``.
    ``v_dim``: a head's columns in ``v_cache``'s rows where they are not
    ``Dh`` (the caches then differ in width). ``sink``: (H,) a head's sink
    logit, beside the SCALED scores in the softmax's sum, with no value.
    Returns q's shape, ``v_dim`` columns a head.

    ``H % n_kv == 0`` (grouped-query attention; H == n_kv is plain MHA).
    """
    one = q.ndim == 3
    if one:
        q = q[:, None]
    B, Lb, H, Dh = q.shape
    if early is not None and not 0 < early[0] < Lb:
        raise ValueError(f"early {early[0]}: some, not all, of the {Lb} "
                         "query positions")
    S, W, Wv = k_cache.shape[2], k_cache.shape[3], v_cache.shape[3]
    if H % n_kv:
        raise ValueError(f"query heads {H} not divisible by KV heads {n_kv}")
    Dv = Dh if v_dim is None else int(v_dim)
    C, Cv = n_kv * Dh, n_kv * Dv
    if W < C or Wv < Cv:
        raise ValueError(f"cache rows hold {W} / {Wv} values, {n_kv} x "
                         f"{Dh} / {Dv} asked")
    per_group = Lb * (H // n_kv)
    group_major, kvp, rep = query_plan(n_kv, per_group)
    # rows of the kernel's query input: a unit's rows group-major (padded to
    # whole tiles: zero queries, dropped below), else one a query of a group
    q_rows = kvp if group_major else per_group
    # a block is a multiple of the bf16 sublane tile, or the whole of S; the
    # last block of an S that does not tile is partial and always an edge
    bk = S if S <= block_k else _round_up(block_k, ROW_TILE)
    nk = pl.cdiv(S, bk)

    # row (l, r) of the kernel's query input: position l's r-th query head of
    # every KV group, each in its own group's columns; scale folded in
    qf = (q * jnp.asarray(1.0 / math.sqrt(Dh), q.dtype)).reshape(
        B, Lb, n_kv, H // n_kv, Dh).transpose(0, 1, 3, 2, 4).reshape(
            B, per_group, C)
    qf = jnp.pad(qf.astype(k_cache.dtype),
                 ((0, 0), (0, q_rows - per_group), (0, W - C)))

    # (the last valid slot, the layer[, the early positions' last slot])
    scalars = jnp.stack([jnp.asarray(x, jnp.int32).reshape(()) for x in
                         (pos, layer) + (() if early is None else early[1:])])
    # blocks past the valid boundary present the boundary block's index again
    # → the pipeline skips their DMA entirely
    # (index-map signature: grid indices first, then the scalar-prefetch refs)
    kmap = lambda b, j, sc: (sc[1], b, jnp.minimum(j, sc[0] // bk), 0)
    qmap = lambda b, j, sc: (b, 0, 0)
    item = k_cache.dtype.itemsize

    kernel = functools.partial(
        _decode_kernel, block_k=bk, num_k=nk, rep=rep, kvp=kvp, head_dim=Dh,
        group_major=group_major,
        early_queries=early[0] * (H // n_kv) if early else 0,
        **({} if v_dim is None else {"v_dim": Dv}))
    operands, sink_specs = (scalars, qf, k_cache, v_cache), []
    if sink is not None:
        # one more input, handed to the body by name: it sits between the
        # inputs and the output in the call's order
        body = kernel
        kernel = lambda sc, q, k, v, s, o, *scratch: body(
            sc, q, k, v, o, *scratch, sink_ref=s)
        operands += (_sink_rows(sink, n_kv, Lb, group_major, kvp),)
        sink_specs = [pl.BlockSpec((rep * kvp, LANES),
                                   lambda b, j, sc: (0, 0))]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nk),
            in_specs=[
                pl.BlockSpec((1, q_rows, W), qmap),
                pl.BlockSpec((1, 1, bk, W), kmap),
                pl.BlockSpec((1, 1, bk, Wv), kmap),
            ] + sink_specs,
            out_specs=pl.BlockSpec((1, q_rows, Wv), qmap),
            scratch_shapes=[pltpu.VMEM((rep * kvp, W), k_cache.dtype),
                            pltpu.VMEM((rep * kvp, Wv), jnp.float32),
                            pltpu.VMEM((rep * kvp, LANES), jnp.float32),
                            pltpu.VMEM((rep * kvp, LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, q_rows, Wv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        # what a full cache costs; the scheduler has no better number for a
        # length that is data
        cost_estimate=pl.CostEstimate(
            flops=int(2 * B * rep * kvp * S * (W + Wv)),
            bytes_accessed=int(B * S * (W + Wv) * item),
            transcendentals=int(B * rep * kvp * S)),
        name="decode_attn",
    )(*operands)
    out = out[:, :per_group, :Cv].reshape(
        B, Lb, H // n_kv, n_kv, Dv).transpose(0, 1, 3, 2, 4).reshape(
            B, Lb, H, Dv)
    return out[:, 0] if one else out


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
