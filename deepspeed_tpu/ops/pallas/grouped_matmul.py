"""Grouped matmul — Pallas TPU kernel for the routed-expert MLP.

The TPU-native replacement for the reference's expert loop
(``deepspeed/moe/experts.py``: one ``nn.Module`` call per local expert over
the tokens dispatched to it) and its capacity-padded einsum dispatch
(``sharded_moe.py:472``). Rows that chose the same expert sit together, and
each ROW TILE of the left operand is multiplied by the weights of the one
expert it belongs to:

* the weights are the model's stacked ``(L, E, K, N)`` leaf, read IN PLACE:
  the layer and every row tile's expert are scalar-prefetched block indices.
  A layer slice ``(E, K, N)`` handed to a custom call is a copy (805 MB a
  layer at OLMoE-1B-7B; PERF.md, PR 25 found the same of the KV cache), and
  an XLA gather of the chosen experts materialises them; here a decode step
  reads the 8 chosen experts of 64 once and nothing else;
* groups are TILE-ALIGNED (``group_layout``): each expert's rows are padded
  to whole row tiles, so a tile has one expert, no tile is visited twice and
  no store is masked. Padding rows hold a real token's row (finite values)
  and are never read back. The number of tiles is static
  (``num_row_tiles``: rows / tile + one partial tile for each group that can
  be non-empty); how many of them hold rows is data (``n_active``, scalar
  prefetched): the tiles past it re-present the last active tile's block
  indices, so the pipeline issues no DMA for them, and ``pl.when`` skips
  their compute. A row may belong to NO group (group number = the number
  of groups: a pair routed to an expert another chip holds): it sorts
  last, takes no tile and is computed by no call; with no row in any group
  ``n_active`` is 0 and every tile is skipped;
* consecutive tiles of one expert present the same weight block, so each
  expert's weights cross HBM -> VMEM once per call, in blocks of up to
  ``RHS_BLOCK_BYTES``;
* one body, two kernels: ``moe_gmm`` (``rows @ w``) and ``moe_gmm_swiglu``
  (``silu(rows @ gate) * (rows @ up)`` in one pass over the rows, so the two
  (rows, F) intermediates never go to HBM). Decode (8 rows, one to a tile
  of 16) and prefill (T x 8 rows in tiles of 128) run the same body at two
  tile sizes; the name carries the tile (``moe_gmm_thin`` /
  ``moe_gmm_swiglu_thin`` at 16 rows, ``moe_gmm_full`` /
  ``moe_gmm_swiglu_full`` at 128) so a device trace tells the regimes apart.

The plain-XLA form of the same contraction is ``jax.lax.ragged_dot`` over
the unpadded sorted rows (``moe/dropless.py``): the CPU path, the training
path, and the reference this kernel is tested against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows of a tile: one bf16 sublane tile where a call has a handful of rows
# (a decode step: every row is its own group), an MXU pass otherwise. A
# larger tile pads more (half a tile a group on average: at 256 rows an
# expert, 128 wastes a fifth of the MXU's work and 256 a third).
ROW_TILE_FEW, ROW_TILE = 16, 128
FEW_ROWS = 256
# weight bytes a grid step streams, over all the weight operands of the call:
# 2 x (2048 x 1024) bf16 for the fused gate/up, (1024 x 2048) for down. Large
# blocks keep a decode step's 24 calls a layer near the HBM rate; twice this
# (double buffering) plus the row and output tiles must fit VMEM_LIMIT.
RHS_BLOCK_BYTES = 8 * 1024 * 1024
VMEM_LIMIT = 40 * 1024 * 1024


def row_tile(n_rows: int) -> int:
    return ROW_TILE_FEW if n_rows <= FEW_ROWS else ROW_TILE


def num_row_tiles(n_rows: int, n_groups: int, tm: int) -> int:
    """Tiles that ``n_rows`` rows in at most ``n_groups`` tile-aligned
    groups can fill: sum of ceil(size / tm) <= rows / tm + non-empty groups."""
    return n_rows // tm + min(n_groups, n_rows)


def supports(k: int, n: int) -> bool:
    """Shapes the kernel tiles: both weight dimensions in whole lane tiles."""
    return k % LANES == 0 and n % LANES == 0


def group_layout(group_of_row, n_groups: int, tm: int):
    """Where every row goes when rows are sorted by group and every group is
    padded to whole tiles of ``tm`` rows. ``group_of_row``: (M,) int32 in
    ``[0, n_groups]``; ``n_groups`` itself = the row belongs to no group and
    goes to no tile (its ``pos`` means nothing).

    -> ``sizes`` (G,) rows per group; ``tile_group`` (R,) the group of every
    row tile (tiles past ``n_active`` repeat the last active tile's);
    ``n_active`` () tiles that hold rows; ``src`` (R * tm,) for every padded
    row the index of the row it holds (a padding row holds some real row);
    ``pos`` (M,) the padded row every input row went to."""
    M = group_of_row.shape[0]
    R = num_row_tiles(M, n_groups, tm)
    order = jnp.argsort(group_of_row, stable=True).astype(jnp.int32)
    sizes = jnp.bincount(group_of_row, length=n_groups).astype(jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    tiles = -(-sizes // tm)
    tile_ends = jnp.cumsum(tiles)
    n_active = tile_ends[-1]
    padded_starts = (tile_ends - tiles) * tm
    # sorted position of every row, then its rank within its group
    sorted_pos = jnp.zeros((M,), jnp.int32).at[order].set(
        jnp.arange(M, dtype=jnp.int32))
    of_row = jnp.minimum(group_of_row, n_groups - 1)
    pos = jnp.minimum(padded_starts[of_row] + sorted_pos - starts[of_row],
                      R * tm - 1)
    tile = jnp.clip(jnp.arange(R, dtype=jnp.int32), 0,
                    jnp.maximum(n_active - 1, 0))
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_ends, tile, side="right"),
        n_groups - 1).astype(jnp.int32)
    row = jnp.arange(R * tm, dtype=jnp.int32)
    g = jnp.repeat(tile_group, tm)
    rank = jnp.minimum(row - padded_starts[g], sizes[g] - 1)
    src = order[jnp.clip(starts[g] + rank, 0, M - 1)]
    return sizes, tile_group, n_active, src, pos


def _kernel(meta_ref, tile_group_ref, x_ref, *refs, swiglu: bool):
    del tile_group_ref                      # read by the index maps only
    o_ref = refs[-1]

    @pl.when(pl.program_id(1) < meta_ref[1])
    def _tile():
        x = x_ref[...]
        dot = lambda w_ref: jax.lax.dot_general(
            x, w_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if swiglu:
            out = jax.nn.silu(dot(refs[0])) * dot(refs[1])
        else:
            out = dot(refs[0])
        o_ref[...] = out.astype(o_ref.dtype)


def grouped_matmul(x, weights, layer, tile_group, n_active, *, tm: int,
                   swiglu: bool = False):
    """``x``: (R * tm, K) rows in tile-aligned groups (``group_layout``);
    ``weights``: one stacked (L, E, K, N) leaf, or with ``swiglu`` the pair
    (gate, up); ``layer``: traced int32 scalar; ``tile_group`` (R,) and
    ``n_active`` () as ``group_layout`` gives them (``n_active`` may be 0:
    nothing is computed). -> (R * tm, N): tile r is
    ``x[tile r] @ w[layer, tile_group[r]]`` (``swiglu``: ``silu(x @ gate) *
    (x @ up)``) for r < n_active, and undefined past it."""
    weights = tuple(weights) if swiglu else (weights,)
    if len(weights) != (2 if swiglu else 1):
        raise ValueError("swiglu takes the pair (gate, up)")
    rows, K = x.shape
    L, E, Kw, N = weights[0].shape
    if Kw != K or any(w.shape != weights[0].shape for w in weights):
        raise ValueError(f"rows hold {K} values, weights are "
                         f"{[w.shape for w in weights]}")
    if rows % tm:
        raise ValueError(f"{rows} rows are not whole tiles of {tm}")
    R = rows // tm
    item = weights[0].dtype.itemsize
    # the widest column block, in whole lane tiles (or all of N), that keeps
    # a step's weight blocks under RHS_BLOCK_BYTES
    tn = N
    while tn % (2 * LANES) == 0 and len(weights) * K * tn * item > RHS_BLOCK_BYTES:
        tn //= 2
    meta = jnp.stack([jnp.asarray(layer, jnp.int32).reshape(()),
                      jnp.asarray(n_active, jnp.int32).reshape(())])
    # index maps: grid indices first, then the scalar-prefetch refs. A tile
    # past the active ones presents the last active tile's blocks again
    live = lambda r, meta: jnp.maximum(jnp.minimum(r, meta[1] - 1), 0)
    xmap = lambda n, r, meta, tg: (live(r, meta), 0)
    wmap = lambda n, r, meta, tg: (meta[0], tg[r], 0, n)
    omap = lambda n, r, meta, tg: (live(r, meta), n)
    return pl.pallas_call(
        functools.partial(_kernel, swiglu=swiglu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tn, R),
            in_specs=[pl.BlockSpec((tm, K), xmap)]
            + [pl.BlockSpec((1, 1, K, tn), wmap)] * len(weights),
            out_specs=pl.BlockSpec((tm, tn), omap)),
        out_shape=jax.ShapeDtypeStruct((rows, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        # every tile full and every group met: the scheduler has no better
        # number for sizes that are data
        cost_estimate=pl.CostEstimate(
            flops=int(2 * len(weights) * rows * K * N),
            bytes_accessed=int(item * (rows * (K + N)
                                       + len(weights) * min(E, R) * K * N)),
            transcendentals=int(rows * N if swiglu else 0)),
        name=f"moe_gmm{'_swiglu' if swiglu else ''}_"
             f"{'thin' if tm < ROW_TILE else 'full'}",
    )(meta, tile_group.astype(jnp.int32), x, *weights)
