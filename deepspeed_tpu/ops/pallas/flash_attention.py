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
with Δ = rowsum(dO ∘ O) computed outside the kernel (five matmuls a tile).

Causal execution (self-attention; the path every prefill and every train
step takes). ONE forward kernel for every length, its shapes from
``flash_forward_plan(t, d, dv, dtype)`` and nothing else:

* **A grid step is a q block against a SPAN of keys** — up to four square
  sub-blocks wide (2,048 columns at the default 512; the whole length
  while that fits). The (q block, span) pairs are a 1-D grid looked up from
  scalar-prefetch arrays (``pltpu.PrefetchScalarGridSpec``) that list only
  the spans which begin at or under the q block's last row: a span above the
  diagonal is never fetched — a ``pl.when`` skip alone would still pay its
  DMA. What a grid step costs whatever it holds (the pipeline's step, its
  DMA waits, the branches) is paid once a span, not once a 512 x 512 tile.
* **Inside a step the span is walked up to the diagonal and no further.**
  Every walk is unrolled into one basic block, two sub-blocks to a softmax
  update (one q.k matmul 1,024 columns wide; the max, the rescale and the
  accumulator's pass at half the rate), so the scheduler lays the next
  update's matmul beside this one's softmax; the running max, sum and
  accumulator are values inside a walk. A span wholly under the diagonal is
  one walk. The span that holds the diagonal walks what lies under it by
  the binary digits of its count (a walk of 2, a walk of 1: the count is
  dynamic, each walk's length static), then takes the ONE sub-block the
  diagonal crosses — the only one to pay the iota / compare / select —
  with its left neighbour, and writes the q block out. Nothing above the
  diagonal is run: ``ForwardPlan.sub_blocks_run`` is the causal count,
  T = 1,024 in 512s runs 3 sub-blocks, 2 of them masked.
* **Per-row statistics stay lane-broadcast**, (rows, 128) with every lane
  equal, and meet a (rows, n) operand tiled along the lanes (``_lanes``):
  a (rows, 1) column pays an XLU broadcast a row group at every use, which
  measured 2 x the whole kernel's time at T = 16,384.
* **ONE backward kernel** for every length (``_bwd_causal_kernel``, its
  shapes from ``flash_backward_plan(t, d, dtype, window)``): P and dS are
  recomputed ONCE a tile and all three gradients taken from them, five
  matmuls and one exp where a dq kernel beside a dkv kernel ran seven and
  two. The grid is the k-major list of the (k block, q block) pairs at or
  under the diagonal (``_causal_pairs_colmajor``: T = 1,024 in 512s is 3
  pairs a head, not the square's 4; of a diagonal pair's four quarters the
  one above the diagonal is not run either). A step works on the
  TRANSPOSED scores, S^T = K Q^T (keys, q rows), so the log-sum-exp and
  delta meet it as the lane-dense (1, block) rows they arrive in,
  broadcast along sublanes: no column, no cross-lane move; dV += P^T dO
  and dK += dS^T Q are plain products, dQ += (dS^T)^T K the one that
  contracts the leading dimension. dk and dv accumulate in (block, d)
  float32 scratch over a k block's pairs; dq accumulates in a float32
  scratch of the WHOLE head (T x d x 4 bytes: 0.39 MB at 1,024 x 96, 12.6
  MB at 16,384 x 192), and q block i is written out at k block i's first
  pair, its last product. It reads the log-sum-exp by row, whatever block
  wrote it.
* **A causal WINDOW** (``flash_attention(window=)``: row i sees the keys j
  with ``0 <= i - j < window``; a model's window layers) is a parameter of
  the same plans, not another family: a q block's span list starts at the
  span that holds the first sub-block with a key inside the window
  (``_causal_spans``: a second bound on the same list), the backward's
  pair list keeps the pairs of the band alone (``_causal_pairs_colmajor``:
  a k block's q blocks end at the last that sees it), and a sub-block
  pays the mask where the diagonal OR the window's trailing
  edge crosses it — that edge is a diagonal too (``row - col = window``),
  which is why no block-sparse layout can express it. The windowed forward
  (``_fwd_window_kernel``) runs one softmax update a sub-block, scratch to
  scratch, not yet the wide walk above. Its calls are ``flash_fwd_win`` /
  ``flash_bwd_dkv_win``. Without a window every plan, list and traced
  program is what it was. A window NARROWER than half a sub-block (128
  keys under the default 512) takes a smaller sub-block
  (``_window_sub_block``): at 512 a q block runs two 512 x 512 sub-blocks
  for a band of 128 columns a row, eight times the band. A learned SINK
  logit a head (``flash_attention(sink=)``, with a window, forward only: it
  takes mass and carries no value) is the online softmax's INITIAL state,
  ``m = b_h, l = 1, acc = 0``: one small operand and no work a sub-block.
* **A BLOCK-causal mask** (``flash_attention(block=)``, forward only: the
  prefill of a model that generates by diffusion over blocks): row i sees
  the keys j with ``j // block <= i // block``, blocks counted from
  position 0. ``block`` divides 128 and a sub-block is whole 128s (or the
  whole length, which begins at 0), so a block never straddles two
  sub-blocks: the span lists and the walks are the causal ones and only
  the diagonal's sub-block differs, its mask ``col <= row | (block - 1)``
  where the causal one is ``col <= row``. The backward refuses it.

* **A PREFILL's forward takes q, k and v as the projections made them**
  (``flash_prefill``, FORWARD only; its one caller is
  ``models/common.py::prefill_attention``, reached from
  ``LlamaModel.prefill``; a caller that needs a gradient takes
  ``flash_attention``). Operands: q (B, T, H, D) not rotated and not
  scaled, k (B, T, KV, D) rotated, v (B, T, KV, Dv), the model's ``cos`` /
  ``sin`` (T, r) float32 or none, the layer's ``window`` / ``sink`` /
  ``block``. The same two kernels and plans as above
  (``_causal_forward``), with three things done inside the call that
  ``flash_attention``'s callers do in passes of q's size around it: (1) K
  and V stay at their own KV heads, q's head b reading theirs at ``b //
  (H / KV)`` in the k and v index maps: no repeated K or V is written; (2)
  a q block is rotated and scaled in float32 in VMEM at the start of its
  key walk and rounded once (``_turn_q``: the first ``r`` lanes by one
  ``pltpu.roll`` against the sine with its first half negated, the others
  passed through; scratch ``q_sc``, the tables' rows blocked by the q
  block's index); (3) an output whose head is whole 128 lanes is written
  in place as the (B, T, H * Dv) rows the out-projection reads, a head its
  lane block (``o_rows``, ``_in_place``). q, k and v go in head-major: XLA
  lays their producers' outputs out that way at no pass of its own, Mosaic
  refuses a 192-lane block of a row (and a squeezed head of (B, T, H,
  192)), and at 128 lanes the row-block read measured slower. With equal
  heads, no tables and a scale of 1.0 ``_causal_forward`` traces to the
  program it always traced to (the pinned digests).

Layout: (B, T, H, D) in/out (matches deepspeed_tpu.models); internally
(B·H, T, D) (``flash_prefill``: above). v may have a head size of its own
(latent attention: q.k at 192 columns, v at 128): the FORWARD kernels take the value width from v — the
accumulator, the output and the P @ V pass are that wide, nothing is padded.
The backward kernels take one width: a narrower v goes in with zero columns
(``_attention_vjp``). The per-row statistics that pass between the
kernels, the log-sum-exp and the backward's delta, are (B·H, 1, T)
float32, lane-dense, since a (B·H, T, 1) array pads the 1 to 128 lanes in
HBM — 128 x the bytes for the kernel to write, for XLA to copy and keep.
A forward kernel turns its query block's column into that row in VMEM
(``_row``); the causal backward reads the rows as they are, against the
transposed scores; the non-causal and the block-sparse backward pairs,
which keep q rows down the sublanes, turn them back into columns
(``_col``, in ``_bwd_p_ds``).
So a q block is a multiple of 128 or the whole length (``flash_supports``;
a caller takes its einsum path for another, as ``local_causal_attention``
does). The block-sparse kernels, whose tile is the layout's, keep a row a
block instead: (B·H·n, 1, block).

Every ``pallas_call`` carries a ``name`` (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``; ``sparse_`` before each for the block-sparse kernels).
The causal backward's ONE call is ``flash_bwd_dkv`` (``flash_bwd_dkv_win``
under a window): it is the dkv kernel, grid and all, which also returns
dq. So ``flash_bwd_dq`` names the NON-causal dq kernel alone, and no train
step of a causal model holds a call of that name since PR 47 (it left the
device-op breakdowns of the train cells; a reader that matches
``flash_bwd_dq|flash_bwd_dkv`` reads the whole backward as before).
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
from typing import NamedTuple, Optional

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
    """Whether :func:`flash_attention` can tile these lengths. Causal means
    self-attention (one length), and that can at the default blocks,
    whatever the length (it pads; see ``_padded_len``); the other forms —
    which have no mask to hide pad keys behind — only at lengths that tile
    as they are: q in blocks of whole 128s (or one block), k in whole 8s.
    576 = 9 x 64 does not, nor does any longer length in blocks of 64."""
    block_q = block_q or DEFAULT_BLOCK_Q
    block_k = block_k or DEFAULT_BLOCK_K
    if causal:
        if t_q != t_k:
            return False
        t_q = t_k = _padded_len(t_q, min(block_q, block_k))
    return _tiles(t_q, block_q, _LANES) and _tiles(t_k, block_k, 8)


def _first_block(i, block: int, window):
    """The first block of keys that holds one inside the window of some row
    of q block ``i`` (``0 <= row - col < window``); 0 without a window. On a
    Python int or a traced scalar alike."""
    if window is None:
        return 0
    return (jnp.maximum if isinstance(i, jax.Array) else max)(
        i * block - window + 1, 0) // block


def _last_block(k, block: int, window, n: int):
    """The last q block, of ``n``, a row of which sees a key of block ``k``;
    ``n - 1`` without a window. On a Python int or a traced scalar alike."""
    if window is None:
        return n - 1
    return (jnp.minimum if isinstance(k, jax.Array) else min)(
        n - 1, (k * block + block + window - 2) // block)


def _inside_window(qi, ki, block: int, window: int):
    """Whether EVERY (row, col) of the block pair is inside the window: the
    pair's smallest col against its largest row."""
    return ki * block >= qi * block + block - window


def _causal_spans(rows: int, n_sub: int, sub: int = 0, window=None):
    """(q block, span) pairs, row-major: for q block i the spans of ``n_sub``
    sub-blocks that begin at or under its last row, the diagonal's last —
    and, with a window, that end at or after the first sub-block which holds
    a key inside it (a second bound on the same list)."""
    first = [_first_block(i, sub, window) // n_sub for i in range(rows)]
    qi = np.concatenate([np.full(i // n_sub + 1 - first[i], i, np.int32)
                         for i in range(rows)])
    si = np.concatenate([np.arange(first[i], i // n_sub + 1, dtype=np.int32)
                         for i in range(rows)])
    return qi, si


def _causal_pairs_colmajor(nq: int, block: int = 0, window=None):
    """Lower-triangle block pairs, column-major (qi ascending within each ki)
    — the causal backward's iteration order: each ki row accumulates over
    qi = ki..nq-1, with a window up to the last q block that sees it."""
    last = [_last_block(i, block, window, nq) for i in range(nq)]
    ki = np.concatenate([np.full(last[i] + 1 - i, i, np.int32)
                         for i in range(nq)])
    qi = np.concatenate([np.arange(i, last[i] + 1, dtype=np.int32)
                         for i in range(nq)])
    return ki, qi


def _lanes(x, n: int):
    """A per-row statistic kept lane-broadcast, (rows, 128) with every lane
    of a row equal, as (rows, n): itself tiled along the lanes where n is
    whole 128s (no cross-lane move: a (rows, 1) column would pay an XLU
    broadcast a row group wherever it meets a (rows, n) operand), a lane
    slice under 128, a column broadcast for the rest."""
    if n % _LANES == 0:
        return x if n == _LANES else pltpu.repeat(x, n // _LANES, axis=1)
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _softmax_update(s, v, m, l, acc):
    """One FA2 streaming-softmax update in VALUES: scores ``s`` (rows, cols)
    float32, already masked where the diagonal crosses them, the running max
    ``m`` and sum ``l`` lane-broadcast (rows, 128), ``acc`` (rows, Dv)
    float32. ``p`` goes to the MXU in v's dtype."""
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - _lanes(m_new, s.shape[1]))
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc * _lanes(corr, acc.shape[1]) + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _scores(q, k, scale, mask_rc=None, window=None):
    """q . k^T in float32; ``mask_rc`` = (rows, cols), each score's q and
    key POSITION, where the block crosses the diagonal or, with a
    ``window``, its trailing edge, else None (an interior block pays none
    of the mask's VPU passes). The causal backward hands k for q and q for
    k, and the positions of that transposed block."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * scale
    if mask_rc is not None:
        rows, cols = mask_rc
        keep = rows >= cols
        if window is not None:
            keep = keep & (rows - cols < window)
        s = jnp.where(keep, s, NEG_INF)
    return s


def _online_softmax_block(q, k, v, acc_sc, m_sc, l_sc, scale, mask_rc=None):
    """``_softmax_update`` on a grid step's own blocks and (rows, 128)
    scratch: the non-causal and the block-sparse forward, one k block a
    grid step."""
    m_sc[:], l_sc[:], acc_sc[:] = _softmax_update(
        _scores(q, k, scale, mask_rc), v, m_sc[:], l_sc[:], acc_sc[:])


def _block_iotas(block_q, block_k, qi, ki):
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + qi * block_q
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + ki * block_k
    return rows, cols


def _row(x):
    """(n, 128) with every lane of a row equal -> (1, n), lane-dense: how a
    per-row statistic (the log-sum-exp) leaves a kernel. A (n, 1) output
    pads the 1 to 128 lanes in HBM: 128 x the bytes, written by the kernel
    and read by whatever takes it next. In whole 128s the row is the
    diagonal of each (128, 128) group, summed down its zeros (exact; a
    select and fifteen adds a group where the transpose moves every vreg
    through the XLU)."""
    n = x.shape[0]
    if n % _LANES:
        return x.T[:1]
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    diagonal = jnp.where((rows & (_LANES - 1)) == cols, x, 0.0)
    groups = jnp.sum(diagonal.reshape(n // _LANES, _LANES, _LANES), axis=1,
                     keepdims=True)
    return jnp.concatenate(list(groups), axis=1)


def _col(row):
    """(1, n) lane-dense -> (n, 1): a per-row statistic (log-sum-exp, delta)
    as the column a (n, block_k) score block subtracts. The non-causal and
    the block-sparse backward pairs' (``_bwd_p_ds``); the causal backward
    keeps the row."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


def _write_out(o_ref, lse_ref, m, l, acc):
    """Write a query block's output and log-sum-exp from the running max
    and sum (lane-broadcast, (rows, 128)) and the accumulator."""
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / _lanes(l, acc.shape[1])).astype(o_ref.dtype)
    lse_ref[0] = _row(m + jnp.log(l))


# ------------------------------------------------------ forward (causal)
class ForwardPlan(NamedTuple):
    """The shapes of the causal forward for one (T, D, Dv, dtype), and what
    its static grid will run; the counts are of ONE (batch x head) row."""
    block_q: int            # query rows of a grid step
    span: int               # keys and values a grid step holds
    sub_block: int          # columns of one pass of the in-step loop
    grid_steps: int
    sub_blocks_run: int     # at or under the diagonal: none above it
    sub_blocks_masked: int  # those the diagonal crosses: one a row of them


# a span is at most this many sub-blocks (every walk of it is unrolled: code
# size and the kernel's compile time grow with it, 1 s at one sub-block a
# step, 2.5 s at four, 5 s at eight, and a prompt length's prefill compiles
# inside its first request's deadline; eight measured no faster) and this
# many bytes of K and V (each double-buffered in VMEM beside the float32
# score tiles, which at two sub-blocks an update pass the 16 MiB default)
_SPAN_SUB_BLOCKS = 4
_SPAN_BYTES = 4 << 20
_FWD_VMEM_BYTES = 32 << 20


# a window's sub-block is the smallest halving of the caller's block that
# still holds this many windows (and whole 128s): the band of a q block is
# ``sub + window`` columns wide and the kernel runs whole sub-blocks
_WINDOWS_A_SUB_BLOCK = 2


def _window_sub_block(t: int, sub: int, window: int) -> int:
    while sub % 2 == 0 and sub // 2 >= max(
            _LANES, _WINDOWS_A_SUB_BLOCK * window) and t % (sub // 2) == 0:
        sub //= 2
    return sub


def flash_forward_plan(t: int, d: int, dv: int, dtype,
                       block_q: int = DEFAULT_BLOCK_Q,
                       block_k: int = DEFAULT_BLOCK_K,
                       window=None) -> ForwardPlan:
    """What the causal forward runs for a call of length ``t`` (padded as
    ``flash_attention`` pads it), q.k width ``d``, v width ``dv``: the one
    place the kernel takes its shapes from, a pure function of what the call
    can see. The sub-block is the square matmul tile (``_pick_block``: the
    caller's block or the largest halving of it that divides the length),
    a q block is one row of them, and a span is as many of them as fit the
    two limits above, evened out over the length (T = 3,072 in 512s: two
    spans of three, not four and two). The last span of an awkward length
    may end past the keys: the loop stops at the diagonal, before them.

    ``window`` (a causal window shorter than the length: a row sees itself
    and the ``window - 1`` keys before it) keeps the shapes and bounds the
    lists from the other side too: a q block's spans start at the one that
    holds the first sub-block with a key inside the window, nothing before
    that sub-block is run, and a sub-block pays a mask where the diagonal
    OR the window's trailing edge crosses it."""
    t = _padded_len(t, min(block_q, block_k))
    sub = _pick_block(t, min(block_q, block_k))
    if window is not None:
        sub = _window_sub_block(t, sub, window)
    rows = t // sub
    widest = max(1, min(_SPAN_SUB_BLOCKS, _SPAN_BYTES // (
        sub * (d + dv) * jnp.dtype(dtype).itemsize)))
    n_sub = -(-rows // -(-rows // widest))
    # without a window: every span from the first, the causal half of the
    # sub-blocks, one masked a row of them
    first = [_first_block(i, sub, window) for i in range(rows)]
    return ForwardPlan(
        block_q=sub, span=n_sub * sub, sub_block=sub,
        grid_steps=sum(i // n_sub - first[i] // n_sub + 1
                       for i in range(rows)),
        sub_blocks_run=sum(i - first[i] + 1 for i in range(rows)),
        sub_blocks_masked=sum(
            1 for i in range(rows) for g in range(first[i], i + 1)
            if g == i or (window is not None
                          and not _inside_window(i, g, sub, window))))


def _turn_q(q_ref, q_sc, cos_ref, sin_ref, scale: float):
    """The q block as its whole key walk multiplies it, made ONCE a block
    into ``q_sc``: in float32, the first ``r`` lanes of the head rotated
    (rotate-half is ONE roll by ``r / 2`` inside those lanes against the
    sine with its first half negated, ``_signed_sin``; ``cos_ref`` /
    ``sin_ref`` are the block's (rows, r) float32 rows; None: no rotary
    embedding), the others passed through, every lane times the softmax
    scale, then ONE rounding to the operand's type."""
    x = q_ref[0].astype(jnp.float32)
    if scale != 1.0:
        x = x * scale
    if cos_ref is None:
        q_sc[:] = x.astype(q_sc.dtype)
        return
    r = cos_ref.shape[-1]
    head = x[:, :r]
    head = head * cos_ref[:] + pltpu.roll(head, r // 2, 1) * sin_ref[:]
    if r < x.shape[1]:
        q_sc[:] = x.astype(q_sc.dtype)
    q_sc[:, :r] = head.astype(q_sc.dtype)


def _fwd_causal_kernel(qi_arr, si_arr, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       acc_sc, m_sc, l_sc, q_sc=None, *, scale: float,
                       sub: int, n_sub: int, block: int = 1,
                       q_scale: float = 1.0, cos_ref=None, sin_ref=None):
    """A q block (one row of square sub-blocks) against a span of ``n_sub``
    of them. Every walk is unrolled, two sub-blocks a softmax update (one
    q.k matmul 2 x sub columns wide: the max, the rescale and the
    accumulator's pass are paid half as often), and one basic block, so the
    scheduler lays the next update's matmul beside this one's softmax. The
    running max, sum and accumulator are values inside a walk and touch
    their scratch between walks. ``block`` > 1: the diagonal's sub-block
    under the block-causal mask (a power of two that divides the sub-block:
    a row's block ends at ``row | (block - 1)``)."""
    f = pl.program_id(1)
    qi, si = qi_arr[f], si_arr[f]
    q = q_ref[0] if q_sc is None else None
    # sub-blocks of this span wholly under the diagonal: all of them, or, in
    # the q block's last span, those before the one the diagonal crosses
    under = jnp.minimum(qi - si * n_sub, n_sub)

    @pl.when(si == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        if q_sc is not None:
            _turn_q(q_ref, q_sc, cos_ref, sin_ref, q_scale)

    if q_sc is not None:
        q = q_sc[:]

    def update(carry, j, width, diagonal=False):
        """``width`` sub-blocks from the span's j-th on, the last of them the
        diagonal's (square, so the mask is of local indices) if so told."""
        at = j * sub if isinstance(j, int) else pl.multiple_of(j * sub, sub)
        s = _scores(q, k_ref[0, pl.ds(at, width * sub), :], scale)
        if diagonal:
            rows, cols = _block_iotas(sub, sub, 0, 0)
            if block > 1:
                rows = rows | (block - 1)
            last = jnp.where(rows >= cols, s[:, -sub:], NEG_INF)
            s = last if width == 1 else jnp.concatenate(
                [s[:, :-sub], last], axis=1)
        return _softmax_update(s, v_ref[0, pl.ds(at, width * sub), :], *carry)

    def walk(j, n):
        """``n`` sub-blocks under the diagonal from the j-th, scratch to
        scratch."""
        carry = m_sc[:], l_sc[:], acc_sc[:]
        for i in range(0, n - 1, 2):
            carry = update(carry, j + i, 2)
        if n % 2:
            carry = update(carry, j + n - 1, 1)
        m_sc[:], l_sc[:], acc_sc[:] = carry

    @pl.when(under == n_sub)
    def _under():
        walk(0, n_sub)

    @pl.when(under < n_sub)
    def _diagonal():
        # the last update takes the diagonal's sub-block with the one before
        # it; what lies before those is walked by the binary digits of its
        # count, most significant first: each digit a walk of its own size
        before = jnp.maximum(under - 1, 0)
        j = 0
        for bit in reversed(range(max(n_sub - 2, 0).bit_length())):
            digit = before & (1 << bit)
            pl.when(digit != 0)(functools.partial(walk, j, 1 << bit))
            j = j + digit

        @pl.when(under == 0)
        def _first():
            _write_out(o_ref, lse_ref, *update(
                (m_sc[:], l_sc[:], acc_sc[:]), 0, 1, diagonal=True))

        if n_sub > 1:
            @pl.when(under > 0)
            def _pair():
                _write_out(o_ref, lse_ref, *update(
                    (m_sc[:], l_sc[:], acc_sc[:]), under - 1, 2,
                    diagonal=True))


def _causal_forward(q, k, v, scale, block_q, block_k, window=None,
                    block=None, sink=None, turn=None, o_rows=None):
    """``sink`` (with a window): (B*H, 1, 128) float32, each row's sink
    logit lane-broadcast. k and v may hold FEWER heads than q (grouped
    queries): q's head ``b`` reads theirs ``b // rep``, nothing is repeated.
    ``o_rows`` (q's heads a batch row; the value width whole 128 lanes): the
    output is written IN PLACE as the rows the out-projection reads, (B, T,
    heads * Dv), a head its block of lanes, not head-major. ``turn`` =
    (softmax scale, cos, sin): the q block is scaled and rotated in VMEM at
    the start of its key walk (``_turn_q``; cos / sin (T, r) float32 or
    None), where ``scale`` then is 1.0."""
    bh, t, d = q.shape
    dv = v.shape[2]
    rep = bh // k.shape[0]
    kv = (lambda b: b // rep) if rep > 1 else (lambda b: b)
    plan = flash_forward_plan(t, d, dv, q.dtype, block_q, block_k, window)
    sub, n_sub = plan.sub_block, plan.span // plan.sub_block
    qi_arr, si_arr = _causal_spans(t // sub, n_sub, sub, window)
    if window is None:
        kernel, name = functools.partial(
            _fwd_causal_kernel, scale=scale, sub=sub, n_sub=n_sub,
            **({"block": block} if block else {})), "flash_fwd"
        pairs = t * t // 2                      # the causal half
    else:
        kernel, name = functools.partial(
            _fwd_window_kernel, scale=scale, sub=sub, n_sub=n_sub,
            window=window), "flash_fwd_win"
        pairs = plan.sub_blocks_run * sub * sub
    # further inputs, handed to the body by name: they sit between the
    # three operands and the outputs in the call's order
    extra, extra_specs, scratch = {}, [], []
    if turn is not None:
        q_scale, cos, sin = turn
        kernel = functools.partial(kernel, q_scale=q_scale)
        if cos is not None:
            extra.update(cos_ref=cos, sin_ref=sin)
            extra_specs += [pl.BlockSpec(
                (sub, cos.shape[-1]), lambda b, f, qa, sa: (qa[f], 0))] * 2
        scratch = [pltpu.VMEM((sub, d), q.dtype)]
    if sink is not None:
        extra["sink_ref"] = sink
        extra_specs.append(pl.BlockSpec((1, 1, _LANES),
                                        lambda b, f, qa, sa: (b, 0, 0)))
    if extra:
        body, names = kernel, tuple(extra)
        kernel = lambda qa, sa, q, k, v, *rest: body(
            qa, sa, q, k, v, *rest[len(names):],
            **dict(zip(names, rest[:len(names)])))
    if o_rows:
        o_shape = (bh // o_rows, t, o_rows * dv)
        at_o = lambda g, i: (g // o_rows, i, g % o_rows)
    else:
        o_shape, at_o = (bh, t, dv), lambda g, i: (g, i, 0)
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, len(qi_arr)),
            in_specs=[
                pl.BlockSpec((1, sub, d), lambda b, f, qa, sa: (b, qa[f], 0)),
                pl.BlockSpec((1, plan.span, d),
                             lambda b, f, qa, sa: (kv(b), sa[f], 0)),
                pl.BlockSpec((1, plan.span, dv),
                             lambda b, f, qa, sa: (kv(b), sa[f], 0)),
            ] + extra_specs,
            out_specs=(
                pl.BlockSpec((1, sub, dv),
                             lambda b, f, qa, sa: at_o(b, qa[f])),
                pl.BlockSpec((1, 1, sub), lambda b, f, qa, sa: (b, 0, qa[f])),
            ),
            scratch_shapes=[pltpu.VMEM((sub, dv), jnp.float32),
                            pltpu.VMEM((sub, _LANES), jnp.float32),
                            pltpu.VMEM((sub, _LANES), jnp.float32)] + scratch,
        ),
        out_shape=(jax.ShapeDtypeStruct(o_shape, q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, t), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_FWD_VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * bh * pairs * (d + dv)),
            bytes_accessed=int((q.size + k.size + 2 * v.size) * q.dtype.itemsize),
            transcendentals=int(bh * pairs)),
    )(jnp.asarray(qi_arr), jnp.asarray(si_arr), q, k, v, *extra.values())


def _fwd_window_kernel(qi_arr, si_arr, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       acc_sc, m_sc, l_sc, q_sc=None, *, scale: float,
                       sub: int, n_sub: int, window: int, sink_ref=None,
                       q_scale: float = 1.0, cos_ref=None, sin_ref=None):
    """The causal forward under a window: a q block against one of the spans
    that hold a key it sees. Of the span's sub-blocks those from the first
    with a key inside the window up to the diagonal's are run, one softmax
    update each, scratch to scratch; the diagonal's and the one(s) the
    window's trailing edge crosses (a diagonal too: ``row - col = window``)
    pay the mask, the ones between them none. A row may see nothing of the
    trailing sub-block: what its update leaves in the running sum is wiped
    by the next update's rescale (``exp(NEG_INF - m)`` is 0), and the
    diagonal's sub-block always holds the row's own key. ``sink_ref`` (1, 1,
    128): the head's sink logit, the softmax's initial state (mass ``exp(b)``
    and no value; the log-sum-exp written counts it)."""
    f = pl.program_id(1)
    qi, si = qi_arr[f], si_arr[f]
    q = q_ref[0] if q_sc is None else None
    first = _first_block(qi, sub, window)

    @pl.when(si == first // n_sub)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        if sink_ref is None:
            m_sc[:] = jnp.full_like(m_sc, NEG_INF)
            l_sc[:] = jnp.zeros_like(l_sc)
        else:
            m_sc[:] = jnp.broadcast_to(sink_ref[0], m_sc.shape)
            l_sc[:] = jnp.ones_like(l_sc)
        if q_sc is not None:
            _turn_q(q_ref, q_sc, cos_ref, sin_ref, q_scale)

    if q_sc is not None:
        q = q_sc[:]

    def update(j, g, masked):
        mask_rc = _block_iotas(sub, sub, qi, g) if masked else None
        s = _scores(q, k_ref[0, pl.ds(j * sub, sub), :], scale, mask_rc,
                    window)
        m_sc[:], l_sc[:], acc_sc[:] = _softmax_update(
            s, v_ref[0, pl.ds(j * sub, sub), :], m_sc[:], l_sc[:], acc_sc[:])

    for j in range(n_sub):
        g = si * n_sub + j
        run = (g >= first) & (g <= qi)
        inside = (g < qi) & _inside_window(qi, g, sub, window)
        pl.when(run & inside)(functools.partial(update, j, g, False))
        pl.when(run & jnp.logical_not(inside))(
            functools.partial(update, j, g, True))

    @pl.when(si == qi // n_sub)
    def _write():
        _write_out(o_ref, lse_ref, m_sc[:], l_sc[:], acc_sc[:])


# ------------------------------------- forward (non-causal, t_q and t_k apart)
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc,
                *, scale: float, num_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    _online_softmax_block(q_ref[0], k_ref[0], v_ref[0],
                          acc_sc, m_sc, l_sc, scale)

    @pl.when(ki == num_k - 1)
    def _finalize():
        _write_out(o_ref, lse_ref, m_sc[:], l_sc[:], acc_sc[:])


def _flash_forward(q, k, v, scale, causal, block_q, block_k, window=None,
                   block=None, sink=None):
    if causal:              # self-attention: ``flash_attention`` saw to that
        return _causal_forward(q, k, v, scale, block_q, block_k, window,
                               block, sink)
    bh, t_q, d = q.shape
    t_k, dv = k.shape[1], v.shape[2]
    bq = _pick_block(t_q, block_q)
    bk = _pick_block(t_k, block_k)
    nq, nk = t_q // bq, t_k // bk
    rep = bh // k.shape[0]      # grouped queries: k, v at their own heads
    kv = (lambda b: b // rep) if rep > 1 else (lambda b: b)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, num_k=nk),
        name="flash_fwd",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (kv(b), j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (kv(b), j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ),
        out_shape=(jax.ShapeDtypeStruct((bh, t_q, dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, t_q), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((bq, dv), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * bh * t_q * t_k * (d + dv)),
            bytes_accessed=int((q.size + k.size + 2 * v.size) * q.dtype.itemsize),
            transcendentals=int(bh * t_q * t_k)),
    )(q, k, v)


# -------------------------------------------------------------------- backward
def _bwd_p_ds(q, k, v, do, lse, delta, scale, mask_rc=None):
    """Recompute P and dS for one block, q rows down and keys across: the
    non-causal and the block-sparse dq / dkv pairs, each kernel of which
    calls it (so a pair recomputes P twice). ``lse`` and ``delta`` arrive as
    the query block's lane-dense rows and are turned into columns
    (``_col``). The causal backward has neither (``_bwd_causal_kernel``)."""
    p = jnp.exp(_scores(q, k, scale, mask_rc) - _col(lse))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - _col(delta))
    if scale != 1.0:
        ds = ds * scale
    ds = ds.astype(k.dtype)
    return p, ds


# ----------------------------------------------------- backward (causal)
class BackwardPlan(NamedTuple):
    """The shapes of the causal backward for one (T, D, dtype, window), and
    what its static grid will run; the counts are of ONE (batch x head)
    row."""
    block: int                  # rows of a q block = keys of a k block
    pairs_run: int              # (k block, q block) pairs: the grid's steps
    pairs_masked: int           # those the diagonal or the window's edge crosses
    matmuls_per_pair: int
    dq_accumulator_bytes: int   # the head's float32 dq, resident in VMEM
    diagonal_quarters: int      # of the diagonal's pair, the quarters run


# the causal backward keeps a head's whole float32 dq in VMEM (128 MiB on a
# v5e): up to this many bytes of it, T = 131,072 at d = 128, eight times the
# longest cell's; past it ``_causal_backward`` refuses the call (the pair of
# triangular kernels that needed no such buffer went with PR 47: no test, no
# cell and no model here is within a factor of four of the bound)
_BWD_DQ_BYTES = 64 << 20
_BWD_VMEM_BYTES = 32 << 20     # the blocks and the score tiles beside it


def flash_backward_plan(t: int, d: int, dtype, window=None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K) -> BackwardPlan:
    """What the causal backward runs for a call of length ``t`` (padded as
    ``flash_attention`` pads it) at head width ``d``: the one place
    ``_causal_backward`` takes its shapes from, a pure function of what the
    call can see. The block is the forward's sub-block (``_pick_block``),
    square; the pairs are ``_causal_pairs_colmajor``'s, a k block against
    the q blocks from its diagonal down and, with a ``window``, as far as
    the last that sees it. T = 1,024 in 512s: 3 pairs, 2 of them masked,
    where a rectangular grid ran the square's 4; and of a diagonal pair's
    four quarters the one above the diagonal is not run either, where half
    a block is whole 128s (measured on the chip against blocks of 256,
    which walk the same 10 quarters in 10 grid steps: 1.38 against 1.79 ms
    at 128 x 1,024 x 96). ``dtype`` is there as ``flash_forward_plan`` has
    it; nothing depends on it yet: every accumulator is float32."""
    t = _padded_len(t, min(block_q, block_k))
    block = _pick_block(t, min(block_q, block_k))
    ki, qi = _causal_pairs_colmajor(t // block, block, window)
    return BackwardPlan(
        block=block, pairs_run=len(ki),
        pairs_masked=sum(
            1 for k, q in zip(ki, qi)
            if k == q or (window is not None
                          and not _inside_window(q, k, block, window))),
        matmuls_per_pair=5,
        dq_accumulator_bytes=t * d * 4,
        # half a block has to be whole 128-lane groups of the statistics' row
        diagonal_quarters=3 if block % (2 * _LANES) == 0 else 4)


def _bwd_causal_kernel(ki_arr, qi_arr, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, dq_ref, dk_sc, dv_sc, dq_sc,
                       *, scale, block, num_q, split, window=None):
    """A k block against one of the q blocks that see it, all three
    gradients from ONE recompute of P, on the TRANSPOSED scores: S^T = K Q^T
    is (keys, q rows), so the log-sum-exp and delta meet it as the
    lane-dense (1, block) rows they arrive in, broadcast along sublanes: no
    column, no cross-lane move. P^T = exp(S^T - lse), dP^T = V dO^T, dS^T =
    P^T (dP^T - delta); dV += P^T dO and dK += dS^T Q are plain products,
    dQ[qi] += (dS^T)^T K the one that contracts the leading dimension. Five
    matmuls and one exp a pair.

    dk and dv accumulate over the k block's pairs in (block, d) scratch; dq
    accumulates in the float32 scratch of the WHOLE head, in the rows of the
    pair's q block. The pairs are k-major, so q block i has taken its last
    product when k block i's first pair (the diagonal's) is done: that step
    writes dq's block i out, which is why dq's output block follows ``ki``
    as dk's and dv's do."""
    f = pl.program_id(1)
    ki, qi = ki_arr[f], qi_arr[f]
    rows = pl.ds(pl.multiple_of(qi * block, block), block)

    @pl.when(f == 0)
    def _head():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    @pl.when(qi == ki)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def _tile(masked, keys=(0, block), cols=(0, block)):
        """The pair's keys ``keys`` against its q rows ``cols`` (static
        halves of the block, or all of it)."""
        kk, qq = pl.ds(*keys), pl.ds(*cols)
        q, do = q_ref[0, qq, :], do_ref[0, qq, :]
        k, v = k_ref[0, kk, :], v_ref[0, kk, :]
        mask = None
        if masked:
            # transposed: keys down the sublanes, q rows along the lanes
            shape = (keys[1], cols[1])
            kpos = jax.lax.broadcasted_iota(jnp.int32, shape, 0) \
                + (ki * block + keys[0])
            qpos = jax.lax.broadcasted_iota(jnp.int32, shape, 1) \
                + (qi * block + cols[0])
            mask = qpos, kpos
        p = jnp.exp(_scores(k, q, scale, mask, window) - lse_ref[0, :, qq])
        dp = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, qq])
        if scale != 1.0:
            ds = ds * scale
        ds = ds.astype(k.dtype)
        dv_sc[kk, :] += jax.lax.dot_general(p.astype(do.dtype), do,
                                            (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)
        dk_sc[kk, :] += jax.lax.dot_general(ds, q, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)
        at = pl.ds(pl.multiple_of(qi * block + cols[0], cols[1]), cols[1])
        dq_sc[at, :] += jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == ki)
    def _diagonal():
        if split:
            # the quarter above the diagonal (the later keys against the
            # earlier rows) is never run: 3 of the tile's 4 quarters
            half = block // 2
            _tile(True, keys=(0, half))
            _tile(True, keys=(half, half), cols=(half, half))
        else:
            _tile(True)
        dq_ref[0] = dq_sc[rows, :].astype(dq_ref.dtype)

    interior = qi > ki
    if window is not None:
        interior = interior & _inside_window(qi, ki, block, window)
        pl.when(jnp.logical_not(interior) & (qi > ki))(
            functools.partial(_tile, True))
    pl.when(interior)(functools.partial(_tile, False))

    @pl.when(qi == _last_block(ki, block, window, num_q))
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _causal_backward(q, k, v, do, lse, delta, scale, block_q, block_k,
                     window=None):
    """dq, dk, dv of causal self-attention in ONE call, named
    ``flash_bwd_dkv`` (``flash_bwd_dkv_win`` under a window): it is the dkv
    kernel, grid and all, which now returns dq too. No call is named
    ``flash_bwd_dq`` in a causal backward any more."""
    bh, t, d = q.shape
    plan = flash_backward_plan(t, d, q.dtype, window, block_q, block_k)
    if plan.dq_accumulator_bytes > _BWD_DQ_BYTES:
        raise NotImplementedError(
            f"flash_attention backward: a head's float32 dq at length {t}, "
            f"width {d} is {plan.dq_accumulator_bytes} bytes, over the "
            f"{_BWD_DQ_BYTES} the causal backward keeps in VMEM")
    block, nq = plan.block, t // plan.block
    ki_arr, qi_arr = _causal_pairs_colmajor(nq, block, window)
    # the kernel is traced with the window only where there is one
    win = {} if window is None else {"window": window}
    at_q = pl.BlockSpec((1, block, d), lambda b, f, ka, qa: (b, qa[f], 0))
    at_k = pl.BlockSpec((1, block, d), lambda b, f, ka, qa: (b, ka[f], 0))
    stat = pl.BlockSpec((1, 1, block), lambda b, f, ka, qa: (b, 0, qa[f]))
    tiles = plan.pairs_run * block * block
    dk, dv, dq = pl.pallas_call(
        functools.partial(_bwd_causal_kernel, scale=scale, block=block,
                          num_q=nq, split=plan.diagonal_quarters == 3,
                          **win),
        name="flash_bwd_dkv" + ("" if window is None else "_win"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, plan.pairs_run),
            in_specs=[at_q, at_k, at_k, at_q, stat, stat],
            out_specs=(at_k, at_k, at_k),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                            pltpu.VMEM((block, d), jnp.float32),
                            pltpu.VMEM((t, d), jnp.float32)],
        ),
        out_shape=(jax.ShapeDtypeStruct((bh, t, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, t, d), v.dtype),
                   jax.ShapeDtypeStruct((bh, t, d), q.dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # beside the blocks and the score tiles, the head's dq as VMEM
            # holds it: d padded to whole 128 lanes
            vmem_limit_bytes=_BWD_VMEM_BYTES + t * -(-d // _LANES) * _LANES * 4),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * bh * tiles * d * plan.matmuls_per_pair),
            bytes_accessed=int(7 * q.size * q.dtype.itemsize),
            transcendentals=int(bh * tiles)),
        # dk, dv and dq take k's, v's and q's buffers (operands 3, 4, 2 after
        # the two pair lists): k and v block i are read by k block i's pairs
        # alone and dk, dv block i written after them; q block i is read last
        # by k block i's FIRST pair and dq block i written after its last.
        # All three gradients at once would else stand beside all four
        # inputs: +183 MB on the step of 32 x 16,384 x 192 (AOT, PR 47)
        input_output_aliases={3: 0, 4: 1, 2: 2},
    )(jnp.asarray(ki_arr), jnp.asarray(qi_arr), q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------ backward (non-causal, t_q and t_k apart)
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc,
                   *, scale, num_k):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    _, ds = _bwd_p_ds(q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
                      delta_ref[0], scale)
    dq_sc[:] += jax.lax.dot_general(ds, k_ref[0], (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(ki == num_k - 1)
    def _finalize():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_sc, dv_sc, *, scale, num_q):
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    p, ds = _bwd_p_ds(q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
                      delta_ref[0], scale)
    dv_sc[:] += jax.lax.dot_general(p.astype(do_ref.dtype), do_ref[0],
                                    (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    dk_sc[:] += jax.lax.dot_general(ds, q_ref[0], (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _flash_backward(res, g, scale, causal, block_q, block_k, window=None,
                    block=None):
    if block:
        raise NotImplementedError(
            "flash_attention(block=): the block-causal mask is the forward's "
            "(a prefill's); no backward kernel carries it")
    q, k, v, o, lse = res
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None]        # (bh, 1, t_q), as lse
    if causal:              # self-attention: ``flash_attention`` saw to that
        return _causal_backward(q, k, v, do, lse, delta, scale, block_q,
                                block_k, window)
    bq = _pick_block(t_q, block_q)
    bk = _pick_block(t_k, block_k)
    nq, nk = t_q // bq, t_k // bk

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, num_k=nk),
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
        functools.partial(_bwd_dkv_kernel, scale=scale, num_q=nq),
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
        dv = v.shape[-1]
        # the backward kernels take ONE width: a narrower v (latent
        # attention: q.k 192, v 128) goes in with zero columns, and so do o
        # and its cotangent: P v, dO v^T and rowsum(dO . o) are what they
        # were, the extra columns of dv come out zero and are dropped
        wide = lambda x: x if dv == d else jnp.pad(
            x, ((0, 0),) * (x.ndim - 1) + ((0, d - dv),))
        dq, dk, dv_wide = backward(
            (_to_bhtd(q), _to_bhtd(k), _to_bhtd(wide(v)),
             _to_bhtd(wide(o.reshape(b, t, h, dv))), lse[:, None]),
            _to_bhtd(wide(g)), *static)
        return _to_bthd(dq, b), _to_bthd(dk, b), \
            _to_bthd(dv_wide, b)[..., :dv]

    attend.defvjp(fwd, bwd)
    return attend


_flash_bthd = _attention_vjp(_flash_forward, _flash_backward)


def block_mask_supports(block) -> bool:
    """Whether the forward carries a block-causal mask of ``block``
    positions: a power of two that divides a sub-block's 128s."""
    return isinstance(block, int) and block > 1 and _LANES % block == 0


def _static_masks(t: int, causal: bool, window, block, sink):
    """A call's ``window`` and ``block`` as the plans take them (a window
    that reaches the whole length is None unless a sink comes with it; a
    block of 1 is None), or the reason the kernels do not carry them."""
    if window is not None:
        if not causal or int(window) < 1:
            raise ValueError(f"flash_attention: window={window} is a causal "
                             "window of at least the row's own key")
        window = int(window) if window < t or sink is not None else None
    if sink is not None and window is None:
        raise ValueError("flash_attention: a sink is a window layer's (the "
                         "windowed forward carries it)")
    if block is not None and int(block) > 1:
        if not causal or window is not None or t % int(block) \
                or not block_mask_supports(block):
            raise ValueError(
                f"flash_attention: block={block} is a block-causal mask of a "
                "power of two that divides 128 and the length, with no "
                "window")
        return window, int(block)
    return window, None


def _sink_rows(sink, b: int, h: int):
    """A head's sink logit (H,) as the windowed forward reads it: (B*H, 1,
    128) float32, lane-broadcast."""
    return jnp.broadcast_to(jnp.tile(sink.astype(jnp.float32), b)[
        :, None, None], (b * h, 1, _LANES))


def flash_attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
                    window: Optional[int] = None, block: Optional[int] = None,
                    sink=None):
    """q, k: (B, T, H, D), v: (B, T, H, Dv) → (B, T, H, Dv); Dv <= D, the
    softmax scale is D's. Differentiable; bf16-friendly.

    ``window`` (a Python int, causal only): row i sees the keys j with ``0 <=
    i - j < window``. The same kernel family with the window in its plan
    and pair lists (``flash_forward_plan``); its calls are named
    ``flash_fwd_win`` / ``flash_bwd_dkv_win``. A
    window that reaches the whole length is no window: that call's plans,
    lists and programs are those of a call without one.

    ``block`` (a Python int, causal, no window, FORWARD only): the
    block-causal mask, row i sees the keys j with ``j // block <= i //
    block`` (``block_mask_supports``); 1 or None: the causal mask.

    ``sink`` ((H,), with a window, FORWARD only): a head's learned sink
    logit, which stands beside the row's scaled scores in the softmax's sum
    and carries no value: ``p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))``.
    The call is the windowed kernel whatever the length.

    Causal self-attention at a length the kernels cannot tile is padded at
    the END of the sequence and the pad rows sliced off the output: under
    the causal mask a pad key is visible only to pad queries, so no kept
    row changes, and the pad rows' cotangents are zero. Other forms raise
    on an untileable length (``flash_supports`` tells callers beforehand).
    """
    t, d = q.shape[1], q.shape[-1]
    window, block = _static_masks(t, causal, window, block, sink)
    if not flash_supports(t, k.shape[1], causal, block_q, block_k):
        raise ValueError(
            f"flash_attention: lengths ({t}, {k.shape[1]}) with causal="
            f"{causal} do not tile in blocks ({block_q}, {block_k}): q in "
            "whole 128s or one block, k in whole 8s — causal is "
            "self-attention (one length), and only that is padded, to a "
            "multiple of 128")
    if causal:
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
    if sink is not None:        # the forward alone: no rule for a gradient
        b, h = q.shape[0], q.shape[2]
        o, _ = _flash_forward(_to_bhtd(q), _to_bhtd(k), _to_bhtd(v), 1.0,
                              True, int(block_q), int(block_k), window,
                              sink=_sink_rows(sink, b, h))
        return _to_bthd(o, b)[:, :t]
    # (a pad key lies in a LATER block than every kept row: under the block
    # mask too it is visible to pad queries alone)
    o = _flash_bthd(q, k, v, (1.0, bool(causal), int(block_q), int(block_k),
                              window) + ((block,) if block else ()))
    return o[:, :t]


# ------------------------------------------ a prefill's forward, as projected
def _signed_sin(sin):
    """``sin`` (T, r) with its first half negated: rotate-half, ``[-x2, x1]
    * sin``, is then ``roll(x, r / 2) * _signed_sin(sin)``, one roll."""
    half = sin.shape[-1] // 2
    return jnp.concatenate([-sin[..., :half], sin[..., half:]], axis=-1)


def _in_place(width: int) -> bool:
    """Whether the kernel writes an output of this head width where the
    out-projection reads it, as a lane block of (B, T, H * width) rows:
    Mosaic takes a block of whole 128-lane groups of a row (a 192-wide head
    is none, nor is a squeezed head of (B, T, H, 192): the block's last two
    dimensions would be (1, 192)). The INPUTS stay head-major whatever
    their width: XLA lays the projection's (or the norm's, the rotation's)
    output out by head at no pass of its own, and a row block's 256-byte
    pieces read slower than a head's contiguous rows (ranked on the chip,
    PERF.md section 6, PR 52)."""
    return width % _LANES == 0


def prefill_supports(d: int, rotary_dim: Optional[int]) -> bool:
    """Whether ``flash_prefill`` rotates ``rotary_dim`` leading columns of a
    ``d``-wide head (None: no rotary embedding): an even count of them, at
    most the head's (the roll is by half of it, inside those lanes)."""
    return rotary_dim is None or (
        rotary_dim % 2 == 0 and 0 < rotary_dim <= d)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "scale", "window", "block", "block_q", "block_k"))
def flash_prefill(q, k, v, cos=None, sin=None, sink=None, *,
                  scale: Optional[float] = None,
                  window: Optional[int] = None, block: Optional[int] = None,
                  block_q: int = DEFAULT_BLOCK_Q,
                  block_k: int = DEFAULT_BLOCK_K):
    """Causal self-attention of a PREFILL on q, k and v as the projections
    made them; FORWARD only (a caller that needs a gradient takes
    :func:`flash_attention`). q: (B, T, H, D), NOT rotated and NOT scaled;
    k: (B, T, KV, D), rotated (a cache keeps it so: 4 - 8 heads, one small
    pass outside); v: (B, T, KV, Dv); KV divides H -> (B, T, H, Dv).

    What ``flash_attention``'s callers do in passes around it is done
    inside the one kernel call: q's head ``h`` reads K and V of head ``h //
    (H / KV)`` by index map (nothing is repeated); a q block is rotated by
    ``cos`` / ``sin`` ((T, r) float32, the model's tables: the first ``r``
    columns of a head rotate-half, the others pass; None: no rotary
    embedding) and scaled in float32 in VMEM at the start of its key walk,
    rounded ONCE (``_turn_q``); an output whose head is whole 128 lanes is
    written in place as the (B, T, H * Dv) rows the out-projection reads
    (``_in_place``); q, k and v go in head-major, which XLA makes the layout
    their producers write. ``window``, ``sink``, ``block``:
    ``flash_attention``'s.
    Under ``jax.jit(inline=True)``: the kernel's body is traced once a
    shape a process, not once a call site."""
    b, t, h, d = q.shape
    dv = v.shape[-1]
    window, block = _static_masks(t, True, window, block, sink)
    pad = _padded_len(t, min(block_q, block_k)) - t
    if pad:         # at the end: a pad key is visible to pad queries alone
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    if cos is not None:
        cos, sin = (jnp.pad(x.astype(jnp.float32), ((0, pad), (0, 0)))
                    for x in (cos, _signed_sin(sin)))
    o, _ = _causal_forward(
        _to_bhtd(q), _to_bhtd(k), _to_bhtd(v), 1.0, block_q, block_k, window,
        block, None if sink is None else _sink_rows(sink, b, h),
        turn=(1.0 / math.sqrt(d) if scale is None else scale, cos, sin),
        o_rows=h if _in_place(dv) else None)
    o = o.reshape(b, t + pad, h, dv) if _in_place(dv) else _to_bthd(o, b)
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
        _write_out(o_ref, lse_ref, m_sc[:], l_sc[:], acc_sc[:])


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
