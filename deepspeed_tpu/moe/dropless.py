"""Dropless top-k routing and the routed SwiGLU expert MLP.

``sharded_moe.py`` is the reference's GShard form: top-1 or top-2, a
capacity per expert, overflow dropped, tokens scattered into an ``(E, C, D)``
buffer and every expert run over its whole buffer. Today's open MoE models
(OLMoE, Qwen-MoE, DeepSeek, Moonlight) route top-k for k up to 8 with NO
capacity: every (token, expert) pair is computed. Here the pairs are sorted
by expert and the experts see ragged groups — group sizes instead of
padding — so nothing is dropped and no slot is wasted:

* ``route_topk``: router matmul, a score for ALL experts (their softmax,
  or each one's sigmoid — the DeepSeek-V3 convention) and top-k of the
  scores in float32; weights are the chosen scores, renormalised only where
  the model says so (OLMoE: ``norm_topk_prob`` false) and scaled where it
  says so (``routed_scaling_factor``); a per-expert ``bias`` joins the
  scores for the choice alone;
* ``balance_bias``: the aux-loss-free rule that moves such a bias once a
  step from the pairs each expert was routed;
* ``routed_mlp``: ``sum_j w[t, j] * down_e(silu(gate_e(x_t)) * up_e(x_t))``
  over the k chosen experts ``e = experts[t, j]`` — or, told that the leaves
  hold a chip's SHARE of the experts (``first``: the router's number of the
  first one held), over those of the k that are held here: the router still
  chooses among all, a pair that fell on another chip's expert takes no row
  tile and adds exactly zero, and a served call none of whose pairs is held
  (a decode step, most of the time) runs no expert kernel at all. Nothing
  stands in for the other chips or their traffic. Where the program is for
  one TPU device and the widths tile, the three contractions are the Pallas
  grouped matmul (``ops/pallas/grouped_matmul.py``) reading the stacked
  ``(L, E, ...)`` leaves in place; otherwise — the CPU, a multi-device mesh,
  training — ``jax.lax.ragged_dot`` over the sorted rows, which is also the
  reference the kernel is tested against. On either path a share moves only
  the rows of the pairs it holds, in a buffer of ``share_capacity`` rows
  (the kernel's: wherever a call holds more pairs than that, a prefill, and
  a float32 row is at most ``_SCATTER_ROW_BYTES``), and what a call holds
  beyond that goes through the same code in further chunks of that size. The path is chosen by what the code observes, never
  by an option and never by a failure;
* ``load_balancing_loss``: the Switch / Hugging Face auxiliary loss from
  per-layer sums, so a scanned trunk can carry them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.telemetry.scopes import scope


def route_topk(x, router_w, k: int, renormalize: bool = False,
               scoring: str = "softmax", scale: float = 1.0, bias=None):
    """x (T, D), router_w (D, E) -> ``probs`` (T, E) float32 scores of all
    experts (``scoring``: their ``softmax``, or each one's ``sigmoid``),
    ``weights`` (T, k) float32 = the k largest scores, divided by their sum
    with ``renormalize``, times ``scale``; ``experts`` (T, k) int32.
    ``bias`` (E,): a per-expert bias for the SELECTION only (aux-loss-free
    balancing, arXiv:2408.15664): the k experts are the largest of ``probs +
    bias``, their weights ``probs`` at those experts, without it; it takes
    no gradient."""
    with scope("moe/router"):
        # "highest": a TPU's default float32 matmul is one bf16 pass
        logits = jnp.matmul(x.astype(jnp.float32),
                            router_w.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        if bias is None:
            weights, experts = jax.lax.top_k(probs, k)
        else:
            _, experts = jax.lax.top_k(
                probs + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
            weights = jnp.take_along_axis(probs, experts, axis=-1)
        if renormalize:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + 1e-20)
        if scale != 1.0:
            weights = weights * scale
        return probs, weights, experts.astype(jnp.int32)


def load_balancing_loss(expert_tokens, prob_sums, n_tokens):
    """``E * sum_e f_e * P_e`` (Fedus et al. 2021 eq. 4, as Hugging Face's
    ``load_balancing_loss_func`` computes it over all layers at once):
    ``expert_tokens`` (L, E) pairs routed to each expert, ``prob_sums``
    (L, E) the router probabilities summed over the tokens, ``n_tokens`` the
    tokens a layer saw. f_e counts every one of a token's k choices; the
    gradient flows through P_e only."""
    L, E = prob_sums.shape
    denom = jnp.float32(L * n_tokens)
    f = jnp.sum(expert_tokens.astype(jnp.float32), axis=0) / denom
    p = jnp.sum(prob_sums.astype(jnp.float32), axis=0) / denom
    return E * jnp.sum(jax.lax.stop_gradient(f) * p)


def balance_bias(bias, expert_pairs, rate: float):
    """The aux-loss-free balancing rule (arXiv:2408.15664, as torchtitan's
    MoE applies it), one routed layer a row: ``bias`` (L, E) float32,
    ``expert_pairs`` (L, E) the pairs each expert was routed in the step ->
    ``bias + d - mean(d)`` with ``d = rate * sign(mean(n) - n)``: an expert
    under the mean load becomes likelier to be chosen, one over it less."""
    n = expert_pairs.astype(jnp.float32)
    d = rate * jnp.sign(jnp.mean(n, axis=-1, keepdims=True) - n)
    return bias + d - jnp.mean(d, axis=-1, keepdims=True)


def _use_kernel(x, w) -> bool:
    """The kernel where the program is for ONE TPU device (GSPMD cannot
    partition a Mosaic call; experts over chips are open), the stacked
    weights have the rows' type and both widths tile."""
    from deepspeed_tpu.models.common import _kernel_target
    from deepspeed_tpu.ops.pallas import grouped_matmul as gmm

    mesh, on_tpu = _kernel_target()
    return on_tpu and (mesh is None or mesh.size == 1) \
        and w.dtype == x.dtype and gmm.supports(*w.shape[2:])


def _layer_of(w, layer):
    return w if layer is None else jax.lax.dynamic_index_in_dim(
        w, layer, 0, keepdims=False)


# rows: a share's compact buffer comes in whole tiles of the products' rows
_ROW_TILE = 128
# the widest float32 row the kernel path's compact form scatter-adds: measured
# on a v5e at 2,048 and 4,096 columns (sdar gen132.c1 unmoved, solar doc32k.c1
# TTFT -7%, mimo doc24k.c1 -18%); at 7,680 the same form LOST (openpangu
# doc8k.c1: TTFT +19%, ~9 ms a routed layer at 4,096 tokens; PERF.md section
# 6, PR 49), so wider rows keep the full-size buffers until that is profiled
_SCATTER_ROW_BYTES = 16 * 1024


def share_capacity(pairs: int, held: int, n_experts: int) -> int:
    """Rows of the buffer a SHARE's routed MLP moves its held pairs through:
    twice the even share of a call's ``pairs`` (token, expert) pairs where the
    leaves hold ``held`` of the router's ``n_experts``, in whole row tiles, at
    most every pair. A shape the code works out, never an option: the
    products run over this many rows whatever the call holds, and a call that
    holds more (``share_overflowed``) runs as many chunks of them."""
    even = -(-pairs * held // n_experts)
    return min(pairs, -(-2 * even // _ROW_TILE) * _ROW_TILE)


def share_overflowed(held_pairs, pairs: int, n_experts: int):
    """``held_pairs`` (..., E) pairs each held expert was given in a call of
    ``pairs`` -> (...) bool: the call held more than ``share_capacity`` rows
    (the train step's ``overflow_calls``, ``models/llama.py``)."""
    return jnp.sum(held_pairs, axis=-1) > share_capacity(
        pairs, held_pairs.shape[-1], n_experts)


def routed_mlp(x, weights, experts, gate_w, up_w, down_w, layer=None,
               first=None, n_experts=None):
    """x (T, D); ``weights`` / ``experts`` (T, k) from ``route_topk``;
    ``gate_w`` / ``up_w`` (E, D, F) and ``down_w`` (E, F, D), or the stacked
    (L, E, ...) leaves with the traced ``layer`` to take. ``first`` None: the
    leaves hold every expert the router can choose. An int: they hold the E
    experts ``first .. first + E - 1`` of the router's ``n_experts``, and a
    pair whose expert is not among them adds exactly zero.
    -> (out (T, D) in x's type, pairs routed to each held expert (E,) int32)."""
    with scope("moe/experts"):
        return _routed_mlp(x, weights, experts, gate_w, up_w, down_w, layer,
                           first, n_experts)


def _routed_mlp(x, weights, experts, gate_w, up_w, down_w, layer, first,
                n_experts):
    T, D = x.shape
    k = experts.shape[1]
    E = gate_w.shape[-3]
    flat = experts.reshape(-1)                       # pair p = token p // k
    if first is not None:
        held = (flat >= first) & (flat < first + E)
        flat = jnp.where(held, flat - first, E)      # E: no group, sorts last
    if layer is not None and _use_kernel(x, gate_w):
        from deepspeed_tpu.ops.pallas import grouped_matmul as gmm

        capacity = T * k if first is None else share_capacity(
            T * k, E, n_experts or E)
        # a prefill: the held pairs are a part of it
        if capacity < T * k and D * 4 <= _SCATTER_ROW_BYTES:
            sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
            return _share_kernel_mlp(
                x, weights, jnp.argsort(flat, stable=True), sizes,
                (gate_w, up_w, down_w), layer, capacity).astype(x.dtype), sizes
        tm = gmm.row_tile(T * k)
        sizes, tile_group, n_active, src, pos = gmm.group_layout(flat, E, tm)
        call = lambda rows, w, **kw: gmm.grouped_matmul(
            rows, w, layer, tile_group, n_active, tm=tm, **kw)

        def computed():
            h = call(x[src // k], (gate_w, up_w), swiglu=True)
            return call(h, down_w)[pos]

        # a share may hold none of this call's pairs: then no kernel runs
        # and no expert's weights leave HBM
        y = computed() if first is None else jax.lax.cond(
            n_active > 0, computed, lambda: jnp.zeros((T * k, D), x.dtype))
        if first is not None:   # whatever row an unheld pair was handed
            y = jnp.where(held[:, None], y, jnp.zeros_like(y))
    else:
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
        if first is not None:
            leaves = tuple(_layer_of(w, layer)
                           for w in (gate_w, up_w, down_w))
            capacity = share_capacity(T * k, E, n_experts or E)
            return _share_mlp(x, weights, order, sizes, leaves,
                              capacity).astype(x.dtype), sizes
        dot = lambda rows, w: jax.lax.ragged_dot(
            rows, _layer_of(w, layer).astype(rows.dtype), sizes)
        rows = x[order // k]
        h = jax.nn.silu(dot(rows, gate_w)) * dot(rows, up_w)
        y = dot(h, down_w)[jnp.argsort(order)]
    out = jnp.einsum("tk,tkd->td", weights, y.reshape(T, k, D).astype(
        jnp.float32))
    return out.astype(x.dtype), sizes


def _share_mlp(x, weights, order, sizes, leaves, capacity):
    """A share's pairs through ``ragged_dot``: ``order`` (T*k,) the pairs
    sorted by held expert, ``sizes`` (E,) a group, the unheld pairs behind
    the last group -> (T, D) float32. Only the first ``sum(sizes)`` entries
    of ``order`` are anyone's rows, so the three products and the row buffers
    around them take ``capacity`` rows and not T*k: the sorted pairs go
    through ``_grouped_rows_mlp`` a chunk of ``capacity`` at a time, as many
    chunks as hold a grouped row and never fewer than one (no arm skips a
    call that holds nothing: the buffer costs its rows whatever the count,
    as a deployment's balanced call does): ONE trip in nearly every call.
    Every pair is computed at any count, in the same products and
    precision."""
    chunks = -(-order.shape[0] // capacity)
    order = jnp.pad(order, (0, chunks * capacity - order.shape[0]))
    return _chunks_mlp(capacity, x, weights, order, sizes, *leaves)


def _share_kernel_mlp(x, weights, order, sizes, leaves, layer, capacity):
    """``_share_mlp`` for the grouped-matmul kernel (a served prefill on one
    TPU device): the pairs sorted by held expert go through the kernel a
    chunk of ``capacity`` rows at a time, as many chunks as hold a grouped
    row (none where the share holds no pair of the call: no kernel runs), so
    the row buffers around the two calls take ``capacity`` rows and not T*k
    (at 24,576 tokens x 8 the gather of every pair's row and the gather back
    were 1.6 GB each a layer). -> (T, D) float32."""
    from deepspeed_tpu.ops.pallas import grouped_matmul as gmm

    gate_w, up_w, down_w = leaves
    k, E = weights.shape[1], sizes.shape[0]
    tm = gmm.row_tile(capacity)
    chunks = -(-order.shape[0] // capacity)
    order = jnp.pad(order, (0, chunks * capacity - order.shape[0]))

    def step(i, out):
        pairs, part = _chunk(order, sizes, capacity, i)
        # the chunk's rows are sorted by group; past the groups: no group
        group = jnp.searchsorted(jnp.cumsum(part), jnp.arange(
            capacity, dtype=jnp.int32), side="right").astype(jnp.int32)
        _, tile_group, n_active, src, pos = gmm.group_layout(group, E, tm)
        call = lambda rows, w, **kw: gmm.grouped_matmul(
            rows, w, layer, tile_group, n_active, tm=tm, **kw)
        token = pairs // k
        h = call(x[token[src]], (gate_w, up_w), swiglu=True)
        # whatever row a pair of no group was handed: zeros
        y = jnp.where((group < E)[:, None], call(h, down_w)[pos], 0)
        return out.at[token].add(
            y.astype(jnp.float32) * weights.reshape(-1)[pairs][:, None])

    return jax.lax.fori_loop(0, -(-jnp.sum(sizes) // capacity), step,
                             jnp.zeros(x.shape, jnp.float32))


def _chunk(order, sizes, capacity, i):
    """(the i-th ``capacity`` of the sorted pairs, of each group the rows
    among them): a group that straddles two chunks is split between them."""
    lo = i * capacity
    ends = jnp.cumsum(sizes)
    part = jnp.minimum(ends, lo + capacity) - jnp.maximum(ends - sizes, lo)
    return jax.lax.dynamic_slice(order, (lo,), (capacity,)), \
        jnp.clip(part, 0)


def _trips(sizes, capacity):
    return jnp.maximum(-(-jnp.sum(sizes) // capacity), 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunks_mlp(capacity, x, weights, order, sizes, gate_w, up_w, down_w):
    """The loop of ``_share_mlp``. Its own VJP, because the trip count is
    the call's and because of what a loop keeps for a backward otherwise
    (every possible trip's row buffers: T*k rows again): the backward keeps
    the INPUTS and runs a chunk's rows again before it pulls back through
    them (the products and what feeds them; the combine is not needed)."""
    def step(i, out):
        return out + _grouped_rows_mlp(
            x, weights, *_chunk(order, sizes, capacity, i),
            gate_w, up_w, down_w)

    return jax.lax.fori_loop(0, _trips(sizes, capacity), step,
                             jnp.zeros(x.shape, jnp.float32))


def _chunks_fwd(capacity, *operands):
    return _chunks_mlp(capacity, *operands), operands


def _chunks_bwd(capacity, operands, g):
    x, weights, order, sizes, *leaves = operands

    def step(i, pulled):
        pairs, part = _chunk(order, sizes, capacity, i)
        return jax.tree.map(jnp.add, pulled, jax.vjp(
            lambda x, weights, *leaves: _grouped_rows_mlp(
                x, weights, pairs, part, *leaves),
            x, weights, *leaves)[1](g))

    d_x, d_weights, *d_leaves = jax.lax.fori_loop(
        0, _trips(sizes, capacity), step,
        tuple(jnp.zeros_like(a) for a in (x, weights, *leaves)))
    return (d_x, d_weights, None, None, *d_leaves)


_chunks_mlp.defvjp(_chunks_fwd, _chunks_bwd)


def _grouped_rows_mlp(x, weights, pairs, sizes, gate_w, up_w, down_w):
    """``pairs`` (C,) of the flattened (token, j) pairs, the first
    ``sum(sizes)`` sorted into groups of ``sizes`` (E,), the others of no
    group -> (T, D) float32: each grouped pair's expert output times its
    weight, summed into its token's row."""
    k = weights.shape[1]
    token = pairs // k
    # What ``ragged_dot`` and its transposes give a row of NO group is
    # whatever the buffer held (the TPU's grouped kernels never visit it),
    # and a product's d(rows) would carry that into d(x): zeros go in and
    # zeros come out, so the cotangents of those rows are zeros too.
    in_group = (jnp.arange(pairs.shape[0]) < jnp.sum(sizes))[:, None]
    grouped = lambda a: jnp.where(in_group, a, jnp.zeros_like(a))
    dot = lambda rows, w: grouped(jax.lax.ragged_dot(
        rows, w.astype(rows.dtype), sizes))
    rows = grouped(x.at[token].get(mode="promise_in_bounds"))
    h = jax.nn.silu(dot(rows, gate_w)) * dot(rows, up_w)
    y = dot(h, down_w).astype(jnp.float32) \
        * weights.reshape(-1).at[pairs].get(mode="promise_in_bounds")[:, None]
    return jnp.zeros(x.shape, jnp.float32).at[token].add(
        y, mode="promise_in_bounds")
