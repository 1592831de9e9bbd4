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
  tile and adds exactly zero, and a call none of whose pairs is held (a
  decode step, most of the time) runs no expert kernel at all. Nothing
  stands in for the other chips or their traffic. Where the program is for
  one TPU device and the widths tile, the three contractions are the Pallas
  grouped matmul (``ops/pallas/grouped_matmul.py``) reading the stacked
  ``(L, E, ...)`` leaves in place; otherwise — the CPU, a multi-device mesh,
  training — ``jax.lax.ragged_dot`` over the sorted rows, which is also the
  reference the kernel is tested against. The path is chosen by what the
  code observes, never by an option and never by a failure;
* ``load_balancing_loss``: the Switch / Hugging Face auxiliary loss from
  per-layer sums, so a scanned trunk can carry them.
"""

from __future__ import annotations

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


def routed_mlp(x, weights, experts, gate_w, up_w, down_w, layer=None,
               first=None):
    """x (T, D); ``weights`` / ``experts`` (T, k) from ``route_topk``;
    ``gate_w`` / ``up_w`` (E, D, F) and ``down_w`` (E, F, D), or the stacked
    (L, E, ...) leaves with the traced ``layer`` to take. ``first`` None: the
    leaves hold every expert the router can choose. An int: they hold the E
    experts ``first .. first + E - 1`` of the router's, and a pair whose
    expert is not among them adds exactly zero.
    -> (out (T, D) in x's type, pairs routed to each held expert (E,) int32)."""
    with scope("moe/experts"):
        return _routed_mlp(x, weights, experts, gate_w, up_w, down_w, layer,
                           first)


def _routed_mlp(x, weights, experts, gate_w, up_w, down_w, layer, first):
    T, D = x.shape
    k = experts.shape[1]
    E = gate_w.shape[-3]
    flat = experts.reshape(-1)                       # pair p = token p // k
    if first is not None:
        held = (flat >= first) & (flat < first + E)
        flat = jnp.where(held, flat - first, E)      # E: no group, sorts last
    if layer is not None and _use_kernel(x, gate_w):
        from deepspeed_tpu.ops.pallas import grouped_matmul as gmm

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
    else:
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
        dot = lambda rows, w: jax.lax.ragged_dot(
            rows, _layer_of(w, layer).astype(rows.dtype), sizes)
        rows = x[order // k]
        if first is None:
            grouped = lambda a: a
        else:
            # A share's unheld pairs sort last and belong to NO group. What
            # ``ragged_dot`` and its transposes give such a row is whatever
            # the buffer held (the TPU's grouped kernels never visit it), and
            # a product's d(rows) would carry that into d(x): zeros go in and
            # zeros come out, so the cotangents of those rows are zeros too.
            in_group = (jnp.arange(T * k) < jnp.sum(sizes))[:, None]
            grouped = lambda a: jnp.where(in_group, a, jnp.zeros_like(a))
        rows = grouped(rows)
        h = jax.nn.silu(grouped(dot(rows, gate_w))) \
            * grouped(dot(rows, up_w))
        y = dot(h, down_w)[jnp.argsort(order)]
    if first is not None:       # whatever row an unheld pair was handed
        y = jnp.where(held[:, None], y, jnp.zeros_like(y))
    out = jnp.einsum("tk,tkd->td", weights, y.reshape(T, k, D).astype(
        jnp.float32))
    return out.astype(x.dtype), sizes
