"""openPangu-Ultra-MoE (``model_type: pangu_ultra_moe``; the published
``config.json`` of ``FreedomIntelligence/openPangu-Ultra-MoE-718B``): the
program's model, the plain reference, and the operations and bytes the
algorithm needs — for ONE CHIP'S SHARE of a stated deployment.

The program's model is ``models/llama.py``'s trunk with the mixer, norms and
MLP this architecture's blocks hold. Every function takes the configuration
file's dict. The sizes are under its ``"model"`` key, named as in the
published file (``hidden_size``, ``num_hidden_layers``,
``first_k_dense_replace``, ``num_attention_heads``, ``q_lora_rank``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``intermediate_size`` = the DENSE layers' width, ``moe_intermediate_size`` =
one expert's, ``n_routed_experts``, ``n_shared_experts``,
``num_experts_per_tok``, ``norm_topk_prob``, ``routed_scaling_factor``,
``sandwich_norm``, ``rope_theta``, ``rms_norm_eps``, ``vocab_size``,
``num_nextn_predict_layers``). **The share** (model-configs guide, section
4): where the file lists ``n_routed_experts`` under ``reduced``, the value
under ``model`` is the number of experts HELD here, ``published
.n_routed_experts`` is the router's width, and ``share.experts_first`` the
router's number of the first held one. The router scores all of them and
picks ``num_experts_per_tok``; the pairs that fall on held experts are
computed, the others add nothing — in the program and in the reference
alike, which is given the same stacked leaves ``(L, held, ...)``. A sliced
vocabulary is a smaller vocabulary.

**The reference** is the forward pass in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision, no kernel, no cache, one sequence,
attention UN-absorbed. A layer, x (T, D):

* ``h = RMSNorm(x; attn_norm_g)``; ``c_q = RMSNorm(h q_a_w; q_a_norm_g)``;
  ``q = c_q q_b_w`` -> heads x ``[q_nope | q_rope]``, ``q_rope`` rotated;
  ``[c_kv | k_r] = h kv_a_w``, ``c_kv <- RMSNorm(c_kv; kv_a_norm_g)``,
  ``k_r`` rotated (ONE for all heads); per head ``k_nope = c_kv W_UK^T``
  (``kv_b_k_w`` (H, nope, C)) and ``v = c_kv W_UV`` (``kv_b_v_w`` (H, C,
  v)); ``s = (q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)``, causal
  softmax, ``o = p v``, ``a = concat(o) o_w``;
* sandwich norm: ``x <- x + RMSNorm(a; post_attn_norm_g)``; ``m =
  MLP(RMSNorm(x; mlp_norm_g))``; ``x <- x + RMSNorm(m; post_mlp_norm_g)``;
* MLP of the first ``first_k_dense_replace`` layers: SwiGLU of width
  ``intermediate_size``. Of the others: ``s = sigmoid(h router_w)`` over the
  router's whole width, the ``num_experts_per_tok`` largest, ``w = s_top /
  (sum(s_top) + 1e-20) x routed_scaling_factor``, ``sum_j w_j E_{e_j}(h)``
  over the chosen experts HELD here, plus the shared expert's SwiGLU of
  width ``n_shared_experts x moe_intermediate_size``, always;
* final RMSNorm, untied head.

So that it fits the chip at 8,192 tokens beside the served weights (128
heads x 8192^2 float32 scores at once are 34 GB), heads are walked in blocks
of ``HEAD_BLOCK`` and query rows in blocks of up to ``ROW_BLOCK``
(``lax.map``), every SwiGLU in column blocks of up to ``MLP_BLOCK``, the
experts one at a time, each weight block sliced out of the stacked leaf and
upcast alone. It reads the SAME parameter values the system holds, in the
program's layout, so a difference is a difference of arithmetic.

Departures from the published modelling code (``modeling_openpangu_moe.py``
beside the checkpoint, DeepSeek-V3's structure), each on purpose:
(1) rotary pairs are (i, i + rope/2) — the repo's rotate-half form — where
the checkpoint stores interleaved pairs (2i, 2i + 1): a loader's permutation
of ``q_b_w``'s and ``kv_a_w``'s rotary columns, no change of the function;
(2) ``kv_b_proj`` (kv_lora, H x (nope + v)) is held as two leaves, ``kv_b_k_w
(H, nope, C)`` and ``kv_b_v_w (H, C, v)``: a loader's split and transpose, so
that the absorbed decode reads each in place;
(3) the router scores with a SIGMOID: not a key of the config, the
convention of the family whose keys it uses (``routed_scaling_factor``,
``norm_topk_prob``) — under ``assumed`` in the configuration file, read
from its ``assumed_values`` (a reference of another scoring refuses); no
selection bias and no grouped selection (the config has no ``n_group`` /
``topk_group`` / ``topk_method``);
(4) the router's logits in float32 from float32 activations; an expert's
output weighted and summed in float32;
(5) multi-token prediction (``num_nextn_predict_layers``) is refused, not
ignored, unless 0: the draft head changes no logit of the main model;
(6) no attention mask (one unpadded sequence), default rotary frequencies
only, no bias anywhere.

**Near-ties of the router** (``TIE``, ``RESOLUTIONS``, ``reference_logits``).
Top-k is not continuous: where a held expert's router logit lies closer to
the CUT (midway between a token's 8th and 9th logits) than the arithmetic of
the served type can tell, a bf16 program and this float32 pass may put it on
different sides, BOTH validly, and with seeded random weights, 5 layers and
a normalised branch that one choice moves the token's logits by up to the
whole spread. On the chip (PERF.md, PR 31; ``benchmark/pangu_witness.py``)
that happens at ~1.2% of (token, routed layer) pairs, at a distance from the
cut of 0.0072 standard deviations of the token's router logits (rms; the
largest of 71 first flips of a position: 0.026); given the program's OWN
choices the reference agrees with it to the arithmetic's floor at every
position. ``TIE`` = 0.04 is the distance within which a held expert's side
counts as open. ``reference_forward`` takes ``way`` (T,) int32, a position's
resolution number: at each routed layer in turn, on the input the earlier
choices made, the open held experts (at most the two nearest the cut) may
each change sides, r = 1, 2 or 4 ways; the position takes number ``way %
r`` and hands ``way // r`` on (a mixed-radix number whose digits are its
layers' choices; 0 is the plain pass, and nothing changes anywhere but at an
open expert). ``reference_loss`` and every comparison of LOGITS use the
plain pass. ``reference_logits`` is what ``systems.ServeSystem.check`` and
``long_check.py`` hold a served token to, blind to the program's choices: it
evaluates ``RESOLUTIONS`` = 16 passes, pass j giving EVERY position its
resolution number j (so a position with up to four open experts on its way
meets every combination of them) while it sees the earlier positions
through the PLAIN pass's latent rows (``others``): a resolution is of one
position's own choices, and positions do not see each other's (left to
themselves they would: the post-norm makes a 9% change of a few context
rows a 6% change of an attention branch, enough to move an open expert
across the cut). It shifts each row by its own best logit and
returns, per position and token, the largest over the passes, put back at
the plain pass's best. ``max - logit[token]`` of that array is at most m
exactly where SOME valid resolution has the token within m of its best; a
position with no open expert is one pass sixteen times.

**The counts**: only matrix multiplications. A token meets every weight of
attention, the router and the shared expert, and of the routed experts the
EXPECTED share held here: ``num_experts_per_tok x held / router width``
experts a layer (0.5 at 8 x 16 / 256). The attention kernels' own counts
(``mla_*``) are the least the algorithm can do at a given length, so a
roofline share computed from them and the lengths the chip really ran cannot
pass 100%.
"""

import collections
import math

import jax
import jax.numpy as jnp

from benchmark.families.llama import _f32, _rms_norm, _rotate

Sizes = collections.namedtuple(
    "Sizes", "d layers dense_layers heads q_rank kv_rank nope rope v "
             "dense_mlp expert held first router top_k shared vocab")
EXPERT_LEAVES = ("expert_gate_w", "expert_up_w", "expert_down_w")
HEAD_BLOCK, ROW_BLOCK, MLP_BLOCK = 8, 1024, 2048
TIE = 0.04      # standard deviations of a token's router logits (docstring)
RESOLUTIONS = 16


# ------------------------------------------------------ the program's model
def vocab_size(cfg):
    return cfg["model"]["vocab_size"]


def _sizes(cfg):
    m = cfg["model"]
    held = m["n_routed_experts"]
    cut = "n_routed_experts" in cfg.get("reduced", ())
    return Sizes(
        m["hidden_size"], m["num_hidden_layers"], m["first_k_dense_replace"],
        m["num_attention_heads"], m["q_lora_rank"], m["kv_lora_rank"],
        m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
        m["intermediate_size"], m["moe_intermediate_size"], held,
        cfg.get("share", {}).get("experts_first", 0) if cut else 0,
        cfg["published"]["n_routed_experts"] if cut else held,
        m["num_experts_per_tok"], m["n_shared_experts"], m["vocab_size"])


def _refuse_what_is_not_computed(cfg):
    m = cfg["model"]
    if cfg["assumed_values"]["router_scoring"] != "sigmoid" \
            or m.get("num_nextn_predict_layers") or m.get("rope_scaling") \
            or m.get("attention_bias") \
            or m.get("num_key_value_heads", m["num_attention_heads"]) \
            != m["num_attention_heads"] or m.get("hidden_act", "silu") != "silu":
        raise SystemExit(
            "benchmark: the pangu_ultra_moe family computes a sigmoid router, "
            "no multi-token prediction head, no rope_scaling, no attention "
            "bias, every head its own key and value, and SiLU; this file "
            "asks otherwise")


def build_model(cfg, kind):
    """``deepspeed_tpu``'s Llama trunk with latent attention, sandwich norm,
    a sigmoid router over the published width, this chip's experts and the
    shared expert. A serve system asks for the parameters in the type it
    serves (``families/olmoe.py``)."""
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

    m, z = cfg["model"], _sizes(cfg)
    _refuse_what_is_not_computed(cfg)
    over = {"remat": cfg["train"]["remat"]} if kind == "train" else {}
    if kind == "serve" and cfg["serve"]["dtype"] == "bf16":
        over["param_dtype"] = jnp.bfloat16
    return LlamaModel(LlamaConfig(
        vocab_size=z.vocab, n_positions=m["max_position_embeddings"],
        n_embd=z.d, n_layer=z.layers, n_head=z.heads,
        intermediate_size=z.expert, dense_intermediate_size=z.dense_mlp,
        n_dense_layers=z.dense_layers, rope_theta=m["rope_theta"],
        rms_norm_eps=m["rms_norm_eps"],
        tie_embeddings=m["tie_word_embeddings"], n_experts=z.router,
        n_experts_per_tok=z.top_k, norm_topk_prob=m["norm_topk_prob"],
        n_shared_experts=z.shared,
        # what the published file does not say (its ``assumed``)
        router_scoring=cfg["assumed_values"]["router_scoring"],
        routed_scaling_factor=m["routed_scaling_factor"],
        experts_held=(z.first, z.held), q_lora_rank=z.q_rank,
        kv_lora_rank=z.kv_rank, qk_nope_head_dim=z.nope,
        qk_rope_head_dim=z.rope, v_head_dim=z.v,
        sandwich_norm=m["sandwich_norm"], **over))


# ------------------------------------------------------ the plain reference
def _block_of(n, most):
    """The largest divisor of n that is at most ``most``."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _take(leaf, layer, start, size, axis):
    """float32 ``leaf[layer]`` cut to ``[start, start + size)`` along
    ``axis`` of the layer's own axes: one block of one layer, upcast alone."""
    starts = [layer] + [0] * (leaf.ndim - 1)
    sizes = [1] + list(leaf.shape[1:])
    starts[axis + 1], sizes[axis + 1] = start, size
    return _f32(jax.lax.dynamic_slice(leaf, starts, sizes)[0])


def _layer_of(leaf, layer):
    return _take(leaf, layer, 0, leaf.shape[1], 0)


def _attention(x, blocks, layer, z, theta, eps, others=None):
    """-> (``a`` (T, D): latent attention, un-absorbed, before any
    post-norm; the layer's latent rows ``[c_kv | k_rope]`` (T, kv_rank +
    rope)). ``others`` (T, kv_rank + rope), None = the rows computed here: a
    position attends to ITS OWN row as computed here and to every earlier
    position through its row of ``others`` (a resolution of one position's
    near-ties is evaluated against the plain pass's context: the module's
    docstring)."""
    T = x.shape[0]
    get = lambda name: _layer_of(blocks[name], layer)
    h = _rms_norm(x, get("attn_norm_g"), eps)
    c_q = _rms_norm(h @ get("q_a_w"), get("q_a_norm_g"), eps)
    kv = h @ get("kv_a_w")
    c_kv = _rms_norm(kv[:, :z.kv_rank], get("kv_a_norm_g"), eps)
    k_r = _rotate(kv[:, None, z.kv_rank:], theta)[:, 0]       # (T, rope)
    seen_c, seen_r = (c_kv, k_r) if others is None else \
        (others[:, :z.kv_rank], others[:, z.kv_rank:])
    hb, rb = _block_of(z.heads, HEAD_BLOCK), _block_of(T, ROW_BLOCK)
    qk = z.nope + z.rope

    def head_block(i, acc):
        q = (c_q @ _take(blocks["q_b_w"], layer, i * hb * qk, hb * qk, 1)
             ).reshape(T, hb, qk)
        q_nope, q_rope = q[..., :z.nope], _rotate(q[..., z.nope:], theta)
        up_k = _take(blocks["kv_b_k_w"], layer, i * hb, hb, 0)
        up_v = _take(blocks["kv_b_v_w"], layer, i * hb, hb, 0)
        k_nope = jnp.einsum("tc,hnc->thn", seen_c, up_k)
        v = jnp.einsum("tc,hcd->thd", seen_c, up_v)
        if others is not None:
            k_own = jnp.einsum("tc,hnc->thn", c_kv, up_k)
            v_own = jnp.einsum("tc,hcd->thd", c_kv, up_v)

        def rows(j):
            cut = lambda t: jax.lax.dynamic_slice_in_dim(t, j * rb, rb, 0)
            at = (j * rb + jnp.arange(rb))[:, None]
            s = (jnp.einsum("qhn,khn->hqk", cut(q_nope), k_nope)
                 + jnp.einsum("qhr,kr->hqk", cut(q_rope), seen_r)
                 ) / math.sqrt(qk)
            if others is not None:
                itself = (jnp.arange(T)[None, :] == at)[None]
                s_own = (jnp.einsum("qhn,qhn->hq", cut(q_nope), cut(k_own))
                         + jnp.einsum("qhr,qr->hq", cut(q_rope), cut(k_r))
                         ) / math.sqrt(qk)
                s = jnp.where(itself, s_own[..., None], s)
            s = jnp.where((jnp.arange(T)[None, :] <= at)[None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("hqk,khd->qhd", p, v)
            if others is not None:
                o = o + jnp.einsum("hq,qhd->qhd",
                                   jnp.sum(jnp.where(itself, p, 0.0), axis=-1),
                                   cut(v_own) - cut(v))
            return o

        o = jax.lax.map(rows, jnp.arange(T // rb)).reshape(T, hb * z.v)
        return acc + o @ _take(blocks["o_w"], layer, i * hb * z.v, hb * z.v, 0)

    return jax.lax.fori_loop(0, z.heads // hb, head_block,
                             jnp.zeros_like(x)), \
        jnp.concatenate([c_kv, k_r], axis=-1)


def _swiglu(h, leaves, names, at, width):
    """``down(silu(gate(h)) * up(h))`` of width ``width``, its columns in
    blocks; ``at``: the leading indices of the three stacked leaves."""
    gate, up, down = (leaves[n] for n in names)
    b, d = _block_of(width, MLP_BLOCK), h.shape[1]
    cut = lambda leaf, r0, rows, c0, cols: _f32(jax.lax.dynamic_slice(
        leaf, (*at, r0, c0), (1,) * len(at) + (rows, cols))).reshape(rows, cols)

    def block(i, acc):
        inner = jax.nn.silu(h @ cut(gate, 0, d, i * b, b)) \
            * (h @ cut(up, 0, d, i * b, b))
        return acc + inner @ cut(down, i * b, b, 0, d)

    return jax.lax.fori_loop(0, width // b, block, jnp.zeros_like(h))


def _route(h, router_w, z, renormalize, scale, way, held):
    """-> weights (T, k), chosen experts (T, k), what is left of ``way``
    (T,), ``distance`` (T, experts held here): how far each held expert's
    router logit lies from the CUT (midway between the k-th and the next
    logit of the row), in standard deviations of the row's logits, and the
    logits (T, router width). ``way`` (T,) int32, 0 = the plain pass: the
    held experts within ``TIE`` of the cut, at most the two nearest, may
    each change sides (taken <-> left out): r = 1, 2 or 4 ways, of which
    this row takes number ``way % r`` and hands ``way // r`` on to the next
    routed layer. ``held`` (T, experts held) int, None = all -1: a held
    expert's side is GIVEN (1 taken, 0 left out; -1: as above) — the
    reference evaluated on a program's own choices
    (``benchmark/pangu_witness.py``). The other places go to the best of the
    rest, by the scores."""
    k, T = z.top_k, h.shape[0]
    logits = h @ router_w
    scores = jax.nn.sigmoid(logits)            # monotone: the logits' order
    mine = logits[:, z.first:z.first + z.held]
    side = jnp.full((T, z.held), -1, jnp.int8)
    if z.router > k:
        edge = jax.lax.top_k(logits, k + 1)[0][:, k - 1:]
        cut = jnp.mean(edge, axis=-1, keepdims=True)
        distance = jnp.abs(mine - cut) / jnp.std(logits, axis=-1,
                                                 keepdims=True)
        near, which = jax.lax.top_k(-distance, min(2, z.held))
        near = -near <= TIE                                 # (T, 1 or 2)
        ways = 1 << jnp.sum(near, axis=-1)                  # 1, 2 or 4
        digit, way = way % ways, way // ways
        # the nearest takes the digit's low bit; the second only if near
        turn = ((digit[:, None] >> jnp.arange(near.shape[1])) & 1) > 0
        taken = jnp.take_along_axis(mine, which, axis=-1) > cut
        given = jnp.where(near, taken ^ turn, -1).astype(jnp.int8)
        side = jax.vmap(lambda s, w, g: s.at[w].set(g))(side, which, given)
    else:                                      # the router picks every expert
        distance = jnp.full((T, z.held), jnp.inf)
    if held is not None:
        side = jnp.where(held >= 0, held.astype(jnp.int8), side)
    # scores lie in (0, 1): a given side outranks, or is outranked by, all
    key = scores.at[:, z.first:z.first + z.held].add(
        jnp.where(side > 0, 2.0, jnp.where(side == 0, -2.0, 0.0)))
    chosen = jax.lax.top_k(key, k)[1]
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * scale, chosen, way, distance, logits


def _routed(h, weights, chosen, blocks, layer, z):
    """The chosen experts HELD here, one at a time; a token that did not
    choose one, or chose one held elsewhere, adds exactly zero."""
    def one(e, acc):
        y = _swiglu(h, blocks, EXPERT_LEAVES, (layer, e), z.expert)
        mine = chosen == z.first + e            # (T, k): at most one True
        w = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1, keepdims=True)
        return acc + jnp.where(jnp.any(mine, axis=-1, keepdims=True),
                               w * y, 0.0)

    return jax.lax.fori_loop(0, z.held, one, jnp.zeros_like(h))


def _layer(x, blocks, layer, z, m, way=None, held=None, others=None):
    """-> (x after the layer, what is left of ``way``, the layer's ``latent``
    rows and, of a routed layer, the router's ``chosen`` / ``distance`` /
    ``router_logits``)."""
    theta, eps = float(m["rope_theta"]), float(m["rms_norm_eps"])
    get = lambda name: _layer_of(blocks[name], layer)
    sandwich = bool(m["sandwich_norm"])
    a, latent = _attention(x, blocks, layer, z, theta, eps, others)
    x = x + (_rms_norm(a, get("post_attn_norm_g"), eps) if sandwich else a)
    h = _rms_norm(x, get("mlp_norm_g"), eps)
    routed = {"latent": latent}
    if "router_w" in blocks:
        weights, chosen, way, distance, logits = _route(
            h, get("router_w"), z, m["norm_topk_prob"],
            float(m["routed_scaling_factor"]), way, held)
        routed.update(chosen=chosen, distance=distance, router_logits=logits)
        out = _routed(h, weights, chosen, blocks, layer, z)
        if z.shared:
            out = out + _swiglu(h, blocks, ("shared_gate_w", "shared_up_w",
                                            "shared_down_w"), (layer,),
                                z.shared * z.expert)
    else:
        out = _swiglu(h, blocks, ("gate_w", "up_w", "down_w"), (layer,),
                      z.dense_mlp)
    return x + (_rms_norm(out, get("post_mlp_norm_g"), eps) if sandwich
                else out), way, routed


def reference_forward(params, ids, cfg, way=None, held=None, others=None):
    """ids (T,) int32 -> (float32 logits (T, vocab), of the routed layers
    their ``latent`` rows (routed layers, T, kv_rank + rope), the routers'
    ``chosen`` experts (routed layers, T, k), ``distance`` (routed layers,
    T, experts held) of each held expert's logit from the row's cut in the
    row's standard deviations, ``router_logits`` (routed layers, T, router
    width)) of one sequence. ``way`` (T,) int32, None = 0 everywhere = the
    plain pass: which of its near-ties' resolutions each position takes
    (``_route``; the module's docstring). ``others`` (routed layers, T,
    kv_rank + rope), None = this pass's own: the latent rows every position
    sees the EARLIER ones through (``_attention``). ``held`` (routed layers,
    T, experts held) int, None = all -1: a held expert's side given."""
    m, z = cfg["model"], _sizes(cfg)
    _refuse_what_is_not_computed(cfg)
    routed = z.layers - z.dense_layers
    if way is None:
        way = jnp.zeros(ids.shape[0], jnp.int32)
    if held is None:
        held = jnp.full((routed, ids.shape[0], z.held), -1, jnp.int8)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"])[ids]
        for l in range(z.dense_layers):
            x = _layer(x, params["dense_blocks"], l, z, m)[0]

        def layer(carry, at):
            x, way, routers = _layer(carry[0], params["blocks"], at[0], z, m,
                                     carry[1], *at[1:])
            return (x, way), routers

        (x, _), routers = jax.lax.scan(
            layer, (x, way), (jnp.arange(routed), held)
            + (() if others is None else (others,)))
        x = _rms_norm(x, params["norm_g"], float(m["rms_norm_eps"]))
        head = _f32(params["wte"]).T if m["tie_word_embeddings"] \
            else _f32(params["lm_head"])
        return x @ head, routers


def _resolved(params, ids, cfg):
    """-> (the plain pass's logits, resolution number r -> the logits of the
    pass in which EVERY position takes its resolution r against the plain
    pass's context)."""
    plain, routers = reference_forward(params, ids, cfg)
    return plain, lambda r: reference_forward(
        params, ids, cfg, jnp.full(ids.shape[0], r, jnp.int32),
        others=routers["latent"])[0]


def resolution_logits(params, ids, cfg, last):
    """(RESOLUTIONS, last, vocab): the last ``last`` positions' logits under
    each resolution number; row 0 the plain pass."""
    resolved = _resolved(params, ids, cfg)[1]
    return jax.lax.map(lambda r: resolved(r)[-last:],
                       jnp.arange(RESOLUTIONS))


def reference_logits(params, ids, cfg):
    """The logits a served token is held to: per position and token the
    largest over the near-ties' resolutions of (logit - that resolution's
    best), put back at the plain pass's best (the module's docstring). One
    resolution at a time: at 8,192 positions all of them are 10 GB."""
    shifted = lambda lg: lg - jnp.max(lg, axis=-1, keepdims=True)
    plain, resolved = _resolved(params, ids, cfg)
    return jax.lax.fori_loop(
        1, RESOLUTIONS,
        lambda r, best: jnp.maximum(best, shifted(resolved(r))),
        shifted(plain)) + jnp.max(plain, axis=-1, keepdims=True)


def reference_loss(params, ids, cfg):
    """Mean cross entropy of predicting ids[1:] from ids[:-1] (no auxiliary
    term: a share of the experts cannot form the load-balancing loss)."""
    lg = reference_forward(params, ids, cfg)[0][:-1]
    logp = lg - jax.nn.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))


# ----------------------------------------- operations and bytes from shapes
def attention_params(cfg):
    """Matmul weights of one layer's latent attention: q_a, q_b, kv_a, the
    two halves of kv_b, o. 196.6 M at the published widths."""
    z = _sizes(cfg)
    return z.d * z.q_rank + z.q_rank * z.heads * (z.nope + z.rope) \
        + z.d * (z.kv_rank + z.rope) \
        + z.kv_rank * z.heads * (z.nope + z.v) + z.heads * z.v * z.d


def _expert_params(z):
    return 3 * z.d * z.expert


def _norm_params(cfg):
    z = _sizes(cfg)
    per_layer = (4 if cfg["model"]["sandwich_norm"] else 2) * z.d \
        + z.q_rank + z.kv_rank
    return z.layers * per_layer + z.d


def held_params(cfg):
    """Every parameter this chip holds: 4.919 B at the published widths and
    the stated share."""
    z = _sizes(cfg)
    routed = z.layers - z.dense_layers
    embed = z.vocab * z.d * (1 if cfg["model"]["tie_word_embeddings"] else 2)
    return z.layers * attention_params(cfg) + _norm_params(cfg) \
        + z.dense_layers * 3 * z.d * z.dense_mlp \
        + routed * (z.d * z.router + (z.held + z.shared) * _expert_params(z)) \
        + embed


def experts_met(cfg):
    """Routed experts HELD HERE that a token is expected to meet in one
    layer: ``num_experts_per_tok x held / router width`` (0.5)."""
    z = _sizes(cfg)
    return z.top_k * z.held / z.router


def matmul_params(cfg):
    """Parameters that sit in a matrix multiplication for one token, on
    this chip: attention in every layer, the dense layers' MLP, in a routed
    layer the router, the shared expert and the expected share of routed
    experts (``experts_met``), and the output head. 1.846 B."""
    z = _sizes(cfg)
    routed = z.layers - z.dense_layers
    return z.layers * attention_params(cfg) \
        + z.dense_layers * 3 * z.d * z.dense_mlp \
        + routed * (z.d * z.router
                    + (z.shared + experts_met(cfg)) * _expert_params(z)) \
        + z.d * z.vocab


def mla_prefill_attn_flops(cfg, seq):
    """Causal latent attention, un-absorbed, forward over one sequence, all
    layers: q.k over ``nope + rope`` columns and p.v over ``v``, every head
    against every earlier position: half the square."""
    z = _sizes(cfg)
    return z.layers * z.heads * seq * seq / 2 * 2 * (z.nope + z.rope + z.v)


def mla_decode_attn_flops(cfg, context):
    """Absorbed decode attention of ONE token over ``context`` cached rows,
    all layers: every head's scores over the row's ``kv_rank + rope``
    columns and its weighted sum over ``kv_rank``."""
    z = _sizes(cfg)
    return z.layers * context * z.heads * 2 * (2 * z.kv_rank + z.rope)


def kv_bytes_per_position(cfg, itemsize=2):
    """Bytes one cached position holds across all layers: the latent row
    ``[c_kv | k_rope]``, once for all heads (5,760 at the published widths
    over the 5 layers held; K and V of 128 heads would be 409,600)."""
    z = _sizes(cfg)
    return z.layers * (z.kv_rank + z.rope) * itemsize


def mla_decode_attn_bytes(cfg, context, itemsize=2):
    """The latent rows one decoded token's attention reads, each ONCE."""
    return context * kv_bytes_per_position(cfg, itemsize)


def attention_flops_fwd(cfg, seq):
    return mla_prefill_attn_flops(cfg, seq)


def train_flops_per_token(cfg, seq):
    """Forward + backward FLOPs per trained token at sequence length
    ``seq``: 6 x the matmul parameters a token meets here, plus attention at
    3x its forward. (No cell trains this configuration.)"""
    return 6 * matmul_params(cfg) + 3 * attention_flops_fwd(cfg, seq) / seq


def weight_bytes(cfg, itemsize=2):
    """Bytes of weights one decode step must stream: the matmul weights a
    token meets (``matmul_params``: the EXPECTED held experts) and the
    norms' gains. 3.69 GB in bf16. The lookup reads one row."""
    return (matmul_params(cfg) + _norm_params(cfg)) * itemsize


def decode_flops_per_token(cfg):
    """One token through every matmul weight it meets; the attention over
    the cache is ``mla_decode_attn_flops``, at a context."""
    return 2 * matmul_params(cfg)


def decode_bytes_per_token(cfg, context, itemsize=2):
    """HBM bytes one decode step needs: the weights a token meets once, and
    the latent rows of the ``context`` positions it attends to."""
    return weight_bytes(cfg, itemsize) \
        + context * kv_bytes_per_position(cfg, itemsize)
