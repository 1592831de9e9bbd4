"""MiMo-V2-Flash (``model_type: mimo_v2_flash``; the published ``config.json``
of ``XiaomiMiMo/MiMo-V2-Flash``, 309B-A15B): the program's model, the plain
reference, and the operations and bytes the algorithm needs, for ONE CHIP'S
SHARE of a stated deployment, on the SERVING path.

Every function takes the configuration file's dict. The sizes are under its
``"model"`` key, named as in the published file (``hidden_size``,
``num_hidden_layers``, ``hybrid_layer_pattern`` (1 = a WINDOW layer),
``moe_layer_freq`` (0 = a dense MLP), ``num_attention_heads``,
``num_key_value_heads`` (full layers), ``swa_num_key_value_heads`` (window
layers), ``head_dim`` (q.k), ``v_head_dim``, ``partial_rotary_factor``,
``rope_theta`` / ``swa_rope_theta``, ``sliding_window``,
``add_swa_attention_sink_bias``, ``attention_value_scale``,
``intermediate_size`` = the dense layer's width, ``moe_intermediate_size`` =
one expert's, ``n_routed_experts``, ``num_experts_per_tok``,
``norm_topk_prob``, ``scoring_func``, ``topk_method``, ``layernorm_epsilon``,
``vocab_size``). **The share** (model-configs guide, section 4):
``n_routed_experts`` is listed under ``reduced``, so the value under
``model`` is the number of experts HELD here, ``published.n_routed_experts``
the router's width and ``share.experts_first`` the router's number of the
first held one. The router scores all of them and picks
``num_experts_per_tok``; the pairs that fall on held experts are computed,
the others add nothing, in the program and in the reference alike, which
reads the same stacked leaves ``(L, held, ...)``. A sliced vocabulary is a
smaller vocabulary.

**The reference** (``reference_forward``): float32, ``highest`` matmul
precision, no kernel, no cache, no batching, one sequence. A layer l, x (T,
4096), eps 1e-5, no bias anywhere: ``h = x + Attn_l(RMSNorm(x))``, ``y = h +
FFN_l(RMSNorm(h))``; final RMSNorm, untied head.

* Attn, both kinds: ``q = n W_q`` as 64 heads x 192, ``k = n W_k`` as KV
  heads x 192, ``v = 0.707 n W_v`` as KV heads x 128 (``attention_value_scale``);
  KV heads 4 on a FULL layer (``hybrid_layer_pattern[l]`` 0), 8 on a WINDOW
  layer (1). Rotary embedding on the FIRST 64 columns of every head of q and
  k (``int(0.334 x 192)``; rotate-half pairs (i, i + 32)), theta 5,000,000 on
  full layers, 10,000 on window layers, absolute positions from 0; the
  other 128 columns pass as they are. Scores ``s_ij = q_i . k_j /
  sqrt(192)``, query head h on KV head ``h // (64 / KV)``; j admitted for i
  iff ``j <= i`` and, on a window layer, ``i - j < 128`` (the row's own key
  among the 128). A full layer: the plain softmax. A window layer: ``p_ij =
  exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))``, b_h the head's learned SINK
  logit (leaf ``sink``): it takes mass and carries no value. ``o_i = sum_j
  p_ij v_j``, heads concatenated (8,192), ``W_o``.
* FFN of layer 0 (``moe_layer_freq`` 0): SwiGLU of width 16,384. Of the
  others: ``s = sigmoid(n W_r)`` over the router's whole width (float32),
  the 8 largest of ``s + b`` (``router_bias``: SELECTION only, ``noaux_tc``;
  one group), ``w = s_top / sum(s_top)``, no scaling, no shared expert;
  ``sum_e w_e W_down,e (silu(n W_gate,e) * n W_up,e)`` over the chosen
  experts HELD here, walked one at a time.

So that it fits the chip at 24,576 tokens beside the served weights, query
heads are walked ``HEAD_BLOCK`` at a time and query rows in blocks of up to
``ROW_BLOCK`` (a window layer's block against the ``ROW_BLOCK + 128`` keys
its band can hold), the dense SwiGLU in column blocks of ``MLP_BLOCK``, the
experts one at a time, each weight block sliced out of its stacked leaf and
upcast alone; ``last=`` returns the logits of the last rows only. It reads
the SAME parameter values the system holds, in the program's layout
(``dense_blocks``: layer 0 whole; ``blocks``: the routed layers' norms,
router and experts; ``attn_blocks`` / ``win_blocks``: their full / window
mixers), so a difference is a difference of arithmetic.

Departures from the published modelling code, each on purpose: (1) q/k/v/o
and the experts are held in the program's layout, (in, out) matrices, the
experts stacked ``(L, held, ...)``: a loader's transposes; (2) the router's
logits from float32 activations and an expert's output weighted and summed
in float32; (3) no attention mask but the causal (window) one: one unpadded
sequence; (4) default rotary frequencies only; (5) grouped selection is
refused unless ``n_group`` = ``topk_group`` = 1 (the published values); (6)
the 3 multi-token-prediction layers ``described_as`` speaks of have no key
in the config and are left out: they change no logit of the main model. What
the config does not say is under ``assumed`` in the configuration file.

**Near-ties of the router** (``TIE``, ``RESOLUTIONS``; the openPangu family's
construction, ``families/pangu_ultra_moe.py``). Top-k is not continuous:
where a held expert's selection score lies closer to the CUT (midway between
a token's 8th and 9th) than bf16 arithmetic can tell, the program and this
float32 pass may put it on different sides, both validly, and here ONE
expert's weighted output is ~4% of the residual's norm: a logit moves by
more than the margin. ``reference_forward`` takes ``way`` (rows,) int32, a
row's resolution number: at each routed layer in turn the open held experts
(at most the two nearest the cut) may each change sides, r = 1, 2 or 4 ways;
the row takes ``way % r`` and hands ``way // r`` on; 0 is the plain pass.
A resolution is of ONE position's own choices: the resolved rows see every
earlier position through the PLAIN pass's K/V rows (``others``) and
themselves through their own. ``reference_logits`` evaluates ``RESOLUTIONS``
= 16 such passes, each giving every row that number, shifts each row by its
own best logit and returns per row and token the largest over the passes,
put back at the plain pass's best: ``max - logit[token] <= m`` exactly where
SOME valid resolution has the token within m of its best.

**The counts**: only matrix multiplications. A token meets every mixer and
router weight and the EXPECTED share of routed experts held here (``8 x 8 /
256`` = 0.25 a layer: ``experts_met``), so the standing decode roofline can
only read low. Attention is BANDED on a window layer: ``win_flash_flops``
counts ``min(i + 1, 128)`` keys a row, ``decode_kv_bytes`` the ring at
``min(context, 128)`` slots. The kernels' own counts are the least the
algorithm can do at the lengths the chip ran, so no share passes 100%.
"""

import collections
import math

import jax
import jax.numpy as jnp

from benchmark.families.llama import _f32, _rms_norm

Sizes = collections.namedtuple(
    "Sizes", "d layers kinds dense heads kv win_kv dh dv rot theta win_theta "
             "window v_scale dense_mlp expert held first router top_k vocab "
             "eps")
EXPERT_LEAVES = ("expert_gate_w", "expert_up_w", "expert_down_w")
HEAD_BLOCK, ROW_BLOCK, MLP_BLOCK = 8, 512, 2048
TIE = 0.04      # standard deviations of a row's selection scores (docstring)
RESOLUTIONS = 16


# ------------------------------------------------------ the program's model
def vocab_size(cfg):
    return cfg["model"]["vocab_size"]


def _sizes(cfg):
    m = cfg["model"]
    held = m["n_routed_experts"]
    cut = "n_routed_experts" in cfg.get("reduced", ())
    layers = m["num_hidden_layers"]
    kinds = tuple("win" if w else "attn"
                  for w in m["hybrid_layer_pattern"][:layers])
    dense = sum(1 for f in m["moe_layer_freq"][:layers] if not f)
    return Sizes(
        m["hidden_size"], layers, kinds, dense, m["num_attention_heads"],
        m["num_key_value_heads"], m["swa_num_key_value_heads"], m["head_dim"],
        m["v_head_dim"], 2 * (int(m["partial_rotary_factor"] * m["head_dim"])
                              // 2),
        float(m["rope_theta"]), float(m["swa_rope_theta"]),
        m["sliding_window"], float(m["attention_value_scale"]),
        m["intermediate_size"], m["moe_intermediate_size"], held,
        cfg.get("share", {}).get("experts_first", 0) if cut else 0,
        cfg["published"]["n_routed_experts"] if cut else held,
        m["num_experts_per_tok"], m["vocab_size"],
        float(m["layernorm_epsilon"]))


def _refuse_what_is_not_computed(cfg):
    m = cfg["model"]
    layers = m["num_hidden_layers"]
    freq = list(m["moe_layer_freq"][:layers])
    if len(m["hybrid_layer_pattern"]) < layers or len(freq) < layers \
            or freq != [0] * (layers - sum(freq)) + [1] * sum(freq) \
            or not sum(freq) \
            or m.get("scoring_func") != "sigmoid" \
            or m.get("topk_method") != "noaux_tc" \
            or m.get("n_group", 1) != 1 or m.get("topk_group", 1) != 1 \
            or m.get("routed_scaling_factor") not in (None, 1.0) \
            or m.get("n_shared_experts") or m.get("attention_bias") \
            or m.get("rope_scaling") or m.get("tie_word_embeddings") \
            or m.get("hidden_act", "silu") != "silu" \
            or not m.get("add_swa_attention_sink_bias") \
            or m.get("add_full_attention_sink_bias") \
            or m.get("swa_num_attention_heads", m["num_attention_heads"]) \
            != m["num_attention_heads"] \
            or m.get("swa_head_dim", m["head_dim"]) != m["head_dim"] \
            or m.get("swa_v_head_dim", m["v_head_dim"]) != m["v_head_dim"] \
            or {m.get("sliding_window_size", m["sliding_window"]),
                m.get("attention_chunk_size", m["sliding_window"])} \
            != {m["sliding_window"]}:
        raise SystemExit(
            "benchmark: the mimo_v2_flash family computes leading dense "
            "layers then routed ones, a sigmoid router with a selection bias "
            "(noaux_tc, one group, no scaling, no shared expert), a sink on "
            "the window layers alone, the two kinds' heads and head sizes "
            "alike but for their KV heads, one window said three times, no "
            "bias, default rotary frequencies, SiLU and an untied head; this "
            "file asks otherwise")


def build_model(cfg, kind):
    """``deepspeed_tpu``'s Llama trunk with what this architecture's blocks
    hold: window layers with a mixer, a sink and a ring cache of their own,
    q.k at 192 and v at 128, a partial rotary embedding with a base a kind,
    the scaled v, a sigmoid router with a selection bias over the published
    width and this chip's experts. A serve system asks for the parameters in
    the type it serves (``families/olmoe.py``). No ``train`` system: the
    smallest cut the guide's floors allow is 31 GB of training state."""
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

    if kind != "serve":
        raise SystemExit("benchmark: the mimo_v2_flash family is served only "
                         "(its smallest admissible cut does not fit one "
                         "chip's training state)")
    m, z = cfg["model"], _sizes(cfg)
    _refuse_what_is_not_computed(cfg)
    over = {"param_dtype": jnp.bfloat16} if cfg["serve"]["dtype"] == "bf16" \
        else {}
    return LlamaModel(LlamaConfig(
        vocab_size=z.vocab, n_positions=m["max_position_embeddings"],
        n_embd=z.d, n_layer=z.layers, n_head=z.heads, n_kv_head=z.kv,
        head_dim=z.dh, v_head_dim=z.dv, rotary_dim=z.rot,
        rope_theta=z.theta, window_rope_theta=z.win_theta,
        value_scale=z.v_scale, rms_norm_eps=z.eps, tie_embeddings=False,
        layer_types=tuple("sliding_attention" if k == "win"
                          else "full_attention" for k in z.kinds),
        sliding_window=z.window, window_kv_head=z.win_kv, window_sink=True,
        intermediate_size=z.expert, dense_intermediate_size=z.dense_mlp,
        n_dense_layers=z.dense, n_experts=z.router,
        n_experts_per_tok=z.top_k, norm_topk_prob=m["norm_topk_prob"],
        router_scoring="sigmoid", router_bias=True,
        experts_held=(z.first, z.held) if z.held < z.router else None,
        **over))


# ------------------------------------------------------ the plain reference
def _block_of(n, most):
    """The largest divisor of n that is at most ``most``."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _rotate_at(x, theta, positions, rot):
    """The rotary embedding on the first ``rot`` columns of every head of x
    (T, heads, Dh) at ``positions`` (T,): pair i is (x[i], x[i + rot / 2]),
    turned by position / theta^(2i / rot); the other columns pass."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def _layer_leaves(params, z, l):
    """(the leaves of layer l's mixer, its index in them; the leaves of its
    norms and MLP, its index in them), as ``models/llama.py`` lays them
    out."""
    if l < z.dense:
        return (params["dense_blocks"], l), (params["dense_blocks"], l)
    kind = z.kinds[l]
    mine = params["win_blocks" if kind == "win" else "attn_blocks"]
    return (mine, z.kinds[z.dense:l].count(kind)), \
        (params["blocks"], l - z.dense)


def _attention(h, at, kind, leaves, i, z, others=None):
    """-> (Attn(h) (n, D), this call's rows (k (n, KV, 192) rotated, v (n,
    KV, 128) scaled)). ``h`` (n, D): the normed input at absolute positions
    ``at`` (n,). ``others``: None (the n rows are a whole sequence from 0
    and see each other), or (k, v, first): the PLAIN pass's rows of
    positions ``first ..`` up to the last of ``at``; a row then sees every
    earlier position through them and ITSELF through its own row."""
    n = h.shape[0]
    kv = z.win_kv if kind == "win" else z.kv
    rep = z.heads // kv
    theta = z.win_theta if kind == "win" else z.theta
    take = lambda name: _f32(leaves[name][i])
    k = _rotate_at((h @ take("k_w")).reshape(n, kv, z.dh), theta, at, z.rot)
    v = z.v_scale * (h @ take("v_w")).reshape(n, kv, z.dv)
    sink = _f32(leaves["sink"][i]) if kind == "win" else None
    if others is None:
        keys, values, key_at = k, v, at
    else:
        keys, values = others[0], others[1]
        key_at = others[2] + jnp.arange(keys.shape[0])
    hb = _block_of(rep, HEAD_BLOCK)        # query heads of ONE KV head
    rb = _block_of(n, ROW_BLOCK)
    # a window layer's row block against the keys its band can hold (a
    # whole sequence only: the resolved rows are few)
    banded = kind == "win" and others is None and n > rb + z.window
    if banded:
        pad = lambda t: jnp.pad(t, ((z.window, 0),) + ((0, 0),) * (t.ndim - 1))
        keys, values = pad(keys), pad(values)
        key_at = jnp.concatenate([jnp.full(z.window, -1, key_at.dtype),
                                  key_at])

    def head_block(b, acc):
        g = b * hb // rep
        q = _rotate_at((h @ _f32(jax.lax.dynamic_slice_in_dim(
            leaves["q_w"][i], b * hb * z.dh, hb * z.dh, 1))).reshape(
                n, hb, z.dh), theta, at, z.rot)
        kg = jax.lax.dynamic_index_in_dim(keys, g, 1, keepdims=False)
        vg = jax.lax.dynamic_index_in_dim(values, g, 1, keepdims=False)
        b_h = None if sink is None else \
            jax.lax.dynamic_slice_in_dim(sink, b * hb, hb)[:, None, None]

        def rows(j):
            cut = lambda t: jax.lax.dynamic_slice_in_dim(t, j * rb, rb, 0)
            kk, vv, ka = kg, vg, key_at
            if banded:      # padded slots j*rb .. : positions j*rb - window ..
                span = lambda t: jax.lax.dynamic_slice_in_dim(
                    t, j * rb, rb + z.window, 0)
                kk, vv, ka = span(kg), span(vg), span(key_at)
            qa = cut(at)[:, None]
            s = jnp.einsum("qhd,kd->hqk", cut(q), kk) / math.sqrt(z.dh)
            seen = (ka[None, :] >= 0) & (ka[None, :] <= qa)
            if kind == "win":
                seen = seen & (qa - ka[None, :] < z.window)
            if others is not None:
                itself = (ka[None, :] == qa)[None]
                own_k = jax.lax.dynamic_index_in_dim(cut(k), g, 1, False)
                s_own = jnp.einsum("qhd,qd->hq", cut(q), own_k) \
                    / math.sqrt(z.dh)
                s = jnp.where(itself, s_own[..., None], s)
            s = jnp.where(seen[None], s, -jnp.inf)
            m = jnp.max(s, axis=-1, keepdims=True)
            if b_h is not None:
                m = jnp.maximum(m, b_h)
            e = jnp.exp(s - m)
            total = jnp.sum(e, axis=-1, keepdims=True)
            if b_h is not None:
                total = total + jnp.exp(b_h - m)
            p = e / total
            o = jnp.einsum("hqk,kd->qhd", p, vv)
            if others is not None:
                own_v = jax.lax.dynamic_index_in_dim(cut(v), g, 1, False)
                mine = jnp.sum(jnp.where(itself, p, 0.0), axis=-1)   # (h, q)
                theirs = jnp.einsum("hqk,kd->qhd",
                                    jnp.where(itself, p, 0.0), vv)
                o = o - theirs + jnp.einsum("hq,qd->qhd", mine, own_v)
            return o

        o = jax.lax.map(rows, jnp.arange(n // rb)).reshape(n, hb * z.dv)
        return acc + o @ _f32(jax.lax.dynamic_slice_in_dim(
            leaves["o_w"][i], b * hb * z.dv, hb * z.dv, 0))

    out = jax.lax.fori_loop(0, z.heads // hb, head_block,
                            jnp.zeros((n, z.d), jnp.float32))
    return out, (k, v)


def _swiglu(h, gate, up, down, width):
    """``down(silu(gate(h)) * up(h))``, its ``width`` columns in blocks, each
    weight block sliced out of its (bf16) leaf and upcast alone."""
    b = _block_of(width, MLP_BLOCK)

    def block(j, acc):
        cols = lambda w: _f32(jax.lax.dynamic_slice_in_dim(w, j * b, b, 1))
        inner = jax.nn.silu(h @ cols(gate)) * (h @ cols(up))
        return acc + inner @ _f32(jax.lax.dynamic_slice_in_dim(
            down, j * b, b, 0))

    return jax.lax.fori_loop(0, width // b, block, jnp.zeros_like(h))


def _route(h, router_w, bias, z, renormalize, way):
    """-> (weights (n, k), chosen experts (n, k), what is left of ``way``
    (n,), ``distance`` (n, held): how far each held expert's SELECTION score
    ``s + b`` lies from the CUT (midway between the row's k-th and the
    next), in standard deviations of the row's selection scores). ``way``
    (n,) int32, 0 = the plain pass: the held experts within ``TIE`` of the
    cut, at most the two nearest, may each change sides: r = 1, 2 or 4 ways,
    of which this row takes ``way % r`` and hands ``way // r`` on."""
    k, n = z.top_k, h.shape[0]
    scores = jax.nn.sigmoid(h @ router_w)
    select = scores + bias[None, :]
    mine = select[:, z.first:z.first + z.held]
    side = jnp.full((n, z.held), -1, jnp.int8)
    if z.router > k:
        edge = jax.lax.top_k(select, k + 1)[0][:, k - 1:]
        cut = jnp.mean(edge, axis=-1, keepdims=True)
        distance = jnp.abs(mine - cut) / jnp.std(select, axis=-1,
                                                 keepdims=True)
        near, which = jax.lax.top_k(-distance, min(2, z.held))
        near = -near <= TIE                                 # (n, 1 or 2)
        ways = 1 << jnp.sum(near, axis=-1)                  # 1, 2 or 4
        digit, way = way % ways, way // ways
        turn = ((digit[:, None] >> jnp.arange(near.shape[1])) & 1) > 0
        taken = jnp.take_along_axis(mine, which, axis=-1) > cut
        given = jnp.where(near, taken ^ turn, -1).astype(jnp.int8)
        side = jax.vmap(lambda s, w, g: s.at[w].set(g))(side, which, given)
    else:                                      # the router picks every expert
        distance = jnp.full((n, z.held), jnp.inf)
    # a given side outranks, or is outranked by, every selection score
    key = select.at[:, z.first:z.first + z.held].add(
        jnp.where(side > 0, 4.0, jnp.where(side == 0, -4.0, 0.0)))
    chosen = jax.lax.top_k(key, k)[1]
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights, chosen, way, distance


def _experts(h, weights, chosen, blocks, i, z):
    """The chosen experts HELD here, one at a time; a row that did not
    choose one, or chose one held elsewhere, adds exactly zero."""
    def one(e, acc):
        take = lambda name: jax.lax.dynamic_index_in_dim(
            blocks[name][i], e, 0, keepdims=False)
        y = _swiglu(h, take("expert_gate_w"), take("expert_up_w"),
                    take("expert_down_w"), z.expert)
        mine = chosen == z.first + e            # (n, k): at most one True
        w = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1, keepdims=True)
        return acc + jnp.where(jnp.any(mine, axis=-1, keepdims=True),
                               w * y, 0.0)

    return jax.lax.fori_loop(0, z.held, one, jnp.zeros_like(h))


def reference_forward(params, ids, cfg, way=None, others=None, last=None,
                      keep=None):
    """ids (T,) int32 -> (float32 logits of the last ``last`` positions
    (None: all T), ``kept``: a list, a layer an entry, of its ``k`` (rows,
    KV, 192) rotated and ``v`` (rows, KV, 128) scaled rows, the absolute
    position ``first`` of row 0 and, of a routed layer, the routers'
    ``chosen`` (rows, k) and ``distance`` (rows, held)). The plain pass
    (``others`` None) walks all T positions and keeps every row of a full
    layer and the last ``keep + window`` rows of a window layer (``keep``
    None: all). With ``others`` (a plain pass's ``kept``) only the last
    ``last`` positions are walked, each seeing the earlier ones through
    ``others`` and itself through its own rows, under the resolution number
    ``way`` (rows,) int32 gives it (None: 0, the plain pass)."""
    m, z = cfg["model"], _sizes(cfg)
    _refuse_what_is_not_computed(cfg)
    T = ids.shape[0]
    n = T if others is None else (T if last is None else int(last))
    at = jnp.arange(T - n, T)
    if way is None:
        way = jnp.zeros(n, jnp.int32)
    kept = []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"])[ids[T - n:]]
        for l in range(z.layers):
            (mixer, i), (rest, r) = _layer_leaves(params, z, l)
            kind = z.kinds[l]
            seen = None if others is None else (
                others[l]["k"], others[l]["v"], others[l]["first"])
            a, (k, v) = _attention(
                _rms_norm(x, _f32(rest["attn_norm_g"][r]), z.eps), at, kind,
                mixer, i, z, seen)
            x = x + a
            h = _rms_norm(x, _f32(rest["mlp_norm_g"][r]), z.eps)
            first = T - n
            if others is None and keep is not None and kind == "win":
                rows = min(T, int(keep) + z.window)
                k, v, first = k[T - rows:], v[T - rows:], T - rows
            entry = {"k": k, "v": v, "first": first}
            if "router_w" in rest:
                weights, chosen, way, distance = _route(
                    h, _f32(rest["router_w"][r]), _f32(rest["router_bias"][r]),
                    z, m["norm_topk_prob"], way)
                entry.update(chosen=chosen, distance=distance)
                x = x + _experts(h, weights, chosen, rest, r, z)
            else:
                x = x + _swiglu(h, rest["gate_w"][r], rest["up_w"][r],
                                rest["down_w"][r], z.dense_mlp)
            kept.append(entry)
        x = _rms_norm(x, _f32(params["norm_g"]), z.eps)
        if last is not None:
            x = x[-int(last):]
        return x @ _f32(params["lm_head"]), kept


def resolution_logits(params, ids, cfg, last):
    """(RESOLUTIONS, last, vocab): the last ``last`` positions' logits under
    each resolution number, every one against the plain pass's context; row
    0 is the plain pass itself."""
    last = int(last)
    plain, kept = reference_forward(params, ids, cfg, last=last, keep=last)
    resolved = lambda r: reference_forward(
        params, ids, cfg, jnp.full(last, r, jnp.int32), others=kept,
        last=last)[0]
    return jnp.concatenate([plain[None], jax.lax.map(
        resolved, jnp.arange(1, RESOLUTIONS))])


def reference_logits(params, ids, cfg, last=None):
    """The logits a served token is held to (the module's docstring): per
    position and token the largest over the near-ties' resolutions of (logit
    - that resolution's best), put back at the plain pass's best. ``last``:
    of the last positions only (a long context)."""
    every = resolution_logits(params, ids, cfg,
                              ids.shape[0] if last is None else last)
    best = jnp.max(every, axis=-1, keepdims=True)
    return jnp.max(every - best, axis=0) + best[0]


def reference_loss(params, ids, cfg):
    """Mean cross entropy of predicting ids[1:] from ids[:-1], the plain
    pass (no auxiliary term: the selection bias is moved by a rule, and a
    share of the experts cannot form a balancing loss)."""
    lg = reference_forward(params, ids, cfg)[0][:-1]
    logp = lg - jax.nn.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))


# ----------------------------------------- operations and bytes from shapes
def mixer_params(cfg, kind):
    """Matmul weights of one layer's attention: 89,128,960 on a full layer,
    94,371,840 on a window layer (+ 64 sink logits)."""
    z = _sizes(cfg)
    kv = z.win_kv if kind == "win" else z.kv
    return z.d * z.heads * z.dh + z.d * kv * (z.dh + z.dv) \
        + z.heads * z.dv * z.d


def _expert_params(z):
    return 3 * z.d * z.expert


def _norm_params(z):
    return z.layers * 2 * z.d + z.d


def _mixers(cfg):
    z = _sizes(cfg)
    return sum(mixer_params(cfg, k) for k in z.kinds)


def held_params(cfg):
    """Every parameter this chip holds: 3,997,286,016 at the published
    widths and the stated share."""
    z = _sizes(cfg)
    routed = z.layers - z.dense
    return _mixers(cfg) + z.kinds.count("win") * z.heads \
        + z.dense * 3 * z.d * z.dense_mlp \
        + routed * (z.d * z.router + z.router + z.held * _expert_params(z)) \
        + _norm_params(z) + 2 * z.vocab * z.d


def experts_met(cfg):
    """Routed experts HELD HERE that a token is expected to meet in one
    layer: ``num_experts_per_tok x held / router width`` (0.25)."""
    z = _sizes(cfg)
    return z.top_k * z.held / z.router


def matmul_params(cfg):
    """Parameters that sit in a matrix multiplication for one token, on this
    chip: every mixer, the dense layer's MLP, a routed layer's router and
    the expected share of routed experts, the head. 1.579 B."""
    z = _sizes(cfg)
    routed = z.layers - z.dense
    return _mixers(cfg) + z.dense * 3 * z.d * z.dense_mlp \
        + routed * (z.d * z.router + experts_met(cfg) * _expert_params(z)) \
        + z.d * z.vocab


def kv_bytes_per_position(cfg, itemsize=2):
    """Bytes one cached position holds across the FULL layers: K at 4 x 192
    and V at 4 x 128 (7,680 at three full layers)."""
    z = _sizes(cfg)
    return z.kinds.count("attn") * z.kv * (z.dh + z.dv) * itemsize


def window_bytes_per_sequence(cfg, itemsize=2):
    """Bytes the window layers' rings hold a sequence, whatever its length:
    128 slots of K at 8 x 192 and V at 8 x 128 (6,553,600 at ten)."""
    z = _sizes(cfg)
    return z.kinds.count("win") * z.window * z.win_kv * (z.dh + z.dv) \
        * itemsize


def decode_kv_bytes(cfg, context, itemsize=2):
    """K/V bytes ONE decoded token's attention reads, all layers: the full
    layers' rows of the ``context`` positions, the rings at ``min(context,
    128)`` slots."""
    window = _sizes(cfg).window
    return context * kv_bytes_per_position(cfg, itemsize) \
        + window_bytes_per_sequence(cfg, itemsize) \
        * min(context, window) / window


def weight_bytes(cfg, itemsize=2):
    """Bytes of weights one decode step must stream: the matmul weights a
    token meets (the EXPECTED held experts), the norms' gains, the sinks and
    the selection biases. 3.16 GB in bf16. The lookup reads one row."""
    z = _sizes(cfg)
    return (matmul_params(cfg) + _norm_params(z)
            + z.kinds.count("win") * z.heads
            + (z.layers - z.dense) * z.router) * itemsize


def decode_flops_per_token(cfg):
    return 2 * matmul_params(cfg)


def decode_bytes_per_token(cfg, context, itemsize=2):
    """HBM bytes one decode step needs: the weights a token meets once and
    the K/V its attention reads (``decode_kv_bytes``: the ring counts at
    most 128 slots)."""
    return weight_bytes(cfg, itemsize) \
        + decode_kv_bytes(cfg, context, itemsize)


def full_flash_flops(cfg, seq):
    """The FULL layers' causal attention, forward over one sequence: every
    head against every earlier position and itself, q.k over 192 columns and
    p.v over 128."""
    z = _sizes(cfg)
    return z.kinds.count("attn") * z.heads * seq * (seq + 1) / 2 \
        * 2 * (z.dh + z.dv)


def full_flash_bytes(cfg, seq, itemsize=2):
    """q and o at 64 heads, K and V at the KV heads' width, each once."""
    z = _sizes(cfg)
    return z.kinds.count("attn") * seq * (
        z.heads * (z.dh + z.dv) + z.kv * (z.dh + z.dv)) * itemsize


def win_flash_flops(cfg, seq):
    """The WINDOW layers' banded attention, forward over one sequence: row i
    meets ``min(i + 1, 128)`` keys."""
    z = _sizes(cfg)
    w = min(z.window, seq)
    pairs = w * (w + 1) / 2 + (seq - w) * w
    return z.kinds.count("win") * z.heads * pairs * 2 * (z.dh + z.dv)


def win_flash_bytes(cfg, seq, itemsize=2):
    z = _sizes(cfg)
    return z.kinds.count("win") * seq * (
        z.heads * (z.dh + z.dv) + z.win_kv * (z.dh + z.dv)) * itemsize


def attention_flops_fwd(cfg, seq):
    return full_flash_flops(cfg, seq) + win_flash_flops(cfg, seq)


def train_flops_per_token(cfg, seq):
    """Forward + backward FLOPs of one token at sequence length ``seq``: 6 x
    the matmul parameters a token meets here, plus attention at 3x its
    forward. (No cell trains this configuration: the harness's interface
    asks every family for the function.)"""
    return 6 * matmul_params(cfg) + 3 * attention_flops_fwd(cfg, seq) / seq
