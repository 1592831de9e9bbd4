"""Solar-Open2 (``model_type: solar_open2``; the published ``config.json`` of
``upstage/Solar-Open2-250B``): the program's model, the plain reference, and
the operations and bytes the algorithm needs — for ONE CHIP'S SHARE of a
stated deployment.

A hybrid: of every ``gqa_interval + 1`` = 4 layers the first mixes with
softmax attention (grouped queries, NO positional embedding, an output
gate) and three with Kimi Delta Attention (KDA: a gated delta rule with a
per-channel decay behind a short causal convolution), whose memory of the
sequence is a fixed-size STATE and not a row a position. Every layer's MLP
is routed (sigmoid scores, top-k, normalised) beside one shared expert. The
program's model is ``models/llama.py``'s trunk with a layer pattern
(``models/kda.py`` is the KDA mixer). Every function takes the
configuration file's dict; the sizes are under its ``"model"`` key, named
as in the published file (``hidden_size``, ``num_hidden_layers``,
``gqa_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``linear_attn_config`` {``num_heads``, ``head_dim``,
``short_conv_kernel_size``}, ``moe_intermediate_size``,
``n_routed_experts``, ``n_shared_experts``, ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor``, ``use_rope``,
``use_gqa_gate``, ``kda_use_full_proj``, ``kda_allow_neg_eigval``,
``rms_norm_eps``, ``vocab_size``). **The share** is
``families/pangu_ultra_moe.py``'s: ``n_routed_experts`` under ``reduced``
means the value under ``model`` counts the experts HELD here,
``published.n_routed_experts`` is the router's width and
``share.experts_first`` the router's number of the first held one; the
pairs that fall on held experts are computed, the others add nothing, in
the program and in the reference alike.

**The reference** is the forward pass in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision, no kernel, no cache, one sequence.
Every layer, x (T, D), pre-norm: ``x <- x + Mixer(RMSNorm(x; attn_norm_g))``;
``x <- x + MoE(RMSNorm(x; mlp_norm_g))``.

* KDA layer, per position t and head: ``[q | k | v] = SiLU(conv(h
  kda_qkv_w))``, the convolution an explicit sum over the last 4 positions
  (``kda_conv_w`` (4, 3 H dk), the last tap on t, zeros before the
  sequence); q, k L2-normalised (``/ sqrt(sum + 1e-6)``), q x dk^-1/2; ``g
  = -exp(kda_a_log[head]) softplus((h kda_f_a_w) kda_f_b_w + kda_dt_bias)``
  per channel; ``beta = 2 sigmoid(h kda_b_w)``; then the recurrence TOKEN BY
  TOKEN (``lax.scan`` over positions, NOT the chunked algebra of
  ``ops/pallas/kda.py``, so that it is independent of the code under test):
  ``S' = Diag(e^g) S``, ``S <- S' + beta k (v - S'^T k)^T``, ``o = S^T q``;
  ``y = [RMSNorm_head(o; kda_o_norm_g) * sigmoid((h kda_g_a_w) kda_g_b_w)]
  o_w``.
* softmax layer: ``q`` 64 heads, ``k``, ``v`` 8 heads of 128 (query head j
  reads KV head j // 8), no rotation, causal softmax at 128^-1/2 in full,
  ``y = [attn * sigmoid(h attn_gate_w)] o_w``.
* MoE: ``s = sigmoid(h router_w)`` over the router's whole width in
  float32, the ``num_experts_per_tok`` largest, ``w = s_top / (sum(s_top) +
  1e-20) x routed_scaling_factor``, the chosen experts HELD here one at a
  time (SwiGLU of width ``moe_intermediate_size``), plus the shared
  expert's SwiGLU, always.
* final RMSNorm, untied head.

So that it fits the chip at 32,768 tokens beside the served weights, the
softmax layer walks KV-head groups and query rows in blocks, a KDA layer
walks the sequence in segments of up to ``KDA_SEGMENT`` rows (projections
and gates a segment at a time, the token scan carrying the state across
them), every SwiGLU its columns in blocks. It reads the SAME parameter
values the system holds, in the program's layout.

Departures from the published description, each on purpose: (1) what the
config does not say is the configuration file's ``assumed`` (sigmoid
scoring, SiLU, the softmax gate's shape, KDA's details and draws); a
reference of another scoring or activation refuses; (2) the three KDA
projections are one leaf, a loader's concatenation; (3) no attention mask
(one unpadded sequence), no bias anywhere; (4) the router's logits in
float32 from float32 activations.

**Near-ties of the router** are ``families/pangu_ultra_moe.py``'s finding
and its cure (``TIE``, ``RESOLUTIONS``, ``_route``: imported, not copied): a
bf16 program and this float32 pass may put a held expert whose router logit
lies at the cut on different sides, both validly. ``reference_forward``
takes ``way`` (a position's resolution number) and ``others``: the plain
pass's layer INPUTS (layers, T, D), through which a position sees every
EARLIER position — their keys and values in the softmax layer; in a KDA
layer their rows of the convolution's window and the state they left —
while its own row is computed from its own input. ``reference_logits``, what
``systems.ServeSystem.check`` and ``long_check.py`` hold a served token to,
evaluates ``RESOLUTIONS`` passes, shifts each row by its own best logit and
returns per position and token the largest over the passes, put back at the
plain pass's best: ``max - logit[token]`` of it is at most m exactly where
SOME valid resolution has the token within m of its best. **The statistic is
then of the logits' ORDER at the near-ties, not of their values**: every
comparison of VALUES (the CPU tests; ``long_check.py``'s
``worst_logit_difference`` reads this envelope) uses ``reference_forward``'s
plain pass, which is what the tolerances on logits are written for.

**The embedding's draw** (``assumed_values.embedding_std``, 1: a torch
embedding's default, N(0, 1)). With rows of std 0.02, as every other
configuration here draws them, and every branch's input RMS-normalised, the
residual stream of this 4-layer model is ALL branch output (rms 0.02 against
~0.6 a layer): bf16's rounding of one branch is rounding of the whole stream,
the softmax over scores of std 1.6 and each SwiGLU multiply it (x 2.2 and x
1.5, measured piece by piece), and it grows from layer to layer — hidden
states 1.9 / 3.4 / 4.6% off after layers 0 / 1 / 2 on the chip, the same on
the CPU, the same with every kernel off (PERF.md, PR 33), logits off by a
sigma of 0.06 where the harness's margin for a served token is 0.1: a
CORRECT bf16 program fails that check on about half the seeds. No arithmetic
short of three bf16 passes a matmul cures it. At std 1 the stream has a
scale of its own, as a trained model's has (0.45 / 0.67 / 0.84%, logits'
sigma 0.010; the logits' spread is the head's and stays 1.28); the
mechanisms' shapes, operations and bytes are untouched. The draw also makes
the check less sensitive to the mixers (a hidden-state error of 4.6% reads
0.84%), so what it still refuses was measured at this draw
(``benchmark/kda_witness.py`` on the chip, 4,096 tokens, PERF.md PR 33): a
dropped convolution tap reads a shortfall of 1.62 and beta without its
factor 2 0.74 against the margin of 0.1 and the sound program's 0.023; a
bfloat16 state it does NOT see (median difference 0.0115 for 0.0104), which
the same functions in float32 show (2.9e-3 for 4.4e-6).

**The counts**: a token meets every weight of its mixer, the router and the
shared expert, and of the routed experts the EXPECTED share held here
(``num_experts_per_tok x held / router width`` = 1 a layer). The KDA
kernel's own counts (``kda_prefill_*``) are what the state pass
(``kda_chunk_fwd``) must do at the program's chunk: its operands read once,
its outputs written once, its four matmuls a chunk.
"""

import collections
import math

import jax
import jax.numpy as jnp

from benchmark.families.llama import _f32, _rms_norm
from benchmark.families.pangu_ultra_moe import (RESOLUTIONS, _block_of,
                                                _layer_of, _route, _routed,
                                                _swiglu, _take)

Sizes = collections.namedtuple(
    "Sizes", "d layers softmax heads kv dh kda_heads dk conv expert held "
             "first router top_k shared vocab")
SHARED_LEAVES = ("shared_gate_w", "shared_up_w", "shared_down_w")
ROW_BLOCK, KDA_SEGMENT = 512, 2048
L2_EPS = 1e-6
LLAMA_EMBED_STD = 0.02      # what ``models/llama.py::init_params`` draws


# ------------------------------------------------------ the program's model
def vocab_size(cfg):
    return cfg["model"]["vocab_size"]


def _sizes(cfg):
    m = cfg["model"]
    held = m["n_routed_experts"]
    cut = "n_routed_experts" in cfg.get("reduced", ())
    lin = m["linear_attn_config"]
    return Sizes(
        m["hidden_size"], m["num_hidden_layers"], tuple(m["gqa_layers"]),
        m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"],
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
        m["moe_intermediate_size"], held,
        cfg.get("share", {}).get("experts_first", 0) if cut else 0,
        cfg["published"]["n_routed_experts"] if cut else held,
        m["num_experts_per_tok"], m["n_shared_experts"], m["vocab_size"])


def _refuse_what_is_not_computed(cfg):
    m = cfg["model"]
    lin = m["linear_attn_config"]
    if {k: cfg["assumed_values"].get(k) for k in
            ("router_scoring", "hidden_act")} != {"router_scoring": "sigmoid",
                                                 "hidden_act": "silu"} \
            or m["use_rope"] or not m["use_gqa_gate"] \
            or m["kda_use_full_proj"] or not m["kda_allow_neg_eigval"] \
            or m["first_k_dense_replace"] or m["tie_word_embeddings"] \
            or lin["num_kv_heads"] not in (None, lin["num_heads"]) \
            or not m["n_shared_experts"]:
        raise SystemExit(
            "benchmark: the solar_open2 family computes a sigmoid router, "
            "SiLU, softmax layers without rotary embedding and with an "
            "output gate, KDA with low-rank gates and beta in (0, 2), every "
            "layer routed beside a shared expert, an untied head; this file "
            "asks otherwise")


def build_model(cfg, kind):
    """``deepspeed_tpu``'s Llama trunk with the layer pattern, gated NoPE
    softmax layers at the published head size, KDA layers, a sigmoid router
    over the published width, this chip's experts and the shared expert. A
    serve system asks for the parameters in the type it serves."""
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

    m, z = cfg["model"], _sizes(cfg)
    _refuse_what_is_not_computed(cfg)
    over = {"remat": cfg["train"]["remat"]} if kind == "train" else {}
    if kind == "serve" and cfg["serve"]["dtype"] == "bf16":
        over["param_dtype"] = jnp.bfloat16
    model = LlamaModel(LlamaConfig(
        vocab_size=z.vocab, n_positions=m["max_position_embeddings"],
        n_embd=z.d, n_layer=z.layers, n_head=z.heads, n_kv_head=z.kv,
        head_dim=z.dh, intermediate_size=z.expert,
        rms_norm_eps=m["rms_norm_eps"], tie_embeddings=False,
        n_experts=z.router, n_experts_per_tok=z.top_k,
        norm_topk_prob=m["norm_topk_prob"], n_shared_experts=z.shared,
        router_scoring=cfg["assumed_values"]["router_scoring"],
        routed_scaling_factor=m["routed_scaling_factor"],
        experts_held=(z.first, z.held), use_rope=False, attn_gate=True,
        gqa_layers=z.softmax, kda_heads=z.kda_heads, kda_head_dim=z.dk,
        kda_conv=z.conv, **over))
    # the weights are the benchmark's to draw: the embedding's rows at the
    # configuration's ``embedding_std`` (the module's docstring), everything
    # else as ``models/llama.py`` draws it
    grow = float(cfg["assumed_values"]["embedding_std"]) / LLAMA_EMBED_STD
    draw = model.init_params

    def init_params(key):
        params = draw(key)
        return {**params, "wte": (params["wte"] * grow).astype(
            params["wte"].dtype)}

    model.init_params = init_params
    return model


# ------------------------------------------------------ the plain reference
def _softmax_mixer(h, seen, blocks, layer, z):
    """Gated causal softmax attention of h (T, D), the layer's normed input,
    without positions -> (T, D). A position attends to ITS OWN key and
    value, computed from h, and to every earlier position through ``seen``
    (T, D) — the same normed input of the plain pass, or h itself."""
    T = h.shape[0]
    rep, rb = z.heads // z.kv, _block_of(T, ROW_BLOCK)
    wide = rep * z.dh

    def group(g, acc):
        cols = lambda name, width: _take(blocks[name], layer, g * width,
                                         width, 1)
        q = (h @ cols("q_w", wide)).reshape(T, rep, z.dh)
        k_w, v_w = cols("k_w", z.dh), cols("v_w", z.dh)
        k, v, k_own, v_own = seen @ k_w, seen @ v_w, h @ k_w, h @ v_w

        def rows(j):
            cut = lambda t: jax.lax.dynamic_slice_in_dim(t, j * rb, rb, 0)
            at = (j * rb + jnp.arange(rb))[:, None]
            itself = (jnp.arange(T)[None, :] == at)[None]
            s = jnp.einsum("qrd,kd->rqk", cut(q), k)
            s = jnp.where(itself, jnp.einsum("qrd,qd->rq", cut(q),
                                             cut(k_own))[..., None], s)
            s = jnp.where((jnp.arange(T)[None, :] <= at)[None],
                          s / math.sqrt(z.dh), -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("rqk,kd->qrd", p, v) + jnp.einsum(
                "rq,qd->qrd", jnp.sum(jnp.where(itself, p, 0.0), axis=-1),
                cut(v_own) - cut(v))

        o = jax.lax.map(rows, jnp.arange(T // rb)).reshape(T, wide)
        gate = jax.nn.sigmoid(h @ cols("attn_gate_w", wide))
        return acc + (o * gate) @ _take(blocks["o_w"], layer, g * wide,
                                        wide, 0)

    return jax.lax.fori_loop(0, z.kv, group, jnp.zeros_like(h))


def _kda_mixer(h, seen, blocks, layer, z, eps):
    """KDA of h (T, D), the layer's normed input -> (T, D): the recurrence
    token by token. A position's own row (its projection, gates, decay and
    step) is computed from h; what it meets of the EARLIER positions — their
    rows in the convolution's window and the state they left — from ``seen``
    (T, D), the plain pass's normed input or h itself: the scan carries the
    state along ``seen``'s trajectory and each position's output is read
    from ITS OWN update of it."""
    T = h.shape[0]
    H, dk = z.kda_heads, z.dk
    seg = _block_of(T, KDA_SEGMENT)
    get = lambda name: _layer_of(blocks[name], layer)
    qkv_w, conv_w = get("kda_qkv_w"), get("kda_conv_w")
    decay = -jnp.exp(get("kda_a_log"))[:, None]
    dt_bias = get("kda_dt_bias").reshape(H, dk)
    taps = z.conv

    def row_inputs(conv, x):
        """From the convolution's output and the normed input of the same
        positions: q, k, v (n, H, dk), g (n, H, dk), beta (n, H)."""
        q, k, v = (t.reshape(-1, H, dk) for t in
                   jnp.split(jax.nn.silu(conv), 3, axis=-1))
        unit = lambda t: t / jnp.sqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)
        g = decay * jax.nn.softplus(
            ((x @ get("kda_f_a_w")) @ get("kda_f_b_w")).reshape(-1, H, dk)
            + dt_bias)
        return unit(q) * dk ** -0.5, unit(k), v, g, \
            2.0 * jax.nn.sigmoid(x @ get("kda_b_w"))

    def update(state, k, v, g, beta):
        state = state * jnp.exp(g)[..., None]
        delta = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", state, k))
        return state + k[..., None] * delta[:, None, :]

    def segment(carry, j):
        state, tail = carry                 # (H, dk, dv), (taps - 1, 3 H dk)
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, j * seg, seg, 0)
        own, there = cut(h), cut(seen)
        window = jnp.concatenate([tail, there @ qkv_w])    # seen's rows
        earlier = sum(conv_w[i] * window[i:i + seg] for i in range(taps - 1))
        q, k, v, g, beta = row_inputs(
            earlier + conv_w[-1] * (own @ qkv_w), own)
        _, k_s, v_s, g_s, beta_s = row_inputs(
            earlier + conv_w[-1] * window[taps - 1:], there)

        def step(state, at):
            q, k, v, g, beta, k_s, v_s, g_s, beta_s = at
            o = jnp.einsum("hkv,hk->hv", update(state, k, v, g, beta), q)
            return update(state, k_s, v_s, g_s, beta_s), o

        state, o = jax.lax.scan(
            step, state, (q, k, v, g, beta, k_s, v_s, g_s, beta_s))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
            * get("kda_o_norm_g")
        gate = jax.nn.sigmoid((own @ get("kda_g_a_w")) @ get("kda_g_b_w"))
        return (state, window[seg:]), \
            (o.reshape(seg, H * dk) * gate) @ get("o_w")

    _, out = jax.lax.scan(
        segment, (jnp.zeros((H, dk, dk), jnp.float32),
                  jnp.zeros((taps - 1, 3 * H * dk), jnp.float32)),
        jnp.arange(T // seg))
    return out.reshape(T, -1)


def reference_forward(params, ids, cfg, way=None, held=None, others=None,
                      last=None):
    """ids (T,) int32 -> (float32 logits (T, vocab), per layer: its
    ``inputs`` (layers, T, D) before the mixer's norm, the routers'
    ``chosen`` experts (layers, T, k), ``distance`` (layers, T, experts
    held) of each held expert's logit from the row's cut in the row's
    standard deviations, ``router_logits`` (layers, T, router width)) of one
    sequence. ``way`` (T,) int32, None = 0 everywhere = the plain pass:
    which of its near-ties' resolutions each position takes
    (``pangu_ultra_moe._route``). ``others`` (layers, T, D), None = this
    pass's own: the layer inputs every position sees the EARLIER ones
    through. ``held`` (layers, T, experts held) int, None = all -1: a held
    expert's side given. ``last``: the head for the last ``last`` positions
    only, logits (last, vocab) — every layer still runs over all of the
    sequence (at 32,768 tokens (T, vocab) float32 is 3.2 GB)."""
    m, z = cfg["model"], _sizes(cfg)
    _refuse_what_is_not_computed(cfg)
    eps = float(m["rms_norm_eps"])
    T = ids.shape[0]
    if way is None:
        way = jnp.zeros(T, jnp.int32)
    blocks = params["blocks"]
    kept = collections.defaultdict(list)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"])[ids]
        for l in range(z.layers):
            gain = _layer_of(blocks["attn_norm_g"], l)
            h = _rms_norm(x, gain, eps)
            seen = h if others is None else _rms_norm(others[l], gain, eps)
            kept["inputs"].append(x)
            if l in z.softmax:
                a = _softmax_mixer(h, seen, params["attn_blocks"],
                                   z.softmax.index(l), z)
            else:
                a = _kda_mixer(h, seen, params["kda_blocks"],
                               l - sum(s < l for s in z.softmax), z, eps)
            x = x + a
            h = _rms_norm(x, _layer_of(blocks["mlp_norm_g"], l), eps)
            weights, chosen, way, distance, logits = _route(
                h, _layer_of(blocks["router_w"], l), z, m["norm_topk_prob"],
                float(m["routed_scaling_factor"]), way,
                None if held is None else held[l])
            kept["chosen"].append(chosen)
            kept["distance"].append(distance)
            kept["router_logits"].append(logits)
            x = x + _routed(h, weights, chosen, blocks, l, z) \
                + _swiglu(h, blocks, SHARED_LEAVES, (l,), z.shared * z.expert)
        x = _rms_norm(x[-(last or T):], params["norm_g"], eps)
        return x @ _f32(params["lm_head"]), \
            {name: jnp.stack(rows) for name, rows in kept.items()}


def reference_logits(params, ids, cfg, last=None):
    """The logits a served token is held to: per position and token the
    largest over the near-ties' resolutions of (logit - that resolution's
    best), put back at the plain pass's best (the module's docstring;
    ``pangu_ultra_moe.reference_logits``'s form). One resolution at a
    time. ``last``: of the last ``last`` positions only
    (``reference_forward``)."""
    shifted = lambda lg: lg - jnp.max(lg, axis=-1, keepdims=True)
    plain, kept = reference_forward(params, ids, cfg, last=last)
    resolved = lambda r: reference_forward(
        params, ids, cfg, jnp.full(ids.shape[0], r, jnp.int32),
        others=kept["inputs"], last=last)[0]
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
def softmax_params(cfg):
    """Matmul weights of one softmax layer's mixer: q, k, v, o and the
    output gate. 109.1 M at the published widths."""
    z = _sizes(cfg)
    return 3 * z.d * z.heads * z.dh + 2 * z.d * z.kv * z.dh


def kda_params(cfg):
    """Matmul weights of one KDA layer's mixer: the q | k | v projection,
    the two low-rank gates, beta, o. 137.6 M at the published widths (the
    convolution's taps, the decay's constants and the head norm's gain, 0.1
    M, are elementwise: ``_small_params``)."""
    z = _sizes(cfg)
    wide = z.kda_heads * z.dk
    return 4 * z.d * wide + 2 * (z.d * z.dk + z.dk * wide) + z.d * z.kda_heads


def _small_params(cfg):
    """What is held and read but sits in no matmul: norm gains, and of a KDA
    layer the taps, ``a_log``, ``dt_bias`` and the head norm's gain."""
    z = _sizes(cfg)
    wide = z.kda_heads * z.dk
    return z.layers * 2 * z.d + z.d + (z.layers - len(z.softmax)) * (
        3 * wide * z.conv + z.kda_heads + wide + z.dk)


def _expert_params(z):
    return 3 * z.d * z.expert


def _mixer_params(cfg):
    z = _sizes(cfg)
    return len(z.softmax) * softmax_params(cfg) \
        + (z.layers - len(z.softmax)) * kda_params(cfg)


def held_params(cfg):
    """Every parameter this chip holds: 3.31 B at the published widths and
    the stated share."""
    z = _sizes(cfg)
    return _mixer_params(cfg) + _small_params(cfg) + 2 * z.vocab * z.d \
        + z.layers * (z.d * z.router + (z.held + z.shared) * _expert_params(z))


def experts_met(cfg):
    """Routed experts HELD HERE that a token is expected to meet in one
    layer: ``num_experts_per_tok x held / router width`` (1)."""
    z = _sizes(cfg)
    return z.top_k * z.held / z.router


def matmul_params(cfg):
    """Parameters that sit in a matrix multiplication for one token, on this
    chip: its layer's mixer, the router, the shared expert, the expected
    share of routed experts, and the output head."""
    z = _sizes(cfg)
    return _mixer_params(cfg) + z.d * z.vocab + z.layers * (
        z.d * z.router + (z.shared + experts_met(cfg)) * _expert_params(z))


def kda_state_flops_per_token(cfg):
    """The recurrence itself for one token, all KDA layers: the decay, S'^T
    k, the rank-1 update and S^T q over a (dk, dv) state a head."""
    z = _sizes(cfg)
    return (z.layers - len(z.softmax)) * z.kda_heads * 7 * z.dk * z.dk


def state_bytes_per_sequence(cfg, itemsize=2):
    """What the KDA layers keep of ONE sequence whatever its length: the
    float32 state and the convolution's window (13.0 MB at the published
    widths over the 3 layers held)."""
    z = _sizes(cfg)
    return (z.layers - len(z.softmax)) * (
        z.kda_heads * z.dk * z.dk * 4
        + (z.conv - 1) * 3 * z.kda_heads * z.dk * itemsize)


def kv_bytes_per_position(cfg, itemsize=2):
    """Bytes one cached position holds: K and V at the KV heads, in the
    SOFTMAX layers only (4,096 at the published widths over the 1 held;
    every layer caching would be 16,384)."""
    z = _sizes(cfg)
    return len(z.softmax) * 2 * z.kv * z.dh * itemsize


def softmax_attn_flops_fwd(cfg, seq):
    """Causal softmax attention over one sequence, the softmax layers: q.k
    and p.v, every head against every earlier position: half the square."""
    z = _sizes(cfg)
    return len(z.softmax) * z.heads * seq * seq / 2 * 4 * z.dh


def kda_prefill_flops(cfg, seq):
    """What the state pass of the chunked form (``kda_chunk_fwd``) must
    multiply for one sequence, all KDA layers, at the program's chunk C:
    per chunk and head W S, Qg S and Kend^T delta (C x dk x dv each) and
    Aqk delta (C x C x dv). The chunk-local operands (scores by halving,
    the in-chunk inverse) are XLA matmuls outside the kernel."""
    from deepspeed_tpu.ops.pallas.kda import CHUNK

    z = _sizes(cfg)
    chunks = -(-seq // CHUNK)
    return (z.layers - len(z.softmax)) * z.kda_heads * chunks * 2 * CHUNK \
        * (3 * z.dk * z.dk + CHUNK * z.dk)


def kda_prefill_bytes(cfg, seq, itemsize=2):
    """What that pass must move: per position and head U, W, Qg, Kend (dk
    values each) and a row of Aqk (C) read, the output (dv) written; per
    chunk the decay (dk float32); the state once in and once out."""
    from deepspeed_tpu.ops.pallas.kda import CHUNK

    z = _sizes(cfg)
    chunks = -(-seq // CHUNK)
    return (z.layers - len(z.softmax)) * z.kda_heads * (
        chunks * CHUNK * (5 * z.dk + CHUNK) * itemsize + chunks * z.dk * 4
        + 2 * z.dk * z.dk * 4)


def attention_flops_fwd(cfg, seq):
    return softmax_attn_flops_fwd(cfg, seq) \
        + seq * kda_state_flops_per_token(cfg)


def train_flops_per_token(cfg, seq):
    """Forward + backward FLOPs per trained token at sequence length
    ``seq``. (No cell trains this configuration.)"""
    return 6 * matmul_params(cfg) + 3 * attention_flops_fwd(cfg, seq) / seq


def weight_bytes(cfg, itemsize=2):
    """Bytes of weights one decode step must stream: the matmul weights a
    token meets (``matmul_params``: the EXPECTED held experts) and the
    small leaves. 1.48 GB in bf16. The lookup reads one row."""
    return (matmul_params(cfg) + _small_params(cfg)) * itemsize


def decode_flops_per_token(cfg):
    """One token through every matmul weight it meets and the KDA layers'
    recurrence; the softmax layer's attention over the cache is 4 x head_dim
    x heads a position, at a context."""
    return 2 * matmul_params(cfg) + kda_state_flops_per_token(cfg)


def decode_bytes_per_token(cfg, context, itemsize=2):
    """HBM bytes one decode step needs: the weights a token meets once, the
    softmax layers' K and V of the ``context`` positions it attends to, and
    the KDA layers' state read and written (the window too)."""
    return weight_bytes(cfg, itemsize) \
        + context * kv_bytes_per_position(cfg, itemsize) \
        + 2 * state_bytes_per_sequence(cfg, itemsize)
