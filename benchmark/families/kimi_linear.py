"""Kimi-Linear-48B-A3B (``model_type: kimi_linear``; the published
``config.json`` of ``moonshotai/Kimi-Linear-48B-A3B-Instruct``): the
program's model, the plain reference, and the operations and bytes the
algorithm needs — for ONE CHIP'S SHARE of a stated deployment, on the
TRAINING path.

The program's model is ``models/llama.py``'s trunk with what this
architecture's blocks hold. Every function takes the configuration file's
dict; the sizes are under its ``"model"`` key, named as in the published
file (``hidden_size``, ``num_hidden_layers``, ``first_k_dense_replace``,
``linear_attn_config`` = ``{kda_layers, full_attn_layers, num_heads,
head_dim, short_conv_kernel_size}`` with the layers numbered FROM 1,
``num_attention_heads``, ``kv_lora_rank``, ``q_lora_rank`` (null),
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``mla_use_nope``,
``intermediate_size`` = the DENSE layer's width, ``moe_intermediate_size`` =
one expert's, ``num_experts``, ``num_shared_experts``,
``num_experts_per_token``, ``moe_renormalize``, ``routed_scaling_factor``,
``moe_router_activation_func``, ``rms_norm_eps``, ``vocab_size``). **The
share** (model-configs guide, section 4): ``num_experts`` is listed under
``reduced``, so the value under ``model`` is the number of experts HELD
here, ``published.num_experts`` is the router's width and
``share.experts_first`` the router's number of the first held one. The
router scores all of them and picks ``num_experts_per_token``; the pairs
that fall on held experts are computed, the others add nothing — in the
program and in the reference alike. A sliced vocabulary is a smaller
vocabulary.

**The reference** is the forward pass in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision, no kernel, no cache, one sequence;
its gradients are ``jax.grad`` of it. A layer, x (T, D), eps 1e-5, ``h =
RMSNorm(x; attn_norm_g)``:

* a KDA layer (``linear_attn_config.kda_layers``), H heads of dk = dv =
  ``head_dim``: ``[q | k | v] = SiLU(conv(h kda_qkv_w))``, a causal depthwise
  convolution over the last ``short_conv_kernel_size`` positions (the last
  tap on the current one, no bias); q and k L2-normalised a head (eps 1e-6),
  q x dk^-1/2; the log-decay a channel ``g = -exp(A_log[head]) softplus((h
  f_a) f_b + dt_bias)``; ``beta = 2 sigmoid(h b_w)``; then TOKEN BY TOKEN
  (``lax.scan``, ``SCAN_BLOCK`` steps under one ``jax.checkpoint`` so that
  the gradient keeps a block's first state and not 16,384 of them)::

      S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t

  ``a = o_w [RMSNorm_head(o; kda_o_norm_g) * sigmoid((h g_a) g_b)]``;
* the MLA layer (``full_attn_layers``): ``q = h q_w`` (H heads of nope +
  rope columns, NO low-rank bottleneck: ``q_lora_rank`` null); ``[c_kv |
  k_pe] = h kv_a_w`` (``kv_lora_rank`` + rope), ``c_kv`` RMS-normed
  (``kv_a_norm_g``); ``k_nope = c_kv kv_b_k_w``, ``v = c_kv kv_b_v_w`` a
  head; ``k = [k_nope | k_pe]``, the SAME ``k_pe`` for every head, NO
  rotation of any column (``mla_use_nope``); softmax(q k^T / sqrt(nope +
  rope)) under the causal mask, a block of ``ROW_BLOCK`` query rows and one
  head at a time; ``a = o_w attn``;
* ``x <- x + a``; ``m = MLP(RMSNorm(x; mlp_norm_g))``; ``x <- x + m`` (two
  norms a layer);
* MLP of the first ``first_k_dense_replace`` layers: SwiGLU of width
  ``intermediate_size``. Of the others: ``s = sigmoid(h router_w)`` over the
  router's whole width; the ``num_experts_per_token`` largest of ``s + b``
  (``router_bias``: selection ONLY); ``w = s_top / (sum(s_top) + 1e-20) x
  routed_scaling_factor`` (without b); ``sum_j w_j E_{e_j}(h)`` over the
  chosen experts HELD here, each a SwiGLU of width
  ``moe_intermediate_size``, walked one at a time, plus the shared expert's
  SwiGLU, always;
* final RMSNorm, untied head; the loss is next-token cross entropy and
  nothing else (the bias is moved by the aux-loss-free rule inside the
  engine's step, not by a loss term).

It reads the SAME parameter values the system holds, in the program's
layout, so a difference is a difference of arithmetic.

Departures from the published modelling code, each on purpose: (1) the q,
k, v projections of a KDA layer and their three convolutions are held side
by side as ONE ``kda_qkv_w`` / ``kda_conv_w`` (a loader's concatenation),
``kv_b_proj`` as two leaves, the experts stacked ``(L, held, ...)``; (2)
the router's logits and the weighted sum of the experts' outputs in
float32; (3) no attention mask but the causal one: one unpadded sequence;
(4) grouped selection is refused unless ``num_expert_group`` = ``topk_group``
= 1 (the published values: one group is no grouping). What the published
config does not say is under ``assumed`` in the configuration file.

**The counts**: only matrix multiplications. A token meets every weight of
its mixer, the router and the shared expert and, of the routed experts, the
EXPECTED share held here (``num_experts_per_token x held / router width`` =
0.25 expert a layer at 8 x 8 / 256). KDA's core is the chunked form's
``chunk_operands`` (XLA) and state pass (the kernels) at the program's
chunk; the kernels' own counts (``kda_train_*``) are what each must do with
the forward's states at hand — its operands read once, its outputs written
once, its matmuls — so the second kernel's making a group's states again is
the program's cost and no share can pass 100% by construction.
"""

import collections
import math

import jax
import jax.numpy as jnp

from benchmark.families.afmoe import (EXPERT_LEAVES, GMM_PRODUCTS, _block_of,
                                      _swiglu, mean_keys, route)
from benchmark.families.llama import _f32, _rms_norm, _rotate

Sizes = collections.namedtuple(
    "Sizes", "d layers dense_layers kinds heads nope rope v latent kda_heads "
             "dk conv dense_mlp expert held first router top_k shared vocab")
ROW_BLOCK, SCAN_BLOCK = 1024, 64
L2_EPS = 1e-6
CHUNK = 64      # ``ops/pallas/kda.py``'s chunk: the counts are at it


# ------------------------------------------------------ the program's model
def vocab_size(cfg):
    return cfg["model"]["vocab_size"]


def _sizes(cfg):
    m = cfg["model"]
    lin = m["linear_attn_config"]
    cut = "num_experts" in cfg.get("reduced", ())
    layers = m["num_hidden_layers"]
    kinds = tuple("kda" if l in lin["kda_layers"] else "mla"
                  for l in range(1, layers + 1))
    return Sizes(
        m["hidden_size"], layers, m["first_k_dense_replace"], kinds,
        m["num_attention_heads"], m["qk_nope_head_dim"],
        m["qk_rope_head_dim"], m["v_head_dim"], m["kv_lora_rank"],
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
        m["intermediate_size"], m["moe_intermediate_size"], m["num_experts"],
        cfg.get("share", {}).get("experts_first", 0) if cut else 0,
        cfg["published"]["num_experts"] if cut else m["num_experts"],
        m["num_experts_per_token"], m["num_shared_experts"], m["vocab_size"])


def _refuse_what_is_not_computed(cfg):
    m = cfg["model"]
    lin = m["linear_attn_config"]
    layers = set(range(1, m["num_hidden_layers"] + 1))
    if m["moe_router_activation_func"] != "sigmoid" or m.get("rope_scaling") \
            or m.get("hidden_act", "silu") != "silu" \
            or (m.get("num_expert_group", 1), m.get("topk_group", 1)) != (1, 1) \
            or not m["mla_use_nope"] or m["q_lora_rank"] \
            or m["tie_word_embeddings"] or m.get("moe_layer_freq", 1) != 1 \
            or m["num_key_value_heads"] != m["num_attention_heads"] \
            or m.get("num_nextn_predict_layers", 0) \
            or set(lin["kda_layers"]) | set(lin["full_attn_layers"]) != layers \
            or set(lin["kda_layers"]) & set(lin["full_attn_layers"]):
        raise SystemExit(
            "benchmark: the kimi_linear family computes a sigmoid router "
            "with one selection group, SiLU, latent attention without "
            "positions and without a low-rank q, every layer after the "
            "leading dense ones routed, an untied head, and each layer in "
            "exactly one of kda_layers / full_attn_layers (numbered from "
            "1); this file asks otherwise")


def build_model(cfg, kind):
    """``deepspeed_tpu``'s Llama trunk with KDA and latent-attention layers
    in the published pattern behind the leading dense layer, no rotary
    embedding, a sigmoid router with a selection bias over the published
    width, this chip's experts and the shared expert."""
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

    m, z = cfg["model"], _sizes(cfg)
    _refuse_what_is_not_computed(cfg)
    over = {"remat": cfg["train"]["remat"]} if kind == "train" else {}
    if kind == "serve" and cfg["serve"]["dtype"] == "bf16":
        over["param_dtype"] = jnp.bfloat16
    return LlamaModel(LlamaConfig(
        vocab_size=z.vocab, n_positions=m["model_max_length"], n_embd=z.d,
        n_layer=z.layers, n_head=z.heads, intermediate_size=z.expert,
        dense_intermediate_size=z.dense_mlp, n_dense_layers=z.dense_layers,
        rms_norm_eps=m["rms_norm_eps"], tie_embeddings=False,
        n_experts=z.router, n_experts_per_tok=z.top_k,
        norm_topk_prob=m["moe_renormalize"], n_shared_experts=z.shared,
        router_scoring=m["moe_router_activation_func"],
        routed_scaling_factor=m["routed_scaling_factor"],
        experts_held=(z.first, z.held), use_rope=False,
        gqa_layers=tuple(l for l, k in enumerate(z.kinds) if k == "mla"),
        kda_heads=z.kda_heads, kda_head_dim=z.dk, kda_conv=z.conv,
        q_lora_rank=0, kv_lora_rank=z.latent, qk_nope_head_dim=z.nope,
        qk_rope_head_dim=z.rope, v_head_dim=z.v,
        # what the published file does not say (its ``assumed``)
        router_bias=True,
        router_bias_rate=cfg["assumed_values"]["router_bias_rate"], **over))


# ------------------------------------------------------ the plain reference
def _kda(h, blk, z, eps, beta_unscaled=False, tap_dropped=False):
    """h (T, D) normed -> the KDA mixer's output (T, D): the recurrence
    token by token. ``beta_unscaled`` / ``tap_dropped``: two broken forms
    the witness (``benchmark/kimi_witness.py``) must refuse; never set
    otherwise."""
    T = h.shape[0]
    H, dk = z.kda_heads, z.dk
    get = lambda name: _f32(blk[name])
    conv_w = get("kda_conv_w")
    window = jnp.concatenate(
        [jnp.zeros((z.conv - 1, 3 * H * dk), jnp.float32),
         h @ get("kda_qkv_w")])
    taps = range(1 if tap_dropped else 0, z.conv)
    q, k, v = (t.reshape(T, H, dk) for t in jnp.split(jax.nn.silu(
        sum(conv_w[i] * window[i:i + T] for i in taps)), 3, axis=-1))
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                  + L2_EPS)
    q, k = unit(q) * dk ** -0.5, unit(k)
    g = -jnp.exp(get("kda_a_log"))[:, None] * jax.nn.softplus(
        ((h @ get("kda_f_a_w")) @ get("kda_f_b_w")).reshape(T, H, dk)
        + get("kda_dt_bias").reshape(H, dk))
    beta = (1.0 if beta_unscaled else 2.0) * jax.nn.sigmoid(h @ get("kda_b_w"))

    def step(state, at):
        q, k, v, g, beta = at
        state = state * jnp.exp(g)[..., None]
        delta = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", state, k))
        state = state + k[..., None] * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q)

    sb = _block_of(T, SCAN_BLOCK)
    block = jax.checkpoint(lambda state, rows: jax.lax.scan(step, state, rows))
    _, o = jax.lax.scan(
        block, jnp.zeros((H, dk, dk), jnp.float32),
        tuple(t.reshape(T // sb, sb, *t.shape[1:]) for t in (q, k, v, g, beta)))
    o = o.reshape(T, H, dk)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * get("kda_o_norm_g")
    gate = jax.nn.sigmoid((h @ get("kda_g_a_w")) @ get("kda_g_b_w"))
    return (o.reshape(T, H * dk) * gate) @ get("o_w")


def _mla(h, blk, z, eps, theta, rope_on_mla=False):
    """h (T, D) normed -> the latent-attention mixer's output (T, D),
    un-absorbed, one head and a block of query rows at a time.
    ``rope_on_mla``: the broken form that rotates the ``rope`` columns."""
    T = h.shape[0]
    get = lambda name: _f32(blk[name])
    q = (h @ get("q_w")).reshape(T, z.heads, z.nope + z.rope)
    kv = h @ get("kv_a_w")
    c_kv = _rms_norm(kv[:, :z.latent], blk["kv_a_norm_g"], eps)
    k_pe = kv[:, None, z.latent:]                        # one for all heads
    if rope_on_mla:
        k_pe = _rotate(k_pe, theta)
        q = jnp.concatenate([q[..., :z.nope], _rotate(q[..., z.nope:], theta)],
                            axis=-1)
    k = jnp.concatenate(
        [jnp.einsum("tc,hnc->thn", c_kv, get("kv_b_k_w")),
         jnp.broadcast_to(k_pe, (T, z.heads, z.rope))], axis=-1)
    v = jnp.einsum("tc,hcd->thd", c_kv, get("kv_b_v_w"))
    rb = _block_of(T, ROW_BLOCK)
    scale = 1.0 / math.sqrt(z.nope + z.rope)

    def head(at):
        q, k, v = at                                    # (T, .) of one head

        @jax.checkpoint
        def rows(at):
            j, q_rows = at
            keep = (j * rb + jnp.arange(rb))[:, None] >= jnp.arange(T)[None, :]
            p = jax.nn.softmax(jnp.where(keep, (q_rows @ k.T) * scale,
                                         -jnp.inf), axis=-1)
            return p @ v

        return jax.lax.map(rows, (jnp.arange(T // rb),
                                  q.reshape(T // rb, rb, -1))).reshape(T, -1)

    attn = jax.lax.map(head, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
    return jnp.moveaxis(attn, 0, 1).reshape(T, z.heads * z.v) @ get("o_w")


def _mlp(h, blk, z, m, bias_left_out=False):
    if "router_w" not in blk:
        return _swiglu(h, blk["gate_w"], blk["up_w"], blk["down_w"])
    bias = jnp.zeros_like(blk["router_bias"]) if bias_left_out \
        else blk["router_bias"]
    weights = route(h, blk["router_w"], bias, z, m["moe_renormalize"],
                    m["routed_scaling_factor"])
    out = _swiglu(h, blk["shared_gate_w"], blk["shared_up_w"],
                  blk["shared_down_w"])
    for e in range(z.held):                     # the experts one at a time
        out = out + weights[:, z.first + e, None] * _swiglu(
            h, *(blk[n][e] for n in EXPERT_LEAVES))
    return out


def _layer(x, blk, z, m, kind, **broken):
    eps = float(m["rms_norm_eps"])
    bias_left_out = broken.pop("bias_left_out", False)
    h = _rms_norm(x, blk["attn_norm_g"], eps)
    if kind == "kda":
        broken.pop("rope_on_mla", None)
        x = x + _kda(h, blk, z, eps, **broken)
    else:
        x = x + _mla(h, blk, z, eps, float(m["rope_theta"]),
                     broken.get("rope_on_mla", False))
    return x + _mlp(_rms_norm(x, blk["mlp_norm_g"], eps), blk, z, m,
                    bias_left_out)


def layer_blocks(params, z):
    """Every layer's block — a dict of its leaves — in order, from the
    program's stacks: the dense layers hold their mixer themselves, a routed
    layer's lies in its kind's stack (``attn_blocks`` / ``kda_blocks``)."""
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    out = [at(params["dense_blocks"], l) for l in range(z.dense_layers)]
    own = z.kinds[z.dense_layers:]
    for l, kind in enumerate(own):
        stack = "kda_blocks" if kind == "kda" else "attn_blocks"
        out.append({**at(params["blocks"], l),
                    **at(params[stack], own[:l].count(kind))})
    return out


def reference_logits(params, ids, cfg, **broken):
    """ids (T,) int32 -> float32 logits (T, vocab) of one sequence. A layer
    at a time (its kind is static), each under ``jax.checkpoint``: the
    gradient keeps a layer's input."""
    _refuse_what_is_not_computed(cfg)
    m, z = cfg["model"], _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"])[ids]
        for kind, blk in zip(z.kinds, layer_blocks(params, z)):
            x = jax.checkpoint(
                lambda x, blk, kind=kind: _layer(x, blk, z, m, kind, **broken)
            )(x, blk)
        x = _rms_norm(x, params["norm_g"], float(m["rms_norm_eps"]))
        return x @ _f32(params["lm_head"])


def reference_loss(params, ids, cfg, **broken):
    """Mean cross entropy of predicting ids[1:] from ids[:-1]."""
    lg = reference_logits(params, ids, cfg, **broken)[:-1]
    logp = lg - jax.nn.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))


# ----------------------------------------- operations and bytes from shapes
def kda_params(cfg):
    """Matmul weights of one KDA mixer: the q | k | v projection, the two
    low-rank gates, beta, o. 39,460,864 at the published widths (the
    convolution's taps, the decay's constants and the head norm's gain,
    53,408, are elementwise)."""
    z = _sizes(cfg)
    wide = z.kda_heads * z.dk
    return 4 * z.d * wide + 2 * (z.d * z.dk + z.dk * wide) + z.d * z.kda_heads


def _kda_small(z):
    wide = z.kda_heads * z.dk
    return z.conv * 3 * wide + z.kda_heads + wide + z.dk


def mla_params(cfg):
    """Matmul weights of the latent-attention mixer: q straight from the
    stream, the latent row, the two up-projections, o. 29,114,368 (+ the
    latent's norm gain, 512)."""
    z = _sizes(cfg)
    return z.d * z.heads * (z.nope + z.rope) + z.d * (z.latent + z.rope) \
        + z.latent * z.heads * (z.nope + z.v) + z.heads * z.v * z.d


def _expert_params(z):
    return 3 * z.d * z.expert


def _elementwise_params(cfg):
    """What is held and is no matrix: the norms' gains, the convolutions'
    taps, the decays' constants, the routers' biases."""
    z = _sizes(cfg)
    kda = z.kinds.count("kda")
    return kda * _kda_small(z) + (z.layers - kda) * z.latent \
        + z.layers * 2 * z.d + z.d + (z.layers - z.dense_layers) * z.router


def held_params(cfg):
    """Every parameter this chip holds: 602,434,432 at the published widths
    and the stated share."""
    z = _sizes(cfg)
    kda = z.kinds.count("kda")
    return kda * kda_params(cfg) + (z.layers - kda) * mla_params(cfg) \
        + _elementwise_params(cfg) \
        + z.dense_layers * 3 * z.d * z.dense_mlp \
        + (z.layers - z.dense_layers) * (
            z.d * z.router + (z.held + z.shared) * _expert_params(z)) \
        + 2 * z.vocab * z.d


def experts_met(cfg):
    """Routed experts HELD HERE that a token is expected to meet in one
    layer: ``num_experts_per_token x held / router width`` (0.25)."""
    z = _sizes(cfg)
    return z.top_k * z.held / z.router


def matmul_params(cfg):
    """Parameters that sit in a matrix multiplication for one token, on this
    chip: each layer's mixer, the dense layer's MLP, in a routed layer the
    router, the shared expert and the expected share of routed experts, and
    the output head. 336,080,896."""
    z = _sizes(cfg)
    routed = z.layers - z.dense_layers
    kda = z.kinds.count("kda")
    return kda * kda_params(cfg) + (z.layers - kda) * mla_params(cfg) \
        + z.dense_layers * 3 * z.d * z.dense_mlp \
        + routed * (z.d * z.router
                    + (z.shared + experts_met(cfg)) * _expert_params(z)) \
        + z.d * z.vocab


def mla_train_attn_flops(cfg, seq, backward=True):
    """The latent-attention layers' causal self-attention over one sequence
    as the flash algorithm does it, un-absorbed: forward QK^T at nope + rope
    columns and PV at v's, every head against the ``mean_keys`` its mask
    lets a query meet; backward 2.5 x that (five matmuls for the forward's
    two: it keeps no scores)."""
    z = _sizes(cfg)
    fwd = z.kinds.count("mla") * 2 * z.heads * (z.nope + z.rope + z.v) \
        * seq * mean_keys(seq)
    return fwd * 3.5 if backward else fwd


def mla_train_attn_bytes(cfg, seq, backward=True, itemsize=2):
    """HBM traffic that attention cannot avoid for one sequence: forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes dq,
    dk, dv (q, k at nope + rope columns, v and o at v's)."""
    z = _sizes(cfg)
    qk, vo = 2 * (z.nope + z.rope), 2 * z.v
    each = (qk + vo) + ((qk + vo) + z.v + (qk + z.v) if backward else 0)
    return z.kinds.count("mla") * each * seq * z.heads * itemsize


def _kda_core_flops_fwd(z, seq):
    """One KDA layer's chunked form forward over ``seq`` positions, what is
    NOT a weight's matmul: the level-split scores of q and k against k (2 x
    log2(chunk) levels of chunk x chunk x dk), the in-chunk inverse (~6
    chunk x chunk x chunk matmuls), its application to [K | V], and the
    state pass."""
    c, levels = CHUNK, int(math.log2(CHUNK))
    a_position = 2 * 2 * levels * c * z.dk + 2 * 6 * c * c \
        + 2 * c * 2 * z.dk + _state_pass_flops(z, backward=False)
    return z.kda_heads * seq * a_position


def _state_pass_flops(z, backward):
    """A head's state pass a position: forward the three products with the
    state (6 dk dv) and Aqk's (2 chunk dv); backward their six transposes
    and Aqk's two."""
    return (12 * z.dk * z.dk + 4 * CHUNK * z.dk) if backward \
        else 6 * z.dk * z.dk + 2 * CHUNK * z.dk


def kda_train_flops(cfg, seq, backward=False):
    """What ``kda_chunk_fwd`` (``backward`` false) or ``kda_chunk_bwd`` must
    do for one sequence over all KDA layers: the state pass's matmuls
    (``ops/pallas/kda.py``'s docstring), with the states of the forward at
    hand."""
    z = _sizes(cfg)
    return z.kinds.count("kda") * z.kda_heads * seq \
        * _state_pass_flops(z, backward)


def kda_train_bytes(cfg, seq, backward=False, itemsize=2):
    """Bytes the same kernel cannot avoid: forward reads u, w, qg, kend (dk
    each; u at dv = dk) and aqk (chunk) a position and writes o; backward
    reads those and do and writes the five cotangents."""
    z = _sizes(cfg)
    rows = 4 * z.dk + CHUNK
    each = 2 * rows + z.dk if backward else rows + z.dk
    return z.kinds.count("kda") * z.kda_heads * seq * each * itemsize


def train_flops_per_token(cfg, seq):
    """Forward + backward FLOPs per trained token at sequence length
    ``seq``: 6 x the matmul parameters a token meets here (the EXPECTED held
    pairs), the causal latent attention at 3 x its forward, and KDA's core
    at 3 x its forward. Recomputed operations (remat, the flash backward's
    scores, the second kernel's states) do not count."""
    z = _sizes(cfg)
    return 6 * matmul_params(cfg) \
        + 3 * mla_train_attn_flops(cfg, seq, backward=False) / seq \
        + 3 * z.kinds.count("kda") * _kda_core_flops_fwd(z, seq) / seq


def flash_flops_per_sequence(cfg, seq, backward=True):
    return mla_train_attn_flops(cfg, seq, backward)


def flash_bytes_per_sequence(cfg, seq, backward=True, itemsize=2):
    return mla_train_attn_bytes(cfg, seq, backward, itemsize)


def moe_gmm_flops_per_pair(cfg, remat=True):
    """FLOPs of the routed experts' grouped products for one COUNTED (token,
    expert) pair held here, over a train step (afmoe's count)."""
    z = _sizes(cfg)
    products = sum(n for name, n in GMM_PRODUCTS.items()
                   if remat or name != "recompute")
    return products * 2 * z.d * z.expert


def moe_gmm_bytes_per_step(cfg, remat=True, itemsize=2):
    z = _sizes(cfg)
    products = sum(n for name, n in GMM_PRODUCTS.items()
                   if remat or name != "recompute")
    return (z.layers - z.dense_layers) * z.held * products \
        * z.d * z.expert * itemsize


def weight_bytes(cfg, itemsize=2):
    """Bytes of weights one decode step must stream: the matmul weights a
    token meets and what is elementwise. (No cell serves this
    configuration.)"""
    return (matmul_params(cfg) + _elementwise_params(cfg)) * itemsize


def decode_flops_per_token(cfg):
    return 2 * matmul_params(cfg)


def decode_bytes_per_token(cfg, context, itemsize=2):
    """The weights a token meets once, the latent rows it attends to
    (``kv_lora_rank`` + rope a position a latent-attention layer) and the
    KDA layers' float32 states, read and written."""
    z = _sizes(cfg)
    kda = z.kinds.count("kda")
    return weight_bytes(cfg, itemsize) \
        + (z.layers - kda) * context * (z.latent + z.rope) * itemsize \
        + kda * 2 * z.kda_heads * z.dk * z.dk * 4
