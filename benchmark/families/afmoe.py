"""Trinity-Mini (``model_type: afmoe``; the published ``config.json`` of
``arcee-ai/Trinity-Mini``): the program's model, the plain reference, and the
operations and bytes the algorithm needs — for ONE CHIP'S SHARE of a stated
deployment, on the TRAINING path.

The program's model is ``models/llama.py``'s trunk with what this
architecture's blocks hold. Every function takes the configuration file's
dict; the sizes are under its ``"model"`` key, named as in the published
file (``hidden_size``, ``num_hidden_layers``, ``num_dense_layers``,
``layer_types``, ``sliding_window``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``intermediate_size`` = the DENSE
layers' width, ``moe_intermediate_size`` = one expert's, ``num_experts``,
``num_shared_experts``, ``num_experts_per_tok``, ``route_norm``,
``route_scale``, ``score_func``, ``load_balance_coeff``, ``mup_enabled``,
``rope_theta``, ``rms_norm_eps``, ``vocab_size``). **The share**
(model-configs guide, section 4): ``num_experts`` is listed under
``reduced``, so the value under ``model`` is the number of experts HELD here,
``published.num_experts`` is the router's width and ``share.experts_first``
the router's number of the first held one. The router scores all of them
and picks ``num_experts_per_tok``; the pairs that fall on held experts are
computed, the others add nothing — in the program and in the reference
alike. A sliced vocabulary is a smaller vocabulary.

**The reference** is the forward pass in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision, no kernel, no cache, one sequence;
its gradients are ``jax.grad`` of it. A layer, x (T, D), eps 1e-5:

* embedding ``x = wte[ids] * sqrt(hidden_size)`` (``mup_enabled``);
* ``h = RMSNorm(x; attn_norm_g)``; ``q = h q_w`` (heads x 128), ``k = h
  k_w``, ``v = h v_w`` (KV heads x 128); RMSNorm of q and of k over the 128
  columns of EACH head (gains ``q_norm_g``, ``k_norm_g`` (128,)); rotary
  embedding (rotate-half, theta 10,000, the whole head) on
  ``sliding_attention`` layers ONLY; scores ``q . k / sqrt(128)``, query
  head j on KV head ``j // (heads / KV)``; mask from ``i - j``:
  ``full_attention`` ``i - j >= 0``, ``sliding_attention`` ``0 <= i - j <
  sliding_window``; softmax; ``a = o_w [attn * sigmoid(h attn_gate_w)]``;
* four norms: ``x <- x + RMSNorm(a; post_attn_norm_g)``; ``m =
  MLP(RMSNorm(x; mlp_norm_g))``; ``x <- x + RMSNorm(m; post_mlp_norm_g)``;
* MLP of the first ``num_dense_layers`` layers: SwiGLU of width
  ``intermediate_size``. Of the others: ``s = sigmoid(h router_w)`` over the
  router's whole width; the ``num_experts_per_tok`` largest of ``s + b``
  (``router_bias``: selection ONLY); ``w = s_top / (sum(s_top) + 1e-20) x
  route_scale`` (without b); ``sum_j w_j E_{e_j}(h)`` over the chosen experts
  HELD here, each a SwiGLU of width ``moe_intermediate_size``, walked one at
  a time, plus the shared expert's SwiGLU, always;
* final RMSNorm, untied head; the loss is next-token cross entropy and
  nothing else (no auxiliary loss: ``load_balance_coeff`` is the RATE of the
  bias rule, applied by the engine after the step, not a loss term).

So that 8,192 tokens fit (32 heads x 8192^2 float32 scores at once are 8.6
GB), the scores are walked one KV head's group of query heads at a time and
query rows in blocks of up to ``ROW_BLOCK`` (``lax.map``, each block under
``jax.checkpoint`` so that the gradient keeps a block's inputs and not its
scores). It reads the SAME parameter values the system holds, in the
program's layout, so a difference is a difference of arithmetic.

Departures from the published modelling code (the family's
``modeling_afmoe.py``), each on purpose: (1) q/k/v/o/gate and the experts
are held in the program's layout — (in, out) matrices, the experts stacked
``(L, held, ...)`` — a loader's transposes; (2) the router's logits and the
weighted sum of the experts' outputs in float32; (3) no attention mask but
the causal (window) one: one unpadded sequence; (4) default rotary
frequencies only (``rope_scaling`` null); (5) grouped selection is refused
unless ``n_group`` = ``topk_group`` = 1 (the published values: one group is
no grouping). What the published config does not say is under ``assumed`` in
the configuration file.

**The counts**: only matrix multiplications. A token meets every weight of
attention, the router and the shared expert and, of the routed experts, the
EXPECTED share held here (``num_experts_per_tok x held / router width`` = 1
expert a layer at 8 x 16 / 128). Attention is BANDED: a query of a window
layer meets ``mean_keys`` keys, not half the sequence. The flash kernels'
own counts (``win_flash_*``) are the least the algorithm can do at a length,
and the grouped matmuls' roofline takes COUNTED pairs, so neither share can
pass 100% by construction.
"""

import collections
import math

import jax
import jax.numpy as jnp

from benchmark.families.llama import _f32, _rms_norm, _rotate

Sizes = collections.namedtuple(
    "Sizes", "d layers dense_layers kinds window heads kv dh dense_mlp "
             "expert held first router top_k shared vocab")
EXPERT_LEAVES = ("expert_gate_w", "expert_up_w", "expert_down_w")
KINDS = ("full_attention", "sliding_attention")
ROW_BLOCK = 1024


# ------------------------------------------------------ the program's model
def vocab_size(cfg):
    return cfg["model"]["vocab_size"]


def _sizes(cfg):
    m = cfg["model"]
    cut = "num_experts" in cfg.get("reduced", ())
    return Sizes(
        m["hidden_size"], m["num_hidden_layers"], m["num_dense_layers"],
        tuple(m["layer_types"]), m["sliding_window"],
        m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"],
        m["intermediate_size"], m["moe_intermediate_size"], m["num_experts"],
        cfg.get("share", {}).get("experts_first", 0) if cut else 0,
        cfg["published"]["num_experts"] if cut else m["num_experts"],
        m["num_experts_per_tok"], m["num_shared_experts"], m["vocab_size"])


def _refuse_what_is_not_computed(cfg):
    m = cfg["model"]
    if m["score_func"] != "sigmoid" or m.get("rope_scaling") \
            or m.get("hidden_act", "silu") != "silu" \
            or (m.get("n_group", 1), m.get("topk_group", 1)) != (1, 1) \
            or not m.get("mup_enabled") or m["tie_word_embeddings"] \
            or len(m["layer_types"]) != m["num_hidden_layers"] \
            or not set(m["layer_types"]) <= set(KINDS):
        raise SystemExit(
            "benchmark: the afmoe family computes a sigmoid router with one "
            "selection group, SiLU, default rotary frequencies, the "
            "embedding multiplier (mup_enabled), an untied head and a "
            "layer_types entry a layer; this file asks otherwise")


def build_model(cfg, kind):
    """``deepspeed_tpu``'s Llama trunk with window and full softmax layers
    in the published pattern, per-head q/k norm, the output gate, four
    norms, the embedding multiplier, a sigmoid router with a selection bias
    over the published width, this chip's experts and the shared expert."""
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

    m, z = cfg["model"], _sizes(cfg)
    _refuse_what_is_not_computed(cfg)
    over = {"remat": cfg["train"]["remat"]} if kind == "train" else {}
    if kind == "serve" and cfg["serve"]["dtype"] == "bf16":
        over["param_dtype"] = jnp.bfloat16
    return LlamaModel(LlamaConfig(
        vocab_size=z.vocab, n_positions=m["max_position_embeddings"],
        n_embd=z.d, n_layer=z.layers, n_head=z.heads, n_kv_head=z.kv,
        head_dim=z.dh, intermediate_size=z.expert,
        dense_intermediate_size=z.dense_mlp, n_dense_layers=z.dense_layers,
        rope_theta=m["rope_theta"], rms_norm_eps=m["rms_norm_eps"],
        tie_embeddings=False, n_experts=z.router, n_experts_per_tok=z.top_k,
        norm_topk_prob=m["route_norm"], n_shared_experts=z.shared,
        router_scoring=m["score_func"], routed_scaling_factor=m["route_scale"],
        experts_held=(z.first, z.held), layer_types=z.kinds,
        sliding_window=z.window, router_bias=True,
        router_bias_rate=m["load_balance_coeff"],
        embed_scale=math.sqrt(z.d),
        # what the published file does not say (its ``assumed``)
        sandwich_norm=True, attn_gate=True, qk_norm="head",
        global_rope=False, **over))


# ------------------------------------------------------ the plain reference
def _block_of(n, most):
    """The largest divisor of n that is at most ``most``."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def _attention(h, blk, z, kind, theta, eps, window_off=False,
               rotate_full=False):
    """h (T, D) normed -> the mixer's output before ``o_w``'s post-norm.
    ``window_off`` / ``rotate_full``: the two broken forms the witness
    (``benchmark/afmoe_witness.py``) must refuse; never set otherwise."""
    T = h.shape[0]
    get = lambda name: _f32(blk[name])
    q = _rms_norm((h @ get("q_w")).reshape(T, z.heads, z.dh),
                  blk["q_norm_g"], eps)
    k = _rms_norm((h @ get("k_w")).reshape(T, z.kv, z.dh),
                  blk["k_norm_g"], eps)
    v = (h @ get("v_w")).reshape(T, z.kv, z.dh)
    sliding = kind == "sliding_attention"
    if sliding or rotate_full:
        q, k = _rotate(q, theta), _rotate(k, theta)
    window = z.window if sliding and not window_off else T
    rb, group = _block_of(T, ROW_BLOCK), z.heads // z.kv
    q = q.reshape(T // rb, rb, z.kv, group, z.dh)

    @jax.checkpoint
    def rows(at):
        j, q_rows = at                          # (rb, KV, group, Dh)
        dist = (j * rb + jnp.arange(rb))[:, None] - jnp.arange(T)[None, :]
        keep = (dist >= 0) & (dist < window)    # i - j
        out = []
        for g in range(z.kv):                   # one KV head's query heads
            s = jnp.einsum("qrd,kd->rqk", q_rows[:, g], k[:, g]) \
                / math.sqrt(z.dh)
            p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
            out.append(jnp.einsum("rqk,kd->qrd", p, v[:, g]))
        return jnp.stack(out, axis=1)           # (rb, KV, group, Dh)

    attn = jax.lax.map(rows, (jnp.arange(T // rb), q)).reshape(
        T, z.heads * z.dh)
    return (attn * jax.nn.sigmoid(h @ get("attn_gate_w"))) @ get("o_w")


def _swiglu(h, gate_w, up_w, down_w):
    return (jax.nn.silu(h @ _f32(gate_w)) * (h @ _f32(up_w))) @ _f32(down_w)


def route(h, router_w, bias, z, renormalize, scale):
    """-> (T, router width) float32: a token's weight at each expert it
    chose, zero elsewhere. The choice is by ``s + bias``, the weight from
    ``s`` alone."""
    s = jax.nn.sigmoid(h @ _f32(router_w))
    _, chosen = jax.lax.top_k(s + _f32(bias), z.top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(w * scale)


def _mlp(h, blk, z, m, no_bias=False):
    if "router_w" not in blk:
        return _swiglu(h, blk["gate_w"], blk["up_w"], blk["down_w"])
    bias = jnp.zeros_like(blk["router_bias"]) if no_bias \
        else blk["router_bias"]
    weights = route(h, blk["router_w"], bias, z, m["route_norm"],
                    m["route_scale"])
    out = _swiglu(h, blk["shared_gate_w"], blk["shared_up_w"],
                  blk["shared_down_w"])
    for e in range(z.held):                     # the experts one at a time
        out = out + weights[:, z.first + e, None] * _swiglu(
            h, *(blk[n][e] for n in EXPERT_LEAVES))
    return out


def _layer(x, blk, z, m, kind, **broken):
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    no_bias = broken.pop("no_bias", False)
    a = _attention(_rms_norm(x, blk["attn_norm_g"], eps), blk, z, kind,
                   theta, eps, **broken)
    x = x + _rms_norm(a, blk["post_attn_norm_g"], eps)
    out = _mlp(_rms_norm(x, blk["mlp_norm_g"], eps), blk, z, m, no_bias)
    return x + _rms_norm(out, blk["post_mlp_norm_g"], eps)


def reference_logits(params, ids, cfg, **broken):
    """ids (T,) int32 -> float32 logits (T, vocab) of one sequence. A layer
    at a time (its kind is static), each under ``jax.checkpoint``: the
    gradient keeps a layer's input."""
    _refuse_what_is_not_computed(cfg)
    m, z = cfg["model"], _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"])[ids] * math.sqrt(z.d)
        for l, kind in enumerate(z.kinds):
            stack, at = ("dense_blocks", l) if l < z.dense_layers \
                else ("blocks", l - z.dense_layers)
            blk = jax.tree.map(lambda a: a[at], params[stack])
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
def attention_params(cfg):
    """Matmul weights of one layer's mixer: q, o and the output gate (d x
    heads*Dh each), k and v (d x KV*Dh each). 27,262,976."""
    z = _sizes(cfg)
    return 3 * z.d * z.heads * z.dh + 2 * z.d * z.kv * z.dh


def _expert_params(z):
    return 3 * z.d * z.expert


def held_params(cfg):
    """Every parameter this chip holds: 705,474,304 at the published widths
    and the stated share (the norms: four gains a layer, two head norms, the
    final one; the router with its bias)."""
    z = _sizes(cfg)
    routed = z.layers - z.dense_layers
    return z.layers * (attention_params(cfg) + 4 * z.d + 2 * z.dh) + z.d \
        + z.dense_layers * 3 * z.d * z.dense_mlp \
        + routed * (z.d * z.router + z.router
                    + (z.held + z.shared) * _expert_params(z)) \
        + 2 * z.vocab * z.d


def experts_met(cfg):
    """Routed experts HELD HERE that a token is expected to meet in one
    layer: ``num_experts_per_tok x held / router width`` (1.0)."""
    z = _sizes(cfg)
    return z.top_k * z.held / z.router


def matmul_params(cfg):
    """Parameters that sit in a matrix multiplication for one token, on
    this chip: attention in every layer, the dense layers' MLP, in a routed
    layer the router, the shared expert and the expected share of routed
    experts, and the output head. 276,692,992."""
    z = _sizes(cfg)
    routed = z.layers - z.dense_layers
    return z.layers * attention_params(cfg) \
        + z.dense_layers * 3 * z.d * z.dense_mlp \
        + routed * (z.d * z.router
                    + (z.shared + experts_met(cfg)) * _expert_params(z)) \
        + z.d * z.vocab


def mean_keys(seq, window=None):
    """Keys a query meets, averaged over a sequence's ``seq`` queries: query
    i meets ``min(i + 1, window)`` (4,096.5 at 8,192 without a window,
    1,792.1 under one of 2,048)."""
    w = seq if window is None else min(window, seq)
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def _layer_keys(cfg, seq, kind):
    z = _sizes(cfg)
    return mean_keys(seq, z.window if kind == "sliding_attention" else None)


def attention_flops_fwd(cfg, seq, kinds=KINDS):
    """Banded causal self-attention forward over one sequence, the layers of
    ``kinds``: QK^T and PV, every QUERY head against the keys its mask lets
    it meet."""
    z = _sizes(cfg)
    return sum(2 * 2 * z.heads * z.dh * seq * _layer_keys(cfg, seq, kind)
               for kind in z.kinds if kind in kinds)


def train_flops_per_token(cfg, seq):
    """Forward + backward FLOPs per trained token at sequence length
    ``seq``: 6 x the matmul parameters a token meets here (the EXPECTED held
    pairs), plus the banded attention at 3x its forward. Recomputed
    operations (remat, the flash backward's scores) do not count."""
    return 6 * matmul_params(cfg) + 3 * attention_flops_fwd(cfg, seq) / seq


def flash_flops_per_sequence(cfg, seq, backward=True, kinds=KINDS):
    """The flash algorithm over one sequence: forward 2 matmuls, backward 5
    (it keeps no scores, so recomputing them is part of the algorithm), over
    the band."""
    fwd = attention_flops_fwd(cfg, seq, kinds)
    return fwd * (1 + 2.5) if backward else fwd


def flash_bytes_per_sequence(cfg, seq, backward=True, itemsize=2,
                             kinds=KINDS):
    """HBM traffic attention cannot avoid for one sequence: forward reads q,
    k, v and writes o; backward reads q, k, v, o, do and writes dq, dk, dv;
    q, o, do, dq at the query heads' width, k, v, dk, dv at the KV heads'
    (the program repeats K/V to the query heads before its kernel: those
    bytes are the program's, not the algorithm's)."""
    z = _sizes(cfg)
    each = 2 + (4 if backward else 0)
    return sum(kind in kinds for kind in z.kinds) \
        * each * seq * (z.heads + z.kv) * z.dh * itemsize


def win_flash_flops_per_sequence(cfg, seq, backward=True):
    """``flash_flops_per_sequence`` of the WINDOW layers: what the
    ``flash_*_win`` calls of a step must do."""
    return flash_flops_per_sequence(cfg, seq, backward,
                                    kinds=("sliding_attention",))


def win_flash_bytes_per_sequence(cfg, seq, backward=True, itemsize=2):
    return flash_bytes_per_sequence(cfg, seq, backward, itemsize,
                                    kinds=("sliding_attention",))


# the grouped products of ONE (token, expert) pair in a train step: forward
# gate, up, down; backward each one's two transposes (d rows, d weights);
# remat 'attn' re-runs the forward's three
GMM_PRODUCTS = {"fwd": 3, "bwd": 6, "recompute": 3}


def moe_gmm_flops_per_pair(cfg, remat=True):
    """FLOPs of the routed experts' grouped products for one COUNTED (token,
    expert) pair held here, over a train step: each product is 2 x d x
    expert width."""
    z = _sizes(cfg)
    products = sum(n for name, n in GMM_PRODUCTS.items()
                   if remat or name != "recompute")
    return products * 2 * z.d * z.expert


def moe_gmm_bytes_per_step(cfg, remat=True, itemsize=2):
    """Bytes the grouped products of a train step cannot avoid whatever the
    pairs: each product reads (or, a weight's gradient, writes) one
    expert-sized matrix of every held expert in every routed layer."""
    z = _sizes(cfg)
    products = sum(n for name, n in GMM_PRODUCTS.items()
                   if remat or name != "recompute")
    return (z.layers - z.dense_layers) * z.held * products \
        * z.d * z.expert * itemsize


def weight_bytes(cfg, itemsize=2):
    """Bytes of weights one decode step must stream: the matmul weights a
    token meets and the norms' gains. (No cell serves this configuration.)"""
    z = _sizes(cfg)
    return (matmul_params(cfg) + z.layers * (4 * z.d + 2 * z.dh) + z.d) \
        * itemsize


def kv_bytes_per_position(cfg, itemsize=2):
    """Bytes of K and V one cached position holds across all layers (the
    program's cache keeps the whole context for window layers too)."""
    z = _sizes(cfg)
    return z.layers * 2 * z.kv * z.dh * itemsize


def decode_flops_per_token(cfg):
    return 2 * matmul_params(cfg)


def decode_bytes_per_token(cfg, context, itemsize=2):
    """The weights a token meets once, and the K/V it attends to: the whole
    context in a full layer, the window's in a window layer."""
    z = _sizes(cfg)
    seen = sum(min(context, z.window) if kind == "sliding_attention"
               else context for kind in z.kinds)
    return weight_bytes(cfg, itemsize) \
        + seen * 2 * z.kv * z.dh * itemsize
