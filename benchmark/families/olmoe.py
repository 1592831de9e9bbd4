"""OLMoE (``model_type: olmoe``; Muennighoff et al. 2024, arXiv:2409.02060):
the program's model, the plain reference, and the operations and bytes the
algorithm needs.

The program's model is ``models/llama.py``'s trunk with the two variations
OLMoE makes to the Llama block. Every function takes the configuration
file's dict; the sizes are under its ``"model"`` key, named as in the
published ``config.json`` (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size`` = the
width of ONE expert, ``num_experts``, ``num_experts_per_tok``,
``norm_topk_prob``, ``max_position_embeddings``, ``rope_theta``,
``rms_norm_eps``, ``tie_word_embeddings``, ``router_aux_loss_coef``).

**The reference** is the forward pass and next-token loss in straightforward
``jax.numpy``, float32, ``highest`` matmul precision, no kernel, no cache,
no batching: one sequence. It is the Llama reference beside this file
(``families/llama.py``: embeddings, pre-norm residual blocks, RMSNorm,
rotate-half rotary embeddings, causal attention, final norm, untied head)
with, as Hugging Face's ``OlmoeForCausalLM`` (transformers 4.57) computes
them:

(a) ``q_norm`` and ``k_norm``: an RMSNorm with its own gain over the WHOLE
    q projection (heads x head_dim wide) and the whole k projection, BEFORE
    the split into heads and before the rotary embedding — not per head;
(b) in place of the dense MLP, a router ``gate: Linear(hidden, experts, no
    bias)``, a softmax over ALL experts, the top ``num_experts_per_tok`` of
    the PROBABILITIES, their values as weights — not renormalised where
    ``norm_topk_prob`` is false — and the output ``sum_j w_j * down_e(
    silu(gate_e(x)) * up_e(x))`` over the chosen experts e. The experts are
    walked ONE AT A TIME, each over the whole sequence with a ``where`` that
    keeps the tokens that chose it, so one expert's float32 weights (25 MB
    at the published widths) are live and never all 64 (1.7 GB a layer).

Departures from ``OlmoeForCausalLM``, each on purpose: the router's logits
are computed in float32 from float32 activations (Hugging Face computes the
router matmul in the model's type and only the softmax in float32 — the
same thing in a float32 model, which is what the test compares); an
expert's output is weighted and summed in float32 (Hugging Face casts the
weight to the model's type and ``index_add``s); ``clip_qkv`` and a
``rope_scaling`` are refused, not ignored (the published file has null for
both); no attention mask (one unpadded sequence); ``lax.scan`` over the
layers and ``lax.fori_loop`` over the experts, for compile time. It reads
the SAME parameter values the system holds, in the program's layout —
stacked leaves ``blocks/*`` of shape (L, ...), weights as (in, out), expert
leaves (L, E, in, out) — slicing ONE expert of ONE layer at a time out of
the stacked leaf, so a difference is a difference of arithmetic.

``reference_router_aux`` is the load-balancing loss as Hugging Face's
``load_balancing_loss_func`` computes it (all layers' router probabilities
concatenated); ``reference_loss``, what the model trains on, is
``reference_next_token_loss + router_aux_loss_coef * reference_router_aux``.

**The counts** follow ``families/llama.py``: only matrix multiplications; a
token meets ``num_experts_per_tok`` experts, so the FLOPs and a decode
step's bytes count those and not all of them. The grouped-matmul kernel's
own counts (``moe_*``) are the expert matmuls alone: the least the
algorithm can do, so a roofline share computed from them cannot pass 100%.
"""

import collections
import math

import jax
import jax.numpy as jnp

from benchmark.families import llama as _llama
from benchmark.families.llama import _f32, _rms_norm, _rotate

Sizes = collections.namedtuple(
    "Sizes", "d layers heads kv dh expert experts top_k vocab")
EXPERT_LEAVES = ("expert_gate_w", "expert_up_w", "expert_down_w")


# ------------------------------------------------------ the program's model
def vocab_size(cfg):
    return cfg["model"]["vocab_size"]


def _sizes(cfg):
    m = cfg["model"]
    heads = m["num_attention_heads"]
    return Sizes(m["hidden_size"], m["num_hidden_layers"], heads,
                 m.get("num_key_value_heads", heads),
                 m.get("head_dim") or m["hidden_size"] // heads,
                 m["intermediate_size"], m["num_experts"],
                 m["num_experts_per_tok"], m["vocab_size"])


def _refuse_what_is_not_computed(m):
    if m.get("rope_scaling") or m.get("clip_qkv") is not None:
        raise SystemExit("benchmark: the OLMoE family has the default rotary "
                         "frequencies and no clip_qkv; this file sets one")


def build_model(cfg, kind):
    """``deepspeed_tpu``'s Llama trunk with OLMoE's q/k norm and routed
    experts. A serve system asks for the parameters in the type it serves:
    ``systems.ServeSystem`` draws them in one jitted call, and a float32
    copy of one expert leaf (8.6 GB) beside 13.84 GB of bf16 weights does
    not fit a 16 GB chip."""
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

    m, z = cfg["model"], _sizes(cfg)
    _refuse_what_is_not_computed(m)
    if z.heads * z.dh != z.d:
        raise SystemExit(f"benchmark: models/llama.py takes head_dim = "
                         f"hidden_size / heads; the file has {z.heads} x "
                         f"{z.dh} at hidden_size {z.d}")
    over = {"remat": cfg["train"]["remat"]} if kind == "train" else {}
    if kind == "serve" and cfg["serve"]["dtype"] == "bf16":
        over["param_dtype"] = jnp.bfloat16
    return LlamaModel(LlamaConfig(
        vocab_size=z.vocab, n_positions=m["max_position_embeddings"],
        n_embd=z.d, n_layer=z.layers, n_head=z.heads, n_kv_head=z.kv,
        intermediate_size=z.expert, rope_theta=m["rope_theta"],
        rms_norm_eps=m["rms_norm_eps"], tie_embeddings=m["tie_word_embeddings"],
        qk_norm=True, n_experts=z.experts, n_experts_per_tok=z.top_k,
        norm_topk_prob=m["norm_topk_prob"],
        router_aux_loss_coef=m["router_aux_loss_coef"], **over))


# ------------------------------------------------------ the plain reference
def _attention(x, blk, z, theta, eps):
    T = x.shape[0]
    h = _rms_norm(x, blk["attn_norm_g"], eps)
    q = _rms_norm(h @ _f32(blk["q_w"]), blk["q_norm_g"], eps)   # (a)
    k = _rms_norm(h @ _f32(blk["k_w"]), blk["k_norm_g"], eps)
    q = _rotate(q.reshape(T, z.heads, z.dh), theta)
    k = _rotate(k.reshape(T, z.kv, z.dh), theta)
    v = (h @ _f32(blk["v_w"])).reshape(T, z.kv, z.dh)
    q = q.reshape(T, z.kv, z.heads // z.kv, z.dh)
    scores = jnp.einsum("qgrd,kgd->grqk", q, k) / math.sqrt(z.dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, axis=-1), v)
    return x + attn.reshape(T, z.heads * z.dh) @ _f32(blk["o_w"])


def _route(h, router_w, top_k, renormalize):
    """(b): -> probabilities (T, E), weights (T, k), chosen experts (T, k)."""
    probs = jax.nn.softmax(h @ _f32(router_w), axis=-1)
    weights, chosen = jax.lax.top_k(probs, top_k)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return probs, weights, chosen


def _experts(h, weights, chosen, stacked, layer, n_experts):
    """The k chosen experts' weighted outputs for every token of h (T, D):
    one expert at a time, its three matrices sliced out of the stacked
    (L, E, ...) leaves; a token that did not choose it adds exactly zero."""
    def one(e, acc):
        take = lambda name: _f32(jax.lax.dynamic_slice(
            stacked[name], (layer, e, 0, 0),
            (1, 1) + stacked[name].shape[2:])[0, 0])
        y = (jax.nn.silu(h @ take("expert_gate_w")) * (h @ take("expert_up_w"))
             ) @ take("expert_down_w")
        mine = chosen == e                      # (T, k): at most one True
        w = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1, keepdims=True)
        return acc + jnp.where(jnp.any(mine, axis=-1, keepdims=True),
                               w * y, 0.0)

    return jax.lax.fori_loop(0, n_experts, one, jnp.zeros_like(h))


def reference_forward(params, ids, cfg):
    """ids (T,) int32 -> (float32 logits (T, vocab), the router's
    probabilities (L, T, E), the experts chosen (L, T, k)) of one sequence."""
    m, z = cfg["model"], _sizes(cfg)
    _refuse_what_is_not_computed(m)
    theta, eps = float(m["rope_theta"]), float(m["rms_norm_eps"])
    blocks = params["blocks"]
    stacked = {n: blocks[n] for n in EXPERT_LEAVES}
    sliced = {n: v for n, v in blocks.items() if n not in EXPERT_LEAVES}

    def block(x, xs):
        blk, layer = xs
        x = _attention(x, blk, z, theta, eps)
        h = _rms_norm(x, blk["mlp_norm_g"], eps)
        probs, weights, chosen = _route(h, blk["router_w"], z.top_k,
                                        m["norm_topk_prob"])
        return x + _experts(h, weights, chosen, stacked, layer, z.experts), \
            (probs, chosen)

    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"])[ids]
        x, (probs, chosen) = jax.lax.scan(
            block, x, (sliced, jnp.arange(z.layers)))
        x = _rms_norm(x, params["norm_g"], eps)
        head = _f32(params["wte"]).T if m["tie_word_embeddings"] \
            else _f32(params["lm_head"])
        return x @ head, probs, chosen


def reference_logits(params, ids, cfg):
    return reference_forward(params, ids, cfg)[0]


def _next_token_loss(logits, ids):
    lg = logits[:-1]
    logp = lg - jax.nn.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))


def _router_aux(probs, chosen, z):
    """``num_experts * sum_{j, e} f[j, e] * P[e]`` over the (layer, token)
    pairs of the whole sequence taken together: f[j, e] the share of them
    whose j-th choice is e, P[e] the mean router probability of e."""
    probs = probs.reshape(-1, z.experts)
    chosen = chosen.reshape(-1, z.top_k)
    f = jnp.mean(jax.nn.one_hot(chosen, z.experts, dtype=jnp.float32), axis=0)
    return z.experts * jnp.sum(f * jnp.mean(probs, axis=0)[None, :])


def reference_next_token_loss(params, ids, cfg):
    """Mean cross entropy of predicting ids[1:] from ids[:-1]."""
    return _next_token_loss(reference_logits(params, ids, cfg), ids)


def reference_router_aux(params, ids, cfg):
    _, probs, chosen = reference_forward(params, ids, cfg)
    return _router_aux(probs, chosen, _sizes(cfg))


def reference_loss(params, ids, cfg):
    """What the model trains on: the next-token loss plus
    ``router_aux_loss_coef`` x the load-balancing loss, as
    ``OlmoeForCausalLM`` returns it with ``output_router_logits``."""
    logits, probs, chosen = reference_forward(params, ids, cfg)
    return _next_token_loss(logits, ids) \
        + cfg["model"]["router_aux_loss_coef"] * _router_aux(
            probs, chosen, _sizes(cfg))


# ----------------------------------------- operations and bytes from shapes
def _attention_params(z):
    return 2 * z.d * z.heads * z.dh + 2 * z.d * z.kv * z.dh


def matmul_params(cfg):
    """Parameters that sit in a matrix multiplication on every token: per
    layer q, k, v, o, the router (d x experts) and the ``num_experts_per_tok``
    experts the token is routed to (3 x d x expert width each); plus the
    output head. 1.18 B at the published sizes, of 6.92 B held."""
    z = _sizes(cfg)
    return z.layers * (_attention_params(z) + z.d * z.experts
                       + z.top_k * 3 * z.d * z.expert) + z.d * z.vocab


attention_flops_fwd = _llama.attention_flops_fwd
flash_flops_per_sequence = _llama.flash_flops_per_sequence
flash_bytes_per_sequence = _llama.flash_bytes_per_sequence
kv_bytes_per_position = _llama.kv_bytes_per_position


def train_flops_per_token(cfg, seq):
    """Forward + backward FLOPs per trained token at sequence length
    ``seq``: 6 x the matmul parameters a token meets, plus attention at 3x
    its forward."""
    return 6 * matmul_params(cfg) + 3 * attention_flops_fwd(cfg, seq) / seq


def weight_bytes(cfg, itemsize=2):
    """Bytes of weights one decode step must stream: the matmul weights a
    token meets (the CHOSEN experts only), the four norms of a layer (two
    of width d, q_norm, k_norm), the final norm. The lookup reads one row."""
    z = _sizes(cfg)
    norms = z.layers * (2 * z.d + z.heads * z.dh + z.kv * z.dh) + z.d
    return (matmul_params(cfg) + norms) * itemsize


def decode_flops_per_token(cfg):
    return 2 * matmul_params(cfg)


def decode_bytes_per_token(cfg, context, itemsize=2):
    """HBM bytes one decode step needs: the weights a token meets once, and
    the K/V of the ``context`` positions it attends to."""
    return weight_bytes(cfg, itemsize) \
        + context * kv_bytes_per_position(cfg, itemsize)


# the grouped matmul alone (ops/pallas/grouped_matmul.py: ``moe_gmm*``)
def _expert_params(z):
    return 3 * z.d * z.expert


def moe_flops_per_token(cfg):
    """FLOPs of the expert matmuls for one token, all layers."""
    z = _sizes(cfg)
    return 2 * z.layers * z.top_k * _expert_params(z)


def moe_bytes_decode(cfg, itemsize=2):
    """Weight bytes the expert matmuls of ONE decoded token read, all
    layers: the chosen experts' three matrices (activations are a few kB)."""
    z = _sizes(cfg)
    return z.layers * z.top_k * _expert_params(z) * itemsize


def moe_bytes_prefill(cfg, itemsize=2):
    """Weight bytes the expert matmuls of one prefill read, all layers:
    every expert once (a prompt of a thousand tokens and more meets them
    all); the rows' own traffic is left out, so this is the least."""
    z = _sizes(cfg)
    return z.layers * z.experts * _expert_params(z) * itemsize
