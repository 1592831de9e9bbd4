"""The GPT-2 family (``gpt2-760m``, ``gpt2-xl``): the program's model, the
plain reference, and the operations and bytes the algorithm needs.

Every function takes the configuration file's dict; the sizes are under
its ``"model"`` key, named as in a Hugging Face GPT-2 ``config.json``.

**The reference** is the forward pass and next-token loss in
straightforward ``jax.numpy``, float32, ``highest`` matmul precision, no
kernel, no cache, no batching tricks. It follows Radford et al. 2019:
learned token + position embeddings; pre-LN blocks ``x + attn(ln1(x))``,
``x + mlp(ln2(x))`` with ``gelu_new`` (tanh approximation); causal softmax
attention scaled by 1/sqrt(head_dim); final layer norm; output head tied
to the token embedding. It reads the SAME parameter values the system
holds (whatever their dtype, upcast to float32 one layer at a time) in the
program's parameter layout — stacked per-layer leaves ``blocks/*`` of
shape (L, ...) — so a difference is a difference of arithmetic, not of
weights. The one non-``jnp`` construct is ``lax.scan`` over the layers,
for compile time; its body is the per-layer equations as written.

**The counts** are stricter than ``GPT2Config.flops_per_token`` (ROADMAP
S9): only matrix multiplications count — the layers and the tied output
head. The token and position LOOKUPS are not matmuls and do no FLOPs;
causal attention does half the square. Recomputation (remat) is not
counted: these are the operations the model needs, not the ones the
hardware ran.
"""

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


# ------------------------------------------------------ the program's model
def vocab_size(cfg):
    return cfg["model"]["vocab_size"]


def build_model(cfg, kind):
    """``deepspeed_tpu``'s GPT-2 at this configuration's sizes; a train
    system takes the configuration's remat setting."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    m = cfg["model"]
    over = {"remat": cfg["train"]["remat"]} if kind == "train" else {}
    return GPT2Model(GPT2Config(
        vocab_size=m["vocab_size"], n_positions=m["n_positions"],
        n_embd=m["n_embd"], n_layer=m["n_layer"], n_head=m["n_head"],
        activation=m["activation_function"],
        tie_embeddings=m["tie_word_embeddings"], **over))


# ------------------------------------------------------ the plain reference
def _f32(x):
    return x.astype(jnp.float32)


def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * _f32(g) + _f32(b)


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, blk, n_head):
    T, d = x.shape
    h = _layer_norm(x, blk["ln1_g"], blk["ln1_b"])
    qkv = h @ _f32(blk["qkv_w"]) + _f32(blk["qkv_b"])
    q, k, v = (t.reshape(T, n_head, d // n_head) for t in
               jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d // n_head)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(T, d) @ _f32(blk["proj_w"]) + _f32(blk["proj_b"])
    h = _layer_norm(x, blk["ln2_g"], blk["ln2_b"])
    h = _gelu_new(h @ _f32(blk["fc_w"]) + _f32(blk["fc_b"]))
    return x + h @ _f32(blk["fc2_w"]) + _f32(blk["fc2_b"])


def reference_logits(params, ids, cfg):
    """ids (T,) int32 -> float32 logits (T, vocab) of one sequence."""
    n_head = cfg["model"]["n_head"]
    with jax.default_matmul_precision("highest"):
        T = ids.shape[0]
        x = _f32(params["wte"])[ids] + _f32(params["wpe"])[:T]
        x, _ = jax.lax.scan(lambda x, blk: (_block(x, blk, n_head), None),
                            x, params["blocks"])
        x = _layer_norm(x, params["lnf_g"], params["lnf_b"])
        return x @ _f32(params["wte"]).T


def reference_loss(params, ids, cfg):
    """Mean cross entropy of predicting ids[1:] from ids[:-1]."""
    lg = reference_logits(params, ids, cfg)[:-1]
    logp = lg - jax.nn.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))


# ----------------------------------------- operations and bytes from shapes
def matmul_params(cfg):
    """Parameters that sit in a matrix multiplication on every token: per
    layer qkv (d x 3d), proj (d x d), fc (d x 4d), fc2 (4d x d) = 12 d^2;
    plus the output head (d x vocab; tied to wte, but the projection is a
    real matmul). No biases, layer norms, wpe, or the wte lookup."""
    m = cfg["model"]
    d, layers, vocab = m["n_embd"], m["n_layer"], m["vocab_size"]
    return layers * 12 * d * d + d * vocab


def attention_flops_fwd(cfg, seq):
    """Causal self-attention forward over one sequence of ``seq`` tokens,
    all layers: QK^T and PV are 2*T*T*d FLOPs each per layer, and the
    causal mask needs half of each square."""
    d, layers = cfg["model"]["n_embd"], cfg["model"]["n_layer"]
    return layers * 2 * (2 * seq * seq * d) / 2


def train_flops_per_token(cfg, seq):
    """Forward + backward FLOPs per trained token at sequence length
    ``seq``: 6 x matmul parameters, plus attention at 3x its forward."""
    return 6 * matmul_params(cfg) + 3 * attention_flops_fwd(cfg, seq) / seq


def flash_flops_per_sequence(cfg, seq, backward=True):
    """What the flash kernels must do for one sequence through all layers:
    forward 2 matmuls (QK^T, PV); backward 5 (recompute S, dV, dP, dQ, dK:
    the flash algorithm keeps no S, so its recomputation is part of the
    algorithm, unlike remat). Causal: half the square each."""
    fwd = attention_flops_fwd(cfg, seq)
    return fwd * (1 + 2.5) if backward else fwd


def flash_bytes_per_sequence(cfg, seq, backward=True, itemsize=2):
    """HBM traffic the flash kernels cannot avoid for one sequence, all
    layers: forward reads q,k,v and writes o (4 tensors of T x d);
    backward reads q,k,v,o,do and writes dq,dk,dv (8 more). The
    per-row log-sum-exp vectors are left out (1/head_dim of a tensor)."""
    m = cfg["model"]
    tensors = 4 + (8 if backward else 0)
    return m["n_layer"] * tensors * seq * m["n_embd"] * itemsize


def weight_bytes(cfg, itemsize=2):
    """Bytes of weights one decode step must stream: every matmul weight,
    the biases and layer norms, and the output head. The wte/wpe LOOKUPS
    read one row each and are left out (wte is counted once, as the
    head)."""
    d, layers = cfg["model"]["n_embd"], cfg["model"]["n_layer"]
    small = layers * (3 * d + d + 4 * d + d + 4 * d) + 2 * d   # biases + LN
    return (matmul_params(cfg) + small) * itemsize


def kv_bytes_per_position(cfg, itemsize=2):
    """Bytes of K and V one cached position holds across all layers."""
    return cfg["model"]["n_layer"] * 2 * cfg["model"]["n_embd"] * itemsize


def decode_flops_per_token(cfg):
    """One decode step multiplies one token through every matmul weight."""
    return 2 * matmul_params(cfg)


def decode_bytes_per_token(cfg, context, itemsize=2):
    """HBM bytes one decode step needs: the weights once, and the K/V of
    the ``context`` positions the token actually attends to (not the slots
    a preallocated cache happens to have)."""
    return weight_bytes(cfg, itemsize) \
        + context * kv_bytes_per_position(cfg, itemsize)
