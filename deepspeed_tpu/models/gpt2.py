"""GPT-2 family decoder — the flagship training model.

The reference trains GPT-2/Megatron-GPT via external model code (DeepSpeed
wraps it; cf. tests/model/Megatron_GPT2, BASELINE configs "GPT-2 125M/1.3B").
Here the model is in-tree and TPU-shaped:

* layer-stacked parameters scanned with ``lax.scan`` → O(1) compile time in
  depth, XLA pipelines the layer loop;
* Megatron-style tensor-parallel PartitionSpecs on qkv/proj/mlp (column then
  row) so TP is pure sharding metadata — GSPMD inserts the per-layer psum the
  reference does by hand in LinearAllreduce (module_inject/layers.py:15);
* bf16 compute, fp32 logits/loss; optional remat (activation checkpointing,
  reference activation_checkpointing/checkpointing.py role);
* attention pluggable: XLA einsum path or the Pallas flash kernel
  (deepspeed_tpu.ops.pallas.flash_attention).

Sizes follow the GPT-2/GPT-3 ladder used in DeepSpeed docs and tests.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.telemetry.scopes import scope


@dataclasses.dataclass
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    # MLP activation (HF naming): 'gelu_new' (tanh approx — what GPT-2 itself
    # uses), 'gelu' (exact erf), or 'relu' (OPT)
    activation: str = "gelu_new"
    dtype: Any = jnp.bfloat16
    # activation checkpointing: False/'none', True/'full' (recompute all),
    # or 'dots' (save matmul outputs, recompute elementwise — usually the
    # right trade on TPU where HBM, not FLOPs, is the binding constraint)
    remat: Any = True
    # remat the chunked-CE loss scan (models/common.py chunked_lm_loss):
    # True keeps peak HBM bounded (no saved per-chunk fp32 logits, ~2.4G at
    # B=12/T=1024/V=50k); False buys ~1% step time back when the model fits
    # with slack (the bench sets it for the small-model presets)
    remat_loss_chunks: bool = True
    use_flash_attention: bool = True
    # flash kernel tile edge (block_q == block_k), a multiple of 128; None =
    # kernel default (512). An autotuner axis: smaller tiles fit tighter VMEM
    # at long head_dim, larger amortize the grid
    flash_block: Optional[int] = None
    tie_embeddings: bool = True
    lm_head_bias: bool = False       # GPT-J style bias on the (untied) head
    # BLOOM-style variant switches: ALiBi replaces the learned position table
    # (no wpe param; attention gets per-head linear position biases) and an
    # extra layernorm follows the token embedding
    alibi: bool = False
    embed_layernorm: bool = False
    # GPT-NeoX/Pythia-style variant switches: rotary embeddings on the first
    # rotary_pct of each head (no wpe; rotate-half convention) and the
    # parallel-residual block x + attn(ln1(x)) + mlp(ln2(x))
    rotary_pct: float = 0.0          # 0 = learned positions
    rotary_theta: float = 10000.0
    rotary_interleaved: bool = False  # GPT-J rotate-every-two convention
    parallel_residual: bool = False
    # block-sparse attention (reference ds_config "sparse_attention" block /
    # ops/sparse_attention): {"mode": "fixed"|"variable"|"bigbird"|
    # "bslongformer"|"dense", "block": int, ...} — kwargs of the matching
    # SparsityConfig. Overrides flash/einsum attention when set.
    sparse_attention: Optional[dict] = None
    # sequence parallelism over the 'seq' mesh axis: False | 'ring' | 'ulysses'
    # (parallel/sequence.py — long-context support beyond the reference)
    sequence_parallel: Any = False
    # GPT-Neo variant (reference module_inject/containers/gptneo.py): per-layer
    # 'global' | 'local' attention; local = causal sliding window of
    # window_size. The window rides the layer scan as a traced per-layer
    # scalar (0 = global), so mixed patterns compile to ONE scanned program;
    # windowed layers take the einsum path (the flash kernel has no window).
    attention_layers: Optional[tuple] = None
    window_size: int = 256
    # lax.scan unroll factor for the layer loop (same knob as bert's): >1
    # trades compile time for schedule freedom — fewer while-loop iterations
    # and less saved-activation dynamic-update-slice traffic
    scan_unroll: int = 1

    VALID_REMAT = (False, None, "none", True, "full", "dots", "attn",
                   "attn_mlp")

    def __post_init__(self):
        from deepspeed_tpu.models.common import check_flash_block

        if self.remat not in self.VALID_REMAT:
            raise ValueError(f"remat={self.remat!r} not in {self.VALID_REMAT}")
        check_flash_block(self.flash_block)
        if self.activation not in ("gelu", "gelu_new", "relu", "quick_gelu"):
            raise ValueError(f"activation {self.activation!r} not in "
                             "('gelu', 'gelu_new', 'relu', 'quick_gelu')")
        if not 0.0 <= self.rotary_pct <= 1.0:
            raise ValueError(f"rotary_pct {self.rotary_pct} not in [0, 1]")
        if self.alibi and self.rotary_pct:
            raise ValueError("alibi and rotary_pct are mutually exclusive "
                             "position mechanisms")
        if self.sparse_attention is not None:
            mode = dict(self.sparse_attention).get("mode", "fixed")
            if mode not in ("dense", "fixed", "variable", "bigbird",
                            "bslongformer", "localslidingwindow"):
                raise ValueError(f"sparse_attention mode {mode!r} unknown")
            if self.sequence_parallel:
                raise NotImplementedError(
                    "sparse_attention does not compose with ring/Ulysses "
                    "sequence parallelism")
            if self.alibi:
                raise NotImplementedError(
                    "sparse_attention does not carry ALiBi biases")
        if self.attention_layers is not None:
            object.__setattr__(self, "attention_layers",
                               tuple(self.attention_layers))
            if len(self.attention_layers) != self.n_layer:
                raise ValueError(
                    f"attention_layers has {len(self.attention_layers)} "
                    f"entries for n_layer={self.n_layer}")
            bad = set(self.attention_layers) - {"global", "local"}
            if bad:
                raise ValueError(f"attention_layers entries {bad} not in "
                                 "('global', 'local')")
            if "local" in self.attention_layers:
                if self.window_size <= 0:
                    raise ValueError("local attention needs window_size > 0")
                if self.sparse_attention is not None or self.sequence_parallel:
                    raise NotImplementedError(
                        "GPT-Neo local attention does not compose with "
                        "sparse_attention or sequence parallelism")

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    def num_params(self) -> int:
        d, l, v, t = self.n_embd, self.n_layer, self.vocab_size, self.n_positions
        per_layer = 12 * d * d + 13 * d
        return v * d + t * d + l * per_layer + 2 * d

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Forward+backward model FLOPs per token: 6N + 12·l·d·s — the
        Megatron-paper accounting the reference community uses for its TFLOPS
        numbers (SURVEY §6; docs/_posts/2022-07-26-deepspeed-azure.md:90).
        Remat recompute is intentionally NOT counted (model flops, not
        hardware flops)."""
        s = seq_len or self.n_positions
        return 6 * self.num_params() + 12 * self.n_layer * self.n_embd * s


PRESETS = {
    "gpt2-tiny": GPT2Config(vocab_size=2048, n_positions=256, n_embd=128, n_layer=2, n_head=4),
    "gpt2-125m": GPT2Config(n_embd=768, n_layer=12, n_head=12),
    "gpt2-350m": GPT2Config(n_embd=1024, n_layer=24, n_head=16),
    "gpt2-760m": GPT2Config(n_embd=1536, n_layer=24, n_head=16),
    "gpt2-1.3b": GPT2Config(n_embd=2048, n_layer=24, n_head=16, n_positions=2048),
    "gpt2-xl": GPT2Config(n_embd=1600, n_layer=48, n_head=25, n_positions=1024),
    "gpt2-2.7b": GPT2Config(n_embd=2560, n_layer=32, n_head=32, n_positions=2048),
    "gpt2-6.7b": GPT2Config(n_embd=4096, n_layer=32, n_head=32, n_positions=2048),
}


def _init_linear(key, fan_in, shape, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale)


class GPT2Model:
    """Functional GPT-2: params are a dict with stacked per-layer leaves."""

    def __init__(self, config: GPT2Config):
        self.config = config
        self._sparse = None

    def _sparse_attention(self, q, k, v):
        """Config-driven block-sparse attention (reference SparseSelfAttention
        applied via the ds_config "sparse_attention" block). Off-TPU the
        Pallas kernel cannot lower — the dense token-level expansion of the
        layout stands in (exact, just not sparse-fast)."""
        if self._sparse is None:
            from deepspeed_tpu.ops import sparse_attention as sa

            d = dict(self.config.sparse_attention)
            mode = d.pop("mode", "fixed")
            cls = {"dense": sa.DenseSparsityConfig,
                   "fixed": sa.FixedSparsityConfig,
                   "variable": sa.VariableSparsityConfig,
                   "bigbird": sa.BigBirdSparsityConfig,
                   "bslongformer": sa.BSLongformerSparsityConfig,
                   "localslidingwindow": sa.LocalSlidingWindowSparsityConfig}[mode]
            self._sparse = sa.SparseSelfAttention(
                cls(num_heads=self.config.n_head, **d))
        from deepspeed_tpu.utils import env_flag

        if jax.default_backend() != "tpu" and not env_flag(
                "DS_TPU_SPARSE_INTERPRET"):
            # the dense token-level oracle is orders of magnitude faster than
            # Pallas interpret mode; DS_TPU_SPARSE_INTERPRET=1 forces the real
            # kernel off-TPU (CI exercises it via the interpret monkeypatch)
            from deepspeed_tpu.ops.pallas import SAVED_O
            from deepspeed_tpu.ops.pallas.flash_attention import sparse_mha_reference

            return checkpoint_name(
                sparse_mha_reference(q, k, v,
                                     self._sparse.get_layout(q.shape[1]),
                                     causal=True), SAVED_O)
        return self._sparse(q, k, v, causal=True)

    # ---------------------------------------------------------------- params
    def init_params(self, rng) -> Dict[str, Any]:
        c = self.config
        d, l = c.n_embd, c.n_layer
        keys = jax.random.split(rng, 10)
        proj_scale = 0.02 / math.sqrt(2 * l)  # GPT-2 residual-scaled init
        params = {
            "wte": jax.random.normal(keys[0], (c.vocab_size, d), jnp.float32) * 0.02,
            "blocks": {
                "ln1_g": jnp.ones((l, d), jnp.float32),
                "ln1_b": jnp.zeros((l, d), jnp.float32),
                "qkv_w": _init_linear(keys[2], d, (l, d, 3 * d), 0.02),
                "qkv_b": jnp.zeros((l, 3 * d), jnp.float32),
                "proj_w": _init_linear(keys[3], d, (l, d, d), proj_scale),
                "proj_b": jnp.zeros((l, d), jnp.float32),
                "ln2_g": jnp.ones((l, d), jnp.float32),
                "ln2_b": jnp.zeros((l, d), jnp.float32),
                "fc_w": _init_linear(keys[4], d, (l, d, 4 * d), 0.02),
                "fc_b": jnp.zeros((l, 4 * d), jnp.float32),
                "fc2_w": _init_linear(keys[5], 4 * d, (l, 4 * d, d), proj_scale),
                "fc2_b": jnp.zeros((l, d), jnp.float32),
            },
            "lnf_g": jnp.ones((d,), jnp.float32),
            "lnf_b": jnp.zeros((d,), jnp.float32),
        }
        if not c.alibi and not c.rotary_pct:
            params["wpe"] = jax.random.normal(keys[1], (c.n_positions, d), jnp.float32) * 0.01
        if c.embed_layernorm:
            params["emb_ln_g"] = jnp.ones((d,), jnp.float32)
            params["emb_ln_b"] = jnp.zeros((d,), jnp.float32)
        if not c.tie_embeddings:
            params["lm_head"] = jax.random.normal(keys[6], (d, c.vocab_size), jnp.float32) * 0.02
            if c.lm_head_bias:
                params["lm_head_b"] = jnp.zeros((c.vocab_size,), jnp.float32)
        return params

    def param_partition_specs(self) -> Dict[str, Any]:
        """Megatron TP layout over the 'tensor' mesh axis. Leading layer dim of
        stacked block params is never sharded (it's the scan axis)."""
        c = self.config
        specs = {
            "wte": P("tensor", None),          # vocab-sharded embedding
            "blocks": {
                "ln1_g": P(None, None), "ln1_b": P(None, None),
                "qkv_w": P(None, None, "tensor"),   # column parallel
                "qkv_b": P(None, "tensor"),
                "proj_w": P(None, "tensor", None),  # row parallel
                "proj_b": P(None, None),
                "ln2_g": P(None, None), "ln2_b": P(None, None),
                "fc_w": P(None, None, "tensor"),
                "fc_b": P(None, "tensor"),
                "fc2_w": P(None, "tensor", None),
                "fc2_b": P(None, None),
            },
            "lnf_g": P(None), "lnf_b": P(None),
        }
        if not c.alibi and not c.rotary_pct:
            specs["wpe"] = P(None, None)
        if c.embed_layernorm:
            specs["emb_ln_g"] = P(None)
            specs["emb_ln_b"] = P(None)
        if not c.tie_embeddings:
            specs["lm_head"] = P(None, "tensor")
            if c.lm_head_bias:
                specs["lm_head_b"] = P("tensor")
        return specs

    # --------------------------------------------------------------- compute
    @staticmethod
    def _layer_norm(x, g, b, eps=1e-5):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
        return (y * g + b).astype(x.dtype)

    def _alibi(self):
        if not self.config.alibi:
            return None
        from deepspeed_tpu.models.common import alibi_slopes

        return alibi_slopes(self.config.n_head)

    def _layer_windows(self):
        """(L,) int32 per-layer attention window (0 = global) when the
        GPT-Neo 'local' pattern is configured, else None."""
        c = self.config
        if not c.attention_layers or "local" not in c.attention_layers:
            return None
        return jnp.asarray([c.window_size if a == "local" else 0
                            for a in c.attention_layers], jnp.int32)

    def _attention(self, q, k, v, window=None):
        """q,k,v: (B, T, H, Dh). Causal self-attention (block-sparse when
        configured, else the models/common.py dispatch: sequence-parallel →
        flash → einsum). ``window``: traced per-layer sliding window
        (GPT-Neo local layers; 0/None = global)."""
        from deepspeed_tpu.models.common import causal_attention

        c = self.config
        if c.sparse_attention is not None:
            return self._sparse_attention(q, k, v)
        return causal_attention(q, k, v, use_flash=c.use_flash_attention,
                                sequence_parallel=c.sequence_parallel,
                                alibi=self._alibi(),
                                flash_block=c.flash_block, window=window)

    def _attention_local(self, q, k, v, window=None):
        from deepspeed_tpu.models.common import local_causal_attention

        return local_causal_attention(q, k, v, self.config.use_flash_attention,
                                      alibi=self._alibi(), window=window)

    def _embed(self, params, input_ids):
        """Token (+ learned position, unless ALiBi) embedding, with BLOOM's
        optional post-embedding layernorm."""
        c = self.config
        T = input_ids.shape[1]
        x = params["wte"].astype(c.dtype)[input_ids]
        if not c.alibi and not c.rotary_pct:
            x = x + params["wpe"].astype(c.dtype)[:T]
        if c.embed_layernorm:
            x = self._layer_norm(x, params["emb_ln_g"], params["emb_ln_b"])
        return x

    def _dropout(self, x, rng):
        p = self.config.dropout
        if p == 0.0 or rng is None:
            return x
        keep = jax.random.bernoulli(rng, 1.0 - p, x.shape)
        return jnp.where(keep, x / (1.0 - p), jnp.zeros_like(x))

    def _block(self, x, blk, rng, rope=None, window=None):
        q, k, v = self._block_kv(x, blk, rope)
        # what remat='attn' saves of attention is named where it is made
        # (common.remat_wrap): the rest of the block is recomputed
        with scope("attn/core"):
            attn = self._attention(q, k, v, window=window)
        return self._block_finish(x, blk, attn, rng)

    def _lm_logits(self, params, x):
        """Final hidden → fp32 logits (tied or untied head, optional GPT-J
        style head bias)."""
        c = self.config
        with scope("head"):
            head = (params["wte"].T if c.tie_embeddings else params["lm_head"]).astype(x.dtype)
            logits = (x @ head).astype(jnp.float32)
            if "lm_head_b" in params:
                logits = logits + params["lm_head_b"].astype(jnp.float32)
            return logits

    def apply(self, params, input_ids, rng=None):
        """input_ids (B, T) int32 → logits (B, T, V) fp32."""
        return self._lm_logits(params, self._trunk(params, input_ids, rng))

    def _trunk(self, params, input_ids, rng=None, pld_theta=None):
        c = self.config
        B, T = input_ids.shape
        with scope("embed"):
            x = self._embed(params, input_ids)
            if rng is not None and c.dropout > 0.0:
                rng, emb_key = jax.random.split(rng)
                x = self._dropout(x, emb_key)

        from deepspeed_tpu.models.common import layer_scan, remat_wrap

        block_fn = remat_wrap(self._block, c.remat)

        layer_rngs = jax.random.split(rng, c.n_layer) if (rng is not None and c.dropout > 0.0) else None
        rope = self._rope_tables(jnp.arange(T))
        windows = self._layer_windows()   # None (empty pytree leaf) or (L,)

        # Progressive Layer Drop (reference runtime/progressive_layer_drop.py:8
        # + the DeepSpeedExamples BERT pld_theta forward kwarg): per-block
        # stochastic-depth gate with depth-scaled keep probability. θ is a
        # TRACED scalar — the engine evaluates the θ(t) schedule from
        # state.step inside the jitted step, so no recompile as it anneals.
        use_pld = pld_theta is not None and rng is not None
        if use_pld:
            from deepspeed_tpu.runtime.progressive_layer_drop import layer_keep_probs

            keep_p = layer_keep_probs(pld_theta, c.n_layer)          # (L,)
            pld_rngs = jax.random.split(jax.random.fold_in(rng, 0x9D), c.n_layer)
        else:
            keep_p = pld_rngs = None

        def scan_body(carry, xs):
            blk, lrng, w, kp, prng = xs
            x = block_fn(carry, blk, lrng, rope, w)
            if use_pld:
                # gate the block's residual contribution; 1/p inverted scaling
                # keeps E[x] so inference (no θ) needs no rescale
                gate = jnp.where(jax.random.bernoulli(prng, kp),
                                 1.0 / kp, 0.0).astype(x.dtype)
                x = carry + gate * (x - carry)
            return x, None

        # the blocks' walk: a lax.scan (models/common.py::layer_scan); under
        # ZeRO-3 each block gathers its own weights inside remat_wrap
        # (runtime/zero/partition.py::LayerGathers)
        with scope("layers"):
            x, _ = layer_scan(scan_body, x,
                              (params["blocks"], layer_rngs, windows,
                               keep_p, pld_rngs),
                              unroll=max(1, int(c.scan_unroll)))
        with scope("head"):
            return self._layer_norm(x, params["lnf_g"], params["lnf_b"])

    def hidden_states(self, params, input_ids, rng=None):
        """Transformer trunk only: (B, T) → final hidden (B, T, D)."""
        return self._trunk(params, input_ids, rng)

    def loss(self, params, batch, rng=None, pld_theta=None):
        """batch: dict with input_ids (B,T) [+ optional labels/loss_mask] or a
        bare (B,T) array — next-token cross entropy.

        The vocab projection + CE is computed in sequence chunks so the full
        (B, T, V) fp32 logits tensor is never materialized (the same memory
        trick as the reference's fused softmax-CE kernels, csrc/transformer/
        softmax_kernels.cu — at V≈50k this is multiple GB per microbatch).

        ``pld_theta``: traced Progressive-Layer-Drop keep-probability scalar
        (engine passes it when the ``progressive_layer_drop`` config block is
        enabled); None = all blocks run.
        """
        from deepspeed_tpu.models.common import chunked_lm_loss, parse_lm_batch

        ids, labels, mask = parse_lm_batch(batch)
        c = self.config
        x = self._trunk(params, ids, rng, pld_theta=pld_theta)
        with scope("head"):
            x = x[:, :-1]  # (B, T-1, D)
            head = (params["wte"].T if c.tie_embeddings else params["lm_head"]).astype(x.dtype)
            return chunked_lm_loss(x, head, labels[:, 1:],
                                   mask[:, 1:] if mask is not None else None,
                                   bias=params.get("lm_head_b"),
                                   remat=c.remat_loss_chunks)


    # ------------------------------------------------------------- inference
    def init_cache(self, batch_size: int, max_len: int):
        """KV cache: (L, B, max_len, W) per k/v — the heads folded into
        lane-dense rows (models/common.py ``init_kv_cache``) — plus current
        length. The TPU counterpart of the reference's InferenceContext KV
        workspace (csrc/transformer/inference/includes/inference_context.h:287)."""
        from deepspeed_tpu.models.common import init_kv_cache

        c = self.config
        if c.sparse_attention is not None:
            # prefill/decode attend densely over the cache; serving a
            # sparse-trained model that way would silently mismatch the
            # trained attention distribution
            raise NotImplementedError(
                "KV-cache generation does not apply sparse_attention "
                "layouts; serve with sparse_attention=None only if the "
                "model was also trained dense")
        return init_kv_cache(c.n_layer, batch_size, max_len, c.n_head,
                             c.head_dim, c.dtype)

    def cache_partition_specs(self):
        from deepspeed_tpu.models.common import kv_cache_partition_specs

        return kv_cache_partition_specs(self.config.n_head,
                                        self.config.head_dim)

    def _rope_tables(self, positions):
        """cos/sin for the rotary fraction of each head, or None."""
        c = self.config
        if not c.rotary_pct:
            return None
        from deepspeed_tpu.models.common import _rope_cos_sin

        # round, not int(): converted ratios like 32/96 reconstruct exactly
        rot = round(c.head_dim * c.rotary_pct)
        rot -= rot % 2
        with scope("attn/qkv"):
            return _rope_cos_sin(positions, rot, c.rotary_theta,
                                 interleaved=c.rotary_interleaved)

    def _apply_partial_rope(self, q, k, rope):
        """Partial rotary: rotate the first rotary_pct of each head's dims
        (NeoX rotate-half or GPT-J rotate-every-two), pass the rest
        through."""
        if rope is None:
            return q, k
        from deepspeed_tpu.models.common import apply_rope

        il = self.config.rotary_interleaved
        cos, sin = rope
        rot = cos.shape[-1]
        qr = apply_rope(q[..., :rot], cos, sin, il)
        kr = apply_rope(k[..., :rot], cos, sin, il)
        return (jnp.concatenate([qr, q[..., rot:]], axis=-1),
                jnp.concatenate([kr, k[..., rot:]], axis=-1))

    def _block_kv(self, x, blk, rope=None):
        """One block's q,k,v for the current x (no attention yet)."""
        c = self.config
        B, T, D = x.shape
        with scope("attn/qkv"):
            h = self._layer_norm(x, blk["ln1_g"], blk["ln1_b"])
            qkv = h @ blk["qkv_w"].astype(h.dtype) + blk["qkv_b"].astype(h.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            to_heads = lambda t: t.reshape(B, T, c.n_head, c.head_dim)
            q, k = self._apply_partial_rope(to_heads(q), to_heads(k), rope)
            return q, k, to_heads(v)

    def _mlp(self, h_in, blk):
        with scope("mlp/up"):
            h = h_in @ blk["fc_w"].astype(h_in.dtype) + blk["fc_b"].astype(h_in.dtype)
            act = self.config.activation
            if act == "relu":
                h = jax.nn.relu(h)
            elif act == "quick_gelu":  # CLIP text encoder: x·sigmoid(1.702x)
                h = h * jax.nn.sigmoid(1.702 * h)
            else:
                h = jax.nn.gelu(h, approximate=(act == "gelu_new"))
            # named so remat='attn_mlp' can save the activation and skip the
            # fc/fc2 matmul recompute in backward
            h = checkpoint_name(h, "mlp_act")
        with scope("mlp/down"):
            return h @ blk["fc2_w"].astype(h.dtype) + blk["fc2_b"].astype(h.dtype)

    def _block_finish(self, x, blk, attn, rng=None):
        B, T, D = x.shape
        dk = (lambda i: jax.random.fold_in(rng, i)) if rng is not None else (lambda i: None)
        with scope("attn/out"):
            a = attn.reshape(B, T, D) @ blk["proj_w"].astype(x.dtype) + blk["proj_b"].astype(x.dtype)
            a = self._dropout(a, dk(0))
            if not self.config.parallel_residual:
                x = x + a
        # NeoX (parallel_residual): x + attn(ln1(x)) + mlp(ln2(x)) — both
        # branches read the block input, so the MLP does not wait on the
        # attention residual
        with scope("mlp"):
            h = self._layer_norm(x, blk["ln2_g"], blk["ln2_b"])
            m = self._dropout(self._mlp(h, blk), dk(1))
            return x + a + m if self.config.parallel_residual else x + m

    def prefill(self, params, input_ids, cache):
        """Process the prompt, fill the cache, return last-position logits."""
        from deepspeed_tpu.models.common import kv_cache_rows

        c = self.config
        B, T = input_ids.shape
        max_len = cache["k"].shape[2]
        with scope("embed"):
            x = self._embed(params, input_ids)
        rope = self._rope_tables(jnp.arange(T))

        windows = self._layer_windows()

        def body(carry, xs):
            blk, w = xs
            x = carry
            q, k, v = self._block_kv(x, blk, rope)
            with scope("attn/core"):
                attn = self._attention_local(q, k, v, window=w)
                rows = (kv_cache_rows(k, max_len), kv_cache_rows(v, max_len))
            x = self._block_finish(x, blk, attn)
            return x, rows

        with scope("layers"):
            x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], windows))
        with scope("head"):
            x = self._layer_norm(x, params["lnf_g"], params["lnf_b"])
        logits = self._lm_logits(params, x[:, -1])
        cache = {"k": ks, "v": vs, "pos": jnp.int32(T)}
        return logits, cache

    def _decode_embed(self, params, token, pos):
        """(B,) token + scalar position → embedded (B, 1, D) — the decode
        counterpart of _embed, shared with the MoE decode path."""
        c = self.config
        x = params["wte"].astype(c.dtype)[token][:, None]  # (B, 1, D)
        if not c.alibi and not c.rotary_pct:
            x = x + jax.lax.dynamic_slice_in_dim(
                params["wpe"].astype(c.dtype), pos, 1, 0)[None]
        if c.embed_layernorm:
            x = self._layer_norm(x, params["emb_ln_g"], params["emb_ln_b"])
        return x

    def decode_step(self, params, token, cache):
        """One token for every sequence: (B,) → logits (B, V), cache advanced.
        The jitted equivalent of the reference's per-token softmax_context
        path (csrc/transformer/inference/pt_binding.cpp qkv_gemm_/softmax_context_)."""
        c = self.config
        pos = cache["pos"]
        with scope("embed"):
            x = self._decode_embed(params, token, pos)

        from deepspeed_tpu.models.common import (cached_decode_attention,
                                                 kv_cache_write,
                                                 read_as_stored)

        rope = self._rope_tables(pos[None])

        windows = self._layer_windows()

        # The stacked (L, B, S, W) cache rides the scan CARRY, updated in
        # place with a per-layer DUS, and attention reads layer l of it in
        # place. The previous layout passed it as xs/ys, which makes
        # lax.scan assemble a brand-new stacked output buffer every decode
        # step — a full cache copy per token (measured 13ms/step at B=32 on
        # gpt2-760m v5e; the carry aliases instead of copying).
        def body(carry, xs):
            x, cache_k, cache_v = carry
            blk, w, l = xs
            blk = dict(blk, fc2_w=read_as_stored(blk["fc2_w"]))
            q, k, v = self._block_kv(x, blk, rope)     # (B, 1, H, Dh)
            with scope("attn/core"):
                cache_k = kv_cache_write(cache_k, k, l, pos)
                cache_v = kv_cache_write(cache_v, v, l, pos)
                attn = cached_decode_attention(
                    q[:, 0], cache_k, cache_v, l, pos, c.n_head,
                    alibi=self._alibi(), window=w)[:, None]
            x = self._block_finish(x, blk, attn)
            return (x, cache_k, cache_v), None

        with scope("layers"):
            (x, ks, vs), _ = jax.lax.scan(
                body, (x, cache["k"], cache["v"]),
                (params["blocks"], windows, jnp.arange(c.n_layer)))
        with scope("head"):
            x = self._layer_norm(x, params["lnf_g"], params["lnf_b"])
        logits = self._lm_logits(params, x[:, 0])
        return logits, {"k": ks, "v": vs, "pos": pos + 1}


def synthetic_lm_batch(batch_size: int, seq_len: int, vocab_size: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab_size, size=(batch_size, seq_len), dtype=np.int32)}
