"""BERT family encoder — masked-LM pretraining (the reference's headline
benchmark: BERT-large at 64 TFLOPS/V100, docs/_posts/2020-05-28-fastest-bert-
training.md; its kernel-parity tests are all BERT-based, tests/unit/ops/
accelerators vs the vendored HF BERT).

TPU-shaped like the decoder families (layer-stacked ``lax.scan`` trunk,
Megatron TP PartitionSpecs, pluggable flash attention — bidirectional here,
``causal=False``), with BERT's own pieces:

* post-LN blocks: x = LN(x + attn(x)); x = LN(x + mlp(x));
* word + learned-position + token-type embeddings with an embedding LN;
* MLM head: transform(dense+gelu+LN) then decode against the tied word
  embedding plus a free output bias; loss masks to labels != -100 (HF
  convention).

Implements init_params / loss / apply / param_partition_specs, so
``initialize()``, ZeRO, TP, and checkpointing apply unchanged (no KV-cache
protocol — encoders don't autoregress). Weights convert from HF
``BertForMaskedLM`` via module_inject/hf.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

IGNORE_INDEX = -100

_mlm_overflow_warned = False


def _warn_mlm_overflow_once(overflow, maxp):
    global _mlm_overflow_warned
    if bool(overflow) and not _mlm_overflow_warned:
        _mlm_overflow_warned = True
        from deepspeed_tpu.utils.logging import logger
        logger.warning(
            f"MLM batch has rows with more than max_predictions_per_seq="
            f"{maxp} labels; the gathered head drops the excess from the "
            "loss. Cap masking in the data pipeline (the original BERT "
            "builder's max_predictions_per_seq truncation) or raise the knob.")


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    n_positions: int = 512
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    intermediate_size: Optional[int] = None   # None → 4·d
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    activation: str = "gelu"                  # BERT uses exact-erf gelu
    dtype: Any = jnp.bfloat16
    # False/'none' | True/'full' | 'dots' | 'attn' — same policy ladder as the
    # decoders (models/common.py remat_wrap): 'attn' keeps attention's output
    # and the flash kernel's log-sum-exp, so the backward never re-runs it
    remat: Any = False
    # remat the chunked-CE loss scan (see gpt2.GPT2Config.remat_loss_chunks)
    remat_loss_chunks: bool = True
    use_flash_attention: bool = True
    # flash kernel tile edge (block_q == block_k), a multiple of 128; None =
    # kernel default. The bidirectional grid has no triangular skip, so the
    # full-sequence tile (= seq_len) removes all tiling overhead at BERT's
    # short seqs; a length that does not tile in whole 128s (576) takes the
    # einsum path
    flash_block: Optional[int] = None
    # lax.scan unroll factor for the layer loop: >1 trades compile time for
    # schedule freedom (fewer while-loop iterations and less saved-activation
    # dynamic-update-slice traffic, which profiles as ~15% of a remat='dots'
    # step on v5e)
    scan_unroll: int = 1
    # MLM head over gathered masked positions only (the original BERT's
    # gather_indexes: at 15% masking the vocab projection+CE runs on ~1/6 of
    # the tokens). Static shape: positions are padded/truncated to
    # max_predictions_per_seq; None = project every position. Loss value is
    # identical (unmasked positions carry zero weight either way) ONLY if the
    # data pipeline guarantees no row carries more labels than the cap — the
    # original BERT data builder truncates masking at exactly this knob; rows
    # over the cap silently train on a truncated loss. Set DS_DEBUG_MLM=1 to
    # assert the invariant at runtime (one warning per process, adds a small
    # host sync per step).
    max_predictions_per_seq: Optional[int] = None

    VALID_REMAT = (False, None, "none", True, "full", "dots", "attn")

    def __post_init__(self):
        from deepspeed_tpu.models.common import check_flash_block

        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.n_embd
        if self.activation not in ("gelu", "gelu_new", "relu"):
            raise ValueError(f"activation {self.activation!r} unknown")
        if self.remat not in self.VALID_REMAT:
            raise ValueError(f"remat={self.remat!r} not in {self.VALID_REMAT}")
        check_flash_block(self.flash_block)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    def num_params(self) -> int:
        c = self
        d, i = c.n_embd, c.intermediate_size
        emb = (c.vocab_size + c.n_positions + c.type_vocab_size) * d + 2 * d
        per_layer = 4 * d * d + 4 * d + 2 * d * i + d + i + 4 * d
        head = d * d + d + 2 * d + c.vocab_size     # transform + LN + decoder bias
        return emb + c.n_layer * per_layer + head

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """6N + 12·l·d·s, the same Megatron accounting as the decoders (the
        reference's BERT TFLOPS numbers use the equivalent formula). When the
        MLM head runs only on gathered masked positions, the head matmuls the
        model genuinely skips are subtracted — MFU stays honest."""
        s = seq_len or self.n_positions
        f = 6 * self.num_params() + 12 * self.n_layer * self.n_embd * s
        maxp = self.max_predictions_per_seq
        if maxp is not None and maxp < s:
            # per-token head work: vocab decode (d·V, tied wte) + transform (d²)
            head = self.n_embd * self.vocab_size + self.n_embd * self.n_embd
            f -= 6.0 * head * (1.0 - maxp / s)
        return f


PRESETS = {
    "bert-tiny": BertConfig(vocab_size=1024, n_positions=128, n_embd=64,
                            n_layer=2, n_head=4, intermediate_size=128),
    "bert-base": BertConfig(),
    "bert-large": BertConfig(n_embd=1024, n_layer=24, n_head=16),
}


class BertModel:
    """Functional BERT MLM: params are a dict with stacked per-layer leaves."""

    def __init__(self, config: BertConfig):
        self.config = config

    # ---------------------------------------------------------------- params
    def init_params(self, rng) -> Dict[str, Any]:
        c = self.config
        d, i, l = c.n_embd, c.intermediate_size, c.n_layer
        keys = jax.random.split(rng, 10)
        s = 0.02
        norm = lambda key, shape: jax.random.normal(key, shape, jnp.float32) * s
        return {
            "wte": norm(keys[0], (c.vocab_size, d)),
            "wpe": norm(keys[1], (c.n_positions, d)),
            "wtype": norm(keys[2], (c.type_vocab_size, d)),
            "emb_ln_g": jnp.ones((d,), jnp.float32),
            "emb_ln_b": jnp.zeros((d,), jnp.float32),
            "blocks": {
                "qkv_w": norm(keys[3], (l, d, 3 * d)),
                "qkv_b": jnp.zeros((l, 3 * d), jnp.float32),
                "proj_w": norm(keys[4], (l, d, d)),
                "proj_b": jnp.zeros((l, d), jnp.float32),
                "attn_ln_g": jnp.ones((l, d), jnp.float32),
                "attn_ln_b": jnp.zeros((l, d), jnp.float32),
                "fc_w": norm(keys[5], (l, d, i)),
                "fc_b": jnp.zeros((l, i), jnp.float32),
                "fc2_w": norm(keys[6], (l, i, d)),
                "fc2_b": jnp.zeros((l, d), jnp.float32),
                "mlp_ln_g": jnp.ones((l, d), jnp.float32),
                "mlp_ln_b": jnp.zeros((l, d), jnp.float32),
            },
            # MLM head (HF cls.predictions): transform dense+LN, decoder bias
            # (decoder weight tied to wte)
            "mlm_w": norm(keys[7], (d, d)),
            "mlm_b": jnp.zeros((d,), jnp.float32),
            "mlm_ln_g": jnp.ones((d,), jnp.float32),
            "mlm_ln_b": jnp.zeros((d,), jnp.float32),
            "decoder_b": jnp.zeros((c.vocab_size,), jnp.float32),
        }

    def param_partition_specs(self) -> Dict[str, Any]:
        return {
            "wte": P("tensor", None),
            "wpe": P(None, None),
            "wtype": P(None, None),
            "emb_ln_g": P(None), "emb_ln_b": P(None),
            "blocks": {
                "qkv_w": P(None, None, "tensor"),
                "qkv_b": P(None, "tensor"),
                "proj_w": P(None, "tensor", None),
                "proj_b": P(None, None),
                "attn_ln_g": P(None, None), "attn_ln_b": P(None, None),
                "fc_w": P(None, None, "tensor"),
                "fc_b": P(None, "tensor"),
                "fc2_w": P(None, "tensor", None),
                "fc2_b": P(None, None),
                "mlp_ln_g": P(None, None), "mlp_ln_b": P(None, None),
            },
            "mlm_w": P(None, None), "mlm_b": P(None),
            "mlm_ln_g": P(None), "mlm_ln_b": P(None),
            "decoder_b": P("tensor"),
        }

    # --------------------------------------------------------------- compute
    def _layer_norm(self, x, g, b):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + self.config.layer_norm_eps)
        return (y * g + b).astype(x.dtype)

    def _act(self, h):
        a = self.config.activation
        if a == "relu":
            return jax.nn.relu(h)
        return jax.nn.gelu(h, approximate=(a == "gelu_new"))

    def _attention(self, q, k, v, attention_mask):
        """Bidirectional attention via the shared dispatch; ``attention_mask``
        (B, T) True=attend routes to the masked einsum path (the flash
        kernel is mask-free)."""
        from deepspeed_tpu.models.common import local_causal_attention

        return local_causal_attention(q, k, v,
                                      use_flash=self.config.use_flash_attention,
                                      causal=False,
                                      key_padding_mask=attention_mask,
                                      flash_block=self.config.flash_block)

    def _block(self, x, blk, attention_mask):
        c = self.config
        B, T, D = x.shape
        qkv = x @ blk["qkv_w"].astype(x.dtype) + blk["qkv_b"].astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        to_heads = lambda t: t.reshape(B, T, c.n_head, c.head_dim)
        attn = self._attention(to_heads(q), to_heads(k), to_heads(v),
                               attention_mask)
        attn = attn.reshape(B, T, D)
        attn = attn @ blk["proj_w"].astype(x.dtype) + blk["proj_b"].astype(x.dtype)
        x = self._layer_norm(x + attn, blk["attn_ln_g"], blk["attn_ln_b"])
        h = x @ blk["fc_w"].astype(x.dtype) + blk["fc_b"].astype(x.dtype)
        h = self._act(h) @ blk["fc2_w"].astype(x.dtype) + blk["fc2_b"].astype(x.dtype)
        return self._layer_norm(x + h, blk["mlp_ln_g"], blk["mlp_ln_b"])

    def _trunk(self, params, input_ids, token_type_ids=None, attention_mask=None,
               rng=None, pld_theta=None):
        c = self.config
        B, T = input_ids.shape
        x = params["wte"].astype(c.dtype)[input_ids] \
            + params["wpe"].astype(c.dtype)[:T][None] \
            + params["wtype"].astype(c.dtype)[
                jnp.zeros_like(input_ids) if token_type_ids is None else token_type_ids]
        x = self._layer_norm(x, params["emb_ln_g"], params["emb_ln_b"])

        from deepspeed_tpu.models.common import layer_scan, remat_wrap

        block_fn = remat_wrap(self._block, c.remat)

        # Progressive Layer Drop gate (same design as models/gpt2.py _trunk:
        # depth-scaled keep probs, inverted 1/p scaling, θ traced) — PLD's
        # home model family (arXiv:2010.13369 trains BERT)
        use_pld = pld_theta is not None and rng is not None
        if use_pld:
            from deepspeed_tpu.runtime.progressive_layer_drop import layer_keep_probs

            keep_p = layer_keep_probs(pld_theta, c.n_layer)
            pld_rngs = jax.random.split(jax.random.fold_in(rng, 0x9D), c.n_layer)
        else:
            keep_p = pld_rngs = None

        def scan_body(carry, xs):
            blk, kp, prng = xs
            x = block_fn(carry, blk, attention_mask)
            if use_pld:
                gate = jnp.where(jax.random.bernoulli(prng, kp),
                                 1.0 / kp, 0.0).astype(x.dtype)
                x = carry + gate * (x - carry)
            return x, None

        # the blocks' walk: a lax.scan (models/common.py::layer_scan); under
        # ZeRO-3 each block gathers its own weights inside remat_wrap
        x, _ = layer_scan(scan_body, x, (params["blocks"], keep_p, pld_rngs),
                          unroll=c.scan_unroll)
        return x

    def hidden_states(self, params, input_ids, token_type_ids=None,
                      attention_mask=None, rng=None):
        return self._trunk(params, input_ids, token_type_ids, attention_mask)

    def _mlm_transform(self, params, x):
        """HF cls.predictions.transform: dense + activation + LayerNorm."""
        h = x @ params["mlm_w"].astype(x.dtype) + params["mlm_b"].astype(x.dtype)
        return self._layer_norm(self._act(h), params["mlm_ln_g"], params["mlm_ln_b"])

    def _mlm_logits(self, params, x):
        h = self._mlm_transform(params, x)
        logits = (h @ params["wte"].T.astype(h.dtype)).astype(jnp.float32)
        return logits + params["decoder_b"].astype(jnp.float32)

    def apply(self, params, input_ids, token_type_ids=None, attention_mask=None,
              rng=None):
        """(B, T) → MLM logits (B, T, V) fp32."""
        return self._mlm_logits(
            params, self._trunk(params, input_ids, token_type_ids, attention_mask))

    def loss(self, params, batch, rng=None, pld_theta=None):
        """Masked-LM cross entropy. ``batch``: dict with input_ids and labels
        ((B, T), -100 = not predicted — the HF convention) [+ optional
        token_type_ids / attention_mask]. The vocab projection runs through
        the shared chunked CE (models/common.py) so the (B, T, V) fp32
        logits tensor is never materialized. ``pld_theta``: traced
        Progressive-Layer-Drop keep probability (None = all blocks run)."""
        from deepspeed_tpu.models.common import chunked_lm_loss

        ids = batch["input_ids"]
        labels = batch.get("labels", ids)
        x = self._trunk(params, ids, batch.get("token_type_ids"),
                        batch.get("attention_mask"), rng=rng,
                        pld_theta=pld_theta)
        mask = (labels != IGNORE_INDEX)
        maxp = self.config.max_predictions_per_seq
        if maxp is not None and maxp < ids.shape[1]:
            from deepspeed_tpu.utils import env_flag
            if env_flag("DS_DEBUG_MLM"):
                # data-side invariant check: the gathered head silently drops
                # labels past the cap, so a pipeline that masks more than
                # max_predictions_per_seq per row trains on a different loss
                overflow = jnp.max(jnp.sum(mask, axis=1)) > maxp
                jax.debug.callback(_warn_mlm_overflow_once, overflow, maxp)
            # gather_indexes (original BERT run_pretraining): transform +
            # vocab projection only at the (padded-static) masked positions.
            # top_k on the mask is stable, so real positions come first; rows
            # with fewer than maxp labels pad with zero-weight positions.
            w, pos = jax.lax.top_k(mask.astype(jnp.int32), maxp)   # (B, maxp)
            x = jnp.take_along_axis(x, pos[..., None], axis=1)
            labels = jnp.take_along_axis(jnp.where(mask, labels, 0), pos, axis=1)
            mask = w.astype(jnp.bool_)
        h = self._mlm_transform(params, x)
        safe = jnp.where(mask, labels, 0)
        return chunked_lm_loss(h, params["wte"].T.astype(h.dtype), safe,
                               loss_mask=mask, bias=params["decoder_b"],
                               remat=self.config.remat_loss_chunks)


def synthetic_mlm_batch(batch_size: int, seq_len: int, vocab_size: int,
                        mask_frac: float = 0.15, seed: int = 0,
                        max_predictions: Optional[int] = None):
    """Random MLM batch: 15% of positions predicted (HF -100 convention),
    masked inputs replaced by token 0 (the [MASK] stand-in).

    ``max_predictions`` caps the masked count per row (the original BERT data
    builder's max_predictions_per_seq truncation) so the gathered MLM head
    sees every label — without it, Binomial(seq, 0.15) rows routinely exceed
    ceil(0.15·seq) and the gather path would silently drop the excess."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(4, vocab_size, size=(batch_size, seq_len), dtype=np.int32)
    predict = rng.random((batch_size, seq_len)) < mask_frac
    if max_predictions is not None:
        # unmask the excess per row (keep the first max_predictions)
        excess = np.cumsum(predict, axis=1) > max_predictions
        predict &= ~excess
    labels = np.where(predict, ids, IGNORE_INDEX).astype(np.int32)
    inputs = np.where(predict, 0, ids).astype(np.int32)
    return {"input_ids": inputs, "labels": labels}
