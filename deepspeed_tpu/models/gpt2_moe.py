"""Switch-Transformer-style MoE GPT-2.

The BASELINE milestone config "Switch-Transformer 8-expert MoE (a2a over ICI)".
Every other block's dense MLP is replaced by a top-1-gated expert bank
(reference role: deepspeed/moe applied to Megatron GPT, cf.
docs/_posts/2021-12-09-deepspeed-moe-nlg.md). Expert weights shard over the
'expert' mesh axis; the rest of the model is the plain GPT-2.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.common import remat_wrap
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.telemetry.scopes import scope


class MoEGPT2(GPT2Model):
    """GPT-2 with MoE MLPs on odd blocks (0-indexed: 1, 3, ...)."""

    def __init__(self, config: GPT2Config, num_experts: int = 8, ep_size: int = 1,
                 k: int = 1, capacity_factor: float = 1.25,
                 eval_capacity_factor: float = 1.0, min_capacity: int = 4,
                 drop_tokens: bool = True, aux_loss_coef: float = 0.01):
        super().__init__(config)
        if config.parallel_residual:
            # the MoE half-block is attn-then-MoE sequential; the inherited
            # dense block would go parallel — a half-applied architecture
            raise NotImplementedError(
                "MoEGPT2 does not implement parallel_residual")
        if config.attention_layers and "local" in config.attention_layers:
            # the MoE trunk/prefill/decode paths do not thread the per-layer
            # window; accepting the config would silently attend globally
            raise NotImplementedError(
                "MoEGPT2 does not implement GPT-Neo local attention layers")
        # drop_tokens=False matters for serving parity: capacity dropping is
        # computed over the flattened token population, so an incremental
        # decode (different population per call) can drop differently than
        # the teacher-forced full forward
        self.moe = MoE(hidden_size=config.n_embd, num_experts=num_experts,
                       ep_size=ep_size, k=k, capacity_factor=capacity_factor,
                       eval_capacity_factor=eval_capacity_factor,
                       min_capacity=min_capacity, drop_tokens=drop_tokens)
        self.aux_loss_coef = aux_loss_coef
        self.moe_every = 2

    def init_params(self, rng) -> Dict[str, Any]:
        k1, k2 = jax.random.split(rng)
        params = super().init_params(k1)
        n_moe = self.config.n_layer // self.moe_every
        keys = jax.random.split(k2, n_moe)
        moe_params = [self.moe.init_params(k) for k in keys]
        # stack over the moe-layer dim (scanned separately from dense blocks)
        params["moe"] = jax.tree.map(lambda *xs: jnp.stack(xs), *moe_params)
        return params

    def param_partition_specs(self) -> Dict[str, Any]:
        specs = super().param_partition_specs()
        moe_spec = self.moe.param_partition_specs()
        # add the stacked moe-layer leading dim (never sharded)
        specs["moe"] = jax.tree.map(
            lambda s: P(None, *tuple(s)), moe_spec, is_leaf=lambda x: isinstance(x, P))
        return specs

    def _paired_blocks(self, params):
        n_pairs = self.config.n_layer // self.moe_every
        return n_pairs, jax.tree.map(
            lambda t: t.reshape((n_pairs, self.moe_every) + t.shape[1:]),
            params["blocks"])

    def _moe_trunk(self, params, ids, rng=None, train=False):
        """(B, T) → (final hidden (B, T, D), mean aux loss). Interleaves
        dense blocks and MoE MLP blocks without python-loop unrolling of the
        dense part: scans pairs of (dense block, moe layer)."""
        c = self.config
        B, T = ids.shape
        with scope("embed"):
            x = self._embed(params, ids)
        rope = self._rope_tables(jnp.arange(T))
        n_pairs, paired = self._paired_blocks(params)

        def pair_fn(x, pair_blocks, moe_p):
            # dense block 0 of the pair
            b0 = jax.tree.map(lambda t: t[0], pair_blocks)
            x = self._block(x, b0, None, rope)
            # block 1: attention part of the dense block, MoE as its MLP
            b1 = jax.tree.map(lambda t: t[1], pair_blocks)
            x = self._attn_sublayer(x, b1, rope)
            with scope("moe"):
                h = self._layer_norm(x, b1["ln2_g"], b1["ln2_b"])
                moe_out, l_aux = self.moe(moe_p, h, rng, train=train)
                return x + moe_out, l_aux

        # the configured remat policy applies per PAIR (dense block + MoE
        # half-block): without it every expert hidden and dispatch buffer is
        # saved for backward and an E=8 bank blows a 16G chip at bench shapes
        pair_fn = remat_wrap(pair_fn, self.config.remat)

        def pair_body(carry, xs):
            x, aux = carry
            pair_blocks, moe_p = xs
            x, l_aux = pair_fn(x, pair_blocks, moe_p)
            return (x, aux + l_aux), None

        with scope("layers"):
            (x, aux), _ = jax.lax.scan(pair_body, (x, jnp.float32(0.0)),
                                       (paired, params["moe"]))
        with scope("head"):
            x = self._layer_norm(x, params["lnf_g"], params["lnf_b"])
        return x, aux / n_pairs

    def apply(self, params, input_ids, rng=None):
        """(B, T) → full-sequence logits through the MoE trunk (the inherited
        dense apply would read the odd blocks' UNTRAINED dense MLP weights)."""
        x, _ = self._moe_trunk(params, input_ids, rng, train=False)
        return self._lm_logits(params, x)

    def loss(self, params, batch, rng=None):
        """Cross-entropy + load-balance aux loss."""
        from deepspeed_tpu.models.common import chunked_lm_loss, parse_lm_batch

        ids, labels, mask = parse_lm_batch(batch)
        x, aux = self._moe_trunk(params, ids, rng, train=True)
        # chunked vocab projection + CE, same as the dense trunk: the full
        # (B, T, V) fp32 logits tensor (≈2.5G at bs=12/seq=1024/V=50k) never
        # materializes — this is what lets the E=8 bank train on one 16G chip
        with scope("head"):
            x = x[:, :-1]
            head = (params["wte"].T if self.config.tie_embeddings
                    else params["lm_head"]).astype(x.dtype)
            ce = chunked_lm_loss(x, head, labels[:, 1:],
                                 mask[:, 1:] if mask is not None else None,
                                 bias=params.get("lm_head_b"),
                                 remat=self.config.remat_loss_chunks)
        return ce + self.aux_loss_coef * aux

    def _attn_sublayer(self, x, blk, rope=None):
        B, T, D = x.shape
        q, k, v = self._block_kv(x, blk, rope)
        with scope("attn/core"):
            attn = self._attention(q, k, v).reshape(B, T, D)
        with scope("attn/out"):
            return x + attn @ blk["proj_w"].astype(x.dtype) + blk["proj_b"].astype(x.dtype)

    # ------------------------------------------------------------- inference
    # Same cache layout/protocol as the dense GPT-2 ((L, B, max_len, W)
    # per k/v — init_cache and cache_partition_specs inherit), but the layer
    # walk must be the PAIRED one: the inherited prefill/decode would run the
    # odd blocks' untrained dense MLPs instead of the expert bank. This is
    # the expert-parallel serving path (reference inference/config.py:167 moe
    # block + module_inject/containers/base_moe.py): on an expert>1 mesh the
    # gated dispatch inside the scan compiles to a2a on the expert axis.

    def prefill(self, params, input_ids, cache):
        from deepspeed_tpu.models.common import kv_cache_rows

        c = self.config
        B, T = input_ids.shape
        max_len = cache["k"].shape[2]
        x = self._embed(params, input_ids)
        rope = self._rope_tables(jnp.arange(T))
        _, paired = self._paired_blocks(params)

        pad_kv = lambda k: kv_cache_rows(k, max_len)

        def body(x, xs):
            pair_blocks, moe_p = xs
            b0 = jax.tree.map(lambda t: t[0], pair_blocks)
            q0, k0, v0 = self._block_kv(x, b0, rope)
            x = self._block_finish(x, b0, self._attention_local(q0, k0, v0))
            b1 = jax.tree.map(lambda t: t[1], pair_blocks)
            q1, k1, v1 = self._block_kv(x, b1, rope)
            attn = self._attention_local(q1, k1, v1).reshape(B, T, -1)
            x = x + attn @ b1["proj_w"].astype(x.dtype) + b1["proj_b"].astype(x.dtype)
            h = self._layer_norm(x, b1["ln2_g"], b1["ln2_b"])
            moe_out, _ = self.moe(moe_p, h, None, train=False)
            x = x + moe_out
            return x, (jnp.stack([pad_kv(k0), pad_kv(k1)]),
                       jnp.stack([pad_kv(v0), pad_kv(v1)]))

        x, (ks, vs) = jax.lax.scan(body, x, (paired, params["moe"]))
        x = self._layer_norm(x, params["lnf_g"], params["lnf_b"])
        logits = self._lm_logits(params, x[:, -1])
        to_layers = lambda t: t.reshape((c.n_layer,) + t.shape[2:])
        return logits, {"k": to_layers(ks), "v": to_layers(vs),
                        "pos": jnp.int32(T)}

    def decode_step(self, params, token, cache):
        from deepspeed_tpu.models.common import (cached_decode_attention,
                                                 kv_cache_write)

        c = self.config
        pos = cache["pos"]
        x = self._decode_embed(params, token, pos)
        rope = self._rope_tables(pos[None])
        n_pairs, paired = self._paired_blocks(params)

        # stacked (L, ...) cache rides the scan CARRY with per-layer in-place
        # DUS at 2p / 2p+1 (see gpt2.decode_step: the xs/ys layout copied
        # the whole cache every decode step)
        def attend(x, blk, cache_k, cache_v, l):
            q, k, v = self._block_kv(x, blk, rope)          # (B, 1, H, Dh)
            cache_k = kv_cache_write(cache_k, k, l, pos)
            cache_v = kv_cache_write(cache_v, v, l, pos)
            attn = cached_decode_attention(q[:, 0], cache_k, cache_v, l, pos,
                                           c.n_head,
                                           alibi=self._alibi())[:, None]
            return attn, cache_k, cache_v

        def body(carry, xs):
            x, cache_k, cache_v = carry
            pair_blocks, moe_p, p = xs
            b0 = jax.tree.map(lambda t: t[0], pair_blocks)
            attn0, cache_k, cache_v = attend(x, b0, cache_k, cache_v,
                                             self.moe_every * p)
            x = self._block_finish(x, b0, attn0)
            b1 = jax.tree.map(lambda t: t[1], pair_blocks)
            attn1, cache_k, cache_v = attend(x, b1, cache_k, cache_v,
                                             self.moe_every * p + 1)
            B = x.shape[0]
            a = attn1.reshape(B, 1, -1)
            x = x + a @ b1["proj_w"].astype(x.dtype) + b1["proj_b"].astype(x.dtype)
            h = self._layer_norm(x, b1["ln2_g"], b1["ln2_b"])
            moe_out, _ = self.moe(moe_p, h, None, train=False)
            x = x + moe_out
            return (x, cache_k, cache_v), None

        (x, ks, vs), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (paired, params["moe"], jnp.arange(n_pairs)))
        x = self._layer_norm(x, params["lnf_g"], params["lnf_b"])
        logits = self._lm_logits(params, x[:, 0])
        return logits, {"k": ks, "v": vs, "pos": pos + 1}
