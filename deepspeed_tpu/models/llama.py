"""LLaMA family decoder — the second real model family.

The reference serves LLaMA through a per-architecture injection policy
(module_inject/containers/llama.py, replace_policy registration) over a loaded
HF torch module. Here the architecture is implemented TPU-native with the same
design as models/gpt2.py — layer-stacked params scanned with ``lax.scan``,
Megatron TP as PartitionSpecs, pluggable flash attention — covering the
LLaMA-specific pieces the GPT-2 trunk lacks:

* RMSNorm (no mean subtraction, no bias) in fp32;
* rotary position embeddings (rotate-half convention, matching HF's
  ``apply_rotary_pos_emb`` so converted checkpoints are bit-compatible);
* SwiGLU MLP (gate/up/down, no biases anywhere);
* grouped-query attention: ``n_kv_head <= n_head`` KV heads, repeated to the
  query head count at attention time — the KV cache stores only the KV heads,
  which is the GQA inference memory win;
* the block's two variations that OLMoE (``model_type: olmoe``) is built
  from, each chosen by the leaves a block holds: ``qk_norm`` — an RMSNorm
  over the WHOLE q and k projections before the split into heads and before
  RoPE — and, in place of the dense SwiGLU, ``n_experts`` routed SwiGLU
  experts with dropless top-k dispatch (moe/dropless.py): stacked leaves
  ``router_w (L, D, E)``, ``expert_gate_w`` / ``expert_up_w (L, E, D, F)``,
  ``expert_down_w (L, E, F, D)``. ``prefill`` / ``decode_step`` / ``loss``
  stay one set: the serving programs keep the expert leaves OUT of the layer
  scan's sliced operands (a per-layer slice of a stacked expert leaf is an
  805 MB copy at OLMoE-1B-7B) and hand the grouped-matmul kernel the whole
  leaf with the layer as an index.

Implements the same model protocol as GPT2Model (init_params, loss, apply,
prefill/decode_step, partition specs), so ``initialize()``,
``init_inference()``, ZeRO, TP, and the checkpoint engine apply unchanged.
Weights convert from HF ``LlamaForCausalLM`` via module_inject/hf.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.common import _rope_cos_sin, apply_rope


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    n_positions: int = 2048          # max sequence length (RoPE has no table)
    n_embd: int = 4096
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: Optional[int] = None  # None → n_head (no GQA)
    intermediate_size: Optional[int] = None  # None → LLaMA's 8/3·d rounded to 256
    rope_theta: float = 10000.0
    # None | {"rope_type": "linear", "factor": f}
    #      | {"rope_type": "llama3", "factor", "low_freq_factor",
    #         "high_freq_factor", "original_max_position_embeddings"}
    # (HF config.rope_scaling semantics — llama3 is the 3.1+ long-context NTK)
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    # remat the chunked-CE loss scan (see gpt2.GPT2Config.remat_loss_chunks)
    remat_loss_chunks: bool = True
    tie_embeddings: bool = False     # llama3.2-1B/3B style tied lm_head
    # OLMoE's block: RMSNorm over the whole q / k projection; routed experts
    # of width ``intermediate_size`` in place of the dense MLP (0 = dense)
    qk_norm: bool = False
    n_experts: int = 0
    n_experts_per_tok: int = 0
    norm_topk_prob: bool = False     # renormalise the k chosen probabilities
    router_aux_loss_coef: float = 0.0   # x the load-balancing loss, in loss()
    dtype: Any = jnp.bfloat16
    # what init_params draws in: a server that holds bf16 weights asks for
    # them as such, so no float32 copy of a 8.6 GB expert leaf ever exists
    param_dtype: Any = jnp.float32
    remat: Any = True                # False | True/'full' | 'dots' | 'attn'
    use_flash_attention: bool = True
    sequence_parallel: Any = False   # False | 'ring' | 'ulysses'

    VALID_REMAT = (False, None, "none", True, "full", "dots", "attn")

    VALID_ROPE_TYPES = ("default", "linear", "llama3")

    def __post_init__(self):
        if self.remat not in self.VALID_REMAT:
            raise ValueError(f"remat={self.remat!r} not in {self.VALID_REMAT}")
        if self.rope_scaling is not None:
            kind = self.rope_scaling.get("rope_type",
                                         self.rope_scaling.get("type", "default"))
            if kind not in self.VALID_ROPE_TYPES:
                raise ValueError(f"rope_scaling type {kind!r} not supported "
                                 f"(have: {self.VALID_ROPE_TYPES})")
        if self.n_kv_head is None:
            self.n_kv_head = self.n_head
        if self.n_head % self.n_kv_head:
            raise ValueError(f"n_head={self.n_head} not divisible by "
                             f"n_kv_head={self.n_kv_head}")
        if self.intermediate_size is None:
            self.intermediate_size = 256 * ((int(8 * self.n_embd / 3) + 255) // 256)
        if self.n_experts and not 0 < self.n_experts_per_tok <= self.n_experts:
            raise ValueError(f"n_experts_per_tok={self.n_experts_per_tok} "
                             f"of n_experts={self.n_experts}")

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def kv_dim(self) -> int:
        return self.n_kv_head * self.head_dim

    def num_params(self, active: bool = False) -> int:
        """``active``: count only the experts a token is routed to."""
        c = self
        d, i, l, v = c.n_embd, c.intermediate_size, c.n_layer, c.vocab_size
        mlps = (c.n_experts_per_tok if active else c.n_experts) or 1
        per_layer = d * d + 2 * d * c.kv_dim + d * d + mlps * 3 * d * i \
            + 2 * d + d * c.n_experts
        if c.qk_norm:
            per_layer += d + c.kv_dim
        embeds = v * d if c.tie_embeddings else 2 * v * d
        return embeds + l * per_layer + d

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Megatron accounting (6N + 12·l·d·s), as in GPT2Config: GQA does not
        change the attention score/value FLOPs, only the KV projection (already
        inside N). N counts the experts a token meets, not all of them."""
        s = seq_len or self.n_positions
        return 6 * self.num_params(active=True) \
            + 12 * self.n_layer * self.n_embd * s


PRESETS = {
    "llama-tiny": LlamaConfig(vocab_size=512, n_positions=128, n_embd=64,
                              n_layer=2, n_head=4, n_kv_head=2,
                              intermediate_size=128),
    # allenai/OLMoE-1B-7B-0125-Instruct config.json: 6.92 B parameters,
    # 1.3 B of them met by a token (64 experts of width 1024, top-8)
    "olmoe-1b-7b": LlamaConfig(vocab_size=50304, n_positions=4096,
                               n_embd=2048, n_layer=16, n_head=16,
                               intermediate_size=1024, qk_norm=True,
                               n_experts=64, n_experts_per_tok=8,
                               router_aux_loss_coef=0.01),
    "llama-7b": LlamaConfig(),
    # llama-3.2-1B (HF meta-llama/Llama-3.2-1B, incl. its llama3-NTK rope
    # scaling and 128k context): the one llama preset that pretrains on a
    # single 16G chip (bf16 params 2.5G + offloaded fp32 Adam state; the
    # V=128k logit residuals stay bounded by the remat_loss_chunks default)
    "llama3.2-1b": LlamaConfig(vocab_size=128256, n_positions=131072,
                               n_embd=2048, n_layer=16, n_head=32,
                               n_kv_head=8, intermediate_size=8192,
                               rope_theta=500000.0, tie_embeddings=True,
                               rope_scaling={"rope_type": "llama3",
                                             "factor": 32.0,
                                             "low_freq_factor": 1.0,
                                             "high_freq_factor": 4.0,
                                             "original_max_position_embeddings": 8192}),
    "llama-13b": LlamaConfig(n_embd=5120, n_layer=40, n_head=40,
                             intermediate_size=13824),
    "llama2-7b": LlamaConfig(n_positions=4096),
    "llama2-70b": LlamaConfig(n_embd=8192, n_layer=80, n_head=64, n_kv_head=8,
                              n_positions=4096, intermediate_size=28672),
    "llama3-8b": LlamaConfig(vocab_size=128256, n_positions=8192, n_embd=4096,
                             n_layer=32, n_head=32, n_kv_head=8,
                             intermediate_size=14336, rope_theta=500000.0),
    "llama3.1-8b": LlamaConfig(vocab_size=128256, n_positions=131072,
                               n_embd=4096, n_layer=32, n_head=32, n_kv_head=8,
                               intermediate_size=14336, rope_theta=500000.0,
                               rope_scaling={"rope_type": "llama3",
                                             "factor": 8.0,
                                             "low_freq_factor": 1.0,
                                             "high_freq_factor": 4.0,
                                             "original_max_position_embeddings": 8192}),
}


class LlamaModel:
    """Functional LLaMA: params are a dict with stacked per-layer leaves."""

    def __init__(self, config: LlamaConfig):
        self.config = config

    # ---------------------------------------------------------------- params
    def init_params(self, rng) -> Dict[str, Any]:
        c = self.config
        d, i, l = c.n_embd, c.intermediate_size, c.n_layer
        keys = jax.random.split(rng, 8)
        s = 0.02
        proj_scale = s / math.sqrt(2 * l)   # residual-scaled, as in GPT-2 init
        norm = lambda key, shape, scale: \
            jax.random.normal(key, shape, c.param_dtype) * scale
        ones = lambda *shape: jnp.ones(shape, c.param_dtype)
        blocks = {
            "attn_norm_g": ones(l, d),
            "q_w": norm(keys[1], (l, d, d), s),
            "k_w": norm(keys[2], (l, d, c.kv_dim), s),
            "v_w": norm(keys[3], (l, d, c.kv_dim), s),
            "o_w": norm(keys[4], (l, d, d), proj_scale),
            "mlp_norm_g": ones(l, d),
        }
        if c.qk_norm:
            blocks.update(q_norm_g=ones(l, d), k_norm_g=ones(l, c.kv_dim))
        if c.n_experts:
            # an expert leaf one layer at a time: the generator's temporaries
            # are a layer's, not the 2.1 G elements of the whole leaf
            e = c.n_experts
            per_layer = lambda key, shape, scale: jax.lax.map(
                lambda k: norm(k, shape, scale), jax.random.split(key, l))
            blocks.update(
                router_w=norm(jax.random.fold_in(keys[5], 1), (l, d, e), s),
                expert_gate_w=per_layer(keys[5], (e, d, i), s),
                expert_up_w=per_layer(keys[6], (e, d, i), s),
                expert_down_w=per_layer(keys[7], (e, i, d), proj_scale))
        else:
            blocks.update(gate_w=norm(keys[5], (l, d, i), s),
                          up_w=norm(keys[6], (l, d, i), s),
                          down_w=norm(keys[7], (l, i, d), proj_scale))
        params = {"wte": norm(keys[0], (c.vocab_size, d), s),
                  "blocks": blocks, "norm_g": ones(d)}
        if not c.tie_embeddings:
            params["lm_head"] = norm(jax.random.fold_in(keys[0], 1),
                                     (d, c.vocab_size), s)
        return params

    def param_partition_specs(self) -> Dict[str, Any]:
        """Megatron TP over the 'tensor' mesh axis: q/k/v/gate/up column
        parallel, o/down row parallel, vocab-sharded embedding. The routed
        experts are replicated: the one-chip server is what runs today, and
        experts over chips are ROADMAP R1's open half."""
        c = self.config
        blocks = {
            "attn_norm_g": P(None, None),
            "q_w": P(None, None, "tensor"),
            "k_w": P(None, None, "tensor"),
            "v_w": P(None, None, "tensor"),
            "o_w": P(None, "tensor", None),
            "mlp_norm_g": P(None, None),
        }
        if c.qk_norm:
            blocks.update(q_norm_g=P(None, None), k_norm_g=P(None, None))
        if c.n_experts:
            blocks.update(router_w=P(None, None, None),
                          expert_gate_w=P(None, None, None, None),
                          expert_up_w=P(None, None, None, None),
                          expert_down_w=P(None, None, None, None))
        else:
            blocks.update(gate_w=P(None, None, "tensor"),
                          up_w=P(None, None, "tensor"),
                          down_w=P(None, "tensor", None))
        specs = {"wte": P("tensor", None), "blocks": blocks,
                 "norm_g": P(None)}
        if not c.tie_embeddings:
            specs["lm_head"] = P(None, "tensor")
        return specs

    # --------------------------------------------------------------- compute
    def _head(self, params, dtype):
        head = (params["wte"].T if self.config.tie_embeddings
                else params["lm_head"])
        return head.astype(dtype)

    def _rms_norm(self, x, g):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + self.config.rms_norm_eps) * g).astype(x.dtype)

    def _repeat_kv(self, t):
        """(B, T, KV, Dh) → (B, T, H, Dh) for the attention kernel."""
        rep = self.config.n_head // self.config.n_kv_head
        return t if rep == 1 else jnp.repeat(t, rep, axis=2)

    def _attention(self, q, k, v):
        """q: (B,T,H,Dh); k,v: (B,T,KV,Dh). Causal self-attention with GQA:
        KV heads are repeated to the query head count, then the shared
        dispatch (models/common.py: sequence-parallel → flash → einsum)."""
        from deepspeed_tpu.models.common import causal_attention

        c = self.config
        return causal_attention(q, self._repeat_kv(k), self._repeat_kv(v),
                                use_flash=c.use_flash_attention,
                                sequence_parallel=c.sequence_parallel)

    def _block_qkv(self, x, blk, cos, sin):
        """One block's RoPE'd q, k, v for the current x."""
        c = self.config
        B, T, D = x.shape
        h = self._rms_norm(x, blk["attn_norm_g"])
        hd = h.astype(c.dtype)
        q = hd @ blk["q_w"].astype(hd.dtype)
        k = hd @ blk["k_w"].astype(hd.dtype)
        if "q_norm_g" in blk:
            # OLMoE: over the whole projection, before the heads are split
            q = self._rms_norm(q, blk["q_norm_g"])
            k = self._rms_norm(k, blk["k_norm_g"])
        q = q.reshape(B, T, c.n_head, c.head_dim)
        k = k.reshape(B, T, c.n_kv_head, c.head_dim)
        v = (hd @ blk["v_w"].astype(hd.dtype)).reshape(B, T, c.n_kv_head, c.head_dim)
        return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v

    EXPERT_LEAVES = ("expert_gate_w", "expert_up_w", "expert_down_w")

    def _mlp(self, h, blk, stacked=None, layer=None):
        """The block's MLP on the normed h (B, T, D) -> (out, router
        statistics or None). Dense SwiGLU where the block holds ``gate_w``;
        routed experts where it holds ``router_w``: the expert leaves are the
        block's own (E, ...) slices, or ``stacked`` (L, E, ...) leaves with
        the traced ``layer`` (the serving programs: see the module's
        docstring). Statistics: pairs routed to each expert (E,) int32 and
        the router's probabilities summed over the tokens (E,) float32."""
        if "router_w" not in blk:
            gate = h @ blk["gate_w"].astype(h.dtype)
            up = h @ blk["up_w"].astype(h.dtype)
            return (jax.nn.silu(gate) * up) @ blk["down_w"].astype(h.dtype), None
        from deepspeed_tpu.moe.dropless import route_topk, routed_mlp

        c = self.config
        B, T, D = h.shape
        tokens = h.reshape(B * T, D)
        probs, weights, experts = route_topk(
            tokens, blk["router_w"], c.n_experts_per_tok, c.norm_topk_prob)
        leaves = stacked if stacked is not None else blk
        out, sizes = routed_mlp(
            tokens, weights, experts,
            *(leaves[n] for n in self.EXPERT_LEAVES), layer=layer)
        return out.reshape(B, T, D), (sizes, jnp.sum(probs, axis=0))

    def _split_experts(self, blocks):
        """(the leaves a layer scan may slice, the stacked expert leaves it
        must not — None for a dense model)."""
        if "router_w" not in blocks:
            return blocks, None
        return ({n: v for n, v in blocks.items()
                 if n not in self.EXPERT_LEAVES},
                {n: blocks[n] for n in self.EXPERT_LEAVES})

    def _block_finish(self, x, blk, attn, stacked=None, layer=None):
        """-> (x after the attention output and the MLP, router statistics
        or None)."""
        B, T, D = x.shape
        a = attn.reshape(B, T, D) @ blk["o_w"].astype(x.dtype)
        x = x + a
        h = self._rms_norm(x, blk["mlp_norm_g"])
        out, stats = self._mlp(h, blk, stacked, layer)
        return x + out, stats

    def _block(self, x, blk, cos_sin):
        cos, sin = cos_sin
        q, k, v = self._block_qkv(x, blk, cos, sin)
        attn = self._attention(q, k, v)
        attn = checkpoint_name(attn, "attn_out")
        return self._block_finish(x, blk, attn)

    def _trunk(self, params, input_ids, rng=None, with_router_stats=False):
        c = self.config
        B, T = input_ids.shape
        x = params["wte"].astype(c.dtype)[input_ids]
        cos, sin = _rope_cos_sin(jnp.arange(T), c.head_dim, c.rope_theta, c.rope_scaling)

        block_fn = self._block
        if c.remat in (True, "full"):
            block_fn = jax.checkpoint(
                block_fn, policy=jax.checkpoint_policies.nothing_saveable)
        elif c.remat == "dots":
            block_fn = jax.checkpoint(
                block_fn,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        elif c.remat == "attn":
            block_fn = jax.checkpoint(
                block_fn,
                policy=jax.checkpoint_policies.save_only_these_names("attn_out"))

        def scan_body(carry, blk):
            return block_fn(carry, blk, (cos, sin))

        # overridable layer scan (overlap engine's ZeRO-3 gather prefetch;
        # a plain lax.scan when nothing is installed)
        from deepspeed_tpu.models.common import layer_scan

        x, stats = layer_scan(scan_body, x, params["blocks"])
        x = self._rms_norm(x, params["norm_g"])
        return (x, stats) if with_router_stats else x

    def hidden_states(self, params, input_ids, rng=None):
        return self._trunk(params, input_ids, rng)

    def apply(self, params, input_ids, rng=None):
        """input_ids (B, T) int32 → logits (B, T, V) fp32."""
        x = self._trunk(params, input_ids, rng)
        return (x @ self._head(params, x.dtype)).astype(jnp.float32)

    def loss(self, params, batch, rng=None):
        """Next-token cross entropy with the chunked vocab projection
        (models/common.py); a routed model adds ``router_aux_loss_coef`` x
        the load-balancing loss over every layer and position."""
        from deepspeed_tpu.models.common import chunked_lm_loss, parse_lm_batch

        c = self.config
        ids, labels, mask = parse_lm_batch(batch)
        x, stats = self._trunk(params, ids, rng, with_router_stats=True)
        x = x[:, :-1]
        head = self._head(params, x.dtype)
        loss = chunked_lm_loss(x, head, labels[:, 1:],
                               mask[:, 1:] if mask is not None else None,
                               remat=c.remat_loss_chunks)
        if stats is not None and c.router_aux_loss_coef:
            from deepspeed_tpu.moe.dropless import load_balancing_loss

            loss = loss + c.router_aux_loss_coef * load_balancing_loss(
                *stats, n_tokens=ids.size)
        return loss

    # ------------------------------------------------------------- inference
    def init_cache(self, batch_size: int, max_len: int):
        """KV cache holds only the KV heads, folded into lane-dense rows:
        (L, B, max_len, W) (models/common.py ``init_kv_cache``) — the GQA
        memory win over the reference's full-head InferenceContext workspace
        (csrc/transformer/inference/includes/inference_context.h:287). A
        routed model's cache also carries ``expert_tokens`` (L, E) int32: the
        (token, expert) pairs each expert has been given since the prompt's
        first token, summed by the compiled programs themselves (the
        front-end reads it back when a request resolves)."""
        from deepspeed_tpu.models.common import init_kv_cache

        c = self.config
        cache = init_kv_cache(c.n_layer, batch_size, max_len, c.n_kv_head,
                              c.head_dim, c.dtype)
        if c.n_experts:
            cache["expert_tokens"] = jnp.zeros((c.n_layer, c.n_experts),
                                               jnp.int32)
        return cache

    def cache_partition_specs(self):
        from deepspeed_tpu.models.common import kv_cache_partition_specs

        specs = kv_cache_partition_specs(self.config.n_kv_head,
                                         self.config.head_dim)
        if self.config.n_experts:
            specs["expert_tokens"] = P()
        return specs

    def prefill(self, params, input_ids, cache):
        """Process the prompt, fill the cache, return last-position logits."""
        from deepspeed_tpu.models.common import (kv_cache_rows,
                                                 local_causal_attention)

        c = self.config
        B, T = input_ids.shape
        max_len = cache["k"].shape[2]
        x = params["wte"].astype(c.dtype)[input_ids]
        cos, sin = _rope_cos_sin(jnp.arange(T), c.head_dim, c.rope_theta, c.rope_scaling)
        blocks, experts = self._split_experts(params["blocks"])

        def body(carry, xs):
            x = carry
            blk, l = xs
            q, k, v = self._block_qkv(x, blk, cos, sin)
            attn = local_causal_attention(q, self._repeat_kv(k),
                                          self._repeat_kv(v),
                                          c.use_flash_attention)
            x, stats = self._block_finish(x, blk, attn, experts, l)
            return x, (kv_cache_rows(k, max_len), kv_cache_rows(v, max_len),
                       None if stats is None else stats[0])

        x, (ks, vs, routed) = jax.lax.scan(
            body, x, (blocks, jnp.arange(c.n_layer)))
        x = self._rms_norm(x, params["norm_g"])
        logits = (x[:, -1] @ self._head(params, x.dtype)).astype(jnp.float32)
        cache = {"k": ks, "v": vs, "pos": jnp.int32(T)}
        if routed is not None:
            cache["expert_tokens"] = routed
        return logits, cache

    def decode_step(self, params, token, cache):
        """One token for every sequence: (B,) → logits (B, V), cache advanced."""
        c = self.config
        B = token.shape[0]
        pos = cache["pos"]
        x = params["wte"].astype(c.dtype)[token][:, None]   # (B, 1, D)
        cos, sin = _rope_cos_sin(pos[None], c.head_dim, c.rope_theta, c.rope_scaling)
        blocks, experts = self._split_experts(params["blocks"])

        from deepspeed_tpu.models.common import (cached_decode_attention,
                                                 kv_cache_write)

        # stacked cache rides the scan CARRY (in-place per-layer DUS); the
        # xs/ys layout made lax.scan assemble a fresh stacked cache buffer
        # every decode step — see gpt2.decode_step for the measured cost
        def body(carry, xs):
            x, cache_k, cache_v = carry
            blk, l = xs
            q, k, v = self._block_qkv(x, blk, cos, sin)     # q (B,1,H,Dh)
            cache_k = kv_cache_write(cache_k, k, l, pos)
            cache_v = kv_cache_write(cache_v, v, l, pos)
            # GQA decode against the KV-head cache — repeated K/V are never
            # materialized (grouped einsum or the Pallas streaming kernel)
            attn = cached_decode_attention(q[:, 0], cache_k, cache_v, l, pos,
                                           c.n_kv_head)[:, None]
            x, stats = self._block_finish(x, blk, attn, experts, l)
            return (x, cache_k, cache_v), None if stats is None else stats[0]

        (x, ks, vs), routed = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (blocks, jnp.arange(c.n_layer)))
        x = self._rms_norm(x, params["norm_g"])
        logits = (x[:, 0] @ self._head(params, x.dtype)).astype(jnp.float32)
        out = {"k": ks, "v": vs, "pos": pos + 1}
        if routed is not None:
            out["expert_tokens"] = cache["expert_tokens"] + routed
        return logits, out
