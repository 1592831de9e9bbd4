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
  leaf with the layer as an index;
* the further variations today's large routed models make, each again
  chosen by the leaves a block holds (ROADMAP D4: one block that takes its
  mixer, its norms and its MLP per layer):
  - **latent attention (MLA)**, the mixer of a block that holds ``kv_a_w``:
    queries through a low-rank bottleneck (``q_a_w``, ``q_a_norm_g``,
    ``q_b_w``; or, ``q_lora_rank`` 0, straight from one ``q_w``) into
    heads of ``[nope | rope]`` columns (rotated, or with ``use_rope``
    false carried as they are); keys and values
    through ONE latent row a position, ``[c_kv | k_rope]`` (``kv_a_w``,
    ``kv_a_norm_g``), from which ``kv_b_k_w (L, H, nope, C)`` makes every
    head's un-rotated key part and ``kv_b_v_w (L, H, C, v)`` its value.
    The trunk and ``prefill`` expand the row into per-head keys and values
    (q.k at nope + rope columns, v at its own width); ``decode_step``
    ABSORBS the two up-projections into the query and the output, attends
    over the cached latent rows themselves (``common
    .latent_decode_attention``) and caches nothing per head;
  - **sandwich norm**: a block that holds ``post_attn_norm_g`` /
    ``post_mlp_norm_g`` normalises each branch's OUTPUT too, before the
    residual add (four gains a layer);
  - **a shared expert** beside the routed ones (``shared_gate_w`` ...),
    which every token meets; a router that scores with a sigmoid and
    scales the chosen weights (``moe/dropless.py::route_topk``);
  - **a chip's share of the experts**: ``experts_held = (first, count)``.
    The router keeps its ``n_experts`` outputs and its top-k; the expert
    leaves are ``(L, count, ...)``; a pair routed to an expert outside the
    share adds nothing here (another chip adds it);
  - **two stacks**: ``n_dense_layers`` leading layers with a dense SwiGLU
    of width ``dense_intermediate_size`` in ``params["dense_blocks"]``,
    the routed layers in ``params["blocks"]``; the trunk, ``prefill`` and
    ``decode_step`` scan one after the other, and the cache's layer axis
    runs over both;
  - **a head size of the configuration's own** (``head_dim``; None:
    ``n_embd // n_head``), **no rotary embedding** (``use_rope`` false) and
    **an output gate** on softmax attention (a block that holds
    ``attn_gate_w``: ``o_w [attn * sigmoid(x attn_gate_w)]``);
  - **a layer pattern** (``gqa_layers``: the layers that mix with softmax
    attention; the others with KDA, ``models/kda.py``, a gated delta rule
    whose memory is a fixed-size state and not a row a position).
    ``LlamaConfig.pattern`` is one period of it. ``params["blocks"]`` then
    holds what EVERY layer has (norms, router, experts) stacked over all
    layers, and each kind of mixer lies in a stack of its own over the
    layers that have it (``attn_blocks``, ``kda_blocks``); the trunk,
    ``prefill`` and ``decode_step`` scan over PERIODS with the period's
    layers in the body — a run of consecutive layers of one kind an inner
    scan that indexes the period's leaves by layer — so each layer's
    weights are read in place, once, and the stacked expert leaves still go
    whole to the grouped-matmul kernel with the layer as an index. The
    cache holds two kinds of state in one dict: ``k``, ``v`` over the
    SOFTMAX layers only, and ``kda_state`` / ``kda_conv`` over the KDA
    layers (``models/common.py::cache_footprint`` tells them apart by
    name). ``loss`` runs KDA through the chunked form with the state
    pass's own backward (``ops/pallas/kda.py::state_pass``: both kernels
    on a TPU), a segment at a time. The softmax kind of such a pattern
    may be latent attention, and leading dense layers go with it where
    they are all of ONE kind: ``dense_blocks`` then holds that kind's
    mixer leaves itself (``LlamaConfig.dense_mixer``), ``attn_blocks`` /
    ``kda_blocks`` run over the routed layers;
  - **window and full softmax layers in one pattern** (``layer_types``, a
    layer's kind by the published key, and ``sliding_window``; afmoe): the
    two kinds hold the SAME leaves, so they stay in ``blocks`` /
    ``dense_blocks`` and the kind reaches a block from the pattern
    (``_block(kind=)``): a window layer's attention is ``causal_attention
    (window=)`` — the flash kernels' band on a TPU — and, with
    ``global_rope`` false, it alone is rotated. Leading dense layers go with
    such a pattern: each stack walks its own phase of it
    (``LlamaConfig.stack_pattern``). The cached walk keeps the WHOLE context
    for every layer and gives a window layer's decode step the window's
    slots (``cached_decode_attention(window=)``: the einsum, the decode
    kernel carries no window);
  - **window layers with a mixer of their OWN** (``window_kv_head``: another
    number of KV heads than the full layers'; ``window_sink``: a learned
    sink logit a head, which takes mass in the softmax and carries no value;
    MiMo-V2-Flash): the two softmax kinds' LEAVES differ, so each lies in a
    stack of its own over the layers that have it (``attn_blocks``,
    ``win_blocks``) beside ``blocks``, as KDA layers do, and a block's KV
    head count is read off its ``k_w``. The cache holds TWO kinds of rows in
    the one dict: ``k`` / ``v`` over the FULL layers, the whole context; and
    ``win_k`` / ``win_v`` over the window layers, a RING of ``sliding_window``
    slots (``common.init_kv_ring``: a decode step writes slot ``pos %
    window``, a prefill the prompt's last ``window`` positions; K is rotated
    before it is written, so slot order means nothing to the softmax), which
    a decode step attends whole through the decode kernel (valid length
    ``min(pos + 1, window)``) and a prefill never reads: its window layers
    run the flash kernels' band. With them GQA's other widths: a value head
    size of its own (``v_head_dim``: q.k at 192, v at 128; K rows and V rows
    then differ in width), a rotary embedding over a head's first
    ``rotary_dim`` columns, a rotary base by kind (``window_rope_theta``),
    ``value_scale`` x v;
  - **per-head q/k norm** (``qk_norm="head"``: gains ``(head_dim,)``, told
    from OLMoE's whole-projection form by the leaf's shape), **an embedding
    multiplier** (``embed_scale``), and **a selection bias** of the router
    (a block that holds ``router_bias``: ``route_topk(bias=)``), which no
    gradient and no optimizer moves: the model names it to the engine
    (``ruled_leaves``) with the rule that does (``apply_rule``: aux-loss-free
    balancing from the step's routing counts, ``loss_and_aux``);
  - **generation by diffusion over blocks** (``block_length`` > 1; SDAR):
    the attention mask is BLOCK-causal (position i sees j where ``j //
    block_length <= i // block_length``: ``local_causal_attention(block=)``,
    the flash forward's ``block=`` on a TPU) in the trunk and in
    ``prefill``, and beside ``decode_step`` the model has ``block_step``:
    ``block_length`` positions at once, their rows written into slots ``pos
    .. pos + Lb - 1`` and attended, all of them, over slots ``0 .. pos + Lb
    - 1`` (``cached_decode_attention`` with a block of query positions: the
    decode kernel's multi-row form), the logits row of position i
    predicting the token AT i. ``pos`` advances only when a step COMMITS
    a block: in a pass of its own (``commit=True``, the definition) or, as
    the engine runs it, CARRIED in the next block's first pass (``pending=``:
    ``2 Lb`` positions, the finished block's blind to the new block's slots:
    ``cached_decode_attention(early=)``). How a block is denoised
    (``denoising_steps``, ``remasking``, ``confidence_threshold``,
    ``mask_token_id``) is the configuration's and
    the inference engine's (``inference/engine.py``: ``block_decoding``).
    Softmax GQA layers only: a KDA, window or latent layer is refused.

Implements the same model protocol as GPT2Model (init_params, loss, apply,
prefill/decode_step, partition specs), so ``initialize()``,
``init_inference()``, ZeRO, TP, and the checkpoint engine apply unchanged.
Weights convert from HF ``LlamaForCausalLM`` via module_inject/hf.py.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.telemetry.scopes import scope
from deepspeed_tpu.models.common import (_rope_cos_sin, apply_rope,
                                         apply_rope_leading, remat_wrap)


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    n_positions: int = 2048          # max sequence length (RoPE has no table)
    n_embd: int = 4096
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: Optional[int] = None  # None → n_head (no GQA)
    head_dim: Optional[int] = None   # None → n_embd // n_head
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
    # "head": per HEAD instead, gains (head_dim,), after the split (afmoe)
    qk_norm: Any = False
    n_experts: int = 0
    n_experts_per_tok: int = 0
    norm_topk_prob: bool = False     # renormalise the k chosen probabilities
    router_aux_loss_coef: float = 0.0   # x the load-balancing loss, in loss()
    # a routed model's other MLPs: ``n_dense_layers`` leading layers keep a
    # dense SwiGLU of width ``dense_intermediate_size``; every routed layer
    # adds ``n_shared_experts`` experts (one SwiGLU of n_shared x
    # intermediate_size) that every token meets
    n_dense_layers: int = 0
    dense_intermediate_size: Optional[int] = None
    n_shared_experts: int = 0
    router_scoring: str = "softmax"     # | "sigmoid" (route_topk)
    routed_scaling_factor: float = 1.0  # x the chosen weights
    # (first, count): the experts, of the router's ``n_experts``, whose
    # weights this chip holds; None = all of them
    experts_held: Optional[tuple] = None
    # latent attention (MLA) where kv_lora_rank > 0: the widths of the two
    # bottlenecks (q_lora_rank 0: no bottleneck, the queries from one
    # ``q_w``), of a head's un-rotated and rotated q.k columns (with
    # ``use_rope`` false the second group is carried unrotated), of its v
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    sandwich_norm: bool = False         # RMSNorm each branch's output too
    use_rope: bool = True               # False: no positional embedding (NoPE)
    attn_gate: bool = False             # softmax output x sigmoid(x attn_gate_w)
    # the layer pattern of a hybrid model: the layers whose mixer is softmax
    # attention (None: every layer); the others mix with KDA (models/kda.py),
    # ``kda_heads`` heads of ``kda_head_dim`` behind a causal depthwise
    # convolution over ``kda_conv`` positions. The pattern must repeat with a
    # period that divides n_layer (``pattern``)
    gqa_layers: Optional[tuple] = None
    # the OTHER layer pattern, of softmax layers alone: a layer's kind by the
    # published key, "full_attention" | "sliding_attention" (a causal window
    # of ``sliding_window`` keys, the row's own among them). Same leaves,
    # another mask; ``global_rope`` false: the full layers take no rotary
    # embedding (afmoe rotates where the layer is local). Each stack (the
    # leading dense layers, the routed ones) walks its own phase of it
    layer_types: Optional[tuple] = None
    sliding_window: int = 0
    global_rope: bool = True
    # window layers with a mixer of their OWN leaves: ``window_kv_head`` KV
    # heads (None: ``n_kv_head``), a learned sink logit a head
    # (``window_sink``); either gives them a stack (``win_blocks``) and a
    # cache (a ring of ``sliding_window`` slots) of their own. Their rotary
    # base where it is not ``rope_theta``
    window_kv_head: Optional[int] = None
    window_sink: bool = False
    window_rope_theta: Optional[float] = None
    # GQA's other widths: the columns of a head the rotary embedding turns
    # (None: all of them), x v before it is attended; v's head size is
    # ``v_head_dim`` (0: ``head_dim``), which latent attention has too
    rotary_dim: Optional[int] = None
    value_scale: float = 1.0
    # x the embedding's output (muP: n_embd ** 0.5)
    embed_scale: float = 1.0
    # a per-expert bias of the router's SELECTION (leaf ``router_bias``,
    # route_topk(bias=)), which no gradient and no optimizer moves: once a
    # step ``router_bias_rate`` x the balancing rule (moe/dropless.py::
    # balance_bias) from the step's routing counts, through the engine
    router_bias: bool = False
    router_bias_rate: float = 0.0
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    # generation by diffusion over blocks (0: autoregressive): a step yields
    # ``block_length`` tokens after up to ``denoising_steps`` (0: as many as
    # the block is long) forward passes over the block, every position not
    # yet chosen the ``mask_token_id`` (None: the vocabulary's last id); a
    # pass unmasks by ``remasking`` (common.REMASKING: the leftmost, the most
    # confident, or every one above ``confidence_threshold``), at least
    # ``block_length // denoising_steps`` positions
    block_length: int = 0
    denoising_steps: int = 0
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: Optional[int] = None
    dtype: Any = jnp.bfloat16
    # what init_params draws in: a server that holds bf16 weights asks for
    # them as such, so no float32 copy of a 8.6 GB expert leaf ever exists
    param_dtype: Any = jnp.float32
    remat: Any = True                # False | True/'full' | 'dots' | 'attn'
    use_flash_attention: bool = True
    sequence_parallel: Any = False   # False | 'ring' | 'ulysses'

    VALID_REMAT = (False, None, "none", True, "full", "dots", "attn")

    VALID_ROPE_TYPES = ("default", "linear", "llama3")

    VALID_ROUTER_SCORING = ("softmax", "sigmoid")

    LAYER_TYPES = {"full_attention": "attn", "sliding_attention": "win"}

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
        if self.head_dim is None:
            self.head_dim = self.n_embd // self.n_head
        if self.n_head % self.n_kv_head:
            raise ValueError(f"n_head={self.n_head} not divisible by "
                             f"n_kv_head={self.n_kv_head}")
        if self.intermediate_size is None:
            self.intermediate_size = 256 * ((int(8 * self.n_embd / 3) + 255) // 256)
        if self.n_experts and not 0 < self.n_experts_per_tok <= self.n_experts:
            raise ValueError(f"n_experts_per_tok={self.n_experts_per_tok} "
                             f"of n_experts={self.n_experts}")
        if self.router_scoring not in self.VALID_ROUTER_SCORING:
            raise ValueError(f"router_scoring={self.router_scoring!r} not in "
                             f"{self.VALID_ROUTER_SCORING}")
        if not 0 <= self.n_dense_layers < max(self.n_layer, 1) or \
                (self.n_dense_layers and not self.n_experts):
            raise ValueError(f"n_dense_layers={self.n_dense_layers}: leading "
                             "dense layers of a ROUTED model, fewer than "
                             f"n_layer={self.n_layer}")
        if self.dense_intermediate_size is None:
            self.dense_intermediate_size = self.intermediate_size
        if self.experts_held is not None:
            first, count = self.experts_held = tuple(self.experts_held)
            if not (0 <= first and 0 < count
                    and first + count <= self.n_experts):
                raise ValueError(f"experts_held={self.experts_held} of "
                                 f"n_experts={self.n_experts}")
            if count < self.n_experts and self.router_aux_loss_coef:
                raise ValueError("the load-balancing loss needs every "
                                 "expert's count: a share of the experts "
                                 "takes router_aux_loss_coef=0")
        if self.mla and not (self.qk_nope_head_dim
                             and self.qk_rope_head_dim and self.v_head_dim
                             and self.n_kv_head == self.n_head
                             and not self.qk_norm):
            raise ValueError("latent attention takes qk_nope_head_dim, "
                             "qk_rope_head_dim and v_head_dim, n_kv_head = "
                             "n_head and no qk_norm (q_lora_rank 0: the "
                             "queries straight from q_w)")
        if self.attn_gate and self.mla:
            raise ValueError("attn_gate: the output gate of a GQA mixer, not "
                             "of latent attention")
        if self.gqa_layers is not None:
            self.gqa_layers = tuple(sorted(set(self.gqa_layers)))
            if not self.gqa_layers or self.gqa_layers[0] < 0 \
                    or self.gqa_layers[-1] >= self.n_layer \
                    or not (self.kda_heads > 0 and self.kda_head_dim > 0
                            and self.kda_conv > 1):
                raise ValueError(
                    f"gqa_layers={self.gqa_layers}: softmax layers among "
                    f"n_layer={self.n_layer}, the others KDA layers of "
                    "kda_heads x kda_head_dim behind a convolution of "
                    "kda_conv > 1 positions")
            if self.sequence_parallel:
                raise ValueError(
                    "a layer pattern (gqa_layers) with sequence parallelism "
                    "is not built: KDA layers carry a state along the "
                    "sequence")
            if len(set(self.kinds[:self.n_dense_layers])) > 1:
                raise ValueError(
                    f"a layer pattern (gqa_layers={self.gqa_layers}) whose "
                    f"{self.n_dense_layers} leading dense layers are not of "
                    "one kind is not built: the dense stack holds ONE kind "
                    "of mixer's leaves")
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            if len(self.layer_types) != self.n_layer or self.sliding_window < 1 \
                    or not set(self.layer_types) <= set(self.LAYER_TYPES):
                raise ValueError(
                    f"layer_types: one of {sorted(self.LAYER_TYPES)} a layer "
                    f"(n_layer={self.n_layer}) and a sliding_window >= 1, "
                    f"not {self.layer_types} / {self.sliding_window}")
            if self.gqa_layers is not None or self.mla \
                    or self.sequence_parallel:
                raise ValueError(
                    "layer_types (window and full softmax layers) with "
                    "gqa_layers, latent attention or sequence parallelism "
                    "is not built")
        if self.window_kv_head is not None or self.window_sink \
                or self.window_rope_theta is not None:
            kv = self.window_kv_head or self.n_kv_head
            if self.layer_types is None or self.n_head % kv \
                    or self.sequence_parallel \
                    or len(set(self.kinds[:self.n_dense_layers])) > 1:
                raise ValueError(
                    "window_kv_head / window_sink / window_rope_theta: of "
                    "the window layers of a layer_types pattern, "
                    f"window_kv_head={kv} dividing n_head={self.n_head}, "
                    "leading dense layers of ONE kind, no sequence "
                    "parallelism")
        if self.rotary_dim is not None and not (
                0 < self.rotary_dim <= self.head_dim
                and self.rotary_dim % 2 == 0 and not self.mla):
            raise ValueError(f"rotary_dim={self.rotary_dim}: an even number "
                             f"of a GQA head's {self.head_dim} columns")
        if self.qk_norm not in (False, True, "head") or \
                (self.router_bias and not self.n_experts):
            raise ValueError(f"qk_norm={self.qk_norm!r} (False | True | "
                             "'head'); router_bias is a routed model's")
        if self.block_length:
            from deepspeed_tpu.models.common import REMASKING

            self.denoising_steps = self.denoising_steps or self.block_length
            if self.mask_token_id is None:
                self.mask_token_id = self.vocab_size - 1
            if self.block_length < 2 \
                    or not 1 <= self.denoising_steps <= self.block_length \
                    or self.remasking not in REMASKING \
                    or not 0 <= self.mask_token_id < self.vocab_size:
                raise ValueError(
                    f"block_length={self.block_length}: at least 2 positions "
                    f"a block, denoised in 1 .. block_length passes "
                    f"(denoising_steps={self.denoising_steps}) by one of "
                    f"{REMASKING} (remasking={self.remasking!r}), "
                    f"mask_token_id={self.mask_token_id} inside the "
                    "vocabulary")
            if self.gqa_layers is not None or self.layer_types is not None \
                    or self.mla or self.sequence_parallel:
                raise ValueError(
                    "block_length (generation by diffusion over blocks) with "
                    "a KDA, window or latent layer or sequence parallelism "
                    "is not built: the block step attends through softmax "
                    "GQA layers' K/V rows")

    @property
    def kv_dim(self) -> int:
        return self.n_kv_head * self.head_dim

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def rope_dim(self) -> int:
        """Columns of a head the rotary embedding turns."""
        return self.qk_rope_head_dim if self.mla \
            else self.rotary_dim or self.head_dim

    @property
    def v_dim(self) -> int:
        """A GQA head's columns of v (and of the attention's output)."""
        return self.v_head_dim or self.head_dim

    @property
    def own_window(self) -> bool:
        """Whether the window layers hold leaves a full layer does not: a
        stack and a cache of their own."""
        return self.window_kv_head is not None or self.window_sink

    def kv_heads(self, kind="attn") -> int:
        return self.window_kv_head if kind == "win" and self.window_kv_head \
            else self.n_kv_head

    @property
    def latent_dim(self) -> int:
        """Values of one cached latent row: ``[c_kv | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kinds(self) -> tuple:
        """Every layer's kind of mixer: ``attn`` (full softmax), ``win``
        (softmax under a window), ``kda``."""
        if self.layer_types is not None:
            return tuple(self.LAYER_TYPES[t] for t in self.layer_types)
        if self.gqa_layers is None:
            return ("attn",) * self.n_layer
        return tuple("attn" if l in self.gqa_layers else "kda"
                     for l in range(self.n_layer))

    def stack_pattern(self, first: int, count: int) -> tuple:
        """One period of the pattern as the stack of layers ``first .. first
        + count - 1`` walks it: the shortest run of kinds the stack repeats.
        ``("attn",)`` where every layer is softmax attention, ``("attn",
        "kda", "kda", "kda")`` for one softmax layer in four; a stack that
        begins inside a period begins at that phase."""
        kinds = list(self.kinds[first:first + count])
        period = next(p for p in range(1, count + 1)
                      if count % p == 0 and kinds == kinds[:p] * (count // p))
        return tuple(kinds[:period])

    @property
    def pattern(self) -> tuple:
        """One period of the layer pattern of the stack ``params["blocks"]``
        holds (all layers, or those after the leading dense ones)."""
        return self.stack_pattern(self.n_dense_layers,
                                  self.n_layer - self.n_dense_layers)

    @property
    def dense_mixer(self):
        """What the leading dense layers' stack holds of a mixer: true, the
        model's one kind of softmax mixer; with a layer pattern the ONE kind
        of those layers (``"attn"`` | ``"kda"``)."""
        return self.kinds[0] if self.gqa_layers is not None \
            or self.own_window else True

    @property
    def n_attn_layers(self) -> int:
        """Layers that keep rows a position in the cache."""
        if self.own_window:
            return self.kinds.count("attn")
        return self.n_layer if self.gqa_layers is None \
            else len(self.gqa_layers)

    @property
    def n_held(self) -> int:
        """Experts whose weights are held here."""
        return self.experts_held[1] if self.experts_held else self.n_experts

    @property
    def n_moe_layers(self) -> int:
        return self.n_layer - self.n_dense_layers if self.n_experts else 0

    def num_params(self, active: bool = False) -> int:
        """Parameters HELD (a share of the experts counts its own);
        ``active``: count only the experts a token is routed to."""
        c = self
        d, i, v = c.n_embd, c.intermediate_size, c.vocab_size
        if c.mla:
            qk = c.qk_nope_head_dim + c.qk_rope_head_dim
            attn = (d * c.q_lora_rank + c.q_lora_rank
                    + c.q_lora_rank * c.n_head * qk if c.q_lora_rank
                    else d * c.n_head * qk) \
                + d * c.latent_dim + c.kv_lora_rank \
                + c.kv_lora_rank * c.n_head * (c.qk_nope_head_dim
                                               + c.v_head_dim) \
                + c.n_head * c.v_head_dim * d
        else:
            heads = c.n_head * c.head_dim
            gqa = lambda kv: (1 + c.attn_gate) * d * heads \
                + c.n_head * c.v_dim * d + d * kv * (c.head_dim + c.v_dim)
            attn = gqa(c.n_kv_head)
            if c.qk_norm:
                attn += 2 * c.head_dim if c.qk_norm == "head" \
                    else heads + c.kv_dim
        norms = (4 if c.sandwich_norm else 2) * d
        mlps = (c.n_experts_per_tok if active else c.n_held) or 1
        routed = attn + norms + d * c.n_experts \
            + (c.n_experts if c.router_bias else 0) \
            + (mlps + c.n_shared_experts) * 3 * d * i
        dense = attn + norms + 3 * d * c.dense_intermediate_size
        embeds = v * d if c.tie_embeddings else 2 * v * d
        total = embeds + c.n_dense_layers * dense \
            + (c.n_layer - c.n_dense_layers) * routed + d
        if c.gqa_layers is not None:    # the KDA layers' mixer for softmax's
            from deepspeed_tpu.models import kda

            total += (c.n_layer - c.n_attn_layers) * (kda.num_params(c) - attn)
        if c.own_window:                # the window layers' mixer for a full one's
            total += c.kinds.count("win") * (
                gqa(c.kv_heads("win")) - gqa(c.n_kv_head)
                + c.n_head * c.window_sink)
        return total

    @property
    def passes_per_token(self) -> float:
        """Forward passes over a position for one generated token: 1
        autoregressively; of a block-diffusion model the block's
        ``denoising_steps`` passes (the most: a confident block needs fewer)
        and the one that commits it (as rows of the next block's first pass:
        the FLOPs of a pass, not a stream of the weights)."""
        return self.denoising_steps + 1 if self.block_length else 1

    def generate_flops_per_token(self, context: int = 0) -> float:
        """FLOPs of ONE generated token at ``context`` cached positions: 2 a
        parameter the token meets and 4 a cached position a head column, x
        ``passes_per_token``. What a profile or a service estimate divides a
        generation's time by, where ``flops_per_token`` is a TRAINED
        token's."""
        attended = self.n_attn_layers * context
        if self.own_window:             # a ring holds the window and no more
            attended += self.kinds.count("win") * min(context,
                                                      self.sliding_window)
        return self.passes_per_token * (
            2 * self.num_params(active=True)
            + 2 * self.n_head * (self.head_dim + self.v_dim) * attended)

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Megatron accounting (6N + 12·l·d·s), as in GPT2Config: GQA does not
        change the attention score/value FLOPs, only the KV projection (already
        inside N). N counts the experts a token meets, not all of them."""
        s = seq_len or self.n_positions
        # scores and values over the context: the softmax layers only, at the
        # heads' own width (a KDA layer's state costs the same at any length:
        # 6 dk dv a head a token forward, in N's order of magnitude, left out)
        return 6 * self.num_params(active=True) \
            + 12 * self.n_attn_layers * self.n_head * self.head_dim * s


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

    @property
    def block_decoding(self):
        """The model protocol's word to the inference engine on HOW it
        generates: None, one token a step from ``decode_step``; or, for a
        model that generates by diffusion over blocks, the block's length
        and how it is denoised (``common.BlockDecoding``), through
        ``block_step``."""
        c = self.config
        if not c.block_length:
            return None
        from deepspeed_tpu.models.common import BlockDecoding

        return BlockDecoding(c.block_length, c.denoising_steps, c.remasking,
                             float(c.confidence_threshold), c.mask_token_id)

    # ---------------------------------------------------------------- params
    def _init_mixer(self, keys, l: int, kind="attn") -> Dict[str, Any]:
        """``l`` softmax mixers, stacked: GQA (with its q/k norm and output
        gate where the configuration has them) or latent attention.
        ``kind``: ``"win"`` for window layers that hold leaves of their own
        (their KV head count, a ``sink`` logit a head)."""
        c = self.config
        d, s = c.n_embd, 0.02
        fold = jax.random.fold_in
        proj_scale = s / math.sqrt(2 * c.n_layer)   # residual-scaled (GPT-2)
        norm = lambda key, shape, scale: \
            jax.random.normal(key, shape, c.param_dtype) * scale
        ones = lambda *shape: jnp.ones(shape, c.param_dtype)
        if c.mla:
            h, n, r = c.n_head, c.qk_nope_head_dim, c.qk_rope_head_dim
            queries = dict(
                q_a_w=norm(keys[1], (l, d, c.q_lora_rank), s),
                q_a_norm_g=ones(l, c.q_lora_rank),
                q_b_w=norm(keys[2], (l, c.q_lora_rank, h * (n + r)), s)) \
                if c.q_lora_rank else dict(
                    q_w=norm(keys[1], (l, d, h * (n + r)), s))
            return dict(
                **queries,
                kv_a_w=norm(keys[3], (l, d, c.latent_dim), s),
                kv_a_norm_g=ones(l, c.kv_lora_rank),
                kv_b_k_w=norm(fold(keys[3], 1), (l, h, n, c.kv_lora_rank), s),
                kv_b_v_w=norm(fold(keys[3], 2),
                              (l, h, c.kv_lora_rank, c.v_head_dim), s),
                o_w=norm(keys[4], (l, h * c.v_head_dim, d), proj_scale))
        heads, kv = c.n_head * c.head_dim, c.kv_heads(kind)
        mixer = dict(q_w=norm(keys[1], (l, d, heads), s),
                     k_w=norm(keys[2], (l, d, kv * c.head_dim), s),
                     v_w=norm(keys[3], (l, d, kv * c.v_dim), s),
                     o_w=norm(keys[4], (l, c.n_head * c.v_dim, d),
                              proj_scale))
        if kind == "win" and c.window_sink:
            # N(0, 1): beside scores of about that spread it takes a share
            # of the mass one can see (a trained sink does)
            mixer.update(sink=norm(fold(keys[4], 1), (l, c.n_head), 1.0))
        if c.qk_norm == "head":
            mixer.update(q_norm_g=ones(l, c.head_dim),
                         k_norm_g=ones(l, c.head_dim))
        elif c.qk_norm:
            mixer.update(q_norm_g=ones(l, heads), k_norm_g=ones(l, c.kv_dim))
        if c.attn_gate:
            mixer.update(attn_gate_w=norm(fold(keys[1], 1), (l, d, heads), s))
        return mixer

    def _init_stack(self, keys, l: int, routed: bool,
                    mixer: bool = True) -> Dict[str, Any]:
        """``l`` layers of one kind, stacked: the mixer's leaves (``mixer``
        true or ``"attn"``: softmax; ``"kda"``: ``models/kda.py``'s; false:
        none, the model keeps its mixers in stacks of their own: a layer
        pattern), the norms' gains, then a dense MLP or the router, the held
        experts and the shared expert. ``keys``: ``init_params``' eight (a
        leaf that came later folds a number into one of them, so the older
        leaves draw what they always drew)."""
        c = self.config
        d = c.n_embd
        fold = jax.random.fold_in
        s = 0.02
        proj_scale = s / math.sqrt(2 * c.n_layer)   # residual-scaled (GPT-2)
        norm = lambda key, shape, scale: \
            jax.random.normal(key, shape, c.param_dtype) * scale
        ones = lambda *shape: jnp.ones(shape, c.param_dtype)
        blocks = {"attn_norm_g": ones(l, d), "mlp_norm_g": ones(l, d)}
        if mixer == "kda":
            from deepspeed_tpu.models import kda

            blocks.update(kda.init_leaves(c, fold(keys[1], 7), l, proj_scale))
        elif mixer:
            blocks.update(self._init_mixer(
                keys, l, "win" if mixer == "win" else "attn"))
        if c.sandwich_norm:
            blocks.update(post_attn_norm_g=ones(l, d),
                          post_mlp_norm_g=ones(l, d))
        if not routed:
            i = c.dense_intermediate_size if c.n_experts \
                else c.intermediate_size
            blocks.update(gate_w=norm(keys[5], (l, d, i), s),
                          up_w=norm(keys[6], (l, d, i), s),
                          down_w=norm(keys[7], (l, i, d), proj_scale))
            return blocks
        # an expert leaf one layer at a time: the generator's temporaries
        # are a layer's, not the 2.1 G elements of the whole leaf
        i, e = c.intermediate_size, c.n_held
        per_layer = lambda key, shape, scale: jax.lax.map(
            lambda k: norm(k, shape, scale), jax.random.split(key, l))
        blocks.update(
            router_w=norm(fold(keys[5], 1), (l, d, c.n_experts), s),
            expert_gate_w=per_layer(keys[5], (e, d, i), s),
            expert_up_w=per_layer(keys[6], (e, d, i), s),
            expert_down_w=per_layer(keys[7], (e, i, d), proj_scale))
        if c.router_bias:
            # not zeros: a selection by s + b then differs from one by s
            # from the first step on (what a trained model's bias does)
            blocks.update(router_bias=norm(fold(keys[5], 3),
                                           (l, c.n_experts), 0.01))
        if c.n_shared_experts:
            si = c.n_shared_experts * i
            blocks.update(shared_gate_w=norm(fold(keys[5], 2), (l, d, si), s),
                          shared_up_w=norm(fold(keys[6], 2), (l, d, si), s),
                          shared_down_w=norm(fold(keys[7], 2), (l, si, d),
                                             proj_scale))
        return blocks

    @property
    def stacked_params_key(self):
        """The layer-stacked subtrees of :meth:`init_params` (ZeRO judges
        their leaves a layer and states their gather: zero/partition.py);
        first the one every layer has."""
        c = self.config
        return ("blocks",) \
            + (("attn_blocks", "kda_blocks") if c.gqa_layers is not None else ()) \
            + (("attn_blocks", "win_blocks") if c.own_window else ()) \
            + (("dense_blocks",) if c.n_dense_layers else ())

    def init_params(self, rng) -> Dict[str, Any]:
        """``blocks``: the layers' leaves, stacked over the layers (with
        ``dense_blocks`` ahead of them where leading layers are dense). A
        model with a layer pattern keeps in ``blocks`` what every layer has
        (norms, router, experts), over ALL layers, and each kind of mixer in
        a stack of its own over the layers that have it: ``attn_blocks``
        (softmax) and ``kda_blocks`` (``models/kda.py``); its leading dense
        layers, all of one kind, hold that kind's leaves themselves. So
        does a model whose window layers hold leaves of their own:
        ``attn_blocks`` (full) and ``win_blocks``."""
        c = self.config
        keys = jax.random.split(rng, 8)
        norm = lambda key, shape: \
            jax.random.normal(key, shape, c.param_dtype) * 0.02
        hybrid = c.gqa_layers is not None
        params = {"wte": norm(keys[0], (c.vocab_size, c.n_embd)),
                  "blocks": self._init_stack(
                      keys, c.n_layer - c.n_dense_layers,
                      routed=bool(c.n_experts),
                      mixer=not (hybrid or c.own_window)),
                  "norm_g": jnp.ones((c.n_embd,), c.param_dtype)}
        if c.own_window:
            own = c.kinds[c.n_dense_layers:]
            params["attn_blocks"] = self._init_mixer(keys, own.count("attn"))
            params["win_blocks"] = self._init_mixer(
                jax.random.split(jax.random.fold_in(rng, 3), 8),
                own.count("win"), "win")
        if hybrid:
            from deepspeed_tpu.models import kda

            own = c.kinds[c.n_dense_layers:]
            params["attn_blocks"] = self._init_mixer(keys, own.count("attn"))
            params["kda_blocks"] = kda.init_leaves(
                c, jax.random.fold_in(rng, 2), own.count("kda"),
                0.02 / math.sqrt(2 * c.n_layer))
        if c.n_dense_layers:
            params["dense_blocks"] = self._init_stack(
                jax.random.split(jax.random.fold_in(rng, 1), 8),
                c.n_dense_layers, routed=False, mixer=c.dense_mixer)
        if not c.tie_embeddings:
            params["lm_head"] = norm(jax.random.fold_in(keys[0], 1),
                                     (c.n_embd, c.vocab_size))
        return params

    def _mixer_specs(self, kind="attn") -> Dict[str, Any]:
        c = self.config
        rep = lambda rank: P(*([None] * rank))
        if c.mla:
            # replicated: latent attention under tensor parallelism is open
            # (one latent row a position cannot be split by head)
            queries = dict(q_a_w=rep(3), q_a_norm_g=rep(2), q_b_w=rep(3)) \
                if c.q_lora_rank else dict(q_w=rep(3))
            return dict(**queries, kv_a_w=rep(3), kv_a_norm_g=rep(2),
                        kv_b_k_w=rep(4), kv_b_v_w=rep(4), o_w=rep(3))
        specs = dict(q_w=P(None, None, "tensor"), k_w=P(None, None, "tensor"),
                     v_w=P(None, None, "tensor"), o_w=P(None, "tensor", None))
        if c.qk_norm:
            specs.update(q_norm_g=rep(2), k_norm_g=rep(2))
        if c.attn_gate:
            specs.update(attn_gate_w=P(None, None, "tensor"))
        if kind == "win" and c.window_sink:
            specs.update(sink=P(None, "tensor"))
        return specs

    def _stack_specs(self, routed: bool, mixer: bool = True) -> Dict[str, Any]:
        c = self.config
        rep = lambda rank: P(*([None] * rank))
        blocks = {"attn_norm_g": rep(2), "mlp_norm_g": rep(2)}
        if mixer == "kda":
            from deepspeed_tpu.models import kda

            blocks.update(kda.leaf_specs())
        elif mixer:
            blocks.update(self._mixer_specs(
                "win" if mixer == "win" else "attn"))
        if c.sandwich_norm:
            blocks.update(post_attn_norm_g=rep(2), post_mlp_norm_g=rep(2))
        if not routed:
            blocks.update(gate_w=P(None, None, "tensor"),
                          up_w=P(None, None, "tensor"),
                          down_w=P(None, "tensor", None))
            return blocks
        blocks.update(router_w=rep(3), expert_gate_w=rep(4),
                      expert_up_w=rep(4), expert_down_w=rep(4))
        if c.router_bias:
            blocks.update(router_bias=rep(2))
        if c.n_shared_experts:
            blocks.update(shared_gate_w=P(None, None, "tensor"),
                          shared_up_w=P(None, None, "tensor"),
                          shared_down_w=P(None, "tensor", None))
        return blocks

    def param_partition_specs(self) -> Dict[str, Any]:
        """Megatron TP over the 'tensor' mesh axis: q/k/v/gate/up column
        parallel, o/down row parallel, vocab-sharded embedding. The routed
        experts are replicated (``experts_held`` says which of the router's
        experts a chip's leaves hold; the exchange of rows between chips is
        ROADMAP R1's open half), and so are a latent-attention mixer and a
        KDA mixer (``models/kda.py::leaf_specs``)."""
        c = self.config
        hybrid = c.gqa_layers is not None
        specs = {"wte": P("tensor", None),
                 "blocks": self._stack_specs(
                     bool(c.n_experts), mixer=not (hybrid or c.own_window)),
                 "norm_g": P(None)}
        if c.own_window:
            specs["attn_blocks"] = self._mixer_specs()
            specs["win_blocks"] = self._mixer_specs("win")
        if hybrid:
            from deepspeed_tpu.models import kda

            specs["attn_blocks"] = self._mixer_specs()
            specs["kda_blocks"] = kda.leaf_specs()
        if c.n_dense_layers:
            specs["dense_blocks"] = self._stack_specs(False,
                                                      mixer=c.dense_mixer)
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
        rep = self.config.n_head // t.shape[2]
        return t if rep == 1 else jnp.repeat(t, rep, axis=2)

    def _causal(self, q, k, v, window=None, **sink):
        """The trunk's attention on full-head q, k, v: the shared dispatch
        (models/common.py: sequence-parallel → flash → einsum). ``sink``: a
        window layer's sink logits, where it holds them."""
        from deepspeed_tpu.models.common import causal_attention

        c = self.config
        if c.block_length:
            from deepspeed_tpu.models.common import local_causal_attention

            return local_causal_attention(q, k, v, c.use_flash_attention,
                                          block=c.block_length)
        return causal_attention(q, k, v, use_flash=c.use_flash_attention,
                                sequence_parallel=c.sequence_parallel,
                                window=window, **sink)

    def _window(self, kind):
        """A layer kind's causal window: ``sliding_window`` keys for a
        window layer, None for every other."""
        return self.config.sliding_window if kind == "win" else None

    def _rope_of(self, kind, cos_sin):
        """A layer kind's rotary tables: the model's (its own kind's, where
        the kinds have a base each), or none for the full layers of a
        pattern whose window layers alone are rotated."""
        if isinstance(cos_sin, dict):
            return cos_sin[kind]
        return (None, None) if kind == "attn" and not self.config.global_rope \
            else cos_sin

    def _cache_group(self, kind):
        """Which of the cache's arrays a layer of ``kind`` keeps its part of
        a sequence in: KDA's state, a window layer's ring where the model
        gives it one, else the rows a position."""
        return kind if kind == "kda" or (
            kind == "win" and self.config.own_window) else "attn"

    @staticmethod
    def _sink_of(blk):
        return {"sink": blk["sink"]} if "sink" in blk else {}

    def _embed(self, params, ids):
        c = self.config
        with scope("embed"):
            x = params["wte"].astype(c.dtype)[ids]
            if c.embed_scale != 1.0:
                x = (x.astype(jnp.float32) * c.embed_scale).astype(c.dtype)
            return x

    def _stacks(self, params, split_experts=True, by_index=False):
        """The trunk's stacks in order, each as ``(xs, experts, first, view,
        pattern)`` (``pattern``: one period of the stack's kinds of layer):
        ``xs`` is what a scan over the stack's PERIODS of the layer pattern
        slices, ``experts`` the stacked ``(L, E, ...)`` expert leaves it must
        not (None for a dense stack, and where ``split_experts`` is false:
        the trunk under ``loss`` slices every leaf a layer), ``first`` the index of the stack's
        first layer in the model and in the cache, and ``view(per, j, i)`` the
        block — a dict of one layer's leaves — of layer j + i of a period
        ``per`` (j static, i a traced offset inside a run of one kind). With one kind of mixer a period is a layer and ``xs`` the
        stacked blocks themselves. With a layer pattern ``xs`` holds the
        leaves every layer has regrouped ``(L / p, p, ...)`` (a reshape of
        the leading axis: no copy) and, where the kinds of mixer have leaves
        of their own (KDA beside softmax), each kind's own stack regrouped
        by what a period holds of it (the leading dense layers of such a
        model are of one kind and hold its leaves themselves: a period is a
        layer). Window and full softmax layers hold
        the same leaves: they stay in ``blocks`` / ``dense_blocks``, and
        each of the two stacks walks its own phase of the pattern.
        ``by_index`` (the cached walk): where window layers hold leaves of
        their own, each softmax kind's stack stays WHOLE outside ``xs`` and
        ``view(per, j, i, n)`` indexes it by the layer's place in it, from
        the period ``n``: a slice of a period's mixers taken by the outer
        scan and indexed again by a run's loop is a COPY of them a period a
        decode step (5 x (4096, 12288) + 5 x (8192, 4096) bf16 = 0.84 GB,
        twice a token: the first chip run's 10.8 ms a token against 3.9 of
        weights, PERF.md PR 49); one dynamic slice of one layer is read in
        place by the matmul that takes it."""
        c = self.config
        blocks, experts = self._split_experts(params["blocks"]) \
            if split_experts else (params["blocks"], None)
        group = lambda tree, each: jax.tree.map(
            lambda a: a.reshape(a.shape[0] // each, each, *a.shape[1:]), tree)
        stacks = []
        # (leaves, expert leaves, first layer, layers, mixers in stacks of
        # their own: the routed stack of a KDA pattern, or of one whose
        # window layers hold leaves of their own)
        for held, exp, first, count, own in (
                (params.get("dense_blocks"), None, 0, c.n_dense_layers, False),
                (blocks, experts, c.n_dense_layers,
                 c.n_layer - c.n_dense_layers,
                 c.gqa_layers is not None or c.own_window)):
            if not count:
                continue
            pattern = c.stack_pattern(first, count)
            if own and by_index and c.own_window:
                whole = {kind: params[f"{kind}_blocks"]
                         for kind in set(pattern)}

                def view(per, j, i=0, n=0, pattern=pattern, whole=whole):
                    kind = pattern[j]
                    at = n * pattern.count(kind) + pattern[:j].count(kind) + i
                    return {**jax.tree.map(lambda a: a[j + i], per["all"]),
                            **jax.tree.map(lambda a: a[at], whole[kind])}

                stacks.append(({"all": group(held, len(pattern))}, exp, first,
                               view, pattern))
            elif own:
                xs = {"all": group(held, len(pattern)),
                      **{kind: group(params[f"{kind}_blocks"],
                                     pattern.count(kind))
                         for kind in sorted(set(pattern))}}

                def view(per, j, i=0, n=None, pattern=pattern):
                    kind = pattern[j]
                    mine = pattern[:j].count(kind)
                    return {**jax.tree.map(lambda a: a[j + i], per["all"]),
                            **jax.tree.map(lambda a: a[mine + i], per[kind])}

                stacks.append((xs, exp, first, view, pattern))
            elif len(pattern) == 1:
                stacks.append((held, exp, first,
                               lambda per, j, i=0, n=None: per, pattern))
            else:
                stacks.append((
                    group(held, len(pattern)), exp, first,
                    lambda per, j, i=0, n=None: jax.tree.map(
                        lambda a: a[j + i], per), pattern))
        return stacks

    def _layer_at(self, pattern, n, j, i=0):
        """For layer j + i of period ``n`` (n, i traced) of a stack that
        walks ``pattern``: (its index in the stack, its index among the
        stack's layers that keep what it keeps of a sequence — the layer
        axis of those cache arrays (``_cache_group``): rows a position for
        the softmax kinds, full or window, a state for KDA, a ring for
        window layers that have one)."""
        if len(pattern) == 1:
            return n, n
        group = self._cache_group
        same = [group(k) == group(pattern[j]) for k in pattern]
        return n * len(pattern) + j + i, \
            n * sum(same) + sum(same[:j]) + i

    def _rope(self, positions):
        """(cos, sin), or (None, None) for a model without a positional
        embedding (``use_rope`` false)."""
        c = self.config
        if not c.use_rope:
            return None, None
        with scope("attn/qkv"):
            if c.window_rope_theta is not None:     # a base a kind
                return {kind: _rope_cos_sin(positions, c.rope_dim, theta,
                                            c.rope_scaling)
                        for kind, theta in (("attn", c.rope_theta),
                                            ("win", c.window_rope_theta))}
            return _rope_cos_sin(positions, c.rope_dim, c.rope_theta,
                                 c.rope_scaling)

    _rotate = staticmethod(apply_rope_leading)

    def _block_qkv(self, x, blk, cos, sin):
        """One GQA block's q, k, v for the current x, rotated where the
        model has a rotary embedding. The KV heads are as many as the
        block's ``k_w`` makes (a window layer may hold another number than a
        full one), v's columns a head what its ``v_w`` makes."""
        c = self.config
        B, T, D = x.shape
        n_kv = blk["k_w"].shape[-1] // c.head_dim
        with scope("attn/qkv"):
            h = self._rms_norm(x, blk["attn_norm_g"])
            hd = h.astype(c.dtype)
            q = hd @ blk["q_w"].astype(hd.dtype)
            k = hd @ blk["k_w"].astype(hd.dtype)
            per_head = "q_norm_g" in blk \
                and blk["q_norm_g"].shape[-1] == c.head_dim
            if "q_norm_g" in blk and not per_head:
                # OLMoE: over the whole projection, before the heads are split
                q = self._rms_norm(q, blk["q_norm_g"])
                k = self._rms_norm(k, blk["k_norm_g"])
            q = q.reshape(B, T, c.n_head, c.head_dim)
            k = k.reshape(B, T, n_kv, c.head_dim)
            if per_head:
                # gains (head_dim,): each head's own 128 columns (with ONE
                # head the two forms are one)
                q = self._rms_norm(q, blk["q_norm_g"])
                k = self._rms_norm(k, blk["k_norm_g"])
            v = (hd @ blk["v_w"].astype(hd.dtype)).reshape(B, T, n_kv, -1)
            if c.value_scale != 1.0:
                v = v * jnp.asarray(c.value_scale, v.dtype)
            if cos is None:
                return q, k, v
            return self._rotate(q, cos, sin), self._rotate(k, cos, sin), v

    def _gated(self, attn, x, blk):
        """The softmax output (B, T, H, Dh) times ``sigmoid(h attn_gate_w)``,
        one gate a head channel, from the block's normed input h (the one
        q, k and v came from) — where the block holds that leaf."""
        if "attn_gate_w" not in blk:
            return attn
        with scope("attn/out"):
            hd = self._rms_norm(x, blk["attn_norm_g"]).astype(self.config.dtype)
            gate = jax.nn.sigmoid((hd @ blk["attn_gate_w"].astype(hd.dtype)
                                   ).astype(jnp.float32)).reshape(attn.shape)
            return (attn * gate).astype(attn.dtype)

    def _block_latent(self, x, blk, cos, sin):
        """A latent-attention block's queries and its ONE cached row a
        position: ``q_nope`` (B, T, H, nope), ``q_rope`` (B, T, H, rope)
        rotated, ``latent`` (B, T, 1, C + rope) = ``[RMSNorm(c_kv) |
        RoPE(k_rope)]`` — the rotary key is one for all heads. The queries
        through the low-rank bottleneck, or straight from ``q_w`` where the
        block holds that; without a rotary embedding (``cos`` None) the
        ``rope`` columns are carried as they are."""
        c = self.config
        B, T, _ = x.shape
        n, C = c.qk_nope_head_dim, c.kv_lora_rank
        rotate = (lambda t: t) if cos is None \
            else (lambda t: apply_rope(t, cos, sin))
        with scope("attn/qkv"):
            hd = self._rms_norm(x, blk["attn_norm_g"]).astype(c.dtype)
            if "q_w" in blk:
                q = hd @ blk["q_w"].astype(hd.dtype)
            else:
                cq = self._rms_norm(hd @ blk["q_a_w"].astype(hd.dtype),
                                    blk["q_a_norm_g"])
                q = cq @ blk["q_b_w"].astype(hd.dtype)
            q = q.reshape(B, T, c.n_head, n + c.qk_rope_head_dim)
            kv = hd @ blk["kv_a_w"].astype(hd.dtype)         # (B, T, C + rope)
            latent = jnp.concatenate(
                [self._rms_norm(kv[..., None, :C], blk["kv_a_norm_g"]),
                 rotate(kv[..., None, C:])], axis=-1)
            return q[..., :n], rotate(q[..., n:]), latent

    def _prefill_form(self, kind, t: int, cos):
        """The form a prefill of ``t`` positions gives a softmax layer of
        ``kind`` whose rotary table is ``cos`` (None: not rotated):
        ``models/common.py::prefill_attention_form`` for a GQA layer;
        latent attention expands a key a head and takes the plain form."""
        from deepspeed_tpu.models.common import prefill_attention_form

        c = self.config
        if c.mla:
            return "plain"
        return prefill_attention_form(
            t, c.head_dim, None if cos is None else cos.shape[-1],
            c.use_flash_attention, self._window(kind), c.block_length or None)

    def _attend(self, x, blk, cos_sin, attention, fused=None):
        """A block's causal self-attention over the whole of x (the trunk,
        prefill), by the mixer whose leaves it holds -> (attn (B, T, H,
        Dv), the rows a cache keeps of it: GQA (k, v) at the KV heads,
        latent attention (latent,)). ``attention`` takes full-head q, k, v.
        ``fused`` (a prefill's GQA layer in the fused form: its window,
        block and sink): q, k and v go to the kernel as the projections
        made them — K rotated in its one small pass, since the cache keeps
        it so; q's rotation, the scale and the KV heads' grouping are the
        kernel's (``models/common.py::prefill_attention``)."""
        c = self.config
        if "kv_a_w" not in blk and fused is None:
            q, k, v = self._block_qkv(x, blk, *cos_sin)
            with scope("attn/core"):
                attn = attention(q, self._repeat_kv(k), self._repeat_kv(v))
            return self._gated(attn, x, blk), (k, v)
        if "kv_a_w" not in blk:
            from deepspeed_tpu.models.common import prefill_attention

            cos, sin = cos_sin
            q, k, v = self._block_qkv(x, blk, None, None)
            if cos is not None:
                with scope("attn/qkv"):
                    k = self._rotate(k, cos, sin)
            with scope("attn/core"):
                attn = prefill_attention(q, k, v, cos, sin, **fused)
            return self._gated(attn, x, blk), (k, v)
        # un-absorbed: every head's key and value expanded from the latent
        # row (q.k at nope + rope columns, v at its own width); absorbing
        # here would cost (C + rope + C) / (nope + rope + v) = 3.4 x the FLOPs
        q_nope, q_rope, latent = self._block_latent(x, blk, *cos_sin)
        with scope("attn/qkv"):
            c_kv, k_rope = latent[:, :, 0, :c.kv_lora_rank], \
                latent[..., c.kv_lora_rank:]
            k_nope = jnp.einsum("btc,hnc->bthn", c_kv,
                                blk["kv_b_k_w"].astype(c_kv.dtype))
            v = jnp.einsum("btc,hcd->bthd", c_kv,
                           blk["kv_b_v_w"].astype(c_kv.dtype))
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
        with scope("attn/core"):
            return attention(q, k, v), (latent,)

    def _attend_cached(self, x, blk, cos_sin, caches, layer, pos,
                       window=None, early: int = 0, ring: bool = False):
        """The new token's attention over the cache (decode): its rows are
        written into slot ``pos`` of ``layer``, then attended with the rest
        — under a ``window`` with the last ``window`` slots (the cache holds
        the whole context for every layer; ``cached_decode_attention`` takes
        its einsum for a window: the decode kernel carries none) or, where
        the caches are a ``ring`` of ``window`` slots, written into slot
        ``pos % window`` and attended with all that is valid of the ring
        (the decode kernel, K and V at their own widths, the block's sink).
        -> (attn (B, 1, H, Dv), the caches). x may hold a BLOCK of T
        positions (``block_step``): their rows go into slots ``pos .. pos +
        T - 1`` and every one of them attends over slots ``0 .. pos + T -
        1``, but the first ``early`` of them (a block carried before the
        step's own) over slots ``0 .. pos + early - 1``; -> attn (B, T, H,
        Dv)."""
        from deepspeed_tpu.models.common import (cached_decode_attention,
                                                 kv_cache_write,
                                                 kv_ring_write,
                                                 latent_decode_attention)

        c = self.config
        if "kv_a_w" not in blk:
            q, k, v = self._block_qkv(x, blk, *cos_sin)     # q (B,1,H,Dh)
            write = kv_ring_write if ring else kv_cache_write
            with scope("attn/core"):
                cache_k = write(caches[0], k, layer, pos)
                cache_v = write(caches[1], v, layer, pos)
                # GQA decode against the KV-head cache — repeated K/V are
                # never materialized (grouped einsum or the Pallas streaming
                # kernel)
                if x.shape[1] > 1:
                    attn = cached_decode_attention(
                        q, cache_k, cache_v, layer, pos + x.shape[1] - 1,
                        c.n_kv_head,
                        early=(early, pos + early - 1) if early else None)
                    return self._gated(attn, x, blk), (cache_k, cache_v)
                # the KV heads the block's k_w has; a ring is attended whole,
                # at what is valid of it; v's own width and the block's sink
                # where the model has them
                wide = {} if c.v_dim == c.head_dim else {"v_dim": c.v_dim}
                attn = cached_decode_attention(
                    q[:, 0], cache_k, cache_v, layer,
                    jnp.minimum(pos, window - 1) if ring else pos,
                    k.shape[2], window=None if ring else window, **wide,
                    **self._sink_of(blk))
            return self._gated(attn[:, None], x, blk), (cache_k, cache_v)
        # absorbed: q.k_nope = (q_nope W_UK^T).c_kv and p.v = (p.c_kv) W_UV,
        # so the scores and the weighted sum are over the latent rows
        # themselves, read once for all heads
        q_nope, q_rope, latent = self._block_latent(x, blk, *cos_sin)
        with scope("attn/qkv"):
            q_lat = jnp.einsum("bhn,hnc->bhc", q_nope[:, 0],
                               blk["kv_b_k_w"].astype(q_nope.dtype))
        with scope("attn/core"):
            cache = kv_cache_write(caches[0], latent, layer, pos)
            o_lat = latent_decode_attention(
                jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1), cache, layer,
                pos, v_width=c.kv_lora_rank,
                scale=1.0 / math.sqrt(c.qk_nope_head_dim
                                      + c.qk_rope_head_dim))
        with scope("attn/out"):
            attn = jnp.einsum("bhc,hcd->bhd", o_lat,
                              blk["kv_b_v_w"].astype(o_lat.dtype))
        return attn[:, None], (cache,)

    EXPERT_LEAVES = ("expert_gate_w", "expert_up_w", "expert_down_w")

    @staticmethod
    def _swiglu(h, gate_w, up_w, down_w):
        gate = h @ gate_w.astype(h.dtype)
        up = h @ up_w.astype(h.dtype)
        return (jax.nn.silu(gate) * up) @ down_w.astype(h.dtype)

    def _mlp(self, h, blk, stacked=None, layer=None):
        """The block's MLP on the normed h (B, T, D) -> (out, router
        statistics or None). Dense SwiGLU where the block holds ``gate_w``;
        routed experts where it holds ``router_w``: the expert leaves are the
        block's own (E, ...) slices, or ``stacked`` (L, E, ...) leaves with
        the traced ``layer`` (the serving programs: see the module's
        docstring), E the experts held here; plus the shared expert where
        it holds ``shared_gate_w``. Statistics: pairs routed to each held
        expert (E,) int32 and the router's scores summed over the tokens
        (n_experts,) float32; where the block holds a selection bias
        (``router_bias``) also the pairs routed to EACH of the router's
        experts (n_experts,) int32, held here or not: what the balancing
        rule reads."""
        if "router_w" not in blk:
            return self._swiglu(h, blk["gate_w"], blk["up_w"],
                                blk["down_w"]), None
        from deepspeed_tpu.moe.dropless import route_topk, routed_mlp

        c = self.config
        B, T, D = h.shape
        tokens = h.reshape(B * T, D)
        # a softmax router is called with the four arguments it always had:
        # tests/benchmark/test_olmoe_family.py lays a wrapper of exactly that
        # signature over route_topk, and a benchmark file is not this
        # change's to edit
        scored = {} if (c.router_scoring, c.routed_scaling_factor) == \
            ("softmax", 1.0) else {"scoring": c.router_scoring,
                                   "scale": c.routed_scaling_factor}
        if "router_bias" in blk:
            scored["bias"] = blk["router_bias"]
        probs, weights, experts = route_topk(
            tokens, blk["router_w"], c.n_experts_per_tok, c.norm_topk_prob,
            **scored)
        leaves = stacked if stacked is not None else blk
        out, sizes = routed_mlp(
            tokens, weights, experts,
            *(leaves[n] for n in self.EXPERT_LEAVES), layer=layer,
            first=c.experts_held[0] if c.experts_held else None,
            n_experts=c.n_experts)
        out = out.reshape(B, T, D)
        if "shared_gate_w" in blk:
            with scope("moe/shared"):
                out = out + self._swiglu(
                    h, blk["shared_gate_w"], blk["shared_up_w"],
                    blk["shared_down_w"])
        stats = (sizes, jnp.sum(probs, axis=0))
        if "router_bias" in blk:
            with scope("moe/router"):
                stats += (jnp.bincount(experts.reshape(-1),
                                       length=c.n_experts).astype(jnp.int32),)
        return out, stats

    def _split_experts(self, blocks):
        """(the leaves a layer scan may slice, the stacked expert leaves it
        must not — None for a dense stack)."""
        if "router_w" not in blocks:
            return blocks, None
        return ({n: v for n, v in blocks.items()
                 if n not in self.EXPERT_LEAVES},
                {n: blocks[n] for n in self.EXPERT_LEAVES})

    def _block_finish(self, x, blk, attn, stacked=None, layer=None):
        """-> (x after the attention output and the MLP, router statistics
        or None). Pre-norm; sandwich norm (each branch's output normalised
        too) where the block holds the two post-norm gains."""
        B, T, _ = x.shape
        with scope("kda/out" if "kda_qkv_w" in blk else "attn/out"):
            a = attn.reshape(B, T, -1) @ blk["o_w"].astype(x.dtype)
            if "post_attn_norm_g" in blk:
                a = self._rms_norm(a, blk["post_attn_norm_g"])
            x = x + a
        with scope("moe" if "router_w" in blk else "mlp"):
            h = self._rms_norm(x, blk["mlp_norm_g"])
            out, stats = self._mlp(h, blk, stacked, layer)
            if "post_mlp_norm_g" in blk:
                out = self._rms_norm(out, blk["post_mlp_norm_g"])
            return x + out, stats

    def _block(self, x, blk, cos_sin, kind="attn"):
        """One layer of the trunk: a new sequence, nothing kept of it.
        ``kind``: what the layer pattern says of it where its leaves cannot
        (a window layer holds a full layer's)."""
        if "kda_qkv_w" in blk:
            from deepspeed_tpu.models import kda

            c = self.config
            with scope("kda"):
                fresh = kda.init_state(c, 1, x.shape[0])
                attn, _, _ = kda.mix(
                    c, self._rms_norm(x, blk["attn_norm_g"]), blk,
                    fresh["kda_conv"][0], fresh["kda_state"][0],
                    differentiable=True)
        else:
            attn, _ = self._attend(
                x, blk, self._rope_of(kind, cos_sin),
                functools.partial(self._causal, window=self._window(kind),
                                  **self._sink_of(blk)))
        return self._block_finish(x, blk, attn)

    def _trunk(self, params, input_ids, rng=None, with_router_stats=False):
        c = self.config
        B, T = input_ids.shape
        x = self._embed(params, input_ids)
        cos_sin = self._rope(jnp.arange(T))
        block_fn = {kind: remat_wrap(
            functools.partial(self._block, kind=kind), c.remat)
            for kind in set(c.kinds)}

        # the blocks' walk: a lax.scan (models/common.py::layer_scan); under
        # ZeRO-3 each block gathers its own weights inside remat_wrap
        from deepspeed_tpu.models.common import layer_scan

        for xs, _, _, view, pattern in self._stacks(params,
                                                    split_experts=False):

            def scan_body(carry, per):
                if len(pattern) == 1:
                    return block_fn[pattern[0]](carry, per, cos_sin)
                stats = []
                for j, kind in enumerate(pattern):
                    carry, st = block_fn[kind](carry, view(per, j), cos_sin)
                    stats.append(st)
                return carry, None if stats[0] is None else \
                    jax.tree.map(lambda *a: jnp.stack(a), *stats)

            with scope("layers"):
                x, stats = layer_scan(scan_body, x, xs)  # the last: routed
        if stats is not None and len(pattern) > 1:      # (L / p, p, ..) -> L
            stats = jax.tree.map(
                lambda a: a.reshape(-1, *a.shape[2:]), stats)
        with scope("head"):
            x = self._rms_norm(x, params["norm_g"])
        return (x, stats) if with_router_stats else x

    def hidden_states(self, params, input_ids, rng=None):
        return self._trunk(params, input_ids, rng)

    def apply(self, params, input_ids, rng=None):
        """input_ids (B, T) int32 → logits (B, T, V) fp32."""
        x = self._trunk(params, input_ids, rng)
        with scope("head"):
            return (x @ self._head(params, x.dtype)).astype(jnp.float32)

    def loss(self, params, batch, rng=None):
        """Next-token cross entropy with the chunked vocab projection
        (models/common.py); a routed model adds ``router_aux_loss_coef`` x
        the load-balancing loss over every routed layer and position."""
        return self.loss_and_aux(params, batch, rng)[0]

    def loss_and_aux(self, params, batch, rng=None):
        """-> (:meth:`loss`, what the step's routing made beside it: None,
        or for a model with a selection bias ``expert_pairs`` (L routed,
        n_experts) — the pairs each of the router's experts was routed,
        which :meth:`apply_rule` reads — ``held_pairs`` (L routed, E held),
        ``overflow_calls`` (of the L routed layers' calls, those that held
        more pairs than a share's buffer: ``moe/dropless.py::share_capacity``)
        and ``bias_abs_max``, for the step's ``moe/expert_tokens`` instant)."""
        from deepspeed_tpu.models.common import chunked_lm_loss, parse_lm_batch

        c = self.config
        if c.block_length:
            raise NotImplementedError(
                "a model that generates by diffusion over blocks is trained "
                "on its noise schedule, which this repository has no source "
                "for: the next-token loss is not its loss")
        ids, labels, mask = parse_lm_batch(batch)
        x, stats = self._trunk(params, ids, rng, with_router_stats=True)
        with scope("head"):
            x = x[:, :-1]
            head = self._head(params, x.dtype)
            loss = chunked_lm_loss(x, head, labels[:, 1:],
                                   mask[:, 1:] if mask is not None else None,
                                   remat=c.remat_loss_chunks)
        if stats is not None and c.router_aux_loss_coef:
            from deepspeed_tpu.moe.dropless import load_balancing_loss

            with scope("moe/router"):
                loss = loss + c.router_aux_loss_coef * load_balancing_loss(
                    *stats[:2], n_tokens=ids.size)
        if not c.router_bias:
            return loss, None
        from deepspeed_tpu.moe.dropless import share_overflowed

        with scope("moe/router"):
            return loss, {
                "expert_pairs": stats[2], "held_pairs": stats[0],
                "overflow_calls": jnp.sum(share_overflowed(
                    stats[0], ids.size * c.n_experts_per_tok, c.n_experts),
                    dtype=jnp.int32),
                "bias_abs_max": jnp.max(jnp.abs(
                    params["blocks"]["router_bias"].astype(jnp.float32)))}

    # ------------------------------------- leaves a rule moves (the engine)
    def ruled_leaves(self, params):
        """The model protocol's hook for leaves the optimizer leaves alone:
        None, or a tree like ``params`` of bools, true at a leaf that no
        gradient, weight decay or moment moves and :meth:`apply_rule` does
        (the engine: ``runtime/engine.py::_apply_grads``)."""
        if not self.config.router_bias:
            return None
        ruled = jax.tree.map(lambda _: False, params)
        ruled["blocks"]["router_bias"] = True
        return ruled

    def apply_rule(self, params, aux):
        """``params`` (the optimizer's targets: the float32 masters) with the
        ruled leaves moved by the step's ``aux`` (:meth:`loss_and_aux`): the
        selection bias by the aux-loss-free balancing rule, once a step, a
        routed layer a row."""
        from deepspeed_tpu.moe.dropless import balance_bias

        with scope("optimizer/router_bias"):
            blocks = dict(params["blocks"])
            blocks["router_bias"] = balance_bias(
                blocks["router_bias"], aux["expert_pairs"],
                self.config.router_bias_rate).astype(
                    blocks["router_bias"].dtype)
            return {**params, "blocks": blocks}

    def report_aux(self, step, aux):
        """The step's routing counts (host values of :meth:`loss_and_aux`'s
        second result) as the serving front-end reports a request's: the
        counter ``moe/expert_tokens`` and an instant of that name; beside
        them the routed-layer calls that held more than a share's buffer
        (counter ``moe/share_overflow_calls``, the instant's
        ``overflow_calls``)."""
        from deepspeed_tpu import telemetry

        c = self.config
        counts = np.asarray(aux["held_pairs"])
        held = c.experts_held or (0, c.n_experts)
        overflow = int(aux["overflow_calls"])
        registry = telemetry.get_registry()
        registry.counter("moe/expert_tokens").inc(float(counts.sum()))
        registry.counter("moe/share_overflow_calls").inc(float(overflow))
        telemetry.get_tracer().instant(
            "moe/expert_tokens", cat="moe", trace=step, step=step,
            counts=counts.tolist(), held_first=int(held[0]),
            held=int(held[1]),
            routed_pairs=int(np.asarray(aux["expert_pairs"]).sum()),
            overflow_calls=overflow,
            bias_abs_max=float(aux["bias_abs_max"]))

    # ------------------------------------------------------------- inference
    def _cache_layout(self):
        """(heads folded into a row, values a head, the cache's row arrays in
        ``_attend``'s order): K and V at the KV heads, or ONE latent row a
        position, ``[c_kv | k_rope]``, for all heads."""
        c = self.config
        if c.mla:
            return 1, c.latent_dim, ("kv",)
        return (c.n_kv_head, c.head_dim if c.v_dim == c.head_dim
                else (c.head_dim, c.v_dim), ("k", "v"))

    def _ring_layout(self):
        """(window layers, slots, KV heads, a head's columns in K and in V
        rows) of the rings a model keeps for window layers of their own."""
        c = self.config
        return (c.kinds.count("win"), c.sliding_window, c.kv_heads("win"),
                (c.head_dim, c.v_dim))

    def _cache_names(self):
        """The cache's arrays that ride the layer scan's carry."""
        rows = self._cache_layout()[2]
        if self.config.own_window:
            from deepspeed_tpu.models.common import CACHE_RING_ROWS

            return rows + CACHE_RING_ROWS
        if self.config.gqa_layers is None:
            return rows
        from deepspeed_tpu.models import kda

        return rows + kda.STATE_LEAVES

    def init_cache(self, batch_size: int, max_len: int):
        """KV cache holds only the KV heads, folded into lane-dense rows:
        (L, B, max_len, W) (models/common.py ``init_kv_cache``) — the GQA
        memory win over the reference's full-head InferenceContext workspace
        (csrc/transformer/inference/includes/inference_context.h:287). A
        latent-attention model caches ONE array ``kv`` of that form, nothing
        per head. A model with a layer pattern holds TWO kinds of cache in
        the one dict: those rows over its SOFTMAX layers only, ``(L_softmax,
        B, max_len, W)``, and what each KDA layer keeps of a sequence
        whatever its length (``models/kda.py::init_state``): ``kda_state``
        (L_kda, B, H, dk, dv) float32 and ``kda_conv`` (L_kda, B, taps - 1,
        3 H dk). A routed model's cache also carries ``expert_tokens``
        (L_routed, E held) int32: the (token, expert) pairs each held expert
        has been given since the prompt's first token — summed by the
        compiled programs themselves (the front-end reads them back when a
        request resolves). A block-diffusion model's carries
        ``block_passes`` (4,) int32, summed the same way (what its block
        steps have run: ``common.BLOCK_COUNTS``), and ``pending`` (B, Lb)
        int32: the final tokens of the block at ``pos .. pos + Lb - 1`` that
        is finished but not committed, for the next block's first pass to
        carry (``block_step(pending=)``); zeros until a block is. A model
        whose window layers hold leaves of their own keeps ``k`` / ``v`` over
        its FULL layers only and, for the window layers, ``win_k`` /
        ``win_v`` (L_win, B, sliding_window, W): a ring, the same bytes
        whatever ``max_len`` (``common.init_kv_ring``)."""
        from deepspeed_tpu.models.common import init_kv_cache, init_kv_ring

        c = self.config
        n_kv, dim, rows = self._cache_layout()
        cache = init_kv_cache(c.n_attn_layers, batch_size, max_len, n_kv,
                              dim, c.dtype, rows=rows)
        if c.own_window:
            layers, slots, kv, dims = self._ring_layout()
            cache.update(init_kv_ring(layers, batch_size, slots, kv, dims,
                                      c.dtype))
        if c.gqa_layers is not None:
            from deepspeed_tpu.models import kda

            cache.update(kda.init_state(c, c.n_layer - c.n_attn_layers,
                                        batch_size))
        if c.n_experts:
            cache["expert_tokens"] = jnp.zeros((c.n_moe_layers, c.n_held),
                                               jnp.int32)
        if c.block_length:
            cache["block_passes"] = jnp.zeros((4,), jnp.int32)
            cache["pending"] = jnp.zeros((batch_size, c.block_length),
                                         jnp.int32)
        return cache

    def cache_partition_specs(self):
        from deepspeed_tpu.models.common import kv_cache_partition_specs

        n_kv, dim, rows = self._cache_layout()
        specs = kv_cache_partition_specs(n_kv, dim, rows=rows)
        if self.config.own_window:
            from deepspeed_tpu.models.common import CACHE_RING_ROWS

            _, _, kv, dims = self._ring_layout()
            specs.update(kv_cache_partition_specs(kv, dims,
                                                  rows=CACHE_RING_ROWS))
        if self.config.gqa_layers is not None:
            from deepspeed_tpu.models import kda

            specs.update(kda.state_specs())
        if self.config.n_experts:
            specs["expert_tokens"] = P()
        if self.config.block_length:
            specs["block_passes"] = specs["pending"] = P()
        return specs

    def _mix_cached(self, x, blk, cos_sin, caches, at, pos, attention=None,
                    kind="attn", early: int = 0):
        """A layer's mixer on x against the cache, by the leaves the block
        holds -> (what ``_block_finish`` takes, the caches). ``caches``: the
        arrays of ``_cache_names`` (the rows a position first); ``at``: the
        layer's index in its own kind's arrays. ``attention`` (prefill): x is
        a whole prompt, written from slot 0 on; None (decode): x is the one
        new position ``pos``. A KDA layer continues the window and the state
        the cache holds for it — zeros for a new sequence — and puts back
        what the last position left. ``kind``: the layer's, where its leaves
        cannot say it (a window layer: the same rows in the cache, a window
        over them; or, in a model that gives it one, a ring of its own: the
        two arrays after the rows). ``early``: ``_attend_cached``'s."""
        n_rows = len(self._cache_layout()[2])
        if "kda_qkv_w" not in blk:
            cos_sin, window = self._rope_of(kind, cos_sin), self._window(kind)
            ring = self._cache_group(kind) == "win"
            lo = n_rows if ring else 0
            if attention is None:
                attn, rows = self._attend_cached(
                    x, blk, cos_sin, caches[lo:lo + n_rows], at, pos, window,
                    early, ring)
            else:
                from deepspeed_tpu.models.common import (kv_cache_write,
                                                         kv_ring_write)

                masks = {"window": window, **self._sink_of(blk)}
                fused = self._prefill_form(
                    kind, x.shape[1], cos_sin[0]) == "fused"
                attn, kept = self._attend(
                    x, blk, cos_sin, attention if window is None
                    else functools.partial(attention, **masks),
                    {**masks, "block": self.config.block_length or None}
                    if fused else None)
                write = kv_ring_write if ring else kv_cache_write
                with scope("attn/core"):
                    rows = tuple(write(held, t, at, 0)
                                 for held, t in zip(caches[lo:], kept))
            return attn, caches[:lo] + rows + caches[lo + n_rows:]
        from deepspeed_tpu.models import kda

        states, tails = caches[n_rows:]
        layer_of = lambda a: jax.lax.dynamic_index_in_dim(a, at, 0,
                                                          keepdims=False)
        put = lambda a, new: jax.lax.dynamic_update_index_in_dim(
            a, new.astype(a.dtype), at, 0)
        with scope("kda"):
            attn, tail, state = kda.mix(
                self.config, self._rms_norm(x, blk["attn_norm_g"]), blk,
                layer_of(tails), layer_of(states))
            return attn, caches[:n_rows] + (put(states, state),
                                            put(tails, tail))

    def _run_cached(self, params, x, cache, cos_sin, pos, attention=None,
                    early: int = 0):
        """x through every layer against the cache (``_mix_cached``) -> (x,
        the cache's carried arrays by name, the pairs each held expert was
        given (L_routed, E held) or None). As in gpt2.decode_step the
        stacked cache rides the scan CARRY and each layer updates its own
        part in place, whichever stack and kind it is of; the stacked expert
        leaves stay out of the scan's sliced operands. A period's consecutive
        layers of ONE kind (three KDA layers) are an inner scan whose body
        indexes the period's leaves by layer, so a kind's layer is compiled
        once and not once a layer (a prompt length's program must compile
        inside a request's deadline: 32 s -> 24 s for an 8,192-token
        prefill). The compiled decode step then shows a ``dynamic-slice`` a
        stacked leaf a layer where the unrolled form shows none; on the chip
        it is no copy (a tick of 16 steps: 39.7 ms on the device with the
        run as a loop, 42.5 ms unrolled; PERF.md, PR 33)."""
        names = self._cache_names()
        caches, routed = tuple(cache[n] for n in names), None
        for xs, experts, first, view, pattern in self._stacks(
                params, by_index=True):
            # (first layer, layers) of each run of one kind in a period
            sizes = [len(list(same)) for _, same in itertools.groupby(pattern)]
            runs = list(zip(itertools.accumulate([0] + sizes), sizes))

            # the stack's first layer in each kind of cache array: the layers
            # before it that keep rows a position, those that keep a state,
            # those that keep a ring
            group = self._cache_group
            before = {kind: sum(group(k) == group(kind)
                                for k in self.config.kinds[:first])
                      for kind in pattern}

            def layer(x, caches, per, n, j, i=0):
                blk = view(per, j, i, n)
                at, mine = self._layer_at(pattern, n, j, i)
                attn, caches = self._mix_cached(
                    x, blk, cos_sin, caches, before[pattern[j]] + mine, pos,
                    attention, pattern[j], early)
                x, stats = self._block_finish(x, blk, attn, experts, at)
                return x, caches, None if stats is None else stats[0]

            def body(carry, at):
                x, caches = carry
                per, n = at
                if len(pattern) == 1:
                    x, caches, given = layer(x, caches, per, n, 0)
                    return (x, caches), given
                given = []
                for j, count in runs:
                    if count == 1:
                        x, caches, g = layer(x, caches, per, n, j)
                        given.append(None if g is None else g[None])
                        continue

                    def one_of_run(carry, i):
                        x, caches, g = layer(*carry, per, n, j, i)
                        return (x, caches), g

                    (x, caches), g = jax.lax.scan(
                        one_of_run, (x, caches), jnp.arange(count))
                    given.append(g)
                return (x, caches), None if given[0] is None \
                    else jnp.concatenate(given)

            n = next(iter(jax.tree.leaves(xs))).shape[0]
            with scope("layers"):
                (x, caches), routed = jax.lax.scan(
                    body, (x, caches), (xs, jnp.arange(n)))
        if routed is not None and routed.ndim > 2:      # (L / p, p, E) -> L
            routed = routed.reshape(-1, routed.shape[-1])
        return x, dict(zip(names, caches)), routed

    def prefill(self, params, input_ids, cache):
        """Process the prompt, fill the cache, return last-position logits.
        Counts, when the program is traced, its softmax layers by the form
        their attention took (``_prefill_form``): registry counter
        ``kernels/prefill_attn_calls{form=fused|plain}``."""
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.models.common import local_causal_attention

        c = self.config
        B, T = input_ids.shape
        x = self._embed(params, input_ids)
        masked = {"block": c.block_length} if c.block_length else {}
        attention = lambda q, k, v, window=None, **sink: \
            local_causal_attention(q, k, v, c.use_flash_attention,
                                   window=window, **masked, **sink)
        cos_sin = self._rope(jnp.arange(T))
        x, out, routed = self._run_cached(params, x, cache, cos_sin, 0,
                                          attention)
        # at trace time: the program's softmax layers by the form they took
        for kind in set(c.kinds) - {"kda"}:
            telemetry.get_registry().counter(
                "kernels/prefill_attn_calls", {"form": self._prefill_form(
                    kind, T, self._rope_of(kind, cos_sin)[0])}).inc(
                        c.kinds.count(kind))
        with scope("head"):
            x = self._rms_norm(x, params["norm_g"])
            logits = (x[:, -1] @ self._head(params, x.dtype)
                      ).astype(jnp.float32)
        out["pos"] = jnp.int32(T)
        if routed is not None:
            out["expert_tokens"] = routed
        for kept in ("block_passes", "pending"):
            if kept in cache:
                out[kept] = cache[kept]
        return logits, out

    def decode_step(self, params, token, cache):
        """One token for every sequence: (B,) → logits (B, V), cache advanced."""
        c = self.config
        pos = cache["pos"]
        x = self._embed(params, token)[:, None]                 # (B, 1, D)
        x, out, routed = self._run_cached(params, x, cache,
                                          self._rope(pos[None]), pos)
        with scope("head"):
            x = self._rms_norm(x, params["norm_g"])
            logits = (x[:, 0] @ self._head(params, x.dtype)
                      ).astype(jnp.float32)
        out["pos"] = pos + 1
        if routed is not None:
            out["expert_tokens"] = cache["expert_tokens"] + routed
        return logits, out

    def block_step(self, params, tokens, masked, cache, commit: bool = False,
                   pending=None):
        """One forward pass over a BLOCK of a model that generates by
        diffusion over blocks: ``tokens`` (B, Lb) int32 at positions ``pos ..
        pos + Lb - 1``, read as the mask token where ``masked`` (B, Lb) bool
        says so (masked-ness is carried beside the tokens: a model may
        CHOOSE the mask id). The block's K/V rows are written into its own
        slots and every position attends over slots ``0 .. pos + Lb - 1``,
        the block itself whole. -> (logits (B, Lb, V) float32, row i the
        prediction of the token AT position i; the cache, ``pos`` where it
        was: the next pass over the block overwrites the rows). ``commit``:
        the pass over a FINISHED block that leaves its rows for good: ->
        (None, the cache with ``pos`` advanced by Lb). That pair is the
        DEFINITION; what the engine runs is ``pending`` (B, Lb) int32: the
        final tokens of the finished block BEFORE this one, which lies at
        ``pos .. pos + Lb - 1`` uncommitted, carried in this pass: ``2 Lb``
        positions from ``pos`` on, the pending block's then this block's,
        all their rows written; the pending positions attend over slots ``0
        .. pos + Lb - 1`` (every layer computes for them what the
        committing pass would), this block's over ``0 .. pos + 2 Lb - 1``.
        -> (logits of THIS block's positions, the cache with ``pos`` advanced
        by Lb: the pending block is committed). One walk with ``prefill``
        and ``decode_step`` (``_run_cached``)."""
        c = self.config
        if not c.block_length:
            raise ValueError("block_step: the model's configuration has no "
                             "block_length (it generates a token a step)")
        if commit and pending is not None:
            raise ValueError("block_step: a pass commits its own block or "
                             "carries the one before it")
        pos, Lb = cache["pos"], tokens.shape[1]
        ids = jnp.where(masked, jnp.int32(c.mask_token_id), tokens)
        carried = 0 if pending is None else pending.shape[1]
        if carried:
            ids = jnp.concatenate([pending.astype(ids.dtype), ids], axis=1)
        x = self._embed(params, ids)                    # (B, [Lb +] Lb, D)
        x, out, routed = self._run_cached(
            params, x, cache, self._rope(pos + jnp.arange(carried + Lb)), pos,
            early=carried)
        logits = None
        if not commit:
            with scope("head"):
                h = self._rms_norm(x[:, carried:], params["norm_g"])
                logits = (h @ self._head(params, h.dtype)).astype(jnp.float32)
        out["pos"] = pos + (Lb if commit else carried)
        if routed is not None:
            out["expert_tokens"] = cache["expert_tokens"] + routed
        out["block_passes"] = cache["block_passes"] + jnp.asarray(
            [not commit, bool(commit), bool(carried), 0], jnp.int32)
        out["pending"] = cache["pending"]
        return logits, out
