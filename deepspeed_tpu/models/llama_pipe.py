"""Pipeline-parallel LLaMA (GQA family through the in-jit 1F1B executor).

Same design as models/gpt2_pipe.py — the shared PipelinedDecoderMixin owns
structure conversion, 'pipe'-axis partition specs, the chunked last-stage CE,
and the cached loss builder; this class contributes only the LLaMA stage
compute (RoPE tables + GQA blocks) and the embed/final-norm/head hooks. The
reference partitions arbitrary LayerSpec stage content (pipe/module.py:353);
here any LlamaConfig — GQA, rope scaling, tied head — pipelines because the
per-block compute is the base model's own ``_block``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.common import _rope_cos_sin
from deepspeed_tpu.models.gpt2_pipe import PipelinedDecoderMixin
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel


class PipelinedLlama(PipelinedDecoderMixin, LlamaModel):
    """Model-protocol implementation whose loss is the in-jit pipeline."""

    def __init__(self, config: LlamaConfig, num_stages: int, num_micro: int,
                 schedule: str = "1f1b"):
        super().__init__(config)
        if config.n_layer % num_stages:
            raise ValueError(
                f"n_layer {config.n_layer} not divisible by stages {num_stages}")
        if config.sequence_parallel:
            raise NotImplementedError(
                "PipelinedLlama does not compose with sequence_parallel; "
                "use the non-pipelined LlamaModel")
        if schedule not in ("1f1b", "gpipe"):
            raise ValueError(f"schedule {schedule!r} not in ('1f1b', 'gpipe')")
        self.num_stages = num_stages
        self.num_micro = num_micro
        self.schedule = schedule
        self._pipe_loss = None

    # --------------------------------------------------------------- compute
    def _stage_fn(self, stage_params, x, rng):
        c = self.config
        cos_sin = _rope_cos_sin(jnp.arange(x.shape[1]), c.head_dim,
                                c.rope_theta, c.rope_scaling)

        def body(carry, blk):
            return self._block(carry, blk, cos_sin)    # (x, None): dense

        out, _ = jax.lax.scan(body, x, stage_params)
        return out

    def _first_stage_fn(self, shared, mb, rng):
        ids = mb["input_ids"] if isinstance(mb, dict) else mb
        return shared["wte"].astype(self.config.dtype)[ids]

    def _final_norm_shared(self, shared, x):
        return self._rms_norm(x, shared["norm_g"])

    def _head_shared(self, shared, dtype):
        return self._head(shared, dtype)
