"""Model-family registry shared by the entry points that take a preset name.

One place maps a preset name (``gpt2-*``, ``gpt2-moe-*``, ``llama-*``,
``bert-*``) to (model class, synthetic-batch builder, preset table) so
``bin/ds_tune``, ``ds_doctor``, ``ds_roofline`` and ``ds_serve`` cannot
drift apart on family dispatch.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple


def resolve_family(model_name: str, moe_experts: int = 8
                   ) -> Tuple[Callable, Callable, Dict[str, Any]]:
    """→ (model_cls, make_batch(batch, seq, vocab, **kw), PRESETS)."""
    from deepspeed_tpu.models.gpt2 import (PRESETS as GPT2_PRESETS,
                                           GPT2Model, synthetic_lm_batch)

    if model_name.startswith("llama"):
        from deepspeed_tpu.models.llama import PRESETS, LlamaModel

        return LlamaModel, synthetic_lm_batch, PRESETS
    if model_name.startswith("bert"):
        from deepspeed_tpu.models.bert import (PRESETS, BertModel,
                                               synthetic_mlm_batch)

        return BertModel, synthetic_mlm_batch, PRESETS
    if model_name.startswith("gpt2-moe"):
        # "gpt2-moe-125m" rides the gpt2-125m trunk: Switch-style top-1
        # expert bank on odd blocks; single process serves ep_size=1 (the
        # dp×ep a2a program is dryrun_multichip's job)
        from deepspeed_tpu.models.gpt2_moe import MoEGPT2

        cls = functools.partial(MoEGPT2, num_experts=moe_experts, ep_size=1)
        return cls, synthetic_lm_batch, {
            model_name: GPT2_PRESETS[model_name.replace("-moe", "")]}
    return GPT2Model, synthetic_lm_batch, GPT2_PRESETS
