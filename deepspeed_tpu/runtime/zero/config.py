"""ZeRO configuration (ds_config "zero_optimization" block).

Key-compatible with the reference's ``deepspeed/runtime/zero/config.py:76``
(DeepSpeedZeroConfig) and ``zero/offload_config.py`` (offload device enums,
pin_memory, ratio). On TPU several CUDA-era knobs become advisory: XLA already
overlaps collectives with compute, so ``overlap_comm`` et al. are accepted and
recorded but do not change generated code. Knobs that *are* real on TPU:
``stage``, offload devices (host memory / path for NVMe), bucket sizes (chunked
allgather in the explicit shard_map path), and ``stage3_param_persistence_threshold``
(small params stay replicated instead of dp-sharded).
"""

from __future__ import annotations

from enum import Enum
from typing import Optional

from pydantic import Field

from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel


class ZeroStageEnum(int, Enum):
    """cf. reference zero/config.py:67."""
    disabled = 0
    optimizer_states = 1
    gradients = 2
    weights = 3
    max_stage = 3


class OffloadDeviceEnum(str, Enum):
    """cf. reference zero/offload_config.py."""
    none = "none"
    cpu = "cpu"
    nvme = "nvme"


class DeepSpeedZeroOffloadParamConfig(DeepSpeedConfigModel):
    device: OffloadDeviceEnum = "none"
    nvme_path: Optional[str] = None
    buffer_count: int = Field(5, ge=0)
    buffer_size: int = Field(100_000_000, ge=0)
    max_in_cpu: int = Field(1_000_000_000, ge=0)
    pin_memory: bool = False


class DeepSpeedZeroOffloadOptimizerConfig(DeepSpeedConfigModel):
    device: OffloadDeviceEnum = "none"
    nvme_path: Optional[str] = None
    buffer_count: int = Field(4, ge=0)
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False
    ratio: float = Field(1.0, ge=0.0, le=1.0)
    # TPU extra (no reference counterpart): double-buffer the streamed
    # optimizer update — each host pull chains on the write-back TWO chunks
    # back instead of one, overlapping transfer with compute at the cost of
    # a second working set. Round 5 on a v5e host link: overlap measured
    # 0.368 -> 0.384-0.388 MFU on gpt2-1.3b but destabilized gpt2-xl at 48
    # layers (not re-measured since; ROADMAP S3/D5). None = keep the
    # DS_TPU_OFFLOAD_OVERLAP env default; the autotuner sweeps this axis.
    stream_overlap: Optional[bool] = None


class DeepSpeedZeroConfig(DeepSpeedConfigModel):
    stage: ZeroStageEnum = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = Field(500_000_000, ge=0)
    allgather_partitions: bool = True
    allgather_bucket_size: int = Field(500_000_000, ge=0)
    overlap_comm: Optional[bool] = None
    load_from_fp32_weights: bool = True
    elastic_checkpoint: bool = False

    offload_param: Optional[DeepSpeedZeroOffloadParamConfig] = None
    offload_optimizer: Optional[DeepSpeedZeroOffloadOptimizerConfig] = None

    sub_group_size: int = Field(1_000_000_000, ge=0)
    cpu_offload_param: Optional[bool] = Field(
        None, json_schema_extra={"deprecated": True, "new_param": "offload_param",
                                 "new_param_fn": lambda v: DeepSpeedZeroOffloadParamConfig(device="cpu") if v else None})
    cpu_offload_use_pin_memory: Optional[bool] = Field(None, json_schema_extra={"deprecated": True})
    cpu_offload: Optional[bool] = Field(
        None, json_schema_extra={"deprecated": True, "new_param": "offload_optimizer",
                                 "new_param_fn": lambda v: DeepSpeedZeroOffloadOptimizerConfig(device="cpu") if v else None})

    prefetch_bucket_size: int = Field(50_000_000, ge=0, alias="stage3_prefetch_bucket_size")
    param_persistence_threshold: int = Field(
        100_000, ge=0, alias="stage3_param_persistence_threshold",
        description="stage 3: a parameter under this many elements stays whole on every chip (its master, moments and gradient stay sharded). A leaf of a layer-stacked subtree (`model.stacked_params_key`, `blocks` by default) is judged by the elements ONE layer holds, as the reference judges one layer's parameter; any other leaf by all of its elements")
    model_persistence_threshold: int = Field(2**63 - 1, ge=0, alias="stage3_model_persistence_threshold")
    max_live_parameters: int = Field(1_000_000_000, ge=0, alias="stage3_max_live_parameters")
    max_reuse_distance: int = Field(1_000_000_000, ge=0, alias="stage3_max_reuse_distance")
    gather_16bit_weights_on_model_save: bool = Field(False, alias="stage3_gather_16bit_weights_on_model_save")

    ignore_unused_parameters: bool = True
    legacy_stage1: bool = False
    round_robin_gradients: bool = False
    memory_efficient_linear: bool = True

    # TPU-only extension: which mesh axes ZeRO shards over (default: all
    # data-parallel axes). Mirrors MiCS-style scoped sharding (zero/mics.py:31)
    # when set to a strict subset, with hierarchical gather across the rest.
    shard_axes: Optional[list] = None
    # MiCS parity knobs (reference zero/mics.py): size of the replication
    # ("shard") group; hierarchical allgather intra-group then inter-group.
    mics_shard_size: int = Field(-1, ge=-1)
    mics_hierarchical_params_gather: bool = False

    @property
    def zero_enabled(self) -> bool:
        return int(self.stage) > 0
