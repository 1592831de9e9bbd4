"""TPU (and CPU-mesh fallback) implementation of the accelerator seam.

Counterpart of the reference's ``accelerator/cuda_accelerator.py:19``
(CUDA_Accelerator): names its comm backend ('xccl' here, 'nccl' there — cf.
cuda_accelerator.py:23), exposes device/memory/dtype facts, and hands out op
builders. Device discovery uses ``jax.devices()``; when JAX is running on the
CPU backend (e.g. tests with --xla_force_host_platform_device_count=8) the same
class serves as the "fake mesh" accelerator, like the reference's CPU fallback.
"""

from __future__ import annotations

import functools
import os
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator

class TPU_Accelerator(DeepSpeedAccelerator):
    def __init__(self):
        super().__init__()
        self._name = "tpu" if jax.default_backend() not in ("cpu",) else "cpu"
        self._communication_backend_name = "xccl"
        self._current_device_index = 0
        self._seed = 0

    # ------------------------------------------------------------------ device
    def is_available(self) -> bool:
        try:
            return len(jax.devices()) > 0
        except Exception:
            return False

    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return self._name
        return f"{self._name}:{device_index}"

    def device(self, device_index: Optional[int] = None):
        devs = jax.local_devices()
        return devs[device_index if device_index is not None else self._current_device_index]

    def device_count(self) -> int:
        return jax.local_device_count()

    def global_device_count(self) -> int:
        return jax.device_count()

    def process_count(self) -> int:
        return jax.process_count()

    def process_index(self) -> int:
        return jax.process_index()

    def current_device(self) -> int:
        return self._current_device_index

    def current_device_name(self) -> str:
        return f"{self._name}:{self._current_device_index}"

    def set_device(self, device_index: int) -> None:
        self._current_device_index = device_index

    def synchronize(self, device_index: Optional[int] = None) -> None:
        jax.effects_barrier()

    def device_kind(self) -> str:
        return getattr(jax.local_devices()[0], "device_kind", "unknown")

    # ------------------------------------------------------------------- RNG
    def manual_seed(self, seed: int):
        self._seed = int(seed)
        return jax.random.PRNGKey(self._seed)

    def manual_seed_all(self, seed: int):
        return self.manual_seed(seed)

    def initial_seed(self) -> int:
        return self._seed

    # ---------------------------------------------------------------- memory
    def _stats(self, device_index: Optional[int] = None) -> dict:
        try:
            return self.device(device_index).memory_stats() or {}
        except Exception:
            return {}

    def memory_allocated(self, device_index: Optional[int] = None) -> int:
        return int(self._stats(device_index).get("bytes_in_use", 0))

    def max_memory_allocated(self, device_index: Optional[int] = None) -> int:
        return int(self._stats(device_index).get("peak_bytes_in_use", 0))

    def reset_peak_memory_stats(self, device_index: Optional[int] = None) -> None:
        # XLA exposes no peak-reset; callers should diff snapshots instead.
        pass

    def total_memory(self, device_index: Optional[int] = None) -> int:
        return int(self._stats(device_index).get("bytes_limit", 0))

    def available_memory(self, device_index: Optional[int] = None) -> int:
        s = self._stats(device_index)
        return int(s.get("bytes_limit", 0)) - int(s.get("bytes_in_use", 0))

    def memory_stats(self, device_index: Optional[int] = None) -> dict:
        return self._stats(device_index)

    # ----------------------------------------------------------------- dtype
    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        # fp16 compute is supported by XLA on TPU (upcast in MXU); kept for
        # ds_config parity, though bf16 is preferred.
        return True

    def is_triton_supported(self) -> bool:
        return False

    def supported_dtypes(self) -> List[Any]:
        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8, jnp.int32]

    def preferred_dtype(self):
        return jnp.bfloat16

    # ------------------------------------------------------------------ comm
    def communication_backend_name(self) -> str:
        return self._communication_backend_name

    # ----------------------------------------------------------------- perf
    def chip_spec(self):
        """This device's row of THE peak table (``analysis/chips.py``, keyed
        by ``device_kind``). A device the table does not list raises
        ``KeyError`` — there is no default chip; the ``cpu`` platform gets
        the nominal ``cpu-sim`` row."""
        from deepspeed_tpu.analysis import chips

        dev = jax.local_devices()[0]
        return chips.resolve_chip(
            chips.detect_chip_name(dev.device_kind, dev.platform))

    def peak_flops(self, dtype: Any = None) -> float:
        peak = self.chip_spec().peak_flops
        if dtype in (jnp.float32, np.float32, "float32", "fp32"):
            peak = peak / 2.0
        return peak

    def memory_bandwidth(self) -> float:
        """Peak HBM bandwidth per chip, bytes/s."""
        return self.chip_spec().hbm_bytes_per_s

    def hbm_bytes(self) -> int:
        """Device memory per chip: the limit the runtime reports, else (a
        backend without ``memory_stats``) the peak table's capacity."""
        return self.total_memory() or self.chip_spec().hbm_bytes

    # ------------------------------------------------------------- op builder
    def create_op_builder(self, op_name: str):
        builder = self.get_op_builder(op_name)
        return builder() if builder is not None else None

    def get_op_builder(self, op_name: str):
        from deepspeed_tpu.ops.op_builder import get_builder_class

        return get_builder_class(op_name)

    # --------------------------------------------------------------- platform
    def on_accelerator(self, array: Any) -> bool:
        try:
            shards = array.addressable_shards
            return all(s.device.platform != "cpu" or self._name == "cpu" for s in shards)
        except AttributeError:
            return False

    def is_synchronized_device(self) -> bool:
        return False

    def pin_memory(self, array, align_bytes: int = 1):
        # Host arrays in JAX are already transfer-ready; kept for API parity
        # with reference pin_memory (abstract_accelerator.py:217).
        return array

    def is_pinned(self, array) -> bool:
        return True

    def ici_topology(self):
        """Best-effort ICI mesh shape (x, y, z) from device coords, else None."""
        devs = jax.devices()
        coords = [getattr(d, "coords", None) for d in devs]
        if any(c is None for c in coords):
            return None
        dims = tuple(max(c[i] for c in coords) + 1 for i in range(len(coords[0])))
        return dims


@functools.lru_cache(None)
def get_accelerator() -> TPU_Accelerator:
    """Singleton accessor (reference: accelerator/real_accelerator.py:37).

    Discovery is trivial on TPU: JAX already picked the platform. The
    DSTPU_ACCELERATOR env var can force 'cpu' for debugging.
    """
    forced = os.environ.get("DSTPU_ACCELERATOR")
    if forced == "cpu":
        jax.config.update("jax_platforms", "cpu")
    return TPU_Accelerator()
