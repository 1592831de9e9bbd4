"""Wall-clock and throughput timers.

TPU-native counterpart of the reference's ``deepspeed/utils/timer.py``
(SynchronizedWallClockTimer at :33, ThroughputTimer at :137). On GPU the reference
synchronizes via CUDA events; on TPU the equivalent barrier is
``jax.block_until_ready`` on the most recent output (XLA dispatch is async). We
keep the same public surface: ``timers(name).start()/stop()``, ``.log(names)``,
``.elapsed()``, plus ``ThroughputTimer`` for samples/sec reporting.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from deepspeed_tpu.utils.logging import log_dist

try:
    import psutil

    _PSUTIL = True
except Exception:  # pragma: no cover
    _PSUTIL = False

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
BACKWARD_INNER_MICRO_TIMER = "bwd_inner_microstep"
BACKWARD_INNER_GLOBAL_TIMER = "bwd_inner"
BACKWARD_REDUCE_MICRO_TIMER = "bwd_allreduce_microstep"
BACKWARD_REDUCE_GLOBAL_TIMER = "bwd_allreduce"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"
TRAIN_BATCH_TIMER = "train_batch"


def _device_sync(sync_obj=None) -> None:
    """Block until outstanding device work completes (CUDA-event analogue)."""
    import jax

    if sync_obj is not None:
        jax.block_until_ready(sync_obj)
    else:
        # Cheap full-queue barrier: tiny transfer forces a flush of prior work
        # on the default device.
        jax.effects_barrier()


class _Timer:
    def __init__(self, name: str):
        self.name_ = name
        self.started_ = False
        self.start_time = 0.0
        self.elapsed_records: List[float] = []

    def start(self) -> None:
        if self.started_:
            raise RuntimeError(f"timer {self.name_} has already been started")
        self.start_time = time.time()
        self.started_ = True

    def stop(self, reset: bool = False, record: bool = True, sync_obj=None) -> None:
        if not self.started_:
            raise RuntimeError(f"timer {self.name_} is not started")
        _device_sync(sync_obj)
        elapsed = time.time() - self.start_time
        if record:
            self.elapsed_records.append(elapsed)
        self.started_ = False

    def reset(self) -> None:
        self.started_ = False
        self.elapsed_records = []

    def elapsed(self, reset: bool = True) -> float:
        """Total recorded seconds (optionally resetting)."""
        total = sum(self.elapsed_records)
        if self.started_:
            total += time.time() - self.start_time
        if reset:
            self.elapsed_records = []
        return total

    def mean(self) -> float:
        if not self.elapsed_records:
            return 0.0
        return sum(self.elapsed_records) / len(self.elapsed_records)


class SynchronizedWallClockTimer:
    """Group of named timers; device-synchronized on stop."""

    def __init__(self):
        self.timers: Dict[str, _Timer] = {}

    def __call__(self, name: str) -> _Timer:
        if name not in self.timers:
            self.timers[name] = _Timer(name)
        return self.timers[name]

    def has_timer(self, name: str) -> bool:
        return name in self.timers

    @staticmethod
    def memory_usage() -> str:
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats() or {}
            in_use = stats.get("bytes_in_use", 0) / (1024**3)
            peak = stats.get("peak_bytes_in_use", 0) / (1024**3)
            return f"Device mem in-use {in_use:.2f} GB | peak {peak:.2f} GB"
        except Exception:
            return "Device mem stats unavailable"

    def log(self, names: List[str], normalizer: float = 1.0, reset: bool = True,
            memory_breakdown: bool = False, ranks: Optional[List[int]] = None) -> None:
        assert normalizer > 0.0
        parts = []
        for name in names:
            if name in self.timers:
                ms = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                parts.append(f"{name}: {ms:.2f}")
        msg = "time (ms) | " + " | ".join(parts)
        if memory_breakdown:
            msg += " | " + self.memory_usage()
        log_dist(msg, ranks=ranks or [0])

    def get_timers(self):
        return self.timers


class NoopTimer:
    """Used when wall_clock_breakdown is off — zero overhead."""

    class _N:
        def start(self, *a, **k):
            pass

        def stop(self, *a, **k):
            pass

        def reset(self, *a, **k):
            pass

        def elapsed(self, *a, **k):
            return 0.0

        def mean(self):
            return 0.0

    def __init__(self):
        self._n = self._N()

    def __call__(self, name):
        return self._n

    def has_timer(self, name):
        return False

    def log(self, *a, **k):
        pass

    def get_timers(self):
        return {}


class ThroughputTimer:
    """Samples/sec + TFLOPs reporting (cf. reference ThroughputTimer timer.py:137)."""

    def __init__(self, batch_size: int, start_step: int = 2, steps_per_output: int = 50,
                 monitor_memory: bool = False, logging_fn=None,
                 sync_every_step: bool = True, flops_estimator=None):
        self.start_time = 0.0
        self.end_time = 0.0
        self.started = False
        self.batch_size = max(1, batch_size)
        self.start_step = start_step
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0.0
        self.step_elapsed_time = 0.0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory and _PSUTIL
        self.logging = logging_fn or (lambda m: log_dist(m, ranks=[0]))
        self.initialized = False
        # syncing on every stop() blocks the host until the step's result is
        # back, so the next step cannot be enqueued behind the running one
        # (on a local chip: the dispatch gap lands between steps); when off,
        # only the stops that emit a log line sync, and intermediate steps
        # pipeline freely. Note
        # un-synced windows attribute host time between steps to the device
        # (the device computes through those gaps), so reported samples/sec
        # can read high when the input pipeline stalls — enable
        # wall_clock_breakdown for strict per-step accounting.
        self.sync_every_step = sync_every_step
        # TFLOPs column: flops_estimator() -> analytical FLOPs of one global
        # batch (the engine wires profiling/flops_profiler's jaxpr counter).
        # Called LAZILY on the first emitted log line only — runs that never
        # log throughput never pay for the trace.
        self.flops_estimator = flops_estimator
        self.flops_per_batch = None

    def set_flops_per_batch(self, flops: float):
        """Explicit override for callers that already know the model cost."""
        self.flops_per_batch = float(flops)

    def _tflops_suffix(self, per_step_time: float) -> str:
        if self.flops_per_batch is None and self.flops_estimator is not None:
            try:
                self.flops_per_batch = float(self.flops_estimator() or 0.0)
            except Exception as e:  # estimation must never break the log line
                log_dist(f"throughput: flops estimate unavailable ({e})", ranks=[0])
                self.flops_per_batch = 0.0
        if not self.flops_per_batch or per_step_time <= 0:
            return ""
        return f", EstTFLOPs={self.flops_per_batch / per_step_time / 1e12:.2f}"

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def _init_timer(self):
        self.initialized = True

    def start(self):
        self._init_timer()
        self.started = True
        if self.global_step_count >= self.start_step:
            self.start_time = time.time()

    def stop(self, global_step: bool = False, report_speed: bool = True, sync_obj=None):
        if not self.started:
            return
        self.started = False
        self.micro_step_count += 1
        if global_step:
            self.global_step_count += 1
        if self.start_time > 0:
            will_log = (global_step and report_speed and self.steps_per_output
                        and self.global_step_count % self.steps_per_output == 0)
            if self.sync_every_step or will_log:
                _device_sync(sync_obj)
            self.end_time = time.time()
            duration = self.end_time - self.start_time
            self.total_elapsed_time += duration
            self.step_elapsed_time += duration
            self.start_time = 0.0
            if will_log:
                self.logging(
                    f"epoch={self.epoch_count}/micro_step={self.micro_step_count}/"
                    f"global_step={self.global_step_count}, "
                    f"RunningAvgSamplesPerSec={self.avg_samples_per_sec():.3f}, "
                    f"CurrSamplesPerSec={self.batch_size / self.step_elapsed_time * self.steps_per_output:.3f}"
                    + self._tflops_suffix(self.step_elapsed_time / self.steps_per_output))
                self.step_elapsed_time = 0.0

    def avg_samples_per_sec(self) -> float:
        if self.total_elapsed_time > 0 and self.global_step_count > self.start_step:
            samples = self.batch_size * (self.global_step_count - self.start_step)
            return samples / self.total_elapsed_time
        return 0.0


def trim_mean(data: List[float], trim_percent: float) -> float:
    """Mean after trimming ``trim_percent`` from both tails (reference timer.py tail)."""
    assert 0.0 <= trim_percent <= 1.0
    if not data:
        return 0.0
    n = len(data)
    data = sorted(data)
    strip = int(n * trim_percent)
    kept = data[strip: n - strip] or data
    return sum(kept) / len(kept)
