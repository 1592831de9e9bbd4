"""Unified telemetry: metrics registry + step tracing + exporters.

The reference DeepSpeed spreads observability over four half-connected
mechanisms (SynchronizedWallClockTimer, CommsLogger, flops profiler,
monitor fan-out). Here one process-wide *session* owns:

* a :class:`~deepspeed_tpu.telemetry.registry.MetricsRegistry` (counters,
  gauges, histograms with p50/p90/p99 reservoirs) that the training engine,
  comm layer, inference engine, and resilience subsystem all feed;
* a :class:`~deepspeed_tpu.telemetry.tracing.StepTracer` emitting
  Chrome-trace/Perfetto JSON spans for the host-visible step phases;
* exporters — append-only JSONL (``bin/ds_metrics`` renders it),
  Prometheus text exposition, and the existing ``MonitorMaster`` fan-out
  (TensorBoard/W&B/CSV get the series for free).

Enabled by the ``telemetry`` ds_config block (engine init calls
:func:`configure`); when off, :func:`get_registry` returns a shared no-op
singleton (every metric update costs one call into a ``pass``) and
:func:`get_tracer` the process-wide span ring (``tracing.RING``: the last
``RING_SPANS`` spans, always recorded). Instrumented layers NEVER hold
either across a reconfigure — they re-fetch through the module functions.
"""

from __future__ import annotations

import atexit
import os
from typing import Optional

from deepspeed_tpu.telemetry.exporters import (JSONLExporter, MonitorExporter,
                                               PrometheusExporter)
from deepspeed_tpu.telemetry.registry import (NOOP_REGISTRY, Counter, Gauge,
                                              Histogram, MetricsRegistry,
                                              NoopRegistry)
from deepspeed_tpu.telemetry.tracing import RING, Span, StepTracer
from deepspeed_tpu.utils.logging import logger

__all__ = [
    "MetricsRegistry", "NoopRegistry", "Counter", "Gauge", "Histogram",
    "StepTracer", "Span", "RING", "TelemetrySession", "JSONLExporter",
    "PrometheusExporter", "MonitorExporter", "configure", "install_session",
    "deconfigure", "get_session", "get_registry", "get_tracer", "flush",
    "METRICS_FILE", "PROMETHEUS_FILE", "TRACE_FILE",
]

METRICS_FILE = "metrics.jsonl"
PROMETHEUS_FILE = "metrics.prom"
TRACE_FILE = "trace.json"


class TelemetrySession:
    """One run's live telemetry state: registry + tracer + exporters.

    File exporters exist only on process 0 (the session still *collects* on
    every rank — cross-rank aggregation is a log-analysis job, and rank-local
    registries are what straggler work needs); the MonitorMaster fan-out is
    already rank-0-gated internally.
    """

    def __init__(self, cfg, monitor=None):
        import jax

        self.cfg = cfg
        self.source = "manual"          # "config" when installed by engine init
        self.registry = MetricsRegistry(
            default_max_samples=cfg.histogram_max_samples,
            default_bounds=cfg.histogram_buckets or None)
        rank = jax.process_index()
        # None with ``trace: false``: get_tracer() then hands out the ring
        self.tracer = (StepTracer(max_events=cfg.max_trace_events, pid=rank)
                       if cfg.trace else None)
        # new session = new trace file + clock: restart the comm layer's
        # per-(op, group) collective seq counters with it, so every rank's
        # (op, seq, group) trace identities stay alignable by ds_prof even
        # when ranks (re)start at different times (elastic restarts)
        from deepspeed_tpu.comm import comm as _comm

        _comm.reset_collective_trace_seq()
        self.output_dir = cfg.output_dir
        self.exporters = []
        self.trace_path = None
        if rank == 0:
            os.makedirs(cfg.output_dir, exist_ok=True)
            if cfg.jsonl:
                self.exporters.append(JSONLExporter(os.path.join(cfg.output_dir, METRICS_FILE)))
            if cfg.prometheus:
                self.exporters.append(PrometheusExporter(os.path.join(cfg.output_dir, PROMETHEUS_FILE)))
        if cfg.trace:
            # trace files are PER RANK (straggler hunting needs every host's
            # spans; metrics stay rank-0 — cross-rank series aggregation is a
            # log-analysis job, span skew is not). trace.json on rank 0 keeps
            # the single-host name; other ranks write trace.rank<N>.json
            # beside it on their own filesystem view.
            name = TRACE_FILE if rank == 0 else \
                TRACE_FILE.replace(".json", f".rank{rank}.json")
            os.makedirs(cfg.output_dir, exist_ok=True)
            self.trace_path = os.path.join(cfg.output_dir, name)
            _rotate_stale_trace(self.trace_path)
        if cfg.monitor and monitor is not None:
            self.exporters.append(MonitorExporter(monitor))
        self._last_step = 0

    def step_end(self, step: int) -> None:
        """Engine calls this once per global step; flushes every
        ``flush_interval`` steps."""
        self._last_step = step
        if self.cfg.flush_interval and step % self.cfg.flush_interval == 0:
            self.flush(step)

    def flush(self, step: Optional[int] = None) -> None:
        snap = self.registry.snapshot()
        step = self._last_step if step is None else step
        for e in self.exporters:
            try:
                e.export(snap, step=step)
            except Exception as exc:   # telemetry must never kill the run
                logger.warning(f"telemetry exporter {type(e).__name__} failed: {exc}")
        if self.trace_path is not None:
            try:
                self.tracer.write(self.trace_path)
            except Exception as exc:
                logger.warning(f"telemetry trace write failed: {exc}")


def _rotate_stale_trace(path: str) -> None:
    """A new session must not clobber the previous session's trace — an
    elastic restart used to overwrite ``trace.json`` and destroy exactly
    the evidence a post-mortem (and ``ds_prof goodput``'s downtime
    accounting) needs. Rotate the old file aside as
    ``trace.session<N>[...].json``; ``ds_prof merge`` excludes rotated
    sessions from its default dir scan (two sessions of one rank must not
    read as two ranks), ``ds_prof goodput`` includes them (restarts are
    the point)."""
    if not os.path.exists(path):
        return
    head, tail = os.path.split(path)
    suffix = tail[len("trace"):]                # ".json" / ".rank3.json"
    for n in range(1, 10_000):
        rotated = os.path.join(head, f"trace.session{n}{suffix}")
        if not os.path.exists(rotated):
            break
    try:
        os.replace(path, rotated)
    except OSError as exc:
        logger.warning(f"telemetry: could not rotate stale trace {path}: {exc}")


_session: Optional[TelemetrySession] = None
_atexit_registered = False


def configure(cfg=None, monitor=None) -> Optional[TelemetrySession]:
    """Install (or tear down) the process-wide session from a ds_config
    ``telemetry`` block — the engine-init entry point. A disabled block
    removes only a previous CONFIG-installed session (same contract as
    ``resilience.chaos``: a new engine must not inherit the last engine's
    session, but must not clobber a test's manual install either)."""
    global _session, _atexit_registered
    if cfg is None or not cfg.enabled:
        if _session is not None and _session.source == "config":
            _flush_quietly(_session)      # don't drop the old run's tail
            _session = None
        return None
    if _session is not None:
        _flush_quietly(_session)          # replacement: old session's data lands first
    s = TelemetrySession(cfg, monitor=monitor)
    s.source = "config"
    _session = s
    if not _atexit_registered:
        _atexit_registered = True
        atexit.register(_atexit_flush)
    return s


def _flush_quietly(s: TelemetrySession) -> None:
    try:
        s.flush()
    except Exception:
        pass


def _atexit_flush():
    if _session is not None:
        _flush_quietly(_session)


def install_session(s: TelemetrySession) -> None:
    """Test / embedding hook: install a hand-built session."""
    global _session
    _session = s


def deconfigure() -> None:
    """Flush and remove the session regardless of who installed it."""
    global _session
    if _session is not None:
        _flush_quietly(_session)
    _session = None


def get_session() -> Optional[TelemetrySession]:
    return _session


def get_registry():
    """The live registry, or the shared no-op when telemetry is off."""
    return _session.registry if _session is not None else NOOP_REGISTRY


def get_tracer():
    """The live session's tracer, or the always-there span ring."""
    s = _session
    return s.tracer if s is not None and s.tracer is not None else RING


def flush() -> None:
    if _session is not None:
        _session.flush()
