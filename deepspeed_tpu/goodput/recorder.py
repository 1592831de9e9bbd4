"""GoodputMeter — the engine-side goodput meter (``goodput`` ds_config
block).

Imported ONLY when the block is present (strict no-op contract, same as
``profiling`` / ``perf`` / ``serving``). The meter owns no clocks of its
own — it classifies the spans the telemetry tracer already records:

* per step: the newest COMPLETE step's ledger (the current step's
  ``train_batch`` span is still open when the engine's post-step hook
  runs, so the live series lag one step) → ``goodput/*`` registry
  series for ``ds_top`` / ``ds_metrics --follow``;
* at perf-record time: :meth:`attribution` folds the per-step ledgers
  of the timed window into the dict a perf-ledger entry embeds
  (``ds_perf gate`` gates the resulting ``goodput_fraction``);
* at init: :func:`install_compile_listener` registers a
  ``jax.monitoring`` duration listener that stamps every backend
  compile as a ``compile`` span — real compiler seconds, not a guess
  from cold-step excess.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from deepspeed_tpu import telemetry as _telemetry
from deepspeed_tpu.goodput.ledger import (goodput_fraction, step_ledgers,
                                          sum_buckets, top_badput)
from deepspeed_tpu.goodput.taxonomy import BUCKETS, is_span
from deepspeed_tpu.utils.logging import logger

_LISTENER = {"installed": False}


def install_compile_listener() -> bool:
    """Register a process-wide ``jax.monitoring`` listener that stamps
    backend-compile durations as ``compile`` spans on the LIVE tracer
    (re-fetched per event, so sessions can come and go). Idempotent;
    there is no per-listener deregistration in jax, so once installed it
    stays — a later engine without the goodput block just feeds spans to
    whatever tracer is live (the no-op one when telemetry is off)."""
    if _LISTENER["installed"]:
        return True
    try:
        import jax.monitoring as _mon

        def _on_compile_event(event, duration, **kw):
            # /jax/core/compile/{jaxpr_trace,jaxpr_to_mlir_module,
            # backend_compile}_duration — sequential sub-phases of one
            # compile, each stamped as it ends so they do not overlap
            if "compile" in event and event.endswith("_duration"):
                try:
                    _telemetry.get_tracer().complete(
                        "compile", float(duration) * 1e6, cat="compile",
                        phase=event.rsplit("/", 1)[-1])
                except Exception:   # a broken tracer must not kill compiles
                    pass

        _mon.register_event_duration_secs_listener(_on_compile_event)
    except Exception as e:          # pragma: no cover - jax without monitoring
        logger.warning(f"goodput: compile listener unavailable: {e}")
        return False
    _LISTENER["installed"] = True
    return True


class GoodputMeter:
    def __init__(self, cfg, engine=None):
        self.cfg = cfg
        self.engine = engine
        self._buf: List[dict] = []      # recent span events, pruned per step
        self._idx = 0                   # consumed prefix of tracer.events
        self._last_step = -1
        self._mfu_denom: Optional[float] = None   # flops/(peak*ndev), cached
        self._totals = {b: 0.0 for b in BUCKETS}
        if cfg.compile_spans:
            install_compile_listener()

    # -------------------------------------------------------------- per step
    def on_step(self, step: int) -> None:
        """Engine post-step hook: classify any newly completed steps and
        export their ledgers as ``goodput/*`` series. Incremental — only
        events appended since the last call are scanned, and the buffer
        is pruned past each reported step, so the per-step cost stays
        O(one step's spans) on arbitrarily long runs."""
        session = _telemetry.get_session()
        if session is None:
            return
        events = getattr(session.tracer, "events", None)
        if events is None:
            return
        if len(events) < self._idx:     # new tracer (session replaced)
            self._idx, self._buf, self._last_step = 0, [], -1
        new = events[self._idx:]
        self._idx = len(events)
        self._buf.extend(ev for ev in new if is_span(ev))
        if not self._buf:
            return
        fresh = [l for l in step_ledgers(self._buf)
                 if l["step"] > self._last_step]
        for led in fresh:
            self._export(session.registry, led)
        if fresh:
            self._last_step = fresh[-1]["step"]
            cutoff = fresh[-1]["start_us"] + fresh[-1]["wall_us"]
            self._buf = [ev for ev in self._buf
                         if ev["ts"] + ev["dur"] > cutoff]

    def _export(self, reg, led: Dict[str, Any]) -> None:
        wall_s = led["wall_us"] / 1e6
        buckets = led["buckets"]
        if led["wall_us"] > 0:
            # the partition sums exactly by construction; a violation of
            # the configured tolerance means the ledger math broke, and a
            # silently wrong time ledger is worse than none
            err = abs(sum(buckets.values()) - led["wall_us"]) / led["wall_us"]
            if err > self.cfg.tolerance:
                reg.counter("goodput/closure_violations").inc()
                logger.warning(
                    f"goodput: step {led['step']} ledger buckets sum to "
                    f"{err:.1%} off its wall window (tolerance "
                    f"{self.cfg.tolerance:.0%}) — ledger math bug?")
        reg.gauge("goodput/step").set(led["step"])
        reg.gauge("goodput/step_wall_s").set(wall_s)
        reg.histogram("goodput/step_wall_seconds").observe(wall_s)
        for b in BUCKETS:
            frac = buckets.get(b, 0.0) / led["wall_us"] if led["wall_us"] else 0.0
            reg.gauge("goodput/fraction", labels={"bucket": b}).set(frac)
            self._totals[b] += buckets.get(b, 0.0)
        gf = goodput_fraction(buckets)
        if gf is not None:
            reg.gauge("goodput/goodput_fraction").set(gf)
        job_gf = goodput_fraction(self._totals)
        if job_gf is not None:
            reg.gauge("goodput/job_goodput_fraction").set(job_gf)
        tb = top_badput(buckets)
        if tb is not None and led["wall_us"]:
            reg.gauge("goodput/top_badput_fraction").set(tb[1] / led["wall_us"])
        mfu = self._mfu(wall_s)
        if mfu is not None:
            reg.gauge("goodput/mfu").set(mfu)

    def _mfu(self, step_wall_s: float) -> Optional[float]:
        """MFU of one global step: flops-per-batch (the flops profiler's
        jaxpr walk, computed once and cached as a ratio against peak ×
        device count) over the step's wall seconds."""
        if step_wall_s <= 0 or self.engine is None:
            return None
        if self._mfu_denom is None:
            try:
                import jax

                from deepspeed_tpu.accelerator import get_accelerator

                flops = float(self.engine._estimate_step_flops())
                peak = float(get_accelerator().peak_flops())
                ndev = jax.device_count()
                self._mfu_denom = (flops / (peak * ndev)
                                   if flops > 0 and peak > 0 else 0.0)
            except Exception as e:
                logger.warning(f"goodput: MFU estimate unavailable: {e}")
                self._mfu_denom = 0.0
        if not self._mfu_denom:
            return None
        return self._mfu_denom / step_wall_s

    # ----------------------------------------------------------- attribution
    def attribution(self, events: Optional[List[dict]] = None,
                    timed_steps: Optional[int] = None) -> Dict[str, Any]:
        """The ``goodput`` block of a perf-ledger entry: per-step ledgers
        of the timed window (last ``timed_steps`` complete steps), the
        summed buckets, and the window's goodput fraction. Buckets sum to
        each step's measured wall window exactly (asserted at 5% against
        the train span samples by tests/unit/test_goodput.py)."""
        if events is None:
            session = _telemetry.get_session()
            events = list(getattr(session.tracer, "events", []) or []) \
                if session is not None else []
        ledgers = step_ledgers(events)
        if timed_steps and timed_steps > 0:
            ledgers = ledgers[-timed_steps:]
        if not ledgers:
            return {}
        total = sum_buckets([l["buckets"] for l in ledgers])
        out: Dict[str, Any] = {
            "per_step": [
                {"step": l["step"],
                 "wall_us": round(l["wall_us"], 1),
                 "buckets_us": {b: round(v, 1)
                                for b, v in l["buckets"].items() if v > 0}}
                for l in ledgers],
            "buckets_us": {b: round(v, 1) for b, v in total.items() if v > 0},
        }
        gf = goodput_fraction(total)
        if gf is not None:
            out["goodput_fraction"] = round(gf, 4)
        tb = top_badput(total)
        if tb is not None:
            out["top_badput"] = tb[0]
        return out
