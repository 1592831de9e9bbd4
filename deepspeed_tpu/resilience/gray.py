"""ds_gray — fail-slow defense: straggler blame, microprobe confirmation, evict.

The resilience stack catches devices that die (watchdog), lie (ds_sentry)
and disappear (rewind/resize) — but a device that merely gets SLOW trips
no alarm: a thermally-throttled chip, a flaky link or a busy host drags
every blocking collective to its pace, the loss stays perfect, every
guard stays green, and the fleet quietly runs at the straggler's speed.
At wire-speed collectives one fail-slow participant caps the whole
fleet's bus bandwidth — gray failure is the last unhandled fault class,
and the evidence was already being recorded and ignored.

Three mechanisms, one manager (the fail-slow sibling of ds_sentry):

* **evidence fusion** — a suspicion EWMA
  (``s' = hysteresis*s + (1-hysteresis)*evidence``) fed per step by the
  comms logger's recent-window skew (``CommsLogger.straggler_report``,
  now exported as ``comm/skew{op=,size=}`` gauges), the rank-local
  ``straggler_wait`` excess the comm layer stamps beyond its
  fastest-half baseline (``comm/straggler_excess_us``), and watchdog
  near-miss margins (a step that finishes just under the deadline).
  Hysteresis plus a ``min_evidence`` floor of distinct evidence-bearing
  steps mean a recompile spike or a one-off GC pause can never reach a
  probe, let alone a verdict — the same startup-floor discipline the
  watchdog uses.
* **microprobe confirmation** — skew evidence is device-ANONYMOUS (every
  rank's collectives stretch when anyone straggles), so past the blame
  threshold the manager runs a tiny synchronized probe OFF the step
  path: a per-device local matmul (slow-compute) and a pairwise
  neighbor transfer (slow-link); a device outlying in both phases is
  slow-HOST. The probe runs under a ``cat="probe"`` span, priced as the
  goodput ``probe`` badput bucket and gated by ``ds_perf gate`` as
  ``gray_overhead`` — suspicion-triggered probes are rate-limited by
  ``probe_interval``, and an inconclusive probe DECAYS suspicion (the
  fleet-wide pause that inflated the windows was not a device).
* **verdict & action ladder** — observe → warn (``warn_threshold``) →
  after ``probe_confirmations`` consecutive probes name the same
  device, a :class:`GrayVerdict` (device, kind, evidence window, probe
  tables) lands in telemetry and the elastic agent's
  ``restart_log.jsonl``; with ``evict: true`` and the resize path
  armed, the culprit is quarantined via the same
  TBS-divisibility-stepped :class:`FleetResizeEvent` shrink ds_sentry
  uses, and the run resumes resharded on survivors that no longer wait
  for the slow chip. ``evict: false`` (or resize unarmed) is
  report-only; more verdicts than ``max_verdicts`` escalates to
  :class:`GrayError`.

Drillable end to end: the chaos injector's ``slow_device`` fault class
(resilience/chaos.py) persistently inflates one simulated device's
collective waits — deterministic per seed — so the whole blame → probe →
evict → recover chain runs in tests without a throttled chip
(tests/unit/test_gray.py).

STRICT no-op contract: this module is imported only when the ``gray``
ds_config block is present and enabled; without it there are no probes,
no suspicion state, and the lowered step HLO is byte-identical (asserted
in tests).
"""

from __future__ import annotations

import json
import os
import sys
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

from deepspeed_tpu.utils.logging import log_dist, logger

# a probe phase must outlie its fleet fastest-half baseline by this
# factor before it counts — fleet-wide noise (CPU-simulated devices
# jitter plenty) must classify as inconclusive, not as a culprit
PROBE_OUTLIER = 2.0

# near-miss margin: a step landing within this fraction of the watchdog
# deadline is evidence the fleet is running slower than its own history
NEAR_MISS_FRACTION = 0.8


class GrayError(RuntimeError):
    """Fail-slow degradation the manager cannot act on any further: more
    confirmed verdicts than ``gray.max_verdicts`` tolerates. The fleet
    (or its fabric) is degrading faster than eviction can help — replace
    the workers instead of shrinking again."""


@dataclass
class GrayVerdict:
    """One confirmed fail-slow event: the step it was confirmed on, the
    device the probes blamed, the slowness kind (slow-compute /
    slow-link / slow-host), and the evidence trail (suspicion history +
    per-device probe tables)."""
    step: int
    device: int
    kind: str
    evidence: Dict[str, Any] = field(default_factory=dict)

    def to_record(self) -> dict:
        from deepspeed_tpu.telemetry.events import stamp_envelope

        return stamp_envelope(
            {"event": "gray_verdict", "step": int(self.step),
             "device": int(self.device), "kind": self.kind,
             "evidence": self.evidence, "wall_ts": time.time()},
            kind="gray_verdict", severity="error")


def classify_probe(compute_us: Dict[int, float], link_us: Dict[int, float],
                   outlier: float = PROBE_OUTLIER
                   ) -> Optional[Tuple[int, str, float]]:
    """Classify one probe's per-device phase timings (µs) into a culprit.

    Each phase is normalized against its own fleet fastest-half mean (the
    same trimmed baseline the comm layer's straggler excess uses — robust
    to the outlier itself). A device whose worst phase ratio clears
    ``outlier`` is a suspect; among suspects the worst ratio wins:

    * both phases outlying COMPARABLY (within ``outlier`` of each other)
      → ``slow-host``: a dragged host slows everything it dispatches by
      a similar factor, while a throttled chip whose link phase merely
      jitters past the outlier bar shows a lopsided spread — the
      dominant phase names the kind then;
    * compute outlying (or worse than link) → ``slow-compute``;
    * link outlying alone (or worse than compute) → ``slow-link``.

    Returns ``(device, kind, worst_ratio)`` or None when no device
    outlies — the INCONCLUSIVE result a fleet-wide pause must produce.
    Pure: unit-testable without any device.
    """
    def ratios(table: Dict[int, float]) -> Dict[int, float]:
        vals = sorted(table.values())
        if not vals:
            return {}
        fastest = vals[:max(1, len(vals) // 2)]
        base = sum(fastest) / len(fastest)
        if base <= 0.0:
            return {}
        return {d: v / base for d, v in table.items()}

    rc = ratios(compute_us)
    rl = ratios(link_us)
    best: Optional[Tuple[int, str, float]] = None
    for d in sorted(set(rc) | set(rl)):
        c = rc.get(d, 0.0)
        l = rl.get(d, 0.0)
        worst = max(c, l)
        if worst < outlier:
            continue
        if c >= outlier and l >= outlier and \
                max(c, l) < outlier * min(c, l):
            kind = "slow-host"
        elif c >= l:
            kind = "slow-compute"
        else:
            kind = "slow-link"
        if best is None or worst > best[2]:
            best = (d, kind, worst)
    return best


def _registry():
    from deepspeed_tpu import telemetry

    return telemetry.get_registry()


def _tracer():
    from deepspeed_tpu import telemetry

    return telemetry.get_tracer()


class GrayManager:
    """Per-engine driver of the fail-slow defense: fuse evidence → build
    suspicion → probe → confirm → warn/evict. All host-side wall-clock
    work — unlike ds_sentry it needs nothing from the compiled program,
    so it stands down on no step path."""

    def __init__(self, engine, cfg):
        self.engine = engine
        self.cfg = cfg
        self.suspicion = 0.0
        self.evidence_steps = 0          # consecutive-ish evidence count
        self.probes = 0
        self.verdicts = 0
        self.warnings = 0
        self.last_verdict: Optional[GrayVerdict] = None
        self._last_probe_step = -(10 ** 9)
        self._streak: list = []          # consecutive probe namings
        self._above_warn = False
        self._recent_evidence: deque = deque(maxlen=32)
        # baseline against PRE-EXISTING state, not zero: after an evict
        # restart the registry's cumulative straggler-excess counter and
        # the comms logger's latency windows survive the engine rebuild
        # still carrying the old culprit's drag — a fresh manager that
        # read them as new evidence would re-accuse the healthy survivor
        # fleet (the restart-pause false positive)
        from deepspeed_tpu.comm import comm as _comm

        self._last_excess_us = float(
            _registry().counter("comm/straggler_excess_us").value)
        if _comm.comms_logger is not None:
            _comm.comms_logger.reset_straggler_windows()
        reg = _registry()
        reg.gauge("gray/blame_threshold").set(float(cfg.blame_threshold))
        reg.gauge("gray/suspicion").set(0.0)
        log_dist(
            f"gray: fail-slow defense armed (blame_threshold="
            f"{cfg.blame_threshold}, hysteresis={cfg.hysteresis}, "
            f"min_evidence={cfg.min_evidence}, probe_interval="
            f"{cfg.probe_interval}, evict={cfg.evict})", ranks=[0])

    # ------------------------------------------------------------ evidence
    def _skew_evidence(self) -> Tuple[float, list]:
        """Straggler skew over the comms logger's recent windows: any
        (op, size) key whose window has enough samples AND whose
        max-vs-mean skew clears ``suspicion_threshold`` is evidence the
        fleet keeps blocking on a late participant."""
        from deepspeed_tpu.comm import comm as _comm

        cl = _comm.comms_logger
        if cl is None:
            return 0.0, []
        floor = cl.STRAGGLER_MIN_SAMPLES
        rows = [(op, size, n, mean, worst, skew)
                for op, size, n, mean, worst, skew in cl.straggler_report()
                if n >= floor and skew >= float(self.cfg.suspicion_threshold)]
        return (1.0 if rows else 0.0), rows[:4]

    def _excess_evidence(self) -> Tuple[float, float]:
        """Rank-local straggler excess: the comm layer's cumulative
        ``comm/straggler_excess_us`` counter (stamped when a collective
        lands beyond 2x its fastest-half baseline) moved this step."""
        cur = float(_registry().counter("comm/straggler_excess_us").value)
        delta = cur - self._last_excess_us
        self._last_excess_us = cur
        return (1.0 if delta > 0.0 else 0.0), delta

    def _near_miss_evidence(self) -> Tuple[float, float]:
        """Watchdog near-miss: the last step finished within
        ``NEAR_MISS_FRACTION`` of the armed deadline — the fleet is
        running close to what its own history calls a hang."""
        wd = getattr(self.engine, "_watchdog", None)
        durations = getattr(wd, "_durations", None)
        if not durations:
            return 0.0, 0.0
        last = float(durations[-1])
        deadline = float(wd.deadline_s())
        if deadline <= 0.0 or last < NEAR_MISS_FRACTION * deadline:
            return 0.0, 0.0
        return 1.0, last / deadline

    def update_suspicion(self, evidence: float) -> float:
        """One EWMA step: ``s' = h*s + (1-h)*evidence``. Evidence-bearing
        steps also raise the ``min_evidence`` floor counter; quiet steps
        lower it — a lone spike decays out of both before any probe can
        fire. Factored out so the false-positive matrix is testable
        without a live engine."""
        h = float(self.cfg.hysteresis)
        self.suspicion = h * self.suspicion + (1.0 - h) * float(evidence)
        if evidence > 0.0:
            self.evidence_steps += 1
        else:
            self.evidence_steps = max(0, self.evidence_steps - 1)
        return self.suspicion

    def should_probe(self, step: int) -> bool:
        """Probe when an unconditional cadence says so (``probe_every``,
        the CI pricing mode), or when suspicion clears the blame
        threshold with the evidence floor met and the probe rate limit
        open."""
        pe = int(self.cfg.probe_every)
        if pe > 0 and step % pe == 0:
            return True
        return (self.suspicion >= float(self.cfg.blame_threshold)
                and self.evidence_steps >= int(self.cfg.min_evidence)
                and step - self._last_probe_step >= int(self.cfg.probe_interval))

    # ---------------------------------------------------------------- hook
    def after_step(self, step: int, metrics) -> None:
        """Called AFTER the step landed (post sdc hook, pre rewind
        snapshot). Fuses this step's evidence into the suspicion EWMA and
        walks the action ladder. May raise :class:`FleetResizeEvent`
        (quarantine-evict) or :class:`GrayError` (escalation)."""
        from deepspeed_tpu.comm import comm as _comm

        # the skew windows ARE the primary evidence: if nothing armed the
        # comms logger (no comms_logger block, telemetry-only run), arm it
        # now — append cost is O(1) per eager collective
        if _comm.comms_logger is None:
            _comm.configure(enabled=True)
        skew_ev, skew_rows = self._skew_evidence()
        excess_ev, excess_us = self._excess_evidence()
        miss_ev, miss_margin = self._near_miss_evidence()
        evidence = max(skew_ev, excess_ev, miss_ev)
        self.update_suspicion(evidence)
        if evidence > 0.0:
            self._recent_evidence.append(
                {"step": int(step), "skew": skew_rows,
                 "straggler_excess_us": round(excess_us, 1),
                 "near_miss_margin": round(miss_margin, 3)})
        reg = _registry()
        reg.gauge("gray/suspicion").set(self.suspicion)
        reg.gauge("gray/evidence_steps").set(float(self.evidence_steps))
        self._maybe_warn(step)
        if not self.should_probe(step):
            return
        self._last_probe_step = step
        compute_us, link_us = self._run_probe(step)
        named = classify_probe(compute_us, link_us)
        if named is None:
            # a fleet-wide pause (recompile, checkpoint, GC) inflated the
            # windows but no DEVICE outlies — decay hard and start the
            # confirmation streak over
            self._streak = []
            self.suspicion *= float(self.cfg.hysteresis)
            reg.gauge("gray/suspicion").set(self.suspicion)
            return
        device, kind, ratio = named
        reg.gauge("gray/suspect_device").set(float(device))
        self._streak.append({"device": int(device), "kind": kind,
                             "ratio": round(ratio, 2), "step": int(step)})
        need = int(self.cfg.probe_confirmations)
        tail = self._streak[-need:]
        if len(tail) < need or any(t["device"] != device for t in tail):
            return
        evidence_trail = {
            "suspicion": round(self.suspicion, 4),
            "evidence_steps": int(self.evidence_steps),
            "window": list(self._recent_evidence),
            "probes": list(self._streak),
            "probe_compute_us": {str(d): round(v, 1)
                                 for d, v in compute_us.items()},
            "probe_link_us": {str(d): round(v, 1)
                              for d, v in link_us.items()},
        }
        self._handle_verdict(step, device, kind, evidence_trail)

    # ---------------------------------------------------------------- warn
    def _maybe_warn(self, step: int) -> None:
        warn_at = float(self.cfg.warn_threshold)
        if warn_at <= 0.0:
            return
        if self.suspicion >= warn_at and not self._above_warn:
            self._above_warn = True
            self.warnings += 1
            _registry().counter("gray/warnings").inc()
            _tracer().instant("gray_warn", cat="resilience", step=step,
                              suspicion=round(self.suspicion, 4))
            logger.warning(
                f"gray: suspicion {self.suspicion:.2f} crossed "
                f"warn_threshold {warn_at} at step {step} — the fleet "
                "keeps blocking on a late participant (probe pending "
                "confirmation)")
        elif self.suspicion < warn_at:
            self._above_warn = False

    # --------------------------------------------------------------- probe
    def _run_probe(self, step: int) -> Tuple[Dict[int, float],
                                             Dict[int, float]]:
        """The microprobe: OFF the step path, two tiny synchronized
        phases over the engine's mesh devices. Phase 1 times a local
        ``probe_size``² matmul per device (slow-compute evidence); phase
        2 times a pairwise neighbor transfer, charged to the SOURCE
        device (slow-link evidence). Runs under a ``cat="probe"`` span so
        the goodput ledger prices it as the ``probe`` badput bucket and
        ``ds_perf gate`` can hold ``gray_overhead`` to budget."""
        import jax
        import numpy as np

        from deepspeed_tpu.resilience import chaos as _chaos

        self.probes += 1
        _registry().counter("gray/probes").inc()
        inj = _chaos.active_injector()
        n = int(self.cfg.probe_size)
        x = np.ones((n, n), np.float32)
        devices = sorted(self.engine.mesh.devices.flatten(),
                         key=lambda d: int(d.id))
        compute_us: Dict[int, float] = {}
        link_us: Dict[int, float] = {}
        with _tracer().span("probe", cat="probe", step=step):
            resident = {}
            for d in devices:
                t0 = time.perf_counter()
                a = jax.device_put(x, d)
                (a @ a).block_until_ready()
                el = time.perf_counter() - t0
                if inj is not None:
                    extra = inj.gray_probe_extra_s(int(d.id), el, "compute")
                    if extra > 0.0:
                        time.sleep(extra)
                        el += extra
                compute_us[int(d.id)] = el * 1e6
                resident[int(d.id)] = a
            for i, d in enumerate(devices):
                nxt = devices[(i + 1) % len(devices)]
                t0 = time.perf_counter()
                jax.device_put(resident[int(d.id)],
                               nxt).block_until_ready()
                el = time.perf_counter() - t0
                if inj is not None:
                    extra = inj.gray_probe_extra_s(int(d.id), el, "link")
                    if extra > 0.0:
                        time.sleep(extra)
                        el += extra
                link_us[int(d.id)] = el * 1e6
        return compute_us, link_us

    # ------------------------------------------------------------- verdict
    def _handle_verdict(self, step: int, device: int, kind: str,
                        evidence: dict) -> None:
        eng = self.engine
        self.verdicts += 1
        self.last_verdict = GrayVerdict(step=step, device=device, kind=kind,
                                        evidence=evidence)
        reg = _registry()
        reg.counter("gray/verdicts", labels={"device": str(device)}).inc()
        reg.gauge("gray/last_verdict_step").set(float(step))
        reg.gauge("gray/last_verdict_device").set(float(device))
        _tracer().instant("gray_verdict", cat="resilience", step=step,
                          device=device, kind=kind)
        _bb = sys.modules.get("deepspeed_tpu.blackbox")
        if _bb is not None:
            _bb.record("gray_verdict", "error",
                       {"device": int(device), "kind": kind,
                        "suspicion": evidence.get("suspicion"),
                        "verdicts": self.verdicts}, step=step)
        logger.error(
            f"gray: VERDICT at step {step} — device {device} confirmed "
            f"{kind} by {len(evidence.get('probes', []))} probe(s) after "
            f"suspicion {evidence.get('suspicion')} (the fleet has been "
            "pacing its collectives to this chip)")
        self._persist_verdict(self.last_verdict)
        if self.verdicts > int(self.cfg.max_verdicts):
            raise GrayError(
                f"gray: {self.verdicts} fail-slow verdict(s) exceed "
                f"gray.max_verdicts={self.cfg.max_verdicts} — the fleet is "
                "degrading faster than eviction helps; replace the workers "
                "instead of shrinking again")
        if self.cfg.evict and \
                getattr(eng, "_elastic_resize", None) is not None:
            self._quarantine_and_evict(device)      # raises FleetResizeEvent
        else:
            # report-only rung: the verdict is on record (telemetry +
            # restart_log); reset the scorer so the SAME drag must
            # re-accumulate evidence before the next verdict
            self.suspicion = 0.0
            self.evidence_steps = 0
            self._streak = []
            reg.gauge("gray/suspicion").set(0.0)
            log_dist(
                f"gray: report-only (evict={bool(self.cfg.evict)}, "
                f"resize {'armed' if getattr(eng, '_elastic_resize', None) is not None else 'unarmed'}) "
                f"— device {device} stays in the fleet; verdict recorded",
                ranks=[0])

    def _persist_verdict(self, verdict: GrayVerdict) -> None:
        """Append the verdict to the same ``restart_log.jsonl`` timeline
        the elastic agent and ds_sentry write (readers skip records whose
        ``event`` they don't know)."""
        from deepspeed_tpu import telemetry

        session = telemetry.get_session()
        out_dir = getattr(session, "output_dir", None) if session else None
        if not out_dir:
            return
        try:
            path = os.path.join(str(out_dir), "restart_log.jsonl")
            with open(path, "a") as f:
                f.write(json.dumps(verdict.to_record(), default=str) + "\n")
        except OSError as e:
            logger.warning(f"gray: could not persist verdict ({e})")

    # ------------------------------------------------------------ eviction
    def _quarantine_and_evict(self, device: int) -> None:
        """Same shape as ds_sentry's quarantine: the culprit leaves the
        survivor set, the post-event world steps down to the largest
        train_batch_size-divisible count, and the raised
        :class:`FleetResizeEvent` hands the restart to the elastic agent
        — survivors come back resharded and no longer pace themselves to
        the slow chip."""
        from deepspeed_tpu.elasticity import resize as rz

        eng = self.engine
        from_world = len(rz.survivor_devices())
        rz.quarantine_device(device)
        pool = rz.survivor_devices()
        tbs = int(eng.train_batch_size())
        to_world = len(pool)
        while to_world > 1 and tbs % to_world:
            to_world -= 1
        rz.set_fleet_target(to_world)
        _registry().counter("gray/evictions",
                            labels={"device": str(device)}).inc()
        logger.warning(
            f"gray: quarantining fail-slow device {device} — evicting via "
            f"fleet shrink {from_world} -> {to_world} device(s) "
            f"(train_batch_size {tbs} picks the largest divisible "
            "survivor world)")
        raise rz.FleetResizeEvent("shrink", from_world, to_world)
