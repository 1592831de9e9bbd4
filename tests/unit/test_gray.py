"""ds_gray tests — fail-slow defense: straggler blame, microprobe, evict.

All CPU-only on the faked 8-device mesh; the chaos injector's
``slow_device`` fault class stands in for a thermally-throttled chip /
flaky link by inflating one simulated device's collective waits. The
matrix the acceptance criteria name:

* config lint: an armed ``slow_device`` fault without an inflation
  factor is refused; gray knobs get did-you-mean; the schema pass knows
  the block (gray-without-telemetry is an error, evict-without-resize
  an info);
* strict no-op: without the ``gray`` block the module is never imported
  and the lowered step HLO is byte-identical — and because the defense
  is entirely host-side, an ARMED block lowers the same HLO too;
* the false-positive matrix: a lone evidence spike and a
  recompile-burst pattern decay below the blame threshold and never
  reach a probe (hysteresis + min_evidence floor);
* ``classify_probe`` units: slow-compute / slow-link / slow-host /
  inconclusive, worst-ratio-wins;
* THE evict drill: device 3 of 8 runs 5x slow from step 11 — blamed
  from the comm windows, confirmed by two probes, evicted via the
  ds_sentry-shaped FleetResizeEvent shrink 8->6, post-evict step wall
  collapses >= 5x and the 6 survivors out-throughput the dragged 8,
  everything priced in ``ds_prof goodput`` and rendered by
  ``ds_metrics``;
* the report-only + escalation drill (``evict: false`` records verdicts
  without touching the fleet; past ``max_verdicts`` a GrayError);
* the randomized slow-device sweep and the probe / flight-recorder
  pricing run (both in tests/slow_tests.txt).
"""

import itertools
import json
import os
import signal
import subprocess
import sys
import time
import types

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.elasticity import DSElasticAgent
from deepspeed_tpu.models.simple import SimpleModel
from deepspeed_tpu.resilience import (ChaosInjector, install_chaos,
                                      uninstall_chaos)

pytestmark = pytest.mark.gray

HIDDEN = 16
TBS = 24                # divides 8 and 6 — the evict-drill worlds
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GRAY_MOD = "deepspeed_tpu.resilience.gray"

# the drill-speed knobs: tighter than the production defaults so the
# blame -> probe -> confirm ladder runs in a dozen steps instead of a
# hundred — the MECHANISM under test is identical
GRAY_FAST = {"blame_threshold": 0.3, "min_evidence": 2, "probe_interval": 2,
             "probe_confirmations": 2, "warn_threshold": 0.1}

# slow fault: device 3 turns 5x slow at chaos step 11 — late enough that
# the comm windows hold a fast baseline (STRAGGLER_MIN_SAMPLES) first,
# with a floor making each dragged collective decisively slow on CPU
SLOW_CHAOS = {"enabled": True, "seed": 7, "slow_from_step": 11,
              "slow_device": 3, "slow_factor": 5.0, "slow_min_s": 0.1}

# zero3 + the serial overlap schedule: the per-step eager gather phase
# is what record_phase_span times, feeding the straggler windows the
# evidence chain starts from
SERIAL_ZERO3 = {"zero_optimization": {"stage": 3},
                "overlap": {"schedule": "serial"}}


@pytest.fixture(autouse=True)
def _clean_slate():
    """Fresh chaos, fresh tier-0 ring, full fleet, untouched handlers —
    and no leaked comms logger (gray arms the global one lazily)."""
    orig = {s: signal.getsignal(s) for s in (signal.SIGTERM, signal.SIGINT)}
    yield
    uninstall_chaos()
    comm.comms_logger = None
    rw = sys.modules.get("deepspeed_tpu.resilience.rewind")
    if rw is not None:
        rw.clear_ram_snapshots()
    rz = sys.modules.get("deepspeed_tpu.elasticity.resize")
    if rz is not None:
        rz.clear_fleet_events()
    for s, h in orig.items():
        signal.signal(s, h)


def plain_engine(extra=None, rewind=None):
    """An engine over the FULL backend mesh."""
    comm.cdb = None
    cfg = {"train_batch_size": TBS,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "steps_per_print": 0}
    if rewind is not None:
        cfg["rewind"] = rewind
    if extra:
        cfg.update(extra)
    engine, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=HIDDEN, nlayers=2), config=cfg)
    return engine


def survivor_engine(extra=None, rewind=None):
    """An engine whose dp mesh spans the simulated fleet's survivors,
    elastic resize armed — what the evict drill's factory builds."""
    from deepspeed_tpu.elasticity import resize as rz

    comm.cdb = None
    cfg = {"train_batch_size": TBS,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "steps_per_print": 0,
           "elasticity": {"resize": {"enabled": True}}}
    if rewind is not None:
        cfg["rewind"] = rewind
    if extra:
        cfg.update(extra)
    engine, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=HIDDEN, nlayers=2), config=cfg,
        mpu=types.SimpleNamespace(mesh=rz.survivor_mesh()))
    return engine


def batch(seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(TBS, HIDDEN).astype(np.float32),
            rng.randn(TBS, HIDDEN).astype(np.float32))


def batch_seq():
    return (batch(seed=i) for i in itertools.count())


def _mgr(**over):
    """A GrayManager off any engine — the scorer is host-side state, so
    the false-positive matrix drives it directly."""
    from deepspeed_tpu.resilience.gray import GrayManager
    from deepspeed_tpu.runtime.config import GrayConfig

    return GrayManager(types.SimpleNamespace(), GrayConfig(**over))


# ------------------------------------------------------------ config lint
class TestConfigValidation:
    def test_slow_armed_without_factor_refused(self):
        with pytest.raises(ValueError, match="slow_factor"):
            plain_engine(extra={"resilience": {
                "chaos": {"enabled": True, "slow_from_step": 3}}})

    def test_slow_rate_armed_without_factor_refused(self):
        with pytest.raises(ValueError, match="slow_factor"):
            plain_engine(extra={"resilience": {
                "chaos": {"enabled": True, "slow_rate": 0.5}}})

    def test_slow_bad_kind_refused(self):
        with pytest.raises(ValueError, match="slow_kind"):
            plain_engine(extra={"resilience": {
                "chaos": {"enabled": True, "slow_from_step": 3,
                          "slow_factor": 5.0, "slow_kind": "thermal"}}})

    def test_unknown_gray_key_did_you_mean(self):
        with pytest.raises(ValueError, match="probe_interval"):
            plain_engine(extra={"gray": {"probe_intervall": 5}})

    def test_degenerate_hysteresis_refused(self):
        # hysteresis 0 = no memory (every spike is a verdict candidate),
        # hysteresis 1 = suspicion can never move; both are refused
        for h in (0.0, 1.0):
            with pytest.raises(ValueError, match="hysteresis"):
                plain_engine(extra={"gray": {"hysteresis": h}})

    def test_probe_interval_zero_refused(self):
        with pytest.raises(ValueError, match="probe_interval"):
            plain_engine(extra={"gray": {"probe_interval": 0}})

    def test_schema_pass_knows_the_block(self):
        from deepspeed_tpu.analysis.schema import walk_config

        base = {"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
        # did-you-mean on a typo'd gray key
        findings, _ = walk_config({**base, "gray": {"blame_treshold": 0.5}})
        assert any("blame_threshold" in f.message for f in findings)
        # gray without telemetry: verdicts/evidence unrecordable -> error
        findings, _ = walk_config({**base, "gray": {}})
        bad = [f for f in findings
               if f.citation == "gray vs telemetry.output_dir"]
        assert bad and bad[0].severity == "error"
        with_tel = {**base, "telemetry": {"enabled": True}, "gray": {}}
        findings, _ = walk_config(with_tel)
        assert not any(f.citation == "gray vs telemetry.output_dir"
                       for f in findings)
        # evict without the resize path: every verdict is report-only
        findings, _ = walk_config(with_tel)
        info = [f for f in findings
                if f.citation == "gray.evict vs elasticity.resize"]
        assert info and info[0].severity == "info"
        findings, _ = walk_config(
            {**with_tel, "elasticity": {"resize": {"enabled": True}}})
        assert not any(f.citation == "gray.evict vs elasticity.resize"
                       for f in findings)


# ------------------------------------------------------------ strict no-op
class TestStrictNoOp:
    def _without_module(self):
        return {m: sys.modules.pop(m) for m in list(sys.modules)
                if m == GRAY_MOD}

    def test_block_absent_never_imports_module(self):
        saved = self._without_module()
        try:
            engine = plain_engine()
            engine.train_batch(batch())
            assert engine._gray is None
            assert GRAY_MOD not in sys.modules
        finally:
            sys.modules.update(saved)

    def test_enabled_false_never_imports_module(self):
        saved = self._without_module()
        try:
            engine = plain_engine(extra={"gray": {"enabled": False}})
            engine.train_batch(batch())
            assert engine._gray is None
            assert GRAY_MOD not in sys.modules
        finally:
            sys.modules.update(saved)

    def test_step_hlo_byte_identical_even_armed(self):
        """Absent == enabled:false down to the lowered HLO bytes — and
        because the whole defense is host-side (evidence, probes and
        verdicts never touch the compiled program, unlike ds_sentry's
        in-step checksum), an ARMED block lowers the same bytes too."""
        def lowered(extra):
            engine = plain_engine(extra=extra)
            b = engine._shard_batch(batch())
            abstract = lambda t: jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), t)
            with engine.mesh:
                return engine._get_compiled_train_batch(1).lower(
                    abstract(engine.state), abstract(b)).as_text()

        absent = lowered(None)
        off = lowered({"gray": {"enabled": False}})
        armed = lowered({"gray": {}})
        assert absent == off
        assert armed == absent


# ----------------------------------------------------- false-positive matrix
class TestFalsePositiveMatrix:
    def test_single_spike_decays_below_blame(self):
        """A lone evidence spike (one GC pause) decays out of both the
        EWMA and the evidence floor before any probe can fire."""
        m = _mgr()
        m.update_suspicion(1.0)
        assert m.suspicion < m.cfg.blame_threshold
        assert not m.should_probe(1)
        for step in range(2, 12):
            m.update_suspicion(0.0)
            assert not m.should_probe(step)
        assert m.suspicion < 0.05
        assert m.evidence_steps == 0

    def test_recompile_burst_pattern_never_probes(self):
        """Recompile/checkpoint pauses come in short bursts; with the
        default min_evidence floor a 2-on/2-off pattern never accumulates
        enough distinct evidence steps to probe, and suspicion stays
        under the blame threshold."""
        m = _mgr()
        step = 0
        for _ in range(6):
            for ev in (1.0, 1.0, 0.0, 0.0):
                step += 1
                m.update_suspicion(ev)
                assert not m.should_probe(step), (step, m.suspicion)
        assert m.suspicion < m.cfg.blame_threshold
        assert m.evidence_steps < m.cfg.min_evidence

    def test_sustained_evidence_probes_with_rate_limit(self):
        m = _mgr()
        for step in range(1, 9):
            m.update_suspicion(1.0)
        assert m.suspicion >= m.cfg.blame_threshold
        assert m.should_probe(100)
        m._last_probe_step = 100
        assert not m.should_probe(101)           # probe_interval rate limit
        assert m.should_probe(100 + int(m.cfg.probe_interval))

    def test_probe_every_cadence_ignores_suspicion(self):
        m = _mgr(probe_every=2)
        assert m.suspicion == 0.0
        assert m.should_probe(4)
        assert not m.should_probe(5)

    def test_inconclusive_probe_is_the_recompile_defense(self):
        """A fleet-wide pause inflates every device's window equally —
        classify_probe must return None (no outlier), which resets the
        confirmation streak in after_step."""
        from deepspeed_tpu.resilience.gray import classify_probe

        paused = {d: 5000.0 + 10 * d for d in range(8)}   # uniform-ish
        assert classify_probe(paused, paused) is None


# ------------------------------------------------------- classify_probe units
class TestClassifyProbe:
    def test_slow_compute(self):
        from deepspeed_tpu.resilience.gray import classify_probe

        got = classify_probe({0: 10, 1: 11, 2: 10, 3: 55},
                             {0: 5, 1: 5, 2: 6, 3: 5})
        assert got == (3, "slow-compute", pytest.approx(5.5, abs=0.5))

    def test_slow_link(self):
        from deepspeed_tpu.resilience.gray import classify_probe

        got = classify_probe({0: 10, 1: 11, 2: 10, 3: 10},
                             {0: 5, 1: 5, 2: 6, 3: 40})
        assert got[0] == 3 and got[1] == "slow-link"

    def test_slow_host_outlies_both_phases(self):
        from deepspeed_tpu.resilience.gray import classify_probe

        got = classify_probe({0: 10, 1: 10, 2: 10, 3: 50},
                             {0: 5, 1: 5, 2: 5, 3: 30})
        assert got[0] == 3 and got[1] == "slow-host"

    def test_lopsided_spread_is_not_slow_host(self):
        """A throttled chip's massive compute ratio plus a link phase
        that merely jitters past the outlier bar must classify by the
        DOMINANT phase — slow-host needs both phases dragged comparably
        (a real slow host slows everything it dispatches similarly)."""
        from deepspeed_tpu.resilience.gray import classify_probe

        got = classify_probe({0: 10, 1: 10, 2: 10, 3: 900},
                             {0: 5, 1: 5, 2: 5, 3: 13})
        assert got[0] == 3 and got[1] == "slow-compute"

    def test_worst_ratio_wins_among_suspects(self):
        from deepspeed_tpu.resilience.gray import classify_probe

        got = classify_probe({0: 25, 1: 10, 2: 10, 3: 90, 4: 10, 5: 10},
                             {d: 5 for d in range(6)})
        assert got[0] == 3

    def test_empty_tables_inconclusive(self):
        from deepspeed_tpu.resilience.gray import classify_probe

        assert classify_probe({}, {}) is None
        assert classify_probe({0: 0.0, 1: 0.0}, {}) is None


# ------------------------------------------------------- THE evict drill
@pytest.mark.chaos
class TestEvictDrill:
    @pytest.mark.incident_drill(device=3)
    def test_THE_drill_slow_device_blamed_probed_evicted_8_to_6(
            self, tmp_path, incident_forensics):
        """The acceptance drill, end to end: device 3 of 8 turns 5x slow
        at step 11 — the comm windows stamp straggler excess, suspicion
        crosses the blame threshold, two microprobes name device 3
        slow-compute, the verdict lands in restart_log.jsonl and the
        fleet shrinks 8->6 via FleetResizeEvent under the elastic agent
        (24 % 7 != 0 steps the survivor world to 6). Post-evict the
        chaos drag stands down (the chip is quarantined): the step wall
        collapses >= 5x, so the 6 survivors out-throughput the dragged 8
        — and the whole event is priced in `ds_prof goodput`
        (straggler_wait + probe buckets, restart/shrink annotations) and
        rendered by the `ds_metrics` gray footer."""
        from deepspeed_tpu import telemetry

        save = str(tmp_path / "ckpt")
        tel = str(tmp_path / "tel")

        def factory():
            return survivor_engine(
                rewind={"ram_interval": 2, "keep": 4},
                extra={**SERIAL_ZERO3,
                       "gray": dict(GRAY_FAST),
                       # the verdict is an error-severity blackbox event:
                       # the flight recorder must dump an incident bundle
                       # the incident_forensics teardown merges + blames
                       "blackbox": {},
                       "telemetry": {"enabled": True, "output_dir": tel,
                                     "prometheus": False, "trace": True,
                                     "flush_interval": 1}})

        install_chaos(ChaosInjector(seed=7, slow_from_step=11,
                                    slow_device=3, slow_factor=5.0,
                                    slow_min_s=0.1))
        ticks = []
        agent = DSElasticAgent(factory, save, checkpoint_interval=100,
                               max_restarts=2, install_signal_handlers=False)
        try:
            out = agent.run(batch_seq, num_steps=24,
                            step_callback=lambda s, l: ticks.append(
                                (s, time.perf_counter())))
        finally:
            telemetry.flush()
            telemetry.deconfigure()
        assert out["status"] == "complete"
        assert out["final_step"] == 24
        assert out["restarts"] == 1
        # resumed resharded on the 6 survivors — WITHOUT the slow chip
        assert dict(agent.engine.mesh.shape)["data"] == 6
        assert 3 not in [d.id for d in agent.engine.mesh.devices.flatten()]
        drill = out["restart_log"][0]
        assert "FleetResizeEvent" in drill["error"]
        assert drill["tier"] == "ram"
        assert drill["resize"] == {"kind": "shrink", "from_world": 8,
                                   "to_world": 6}
        assert drill["steps_lost"] is not None
        assert drill["steps_lost"] <= 2              # <= ram_interval
        # the verdict landed in the shared restart_log.jsonl timeline,
        # blaming the right device with the right kind
        with open(os.path.join(tel, "restart_log.jsonl")) as f:
            recs = [json.loads(l) for l in f if l.strip()]
        verdicts = [r for r in recs if r.get("event") == "gray_verdict"]
        assert len(verdicts) == 1
        assert verdicts[0]["device"] == 3
        assert verdicts[0]["kind"] == "slow-compute"
        assert 12 <= verdicts[0]["step"] <= 20
        ev = verdicts[0]["evidence"]
        assert len(ev["probes"]) >= 2
        assert all(p["device"] == 3 for p in ev["probes"][-2:])
        verdict_step = verdicts[0]["step"]

        # ---- the collapse: dragged-8 steps (slow active, pre-verdict)
        # vs post-evict survivor steps, from the step_callback clock.
        # Callback steps are the agent's PRE-increment counter (callback
        # s = host step s+1), so wall[s] is host step s+1: the drag is
        # in wall[10] (chaos step 11) through wall[verdict-2]; the pair
        # straddling the restart (callback verdict-1 carries the whole
        # restore + recompile) stays out of both windows. The earliest
        # verdict the ladder allows (step 12, when a loaded host has
        # raised suspicion before the drag) still leaves wall[10].
        walls = {}
        for (s0, t0), (s1, t1) in zip(ticks, ticks[1:]):
            if s1 == s0 + 1:
                walls.setdefault(s1, t1 - t0)
        dragged = [walls[s] for s in range(10, verdict_step - 1)
                   if s in walls]
        post = [walls[s] for s in range(20, 24) if s in walls]
        assert dragged and len(post) >= 3
        # what the chaos GUARANTEES, whatever the host's load: every
        # dragged step slept the 0.1 s floor inside its gather phase.
        # Load only ever ADDS time to a step, so the collapse compares
        # the fastest step of each phase: >= 5x, i.e. the 6 survivors
        # push more samples/sec than the dragged 8 ever did
        assert min(dragged) >= 0.1, dragged
        assert min(dragged) >= 5.0 * min(post), (dragged, post)

        # ---- PRICED: the goodput report carries the probe and
        # straggler_wait badput and annotates the shrink
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_prof"),
             "goodput", tel], capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        assert "restart" in proc.stdout
        assert "shrink 8->6 resharded" in proc.stdout
        assert "recovered from ram tier" in proc.stdout
        procj = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_prof"),
             "goodput", tel, "--json"], capture_output=True, text=True)
        assert procj.returncode == 0, procj.stderr
        rep = json.loads(procj.stdout)
        assert rep["buckets_s"].get("straggler_wait", 0.0) > 0.0
        assert rep["buckets_s"].get("probe", 0.0) > 0.0
        # ---- RENDERED: the ds_metrics gray footer
        proc2 = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_metrics"), tel],
            capture_output=True, text=True)
        assert proc2.returncode == 0, proc2.stderr
        assert "gray:" in proc2.stdout
        assert "dev3" in proc2.stdout
        assert "VERDICTS" in proc2.stdout
        assert "evicted 1 device(s)" in proc2.stdout


# ------------------------------------- report-only + escalation drill
@pytest.mark.chaos
class TestReportOnlyAndEscalation:
    def test_report_only_then_escalates_past_max_verdicts(self, tmp_path):
        """``evict: false`` with ``max_verdicts: 1``: the first verdict
        is report-only (recorded, fleet untouched, scorer reset so the
        same drag must re-accumulate evidence), the second escalates to
        GrayError — with the verdict still recorded before giving up."""
        from deepspeed_tpu.resilience.gray import GrayError

        tel = str(tmp_path / "tel")
        engine = plain_engine(extra={
            **SERIAL_ZERO3,
            "gray": {**GRAY_FAST, "evict": False, "max_verdicts": 1},
            "telemetry": {"enabled": True, "output_dir": tel,
                          "prometheus": False, "trace": True,
                          "flush_interval": 1},
            "resilience": {"chaos": SLOW_CHAOS}})
        try:
            with pytest.raises(GrayError, match="max_verdicts"):
                for i in range(1, 40):
                    engine.train_batch(batch(i))
            mgr = engine._gray
            assert mgr.verdicts == 2
            assert mgr.last_verdict.device == 3
            assert mgr.last_verdict.kind == "slow-compute"
            # report-only left the fleet intact: still 8 devices, no
            # quarantine ever issued
            assert dict(engine.mesh.shape)["data"] == 8
            from deepspeed_tpu.elasticity import resize as rz
            assert rz.quarantined_devices() == set()
            # both verdicts persisted to the shared timeline
            with open(os.path.join(tel, "restart_log.jsonl")) as f:
                recs = [json.loads(l) for l in f if l.strip()]
            assert len([r for r in recs
                        if r.get("event") == "gray_verdict"]) == 2
            # the warn rung fired on the way up
            assert mgr.warnings >= 1
            # satellite: the comm windows were exported as skew gauges
            with open(os.path.join(tel, "metrics.jsonl")) as f:
                mrecs = [json.loads(l) for l in f if l.strip()]
            skews = [r for r in mrecs if r.get("name") == "comm/skew"]
            assert skews
            assert all({"op", "size"} <= set(r.get("labels") or {})
                       for r in skews)
        finally:
            from deepspeed_tpu import telemetry
            telemetry.flush()
            telemetry.deconfigure()


# ----------------------------------------------------------- observability
class TestObservability:
    def test_render_gray_line(self):
        from deepspeed_tpu.goodput.tail import render_gray_line

        assert render_gray_line({}, {}) is None
        line = render_gray_line(
            {"gray/suspicion": 0.72, "gray/blame_threshold": 0.6,
             "gray/suspect_device": 3.0, "gray/last_verdict_step": 15.0,
             "gray/last_verdict_device": 3.0},
            {"gray/probes": 4.0, "gray/verdicts{device=3}": 1.0,
             "gray/evictions{device=3}": 1.0, "gray/warnings": 2.0})
        assert "gray:" in line
        assert "suspicion 0.72/0.60" in line
        assert "suspect dev3" in line
        assert "4 probe(s)" in line
        assert "VERDICTS 1 (1x dev3)" in line
        assert "last blamed dev3 @step 15" in line
        assert "evicted 1 device(s)" in line
        assert "2 warning(s)" in line

    def test_render_gray_line_quiet_run(self):
        from deepspeed_tpu.goodput.tail import render_gray_line

        line = render_gray_line({"gray/suspicion": 0.02,
                                 "gray/blame_threshold": 0.6}, {})
        assert "no verdicts" in line
        assert "evicted" not in line

    def test_ds_top_frame_has_gray_line(self):
        from deepspeed_tpu.goodput.top import render_frame

        records = [
            {"kind": "gauge", "name": "gray/suspicion", "value": 0.7,
             "step": 9},
            {"kind": "gauge", "name": "gray/blame_threshold", "value": 0.6},
            {"kind": "counter", "name": "gray/verdicts",
             "labels": {"device": "3"}, "value": 1.0},
        ]
        frame = render_frame(records)
        assert "gray:" in frame
        assert "VERDICTS 1" in frame


# ---------------------------------------------- per-rank blame (merge --json)
class TestMergeRankCostShare:
    @staticmethod
    def _span(name, ts, dur, cat="train", step=None, **args):
        a = dict(args)
        if step is not None:
            a["step"] = step
        return {"ph": "X", "name": name, "ts": float(ts),
                "dur": float(dur), "cat": cat, "args": a}

    def test_merge_json_reports_rank_cost_share(self, tmp_path):
        """`ds_prof merge --json` blames per rank: the straggling rank's
        fraction of the total fleet waiting time, normalized to sum to
        1 — the number a gray-failure hunt sorts by."""
        r0 = [self._span("train_batch", 0, 100, step=3),
              self._span("all_reduce", 40, 30, cat="comm",
                         op="all_reduce", seq=0, group="")]
        r1 = [self._span("train_batch", 0, 100, step=3),
              self._span("all_reduce", 10, 60, cat="comm",
                         op="all_reduce", seq=0, group="")]
        for rank, evs in ((0, r0), (1, r1)):
            with open(tmp_path / f"trace.rank{rank}.json", "w") as f:
                json.dump({"traceEvents": evs}, f)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_prof"), "merge",
             str(tmp_path / "trace.rank0.json"),
             str(tmp_path / "trace.rank1.json"), "--no-align", "--json"],
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        rep = json.loads(proc.stdout)
        assert set(rep["rank_cost_share"]) == {"0", "1"}
        # rank 0 arrived last (ts 40 vs 10): all the waiting is its fault
        assert rep["rank_cost_share"]["0"] == 1.0
        assert rep["rank_cost_share"]["1"] == 0.0
        assert sum(rep["rank_cost_share"].values()) == pytest.approx(1.0)
        assert rep["rank_cost_us"]["0"] > 0


# ------------------------------------------------- gray_overhead self-gate
class TestGrayOverheadGate:
    @staticmethod
    def _entry(go, value=0.5):
        return {"metric": "gpt2-x pretrain MFU (bs=2/chip, seq=64)",
                "value": value, "unit": "MFU",
                "attribution": {"gray_overhead": go}}

    def test_gate_fails_synthetic_regression_exits_2(self, tmp_path,
                                                     capsys):
        """`ds_perf gate --metric gray_overhead`: probe cost creeping
        past the floor is a regression (exit 2); within-floor drift
        passes (exit 0)."""
        from deepspeed_tpu.perf import ledger as led
        from deepspeed_tpu.perf.cli import main

        base = str(tmp_path / "base.jsonl")
        cand = str(tmp_path / "cand.jsonl")
        led.append_entry(base, self._entry(0.01))
        led.append_entry(cand, self._entry(0.05))
        rc = main(["gate", "--baseline", base, "--candidate", cand,
                   "--metric", "gray_overhead"])
        assert rc == 2
        out = capsys.readouterr().out
        assert "gray_overhead" in out and "REGRESSED" in out

    def test_gate_passes_within_floor(self, tmp_path, capsys):
        from deepspeed_tpu.perf import ledger as led
        from deepspeed_tpu.perf.cli import main

        base = str(tmp_path / "base.jsonl")
        cand = str(tmp_path / "cand.jsonl")
        led.append_entry(base, self._entry(0.010))
        led.append_entry(cand, self._entry(0.012))
        rc = main(["gate", "--baseline", base, "--candidate", cand,
                   "--metric", "gray_overhead"])
        assert rc == 0
        assert "PASS" in capsys.readouterr().out


# ------------------------------------------------------- randomized sweep
def test_randomized_slow_sweep():
    """Slow sweep (tests/slow_tests.txt): seeded random device/factor
    slow faults — every one is blamed to the injected device, confirmed
    by probes with the right kind, and recorded report-only."""
    for seed in range(3):
        rng = np.random.RandomState(seed)
        uninstall_chaos()
        comm.comms_logger = None
        device = int(rng.randint(0, 8))
        factor = float(rng.uniform(4.0, 8.0))
        from_step = int(rng.randint(11, 14))
        engine = plain_engine(extra={
            **SERIAL_ZERO3,
            "gray": {**GRAY_FAST, "evict": False, "max_verdicts": 99},
            "resilience": {"chaos": {
                "enabled": True, "seed": seed + 11,
                "slow_from_step": from_step, "slow_device": device,
                "slow_factor": factor, "slow_min_s": 0.08}}})
        for i in range(1, from_step + 12):
            engine.train_batch(batch(i))
        ctx = (seed, device, factor, from_step)
        mgr = engine._gray
        assert mgr.verdicts >= 1, ctx
        assert mgr.last_verdict.device == device, ctx
        assert mgr.last_verdict.kind == "slow-compute", ctx
        assert dict(engine.mesh.shape)["data"] == 8, ctx


# ------------------------------------- probe and flight-recorder pricing
def test_probe_and_blackbox_overhead_under_budget(tmp_path, tiny_ledger_run):
    """gpt2-tiny with unconditional probes every 2 steps (the three timed
    steps must hold a probe) and the flight recorder armed: the ledger
    entry PRICES both defences. The gray contract is <= 2% of wall at the
    DEFAULT cadence (a suspicion-gated probe at most every
    probe_interval=10 steps), so the budget scales by the cadence ratio —
    same per-probe cost, more probes per wall. probe_confirmations sits
    out of reach: this run prices the defence, it must never verdict on
    CPU-sim probe noise. The always-on recorder must cost under 0.5% of
    wall and write ZERO bundles on a clean run."""
    every = 2
    engine, entry = tiny_ledger_run(tmp_path, extra={
        "gray": {"probe_every": every, "probe_confirmations": 1_000_000,
                 "evict": False},
        "blackbox": {}})
    att = entry["attribution"]
    go = att.get("gray_overhead")
    assert go is not None, "gray armed but the entry carries no gray_overhead"
    assert att["goodput"]["buckets_us"].get("probe", 0.0) > 0.0, \
        "gray armed but no probe bucket landed in the timed window"
    budget = 0.02 * (10.0 / every)
    assert 0.0 < go < budget, (
        f"gray_overhead {go:.4f} exceeds {budget:.3f} (2%-of-wall contract "
        f"scaled from probe_interval=10 to probe_every={every})")
    bo = att.get("blackbox_overhead")
    assert bo is not None, \
        "blackbox armed but the entry carries no blackbox_overhead"
    assert bo < 0.005, (
        f"blackbox_overhead {bo:.5f} exceeds the 0.5%-of-wall budget")
    assert engine._blackbox is not None \
        and engine._blackbox.bundles_written == 0, (
        "clean run wrote incident bundle(s): a severity>=error event "
        "fired with no fault injected")
