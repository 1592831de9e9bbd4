"""Goodput/badput accounting tests (ISSUE 8 acceptance surface).

The closed per-step ledger (partition sums to the wall window exactly,
priorities resolve overlaps), the engine meter behind the ``goodput``
ds_config block (series export, compile-span listener, strict no-op
without the block), cross-restart job reports (the synthetic two-session
fixture with an injected elastic restart must attribute the downtime to
the ``restart`` bucket), the tail-follower shared by ``ds_metrics
--follow`` and ``bin/ds_top``, the ``ds_prof merge`` degradation cases
(missing ranks, a restart mid-trace, empty/truncated files), the serving
request-span TTFT decomposition, and the perf_record goodput chain.
"""

import importlib.util
import io
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from deepspeed_tpu.goodput.ledger import (classify_window, goodput_fraction,
                                          load_trace_file, session_ledger,
                                          step_ledgers, step_windows,
                                          sum_buckets, top_badput)
from deepspeed_tpu.goodput.taxonomy import BUCKETS, GOODPUT_BUCKETS

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _span(name, ts, dur, cat="train", **args):
    return {"name": name, "cat": cat, "ph": "X", "ts": float(ts),
            "dur": float(dur), "pid": 0, "tid": 0, "args": args}


@pytest.mark.goodput
class TestTaxonomyLedger:
    def test_partition_sums_exactly_and_respects_priority(self):
        # a step: data wait, a train_batch envelope, a compile burst and a
        # comm span inside it, a checkpoint after it, idle at the end
        events = [
            _span("data", 0, 1000, step=0),
            _span("train_batch", 1000, 8000, step=0),
            _span("compile", 1500, 2000, cat="compile"),
            _span("all_reduce", 5000, 1000, cat="comm", op="all_reduce",
                  seq=0, group=""),
            _span("save_checkpoint", 9000, 500, cat="checkpoint"),
        ]
        window = (0.0, 10000.0)
        b = classify_window(events, window)
        assert abs(sum(b.values()) - 10000.0) < 1e-6
        assert b["data_wait"] == 1000.0
        # compile WINS over the enclosing train_batch (priority)
        assert b["compile"] == 2000.0
        # train_batch fully CONTAINS the comm span: it is an envelope
        # around a blocking collective, not overlapped compute — the comm
        # is exposed (same container-drop rule as FleetTrace)
        assert b["exposed_comm"] == 1000.0
        assert b["checkpoint"] == 500.0
        assert b["compute"] == 8000.0 - 2000.0 - 1000.0
        assert b["idle"] == 10000.0 - 1000.0 - 8000.0 - 500.0

    def test_exposed_comm_outside_compute(self):
        # comm sticking out past the compute span IS exposed
        events = [
            _span("train_batch", 0, 4000, step=0),
            _span("all_reduce", 3000, 3000, cat="comm", op="all_reduce",
                  seq=0, group=""),
        ]
        b = classify_window(events, (0.0, 6000.0))
        assert b["exposed_comm"] == 2000.0
        assert b["compute"] == 4000.0
        assert sum(b.values()) == 6000.0

    def test_watchdog_stall_wins_over_everything(self):
        events = [
            _span("train_batch", 0, 5000, step=0),
            _span("watchdog_stall", 1000, 3000, cat="stall"),
        ]
        b = classify_window(events, (0.0, 5000.0))
        assert b["watchdog_stall"] == 3000.0
        assert b["compute"] == 2000.0

    def test_step_windows_include_data_span(self):
        events = [
            _span("data", 100, 400, step=3),
            _span("train_batch", 500, 2000, step=3),
            _span("data", 2600, 100, step=4),
            _span("train_batch", 2700, 1800, step=4),
        ]
        ws = step_windows(events)
        assert ws == [(3, (100.0, 2500.0)), (4, (2600.0, 4500.0))]
        ledgers = step_ledgers(events)
        for led in ledgers:
            assert abs(sum(led["buckets"].values()) - led["wall_us"]) < 1e-6

    def test_straggler_intervals_claim_their_slot(self):
        events = [
            _span("train_batch", 0, 4000, step=0),
            _span("all_reduce", 3000, 3000, cat="comm", op="all_reduce",
                  seq=0, group=""),
        ]
        b = classify_window(events, (0.0, 6000.0),
                            straggler_intervals=[(4500.0, 6000.0)])
        # the tail of the exposed comm was really waiting for a straggler
        assert b["straggler_wait"] == 1500.0
        assert b["exposed_comm"] == 500.0
        assert sum(b.values()) == 6000.0

    def test_session_ledger_and_helpers(self):
        events = [
            _span("data", 0, 500, step=0),
            _span("train_batch", 500, 4500, step=0),
            _span("data", 6000, 500, step=1),
            _span("train_batch", 6500, 3500, step=1),
        ]
        led = session_ledger(events)
        assert led["wall_us"] == 10000.0
        assert abs(sum(led["buckets"].values()) - 10000.0) < 1e-6
        assert led["buckets"]["idle"] == 1000.0     # the inter-step gap
        assert len(led["steps"]) == 2
        gf = goodput_fraction(led["buckets"])
        assert gf == pytest.approx(0.8)
        assert top_badput(led["buckets"])[0] in ("idle", "data_wait")
        total = sum_buckets([led["buckets"], led["buckets"]])
        assert total["compute"] == 2 * led["buckets"]["compute"]


class _EngineMixin:
    def _engine(self, goodput=None, telemetry_cfg=None):
        import deepspeed_tpu
        from deepspeed_tpu.models.simple import SimpleModel

        cfg = {"train_batch_size": 8, "steps_per_print": 0,
               "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}}
        if telemetry_cfg is not None:
            cfg["telemetry"] = telemetry_cfg
        if goodput is not None:
            cfg["goodput"] = goodput
        engine, *_ = deepspeed_tpu.initialize(
            model=SimpleModel(hidden_dim=16, nlayers=2), config=cfg)
        return engine

    @staticmethod
    def _batch(i=0):
        rng = np.random.RandomState(i)
        return (rng.randn(8, 16).astype(np.float32),
                rng.randn(8, 16).astype(np.float32))


@pytest.mark.goodput
class TestEngineGoodput(_EngineMixin):
    def test_series_exported_and_lag_one_step(self, tmp_path):
        from deepspeed_tpu import telemetry

        engine = self._engine(
            goodput={},
            telemetry_cfg={"enabled": True,
                           "output_dir": str(tmp_path / "t"),
                           "flush_interval": 1000})
        try:
            for i in range(4):
                engine.train_batch(self._batch(i))
            assert engine._goodput is not None
            by_name = {}
            for r in telemetry.get_registry().snapshot():
                key = (r["name"],) + tuple(sorted(
                    (r.get("labels") or {}).items()))
                by_name[key] = r
            # the live series lag one step: spans carry the PRE-increment
            # step counter (0..3 over 4 batches), and the 4th batch's
            # hook sees spans 0..2 complete (span 3 is still open)
            assert by_name[("goodput/step",)]["value"] == 2
            gf = by_name[("goodput/goodput_fraction",)]["value"]
            assert 0.0 < gf <= 1.0
            fr = {k[1][1]: v["value"] for k, v in by_name.items()
                  if k[0] == "goodput/fraction"}
            assert set(fr) == set(BUCKETS)
            assert abs(sum(fr.values()) - 1.0) < 1e-6
            assert by_name[("goodput/step_wall_s",)]["value"] > 0
            # no closure violations on a healthy run
            assert ("goodput/closure_violations",) not in by_name
        finally:
            telemetry.deconfigure()

    def test_compile_spans_stamped_by_listener(self, tmp_path):
        from deepspeed_tpu import telemetry

        engine = self._engine(
            goodput={},
            telemetry_cfg={"enabled": True,
                           "output_dir": str(tmp_path / "t"),
                           "flush_interval": 1000})
        try:
            engine.train_batch(self._batch())
            events = list(telemetry.get_session().tracer.events)
            compiles = [e for e in events if e.get("cat") == "compile"]
            assert compiles, "the jax.monitoring listener must stamp " \
                             "backend compiles as compile spans"
            assert all(e["name"] == "compile" for e in compiles)
        finally:
            telemetry.deconfigure()

    def test_attribution_closure_within_tolerance(self, tmp_path):
        """THE acceptance bound: every per-step breakdown's buckets sum to
        within 5% of the measured step wall time (data + train_batch
        window, measured independently from the raw spans)."""
        from deepspeed_tpu import telemetry

        engine = self._engine(
            goodput={},
            telemetry_cfg={"enabled": True,
                           "output_dir": str(tmp_path / "t"),
                           "flush_interval": 1000})
        try:
            for i in range(5):
                engine.train_batch(self._batch(i))
            events = list(telemetry.get_session().tracer.events)
            att = engine._goodput.attribution(events, timed_steps=3)
            assert att["goodput_fraction"] > 0
            assert len(att["per_step"]) == 3
            # independently measured step wall: the step's span extents
            by_step = {}
            for ev in events:
                step = (ev.get("args") or {}).get("step")
                if ev.get("ph") == "X" and isinstance(step, int) \
                        and ev.get("name") in ("data", "train_batch"):
                    lo, hi = by_step.get(step, (float("inf"), 0.0))
                    by_step[step] = (min(lo, ev["ts"]),
                                     max(hi, ev["ts"] + ev["dur"]))
            for led in att["per_step"]:
                total = sum(led["buckets_us"].values())
                assert total == pytest.approx(led["wall_us"], rel=1e-3)
                lo, hi = by_step[led["step"]]
                measured = hi - lo
                assert abs(total - measured) / measured < 0.05
        finally:
            telemetry.deconfigure()

    def test_strict_noop_without_block(self, tmp_path):
        """Without the ``goodput`` block the package is provably never
        imported and no meter exists (same contract as profiling/perf)."""
        mods = [m for m in list(sys.modules) if m.startswith("deepspeed_tpu.goodput")]
        saved = {m: sys.modules.pop(m) for m in mods}
        try:
            engine = self._engine(
                telemetry_cfg={"enabled": True,
                               "output_dir": str(tmp_path / "t"),
                               "flush_interval": 1000})
            engine.train_batch(self._batch())
            assert engine._goodput is None
            assert not any(m.startswith("deepspeed_tpu.goodput")
                           for m in sys.modules)
        finally:
            from deepspeed_tpu import telemetry

            telemetry.deconfigure()
            sys.modules.update(saved)

    def test_block_with_enabled_false_is_noop(self, tmp_path):
        engine = self._engine(goodput={"enabled": False})
        engine.train_batch(self._batch())
        assert engine._goodput is None


@pytest.mark.goodput
class TestSessionAnchors:
    def test_tracer_metadata_carries_clock_anchor(self):
        from deepspeed_tpu.telemetry.tracing import StepTracer

        before = time.time()
        tr = StepTracer(pid=3)
        after = time.time()
        meta = tr.to_chrome_trace()["metadata"]
        anchor = meta["clock_anchor"]
        assert before <= anchor["epoch_s"] <= after
        assert "monotonic_s" in anchor
        assert meta["rank"] == 3

    def test_new_session_rotates_stale_trace(self, tmp_path):
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.runtime.config import TelemetryConfig

        out = str(tmp_path / "t")
        cfg = TelemetryConfig(enabled=True, output_dir=out,
                              flush_interval=1000, prometheus=False)
        s1 = telemetry.configure(cfg)
        try:
            with s1.tracer.span("train_batch", step=0):
                pass
            s1.flush()
            assert os.path.exists(os.path.join(out, "trace.json"))
            s2 = telemetry.configure(cfg)      # restart: same dir
            with s2.tracer.span("train_batch", step=0):
                pass
            s2.flush()
        finally:
            telemetry.deconfigure()
        assert os.path.exists(os.path.join(out, "trace.json"))
        assert os.path.exists(os.path.join(out, "trace.session1.json"))
        a1 = load_trace_file(os.path.join(out, "trace.session1.json"))
        a2 = load_trace_file(os.path.join(out, "trace.json"))
        assert a1["anchor_epoch_s"] is not None
        assert a2["anchor_epoch_s"] >= a1["anchor_epoch_s"]


# --------------------------------------------------------------- job report
def _session_trace(rank, epoch0, spans, extra_meta=None):
    events = [{"name": "process_name", "ph": "M", "pid": rank, "tid": 0,
               "args": {"name": f"deepspeed_tpu rank {rank}"}}]
    events += spans
    meta = {"rank": rank, "dropped_events": 0,
            "clock_anchor": {"epoch_s": epoch0, "monotonic_s": 0.0}}
    meta.update(extra_meta or {})
    return {"traceEvents": events, "displayTimeUnit": "ms", "metadata": meta}


def _steps(n, start_us=0.0, step_us=100_000.0, first_step=0):
    spans = []
    t = start_us
    for i in range(n):
        spans.append(_span("data", t, 2000, step=first_step + i))
        spans.append(_span("train_batch", t + 2000, step_us - 2000,
                           step=first_step + i))
        t += step_us
    return spans


@pytest.mark.goodput
class TestJobReport:
    def test_two_session_restart_downtime_attributed(self, tmp_path):
        """The acceptance fixture: one rank, an elastic restart with 5 s
        of downtime between two sessions — the job report must charge the
        gap to the ``restart`` bucket and name the restart reason."""
        from deepspeed_tpu.goodput.report import (build_job_report,
                                                  render_goodput_report)

        t0 = 1_700_000_000.0
        s1 = tmp_path / "trace.session1.json"
        s2 = tmp_path / "trace.json"
        # session 1: 2 steps over 0.2 s, then the job dies; session 2
        # starts 5 s after session 1's last span ends
        s1.write_text(json.dumps(_session_trace(0, t0, _steps(2))))
        s2.write_text(json.dumps(_session_trace(
            0, t0 + 0.2 + 5.0, _steps(2, first_step=2))))
        rlog = tmp_path / "restart_log.jsonl"
        rlog.write_text(json.dumps(
            {"restart": 1, "error": "WatchdogTimeout: step 2 hung",
             "step": 2, "backoff_s": 1.0, "ts": t0 + 2.0}) + "\n")
        from deepspeed_tpu.goodput.report import load_restart_log

        report = build_job_report([str(s1), str(s2)],
                                  restart_log=load_restart_log([str(tmp_path)]))
        assert report["ranks"] == [0]
        assert report["sessions"] == 2
        b = report["buckets_s"]
        assert b["restart"] == pytest.approx(5.0, rel=0.01)
        assert b["compute"] == pytest.approx(4 * 0.098, rel=0.01)
        assert report["restarts"][0]["reasons"] == \
            ["WatchdogTimeout: step 2 hung"]
        # ledger closes: fleet seconds == sum of buckets
        assert sum(b.values()) == pytest.approx(report["fleet_seconds"])
        text = render_goodput_report(report)
        assert "restart" in text and "WatchdogTimeout" in text
        assert "goodput:" in text

    def test_background_span_does_not_stretch_session_or_gap(self, tmp_path):
        """A background async-checkpoint commit span that outlives the
        step loop must not define the session's extent: pre-fix it
        stretched session 1 into the restart gap, compressed the charged
        downtime to ~0 and pushed the restart record outside the match
        window — silently dropping the resize annotation THE drill
        asserts on."""
        from deepspeed_tpu.goodput.report import (build_job_report,
                                                  render_goodput_report)

        t0 = 1_700_000_000.0
        spans = _steps(2)
        # commit thread finishes 4.8 s into the 5 s restart gap
        spans.append(_span("save_checkpoint", 150_000, 4_850_000,
                           cat="checkpoint", background=True))
        s1 = tmp_path / "trace.session1.json"
        s2 = tmp_path / "trace.json"
        s1.write_text(json.dumps(_session_trace(0, t0, spans)))
        s2.write_text(json.dumps(_session_trace(
            0, t0 + 0.2 + 5.0, _steps(2, first_step=2))))
        rlog = [{"restart": 1, "error": "FleetResizeEvent: fleet shrink",
                 "ts": t0 + 0.25, "tier": "ram", "snapshot_step": 2,
                 "steps_lost": 1, "restore_s": 0.01, "reshard_s": 0.01,
                 "resize": {"kind": "shrink", "from_world": 8,
                            "to_world": 6}}]
        report = build_job_report([str(s1), str(s2)], restart_log=rlog)
        assert report["buckets_s"]["restart"] == pytest.approx(5.0, rel=0.01)
        assert report["restarts"][0]["reasons"] == \
            ["FleetResizeEvent: fleet shrink"]
        text = render_goodput_report(report)
        assert "shrink 8->6 resharded" in text

    def test_unmatched_record_attaches_to_nearest_gap(self, tmp_path):
        """A restart record whose ts misses every gap's exact window
        (anchor wobble, a late flush) still annotates the nearest gap —
        loudly — instead of vanishing from the report."""
        from deepspeed_tpu.goodput.report import (build_job_report,
                                                  render_goodput_report)

        t0 = 1_700_000_000.0
        s1 = tmp_path / "trace.session1.json"
        s2 = tmp_path / "trace.json"
        s1.write_text(json.dumps(_session_trace(0, t0, _steps(2))))
        s2.write_text(json.dumps(_session_trace(
            0, t0 + 0.2 + 5.0, _steps(20, first_step=2))))
        # stamped 2.5 s AFTER session 2 began (a slow restore): outside
        # the gap's +1 s window, inside the 30 s nearest-gap slack
        rlog = [{"restart": 1, "error": "resume from disk tier",
                 "ts": t0 + 5.2 + 2.5, "tier": "disk", "snapshot_step": 2,
                 "steps_lost": 0, "restore_s": 2.4}]
        report = build_job_report([str(s1), str(s2)], restart_log=rlog)
        assert report["restarts"][0]["reasons"] == ["resume from disk tier"]
        assert report["restarts"][0]["recoveries"][0]["tier"] == "disk"
        assert any("nearest gap" in w for w in report["warnings"])
        assert "disk tier" in render_goodput_report(report)

    def test_missing_anchor_degrades_loudly(self, tmp_path):
        from deepspeed_tpu.goodput.report import build_job_report

        s1 = tmp_path / "a.json"
        s2 = tmp_path / "b.json"
        t1 = _session_trace(0, 100.0, _steps(1))
        t2 = _session_trace(0, 0.0, _steps(1))
        del t2["metadata"]["clock_anchor"]
        s1.write_text(json.dumps(t1))
        s2.write_text(json.dumps(t2))
        report = build_job_report([str(s1), str(s2)])
        assert report["buckets_s"]["restart"] == 0.0
        assert any("clock anchor" in w for w in report["warnings"])

    def test_fleet_straggler_attribution(self, tmp_path):
        from deepspeed_tpu.goodput.report import build_job_report

        t0 = 1_700_000_000.0
        comm0 = [_span("all_reduce", 50_000, 40_000, cat="comm",
                       op="all_reduce", seq=0, group="")]
        comm1 = [_span("all_reduce", 80_000, 10_000, cat="comm",
                       op="all_reduce", seq=0, group="")]
        p0 = tmp_path / "trace.json"
        p1 = tmp_path / "trace.rank1.json"
        p0.write_text(json.dumps(_session_trace(
            0, t0, _steps(1) + comm0)))
        p1.write_text(json.dumps(_session_trace(
            1, t0, _steps(1) + comm1)))
        report = build_job_report([str(p0), str(p1)])
        # rank 0 arrived 30 ms early -> it waited for the straggler
        r0 = report["per_rank"][0]["buckets_us"]
        assert r0["straggler_wait"] == pytest.approx(30_000, rel=0.01)
        assert report["per_rank"][1]["buckets_us"]["straggler_wait"] == 0.0

    def test_ds_prof_goodput_cli(self, tmp_path, capsys):
        from deepspeed_tpu.profiling.cli import main

        t0 = 1_700_000_000.0
        (tmp_path / "trace.session1.json").write_text(
            json.dumps(_session_trace(0, t0, _steps(2))))
        (tmp_path / "trace.json").write_text(
            json.dumps(_session_trace(0, t0 + 0.2 + 3.0,
                                      _steps(2, first_step=2))))
        (tmp_path / "restart_log.jsonl").write_text(json.dumps(
            {"restart": 1, "error": "BadStepError: loss blew up",
             "ts": t0 + 1.0}) + "\n")
        assert main(["goodput", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "restart" in out and "BadStepError" in out
        assert main(["goodput", str(tmp_path), "--json"]) == 0
        data = json.loads(capsys.readouterr().out)
        assert data["buckets_s"]["restart"] == pytest.approx(3.0, rel=0.01)

    def test_empty_dir_fails_loudly(self, tmp_path):
        from deepspeed_tpu.profiling.cli import main

        assert main(["goodput", str(tmp_path)]) == 2


# ------------------------------------------------------------------ tailers
@pytest.mark.goodput
class TestTailers:
    def test_tailer_appends_torn_lines_truncation(self, tmp_path):
        from deepspeed_tpu.goodput.tail import JSONLTailer

        p = tmp_path / "m.jsonl"
        t = JSONLTailer(str(p))
        assert t.poll() == []                      # not created yet
        with open(p, "w") as f:
            f.write('{"a": 1}\n{"a": 2}\n')
        assert [r["a"] for r in t.poll()] == [1, 2]
        assert t.poll() == []
        with open(p, "a") as f:
            f.write('{"a": 3')                     # torn mid-append
        assert t.poll() == []                      # waits for the newline
        with open(p, "a") as f:
            f.write('}\n')
        assert [r["a"] for r in t.poll()] == [3]
        # truncation: a fresh run reuses the path
        with open(p, "w") as f:
            f.write('{"b": 1}\n')
        recs = t.poll()
        assert [r.get("b") for r in recs] == [1]
        assert t.resets == 1
        # rotation: new inode at the same path
        os.replace(str(tmp_path / "m.jsonl"), str(tmp_path / "old"))
        with open(p, "w") as f:
            f.write('{"c": 1}\nnot json\n')
        recs = t.poll()
        assert [r.get("c") for r in recs] == [1]
        assert t.bad_lines == 1

    def test_metrics_follower_keeps_last_per_series(self, tmp_path):
        from deepspeed_tpu.goodput.tail import MetricsFollower

        p = tmp_path / "m.jsonl"
        f = MetricsFollower(str(p))
        rec = {"kind": "gauge", "name": "train/loss", "labels": {},
               "value": 5.0, "ts": 1.0, "step": 1}
        with open(p, "w") as fh:
            fh.write(json.dumps(rec) + "\n")
            fh.write(json.dumps(dict(rec, value=3.0, step=2)) + "\n")
        assert f.poll() is True
        [r] = f.records()
        assert r["value"] == 3.0 and r["step"] == 2
        assert f.poll() is False

    def test_ds_metrics_follow(self, tmp_path):
        import importlib.machinery

        loader = importlib.machinery.SourceFileLoader(
            "_ds_metrics_test", os.path.join(REPO, "bin", "ds_metrics"))
        spec = importlib.util.spec_from_loader(loader.name, loader)
        mod = importlib.util.module_from_spec(spec)
        loader.exec_module(mod)
        p = tmp_path / "metrics.jsonl"
        with open(p, "w") as f:
            f.write(json.dumps({"kind": "gauge", "name": "train/loss",
                                "labels": {}, "value": 2.5, "ts": 1.0,
                                "step": 7}) + "\n")
        out = io.StringIO()
        assert mod.follow(str(p), interval=0.01, max_polls=2, out=out) == 0
        text = out.getvalue()
        assert "telemetry summary" in text and "train/loss" in text

    def test_ds_top_once_cli(self, tmp_path):
        p = tmp_path / "metrics.jsonl"
        recs = [
            {"kind": "gauge", "name": "goodput/goodput_fraction",
             "labels": {}, "value": 0.82, "ts": time.time(), "step": 12},
            {"kind": "gauge", "name": "goodput/step_wall_s", "labels": {},
             "value": 0.5, "ts": time.time(), "step": 12},
            {"kind": "gauge", "name": "goodput/fraction",
             "labels": {"bucket": "exposed_comm"}, "value": 0.18,
             "ts": time.time(), "step": 12},
            {"kind": "gauge", "name": "train/samples_per_sec",
             "labels": {}, "value": 42.0, "ts": time.time(), "step": 12},
        ]
        with open(p, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_top"),
             str(tmp_path), "--once"],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "goodput  82.0%" in proc.stdout
        assert "exposed_comm 18.0%" in proc.stdout
        assert "step 12" in proc.stdout

    def test_render_frame_serving_line(self):
        from deepspeed_tpu.goodput.top import render_frame

        now = time.time()
        recs = [
            {"kind": "gauge", "name": "serving/state", "labels": {},
             "value": 1, "ts": now, "step": None},
            {"kind": "gauge", "name": "serving/queue_depth", "labels": {},
             "value": 3, "ts": now, "step": None},
            {"kind": "histogram", "name": "serving/ttft_seconds",
             "labels": {}, "count": 5, "p50": 0.2, "p90": 0.4, "p99": 0.5,
             "max": 0.6, "sum": 1.0, "min": 0.1, "ts": now, "step": None},
            {"kind": "counter", "name": "serving/shed",
             "labels": {"reason": "queue_full"}, "value": 2, "ts": now,
             "step": None},
        ]
        frame = render_frame(recs, source="x")
        assert "serving: ready" in frame
        assert "queue 3" in frame
        assert "ttft p50 0.2s" in frame
        assert "shed 2" in frame


# ------------------------------------------------------- ds_prof merge gaps
@pytest.mark.goodput
class TestMergeDegradation:
    def test_missing_rank_warns(self, tmp_path):
        from deepspeed_tpu.profiling.aggregate import FleetTrace

        for rank in (0, 2):
            (tmp_path / f"trace.rank{rank}.json").write_text(
                json.dumps(_session_trace(rank, 0.0, _steps(1))))
        ft = FleetTrace.from_files(
            [str(tmp_path / "trace.rank0.json"),
             str(tmp_path / "trace.rank2.json")])
        assert sorted(ft.by_rank) == [0, 2]
        assert any("missing rank" in w and "1" in w for w in ft.warnings)

    def test_two_files_one_rank_is_loud_error(self, tmp_path):
        from deepspeed_tpu.profiling.aggregate import FleetTrace

        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        a.write_text(json.dumps(_session_trace(0, 0.0, _steps(1))))
        b.write_text(json.dumps(_session_trace(0, 0.0, _steps(1))))
        with pytest.raises(ValueError, match="rank 0"):
            FleetTrace.from_files([str(a), str(b)])

    def test_restart_mid_trace_excluded_from_matching(self, tmp_path):
        """A rank whose trace holds TWO sessions (elastic restart: the
        per-session seq counters reset, so identities repeat) must not
        phantom-match the other ranks — duplicated identities are dropped
        from alignment/straggler analysis, loudly."""
        from deepspeed_tpu.profiling.aggregate import FleetTrace

        comm = lambda ts: _span("all_reduce", ts, 1000, cat="comm",
                                op="all_reduce", seq=0, group="")
        restarted = _session_trace(0, 0.0, [comm(1000), comm(500_000)])
        healthy = _session_trace(1, 0.0, [comm(1000)])
        a = tmp_path / "trace.json"
        b = tmp_path / "trace.rank1.json"
        a.write_text(json.dumps(restarted))
        b.write_text(json.dumps(healthy))
        ft = FleetTrace.from_files([str(a), str(b)])
        assert ft.collective_matches() == []
        assert ft.straggler_table() == []       # no fabricated straggler
        assert any("more than once" in w for w in ft.warnings)
        assert ft.clock_offsets() == {0: 0.0, 1: 0.0}

    def test_empty_and_truncated_files(self, tmp_path, capsys):
        from deepspeed_tpu.profiling.aggregate import FleetTrace
        from deepspeed_tpu.profiling.cli import main

        empty = tmp_path / "trace.rank1.json"
        empty.write_text("")
        good = tmp_path / "trace.json"
        good.write_text(json.dumps(_session_trace(0, 0.0, _steps(1))))
        torn = tmp_path / "trace.rank2.jsonl"
        with open(torn, "w") as f:
            f.write(json.dumps(_span("train_batch", 0, 1000, step=0)) + "\n")
            f.write('{"name": "tr')            # killed mid-append
        ft = FleetTrace.from_files([str(good), str(empty), str(torn)])
        assert sorted(ft.by_rank) == [0, 2]    # no phantom lane for rank 1
        assert any("empty trace" in w for w in ft.warnings)
        assert any("torn" in w for w in ft.warnings)
        # the CLI surfaces the warnings on stderr and still merges
        assert main(["merge", str(tmp_path)]) == 0
        err = capsys.readouterr().err
        assert "empty trace" in err and "torn" in err

    def test_merge_dir_scan_excludes_rotated_sessions(self, tmp_path,
                                                      capsys):
        from deepspeed_tpu.profiling.cli import main

        # a restart left two sessions of rank 0 in the dir; merge must
        # scan only the live trace.json, not die on a two-claims error
        (tmp_path / "trace.session1.json").write_text(
            json.dumps(_session_trace(0, 0.0, _steps(1))))
        (tmp_path / "trace.json").write_text(
            json.dumps(_session_trace(0, 10.0, _steps(1, first_step=1))))
        assert main(["merge", str(tmp_path), "--json"]) == 0
        data = json.loads(capsys.readouterr().out)
        assert data["ranks"] == [0]


# --------------------------------------------------------- serving spans
@pytest.mark.goodput
class TestServingRequestSpans:
    def test_ttft_decomposition_series(self, tmp_path):
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
        from deepspeed_tpu.inference.engine import InferenceEngine
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
        from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                                  TelemetryConfig)
        from deepspeed_tpu.serving import ServingFrontEnd

        cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=32,
                         n_layer=1, n_head=2)
        engine = InferenceEngine(
            GPT2Model(cfg),
            DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=16))
        tel = telemetry.configure(TelemetryConfig(
            enabled=True, output_dir=str(tmp_path / "t"),
            flush_interval=1000, prometheus=False))
        ds = DeepSpeedConfig({"serving": {"decode_tick_tokens": 4,
                                          "max_queue_depth": 4}})
        fe = ServingFrontEnd(engine, ds.serving, start=True)
        try:
            prompt = (np.arange(4)[None, :] % 64).astype(np.int32)
            r = fe.submit(prompt, max_new_tokens=4)
            r.result(timeout=300)
            assert r.status == "completed"
            names = {rec["name"] for rec in tel.registry.snapshot()}
            assert "serving/prefill_seconds" in names
            assert "serving/decode_chunk_seconds" in names
            assert "serving/queue_wait_seconds" in names
            spans = [e for e in tel.tracer.events
                     if e.get("cat") == "serving"]
            by_name = {e["name"] for e in spans}
            assert {"admission_wait", "prefill", "decode"} <= by_name
            # every span of the request's service names it; the wait on an
            # empty queue is no request's
            assert all((e.get("args") or {}).get("request") == r.id
                       for e in spans if e["name"] != "queue_empty")
            # the SLO renderer decomposes TTFT from the new series
            from deepspeed_tpu.profiling.report import \
                render_serving_summary

            text = render_serving_summary(
                [rec for rec in tel.registry.snapshot()
                 if rec["name"].startswith("serving/")])
            assert "prefill_seconds" in text
            assert "ttft decomposition" in text
        finally:
            fe.close()
            telemetry.deconfigure()


# ------------------------------------------------------------- schema/gate
@pytest.mark.goodput
class TestSchemaAndGate:
    def test_top_level_did_you_mean(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        with pytest.raises(ValueError, match="goodput"):
            DeepSpeedConfig({"train_batch_size": 8, "goodputt": {}})

    def test_unknown_key_inside_block(self):
        from deepspeed_tpu.runtime.config import GoodputConfig

        with pytest.raises(Exception, match="compile_spans"):
            GoodputConfig(compile_span=True)

    def test_schema_pass_goodput_without_telemetry(self):
        from deepspeed_tpu.analysis.schema import walk_config

        findings, _ = walk_config({"train_batch_size": 8, "goodput": {}})
        msgs = [f.message for f in findings]
        assert any("goodput is enabled without telemetry" in m for m in msgs)
        findings, _ = walk_config({"train_batch_size": 8, "goodput": {},
                                   "telemetry": {"enabled": True}})
        msgs = [f.message for f in findings]
        assert not any("goodput is enabled without" in m for m in msgs)

    def test_gate_fails_on_goodput_regression(self, tmp_path):
        from deepspeed_tpu.perf import ledger as led
        from deepspeed_tpu.perf.cli import main

        entry = {"metric": "m pretrain MFU (x)", "value": 0.5,
                 "unit": "MFU", "model": "m", "fingerprint": "f",
                 "headline": True, "goodput_fraction": 0.9}
        base = str(tmp_path / "base.jsonl")
        cand = str(tmp_path / "cand.jsonl")
        led.append_entry(base, dict(entry))
        # headline value holds, goodput collapses -> gate must fail
        led.append_entry(cand, dict(entry, goodput_fraction=0.6))
        assert main(["gate", "--baseline", base, "--candidate", cand]) == 2
        # both fine -> pass
        cand2 = str(tmp_path / "cand2.jsonl")
        led.append_entry(cand2, dict(entry, goodput_fraction=0.89))
        assert main(["gate", "--baseline", base, "--candidate", cand2]) == 0

    def test_compare_reports_goodput_fields(self):
        from deepspeed_tpu.perf import ledger as led

        old = {"metric": "m (x)", "value": 1.0, "goodput_fraction": 0.8}
        new = {"metric": "m (x)", "value": 1.0, "goodput_fraction": 0.7}
        r = led.compare(old, new)
        assert r["old_goodput"] == 0.8 and r["new_goodput"] == 0.7
        assert r["goodput_regressed"] is True
        assert r["verdict"] == "within_noise"   # headline itself held


@pytest.mark.goodput
class TestLedgerEntryGoodput:
    """The acceptance of ``engine.perf_record`` under the ``goodput``
    block: the entry carries a per-step goodput breakdown whose buckets
    sum to within 5% of the measured step wall time, and the hoisted
    goodput_fraction is gateable."""

    @pytest.fixture(scope="class")
    def smoke(self, tmp_path_factory, tiny_ledger_run):
        tmp = tmp_path_factory.mktemp("goodput_smoke")
        tiny_ledger_run(tmp, seq=64)
        return str(tmp / "ledger.jsonl")

    def test_entry_carries_closed_goodput_breakdown(self, smoke):
        from deepspeed_tpu.perf import ledger as led

        [entry] = led.load_entries(smoke)
        gp = entry["attribution"]["goodput"]
        assert gp["per_step"], "every entry must carry per-step ledgers"
        for step in gp["per_step"]:
            total = sum(step["buckets_us"].values())
            assert abs(total - step["wall_us"]) / step["wall_us"] < 0.05
        assert 0.0 < gp["goodput_fraction"] <= 1.0
        assert entry["goodput_fraction"] == gp["goodput_fraction"]
        # the per-step wall windows agree with the independently recorded
        # train_batch samples (seconds) to the acceptance tolerance plus
        # the data-wait the window includes
        assert len(entry["samples"]) >= len(gp["per_step"])

    def test_goodput_fraction_gates(self, smoke, tmp_path):
        from deepspeed_tpu.perf import ledger as led
        from deepspeed_tpu.perf.cli import main

        ledger = smoke
        assert main(["gate", "--baseline", ledger,
                     "--candidate", ledger]) == 0
        [entry] = led.load_entries(ledger)
        # synthetic candidate whose headline holds but whose goodput
        # collapsed to half — per-step ledgers scaled consistently, so
        # the t gate sees a REAL step-level collapse (matching per-step
        # evidence would rightly exonerate an aggregate-only blip)
        cand = str(tmp_path / "cand.jsonl")
        synthetic = json.loads(json.dumps(
            {k: v for k, v in entry.items() if k != "samples"}))
        synthetic["goodput_fraction"] = entry["goodput_fraction"] * 0.5
        for s in synthetic["attribution"]["goodput"]["per_step"]:
            compute = s["buckets_us"].get("compute", 0.0) * 0.5
            s["buckets_us"]["compute"] = compute
            s["buckets_us"]["idle"] = s["wall_us"] - sum(
                v for k, v in s["buckets_us"].items() if k != "idle")
        led.append_entry(cand, synthetic)
        assert main(["gate", "--baseline", ledger,
                     "--candidate", cand]) == 2
