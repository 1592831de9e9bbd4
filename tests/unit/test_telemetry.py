"""Unified telemetry tests — registry/histogram math, trace JSON,
Prometheus exposition, disabled-mode no-ops, resilience counters under
chaos, and the end-to-end train+infer acceptance path (ISSUE 2).

All CPU-only and deterministic; the chaos-driven tests reuse the seedable
injector (resilience/chaos.py) and carry the ``chaos`` marker.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu import telemetry
from deepspeed_tpu.comm import comm
from deepspeed_tpu.models.simple import SimpleModel
from deepspeed_tpu.resilience import ChaosInjector, install_chaos, uninstall_chaos
from deepspeed_tpu.runtime.config import DeepSpeedConfig, TelemetryConfig
from deepspeed_tpu.telemetry import (MetricsRegistry, NoopRegistry,
                                     PrometheusExporter, StepTracer,
                                     TelemetrySession, tracing)
from deepspeed_tpu.telemetry.registry import NOOP_REGISTRY

HIDDEN = 16
REPO = os.path.join(os.path.dirname(__file__), "..", "..")


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    yield
    telemetry.deconfigure()
    uninstall_chaos()


def _engine(telemetry_cfg=None, resilience=None):
    comm.cdb = None
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "tpu": {"data": 8},
           # synchronous saves: the chaos/counter assertions below must see
           # the 'latest' write land before the snapshot is taken
           "checkpoint": {"async_save": False},
           "steps_per_print": 0}
    if telemetry_cfg is not None:
        cfg["telemetry"] = telemetry_cfg
    if resilience is not None:
        cfg["resilience"] = resilience
    engine, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=HIDDEN, nlayers=2), config=cfg)
    return engine


def _batch(seed=0, bad=False):
    rng = np.random.RandomState(seed)
    x = rng.randn(8, HIDDEN).astype(np.float32)
    y = rng.randn(8, HIDDEN).astype(np.float32)
    if bad:
        x[0, 0] = np.nan
    return (x, y)


# ------------------------------------------------------------ registry math
class TestRegistry:
    def test_counter_and_gauge(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        reg.counter("c").inc(2.5)
        reg.gauge("g").set(1.0)
        reg.gauge("g").set(-4.0)
        snap = {(r["name"], r["kind"]): r for r in reg.snapshot()}
        assert snap[("c", "counter")]["value"] == 3.5
        assert snap[("g", "gauge")]["value"] == -4.0

    def test_labels_separate_series(self):
        reg = MetricsRegistry()
        reg.counter("ops", labels={"op": "a"}).inc()
        reg.counter("ops", labels={"op": "b"}).inc(2)
        vals = {tuple(sorted(r["labels"].items())): r["value"] for r in reg.snapshot()}
        assert vals[(("op", "a"),)] == 1 and vals[(("op", "b"),)] == 2

    def test_histogram_exact_percentiles_when_under_reservoir(self):
        reg = MetricsRegistry(default_max_samples=1000)
        h = reg.histogram("lat")
        for v in range(1, 101):          # 1..100
            h.observe(float(v))
        assert h.count == 100
        assert h.sum == pytest.approx(5050.0)
        assert h.min == 1.0 and h.max == 100.0
        assert h.percentile(50) == pytest.approx(50.5)
        assert h.percentile(90) == pytest.approx(90.1)
        assert h.percentile(99) == pytest.approx(99.01)

    def test_reservoir_bounds_memory_and_stays_representative(self):
        h = MetricsRegistry(default_max_samples=100).histogram("lat")
        for v in range(10_000):
            h.observe(float(v))
        assert len(h.samples) == 100          # bounded
        assert h.count == 10_000              # exact count survives
        assert h.max == 9999.0
        # a uniform sample of U[0,1e4) has p50 near 5000
        assert 2500 < h.percentile(50) < 7500

    def test_histogram_bucket_counts(self):
        h = MetricsRegistry().histogram("lat", bounds=[0.1, 1.0, 10.0])
        for v in (0.05, 0.5, 0.7, 5.0, 50.0):
            h.observe(v)
        assert h.bucket_counts == [1, 2, 1, 1]
        snap = h.snapshot()
        assert snap["bounds"] == [0.1, 1.0, 10.0]
        assert snap["bucket_counts"] == [1, 2, 1, 1]

    def test_registry_default_bounds_flow_to_histograms(self):
        reg = MetricsRegistry(default_bounds=[1.0, 2.0])
        assert reg.histogram("x").bounds == [1.0, 2.0]
        assert reg.histogram("y", bounds=[]).bounds is None  # explicit opt-out


# ------------------------------------------------------------- trace JSON
class TestTracer:
    def test_chrome_trace_well_formed(self, tmp_path):
        tr = StepTracer(pid=3)
        with tr.span("train_batch", step=1):
            with tr.span("fwd", step=1):
                pass
        tr.instant("sentinel_rewind", cat="resilience", reason="nan")
        path = str(tmp_path / "trace.json")
        tr.write(path)
        doc = json.load(open(path))
        assert isinstance(doc["traceEvents"], list)
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert {e["name"] for e in spans} == {"train_batch", "fwd"}
        for e in spans:
            assert e["pid"] == 3 and "ts" in e and "dur" in e and e["dur"] >= 0
            assert e["args"]["step"] == 1
        # nesting: fwd closed before train_batch, so fwd sits inside it
        by = {e["name"]: e for e in spans}
        assert by["fwd"]["ts"] >= by["train_batch"]["ts"]
        assert by["fwd"]["dur"] <= by["train_batch"]["dur"]
        assert [e for e in doc["traceEvents"] if e.get("ph") == "i"]

    def test_span_closes_on_exception(self):
        tr = StepTracer()
        with pytest.raises(RuntimeError):
            with tr.span("fwd"):
                raise RuntimeError("boom")
        assert [e["name"] for e in tr.events] == ["fwd"]

    def test_max_events_drops_not_grows(self):
        tr = StepTracer(max_events=3)
        for i in range(10):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.events) == 3
        assert tr.dropped == 7
        assert [s.name for s in tr.snapshot()] == ["s0", "s1", "s2"]

    def test_ring_is_bounded_and_overwrites_the_oldest(self):
        tr = StepTracer(max_events=3, ring=True)
        assert not tr.wrapped
        for i in range(10):
            with tr.span(f"s{i}"):
                pass
        assert [s.name for s in tr.snapshot()] == ["s7", "s8", "s9"]
        assert [e["name"] for e in tr.events] == ["s7", "s8", "s9"]
        assert tr.dropped == 0 and tr.wrapped
        assert tracing.RING.ring and tracing.RING.max_events == \
            tracing.RING_SPANS >= 16_000

    def test_span_records_absolute_monotonic_ids_parents_and_trace(self):
        tr = StepTracer()
        t_before = time.monotonic()
        with tr.span("train_batch", trace=7, step=7) as outer:
            with tr.span("dispatch", step=7) as inner:
                pass
            late = tr.record("wait", inner.t1, inner.t1 + 0.5, step=7)
            tr.instant("door_compile", cat="door", label="x")
        other = tr.record("data", 1.0, 2.0, trace=8)
        t_after = time.monotonic()
        spans = tr.snapshot()
        assert [s.name for s in spans] == [
            "dispatch", "wait", "door_compile", "train_batch", "data"]
        assert len({s.id for s in spans}) == 5
        assert t_before <= outer.t0 <= inner.t0 <= inner.t1 <= outer.t1 \
            <= t_after
        assert outer.parent is None and other.parent is None
        assert inner.parent == late.parent == spans[2].parent == outer.id
        # children take their trace from the span above; an explicit one wins
        assert [s.trace for s in spans] == [7, 7, 7, 7, 8]
        assert (late.t0, late.t1, late.dur) == (inner.t1, inner.t1 + 0.5, 0.5)
        assert spans[2].t1 is None and spans[2].dur == 0.0     # an instant
        assert inner.args == {"step": 7}

    def test_explicit_parent_and_the_deadline_worker(self):
        """Parents cross threads two ways: ``record(parent=...)`` after the
        fact, and ``run_with_deadline`` carrying the open span into its
        worker."""
        from deepspeed_tpu.resilience.watchdog import run_with_deadline

        tr = StepTracer()
        with tr.span("decode", cat="serving", trace="req-1") as tick:
            run_with_deadline(
                lambda: tr.instant("door_compile", cat="door"), timeout=30)
        child = tr.record("tick_wait", tick.t0, tick.t1, cat="serving",
                          parent=tick)
        (door,) = [s for s in tr.snapshot() if s.name == "door_compile"]
        assert door.parent == child.parent == tick.id
        assert door.trace == child.trace == "req-1"
        # a thread started any other way begins with no span above it
        t = threading.Thread(target=lambda: tr.instant("lone"))
        with tr.span("outer"):
            t.start()
            t.join(30)
        (lone,) = [s for s in tr.snapshot() if s.name == "lone"]
        assert lone.parent is None

    def test_chrome_events_keep_their_keys(self):
        tr = StepTracer(pid=2)
        with tr.span("train_batch", trace=4, step=4) as sp:
            tr.complete("comm:all_reduce", 250.0, cat="comm", op="all_reduce")
            tr.instant("sentinel_rewind", cat="resilience")
        comm_ev, instant, span = tr.to_chrome_trace()["traceEvents"][1:]
        for ev in (comm_ev, span):
            assert set(ev) == {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                               "args"}
            assert ev["ph"] == "X" and ev["pid"] == 2 and ev["tid"] == 0
        assert set(instant) == {"name", "cat", "ph", "s", "ts", "pid", "tid",
                                "args"} and instant["ph"] == "i"
        assert span["args"] == {"step": 4, "id": sp.id, "parent": None,
                                "trace": 4}
        assert comm_ev["args"]["parent"] == sp.id
        assert comm_ev["dur"] == pytest.approx(250.0)
        # µs since the tracer's start, derived from the absolute stamps
        assert span["ts"] == pytest.approx((sp.t0 - tr._t0) * 1e6)
        assert span["dur"] == pytest.approx(sp.dur * 1e6)
        # a session's list is converted once and then only appended to
        first = tr.events
        with tr.span("step"):
            pass
        assert tr.events is first and len(first) == 4

    def test_span_is_in_a_profile_as_ds_and_never_as_bench(self, tmp_path):
        import jax

        tr = StepTracer()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with tr.span("train_batch", trace=3, step=3):
                with tr.span("save_checkpoint", cat="checkpoint"):
                    pass
            tr.record("wait", 1.0, 2.0)         # stamps only: ring only
        finally:
            jax.profiler.stop_trace()
        with tr.span("after"):                  # no profile: no annotation
            pass
        (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        events = {e.name: {k: str(v) for k, v in e.stats}
                  for plane in jax.profiler.ProfileData.from_file(
                      str(path)).planes if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name.startswith(("ds/", "bench/"))}
        assert set(events) == {"ds/train/train_batch",
                               "ds/checkpoint/save_checkpoint"}
        assert events["ds/train/train_batch"] == {"trace": "3", "step": "3"}


class TestProgramDoor:
    def test_one_event_per_new_specialization_none_on_a_warm_call(self):
        from deepspeed_tpu.sharding import INHERIT, sharded_jit
        from deepspeed_tpu.sharding.jit import door_events

        label = "test/door_double"
        double = sharded_jit(lambda x: x * 2, label=label,
                             in_shardings=INHERIT, out_shardings=INHERIT,
                             donate_argnums=())
        mine = lambda: [e for e in door_events() if e[1] == label]
        ring = telemetry.get_tracer()
        instants = lambda: [s for s in ring.snapshot()
                            if s.name == "door_compile"
                            and s.args["label"] == label]
        assert mine() == [] and instants() == []
        t0 = time.monotonic()
        double(jnp.ones(4))                     # first shape: through the door
        assert [(l, n) for _, l, n in mine()] == [(label, 1)]
        assert t0 <= mine()[0][0] <= time.monotonic()
        for _ in range(3):                      # warm: nothing
            double(jnp.ones(4))
        assert len(mine()) == 1
        double(jnp.ones(5))                     # a new shape: once more
        double(jnp.ones(4))
        double(jnp.ones(5))
        assert [(l, n) for _, l, n in mine()] == [(label, 1), (label, 2)]
        # each is also an instant in the tracer, under a name goodput does
        # not book as compile badput
        assert [s.args["specializations"] for s in instants()] == [1, 2]
        assert all(s.cat == "door" and s.t1 is None for s in instants())


# ----------------------------------------------------- prometheus exposition
class TestPrometheusFormat:
    def test_exposition_format(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("train/steps").inc(7)
        reg.gauge("train/loss").set(1.5)
        h = reg.histogram("comm/op_latency_seconds", labels={"op": "all_reduce"})
        for v in (0.001, 0.002, 0.004):
            h.observe(v)
        hb = reg.histogram("lat_bounded", bounds=[0.01, 0.1])
        hb.observe(0.005)
        hb.observe(0.5)
        exp = PrometheusExporter(str(tmp_path / "m.prom"))
        exp.export(reg.snapshot(), step=7)
        text = open(str(tmp_path / "m.prom")).read()
        assert "# TYPE ds_train_steps counter" in text
        assert "# TYPE ds_train_loss gauge" in text
        assert "# TYPE ds_comm_op_latency_seconds summary" in text
        assert "# TYPE ds_lat_bounded histogram" in text
        assert 'ds_comm_op_latency_seconds{op="all_reduce",quantile="0.5"} 0.002' in text
        assert 'ds_comm_op_latency_seconds_count{op="all_reduce"} 3' in text
        assert 'ds_lat_bounded_bucket{le="+Inf"} 2' in text
        # every non-comment line is NAME{labels} VALUE with a legal name
        line_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.+eEinf]+$")
        for line in text.strip().split("\n"):
            if not line.startswith("#"):
                assert line_re.match(line), line


# --------------------------------------------------------- disabled = no-op
class TestDisabledNoop:
    def test_module_defaults_registry_noop_tracer_ring(self):
        assert telemetry.get_session() is None
        reg = telemetry.get_registry()
        assert isinstance(reg, NoopRegistry) and not reg.enabled
        reg.counter("x").inc()
        reg.gauge("x").set(1)
        reg.histogram("x").observe(1)
        assert len(reg) == 0 and reg.snapshot() == []
        # the span recorder is always there: the process-wide ring
        tracer = telemetry.get_tracer()
        assert tracer is tracing.RING
        with tracer.span("fwd", step=41) as sp:
            pass
        assert tracer.snapshot()[-1] is sp
        last = tracer.to_chrome_trace()["traceEvents"][-1]
        assert last["name"] == "fwd" and last["args"]["id"] == sp.id

    def test_session_without_trace_leaves_the_ring_in_place(self, tmp_path):
        cfg = TelemetryConfig(enabled=True, trace=False,
                              output_dir=str(tmp_path / "t"))
        session = telemetry.configure(cfg)
        assert session.tracer is None and session.trace_path is None
        assert telemetry.get_tracer() is tracing.RING
        session.flush()
        assert not (tmp_path / "t" / "trace.json").exists()
        traced = telemetry.configure(TelemetryConfig(
            enabled=True, output_dir=str(tmp_path / "t")))
        assert telemetry.get_tracer() is traced.tracer is not tracing.RING
        assert not traced.tracer.ring

    def test_configure_disabled_removes_config_session(self, tmp_path):
        cfg = TelemetryConfig(enabled=True, output_dir=str(tmp_path / "t"))
        assert telemetry.configure(cfg) is not None
        assert telemetry.get_registry().enabled
        assert telemetry.configure(TelemetryConfig()) is None
        assert not telemetry.get_registry().enabled

    def test_engine_disabled_adds_no_files_and_no_registry_entries(self, tmp_path, monkeypatch):
        """Acceptance companion: the disabled path creates nothing."""
        monkeypatch.chdir(tmp_path)           # catch any stray ./ds_telemetry
        engine = _engine()                    # no telemetry block
        assert engine.telemetry is None
        engine.train_batch(_batch())
        loss = engine.forward(_batch(1))
        engine.backward(loss)
        engine.step()
        comm.all_reduce(np.ones((8, 4), np.float32))
        assert telemetry.get_registry() is NOOP_REGISTRY
        assert len(telemetry.get_registry()) == 0
        assert telemetry.get_registry().snapshot() == []
        assert not os.path.exists(str(tmp_path / "ds_telemetry"))
        assert os.listdir(tmp_path) == []

    def test_engine_without_a_session_leaves_its_spans_in_the_ring(self):
        """The step's own spans exist with no ``telemetry`` block: data,
        then train_batch over dispatch and post_step, sharing the step as
        their ``trace``; ``wait`` only where the step itself reads its
        outputs on the host (here: never)."""
        engine = _engine()
        ring = telemetry.get_tracer()
        assert ring is tracing.RING
        before = {s.id for s in ring.snapshot()}
        for i in range(2):
            engine.train_batch(_batch(i))
        spans = [s for s in ring.snapshot() if s.id not in before
                 and s.cat == "train"]
        assert [s.name for s in spans] == [
            "data", "dispatch", "post_step", "train_batch"] * 2
        assert [s.trace for s in spans] == [0] * 4 + [1] * 4
        assert all(s.args["step"] == s.trace for s in spans)
        data, dispatch, post, batch = spans[:4]
        assert data.parent is None and batch.parent is None
        assert dispatch.parent == post.parent == batch.id
        assert data.t1 <= batch.t0 <= dispatch.t0 <= dispatch.t1 \
            <= post.t0 <= post.t1 <= batch.t1

    def test_wait_span_only_where_the_step_reads_its_outputs(self, tmp_path):
        engine = _engine(telemetry_cfg={"enabled": True,
                                        "output_dir": str(tmp_path / "t")})
        engine.train_batch(_batch())
        spans = telemetry.get_tracer().snapshot()
        (batch,) = [s for s in spans if s.name == "train_batch"]
        kids = [s.name for s in spans if s.parent == batch.id
                and s.cat == "train"]
        assert kids == ["dispatch", "wait", "post_step"]


# ------------------------------------------------- resilience counters
@pytest.mark.chaos
class TestResilienceCounters:
    def test_chaos_and_retry_counters_increment(self, tmp_path):
        engine = _engine(telemetry_cfg={"enabled": True,
                                        "output_dir": str(tmp_path / "t"),
                                        "flush_interval": 1000})
        # first 'latest' write fails -> one chaos injection, one retried op
        install_chaos(ChaosInjector(fail_at={"latest": [1]}))
        engine.train_batch(_batch())
        engine.save_checkpoint(str(tmp_path / "ck"))
        snap = {(r["name"], tuple(sorted(r["labels"].items()))): r
                for r in telemetry.get_registry().snapshot()}
        chaos_hits = [r for (n, _), r in snap.items()
                      if n == "resilience/chaos_injections"]
        assert chaos_hits and sum(r["value"] for r in chaos_hits) >= 1
        retries = [r for (n, _), r in snap.items() if n == "resilience/retries"]
        assert retries and sum(r["value"] for r in retries) >= 1

    def test_ds_chaos_env_injection_counts(self, tmp_path, monkeypatch):
        """DS_CHAOS env switch (no config) also feeds the counter."""
        from deepspeed_tpu.resilience import chaos as chaos_mod

        engine = _engine(telemetry_cfg={"enabled": True,
                                        "output_dir": str(tmp_path / "t"),
                                        "flush_interval": 1000})
        monkeypatch.setenv("DS_CHAOS", "seed=7,delay_rate=1.0,max_delay_s=0.001")
        monkeypatch.setattr(chaos_mod, "_env_checked", False)
        monkeypatch.setattr(chaos_mod, "_installed", None)
        engine.train_batch(_batch())
        engine.save_checkpoint(str(tmp_path / "ck"))
        hits = [r for r in telemetry.get_registry().snapshot()
                if r["name"] == "resilience/chaos_injections"
                and r["labels"].get("action") == "delay"]
        assert hits and sum(r["value"] for r in hits) >= 1

    def test_verify_failure_counter(self, tmp_path):
        from deepspeed_tpu.resilience import verify_tag

        cfg = TelemetryConfig(enabled=True, output_dir=str(tmp_path / "t"))
        telemetry.configure(cfg)
        ok, _ = verify_tag(str(tmp_path / "no_such_tag"))
        assert not ok
        snap = [r for r in telemetry.get_registry().snapshot()
                if r["name"] == "resilience/verify_failures"]
        assert snap and snap[0]["value"] == 1


# ---------------------------------------------------------- comm layer
class TestCommTelemetry:
    def test_busbw_fourth_slot_populated(self):
        logger = comm.CommsLogger()
        logger.append("all_reduce", "all_reduce", latency=0.001, msg_size=1 << 20, n=8)
        count, lats, algbw, busbw = logger.comms_dict["all_reduce"][1 << 20]
        assert count == 1 and len(lats) == 1
        assert busbw[0] == pytest.approx(algbw[0] * 2 * 7 / 8)
        d = logger.log_all(print_log=False, show_straggler=True)
        assert d is logger.comms_dict

    def test_straggler_skew_from_recent_window(self):
        logger = comm.CommsLogger()
        for lat in [0.001] * 5 + [0.01]:
            logger.append("all_gather", "all_gather", latency=lat, msg_size=4096, n=4)
        (op, size, n, mean, worst, skew), = logger.straggler_report()
        assert (op, size, n) == ("all_gather", 4096, 6)
        assert worst == pytest.approx(0.01)
        assert skew == pytest.approx(0.01 / (0.015 / 6))

    def test_eager_collective_feeds_histograms(self, tmp_path):
        _engine(telemetry_cfg={"enabled": True, "output_dir": str(tmp_path / "t"),
                               "flush_interval": 1000})
        comm.all_reduce(np.ones((8, 4), np.float32))
        hists = [r for r in telemetry.get_registry().snapshot()
                 if r["kind"] == "histogram" and r["name"] == "comm/op_latency_seconds"]
        assert hists and hists[0]["labels"]["op"] == "all_reduce"
        assert hists[0]["count"] >= 1 and hists[0]["max"] > 0


# ------------------------------------------------------------ monitor fixes
class TestMonitorFixes:
    def _csv(self, tmp_path):
        from deepspeed_tpu.monitor.monitor import csvMonitor
        from deepspeed_tpu.runtime.config import CSVConfig

        return csvMonitor(CSVConfig(enabled=True, output_path=str(tmp_path),
                                    job_name="job"))

    def test_csv_monitor_caches_handles(self, tmp_path):
        mon = self._csv(tmp_path)
        for step in range(5):
            mon.write_events([("Train/loss", 1.0 + step, step),
                              ("Train/lr", 0.1, step)])
        assert len(mon._files) == 2          # one cached handle per tag
        mon.close()
        rows = open(os.path.join(str(tmp_path), "job", "Train_loss.csv")).read().strip().split("\n")
        assert rows[0] == "step,Train/loss" and len(rows) == 6

    def test_csv_monitor_append_after_reopen_keeps_single_header(self, tmp_path):
        mon = self._csv(tmp_path)
        mon.write_events([("t", 1.0, 0)])
        mon.close()
        mon2 = self._csv(tmp_path)
        mon2.write_events([("t", 2.0, 1)])
        mon2.close()
        rows = open(os.path.join(str(tmp_path), "job", "t.csv")).read().strip().split("\n")
        assert rows == ["step,t", "0,1.0", "1,2.0"]

    def test_write_events_signatures_reconciled(self):
        import inspect

        from deepspeed_tpu.monitor.monitor import (Monitor, MonitorMaster,
                                                   TensorBoardMonitor,
                                                   WandbMonitor, csvMonitor)

        for cls in (Monitor, MonitorMaster, TensorBoardMonitor, WandbMonitor,
                    csvMonitor):
            params = inspect.signature(cls.write_events).parameters
            assert list(params) == ["self", "event_list", "flush"], cls.__name__
            assert params["flush"].default is True, cls.__name__


# --------------------------------------------------------- throughput TFLOPs
class TestThroughputTFLOPs:
    def _timer(self, estimator, **kw):
        from deepspeed_tpu.utils.timer import ThroughputTimer

        msgs = []
        t = ThroughputTimer(batch_size=4, start_step=0, steps_per_output=2,
                            logging_fn=msgs.append, sync_every_step=False,
                            flops_estimator=estimator, **kw)
        return t, msgs

    def test_log_line_carries_tflops(self):
        calls = {"n": 0}

        def estimator():
            calls["n"] += 1
            return 2.0e12

        t, msgs = self._timer(estimator)
        for _ in range(4):
            t.start()
            t.stop(global_step=True)
        assert msgs and all("EstTFLOPs=" in m for m in msgs)
        assert calls["n"] == 1               # lazily estimated once, cached

    def test_estimator_failure_degrades_gracefully(self):
        def estimator():
            raise RuntimeError("untraceable")

        t, msgs = self._timer(estimator)
        for _ in range(2):
            t.start()
            t.stop(global_step=True)
        assert msgs and "EstTFLOPs" not in msgs[0]
        assert "SamplesPerSec" in msgs[0]

    def test_engine_estimates_real_flops(self, tmp_path):
        engine = _engine(telemetry_cfg={"enabled": True,
                                        "output_dir": str(tmp_path / "t"),
                                        "flush_interval": 1000})
        engine.train_batch(_batch())
        flops = engine._estimate_step_flops()
        # SimpleModel: 2 layers of HIDDENxHIDDEN matmul, fwd+bwd, 8 samples —
        # the jaxpr walk must see strictly positive matmul flops
        assert flops > 0
        assert engine.tput_timer.flops_estimator.__func__ is \
            type(engine)._estimate_step_flops


# ------------------------------------------------------------- end to end
@pytest.mark.chaos
def test_train_and_infer_with_telemetry(tmp_path):
    """ISSUE 2 acceptance: short train loop + generate with telemetry on;
    asserts (a) fwd/bwd/step spans in the trace JSON, (b) non-empty comm-op
    histograms, (c) sentinel-rewind counter increments under injected chaos,
    (d) bin/ds_metrics renders the JSONL without error."""
    out = str(tmp_path / "telem")
    engine = _engine(
        telemetry_cfg={"enabled": True, "output_dir": out, "flush_interval": 1},
        resilience={"sentinel": {"enabled": True, "patience": 2, "max_rewinds": 2},
                    "chaos": {"enabled": True, "seed": 7, "delay_rate": 1.0,
                              "max_delay_s": 0.001}})
    assert engine.telemetry is not None

    # --- train: 3-call API (fwd/bwd/step spans) + fused train_batch -------
    for i in range(2):
        loss = engine.forward(_batch(i))
        engine.backward(loss)
        engine.step()
    engine.train_batch(_batch(2))

    # --- sentinel rewind under chaos (delays injected into the save I/O) --
    engine.save_checkpoint(str(tmp_path / "ck"))
    step_before = int(engine.state.step)
    engine.train_batch(_batch(3, bad=True))
    engine.train_batch(_batch(4, bad=True))      # streak hits patience -> rewind
    assert int(engine.state.step) == step_before

    # --- eager comm ops feed the per-op/per-size histograms ---------------
    comm.all_reduce(np.ones((8, 4), np.float32))
    comm.all_gather(np.ones((8, 4), np.float32))

    # --- inference: TTFT / per-token decode through the same session ------
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    tiny = GPT2Config(vocab_size=128, n_positions=64, n_embd=32, n_layer=2,
                      n_head=2, dtype=jnp.float32, remat=False,
                      use_flash_attention=False)
    inf = deepspeed_tpu.init_inference(GPT2Model(tiny),
                                       config={"dtype": "float32",
                                               "max_out_tokens": 64})
    prompt = np.arange(8, dtype=np.int32).reshape(1, 8)
    got = inf.generate(prompt, max_new_tokens=4)
    assert got.shape == (1, 12)

    telemetry.flush()

    # (a) spans
    trace = json.load(open(os.path.join(out, "trace.json")))
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"fwd", "bwd", "step", "train_batch", "data",
            "save_checkpoint", "load_checkpoint", "prefill", "decode"} <= names

    snap = telemetry.get_registry().snapshot()
    by_name = {}
    for r in snap:
        by_name.setdefault(r["name"], []).append(r)

    # (b) comm histograms
    comm_h = by_name.get("comm/op_latency_seconds", [])
    assert comm_h and sum(r["count"] for r in comm_h) >= 2
    assert {r["labels"]["op"] for r in comm_h} >= {"all_reduce", "all_gather"}

    # (c) sentinel rewind + chaos injection counters
    assert sum(r["value"] for r in by_name["resilience/sentinel_rewinds"]) >= 1
    assert sum(r["value"] for r in by_name["resilience/chaos_injections"]) >= 1

    # inference series landed too
    assert by_name["inference/ttft_seconds"][0]["count"] >= 1
    assert by_name["inference/decode_per_token_seconds"][0]["count"] >= 1
    assert sum(r["value"] for r in by_name["inference/generated_tokens"]) == 4

    # prometheus file exists and parses as exposition text
    prom = open(os.path.join(out, "metrics.prom")).read()
    assert "# TYPE ds_train_loss gauge" in prom

    # (d) ds_metrics renders the JSONL
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_metrics"),
         os.path.join(out, "metrics.jsonl")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert "telemetry summary" in proc.stdout
    assert "resilience/sentinel_rewinds" in proc.stdout
    assert "comm/op_latency_seconds" in proc.stdout

    # --json mode round-trips
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_metrics"), out, "--json"],
        capture_output=True, text=True)
    assert proc.returncode == 0
    assert any(r["name"] == "train/loss" for r in json.loads(proc.stdout))


def test_install_session_gets_engine_gauges(tmp_path):
    """A manually installed session (install_session, not the config path)
    must receive the engine's per-step gauges too — the engine gates on the
    live session, not its construction-time reference."""
    cfg = TelemetryConfig(enabled=True, output_dir=str(tmp_path / "t"),
                          flush_interval=1000)
    telemetry.install_session(TelemetrySession(cfg))
    engine = _engine()                    # no telemetry block in ds_config
    assert engine.telemetry is None       # config path did not install it...
    engine.train_batch(_batch())
    snap = telemetry.get_registry().snapshot()
    assert any(r["name"] == "train/loss" for r in snap)   # ...but gauges land


def test_inference_false_keeps_fused_generate(tmp_path):
    """telemetry.inference=false: generate() stays on the fused
    single-program path (no per-request host sync, no double dequant) and
    records no inference series."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = TelemetryConfig(enabled=True, output_dir=str(tmp_path / "t"),
                          inference=False, flush_interval=1000)
    telemetry.configure(cfg)
    tiny = GPT2Config(vocab_size=128, n_positions=64, n_embd=32, n_layer=2,
                      n_head=2, dtype=jnp.float32, remat=False,
                      use_flash_attention=False)
    inf = deepspeed_tpu.init_inference(GPT2Model(tiny),
                                       config={"dtype": "float32",
                                               "max_out_tokens": 64})
    inf.generate(np.arange(8, dtype=np.int32).reshape(1, 8), max_new_tokens=4)
    assert any(k[0] == "gen" for k in inf._compiled)      # fused program
    assert not any(k[0] == "gen2" for k in inf._compiled)
    assert not any(r["name"].startswith("inference/")
                   for r in telemetry.get_registry().snapshot())


def test_smoke_one_step_writes_valid_files(tmp_path):
    """CI smoke: ONE training step with telemetry on; the JSONL parses line
    by line and the trace is a well-formed Chrome-trace document."""
    out = str(tmp_path / "telem")
    engine = _engine(telemetry_cfg={"enabled": True, "output_dir": out,
                                    "flush_interval": 1})
    engine.train_batch(_batch())
    telemetry.flush()
    lines = open(os.path.join(out, "metrics.jsonl")).read().strip().split("\n")
    recs = [json.loads(l) for l in lines]
    assert recs and all({"kind", "name", "ts"} <= set(r) for r in recs)
    assert any(r["name"] == "train/loss" for r in recs)
    doc = json.load(open(os.path.join(out, "trace.json")))
    assert any(e.get("name") == "train_batch" and e.get("ph") == "X"
               for e in doc["traceEvents"])
    assert open(os.path.join(out, "metrics.prom")).read().startswith("# TYPE")


def test_monitor_fanout_gets_telemetry_series(tmp_path):
    """telemetry.monitor=true routes registry series through MonitorMaster
    (CSV writer here) as Telemetry/* tags."""
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    ds = DeepSpeedConfig({"csv_monitor": {"enabled": True,
                                          "output_path": str(tmp_path / "csv"),
                                          "job_name": "job"},
                          "telemetry": {"enabled": True,
                                        "output_dir": str(tmp_path / "t"),
                                        "monitor": True, "flush_interval": 1}})
    monitor = MonitorMaster(ds.monitor_config)
    session = telemetry.configure(ds.telemetry, monitor=monitor)
    session.registry.gauge("train/loss").set(0.5)
    session.step_end(1)
    monitor.csv_monitor.close()
    files = os.listdir(os.path.join(str(tmp_path / "csv"), "job"))
    assert "Telemetry_train_loss.csv" in files
