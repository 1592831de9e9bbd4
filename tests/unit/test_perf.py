"""Perf-ledger tests: structured entries, noise-bound diff/gate, exposed
comm, autotuner exact-memory pruning + calibration, zero-overhead-when-off,
and the engine.perf_record end-to-end acceptance chain."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from deepspeed_tpu.perf import calibration as cal
from deepspeed_tpu.perf import ledger as led

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _entry(metric="gpt2-x pretrain MFU (bs=2/chip, seq=64)", value=0.5,
           unit="MFU", samples=None, **kw):
    e = {"metric": metric, "value": value, "unit": unit}
    if samples is not None:
        e["samples"] = samples
    e.update(kw)
    return e


@pytest.mark.perf
class TestLedger:
    def test_append_and_load_roundtrip(self, tmp_path):
        p = str(tmp_path / "l.jsonl")
        e = led.append_entry(p, _entry())
        assert e["schema"] == led.SCHEMA_VERSION and "ts" in e
        e2 = led.append_entry(p, _entry(value=0.6))
        got = led.load_entries(p)
        assert [g["value"] for g in got] == [0.5, 0.6]
        assert e2["schema"] == led.SCHEMA_VERSION

    def test_torn_final_line_skipped(self, tmp_path):
        p = str(tmp_path / "l.jsonl")
        led.append_entry(p, _entry())
        with open(p, "a") as f:
            f.write('{"metric": "torn by a kill -9')
        assert len(led.load_entries(p)) == 1

    def test_series_key_strips_config(self):
        a = _entry("gpt2-760m pretrain MFU (bs=12/chip, seq=1024)")
        b = _entry("gpt2-760m pretrain MFU (bs=14/chip, seq=2048)")
        assert led.series_key(a) == led.series_key(b)
        c = _entry(unit="decode-tok/s/chip")
        assert led.series_key(a) != led.series_key(c)

    def test_series_key_honors_explicit_series_field(self):
        ok = _entry("gpt2-760m pretrain MFU (bs=12/chip)")
        fail = _entry("gpt2-760m FAILED: RuntimeError boom", value=0.0,
                      series="gpt2-760m pretrain MFU", failed=True)
        assert led.series_key(fail) == led.series_key(ok)

    def test_latest_by_series_failed_never_shadows(self, tmp_path):
        entries = [_entry(value=0.5),
                   _entry("gpt2-x pretrain MFU FAILED: Boom", value=0.0),
                   _entry(value=0.48)]
        latest = led.latest_by_series(entries)
        # the FAILED line is its own series (different prefix); the real
        # series' latest is the last real measurement
        real = latest[led.series_key(entries[0])]
        assert real["value"] == 0.48

    def test_latest_by_series_skip_flag_never_shadows(self):
        entries = [_entry(value=0.5), _entry(value=0.0, skipped=True)]
        latest = led.latest_by_series(entries)
        assert latest[led.series_key(entries[0])]["value"] == 0.5

    def test_load_baseline_rejects_a_document_that_is_no_ledger(
            self, tmp_path):
        """Input from outside the program is rejected, not guessed at: the
        pre-PR-1 ``{"cmd","rc","tail","parsed"}`` wrapper is no ledger,
        and the error names the two formats that are."""
        p = str(tmp_path / "BENCH_r99.json")
        with open(p, "w") as f:
            json.dump({"n": 1, "cmd": "bench", "rc": 0,
                       "tail": json.dumps(_entry()), "parsed": _entry()}, f)
        with pytest.raises(ValueError) as e:
            led.load_baseline(p)
        msg = str(e.value)
        assert p in msg and "'tail'" in msg
        assert "JSON lines" in msg and "one JSON document" in msg
        from deepspeed_tpu.perf.cli import main as perf_main

        with pytest.raises(SystemExit) as ex:
            perf_main(["show", p])
        assert ex.value.code == 1

    def test_load_baseline_jsonl_passthrough(self, tmp_path):
        p = str(tmp_path / "l.jsonl")
        led.append_entry(p, _entry())
        assert len(led.load_baseline(p)) == 1

    def test_load_baseline_single_line_jsonl_is_one_entry(self, tmp_path):
        """A one-entry .jsonl is ALSO valid whole-file JSON — the
        extension must route it line-wise (one entry), never to the
        one-dict fallback paths."""
        p = str(tmp_path / "single.jsonl")
        led.append_entry(p, _entry(value=0.42))
        entries = led.load_baseline(p)
        assert len(entries) == 1 and entries[0]["value"] == 0.42
        assert led.series_key(entries[0]) == led.series_key(_entry())

    def test_load_baseline_jsonl_skips_torn_line(self, tmp_path):
        p = str(tmp_path / "torn.ndjson")
        led.append_entry(p, _entry(value=0.5))
        led.append_entry(p, _entry(value=0.6))
        with open(p, "a") as f:
            f.write('{"metric": "torn by a kill -9')
        assert [e["value"] for e in led.load_baseline(p)] == [0.5, 0.6]

    def test_gate_accepts_jsonl_baseline(self, tmp_path):
        """ds_perf gate --baseline ledger.jsonl --candidate ledger.jsonl:
        a ledger gates against itself."""
        from deepspeed_tpu.perf.cli import main as perf_main

        p = str(tmp_path / "ledger.jsonl")
        led.append_entry(p, _entry(samples=[0.5, 0.5, 0.5],
                                   headline=True, fingerprint="f"))
        assert perf_main(["gate", "--baseline", p, "--candidate", p]) == 0

    def test_git_rev_of_this_repo(self):
        rev = led.git_rev(REPO)
        assert rev and len(rev) >= 7


@pytest.mark.perf
class TestCompare:
    def test_significant_regression(self):
        old = _entry(value=0.5, samples=[1.0, 1.01, 0.99, 1.0],
                     fingerprint="aa")
        new = _entry(value=0.4, samples=[1.3, 1.31, 1.29, 1.3],
                     fingerprint="bb")
        r = led.compare(old, new)
        assert r["verdict"] == "regression"
        assert r["significant"] is True
        assert r["fingerprint_changed"] is True

    def test_noisy_drop_is_within_noise(self):
        """A value drop whose step-time samples cannot clear the t gate is
        NOT a regression — the r4 llama false-collapse rule."""
        old = _entry(value=0.5, samples=[1.0, 1.6, 0.8, 1.4])
        new = _entry(value=0.42, samples=[1.1, 1.7, 0.9, 1.5])
        r = led.compare(old, new)
        assert r["significant"] is False
        assert r["verdict"] == "within_noise"

    def test_underpowered_samples_cannot_exonerate(self):
        """Two samples per side have a t critical value of 12.71 — 'not
        significant' there means 'cannot tell'. A past-tolerance drop
        must fall back to the threshold verdict, not get a pass."""
        old = _entry(value=0.57, samples=[1.00, 1.01])
        new = _entry(value=0.41, samples=[1.30, 1.45])
        r = led.compare(old, new)
        assert r["significant"] is None      # underpowered, no verdict
        assert r["verdict"] == "regression"

    def test_powered_noise_still_exonerates(self):
        old = _entry(value=0.50, samples=[1.0, 1.6, 0.8])
        new = _entry(value=0.42, samples=[1.1, 1.7, 0.9])
        r = led.compare(old, new)
        assert r["significant"] is False and r["verdict"] == "within_noise"

    def test_no_samples_falls_back_to_threshold(self):
        r = led.compare(_entry(value=0.5), _entry(value=0.4))
        assert r["t_stat"] is None and r["verdict"] == "regression"
        r = led.compare(_entry(value=0.5), _entry(value=0.49))
        assert r["verdict"] == "within_noise"

    def test_improvement_symmetric(self):
        r = led.compare(_entry(value=0.4, samples=[1.3] * 4 + [1.31]),
                        _entry(value=0.5, samples=[1.0] * 4 + [1.01]))
        assert r["verdict"] == "improvement"

    def test_fingerprint_change_disables_exoneration(self):
        """Flat step times cannot wave through a value change caused by a
        DIFFERENT config (e.g. tokens/step drift halving MFU)."""
        old = _entry(value=0.5, samples=[1.0, 1.01, 0.99, 1.0],
                     fingerprint="aa")
        new = _entry(value=0.25, samples=[1.0, 1.01, 0.99, 1.0],
                     fingerprint="bb")
        r = led.compare(old, new)
        assert r["significant"] is False        # step times ARE flat
        assert r["fingerprint_changed"] is True
        assert r["verdict"] == "regression"     # threshold decides anyway
        # same samples, same fingerprint -> genuinely within noise
        r2 = led.compare(dict(old), dict(new, fingerprint="aa"))
        assert r2["verdict"] == "within_noise"

    def test_welch_t_degenerate_inputs(self):
        assert led.welch_t([1.0], [1.0, 2.0]) is None
        assert led.welch_t([1.0, 1.0], [1.0, 1.0]) is None
        assert led.welch_t([1.0, 1.0], [2.0, 2.0]) == float("inf")


@pytest.mark.perf
class TestPerfCLI:
    def _ledgers(self, tmp_path, new_value=0.4, samples=True):
        base = str(tmp_path / "base.jsonl")
        cand = str(tmp_path / "cand.jsonl")
        led.append_entry(base, _entry(
            value=0.5, samples=[1.0, 1.01, 0.99, 1.0] if samples else None))
        led.append_entry(cand, _entry(
            value=new_value,
            samples=[1.3, 1.29, 1.31, 1.3] if samples else None))
        return base, cand

    def test_gate_exits_2_on_regression(self, tmp_path, capsys):
        from deepspeed_tpu.perf.cli import main

        base, cand = self._ledgers(tmp_path)
        rc = main(["gate", "--baseline", base, "--candidate", cand])
        assert rc == 2
        assert "FAIL" in capsys.readouterr().out

    def test_gate_passes_within_tolerance(self, tmp_path, capsys):
        from deepspeed_tpu.perf.cli import main

        base, cand = self._ledgers(tmp_path, new_value=0.49)
        rc = main(["gate", "--baseline", base, "--candidate", cand,
                   "--rel-tol", "0.05"])
        assert rc == 0
        assert "PASS" in capsys.readouterr().out

    def test_gate_missing_series_fails_by_default(self, tmp_path, capsys):
        """A gated series the candidate never measured fails the gate —
        a bench that crashed before its line looks exactly like one that
        was never run. --allow-missing downgrades to a warning."""
        from deepspeed_tpu.perf.cli import main

        base = str(tmp_path / "base.jsonl")
        cand = str(tmp_path / "cand.jsonl")
        led.append_entry(base, _entry())
        led.append_entry(cand, _entry("other serving decode (x)",
                                      unit="decode-tok/s/chip"))
        assert main(["gate", "--baseline", base, "--candidate", cand]) == 3
        assert "FAIL" in capsys.readouterr().out
        assert main(["gate", "--baseline", base, "--candidate", cand,
                     "--allow-missing"]) == 0
        assert "WARN" in capsys.readouterr().out

    def test_gate_crashed_newest_fails_despite_older_success(
            self, tmp_path, capsys):
        """Append-only ledger with last week's success + today's FAILED
        line of the same series: the gate must fail — the fail line's
        explicit `series` field ties it to the measurement it failed to
        produce."""
        from deepspeed_tpu.perf.cli import main

        base = str(tmp_path / "base.jsonl")
        cand = str(tmp_path / "cand.jsonl")
        led.append_entry(base, _entry())
        led.append_entry(cand, _entry(value=0.5))          # older success
        led.append_entry(cand, {
            "metric": "gpt2-x FAILED: RuntimeError boom", "value": 0.0,
            "unit": "MFU", "series": "gpt2-x pretrain MFU",
            "failed": True, "error_type": "RuntimeError"})
        assert main(["gate", "--baseline", base, "--candidate", cand]) == 2
        out = capsys.readouterr().out
        assert "FAIL" in out and "RuntimeError" in out

    def test_gate_failed_candidate_line_fails(self, tmp_path):
        from deepspeed_tpu.perf.cli import main

        base = str(tmp_path / "base.jsonl")
        cand = str(tmp_path / "cand.jsonl")
        led.append_entry(base, _entry(value=0.5))
        led.append_entry(cand, _entry(value=0.0))
        assert main(["gate", "--baseline", base, "--candidate", cand]) == 2

    def test_gate_reappended_success_after_failed_retry_passes(
            self, tmp_path):
        """bench re-appends the KEPT measurement when a regression-guard
        retry loses/crashes — the gate must judge that, not the discarded
        retry's failure line."""
        from deepspeed_tpu.perf.cli import main

        base = str(tmp_path / "base.jsonl")
        cand = str(tmp_path / "cand.jsonl")
        led.append_entry(base, _entry(value=0.5))
        led.append_entry(cand, _entry(value=0.5))
        led.append_entry(cand, {
            "metric": "gpt2-x FAILED: TimeoutError deadline", "value": 0.0,
            "unit": "MFU", "series": "gpt2-x pretrain MFU", "failed": True})
        led.append_entry(cand, _entry(value=0.5, kept_after_retry=True))
        assert main(["gate", "--baseline", base, "--candidate", cand]) == 0

    def test_diff_json_output(self, tmp_path, capsys):
        from deepspeed_tpu.perf.cli import main

        base, cand = self._ledgers(tmp_path)
        assert main(["diff", base, cand, "--json"]) == 0
        [r] = json.loads(capsys.readouterr().out)
        assert r["verdict"] == "regression" and r["significant"] is True

    def test_show_lists_series(self, tmp_path, capsys):
        from deepspeed_tpu.perf.cli import main

        base, _ = self._ledgers(tmp_path)
        assert main(["show", base]) == 0
        assert "gpt2-x pretrain MFU" in capsys.readouterr().out

    def test_bin_ds_perf_subprocess(self, tmp_path):
        base = str(tmp_path / "base.jsonl")
        led.append_entry(base, _entry())
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_perf"),
             "show", base], capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        assert "gpt2-x pretrain MFU" in proc.stdout


@pytest.mark.perf
class TestCalibrationReport:
    def _rows(self, tmp_path):
        p = str(tmp_path / "l.jsonl")
        led.append_entry(p, {
            "kind": "tune_candidate", "exp_id": 0, "status": "ok",
            "tune": {"micro_batch": 8, "remat": "attn"},
            "predicted": {"mfu": 0.5, "hbm_bytes": 10 * 2**30},
            "measured": {"mfu": 0.4, "hbm_bytes": 12 * 2**30}})
        led.append_entry(p, {
            "kind": "tune_candidate", "exp_id": 1, "status": "oom",
            "tune": {"micro_batch": 32, "remat": "none"},
            "predicted": {"mfu": 0.55, "hbm_bytes": 20 * 2**30},
            "measured": {"mfu": None, "hbm_bytes": 30 * 2**30}})
        led.append_entry(p, {"kind": "tune_summary",
                             "counters": {"pruned_first_order": 1,
                                          "pruned_exact": 2}})
        return p

    def test_rows_and_summary_math(self, tmp_path):
        rows = cal.calibration_rows(led.load_entries(self._rows(tmp_path)))
        assert len(rows) == 2
        assert rows[0]["mfu_err_pct"] == pytest.approx(25.0)
        assert rows[0]["hbm_err_pct"] == pytest.approx(-100 / 6, rel=1e-3)
        s = cal.calibration_summary(rows)
        assert s["mfu_mape_pct"] == pytest.approx(25.0)

    def test_cli_renders_counters(self, tmp_path, capsys):
        from deepspeed_tpu.perf.cli import main

        assert main(["calibration", self._rows(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "MFU cost-model error" in out
        assert "pruned before compile (first-order model): 1" in out
        assert "pruned before execution (exact memory_analysis): 2" in out

    def test_predict_mfu_orders_sanely(self):
        fast = cal.predict_mfu({"remat": "none", "micro_batch": 16})
        slow = cal.predict_mfu({"remat": "full", "micro_batch": 2})
        off = cal.predict_mfu({"remat": "none", "micro_batch": 16,
                               "offload": True, "gas": 1})
        assert fast > slow and fast > off
        assert 0.0 < slow < 1.0 and 0.0 < off < 1.0


@pytest.mark.perf
@pytest.mark.profiling
class TestExposedComm:
    @staticmethod
    def _span(name, ts, dur, cat="train", step=None, **args):
        a = dict(args)
        if step is not None:
            a["step"] = step
        return {"ph": "X", "name": name, "cat": cat, "ts": ts, "dur": dur,
                "args": a}

    def _fleet(self):
        from deepspeed_tpu.profiling.aggregate import FleetTrace

        ft = FleetTrace()
        ft.add_rank(0, [
            self._span("train_batch", 0, 100, step=3),
            self._span("fwd", 0, 40, step=3),
            self._span("all_reduce", 40, 30, cat="comm",
                       op="all_reduce", seq=0, group=""),
            self._span("step", 70, 30, step=3),
        ])
        return ft

    def test_fully_exposed_single_rank(self):
        ft = self._fleet()
        assert ft.exposed_comm_us(step=3, align=False) == 30.0

    def test_overlap_by_other_rank_compute_subtracts(self):
        ft = self._fleet()
        ft.add_rank(1, [self._span("train_batch", 0, 100, step=3),
                        self._span("fwd", 0, 60, step=3)])
        # comm runs 40-70; rank 1 computes through 60 -> only 60-70 exposed
        assert ft.exposed_comm_us(step=3, align=False) == 10.0

    def test_no_comm_is_zero_no_spans_is_none(self):
        from deepspeed_tpu.profiling.aggregate import FleetTrace

        ft = FleetTrace()
        ft.add_rank(0, [self._span("train_batch", 0, 100, step=1),
                        self._span("fwd", 0, 100, step=1)])
        assert ft.exposed_comm_us(step=1, align=False) == 0.0
        assert ft.exposed_comm_us(step=99, align=False) is None

    def test_summary_averages_steps(self):
        ft = self._fleet()
        ft.add_rank(1, [
            self._span("train_batch", 200, 100, step=4),
            self._span("all_gather", 200, 20, cat="comm",
                       op="all_gather", seq=1, group=""),
        ])
        s = ft.exposed_comm_summary(align=False)
        assert s["per_step"] == {3: 30.0, 4: 20.0}
        assert s["avg_us_per_step"] == 25.0

    def test_critical_path_unchanged_by_refactor(self):
        ft = self._fleet()
        cp = ft.critical_path(step=3, align=False)
        assert cp is not None
        assert [seg[1] for seg in cp.segments] == ["fwd", "all_reduce",
                                                   "step"]
        assert cp.total_us == 100.0

    def test_interval_arithmetic(self):
        from deepspeed_tpu.profiling.aggregate import (_measure,
                                                       _merge_intervals,
                                                       _subtract_intervals)

        a = _merge_intervals([(0, 10), (5, 15), (20, 30)])
        assert a == [(0, 15), (20, 30)]
        s = _subtract_intervals(a, [(3, 7), (12, 22)])
        assert s == [(0, 3), (7, 12), (22, 30)]
        assert _measure(s) == 16

    def test_render_exposed_comm_line(self):
        from deepspeed_tpu.profiling.report import render_exposed_comm

        out = render_exposed_comm({"per_step": {3: 30.0, 4: 20.0},
                                   "avg_us_per_step": 25.0})
        assert "exposed_comm_us_per_step: 25" in out
        assert "worst step 3" in out
        assert "n/a" in render_exposed_comm(None)

    def test_ds_prof_merge_reports_exposed_comm(self, tmp_path):
        trace = str(tmp_path / "trace.rank0.json")
        with open(trace, "w") as f:
            json.dump({"traceEvents": self._fleet().by_rank[0]}, f)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_prof"),
             "merge", trace, "--json"], capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        rep = json.loads(proc.stdout)
        assert rep["exposed_comm_us_per_step"] == 30.0
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_prof"),
             "merge", trace], capture_output=True, text=True)
        assert "exposed_comm_us_per_step: 30" in proc.stdout


@pytest.mark.perf
class TestAttribution:
    def test_span_breakdown_percentiles(self):
        from deepspeed_tpu.perf.attribution import span_breakdown

        events = [{"ph": "X", "name": "fwd", "dur": float(d)}
                  for d in range(1, 101)]
        events.append({"ph": "M", "name": "process_name"})
        b = span_breakdown(events)
        assert b["fwd"]["count"] == 100
        assert b["fwd"]["p50_us"] == pytest.approx(50.5)
        assert b["fwd"]["p99_us"] == pytest.approx(99.0)   # 99.01 rounded

    def test_train_step_samples_trailing_window(self):
        from deepspeed_tpu.perf.attribution import train_step_samples

        events = [{"ph": "X", "name": "train_batch", "dur": d * 1e6}
                  for d in (9.0, 1.0, 1.1, 1.2)]
        assert train_step_samples(events, last=3) == [1.0, 1.1, 1.2]
        assert len(train_step_samples(events)) == 4

    def test_span_breakdown_windowed_excludes_warmup(self):
        """The attribution p99 must describe the timed window, not the
        warmup/compile step (a seconds-long span would dominate it)."""
        from deepspeed_tpu.perf.attribution import (span_breakdown,
                                                    trailing_window)

        events = [{"ph": "X", "name": "train_batch", "dur": 5e6}]   # compile
        events += [{"ph": "X", "name": "train_batch", "dur": 1000.0 + i}
                   for i in range(3)]
        events.append({"ph": "X", "name": "save_checkpoint", "dur": 7.0})
        b = span_breakdown(trailing_window(events, 3))
        assert b["train_batch"]["count"] == 3
        assert b["train_batch"]["p99_us"] < 2000       # compile excluded
        assert b["save_checkpoint"]["count"] == 1      # one-shots survive

    def test_exposed_comm_windowed_to_last_steps(self):
        from deepspeed_tpu.perf.attribution import exposed_comm_from_events

        def step(n, comm_us):
            return [
                {"ph": "X", "name": "train_batch", "cat": "train",
                 "ts": n * 1000.0, "dur": 900.0, "args": {"step": n}},
                {"ph": "X", "name": "fwd", "cat": "train",
                 "ts": n * 1000.0, "dur": 900.0 - comm_us,
                 "args": {"step": n}},
                {"ph": "X", "name": "all_reduce", "cat": "comm",
                 "ts": n * 1000.0 + 900.0 - comm_us, "dur": comm_us,
                 "args": {"op": "all_reduce", "seq": n, "group": ""}},
            ]

        events = step(1, 500.0) + step(2, 100.0) + step(3, 100.0)
        assert exposed_comm_from_events(events) == pytest.approx(700 / 3)
        assert exposed_comm_from_events(events, last_steps=2) == \
            pytest.approx(100.0)


@pytest.mark.perf
class TestEnginePerfWiring:
    def _engine(self, tmp_path, perf=None, telemetry_cfg=None):
        import deepspeed_tpu
        from deepspeed_tpu.models.simple import SimpleModel

        cfg = {"train_batch_size": 8, "steps_per_print": 0,
               "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}}
        if telemetry_cfg is not None:
            cfg["telemetry"] = telemetry_cfg
        if perf is not None:
            cfg["perf"] = perf
        engine, *_ = deepspeed_tpu.initialize(
            model=SimpleModel(hidden_dim=16, nlayers=2), config=cfg)
        return engine

    @staticmethod
    def _batch(i=0):
        rng = np.random.RandomState(i)
        return (rng.randn(8, 16).astype(np.float32),
                rng.randn(8, 16).astype(np.float32))

    def test_perf_record_structured_entry_and_ledger(self, tmp_path):
        from deepspeed_tpu import telemetry

        ledger = str(tmp_path / "ledger.jsonl")
        engine = self._engine(
            tmp_path, perf={"ledger_path": ledger},
            telemetry_cfg={"enabled": True,
                           "output_dir": str(tmp_path / "t"),
                           "flush_interval": 1000})
        try:
            for i in range(3):
                engine.train_batch(self._batch(i))
            e = engine.perf_record("simple train (bs=8)", 123.0,
                                   "tok/s", model="simple", seed=0,
                                   timed_steps=2)
            assert e["fingerprint"] and e["git_rev"]
            assert e["env"]["n_dev"] == 8
            assert len(e["samples"]) == 2
            assert "train_batch" in e["attribution"]["spans"]
            assert e["attribution"]["memory"]["bucket_bytes"]["params"] > 0
            [got] = led.load_entries(ledger)
            assert got["metric"] == "simple train (bs=8)"
        finally:
            telemetry.deconfigure()

    def test_perf_record_without_telemetry_still_records(self, tmp_path):
        ledger = str(tmp_path / "ledger.jsonl")
        engine = self._engine(tmp_path, perf={"ledger_path": ledger})
        engine.train_batch(self._batch())
        e = engine.perf_record("simple train (bs=8)", 1.0, "tok/s")
        assert "samples" not in e          # no tracer -> no span samples
        assert e["attribution"]["memory"]["total_bytes"] > 0
        assert e["fingerprint"]

    def test_strict_noop_without_block(self, tmp_path):
        """Without the ``perf`` block the package is never imported and
        perf_record refuses (a silently dropped record would be worse)."""
        mods = [m for m in list(sys.modules)
                if m == "deepspeed_tpu.perf" or
                m.startswith("deepspeed_tpu.perf.")]
        saved = {m: sys.modules.pop(m) for m in mods}
        try:
            engine = self._engine(tmp_path)
            engine.train_batch(self._batch())
            assert engine._perf_recorder is None
            assert not any(m == "deepspeed_tpu.perf"
                           or m.startswith("deepspeed_tpu.perf.")
                           for m in sys.modules)
            with pytest.raises(RuntimeError, match="perf"):
                engine.perf_record("x", 1.0, "u")
        finally:
            sys.modules.update(saved)

    def test_block_with_enabled_false_is_noop(self, tmp_path):
        engine = self._engine(tmp_path, perf={"enabled": False})
        assert engine._perf_recorder is None

    def test_attribution_false_config_knob_respected(self, tmp_path):
        from deepspeed_tpu.profiling import memory as prof_memory

        engine = self._engine(tmp_path, perf={"attribution": False})
        engine.train_batch(self._batch())
        census_before = prof_memory.CENSUS_CALLS
        e = engine.perf_record("x train (y)", 1.0, "u")
        assert "attribution" not in e         # headline + identity only
        assert e["fingerprint"]
        assert prof_memory.CENSUS_CALLS == census_before
        # explicit call-site override beats the config default
        e = engine.perf_record("x train (y)", 1.0, "u", attribution=True)
        assert "attribution" in e

    def test_empty_ledger_path_returns_entry_without_file(self, tmp_path,
                                                          monkeypatch):
        monkeypatch.chdir(tmp_path)   # guard: nothing may be written anywhere
        engine = self._engine(tmp_path, perf={})
        engine.train_batch(self._batch())
        e = engine.perf_record("x train (y)", 1.0, "u")
        assert e["fingerprint"]
        assert list(tmp_path.glob("*.jsonl")) == []

    def test_aot_memory_analysis_before_any_step(self, tmp_path):
        engine = self._engine(tmp_path)
        ma = engine.aot_memory_analysis(self._batch())
        if ma is None:
            pytest.skip("backend exposes no memory_analysis")
        assert set(ma) == {"argument", "output", "temp", "alias",
                           "generated_code"}
        assert ma["argument"] > 0
        # the AOT lower/compile is cached: the first real step reuses it
        engine.train_batch(self._batch())

    def test_config_rejects_unknown_perf_key(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        with pytest.raises(Exception, match="ledger"):
            DeepSpeedConfig({"train_batch_size": 8,
                             "perf": {"ledgre_path": "x"}})

    def test_schema_pass_knows_perf_block(self):
        from deepspeed_tpu.analysis.schema import walk_config

        findings, cfg = walk_config(
            {"train_batch_size": 8, "perf": {}}, world_size=1)
        assert cfg is not None
        [f] = [f for f in findings if f.rule == "config/cross-field"]
        assert "perf.attribution" in f.citation

    def test_schema_pass_quiet_with_telemetry_trace(self):
        from deepspeed_tpu.analysis.schema import walk_config

        findings, _ = walk_config(
            {"train_batch_size": 8, "perf": {},
             "telemetry": {"enabled": True}}, world_size=1)
        assert not [f for f in findings
                    if "perf.attribution" in f.citation]


@pytest.mark.perf
class TestAutotunerExactMemory:
    """Satellite: the first-order HBM model and ``memory_analysis``
    disagree — the exact-accounting path must win, and the skipped-compile
    counter must be recorded."""

    def _tuner(self, tmp_path, assume_hbm=None, **cfg_kw):
        import dataclasses

        from deepspeed_tpu.autotuning import Autotuner, AutotuningConfig
        from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2Model,
                                               synthetic_lm_batch)

        gcfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=32,
                          n_layer=2, n_head=2)

        def model_factory(remat="attn", **kw):
            return GPT2Model(dataclasses.replace(
                gcfg, remat=remat if remat != "none" else False))

        def batch_factory(bs):
            return synthetic_lm_batch(bs, 32, gcfg.vocab_size, seed=0)

        tuning = AutotuningConfig(
            enabled=True, start_profile_step=1, end_profile_step=2,
            results_dir=str(tmp_path), exps_dir=str(tmp_path / "exps"),
            mbs_list=[1], remat_list=["attn"], zero_stage_list=[1],
            assume_hbm_bytes=assume_hbm, **cfg_kw)
        return Autotuner(model_factory, batch_factory,
                         {"optimizer": {"type": "adam",
                                        "params": {"lr": 1e-3}},
                          "steps_per_print": 0},
                         tuning, seq_len=32)

    def test_exact_accounting_wins_over_first_order(self, tmp_path):
        """First-order model says FITS (its estimate is well under the
        budget) but the compiler's ledger says the real step does not —
        the candidate is pruned BEFORE execution, with the exact bytes in
        the record."""
        tuner = self._tuner(tmp_path)
        exact = _probe_exact_bytes(tuner)
        # budget chosen between the two verdicts: first-order estimate
        # fits comfortably under 1.5x, exact need exceeds 92%
        assume = int(exact / 0.92) - 1
        assert tuner.estimate_hbm_bytes(
            {"micro_batch": 1, "zero": 1, "remat": "attn"}, 8,
            hbm=assume) < 1.5 * assume
        tuner = self._tuner(tmp_path, assume_hbm=assume)
        tuner.tune()
        [exp] = tuner.experiments
        assert exp.status == "oom"
        assert "exact memory_analysis" in exp.error
        assert exp.extras["hbm_exact"] > 0.92 * assume
        assert tuner.pruned_exact == 1 and tuner.pruned_first_order == 0
        summary = json.load(open(tmp_path / "summary.json"))
        assert summary["counters"]["pruned_exact"] == 1

    def test_first_order_prune_skips_compile_and_counts(self, tmp_path,
                                                        monkeypatch):
        from deepspeed_tpu.autotuning.autotuner import Autotuner

        tuner = self._tuner(tmp_path, assume_hbm=1 << 30)
        monkeypatch.setattr(Autotuner, "estimate_hbm_bytes",
                            lambda self, tune, n_dev, hbm=None: 100 << 30)
        monkeypatch.setattr(
            Autotuner, "_run_one",
            lambda self, exp, hbm=None: pytest.fail(
                "first-order-pruned candidate must never compile"))
        tuner.tune()
        [exp] = tuner.experiments
        assert exp.status == "pruned"
        assert tuner.pruned_first_order == 1
        summary = json.load(open(tmp_path / "summary.json"))
        assert summary["counters"]["pruned_first_order"] == 1
        entries = led.load_entries(str(tmp_path / "perf_ledger.jsonl"))
        kinds = [e.get("kind") for e in entries]
        assert kinds == ["tune_candidate", "tune_summary"]
        assert entries[-1]["counters"]["pruned_first_order"] == 1

    def test_candidate_under_budget_runs_and_calibrates(self, tmp_path):
        tuner = self._tuner(tmp_path, assume_hbm=64 << 30)
        best = tuner.tune()
        assert best is not None
        [exp] = tuner.experiments
        assert exp.status == "ok"
        assert exp.extras.get("predicted_mfu") is not None
        entries = led.load_entries(str(tmp_path / "perf_ledger.jsonl"))
        [c] = [e for e in entries if e.get("kind") == "tune_candidate"]
        assert c["predicted"]["mfu"] is not None
        assert c["predicted"]["hbm_bytes"] is not None
        assert c["measured"]["mfu"] is not None
        assert c["fingerprint"]
        rows = cal.calibration_rows(entries)
        assert rows and rows[0]["mfu_err_pct"] is not None

    def test_ledger_disabled_builds_no_entries(self, tmp_path):
        """--ledger none (ledger_path="") must skip entry construction
        entirely — no file, no fingerprint hashing on the search path."""
        tuner = self._tuner(tmp_path, assume_hbm=64 << 30, ledger_path="")
        assert tuner.tune() is not None
        assert not (tmp_path / "perf_ledger.jsonl").exists()

    def test_exact_check_disabled_runs_over_budget(self, tmp_path):
        """With exact_memory_check off and a tiny assumed HBM, the (loose)
        first-order prune still fires — the candidate never runs — which
        is exactly the behavior the exact path replaces near the
        boundary."""
        tuner = self._tuner(tmp_path, assume_hbm=1 << 15,
                            exact_memory_check=False)
        tuner.tune()
        [exp] = tuner.experiments
        assert exp.status == "pruned"
        assert tuner.pruned_first_order == 1 and tuner.pruned_exact == 0


def _probe_exact_bytes(tuner):
    """Measure a tuner's sole candidate's exact AOT bytes once, so the
    disagree fixture can pick a budget between the two models' verdicts."""
    import gc

    import jax

    import deepspeed_tpu

    cfg = {k: v for k, v in tuner.candidate_space()[0].items()
           if k != "_tune"}
    model = tuner.model_factory(remat="attn")
    engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
    batch = tuner.batch_factory(engine.train_batch_size())
    ma = engine.aot_memory_analysis(batch)
    engine.state = None
    engine.invalidate_compiled()
    jax.clear_caches()
    gc.collect()
    if ma is None:
        pytest.skip("backend exposes no memory_analysis")
    return (ma["argument"] + ma["output"] - ma["alias"] + ma["temp"]
            + ma["generated_code"])


@pytest.mark.perf
class TestZeroOverheadWhenOff:
    """Measure the README "zero-overhead when disabled" claim: a step
    through the engine with NO observability blocks must sit within noise
    of invoking the engine's own compiled step directly, and the no-op
    instrumentation points must cost microseconds. Measured deltas are
    recorded in docs/CONFIG.md (telemetry section)."""

    def _engine(self):
        import deepspeed_tpu
        from deepspeed_tpu.models.simple import SimpleModel

        engine, *_ = deepspeed_tpu.initialize(
            model=SimpleModel(hidden_dim=16, nlayers=1),
            config={"train_batch_size": 8, "steps_per_print": 0,
                    "optimizer": {"type": "sgd", "params": {"lr": 1e-3}}})
        return engine

    def test_observability_off_is_really_off(self):
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.profiling import memory as prof_memory
        from deepspeed_tpu.telemetry.registry import NOOP_REGISTRY

        telemetry.deconfigure()
        engine = self._engine()
        census_before = prof_memory.CENSUS_CALLS
        rng = np.random.RandomState(0)
        batch = (rng.randn(8, 16).astype(np.float32),
                 rng.randn(8, 16).astype(np.float32))
        for _ in range(3):
            engine.train_batch(batch)
        assert telemetry.get_registry() is NOOP_REGISTRY
        assert prof_memory.CENSUS_CALLS == census_before
        assert engine._mem_profiler is None
        assert engine._perf_recorder is None

    def test_noop_instrumentation_point_cost(self):
        """One disabled instrumentation hit (tracer span + registry
        lookup) must cost single-digit microseconds."""
        from deepspeed_tpu import telemetry

        telemetry.deconfigure()
        tracer = telemetry.get_tracer()
        reg = telemetry.get_registry()
        n = 20_000
        t0 = time.perf_counter()
        for i in range(n):
            with tracer.span("fwd", step=i):
                pass
            reg.counter("train/steps").inc()
            reg.gauge("train/loss").set(1.0)
        per_call_us = (time.perf_counter() - t0) / n * 1e6
        assert per_call_us < 25.0, f"noop instrumentation {per_call_us:.1f}us"

    def test_engine_step_within_noise_of_bare_compiled_step(self):
        """Engine step (blocks absent) vs the same compiled program called
        directly. Bound is generous (CI boxes are noisy) but would still
        catch an accidentally-always-on census / sync / exporter."""
        import jax

        from deepspeed_tpu import telemetry

        telemetry.deconfigure()
        engine = self._engine()
        rng = np.random.RandomState(0)
        batch = (rng.randn(8, 16).astype(np.float32),
                 rng.randn(8, 16).astype(np.float32))
        for _ in range(3):
            loss = engine.train_batch(batch)       # compile + warm
        float(loss)
        compiled = engine._get_compiled_train_batch(1)
        sharded = engine._shard_batch(batch)
        k = 20

        def bare_window():
            t0 = time.perf_counter()
            with engine.mesh:
                for _ in range(k):
                    engine.state, metrics = compiled(engine.state, sharded)
            float(metrics.loss)
            return time.perf_counter() - t0

        def engine_window():
            t0 = time.perf_counter()
            for _ in range(k):
                loss = engine.train_batch(batch)
            float(loss)
            return time.perf_counter() - t0

        bare = min(bare_window() for _ in range(5))
        eng = min(engine_window() for _ in range(5))
        overhead_ms = (eng - bare) / k * 1e3
        # measured on the 8-device CPU mesh dev box: ~0.1-0.4 ms/step
        # (tree-map sharding checks + counters), vs multi-ms device steps
        # on any real model. 2.5ms absolute or 250% relative = a real
        # always-on hook, not scheduler noise (min-of-5 windows: a loaded
        # 2-core CI box legitimately doubles a window's host-side share).
        assert overhead_ms < max(2.5, 2.5 * bare / k * 1e3), (
            f"engine overhead {overhead_ms:.2f}ms/step over bare "
            f"{bare / k * 1e3:.2f}ms/step")


@pytest.mark.perf
class TestEngineLedgerEntry:
    """The acceptance chain of ``engine.perf_record``: a gpt2-tiny run on
    the CPU produces a ledger entry with span breakdown, memory buckets
    and fingerprint; ds_perf gate works on it and fails a synthetic
    regression."""

    @pytest.fixture(scope="class")
    def smoke(self, tmp_path_factory, tiny_ledger_run):
        tmp = tmp_path_factory.mktemp("ledger_smoke")
        _, entry = tiny_ledger_run(tmp, seq=64)
        return entry, str(tmp / "ledger.jsonl")

    def test_smoke_emits_attributed_ledger_entry(self, smoke):
        returned, ledger = smoke
        assert returned["unit"] == "tok/s" and returned["value"] > 0
        [entry] = led.load_entries(ledger)
        assert entry["model"] == "gpt2-tiny"
        assert entry["fingerprint"] and entry["git_rev"]
        assert entry["config"]["seq"] == 64
        assert entry["env"]["backend"] == "cpu"
        assert entry["samples"]
        assert "train_batch" in entry["attribution"]["spans"]
        assert entry["attribution"]["memory"]["bucket_bytes"]["params"] > 0
        # what perf_record hands back IS the ledger entry
        assert returned["fingerprint"] == entry["fingerprint"]

    def test_gate_passes_against_own_run_and_fails_synthetic_regression(
            self, smoke, tmp_path):
        from deepspeed_tpu.perf.cli import main

        _, ledger = smoke
        [entry] = led.load_entries(ledger)
        # same-run baseline: must pass
        assert main(["gate", "--baseline", ledger,
                     "--candidate", ledger]) == 0
        # synthetic regression: a baseline claiming 3x the measured value
        # (no samples on the baseline side -> plain threshold comparison;
        # the t path is covered by TestCompare) must fail the gate
        base = str(tmp_path / "base.jsonl")
        synthetic = {k: v for k, v in entry.items() if k != "samples"}
        synthetic["value"] = entry["value"] * 3
        led.append_entry(base, synthetic)
        assert main(["gate", "--baseline", base,
                     "--candidate", ledger]) == 2
