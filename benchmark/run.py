#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in ``BENCHMARK.json``, finds its configuration, traffic
mix and metrics by name, builds the system under test, warms up every
shape the cell uses (set-up), measures for ``--seconds`` on the host
clock, checks the outputs, and prints ONE JSON object as the last line of
stdout. ``--trace 0``: the end-to-end metrics, profiler off, server
untouched. ``--trace 1``: the per-layer metrics, with a device trace of
the first ``trace_seconds`` (traffic file) of the window. With no TPU, or
fewer chips than the cell asks for, it fails and prints no result: it
never falls back to the CPU.
"""

import time

T_PROCESS = time.monotonic()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import tempfile      # noqa: E402
import types         # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
# libtpu logs to the fixed path /tmp/tpu_logs unless told otherwise; keep
# them under the TMPDIR this run was given (read when jax loads libtpu)
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))

from benchmark import manifest as mf          # noqa: E402
from benchmark import drivers, readers        # noqa: E402
from benchmark.recorder import CompileCounter, Recorder  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"       # fixed: the path is part of the key
TRACE_DIR = ROOT / ".bench_trace"


class Tracer:
    """The profiler, on for the first ``seconds`` of the window."""

    def __init__(self, out_dir, seconds, rec):
        self.out_dir, self.seconds, self.rec = str(out_dir), seconds, rec
        self.active = False
        self.host_window = None     # (start, stop asked) on the host clock
        self.stopped_at = None      # stop_trace returned: seconds later
        self._span = None

    def start(self):
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # no per-Python-call events
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.active = True
        self._span = self.rec.span("window")
        self._span.__enter__()
        self._t0 = time.monotonic()

    def stop_if_due(self, now):
        if self.active and now - self._t0 >= self.seconds:
            self.stop()

    def stop(self):
        import jax

        if not self.active:
            return
        self.active = False
        self.host_window = (self._t0, time.monotonic())
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stopped_at = time.monotonic()


def device_stamp(devices):
    """The device as jax reports it, and the peak on the fullest chip."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def read_metrics(manifest, cell, group, ctx):
    out = {}
    for m in mf.metrics_for(manifest, cell["name"], group):
        spec, custom = mf.metric_spec(group, m["name"])
        read = custom or getattr(readers, spec["reader"])
        value = read(ctx, spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(workload, seed, seconds, trace, manifest=None,
            platforms=("tpu",), traffic_dir=None):
    """Run one cell; -> (result dict for the last line, info dict for the
    line before it). ``platforms`` other than ("tpu",) exist for the CPU
    rehearsal in tests/benchmark only; the command line cannot pass it."""
    manifest = manifest or mf.load_manifest()
    cell = mf.find_cell(manifest, workload)
    cfg = mf.load_json(mf.config_path(manifest, cell["config"]))
    tdir = Path(traffic_dir) if traffic_dir else mf.BENCH_DIR / "traffic"
    traffic = mf.load_json(tdir / f"{cell['traffic']}.json")
    driver, kind = drivers.get(traffic["driver"])

    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # keep every program, also those that compile in under a second: a warm
    # run then loads all of them and set-up stays the same from run to run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if devices[0].platform not in platforms:
        raise SystemExit(
            f"benchmark: no accelerator — jax reports platform "
            f"{devices[0].platform!r}; this benchmark runs on a TPU only")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"benchmark: cell {workload!r} asks for "
                         f"{cell['chips']} chips, jax reports {len(devices)}")
    peaks_table = mf.load_json(mf.BENCH_DIR / "peaks.json")
    kind_name = devices[0].device_kind
    if kind_name not in peaks_table and devices[0].platform == "tpu":
        raise SystemExit(f"benchmark: device_kind {kind_name!r} is not in "
                         "benchmark/peaks.json — add its published peaks")
    peaks = peaks_table.get(kind_name)
    compiles = CompileCounter()
    t_import = time.monotonic()

    from benchmark import families, systems

    rec = Recorder(annotate=bool(trace))
    system = systems.build(kind, cfg, traffic, seed, cell["chips"])
    t_init = time.monotonic()
    check_ok, check_detail = system.check(seed)
    t_check = time.monotonic()
    tracer = None
    if trace:
        system.instrument(rec)
        tracer = Tracer(TRACE_DIR / workload,
                        float(traffic.get("trace_seconds", 4.0)), rec)
    record = driver.run(system, traffic, seed, float(seconds), rec, tracer)
    t_done = time.monotonic()
    stamp = device_stamp(devices)
    system.close()

    # what a metric reader sees (readers.py documents the fields)
    ctx = types.SimpleNamespace(
        notes={}, record=record, rec=rec, config=cfg,
        family=families.get(cfg["family"]), traffic=traffic,
        chips=cell["chips"], peaks=peaks, compiles=compiles,
        setup_s=record["t_start"] - T_PROCESS,
        memory_peak_bytes=stamp["memory_peak_bytes"],
        trace=None, trace_host_window=None,
        profiler_stop=(tracer.host_window[1], tracer.stopped_at)
        if trace else None)
    breakdown = None
    if trace:
        from benchmark import trace_reduce

        ctx.trace_host_window = tracer.host_window
        ctx.trace = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(tracer.out_dir)),
            driver.GAP_LABELS, driver.GAP_DEFAULT)
        if ctx.trace["n_devices"]:
            stamp["busy_s"] = ctx.trace["busy_s"]
            stamp["window_s"] = ctx.trace["window_s"]
            breakdown = trace_reduce.breakdown(ctx.trace)
        shutil.rmtree(tracer.out_dir, ignore_errors=True)
    group = "per_layer" if trace else "end_to_end"
    result = {"correct": bool(check_ok and record["correct"]),
              "attempted": record["attempted"], "failed": record["failed"],
              "metrics": read_metrics(manifest, cell, group, ctx),
              "device": stamp}
    if breakdown is not None:
        result["breakdown"] = breakdown
    of = {"of": {"train": "steps", "serve": "callbacks"}[kind],
          "per_chip": kind == "train"}
    info = {"workload": workload, "seed": seed, "seconds": seconds,
            "setup_split_s": {
                "import": t_import - T_PROCESS, "init": t_init - t_import,
                "correctness_check": t_check - t_init,
                "warm_up": record["t_start"] - t_check,
                "of_which_compile_or_cache_load":
                    compiles.seconds(T_PROCESS, record["t_start"]),
                "programs_through_the_compiler":
                    compiles.count(T_PROCESS, record["t_start"])},
            "drain_and_reduce_s": time.monotonic() - record["t_end"],
            "run_s": t_done - T_PROCESS,
            # beside the judged rate, so that a run which held a stall
            # says so itself (the readers of the per-layer diagnostics)
            "window": {"rate": readers.window_rate(ctx, of),
                       "steady_rate": readers.steady_rate(ctx, of),
                       "longest_interval_s": readers.longest_interval(ctx, of)},
            "check": check_detail, "notes": {**record["notes"], **ctx.notes}}
    if trace:       # what the profiler's own stop cost, on the host clock
        info["profiler_stop_s"] = tracer.stopped_at - tracer.host_window[1]
    return result, info


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    a = ap.parse_args(argv)
    result, info = execute(a.workload, a.seed, a.seconds, a.trace)
    sys.stdout.flush()
    print("BENCH_INFO " + json.dumps(info), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
