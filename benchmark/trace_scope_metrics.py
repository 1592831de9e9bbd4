#!/usr/bin/env python3
"""``trace_metric_files.py`` plus the by-scope metrics of a train step
(``benchmark/scope_metrics.py``), whose files wait under
``benchmark/train_scope_metrics`` and not under ``benchmark/layer_metrics``:
``tests/benchmark/test_pangu_family.py`` pins that no waiting file there
moves a metric ``gpt2-760m.train.z1`` reports, and only a ``benchmark`` PR
may edit that test. That PR moves the sixteen files over as they are
(``<name>.json`` + ``<name>.py``, the form ``manifest.metric_spec`` reads)
and this script goes.

    python3 benchmark/trace_scope_metrics.py --workload <train cell> \
        --seed <n> [--seconds 30]

One traced run of the cell: its entries of ``BENCHMARK.json``, the waiting
files of ``layer_metrics`` that move what it reports, and the eight files
here. Prints what ``run.py --trace 1`` prints.
"""

import argparse
import importlib.util
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import manifest as mf          # noqa: E402
from benchmark import run                     # noqa: E402
from benchmark.trace_metric_files import ENTRY_KEYS, with_metric_files  # noqa: E402

SCOPE_DIR = mf.BENCH_DIR / "train_scope_metrics"


def with_scope_metrics(manifest, cell):
    """``manifest`` plus, for ``cell`` only, an entry of every file under
    ``SCOPE_DIR`` that moves a metric the cell reports."""
    moved = {m["name"] for m in mf.metrics_for(manifest, cell, "end_to_end")}
    out = dict(manifest, per_layer=list(manifest["per_layer"]))
    for path in sorted(SCOPE_DIR.glob("*.json")):
        spec = mf.load_json(path)
        if spec["moves"] in moved:
            out["per_layer"].append({**{k: spec[k] for k in ENTRY_KEYS},
                                     "workloads": [cell]})
    return out


def metric_spec_from(directory, fallback):
    """``manifest.metric_spec`` that looks under ``directory`` first."""
    def metric_spec(group, name):
        if group != "end_to_end" and (directory / f"{name}.json").exists():
            code = importlib.util.spec_from_file_location(
                "benchmark._metric_" + re.sub(r"\W", "_", name),
                directory / f"{name}.py")
            module = importlib.util.module_from_spec(code)
            code.loader.exec_module(module)
            return mf.load_json(directory / f"{name}.json"), module.read
        return fallback(group, name)

    return metric_spec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    a = ap.parse_args(argv)
    manifest = with_scope_metrics(
        with_metric_files(mf.load_manifest(), a.workload), a.workload)
    mf.metric_spec = metric_spec_from(SCOPE_DIR, mf.metric_spec)
    result, info = run.execute(a.workload, a.seed, a.seconds, 1,
                               manifest=manifest)
    print("BENCH_INFO " + json.dumps(info), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
