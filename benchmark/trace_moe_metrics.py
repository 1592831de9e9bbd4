#!/usr/bin/env python3
"""``trace_scope_metrics.py`` plus the per-layer metrics of a routed,
windowed train step (``benchmark/afmoe_metrics.py``), whose five files wait
under ``benchmark/train_moe_metrics``: not under ``benchmark/layer_metrics``
(``tests/benchmark/test_pangu_family.py`` pins that no waiting file there
moves a metric ``gpt2-760m.train.z1`` reports) and not beside PR 35's eight
under ``benchmark/train_scope_metrics`` (``tests/benchmark/test_scope_metrics
.py`` pins those eight by name, layer and source), and only a ``benchmark``
PR may edit either test. That PR moves the twenty-five files over as they
are (``<name>.json`` + ``<name>.py``, the form ``manifest.metric_spec``
reads) and this script goes with ``trace_scope_metrics.py``.

    python3 benchmark/trace_moe_metrics.py --workload <train cell> \
        --seed <n> [--seconds 30]

One traced run of the cell: its entries of ``BENCHMARK.json``, the waiting
files of ``layer_metrics`` that move what it reports, the eight by-scope
files and the five here. A reader that finds nothing to read (a cell with no
window layer, no routed expert) leaves its metric out. Prints what ``run.py
--trace 1`` prints.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import manifest as mf          # noqa: E402
from benchmark import run                     # noqa: E402
from benchmark.trace_metric_files import ENTRY_KEYS, with_metric_files  # noqa: E402
from benchmark.trace_scope_metrics import (SCOPE_DIR, metric_spec_from,  # noqa: E402
                                           with_scope_metrics)

MOE_DIR = mf.BENCH_DIR / "train_moe_metrics"


def with_moe_metrics(manifest, cell):
    """``manifest`` plus, for ``cell`` only, an entry of every file under
    ``MOE_DIR`` that moves a metric the cell reports."""
    moved = {m["name"] for m in mf.metrics_for(manifest, cell, "end_to_end")}
    out = dict(manifest, per_layer=list(manifest["per_layer"]))
    for path in sorted(MOE_DIR.glob("*.json")):
        spec = mf.load_json(path)
        if spec["moves"] in moved:
            out["per_layer"].append({**{k: spec[k] for k in ENTRY_KEYS},
                                     "workloads": [cell]})
    return out


def grown(manifest, cell):
    """(the manifest with every waiting file of the three places as an entry
    of ``cell``, the ``metric_spec`` that finds their files)."""
    manifest = with_moe_metrics(with_scope_metrics(
        with_metric_files(manifest, cell), cell), cell)
    return manifest, metric_spec_from(
        MOE_DIR, metric_spec_from(SCOPE_DIR, mf.metric_spec))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    a = ap.parse_args(argv)
    manifest, mf.metric_spec = grown(mf.load_manifest(), a.workload)
    result, info = run.execute(a.workload, a.seed, a.seconds, 1,
                               manifest=manifest)
    print("BENCH_INFO " + json.dumps(info), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
