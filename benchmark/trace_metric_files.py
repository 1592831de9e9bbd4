#!/usr/bin/env python3
"""One traced run of a cell that also reads the per-layer metrics which
exist as FILES under ``benchmark/layer_metrics`` but are not entries of
``BENCHMARK.json`` (PERF.md section 7: an entry appended breaks the pin of
``tests/benchmark/test_program_spans.py``, one put in the middle reads to
the driver as a change to what was there).

    python3 benchmark/trace_metric_files.py --workload <cell> --seed <n> \
        [--seconds 30]

The manifest is the real one plus, for ``--workload`` alone, an entry for
every metric file that has none and whose ``moves`` the cell reports. Prints
what ``run.py --trace 1`` prints: ``BENCH_INFO`` and the result line.
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

ENTRY_KEYS = ("name", "unit", "better", "source", "layer", "moves")


def with_metric_files(manifest, cell):
    """``manifest`` plus a ``per_layer`` entry, for ``cell`` only, of every
    metric file that is not an entry and moves a metric the cell reports."""
    have = {m["name"] for m in manifest["per_layer"]}
    moved = {m["name"] for m in mf.metrics_for(manifest, cell, "end_to_end")}
    out = dict(manifest, per_layer=list(manifest["per_layer"]))
    for path in sorted((mf.BENCH_DIR / "layer_metrics").glob("*.json")):
        spec = mf.load_json(path)
        if spec["name"] not in have and spec["moves"] in moved:
            out["per_layer"].append({**{k: spec[k] for k in ENTRY_KEYS},
                                     "workloads": [cell]})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    a = ap.parse_args(argv)
    result, info = run.execute(
        a.workload, a.seed, a.seconds, 1,
        manifest=with_metric_files(mf.load_manifest(), a.workload))
    print("BENCH_INFO " + json.dumps(info), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
