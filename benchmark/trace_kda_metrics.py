#!/usr/bin/env python3
"""``trace_moe_metrics.py`` plus the per-layer metrics of a train step that
holds KDA and latent-attention layers (``benchmark/kimi_metrics.py``), whose
five files wait under ``benchmark/train_kda_metrics``: not under
``benchmark/layer_metrics``, not beside PR 35's eight under
``benchmark/train_scope_metrics`` and not beside PR 37's five under
``benchmark/train_moe_metrics`` (tests of the benchmark's pin each of those
places by name, and only a ``benchmark`` PR may edit them). That PR moves the
thirty files over as they are (``<name>.json`` + ``<name>.py``, the form
``manifest.metric_spec`` reads) and this script goes with the other two.

    python3 benchmark/trace_kda_metrics.py --workload <train cell> \
        --seed <n> [--seconds 30]

One traced run of the cell: its entries of ``BENCHMARK.json``, the waiting
files of ``layer_metrics`` that move what it reports, the eight by-scope
files, the routed experts' five and the five here. A reader that finds
nothing to read (a cell with no KDA layer, no latent attention) leaves its
metric out. Prints what ``run.py --trace 1`` prints.
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
from benchmark import trace_moe_metrics       # noqa: E402
from benchmark.trace_metric_files import ENTRY_KEYS  # noqa: E402
from benchmark.trace_scope_metrics import metric_spec_from  # noqa: E402

KDA_DIR = mf.BENCH_DIR / "train_kda_metrics"


def grown(manifest, cell):
    """(``trace_moe_metrics.grown``'s manifest plus, for ``cell`` only, an
    entry of every file under ``KDA_DIR`` that moves a metric the cell
    reports; the ``metric_spec`` that finds all their files)."""
    manifest, metric_spec = trace_moe_metrics.grown(manifest, cell)
    moved = {m["name"] for m in mf.metrics_for(manifest, cell, "end_to_end")}
    for path in sorted(KDA_DIR.glob("*.json")):
        spec = mf.load_json(path)
        if spec["moves"] in moved:
            manifest["per_layer"].append(
                {**{k: spec[k] for k in ENTRY_KEYS}, "workloads": [cell]})
    return manifest, metric_spec_from(KDA_DIR, metric_spec)


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
