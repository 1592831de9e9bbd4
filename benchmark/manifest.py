"""``BENCHMARK.json`` and the data files it names.

A cell is ``{"name", "config", "traffic", "chips"}``; the harness finds
``configs/<config>.json``, ``traffic/<traffic>.json`` and, for every metric
the cell reports, ``end_to_end/<name>.json`` or ``layer_metrics/<name>.json``
(with an optional ``<name>.py`` beside it that defines ``read(ctx,
params)``). Adding a cell, a mix or a metric is adding a file and an entry.
"""

import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    return load_json(Path(root) / "BENCHMARK.json")


def find_cell(manifest, name):
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                     f"(have: {[c['name'] for c in manifest['workloads']]})")


def config_path(manifest, config, root=ROOT):
    for c in manifest["configs"]:
        if c["name"] == config:
            return Path(root) / c["file"]
    raise SystemExit(f"benchmark: no config {config!r} in BENCHMARK.json")


def traffic_path(traffic):
    return BENCH_DIR / "traffic" / f"{traffic}.json"


def metrics_for(manifest, cell_name, group):
    """The metrics of ``group`` ('end_to_end' | 'per_layer') that this cell
    reports: those with no ``workloads`` key, or that list the cell."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def metric_spec(group, name):
    """-> (spec dict, ``read`` callable or None) for one metric's files."""
    sub = "end_to_end" if group == "end_to_end" else "layer_metrics"
    spec = load_json(BENCH_DIR / sub / f"{name}.json")
    code = BENCH_DIR / sub / f"{name}.py"
    if not code.exists():
        return spec, None
    mod_spec = importlib.util.spec_from_file_location(
        f"benchmark._metric_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", code)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return spec, mod.read
