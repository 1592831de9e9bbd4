"""Kimi-Linear's tiny configuration (``tests/benchmark/data/kimi-tiny.json``)
as a CELL on the CPU: its files through the manifest checks, a train cell
through ``run.execute`` (the standing proof that the harness runs the
family's training path), and ``benchmark/kimi_witness.py`` at a small size.
The family's model against its reference: ``test_kimi_family.py``."""

import copy
import json

import jax

from benchmark import manifest as mf
from benchmark import run
from tests.benchmark import rehearsal, test_manifest
from tests.benchmark.test_kimi_family import KDA_METRICS
from tests.benchmark.test_pangu_family import jax_config_restored  # noqa: F401

DATA = rehearsal.DATA
CELL = "kimi-tiny.train.s.tiny"


def kimi_manifest(train_chips=1):
    """``rehearsal.manifest()`` plus one entry: ``kimi-tiny`` and its train
    cell, appended to every train metric."""
    m = copy.deepcopy(rehearsal.manifest(train_chips))
    body = mf.load_json(DATA / "kimi-tiny.json")
    m["configs"].append({
        "name": "kimi-tiny", "source": body["source"],
        "reduced": body["reduced"], "why": "rehearsal",
        "file": "tests/benchmark/data/kimi-tiny.json"})
    m["workloads"].append({"name": CELL, "config": "kimi-tiny",
                           "why": "rehearsal", "traffic": "train.s.tiny",
                           "chips": train_chips})
    for metric in m["end_to_end"] + m["per_layer"]:
        if any(".train." in w for w in metric.get("workloads", [])):
            metric["workloads"].append(CELL)
    return m


def test_the_tiny_configuration_passes_every_manifest_check():
    m = kimi_manifest()
    book = test_manifest.Book("kimi", m, "tests/benchmark/data/",
                              DATA / "traffic")
    config = next(c for c in m["configs"] if c["name"] == "kimi-tiny")
    test_manifest.test_config_entry_and_file(book, config)
    test_manifest.test_cell_entry_and_its_files(
        book, next(c for c in m["workloads"] if c["name"] == CELL))
    test_manifest.test_names_are_unique(book)
    for name in test_manifest.names(m, "configs", "workloads"):
        test_manifest.test_every_name_uses_only_the_allowed_characters(
            book, name)


def test_rehearsal_train_stream_traced(jax_config_restored):  # noqa: F811
    """REHEARSAL, not a measurement: ``deepspeed_tpu.initialize`` ->
    ``engine.train_batch`` through ``run.execute`` on the CPU mesh, bf16,
    ZeRO-1, the learning rate warmed up through the engine's scheduler: the
    engine's loss against this family's reference at set-up, nothing
    compiles in the window (the rate reaches the step as an operand), and
    the routing counts the steps left are read as the two counter metrics."""
    from benchmark import trace_kda_metrics

    real = mf.metric_spec
    manifest, mf.metric_spec = trace_kda_metrics.grown(
        kimi_manifest(jax.device_count()), CELL)
    try:
        result, info = run.execute(
            CELL, seed=2147483693, seconds=5.0, trace=1, manifest=manifest,
            platforms=("cpu",), traffic_dir=DATA / "traffic")
    finally:
        mf.metric_spec = real
    line = json.loads(json.dumps(result))
    assert line["failed"] == 0 and line["attempted"] >= 1, line
    check = info["check"]
    assert abs(check["loss_system"] - check["loss_reference"]) \
        <= check["tolerance"]
    if line["attempted"] > 3:
        assert line["correct"], (line, info["notes"])
    assert line["metrics"]["train.compiles_in_window"]["value"] == 0.0
    door = line["metrics"].get("train.door_compiles_in_window")
    assert door is None or door["value"] == 0.0
    share = line["metrics"]["train.moe_held_pair_share"]
    assert share["unit"] == "%" and 5 < share["value"] < 60     # 25 if even
    assert line["metrics"]["train.moe_load_max_over_mean"]["value"] >= 1.0
    assert info["notes"]["moe_held"] == [8, 8]
    # no device plane on the CPU: the trace readers are left out
    assert not set(KDA_METRICS) & set(line["metrics"])


def test_the_witness_refuses_the_broken_programs_at_a_small_size():
    """``benchmark/kimi_witness.py`` on the CPU (bf16, no kernel) at 160
    tokens: the sound program inside the limits, each broken one outside at
    least one of them; and the state pass alone in float32, where a ``dS``
    rounded to bfloat16 a chunk shows after three chunks already. The real
    limits are the chip's, at the published widths."""
    from benchmark import kimi_witness

    cfg = mf.load_json(DATA / "kimi-tiny.json")
    # this size's own readings (seed 3): sound <= 0.047 on the dense leaves,
    # 0.203 on router_w, 0.036 on the expert; backward_8bit >= 0.077
    limits = {name: 0.08 for name in kimi_witness.LIMITS}
    limits.update(loss=0.02, router_w=0.30, expert_gate_w=0.10)
    out = kimi_witness.witness(cfg, 3, 160, True, limits=limits)
    assert set(out["forms"]) == {"sound", "core_float32",
                                 *kimi_witness.BROKEN}
    assert out["forms"]["sound"]["over_its_limit"] == []
    for name in kimi_witness.BROKEN:
        assert out["forms"][name]["over_its_limit"], name
    core = out["forms"]["core_float32"]
    assert core["sound"] < 1e-5 < kimi_witness.CORE_LIMIT < core["state_bf16"]
    assert set(kimi_witness.LIMITS) == set(limits)
