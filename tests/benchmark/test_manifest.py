"""BENCHMARK.json against its contract, and against the data files it
names: a manifest the driver would refuse must fail here first."""

import re

import pytest

from benchmark import drivers, families, readers
from benchmark import manifest as mf

M = mf.load_manifest()
CELLS = [c["name"] for c in M["workloads"]]
E2E = [m["name"] for m in M["end_to_end"]]
LAYER = [m["name"] for m in M["per_layer"]]
ONE_LINE = re.compile(r"^[^\t\n]{1,200}$")


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert len((mf.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert 2 <= len(M["workloads"]) <= 24 and 1 <= len(M["configs"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16 and 1 <= len(M["per_layer"]) <= 128
    assert M["paths"] == ["benchmark", "tests/benchmark"]
    assert M["command"][:2] == ["python3", "benchmark/run.py"]
    assert all(ONE_LINE.match(w) for w in M["command"])


def test_a_full_check_fits_the_drivers_budget_at_24_cells():
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("name", CELLS + E2E + LAYER
                         + [c["name"] for c in M["configs"]]
                         + [c["traffic"] for c in M["workloads"]])
def test_every_name_uses_only_the_allowed_characters(name):
    assert mf.NAME_RE.match(name), name


def test_names_are_unique():
    for group in (CELLS, E2E + LAYER, [c["name"] for c in M["configs"]]):
        assert len(group) == len(set(group))
    pairs = [(c["config"], c["traffic"]) for c in M["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", M["end_to_end"] + M["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    e2e = metric["name"] in E2E
    allowed = {"name", "unit", "better", "source", "workloads"} | \
        ({"bound"} if e2e else {"layer", "moves"})
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert mf.UNIT_RE.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in mf.SOURCES
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert ONE_LINE.match(metric["layer"])
        assert metric["moves"] in E2E
    for w in metric.get("workloads", []):
        assert w in CELLS


@pytest.mark.parametrize("metric", M["end_to_end"] + M["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_has_a_data_file_that_agrees_and_a_reader(metric):
    group = "end_to_end" if metric["name"] in E2E else "per_layer"
    spec, custom = mf.metric_spec(group, metric["name"])
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        if key in metric:
            assert spec[key] == metric[key], key
    assert custom is not None or callable(getattr(readers, spec["reader"]))
    assert "workloads" not in spec      # BENCHMARK.json alone says where


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda c: c["name"])
def test_cell_entry_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] in (1, 4) and ONE_LINE.match(cell["why"])
    cfg = mf.load_json(mf.config_path(M, cell["config"]))
    traffic = mf.load_json(mf.traffic_path(cell["traffic"]))
    _, kind = drivers.get(traffic["driver"])
    assert kind in cfg               # engine settings for this kind of system
    mine = lambda group: [m["name"] for m in mf.metrics_for(M, cell["name"], group)]
    assert "setup_s" in mine("end_to_end") and len(mine("end_to_end")) >= 2
    assert mine("per_layer")
    for m in mf.metrics_for(M, cell["name"], "per_layer"):
        assert m["moves"] in mine("end_to_end"), (m["name"], m["moves"])


def test_at_most_one_cell_asks_for_four_chips():
    four = [c["name"] for c in M["workloads"] if c["chips"] == 4]
    assert four == ["gpt2-xl.train.z3x4"]
    assert len(four) <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert ONE_LINE.match(config["source"]) and ONE_LINE.match(config["why"])
    assert config["file"].startswith("benchmark/configs/")
    assert any(c["config"] == config["name"] for c in M["workloads"])
    body = mf.load_json(mf.ROOT / config["file"])
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"] and len(config["reduced"]) <= 16
    # the family module the harness dispatches on has the whole interface
    fam = families.get(body["family"])
    for fn in ("vocab_size", "build_model", "reference_logits",
               "reference_loss", "train_flops_per_token",
               "decode_flops_per_token", "decode_bytes_per_token"):
        assert callable(getattr(fam, fn)), fn
    assert fam.vocab_size(body) == 50257
    model = body["model"]
    assert model["n_embd"] == model["n_head"] * model["head_dim"]
    assert model["n_inner"] == 4 * model["n_embd"]
    for key in config["reduced"]:       # never a width
        assert mf.NAME_RE.match(key)
        assert not re.search(r"(_dim|_rank)$|embd|inner|hidden|head", key)


def test_published_widths():
    m760 = mf.load_json(mf.config_path(M, "gpt2-760m"))["model"]
    xl = mf.load_json(mf.config_path(M, "gpt2-xl"))["model"]
    assert (m760["n_layer"], m760["n_embd"], m760["n_head"], m760["head_dim"]) \
        == (24, 1536, 16, 96)           # Brown et al. 2020, Table 2.1
    assert (xl["n_layer"], xl["n_embd"], xl["n_head"], xl["head_dim"]) \
        == (48, 1600, 25, 64)           # openai-community/gpt2-xl config.json
    assert m760["vocab_size"] == xl["vocab_size"] == 50257


def test_every_file_under_paths_is_named_from_allowed_characters():
    for top in M["paths"]:
        for p in (mf.ROOT / top).rglob("*"):
            if "__pycache__" in p.parts or p.is_dir():
                continue
            rel = p.relative_to(mf.ROOT).as_posix()
            assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", rel), rel


def test_peaks_table_has_the_v5e_row_with_its_source():
    peaks = mf.load_json(mf.BENCH_DIR / "peaks.json")
    row = peaks["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert row["hbm_bytes"] == 16 * 2**30 and "Google Cloud" in peaks["_source"]


def test_unknown_names_fail_loudly():
    with pytest.raises(SystemExit):
        mf.find_cell(M, "no.such.cell")
    with pytest.raises(SystemExit):
        drivers.get("no_such_driver")
