"""``benchmark/sdar_block_metrics.py``: the block step's three metrics counted
by ``blocks``, on the request spans of BOTH programs: the one whose blocks
end in a pass that only commits (``commits`` = ``blocks``) and the one that
carries a block's commit in the next block's first pass (``commits`` 0,
``carried`` = ``blocks`` - 1), where ``benchmark/sdar_metrics.py``'s readers
fall silent. The files wait beside the older four (``PERF.md`` section 7),
read on the chip through ``benchmark/trace_metric_files.py``."""

import json

import pytest

from benchmark import manifest as mf
from benchmark import run
from tests.benchmark import test_sdar_family as sdar
from tests.benchmark.test_pangu_family import read_metric, span
from tests.benchmark.test_sdar_family import jax_config_restored  # noqa: F401

METRICS = ("tpot.block_tokens_per_pass", "tpot.block_pass_device_p50_s",
           "tpot.block_decode_attn_roofline")
# a request's span under either program: 33 blocks of 2 denoising passes
COMMIT_PASS = dict(passes=66, commits=33, blocks=33)
CARRIED = dict(passes=66, commits=0, carried=32, blocks=33)
OPS = {**sdar.SDAR_OPS,
       "%decode_attn.7 = bf16[1,64,512]{2,1,0} custom-call(%a)": 0.06}


def requests(counts):
    return [sdar.block_request(2.0, **counts), sdar.block_request(4.0, **counts)]


@pytest.mark.parametrize("counts, per_token, per_block", [
    (COMMIT_PASS, 4 / 3, 3), (CARRIED, 2.0, 2)], ids=["commit_pass", "carried"])
def test_the_three_metrics_count_by_blocks(counts, per_token, per_block,
                                           monkeypatch):
    ctx = sdar.sdar_ctx(OPS, requests(counts), monkeypatch)
    fam, cfg = ctx.family, ctx.config
    assert read_metric(METRICS[0], ctx) == pytest.approx(per_token)
    assert ctx.notes["carried_per_block"] == pytest.approx(
        counts.get("carried", 0) / 33)
    # a chunk: `per_block` passes a block x 4 blocks a tick; its median 0.08 s
    assert read_metric(METRICS[1], ctx) == pytest.approx(
        0.08 / (4 * per_block))
    assert ctx.notes["forward_passes_per_decode_chunk"] == 4 * per_block
    # decode_attn, both shapes: 0.16 s over 10 chunks and 3 prefills' passes,
    # against the K/V of 4,000 + (16 + 4) / 2 slots: a pass reads them once,
    # whether it carries a block or not
    share = read_metric(METRICS[2], ctx)
    per_pass = 0.16 / (10 * 4 * per_block + 3 * per_block)
    assert share == pytest.approx(
        100 * fam.block_attn_bytes(cfg, 4010) / 819e9 / per_pass)
    assert ctx.notes["block_decode_attn_roofline_bound"] == "memory"
    assert ctx.notes["block_decode_attn_carrying_share"] == pytest.approx(
        counts.get("carried", 0) / 66)
    assert 0 < share < 100


def test_a_carrying_pass_has_the_flops_of_two_blocks(monkeypatch):
    """Where the FLOPs set the least time (a chip with a slow matrix unit),
    a carrying pass counts its own block's and the carried block's, which
    sees a block's length fewer slots."""
    ctx = sdar.sdar_ctx(OPS, requests(CARRIED), monkeypatch)
    ctx.peaks = dict(ctx.peaks, bf16_flops_per_s=1e12)
    fam, cfg = ctx.family, ctx.config
    share = read_metric(METRICS[2], ctx)
    own, both = fam.block_attn_flops(cfg, 4010), fam.block_attn_flops(
        cfg, 4010) + fam.block_attn_flops(cfg, 4006)
    least = ((1 - 32 / 66) * own + 32 / 66 * both) / 1e12
    assert ctx.notes["block_decode_attn_roofline_bound"] == "compute"
    assert share == pytest.approx(100 * least / (0.16 / (10 * 8 + 3 * 2)))


@pytest.mark.parametrize("name", METRICS)
def test_the_metrics_read_nothing_where_there_is_nothing_to_read(
        name, monkeypatch):
    """An autoregressive model's spans, no such kernel, no such function in
    the family, no device plane -> None, no raise."""
    old = [span("request", 1.0, 2.0, prompt_len=2048, new_tokens=16,
                decode_ticks=1)]
    assert read_metric(name, sdar.sdar_ctx(OPS, old, monkeypatch)) is None
    if "roofline" in name:
        no_kernel = {k: v for k, v in OPS.items() if k.startswith("%fusion")}
        assert read_metric(name, sdar.sdar_ctx(
            no_kernel, requests(CARRIED), monkeypatch)) is None
        assert read_metric(name, sdar.sdar_ctx(
            OPS, requests(CARRIED), monkeypatch, family="olmoe")) is None
    off_device = sdar.sdar_ctx(OPS, requests(CARRIED), monkeypatch)
    off_device.trace = None
    if name != METRICS[0]:                  # a count, device or no device
        assert read_metric(name, off_device) is None


def test_the_files_wait_beside_the_older_four():
    """Not entries; ``trace_metric_files.py`` adds them for the real cell,
    and for no train cell."""
    from benchmark.trace_metric_files import with_metric_files

    real = mf.load_manifest()
    assert not {m["name"] for m in real["per_layer"]} & set(METRICS)
    grown = with_metric_files(real, sdar.REAL_CELL)["per_layer"]
    assert grown[:len(real["per_layer"])] == real["per_layer"]
    added = {m["name"]: m for m in grown[len(real["per_layer"]):]}
    assert set(METRICS) | set(sdar.METRICS) <= set(added)
    for name in METRICS:
        spec, read = mf.metric_spec("per_layer", name)
        assert callable(read) and spec["moves"] == "tpot_p50_s"
        assert mf.NAME_RE.match(name) and mf.UNIT_RE.match(spec["unit"])
        assert spec["source"] in mf.SOURCES and spec["layer"] in {
            m["layer"] for m in real["per_layer"]}
        assert added[name]["workloads"] == [sdar.REAL_CELL]
    assert with_metric_files(real, "gpt2-760m.train.z1")["per_layer"] == \
        real["per_layer"]


def test_rehearsal_reads_two_tokens_a_pass(jax_config_restored):  # noqa: F811
    """REHEARSAL, not a measurement: the tiny cell through ``run.execute``
    on the CPU with the three files as entries. The program's own count is
    there, device or no device: 4 tokens over 2 passes, less the blocks a
    request's last tick runs and nobody reads (16 or 32 new tokens)."""
    m = sdar.sdar_manifest()
    keys = ("name", "unit", "better", "source", "layer", "moves")
    m["per_layer"] += [
        {**{k: mf.metric_spec("per_layer", name)[0][k] for k in keys},
         "workloads": [sdar.CELL]} for name in METRICS]
    result, info = run.execute(sdar.CELL, seed=3, seconds=1.5, trace=1,
                               manifest=m, platforms=("cpu",),
                               traffic_dir=sdar.DATA / "traffic")
    line = json.loads(json.dumps(result))
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 2
    per_pass = line["metrics"][METRICS[0]]["value"]
    assert 4 / 3 < per_pass <= 2 + 1e-9
    assert info["notes"]["carried_per_block"] > 0.7
    assert not set(METRICS[1:]) & set(line["metrics"])
    # the older count reads the same spans and finds no commit pass in them
    assert "tpot.tokens_per_pass" not in line["metrics"]
