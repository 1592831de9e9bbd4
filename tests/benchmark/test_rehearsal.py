"""REHEARSAL, not a measurement: ``run.py``'s three drivers in-process at
gpt2-tiny on the CPU mesh, through the same ``execute`` the command line
calls. It proves the plumbing (cell lookup by name, set-up, warm-up, window,
correctness check, result line) and nothing about speed: a number from
here is never written under the name of a device metric."""

import copy
import json

import jax
import pytest

from benchmark import manifest as mf
from benchmark import run

DATA = mf.ROOT / "tests" / "benchmark" / "data"
TRAFFIC = {"train": "train.tiny", "closed": "serve.closed.tiny",
           "open": "serve.open.tiny"}


def tiny_manifest():
    """The real manifest's metrics over three gpt2-tiny cells."""
    m = copy.deepcopy(mf.load_manifest())
    m["configs"] = [{"name": "gpt2-tiny", "source": "rehearsal", "reduced": [],
                     "file": "tests/benchmark/data/gpt2-tiny.json", "why": "x"}]
    # the train engine lays its mesh over every device jax reports: on the
    # CPU test mesh that is all eight (ZeRO-1 over data=8)
    m["workloads"] = [{"name": f"gpt2-tiny.{t}", "config": "gpt2-tiny",
                       "traffic": t, "why": "rehearsal",
                       "chips": jax.device_count() if t == "train.tiny" else 1}
                      for t in TRAFFIC.values()]
    for metric in m["end_to_end"] + m["per_layer"]:
        if "workloads" in metric:
            train = any(".train." in w for w in metric["workloads"])
            metric["workloads"] = [w["name"] for w in m["workloads"]
                                   if (".train." in w["name"]) == train]
    return m


@pytest.fixture
def jax_config_restored():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def rehearse(which, trace, seconds=1.5):
    return run.execute(f"gpt2-tiny.{TRAFFIC[which]}", seed=3, seconds=seconds,
                       trace=trace, manifest=tiny_manifest(),
                       platforms=("cpu",), traffic_dir=DATA / "traffic")


def check_line(result, metrics):
    line = json.loads(json.dumps(result))       # it must be plain JSON
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"   # a rehearsal says so itself
    assert set(line["metrics"]) == set(metrics)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_rehearsal_train_stream(jax_config_restored):
    result, info = rehearse("train", trace=0)
    check_line(result, {"train_tok_s_chip", "setup_s"})
    assert abs(info["check"]["loss_system"] - info["check"]["loss_reference"]) \
        <= info["check"]["tolerance"]
    assert info["notes"]["loss_last_quarter"] < info["notes"]["loss_first_quarter"]
    # the info line carries the diagnostics beside the judged rate
    assert info["window"]["rate"] == result["metrics"]["train_tok_s_chip"]["value"]
    assert info["window"]["steady_rate"] > 0
    assert info["window"]["longest_interval_s"] > 0
    split = info["setup_split_s"]
    assert split["programs_through_the_compiler"] > 0
    assert result["metrics"]["setup_s"]["value"] == pytest.approx(
        split["import"] + split["init"] + split["correctness_check"]
        + split["warm_up"], rel=1e-6)


def test_rehearsal_train_stream_traced(jax_config_restored):
    """--trace 1 in a train cell: the host-side per-layer metrics; nothing
    compiles inside the window; the diagnostics leave out the profiler's
    own stop."""
    result, info = rehearse("train", trace=1)
    line = json.loads(json.dumps(result))
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) >= {"train.compiles_in_window",
                                    "train.step_host_p50_s",
                                    "train.steady_tok_s_chip",
                                    "train.longest_step_s"}
    assert "train.mfu" not in line["metrics"]       # no peak for a CPU
    assert line["metrics"]["train.compiles_in_window"]["value"] == 0.0
    assert line["metrics"]["train.longest_step_s"]["value"] < 1.5
    assert info["profiler_stop_s"] > 0
    assert "busy_s" not in line["device"]


def test_train_weights_come_from_the_seed_and_set_up_does_not(
        jax_config_restored):
    """The engine is always built from ONE seed (its init program closes
    over the key, so another seed would be another compile) and the weights
    are then drawn from ``--seed``: other seed, other weights; same seed,
    same weights; same placements and dtypes as the engine made."""
    import numpy as np

    from benchmark import systems

    cfg = mf.load_json(DATA / "gpt2-tiny.json")
    traffic = mf.load_json(DATA / "traffic" / "train.tiny.json")
    wte = {}
    for name, seed in (("a", 3), ("b", 4), ("a2", 3)):
        s = systems.TrainSystem(cfg, traffic, seed, jax.device_count())
        st = s.engine.state
        assert st.params["wte"].dtype == np.dtype("bfloat16")
        assert st.master["wte"].dtype == np.float32
        assert st.params["wte"].sharding == s.engine.state_shardings.params["wte"]
        wte[name] = np.asarray(st.master["wte"])
        np.testing.assert_array_equal(
            np.asarray(st.params["blocks"]["fc_w"].astype(np.float32)),
            np.asarray(st.master["blocks"]["fc_w"].astype("bfloat16")
                       .astype(np.float32)))
        s.close()
    assert (wte["a"] != wte["b"]).any()
    np.testing.assert_array_equal(wte["a"], wte["a2"])


def test_rehearsal_closed_loop_traced(jax_config_restored):
    """With --trace 1 the per-layer metrics; on the CPU there is no device
    plane, so the readers of the device trace return nothing and are left
    out of the line, and no ``busy_s`` is claimed."""
    result, info = rehearse("closed", trace=1)
    line = json.loads(json.dumps(result))
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 4
    assert set(line["metrics"]) == {
        "ttft.queue_wait_p50_s", "serve.compiles_in_window", "ttft_p90_s",
        "caller_turnaround_p99_s", "serve.steady_tok_s",
        "serve.longest_callback_gap_s"}
    assert line["metrics"]["serve.compiles_in_window"]["value"] == 0.0
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert info["notes"]["sentinels_compared"] > 0
    assert info["check"]["worst_logit_shortfall"] <= info["check"]["margin"]
    assert not (mf.ROOT / ".bench_trace" / "gpt2-tiny.serve.closed.tiny").exists()


def test_rehearsal_open_loop(jax_config_restored):
    result, info = rehearse("open", trace=0)
    check_line(result, {"serve_tok_s", "ttft_p50_s", "tpot_p50_s", "setup_s"})


def test_the_command_fails_without_a_tpu(jax_config_restored):
    """No fallback: on this CPU-only sandbox the real entry point exits
    non-zero before it builds anything, and prints no result."""
    with pytest.raises(SystemExit) as e:
        run.execute("gpt2-760m.train.z1", seed=1, seconds=1.0, trace=0)
    assert "no accelerator" in str(e.value)


def test_a_cell_that_asks_for_more_chips_than_there_are_fails(
        jax_config_restored, monkeypatch):
    m = tiny_manifest()
    m["workloads"][0]["chips"] = 4
    one = jax.local_devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    with pytest.raises(SystemExit) as e:
        run.execute("gpt2-tiny.train.tiny", 1, 1.0, 0, manifest=m,
                    platforms=("cpu",), traffic_dir=DATA / "traffic")
    assert "asks for 4 chips" in str(e.value)
