"""Test harness: fake 8-device CPU mesh.

The TPU translation of the reference's DistributedTest fork-based harness
(tests/unit/common.py:86): instead of forking world_size processes, JAX gives
us N virtual devices in ONE process via --xla_force_host_platform_device_count
(SURVEY §4 "TPU translation"). Every test sees an 8-device CPU backend and
builds whatever mesh shape it needs.
"""

import os

# Must be set before jax initializes its backend.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax

jax.config.update("jax_platforms", "cpu")

import pytest


def _slow_nodeids():
    """Measured-duration slow list (tests/slow_tests.txt, ≥5s on the 1-core
    CI box; parameterized ids match by base name). Regenerate from
    `pytest --durations=0` output when the suite's shape changes."""
    import os

    path = os.path.join(os.path.dirname(__file__), "slow_tests.txt")
    try:
        with open(path) as f:
            return {line.strip() for line in f if line.strip()}
    except OSError:
        return set()


# The files whose tests take longest, longest first (the sums of their
# tests' times in PR 46's whole six-worker tier-1 run in the sandbox under
# the driver's command, 2 Oct 2026, on a loaded machine: 856 s down to 252 s
# of 7,923, the run 1,373 s of 1,470 allowed with PR 45's list, which had
# `test_kimi_cell.py` (395 s) last of its twelve; the driver's own run of
# PR 46's tree, 5,937 s of tests in 1,174 s, ranks the same first five).
# `--dist loadfile` hands whole files to workers in the order of
# its queue, which xdist sorts by a file's NUMBER of tests: a file of three
# tests that take 200-370 s (`test_kimi_cell.py`) then starts last and one
# worker ends alone with it (~100 s of a run). With the long files at the
# head and the others behind them in xdist's own order, a run ends 70-110 s
# sooner. Regenerate from a run's junit file when the suite's shape changes.
# PR 47: TWO files stand among the first six, which xdist hands to six FRESH
# workers, for what they need and not for their length (the two they
# displaced follow at once): `test_profiling.py`'s census counts every live
# array of its process (the engines `test_zero3_gather.py` leaves behind
# fail it) and `test_first_token.py` aborts inside XLA:CPU when it follows
# `test_solar_family.py` + `test_pangu_family.py` in one process. Both
# reproduce at PR 46's commit with that order in ONE process; which files a
# worker meets in a row moves with every file's length (here
# `test_flash_attention.py`, 52 -> 73 tests): three whole runs of three.
_LONG_FILES = (
    "test_chip_bringup.py", "test_kda.py", "test_afmoe_family.py",
    "test_solar_family.py", "test_first_token.py", "test_profiling.py",
    "test_kimi_cell.py", "test_zero3_gather.py", "test_pangu.py",
    "test_flash_attention.py", "test_afmoe.py", "test_pangu_family.py",
    "test_olmoe_family.py")


def pytest_configure(config):
    # the queue keeps the collection's order, which the hook below sets
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    """Everything not slow is smoke: `pytest -m smoke` = the fast profile,
    `pytest -m slow` = the measured long tail, plain `pytest` = both. The
    long files' tests come first (``_LONG_FILES``), then the other files,
    those of most tests first; each file's tests in their own order."""
    import collections

    rank = {name: i for i, name in enumerate(_LONG_FILES)}
    tests_in = collections.Counter(item.path for item in items)
    items.sort(key=lambda item: (rank.get(item.path.name, len(rank)),
                                 -tests_in[item.path], str(item.path)))
    slow = _slow_nodeids()
    for item in items:
        base = item.nodeid.split("[", 1)[0]
        if base in slow and "slow" not in item.keywords:
            item.add_marker(pytest.mark.slow)
        if "slow" not in item.keywords:
            item.add_marker(pytest.mark.smoke)


@pytest.fixture(autouse=True)
def _reset_comm():
    """Each test gets a fresh global comm backend (and sharding core)."""
    yield
    from deepspeed_tpu.comm import comm

    comm.cdb = None
    from deepspeed_tpu.sharding import mesh as _smesh
    from deepspeed_tpu.sharding import jit as _sjit

    _smesh.reset_global_mesh()
    _sjit.reset_program_table()


@pytest.fixture(autouse=True)
def _witness_chaos(request):
    """Every chaos-marked drill runs under the runtime lock witness: the
    fault-injection suite is where framework threads contend hardest, so
    an acquisition-order inversion introduced by a refactor surfaces HERE
    as a failed teardown assert — with both acquire sites named — instead
    of as a once-a-month fleet wedge. Tests that deliberately manufacture
    inversions reset the witness themselves before returning."""
    if "chaos" not in request.keywords:
        yield
        return
    from deepspeed_tpu.analysis.race import witness_findings
    from deepspeed_tpu.utils import locks as _locks

    _locks.enable_witness(reset=True)
    try:
        yield
        findings = witness_findings()
        assert not findings, "\n".join(f.message for f in findings)
    finally:
        _locks.disable_witness()
        _locks.reset_witness()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Stamp each phase's report on the item so teardown-time fixtures
    (incident_forensics) can tell a PASSING drill from a failing one —
    forensics asserts must never shadow the drill's own failure."""
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "rep_" + rep.when, rep)


@pytest.fixture
def incident_forensics(request, tmp_path):
    """Post-drill incident forensics (the ds_blackbox acceptance rider):
    after a PASSING ``@pytest.mark.incident_drill(device=D)`` evict drill
    whose telemetry landed in ``tmp_path/"tel"``, the flight recorder
    must have dumped >= 1 incident bundle, and ``bin/ds_incident report``
    must merge it into a timeline naming the blamed device D as first
    cause. Runs as teardown so the drill body stays unchanged; skipped
    when the drill itself failed (one failure, not two)."""
    import subprocess
    import sys as _sys

    yield
    # teardown always releases the recorder's SIGUSR1 sentinel thread,
    # pass or fail — the thread-lifecycle sentinel would flag a leak
    from deepspeed_tpu import blackbox as _bb

    _bb.deconfigure()
    rep = getattr(request.node, "rep_call", None)
    if rep is None or not rep.passed:
        return
    marker = request.node.get_closest_marker("incident_drill")
    device = marker.kwargs.get("device") if marker else None
    tel = os.path.join(str(tmp_path), "tel")
    incidents = os.path.join(tel, "incidents")
    assert os.path.isdir(incidents), (
        "drill passed but the flight recorder wrote no incident bundle "
        f"under {tel} — the error-severity verdict should have triggered "
        "a dump")
    bundles = [d for d in os.listdir(incidents)
               if not d.endswith(".tmp")]
    assert bundles, f"incidents/ exists but holds no bundle: {incidents}"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [_sys.executable, os.path.join(repo, "bin", "ds_incident"),
         "report", tel], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "first cause:" in proc.stdout, proc.stdout
    if device is not None:
        assert f"device {device}" in proc.stdout, proc.stdout


@pytest.fixture
def mesh8():
    from deepspeed_tpu.parallel.topology import build_mesh

    return build_mesh(axis_dims={"pipe": 1, "data": 8, "expert": 1, "seq": 1, "tensor": 1})


@pytest.fixture(scope="session")
def tiny_ledger_run():
    """``run(out_dir, extra=None, devices=1, seq=128) -> (engine, entry)``:
    gpt2-tiny through ``deepspeed_tpu.initialize`` with the ``telemetry``,
    ``perf`` and ``goodput`` blocks (plus whatever ``extra`` arms), two
    warm-up steps, three timed ones (the fewest the ledger's t gate has
    power on), then one ``engine.perf_record``. ``devices`` is the width
    of the data-parallel mesh (ZeRO-3 past one device); ``None`` leaves
    the mesh to the config. The entry is also the one line of
    ``out_dir/ledger.jsonl``. Process globals the run armed are reset
    before it returns."""
    import sys
    import time
    import types

    steps = 3

    def run(out_dir, extra=None, devices=1, seq=128):
        import deepspeed_tpu
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.models.gpt2 import (PRESETS, GPT2Model,
                                               synthetic_lm_batch)
        from deepspeed_tpu.parallel.topology import build_mesh

        n_dev = devices or len(jax.devices())
        mcfg = PRESETS["gpt2-tiny"]
        cfg = {
            "train_batch_size": 2 * n_dev,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-4, "weight_decay": 0.01}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 3 if n_dev > 1 else 1},
            "gradient_clipping": 1.0,
            "steps_per_print": 0,
            "telemetry": {"enabled": True, "prometheus": False,
                          "output_dir": str(out_dir / "telemetry"),
                          "flush_interval": 1_000_000},
            "profiling": {"sample_interval": 1_000_000},
            "perf": {"ledger_path": str(out_dir / "ledger.jsonl")},
            "goodput": {},
        }
        cfg.update(extra or {})
        mpu = None
        if devices:
            mpu = types.SimpleNamespace(mesh=build_mesh(
                axis_dims={"pipe": 1, "data": devices, "expert": 1, "seq": 1,
                           "tensor": 1}, devices=jax.devices()[:devices]))
        try:
            engine, *_ = deepspeed_tpu.initialize(
                model=GPT2Model(mcfg), config=cfg, mpu=mpu)
            batch = engine._shard_batch(synthetic_lm_batch(
                cfg["train_batch_size"], seq, mcfg.vocab_size, seed=0))
            for _ in range(2):
                loss = engine.train_batch(batch)
            float(loss)
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = engine.train_batch(batch)
            float(loss)
            tok_s = cfg["train_batch_size"] * seq * steps / (
                time.perf_counter() - t0)
            entry = engine.perf_record(
                f"gpt2-tiny pretrain tok/s (seq={seq}, {n_dev} device(s))",
                round(tok_s, 1), "tok/s", model="gpt2-tiny", seed=0,
                timed_steps=steps, config={"seq": seq, "steps": steps})
            telemetry.flush()
            return engine, entry
        finally:
            telemetry.deconfigure()
            if "deepspeed_tpu.blackbox" in sys.modules:
                sys.modules["deepspeed_tpu.blackbox"].deconfigure()

    return run
