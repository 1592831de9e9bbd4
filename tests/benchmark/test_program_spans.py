"""The readers of the program's own spans (``benchmark/program_spans.py``):
on spans and module events made by hand, with known answers, and once
against the REAL front-end and train engine at gpt2-tiny on the CPU, so
that renaming or dropping a span the benchmark reads fails here first. A
time from the CPU is a rehearsal of the plumbing and never a device metric.
"""

import types

import jax
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import program_spans as ps
from benchmark import systems
from benchmark.drivers import closed_loop
from benchmark.recorder import Recorder
from deepspeed_tpu.telemetry import StepTracer

DATA = mf.ROOT / "tests" / "benchmark" / "data"
M = mf.load_manifest()
NEW = ["ttft.prefill_tick_p50_s", "ttft.first_chunk_p50_s",
       "tpot.tick_launch_p50_s", "tpot.tick_wait_excess_p50_s",
       "tpot.tick_return_p50_s", "tpot.deliver_p50_s",
       "serve.door_compiles_in_window", "train.place_batch_p50_s",
       "train.dispatch_p50_s", "train.post_step_p50_s",
       "train.door_compiles_in_window", "train.flash_fwd_s_per_step",
       "train.flash_bwd_s_per_step"]
LO, HI = 100.0, 110.0           # the traced window on the host clock
SHIFT = -95.0                   # ... which the profile saw as 5.0 - 15.0
SKEW = 2e-4                     # the two clocks agree only so far


def read(name, ctx):
    """A metric through its own data file and ``<name>.py``, as the harness
    reads it."""
    spec, custom = mf.metric_spec("per_layer", name)
    assert custom is not None, name
    return custom(ctx, spec.get("params", {}))


def trace(modules=(), ops=None, n_devices=1):
    by_name = {}
    for s, e, nm in modules:
        by_name.setdefault(nm, []).append(e - s)
    return {"n_devices": n_devices, "annotations": [
                ("window", LO + SHIFT, HI + SHIFT)],
            "module_events": sorted(modules), "modules": by_name,
            "op_text_seconds": ops or {}}


def make_ctx(tracer, monkeypatch, requests=(), **trace_kw):
    monkeypatch.setattr(ps, "_live_tracer", lambda: tracer)
    return types.SimpleNamespace(
        notes={}, trace=trace(**trace_kw), trace_host_window=(LO, HI),
        record={"t_start": LO, "t_end": LO + 30.0, "requests": list(requests)})


def tick(tr, req, name, t0, launch, wait, ret, device, modules):
    """One tick span under ``req`` with its three children, and (``device``)
    the execution it waited for, ending when the wait's excess is half
    spent. -> the tick's end."""
    program = {"prefill": "jit_prefill(1)", "decode": "jit_decode_chunk(2)"}
    t1 = t0 + launch + wait + ret
    span = tr.record(name, t0, t1, cat="serving", parent=req)
    a, b = t0 + launch, t0 + launch + wait
    for child, s, e in (("tick_launch", t0, a), ("tick_wait", a, b),
                        ("tick_return", b, t1)):
        tr.record(child, s, e, cat="serving", parent=span)
    if device is not None:
        end = b - (wait - device) / 2 + SHIFT + SKEW
        modules.append((end - device, end, program[name]))
    return t1


def one_request(tr, modules, t, wait_s, prefill_dev, ticks, rid):
    """admission_wait, a prefill tick, ``ticks`` decode ticks each followed
    by a deliver; -> (end, the driver's record of it)."""
    t_submit = t - wait_s - 0.0001
    req = tr.record("request", t, t, cat="serving", trace=rid, request=rid)
    tr.record("admission_wait", t - wait_s, t, cat="serving", parent=req)
    t = tick(tr, req, "prefill", t + 0.0002, 0.0005, prefill_dev + 0.0015,
             0.0002, prefill_dev, modules)
    prefill_done = t
    stamps = []
    first_tokens = None
    for launch, wait, ret, device, deliver in ticks:
        t = tick(tr, req, "decode", t + 0.0001, launch, wait, ret, device,
                 modules)
        tr.record("deliver", t, t + deliver, cat="serving", parent=req)
        if first_tokens is None:
            first_tokens = t + deliver / 2
        stamps.append((t + deliver * 0.75, 16))
        t += deliver
    req.t1 = t
    req.args.update(prompt_len=64, new_tokens=16 * len(ticks),
                    status="completed", prefill_done_at=prefill_done,
                    first_tokens_at=first_tokens)
    return t, {"t_submit": t_submit, "t_ref": t_submit, "t_done": t + 0.0001,
               "stamps": stamps, "status": "completed",
               "n_tokens": 16 * len(ticks), "new_tokens": 16 * len(ticks)}


@pytest.fixture
def serve_case(monkeypatch):
    """Two requests of three decode ticks inside the window (launch 0.4,
    0.5, 0.6 ms; wait = device 0.100 s + 2.0, 2.2, 2.4 ms; return 0.1, 0.2,
    0.3 ms; deliver 0.8 ms); the second request's last tick has NO paired
    execution; a third request ends after the window."""
    tr = StepTracer(max_events=4096, ring=True)
    modules, requests = [], []
    ticks = [(0.0004, 0.1020, 0.0001, 0.100, 0.0008),
             (0.0005, 0.1022, 0.0002, 0.100, 0.0008),
             (0.0006, 0.1024, 0.0003, 0.100, 0.0008)]
    t, r = one_request(tr, modules, 101.0, 0.50, 0.020, ticks, "req-1")
    requests.append(r)
    unpaired = ticks[:2] + [(0.0006, 0.1024, 0.0003, None, 0.0008)]
    t, r = one_request(tr, modules, t + 0.001, 0.25, 0.030, unpaired, "req-2")
    requests.append(r)
    _, r = one_request(tr, modules, 110.2, 0.10, 0.020, ticks, "req-3")
    requests.append(r)
    return tr, make_ctx(tr, monkeypatch, requests, modules=modules)


def test_span_percentiles_follow_the_tree_and_the_window(serve_case):
    _, ctx = serve_case
    # six decode ticks inside the window (the third request's are outside)
    assert read("tpot.tick_launch_p50_s", ctx) == pytest.approx(0.0005)
    assert read("tpot.tick_return_p50_s", ctx) == pytest.approx(0.0002)
    assert read("tpot.deliver_p50_s", ctx) == pytest.approx(0.0008)
    assert ctx.notes["samples"]["decode/tick_launch"] == 6
    assert ctx.notes["samples"]["deliver"] == 6
    # the prefill ticks: device 20 and 30 ms + 1.5 ms excess + 0.7 ms
    assert read("ttft.prefill_tick_p50_s", ctx) == pytest.approx(0.0272)
    assert ctx.notes["samples"]["prefill"] == 2


def test_first_chunk_and_what_ttft_has_besides(serve_case):
    _, ctx = serve_case
    # prefill done -> 0.1 ms -> tick (0.4 + 102.0 + 0.1 ms) -> half a deliver
    assert read("ttft.first_chunk_p50_s", ctx) == pytest.approx(
        0.0001 + 0.1025 + 0.0004)
    # ttft (submit -> first callback) less wait, prefill tick, first chunk:
    # the submit call 0.1 ms + 0.2 ms before the prefill tick + a quarter
    # of a deliver until the callback
    assert ctx.notes["ttft_unaccounted_s"] == pytest.approx(0.0005)


def test_wait_excess_pairs_by_midpoint_and_skips_the_unpaired(serve_case):
    _, ctx = serve_case
    # five of six decode ticks have their execution: excess 2.0, 2.2, 2.4,
    # 2.0, 2.2 ms -> median 2.2 ms; the clock skew moves nothing
    assert read("tpot.tick_wait_excess_p50_s", ctx) == pytest.approx(0.0022)
    assert ctx.notes["samples"]["tick_wait~decode_chunk"] == 5
    assert ctx.notes["tick_residual_s"] == pytest.approx(0.0, abs=1e-12)
    # a request's tpot runs from its first callback to its last: ticks two
    # and three with their delivers and the 0.1 ms before each = 0.2080 s
    # for 32 tokens, so 0.1040 s a tick; device 0.100 + launch 0.5 + excess
    # 2.2 + return 0.2 + deliver 0.8 ms = 0.1037 s are covered
    assert ctx.notes["tick_unaccounted_s"] == pytest.approx(0.0003, abs=1e-9)


def test_a_wrapped_ring_is_said_and_not_read(monkeypatch):
    tr = StepTracer(max_events=4, ring=True)
    for i in range(6):                          # wraps; oldest kept: 104.0
        tr.record("deliver", 102.0 + i, 102.5 + i, cat="serving")
    ctx = make_ctx(tr, monkeypatch)
    assert read("tpot.deliver_p50_s", ctx) is None
    assert ctx.notes["program_spans"] == ps.WRAPPED
    # wrapped, but only spans from before the window were lost: read
    tr = StepTracer(max_events=4, ring=True)
    for t in (50.0, 99.0, 101.0, 102.0, 103.0):
        tr.record("deliver", t, t + 0.5, cat="serving")
    ctx = make_ctx(tr, monkeypatch)
    assert tr.wrapped
    assert read("tpot.deliver_p50_s", ctx) == pytest.approx(0.5)
    assert "program_spans" not in ctx.notes


def test_door_counter_reads_the_window_and_names_the_set_up(monkeypatch):
    from deepspeed_tpu.sharding import jit as door

    monkeypatch.setattr(door, "_DOOR_EVENTS", [
        (20.0, "engine/init_state", 1), (40.0, "engine/train_batch", 1),
        (41.0, "engine/train_batch", 2), (LO + 3.0, "engine/train_batch", 3),
        (LO + 31.0, "engine/eval", 1)])
    ctx = make_ctx(StepTracer(ring=True), monkeypatch)
    assert read("train.door_compiles_in_window", ctx) == 1.0
    assert ctx.notes["door"] == {
        "in_window": ["engine/train_batch"],
        "during_setup": {"engine/init_state": 1, "engine/train_batch": 2}}
    monkeypatch.setattr(door, "_DOOR_EVENTS", door._DOOR_EVENTS[:3])
    assert read("serve.door_compiles_in_window", ctx) == 0.0


def test_kernel_seconds_go_by_the_instruction_name(monkeypatch):
    call = ('custom-call(%p.1, %flash_fwd.7), custom_call_target='
            '"tpu_custom_call"')
    ops = {f"%flash_fwd.3 = bf16[128,1024,96]{{2,1,0}} {call}": 0.30,
           f"%flash_fwd.12 = bf16[128,1024,96]{{2,1,0}} {call}": 0.34,
           f"%flash_bwd_dq.2 = bf16[128,1024,96]{{2,1,0}} {call}": 0.20,
           # as jax names it where no remat scope wraps the transform
           f"%transpose_jvp_flash_bwd_dkv__.2 = (bf16[128,1024,96]) {call}":
               0.50,
           # an op that only CONSUMES a kernel's result is not the kernel
           "%fusion.9 = bf16[8] fusion(%flash_fwd.3), kind=kLoop": 9.0}
    steps = [(6.0 + i, 6.4 + i, "jit_step_fn(3)") for i in range(4)]
    ctx = make_ctx(StepTracer(ring=True), monkeypatch, modules=steps, ops=ops)
    assert read("train.flash_fwd_s_per_step", ctx) == pytest.approx(0.16)
    assert read("train.flash_bwd_s_per_step", ctx) == pytest.approx(0.175)
    # kernels the program did not name (the commit before): nothing to read
    ctx.trace["op_text_seconds"] = {
        f"%closed_call.3 = bf16[128,1024,96] {call}": 0.3}
    assert read("train.flash_fwd_s_per_step", ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_no_device_plane_reads_nothing(name, serve_case):
    """The CPU rehearsal (``test_rehearsal_closed_loop_traced`` pins its
    metric set): no device plane, no program-span metric."""
    _, ctx = serve_case
    ctx.trace["n_devices"] = 0
    assert read(name, ctx) is None
    ctx.trace = None
    assert read(name, ctx) is None and ctx.notes == {}


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_recorder_reads_nothing(name, monkeypatch):
    """The benchmark's files are laid over the commit BEFORE this one too:
    its tracer records nothing and its door counts nothing. The readers
    return None there and do not raise."""
    from deepspeed_tpu.sharding import jit as door

    class OldNoopTracer:
        events = []

    monkeypatch.delattr(door, "door_events")
    ctx = make_ctx(OldNoopTracer(), monkeypatch, modules=[
        (6.0, 6.4, "jit_step_fn(3)"), (7.0, 7.1, "jit_decode_chunk(2)")])
    assert read(name, ctx) is None


def test_every_new_metric_is_in_the_manifest_with_its_cells():
    by_name = {m["name"]: m for m in M["per_layer"]}
    assert [m["name"] for m in M["per_layer"]][-len(NEW):] == NEW
    for name in NEW:
        cells = by_name[name]["workloads"]
        assert all((".train." in c) == name.startswith("train.")
                   for c in cells) and len(cells) == 2
        assert by_name[name]["source"] in (
            "program_span", "program_counter", "device_trace")


# ------------------------------------------------- against the real program
def around(spans, name, program, device_share=0.9):
    """Module events made up around the program's own spans ``name``: each
    execution lies inside its span and takes ``device_share`` of it."""
    out = []
    for s in spans:
        if s.name == name:
            pad = (s.t1 - s.t0) * (1 - device_share) / 2
            out.append((s.t0 + pad + SHIFT, s.t1 - pad + SHIFT, program))
    return out


def live_ctx(lo, hi, record, modules, ops=None):
    tr = trace(modules=modules, ops=ops)
    tr["annotations"] = [("window", lo + SHIFT, hi + SHIFT)]
    return types.SimpleNamespace(notes={}, trace=tr,
                                 trace_host_window=(lo, hi), record=record)


def test_the_real_front_end_has_every_span_the_serve_metrics_read():
    import time

    from deepspeed_tpu import telemetry

    cfg = mf.load_json(DATA / "gpt2-tiny.json")
    system = systems.ServeSystem(cfg, {}, seed=3, chips=1)
    rec = Recorder()
    rng = np.random.default_rng(3)
    try:
        system.warm(24, 2 * system.tick_tokens)
        lo = time.monotonic()
        requests = [closed_loop.serve_one(system, {
            "caller": "A", "seq": i, "prompt_len": 24, "sentinel": False,
            "new_tokens": 3 * system.tick_tokens,
            "prompt": rng.integers(0, system.vocab, size=24, dtype=np.int32)},
            rec, time.monotonic) for i in range(3)]
    finally:
        system.close()
    hi = time.monotonic()
    assert all(closed_loop.request_ok(r) for r in requests)
    spans = [s for s in telemetry.get_tracer().snapshot() if s.t0 >= lo]
    waits = [s for s in spans if s.name == "tick_wait"]
    by_id = {s.id: s for s in spans}
    decode_waits = [w for w in waits if by_id[w.parent].name == "decode"]
    assert len(decode_waits) == 9
    ctx = live_ctx(lo, hi, {"t_start": lo, "t_end": hi, "requests": requests},
                   around(decode_waits, "tick_wait", "jit_decode_chunk(7)"))
    values = {name: read(name, ctx) for name in NEW if "train." not in name}
    assert all(v is not None for v in values.values()), values
    assert values["serve.door_compiles_in_window"] == 0.0
    # (the list is the process's: other tests' programs are in it too)
    assert {"serving/prefill", "serving/decode_chunk"} <= set(
        ctx.notes["door"]["during_setup"])
    for name in ("ttft.prefill_tick_p50_s", "ttft.first_chunk_p50_s",
                 "tpot.tick_launch_p50_s", "tpot.tick_return_p50_s",
                 "tpot.deliver_p50_s"):
        assert values[name] > 0, name
    # a tenth of each wait was made up as excess
    waits_p50 = sorted(w.dur for w in decode_waits)[4]
    assert 0 < values["tpot.tick_wait_excess_p50_s"] <= waits_p50
    assert ctx.notes["samples"]["tick_wait~decode_chunk"] == 9
    assert abs(ctx.notes["tick_residual_s"]) < 50e-6
    # on this CPU the "device" is 90 % of the wait by construction: the
    # identities are computed, whatever they come to
    assert "tick_unaccounted_s" in ctx.notes
    assert "ttft_unaccounted_s" in ctx.notes


def test_the_real_train_engine_has_every_span_the_train_metrics_read():
    import time

    from deepspeed_tpu import telemetry

    cfg = mf.load_json(DATA / "gpt2-tiny.json")
    traffic = mf.load_json(DATA / "traffic" / "train.tiny.json")
    system = systems.TrainSystem(cfg, traffic, 3, jax.device_count())
    rng = np.random.default_rng(3)
    batch = lambda: rng.integers(
        0, system.vocab, size=(system.global_batch, traffic["seq_len"]),
        dtype=np.int32)
    system.step(batch())                        # compiles: set-up
    lo = time.monotonic()
    for _ in range(3):
        system.step(batch())
    hi = time.monotonic()
    system.close()
    spans = [s for s in telemetry.get_tracer().snapshot()
             if s.t0 >= lo and s.cat == "train"]
    call = 'custom-call(%p), custom_call_target="tpu_custom_call"'
    ctx = live_ctx(lo, hi, {"t_start": lo, "t_end": hi},
                   around(spans, "train_batch", "jit_step_fn(5)"),
                   ops={f"%flash_fwd.1 = bf16[8,64,32] {call}": 0.03,
                        f"%flash_bwd_dq.1 = bf16[8,64,32] {call}": 0.06})
    values = {name: read(name, ctx) for name in NEW if "train." in name}
    assert all(v is not None for v in values.values()), values
    assert values["train.door_compiles_in_window"] == 0.0
    assert "engine/train_batch[gas=1]" in ctx.notes["door"]["during_setup"]
    assert ctx.notes["samples"]["data"] == 3
    assert ctx.notes["samples"]["train_batch/dispatch"] == 3
    assert ctx.notes["samples"]["train_batch/post_step"] == 3
    assert values["train.flash_fwd_s_per_step"] == pytest.approx(0.01)
    assert values["train.flash_bwd_s_per_step"] == pytest.approx(0.02)
