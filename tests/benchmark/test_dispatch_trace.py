"""``benchmark/dispatch_trace.py`` on contexts made by hand, with known
answers: the clock's bracket, the join of ``dispatch`` records to
executions, a gap's split among spans, and the five readers where there is
nothing to read. No engine is built here; a time from this file is
arithmetic and never a device metric."""

import types

import pytest

from benchmark import dispatch_trace as dt
from benchmark import manifest as mf
from benchmark import program_spans as ps
from benchmark import stats
from deepspeed_tpu.telemetry import StepTracer

FIVE = ["tpot.chunk_gap_device_p50_s", "tpot.chunks_behind_frac",
        "serve.request_edge_idle_p50_s", "ttft.prefill_dispatch_p50_s",
        "serve.idle_in_program_frac"]
LO, HI = 100.0, 104.0           # the traced window on the host clock
OFF = -95.0                     # host -> trace
# how far an annotation starts before its span's t0 / ends after its t1:
# most pairs bound the offset loosely, a few to under a microsecond
LEADS = (4e-4, 2e-5, 3e-6, 4e-7)
US = 1e-6
SPAWN, CALL, CALL_BEHIND = 100 * US, 300 * US, 200 * US
# a program starts START after its dispatch began and the host sees it
# ready EXCESS after it ended: equal, as the join's middle takes them to be
START, HOP, EXCESS = 60 * US, 40 * US, 60 * US
RETURN, DELIVER, CLOSE, STATUS = 100 * US, 500 * US, 200 * US, 150 * US
PROGRAM = {"prefill": "jit_prefill(11)",
           "decode_chunk": "jit_decode_chunk(12)"}


def read(name, ctx):
    spec, custom = mf.metric_spec("per_layer", name)
    assert custom is not None, name
    return custom(ctx, spec.get("params", {}))


class Case:
    """A serial server's timeline on the host clock: spans into a tracer,
    harness spans with their annotations, and the device as one queue that
    starts a program ``START`` after its dispatch began or ``HOP`` after the
    program before it ended, whichever is later."""

    def __init__(self, device_skew=0.0):
        self.tr = StepTracer(max_events=8192, ring=True)
        self.rec, self.ann, self.execs = [], [], []
        self.free, self.seq, self.skew = 0.0, 0, device_skew
        self.harness("window", LO, HI)

    def harness(self, name, t0, t1):
        lead = LEADS[len(self.rec) % len(LEADS)]
        lag = LEADS[-1 - len(self.rec) % len(LEADS)]
        self.rec.append((name, t0, t1, {}))
        self.ann.append((name, t0 + OFF - lead, t1 + OFF + lag))

    def dispatch(self, tick, rid, program, index, behind, t0, cost, dev):
        self.seq += 1
        start = max(t0 + START, self.free + HOP)
        self.free = start + dev
        self.execs.append((start, self.free, PROGRAM[program], self.seq))
        self.tr.record("dispatch", t0, t0 + cost, cat="serving", parent=tick,
                       request=rid, program=program, index=index,
                       behind=behind, seq=self.seq)
        return self.seq, t0 + cost

    def tick(self, req, rid, phase, index, t, own, dev, send_behind):
        """One tick from ``t``; ``own``: (seq, execution end) of the chunk
        the tick before sent behind its own, else None. -> (its end, what
        it sent behind or None)."""
        tick = self.tr.record(phase, t, t, cat="serving", parent=req,
                              request=rid, index=max(index - 1, 0))
        cur = t + SPAWN
        self.tr.record("worker_start", t, cur, cat="serving", parent=tick)
        if own is None:
            seq, cur = self.dispatch(
                tick, rid, "prefill" if phase == "prefill" else "decode_chunk",
                index, False, cur, CALL, dev[0])
            own = (seq, self.free)
        behind = None
        if send_behind:
            seq, cur = self.dispatch(tick, rid, "decode_chunk", index + 1,
                                     True, cur, CALL_BEHIND, dev[1])
            behind = (seq, self.free)
        ready = max(cur, own[1] + EXCESS)
        tick.t1 = ready + RETURN
        for name, t0, t1, args in (("tick_launch", t, cur, {}),
                                   ("tick_wait", cur, ready, {"seq": own[0]}),
                                   ("tick_return", ready, tick.t1, {})):
            self.tr.record(name, t0, t1, cat="serving", parent=tick, **args)
        self.harness("tick", t - 5 * US, tick.t1 + 5 * US)
        return tick.t1, behind

    def request(self, rid, t, chunks, mode="behind", prefill_dev=0.020,
                chunk_dev=0.050, dropped=False):
        """``request`` from ``t``: a prefill and ``chunks`` decode ticks,
        each chunk sent ``behind`` the program before it or (``serial``) by
        its own tick; ``dropped``: one more chunk is sent behind the last
        tick and never waited for. -> when the serve loop is free again."""
        req = self.tr.record("request", t, t, cat="serving", trace=rid,
                             request=rid)
        self.tr.record("admission_wait", t - 0.3, t, cat="serving",
                       parent=req, request=rid)
        ahead = mode == "behind"
        dev = (prefill_dev, chunk_dev)
        t, sent = self.tick(req, rid, "prefill", 0, t + 200 * US, None, dev,
                            ahead and (chunks > 0 or dropped))
        for k in range(1, chunks + 1):
            self.tr.record("deliver", t, t + DELIVER, cat="serving",
                           parent=req, request=rid)
            t, sent = self.tick(
                req, rid, "decode", k, t + DELIVER, sent,
                (chunk_dev, chunk_dev), ahead and (k < chunks or dropped))
        self.tr.record("deliver", t, t + DELIVER, cat="serving", parent=req,
                       request=rid)
        t += DELIVER
        self.tr.record("request_close", t, t + CLOSE, cat="serving",
                       parent=req, request=rid)
        req.t1 = t + CLOSE + 20 * US
        self.harness("request", req.t0 - 8 * US, req.t1 + 8 * US)
        self.tr.record("status_write", req.t1 + 10 * US,
                       req.t1 + 10 * US + STATUS, cat="serving", request=rid)
        return req.t1 + 10 * US + STATUS

    def idle(self, t0, t1):
        self.tr.record("queue_empty", t0, t1 - 5 * US, cat="serving")
        return t1

    def ctx(self, monkeypatch, extra_execs=()):
        monkeypatch.setattr(ps, "_live_tracer", lambda: self.tr)
        # the trace's window IS the harness's ``window`` annotation
        w0, w1 = next(((s, e) for nm, s, e in self.ann if nm == "window"),
                      (LO + OFF, HI + OFF))
        mods = sorted([(s + OFF + self.skew, e + OFF + self.skew, nm)
                       for s, e, nm, _ in self.execs] + list(extra_execs))
        inside = [m for m in mods if m[0] >= w0 and m[1] <= w1]
        busy = stats.union(stats.clip([(s, e) for s, e, _ in mods], w0, w1))
        trace = {"n_devices": 1, "window_s": w1 - w0, "module_events": inside,
                 "annotations": sorted(a for a in self.ann
                                       if a[1] >= w0 and a[2] <= w1),
                 "idle_gaps": {"in_tick": (w1 - w0) - stats.total(busy),
                               "inside_program": 0.0}}
        return types.SimpleNamespace(
            notes={}, trace=trace, trace_host_window=(LO, HI),
            rec=types.SimpleNamespace(spans=list(self.rec)),
            record={"t_start": LO, "t_end": LO + 30.0, "requests": []})


def steady(case, n=12, chunks=2, mode="behind", **kw):
    """Requests back to back from before the window to after it, a caller's
    turnaround of 0.7 ms between them."""
    t = LO - 0.2
    for i in range(n):
        t = case.request(f"req-{i}", t, chunks, mode, **kw)
        t = case.idle(t, t + 700 * US)
        if t > HI + 0.2:
            break
    return case


# ------------------------------------------------------------------ the clock
def test_the_bracket_closes_on_the_offset_to_the_microsecond(monkeypatch):
    ctx = steady(Case(), n=40).ctx(monkeypatch)
    # most pairs are 0.4 ms or 20 us loose on a side; the tightest decide
    assert dt.clock(ctx) == pytest.approx(OFF, abs=1 * US)
    assert 0 < ctx.notes["clock_bracket_s"] < 2 * US
    assert ctx.notes["clock_pairs"] > 60
    assert "dispatch_trace" not in ctx.notes


@pytest.mark.parametrize("how", ["empty", "wide", "no_window"])
def test_a_bracket_that_is_empty_or_wide_is_no_clock(monkeypatch, how):
    case = steady(Case(), n=40)
    if how == "empty":      # one pair says the clocks are 0.3 ms further apart
        i = len(case.ann) // 8 * 4 + 2      # a pair that is 3 us loose
        name, s, e = case.ann[i]
        case.ann[i] = (name, s + 3e-4, e + 3e-4)
    elif how == "wide":     # every pair is loose
        case.ann = [(n, s - 2e-4, e + 2e-4) for n, s, e in case.ann]
    else:
        case.ann = [a for a in case.ann if a[0] != "window"]
    ctx = case.ctx(monkeypatch)
    assert dt.clock(ctx) is None
    assert ctx.notes["dispatch_trace"].startswith("no clock")
    for name in ("tpot.chunk_gap_device_p50_s", "serve.idle_in_program_frac",
                 "serve.request_edge_idle_p50_s"):
        assert read(name, ctx) is None
    # the two that read the program's spans alone do not need it
    assert read("tpot.chunks_behind_frac", ctx) == 100.0
    assert read("ttft.prefill_dispatch_p50_s", ctx) == pytest.approx(
        SPAWN + CALL)


# ------------------------------------------------------------------- the join
def by_request(pairs):
    out = {}
    for p in pairs:
        a = p.dispatch.args
        out.setdefault(a["request"], []).append(
            (a["program"], a["index"], a["behind"]))
    return out


@pytest.mark.parametrize("skew", [0.0, -1e-4, -1.5e-3, 5e-4])
def test_a_request_back_to_back_is_joined_execution_by_execution(
        monkeypatch, skew):
    """... whatever the profiling session's shift of the device line: the
    bracket that causality leaves for it holds the shift that was made."""
    case = steady(Case(device_skew=skew), chunks=2)
    ctx = case.ctx(monkeypatch)
    pairs = dt.join(ctx)
    assert pairs is not None, ctx.notes
    lo, hi = ctx.notes["device_line_early_s"]
    assert lo <= -skew <= hi and hi - lo < 0.5e-3
    assert len(pairs) == len(ctx.trace["module_events"]) \
        == ctx.notes["dispatches_joined"]
    seqs = [p.dispatch.args["seq"] for p in pairs]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    truth = {seq: (s, e) for s, e, _, seq in case.execs}
    for p in pairs:
        s, e = truth[p.dispatch.args["seq"]]
        assert (p.start, p.end) == (s + OFF + skew, e + OFF + skew)
    whole = [v for v in by_request(pairs).values() if len(v) == 3]
    assert len(whole) >= 8
    assert all(v == [("prefill", 0, False), ("decode_chunk", 1, True),
                     ("decode_chunk", 2, True)] for v in whole)


def test_a_serial_request_and_a_dropped_chunk_are_joined(monkeypatch):
    case = Case()
    t = case.request("serial", LO + 0.1, 2, mode="serial")
    t = case.idle(t, t + 700 * US)
    t = case.request("dropper", t, 1, dropped=True)
    t = case.idle(t, t + 700 * US)
    case.request("after", t, 1)
    ctx = case.ctx(monkeypatch)
    got = by_request(dt.join(ctx))
    assert got["serial"] == [("prefill", 0, False), ("decode_chunk", 1, False),
                             ("decode_chunk", 2, False)]
    # the chunk sent behind the last tick ran and nobody waited for it
    assert got["dropper"] == [("prefill", 0, False), ("decode_chunk", 1, True),
                              ("decode_chunk", 2, True)]
    waited = {s.args["seq"] for s in case.tr.snapshot()
              if s.name == "tick_wait"}
    (lost,) = [p for p in dt.join(ctx)
               if p.dispatch.args["seq"] not in waited]
    assert lost.dispatch.args["request"] == "dropper" \
        and lost.dispatch.args["index"] == 2
    assert got["after"] == [("prefill", 0, False), ("decode_chunk", 1, True)]
    # 33 % of the decode chunks were sent by their own tick
    assert read("tpot.chunks_behind_frac", ctx) == pytest.approx(100 * 3 / 5)
    # the next request's prefill waits for the dropped chunk: its edge is
    # measured from that chunk's end
    assert read("serve.request_edge_idle_p50_s", ctx) is not None
    assert ctx.notes["samples"]["request_edge"] == 2


@pytest.mark.parametrize("fault", ["one_too_many", "kind", "missing"])
def test_a_disagreement_is_no_join_and_says_where(monkeypatch, fault):
    case = steady(Case())
    extra = []
    if fault == "one_too_many":     # an execution nobody dispatched
        s, e, nm, _ = case.execs[-1]
        extra = [(HI + OFF - 0.004, HI + OFF - 0.003, nm)]
    elif fault == "kind":
        i = next(i for i, x in enumerate(case.execs) if x[0] > LO + 1.0
                 and x[2] == PROGRAM["prefill"])
        s, e, _, seq = case.execs[i]
        case.execs[i] = (s, e, PROGRAM["decode_chunk"], seq)
    else:                           # a dispatch in mid-window never ran
        i = next(i for i, x in enumerate(case.execs) if x[0] > LO + 1.0)
        del case.execs[i]
    ctx = case.ctx(monkeypatch, extra)
    assert dt.join(ctx) is None
    assert ctx.notes["dispatch_trace"].startswith("no join: ")
    assert read("tpot.chunk_gap_device_p50_s", ctx) is None
    assert read("serve.idle_in_program_frac", ctx) is None
    assert "idle_identity" not in ctx.notes


# ---------------------------------------------------------- a gap's owners
def test_a_gap_is_split_by_seconds_of_overlap_the_deepest_first(monkeypatch):
    case = Case()
    for i in range(4):      # a clock: four harness spans
        case.harness("call", 100.1 + i, 100.2 + i)
    tr = case.tr
    req = tr.record("request", 101.0, 101.010, cat="serving", request="r")
    tr.record("admission_wait", 100.5, 101.0, cat="serving", parent=req)
    tick = tr.record("decode", 101.001, 101.004, cat="serving", parent=req)
    tr.record("dispatch", 101.0012, 101.0015, cat="serving", parent=tick,
              request="r", program="decode_chunk", index=1, behind=False,
              seq=1)
    tr.record("tick_launch", 101.001, 101.002, cat="serving", parent=tick)
    tr.record("deliver", 101.004, 101.006, cat="serving", parent=req)
    tr.record("queue_empty", 101.011, 101.012, cat="serving")
    case.execs = [(100.9, 101.0005, PROGRAM["decode_chunk"], 0),
                  (101.005, 101.0115, PROGRAM["decode_chunk"], 1),
                  (101.013, 101.02, PROGRAM["decode_chunk"], 2)]
    ctx = case.ctx(monkeypatch)
    # no join to make here: the device's line as the trace has it
    ctx._dispatch_join, ctx._dispatch_device_early = [], 0.0
    (a, b, first), (_, _, second) = dt.owners(ctx)
    assert (a, b) == (101.0005 + OFF, 101.005 + OFF)
    # 0.5 ms of the request's own before the tick, the launch less the
    # dispatch inside it, the tick's own after its launch, the deliver up to
    # the next execution; admission_wait owns nothing
    assert first == {
        "request": pytest.approx(0.0005), "tick_launch": pytest.approx(0.0007),
        "dispatch": pytest.approx(0.0003), "decode": pytest.approx(0.002),
        "deliver": pytest.approx(0.001)}
    assert sum(first.values()) == pytest.approx(b - a)
    # after the request and the execution: half of the empty queue's
    # millisecond, then no span until the next execution
    assert second == {"queue_empty": pytest.approx(0.0005),
                      dt.UNSPANNED: pytest.approx(0.001)}


# ----------------------------------------------------------------- the readers
@pytest.mark.parametrize("skew", [0.0, -1.5e-3])
def test_the_five_readers_on_a_loop_that_runs_back_to_back(monkeypatch, skew):
    """... the same whether or not the profiling session wrote the device's
    line 1.5 ms early: the join finds the shift and the split follows it."""
    ctx = steady(Case(device_skew=skew), n=40, chunks=3).ctx(monkeypatch)
    values = {name: read(name, ctx) for name in FIVE}
    assert None not in values.values(), ctx.notes
    # a chunk sent behind starts HOP after the program before it
    assert values["tpot.chunk_gap_device_p50_s"] == pytest.approx(HOP)
    assert ctx.notes["chunk_gap_behind_p50_s"] == pytest.approx(HOP)
    assert "chunk_gap_serial_p50_s" not in ctx.notes
    assert values["tpot.chunks_behind_frac"] == 100.0
    assert values["ttft.prefill_dispatch_p50_s"] == pytest.approx(SPAWN + CALL)
    assert ctx.notes["prefill_worker_start_p50_s"] == pytest.approx(SPAWN)
    assert ctx.notes["prefill_dispatch_call_p50_s"] == pytest.approx(CALL)
    # the last chunk's end -> the wait's excess, return, deliver, close, the
    # request's tail, the status write, the callers' 0.7 ms, the next
    # request's preamble, spawn, and START into the prefill's dispatch
    edge = EXCESS + RETURN + DELIVER + CLOSE + 20 * US + 10 * US + STATUS \
        + 700 * US + 200 * US + SPAWN + START
    assert values["serve.request_edge_idle_p50_s"] == pytest.approx(edge)
    by_span = ctx.notes["request_edge_by_span"]
    assert by_span["queue_empty"] == pytest.approx(695 * US)
    assert by_span["request_close"] == pytest.approx(CLOSE)
    assert by_span["status_write"] == pytest.approx(STATUS)
    assert by_span["worker_start"] == pytest.approx(SPAWN)
    assert by_span["dispatch"] == pytest.approx(START)
    assert by_span["tick_wait"] == pytest.approx(EXCESS)
    assert by_span[dt.UNSPANNED] == pytest.approx(15 * US)
    assert sum(by_span.values()) == pytest.approx(edge)
    n = ctx.notes["samples"]
    assert n["request_edge"] >= 20 and n["chunk_gap"] > 60
    # every gap between two executions is a chunk gap or a request edge
    identity = ctx.notes["idle_identity"]
    assert identity["other_gaps_s"] == pytest.approx(0.0, abs=1e-9)
    assert identity["chunk_gaps_s"] + identity["request_edges_s"] \
        + identity["window_edges_s"] == pytest.approx(
            identity["between_execution_s"])
    assert 0 <= identity["window_edges_s"] < 2 * edge
    spans = ctx.notes["idle_by_program_span"]
    assert dt.UNSPANNED not in spans and spans["queue_empty"] > 0
    total = sum(spans.values()) + ctx.notes["idle_unaccounted_s"]
    assert total == pytest.approx(identity["chunk_gaps_s"]
                                  + identity["request_edges_s"])
    window = ctx.trace["window_s"]
    idle_frac = 100 * identity["between_execution_s"] / window
    assert values["serve.idle_in_program_frac"] == pytest.approx(
        100 * (total - spans["queue_empty"]
               - ctx.notes["idle_unaccounted_s"]) / window)
    assert 0 < values["serve.idle_in_program_frac"] < idle_frac


def test_a_loop_gone_serial_shows_in_two_metrics(monkeypatch):
    ctx = steady(Case(), n=40, chunks=3, mode="serial").ctx(monkeypatch)
    assert read("tpot.chunks_behind_frac", ctx) == 0.0
    # the wait's excess, return, deliver, spawn, START into the dispatch
    gap = EXCESS + RETURN + DELIVER + SPAWN + START
    assert read("tpot.chunk_gap_device_p50_s", ctx) == pytest.approx(gap)
    assert ctx.notes["chunk_gap_serial_p50_s"] == pytest.approx(gap)
    assert "chunk_gap_behind_p50_s" not in ctx.notes


def test_a_chunk_gap_cannot_read_below_zero(monkeypatch):
    """Two executions that overlap in the trace (a device stamp that ran
    early) leave no interval between them: zero, not a negative time."""
    case = steady(Case(), n=40, chunks=3)
    for i, (s, e, nm, seq) in enumerate(case.execs):
        if nm == PROGRAM["decode_chunk"]:
            case.execs[i] = (s - 90 * US, e, nm, seq)
    ctx = case.ctx(monkeypatch)
    assert read("tpot.chunk_gap_device_p50_s", ctx) == 0.0
    assert ctx.notes["samples"]["chunk_gap"] > 60


@pytest.mark.parametrize("name", FIVE)
def test_nothing_to_read_reads_none(name, monkeypatch):
    """No device plane (the CPU rehearsal), the parent's program (spans, no
    ``dispatch``), a program with no recorder, a ring that wrapped past the
    window: None, and the notes as the standing readers leave them."""
    ctx = steady(Case()).ctx(monkeypatch)
    ctx.trace["n_devices"] = 0
    assert read(name, ctx) is None and ctx.notes == {}
    ctx.trace = None
    assert read(name, ctx) is None and ctx.notes == {}

    case = steady(Case())
    parent = StepTracer(max_events=8192, ring=True)
    for s in case.tr.snapshot():
        if s.name not in ("dispatch", "worker_start", "request_close",
                          "queue_empty"):
            parent.spans.append(s)
    ctx = case.ctx(monkeypatch)
    monkeypatch.setattr(ps, "_live_tracer", lambda: parent)
    assert read(name, ctx) is None and ctx.notes == {}

    class OldNoopTracer:
        events = []

    ctx = case.ctx(monkeypatch)
    monkeypatch.setattr(ps, "_live_tracer", OldNoopTracer)
    assert read(name, ctx) is None and ctx.notes == {}

    small = StepTracer(max_events=64, ring=True)
    for s in case.tr.snapshot():
        small.spans.append(s)
    assert small.wrapped
    ctx = case.ctx(monkeypatch)
    monkeypatch.setattr(ps, "_live_tracer", lambda: small)
    assert read(name, ctx) is None
    assert ctx.notes == {"program_spans": ps.WRAPPED}


def test_the_five_are_the_manifests_last_entries_with_their_cells():
    m = mf.load_manifest()
    cells = {e["name"]: e["workloads"] for e in m["end_to_end"]
             if "workloads" in e}
    last = m["per_layer"][-len(FIVE):]
    assert [e["name"] for e in last] == FIVE
    for e in last:
        spec = mf.load_json(mf.BENCH_DIR / "layer_metrics"
                            / f"{e['name']}.json")
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} \
            == {k: e[k] for k in ("unit", "better", "source", "layer",
                                  "moves")}
        assert e["workloads"] == cells[e["moves"]]
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_a_stretch_the_profiler_wrote_as_one_event_is_stepped_over(
        monkeypatch):
    """Seen on the chip in a 15 s window: ONE event of 3.84 s over 46 chunk
    executions. It starts as its dispatch's execution would and outlasts
    the waits of the dispatches after it: left out with them and said; the
    executions after it find their dispatches again, and no gap is
    measured across the hole."""
    case = steady(Case(), n=40, chunks=3)
    i = next(i for i, x in enumerate(case.execs) if x[0] > LO + 1.0
             and x[2] == PROGRAM["decode_chunk"])
    hidden = case.execs[i:i + 9]
    case.execs[i:i + 9] = [(hidden[0][0], hidden[-1][1], hidden[0][2],
                            hidden[0][3])]
    ctx = case.ctx(monkeypatch)
    pairs = dt.join(ctx)
    assert pairs is not None, ctx.notes
    assert ctx.notes["executions_merged"] == [
        pytest.approx(hidden[-1][1] - hidden[0][0])]
    seqs = [p.dispatch.args["seq"] for p in pairs]
    assert [(a, b) for a, b in zip(seqs, seqs[1:]) if b != a + 1] \
        == [(hidden[0][3] - 1, hidden[-1][3] + 1)]
    truth = {seq: s for s, _, _, seq in case.execs}
    assert all(p.start == truth[p.dispatch.args["seq"]] + OFF for p in pairs)
    whole = steady(Case(), n=40, chunks=3).ctx(monkeypatch)
    for name in ("tpot.chunk_gap_device_p50_s",
                 "serve.request_edge_idle_p50_s"):
        assert read(name, ctx) == pytest.approx(read(name, whole))
    # nine executions under one event: the ten gaps at and between them
    n, m = ctx.notes["samples"], whole.notes["samples"]
    assert n["request_edge"] + n["chunk_gap"] \
        == m["request_edge"] + m["chunk_gap"] - 10
    # ... of which the two beside the event are idle of neither kind
    assert ctx.notes["idle_identity"]["other_gaps_s"] == pytest.approx(
        2 * HOP)
