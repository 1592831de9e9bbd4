"""The metric readers on records made by hand."""

import pytest

from benchmark import families, readers
from benchmark import manifest as mf
from benchmark.recorder import Recorder


class Ctx:
    def __init__(self, **kw):
        self.notes = {}
        self.trace = None
        self.__dict__.update(kw)


def step(t0, t1, tokens=8192, loss=5.0):
    return {"t0": t0, "t1": t1, "tokens": tokens, "loss": loss}


TRAIN = {"of": "steps", "per_chip": True}
SERVE = {"of": "callbacks"}


def train_ctx(ends, t_end=100.0, chips=1, **kw):
    return Ctx(record={"t_start": 0.0, "t_end": t_end,
                       "steps": [step(0, e) for e in ends]},
               chips=chips, profiler_stop=None, **kw)


def test_train_rate_counts_whole_steps_that_end_inside_the_window():
    steps = [step(10.0 + 0.4 * i, 10.4 + 0.4 * i) for i in range(6)]
    # window 10.0-12.1: steps ending 10.4 .. 12.0 are inside (5 of them);
    # the sixth ends at 12.4 and is drained outside the timing
    ctx = Ctx(record={"t_start": 10.0, "t_end": 12.1, "steps": steps}, chips=4)
    assert readers.window_rate(ctx, TRAIN) == pytest.approx(8192 / 0.4 / 4)
    # moving the nominal end inside the same step changes nothing: the
    # quotient's time runs to the end of the last whole step
    ctx.record["t_end"] = 12.39
    assert readers.window_rate(ctx, TRAIN) == pytest.approx(8192 / 0.4 / 4)
    ctx.record["t_end"] = 10.2
    assert readers.window_rate(ctx, TRAIN) is None


def test_the_judged_rate_is_one_quotient_and_a_stall_is_in_it():
    """ISSUE 23's definition: tokens of the window / its time. One 2 s stall
    in 16 s of steps costs the judged rate its 11%; the diagnostics beside
    it say it was a stall (the steady rate is unmoved, the longest interval
    is 2.4 s) and not a slower program."""
    ends = [0.4 * (i + 1) for i in range(40)]
    stalled = [t + (2.0 if i >= 17 else 0.0) for i, t in enumerate(ends)]
    assert readers.window_rate(train_ctx(ends), TRAIN) == pytest.approx(20480)
    ctx = train_ctx(stalled)
    assert readers.window_rate(ctx, TRAIN) == pytest.approx(40 * 8192 / 18.0)
    assert readers.steady_rate(ctx, TRAIN) == pytest.approx(20480)
    assert readers.longest_interval(ctx, TRAIN) == pytest.approx(2.4)


def test_a_slowdown_that_recurs_moves_both_rates():
    """Every fifth step 0.2 s slower: each run of five holds one, so the
    grouped median drops by the same 10% the judged rate does."""
    t, ends = 0.0, []
    for i in range(40):
        t += 0.6 if i % 5 == 4 else 0.4
        ends.append(t)
    ctx = train_ctx(ends)
    assert readers.window_rate(ctx, TRAIN) == pytest.approx(5 * 8192 / 2.2)
    assert readers.steady_rate(ctx, TRAIN) == pytest.approx(5 * 8192 / 2.2)


def test_diagnostics_leave_out_the_profilers_own_stop():
    """A traced train run: the step loop waits 3 s for the profiler to stop.
    The judged rate is not read in such a run; the diagnostics leave out the
    interval that overlaps the stop and keep the rest of the window."""
    ends = [0.4 * (i + 1) for i in range(10)] \
        + [4.0 + 3.0 + 0.4 * (i + 1) for i in range(20)]   # stop_trace: 3 s
    ctx = train_ctx(ends)
    assert readers.longest_interval(ctx, TRAIN) == pytest.approx(3.4)
    ctx.profiler_stop = (4.1, 7.1)
    assert readers.longest_interval(ctx, TRAIN) == pytest.approx(0.4)
    assert readers.steady_rate(ctx, TRAIN) == pytest.approx(20480)
    # a stop that outlasts the window (a serve cell's takes a minute) leaves
    # what came before it; one that covers everything leaves nothing
    ctx.profiler_stop = (4.1, 1e9)
    assert len(readers._intervals(ctx, "steps", skip=ctx.profiler_stop)[0]) == 10
    ctx.profiler_stop = (0.0, 1e9)
    assert readers.longest_interval(ctx, TRAIN) is None
    assert readers.steady_rate(ctx, TRAIN) is None
    with pytest.raises(ValueError):
        readers.window_rate(ctx, {"of": "nope"})


@pytest.mark.parametrize("n,groups,size", [(125, 8, 15), (17, 8, 2), (5, 2, 2),
                                           (3, 1, 3), (1, 1, 1)])
def test_groups_are_equal_runs_of_consecutive_intervals(n, groups, size):
    # interval i takes i+1 seconds: the rate of a run shows which it was
    work, seconds = [1.0] * n, [float(i + 1) for i in range(n)]
    rate = readers.grouped_median_rate(work, seconds)
    runs = [size / sum(seconds[g * size:(g + 1) * size]) for g in range(groups)]
    assert rate == pytest.approx(sorted(runs)[len(runs) // 2] if groups % 2
                                 else sum(sorted(runs)[groups // 2 - 1:groups // 2 + 1]) / 2)


def req(caller, t_submit, stamps, t_done, new, status="completed", wait=0.0):
    return {"caller": caller, "seq": 0, "prompt_len": 8, "new_tokens": new,
            "sentinel": False, "t_submit": t_submit, "t_ref": t_submit,
            "stamps": stamps, "t_done": t_done, "status": status,
            "n_tokens": sum(n for _, n in stamps), "queue_wait": wait,
            "tokens": None}


@pytest.fixture
def serve_ctx():
    requests = [
        req("a", 0.5, [(1.0, 16), (1.1, 16)], 1.1, 32),           # before window
        req("a", 1.2, [(2.0, 16), (2.2, 16), (2.4, 16)], 2.4, 48, wait=0.3),
        req("b", 2.0, [(3.0, 16)], 3.0, 16, wait=0.9),            # one tick
        req("a", 2.5, [(3.5, 16), (3.6, 16)], 3.6, 32, wait=0.5),
        req("b", 3.1, [(4.0, 16)], 4.0, 32, status="partial"),    # failed
        req("a", 3.7, [(9.0, 16), (9.1, 16)], 9.1, 32),           # after window
    ]
    return Ctx(record={"t_start": 1.5, "t_end": 5.0, "requests": requests},
               rec=Recorder(), chips=1, profiler_stop=None)


def test_latency_samples_are_requests_completed_in_the_window(serve_ctx):
    p = lambda field, q=50: readers.request_percentile(
        serve_ctx, {"field": field, "q": q})
    assert p("ttft") == pytest.approx(1.0)        # 0.8, 1.0, 1.0
    assert serve_ctx.notes["samples"]["ttft"] == 3
    # (2.4-2.0)/32 and (3.6-3.5)/16; the one-tick request gives no sample
    assert p("tpot") == pytest.approx((0.0125 + 0.00625) / 2)
    assert serve_ctx.notes["samples"]["tpot"] == 2
    assert p("queue_wait") == pytest.approx(0.5)
    assert p("late") is None


def test_serve_rate_counts_tokens_delivered_in_the_window(serve_ctx):
    # callbacks inside 1.5-5.0: 2.0 2.2 2.4 3.0 3.5 3.6 4.0; the first opens
    # the first interval; six intervals of 16 tokens in 2.0 s
    assert readers.window_rate(serve_ctx, SERVE) == pytest.approx(6 * 16 / 2.0)
    # the diagnostics: three runs of two, 32/0.4, 32/1.1, 32/0.5 tokens/s
    assert readers.steady_rate(serve_ctx, SERVE) == pytest.approx(64.0)
    assert readers.longest_interval(serve_ctx, SERVE) == pytest.approx(0.6)


def test_turnaround_is_per_caller(serve_ctx):
    # a: 1.1->1.2 (submit before the window), 2.4->2.5, 3.6->3.7; b: 3.0->3.1
    vals = readers._request_values(serve_ctx, "turnaround")
    assert sorted(round(v, 6) for v in vals) == [0.1, 0.1, 0.1]
    with pytest.raises(ValueError):
        readers._request_values(serve_ctx, "nope")


def test_span_percentile_and_counters():
    rec = Recorder()
    rec.spans = [("train_step", 1.0, 1.4, {}), ("train_step", 1.5, 1.8, {}),
                 ("train_step", 0.1, 0.9, {}), ("feed_batch", 1.4, 1.5, {})]
    ctx = Ctx(record={"t_start": 1.0, "t_end": 2.0}, rec=rec)
    assert readers.span_percentile(ctx, {"span": "train_step", "q": 50}) \
        == pytest.approx(0.35)

    class Compiles:
        def count(self, lo, hi):
            return 2 if (lo, hi) == (1.0, 2.0) else 0

    ctx.compiles = Compiles()
    assert readers.compiles_in_window(ctx, {}) == 2.0


def test_device_readers_return_nothing_without_a_trace_or_peaks():
    ctx = Ctx(record={"t_start": 0, "t_end": 1, "steps": []}, rec=Recorder(),
              peaks=None, memory_peak_bytes=0, trace_host_window=None,
              profiler_stop=None, chips=1)
    for name in ("device_idle_frac", "coll_exposed_frac", "flash_roofline",
                 "decode_roofline", "tick_host_gap", "hbm_peak_frac",
                 "train_mfu", "window_rate", "steady_rate", "longest_interval"):
        assert getattr(readers, name)(ctx, {
            "match": "x", "step_match": "x", "span": "tick", "q": 50,
            "of": "steps"}) is None
    assert readers.module_device_percentile(ctx, {"match": "x", "q": 50}) is None


def test_trace_readers_on_a_reduced_trace():
    peaks = mf.load_json(mf.BENCH_DIR / "peaks.json")["TPU v5 lite"]
    M = mf.load_manifest()
    xl = mf.load_json(mf.config_path(M, "gpt2-xl"))
    rec = Recorder()
    rec.spans = [("tick", 1.0, 1.11, {"phase": "decode", "context": 100}),
                 ("tick", 1.2, 1.31, {"phase": "decode", "context": 300}),
                 ("tick", 0.9, 0.95, {"phase": "prefill", "context": 64})]
    trace = {"modules": {"jit_decode_chunk(1)": [0.1072, 0.1072],
                         "jit_prefill(2)": [0.02]},
             "module_events": [(10.0, 10.02, "jit_prefill(2)"),
                               (10.051, 10.1582, "jit_decode_chunk(1)"),
                               (10.201, 10.3082, "jit_decode_chunk(1)")],
             "annotations": [("tick", 9.99, 10.03), ("tick", 10.05, 10.16),
                             ("tick", 10.2, 10.312)],
             "idle_frac": 0.04, "window_s": 2.0, "collective_exposed_s": 0.5,
             "op_seconds": {}}
    # the tick size is what the stream callbacks delivered, not a constant
    requests = [req("a", 0.5, [(1.11, 16), (1.31, 16), (1.4, 7)], 1.4, 39)]
    ctx = Ctx(record={"t_start": 0, "t_end": 5, "requests": requests},
              rec=rec, peaks=peaks, config=xl, family=families.get("gpt2"),
              traffic={}, trace=trace, trace_host_window=(0.99, 1.4))
    assert readers.module_device_percentile(
        ctx, {"match": "decode_chunk", "q": 50}) == pytest.approx(0.1072)
    assert readers.tick_host_gap(
        ctx, {"span": "tick", "match": "decode_chunk", "q": 50}) \
        == pytest.approx(((0.11 - 0.1072) + (0.112 - 0.1072)) / 2)
    assert readers.device_idle_frac(ctx, {}) == pytest.approx(4.0)
    assert readers.coll_exposed_frac(ctx, {}) == pytest.approx(25.0)
    # contexts 100 and 300 -> tokens at 207.5 on average; PR 22 read 58.3%
    # at a mean context near 270
    got = readers.decode_roofline(ctx, {"match": "decode_chunk"})
    assert ctx.notes["decode_context_mean"] == pytest.approx(207.5)
    assert ctx.notes["decode_roofline_bound"] == "memory"
    assert got == pytest.approx(57.9, abs=0.2)


def test_a_family_supplies_the_counts_and_one_without_them_reads_nothing():
    """The readers know no architecture: MFU and the rooflines take their
    operations and bytes from the configuration's family module, and a
    family that lacks a function leaves that metric out of the line."""
    import types

    peaks = mf.load_json(mf.BENCH_DIR / "peaks.json")["TPU v5 lite"]
    other = types.SimpleNamespace(
        train_flops_per_token=lambda cfg, seq: cfg["flops"] * seq)
    ctx = train_ctx([0.5 * (i + 1) for i in range(8)], peaks=peaks,
                    family=other, config={"flops": 1e6},
                    traffic={"seq_len": 1000, "engine": {"micro_batch_per_chip": 8}},
                    trace={"op_text_seconds": {}, "modules": {}})
    assert readers.train_mfu(ctx, {}) == pytest.approx(
        100 * 1e9 * (8192 / 0.5) / 197e12)
    assert readers.flash_roofline(ctx, {"match": "x", "step_match": "x"}) is None
    ctx.family = types.SimpleNamespace()
    assert readers.train_mfu(ctx, {}) is None
    with pytest.raises(SystemExit):
        families.get("no_such_family")
    with pytest.raises(SystemExit):
        families.get("../run")
