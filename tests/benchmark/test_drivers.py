"""The closed- and open-loop drivers against a fake serial server with
fixed service times: the medians the serve cells are judged by do not
depend on the seed, and equal the values worked out by hand."""

import statistics

import pytest

from benchmark import manifest as mf
from benchmark import readers
from benchmark.drivers import closed_loop, open_loop
from benchmark.recorder import Recorder
from tests.benchmark import fake_server as fs

SEEDS = range(10)
WINDOW_S = float(mf.load_manifest()["run_seconds"])


class Ctx:
    def __init__(self, record, rec):
        self.record, self.rec, self.notes = record, rec, {}
        self.chips, self.profiler_stop = 1, None


def run_closed(traffic_name, seed, **server_kw):
    traffic = mf.load_json(mf.traffic_path(traffic_name))
    clock = fs.VirtualClock()
    server = fs.FakeSerialServer(clock, n_callers=len(traffic["callers"]),
                                 **server_kw)
    rec = Recorder()
    try:
        record = closed_loop.run(server, traffic, seed, WINDOW_S, rec,
                                 clock=clock)
    finally:
        server.close()
    return Ctx(record, rec), traffic


def e2e(ctx):
    return {
        "ttft_p50_s": readers.request_percentile(ctx, {"field": "ttft", "q": 50}),
        "tpot_p50_s": readers.request_percentile(ctx, {"field": "tpot", "q": 50}),
        "serve_tok_s": readers.window_rate(ctx, {"of": "callbacks"}),
        "queue_wait_p50_s": readers.request_percentile(
            ctx, {"field": "queue_wait", "q": 50}),
    }


@pytest.fixture(scope="module")
def chat_runs():
    return [e2e(run_closed("serve.chat.c4", s)[0]) for s in SEEDS]


@pytest.fixture(scope="module")
def doc_runs():
    return [e2e(run_closed("serve.doc.c1", s)[0]) for s in SEEDS]


def chat_analytic():
    """Round-robin A,B,C,D: a request of B waits for C's and D's previous
    and A's current service, then its own prefill and first tick; C's
    likewise. Prompt lengths alternate in step, so each has two values."""
    traffic = mf.load_json(mf.traffic_path("serve.chat.c4"))
    c = {x["name"]: x for x in traffic["callers"]}
    S = lambda n, k: fs.service_s(c[n]["prompt_lens"][k % 2], c[n]["new_tokens"][0])
    P = lambda n, k: fs.prefill_s(c[n]["prompt_lens"][k % 2])
    vals = []
    for k in (0, 1):
        vals.append(S("C", k - 1) + S("D", k - 1) + S("A", k) + P("B", k) + fs.TICK_S)
        vals.append(S("D", k - 1) + S("A", k) + S("B", k) + P("C", k) + fs.TICK_S)
    cycle = sum(S(n, k) for n in "ABCD" for k in (0, 1)) / 2
    tokens = sum(c[n]["new_tokens"][0] for n in "ABCD")
    return vals, tokens / cycle


@pytest.mark.parametrize("metric", ["ttft_p50_s", "tpot_p50_s", "serve_tok_s"])
def test_chat_c4_is_steady_across_ten_seeds(chat_runs, metric):
    vals = [r[metric] for r in chat_runs]
    assert (max(vals) - min(vals)) / statistics.median(vals) < 0.01, vals


def test_chat_c4_matches_the_round_robin_worked_out_by_hand(chat_runs):
    ttft_values, tok_s = chat_analytic()
    for r in chat_runs:
        assert min(ttft_values) - 1e-9 <= r["ttft_p50_s"] <= max(ttft_values) + 1e-9
        assert r["ttft_p50_s"] == pytest.approx(2.8, rel=0.02)   # ISSUE 23
        assert r["tpot_p50_s"] == pytest.approx(fs.TICK_S / 16, rel=1e-9)
        assert r["serve_tok_s"] == pytest.approx(tok_s, rel=5e-3)
        # the queue is three deep always: wait is nearly all of TTFT
        assert r["queue_wait_p50_s"] / r["ttft_p50_s"] > 0.9


@pytest.mark.parametrize("mix", ["serve.chat.c4", "serve.doc.c1"])
def test_a_run_on_the_virtual_clock_repeats_to_the_last_digit(mix):
    """Nothing in a record depends on which thread the machine ran first:
    the first round is served in the callers' order, and the window opens
    at the instant the last warm-up request resolved."""
    a, b = (e2e(run_closed(mix, 4)[0]) for _ in range(2))
    assert a == b


@pytest.mark.parametrize("metric", ["ttft_p50_s", "tpot_p50_s", "serve_tok_s"])
def test_doc_c1_is_steady_across_ten_seeds(doc_runs, metric):
    vals = [r[metric] for r in doc_runs]
    assert (max(vals) - min(vals)) / statistics.median(vals) < 0.01, vals


def test_doc_c1_median_ttft_is_the_768_token_group(doc_runs):
    for r in doc_runs:
        assert r["ttft_p50_s"] == pytest.approx(
            fs.prefill_s(768) + fs.TICK_S, rel=1e-9)
        assert r["queue_wait_p50_s"] == pytest.approx(0.0, abs=1e-9)
        assert r["tpot_p50_s"] == pytest.approx(fs.TICK_S / 16, rel=1e-9)


def test_doc_c1_window_holds_over_a_hundred_requests():
    """~0.25 s a request: a 30 s window holds ~120, so the 768-token group
    has ~24 TTFT samples and ``ttft_p90_s`` a dozen beyond it."""
    ctx, _ = run_closed("serve.doc.c1", 3)
    r = ctx.record
    assert r["correct"] and r["failed"] == 0
    assert 3.5 * WINDOW_S <= r["attempted"] <= 4.5 * WINDOW_S
    done = [q for q in r["requests"] if r["t_start"] <= q["t_done"] <= r["t_end"]]
    # the 16-token requests end at their first callback: TTFT but no TPOT
    one_tick = [q for q in done if q["new_tokens"] == 16]
    assert one_tick and all(len(q["stamps"]) == 1 for q in one_tick)
    assert ctx.notes == {} and readers.request_percentile(
        ctx, {"field": "tpot", "q": 50}) is not None
    assert ctx.notes["samples"]["tpot"] == len(done) - len(one_tick)


def test_attempted_and_failed_count_refusals():
    ctx, _ = run_closed("serve.doc.c1", 1, refuse_every=10)
    r = ctx.record
    assert r["failed"] > 0 and not r["correct"]
    refused = [q for q in r["requests"] if q["status"].startswith("refused")
               and r["t_start"] <= q["t_submit"] <= r["t_end"]]
    assert r["failed"] == len(refused)
    assert r["attempted"] > r["failed"]


def test_a_sentinel_that_returns_other_tokens_is_not_correct():
    ok, _ = run_closed("serve.chat.c4", 1)
    assert ok.record["correct"] and ok.record["notes"]["sentinels_compared"] > 8
    bad, _ = run_closed("serve.chat.c4", 1, flaky_sentinel=True)
    assert bad.record["failed"] == 0 and not bad.record["correct"]


def test_turnaround_is_completion_to_that_callers_next_submit():
    ctx, _ = run_closed("serve.chat.c4", 2)
    # on the virtual clock a caller resubmits at the instant it was answered
    assert readers.request_percentile(
        ctx, {"field": "turnaround", "q": 99}) == pytest.approx(0.0, abs=1e-9)


# ------------------------------------------------------------- open loop
OPEN = {"driver": "open_loop",
        "arrivals": {"process": "burst", "rate_per_s": 0.0,
                     "burst_every_s": 0.2, "burst_size": 4},
        "shapes": [[8, 16, 1]]}


def test_open_loop_times_from_when_a_request_was_due(monkeypatch):
    """Four requests fall due at once on a serial server: the k-th is
    answered k services after it was DUE, though it was submitted later,
    and how late the generator ran is in every record."""
    monkeypatch.setattr(fs, "TICK_S", 0.02)
    clock = fs.RealClock()
    server = fs.FakeSerialServer(clock)
    rec = Recorder()
    try:
        record = open_loop.run(server, OPEN, 5, 0.5, rec, clock=clock)
    finally:
        server.close()
    assert record["correct"] and record["failed"] == 0
    assert record["attempted"] == 8          # bursts at 0.2 s and 0.4 s
    service = fs.prefill_s(8) + 0.02
    first_burst = sorted(
        (q for q in record["requests"] if q["t_ref"] - record["t_start"] < 0.3),
        key=lambda q: q["stamps"][0][0])
    assert len(first_burst) == 4
    for k, q in enumerate(first_burst, start=1):
        assert q["t_ref"] == pytest.approx(record["t_start"] + 0.2, abs=1e-9)
        assert q["t_submit"] >= q["t_ref"] and q["late_s"] >= 0.0
        assert q["stamps"][0][0] - q["t_ref"] == pytest.approx(
            k * service, abs=0.6 * service)
    ctx = Ctx(record, rec)
    late = readers.request_percentile(ctx, {"field": "late", "q": 99})
    assert late is not None and 0.0 <= late < 0.05
    ttft = readers.request_percentile(ctx, {"field": "ttft", "q": 50})
    assert ttft == pytest.approx(2.5 * service, abs=service)
