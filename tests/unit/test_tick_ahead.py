"""A decode chunk is dispatched before the chunk ahead of it has been waited
on (``ServingFrontEnd._chunk_behind``): a request that cannot end early runs
its ticks back to back on the device, and everything the front-end
guarantees holds as it held tick by tick. The serial loop these tests
compare with is the SAME loop with nothing ahead: a front-end whose
``_chunk_behind`` answers None."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

CHUNK = 4
GREEDY = dict(do_sample=False)
SAMPLED = dict(do_sample=True, temperature=0.8, top_k=12, top_p=0.95, seed=7)

MODELS = {
    # a token a step
    "token": lambda: GPT2Model(GPT2Config(
        vocab_size=256, n_positions=128, n_embd=64, n_layer=2, n_head=4)),
    # a block of 4 a step, routed experts of which a share is held: the
    # cache carries ``block_passes`` and ``expert_tokens`` from tick to tick
    "block": lambda: LlamaModel(LlamaConfig(
        vocab_size=128, n_positions=128, n_embd=48, n_layer=2, n_head=4,
        n_kv_head=2, head_dim=16, intermediate_size=24, qk_norm="head",
        n_experts=16, n_experts_per_tok=4, norm_topk_prob=True,
        experts_held=(8, 4), rope_theta=1e6, rms_norm_eps=1e-6,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        use_flash_attention=False, block_length=4, denoising_steps=2,
        remasking="low_confidence_static")),
}


@pytest.fixture(scope="module", params=list(MODELS))
def served(request):
    """(kind, engine, the programs and warm counts its front-ends share):
    one compile a sampling for the module."""
    engine = InferenceEngine(
        MODELS[request.param](),
        DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=64))
    return request.param, engine, {}, {}


@pytest.fixture(autouse=True)
def _chaos_clean():
    yield
    from deepspeed_tpu.resilience import chaos

    chaos.uninstall_chaos()


def _frontend(served, serial=False, warm=None, **serving):
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.serving import ServingFrontEnd

    _, engine, programs, shared_warm = served
    serving.setdefault("decode_tick_tokens", CHUNK)
    serving.setdefault("max_queue_depth", 8)
    fe = ServingFrontEnd(engine, DeepSpeedConfig({"serving": serving}).serving,
                         start=False)
    fe._programs = programs
    fe._warm = shared_warm if warm is None else warm
    if serial:
        fe._chunk_behind = lambda req, phase, out: None
    return fe.start()


def _prompt(n=8):
    return ((np.arange(n) * 5)[None, :] % 100).astype(np.int32)


def _warm_up(served, **how):
    """Both decode specializations of the sampling have run."""
    fe = _frontend(served, serial=True)
    try:
        r = fe.submit(_prompt(), max_new_tokens=1 + 3 * CHUNK, **how)
        assert r.result(timeout=600).status == "completed", r.reason
    finally:
        fe.close()


def _serve(served, n, serial=False, stream=None, **how):
    fe = _frontend(served, serial=serial)
    try:
        r = fe.submit(_prompt(), max_new_tokens=n, stream=stream, **how)
        r.result(timeout=600)
    finally:
        fe.close()
    return r, fe


def _spans_of(req, name):
    from deepspeed_tpu import telemetry

    return sorted((s for s in telemetry.get_tracer().snapshot()
                   if s.name == name and s.args.get("request") == req.id),
                  key=lambda s: s.t0)


def _first(kind):
    """Tokens the prefill tick delivers: one, or the first block's new ones
    (the prompt of 8 is two whole blocks)."""
    return 1 if kind == "token" else 4


# ----------------------------------------------------------------- the tokens
@pytest.mark.serving
@pytest.mark.parametrize("n", [1 + CHUNK, 2 + 3 * CHUNK, 7 * CHUNK])
@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
def test_the_tokens_are_the_serial_loops(served, sampling, n):
    """Same programs, same arguments in the same order: the request's
    tokens, its callbacks and what the programs counted in the cache are
    the serial loop's; every decode chunk of a warm request went ahead (the
    first one behind the prefill) and none was dropped."""
    kind = served[0]
    how = GREEDY if sampling == "greedy" else SAMPLED
    _warm_up(served, **how)
    calls, serial_calls = [], []
    req, fe = _serve(served, n, stream=calls.append, **how)
    ref, serial = _serve(served, n, serial=True, stream=serial_calls.append,
                         **how)
    assert req.status == ref.status == "completed"
    assert req.tokens == ref.tokens and len(req.tokens) == n
    assert calls == serial_calls
    ticks = -(-(n - _first(kind)) // CHUNK)
    assert req.decode_ticks == ref.decode_ticks == ticks
    assert (fe.counts["ticks_ahead"], fe.counts["ticks_serial"],
            fe.counts["ticks_dropped"]) == (ticks, 0, 0)
    assert (serial.counts["ticks_ahead"], serial.counts["ticks_serial"],
            serial.counts["ticks_dropped"]) == (0, ticks, 0)
    assert [s.args["ahead"] for s in _spans_of(req, "decode")] == [True] * ticks
    assert [s.args["ahead"] for s in _spans_of(ref, "decode")] == [False] * ticks
    assert "ahead" not in _spans_of(req, "prefill")[0].args
    assert fe._ahead is None
    # counted from the last DELIVERED chunk's cache
    assert req.block_passes == ref.block_passes
    assert (req.block_passes is None) == (kind == "token")
    for name in ("passes", "carried", "blocks"):
        assert fe.counts[name] == serial.counts[name]
    routed, routed_ref = (_spans_of(r, "moe/expert_tokens") for r in (req, ref))
    assert len(routed) == len(routed_ref) == (kind == "block")
    for a, b in zip(routed, routed_ref):
        assert a.args["counts"] == b.args["counts"]
        assert a.args["routed_pairs"] == b.args["routed_pairs"]
    (span,), (span_ref,) = (_spans_of(r, "request") for r in (req, ref))
    assert span.args["cache_positions"] == span_ref.args["cache_positions"]
    assert span.args["decode_ticks"] == ticks


# ------------------------------------------------------ the dispatch records
def _counted(served):
    """The served programs behind wrappers that note every call, in call
    order; -> (the calls, what puts the programs back)."""
    programs, calls, kept = served[2], [], dict(served[2])
    note = lambda name, f: lambda *a: (calls.append(name), f(*a))[1]
    for key, (prefill, chunk) in kept.items():
        programs[key] = (note("prefill", prefill), note("decode_chunk", chunk))
    return calls, lambda: programs.update(kept)


def _dispatches(req):
    return sorted(_spans_of(req, "dispatch"), key=lambda s: s.args["seq"])


def _waited(req):
    """{seq a ``tick_wait`` of the request names: (program, index) its tick
    waits for}."""
    ticks = {s.id: s for name in ("prefill", "decode")
             for s in _spans_of(req, name)}
    return {w.args["seq"]: ("prefill", 0) if ticks[w.parent].name == "prefill"
            else ("decode_chunk", ticks[w.parent].args["index"] + 1)
            for w in _spans_of(req, "tick_wait")}


@pytest.mark.serving
@pytest.mark.parametrize("serial", [False, True])
def test_every_program_call_is_one_dispatch_record(served, serial):
    """One ``dispatch`` a call of a served program, ``seq`` rising in call
    order, ``behind`` exactly for the calls ``_chunk_behind`` makes, and a
    ``tick_wait`` names the dispatch of ITS request and index; the tokens
    are the serial loop's."""
    _warm_up(served, **GREEDY)
    n = 2 + 3 * CHUNK
    ticks = -(-(n - _first(served[0])) // CHUNK)
    ref, _ = _serve(served, n, serial=True, **GREEDY)
    calls, restore = _counted(served)
    try:
        req, fe = _serve(served, n, serial=serial, **GREEDY)
    finally:
        restore()
    assert req.tokens == ref.tokens
    sent = _dispatches(req)
    assert [s.args["program"] for s in sent] == calls \
        == ["prefill"] + ["decode_chunk"] * ticks
    seqs = [s.args["seq"] for s in sent]
    assert all(a < b for a, b in zip(seqs, seqs[1:]))
    assert [s.t0 for s in sent] == sorted(s.t0 for s in sent)
    assert [s.args["index"] for s in sent] == list(range(ticks + 1))
    assert [s.args["behind"] for s in sent] \
        == [False] + [not serial] * ticks
    assert all(s.trace == req.id and s.t0 <= s.t1 for s in sent)
    # every dispatch was waited for, by the tick of its own index
    by_seq = {s.args["seq"]: (s.args["program"], s.args["index"])
              for s in sent}
    assert _waited(req) == by_seq
    by_id = {s.id: s for name in ("prefill", "decode")
             for s in _spans_of(req, name)}
    for s in sent:
        tick = by_id[s.parent]
        # its own tick's, or (behind) the tick's before it
        assert s.args["index"] - bool(s.args["behind"]) == (
            0 if tick.name == "prefill" else tick.args["index"] + 1)
        assert tick.t0 <= s.t0 and s.t1 <= tick.t1
    assert fe.counts["ticks_dropped"] == 0


@pytest.mark.serving
def test_the_loops_other_records(served):
    """``worker_start`` opens every tick, ``request_close`` runs from the
    last delivery to the resolution, and a server that waits for work writes
    ONE ``queue_empty`` a wait, however many polls it took."""
    from deepspeed_tpu import telemetry

    _warm_up(served, **GREEDY)
    fe = _frontend(served)
    t_start = time.monotonic()
    try:
        time.sleep(6 * fe.WORKER_POLL_S)
        reqs = [fe.submit(_prompt(), max_new_tokens=1 + CHUNK, **GREEDY)
                for _ in range(2)]
        for r in reqs:
            assert r.result(timeout=600).status == "completed"
    finally:
        fe.close()
    for r in reqs:
        ticks = _spans_of(r, "prefill") + _spans_of(r, "decode")
        starts = _spans_of(r, "worker_start")
        assert [s.parent for s in starts] == [t.id for t in ticks]
        for tick, start, wait in zip(ticks, starts, _spans_of(r, "tick_wait")):
            assert start.t0 == tick.t0 and start.t1 <= wait.t0
        (close,) = _spans_of(r, "request_close")
        (span,) = _spans_of(r, "request")
        assert close.parent == span.id
        assert close.t0 == _spans_of(r, "deliver")[-1].t1
        assert r.finished_at <= close.t1 <= span.t1
    empty = [s for s in telemetry.get_tracer().snapshot()
             if s.name == "queue_empty" and s.t0 >= t_start]
    # the wait before the first request; the second was queued behind it
    assert len(empty) == 1 and empty[0].parent is None
    assert empty[0].dur >= 5 * fe.WORKER_POLL_S
    assert empty[0].t1 <= _spans_of(reqs[0], "request")[0].t0


# ------------------------------------------------------- when nothing is ahead
@pytest.mark.serving
@pytest.mark.parametrize("why", ["eos", "cold", "owed_nothing"])
def test_nothing_is_dispatched_ahead(served, why):
    """The loop is tick by tick for a request that may end early (an EOS
    id), while a specialization has not run (a compile must not sit inside
    another tick's deadline) and when the tick in flight delivers the last
    token owed."""
    kind = served[0]
    _warm_up(served, **GREEDY)
    kw, warm, n = {}, None, 1 + 2 * CHUNK
    if why == "eos":
        kw = {"eos_token_id": 127}          # its own program: warm it too
        _warm_up(served, **GREEDY, **kw)
    elif why == "cold":
        warm = {}       # two decode ticks: the chunk's two specializations
    else:
        n = _first(kind)
    fe = _frontend(served, warm=warm)
    try:
        r = fe.submit(_prompt(), max_new_tokens=n, **GREEDY, **kw)
        assert r.result(timeout=600).status == "completed", r.reason
        assert fe.counts["ticks_ahead"] == fe.counts["ticks_dropped"] == 0
        assert fe.counts["ticks_serial"] == r.decode_ticks
        sent = _dispatches(r)
        assert [s.args["behind"] for s in sent] \
            == [False] * (1 + r.decode_ticks)
        assert [s.args["index"] for s in sent] \
            == list(range(1 + r.decode_ticks))
        if why == "cold":
            assert r.decode_ticks == 2
            # both have run now: the next request's chunks all go ahead
            r2 = fe.submit(_prompt(), max_new_tokens=n, **GREEDY)
            assert r2.result(timeout=600).status == "completed"
            assert fe.counts["ticks_ahead"] == 2
            assert fe.counts["ticks_serial"] == 2
            assert r2.tokens == r.tokens
        elif why == "owed_nothing":
            assert r.decode_ticks == 0
    finally:
        fe.close()


# ------------------------------------------------------------ the guarantees
def _spy_failures(fe):
    heard = []
    record = fe.breaker.record_failure

    def record_failure():
        heard.append(1)
        record()

    fe.breaker.record_failure = record_failure
    return heard


@pytest.mark.serving
@pytest.mark.chaos
@pytest.mark.parametrize("where", ["hook", "wait"])
def test_a_hung_tick_drops_the_chunk_ahead_and_the_server_serves_on(
        served, monkeypatch, where):
    """The second decode tick hangs, in the chaos hook (its chunk in flight
    since the tick before) or in ``block_until_ready`` (the chunk after it
    queued behind as well): a clean timeout inside the tick's cap, the
    request ``partial`` with every token delivered, ONE breaker failure, the
    chunk that was ahead dropped unread and counted, the next request
    completed."""
    from deepspeed_tpu.resilience import chaos

    kind, rid = served[0], f"hung-{served[0]}-{where}"
    _warm_up(served, **GREEDY)
    ref, _ = _serve(served, 6 * CHUNK, serial=True, **GREEDY)
    fe = _frontend(served, decode_tick_timeout_s=0.6, breaker_threshold=3)
    heard = _spy_failures(fe)
    if where == "hook":
        # the hook's call #1 is the prefill tick, #3 the second decode tick
        chaos.install_chaos(chaos.ChaosInjector(
            hang_at={"decode_step": [3]}, hang_s=2.0))
    else:
        waits, ready = [], jax.block_until_ready

        def hung_third(x):
            name = threading.current_thread().name
            if name == f"ds-deadline-serve-tick[{rid}]":
                waits.append(name)
                if len(waits) == 3:
                    time.sleep(2.0)
            return ready(x)

        monkeypatch.setattr(jax, "block_until_ready", hung_third)
    try:
        t0 = time.monotonic()
        calls = []
        r = fe.submit(_prompt(), max_new_tokens=6 * CHUNK, stream=calls.append,
                      request_id=rid, **GREEDY)
        r.result(timeout=60)
        assert time.monotonic() - t0 < 1.8
        delivered = _first(kind) + CHUNK
        assert (r.status, r.reason) == ("partial", "timeout")
        assert r.tokens == ref.tokens[:delivered]
        assert [t for c in calls for t in c] == r.tokens
        assert r.decode_ticks == 1
        assert len(heard) == 1 and fe.breaker.state == "closed"
        # behind the prefill and behind the first decode tick; what the
        # dead tick's worker dispatched is nobody's
        assert (fe.counts["ticks_ahead"], fe.counts["ticks_dropped"],
                fe.counts["timed_out"]) == (2, 1, 1)
        assert fe._ahead is None
        # a dying tick's dispatches are records too: the chunk its worker
        # sent behind before it hung in the wait
        sent = _dispatches(r)
        assert [s.args["index"] for s in sent] \
            == [0, 1, 2] + [3] * (where == "wait")
        assert sorted(_waited(r).values()) == [("decode_chunk", 1),
                                               ("prefill", 0)]
        dead = _spans_of(r, "decode")[-1]
        assert all(s.parent == dead.id for s in sent[3:])
        chaos.uninstall_chaos()
        monkeypatch.undo()
        r2 = fe.submit(_prompt(), max_new_tokens=6 * CHUNK, **GREEDY)
        assert r2.result(timeout=60).status == "completed"
        assert r2.tokens == ref.tokens
        assert fe.counts["ticks_dropped"] == 1
        # closed by now: the worker is serving the request after it
        (span,) = _spans_of(r, "request")
        assert span.args["decode_ticks"] == 1
        assert span.args["new_tokens"] == delivered
    finally:
        fe.close()
        time.sleep(1.6)     # let the disowned worker drain its sleep


@pytest.mark.serving
@pytest.mark.chaos
def test_a_request_deadline_with_a_chunk_ahead(served):
    """Every tick pays an injected delay and the request runs out of ITS
    budget mid-decode, a chunk ahead of it: ``partial`` / ``deadline`` with
    what was delivered, the breaker hears nothing, the chunk is dropped."""
    from deepspeed_tpu.resilience import chaos

    _warm_up(served, **GREEDY)
    ref, _ = _serve(served, 40, serial=True, **GREEDY)
    fe = _frontend(served, decode_tick_timeout_s=30.0)
    heard = _spy_failures(fe)
    chaos.install_chaos(chaos.ChaosInjector(
        delay_at={"decode_step": list(range(1, 40))}, max_delay_s=0.25))
    try:
        r = fe.submit(_prompt(), max_new_tokens=40, deadline_s=0.9, **GREEDY)
        r.result(timeout=60)
        assert (r.status, r.reason) == ("partial", "deadline")
        assert 0 < len(r.tokens) < 40 and r.tokens == ref.tokens[:len(r.tokens)]
        assert not heard and fe.breaker.state == "closed"
        assert (fe.counts["timed_out"], fe.counts["ticks_dropped"]) == (1, 1)
        assert fe.counts["ticks_ahead"] == r.decode_ticks + 1
        assert fe._ahead is None
        # the dropped chunk: a dispatch that no tick_wait names, the last
        sent, waited = _dispatches(r), _waited(r)
        assert len(sent) == len(waited) + 1
        assert [s.args["seq"] for s in sent[:-1]] == sorted(waited)
        lost = sent[-1].args
        assert lost["seq"] not in waited and lost["behind"]
        assert lost["index"] == r.decode_ticks + 1
    finally:
        fe.close()


@pytest.mark.serving
@pytest.mark.chaos
def test_drain_with_a_chunk_ahead(served):
    """SIGTERM mid-stream: the in-flight request is capped at the drain's
    grace with its partial flushed, the chunk that was ahead of the cap is
    dropped, the worker exits and the server is dead."""
    from deepspeed_tpu.launcher.launch import DRAIN_EXIT_CODE
    from deepspeed_tpu.resilience import chaos

    _warm_up(served, **GREEDY)
    ref, _ = _serve(served, 40, serial=True, **GREEDY)
    fe = _frontend(served, drain_grace_s=0.4, decode_tick_timeout_s=30.0)
    chaos.install_chaos(chaos.ChaosInjector(
        delay_at={"decode_step": list(range(1, 40))}, max_delay_s=0.2))
    try:
        chunks = []
        r = fe.submit(_prompt(), max_new_tokens=40, deadline_s=60,
                      stream=chunks.append, **GREEDY)
        time.sleep(0.7)                     # mid-stream
        fe.begin_drain("signal")
        assert fe.drain(timeout=30) == DRAIN_EXIT_CODE
        r.result(timeout=5)
        assert (r.status, r.reason) == ("partial", "drained")
        assert chunks and [t for c in chunks for t in c] == r.tokens
        assert r.tokens == ref.tokens[:len(r.tokens)]
        assert (fe.counts["drained"], fe.counts["ticks_dropped"]) == (1, 1)
        assert fe.state == "dead" and fe._ahead is None
    finally:
        fe.close()


# ---------------------------------------------------------- the harness's wrap
@pytest.mark.serving
def test_one_call_a_tick_through_the_instance_attribute(served):
    """``benchmark/systems.py::ServeSystem.instrument`` replaces
    ``front._tick`` by a wrapper ``(req, fn, warm_key)`` that calls the
    original with ``warm_key=`` as a keyword and reads
    ``len(req.tokens)`` at entry. One call a tick, entered after the
    delivery of the tick before, returned when ITS chunk is ready: the
    call's interval holds its own tick's span and ``tick_wait``."""
    kind = served[0]
    _warm_up(served, **GREEDY)
    fe = _frontend(served)
    tick, seen = fe._tick, []

    def timed_tick(req, fn, warm_key):
        entry = (str(warm_key[0]), len(req.tokens), time.monotonic())
        try:
            return tick(req, fn, warm_key=warm_key)
        finally:
            seen.append(entry + (time.monotonic(),))

    fe._tick = timed_tick
    try:
        n = 5 * CHUNK
        r = fe.submit(_prompt(), max_new_tokens=n, **GREEDY)
        assert r.result(timeout=600).status == "completed"
    finally:
        fe.close()
    ticks = r.decode_ticks
    assert [s[0] for s in seen] == ["prefill"] + ["decode"] * ticks
    # what had been delivered when each call was entered
    assert [s[1] for s in seen] == [0] + [
        _first(kind) + CHUNK * i for i in range(ticks)]
    assert fe.counts["ticks_ahead"] == ticks
    spans = _spans_of(r, "prefill") + _spans_of(r, "decode")
    waits = _spans_of(r, "tick_wait")
    assert len(spans) == len(waits) == len(seen)
    for (_, _, t0, t1), span, wait in zip(seen, spans, waits):
        assert wait.parent == span.id
        assert t0 <= span.t0 <= wait.t0 <= wait.t1 <= span.t1 <= t1
    # a call is entered after the delivery of the tick before it
    delivers = _spans_of(r, "deliver")
    assert len(delivers) == len(seen)
    for deliver, (_, _, t0, _) in zip(delivers, seen[1:]):
        assert deliver.t1 <= t0
