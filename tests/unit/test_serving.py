"""ds_serve fault-tolerant front-end tests: admission/shedding, per-tick
deadlines, circuit breaker, graceful drain, chaos decode_step drills, the
zero-silent-drops e2e acceptance drill, strict no-op without the block,
schema pass, and the ds_serve --smoke / ds_metrics --serving CLI chain."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# shared across frontends in this module: every front-end serves the same
# module with the same chunking, so the jitted (prefill, decode) pair and
# the warm-tick counters are reusable — one compile for the whole file
_SHARED_PROGRAMS: dict = {}
_SHARED_WARM: dict = {}
CHUNK = 4


@pytest.fixture(scope="module")
def engine():
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=256, n_positions=128, n_embd=64,
                     n_layer=2, n_head=4)
    return InferenceEngine(
        GPT2Model(cfg),
        DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=64))


@pytest.fixture(autouse=True)
def _chaos_clean():
    yield
    from deepspeed_tpu.resilience import chaos

    chaos.uninstall_chaos()


def _frontend(engine, start=True, agent=None, **serving):
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.serving import ServingFrontEnd

    serving.setdefault("decode_tick_tokens", CHUNK)
    serving.setdefault("max_queue_depth", 8)
    ds = DeepSpeedConfig({"serving": serving})
    fe = ServingFrontEnd(engine, ds.serving, agent=agent, start=False)
    fe._programs = _SHARED_PROGRAMS
    fe._warm = _SHARED_WARM
    if start:
        fe.start()
    return fe


def _prompt(n=8, base=0):
    return (np.arange(base, base + n)[None, :] % 256).astype(np.int32)


@pytest.mark.serving
class TestAdmission:
    def test_completed_request_matches_generate(self, engine):
        fe = _frontend(engine)
        try:
            chunks = []
            r = fe.submit(_prompt(), max_new_tokens=12, stream=chunks.append)
            r.result(timeout=300)
            assert r.status == "completed" and r.reason == ""
            assert len(r.tokens) == 12
            assert r.ttft_s is not None and r.ttft_s > 0
            # the serving path must emit EXACTLY what generate() emits
            ref = np.asarray(engine.generate(_prompt(), max_new_tokens=12))
            assert r.tokens == ref[0, 8:].tolist()
            # ...and the streaming consumer saw every token, in order
            assert [t for c in chunks for t in c] == r.tokens
        finally:
            fe.close()

    def test_sampled_request_matches_generate(self, engine):
        fe = _frontend(engine)
        try:
            r = fe.submit(_prompt(), max_new_tokens=8, do_sample=True,
                          temperature=0.8, top_k=12, seed=7)
            r.result(timeout=300)
            assert r.status == "completed"
            ref = np.asarray(engine.generate(
                _prompt(), max_new_tokens=8, do_sample=True,
                temperature=0.8, top_k=12, seed=7))
            # rng threads through the scan carry identically whether the
            # decode runs as one program or in chunks
            assert r.tokens == ref[0, 8:].tolist()
        finally:
            fe.close()

    def test_queue_full_sheds_structured(self, engine):
        from deepspeed_tpu.serving import ShedError

        fe = _frontend(engine, start=False, max_queue_depth=2)
        try:
            fe.submit(_prompt(), max_new_tokens=4)
            fe.submit(_prompt(base=8), max_new_tokens=4)
            with pytest.raises(ShedError) as ei:
                fe.submit(_prompt(base=16), max_new_tokens=4)
            assert ei.value.reason == "queue_full"
            assert ei.value.queue_depth == 2
            assert ei.value.retry_after_s > 0
            assert fe.counts["shed{reason=queue_full}"] == 1
        finally:
            fe.close()

    def test_deadline_unreachable_sheds_early(self, engine):
        from deepspeed_tpu.serving import ShedError

        fe = _frontend(engine, start=False, max_queue_depth=8)
        try:
            fe._service_ema = 0.5              # a warmed server's estimate
            fe.submit(_prompt(), max_new_tokens=4)
            fe.submit(_prompt(base=8), max_new_tokens=4)
            # 2 queued × 0.5s each — a 0.2s deadline cannot make it
            with pytest.raises(ShedError) as ei:
                fe.submit(_prompt(base=16), max_new_tokens=4, deadline_s=0.2)
            assert ei.value.reason == "deadline_unreachable"
            assert ei.value.est_wait_s > 0.2
        finally:
            fe.close()

    def test_oversized_request_refused_not_shed(self, engine):
        fe = _frontend(engine, start=False)
        try:
            with pytest.raises(ValueError, match="max_out_tokens"):
                fe.submit(_prompt(32), max_new_tokens=64)   # 96 > 64
            assert fe.counts["admitted"] == 0
        finally:
            fe.close()

    def test_program_variant_limit_sheds_structured(self, engine):
        from deepspeed_tpu.serving import ShedError

        fe = _frontend(engine, start=False, max_program_variants=1)
        try:
            # greedy pair is already in the shared program cache (len >= 1),
            # so any NEW sampling combination must shed instead of compiling
            with pytest.raises(ShedError) as ei:
                fe.submit(_prompt(), max_new_tokens=4, do_sample=True,
                          temperature=0.123)
            assert ei.value.reason == "sampling_variant_limit"
            # a cached combination still admits
            fe.submit(_prompt(), max_new_tokens=4)
            assert fe.counts["admitted"] == 1
        finally:
            fe.close()

    def test_program_variant_limit_counts_queued_variants(self, engine):
        """The bound must see variants that are ADMITTED but not yet
        compiled — a burst of unique variants queued before the worker
        runs must not slip past a compiled-programs-only check."""
        from deepspeed_tpu.serving import ShedError

        fe = _frontend(engine, start=False, max_program_variants=1)
        fe._programs = {}        # nothing compiled yet
        try:
            fe.submit(_prompt(), max_new_tokens=4, do_sample=True,
                      temperature=0.5)          # queued, uncompiled variant
            with pytest.raises(ShedError) as ei:
                fe.submit(_prompt(), max_new_tokens=4, do_sample=True,
                          temperature=0.6)      # second distinct variant
            assert ei.value.reason == "sampling_variant_limit"
            # the variant already queued still admits more requests
            fe.submit(_prompt(base=8), max_new_tokens=4, do_sample=True,
                      temperature=0.5)
            assert fe.counts["admitted"] == 2
        finally:
            fe.close()

    @pytest.mark.chaos
    def test_probe_slot_released_on_deadline_expiry(self, engine):
        """A half-open probe that dies of its own deadline before any tick
        must hand the slot back — the breaker must not wedge half_open."""
        from deepspeed_tpu.resilience.chaos import (ChaosInjector,
                                                    install_chaos,
                                                    uninstall_chaos)

        install_chaos(ChaosInjector(fail_at={"decode_step": [1, 2]}))
        fe = _frontend(engine, breaker_threshold=2, breaker_cooldown_s=0.2)
        try:
            fe.submit(_prompt(), max_new_tokens=4).result(timeout=60)
            fe.submit(_prompt(), max_new_tokens=4).result(timeout=60)
            assert fe.breaker.state == "open"
            uninstall_chaos()
            time.sleep(0.25)
            # this probe claims the half-open slot, then expires in the
            # queue before its first tick (deadline far below any service)
            p = fe.submit(np.zeros((1, 1), np.int32), max_new_tokens=1,
                          deadline_s=1e-4, is_probe=True)
            p.result(timeout=60)
            assert p.status == "shed" and p.reason == "deadline"
            # the slot came back: a real probe can still half-open → close
            p2 = fe.probe(timeout=60)
            assert p2.status == "completed"
            assert fe.breaker.state == "closed"
        finally:
            fe.close()

    def test_capacity_from_kv_budget(self, engine):
        from deepspeed_tpu.runtime.config import ServingConfig
        from deepspeed_tpu.serving import (kv_bytes_per_request,
                                           resolve_capacity)

        per_req = kv_bytes_per_request(engine.module, 64)
        assert per_req > 0
        cfg = ServingConfig(hbm_bytes=1 << 30, kv_budget_fraction=0.5)
        cap, detail = resolve_capacity(engine, cfg)
        import jax

        params_bytes = sum(int(x.nbytes)
                           for x in jax.tree.leaves(engine.params))
        expect = max(1, int(((1 << 30) - params_bytes) * 0.5 // per_req))
        assert cap == expect
        assert detail["kv_bytes_per_request"] == per_req
        assert detail["source"] == "kv_budget(config)"
        # an explicit bound wins over the budget
        cap2, detail2 = resolve_capacity(
            engine, ServingConfig(max_queue_depth=3))
        assert cap2 == 3 and detail2["source"] == "max_queue_depth"


@pytest.mark.serving
class TestSpans:
    """The front-end's own spans and stamps (the names are a contract with
    benchmark/program_spans.py and docs/CONFIG.md)."""

    @staticmethod
    def _serve_one(engine, new_tokens=12, status="completed"):
        from deepspeed_tpu import telemetry

        tracer = telemetry.get_tracer()
        before = {s.id for s in tracer.snapshot()}
        fe = _frontend(engine)
        try:
            r = fe.submit(_prompt(), max_new_tokens=new_tokens)
            r.result(timeout=300)
            assert r.status == status
        finally:
            fe.close()      # joins the worker: status_write is recorded
        return r, [s for s in tracer.snapshot() if s.id not in before
                   and s.args.get("request") == r.id]

    def test_no_session_records_into_the_ring(self, engine):
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.telemetry import tracing

        assert telemetry.get_session() is None
        assert telemetry.get_tracer() is tracing.RING
        _, spans = self._serve_one(engine, new_tokens=4)
        assert {s.name for s in spans} == {
            "request", "admission_wait", "prefill", "decode", "worker_start",
            "dispatch", "tick_launch", "tick_wait", "tick_return", "deliver",
            "request_close", "status_write"}

    def test_stamps_are_ordered_and_in_the_request_span(self, engine):
        r, spans = self._serve_one(engine)
        assert r.submitted_at <= r.started_at <= r.prefill_done_at \
            <= r.first_tokens_at <= r.finished_at
        assert r.ttft_s == r.first_tokens_at - r.submitted_at
        (req,) = [s for s in spans if s.name == "request"]
        assert req.t0 == r.started_at and req.t1 >= r.finished_at
        import jax

        # the cache its positions hold: 8 prompt + 3 ticks of 4 steps, each
        # the bytes of one row of the cache's k and v, (L, B, S, W) each;
        # a model all of whose layers cache rows keeps no state a sequence
        cache = jax.eval_shape(lambda: engine.module.init_cache(1, 8))
        a_position = sum(cache[n].shape[0] * cache[n].shape[3]
                         * cache[n].dtype.itemsize for n in ("k", "v"))
        assert a_position > 0
        assert req.args == {
            "request": r.id, "prompt_len": 8, "new_tokens": 12,
            "status": "completed", "decode_ticks": 3,
            "prefill_done_at": r.prefill_done_at,
            "first_tokens_at": r.first_tokens_at,
            "cache_positions": 20, "cache_bytes": 20 * a_position,
            "state_bytes": 0}
        (wait,) = [s for s in spans if s.name == "admission_wait"]
        assert (wait.t0, wait.t1) == (r.submitted_at, r.started_at)
        (prefill,) = [s for s in spans if s.name == "prefill"]
        assert prefill.t1 == r.prefill_done_at

    def test_spans_of_a_request_form_one_tree(self, engine):
        # 12 tokens: the prefill's one, then ceil(11 / 4) = 3 decode ticks
        r, spans = self._serve_one(engine)
        assert len({s.id for s in spans}) == len(spans)
        by_id = {s.id: s for s in spans}
        (req,) = [s for s in spans if s.name == "request"]
        assert req.parent is None
        tree = [s for s in spans if s.name != "status_write"]
        assert all(s.trace == r.id for s in tree)
        for s in tree:
            if s is not req:
                want = ("request",) if s.name in (
                    "admission_wait", "prefill", "decode", "deliver",
                    "request_close") else ("prefill", "decode")
                assert by_id[s.parent].name in want, (s.name, s.parent)
        decodes = [s for s in spans if s.name == "decode"]
        assert [s.args["index"] for s in decodes] == [0, 1, 2]
        # positions in the cache when the tick starts: the token a tick
        # steps on is in ``req.tokens`` already, and not yet in the cache
        assert [s.args["context"] for s in decodes] == [8, 12, 16]
        # one deliver after the prefill tick and one after every decode tick
        assert len([s for s in spans if s.name == "deliver"]) == 4
        # from the last delivery to the resolution, the client's answer
        (close,) = [s for s in spans if s.name == "request_close"]
        last = max(s.t1 for s in spans if s.name == "deliver")
        assert close.t0 == last and close.t1 >= r.finished_at
        assert close.t1 <= req.t1
        # the status write follows the request and is not part of its tree
        (status,) = [s for s in spans if s.name == "status_write"]
        assert status.parent is None and status.trace is None
        assert status.t0 >= req.t1

    def test_three_children_tile_the_tick(self, engine):
        _, spans = self._serve_one(engine)
        ticks = [s for s in spans if s.name in ("prefill", "decode")]
        assert len(ticks) == 4
        for tick in ticks:
            under = sorted((s for s in spans if s.parent == tick.id),
                           key=lambda s: s.t0)
            kids = [k for k in under if k.name.startswith("tick_")]
            assert [k.name for k in kids] == [
                "tick_launch", "tick_wait", "tick_return"]
            assert kids[0].t0 == tick.t0 and kids[2].t1 == tick.t1
            assert kids[0].t1 == kids[1].t0 and kids[1].t1 == kids[2].t0
            assert abs(sum(k.dur for k in kids) - tick.dur) < 50e-6
            # what splits the launch: the worker thread's spawn, then every
            # program this call handed the device
            inside = [k for k in under if not k.name.startswith("tick_")]
            assert inside[0].name == "worker_start"
            assert {k.name for k in inside[1:]} <= {"dispatch"}
            assert inside[0].t0 == tick.t0
            for a, b in zip(inside, inside[1:]):
                assert a.t1 <= b.t0
            assert inside[-1].t1 <= kids[0].t1
        # a tick (the prefill too) and its deliver follow each other at once
        delivers = [s for s in spans if s.name == "deliver"]
        assert len(delivers) == len(ticks)
        for tick, deliver in zip(ticks, delivers):
            assert 0 <= deliver.t0 - tick.t1 < 1e-3

    def test_a_tick_that_dies_records_no_children(self, engine):
        from deepspeed_tpu.resilience.chaos import (ChaosInjector,
                                                    install_chaos)

        # the hook's call #1 is the prefill tick, #2 the first decode tick:
        # the prefill's token was delivered, so the request ends partial
        install_chaos(ChaosInjector(fail_at={"decode_step": [2]}))
        r, spans = self._serve_one(engine, new_tokens=8, status="partial")
        assert len(r.tokens) == 1
        (req,) = [s for s in spans if s.name == "request"]
        assert req.args["status"] == "partial"
        assert req.args["decode_ticks"] == 0
        assert req.args["first_tokens_at"] == r.first_tokens_at
        (dead,) = [s for s in spans if s.name == "decode"]
        assert not [s for s in spans if s.parent == dead.id]

    def test_every_span_is_in_a_device_profile_as_ds(self, engine, tmp_path):
        """The with-spans enter a TraceAnnotation ``ds/serving/<name>``
        while a profile is being taken; the three children of a tick are
        stamped in a short-lived worker thread and live in the ring only;
        no program span may pose as the benchmark's (``bench/``)."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            r, _ = self._serve_one(engine, new_tokens=4)
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        events = [e for plane in jax.profiler.ProfileData.from_file(
                      str(path)).planes if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name.startswith(("ds/", "bench/"))]
        assert {e.name for e in events} == {
            "ds/serving/request", "ds/serving/prefill", "ds/serving/decode",
            "ds/serving/deliver", "ds/serving/status_write"}
        decode = next(e for e in events if e.name == "ds/serving/decode")
        stats = {k: str(v) for k, v in decode.stats}
        assert stats["request"] == r.id and stats["trace"] == r.id
        assert stats["context"] == "8" and stats["index"] == "0"

    def test_histograms_observe_the_spans_own_durations(self, engine,
                                                        tmp_path):
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.runtime.config import TelemetryConfig

        tel = telemetry.configure(TelemetryConfig(
            enabled=True, output_dir=str(tmp_path / "t"),
            flush_interval=1000, prometheus=False))
        try:
            r, spans = self._serve_one(engine, new_tokens=4)
            assert spans and telemetry.get_tracer() is tel.tracer
            hist = {rec["name"]: rec for rec in tel.registry.snapshot()
                    if rec["name"].startswith("serving/")}
            one = lambda name: next(s for s in spans if s.name == name).dur
            for series, want in (
                    ("serving/prefill_seconds", one("prefill")),
                    ("serving/decode_chunk_seconds", one("decode")),
                    ("serving/queue_wait_seconds", one("admission_wait")),
                    ("serving/ttft_seconds", r.ttft_s)):
                assert hist[series]["count"] == 1
                assert hist[series]["max"] == pytest.approx(want, abs=1e-9)
        finally:
            telemetry.deconfigure()


@pytest.mark.serving
@pytest.mark.chaos
class TestFailurePaths:
    def test_request_deadline_caps_decode(self, engine):
        from deepspeed_tpu.resilience.chaos import (ChaosInjector,
                                                    install_chaos)

        # every tick pays a 0.25s injected delay; a 0.6s deadline dies
        # mid-decode with a partial and the reason on it
        install_chaos(ChaosInjector(
            delay_at={"decode_step": list(range(1, 40))}, max_delay_s=0.25))
        fe = _frontend(engine, decode_tick_timeout_s=30.0)
        try:
            r = fe.submit(_prompt(), max_new_tokens=40, deadline_s=0.9)
            r.result(timeout=60)
            assert r.status in ("partial", "shed")
            assert r.reason == "deadline"
            assert len(r.tokens) < 40
            assert fe.counts["timed_out"] == 1
            # a request deadline is not an engine failure
            assert fe.breaker.state == "closed"
        finally:
            fe.close()

    def test_hung_tick_times_out_and_server_survives(self, engine):
        from deepspeed_tpu.resilience.chaos import (ChaosInjector,
                                                    install_chaos,
                                                    uninstall_chaos)

        install_chaos(ChaosInjector(hang_at={"decode_step": [2]}, hang_s=3.0))
        fe = _frontend(engine, decode_tick_timeout_s=0.8)
        try:
            t0 = time.monotonic()
            r = fe.submit(_prompt(), max_new_tokens=8)
            r.result(timeout=60)
            # the 3s hang became a clean sub-second timeout, not a wedge
            assert time.monotonic() - t0 < 2.5
            assert r.status in ("failed", "partial")
            assert r.reason == "timeout"
            uninstall_chaos()
            # the server keeps serving
            r2 = fe.submit(_prompt(), max_new_tokens=8).result(timeout=60)
            assert r2.status == "completed"
        finally:
            fe.close()
            time.sleep(2.5)    # let the disowned hang thread drain its sleep

    def test_circuit_opens_sheds_and_recovers_via_probe(self, engine):
        from deepspeed_tpu.resilience.chaos import (ChaosInjector,
                                                    install_chaos,
                                                    uninstall_chaos)
        from deepspeed_tpu.serving import ShedError

        install_chaos(ChaosInjector(fail_at={"decode_step": [1, 2]}))
        fe = _frontend(engine, breaker_threshold=2, breaker_cooldown_s=0.4)
        try:
            r1 = fe.submit(_prompt(), max_new_tokens=4).result(timeout=60)
            r2 = fe.submit(_prompt(), max_new_tokens=4).result(timeout=60)
            assert r1.status == "failed" and "ChaosError" in r1.reason
            assert r2.status == "failed"
            assert fe.breaker.state == "open"
            assert fe.state == "degraded"
            with pytest.raises(ShedError) as ei:
                fe.submit(_prompt(), max_new_tokens=4)
            assert ei.value.reason == "circuit_open"
            assert 0 < ei.value.retry_after_s <= 0.4
            uninstall_chaos()
            time.sleep(0.45)                   # cooldown elapses
            p = fe.probe(timeout=60)
            assert p.status == "completed"
            assert fe.breaker.state == "closed"
            assert fe.state == "ready"
            t = fe.counts
            assert t["circuit_transitions{from=closed,to=open}"] == 1
            assert t["circuit_transitions{from=open,to=half_open}"] == 1
            assert t["circuit_transitions{from=half_open,to=closed}"] == 1
        finally:
            fe.close()

    def test_failed_probe_reopens_circuit(self, engine):
        from deepspeed_tpu.resilience.chaos import (ChaosInjector,
                                                    install_chaos)

        # ticks 1+2 fail the two requests that open the circuit; tick 3
        # fails the probe, which must re-open it
        install_chaos(ChaosInjector(fail_at={"decode_step": [1, 2, 3]}))
        fe = _frontend(engine, breaker_threshold=2, breaker_cooldown_s=0.3)
        try:
            fe.submit(_prompt(), max_new_tokens=4).result(timeout=60)
            fe.submit(_prompt(), max_new_tokens=4).result(timeout=60)
            assert fe.breaker.state == "open"
            time.sleep(0.35)
            p = fe.probe(timeout=60)
            assert p.status == "failed"
            assert fe.breaker.state == "open"
            assert fe.counts["circuit_transitions{from=half_open,to=open}"] == 1
        finally:
            fe.close()


@pytest.mark.serving
@pytest.mark.chaos
class TestDrain:
    def test_drain_mid_stream_flushes_partials(self, engine):
        from deepspeed_tpu.launcher.launch import (DRAIN_EXIT_CODE,
                                                   HEARTBEAT_KILL_EXIT_CODE)
        from deepspeed_tpu.resilience.chaos import (ChaosInjector,
                                                    install_chaos)
        from deepspeed_tpu.serving import ShedError

        assert DRAIN_EXIT_CODE != HEARTBEAT_KILL_EXIT_CODE != 0
        install_chaos(ChaosInjector(
            delay_at={"decode_step": list(range(1, 40))}, max_delay_s=0.2))
        fe = _frontend(engine, drain_grace_s=0.8, decode_tick_timeout_s=30.0)
        try:
            chunks = []
            r1 = fe.submit(_prompt(), max_new_tokens=40, deadline_s=60,
                           stream=chunks.append)
            r2 = fe.submit(_prompt(base=8), max_new_tokens=4)   # queued behind
            time.sleep(0.7)                    # r1 is mid-stream
            fe.begin_drain("signal")
            code = fe.drain(timeout=30)
            r1.result(timeout=5)
            r2.result(timeout=5)
            # in-flight: finished-or-capped with its partial flushed
            assert r1.status in ("partial", "completed")
            if r1.status == "partial":
                assert r1.reason == "drained"
            assert chunks, "streaming consumer never saw the partial"
            assert [t for c in chunks for t in c] == r1.tokens[:sum(
                len(c) for c in chunks)]
            # queued: structured shed, never silently dropped, with the
            # back-off hint on the resolved request; counted on the
            # admitted side of the ledger (shed_admitted, not shed)
            assert r2.status == "shed" and r2.reason == "draining"
            assert r2.retry_after_s > 0
            assert r2.to_dict()["retry_after_s"] == r2.retry_after_s
            assert fe.counts["shed_admitted{reason=draining}"] == 1
            # distinct, launcher-recognizable exit code for a signal drain
            assert code == DRAIN_EXIT_CODE
            assert fe.state == "dead"
            with pytest.raises(ShedError):
                fe.submit(_prompt(), max_new_tokens=4)
        finally:
            fe.close()

    def test_agent_preemption_flag_triggers_drain(self, engine):
        from deepspeed_tpu.launcher.launch import DRAIN_EXIT_CODE

        class FakeAgent:
            preempted = False

        agent = FakeAgent()
        fe = _frontend(engine, agent=agent)
        try:
            r = fe.submit(_prompt(), max_new_tokens=4)
            r.result(timeout=60)
            agent.preempted = True
            code = fe.drain(timeout=30)
            assert fe.state == "dead"
            assert code == DRAIN_EXIT_CODE
            assert fe.counts["state_transitions{from=ready,to=draining}"] == 1
        finally:
            fe.close()

    def test_elastic_agent_exposes_preempted_property(self):
        from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent

        agent = DSElasticAgent(engine_factory=lambda: None, save_dir="/tmp/x",
                               install_signal_handlers=False)
        assert agent.preempted is False
        agent.preempt()
        assert agent.preempted is True

    def test_e2e_chaos_drill_zero_silent_drops(self, engine):
        """The acceptance drill: N concurrent clients, injected decode
        fail + hang, drain mid-flight — every admitted request resolves
        to tokens / partial+reason / structured shed, the circuit opens
        and the process never wedges."""
        from deepspeed_tpu.resilience.chaos import (ChaosInjector,
                                                    install_chaos)
        from deepspeed_tpu.serving import ShedError

        install_chaos(ChaosInjector(fail_at={"decode_step": [4]},
                                    hang_at={"decode_step": [7]},
                                    hang_s=2.0))
        fe = _frontend(engine, max_queue_depth=4, breaker_threshold=3,
                       decode_tick_timeout_s=0.8, drain_grace_s=1.0)
        results, sheds, lock = [], [], threading.Lock()

        def client(i):
            try:
                r = fe.submit(_prompt(base=i), max_new_tokens=8,
                              deadline_s=120)
                r.result(timeout=120)
                with lock:
                    results.append(r)
            except ShedError as e:
                with lock:
                    sheds.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        try:
            for t in threads[:6]:
                t.start()
            time.sleep(1.0)
            fe.begin_drain("signal")
            for t in threads[6:]:
                t.start()                       # submitted after drain began
            for t in threads:
                t.join(timeout=180)
            assert not any(t.is_alive() for t in threads), "a client wedged"
            # zero silent drops: all 8 clients got a terminal answer
            assert len(results) + len(sheds) == 8
            for r in results:
                assert r.status in ("completed", "partial", "shed", "failed"), r
                if r.status != "completed":
                    assert r.reason, f"terminal without a reason: {r}"
            fe.drain(timeout=30)
            assert fe.state == "dead"
            # the ledger adds up EXACTLY: every admitted request is one of
            # completed/timed_out/drained/failed/shed_admitted — at-the-door
            # refusals live in the separate shed{...} series
            c = fe.counts
            admitted = c.get("admitted", 0)
            resolved = (c.get("completed", 0) + c.get("failed", 0)
                        + c.get("timed_out", 0) + c.get("drained", 0)
                        + sum(v for k, v in c.items()
                              if k.startswith("shed_admitted{")))
            assert admitted == len(results)
            assert resolved == admitted
        finally:
            fe.close()
            time.sleep(1.5)    # let any disowned hang thread finish sleeping


@pytest.mark.serving
class TestStrictNoop:
    def test_strict_noop_without_block(self, tmp_path):
        """Without the ``serving`` block the package is never imported and
        no serving thread exists (the PR 4-6 contract)."""
        import deepspeed_tpu
        from deepspeed_tpu.models.simple import SimpleModel

        mods = [m for m in list(sys.modules)
                if m == "deepspeed_tpu.serving"
                or m.startswith("deepspeed_tpu.serving.")]
        saved = {m: sys.modules.pop(m) for m in mods}
        try:
            engine, *_ = deepspeed_tpu.initialize(
                model=SimpleModel(hidden_dim=16, nlayers=2),
                config={"train_batch_size": 8, "steps_per_print": 0,
                        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
            batch = (np.ones((8, 16), np.float32), np.zeros((8, 16), np.float32))
            engine.train_batch(batch)
            assert not any(m == "deepspeed_tpu.serving"
                           or m.startswith("deepspeed_tpu.serving.")
                           for m in sys.modules)
            assert not any(t.name.startswith("ds-serve")
                           for t in threading.enumerate())
        finally:
            sys.modules.update(saved)

    def test_config_block_parses_and_gates(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        ds = DeepSpeedConfig({})
        assert ds.serving_present is False
        ds2 = DeepSpeedConfig({"serving": {}})
        assert ds2.serving_present and ds2.serving.enabled
        with pytest.raises(ValueError, match="decode_tick_tokens"):
            DeepSpeedConfig({"serving": {"decode_tick_tokens": 0}})

    def test_from_ds_config_gates_on_presence_and_enabled(self, engine):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        from deepspeed_tpu.serving import from_ds_config

        assert from_ds_config(engine, DeepSpeedConfig({})) is None
        assert from_ds_config(
            engine, DeepSpeedConfig({"serving": {"enabled": False}})) is None


@pytest.mark.serving
@pytest.mark.analysis
class TestSchema:
    def test_unknown_serving_key_did_you_mean(self):
        from deepspeed_tpu.analysis.schema import walk_config

        findings, _ = walk_config({"serving": {"max_que_depth": 4}})
        errs = [f for f in findings if f.severity == "error"]
        assert any("max_que_depth" in f.message and
                   "max_queue_depth" in f.message for f in errs)

    def test_serving_without_telemetry_warns(self):
        from deepspeed_tpu.analysis.schema import walk_config

        findings, cfg = walk_config({"serving": {}})
        assert cfg is not None
        assert any(f.citation == "serving.enabled vs telemetry.enabled"
                   and f.severity == "warning" for f in findings)
        # with telemetry on, the warning goes away
        findings2, _ = walk_config({"serving": {},
                                    "telemetry": {"enabled": True}})
        assert not any(f.citation == "serving.enabled vs telemetry.enabled"
                       for f in findings2)

    def test_tick_deadline_vs_watchdog_floor(self):
        from deepspeed_tpu.analysis.schema import walk_config

        pd = {"serving": {"decode_tick_timeout_s": 120.0},
              "watchdog": {"enabled": True, "min_step_timeout": 60.0},
              "telemetry": {"enabled": True}}
        findings, _ = walk_config(pd)
        assert any(f.citation ==
                   "serving.decode_tick_timeout_s vs watchdog.min_step_timeout"
                   and f.severity == "warning" for f in findings)
        pd["serving"]["decode_tick_timeout_s"] = 30.0
        findings2, _ = walk_config(pd)
        assert not any("decode_tick_timeout_s" in f.citation
                       for f in findings2)

    def test_queue_bound_vs_kv_budget(self):
        from deepspeed_tpu.analysis.schema import walk_config

        findings, _ = walk_config({
            "serving": {"max_queue_depth": 64, "hbm_bytes": 1 << 30},
            "telemetry": {"enabled": True}})
        assert any(f.citation == "serving.max_queue_depth vs serving.hbm_bytes"
                   and f.severity == "warning" for f in findings)


@pytest.mark.serving
class TestCLI:
    def test_ds_serve_smoke_end_to_end(self, tmp_path):
        """Acceptance: the full admit→prefill→decode→drain pipeline runs
        on CPU and emits serving/* telemetry that ds_metrics renders."""
        out = str(tmp_path / "smoke")
        from deepspeed_tpu.serving.cli import main as cli_main

        rc = cli_main(["--smoke", "--output_dir", out])
        assert rc == 0
        assert os.path.isfile(os.path.join(out, "metrics.jsonl"))
        assert os.path.isfile(os.path.join(out, "serving_status.json"))
        with open(os.path.join(out, "serving_status.json")) as f:
            status = json.load(f)
        assert status["state"] == "dead"
        assert status["counts"]["completed"] == 2
        # acceptance chain: ds_metrics --serving renders the real JSONL
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_metrics"),
             out, "--serving"], capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        assert "request lifecycle" in proc.stdout
        assert "admitted" in proc.stdout
        assert "ttft_deadline_fraction" in proc.stdout
        # and ds_serve status renders the same run (stdlib path)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_serve"),
             "status", out], capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        assert "state: dead" in proc.stdout
        assert "breaker: closed" in proc.stdout

    def test_ds_serve_status_no_data(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_serve"),
             "status", str(tmp_path)], capture_output=True, text=True)
        assert proc.returncode == 1
        assert "no serving_status.json" in proc.stderr

    def test_serving_summary_no_data(self, tmp_path):
        (tmp_path / "metrics.jsonl").write_text(
            json.dumps({"kind": "gauge", "name": "train/loss",
                        "value": 1.0}) + "\n")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_metrics"),
             str(tmp_path), "--serving"], capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        assert "no serving/* series" in proc.stdout
