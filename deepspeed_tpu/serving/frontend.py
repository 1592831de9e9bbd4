"""The request lifecycle manager — admission, deadlines, breaker, drain.

One worker thread pulls admitted requests off a bounded queue and drives
each through ``prefill`` + chunked ``decode`` ticks (the programs come
from :func:`~deepspeed_tpu.inference.engine.build_serving_programs`, the
same scan body ``generate()`` compiles), delivering what a tick chose when
it returns: the prefill tick the first token, a decode tick its chunk. A
step of the programs may emit MORE than one token a row (a model that
generates by diffusion over blocks: the prefill tick then delivers the
first block, a decode tick ``decode_tick_tokens // block`` of them; a
block's keys and values are committed by the first pass of the NEXT block,
so the last block a request is delivered is never committed), so
tokens, cache positions and the service estimate are counted from what
the programs return, never from "one token a step".
Every tick runs under the watchdog's ``run_with_deadline``, so a hung
device step — or an injected chaos ``decode_step`` hang — surfaces as a
clean per-request timeout instead of a wedged server, and the host checks
the request deadline, the drain flag, and the elastic agent's preemption
flag between ticks. A request that cannot end early (no EOS id) has its
next decode chunk dispatched BEHIND the one a tick waits for, so the host's
work of a tick runs while the device computes (``_chunk_behind``). Every
call that hands the device a program is a ``dispatch`` record with its
place in the process's dispatch order (``seq``), and a ``tick_wait`` names
the one it blocked on: a reader pairs the device's executions with them,
one for one.

The invariant everything here serves: **an admitted request reaches
exactly one terminal status** (completed / partial / shed / failed), and
the reason travels with it. Overload sheds at admission with a
structured :class:`~deepspeed_tpu.serving.admission.ShedError`; engine
sickness opens the circuit breaker (queued requests shed with
retry-after, readiness → degraded, a probe half-opens after cooldown);
SIGTERM/preemption drains (admission stops, in-flight requests finish or
deadline-cap, streaming consumers get their partials) and the process
exits with :data:`DRAIN_EXIT_CODE` so the launcher's supervision loop
can tell a clean drain from a crash.

Health states: ``starting → ready ⇄ degraded → draining → dead``.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import signal
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

import numpy as np

from deepspeed_tpu import telemetry as _telemetry
from deepspeed_tpu.launcher.launch import DRAIN_EXIT_CODE  # noqa: F401 (re-exported)
from deepspeed_tpu.resilience.watchdog import WatchdogTimeout, run_with_deadline
from deepspeed_tpu.serving.admission import (Request, ShedError,
                                             resolve_capacity)
from deepspeed_tpu.serving.breaker import CLOSED, OPEN, CircuitBreaker
from deepspeed_tpu.utils import locks as _locks
from deepspeed_tpu.utils.logging import logger

STATUS_FILE = "serving_status.json"

# every call that hands the device a program, process-wide and in dispatch
# order: the order the device runs them in (``next`` on it is atomic)
_DISPATCHES = itertools.count(1)
_Dispatch = collections.namedtuple(
    "_Dispatch", "out program index behind seq t0 t1")


def _dispatch(call, program: str, index: int, behind: bool) -> _Dispatch:
    """``call()`` hands the device a program (``prefill`` |
    ``decode_chunk``; ``index`` 0 for the prefill, k for a request's k-th
    chunk; ``behind``: the request's program before it was still to be
    waited for). -> its outputs with the ``dispatch`` record's fields:
    ``t0`` just before the call, ``t1`` when it returned, what the dispatch
    cost the host."""
    seq = next(_DISPATCHES)
    t0 = time.monotonic()
    out = call()
    return _Dispatch(out, program, index, behind, seq, t0, time.monotonic())


class ServerState:
    """Health/readiness states, with stable numeric codes for the
    ``serving/state`` gauge (a gauge cannot carry a string)."""
    STARTING = "starting"
    READY = "ready"
    DEGRADED = "degraded"
    DRAINING = "draining"
    DEAD = "dead"
    CODES = {STARTING: 0, READY: 1, DEGRADED: 2, DRAINING: 3, DEAD: 4}


class ServingFrontEnd:
    """Fault-tolerant serving wrapper around an
    :class:`~deepspeed_tpu.inference.engine.InferenceEngine`.

    ``cfg`` is the ``serving`` ds_config block (``ServingConfig``);
    ``agent`` (optional) is a :class:`DSElasticAgent` whose ``preempted``
    flag triggers drain; ``start=False`` defers the worker thread (tests
    fill the queue first, then :meth:`start`)."""

    WORKER_POLL_S = 0.02

    def __init__(self, engine, cfg=None, agent=None, start: bool = True,
                 status_dir: Optional[str] = None):
        if cfg is None:
            from deepspeed_tpu.runtime.config import ServingConfig
            cfg = ServingConfig()
        if not cfg.enabled:
            raise ValueError("serving.enabled is false — the front-end "
                             "refuses to serve a config that opted out")
        self.engine = engine
        self.cfg = cfg
        self.agent = agent
        rlock = _locks.make_rlock("serving.frontend")  # ONE lock: queue + breaker
        self._lock = _locks.make_condition("serving.frontend", rlock)
        self._queue: collections.deque = collections.deque()
        self._in_flight: Optional[Request] = None
        self.capacity, self.capacity_detail = resolve_capacity(engine, cfg)
        self.breaker = CircuitBreaker(
            threshold=cfg.breaker_threshold, cooldown_s=cfg.breaker_cooldown_s,
            on_transition=self._on_breaker, lock=rlock)
        self._state = ServerState.STARTING
        self._draining = False
        self._drain_reason = ""
        self._drain_deadline: Optional[float] = None
        self._stop = threading.Event()
        self._worker: Optional[threading.Thread] = None
        self._programs: Dict[tuple, tuple] = {}
        # tokens a row one step of the engine's programs emits: 1, or the
        # block of a model that generates by diffusion over blocks
        from deepspeed_tpu.inference.engine import step_tokens

        self._step_tokens = step_tokens(engine.module)
        self._warm: Dict[tuple, int] = {}    # tick key -> successful runs
        # the decode chunk dispatched behind the last tick, the next tick's
        # to wait for; written by the serving thread alone
        self._ahead: Optional[_Dispatch] = None
        self._service_ema: Optional[float] = None
        self.counts: Dict[str, float] = collections.defaultdict(float)
        self.exit_code = 0
        self._status_dir = status_dir
        self._req_seq = 0
        self._set_state_gauge()
        self._reg().gauge("serving/capacity").set(self.capacity)
        if start:
            self.start()

    # -------------------------------------------------------------- telemetry
    @staticmethod
    def _reg():
        return _telemetry.get_registry()

    def _count(self, name: str, labels: Optional[Dict[str, str]] = None,
               n: float = 1.0) -> None:
        key = name if not labels else \
            name + "{" + ",".join(f"{k}={v}" for k, v in sorted(labels.items())) + "}"
        self.counts[key] += n
        self._reg().counter(f"serving/{name}", labels=labels).inc(n)

    def _set_queue_gauge(self) -> None:
        depth = len(self._queue) + (1 if self._in_flight is not None else 0)
        self._reg().gauge("serving/queue_depth").set(depth)

    def _set_state_gauge(self) -> None:
        self._reg().gauge("serving/state").set(ServerState.CODES[self._state])

    # ------------------------------------------------------------- lifecycle
    def start(self) -> "ServingFrontEnd":
        with self._lock:
            if self._worker is not None and self._worker.is_alive():
                return self
            self._worker = _locks.spawn_thread(self._serve_loop,
                                               name="ds-serve-worker",
                                               owner="serving", daemon=True)
            self._worker.start()
            if self._state == ServerState.STARTING:
                self._transition(ServerState.READY)
        return self

    def _transition(self, to: str) -> None:
        with self._lock:
            frm = self._state
            if frm == to or frm == ServerState.DEAD:
                return
            self._state = to
            self._count("state_transitions", labels={"from": frm, "to": to})
            self._set_state_gauge()
            logger.info(f"serving state: {frm} -> {to}"
                        + (f" ({self._drain_reason})" if to == ServerState.DRAINING else ""))
            bb = sys.modules.get("deepspeed_tpu.blackbox")
            if bb is not None:
                degraded = to in (ServerState.DRAINING, ServerState.DEGRADED,
                                  ServerState.DEAD)
                bb.record("serving_transition",
                          "warning" if degraded else "info",
                          {"from": frm, "to": to,
                           "reason": self._drain_reason
                           if to == ServerState.DRAINING else None})
        self._write_status()

    @property
    def state(self) -> str:
        return self._state

    def install_signal_handlers(self) -> bool:
        """SIGTERM/SIGINT → graceful drain (main thread only). The handler
        only sets flags — the worker does the draining — so it is
        async-signal-safe in the Python sense."""
        def _on_signal(signum, frame):
            logger.warning(f"serving: received signal {signum} — draining")
            self.begin_drain("signal")

        try:
            signal.signal(signal.SIGTERM, _on_signal)
            signal.signal(signal.SIGINT, _on_signal)
            return True
        except ValueError:
            logger.warning("serving: cannot install signal handlers outside "
                           "the main thread; use begin_drain()/attach an agent")
            return False

    # -------------------------------------------------------------- admission
    def submit(self, prompt, max_new_tokens: int = 32,
               deadline_s: Optional[float] = None, stream=None,
               request_id: Optional[str] = None, do_sample: bool = False,
               temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
               eos_token_id: Optional[int] = None, seed: int = 0,
               is_probe: bool = False) -> Request:
        """Admit a request or raise :class:`ShedError`. Admission is where
        load shedding happens EARLY — a request whose estimated TTFT
        already blows its deadline is refused now, not decoded into a
        guaranteed timeout later.

        ``stream`` is called with each new list of tokens as a tick
        delivers it: the first call carries the first token alone (the
        prefill tick chose it), later calls up to ``decode_tick_tokens``,
        the last one cut to what is owed (or the EOS padding). Of a model
        that generates by diffusion over blocks the first call carries the
        first BLOCK's new tokens (the prefill tick denoised it): the first
        tokens that exist."""
        ids = np.asarray(prompt, dtype=np.int32)
        if ids.ndim == 1:
            ids = ids[None, :]
        if ids.ndim != 2 or ids.shape[0] != 1:
            raise ValueError(f"serving requests are single-sequence: prompt "
                             f"shape {ids.shape} (batching is the scheduler's "
                             "job, not the client's)")
        total = ids.shape[1] + int(max_new_tokens)
        max_len = int(self.engine._config.max_out_tokens)
        if total > max_len:
            raise ValueError(f"prompt {ids.shape[1]} + max_new_tokens "
                             f"{max_new_tokens} exceeds max_out_tokens {max_len}")
        deadline = float(deadline_s) if deadline_s is not None \
            else float(self.cfg.default_deadline_s)
        pkey = (bool(do_sample), float(temperature), int(top_k),
                float(top_p), eos_token_id)
        with self._lock:
            # sampling params are CLIENT-controlled jit cache keys: each
            # new combination costs a multi-second compile (serializing
            # the worker) and pins a program forever — bound them, and
            # say no with structure instead of compiling forever. The
            # bound counts compiled programs PLUS the distinct variants
            # already admitted (queued/in-flight) — a burst of unique
            # variants queued before the worker compiles any must not
            # slip past a compiled-only check.
            known = set(self._programs)
            known.update(self._program_key(r) for r in self._queue)
            if self._in_flight is not None:
                known.add(self._program_key(self._in_flight))
            if pkey not in known and \
                    len(known) >= int(self.cfg.max_program_variants):
                self._shed_count("sampling_variant_limit")
                raise ShedError("sampling_variant_limit",
                                queue_depth=len(self._queue),
                                retry_after_s=self.cfg.shed_retry_after_s)
            if self._state in (ServerState.DRAINING, ServerState.DEAD):
                self._shed_count("draining")
                raise ShedError("draining",
                                queue_depth=len(self._queue),
                                retry_after_s=self.cfg.shed_retry_after_s)
            depth = len(self._queue) + (1 if self._in_flight is not None else 0)
            if depth >= self.capacity:
                self._shed_count("queue_full")
                raise ShedError(
                    "queue_full", queue_depth=depth,
                    est_wait_s=depth * (self._service_ema or 0.0),
                    retry_after_s=self.cfg.shed_retry_after_s)
            if self._service_ema is not None:
                est_ttft = (depth + 0.5) * self._service_ema
                if est_ttft > deadline:
                    self._shed_count("deadline_unreachable")
                    raise ShedError("deadline_unreachable", queue_depth=depth,
                                    est_wait_s=est_ttft,
                                    retry_after_s=self.cfg.shed_retry_after_s)
            # breaker LAST: admits() in half-open claims the single probe
            # slot, so no later check may shed the request after it
            ok, retry_after = self.breaker.admits()
            if not ok:
                self._shed_count("circuit_open")
                raise ShedError("circuit_open", queue_depth=len(self._queue),
                                retry_after_s=retry_after)
            self._req_seq += 1
            req = Request(prompt=ids, max_new_tokens=int(max_new_tokens),
                          deadline_s=deadline,
                          id=request_id or f"req-{self._req_seq}-{uuid.uuid4().hex[:6]}",
                          stream=stream, do_sample=bool(do_sample),
                          temperature=float(temperature), top_k=int(top_k),
                          top_p=float(top_p), eos_token_id=eos_token_id,
                          seed=int(seed), is_probe=is_probe)
            req.submitted_at = time.monotonic()
            self._queue.append(req)
            self._count("admitted")
            self._set_queue_gauge()
            self._lock.notify_all()
        return req

    def probe(self, timeout: Optional[float] = 30.0) -> Request:
        """A minimal synthetic request (1 prompt token, 1 new token) —
        what half-opens an open circuit after its cooldown."""
        req = self.submit(np.zeros((1, 1), np.int32), max_new_tokens=1,
                          deadline_s=timeout, is_probe=True)
        return req.result(timeout=timeout)

    def _shed_count(self, reason: str) -> None:
        self._count("shed", labels={"reason": reason})
        bb = sys.modules.get("deepspeed_tpu.blackbox")
        if bb is not None:
            bb.record("shed", "warning", {"reason": reason})

    def _resolve_shed(self, req: Request, reason: str,
                      retry_after_s: float = 0.0) -> None:
        """Resolve an ALREADY-ADMITTED request as shed (drain/circuit-open
        empty the queue this way; clients see status='shed' + reason +
        the retry-after back-off hint). Counted as ``shed_admitted`` — a
        DIFFERENT series from the at-the-door ``shed`` refusals, so the
        ledger reconciliation `admitted == completed + timed_out + drained
        + failed + Σ shed_admitted` stays checkable from the JSONL."""
        self._count("shed_admitted", labels={"reason": reason})
        bb = sys.modules.get("deepspeed_tpu.blackbox")
        if bb is not None:
            bb.record("shed_admitted", "warning",
                      {"reason": reason, "retry_after_s": retry_after_s})
        req.retry_after_s = float(retry_after_s)
        self._resolve(req, "shed", reason)

    # ------------------------------------------------------------ breaker cb
    def _on_breaker(self, frm: str, to: str) -> None:
        # runs under the shared lock (see CircuitBreaker.__init__)
        self._count("circuit_transitions", labels={"from": frm, "to": to})
        if to == OPEN:
            while self._queue:
                self._resolve_shed(self._queue.popleft(), "circuit_open",
                                   retry_after_s=self.cfg.breaker_cooldown_s)
            self._set_queue_gauge()
            if self._state == ServerState.READY:
                self._transition(ServerState.DEGRADED)
        elif to == CLOSED and self._state == ServerState.DEGRADED:
            self._transition(ServerState.READY)

    # ----------------------------------------------------------------- drain
    @_locks.signal_safe("runs on the main thread (Python delivers signals "
                        "there); the shared serving.frontend RLock is "
                        "reentrant, so interrupting a lock-holding submit() "
                        "re-enters instead of deadlocking, and the handler "
                        "only flips flags + sheds the queue — the worker "
                        "does the actual draining")
    def begin_drain(self, reason: str = "signal") -> None:
        """Stop admission, shed the queue, deadline-cap the in-flight
        request at ``drain_grace_s``, then die. Idempotent."""
        with self._lock:
            if self._draining or self._state == ServerState.DEAD:
                return
            self._draining = True
            self._drain_reason = reason
            self._drain_deadline = time.monotonic() + float(self.cfg.drain_grace_s)
            self._transition(ServerState.DRAINING)
            while self._queue:
                self._resolve_shed(self._queue.popleft(), "draining",
                                   retry_after_s=self.cfg.shed_retry_after_s)
            self._set_queue_gauge()
            self._lock.notify_all()

    def drain(self, timeout: Optional[float] = None) -> int:
        """Wait for the drain to complete (worker exited, state dead);
        returns the exit code the process should use —
        :data:`DRAIN_EXIT_CODE` for a signal/preemption drain, 0 for a
        programmatic shutdown."""
        w = self._worker
        if w is not None:
            w.join(timeout)
        return self.exit_code

    def close(self) -> None:
        """Hard-ish stop for tests/embedding: drain with zero grace and
        stop the worker. The worker is a daemon, so a tick wedged past
        its deadline cannot block interpreter exit."""
        with self._lock:
            self.cfg = self.cfg.model_copy(update={"drain_grace_s": 0.0}) \
                if hasattr(self.cfg, "model_copy") else self.cfg
            self.begin_drain("closed")
        self._stop.set()
        with self._lock:
            self._lock.notify_all()
        w = self._worker
        if w is not None:
            w.join(timeout=5.0)

    def _poll_preempt(self) -> None:
        if self.agent is not None and getattr(self.agent, "preempted", False) \
                and not self._draining:
            logger.warning("serving: elastic agent reports preemption — draining")
            self.begin_drain("preemption")

    # ---------------------------------------------------------------- worker
    def _serve_loop(self) -> None:
        empty_since = None      # the first poll that found the queue empty
        try:
            while True:
                self._poll_preempt()
                req = None
                with self._lock:
                    if self._queue:
                        req = self._queue.popleft()
                        self._in_flight = req
                        self._set_queue_gauge()
                        popped = time.monotonic()
                    elif self._draining or self._stop.is_set():
                        break
                    else:
                        if empty_since is None:
                            empty_since = time.monotonic()
                        self._lock.wait(self.WORKER_POLL_S)
                        continue
                if empty_since is not None:
                    # ONE record a wait, however many polls it took: the
                    # callers' time, not the program's
                    _telemetry.get_tracer().record(
                        "queue_empty", empty_since, popped, cat="serving")
                    empty_since = None
                try:
                    self._process(req)
                finally:
                    with self._lock:
                        if not req.done:    # a BaseException escaped
                            # _process (SystemExit from a tick, async
                            # interrupt): the client must still get a
                            # terminal answer, not block forever
                            self._count("failed")
                            self._resolve(req, "failed", "worker_dead")
                        self._in_flight = None
                        self._set_queue_gauge()
                # outside the request's span (its client has its answer
                # already); ``request`` says whose resolution it follows
                with _telemetry.get_tracer().span("status_write",
                                                  cat="serving",
                                                  request=req.id):
                    self._write_status()
        except BaseException as e:      # noqa: BLE001 - last line of defense
            logger.error(f"serving worker died: {type(e).__name__}: {e}")
            with self._lock:
                while self._queue:
                    self._resolve_shed(self._queue.popleft(), "worker_dead")
            raise
        finally:
            with self._lock:
                if self._drain_reason in ("signal", "preemption"):
                    self.exit_code = DRAIN_EXIT_CODE
                self._transition(ServerState.DEAD)

    # ----------------------------------------------------------- the request
    def _program_key(self, req: Request) -> tuple:
        # must mirror the pkey submit() builds for the variant bound:
        # Request construction coerces each field to the same type
        return (req.do_sample, req.temperature, req.top_k, req.top_p,
                req.eos_token_id)

    def _get_programs(self, req: Request) -> tuple:
        key = self._program_key(req)
        if key not in self._programs:
            from deepspeed_tpu.inference.engine import build_serving_programs
            from deepspeed_tpu.sharding import INHERIT, sharded_jit

            eng = self.engine
            cache_sh = eng.sharding.cache_shardings(eng.module)
            pf, dc = build_serving_programs(
                eng.module,
                max_total_len=int(eng._config.max_out_tokens),
                chunk_tokens=int(self.cfg.decode_tick_tokens),
                do_sample=req.do_sample, temperature=req.temperature,
                top_k=req.top_k, top_p=req.top_p,
                eos_token_id=req.eos_token_id,
                param_transform=eng._dequant,
                cache_shardings=cache_sh)
            params_in = eng._params_in_shardings()
            cache_io = cache_sh if cache_sh is not None else INHERIT
            # serving batches are ragged (whatever requests are in flight),
            # so ids/tok/done/rng explicitly INHERIT; the KV cache — the one
            # big buffer that cycles program-to-program across ticks — is
            # pinned to the registry's placement both ways
            self._programs[key] = (
                sharded_jit(pf, label="serving/prefill", donate_argnums=(),
                            mesh=eng.mesh,
                            in_shardings=(params_in, INHERIT, INHERIT),
                            # a block step's prefill hands over its first
                            # block beside the carry
                            out_shardings=(INHERIT, cache_io, INHERIT, INHERIT)
                            + (INHERIT,) * (self._step_tokens > 1)),
                sharded_jit(dc, label="serving/decode_chunk",
                            # NO donation: a tick that dies on its deadline
                            # leaves the request's last-good cache intact for
                            # the partial-flush path — donating it here would
                            # trade that guarantee for one buffer of HBM
                            donate_argnums=(), mesh=eng.mesh,
                            in_shardings=(params_in, INHERIT, cache_io,
                                          INHERIT, INHERIT),
                            out_shardings=(INHERIT, cache_io, INHERIT,
                                           INHERIT, INHERIT)))
        return self._programs[key]

    def _tick(self, req: Request, fn, warm_key: tuple):
        """Run one device tick (prefill or a decode chunk) under a hard
        deadline. The chaos ``decode_step`` hook runs INSIDE the deadline,
        so an injected hang trips it exactly like a real device wedge.
        Raises WatchdogTimeout (tick cap / hung step) or
        _RequestDeadline (the request's own budget, drain cap).

        ``fn()`` dispatches the tick's program; where the tick before
        dispatched this chunk behind its own (``self._ahead``), that is
        what the tick waits for and ``fn`` is not called. Either way the
        tick waits for ITS program's outputs alone, after dispatching the
        chunk that follows where :meth:`_chunk_behind` allows one: the
        first token leaves when the prefill returns, whatever is queued
        behind it.

        The tick is one span (``prefill`` | ``decode``; a decode tick says
        whether its chunk was dispatched ``ahead``) from entry to return,
        tiled by three children: ``tick_launch`` (entry until whatever
        this call dispatches, its own program and the chunk behind it, has
        been dispatched in the deadline worker), ``tick_wait``
        (``block_until_ready`` of its own program; ``seq`` names the
        ``dispatch`` it blocked on) and ``tick_return`` (until this method
        returns). Inside ``tick_launch``: ``worker_start`` (entry until the
        deadline worker's first statement: the thread's spawn) and one
        ``dispatch`` for every program this call hands the device (a chunk
        the tick before sent behind its own is that tick's record). The
        worker is a new thread per tick, so it only takes stamps; the
        records are written from here, a dying tick's dispatches too."""
        import jax

        phase = str(warm_key[0])        # "prefill" | "decode"
        program = "prefill" if phase == "prefill" else "decode_chunk"
        ahead = self._ahead             # dropped by _serve if the tick dies
        tracer = _telemetry.get_tracer()
        # the worker's: entered, all dispatched, outputs ready; and what it
        # handed the device
        stamps: List[float] = []
        sent: List[_Dispatch] = []
        # request-scoped span: with the admission_wait span this lets
        # ds_metrics --serving decompose TTFT into queue-wait vs compute,
        # and a device profile show WHICH request a tick served
        try:
            with tracer.span(
                    phase, cat="serving", request=req.id,
                    # positions in the cache when the tick starts: the
                    # prompt and every token but the last, which this tick
                    # steps on (a block step has written every token it
                    # delivered; the last block's are committed by this
                    # tick's first pass)
                    context=int(req.prompt.shape[1]) + max(
                        len(req.tokens) - (self._step_tokens == 1), 0),
                    index=req.decode_ticks) as tick:
                if phase == "decode":
                    tick.args["ahead"] = ahead is not None
                now = tick.t0
                remaining = req.deadline_at - now
                if self._draining and self._drain_deadline is not None:
                    remaining = min(remaining, self._drain_deadline - now)
                if remaining <= 0:
                    raise _RequestDeadline()
                # a tick is "warm" only once its exact jit SPECIALIZATION
                # has run: prefill specializes per prompt length; the decode
                # chunk's call #1 takes prefill outputs, call #2+ its OWN
                # outputs — XLA may hand those back in another layout and
                # specialize again — so the two call positions carry
                # distinct warm keys. Until a specialization has run, the
                # startup cap applies; a compile must never read as a hang.
                cold = not self._warm.get(warm_key)
                cap = float(self.cfg.startup_tick_timeout_s) if cold \
                    else float(self.cfg.decode_tick_timeout_s)
                budget = max(0.01, min(cap, remaining))

                def run():
                    stamps.append(time.monotonic())
                    from deepspeed_tpu.resilience.chaos import active_injector

                    inj = active_injector()
                    if inj is not None and inj.targets("decode_step"):
                        inj.before("decode_step", req.id)
                    with self.engine.mesh:
                        own = ahead
                        if own is None:
                            own = _dispatch(
                                fn, program,
                                req.decode_ticks + (phase == "decode"), False)
                            sent.append(own)
                        behind = self._chunk_behind(req, phase, own.out)
                        if behind is not None:
                            sent.append(behind)
                        stamps.append(time.monotonic())
                        jax.block_until_ready(own.out)
                        stamps.append(time.monotonic())
                    return own, behind

                try:
                    # a tick bound by the REQUEST's budget (budget < cap)
                    # that expires is a deadline over healthy compute, not a
                    # hang — it must not stamp a goodput watchdog_stall span
                    own, self._ahead = run_with_deadline(
                        run, timeout=budget, name=f"serve-tick[{req.id}]",
                        stall_span=budget >= cap)
                except WatchdogTimeout:
                    if budget < cap:
                        # the request's own budget (or the drain cap) was
                        # the binding constraint: a deadline, not a hang
                        raise _RequestDeadline() from None
                    raise
                self._warm[warm_key] = self._warm.get(warm_key, 0) + 1
                # "K consecutive decode-step failures" is TICK-granular:
                # every healthy tick resets the streak (a deadline-partial
                # request full of good ticks is not evidence of a sick
                # engine), and a working tick is what closes a half-open
                # circuit
                self.breaker.record_success()
        finally:
            # whatever the worker handed the device, of a tick that died too
            for d in sent:
                tracer.record("dispatch", d.t0, d.t1, cat="serving",
                              parent=tick, request=req.id, program=d.program,
                              index=d.index, behind=d.behind, seq=d.seq)
        # decode chunks by when they were DISPATCHED: behind a program still
        # to be waited for, or by their own tick
        if self._ahead is not None:
            self._count("ticks_ahead")
        if phase == "decode" and ahead is None:
            self._count("ticks_serial")
        entered, launched, ready = stamps
        for name, t0, t1, args in (
                ("worker_start", tick.t0, entered, {}),
                ("tick_launch", tick.t0, launched, {}),
                ("tick_wait", launched, ready, {"seq": own.seq}),
                ("tick_return", ready, tick.t1, {})):
            tracer.record(name, t0, t1, cat="serving", parent=tick,
                          request=req.id, **args)
        if cold:
            req.compile_s += tick.dur
        if phase == "prefill":
            req.prefill_done_at = tick.t1
        self._reg().histogram(f"serving/{program}_seconds").observe(tick.dur)
        return own.out

    def _chunk_behind(self, req: Request, phase: str, out: tuple):
        """Dispatch the decode chunk that FOLLOWS the tick whose program
        returned ``out`` (device arrays, ready or not: the carry among them
        is the chunk's input, so the device starts it when that program
        ends) and return the dispatch (its outputs among it), or None: the
        loop is then tick by tick. Decided a tick, from what the request
        says of itself:

        - it has no EOS id, so nothing the tick in flight returns can make
          the chunk needless (``decode_chunk`` scans all its steps whatever
          ``done`` says: a chunk dispatched past an EOS would hold the
          device in front of the next request's prefill);
        - it is still owed tokens after those the tick in flight delivers;
        - the chunk's specialization has run: a compile must never sit
          inside another tick's deadline.

        What is dispatched here and never waited for (the tick died, the
        request ran out of its budget) costs at most one chunk of device
        time, counted ``serving/ticks_dropped``."""
        if req.eos_token_id is not None:
            return None
        fresh = out[4] if len(out) > 4 else out[0]      # what the tick delivers
        if len(req.tokens) + fresh.size >= req.max_new_tokens:
            return None
        if not self._warm.get(("decode", self._program_key(req),
                               int(phase == "decode"))):
            return None
        tok, cache, done, rng = out[:4]
        chunk = self._get_programs(req)[1]
        return _dispatch(
            lambda: chunk(self.engine.params, tok, cache, done, rng),
            "decode_chunk", req.decode_ticks + (phase == "decode") + 1, True)

    def _process(self, req: Request) -> None:
        tracer = _telemetry.get_tracer()
        # the request's own span, from here to its resolution; every span
        # below hangs under it and shares its ``trace`` (the request id)
        with tracer.span("request", cat="serving", trace=req.id,
                         request=req.id) as span:
            req.started_at = span.t0
            try:
                self._serve(req, tracer)
            finally:
                # a probe that ended with NO tick verdict (expired in
                # queue, drain-capped before its first tick) must hand the
                # half-open slot back, or the breaker wedges in half_open
                self.breaker.release_probe()
                # whoever reads the spans keeps no handle to the Request
                positions = self._cache_positions(req)
                if req.block_passes is not None:
                    # a model that generates by diffusion over blocks: what
                    # its block steps ran, as the programs counted it
                    from deepspeed_tpu.models.common import BLOCK_COUNTS

                    dec = self.engine.module.block_decoding
                    span.args.update(
                        zip(BLOCK_COUNTS, req.block_passes),
                        block_length=int(dec.length),
                        denoising_steps=int(dec.steps))
                span.args.update(
                    prompt_len=int(req.prompt.shape[1]),
                    new_tokens=len(req.tokens), status=req.status,
                    decode_ticks=req.decode_ticks,
                    prefill_done_at=req.prefill_done_at,
                    first_tokens_at=req.first_tokens_at,
                    # what the request's context cost the cache: positions
                    # its programs wrote (a sequence) and the bytes the
                    # cache's own arrays hold for them; beside them what a
                    # sequence keeps WHATEVER its length (a KDA layer's
                    # state)
                    cache_positions=positions,
                    cache_bytes=positions * int(req.prompt.shape[0])
                    * req.cache_position_bytes,
                    state_bytes=int(req.prompt.shape[0])
                    * req.cache_state_bytes)
                if req.cache_ring_slots:
                    # window layers that keep a ring: its bytes a sequence,
                    # whatever the length (``cache_bytes`` counts the FULL
                    # layers' rows alone), and the decode steps that
                    # overwrote a slot still inside the window
                    wraps = self._ring_wraps(req, positions)
                    self._count("ring_wraps", n=wraps)
                    span.args.update(
                        window_bytes=int(req.prompt.shape[0])
                        * req.cache_window_bytes, ring_wraps=wraps)

    def _cache_positions(self, req: Request) -> int:
        """Positions of a sequence the request's programs have written in
        the cache: the prompt and every step of every decode chunk; of a
        model whose step is a block, the prompt's whole blocks, the block
        of the prefill tick and those of every decode chunk (the last of
        them written by its passes, committed by none)."""
        if req.prefill_done_at is None:
            return 0
        n, prompt = self._step_tokens, int(req.prompt.shape[1])
        chunk = req.decode_ticks * int(self.cfg.decode_tick_tokens)
        return prompt + chunk if n == 1 else prompt - prompt % n + n + chunk

    def _ring_wraps(self, req: Request, positions: int) -> int:
        """Decode steps of the request that wrote a ring slot which held a
        position: those at positions past the ring's length."""
        prompt = int(req.prompt.shape[1])
        return max(0, positions - max(prompt, req.cache_ring_slots))

    def _positions_run(self, req: Request) -> int:
        """Positions the request's programs have put through the layers:
        those in the cache; of a block model the prompt's whole blocks and a
        block's length for every pass, denoising or committing, and for
        every block a pass carried beside its own."""
        if req.block_passes is None:
            return self._cache_positions(req)
        n, prompt = self._step_tokens, int(req.prompt.shape[1])
        passes, commits, carried, _ = req.block_passes
        return prompt - prompt % n + n * (passes + commits + carried)

    def _serve(self, req: Request, tracer) -> None:
        import jax

        from deepspeed_tpu.models.common import cache_footprint, cache_ring

        req.status = "running"
        reg = self._reg()
        wait_s = req.started_at - req.submitted_at
        reg.histogram("serving/queue_wait_seconds").observe(wait_s)
        # the first leg of the request-scoped admission_wait -> prefill ->
        # decode chain, from the two stamps the request already carries
        tracer.record("admission_wait", req.submitted_at, req.started_at,
                      cat="serving", request=req.id)
        pkey = self._program_key(req)
        try:
            prefill, decode_chunk = self._get_programs(req)
            ids = np.asarray(req.prompt, dtype=np.int32)
            # committed to the mesh, like the carry the programs hand back:
            # an uncommitted key types differently from a program's own
            # output and would compile the decode chunk a second time
            rng = jax.device_put(jax.random.PRNGKey(req.seed),
                                 self.engine.sharding.replicated())
            try:
                # (tok, cache, done, rng) and, of a block step, its first
                # block
                tok, cache, done, rng, *first = self._tick(
                    req, lambda: prefill(self.engine.params, ids, rng),
                    warm_key=("prefill", pkey, ids.shape[1]))
                # told apart by what the model's cache says of itself (the
                # names of its leaves), not by the number of dimensions
                req.cache_position_bytes, req.cache_state_bytes = \
                    cache_footprint(cache)
                req.cache_window_bytes, req.cache_ring_slots = \
                    cache_ring(cache)
                # prefill chose the first token: it leaves now, alone (of a
                # block step the first block's new tokens)
                finished, delivered = self._deliver(
                    req, first[0] if first else tok, done, tracer)
                # ONE loop: a tick waits for the chunk the tick before
                # dispatched behind its own, or dispatches its own
                # (``_tick``); ``cache`` is always the last DELIVERED
                # chunk's
                while not finished and len(req.tokens) < req.max_new_tokens:
                    self._poll_preempt()
                    tok, cache, done, rng, toks = self._tick(
                        req, lambda: decode_chunk(self.engine.params, tok,
                                                  cache, done, rng),
                        warm_key=("decode", pkey, min(req.decode_ticks, 1)))
                    req.decode_ticks += 1
                    finished, delivered = self._deliver(req, toks, done,
                                                        tracer)
            finally:
                if self._ahead is not None:
                    # dispatched behind a tick that died or a deadline:
                    # dropped unread, before the request resolves
                    self._ahead = None
                    self._count("ticks_dropped")
            self._count_block_passes(req, cache)
            self._count_expert_tokens(req, cache, tracer)
            self._observe_service(req)
            self._count("completed")
            self._resolve(req, "completed", "")
            # the last delivery -> the client has its answer: the counts
            # read back from the device, the service estimate, the resolution
            tracer.record("request_close", delivered, time.monotonic(),
                          cat="serving", request=req.id)
        except _RequestDeadline:
            # the request ran out of ITS budget; every tick that ran was
            # healthy, so the breaker hears nothing. The ledger counts by
            # terminal REASON class (completed / timed_out / drained /
            # failed / shed_admitted) — exactly one per resolution, so
            # `admitted == their sum` is checkable from the JSONL.
            reason = "drained" if self._draining else "deadline"
            if req.tokens or req.ttft_s is not None:
                self._count("drained" if self._draining else "timed_out")
                self._resolve(req, "partial", reason)
            else:
                # expired before producing anything — a late shed, honest
                # about the fact that no work reached the client
                self._resolve_shed(req, reason,
                                   retry_after_s=self.cfg.shed_retry_after_s)
        except WatchdogTimeout as e:
            # a tick blew its cap with request budget left: the ENGINE
            # hung, not the request — breaker counts it
            self.breaker.record_failure()
            self._count("timed_out")
            logger.error(f"serving: hung tick on {req.id}: {e}")
            self._resolve(req, "partial" if req.tokens else "failed", "timeout")
        except Exception as e:      # noqa: BLE001 - resolved, never dropped
            self.breaker.record_failure()
            self._count("failed")
            # the server keeps serving (this is its boundary) but the
            # failure keeps its traceback; a caller that must not pass on a
            # dead engine checks req.status — chip_smoke.py does
            logger.error(f"serving: request {req.id} failed: "
                         f"{type(e).__name__}: {e}", exc_info=True)
            self._resolve(req, "partial" if req.tokens else "failed",
                          f"error: {type(e).__name__}: {e}")

    def _deliver(self, req: Request, toks, done, tracer) -> tuple:
        """A tick's new tokens come to the host and go to the client, cut
        to what the request is still owed: the one token of the prefill
        tick (a block step's first block), up to ``decode_tick_tokens`` of
        a decode tick. -> (whether every row has passed its EOS: the rest is
        then padded with it and no further tick runs; when the delivery
        ended)."""
        with tracer.span("deliver", cat="serving", request=req.id) as span:
            fresh = np.asarray(toks).reshape(-1).tolist()
            fresh = fresh[:req.max_new_tokens - len(req.tokens)]
            req.tokens.extend(fresh)
            self._count("tokens_streamed", n=len(fresh))
            if req.first_tokens_at is None:
                req.first_tokens_at = time.monotonic()
                req.ttft_s = req.first_tokens_at - req.submitted_at
                reg = self._reg()
                reg.histogram("serving/ttft_seconds").observe(req.ttft_s)
                reg.histogram("serving/ttft_deadline_fraction").observe(
                    req.ttft_s / req.deadline_s)
            self._flush_stream(req, fresh)
            finished = bool(np.asarray(done).all())
            if finished:
                # parity with generate(): post-EOS positions hold EOS
                eos = max(int(req.eos_token_id or 0), 0)
                pad = [eos] * (req.max_new_tokens - len(req.tokens))
                req.tokens.extend(pad)
                self._flush_stream(req, pad)
        return finished, span.t1

    def _count_block_passes(self, req: Request, cache) -> None:
        """A block-diffusion model's programs sum, in the cache they hand
        from tick to tick, what their block steps ran (``block_passes``, in
        ``BLOCK_COUNTS``' order): forward passes that denoise, forward
        passes that ONLY commit (none: a served block's commit is carried),
        blocks whose commit rode in a later block's first pass, blocks
        finished. One read when the request has its tokens, as
        ``expert_tokens``: onto the request (its span's ``passes`` /
        ``commits`` / ``carried`` / ``blocks``) and into the counters
        ``serving/passes`` (forward passes of either kind),
        ``serving/carried`` and ``serving/blocks``."""
        ran = cache.get("block_passes") if isinstance(cache, dict) else None
        if ran is None:
            return
        req.block_passes = tuple(int(v) for v in np.asarray(ran))
        passes, commits, carried, blocks = req.block_passes
        self._count("passes", n=passes + commits)
        self._count("carried", n=carried)
        self._count("blocks", n=blocks)

    def _count_expert_tokens(self, req: Request, cache, tracer) -> None:
        """A routed (MoE) model's programs sum, in the cache they hand from
        tick to tick, the (token, expert) pairs every expert HELD here was
        given in every routed layer (``expert_tokens`` (L, E held)). One
        read when the request has its tokens, not one a tick: into the
        counter ``moe/expert_tokens`` and, whole, into an instant of that
        name in the tracer, with the share the counts are of (``held_first``
        of the router's experts, ``held`` of them) and ``routed_pairs``: all
        the pairs the routers made of the positions run, held here or not."""
        routed = cache.get("expert_tokens") if isinstance(cache, dict) else None
        if routed is None:
            return
        counts = np.asarray(routed)
        config = getattr(self.engine.module, "config", None)
        held = getattr(config, "experts_held", None) or (0, counts.shape[-1])
        pairs = counts.shape[0] * int(req.prompt.shape[0]) \
            * self._positions_run(req) * getattr(config, "n_experts_per_tok", 0)
        self._reg().counter("moe/expert_tokens").inc(float(counts.sum()))
        tracer.instant("moe/expert_tokens", cat="moe", trace=req.id,
                       request=req.id, counts=counts.tolist(),
                       held_first=int(held[0]), held=int(held[1]),
                       routed_pairs=int(pairs or counts.sum()))

    def _flush_stream(self, req: Request, toks: List[int]) -> None:
        if req.stream is None or not toks:
            return
        try:
            req.stream(list(toks))
        except Exception as e:      # a slow/broken consumer must not kill serving
            logger.warning(f"serving: stream consumer for {req.id} raised: {e}")

    def _observe_service(self, req: Request) -> None:
        # admission estimates the wait from what a request's SERVICE takes.
        # A tick that compiled its program says what a compile takes (a 20 s
        # compile read as load sheds the next request of a cold start as
        # "deadline_unreachable"), so such a tick is left out WHOLE — its
        # run cannot be told from its compile — and the rest of the request
        # still feeds the estimate: a server whose every prompt length is
        # new estimates low by a prefill's run, never by nothing
        dur = time.monotonic() - req.started_at - req.compile_s
        self._service_ema = dur if self._service_ema is None \
            else 0.8 * self._service_ema + 0.2 * dur
        reg = self._reg()
        reg.histogram("serving/request_seconds").observe(
            time.monotonic() - req.submitted_at)
        reg.histogram("serving/tokens_per_request").observe(len(req.tokens))

    def _resolve(self, req: Request, status: str, reason: str) -> None:
        # no status-file write here: resolutions can happen in bulk under
        # the admission lock (a drain shedding the whole queue) — the
        # worker writes once per served request, transitions once each
        req.status = status
        req.reason = reason
        req.finished_at = time.monotonic()
        req._done.set()

    # ---------------------------------------------------------------- status
    def status(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "state": self._state,
                "queue_depth": len(self._queue),
                "in_flight": self._in_flight.id if self._in_flight else None,
                "capacity": self.capacity,
                "capacity_detail": dict(self.capacity_detail),
                "breaker": self.breaker.state,
                "draining": self._draining,
                "drain_reason": self._drain_reason,
                "service_ema_s": self._service_ema,
                "counts": dict(self.counts),
            }

    def _status_path(self) -> Optional[str]:
        if self._status_dir:
            return os.path.join(self._status_dir, STATUS_FILE)
        s = _telemetry.get_session()
        if s is not None:
            return os.path.join(s.output_dir, STATUS_FILE)
        return None

    def _write_status(self) -> None:
        path = self._status_path()
        if path is None:
            return
        try:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            # per-thread tmp name: resolver and drainer may write concurrently
            tmp = f"{path}.{threading.get_ident()}.tmp"
            with open(tmp, "w") as f:
                json.dump(self.status(), f, indent=1, sort_keys=True)
            os.replace(tmp, path)       # atomic: status readers never see a torn file
        except OSError as e:
            logger.warning(f"serving: status write failed: {e}")


class _RequestDeadline(Exception):
    """Internal: the request's own deadline (or the drain cap) expired —
    distinct from WatchdogTimeout so a deadline-bound request is not
    mistaken for a hung engine (no breaker failure, no timeout counter)."""


def from_ds_config(engine, ds_config, agent=None, start: bool = True,
                   status_dir: Optional[str] = None) -> Optional[ServingFrontEnd]:
    """Build a front-end from a parsed ``DeepSpeedConfig``. Returns None
    when the ``serving`` block is absent or disabled — note the STRICT
    no-op contract lives one level up: code that has no serving block
    must never import this package at all."""
    if not getattr(ds_config, "serving_present", False) \
            or not ds_config.serving.enabled:
        return None
    if ds_config.telemetry.enabled and _telemetry.get_session() is None:
        _telemetry.configure(ds_config.telemetry)
    return ServingFrontEnd(engine, ds_config.serving, agent=agent,
                           start=start, status_dir=status_dir)
