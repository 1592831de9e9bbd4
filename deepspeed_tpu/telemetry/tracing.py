"""The span recorder: host-visible phases on the clocks everything else uses.

A :class:`Span` is ``name``, ``cat``, ``t0``/``t1`` in ABSOLUTE
``time.monotonic()`` seconds (the clock of ``Request.submitted_at`` and of
any harness around the program), a process-unique ``id``, the ``parent``
id (the span that was open on this thread — or on the thread that started
this one's work — when it began), a ``trace`` identifier shared by every
span of one request (its id) or one train step (its number), and small
``args``. Spans mark the layer boundaries: data / train_batch / dispatch /
wait / post_step in the train engine; in the serving front-end request /
admission_wait / prefill / decode / deliver / request_close, under a tick
worker_start / dispatch / tick_launch / tick_wait / tick_return, and
beside a request status_write / queue_empty; checkpoint save/load,
``door_compile`` at ``sharded_jit``. Where the work leaves the host it is
a record of its own: a serving ``dispatch`` is one call that hands the
device a program (``program``, ``index``, ``behind``, ``seq`` = its place in
the process's dispatch order) and a ``tick_wait`` names the ``seq`` it
blocked on, so a reader of a device profile pairs executions with
dispatches one for one. What an operator reads from them: a ``behind``
share of the decode chunks under 100% is a loop gone serial, a rising
``request_close`` a slow read-back of the counts, ``queue_empty`` the
callers' time and not the program's.

Two places hold them. With no ``telemetry`` session the process-wide
:data:`RING` keeps the last :data:`RING_SPANS`; a session with ``trace:
true`` owns a :class:`StepTracer` that keeps the first ``max_trace_events``
(counting what it drops) and writes them as Chrome Trace Event JSON
(``trace.json``: complete events ``ph="X"``, microsecond ``ts``/``dur``
since the tracer's start; ``chrome://tracing`` and https://ui.perfetto.dev
open it). ``telemetry.get_tracer()`` hands out whichever is live.

Every ``with tracer.span(...)`` also enters a
``jax.profiler.TraceAnnotation("ds/<cat>/<name>")``: with no profile being
taken that is a flag test in the runtime; inside ``jax.profiler.trace(dir)``
the span sits on the host plane of the ``.xplane.pb``, on the device's
clock, over the device ops it launched.
"""

from __future__ import annotations

import collections
import contextvars
import itertools
import json
import os
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from deepspeed_tpu.utils.logging import logger

# The ring holds four times the spans of the longest benchmark cell's whole
# run (gpt2-xl.serve.chat.c4: ~750 ticks a 30 s window x ~8 spans a tick +
# ~45 requests x ~7, and a sixth of that again in set-up and drain: ~7,500)
# and twice those of a CPU rehearsal of a serve cell, whose toy model ticks
# a thousand times a second (tests/benchmark: ~16,000 a run).
RING_SPANS = 32_768

_ids = itertools.count(1)
_profiling = TraceAnnotation.is_enabled
# the span open on this thread; run_with_deadline copies the context into
# its worker, so what a tick's worker records hangs under the tick
_current: contextvars.ContextVar = contextvars.ContextVar("ds_span",
                                                         default=None)


class Span:
    """One record, and the context manager that makes it. ``t1`` is None
    for an instant."""

    __slots__ = ("name", "cat", "t0", "t1", "id", "parent", "trace", "args",
                 "_tracer", "_ann", "_token")

    def __init__(self, tracer, name, cat, trace, args, t0=None, t1=None,
                 parent=None):
        above = _current.get() if parent is None else parent
        self.name, self.cat, self.args = name, cat, args
        self.t0, self.t1 = t0, t1
        self.id = next(_ids)
        self.parent = None if above is None else above.id
        self.trace = trace if trace is not None or above is None \
            else above.trace
        self._tracer = tracer

    def __enter__(self):
        # the flag test is all a span pays while no profile is being taken
        # (a profile that starts inside a span misses that one span)
        self._ann = None
        if _profiling():
            self._ann = TraceAnnotation(f"ds/{self.cat}/{self.name}",
                                        trace=self.trace, **self.args)
            self._ann.__enter__()
        self._token = _current.set(self)
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic()
        _current.reset(self._token)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._ann = self._token = None
        self._tracer._emit(self)
        return False

    @property
    def dur(self) -> float:
        return 0.0 if self.t1 is None else self.t1 - self.t0

    def __repr__(self):
        return (f"Span({self.cat}/{self.name} id={self.id} "
                f"parent={self.parent} trace={self.trace!r} "
                f"t0={self.t0} dur={self.dur:.6f} {self.args})")


class StepTracer:
    """Collects spans. A session's tracer is bounded by ``max_events``
    (overflow is counted — surfaced in the trace metadata and a one-shot
    warning — and never grows memory without bound on a long run); with
    ``ring=True`` it keeps the LAST ``max_events`` instead and drops
    nothing it has to report."""

    def __init__(self, max_events: int = 100_000, pid: int = 0,
                 ring: bool = False):
        # monotonic+epoch clock anchor, captured back-to-back: Chrome ``ts``
        # values are µs since _t0, and epoch0 places that zero on wall
        # time — how ds_prof goodput stitches sessions across elastic
        # restarts, and how merged Perfetto timelines get absolute time
        self._t0 = time.monotonic()
        self.epoch0 = time.time()
        self.pid = int(pid)
        self.max_events = int(max_events)
        self.ring = bool(ring)
        self.spans = collections.deque(maxlen=self.max_events) if ring else []
        self.dropped = 0
        self._chrome: List[dict] = []   # Chrome form of spans[:len(_chrome)]
        self._written_state = None      # (len(spans), dropped) at last write

    def _emit(self, span: Span) -> None:
        if not self.ring and len(self.spans) >= self.max_events:
            if self.dropped == 0:
                # once, loudly: a silently truncated trace reads as "the
                # run got quiet at step N" — the worst kind of wrong
                logger.warning(
                    f"StepTracer hit max_events={self.max_events}; further "
                    "spans are counted but not recorded (dropped-event count "
                    "lands in the trace metadata; raise "
                    "telemetry.max_trace_events to keep them)")
            self.dropped += 1
            return
        self.spans.append(span)

    # ------------------------------------------------------------ recording
    def span(self, name: str, cat: str = "train", trace=None, **args) -> Span:
        """``with tracer.span("fwd", step=3) as s: ...`` — records one span
        covering the block (exceptions still close it). ``trace`` defaults
        to the enclosing span's; ``s.args`` may be added to inside."""
        return Span(self, name, cat, trace, args)

    def record(self, name: str, t0: float, t1: float, cat: str = "train",
               trace=None, parent: Optional[Span] = None, **args) -> Span:
        """A span whose two ``time.monotonic()`` stamps the caller took
        itself (on another thread, or before it knew the name), under
        ``parent`` if given, else under the span open here. It is in this
        tracer, not in a device profile."""
        span = Span(self, name, cat, trace, args, t0, t1, parent)
        self._emit(span)
        return span

    def instant(self, name: str, cat: str = "train", trace=None,
                **args) -> None:
        self._emit(Span(self, name, cat, trace, args, time.monotonic()))

    def complete(self, name: str, dur_us: float, cat: str = "train",
                 **args) -> None:
        """Record a complete span ending NOW with the given duration —
        for callers that already measured the interval themselves (the
        comm layer's ``timed_op`` wraps the block+sync it times)."""
        end = time.monotonic()
        self.record(name, end - float(dur_us) * 1e-6, end, cat=cat, **args)

    # -------------------------------------------------------------- reading
    def snapshot(self) -> List[Span]:
        """The spans held now, oldest first."""
        return list(self.spans)

    @property
    def wrapped(self) -> bool:
        """True once the ring may have overwritten its oldest spans."""
        return self.ring and len(self.spans) >= self.max_events

    def _event(self, s: Span) -> dict:
        ev: Dict[str, Any] = {
            "name": s.name, "cat": s.cat, "ts": (s.t0 - self._t0) * 1e6,
            "pid": self.pid, "tid": 0,
            "args": {**s.args, "id": s.id, "parent": s.parent,
                     "trace": s.trace}}
        if s.t1 is None:
            ev.update(ph="i", s="p")
        else:
            ev.update(ph="X", dur=(s.t1 - s.t0) * 1e6)
        return ev

    @property
    def events(self) -> List[dict]:
        """The spans as Chrome trace events (what ``goodput``, ``perf`` and
        the incident bundle read). A session's list only grows, so each
        span is converted once; the ring is converted whole per read."""
        if self.ring:
            return [self._event(s) for s in self.snapshot()]
        for s in self.spans[len(self._chrome):]:
            self._chrome.append(self._event(s))
        return self._chrome

    def to_chrome_trace(self) -> dict:
        meta = [{"name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
                 "args": {"name": f"deepspeed_tpu rank {self.pid}"}}]
        return {"traceEvents": meta + self.events, "displayTimeUnit": "ms",
                "metadata": {"rank": self.pid, "max_events": self.max_events,
                             "dropped_events": self.dropped,
                             "clock_anchor": {"epoch_s": self.epoch0,
                                              "monotonic_s": self._t0}}}

    def write(self, path: str) -> None:
        """Atomic dump (tmp + replace): a reader mid-run never sees a
        half-written JSON. No-op when nothing changed since the last write —
        the whole-file dump is O(spans so far) and a flush with no new data
        should cost nothing. The FIRST drop counts as a change (so the
        metadata's truncation flag reaches disk), but later drop-count
        bumps do not: past the cap only `dropped` moves, and re-serializing
        the full capped buffer every flush just to update one integer is
        the exact cost this guard exists to avoid — the on-disk count is
        'dropped as of the first post-cap flush', the in-memory counter
        stays exact."""
        state = (len(self.spans), self.dropped > 0)
        if state == self._written_state:
            return
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        os.replace(tmp, path)
        self._written_state = state


# What ``telemetry.get_tracer()`` hands out when no session traces: the
# harness that measures the program has no place to arm one, and the
# operator who meets a slow state wants the last spans without having
# predicted the need.
RING = StepTracer(max_events=RING_SPANS, ring=True)
