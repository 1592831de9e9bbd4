"""Host spans and counters, recorded from the benchmark's own files.

Spans are kept in memory (name, start, end, args on ``time.monotonic``)
and, when a device trace is being taken, also written into the profiler's
own trace as ``jax.profiler.TraceAnnotation`` so that the trace reduction
can say what the host was doing in each idle gap of the device. With
tracing off a span costs two clock reads and a list append.
"""

import contextlib
import threading
import time


class Recorder:
    def __init__(self, annotate=False):
        self.annotate = annotate
        self.spans = []          # (name, t0, t1, args)
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name, **args):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench/{name}", **args)
            ann.__enter__()
        t0 = time.monotonic()
        try:
            yield
        finally:
            t1 = time.monotonic()
            if ann is not None:
                ann.__exit__(None, None, None)
            with self._lock:
                self.spans.append((name, t0, t1, args))

    def named(self, name, lo=None, hi=None):
        """Spans called ``name`` that lie wholly inside [lo, hi]."""
        return [s for s in self.spans if s[0] == name
                and (lo is None or s[1] >= lo) and (hi is None or s[2] <= hi)]


class CompileCounter:
    """Counts what jax compiles or loads: one event per program that goes
    through the compiler's door, cache hit or miss (``backend_compile`` is
    timed around both). ``in_window`` must come out 0."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.events = []         # (monotonic time, seconds, event name)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in (self.EVENT, self.LOWER):
            self.events.append((time.monotonic(), float(secs), name))

    def count(self, lo, hi):
        return sum(1 for t, _, n in self.events
                   if n == self.EVENT and lo <= t <= hi)

    def seconds(self, lo, hi):
        return sum(s for t, s, _ in self.events if lo <= t <= hi)
