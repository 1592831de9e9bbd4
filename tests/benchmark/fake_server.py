"""A fake serial server for the driver tests: FIFO, one request at a time,
fixed service times, on a virtual or a real clock. It has the surface the
drivers use of a serve system (``submit``, ``warm``, ``vocab``) and of a
request handle (``result``, ``status``, ``tokens``, ``submitted_at``,
``started_at``)."""

import collections
import threading
import time

TICK_TOKENS = 16
TICK_S = 16 * 6.93e-3           # PR 22's tpot_p50_s on the chip, per tick


def prefill_s(prompt_len):
    return 0.004 + 2.7e-5 * prompt_len      # 768 tokens -> 24.7 ms


def service_s(prompt_len, new_tokens):
    return prefill_s(prompt_len) + -(-new_tokens // TICK_TOKENS) * TICK_S


class VirtualClock:
    """Time that moves only when the server serves: a 51 s window costs
    milliseconds, and nothing depends on the machine's load."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


class RealClock:
    def __call__(self):
        return time.monotonic()

    def advance(self, dt):
        time.sleep(dt)


class Handle:
    def __init__(self, prompt_len, new_tokens, stream, now):
        self.prompt_len, self.new_tokens, self.stream = prompt_len, new_tokens, stream
        self.status, self.tokens = "queued", []
        self.submitted_at, self.started_at = now, None
        self._done = threading.Event()

    def result(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError("fake request not resolved")
        return self


class FakeSerialServer:
    """``n_callers``: with a closed loop on a virtual clock the server takes
    the next request only once every caller that is still alive has one
    outstanding, and takes those submitted at the same virtual instant in
    the order of their threads' names (the drivers name a caller's thread
    after it: the first round is A, B, C, D whichever thread the machine ran
    first). So the order is the round-robin a real FIFO gives, whatever the
    machine's load; a caller whose thread has ended (the drain at the
    window's end) is no longer waited for. ``refuse_every``: every n-th submission raises, like
    a shed."""

    vocab = 50257
    tick_tokens = TICK_TOKENS

    def __init__(self, clock, n_callers=None, refuse_every=0,
                 flaky_sentinel=False):
        self.clock, self.n_callers = clock, n_callers
        self.refuse_every, self.flaky_sentinel = refuse_every, flaky_sentinel
        self._callers = set()
        self._q = collections.deque()
        self._cv = threading.Condition()
        self._outstanding = 0
        self._submissions = 0
        self._stop = False
        self._worker = threading.Thread(target=self._serve, daemon=True)
        self._worker.start()

    def warm(self, prompt_len, new_tokens):
        pass

    def submit(self, prompt, new_tokens, stream):
        with self._cv:
            self._callers.add(threading.current_thread())
            self._submissions += 1
            if self.refuse_every and self._submissions % self.refuse_every == 0:
                raise RuntimeError("request shed (fake)")
            h = Handle(len(prompt), int(new_tokens), stream, self.clock())
            h.first_id = int(prompt[0])
            h.order = (h.submitted_at, threading.current_thread().name)
            self._q.append(h)
            self._outstanding += 1
            self._cv.notify_all()
        return h

    def close(self):
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._worker.join(5.0)

    def _next(self):
        with self._cv:
            while not self._stop:
                if self._q:
                    if self.n_callers is None:
                        return self._q.popleft()
                    gone = sum(not t.is_alive() for t in self._callers)
                    if self._outstanding >= self.n_callers - gone:
                        first = min(self._q, key=lambda h: h.order)
                        self._q.remove(first)
                        return first
                self._cv.wait(0.001)
        return None

    def _serve(self):
        n_served = 0
        while True:
            h = self._next()
            if h is None:
                return
            h.started_at = self.clock()
            h.status = "running"
            self.clock.advance(prefill_s(h.prompt_len))
            left = h.new_tokens
            n_served += 1
            while left > 0:
                self.clock.advance(TICK_S)
                n = min(TICK_TOKENS, left)
                # deterministic "tokens": a function of the prompt alone
                base = h.first_id + len(h.tokens)
                if self.flaky_sentinel and n_served % 7 == 0:
                    base += 1
                fresh = [(base + i) % self.vocab for i in range(n)]
                h.tokens.extend(fresh)
                left -= n
                if h.stream is not None:
                    h.stream(fresh)
            h.status = "completed"
            with self._cv:
                self._outstanding -= 1
            h._done.set()
