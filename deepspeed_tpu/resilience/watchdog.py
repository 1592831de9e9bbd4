"""Distributed watchdog: hang detection and clean abort instead of wedged jobs.

At multi-host scale the dominant failure mode is not a crash but a *wedge*:
one rank stalls inside a collective, every other rank blocks with it, and
the job burns TPU-hours silently (the reference exposes
``monitored_barrier`` timeouts and an elastic agent for exactly this;
"The Big Send-off" in PAPERS.md makes the same point — one stuck rank gates
every collective). This module is the live defense:

* :class:`StepWatchdog` — an arm/disarm deadline around each engine step.
  The deadline adapts (``factor`` × a moving percentile of recent step
  times, floored at ``min_timeout``) so a recompile or a slow first step
  doesn't false-positive. On expiry the stacks of EVERY thread are dumped
  via :mod:`faulthandler`, ``resilience/watchdog_timeouts`` is counted, and
  :class:`WatchdogTimeout` is raised *inside the armed thread* (delivered
  between bytecodes — it interrupts host-side stalls; a wedge inside a C
  call cannot be unblocked, only reported, so ``on_timeout="kill"``
  escalates to SIGABRT for supervised deployments where the launcher
  restarts the job).
* :func:`run_with_deadline` — a one-shot deadline around a blocking call
  (``comm.monitored_barrier`` uses it): the call runs in a disposable
  worker thread, the caller waits with a timeout and gets a clean
  :class:`WatchdogTimeout` back while the wedged worker is disowned.
* :func:`touch_heartbeat` — the engine touches a heartbeat file each step;
  the launcher's supervision loop kills the process group when it goes
  stale (the defense of last resort: it works even when every Python
  thread is wedged under a C call).

Everything here is a strict no-op unless the ``watchdog`` ds_config block
is enabled (the engine creates no :class:`StepWatchdog`, starts no thread,
and writes no heartbeat without it).
"""

from __future__ import annotations

import contextvars
import faulthandler
import math
import os
import signal
import sys
import threading
import time
from collections import deque
from typing import Callable, Optional

from deepspeed_tpu.utils import locks as _locks
from deepspeed_tpu.utils.logging import logger


class WatchdogTimeout(RuntimeError):
    """A watched operation (step, barrier) blew its deadline. Restartable:
    the elastic agent treats it like any step failure (restart from the
    last verified checkpoint); the launcher's heartbeat supervision is the
    fallback when even this exception cannot be delivered."""


_default_dump_path: Optional[str] = None
_default_dump_path_source: Optional[str] = None


def set_default_dump_path(path: Optional[str], source: str = "manual") -> None:
    """Default file for stack dumps whose call site has no explicit path —
    the engine installs ``watchdog.stack_dump_file`` here (``source=
    "config"``) so barrier and startup-fingerprint timeouts land in the
    same file as step timeouts. Source-tracked like the barrier default:
    an engine without the block clears only config installs."""
    global _default_dump_path, _default_dump_path_source
    _default_dump_path = path or None
    _default_dump_path_source = None if not path else source


def clear_config_dump_path() -> None:
    """Remove only a CONFIG-installed dump path (engine init with the
    watchdog block absent); manual installs are deliberately left alone."""
    global _default_dump_path, _default_dump_path_source
    if _default_dump_path_source == "config":
        _default_dump_path = None
        _default_dump_path_source = None


def dump_all_stacks(path: Optional[str] = None, reason: str = "",
                    to_stderr: bool = True) -> None:
    """faulthandler dump of every thread's stack — to ``path`` (appended,
    so repeated dumps of one incident stay together; defaults to the
    engine-installed ``stack_dump_file``) plus stderr (suppressible with
    ``to_stderr=False`` for callers whose signal path already produced a
    stderr dump). Never raises: the dump is diagnostic garnish on an
    abort already underway."""
    path = path or _default_dump_path
    banner = f"\n==== watchdog stack dump ({reason or 'requested'}) ====\n"
    # a live wedge names its holder: which instrumented lock is held, by
    # which thread, since when — the stack dump says where threads ARE,
    # this says what they are waiting FOR
    try:
        holders = _locks.format_lock_holders() + "\n"
    except Exception as e:  # pragma: no cover - diagnostic path
        holders = f"lock holders: unavailable ({e})\n"
    if to_stderr:
        try:
            sys.stderr.write(banner)
            sys.stderr.flush()
            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
            sys.stderr.write(holders)
            sys.stderr.flush()
        except Exception as e:  # pragma: no cover - diagnostic path
            logger.warning(f"watchdog: stderr stack dump failed: {e}")
    if path:
        try:
            with open(path, "a") as f:
                f.write(banner)
                f.flush()
                faulthandler.dump_traceback(file=f, all_threads=True)
                f.write(holders)
        except Exception as e:  # pragma: no cover - diagnostic path
            logger.warning(f"watchdog: stack dump to {path} failed: {e}")


def _async_raise(tid: int, message: str) -> bool:
    """Deliver WatchdogTimeout into thread ``tid``. CPython delivers async
    exceptions between bytecodes — this interrupts Python-level stalls
    (sleep loops, host-side spins) but NOT a thread wedged inside one C
    call; the launcher heartbeat covers that case."""
    import ctypes

    # the class is instantiated at delivery time with no args, so carry the
    # message in a throwaway subclass (isinstance(WatchdogTimeout) holds)
    exc = type("WatchdogTimeout", (WatchdogTimeout,),
               {"__init__": lambda self: WatchdogTimeout.__init__(self, message)})
    n = ctypes.pythonapi.PyThreadState_SetAsyncExc(
        ctypes.c_ulong(tid), ctypes.py_object(exc))
    if n > 1:  # pragma: no cover - CPython contract violation; undo
        ctypes.pythonapi.PyThreadState_SetAsyncExc(ctypes.c_ulong(tid), None)
        return False
    return n == 1


def _cancel_async_exc(tid: int) -> None:
    """Clear a pending (not-yet-delivered) async exception on ``tid`` —
    NULL exc cancels, per the CPython contract."""
    import ctypes

    ctypes.pythonapi.PyThreadState_SetAsyncExc(ctypes.c_ulong(tid), None)


def _count_timeout(kind: str, stall_s: Optional[float] = None) -> None:
    from deepspeed_tpu import telemetry

    telemetry.get_registry().counter(
        "resilience/watchdog_timeouts", labels={"kind": kind}).inc()
    telemetry.get_tracer().instant("watchdog_timeout", cat="resilience",
                                   kind=kind)
    _bb = sys.modules.get("deepspeed_tpu.blackbox")
    if _bb is not None:
        _bb.record("watchdog_timeout", "error",
                   {"kind": kind, "stall_s": stall_s})
    if stall_s is not None and stall_s > 0:
        # the stall itself as a complete span ending NOW: the goodput
        # ledger charges this window to `watchdog_stall` instead of
        # letting a wedged step masquerade as compute
        telemetry.get_tracer().complete("watchdog_stall", stall_s * 1e6,
                                        cat="stall", kind=kind)


def run_with_deadline(fn: Callable, timeout: float, name: str = "op",
                      dump_path: Optional[str] = None,
                      on_timeout_info: Optional[Callable[[], str]] = None,
                      stall_span: bool = True):
    """Run ``fn()`` under a hard deadline; return its value or re-raise its
    exception. On expiry: all-thread stack dump, ``watchdog_timeouts``
    counter, and a clean :class:`WatchdogTimeout` in the CALLER — the
    wedged worker thread cannot be cancelled, only disowned (daemon), which
    is the point: the caller gets control back instead of blocking forever.
    ``on_timeout_info()`` (e.g. the barrier's missing-rank roster) is
    appended to the message. ``stall_span=False`` suppresses the goodput
    ``watchdog_stall`` span on expiry — for callers whose deadline is a
    REQUEST budget, not a hang detector (the serving tick loop): a
    routine SLO miss over healthy compute must not read as a wedged
    engine in the time ledger."""
    if timeout is None or timeout <= 0:
        raise ValueError(f"run_with_deadline({name!r}): timeout must be positive, got {timeout!r}")
    result: dict = {}
    done = threading.Event()
    # the caller's context variables (the tracer's open span) go with the
    # work, so what ``fn`` records hangs under the span that asked for it
    ctx = contextvars.copy_context()

    def worker():
        try:
            result["value"] = ctx.run(fn)
        except BaseException as e:  # noqa: BLE001 - re-raised in the caller
            result["error"] = e
        finally:
            done.set()

    # expect_join=False: a worker wedged past its deadline is DISOWNED by
    # design — the leak sentinel must not count it against teardown
    t = _locks.spawn_thread(worker, name=f"ds-deadline-{name}",
                            owner="watchdog", daemon=True, expect_join=False)
    t.start()
    if not done.wait(timeout):
        _count_timeout("deadline", stall_s=timeout if stall_span else None)
        extra = ""
        if on_timeout_info is not None:
            try:
                extra = on_timeout_info()
            except Exception as e:  # info is garnish, never mask the timeout
                extra = f" (timeout-info callback failed: {e})"
        msg = f"watchdog: {name} did not complete within {timeout:.1f}s{extra}"
        logger.error(msg)
        dump_all_stacks(dump_path, reason=msg)
        raise WatchdogTimeout(msg)
    if "error" in result:
        raise result["error"]
    return result.get("value")


class StepWatchdog:
    """Arm/disarm deadline around engine steps, fired by one daemon monitor
    thread (started lazily on the first :meth:`arm` — a constructed-but-
    never-armed watchdog owns no thread).

    Deadline policy: ``max(min_timeout, factor × P(percentile) of the last
    ``window`` step durations)``; with no history yet (the first step
    compiles) the much larger ``startup_timeout`` applies. A recompile
    mid-run is covered by ``min_timeout`` — set it above your compile time.

    On expiry: stacks dumped, ``resilience/watchdog_timeouts`` counted, then
    ``on_timeout``: ``"raise"`` delivers :class:`WatchdogTimeout` into the
    armed thread (interrupts Python-level stalls; the elastic agent
    restarts from the last verified checkpoint), ``"kill"`` SIGABRTs the
    process (faulthandler prints stacks on the way out — for supervised
    multi-host jobs where one controller cannot restart in-process anyway).
    """

    POLL_S = 0.05           # monitor wake quantum = detection slack

    def __init__(self, factor: float = 3.0, percentile: float = 0.95,
                 window: int = 32, min_timeout: float = 60.0,
                 startup_timeout: float = 600.0, on_timeout: str = "raise",
                 dump_path: Optional[str] = None, name: str = "step"):
        if on_timeout not in ("raise", "kill"):
            raise ValueError(f"watchdog on_timeout must be 'raise' or 'kill', got {on_timeout!r}")
        if factor <= 0 or not (0.0 < percentile <= 1.0):
            raise ValueError("watchdog factor must be > 0 and percentile in (0, 1]")
        self.factor = float(factor)
        self.percentile = float(percentile)
        self.min_timeout = float(min_timeout)
        self.startup_timeout = float(startup_timeout)
        self.on_timeout = on_timeout
        self.dump_path = dump_path
        self.name = name
        self.trips = 0
        self.last_trip_reason = ""
        self._durations: deque = deque(maxlen=int(window))
        self._lock = _locks.make_lock("watchdog.step")
        self._armed_tid: Optional[int] = None
        self._armed_at = 0.0
        self._deadline = 0.0
        # arm-generation handshake closing the fire/disarm race: the monitor
        # records which arm it fired for, disarm cancels a fire for the
        # CURRENT generation whose exception has not been delivered yet — a
        # timeout landing in unrelated later code (the next step, a
        # checkpoint write) would be worse than the late step it targeted
        self._gen = 0
        self._fired_gen = -1
        self._cancel_gen = -1
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------- policy
    def observe(self, duration: float) -> None:
        """Feed a step duration without arm/disarm (tests, external timers)."""
        with self._lock:
            self._durations.append(float(duration))

    def deadline_s(self) -> float:
        """The deadline the next arm() would use."""
        with self._lock:
            durs = sorted(self._durations)
        if not durs:
            return self.startup_timeout
        idx = min(len(durs) - 1,
                  max(0, int(math.ceil(self.percentile * len(durs))) - 1))
        return max(self.min_timeout, self.factor * durs[idx])

    # ------------------------------------------------------------ arm/disarm
    def arm(self, timeout: Optional[float] = None) -> float:
        """Start the countdown for the calling thread; returns the deadline
        used. Re-arming while armed just moves the deadline."""
        t = float(timeout) if timeout is not None else self.deadline_s()
        with self._lock:
            self._gen += 1
            self._armed_tid = threading.get_ident()
            self._armed_at = time.monotonic()
            self._deadline = self._armed_at + t
        self._ensure_thread()
        return t

    def extend_if_armed(self, timeout: Optional[float] = None) -> bool:
        """Push the CURRENT arm's deadline out by ``timeout`` (default
        ``startup_timeout``) — for legitimate step-sized work inside the
        armed region, e.g. a sentinel-rewind checkpoint restore, which must
        not be aborted for merely exceeding a step-time-derived deadline.
        A no-op (False) when nothing is armed, so calling it from code that
        also runs outside steps never arms a countdown nobody will stop."""
        with self._lock:
            if self._armed_tid is None:
                return False
            t = float(timeout) if timeout is not None else self.startup_timeout
            self._deadline = time.monotonic() + t
            return True

    def disarm(self) -> Optional[float]:
        """Stop the countdown; the elapsed time feeds the moving-percentile
        history. Returns the duration (None if not armed — including when
        the monitor already fired for this arm, in which case any pending
        not-yet-delivered WatchdogTimeout is cancelled so it cannot land in
        unrelated later code)."""
        with self._lock:
            if self._armed_tid is not None:
                dur = time.monotonic() - self._armed_at
                self._durations.append(dur)
                self._armed_tid = None
                return dur
            if self._fired_gen == self._gen and self._cancel_gen != self._gen:
                self._cancel_gen = self._gen
                _cancel_async_exc(threading.get_ident())
            return None

    def close(self) -> None:
        """Stop the monitor thread (engine teardown / agent restart)."""
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=2 * self.POLL_S + 1.0)

    # ------------------------------------------------------------- monitor
    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = _locks.spawn_thread(
                self._monitor, name=f"ds-watchdog-{self.name}",
                owner="watchdog", daemon=True)
            self._thread.start()

    def _monitor(self) -> None:
        while not self._stop.wait(self.POLL_S):
            with self._lock:
                tid = self._armed_tid
                expired = tid is not None and time.monotonic() >= self._deadline
                waited = time.monotonic() - self._armed_at
                if expired:
                    self._armed_tid = None      # one-shot per arm
                    gen = self._gen
                    self._fired_gen = gen       # disarm() may now cancel
            if expired:
                self._fire(tid, gen, waited)

    # (separated so tests can stub the process-kill escalation)
    _kill = staticmethod(lambda: os.kill(os.getpid(), signal.SIGABRT))

    def _fire(self, tid: int, gen: int, waited: float) -> None:
        msg = (f"watchdog[{self.name}]: armed operation exceeded its "
               f"{waited:.1f}s deadline (policy: max({self.min_timeout:g}s, "
               f"{self.factor:g} × p{int(self.percentile * 100)} of recent steps))")
        self.trips += 1
        self.last_trip_reason = msg
        _count_timeout(self.name, stall_s=waited)
        logger.error(msg)
        dump_all_stacks(self.dump_path, reason=msg)
        if self.on_timeout == "kill":
            logger.error(f"watchdog[{self.name}]: on_timeout=kill — aborting the process")
            self._kill()
            return
        with self._lock:
            # the stack dump above is slow; the op may have completed (and
            # disarmed) meanwhile — deliver nothing into unrelated code
            if self._cancel_gen == gen:
                logger.warning(f"watchdog[{self.name}]: operation completed "
                               "just past its deadline; timeout not delivered")
                return
            delivered = _async_raise(tid, msg)
        if not delivered:  # pragma: no cover - thread already gone
            logger.warning(f"watchdog[{self.name}]: armed thread {tid} vanished "
                           "before the timeout could be delivered")


def touch_heartbeat(path: str) -> bool:
    """Advance the heartbeat file's mtime (creating it first). The launcher's
    supervision loop reads the mtime; a failure here must never kill the
    step, so errors log-and-continue (the stale heartbeat they cause is
    itself the operator signal)."""
    try:
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "a"):
            pass
        os.utime(path, None)
        return True
    except OSError as e:
        logger.warning(f"watchdog: heartbeat touch failed for {path}: {e}")
        return False
