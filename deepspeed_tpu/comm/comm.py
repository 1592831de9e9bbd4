"""xccl — the XLA-collectives communication layer.

Counterpart of the reference's ``deepspeed/comm/comm.py`` (torch.distributed-
shaped module API over a global backend object ``cdb``, comm.py:53, installed by
``init_distributed:562``) and its only backend ``TorchBackend``
(comm/torch.py:39). Same surface, TPU-native semantics:

* ``all_reduce → jax.lax.psum``, ``all_gather → jax.lax.all_gather``,
  ``reduce_scatter → jax.lax.psum_scatter``, ``all_to_all → jax.lax.all_to_all``,
  ``send/recv → jax.lax.ppermute`` — all over **named mesh axes** instead of
  NCCL communicators. A "process group" is a tuple of mesh axis names
  (cf. SURVEY §2.4 mapping table).
* Called **inside a traced context** (shard_map/jit), these lower to ICI/DCN
  collectives in the compiled program — this is the hot path, used by ZeRO,
  MoE, pipeline, ring attention.
* Called **eagerly** they wrap themselves in a one-op ``shard_map`` over the
  global mesh, so test code can exercise the API exactly like the reference's
  ``tests/unit/comm/test_dist.py`` does (input carries the group axis as its
  leading dimension, one shard per group member).
* Multi-host bootstrap is ``jax.distributed.initialize()`` — the analogue of
  the NCCL rendezvous in ``TorchBackend.init_process_group`` (torch.py:84).

Every collective is wrapped by ``timed_op`` feeding the comms logger, matching
comm.py:104's profiling decorator.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.parallel.topology import (ALL_AXES, DP_AXES, build_mesh)
from deepspeed_tpu.utils import locks as _locks
from deepspeed_tpu.utils.logging import log_dist, logger


class ReduceOp:
    """cf. reference comm/comm.py:33."""
    SUM = "sum"
    PRODUCT = "product"
    MIN = "min"
    MAX = "max"
    AVG = "avg"
    BAND = "band"
    BOR = "bor"
    BXOR = "bxor"
    UNUSED = "unused"


AxisName = Union[str, Tuple[str, ...]]


class CommGroup:
    """A communication group = subset of mesh axis names (+ the mesh)."""

    def __init__(self, mesh: Mesh, axes: AxisName):
        self.mesh = mesh
        self.axes: Tuple[str, ...] = (axes,) if isinstance(axes, str) else tuple(axes)
        for a in self.axes:
            if a not in mesh.axis_names:
                raise ValueError(f"axis {a} not in mesh axes {mesh.axis_names}")

    @property
    def size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.axes]))

    def __repr__(self):
        return f"CommGroup(axes={self.axes}, size={self.size})"


class XCCLBackend:
    """Global backend state (the reference's ``cdb``, comm.py:53)."""

    def __init__(self, mesh: Mesh):
        self.name = "xccl"
        self.mesh = mesh
        self.initialized = True
        self.world_group = CommGroup(mesh, tuple(mesh.axis_names))

    def group(self, axes: Optional[AxisName]) -> CommGroup:
        if axes is None:
            return self.world_group
        if isinstance(axes, CommGroup):
            return axes
        return CommGroup(self.mesh, axes)


cdb: Optional[XCCLBackend] = None
comms_logger = None  # installed by configure()

# ds_doctor record mode (analysis/collectives.py): when installed, every
# collective — eager or traced — reports (op, shape, dtype, group axes)
# so the static per-rank sequence can be diffed across ranks BEFORE the
# mismatched program deadlocks at runtime. One `is None` check when off.
_collective_recorder = None


def set_collective_recorder(recorder) -> None:
    """Install/remove (None) the collective recorder callback
    ``recorder(op, shape, dtype, axes)``."""
    global _collective_recorder
    _collective_recorder = recorder


def _record_collective(op: str, tensor, group) -> None:
    rec = _collective_recorder
    if rec is None:
        return
    try:
        shape = tuple(getattr(tensor, "shape", ()))
        dtype = str(jnp.dtype(tensor.dtype)) if hasattr(tensor, "dtype") else "-"
    except Exception:
        shape, dtype = (), "-"
    try:
        axes = _axes(group)
    except Exception:
        axes = ()
    rec(op, shape, dtype, axes)


# ds_prof fleet aggregation: per-(op, group) sequence numbers stamped onto
# the timed collectives' trace spans, so `ds_prof merge` can match the
# k-th all_reduce over `data` on rank 0 with the k-th on rank 7 and
# compute arrival skew — the same (op, seq, group) identity the ds_doctor
# collective fingerprints canonicalize. Advances only on the timed eager
# path, which every rank takes identically under the same config.
_collective_trace_seq: dict = {}


def _next_collective_seq(op: str, group_desc: str) -> int:
    key = (op, group_desc)
    n = _collective_trace_seq.get(key, 0)
    _collective_trace_seq[key] = n + 1
    return n


def reset_collective_trace_seq() -> None:
    """Restart the per-(op, group) seq counters. Called by the telemetry
    session constructor: a new session means a new trace file and clock,
    and after an elastic restart a surviving rank (counters at N) and a
    replaced rank (fresh process, counters at 0) must both restart at 0
    or their (op, seq, group) identities never match again."""
    _collective_trace_seq.clear()


def _group_desc(group) -> str:
    try:
        return "+".join(_axes(group)) or "world"
    except Exception:
        return "world"


def record_engine_collective(op: str, shape, dtype, axes) -> None:
    """Register an ENGINE-ISSUED collective with the ds_doctor recorder
    (analysis/collectives.py record mode): GSPMD-inserted collectives —
    the layer stack's ZeRO-3 gathers (zero/partition.py::LayerGathers) —
    never pass through the eager ``dist.*`` wrappers, so they
    would be invisible to the cross-rank sequence fingerprint without
    this hook. Called at TRACE time from the step builder; one `is None`
    check when no recorder is installed."""
    rec = _collective_recorder
    if rec is None:
        return
    rec(op, tuple(int(s) for s in shape), str(dtype), tuple(axes))


def record_phase_span(op: str, seconds: float, group_desc: str,
                      nbytes: int = 0) -> None:
    """Emit a rank-matchable ``cat="comm"`` trace span for an engine-level
    collective PHASE — a separately dispatched XLA program whose content
    is collectives (the overlap engine's serial ZeRO-3 gather), timed to
    completion by the caller. Carries the same ``(op, seq, group)``
    identity as the eager ``timed_op`` spans, so ``ds_prof merge`` aligns
    and skews it across ranks and ``exposed_comm_us_per_step`` prices it."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.resilience import chaos as _chaos

    inj = _chaos.active_injector()
    if inj is not None and inj.slow_armed():
        # fail-slow drill: the phase is already timed by the caller, so
        # the injected excess is slept here (still inside the step's wall
        # clock) and added to every record of the phase
        extra = inj.slow_extra_s(seconds)
        if extra > 0.0:
            time.sleep(extra)
            seconds += extra
    registry = telemetry.get_registry()
    if comms_logger is not None:
        # phase latencies ride the same recent-window machinery as the
        # eager ops: skew gauges + rank-local straggler excess cover the
        # serial ZeRO-3 gather too (ds_gray's evidence must not go blind
        # when the schedule moves collectives out of the eager wrappers)
        comms_logger.append(op, op, seconds, int(nbytes))
        if registry.enabled:
            registry.gauge("comm/skew",
                           labels={"op": op, "size": str(int(nbytes))}
                           ).set(comms_logger.window_skew(op, int(nbytes)))
        excess = comms_logger.straggler_excess(op, int(nbytes), seconds)
        if excess > 0.0:
            telemetry.get_tracer().complete(
                "straggler_wait", excess * 1e6, cat="straggler", op=op)
            if registry.enabled:
                registry.counter("comm/straggler_excess_us").inc(
                    excess * 1e6)
    if registry.enabled:
        registry.histogram("comm/op_latency_seconds",
                           labels={"op": op, "size": str(int(nbytes))}
                           ).observe(seconds)
        registry.counter("comm/op_calls", labels={"op": op}).inc()
        registry.counter("comm/op_bytes", labels={"op": op}).inc(int(nbytes))
    telemetry.get_tracer().complete(
        f"comm:{op}", seconds * 1e6, cat="comm", op=op,
        seq=_next_collective_seq(op, group_desc), group=group_desc,
        bytes=int(nbytes))


def is_initialized() -> bool:
    return cdb is not None


def init_distributed(dist_backend: str = "xccl",
                     auto_mpi_discovery: bool = True,
                     distributed_port: int = 29500,
                     verbose: bool = True,
                     timeout=None,
                     init_method: Optional[str] = None,
                     dist_init_required: Optional[bool] = None,
                     config=None,
                     rank: int = -1,
                     world_size: int = -1,
                     mesh: Optional[Mesh] = None,
                     mesh_config=None) -> XCCLBackend:
    """Bootstrap multi-host JAX (if needed) and install the global mesh backend.

    Mirrors reference init_distributed (comm/comm.py:562): idempotent; discovers
    coordinator from env (JAX_COORDINATOR_ADDRESS / MASTER_ADDR like the
    launcher sets). Single-process single-host needs no rendezvous at all.
    """
    global cdb
    if timeout is not None:
        try:
            timeout = float(timeout.total_seconds())  # datetime.timedelta (reference contract)
        except AttributeError:
            timeout = float(timeout)
        if timeout <= 0:
            raise ValueError(f"init_distributed(timeout={timeout!r}): timeout "
                             "must be a positive number of seconds")
    if cdb is not None and mesh is None:
        # same-process topology change: a different mesh_config rebuilds the
        # backend (engine construction passes mesh_config; driver scripts
        # must not need to reach into module internals)
        if mesh_config is not None:
            from deepspeed_tpu.sharding.mesh import ensure_global_mesh

            candidate = ensure_global_mesh(mesh_config=mesh_config)
            if candidate is not cdb.mesh:
                cdb = XCCLBackend(candidate)
        return cdb

    # IMPORTANT: decide on multihost bring-up from ENV ONLY — even
    # jax.process_count() initializes the XLA backend, after which
    # jax.distributed.initialize refuses to run. Whether the distributed
    # client already exists is read from jax's own state, not the backend.
    if _dist_client() is None and (os.environ.get("DSTPU_NUM_PROCESSES") or
                                os.environ.get("COORDINATOR_ADDRESS") or
                                os.environ.get("JAX_COORDINATOR_ADDRESS")):
        coord = (os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get("COORDINATOR_ADDRESS")
                 or f"{os.environ.get('MASTER_ADDR', 'localhost')}:{distributed_port}")

        # process count/id: explicit args win, then the launcher's env
        # contract (launcher/launch.py build_env: JAX_NUM_PROCESSES/
        # JAX_PROCESS_ID + reference-compatible WORLD_SIZE/RANK); empty or
        # non-numeric env values are treated as unset
        def _env_int(*names):
            for n in names:
                v = os.environ.get(n)
                if v:
                    try:
                        return int(v)
                    except ValueError:
                        logger.warning(f"ignoring non-numeric {n}={v!r}")
            return None

        nproc = world_size if world_size > 0 else \
            (_env_int("DSTPU_NUM_PROCESSES", "JAX_NUM_PROCESSES", "WORLD_SIZE") or 1)
        pid = rank if rank >= 0 else \
            (_env_int("DSTPU_PROCESS_ID", "JAX_PROCESS_ID", "RANK") or 0)
        # a world of one has nobody to rendezvous with (the launcher's
        # single-host path exports exactly that). For more, a failed
        # rendezvous raises: carrying on would run N independent jobs
        if nproc > 1:
            jax.distributed.initialize(**_jax_init_kwargs(coord, nproc, pid, timeout))
            if verbose:
                log_dist(f"jax.distributed initialized: {nproc} processes via {coord}", ranks=[0])

    if mesh is None:
        # THE mesh: built once per topology and cached process-globally, so
        # every engine's programs compile against one device order
        from deepspeed_tpu.sharding.mesh import ensure_global_mesh

        mesh = ensure_global_mesh(mesh_config=mesh_config)
    else:
        from deepspeed_tpu.sharding.mesh import adopt_global_mesh

        adopt_global_mesh(mesh)
    cdb = XCCLBackend(mesh)
    if verbose:
        log_dist(f"xccl backend ready: mesh={dict(mesh.shape)} on {get_accelerator().device_kind()}", ranks=[0])
    return cdb


def _jax_init_kwargs(coord: str, nproc: int, pid: int, timeout=None) -> dict:
    """kwargs for ``jax.distributed.initialize``: the rendezvous triple plus
    ``initialization_timeout`` when the caller set one (the reference passes
    its ``timeout`` into the NCCL rendezvous, torch.py:84 — here it bounds
    the coordinator handshake)."""
    kwargs = dict(coordinator_address=coord, num_processes=nproc, process_id=pid)
    if timeout is not None:
        kwargs["initialization_timeout"] = max(1, int(timeout))
    return kwargs


def get_mesh() -> Mesh:
    assert cdb is not None, "deepspeed_tpu.comm not initialized — call init_distributed()"
    return cdb.mesh


def set_mesh(mesh: Mesh) -> None:
    global cdb
    from deepspeed_tpu.sharding.mesh import adopt_global_mesh

    adopt_global_mesh(mesh)
    cdb = XCCLBackend(mesh)


def get_rank(group=None) -> int:
    """Process rank (multi-host). Device-level position comes from the mesh."""
    return jax.process_index()


def get_world_size(group=None) -> int:
    if cdb is not None and group is not None:
        return cdb.group(group).size
    return jax.device_count()


def get_local_rank() -> int:
    return jax.process_index()


def get_world_group() -> Optional[CommGroup]:
    return cdb.world_group if cdb else None


def new_group(axes: AxisName) -> CommGroup:
    """Groups are declared by mesh axis name, not rank list — rank-list groups
    are a NCCL-ism; on TPU all group structure lives in the mesh."""
    assert cdb is not None
    return cdb.group(axes)


# --------------------------------------------------------------------------- #
# comms logging (reference utils/comms_logging.py + timed_op comm.py:104)
# --------------------------------------------------------------------------- #
def _busbw_factor(op_name: str, n: int) -> float:
    """Bus-bandwidth correction (reference utils/comms_logging.py get_bw):
    what the interconnect actually moved per link, vs the algorithmic bytes.
    ``n`` = group size; n<=1 means no wire traffic at all."""
    if n <= 1:
        return 1.0
    if "all_reduce" in op_name or "inference_all_reduce" in op_name:
        return 2.0 * (n - 1) / n
    if ("all_gather" in op_name or "reduce_scatter" in op_name
            or "all_to_all" in op_name):
        return (n - 1) / n
    return 1.0


class CommsLogger:
    STRAGGLER_WINDOW = 64       # recent-latency window per (op, size)
    STRAGGLER_SKEW = 3.0        # max/mean ratio that flags a straggler
    STRAGGLER_MIN_SAMPLES = 8   # window floor before any rank-local
                                # straggler excess is stamped — a cold
                                # window (first steps, post-recompile)
                                # has no baseline worth trusting

    def __init__(self, verbose=False, debug=False, prof_all=True, prof_ops=None):
        self.verbose = verbose
        self.debug = debug
        self.prof_all = prof_all
        self.prof_ops = prof_ops or []
        self.comms_dict = {}
        # (raw_name, msg_size) -> deque of the last STRAGGLER_WINDOW latencies
        self._recent = {}
        # timed ops fire from checkpoint-I/O / serving / watchdog threads
        # while the main thread reads log_all/straggler_report: every
        # multi-field comms_dict/_recent update is one critical section
        self._lock = _locks.make_lock("comm.logger")

    def append(self, raw_name, record_name, latency, msg_size, n=1):
        with self._lock:
            entry = self.comms_dict.setdefault(raw_name, {})
            # per-size record: [count, latencies, algo GB/s, bus GB/s] — same
            # 4-slot layout as the reference's comms_dict
            sizes = entry.setdefault(msg_size, [0, [], [], []])
            sizes[0] += 1
            sizes[1].append(latency)
            if latency > 0:
                algbw = msg_size / latency / 1e9
                sizes[2].append(algbw)
                sizes[3].append(algbw * _busbw_factor(raw_name, n))
            key = (raw_name, msg_size)
            recent = self._recent.get(key)
            if recent is None:
                from collections import deque

                self._recent[key] = recent = deque(maxlen=self.STRAGGLER_WINDOW)
            recent.append(latency)
        if self.verbose:
            log_dist(f"comm op: {record_name} | msg size: {msg_size} | latency(ms): {latency*1000:.2f}", ranks=[0])

    def reset_straggler_windows(self) -> None:
        """Drop the recent-latency windows (the cumulative comms_dict
        stays). After an evict restart the windows still hold the old
        culprit's dragged latencies — a consumer baselining a NEW fleet
        (ds_gray re-arming on the survivors) must start them empty or the
        stale tail reads as fresh skew for up to STRAGGLER_WINDOW calls."""
        with self._lock:
            self._recent.clear()

    def straggler_report(self):
        """Per-(op, size) max-vs-mean latency skew over the recent window.

        Deviation from the reference (which diffs wall-clocks ACROSS ranks
        under a barrier): XLA collectives rendezvous internally, so a slow
        participant stretches everyone's latency — skew across the recent
        TIME window of the same op exposes the same intermittent straggler
        without adding barriers. Returns [(op, size, n, mean, max, skew)].
        """
        rows = []
        with self._lock:
            snap = {k: list(v) for k, v in self._recent.items()}
        for (op, size), lats in sorted(snap.items()):
            if not lats:
                continue
            mean = sum(lats) / len(lats)
            worst = max(lats)
            rows.append((op, size, len(lats), mean, worst,
                         worst / mean if mean > 0 else 0.0))
        return rows

    def window_skew(self, raw_name, msg_size) -> float:
        """One key's max-vs-mean skew over the recent window — the
        ``straggler_report`` row for the just-appended op, O(window), so
        the comm layer can export it as a live gauge per call."""
        with self._lock:
            lats = list(self._recent.get((raw_name, msg_size)) or ())
        if not lats:
            return 0.0
        mean = sum(lats) / len(lats)
        return max(lats) / mean if mean > 0 else 0.0

    def straggler_excess(self, raw_name, msg_size, latency) -> float:
        """Rank-local straggler excess: seconds ``latency`` lands beyond
        the recent FASTEST-HALF mean of this key's window. The trimmed
        baseline is robust to the slow tail itself (a persistently
        dragged op does not launder its own excess into the baseline
        until the whole window has turned over), and the sample floor +
        2x trigger keep cold windows and ordinary jitter at exactly
        0.0 — the goodput ``straggler_wait`` bucket must stay empty on a
        healthy rank."""
        with self._lock:
            lats = list(self._recent.get((raw_name, msg_size)) or ())
        if len(lats) < self.STRAGGLER_MIN_SAMPLES:
            return 0.0
        fastest = sorted(lats)[:max(1, len(lats) // 2)]
        baseline = sum(fastest) / len(fastest)
        if baseline <= 0.0 or latency < 2.0 * baseline:
            return 0.0
        return latency - baseline

    def log_all(self, print_log=True, show_straggler=False):
        lines = ["Comms summary:"]
        with self._lock:
            snap = {op: {size: (rec[0], list(rec[1]), list(rec[2]), list(rec[3]))
                         for size, rec in per_size.items()}
                    for op, per_size in self.comms_dict.items()}
        for op, per_size in snap.items():
            for size, (count, lats, bws, busbws) in sorted(per_size.items()):
                avg_lat = sum(lats) / max(1, len(lats))
                avg_bw = sum(bws) / max(1, len(bws)) if bws else 0.0
                avg_busbw = sum(busbws) / max(1, len(busbws)) if busbws else 0.0
                lines.append(f"  {op:26s} size={size:>12d} count={count:>6d} "
                             f"avg_lat={avg_lat*1e3:8.3f}ms algo_bw={avg_bw:8.2f}GB/s "
                             f"bus_bw={avg_busbw:8.2f}GB/s")
        if show_straggler:
            lines.append(f"Straggler skew (max vs mean latency, last "
                         f"{self.STRAGGLER_WINDOW} calls per op/size):")
            for op, size, cnt, mean, worst, skew in self.straggler_report():
                flag = "  <-- straggler" if skew >= self.STRAGGLER_SKEW and cnt >= 4 else ""
                lines.append(f"  {op:26s} size={size:>12d} window={cnt:>4d} "
                             f"mean={mean*1e3:8.3f}ms max={worst*1e3:8.3f}ms "
                             f"skew={skew:5.2f}x{flag}")
        if print_log:
            log_dist("\n".join(lines), ranks=[0])
        return self.comms_dict


def configure(deepspeed_config=None, enabled=None, prof_all=None, prof_ops=None, verbose=None, debug=None):
    global comms_logger
    cc = deepspeed_config.comms_config if deepspeed_config is not None else None
    enabled = enabled if enabled is not None else (cc.enabled if cc else False)
    if enabled:
        comms_logger = CommsLogger(
            verbose=verbose if verbose is not None else (cc.verbose if cc else False),
            debug=debug if debug is not None else (cc.debug if cc else False),
            prof_all=prof_all if prof_all is not None else (cc.prof_all if cc else True),
            prof_ops=prof_ops if prof_ops is not None else (cc.prof_ops if cc else []),
        )


def _nbytes(x) -> int:
    try:
        return int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
    except Exception:
        return 0


def timed_op(func):
    """Time eager collectives into the comms logger AND the telemetry
    histograms (per-op / per-size latency + bytes). In-trace calls pass
    through untouched — XLA owns that timing (comm.py:104 role)."""
    import inspect

    # position of `group` in the wrapped signature varies per collective
    # (all_reduce: 3rd, all_gather: 2nd, ...) — resolve it once so a
    # positionally-passed group still yields the right bus-bw group size
    params = list(inspect.signature(func).parameters)
    group_idx = params.index("group") - 1 if "group" in params else None

    @functools.wraps(func)
    def wrapper(tensor, *args, **kwargs):
        from deepspeed_tpu import telemetry

        if _collective_recorder is not None:
            group = kwargs.get("group")
            if group is None and group_idx is not None and group_idx < len(args):
                group = args[group_idx]
            _record_collective(func.__name__, tensor, group)
        registry = telemetry.get_registry()
        in_trace = isinstance(tensor, jax.core.Tracer)
        if (comms_logger is None and not registry.enabled) or in_trace:
            if not in_trace:
                # the `collective` chaos target fires on EAGER collectives
                # whether or not anything is timing them (a watchdog drill
                # without a telemetry block must still inject) — trace-time
                # calls are excluded: a sleep during tracing is not a fault
                from deepspeed_tpu.resilience import chaos as _chaos

                inj = _chaos.active_injector()
                if inj is not None and inj.targets("collective"):
                    inj.before("collective", func.__name__)
            return func(tensor, *args, **kwargs)
        t0 = time.perf_counter()
        from deepspeed_tpu.resilience import chaos as _chaos

        inj = _chaos.active_injector()
        if inj is not None and inj.targets("collective"):
            # `collective` chaos target: a scripted/randomized delay or
            # hang INSIDE the timed window inflates this op's comm span —
            # stragglers and exposed-comm inflation become deterministically
            # drillable without a slow interconnect (mirrors the
            # train_step/decode_step step targets)
            inj.before("collective", func.__name__)
        result = func(tensor, *args, **kwargs)
        jax.block_until_ready(result)
        if inj is not None and inj.slow_armed():
            # `slow_device` fault class: the persistent fail-slow excess
            # is slept INSIDE the timed window, so the inflated wait
            # lands in this op's comm span, the comms logger's skew
            # deque, and the straggler evidence — a fleet blocking on
            # one slow participant, without a slow chip
            extra = inj.slow_extra_s(time.perf_counter() - t0)
            if extra > 0.0:
                time.sleep(extra)
        latency = time.perf_counter() - t0
        size = _nbytes(tensor)
        group = kwargs.get("group")
        if group is None and group_idx is not None and group_idx < len(args):
            group = args[group_idx]
        n = get_world_size(group)
        if comms_logger is not None:
            comms_logger.append(func.__name__, kwargs.get("log_name", func.__name__),
                                latency, size, n=n)
            if registry.enabled:
                # straggler skew as a live gauge (not just log_all print):
                # ds_gray, ds_top and offline tools read it from
                # metrics.jsonl as comm/skew{op=,size=}
                registry.gauge("comm/skew",
                               labels={"op": func.__name__,
                                       "size": str(size)}
                               ).set(comms_logger.window_skew(
                                   func.__name__, size))
            excess = comms_logger.straggler_excess(func.__name__, size,
                                                   latency)
            if excess > 0.0:
                # rank-local straggler_wait: the slice of this call beyond
                # the recent fastest-half baseline, as a cat="straggler"
                # span nested in the comm span — it outranks exposed_comm
                # in the taxonomy, so the excess is re-charged to the
                # straggler, not claimed as ordinary comm
                telemetry.get_tracer().complete(
                    "straggler_wait", excess * 1e6, cat="straggler",
                    op=func.__name__)
                if registry.enabled:
                    registry.counter("comm/straggler_excess_us").inc(
                        excess * 1e6)
        if registry.enabled:
            registry.histogram("comm/op_latency_seconds",
                               labels={"op": func.__name__, "size": str(size)}).observe(latency)
            registry.counter("comm/op_calls", labels={"op": func.__name__}).inc()
            registry.counter("comm/op_bytes", labels={"op": func.__name__}).inc(size)
        # rank-matchable trace span: (op, seq, group) is the fleet-wide
        # identity ds_prof merges/skews on (no-op without a live tracer)
        gd = _group_desc(group)
        telemetry.get_tracer().complete(
            f"comm:{func.__name__}", latency * 1e6, cat="comm",
            op=func.__name__, seq=_next_collective_seq(func.__name__, gd),
            group=gd, bytes=size)
        return result

    return wrapper


# --------------------------------------------------------------------------- #
# collectives
# --------------------------------------------------------------------------- #
def _axes(group) -> Tuple[str, ...]:
    if group is None:
        if cdb is not None:
            return tuple(cdb.mesh.axis_names)
        raise RuntimeError("comm not initialized and no group given")
    if isinstance(group, CommGroup):
        return group.axes
    return (group,) if isinstance(group, str) else tuple(group)


def _in_trace(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _eager_shard_map(fn, group, x, extra_leading_out: bool = False,
                     name: str = "collective"):
    """Run a one-collective shard_map over the mesh for eager API usage.

    Convention (documented in the module docstring): the input's leading dim
    enumerates the group members, i.e. shape (group_size, ...). We shard that
    dim over the group axes, apply the collective, and return the result with
    the same convention.
    """
    mesh = get_mesh()
    axes = _axes(group)
    spec = P(axes)
    in_spec = P(axes, *([None] * (x.ndim - 1)))
    out_first = axes if extra_leading_out else None
    out_spec = P(out_first, *([None] * (x.ndim - 1)))
    shard_fn = jax.shard_map(fn, mesh=mesh, in_specs=in_spec,
                             out_specs=out_spec)
    from deepspeed_tpu.sharding import sharded_jit

    # label by the COLLECTIVE name, not the closure's (__name__ is '_k' for
    # every wrapper — one shared label would overwrite the program table)
    return sharded_jit(
        shard_fn, label=f"comm/eager_{name}",
        in_shardings=(NamedSharding(mesh, in_spec),),
        out_shardings=NamedSharding(mesh, out_spec),
        donate_argnums=(), mesh=mesh)(x)


_REDUCERS_TRACED = {
    ReduceOp.SUM: lax.psum,
    ReduceOp.MAX: lax.pmax,
    ReduceOp.MIN: lax.pmin,
    ReduceOp.AVG: lambda x, ax: lax.pmean(x, ax),
}


@timed_op
def all_reduce(tensor, op: str = ReduceOp.SUM, group=None, async_op: bool = False, log_name="all_reduce"):
    """SUM/MAX/MIN/AVG across the group axes.

    Traced: ``lax.psum(x, axes)`` — the hot path inside shard_map.
    Eager: leading dim is the group dim; every member's slot gets the reduction.
    """
    axes = _axes(group)

    def _product(x):
        # sign-safe product: psum of log|x| for magnitude, psum of sign
        # parity for sign; exact zeros propagate as zeros.
        mag = jnp.exp(lax.psum(jnp.log(jnp.abs(x) + jnp.where(x == 0, 1.0, 0.0)), axes))
        neg = lax.psum(jnp.where(x < 0, 1.0, 0.0), axes)
        has_zero = lax.pmax(jnp.where(x == 0, 1.0, 0.0), axes)
        sign = jnp.where(jnp.mod(neg, 2.0) == 1.0, -1.0, 1.0)
        return jnp.where(has_zero == 1.0, 0.0, sign * mag)

    if _in_trace(tensor):
        if op == ReduceOp.PRODUCT:
            return _product(tensor)
        return _REDUCERS_TRACED[op](tensor, axes)

    def _k(x):
        x = jnp.squeeze(x, 0)
        if op == ReduceOp.PRODUCT:
            r = _product(x)
        else:
            r = _REDUCERS_TRACED[op](x, axes)
        return r[None]

    return _eager_shard_map(_k, group, tensor, extra_leading_out=True, name="all_reduce")


@timed_op
def inference_all_reduce(tensor, op=ReduceOp.SUM, group=None, log_name="inference_all_reduce"):
    # the UNdecorated all_reduce: nesting two timed_op wrappers would log the
    # same wire traffic under both op names (and sync twice)
    return all_reduce.__wrapped__(tensor, op=op, group=group)


@timed_op
def all_gather(tensor, group=None, axis: int = 0, tiled: bool = False, log_name="all_gather"):
    """Traced: lax.all_gather over group axes (concatenated along ``axis``)."""
    axes = _axes(group)
    if _in_trace(tensor):
        return lax.all_gather(tensor, axes, axis=axis, tiled=tiled)
    def _k(x):
        return lax.all_gather(jnp.squeeze(x, 0), axes, axis=0, tiled=False)[None]
    return _eager_shard_map(_k, group, tensor, extra_leading_out=True, name="all_gather")


def all_gather_into_tensor(output_unused, tensor, group=None):
    """Reference signature parity (comm/torch.py:123); output arg is ignored
    because JAX is functional — the gathered array is returned."""
    return all_gather(tensor, group=group, tiled=True)


@timed_op
def reduce_scatter(tensor, group=None, op=ReduceOp.SUM, scatter_dimension: int = 0,
                   tiled: bool = True, log_name="reduce_scatter"):
    """Traced: lax.psum_scatter. Eager: leading-dim group convention."""
    axes = _axes(group)
    if _in_trace(tensor):
        return lax.psum_scatter(tensor, axes, scatter_dimension=scatter_dimension, tiled=tiled)
    def _k(x):
        return lax.psum_scatter(jnp.squeeze(x, 0), axes, scatter_dimension=0, tiled=True)[None]
    return _eager_shard_map(_k, group, tensor, extra_leading_out=True, name="reduce_scatter")


def reduce_scatter_tensor(output_unused, tensor, op=ReduceOp.SUM, group=None):
    return reduce_scatter(tensor, group=group, op=op)


@timed_op
def all_to_all_single(tensor, group=None, split_axis: int = 0, concat_axis: int = 0,
                      log_name="all_to_all_single"):
    """Traced: lax.all_to_all (the MoE dispatch primitive, cf. sharded_moe.py:90)."""
    axes = _axes(group)
    if _in_trace(tensor):
        return lax.all_to_all(tensor, axes, split_axis=split_axis, concat_axis=concat_axis, tiled=True)
    def _k(x):
        return lax.all_to_all(jnp.squeeze(x, 0), axes, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)[None]
    return _eager_shard_map(_k, group, tensor, extra_leading_out=True, name="all_to_all")


all_to_all = all_to_all_single


@timed_op
def broadcast(tensor, src: int = 0, group=None, async_op: bool = False, log_name="broadcast"):
    """Traced: every member takes src's value (ppermute-free: psum of masked)."""
    axes = _axes(group)
    if _in_trace(tensor):
        idx = lax.axis_index(axes if len(axes) > 1 else axes[0])
        contrib = jnp.where(idx == src, tensor, jnp.zeros_like(tensor))
        return lax.psum(contrib, axes)
    def _k(x):
        x = jnp.squeeze(x, 0)
        idx = lax.axis_index(axes if len(axes) > 1 else axes[0])
        contrib = jnp.where(idx == src, x, jnp.zeros_like(x))
        return lax.psum(contrib, axes)[None]
    return _eager_shard_map(_k, group, tensor, extra_leading_out=True, name="broadcast")


def ppermute(tensor, perm, group=None):
    """Point-to-point collective permute — the TPU-native send/recv
    (reference pipe/p2p.py send:50/recv:71 become one fused ppermute over ICI)."""
    _record_collective("ppermute", tensor, group)
    axes = _axes(group)
    axis = axes[0] if len(axes) == 1 else axes
    return lax.ppermute(tensor, axis, perm)


def send(tensor, dst: int, group=None, tag: int = 0):
    raise NotImplementedError(
        "xccl has no eager point-to-point send; use comm.ppermute inside a "
        "shard_map (pipeline p2p does this — see deepspeed_tpu.runtime.pipe.p2p)")


def recv(tensor, src: int, group=None, tag: int = 0):
    raise NotImplementedError(
        "xccl has no eager point-to-point recv; use comm.ppermute inside a shard_map")


def barrier(group=None, log_name="barrier"):
    """Cross-process sync point. In-trace it's a no-op (XLA orders ops)."""
    _record_collective("barrier", None, group)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(log_name)
    else:
        jax.effects_barrier()


_default_barrier_timeout: Optional[float] = None
_default_barrier_timeout_source: Optional[str] = None
_monitored_barrier_seq = 0


def set_default_barrier_timeout(timeout: Optional[float],
                                source: str = "manual") -> None:
    """Default deadline for ``monitored_barrier`` calls that pass none —
    the engine sets this from the ``watchdog.barrier_timeout`` knob with
    ``source="config"``. Source tracking mirrors ``uninstall_config_chaos``:
    an engine built WITHOUT the watchdog block clears only a previous
    engine's CONFIG-installed default, never a manual install."""
    global _default_barrier_timeout, _default_barrier_timeout_source
    if timeout is not None and timeout <= 0:
        raise ValueError(f"barrier timeout must be positive, got {timeout!r}")
    _default_barrier_timeout = timeout
    _default_barrier_timeout_source = None if timeout is None else source


def clear_config_barrier_timeout() -> None:
    """Remove only a CONFIG-installed barrier default (engine init with the
    watchdog block absent); manual installs are deliberately left alone."""
    global _default_barrier_timeout, _default_barrier_timeout_source
    if _default_barrier_timeout_source == "config":
        _default_barrier_timeout = None
        _default_barrier_timeout_source = None


def _dist_client():
    """The jax coordination-service client (None single-host / pre-init)."""
    from jax._src import distributed as _jax_distributed

    return _jax_distributed.global_state.client


def monitored_barrier(group=None, timeout=None, wait_all_ranks=False,
                      log_name="monitored_barrier"):
    """Barrier with a real deadline (reference comm.py monitored_barrier —
    which this port used to silently strip of BOTH its arguments).

    Single process: a plain :func:`barrier` — no threads, no deadline
    (there is nobody to wait for). Multi-process with a ``timeout`` (or a
    default installed via :func:`set_default_barrier_timeout`): the sync
    runs under a background-thread deadline; on expiry every thread's stack
    is dumped via faulthandler, ``resilience/watchdog_timeouts`` is
    counted, and :class:`~deepspeed_tpu.resilience.watchdog.WatchdogTimeout`
    is raised — the caller gets control back while the wedged sync thread
    is disowned. ``wait_all_ranks=True`` records each process's arrival in
    the jax coordination-service KV store (a host-side agreement round)
    so the timeout message NAMES the processes that never reached the
    barrier instead of just "it hung".
    """
    global _monitored_barrier_seq
    if timeout is not None:
        try:
            timeout = float(timeout.total_seconds())  # timedelta (reference contract)
        except AttributeError:
            timeout = float(timeout)
        if timeout <= 0:
            raise ValueError(f"monitored_barrier(timeout={timeout!r}): timeout must be positive")
    if jax.process_count() == 1:
        return barrier(group, log_name=log_name)
    if timeout is None:
        timeout = _default_barrier_timeout
    if timeout is None:
        return barrier(group, log_name=log_name)

    from deepspeed_tpu.resilience.watchdog import run_with_deadline

    _monitored_barrier_seq += 1
    seq = _monitored_barrier_seq    # all ranks call in lockstep → keys align
    roster = None
    client = _dist_client()
    if wait_all_ranks and client is not None:
        roster = f"ds_tpu/monitored_barrier/{log_name}/{seq}"
        try:
            client.key_value_set(f"{roster}/{jax.process_index()}", "1")
        except Exception as e:
            logger.warning(f"monitored_barrier: arrival roster unavailable ({e})")
            roster = None

    def _missing_info() -> str:
        if not wait_all_ranks:
            return ""
        if roster is None:
            return " (arrival roster unavailable — no coordination-service KV store)"
        try:
            entries = client.key_value_dir_get(roster)
            arrived = {int(str(k).rsplit("/", 1)[-1]) for k, _ in entries}
        except Exception as e:
            return f" (arrival roster unreadable: {e})"
        missing = sorted(set(range(jax.process_count())) - arrived)
        if missing:
            return f"; processes that never reached the barrier: {missing}"
        return "; every process arrived — the sync itself wedged"

    out = run_with_deadline(lambda: barrier(group, log_name=log_name),
                            timeout=timeout,
                            name=f"{log_name}[{seq}]",
                            on_timeout_info=_missing_info)
    if roster is not None:
        # each rank retires its own arrival key on success — thousands of
        # barriers over a multi-day job must not grow the coordinator's KV
        # store without bound (on timeout the keys stay for post-mortems)
        try:
            client.key_value_delete(f"{roster}/{jax.process_index()}")
        except Exception:
            pass
    return out


def reduce(tensor, dst: int = 0, op=ReduceOp.SUM, group=None):
    """Rooted reduce has no ICI advantage on TPU — lower to all_reduce, callers
    read their slot (same trick the reference uses in reverse for bcast)."""
    return all_reduce(tensor, op=op, group=group)


def gather(tensor, dst: int = 0, group=None):
    return all_gather(tensor, group=group)


def scatter(tensor, src: int = 0, group=None):
    raise NotImplementedError("use sharding constraints / device_put for scatter on TPU")


def all_gather_coalesced(tensors, group=None):
    """Gather a list of arrays with one fused program (reference torch.py:135)."""
    axes = _axes(group)
    if tensors and _in_trace(tensors[0]):
        return [lax.all_gather(t, axes, tiled=True) for t in tensors]
    return [all_gather(t, group=group) for t in tensors]


def all_reduce_coalesced(tensors, op=ReduceOp.SUM, group=None):
    if tensors and _in_trace(tensors[0]):
        axes = _axes(group)
        return list(lax.psum(tuple(tensors), axes))
    return [all_reduce(t, op=op, group=group) for t in tensors]


# ------------------------------------------------------------------ host-side
def allgather_host(value, log_name="allgather_host"):
    """Host-side (numpy) per-process allgather: returns an array with a
    leading process dimension. The ONE routing point for untimed host
    collectives outside this module — the ds_doctor self-lint forbids
    raw ``multihost_utils.process_allgather`` elsewhere (it would bypass
    the collective recorder and any timing/telemetry), so the
    consistency guard and the elastic agent come through here."""
    arr = np.asarray(value)
    _record_collective(log_name, arr, None)
    if jax.process_count() == 1:
        return arr[None, ...]
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(arr))


def broadcast_object_list(obj_list, src=0, group=None):
    """Cross-process python-object broadcast (reference send_obj/recv_obj
    pickle path, pipe/p2p.py:100). Uses multihost broadcast of host bytes."""
    if jax.process_count() == 1:
        return obj_list
    import pickle

    from jax.experimental import multihost_utils

    payload = pickle.dumps(obj_list)
    arr = np.frombuffer(payload, dtype=np.uint8)
    n = multihost_utils.broadcast_one_to_all(np.array([arr.size], dtype=np.int64))
    buf = np.zeros(int(n[0]), dtype=np.uint8)
    if jax.process_index() == src:
        buf[: arr.size] = arr
    out = multihost_utils.broadcast_one_to_all(buf)
    return pickle.loads(out.tobytes())


def log_summary(show_straggler=False):
    if comms_logger is not None:
        return comms_logger.log_all(show_straggler=show_straggler)


def get_global_rank(group=None, group_rank: int = 0) -> int:
    return group_rank


def destroy_process_group(group=None):
    global cdb
    cdb = None
