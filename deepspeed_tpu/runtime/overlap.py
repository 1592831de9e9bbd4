"""Overlap engine — hide the ZeRO collectives behind compute.

The reference hides ZeRO-3 communication with hand-scheduled CUDA streams:
``PartitionedParameterCoordinator`` prefetches the next submodule's
allgather while the current one computes (stage3.py fetch/prefetch/release
state machine) and ``overlap_comm`` launches the gradient reduce-scatter on
a side stream during backward. On TPU the schedule belongs to XLA, so the
same wins are expressed as *program structure* the compiler can overlap:

* **param-gather prefetch** (:func:`prefetched_layer_scan`) — the fused
  train step's layer loop is rebuilt as a double-buffered scan: the
  ZeRO-3 gather of layer *i+1*'s (dp-sharded) stacked params is issued as
  an independent op while layer *i* computes, so the latency-hiding
  scheduler can overlap gather and matmul instead of serializing
  slice → gather → compute inside one iteration. Specs come straight from
  the existing :class:`~deepspeed_tpu.runtime.zero.partition.ShardingPlan`.
* **per-block grad reduce-scatter** — the gather is a ``custom_vjp`` whose
  backward constrains the cotangent back to the *sharded* layout, so the
  reduce-scatter of layer *i*'s grads is issued inside the backward scan
  (while layer *i-1*'s backward computes) instead of one fused
  post-backward reduction (``grad_reduce: "scan"`` vs ``"post"``).
* **latency-hiding scheduler preset** (:func:`apply_scheduler_flags`) —
  the XLA flags that let the TPU scheduler actually move async collectives
  behind compute, applied once at engine init and reported by
  ``ds_report``.
* **async checkpoint snapshot** (:class:`AsyncSnapshotter`) — a device-side
  copy of the state is taken on the step path (HBM-bandwidth fast) and the
  device→host transfer plus the PR 1 verified orbax/manifest write run on
  a background thread, so the ``checkpoint`` badput bucket stops charging
  the step.

**Measuring the win.** One fused XLA program is opaque to host-side
spans: its internal collectives never appear as ``cat="comm"`` trace
events, so a fused step's ``exposed_comm_us_per_step`` reads ~0 whether
or not the schedule overlaps. ``schedule: "serial"`` is the *measured
un-overlapped baseline*: the classic blocking ZeRO-3 schedule the
reference runs without prefetch — a separately dispatched all-gather
program (timed to completion, emitted as a rank-matchable comm span with
the same ``(op, seq, group)`` identity ``ds_prof merge`` aligns on)
followed by the compute program. ``ds_prof merge`` / the perf-ledger
goodput block then price exactly what the overlapped schedule removes
from the host timeline; the ``collective`` chaos target can inflate it
deterministically for drills.

STRICT no-op contract: this module is imported only when the ``overlap``
ds_config block is present and enabled; without it the engine's step
builder, the models' ``layer_scan`` and the checkpoint path are untouched
(asserted byte-identical in tests/unit/test_overlap.py).
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.runtime.zero.partition import (GATHERED_NAME, ShardingPlan,
                                                  _axes_of, _spec_tuple,
                                                  drop_dp_axes, gather_on_use,
                                                  stacked_param_keys)
from deepspeed_tpu.utils import locks as _locks
from deepspeed_tpu.utils.logging import log_dist, logger

# ---------------------------------------------------------------------------
# XLA latency-hiding scheduler preset (component 3)
# ---------------------------------------------------------------------------
# The flags that make "the compiler overlaps it" true on TPU: async
# collectives + the latency-hiding scheduler that moves their waits behind
# compute (T3 / "The Big Send-off" both lean on this machinery; maxtext
# ships the same preset). Harmless but inert on the CPU backend — the CPU
# scheduler executes thunks serially regardless, which is exactly why the
# serial/overlapped *measurement* above is span-based, not flag-based.
SCHEDULER_FLAG_PRESET = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
)

def scheduler_flag_status() -> List[Tuple[str, bool]]:
    """(flag, present-in-XLA_FLAGS) for the preset — what ``ds_report``
    prints, importable without an engine. Presence is matched on WHOLE
    flag names (a set flag that is a prefix of another, e.g.
    ``..._fusion`` vs ``..._fusion_fuse_all_gather``, must not mask it)."""
    current = {tok.split("=", 1)[0]
               for tok in os.environ.get("XLA_FLAGS", "").split()}
    return [(f, f.split("=", 1)[0] in current) for f in SCHEDULER_FLAG_PRESET]


def apply_scheduler_flags() -> List[str]:
    """Append the preset's missing flags to ``XLA_FLAGS`` and return what
    was added. The env var is how XLA receives scheduler flags, so flags
    added after this process's backend initialized only reach CHILD
    processes (the launcher exports XLA_FLAGS — ``EXPORT_ENVS``); a
    warning says so once. Flags the user already set are left alone.

    TPU backend only: these flags are registered by the TPU compiler —
    a CPU/GPU XLA aborts the PROCESS on unknown ``XLA_FLAGS`` entries
    (``parse_flags_from_env.cc``), and any subprocess inheriting the env
    would die at backend init. Off-TPU the preset is reported by
    ``ds_report`` as inapplicable instead of applied."""
    if jax.default_backend() != "tpu":
        log_dist("overlap.scheduler_flags: latency-hiding preset is "
                 "TPU-compiler-only (a CPU/GPU XLA aborts on unknown "
                 "XLA_FLAGS); not applied on this backend", ranks=[0])
        return []
    added = [f for f, present in scheduler_flag_status() if not present]
    if not added:
        return []
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " " + " ".join(added)).strip()
    try:
        initialized = jax._src.xla_bridge._backends  # noqa: SLF001
    except Exception:
        initialized = None
    if initialized:
        logger.warning(
            "overlap.scheduler_flags: the jax backend of THIS process was "
            "already initialized, so the latency-hiding preset only reaches "
            "child processes (launcher workers inherit XLA_FLAGS). Set "
            "XLA_FLAGS before process start for the training process itself; "
            "`ds_report` shows the live flag set.")
    log_dist(f"overlap: XLA scheduler preset appended ({len(added)} flag(s): "
             + " ".join(f.split('=', 1)[0] for f in added) + ")", ranks=[0])
    return added


# ---------------------------------------------------------------------------
# gathered-spec math
# ---------------------------------------------------------------------------
def gathered_param_specs(plan: ShardingPlan, param_shapes: Any) -> Any:
    """plan.param_specs with the dp axes dropped from every leaf — the
    placement of the serial schedule's explicit gather phase."""
    return jax.tree.map(
        lambda sh, sp: drop_dp_axes(sp, len(sh.shape), plan.dp_axes),
        param_shapes, plan.param_specs)


def _leaf_nbytes(shape_struct) -> int:
    return int(np.prod(shape_struct.shape)) * jnp.dtype(shape_struct.dtype).itemsize


# ---------------------------------------------------------------------------
# stacked-subtree matching (the model's layer-scanned params)
# ---------------------------------------------------------------------------
class StackedGatherPlan:
    """Gather/reduce specs for the model's layer-stacked param subtree
    (``params["blocks"]`` by convention; ``model.stacked_params_key``
    overrides). Built once at engine init from the ShardingPlan; matched
    against scan ``xs`` elements at trace time by treedef + leaf shapes."""

    def __init__(self, plan: ShardingPlan, shapes_subtree: Any,
                 specs_subtree: Any, grad_reduce: str, remat_gather: bool,
                 wire=None):
        self.mesh = plan.mesh
        self.dp_axes = tuple(plan.dp_axes)
        self.grad_reduce = grad_reduce
        self.remat_gather = remat_gather
        leaves, self.treedef = jax.tree_util.tree_flatten(shapes_subtree)
        self.stacked_shapes = [tuple(l.shape) for l in leaves]
        self.n_layers = int(leaves[0].shape[0]) if leaves else 0
        spec_leaves = self.treedef.flatten_up_to(specs_subtree)
        # per leaf: (gathered slice spec, sharded slice spec) or None when
        # the leaf carries no dp sharding (persistence-threshold smalls)
        self.slice_specs: List[Optional[Tuple[P, P]]] = []
        for sh, sp in zip(leaves, spec_leaves):
            entries = _spec_tuple(sp, len(sh.shape))[1:]   # drop the L dim
            sharded = P(*entries)
            gathered = drop_dp_axes(sharded, len(entries), self.dp_axes)
            if tuple(gathered) == tuple(_spec_tuple(sharded, len(entries))):
                self.slice_specs.append(None)
            else:
                self.slice_specs.append((gathered, sharded))
        # ds_wire (runtime/wire.py): per-leaf quantized-gather plans — the
        # qwZ/hpZ drop-in for the gather below. None entries (or no wire
        # engine at all) keep the full-width path byte-identical.
        self.wire = wire if wire is not None and \
            getattr(wire, "weight_active", False) else None
        self.wire_leaves = (self.wire.plan_stacked(leaves, self.slice_specs)
                            if self.wire is not None else None)
        self.secondary = bool(self.wire is not None and self.wire.secondary
                              and any(lw is not None and lw.sec_q is not None
                                      for lw in self.wire_leaves))

    @property
    def active(self) -> bool:
        return any(s is not None for s in self.slice_specs)

    def matches(self, element: Any) -> bool:
        """Does a scan ``xs`` element look like a per-layer slice source of
        this stacked subtree (same treedef, same stacked leaf shapes)?"""
        try:
            leaves, treedef = jax.tree_util.tree_flatten(element)
        except Exception:
            return False
        if treedef != self.treedef or len(leaves) != len(self.stacked_shapes):
            return False
        return all(tuple(getattr(l, "shape", ())) == s
                   for l, s in zip(leaves, self.stacked_shapes))

    def _gather_leaf(self, x, gathered: P, sharded: P):
        """The placement layer's gather-on-use (zero/partition.py), whose
        BACKWARD issues the per-block reduce-scatter — grad_reduce="scan".
        "post" keeps the plain constraint: cotangents stay gathered through
        the backward scan and the engine's final grad constraint does one
        fused reduction."""
        return gather_on_use(
            x, NamedSharding(self.mesh, gathered),
            NamedSharding(self.mesh, sharded)
            if self.grad_reduce == "scan" else None)

    def gather_slice(self, sliced_element: Any, sec_slices=None) -> Any:
        """Gather one layer's slice of the stacked subtree (leaves without
        dp sharding pass through untouched). With a wire plan, eligible
        leaves gather QUANTIZED (codes + scales on the wire; from the hpZ
        secondary replica's slice when one is held) — the quantized op
        identity is recorded distinctly so the PR 4 collective fingerprints
        hash it stably."""
        from jax.ad_checkpoint import checkpoint_name

        from deepspeed_tpu.comm import comm as _comm

        leaves = self.treedef.flatten_up_to(sliced_element)
        out = []
        for i, (leaf, specs, stacked) in enumerate(
                zip(leaves, self.slice_specs, self.stacked_shapes)):
            if specs is None:
                out.append(leaf)
                continue
            gathered, sharded = specs
            lw = self.wire_leaves[i] if self.wire_leaves is not None else None
            if lw is not None:
                sec_qt = sec_slices[i] if sec_slices is not None else None
                op = (f"zero3_gather[q{lw.bits}"
                      + ("/sec]" if sec_qt is not None else "]"))
                axes = (("ici",) if sec_qt is not None else self.dp_axes)
                _comm.record_engine_collective(
                    op, stacked[1:], getattr(leaf, "dtype", "?"), axes)
                g = lw.gather(leaf, sec_qt, self.grad_reduce)
            else:
                _comm.record_engine_collective(
                    "zero3_gather", stacked[1:], getattr(leaf, "dtype", "?"),
                    self.dp_axes)
                g = self._gather_leaf(leaf, gathered, sharded)
            out.append(checkpoint_name(g, GATHERED_NAME))
        return jax.tree_util.tree_unflatten(self.treedef, out)

    # ------------------------------------------------- hpZ secondary replica
    def build_secondary(self, element: Any):
        """The per-step secondary replica of one matched stacked element:
        a list (aligned with the flattened leaves) of stacked
        QuantizedTensors constrained to the intra-host `secondary` specs —
        ONE inter-host code gather for the whole stack — or None entries
        for leaves that keep the full-width path."""
        from deepspeed_tpu.comm import comm as _comm

        leaves = self.treedef.flatten_up_to(element)
        out = []
        for leaf, lw, stacked in zip(leaves, self.wire_leaves,
                                     self.stacked_shapes):
            if lw is None or lw.sec_q is None:
                out.append(None)
                continue
            _comm.record_engine_collective(
                f"hpz_secondary[q{lw.bits}]", stacked,
                getattr(leaf, "dtype", "?"), self.dp_axes)
            out.append(lw.quantize_stacked(leaf))
        return out

    def slice_secondary(self, sec, i):
        """Layer ``i``'s slices of a build_secondary() result."""
        if sec is None:
            return None
        return [lw.slice_qt(qt, i) if qt is not None else None
                for lw, qt in zip(self.wire_leaves, sec)]

    def constrain_gathered(self, element: Any) -> Any:
        """Re-pin a gathered slice's wired leaves at the GATHERED placement
        (applied to the ring-carry slot right before the body consumes it):
        without the anchor at the use site, GSPMD may store the carry/
        residuals sharded and re-gather the weight at the matmul — at full
        width, unwinding the quantized gather's entire wire win."""
        if self.wire_leaves is None:
            return element
        import jax.lax as lax

        leaves = self.treedef.flatten_up_to(element)
        out = [lax.with_sharding_constraint(leaf, lw.gathered_leaf)
               if lw is not None else leaf
               for leaf, lw in zip(leaves, self.wire_leaves)]
        return jax.tree_util.tree_unflatten(self.treedef, out)


def find_stacked_plan(engine, cfg) -> Optional[StackedGatherPlan]:
    """The model's layer-stacked param subtree, as a gather plan — None
    when there is nothing to prefetch (no stacked key, stage < 3, or no
    leaf actually dp-sharded)."""
    key = stacked_param_keys(engine.module)[0]   # the ring walks one stack
    shapes = getattr(engine.plan, "_master_shapes", None)
    specs = engine.plan.param_specs
    if not (isinstance(shapes, dict) and key in shapes
            and isinstance(specs, dict) and key in specs):
        return None
    sp = StackedGatherPlan(engine.plan, shapes[key], specs[key],
                           grad_reduce=cfg.grad_reduce,
                           remat_gather=cfg.remat_gather,
                           wire=getattr(engine, "_wire", None))
    return sp if sp.active else None


# ---------------------------------------------------------------------------
# the double-buffered prefetch scan (components 1 + 2)
# ---------------------------------------------------------------------------
def prefetched_layer_scan(body, init, xs, unroll: int,
                          stacked: StackedGatherPlan, depth: int):
    """A ``lax.scan`` over layer-stacked ``xs`` where the ZeRO-3 gather of
    layer ``i+depth``'s params is issued while layer ``i`` computes.

    The gathered slices ride the carry as a ``depth``-deep ring buffer, so
    the gather for a future layer has NO data dependency on the current
    layer's compute — which is precisely what lets the latency-hiding
    scheduler overlap the two (inside one scan iteration the naive
    slice → gather → matmul chain is serial by construction). The gather's
    backward re-shards the cotangent per layer (see
    :meth:`StackedGatherPlan._gather_leaf`), and ``remat_gather`` wraps
    the gather in ``jax.checkpoint(..., nothing_saveable)`` so the
    backward REGATHERS instead of saving L gathered slices.
    """
    elements = xs if isinstance(xs, tuple) else (xs,)
    matched = [stacked.matches(e) for e in elements]
    length = stacked.n_layers
    if not any(matched) or length <= 0:
        return jax.lax.scan(body, init, xs, unroll=max(1, int(unroll)))
    depth = max(1, min(int(depth), max(1, length - 1)))

    # ds_wire hpZ: the secondary quantized replica of each matched stacked
    # element, built ONCE per step (one inter-host code gather); per-layer
    # gathers — forward and the remat-replayed backward regather, whose
    # inputs these slices become — then stay on the intra-host axis.
    secondary = None
    if getattr(stacked, "secondary", False):
        secondary = [stacked.build_secondary(e) if m else None
                     for e, m in zip(elements, matched)]

    def slice_prim(i):
        return tuple(jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), e)
            for e in elements)

    if secondary is None:
        slice_at = slice_prim
        raw_gather = lambda sl: tuple(
            stacked.gather_slice(e) if m else e for e, m in zip(sl, matched))
    else:
        def slice_at(i):
            return (slice_prim(i),
                    tuple(stacked.slice_secondary(s, i) if s is not None
                          else None for s in secondary))

        def raw_gather(sl):
            prim, secs = sl
            return tuple(
                stacked.gather_slice(e, sec_slices=secs[j]) if m else e
                for j, (e, m) in enumerate(zip(prim, matched)))

    if stacked.remat_gather:
        gather = jax.checkpoint(
            raw_gather, policy=jax.checkpoint_policies.nothing_saveable)
    else:
        gather = raw_gather

    def rewrap(sliced_tuple):
        return sliced_tuple if isinstance(xs, tuple) else sliced_tuple[0]

    buf = tuple(gather(slice_at(min(j, length - 1))) for j in range(depth))

    def loop(carry, i):
        c, ring = carry
        nxt = gather(slice_at(jnp.minimum(i + depth, length - 1)))
        head = ring[0]
        if secondary is not None or stacked.wire_leaves is not None:
            head = tuple(stacked.constrain_gathered(e) if m else e
                         for e, m in zip(head, matched))
        new_c, y = body(c, rewrap(head))
        return (new_c, ring[1:] + (nxt,)), y

    (final, _), ys = jax.lax.scan(loop, (init, buf), jnp.arange(length),
                                  unroll=max(1, int(unroll)))
    return final, ys


# ---------------------------------------------------------------------------
# the engine-side driver
# ---------------------------------------------------------------------------
class OverlapEngine:
    """Per-engine overlap state: the stacked gather plan, the serial
    (measured) schedule's compiled phases, the async snapshotter, and the
    trace-time layer-scan override."""

    def __init__(self, engine, cfg):
        self.engine = engine
        self.cfg = cfg
        self.scheduler_flags_added: List[str] = []
        self._gather_compiled = None
        self._serial_compute = {}
        self._snapshotter = None
        self._stacked: Optional[StackedGatherPlan] = None
        self._warned_inactive = False

        unsupported = []
        if engine._onebit:
            unsupported.append("1-bit optimizers (shard_map-local step)")
        if engine._nvme_optimizer is not None:
            unsupported.append("NVMe-offloaded optimizer (host-side step)")
        if engine._host_offload_param:
            unsupported.append("host-offloaded params (their stream-in is "
                               "already the gather)")
        self.unsupported = "; ".join(unsupported)
        self._serial_inactive = False
        if cfg.schedule == "serial" and not unsupported and (
                engine.plan.zero_stage < 3 or not engine.plan.dp_axes):
            self._serial_inactive = True
            log_dist(
                "overlap.schedule='serial': nothing to expose — params are "
                f"not dp-sharded on this config (ZeRO stage "
                f"{engine.plan.zero_stage}, dp axes "
                f"{engine.plan.dp_axes}); running the fused step instead "
                "of dispatching an empty gather phase", ranks=[0])
        if self.unsupported:
            log_dist(f"overlap: step restructuring disabled for this engine "
                     f"({self.unsupported}); scheduler flags / async "
                     "checkpoint still apply", ranks=[0])
        else:
            if engine.plan.zero_stage < 3 and cfg.param_prefetch > 0:
                log_dist(
                    f"overlap.param_prefetch: ZeRO stage is "
                    f"{engine.plan.zero_stage} — params are not dp-sharded, "
                    "so there is no per-layer gather to prefetch (stage 3 "
                    "activates it); grad placement is unchanged", ranks=[0])
            self._stacked = find_stacked_plan(engine, cfg)
            if self._stacked is not None and \
                    cfg.param_prefetch >= self._stacked.n_layers > 0:
                log_dist(
                    f"overlap.param_prefetch={cfg.param_prefetch} >= the "
                    f"model's layer count ({self._stacked.n_layers}): the "
                    "whole stack would be gathered up front (no memory win "
                    f"over replication); clamping to "
                    f"{self._stacked.n_layers - 1}", ranks=[0])
        if cfg.scheduler_flags:
            self.scheduler_flags_added = apply_scheduler_flags()
        if cfg.async_checkpoint:
            self._snapshotter = AsyncSnapshotter(engine)

    # ------------------------------------------------------------ scheduling
    @property
    def schedule(self) -> str:
        if self.unsupported:
            return "off"
        if self._serial_inactive:
            return "overlapped"
        return self.cfg.schedule

    @property
    def gathers_layers(self) -> bool:
        """This engine gathers the layer stack's ZeRO-3 leaves itself (the
        serial schedule's phase, the prefetch ring), so the engine's default
        gather-on-use (zero/partition.py::LayerGathers) stays off."""
        return self.schedule == "serial" or (
            self.schedule == "overlapped" and self.cfg.param_prefetch > 0
            and self._stacked is not None)

    def invalidate_compiled(self):
        self._gather_compiled = None
        self._serial_compute = {}

    def scan_context(self):
        """Context manager installing the prefetched layer scan for the
        duration of a TRACE of the step function (jit tracing or the
        ds_doctor abstract re-trace). No-op outside the overlapped
        schedule or when the model exposes no stacked subtree."""
        if self.schedule != "overlapped" or self.cfg.param_prefetch <= 0:
            return nullcontext()
        stacked = self._stacked
        if stacked is None:
            if not self._warned_inactive:
                self._warned_inactive = True
                log_dist(
                    "overlap: param-gather prefetch inactive — the model "
                    "exposes no dp-sharded layer-stacked param subtree "
                    "(key "
                    f"{stacked_param_keys(self.engine.module)[0]!r}"
                    "); the step compiles unrestructured", ranks=[0])
            return nullcontext()
        depth = self.cfg.param_prefetch

        @contextmanager
        def ctx():
            from deepspeed_tpu.models import common as _mcommon

            def impl(body, init, xs, unroll):
                return prefetched_layer_scan(body, init, xs, unroll,
                                             stacked, depth)

            prev = _mcommon.set_layer_scan_impl(impl)
            try:
                yield
            finally:
                _mcommon.set_layer_scan_impl(prev)

        return ctx()

    # --------------------------------------------------- the serial schedule
    def _gathered_shardings(self):
        plan = self.engine.plan
        shapes = plan._master_shapes
        specs = gathered_param_specs(plan, shapes)
        return jax.tree.map(lambda s: NamedSharding(plan.mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    def _gather_phase_bytes(self) -> int:
        plan = self.engine.plan
        shapes = plan._master_shapes
        total = 0
        is_p = lambda x: isinstance(x, P)
        for sh, sp in zip(jax.tree.leaves(shapes),
                          jax.tree.leaves(plan.param_specs, is_leaf=is_p)):
            axes = set()
            for e in _spec_tuple(sp, len(sh.shape)):
                axes.update(_axes_of(e))
            if any(a in plan.dp_axes for a in axes):
                total += _leaf_nbytes(sh)
        return total

    def serial_step(self, state, batch, gas: int):
        """The measured un-overlapped ZeRO-3 schedule: a blocking,
        span-timed all-gather program, then the compute program over the
        gathered params. This is what ``schedule: "overlapped"`` removes
        from the host timeline — the before side of the ledger delta."""
        from deepspeed_tpu.comm import comm as _comm
        from deepspeed_tpu.resilience import chaos as _chaos

        eng = self.engine
        if self._gather_compiled is None:
            from deepspeed_tpu.sharding import sharded_jit

            wire = getattr(eng, "_wire", None)
            if wire is not None and wire.weight_active:
                # ds_wire qwZ on the measured serial schedule: the explicit
                # gather phase moves codes + scales, and the timed comm
                # span bills the actual (padded) wire bytes — the chaos
                # `collective` delay drill inflates the same span
                leaf_fn, self._gather_bytes = wire.serial_gather(
                    eng.plan._master_shapes, eng.plan.param_specs,
                    eng.plan.dp_axes)

                def gather_fn(p):
                    leaves, tdef = jax.tree_util.tree_flatten(p)
                    return tdef.unflatten(
                        [leaf_fn(i, x) for i, x in enumerate(leaves)])

                label = "overlap/zero3_gather_q"
            else:
                gather_fn = lambda p: p
                label = "overlap/zero3_gather"
                self._gather_bytes = self._gather_phase_bytes()
            self._gather_compiled = sharded_jit(
                gather_fn, label=label,
                donate_argnums=(), mesh=eng.mesh,
                in_shardings=(eng.state_shardings.params,),
                out_shardings=self._gathered_shardings())
        group = "+".join(eng.plan.dp_axes) or "world"
        t0 = time.perf_counter()
        inj = _chaos.active_injector()
        if inj is not None and inj.targets("collective"):
            # inside the timed window: an injected delay inflates this
            # phase's comm span exactly like a slow interconnect would
            inj.before("collective", "zero3_gather")
        with eng.mesh:
            params_g = self._gather_compiled(state.params)
        jax.block_until_ready(params_g)
        _comm.record_phase_span("zero3_gather",
                                time.perf_counter() - t0, group,
                                nbytes=self._gather_bytes)
        # key includes the batch's pytree layout: the explicit batch
        # in_shardings pin a structure, so a layout change must rebuild
        # (same contract as engine._get_compiled_train_batch)
        skey = (gas, eng._batch_struct_key(batch))
        if skey not in self._serial_compute:
            def compute_fn(state, params_g, batch):
                scale = (state.scaler.scale if state.scaler is not None
                         else jnp.float32(1.0))
                loss, grads = eng._accumulated_loss_grads(
                    state, batch, gas, scale, fwd_params=params_g)
                return eng._apply_grads(state, grads, loss)

            from deepspeed_tpu.sharding import sharded_jit

            self._serial_compute[skey] = sharded_jit(
                compute_fn, label=f"overlap/serial_compute[gas={gas}]",
                donate_argnums=(0, 1), mesh=eng.mesh,
                in_shardings=(eng.state_shardings,
                              self._gathered_shardings(),
                              eng.sharding.batch_shardings(batch)),
                out_shardings=(eng.state_shardings,
                               eng.sharding.replicated()))
        with eng.mesh:
            return self._serial_compute[skey](state, params_g, batch)

    # -------------------------------------------------------- async snapshot
    def save_checkpoint_async(self, save_dir, tag=None, client_state=None,
                              save_latest=True):
        assert self._snapshotter is not None
        return self._snapshotter.save(save_dir, tag=tag,
                                      client_state=client_state,
                                      save_latest=save_latest)

    @property
    def async_checkpoint(self) -> bool:
        return self._snapshotter is not None


class AsyncSnapshotter:
    """Checkpoint snapshots off the step path (component 4).

    On the step path only a DEVICE-side copy of the state is taken (a few
    ms of HBM bandwidth — and mandatory for correctness: the next step
    DONATES ``engine.state``'s buffers, so a background device→host read
    of the live state would race the donation). A background thread then
    pays the device→host transfer and runs the UNCHANGED PR 1 verified
    save (orbax → sidecars → manifest → 'latest'), so a slow filesystem
    or a big transfer never charges the ``checkpoint`` badput bucket of a
    step. Cost: one extra state copy resident in device memory until the
    background save drains (the classic snapshot trade — size it with the
    ds_prof memory census).
    """

    def __init__(self, engine):
        self.engine = engine
        self._copy = None
        self._lock = _locks.make_lock("overlap.snapshotter")

    def _device_copy(self, state):
        if self._copy is None:
            from deepspeed_tpu.sharding import INHERIT, sharded_jit

            # jnp.copy per leaf: a real on-device copy op — jit output
            # buffers never alias undonated inputs, so the snapshot owns
            # its memory and the step's donation cannot invalidate it
            self._copy = sharded_jit(
                lambda s: jax.tree.map(jnp.copy, s),
                label="overlap/snapshot_copy", donate_argnums=(),
                mesh=self.engine.mesh,
                in_shardings=INHERIT, out_shardings=INHERIT)
        with self.engine.mesh:
            return self._copy(state)

    _warned_multihost = False

    def save(self, save_dir, tag=None, client_state=None, save_latest=True):
        from deepspeed_tpu import telemetry as _telemetry
        from deepspeed_tpu.runtime.checkpoint_engine import engine as ckpt

        eng = self.engine
        if jax.process_count() > 1:
            # the orbax save is a CROSS-HOST collective: running it on a
            # background thread while the main thread dispatches the next
            # step's collectives interleaves two collective streams per
            # host — a deadlock class the watchdog would catch but the
            # schedule should never create. Multi-controller saves stay on
            # the step path (orbax's own async_save still backgrounds the
            # write half).
            if not AsyncSnapshotter._warned_multihost:
                AsyncSnapshotter._warned_multihost = True
                logger.warning(
                    "overlap.async_checkpoint: snapshot saves are "
                    "single-controller only (a background cross-host orbax "
                    "collective would race the step's collectives); using "
                    "the synchronous verified save path")
            return ckpt.save_engine_checkpoint(
                eng, save_dir, tag=tag, client_state=client_state,
                save_latest=save_latest)
        tag = tag or f"global_step{int(eng.state.step)}"
        with self._lock:
            # one in-flight snapshot at a time: a second save while the
            # first still writes would double the resident copy AND race
            # the 'latest' advance ordering. Deliberately blocking inside
            # the lock: the drain IS the serialization the lock exists for
            # (callers are the step loop + at-exit paths, never
            # latency-critical), and no pending committer ever takes
            # overlap.snapshotter — a leaf lock, no cycle possible
            # (wait_for_pending_saves joins outside its own lock and skips
            # the current thread).
            # race-allow: blocking-under-lock — leaf-lock drain is the point
            ckpt.wait_for_pending_saves()
            snap = self._device_copy(eng.state)
            # host-side progress facts captured NOW, not when the
            # background thread gets around to writing them — the commit
            # may land many steps later and must describe THIS instant
            host_meta = ckpt.capture_host_meta(eng)

            def _commit():
                try:
                    with _telemetry.get_tracer().span(
                            "checkpoint_commit_async", cat="checkpoint",
                            background=True, tag=str(tag)):
                        ckpt.save_engine_checkpoint(
                            eng, save_dir, tag=tag, client_state=client_state,
                            save_latest=save_latest, state=snap,
                            force_sync=True, host_meta=host_meta)
                except Exception as e:
                    logger.error(
                        f"async checkpoint snapshot {tag}: background save "
                        f"failed ({e}); 'latest' was not advanced")

            t = _locks.spawn_thread(_commit, daemon=True,
                                    name=f"ds-ckpt-snapshot-{tag}",
                                    owner="checkpoint")
            ckpt.register_pending_save(t)
            t.start()
        return True
