"""The ``overlap`` block: a scheduler preset, a measuring schedule, a snapshot.

ZeRO-3's gather of a layer's weights is NOT here. It is stated where a block
uses its weights (``runtime/zero/partition.py::LayerGathers``, seated in
``models/common.py::remat_wrap``) and is part of every stage-3 step with or
without this block. Until PR 44 this file also held a prefetch ring (a
double-buffered layer scan that gathered layer *i+depth* while layer *i*
computed). PR 36 ran both on a v5e host's four chips on gpt2-xl ZeRO-3
over data=4: the ring was busy 1,919.3 ms a step against the stated
gather's 1,821.3, ``train.mfu`` 41.69 against 43.86,
``train.coll_exposed_frac`` 5.76 against 1.65 (one traced run each, my chip
run, PR 36), and it walked one stack where four of the benchmark's models
hold several. It lost its only measurement and was removed. What is left:

* **latency-hiding scheduler preset** (:func:`apply_scheduler_flags`) —
  the XLA flags that let the TPU scheduler move async collectives behind
  compute, applied once at engine init and reported by ``ds_report``.
* **the serial schedule** (``schedule: "serial"``,
  :meth:`OverlapEngine.serial_step`) — a measuring tool, not a way to
  train fast. One fused XLA program is opaque to host-side spans: its
  collectives never appear as ``cat="comm"`` trace events. The serial
  schedule is the classic blocking ZeRO-3 schedule instead: a separately
  dispatched all-gather program of the whole parameter tree (timed to
  completion, emitted as a rank-matchable comm span with the same
  ``(op, seq, group)`` identity ``ds_prof merge`` aligns on) followed by
  the compute program over the gathered copy. ``ds_prof merge``, the
  perf ledger's goodput block and ``ds_gray``'s evidence chain read that
  ``zero3_gather`` span; the ``collective`` chaos target inflates it
  deterministically for drills. ``schedule: "overlapped"`` (the default)
  names the engine's one fused step, unchanged by this block.
* **async checkpoint snapshot** (:class:`AsyncSnapshotter`) — a device-side
  copy of the state is taken on the step path (HBM-bandwidth fast) and the
  device→host transfer plus the verified orbax/manifest write run on a
  background thread, so the ``checkpoint`` badput bucket stops charging
  the step.

STRICT no-op contract: this module is imported only when the ``overlap``
ds_config block is present and enabled, and with ``schedule:
"overlapped"`` the lowered train step is the text of the step without the
block (asserted in tests/unit/test_overlap.py).
"""

from __future__ import annotations

import os
import time
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.runtime.zero.partition import (ShardingPlan, _axes_of,
                                                  _spec_tuple, drop_dp_axes)
from deepspeed_tpu.utils import locks as _locks
from deepspeed_tpu.utils.logging import log_dist, logger

# ---------------------------------------------------------------------------
# XLA latency-hiding scheduler preset
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
# the engine-side driver
# ---------------------------------------------------------------------------
class OverlapEngine:
    """Per-engine overlap state: the serial (measured) schedule's compiled
    phases and the async snapshotter."""

    def __init__(self, engine, cfg):
        self.engine = engine
        self.cfg = cfg
        self.scheduler_flags_added: List[str] = []
        self._gather_compiled = None
        self._serial_compute = {}
        self._snapshotter = None

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
            log_dist(f"overlap: the serial schedule is off for this engine "
                     f"({self.unsupported}); scheduler flags / async "
                     "checkpoint still apply", ranks=[0])
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

    def invalidate_compiled(self):
        self._gather_compiled = None
        self._serial_compute = {}

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
        gathered params. This is what the fused step (``schedule:
        "overlapped"``) keeps off the host timeline — the before side of
        the ledger delta."""
        from deepspeed_tpu.comm import comm as _comm
        from deepspeed_tpu.resilience import chaos as _chaos

        eng = self.engine
        if self._gather_compiled is None:
            from deepspeed_tpu.sharding import sharded_jit

            self._gather_bytes = self._gather_phase_bytes()
            self._gather_compiled = sharded_jit(
                lambda p: p, label="overlap/zero3_gather",
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
    """Checkpoint snapshots off the step path.

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
