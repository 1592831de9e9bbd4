"""DeepSpeedEngine — the training engine.

Counterpart of the reference's ``deepspeed/runtime/engine.py`` (DeepSpeedEngine
:181, ~3.3k LoC god object). The torch engine wraps an nn.Module and mutates
it through forward/backward/step with hook-driven communication. The TPU-native
engine is functional: all training state (params, fp32 masters, optimizer
state, loss-scale) lives in one ``TrainState`` pytree whose placement comes
from the ZeRO ``ShardingPlan``; a single donated, jitted update advances it.
The reference's three-call API (``forward`` engine.py:1663, ``backward`` :1804,
``step`` :2000) is kept as shims over the same compiled pieces, and
``train_batch(batch)`` is the fused fast path (grad-accumulation microbatches
as a ``lax.scan``).

What the reference does with streams/hooks, XLA does in the scheduler: ZeRO-3
allgather-on-use + prefetch = GSPMD sharded params, gathered where the program
says (the layer stack's leaves where the block uses them, the loss head once a
step: zero/partition.py); overlapped reduce-scatter = grad sharding
constraints; bucket sizes become advisory (SURVEY §7).
"""

from __future__ import annotations

import collections
import inspect
import math
import os
import sys
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu import comm as dist
from deepspeed_tpu import telemetry as _telemetry
from deepspeed_tpu.telemetry.scopes import scope
from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.ops.optimizers import build_optimizer
from deepspeed_tpu.parallel.topology import DATA_AXIS, EXPERT_AXIS, ParallelGrid
from deepspeed_tpu.sharding import INHERIT, sharded_jit
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.fp16.loss_scaler import (CreateLossScaler, DynamicLossScaler,
                                                    LossScaleState, grads_finite)
from deepspeed_tpu.runtime.lr_schedules import LRSchedule, build_lr_schedule
from deepspeed_tpu.runtime.zero.partition import (ShardingPlan, partition_report,
                                                  plan_sharding, stacked_param_keys)
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER, NoopTimer,
                                       STEP_GLOBAL_TIMER, SynchronizedWallClockTimer,
                                       ThroughputTimer, TRAIN_BATCH_TIMER)

MEMORY_OPT_ALLREDUCE_SIZE = 500_000_000


class TrainState(NamedTuple):
    """Everything that changes during training, as one pytree."""
    step: jnp.ndarray            # i32 global step
    params: Any                  # compute-dtype params (what forward reads)
    master: Any                  # fp32 master copy (None => params are master)
    opt_state: Any
    scaler: Any                  # LossScaleState or None
    rng: jnp.ndarray             # PRNG key for dropout etc.
    skipped_steps: jnp.ndarray   # i32


class StepMetrics(NamedTuple):
    loss: jnp.ndarray
    grad_norm: jnp.ndarray
    lr: jnp.ndarray
    loss_scale: jnp.ndarray
    overflow: jnp.ndarray
    # ds_sentry online state checksum (uint32 fold of the updated
    # params/opt_state) — None unless the `sdc` block arms it; a None
    # field is an EMPTY pytree node, so the absent-block step program
    # traces and lowers byte-identically
    checksum: Any = None
    # what the model's loss made beside the loss (``loss_and_aux``), where
    # the model names leaves a rule moves: a routed model's routing counts.
    # It leaves the compiled step with the loss, replicated; None (an empty
    # pytree node) for every other model
    aux: Any = None


def _index_tag(index, shape) -> str:
    """Stable string for a shard's global index range (slices normalized
    against the array shape) — the NVMe swap-file key suffix."""
    idx = tuple((s.start or 0, s.stop if s.stop is not None else dim)
                for s, dim in zip(index, shape))
    return "_".join(f"{a}-{b}" for a, b in idx) or "all"


def _is_optax_like(opt) -> bool:
    return hasattr(opt, "init") and hasattr(opt, "update")


def _supports_lr_override(opt) -> bool:
    if not hasattr(opt, "update"):
        return False
    try:
        return "lr_override" in inspect.signature(opt.update).parameters
    except (TypeError, ValueError):
        return False


def _resolve_stream_overlap(off_opt) -> bool:
    """Double-buffered host streaming for the offloaded optimizer update:
    the ``stream_overlap`` config field wins when set; the
    ``DS_TPU_OFFLOAD_OVERLAP`` env knob is the fallback when it is None
    (or when there is no offload_optimizer block at all)."""
    from deepspeed_tpu.utils import env_flag

    cfg = off_opt.stream_overlap if off_opt is not None else None
    return env_flag("DS_TPU_OFFLOAD_OVERLAP") if cfg is None else bool(cfg)


class DeepSpeedEngine:
    def __init__(self,
                 args=None,
                 model=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu=None,
                 dist_init_required=None,
                 collate_fn=None,
                 config=None,
                 config_class: Optional[DeepSpeedConfig] = None,
                 dont_change_device=False):
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training_data = training_data
        self.collate_fn = collate_fn

        # ---- config ------------------------------------------------------
        if config_class is None:
            config_class = DeepSpeedConfig(config if config is not None else {})
        self._config = config_class

        # ---- distributed backend / mesh ---------------------------------
        if mpu is not None and hasattr(mpu, "mesh"):
            mesh = mpu.mesh
            mics = int(getattr(self._config.zero_config, "mics_shard_size", -1) or -1)
            if mics > 0 and mesh.shape.get("mics", 1) != mics:
                raise ValueError(
                    f"mics_shard_size={mics} with a user-supplied mpu mesh: "
                    "the mesh must already carry a 'mics' axis of that size "
                    "(build it via parallel.topology.build_mesh with "
                    "axis_dims={'mics': ...}), or omit mpu so initialize() "
                    "factors the data axis itself")
            dist.init_distributed(mesh=mesh, verbose=False)
        else:
            mesh_cfg = self._config.mesh_config
            mics = int(getattr(self._config.zero_config, "mics_shard_size", -1) or -1)
            if mics > 0 and mesh_cfg.mics == 1:
                # MiCS (ref zero/mics.py:31): factor the data axis into
                # (data = replica groups, mics = in-group shard) so ZeRO
                # state shards over the small contiguous group only
                if mesh_cfg.data != -1:
                    if mesh_cfg.data % mics:
                        raise ValueError(
                            f"mics_shard_size={mics} does not divide the "
                            f"data axis ({mesh_cfg.data})")
                    mesh_cfg = mesh_cfg.model_copy(
                        update={"data": mesh_cfg.data // mics, "mics": mics})
                else:
                    mesh_cfg = mesh_cfg.model_copy(update={"mics": mics})
            backend = dist.init_distributed(mesh_config=mesh_cfg, verbose=False)
            mesh = backend.mesh
        self.mesh = mesh
        self.grid = ParallelGrid(mesh)
        self.dp_world_size = self.grid.get_data_parallel_world_size()
        self.mp_world_size = self.grid.get_model_parallel_world_size()
        self._config._configure_train_batch_size(self.dp_world_size)

        # ---- watchdog (before any model/state work) ----------------------
        # live hang/desync defense (resilience/watchdog.py + consistency.py),
        # installed FIRST: the startup fingerprint agreement must run before
        # _init_state issues the first sharded computation — two ranks with
        # different configs would otherwise wedge or crash inside state
        # materialization with no DesyncError ever naming the divergence.
        # STRICT no-op when the block is absent: no StepWatchdog object, no
        # monitor thread, no heartbeat writes, no agreement collectives —
        # the per-step cost of a disabled watchdog is two `is None` checks.
        wd_cfg = self._config.watchdog
        self._watchdog = None
        self._heartbeat_path = None
        self._heartbeat_interval = 1
        self._consistency_interval = 0
        if wd_cfg.enabled:
            from deepspeed_tpu.resilience.watchdog import (StepWatchdog,
                                                           set_default_dump_path)

            # barrier / startup-fingerprint timeouts dump to the same file
            set_default_dump_path(wd_cfg.stack_dump_file or None, source="config")
            self._watchdog = StepWatchdog(
                factor=wd_cfg.step_timeout_factor,
                percentile=wd_cfg.step_timeout_percentile,
                window=wd_cfg.window,
                min_timeout=wd_cfg.min_step_timeout,
                startup_timeout=wd_cfg.startup_timeout,
                on_timeout=wd_cfg.on_timeout,
                dump_path=wd_cfg.stack_dump_file or None)
            dist.set_default_barrier_timeout(wd_cfg.barrier_timeout,
                                             source="config")
            hb = wd_cfg.heartbeat_file or os.environ.get("DS_TPU_HEARTBEAT_FILE", "")
            if hb:
                self._heartbeat_path = hb
                self._heartbeat_interval = wd_cfg.heartbeat_interval
            self._consistency_interval = wd_cfg.consistency_interval
            if wd_cfg.check_fingerprint_at_init:
                from deepspeed_tpu.resilience.consistency import \
                    verify_startup_consistency

                # every rank must be running the same (config, topology,
                # code) BEFORE the first collective — a desynced rank fails
                # here, loudly, instead of corrupting training; the deadline
                # covers a peer that died between rendezvous and engine init
                self._config_fingerprint = verify_startup_consistency(
                    self._config._param_dict, mesh=self.mesh,
                    timeout=wd_cfg.barrier_timeout)
        else:
            # same contract as resilience.chaos: a later engine built
            # WITHOUT the block must not inherit the previous engine's
            # barrier deadline or dump file — absent block means plain
            # barriers (manual set_default_barrier_timeout installs are
            # left alone)
            dist.clear_config_barrier_timeout()
            from deepspeed_tpu.resilience.watchdog import clear_config_dump_path

            clear_config_dump_path()

        # ---- model protocol ---------------------------------------------
        # `model` provides init_params(rng) + loss(params, batch, rng) — the
        # functional stand-in for the reference's nn.Module. Alternatively
        # model_parameters carries an initial param pytree and `model` is a
        # bare callable loss_fn(params, batch, rng).
        self.module = model
        if hasattr(model, "loss"):
            self._loss_fn = model.loss
        elif callable(model):
            self._loss_fn = model
        else:
            raise ValueError("model must provide .loss(params, batch, rng) or be callable")

        self.train_dtype = self._config.train_dtype
        self.fp16_enabled = self._config.fp16.enabled
        self.bf16_enabled = self._config.bf16.enabled
        self.zero_stage = self._config.zero_optimization_stage

        # ---- abstract shapes + sharding plan ----------------------------
        seed_key = jax.random.PRNGKey(self._config.seed)
        if model_parameters is not None:
            param_shapes = jax.eval_shape(lambda: model_parameters)
            init_fn = lambda: model_parameters
        elif hasattr(model, "init_params"):
            param_shapes = jax.eval_shape(model.init_params, seed_key)
            init_fn = lambda: model.init_params(seed_key)
        else:
            raise ValueError("Provide model.init_params(rng) or model_parameters")

        # leaves the optimizer leaves alone and a rule of the model's moves
        # (the model protocol's ``ruled_leaves`` / ``loss_and_aux`` /
        # ``apply_rule``: a router's selection bias, balanced from the
        # step's routing counts). None for a model that names none: the
        # step then traces what it always did
        self._ruled = model.ruled_leaves(param_shapes) \
            if hasattr(model, "ruled_leaves") else None
        if self._ruled is not None:
            self._loss_fn = model.loss_and_aux
        self._aux_pending = collections.deque()

        tp_specs = None
        if hasattr(model, "param_partition_specs"):
            tp_specs = model.param_partition_specs()
        self.plan: ShardingPlan = plan_sharding(
            param_shapes, mesh, zero_config=self._config.zero_config, tp_specs=tp_specs,
            stacked_keys=stacked_param_keys(model))
        # the spec registry the plan is a view over — the ONE source every
        # sharded_jit call site reads its in/out shardings from
        self.sharding = self.plan.registry
        log_dist(partition_report(self.plan, param_shapes), ranks=[0])

        # ---- static analysis (ds_doctor) ---------------------------------
        # STRICT no-op when the ``analysis`` block is absent: the analysis
        # package is never imported and no pass runs (asserted in tests).
        # With the block: the schema + sharding passes run HERE — before any
        # state is materialized, so a doomed config dies in milliseconds —
        # and the graph + collective passes run at the first train_batch
        # (the batch shape is only known then), on a re-TRACE of the step,
        # never an extra compile. fail_on=error|warn aborts with
        # AnalysisError; 'never' reports only.
        self._analysis_enabled = (self._config.analysis_present
                                  and self._config.analysis.enabled)
        self._analysis_graph_done = False
        self._analysis_xray_done = False
        # ds_roofline: own block, same once-after-first-step timing as xray
        self._roofline_done = False
        self._roofline_result = None
        self._analysis_batch_shapes = None
        self._collective_fingerprint = None
        if self._analysis_enabled:
            from deepspeed_tpu.analysis import engine_init_analysis

            engine_init_analysis(self, param_shapes)

        # ---- ZeRO-Offload policy ----------------------------------------
        # CPU offload = state lives in host memory (pinned_host memory kind)
        # and streams through the chip inside the step program — the TPU
        # answer to the reference's CPU Adam (csrc/adam/cpu_adam.cpp): HBM
        # capacity is the scarce resource, not FLOPs, so the chip still does
        # the math. NVMe offload (ZeRO-Infinity, swap_tensor/) steps the
        # optimizer host-side with state swapped through the aio layer.
        off_opt = self._config.zero_config.offload_optimizer
        off_param = self._config.zero_config.offload_param
        on_tpu = jax.default_backend() == "tpu"
        self._host_offload_opt = bool(off_opt and off_opt.device == "cpu")
        self._host_offload_param = bool(off_param and off_param.device == "cpu")
        self._nvme_offload = bool(off_opt and off_opt.device == "nvme")
        if (self._host_offload_opt or self._host_offload_param) and not on_tpu:
            # chosen by what the backend can compile, up front: XLA:CPU has
            # the pinned_host memory kind but its SPMD partitioner rejects
            # the placement annotations the streamed step carries (jax
            # 0.9.0: "Side-effect HLO must have sharding"), so the CPU
            # tests train the same config with the state left in place
            logger.warning(
                "offload to host memory needs the TPU backend: this "
                f"{jax.default_backend()} run keeps optimizer state/params "
                "in device memory (same numerics, no offload)")
            self._host_offload_opt = self._host_offload_param = False
        # Moments-only offload: when the fp32 MASTER fits HBM next to the
        # bf16 params + grads (+ remat activations), keep it resident and
        # stream only mu/nu — cuts the per-step host traffic by a third (the
        # reference's offload_optimizer.ratio partial-offload role, decided
        # by capacity instead of a fraction knob). DS_TPU_OFFLOAD_MASTER=
        # host|hbm overrides the capacity heuristic.
        self._offload_master_host = self._host_offload_opt
        if self._host_offload_opt:
            mode = os.environ.get("DS_TPU_OFFLOAD_MASTER", "auto").lower()
            if mode in ("hbm", "device", "resident"):
                self._offload_master_host = False
            elif mode in ("host", "pinned", "cpu"):
                self._offload_master_host = True
            else:
                n = sum(int(np.prod(l.shape))
                        for l in jax.tree.leaves(param_shapes))
                shards = max(1, int(np.prod([mesh.shape[a]
                                             for a in self.plan.dp_axes] or [1])))
                hbm = get_accelerator().hbm_bytes()
                # resident set with master in HBM ≈ fp32 master (4n,
                # dp-sharded at stage>=1) + bf16 params (2n, sharded only at
                # stage 3) + bf16 grads (2n, sharded at stage>=2) + the
                # whole-leaf mu/nu transients + the NEW master tree until XLA
                # aliases it onto the donated old one (measured: it does not,
                # 19.2G at 1.3B on 15.75G) — so auto only keeps the master
                # resident when the margin is wide; force with
                # DS_TPU_OFFLOAD_MASTER=hbm to experiment past the heuristic
                stage = self.plan.zero_stage
                resident = (4 * n / shards
                            + 2 * n / (shards if stage >= 3 else 1)
                            + 2 * n / (shards if stage >= 2 else 1))
                self._offload_master_host = resident > 0.55 * hbm
            if not self._offload_master_host:
                log_dist("ZeRO-Offload: fp32 master stays in HBM; streaming "
                         "moments only (DS_TPU_OFFLOAD_MASTER=host to force "
                         "full offload)", ranks=[0])
        self._nvme_optimizer = None
        if self._nvme_offload:
            from deepspeed_tpu.runtime.swap_tensor.optimizer_swapper import SwappedOptimizer

            folder = off_opt.nvme_path or "/tmp/ds_tpu_nvme_swap"
            if jax.process_count() > 1:
                # each host swaps only its addressable shards; per-host
                # subfolders keep shared-filesystem deployments collision-free
                folder = f"{folder}/host{jax.process_index()}"
            self._nvme_optimizer = SwappedOptimizer(
                swap_folder=folder,
                optimizer_name=self._config.optimizer_name or "adamw",
                optimizer_params=dict(self._config.optimizer_params or {}),
                aio_config=self._config.aio_config.model_dump(),
                buffer_count=off_opt.buffer_count)

        # ---- optimizer ---------------------------------------------------
        self.optimizer = self._configure_optimizer()
        self._lr_supports_override = _supports_lr_override(self.optimizer)

        # 1-bit optimizer family: the update runs inside a shard_map over the
        # data axis so grads stay worker-local and the compressed exchange is
        # real (reference onebit/adam.py + runtime/comm/nccl.py roles).
        self._onebit = bool(getattr(self.optimizer, "is_onebit", False))
        if self._onebit:
            if self._config.fp16.enabled:
                raise ValueError("1-bit optimizers support bf16/fp32 (fp16 dynamic "
                                 "loss scaling would sit inside the compressed loop)")
            if self.zero_stage != 0:
                raise ValueError("1-bit optimizers require ZeRO stage 0 (parity with "
                                 "the reference: compressed comm replaces ZeRO's)")
            for ax, n in dict(mesh.shape).items():
                if ax != DATA_AXIS and n > 1:
                    raise ValueError(f"1-bit optimizers need a pure-DP mesh; axis "
                                     f"{ax!r} has size {n}")
            if self._config.gradient_clipping:
                log_dist("gradient_clipping is ignored with 1-bit optimizers "
                         "(clipping before compression would break error feedback)",
                         ranks=[0])

        # ---- lr schedule -------------------------------------------------
        self.lr_scheduler = self._configure_lr_scheduler()

        # ---- loss scaler -------------------------------------------------
        dynamic = self._config.fp16.loss_scale == 0.0
        self.loss_scaler = CreateLossScaler(
            self.train_dtype, self._config.fp16.loss_scale, dynamic,
            dynamic_loss_args={
                "init_scale": 2.0 ** self._config.fp16.initial_scale_power,
                "scale_window": self._config.fp16.loss_scale_window,
                "min_scale": self._config.fp16.min_loss_scale,
                "delayed_shift": self._config.fp16.hysteresis,
                "consecutive_hysteresis": self._config.fp16.consecutive_hysteresis,
            }) if self.fp16_enabled else None

        # master-weight policy: fp32 master kept when computing in low precision
        # (with NVMe offload the master lives on disk in the SwappedOptimizer)
        self._keep_master = (self.train_dtype != jnp.float32) and (
            self.fp16_enabled or self._config.bf16.master_weights) and \
            self._nvme_optimizer is None
        if self._nvme_optimizer is not None and self.fp16_enabled:
            raise ValueError("NVMe optimizer offload supports bf16/fp32 only "
                             "(fp16 dynamic loss scaling is a device-side loop)")

        # ---- overlap engine ----------------------------------------------
        # runtime/overlap.py: the XLA latency-hiding scheduler preset, async
        # checkpoint snapshots, and the measured un-overlapped "serial"
        # schedule whose gather phase lands as a comm span. STRICT no-op
        # when the block is absent: the module is never imported and the
        # checkpoint path is untouched; present or absent, the fused step
        # traces the same program (asserted in tests).
        self._overlap = None
        if self._config.overlap_present and self._config.overlap.enabled:
            from deepspeed_tpu.runtime.overlap import OverlapEngine

            self._overlap = OverlapEngine(self, self._config.overlap)
        if self._ruled is not None and (
                self._onebit or self._nvme_optimizer is not None
                or self._host_offload_opt or (
                    self._overlap is not None
                    and self._overlap.schedule == "serial")):
            raise NotImplementedError(
                "a model whose rule moves leaves the optimizer leaves alone "
                "(ruled_leaves) trains through the fused train_batch step: "
                "not with a 1-bit or NVMe optimizer, an offloaded optimizer "
                "state or the overlap engine's serial schedule")

        # ---- ZeRO-3 gather-on-use of the layer stack ---------------------
        # zero/partition.py: the plan's rule (None on one chip, at stage
        # 0-2, where no stacked leaf is sharded), applied to what a block is
        # handed while the loss's gradient is traced. Not where the step is
        # manual over the data axes (1-bit) or the serial schedule's phase
        # has gathered the whole tree (runtime/overlap.py): no leaf twice.
        self._layer_gathers = self.plan.layer_gathers
        if self._onebit or (self._overlap is not None
                            and self._overlap.schedule == "serial"):
            self._layer_gathers = None

        # ---- materialize state sharded ----------------------------------
        self.state, self.state_shardings = self._init_state(init_fn, param_shapes, seed_key)

        # ---- compiled steps ---------------------------------------------
        self._compiled_train_batch = {}
        self._compiled_fwd_bwd = None
        self._compiled_apply = None
        self._compiled_eval = None
        self._compiled_loss_grads = {}
        self._grad_buffer = None
        self._last_metrics: Optional[StepMetrics] = None
        self.micro_steps = 0
        self.global_samples = 0
        self.gradient_accumulation_steps = lambda: self._config.gradient_accumulation_steps

        # ---- resilience --------------------------------------------------
        # bad-step sentinel: after K consecutive non-finite/overflow/spike
        # steps, rewind to the last verified checkpoint instead of burning
        # the rest of the job (resilience/sentinel.py)
        res_cfg = self._config.resilience
        self._bad_step_sentinel = None
        self._sentinel_rewinds = 0
        self._ckpt_save_dir = None           # last save/load dir = rewind target
        if res_cfg.sentinel.enabled:
            from deepspeed_tpu.resilience.sentinel import BadStepSentinel

            self._bad_step_sentinel = BadStepSentinel(
                patience=res_cfg.sentinel.patience,
                spike_factor=res_cfg.sentinel.spike_factor,
                window=res_cfg.sentinel.window,
                max_rewinds=res_cfg.sentinel.max_rewinds)
        # ---- rewind ladder (tiered in-memory checkpoints) ----------------
        # resilience/rewind.py: tier-0 host-RAM snapshot ring every
        # ram_interval steps, tier-1 emergency save on preemption, the
        # ladder-walking restore. STRICT no-op when the ``rewind`` block
        # is absent: the module is never imported, zero extra device
        # copies or threads (asserted in tests) — the per-step cost of a
        # disabled ladder is one `is None` check.
        self._rewind = None
        self._last_recovery = None
        if self._config.rewind_present and self._config.rewind.enabled:
            from deepspeed_tpu.resilience.rewind import RewindManager

            self._rewind = RewindManager(self, self._config.rewind)
        # ---- elastic resize (ds_resize) ----------------------------------
        # elasticity.resize: arm the snapshot ladder's survivor-mesh
        # reshard path. Holding the pydantic block is enough — the resize
        # module itself is imported only at a restore that actually
        # crosses a world change (STRICT no-op otherwise: no import, no
        # thread, no device copy — asserted in tests/unit/test_resize.py).
        ecfg = self._config.elasticity_config
        self._elastic_resize = ecfg.resize if ecfg.resize.enabled else None
        from deepspeed_tpu.resilience import chaos as _chaos_mod

        if res_cfg.chaos.enabled:
            _chaos_mod.install_chaos(_chaos_mod.ChaosInjector.from_config(res_cfg.chaos))
        else:
            # don't inherit a previous engine's config-installed drill (env
            # and manual installs are deliberately left alone)
            _chaos_mod.uninstall_config_chaos()

        # ---- telemetry ---------------------------------------------------
        self.wall_clock_breakdown = self._config.wall_clock_breakdown
        self.timers = SynchronizedWallClockTimer() if self.wall_clock_breakdown else NoopTimer()
        self.tput_timer = ThroughputTimer(batch_size=self.train_batch_size(),
                                          steps_per_output=self._config.steps_per_print,
                                          sync_every_step=self.wall_clock_breakdown,
                                          flops_estimator=self._estimate_step_flops)
        from deepspeed_tpu.monitor.monitor import MonitorMaster

        self.monitor = MonitorMaster(self._config.monitor_config)
        # unified telemetry session (telemetry/__init__.py): metrics registry
        # + step tracing + exporters; None when the block is disabled — every
        # per-step hook below guards on that, and module-level consumers
        # (comm timed_op, resilience counters) see the noop registry
        self.telemetry = _telemetry.configure(self._config.telemetry,
                                              monitor=self.monitor)
        # Watchdog stack dumps used to be stderr-only unless the user set an
        # explicit stack_dump_file; route them into the telemetry dir by
        # default so incident bundles (and remote debugging) can capture
        # them. An explicit watchdog.stack_dump_file still wins (it was
        # installed above and this branch is skipped).
        if (self._watchdog is not None
                and not self._config.watchdog.stack_dump_file):
            sess = _telemetry.get_session()
            if sess is not None and sess.output_dir:
                from deepspeed_tpu.resilience.watchdog import \
                    set_default_dump_path

                set_default_dump_path(
                    os.path.join(sess.output_dir, "stacks.txt"),
                    source="config")
        # ---- memory profiler (ds_prof) -----------------------------------
        # HBM live-buffer census + executable accounting + leak sentinel
        # (profiling/memory.py), sampled every profiling.sample_interval
        # steps. STRICT no-op when the ``profiling`` block is absent: the
        # module is never imported and zero census calls run (asserted in
        # tests) — the per-step cost of a disabled profiler is one
        # `is None` check.
        self._mem_profiler = None
        prof_cfg = self._config.profiling
        if self._config.profiling_present and prof_cfg.enabled:
            from deepspeed_tpu.profiling.memory import (MemoryProfiler,
                                                        SpanMemoryTracer)

            self._mem_profiler = MemoryProfiler(
                sample_interval=prof_cfg.sample_interval,
                memory=prof_cfg.memory,
                executable_analysis=prof_cfg.executable_analysis,
                leak_window=prof_cfg.leak_window,
                leak_min_growth_bytes=prof_cfg.leak_min_growth_bytes)
            if prof_cfg.span_memory:
                session = _telemetry.get_session()
                # hook per-span peak deltas into the live tracer; sessions
                # re-fetch through get_tracer(), so wrapping the session's
                # tracer covers every instrumentation point
                if session is not None and session.tracer is not None \
                        and not isinstance(session.tracer, SpanMemoryTracer):
                    session.tracer = SpanMemoryTracer(session.tracer)
        # ---- perf ledger recorder ----------------------------------------
        # structured, attributed benchmark records (perf/recorder.py) behind
        # the ``perf`` ds_config block. STRICT no-op when the block is
        # absent: the perf package is never imported and perf_record()
        # raises — same contract as ``analysis`` / ``profiling``.
        self._perf_recorder = None
        if self._config.perf_present and self._config.perf.enabled:
            from deepspeed_tpu.perf.recorder import PerfRecorder

            self._perf_recorder = PerfRecorder(self, self._config.perf)
        # ---- goodput meter -------------------------------------------------
        # closed per-step badput ledger over the telemetry spans + the
        # jax.monitoring compile-span listener (goodput/recorder.py) behind
        # the ``goodput`` ds_config block. STRICT no-op when the block is
        # absent: the goodput package is never imported, no listener is
        # registered — same contract as ``analysis``/``profiling``/``perf``.
        self._goodput = None
        if self._config.goodput_present and self._config.goodput.enabled:
            from deepspeed_tpu.goodput.recorder import GoodputMeter

            self._goodput = GoodputMeter(self._config.goodput, engine=self)
        # ---- sdc sentry (ds_sentry) ---------------------------------------
        # silent-data-corruption defense (resilience/sdc.py): replay
        # audits on TPU determinism, online state checksums, per-device
        # blame, quarantine-and-evict, poison-free snapshot ladder.
        # STRICT no-op when the ``sdc`` block is absent: the module is
        # never imported, the step metrics carry no checksum, and the
        # lowered step HLO is byte-identical (asserted in tests).
        self._sdc = None
        if self._config.sdc_present and self._config.sdc.enabled:
            from deepspeed_tpu.resilience.sdc import SdcManager

            self._sdc = SdcManager(self, self._config.sdc)
        # ---- gray failure defense (ds_gray) -------------------------------
        # fail-slow defense (resilience/gray.py): straggler-skew evidence
        # fusion, microprobe confirmation (slow-compute/link/host), and
        # quarantine-and-evict via the same fleet-shrink path as ds_sentry.
        # STRICT no-op when the ``gray`` block is absent: the module is
        # never imported, no probes run, and the lowered step HLO is
        # byte-identical (asserted in tests).
        self._gray = None
        if self._config.gray_present and self._config.gray.enabled:
            from deepspeed_tpu.resilience.gray import GrayManager

            self._gray = GrayManager(self, self._config.gray)
        # ---- blackbox flight recorder (ds_blackbox) ------------------------
        # always-on incident forensics (blackbox/): bounded event ring fed
        # by every failure detector through one envelope schema, trigger →
        # atomic incidents/<ts>_<trigger>/ bundle dumps, merged cross-rank
        # by bin/ds_incident. STRICT no-op when the ``blackbox`` block is
        # absent: the module is never imported, and the lowered HLO is
        # byte-identical whether absent or armed (host-side only; both
        # asserted in tests). Producers emit via
        # sys.modules.get("deepspeed_tpu.blackbox") so an unarmed run
        # never even pays the import.
        self._blackbox = None
        if self._config.blackbox_present and self._config.blackbox.enabled:
            from deepspeed_tpu import blackbox as _blackbox_mod

            self._blackbox = _blackbox_mod.configure(
                self._config.blackbox, rank=dist.get_rank())
            if self._blackbox is not None:
                # the startup-consistency hash when the watchdog agreement
                # ran, else the same config_fingerprint the perf ledger
                # stamps — ds_incident merge refuses to mix bundles whose
                # fingerprints disagree (different runs, not one incident)
                fp = getattr(self, "_config_fingerprint", None)
                if fp is None:
                    try:
                        from deepspeed_tpu.resilience.consistency import \
                            config_fingerprint
                        fp = config_fingerprint(
                            self._config.to_dict(),
                            mesh=getattr(self, "mesh", None))
                    except Exception:
                        fp = None
                self._blackbox.config_fingerprint = fp
                # bundles are per-PROCESS (one recorder per host process),
                # so the merge's missing-rank denominator is the process
                # count, not the device count — an 8-device single-process
                # sim writes exactly one bundle and that is complete
                self._blackbox.world_size = jax.process_count()
        self._flops_probe = None
        dist.configure(self._config)
        self.flops_profiler_cfg = self._config.flops_profiler_config
        if self._config.activation_checkpointing_config.partition_activations or \
                self._config.activation_checkpointing_config.cpu_checkpointing:
            from deepspeed_tpu.runtime.activation_checkpointing import checkpointing

            checkpointing.configure(deepspeed_config=self._config)

        self.dataloader = None
        if training_data is not None:
            self.dataloader = self.deepspeed_io(training_data, route="train")

        # arm compression-aware training when ds_config carries a
        # compression_training block (clients may also call
        # deepspeed_tpu.compression.init_compression explicitly)
        self._compression = None
        if self._config.compression_config:
            from deepspeed_tpu.compression.compress import init_compression

            init_compression(self, {"compression_training": self._config.compression_config})

        # curriculum learning (reference engine.py:336 legacy block +
        # data_efficiency.data_sampling.curriculum_learning): seqlen
        # difficulty is applied host-side per train_batch
        self.curriculum_scheduler = None
        from deepspeed_tpu.runtime.data_pipeline.data_sampling import \
            curriculum_config_from_ds

        cl_cfg = curriculum_config_from_ds(self._config._param_dict)
        if cl_cfg.get("enabled"):
            from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler import \
                CurriculumScheduler

            self.curriculum_scheduler = CurriculumScheduler(cl_cfg)

        # Progressive Layer Drop (reference engine.py:334
        # _configure_progressive_layer_drop): the host object mirrors θ(t) for
        # reporting; the jitted step evaluates the same schedule from
        # state.step (see _micro_loss_and_grads) so it needs no host update.
        self.progressive_layer_drop = None
        if self._config.pld_enabled:
            from deepspeed_tpu.runtime.progressive_layer_drop import \
                ProgressiveLayerDrop

            if not self._loss_accepts_pld():
                raise ValueError(
                    "progressive_layer_drop.enabled=true but the model loss "
                    "does not accept a pld_theta kwarg — use a model with "
                    "PLD gates (models.gpt2/bert) or add pld_theta support")
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=self._config.pld_config.theta,
                gamma=self._config.pld_config.gamma)

        # Eigenvalue (reference engine.py:330 _configure_eigenvalue): block
        # Hessian curvature via power iteration, feeding MoQ's per-layer
        # quantization-period stretch at gas boundaries (engine.py:2027).
        self.eigenvalue = None
        self.block_eigenvalue = None
        if self._config.eigenvalue_enabled:
            from deepspeed_tpu.runtime.eigenvalue import Eigenvalue

            ec = self._config.eigenvalue_config
            self.eigenvalue = Eigenvalue(
                verbose=ec.verbose, max_iter=ec.max_iter, tol=ec.tol,
                stability=ec.stability,
                gas_boundary_resolution=ec.gas_boundary_resolution,
                layer_name=ec.layer_name, layer_num=ec.layer_num)

        for key in self._config.advisory_keys_set:
            from deepspeed_tpu.runtime.config import ADVISORY_NOOP_KEYS

            log_dist(f"config key {key!r} accepted (advisory no-op on TPU): "
                     f"{ADVISORY_NOOP_KEYS[key]}", ranks=[0])
        if self._config.dump_state:
            # reference engine.py dump_state role; the partition report was
            # already logged unconditionally above
            self._config.print_config()

        log_dist(f"engine ready: dtype={jnp.dtype(self.train_dtype).name}, zero={self.zero_stage}, "
                 f"dp={self.dp_world_size}, tp={self.mp_world_size}, "
                 f"micro_batch={self.train_micro_batch_size_per_gpu()}, "
                 f"gas={self._config.gradient_accumulation_steps}", ranks=[0])

    # ------------------------------------------------------------- plumbing
    def _configure_optimizer(self):
        if self.client_optimizer is not None:
            if not _is_optax_like(self.client_optimizer):
                raise ValueError("client optimizer must be an optax.GradientTransformation")
            log_dist("Using client optimizer", ranks=[0])
            return self.client_optimizer
        if self._nvme_optimizer is not None:
            import optax

            log_dist("Optimizer state on NVMe (SwappedOptimizer); device-side "
                     "optimizer is identity", ranks=[0])
            return optax.identity()
        name = self._config.optimizer_name
        if name is None:
            raise ValueError("No optimizer in ds_config and none passed to initialize()")
        params = dict(self._config.optimizer_params or {})
        if self._config.optimizer_legacy_fusion:
            log_dist("optimizer.legacy_fusion accepted (advisory no-op on "
                     "TPU): optimizer math is XLA-fused into the train step "
                     "by construction — there is no unfused fallback to "
                     "select away from", ranks=[0])
        log_dist(f"Using DeepSpeed optimizer: {name}", ranks=[0])
        return build_optimizer(name, params)

    def _configure_lr_scheduler(self) -> Optional[LRSchedule]:
        if self.client_lr_scheduler is not None:
            return self.client_lr_scheduler
        if self._config.scheduler_name is not None:
            return build_lr_schedule(self._config.scheduler_name,
                                     self._config.scheduler_params or {})
        return None

    def _base_lr(self) -> float:
        p = self._config.optimizer_params or {}
        return float(p.get("lr", 1e-3))

    def _lr_at(self, step):
        if self.lr_scheduler is not None:
            return self.lr_scheduler.lr_at(step)
        return jnp.float32(self._base_lr())

    def _init_state(self, init_fn, param_shapes, seed_key):
        """Shard-aware state materialization — the zero.Init equivalent
        (partition_parameters.py:603): params are created directly into their
        shards (via jit out_shardings), never fully replicated on one chip."""
        plan = self.plan
        mesh = self.mesh
        to_train_dtype = lambda p: p.astype(self.train_dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p
        to_f32 = lambda p: p.astype(jnp.float32) if jnp.issubdtype(p.dtype, jnp.floating) else p

        param_sh = plan.param_shardings()
        if self._host_offload_param:
            param_sh = jax.tree.map(lambda s: s.with_memory_kind("pinned_host"), param_sh)
        master_sh = plan.master_shardings(
            "pinned_host" if (self._host_offload_opt
                              and self._offload_master_host) else None)

        def build():
            raw = init_fn()
            params = jax.tree.map(to_train_dtype, raw)
            params = jax.lax.with_sharding_constraint(params, plan.param_specs)
            master = None
            if self._keep_master:
                master = jax.tree.map(to_f32, raw)
                master = jax.lax.with_sharding_constraint(master, plan.master_specs)
            opt_target = master if master is not None else params
            opt_state = self.optimizer.init(opt_target)
            return params, master, opt_state

        # abstract pass first: opt-state STRUCTURE without touching memory,
        # so every piece can be allocated straight into its final placement
        # (incl. pinned_host) via out_shardings — building fp32 master +
        # moments on-device and device_put'ing them to host afterwards needs
        # ~7x param bytes of HBM and OOMs exactly the models offload exists
        # for (observed: gpt2-1.3b on one 16G chip)
        with mesh:
            abstract = jax.eval_shape(build)
        a_params, a_master, a_opt = abstract
        if self._onebit:
            opt_specs = self.optimizer.state_partition_specs()
        else:
            opt_specs = plan.map_opt_state_specs(
                a_opt, a_master if a_master is not None else a_params)
        opt_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), opt_specs)
        if self._host_offload_opt:
            opt_sh = jax.tree.map(lambda s: s.with_memory_kind("pinned_host"), opt_sh)

        with mesh:
            params, master, opt_state = sharded_jit(
                build, label="engine/init_state",
                in_shardings=(), donate_argnums=(), mesh=mesh,
                out_shardings=(param_sh,
                               master_sh if self._keep_master else None,
                               opt_sh))()

        if self._nvme_optimizer is not None:
            # seed the swap files from THIS HOST's shards of the params,
            # decomposed the way the step keys them (grad placement)
            with mesh:
                grad_view = jax.device_put(params, self._nvme_grad_shardings())
            named = {}
            for path, leaf in jax.tree_util.tree_flatten_with_path(grad_view)[0]:
                for key, slab, _ in self._host_shard_items(
                        leaf, self._leaf_name(path)):
                    named[key] = slab.astype(np.float32)
            self._nvme_optimizer.init_from_params(named)

        repl = NamedSharding(mesh, P())
        # the scalars are COMMITTED to the mesh like every other field: an
        # uncommitted host scalar carries no mesh in its type, the step's own
        # outputs do, and that difference alone made the second train_batch
        # re-trace and re-compile the whole step (jax 0.9 types shardings)
        on_mesh = lambda x: jax.device_put(x, repl)
        scaler_state = jax.tree.map(on_mesh, self.loss_scaler.initial_state()) \
            if self.loss_scaler else None
        state = TrainState(step=on_mesh(jnp.int32(0)), params=params,
                           master=master, opt_state=opt_state,
                           scaler=scaler_state,
                           rng=on_mesh(seed_key),
                           skipped_steps=on_mesh(jnp.int32(0)))
        shardings = TrainState(
            step=repl,
            params=param_sh,
            master=master_sh if master is not None else None,
            opt_state=opt_sh,
            scaler=jax.tree.map(lambda _: repl, scaler_state) if scaler_state is not None else None,
            rng=repl,
            skipped_steps=repl)
        return state, shardings

    def invalidate_compiled(self):
        """Drop every cached jitted program. Anything that changes traced
        behavior outside the TrainState (arming compression, swapping the
        loss fn) must call this or stale programs keep the old semantics."""
        self._compiled_train_batch = {}
        self._compiled_fwd_bwd = None
        self._compiled_apply = None
        self._compiled_eval = None
        self._compiled_accum = None
        self._compiled_loss_grads = {}
        if getattr(self, "_overlap", None) is not None:
            self._overlap.invalidate_compiled()
        if hasattr(self, "_gen_compiled"):      # hybrid engine generation
            self._gen_compiled = {}

    # -------------------------------------------------------- compute pieces
    def _dev_kind(self, shardings):
        """Device-memory twins of (possibly host-resident) shardings."""
        return jax.tree.map(lambda s: s.with_memory_kind("device"), shardings)

    def _compute_params(self, params, step=None):
        """Inside-trace: stream host-offloaded params into HBM for compute;
        apply the armed compression transform (QAT fake-quant / pruning
        masks, compression/compress.py) when a step is in scope."""
        if self._host_offload_param:
            params = jax.device_put(params, self._dev_kind(self.state_shardings.params))
        comp = getattr(self, "_compression", None)
        if comp is not None and step is not None:
            params = comp.transform(params, step)
        return params

    def _micro_loss_and_grads(self, params, batch, rng, scale, step=None):
        """One microbatch: loss (unscaled, for reporting) + scaled grads.
        ``step`` (traced) feeds the PLD θ(t) schedule when enabled."""
        return self._micro_loss_grads_aux(params, batch, rng, scale, step)[:2]

    def _micro_loss_grads_aux(self, params, batch, rng, scale, step=None):
        """:meth:`_micro_loss_and_grads` + what the loss made beside itself
        for the model's rule (None unless the model names ruled leaves)."""
        kw = {}
        if self.progressive_layer_drop is not None and step is not None:
            from deepspeed_tpu.runtime.progressive_layer_drop import theta_at

            pld = self._config.pld_config
            kw["pld_theta"] = theta_at(step, pld.theta, pld.gamma)

        def scaled_loss(p):
            out = self._loss_fn(p, batch, rng, **kw) if self._loss_accepts_rng() \
                else self._loss_fn(p, batch, **kw)
            loss = out[0] if isinstance(out, tuple) else out
            aux = out[1] if self._ruled is not None else None
            return loss.astype(jnp.float32) * scale, (loss, aux)

        grad = jax.grad(scaled_loss, has_aux=True)
        if self._layer_gathers is None:
            grads, (loss, aux) = grad(params)
        else:
            from deepspeed_tpu.models.common import layer_leaves_hook

            with layer_leaves_hook(self._layer_gathers):
                grads, (loss, aux) = grad(params)
        return loss, grads, aux

    def _loss_accepts_rng(self) -> bool:
        if not hasattr(self, "_rng_ok"):
            try:
                sig = inspect.signature(self._loss_fn)
                self._rng_ok = len([p for p in sig.parameters.values()
                                    if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]) >= 3 \
                    or "rng" in sig.parameters
            except (TypeError, ValueError):
                self._rng_ok = False
        return self._rng_ok

    def _loss_accepts_pld(self) -> bool:
        try:
            return "pld_theta" in inspect.signature(self._loss_fn).parameters
        except (TypeError, ValueError):
            return False

    def _apply_grads(self, state: TrainState, grads, loss,
                     aux=None) -> Tuple[TrainState, StepMetrics]:
        """Shared optimizer phase: unscale→clip→update→cast-back→scale bookkeeping.
        ``aux``: the loss's second result, for the model's rule on the
        leaves the optimizer leaves alone (``self._ruled``).

        Mirrors stage3.step (stage3.py:1775): overflow check, unscale_and_clip,
        optimizer update, fp32→bf16/fp16 copy-back — but as one fused XLA
        program over the sharded state."""
        with scope("optimizer/gnorm"):
            plan = self.plan
            scale = state.scaler.scale if state.scaler is not None else jnp.float32(1.0)

            # move grads to their ZeRO placement (stage>=2: reduce-scattered)
            grads = jax.lax.with_sharding_constraint(grads, plan.grad_specs)

            finite = grads_finite(grads) if state.scaler is not None else jnp.bool_(True)

            # Unscale + global-norm clip WITHOUT materializing a second fp32 grad
            # tree (at 1B params that tree is 4GB): norms are fused reductions,
            # and the per-leaf f32 cast happens inside the (fused) scale op.
            inv_scale = 1.0 / scale
            clip = self._config.gradient_clipping
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
            grad_norm = jnp.sqrt(sq) * inv_scale  # unscaled norm (reference clip_grad_norm_)
            coef = inv_scale
            if clip > 0:
                coef = coef * jnp.minimum(1.0, clip / (grad_norm + 1e-6))
            grads = jax.tree.map(lambda g: (g.astype(jnp.float32) * coef).astype(g.dtype), grads)

        masters = state.master if state.master is not None else state.params
        opt_state_in = state.opt_state

        # ZeRO-Offload big-model path: Adam-family state streams through HBM
        # ONE LEAF AT A TIME — whole-tree stream-in needs params+master+
        # moments resident simultaneously (~7x param bytes) and OOMs exactly
        # the models offload exists for (observed: gpt2-1.3b on 16G)
        from deepspeed_tpu.ops.optimizers import AdamState

        if self._host_offload_opt and state.master is not None and \
                isinstance(opt_state_in, AdamState) and self._offload_streamed():
            return self._apply_grads_streamed_adam(state, grads, loss,
                                                   grad_norm, finite)

        with scope("optimizer/update"):
            # whole-tree stream-in (small models / non-Adam optimizers): XLA
            # overlaps these DMAs with the grad epilogue. When there is no fp32
            # master, params ARE the optimizer target, so param offload implies
            # the same stream-in.
            if state.master is not None:
                if self._host_offload_opt:
                    masters = jax.device_put(masters, self._dev_kind(self.state_shardings.master))
            elif self._host_offload_param:
                masters = jax.device_put(masters, self._dev_kind(self.state_shardings.params))
            if self._host_offload_opt:
                opt_state_in = jax.device_put(opt_state_in, self._dev_kind(self.state_shardings.opt_state))
            lr = self._lr_at(state.step)
            if self._lr_supports_override:
                updates, new_opt = self.optimizer.update(grads, opt_state_in, masters, lr_override=lr)
            else:
                updates, new_opt = self.optimizer.update(grads, opt_state_in, masters)
            import optax

            new_masters = optax.apply_updates(masters, updates)
            if self._ruled is not None:
                # a ruled leaf keeps what it held (its gradient is zero, so
                # are its moments; the update's weight decay is dropped
                # here), then the model's rule moves it from the step's aux
                new_masters = jax.tree.map(
                    lambda ruled, new, old: old if ruled else new,
                    self._ruled, new_masters, masters)
                if aux is not None:
                    new_masters = self.module.apply_rule(new_masters, aux)
            new_masters = jax.lax.with_sharding_constraint(new_masters, plan.master_specs if state.master is not None else plan.param_specs)

            keep = lambda new, old: jnp.where(finite, new, old)
            new_masters = jax.tree.map(keep, new_masters, masters)
            new_opt = jax.tree.map(keep, new_opt, opt_state_in)

        with scope("optimizer/cast"):
            if state.master is not None:
                new_params = jax.tree.map(
                    lambda m, p: m.astype(p.dtype) if jnp.issubdtype(p.dtype, jnp.floating) else m,
                    new_masters, state.params)
                new_params = jax.lax.with_sharding_constraint(new_params, plan.param_specs)
                master_out = new_masters
            else:
                new_params = new_masters
                master_out = None

            if self._host_offload_opt:
                # stream updated fp32 state back out to host memory
                if master_out is not None:
                    master_out = jax.device_put(master_out, self.state_shardings.master)
                new_opt = jax.device_put(new_opt, self.state_shardings.opt_state)
            if self._host_offload_param:
                new_params = jax.device_put(new_params, self.state_shardings.params)

            new_scaler = self.loss_scaler.update(state.scaler, finite) if state.scaler is not None else None
            new_state = TrainState(step=state.step + 1,
                                   params=new_params,
                                   master=master_out,
                                   opt_state=new_opt,
                                   scaler=new_scaler,
                                   rng=jax.random.fold_in(state.rng, state.step),
                                   skipped_steps=state.skipped_steps + (~finite).astype(jnp.int32))
            metrics = StepMetrics(loss=loss, grad_norm=grad_norm, lr=lr,
                                  loss_scale=scale, overflow=~finite, aux=aux)
        return new_state, metrics

    def _offload_streamed(self) -> bool:
        """Whole-tree stream-in when the fp32 state fits HBM next to the
        model (faster: XLA overlaps the DMAs); leaf-streamed otherwise (the
        only way models whose optimizer state exceeds HBM can step at all)."""
        cached = getattr(self, "_offload_streamed_cached", None)
        if cached is not None:
            return cached
        from deepspeed_tpu.utils import env_flag
        if env_flag("DS_TPU_FORCE_STREAMED_OFFLOAD"):
            # test hook: exercise the leaf-streamed (and chunked) update on
            # models small enough to verify numerics against the in-HBM path
            self._offload_streamed_cached = True
            return True
        n = sum(l.size for l in jax.tree.leaves(self.state.params))
        # ZeRO shards the fp32 state over the dp axes: the whole-tree
        # stream-in is PER-DEVICE bytes, not global
        shards = max(1, int(np.prod([self.mesh.shape[a]
                                     for a in self.plan.dp_axes] or [1])))
        hbm = get_accelerator().hbm_bytes()
        # host-resident fp32 streamed in at once: master+mu+nu = 12
        # bytes/param, or mu+nu = 8 when the master stays in HBM (which also
        # shrinks the budget the stream-in must fit into)
        stream_bytes = (12 if self._offload_master_host else 8) * n / shards
        budget = hbm - (0 if self._offload_master_host else 4 * n / shards)
        self._offload_streamed_cached = stream_bytes > 0.6 * budget
        if self._offload_streamed_cached:
            log_dist("ZeRO-Offload: leaf-streamed optimizer update "
                     f"({stream_bytes / 2**30:.1f}G streamed fp32/device vs "
                     f"{budget / 2**30:.1f}G free HBM)", ranks=[0])
        return self._offload_streamed_cached

    def _apply_grads_streamed_adam(self, state: TrainState, grads, loss,
                                   grad_norm, finite) -> Tuple[TrainState, StepMetrics]:
        """Leaf-streamed AdamW for host-offloaded optimizer state.

        The reference's cpu_adam steps each parameter group on the host; here
        the chip still does the math, but each leaf's fp32 master/mu/nu are
        pulled to HBM, updated, and written back BEFORE the next leaf starts
        (a scalar read of each host write is threaded into the next leaf's
        pull, so XLA cannot prefetch the whole state). Peak HBM = one leaf's
        working set. grads arrive already unscaled+clipped."""
        from deepspeed_tpu.ops.optimizers import AdamState

        from deepspeed_tpu.ops.optimizers import (adam_bias_corrections,
                                                  adam_leaf_update)

        cfg = dict(self._config.optimizer_params or {})
        b1, b2 = cfg.get("betas", (0.9, 0.999))
        eps = float(cfg.get("eps", 1e-8))
        wd = float(cfg.get("weight_decay", 0.0))
        adam_w_mode = self._config.optimizer_name != "adam" or \
            bool(cfg.get("adam_w_mode", True))
        bias_correction = bool(cfg.get("bias_correction", True))
        lr = self._lr_at(state.step)

        opt_in: AdamState = state.opt_state
        count = opt_in.count + 1
        cf = count.astype(jnp.float32)
        bc1, bc2 = adam_bias_corrections(cf, b1, b2, bias_correction)

        m_leaves, m_def = jax.tree_util.tree_flatten(state.master)
        g_leaves = jax.tree_util.tree_flatten(grads)[0]
        mu_leaves = jax.tree_util.tree_flatten(opt_in.mu)[0]
        nu_leaves = jax.tree_util.tree_flatten(opt_in.nu)[0]
        p_leaves, p_def = jax.tree_util.tree_flatten(state.params)
        msh = jax.tree_util.tree_flatten(self.state_shardings.master)[0]
        mush = jax.tree_util.tree_flatten(self.state_shardings.opt_state.mu)[0]
        nush = jax.tree_util.tree_flatten(self.state_shardings.opt_state.nu)[0]
        psh = jax.tree_util.tree_flatten(self.state_shardings.params)[0]

        keep = lambda new, old: jnp.where(finite, new, old)
        # ordering: each pull chains on a previous chunk's host write-back.
        # stream_overlap (config; DS_TPU_OFFLOAD_OVERLAP env fallback) chains
        # on the write TWO steps back instead (double-buffering, peak = two
        # working sets). Link-speed dependent: on v5e gpt2-1.3b it measures
        # 0.368 -> 0.384-0.388 MFU, but it destabilizes gpt2-xl (worker
        # faults / 3x collapses), so strict serial stays the global default
        # and the autotuner sweeps the axis per model.
        token = token_prev = jnp.float32(0.0)
        # giant leaves (layer-stacked (L, ...) weights are GBs in fp32 — a
        # gpt2-1.3b fc stack is 1.5G and its streamed update needs ~6 temps
        # of that size at once, observed OOM on 16G) stream in chunks along
        # the stack dim; the updated chunk DUSes back into the host-resident
        # buffer (a host-DMA subrange write, the same mechanism XLA's
        # activation-offload uses)
        import os

        from deepspeed_tpu.utils import env_flag
        chunk_budget = int(os.environ.get("DS_TPU_OFFLOAD_CHUNK_BYTES",
                                          256 << 20))  # fp32 bytes per chunk
        def dev_token(x):
            # ordering token from the DEVICE-side update result: chunk c+1's
            # pull then depends on chunk c's compute, which transitively
            # depends on chunk c's pull — the scheduler cannot prefetch the
            # whole state. (Scalar reads of HOST buffers would order the
            # write-backs too, but host-memory dynamic-slice emission crashes
            # the TPU compiler on several stacked-leaf layouts; write-back
            # DMAs overlapping the next chunk is fine for both correctness
            # and the peak bound, as buffers free on write completion.)
            return x.ravel()[0].astype(jnp.float32)

        serial = not _resolve_stream_overlap(
            self._config.zero_config.offload_optimizer)

        def advance(new_tok):
            nonlocal token, token_prev
            token_prev, token = (new_tok, new_tok) if serial else (token, new_tok)

        out_m, out_mu, out_nu, out_p = [], [], [], []
        for i in range(len(m_leaves)):
            dev = lambda sh: sh.with_memory_kind("device")
            leaf = m_leaves[i]
            n_chunks = 1
            # only ndim>=3 (layer-stacked) leaves chunk: their leading dim is
            # outside the (8,128) tile so host-DMA slices stay tile-aligned;
            # slicing a 2D table's row dim (e.g. a 50257-row vocab embedding)
            # hits sublane misalignment in the TPU DUS emitter
            # chunking exists to bound the HOST-pull working set of m+mu+nu.
            # With a DEVICE-resident master (moments-only offload) the chunked
            # path is a net LOSS: per-chunk DUS re-assembly double-buffers the
            # full fp32 leaf on device (observed 2x1.5G on the fc stacks),
            # while whole-leaf mu/nu pulls stay bounded by the serial token
            # chain at ~2 leaf-sizes.
            if leaf.ndim >= 3 and self._offload_master_host:
                want = max(1, math.ceil(leaf.size * 4 / chunk_budget))
                # only equal chunks (static shapes)
                n_chunks = next((c for c in range(min(want, leaf.shape[0]),
                                                  leaf.shape[0] + 1)
                                 if leaf.shape[0] % c == 0), 1)
            rows = leaf.shape[0] // n_chunks if leaf.ndim >= 1 and n_chunks > 1 else 0

            def pull_update_writeback(sl):
                """One pull→Adam→write-back round on `sl(leaf)`. EVERY pull
                folds in the ordering token (a scalar read chained off a
                previous update): without the data dependency the scheduler
                is free to prefetch all moment leaves at once, defeating the
                bounded-peak guarantee. A DEVICE-resident master (moments-only
                offload) takes no pull, no token fold, and no write-back —
                the chain arithmetic on a resident leaf materializes a full
                copy (observed: six 392M temps on the unchunkable 2D vocab
                embedding, the difference between fitting and OOM at 1.3B)."""
                chain = lambda x: x + token_prev.astype(x.dtype) * 0
                if self._offload_master_host:
                    m = jax.device_put(chain(sl(m_leaves[i])), dev(msh[i]))
                else:
                    m = sl(m_leaves[i])
                mu = jax.device_put(chain(sl(mu_leaves[i])), dev(mush[i]))
                nu = jax.device_put(chain(sl(nu_leaves[i])), dev(nush[i]))
                m_n, mu_n, nu_n = adam_leaf_update(
                    m, mu, nu, sl(g_leaves[i]), lr, b1, b2, eps, wd,
                    adam_w_mode, bc1, bc2)
                m_n = keep(m_n, m)
                mu_n = keep(mu_n, mu)
                nu_n = keep(nu_n, nu)
                p_n = m_n.astype(p_leaves[i].dtype)
                advance(dev_token(m_n))
                m_out = (jax.device_put(m_n, msh[i]) if self._offload_master_host
                         else m_n)
                return (m_out, jax.device_put(mu_n, mush[i]),
                        jax.device_put(nu_n, nush[i]), jax.device_put(p_n, psh[i]))

            if n_chunks == 1:
                hm, hmu, hnu, hp = pull_update_writeback(lambda x: x)
            else:
                hm, hmu, hnu = m_leaves[i], mu_leaves[i], nu_leaves[i]
                hp = p_leaves[i]
                for c in range(n_chunks):
                    start = c * rows
                    cm, cmu, cnu, cp = pull_update_writeback(
                        lambda x: jax.lax.dynamic_slice_in_dim(x, start, rows, 0))
                    dus = jax.lax.dynamic_update_slice_in_dim
                    hm = dus(hm, cm, start, 0)
                    hmu = dus(hmu, cmu, start, 0)
                    hnu = dus(hnu, cnu, start, 0)
                    hp = dus(hp, cp, start, 0)
                hm = jax.device_put(hm, msh[i])
                hmu = jax.device_put(hmu, mush[i])
                hnu = jax.device_put(hnu, nush[i])
                hp = jax.device_put(hp, psh[i])
            out_m.append(hm)
            out_mu.append(hmu)
            out_nu.append(hnu)
            out_p.append(hp)

        new_master = jax.tree_util.tree_unflatten(m_def, out_m)
        new_opt = AdamState(count=keep(count, opt_in.count),
                            mu=jax.tree_util.tree_unflatten(m_def, out_mu),
                            nu=jax.tree_util.tree_unflatten(m_def, out_nu))
        new_params = jax.tree_util.tree_unflatten(p_def, out_p)

        scale = state.scaler.scale if state.scaler is not None else jnp.float32(1.0)
        new_scaler = self.loss_scaler.update(state.scaler, finite) \
            if state.scaler is not None else None
        new_state = TrainState(step=state.step + 1,
                               params=new_params,
                               master=new_master,
                               opt_state=new_opt,
                               scaler=new_scaler,
                               rng=jax.random.fold_in(state.rng, state.step),
                               skipped_steps=state.skipped_steps + (~finite).astype(jnp.int32))
        metrics = StepMetrics(loss=loss, grad_norm=grad_norm, lr=lr,
                              loss_scale=scale, overflow=~finite)
        return new_state, metrics

    def _accumulated_loss_grads(self, state: TrainState, batch, gas: int,
                                scale, fwd_params=None, with_aux=False):
        """Mean loss + mean grads over the accumulation window — shared by the
        fused train step and the NVMe host-step path (gas>1: lax.scan over
        microbatches, reference engine grad-accumulation semantics).
        ``fwd_params`` overrides the forward's params (the overlap engine's
        serial schedule feeds the pre-gathered copy; grads then fall out in
        the gathered layout and the grad-spec constraint does the reduce).
        ``with_aux``: -> (loss, grads, aux) with the loss's second result
        over the window: its integer leaves are counts and add over the
        micro-batches, the others are the last micro-batch's."""
        plan = self.plan
        params_c = self._compute_params(
            state.params if fwd_params is None else fwd_params,
            step=state.step)
        if gas == 1:
            rng = jax.random.fold_in(state.rng, state.step)
            out = self._micro_loss_grads_aux(params_c, batch, rng, scale,
                                             step=state.step)
            return out if with_aux else out[:2]

        def split(x):  # microbatch split: leading dim -> (gas, micro)
            return x.reshape((gas, x.shape[0] // gas) + x.shape[1:])

        mbs = jax.tree.map(split, batch)

        # grad-accumulation dtype (reference data_types.grad_accum_dtype):
        # fp32 is exact; bf16 halves the resident accumulator — the knob that
        # makes gas>1 fit next to a full optimizer state on a 16G chip
        cfg_dt = getattr(self._config.data_types_config, "grad_accum_dtype", None)
        acc_map = {None: jnp.float32, "fp32": jnp.float32, "float32": jnp.float32,
                   "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
                   "fp16": jnp.float16, "float16": jnp.float16}
        if cfg_dt not in acc_map:
            raise ValueError(f"data_types.grad_accum_dtype={cfg_dt!r} not in "
                             f"{sorted(k for k in acc_map if k)} (reference "
                             "config raises on unsupported values too)")
        acc_dtype = acc_map[cfg_dt]

        def body(carry, mb):
            acc, i = carry
            rng = jax.random.fold_in(jax.random.fold_in(state.rng, state.step), i)
            loss, grads, aux = self._micro_loss_grads_aux(
                params_c, mb, rng, scale, step=state.step)
            grads = jax.lax.with_sharding_constraint(grads, plan.grad_specs)
            acc = jax.tree.map(lambda a, g: a + g.astype(acc_dtype), acc, grads)
            return (acc, i + 1), (loss, aux)

        # scope "accumulate": what the scan itself adds (the accumulator, its
        # adds, the micro-batch slices); a micro-batch's ops keep the model's
        with scope("accumulate"):
            zero_acc = jax.tree.map(lambda s: jnp.zeros(s.shape, acc_dtype),
                                    jax.eval_shape(lambda: params_c))
            zero_acc = jax.lax.with_sharding_constraint(zero_acc,
                                                        plan.grad_specs)
            # NOT unrolled: measured on v5e gpt2-760m/gas=4, unroll=2 OOMs by
            # 1.9G and unroll=4 by 4.7G — XLA interleaves the unrolled
            # micros, so each extra body keeps a full live activation set
            # (~1.8G). The scan's sequencing is what bounds gas>1 memory to
            # one micro.
            (acc, _), (losses, auxes) = jax.lax.scan(
                body, (zero_acc, jnp.int32(0)), mbs)
            grads = jax.tree.map(
                lambda g: (g.astype(jnp.float32) / gas).astype(g.dtype), acc)
            if not with_aux:
                return jnp.mean(losses), grads
            return jnp.mean(losses), grads, jax.tree.map(
                lambda a: jnp.sum(a, axis=0)
                if jnp.issubdtype(a.dtype, jnp.integer) else a[-1], auxes)

    def _build_train_batch_fn(self, gas: int):
        """Fused train step: scan over gradient-accumulation microbatches."""
        # ds_sentry online checksum: one extra fused reduction riding the
        # step (like the grad norm). Resolved at BUILD time so the
        # absent-block trace is byte-identical (the sdc module is never
        # imported without its config block).
        sdc_fold = None
        sdc = getattr(self, "_sdc", None)
        if sdc is not None and sdc.checksum_armed:
            from deepspeed_tpu.resilience.sdc import fold_state as sdc_fold

        def step_fn(state: TrainState, batch):
            scale = state.scaler.scale if state.scaler is not None else jnp.float32(1.0)
            mean_loss, grads, aux = self._accumulated_loss_grads(
                state, batch, gas, scale, with_aux=True)
            new_state, metrics = self._apply_grads(state, grads, mean_loss, aux)
            if sdc_fold is not None:
                metrics = metrics._replace(checksum=sdc_fold(
                    (new_state.params, new_state.opt_state)))
            return new_state, metrics

        return step_fn

    def _batch_struct_key(self, batch):
        """Structure key for per-batch-layout program caching: treedef +
        per-leaf rank (shardings depend on rank, jit respecializes on
        shapes itself)."""
        if batch is None:
            return None
        flat, treedef = jax.tree_util.tree_flatten(batch)
        # np.ndim reads .ndim where there is one: no host copy of a
        # pre-placed leaf, and a multi-host global array has no host view
        return (treedef, tuple(np.ndim(x) for x in flat))

    def _batch_in_shardings(self, batch):
        """THE batch in_shardings policy for every compiled step variant:
        registry-derived per-leaf placements (the same ones _shard_batch
        commits) — so even an uncommitted host batch cannot make XLA
        invent a layout — or the explicit INHERIT when no batch is in
        hand (AOT lowering/test paths)."""
        return (self.sharding.batch_shardings(batch)
                if batch is not None else INHERIT)

    def _get_compiled_train_batch(self, gas: int, batch=None):
        key = (gas, self._batch_struct_key(batch))
        if key not in self._compiled_train_batch:
            fn = self._build_train_batch_fn(gas)
            # metrics are scalars — replicated, stated as such
            batch_sh = self._batch_in_shardings(batch)
            self._compiled_train_batch[key] = sharded_jit(
                fn, label=f"engine/train_batch[gas={gas}]",
                donate_argnums=(0,), mesh=self.mesh,
                in_shardings=(self.state_shardings, batch_sh),
                out_shardings=(self.state_shardings,
                               self.sharding.replicated()),
                # xray promise-vs-actual: arg 0 is the TrainState whose
                # families (params/master/opt_state) the ZeRO stage promises
                # partitioned — TrainState is a NamedTuple, so tree paths
                # are indices and the field names ride the meta
                meta={"state_argnum": 0,
                      "state_fields": list(TrainState._fields)})
        return self._compiled_train_batch[key]

    # ------------------------------------------------- 1-bit optimizer path
    def _build_train_batch_fn_onebit(self, gas: int, phase: str):
        """Train step with worker-local grads: loss+grad+momentum+compressed
        sync+update all inside one shard_map over the data axis. Phase
        ('warmup'/'compressed'[...]) is host-selected like the reference's
        python stage switch — no collective inside lax.cond."""
        opt = self.optimizer
        mesh = self.mesh
        spec_of = lambda tree: jax.tree.map(lambda s: s.spec, tree)
        state_specs = spec_of(self.state_shardings)

        def local_step(state: TrainState, batch):
            masters0 = state.master if state.master is not None else state.params
            fwd_params = opt.effective_params(state.params, masters0, state.opt_state)
            fwd_params = self._compute_params(fwd_params, step=state.step)
            state = state._replace(params=fwd_params)
            if gas == 1:
                rng = jax.random.fold_in(state.rng, state.step)
                loss, grads = self._micro_loss_and_grads(state.params, batch, rng,
                                                         jnp.float32(1.0),
                                                         step=state.step)
            else:
                def split(x):
                    return x.reshape((gas, x.shape[0] // gas) + x.shape[1:])

                mbs = jax.tree.map(split, batch)

                def body(carry, mb):
                    acc, i = carry
                    rng = jax.random.fold_in(jax.random.fold_in(state.rng, state.step), i)
                    l, g = self._micro_loss_and_grads(state.params, mb, rng,
                                                      jnp.float32(1.0),
                                                      step=state.step)
                    acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), acc, g)
                    return (acc, i + 1), l

                zero_acc = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
                (acc, _), losses = jax.lax.scan(body, (zero_acc, jnp.int32(0)), mbs)
                grads = jax.tree.map(lambda g: g / gas, acc)
                loss = jnp.mean(losses)

            masters = masters0  # the SYNCED values (never the drifted fwd params)
            lr = self._lr_at(state.step)
            updates, new_opt = opt.update_local(grads, state.opt_state, masters, lr, phase)
            new_masters = jax.tree.map(
                lambda m, u: (m.astype(jnp.float32) + u.astype(jnp.float32)).astype(m.dtype),
                masters, updates)
            if state.master is not None:
                new_params = jax.tree.map(
                    lambda m, p: m.astype(p.dtype) if jnp.issubdtype(p.dtype, jnp.floating) else m,
                    new_masters, state.params)
                master_out = new_masters
            else:
                new_params, master_out = new_masters, None

            loss_avg = jax.lax.pmean(loss.astype(jnp.float32), DATA_AXIS)
            # ||g||-proxy: sqrt(E_w ||g_local||²) — the dense global-mean grad
            # never exists in the compressed stage, so report the RMS of the
            # local-grad norms instead (documented deviation).
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
            gnorm = jnp.sqrt(jax.lax.pmean(sq, DATA_AXIS))
            new_state = TrainState(step=state.step + 1, params=new_params,
                                   master=master_out, opt_state=new_opt,
                                   scaler=None,
                                   rng=jax.random.fold_in(state.rng, state.step),
                                   skipped_steps=state.skipped_steps)
            metrics = StepMetrics(loss=loss_avg, grad_norm=gnorm, lr=lr,
                                  loss_scale=jnp.float32(1.0), overflow=jnp.bool_(False))
            return new_state, metrics

        def step_fn(state, batch):
            batch_specs = jax.tree.map(lambda x: P(DATA_AXIS, *([None] * (x.ndim - 1))), batch)
            repl = jax.tree.map(lambda _: P(), jax.eval_shape(lambda: StepMetrics(
                jnp.float32(0), jnp.float32(0), jnp.float32(0), jnp.float32(0), jnp.bool_(False))))
            return jax.shard_map(local_step, mesh=mesh,
                                 in_specs=(state_specs, batch_specs),
                                 out_specs=(state_specs, repl),
                                 check_vma=False)(state, batch)

        return step_fn

    def _get_compiled_onebit(self, gas: int, phase: str, batch=None):
        key = (gas, phase, self._batch_struct_key(batch))
        if key not in self._compiled_train_batch:
            batch_sh = self._batch_in_shardings(batch)
            self._compiled_train_batch[key] = sharded_jit(
                self._build_train_batch_fn_onebit(gas, phase),
                label=f"engine/train_batch_onebit[gas={gas},{phase}]",
                donate_argnums=(0,), mesh=self.mesh,
                in_shardings=(self.state_shardings, batch_sh),
                out_shardings=(self.state_shardings,
                               self.sharding.replicated()),
                meta={"state_argnum": 0,
                      "state_fields": list(TrainState._fields)})
        return self._compiled_train_batch[key]

    # --------------------------------------------------- NVMe-offload stepping
    # (module-level _index_tag builds the stable shard-range key suffix)
    @staticmethod
    def _leaf_name(path) -> str:
        return "/".join(str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
                        for p in path)

    def _get_compiled_loss_grads(self, gas: int, batch=None):
        """(loss, mean grads, global grad norm) over the accumulation window —
        no optimizer. The norm is computed IN-JIT over the global sharded
        grads, so every host reads the same scalar (multi-host safe)."""
        if getattr(self, "_compiled_loss_grads", None) is None:
            self._compiled_loss_grads = {}
        key = (gas, self._batch_struct_key(batch))
        if key not in self._compiled_loss_grads:
            def fn(state: TrainState, batch):
                loss, grads = self._accumulated_loss_grads(
                    state, batch, gas, jnp.float32(1.0))
                sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads))
                return loss, grads, jnp.sqrt(sq)

            # pin the grads to the plan's grad placement: the NVMe swap-file
            # keys encode shard index ranges, so init and step must agree on
            # the decomposition
            batch_sh = self._batch_in_shardings(batch)
            repl = self.sharding.replicated()
            self._compiled_loss_grads[key] = sharded_jit(
                fn, label=f"engine/loss_grads[gas={gas}]",
                donate_argnums=(), mesh=self.mesh,
                in_shardings=(self.state_shardings, batch_sh),
                out_shardings=(repl, self._nvme_grad_shardings(), repl))
        return self._compiled_loss_grads[key]

    @staticmethod
    def _host_shard_items(leaf, name: str):
        """This host's UNIQUE shards of a global array: [(key, slab, index)].

        Multi-host NVMe decomposition: each host owns the shard index ranges
        any of its devices hold (replicas dedupe by index; a range replicated
        across hosts is updated identically on each — deterministic math, no
        cross-host comm). The key encodes the index range so the swap files
        of different ranges never collide.
        """
        seen = {}
        for sh in leaf.addressable_shards:
            tag = _index_tag(sh.index, leaf.shape)
            if tag not in seen:
                seen[tag] = sh
        return [(f"{name}@{tag}", np.asarray(sh.data), sh.index)
                for tag, sh in sorted(seen.items())]

    def _nvme_grad_shardings(self):
        """The decomposition the NVMe host step is keyed on (grad placement)."""
        return self.plan.grad_shardings()

    def _train_batch_nvme(self, batch, gas: int) -> StepMetrics:
        """ZeRO-Infinity step: grads on device, Adam on host with NVMe-swapped
        state (reference stage3 step + partitioned_optimizer_swapper roles).
        Multi-host: each host steps only its addressable grad shards and the
        global params reassemble from per-device slabs — no host ever
        materializes the full tree."""
        with self.mesh:
            loss, grads, gnorm = self._get_compiled_loss_grads(
                gas, batch)(self.state, batch)
        grad_norm = float(gnorm)
        named_grads = {}
        shard_index = {}     # leaf name -> {index tag -> key}
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        for path, leaf in flat:
            name = self._leaf_name(path)
            for key, slab, idx in self._host_shard_items(leaf, name):
                named_grads[key] = slab.astype(np.float32)
                shard_index.setdefault(name, {})[_index_tag(idx, leaf.shape)] = key
        clip = self._config.gradient_clipping
        scale = 1.0
        if clip and clip > 0 and grad_norm > clip:
            scale = clip / (grad_norm + 1e-6)
        lr = float(self._lr_at(self.state.step))
        new_masters = self._nvme_optimizer.step(named_grads, lr=lr, grad_scale=scale)

        # reassemble the global param tree: every LOCAL device contributes its
        # grad-decomposition slab, then a plain device_put reshards to the
        # param placement (collective copy; the step is disk-bound anyway)
        flat_p, treedef = jax.tree_util.tree_flatten_with_path(self.state.params)
        flat_g = jax.tree_util.tree_flatten(grads)[0]
        new_leaves = []
        for (path, p_leaf), g_leaf in zip(flat_p, flat_g):
            name = self._leaf_name(path)
            per_dev = []
            for sh in g_leaf.addressable_shards:
                key = shard_index[name][_index_tag(sh.index, g_leaf.shape)]
                slab = np.asarray(new_masters[key], dtype=p_leaf.dtype)
                per_dev.append(jax.device_put(slab, sh.device))
            garr = jax.make_array_from_single_device_arrays(
                g_leaf.shape, g_leaf.sharding, per_dev)
            new_leaves.append(garr)
        new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
        new_params = jax.device_put(new_params, self.state_shardings.params)
        self.state = self.state._replace(
            step=self.state.step + 1,
            params=new_params,
            rng=jax.random.fold_in(self.state.rng, self.state.step))
        return StepMetrics(loss=loss, grad_norm=jnp.float32(grad_norm),
                           lr=jnp.float32(lr), loss_scale=jnp.float32(1.0),
                           overflow=jnp.bool_(False))

    # ----------------------------------------------------------- public API
    def train_batch(self, batch=None, data_iter=None) -> jnp.ndarray:
        """Consume one *global* batch (all microbatches) and take one step.

        The idiomatic entry point (reference PipelineEngine.train_batch:286 has
        the same contract). Returns the mean loss.
        """
        if self._watchdog is None:
            return self._train_batch_outer(batch, data_iter)
        # armed before the data fetch: a wedged input pipeline is a hang
        # like any other — the deadline covers data + device step + the
        # host syncs in _post_step; disarm feeds the step-time history
        self._watchdog.arm()
        try:
            return self._train_batch_outer(batch, data_iter)
        finally:
            self._watchdog.disarm()

    def _train_batch_outer(self, batch, data_iter):
        gas = self._config.gradient_accumulation_steps
        step = getattr(self, "_host_step", 0)
        # fetching the batch and placing it on the mesh
        with _telemetry.get_tracer().span("data", trace=step, step=step):
            if batch is None:
                assert data_iter is not None, "train_batch needs a batch or data_iter"
                batch = next(data_iter)
            if self.curriculum_scheduler is not None:
                from deepspeed_tpu.runtime.data_pipeline.data_sampling import \
                    apply_seqlen_curriculum

                difficulty = self.curriculum_scheduler.update_difficulty(
                    getattr(self, "_host_step", 0) + 1)
                batch = apply_seqlen_curriculum(batch, difficulty)
            batch = self._shard_batch(batch)
        self.timers(TRAIN_BATCH_TIMER).start()
        self.tput_timer.start()
        return self._train_batch_inner(batch, gas)

    def _train_batch_inner(self, batch, gas):
        if self._analysis_enabled:
            self._run_step_analysis(batch, gas)
        if self._flops_probe is None:
            # abstract batch shape for the lazy TFLOPs estimate (holds no
            # device buffers; see _estimate_step_flops)
            self._flops_probe = (jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch), gas)
        from deepspeed_tpu.resilience import chaos as _chaos_mod

        # chaos step hook + consistency cadence run inside train_batch's
        # armed region, so an injected (or real) stall in either is covered
        inj = _chaos_mod.active_injector()
        if inj is not None and inj.targets("train_step"):
            inj.before("train_step", f"step={getattr(self, '_host_step', 0) + 1}")
        loss = self._train_batch_instrumented(batch, gas)
        if self._analysis_enabled and not self._analysis_xray_done and \
                "xray" in (self._config.analysis.passes or ()):
            # post-GSPMD x-ray AFTER the first step: the program table now
            # holds compiled programs with captured abstract args. Opt-in
            # by naming the pass — each analyzed program costs one AOT
            # compile (same path as aot_memory_analysis), not a trace.
            self._analysis_xray_done = True
            from deepspeed_tpu.analysis.xray import engine_xray_analysis

            engine_xray_analysis(self)
        if not self._roofline_done and self._config.roofline_present and \
                self._config.roofline.enabled:
            # ds_roofline AFTER the first step, same xray-style timing:
            # price every compiled program against the chip peak table
            # (one memoized AOT compile each). STRICT no-op without the
            # block — the module is never imported (asserted in tests).
            self._roofline_done = True
            from deepspeed_tpu.analysis.roofline import \
                engine_roofline_analysis

            engine_roofline_analysis(self)
        if self._consistency_interval and \
                self._host_step % self._consistency_interval == 0:
            from deepspeed_tpu.resilience.consistency import \
                check_step_agreement

            # ds_sentry: cross the online state checksum through the
            # agreement round too — dp-replicated STATE, not just the
            # loss scalar, must agree across hosts
            extra = (self._sdc.agreement_bytes(self._last_metrics)
                     if self._sdc is not None else b"")
            check_step_agreement(self._host_step, float(loss),
                                 rng=self.state.rng, extra=extra)
        return loss

    def _run_step_analysis(self, batch, gas):
        """ds_doctor step-0 hook. First batch: abstract re-trace of the
        exact step function about to compile → graph + collective passes
        (may raise AnalysisError per analysis.fail_on — i.e. BEFORE the
        first compile burns accelerator time). Later batches: a cheap
        shape-stability check (each new shape silently compiles a whole
        new program) that warns once and stands down."""
        if not self._analysis_graph_done:
            from deepspeed_tpu.analysis import engine_graph_analysis
            from deepspeed_tpu.analysis.graph_lint import batch_shape_map

            self._analysis_graph_done = True
            self._analysis_batch_shapes = batch_shape_map(batch)
            engine_graph_analysis(self, batch, gas)
        elif self._analysis_batch_shapes is not None:
            from deepspeed_tpu.analysis.findings import AnalysisReport
            from deepspeed_tpu.analysis.graph_lint import diff_batch_shapes

            findings = diff_batch_shapes(self._analysis_batch_shapes, batch)
            if findings:
                # report + count, never abort: a mid-run shape change is a
                # perf bug, not a correctness one (aborting is the
                # watchdog's call, not the linter's); warn once per run
                self._analysis_batch_shapes = None
                report = AnalysisReport().extend(findings, "graph")
                report.count_into_registry()
                log_dist(report.render("ds_doctor: batch shape changed"),
                         ranks=[0])

    def _step_reads_host(self) -> bool:
        """True where this step's own bookkeeping reads the step's outputs
        on the host, and so waits for the device: a log line, the monitor,
        a telemetry session, synchronized timers. Otherwise the step has no
        host sync of its own and the caller's read of the loss ends it."""
        every = self._config.steps_per_print
        return bool(self.wall_clock_breakdown or self.monitor.enabled
                    or _telemetry.get_session() is not None
                    or (every and (getattr(self, "_host_step", 0) + 1)
                        % every == 0))

    def _train_batch_instrumented(self, batch, gas):
        tracer = _telemetry.get_tracer()
        step = getattr(self, "_host_step", 0)
        with tracer.span("train_batch", trace=step, step=step):
            if self._sdc is not None:
                # audit-interval steps stash a device-side copy of the
                # pre-step state + batch so after_step can replay the
                # exact step against the same compiled program
                self._sdc.maybe_stash(step + 1, batch, gas)
            # the compiled step's call returns (the device runs on)
            with tracer.span("dispatch", step=step):
                if self._nvme_optimizer is not None:
                    metrics = self._train_batch_nvme(batch, gas)
                elif self._onebit:
                    phase = self.optimizer.phase_for_step(step)
                    with self.mesh:
                        self.state, metrics = self._get_compiled_onebit(
                            gas, phase, batch)(self.state, batch)
                elif self._overlap is not None and \
                        self._overlap.schedule == "serial":
                    # the measured un-overlapped ZeRO-3 schedule: a
                    # blocking, span-timed all-gather phase, then the
                    # compute program — what `overlap.schedule:
                    # "overlapped"` removes from the host timeline
                    # (runtime/overlap.py module docstring)
                    self.state, metrics = self._overlap.serial_step(
                        self.state, batch, gas)
                else:
                    with self.mesh:
                        self.state, metrics = self._get_compiled_train_batch(
                            gas, batch)(self.state, batch)
            if self._step_reads_host():
                # the step's first host sync, in one named place: what
                # follows would block on its first read anyway
                with tracer.span("wait", step=step):
                    jax.block_until_ready(metrics.loss)
            # all host work after it
            with tracer.span("post_step", step=step):
                self._last_metrics = metrics
                self.micro_steps += gas
                self.global_samples += self.train_batch_size()
                self._post_step(metrics)
                self._report_aux(metrics.aux)
                if self._bad_step_sentinel is not None:
                    self._check_bad_step(metrics)
                from deepspeed_tpu.resilience import chaos as _chaos_mod

                _inj = _chaos_mod.active_injector()
                if _inj is not None and _inj.bitflip_armed():
                    # chaos `bitflip` fault class: corrupt the post-step state
                    # BEFORE the sdc audit looks at it — exactly the window a
                    # real cosmic-ray flip lands in
                    _flipped = _inj.perturb_state(self.state, self._host_step)
                    if _flipped is not None:
                        self.state = _flipped
                if self._sdc is not None:
                    # replay audit + blame; may raise FleetResizeEvent
                    # (quarantine-and-evict) or rewind the engine in place
                    self._sdc.after_step(self._host_step, metrics)
                if self._gray is not None:
                    # fail-slow evidence fusion + microprobe; may raise
                    # FleetResizeEvent (quarantine-and-evict) or GrayError
                    self._gray.after_step(self._host_step, metrics)
                if self._rewind is not None:
                    # AFTER the sentinel: a step the sentinel flagged (or a
                    # rewound-to step) must not enter the tier-0 ring
                    self._rewind.maybe_snapshot(self._host_step, metrics)
                if self._blackbox is not None:
                    # flight-recorder heartbeat: one locked deque append — the
                    # rolling step tail every incident bundle ships
                    self._blackbox.on_step(self._host_step)
                # the timer stop syncs on the loss, so the enclosing span's
                # duration covers the device step, not just its dispatch
                self.timers(TRAIN_BATCH_TIMER).stop(sync_obj=metrics.loss)
                self.tput_timer.stop(global_step=True, sync_obj=metrics.loss)
        if self.eigenvalue is not None:
            # OUTSIDE the TRAIN_BATCH_TIMER/tput window AND the
            # train_batch span: the power-iteration estimate used to
            # inflate gas-boundary step times and deflate reported
            # throughput — it is its own measured phase now
            with _telemetry.get_tracer().span(
                    "eigenvalue", step=getattr(self, "_host_step", 0)):
                self._maybe_update_eigenvalue(batch)
        if self.flops_profiler_cfg.enabled and \
                getattr(self, "_host_step", 0) == self.flops_profiler_cfg.profile_step:
            self._run_flops_profiler(batch, gas)
        return metrics.loss

    def _run_flops_profiler(self, batch, gas: int):
        """Profile the compiled train step (reference engine.forward:1675-1693
        drives FlopsProfiler at flops_profiler.profile_step)."""
        from deepspeed_tpu.profiling.flops_profiler.profiler import FlopsProfiler

        cfg = self.flops_profiler_cfg
        if self._nvme_optimizer is not None:
            logger.warning("flops profiler: unsupported for the NVMe-offload "
                           "optimizer path (host-side stepping); skipping")
            return
        prof = FlopsProfiler(ds_engine=self)
        # profile the step function the engine actually runs for this config;
        # _host_step was already incremented by _post_step, so the step just
        # executed used phase_for_step(_host_step - 1)
        if self._onebit:
            phase = self.optimizer.phase_for_step(
                max(0, getattr(self, "_host_step", 1) - 1))
            step_fn = self._build_train_batch_fn_onebit(gas, phase)
        else:
            step_fn = self._build_train_batch_fn(gas)
        try:
            with self.mesh:
                prof.profile_fn(step_fn, self.state, batch,
                                params=self.state.params)
        except Exception as e:
            logger.warning(f"flops profiling failed: {e}")
            return
        if dist.get_rank() == 0:
            prof.print_model_profile(profile_step=cfg.profile_step,
                                     module_depth=cfg.module_depth,
                                     top_modules=cfg.top_modules,
                                     detailed=cfg.detailed,
                                     output_file=cfg.output_file)

    def _shard_batch(self, batch):
        """Place a host batch onto the mesh, batch dim over the DP axes.

        Single-host: the batch is global; device_put scatters it. Multi-host:
        each process holds its local 1/nproc share (what DeepSpeedDataLoader
        yields), assembled into the global array without any cross-host copy
        via make_array_from_process_local_data.
        """
        multihost = jax.process_count() > 1

        def put(x):
            # ONE source for batch placement: the registry (clamped per rank)
            sh = self.sharding.batch_sharding(np.ndim(x))
            if hasattr(x, "sharding") and x.sharding == sh:
                return x
            x = np.asarray(x)
            if multihost:
                return jax.make_array_from_process_local_data(sh, x)
            return jax.device_put(x, sh)

        return jax.tree.map(put, batch)

    # --- reference 3-call API -------------------------------------------
    def forward(self, batch, *args, **kwargs):
        """Compute loss AND stash this microbatch's gradients (fused — same
        cost as the reference's forward+backward pair; see module docstring)."""
        if self._onebit or self._ruled is not None:
            raise NotImplementedError(
                "1-bit optimizers (grads must stay worker-local) and models "
                "with ruled leaves (the rule reads the step's aux) use the "
                "fused train_batch() path")
        with _telemetry.get_tracer().span("fwd", step=getattr(self, "_host_step", 0)):
            self.timers(FORWARD_GLOBAL_TIMER).start()
            batch = self._shard_batch(batch)
            if (self._compiled_fwd_bwd is not None and
                    getattr(self, "_fwd_bwd_struct", None)
                    != self._batch_struct_key(batch)):
                self._compiled_fwd_bwd = None   # batch layout changed: rebuild
            if self._compiled_fwd_bwd is None:
                self._fwd_bwd_struct = self._batch_struct_key(batch)
                def fwd_bwd(state: TrainState, batch):
                    scale = state.scaler.scale if state.scaler is not None else jnp.float32(1.0)
                    rng = jax.random.fold_in(jax.random.fold_in(state.rng, state.step),
                                             jnp.int32(0))
                    loss, grads = self._micro_loss_and_grads(
                        self._compute_params(state.params, step=state.step),
                        batch, rng, scale, step=state.step)
                    grads = jax.lax.with_sharding_constraint(grads, self.plan.grad_specs)
                    return loss, grads

                self._compiled_fwd_bwd = sharded_jit(
                    fwd_bwd, label="engine/fwd_bwd",
                    donate_argnums=(), mesh=self.mesh,
                    in_shardings=(self.state_shardings,
                                  self.sharding.batch_shardings(batch)),
                    out_shardings=(self.sharding.replicated(),
                                   self.plan.grad_shardings()))
            with self.mesh:
                loss, grads = self._compiled_fwd_bwd(self.state, batch)
            self._pending_grads = grads
            self.timers(FORWARD_GLOBAL_TIMER).stop(sync_obj=loss)
        return loss

    __call__ = forward

    def backward(self, loss=None, allreduce_gradients=True, release_loss=False):
        """Accumulate the stashed microbatch grads into the grad buffer."""
        with _telemetry.get_tracer().span("bwd", step=getattr(self, "_host_step", 0)):
            self.timers(BACKWARD_GLOBAL_TIMER).start()
            assert getattr(self, "_pending_grads", None) is not None, \
                "backward() must follow forward() (grads are computed fused)"
            grads = self._pending_grads
            self._pending_grads = None
            if self._grad_buffer is None:
                self._grad_buffer = grads
            else:
                if self._compiled_accum is None:
                    grad_sh = self.plan.grad_shardings()
                    self._compiled_accum = sharded_jit(
                        lambda a, g: jax.tree.map(lambda x, y: x + y.astype(x.dtype), a, g),
                        label="engine/grad_accum", donate_argnums=(0,),
                        mesh=self.mesh, in_shardings=(grad_sh, grad_sh),
                        out_shardings=grad_sh)
                with self.mesh:
                    self._grad_buffer = self._compiled_accum(self._grad_buffer, grads)
            self._micro_loss = loss
            self.micro_steps += 1
            self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss

    _compiled_accum = None

    def is_gradient_accumulation_boundary(self) -> bool:
        return self.micro_steps % self._config.gradient_accumulation_steps == 0

    def step(self):
        """Apply the optimizer at a gradient-accumulation boundary."""
        self.timers(STEP_GLOBAL_TIMER).start()
        if not self.is_gradient_accumulation_boundary():
            self.timers(STEP_GLOBAL_TIMER).stop()
            return  # mid-accumulation: reference engine also no-ops the model step
        if self._watchdog is not None:
            self._watchdog.arm()
        try:
            self._step_at_boundary()
        finally:
            if self._watchdog is not None:
                self._watchdog.disarm()

    def _step_at_boundary(self):
        with _telemetry.get_tracer().span("step", step=getattr(self, "_host_step", 0)):
            assert self._grad_buffer is not None, "step() called with no accumulated gradients"
            gas = self._config.gradient_accumulation_steps
            if self._compiled_apply is None:
                def apply_fn(state, grads, loss):
                    grads = jax.tree.map(lambda g: g / gas, grads)
                    return self._apply_grads(state, grads, loss)

                self._compiled_apply = sharded_jit(
                    apply_fn, label="engine/apply_grads",
                    donate_argnums=(0, 1), mesh=self.mesh,
                    in_shardings=(self.state_shardings,
                                  self.plan.grad_shardings(),
                                  self.sharding.replicated()),
                    out_shardings=(self.state_shardings,
                                   self.sharding.replicated()))
            loss = self._micro_loss if self._micro_loss is not None else jnp.float32(0.0)
            with self.mesh:
                self.state, metrics = self._compiled_apply(self.state, self._grad_buffer, loss)
            self._grad_buffer = None
            self._last_metrics = metrics
            self.global_samples += self.train_batch_size()
            self._post_step(metrics)
            if self._bad_step_sentinel is not None:
                self._check_bad_step(metrics)
            if self._rewind is not None:
                self._rewind.maybe_snapshot(self._host_step, metrics)
            self.timers(STEP_GLOBAL_TIMER).stop(sync_obj=metrics.loss)

    def eval_batch(self, batch):
        """Loss without grads (for eval loops)."""
        batch = self._shard_batch(batch)
        if (self._compiled_eval is not None and
                getattr(self, "_eval_struct", None)
                != self._batch_struct_key(batch)):
            self._compiled_eval = None          # batch layout changed: rebuild
        if self._compiled_eval is None:
            self._eval_struct = self._batch_struct_key(batch)
            def ev(state, batch):
                p = self._compute_params(state.params, step=state.step)
                out = self._loss_fn(p, batch, state.rng) if self._loss_accepts_rng() \
                    else self._loss_fn(p, batch)
                return out[0] if isinstance(out, tuple) else out

            self._compiled_eval = sharded_jit(
                ev, label="engine/eval_batch", donate_argnums=(),
                mesh=self.mesh,
                in_shardings=(self.state_shardings,
                              self.sharding.batch_shardings(batch)),
                out_shardings=self.sharding.replicated())
        with self.mesh:
            return self._compiled_eval(self.state, batch)

    def _report_aux(self, aux, wait=False):
        """Hand the model the host values of its loss's second result
        (``module.report_aux(step, aux)``: a routed model's counter), with
        no host sync of this step's own: the copy to the host is started
        here and read by a LATER step's call, once it has landed; ``wait``
        (the end of a run) reads what is still pending."""
        report = getattr(self.module, "report_aux", None)
        if report is None:
            return
        if aux is not None:
            for leaf in jax.tree.leaves(aux):
                leaf.copy_to_host_async()
            self._aux_pending.append((self._host_step, aux))
        while self._aux_pending and (wait or all(
                leaf.is_ready()
                for leaf in jax.tree.leaves(self._aux_pending[0][1]))):
            step, landed = self._aux_pending.popleft()
            report(step, jax.tree.map(np.asarray, landed))

    def _post_step(self, metrics: StepMetrics):
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        # host-side step counter: never force a device sync just for logging
        self._host_step = getattr(self, "_host_step", 0) + 1
        step = self._host_step
        if self._heartbeat_path is not None and \
                step % self._heartbeat_interval == 0:
            from deepspeed_tpu.resilience.watchdog import touch_heartbeat

            # liveness proof for the launcher's supervision loop: mtime
            # advancing = steps completing (works even when this process's
            # Python threads can't be reached — the ABSENCE of touches is
            # the signal)
            touch_heartbeat(self._heartbeat_path)
        if self.progressive_layer_drop is not None:
            # mirror of the jitted θ(t) — reference engine.py updates PLD state
            # host-side each step; here it is reporting-only (the compiled
            # step already evaluated the same schedule from state.step)
            self.progressive_layer_drop.update_state(step)
        if self._config.steps_per_print and step % self._config.steps_per_print == 0:
            log_dist(f"step={step} loss={float(metrics.loss):.4f} "
                     f"lr={float(metrics.lr):.3e} gnorm={float(metrics.grad_norm):.3f}"
                     + (f" scale={float(metrics.loss_scale):.0f}" if self.fp16_enabled else ""),
                     ranks=[0])
            if self._config.memory_breakdown:
                from deepspeed_tpu.runtime.utils import see_memory_usage

                see_memory_usage(f"after step {step}", force=True)
        if self.monitor.enabled:
            self.monitor.write_events([("Train/Samples/train_loss", float(metrics.loss), self.global_samples),
                                       ("Train/Samples/lr", float(metrics.lr), self.global_samples)])
        session = _telemetry.get_session()
        if session is not None:
            self._record_step_telemetry(session, metrics, step)
        if self._goodput is not None:
            # classifies the PREVIOUS step (this step's train_batch span is
            # still open here) — live goodput/* series lag one step
            self._goodput.on_step(step)
        if self._mem_profiler is not None:
            self._mem_profiler.maybe_sample(self, step)

    def memory_census(self):
        """On-demand live-buffer census attributed to this engine's state
        (params / master / optimizer state / grad buffer / misc vs other);
        returns a :class:`~deepspeed_tpu.profiling.memory.CensusResult`.
        Works with or without the ``profiling`` block — this is the
        interactive entry point, the block is the sampling one."""
        from deepspeed_tpu.profiling.memory import census, named_engine_pytrees

        return census(named_engine_pytrees(self))

    def perf_record(self, metric: str, value: float, unit: str, **kwargs):
        """Append one structured entry to the perf ledger (``perf``
        ds_config block): the headline triple plus fingerprint / git rev /
        env facts / per-step samples / telemetry attribution. Returns the
        entry dict. Raises when the ``perf`` block is absent or disabled —
        a silently dropped benchmark record is worse than an error."""
        if self._perf_recorder is None:
            raise RuntimeError(
                "perf_record() needs the ds_config 'perf' block (the perf "
                "recorder is a strict no-op without it)")
        return self._perf_recorder.record(metric, value, unit, **kwargs)

    def aot_memory_analysis(self, batch, gas=None):
        """XLA ``memory_analysis`` of the exact train step this engine
        would compile for ``batch`` — WITHOUT executing it: no step runs,
        no step buffers are allocated. This is the autotuner's exact OOM
        check: argument/output/temp bytes from the compiler's own ledger
        instead of a first-order model. COST: the AOT ``lower().compile()``
        does NOT fully prime jax's jit dispatch cache — a later real
        ``train_batch`` re-traces and re-pays most of the compile
        (measured ~25% reuse on cpu jax 0.4.37) — so callers that go on
        to run the step pay roughly one extra compile for the analysis.
        Returns the byte dict or None (host-stepped NVMe / 1-bit
        shard_map paths have no single jitted step; some backends expose
        no analysis)."""
        if self._nvme_optimizer is not None or self._onebit:
            return None
        gas = int(gas or self._config.gradient_accumulation_steps)

        def abstract(x):
            arr = x if hasattr(x, "shape") else np.asarray(x)
            return jax.ShapeDtypeStruct(
                arr.shape, arr.dtype,
                sharding=self.sharding.batch_sharding(len(arr.shape)))

        shapes = jax.tree.map(abstract, batch)
        jitted = self._get_compiled_train_batch(gas, shapes)
        try:
            with self.mesh:
                mem = jitted.lower(self.state, shapes).compile().memory_analysis()
        except Exception as e:
            logger.warning(f"aot memory_analysis unavailable: {e}")
            return None
        if mem is None:
            return None
        out = {}
        for key in ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "alias_size_in_bytes",
                    "generated_code_size_in_bytes"):
            out[key.replace("_size_in_bytes", "")] = int(getattr(mem, key, 0) or 0)
        return out

    def _record_step_telemetry(self, session, metrics: StepMetrics, step: int):
        """Per-step registry updates + exporter flush cadence. Gated on the
        LIVE session (not the construction-time self.telemetry), so sessions
        installed via telemetry.install_session() get the same series; the
        float() reads force one host sync per step — the same cost the
        monitor fan-out already pays, and what the user opted into by
        enabling telemetry."""
        reg = session.registry
        reg.counter("train/steps").inc()
        reg.counter("train/samples").inc(self.train_batch_size())
        reg.gauge("train/loss").set(float(metrics.loss))
        reg.gauge("train/grad_norm").set(float(metrics.grad_norm))
        reg.gauge("train/lr").set(float(metrics.lr))
        if self.fp16_enabled:
            reg.gauge("train/loss_scale").set(float(metrics.loss_scale))
        if bool(metrics.overflow):
            reg.counter("train/overflow_steps").inc()
        sps = self.tput_timer.avg_samples_per_sec()
        if sps > 0:
            reg.gauge("train/samples_per_sec").set(sps)
        try:
            stats = jax.local_devices()[0].memory_stats() or {}
            reg.gauge("device/bytes_in_use").set(float(stats.get("bytes_in_use", 0)))
            reg.gauge("device/peak_bytes_in_use").set(float(stats.get("peak_bytes_in_use", 0)))
        except Exception:
            pass  # memory_stats is backend-dependent (absent on CPU)
        session.step_end(step)

    def _estimate_step_flops(self) -> float:
        """Analytical FLOPs of ONE global train batch (jaxpr matmul/conv walk,
        profiling/flops_profiler). Called lazily by the ThroughputTimer's
        first log line and cached there; 0.0 when nothing can be traced yet
        (no batch seen / host-stepped NVMe path / 1-bit shard_map step)."""
        if self._flops_probe is None or self._nvme_optimizer is not None \
                or self._onebit:
            return 0.0
        from deepspeed_tpu.profiling.flops_profiler.profiler import \
            count_jaxpr_flops

        batch_shapes, gas = self._flops_probe
        with self.mesh:
            flops, _ = count_jaxpr_flops(
                self._build_train_batch_fn(gas), self.state, batch_shapes)
        _telemetry.get_registry().gauge("train/flops_per_batch").set(float(flops))
        return float(flops)

    def _check_bad_step(self, metrics: StepMetrics):
        """Bad-step sentinel (resilience.sentinel config block): feed the
        host-side loss/overflow to the sentinel; when it trips, rewind
        through the SNAPSHOT LADDER — the in-RAM tier-0 snapshot when the
        ``rewind`` block holds one (milliseconds, no disk reload), else
        the last verified disk checkpoint (the load path walks back past
        corrupt tags itself). With nothing to rewind to, or past the
        rewind budget, raise BadStepError for the elastic agent /
        launcher to handle. Each rewind counts
        ``resilience/sentinel_rewinds{tier=}``."""
        from deepspeed_tpu.resilience.sentinel import BadStepError

        sentinel = self._bad_step_sentinel
        if not sentinel.observe(float(metrics.loss), overflow=bool(metrics.overflow)):
            return
        reason = sentinel.last_reason
        has_ram = self._rewind is not None and self._rewind.has_ram_snapshot()
        if self._ckpt_save_dir is None and not has_ram:
            raise BadStepError(
                f"bad-step sentinel tripped ({reason}, patience="
                f"{sentinel.patience}) and no checkpoint has been saved or "
                "loaded this run (and no RAM snapshot is held) — nothing "
                "to rewind to")
        if self._sentinel_rewinds >= sentinel.max_rewinds:
            raise BadStepError(
                f"bad-step sentinel tripped ({reason}) after "
                f"{self._sentinel_rewinds} rewind(s) — giving up")
        self._sentinel_rewinds += 1
        logger.warning(f"bad-step sentinel: {reason} for {sentinel.patience} "
                       f"consecutive step(s); rewinding through the snapshot "
                       f"ladder (rewind "
                       f"{self._sentinel_rewinds}/{sentinel.max_rewinds})")
        tier = None
        if has_ram:
            info = self._rewind.restore_from_ram()
            if info is not None:
                tier = "ram"
        if tier is None:
            if self._ckpt_save_dir is None:
                raise BadStepError(
                    f"bad-step sentinel tripped ({reason}): the RAM "
                    "snapshot was unusable and no checkpoint has been "
                    "saved or loaded this run — nothing to rewind to")
            path, _ = self.load_checkpoint(self._ckpt_save_dir)
            if path is None:
                raise BadStepError(
                    f"bad-step sentinel tripped ({reason}) but no restorable "
                    f"checkpoint was found in {self._ckpt_save_dir}")
            tier = (getattr(self, "_last_recovery", None) or {}).get("tier",
                                                                     "disk")
        _telemetry.get_registry().counter(
            "resilience/sentinel_rewinds", labels={"tier": tier}).inc()
        _telemetry.get_tracer().instant("sentinel_rewind", cat="resilience",
                                        reason=reason, tier=tier)
        _bb = sys.modules.get("deepspeed_tpu.blackbox")
        if _bb is not None:
            _bb.record("sentinel_rewind", "error",
                       {"reason": reason, "tier": tier,
                        "rewind": self._sentinel_rewinds,
                        "max_rewinds": sentinel.max_rewinds},
                       step=getattr(self, "_host_step", None))
        sentinel.reset()

    # ------------------------------------------------------------ accessors
    def curriculum_learning_enabled(self) -> bool:
        return self.curriculum_scheduler is not None

    def curriculum_enabled_legacy(self) -> bool:
        """reference engine.py:509 name parity."""
        return self.curriculum_learning_enabled()

    def set_custom_curriculum_learning_schedule(self, schedule_func_dict):
        """reference engine.py:425: install a custom difficulty function
        ({'get_difficulty': fn(step)->int})."""
        assert self.curriculum_scheduler is not None, \
            "curriculum learning is not enabled in this config"
        fn = schedule_func_dict["get_difficulty"] \
            if isinstance(schedule_func_dict, dict) else schedule_func_dict
        self.curriculum_scheduler.set_custom_get_difficulty(fn)

    def _maybe_update_eigenvalue(self, batch):
        """Gas-boundary MoQ coupling (reference engine.py:2025-2035): every
        ``gas_boundary_resolution`` steps while quantization stages are armed,
        re-estimate block eigenvalues on the first microbatch and stretch the
        per-layer quantization periods. Factors are trace-time constants, so a
        CHANGE invalidates compiled steps — they move only when a block's
        normalized curvature crosses a 0.25 boundary, so recompiles are rare.
        The measurement informs steps AFTER this one (the reference computes
        pre-step; one step of lag is the price of keeping the train step
        free of host round-trips)."""
        comp = getattr(self, "_compression", None)
        step = getattr(self, "_host_step", 0)
        if (comp is None or not comp.any_quant_armed()
                or step % self.eigenvalue.gas_boundary_resolution
                or not comp.any_precision_switch(step)):
            # the reference gates on quantizer.any_precision_switch()
            # (engine.py:2025): once every layer is at its terminal bit
            # width the estimate can no longer change anything — stop paying
            # for power iterations
            return
        mb = self.train_micro_batch_size_per_gpu()
        micro = jax.tree.map(lambda x: x[:mb], batch)

        def loss_scalar(p, b):
            out = self._loss_fn(p, b, None) if self._loss_accepts_rng() \
                else self._loss_fn(p, b)
            return out[0] if isinstance(out, tuple) else out

        rng = jax.random.fold_in(self.state.rng, 0xE1 + step)
        self.block_eigenvalue = self.eigenvalue.compute_eigenvalue(
            loss_scalar, self.state.params, micro, rng)
        if self.block_eigenvalue:
            raw = [ev for ev, _ in self.block_eigenvalue.values()]
            old = getattr(comp, "_ev_factors", None)
            factors = []
            for l, ev in enumerate(raw):
                new = 1 + int(ev * 4)
                if old is not None and l < len(old) and new != old[l]:
                    # hysteresis: power iteration restarts from random v0 and
                    # post_process renormalizes per measurement, so estimates
                    # near a 0.25 bucket edge wobble — accept a flip only when
                    # 4·ev moved past the ADJACENT bucket's midpoint, else a
                    # boundary-riding layer recompiles the train step every
                    # gas boundary
                    if abs(4.0 * ev - (old[l] - 0.5)) <= 1.0:
                        new = old[l]
                factors.append(new)
            if comp.set_eigenvalue_factors(
                    factors, layer_name=self.eigenvalue.layer_name, step=step):
                self.invalidate_compiled()

    def eigenvalue_enabled(self) -> bool:
        """reference engine.py:485 name parity."""
        return self.eigenvalue is not None

    def pld_enabled(self) -> bool:
        """reference engine.py:475 name parity."""
        return self.progressive_layer_drop is not None

    def pld_theta(self) -> float:
        """reference engine.py:479: current θ(t) of the PLD schedule (the
        value the NEXT step will use; the jitted step computes it on-device)."""
        return (self.progressive_layer_drop.get_theta()
                if self.progressive_layer_drop is not None else 1.0)

    def train_batch_size(self) -> int:
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self) -> int:
        return self._config.train_micro_batch_size_per_gpu

    def get_lr(self):
        return [float(self._lr_at(self.state.step))]

    def get_global_grad_norm(self) -> Optional[float]:
        return float(self._last_metrics.grad_norm) if self._last_metrics else None

    def get_loss_scale(self) -> float:
        return float(self.state.scaler.scale) if self.state.scaler is not None else 1.0

    @property
    def skipped_steps(self) -> int:
        return int(self.state.skipped_steps)

    @property
    def global_steps(self) -> int:
        return int(self.state.step)

    def zero_optimization(self) -> bool:
        return self.zero_stage > 0

    def zero_optimization_stage(self) -> int:
        return self.zero_stage

    def get_data_parallel_world_size(self):
        return self.dp_world_size

    def get_model_parallel_world_size(self):
        return self.mp_world_size

    def module_state_dict(self):
        """Gathered (unsharded) params on host — reference module_state_dict."""
        with self.mesh:
            gathered = sharded_jit(
                lambda p: p, label="engine/consolidate_params",
                donate_argnums=(), mesh=self.mesh,
                in_shardings=(self.state_shardings.params,),
                out_shardings=jax.tree.map(lambda _: NamedSharding(self.mesh, P()),
                                           self.state.params))(self.state.params)
        return jax.tree.map(np.asarray, gathered)

    # ------------------------------------------------------------ dataloader
    def deepspeed_io(self, dataset, batch_size=None, route=None,
                     data_sampler=None, **kwargs):
        """Build a DeepSpeedDataLoader over ``dataset``.

        ``route`` must be ``"train"`` for the loader that feeds training:
        only then does the metric-based curriculum sampler AUTO-construct and
        become the engine's checkpointed curriculum state. Loaders built with
        ``route=None`` or ``route="eval"`` never auto-construct one — so a
        validation loader built first can't silently bind the curriculum (and
        its checkpointed position) to the wrong dataset. An explicitly passed
        ``data_sampler`` still binds on route=None (passing one is already
        intentional); route="eval" keeps even explicit samplers loader-local.
        (The engine's own ``training_data`` loader passes route="train".)
        """
        from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader

        bs = batch_size or self.train_batch_size()

        def _file_based_curriculum():
            # metric-based curriculum sampling (reference DeepSpeedDataSampler,
            # data_sampling/data_sampler.py): configured when the
            # data_efficiency block carries curriculum metrics with analyzer
            # index files — distinct from the seqlen-TRUNCATION curriculum,
            # which has no per-sample index files
            de = self._config.data_efficiency_config or {}
            cl = de.get("data_sampling", {}).get("curriculum_learning", {})
            metrics = cl.get("curriculum_metrics", {})
            file_based = {n: m for n, m in metrics.items()
                          if "index_to_sample_path" in m
                          or m.get("clustering_type") == "single_cluster"}
            if (de.get("enabled", True) and cl.get("enabled") and file_based
                    and de.get("data_sampling", {}).get("enabled", True)):
                return de, cl, file_based
            return None

        if (data_sampler is None and route == "train"
                and getattr(self, "_data_sampler", None) is None):
            # Eval loaders (route='eval') and repeat calls never build or
            # overwrite the training sampler — its position is checkpointed
            # state.
            found = _file_based_curriculum()
            if found:
                de, cl, file_based = found
                from deepspeed_tpu.runtime.data_pipeline.data_sampler import \
                    DeepSpeedDataSampler

                cfg = dict(de)
                cfg["data_sampling"] = dict(de["data_sampling"])
                cfg["data_sampling"]["curriculum_learning"] = {
                    **cl, "curriculum_metrics": file_based}
                data_sampler = DeepSpeedDataSampler(cfg, len(dataset), bs)
                pending = getattr(self, "_pending_sampler_state", None)
                if pending:
                    data_sampler.load_state_dict(pending)
                    self._pending_sampler_state = None
        elif (route is None and data_sampler is None
                and getattr(self, "_data_sampler", None) is None
                and (getattr(self, "_pending_sampler_state", None) is not None
                     or _file_based_curriculum() is not None)):
            # a metric curriculum is configured (or its checkpoint state is
            # pending) but this loader's route is ambiguous — a caller from
            # before the route narrowing building its training loader without
            # route= would otherwise silently train on uniform sampling (or
            # restart the curriculum from sample 0). route='eval' is an
            # explicit choice and stays silent.
            logger.warning(
                "a metric-based curriculum is configured but this loader was "
                "built with route=None, which does NOT engage the curriculum "
                "sampler; pass route='train' on the training loader (or "
                "route='eval' to silence this for eval loaders)")
        # A sampler becomes the engine's checkpointed curriculum state when
        # the route says train. An EXPLICITLY passed sampler also binds on
        # route=None (the pre-narrowing contract — passing one is already an
        # intentional act); only the AUTO-construction above requires the
        # explicit route, because that is what could silently bind to the
        # wrong dataset. route='eval' samplers ride the loader only.
        if (data_sampler is not None and route in (None, "train")
                and getattr(self, "_data_sampler", None) is None):
            self._data_sampler = data_sampler
        dl_kwargs = {}
        if self._config.dataloader_drop_last is not None:
            # reference "dataloader_drop_last" top-level key (config.py:941)
            dl_kwargs["drop_last"] = bool(self._config.dataloader_drop_last)
        return DeepSpeedDataLoader(dataset, batch_size=bs,
                                   collate_fn=self.collate_fn,
                                   data_sampler=data_sampler, **dl_kwargs)

    # ------------------------------------------------------------ checkpoint
    def _touch_heartbeat_now(self):
        """Heartbeat touch outside the step cadence: long between-step
        phases (a retried checkpoint commit, a load) are progress, not a
        wedge — without these touches the launcher's stale-heartbeat
        supervision would kill a healthy job mid-save. A single commit
        longer than ``--heartbeat_timeout`` still needs the timeout sized
        above it (documented in CONFIG.md)."""
        if self._heartbeat_path is not None:
            from deepspeed_tpu.resilience.watchdog import touch_heartbeat

            touch_heartbeat(self._heartbeat_path)
        if self._watchdog is not None:
            # a save/load reached from INSIDE an armed step (sentinel
            # rewind) is step-sized work, not step-time-sized — push the
            # deadline out to startup_timeout instead of async-aborting a
            # healthy multi-minute restore at the step deadline
            self._watchdog.extend_if_armed()

    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True,
                        exclude_frozen_parameters=False):
        from deepspeed_tpu.runtime.checkpoint_engine.engine import save_engine_checkpoint

        self._ckpt_save_dir = save_dir      # the bad-step sentinel's rewind target
        self._touch_heartbeat_now()
        with _telemetry.get_tracer().span("save_checkpoint", cat="checkpoint"):
            try:
                if self._overlap is not None and self._overlap.async_checkpoint:
                    # overlap.async_checkpoint: this span covers only the
                    # device-side snapshot copy; the device→host transfer
                    # + verified write run on a background thread whose
                    # span is tagged background=True (the goodput ledger
                    # does not charge it to the step)
                    return self._overlap.save_checkpoint_async(
                        save_dir, tag=tag, client_state=client_state,
                        save_latest=save_latest)
                return save_engine_checkpoint(self, save_dir, tag=tag, client_state=client_state,
                                              save_latest=save_latest)
            finally:
                self._touch_heartbeat_now()

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False, custom_load_fn=None):
        from deepspeed_tpu.runtime.checkpoint_engine.engine import load_engine_checkpoint

        self._touch_heartbeat_now()
        with _telemetry.get_tracer().span("load_checkpoint", cat="checkpoint"):
            path, client_state = load_engine_checkpoint(
                self, load_dir, tag=tag,
                load_optimizer_states=load_optimizer_states,
                load_module_only=load_module_only)
        self._touch_heartbeat_now()
        if path is not None:
            self._ckpt_save_dir = load_dir  # the bad-step sentinel's rewind target
        return path, client_state
