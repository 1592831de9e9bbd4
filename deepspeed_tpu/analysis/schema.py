"""Pass 3a — recursive config schema walk + cross-field constraints.

``runtime/config.py`` already rejects unknown TOP-level keys with
did-you-mean hints and every pydantic sub-block forbids extra fields
(with the same hints, via ``DeepSpeedConfigModel``); this pass goes two
steps further, as findings instead of a first-error exception:

* every sub-block is validated INDEPENDENTLY, so one report lists every
  broken block instead of stopping at the first;
* the raw-dict blocks the runtime consumes permissively (``autotuning``,
  ``data_efficiency``, ``sparse_attention``, legacy
  ``curriculum_learning``) are walked against their accepted key sets —
  a typo there used to be a silent no-op, the worst failure mode a
  config surface can have;
* cross-FIELD constraints that are individually valid but jointly
  wrong (ZeRO stage vs offload, 1-bit optimizer vs stage/fp16, MiCS vs
  mesh divisibility, watchdog vs telemetry) are checked statically,
  instead of erroring at engine init after the job already scheduled.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from deepspeed_tpu.analysis.findings import Finding

RULE_UNKNOWN_KEY = "config/unknown-key"
RULE_INVALID = "config/invalid-value"
RULE_CROSS_FIELD = "config/cross-field"

def _block_models() -> Dict[str, type]:
    """Top-level key -> pydantic block model (mirrors DeepSpeedConfig)."""
    from deepspeed_tpu.compression.config import CompressionConfig
    from deepspeed_tpu.runtime import config as C
    from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig

    return {
        "fp16": C.FP16Config, "bf16": C.BF16Config, "bfloat16": C.BF16Config,
        "zero_optimization": DeepSpeedZeroConfig,
        "comms_logger": C.CommsLoggerConfig,
        "flops_profiler": C.FlopsProfilerConfig,
        "activation_checkpointing": C.ActivationCheckpointingConfig,
        "tensorboard": C.TensorboardConfig, "wandb": C.WandbConfig,
        "csv_monitor": C.CSVConfig, "pipeline": C.PipelineConfig,
        "tpu": C.TPUMeshConfig, "checkpoint": C.CheckpointConfig,
        "data_types": C.DataTypesConfig, "aio": C.AioConfig,
        "elasticity": C.ElasticityConfig,
        "hybrid_engine": C.HybridEngineConfig,
        "gradient_compression": C.GradientCompressionConfig,
        "eigenvalue": C.EigenvalueConfig,
        "progressive_layer_drop": C.PLDConfig,
        "resilience": C.ResilienceConfig, "rewind": C.RewindConfig,
        "sdc": C.SdcConfig, "gray": C.GrayConfig,
        "watchdog": C.WatchdogConfig,
        "telemetry": C.TelemetryConfig, "analysis": C.AnalysisConfig,
        "profiling": C.ProfilingConfig, "perf": C.PerfConfig,
        "serving": C.ServingConfig, "goodput": C.GoodputConfig,
        "overlap": C.OverlapConfig,
        "roofline": C.RooflineConfig, "blackbox": C.BlackboxConfig,
        "compression_training": CompressionConfig,
    }


def _check_raw_block(pd: dict, findings: List[Finding]) -> None:
    """Unknown-key walk over the raw-dict blocks, against the same
    accepted-key sets config parsing enforces (runtime/config.py
    ``RAW_BLOCK_KEYS``) — here as one finding per key so the report is
    complete instead of first-error-wins."""
    from deepspeed_tpu.runtime.config import RAW_BLOCK_KEYS
    from deepspeed_tpu.runtime.config_utils import format_unknown_key_hints

    for where, accepted in RAW_BLOCK_KEYS.items():
        head, _, tail = where.partition(".")
        block = pd.get(head)
        if tail and isinstance(block, dict):
            block = block.get(tail)
        if not isinstance(block, dict):
            continue
        for key in sorted(set(block) - accepted):
            findings.append(Finding(
                rule=RULE_UNKNOWN_KEY, severity="error",
                message=(f"unknown key "
                         f"{format_unknown_key_hints({key}, accepted)} in "
                         f"the {where} block — it would be silently ignored"),
                citation=f"{where}.{key}", pass_name="schema"))


def _trim(msg: str, limit: int = 400) -> str:
    msg = " ".join(str(msg).split())
    return msg if len(msg) <= limit else msg[:limit] + "…"


def _cross_field(cfg, pd: dict, findings: List[Finding]) -> None:
    from deepspeed_tpu.runtime.config import (ONEBIT_ADAM_OPTIMIZER,
                                              ONEBIT_LAMB_OPTIMIZER,
                                              ZERO_ONE_ADAM_OPTIMIZER)

    def add(severity, message, citation):
        findings.append(Finding(rule=RULE_CROSS_FIELD, severity=severity,
                                message=message, citation=citation,
                                pass_name="schema"))

    zc = cfg.zero_config
    stage = int(zc.stage)
    if zc.offload_param is not None and stage < 3:
        add("error",
            f"zero_optimization.offload_param requires ZeRO stage 3 (params "
            f"are only partitioned at stage 3) but stage is {stage} — the "
            "offload would silently not happen",
            "zero_optimization.offload_param vs .stage")
    if zc.offload_optimizer is not None and stage == 0:
        add("warning",
            "zero_optimization.offload_optimizer with ZeRO stage 0 offloads "
            "the FULL (unsharded) optimizer state through every host — set "
            "stage >= 1 so each host streams only its shard",
            "zero_optimization.offload_optimizer vs .stage")
    onebit = cfg.optimizer_name in (ONEBIT_ADAM_OPTIMIZER,
                                    ONEBIT_LAMB_OPTIMIZER,
                                    ZERO_ONE_ADAM_OPTIMIZER)
    if onebit and stage != 0:
        add("error",
            f"1-bit optimizer {cfg.optimizer_name!r} requires ZeRO stage 0 "
            f"(compressed comm replaces ZeRO's) but stage is {stage} — "
            "engine init will refuse this config",
            "optimizer.type vs zero_optimization.stage")
    if onebit and cfg.fp16.enabled:
        add("error",
            f"1-bit optimizer {cfg.optimizer_name!r} with fp16: dynamic loss "
            "scaling would sit inside the compressed loop — use bf16/fp32",
            "optimizer.type vs fp16.enabled")
    if zc.offload_optimizer is not None and \
            zc.offload_optimizer.device == "nvme" and cfg.fp16.enabled:
        add("error",
            "NVMe optimizer offload supports bf16/fp32 only (fp16 dynamic "
            "loss scaling is a device-side loop) — engine init will refuse",
            "zero_optimization.offload_optimizer.device vs fp16.enabled")
    mics = int(getattr(zc, "mics_shard_size", -1) or -1)
    if mics > 0 and cfg.mesh_config.data not in (-1, None) and \
            cfg.mesh_config.data % mics:
        add("error",
            f"zero_optimization.mics_shard_size={mics} does not divide the "
            f"tpu.data axis ({cfg.mesh_config.data}) — engine init will "
            "refuse this mesh factoring",
            "zero_optimization.mics_shard_size vs tpu.data")
    wd = cfg.watchdog
    if "watchdog" in pd and not wd.enabled and wd.consistency_interval > 0:
        add("warning",
            "watchdog.consistency_interval is set but watchdog.enabled is "
            "false — no agreement round will ever run",
            "watchdog.consistency_interval vs .enabled")
    tel = cfg.telemetry
    if tel.enabled and tel.monitor and not (
            cfg.monitor_config.tensorboard.enabled
            or cfg.monitor_config.wandb.enabled
            or cfg.monitor_config.csv_monitor.enabled):
        add("warning",
            "telemetry.monitor fans metrics out through the monitor writers "
            "but no tensorboard/wandb/csv_monitor block is enabled — the "
            "fan-out goes nowhere",
            "telemetry.monitor vs tensorboard/wandb/csv_monitor")
    if wd.enabled and not tel.enabled:
        add("info",
            "watchdog is enabled without telemetry: watchdog_timeouts / "
            "desync counters go to the no-op registry (detection still "
            "works; you just cannot chart it)",
            "watchdog.enabled vs telemetry.enabled")
    prof = cfg.profiling
    if "profiling" in pd and prof.enabled:
        if not tel.enabled:
            add("warning",
                "profiling is enabled without telemetry: the census / "
                "executable / span-peak series go to the no-op registry and "
                "are never exported — only the leak-sentinel log warning "
                "survives; enable the telemetry block to chart them",
                "profiling.enabled vs telemetry.enabled")
        elif prof.span_memory and not tel.trace:
            add("warning",
                "profiling.span_memory hooks per-span memory deltas into the "
                "step tracer, but telemetry.trace is false — there are no "
                "spans to hook",
                "profiling.span_memory vs telemetry.trace")
    srv = cfg.serving
    if "serving" in pd and srv.enabled:
        if not tel.enabled:
            add("warning",
                "serving is enabled without telemetry: the serving/* SLO "
                "series (admitted/shed/timed-out counters, queue depth, "
                "TTFT-vs-deadline) go to the no-op registry and ds_serve "
                "status / ds_metrics --serving will be blind — requests "
                "still terminate deterministically, you just cannot prove "
                "it from the logs",
                "serving.enabled vs telemetry.enabled")
        if wd.enabled and srv.decode_tick_timeout_s > wd.min_step_timeout:
            add("warning",
                f"serving.decode_tick_timeout_s ({srv.decode_tick_timeout_s:g}s) "
                f"exceeds the watchdog floor watchdog.min_step_timeout "
                f"({wd.min_step_timeout:g}s): a hung decode tick would trip "
                "the ENGINE watchdog (whole-process abort/restart) before "
                "the per-request timeout can resolve it cleanly — keep the "
                "tick deadline at or below the watchdog floor",
                "serving.decode_tick_timeout_s vs watchdog.min_step_timeout")
        if srv.max_queue_depth > 0 and srv.hbm_bytes > 0:
            add("warning",
                f"serving.max_queue_depth ({srv.max_queue_depth}) overrides "
                "the memory-census KV-budget sizing, but serving.hbm_bytes "
                "is also set: if the explicit bound admits more KV cache "
                "than the budget holds, requests OOM instead of shedding — "
                "drop max_queue_depth (let the budget size admission) or "
                "drop hbm_bytes",
                "serving.max_queue_depth vs serving.hbm_bytes")
        if srv.default_deadline_s < srv.decode_tick_timeout_s:
            add("info",
                f"serving.default_deadline_s ({srv.default_deadline_s:g}s) is "
                f"below decode_tick_timeout_s ({srv.decode_tick_timeout_s:g}s): "
                "a request's whole budget fits inside one tick, so deadline "
                "misses are detected at tick granularity — expected for "
                "latency-tight SLOs, just know the detection latency",
                "serving.default_deadline_s vs serving.decode_tick_timeout_s")
    ov = cfg.overlap
    if "overlap" in pd and ov.enabled:
        if ov.schedule == "serial" and not (tel.enabled and tel.trace):
            add("warning",
                "overlap.schedule='serial' is the MEASURED un-overlapped "
                "baseline — its blocking gather phase exists to land as "
                "comm spans — but telemetry step tracing is off, so the "
                "exposed-comm cost is paid and never recorded; enable the "
                "telemetry block (trace: true) or use "
                "schedule='overlapped'",
                "overlap.schedule vs telemetry.trace")
        if zc.offload_param is not None:
            add("warning",
                "overlap with zero_optimization.offload_param: the serial "
                "schedule is off for host-offloaded params (their "
                "stream-in IS the gather); scheduler flags and the async "
                "checkpoint snapshot still apply",
                "overlap vs zero_optimization.offload_param")
    roof = cfg.roofline
    if "roofline" in pd and roof.enabled:
        chip = (roof.chip or "").strip()
        if chip and chip != "auto":
            from deepspeed_tpu.analysis import chips as _chips
            try:
                _chips.resolve_chip(chip)
            except KeyError:
                add("error",
                    f"roofline.chip={chip!r} is not in the "
                    "analysis/chips.py peak table — the pass would raise "
                    f"at its first report; known: "
                    f"{', '.join(_chips.known_chips())} (or 'auto')",
                    "roofline.chip vs analysis/chips.py")
        if "perf" not in pd:
            add("warning",
                "roofline without the perf block: the pass runs and logs "
                "its report, but mfu_ceiling/mfu_gap never land in a "
                "ledger entry — `ds_perf gate --metric mfu_gap` will exit "
                "3 (missing) on every run (add \"perf\": {})",
                "roofline vs perf")
    rw = cfg.rewind
    if "rewind" in pd and rw.enabled:
        if not cfg.resilience.verify_on_load:
            add("warning",
                "rewind with resilience.verify_on_load=false: the restore "
                "ladder prefers an emergency_step<N> tag over a stale "
                "'latest' only because the tag VERIFIES — with "
                "verification off, a truncated emergency flush (a host "
                "reclaimed mid-write) would be restored instead of walked "
                "past",
                "rewind vs resilience.verify_on_load")
        sent = cfg.resilience.sentinel
        if sent.enabled and sent.patience >= rw.ram_interval * rw.keep:
            add("warning",
                f"resilience.sentinel.patience ({sent.patience}) >= "
                f"rewind.ram_interval × keep ({rw.ram_interval} × {rw.keep}"
                f" = {rw.ram_interval * rw.keep}): by the time the sentinel "
                "trips, every tier-0 RAM snapshot in the ring may already "
                "hold the diverging trajectory — the rewind would land "
                "inside the cliff; raise rewind.keep or lower "
                "rewind.ram_interval",
                "resilience.sentinel.patience vs rewind.ram_interval")
        if rw.emergency_save and not cfg.elasticity_config.enabled:
            add("info",
                "rewind.emergency_save is flushed by the elastic agent's "
                "preemption watch (DSElasticAgent / bin/ds_elastic): "
                "without an agent or launcher supervising the run, nothing "
                "delivers the flush when SIGTERM lands — tier-0 RAM "
                "snapshots and the sentinel's in-RAM rewind still work",
                "rewind.emergency_save vs elasticity.enabled")
    rz = cfg.elasticity_config.resize
    if "elasticity" in pd and rz.enabled:
        if not ("rewind" in pd and rw.enabled):
            add("warning",
                "elasticity.resize without the rewind block: the tier-0 RAM "
                "ring and tier-1 emergency tags do not exist, so a "
                "world-size change can only be served by the tier-2 disk "
                "checkpoint — steps_lost is bounded by the checkpoint "
                "interval, not rewind.ram_interval; enable the rewind block "
                "for one-SIGTERM-window resizes",
                "elasticity.resize vs rewind")
        elif "emergency" in rz.tiers and not rw.emergency_save:
            add("info",
                "elasticity.resize.tiers allows the 'emergency' tier but "
                "rewind.emergency_save is false: no emergency_step<N> tag "
                "is ever written, so a cross-process resize (host reclaim) "
                "falls through to the disk tier — only the in-process RAM "
                "reshard benefits",
                "elasticity.resize.tiers vs rewind.emergency_save")
        # only checkable against a BOUND world (an engine set dp_world_size):
        # an offline config lint runs on whatever machine the operator has,
        # and its device count says nothing about the fleet the config
        # targets (it would also drag jax backend init into a jax-free pass)
        n_dev = getattr(cfg, "dp_world_size", None)
        if n_dev and rz.min_world_size > n_dev:
            add("warning",
                f"elasticity.resize.min_world_size={rz.min_world_size} "
                f"exceeds the visible world of {n_dev} device(s): EVERY "
                "resize (and the current world itself) falls below the "
                "floor, so any world change becomes a loud refusal — is "
                "the floor meant for a bigger fleet?",
                "elasticity.resize.min_world_size")
    sdc = cfg.sdc
    if "sdc" in pd and sdc.enabled:
        if not ("rewind" in pd and rw.enabled):
            add("warning",
                "sdc without the rewind block: a corruption verdict with no "
                "elastic resize (or when eviction is refused) recovers by "
                "rewinding to the newest audited-clean snapshot — with no "
                "tier-0 RAM ring the only fallback is the tier-2 disk "
                "checkpoint, so every verdict costs up to a full checkpoint "
                "interval of steps; enable the rewind block so detection "
                "latency (≤ sdc.audit_interval steps) bounds the loss",
                "sdc vs rewind")
        if wd.consistency_interval > 0 and \
                sdc.audit_interval < wd.consistency_interval:
            add("info",
                f"sdc.audit_interval ({sdc.audit_interval}) is tighter than "
                f"watchdog.consistency_interval ({wd.consistency_interval}): "
                "replay audits will catch a flip before the cross-host "
                "agreement round ever sees its checksum — expected when you "
                "want device-granular blame first; just know the agreement "
                "round is then a backstop, not the detector",
                "sdc.audit_interval vs watchdog.consistency_interval")
    gray = cfg.gray
    if "gray" in pd and gray.enabled:
        if not (tel.enabled and tel.output_dir):
            add("error",
                "gray without a telemetry output_dir: the fail-slow defense "
                "is pure observability until it evicts — suspicion/probe "
                "gauges, gray_warn/gray_verdict trace events and the "
                "restart_log.jsonl verdict ledger all land in the telemetry "
                "session, so without one every verdict is unrecordable "
                "(undiagnosable after the fact); enable the telemetry block "
                "with an output_dir",
                "gray vs telemetry.output_dir")
        if gray.evict and not ("elasticity" in pd and rz.enabled):
            add("info",
                "gray.evict without elasticity.resize: a confirmed slow "
                "device cannot be evicted, so every verdict degrades to "
                "report-only (recorded + telemetry, fleet untouched) — "
                "enable the resize block for quarantine-and-evict, or set "
                "gray.evict: false to make the intent explicit",
                "gray.evict vs elasticity.resize")
    bb = cfg.blackbox
    if "blackbox" in pd and bb.enabled:
        if not (tel.enabled and tel.output_dir) and not bb.output_dir:
            add("error",
                "blackbox without anywhere to land a bundle: the flight "
                "recorder's ring lives in RAM, but a trigger (severity>="
                f"{bb.trigger_severity}, SIGUSR1, `ds_incident snap`) must "
                "write incidents/<ts>_<trigger>/ somewhere — and with no "
                "telemetry output_dir there are also no metrics/trace tails "
                "or restart_log to bundle, so the forensics are empty; "
                "enable the telemetry block with an output_dir (or set "
                "blackbox.output_dir for a bare events-only recorder)",
                "blackbox vs telemetry.output_dir")
        elif not (tel.enabled and tel.output_dir):
            add("warning",
                "blackbox.output_dir without the telemetry block: bundles "
                "will carry the event ring, stacks and env report, but no "
                "metrics/trace tails and no restart_log slice — `ds_incident "
                "report` degrades to wall-clock alignment with no goodput "
                "cost; enable telemetry with an output_dir for the full "
                "forensic record",
                "blackbox.output_dir vs telemetry")
    gp = cfg.goodput
    if "goodput" in pd and gp.enabled and not (tel.enabled and tel.trace):
        add("warning",
            "goodput is enabled without telemetry step tracing: the ledger "
            "classifies the tracer's spans, and with no spans every step "
            "reads as 100% idle — enable the telemetry block (with trace: "
            "true) for goodput/* series, ds_top and per-entry breakdowns",
            "goodput.enabled vs telemetry.trace")
    perf = cfg.perf
    if "perf" in pd and perf.enabled and perf.attribution \
            and not (tel.enabled and tel.trace):
        add("info",
            "perf.attribution embeds span p50/p99, step samples and "
            "exposed-comm from the telemetry tracer, but telemetry.trace is "
            "off — entries will carry memory/flops attribution only (enable "
            "the telemetry block for the full breakdown)",
            "perf.attribution vs telemetry.trace")
    ac = cfg.analysis
    if "analysis" in pd and ac.enabled:
        if ac.race_witness and not tel.enabled:
            add("warning",
                "analysis.race_witness records lock-acquisition order for "
                "the race pass's inversion report and the SIGUSR1 "
                "lock-holders table, but telemetry is off — the witness "
                "still records (and ds_doctor race --witness reads saved "
                "logs), you just lose the correlated trace/series view; "
                "enable the telemetry block",
                "analysis.race_witness vs telemetry.enabled")
        for entry in ac.race_allowlist:
            rule = str(entry).split(":", 1)[0]
            known = ("race/lock-order", "race/blocking-under-lock",
                     "race/signal-unsafe", "race/witness-inversion")
            if rule not in known:
                add("warning",
                    f"analysis.race_allowlist entry {entry!r} names unknown "
                    f"rule {rule!r} — it suppresses nothing; known rules: "
                    f"{', '.join(known)}",
                    "analysis.race_allowlist")


def walk_config(pd: dict, world_size: Optional[int] = None
                ) -> Tuple[List[Finding], Optional[object]]:
    """Validate a ds_config dict; returns (findings, DeepSpeedConfig|None).

    Unlike plain construction (first error wins), every sub-block is
    checked independently so the report is complete in one shot."""
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    findings: List[Finding] = []
    if not isinstance(pd, dict):
        return [Finding(rule=RULE_INVALID, severity="error",
                        message=f"ds_config must be a dict, got {type(pd).__name__}",
                        citation="ds_config", pass_name="schema")], None

    for key, model in _block_models().items():
        block = pd.get(key)
        if not isinstance(block, dict):
            continue
        try:
            model(**block)
        except ValueError as e:
            findings.append(Finding(
                rule=RULE_UNKNOWN_KEY if "Unknown key" in str(e)
                else RULE_INVALID,
                severity="error", message=_trim(e), citation=key,
                pass_name="schema"))
    _check_raw_block(pd, findings)

    cfg = None
    try:
        cfg = DeepSpeedConfig(dict(pd), world_size=world_size)
    except ValueError as e:
        msg = _trim(e)
        dup = any(f.message == msg for f in findings) or (
            "Unknown key(s)" in msg
            and any(f.rule == RULE_UNKNOWN_KEY for f in findings))
        if not dup:
            findings.append(Finding(rule=RULE_INVALID, severity="error",
                                    message=msg, citation="ds_config",
                                    pass_name="schema"))
    if cfg is not None:
        _cross_field(cfg, pd, findings)
    return findings, cfg
