"""Autotuner — config-space search over short measured training runs.

Counterpart of the reference's ``autotuning/autotuner.py`` (Autotuner :404,
~3k LoC with ``runner.py`` :449 as the CLI entry): enumerate candidate
ds_configs (ZeRO stage × micro-batch × ...), run each briefly, measure the
chosen metric, prune what cannot work, and emit the best config. The
reference launches each experiment as a separate multi-GPU job via the
launcher and scrapes metrics from logs; on TPU's single-controller runtime
the experiments run IN-PROCESS — a config that doesn't fit fails at XLA
compile time with a catchable ResourceExhausted, so OOM pruning is exact
rather than log-scraped, and there is no scheduler/job machinery to port.

Tuner strategies (reference tuner/ package): grid search, random, and a
model-based ordering that ranks candidates by a simple memory/throughput
prior and stops after ``early_stopping`` non-improving experiments.

ds_config surface (reference constants.py "autotuning" block): enabled,
metric (throughput|latency|flops), start_profile_step/end_profile_step,
tuner_type, tuner_early_stopping, tuner_num_trials, results_dir, exps_dir,
max_train_micro_batch_size_per_gpu, mbs_list, zero_stage_list (TPU extra:
remat_list).
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import random
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from deepspeed_tpu.utils.logging import logger

METRIC_THROUGHPUT = "throughput"
METRIC_LATENCY = "latency"
METRIC_FLOPS = "flops"


@dataclass
class AutotuningConfig:
    enabled: bool = False
    metric: str = METRIC_THROUGHPUT
    start_profile_step: int = 2
    end_profile_step: int = 6
    tuner_type: str = "model_based"          # gridsearch | random | model_based
    tuner_early_stopping: int = 5
    tuner_num_trials: int = 50
    results_dir: str = "autotuning_results"
    exps_dir: str = "autotuning_exps"
    fast: bool = True
    mbs_list: Optional[List[int]] = None
    zero_stage_list: Optional[List[int]] = None
    remat_list: Optional[List[str]] = None   # TPU extra: none|full|dots|attn
    gas_list: Optional[List[int]] = None     # gradient accumulation steps
    tp_list: Optional[List[int]] = None      # tensor-parallel degrees
    offload_list: Optional[List[bool]] = None  # host-offload optimizer on/off
    # streamed-offload scheduling: False = strict-serial leaf chain, True =
    # double-buffered (pull chains on the write TWO leaves back). Hardware-
    # dependent (serial wins through a slow host link, overlap should win on
    # real TPU-VM PCIe) — a MEASURED axis, not a baked default. Only expands
    # candidates that offload.
    offload_overlap_list: Optional[List[bool]] = None
    # flash kernel tile edges: multiples of 128 (the model configs refuse another)
    flash_block_list: Optional[List[Optional[int]]] = None
    # first-order HBM model: candidates predicted over this fraction of HBM
    # are pruned BEFORE compiling; 0 disables. Default 1.5 (= only prune
    # candidates 50% past HBM) because the model omits real contributors
    # (grad-accum buffers, streamed-offload working set, fragmentation) and
    # guesses activation bytes per remat policy — near the boundary the
    # exact-accounting check below must stay the arbiter, so only clearly
    # hopeless configs are skipped without ever compiling.
    hbm_prune_fraction: float = 1.5
    # exact OOM pruning: AOT-lower the candidate's real train step
    # (engine.aot_memory_analysis — the compiler's own argument/output/temp
    # ledger, no execution) and skip the MEASUREMENT when it exceeds
    # exact_memory_fraction of HBM. Near the boundary this wins over the
    # first-order model in both directions: a candidate the first-order
    # model calls hopeless but the compiler prices under budget runs; one
    # it calls fine but the compiler prices over budget is pruned before
    # the device ever allocates a step. COST: the AOT compile does not
    # fully prime the jit dispatch cache, so a candidate that goes on to
    # run pays roughly one extra compile (pruned candidates pay only the
    # AOT one — cheaper than the runtime OOM they avoid). Compile-bound
    # mega-sweeps can trade exactness back with exact_memory_check: false
    # (ds_tune --no-exact-memory).
    exact_memory_check: bool = True
    exact_memory_fraction: float = 0.92
    # HBM budget override (bytes) for the pruning checks: planning a sweep
    # for a different chip, or testing the pruning logic off-device, where
    # memory_stats() exposes no bytes_limit. None = ask the local device.
    assume_hbm_bytes: Optional[int] = None
    # perf ledger: every candidate appends one predicted-vs-measured entry
    # (kind="tune_candidate") here; "" disables, None = the default
    # <results_dir>/perf_ledger.jsonl. `ds_perf calibration` renders the
    # cost-model error report over it.
    ledger_path: Optional[str] = None

    @classmethod
    def from_ds_config(cls, pd: Dict) -> "AutotuningConfig":
        block = dict(pd.get("autotuning", {}))
        known = {f for f in cls.__dataclass_fields__}
        return cls(**{k: v for k, v in block.items() if k in known})


@dataclass
class Experiment:
    """One measured candidate (reference exps json schema role)."""
    exp_id: int
    ds_config: Dict[str, Any]
    status: str = "pending"                  # pending | ok | oom | error
    metric_val: float = 0.0
    tok_per_sec: float = 0.0
    step_time_s: float = 0.0
    error: str = ""
    extras: Dict[str, Any] = field(default_factory=dict)

    def record(self) -> Dict[str, Any]:
        return {"exp_id": self.exp_id, "status": self.status,
                "metric_val": self.metric_val, "tok_per_sec": self.tok_per_sec,
                "step_time_s": self.step_time_s, "error": self.error,
                "ds_config": self.ds_config, **self.extras}


class Autotuner:
    """Search the candidate space with real short runs.

    ``model_factory() -> model`` builds a fresh model per experiment (param
    memory must be released between candidates); ``batch_factory(batch_size)
    -> batch`` supplies data. ``base_config`` is the user's ds_config; tuned
    keys override it per candidate.
    """

    def __init__(self, model_factory, batch_factory, base_config: Dict,
                 tuning: Optional[AutotuningConfig] = None,
                 seq_len: Optional[int] = None):
        self.model_factory = model_factory
        self.batch_factory = batch_factory
        self.base_config = dict(base_config)
        self.tuning = tuning or AutotuningConfig.from_ds_config(self.base_config)
        self.seq_len = seq_len
        self.experiments: List[Experiment] = []
        # pruning counters, recorded in summary.json + the perf ledger's
        # tune_summary entry: how many candidates never compiled (first-order
        # model) vs never executed (exact memory_analysis)
        self.pruned_first_order = 0
        self.pruned_exact = 0

    # -------------------------------------------------------------- space
    def candidate_space(self) -> List[Dict[str, Any]]:
        import jax

        n_dev = len(jax.devices())
        t = self.tuning
        mbs_list = t.mbs_list or [4, 8, 16, 32]
        zero_list = t.zero_stage_list if t.zero_stage_list is not None else \
            ([1] if n_dev == 1 else [1, 2, 3])
        remat_list = t.remat_list or ["attn", "full"]
        # no gas axis ⇒ keep the user's base accumulation, don't reset to 1
        gas_list = t.gas_list or [
            int(self.base_config.get("gradient_accumulation_steps", 1))]
        tp_list = t.tp_list or [1]
        bad_tp = [tp for tp in tp_list if n_dev % tp]
        tp_list = [tp for tp in tp_list if n_dev % tp == 0]
        if bad_tp:
            logger.warning(f"autotuner: tp degrees {bad_tp} do not divide "
                           f"the device count {n_dev}; dropped")
        if not tp_list:
            raise ValueError(
                f"no usable tensor-parallel degree: tp_list={t.tp_list} vs "
                f"{n_dev} devices")
        off_list = t.offload_list or [False]
        ov_list = t.offload_overlap_list or [False]
        fb_list = t.flash_block_list or [None]
        out = []
        for mbs, stage, remat, gas, tp, off, ov, fb in itertools.product(
                mbs_list, zero_list, remat_list, gas_list, tp_list, off_list,
                ov_list, fb_list):
            if ov and not off:
                continue   # overlap only exists on the offload path
            cfg = json.loads(json.dumps(self.base_config))   # deep copy
            dp = n_dev // tp
            cfg["train_batch_size"] = mbs * dp * gas
            cfg["train_micro_batch_size_per_gpu"] = mbs
            cfg["gradient_accumulation_steps"] = gas
            zc = cfg.setdefault("zero_optimization", {})
            zc["stage"] = stage
            if off:
                # stream_overlap rides the candidate config (not env), so the
                # winning ds_config the tuner reports reproduces the result
                zc["offload_optimizer"] = {"device": "cpu",
                                           "stream_overlap": bool(ov)}
            if tp > 1:
                cfg.setdefault("tpu", {})["tensor"] = tp
            # NOTE: gas>1 candidates keep the user's grad_accum_dtype — a
            # perf tuner must not silently switch accumulation to bf16
            # (convergence-affecting); pass it in base_config to tune with it
            cfg["_tune"] = {"remat": remat, "micro_batch": mbs, "zero": stage,
                            "gas": gas, "tp": tp, "offload": off,
                            "offload_overlap": ov, "flash_block": fb}
            out.append(cfg)
        return out

    # --------------------------------------------------------- HBM cost model
    def estimate_hbm_bytes(self, tune: Dict[str, Any],
                           n_dev: int, hbm: Optional[int] = None) -> Optional[int]:
        """First-order per-device HBM for a candidate: params + grads +
        optimizer state (placement-aware) + activations (remat-aware).
        Needs a model config exposing num_params/n_layer/n_embd; returns
        None (no pruning) otherwise."""
        mc = getattr(self._probe_model(), "config", None)
        if mc is None or not hasattr(mc, "num_params"):
            return None
        n = mc.num_params()
        seq = self.seq_len or getattr(mc, "n_positions", 1024)
        d = getattr(mc, "n_embd", 1024)
        L = getattr(mc, "n_layer", 12)
        tp = tune.get("tp", 1)
        dp = max(1, n_dev // tp)
        stage = tune.get("zero", 1)
        mbs = tune["micro_batch"]
        bt = mbs * seq
        params = 2 * n // tp                               # bf16 compute copy
        if stage >= 3:
            params //= dp                                  # dp-sharded params
        opt = 12 * n // tp                                 # fp32 master+mu+nu
        if stage >= 1:
            opt //= dp
        grads = 2 * n // tp                                # bf16
        if stage >= 2:
            grads //= dp
        if tune.get("offload"):
            # the engine's moments-only auto policy (runtime/engine.py) keeps
            # the fp32 MASTER resident when (master+params+grads) fits 0.55
            # of HBM — mirror it so offload candidates are not underestimated
            opt = 0                                        # mu/nu pinned_host
            master = 4 * n // tp
            if stage >= 1:
                master //= dp
            if hbm is not None and (master + params + grads) <= 0.55 * hbm \
                    and os.environ.get("DS_TPU_OFFLOAD_MASTER",
                                       "auto").lower() not in ("host",
                                                               "pinned",
                                                               "cpu"):
                opt = master
        acc = 2 * n // tp if tune.get("gas", 1) > 1 else 0  # accumulator
        if stage >= 2:
            acc //= dp
        # activation bytes per layer per token (bf16), by remat policy:
        # 'full' keeps boundaries only (~1d); 'attn' + attention outs (~2d);
        # 'dots' keeps matmul outs (~14d); 'none' everything (~20d)
        per_tok_d = {"full": 1.5, "attn": 3, "dots": 14,
                     "none": 20, False: 20}.get(tune.get("remat", "attn"), 14)
        acts = int(2 * bt * d * per_tok_d * L) // tp
        return params + opt + grads + acc + acts

    _probe_cache = None

    def _probe_model(self):
        """One throwaway model instance for config introspection."""
        if self._probe_cache is None:
            try:
                self._probe_cache = self.model_factory()
            except TypeError:
                self._probe_cache = self.model_factory(remat="attn")
        return self._probe_cache

    def _order(self, cands: List[Dict]) -> List[Dict]:
        t = self.tuning
        if t.tuner_type == "random":
            cands = list(cands)
            random.Random(0).shuffle(cands)
            return cands[: t.tuner_num_trials]
        if t.tuner_type == "model_based":
            # prior: in-HBM before offload (offload trades speed for
            # capacity), bigger micro-batches first (better MXU util),
            # cheaper remat later (more memory), small gas first (same math,
            # faster experiments)
            memory_rank = {"full": 0, "attn": 1, "dots": 2, "none": 3}
            cands = sorted(cands, key=lambda c: (
                1 if c["_tune"].get("offload") else 0,
                -c["_tune"]["micro_batch"],
                memory_rank.get(c["_tune"]["remat"], 9),
                c["_tune"].get("gas", 1),
                c["_tune"].get("tp", 1)))
            return cands[: t.tuner_num_trials]
        return list(cands)[: t.tuner_num_trials]   # gridsearch

    # --------------------------------------------------------------- running
    def _run_one(self, exp: Experiment, hbm: Optional[int] = None):
        import deepspeed_tpu

        t = self.tuning
        cfg = {k: v for k, v in exp.ds_config.items() if k != "_tune"}
        tune = exp.ds_config.get("_tune", {})
        refs = {}   # explicit slot so `finally` can drop device buffers
        try:
            import inspect

            kw = {}
            try:
                sig = inspect.signature(self.model_factory).parameters
                accepted = set(sig)
                if any(p.kind is inspect.Parameter.VAR_KEYWORD
                       for p in sig.values()):
                    accepted |= {"remat", "flash_block"}
            except (TypeError, ValueError):
                accepted = {"remat"}
            if "remat" in tune and "remat" in accepted:
                kw["remat"] = tune["remat"]
            if tune.get("flash_block") and "flash_block" in accepted:
                kw["flash_block"] = tune["flash_block"]
            model = self.model_factory(**kw)
            refs["model"] = model
            engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
            refs["engine"] = engine
            batch = self.batch_factory(engine.train_batch_size())
            refs["batch"] = batch
            if t.exact_memory_check:
                # exact OOM gate: the compiler's own memory ledger for the
                # EXACT step this candidate would run (AOT lower+compile,
                # nothing executed; the compile is cached for the real
                # steps). Near the HBM boundary this overrides whatever the
                # first-order model guessed — in both directions.
                ma = engine.aot_memory_analysis(
                    batch, gas=tune.get("gas") or None)
                if ma is not None:
                    need = (ma["argument"] + ma["output"] - ma["alias"]
                            + ma["temp"] + ma["generated_code"])
                    exp.extras["memory_analysis"] = ma
                    exp.extras["hbm_exact"] = need
                    if hbm and need > t.exact_memory_fraction * hbm:
                        self.pruned_exact += 1
                        exp.status = "oom"
                        exp.error = (
                            f"exact memory_analysis: {need / 2**30:.2f}G "
                            f"(argument+output-alias+temp+code) > "
                            f"{t.exact_memory_fraction:.0%} of "
                            f"{hbm / 2**30:.1f}G HBM — pruned before "
                            f"execution")
                        return
            warm = max(1, t.start_profile_step)
            for _ in range(warm):
                loss = engine.train_batch(batch)
            float(loss)
            steps = max(1, t.end_profile_step - t.start_profile_step)
            t0 = time.time()
            for _ in range(steps):
                loss = engine.train_batch(batch)
            float(loss)
            dt = (time.time() - t0) / steps
            tokens = self._batch_tokens(batch)
            exp.step_time_s = dt
            exp.tok_per_sec = tokens / dt
            exp.status = "ok"
            mfu = self._measured_mfu(model, exp.tok_per_sec)
            if mfu is not None:
                exp.extras["measured_mfu"] = mfu
            if t.metric == METRIC_LATENCY:
                exp.metric_val = -dt
            elif t.metric == METRIC_FLOPS and hasattr(model, "config") and \
                    hasattr(model.config, "flops_per_token"):
                exp.metric_val = exp.tok_per_sec * model.config.flops_per_token(
                    self.seq_len)
            else:
                exp.metric_val = exp.tok_per_sec
        except Exception as e:  # compile OOM / invalid config — prune exactly
            msg = str(e)
            exp.status = "oom" if ("RESOURCE_EXHAUSTED" in msg
                                   or "out of memory" in msg.lower()) else "error"
            exp.error = msg[:500]
        finally:
            # release THIS candidate's device memory before the next compile:
            # drop the engine/state refs, drop jit caches holding compiled
            # programs (their constants pin buffers), then collect
            eng = refs.get("engine")
            if eng is not None:
                eng.state = None
                if hasattr(eng, "invalidate_compiled"):
                    eng.invalidate_compiled()
            refs.clear()
            try:
                import jax

                jax.clear_caches()
            except Exception:
                pass
            gc.collect()

    def _measured_mfu(self, model, tok_per_sec: float) -> Optional[float]:
        """Measured MFU of one candidate (None when the model exposes no
        flops_per_token — calibration then covers HBM only)."""
        mc = getattr(model, "config", None)
        if mc is None or not hasattr(mc, "flops_per_token"):
            return None
        try:
            import jax

            from deepspeed_tpu.accelerator import get_accelerator

            seq = self.seq_len or getattr(mc, "n_positions", 1024)
            peak = get_accelerator().peak_flops()
            n_dev = len(jax.devices())
            return round(tok_per_sec / n_dev * mc.flops_per_token(seq)
                         / peak, 4)
        except Exception:
            return None

    def _hbm_bytes(self) -> Optional[int]:
        """The pruning budget: ``assume_hbm_bytes`` when set (planning for
        another chip / testing off-device), else the local device's
        ``bytes_limit``; None when neither is known (no pruning)."""
        if self.tuning.assume_hbm_bytes:
            return int(self.tuning.assume_hbm_bytes)
        try:
            import jax

            return int(jax.local_devices()[0].memory_stats()["bytes_limit"])
        except Exception:
            return None

    @staticmethod
    def _batch_tokens(batch) -> int:
        import numpy as np

        if isinstance(batch, dict):
            x = next(iter(batch.values()))
        elif isinstance(batch, (tuple, list)):
            x = batch[0]
        else:
            x = batch
        x = np.asarray(x)
        return int(x.shape[0] * (x.shape[1] if x.ndim > 1 else 1))

    def _candidate_entry(self, exp: Experiment) -> Dict[str, Any]:
        """One predicted-vs-measured ledger record (kind=tune_candidate).
        Measured HBM prefers the compiler's exact accounting (hbm_exact:
        argument+output-alias+temp+code of the real step) over nothing —
        runtime peak stats are allocator-lifetime, not per-program, so
        they would overstate every candidate after the first."""
        from deepspeed_tpu.perf import ledger as perf_ledger

        tune = exp.ds_config.get("_tune", {})
        fingerprint = ""
        try:
            from deepspeed_tpu.resilience.consistency import \
                config_fingerprint

            fingerprint = config_fingerprint(
                {k: v for k, v in exp.ds_config.items() if k != "_tune"})
        except Exception:
            pass
        return {
            "kind": "tune_candidate", "exp_id": exp.exp_id,
            "status": exp.status, "error": exp.error,
            "tune": {k: v for k, v in tune.items() if v is not None},
            "predicted": {"mfu": exp.extras.get("predicted_mfu"),
                          "hbm_bytes": exp.extras.get("hbm_estimate")},
            "measured": {"mfu": exp.extras.get("measured_mfu"),
                         "hbm_bytes": exp.extras.get("hbm_exact")},
            "metric": self.tuning.metric, "metric_val": exp.metric_val,
            "tok_per_sec": exp.tok_per_sec, "step_time_s": exp.step_time_s,
            "git_rev": perf_ledger.git_rev(), "fingerprint": fingerprint,
        }

    def _ledger_path(self) -> Optional[str]:
        t = self.tuning
        if t.ledger_path == "":
            return None
        return t.ledger_path or os.path.join(t.results_dir,
                                             "perf_ledger.jsonl")

    def _ledger_append(self, path: Optional[str], entry):
        """``entry`` may be a dict or a zero-arg builder — construction
        happens INSIDE the guard, so a disabled ledger skips the work
        entirely (fingerprint hashing, git lookup) and a broken entry
        builder degrades to a warning, never a dead search."""
        if path is None:
            return
        try:
            from deepspeed_tpu.perf import ledger as perf_ledger

            perf_ledger.append_entry(path,
                                     entry() if callable(entry) else entry)
        except Exception as e:       # the ledger must never kill the search
            logger.warning(f"autotuner: perf ledger append failed: {e}")

    def tune(self) -> Optional[Dict[str, Any]]:
        """Run the search; returns the best ds_config (without _tune keys).

        Every candidate appends one ``tune_candidate`` entry (predicted vs
        measured MFU / HBM) to the perf ledger, and the search closes with
        a ``tune_summary`` entry carrying the pruning counters — the raw
        material of ``ds_perf calibration``.
        """
        t = self.tuning
        os.makedirs(t.exps_dir, exist_ok=True)
        os.makedirs(t.results_dir, exist_ok=True)
        cands = self._order(self.candidate_space())
        logger.info(f"autotuner: {len(cands)} candidates "
                    f"({t.tuner_type}, metric={t.metric})")
        import jax

        from deepspeed_tpu.perf.calibration import predict_mfu

        ledger_path = self._ledger_path()
        hbm = self._hbm_bytes()
        n_dev = len(jax.devices())
        best: Optional[Experiment] = None
        since_improved = 0
        for i, cfg in enumerate(cands):
            exp = Experiment(exp_id=i, ds_config=cfg)
            self.experiments.append(exp)
            tune = cfg.get("_tune", {})
            est = self.estimate_hbm_bytes(tune, n_dev, hbm=hbm)
            if est is not None:
                exp.extras["hbm_estimate"] = est
            exp.extras["predicted_mfu"] = predict_mfu(tune)
            if hbm is not None and t.hbm_prune_fraction and est is not None \
                    and est > t.hbm_prune_fraction * hbm:
                # hopeless by the first-order model: skip the compile. The
                # threshold is deliberately loose (default 1.5x HBM) — the
                # exact memory_analysis gate in _run_one owns the boundary.
                self.pruned_first_order += 1
                exp.status = "pruned"
                exp.error = (f"estimated {est/2**30:.1f}G > "
                             f"{t.hbm_prune_fraction:.0%} of "
                             f"{hbm/2**30:.1f}G HBM")
                logger.info(f"autotuner exp {i}: pruned "
                            f"(tune={tune}, {exp.error})")
            else:
                self._run_one(exp, hbm=hbm)
                logger.info(f"autotuner exp {i}: {exp.status} "
                            f"tune={tune} tok/s={exp.tok_per_sec:.0f}")
            with open(os.path.join(t.exps_dir, f"exp_{i}.json"), "w") as f:
                json.dump(exp.record(), f, indent=2)
            self._ledger_append(ledger_path,
                                lambda: self._candidate_entry(exp))
            if exp.status == "pruned":
                continue
            if exp.status == "ok" and (best is None or exp.metric_val > best.metric_val):
                best = exp
                since_improved = 0
            else:
                since_improved += 1
                if t.tuner_early_stopping and since_improved >= t.tuner_early_stopping:
                    logger.info("autotuner: early stopping")
                    break
        counters = {"pruned_first_order": self.pruned_first_order,
                    "pruned_exact": self.pruned_exact,
                    "experiments": len(self.experiments)}
        summary = {"num_experiments": len(self.experiments),
                   "best_exp_id": best.exp_id if best else None,
                   "metric": t.metric,
                   "best_metric_val": best.metric_val if best else None,
                   "counters": counters,
                   "experiments": [e.record() for e in self.experiments]}
        with open(os.path.join(t.results_dir, "summary.json"), "w") as f:
            json.dump(summary, f, indent=2)
        self._ledger_append(ledger_path, {
            "kind": "tune_summary", "counters": counters,
            "best_exp_id": best.exp_id if best else None,
            "metric": t.metric})
        if best is None:
            logger.warning("autotuner: no candidate succeeded")
            return None
        best_cfg = {k: v for k, v in best.ds_config.items() if k != "_tune"}
        best_cfg["_tuned"] = best.ds_config.get("_tune", {})
        with open(os.path.join(t.results_dir, "ds_config_optimal.json"), "w") as f:
            json.dump(best_cfg, f, indent=2)
        return best_cfg
