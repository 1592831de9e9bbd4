"""PerfRecorder — the engine's ledger pen (``perf`` ds_config block).

Imported ONLY when the block is present (strict no-op contract, same as
``analysis`` / ``profiling``: without the block this module never enters
``sys.modules``). The recorder owns nothing heavy — it stamps structured
ledger entries from what the run already knows:

* identity: config/code **fingerprint** (the PR 3
  ``consistency.config_fingerprint`` — same hash the cross-rank guard
  agrees on at init), git revision, backend/env facts;
* attribution: :func:`deepspeed_tpu.perf.attribution.collect` over the
  live telemetry session + engine profiling hooks;
* the caller's headline (metric string / value / unit / model / knobs).

A training script calls ``engine.perf_record(...)`` (which lands in
:meth:`PerfRecorder.record`) once per number it wants kept. Entries
append to ``perf.ledger_path`` (rank 0 only) and are returned to the
caller either way.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, Optional

from deepspeed_tpu.perf import attribution as _attribution
from deepspeed_tpu.perf import ledger as _ledger
from deepspeed_tpu.utils.logging import logger


class PerfRecorder:
    def __init__(self, engine, cfg):
        self.engine = engine
        self.cfg = cfg
        self._fingerprint: Optional[str] = None

    # ------------------------------------------------------------- identity
    def fingerprint(self) -> str:
        """The run's config/code fingerprint — PR 3's consistency hash, so
        'same fingerprint' means 'the startup guard would have agreed'."""
        if self._fingerprint is None:
            from deepspeed_tpu.resilience.consistency import \
                config_fingerprint

            try:
                self._fingerprint = config_fingerprint(
                    self.engine._config.to_dict(),
                    mesh=getattr(self.engine, "mesh", None))
            except Exception as e:
                logger.warning(f"perf: fingerprint failed: {e}")
                self._fingerprint = ""
        return self._fingerprint

    @staticmethod
    def env_facts() -> Dict[str, Any]:
        import jax

        return {
            "backend": jax.default_backend(),
            "n_dev": len(jax.devices()),
            "n_proc": jax.process_count(),
            "jax": jax.__version__,
            "python": sys.version.split()[0],
        }

    # -------------------------------------------------------------- recording
    def record(self, metric: str, value: float, unit: str,
               model: Optional[str] = None,
               config: Optional[Dict[str, Any]] = None,
               seed: Optional[int] = None,
               samples: Optional[list] = None,
               timed_steps: Optional[int] = None,
               extra: Optional[Dict[str, Any]] = None,
               attribution: Optional[bool] = None) -> Dict[str, Any]:
        """Build one structured ledger entry (and append it when
        ``perf.ledger_path`` is set and this is process 0). The legacy
        ``metric`` string stays the compat surface — drivers that parse
        ``{"metric", "value", "unit"}`` keep working unchanged.
        ``attribution`` defaults to the config block's knob (false =
        headline + identity fields only: no census walk, no flops trace,
        no span fold)."""
        import jax

        from deepspeed_tpu import telemetry

        session = telemetry.get_session()
        entry: Dict[str, Any] = {
            "metric": metric, "value": value, "unit": unit,
            "model": model,
            "config": dict(config or {}),
            "env": self.env_facts(),
            "seed": seed,
            "git_rev": _ledger.git_rev(),
            "fingerprint": self.fingerprint(),
        }
        try:
            # the MESH device count, not the backend's: an elastic run on
            # 6 survivors of an 8-device backend measured a 6-wide world
            import numpy as _np

            entry["world_size"] = int(_np.prod(
                [int(v) for v in dict(self.engine.mesh.shape).values()]))
            # the mesh identity string ("data=4×tensor=2") next to the bare
            # world size: a ledger line is only comparable to another laid
            # out the same way, and 8 chips as dp=8 vs dp=4×tp=2 are two
            # different experiments
            from deepspeed_tpu.sharding.mesh import mesh_axes_string

            entry["mesh_axes"] = mesh_axes_string(self.engine.mesh)
        except Exception:
            pass
        resized = (getattr(self.engine, "_last_recovery", None)
                   or {}).get("resize")
        if resized:
            # the run crossed a world resize: its numbers are not two
            # views of one experiment with ANY baseline — ds_perf
            # compare/gate treats this as fingerprint-changed, never a
            # silent comparison
            entry["world_resized"] = dict(resized)
        if session is not None:
            entry["telemetry_dir"] = session.output_dir
        events = _attribution.tracer_events(session)
        if samples is None and events:
            samples = _attribution.train_step_samples(events,
                                                      last=timed_steps)
        if samples:
            entry["samples"] = [round(float(s), 6) for s in samples]
        want_attribution = (self.cfg.attribution if attribution is None
                            else attribution)
        if want_attribution:
            ecfg = getattr(self.engine, "_config", None)
            roofline_on = bool(
                getattr(ecfg, "roofline_present", False)
                and getattr(getattr(ecfg, "roofline", None), "enabled",
                            False))
            entry["attribution"] = _attribution.collect(
                self.engine, session=session, timed_steps=timed_steps,
                static_comm=getattr(self.cfg, "static_comm", True),
                roofline=roofline_on)
            gf = (entry["attribution"].get("goodput") or {}).get(
                "goodput_fraction")
            if gf is not None:
                # hoisted to the top level so ds_perf compare/gate can
                # treat it as a first-class gated metric
                entry["goodput_fraction"] = gf
            mc = entry["attribution"].get("mfu_ceiling")
            if mc is not None:
                # hoisted like goodput_fraction; mfu_gap = ceiling −
                # measured is only defined when the headline IS an MFU
                entry["mfu_ceiling"] = round(float(mc), 4)
                if str(unit).strip().upper() == "MFU":
                    entry["mfu_gap"] = round(
                        max(0.0, float(mc) - float(value)), 4)
        if extra:
            entry.update(extra)
        path = self.cfg.ledger_path
        if path and jax.process_index() == 0:
            try:
                entry = _ledger.append_entry(path, entry)
            except OSError as e:     # the ledger must never kill the run
                logger.warning(f"perf: ledger append to {path!r} failed: {e}")
        return entry
