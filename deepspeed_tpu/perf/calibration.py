"""Cost-model calibration: predicted vs measured, per autotuner candidate.

``ds_tune`` orders its search with first-order models (an analytic HBM
estimate, a closed-form MFU prior). Those models are only as good as the
last time anyone checked them against ground truth — which, before the
perf ledger, was never. Here:

* :func:`predict_mfu` — the explicit first-order MFU prior (remat
  recompute tax × micro-batch MXU-utilization ramp × offload
  amortization). Deliberately simple: its job is to ORDER candidates,
  and the calibration report is what tells us when it stops being able
  to;
* the autotuner appends one ``kind="tune_candidate"`` ledger entry per
  experiment with ``predicted`` (MFU, HBM bytes) and ``measured`` (MFU
  from the timed window, HBM from XLA's ``memory_analysis``);
* :func:`calibration_rows` / :func:`render_calibration` — the
  ``ds_perf calibration`` report: per-candidate error and aggregate
  mean-absolute-percentage error, so "should we widen the search space /
  trust the pruner more" is an evidence question.

Pure stdlib except :func:`predict_mfu` (which only does arithmetic on a
model config the caller supplies) — the report side runs laptop-side.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

# remat policy → fraction of peak the fwd+bwd step can plausibly reach
# (the recompute tax: 'full' recomputes the whole fwd in bwd, 'attn' only
# the cheap matmul chain, 'none' recomputes nothing). Ballparked from the
# v5e sweeps of the rounds before PR 1 (never re-measured); calibration
# exists precisely because these decay.
_REMAT_EFFICIENCY = {"none": 0.55, False: 0.55, "attn": 0.50,
                     "dots": 0.42, "full": 0.38}
# micro-batch below which the MXU stays under-filled (measured: the 760m
# family ramps roughly linearly to ~bs=8 on v5e, flat after ~12)
_MBS_SATURATION = 8.0
# offload: the streamed fp32 update costs roughly this many microbatch
# equivalents of wall time per optimizer step; gas amortizes it
_OFFLOAD_UPDATE_MICROBATCH_EQ = 10.0


def predict_mfu(tune: Dict[str, Any]) -> float:
    """First-order MFU prior for one candidate's ``_tune`` knobs."""
    eff = _REMAT_EFFICIENCY.get(tune.get("remat", "attn"), 0.45)
    mbs = float(tune.get("micro_batch", 8) or 8)
    eff *= min(1.0, mbs / _MBS_SATURATION)
    if tune.get("offload"):
        gas = float(tune.get("gas", 1) or 1)
        eff *= gas / (gas + _OFFLOAD_UPDATE_MICROBATCH_EQ)
    return round(eff, 4)


def pct_err(predicted: Optional[float], measured: Optional[float]
            ) -> Optional[float]:
    """Signed relative error of the prediction, in % of the measurement."""
    if predicted is None or not measured:
        return None
    return 100.0 * (float(predicted) - float(measured)) / float(measured)


def calibration_rows(entries: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Per-candidate predicted-vs-measured rows out of a ledger's
    ``tune_candidate`` entries."""
    rows = []
    for e in entries:
        if e.get("kind") != "tune_candidate":
            continue
        pred = e.get("predicted") or {}
        meas = e.get("measured") or {}
        rows.append({
            "exp_id": e.get("exp_id"),
            "status": e.get("status"),
            "tune": e.get("tune") or {},
            "predicted_mfu": pred.get("mfu"),
            "measured_mfu": meas.get("mfu"),
            "mfu_err_pct": pct_err(pred.get("mfu"), meas.get("mfu")),
            "predicted_hbm_bytes": pred.get("hbm_bytes"),
            "measured_hbm_bytes": meas.get("hbm_bytes"),
            "hbm_err_pct": pct_err(pred.get("hbm_bytes"),
                                   meas.get("hbm_bytes")),
        })
    return rows


def _mape(errs: List[Optional[float]]) -> Optional[float]:
    xs = [abs(e) for e in errs if e is not None]
    return sum(xs) / len(xs) if xs else None


def calibration_summary(rows: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    return {
        "candidates": len(rows),
        "measured": sum(1 for r in rows if r["measured_mfu"] is not None
                        or r["measured_hbm_bytes"] is not None),
        "mfu_mape_pct": _mape([r["mfu_err_pct"] for r in rows]),
        "hbm_mape_pct": _mape([r["hbm_err_pct"] for r in rows]),
    }


def render_calibration(rows: Sequence[Dict[str, Any]],
                       counters: Optional[Dict[str, Any]] = None,
                       source: Optional[str] = None) -> str:
    """The human-readable ``ds_perf calibration`` report."""
    if not rows:
        return ("calibration: no tune_candidate entries found"
                + (f" in {source}" if source else "")
                + " — run ds_tune (it appends predicted-vs-measured per "
                  "candidate to its perf ledger)")
    out = ["cost-model calibration" + (f": {source}" if source else "")]
    header = ("exp", "status", "knobs", "pred MFU", "meas MFU", "err%",
              "pred HBM", "meas HBM", "err%")
    table = [header]

    def fmt(v, kind):
        if v is None:
            return "-"
        if kind == "mfu":
            return f"{v:.3f}"
        if kind == "pct":
            return f"{v:+.0f}%"
        return f"{v / 2**30:.2f}G"

    for r in rows:
        knobs = r["tune"]
        knob_s = ",".join(f"{k}={v}" for k, v in sorted(knobs.items())
                          if v not in (None, False) and k != "zero")[:40]
        table.append((str(r["exp_id"]), str(r["status"]), knob_s or "-",
                      fmt(r["predicted_mfu"], "mfu"),
                      fmt(r["measured_mfu"], "mfu"),
                      fmt(r["mfu_err_pct"], "pct"),
                      fmt(r["predicted_hbm_bytes"], "hbm"),
                      fmt(r["measured_hbm_bytes"], "hbm"),
                      fmt(r["hbm_err_pct"], "pct")))
    widths = [max(len(row[i]) for row in table) for i in range(len(header))]
    for i, row in enumerate(table):
        out.append("  ".join(c.ljust(w) for c, w in zip(row, widths)).rstrip())
        if i == 0:
            out.append("  ".join("-" * w for w in widths))
    s = calibration_summary(rows)
    out.append("")
    out.append(f"candidates: {s['candidates']} ({s['measured']} measured)")
    if s["mfu_mape_pct"] is not None:
        out.append(f"MFU cost-model error (MAPE):  {s['mfu_mape_pct']:.1f}%")
    if s["hbm_mape_pct"] is not None:
        out.append(f"HBM cost-model error (MAPE):  {s['hbm_mape_pct']:.1f}%")
    if counters:
        pruned_fo = counters.get("pruned_first_order", 0)
        pruned_ex = counters.get("pruned_exact", 0)
        out.append(f"pruned before compile (first-order model): {pruned_fo}")
        out.append(f"pruned before execution (exact memory_analysis): "
                   f"{pruned_ex}")
    return "\n".join(out)
