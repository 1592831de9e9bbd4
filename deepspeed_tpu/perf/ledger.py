"""Append-only perf ledger: every benchmark number, with attribution.

Two lessons from the records that came before it: a metric line
that is just ``{"metric", "value"}`` cannot be diffed against anything
(the config is crammed into the metric STRING), and a regression found
five PRs later cannot be attributed to anything (the line carries no
fingerprint, no environment, no breakdown). The ledger fixes both:

* every run appends one JSON object per benchmark line to a ``.jsonl``
  file (append-only — history is the point);
* each entry is keyed by a **config/code fingerprint** (the same sha256
  the PR 3 cross-rank consistency guard broadcasts at init, so "did the
  config change?" has the same answer in both subsystems) plus the git
  revision;
* each entry carries per-step ``samples`` so two entries can be compared
  with NOISE BOUNDS (Welch-style t gate over the step-time reservoirs)
  instead of eyeballing two scalars;
* ``attribution`` embeds the telemetry the run already collected —
  per-span p50/p99, memory-census buckets, flops, exposed-comm µs/step —
  so a regressed line says WHERE the time went.

Everything here is pure stdlib: ``bin/ds_perf`` diffs ledgers on a laptop
with no jax installed, exactly like ``bin/ds_prof`` merges traces.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

SCHEMA_VERSION = 1

# ------------------------------------------------------------------ identity
_GIT_REV_CACHE: Dict[str, str] = {}


def git_rev(cwd: Optional[str] = None) -> str:
    """Short git revision of ``cwd`` (or this file's repo); "" when not a
    checkout. Cached — a run asks once per entry it records."""
    key = cwd or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if key not in _GIT_REV_CACHE:
        try:
            _GIT_REV_CACHE[key] = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"], cwd=key,
                capture_output=True, text=True, timeout=5,
            ).stdout.strip()
        except Exception:
            _GIT_REV_CACHE[key] = ""
    return _GIT_REV_CACHE[key]


def series_key(entry: Dict[str, Any]) -> str:
    """The identity two entries must share to be comparable: an explicit
    ``series`` field when present (failure/skip lines set it — their
    metric string is ``"<label> FAILED: ..."``, which must still land in
    the same series as the measurement it failed to produce), else the
    metric string's config-free prefix (everything before " (") plus the
    unit: metric strings have the shape ``"<name> <what> (knobs...)"``."""
    series = entry.get("series")
    if series:
        return f"{series} [{entry.get('unit', '')}]"
    metric = str(entry.get("metric", ""))
    name = metric.split(" (", 1)[0].strip()
    return f"{name} [{entry.get('unit', '')}]"


# ------------------------------------------------------------------ appending
def append_entry(path: str, entry: Dict[str, Any]) -> Dict[str, Any]:
    """Append one entry to the ledger (stamps schema version + timestamp);
    returns the stamped entry. Append-only by design: the ledger IS the
    history, ``ds_perf diff`` picks entries out of it."""
    entry = dict(entry)
    entry.setdefault("schema", SCHEMA_VERSION)
    entry.setdefault("ts", time.time())
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(entry, default=str) + "\n")
    return entry


def load_entries(path: str) -> List[Dict[str, Any]]:
    """All well-formed entries of a ledger JSONL, in file order. A torn
    final line (run killed mid-append) is skipped, not fatal."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict):
                out.append(rec)
    return out


def load_baseline(path: str) -> List[Dict[str, Any]]:
    """Entries from either format a baseline can live in: a perf ledger
    (JSON lines, as ``engine.perf_record`` appends them) or one JSON
    document holding an entry or a list of entries. Any other document
    is rejected, not guessed at."""
    if path.endswith((".jsonl", ".ndjson")):
        # a perf ledger BY EXTENSION: parse line-wise natively instead of
        # relying on the whole-text json.loads to fail first — a
        # single-entry .jsonl is itself valid JSON and would otherwise be
        # misread as the one-dict case only by luck of ordering
        return load_entries(path)
    with open(path) as f:
        text = f.read()
    try:
        data = json.loads(text)
    except ValueError:
        return load_entries(path)
    entries = data if isinstance(data, list) else [data]
    bad = next((e for e in entries
                if not isinstance(e, dict) or "metric" not in e), None)
    if bad is not None:
        what = (f"an object with keys {sorted(bad)}" if isinstance(bad, dict)
                else type(bad).__name__)
        raise ValueError(
            f"{path}: not a perf ledger. ds_perf reads (1) JSON lines, one "
            "entry per line, and (2) one JSON document holding an entry or "
            f"a list of entries, each with a \"metric\"; found {what}")
    return entries


def is_nonmeasurement(entry: Dict[str, Any]) -> bool:
    """Failure/skip lines: a record of what did NOT get measured."""
    return bool(entry.get("skipped") or entry.get("failed")
                or "FAILED" in str(entry.get("metric", ""))
                or "SKIPPED" in str(entry.get("metric", "")))


def latest_by_series(entries: Sequence[Dict[str, Any]]
                     ) -> Dict[str, Dict[str, Any]]:
    """Last REAL entry per series key (file order = append order = time
    order). Skipped/failed lines never shadow a real measurement of the
    same series — they are what ``show``/``diff`` should look past. The
    gate additionally consults :func:`newest_by_series` so a crashed
    gated benchmark cannot hide behind a previous run's success."""
    out: Dict[str, Dict[str, Any]] = {}
    for e in entries:
        k = series_key(e)
        if is_nonmeasurement(e):
            out.setdefault(k, e)     # better than nothing, but never shadows
            continue
        out[k] = e
    return out


def newest_by_series(entries: Sequence[Dict[str, Any]]
                     ) -> Dict[str, Dict[str, Any]]:
    """Last entry per series key INCLUDING failures/skips — 'what did the
    newest run actually do', the question the regression gate asks."""
    out: Dict[str, Dict[str, Any]] = {}
    for e in entries:
        out[series_key(e)] = e
    return out


# ------------------------------------------------------------- noise bounds
def _mean_std(xs: Sequence[float]) -> Tuple[float, float, int]:
    n = len(xs)
    if n == 0:
        return 0.0, 0.0, 0
    mean = sum(xs) / n
    if n < 2:
        return mean, 0.0, n
    var = sum((x - mean) ** 2 for x in xs) / (n - 1)
    return mean, math.sqrt(var), n


def welch_t(a: Sequence[float], b: Sequence[float]) -> Optional[float]:
    """Welch's t statistic for mean(a) != mean(b); None when either side
    has fewer than 2 samples or both have zero variance."""
    ma, sa, na = _mean_std(a)
    mb, sb, nb = _mean_std(b)
    if na < 2 or nb < 2:
        return None
    se2 = sa * sa / na + sb * sb / nb
    if se2 <= 0:
        return None if ma == mb else math.inf
    return (ma - mb) / math.sqrt(se2)

# ~97.5th percentile of t for small df — indexed by min(n_a, n_b) - 1
# (conservative df choice; Welch df would only ever be larger).
_T_CRIT = {1: 12.71, 2: 4.30, 3: 3.18, 4: 2.78, 5: 2.57, 6: 2.45, 7: 2.36,
           8: 2.31, 9: 2.26, 10: 2.23, 15: 2.13, 20: 2.09, 30: 2.04}

# Exposed-comm regression floor (µs/step): relative tolerance alone would
# flag microsecond jitter on entries that expose next to nothing.
EXPOSED_COMM_FLOOR_US = 50.0

# Static-comm regression floor (bytes/device/step): the xray ring-model
# bill is DETERMINISTIC for a fixed program, so any growth is a real
# schedule change — but sub-floor deltas (a rounding-level reshard on a
# tiny fixture) should not fail CI.
STATIC_COMM_FLOOR_BYTES = 1 << 20

# SDC audit-overhead regression floor (absolute fraction points of wall):
# the sdc sentry's contract is "the defense costs < audit_interval⁻¹ of
# wall", so half a point of growth is noise on a short window but a
# point of growth on a 100-step audit cadence means an audit got 2x
# slower — real.
SDC_OVERHEAD_FLOOR = 0.005

# Gray probe-overhead regression floor (absolute fraction points of
# wall): the ds_gray contract is "probe cost <= 2% of wall at the
# default cadence", so half a point of growth is noise on a short
# window, but a point of sustained growth means probes got materially
# more expensive (or fire far more often) — real.
GRAY_OVERHEAD_FLOOR = 0.005

# Blackbox recorder-overhead regression floor (absolute fraction points
# of wall): the ds_blackbox contract is "always-on costs (nearly)
# nothing" — the ring append is a deque.append under a lock, so the
# honest number is well under half a percent of step wall. A sustained
# half-point of growth means the recorder grew work on the step path
# (or producers started flooding the ring) — real.
BLACKBOX_OVERHEAD_FLOOR = 0.005

# mfu_gap regression floor (absolute MFU points): the roofline gap is
# ceiling − measured, already a ratio in [0,1]; growth below two MFU
# points is CPU-sim noise, growth past it means either the measured MFU
# dropped or the program's analytic ceiling rose (a layout/fusion change
# freed headroom nobody collected) — both worth a red gate.
MFU_GAP_FLOOR = 0.02

# Attribution-level metrics `ds_perf gate/diff --metric` understands in
# addition to series-key substrings: these select WHAT is compared (the
# embedded attribution value), not WHICH series.
ATTRIBUTION_METRICS = ("exposed_comm", "goodput", "static_comm_bytes",
                       "sdc_overhead", "gray_overhead", "blackbox_overhead",
                       "mfu_gap")

# Minimum per-side sample count for the t gate to carry a verdict: with
# fewer, a failed significance test means "underpowered", not "noise",
# and must NOT exonerate a past-tolerance regression (a 2-sample ledger
# entry would otherwise green-light a 28% drop — df=1's 12.71 critical
# value is nearly unreachable).
MIN_POWER_SAMPLES = 3


def t_critical(na: int, nb: int) -> float:
    df = max(1, min(na, nb) - 1)
    for bound in sorted(_T_CRIT):
        if df <= bound:
            return _T_CRIT[bound]
    return 1.96


def _goodput_step_samples(entry: Dict[str, Any]) -> List[float]:
    """Per-step goodput fractions out of an entry's embedded per-step
    ledgers — the noise reservoir the goodput gate's t test runs on."""
    steps = (((entry.get("attribution") or {}).get("goodput") or {})
             .get("per_step")) or []
    out = []
    for s in steps:
        wall = float(s.get("wall_us") or 0.0)
        if wall > 0:
            out.append(float((s.get("buckets_us") or {}).get("compute", 0.0))
                       / wall)
    return out


def compare(old: Dict[str, Any], new: Dict[str, Any],
            rel_tol: float = 0.05) -> Dict[str, Any]:
    """Compare two entries of one series with noise bounds.

    ``value`` carries the headline scalar (higher = better for every
    bench unit); ``samples`` (per-step wall seconds, lower = better) feed
    the significance test when both sides have them. The verdict:

    * ``regression``  — new value below tolerance AND (no/insufficient
      samples, or the step-time delta is t-significant). A noisy pair
      that cannot clear the t gate is ``within_noise``, not a regression
      — exactly the r4 llama false-collapse this machinery exists to not
      repeat. The t gate only gets to EXONERATE a delta when it has
      statistical power: below ``MIN_POWER_SAMPLES`` per side (df=1
      needs |t|>12.7 — nearly nothing clears that, so 'not significant'
      means 'cannot tell', not 'fine') the verdict falls back to the
      plain threshold, same as legacy sample-less entries. A changed
      config fingerprint also disables exoneration — step-time noise
      says nothing about a value change caused by a different config.
    * ``improvement`` — symmetric.
    * ``within_noise`` — everything else.
    """
    vo = float(old.get("value") or 0.0)
    vn = float(new.get("value") or 0.0)
    delta = vn - vo
    rel = delta / vo if vo else (0.0 if vn == 0 else math.inf)
    sa = [float(x) for x in (old.get("samples") or [])]
    sb = [float(x) for x in (new.get("samples") or [])]
    t = welch_t(sa, sb)
    significant = None
    if t is not None and min(len(sa), len(sb)) >= MIN_POWER_SAMPLES:
        significant = abs(t) > t_critical(len(sa), len(sb))
    # world identity: an entry measured on a different device count — or
    # one whose run crossed an elastic RESIZE mid-run (world_resized,
    # stamped by the recorder from the engine's recovery record) — is
    # NEVER silently compared: per-device throughput, exposed comm and
    # goodput all scale with the world, so the pair is treated as
    # fingerprint-changed (plain-threshold verdict, tagged by the CLI).
    def _world(e):
        w = e.get("world_size")
        if w is None:
            w = (e.get("env") or {}).get("n_dev")
        try:
            return int(w) if w is not None else None
        except (TypeError, ValueError):
            return None

    wo, wn = _world(old), _world(new)
    # same device count laid out differently (dp=8 vs dp=4×tp=2) is a
    # different experiment too: the mesh_axes string the recorder stamps
    # participates in the world identity
    mo, mn = old.get("mesh_axes"), new.get("mesh_axes")
    world_changed = bool(
        (wo is not None and wn is not None and wo != wn)
        or (mo is not None and mn is not None and mo != mn)
        or old.get("world_resized") or new.get("world_resized"))
    out = {
        "series": series_key(new),
        "old_value": vo, "new_value": vn,
        "delta": delta, "rel_delta": rel,
        "old_rev": old.get("git_rev"), "new_rev": new.get("git_rev"),
        "old_fingerprint": old.get("fingerprint"),
        "new_fingerprint": new.get("fingerprint"),
        "old_world": wo, "new_world": wn,
        "old_mesh_axes": mo, "new_mesh_axes": mn,
        "world_changed": world_changed,
        "fingerprint_changed": world_changed or (
            bool(old.get("fingerprint")) and bool(new.get("fingerprint"))
            and old.get("fingerprint") != new.get("fingerprint")),
        "t_stat": t, "significant": significant,
        "n_old": len(sa), "n_new": len(sb),
    }
    # goodput_fraction rides along as a second gated metric when BOTH
    # entries carry it (entries recorded under the `goodput` ds_config
    # block): a headline that holds while goodput collapses means the
    # job got its throughput by burning more wall time on badput —
    # exactly the regression the taxonomy exists to catch. The drop is
    # judged in ABSOLUTE fraction points against rel_tol (goodput is
    # already a ratio; a 5% *relative* drop of a 0.2 goodput would be
    # a 1-point blip), under the SAME noise discipline as the headline:
    # per-step goodput fractions (from the embedded per-step ledgers)
    # feed a t gate that may exonerate a past-tolerance drop — one
    # stall-y step in a short window must not fail CI — with the same
    # power floor and fingerprint-change escape hatch.
    # exposed_comm_us_per_step rides along the same way (entries recorded
    # under a telemetry session carry it in `attribution`): LOWER is
    # better — exposed communication is time the chip waits — so the
    # regression direction flips vs the headline. Judged relative with an
    # absolute floor (EXPOSED_COMM_FLOOR_US): a 0 → 40µs blip on a step
    # that exposes nothing must not fail CI, a 0 → 20ms un-overlap must.
    # `ds_perf gate --metric exposed_comm` turns the flag into teeth.
    eo = (old.get("attribution") or {}).get("exposed_comm_us_per_step")
    en = (new.get("attribution") or {}).get("exposed_comm_us_per_step")
    if eo is not None and en is not None:
        eo, en = float(eo), float(en)
        out["old_exposed_comm_us"] = eo
        out["new_exposed_comm_us"] = en
        out["exposed_comm_delta_us"] = en - eo
        out["exposed_comm_regressed"] = (
            (en - eo) > max(rel_tol * max(eo, 1.0), EXPOSED_COMM_FLOOR_US))
    # static_comm_bytes rides the same way (stamped by the xray pass from
    # the COMPILED train program's collective schedule): LOWER is better,
    # and unlike a measured metric it is deterministic per program — a
    # quantized/hierarchical collective rewrite (ROADMAP Item 2) shows up
    # as a drop here with no hardware in the loop, and a schedule
    # regression (an extra all-gather, a lost overlap rewrite) as growth.
    # Judged relative with an absolute floor; no t gate (nothing to be
    # noisy about).
    so = (old.get("attribution") or {}).get("static_comm_bytes")
    sn = (new.get("attribution") or {}).get("static_comm_bytes")
    if so is not None and sn is not None:
        so, sn = float(so), float(sn)
        out["old_static_comm_bytes"] = so
        out["new_static_comm_bytes"] = sn
        out["static_comm_delta_bytes"] = sn - so
        out["static_comm_regressed"] = (
            (sn - so) > max(rel_tol * max(so, 1.0), STATIC_COMM_FLOOR_BYTES))
    # sdc_overhead rides the same way (stamped by the perf attribution
    # from the goodput ledger's `audit` bucket when the sdc sentry is
    # armed): LOWER is better — it is the wall-fraction the replay audits
    # cost — judged in ABSOLUTE fraction points (it is already a ratio)
    # with a floor, same shape as the goodput gate's drop test.
    # `ds_perf gate --metric sdc_overhead` turns the flag into teeth.
    ko = (old.get("attribution") or {}).get("sdc_overhead")
    kn = (new.get("attribution") or {}).get("sdc_overhead")
    if ko is not None and kn is not None:
        ko, kn = float(ko), float(kn)
        out["old_sdc_overhead"] = ko
        out["new_sdc_overhead"] = kn
        out["sdc_overhead_delta"] = kn - ko
        out["sdc_overhead_regressed"] = (
            (kn - ko) > max(rel_tol * max(ko, SDC_OVERHEAD_FLOOR),
                            SDC_OVERHEAD_FLOOR))
    # gray_overhead rides the same way (stamped from the goodput ledger's
    # `probe` bucket when ds_gray is armed): LOWER is better — the
    # wall-fraction the fail-slow microprobes cost — judged in ABSOLUTE
    # fraction points with a floor. `ds_perf gate --metric gray_overhead`
    # is the subsystem's self-gate (probe cost <= 2% of wall at the
    # default cadence).
    yo = (old.get("attribution") or {}).get("gray_overhead")
    yn = (new.get("attribution") or {}).get("gray_overhead")
    if yo is not None and yn is not None:
        yo, yn = float(yo), float(yn)
        out["old_gray_overhead"] = yo
        out["new_gray_overhead"] = yn
        out["gray_overhead_delta"] = yn - yo
        out["gray_overhead_regressed"] = (
            (yn - yo) > max(rel_tol * max(yo, GRAY_OVERHEAD_FLOOR),
                            GRAY_OVERHEAD_FLOOR))
    # blackbox_overhead rides the same way (the flight recorder's own
    # append-time accounting when ds_blackbox is armed): LOWER is better
    # — the wall-fraction the always-on ring costs — judged in ABSOLUTE
    # fraction points with a floor. `ds_perf gate --metric
    # blackbox_overhead` is the subsystem's self-gate (recorder cost
    # <= ~0.5% of wall, i.e. "always-on is effectively free").
    bo = (old.get("attribution") or {}).get("blackbox_overhead")
    bn = (new.get("attribution") or {}).get("blackbox_overhead")
    if bo is not None and bn is not None:
        bo, bn = float(bo), float(bn)
        out["old_blackbox_overhead"] = bo
        out["new_blackbox_overhead"] = bn
        out["blackbox_overhead_delta"] = bn - bo
        out["blackbox_overhead_regressed"] = (
            (bn - bo) > max(rel_tol * max(bo, BLACKBOX_OVERHEAD_FLOOR),
                            BLACKBOX_OVERHEAD_FLOOR))
    # roofline mfu_gap (hoisted top-level, like goodput_fraction): LOWER
    # is better — the distance between the measured MFU and the analytic
    # HLO-model ceiling — judged in ABSOLUTE MFU points with a floor
    # (it is already a ratio). `ds_perf gate --metric mfu_gap` arms it.
    mo, mn = old.get("mfu_gap"), new.get("mfu_gap")
    if mo is not None and mn is not None:
        mo, mn = float(mo), float(mn)
        out["old_mfu_gap"] = mo
        out["new_mfu_gap"] = mn
        out["mfu_gap_delta"] = mn - mo
        out["mfu_gap_regressed"] = (
            (mn - mo) > max(rel_tol * max(mo, MFU_GAP_FLOOR),
                            MFU_GAP_FLOOR))
    go, gn = old.get("goodput_fraction"), new.get("goodput_fraction")
    if go is not None and gn is not None:
        out["old_goodput"] = float(go)
        out["new_goodput"] = float(gn)
        out["goodput_delta"] = float(gn) - float(go)
        ga = _goodput_step_samples(old)
        gb = _goodput_step_samples(new)
        gt = welch_t(ga, gb)
        g_sig = None
        if gt is not None and min(len(ga), len(gb)) >= MIN_POWER_SAMPLES:
            g_sig = abs(gt) > t_critical(len(ga), len(gb))
        g_exonerated = g_sig is False and not out["fingerprint_changed"]
        out["goodput_regressed"] = (out["goodput_delta"] < -rel_tol
                                    and not g_exonerated)
    # the t gate runs on STEP-TIME samples; when the config fingerprint
    # changed, the headline value and the step time are no longer two
    # views of one experiment (e.g. tokens/step drifted: MFU halves while
    # step time stays flat) — a flat step time must not exonerate a
    # past-tolerance value change, so the verdict falls back to the plain
    # threshold (the CLI tags the line '[config fingerprint changed]')
    exonerated = significant is False and not out["fingerprint_changed"]
    if rel < -rel_tol and not exonerated:
        out["verdict"] = "regression"
    elif rel > rel_tol and not exonerated:
        out["verdict"] = "improvement"
    else:
        out["verdict"] = "within_noise"
    return out
