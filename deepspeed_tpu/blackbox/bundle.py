"""Incident bundle writer: atomic ``incidents/<ts>_<trigger>/`` dumps.

A bundle is everything a 3am responder needs from ONE rank, under a hard
size budget:

    manifest.json       identity: trigger, rank, clock anchor, config
                        fingerprint, schema version, truncation notes
    events.jsonl        the flight-recorder ring (envelope events)
    step_tail.jsonl     rolling per-step samples from the recorder
    metrics_tail.jsonl  tail of the telemetry metrics.jsonl
    trace_tail.jsonl    recent trace spans (live tracer + rotated sessions)
    restart_log.jsonl   tail slice of the elastic agent's restart log
    env.json            software/hardware report rows
    stacks.txt          faulthandler stacks + held-locks table

Written to a ``.tmp`` sibling then ``os.replace``d into place, so readers
(and crash-during-dump) never see a half bundle.
"""

from __future__ import annotations

import faulthandler
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

from deepspeed_tpu.telemetry.events import SCHEMA_VERSION
from deepspeed_tpu.utils import locks as _locks
from deepspeed_tpu.utils.logging import logger

# Fractions of the byte budget granted to each capped artifact.  Manifest,
# env, and stacks are small and uncapped; the ring is already bounded by
# ring_size.  Remaining budget is split across the file tails.
_TAIL_SHARES = {"metrics_tail.jsonl": 0.35, "trace_tail.jsonl": 0.45,
                "restart_log.jsonl": 0.20}


def _tail_lines(path: str, max_bytes: int) -> (List[str], bool):
    """Last complete lines of ``path`` fitting in ``max_bytes``.

    Returns (lines, truncated).  A torn first line (we landed mid-record)
    is dropped, which also protects against reading a half-written JSONL
    record at the live end of the file.
    """
    try:
        size = os.path.getsize(path)
        with open(path, "rb") as f:
            if size > max_bytes:
                f.seek(size - max_bytes)
            data = f.read(max_bytes)
    except OSError:
        return [], False
    truncated = size > max_bytes
    text = data.decode("utf-8", errors="replace")
    lines = text.splitlines()
    if truncated and lines:
        lines = lines[1:]  # first line is almost certainly torn
    return [ln for ln in lines if ln.strip()], truncated


def _write_jsonl(path: str, records: List[Dict[str, Any]]) -> None:
    with open(path, "w", encoding="utf-8") as f:
        for r in records:
            f.write(json.dumps(r, default=str) + "\n")


def _collect_trace_tail(base_dir: str, span_tail: int, max_bytes: int,
                        notes: List[str]) -> List[Dict[str, Any]]:
    """Recent trace spans: live tracer events first, then rotated sessions.

    Each record is one chrome-trace event dict plus a ``_session`` tag and,
    once per source, a ``_clock_anchor`` record so ds_incident can align
    ranks exactly the way ``ds_prof merge`` does.
    """
    out: List[Dict[str, Any]] = []
    try:
        from deepspeed_tpu import telemetry
        tracer = telemetry.get_tracer()
    except Exception:  # noqa: BLE001
        tracer = None
    if tracer is not None and getattr(tracer, "events", None) is not None:
        anchor = {"epoch_s": getattr(tracer, "epoch0", None),
                  "monotonic_s": getattr(tracer, "_t0", None)}
        out.append({"_clock_anchor": anchor, "_session": "live",
                    "rank": getattr(tracer, "pid", 0)})
        for ev in list(tracer.events)[-span_tail:]:
            rec = dict(ev)
            rec["_session"] = "live"
            out.append(rec)
    # Rotated sessions (trace.session<N>.json) — parse bounded-size files
    # only; note anything skipped so the manifest stays honest.
    try:
        names = sorted(n for n in os.listdir(base_dir)
                       if n.startswith("trace.session") and n.endswith(".json"))
    except OSError:
        names = []
    for name in names:
        path = os.path.join(base_dir, name)
        try:
            if os.path.getsize(path) > max(max_bytes, 1 << 23):
                notes.append(f"skipped oversized rotated trace {name}")
                continue
            with open(path, "r", encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            notes.append(f"unreadable rotated trace {name}: {e}")
            continue
        meta = doc.get("metadata", {}) if isinstance(doc, dict) else {}
        out.append({"_clock_anchor": meta.get("clock_anchor"),
                    "_session": name, "rank": meta.get("rank")})
        events = doc.get("traceEvents", []) if isinstance(doc, dict) else []
        for ev in events[-span_tail:]:
            rec = dict(ev)
            rec["_session"] = name
            out.append(rec)
    return out


def _env_report() -> Dict[str, Any]:
    out: Dict[str, Any] = {"argv": list(sys.argv),
                           "cwd": os.getcwd(), "pid": os.getpid()}
    try:
        from deepspeed_tpu import env_report
        out["software"] = [[str(k), str(v)] for k, v in env_report.software_report()]
        out["hardware"] = [[str(k), str(v)] for k, v in env_report.hardware_report()]
    except Exception as e:  # noqa: BLE001
        out["error"] = str(e)
    env_keys = ("JAX_PLATFORMS", "XLA_FLAGS", "TPU_CHIPS_PER_HOST_BOUNDS",
                "LIBTPU_INIT_ARGS")
    out["env"] = {k: os.environ[k] for k in env_keys if k in os.environ}
    return out


def _write_stacks(path: str) -> None:
    # faulthandler writes to a raw fd, not a Python stream — it must get
    # the real on-disk file (a StringIO has no fileno and the dump would
    # silently degrade to an error note).
    with open(path, "w", encoding="utf-8") as f:
        f.write(f"# blackbox stack dump pid={os.getpid()} "
                f"ts={time.time():.3f}\n")
        f.flush()
        try:
            faulthandler.dump_traceback(file=f, all_threads=True)
        except Exception as e:  # noqa: BLE001
            f.write(f"(faulthandler failed: {e})\n")
        f.write("\n")
        try:
            f.write(_locks.format_lock_holders())
            f.write("\n")
        except Exception as e:  # noqa: BLE001
            f.write(f"(lock holders unavailable: {e})\n")


def write_bundle(recorder, trigger: str, base_dir: str) -> Optional[str]:
    """Atomically write one incident bundle under ``base_dir``/incidents."""
    cfg = recorder.cfg
    budget = int(float(getattr(cfg, "max_bundle_mb", 16.0)) * (1 << 20))
    span_tail = int(getattr(cfg, "span_tail", 256))
    incidents = os.path.join(base_dir, "incidents")
    os.makedirs(incidents, exist_ok=True)
    stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
    safe_trigger = "".join(c if c.isalnum() or c in "-_" else "-"
                           for c in str(trigger))[:48] or "event"
    name = f"{stamp}_{safe_trigger}"
    final = os.path.join(incidents, name)
    n = 1
    while os.path.exists(final):
        n += 1
        final = os.path.join(incidents, f"{name}.{n}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    notes: List[str] = []

    # Snapshot-then-release: every recorder read below takes the ring lock
    # briefly and copies; no lock is held across any file write.
    events = recorder.ring_snapshot()
    step_tail = recorder.step_tail_snapshot()
    _write_jsonl(os.path.join(tmp, "events.jsonl"), events)
    _write_jsonl(os.path.join(tmp, "step_tail.jsonl"), step_tail)

    for fname, share in _TAIL_SHARES.items():
        cap = max(4096, int(budget * share))
        if fname == "trace_tail.jsonl":
            records = _collect_trace_tail(base_dir, span_tail, cap, notes)
            # Enforce the byte cap post-hoc: keep the newest records.
            lines = [json.dumps(r, default=str) for r in records]
            while lines and sum(len(l) + 1 for l in lines) > cap:
                # Never drop anchor records — alignment depends on them.
                for i, l in enumerate(lines):
                    if "_clock_anchor" not in l:
                        del lines[i]
                        notes.append("trace_tail trimmed to byte budget")
                        break
                else:
                    break
            with open(os.path.join(tmp, fname), "w", encoding="utf-8") as f:
                f.write("\n".join(lines) + ("\n" if lines else ""))
            continue
        src = os.path.join(base_dir,
                           "metrics.jsonl" if fname == "metrics_tail.jsonl"
                           else "restart_log.jsonl")
        lines, truncated = _tail_lines(src, cap)
        if truncated:
            notes.append(f"{fname}: source truncated to last {cap} bytes")
        with open(os.path.join(tmp, fname), "w", encoding="utf-8") as f:
            f.write("\n".join(lines) + ("\n" if lines else ""))

    with open(os.path.join(tmp, "env.json"), "w", encoding="utf-8") as f:
        json.dump(_env_report(), f, indent=1, default=str)
    _write_stacks(os.path.join(tmp, "stacks.txt"))

    manifest = {
        "schema_version": SCHEMA_VERSION,
        "trigger": str(trigger),
        "rank": recorder.rank,
        "world_size": recorder.world_size,
        "ts": recorder.now()["ts"],
        "clock_anchor": recorder.clock_anchor(),
        "config_fingerprint": recorder.config_fingerprint,
        "events_total": recorder.events_total,
        "errors_total": recorder.errors_total,
        "ring_len": len(events),
        "last_step": recorder.last_step,
        "budget_bytes": budget,
        "notes": notes,
        "files": sorted(os.listdir(tmp)) + ["manifest.json"],
    }
    with open(os.path.join(tmp, "manifest.json"), "w", encoding="utf-8") as f:
        json.dump(manifest, f, indent=1, default=str)

    # Hard budget backstop: if we still overflowed (pathological tails),
    # drop the biggest capped artifact rather than exceed the budget.
    total = sum(os.path.getsize(os.path.join(tmp, fn))
                for fn in os.listdir(tmp))
    if total > budget:
        victims = sorted(_TAIL_SHARES, reverse=True,
                         key=lambda fn: os.path.getsize(os.path.join(tmp, fn))
                         if os.path.exists(os.path.join(tmp, fn)) else 0)
        for fn in victims:
            p = os.path.join(tmp, fn)
            if os.path.exists(p) and total > budget:
                total -= os.path.getsize(p)
                os.truncate(p, 0)
                notes.append(f"{fn} emptied: bundle exceeded "
                             f"{budget} byte budget")
        with open(os.path.join(tmp, "manifest.json"), "w",
                  encoding="utf-8") as f:
            manifest["notes"] = notes
            json.dump(manifest, f, indent=1, default=str)

    os.replace(tmp, final)
    return final


def prune_bundles(incidents_dir: str, max_bundles: int) -> None:
    """Delete the oldest bundles past ``max_bundles`` (and stale .tmp)."""
    try:
        entries = sorted(
            e for e in os.listdir(incidents_dir)
            if os.path.isdir(os.path.join(incidents_dir, e)))
    except OSError:
        return
    for e in list(entries):
        if e.endswith(".tmp"):
            shutil.rmtree(os.path.join(incidents_dir, e), ignore_errors=True)
            entries.remove(e)
    excess = len(entries) - max(1, int(max_bundles))
    for e in entries[:max(0, excess)]:
        logger.warning("blackbox: pruning old incident bundle %s", e)
        shutil.rmtree(os.path.join(incidents_dir, e), ignore_errors=True)
