"""``bin/ds_perf`` — perf-ledger diff, regression gate, calibration report.

Subcommands (all pure stdlib — run them on a laptop, in CI, anywhere):

* ``ds_perf show <ledger>`` — latest entry per benchmark series, with
  fingerprint/revision so "what changed" is visible at a glance.
* ``ds_perf diff <A> <B> [--rel-tol 0.05]`` — compare the latest entries
  of every series two ledgers share, with noise bounds: a delta only
  counts as regression/improvement when the per-step samples clear a
  Welch-style t gate (entries without samples fall back to the plain
  threshold).
* ``ds_perf gate --baseline base.jsonl [--candidate perf_ledger.jsonl]``
  — CI teeth: exit 2 when a gated series regresses OR its newest
  candidate entry is a failure line (a crashed headline bench fails the
  gate even when an older success sits in the append-only ledger), exit
  3 when a gated series was never measured (``--allow-missing``
  downgrades that to a warning). Default gate set = the baseline's
  entries marked ``"headline": true`` (every series when none is);
  ``--metric SUBSTR`` gates matching series instead, ``--all`` gates
  every shared series.
* ``ds_perf calibration <ledger|results_dir>`` — predicted-vs-measured
  cost-model error over the autotuner's ``tune_candidate`` entries.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from deepspeed_tpu.perf import calibration as cal
from deepspeed_tpu.perf import ledger as led


def _fmt_val(v: float) -> str:
    return f"{v:.4f}" if abs(v) < 100 else f"{v:.1f}"


def _load(path: str):
    if not os.path.exists(path):
        print(f"ds_perf: no such file: {path}", file=sys.stderr)
        raise SystemExit(1)
    try:
        return led.load_baseline(path)
    except ValueError as e:
        print(f"ds_perf: {e}", file=sys.stderr)
        raise SystemExit(1)


def _cmd_show(args) -> int:
    latest = led.latest_by_series(_load(args.ledger))
    if not latest:
        print("ds_perf show: ledger holds no entries")
        return 1
    rows = [("series", "value", "unit", "rev", "fingerprint", "samples")]
    for key in sorted(latest):
        e = latest[key]
        rows.append((key.split(" [", 1)[0], _fmt_val(float(e.get("value") or 0.0)),
                     str(e.get("unit", "")), str(e.get("git_rev") or "-"),
                     str(e.get("fingerprint") or "-")[:12],
                     str(len(e.get("samples") or []))))
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    for i, r in enumerate(rows):
        print("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip())
        if i == 0:
            print("  ".join("-" * w for w in widths))
    return 0


def _select(series_keys, metric_substrs):
    if not metric_substrs:
        return list(series_keys)
    return [k for k in series_keys
            if any(s.lower() in k.lower() for s in metric_substrs)]


def _split_metrics(metric_args):
    """``--metric`` accepts BOTH series-key substrings and attribution
    metric names (``exposed_comm``, ``goodput``). The latter select WHAT
    is judged — the embedded attribution value of each gated series —
    not which series: `ds_perf gate --metric exposed_comm` gates the
    default (headline) series set on its exposed-comm µs/step."""
    attr = [m for m in metric_args if m in led.ATTRIBUTION_METRICS]
    series = [m for m in metric_args if m not in led.ATTRIBUTION_METRICS]
    return series, attr


def _world_tag(r):
    """The world-identity tag on a compare line: a pair measured on
    different device counts — or an entry whose run crossed an elastic
    resize mid-run — is fingerprint-changed, never a silent comparison
    (ds_resize contract; ledger.compare sets the flags)."""
    if not r.get("world_changed"):
        return ""
    wo, wn = r.get("old_world"), r.get("new_world")
    if wo is not None and wn is not None and wo != wn:
        return (f"  [world changed {wo} -> {wn} device(s): "
                "not two views of one experiment]")
    mo, mn = r.get("old_mesh_axes"), r.get("new_mesh_axes")
    if mo is not None and mn is not None and mo != mn:
        return (f"  [mesh changed {mo} -> {mn}: same device count, "
                "different layout — not two views of one experiment]")
    return "  [world resized mid-run: not two views of one experiment]"


def _exposed_line(r):
    if "new_exposed_comm_us" not in r:
        return ""
    return (f"  exposed_comm {r['old_exposed_comm_us']:.0f} -> "
            f"{r['new_exposed_comm_us']:.0f} us/step"
            + (" [REGRESSED]" if r.get("exposed_comm_regressed") else ""))


def _static_comm_line(r):
    if "new_static_comm_bytes" not in r:
        return ""
    return (f"  static_comm {r['old_static_comm_bytes'] / 2**20:.2f} -> "
            f"{r['new_static_comm_bytes'] / 2**20:.2f} MiB/dev/step"
            + (" [REGRESSED]" if r.get("static_comm_regressed") else ""))


def _sdc_overhead_line(r):
    if "new_sdc_overhead" not in r:
        return ""
    return (f"  sdc_overhead {r['old_sdc_overhead']:.2%} -> "
            f"{r['new_sdc_overhead']:.2%} of wall"
            + (" [REGRESSED]" if r.get("sdc_overhead_regressed") else ""))


def _gray_overhead_line(r):
    if "new_gray_overhead" not in r:
        return ""
    return (f"  gray_overhead {r['old_gray_overhead']:.2%} -> "
            f"{r['new_gray_overhead']:.2%} of wall"
            + (" [REGRESSED]" if r.get("gray_overhead_regressed") else ""))


def _blackbox_overhead_line(r):
    if "new_blackbox_overhead" not in r:
        return ""
    return (f"  blackbox_overhead {r['old_blackbox_overhead']:.3%} -> "
            f"{r['new_blackbox_overhead']:.3%} of wall"
            + (" [REGRESSED]" if r.get("blackbox_overhead_regressed")
               else ""))


def _mfu_gap_line(r):
    if "new_mfu_gap" not in r:
        return ""
    return (f"  mfu_gap {r['old_mfu_gap']:.3f} -> "
            f"{r['new_mfu_gap']:.3f} below ceiling"
            + (" [REGRESSED]" if r.get("mfu_gap_regressed") else ""))


def _cmd_diff(args) -> int:
    old = led.latest_by_series(_load(args.old))
    new = led.latest_by_series(_load(args.new))
    series_sel, attr_sel = _split_metrics(args.metric)
    shared = _select([k for k in old if k in new], series_sel)
    if not shared:
        print("ds_perf diff: the two ledgers share no benchmark series",
              file=sys.stderr)
        return 1
    results = [led.compare(old[k], new[k], rel_tol=args.rel_tol)
               for k in sorted(shared)]
    if args.json:
        print(json.dumps(results, indent=2))
        return 0
    for r in results:
        mark = {"regression": "--", "improvement": "++",
                "within_noise": "=="}[r["verdict"]]
        noise = ""
        if r["significant"] is not None:
            noise = (f"  (t={r['t_stat']:+.1f} over {r['n_old']}/{r['n_new']}"
                     f" samples: {'significant' if r['significant'] else 'noise'})")
        elif r["t_stat"] is not None:
            noise = (f"  ({r['n_old']}/{r['n_new']} samples: underpowered, "
                     f"threshold verdict)")
        fp = _world_tag(r) or ("  [config fingerprint changed]"
                               if r["fingerprint_changed"] else "")
        print(f"{mark} {r['series']}: {_fmt_val(r['old_value'])} -> "
              f"{_fmt_val(r['new_value'])} ({r['rel_delta']:+.1%})"
              f"{noise}{fp}{_exposed_line(r)}{_static_comm_line(r)}"
              f"{_sdc_overhead_line(r)}{_gray_overhead_line(r)}"
              f"{_blackbox_overhead_line(r)}{_mfu_gap_line(r)}")
        if "exposed_comm" in attr_sel and "new_exposed_comm_us" not in r:
            print(f"   {r['series']}: exposed_comm not recorded on both "
                  "sides (needs telemetry-instrumented entries)")
        if "static_comm_bytes" in attr_sel \
                and "new_static_comm_bytes" not in r:
            print(f"   {r['series']}: static_comm_bytes not recorded on "
                  "both sides (needs perf.static_comm entries)")
        if "sdc_overhead" in attr_sel and "new_sdc_overhead" not in r:
            print(f"   {r['series']}: sdc_overhead not recorded on both "
                  "sides (needs entries measured under the sdc + goodput "
                  "blocks)")
        if "gray_overhead" in attr_sel and "new_gray_overhead" not in r:
            print(f"   {r['series']}: gray_overhead not recorded on both "
                  "sides (needs entries measured under the gray + goodput "
                  "blocks)")
        if "blackbox_overhead" in attr_sel \
                and "new_blackbox_overhead" not in r:
            print(f"   {r['series']}: blackbox_overhead not recorded on "
                  "both sides (needs entries measured under the blackbox "
                  "block with telemetry tracing or the goodput ledger)")
        if "mfu_gap" in attr_sel and "new_mfu_gap" not in r:
            print(f"   {r['series']}: mfu_gap not recorded on both sides "
                  "(needs MFU entries measured under the roofline + perf "
                  "blocks)")
    return 0


def _cmd_gate(args) -> int:
    base = led.latest_by_series(_load(args.baseline))
    cand_path = args.candidate
    cand_entries = _load(cand_path)
    cand = led.latest_by_series(cand_entries)
    # the gate's question is "what did the NEWEST run do" — a gated
    # benchmark whose newest entry is a failure must fail the gate even
    # when an older success of the same series sits in the append-only
    # ledger (and a gated series the run never measured is a failure by
    # default, not a warning: a crashed bench exits the same way a
    # regressed one does)
    newest = led.newest_by_series(cand_entries)
    series_sel, attr_sel = _split_metrics(args.metric)
    if args.all:
        gated = [k for k in base if k in cand or k in newest]
    elif series_sel:
        gated = _select(base.keys(), series_sel)
    else:
        gated = [k for k, e in base.items() if e.get("headline")]
        if not gated:
            gated = list(base)
    if not gated:
        print("ds_perf gate: no gated series selected", file=sys.stderr)
        return 1
    failures, crashed, missing, checked = [], [], [], []
    for k in sorted(gated):
        newest_e = newest.get(k)
        if newest_e is not None and newest_e.get("failed"):
            crashed.append(k)
            continue
        if k not in cand or (newest_e is not None
                             and led.is_nonmeasurement(newest_e)):
            missing.append(k)     # never measured, or newest run skipped it
            continue
        r = led.compare(base[k], cand[k], rel_tol=args.rel_tol)
        if "exposed_comm" in attr_sel and "new_exposed_comm_us" not in r:
            # gating ON exposed_comm but a side never recorded it: that is
            # a missing measurement, not a pass — same policy as a series
            # the run never measured
            missing.append(f"{k} (exposed_comm attribution)")
            continue
        if "static_comm_bytes" in attr_sel \
                and "new_static_comm_bytes" not in r:
            missing.append(f"{k} (static_comm_bytes attribution)")
            continue
        if "sdc_overhead" in attr_sel and "new_sdc_overhead" not in r:
            missing.append(f"{k} (sdc_overhead attribution)")
            continue
        if "gray_overhead" in attr_sel and "new_gray_overhead" not in r:
            missing.append(f"{k} (gray_overhead attribution)")
            continue
        if "blackbox_overhead" in attr_sel \
                and "new_blackbox_overhead" not in r:
            missing.append(f"{k} (blackbox_overhead attribution)")
            continue
        if "mfu_gap" in attr_sel and "new_mfu_gap" not in r:
            missing.append(f"{k} (mfu_gap attribution)")
            continue
        checked.append(r)
        if r["verdict"] == "regression" or not r["new_value"] \
                or r.get("goodput_regressed") \
                or ("exposed_comm" in attr_sel
                    and r.get("exposed_comm_regressed")) \
                or ("static_comm_bytes" in attr_sel
                    and r.get("static_comm_regressed")) \
                or ("sdc_overhead" in attr_sel
                    and r.get("sdc_overhead_regressed")) \
                or ("gray_overhead" in attr_sel
                    and r.get("gray_overhead_regressed")) \
                or ("blackbox_overhead" in attr_sel
                    and r.get("blackbox_overhead_regressed")) \
                or ("mfu_gap" in attr_sel
                    and r.get("mfu_gap_regressed")):
            failures.append(r)
    if args.json:
        print(json.dumps({"checked": checked, "missing": missing,
                          "crashed": crashed,
                          "failures": [f["series"] for f in failures],
                          "rel_tol": args.rel_tol,
                          "allow_missing": args.allow_missing}, indent=2))
    else:
        for r in checked:
            ok = r not in failures
            line = (f"{'PASS' if ok else 'FAIL'} {r['series']}: "
                    f"{_fmt_val(r['old_value'])} -> {_fmt_val(r['new_value'])} "
                    f"({r['rel_delta']:+.1%}, tol {args.rel_tol:.0%})")
            if "new_goodput" in r:
                line += (f" goodput {r['old_goodput']:.3f} -> "
                         f"{r['new_goodput']:.3f}"
                         + (" [REGRESSED]" if r.get("goodput_regressed")
                            else ""))
            print(line + _world_tag(r) + _exposed_line(r)
                  + _static_comm_line(r) + _sdc_overhead_line(r)
                  + _gray_overhead_line(r) + _blackbox_overhead_line(r)
                  + _mfu_gap_line(r))
        for k in crashed:
            e = newest[k]
            print(f"FAIL {k}: newest run FAILED "
                  f"({e.get('error_type', '?')}; see ledger traceback"
                  + (f", telemetry: {e['telemetry_dir']}"
                     if e.get("telemetry_dir") else "") + ")")
        for k in missing:
            print(f"{'WARN' if args.allow_missing else 'FAIL'} {k}: "
                  f"not measured in {cand_path}")
    if failures or crashed:
        return 2
    if missing and not args.allow_missing:
        return 3
    return 0


def _cmd_calibration(args) -> int:
    path = args.ledger
    if os.path.isdir(path):
        path = os.path.join(path, "perf_ledger.jsonl")
    if not os.path.exists(path):
        print(f"ds_perf calibration: no such file: {path}", file=sys.stderr)
        return 1
    entries = led.load_entries(path)
    rows = cal.calibration_rows(entries)
    counters = {}
    for e in entries:
        if e.get("kind") == "tune_summary":
            counters = e.get("counters") or {}
    if args.json:
        print(json.dumps({"rows": rows,
                          "summary": cal.calibration_summary(rows),
                          "counters": counters}, indent=2))
        return 0
    print(cal.render_calibration(rows, counters=counters, source=path))
    return 0 if rows else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="ds_perf",
        description="perf ledger: show / diff / regression gate / "
                    "cost-model calibration")
    sub = p.add_subparsers(dest="cmd")

    s = sub.add_parser("show", help="latest entry per benchmark series")
    s.add_argument("ledger", help="perf ledger JSONL")

    d = sub.add_parser("diff", help="compare two ledgers with noise bounds")
    d.add_argument("old")
    d.add_argument("new")
    d.add_argument("--rel-tol", type=float, default=0.05,
                   help="relative tolerance before a delta counts (default 5%%)")
    d.add_argument("--metric", action="append", default=[],
                   help="only series whose key contains SUBSTR (repeatable); "
                        "the attribution metrics 'exposed_comm'/'goodput' "
                        "instead select WHAT is compared")
    d.add_argument("--json", action="store_true")

    g = sub.add_parser("gate", help="exit 2 on a gated-series regression")
    g.add_argument("--baseline", required=True,
                   help="baseline ledger")
    g.add_argument("--candidate", default="perf_ledger.jsonl",
                   help="candidate ledger (default ./perf_ledger.jsonl)")
    g.add_argument("--rel-tol", type=float, default=0.08,
                   help="allowed relative regression (default 8%%)")
    g.add_argument("--metric", action="append", default=[],
                   help="gate series whose key contains SUBSTR (repeatable); "
                        "default: the baseline's headline entry. "
                        "'exposed_comm' gates the selected series on their "
                        "exposed-comm µs/step attribution (lower is better; "
                        "growth past tolerance + a 50µs floor fails) — the "
                        "overlap win regresses like a headline metric. "
                        "'static_comm_bytes' gates on the xray compiled-HLO "
                        "comm bill (lower is better; deterministic, so any "
                        "growth past tolerance + a 1MiB floor is a real "
                        "schedule regression — no hardware needed). "
                        "'sdc_overhead' gates on the replay-audit cost as a "
                        "fraction of wall (lower is better; absolute-point "
                        "tolerance + a 0.5-point floor — the sdc sentry's "
                        "defense must stay under audit_interval⁻¹ of wall). "
                        "'gray_overhead' gates on the ds_gray microprobe "
                        "cost as a fraction of wall (lower is better; "
                        "absolute-point tolerance + a 0.5-point floor — the "
                        "fail-slow defense must stay <= 2%% of wall at the "
                        "default cadence). "
                        "'blackbox_overhead' gates on the flight recorder's "
                        "ring-append cost as a fraction of wall (lower is "
                        "better; absolute-point tolerance + a 0.5-point "
                        "floor — always-on must stay effectively free). "
                        "'mfu_gap' gates on the roofline distance (analytic "
                        "mfu_ceiling − measured MFU, lower is better; "
                        "absolute-point tolerance + a 2-point floor; "
                        "entries without the roofline attribution count as "
                        "missing — exit 3)")
    g.add_argument("--all", action="store_true",
                   help="gate every series the two files share")
    g.add_argument("--allow-missing", action="store_true",
                   help="downgrade 'gated series not measured in the "
                        "candidate' from a failure (exit 3) to a warning — "
                        "default is to fail, because a bench that crashed "
                        "before its line looks exactly like one that was "
                        "never run")
    g.add_argument("--json", action="store_true")

    c = sub.add_parser("calibration",
                       help="predicted-vs-measured cost-model error report")
    c.add_argument("ledger",
                   help="perf ledger JSONL or a ds_tune results dir")
    c.add_argument("--json", action="store_true")

    args = p.parse_args(argv)
    if args.cmd == "show":
        return _cmd_show(args)
    if args.cmd == "diff":
        return _cmd_diff(args)
    if args.cmd == "gate":
        return _cmd_gate(args)
    if args.cmd == "calibration":
        return _cmd_calibration(args)
    p.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
