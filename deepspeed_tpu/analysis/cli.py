"""ds_doctor CLI — catch the TPU-burning bug before step 0.

Usage::

    ds_doctor --config ds_config.json [options]

Options:
    --config PATH          ds_config JSON (required unless --passes names
                           only selflint / race)
    --model FAMILY         trace a registry family's fwd+bwd graph under the
                           config's compute dtype (gpt2 | llama | moe | bert,
                           or any preset name like gpt2-tiny)
    --graph FILE[:FN]      custom graph builder: FILE is a python file whose
                           FN (default "build_graph") is called with the
                           parsed DeepSpeedConfig and returns (fn, args) or
                           (fn, args, donate_argnums) — your actual train
                           step, linted instead of a fixture
    --collective-log PATH  recorded collective sequence JSON, one flag per
                           rank (analysis.collectives.CollectiveRecorder
                           .save); two or more are diffed across ranks
    --passes LIST          comma list of schema,sharding,graph,collectives,
                           race,selflint (default: every pass its inputs
                           allow)
    --fail-on LEVEL        error | warn | never (default error): exit 2 when
                           findings at/above LEVEL exist
    --world-size N         data-parallel world for batch-triple validation
    --batch N --seq N      synthetic batch geometry for --model (default 2/16)
    --json                 machine-readable report on stdout

Exit codes: 0 = clean (below fail-on), 2 = findings tripped fail-on,
1 = usage/internal error.

Subcommand::

    ds_doctor xray --config ds_config.json [--model gpt2] [--devices 8]

builds a family-fixture engine from the config, runs ONE train step to
populate the ``sharded_jit`` program table, then AOT-compiles every
program and lints the COMPILED HLO (collective-order, promise-vs-actual,
donation audit, static comm bytes) — the post-GSPMD layer the trace
passes cannot see. ``--devices N`` forces N simulated CPU devices (set
before the jax backend initializes), so an 8-way ZeRO config x-rays on a
laptop.

Subcommand::

    ds_doctor race [--witness FILE ...] [--allow RULE ...]

host-side concurrency analysis: the static lock-order / blocking-under-
lock / signal-safety lint over the package (and bin/*), plus
offline analysis of runtime lock-witness logs (``utils.locks
.save_witness``) — acquisition-order inversions are reported with both
call sites even when no deadlock ever manifested. Needs no --config.
"""

from __future__ import annotations

import argparse
import os
import sys


def _parse(argv):
    ap = argparse.ArgumentParser(
        prog="ds_doctor",
        description="static graph/sharding/collective/config analysis")
    ap.add_argument("--config", default=None)
    ap.add_argument("--model", default=None)
    ap.add_argument("--graph", default=None)
    ap.add_argument("--collective-log", action="append", default=[])
    ap.add_argument("--passes", default=None)
    ap.add_argument("--fail-on", default="error",
                    choices=["error", "warn", "never"])
    ap.add_argument("--world-size", type=int, default=None)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--json", action="store_true")
    return ap.parse_args(argv)


def _load_graph_builder(spec: str, cfg):
    """FILE[:FN] -> (fn, args[, donate_argnums]) from user code."""
    path, _, fn_name = spec.partition(":")
    fn_name = fn_name or "build_graph"
    scope: dict = {"__file__": path, "__name__": "_ds_doctor_graph"}
    with open(path) as f:
        exec(compile(f.read(), path, "exec"), scope)
    builder = scope.get(fn_name)
    if builder is None:
        raise SystemExit(f"ds_doctor: {path} defines no {fn_name}()")
    out = builder(cfg)
    if len(out) == 2:
        # no donation opinion from the builder: None (not ()) keeps the
        # donation lint off — run_doctor's contract is that it runs only
        # when the caller/builder actually states the donation set
        fn, args = out
        return fn, args, None
    fn, args, donate = out
    return fn, args, donate


def xray_cli(argv) -> int:
    """``ds_doctor xray`` — build an engine fixture, step once, x-ray
    the compiled fleet."""
    ap = argparse.ArgumentParser(
        prog="ds_doctor xray",
        description="post-GSPMD compiled-HLO analysis of every program "
                    "in the sharded_jit table")
    ap.add_argument("--config", required=True, help="ds_config JSON path")
    ap.add_argument("--model", default="gpt2",
                    help="registry family/preset fixture (default gpt2)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N simulated CPU devices (must be set "
                         "before the jax backend initializes)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--fail-on", default="error",
                    choices=["error", "warn", "never"])
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    if args.devices and args.devices > 1:
        # same rewrite rule as __graft_entry__: a PRE-EXISTING smaller
        # count in XLA_FLAGS must be raised, not silently kept — or the
        # "8-device" analysis quietly runs on a 4-device mesh
        import re

        fl = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", fl)
        if m is None:
            fl = (fl + f" --xla_force_host_platform_device_count="
                  f"{args.devices}").strip()
        elif int(m.group(1)) < args.devices:
            fl = fl.replace(m.group(0),
                            f"--xla_force_host_platform_device_count="
                            f"{args.devices}")
        os.environ["XLA_FLAGS"] = fl
        import jax

        try:
            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass
    from deepspeed_tpu.analysis.findings import AnalysisReport
    from deepspeed_tpu.analysis.xray import xray_for_config

    try:
        result = xray_for_config(args.config, args.model,
                                 batch_size=args.batch, seq_len=args.seq)
    except FileNotFoundError as e:
        print(f"ds_doctor xray: {e}", file=sys.stderr)
        return 1
    report = AnalysisReport().extend(result.findings, "xray")
    if args.json:
        import json as _json

        payload = _json.loads(report.to_json())
        payload["programs"] = result.comm
        print(_json.dumps(payload, indent=2))
    else:
        print(result.render())
        if report.findings:
            print(report.render("ds_doctor xray findings"))
    return 2 if report.should_fail(args.fail_on) else 0


def race_cli(argv) -> int:
    """``ds_doctor race`` — the host-side concurrency report: static
    lock-order cycles, blocking calls under framework locks, signal-
    handler safety, and (with ``--witness``) acquisition-order inversions
    observed at runtime by the instrumented lock factory."""
    ap = argparse.ArgumentParser(
        prog="ds_doctor race",
        description="static lock-order / blocking-under-lock / "
                    "signal-safety lint over the package, plus offline "
                    "witness-log inversion analysis")
    ap.add_argument("--root", default=None,
                    help="package root to analyze (default: the installed "
                         "deepspeed_tpu package)")
    ap.add_argument("--no-scripts", action="store_true",
                    help="skip bin/* (package modules only — "
                         "the scope the engine-init pass uses)")
    ap.add_argument("--witness", action="append", default=[],
                    help="witness JSON from utils.locks.save_witness(); "
                         "repeatable — edges are unioned across files "
                         "(ranks), inversions cite both acquire sites")
    ap.add_argument("--allow", action="append", default=[],
                    help="suppress 'race/<rule>[:<citation substr>]' "
                         "(same grammar as the analysis.race_allowlist "
                         "config knob)")
    ap.add_argument("--fail-on", default="error",
                    choices=["error", "warn", "never"])
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    from deepspeed_tpu.analysis.findings import AnalysisReport
    from deepspeed_tpu.analysis.race import (lint_race, load_witness,
                                             witness_findings)

    report = AnalysisReport()
    report.extend(lint_race(root=args.root,
                            include_scripts=not args.no_scripts,
                            allowlist=tuple(args.allow)), "race")
    if args.witness:
        edges = []
        for path in args.witness:
            try:
                edges.extend(load_witness(path))
            except (OSError, ValueError) as e:
                print(f"ds_doctor race: cannot read witness {path}: {e}",
                      file=sys.stderr)
                return 1
        report.extend(witness_findings(edges), "race")
    if args.json:
        print(report.to_json())
    else:
        print(report.render("ds_doctor race"))
    return 2 if report.should_fail(args.fail_on) else 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "xray":
        return xray_cli(argv[1:])
    if argv and argv[0] == "race":
        return race_cli(argv[1:])
    args = _parse(argv)
    from deepspeed_tpu.analysis.doctor import ALL_PASSES, run_doctor

    # None = "every pass its inputs allow"; an explicit list additionally
    # reports pass-skipped findings when a requested pass cannot run
    passes = tuple(args.passes.split(",")) if args.passes else None
    unknown = [p for p in (passes or ()) if p not in ALL_PASSES]
    if unknown:
        print(f"ds_doctor: unknown pass(es) {unknown}; known: {ALL_PASSES}",
              file=sys.stderr)
        return 1
    if args.config is None and \
            not set(passes or ALL_PASSES) <= {"selflint", "race"}:
        print("ds_doctor: --config is required (or --passes "
              "selflint and/or race)", file=sys.stderr)
        return 1

    graph = None
    if args.graph:
        if args.config is None:
            print("ds_doctor: --graph needs --config", file=sys.stderr)
            return 1
        # deferred: run_doctor parses the config ONCE and hands it to the
        # builder (the graph pass is skipped when the config is invalid —
        # the schema findings explain why)
        graph = lambda cfg: _load_graph_builder(args.graph, cfg)

    try:
        report = run_doctor(
            args.config if args.config is not None else {},
            passes=passes, fail_on=args.fail_on, model=args.model,
            graph=graph,
            collective_logs=args.collective_log or None,
            world_size=args.world_size, batch_size=args.batch,
            seq_len=args.seq)
    except FileNotFoundError as e:
        print(f"ds_doctor: {e}", file=sys.stderr)
        return 1
    if args.json:
        print(report.to_json())
    else:
        print(report.render())
    return 2 if report.should_fail(args.fail_on) else 0


def doctor_section(argv) -> int:
    """``ds_report doctor --config X [--fail-on L]`` — the config/schema
    pass only, rendered as a report section (the full tool is ds_doctor)."""
    ap = argparse.ArgumentParser(prog="ds_report doctor")
    ap.add_argument("--config", required=True)
    ap.add_argument("--fail-on", default="never",
                    choices=["error", "warn", "never"])
    args = ap.parse_args(argv)
    from deepspeed_tpu.analysis.doctor import run_doctor

    report = run_doctor(args.config, passes=("schema",),
                        fail_on=args.fail_on)
    line = "-" * 72
    print(line)
    print("doctor: config/schema findings")
    print(line)
    print(report.render("ds_doctor (schema pass)"))
    print(line)
    print("run bin/ds_doctor for the graph / sharding / collective passes")
    return 2 if report.should_fail(args.fail_on) else 0


if __name__ == "__main__":
    sys.exit(main())
