"""Environment/compatibility report — the ``ds_report`` tool.

Counterpart of reference ``deepspeed/env_report.py`` (driven by
``bin/ds_report``), which tabulates op-build status and torch/cuda versions.
Here: JAX stack versions, backend + device inventory, ICI topology hints,
per-device memory, and kernel (Pallas) availability.
"""

from __future__ import annotations

import importlib
import os
import shutil
import sys

GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"


def _version(mod_name: str):
    try:
        mod = importlib.import_module(mod_name)
        return getattr(mod, "__version__", "unknown")
    except Exception:
        return None


def software_report():
    rows = []
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint", "chex",
                "einops", "numpy", "pydantic"):
        v = _version(mod)
        rows.append((mod, v if v else RED_NO))
    try:
        import deepspeed_tpu

        rows.append(("deepspeed_tpu", deepspeed_tpu.__version__))
    except Exception:
        rows.append(("deepspeed_tpu", RED_NO))
    return rows


def hardware_report():
    rows = []
    try:
        import jax

        backend = jax.default_backend()
        devices = jax.devices()
        rows.append(("backend", backend))
        rows.append(("process count", jax.process_count()))
        rows.append(("global devices", len(devices)))
        rows.append(("local devices", len(jax.local_devices())))
        if devices:
            d = devices[0]
            rows.append(("device kind", d.device_kind))
            coords = getattr(d, "coords", None)
            if coords is not None:
                rows.append(("device 0 coords", coords))
            stats = None
            try:
                stats = d.memory_stats()
            except Exception:
                pass
            if stats:
                lim = stats.get("bytes_limit")
                use = stats.get("bytes_in_use")
                if lim:
                    rows.append(("HBM per device", f"{lim / 2**30:.1f} GiB "
                                 f"({(use or 0) / 2**30:.2f} in use)"))
    except Exception as e:  # pragma: no cover
        rows.append(("jax devices", f"{RED_NO} ({e})"))
    return rows


def profiling_report():
    """ds_prof capability probe: per-device memory stats through the
    accelerator API, and whether this backend's executables expose
    ``memory_analysis`` (the static HBM accounting `profiling` uses)."""
    rows = []
    try:
        from deepspeed_tpu.accelerator import get_accelerator

        acc = get_accelerator()
        n = acc.device_count()
        for i in range(n):
            stats = acc.memory_stats(i)
            if stats:
                lim = stats.get("bytes_limit", 0)
                use = stats.get("bytes_in_use", 0)
                peak = stats.get("peak_bytes_in_use", 0)
                rows.append((f"device {i} memory",
                             f"{use / 2**30:.2f} / {lim / 2**30:.2f} GiB in use "
                             f"(peak {peak / 2**30:.2f})"))
            else:
                rows.append((f"device {i} memory",
                             "no memory_stats on this backend"))
            if i == 0 and n > 4:
                rows.append(("...", f"({n} local devices)"))
                break
    except Exception as e:  # pragma: no cover
        rows.append(("accelerator memory", f"{RED_NO} ({e})"))
    try:
        import jax

        mem = jax.jit(lambda x: x + 1).lower(
            jax.ShapeDtypeStruct((8,), "float32")).compile().memory_analysis()
        rows.append(("memory_analysis", GREEN_OK if mem is not None
                     else f"{RED_NO} (backend returns None)"))
        live = jax.live_arrays()
        rows.append(("live arrays", f"{len(live)} "
                     f"({sum(int(getattr(a, 'nbytes', 0)) for a in live) / 2**20:.1f} MiB)"))
    except Exception as e:  # pragma: no cover
        rows.append(("memory_analysis", f"{RED_NO} ({e})"))
    return rows


def overlap_report():
    """The overlap engine's XLA latency-hiding scheduler preset
    (runtime/overlap.py): which flags are live in this environment's
    XLA_FLAGS. The engine appends missing ones at init ON TPU (a CPU/GPU
    XLA aborts on unknown flags), but only child processes see flags
    added after backend init — this report shows what the NEXT process
    will actually run under."""
    from deepspeed_tpu.runtime.overlap import scheduler_flag_status

    import jax

    rows = [("backend", jax.default_backend()),
            ("preset applies", "yes (TPU)" if jax.default_backend() == "tpu"
             else "no (TPU-compiler flags; engine skips them here)")]
    for flag, present in scheduler_flag_status():
        rows.append((flag.split("=", 1)[0].replace("--xla_", ""),
                     "set" if present else "unset"))
    return rows


def kernel_report():
    rows = []
    try:
        from jax.experimental import pallas  # noqa: F401

        rows.append(("pallas", GREEN_OK))
    except Exception:
        rows.append(("pallas", RED_NO))
    try:
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention  # noqa: F401

        rows.append(("flash_attention kernel", GREEN_OK))
    except Exception:
        rows.append(("flash_attention kernel", RED_NO))
    try:
        from deepspeed_tpu.ops.op_builder import AsyncIOBuilder

        rows.append(("async_io (C++)", GREEN_OK if AsyncIOBuilder().is_compatible() else RED_NO))
    except Exception:
        rows.append(("async_io (C++)", RED_NO))
    for tool in ("g++", "cmake", "ninja"):
        rows.append((tool, GREEN_OK if shutil.which(tool) else RED_NO))
    return rows


def rewind_section(args):
    """``ds_report rewind <save_dir>`` — the restore ladder's view of a
    checkpoint directory: every candidate tag with its tier (emergency vs
    ordinary), step, verification verdict, and which one the ladder would
    pick. The tier-0 RAM ring is process-local and therefore invisible
    here (its status lives in the run's own telemetry — `ds_top` /
    `ds_metrics` render the rewind line)."""
    from deepspeed_tpu.resilience.manifest import (candidate_tags,
                                                   read_latest, tag_step,
                                                   verify_tag)
    from deepspeed_tpu.runtime.checkpoint_engine.engine import (
        is_emergency_tag, tag_world)

    if not args:
        print("usage: ds_report rewind <checkpoint save_dir>",
              file=sys.stderr)
        return 2
    save_dir = os.path.abspath(args[0])
    if not os.path.isdir(save_dir):
        print(f"ds_report rewind: no such directory: {save_dir}",
              file=sys.stderr)
        return 2
    tags = candidate_tags(save_dir)
    latest = read_latest(save_dir)
    print(f"restore ladder for {save_dir}")
    print("(tier-0 RAM snapshots are process-local: see the run's ds_top/"
          "ds_metrics rewind line)")
    if not tags:
        print("  no candidate tags")
        return 1
    picked = None
    rows = []
    for tag in tags:
        tag_dir = os.path.join(save_dir, tag)
        tier = ("tier-1 emergency" if is_emergency_tag(tag_dir)
                else "tier-2 checkpoint")
        ok, reason = verify_tag(tag_dir)
        parsed = tag_step(tag)
        step = str(parsed) if parsed >= 0 else "?"
        # the world the tag was saved under (ds_resize: a load on a
        # different world reshards — emergency tags only with the
        # elasticity.resize knob, orbax tags natively)
        n = tag_world(tag_dir)
        world = str(n) if n else "?"
        mark = ""
        if ok and picked is None:
            picked = tag
            mark = "  <- ladder picks"
        pointer = "  (= 'latest')" if tag == latest else ""
        rows.append(f"  {tag:<28} {tier:<18} step {step:<8} "
                    f"world {world:<4} "
                    f"{GREEN_OK if ok else RED_NO}"
                    f"{'' if ok else ' (' + reason + ')'}{pointer}{mark}")
    print("\n".join(rows))
    if picked is None:
        print("  NOTHING restorable — every candidate failed verification")
        return 1
    return 0


def goodput_section(args):
    """Render the newest session trace's bucket table from a telemetry
    output dir (or an explicit trace file)."""
    from deepspeed_tpu.goodput.ledger import load_trace_file, session_ledger
    from deepspeed_tpu.goodput.report import (find_session_traces,
                                              render_session_table)

    if not args:
        print("usage: ds_report goodput <telemetry_dir | trace.json>",
              file=sys.stderr)
        return 2
    paths = [p for p in find_session_traces(args) if os.path.isfile(p)]
    if not paths:
        print(f"ds_report goodput: no trace files under {args}",
              file=sys.stderr)
        return 2
    # the newest session: rotation preserves history as trace.session<N>,
    # so the un-suffixed trace.json (sorted last by mtime, not name) is
    # the live one — pick by mtime to be robust to either layout
    newest = max(paths, key=lambda p: os.path.getmtime(p))
    trace = load_trace_file(newest)
    led = session_ledger(trace["events"])
    if led is None:
        print(f"ds_report goodput: {newest} holds no spans", file=sys.stderr)
        return 2
    print(render_session_table(led, source=newest))
    return 0


def mesh_section(args):
    """``ds_report mesh [--config ds_config.json] [--model family]`` — the
    unified mesh (axis names × sizes), the registry's per-pytree specs for
    a family fixture, and the per-program in/out spec table of every
    program compiled in this process (sharded_jit's table). Replaces the
    per-subsystem guesswork: ONE view of what runs where."""
    import json

    from deepspeed_tpu.sharding import (ensure_global_mesh, global_mesh,
                                        mesh_axes_string,
                                        render_program_table)

    config_path = model = None
    it = iter(args)
    for a in it:
        if a == "--config":
            config_path = next(it, None)
        elif a == "--model":
            model = next(it, None)
        elif a in ("-h", "--help"):
            print("usage: ds_report mesh [--config ds_config.json] "
                  "[--model gpt2|llama|moe|bert]")
            return 0
    mesh = global_mesh()
    if config_path is not None:
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        with open(config_path) as f:
            cfg = DeepSpeedConfig(json.load(f))
        mesh = ensure_global_mesh(mesh_config=cfg.mesh_config)
    elif mesh is None:
        mesh = ensure_global_mesh()
    line = "-" * 72
    print(line)
    print(f"unified mesh: {mesh_axes_string(mesh)}")
    for a in mesh.axis_names:
        print(f"  {a:<8} {int(mesh.shape[a])}")
    if model is not None:
        import jax

        from deepspeed_tpu.models.registry import resolve_family
        from deepspeed_tpu.runtime.zero.partition import plan_sharding

        try:
            model_cls, _, presets = resolve_family(model)
            preset = sorted(presets)[0]
            m = model_cls(presets[preset])
            shapes = jax.eval_shape(m.init_params, jax.random.PRNGKey(0))
            tp_specs = m.param_partition_specs() if hasattr(
                m, "param_partition_specs") else None
            zc = cfg.zero_config if config_path else None
            plan = plan_sharding(shapes, mesh, zero_config=zc,
                                 tp_specs=tp_specs)
            print(line)
            print(f"registry specs ({model} fixture, preset {preset}):")
            print(plan.registry.describe())
        except Exception as e:
            print(f"(registry preview unavailable for {model!r}: {e})",
                  file=sys.stderr)
    print(line)
    print("compiled programs (this process):")
    print(render_program_table(mesh))
    return 0


def main(args=None):
    args = list(sys.argv[1:] if args is None else args)
    if args and args[0] == "mesh":
        # `ds_report mesh` — the unified mesh + per-program spec table
        return mesh_section(args[1:])
    if args and args[0] == "doctor":
        # `ds_report doctor --config X` — run the ds_doctor config/schema
        # pass against a ds_config and print its findings
        from deepspeed_tpu.analysis.cli import doctor_section

        return doctor_section(args[1:])
    if args and args[0] == "race":
        # `ds_report race [--witness F]` — the host-side concurrency
        # report (static lock-order / blocking / signal lint + witness
        # inversions); the full tool is `ds_doctor race`
        from deepspeed_tpu.analysis.cli import race_cli

        return race_cli(args[1:])
    if args and args[0] == "goodput":
        # `ds_report goodput <telemetry_dir>` — the LATEST session's
        # goodput bucket table (job-level cross-restart stitching is
        # `ds_prof goodput`'s job)
        return goodput_section(args[1:])
    if args and args[0] == "rewind":
        # `ds_report rewind <save_dir>` — the restore ladder's view of a
        # checkpoint dir (tiers, verification, what would be picked)
        return rewind_section(args[1:])
    if args and args[0] == "xray":
        # `ds_report xray --config X [--model F] [--devices N]` — the
        # post-GSPMD compiled-fleet view (collective schedules, actual
        # shardings, donation aliases, static comm bytes); the full tool
        # is `ds_doctor xray`
        from deepspeed_tpu.analysis.cli import xray_cli

        return xray_cli(args[1:])
    if args and args[0] == "incident":
        # `ds_report incident <bundle_or_telemetry_dir>...` — the merged
        # cross-rank incident timeline with first-cause attribution; the
        # full tool is `bin/ds_incident`, which also runs jax-free
        from deepspeed_tpu.blackbox.incident import main as incident_main

        rest = args[1:]
        if not rest or rest[0].startswith("-") or os.path.exists(rest[0]):
            rest = ["report"] + rest
        return incident_main(rest)
    if args and args[0] == "roofline":
        # `ds_report roofline report --hlo DUMP | --config X` — the
        # analytic roofline (per-region FLOPs/bytes, MFU ceilings); the
        # full tool is `bin/ds_roofline`, which also runs jax-free
        from deepspeed_tpu.analysis.roofline import roofline_cli

        rest = args[1:]
        if not rest or rest[0].startswith("-"):
            rest = ["report"] + rest
        return roofline_cli(rest)
    line = "-" * 72
    print(line)
    print("deepspeed_tpu environment report")
    print(line)
    print("software:")
    for k, v in software_report():
        print(f"  {k:<24} {v}")
    print(line)
    print("hardware:")
    for k, v in hardware_report():
        print(f"  {k:<24} {v}")
    print(line)
    print("profiling:")
    for k, v in profiling_report():
        print(f"  {k:<24} {v}")
    print(line)
    print("overlap (latency-hiding scheduler preset):")
    for k, v in overlap_report():
        print(f"  {k:<44} {v}")
    print(line)
    print("kernels/toolchain:")
    for k, v in kernel_report():
        print(f"  {k:<24} {v}")
    print(line)
    print(f"python: {sys.version.split()[0]}  XLA_FLAGS: {os.environ.get('XLA_FLAGS', '')!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
