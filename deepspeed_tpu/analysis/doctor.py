"""ds_doctor orchestration: run passes, collect one report, honor fail_on.

Three entry points share this module:

* :func:`engine_init_analysis` / :func:`engine_graph_analysis` — the
  engine hooks behind the ``analysis`` ds_config block. Init runs the
  schema + sharding passes (param shapes and the plan exist before any
  state is materialized); the graph + collective passes run at the
  FIRST ``train_batch`` (the batch shape is only known then) on an
  abstract re-trace of the exact step function the engine compiles —
  a trace, never a compile, so the cost is seconds of host time.
* :func:`run_doctor` — the ``bin/ds_doctor`` CLI / ``ds_report doctor``
  path: no engine required; family fixtures (gpt2 / llama / moe / bert)
  or a user-supplied graph builder provide the train graph.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple, Union

from deepspeed_tpu.analysis.findings import AnalysisReport, Finding

ALL_PASSES = ("schema", "sharding", "graph", "collectives", "race",
              "selflint", "xray")
# what "no --passes given" expands to: every TRACE-ONLY pass. xray is
# deliberately absent — it AOT-compiles programs (XLA, not a trace), so it
# runs only when named explicitly (same opt-in contract as the engine's).
DEFAULT_PASSES = ("schema", "sharding", "graph", "collectives", "race",
                  "selflint")
# what the engine runs by default (selflint is a CI concern, not a job's;
# xray costs one AOT compile per program — explicit opt-in only. race IS
# here: it is AST-over-package host work like the unspecified-jit lint,
# seconds once per process, and a lock-order cycle is exactly the defect
# you want before step 0, not after the fleet wedges)
ENGINE_PASSES = ("schema", "sharding", "graph", "collectives", "race")


def _wants(acfg, name: str) -> bool:
    passes = list(getattr(acfg, "passes", []) or [])
    return name in (passes or ENGINE_PASSES)


def _finish(report: AnalysisReport, fail_on: str, log=None) -> AnalysisReport:
    report.count_into_registry()
    if log is not None and report.findings:
        log(report.render())
    report.raise_if(fail_on)
    return report


# --------------------------------------------------------------- engine hooks
def engine_init_analysis(engine, param_shapes) -> AnalysisReport:
    """Schema + sharding passes at engine init (before state
    materialization). Raises :class:`AnalysisError` per ``fail_on``."""
    from deepspeed_tpu.analysis.graph_lint import lint_sharding_plan
    from deepspeed_tpu.analysis.schema import walk_config
    from deepspeed_tpu.utils.logging import log_dist

    acfg = engine._config.analysis
    report = AnalysisReport()
    if _wants(acfg, "schema"):
        findings, _ = walk_config(engine._config._param_dict,
                                  world_size=engine.dp_world_size)
        report.extend(findings, "schema")
    if _wants(acfg, "sharding"):
        from deepspeed_tpu.analysis.jit_lint import lint_unspecified_jit

        report.extend(
            lint_sharding_plan(engine.plan, param_shapes,
                               min_elements=acfg.min_replicated_elements),
            "sharding")
        # the unspecified-jit lint: no engine program may enter jax.jit
        # outside sharded_jit (AST over the package, memoized per process).
        # Package only here: the repo-script scan (bin/*) is a CI concern —
        # a job vendoring this package next to its own bin/ must not die
        # at engine init over scripts that never run
        report.extend(lint_unspecified_jit(include_scripts=False),
                      "sharding")
    if _wants(acfg, "race"):
        from deepspeed_tpu.analysis.race import lint_race

        # same package-only scope as the jit lint (scripts are CI's
        # problem), same memoized once-per-process cost
        report.extend(lint_race(include_scripts=False,
                                allowlist=tuple(acfg.race_allowlist)),
                      "race")
        if acfg.race_witness:
            from deepspeed_tpu.utils import locks as _locks

            _locks.enable_witness()
    return _finish(report, acfg.fail_on,
                   log=lambda m: log_dist(m, ranks=[0]))


def engine_graph_analysis(engine, batch, gas: int) -> AnalysisReport:
    """Graph + collective passes on an abstract re-trace of the step the
    engine is about to compile, at the first ``train_batch``."""
    import jax

    from deepspeed_tpu.analysis.collectives import (record_collectives,
                                                    verify_collective_consistency)
    from deepspeed_tpu.analysis.graph_lint import lint_jaxpr
    from deepspeed_tpu.utils.logging import log_dist

    acfg = engine._config.analysis
    report = AnalysisReport()
    if engine._onebit or engine._nvme_optimizer is not None:
        # these engines execute a different program than the standard step
        # builder (shard_map-local 1-bit loop / host-side NVMe optimizer);
        # re-tracing the standard builder would lint a graph that never runs
        report.add(Finding(
            rule="graph/pass-skipped", severity="info",
            message=("graph/collective passes skipped: 1-bit and NVMe-offload"
                     " engines compile a specialized step program the "
                     "abstract re-trace does not model"),
            pass_name="graph"))
        return _finish(report, acfg.fail_on)
    want_graph = _wants(acfg, "graph")
    want_coll = _wants(acfg, "collectives") and acfg.record_collectives
    if not (want_graph or want_coll):
        return _finish(report, acfg.fail_on)

    def _abs_leaf(x):
        if isinstance(x, (bool, int, float, complex)):
            # a bare Python scalar in the batch IS the weak-input hazard —
            # hand the lint the weak 0-d aval it would trace as
            import jax.numpy as jnp

            return jax.ShapeDtypeStruct((), jnp.result_type(x),
                                        weak_type=True)
        # weak_type must survive abstraction or the weak-scalar rule can
        # never fire on the engine path
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    weak_type=getattr(x, "weak_type", False))

    abstract = lambda tree: jax.tree.map(_abs_leaf, tree)
    state_abs, batch_abs = abstract(engine.state), abstract(batch)
    fn = engine._build_train_batch_fn(gas)
    with engine.mesh:
        if want_coll:
            with record_collectives() as rec:
                closed = jax.make_jaxpr(fn)(state_abs, batch_abs)
        else:
            rec = None
            closed = jax.make_jaxpr(fn)(state_abs, batch_abs)
    if want_graph:
        # no donation lint here: the engine owns its donation contract and
        # already donates the state tree (donate_argnums=(0,)); the
        # graph/missing-donation rule targets user-built steps (ds_doctor
        # --graph / run_doctor(donate_argnums=...))
        report.extend(
            lint_jaxpr(closed, train_dtype=engine.train_dtype,
                       min_promote_elements=acfg.min_promote_elements),
            "graph")
    if rec is not None:
        engine._collective_fingerprint = rec.fingerprint()
        report.extend(verify_collective_consistency(rec), "collectives")
    return _finish(report, acfg.fail_on,
                   log=lambda m: log_dist(m, ranks=[0]))


def _compiled_donation_lint(fn, args, donate_argnums, min_bytes: int):
    """The donation story from the COMPILED alias table of the user step
    (the ``graph/missing-donation`` rebase): AOT lower+compile the graph
    with its declared donation, then read what the executable actually
    aliases — a large arg never donated is flagged as missing-donation
    with compiled byte counts, and a donated arg whose buffers produced
    no alias is flagged as ``xray/donation-dropped``. Returns None when
    the compile (or the parameter mapping) is not possible, and the
    caller falls back to the jaxpr heuristic — one defect is one
    finding either way."""
    import jax

    from deepspeed_tpu.analysis.graph_lint import RULE_DONATION
    from deepspeed_tpu.analysis.hlo_model import parse_hlo_module
    from deepspeed_tpu.analysis.xray import RULE_DONATION_DROPPED

    donated = set(donate_argnums)
    try:
        jitted = jax.jit(fn, donate_argnums=tuple(donated))
        model = parse_hlo_module(jitted.lower(*args).compile().as_text())
    except Exception:
        return None
    ranges = []
    n = 0
    for arg in args:
        leaves = len(jax.tree.leaves(arg))
        ranges.append((n, n + leaves))
        n += leaves
    if len(model.parameter_bytes) != n:
        return None     # parameter mapping disagrees — don't guess
    aliased = model.aliased_parameters()
    findings = []
    for i, (lo, hi) in enumerate(ranges):
        nbytes = sum(model.parameter_bytes[lo:hi])
        if i in donated:
            dropped = sum(model.parameter_bytes[j] for j in range(lo, hi)
                          if j not in aliased)
            if dropped >= min_bytes:
                findings.append(Finding(
                    rule=RULE_DONATION_DROPPED, severity="warning",
                    message=(f"train step donates argument {i} but "
                             f"{dropped / 2**20:.0f} MiB of it produced no "
                             "input-output alias in the compiled executable "
                             "— the donation silently dropped (usually a "
                             "dtype/layout change between the donated input "
                             "and every output); old and new stay live "
                             "together"),
                    citation=f"arg[{i}]", pass_name="xray"))
        elif nbytes >= min_bytes:
            findings.append(Finding(
                rule=RULE_DONATION, severity="warning",
                message=(f"train step argument {i} ({nbytes / 2**20:.0f} MiB "
                         "in the compiled executable) is not donated — XLA "
                         "keeps the old tree alive next to the new one, "
                         f"doubling its peak HBM; add donate_argnums=({i},) "
                         "if the caller never reuses it"),
                citation=f"arg[{i}]", pass_name="xray"))
    return findings


# ----------------------------------------------------------------- CLI driver
def _family_tiny(name: str) -> str:
    aliases = {"gpt2": "gpt2-tiny", "llama": "llama-tiny",
               "moe": "gpt2-moe-tiny", "gpt2-moe": "gpt2-moe-tiny",
               "bert": "bert-tiny"}
    return aliases.get(name, name)


def build_family_graph(config, family: str, batch_size: int = 2,
                       seq_len: int = 16) -> Tuple[Callable, tuple]:
    """(fn, args) for the forward+backward graph of a registry model
    family under the config's compute dtype — what the CLI graph pass
    traces when no custom ``--graph`` builder is given."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.registry import resolve_family

    preset = _family_tiny(family)
    model_cls, make_batch, presets = resolve_family(preset)
    if preset not in presets:
        preset = min(presets, key=lambda k: presets[k].num_params()
                     if hasattr(presets[k], "num_params") else 1 << 60)
    mcfg = presets[preset]
    model = model_cls(mcfg)
    seq_len = min(seq_len, mcfg.n_positions)
    batch = make_batch(batch_size, seq_len, mcfg.vocab_size)
    key = jax.random.PRNGKey(0)
    param_shapes = jax.eval_shape(model.init_params, key)
    dtype = config.train_dtype
    to_dtype = lambda s: jax.ShapeDtypeStruct(
        s.shape, dtype if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype)
    params_abs = jax.tree.map(to_dtype, param_shapes)

    def fwd_bwd(params, b):
        def loss_of(p):
            try:
                out = model.loss(p, b, key)
            except TypeError:
                out = model.loss(p, b)
            return out[0] if isinstance(out, tuple) else out

        return jax.value_and_grad(loss_of)(params)

    return fwd_bwd, (params_abs, batch)


def run_doctor(config: Any,
               *,
               passes: Optional[Sequence[str]] = None,
               fail_on: str = "error",
               model: Optional[str] = None,
               graph: Union[Tuple[Callable, tuple], Callable, None] = None,
               donate_argnums: Optional[Sequence[int]] = None,
               collective_logs: Optional[Sequence[str]] = None,
               world_size: Optional[int] = None,
               batch_size: int = 2, seq_len: int = 16,
               raise_on_fail: bool = False) -> AnalysisReport:
    """Run the requested passes over a ds_config (dict or path) without an
    engine. Returns the report; raises only when ``raise_on_fail``.

    ``graph`` is either a prebuilt ``(fn, args)`` pair or a callable
    ``builder(cfg) -> (fn, args[, donate_argnums])`` invoked with the
    parsed config (the CLI's ``--graph`` path — parsing happens once,
    here). The donation lint runs only when ``donate_argnums`` is given
    (or the builder returns one): the built-in family fixtures have
    nothing the caller could donate, so flagging them would be an
    unfixable false positive.

    A pass the caller EXPLICITLY requested that cannot run (missing
    --model/--graph/--collective-log, or a config that failed the schema
    pass) is reported as an info ``<pass>/pass-skipped`` finding instead
    of silently looking like a clean result; with the default pass set,
    inapplicable passes are simply not run (the report header lists what
    ran)."""
    import json as _json

    explicit = passes is not None
    passes = tuple(passes or DEFAULT_PASSES)
    report = AnalysisReport()

    def skipped(pass_name: str, why: str) -> None:
        if explicit and pass_name in passes:
            report.extend([Finding(rule=f"{pass_name}/pass-skipped",
                                   severity="info",
                                   message=f"{pass_name} pass skipped: {why}",
                                   pass_name=pass_name)], pass_name)

    if isinstance(config, str):
        with open(config) as f:
            config = _json.load(f)

    cfg = None
    schema_findings = []
    if any(p in passes for p in ("schema", "sharding", "graph", "race")):
        from deepspeed_tpu.analysis.schema import walk_config

        schema_findings, cfg = walk_config(config, world_size=world_size)
        if "schema" in passes:
            report.extend(schema_findings, "schema")

    def _schema_why() -> str:
        """Skip reason for a broken config — carries the first schema
        error even when the schema pass itself was not requested (a green
        exit with no actionable detail would hide the breakage)."""
        first = next((f.message for f in schema_findings
                      if f.severity == "error"), "")
        return ("the config failed the schema pass"
                + (f" ({first})" if first and "schema" not in passes else ""))

    if "sharding" in passes:
        from deepspeed_tpu.analysis.jit_lint import (lint_program_table,
                                                     lint_unspecified_jit)

        # the unspecified-jit lint needs no model: AST over the package +
        # the runtime program table (whatever compiled this process)
        report.extend(lint_unspecified_jit(), "sharding")
        report.extend(lint_program_table(), "sharding")
        if cfg is not None and model is not None:
            report.extend(_sharding_for_family(cfg, model), "sharding")
        elif model is not None and cfg is None:
            skipped("sharding", _schema_why())
        else:
            # the jit lints ran above; the family sharding-PLAN sub-pass
            # (replicated-leaf lint against the mesh) still needs a fixture
            skipped("sharding",
                    "the sharding-plan lint needs --model (a family fixture "
                    "to plan sharding for); the unspecified-jit lint ran")

    if "graph" in passes:
        if cfg is not None and (model or graph):
            import jax

            from deepspeed_tpu.analysis.graph_lint import (lint_donation,
                                                           lint_jaxpr)

            if graph is None:
                fn, args = build_family_graph(cfg, model,
                                              batch_size=batch_size,
                                              seq_len=seq_len)
            elif callable(graph):
                out = graph(cfg)
                fn, args = out[0], out[1]
                if len(out) > 2:
                    donate_argnums = out[2]
            else:
                fn, args = graph
            closed = jax.make_jaxpr(fn)(*args)
            report.extend(
                lint_jaxpr(closed, train_dtype=cfg.train_dtype,
                           min_promote_elements=cfg.analysis.min_promote_elements),
                "graph")
            if donate_argnums is not None:
                # donation story, one defect = one finding: with the xray
                # pass also requested, the COMPILED alias table is the
                # source of truth (graph/missing-donation rebased on what
                # the executable actually aliases + xray/donation-dropped
                # for declared-but-dropped); the jaxpr heuristic stays the
                # no-compile fallback
                compiled_findings = None
                if "xray" in passes:
                    compiled_findings = _compiled_donation_lint(
                        fn, args, donate_argnums,
                        min_bytes=cfg.analysis.min_donate_bytes)
                if compiled_findings is not None:
                    report.extend(compiled_findings, "xray")
                else:
                    report.extend(
                        lint_donation(args, donate_argnums,
                                      min_bytes=cfg.analysis.min_donate_bytes),
                        "graph")
        else:
            skipped("graph", _schema_why() if cfg is None else
                    "needs --model or --graph (something to trace)")

    if "xray" in passes:
        from deepspeed_tpu.sharding import program_table

        records = [r for r in program_table().values() if r.can_lower()]
        if records:
            from deepspeed_tpu.analysis.xray import run_xray

            kw = {}
            if cfg is not None:
                # honor the SAME thresholds the trace passes honor — a
                # raised min_replicated_elements/min_donate_bytes must
                # silence the xray variants of those findings too
                kw = dict(
                    min_replicated_elements=cfg.analysis.min_replicated_elements,
                    min_donate_bytes=cfg.analysis.min_donate_bytes)
            result = run_xray(records, **kw)
            report.extend(result.findings, "xray")
            report.xray = result       # CLI renders the comm table from this
        else:
            skipped("xray",
                    "the process-global program table holds no dispatched "
                    "programs — run an engine step first (bin/ds_doctor "
                    "xray builds one from --model and does this for you)")

    if "collectives" in passes:
        if collective_logs and len(collective_logs) < 2:
            # passing a log at all states intent — report the skip even
            # with the default pass set, or one mis-captured rank would
            # render as a clean diff
            report.extend([Finding(
                rule="collectives/pass-skipped", severity="info",
                message=("one --collective-log is nothing to diff against — "
                         "record one sequence per rank (two or more)"),
                pass_name="collectives")], "collectives")
        elif collective_logs:
            from deepspeed_tpu.analysis.collectives import (CollectiveRecorder,
                                                            diff_sequences)

            seqs = {i: CollectiveRecorder.load(p)
                    for i, p in enumerate(collective_logs)}
            report.extend(diff_sequences(seqs), "collectives")
        else:
            skipped("collectives",
                    "needs --collective-log files (one per rank, two or "
                    "more) recorded via analysis.collectives")

    if "race" in passes:
        from deepspeed_tpu.analysis.race import lint_race

        allow = tuple(cfg.analysis.race_allowlist) if cfg is not None else ()
        report.extend(lint_race(allowlist=allow), "race")

    if "selflint" in passes:
        from deepspeed_tpu.analysis.selflint import lint_package

        report.extend(lint_package(), "selflint")

    report.count_into_registry()
    if raise_on_fail:
        report.raise_if(fail_on)
    return report


def _sharding_for_family(cfg, family: str):
    """Sharding-plan lint for a family fixture; needs the mesh the config
    asks for to actually exist (CPU test boxes fake 8 devices via
    XLA_FLAGS) — degrades to an info finding when it does not."""
    import jax

    from deepspeed_tpu.analysis.graph_lint import lint_sharding_plan
    from deepspeed_tpu.models.registry import resolve_family
    from deepspeed_tpu.parallel.topology import build_mesh
    from deepspeed_tpu.runtime.zero.partition import plan_sharding

    try:
        mesh = build_mesh(mesh_config=cfg.mesh_config)
    except ValueError as e:
        return [Finding(
            rule="sharding/pass-skipped", severity="info",
            message=(f"sharding pass skipped: the tpu mesh block needs "
                     f"devices this host does not have ({e})"),
            citation="tpu", pass_name="sharding")]
    preset = _family_tiny(family)
    model_cls, _, presets = resolve_family(preset)
    if preset not in presets:
        preset = sorted(presets)[0]
    model = model_cls(presets[preset])
    param_shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    tp_specs = model.param_partition_specs() if hasattr(
        model, "param_partition_specs") else None
    plan = plan_sharding(param_shapes, mesh, zero_config=cfg.zero_config,
                        tp_specs=tp_specs)
    return lint_sharding_plan(plan, param_shapes,
                              min_elements=cfg.analysis.min_replicated_elements)
