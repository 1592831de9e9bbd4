"""Per-layer metrics of the train step's device time under the PROGRAM's own
names: ``deepspeed_tpu.telemetry.scopes`` puts a scope (``attn``, ``mlp``,
``head``, ``optimizer``, ``layers`` ...) into the ``op_name`` of every HLO
instruction the models and the engine trace, and the program's door
(``deepspeed_tpu.sharding.jit.ProgramRecord``) lowers the step program again
after the window and says which instruction carries which ``op_name``
(``instruction_scopes()``) and what the program holds in HBM (``memory()``).

A device profile names an op by its whole instruction text WITHOUT metadata
(``%fusion.66 = bf16[1600]{..} fusion(..), kind=kLoop, ...``), so the reader
joins ``ctx.trace["op_text_seconds"]`` (instruction text -> device SELF
seconds, averaged over the chips) to that table by the instruction's name,
and ``scopes.classify`` gives each op a scope and a pass (``fwd``, ``bwd``,
``recompute``, ``none``). All times are per execution of the step program
(``step_match``), as ``train.flash_fwd_s_per_step``.

The step program is the door's record whose function the ``step_match``
module of the trace is the jit of (``jit_step_fn`` <- ``step_fn``). The
re-lower happens once a run, in the first reader that asks, after the
window, in traced runs only; what it cost is in the notes.

Every reader returns None — and the harness leaves the metric out — where
there is no device plane (a rehearsal on the CPU), where the door has no
such method (the commit before it was added) and where the record cannot be
lowered (collected, never dispatched, refused by the compiler).
"""

import collections
import re
import time

from benchmark import program_spans, readers, trace_reduce

# what each metric sums: the program's vocabulary (telemetry/scopes.py)
GROUPS = {"attn": ("attn", "kda"), "mlp": ("mlp", "moe"),
          "head": ("embed", "head"), "optimizer": ("optimizer", "accumulate"),
          "layer_scan": ("layers",)}
UNSCOPED = "unscoped"


def step_record(ctx, step_match):
    """The door's record of the traced step program, or None."""
    from deepspeed_tpu.sharding import jit as door

    if not hasattr(door.ProgramRecord, "instruction_scopes"):
        return None
    rx = re.compile(step_match)
    modules = [m for m in ctx.trace["modules"] if rx.search(m)]
    for record in door.program_table().values():
        name = getattr(record.jitted, "__name__", None)
        if name and any(re.match(rf"jit_{re.escape(name)}\b", m)
                        for m in modules):
            return record
    return None


def by_scope(op_text_seconds, table, classify):
    """-> ({(scope, pass): seconds} with scope "" for an op of no scope,
    {op key: seconds} of those, the seconds whose instruction ``table``
    knows). ``table``: {instruction name: op_name}."""
    seconds = collections.Counter()
    unscoped = collections.Counter()
    matched = 0.0
    for text, sec in op_text_seconds.items():
        name = trace_reduce.instruction_name(text)
        op_name = table.get(name)
        if op_name is not None:
            matched += sec
        scope, pass_ = classify(op_name or "", name)
        seconds[scope, pass_] += sec
        if not scope:
            unscoped[trace_reduce.op_key(text)] += sec
    return seconds, unscoped, matched


def _table(ctx, p):
    """The run's by-scope table, built once: {"per_step": {(scope, pass):
    seconds a step}, "unscoped_s", "busy_s" (both a step), "memory"}, or
    None (the module's docstring says when). Writes the notes."""
    if hasattr(ctx, "_scope_table"):
        return ctx._scope_table
    ctx._scope_table = None
    if not program_spans._on_device(ctx):
        return None
    n_steps = len(readers._module_durations(ctx, p["step_match"]))
    record = step_record(ctx, p["step_match"])
    if record is None or not n_steps:
        return None
    from deepspeed_tpu.telemetry import scopes

    t0 = time.monotonic()
    try:
        table, memory = record.instruction_scopes(), record.memory()
    except Exception as e:      # the compiler refused the re-lower
        ctx.notes["scope_relower_error"] = f"{type(e).__name__}: {e}"[:300]
        return None
    ctx.notes["scope_relower_s"] = time.monotonic() - t0
    if table is None:
        return None
    seconds, unscoped, matched = by_scope(ctx.trace["op_text_seconds"], table,
                                          scopes.classify)
    total = sum(seconds.values())
    per_step = {k: v / n_steps for k, v in seconds.items()}
    unscoped_s = sum(v for (s, _), v in per_step.items() if not s)
    busy_s = ctx.trace["busy_s"] / n_steps
    nested = collections.defaultdict(dict)
    for (scope, pass_), v in sorted(per_step.items()):
        nested[scope or UNSCOPED][pass_] = round(1e3 * v, 4)
    ctx.notes["device_by_scope"] = dict(nested)       # ms a step
    ctx.notes["unscoped_top"] = [
        [k, round(1e3 * v / n_steps, 4)] for k, v in unscoped.most_common(5)]
    ctx.notes["scope_matched_share"] = 100.0 * matched / total if total else 0.0
    ctx.notes["scope_residual_s"] = busy_s - sum(per_step.values())
    ctx.notes["step_program_memory"] = memory
    ctx._scope_table = {"per_step": per_step, "unscoped_s": unscoped_s,
                        "busy_s": busy_s, "memory": memory}
    return ctx._scope_table


def scope_seconds_per_step(ctx, p):
    """Device self time a step of the ops whose innermost scope is one of
    ``GROUPS[p["group"]]`` (a finer name counts with its scope), every pass,
    kernels included."""
    t = _table(ctx, p)
    if t is None:
        return None
    return sum(v for (scope, _), v in t["per_step"].items()
               if scope.split("/")[0] in GROUPS[p["group"]])


def pass_seconds_per_step(ctx, p):
    """Device self time a step of the ops of pass ``p["pass"]``, whatever
    their scope (``recompute``: ``jax.checkpoint``'s re-run and XLA's own
    ``.remat`` instructions)."""
    t = _table(ctx, p)
    if t is None:
        return None
    return sum(v for (_, pass_), v in t["per_step"].items()
               if pass_ == p["pass"])


def unscoped_frac(ctx, p):
    """Busy time in ops of no scope (the compiler's own copies and waits,
    an instruction the table does not know) over the busy time: the by-scope
    metrics mean little if it is large."""
    t = _table(ctx, p)
    if t is None or not t["busy_s"]:
        return None
    return 100.0 * t["unscoped_s"] / t["busy_s"]


def step_hbm_frac(ctx, p):
    """What the step program holds on a chip as its compiler counts it
    (arguments + outputs - aliased + temporaries + code) over the chip's
    HBM: the figure ``train.hbm_peak_frac`` leaves the temporaries out of."""
    t = _table(ctx, p)
    if t is None or not t["memory"] or ctx.peaks is None:
        return None
    return 100.0 * t["memory"]["total"] / ctx.peaks["hbm_bytes"]
