"""Per-layer metrics read from the PROGRAM's own spans and counters.

The program keeps its last spans in a ring (``deepspeed_tpu.telemetry
.get_tracer()``: name, category, ``t0``/``t1`` on ``time.monotonic()``, id,
parent id, ``trace`` = request id or step number, args) and counts at
``sharded_jit`` every call that made a program specialize
(``deepspeed_tpu.sharding.jit.door_events()``). The readers here keep what
lies inside the traced window (``ctx.trace_host_window``, the same clock),
rebuild the tree from the parent ids, and reduce it. With ``systems.py``
and a family's ``build_model`` this is the third place that imports
``deepspeed_tpu``.

Every reader returns None — and the harness leaves the metric out — where
there is no device plane (a rehearsal on the CPU), where the program has no
such recorder or counter (the commit before they were added), and where the
ring has wrapped past the traced window (``ctx.notes`` then says so).

Host seconds map to the trace's seconds through the harness's ``window``
annotation (``ctx.trace["annotations"]``), which starts where
``ctx.trace_host_window`` does; the two clocks agree only to a fraction of
a millisecond, so the mapping is used for PAIRING alone (a program
execution belongs to the ``tick_wait`` span that holds its midpoint) and
only durations are ever subtracted across them.
"""

import collections
import re
import types

from benchmark import readers, stats

WRAPPED = "the program's span ring wrapped past the traced window"


def _live_tracer():
    from deepspeed_tpu import telemetry

    return telemetry.get_tracer()


def _on_device(ctx):
    return ctx.trace is not None and bool(ctx.trace["n_devices"])


def window_spans(ctx):
    """The program's closed spans that lie wholly inside the traced window,
    oldest first, or None (see the module's docstring)."""
    if not _on_device(ctx):
        return None
    if not hasattr(ctx, "_program_spans"):
        ctx._program_spans = None
        tracer = _live_tracer()
        if hasattr(tracer, "snapshot"):
            spans = tracer.snapshot()
            lo, hi = ctx.trace_host_window
            if tracer.wrapped and (not spans or spans[0].t1 is None
                                   or spans[0].t1 > lo):
                ctx.notes["program_spans"] = WRAPPED
            else:
                ctx._program_spans = [s for s in spans if s.t1 is not None
                                      and s.t0 >= lo and s.t1 <= hi]
    return ctx._program_spans


def _select(spans, name, cat, under=None):
    """Spans called ``name`` in category ``cat``; with ``under``, only those
    whose parent span is called that."""
    by_id = {s.id: s for s in spans}
    out = []
    for s in spans:
        if s.name != name or s.cat != cat:
            continue
        if under is not None:
            parent = by_id.get(s.parent)
            if parent is None or parent.name != under:
                continue
        out.append(s)
    return out


def _count(ctx, key, n):
    ctx.notes.setdefault("samples", {})[key] = n


def _duration_percentile(spans, q, name, cat="serving", under=None):
    return stats.percentile(
        [s.t1 - s.t0 for s in _select(spans, name, cat, under)], q)


def span_percentile(ctx, p):
    """Percentile ``q`` of the durations of the spans ``span`` (category
    ``cat``), with ``under`` only of those directly under a span of that
    name."""
    spans = window_spans(ctx)
    if spans is None:
        return None
    _count(ctx, "/".join(filter(None, (p.get("under"), p["span"]))),
           len(_select(spans, p["span"], p["cat"], p.get("under"))))
    return _duration_percentile(spans, p["q"], p["span"], p["cat"],
                                p.get("under"))


# ---------------------------------------------------------------- serving
def _caller_values(ctx, field):
    """``ttft`` / ``tpot`` as the callers saw them, by the end-to-end
    metrics' own formulas (``readers._request_values``), over the requests
    that completed inside the TRACED window."""
    lo, hi = ctx.trace_host_window
    inside = types.SimpleNamespace(
        record={**ctx.record, "t_start": lo, "t_end": hi})
    return readers._request_values(inside, field)


def first_chunk(ctx, p):
    """``first_tokens_at - prefill_done_at`` of the requests whose
    ``request`` span lies inside the traced window: what a request waits,
    after its prefill, for the first chunk's tokens to be on the host."""
    spans = window_spans(ctx)
    if spans is None:
        return None
    gaps = [s.args["first_tokens_at"] - s.args["prefill_done_at"]
            for s in _select(spans, "request", "serving")
            if s.args.get("first_tokens_at") is not None
            and s.args.get("prefill_done_at") is not None]
    _count(ctx, "request", len(gaps))
    first = stats.percentile(gaps, p["q"])
    # ttft as the caller saw it, less the parts the program stamps
    ttft = stats.percentile(_caller_values(ctx, "ttft"), p["q"])
    wait = _duration_percentile(spans, p["q"], "admission_wait")
    prefill = _duration_percentile(spans, p["q"], "prefill")
    if None not in (first, ttft, wait, prefill):
        ctx.notes["ttft_unaccounted_s"] = ttft - (wait + prefill + first)
    return first


def _wait_excess(ctx, spans, match):
    """[``tick_wait`` duration less the device time of the execution it
    waited for] over the decode ticks that have one."""
    window = [a for a in ctx.trace["annotations"] if a[0] == "window"]
    if not window:
        return []
    shift = window[0][1] - ctx.trace_host_window[0]     # host -> trace
    rx = re.compile(match)
    mods = sorted(((s + e) / 2, e - s)
                  for s, e, nm in ctx.trace["module_events"] if rx.search(nm))
    out, j = [], 0
    for w in sorted(_select(spans, "tick_wait", "serving", "decode"),
                    key=lambda s: s.t0):
        lo, hi = w.t0 + shift, w.t1 + shift
        while j < len(mods) and mods[j][0] < lo:
            j += 1
        if j < len(mods) and mods[j][0] <= hi:
            out.append((w.t1 - w.t0) - mods[j][1])
    return out


def tick_wait_excess(ctx, p):
    """What the runtime adds around a decode chunk: ``tick_wait``
    (``block_until_ready``) less the device time of the paired execution
    (programs matching ``match``). Also writes the two identities of a
    decode tick into the notes: ``tick_residual_s`` (a tick less its three
    children: ~0) and ``tick_unaccounted_s`` (the time per tick, as the
    callers' stream stamps give it, that no span and no device time
    covers)."""
    spans = window_spans(ctx)
    if spans is None:
        return None
    excess = _wait_excess(ctx, spans, p["match"])
    _count(ctx, "tick_wait~" + p["match"], len(excess))
    value = stats.percentile(excess, p["q"])

    children = collections.defaultdict(float)
    for s in spans:
        if s.name in ("tick_launch", "tick_wait", "tick_return"):
            children[s.parent] += s.t1 - s.t0
    ticks = _select(spans, "decode", "serving")
    residual = stats.percentile(
        [t.t1 - t.t0 - children[t.id] for t in ticks if t.id in children], 50)
    if residual is not None:
        ctx.notes["tick_residual_s"] = residual
    device = stats.percentile(readers._module_durations(ctx, p["match"]), 50)
    tpot = stats.percentile(_caller_values(ctx, "tpot"), 50)
    parts = [device, value] + [
        _duration_percentile(spans, 50, name, under=under)
        for name, under in (("tick_launch", "decode"),
                            ("tick_return", "decode"), ("deliver", None))]
    if tpot is not None and None not in parts:
        # tokens a full tick delivers, as the stream callbacks counted them
        tick_tokens = max(n for q in ctx.record["requests"]
                          for _, n in q["stamps"])
        ctx.notes["tick_unaccounted_s"] = tick_tokens * tpot - sum(parts)
    return value


# ------------------------------------------------------------ program door
def door_compiles(ctx, p):
    """Calls that made a program specialize inside the measured window,
    as ``sharded_jit`` counted them; must be 0. The notes name the labels,
    and those that went through during set-up."""
    if not _on_device(ctx):
        return None
    from deepspeed_tpu.sharding import jit as door

    if not hasattr(door, "door_events"):
        return None
    lo, hi = ctx.record["t_start"], ctx.record["t_end"]
    events = door.door_events()
    inside = [label for t, label, _ in events if lo <= t <= hi]
    ctx.notes["door"] = {
        "in_window": sorted(set(inside)),
        "during_setup": dict(collections.Counter(
            label for t, label, _ in events if t < lo))}
    return float(len(inside))


# ----------------------------------------------------------------- kernels
def kernel_seconds_per_step(ctx, p):
    """Device self time, per execution of the step program (``step_match``),
    of the ops whose HLO instruction NAME holds ``match``: the kernels carry
    the names the program gave them (``pallas_call(name=...)``), bare under
    remat (``%flash_fwd.16``) and wrapped where jax names the transform
    (``%jvp_flash_fwd_.1``, ``%transpose_jvp_flash_bwd_dq__.1``)."""
    if not _on_device(ctx):
        return None
    rx = re.compile(r"^%?[\w.\-]*(" + p["match"] + r")[\w.\-]* = ")
    seconds = sum(v for k, v in ctx.trace["op_text_seconds"].items()
                  if rx.match(k))
    n_steps = len(readers._module_durations(ctx, p["step_match"]))
    if seconds <= 0 or not n_steps:
        return None
    return seconds / n_steps
