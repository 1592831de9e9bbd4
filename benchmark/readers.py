"""The generic metric readers. A metric's data file (``end_to_end/<name>
.json`` or ``layer_metrics/<name>.json``) names one with ``reader`` and
gives it ``params``; a metric that needs code of its own puts a ``read(ctx,
params)`` in ``<name>.py`` beside its file instead. A reader that finds
nothing to read returns None and the harness leaves the metric out.

``ctx`` (built in ``run.execute``) holds: ``record`` (the driver's), ``rec`` (host
spans), ``config`` (the configuration file) and ``family`` (its module under
``benchmark/families/``), ``traffic``, ``chips``, ``peaks`` (this device's
row of ``peaks.json``), ``setup_s``, ``compiles`` (``CompileCounter``),
``memory_peak_bytes``, ``trace`` (the reduced device trace, or None with
``--trace 0``), ``trace_host_window`` (the traced window on the host clock),
``profiler_stop`` (None, or the (start, end) of the profiler's own stop, which
takes seconds to minutes) and ``notes`` (printed on the line before the
result: which bound a roofline took, sample counts).
"""

import re

from benchmark import stats


# ------------------------------------------------------------- end to end
def setup_s(ctx, p):
    return ctx.setup_s


def _intervals(ctx, of, skip=None):
    """-> (work, seconds) of every WHOLE interval of the window, in order.

    ``steps``: one interval per training step that ENDS inside the window,
    from the end of the step before it (the first from the window's start,
    which is the end of the last warm-up step) to its own end, so feeding
    the batch is inside it; work = its tokens. ``callbacks``: one interval
    per stream callback that fired inside the window after the first, from
    the callback before it; work = the tokens it delivered (the first
    callback opens the first interval and its own tokens are not counted).
    ``skip``: a (start, end) on the host clock; intervals that overlap it
    are left out."""
    r = ctx.record
    if of == "steps":
        steps = [s for s in r.get("steps", []) if s["t1"] <= r["t_end"]]
        ends = [r["t_start"]] + [s["t1"] for s in steps]
        work = [s["tokens"] for s in steps]
    elif of == "callbacks":
        stamps = sorted(s for q in r.get("requests", []) for s in q["stamps"]
                        if r["t_start"] <= s[0] <= r["t_end"])
        ends = [s[0] for s in stamps]
        work = [n for _, n in stamps[1:]]
    else:
        raise ValueError(f"readers: no intervals of {of!r}")
    both = [(w, b - a) for w, a, b in zip(work, ends, ends[1:])
            if skip is None or b <= skip[0] or a >= skip[1]]
    return [w for w, _ in both], [t for _, t in both]


def window_rate(ctx, p):
    """Work done in the window over the time it took: ALL the whole
    intervals (``_intervals``; ``of``: ``steps`` | ``callbacks``), none left
    out, so a stall anywhere in the window is in the quotient. The
    denominator runs to the end of the last whole interval, not to the
    nominal end of the window: "what ended before second 30" over exactly
    30 s would step by one part in N as the count of steps flips (10 % in
    a cell of 10 steps). ``per_chip``: divide by the cell's chips."""
    work, seconds = _intervals(ctx, p["of"])
    if not work:
        return None
    return sum(work) / sum(seconds) / (ctx.chips if p.get("per_chip") else 1)


GROUPS = 8


def grouped_median_rate(work, seconds):
    """The median, over up to eight equal runs of CONSECUTIVE intervals, of
    (work done in the run / time the run took). One isolated stall falls
    into one run and the median does not see it; a slowdown that recurs
    (every n-th step, every request) is in every run and shows."""
    n = len(work)
    groups = max(1, min(GROUPS, n // 2))
    k = n // groups
    return stats.percentile(
        [sum(work[g * k:(g + 1) * k]) / sum(seconds[g * k:(g + 1) * k])
         for g in range(groups)], 50)


def steady_rate(ctx, p):
    """A DIAGNOSTIC beside ``window_rate``, never judged: the grouped median
    over the window less the profiler's own stop (in a traced train run the
    step loop waits for it). Where it stands above the end-to-end rate, the
    window held a stall (``longest_interval`` says how long) and not a
    slower program."""
    work, seconds = _intervals(ctx, p["of"], skip=ctx.profiler_stop)
    if not work:
        return None
    return grouped_median_rate(work, seconds) \
        / (ctx.chips if p.get("per_chip") else 1)


def longest_interval(ctx, p):
    """The longest whole interval of the window, the profiler's own stop
    left out (a stall of seconds that is not the program's)."""
    _, seconds = _intervals(ctx, p["of"], skip=ctx.profiler_stop)
    return max(seconds) if seconds else None


def _completed_inside(ctx):
    from benchmark.drivers.closed_loop import request_ok

    r = ctx.record
    return [q for q in r.get("requests", [])
            if r["t_start"] <= q["t_done"] <= r["t_end"] and request_ok(q)]


def _request_values(ctx, field):
    reqs = _completed_inside(ctx)
    if field == "ttft":         # first stream callback - submit (or due)
        return [q["stamps"][0][0] - q["t_ref"] for q in reqs if q["stamps"]]
    if field == "tpot":         # needs two callbacks; see PERF.md section 2
        return [(q["stamps"][-1][0] - q["stamps"][0][0])
                / sum(n for _, n in q["stamps"][1:])
                for q in reqs if len(q["stamps"]) >= 2]
    if field == "queue_wait":
        return [q["queue_wait"] for q in reqs if q["queue_wait"] is not None]
    if field == "late":
        return [q["late_s"] for q in reqs if "late_s" in q]
    if field == "turnaround":   # completion -> that caller's next submit()
        by_caller = {}
        out = []
        r = ctx.record
        for q in r.get("requests", []):
            prev = by_caller.get(q["caller"])
            if prev is not None and r["t_start"] <= q["t_submit"] <= r["t_end"]:
                out.append(q["t_submit"] - prev["t_done"])
            by_caller[q["caller"]] = q
        return out
    raise ValueError(f"request_percentile: unknown field {field!r}")


def request_percentile(ctx, p):
    vals = _request_values(ctx, p["field"])
    ctx.notes.setdefault("samples", {})[p["field"]] = len(vals)
    return stats.percentile(vals, p["q"])


# -------------------------------------------------------------- host spans
def span_percentile(ctx, p):
    r = ctx.record
    spans = ctx.rec.named(p["span"], r["t_start"], r["t_end"])
    return stats.percentile([s[2] - s[1] for s in spans], p["q"])


def _family_fn(ctx, name):
    return getattr(getattr(ctx, "family", None), name, None)


def roofline(flops, nbytes, peaks):
    """-> (least seconds the chip could take, which bound sets it)."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def train_mfu(ctx, p):
    """The family's FLOPs per token x ``train_tok_s_chip`` over the peak:
    the end-to-end rate times a constant (it decides nothing)."""
    rate = window_rate(ctx, {"of": "steps", "per_chip": True})
    per_token = _family_fn(ctx, "train_flops_per_token")
    if rate is None or ctx.peaks is None or per_token is None:
        return None
    return 100.0 * per_token(ctx.config, ctx.traffic["seq_len"]) * rate \
        / ctx.peaks["bf16_flops_per_s"]


def compiles_in_window(ctx, p):
    return float(ctx.compiles.count(ctx.record["t_start"],
                                    ctx.record["t_end"]))


def hbm_peak_frac(ctx, p):
    if not ctx.memory_peak_bytes or ctx.peaks is None:
        return None
    return 100.0 * ctx.memory_peak_bytes / ctx.peaks["hbm_bytes"]


# ------------------------------------------------------------ device trace
def _module_durations(ctx, pattern):
    if ctx.trace is None:
        return []
    rx = re.compile(pattern)
    return [d for name, ds in ctx.trace["modules"].items()
            if rx.search(name) for d in ds]


def module_device_percentile(ctx, p):
    """Device time of one program's executions (``XLA Modules`` events whose
    name matches ``match``) inside the traced window."""
    ds = _module_durations(ctx, p["match"])
    ctx.notes.setdefault("samples", {})[p["match"]] = len(ds)
    return stats.percentile(ds, p["q"])


def tick_host_gap(ctx, p):
    """Host span of a tick less the device time of that tick: each
    ``bench/<span>`` annotation is paired with the program execution
    (matching ``match``) whose MIDPOINT lies inside it — the device's clock
    is aligned to the host's only to a fraction of a millisecond (the
    recorded v5e fixture shows a program start 0.1 ms before the host call
    that launched it), and a midpoint does not care."""
    if ctx.trace is None:
        return None
    rx = re.compile(p["match"])
    mods = [((s + e) / 2, e - s) for s, e, nm in ctx.trace["module_events"]
            if rx.search(nm)]
    gaps, j = [], 0
    for name, s, e in ctx.trace["annotations"]:
        if name != p["span"]:
            continue
        while j < len(mods) and mods[j][0] < s:
            j += 1
        if j < len(mods) and mods[j][0] <= e:
            gaps.append((e - s) - mods[j][1])
    return stats.percentile(gaps, p["q"])


def device_idle_frac(ctx, p):
    if ctx.trace is None or ctx.trace["idle_frac"] is None:
        return None
    return 100.0 * ctx.trace["idle_frac"]


def coll_exposed_frac(ctx, p):
    """Collective ops' time with no compute op running on that chip, as a
    share of the traced window."""
    if ctx.trace is None or not ctx.trace["window_s"]:
        return None
    ctx.notes["collective_inflight_frac"] = 100.0 * ctx.trace.get(
        "collective_inflight_s", 0.0) / ctx.trace["window_s"]
    return 100.0 * ctx.trace["collective_exposed_s"] / ctx.trace["window_s"]


def flash_roofline(ctx, p):
    """The flash kernels' share of their roofline: the least time the chip
    could take for the attention of one step (operations and bytes from
    shapes, the family's) over the Mosaic calls' device time per step."""
    ops = _family_fn(ctx, "flash_flops_per_sequence")
    nbytes = _family_fn(ctx, "flash_bytes_per_sequence")
    if ctx.trace is None or ctx.peaks is None or ops is None or nbytes is None:
        return None
    rx = re.compile(p["match"])
    kernel_s = sum(v for k, v in ctx.trace["op_text_seconds"].items()
                   if rx.search(k))
    n_steps = len(_module_durations(ctx, p["step_match"]))
    if kernel_s <= 0 or not n_steps:
        return None
    seqs = ctx.traffic["engine"]["micro_batch_per_chip"]
    T = ctx.traffic["seq_len"]
    least, bound = roofline(seqs * ops(ctx.config, T),
                            seqs * nbytes(ctx.config, T), ctx.peaks)
    ctx.notes["flash_roofline_bound"] = bound
    ctx.notes["flash_s_per_step"] = kernel_s / n_steps
    return 100.0 * least / (kernel_s / n_steps)


def decode_roofline(ctx, p):
    """Bytes one decode step must read (weights once + the K/V of the
    positions actually attended to) at the HBM peak, over the device time
    per token of the decode chunks in the traced window."""
    ds = _module_durations(ctx, p["match"])
    ops = _family_fn(ctx, "decode_flops_per_token")
    nbytes = _family_fn(ctx, "decode_bytes_per_token")
    if not ds or ctx.peaks is None or ops is None or nbytes is None:
        return None
    lo, hi = ctx.trace_host_window
    ticks = [s for s in ctx.rec.named("tick", lo, hi)
             if s[3].get("phase") == "decode"]
    if not ticks:
        return None
    # tokens a full tick delivers, as the stream callbacks counted them
    tick_tokens = max(n for q in ctx.record["requests"] for _, n in q["stamps"])
    # a tick that starts at context c decodes tokens at c .. c+tick-1
    ctx_mean = sum(s[3]["context"] for s in ticks) / len(ticks) \
        + (tick_tokens - 1) / 2
    least, bound = roofline(ops(ctx.config),
                            nbytes(ctx.config, ctx_mean), ctx.peaks)
    ctx.notes["decode_roofline_bound"] = bound
    ctx.notes["decode_context_mean"] = ctx_mean
    return 100.0 * least / (sum(ds) / len(ds) / tick_tokens)
