"""Per-layer metrics of the routed-expert layer (``moe/dropless.py`` and
``ops/pallas/grouped_matmul.py`` of the program): the grouped matmul's share
of its roofline in decode and in prefill, and how evenly the router loads
the experts.

The kernels carry the names the program gave them (``moe_gmm_thin``,
``moe_gmm_swiglu_full``, ...: the row tile tells a decode step's call from a
prefill's); the counts are the family's (``moe_flops_per_token``,
``moe_bytes_decode``, ``moe_bytes_prefill``). The router's load is read from
the instants ``moe/expert_tokens`` the serving front-end leaves in the
program's tracer, one per resolved request. Every reader returns None — and
the harness leaves the metric out — where the program has no such kernel or
instant (the commit before they were added, a family without experts) or
there is no device plane.
"""

from benchmark import program_spans, readers


def gmm_roofline(ctx, p):
    """The least time the chip could take for the expert matmuls of one
    execution of the step program (``step_match``) over the device self time
    the ``match`` kernels took in it. ``phase`` ``decode``: per token, the
    chosen experts' weights; ``prefill``: per prompt, every expert's weights
    once or the FLOPs at the mean traced prompt length, whichever is more."""
    per_step = program_spans.kernel_seconds_per_step(ctx, p)
    fns = [readers._family_fn(ctx, n) for n in
           ("moe_flops_per_token", "moe_bytes_decode", "moe_bytes_prefill")]
    if per_step is None or ctx.peaks is None or None in fns:
        return None
    flops, bytes_decode, bytes_prefill = (f(ctx.config) for f in fns)
    lo, hi = ctx.trace_host_window
    ticks = [s for s in ctx.rec.named("tick", lo, hi)
             if s[3].get("phase") == p["phase"]]
    if not ticks:
        return None
    if p["phase"] == "decode":
        # tokens a full tick decodes, as the stream callbacks counted them
        tokens = max(n for q in ctx.record["requests"] for _, n in q["stamps"])
        least, bound = readers.roofline(flops, bytes_decode, ctx.peaks)
        per_step /= tokens
    else:
        prompt = sum(s[3]["context"] for s in ticks) / len(ticks)
        ctx.notes["moe_prefill_prompt_mean"] = prompt
        least, bound = readers.roofline(flops * prompt, bytes_prefill,
                                        ctx.peaks)
    ctx.notes[f"moe_gmm_{p['phase']}_roofline_bound"] = bound
    return 100.0 * least / per_step


def load_max_over_mean(ctx, p):
    """Pairs routed to the heaviest expert over the mean of its layer, in
    the worst layer, summed over the requests that resolved in the window."""
    tracer = program_spans._live_tracer()
    if not hasattr(tracer, "snapshot"):
        return None
    lo, hi = ctx.record["t_start"], ctx.record["t_end"]
    total = None
    n = 0
    for s in tracer.snapshot():
        if s.name != "moe/expert_tokens" or not lo <= s.t0 <= hi:
            continue
        counts = s.args["counts"]
        total = counts if total is None else \
            [[a + b for a, b in zip(ra, rb)] for ra, rb in zip(total, counts)]
        n += 1
    if not n:
        return None
    ctx.notes.setdefault("samples", {})["moe/expert_tokens"] = n
    return max(max(row) * len(row) / sum(row) for row in total if sum(row))
