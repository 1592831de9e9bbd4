"""Per-layer metrics of KDA layers (``models/kda.py``, ``ops/pallas/kda.py``
of the program) and of what a hybrid model's sequences keep beside their
rows a position: the chunked prefill kernel's share of its roofline, and
the bytes of state a sequence holds whatever its length.

The kernel carries the name the program gave it (``kda_chunk_fwd`` inside
``jit_prefill``); the counts are the family's (``kda_prefill_flops``,
``kda_prefill_bytes``) at each TRACED prefill's own prompt length, never an
expectation, so the share cannot pass 100%. The state's size is read from
the ``request`` spans' closing args (``state_bytes``) the serving front-end
leaves in the program's tracer. Every reader returns None — and the harness
leaves the metric out — where the program has no such kernel or arg (the
commit before they were added, a family without the functions) or there is
no device plane.
"""

from benchmark import program_spans, readers


def prefill_roofline(ctx, p):
    """The least time the chip could take for the state pass of the traced
    prefills (the larger of the family's FLOPs at the bf16 peak and bytes at
    the HBM rate, at each one's own prompt length, averaged) over the device
    self time a prefill the ``match`` kernels took."""
    per_step = program_spans.kernel_seconds_per_step(ctx, p)
    fns = [readers._family_fn(ctx, n)
           for n in ("kda_prefill_flops", "kda_prefill_bytes")]
    if per_step is None or ctx.peaks is None or None in fns:
        return None
    lo, hi = ctx.trace_host_window
    prompts = [s[3]["context"] for s in ctx.rec.named("tick", lo, hi)
               if s[3].get("phase") == "prefill"]
    if not prompts:
        return None
    least = [readers.roofline(fns[0](ctx.config, t), fns[1](ctx.config, t),
                              ctx.peaks) for t in prompts]
    ctx.notes["kda_prefill_prompt_mean"] = sum(prompts) / len(prompts)
    ctx.notes["kda_prefill_s"] = per_step
    ctx.notes["kda_prefill_roofline_bound"] = least[0][1]
    return 100.0 * sum(t for t, _ in least) / len(least) / per_step


def state_bytes_per_sequence(ctx, p):
    """Bytes of state ONE sequence keeps whatever its length, as the
    program's own ``request`` spans of the traced window report them: the
    mean of ``state_bytes`` over the requests that prefilled."""
    spans = program_spans.window_spans(ctx)
    if spans is None:
        return None
    held = [s.args["state_bytes"]
            for s in program_spans._select(spans, "request", "serving")
            if s.args.get("cache_positions")
            and s.args.get("state_bytes") is not None]
    if not held:
        return None
    program_spans._count(ctx, "request~state", len(held))
    return sum(held) / len(held)
