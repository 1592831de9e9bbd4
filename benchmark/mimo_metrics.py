"""Per-layer metrics of a model whose WINDOW layers keep a ring and whose
full layers keep the context (``models/llama.py``: ``win_blocks``, ``common
.init_kv_ring``; the decode kernel's two widths and sink; the windowed flash
forward with a sink): what the rings hold a sequence, and the three
attention kernels' shares of their rooflines.

What the program holds is read from ITS OWN ``request`` spans' closing args
(``window_bytes``: from the shapes of the cache the prefill handed back).
The kernels carry the names the program gave them (``flash_fwd_win`` and
``flash_fwd`` inside ``jit_prefill``, ``decode_attn`` inside
``jit_decode_chunk``); the counts are the family's (``win_flash_flops`` /
``win_flash_bytes``, ``full_flash_flops`` / ``full_flash_bytes``,
``decode_kv_bytes``) at the prompt lengths and contexts the traced ticks
REALLY had, so no share can pass 100%. Every reader returns None — and the
harness leaves the metric out — where the program has no such arg or kernel
(the commit before they were added, a model without rings, a family without
the function) or there is no device plane.
"""

from benchmark import program_spans, readers


def window_bytes_per_sequence(ctx, p):
    """From the program's ``request`` spans in the traced window: the mean
    of ``window_bytes``, what the window layers' rings hold a sequence
    whatever its length, over the requests that prefilled."""
    tracer = program_spans._live_tracer()
    if not hasattr(tracer, "snapshot"):
        return None
    lo, hi = ctx.record["t_start"], ctx.record["t_end"]
    held = [s.args["window_bytes"]
            for s in tracer.snapshot()
            if s.name == "request" and s.cat == "serving" and s.t1 is not None
            and lo <= s.t1 <= hi and s.args.get("cache_positions")
            and s.args.get("window_bytes") is not None]
    if not held:
        return None
    ctx.notes.setdefault("samples", {})["request~window"] = len(held)
    return sum(held) / len(held)


def _ticks(ctx, phase):
    lo, hi = ctx.trace_host_window
    return [s for s in ctx.rec.named("tick", lo, hi)
            if s[3].get("phase") == phase]


def flash_roofline(ctx, p):
    """The attention of the traced prefills that the ``match`` kernel runs
    (the family's ``flops`` / ``bytes`` functions at each one's own prompt
    length: the larger of the FLOPs at the bf16 peak and the operands' bytes
    at the HBM rate, averaged) over the kernel's device self time a
    prefill."""
    per_step = program_spans.kernel_seconds_per_step(ctx, p)
    fns = [readers._family_fn(ctx, p[n]) for n in ("flops", "bytes")]
    if per_step is None or ctx.peaks is None or None in fns:
        return None
    prompts = [s[3]["context"] for s in _ticks(ctx, "prefill")]
    if not prompts:
        return None
    least = sum(readers.roofline(fns[0](ctx.config, t), fns[1](ctx.config, t),
                                 ctx.peaks)[0] for t in prompts) / len(prompts)
    ctx.notes[f"{p['flops']}_prompt_mean"] = sum(prompts) / len(prompts)
    ctx.notes[f"{p['flops']}_s_per_prefill"] = per_step
    return 100.0 * least / per_step


def ring_decode_attn_roofline(ctx, p):
    """The K/V bytes ONE token's attention must read at the mean traced
    context (the family's ``decode_kv_bytes``: the full layers' rows of the
    context, the rings at ``min(context, window)`` slots) at the HBM rate
    over the device self time a token of the ``match`` kernel, both caches'
    calls together."""
    per_step = program_spans.kernel_seconds_per_step(ctx, p)
    nbytes = readers._family_fn(ctx, "decode_kv_bytes")
    if per_step is None or ctx.peaks is None or nbytes is None:
        return None
    ticks = _ticks(ctx, "decode")
    if not ticks:
        return None
    # tokens a full tick decodes, as the stream callbacks counted them; a
    # tick that starts at context c decodes tokens at c .. c + tick - 1
    tokens = max(n for q in ctx.record["requests"] for _, n in q["stamps"])
    context = sum(s[3]["context"] for s in ticks) / len(ticks) \
        + (tokens - 1) / 2
    ctx.notes["ring_decode_context_mean"] = context
    ctx.notes["ring_decode_attn_s_per_token"] = per_step / tokens
    return 100.0 * nbytes(ctx.config, context) \
        / ctx.peaks["hbm_bytes_per_s"] / (per_step / tokens)
