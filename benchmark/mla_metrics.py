"""Per-layer metrics of latent attention (MLA) and of a chip's share of the
routed experts (``models/llama.py``, ``models/common.py``,
``ops/pallas/decode_attention.py`` and ``moe/dropless.py`` of the program):
the two attention kernels' shares of their rooflines, what a cached position
costs, and how much of the router's work falls on the experts held here.

The kernels carry the names the program gave them (``flash_fwd`` inside
``jit_prefill``: q.k at 192 columns, v at 128; ``latent_decode_attn`` inside
``jit_decode_chunk``); the counts are the family's
(``mla_prefill_attn_flops``, ``mla_decode_attn_flops``,
``mla_decode_attn_bytes``) at the lengths the traced ticks REALLY had, never
an expectation, so no share can pass 100%. The cache's cost is read from the
``request`` spans' closing args (``cache_bytes``, ``cache_positions``) and
the held share from the ``moe/expert_tokens`` instants (``counts`` of the
held experts, ``routed_pairs`` of all) the serving front-end leaves in the
program's tracer. Every reader returns None — and the harness leaves the
metric out — where the program has no such kernel, arg or instant (the
commit before they were added, a family without the function) or there is
no device plane.
"""

from benchmark import program_spans, readers


def _ticks(ctx, phase):
    lo, hi = ctx.trace_host_window
    return [s for s in ctx.rec.named("tick", lo, hi)
            if s[3].get("phase") == phase]


def prefill_attn_roofline(ctx, p):
    """The attention FLOPs of the traced prefills (the family's, at each
    one's own prompt length, averaged) at the bf16 peak over the device
    self time a prefill the ``match`` kernel took."""
    per_step = program_spans.kernel_seconds_per_step(ctx, p)
    flops = readers._family_fn(ctx, "mla_prefill_attn_flops")
    if per_step is None or ctx.peaks is None or flops is None:
        return None
    ticks = _ticks(ctx, "prefill")
    if not ticks:
        return None
    prompts = [s[3]["context"] for s in ticks]
    ctx.notes["mla_prefill_prompt_mean"] = sum(prompts) / len(prompts)
    ctx.notes["mla_prefill_attn_s"] = per_step
    least = sum(flops(ctx.config, t) for t in prompts) / len(prompts) \
        / ctx.peaks["bf16_flops_per_s"]
    return 100.0 * least / per_step


def decode_attn_roofline(ctx, p):
    """The least time the chip could take for ONE token's absorbed attention
    at the mean traced context (the larger of the latent rows' bytes at the
    HBM rate and the FLOPs at the bf16 peak: both are linear in the
    context) over the device self time a token the ``match`` kernel took."""
    per_step = program_spans.kernel_seconds_per_step(ctx, p)
    fns = [readers._family_fn(ctx, n)
           for n in ("mla_decode_attn_flops", "mla_decode_attn_bytes")]
    if per_step is None or ctx.peaks is None or None in fns:
        return None
    ticks = _ticks(ctx, "decode")
    if not ticks:
        return None
    # tokens a full tick decodes, as the stream callbacks counted them; a
    # tick that starts at context c decodes tokens at c .. c + tick - 1
    tokens = max(n for q in ctx.record["requests"] for _, n in q["stamps"])
    context = sum(s[3]["context"] for s in ticks) / len(ticks) \
        + (tokens - 1) / 2
    least, bound = readers.roofline(fns[0](ctx.config, context),
                                    fns[1](ctx.config, context), ctx.peaks)
    ctx.notes["mla_decode_attn_roofline_bound"] = bound
    ctx.notes["mla_decode_context_mean"] = context
    ctx.notes["mla_decode_attn_s_per_token"] = per_step / tokens
    return 100.0 * least / (per_step / tokens)


def cache_bytes_per_position(ctx, p):
    """Bytes of cache a position holds, all layers, as the program's own
    ``request`` spans of the traced window report them: the sum of
    ``cache_bytes`` over the sum of ``cache_positions``."""
    spans = program_spans.window_spans(ctx)
    if spans is None:
        return None
    held = [(s.args["cache_bytes"], s.args["cache_positions"])
            for s in program_spans._select(spans, "request", "serving")
            if s.args.get("cache_positions")
            and s.args.get("cache_bytes") is not None]
    if not held:
        return None
    program_spans._count(ctx, "request~cache", len(held))
    return sum(b for b, _ in held) / sum(n for _, n in held)


def held_pair_share(ctx, p):
    """Of all the (token, expert) pairs the router made in the requests that
    resolved in the window, the share that fell on experts held here and
    was computed, in percent (100 x held / router width if routing were
    even). The notes carry the share the counts are of."""
    tracer = program_spans._live_tracer()
    if not hasattr(tracer, "snapshot"):
        return None
    lo, hi = ctx.record["t_start"], ctx.record["t_end"]
    here = made = n = 0
    for s in tracer.snapshot():
        if s.name != "moe/expert_tokens" or not lo <= s.t0 <= hi \
                or not s.args.get("routed_pairs"):
            continue
        here += sum(sum(row) for row in s.args["counts"])
        made += s.args["routed_pairs"]
        ctx.notes["moe_held"] = [s.args.get("held_first"), s.args.get("held")]
        n += 1
    if not n:
        return None
    ctx.notes.setdefault("samples", {})["moe/expert_tokens~share"] = n
    return 100.0 * here / made
