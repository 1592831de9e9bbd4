"""Per-layer metrics of generation by diffusion over blocks (the block step
of ``inference/engine.py``, ``models/llama.py::block_step``, the multi-row
``decode_attn`` and the block-causal ``flash_fwd`` of the program): tokens a
forward pass, a pass's device time, and the two kernels' shares of their
rooflines.

What the program ran is read from ITS OWN counts: the ``request`` spans'
closing args ``passes`` / ``commits`` / ``blocks`` / ``decode_ticks`` (the
passes are summed inside the compiled programs and read back once a
request). The kernels carry the names the program gave them (``decode_attn``
inside ``jit_decode_chunk`` and, for the first block, ``jit_prefill``;
``flash_fwd`` inside ``jit_prefill``); the counts are the family's
(``block_attn_bytes``, ``block_flash_flops``) at the contexts and prompt
lengths the traced ticks REALLY had, so no share can pass 100%. Every reader
returns None — and the harness leaves the metric out — where the program
has no such arg or kernel (the commit before they were added, a model that
emits a token a step, a family without the function) or there is no device
plane.
"""

from benchmark import program_spans, readers, stats


def _block_requests(ctx, lo, hi):
    """The program's ``request`` spans inside (lo, hi) that carry a block
    step's counts."""
    tracer = program_spans._live_tracer()
    if not hasattr(tracer, "snapshot"):
        return []
    return [s.args for s in tracer.snapshot()
            if s.name == "request" and s.cat == "serving" and s.t1 is not None
            and lo <= s.t1 <= hi and s.args.get("passes") is not None
            and s.args.get("commits")]


def tokens_per_pass(ctx, p):
    """Tokens delivered over forward passes run (denoising passes and
    commits), of the requests that resolved in the window: 4 / 3 where a
    block of 4 takes 2 denoising passes and its commit and no tick runs a
    block nobody reads."""
    reqs = _block_requests(ctx, ctx.record["t_start"], ctx.record["t_end"])
    if not reqs:
        return None
    ctx.notes.setdefault("samples", {})["request~passes"] = len(reqs)
    ctx.notes["block_length"] = reqs[0].get("block_length")
    ctx.notes["denoising_steps"] = reqs[0].get("denoising_steps")
    return sum(a["new_tokens"] for a in reqs) \
        / sum(a["passes"] + a["commits"] for a in reqs)


def _passes_by_program(ctx):
    """-> (forward passes a decode chunk runs, forward passes a prefill's
    first block runs, the block's length) from the traced window's requests:
    passes a block x blocks a tick; or None."""
    if not program_spans._on_device(ctx):
        return None
    reqs = [a for a in _block_requests(ctx, *ctx.trace_host_window)
            if a.get("decode_ticks")]
    if not reqs:
        return None
    per_block = sum(a["passes"] + a["commits"] for a in reqs) \
        / sum(a["commits"] for a in reqs)
    tick_blocks = sum(a["commits"] - 1 for a in reqs) \
        / sum(a["decode_ticks"] for a in reqs)
    return per_block * tick_blocks, per_block, reqs[0]["block_length"]


def pass_device(ctx, p):
    """Device time of a decode chunk's executions in the traced window, the
    median, over the forward passes a chunk runs."""
    per = _passes_by_program(ctx)
    ds = readers._module_durations(ctx, p["match"])
    if per is None or not ds:
        return None
    ctx.notes["passes_per_decode_chunk"] = per[0]
    return stats.percentile(ds, 50) / per[0]


def _decode_context(ctx, block):
    """Mean slots valid when a block's rows are in, over the traced decode
    ticks: a tick that starts with c committed positions runs blocks at c,
    c + Lb, ..; each attends its own rows too."""
    lo, hi = ctx.trace_host_window
    ticks = [s for s in ctx.rec.named("tick", lo, hi)
             if s[3].get("phase") == "decode"]
    if not ticks:
        return None
    tokens = max(n for q in ctx.record["requests"] for _, n in q["stamps"])
    return sum(s[3]["context"] for s in ticks) / len(ticks) \
        + (tokens + block) / 2


def block_attn_roofline(ctx, p):
    """The K/V bytes ONE pass's attention must read at the mean traced
    context (the family's ``block_attn_bytes``) at the HBM rate — or its
    FLOPs at the bf16 peak, whichever is more — over the device self time a
    pass of the ``match`` kernel (all its calls in the window over all the
    passes the window's programs ran)."""
    per = _passes_by_program(ctx)
    fns = [readers._family_fn(ctx, n)
           for n in ("block_attn_flops", "block_attn_bytes")]
    if per is None or ctx.peaks is None or None in fns:
        return None
    chunk_s = program_spans.kernel_seconds_per_step(ctx, p)
    context = _decode_context(ctx, per[2])
    if chunk_s is None or context is None:
        return None
    chunks = len(readers._module_durations(ctx, p["step_match"]))
    prefills = len(readers._module_durations(ctx, p["first_match"]))
    per_pass = chunk_s * chunks / (chunks * per[0] + prefills * per[1])
    least, bound = readers.roofline(fns[0](ctx.config, context),
                                    fns[1](ctx.config, context), ctx.peaks)
    ctx.notes["block_attn_roofline_bound"] = bound
    ctx.notes["block_attn_context_mean"] = context
    ctx.notes["block_attn_s_per_pass"] = per_pass
    return 100.0 * least / per_pass


def block_flash_roofline(ctx, p):
    """The block-causal prefill's attention FLOPs (the family's
    ``block_flash_flops`` at each traced prompt, averaged; or its bytes at
    the HBM rate, whichever is more) over the device self time a prefill of
    the ``match`` kernel."""
    per_step = program_spans.kernel_seconds_per_step(ctx, p)
    fns = [readers._family_fn(ctx, n)
           for n in ("block_flash_flops", "block_flash_bytes")]
    if per_step is None or ctx.peaks is None or None in fns:
        return None
    lo, hi = ctx.trace_host_window
    prompts = [s[3]["context"] for s in ctx.rec.named("tick", lo, hi)
               if s[3].get("phase") == "prefill"]
    if not prompts:
        return None
    least = sum(readers.roofline(fns[0](ctx.config, t), fns[1](ctx.config, t),
                                 ctx.peaks)[0] for t in prompts) / len(prompts)
    ctx.notes["block_flash_prompt_mean"] = sum(prompts) / len(prompts)
    ctx.notes["block_flash_s_per_prefill"] = per_step
    return 100.0 * least / per_step
