"""The block step's per-layer metrics counted by BLOCKS: tokens a forward
pass, a pass's device time and ``decode_attn``'s share of its roofline, for
a program whose blocks are committed by no pass of their own.

``benchmark/sdar_metrics.py`` reads a ``request`` span's ``commits`` as its
number of blocks, which held while every block ended in a pass that only
commits. Since the commit rides in the next block's first pass
(``inference/engine.py``, ``models/llama.py::block_step(pending=)``) a
served request closes with ``commits`` 0 and ``carried`` = ``blocks`` - 1,
and those readers find nothing. These read ``blocks``, which the span has
carried since it had block counts at all, so they read BOTH programs: three
passes a block where every block has its commit pass, two where it is
carried. A carrying pass's attention is ``2 x block_length`` rows over the
same K/V: the bytes of a pass, the FLOPs of two (``carried`` says how many
passes carry; a span without it carries none).

Every reader returns None where the program has no such arg or kernel, the
family no such function, or there is no device plane.
"""

from benchmark import program_spans, readers, stats
from benchmark.sdar_metrics import _decode_context


def _block_requests(ctx, lo, hi):
    """The program's ``request`` spans inside (lo, hi) that count blocks."""
    tracer = program_spans._live_tracer()
    if not hasattr(tracer, "snapshot"):
        return []
    return [s.args for s in tracer.snapshot()
            if s.name == "request" and s.cat == "serving" and s.t1 is not None
            and lo <= s.t1 <= hi and s.args.get("passes") is not None
            and s.args.get("blocks")]


def _forward_passes(reqs):
    """Forward passes of either kind: those that denoise (a carrying pass is
    one of them) and those that only commit."""
    return sum(a["passes"] + a.get("commits", 0) for a in reqs)


def tokens_per_pass(ctx, p):
    """Tokens delivered over forward passes run, of the requests that
    resolved in the window: 4 / 3 where a block of 4 takes 2 denoising
    passes and a commit pass, 2 where the commit is carried."""
    reqs = _block_requests(ctx, ctx.record["t_start"], ctx.record["t_end"])
    if not reqs:
        return None
    ctx.notes.setdefault("samples", {})["request~blocks"] = len(reqs)
    ctx.notes["carried_per_block"] = \
        sum(a.get("carried", 0) for a in reqs) / sum(a["blocks"] for a in reqs)
    return sum(a["new_tokens"] for a in reqs) / _forward_passes(reqs)


def _passes_by_program(ctx):
    """-> (forward passes a decode chunk runs, forward passes a prefill's
    first block runs, the share of all passes that carry a block, the
    block's length) from the traced window's requests; or None. The first
    block of a request is the prefill tick's, the others its decode
    ticks'."""
    if not program_spans._on_device(ctx):
        return None
    reqs = [a for a in _block_requests(ctx, *ctx.trace_host_window)
            if a.get("decode_ticks")]
    if not reqs:
        return None
    blocks = sum(a["blocks"] for a in reqs)
    per_block = _forward_passes(reqs) / blocks
    tick_blocks = (blocks - len(reqs)) / sum(a["decode_ticks"] for a in reqs)
    carrying = sum(a.get("carried", 0) for a in reqs) / _forward_passes(reqs)
    return (per_block * tick_blocks, per_block, carrying,
            reqs[0]["block_length"])


def pass_device(ctx, p):
    """Device time of a decode chunk's executions in the traced window, the
    median, over the forward passes a chunk runs (the mean pass: one in two
    carries a block where the commit is carried)."""
    per = _passes_by_program(ctx)
    ds = readers._module_durations(ctx, p["match"])
    if per is None or not ds:
        return None
    ctx.notes["forward_passes_per_decode_chunk"] = per[0]
    return stats.percentile(ds, 50) / per[0]


def decode_attn_roofline(ctx, p):
    """The least a pass's attention could take at the mean traced context,
    over the device self time a pass of the ``match`` kernel (all its calls
    in the window, of either shape, over all the passes the window's
    programs ran). The least: the K/V bytes of the context at the HBM rate
    (the family's ``block_attn_bytes``: read once for all of a pass's rows,
    carried or not) or the pass's FLOPs at the bf16 peak, whichever is
    more; a carrying pass has the FLOPs of its own block and of the carried
    one, which sees a block's length fewer slots."""
    per = _passes_by_program(ctx)
    fns = [readers._family_fn(ctx, n)
           for n in ("block_attn_flops", "block_attn_bytes")]
    if per is None or ctx.peaks is None or None in fns:
        return None
    chunk_s = program_spans.kernel_seconds_per_step(ctx, p)
    context = _decode_context(ctx, per[3])
    if chunk_s is None or context is None:
        return None
    chunks = len(readers._module_durations(ctx, p["step_match"]))
    prefills = len(readers._module_durations(ctx, p["first_match"]))
    per_pass = chunk_s * chunks / (chunks * per[0] + prefills * per[1])
    flops, nbytes = (f(ctx.config, context) for f in fns)
    alone, bound = readers.roofline(flops, nbytes, ctx.peaks)
    carrying, _ = readers.roofline(
        flops + fns[0](ctx.config, context - per[3]), nbytes, ctx.peaks)
    least = (1 - per[2]) * alone + per[2] * carrying
    ctx.notes["block_decode_attn_roofline_bound"] = bound
    ctx.notes["block_decode_attn_context_mean"] = context
    ctx.notes["block_decode_attn_s_per_pass"] = per_pass
    ctx.notes["block_decode_attn_carrying_share"] = per[2]
    return 100.0 * least / per_pass
