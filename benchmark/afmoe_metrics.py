"""Per-layer metrics of what ``trinity-mini.train.z1.s8k`` added to the train
step (``ops/pallas/flash_attention.py``'s windowed kernels, ``moe/dropless
.py``'s grouped products in their training form): each kernel family's share
of its roofline and the routed experts' device time a step. The held share
and the load's evenness are ``benchmark/mla_metrics.py::held_pair_share`` and
``benchmark/moe_metrics.py::load_max_over_mean`` as they are: a train step
leaves the serving front-end's ``moe/expert_tokens`` instant.

The windowed kernels carry the names the program gave them (``flash_fwd_win``
/ ``flash_bwd_dq_win`` / ``flash_bwd_dkv_win``); the grouped products the
name XLA:TPU gives its own Mosaic kernels (``ragged-dot-none``). The counts
are the family's: the BANDED attention of the window layers at the traced
length, and the grouped products of the pairs the step COUNTED (the
instants' ``counts``), never an expectation, so no share can pass 100%.
Every reader returns None — and the harness leaves the metric out — where
the program has no such kernel or instant (the commit before they were
added, a family without the function) or there is no device plane.
"""

from benchmark import program_spans, readers, scope_metrics


def _sequences_a_step(ctx):
    eng = ctx.traffic["engine"]
    return eng["micro_batch_per_chip"] * eng["gradient_accumulation_steps"]


def win_flash_roofline(ctx, p):
    """The least time the chip could take for the window layers' attention
    of one step (forward 2 matmuls and backward 5 over the band; q, k, v, o
    and their gradients once) over the ``*_win`` calls' device time a step."""
    per_step = program_spans.kernel_seconds_per_step(ctx, p)
    fns = [readers._family_fn(ctx, n) for n in
           ("win_flash_flops_per_sequence", "win_flash_bytes_per_sequence")]
    if per_step is None or ctx.peaks is None or None in fns:
        return None
    seqs, T = _sequences_a_step(ctx), ctx.traffic["seq_len"]
    least, bound = readers.roofline(seqs * fns[0](ctx.config, T),
                                    seqs * fns[1](ctx.config, T), ctx.peaks)
    ctx.notes["win_flash_roofline_bound"] = bound
    ctx.notes["win_flash_s_per_step"] = per_step
    return 100.0 * least / per_step


def _held_pairs_a_step(ctx):
    """Mean over the window's ``moe/expert_tokens`` instants of the pairs
    the held experts computed in a step (all routed layers)."""
    tracer = program_spans._live_tracer()
    if not hasattr(tracer, "snapshot"):
        return None
    lo, hi = ctx.record["t_start"], ctx.record["t_end"]
    pairs = [sum(sum(row) for row in s.args["counts"])
             for s in tracer.snapshot()
             if s.name == "moe/expert_tokens" and lo <= s.t0 <= hi]
    return sum(pairs) / len(pairs) if pairs else None


def moe_gmm_roofline(ctx, p):
    """The least time the chip could take for the grouped products of one
    step — the larger of the COUNTED held pairs' FLOPs (forward, the two
    backward products each, what remat re-runs) and the held experts' bytes
    — over the ``ragged-dot`` calls' device time a step."""
    per_step = program_spans.kernel_seconds_per_step(ctx, p)
    fns = [readers._family_fn(ctx, n) for n in
           ("moe_gmm_flops_per_pair", "moe_gmm_bytes_per_step")]
    pairs = _held_pairs_a_step(ctx)
    if per_step is None or ctx.peaks is None or None in fns or not pairs:
        return None
    remat = bool(ctx.config.get("train", {}).get("remat"))
    least, bound = readers.roofline(pairs * fns[0](ctx.config, remat),
                                    fns[1](ctx.config, remat), ctx.peaks)
    ctx.notes["moe_gmm_train_roofline_bound"] = bound
    ctx.notes["moe_gmm_s_per_step"] = per_step
    ctx.notes["moe_held_pairs_per_step"] = pairs
    return 100.0 * least / per_step


def scope_seconds_per_step(ctx, p):
    """Device self time a step of the ops whose innermost scope is exactly
    ``p["scope"]`` (a finer name with its scope: ``moe/experts``), every
    pass; None where no op carries it."""
    t = scope_metrics._table(ctx, p)
    if t is None:
        return None
    return sum(v for (scope, _), v in t["per_step"].items()
               if scope == p["scope"]) or None
