"""Per-layer metrics of what ``kimi-linear-48b-a3b.train.z1.s16k`` added to
the train step (``ops/pallas/kda.py``'s state pass with a backward of its
own, ``flash_fwd`` / ``flash_bwd_*`` with v narrower than q.k): the KDA
mixers' device time a step under the program's own scopes, and each kernel
family's share of its roofline. The routed experts' five are
``benchmark/afmoe_metrics.py``'s as they are.

The kernels carry the names the program gave them (``kda_chunk_fwd`` /
``kda_chunk_bwd``; ``flash_fwd`` / ``flash_bwd_dq`` / ``flash_bwd_dkv``
WITHOUT ``_win``). The counts are the family's, at the traced length: what
the state pass must do with the forward's states at hand, and the causal
half of latent attention at its own widths, so no share can pass 100% by
construction. Every reader returns None — and the harness leaves the metric
out — where the program has no such kernel or scope (the commit before they
were added, a family without the function) or there is no device plane.
"""

from benchmark import program_spans, readers, scope_metrics
from benchmark.afmoe_metrics import _sequences_a_step


def _kernel_roofline(ctx, p, note, flops, nbytes, **of):
    """The least time the chip could take for the family's ``flops`` and
    ``nbytes`` of one step (the larger of the two bounds) over the device
    time a step of the kernels ``p["match"]`` names."""
    per_step = program_spans.kernel_seconds_per_step(ctx, p)
    fns = [readers._family_fn(ctx, name) for name in (flops, nbytes)]
    if per_step is None or ctx.peaks is None or None in fns:
        return None
    seqs, T = _sequences_a_step(ctx), ctx.traffic["seq_len"]
    least, bound = readers.roofline(
        *(seqs * fn(ctx.config, T, **of) for fn in fns), ctx.peaks)
    ctx.notes[f"{note}_roofline_bound"] = bound
    ctx.notes[f"{note}_s_per_step"] = per_step
    return 100.0 * least / per_step


def kda_chunk_roofline(ctx, p):
    """``kda_chunk_fwd`` (``p["backward"]`` false) or ``kda_chunk_bwd``: the
    state pass's matmuls at the bf16 peak or its operands and outputs at the
    HBM's rate, whichever is larger, over the kernel's time a step."""
    back = bool(p["backward"])
    return _kernel_roofline(ctx, p, "kda_chunk_bwd" if back else
                            "kda_chunk_fwd", "kda_train_flops",
                            "kda_train_bytes", backward=back)


def mla_flash_roofline(ctx, p):
    """The latent-attention layers' flash kernels, forward and backward:
    the causal half's FLOPs at q.k 192 / v 128 (the backward kernels run v
    widened to 192: the program's cost) over their time a step."""
    return _kernel_roofline(ctx, p, "mla_flash", "mla_train_attn_flops",
                            "mla_train_attn_bytes")


def kda_seconds_per_step(ctx, p):
    """Device self time a step of every op scoped ``kda`` (``kda/qkv``,
    ``kda/core``, ``kda/out``), the kernels included; by pass in the notes.
    None where no op carries the scope."""
    t = scope_metrics._table(ctx, p)
    if t is None:
        return None
    by_pass = {}
    for (scope, pass_), v in t["per_step"].items():
        if scope.split("/")[0] == "kda":
            by_pass[pass_] = by_pass.get(pass_, 0.0) + v
    ctx.notes["kda_ms_per_step_by_pass"] = {
        k: round(1e3 * v, 4) for k, v in sorted(by_pass.items())}
    return sum(by_pass.values()) or None
