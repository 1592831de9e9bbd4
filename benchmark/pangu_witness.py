#!/usr/bin/env python3
"""Outside any window, on the chip: what ``correct`` of an
``openpangu-ultra-moe`` serve cell rests on (PERF.md, PR 31).

    python3 benchmark/pangu_witness.py --config openpangu-ultra-moe-718b \
        --seeds 1,2,3 [--tokens 256] [--controls]

A bf16 program and the float32 reference may resolve a NEAR-TIE of the
router (a token's 8th and 9th logits closer than bf16 activations can tell
apart) differently, both validly, and one such choice moves that token's
logits by several times the arithmetic's own error. The family's
``reference_logits`` therefore holds a served token to the best of the
near-ties' resolutions (``families/pangu_ultra_moe.py``). This script shows,
a seed a line (also appended to ``chiprun_out/pangu_witness.jsonl``):

* ``check``: ``systems.ServeSystem.check`` as the cell's set-up runs it, and
  ``plain_shortfall``: the same statistic against the PLAIN pass alone;
* the witness: ``--tokens`` positions teacher-forced through the program's
  ``prefill`` (64) + absorbed ``decode_step``s. The program's own choice
  among the experts held here, a token a layer, is read off its
  ``expert_tokens`` counter (the difference from one step to the next).
  LOGITS against the plain pass (``gap_plain``: the largest difference of a
  position), against the reference evaluated ON THE PROGRAM'S CHOICES
  (``gap_on_choices``) and against the nearest of the resolutions
  ``reference_logits`` tries (``gap_matched``). Were the large gaps anything
  but the router's choice, the program's choices would not bring them down
  to what the arithmetic leaves everywhere else. ``flips``: every (token,
  layer, held expert) the two put on different sides, with the distance of
  that expert's logit from the cut in the plain pass (in the row's standard
  deviations; ``TIE`` is the most ``reference_logits`` calls a near-tie: a
  larger one at a LATER layer of the same position follows from the earlier
  flip, which moved that layer's input);
* with ``--controls``, for the FIRST seed: the same ``check`` of a program
  that computes with a leaf changed (the reference is given the true ones):
  no rotary key, no shared expert, a post-norm gain doubled, the attention
  and shared-expert weights rounded to float8 (the nearest type below the
  served one), with how far each moves the teacher-forced logits. Each but
  the doubled gain has to come out NOT ok (``CONTROLS`` says why).

Exit code 1 where a check that has to pass fails, or a control passes.
"""

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np                            # noqa: E402

from benchmark import manifest as mf          # noqa: E402

PROMPT = 64
ATTENTION = ("q_a_w", "q_b_w", "kv_a_w", "kv_b_k_w", "kv_b_v_w", "o_w")
SHARED = ("shared_gate_w", "shared_up_w", "shared_down_w")


def nearest_resolution(got, ways):
    """got (N, V) the program's logits, ways (R, N, V) the reference's under
    each resolution -> (largest difference of a position against the plain
    pass (N,), against the nearest resolution (N,), which that is (N,))."""
    gaps = np.abs(ways - got[None]).max(axis=-1)            # (R, N)
    return gaps[0], gaps.min(axis=0), gaps.argmin(axis=0)


def broken_stacks(params, control, kv_rank):
    """The parameter tree with a leaf of every stack changed."""
    import jax
    import jax.numpy as jnp

    def change(blocks):
        out = dict(blocks)
        if control == "no rotary key":
            out["kv_a_w"] = blocks["kv_a_w"].at[..., kv_rank:].set(0)
        elif control == "no shared expert" and "shared_down_w" in blocks:
            out["shared_down_w"] = jnp.zeros_like(blocks["shared_down_w"])
        elif control == "post-norm gain doubled":
            out["post_attn_norm_g"] = 2 * blocks["post_attn_norm_g"]
        elif control == "float8 attention and shared expert":
            for name in ATTENTION + SHARED:
                if name in blocks:
                    out[name] = blocks[name].astype(jnp.float8_e4m3fn).astype(
                        blocks[name].dtype)
        return {n: v if v is blocks[n] else jax.device_put(
            v, blocks[n].sharding) for n, v in out.items()}

    return {**params, **{stack: change(params[stack])
                         for stack in ("blocks", "dense_blocks")
                         if stack in params}}


# a control -> does ``ServeSystem.check`` have to refuse it. A doubled post-norm
# gain moves every logit by twice the spread and was refused at two seeds of
# three (shortfall 2.9, 3.5); at the third (seed 31) the served continuation
# is ONE token 32 times over and keeps its place: the check's statistic is of
# the logits' order at the 32 served positions. Reported, not required
CONTROLS = {"no rotary key": True, "no shared expert": True,
            "post-norm gain doubled": False,
            "float8 attention and shared expert": True}


def check_with(system, seed, params):
    """``ServeSystem.check`` itself, of a program that serves with ``params``
    while the reference is given the engine's true ones."""
    true, submit = system.engine.params, system.submit

    def served_with_params(prompt, new_tokens, stream):
        system.engine.params = params
        try:
            req = submit(prompt, new_tokens, stream)
            req.result(timeout=1200.0)
        finally:
            system.engine.params = true
        return req

    system.submit = served_with_params
    try:
        return system.check(seed)
    finally:
        system.submit = submit


def served_logits(model, params, seq):
    """The program's logits of positions ``PROMPT - 1 .. len(seq) - 2``
    (prefill, then absorbed decode steps, teacher-forced) and, for the
    decoded positions, its ``expert_tokens`` counter after each step."""
    import jax
    import jax.numpy as jnp

    cache = model.init_cache(1, 1024)
    first, cache = model.prefill(params, seq[None, :PROMPT], cache)

    def step(cache, token):
        logits, cache = model.decode_step(params, token[None], cache)
        return cache, (logits[0], cache["expert_tokens"])

    _, (rest, counts) = jax.lax.scan(step, cache, seq[PROMPT:-1])
    counts = jnp.concatenate([cache["expert_tokens"][None], counts])
    return jnp.concatenate([first, rest]), counts[1:] - counts[:-1]


def main(argv=None, manifest=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="openpangu-ultra-moe-718b")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--tokens", type=int, default=256)
    ap.add_argument("--controls", action="store_true")
    a = ap.parse_args(argv)
    cfg = mf.load_json(mf.config_path(manifest or mf.load_manifest(),
                                     a.config))

    import functools
    import gc

    import jax
    import jax.numpy as jnp

    from benchmark import systems

    out_dir = ROOT / "chiprun_out"
    os.makedirs(out_dir, exist_ok=True)
    bad = 0

    def report(row):
        print("WITNESS " + json.dumps(row), flush=True)
        with open(out_dir / "pangu_witness.jsonl", "a") as f:
            f.write(json.dumps(row) + "\n")

    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        system = systems.ServeSystem(cfg, {}, seed, 1)
        engine, model, family = system.engine, system.engine.module, \
            system.family
        first_held = model.config.experts_held[0]
        ok, detail = system.check(seed)
        bad += not ok
        # the same statistic against the plain pass alone (greedy: the same
        # request gives the same tokens)
        ids = np.random.default_rng([seed, 17]).integers(
            0, system.vocab, size=systems.CHECK_PROMPT, dtype=np.int32)
        req = system.submit(ids, systems.CHECK_NEW, None)
        req.result(timeout=1200.0)
        toks = np.asarray(req.tokens, dtype=np.int32)
        full = np.concatenate([ids, toks])
        seq = np.random.default_rng([seed, 19]).integers(
            0, system.vocab, size=PROMPT + a.tokens, dtype=np.int32)
        forward = jax.jit(functools.partial(family.reference_forward, cfg=cfg))
        serve = jax.jit(functools.partial(served_logits, model))
        with engine.mesh:
            rows = np.asarray(forward(engine.params, full)[0])[
                systems.CHECK_PROMPT - 1:-1]
            got, took = (np.asarray(x) for x in serve(engine.params, seq))
            plain, routers = forward(engine.params, seq)
            # the program's choice among the held experts, where it is
            # known: the decoded positions (steps, routed layers, held)
            chosen = np.asarray(routers["chosen"])          # (L, T, k)
            own = (chosen[..., None] == first_held + np.arange(
                took.shape[-1])).any(axis=2).astype(np.int8)
            theirs = own.copy()
            theirs[:, PROMPT:-1] = took.transpose(1, 0, 2)
            on_choices = np.asarray(forward(
                engine.params, seq, held=jnp.asarray(theirs))[0])
            ways = np.asarray(jax.jit(functools.partial(
                family.resolution_logits, cfg=cfg, last=a.tokens + 1))(
                    engine.params, seq))[:, :-1]
        plain = np.asarray(plain)[PROMPT - 1:-1]
        on_choices = on_choices[PROMPT - 1:-1]
        distance = np.asarray(routers["distance"])          # (L, T, held)
        plain_short = rows.max(axis=-1) - rows[np.arange(len(toks)), toks]
        gap_plain, gap_matched, _ = nearest_resolution(got, ways)
        # what long_check.py judges: how far the program's best token lies
        # under the best, in the plain pass and in its best resolution
        best = got.argmax(axis=-1)
        under = ways.max(axis=-1) - ways[:, np.arange(len(best)), best]
        gap_on_choices = np.abs(got - on_choices).max(axis=-1)
        at = lambda gap, t: round(float(gap[t - PROMPT + 1]), 3)
        flips = [{"position": int(t), "layer": int(l),
                  "expert": first_held + int(e),
                  "distance": round(float(distance[l, t, e]), 4),
                  "gap_plain": at(gap_plain, t),
                  "gap_on_choices": at(gap_on_choices, t),
                  "gap_matched": at(gap_matched, t)}
                 for l, t, e in zip(*np.nonzero(own != theirs))]
        report({
            "seed": seed, "check_ok": ok, **detail,
            "plain_shortfall": float(plain_short.max()),
            "plain_ok": bool(plain_short.max() <= systems.SERVE_LOGIT_MARGIN),
            "check_tokens_distinct": int(len(set(toks.tolist()))),
            "positions": int(len(got)),
            "resolutions": int(len(ways)),
            "reference_logit_spread": float(plain.std()),
            "gap_plain_max": float(gap_plain.max()),
            "gap_plain_median": float(np.median(gap_plain)),
            "gap_on_choices_max": float(gap_on_choices.max()),
            "gap_on_choices_median": float(np.median(gap_on_choices)),
            "gap_matched_max": float(gap_matched.max()),
            "gap_matched_median": float(np.median(gap_matched)),
            "best_token_shortfall_plain": float(under[0].max()),
            "best_token_shortfall_resolved": float(under.min(axis=0).max()),
            "tie": family.TIE, "flips": flips,
            "flips_beyond_tie": sum(f["distance"] > family.TIE
                                    for f in flips),
            "open_share_of_token_layers": float(
                (distance <= family.TIE).any(axis=-1).mean()),
            "peak_gb": (jax.devices()[0].memory_stats() or {}).get(
                "peak_bytes_in_use", 0) / 1e9})
        if a.controls and n == 0:
            for control in CONTROLS:
                broken = broken_stacks(engine.params, control,
                                       cfg["model"]["kv_lora_rank"])
                c_ok, c_detail = check_with(system, seed, broken)
                with engine.mesh:
                    logits = np.asarray(serve(broken, seq)[0])
                del broken
                moved = np.abs(logits - plain).max(axis=-1)
                centred = lambda x: x - x.mean(axis=-1, keepdims=True)
                u, v = centred(plain), centred(logits)
                must_fail = CONTROLS[control]
                bad += bool(c_ok) and must_fail
                report({"seed": seed, "control": control, "check_ok": c_ok,
                        "has_to_fail": must_fail, **c_detail,
                        "gap_plain_max": float(moved.max()),
                        "gap_plain_median": float(np.median(moved)),
                        "row_correlation_median": float(np.median(
                            (u * v).sum(-1) / np.sqrt((u * u).sum(-1)
                                                      * (v * v).sum(-1)))),
                        "row_slope_median": float(np.median(
                            (u * v).sum(-1) / (u * u).sum(-1)))})
        system.close()
        del system, engine, model, forward, serve, routers
        gc.collect()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
