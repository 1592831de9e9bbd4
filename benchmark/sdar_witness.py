#!/usr/bin/env python3
"""Outside any window, on the chip: what ``correct`` of an ``sdar_moe`` serve
cell rests on (PERF.md, PR 45).

    python3 benchmark/sdar_witness.py --config sdar-30b-a3b-chat \
        --seeds 1,2 [--prompts 2048,4096] [--new 132] [--controls 1]

A bf16 program and the float32 reference may differ in two DISCRETE choices,
both validly: a held expert at the router's cut, and which positions a
denoising pass unmasks. ``systems.ServeSystem.check`` is handed neither; this
script is. For each seed and prompt length it runs the timed path's own
functions pass by pass (``module.prefill`` under the block-causal mask, then
``module.block_step`` and the engine's own ``_unmask``, block after block,
``--new`` tokens), keeping of EVERY denoising pass the program's logits, the
state it ran on (which positions were masked) and its own choice among the
experts held here, a row a layer (a wrapper over ``moe/dropless.py::
route_topk`` hands the chosen experts to the host). The reference then
computes the same passes in blocks: one float32 pass over the finished
sequence gives every layer's K/V, and each recorded pass is the block's rows
against the K/V before it.

A line a (seed, prompt) (also appended to ``chiprun_out/sdar_witness.jsonl``):
``gap_on_choices_max`` — the worst |program - reference| over every logit of
every denoising pass, the reference given the program's own unmask order AND
its own choice among the held experts; ``gap_plain_max`` — the same with the
reference's own router choices (what a flip costs); ``router_open_share`` —
(row, layer) pairs of the recorded passes with a held expert within ``TIE``
of the cut; ``router_flips`` — (row, layer, held expert) the two put on
different sides; ``position_open_share`` — first passes whose confidences
leave the set open (``POSITION_TIE``); ``position_flips`` — first passes in
which the program unmasked another set than the reference's own rule would
from the same state; ``conf_rel_err_max`` — how far the program's confidence
of a position lies from the reference's, relative.

With ``--controls 1``, for the FIRST seed and prompt: the same passes of a
program that computes with its mixers' weights (q, k, v, o of every layer)
rounded to float8, the nearest type below the served one, while the
reference is given the true ones: ``gap_on_choices_max`` has to come out
ABOVE ``TOLERANCE``; and ``check_shortfall``, what ``systems.ServeSystem
.check`` computes (64 tokens -> 32 through ``generate()``, held to
``reference_logits``), beside the sound program's.

Exit code 1 where a sound program's ``gap_on_choices_max`` passes
``TOLERANCE`` or a control's does not.
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

# the worst a logit of a denoising pass may differ, the reference on the
# program's own choices. Two readings on the chip (PERF.md section 6, PR 45;
# logits spread by 0.90): the sound bf16 program 0.039-0.062 over five
# (seed, prompt) runs of 66 passes; the same program with its mixers'
# weights rounded to float8, the nearest type below, 1.63. Between them,
# 2.4 x above the one and 11 x below the other
TOLERANCE = 0.15
MIXER = ("q_w", "k_w", "v_w", "o_w")


def record_router_choices(log):
    """``moe/dropless.py::route_topk`` with the chosen experts handed to
    ``log`` (a list) as the program runs, in the order it runs them: a layer
    a call inside the layer scan."""
    import jax

    from deepspeed_tpu.moe import dropless

    real = dropless.route_topk

    def recorded(tokens, router_w, k, renormalize, **kw):
        probs, weights, experts = real(tokens, router_w, k, renormalize, **kw)
        jax.debug.callback(lambda e: log.append(np.asarray(e)), experts,
                           ordered=True)
        return probs, weights, experts

    dropless.route_topk = recorded
    return lambda: setattr(dropless, "route_topk", real)


def float8_mixers(params):
    """The parameter tree with every layer's q, k, v, o rounded to float8
    (e4m3) and back: the other leaves are the tree's own buffers."""
    import jax.numpy as jnp

    blocks = dict(params["blocks"])
    for name in MIXER:
        blocks[name] = blocks[name].astype(jnp.float8_e4m3fn).astype(
            blocks[name].dtype)
    return {**params, "blocks": blocks}


def check_shortfall(cfg, family, model, served, true, seed):
    """``systems.ServeSystem.check``'s statistic without the front-end:
    ``generate()`` of a program that holds ``served``, held to the
    reference on ``true``."""
    import jax

    import deepspeed_tpu
    from benchmark import systems

    P, new = systems.CHECK_PROMPT, systems.CHECK_NEW
    ids = np.random.default_rng([int(seed), 17]).integers(
        0, family.vocab_size(cfg), size=P, dtype=np.int32)
    engine = deepspeed_tpu.init_inference(
        model, dtype=cfg["serve"]["dtype"], params=served,
        max_out_tokens=cfg["serve"]["max_out_tokens"])
    out = np.asarray(engine.generate(ids[None], max_new_tokens=new))[0]
    rows = np.asarray(jax.jit(lambda p, t: family.reference_logits(
        p, t, cfg))(true, out))[P - 1:P - 1 + new]
    return float((rows.max(axis=-1) - rows[np.arange(new), out[P:]]).max())


def run_one(cfg, family, seed, prompt_len, new_tokens, control=False):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference import engine as ie

    z = family._sizes(cfg)
    model = family.build_model(cfg, "serve")
    dec = model.block_decoding
    true = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init_params(key)))(
            jax.random.PRNGKey(int(seed)))
    params = float8_mixers(true) if control else true
    prompt = np.random.default_rng([int(seed), 23]).integers(
        0, z.vocab, size=prompt_len, dtype=np.int32)
    blocks = -(-new_tokens // z.block)
    total = prompt_len + blocks * z.block
    counts = ie._transfer_counts(dec)

    choices = []
    restore = record_router_choices(choices)
    try:
        prefill = jax.jit(lambda p, ids: model.prefill(
            p, ids, model.init_cache(1, -(-total // 128) * 128))[1])
        one_pass = jax.jit(lambda p, t, m, c: model.block_step(p, t, m, c))
        commit = jax.jit(lambda p, t, c: model.block_step(
            p, t, jnp.zeros(t.shape, bool), c, commit=True)[1])
        unmask = jax.jit(lambda conf, masked, n: ie._unmask(conf, masked, n,
                                                            dec))
        cache = prefill(params, prompt[None])
        jax.block_until_ready(cache)
        jax.effects_barrier()
        choices.clear()                 # the prompt's: not compared
        passes, ids = [], list(prompt)
        for b in range(blocks):
            tokens = np.zeros((1, z.block), np.int32)
            masked = np.ones((1, z.block), bool)
            for s in range(dec.steps):
                if not masked.any():
                    break
                logits, cache = one_pass(params, tokens, masked, cache)
                logits = np.asarray(logits[0], np.float32)
                jax.effects_barrier()
                sides = np.stack([
                    (e[:, :, None] == z.first + np.arange(z.held)).any(axis=1)
                    for e in choices]).astype(np.int8)   # (L, Lb, held)
                choices.clear()
                x0 = logits.argmax(axis=-1)
                p = np.exp(logits - logits.max(axis=-1, keepdims=True))
                conf = p[np.arange(z.block), x0] / p.sum(axis=-1)
                move = np.asarray(unmask(conf[None], masked, counts[s]))
                passes.append(dict(
                    start=prompt_len + b * z.block, step=s,
                    tokens=tokens[0].copy(), masked=masked[0].copy(),
                    logits=logits, sides=sides, conf=conf, moved=move[0]))
                tokens = np.where(move, x0[None], tokens)
                masked = masked & ~move
            cache = commit(params, tokens, cache)
            jax.effects_barrier()
            choices.clear()
            ids.extend(tokens[0].tolist())
    finally:
        restore()
    ids = np.asarray(ids, np.int32)

    # the reference, in blocks: the finished sequence's K/V once, then every
    # recorded pass as the block's rows against the K/V before it
    whole = jax.jit(lambda p, ids: family.reference_forward(
        p, ids, jnp.zeros(ids.shape, bool), cfg)[1])
    kept = whole(true, ids)
    prefix = (kept["k"], kept["v"])
    block_pass = jax.jit(lambda p, t, m, start, held: family.reference_forward(
        p, t, m, cfg, start=start, prefix=prefix, held=held))
    own = jnp.full((z.layers, z.block, z.held), -1, jnp.int8)
    out = dict(gap_on_choices=[], gap_plain=[], open_rows=0, rows=0, flips=0,
               first_passes=0, position_open=0, position_flips=0,
               conf_rel_err=[])
    for rec in passes:
        start = jnp.int32(rec["start"])
        plain, routers = block_pass(true, rec["tokens"], rec["masked"],
                                    start, own)
        given, _ = block_pass(true, rec["tokens"], rec["masked"], start,
                              jnp.asarray(rec["sides"]))
        plain, given = np.asarray(plain), np.asarray(given)
        out["gap_plain"].append(float(np.abs(plain - rec["logits"]).max()))
        out["gap_on_choices"].append(
            float(np.abs(given - rec["logits"]).max()))
        distance = np.asarray(routers["distance"])         # (L, Lb, held)
        out["open_rows"] += int((distance.min(axis=-1) <= family.TIE).sum())
        out["rows"] += distance.shape[0] * distance.shape[1]
        theirs = (np.asarray(routers["chosen"])[:, :, :, None]
                  == z.first + np.arange(z.held)).any(axis=2)
        out["flips"] += int((theirs != (rec["sides"] > 0)).sum())
        p = np.exp(given - given.max(axis=-1, keepdims=True))
        conf = p.max(axis=-1) / p.sum(axis=-1)
        live = rec["masked"]
        out["conf_rel_err"].append(float(np.abs(
            conf[live] - rec["conf"][live]).max() / conf[live].min()))
        if rec["step"] == 0 and dec.remasking != "sequential" \
                and counts[0] < z.block:
            out["first_passes"] += 1
            order = np.sort(conf)[::-1]
            n = counts[0]
            out["position_open"] += bool(
                order[n - 1] - order[n] <= family.POSITION_TIE * order[n - 1])
            mine = family.unmask(conf, rec["masked"], n, z)
            out["position_flips"] += bool((mine != rec["moved"]).any())
    return {
        "seed": int(seed), "prompt": int(prompt_len), "new": int(new_tokens),
        **({"control": "float8 mixers"} if control else {}),
        "check_shortfall": check_shortfall(cfg, family, model, params, true,
                                           seed),
        "passes": len(passes),
        "gap_on_choices_max": max(out["gap_on_choices"]),
        "gap_on_choices_median": float(np.median(out["gap_on_choices"])),
        "gap_plain_max": max(out["gap_plain"]),
        "tolerance": TOLERANCE,
        "router_open_share": out["open_rows"] / out["rows"],
        "router_flips": out["flips"], "router_choices": out["rows"],
        "position_open_share": out["position_open"]
        / max(out["first_passes"], 1),
        "position_flips": out["position_flips"],
        "first_passes": out["first_passes"],
        "conf_rel_err_max": max(out["conf_rel_err"]),
        "logit_spread": float(np.std(passes[0]["logits"]))}


def main(argv=None, manifest=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--prompts", default="2048,4096")
    ap.add_argument("--new", type=int, default=132)
    ap.add_argument("--controls", type=int, default=0)
    a = ap.parse_args(argv)
    from benchmark import families

    manifest = manifest or mf.load_manifest()
    cfg = mf.load_json(mf.config_path(manifest, a.config))
    family = families.get(cfg["family"])
    ok = True
    out_dir = ROOT / "chiprun_out"
    runs = [(seed, prompt, False) for seed in a.seeds.split(",")
            for prompt in a.prompts.split(",")]
    if a.controls:
        runs.insert(1, runs[0][:2] + (True,))
    for seed, prompt, control in runs:
        row = run_one(cfg, family, int(seed), int(prompt), a.new, control)
        ok = ok and (row["gap_on_choices_max"] <= TOLERANCE) != control
        print("WITNESS " + json.dumps(row), flush=True)
        os.makedirs(out_dir, exist_ok=True)
        with open(out_dir / "sdar_witness.jsonl", "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
