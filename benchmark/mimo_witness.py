#!/usr/bin/env python3
"""Outside any window: what the harness's own check (64 -> 32 tokens) cannot
show of a configuration whose window layers keep a RING of 128 slots, on the
chip: that check never fills a window.

    python3 benchmark/mimo_witness.py --config mimo-v2-flash --seed <n> \
        [--prompts 8192,24576] [--steps 256] [--controls 1] [--cold 1]

At the cell's widths the SERVED model (bf16, every kernel: the timed path's
own ``prefill`` and ``decode_step``) prefills a prompt and then decodes
``steps - 1`` positions through its two caches, teacher-forced on seeded
ids: the ring wraps ``steps / 128`` times past the prompt. Its LOGITS at the
``steps`` positions are held to the family's float32 reference, computed
in blocks and asked for the last rows only (``resolution_logits(last=)``).

Three limits, each with its reason:

``LOGIT_TOL`` and ``MEDIAN_TOL`` (the served program; both must hold): the
    largest difference of a logit from the reference's, at any of the
    compared positions, under the BEST of the reference's resolutions of the
    router's near-ties at that position (a bf16 program and a float32 pass
    put a held expert that lies on the cut on different sides, both validly,
    and one expert moves a logit by more than bf16 does:
    ``families/mimo_v2_flash.py``), and the median difference from its plain
    pass over all of them. Logits spread by ~1.3. Read at these widths before
    the first chip run (the einsum path on the CPU in bf16, 64 rows): sound
    0.056 / 0.0074, with the loud sinks below 0.113 / 0.0163; a missing
    sink, an unrotated or fully rotated head, the two kinds' rotary bases
    swapped 3.8-7.1 / 0.50-0.99. On the chip (my chip run, PR 49, 256 rows):
    sound 0.084 / 0.0048 at 8,192 and 0.062 / 0.0045 at 24,576, loud 0.054 /
    0.0067; the four broken forms 1.76-3.78 / 0.21-0.48. The limits, 0.4 and
    0.05, lie between: 4.8 x and 7 x over the chip's sound readings, 4.4 x
    and 4 x under the least of its broken ones.
``FLOAT32_TOL`` (the program's own functions in float32 at "highest", no
    kernel): the MEDIAN difference from the reference's plain pass. A ring
    written one slot off replaces ONE of a window's 128 keys by its
    neighbour: it moves the served program's readings only to 0.18 / 0.010
    on the chip (0.29 / 0.032 on the CPU), inside its limits, so it is
    judged where it can be read. On the chip (512 + 255 positions, my chip
    run, PR 49): sound 1.4e-4, one slot off 6.2e-3; on the CPU 4.4e-6 and
    0.028. The limit, 1e-3, lies between the chip's two: x 7 and x 6 away
    (it was 1e-4, set from the CPU's readings, until the chip's float32 came
    out coarser than the CPU's: the first chip run refused the sound form).

``--controls 1`` runs, at ``--control-prompt`` tokens, the sound forms and
then the program with ONE mechanism broken each time (``CONTROLS``), and
exits 0 only if every judged one is REFUSED by its limit: a check that a
broken mechanism passes proves nothing of it. Those runs, reference and
program alike, read the sinks ``LOUD_SINK`` higher: as drawn (N(0, 1)
beside 128 scores of spread ~1.6) a sink takes ~0.3% of a row's mass, which
no bf16 comparison can see; 6 higher it takes about half, so ``loud_sink``
(the kernels' initial state, held to the reference) and ``no_sink`` (the
same run without it) mean something.

``--cold 1`` first puts one request of each prompt length through the
front-end with nothing compiled and reports how long its prefill compiled
(``compile_s``) and whether it ended inside the configuration's deadline.
Prints one JSON object a line; exit code 0 only if every sound form is
within its limit and, where asked for, every judged control is NOT.
"""

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np                            # noqa: E402

from benchmark import manifest as mf          # noqa: E402

LOGIT_TOL = 0.4
MEDIAN_TOL = 0.05
FLOAT32_TOL = 1e-3
LOUD_SINK = 6.0
# name -> (the program, the limit must REFUSE it (None: shown only))
CONTROLS = {"no_sink": ("served", True),
            "unrotated": ("served", True),
            "fully_rotated": ("served", True),
            "theta_swapped": ("served", True),
            "ring_one_slot_off": ("served", None),
            "float32_ring_one_slot_off": ("float32", True)}


@contextlib.contextmanager
def _ring_one_slot_off(broken):
    """A decode step's rows written into slot ``(pos + 1) % window``."""
    from deepspeed_tpu.models import common

    sound = common.kv_ring_write

    def off(ring, t, layer, pos):
        return sound(ring, t, layer, pos + 1 if t.shape[1] == 1 else pos)

    common.kv_ring_write = off if broken else sound
    try:
        yield
    finally:
        common.kv_ring_write = sound


@contextlib.contextmanager
def _no_kernels(off):
    from deepspeed_tpu.models import common

    sound = common._kernel_target
    if off:
        common._kernel_target = lambda: (sound()[0], False)
    try:
        yield
    finally:
        common._kernel_target = sound


def _forms(served):
    """name -> (model, changes the parameters, the ring written one slot
    off, float32 at 'highest' without kernels)."""
    import jax.numpy as jnp

    c = served.config
    make = lambda **kw: type(served)(dataclasses.replace(c, **kw))
    same = lambda p: p
    sinkless = lambda p: {**p, "win_blocks": {
        **p["win_blocks"],
        "sink": jnp.full_like(p["win_blocks"]["sink"], -1e9)}}
    f32 = make(dtype=jnp.float32, use_flash_attention=False)
    return {
        "sound": (served, same, False, False),
        "loud_sink": (served, same, False, False),
        "float32": (f32, same, False, True),
        "no_sink": (served, sinkless, False, False),
        "unrotated": (make(use_rope=False), same, False, False),
        "fully_rotated": (make(rotary_dim=None), same, False, False),
        "theta_swapped": (make(rope_theta=c.window_rope_theta,
                               window_rope_theta=c.rope_theta), same, False,
                          False),
        "ring_one_slot_off": (served, same, True, False),
        "float32_ring_one_slot_off": (f32, same, True, True)}


def _run(form, params, ids, prompt, slots):
    """The program's own prefill of ``ids[:prompt]`` and one decode step a
    later id -> logits of positions ``prompt - 1 ..`` (len(ids) - prompt + 1
    rows)."""
    import jax
    import jax.numpy as jnp

    model, change, off, plain = form
    params = change(params)
    with _ring_one_slot_off(off), _no_kernels(plain), \
            jax.default_matmul_precision("highest" if plain else None):
        cache = model.init_cache(1, slots)
        first, cache = model.prefill(params, ids[None, :prompt], cache)

        def step(cache, token):
            logits, cache = model.decode_step(params, token[None], cache)
            return cache, logits[0]

        _, rest = jax.lax.scan(step, cache, ids[prompt:-1])
    return jnp.concatenate([first, rest])


def _compare(got, every, tol32=None):
    """``got`` (rows, vocab) against the reference's resolutions ``every``
    (R, rows, vocab; row 0 the plain pass)."""
    diff = np.abs(every - got[None]).max(axis=-1)           # (R, rows)
    best = diff.min(axis=0)
    out = {"worst_logit_difference": float(best.max()),
           "worst_against_the_plain_pass": float(diff[0].max()),
           "median_logit_difference": float(np.median(np.abs(every[0] - got))),
           "rows_resolved": int((diff.argmin(axis=0) > 0).sum()),
           "argmax_equal": int((every[0].argmax(-1) == got.argmax(-1)).sum())}
    out["within_its_limit"] = bool(
        out["median_logit_difference"] <= FLOAT32_TOL if tol32
        else out["worst_logit_difference"] <= LOGIT_TOL
        and out["median_logit_difference"] <= MEDIAN_TOL)
    return out


def _cold(system, prompts, rows):
    """One request a prompt length through the front-end, nothing compiled:
    did its prefill compile inside the deadline?"""
    for prompt in prompts:
        t0 = time.monotonic()
        req = system.submit(np.random.default_rng(prompt).integers(
            0, system.vocab, size=prompt, dtype=np.int32), 17, None)
        req.result(timeout=1200.0)
        rows.append({"cold_request": prompt, "status": req.status,
                     "reason": req.reason, "compile_s": req.compile_s,
                     "seconds": time.monotonic() - t0,
                     "deadline_s": req.deadline_s})
        print("WITNESS " + json.dumps(rows[-1]), flush=True)
    return all(r["status"] == "completed" for r in rows)


def witness(cfg, seed, prompts, steps, controls, control_prompt, cold):
    import jax

    from benchmark import systems

    system = systems.ServeSystem(cfg, {}, seed, 1)
    engine, family = system.engine, system.family
    slots = int(cfg["serve"]["max_out_tokens"])
    forms = _forms(engine.module)
    rows, ok = [], True
    if cold:
        ok = _cold(system, prompts, rows)
    program = lambda name, prompt: jax.jit(functools.partial(
        _run, forms[name], prompt=prompt, slots=slots))
    reference = jax.jit(lambda p, ids: family.resolution_logits(
        p, ids, cfg, last=steps + 1))
    runs = [(p, ("sound",), engine.params) for p in prompts]
    if controls:
        loud = {**engine.params, "win_blocks": {
            **engine.params["win_blocks"],
            "sink": engine.params["win_blocks"]["sink"] + LOUD_SINK}}
        runs.append((control_prompt, ("loud_sink", "float32")
                     + tuple(CONTROLS), loud))
    with engine.mesh:
        for prompt, names, params in runs:
            ids = np.random.default_rng([seed, 23, prompt]).integers(
                0, system.vocab, size=prompt + steps, dtype=np.int32)
            # rows of positions prompt - 1 .. prompt + steps - 2: the last
            # id is decoded by nobody
            every = np.asarray(reference(params, ids))[:, :-1]
            for name in names:
                got = np.asarray(program(name, prompt)(params, ids))
                row = {"seed": seed, "prompt": prompt, "decode_steps": steps - 1,
                       "ring_wraps_past_the_prompt": steps // family._sizes(
                           cfg).window, "form": name,
                       **_compare(got, every, name.startswith("float32"))}
                refused = CONTROLS.get(name, (None, False))[1]
                if refused is not None:
                    ok = ok and row["within_its_limit"] != refused
                rows.append(row)
                print("WITNESS " + json.dumps(row), flush=True)
    device = jax.devices()[0]
    system.close()
    return {"seed": seed, "logit_tol": LOGIT_TOL, "median_tol": MEDIAN_TOL,
            "float32_tol": FLOAT32_TOL,
            "device": {"platform": device.platform,
                       "kind": device.device_kind},
            "reference_logit_spread": float(every[0].std()),
            "rows": rows, "ok": bool(ok)}


def main(argv=None, manifest=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompts", default="8192,24576")
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--controls", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control-prompt", type=int, default=512)
    ap.add_argument("--cold", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    manifest = manifest or mf.load_manifest()
    cfg = mf.load_json(mf.config_path(manifest, a.config))
    out = witness(cfg, a.seed, [int(p) for p in a.prompts.split(",") if p],
                  a.steps, bool(a.controls), a.control_prompt, bool(a.cold))
    print(json.dumps({"config": a.config, **out}))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
