#!/usr/bin/env python3
"""Outside any window: what ``long_check.py`` cannot show of a configuration
with KDA layers, on the chip.

    python3 benchmark/kda_witness.py --config <name> --seed <n> \
        [--context 32768] [--last 256] [--controls 1]

``long_check.py``'s comparison — the SERVED model prefills ``context -
last`` tokens through the chunked kernel and decodes the rest through its
state, teacher-forced, against the family's reference in one pass over all
of them — with two differences. (1) The reference is asked for the logits
of the last positions only (``reference_logits(..., last=)``): its (T,
vocab) float32 array, 3.2 GB at 32,768 tokens and held three times over, is
what does not fit beside the served weights, so ``long_check.py`` stops at
16,384. (2) ``--controls 1`` runs the same tokens again with ONE mechanism
of KDA broken in the program each time: ``tap_dropped`` (the convolution's
oldest tap zeroed), ``beta_unscaled`` (beta = sigmoid, without the factor
2) and ``state_bf16`` (the state rounded to bfloat16 whenever a call hands
it on: between a prompt's segments, after prefill, after every decode
step). The first two are held to the margin a served token is held to. A
bfloat16 state that margin CANNOT see — on the chip it moves the served
program's median logit difference from 0.0104 to 0.0115, inside what
bfloat16 does everywhere else — so it is read where it can be: the program's
own functions in float32 at "highest" with no kernel (``float32``, and
``float32_state_bf16``), held by their median difference from the
reference's PLAIN pass to ``FLOAT32_TOL``. A broken form differs from the
sound one by two traced scalars and a leaf's values, so each of the two
programs compiles once. Prints one JSON object; exit code 0 only if every
sound form is within its limit and, where asked for, every judged control is
NOT: a check that a broken mechanism passes proves nothing of it.
"""

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np                            # noqa: E402

from benchmark import manifest as mf          # noqa: E402

# name -> (the program, beta's factor, round the state to bfloat16, zero the
# oldest tap, the control is one its program's limit must refuse)
FORMS = {"sound": ("served", 1.0, False, False, False),
         "tap_dropped": ("served", 1.0, False, True, True),
         "beta_unscaled": ("served", 0.5, False, False, True),
         "state_bf16": ("served", 1.0, True, False, None),     # shown only
         "float32": ("float32", 1.0, False, False, False),
         "float32_state_bf16": ("float32", 1.0, True, False, True)}
# median |logit - the plain pass's| of the float32 program over the compared
# positions. Read on the chip at 4,096 tokens (PERF.md, PR 33): 4.4e-6 sound,
# 2.9e-3 with a bfloat16 state; the limit between them, x 23 and x 29 away
FLOAT32_TOL = 1e-4


@contextlib.contextmanager
def kda_with(beta_factor, round_state, kernels=True):
    """The program's two forms of the recurrence (``common.kda_attention``
    over a segment, ``kda_step`` over one position: ``models/kda.py`` looks
    both up when it is called) given beta x ``beta_factor`` and handing on
    the state rounded to bfloat16 where ``round_state``: traced scalars.
    ``kernels`` False: a program with no Pallas kernel in it."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import common
    from deepspeed_tpu.ops.pallas import kda as ops

    def changed(form):
        def run(q, k, v, g, beta, state, *rest):
            o, state = form(q, k, v, g, beta * beta_factor, state, *rest)
            # ``reduce_precision``, not a convert to bfloat16 and back: that
            # pair is excess precision to XLA on the TPU, which removes it
            # (the first chip run read this control bit for bit as sound)
            return o, jnp.where(round_state, jax.lax.reduce_precision(
                state, exponent_bits=8, mantissa_bits=7), state)
        return run

    sound = common.kda_attention, ops.kda_step, common._kernel_target
    common.kda_attention, ops.kda_step = (changed(f) for f in sound[:2])
    if not kernels:
        common._kernel_target = lambda: (sound[2]()[0], False)
    try:
        yield
    finally:
        common.kda_attention, ops.kda_step, common._kernel_target = sound


def witness(cfg, seed, context, last, controls):
    """-> the JSON object's dict (``ok`` among its keys)."""
    import jax
    import jax.numpy as jnp

    from benchmark import systems

    system = systems.ServeSystem(cfg, {}, seed, 1)
    engine, family = system.engine, system.family
    ids = np.random.default_rng([seed, 19]).integers(
        0, system.vocab, size=context, dtype=np.int32)
    cut = context - last
    served = engine.module
    models = {"served": (served, None), "float32": (type(served)(
        dataclasses.replace(served.config, dtype=jnp.float32,
                            use_flash_attention=False)), "highest")}

    def run(program, params, ids, beta_factor, round_state):
        model, precision = models[program]
        with kda_with(beta_factor, round_state, kernels=precision is None), \
                jax.default_matmul_precision(precision):
            cache = model.init_cache(1, int(cfg["serve"]["max_out_tokens"]))
            first, cache = model.prefill(params, ids[None, :cut], cache)

            def step(cache, token):
                logits, cache = model.decode_step(params, token[None], cache)
                return cache, logits[0]

            _, rest = jax.lax.scan(step, cache, ids[cut:-1])
        return jnp.concatenate([first, rest])   # positions cut-1 .. context-2

    def without_oldest_tap(params):
        taps = params["kda_blocks"]["kda_conv_w"]
        return {**params, "kda_blocks": {
            **params["kda_blocks"], "kda_conv_w": taps.at[:, 0].set(0)}}

    margin = systems.SERVE_LOGIT_MARGIN
    programs = {name: jax.jit(functools.partial(run, name))
                for name in models}
    reference = lambda fn: np.asarray(jax.jit(functools.partial(
        fn, cfg=cfg, last=last + 1))(engine.params, ids))[:-1]
    forms, verdicts = {}, []
    with engine.mesh:
        want = reference(family.reference_logits)
        plain = reference(lambda *a, **kw: family.reference_forward(
            *a, **kw)[0]) if controls else None
        for name in FORMS if controls else ("sound",):
            program, factor, rounded, dropped, refused = FORMS[name]
            params = without_oldest_tap(engine.params) if dropped \
                else engine.params
            got = np.asarray(programs[program](
                params, ids, jnp.float32(factor), jnp.bool_(rounded)))
            best = got.argmax(axis=-1)
            short = want.max(axis=-1) - want[np.arange(len(best)), best]
            value = plain if program == "float32" else want
            forms[name] = {
                "worst_logit_shortfall": float(short.max()),
                "positions_over_the_margin": int((short > margin).sum()),
                "median_logit_difference": float(np.median(
                    np.abs(got - value))),
                "worst_logit_difference": float(np.abs(got - value).max()),
                "argmax_equal": int((want.argmax(axis=-1) == best).sum())}
            within = forms[name]["median_logit_difference"] <= FLOAT32_TOL \
                if program == "float32" else short.max() <= margin
            forms[name]["within_its_limit"] = bool(within)
            if refused is not None:
                verdicts.append(bool(within) != refused)
    device = jax.devices()[0]
    system.close()
    return {"seed": seed, "context": context, "positions_compared": last,
            "device": {"platform": device.platform,
                       "kind": device.device_kind},
            "margin": margin, "float32_tol": FLOAT32_TOL,
            "reference_logit_spread": float(want.std()),
            "forms": forms, "ok": all(verdicts)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--context", type=int, default=32768)
    ap.add_argument("--last", type=int, default=256)
    ap.add_argument("--controls", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    cfg = mf.load_json(mf.config_path(mf.load_manifest(), a.config))
    out = witness(cfg, a.seed, a.context, a.last, bool(a.controls))
    print(json.dumps({"config": a.config, **out}))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
