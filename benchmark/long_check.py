#!/usr/bin/env python3
"""Outside any window: a configuration's SERVED model against its family's
plain reference at a long context, on the chip.

    python3 benchmark/long_check.py --config <name> --seed <n> \
        [--context 2048] [--last 256]

The set-up check of a serve cell (``systems.ServeSystem.check``) compares 32
tokens after a 64-token prompt. This is the model-configs guide's other
half: at the published widths, a seeded sequence of ``context`` tokens, the
last ``last`` query positions against the whole context. The program's model
— built and given its weights exactly as ``ServeSystem`` does — prefills
``context - last`` tokens and then decodes the remaining ones through its
cache, TEACHER-FORCED with the sequence's own tokens; the reference runs one
full forward pass over all of them. Prints one JSON object: the largest
logit difference over those positions, and the largest amount by which the
reference's logit of the program's best token falls short of the
reference's best (the margin ``ServeSystem`` holds a served token to).
"""

import argparse
import functools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np                            # noqa: E402

from benchmark import manifest as mf          # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--context", type=int, default=2048)
    ap.add_argument("--last", type=int, default=256)
    a = ap.parse_args(argv)
    cfg = mf.load_json(mf.config_path(mf.load_manifest(), a.config))

    import jax
    import jax.numpy as jnp

    from benchmark import systems

    system = systems.ServeSystem(cfg, {}, a.seed, 1)
    engine, model, family = system.engine, system.engine.module, system.family
    ids = np.random.default_rng([a.seed, 19]).integers(
        0, system.vocab, size=a.context, dtype=np.int32)
    cut = a.context - a.last

    def served(params, ids):
        cache = model.init_cache(1, int(cfg["serve"]["max_out_tokens"]))
        first, cache = model.prefill(params, ids[None, :cut], cache)

        def step(cache, token):
            logits, cache = model.decode_step(params, token[None], cache)
            return cache, logits[0]

        _, rest = jax.lax.scan(step, cache, ids[cut:-1])
        return jnp.concatenate([first, rest])   # positions cut-1 .. context-2

    with engine.mesh:
        got = np.asarray(jax.jit(served)(engine.params, ids))
        want = np.asarray(jax.jit(functools.partial(
            family.reference_logits, cfg=cfg))(engine.params, ids))[cut - 1:-1]
    best = got.argmax(axis=-1)
    short = want.max(axis=-1) - want[np.arange(len(best)), best]
    print(json.dumps({
        "config": a.config, "seed": a.seed, "context": a.context,
        "positions_compared": int(len(best)),
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "worst_logit_difference": float(np.abs(got - want).max()),
        "median_logit_difference": float(np.median(np.abs(got - want))),
        "reference_logit_spread": float(want.std()),
        "worst_logit_shortfall": float(short.max()),
        "margin": systems.SERVE_LOGIT_MARGIN,
        "argmax_equal": int((want.argmax(axis=-1) == best).sum())}))
    system.close()
    return 0 if short.max() <= systems.SERVE_LOGIT_MARGIN else 1


if __name__ == "__main__":
    sys.exit(main())
