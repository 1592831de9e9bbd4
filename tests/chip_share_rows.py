#!/usr/bin/env python3
"""A share's routed MLP ON THE CHIP, one routed layer of
``trinity-mini.train.z1.s8k`` alone (16,384 tokens x top-8 of 128 experts,
16 held, widths 2048 <-> 1024, bf16): ``moe/dropless.py``'s compact rows
against the full-size form it replaced (``tests/unit/test_afmoe.py::
_full_size_form``: row buffers of all 131,072 pairs), output and every
gradient, and the time of a forward + backward of each, at four held counts:

* ``even``       the router's pairs fall evenly (12.5% held: 16,384 rows);
* ``none``       no pair is held (a collapsed layer);
* ``one_expert`` one held expert is given every token (16,384 rows, a group);
* ``overflow``   more than ``share_capacity`` rows are held, so what the
  buffer does not take goes through the same code once more.

What XLA:TPU's grouped kernels leave in a row of no group is whatever the
buffer held (``PERF.md`` section 6, PR 37): before every compared call the
chip's free memory is filled with NaN and released, so a form that lets
such a row through shows here and nowhere on the CPU.

    chiprun --chips 1 -- python3 tests/chip_share_rows.py [--seed N]

(``--tokens 256`` is the rehearsal of the script itself on the CPU, where
its times mean nothing.)

Prints one JSON line a case and writes them to ``chiprun_out/share_rows.json``;
exit 1 if any output or gradient of the two forms differs by more than
``TOL`` of its norm. Not collected by pytest (the name): it needs the chip.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax                                     # noqa: E402
import jax.numpy as jnp                        # noqa: E402
import numpy as np                             # noqa: E402

from deepspeed_tpu.moe import dropless         # noqa: E402
from tests.unit.test_afmoe import _full_size_form  # noqa: E402

TOKENS, D, F, K, ROUTER, FIRST, HELD = 16384, 2048, 1024, 8, 128, 48, 16
# bf16 products summed in another order: a few units of 2**-8 of a norm; the
# fault this guards against (PR 37) read 1e4 of one
TOL = 2e-2
CALLS = 10


def inputs(seed, T):
    key = jax.random.PRNGKey(seed % (2 ** 31))
    x = jax.random.normal(key, (T, D)).astype(jnp.bfloat16)
    weights = jax.random.uniform(jax.random.fold_in(key, 1), (T, K),
                                 minval=0.05, maxval=0.6)
    leaf = lambda i, *shape: (0.02 * jax.random.normal(
        jax.random.fold_in(key, i), shape)).astype(jnp.bfloat16)
    return x, weights, leaf(2, HELD, D, F), leaf(3, HELD, D, F), \
        leaf(4, HELD, F, D)


def routing(case, seed, T):
    """(T, K) experts of the router's 128, distinct a token."""
    rng = np.random.default_rng(seed)
    scores = rng.random((T, ROUTER))
    held = slice(FIRST, FIRST + HELD)
    if case == "none":
        scores[:, held] -= 2.0
    elif case == "one_expert":
        scores[:, held] -= 2.0
        scores[:, FIRST + 5] += 4.0
    elif case == "overflow":
        scores[:, held] += 0.35
    return jnp.asarray(np.argsort(-scores, axis=1)[:, :K].astype(np.int32))


def poison_free_memory():
    """Fill what the chip has free with NaN, then release it."""
    junk = []
    try:
        for _ in range(24):
            junk.append(jnp.full((256, 1024, 1024), jnp.nan, jnp.bfloat16))
            junk[-1].block_until_ready()
    except Exception:                           # the chip is full: enough
        pass
    for a in junk:
        a.delete()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--tokens", type=int, default=TOKENS)
    args = ap.parse_args()
    T = args.tokens
    device = jax.devices()[0]
    if device.platform != "tpu" and T == TOKENS:
        sys.exit(f"needs the chip, found {device.platform}")
    capacity = dropless.share_capacity(T * K, HELD, ROUTER)
    probe = jnp.cos(jnp.arange(D, dtype=jnp.float32))

    def scalar(form):
        def f(x, weights, gate_w, up_w, down_w, experts):
            out, sizes = form(x, weights, experts, gate_w, up_w, down_w)
            return jnp.sum(out.astype(jnp.float32) * probe), (out, sizes)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))

    forms = {
        "share": scalar(lambda *a: dropless.routed_mlp(
            *a, first=FIRST, n_experts=ROUTER)),
        "full": scalar(lambda *a: _full_size_form(*a, first=FIRST)),
    }
    operands = inputs(args.seed, T)
    lines, ok = [], True
    for case in ("even", "none", "one_expert", "overflow"):
        experts = routing(case, args.seed, T)
        got = {}
        for name, fn in forms.items():
            fn(*operands, experts)[0][0].block_until_ready()    # compile
            poison_free_memory()
            got[name] = jax.device_get(fn(*operands, experts))
        (_, (out, sizes)), grads = got["share"]
        (_, (full_out, full_sizes)), full_grads = got["full"]
        gaps = {}
        for label, a, b in zip(
                ("out", "d_x", "d_weights", "d_gate_w", "d_up_w", "d_down_w"),
                (out,) + tuple(grads), (full_out,) + tuple(full_grads)):
            a, b = (np.asarray(v, np.float32) for v in (a, b))
            norm = float(np.linalg.norm(b))
            gaps[label] = float(np.linalg.norm(a - b)) / max(norm, 1e-30) \
                if np.isfinite(a).all() else float("inf")
        seconds = {}
        for name, fn in forms.items():
            t0 = time.perf_counter()
            for _ in range(CALLS):
                r = fn(*operands, experts)
            jax.block_until_ready(r)
            seconds[name] = (time.perf_counter() - t0) / CALLS
        line = {"case": case, "held_pairs": int(sizes.sum()),
                "capacity": capacity, "pairs": T * K,
                "overflowed": bool(dropless.share_overflowed(
                    sizes, T * K, ROUTER)),
                "sizes_equal": bool((sizes == full_sizes).all()),
                "gap_of_norm": gaps, "tol": TOL,
                "fwd_bwd_ms": {k: 1e3 * v for k, v in seconds.items()},
                "seed": args.seed, "device": device.device_kind}
        line["ok"] = line["sizes_equal"] and max(gaps.values()) <= TOL
        ok &= line["ok"]
        lines.append(line)
        print(json.dumps(line), flush=True)
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / "share_rows.json").write_text(json.dumps(lines, indent=1))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
