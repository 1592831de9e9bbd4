#!/usr/bin/env python3
"""What decides ``trinity-mini``'s correctness at the TIMED length, on the
chip: the harness's own check runs 128 tokens (``systems.CHECK_SEQ``), where
a window of 2,048 never binds.

    python3 benchmark/afmoe_witness.py --config trinity-mini --seed <n> \
        [--context 8192] [--controls 1]

One sequence of ``context`` seeded tokens at the published widths. The
PROGRAM: the trained system's own ``module.loss`` and its gradient (bf16
compute on the parameter values ``engine.state.params`` holds, the windowed
and full flash kernels forward and backward, remat ``'attn'``, the chunked
loss, ``jax.lax.ragged_dot`` over the share's pairs). The REFERENCE: the
family's (``benchmark/families/afmoe.py``: float32 at ``highest``, heads and
rows in blocks, the mask from ``i - j``), its gradient by ``jax.grad`` with
respect to float32 copies of the compared leaves. Compared: the loss, and by
relative error (``|g - g_ref| / |g_ref|``, Frobenius) the gradients of one
window layer's ``q_w``, the full layer's ``q_w``, ``router_w``, one held
expert's ``expert_gate_w`` (the full layer's most loaded) and ``wte``.

``--controls 1`` runs the same tokens through four BROKEN programs, each of
which at least one limit must refuse: ``window_off`` (the window layers see
the whole prefix), ``rope_on_full`` (the full layers rotated too),
``bias_left_out`` (the router chooses by its scores alone; the reference
keeps the bias) and ``backward_8bit`` (every layer's cotangent rounded to an
8-bit float, 5 exponent and 2 mantissa bits: the nearest precision below
the bf16 the configuration states, in the backward only). Prints one JSON
object; exit code 0 only if the sound program is within every limit and,
where asked for, every broken one is outside at least one.

The limits (``LIMITS``, with the readings they lie between) are below.
"""

import argparse
import contextlib
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np                            # noqa: E402

from benchmark import families                # noqa: E402
from benchmark import manifest as mf          # noqa: E402

# what is compared: name -> (path of the stacked leaf, index into it). The
# routed stack holds the published layers 2-5: [sliding, full, sliding,
# sliding]. HEAVIEST: of the full layer's held experts the one whose
# REFERENCE gradient is the largest, the most loaded one. The load is uneven
# (max over mean 7-9 on this stream) and a light expert's gradient is the sum
# of a hundred pairs, of which bf16 and float32 resolve a handful of the
# router's near-ties differently: a fixed expert read 0.216 (seed 11, my chip
# run, PR 37) where every other leaf read under 0.05.
HEAVIEST = None
LEAVES = {"window_q_w": (("blocks", "q_w"), (0,)),
          "full_q_w": (("blocks", "q_w"), (1,)),
          "router_w": (("blocks", "router_w"), ()),
          "expert_gate_w": (("blocks", "expert_gate_w"), (1, HEAVIEST)),
          "wte": (("wte",), ())}
# |loss - reference| and the gradients' relative errors. Each limit lies
# between two readings on the chip at 8,192 tokens (my chip runs, PR 37;
# seeds 11, 2147483700, 3100000007, 2147483811 sound, seeds 11 and
# 2147483811 broken): the largest the sound program gave and the smallest a
# broken program gave.
#   loss           0.00065 | the harness's own 0.01: it decides nothing here
#                  (window_off moves the loss by 0.0018-0.0114, the other
#                  three by under 0.001): the gradients refuse them
#   window_q_w     0.0534  | 0.119 (bias_left_out)
#   full_q_w       0.0542  | 0.119 (bias_left_out)
#   wte            0.0501  | 0.117 (bias_left_out)
#   router_w       0.181   | 0.473 (bias_left_out)
#   expert_gate_w  0.139   | 0.253 (bias_left_out)
# bf16 carries 8 mantissa bits: a gradient that went through ~40 bf16 matmuls
# and the kernels' bf16 probabilities is off by ~5% of its norm, the same in
# every dense leaf. The router's and the expert's are off by more for another
# reason: bf16 and float32 resolve the router's near-ties differently, a few
# (token, expert) pairs in a hundred change hands, and those leaves' gradients
# are sums over the pairs an expert was given. A wrong mask, a rotation that
# should not be, a choice made without the bias or an 8-bit cotangent (which
# underflows to zero: every error reads 1.0) moves at least four of the six
# by twice the sound reading or more.
LIMITS = {"loss": 0.01, "window_q_w": 0.08, "full_q_w": 0.08,
          "router_w": 0.25, "expert_gate_w": 0.19, "wte": 0.08}
BROKEN = ("window_off", "rope_on_full", "bias_left_out", "backward_8bit")


def _round_cotangent():
    """Identity whose cotangent is rounded to 8 bits (e5m2)."""
    import jax

    @jax.custom_vjp
    def f(x):
        return x

    # ``reduce_precision``, not a convert and back: XLA on the TPU removes
    # such a pair as excess precision
    f.defvjp(lambda x: (x, None), lambda _, g: (jax.lax.reduce_precision(
        g, exponent_bits=5, mantissa_bits=2),))
    return f


@contextlib.contextmanager
def backward_8bit(model_cls):
    """Every layer of the trunk hands back a cotangent of 8 bits."""
    rounded, sound = _round_cotangent(), model_cls._block

    def block(self, x, blk, cos_sin, kind="attn"):
        x, stats = sound(self, x, blk, cos_sin, kind)
        return rounded(x), stats

    model_cls._block = block
    try:
        yield
    finally:
        model_cls._block = sound


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _with(tree, path, leaf):
    if not path:
        return leaf
    return {**tree, path[0]: _with(tree[path[0]], path[1:], leaf)}


def witness(cfg, seed, context, controls, limits=None):
    """-> the JSON object's dict (``ok`` among its keys)."""
    import jax
    import jax.numpy as jnp

    limits = limits or LIMITS
    family = families.get(cfg["family"])
    model = family.build_model(cfg, "train")
    ids = np.random.default_rng([seed, 23]).integers(
        0, family.vocab_size(cfg), size=context, dtype=np.int32)
    # the values the trained system holds: the draw, in the compute type
    params = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(model.config.dtype), model.init_params(key)))(
            jax.random.PRNGKey(int(seed)))
    paths = sorted({path for path, _ in LEAVES.values()})

    def reference(params):
        held = {path: _at(params, path).astype(jnp.float32) for path in paths}

        def loss(held):
            merged = params
            for path, leaf in held.items():
                merged = _with(merged, path, leaf)
            return family.reference_loss(merged, ids, cfg)

        return jax.value_and_grad(loss)(held)

    def program(model, params):
        loss, grads = jax.value_and_grad(model.loss)(
            params, {"input_ids": ids[None]})
        return loss, {path: _at(grads, path) for path in paths}

    def readings(got, want):
        (loss, grads), (ref_loss, ref_grads) = got, want
        out = {"loss": abs(float(loss) - float(ref_loss))}
        for name, (path, index) in LEAVES.items():
            g = np.asarray(grads[path], np.float32)
            r = np.asarray(ref_grads[path], np.float32)
            for i in index:
                if i is HEAVIEST:
                    i = int(np.argmax(np.linalg.norm(
                        r.reshape(len(r), -1), axis=1)))
                g, r = g[i], r[i]
            out[name] = float(np.linalg.norm(g - r) / np.linalg.norm(r))
        return out

    with_config = lambda **over: type(model)(
        dataclasses.replace(model.config, **over))
    forms = {"sound": (model, params, contextlib.nullcontext())}
    if controls:
        blocks = params["blocks"]
        forms.update(
            window_off=(with_config(sliding_window=context), params,
                        contextlib.nullcontext()),
            rope_on_full=(with_config(global_rope=True), params,
                          contextlib.nullcontext()),
            bias_left_out=(model, {**params, "blocks": {
                **blocks, "router_bias": jnp.zeros_like(
                    blocks["router_bias"])}}, contextlib.nullcontext()),
            backward_8bit=(model, params, backward_8bit(type(model))))
    want = jax.jit(reference)(params)
    out, verdicts = {}, []
    for name, (form, held, broken) in forms.items():
        with broken:
            got = jax.jit(lambda p, form=form: program(form, p))(held)
        read = readings(got, want)
        over = sorted(k for k, v in read.items() if not v <= limits[k])
        out[name] = {**read, "over_its_limit": over}
        verdicts.append(bool(over) == (name in BROKEN))
    device = jax.devices()[0]
    return {"seed": seed, "context": context,
            "device": {"platform": device.platform,
                       "kind": device.device_kind},
            "loss_reference": float(want[0]), "limits": limits,
            "forms": out, "ok": all(verdicts)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--context", type=int, default=8192)
    ap.add_argument("--controls", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    cfg = mf.load_json(mf.config_path(mf.load_manifest(), a.config))
    out = witness(cfg, a.seed, a.context, bool(a.controls))
    print(json.dumps({"config": a.config, **out}))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
