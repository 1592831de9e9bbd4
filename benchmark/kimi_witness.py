#!/usr/bin/env python3
"""What decides ``kimi-linear-48b-a3b``'s correctness at the TIMED length, on
the chip: the harness's own check runs 128 tokens (``systems.CHECK_SEQ``),
two chunks of KDA and one flash block, and compares a loss.

    python3 benchmark/kimi_witness.py --config kimi-linear-48b-a3b \
        --seed <n> [<n> ...] [--context 16384] [--controls 1]

One sequence of ``context`` seeded tokens at the published widths. The
PROGRAM: the trained system's own ``module.loss`` and its gradient (bf16
compute on the parameter values ``engine.state.params`` holds; the KDA state
pass through ``kda_chunk_fwd`` / ``kda_chunk_bwd`` a segment at a time, the
latent layer through the flash kernels at 192 / 128 forward and backward,
remat ``'attn'``, the chunked loss, ``jax.lax.ragged_dot`` over the share's
pairs). The REFERENCE: the family's (``benchmark/families/kimi_linear.py``:
float32 at ``highest``, the recurrence token by token, latent attention a
head and a block of rows at a time), its gradient by ``jax.grad`` with
respect to float32 copies of the compared leaves. Compared: the loss, and by
relative error (``|g - g_ref| / |g_ref|``, Frobenius) the gradients of the
routed stack's KDA layers' ``kda_qkv_w``, ``kda_a_log``, ``kda_dt_bias`` and
``kda_b_w``, the latent layer's ``q_w`` and ``kv_a_w``, ``wte``, ``router_w``
and one held expert's ``expert_gate_w`` (the latent layer's most loaded).

``--controls 1`` runs the same tokens through six BROKEN programs, each of
which at least one limit must refuse: ``decay_grad_dropped`` (the
state pass's backward hands back no cotangent for a chunk's decay),
``beta_unscaled`` (beta = sigmoid, not 2 sigmoid), ``tap_dropped`` (the
convolution's oldest tap zero), ``rope_on_mla`` (the latent layer's 64
shared columns rotated), ``bias_left_out`` (the router chooses by its scores
alone; the reference keeps the bias) and ``backward_8bit`` (every layer's
cotangent rounded to an 8-bit float, 5 exponent and 2 mantissa bits: the
nearest precision below the bf16 the configuration states, in the backward
only). A seventh, ``state_bf16`` (the backward kernel's ``dS`` rounded to
bfloat16 after every chunk), the bf16 program cannot show: its gradients
read as the sound program's to three digits (seed 11, my chip run, PR 41:
``kda_a_log`` 0.0574 for 0.0549, every other leaf equal), the rounding of one
cotangent drowned in the rounding of every operand. It is held where it can
be seen, ``core_float32``: the state pass alone (``chunked_kda(kernel=True,
vjp=True)``, a segment of 2,048 positions at a time as ``models/kda.py``
walks them) in FLOAT32 at ``context`` positions of the configuration's KDA
heads, its gradients with respect to q, k, v, g and beta against ``jax.grad``
of the recurrence, sound and with that rounding. Prints one JSON object a
seed; exit code 0 only if for every seed the sound program is within every
limit and, where asked for, every broken one is outside at least one.

The limits (``LIMITS``, with the readings they lie between) are below.
"""

import argparse
import contextlib
import dataclasses
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np                            # noqa: E402

from benchmark import families                # noqa: E402
from benchmark import manifest as mf          # noqa: E402
from benchmark.afmoe_witness import (HEAVIEST, _at, _with,  # noqa: E402
                                     backward_8bit)
from benchmark.families.afmoe import _block_of  # noqa: E402

# what is compared: name -> (path of the stacked leaf, index into it). The
# routed stack holds the published layers 2-5: [KDA, KDA, MLA, KDA]; its KDA
# leaves are compared over all three layers at once, the expert is the
# latent layer's (``blocks`` layer 2) most loaded (``afmoe_witness``).
LEAVES = {"kda_qkv_w": (("kda_blocks", "kda_qkv_w"), ()),
          "kda_a_log": (("kda_blocks", "kda_a_log"), ()),
          "kda_dt_bias": (("kda_blocks", "kda_dt_bias"), ()),
          "kda_b_w": (("kda_blocks", "kda_b_w"), ()),
          "mla_q_w": (("attn_blocks", "q_w"), ()),
          "mla_kv_a_w": (("attn_blocks", "kv_a_w"), ()),
          "router_w": (("blocks", "router_w"), ()),
          "expert_gate_w": (("blocks", "expert_gate_w"), (2, HEAVIEST)),
          "wte": (("wte",), ())}
# |loss - reference| and the gradients' relative errors: see PERF.md section
# 6 (PR 41) for the readings each limit lies between.
LIMITS = {"loss": 0.01, "kda_qkv_w": 0.08, "kda_a_log": 0.12,
          "kda_dt_bias": 0.10, "kda_b_w": 0.08, "mla_q_w": 0.08,
          "mla_kv_a_w": 0.07, "router_w": 0.36, "expert_gate_w": 0.30,
          "wte": 0.08}
BROKEN = ("decay_grad_dropped", "beta_unscaled", "tap_dropped",
          "rope_on_mla", "bias_left_out", "backward_8bit")
# ``core_float32``: the largest relative error over the five gradients. On
# the chip at 16,384 positions (seeds 11 / 2147483700 / 3100000007, my chip
# run, PR 41) the sound pass reads 1.04e-4 to 1.20e-4 (float32 matmuls are
# bf16 passes on the MXU, in the kernels too) and ``state_bf16`` 1.21e-3 to
# 1.29e-3: the limit a third of the way up the decade between them, 3.3 x
# the sound reading and a third of the broken one
CORE_LIMIT = 4e-4
CORE_SEGMENT = 2048


@contextlib.contextmanager
def chunk_backward(change):
    """``ops/pallas/kda.py::_chunk_backward`` (the kernel and its ``jnp`` form
    both look it up when they are traced) with ``change`` laid over its seven
    results."""
    from deepspeed_tpu.ops.pallas import kda as ops

    sound = ops._chunk_backward
    ops._chunk_backward = lambda *args: change(*sound(*args))
    try:
        yield
    finally:
        ops._chunk_backward = sound


def _state_bf16(*grads):
    import jax.numpy as jnp

    *rest, dstate = grads
    # a convert and back INSIDE the kernel: Mosaic keeps it (XLA would
    # remove the pair as excess precision)
    return (*rest, dstate.astype(jnp.bfloat16).astype(jnp.float32))


def _decay_grad_dropped(*grads):
    return (*grads[:5], grads[5] * 0.0, grads[6])


@contextlib.contextmanager
def beta_unscaled():
    """``common.kda_attention`` (``models/kda.py`` looks it up when called)
    given half its beta: sigmoid, not 2 sigmoid."""
    from deepspeed_tpu.models import common

    sound = common.kda_attention
    common.kda_attention = lambda q, k, v, g, beta, *rest: sound(
        q, k, v, g, 0.5 * beta, *rest)
    try:
        yield
    finally:
        common.kda_attention = sound


def _tap_dropped(params):
    """The convolution's oldest tap zero in every KDA layer."""
    out = params
    for stack in ("kda_blocks", "dense_blocks"):
        taps = params[stack]["kda_conv_w"]
        out = _with(out, (stack, "kda_conv_w"), taps.at[:, 0].set(0))
    return out


def core_float32(cfg, seed, context, segment=CORE_SEGMENT):
    """-> {"sound": e, "state_bf16": e}: the state pass's own backward in
    float32 against ``jax.grad`` of the recurrence, largest relative error
    (Frobenius) over the gradients of q, k, v, g, beta. Inputs as the mixer
    makes them: unit q (x dk^-1/2) and k, g = -A softplus(.) with A ~ U(1,
    16) a head and dt ~ logU(1e-3, 1e-1) a channel, beta = 2 sigmoid(.)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.common import _kernel_target
    from deepspeed_tpu.ops.pallas import kda as ops

    lin = cfg["model"]["linear_attn_config"]
    H, dk = lin["num_heads"], lin["head_dim"]
    r = np.random.default_rng([seed, 31])
    n = lambda *shape: jnp.asarray(r.standard_normal(shape), jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    dt = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(1e-1), (H, dk))),
                     jnp.float32)
    args = (unit(n(1, context, H, dk)) * dk ** -0.5, unit(n(1, context, H, dk)),
            n(1, context, H, dk),
            -jnp.asarray(r.uniform(1, 16, (H, 1)), jnp.float32)
            * jax.nn.softplus(jnp.log(jnp.expm1(dt))
                              + 0.3 * n(1, context, H, dk)),
            2 * jax.nn.sigmoid(n(1, context, H)))
    probe = n(1, context, H, dk)
    zeros = jnp.zeros((1, H, dk, dk), jnp.float32)
    on_tpu = _kernel_target()[1]

    def walk(one, block):
        """``one`` over blocks of ``block`` positions, the state handed on,
        each block a ``jax.checkpoint``."""
        def loss(*args):
            step = jax.checkpoint(lambda state, rows: one(*rows, state)[::-1])
            _, o = jax.lax.scan(step, zeros, tuple(
                jnp.moveaxis(t.reshape(1, context // block, block,
                                       *t.shape[2:]), 1, 0) for t in args))
            return jnp.sum(jnp.moveaxis(o, 0, 1).reshape(probe.shape) * probe)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))

    chunked = lambda *a: ops.chunked_kda(*a, kernel=on_tpu, vjp=True)
    with jax.default_matmul_precision("highest"):
        want = walk(ops.recurrent_kda, _block_of(context, 64))(*args)
        out = {}
        for name, broken in (("sound", contextlib.nullcontext()),
                             ("state_bf16", chunk_backward(_state_bf16))):
            with broken:
                got = walk(chunked, _block_of(context, segment))(*args)
            out[name] = max(float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
                            for g, w in zip(got, want))
    return out


def witness(cfg, seed, context, controls, limits=None):
    """-> the JSON object's dict (``ok`` among its keys)."""
    import jax
    import jax.numpy as jnp

    limits = limits or LIMITS
    family = families.get(cfg["family"])
    model = family.build_model(cfg, "train")
    ids = np.random.default_rng([seed, 29]).integers(
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

    none = contextlib.nullcontext
    forms = {"sound": (model, params, none())}
    if controls:
        rotated = type(model)(dataclasses.replace(model.config,
                                                  use_rope=True))
        forms.update(
            decay_grad_dropped=(model, params,
                                chunk_backward(_decay_grad_dropped)),
            beta_unscaled=(model, params, beta_unscaled()),
            tap_dropped=(model, _tap_dropped(params), none()),
            rope_on_mla=(rotated, params, none()),
            bias_left_out=(model, _with(
                params, ("blocks", "router_bias"),
                jnp.zeros_like(params["blocks"]["router_bias"])), none()),
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
    if controls:
        out["core_float32"] = core = core_float32(cfg, seed, context)
        verdicts.append(core["sound"] <= CORE_LIMIT < core["state_bf16"])
    device = jax.devices()[0]
    return {"seed": seed, "context": context,
            "device": {"platform": device.platform,
                       "kind": device.device_kind},
            "loss_reference": float(want[0]), "limits": limits,
            "forms": out, "ok": all(verdicts)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--context", type=int, default=16384)
    ap.add_argument("--controls", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    cfg = mf.load_json(mf.config_path(mf.load_manifest(), a.config))
    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:   # as ``run.py``
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    ok = True
    for seed in a.seed:
        out = witness(cfg, seed, a.context, bool(a.controls))
        print(json.dumps({"config": a.config, **out}), flush=True)
        ok = ok and out["ok"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
