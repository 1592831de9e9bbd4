#!/usr/bin/env python3
"""chip_smoke — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the published widths of gpt2-760m (n_embd 1536, 16 heads x 96, 24 layers,
vocab 50257, seq 1024, bf16, random weights from a seed):

* train: ``deepspeed_tpu.initialize`` -> ``engine.train_batch`` x STEPS, a
  fresh host batch each step, AdamW + gradient clipping, flash attention,
  remat "attn". ZeRO stage 1 on one chip; on N > 1 local chips stage 3 over
  ``data = N``.
* serve: ``deepspeed_tpu.init_inference`` -> ``serving.from_ds_config`` ->
  ``ServingFrontEnd.submit`` -> ``begin_drain``/``drain``. On N > 1 chips the
  server is ONE engine at ``tp_size = N`` (heads and MLP columns split over
  the 'tensor' axis), not N one-chip replicas: sharding one model across
  the chips of a host is the path that had never run on real chips.

Every check is fatal and nothing here catches an exception: a failed phase
is a traceback and a non-zero exit. With no accelerator it fails at the
first check and prints no result. One process, no children; the numbers it
prints are information for the benchmark PR, not claims.

    python3 chip_smoke.py        # on a TPU host, from the repo root
"""

import dataclasses
import gc
import importlib.metadata
import json
import re
import statistics
import sys
import time

import numpy as np

MODEL = "gpt2-760m"
SEQ = 1024
# per-chip micro-batch. The compiled step at 8 x 1024 needs 14.6 GB of the
# v5e's 15.75 GB (XLA memory_analysis of this exact program, jax 0.9.0);
# 12 — what round 5 ran on jax 0.4.37 — needs 16.3 GB and no longer fits.
MICRO = 8
STEPS = 6
NEW_TOKENS = 48                      # three decode ticks of 16
PROMPT_LENS = (37, 601, 256, 970)    # short | > 512 and odd | mid | near limit
FLASH_TOL = 2e-2                     # bf16 outputs of O(1): ~3 ulp at 2^-8
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
               "collective-permute")


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok, what):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED — {what}")
    say(f"ok: {what}")


def compiled_text(label):
    """Post-partitioning HLO of a program the run dispatched, re-lowered
    from the abstract arguments ``sharded_jit`` captured at that dispatch."""
    from deepspeed_tpu.sharding import program_table

    rec = program_table()[label]
    with rec.mesh:
        return rec.jitted.lower(*rec.abstract_args,
                                **(rec.abstract_kwargs or {})).compile().as_text()


def batch_source(seed, vocab):
    """``next_batch(b, t)`` drawing fresh host batches from a seeded, skewed
    (Zipf-like) unigram distribution: enough structure that the loss must
    fall within a few steps."""
    rng = np.random.default_rng(seed)
    p = 1.0 / (np.arange(vocab) + 10.0)
    p /= p.sum()
    return lambda b, t: rng.choice(vocab, size=(b, t), p=p).astype(np.int32)


def check_flash_kernel(cfg):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.flash_attention import (flash_attention,
                                                          mha_reference)

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (2, SEQ, cfg.n_head, cfg.head_dim),
                                 jnp.bfloat16) for kk in keys)
    out = jax.jit(flash_attention)(q, k, v)
    ref = jax.jit(mha_reference)(q, k, v)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    check(bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
          and err <= FLASH_TOL,
          f"flash kernel (T {SEQ}, {cfg.n_head}x{cfg.head_dim}, bf16) vs "
          f"mha_reference: max abs err {err:.4f} <= {FLASH_TOL}")


def train_phase(cfg, n_dev):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Model

    stage = 1 if n_dev == 1 else 3
    t0 = time.perf_counter()
    engine, *_ = deepspeed_tpu.initialize(
        model=GPT2Model(dataclasses.replace(cfg, remat="attn")),
        config={"train_micro_batch_size_per_gpu": MICRO,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "AdamW",
                              "params": {"lr": 3e-4, "weight_decay": 0.01}},
                "bf16": {"enabled": True},
                "gradient_clipping": 1.0,
                "zero_optimization": {"stage": stage},
                "tpu": {"data": n_dev},
                "steps_per_print": 0})
    say(f"train: initialize {time.perf_counter() - t0:.1f}s — ZeRO-{stage}, "
        f"mesh data={n_dev}, micro-batch {MICRO}/chip, global batch "
        f"{engine.train_batch_size()}, {cfg.n_layer} layers (depth not cut)")
    check(engine.mesh.size == n_dev and engine.mesh.shape["data"] == n_dev,
          f"the train mesh uses every chip (data={n_dev})")

    next_batch = batch_source(seed=1, vocab=cfg.vocab_size)
    losses, walls = [], []
    for step in range(STEPS):
        batch = {"input_ids": next_batch(engine.train_batch_size(), SEQ)}
        t0 = time.perf_counter()
        loss = float(engine.train_batch(batch))   # host read ends the step
        walls.append(time.perf_counter() - t0)
        losses.append(loss)
        say(f"train: step {step} loss {loss:.4f} wall {walls[-1]:.2f}s")
    check(all(np.isfinite(losses)), f"loss finite at all {STEPS} steps")
    check(losses[-1] < losses[0],
          f"loss fell: {losses[0]:.4f} -> {losses[-1]:.4f}")

    text = compiled_text("engine/train_batch[gas=1]")
    n_mosaic = text.count("tpu_custom_call")
    check(n_mosaic > 0, f"compiled train step holds {n_mosaic} Mosaic calls "
                        "(the flash kernel, not the einsum)")
    colls = {c: len(re.findall(rf"\b{c}(?:-start)?\(", text))
             for c in COLLECTIVES}
    say(f"train: collectives in the compiled step: {colls}")

    if n_dev > 1:
        fp32 = [x for x in jax.tree.leaves((engine.state.master,
                                            engine.state.opt_state))
                if x.ndim >= 1]
        total = sum(x.nbytes for x in fp32)
        held = {d.id: 0 for d in jax.local_devices()}
        for x in fp32:
            for s in x.addressable_shards:
                held[s.device.id] += s.data.nbytes
        check(all(b * n_dev == total for b in held.values()),
              f"each of {n_dev} chips holds 1/{n_dev} of the fp32 master + "
              f"optimizer state ({total / n_dev / 2**30:.2f} of "
              f"{total / 2**30:.2f} GiB)")
    in_use = {d.id: d.memory_stats()["bytes_in_use"]
              for d in jax.local_devices()}
    check(all(b > 0 for b in in_use.values()),
          "every chip reports bytes_in_use > 0: "
          + ", ".join(f"chip{i} {b / 2**30:.2f} GiB"
                      for i, b in in_use.items()))

    steady = statistics.median(walls[2:])
    tokens = engine.train_batch_size() * SEQ
    say(f"train: first step {walls[0]:.1f}s (compile ~"
        f"{walls[0] - steady:.1f}s); steady step {steady:.3f}s (median of "
        f"steps 2-{STEPS - 1}); {tokens / steady:,.0f} tokens/s over "
        f"{n_dev} chip(s)")
    engine.state = None          # hand the HBM to the serving phase


def serve_phase(cfg, n_dev):
    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.gpt2 import GPT2Model
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    t0 = time.perf_counter()
    engine = deepspeed_tpu.init_inference(
        GPT2Model(cfg), dtype="bf16", max_out_tokens=SEQ,
        tensor_parallel={"tp_size": n_dev})
    check(engine.mesh.shape["tensor"] == n_dev,
          f"the serving mesh is tensor={n_dev} (tp_size={n_dev})")
    # generous deadlines: the first wave queues four cold compiles
    front = serving.from_ds_config(engine, DeepSpeedConfig(
        {"serving": {"default_deadline_s": 900.0}}))
    say(f"serve: init_inference + front-end {time.perf_counter() - t0:.1f}s")

    rng = np.random.default_rng(2)

    def wave(name):
        """Submit one request per prompt length, wait for all, check each;
        -> {prompt_len: (seconds of service until the first decode tick's
        tokens reached the client, seconds per token in the last tick)}."""
        stamps = {n: [] for n in PROMPT_LENS}
        reqs = [front.submit(
            rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32),
            max_new_tokens=NEW_TOKENS,
            stream=lambda toks, s=stamps[n]: s.append(time.monotonic()))
            for n in PROMPT_LENS]
        out = {}
        for n, req in zip(PROMPT_LENS, reqs):
            req.result(timeout=900.0)
            check(req.status == "completed" and len(req.tokens) == NEW_TOKENS
                  and all(0 <= t < cfg.vocab_size for t in req.tokens),
                  f"{name} request, prompt {n}: status {req.status!r} "
                  f"{req.reason!r}, {len(req.tokens)}/{NEW_TOKENS} tokens")
            out[n] = (req.ttft_s - (req.started_at - req.submitted_at),
                      (stamps[n][-1] - stamps[n][-2])
                      / front.cfg.decode_tick_tokens)
        return out

    cold, warm = wave("cold"), wave("warm")
    for n in PROMPT_LENS:
        say(f"serve: prompt {n}: prefill + first {front.cfg.decode_tick_tokens}"
            f"-token tick {warm[n][0] * 1e3:.0f} ms warm, {cold[n][0]:.2f}s "
            f"cold (compile ~{cold[n][0] - warm[n][0]:.1f}s); decode "
            f"{warm[n][1] * 1e3:.2f} ms/token")

    n_mosaic = compiled_text("serving/prefill").count("tpu_custom_call")
    check(n_mosaic > 0, f"compiled prefill holds {n_mosaic} Mosaic call(s)")

    front.begin_drain("shutdown")
    code = front.drain(timeout=60.0)
    check(code == 0 and front.state == "dead",
          "front-end drained: worker exited, state dead")


def main():
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no accelerator — jax.devices()[0].platform is "
            f"{dev.platform!r}, need 'tpu'. Send this script through the "
            "chip tool.")
    from deepspeed_tpu.analysis import chips
    from deepspeed_tpu.models.gpt2 import PRESETS

    chip = chips.resolve_chip(chips.detect_chip_name(dev.device_kind,
                                                     dev.platform))
    n_dev = len(devices)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev}
    say(f"platform: {dev.platform}  device_kind: {dev.device_kind}  "
        f"count: {n_dev}  (peak table row {chip.name}: "
        f"{chip.peak_flops / 1e12:.0f} TFLOP/s bf16, "
        f"{chip.hbm_bytes_per_s / 1e9:.0f} GB/s)  jax {jax.__version__} "
        f"jaxlib {importlib.metadata.version('jaxlib')} "
        f"libtpu {importlib.metadata.version('libtpu')}")
    cfg = PRESETS[MODEL]
    say(f"model: {MODEL} n_embd {cfg.n_embd}, {cfg.n_head} heads x "
        f"{cfg.head_dim}, {cfg.n_layer} layers, vocab {cfg.vocab_size}, "
        f"seq {SEQ}")

    t_start = time.perf_counter()
    check_flash_kernel(cfg)
    train_phase(cfg, n_dev)
    gc.collect()
    serve_phase(cfg, n_dev)
    say(f"all phases passed in {time.perf_counter() - t_start:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
