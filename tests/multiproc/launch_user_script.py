"""User training script for the launcher end-to-end test: relies ENTIRELY on
the env the launcher set (JAX coordinator/rank vars) — the reference's
'deepspeed <script>' user-side contract. ``deepspeed_tpu.initialize`` is its
FIRST call: the multi-host rendezvous must happen inside it, before anything
(a log line included) has initialised a jax backend."""

import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.simple import SimpleModel

HIDDEN = 16


def main():
    engine, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=HIDDEN, nlayers=2),
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 0})
    assert jax.process_count() == 2, jax.process_count()
    rank = jax.process_index()
    rng = np.random.RandomState(0)
    x = rng.randn(8, HIDDEN).astype(np.float32)
    y = rng.randn(8, HIDDEN).astype(np.float32)
    rows = 8 // jax.process_count()
    local = (x[rank * rows:(rank + 1) * rows], y[rank * rows:(rank + 1) * rows])
    losses = [float(engine.train_batch(local)) for _ in range(3)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]
    print(f"LAUNCH_OK {rank} {losses[-1]:.6f}", flush=True)


if __name__ == "__main__":
    main()
