"""``train_stream``: one training job fed a fresh host batch every step.

Each step is ``system.step(batch)``, which ends in a host read of the loss
(``float(loss)``), so a step's end stamp is after the device finished it.
The window opens at the end of the last warm-up step and the job runs
until a step starts after ``seconds``; the step in flight at the end is
finished outside the timing.
"""

import math
import time

from benchmark import traffic as gen

# what the host was in, for the trace's idle gaps between program runs
GAP_LABELS = [("feed_batch", "feed_batch"), ("train_step", "in_step")]
GAP_DEFAULT = "between_steps"


def run(system, traffic, seed, seconds, rec, tracer=None,
        clock=time.monotonic):
    next_batch = gen.zipf_batch_source(seed, system.vocab,
                                       traffic.get("zipf_offset", 10.0))
    B, T = system.global_batch, int(traffic["seq_len"])
    for _ in range(int(traffic.get("warmup_steps", 3))):
        loss = system.step(next_batch(B, T))
        if not math.isfinite(loss):
            raise SystemExit(f"benchmark: warm-up loss is {loss}")
    t_start = clock()
    t_end = t_start + seconds
    if tracer is not None:
        tracer.start()
    steps = []
    while True:
        t0 = clock()
        if t0 >= t_end:
            break
        with rec.span("feed_batch"):
            batch = next_batch(B, T)
        with rec.span("train_step", step=len(steps)):
            loss = system.step(batch)
        steps.append({"t0": t0, "t1": clock(), "loss": loss,
                      "tokens": B * T})
        if tracer is not None:
            tracer.stop_if_due(clock())
    if tracer is not None:
        tracer.stop()
    inside = [s for s in steps if s["t1"] <= t_end]
    losses = [s["loss"] for s in inside]
    q = max(1, len(losses) // 4)
    fell = len(losses) >= 2 and \
        sum(losses[-q:]) / q < sum(losses[:q]) / q
    bad = [s for s in steps if not math.isfinite(s["loss"])]
    return {"kind": "train", "t_start": t_start, "t_end": t_end,
            "steps": steps, "attempted": len(steps), "failed": len(bad),
            "correct": fell and not bad,
            "notes": {"loss_first_quarter": sum(losses[:q]) / q if losses else None,
                      "loss_last_quarter": sum(losses[-q:]) / q if losses else None}}
