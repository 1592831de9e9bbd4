"""Admission control: the request object, structured shedding, KV sizing.

The admission bound answers "how many requests may exist (queued + in
flight) before we say no" — and the honest answer comes from memory, not
from a vibes-based constant: every admitted request will eventually hold
a KV cache of ``kv_bytes_per_request`` bytes, so the bound is
``kv_budget_fraction × (HBM − params) ÷ per-request-KV`` unless the
config pins ``max_queue_depth`` explicitly (the PR 5 memory-census role,
applied to serving). Saying no is a first-class outcome: a
:class:`ShedError` carries the queue depth, the estimated wait, and a
retry-after hint, so a load balancer can back off intelligently instead
of hammering a server that already told it why.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from deepspeed_tpu.utils.logging import logger


class ShedError(RuntimeError):
    """Structured admission rejection. Not a failure of the server — the
    server protecting itself is the server working. Carries what a client
    (or load balancer) needs to act: why, how deep the queue is, how long
    the wait would have been, and when to retry."""

    def __init__(self, reason: str, queue_depth: int = 0,
                 est_wait_s: float = 0.0, retry_after_s: float = 0.0):
        self.reason = str(reason)
        self.queue_depth = int(queue_depth)
        self.est_wait_s = float(est_wait_s)
        self.retry_after_s = float(retry_after_s)
        super().__init__(
            f"request shed ({self.reason}): queue_depth={self.queue_depth}, "
            f"est_wait={self.est_wait_s:.2f}s, "
            f"retry_after={self.retry_after_s:.2f}s")


# terminal request statuses — the "no silent drops" contract: every
# admitted request ends in exactly one of these
TERMINAL_STATUSES = ("completed", "partial", "shed", "failed")


@dataclasses.dataclass
class Request:
    """One request's lifecycle record. Clients hold it after ``submit()``
    and wait on :meth:`result`; the front-end resolves it exactly once
    into a terminal status (completed / partial / shed / failed)."""

    prompt: Any                       # (1, T) int32 token ids
    max_new_tokens: int = 32
    deadline_s: float = 30.0          # budget from submission, queue wait included
    id: str = ""
    stream: Optional[Callable[[List[int]], None]] = None
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: Optional[int] = None
    seed: int = 0
    is_probe: bool = False

    # lifecycle fields, owned by the front-end
    status: str = "queued"            # queued|running|<TERMINAL_STATUSES>
    reason: str = ""
    retry_after_s: float = 0.0        # back-off hint on a resolved shed
    tokens: List[int] = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    started_at: Optional[float] = None
    prefill_done_at: Optional[float] = None   # the prefill tick returned
    first_tokens_at: Optional[float] = None   # the first token is on the host
    decode_ticks: int = 0             # decode chunks that ran to their end
    # what a block-diffusion model's block steps ran for the request
    # (``models/common.py::BLOCK_COUNTS``: passes, commits, carried, blocks),
    # read back from the cache when it completes; None: a model that emits
    # a token a step
    block_passes: Optional[Tuple[int, int, int, int]] = None
    compile_s: float = 0.0            # spent in ticks that compiled their program
    # bytes one position of one sequence holds in the cache the prefill
    # handed back, all layers, pad lanes and all (its (L, B, S, W) arrays),
    # and bytes one sequence holds there whatever its length (a KDA layer's
    # state): ``models/common.py::cache_footprint``
    cache_position_bytes: int = 0
    cache_state_bytes: int = 0
    # ... and what its window layers' rings hold, bytes a sequence whatever
    # its length, with a ring's slots (0: the model keeps none):
    # ``models/common.py::cache_ring``
    cache_window_bytes: int = 0
    cache_ring_slots: int = 0
    finished_at: Optional[float] = None
    ttft_s: Optional[float] = None    # first_tokens_at - submitted_at
    _done: threading.Event = dataclasses.field(default_factory=threading.Event,
                                               repr=False)

    @property
    def deadline_at(self) -> float:
        return self.submitted_at + self.deadline_s

    def remaining_s(self, now: Optional[float] = None) -> float:
        return self.deadline_at - (time.monotonic() if now is None else now)

    def result(self, timeout: Optional[float] = None) -> "Request":
        """Block until the request reaches a terminal status; returns self.
        Raises TimeoutError if the front-end has not resolved it in time
        (a test/client guard — the front-end itself never leaves a request
        unresolved)."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.id!r} not resolved within "
                               f"{timeout}s (status={self.status!r})")
        return self

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def to_dict(self) -> Dict[str, Any]:
        d = {"id": self.id, "status": self.status, "reason": self.reason,
             "tokens": list(self.tokens),
             "n_tokens": len(self.tokens),
             "ttft_s": self.ttft_s,
             "deadline_s": self.deadline_s,
             "latency_s": (None if self.finished_at is None
                           else self.finished_at - self.submitted_at)}
        if self.status == "shed" and self.retry_after_s:
            d["retry_after_s"] = self.retry_after_s
        return d


def kv_bytes_per_request(module, max_total_len: int) -> int:
    """KV-cache bytes ONE request holds at the serving cache size —
    computed abstractly (``jax.eval_shape`` over ``init_cache``), nothing
    allocated. This is the unit the admission bound is denominated in."""
    import jax
    import numpy as np

    shapes = jax.eval_shape(lambda: module.init_cache(1, int(max_total_len)))
    total = 0
    for leaf in jax.tree.leaves(shapes):
        total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def kv_bytes_by_kind(module, max_total_len: int) -> Dict[str, int]:
    """What of ``kv_bytes_per_request`` grows with the context and what does
    not: ``per_position`` (the rows a position of the layers that keep the
    whole context), ``per_sequence`` (a KDA layer's state, a window layer's
    ring: a sequence holds them at any length)."""
    import jax

    from deepspeed_tpu.models.common import cache_footprint, cache_ring

    shapes = jax.eval_shape(lambda: module.init_cache(1, int(max_total_len)))
    per_position, state = cache_footprint(shapes)
    return {"per_position": int(per_position),
            "per_sequence": int(state + cache_ring(shapes)[0])}


def resolve_capacity(engine, cfg) -> Tuple[int, Dict[str, Any]]:
    """The admission bound (queued + in-flight requests) and how it was
    derived. An explicit ``max_queue_depth`` wins; otherwise the bound is
    the KV budget: ``kv_budget_fraction × (HBM − params bytes)`` divided
    by the per-request KV footprint at the engine's ``max_out_tokens``."""
    import jax

    detail: Dict[str, Any] = {}
    if cfg.max_queue_depth > 0:
        detail["source"] = "max_queue_depth"
        detail["capacity"] = int(cfg.max_queue_depth)
        return int(cfg.max_queue_depth), detail

    max_len = int(engine._config.max_out_tokens)
    per_req = kv_bytes_per_request(engine.module, max_len)
    if cfg.hbm_bytes > 0:
        hbm, src = int(cfg.hbm_bytes), "config"
    else:
        # the runtime's memory_stats limit, else the peak table's capacity
        # for this device_kind (an unknown device raises — no guessed budget)
        from deepspeed_tpu.accelerator import get_accelerator

        hbm, src = get_accelerator().hbm_bytes(), "device"
    params_bytes = sum(int(x.nbytes) for x in jax.tree.leaves(engine.params))
    budget = max(0, hbm - params_bytes) * float(cfg.kv_budget_fraction)
    cap = max(1, int(budget // max(1, per_req)))
    detail.update({"source": f"kv_budget({src})", "capacity": cap,
                   "hbm_bytes": hbm, "params_bytes": params_bytes,
                   "kv_bytes_per_request": per_req,
                   "kv_bytes": kv_bytes_by_kind(engine.module, max_len),
                   "kv_budget_fraction": float(cfg.kv_budget_fraction),
                   "max_total_len": max_len})
    logger.info(f"serving admission: capacity={cap} requests "
                f"({per_req / 1e6:.1f}MB KV each at {max_len} tokens, "
                f"budget {budget / 1e9:.2f}GB of {hbm / 1e9:.2f}GB HBM)")
    return cap, detail
