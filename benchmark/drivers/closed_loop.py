"""``closed_loop``: N callers, each a thread that submits its next request
the moment its last one resolves. The queue's depth is fixed by
construction (N - 1 waiting while one is served), so a stall costs the
requests in flight and nobody else.

Warm-up (outside the window): every prompt length the mix uses once, then
the loop itself runs until each caller has completed
``warmup_requests_per_caller`` requests; the window opens at the instant
the last of those resolved (read by that caller, so it is an event of the
run and not of a polling thread) and lasts ``seconds``. Callers stop submitting at its end and what is in flight is
drained outside the timing. Latency samples are taken by the readers from
requests that COMPLETE inside the window.
"""

import threading
import time

from benchmark import traffic as gen

GAP_LABELS = [("tick", "in_tick"), ("request", "between_ticks"),
              ("call", "between_requests")]
GAP_DEFAULT = "caller_idle"
RESULT_TIMEOUT_S = 600.0


def serve_one(system, item, rec, clock, t_ref=None):
    """Submit one request, wait for it, return its record. ``t_ref`` is the
    instant latency counts from: the ``submit()`` call on the caller's clock
    (closed loop) or the time the request was DUE (open loop)."""
    stamps = []
    t_submit = clock()
    out = {"caller": item["caller"], "seq": item["seq"],
           "prompt_len": item["prompt_len"], "new_tokens": item["new_tokens"],
           "sentinel": item["sentinel"], "t_submit": t_submit,
           "t_ref": t_submit if t_ref is None else t_ref,
           "stamps": stamps, "status": "refused", "n_tokens": 0,
           "queue_wait": None, "tokens": None}
    try:
        with rec.span("call", caller=item["caller"]):
            handle = system.submit(
                item["prompt"], item["new_tokens"],
                lambda toks: stamps.append((clock(), len(toks))))
            handle.result(RESULT_TIMEOUT_S)
    except Exception as e:      # noqa: BLE001 - a refusal is a failed request
        out["status"] = f"refused: {type(e).__name__}: {e}"
        out["t_done"] = clock()
        return out
    out["t_done"] = clock()
    out["status"] = handle.status
    out["n_tokens"] = len(handle.tokens)
    if handle.started_at is not None:
        out["queue_wait"] = handle.started_at - handle.submitted_at
    if item["sentinel"]:
        out["tokens"] = list(handle.tokens)
    return out


def request_ok(r):
    return r["status"] == "completed" and r["n_tokens"] == r["new_tokens"] \
        and sum(n for _, n in r["stamps"]) == r["new_tokens"]


def sentinels_agree(requests):
    """Equal prompts must give equal tokens: a caller's sentinel prompts of
    one length share their ids, so their outputs must agree on the common
    prefix, every time."""
    seen = {}
    n = 0
    for r in requests:
        if not r["sentinel"] or r["tokens"] is None:
            continue
        key = (r["caller"], r["prompt_len"])
        ref = seen.setdefault(key, r["tokens"])
        k = min(len(ref), len(r["tokens"]))
        if ref[:k] != r["tokens"][:k]:
            return False, n
        if len(r["tokens"]) > len(ref):
            seen[key] = r["tokens"]
        n += 1
    return True, n


def finish(requests, t_start, t_end):
    """The record's common tail for both serving drivers."""
    in_window = [r for r in requests if t_start <= r["t_submit"] <= t_end]
    failed = [r for r in in_window if not request_ok(r)]
    agree, n_sentinel = sentinels_agree(requests)
    return {"kind": "serve", "t_start": t_start, "t_end": t_end,
            "requests": requests, "attempted": len(in_window),
            "failed": len(failed), "correct": agree and not failed,
            "notes": {"sentinels_compared": n_sentinel,
                      "first_failure": failed[0]["status"] if failed else None}}


def warm_shapes(system, traffic):
    """Every prompt length once; the first with two ticks, because the
    decode chunk specialises once on prefill's outputs and once on its
    own."""
    tick = int(system.tick_tokens)
    for i, plen in enumerate(gen.prompt_lengths(traffic)):
        system.warm(plen, 2 * tick if i == 0 else tick)


def run(system, traffic, seed, seconds, rec, tracer=None,
        clock=time.monotonic):
    warm_shapes(system, traffic)
    callers = traffic["callers"]
    warm_n = int(traffic.get("warmup_requests_per_caller", 2))
    every = int(traffic.get("sentinel_every", 4))
    stop, opened, lock = threading.Event(), threading.Event(), threading.Lock()
    done = [[] for _ in callers]
    opened_at = []

    def caller_loop(i):
        for item in gen.caller_plan(callers[i], i, seed, system.vocab, every):
            if stop.is_set():
                return
            r = serve_one(system, item, rec, clock)
            with lock:
                done[i].append(r)
                if not opened_at and all(len(d) >= warm_n for d in done):
                    opened_at.append(r["t_done"])
                    opened.set()
            if r["status"] != "completed":
                time.sleep(0.01)        # a refusing server must not spin us

    threads = [threading.Thread(target=caller_loop, args=(i,),
                                name=f"bench-caller-{c['name']}", daemon=True)
               for i, c in enumerate(callers)]
    for t in threads:
        t.start()
    while not opened.wait(0.05):
        if not any(t.is_alive() for t in threads):
            raise SystemExit("benchmark: callers died during warm-up")
    t_start = opened_at[0]
    t_end = t_start + seconds
    if tracer is not None:
        tracer.start()
    while clock() < t_end:
        time.sleep(0.002)
        if tracer is not None:
            tracer.stop_if_due(clock())
    stop.set()
    if tracer is not None:
        tracer.stop()
    for t in threads:
        t.join(RESULT_TIMEOUT_S)
    if any(t.is_alive() for t in threads):
        raise SystemExit("benchmark: a caller did not finish draining")
    requests = sorted((r for d in done for r in d), key=lambda r: r["t_submit"])
    return finish(requests, t_start, t_end)
