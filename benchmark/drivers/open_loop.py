"""``open_loop``: arrivals on a seeded schedule, whether or not earlier
requests have finished (independent users). One dispatcher thread sleeps
until each request is due and starts a short-lived thread that submits it
and waits; latency is counted from the time the request was DUE, so a
stall is charged to everyone it delays, and how late the generator itself
ran is in every record (``late_s``).

No first cell uses it (PERF.md says why); it ships so that a PR which
claims a gain in an open-loop cell adds data only.
"""

import threading
import time

from benchmark import traffic as gen
from benchmark.drivers import closed_loop

GAP_LABELS = closed_loop.GAP_LABELS
GAP_DEFAULT = "wait_arrival"


def run(system, traffic, seed, seconds, rec, tracer=None,
        clock=time.monotonic, sleep=time.sleep):
    closed_loop.warm_shapes(system, traffic)
    schedule = gen.open_loop_schedule(traffic, seed, seconds, system.vocab)
    requests, threads = [], []
    lock = threading.Lock()

    def one(item, due_at, late):
        r = closed_loop.serve_one(system, item, rec, clock, t_ref=due_at)
        r["late_s"] = late
        with lock:
            requests.append(r)

    t_start = clock()
    t_end = t_start + seconds
    if tracer is not None:
        tracer.start()
    for item in schedule:
        due_at = t_start + item["due"]
        while True:
            now = clock()
            if now >= due_at:
                break
            sleep(min(due_at - now, 0.005))
            if tracer is not None:
                tracer.stop_if_due(clock())
        t = threading.Thread(target=one, args=(item, due_at, now - due_at),
                             name=f"bench-open-{item['seq']}", daemon=True)
        t.start()
        threads.append(t)
    while clock() < t_end:
        sleep(0.002)
        if tracer is not None:
            tracer.stop_if_due(clock())
    if tracer is not None:
        tracer.stop()
    for t in threads:
        t.join(closed_loop.RESULT_TIMEOUT_S)
    if any(t.is_alive() for t in threads):
        raise SystemExit("benchmark: a request did not finish draining")
    requests.sort(key=lambda r: r["t_ref"])
    return closed_loop.finish(requests, t_start, t_end)
