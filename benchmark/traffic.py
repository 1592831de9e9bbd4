"""The general traffic generators. Pure numpy: no jax, no program code.

A traffic mix is a data file (``traffic/<name>.json``) of parameters; these
functions turn it and ``--seed`` into the inputs. The same seed gives the
same inputs; the seed moves token ids and orders, never the amount of work.
"""

import itertools

import numpy as np


def zipf_batch_source(seed, vocab, offset=10.0):
    """``next_batch(b, t)``: fresh host batches from a seeded Zipf-like
    unigram distribution, p(i) ~ 1/(i + offset) — enough structure that the
    loss must fall within a few steps. Copied from ``chip_smoke.py``'s
    ``batch_source`` (the original stays there; see PERF.md)."""
    rng = np.random.default_rng(seed)
    p = 1.0 / (np.arange(vocab) + float(offset))
    p /= p.sum()
    return lambda b, t: rng.choice(vocab, size=(b, t), p=p).astype(np.int32)


def _shapes(caller, rng):
    """Endless (prompt_len, new_tokens) pairs of one caller's layout.

    ``cycle``: request i takes ``prompt_lens[i % n]`` and ``new_tokens[i %
    m]`` — fixed, the seed moves nothing. ``balanced_blocks``: blocks that
    each hold every pairing of a prompt length with a token count once, in
    a seeded order within the block, so every window of a whole number of
    blocks holds exactly the same work whatever the seed."""
    plens, news = caller["prompt_lens"], caller["new_tokens"]
    layout = caller.get("layout", "cycle")
    if layout == "cycle":
        for i in itertools.count():
            yield plens[i % len(plens)], news[i % len(news)]
    elif layout == "balanced_blocks":
        block = list(itertools.product(plens, news))
        while True:
            for j in rng.permutation(len(block)):
                yield block[j]
    else:
        raise ValueError(f"traffic: unknown caller layout {layout!r}")


def caller_plan(caller, index, seed, vocab, sentinel_every):
    """Endless request dicts for one closed-loop caller.

    Every ``sentinel_every``-th request (the first included) carries the
    caller's SENTINEL ids — one fixed seeded sequence cut to the prompt
    length — so equal prompts recur and must return equal tokens. All
    other prompts are fresh seeded ids. Work per request depends on the
    lengths only, so sentinels do not disturb the layout."""
    rng = np.random.default_rng([int(seed), 7919, int(index)])
    sentinel = rng.integers(0, vocab, size=max(caller["prompt_lens"]),
                            dtype=np.int32)
    for i, (plen, new) in enumerate(_shapes(caller, rng)):
        is_sentinel = sentinel_every > 0 and i % sentinel_every == 0
        ids = sentinel[:plen] if is_sentinel else \
            rng.integers(0, vocab, size=plen, dtype=np.int32)
        yield {"caller": caller["name"], "seq": i, "prompt": ids,
               "prompt_len": int(plen), "new_tokens": int(new),
               "sentinel": is_sentinel}


def prompt_lengths(traffic):
    """Every distinct prompt length a serving mix uses (what to warm)."""
    if "callers" in traffic:
        return sorted({p for c in traffic["callers"] for p in c["prompt_lens"]})
    return sorted({s[0] for s in traffic["shapes"]})


def open_loop_schedule(traffic, seed, seconds, vocab):
    """Arrivals of an open loop: a list of request dicts with a ``due``
    offset (seconds from the window start), sorted by it.

    ``arrivals.process``: ``poisson`` (exponential gaps at ``rate_per_s``)
    or ``burst`` (every ``burst_every_s`` seconds ``burst_size`` requests
    fall due at once, on top of a Poisson floor at ``rate_per_s``).
    ``shapes`` is a list of [prompt_len, new_tokens, weight]: each arrival
    draws one, seeded."""
    rng = np.random.default_rng([int(seed), 104729])
    arr = traffic["arrivals"]
    rate = float(arr["rate_per_s"])
    due = []
    t = 0.0
    while rate > 0:
        t += rng.exponential(1.0 / rate)
        if t >= seconds:
            break
        due.append(t)
    if arr["process"] == "burst":
        every, size = float(arr["burst_every_s"]), int(arr["burst_size"])
        for k in range(1, int(seconds / every) + 1):
            if k * every < seconds:
                due.extend([k * every] * size)
    elif arr["process"] != "poisson":
        raise ValueError(f"traffic: unknown arrival process {arr['process']!r}")
    due.sort()
    shapes = traffic["shapes"]
    w = np.array([s[2] for s in shapes], dtype=float)
    picks = rng.choice(len(shapes), size=len(due), p=w / w.sum())
    return [{"caller": "open", "seq": i, "due": float(d),
             "prompt": rng.integers(0, vocab, size=shapes[j][0], dtype=np.int32),
             "prompt_len": int(shapes[j][0]), "new_tokens": int(shapes[j][1]),
             "sentinel": False}
            for i, (d, j) in enumerate(zip(due, picks))]
