"""The traffic generators: layouts that make every seed the same work."""

import collections
import itertools

import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import traffic as gen

CHAT = mf.load_json(mf.traffic_path("serve.chat.c4"))
DOC = mf.load_json(mf.traffic_path("serve.doc.c1"))
VOCAB = 50257


def take(caller, i, seed, n, every=4):
    return list(itertools.islice(
        gen.caller_plan(caller, i, seed, VOCAB, every), n))


@pytest.mark.parametrize("i,name,plens,new", [
    (0, "A", [32, 64], 64), (1, "B", [96, 160], 128),
    (2, "C", [192, 320], 128), (3, "D", [384, 512], 192)])
def test_chat_callers_alternate_their_two_prompt_lengths(i, name, plens, new):
    caller = CHAT["callers"][i]
    assert caller["name"] == name
    items = take(caller, i, seed=5, n=10)
    assert [x["prompt_len"] for x in items] == plens * 5
    assert all(x["new_tokens"] == new for x in items)
    assert all(len(x["prompt"]) == x["prompt_len"] for x in items)


def test_chat_middle_callers_share_an_output_length():
    by = {c["name"]: c for c in CHAT["callers"]}
    assert by["B"]["new_tokens"] == by["C"]["new_tokens"] == [128]
    assert len(gen.prompt_lengths(CHAT)) == 8       # eight prefills to warm


@pytest.mark.parametrize("seed", range(5))
def test_doc_blocks_hold_every_pairing_once(seed):
    items = take(DOC["callers"][0], 0, seed, 45, every=5)
    want = set(itertools.product([512, 640, 768, 896, 960], [16, 32, 48]))
    for b in range(3):
        block = {(x["prompt_len"], x["new_tokens"])
                 for x in items[15 * b:15 * b + 15]}
        assert block == want
    assert gen.prompt_lengths(DOC) == [512, 640, 768, 896, 960]


def test_doc_order_within_a_block_depends_on_the_seed_only():
    order = lambda seed: [(x["prompt_len"], x["new_tokens"])
                          for x in take(DOC["callers"][0], 0, seed, 30)]
    assert order(1) == order(1)
    assert order(1) != order(2)


def test_the_seed_moves_token_ids_not_lengths():
    a = take(CHAT["callers"][2], 2, seed=1, n=6)
    b = take(CHAT["callers"][2], 2, seed=2, n=6)
    assert [x["prompt_len"] for x in a] == [x["prompt_len"] for x in b]
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))
    again = take(CHAT["callers"][2], 2, seed=1, n=6)
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, again))


def test_sentinels_recur_with_equal_ids_per_prompt_length():
    items = take(CHAT["callers"][1], 1, seed=3, n=17, every=4)
    sent = [x for x in items if x["sentinel"]]
    assert [x["seq"] for x in sent] == [0, 4, 8, 12, 16]
    by_len = collections.defaultdict(list)
    for x in sent:
        by_len[x["prompt_len"]].append(x["prompt"])
    for group in by_len.values():
        assert all((g == group[0]).all() for g in group)
    fresh = [x for x in items if not x["sentinel"] and x["prompt_len"] == 96]
    assert not (fresh[0]["prompt"] == fresh[1]["prompt"]).all()


def test_callers_draw_different_ids():
    a = take(CHAT["callers"][0], 0, seed=1, n=1)[0]["prompt"]
    b = take(dict(CHAT["callers"][0]), 1, seed=1, n=1)[0]["prompt"]
    assert (a != b).any()


def test_unknown_layout_is_an_error():
    with pytest.raises(ValueError):
        take({"name": "x", "layout": "nope", "prompt_lens": [8],
              "new_tokens": [8]}, 0, 0, 1)


def test_zipf_stream_is_seeded_skewed_and_fresh_every_batch():
    a, b = gen.zipf_batch_source(7, VOCAB), gen.zipf_batch_source(7, VOCAB)
    x1, x2 = a(8, 1024), a(8, 1024)
    assert x1.shape == (8, 1024) and x1.dtype == np.int32
    assert (x1 == b(8, 1024)).all() and (x1 != x2).any()
    assert (gen.zipf_batch_source(8, VOCAB)(8, 1024) != x1).any()
    assert 0 <= x1.min() and x1.max() < VOCAB
    # p(i) ~ 1/(i+10): the first 100 ids carry ~28% of the mass
    assert 0.2 < (x1 < 100).mean() < 0.36


POISSON = {"arrivals": {"process": "poisson", "rate_per_s": 8.0},
           "shapes": [[64, 32, 3], [512, 128, 1]]}


def test_open_loop_poisson_schedule():
    s = gen.open_loop_schedule(POISSON, seed=1, seconds=50.0, vocab=VOCAB)
    due = [x["due"] for x in s]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 50.0
    assert 340 <= len(s) <= 460                     # 400 +- 3 sigma
    gaps = np.diff(due)
    assert 0.8 < gaps.std() / gaps.mean() < 1.2     # exponential gaps
    short = sum(x["prompt_len"] == 64 for x in s) / len(s)
    assert 0.68 < short < 0.82
    again = gen.open_loop_schedule(POISSON, seed=1, seconds=50.0, vocab=VOCAB)
    assert [x["due"] for x in again] == due
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(s, again))
    assert gen.prompt_lengths(POISSON) == [64, 512]


def test_open_loop_bursts_fall_due_together():
    t = {"arrivals": {"process": "burst", "rate_per_s": 1.0,
                      "burst_every_s": 5.0, "burst_size": 6},
         "shapes": [[64, 32, 1]]}
    s = gen.open_loop_schedule(t, seed=2, seconds=21.0, vocab=VOCAB)
    counts = collections.Counter(x["due"] for x in s)
    assert [counts[d] for d in (5.0, 10.0, 15.0, 20.0)] == [6] * 4
    with pytest.raises(ValueError):
        gen.open_loop_schedule({"arrivals": {"process": "x", "rate_per_s": 1},
                                "shapes": [[8, 8, 1]]}, 0, 1.0, VOCAB)
