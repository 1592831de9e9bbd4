"""MiMo-V2-Flash's share (``benchmark/families/mimo_v2_flash.py``) at a small
size on the CPU: the family's plain reference against the program's model
(``models/llama.py`` with window layers that hold a mixer, a sink and a ring
cache of their own, q.k wider than v, a partial rotary embedding with a base
a kind, a sigmoid router with a selection bias and a share of the experts)
on the same seeded weights: the loss, the logits, prefill then decode THROUGH
a ring that wraps, the shares' parts, the resolutions of the router's
near-ties, what the reference refuses, the published configuration against
its catalog row (copied here: no file outside the repository is read), the
counts, the readers of the four metric files, the tiny configuration through
the manifest checks, ``run.execute`` and the witness."""

import copy
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import families
from benchmark import manifest as mf
from benchmark import run
from tests.benchmark import rehearsal, test_manifest
from tests.benchmark.test_reference import perturbed

DATA = rehearsal.DATA
# float32 at "highest" on both sides: what is left is the order of the sums
# (measured 2e-7 to 6e-7 on logits that spread by 2.5). A ring written one
# slot off moves a logit by 1e-2 and more, a missing sink or rotary width by
# 1e-1 (below).
TOL_PROGRAM = 2e-5
REAL_CELL = "mimo-v2-flash.serve.doc24k.c1"
METRICS = ("serve.window_bytes_per_sequence", "ttft.win_flash_roofline",
           "ttft.full_flash_roofline", "tpot.ring_decode_attn_roofline")


def case():
    """``mimo-tiny.json``, the program's model built through the family
    module and put into float32, seeded weights with every gain moved off 1
    and q scaled so that the scores spread (at a width of 64 they are all
    alike and no position matters), ids."""
    cfg = mf.load_json(DATA / "mimo-tiny.json")
    ref = families.get("mimo_v2_flash")
    model = ref.build_model(cfg, "serve")
    model.config = dataclasses.replace(
        model.config, dtype=jnp.float32, param_dtype=jnp.float32,
        use_flash_attention=False, remat=False)
    params = perturbed(model.init_params(jax.random.PRNGKey(4)), 5)
    for stack in ("attn_blocks", "win_blocks", "dense_blocks"):
        params[stack]["q_w"] = params[stack]["q_w"] * 8.0
    params["lm_head"] = params["lm_head"] * 8.0
    ids = np.random.default_rng(6).integers(0, 250, size=48, dtype=np.int32)
    return types.SimpleNamespace(ref=ref, cfg=cfg, model=model, params=params,
                                 ids=ids)


@pytest.fixture(scope="module")
def tiny():
    return case()


@pytest.fixture(scope="module")
def plain(tiny):
    return jax.jit(lambda p, ids: tiny.ref.reference_forward(
        p, ids, tiny.cfg))(tiny.params, tiny.ids)


def test_the_tiny_file_has_the_mechanisms_on(tiny):
    c, z = tiny.model.config, tiny.ref._sizes(tiny.cfg)
    assert z.kinds == ("attn", "win", "win", "win", "win", "attn", "win")
    assert (z.dense, z.kv, z.win_kv, z.dh, z.dv, z.rot, z.window) == (
        1, 2, 4, 24, 16, 8, 8)
    assert (z.held, z.first, z.router, z.top_k) == (4, 4, 16, 4)
    assert c.own_window and c.window_sink and c.router_bias
    assert (c.rope_theta, c.window_rope_theta, c.value_scale) == (
        5e6, 1e4, 0.707)
    assert c.experts_held == (4, 4) and c.router_scoring == "sigmoid"
    assert "sink" in tiny.params["win_blocks"]
    with pytest.raises(SystemExit, match="served only"):
        tiny.ref.build_model(tiny.cfg, "train")


def test_loss_and_logits_match_the_reference(tiny, plain):
    """(a) the trunk (``loss`` / ``apply``: the einsum on the CPU, every
    window layer under its mask and beside its sink) against the reference."""
    c = tiny
    with jax.default_matmul_precision("highest"):
        got = c.model.apply(c.params, c.ids[None])[0]
        loss = c.model.loss(c.params, {"input_ids": c.ids[None]})
    want = plain[0]
    assert float(jnp.std(want)) > 0.5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL_PROGRAM, rtol=0)
    assert abs(float(loss) - float(c.ref.reference_loss(
        c.params, c.ids, c.cfg))) < TOL_PROGRAM


@pytest.mark.parametrize("prompt", [6, 20])
def test_prefill_then_decode_through_a_wrapping_ring_match_the_reference(
        tiny, plain, prompt):
    """(b) the cached walk: a prompt shorter and longer than the ring of 8,
    then one position at a time to 47: the ring wraps five times."""
    c = tiny
    with jax.default_matmul_precision("highest"):
        logits, cache = jax.jit(c.model.prefill)(
            c.params, c.ids[None, :prompt], c.model.init_cache(1, 64))
        got = [logits[0]]
        step = jax.jit(c.model.decode_step)
        for t in range(prompt, 47):
            logits, cache = step(c.params, c.ids[None, t], cache)
            got.append(logits[0])
    np.testing.assert_allclose(np.asarray(jnp.stack(got)),
                               np.asarray(plain[0][prompt - 1:47]),
                               atol=TOL_PROGRAM, rtol=0)
    assert cache["win_k"].shape == (5, 1, 8, 128)


def test_the_shares_parts_add_up_to_the_uncut_layer(tiny):
    """(c) Four shares of four experts each (a 16-wide router): the routed
    parts of the shares — the reference's ``_experts`` and the program's
    ``routed_mlp(first=)``, share s holding experts 4s .. 4s + 3 of the uncut
    leaves — add up to what ONE chip holding all sixteen computes; nothing
    is shared (no shared expert), so nothing is counted once."""
    from deepspeed_tpu.moe.dropless import route_topk, routed_mlp

    c = tiny
    whole_cfg = copy.deepcopy(c.cfg)
    whole_cfg["model"]["n_routed_experts"] = 16
    whole_cfg["reduced"] = []
    model = c.ref.build_model(whole_cfg, "serve")
    assert model.config.experts_held is None and model.config.n_held == 16
    model.config = dataclasses.replace(model.config, param_dtype=jnp.float32)
    uncut = perturbed(model.init_params(jax.random.PRNGKey(9)), 5)["blocks"]
    leaves = {n: uncut[n] for n in c.ref.EXPERT_LEAVES}
    h = jax.random.normal(jax.random.PRNGKey(3), (12, 64))
    share = lambda s: {n: v[:, 4 * s:4 * s + 4] for n, v in leaves.items()}
    z = c.ref._sizes(c.cfg)
    with jax.default_matmul_precision("highest"):
        _, weights, experts = route_topk(
            h, uncut["router_w"][1], 4, True, scoring="sigmoid",
            bias=uncut["router_bias"][1])
        whole, _ = routed_mlp(h, weights, experts, *leaves.values(), layer=1,
                              first=None, n_experts=16)
        summed = sum(routed_mlp(h, weights, experts, *share(s).values(),
                                layer=1, first=4 * s, n_experts=16)[0]
                     for s in range(4))
        w, chosen, _, _ = c.ref._route(
            h, uncut["router_w"][1], uncut["router_bias"][1], z, True,
            jnp.zeros(12, jnp.int32))
        np.testing.assert_array_equal(np.sort(chosen), np.sort(experts))
        mine = sum(c.ref._experts(h, w, chosen, share(s), 1,
                                  z._replace(first=4 * s)) for s in range(4))
    assert float(jnp.abs(whole).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(summed), np.asarray(whole),
                               atol=2e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(whole),
                               atol=2e-6, rtol=0)


def test_resolutions_change_an_open_row_and_nothing_else(tiny, plain):
    """The router's near-ties: a resolved pass with number 0 IS the plain
    pass (whole, or its last rows against the trimmed rows it kept); another
    number moves exactly the rows that have a held expert within ``TIE`` of
    their cut; ``reference_logits`` holds a token to the best of them."""
    c = tiny
    logits, kept = plain
    forward = lambda **kw: jax.jit(lambda p, ids: c.ref.reference_forward(
        p, ids, c.cfg, **kw))(c.params, c.ids)
    again, _ = forward(others=kept, last=10)
    np.testing.assert_allclose(np.asarray(again), np.asarray(logits[-10:]),
                               atol=TOL_PROGRAM)
    tail, trimmed = forward(last=10, keep=10)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(logits[-10:]),
                               atol=TOL_PROGRAM)
    assert trimmed[1]["k"].shape[0] == 10 + 8 and trimmed[1]["first"] == 30
    assert trimmed[0]["k"].shape[0] == 48           # a full layer: all of it
    every = np.asarray(jax.jit(lambda p, ids: c.ref.resolution_logits(
        p, ids, c.cfg, 48))(c.params, c.ids))
    assert every.shape == (c.ref.RESOLUTIONS, 48, 256)
    np.testing.assert_allclose(every[0], np.asarray(logits), atol=TOL_PROGRAM)
    distance = np.stack([np.asarray(k["distance"]) for k in kept
                         if "distance" in k])           # (routed, T, held)
    open_ = (distance <= c.ref.TIE).any(axis=(0, 2))
    moved = np.abs(every[1:] - every[0]).max(axis=(0, 2)) > TOL_PROGRAM
    assert 0 < open_.sum() < 48
    assert not moved[~open_].any() and moved[open_].any()
    held = np.asarray(c.ref.reference_logits(c.params, c.ids, c.cfg))
    shifted = lambda lg: lg - lg.max(axis=-1, keepdims=True)
    assert (shifted(held) >= shifted(every[0]) - TOL_PROGRAM).all()
    np.testing.assert_allclose(held[~open_], every[0][~open_], atol=TOL_PROGRAM)
    short = np.asarray(c.ref.reference_logits(c.params, c.ids, c.cfg,
                                              last=7))
    np.testing.assert_allclose(short, held[-7:], atol=TOL_PROGRAM)


# name -> (a change of the program's configuration, of its parameters)
BROKEN = {
    "no_sink": ({}, lambda p: {**p, "win_blocks": {
        **p["win_blocks"],
        "sink": jnp.full_like(p["win_blocks"]["sink"], -1e9)}}),
    "unrotated": ({"use_rope": False}, None),
    "fully_rotated": ({"rotary_dim": None}, None),
    "theta_swapped": ({"rope_theta": 1e4, "window_rope_theta": 5e6}, None),
    "value_unscaled": ({"value_scale": 1.0}, None)}


@pytest.mark.parametrize("control", sorted(BROKEN))
def test_broken_mathematics_fails_the_same_comparison(tiny, plain, control):
    c = tiny
    change, edit = BROKEN[control]
    model = type(c.model)(dataclasses.replace(c.model.config, **change))
    params = c.params if edit is None else edit(c.params)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, c.ids[None])[0]
    assert float(jnp.abs(got - plain[0]).max()) > 1000 * TOL_PROGRAM


def test_a_ring_written_one_slot_off_fails_the_same_comparison(tiny, plain):
    """The witness's control, through its own helpers: the float32 program
    with a decode step's rows one slot late."""
    from benchmark import mimo_witness as w

    c = tiny
    forms = w._forms(c.model)
    ids = jnp.asarray(c.ids)
    every = np.asarray(plain[0])[None, 19:47]
    rows = {}
    for name in ("float32", "float32_ring_one_slot_off"):
        got = jax.jit(lambda p, ids, form=forms[name]: w._run(
            form, p, ids, prompt=20, slots=64))(c.params, ids)
        rows[name] = w._compare(np.asarray(got), every, True)
    assert rows["float32"]["within_its_limit"]
    assert rows["float32"]["median_logit_difference"] < 1e-5
    assert not rows["float32_ring_one_slot_off"]["within_its_limit"]
    assert set(w.CONTROLS) < set(forms)


def test_reference_refuses_what_it_does_not_compute(tiny):
    c = tiny
    for key, value in (("scoring_func", "softmax"), ("n_group", 2),
                       ("topk_method", "greedy"), ("n_shared_experts", 1),
                       ("routed_scaling_factor", 2.5),
                       ("add_full_attention_sink_bias", True),
                       ("add_swa_attention_sink_bias", False),
                       ("attention_chunk_size", 64), ("swa_head_dim", 32),
                       ("tie_word_embeddings", True),
                       ("moe_layer_freq", [0, 1, 0, 1, 1, 1, 1])):
        cfg = copy.deepcopy(c.cfg)
        cfg["model"][key] = value
        with pytest.raises(SystemExit, match="mimo_v2_flash family computes"):
            c.ref.reference_forward(c.params, c.ids, cfg)
        with pytest.raises(SystemExit, match="mimo_v2_flash family computes"):
            c.ref.build_model(cfg, "serve")


# ---------------------------- the published configuration and its counts
SOURCE = "https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json"
PATTERN = [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]
# the catalog row's ``config`` (model-configs guide, architectures.jsonl),
# copied: a test reads no file outside the repository
CATALOG_ROW = {
    "attention_value_scale": 0.707, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 16384, "max_position_embeddings": 262144,
    "model_type": "mimo_v2_flash", "num_attention_heads": 64, "head_dim": 192,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "layernorm_epsilon": 1e-05, "rope_theta": 5000000,
    "tie_word_embeddings": False, "vocab_size": 152576,
    "partial_rotary_factor": 0.334, "sliding_window": 128,
    "swa_rope_theta": 10000, "attention_bias": False, "v_head_dim": 128,
    "hybrid_layer_pattern": PATTERN, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "sliding_window_size": 128,
    "attention_chunk_size": 128, "moe_layer_freq": [0] + [1] * 47,
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": None, "num_experts_per_tok": 8,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc",
    "routed_scaling_factor": None, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 8, "swa_head_dim": 192, "swa_v_head_dim": 128}
HELD = {"num_hidden_layers": 13, "hybrid_layer_pattern": PATTERN[:13],
        "moe_layer_freq": [0] + [1] * 12, "n_routed_experts": 8,
        "vocab_size": 19072, "max_position_embeddings": 28672}


def real_config():
    return mf.load_json(mf.config_path(mf.load_manifest(), "mimo-v2-flash"))


def test_published_widths():
    cfg = real_config()
    assert len(PATTERN) == 48 and sum(PATTERN) == 39
    assert cfg["source"] == SOURCE and cfg["family"] == "mimo_v2_flash"
    assert sorted(cfg["reduced"]) == sorted(HELD) == sorted(cfg["published"])
    assert set(cfg["reduced_why"]) == set(HELD)
    for key, value in CATALOG_ROW.items():
        want = HELD.get(key, value)
        assert cfg[key] == want and cfg["model"][key] == want, key
        if key in HELD:
            assert cfg["published"][key] == value
    # two whole periods of 5 : 1 behind the dense full layer
    assert HELD["hybrid_layer_pattern"] == [0] + [1, 1, 1, 1, 0, 1] * 2
    assert cfg["share"] == {**cfg["share"], "chips_per_layer": 32,
                            "experts_first": 96}
    assert "32 chips" in cfg["deployment"] and "train" not in cfg
    assert {"window_kind", "window", "sink", "rotary", "selection_bias",
            "attention_value_scale", "qk_norm", "mtp", "unused", "init"} \
        <= set(cfg["assumed"])
    serve = cfg["serve"]
    assert (serve["dtype"], serve["max_out_tokens"],
            serve["serving"]["default_deadline_s"]) == ("bf16", 28672, 30.0)
    c = families.get("mimo_v2_flash").build_model(cfg, "serve").config
    assert (c.n_embd, c.n_layer, c.n_head, c.n_kv_head, c.window_kv_head,
            c.head_dim, c.v_dim, c.rope_dim) == (4096, 13, 64, 4, 8, 192, 128,
                                                 64)
    assert (c.sliding_window, c.window_sink, c.rope_theta,
            c.window_rope_theta, c.value_scale) == (128, True, 5e6, 1e4, 0.707)
    assert (c.n_experts, c.experts_held, c.n_experts_per_tok,
            c.intermediate_size, c.dense_intermediate_size,
            c.n_dense_layers) == (256, (96, 8), 8, 2048, 16384, 1)
    assert c.pattern == ("win", "win", "win", "win", "attn", "win")
    assert c.param_dtype == jnp.bfloat16 and c.vocab_size == 19072


def test_counts_at_the_published_sizes():
    cfg = real_config()
    ref = families.get("mimo_v2_flash")
    assert ref.mixer_params(cfg, "attn") == 89_128_960
    assert ref.mixer_params(cfg, "win") + 64 == 94_371_904
    total = 3 * 89_128_960 + 10 * 94_371_904 + 201_326_592 \
        + 12 * (1_048_832 + 8 * 25_165_824) + 110_592 + 156_237_824
    assert ref.held_params(cfg) == total == 3_997_286_016
    model = ref.build_model(cfg, "serve")
    assert model.config.num_params() == total
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) == total
    assert ref.experts_met(cfg) == 0.25
    assert abs(ref.matmul_params(cfg) - 1.579e9) < 1e6
    assert abs(ref.weight_bytes(cfg) - 3.16e9) < 5e6
    # what a sequence holds: 7,680 B a position + 6,553,600 B whatever its
    # length, from the family's arithmetic and from the program's own cache
    assert ref.kv_bytes_per_position(cfg) == 3 * 2560 == 7680
    assert ref.window_bytes_per_sequence(cfg) == 10 * 5120 * 128 == 6_553_600
    from deepspeed_tpu.models.common import cache_footprint, cache_ring

    cache = jax.eval_shape(lambda: model.init_cache(1, 28672))
    assert cache_footprint(cache) == (7680, 0)
    assert cache_ring(cache) == (6_553_600, 128)
    assert cache["k"].shape == (3, 1, 28672, 768)
    assert cache["v"].shape == (3, 1, 28672, 512)
    assert cache["win_k"].shape == (10, 1, 128, 1536)
    assert cache["win_v"].shape == (10, 1, 128, 1024)
    # a ring counts at most its 128 slots; a token's K/V at 16k is 0.13 GB
    assert ref.decode_kv_bytes(cfg, 64) == 64 * 7680 + 6_553_600 / 2
    assert ref.decode_kv_bytes(cfg, 16384) == 16384 * 7680 + 6_553_600
    assert ref.decode_bytes_per_token(cfg, 16384) == ref.weight_bytes(cfg) \
        + ref.decode_kv_bytes(cfg, 16384)
    assert ref.decode_flops_per_token(cfg) == 2 * ref.matmul_params(cfg)
    assert ref.full_flash_flops(cfg, 8192) == 3 * 64 * 8192 * 8193 / 2 * 640
    band = 128 * 129 / 2 + (8192 - 128) * 128
    assert ref.win_flash_flops(cfg, 8192) == 10 * 64 * band * 640
    assert ref.win_flash_bytes(cfg, 8192) == 10 * 8192 * (64 + 8) * 320 * 2
    assert ref.full_flash_bytes(cfg, 8192) == 3 * 8192 * (64 + 4) * 320 * 2


# ------------------------------------------------- the four metrics' readers
def mimo_ctx(ops, spans, monkeypatch, family="mimo_v2_flash"):
    """``test_pangu_family.traced_ctx`` (ten decode chunks at context 4,000,
    three prefills of 2,048 / 4,096 / 8,192, callbacks of 16 tokens) over
    this configuration."""
    from tests.benchmark import test_pangu_family as pangu

    ctx = pangu.traced_ctx(ops, spans, family=family, monkeypatch=monkeypatch)
    ctx.config = real_config()
    return ctx


def ring_request(t1, **over):
    from tests.benchmark.test_pangu_family import span

    return span("request", t1 - 0.5, t1, **{**dict(
        prompt_len=8192, new_tokens=129, decode_ticks=8,
        cache_positions=8192 + 128, cache_bytes=(8192 + 128) * 7680,
        state_bytes=0, window_bytes=6_553_600, ring_wraps=128), **over})


MIMO_OPS = {
    "%flash_fwd.3 = bf16[64,8192,128]{2,1,0} custom-call(%q, %k, %v)": 0.3,
    "%flash_fwd_win.2 = bf16[64,8192,128]{2,1,0} custom-call(%q, %k)": 0.06,
    "%decode_attn.5 = bf16[1,16,512]{2,1,0} custom-call(%a)": 0.012,
    "%decode_attn.7 = bf16[1,8,1024]{2,1,0} custom-call(%a)": 0.004,
    "%fusion.9 = bf16[2048]{0} fusion(%decode_attn.5)": 5.0}


def test_the_four_metrics_count_what_the_program_ran(monkeypatch):
    from tests.benchmark.test_pangu_family import read_metric

    ctx = mimo_ctx(MIMO_OPS, [ring_request(2.0), ring_request(4.0)],
                   monkeypatch)
    fam, cfg = ctx.family, ctx.config
    assert read_metric("serve.window_bytes_per_sequence", ctx) == 6_553_600
    assert read_metric("serve.cache_bytes_per_position", ctx) == 7680
    prompts = (2048, 4096, 8192)
    # the windowed kernel alone: 0.06 s over three prefills; the banded
    # FLOPs against the operands' bytes, whichever is more, a prompt
    least = sum(max(fam.win_flash_flops(cfg, t) / 197e12,
                    fam.win_flash_bytes(cfg, t) / 819e9) for t in prompts) / 3
    win = read_metric("ttft.win_flash_roofline", ctx)
    assert win == pytest.approx(100 * least / 0.02)
    # ``flash_fwd`` and NOT ``flash_fwd_win``: 0.3 s over three prefills
    flops = sum(fam.full_flash_flops(cfg, t) for t in prompts) / 3
    full = read_metric("ttft.full_flash_roofline", ctx)
    assert full == pytest.approx(100 * flops / 197e12 / 0.1)
    # both caches' calls: 0.016 s over 10 chunks of 16 tokens at 4,007.5
    ring = read_metric("tpot.ring_decode_attn_roofline", ctx)
    assert ring == pytest.approx(100 * fam.decode_kv_bytes(cfg, 4007.5)
                                 / 819e9 / (0.0016 / 16))
    assert ctx.notes["ring_decode_context_mean"] == 4007.5
    assert all(0 < share < 100 for share in (win, full, ring))


@pytest.mark.parametrize("name", METRICS)
def test_the_metrics_read_nothing_where_there_is_nothing_to_read(
        name, monkeypatch):
    """The benchmark's files are laid over the PARENT too, and
    ``trace_metric_files.py`` over cells of other families: request spans
    without ``window_bytes``, no such kernel, no such function in the
    family, no device plane -> None, no raise."""
    from tests.benchmark.test_pangu_family import read_metric, span

    old = [span("request", 1.0, 2.0, prompt_len=2048, new_tokens=16,
                decode_ticks=1, cache_positions=2064, cache_bytes=1)]
    if name.startswith("serve."):
        assert read_metric(name, mimo_ctx(MIMO_OPS, old, monkeypatch)) is None
        return
    no_kernel = {k: v for k, v in MIMO_OPS.items() if k.startswith("%fusion")}
    assert read_metric(name, mimo_ctx(no_kernel, [ring_request(2.0)],
                                      monkeypatch)) is None
    assert read_metric(name, mimo_ctx(MIMO_OPS, [ring_request(2.0)],
                                      monkeypatch, family="olmoe")) is None
    off_device = mimo_ctx(MIMO_OPS, [ring_request(2.0)], monkeypatch)
    off_device.trace = None
    assert read_metric(name, off_device) is None


# ----------------------- the tiny configuration as a cell: manifest and run
CELL = "mimo-tiny.serve.closed.tiny"


def metric_entries(cells):
    keys = ("name", "unit", "better", "source", "layer", "moves")
    return [{**{k: mf.metric_spec("per_layer", name)[0][k] for k in keys},
             "workloads": list(cells)} for name in METRICS]


def mimo_manifest():
    m = copy.deepcopy(rehearsal.manifest())
    body = mf.load_json(DATA / "mimo-tiny.json")
    m["configs"].append({
        "name": "mimo-tiny", "source": body["source"],
        "reduced": body["reduced"], "why": "rehearsal",
        "file": "tests/benchmark/data/mimo-tiny.json"})
    m["workloads"].append({"name": CELL, "config": "mimo-tiny",
                           "why": "rehearsal", "traffic": "serve.closed.tiny",
                           "chips": 1})
    for metric in m["end_to_end"] + m["per_layer"]:
        if any(".serve." in w for w in metric.get("workloads", [])):
            metric["workloads"].append(CELL)
    m["per_layer"] += metric_entries([CELL])
    return m


@pytest.fixture
def jax_config_restored():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_the_tiny_configuration_passes_every_manifest_check():
    m = mimo_manifest()
    book = test_manifest.Book("mimo", m, "tests/benchmark/data/",
                              DATA / "traffic")
    config = next(c for c in m["configs"] if c["name"] == "mimo-tiny")
    test_manifest.test_config_entry_and_file(book, config)
    test_manifest.test_cell_entry_and_its_files(
        book, next(c for c in m["workloads"] if c["name"] == CELL))
    for metric in m["end_to_end"] + m["per_layer"]:
        test_manifest.test_metric_entry(book, metric)
    for metric in m["per_layer"][-len(METRICS):]:
        assert metric["name"] in METRICS
        test_manifest.test_metric_has_a_data_file_that_agrees_and_a_reader(
            metric)
    test_manifest.test_names_are_unique(book)


def test_the_real_cell_is_in_the_manifest_with_the_serve_metrics():
    """Present, wherever it stands: a later configuration goes behind it."""
    m = mf.load_manifest()
    config, = [c for c in m["configs"] if c["name"] == "mimo-v2-flash"]
    assert config["source"] == SOURCE and sorted(config["reduced"]) == \
        sorted(HELD)
    assert config["file"] == "benchmark/configs/mimo-v2-flash.json"
    cell, = [c for c in m["workloads"] if c["config"] == "mimo-v2-flash"]
    assert (cell["name"], cell["chips"], cell["traffic"]) == (
        REAL_CELL, 1, "serve.doc24k.c1")
    assert len(cell["why"]) <= 200 and "1/32" in cell["why"]
    sdar = "sdar-30b-a3b-chat.serve.gen132.c1"
    reported = set()
    for group in ("end_to_end", "per_layer"):
        for metric in m[group]:
            lists = metric.get("workloads", [])
            assert (REAL_CELL in lists) == (sdar in lists), metric["name"]
            if REAL_CELL in lists:
                reported.add(metric["name"])
    assert {"serve_tok_s", "ttft_p50_s", "tpot_p50_s",
            "tpot.decode_roofline", "serve.hbm_peak_frac"} <= reported
    traffic = mf.load_json(mf.traffic_path("serve.doc24k.c1"))
    caller, = traffic["callers"]
    assert (traffic["driver"], traffic["sentinel_every"],
            traffic["warmup_requests_per_caller"],
            traffic["trace_seconds"]) == ("closed_loop", 5, 6, 8.0)
    assert caller["prompt_lens"] == [8192, 16384, 24576]
    assert caller["new_tokens"] == [129] and caller["layout"] == \
        "balanced_blocks"
    # 129 = the prefill tick's token + 8 whole ticks of 16; the longest
    # request fits the cache
    assert (129 - 1) % 16 == 0
    assert 24576 + 129 <= real_config()["serve"]["max_out_tokens"]
    # the four new metrics are FILES (PERF.md section 7), read on the chip
    # through trace_metric_files.py
    from benchmark.trace_metric_files import with_metric_files

    have = {x["name"] for x in m["per_layer"]}
    assert not have & set(METRICS)
    added = {x["name"]: x for x in with_metric_files(m, REAL_CELL)[
        "per_layer"][len(m["per_layer"]):]}
    assert set(METRICS) | {"serve.cache_bytes_per_position"} <= set(added)
    assert {added[n]["moves"] for n in METRICS} == {"tpot_p50_s",
                                                    "ttft_p50_s"}
    assert {added[n]["layer"] for n in METRICS} == {"models", "kernels"}


def test_rehearsal_closed_loop_traced(jax_config_restored):
    """REHEARSAL, not a measurement: ``init_inference`` ->
    ``ServingFrontEnd.submit`` through ``run.execute`` on the CPU, prompts of
    8-24 tokens and 16-32 new ones through rings of 8 slots, the served
    tokens checked against this family's reference (bf16 weights). The
    device-trace readers find no device plane and are left out; what the
    rings hold is the program's own number, and is there."""
    result, info = run.execute(CELL, seed=3, seconds=1.5, trace=1,
                               manifest=mimo_manifest(), platforms=("cpu",),
                               traffic_dir=DATA / "traffic")
    line = json.loads(json.dumps(result))
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 2
    assert info["check"]["worst_logit_shortfall"] <= info["check"]["margin"]
    assert info["notes"]["sentinels_compared"] > 0
    # 5 window layers x 8 slots x (128 + 128) lanes x 2 B (bf16)
    assert line["metrics"]["serve.window_bytes_per_sequence"]["value"] == \
        5 * 8 * 256 * 2
    assert not set(METRICS[1:]) & set(line["metrics"])


def test_the_witness_runs_the_tiny_configuration(jax_config_restored, capsys):
    """REHEARSAL of ``benchmark/mimo_witness.py`` on the CPU: the timed
    path's own prefill and decode through a ring that wraps three times
    against the reference in blocks, a cold request a prompt length first."""
    from benchmark import mimo_witness

    assert mimo_witness.main(
        ["--config", "mimo-tiny", "--seed", "3", "--prompts", "24",
         "--steps", "24", "--cold", "1"], manifest=mimo_manifest()) == 0
    cold, row = [json.loads(part.splitlines()[0]) for part in
                 capsys.readouterr().out.split("WITNESS ")[1:]]
    assert (cold["cold_request"], cold["status"]) == (24, "completed")
    assert cold["compile_s"] > 0
    assert (row["form"], row["prompt"], row["decode_steps"],
            row["ring_wraps_past_the_prompt"]) == ("sound", 24, 23, 3)
    assert row["within_its_limit"]
    assert row["worst_logit_difference"] <= \
        row["worst_against_the_plain_pass"] + 1e-6
