"""SDAR-MoE's share (``benchmark/families/sdar_moe.py``) at a small size on
the CPU: the family's plain reference against the program's model
(``models/llama.py`` with per-head q/k norm, a share of the experts, the
block-causal mask and the block step) on the same seeded weights: LOGITS of
denoising passes through the cache, ``generate()`` and a served request
against ``reference_generate``, the eight shares' parts, what the check holds
a token to, programs with broken mathematics that the same comparison must
refuse, the published configuration against its catalog row, the counts, the
tiny configuration through the manifest checks and ``run.execute``."""

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
from benchmark import run, systems
from tests.benchmark import rehearsal, test_manifest
from tests.benchmark.test_reference import perturbed

DATA = rehearsal.DATA
# float32 at "highest" on both sides: what is left is the order of the sums
# (measured 3e-7 to 7e-7 on logits that spread by 0.37). bf16 router logits
# move a logit by 1e-3 and more (below), a bf16 softmax by 1e-4, a dropped
# q/k norm gain or rotary angle by 1e-2.
TOL_PROGRAM = 2e-5


def case(**serve_over):
    """``sdar-tiny.json`` (``serve_over`` laid over its decoding rule), the
    program's model built through the family module and put into float32,
    seeded weights with every gain moved off 1, ids."""
    cfg = mf.load_json(DATA / "sdar-tiny.json")
    cfg["serve"].update(serve_over)
    ref = families.get("sdar_moe")
    model = ref.build_model(cfg, "serve")
    model.config = dataclasses.replace(
        model.config, dtype=jnp.float32, param_dtype=jnp.float32,
        use_flash_attention=False, remat=False)
    params = perturbed(model.init_params(jax.random.PRNGKey(4)), 5)
    # a head that spreads the logits: choices differ from position to position
    params["lm_head"] = params["lm_head"] * 8.0
    ids = np.random.default_rng(6).integers(0, 500, size=48, dtype=np.int32)
    return types.SimpleNamespace(ref=ref, cfg=cfg, model=model, params=params,
                                 ids=ids)


@pytest.fixture(scope="module")
def tiny():
    return case()


def forward(c, masked, params=None, **kw):
    return jax.jit(lambda p, ids, m: c.ref.reference_forward(
        p, ids, m, c.cfg, **kw))(c.params if params is None else params,
                                 c.ids, masked)


# ------------------------------------------------ program against reference
def test_the_tiny_file_has_the_mechanisms_on(tiny):
    c = tiny.model.config
    assert (c.n_head, c.n_kv_head, c.head_dim, c.n_embd) == (4, 2, 16, 48)
    assert c.qk_norm == "head" and c.norm_topk_prob
    assert (c.n_experts, c.experts_held, c.n_experts_per_tok) == (16, (8, 4), 4)
    assert (c.block_length, c.denoising_steps, c.remasking) == (
        4, 2, "low_confidence_static")
    assert c.mask_token_id == 511 and c.rope_theta == 1000000
    assert systems.SERVE_LOGIT_MARGIN == tiny.ref.MARGIN


@pytest.fixture(scope="module")
def cached(tiny):
    """The program's two cached calls, jitted once for the module."""
    c = tiny
    with jax.default_matmul_precision("highest"):
        return (jax.jit(lambda ids: c.model.prefill(
                    c.params, ids, c.model.init_cache(1, 64))[1]),
                jax.jit(lambda t, m, cache: c.model.block_step(
                    c.params, t, m, cache)))


@pytest.mark.parametrize("residue", [0, 1, 2, 3])
def test_denoising_passes_through_the_cache_match_the_reference(tiny, cached,
                                                                residue):
    """(a) A prompt of ``20 + residue`` tokens: ``prefill`` of its whole
    blocks, then block steps through the cache with the left-over tokens
    given and the rest masked, then with half of those revealed, against
    ``reference_forward`` on the same states: LOGITS of denoising passes."""
    c, P = tiny, 20 + residue
    whole = P - residue
    ids = jnp.asarray(c.ids)[None]
    at = slice(whole, whole + 4)
    prefill, block_step = cached
    with jax.default_matmul_precision("highest"):
        cache = prefill(ids[:, :whole])
        for reveal in (0, 2):
            masked = np.arange(48) >= P
            masked[P:P + reveal] = False
            want = np.asarray(forward(c, masked)[0])[at]
            got, cache = block_step(ids[:, at], masked[None, at], cache)
            np.testing.assert_allclose(np.asarray(got[0]), want,
                                       atol=TOL_PROGRAM, rtol=0)
    assert want.std() > 0.1 and int(cache["pos"]) == whole


@pytest.mark.parametrize("over", [
    dict(remasking="sequential"), dict(),
    dict(remasking="low_confidence_static", denoising_steps=3),
    dict(remasking="low_confidence_dynamic", confidence_threshold=0.0),
    dict(remasking="low_confidence_dynamic", confidence_threshold=2.0)],
    ids=["sequential", "static", "static3", "dynamic0", "dynamic2"])
def test_generate_and_the_server_emit_what_the_reference_generates(over):
    """(b) ``init_inference`` -> ``generate()`` and ``ServingFrontEnd.submit``
    in float32, greedy, against ``reference_generate`` (full passes, no
    cache): a length that is no whole number of blocks, a prompt that is
    none either; the passes a block took."""
    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    c = case(**over)
    engine = deepspeed_tpu.init_inference(c.model, dtype="fp32",
                                          params=c.params, max_out_tokens=128)
    # the server beside generate() for the cell's rule and the dynamic one
    served = over.get("remasking") != "sequential" and \
        "denoising_steps" not in over
    front = serving.from_ds_config(engine, DeepSpeedConfig({"serving": {
        "decode_tick_tokens": 8, "max_queue_depth": 4}})) if served else None
    try:
        for prompt, new in ((21, 11),):
            with jax.default_matmul_precision("highest"):
                want, log = c.ref.reference_generate(c.params,
                                                     c.ids[:prompt], new, c.cfg)
                out = np.asarray(engine.generate(c.ids[None, :prompt],
                                                 max_new_tokens=new))[0]
                if served:
                    req = front.submit(c.ids[:prompt], max_new_tokens=new)
                    req.result(timeout=300.0)
                    assert req.status == "completed" \
                        and req.tokens == list(want)
            np.testing.assert_array_equal(out[prompt:], want)
            one_pass = over.get("confidence_threshold") == 0.0
            # (a block unmasked in ONE pass reads four mask tokens alike)
            assert len(set(want.tolist())) > (0 if one_pass else 2)
            assert max(s for _, s, _, _ in log) == (
                0 if one_pass else c.cfg["serve"]["denoising_steps"] - 1)
    finally:
        if served:
            front.close()


def test_eos_inside_a_block_ends_the_generation(tiny):
    import deepspeed_tpu

    c = tiny
    engine = deepspeed_tpu.init_inference(c.model, dtype="fp32",
                                          params=c.params, max_out_tokens=128)
    with jax.default_matmul_precision("highest"):
        free, _ = c.ref.reference_generate(c.params, c.ids[:24], 12, c.cfg)
        eos = int(free[5])
        want, _ = c.ref.reference_generate(c.params, c.ids[:24], 12, c.cfg,
                                           eos=eos)
        out = np.asarray(engine.generate(c.ids[None, :24], max_new_tokens=12,
                                         eos_token_id=eos))[0, 24:]
    np.testing.assert_array_equal(out, want)
    first = int(np.argmax(want == eos))
    assert first <= 5 and (want[first:] == eos).all()


def test_the_shares_parts_add_up_to_the_uncut_layer(tiny):
    """(c) Four shares of four experts each (a 16-wide router): the routed
    parts of the shares — the reference's ``_experts`` and the program's
    ``routed_mlp(first=)``, share s holding experts 4s .. 4s + 3 of the uncut
    leaves — add up to what ONE chip holding all sixteen computes."""
    from deepspeed_tpu.moe.dropless import route_topk, routed_mlp

    c = tiny
    whole_cfg = copy.deepcopy(c.cfg)
    whole_cfg["model"]["num_experts"] = 16
    whole_cfg["reduced"] = ["vocab_size", "max_position_embeddings"]
    model = c.ref.build_model(whole_cfg, "serve")
    assert model.config.experts_held is None and model.config.n_held == 16
    model.config = dataclasses.replace(model.config, param_dtype=jnp.float32)
    uncut = perturbed(model.init_params(jax.random.PRNGKey(9)), 5)["blocks"]
    leaves = {n: uncut[n] for n in c.ref.EXPERT_LEAVES}
    h = jax.random.normal(jax.random.PRNGKey(3), (12, 48))
    share = lambda s: {n: v[:, 4 * s:4 * s + 4] for n, v in leaves.items()}
    with jax.default_matmul_precision("highest"):
        _, weights, experts = route_topk(h, uncut["router_w"][1], 4, True)
        whole, _ = routed_mlp(h, weights, experts, *leaves.values(), layer=1,
                              first=None, n_experts=16)
        summed = sum(routed_mlp(h, weights, experts, *share(s).values(),
                                layer=1, first=4 * s, n_experts=16)[0]
                     for s in range(4))
        z = c.ref._sizes(c.cfg)
        w, chosen, _ = c.ref._route(h, uncut["router_w"][1], z, True,
                                    jnp.full((12, 4), -1))
        np.testing.assert_array_equal(np.sort(chosen), np.sort(experts))
        plain = sum(c.ref._experts(h, w, chosen, share(s), 1,
                                   z._replace(first=4 * s)) for s in range(4))
    assert float(jnp.abs(whole).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(summed), np.asarray(whole),
                               atol=2e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(whole),
                               atol=2e-6, rtol=0)


def test_reference_logits_holds_a_token_to_its_own_pass(tiny):
    """What ``ServeSystem.check`` reads: row ``prompt - 1 + k`` is the row of
    new token k, from the pass that unmasked its position; a float32 greedy
    generation lies ON its rows' best; a token moved to another one does
    not."""
    import deepspeed_tpu

    c = tiny
    engine = deepspeed_tpu.init_inference(c.model, dtype="fp32",
                                          params=c.params, max_out_tokens=128)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(engine.generate(c.ids[None, :24],
                                         max_new_tokens=12))[0]
        rows = np.asarray(jax.jit(lambda p, ids: c.ref.reference_logits(
            p, ids, c.cfg, prompt=24))(c.params, out))
    assert rows.shape == (36, 512) and not rows[:23].any()
    served = rows[23:35]
    short = served.max(axis=-1) - served[np.arange(12), out[24:]]
    assert short.max() <= TOL_PROGRAM, short
    with jax.default_matmul_precision("highest"):
        _, log = c.ref.reference_generate(c.params, c.ids[:24], 12, c.cfg)
    for b, s, moved, logits in log:
        for i in moved:
            np.testing.assert_allclose(rows[b * 4 + i - 1], logits[i],
                                       atol=TOL_PROGRAM)
    wrong = out.copy()
    wrong[30] = (wrong[30] + 1) % 500
    with jax.default_matmul_precision("highest"):
        rows = np.asarray(jax.jit(lambda p, ids: c.ref.reference_logits(
            p, ids, c.cfg, prompt=24))(c.params, wrong))[23:35]
    assert (rows.max(axis=-1) - rows[np.arange(12), wrong[24:]]
            ).max() > c.ref.MARGIN


def test_an_open_choice_of_positions_is_resolved_by_the_admissible_sets(tiny,
                                                                        monkeypatch):
    """A program that unmasked ANOTHER admissible pair in the first pass
    (confidences within the tolerance) is held to the rows of its own
    choice; with the tolerance at 0 the same tokens fail."""
    c = tiny
    z = c.ref._sizes(c.cfg)
    ids = c.ids[:28].copy()
    with jax.default_matmul_precision("highest"):
        rows, own, conf, _ = jax.jit(lambda p, ids: c.ref.block_rows(
            p, ids, c.cfg, 24))(c.params, ids)
        own, order = np.asarray(own), np.argsort(-np.asarray(conf))
        # the program's pair: the first and the THIRD most confident
        other = np.zeros(4, bool)
        other[[order[0], order[2]]] = True
        assert (own != other).any()
        alt = np.asarray(jax.jit(lambda p, ids: c.ref.block_rows(
            p, ids, c.cfg, 24, jnp.asarray(other))[0])(c.params, ids))
        ids[24:28] = alt.argmax(axis=-1)        # what such a program emits
        # (the second pass's rows depend on the first pass's tokens: settle)
        for _ in range(3):
            alt = np.asarray(jax.jit(lambda p, ids: c.ref.block_rows(
                p, ids, c.cfg, 24, jnp.asarray(other))[0])(c.params, ids))
            ids[24:28] = alt.argmax(axis=-1)
        check = lambda: np.asarray(jax.jit(
            lambda p, ids: c.ref.reference_logits(p, ids, c.cfg, prompt=24))(
                c.params, ids))[23:27]
        monkeypatch.setattr(c.ref, "POSITION_TIE", 10.0)   # every set is open
        open_rows = check()
        monkeypatch.setattr(c.ref, "POSITION_TIE", 0.0)
        closed_rows = check()
    short = lambda r: (r.max(axis=-1) - r[np.arange(4), ids[24:28]]).max()
    assert short(open_rows) <= TOL_PROGRAM
    assert short(closed_rows) > 0 or (own == other).all()
    assert z.block == 4 and len(c.ref._first_sets(z)) == 6


BROKEN = {
    "bf16 router logits": lambda p: _blocks(p, router_w=lambda w: w.astype(
        jnp.bfloat16).astype(jnp.float32) * (1 + 2 ** -8)),
    "one q/k norm for all heads' columns": lambda p: _blocks(
        p, q_norm_g=lambda g: jnp.ones_like(g)),
    "no expert down projection": lambda p: _blocks(
        p, expert_down_w=lambda w: jnp.zeros_like(w)),
}


def _blocks(params, **change):
    blocks = dict(params["blocks"])
    for name, fn in change.items():
        blocks[name] = fn(blocks[name])
    return {**params, "blocks": blocks}


@pytest.mark.parametrize("control", sorted(BROKEN))
def test_broken_mathematics_fails_the_same_comparison(tiny, control):
    """The program computing with a changed leaf (the reference is given the
    true ones) is outside the tolerance of the comparison above: rounding
    the router's weights to bf16 (the nearest precision below) moves the
    logits of a denoising pass by more than ``TOL_PROGRAM``."""
    c = tiny
    masked = np.arange(48) >= 26
    want = np.asarray(forward(c, masked)[0])[24:28]
    with jax.default_matmul_precision("highest"):
        _, cache = c.model.prefill(BROKEN[control](c.params),
                                   jnp.asarray(c.ids)[None, :24],
                                   c.model.init_cache(1, 64))
        got, _ = c.model.block_step(
            BROKEN[control](c.params), jnp.asarray(c.ids)[None, 24:28],
            masked[None, 24:28], cache)
    assert np.abs(np.asarray(got[0]) - want).max() > 5 * TOL_PROGRAM


def test_a_bf16_softmax_of_the_router_fails_the_tolerance(tiny, monkeypatch):
    """The router's probabilities rounded to bf16 before the top-k weights
    are formed: outside ``TOL_PROGRAM`` too."""
    from deepspeed_tpu.moe import dropless

    c, real = tiny, dropless.route_topk

    def rounded(tokens, router_w, k, renormalize, **kw):
        probs, weights, experts = real(tokens, router_w, k, renormalize, **kw)
        return probs, weights.astype(jnp.bfloat16).astype(weights.dtype), \
            experts

    masked = np.arange(48) >= 24
    want = np.asarray(forward(c, masked)[0])[:24]
    monkeypatch.setattr(dropless, "route_topk", rounded)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(c.model.apply(
            c.params, np.where(masked, 511, c.ids)[None])[0])[:24]
    assert np.abs(got - want).max() > 5 * TOL_PROGRAM


def test_reference_refuses_what_it_does_not_compute(tiny):
    for key, value in (("rope_scaling", {"type": "linear", "factor": 2}),
                       ("attention_bias", True), ("use_sliding_window", True),
                       ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
                       ("tie_word_embeddings", True)):
        cfg = copy.deepcopy(tiny.cfg)
        cfg["model"][key] = value
        with pytest.raises(SystemExit, match="sdar_moe family computes"):
            tiny.ref.build_model(cfg, "serve")
    with pytest.raises(SystemExit, match="served only"):
        tiny.ref.build_model(tiny.cfg, "train")
    with pytest.raises(SystemExit, match="whole blocks"):
        tiny.ref.reference_logits(tiny.params, tiny.ids[:30], tiny.cfg,
                                  prompt=24)


# ------------------------------------- the published configuration, pinned
# (g) the catalog row of ISSUE 45's source, value for value, pinned HERE (a
# row may leave the catalog: tests/benchmark/test_olmoe_family.py)
CATALOG_ROW = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
HELD = {"num_experts": 16, "vocab_size": 18992,
        "max_position_embeddings": 8192}
SOURCE = "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
REAL_CELL = "sdar-30b-a3b-chat.serve.gen132.c1"
METRICS = ("tpot.tokens_per_pass", "tpot.pass_device_p50_s",
           "tpot.block_attn_roofline", "ttft.block_flash_roofline")


def real_config():
    return mf.load_json(mf.BENCH_DIR / "configs" / "sdar-30b-a3b-chat.json")


def test_published_widths():
    cfg = real_config()
    assert cfg["source"] == SOURCE and cfg["family"] == "sdar_moe"
    assert sorted(cfg["reduced"]) == sorted(HELD)
    for key, value in CATALOG_ROW.items():
        want = HELD.get(key, value)
        assert cfg[key] == want and cfg["model"][key] == want, key
        if key in HELD:
            assert cfg["published"][key] == value
    assert cfg["num_hidden_layers"] == 48               # depth is NOT cut
    assert cfg["share"] == {**cfg["share"], "chips_per_layer": 8,
                            "experts_first": 48}
    assert "eight" in cfg["reduced_why"]["num_experts"] \
        and "8-chip" in cfg["deployment"]
    serve = cfg["serve"]
    assert (serve["block_length"], serve["denoising_steps"],
            serve["remasking"], serve["max_out_tokens"]) == (
                4, 2, "low_confidence_static", 8192)
    assert {"block_length", "denoising_steps", "in_place_prediction",
            "mask_token_id", "qk_norm", "rope", "init"} <= set(cfg["assumed"])
    c = families.get("sdar_moe").build_model(cfg, "serve").config
    assert (c.n_embd, c.n_layer, c.n_head, c.n_kv_head, c.head_dim) == (
        2048, 48, 32, 4, 128)
    assert (c.n_experts, c.experts_held, c.n_experts_per_tok,
            c.intermediate_size) == (128, (48, 16), 8, 768)
    assert (c.vocab_size, c.mask_token_id, c.qk_norm) == (18992, 18991, "head")
    assert c.param_dtype == jnp.bfloat16


def test_counts_at_the_published_sizes():
    cfg = real_config()
    ref = families.get("sdar_moe")
    layer = 18_874_368 + 4_352 + 262_144 + 16 * 4_718_592
    assert layer == 94_638_336
    assert ref.held_params(cfg) == 48 * layer + 77_791_232 + 2048 \
        == 4_620_433_408
    assert ref.held_params(cfg) == ref.build_model(
        cfg, "serve").config.num_params()
    assert ref.kv_bytes_per_position(cfg) == 98_304
    assert abs(ref.experts_met(cfg) - 3.6394) < 1e-3
    assert ref.experts_met(cfg, rows=1) == 1.0
    # a pass: ~1.8 GB of mixers, ~1.65 GB of experts, the head
    mixers = 48 * 18_874_368 * 2
    experts = 48 * ref.experts_met(cfg) * 4_718_592 * 2
    assert 1.8e9 < mixers < 1.82e9 and 1.6e9 < experts < 1.7e9
    per_pass = ref.pass_params(cfg) * 2
    assert mixers + experts < per_pass < mixers + experts + 0.15e9
    # a token: 2 passes / 4 (the commit free)
    assert ref.passes_per_token(cfg) == 0.5
    assert ref.decode_bytes_per_token(cfg, 2048) == 0.5 * (
        per_pass + 2048 * 98_304)
    assert ref.block_attn_bytes(cfg, 2048) == 2048 * 98_304
    assert ref.block_flash_flops(cfg, 4096) == 48 * 32 * (
        4096 * 4100 / 2) * 4 * 128
    assert ref.decode_flops_per_token(cfg) == 2 * 2 * ref.matmul_params(cfg)


# ------------------------------------------------- the four metrics' readers
def sdar_ctx(ops, spans, monkeypatch, family="sdar_moe"):
    """``test_pangu_family.traced_ctx`` (ten decode chunks at context 4,000,
    three prefills of 2,048 / 4,096 / 8,192, callbacks of 16 tokens) over
    this configuration."""
    from tests.benchmark import test_pangu_family as pangu

    ctx = pangu.traced_ctx(ops, spans, family=family, monkeypatch=monkeypatch)
    ctx.config = real_config()
    return ctx


def block_request(t1, **over):
    from tests.benchmark.test_pangu_family import span

    # 132 tokens: 33 blocks of 2 passes + a commit, the first in the prefill
    # tick, 8 decode ticks of 4
    return span("request", t1 - 0.5, t1, **{**dict(
        new_tokens=132, passes=66, commits=33, blocks=33, decode_ticks=8,
        block_length=4, denoising_steps=2), **over})


SDAR_OPS = {
    "%flash_fwd.3 = bf16[32,4096,128]{2,1,0} custom-call(%q, %k, %v)": 0.09,
    "%decode_attn.5 = bf16[1,32,512]{2,1,0} custom-call(%a)": 0.1,
    "%fusion.9 = bf16[2048]{0} fusion(%decode_attn.5)": 5.0}


def test_the_four_metrics_count_what_the_program_ran(monkeypatch):
    from tests.benchmark.test_pangu_family import read_metric

    ctx = sdar_ctx(SDAR_OPS, [block_request(2.0), block_request(4.0)],
                   monkeypatch)
    fam, cfg = ctx.family, ctx.config
    assert read_metric("tpot.tokens_per_pass", ctx) == pytest.approx(4 / 3)
    # a chunk: 3 passes a block x 4 blocks a tick; its median 0.08 s
    assert read_metric("tpot.pass_device_p50_s", ctx) == pytest.approx(
        0.08 / 12)
    assert ctx.notes["passes_per_decode_chunk"] == 12
    # decode_attn: 0.1 s over 10 chunks x 12 + 3 prefills x 3 passes, against
    # the K/V of 4,000 + (16 + 4) / 2 slots
    share = read_metric("tpot.block_attn_roofline", ctx)
    per_pass = 0.1 / (10 * 12 + 3 * 3)
    assert share == pytest.approx(
        100 * fam.block_attn_bytes(cfg, 4010) / 819e9 / per_pass)
    assert ctx.notes["block_attn_roofline_bound"] == "memory"
    assert ctx.notes["block_attn_context_mean"] == 4010
    # flash: each traced prompt's FLOPs, averaged, over 0.03 s a prefill
    flops = sum(fam.block_flash_flops(cfg, t) for t in (2048, 4096, 8192)) / 3
    assert read_metric("ttft.block_flash_roofline", ctx) == pytest.approx(
        100 * flops / 197e12 / 0.03)
    assert 0 < share < 100


@pytest.mark.parametrize("name", METRICS)
def test_the_metrics_read_nothing_where_there_is_nothing_to_read(
        name, monkeypatch):
    """The benchmark's files are laid over the PARENT too, and
    ``trace_metric_files.py`` over cells of other families: request spans
    without the block step's counts, no such kernel, no such function in the
    family, no device plane -> None, no raise."""
    from tests.benchmark.test_pangu_family import read_metric, span

    old = [span("request", 1.0, 2.0, prompt_len=2048, new_tokens=16,
                decode_ticks=1)]
    flash = name.startswith("ttft.")
    if not flash:       # the parent's spans; an autoregressive model's
        assert read_metric(name, sdar_ctx(SDAR_OPS, old, monkeypatch)) is None
    no_kernel = {k: v for k, v in SDAR_OPS.items() if k.startswith("%fusion")}
    if "roofline" in name:
        assert read_metric(name, sdar_ctx(
            no_kernel, [block_request(2.0)], monkeypatch)) is None
        assert read_metric(name, sdar_ctx(
            SDAR_OPS, [block_request(2.0)], monkeypatch,
            family="olmoe")) is None
    off_device = sdar_ctx(SDAR_OPS, [block_request(2.0)], monkeypatch)
    off_device.trace = None
    if name != "tpot.tokens_per_pass":      # a count, device or no device
        assert read_metric(name, off_device) is None


# ----------------------- the tiny configuration as a cell: manifest and run
CELL = "sdar-tiny.serve.closed.tiny"


def metric_entries(cells):
    keys = ("name", "unit", "better", "source", "layer", "moves")
    return [{**{k: mf.metric_spec("per_layer", name)[0][k] for k in keys},
             "workloads": list(cells)} for name in METRICS]


def sdar_manifest():
    m = copy.deepcopy(rehearsal.manifest())
    body = mf.load_json(DATA / "sdar-tiny.json")
    m["configs"].append({
        "name": "sdar-tiny", "source": body["source"],
        "reduced": body["reduced"], "why": "rehearsal",
        "file": "tests/benchmark/data/sdar-tiny.json"})
    m["workloads"].append({"name": CELL, "config": "sdar-tiny",
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
    m = sdar_manifest()
    book = test_manifest.Book("sdar", m, "tests/benchmark/data/",
                              DATA / "traffic")
    config = next(c for c in m["configs"] if c["name"] == "sdar-tiny")
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


def test_the_real_cell_is_in_the_manifest_last_with_the_serve_metrics():
    m = mf.load_manifest()
    assert m["configs"][-1]["name"] == "sdar-30b-a3b-chat"
    assert m["configs"][-1]["reduced"] == ["num_experts", "vocab_size",
                                           "max_position_embeddings"]
    cell = m["workloads"][-1]
    assert (cell["name"], cell["chips"], cell["traffic"]) == (
        REAL_CELL, 1, "serve.gen132.c1")
    pangu = "openpangu-ultra-moe-718b.serve.doc8k.c1"
    for group in ("end_to_end", "per_layer"):
        for metric in m[group]:
            lists = metric.get("workloads", [])
            assert (REAL_CELL in lists) == (pangu in lists), metric["name"]
            if REAL_CELL in lists:
                assert lists[-1] == REAL_CELL
    traffic = mf.load_json(mf.traffic_path("serve.gen132.c1"))
    caller, = traffic["callers"]
    assert caller["prompt_lens"] == [512, 1024, 2048, 3072, 4096]
    assert caller["new_tokens"] == [132] and caller["layout"] == \
        "balanced_blocks"
    # 132 = the first block (the prefill tick's) + 8 whole ticks of 4 blocks
    assert (132 - 4) % 16 == 0 and all(p % 4 == 0
                                       for p in caller["prompt_lens"])
    # the four new metrics are FILES (PERF.md section 7), read on the chip
    # through trace_metric_files.py
    from benchmark.trace_metric_files import with_metric_files

    have = {x["name"] for x in m["per_layer"]}
    assert not have & set(METRICS)
    added = {x["name"]: x for x in with_metric_files(m, REAL_CELL)[
        "per_layer"][len(m["per_layer"]):]}
    assert set(METRICS) <= set(added)
    assert {added[n]["moves"] for n in METRICS} == {"tpot_p50_s",
                                                    "ttft_p50_s"}


def test_rehearsal_closed_loop_traced(jax_config_restored):
    """(f) REHEARSAL, not a measurement: ``init_inference`` ->
    ``ServingFrontEnd.submit`` through ``run.execute`` on the CPU, the served
    tokens checked against this family's reference (bf16 weights). The
    device-trace readers find no device plane and are left out; the tokens a
    pass is a COUNT of the program's, and is there."""
    result, info = run.execute(CELL, seed=3, seconds=1.5, trace=1,
                               manifest=sdar_manifest(), platforms=("cpu",),
                               traffic_dir=DATA / "traffic")
    line = json.loads(json.dumps(result))
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 2
    assert info["check"]["worst_logit_shortfall"] <= info["check"]["margin"]
    assert info["notes"]["sentinels_compared"] > 0
    assert "tpot.tokens_per_pass" in line["metrics"]
    per_pass = line["metrics"]["tpot.tokens_per_pass"]["value"]
    # 4 tokens over 2 denoising passes and the commit; the last tick of a
    # request may run blocks nobody reads (16 or 32 new tokens: 4 + 16 ..)
    assert 0.9 < per_pass <= 4 / 3 + 1e-9
    assert not set(METRICS[1:]) & set(line["metrics"])


def test_the_witness_runs_the_tiny_configuration(jax_config_restored, capsys):
    """REHEARSAL of ``benchmark/sdar_witness.py`` on the CPU: every denoising
    pass of the timed path's own functions against the reference in blocks,
    given the program's unmask order and its choice among the held."""
    from benchmark import sdar_witness

    assert sdar_witness.main(["--config", "sdar-tiny", "--seeds", "3",
                              "--prompts", "24", "--new", "10"],
                             manifest=sdar_manifest()) == 0
    row, = [json.loads(part.splitlines()[0]) for part in
            capsys.readouterr().out.split("WITNESS ")[1:]]
    assert "control" not in row
    assert row["check_shortfall"] <= systems.SERVE_LOGIT_MARGIN
    # the control's leaves (the chip runs it: ``--controls 1``)
    params = case().params
    broken = sdar_witness.float8_mixers(params)
    assert broken["lm_head"] is params["lm_head"]
    assert broken["blocks"]["router_w"] is params["blocks"]["router_w"]
    assert float(jnp.abs(broken["blocks"]["q_w"]
                         - params["blocks"]["q_w"]).max()) > 0
    assert (row["seed"], row["prompt"], row["new"]) == (3, 24, 10)
    assert row["passes"] == 6 and row["first_passes"] == 3
    assert row["gap_on_choices_max"] <= row["tolerance"]
    assert row["gap_on_choices_max"] <= row["gap_plain_max"] + 1e-6
    assert row["router_choices"] == 6 * 3 * 4       # passes x layers x rows
    assert 0 <= row["router_open_share"] <= 1 and 0 <= row[
        "position_open_share"] <= 1
