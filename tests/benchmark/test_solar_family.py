"""Solar-Open2's share (``benchmark/families/solar_open2.py``) at a small
size on the CPU: the family's plain reference (the recurrence token by
token) against the program's model (``models/llama.py`` with a layer pattern
of gated NoPE softmax and KDA layers, the sigmoid router, a share of the
experts beside a shared one) on the same seeded weights — ``apply``,
``prefill`` + ``decode_step`` through state and convolution window,
``generate()`` and a served request through ``run.execute``; programs with
broken mathematics that the same comparison must refuse; the shares of one
layer; the published configuration with the published numbers written HERE;
the counts; the three per-layer metrics' readers; the tiny configuration
through the manifest checks."""

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
from tests.benchmark.test_pangu_family import (PEAKS, Tracer,
                                               jax_config_restored, span)
from tests.benchmark.test_reference import perturbed

DATA = rehearsal.DATA
# float32 at "highest" on both sides: what is left is the order of the sums
# and the chunked algebra against the token-by-token scan (measured gaps
# 5e-7 to 3e-6 on logits that spread by 0.4). A dropped convolution tap
# moves a logit by 2e-2, beta without its factor 2 by 1e-1, a dropped gate,
# decay, shared expert or routed pair by 1e-2 and more.
TOL_PROGRAM = 2e-5
# bf16 against float32: 8 mantissa bits through 4 layers of width 64 on
# logits that spread by 0.44; measured 0.025 - 0.074 a token, median 0.039
# (0.032 with the KDA mixers alone kept in float32: they are not where it
# comes from). A router choice that flips on rounding swaps one expert at
# one token (a share holds ~1 of a token's 4 choices): such tokens are
# counted, not hidden.
TOL_BF16, FLIPS = 1e-1, 3


def case(**model_over):
    """``solar-tiny.json`` (``model_over`` laid over its sizes), the
    program's model built through the family module and put into float32,
    seeded weights with every gain moved off 1, ids."""
    cfg = mf.load_json(DATA / "solar-tiny.json")
    cfg["model"].update(model_over)
    ref = families.get("solar_open2")
    model = ref.build_model(cfg, "serve")
    model.config = dataclasses.replace(
        model.config, dtype=jnp.float32, param_dtype=jnp.float32,
        use_flash_attention=False, remat=False)
    params = perturbed(model.init_params(jax.random.PRNGKey(4)), 5)
    ids = np.random.default_rng(6).integers(0, ref.vocab_size(cfg), size=80,
                                            dtype=np.int32)
    return types.SimpleNamespace(ref=ref, cfg=cfg, model=model, params=params,
                                 ids=ids)


@pytest.fixture(scope="module")
def tiny():
    return case()


def reference(c, params=None):
    """The PLAIN pass's logits (no near-tie resolved the other way)."""
    return np.asarray(c.ref.reference_forward(
        c.params if params is None else params, c.ids, c.cfg)[0])


def program_logits(model, params, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.apply(params, ids[None])[0])


# ------------------------------------------------ program against reference
def test_the_tiny_file_has_one_whole_period(tiny):
    c = tiny.model.config
    assert c.pattern == ("attn", "kda", "kda", "kda") and c.n_layer == 4
    assert (c.n_head, c.n_kv_head, c.head_dim) == (4, 2, 32)     # not 64 / 4
    assert (c.kda_heads, c.kda_head_dim, c.kda_conv) == (4, 16, 4)
    assert not c.use_rope and c.attn_gate and c.n_shared_experts == 1
    assert c.router_scoring == "sigmoid" and c.routed_scaling_factor == 1
    assert (c.n_experts, c.experts_held, c.n_experts_per_tok) == (32, (8, 8), 4)
    assert c.norm_topk_prob and c.n_moe_layers == 4


def test_program_matches_the_reference_in_float32(tiny):
    """``apply``: the trunk's periods, the chunked form against the
    reference's token scan, the share."""
    want = reference(tiny)
    got = program_logits(tiny.model, tiny.params, tiny.ids)
    assert want.std() > 0.1
    np.testing.assert_allclose(got, want, atol=TOL_PROGRAM, rtol=0)


def test_prefill_then_decode_matches_the_full_pass(tiny):
    """``prefill`` of 67 tokens (one chunk and three positions), then 12
    ``decode_step``s through the state, the window and the one softmax
    layer's K/V, teacher-forced: LOGITS against the reference's one pass
    over all 80."""
    want = reference(tiny)
    ids = jnp.asarray(tiny.ids)[None]
    with jax.default_matmul_precision("highest"):
        lg, cache = tiny.model.prefill(tiny.params, ids[:, :67],
                                       tiny.model.init_cache(1, 96))
        got = [lg[0]]
        for t in range(67, 79):
            lg, cache = tiny.model.decode_step(tiny.params, ids[:, t], cache)
            got.append(lg[0])
    np.testing.assert_allclose(np.stack(got), want[66:79], atol=TOL_PROGRAM,
                               rtol=0)
    assert set(cache) == {"k", "v", "kda_state", "kda_conv", "pos",
                          "expert_tokens"}
    assert cache["k"].shape[0] == 1 and cache["kda_state"].shape[0] == 3


def test_generate_chooses_what_the_reference_would(tiny):
    """``init_inference`` -> ``generate()`` in float32, greedy: every chosen
    token's reference logit is the reference's best to within TOL_PROGRAM
    (teacher-forced through the reference)."""
    import deepspeed_tpu

    engine = deepspeed_tpu.init_inference(tiny.model, dtype="fp32",
                                          params=tiny.params,
                                          max_out_tokens=128)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(engine.generate(tiny.ids[None, :24],
                                         max_new_tokens=12))[0]
    np.testing.assert_array_equal(out[:24], tiny.ids[:24])
    rows = np.asarray(tiny.ref.reference_logits(
        tiny.params, out, tiny.cfg))[23:35]
    short = rows.max(axis=-1) - rows[np.arange(12), out[24:]]
    assert short.max() <= TOL_PROGRAM, short


def test_program_in_bf16_stays_within_what_bf16_can_do(tiny):
    """bf16 activations against the float32 plain pass: every token within
    TOL_BF16 but the few whose router flipped on rounding."""
    model = type(tiny.model)(dataclasses.replace(tiny.model.config,
                                                 dtype=jnp.bfloat16))
    got = np.asarray(model.apply(tiny.params, tiny.ids[None])[0])
    gap = np.abs(got - reference(tiny)).max(axis=-1)
    assert TOL_PROGRAM < np.median(gap) < TOL_BF16, gap
    assert (gap > TOL_BF16).sum() <= FLIPS, gap


# ------------------------------------- near-ties of the router (reference)
def test_a_resolved_pass_sees_the_earlier_positions_through_the_plain_pass(
        tiny):
    """``others`` = the plain pass's own layer inputs and resolution 0 is the
    plain pass again; a changed input at ONE position then moves only that
    position's logits (its row is its own, every later position reads the
    plain pass's rows: K/V, the window, the state)."""
    plain, kept = tiny.ref.reference_forward(tiny.params, tiny.ids, tiny.cfg)
    zero = jnp.zeros(80, jnp.int32)
    again = tiny.ref.reference_forward(tiny.params, tiny.ids, tiny.cfg, zero,
                                       others=kept["inputs"])[0]
    np.testing.assert_allclose(again, plain, atol=1e-6)
    assert kept["inputs"].shape == (4, 80, 64)
    moved = tiny.ids.copy()
    moved[40] = (moved[40] + 1) % 512
    other = tiny.ref.reference_forward(tiny.params, moved, tiny.cfg, zero,
                                       others=kept["inputs"])[0]
    gap = np.abs(np.asarray(other - plain)).max(axis=-1)
    assert gap[40] > 1e-3 and np.delete(gap, 40).max() < 1e-6


def test_reference_logits_holds_a_token_to_the_best_resolution(tiny):
    """The envelope is never below the plain pass, equals it where no held
    expert lies at the cut, and admits at an open position the token another
    valid resolution makes the best."""
    plain, kept = tiny.ref.reference_forward(tiny.params, tiny.ids, tiny.cfg)
    plain = np.asarray(plain)
    held = np.asarray(tiny.ref.reference_logits(tiny.params, tiny.ids,
                                                tiny.cfg))
    assert (held >= plain - 1e-6).all()
    np.testing.assert_allclose(held.max(-1), plain.max(-1), atol=1e-6)
    from benchmark.families.pangu_ultra_moe import TIE

    open_ = (np.asarray(kept["distance"]) <= TIE).any(axis=(0, 2))
    assert 0 < open_.sum() < 80
    np.testing.assert_allclose(held[~open_], plain[~open_], atol=5e-6)
    assert np.abs(held[open_] - plain[open_]).max() > 1e-4


def test_the_reference_can_be_asked_for_the_last_positions_alone(tiny):
    """``last``: the same logits, the head run over those rows only (what a
    32,768-token check on the chip has room for)."""
    whole = np.asarray(tiny.ref.reference_logits(tiny.params, tiny.ids,
                                                 tiny.cfg))
    tail = np.asarray(tiny.ref.reference_logits(tiny.params, tiny.ids,
                                                tiny.cfg, last=9))
    assert tail.shape == (9, whole.shape[1])
    np.testing.assert_allclose(tail, whole[-9:], atol=1e-6)


def test_the_witness_holds_each_broken_form_to_its_programs_limit():
    """``benchmark/kda_witness.py`` through the serve system on the CPU:
    prefill 64 = one chunk, 32 steps through the state, six forms from two
    compiled programs. At this size no broken form moves a served logit by
    the margin, so ``ok`` is false: the verdict is of the published widths,
    on the chip (PERF.md). What shows here: the dropped tap and beta move a
    logit by more than bf16 does, and a bfloat16 state, lost in the served
    program's own rounding, stands out a thousandfold in float32."""
    from benchmark import kda_witness

    out = kda_witness.witness(mf.load_json(DATA / "solar-tiny.json"), 3, 96,
                              32, True)
    forms = out["forms"]
    assert list(forms) == list(kda_witness.FORMS)
    assert out["positions_compared"] == 32 and out["ok"] is False
    sound, exact = forms["sound"], forms["float32"]
    assert sound["within_its_limit"] and exact["within_its_limit"]
    for name in ("tap_dropped", "beta_unscaled"):
        assert forms[name]["median_logit_difference"] \
            > 3 * sound["median_logit_difference"], name
    assert forms["state_bf16"]["median_logit_difference"] \
        < 1.5 * sound["median_logit_difference"]
    assert exact["median_logit_difference"] < 1e-6
    assert forms["float32_state_bf16"]["median_logit_difference"] \
        > 100 * exact["median_logit_difference"]
    # without controls: the sound served program alone, and its verdict
    alone = kda_witness.witness(mf.load_json(DATA / "solar-tiny.json"), 3,
                                96, 32, False)
    assert list(alone["forms"]) == ["sound"] and alone["ok"] is True


BROKEN = {
    "a convolution tap dropped": ("kda_blocks", lambda b: {
        **b, "kda_conv_w": b["kda_conv_w"].at[:, 0].set(0)}),
    "beta without its factor 2": ("kda_blocks", lambda b: {
        **b, "kda_b_w": b["kda_b_w"] * 0 - 1e-9}),
    "no decay": ("kda_blocks", lambda b: {
        **b, "kda_a_log": b["kda_a_log"] - 30}),
    "no output gate on KDA": ("kda_blocks", lambda b: {
        **b, "kda_g_b_w": b["kda_g_b_w"] * 0}),
    "no gate on the softmax layer": ("attn_blocks", lambda b: {
        **b, "attn_gate_w": b["attn_gate_w"] * 0}),
    "no shared expert": ("blocks", lambda b: {
        **b, "shared_down_w": b["shared_down_w"] * 0}),
    "no routed expert": ("blocks", lambda b: {
        **b, "expert_down_w": b["expert_down_w"] * 0}),
}


@pytest.mark.parametrize("control", BROKEN, ids=BROKEN.keys())
def test_broken_mathematics_fails_the_same_comparison(tiny, control):
    """A program that leaves a term out (here: computes with a leaf changed,
    against the reference on the true ones) is refused by TOL_PROGRAM."""
    stack, change = BROKEN[control]
    changed = {**tiny.params, stack: change(tiny.params[stack])}
    gap = np.abs(program_logits(tiny.model, changed, tiny.ids)
                 - reference(tiny)).max()
    assert gap > 10 * TOL_PROGRAM, (control, gap)


def test_a_state_kept_in_bf16_fails_the_same_comparison(tiny):
    """Decode through a state rounded to bf16 after every step: the error
    adds up over the steps and passes TOL_PROGRAM many times over."""
    want = reference(tiny)
    ids = jnp.asarray(tiny.ids)[None]
    with jax.default_matmul_precision("highest"):
        lg, cache = tiny.model.prefill(tiny.params, ids[:, :40],
                                       tiny.model.init_cache(1, 96))
        for t in range(40, 79):
            cache["kda_state"] = cache["kda_state"].astype(
                jnp.bfloat16).astype(jnp.float32)
            lg, cache = tiny.model.decode_step(tiny.params, ids[:, t], cache)
    assert np.abs(np.asarray(lg[0]) - want[78]).max() > 10 * TOL_PROGRAM


def test_another_share_gives_another_result(tiny):
    other = copy.deepcopy(tiny.cfg)
    other["share"]["experts_first"] = 16
    moved = np.abs(np.asarray(tiny.ref.reference_forward(
        tiny.params, tiny.ids, other)[0]) - reference(tiny)).max()
    assert moved > 100 * TOL_PROGRAM


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """32 experts in 4 shares of 8 (the published file: 320 in 8 of 40): the
    shares' routed parts + the shared expert ONCE = what a chip that holds
    all 32 gives for the layer. Each share routes over all 32 and computes
    its own 8. In the program's ``_mlp`` and in the reference's pieces."""
    from benchmark.families.pangu_ultra_moe import _route, _routed, _swiglu
    from deepspeed_tpu.models.llama import LlamaModel

    whole = LlamaModel(dataclasses.replace(tiny.model.config,
                                           experts_held=None))
    params = whole.init_params(jax.random.PRNGKey(7))
    blk = jax.tree.map(lambda x: x[1], params["blocks"])
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 50, 64))
    z = tiny.ref._sizes(tiny.cfg)
    with jax.default_matmul_precision("highest"):
        want, _ = whole._mlp(h, blk)
        shared = whole._swiglu(h, blk["shared_gate_w"], blk["shared_up_w"],
                               blk["shared_down_w"])
        parts, ref_parts = [], []
        for first in (0, 8, 16, 24):
            share = LlamaModel(dataclasses.replace(
                whole.config, experts_held=(first, 8)))
            mine = {n: (v[first:first + 8] if n in share.EXPERT_LEAVES else v)
                    for n, v in blk.items()}
            out, (sizes, _) = share._mlp(h, mine)
            assert sizes.shape == (8,)
            parts.append(out - shared)
            zs = z._replace(first=first)
            weights, chosen, *_ = _route(
                h[0], blk["router_w"], zs, True, 1.0,
                jnp.zeros(50, jnp.int32), None)
            ref_parts.append(_routed(
                h[0], weights, chosen,
                {n: mine[n][None] for n in share.EXPERT_LEAVES}, 0, zs))
        ref_shared = _swiglu(h[0], {n: blk[n][None] for n in
                                    tiny.ref.SHARED_LEAVES},
                             tiny.ref.SHARED_LEAVES, (0,), 32)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(sum(ref_parts) + ref_shared, want[0],
                               atol=2e-5, rtol=1e-5)


def test_reference_refuses_what_it_does_not_compute(tiny):
    softmax = copy.deepcopy(tiny.cfg)
    softmax["assumed_values"]["router_scoring"] = "softmax"
    with pytest.raises(SystemExit, match="sigmoid"):
        tiny.ref.reference_logits(tiny.params, tiny.ids, softmax)
    for over in ({"use_rope": True}, {"use_gqa_gate": False},
                 {"kda_use_full_proj": True}, {"kda_allow_neg_eigval": False},
                 {"first_k_dense_replace": 1}, {"tie_word_embeddings": True}):
        cfg = copy.deepcopy(tiny.cfg)
        cfg["model"].update(over)
        with pytest.raises(SystemExit, match="solar_open2"):
            tiny.ref.reference_logits(tiny.params, tiny.ids, cfg)
        with pytest.raises(SystemExit, match="solar_open2"):
            tiny.ref.build_model(cfg, "serve")


# ----------------------------- the published configuration and its counts
# upstage/Solar-Open2-250B config.json, as the catalog beside the
# model-configs guide holds it: written HERE, so that the test reads nothing
# outside the repository
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8}
HELD = {"num_hidden_layers": 4, "gqa_layers": [0], "n_routed_experts": 40,
        "vocab_size": 24576, "max_position_embeddings": 36864}
SOURCE = "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"
REAL_CELL = "solar-open2-250b.serve.doc32k.c1"


def published():
    return mf.load_json(mf.BENCH_DIR / "configs" / "solar-open2-250b.json")


def test_published_widths():
    """Every key of the published config under ``model`` and at the file's
    top level: the unreduced ones value for value, the reduced ones at what
    is held here with the published values under ``published``; the router
    is 320 wide and picks 8; the program's model has those sizes."""
    cfg = published()
    assert cfg["source"] == SOURCE and "train" not in cfg
    assert cfg["reduced"] == list(HELD) and cfg["family"] == "solar_open2"
    assert set(cfg["model"]) == set(PUBLISHED)
    for key, value in PUBLISHED.items():
        want = HELD.get(key, value)
        assert cfg["model"][key] == want and cfg[key] == want, key
    assert cfg["published"] == {k: PUBLISHED[k] for k in HELD}
    assert set(cfg["reduced_why"]) == set(HELD)
    assert set(cfg["assumed"]) >= {"router_scoring", "hidden_act",
                                   "softmax_gate", "kda", "kda_init", "init"}
    assert "intermediate_size" in cfg["unused"]
    assert cfg["assumed_values"] == {"router_scoring": "sigmoid",
                                     "hidden_act": "silu",
                                     "embedding_std": 1.0}
    assert "8-chip" in cfg["deployment"]
    assert cfg["share"]["chips_per_layer"] * HELD["n_routed_experts"] == 320
    assert cfg["share"]["experts_first"] % 40 == 0
    assert cfg["serve"]["max_out_tokens"] == 36864
    c = families.get("solar_open2").build_model(cfg, "serve").config
    assert (c.n_embd, c.n_layer, c.n_head, c.n_kv_head, c.head_dim) == \
        (4096, 4, 64, 8, 128)
    assert (c.kda_heads, c.kda_head_dim, c.kda_conv) == (64, 128, 4)
    assert c.pattern == ("attn", "kda", "kda", "kda") and c.gqa_layers == (0,)
    assert (c.n_experts, c.n_experts_per_tok, c.experts_held) == \
        (320, 8, (120, 40))
    assert (c.intermediate_size, c.n_shared_experts, c.n_dense_layers) == \
        (1280, 1, 0)
    assert c.router_scoring == "sigmoid" and c.routed_scaling_factor == 1
    assert c.norm_topk_prob and not c.use_rope and c.attn_gate
    assert c.vocab_size == 24576 and c.n_positions == 36864
    assert c.param_dtype == jnp.bfloat16 and not c.tie_embeddings
    assert c.num_params() == 3_308_352_064


def test_counts_at_the_published_sizes():
    """The numbers ISSUE 33 sized the cell by, from the family's functions."""
    fam, cfg = families.get("solar_open2"), published()
    assert fam.kda_params(cfg) == pytest.approx(137.6e6, rel=0.001)
    assert fam.softmax_params(cfg) == pytest.approx(109.1e6, rel=0.001)
    assert fam.held_params(cfg) == 3_308_352_064            # 3.31 B
    assert fam.experts_met(cfg) == 1.0                      # 8 x 40 / 320
    assert fam.weight_bytes(cfg) == pytest.approx(1.508e9, rel=0.002)
    assert fam.kv_bytes_per_position(cfg) == 4096           # 1 x 2 x 1024 x 2
    assert fam.state_bytes_per_sequence(cfg) == 13_025_280  # 13.0 MB
    model = fam.build_model(cfg, "serve")
    cache = jax.eval_shape(lambda: model.init_cache(1, 36864))
    assert cache["k"].shape == cache["v"].shape == (1, 1, 36864, 1024)
    assert cache["kda_state"].shape == (3, 1, 64, 128, 128)
    assert cache["kda_state"].dtype == jnp.float32
    assert cache["kda_conv"].shape == (3, 1, 3, 24576)
    from deepspeed_tpu.models.common import cache_footprint

    assert cache_footprint(cache) == (4096, 13_025_280)
    # a decode step costs the same at 4k and at 32k but for ONE layer's K/V
    assert fam.decode_bytes_per_token(cfg, 32768) - \
        fam.decode_bytes_per_token(cfg, 4096) == (32768 - 4096) * 4096
    assert fam.decode_bytes_per_token(cfg, 0) == \
        fam.weight_bytes(cfg) + 2 * 13_025_280
    assert fam.decode_flops_per_token(cfg) > 2 * fam.matmul_params(cfg)
    # the state pass at the program's chunk of 64: bandwidth-bound
    T = 16384
    flops, nbytes = fam.kda_prefill_flops(cfg, T), fam.kda_prefill_bytes(cfg, T)
    assert flops == 3 * 64 * (T // 64) * 2 * 64 * (3 * 128 * 128 + 64 * 128)
    assert nbytes == 3 * 64 * ((T // 64) * (64 * (5 * 128 + 64) * 2 + 512)
                               + 2 * 128 * 128 * 4)
    assert flops / nbytes < 197e12 / 819e9
    # a 16k prefill is ~26 TFLOP of matmuls, the softmax layer's causal half-square ~4.4
    assert T * 2 * fam.matmul_params(cfg) == pytest.approx(24.7e12, rel=0.02)
    assert fam.softmax_attn_flops_fwd(cfg, T) == pytest.approx(4.4e12, rel=0.01)
    assert fam.train_flops_per_token(cfg, T) > 6 * fam.matmul_params(cfg)


# --------------------------------------------- the three per-layer metrics
KDA_METRICS = ("ttft.kda_prefill_s_per_prefill", "ttft.kda_prefill_roofline",
               "serve.state_bytes_per_sequence")


def traced_ctx(ops, spans=(), family="solar_open2", monkeypatch=None):
    """What ``run.execute`` hands a reader after a traced run: three
    prefills (4096, 8192, 16384 tokens) in the window, ``ops`` as the
    device's self seconds by HLO instruction, ``spans`` in the program's
    tracer."""
    from benchmark import program_spans
    from benchmark.recorder import Recorder

    rec = Recorder(annotate=False)
    rec.spans = [("tick", 20.0 + i, 20.5 + i,
                  {"phase": "prefill", "context": 4096 * 2 ** i})
                 for i in range(3)]
    if monkeypatch is not None:
        monkeypatch.setattr(program_spans, "_live_tracer",
                            lambda: Tracer(spans))
    return types.SimpleNamespace(
        notes={}, rec=rec, config=published(), family=families.get(family),
        peaks=PEAKS, trace_host_window=(0.0, 100.0),
        record={"requests": [{"stamps": [(1.0, 1), (2.0, 16)]}],
                "t_start": 0.0, "t_end": 100.0},
        trace={"n_devices": 1, "op_text_seconds": ops,
               "modules": {"jit_decode_chunk(7)": [0.03] * 10,
                           "jit_prefill(3)": [0.5] * 3}})


KERNEL_OPS = {
    "%kda_chunk_fwd.3 = (bf16[64,2048,128]{2,1,0}, f32[64,128,128]{2,1,0}) "
    "custom-call(%u, %w)": 0.03,
    "%flash_fwd.3 = bf16[64,8192,128]{2,1,0} custom-call(%q, %k, %v)": 0.2,
    "%fusion.9 = bf16[4096]{0} fusion(%kda_chunk_fwd.3)": 5.0}


def read_metric(name, ctx):
    spec, custom = mf.metric_spec("per_layer", name)
    return custom(ctx, spec.get("params", {}))


def test_the_kernels_roofline_counts_what_the_traced_prefills_really_had():
    ctx = traced_ctx(KERNEL_OPS)
    fam, cfg = ctx.family, ctx.config
    assert read_metric("ttft.kda_prefill_s_per_prefill", ctx) == \
        pytest.approx(0.01)
    # bandwidth-bound: the bytes of each traced prompt, averaged, at 819
    # GB/s over 10 ms a prefill
    nbytes = sum(fam.kda_prefill_bytes(cfg, t)
                 for t in (4096, 8192, 16384)) / 3
    share = read_metric("ttft.kda_prefill_roofline", ctx)
    assert share == pytest.approx(100 * nbytes / 819e9 / 0.01)
    assert ctx.notes["kda_prefill_roofline_bound"] == "memory"
    assert ctx.notes["kda_prefill_prompt_mean"] == pytest.approx(28672 / 3)
    assert 0 < share < 100


def test_state_bytes_read_the_programs_own_numbers(monkeypatch):
    spans = [
        span("request", 1.0, 2.0, cache_bytes=4128 * 4096,
             cache_positions=4128, state_bytes=13_025_280),
        span("request", 3.0, 4.0, cache_bytes=32864 * 4096,
             cache_positions=32864, state_bytes=13_025_280),
        span("request", 5.0, 6.0, cache_bytes=0, cache_positions=0,
             state_bytes=0)]
    ctx = traced_ctx({}, spans, monkeypatch=monkeypatch)
    assert read_metric("serve.state_bytes_per_sequence", ctx) == 13_025_280
    assert ctx.notes["samples"]["request~state"] == 2
    assert read_metric("serve.cache_bytes_per_position", ctx) == 4096


@pytest.mark.parametrize("name", KDA_METRICS)
def test_kda_metrics_read_nothing_where_there_is_nothing_to_read(
        name, monkeypatch):
    """The benchmark's files are laid over the PARENT too, and over cells of
    other families: no such kernel in the trace, no such function in the
    family, no device plane, spans without the new arg -> None, no raise."""
    old = [span("request", 1.0, 2.0, prompt_len=2048, new_tokens=16,
                cache_positions=2064, cache_bytes=2064 * 6400)]
    no_kernel = {k: v for k, v in KERNEL_OPS.items()
                 if not k.startswith("%kda")}
    assert read_metric(name, traced_ctx(no_kernel, old,
                                        monkeypatch=monkeypatch)) is None
    if "roofline" in name:
        assert read_metric(name, traced_ctx(
            KERNEL_OPS, old, family="olmoe", monkeypatch=monkeypatch)) is None
    off_device = traced_ctx(KERNEL_OPS, old, monkeypatch=monkeypatch)
    off_device.trace = None
    assert read_metric(name, off_device) is None

    class OldNoopTracer:
        events = []

    from benchmark import program_spans
    monkeypatch.setattr(program_spans, "_live_tracer", OldNoopTracer)
    if name.startswith("serve."):
        assert read_metric(name, traced_ctx(KERNEL_OPS)) is None


# ----------------------- the tiny configuration as a cell: manifest and run
CELL = "solar-tiny.serve.closed.tiny"


def kda_metric_entries(cells):
    """The ``per_layer`` entries of the three metrics, from their data files
    under ``benchmark/layer_metrics``. The real ``BENCHMARK.json`` does not
    list them: tests/benchmark/test_program_spans.py pins PR 24's thirteen
    as the LAST entries of ``per_layer`` (PERF.md section 7)."""
    keys = ("name", "unit", "better", "source", "layer", "moves")
    return [{**{k: mf.metric_spec("per_layer", name)[0][k] for k in keys},
             "workloads": list(cells)} for name in KDA_METRICS]


def solar_manifest():
    """``rehearsal.manifest()`` plus one entry: ``solar-tiny`` and its serve
    cell, appended to every serve metric, and the three metrics."""
    m = copy.deepcopy(rehearsal.manifest())
    body = mf.load_json(DATA / "solar-tiny.json")
    m["configs"].append({
        "name": "solar-tiny", "source": body["source"],
        "reduced": body["reduced"], "why": "rehearsal",
        "file": "tests/benchmark/data/solar-tiny.json"})
    m["workloads"].append({"name": CELL, "config": "solar-tiny",
                           "why": "rehearsal", "traffic": "serve.closed.tiny",
                           "chips": 1})
    for metric in m["end_to_end"] + m["per_layer"]:
        if any(".serve." in w for w in metric.get("workloads", [])):
            metric["workloads"].append(CELL)
    m["per_layer"] += kda_metric_entries([CELL])
    return m


def test_the_trace_script_adds_the_metric_files_for_its_cell_alone():
    from benchmark.trace_metric_files import with_metric_files

    real = mf.load_manifest()
    grown = with_metric_files(real, REAL_CELL)
    assert grown["per_layer"][:len(real["per_layer"])] == real["per_layer"]
    added = grown["per_layer"][len(real["per_layer"]):]
    # the cell reports ``ttft_p50_s`` alone (below), so the script reads the
    # files that move it: the two of the kernel. ``serve.state_bytes_per_
    # sequence`` moves ``tpot_p50_s`` and waits with ``serve.cache_bytes_per_
    # position`` for a cell that reports it (PERF.md section 7)
    names = {m["name"] for m in added}
    assert {"ttft.kda_prefill_s_per_prefill", "ttft.kda_prefill_roofline"} \
        <= names
    assert {m["moves"] for m in added} == {"ttft_p50_s"}
    assert not names & {"serve.state_bytes_per_sequence",
                        "serve.cache_bytes_per_position"}
    assert all(m["workloads"] == [REAL_CELL] for m in added)


def test_the_tiny_configuration_passes_every_manifest_check():
    m = solar_manifest()
    book = test_manifest.Book("solar", m, "tests/benchmark/data/",
                              DATA / "traffic")
    config = next(c for c in m["configs"] if c["name"] == "solar-tiny")
    test_manifest.test_config_entry_and_file(book, config)
    test_manifest.test_cell_entry_and_its_files(
        book, next(c for c in m["workloads"] if c["name"] == CELL))
    for metric in m["end_to_end"] + m["per_layer"]:
        test_manifest.test_metric_entry(book, metric)
    for metric in m["per_layer"][-len(KDA_METRICS):]:
        assert metric["name"] in KDA_METRICS
        test_manifest.test_metric_has_a_data_file_that_agrees_and_a_reader(
            metric)
    test_manifest.test_names_are_unique(book)
    for name in test_manifest.names(m, "configs", "workloads"):
        test_manifest.test_every_name_uses_only_the_allowed_characters(
            book, name)


def test_the_real_cell_is_in_the_manifest_with_the_ttft_metrics():
    """ISSUE 33's traffic, letter for letter."""
    m = mf.load_manifest()
    cell = mf.find_cell(m, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve.doc32k.c1"
    assert cell["config"] == "solar-open2-250b" and len(cell["why"]) <= 200
    assert m["workloads"][-1] == cell and m["configs"][-1]["name"] == \
        "solar-open2-250b"
    traffic = mf.load_json(mf.traffic_path(cell["traffic"]))
    assert traffic["driver"] == "closed_loop" and "rate" not in traffic
    caller, = traffic["callers"]
    assert caller["layout"] == "balanced_blocks"
    assert caller["prompt_lens"] == [4096, 8192, 16384, 24576, 32768]
    assert caller["new_tokens"] == [32, 64, 96]
    assert (traffic["sentinel_every"], traffic["warmup_requests_per_caller"],
            traffic["trace_seconds"]) == (5, 5, 8.0)
    assert published()["serve"]["serving"]["default_deadline_s"] == 30.0
    # judged on ``ttft_p50_s`` (+ ``setup_s``) and the per-layer metrics that
    # move it. NOT on ``serve_tok_s`` (a block of 15 takes ~14 s here, a 30 s
    # window holds two, and which part of a block lies at its edges moved the
    # one quotient by 6.8-8.2% in the driver's two sets of six) nor on
    # ``tpot_p50_s`` (a 40 ms tick: a host in its slow state adds ~3.5 ms to
    # it, 8.0-8.8%): a new cell may spread by half the 10% bound, the driver
    # refused the cell on both (PERF.md sections 6 and 7), and a per-layer
    # metric is listed only where the cell reports what it moves
    ends = {x["name"] for x in mf.metrics_for(m, cell["name"], "end_to_end")}
    assert ends == {"ttft_p50_s", "setup_s"}
    layers = mf.metrics_for(m, cell["name"], "per_layer")
    assert {x["moves"] for x in layers} == {"ttft_p50_s"}
    assert {x["name"] for x in layers} == {
        x["name"] for x in mf.metrics_for(
            m, "openpangu-ultra-moe-718b.serve.doc8k.c1", "per_layer")
        if x["moves"] == "ttft_p50_s"}
    # appended, nothing put in the middle
    for metric in m["end_to_end"] + m["per_layer"]:
        if REAL_CELL in metric.get("workloads", ()):
            assert metric["workloads"][-1] == REAL_CELL


def test_rehearsal_closed_loop_traced(jax_config_restored):
    """REHEARSAL, not a measurement: ``init_inference`` ->
    ``ServingFrontEnd.submit`` through ``run.execute`` on the CPU, the served
    tokens checked against this family's reference (bf16 weights; prefill of
    64 = one chunk, decode through the state). The device-trace readers find
    no device plane and are left out; what the program COUNTS is there."""
    result, info = run.execute(CELL, seed=3, seconds=1.5, trace=1,
                               manifest=solar_manifest(), platforms=("cpu",),
                               traffic_dir=DATA / "traffic")
    line = json.loads(json.dumps(result))
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 4
    assert info["check"]["worst_logit_shortfall"] <= info["check"]["margin"]
    assert info["notes"]["sentinels_compared"] > 0
    assert set(line["metrics"]) == {
        "ttft.queue_wait_p50_s", "serve.compiles_in_window", "ttft_p90_s",
        "caller_turnaround_p99_s", "serve.steady_tok_s",
        "serve.longest_callback_gap_s"}
