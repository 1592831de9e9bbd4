"""openPangu-Ultra-MoE's share (``benchmark/families/pangu_ultra_moe.py``) at
a small size on the CPU: the family's plain reference against the program's
model (``models/llama.py`` with latent attention, sandwich norm, the sigmoid
router, a share of the experts beside a shared one, two stacks) on the same
seeded weights — ``apply``, ``prefill`` + absorbed ``decode_step`` through the
latent cache, ``generate()`` and a served request through ``run.execute``;
programs with broken mathematics that the same comparison must refuse; the
published configuration against its catalog row; the counts; the four
per-layer metrics' readers; the tiny configuration through the manifest
checks."""

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
# and absorbed against un-absorbed attention (measured gaps 3e-7 to 2e-6 on
# logits that spread by 0.42). A dropped rotary key moves a logit by 1e-3, a
# dropped post-norm, shared expert or routed pair by 1e-2 and more.
TOL_PROGRAM = 2e-5
# bf16 against float32: 8 mantissa bits through 3 layers on logits that
# spread by 0.42; measured 0.012 - 0.036 a token. A router choice that
# flips on rounding swaps one expert at one token, and here that is most of
# the routed output (a share holds ~1 of a token's 4 choices, weights
# normalised to 2.5, the branch's output normalised again): measured 0.30
# at the one token of 48 where it happens. That token is counted, not hidden.
TOL_BF16, FLIPS = 5e-2, 2


def case(**model_over):
    """``pangu-tiny.json`` (``model_over`` laid over its sizes), the program's
    model built through the family module and put into float32, seeded
    weights with every gain moved off 1, ids."""
    cfg = mf.load_json(DATA / "pangu-tiny.json")
    cfg["model"].update(model_over)
    ref = families.get("pangu_ultra_moe")
    model = ref.build_model(cfg, "serve")
    model.config = dataclasses.replace(
        model.config, dtype=jnp.float32, param_dtype=jnp.float32,
        use_flash_attention=False, remat=False)
    params = perturbed(model.init_params(jax.random.PRNGKey(4)), 5)
    ids = np.random.default_rng(6).integers(0, ref.vocab_size(cfg), size=48,
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
def test_the_tiny_file_has_all_six_mechanisms_on(tiny):
    c = tiny.model.config
    assert c.mla and c.sandwich_norm and c.n_shared_experts == 1
    assert c.router_scoring == "sigmoid" and c.routed_scaling_factor == 2.5
    assert (c.n_experts, c.experts_held, c.n_experts_per_tok) == (32, (8, 8), 4)
    assert (c.n_layer, c.n_dense_layers, c.n_moe_layers) == (3, 1, 2)
    assert c.norm_topk_prob and c.rope_theta == 25600000


def test_program_matches_the_reference_in_float32(tiny):
    """``apply``: the trunk's two stacks, un-absorbed attention, the share."""
    want = reference(tiny)
    got = program_logits(tiny.model, tiny.params, tiny.ids)
    assert want.std() > 0.1
    np.testing.assert_allclose(got, want, atol=TOL_PROGRAM, rtol=0)


def test_prefill_then_absorbed_decode_matches_the_full_pass(tiny):
    """``prefill`` of 32 tokens, then 16 ``decode_step``s through the latent
    cache (absorbed attention over the cached rows), teacher-forced: LOGITS
    against the reference's one un-absorbed pass over all 48."""
    want = reference(tiny)
    ids = jnp.asarray(tiny.ids)[None]
    with jax.default_matmul_precision("highest"):
        lg, cache = tiny.model.prefill(tiny.params, ids[:, :32],
                                       tiny.model.init_cache(1, 64))
        got = [lg[0]]
        for t in range(32, 47):
            lg, cache = tiny.model.decode_step(tiny.params, ids[:, t], cache)
            got.append(lg[0])
    np.testing.assert_allclose(np.stack(got), want[31:47], atol=TOL_PROGRAM,
                               rtol=0)
    assert set(cache) == {"kv", "pos", "expert_tokens"}


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
    """Against the PLAIN pass the flipped token stands out; against the
    resolution of the near-ties that lies nearest (``resolution_logits``:
    what ``benchmark/pangu_witness.py`` shows on the chip) every token is
    within what bf16's arithmetic leaves."""
    from benchmark.pangu_witness import nearest_resolution

    model = type(tiny.model)(dataclasses.replace(tiny.model.config,
                                                 dtype=jnp.bfloat16))
    got = np.asarray(model.apply(tiny.params, tiny.ids[None])[0])
    ways = np.asarray(tiny.ref.resolution_logits(tiny.params, tiny.ids,
                                                 tiny.cfg, last=48))
    np.testing.assert_allclose(ways[0], reference(tiny), atol=TOL_PROGRAM)
    plain, matched, which = nearest_resolution(got, ways)
    assert TOL_PROGRAM < np.median(plain) < TOL_BF16, plain
    assert 0 < (plain > TOL_BF16).sum() <= FLIPS, plain
    assert matched.max() < TOL_BF16, matched
    assert (which[plain > TOL_BF16] > 0).all()


# ------------------------------------- near-ties of the router (reference)
def test_a_resolution_changes_a_choice_only_at_an_open_expert(tiny):
    """Resolution number 1 at every position: in the first routed layer the
    held expert nearest the cut changes sides where it lies within TIE (of
    the row's spread) of it, and nothing else moves; the number is a
    mixed-radix one, so a row with no open expert hands it on whole."""
    plain = tiny.ref.reference_forward(tiny.params, tiny.ids, tiny.cfg)[1]
    one = tiny.ref.reference_forward(tiny.params, tiny.ids, tiny.cfg,
                                     jnp.ones(48, jnp.int32))[1]
    chosen, distance = (np.asarray(plain[n]) for n in ("chosen", "distance"))
    other = np.asarray(one["chosen"])
    assert chosen.shape == (2, 48, 4) and distance.shape == (2, 48, 8)
    assert plain["router_logits"].shape == (2, 48, 32)
    # the distance is from midway between the 4th and 5th logit of the row
    row = np.sort(np.asarray(plain["router_logits"])[0, 7])[::-1]
    np.testing.assert_allclose(
        distance[0, 7], np.abs(np.asarray(plain["router_logits"])[0, 7, 8:16]
                               - (row[3] + row[4]) / 2) / row.std(), rtol=1e-5)
    has = lambda c: (c[..., None] == 8 + np.arange(8)).any(axis=-2)
    nearest = distance[0] == distance[0].min(axis=-1, keepdims=True)
    opened = (distance[0] <= tiny.ref.TIE) & nearest
    assert 0 < opened.any(axis=-1).sum() < 48       # some rows, not all
    # layer 0 sees the same input either way: the same distances, and the
    # held expert that changed sides is the nearest open one
    np.testing.assert_array_equal(distance[0], np.asarray(one["distance"])[0])
    np.testing.assert_array_equal(has(chosen)[0] != has(other)[0], opened)
    far = ~opened.any(axis=-1)
    np.testing.assert_array_equal(np.sort(chosen[0][far]),
                                  np.sort(other[0][far]))
    # a row closed in layer 0 spends the digit in layer 1, if open there
    moved = (has(chosen)[1] != has(other)[1]).any(axis=-1)
    assert (moved & far).any()


def test_the_reference_takes_a_programs_own_choice_among_the_held(tiny):
    """``held``: 1 takes a held expert whatever its score, 0 leaves it out,
    -1 leaves the place to the scores; the other places go to the best of
    the rest. Given its own choices back, the pass is unchanged."""
    lg, plain = tiny.ref.reference_forward(tiny.params, tiny.ids, tiny.cfg)
    chosen = np.asarray(plain["chosen"])                    # (2, 48, 4)
    own = (chosen[..., None] == 8 + np.arange(8)).any(axis=2).astype(np.int8)
    same, back = tiny.ref.reference_forward(tiny.params, tiny.ids, tiny.cfg,
                                            held=jnp.asarray(own))
    np.testing.assert_array_equal(np.sort(np.asarray(back["chosen"])),
                                  np.sort(chosen))
    np.testing.assert_allclose(same, lg, atol=TOL_PROGRAM)
    none = tiny.ref.reference_forward(
        tiny.params, tiny.ids, tiny.cfg,
        held=jnp.zeros((2, 48, 8), jnp.int8))[1]["chosen"]
    assert not ((np.asarray(none) >= 8) & (np.asarray(none) < 16)).any()
    first = np.full((2, 48, 8), -1, np.int8)
    first[..., 0] = 1                       # expert 8, wherever it ranked
    took = np.asarray(tiny.ref.reference_forward(
        tiny.params, tiny.ids, tiny.cfg, held=jnp.asarray(first))[1]["chosen"])
    assert (took == 8).any(axis=-1).all()


def test_the_resolution_numbers_meet_every_combination_of_two_open_experts(
        tiny):
    """Numbers 0-3 of a position whose way holds two open experts (two
    layers with one each, or one layer with two) are the four combinations
    of their sides."""
    per = [np.asarray(tiny.ref.reference_forward(
        tiny.params, tiny.ids, tiny.cfg,
        jnp.full(48, r, jnp.int32))[1]["chosen"]) for r in range(4)]
    has = lambda c: (c[..., None] == 8 + np.arange(8)).any(axis=-2)
    sides = np.stack([has(c).transpose(1, 0, 2).reshape(48, -1) for c in per])
    distinct = np.array([len({tuple(sides[r, t]) for r in range(4)})
                         for t in range(48)])
    assert set(distinct) <= {1, 2, 3, 4} and (distinct == 4).any()
    assert (distinct == 1).any()            # no open expert: one pass
    assert tiny.ref.RESOLUTIONS == 16


def test_a_resolution_is_of_one_positions_own_choices(tiny):
    """Every position sees the earlier ones through the PLAIN pass's latent
    rows: a position none of whose held experts is open keeps the plain
    pass's logits in every resolution, whatever the others resolve."""
    lg, plain = tiny.ref.reference_forward(tiny.params, tiny.ids, tiny.cfg)
    assert plain["latent"].shape == (2, 48, 32 + 8)
    same = tiny.ref.reference_forward(tiny.params, tiny.ids, tiny.cfg,
                                      others=plain["latent"])[0]
    np.testing.assert_allclose(same, lg, atol=TOL_PROGRAM)
    ways = np.asarray(tiny.ref.resolution_logits(tiny.params, tiny.ids,
                                                 tiny.cfg, last=48))
    closed = (np.asarray(plain["distance"]) > tiny.ref.TIE).all(axis=(0, 2))
    assert 0 < closed.sum() < 48
    np.testing.assert_allclose(ways[:, closed], np.broadcast_to(
        np.asarray(lg)[closed], ways[:, closed].shape), atol=TOL_PROGRAM)
    assert np.abs(ways[1:, ~closed] - np.asarray(lg)[~closed]).max() > 0.01
    # without it the others' choices leak into every later position
    loose = np.asarray(tiny.ref.reference_forward(
        tiny.params, tiny.ids, tiny.cfg, jnp.ones(48, jnp.int32))[0])
    after = closed & (np.arange(48) > np.nonzero(~closed)[0][0])
    assert np.abs(loose[after] - np.asarray(lg)[after]).max() > 10 * TOL_PROGRAM


def test_reference_logits_holds_a_token_to_the_best_resolution(tiny):
    """``max - logit[token]`` of ``reference_logits`` = the least, over the
    resolutions, of that resolution's own shortfall of the token; equal to
    the plain pass's wherever no resolution differs."""
    ways = np.asarray(tiny.ref.resolution_logits(tiny.params, tiny.ids,
                                                 tiny.cfg, last=48))
    held = np.asarray(tiny.ref.reference_logits(tiny.params, tiny.ids,
                                                tiny.cfg))
    toks = np.random.default_rng(8).integers(0, 512, size=48)
    at = lambda lg: lg.max(axis=-1) - lg[..., np.arange(48), toks]
    np.testing.assert_allclose(at(held), at(ways).min(axis=0), atol=1e-6)
    np.testing.assert_allclose(held.max(axis=-1), ways[0].max(axis=-1),
                               atol=1e-6)
    differs = np.abs(ways - ways[0]).max(axis=(0, 2)) > 1e-3
    assert 0 < differs.sum() < 48
    np.testing.assert_allclose(held[~differs], ways[0][~differs], atol=2e-3)
    assert len(ways) == tiny.ref.RESOLUTIONS
    # a resolution's own best token is held to nothing
    for r in range(len(ways)):
        best = ways[r].argmax(axis=-1)
        assert (held.max(axis=-1) - held[np.arange(48), best]).max() < 1e-6


BROKEN = {
    "no rotary key": lambda b: {**b, "kv_a_w": b["kv_a_w"].at[..., 32:].set(0)},
    "no shared expert": lambda b: {**b, "shared_down_w":
                                   jnp.zeros_like(b["shared_down_w"])},
    "post-norm gain doubled": lambda b: {**b, "post_attn_norm_g":
                                         2 * b["post_attn_norm_g"]},
    "the first held expert's pairs dropped": lambda b: {
        **b, "expert_down_w": b["expert_down_w"].at[:, 0].set(0)},
}


@pytest.mark.parametrize("control", BROKEN, ids=BROKEN.keys())
def test_broken_mathematics_fails_the_same_comparison(tiny, control):
    """A program that leaves a term out (here: computes with a leaf changed,
    against the reference on the true ones) is refused by TOL_PROGRAM."""
    changed = {**tiny.params, "blocks": BROKEN[control](tiny.params["blocks"])}
    gap = np.abs(program_logits(tiny.model, changed, tiny.ids)
                 - reference(tiny)).max()
    assert gap > 10 * TOL_PROGRAM, (control, gap)


def test_another_share_gives_another_result(tiny):
    """The reference is given the SAME share: told another first expert, it
    weights the held leaves by other pairs."""
    other = copy.deepcopy(tiny.cfg)
    other["share"]["experts_first"] = 16
    moved = np.abs(np.asarray(tiny.ref.reference_logits(
        tiny.params, tiny.ids, other)) - reference(tiny)).max()
    assert moved > 100 * TOL_PROGRAM


def test_reference_refuses_what_it_does_not_compute(tiny):
    softmax = copy.deepcopy(tiny.cfg)
    softmax["assumed_values"]["router_scoring"] = "softmax"
    with pytest.raises(SystemExit, match="sigmoid"):
        tiny.ref.reference_logits(tiny.params, tiny.ids, softmax)
    for over in ({"num_nextn_predict_layers": 1}, {"attention_bias": True},
                 {"num_key_value_heads": 2}, {"hidden_act": "gelu"},
                 {"rope_scaling": {"type": "yarn", "factor": 4}}):
        cfg = copy.deepcopy(tiny.cfg)
        cfg["model"].update(over)
        with pytest.raises(SystemExit, match="pangu_ultra_moe"):
            tiny.ref.reference_logits(tiny.params, tiny.ids, cfg)
        with pytest.raises(SystemExit, match="pangu_ultra_moe"):
            tiny.ref.build_model(cfg, "serve")


# ----------------------------- the published configuration and its counts
CATALOG_ROW = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 25600000,
    "routed_scaling_factor": 2.5, "sandwich_norm": True,
    "tie_word_embeddings": False, "v_head_dim": 128, "vocab_size": 153600}
HELD = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
        "n_routed_experts": 16, "vocab_size": 19200,
        "num_nextn_predict_layers": 0, "max_position_embeddings": 32768}
SOURCE = ("https://huggingface.co/FreedomIntelligence/"
          "openPangu-Ultra-MoE-718B/blob/main/config.json")


def published():
    return mf.load_json(mf.BENCH_DIR / "configs" /
                        "openpangu-ultra-moe-718b.json")


def test_published_widths():
    """Every key of the catalog row under ``model`` and at the file's top
    level: the unreduced ones value for value, the six reduced ones at what
    is held here with the row's values under ``published``; the router is
    256 wide and picks 8; the program's model has those sizes."""
    cfg = published()
    assert cfg["source"] == SOURCE and "train" not in cfg
    assert cfg["reduced"] == list(HELD) and cfg["family"] == "pangu_ultra_moe"
    assert set(cfg["model"]) == set(CATALOG_ROW)
    for key, value in CATALOG_ROW.items():
        want = HELD.get(key, value)
        assert cfg["model"][key] == want and cfg[key] == want, key
    assert cfg["published"] == {k: CATALOG_ROW[k] for k in HELD}
    assert set(cfg["assumed"]) >= {"router_scoring", "rope", "kv_b_proj",
                                   "init"}
    assert cfg["assumed_values"] == {"router_scoring": "sigmoid"}
    assert "16 chips" in cfg["deployment"] or "16-chip" in cfg["deployment"]
    assert cfg["share"]["chips_per_layer"] * HELD["n_routed_experts"] == 256
    catalog = mf.Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():    # compared only where the catalog HAS the row
        rows = [json.loads(line) for line in catalog.read_text().splitlines()]
        for row in rows:
            if row["name"] == "openPangu-Ultra-MoE-718B":
                assert row["config"] == CATALOG_ROW
                assert row["source_url"] == SOURCE
    c = families.get("pangu_ultra_moe").build_model(cfg, "serve").config
    assert (c.n_embd, c.n_layer, c.n_dense_layers, c.n_head) == \
        (7680, 5, 1, 128)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (c.n_experts, c.n_experts_per_tok, c.experts_held) == \
        (256, 8, (80, 16))
    assert (c.intermediate_size, c.dense_intermediate_size,
            c.n_shared_experts) == (2048, 18432, 1)
    assert c.router_scoring == "sigmoid" and c.routed_scaling_factor == 2.5
    assert c.norm_topk_prob and c.sandwich_norm and c.vocab_size == 19200
    assert c.param_dtype == jnp.bfloat16 and c.n_positions == 32768
    assert c.num_params() == 4_919_139_840


def test_counts_at_the_published_sizes():
    """The numbers ISSUE 31 sized the cell by, from the family's functions."""
    fam, cfg = families.get("pangu_ultra_moe"), published()
    assert fam.attention_params(cfg) == pytest.approx(196.6e6, rel=0.001)
    assert fam.held_params(cfg) == 4_919_139_840            # 4.919 B
    assert fam.experts_met(cfg) == 0.5                      # 8 x 16 / 256
    assert fam.matmul_params(cfg) == pytest.approx(1.846e9, rel=0.001)
    assert fam.weight_bytes(cfg) == pytest.approx(3.69e9, rel=0.002)
    assert fam.decode_flops_per_token(cfg) == 2 * fam.matmul_params(cfg)
    assert fam.kv_bytes_per_position(cfg) == 5760           # 5 x 576 x 2
    assert fam.decode_bytes_per_token(cfg, 1000) - \
        fam.decode_bytes_per_token(cfg, 0) == 1000 * 5760
    # the cache's own arrays hold a row of 640 lanes (576 + 64 of pad):
    # 6,400 B a position; K and V of 128 heads would be 409,600
    kv = jax.eval_shape(lambda: fam.build_model(cfg, "serve").init_cache(
        1, 8))["kv"]
    assert kv.shape == (5, 1, 8, 640) and kv.dtype == jnp.bfloat16
    assert 5 * 128 * (192 + 128) * 2 == 409_600
    T = 4096
    assert fam.mla_prefill_attn_flops(cfg, T) == \
        5 * 128 * T * T / 2 * 2 * (192 + 128)
    assert fam.mla_decode_attn_flops(cfg, T) == 5 * T * 128 * (576 + 512) * 2
    assert fam.mla_decode_attn_bytes(cfg, T) == 5 * T * 576 * 2
    # the absorbed kernel sits ON the v5e's ridge: 242 FLOP/byte against 240
    ridge = fam.mla_decode_attn_flops(cfg, T) / fam.mla_decode_attn_bytes(cfg, T)
    assert ridge == pytest.approx(241.8, abs=0.1)
    # a 4,096-token prefill is ~17.4 TFLOP; attention alone ~20% of it
    total = T * 2 * fam.matmul_params(cfg) + fam.mla_prefill_attn_flops(cfg, T)
    assert total == pytest.approx(18.6e12, rel=0.02)
    assert fam.mla_prefill_attn_flops(cfg, T) / total == \
        pytest.approx(0.185, abs=0.01)
    assert fam.train_flops_per_token(cfg, T) > 6 * fam.matmul_params(cfg)


# ---------------------------------------------- the four per-layer metrics
MLA_METRICS = ("ttft.mla_prefill_attn_roofline",
               "tpot.mla_decode_attn_roofline",
               "serve.cache_bytes_per_position", "serve.moe_held_pair_share")
PEAKS = mf.load_json(mf.BENCH_DIR / "peaks.json")["TPU v5 lite"]


class Tracer:
    wrapped = False

    def __init__(self, spans):
        self._spans = spans

    def snapshot(self):
        return list(self._spans)


def span(name, t0, t1, cat="serving", **args):
    return types.SimpleNamespace(name=name, cat=cat, t0=t0, t1=t1, id=id(args),
                                 parent=None, trace=None, args=args)


def traced_ctx(ops, spans=(), family="pangu_ultra_moe", monkeypatch=None):
    """What ``run.execute`` hands a reader after a traced run: ten decode
    chunks of 16 tokens at context 4,000 and three prefills (2048, 4096, 8192
    tokens) in the window, ``ops`` as the device's self seconds by HLO
    instruction, ``spans`` in the program's tracer."""
    from benchmark import program_spans
    from benchmark.recorder import Recorder

    rec = Recorder(annotate=False)
    rec.spans = [("tick", 1.0 + i, 1.5 + i, {"phase": "decode", "context": 4000})
                 for i in range(10)]
    rec.spans += [("tick", 20.0 + i, 20.5 + i,
                   {"phase": "prefill", "context": 2048 * 2 ** i})
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
               "modules": {"jit_decode_chunk(7)": [0.08] * 10,
                           "jit_prefill(3)": [0.5] * 3}})


KERNEL_OPS = {
    "%flash_fwd.3 = bf16[128,8192,128]{2,1,0} custom-call(%q, %k, %v)": 0.45,
    "%latent_decode_attn.5 = bf16[1,128,512]{2,1,0} custom-call(%a)": 0.008,
    "%fusion.9 = bf16[7680]{0} fusion(%latent_decode_attn.5)": 5.0}


def read_metric(name, ctx):
    spec, custom = mf.metric_spec("per_layer", name)
    return custom(ctx, spec.get("params", {}))


def test_attention_rooflines_count_what_the_traced_ticks_really_had():
    ctx = traced_ctx(KERNEL_OPS)
    fam, cfg = ctx.family, ctx.config
    # prefill: the FLOPs of each traced prompt, averaged (not at the mean
    # prompt: the square is convex), at the bf16 peak, over 0.15 s a prefill
    flops = sum(fam.mla_prefill_attn_flops(cfg, t)
                for t in (2048, 4096, 8192)) / 3
    assert read_metric("ttft.mla_prefill_attn_roofline", ctx) == \
        pytest.approx(100 * flops / 197e12 / 0.15)
    assert ctx.notes["mla_prefill_prompt_mean"] == pytest.approx(14336 / 3)
    # decode: 0.8 ms a chunk of 16 tokens at a mean context of 4007.5; the
    # FLOPs' time (242 FLOP/byte) edges out the bytes' (the ridge is 240.5)
    share = read_metric("tpot.mla_decode_attn_roofline", ctx)
    least = max(fam.mla_decode_attn_flops(cfg, 4007.5) / 197e12,
                fam.mla_decode_attn_bytes(cfg, 4007.5) / 819e9)
    assert share == pytest.approx(100 * least / (0.0008 / 16))
    assert ctx.notes["mla_decode_attn_roofline_bound"] == "compute"
    assert ctx.notes["mla_decode_context_mean"] == 4007.5
    assert 0 < share < 100


def test_cache_bytes_and_held_share_read_the_programs_own_numbers(monkeypatch):
    spans = [
        span("request", 1.0, 2.0, cache_bytes=2064 * 6400,
             cache_positions=2064),
        span("request", 3.0, 4.0, cache_bytes=8240 * 6400,
             cache_positions=8240),
        span("request", 5.0, 6.0, cache_bytes=0, cache_positions=0),
        span("moe/expert_tokens", 2.0, 2.0, cat="moe", held_first=80, held=16,
             counts=[[10] * 16] * 4, routed_pairs=4 * 2064 * 8),
        span("moe/expert_tokens", 4.0, 4.0, cat="moe", held_first=80, held=16,
             counts=[[30] * 16] * 4, routed_pairs=4 * 8240 * 8)]
    ctx = traced_ctx({}, spans, monkeypatch=monkeypatch)
    assert read_metric("serve.cache_bytes_per_position", ctx) == 6400
    assert ctx.notes["samples"]["request~cache"] == 2
    assert read_metric("serve.moe_held_pair_share", ctx) == pytest.approx(
        100 * 40 * 16 * 4 / (4 * (2064 + 8240) * 8))
    assert ctx.notes["moe_held"] == [80, 16]
    assert ctx.notes["samples"]["moe/expert_tokens~share"] == 2


@pytest.mark.parametrize("name", MLA_METRICS)
def test_mla_metrics_read_nothing_where_there_is_nothing_to_read(
        name, monkeypatch):
    """The benchmark's files are laid over the PARENT too, and over cells of
    other families: no such kernel in the trace, no such function in the
    family, no device plane, spans without the new args, instants without
    ``routed_pairs`` -> None, no raise."""
    old = [span("request", 1.0, 2.0, prompt_len=2048, new_tokens=16),
           span("moe/expert_tokens", 2.0, 2.0, cat="moe",
                counts=[[10] * 64] * 16)]
    no_kernel = {k: v for k, v in KERNEL_OPS.items() if k.startswith("%fusion")}
    assert read_metric(name, traced_ctx(no_kernel, old,
                                        monkeypatch=monkeypatch)) is None
    if "roofline" in name:
        assert read_metric(name, traced_ctx(
            KERNEL_OPS, old, family="olmoe", monkeypatch=monkeypatch)) is None
    off_device = traced_ctx(KERNEL_OPS, old, monkeypatch=monkeypatch)
    off_device.trace = None
    if name != "serve.moe_held_pair_share":     # a count, device or no device
        assert read_metric(name, off_device) is None

    class OldNoopTracer:
        events = []

    from benchmark import program_spans
    monkeypatch.setattr(program_spans, "_live_tracer", OldNoopTracer)
    if not name.endswith("roofline"):
        assert read_metric(name, traced_ctx(KERNEL_OPS)) is None


# ----------------------- the tiny configuration as a cell: manifest and run
CELL = "pangu-tiny.serve.closed.tiny"


def mla_metric_entries(cells):
    """The ``per_layer`` entries of the four metrics, from their data files
    under ``benchmark/layer_metrics``. The real ``BENCHMARK.json`` does not
    list them: tests/benchmark/test_program_spans.py pins PR 24's thirteen as
    the LAST entries of ``per_layer``, the driver reads an entry put in the
    middle as a change to what was there, and a ``model_config`` PR edits no
    file the benchmark has (PERF.md section 7)."""
    keys = ("name", "unit", "better", "source", "layer", "moves")
    return [{**{k: mf.metric_spec("per_layer", name)[0][k] for k in keys},
             "workloads": list(cells)} for name in MLA_METRICS]


def pangu_manifest():
    """``rehearsal.manifest()`` plus one entry: ``pangu-tiny`` and its serve
    cell, appended to every serve metric, and the four metrics."""
    m = copy.deepcopy(rehearsal.manifest())
    body = mf.load_json(DATA / "pangu-tiny.json")
    m["configs"].append({
        "name": "pangu-tiny", "source": body["source"],
        "reduced": body["reduced"], "why": "rehearsal",
        "file": "tests/benchmark/data/pangu-tiny.json"})
    m["workloads"].append({"name": CELL, "config": "pangu-tiny",
                           "why": "rehearsal", "traffic": "serve.closed.tiny",
                           "chips": 1})
    for metric in m["end_to_end"] + m["per_layer"]:
        if any(".serve." in w for w in metric.get("workloads", [])):
            metric["workloads"].append(CELL)
    m["per_layer"] += mla_metric_entries([CELL])
    return m


def test_the_trace_script_adds_the_metric_files_for_its_cell_alone():
    """``benchmark/trace_metric_files.py``: the means, on the chip, of
    reading the metrics that are files and not entries."""
    from benchmark.trace_metric_files import with_metric_files

    real = mf.load_manifest()
    cell = "openpangu-ultra-moe-718b.serve.doc8k.c1"
    grown = with_metric_files(real, cell)
    assert grown["per_layer"][:len(real["per_layer"])] == real["per_layer"]
    added = grown["per_layer"][len(real["per_layer"]):]
    assert set(MLA_METRICS) <= {m["name"] for m in added}
    assert all(m["workloads"] == [cell] for m in added)
    assert with_metric_files(real, "gpt2-760m.train.z1")["per_layer"] == \
        real["per_layer"]
    assert len(real["per_layer"]) == len(mf.load_manifest()["per_layer"])


def test_the_tiny_configuration_passes_every_manifest_check():
    m = pangu_manifest()
    book = test_manifest.Book("pangu", m, "tests/benchmark/data/",
                              DATA / "traffic")
    config = next(c for c in m["configs"] if c["name"] == "pangu-tiny")
    test_manifest.test_config_entry_and_file(book, config)
    test_manifest.test_cell_entry_and_its_files(
        book, next(c for c in m["workloads"] if c["name"] == CELL))
    for metric in m["end_to_end"] + m["per_layer"]:
        test_manifest.test_metric_entry(book, metric)
    for metric in m["per_layer"][-len(MLA_METRICS):]:
        assert metric["name"] in MLA_METRICS
        test_manifest.test_metric_has_a_data_file_that_agrees_and_a_reader(
            metric)
    test_manifest.test_names_are_unique(book)
    for name in test_manifest.names(m, "configs", "workloads"):
        test_manifest.test_every_name_uses_only_the_allowed_characters(
            book, name)


def test_the_real_cell_is_in_the_manifest_with_the_serve_metrics():
    m = mf.load_manifest()
    cell = mf.find_cell(m, "openpangu-ultra-moe-718b.serve.doc8k.c1")
    assert cell["chips"] == 1 and cell["traffic"] == "serve.doc8k.c1"
    traffic = mf.load_json(mf.traffic_path(cell["traffic"]))
    caller, = traffic["callers"]
    assert caller["layout"] == "balanced_blocks"
    assert caller["prompt_lens"] == [2048, 3072, 4096, 6144, 8192]
    assert caller["new_tokens"] == [16, 32, 48]
    assert (traffic["sentinel_every"], traffic["warmup_requests_per_caller"],
            traffic["trace_seconds"]) == (5, 5, 8.0)
    names = {x["name"] for x in mf.metrics_for(m, cell["name"], "end_to_end")}
    assert names == {"serve_tok_s", "ttft_p50_s", "tpot_p50_s", "setup_s"}
    layers = {x["name"] for x in mf.metrics_for(m, cell["name"], "per_layer")}
    assert layers == {x["name"] for x in mf.metrics_for(
        m, "olmoe-1b-7b.serve.doc4k.c1", "per_layer")}


@pytest.fixture
def jax_config_restored():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_rehearsal_closed_loop_traced(jax_config_restored):
    """REHEARSAL, not a measurement: ``init_inference`` ->
    ``ServingFrontEnd.submit`` through ``run.execute`` on the CPU, the served
    tokens checked against this family's reference (bf16 weights). The
    device-trace and program-span readers find no device plane and are left
    out; the held share is a COUNT of the program's, and is there."""
    result, info = run.execute(CELL, seed=3, seconds=1.5, trace=1,
                               manifest=pangu_manifest(), platforms=("cpu",),
                               traffic_dir=DATA / "traffic")
    line = json.loads(json.dumps(result))
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 4
    assert info["check"]["worst_logit_shortfall"] <= info["check"]["margin"]
    assert info["notes"]["sentinels_compared"] > 0
    assert set(line["metrics"]) == {
        "ttft.queue_wait_p50_s", "serve.compiles_in_window", "ttft_p90_s",
        "caller_turnaround_p99_s", "serve.steady_tok_s",
        "serve.longest_callback_gap_s", "serve.moe_held_pair_share"}
    share = line["metrics"]["serve.moe_held_pair_share"]
    assert share["unit"] == "%" and 0 < share["value"] < 100
    assert info["notes"]["moe_held"] == [8, 8]


def test_the_witness_runs_the_tiny_configuration(jax_config_restored, capsys):
    """REHEARSAL of ``benchmark/pangu_witness.py`` on the CPU: the real
    ``ServeSystem.check``, the plain statistic beside it, the teacher-forced
    positions against the nearest resolution; a control's leaves."""
    from benchmark import pangu_witness

    # at this size a control may pass the check (a dropped rotary key moves
    # a logit by 1e-3): the exit code is the chip's to judge
    assert pangu_witness.main(["--config", "pangu-tiny", "--seeds", "3,5",
                               "--tokens", "24", "--controls"],
                              manifest=pangu_manifest()) in (0, 1)
    rows = [json.loads(part.splitlines()[0]) for part in
            capsys.readouterr().out.split("WITNESS ")[1:]]
    assert [r["seed"] for r in rows] == [3] * 5 + [5]
    assert [r.get("control") for r in rows[1:5]] == list(
        pangu_witness.CONTROLS)
    assert all(-1 <= r["row_correlation_median"] <= 1 for r in rows[1:5])
    for row in (rows[0], rows[5]):
        assert row["check_ok"] and row["positions"] == 24
        assert row["resolutions"] == 16
        assert row["gap_matched_max"] <= row["gap_plain_max"] < 1.0
        assert row["gap_on_choices_max"] <= row["gap_plain_max"] + 1e-6
        assert row["worst_logit_shortfall"] <= row["plain_shortfall"] + 1e-6
    params = case().params
    broken = pangu_witness.broken_stacks(params, "no rotary key", 32)
    assert float(jnp.abs(broken["blocks"]["kv_a_w"][..., 32:]).max()) == 0
    assert float(jnp.abs(broken["dense_blocks"]["kv_a_w"][..., 32:]).max()) == 0
    assert broken["blocks"]["q_a_w"] is params["blocks"]["q_a_w"]
    rounded = pangu_witness.broken_stacks(
        params, "float8 attention and shared expert", 32)
    assert rounded["blocks"]["o_w"].dtype == params["blocks"]["o_w"].dtype
    assert float(jnp.abs(rounded["blocks"]["o_w"]
                         - params["blocks"]["o_w"]).max()) > 0
    assert rounded["blocks"]["expert_up_w"] is params["blocks"]["expert_up_w"]
