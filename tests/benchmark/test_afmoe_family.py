"""Trinity-Mini's share (``benchmark/families/afmoe.py``) at a small size on
the CPU: the family's plain reference against the program's model
(``models/llama.py`` with window and full softmax layers in one pattern
behind a leading dense layer, per-head q/k norm, the output gate, four norms,
the embedding multiplier, a biased sigmoid router, a share of the experts
beside a shared one) on the same seeded weights — logits, loss AND
gradients, ``prefill`` + ``decode_step`` under the window, a train cell
through ``run.execute``; programs with broken mathematics that the same
comparison must refuse; the shares of one layer, forward and backward; the
published configuration with the published numbers written HERE; the counts;
the cell's files through the manifest checks; the witness at a small size."""

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
from tests.benchmark.test_pangu_family import jax_config_restored  # noqa: F401
from tests.benchmark.test_reference import perturbed

DATA = rehearsal.DATA
T = 80              # five windows of 16: the window binds from position 16 on
# float32 at "highest" on both sides: what is left is the order of the sums
# (measured gaps 2e-6 on logits that spread by 0.43, 3e-7 on the loss, 1e-6
# of a gradient's norm). A window off by one key moves a logit by 1e-2, a
# rotated full layer by 1e-1, a dropped bias, gate, norm or shared expert by
# 1e-2 and more.
TOL_LOGITS, TOL_LOSS, TOL_GRAD = 5e-5, 5e-6, 1e-4


def case(**model_over):
    """``trinity-tiny.json`` (``model_over`` laid over its sizes), the
    program's model built through the family module and put into float32,
    seeded weights with every gain and the bias moved, ids."""
    cfg = mf.load_json(DATA / "trinity-tiny.json")
    cfg["model"].update(model_over)
    ref = families.get("afmoe")
    model = ref.build_model(cfg, "train")
    model.config = dataclasses.replace(
        model.config, dtype=jnp.float32, use_flash_attention=False,
        remat=False)
    params = perturbed(model.init_params(jax.random.PRNGKey(4)), 5)
    ids = np.random.default_rng(6).integers(0, ref.vocab_size(cfg), size=T,
                                            dtype=np.int32)
    return types.SimpleNamespace(ref=ref, cfg=cfg, model=model, params=params,
                                 ids=ids)


@pytest.fixture(scope="module")
def tiny():
    return case()


def reference(c, params=None, **broken):
    return np.asarray(c.ref.reference_logits(
        c.params if params is None else params, c.ids, c.cfg, **broken))


def program_logits(model, params, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.apply(params, ids[None])[0])


# ------------------------------------------------ program against reference
def test_the_tiny_file_has_a_dense_layer_and_one_whole_period(tiny):
    c = tiny.model.config
    assert c.kinds == ("win", "win", "attn", "win", "win")
    assert c.stack_pattern(0, 1) == ("win",)
    assert c.pattern == ("win", "attn", "win", "win")
    assert (c.n_layer, c.n_dense_layers, c.sliding_window) == (5, 1, 16)
    assert (c.n_head, c.n_kv_head, c.head_dim) == (4, 2, 16)
    assert c.qk_norm == "head" and c.attn_gate and c.sandwich_norm
    assert not c.global_rope and c.use_rope and c.embed_scale == 8.0
    assert c.router_scoring == "sigmoid" and c.routed_scaling_factor == 2.826
    assert (c.n_experts, c.experts_held, c.n_experts_per_tok) == (32, (8, 8), 4)
    assert c.router_bias and c.router_bias_rate == 0.001
    assert tiny.params["blocks"]["router_bias"].shape == (4, 32)
    assert tiny.params["blocks"]["q_norm_g"].shape == (4, 16)
    assert tiny.params["dense_blocks"]["gate_w"].shape == (1, 64, 96)
    assert float(jnp.abs(tiny.params["blocks"]["router_bias"]).min()) > 0


def test_program_matches_the_reference_in_float32(tiny):
    """``apply``: both stacks, each walking its own phase of the pattern,
    the window, the rotary embedding by kind, the biased choice, the share."""
    want = reference(tiny)
    got = program_logits(tiny.model, tiny.params, tiny.ids)
    assert want.std() > 0.3
    np.testing.assert_allclose(got, want, atol=TOL_LOGITS, rtol=0)


def test_loss_and_gradients_match_the_reference(tiny):
    """``module.loss`` (remat 'attn', the chunked head) and its gradient,
    EVERY leaf, against ``jax.grad`` of the reference's loss."""
    model = type(tiny.model)(dataclasses.replace(tiny.model.config,
                                                 remat="attn"))
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(model.loss)(
            tiny.params, {"input_ids": tiny.ids[None]})
    want, ref_grads = jax.value_and_grad(tiny.ref.reference_loss)(
        tiny.params, tiny.ids, tiny.cfg)
    assert float(got) == pytest.approx(float(want), abs=TOL_LOSS)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:       # selection only: no gradient
            assert not np.asarray(g).any() and not np.asarray(r).any()
            continue
        assert np.linalg.norm(r) > 0, name
        assert np.linalg.norm(g - r) <= TOL_GRAD * np.linalg.norm(r), name


@pytest.mark.parametrize("broken,least", [
    ({"window_off": True}, 1e-2), ({"rotate_full": True}, 1e-2),
    ({"no_bias": True}, 1e-2)], ids=lambda x: str(x))
def test_a_broken_reference_is_far_from_the_program(tiny, broken, least):
    """What the comparison rests on: the window, the full layers' missing
    rotation and the bias each move the logits by far more than TOL."""
    got = program_logits(tiny.model, tiny.params, tiny.ids)
    assert np.abs(reference(tiny, **broken) - got).max() > least


def test_prefill_then_decode_under_the_window_matches_the_full_pass(tiny):
    """``prefill`` of 50 tokens (the window already binds), then 29
    ``decode_step``s over a whole-context cache with the window's slots,
    teacher-forced: LOGITS against the reference's one pass over all 80."""
    want = reference(tiny)
    ids = jnp.asarray(tiny.ids)[None]
    with jax.default_matmul_precision("highest"):
        lg, cache = tiny.model.prefill(tiny.params, ids[:, :50],
                                       tiny.model.init_cache(1, 96))
        got = [lg[0]]
        for t in range(50, 79):
            lg, cache = tiny.model.decode_step(tiny.params, ids[:, t], cache)
            got.append(lg[0])
    np.testing.assert_allclose(np.stack(got), want[49:79], atol=TOL_LOGITS,
                               rtol=0)
    assert cache["k"].shape[0] == 5 and cache["expert_tokens"].shape == (4, 8)


# ------------------------------------------------------- the routed layer
def test_the_bias_changes_the_choice_and_never_the_weights():
    from deepspeed_tpu.moe.dropless import route_topk

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (64, 32))
    w = jax.random.normal(keys[1], (32, 16)) * 0.3
    bias = jax.random.normal(keys[2], (16,)) * 0.2
    kw = dict(scoring="sigmoid", scale=2.5)
    probs, plain_w, plain_e = route_topk(x, w, 4, True, **kw)
    _, biased_w, biased_e = route_topk(x, w, 4, True, bias=bias, **kw)
    assert (np.sort(plain_e, -1) != np.sort(biased_e, -1)).any()
    # the choice is by s + b ...
    want_e = np.argsort(-(np.asarray(probs) + np.asarray(bias)), -1)[:, :4]
    assert (np.sort(want_e, -1) == np.sort(biased_e, -1)).all()
    # ... the weights are s at the chosen experts, normalised, scaled
    s = np.take_along_axis(np.asarray(probs), np.asarray(biased_e), -1)
    np.testing.assert_allclose(biased_w, s / s.sum(-1, keepdims=True) * 2.5,
                               rtol=1e-6)
    # a zero bias is no bias; a bias takes no gradient
    _, zero_w, zero_e = route_topk(x, w, 4, True, bias=jnp.zeros(16), **kw)
    assert (zero_e == plain_e).all() and (zero_w == plain_w).all()
    g = jax.grad(lambda b: jnp.sum(route_topk(x, w, 4, True, bias=b,
                                              **kw)[1] ** 2))(bias)
    assert not np.asarray(g).any()
    # the four arguments a softmax router always had still work
    assert route_topk(x, w, 4, False)[1].shape == (64, 4)


def test_the_shares_add_up_to_the_uncut_layer_forward_and_backward():
    """One routed layer of the tiny model, uncut (32 experts) and as the
    four shares of 8: the routed outputs of the shares, with the shared
    expert counted once, add up to the uncut layer's output, and so do the
    gradients of ``router_w`` (a pair held elsewhere adds exact zeros)."""
    c = case()
    whole_cfg = dataclasses.replace(c.model.config, experts_held=None)
    whole = type(c.model)(whole_cfg)
    blocks = jax.tree.map(
        lambda a: a[0], perturbed(whole.init_params(jax.random.PRNGKey(7)),
                                  8)["blocks"])
    h = jax.random.normal(jax.random.PRNGKey(9), (1, 40, 64), jnp.float32)
    probe = jax.random.normal(jax.random.PRNGKey(10), (1, 40, 64))
    shared = lambda blk: whole._swiglu(
        h, blk["shared_gate_w"], blk["shared_up_w"], blk["shared_down_w"])

    def routed(model, blk, router_w):
        out, stats = model._mlp(h, {**blk, "router_w": router_w})
        return jnp.sum((out - shared(blk)) * probe), (out, stats)

    with jax.default_matmul_precision("highest"):
        (_, (want, stats)), want_grad = jax.value_and_grad(
            routed, argnums=2, has_aux=True)(whole, blocks,
                                             blocks["router_w"])
        total, grad, pairs = shared(blocks), 0.0, 0
        for first in range(0, 32, 8):
            part = type(c.model)(dataclasses.replace(
                whole_cfg, experts_held=(first, 8)))
            blk = {**blocks, **{n: blocks[n][first:first + 8]
                                for n in whole.EXPERT_LEAVES}}
            (_, (out, held)), g = jax.value_and_grad(
                routed, argnums=2, has_aux=True)(part, blk,
                                                 blocks["router_w"])
            total, grad = total + out - shared(blk), grad + g
            pairs += int(held[0].sum())
            assert (held[2] == stats[2]).all()      # every share counts all
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(grad, want_grad, atol=2e-5, rtol=0)
    assert pairs == 40 * 4 == int(stats[2].sum())


# ------------------------------------------- the published configuration
CATALOG_ROW = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_expert_groups": 1,
    "num_experts_per_tok": 8, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True}
HELD = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 16,
        "vocab_size": 25024, "max_position_embeddings": 8192,
        "layer_types": ["sliding_attention", "sliding_attention",
                        "full_attention", "sliding_attention",
                        "sliding_attention"]}
PUBLISHED = {"num_hidden_layers": 32, "num_dense_layers": 2,
             "num_experts": 128, "vocab_size": 200192,
             "max_position_embeddings": 131072,
             "layer_types": (["sliding_attention"] * 3
                             + ["full_attention"]) * 8}
REAL_CELL = "trinity-mini.train.z1.s8k"


def published():
    return mf.load_json(mf.BENCH_DIR / "configs" / "trinity-mini.json")


def test_published_widths_and_the_cut():
    """Every key of the catalog row under ``model`` and at the file's top
    level, unreduced keys value for value, reduced keys at what is held;
    no width cut; the program's model has those sizes."""
    cfg = published()
    assert cfg["source"] == ("https://huggingface.co/arcee-ai/Trinity-Mini/"
                             "blob/main/config.json")
    assert cfg["reduced"] == list(HELD) or set(cfg["reduced"]) == set(HELD)
    for key, value in {**CATALOG_ROW, **HELD}.items():
        assert cfg["model"][key] == value and cfg[key] == value, key
    assert set(cfg["model"]) == set(CATALOG_ROW) | set(HELD)
    assert cfg["published"] == PUBLISHED
    assert PUBLISHED["layer_types"][1:6] == HELD["layer_types"]
    for key in cfg["reduced"]:
        assert test_manifest.reduced_key_ok(key) and key in cfg["reduced_why"]
    assert cfg["share"]["chips_per_layer"] * HELD["num_experts"] == 128
    assert cfg["share"]["experts_first"] % 16 == 0
    assert "serve" not in cfg and cfg["train"]["remat"] == "attn"
    assert set(cfg["assumed"]) >= {
        "embedding_multiplier", "norms", "qk_norm", "rope", "output_gate",
        "expert_weight", "load_balance_coeff", "router_bias_init"}
    catalog = mf.Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        rows = [json.loads(line) for line in catalog.read_text().splitlines()]
        row = next(r for r in rows if r["name"] == "Trinity-Mini")
        assert row["source_url"] == cfg["source"]
        for key, value in row["config"].items():
            assert cfg[key] == (HELD[key] if key in HELD else value), key
    c = families.get("afmoe").build_model(cfg, "train").config
    assert (c.n_embd, c.n_layer, c.n_head, c.n_kv_head, c.head_dim) == \
        (2048, 5, 32, 4, 128)
    assert (c.dense_intermediate_size, c.intermediate_size) == (6144, 1024)
    assert (c.n_experts, c.n_experts_per_tok, c.experts_held) == \
        (128, 8, (48, 16))
    assert c.kinds == ("win", "win", "attn", "win", "win")
    assert c.pattern == ("win", "attn", "win", "win")
    assert (c.sliding_window, c.n_dense_layers, c.n_shared_experts) == \
        (2048, 1, 1)
    assert c.routed_scaling_factor == 2.826 and c.norm_topk_prob
    assert c.embed_scale == pytest.approx(45.2548, rel=1e-5)
    assert c.vocab_size == 25024 and c.n_positions == 8192
    assert c.remat == "attn" and not c.tie_embeddings
    assert c.num_params() == 705_474_304


def test_counts_at_the_published_sizes():
    """The numbers ISSUE 37 sized the cell by, from the family's functions."""
    fam, cfg = families.get("afmoe"), published()
    assert fam.attention_params(cfg) + 2 * 128 == 27_263_232
    assert fam.held_params(cfg) == 705_474_304
    shapes = jax.eval_shape(fam.build_model(cfg, "train").init_params,
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 705_474_304
    assert fam.experts_met(cfg) == 1.0                  # 8 x 16 / 128
    assert fam.matmul_params(cfg) == 276_692_992
    assert fam.mean_keys(8192) == 4096.5
    assert fam.mean_keys(8192, 2048) == pytest.approx(1792.125)
    assert fam.mean_keys(1024, 2048) == fam.mean_keys(1024)
    per_token = fam.attention_flops_fwd(cfg, 8192) / 8192
    assert per_token == pytest.approx(184.6e6, rel=1e-3)
    assert 2 * fam.matmul_params(cfg) == pytest.approx(553.4e6, rel=1e-3)
    assert fam.train_flops_per_token(cfg, 8192) == pytest.approx(
        6 * 276_692_992 + 3 * per_token)
    # the window layers' kernels: four of five layers, the band
    assert fam.win_flash_flops_per_sequence(cfg, 8192, backward=False) == \
        4 * 4 * 32 * 128 * 8192 * 1792.125
    assert fam.win_flash_flops_per_sequence(cfg, 8192) == \
        3.5 * fam.win_flash_flops_per_sequence(cfg, 8192, backward=False)
    assert fam.win_flash_bytes_per_sequence(cfg, 8192) == \
        4 * 6 * 8192 * 36 * 128 * 2
    assert fam.flash_flops_per_sequence(cfg, 8192) == pytest.approx(
        3.5 * 8192 * per_token)
    # a counted pair's twelve grouped products; the held experts' bytes
    assert fam.moe_gmm_flops_per_pair(cfg) == 12 * 2 * 2048 * 1024
    assert fam.moe_gmm_flops_per_pair(cfg, remat=False) == 9 * 2 * 2048 * 1024
    assert fam.moe_gmm_bytes_per_step(cfg) == 4 * 16 * 12 * 2048 * 1024 * 2
    assert fam.decode_bytes_per_token(cfg, 8192) - \
        fam.decode_bytes_per_token(cfg, 2048) == 6144 * 2 * 4 * 128 * 2
    assert fam.decode_flops_per_token(cfg) == 2 * fam.matmul_params(cfg)


def test_the_real_cell_is_in_the_manifest_with_the_train_metrics():
    """ISSUE 37's cell, letter for letter."""
    m = mf.load_manifest()
    cell = mf.find_cell(m, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train.z1.s8k"
    assert cell["config"] == "trinity-mini" and len(cell["why"]) <= 200
    # appended after what was there, wherever later cells go
    names = [c["name"] for c in m["workloads"]]
    assert names.index(REAL_CELL) > names.index(
        "solar-open2-250b.serve.doc32k.c1")
    traffic = mf.load_json(mf.traffic_path(cell["traffic"]))
    assert traffic["driver"] == "train_stream" and traffic["seq_len"] == 8192
    assert (traffic["zipf_offset"], traffic["warmup_steps"],
            traffic["trace_seconds"]) == (10.0, 3, 6.0)
    eng = traffic["engine"]
    assert (eng["zero_stage"], eng["gradient_accumulation_steps"]) == (1, 1)
    assert eng["micro_batch_per_chip"] in (4, 2, 1) and "4" in eng["sized_by"]
    e2e = {x["name"] for x in mf.metrics_for(m, REAL_CELL, "end_to_end")}
    assert e2e == {"train_tok_s_chip", "setup_s"}
    layer = {x["name"] for x in mf.metrics_for(m, REAL_CELL, "per_layer")}
    train = {x["name"] for x in m["per_layer"]
             if x["name"].startswith("train.")}
    assert layer == train - {"train.coll_exposed_frac", "train.flash_roofline"}
    for x in m["end_to_end"] + m["per_layer"]:     # appended: after z1.gas4
        if REAL_CELL in x.get("workloads", ()):
            cells = x["workloads"]
            assert cells.index(REAL_CELL) > cells.index(
                "gpt2-760m.train.z1.gas4")


# --------------------------------------- the five waiting metric files
AFMOE_METRICS = ("train.win_flash_roofline", "train.moe_experts_s_per_step",
                 "train.moe_gmm_roofline", "train.moe_held_pair_share",
                 "train.moe_load_max_over_mean")


def test_the_five_files_wait_in_a_place_of_their_own_and_move_the_train_rate():
    """Not entries, not under ``layer_metrics`` and not among PR 35's eight
    (each of those places is pinned by a test of the benchmark's):
    ``benchmark/trace_moe_metrics.py`` reads them, for a train cell alone."""
    from benchmark.trace_moe_metrics import MOE_DIR, grown

    assert sorted(p.stem for p in MOE_DIR.glob("*.json")) == \
        sorted(AFMOE_METRICS) == sorted(p.stem for p in MOE_DIR.glob("*.py"))
    real = mf.load_manifest()
    more, metric_spec = grown(real, REAL_CELL)
    assert more["per_layer"][:len(real["per_layer"])] == real["per_layer"]
    added = {x["name"]: x for x in more["per_layer"]
             if x["name"] in AFMOE_METRICS}
    assert set(added) == set(AFMOE_METRICS)
    for name, x in added.items():
        assert x["moves"] == "train_tok_s_chip" and x["workloads"] == [REAL_CELL]
        assert mf.UNIT_RE.match(x["unit"]) and mf.NAME_RE.match(name)
        spec, read = metric_spec("per_layer", name)
        assert callable(read) and x["source"] in mf.SOURCES
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == x[key]
        assert not (mf.BENCH_DIR / "layer_metrics" / f"{name}.json").exists()
        assert not (mf.BENCH_DIR / "train_scope_metrics"
                    / f"{name}.json").exists()
    assert {x["layer"] for x in added.values()} == {"kernels",
                                                    "routed experts"}
    serve = grown(real, "olmoe-1b-7b.serve.doc4k.c1")[0]
    assert not {x["name"] for x in serve["per_layer"]} & set(AFMOE_METRICS)


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """A run of the PARENT (no ``*_win`` kernel, no ragged-dot, no instant)
    and a CPU rehearsal (no device plane): no reader raises."""
    from benchmark import afmoe_metrics

    fam, cfg = families.get("afmoe"), published()
    trace = {"n_devices": 1, "modules": {"jit_step_fn(1)": [0.5, 0.5]},
             "op_text_seconds": {
                 "%flash_fwd.3 = bf16[1,8,8] custom-call(%a)": 0.2,
                 "%fusion.1 = bf16[8] fusion(%b)": 0.1}, "busy_s": 1.0}
    ctx = types.SimpleNamespace(
        trace=trace, peaks={"bf16_flops_per_s": 197e12,
                            "hbm_bytes_per_s": 819e9, "hbm_bytes": 2 ** 34},
        family=fam, config=cfg, notes={},
        traffic={"seq_len": 8192, "engine": {
            "micro_batch_per_chip": 2, "gradient_accumulation_steps": 1}},
        record={"t_start": 0.0, "t_end": 1.0})
    win = {"match": "flash_(?:fwd|bwd_dq|bwd_dkv)_win",
           "step_match": "step_fn"}
    gmm = {"match": "ragged-dot-none", "step_match": "step_fn"}
    assert afmoe_metrics.win_flash_roofline(ctx, win) is None
    assert afmoe_metrics.moe_gmm_roofline(ctx, gmm) is None
    # with the kernels in the trace: a share of the roofline, under 100
    trace["op_text_seconds"].update({
        "%flash_fwd_win.1 = bf16[1] custom-call(%a)": 0.05,
        "%transpose_jvp_flash_bwd_dq_win__.1 = bf16[1] custom-call(%a)": 0.1,
        "%flash_bwd_dkv_win.2 = bf16[1] custom-call(%a)": 0.1})
    got = afmoe_metrics.win_flash_roofline(ctx, win)
    least = 2 * fam.win_flash_flops_per_sequence(cfg, 8192) / 197e12
    assert got == pytest.approx(100 * least / (0.25 / 2))
    ctx.trace = None
    assert afmoe_metrics.win_flash_roofline(ctx, win) is None
    assert afmoe_metrics.scope_seconds_per_step(
        ctx, {"scope": "moe/experts", "step_match": "step_fn"}) is None


# ----------------------- the tiny configuration as a cell: manifest and run
CELL = "trinity-tiny.train.s.tiny"


def trinity_manifest(train_chips=1):
    """``rehearsal.manifest()`` plus one entry: ``trinity-tiny`` and its
    train cell, appended to every train metric."""
    m = copy.deepcopy(rehearsal.manifest(train_chips))
    body = mf.load_json(DATA / "trinity-tiny.json")
    m["configs"].append({
        "name": "trinity-tiny", "source": body["source"],
        "reduced": body["reduced"], "why": "rehearsal",
        "file": "tests/benchmark/data/trinity-tiny.json"})
    m["workloads"].append({"name": CELL, "config": "trinity-tiny",
                           "why": "rehearsal", "traffic": "train.s.tiny",
                           "chips": train_chips})
    for metric in m["end_to_end"] + m["per_layer"]:
        if any(".train." in w for w in metric.get("workloads", [])):
            metric["workloads"].append(CELL)
    return m


def test_the_tiny_configuration_passes_every_manifest_check():
    m = trinity_manifest()
    book = test_manifest.Book("trinity", m, "tests/benchmark/data/",
                              DATA / "traffic")
    config = next(c for c in m["configs"] if c["name"] == "trinity-tiny")
    test_manifest.test_config_entry_and_file(book, config)
    test_manifest.test_cell_entry_and_its_files(
        book, next(c for c in m["workloads"] if c["name"] == CELL))
    test_manifest.test_names_are_unique(book)
    for name in test_manifest.names(m, "configs", "workloads"):
        test_manifest.test_every_name_uses_only_the_allowed_characters(
            book, name)


def test_rehearsal_train_stream_traced(jax_config_restored):  # noqa: F811
    """REHEARSAL, not a measurement: ``deepspeed_tpu.initialize`` ->
    ``engine.train_batch`` through ``run.execute`` on the CPU mesh, bf16,
    ZeRO-1: the engine's loss against this family's reference at set-up, the
    loss falls, nothing compiles in the window, and the routing counts the
    steps left are read as the two counter metrics."""
    from benchmark import trace_moe_metrics

    real = mf.metric_spec
    manifest, mf.metric_spec = trace_moe_metrics.grown(
        trinity_manifest(jax.device_count()), CELL)
    try:
        result, info = run.execute(
            CELL, seed=2147483659, seconds=5.0, trace=1, manifest=manifest,
            platforms=("cpu",), traffic_dir=DATA / "traffic")
    finally:
        mf.metric_spec = real
    line = json.loads(json.dumps(result))
    assert line["failed"] == 0 and line["attempted"] >= 1, line
    check = info["check"]
    assert abs(check["loss_system"] - check["loss_reference"]) \
        <= check["tolerance"]
    # "the loss falls over the window" needs a few steps INSIDE it: a loaded
    # test machine may fit one (tests/unit/test_afmoe.py trains four)
    if line["attempted"] > 3:
        assert line["correct"], (line, info["notes"])
    assert line["metrics"]["train.compiles_in_window"]["value"] == 0.0
    share = line["metrics"]["train.moe_held_pair_share"]
    assert share["unit"] == "%" and 5 < share["value"] < 60     # 25 if even
    assert line["metrics"]["train.moe_load_max_over_mean"]["value"] >= 1.0
    assert info["notes"]["moe_held"] == [8, 8]
    # no device plane on the CPU: the three trace readers are left out
    assert not {"train.win_flash_roofline", "train.moe_gmm_roofline",
                "train.moe_experts_s_per_step"} & set(line["metrics"])


def test_the_witness_refuses_the_four_broken_programs_at_a_small_size():
    """``benchmark/afmoe_witness.py`` on the CPU (bf16, no kernel) at 96
    tokens under the tiny window: the sound program inside loose limits, each
    broken one outside at least one of them. The real limits are the
    chip's, at the published widths."""
    from benchmark import afmoe_witness

    cfg = mf.load_json(DATA / "trinity-tiny.json")
    limits = {"loss": 0.02, "window_q_w": 0.12, "full_q_w": 0.12,
              "router_w": 0.28, "expert_gate_w": 0.12, "wte": 0.12}
    out = afmoe_witness.witness(cfg, 3, 96, True, limits=limits)
    assert set(out["forms"]) == {"sound", *afmoe_witness.BROKEN}
    assert out["forms"]["sound"]["over_its_limit"] == []
    for name in afmoe_witness.BROKEN:
        assert out["forms"][name]["over_its_limit"], name
    assert out["ok"] and set(afmoe_witness.LIMITS) == set(limits)
