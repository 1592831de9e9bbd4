"""Kimi-Linear's share (``benchmark/families/kimi_linear.py``) at a small
size on the CPU: the family's plain reference (the KDA recurrence token by
token, latent attention un-absorbed, the experts one at a time) against the
program's model (``models/llama.py`` with KDA and latent-attention layers in
one pattern behind a leading dense KDA layer, no positions, a direct ``q_w``,
a biased sigmoid router, a share of the experts beside a shared one) on the
same seeded weights — logits, loss AND gradients through the state pass's
own backward, ``prefill`` + ``decode_step``, programs
with broken mathematics that the same comparison must refuse; the shares of
one layer; the published configuration with the published numbers written
HERE; the counts; the waiting metric files. The tiny configuration as a
cell and the witness at a small size: ``test_kimi_cell.py`` (a file of its
own, so that the two spread over two workers of a run by file)."""

import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import families
from benchmark import manifest as mf
from tests.benchmark import rehearsal, test_manifest
from tests.benchmark.test_reference import perturbed

DATA = rehearsal.DATA
T = 80              # one chunk of 64 and a tail of 16
# float32 at "highest" on both sides: what is left is the order of the sums
# (the chunked algebra against 80 rank-1 updates a head). Measured: 4e-6 on
# logits that spread by 0.33, 5e-7 on the loss, 2e-5 of a gradient's norm
# (kda_a_log's, the smallest leaf). A dropped tap moves a logit by 2e-2,
# beta without its 2 by 1e-2, a rotated latent layer by 4e-3.
TOL_LOGITS, TOL_LOSS, TOL_GRAD = 5e-5, 5e-6, 2e-4


def case(**model_over):
    """``kimi-tiny.json`` (``model_over`` laid over its sizes), the
    program's model built through the family module and put into float32,
    seeded weights with every gain, constant and the bias moved, ids."""
    cfg = mf.load_json(DATA / "kimi-tiny.json")
    cfg["model"].update(model_over)
    ref = families.get("kimi_linear")
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


@pytest.fixture(scope="module")
def reference_gradient(tiny):
    """(loss, gradients) of the reference, made once for both walks."""
    return jax.jit(jax.value_and_grad(
        lambda params: tiny.ref.reference_loss(params, tiny.ids, tiny.cfg)))(
            tiny.params)


def reference(c, params=None, **broken):
    return np.asarray(c.ref.reference_logits(
        c.params if params is None else params, c.ids, c.cfg, **broken))


def program_logits(model, params, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.apply(params, ids[None])[0])


# ------------------------------------------------ program against reference
def test_the_tiny_file_has_a_dense_kda_layer_and_one_whole_period(tiny):
    c = tiny.model.config
    assert c.kinds == ("kda", "kda", "kda", "attn", "kda")
    assert c.stack_pattern(0, 1) == ("kda",) and c.dense_mixer == "kda"
    assert c.pattern == ("kda", "kda", "attn", "kda")
    assert (c.n_layer, c.n_dense_layers, c.gqa_layers) == (5, 1, (3,))
    assert c.mla and not c.q_lora_rank and not c.use_rope
    assert (c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim) == (32, 16, 8, 16)
    assert (c.kda_heads, c.kda_head_dim, c.kda_conv) == (4, 16, 4)
    assert c.router_scoring == "sigmoid" and c.routed_scaling_factor == 2.446
    assert (c.n_experts, c.experts_held, c.n_experts_per_tok) == (32, (8, 8), 4)
    assert c.router_bias and c.router_bias_rate == 0.001
    p = tiny.params
    assert p["dense_blocks"]["kda_qkv_w"].shape == (1, 64, 192)
    assert p["dense_blocks"]["gate_w"].shape == (1, 64, 96)
    assert p["kda_blocks"]["kda_qkv_w"].shape == (3, 64, 192)
    assert p["attn_blocks"]["q_w"].shape == (1, 64, 4 * 24)
    assert p["attn_blocks"]["kv_a_w"].shape == (1, 64, 40)
    assert "q_a_w" not in p["attn_blocks"] and "q_w" not in p["blocks"]
    assert p["blocks"]["router_bias"].shape == (4, 32)
    assert sum(x.size for x in jax.tree.leaves(p)) == c.num_params() \
        == tiny.ref.held_params(tiny.cfg)


def test_program_matches_the_reference_in_float32(tiny):
    """``apply``: both stacks, the chunked KDA against the recurrence, the
    un-rotated latent layer, the biased choice, the share."""
    want = reference(tiny)
    got = program_logits(tiny.model, tiny.params, tiny.ids)
    assert want.std() > 0.25
    np.testing.assert_allclose(got, want, atol=TOL_LOGITS, rtol=0)


@pytest.mark.parametrize("segment", [2048, 32], ids=["whole", "segments"])
def test_loss_and_gradients_match_the_reference(tiny, reference_gradient,
                                                segment, monkeypatch):
    """``module.loss`` (remat 'attn', the chunked head, the state pass's
    custom VJP; with ``segment`` 32 the segment walk under the gradient: two
    segments and a tail of 16) and its gradient, EVERY leaf, against
    ``jax.grad`` of the reference's loss through the recurrence."""
    from deepspeed_tpu.models import kda

    monkeypatch.setattr(kda, "SEGMENT", segment)
    model = type(tiny.model)(dataclasses.replace(tiny.model.config,
                                                 remat="attn"))
    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(model.loss))(
            tiny.params, {"input_ids": tiny.ids[None]})
    want, ref_grads = reference_gradient
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
    ({"beta_unscaled": True}, 5e-3), ({"tap_dropped": True}, 5e-3),
    ({"rope_on_mla": True}, 1e-3), ({"bias_left_out": True}, 5e-3)],
    ids=lambda x: str(x))
def test_a_broken_reference_is_far_from_the_program(tiny, broken, least):
    """What the comparison rests on: beta's factor 2, every tap of the
    convolution, the latent layer's missing rotation and the bias each move
    the logits by far more than TOL."""
    got = program_logits(tiny.model, tiny.params, tiny.ids)
    assert np.abs(reference(tiny, **broken) - got).max() > least


def test_prefill_then_decode_matches_the_full_pass(tiny):
    """``prefill`` of 50 tokens, then 29 ``decode_step``s (the KDA states of
    BOTH stacks and the absorbed latent decode over ONE layer's rows),
    teacher-forced: LOGITS against the reference's one pass over all 80."""
    want = reference(tiny)
    ids = jnp.asarray(tiny.ids)[None]
    with jax.default_matmul_precision("highest"):
        lg, cache = jax.jit(tiny.model.prefill)(
            tiny.params, ids[:, :50], tiny.model.init_cache(1, 96))
        step = jax.jit(tiny.model.decode_step)
        got = [lg[0]]
        for t in range(50, 79):
            lg, cache = step(tiny.params, ids[:, t], cache)
            got.append(lg[0])
    np.testing.assert_allclose(np.stack(got), want[49:79], atol=TOL_LOGITS,
                               rtol=0)
    assert cache["kv"].shape[:3] == (1, 1, 96)          # ONE latent layer
    assert cache["kda_state"].shape == (4, 1, 4, 16, 16)
    assert cache["expert_tokens"].shape == (4, 8)
    assert all(float(jnp.abs(cache["kda_state"][l]).max()) > 0
               for l in range(4))


# ------------------------------------------------------- the routed layer
def test_the_shares_add_up_to_the_uncut_layer():
    """One routed layer of the tiny model, uncut (32 experts) and as the
    four shares of 8: the routed outputs of the shares, with the shared
    expert counted once, add up to the uncut REFERENCE's layer (its router
    over all 32, every expert walked), and each share's program equals the
    reference's share."""
    c = case()
    ref, z = c.ref, c.ref._sizes(c.cfg)
    whole_cfg = dataclasses.replace(c.model.config, experts_held=None)
    whole = type(c.model)(whole_cfg)
    blocks = jax.tree.map(
        lambda a: a[0], perturbed(whole.init_params(jax.random.PRNGKey(7)),
                                  8)["blocks"])
    h = jax.random.normal(jax.random.PRNGKey(9), (40, 64), jnp.float32)
    m = c.cfg["model"]
    with jax.default_matmul_precision("highest"):
        uncut = ref._mlp(h, blocks, z._replace(held=32, first=0), m)
        shared = ref._swiglu(h, blocks["shared_gate_w"], blocks["shared_up_w"],
                             blocks["shared_down_w"])
        total, pairs = shared, 0
        for first in range(0, 32, 8):
            part = type(c.model)(dataclasses.replace(
                whole_cfg, experts_held=(first, 8)))
            blk = {**blocks, **{n: blocks[n][first:first + 8]
                                for n in whole.EXPERT_LEAVES}}
            out, held = part._mlp(h[None], blk)
            want = ref._mlp(h, blk, z._replace(first=first), m)
            np.testing.assert_allclose(out[0], want, atol=2e-5, rtol=0)
            total = total + out[0] - shared
            pairs += int(held[0].sum())
    np.testing.assert_allclose(total, uncut, atol=2e-5, rtol=0)
    assert pairs == 40 * 4


# ------------------------------------------- the published configuration
CATALOG_ROW = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "mla_use_nope": True, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_attention_heads": 32, "num_expert_group": 1,
    "num_experts_per_token": 8, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128}
LINEAR = {"head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}
HELD = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480,
        "model_max_length": 16384,
        "linear_attn_config": {**LINEAR, "kda_layers": [1, 2, 3, 5],
                               "full_attn_layers": [4]}}
PUBLISHED = {"num_hidden_layers": 27, "num_experts": 256,
             "vocab_size": 163840, "model_max_length": 1048576,
             "linear_attn_config": {
                 **LINEAR, "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
                 "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17,
                                18, 19, 21, 22, 23, 25, 26]}}
REAL_CELL = "kimi-linear-48b-a3b.train.z1.s16k"


def published():
    return mf.load_json(mf.BENCH_DIR / "configs" / "kimi-linear-48b-a3b.json")


def test_published_widths_and_the_cut():
    """Every key of the catalog row under ``model`` and at the file's top
    level, unreduced keys value for value, reduced keys at what is held;
    no width cut, inside the nested group either; the program's model has
    those sizes."""
    cfg = published()
    assert cfg["source"] == ("https://huggingface.co/moonshotai/"
                             "Kimi-Linear-48B-A3B-Instruct/blob/main/"
                             "config.json")
    assert set(cfg["reduced"]) == set(HELD)
    for key, value in {**CATALOG_ROW, **HELD}.items():
        assert cfg["model"][key] == value and cfg[key] == value, key
    assert set(cfg["model"]) == set(CATALOG_ROW) | set(HELD)
    assert cfg["published"] == PUBLISHED
    lin = PUBLISHED["linear_attn_config"]
    assert [l for l in lin["kda_layers"] if l <= 5] == [1, 2, 3, 5]
    assert [l for l in lin["full_attn_layers"] if l <= 5] == [4]
    for key in cfg["reduced"]:
        assert test_manifest.reduced_key_ok(key) and key in cfg["reduced_why"]
    assert cfg["share"]["chips_per_layer"] * HELD["num_experts"] == 256
    assert cfg["share"]["experts_first"] == 96
    assert "serve" not in cfg and cfg["train"]["remat"] == "attn"
    assert set(cfg["assumed"]) >= {
        "selection_bias", "router_bias_rate", "mla_use_nope", "kv_a_norm",
        "kda", "kda_init", "init", "lr_warmup"}
    assert set(cfg["unused"]) >= {"head_dim", "rope_theta",
                                  "num_nextn_predict_layers"}
    warm = cfg["train"]["ds_config"]["scheduler"]
    assert warm["type"] == "WarmupLR" and warm["params"]["warmup_max_lr"] == \
        cfg["train"]["ds_config"]["optimizer"]["params"]["lr"] == 3e-4
    assert warm["params"]["warmup_num_steps"] in (100, 200, 400)
    catalog = mf.Path("/opt/skills/guides/model-configs/architectures.jsonl")
    rows = [json.loads(line) for line in catalog.read_text().splitlines()] \
        if catalog.exists() else []
    row = next((r for r in rows
                if r["name"] == "Kimi-Linear-48B-A3B-Instruct"), None)
    if row is None:
        pytest.skip("the catalog's row is gone")
    assert row["source_url"] == cfg["source"]
    for key, value in row["config"].items():
        assert cfg[key] == (HELD[key] if key in HELD else value), key
    c = families.get("kimi_linear").build_model(cfg, "train").config
    assert (c.n_embd, c.n_layer, c.n_head, c.n_dense_layers) == \
        (2304, 5, 32, 1)
    assert (c.dense_intermediate_size, c.intermediate_size) == (9216, 1024)
    assert (c.n_experts, c.n_experts_per_tok, c.experts_held) == \
        (256, 8, (96, 8))
    assert c.kinds == ("kda", "kda", "kda", "attn", "kda")
    assert (c.kv_lora_rank, c.q_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim) == (512, 0, 128, 64, 128)
    assert (c.kda_heads, c.kda_head_dim, c.kda_conv) == (32, 128, 4)
    assert c.routed_scaling_factor == 2.446 and c.norm_topk_prob
    assert not c.use_rope and c.router_bias and c.n_shared_experts == 1
    assert c.vocab_size == 20480 and c.n_positions == 16384
    assert c.remat == "attn" and not c.tie_embeddings
    assert c.num_params() == 602_434_432


def test_counts_at_the_published_sizes():
    """The numbers ISSUE 41 sized the cell by, from the family's functions."""
    from deepspeed_tpu.models import kda

    fam, cfg = families.get("kimi_linear"), published()
    c = fam.build_model(cfg, "train").config
    assert kda.num_params(c) == 39_514_272
    assert fam.kda_params(cfg) + 4 * 3 * 4096 + 32 + 4096 + 128 == 39_514_272
    assert fam.mla_params(cfg) + 512 == 29_114_880
    assert fam.held_params(cfg) == 602_434_432
    shapes = jax.eval_shape(fam.build_model(cfg, "train").init_params,
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 602_434_432
    assert 602_434_432 * 14 / 2 ** 34 == pytest.approx(0.4909, abs=1e-4)
    assert fam.experts_met(cfg) == 0.25                 # 8 x 8 / 256
    assert fam.matmul_params(cfg) == 335_593_472
    # the latent layer's causal attention over 16k: 2.75 TFLOP forward
    fwd = fam.mla_train_attn_flops(cfg, 16384, backward=False)
    assert fwd == 2 * 32 * (192 + 128) * 16384 * 8192.5
    assert fam.mla_train_attn_flops(cfg, 16384) == 3.5 * fwd
    assert fam.flash_flops_per_sequence(cfg, 16384) == 3.5 * fwd
    assert fam.mla_train_attn_bytes(cfg, 16384, backward=False) == \
        16384 * 32 * 2 * (2 * 192 + 2 * 128)
    # the state pass of the four KDA layers: what each kernel must do
    a_position = 6 * 128 * 128 + 2 * 64 * 128
    assert fam.kda_train_flops(cfg, 16384) == 4 * 32 * 16384 * a_position
    assert fam.kda_train_flops(cfg, 16384, backward=True) == \
        2 * fam.kda_train_flops(cfg, 16384)
    assert fam.kda_train_bytes(cfg, 16384) == \
        4 * 32 * 16384 * (4 * 128 + 64 + 128) * 2
    assert fam.kda_train_bytes(cfg, 16384, backward=True) == \
        4 * 32 * 16384 * (2 * (4 * 128 + 64) + 128) * 2
    per_token = fam.train_flops_per_token(cfg, 16384)
    assert per_token > 6 * 335_593_472 + 3 * fwd / 16384
    assert per_token == pytest.approx(2.668e9, rel=1e-3)
    assert fam.moe_gmm_flops_per_pair(cfg) == 12 * 2 * 2304 * 1024
    assert fam.moe_gmm_bytes_per_step(cfg) == 4 * 8 * 12 * 2304 * 1024 * 2
    assert fam.decode_flops_per_token(cfg) == 2 * fam.matmul_params(cfg)
    assert fam.decode_bytes_per_token(cfg, 16384) - \
        fam.decode_bytes_per_token(cfg, 0) == 16384 * 576 * 2


def test_the_real_cell_is_in_the_manifest_with_the_train_metrics():
    """ISSUE 41's cell, letter for letter."""
    m = mf.load_manifest()
    cell = mf.find_cell(m, REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train.z1.s16k"
    assert cell["config"] == "kimi-linear-48b-a3b" and len(cell["why"]) <= 200
    names = [c["name"] for c in m["workloads"]]
    assert names.index(REAL_CELL) > names.index("trinity-mini.train.z1.s8k")
    traffic = mf.load_json(mf.traffic_path(cell["traffic"]))
    assert traffic["driver"] == "train_stream" and traffic["seq_len"] == 16384
    assert (traffic["zipf_offset"], traffic["warmup_steps"],
            traffic["trace_seconds"]) == (10.0, 3, 6.0)
    eng = traffic["engine"]
    assert (eng["zero_stage"], eng["micro_batch_per_chip"],
            eng["gradient_accumulation_steps"]) == (1, 1, 1)
    assert "16384" in eng["sized_by"].replace(",", "")
    e2e = {x["name"] for x in mf.metrics_for(m, REAL_CELL, "end_to_end")}
    assert e2e == {"train_tok_s_chip", "setup_s"}
    layer = {x["name"] for x in mf.metrics_for(m, REAL_CELL, "per_layer")}
    assert layer == {x["name"] for x in mf.metrics_for(
        m, "trinity-mini.train.z1.s8k", "per_layer")} and len(layer) == 13
    for x in m["end_to_end"] + m["per_layer"]:     # appended: the last
        if REAL_CELL in x.get("workloads", ()):
            assert x["workloads"][-1] == REAL_CELL


# --------------------------------------- the five waiting metric files
KDA_METRICS = ("train.kda_s_per_step", "train.kda_core_s_per_step",
               "train.kda_chunk_fwd_roofline", "train.kda_chunk_bwd_roofline",
               "train.mla_flash_roofline")


def test_the_five_files_wait_in_a_place_of_their_own_and_move_the_train_rate():
    """Not entries, not under ``layer_metrics`` and not among PR 35's eight
    or PR 37's five: ``benchmark/trace_kda_metrics.py`` reads them beside
    those, for a train cell alone."""
    from benchmark.trace_kda_metrics import KDA_DIR, grown

    assert sorted(p.stem for p in KDA_DIR.glob("*.json")) == \
        sorted(KDA_METRICS) == sorted(p.stem for p in KDA_DIR.glob("*.py"))
    real = mf.load_manifest()
    more, metric_spec = grown(real, REAL_CELL)
    assert more["per_layer"][:len(real["per_layer"])] == real["per_layer"]
    names = {x["name"] for x in more["per_layer"]}
    assert {"train.moe_held_pair_share", "train.attn_s_per_step"} <= names
    added = {x["name"]: x for x in more["per_layer"]
             if x["name"] in KDA_METRICS}
    assert set(added) == set(KDA_METRICS)
    for name, x in added.items():
        assert x["moves"] == "train_tok_s_chip" and x["workloads"] == [REAL_CELL]
        assert mf.UNIT_RE.match(x["unit"]) and mf.NAME_RE.match(name)
        spec, read = metric_spec("per_layer", name)
        assert callable(read) and x["source"] in mf.SOURCES
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == x[key]
        for place in ("layer_metrics", "train_scope_metrics",
                      "train_moe_metrics"):
            assert not (mf.BENCH_DIR / place / f"{name}.json").exists()
    assert {x["layer"] for x in added.values()} == {"kernels", "models"}
    serve = grown(real, "solar-open2-250b.serve.doc32k.c1")[0]
    assert not {x["name"] for x in serve["per_layer"]} & set(KDA_METRICS)


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """A run of the PARENT (no ``kda_chunk_*`` kernel in a train step) and a
    CPU rehearsal (no device plane): no reader raises; with the kernels in
    the trace each share is the family's count over the kernel's time."""
    from benchmark import kimi_metrics

    fam, cfg = families.get("kimi_linear"), published()
    trace = {"n_devices": 1, "modules": {"jit_step_fn(1)": [0.5, 0.5]},
             "op_text_seconds": {
                 "%flash_fwd_win.3 = bf16[1,8,8] custom-call(%a)": 0.2,
                 "%fusion.1 = bf16[8] fusion(%b)": 0.1}, "busy_s": 1.0}
    ctx = types.SimpleNamespace(
        trace=trace, peaks={"bf16_flops_per_s": 197e12,
                            "hbm_bytes_per_s": 819e9, "hbm_bytes": 2 ** 34},
        family=fam, config=cfg, notes={},
        traffic={"seq_len": 16384, "engine": {
            "micro_batch_per_chip": 1, "gradient_accumulation_steps": 1}},
        record={"t_start": 0.0, "t_end": 1.0})
    spec = lambda name: mf.load_json(
        mf.BENCH_DIR / "train_kda_metrics" / f"{name}.json")["params"]
    fwd, bwd, mla = (spec(f"train.{n}_roofline") for n in
                     ("kda_chunk_fwd", "kda_chunk_bwd", "mla_flash"))
    assert kimi_metrics.kda_chunk_roofline(ctx, fwd) is None
    assert kimi_metrics.kda_chunk_roofline(ctx, bwd) is None
    assert kimi_metrics.mla_flash_roofline(ctx, mla) is None    # *_win only
    trace["op_text_seconds"].update({
        "%jvp_kda_chunk_fwd_.1 = bf16[1] custom-call(%a)": 0.02,
        "%transpose_jvp_kda_chunk_bwd__.1 = bf16[1] custom-call(%a)": 0.06,
        "%flash_fwd.1 = bf16[1] custom-call(%a)": 0.04,
        "%flash_bwd_dq.2 = bf16[1] custom-call(%a)": 0.05,
        "%flash_bwd_dkv.2 = bf16[1] custom-call(%a)": 0.05})
    peak, hbm = 197e12, 819e9
    least = max(fam.kda_train_flops(cfg, 16384) / peak,
                fam.kda_train_bytes(cfg, 16384) / hbm)
    assert kimi_metrics.kda_chunk_roofline(ctx, fwd) == \
        pytest.approx(100 * least / (0.02 / 2))
    least = max(fam.kda_train_flops(cfg, 16384, True) / peak,
                fam.kda_train_bytes(cfg, 16384, True) / hbm)
    assert kimi_metrics.kda_chunk_roofline(ctx, bwd) == \
        pytest.approx(100 * least / (0.06 / 2))
    assert kimi_metrics.mla_flash_roofline(ctx, mla) == pytest.approx(
        100 * fam.mla_train_attn_flops(cfg, 16384) / peak / (0.14 / 2))
    # a family without the counts (every other one): nothing
    ctx.family = families.get("gpt2")
    assert kimi_metrics.kda_chunk_roofline(ctx, fwd) is None
    assert kimi_metrics.mla_flash_roofline(ctx, mla) is None
    ctx.family, ctx.trace = fam, None
    assert kimi_metrics.kda_chunk_roofline(ctx, fwd) is None
    assert kimi_metrics.mla_flash_roofline(ctx, mla) is None
