"""OLMoE's pieces inside the program: the grouped-matmul kernel (interpret
mode, against the XLA form), dropless top-k routing, the Llama trunk with
routed experts through ``initialize`` / ``init_inference`` / the serving
front-end, and the engine holding served-type weights once. The family's
reference, Hugging Face and the broken-mathematics controls are in
tests/benchmark/test_olmoe_family.py; lowering for the chip in
tests/unit/test_chip_bringup.py."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import common
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import grouped_matmul as gmm

TINY = LlamaConfig(vocab_size=512, n_positions=128, n_embd=64, n_layer=2,
                   n_head=4, intermediate_size=32, qk_norm=True, n_experts=8,
                   n_experts_per_tok=3, router_aux_loss_coef=0.01)
F32 = dict(dtype=jnp.float32, remat=False, use_flash_attention=False)


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel run by the Pallas interpreter (the test asks; the kernel
    never picks it by itself)."""
    from jax.experimental import pallas as pl

    monkeypatch.setattr(gmm.pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))


@pytest.fixture
def as_tpu_program(monkeypatch, interpreted):
    """Make model code believe its program is for a TPU: the path
    ``routed_mlp`` chooses there, run by the interpreter."""
    real = common._kernel_target
    monkeypatch.setattr(common, "_kernel_target", lambda: (real()[0], True))


# ------------------------------------------------------------- the kernel
def xla_form(x, w, group_of_row, layer):
    """What the program runs off the TPU: rows sorted by group through
    ``jax.lax.ragged_dot``, back in their own order."""
    order = jnp.argsort(group_of_row, stable=True)
    sizes = jnp.bincount(group_of_row, length=w.shape[1]).astype(jnp.int32)
    return jax.lax.ragged_dot(x[order], w[layer], sizes,
                              precision="highest")[jnp.argsort(order)]


RAGGED = {
    "decode: 8 rows, one to an expert": lambda r: r.permutation(8)[:8] % 6,
    "an empty group and a group of one": lambda r: np.r_[
        r.choice([0, 2, 5], 400), [3]],
    "groups that are no multiple of the tile": lambda r: r.integers(0, 6, 700),
    "all rows in one expert": lambda r: np.full(300, 4),
    "one row": lambda r: np.array([2]),
    "few rows in few experts": lambda r: r.choice([1, 4], 40),
}


@pytest.mark.parametrize("groups", RAGGED.values(), ids=RAGGED.keys())
def test_moe_gmm_matches_the_xla_form_over_ragged_groups(interpreted, groups):
    E, L, K, N, layer = 6, 3, 128, 256, 1
    g = jnp.asarray(groups(np.random.default_rng(0)), jnp.int32)
    M = g.shape[0]
    tm = gmm.row_tile(M)
    keys = jax.random.split(jax.random.PRNGKey(M), 3)
    x = jax.random.normal(keys[0], (M, K), jnp.float32)
    w1, w2 = (jax.random.normal(k, (L, E, K, N), jnp.float32) * 0.1
              for k in keys[1:])
    sizes, tile_group, n_active, src, pos = gmm.group_layout(g, E, tm)
    np.testing.assert_array_equal(sizes, np.bincount(np.asarray(g), minlength=E))
    assert int(n_active) == sum(-(-int(n) // tm) for n in sizes)
    assert tile_group.shape == (gmm.num_row_tiles(M, E, tm),)
    assert int(n_active) <= tile_group.shape[0]
    # every row reaches its own padded slot, in a tile of its own group
    np.testing.assert_array_equal(src[pos], np.arange(M))
    np.testing.assert_array_equal(tile_group[pos // tm], g)
    call = functools.partial(gmm.grouped_matmul, layer=layer,
                             tile_group=tile_group, n_active=n_active, tm=tm)
    a = xla_form(x, w1, g, layer)
    np.testing.assert_allclose(call(x[src], w1)[pos], a, atol=2e-5, rtol=0)
    np.testing.assert_allclose(
        call(x[src], (w1, w2), swiglu=True)[pos],
        jax.nn.silu(a) * xla_form(x, w2, g, layer), atol=2e-5, rtol=0)


def test_moe_gmm_refuses_shapes_it_was_not_given():
    x = jnp.zeros((32, 128))
    w = jnp.zeros((1, 2, 128, 128))
    tg, n = jnp.zeros((2,), jnp.int32), jnp.int32(2)
    with pytest.raises(ValueError, match="whole tiles"):
        gmm.grouped_matmul(x[:30], w, 0, tg, n, tm=16)
    with pytest.raises(ValueError, match="rows hold"):
        gmm.grouped_matmul(x, jnp.zeros((1, 2, 256, 128)), 0, tg, n, tm=16)
    with pytest.raises(ValueError, match="pair"):
        gmm.grouped_matmul(x, w, 0, tg, n, tm=16, swiglu=True)
    assert gmm.supports(2048, 1024) and not gmm.supports(64, 32)
    assert gmm.row_tile(8) == 16 and gmm.row_tile(8 * 1024) == 128


def test_the_kernel_never_interprets_itself():
    import inspect

    assert "interpret" not in inspect.getsource(gmm)


# ------------------------------------------------------------ the routing
def test_route_topk_is_float32_dropless_and_not_renormalised():
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 16), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8), jnp.bfloat16)
    probs, weights, experts = dropless.route_topk(x, w, 3)
    assert probs.dtype == weights.dtype == jnp.float32
    assert experts.shape == (40, 3) and experts.dtype == jnp.int32
    want = jax.nn.softmax(np.asarray(x, np.float32) @ np.asarray(w, np.float32))
    np.testing.assert_allclose(probs, want, atol=1e-6)
    np.testing.assert_allclose(weights, np.sort(want, axis=-1)[:, ::-1][:, :3],
                               atol=1e-6)
    assert (weights.sum(-1) < 1).all()          # the chosen three, as they are
    _, renorm, _ = dropless.route_topk(x, w, 3, renormalize=True)
    np.testing.assert_allclose(renorm.sum(-1), 1.0, atol=1e-6)


@pytest.mark.parametrize("k", [1, 2, 3, 8])
def test_routed_mlp_computes_every_pair_for_any_k(k):
    """No capacity: even with every token on one expert nothing is dropped.
    Against a dense loop over (token, choice) pairs."""
    T, D, F, E = 24, 16, 8, 8
    keys = jax.random.split(jax.random.PRNGKey(k), 5)
    x = jax.random.normal(keys[0], (T, D))
    gate, up = (jax.random.normal(kk, (E, D, F)) for kk in keys[1:3])
    down = jax.random.normal(keys[3], (E, F, D))
    experts = jnp.tile(jnp.arange(k, dtype=jnp.int32), (T, 1))  # all alike
    weights = jax.random.uniform(keys[4], (T, k))
    out, sizes = dropless.routed_mlp(x, weights, experts, gate, up, down)
    want = sum(weights[:, j, None] * (
        (jax.nn.silu(x @ gate[j]) * (x @ up[j])) @ down[j]) for j in range(k))
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-5)
    np.testing.assert_array_equal(sizes, [T] * k + [0] * (E - k))


def test_load_balancing_loss_is_k_for_an_even_router():
    L, E, T, k = 2, 8, 64, 3
    even = jnp.full((L, E), T * k / E)
    assert float(dropless.load_balancing_loss(
        even.astype(jnp.int32), jnp.full((L, E), T / E), T)) == pytest.approx(k)


# ------------------------------------------- the trunk with routed experts
def test_routed_model_through_the_kernel_path_matches_the_xla_form(
        as_tpu_program, monkeypatch):
    """prefill and decode_step hand the kernel the STACKED expert leaves and
    the layer as an index; the result is the XLA form's (float32, widths
    that tile: 128)."""
    cfg = dataclasses.replace(TINY, n_embd=128, intermediate_size=128, **F32)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 97), 0, 512)
    calls = []
    real = gmm.grouped_matmul
    monkeypatch.setattr(gmm, "grouped_matmul", lambda *a, **k: (
        calls.append((a[0].shape, a[1][0].shape if k.get("swiglu")
                      else a[1].shape, k["tm"])), real(*a, **k))[1])
    lg, cache = model.prefill(params, ids[:, :90], model.init_cache(1, 128))
    # 90 x 3 rows in tiles of 128: two whole tiles + one for each expert
    assert (((2 + 8) * 128, 128), (2, 8, 128, 128), 128) in calls
    steps = []
    for t in range(90, 97):
        lg, cache = model.decode_step(params, ids[:, t], cache)
        steps.append(lg[0])
    assert ((3 * 16, 128), (2, 8, 128, 128), 16) in calls           # 1 x 3 rows
    monkeypatch.undo()
    with jax.default_matmul_precision("highest"):
        want = model.apply(params, ids)[0]
    np.testing.assert_allclose(np.stack(steps), want[90:97], atol=1e-4, rtol=0)
    assert int(cache["expert_tokens"].sum()) == 2 * 97 * 3


def test_a_dense_llama_is_unchanged_by_the_new_fields():
    """No router leaf, no q/k gain, no ``expert_tokens`` in the cache, and
    ``_block`` still hands a scan (x, None)."""
    model = LlamaModel(dataclasses.replace(TINY, n_experts=0,
                                           n_experts_per_tok=0, qk_norm=False))
    params = model.init_params(jax.random.PRNGKey(0))
    assert set(params["blocks"]) == {
        "attn_norm_g", "q_w", "k_w", "v_w", "o_w", "mlp_norm_g", "gate_w",
        "up_w", "down_w"}
    assert set(model.init_cache(1, 16)) == {"k", "v", "pos"}
    assert set(model.cache_partition_specs()) == {"k", "v", "pos"}
    assert jax.tree.structure(model.param_partition_specs()) == \
        jax.tree.structure(params)


def test_routed_params_are_drawn_in_the_type_asked_for():
    model = LlamaModel(dataclasses.replace(TINY, param_dtype=jnp.bfloat16))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    assert {x.dtype for x in jax.tree.leaves(shapes)} == {jnp.dtype("bfloat16")}
    assert shapes["blocks"]["expert_gate_w"].shape == (2, 8, 64, 32)
    assert shapes["blocks"]["expert_down_w"].shape == (2, 8, 32, 64)
    assert shapes["blocks"]["router_w"].shape == (2, 64, 8)
    assert jax.tree.structure(model.param_partition_specs()) == \
        jax.tree.structure(shapes)
    # no float32 intermediate of a whole expert leaf in the draw
    text = jax.jit(model.init_params).lower(jax.random.PRNGKey(0)).as_text()
    assert "f32[2,8,64,32]" not in text.replace("x", ",")
    with pytest.raises(ValueError, match="n_experts_per_tok"):
        dataclasses.replace(TINY, n_experts_per_tok=9)


def test_initialize_trains_a_routed_model():
    """``deepspeed_tpu.initialize`` -> ``train_batch``: ZeRO-1 over the CPU
    mesh, bf16 compute; the loss (with its auxiliary term) falls."""
    model = LlamaModel(dataclasses.replace(TINY, remat="attn"))
    n = jax.device_count()
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": n, "steps_per_print": 0,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
        "bf16": {"enabled": True}, "zero_optimization": {"stage": 1}})
    rng = np.random.default_rng(0)
    p = 1.0 / (np.arange(512) + 10.0)
    batch = lambda: {"input_ids": rng.choice(
        512, size=(n, 32), p=p / p.sum()).astype(np.int32)}
    losses = [float(engine.train_batch(batch())) for _ in range(8)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.1, losses


# ------------------------------------------------- the engine and the server
def live_bytes():
    return sum(x.nbytes for x in jax.live_arrays())


@pytest.fixture
def one_device_mesh():
    """What a one-chip server's engine builds: every axis 1, one device (the
    CPU test mesh has eight, and replicating over them is a copy)."""
    from deepspeed_tpu.parallel.topology import ALL_AXES

    return jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape([1] * len(ALL_AXES)), ALL_AXES)


@pytest.fixture
def served():
    """(model, its bf16 params drawn in one call, bytes of one copy)."""
    model = LlamaModel(dataclasses.replace(TINY, param_dtype=jnp.bfloat16))
    before = live_bytes()
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    return model, params, live_bytes() - before


def test_init_inference_holds_served_type_weights_once(served,
                                                       one_device_mesh):
    model, params, one_copy = served
    before = live_bytes()
    engine = deepspeed_tpu.init_inference(model, dtype="bf16", params=params,
                                          max_out_tokens=128,
                                          mesh=one_device_mesh)
    assert live_bytes() - before == 0            # ONE copy live: the caller's
    assert all(a is b for a, b in zip(jax.tree.leaves(engine.params),
                                      jax.tree.leaves(params)))
    del params
    assert live_bytes() - before == 0            # and the engine keeps it
    out = engine.generate(np.arange(12, dtype=np.int32)[None],
                          max_new_tokens=4)
    assert out.shape == (1, 16)


def test_init_inference_still_casts_what_is_not_in_the_served_type(
        served, one_device_mesh):
    model, _, one_copy = served
    f32 = jax.jit(LlamaModel(TINY).init_params)(jax.random.PRNGKey(0))
    before = live_bytes()
    engine = deepspeed_tpu.init_inference(model, dtype="bf16", params=f32,
                                          max_out_tokens=128,
                                          mesh=one_device_mesh)
    assert live_bytes() - before == one_copy     # its own bf16 copy
    assert {x.dtype for x in jax.tree.leaves(engine.params)} == \
        {jnp.dtype("bfloat16")}
    assert f32["wte"].dtype == jnp.float32 and not f32["wte"].is_deleted()


def test_the_front_end_reads_the_expert_counter_once_a_request(served):
    """``init_inference`` -> ``serving.from_ds_config`` -> ``submit``: the
    tokens are ``generate()``'s, and when the request resolves the programs'
    own sum of routed pairs is in the tracer: (L, E), k pairs a layer for
    every position that went through the model."""
    from deepspeed_tpu import serving, telemetry
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    model, params, _ = served
    engine = deepspeed_tpu.init_inference(model, dtype="bf16", params=params,
                                          max_out_tokens=128)
    front = serving.from_ds_config(engine, DeepSpeedConfig({"serving": {}}))
    try:
        prompt = np.arange(20, dtype=np.int32)
        req = front.submit(prompt, max_new_tokens=20)
        req.result(timeout=300)
        assert req.status == "completed" and len(req.tokens) == 20
        want = np.asarray(engine.generate(prompt[None], max_new_tokens=20))
        assert req.tokens == want[0, 20:].tolist()
        mine = [s for s in telemetry.get_tracer().snapshot()
                if s.name == "moe/expert_tokens"
                and s.args.get("request") == req.id]
        assert len(mine) == 1 and mine[0].cat == "moe"
        counts = np.asarray(mine[0].args["counts"])
        # the prefill's token + ceil(19 / 16) = two 16-step ticks: 20 prompt
        # + 32 decoded positions (a tick steps on the token it is handed,
        # then on each it samples but the last)
        assert counts.shape == (2, 8)
        assert (counts.sum(axis=1) == 3 * (20 + 32)).all()
    finally:
        front.begin_drain("shutdown")
        front.drain(timeout=60.0)
