"""The mechanisms a chip's share of a large MLA + MoE model brings into the
program (models/llama.py, models/common.py, moe/dropless.py and the three
kernels): latent attention un-absorbed and absorbed, the latent cache and its
decode kernel (interpret mode, against the einsum), the flash forward with a
value width of its own, the sigmoid router, a share of the experts (the
shares add up; a step with no held pair), sandwich norm, two stacks, and the
front-end's counters. The family's reference and the benchmark's side are in
tests/benchmark/test_pangu_family.py; lowering for the chip in
tests/unit/test_chip_bringup.py."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import common
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.ops.pallas import flash_attention as fa
from deepspeed_tpu.ops.pallas import grouped_matmul as gmm

# 1 dense + 2 routed layers, all six mechanisms on: latent attention,
# sandwich norm, a sigmoid router x 2.5, a shared expert, the second of four
# shares of 8 experts of a 32-wide router, two stacks
TINY = LlamaConfig(
    vocab_size=512, n_positions=128, n_embd=64, n_layer=3, n_head=4,
    intermediate_size=32, n_experts=32, n_experts_per_tok=4,
    norm_topk_prob=True, n_dense_layers=1, dense_intermediate_size=96,
    n_shared_experts=1, router_scoring="sigmoid", routed_scaling_factor=2.5,
    experts_held=(8, 8), q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, sandwich_norm=True, rope_theta=25.6e6)
F32 = dict(dtype=jnp.float32, remat=False, use_flash_attention=False)


@pytest.fixture
def interpreted(monkeypatch):
    """The kernels run by the Pallas interpreter (the test asks; no kernel
    picks it by itself)."""
    from jax.experimental import pallas as pl

    for module in (gmm, da, fa):
        monkeypatch.setattr(module.pl, "pallas_call",
                            functools.partial(pl.pallas_call, interpret=True))


@pytest.fixture
def as_tpu_program(monkeypatch, interpreted):
    """Make model code believe its program is for a TPU: the paths it
    chooses there, run by the interpreter."""
    real = common._kernel_target
    monkeypatch.setattr(common, "_kernel_target", lambda: (real()[0], True))


# ---------------------------------------------------- the latent decode kernel
def latent_case(B, S, H, C, v_width, seed=0, layers=2, dtype=jnp.float32):
    kq, kc = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (B, H, C), jnp.float32).astype(dtype)
    rows = jax.random.normal(kc, (layers, B, S, 1, C), jnp.float32)
    cache = jnp.stack([common.kv_cache_rows(t, S) for t in rows.astype(dtype)])
    return q, cache


@pytest.mark.parametrize("pos", [0, 63, 64, 200, 255])
@pytest.mark.parametrize("block_k", [64, 256, 1024])
def test_latent_decode_kernel_matches_the_einsum(interpreted, pos, block_k):
    """One cache operand: keys = the rows, values = their first 128 lanes."""
    q, cache = latent_case(2, 256, 8, 192, 128)
    assert cache.shape == (2, 2, 256, 256)              # 192 -> 2 lane tiles
    scale = 1 / math.sqrt(24)
    got = da.latent_decode_attention(q, cache, jnp.int32(1), jnp.int32(pos),
                                     v_width=128, scale=scale, block_k=block_k)
    want = common.latent_decode_attention(q, cache, jnp.int32(1),
                                          jnp.int32(pos), 128, scale)
    assert got.shape == (2, 8, 128)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_latent_decode_kernel_ignores_what_lies_past_pos(interpreted):
    """Stale rows past ``pos`` (a longer request's) weigh nothing."""
    q, cache = latent_case(1, 128, 4, 192, 128, seed=3)
    dirty = cache.at[:, :, 41:].set(1e4)
    args = (jnp.int32(0), jnp.int32(40))
    np.testing.assert_array_equal(
        da.latent_decode_attention(q, cache, *args, v_width=128, scale=0.2),
        da.latent_decode_attention(q, dirty, *args, v_width=128, scale=0.2))


def test_latent_decode_kernel_refuses_a_value_that_is_no_lane_tile():
    q, cache = latent_case(1, 64, 4, 192, 128)
    with pytest.raises(ValueError, match="as the value"):
        da.latent_decode_attention(q, cache, 0, 0, v_width=100, scale=1.0)
    with pytest.raises(ValueError, match="asked of them"):
        da.latent_decode_attention(jnp.zeros((1, 4, 300)), cache, 0, 0,
                                   v_width=128, scale=1.0)


def test_the_kernels_never_interpret_themselves():
    import inspect

    assert "interpret" not in inspect.getsource(da)


# ------------------------------------------ flash forward, v narrower than q.k
@pytest.mark.parametrize("T", [256, 200])       # tiles; padded to 256
def test_flash_forward_takes_a_value_width_of_its_own(interpreted, T):
    """q.k at 192 columns, v at 128, the scale 1/sqrt(192): no padding of v,
    no (T, T) scores; against the einsum."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(T), 3)
    q = jax.random.normal(kq, (1, T, 2, 192))
    k = jax.random.normal(kk, (1, T, 2, 192))
    v = jax.random.normal(kv, (1, T, 2, 128))
    got = fa.flash_attention(q, k, v, block_q=128, block_k=128)
    want = common.local_causal_attention(q, k, v, use_flash=False)
    assert got.shape == (1, T, 2, 128)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_flash_backward_takes_a_value_width_of_its_own(interpreted):
    """Latent attention trains (Kimi-Linear): the backward kernels take one
    width, so a narrower v goes in with zero columns beside o and its
    cotangent, and the gradients are the einsum path's."""
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k = (jax.random.normal(kk, (1, 128, 2, 48)) for kk in keys[:2])
    v = jax.random.normal(keys[2], (1, 128, 2, 32))
    loss = lambda attend: lambda q, k, v: jnp.sum(attend(q, k, v) ** 2)
    got = jax.grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, block_q=128, block_k=128)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: common.local_causal_attention(
        q, k, v, use_flash=False)), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=1e-4)


# ------------------------------------------------------------------ the router
def test_sigmoid_router_against_a_hand_computed_case():
    """scores = sigmoid(logits); the k largest SCORES; divided by their sum
    (+ 1e-20); x the scaling factor."""
    x = jnp.eye(2, 3)                                   # logits = rows of w
    w = jnp.log(jnp.array([[1., 3., 1 / 3, 9., 1., 1 / 9],
                           [1 / 3, 1., 1., 1 / 9, 3., 9.],
                           [0., 0., 0., 0., 0., 0.]]) + 1e-30)
    probs, weights, experts = dropless.route_topk(
        x, w, 2, renormalize=True, scoring="sigmoid", scale=2.5)
    # sigmoid(log a) = a / (1 + a): 1/2, 3/4, 1/4, 9/10, 1/2, 1/10
    np.testing.assert_allclose(probs[0], [.5, .75, .25, .9, .5, .1], atol=1e-6)
    np.testing.assert_array_equal(experts, [[3, 1], [5, 4]])
    np.testing.assert_allclose(weights[0], [2.5 * .9 / 1.65, 2.5 * .75 / 1.65],
                               atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.5, atol=1e-6)
    _, plain, _ = dropless.route_topk(x, w, 2, scoring="sigmoid")
    np.testing.assert_allclose(plain[1], [.9, .75], atol=1e-6)


def test_the_softmax_router_is_what_it_was():
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    probs, weights, _ = dropless.route_topk(x, w, 3, renormalize=True)
    want = jax.nn.softmax(np.asarray(x) @ np.asarray(w))
    np.testing.assert_allclose(probs, want, atol=1e-6)
    top = np.sort(want, axis=-1)[:, ::-1][:, :3]
    np.testing.assert_allclose(weights, top / top.sum(-1, keepdims=True),
                               atol=1e-6)


# ------------------------------------------------------ a share of the experts
def expert_case(T=24, D=16, F=8, E=8, k=3, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (T, D))
    gate, up = (jax.random.normal(kk, (E, D, F)) for kk in keys[1:3])
    down = jax.random.normal(keys[3], (E, F, D))
    experts = jnp.argsort(jax.random.uniform(keys[4], (T, E)))[:, :k].astype(
        jnp.int32)
    return x, jax.random.uniform(keys[5], (T, k)), experts, gate, up, down


def dense_loop(x, weights, experts, gate, up, down, first=0):
    """(token, choice) pairs one by one; a pair outside the leaves adds 0."""
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j in range(experts.shape[1]):
            e = int(experts[t, j]) - first
            if 0 <= e < gate.shape[0]:
                h = jax.nn.silu(x[t] @ gate[e]) * (x[t] @ up[e])
                out[t] += float(weights[t, j]) * np.asarray(h @ down[e])
    return out


@pytest.mark.parametrize("first,count", [(0, 8), (0, 3), (2, 4), (5, 3)])
def test_routed_mlp_computes_the_pairs_that_fall_on_its_share(first, count):
    x, weights, experts, gate, up, down = expert_case()
    cut = slice(first, first + count)
    out, sizes = dropless.routed_mlp(x, weights, experts, gate[cut], up[cut],
                                     down[cut], first=first)
    want = dense_loop(x, weights, experts, gate[cut], up[cut], down[cut], first)
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-5)
    held = np.bincount(np.asarray(experts).ravel(), minlength=8)[cut]
    np.testing.assert_array_equal(sizes, held)


@pytest.mark.parametrize("T", [1, 40, 97])      # a decode step; thin; full tile
def test_the_shares_kernel_path_matches_the_xla_form(as_tpu_program, T):
    """The grouped matmul with rows that belong to no held group: through
    ``routed_mlp``'s kernel path (stacked leaves, a traced layer) against
    its XLA form, both given the same share."""
    D, F, E, k = 128, 128, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(T), 6)
    x = jax.random.normal(keys[0], (T, D))
    gate, up = (jax.random.normal(kk, (2, 4, D, F)) * .1 for kk in keys[1:3])
    down = jax.random.normal(keys[3], (2, 4, F, D)) * .1
    experts = jnp.argsort(jax.random.uniform(keys[4], (T, E)))[:, :k].astype(
        jnp.int32)
    weights = jax.random.uniform(keys[5], (T, k))
    got, sizes = dropless.routed_mlp(x, weights, experts, gate, up, down,
                                     layer=jnp.int32(1), first=8)
    want, want_sizes = dropless.routed_mlp(
        x, weights, experts, gate[1], up[1], down[1], first=8)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(sizes, want_sizes)


@pytest.mark.parametrize("held_choices", [4, 3, 0])
def test_a_prefills_share_goes_through_the_kernel_in_chunks(as_tpu_program,
                                                            held_choices):
    """A call larger than ``share_capacity`` (64 tokens x 4 of a router 16
    wide, 4 held: 128 rows of 256) sends only its held pairs' rows through
    the kernel: every pair held = two chunks, three of four = a chunk and a
    half, none = no trip at all (NaN weights are never read, exact zeros)."""
    T, D, F, k = 64, 128, 128, 4
    assert dropless.share_capacity(T * k, 4, 16) == 128
    keys = jax.random.split(jax.random.PRNGKey(held_choices), 5)
    x = jax.random.normal(keys[0], (T, D))
    draw = lambda kk, *shape: jax.random.normal(kk, shape) * .1 \
        if held_choices else jnp.full(shape, jnp.nan)
    gate, up = (draw(kk, 2, 4, D, F) for kk in keys[1:3])
    down = draw(keys[3], 2, 4, F, D)
    # a token's first ``held_choices`` experts are held (8 .. 11), in an
    # order of its own; the others are another chip's
    held = 8 + jnp.argsort(jax.random.uniform(keys[4], (T, 4)))
    experts = jnp.concatenate([held[:, :held_choices],
                               jnp.arange(4 - held_choices)[None].repeat(T, 0)],
                              axis=1).astype(jnp.int32)
    weights = jax.random.uniform(keys[4], (T, k))
    got, sizes = dropless.routed_mlp(x, weights, experts, gate, up, down,
                                     layer=jnp.int32(1), first=8, n_experts=16)
    assert int(sizes.sum()) == T * held_choices
    if not held_choices:
        np.testing.assert_array_equal(got, np.zeros((T, D), np.float32))
        return
    want, want_sizes = dropless.routed_mlp(
        x, weights, experts, gate[1], up[1], down[1], first=8, n_experts=16)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(got, dense_loop(
        x, weights, experts, gate[1], up[1], down[1], first=8),
        atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(sizes, want_sizes)


def test_rows_wider_than_the_measured_scatter_keep_the_full_size_buffers(
        as_tpu_program, monkeypatch):
    """The compact form is taken where a float32 row is at most
    ``_SCATTER_ROW_BYTES`` (what the chip runs of PR 49 showed to gain); one
    lane tile wider and the call goes through the form it had, to the same
    numbers."""
    T, F, k = 40, 128, 4
    D = dropless._SCATTER_ROW_BYTES // 4 + 128
    monkeypatch.setattr(dropless, "_share_kernel_mlp", lambda *a: 1 / 0)
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (T, D))
    gate, up = (jax.random.normal(kk, (1, 4, D, F)) * .02 for kk in keys[1:3])
    down = jax.random.normal(keys[3], (1, 4, F, D)) * .1
    experts = jnp.argsort(jax.random.uniform(keys[4], (T, 16)))[:, :k].astype(
        jnp.int32)
    weights = jax.random.uniform(keys[5], (T, k))
    got, _ = dropless.routed_mlp(x, weights, experts, gate, up, down,
                                 layer=jnp.int32(0), first=8, n_experts=16)
    want, _ = dropless.routed_mlp(x, weights, experts, gate[0], up[0],
                                  down[0], first=8, n_experts=16)
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-3)
    with pytest.raises(ZeroDivisionError):      # at 128 columns: compact
        dropless.routed_mlp(x[:, :128], weights, experts, gate[:, :, :128],
                            up[:, :, :128], down[..., :128],
                            layer=jnp.int32(0), first=8, n_experts=16)


def test_a_step_with_no_held_pair_is_exactly_zero_and_runs_no_kernel(
        as_tpu_program, monkeypatch):
    """``n_active`` 0: every tile skipped, in fact no expert kernel called
    (the ``cond`` takes the other branch), and the output is exact zeros."""
    D = F = 128
    x = jax.random.normal(jax.random.PRNGKey(0), (1, D))
    w = jnp.full((1, 4, D, F), jnp.nan)         # must never be read
    experts = jnp.array([[0, 3, 9, 31]], jnp.int32)     # none in 16 .. 19
    sizes, tile_group, n_active, _, _ = gmm.group_layout(
        jnp.array([4, 4, 4, 4]), 4, 16)
    assert int(n_active) == 0 and int(sizes.sum()) == 0
    assert (np.asarray(tile_group) < 4).all()
    out, sizes = dropless.routed_mlp(x, jnp.ones((1, 4)), experts, w, w,
                                     jnp.full((1, 4, F, D), jnp.nan),
                                     layer=jnp.int32(0), first=16)
    np.testing.assert_array_equal(out, np.zeros((1, D), np.float32))
    np.testing.assert_array_equal(sizes, [0, 0, 0, 0])


def test_group_layout_gives_unheld_rows_no_tile():
    """Rows of group ``n_groups`` sort last and take no tile; the held rows'
    layout is what it is without them."""
    held = jnp.array([2, 0, 2, 1, 0, 2], jnp.int32)
    mixed = jnp.array([2, 3, 0, 3, 2, 1, 3, 0, 2], jnp.int32)   # 3 = no group
    a = gmm.group_layout(held, 3, 16)
    b = gmm.group_layout(mixed, 3, 16)
    np.testing.assert_array_equal(a[0], b[0])
    assert int(a[2]) == int(b[2]) == 3
    np.testing.assert_array_equal(np.asarray(a[1])[:3], np.asarray(b[1])[:3])
    keep = np.asarray(mixed) < 3
    np.testing.assert_array_equal(np.asarray(b[4])[keep], a[4])
    # a padded row of an active tile holds a HELD row
    assert keep[np.asarray(b[3])[:3 * 16]].all()


# ------------------------------------------------------------- the whole block
def model_and_params(cfg=TINY, **over):
    model = LlamaModel(dataclasses.replace(cfg, **{**F32, **over}))
    return model, model.init_params(jax.random.PRNGKey(0))


def test_the_shares_add_up_to_the_uncut_layer():
    """32 experts in 4 shares of 8: the four shares' routed parts + the
    shared expert ONCE = what a chip that holds all 32 gives for the layer.
    Each share routes over all 32 and computes its own 8."""
    whole, params = model_and_params(experts_held=None)
    blk = jax.tree.map(lambda x: x[1], params["blocks"])
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 50, 64))
    with jax.default_matmul_precision("highest"):
        want, _ = whole._mlp(h, blk)
        shared = whole._swiglu(h, blk["shared_gate_w"], blk["shared_up_w"],
                               blk["shared_down_w"])
        parts = []
        for first in (0, 8, 16, 24):
            share = LlamaModel(dataclasses.replace(
                whole.config, experts_held=(first, 8)))
            mine = {n: (v[first:first + 8] if n in share.EXPERT_LEAVES else v)
                    for n, v in blk.items()}
            out, (sizes, _) = share._mlp(h, mine)
            assert sizes.shape == (8,)
            parts.append(out - shared)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5, rtol=1e-5)


def test_absorbed_decode_is_the_unabsorbed_attention():
    """prefill (un-absorbed, per-head keys and values) then ``decode_step``
    (absorbed, over the latent rows) against ``apply``'s full pass, float32:
    the same mathematics, reassociated."""
    model, params = model_and_params()
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 512)
    with jax.default_matmul_precision("highest"):
        want = model.apply(params, ids)
        lg, cache = model.prefill(params, ids[:, :30], model.init_cache(2, 64))
        np.testing.assert_allclose(lg, want[:, 29], atol=2e-5, rtol=0)
        for t in range(30, 40):
            lg, cache = model.decode_step(params, ids[:, t], cache)
            np.testing.assert_allclose(lg, want[:, t], atol=2e-5, rtol=0)
    # one row a position for ALL heads: [c_kv 32 | k_rope 8] in one lane tile
    assert cache["kv"].shape == (3, 2, 64, 128) and int(cache["pos"]) == 40
    assert set(cache) == {"kv", "pos", "expert_tokens"}
    assert float(jnp.abs(cache["kv"][:, :, :40, :40]).min()) > 0
    assert float(jnp.abs(cache["kv"][..., 40:]).max()) == 0
    assert float(jnp.abs(cache["kv"][:, :, 40:]).max()) == 0
    # of 2 routed layers x 2 sequences x 40 positions x 4 choices
    assert cache["expert_tokens"].shape == (2, 8)
    assert 0 < int(cache["expert_tokens"].sum()) < 2 * 2 * 40 * 4


def test_the_kernel_paths_match_the_xla_forms(as_tpu_program):
    """Flash at (nope + rope, v), the latent decode kernel in the stacked
    cache and the share's grouped matmul, all as a program for a TPU takes
    them (interpreted), against ``apply`` (widths that tile)."""
    model, params = model_and_params(
        n_embd=128, intermediate_size=128, qk_nope_head_dim=64,
        qk_rope_head_dim=64, v_head_dim=64, kv_lora_rank=128,
        use_flash_attention=True)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 136), 0, 512)
    lg, cache = model.prefill(params, ids[:, :128], model.init_cache(1, 256))
    steps = []
    for t in range(128, 136):
        lg, cache = model.decode_step(params, ids[:, t], cache)
        steps.append(lg[0])
    with jax.default_matmul_precision("highest"):
        want = model.apply(params, ids)[0]      # flash too, no cache
    np.testing.assert_allclose(np.stack(steps), want[128:136], atol=2e-4,
                               rtol=0)


def test_the_block_is_chosen_by_the_leaves_it_holds():
    model, params = model_and_params()
    dense, routed = params["dense_blocks"], params["blocks"]
    mixer = {"q_a_w", "q_a_norm_g", "q_b_w", "kv_a_w", "kv_a_norm_g",
             "kv_b_k_w", "kv_b_v_w", "o_w"}
    norms = {"attn_norm_g", "post_attn_norm_g", "mlp_norm_g", "post_mlp_norm_g"}
    assert set(dense) == mixer | norms | {"gate_w", "up_w", "down_w"}
    assert set(routed) == mixer | norms | {
        "router_w", "expert_gate_w", "expert_up_w", "expert_down_w",
        "shared_gate_w", "shared_up_w", "shared_down_w"}
    assert dense["gate_w"].shape == (1, 64, 96)
    assert routed["router_w"].shape == (2, 64, 32)          # the ROUTER's width
    assert routed["expert_gate_w"].shape == (2, 8, 64, 32)  # the share held
    assert routed["kv_b_k_w"].shape == (2, 4, 16, 32)
    assert routed["kv_b_v_w"].shape == (2, 4, 32, 16)
    assert jax.tree.structure(model.param_partition_specs()) == \
        jax.tree.structure(params)
    assert jax.tree.structure(model.cache_partition_specs()) == \
        jax.tree.structure(model.init_cache(1, 8))
    assert sum(x.size for x in jax.tree.leaves(params)) == \
        model.config.num_params()


def test_a_dropped_mechanism_changes_the_logits():
    """Each of them is in the arithmetic: taking one out moves a logit by
    far more than the 2e-5 the float32 comparisons above allow (the rotary
    key least: at this size and init the scores are small)."""
    model, params = model_and_params()
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, 512)
    base = model.apply(params, ids)
    blocks = params["blocks"]
    broken = {
        "post-norm gains": {**blocks, "post_mlp_norm_g":
                            blocks["post_mlp_norm_g"] * 2},
        "shared expert": {**blocks, "shared_down_w":
                          jnp.zeros_like(blocks["shared_down_w"])},
        "rotary key": {**blocks, "kv_a_w": blocks["kv_a_w"].at[..., 32:].set(0)},
        "routed experts": {**blocks, "expert_down_w":
                           jnp.zeros_like(blocks["expert_down_w"])},
    }
    for what, changed in broken.items():
        moved = jnp.abs(model.apply({**params, "blocks": changed}, ids) - base)
        assert float(moved.max()) > 1e-3, what


def test_config_refuses_what_the_block_cannot_be():
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(TINY, experts_held=(30, 8))
    with pytest.raises(ValueError, match="router_scoring"):
        dataclasses.replace(TINY, router_scoring="tanh")
    with pytest.raises(ValueError, match="n_dense_layers"):
        dataclasses.replace(TINY, n_dense_layers=3)
    with pytest.raises(ValueError, match="latent attention"):
        dataclasses.replace(TINY, v_head_dim=0)
    # no low-rank q: the queries straight from ``q_w``
    direct = LlamaModel(dataclasses.replace(TINY, q_lora_rank=0))
    leaves = jax.eval_shape(direct.init_params, jax.random.PRNGKey(0))
    assert "q_w" in leaves["blocks"] and "q_a_w" not in leaves["blocks"]
    with pytest.raises(ValueError, match="load-balancing"):
        dataclasses.replace(TINY, router_aux_loss_coef=0.01)


def test_a_share_trains_through_initialize():
    """The trunk (two stacks, remat, the XLA expert form) under
    ``initialize``: the loss falls."""
    model = LlamaModel(dataclasses.replace(TINY, remat="attn"))
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 8, "steps_per_print": 0,
        "optimizer": {"type": "Adam", "params": {"lr": 3e-3}},
        "bf16": {"enabled": True}, "zero_optimization": {"stage": 1}})
    batch = {"input_ids": np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (8, 32), 0, 512))}
    losses = [float(engine.train_batch(batch)) for _ in range(6)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses


# ------------------------------------------------------------- the front-end
def test_the_front_end_reports_the_cache_and_the_share(monkeypatch):
    """``init_inference`` -> ``from_ds_config`` -> ``submit``: ``generate()``'s
    tokens; the ``request`` span closes with the cache its positions hold,
    the ``moe/expert_tokens`` instant with the held counts, the share they
    are of and every pair the router made."""
    from deepspeed_tpu import serving, telemetry
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    model = LlamaModel(dataclasses.replace(TINY, param_dtype=jnp.bfloat16))
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
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
        spans = telemetry.get_tracer().snapshot()
        mine = [s for s in spans if s.name == "moe/expert_tokens"
                and s.args.get("request") == req.id]
        assert len(mine) == 1
        args = mine[0].args
        # 20 prompt + two 16-step ticks = 52 positions, 2 routed layers, top-4
        assert args["routed_pairs"] == 2 * 52 * 4
        assert (args["held_first"], args["held"]) == (8, 8)
        counts = np.asarray(args["counts"])
        assert counts.shape == (2, 8) and 0 < counts.sum() < args["routed_pairs"]
        span = [s for s in spans if s.name == "request"
                and s.args.get("request") == req.id][0]
        assert span.args["cache_positions"] == 52
        # 3 layers x one lane tile (32 + 8 values, 88 pad lanes) x 2 bytes
        # a position: what the cache's own arrays hold
        assert span.args["cache_bytes"] == 52 * 3 * 128 * 2
    finally:
        front.begin_drain("shutdown")
        front.drain(timeout=60.0)
