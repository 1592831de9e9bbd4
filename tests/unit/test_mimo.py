"""Window layers with a mixer, a sink and a RING cache of their own beside
full layers that keep the context (``models/llama.py``: ``win_blocks``,
``window_kv_head``, ``window_sink``; MiMo-V2-Flash's block), at a small size
on the CPU: the two kernels' new forms in interpret mode against the einsum
twins that define them (K and V rows of different widths, the sink as the
online softmax's initial state against the concatenated-column definition,
a ring's valid length, a window narrower than a sub-block), the ring against
a whole-context cache, prefill and decode through a ring that wraps against
the trunk, and what the serving front-end says of a request's cache. The
float32 reference is tests/benchmark/test_mimo_family.py's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.ops.pallas.decode_attention as da
import deepspeed_tpu.ops.pallas.flash_attention as fa
from deepspeed_tpu.models import common
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

KINDS = ("full_attention",) + ("sliding_attention",) * 4 \
    + ("full_attention", "sliding_attention")


def tiny_config(**over):
    """One dense full layer, then one period of routed layers (4 window, a
    full, a window): 2 and 4 KV heads, q.k 24 / v 16, 8 rotated columns, a
    window of 8."""
    kw = dict(vocab_size=256, n_positions=128, n_embd=64, n_layer=7, n_head=8,
              n_kv_head=2, head_dim=24, v_head_dim=16, rotary_dim=8,
              intermediate_size=32, dense_intermediate_size=128,
              n_dense_layers=1, n_experts=16, n_experts_per_tok=4,
              norm_topk_prob=True, router_scoring="sigmoid", router_bias=True,
              experts_held=(4, 4), layer_types=KINDS, sliding_window=8,
              window_kv_head=4, window_sink=True, rope_theta=5e6,
              window_rope_theta=1e4, value_scale=0.707, dtype=jnp.float32,
              remat=False)
    kw.update(over)
    return LlamaConfig(**kw)


@pytest.fixture(scope="module")
def tiny():
    model = LlamaModel(tiny_config())
    params = model.init_params(jax.random.PRNGKey(0))
    # scores that spread (a tiny width's are all alike): positions matter
    for stack in ("attn_blocks", "win_blocks", "dense_blocks"):
        params[stack] = {**params[stack], "q_w": params[stack]["q_w"] * 8.0}
    return model, params


@pytest.fixture
def interpret(monkeypatch):
    from jax.experimental import pallas as pl

    for mod in (da, fa):
        monkeypatch.setattr(mod.pl, "pallas_call", functools.partial(
            pl.pallas_call, interpret=True))


# ------------------------------------------------------------ the kernels
def _caches(key, B, S, kv, dh, dv, layers=2):
    kk, kv_ = jax.random.split(key)
    pack = lambda key, d: jnp.stack([common.kv_cache_rows(t, S) for t in
                                     jax.random.normal(key, (layers, B, S, kv,
                                                             d))])
    return pack(kk, dh), pack(kv_, dv)


@pytest.mark.parametrize("heads,kv,pos", [
    (16, 2, 5),         # 8 a group x 2 KV heads: group-major, a short ring
    (16, 2, 31),        # the whole ring valid
    (8, 8, 17),         # 1 a group x 8: row-major (MHA)
    (16, 4, 31)])       # 4 a group x 4
@pytest.mark.parametrize("sink", [False, True])
def test_decode_kernel_at_two_widths_with_a_sink(interpret, heads, kv, pos,
                                                 sink):
    """``decode_attn`` with K rows at 24 columns a head and V rows at 16, a
    sink a head as the initial state ``m = b, l = 1, acc = 0``, against the
    einsum twin, which lays the sink beside the scores as one more column."""
    B, S, dh, dv = 2, 32, 24, 16
    keys = jax.random.split(jax.random.PRNGKey(heads + kv + pos), 3)
    q = jax.random.normal(keys[0], (B, heads, dh))
    k, v = _caches(keys[1], B, S, kv, dh, dv)
    b = jax.random.normal(keys[2], (heads,)) * 2 if sink else None
    extra = {"v_dim": dv, **({"sink": b} if sink else {})}
    got = da.decode_attention(q, k, v, jnp.int32(1), jnp.int32(pos), n_kv=kv,
                              block_k=16, **extra)
    want = common.cached_decode_attention(q, k, v, jnp.int32(1),
                                          jnp.int32(pos), kv, **extra)
    assert got.shape == (B, heads, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    if sink:    # it takes mass: the rows weigh less than without it
        bare = common.cached_decode_attention(q, k, v, jnp.int32(1),
                                              jnp.int32(pos), kv, v_dim=dv)
        assert float(jnp.abs(bare - want).max()) > 1e-3


def test_the_sink_is_the_concatenated_column():
    """The twin's definition against the online softmax written out: the
    sink is a score with a zero value."""
    s = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 7))
    b = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 1))
    p = common._softmax_beside_sink(s, b)
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), b)
    want = jnp.exp(s - m) / (jnp.exp(b - m) + jnp.sum(jnp.exp(s - m), -1,
                                                      keepdims=True))
    np.testing.assert_allclose(np.asarray(p), np.asarray(want), atol=1e-6)
    assert float(jnp.sum(p, -1).max()) < 1.0


@pytest.mark.parametrize("T,window,block", [
    (512, 128, 512),    # the cell's window: sub-blocks of 256
    (384, 8, 128),      # a band far narrower than a sub-block
    (256, 300, 256),    # the window reaches past the prompt: still the
    (200, 64, 128)])    # windowed kernel (the sink is its); a padded length
def test_windowed_forward_with_a_sink_and_its_own_value_width(interpret, T,
                                                              window, block):
    keys = jax.random.split(jax.random.PRNGKey(T + window), 4)
    q, k = (jax.random.normal(key, (1, T, 4, 24)) for key in keys[:2])
    v = jax.random.normal(keys[2], (1, T, 4, 16))
    b = jax.random.normal(keys[3], (4,)) * 2
    got = fa.flash_attention(q, k, v, window=window, sink=b, block_q=block,
                             block_k=block)
    want = common.local_causal_attention(q, k, v, use_flash=False,
                                         window=window, sink=b)
    assert got.shape == (1, T, 4, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    bare = common.local_causal_attention(q, k, v, use_flash=False,
                                         window=window)
    assert float(jnp.abs(bare - want).max()) > 1e-3


def test_the_forward_plan_narrows_its_sub_block_for_a_narrow_window():
    plan = lambda t, w: fa.flash_forward_plan(t, 192, 128, jnp.bfloat16,
                                              window=w)
    narrow, wide = plan(8192, 128), plan(8192, 2048)
    assert (narrow.sub_block, wide.sub_block) == (256, 512)
    # two sub-blocks a q block, both masked: 512 columns a row for a band
    # of 128, where sub-blocks of 512 run 1,024
    assert narrow.sub_blocks_run == 2 * 32 - 1 == narrow.sub_blocks_masked
    assert plan(1024, 300).sub_block == 512         # the pinned digest's
    assert plan(24576, 128).grid_steps < 2 * 96


def test_a_sink_needs_a_window_and_has_no_gradient():
    q = jnp.zeros((1, 128, 2, 8))
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, q, q, sink=jnp.zeros(2))
    with pytest.raises(ValueError, match="window"):
        common.local_causal_attention(q, q, q, sink=jnp.zeros(2))


# ------------------------------------------------------------ the caches
def test_the_ring_holds_what_a_whole_cache_shows_through_the_window():
    """Positions written one by one into a ring of 8 slots and into a whole
    cache: the ring attended whole (valid length ``min(pos + 1, 8)``) gives
    what the whole cache gives under a window of 8, at every position, with
    K and V at their own widths and a sink."""
    B, S, W, kv, heads, dh, dv = 1, 40, 8, 2, 4, 24, 16
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    ks = jax.random.normal(keys[0], (S, B, 1, kv, dh))
    vs = jax.random.normal(keys[1], (S, B, 1, kv, dv))
    qs = jax.random.normal(keys[2], (S, B, heads, dh))
    b = jax.random.normal(keys[3], (heads,))
    whole = common.init_kv_cache(1, B, S, kv, (dh, dv), jnp.float32)
    ring = common.init_kv_ring(1, B, W, kv, (dh, dv), jnp.float32)
    assert whole["k"].shape[-1] == ring["win_k"].shape[-1] == 128
    assert ring["win_k"].shape[2] == W
    layer = jnp.int32(0)
    for pos in range(S):
        at = jnp.int32(pos)
        whole = {"k": common.kv_cache_write(whole["k"], ks[pos], layer, at),
                 "v": common.kv_cache_write(whole["v"], vs[pos], layer, at)}
        ring = {"win_k": common.kv_ring_write(ring["win_k"], ks[pos], layer,
                                              at),
                "win_v": common.kv_ring_write(ring["win_v"], vs[pos], layer,
                                              at)}
        want = common.cached_decode_attention(
            qs[pos], whole["k"], whole["v"], layer, at, kv, window=W,
            v_dim=dv, sink=b)
        got = common.cached_decode_attention(
            qs[pos], ring["win_k"], ring["win_v"], layer,
            jnp.minimum(at, W - 1), kv, v_dim=dv, sink=b)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)


@pytest.mark.parametrize("T", [5, 8, 21])
def test_a_prompts_last_window_lands_in_its_slots(T):
    """Position p of a prompt lies in slot ``p % window``, as a decode
    step's would: shorter than, equal to and longer than the ring."""
    W = 8
    t = jnp.arange(1, T + 1, dtype=jnp.float32).reshape(1, T, 1, 1) \
        * jnp.ones((1, T, 2, 4))
    ring = common.kv_ring_write(jnp.zeros((1, 1, W, 128)), t, jnp.int32(0), 0)
    for p in range(max(0, T - W), T):
        assert float(ring[0, 0, p % W, 0]) == p + 1
    assert common.cache_ring({"win_k": ring, "win_v": ring}) == (
        2 * W * 128 * 4, W)
    assert common.cache_ring({"k": ring}) == (0, 0)


# -------------------------------------------------------------- the model
def test_window_layers_hold_their_own_leaves(tiny):
    model, params = tiny
    c = model.config
    assert c.own_window and c.pattern == ("win",) * 4 + ("attn", "win")
    assert model.stacked_params_key == ("blocks", "attn_blocks", "win_blocks",
                                        "dense_blocks")
    assert "q_w" not in params["blocks"] and "sink" not in params["attn_blocks"]
    assert params["attn_blocks"]["k_w"].shape == (1, 64, 2 * 24)
    assert params["win_blocks"]["k_w"].shape == (5, 64, 4 * 24)
    assert params["win_blocks"]["v_w"].shape == (5, 64, 4 * 16)
    assert params["win_blocks"]["sink"].shape == (5, 8)
    assert params["dense_blocks"]["k_w"].shape == (1, 64, 2 * 24)
    assert sum(x.size for x in jax.tree.leaves(params)) == c.num_params()
    specs = model.param_partition_specs()
    assert jax.tree.structure(specs) == jax.tree.structure(
        jax.tree.map(lambda _: 0, params))
    cache = model.init_cache(2, 64)
    assert {n: cache[n].shape for n in ("k", "v", "win_k", "win_v")} == {
        "k": (2, 2, 64, 128), "v": (2, 2, 64, 128),
        "win_k": (5, 2, 8, 128), "win_v": (5, 2, 8, 128)}
    assert set(model.cache_partition_specs()) == set(cache)
    # 2 full layers x (128 + 128) lanes x 4 B a position; 5 rings a sequence
    assert common.cache_footprint(cache) == (2 * 256 * 4, 0)
    assert common.cache_ring(cache) == (5 * 8 * 256 * 4, 8)


@pytest.mark.parametrize("prompt", [5, 8, 20])
def test_prefill_and_decode_through_a_wrapping_ring_match_the_trunk(tiny,
                                                                    prompt):
    """The cached walk against the trunk (no cache: every window layer under
    its mask over the whole sequence), through a ring of 8 slots that wraps
    four times."""
    model, params = tiny
    ids = jax.random.randint(jax.random.PRNGKey(prompt), (1, 40), 0, 256)
    full = model.apply(params, ids)[0]
    logits, cache = jax.jit(model.prefill)(params, ids[:, :prompt],
                                           model.init_cache(1, 48))
    got = [logits[0]]
    step = jax.jit(model.decode_step)
    for t in range(prompt, 39):
        logits, cache = step(params, ids[:, t], cache)
        got.append(logits[0])
    assert int(cache["pos"]) == 39
    np.testing.assert_allclose(np.asarray(jnp.stack(got)),
                               np.asarray(full[prompt - 1:39]), atol=3e-5)
    assert float(jnp.abs(full).max()) > 0.1


def test_each_mechanism_moves_the_logits(tiny):
    """What the witness's controls break, each visible at this size: the
    sink, the partial rotary width, the rotary base by kind, the scaled v."""
    import dataclasses

    model, params = tiny
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 40), 0, 256)
    sound = model.apply(params, ids)
    for change in ({"use_rope": False}, {"rotary_dim": None},
                   {"window_rope_theta": 5e6, "rope_theta": 1e4},
                   {"value_scale": 1.0}):
        other = LlamaModel(dataclasses.replace(model.config, **change))
        assert float(jnp.abs(other.apply(params, ids) - sound).max()) > 1e-3, \
            change
    sinkless = {**params, "win_blocks": {
        **params["win_blocks"],
        "sink": jnp.full_like(params["win_blocks"]["sink"], -1e9)}}
    assert float(jnp.abs(model.apply(sinkless, ids) - sound).max()) > 1e-3


def test_the_kernels_are_reached_through_the_cached_walk(tiny, interpret,
                                                         monkeypatch):
    """The program for a TPU, run by the interpreter: prefill takes
    ``flash_fwd_win`` with the sink and ``flash_fwd`` at 24 / 16 columns, a
    decode step ``decode_attn`` over the ring and over the full layers' rows,
    and both agree with the einsum walk."""
    model, params = tiny
    ids = jax.random.randint(jax.random.PRNGKey(5), (1, 21), 0, 256)

    def walk():
        logits, cache = model.prefill(params, ids[:, :16],
                                      model.init_cache(1, 32))
        out = [logits]
        for t in range(16, 21):
            logits, cache = model.decode_step(params, ids[:, t], cache)
            out.append(logits)
        return jnp.stack(out)

    want = walk()
    real = common._kernel_target
    monkeypatch.setattr(common, "_kernel_target", lambda: (real()[0], True))
    text = str(jax.make_jaxpr(lambda: model.prefill(
        params, ids[:, :16], model.init_cache(1, 32)))())
    import re

    assert set(re.findall(r"name=(flash\w+)", text)) == {"flash_fwd",
                                                         "flash_fwd_win"}
    np.testing.assert_allclose(np.asarray(walk()), np.asarray(want),
                               atol=3e-5)


def test_a_model_without_the_keys_is_the_model_it_was():
    """afmoe's pattern (window and full layers with the SAME leaves) keeps
    its leaves in ``blocks`` and the whole context for every layer."""
    c = tiny_config(window_kv_head=None, window_sink=False,
                    window_rope_theta=None, v_head_dim=0, rotary_dim=None,
                    value_scale=1.0)
    model = LlamaModel(c)
    assert not c.own_window and c.n_attn_layers == 7
    assert model.stacked_params_key == ("blocks", "dense_blocks")
    cache = model.init_cache(1, 16)
    assert "win_k" not in cache and cache["k"].shape[0] == 7
    with pytest.raises(ValueError, match="window_kv_head"):
        tiny_config(layer_types=None)
    with pytest.raises(ValueError, match="rotary_dim"):
        tiny_config(rotary_dim=7)


# ------------------------------------------------------- the serving path
def test_a_request_says_what_its_two_caches_hold(tiny):
    """``init_inference`` -> ``ServingFrontEnd.submit``: the ``request`` span
    closes with ``cache_bytes`` over the full layers' rows, ``window_bytes``
    a sequence and the decode steps that overwrote a live slot; the counter
    sums them; the admission's footprint tells the two apart."""
    import deepspeed_tpu
    from deepspeed_tpu import serving, telemetry
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.serving.admission import kv_bytes_by_kind

    model, params = tiny
    engine = deepspeed_tpu.init_inference(model, dtype="fp32", params=params,
                                          max_out_tokens=64)
    front = serving.from_ds_config(engine, DeepSpeedConfig({"serving": {
        "decode_tick_tokens": 4, "default_deadline_s": 120.0}}))
    try:
        assert kv_bytes_by_kind(model, 64) == {
            "per_position": 2 * 256 * 4, "per_sequence": 5 * 8 * 256 * 4}
        req = front.submit(np.arange(5, dtype=np.int32), max_new_tokens=9)
        req.result(timeout=300.0)
        assert req.status == "completed" and len(req.tokens) == 9
        assert (req.cache_window_bytes, req.cache_ring_slots) == (
            5 * 8 * 256 * 4, 8)
    finally:
        front.begin_drain("shutdown")
        front.drain(timeout=60.0)
    # (the span closes after the request resolves: read it from a dead server)
    span = [s for s in telemetry.get_tracer().snapshot()
            if s.name == "request" and s.args.get("request") == req.id][-1]
    # 5 prompt positions + 2 ticks of 4: positions 8 .. 12 overwrote
    positions = 5 + 8
    assert span.args["cache_positions"] == positions
    assert span.args["cache_bytes"] == positions * 2 * 256 * 4
    assert span.args["window_bytes"] == 5 * 8 * 256 * 4
    assert span.args["ring_wraps"] == positions - 8
    assert front.counts["ring_wraps"] == positions - 8      # the counter's
