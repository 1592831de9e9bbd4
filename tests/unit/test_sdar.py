"""Generation by diffusion over blocks (SDAR): the two kernels' new forms in
interpret mode against their einsum twins, the model's ``block_step``
against ``apply``, the engine's block step (``generate()`` fused and split,
the three remasking rules, EOS, lengths that are no whole number of blocks)
and the serving front-end's accounting when a step emits a block. The
float32 reference is tests/benchmark/test_sdar_family.py's.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.ops.pallas.decode_attention as da
import deepspeed_tpu.ops.pallas.flash_attention as fa
from deepspeed_tpu.inference import engine as ie
from deepspeed_tpu.models import common
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel


@pytest.fixture
def interpret(monkeypatch):
    if jax.default_backend() != "tpu":
        from jax.experimental import pallas as pl

        call = functools.partial(pl.pallas_call, interpret=True)
        monkeypatch.setattr(da.pl, "pallas_call", call)
        monkeypatch.setattr(fa.pl, "pallas_call", call)


# ------------------------------------------------------------ the two kernels
def _cache(B, S, KV, Dh, seed, layers=2):
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    pack = lambda key: jnp.stack([
        common.kv_cache_rows(t, S) for t in
        jax.random.normal(key, (layers, B, S, KV, Dh), jnp.float32)])
    return pack(kk), pack(kv)


# (Lb, heads, KV heads): SDAR's 4 x 32 on 4 (group-major: 128 rows), a block
# whose rows a group are no whole tile (group-major, padded), many KV heads
# (row-major), one row
@pytest.mark.parametrize("Lb,heads,kv,pos", [
    (4, 32, 4, 3), (4, 32, 4, 127), (4, 32, 4, 128),
    (3, 8, 2, 200), (4, 16, 16, 130), (2, 4, 4, 255)])
def test_decode_attn_takes_a_block_of_query_positions(interpret, Lb, heads, kv,
                                                      pos):
    B, S, Dh = 1, 256, 32
    q = jax.random.normal(jax.random.PRNGKey(pos), (B, Lb, heads, Dh))
    k, v = _cache(B, S, kv, Dh, seed=pos + 1)
    got = da.decode_attention(q, k, v, jnp.int32(1), jnp.int32(pos), n_kv=kv)
    want = common.cached_decode_attention(q, k, v, jnp.int32(1),
                                          jnp.int32(pos), kv)
    assert got.shape == want.shape == (B, Lb, heads, Dh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    # every position of the block sees every valid slot: one position at a
    # time gives the same rows
    one = common.cached_decode_attention(q[:, 1], k, v, jnp.int32(1),
                                         jnp.int32(pos), kv)
    np.testing.assert_allclose(np.asarray(want[:, 1]), np.asarray(one),
                               atol=2e-6, rtol=2e-6)


def test_one_position_is_the_call_it_was(interpret):
    """``Lb`` = 1: the plan every standing cell has (row-major), the same
    traced program for (B, H, Dh), and (B, 1, H, Dh) equal to it."""
    for heads, kv in ((25, 25), (16, 16), (64, 8), (4, 2)):
        assert da.query_plan(kv, heads // kv) == (
            False, -(-kv // 16) * 16, heads // kv)
    assert da.query_plan(4, 4 * 8) == (True, 32, 4)         # SDAR's block
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    k, v = _cache(2, 256, 4, 32, seed=1)
    one = da.decode_attention(q, k, v, jnp.int32(0), jnp.int32(77), n_kv=4)
    block = da.decode_attention(q[:, None], k, v, jnp.int32(0), jnp.int32(77),
                                n_kv=4)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(block[:, 0]))


def _block_reference(q, k, v, block):
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    at = jnp.arange(T)
    s = jnp.where((at[None, :] // block <= at[:, None] // block)[None, None],
                  s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("T,block,tile", [(256, 4, 128), (64, 4, 512),
                                          (200, 8, 128)])
def test_flash_forward_under_the_block_causal_mask(interpret, T, block, tile):
    keys = jax.random.split(jax.random.PRNGKey(T + block), 3)
    q, k, v = (jax.random.normal(key, (1, T, 2, 32)) for key in keys)
    got = fa.flash_attention(q, k, v, block_q=tile, block_k=tile, block=block)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_block_reference(q, k, v, block)),
                               atol=2e-5, rtol=0)
    # the einsum twin the CPU path takes
    np.testing.assert_allclose(
        np.asarray(common.local_causal_attention(q, k, v, False, block=block)),
        np.asarray(_block_reference(q, k, v, block)), atol=2e-5, rtol=0)
    # the span lists and the plan are the causal ones
    assert fa.flash_forward_plan(T, 32, 32, q.dtype, tile, tile) == \
        fa.flash_forward_plan(T, 32, 32, q.dtype, tile, tile, None)


def test_the_block_mask_is_the_forwards_alone(interpret):
    q = jnp.ones((1, 128, 1, 32))
    with pytest.raises(NotImplementedError, match="forward"):
        jax.grad(lambda q: jnp.sum(fa.flash_attention(q, q, q, block=4)))(q)
    for bad in ({"block": 3}, {"block": 256}, {"block": 4, "window": 8},
                {"block": 4, "causal": False}):
        with pytest.raises(ValueError, match="block"):
            fa.flash_attention(q, q, q, **bad)
    with pytest.raises(ValueError, match="length"):
        fa.flash_attention(q[:, :126], q[:, :126], q[:, :126], block=4)
    # no block: the call it was
    plain = str(jax.make_jaxpr(fa.flash_attention)(q, q, q))
    assert plain == str(jax.make_jaxpr(functools.partial(
        fa.flash_attention, block=None))(q, q, q)) == str(jax.make_jaxpr(
            functools.partial(fa.flash_attention, block=1))(q, q, q))


# ----------------------------------------------------------------- the model
def tiny(**over):
    """4 heads x 16 on 2 KV heads at width 48 (heads x head_dim != width),
    per-head q/k norm, 4 of 16 routed experts held, blocks of 4."""
    return LlamaModel(LlamaConfig(**{**dict(
        vocab_size=128, n_positions=128, n_embd=48, n_layer=2, n_head=4,
        n_kv_head=2, head_dim=16, intermediate_size=24, qk_norm="head",
        n_experts=16, n_experts_per_tok=4, norm_topk_prob=True,
        experts_held=(8, 4), rope_theta=1e6, rms_norm_eps=1e-6,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        use_flash_attention=False, block_length=4, denoising_steps=2,
        remasking="low_confidence_static"), **over}))


@pytest.fixture(scope="module")
def held():
    model = tiny()
    params = model.init_params(jax.random.PRNGKey(2))
    # off-zero norms and a head that spreads the logits, so that choices are
    # not one token everywhere
    params["lm_head"] = params["lm_head"] * 40.0
    return model, params


def test_configuration_refuses_what_the_block_step_does_not_carry():
    for bad, match in ((dict(gqa_layers=(0,), kda_heads=2, kda_head_dim=8),
                        "KDA, window or latent"),
                       (dict(layer_types=("full_attention",
                                          "sliding_attention"),
                             sliding_window=8), "KDA, window or latent"),
                       (dict(denoising_steps=5), "denoised in"),
                       (dict(remasking="entropy"), "remasking"),
                       (dict(block_length=1), "at least 2"),
                       (dict(mask_token_id=128), "mask_token_id")):
        with pytest.raises(ValueError, match=match):
            tiny(**bad)
    c = tiny(denoising_steps=0, mask_token_id=None).config
    assert (c.denoising_steps, c.mask_token_id, c.passes_per_token) == (
        4, 127, 5)
    assert tiny().block_decoding == common.BlockDecoding(
        4, 2, "low_confidence_static", 0.9, 127)
    plain = LlamaModel(dataclasses.replace(tiny().config, block_length=0))
    assert plain.block_decoding is None and ie.step_tokens(plain) == 1
    assert plain.config.passes_per_token == 1
    assert tiny().config.generate_flops_per_token(100) == \
        3 * plain.config.generate_flops_per_token(100)
    with pytest.raises(NotImplementedError, match="noise schedule"):
        tiny().loss(None, jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="block_length"):
        plain.block_step(None, None, None, None)


@pytest.mark.parametrize("prompt", [12])
def test_block_steps_through_the_cache_are_the_full_pass(held, prompt):
    """``prefill`` of whole blocks, then block steps with some positions
    masked, against ``apply`` over the same tokens (the mask token read at
    the masked positions): the cache walk is the trunk's arithmetic; the
    cache after a commit holds the K/V of the final tokens."""
    model, params = held
    ids = np.random.default_rng(prompt).integers(0, 127, size=prompt + 8,
                                                 dtype=np.int32)
    masked = np.zeros(prompt + 8, bool)
    masked[prompt + 1:prompt + 4] = True
    with jax.default_matmul_precision("highest"):
        apply = jax.jit(lambda read: model.apply(params, read[None])[0])
        prefill = jax.jit(lambda ids: model.prefill(
            params, ids, model.init_cache(1, 64))[1])
        step = jax.jit(lambda t, m, c: model.block_step(params, t, m, c))
        read = np.where(masked, 127, ids)
        want = np.asarray(apply(read))
        cache = prefill(ids[None, :prompt])
        at = slice(prompt, prompt + 4)
        got, passed = step(ids[None, at], masked[None, at], cache)
        np.testing.assert_allclose(np.asarray(got[0]), want[at], atol=2e-5)
        assert int(passed["pos"]) == prompt
        assert passed["block_passes"].tolist() == [1, 0, 0, 0]
        none, kept = model.block_step(params, ids[None, at],
                                      np.zeros((1, 4), bool), passed,
                                      commit=True)
        assert none is None and int(kept["pos"]) == prompt + 4
        assert kept["block_passes"].tolist() == [1, 1, 0, 0]
        # (e) the committed rows are the final tokens': one prefill of all of
        # them writes the same cache
        whole = prefill(ids[None, :prompt + 4])
        for name in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(kept[name][:, :, :prompt + 4]),
                np.asarray(whole[name][:, :, :prompt + 4]), atol=2e-6)
        # the next block sees them
        nxt = slice(prompt + 4, prompt + 8)
        got, _ = step(ids[None, nxt], np.ones((1, 4), bool), kept)
        read = np.where(np.arange(prompt + 8) >= prompt + 4, 127, ids)
        want = np.asarray(apply(read))
        np.testing.assert_allclose(np.asarray(got[0]), want[nxt], atol=2e-5)
    assert int(kept["expert_tokens"].sum()) > int(
        cache["expert_tokens"].sum())


# (prompt, which of the NEW block's positions are masked): the engine's
# carrying pass (all masked), a block that opens with two given tokens, and a
# pending block that is the sequence's first (``pos`` 0)
@pytest.mark.parametrize("prompt,masked", [(12, (1, 1, 1, 1)),
                                           (8, (0, 0, 1, 1)),
                                           (0, (1, 1, 1, 1))])
def test_a_carrying_pass_is_the_commit_and_the_next_pass(held, prompt, masked):
    """``block_step(pending=)`` over [the finished block | the next] leaves
    the cache rows, ``pos`` and the next block's logits that the DEFINITION
    leaves: ``block_step(commit=True)`` over the finished block, then a plain
    pass over the next (float32, the einsum path: the same rows from the
    same inputs, layer by layer)."""
    model, params = held
    ids = np.random.default_rng(prompt + 1).integers(
        0, 127, size=(2, prompt + 8), dtype=np.int32)
    masked = np.broadcast_to(np.asarray(masked, bool), (2, 4))
    done, nxt = ids[:, prompt:prompt + 4], ids[:, prompt + 4:]
    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(2, 32)
        if prompt:
            _, cache = jax.jit(model.prefill)(params, ids[:, :prompt], cache)
        # the finished block's own denoising pass came before either
        _, cache = model.block_step(params, done, np.zeros((2, 4), bool),
                                    cache)
        _, kept = model.block_step(params, done, np.zeros((2, 4), bool),
                                   cache, commit=True)
        want, plain = model.block_step(params, nxt, masked, kept)
        got, carried = model.block_step(params, nxt, masked, cache,
                                        pending=done)
    assert got.shape == want.shape == (2, 4, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)
    assert int(carried["pos"]) == int(plain["pos"]) == prompt + 4
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(carried[name][:, :, :prompt + 8]),
            np.asarray(plain[name][:, :, :prompt + 8]), atol=1e-6, rtol=0)
    # the same rows met the routers; one forward pass where there were two
    np.testing.assert_array_equal(np.asarray(carried["expert_tokens"]),
                                  np.asarray(plain["expert_tokens"]))
    assert plain["block_passes"].tolist() == [2, 1, 0, 0]
    assert carried["block_passes"].tolist() == [2, 0, 1, 0]
    with pytest.raises(ValueError, match="commits its own block or carries"):
        model.block_step(params, nxt, masked, cache, commit=True,
                         pending=done)


def plain_generate(model, params, prompt, new, eos=None):
    """Block diffusion by FULL passes of ``apply``, no cache, in numpy: what
    the engine's block step has to reproduce (greedy)."""
    dec = model.block_decoding
    P = len(prompt)
    n_blocks = -(-(P + new) // dec.length)
    ids = np.zeros(n_blocks * dec.length, np.int32)
    ids[:P] = prompt
    masked = np.arange(len(ids)) >= P
    passes = 0
    counts = ie._transfer_counts(dec)
    with jax.default_matmul_precision("highest"):
        apply = jax.jit(lambda read: model.apply(params, read[None])[0])
    for b in range(P // dec.length, n_blocks):
        at = slice(b * dec.length, (b + 1) * dec.length)
        for s in range(dec.steps):
            if not masked[at].any():
                break
            passes += 1
            read = np.where(masked, dec.mask_token_id, ids)
            with jax.default_matmul_precision("highest"):
                logits = np.asarray(apply(read))[at]
            x0 = logits.argmax(-1)
            p = np.exp(logits - logits.max(-1, keepdims=True))
            conf = p[np.arange(dec.length), x0] / p.sum(-1)
            where = np.flatnonzero(masked[at])
            if dec.remasking == "sequential":
                picked = where[:counts[s]]
            else:
                picked = sorted(where, key=lambda i: (-conf[i], i))[:counts[s]]
                high = [i for i in where if conf[i] > dec.threshold]
                if dec.remasking == "low_confidence_dynamic" \
                        and len(high) >= counts[s]:
                    picked = high
            for i in picked:
                ids[at][i], masked[at][i] = x0[i], False
        lo = max(P, b * dec.length)
        new_here = ids[lo:(b + 1) * dec.length]
        if eos is not None and (new_here == eos).any():
            ids[lo + int(np.argmax(new_here == eos)):] = eos
            break
    return ids[P:P + new], passes


# each rule with prompts of two residues mod 4 (all four over the rules) and
# lengths that are no whole number of blocks
RULES = [("sequential", 0.9, 2, ((9, 7),)),
         ("low_confidence_static", 0.9, 2, ((10, 1), (11, 13))),
         ("low_confidence_static", 0.9, 3, ((3, 6),)),
         # thresholds 0 (every masked position passes: one pass a block) and
         # 2 (none does: the floor, as the static rule)
         ("low_confidence_dynamic", 0.0, 2, ((8, 9),)),
         ("low_confidence_dynamic", 2.0, 2, ((9, 8),)),
         ("low_confidence_dynamic", 0.02, 4, ((6, 10),))]


@pytest.mark.parametrize("rule,threshold,steps,shapes", RULES)
def test_generate_is_block_diffusion_by_full_passes(held, rule, threshold,
                                                    steps, shapes):
    """``generate()`` (one fused program), batch of 2."""
    _, params = held
    model = tiny(remasking=rule, confidence_threshold=threshold,
                 denoising_steps=steps)
    engine = deepspeed_tpu.init_inference(model, dtype="fp32", params=params,
                                          max_out_tokens=128)
    rng = np.random.default_rng(steps)
    for prompt, new in shapes:
        ids = rng.integers(0, 127, size=(2, prompt), dtype=np.int32)
        with jax.default_matmul_precision("highest"):
            out = np.asarray(engine.generate(ids, max_new_tokens=new))
        assert out.shape == (2, prompt + new)
        np.testing.assert_array_equal(out[:, :prompt], ids)
        for row in range(2):
            want, _ = plain_generate(model, params, ids[row], new)
            np.testing.assert_array_equal(out[row, prompt:], want)


def test_generate_split_and_sampled(held):
    """The observed path (two programs) emits the fused path's tokens; a
    sampled generation repeats under its seed and stays in the vocabulary;
    EOS inside a block ends the row there."""
    model, params = held
    engine = deepspeed_tpu.init_inference(model, dtype="fp32", params=params,
                                          max_out_tokens=128)
    ids = np.random.default_rng(0).integers(0, 127, size=(1, 10),
                                            dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        fused = np.asarray(engine.generate(ids, max_new_tokens=11))
        engine.profile_model_time()
        split = np.asarray(engine.generate(ids, max_new_tokens=11))
        engine._model_profile_enabled = False
        np.testing.assert_array_equal(fused, split)
        a = np.asarray(engine.generate(ids, max_new_tokens=9, do_sample=True,
                                       temperature=0.8, top_k=20, seed=3))
        b = np.asarray(engine.generate(ids, max_new_tokens=9, do_sample=True,
                                       temperature=0.8, top_k=20, seed=3))
        np.testing.assert_array_equal(a, b)
        assert a.max() < 128 and (a != fused[:, :19]).any()
        # an EOS the greedy generation meets in its second block
        eos = int(fused[0, 10 + 5])
        want, _ = plain_generate(model, params, ids[0], 11, eos=eos)
        got = np.asarray(engine.generate(ids, max_new_tokens=11,
                                         eos_token_id=eos))[0, 10:]
        np.testing.assert_array_equal(got, want)
        first = int(np.argmax(want == eos))
        assert first <= 5 and (want[first:] == eos).all()


def test_block_passes_counts_the_static_rules_trip():
    dec = common.BlockDecoding(4, 2, "low_confidence_static", 0.9, 0)
    assert ie._transfer_counts(dec) == (2, 2)
    assert [ie.block_passes(dec, g) for g in range(4)] == [2, 2, 1, 1]
    dec = common.BlockDecoding(8, 3, "sequential", 0.9, 0)
    assert ie._transfer_counts(dec) == (3, 3, 2)
    assert [ie.block_passes(dec, g) for g in range(8)] == [3, 3, 2, 2, 2, 1,
                                                           1, 1]


def test_the_autoregressive_programs_are_the_parents():
    """The one-token step comes out of this change the same program: a small
    dense model's serving pair traces to the jaxprs the functions as they
    stood before it trace to (``_decode_scan_step`` straight into the scan,
    ``_next_token`` after ``module.prefill``)."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    model = GPT2Model(GPT2Config(vocab_size=64, n_positions=64, n_embd=32,
                                 n_layer=2, n_head=2))
    params = model.init_params(jax.random.PRNGKey(0))
    sampling = ie._sampling(False, 1.0, 0, 1.0, None)
    prefill, chunk = ie.build_serving_programs(model, 64, 16, False, 1.0, 0,
                                               1.0, None)

    def parent_prefill(params, ids, rng):
        cache = model.init_cache(ids.shape[0], 64)
        cc = ie._resolve_cache_shardings(model, None)
        if cc is not None:
            cache = jax.lax.with_sharding_constraint(cache, cc)
        logits, cache = model.prefill(params, ids, cache)
        tok, done, rng = ie._next_token(
            logits, jnp.zeros((ids.shape[0],), jnp.bool_), rng, *sampling)
        return tok, cache, done, rng

    def parent_chunk(params, tok, cache, done, rng):
        (tok, cache, done, rng), toks = jax.lax.scan(
            ie._decode_scan_step(model, params, sampling),
            (tok, cache, done, rng), None, length=16)
        return tok, cache, done, rng, toks.T

    ids, key = jnp.zeros((1, 8), jnp.int32), jax.random.PRNGKey(0)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    with mesh:
        assert str(jax.make_jaxpr(prefill)(params, ids, key)) == \
            str(jax.make_jaxpr(parent_prefill)(params, ids, key))
        carry = jax.eval_shape(prefill, params, ids, key)
        assert str(jax.make_jaxpr(chunk)(params, *carry)) == \
            str(jax.make_jaxpr(parent_chunk)(params, *carry))
        # and compile to the same instructions
        text = lambda f, *a: jax.jit(f).lower(*a).compile().as_text()
        strip = lambda t: "\n".join(
            line.split(", metadata=")[0] for line in t.splitlines()
            if " = " in line)
        assert strip(text(chunk, params, *carry)) == \
            strip(text(parent_chunk, params, *carry))


# -------------------------------------------------------- the serving front-end
def test_the_front_end_counts_what_a_block_step_returns(held):
    """``ServingFrontEnd.submit``: the tokens ``generate()`` emits, the first
    callback the first block's new tokens, a request whose length is no
    whole number of blocks resolved with exactly that many; the request
    span's counts; the programs' own pass counter."""
    from deepspeed_tpu import serving, telemetry
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    model, params = held
    engine = deepspeed_tpu.init_inference(model, dtype="fp32", params=params,
                                          max_out_tokens=128)
    front = serving.from_ds_config(engine, DeepSpeedConfig({"serving": {
        "decode_tick_tokens": 8, "max_queue_depth": 4}}))
    try:
        rng = np.random.default_rng(1)
        for prompt, new in ((8, 20), (11, 6)):
            ids = rng.integers(0, 127, size=prompt, dtype=np.int32)
            calls = []
            with jax.default_matmul_precision("highest"):
                req = front.submit(ids, max_new_tokens=new,
                                   stream=calls.append)
                req.result(timeout=300.0)
                want = np.asarray(engine.generate(
                    ids[None], max_new_tokens=new))[0, prompt:]
            assert req.status == "completed", req.reason
            np.testing.assert_array_equal(req.tokens, want)
            first = min(4 - prompt % 4, new)
            assert len(calls[0]) == first
            assert [t for c in calls for t in c] == list(want)
            assert all(len(c) <= 8 for c in calls[1:])
            ticks = -(-(new - first) // 8) if new > first else 0
            assert req.decode_ticks == ticks
            blocks = 1 + 2 * ticks
            # 2 passes a block; the first block as many as its masks need.
            # No pass only commits: every block but the last rode in the
            # first pass of the next
            passes = 2 * (blocks - 1) + ie.block_passes(
                model.block_decoding, prompt % 4)
            assert req.block_passes == (passes, 0, blocks - 1, blocks)
            span = [s for s in telemetry.get_tracer().snapshot()
                    if s.name == "request" and s.args.get("request") == req.id]
            args = span[-1].args
            assert (args["blocks"], args["passes"], args["commits"],
                    args["carried"]) == (blocks, passes, 0, blocks - 1)
            assert (args["block_length"], args["denoising_steps"]) == (4, 2)
            assert args["cache_positions"] == prompt - prompt % 4 + 4 * blocks
            assert args["new_tokens"] == new
            # every pass and every carried block is 4 positions the routers
            # saw: (token, expert) pairs in 2 routed layers, 4 a token
            routed = [s for s in telemetry.get_tracer().snapshot()
                      if s.name == "moe/expert_tokens"
                      and s.args.get("request") == req.id]
            assert routed[-1].args["routed_pairs"] == 2 * 4 * (
                prompt - prompt % 4 + 4 * (passes + blocks - 1))
        assert front.counts["blocks"] == sum(
            1 + 2 * n for n in (2, 1))
        assert front.counts["carried"] == front.counts["blocks"] - 2
        assert front.counts["passes"] > front.counts["blocks"]
        # a tick that is no whole number of blocks is refused when the
        # programs are built, not served wrong
        bad = serving.from_ds_config(engine, DeepSpeedConfig({"serving": {
            "decode_tick_tokens": 6, "max_queue_depth": 4}}))
        try:
            req = bad.submit(np.zeros(4, np.int32), max_new_tokens=4)
            req.result(timeout=60.0)
            assert req.status == "failed" and "whole number" in req.reason
        finally:
            bad.close()
    finally:
        front.close()


def test_a_request_of_33_blocks_reads_66_0_32_33(held, tmp_path):
    """The cell's request in small (a prompt of whole blocks, 132 new
    tokens, ticks of 16): the first block in the prefill tick and 8 ticks of
    4; every block but the last rode in the next block's first pass, no pass
    only committed; the span, the request and the registry's counters say
    so, and the tokens are the definition's."""
    from deepspeed_tpu import serving, telemetry
    from deepspeed_tpu.runtime.config import DeepSpeedConfig, TelemetryConfig

    _, params = held
    model = tiny(n_positions=160)
    tel = telemetry.configure(TelemetryConfig(
        enabled=True, output_dir=str(tmp_path / "t"), flush_interval=1000,
        prometheus=False))
    engine = deepspeed_tpu.init_inference(model, dtype="fp32", params=params,
                                          max_out_tokens=160)
    front = serving.from_ds_config(engine, DeepSpeedConfig({"serving": {
        "decode_tick_tokens": 16, "max_queue_depth": 4}}))
    try:
        ids = np.random.default_rng(7).integers(0, 127, size=12,
                                                dtype=np.int32)
        with jax.default_matmul_precision("highest"):
            req = front.submit(ids, max_new_tokens=132)
            req.result(timeout=300.0)
        assert req.status == "completed", req.reason
        want, passes = plain_generate(model, params, ids, 132)
        np.testing.assert_array_equal(req.tokens, want)
        assert passes == 66 and req.decode_ticks == 8
        assert req.block_passes == (66, 0, 32, 33)
        args = [s for s in telemetry.get_tracer().snapshot()
                if s.name == "request" and s.args.get("request") == req.id
                ][-1].args
        assert [args[n] for n in common.BLOCK_COUNTS] == [66, 0, 32, 33]
        assert args["cache_positions"] == 12 + 4 * 33
        assert (front.counts["passes"], front.counts["carried"],
                front.counts["blocks"]) == (66, 32, 33)
        assert {name: tel.registry.counter(f"serving/{name}").value
                for name in ("passes", "carried", "blocks")} == {
                    "passes": 66, "carried": 32, "blocks": 33}
    finally:
        front.close()
        telemetry.deconfigure()
