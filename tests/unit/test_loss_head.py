"""The chunked loss head (models/common.py ``chunked_lm_loss``) follows the
mesh it is traced under: one scan over all the rows on one device, and on a
mesh whose devices are all on the batch axes a scan per chip over the chip's
own rows, with the head gathered once ahead of it and its gradient reduced
once after it. Placement must not change the mathematics."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.models import common
from deepspeed_tpu.parallel.topology import ALL_AXES
from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
from deepspeed_tpu.runtime.zero.partition import plan_sharding

VOCAB, ROWS = 127, 8        # 127: like 50257, no multiple of the four chips


def _mesh(**dims):
    shape = [dims.get(a, 1) for a in ALL_AXES]
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), ALL_AXES)


def _primitives(jaxpr, found=None):
    """{primitive name: [eqn, ...]} of a jaxpr and every jaxpr inside it."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        found.setdefault(eqn.primitive.name, []).append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


def _gpt2(**kw):
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    return GPT2Model(GPT2Config(
        vocab_size=VOCAB, n_positions=32, n_embd=32, n_layer=2, n_head=4,
        dtype=jnp.float32, remat=False, **kw))


def _gpt2_moe(**kw):
    from deepspeed_tpu.models.gpt2 import GPT2Config
    from deepspeed_tpu.models.gpt2_moe import MoEGPT2

    return MoEGPT2(GPT2Config(
        vocab_size=VOCAB, n_positions=32, n_embd=32, n_layer=2, n_head=4,
        dtype=jnp.float32, remat=False, **kw), num_experts=4)


def _llama(**kw):
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

    return LlamaModel(LlamaConfig(
        vocab_size=VOCAB, n_positions=32, n_embd=32, n_layer=2, n_head=4,
        intermediate_size=64, dtype=jnp.float32, remat=False, **kw))


def _bert(**kw):
    from deepspeed_tpu.models.bert import BertConfig, BertModel

    return BertModel(BertConfig(
        vocab_size=VOCAB, n_positions=32, n_embd=32, n_layer=2, n_head=4,
        dtype=jnp.float32, **kw))


def _batch(model, seq, masked):
    rng = np.random.default_rng(seq)
    if type(model).__name__ == "BertModel":
        from deepspeed_tpu.models.bert import synthetic_mlm_batch

        return synthetic_mlm_batch(ROWS, seq, VOCAB, mask_frac=0.4, seed=seq)
    batch = {"input_ids": rng.integers(0, VOCAB, (ROWS, seq), dtype=np.int32)}
    if masked:
        batch["loss_mask"] = (rng.random((ROWS, seq)) < 0.7).astype(np.float32)
    return batch


# (builder, its keywords, sequence length, loss_mask): every caller's family,
# the head tied and untied, with and without bias and mask, the chunk
# rematerialized or not; 14 - 1 = 13 is prime, so its chunks are of ONE position
CASES = {
    "gpt2-tied": (_gpt2, {}, 17, False),
    "gpt2-tied-mask-noremat": (_gpt2, {"remat_loss_chunks": False}, 17, True),
    "gpt2-untied-bias-mask": (
        _gpt2, {"tie_embeddings": False, "lm_head_bias": True}, 17, True),
    "gpt2-prime": (_gpt2, {}, 14, False),
    "gpt2-prime-mask-noremat": (_gpt2, {"remat_loss_chunks": False}, 14, True),
    "gpt2_moe": (_gpt2_moe, {}, 17, False),
    "gpt2_moe-mask-noremat": (_gpt2_moe, {"remat_loss_chunks": False}, 17, True),
    "llama-untied": (_llama, {}, 17, False),
    "llama-tied-mask-noremat": (
        _llama, {"tie_embeddings": True, "remat_loss_chunks": False}, 17, True),
    "llama-prime": (_llama, {}, 14, True),
    "bert-bias-mask": (_bert, {}, 16, True),
    "bert-noremat": (_bert, {"remat_loss_chunks": False}, 16, True),
    "bert-prime": (_bert, {}, 13, True),
}


@pytest.mark.parametrize("case", CASES)
def test_loss_and_gradients_under_data_4_equal_one_device(case, monkeypatch):
    build, kw, seq, masked = CASES[case]
    # three positions of the eight rows a chunk on one device, of a chip's
    # two rows on the mesh: several chunks either way, of different lengths
    monkeypatch.setattr(common, "_CHUNK_ELEMS", 3 * ROWS * VOCAB)
    model = build(**kw)
    params = model.init_params(jax.random.PRNGKey(1))
    if "lm_head_b" in params:       # drawn as zeros: make the bias count
        params["lm_head_b"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(2), params["lm_head_b"].shape)
    batch = _batch(model, seq, masked)
    step = jax.value_and_grad(lambda p, b: model.loss(p, b))

    assert "shard_map" not in _primitives(jax.make_jaxpr(step)(params, batch).jaxpr)
    want_loss, want = jax.jit(step)(params, batch)

    mesh = _mesh(data=4)
    plan = plan_sharding(
        jax.eval_shape(lambda: params), mesh,
        zero_config=DeepSpeedZeroConfig(stage=3, stage3_param_persistence_threshold=0),
        tp_specs=model.param_partition_specs())
    rows = jax.tree.map(lambda _: NamedSharding(mesh, P("data")), batch)
    with mesh:
        assert "shard_map" in _primitives(jax.make_jaxpr(step)(params, batch).jaxpr)
        got_loss, got = jax.jit(
            step, in_shardings=(plan.param_shardings(), rows),
            out_shardings=(NamedSharding(mesh, P()), plan.grad_shardings()))(
                params, batch)

    # the tolerance of tests/unit/test_engine.py's ZeRO stage comparisons
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-4, atol=2e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(want)
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


def _loss_scans(preset, rows, mesh):
    """(lengths of the loss head's scans, has the trace a shard_map) of
    ``GPT2Model.loss`` + gradient at a preset's published widths, traced
    abstractly: the trunk's scan is the one as long as the layers."""
    from deepspeed_tpu.models.gpt2 import PRESETS, GPT2Model

    cfg = dataclasses.replace(PRESETS[preset], remat="attn")
    model = GPT2Model(cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((rows, 1024), jnp.int32)
    with mesh or contextlib.nullcontext():
        prims = _primitives(jax.make_jaxpr(jax.value_and_grad(
            lambda p, b: model.loss(p, {"input_ids": b})))(params, ids).jaxpr)
    lengths = {e.params["length"] for e in prims.get("scan", [])}
    return lengths - {cfg.n_layer}, "shard_map" in prims


@pytest.mark.parametrize("preset,rows,dims,chunks,per_chip", [
    # one chip (gpt2-760m.train.z1 and .z1.gas4): today's program, 11 chunks
    # of 93 positions of 8 (6) rows
    ("gpt2-760m", 8, None, 11, False),
    ("gpt2-760m", 6, None, 11, False),
    ("gpt2-760m", 8, {}, 11, False),
    # gpt2-xl.train.z3x4: 31 chunks of 33 positions of a chip's OWN 16 rows
    # (of all 64 rows it was 93 chunks of 11)
    ("gpt2-xl", 64, {"data": 4}, 31, True),
    ("gpt2-xl", 64, {"data": 2, "mics": 2}, 31, True),
    # a mesh that also shards the vocabulary, the sequence or the layers, and
    # rows the batch axes do not divide, stay with the partitioner
    ("gpt2-xl", 64, {"data": 2, "tensor": 2}, 93, False),
    ("gpt2-xl", 64, {"data": 2, "seq": 2}, 93, False),
    ("gpt2-xl", 64, {"data": 2, "pipe": 2}, 93, False),
    ("gpt2-xl", 6, {"data": 4}, 11, False),
])
def test_chunks_and_path_follow_the_mesh(preset, rows, dims, chunks, per_chip):
    mesh = None if dims is None else _mesh(**dims)
    lengths, has_shard_map = _loss_scans(preset, rows, mesh)
    assert lengths == {chunks}
    assert has_shard_map == per_chip


def test_a_region_that_is_already_manual_keeps_the_one_scan():
    mesh = _mesh(data=4)
    x = jnp.ones((ROWS, 6, 8))
    head = jnp.ones((8, VOCAB))
    targets = jnp.zeros((ROWS, 6), jnp.int32)

    def inside(x, targets):
        return common.chunked_lm_loss(x, head, targets)[None]

    with mesh:
        jaxpr = jax.make_jaxpr(jax.shard_map(
            inside, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=P("data"), check_vma=False))(x, targets).jaxpr
    assert len(_primitives(jaxpr)["shard_map"]) == 1


def test_a_head_that_is_replicated_anyway_is_not_gathered():
    """ZeRO 0-2 keep the parameters whole on every chip: the per-chip scan
    must not cut the head up to gather it again (a first form of it did,
    and the partitioner then resharded the embedding's gradient too)."""
    model = _gpt2()
    params = model.init_params(jax.random.PRNGKey(1))
    batch = _batch(model, 17, False)
    mesh = _mesh(data=4)
    whole = NamedSharding(mesh, P())
    with mesh:
        text = jax.jit(
            jax.value_and_grad(lambda p, b: model.loss(p, b)),
            in_shardings=(jax.tree.map(lambda _: whole, params),
                          {"input_ids": NamedSharding(mesh, P("data"))}),
            out_shardings=whole).lower(params, batch).compile().as_text()
    assert "shard_map" in text          # the per-chip path, by its op_name
    for op in ("all-gather", "all-to-all", "collective-permute"):
        assert f" {op}(" not in text and f" {op}-start(" not in text, op


@pytest.mark.parametrize("length,budget,want", [
    (1023, 166, (93, 1023)),        # gpt2-760m.train.z1: a divisor, as ever
    (1023, 83, (33, 1023)),         # gpt2-xl.train.z3x4
    (8191, 1340, (1024, 8192)),     # 8,191 is a prime: one position of padding
    (8191, 2681, (2048, 8192)),
    (97, 16, (8, 104)),
    (127, 127, (127, 127)),
    (13, 3, (1, 13)),               # a budget of a few positions is a test's
])
def test_a_length_without_a_divisor_near_the_budget_is_padded(
        length, budget, want):
    assert common._chunk_len(length, budget) == want


def test_padded_chunks_change_neither_the_loss_nor_its_gradients(monkeypatch):
    """97 positions at a budget of 16: thirteen chunks of 8 over 104, the
    seven rows of padding cut off again. Against one chunk of all 97."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 97, 16))
    head = jax.random.normal(jax.random.fold_in(key, 1), (16, VOCAB))
    targets = jax.random.randint(jax.random.fold_in(key, 2), (2, 97), 0, VOCAB)
    mask = (jax.random.uniform(jax.random.fold_in(key, 3), (2, 97)) < 0.7)
    step = jax.value_and_grad(
        lambda x, head: common.chunked_lm_loss(x, head, targets, mask),
        argnums=(0, 1))
    want_loss, want = step(x, head)
    monkeypatch.setattr(common, "_CHUNK_ELEMS", 16 * 2 * VOCAB)
    jaxpr = jax.make_jaxpr(step)(x, head).jaxpr
    assert {eqn.params["length"] for eqn in _primitives(jaxpr)["scan"]} == {13}
    got_loss, got = step(x, head)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
