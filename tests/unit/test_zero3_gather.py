"""ZeRO-3's gather-on-use of the layer stack (runtime/zero/partition.py
``LayerGathers``, seated in models/common.py ``remat_wrap``): where a block
uses a leaf that ZeRO sharded, the leaf is constrained to its spec without
the DP axes and its cotangent back to the sharded spec. Placement must not
change the mathematics; one chip, stage 0-2 and the serial schedule's
compute program must not meet the rule at all."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models import common
from deepspeed_tpu.parallel.topology import ALL_AXES
from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
from deepspeed_tpu.runtime.zero.partition import (GATHERED_NAME, drop_dp_axes,
                                                  plan_sharding,
                                                  stacked_param_keys)

VOCAB, ROWS, SEQ = 128, 8, 16


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


def _gathered(jaxpr):
    """The leaves named as gathered in a jaxpr and every jaxpr inside it."""
    return [e for e in _primitives(jaxpr).get("name", [])
            if e.params["name"] == GATHERED_NAME]


def _gpt2(**kw):
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    return GPT2Model(GPT2Config(**{**dict(
        vocab_size=VOCAB, n_positions=32, n_embd=32, n_layer=2, n_head=4,
        dtype=jnp.float32, remat="attn", use_flash_attention=False), **kw}))


def _gpt2_moe(**kw):
    from deepspeed_tpu.models.gpt2 import GPT2Config
    from deepspeed_tpu.models.gpt2_moe import MoEGPT2

    return MoEGPT2(GPT2Config(
        vocab_size=VOCAB, n_positions=32, n_embd=32, n_layer=2, n_head=4,
        dtype=jnp.float32, remat="attn", use_flash_attention=False, **kw),
        num_experts=4)


def _llama(**kw):
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

    return LlamaModel(LlamaConfig(**{**dict(
        vocab_size=VOCAB, n_positions=32, n_embd=32, n_layer=2, n_head=4,
        intermediate_size=64, dtype=jnp.float32, remat="attn",
        use_flash_attention=False), **kw}))


def _bert(**kw):
    from deepspeed_tpu.models.bert import BertConfig, BertModel

    return BertModel(BertConfig(
        vocab_size=VOCAB, n_positions=32, n_embd=32, n_layer=2, n_head=4,
        dtype=jnp.float32, remat="attn", **kw))


def _batch(model):
    if type(model).__name__ == "BertModel":
        from deepspeed_tpu.models.bert import synthetic_mlm_batch

        return synthetic_mlm_batch(ROWS, SEQ, VOCAB, mask_frac=0.4, seed=SEQ)
    rng = np.random.default_rng(SEQ)
    return {"input_ids": rng.integers(0, VOCAB, (ROWS, SEQ), dtype=np.int32)}


# (builder, its keywords, mesh, leaves a block must see gathered, leaves it
# must not): every family that walks its layers through ``remat_wrap``. The
# threshold (1,000 elements A LAYER) keeps norms and biases whole, as the
# default does at published widths.
CASES = {
    "gpt2-data4": (_gpt2, {}, {"data": 4},
                   {"qkv_w", "proj_w", "fc_w", "fc2_w"}, {"qkv_b", "ln1_g"}),
    # no remat: the block is checkpointed for the gathered leaves alone
    "gpt2-data4-noremat": (_gpt2, {"remat": False}, {"data": 4},
                           {"qkv_w", "proj_w", "fc_w", "fc2_w"}, {"fc_b"}),
    "gpt2-full-remat-mics": (_gpt2, {"remat": True}, {"data": 2, "mics": 2},
                             {"qkv_w", "proj_w", "fc_w", "fc2_w"}, set()),
    # the gathered spec keeps the tensor axis
    "gpt2-tensor2-data2": (_gpt2, {}, {"tensor": 2, "data": 2},
                           {"qkv_w", "proj_w", "fc_w", "fc2_w"}, {"fc_b"}),
    "llama-dense-data4": (_llama, {}, {"data": 4},
                          {"q_w", "k_w", "v_w", "o_w", "gate_w", "up_w",
                           "down_w"}, {"attn_norm_g"}),
    # llama's routed experts carry no expert axis of their own (replicated
    # by the model, so ZeRO shards them and the block gathers them)
    "llama-routed-data2-expert2": (
        _llama, {"n_experts": 4, "n_experts_per_tok": 2},
        {"data": 2, "expert": 2},
        {"q_w", "o_w", "expert_gate_w", "expert_up_w", "expert_down_w"},
        {"router_w"}),
    # a block handed a PAIR of layers (leading axis 2); the MoE bank is
    # sharded over 'expert' by the model and is no layer-stacked leaf
    "gpt2_moe-data2-expert2": (_gpt2_moe, {}, {"data": 2, "expert": 2},
                               {"qkv_w", "proj_w", "fc_w", "fc2_w"},
                               {"wi", "wo"}),
    "bert-data4": (_bert, {}, {"data": 4}, None, set()),
    # the stacks PR 31-41 added. A layer pattern: what every layer has in
    # ``blocks``, each mixer's leaves in a stack of its own
    "llama-kda-pattern-data4": (
        _llama, {"gqa_layers": (1,), "kda_heads": 2,
                 "kda_head_dim": 16, "use_rope": False, "attn_gate": True},
        {"data": 4},
        {"q_w", "attn_gate_w", "kda_qkv_w", "gate_w", "down_w"},
        {"attn_norm_g", "kda_conv_w"}),
    # window and full softmax layers behind a leading dense layer
    "llama-window-dense-lead-data4": (
        _llama, {"n_layer": 3, "n_experts": 4, "n_experts_per_tok": 2,
                 "n_dense_layers": 1, "dense_intermediate_size": 96,
                 "layer_types": ("sliding_attention", "full_attention",
                                 "sliding_attention"),
                 "sliding_window": 4, "global_rope": False,
                 "qk_norm": "head"},
        {"data": 4},
        {"q_w", "o_w", "gate_w", "expert_gate_w", "expert_down_w"},
        {"q_norm_g"}),
    # latent attention whose queries come straight from q_w
    "llama-latent-direct-q-data4": (
        _llama, {"kv_lora_rank": 32, "qk_nope_head_dim": 8,
                 "qk_rope_head_dim": 8, "v_head_dim": 8},
        {"data": 4}, {"q_w", "kv_a_w", "kv_b_k_w", "kv_b_v_w", "o_w"},
        {"kv_a_norm_g"}),
    "llama-shared-expert-data2-expert2": (
        _llama, {"n_experts": 4, "n_experts_per_tok": 2,
                 "n_shared_experts": 1, "router_scoring": "sigmoid",
                 "norm_topk_prob": True},
        {"data": 2, "expert": 2},
        {"shared_gate_w", "shared_up_w", "shared_down_w", "expert_up_w"},
        {"router_w"}),
    # a chip's share of the experts: the held leaves are (count, ...) wide
    "llama-experts-held-data4": (
        _llama, {"n_experts": 8, "n_experts_per_tok": 2,
                 "experts_held": (2, 4), "router_scoring": "sigmoid"},
        {"data": 4},
        {"expert_gate_w", "expert_up_w", "expert_down_w", "q_w"},
        {"router_w"}),
}


def _plan(model, params, mesh, stage=3):
    return plan_sharding(
        jax.eval_shape(lambda: params), mesh,
        zero_config=DeepSpeedZeroConfig(
            stage=stage, stage3_param_persistence_threshold=1000),
        tp_specs=model.param_partition_specs(),
        stacked_keys=stacked_param_keys(model))


@pytest.mark.parametrize("case", CASES)
def test_loss_and_gradients_under_the_rule_equal_one_device(case):
    build, kw, dims, gathered, whole = CASES[case]
    model = build(**kw)
    params = model.init_params(jax.random.PRNGKey(1))
    batch = _batch(model)
    step = jax.value_and_grad(lambda p, b: model.loss(p, b))

    assert not _gathered(jax.make_jaxpr(step)(params, batch).jaxpr)
    want_loss, want = jax.jit(step)(params, batch)

    mesh = _mesh(**dims)
    plan = _plan(model, params, mesh)
    rule = plan.layer_gathers
    if gathered is not None:
        assert gathered <= set(rule.leaves), sorted(rule.leaves)
    assert not whole & set(rule.leaves), sorted(rule.leaves)
    for name, forms in rule.leaves.items():
        for _, spec, sharded in forms:
            axes = {a for e in spec if e for a in ([e] if isinstance(e, str) else e)}
            assert not axes & set(plan.dp_axes), (name, spec)
            assert spec != sharded
    rows = jax.tree.map(lambda _: NamedSharding(mesh, plan.batch_spec), batch)
    with mesh, common.layer_leaves_hook(rule):
        n = len(_gathered(jax.make_jaxpr(model.loss)(params, batch).jaxpr))
        got_loss, got = jax.jit(
            step, in_shardings=(plan.param_shardings(), rows),
            out_shardings=(NamedSharding(mesh, P()), plan.grad_shardings()))(
                params, batch)
    # the forward names each sharded leaf of the block once a block traced
    # (a model of several stacks traces a block a kind of layer, each with
    # the leaves every layer has and its own)
    assert n >= len(rule.leaves), n
    assert len(stacked_param_keys(model)) > 1 or n % len(rule.leaves) == 0, n

    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-4, atol=2e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(want)
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


def test_a_leaf_keeps_the_axis_its_model_gave_it():
    """An expert axis is a DP axis: ZeRO leaves a leaf the model sharded
    over it alone (``_shard_over_dp``), and so does the gather."""
    assert drop_dp_axes(P(None, ("tensor", "data")), 2, ("data",)) \
        == P(None, "tensor")
    assert drop_dp_axes(P("expert", None, ("data", "expert")), 3,
                        ("data", "expert"), own=P("expert")) \
        == P("expert", None, None)
    mesh = _mesh(data=2, expert=2)
    shapes = {"blocks": {
        "w": jax.ShapeDtypeStruct((2, 64, 64), jnp.float32),
        "experts": jax.ShapeDtypeStruct((2, 4, 64, 64), jnp.float32)}}
    plan = plan_sharding(
        shapes, mesh, zero_config=DeepSpeedZeroConfig(
            stage=3, stage3_param_persistence_threshold=0),
        tp_specs={"blocks": {"w": P(), "experts": P(None, "expert")}})
    assert plan.param_specs["blocks"]["experts"] == P(None, "expert", None, None)
    assert set(plan.layer_gathers.leaves) == {"w"}
    (shape, gathered, sharded), = plan.layer_gathers.leaves["w"]
    assert (shape, gathered, sharded) == (
        (64, 64), P(None, None), P(None, ("data", "expert")))


def test_a_region_that_is_already_manual_is_left_alone():
    mesh = _mesh(data=4)
    model = _gpt2()
    params = model.init_params(jax.random.PRNGKey(1))
    rule = _plan(model, params, mesh).layer_gathers
    blk = jax.tree.map(lambda a: a[0], params["blocks"])

    def inside(x):
        return common.remat_wrap(lambda x, b: x @ b["qkv_w"], "attn")(x, blk)

    with mesh, common.layer_leaves_hook(rule):
        auto = jax.make_jaxpr(inside)(jnp.ones((8, 32))).jaxpr
        manual = jax.make_jaxpr(jax.shard_map(
            inside, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False))(jnp.ones((8, 32))).jaxpr
    assert len(_gathered(auto)) == len(rule.leaves)
    assert not _gathered(manual)


# ------------------------------------------------------------- the engine
def _engine(stage=3, model=None, **over):
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=256, n_positions=32, n_embd=32, n_layer=2,
                     n_head=2, remat="attn", use_flash_attention=False)
    engine, *_ = deepspeed_tpu.initialize(model=model or GPT2Model(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage,
                              "stage3_param_persistence_threshold": 1000},
        "steps_per_print": 0, **over})
    return engine


def _step_trace(engine):
    """(the step's jaxpr, the collectives the engine stated while tracing)"""
    from deepspeed_tpu.analysis.collectives import record_collectives
    from deepspeed_tpu.models.gpt2 import synthetic_lm_batch

    abstract = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    batch = engine._shard_batch(synthetic_lm_batch(8, 32, 256))
    with engine.mesh, record_collectives(apply_chaos=False) as rec:
        jaxpr = jax.make_jaxpr(engine._build_train_batch_fn(1))(
            abstract(engine.state), abstract(batch)).jaxpr
    return jaxpr, [r for r in rec.records if r.op.startswith("zero3_gather")]


def test_the_default_step_states_one_gather_a_sharded_leaf():
    engine = _engine()
    jaxpr, records = _step_trace(engine)
    assert engine._layer_gathers is engine.plan.layer_gathers
    # the four weights of a block, at ONE layer's shape, over the DP axes;
    # the biases and norms (under 1,000 elements a layer) stay whole
    assert sorted(r.shape for r in records) == [
        (32, 32), (32, 96), (32, 128), (128, 32)]
    assert {r.axes for r in records} == {("data",)}
    assert all(r.site.startswith("partition.py") for r in records)
    assert _gathered(jaxpr)
    specs = engine.plan.param_specs["blocks"]
    assert "data" not in str(specs["qkv_b"]) + str(specs["ln1_g"])
    assert "data" in str(engine.plan.master_specs["blocks"]["qkv_b"])
    assert "data" in str(engine.plan.grad_specs["blocks"]["qkv_b"])


@pytest.mark.parametrize("stage", [0, 1, 2])
def test_stages_under_3_trace_no_gather(stage):
    engine = _engine(stage=stage)
    jaxpr, records = _step_trace(engine)
    assert engine._layer_gathers is None
    assert not records and not _gathered(jaxpr)
    assert "custom_vjp_call" not in _primitives(jaxpr)


@pytest.mark.parametrize("overlap", [{}, {"schedule": "serial"}])
def test_with_the_overlap_block_no_leaf_is_gathered_twice(overlap):
    """There is one place a layer's sharded weight is gathered. With the
    block and the fused step it is the rule's, as without the block; the
    serial schedule's phase has gathered the whole tree before its compute
    program runs, so there the rule is off and the trace states no gather."""
    engine = _engine(overlap=dict(overlap, scheduler_flags=False,
                                  async_checkpoint=False))
    jaxpr, records = _step_trace(engine)
    if overlap:
        assert engine._layer_gathers is None
        assert not records and not _gathered(jaxpr)
        return
    assert engine._layer_gathers is engine.plan.layer_gathers
    assert sorted(r.shape for r in records) == [
        (32, 32), (32, 96), (32, 128), (128, 32)]
    assert all(r.site.startswith("partition.py") for r in records)


def _routed_llama():
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

    return LlamaModel(LlamaConfig(
        vocab_size=256, n_positions=32, n_embd=32, n_layer=3, n_head=2,
        intermediate_size=64, n_experts=4, n_experts_per_tok=2,
        n_dense_layers=1, remat="attn", use_flash_attention=False))


@pytest.mark.parametrize("gas", [1, 4])
@pytest.mark.parametrize("family", ["gpt2", "llama-routed"])
def test_promise_vs_actual_sharding_of_the_default_step(family, gas):
    """8-way promise-vs-actual of the step every stage-3 job runs: each
    materialized leaf sits at the plan's placement (params, fp32 master,
    optimizer moments) and is still there after a step that gathers the
    layers' weights on use, with and without an accumulation scan, over
    one stack (gpt2) and two (``dense_blocks`` ahead of ``blocks``)."""
    from deepspeed_tpu.models.gpt2 import synthetic_lm_batch

    model = _routed_llama() if family == "llama-routed" else None
    engine = _engine(bf16={"enabled": True}, train_batch_size=8 * gas,
                     gradient_accumulation_steps=gas,
                     **({"model": model} if model else {}))
    assert engine._layer_gathers is engine.plan.layer_gathers is not None
    plan = engine.plan
    assert plan.dp_axes == ("data",)

    def check(tree, specs):
        leaves = jax.tree.leaves(tree)
        spec_leaves = jax.tree.leaves(specs,
                                      is_leaf=lambda x: isinstance(x, P))
        assert len(leaves) == len(spec_leaves)
        for leaf, spec in zip(leaves, spec_leaves):
            assert leaf.sharding.spec == spec, \
                f"promised {spec}, actual {leaf.sharding.spec}"

    for stepped in (False, True):   # as materialized, as a step hands it back
        if stepped:
            assert np.isfinite(float(engine.train_batch(
                synthetic_lm_batch(8 * gas, 32, 256))))
        check(engine.state.params, plan.param_specs)
        check(engine.state.master, plan.master_specs)
        moments = [x for x in engine.state.opt_state if isinstance(x, dict)]
        assert len(moments) == 2                        # AdamW's mu and nu
        for tree in moments:
            check(tree, plan.master_specs)
    # the promise is real: the stacks' weights are dp-sharded as params
    sharded = [l for l in jax.tree.leaves(engine.state.params)
               if "data" in str(l.sharding.spec)]
    assert len(sharded) >= len(plan.layer_gathers.leaves)


def test_one_chip_traces_the_parents_step():
    """No DP axis over more than one device: no rule, and ``remat_wrap``
    hands the block to ``jax.checkpoint`` as it is."""
    mesh = _mesh()
    model = _gpt2()
    params = model.init_params(jax.random.PRNGKey(1))
    assert _plan(model, params, mesh).layer_gathers is None
    block = lambda x, blk: x
    assert common._LAYER_LEAVES_HOOK is None
    wrapped = common.remat_wrap(block, "attn")
    assert wrapped.__wrapped__ is block
