"""What Trinity-Mini (afmoe) asked of the program, at a small size on the
CPU: the layer pattern of window and full softmax layers with its stacks,
the selection bias's rule through the engine (the optimizer never touches
it), the step's routing counts as the ``moe/expert_tokens`` instant without a
host sync of the step's own, and the compiler's own kernel names resolved to
the program's scopes. The model against its float32 reference is
``tests/benchmark/test_afmoe_family.py``; the windowed kernels
``tests/unit/test_flash_attention.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import telemetry
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
from deepspeed_tpu.moe.dropless import balance_bias

TYPES = ("sliding_attention", "sliding_attention", "full_attention",
         "sliding_attention", "sliding_attention")


def _release(*engines):
    """Free an engine's device buffers now: a later test file in the same
    worker counts every live array (``test_profiling.py``'s census)."""
    import gc

    for engine in engines:
        for leaf in jax.tree.leaves(engine.state):
            leaf.delete()
        engine.invalidate_compiled()
    gc.collect()


def config(**over):
    return LlamaConfig(**{**dict(
        vocab_size=256, n_positions=64, n_embd=64, n_layer=5, n_head=4,
        n_kv_head=2, head_dim=16, intermediate_size=32,
        dense_intermediate_size=96, n_dense_layers=1, n_experts=16,
        n_experts_per_tok=4, norm_topk_prob=True, router_scoring="sigmoid",
        routed_scaling_factor=2.826, n_shared_experts=1, sandwich_norm=True,
        attn_gate=True, qk_norm="head", embed_scale=8.0, layer_types=TYPES,
        sliding_window=8, global_rope=False, router_bias=True,
        router_bias_rate=0.001, experts_held=(4, 8), remat="attn"), **over})


# ------------------------------------------------------------ the pattern
@pytest.mark.parametrize("types,dense,patterns", [
    (TYPES, 1, [("win",), ("win", "attn", "win", "win")]),
    (TYPES[1:], 0, [("win", "attn", "win", "win")]),
    (("full_attention",) * 4, 2, [("attn",), ("attn",)]),
    (("sliding_attention", "full_attention") * 3, 2,
     [("win", "attn"), ("win", "attn")]),
    (("sliding_attention", "full_attention") * 3, 1,
     [("win",), ("attn", "win", "attn", "win", "attn")])],
    ids=lambda x: str(x)[:40])
def test_each_stack_walks_its_own_phase_of_the_pattern(types, dense, patterns):
    c = config(layer_types=types, n_layer=len(types), n_dense_layers=dense)
    model = LlamaModel(c)
    stacks = jax.eval_shape(
        lambda key: [s[0] for s in model._stacks(model.init_params(key))],
        jax.random.PRNGKey(0))
    stacks = [(xs, *s[1:]) for xs, s in zip(stacks, model._stacks(
        jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), jax.eval_shape(
            model.init_params, jax.random.PRNGKey(0)))))]
    assert [s[4] for s in stacks] == patterns
    assert [s[2] for s in stacks] == ([0, dense] if dense else [0])
    assert c.pattern == patterns[-1] and c.n_attn_layers == len(types)
    for xs, _, _, _, pattern in stacks:
        lead = {a.shape[1] for a in jax.tree.leaves(xs)} if len(pattern) > 1 \
            else set()
        assert lead <= {len(pattern)}


@pytest.mark.parametrize("over,match", [
    ({"layer_types": TYPES[:3]}, "layer_types"),
    ({"sliding_window": 0}, "layer_types"),
    ({"layer_types": ("local",) * 5}, "layer_types"),
    ({"sequence_parallel": "ring"}, "not built"),
    ({"qk_norm": "rows"}, "qk_norm"),
    ({"n_experts": 0, "n_experts_per_tok": 0, "n_dense_layers": 0,
      "experts_held": None}, "router_bias")], ids=lambda x: str(x)[:30])
def test_a_configuration_the_trunk_cannot_run_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        config(**over)


def test_a_model_without_the_new_keys_holds_the_leaves_it_always_held():
    plain = LlamaModel(LlamaConfig(vocab_size=64, n_embd=32, n_layer=2,
                                   n_head=2, qk_norm=True, n_experts=4,
                                   n_experts_per_tok=2, intermediate_size=16))
    params = jax.eval_shape(plain.init_params, jax.random.PRNGKey(0))
    assert "router_bias" not in params["blocks"]
    assert params["blocks"]["q_norm_g"].shape == (2, 32)     # whole projection
    assert plain.ruled_leaves(params) is None
    assert plain.config.kinds == ("attn", "attn")
    loss, aux = plain.loss_and_aux(
        plain.init_params(jax.random.PRNGKey(0)),
        {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    assert aux is None and loss.shape == ()


# ------------------------------------------------------ the balancing rule
def test_the_rule_moves_the_bias_by_the_stated_amounts():
    bias = jnp.asarray([[0.0, 0.1, -0.2, 0.05], [1.0, 1.0, 1.0, 1.0]])
    pairs = jnp.asarray([[10, 2, 6, 6], [3, 3, 3, 3]])
    got = np.asarray(balance_bias(bias, pairs, 0.001))
    # row 0: mean 6; d = 0.001 * sign(6 - n) = [-1, +1, 0, 0] e-3, mean 0
    np.testing.assert_allclose(got[0], [-0.001, 0.101, -0.2, 0.05], atol=1e-7)
    np.testing.assert_allclose(got[1], 1.0, atol=1e-7)      # even: unmoved
    lopsided = np.asarray(balance_bias(jnp.zeros((1, 4)),
                                       jnp.asarray([[9, 1, 1, 1]]), 0.001))
    # d = [-1, 1, 1, 1] e-3, mean 0.5e-3: centred
    np.testing.assert_allclose(lopsided[0],
                               [-0.0015, 0.0005, 0.0005, 0.0005], atol=1e-7)
    assert abs(lopsided.sum()) < 1e-9


@pytest.fixture(scope="module", params=[1, 2], ids=["gas1", "gas2"])
def trained(request):
    """Four steps of the tiny model through ``deepspeed_tpu.initialize`` /
    ``engine.train_batch`` with a heavy weight decay, the masters and
    moments read after every step."""
    gas = request.param
    model = LlamaModel(config())
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-2, "weight_decay": 0.5}},
        "bf16": {"enabled": True}, "zero_optimization": {"stage": 1},
        "gradient_clipping": 1.0, "steps_per_print": 0})
    batch = engine.train_batch_size()
    ids = np.random.default_rng(0).integers(0, 256, (batch, 32),
                                            dtype=np.int32)
    bias = lambda: np.asarray(engine.state.master["blocks"]["router_bias"])
    seen, losses = [bias()], []
    for _ in range(4):
        losses.append(float(engine.train_batch({"input_ids": ids})))
        seen.append(bias())
    engine._report_aux(None, wait=True)
    yield engine, model, ids, seen, losses
    _release(engine)


def test_the_rule_moves_the_bias_and_the_optimizer_never_touches_it(trained):
    engine, model, ids, seen, losses = trained
    assert losses[-1] < losses[0]
    for before, after in zip(seen, seen[1:]):
        step = (after - before) / 0.001
        # d - mean(d) with d in {-1, 0, 1}: every row's moves sum to zero
        # and differ from each other by whole units; a weight decay of 0.5 x
        # lr 1e-2 would shrink every entry by 0.5% a step and sum to no zero
        assert np.abs(step.sum(axis=-1)).max() < 1e-3
        spread = step - step.min(axis=-1, keepdims=True)
        np.testing.assert_allclose(spread, np.round(spread), atol=2e-3)
        assert spread.max() in (pytest.approx(1.0, abs=2e-3),
                                pytest.approx(2.0, abs=2e-3))
    # the moments of the ruled leaf stay what they were drawn as: zeros
    opt = engine.state.opt_state
    for moment in (opt.mu["blocks"]["router_bias"],
                   opt.nu["blocks"]["router_bias"]):
        assert not np.asarray(moment).any()
    # every other leaf moved by the optimizer
    assert np.abs(np.asarray(engine.state.master["blocks"]["router_w"])
                  ).sum() > 0
    # the compute copy follows the master
    np.testing.assert_allclose(
        np.asarray(engine.state.params["blocks"]["router_bias"],
                   np.float32), seen[-1], rtol=1e-2)


def test_the_rule_reads_the_steps_own_routing(trained):
    """Step 1's move is the rule on the counts the loss's aux gives for the
    parameters the step started from."""
    engine, model, ids, seen, _ = trained
    fresh = LlamaModel(config())
    engine2, *_ = deepspeed_tpu.initialize(model=fresh, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps":
            engine._config.gradient_accumulation_steps,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "bf16": {"enabled": True}, "zero_optimization": {"stage": 1},
        "steps_per_print": 0})
    pairs = 0
    gas = engine._config.gradient_accumulation_steps
    for micro in np.split(ids, gas):
        _, aux = jax.jit(fresh.loss_and_aux)(engine2.state.params,
                                             {"input_ids": micro})
        pairs = pairs + np.asarray(aux["expert_pairs"])
    want = np.asarray(balance_bias(jnp.asarray(seen[0]), jnp.asarray(pairs),
                                   0.001))
    np.testing.assert_allclose(seen[1], want, atol=1e-6)
    _release(engine2)


def test_a_step_leaves_the_counter_without_a_host_sync_of_its_own(trained):
    engine, model, ids, _, _ = trained
    events = [s for s in telemetry.get_tracer().snapshot()
              if s.name == "moe/expert_tokens"][-4:]
    assert len(events) == 4 and not engine._aux_pending
    for e in events:
        counts = np.asarray(e.args["counts"])
        assert counts.shape == (4, 8)                   # routed layers x held
        assert (e.args["held_first"], e.args["held"]) == (4, 8)
        assert e.args["routed_pairs"] == 4 * ids.size * 4   # L x tokens x k
        assert 0 < counts.sum() < e.args["routed_pairs"]
        assert 0 < e.args["bias_abs_max"] < 0.1
        # 8 of the router's 16 held: the buffer takes every pair
        assert e.args["overflow_calls"] == 0
    assert [e.args["step"] for e in events] == [1, 2, 3, 4]


def test_the_three_call_api_refuses_a_model_with_ruled_leaves():
    engine, *_ = deepspeed_tpu.initialize(model=LlamaModel(config()), config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 0})
    with pytest.raises(NotImplementedError, match="ruled leaves"):
        engine.forward({"input_ids": np.zeros((8, 16), np.int32)})
    _release(engine)


# ------------------------------------------ the compiler's own kernel names
def test_a_bare_compiler_name_takes_its_operands_scope():
    """XLA:TPU rewrites ``ragged_dot`` into its own Mosaic kernel and names
    it ``ragged-dot-none``, with no path of the program's: the door names it
    by its operands, a backward product by the cotangent it reads."""
    from deepspeed_tpu.sharding.jit import _instruction_scopes
    from deepspeed_tpu.telemetry.scopes import classify

    fwd = "jit(step_fn)/jvp(layers)/while/body/closed_call/checkpoint/moe/moe/experts/gather"
    rerun = ("jit(step_fn)/transpose(jvp(layers))/while/body/closed_call/"
             "checkpoint/rematted_computation/moe/moe/experts/gather")
    bwd = ("jit(step_fn)/transpose(jvp(layers))/while/body/closed_call/"
           "checkpoint/moe/moe/experts/mul")
    scan = "jit(step_fn)/transpose(jvp(layers))/while/body/dynamic_slice"
    text = f"""ENTRY %main (p: f32[8]) -> f32[8] {{
  %rows = bf16[8,4] fusion(%p), kind=kLoop, metadata={{op_name="{fwd}"}}
  %again = bf16[8,4] fusion(%p), kind=kLoop, metadata={{op_name="{rerun}"}}
  %cot = bf16[8,4] fusion(%p), kind=kLoop, metadata={{op_name="{bwd}"}}
  %w = bf16[2,4,4] fusion(%p), kind=kLoop, metadata={{op_name="{scan}"}}
  %ragged-dot-none = bf16[8,4] custom-call(%rows, %w), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %ragged-dot-none.1 = bf16[8,4] custom-call(%w, %again), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %ragged-dot-none.2 = bf16[8,4] custom-call(%again, %cot, %w), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
}}"""
    table = _instruction_scopes(text)
    got = {n: classify(table[n], n) for n in table if n.startswith("ragged")}
    assert got == {"ragged-dot-none": ("moe/experts", "fwd"),
                   "ragged-dot-none.1": ("moe/experts", "recompute"),
                   "ragged-dot-none.2": ("moe/experts", "bwd")}
    assert classify("jit(f)/optimizer/router_bias/add") == \
        ("optimizer/router_bias", "none")


# ------------------------------------------- a share's rows outside any group
def _poisoned_ragged_dot():
    """``jax.lax.ragged_dot`` as the chip runs it: the rows of NO group come
    back as the buffer held them (NaN here), forward and in d(rows)."""
    clean = jax.lax.ragged_dot

    def poison(a, sizes):
        outside = jnp.arange(a.shape[0]) >= jnp.sum(sizes)
        return jnp.where(outside[:, None], jnp.nan, a)

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        return poison(clean(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, pull = jax.vjp(lambda l, r: clean(l, r, sizes), lhs, rhs)
        d_lhs, d_rhs = pull(jnp.where(jnp.isnan(g), 0.0, g))
        bad = jnp.any(jnp.isnan(jnp.where(      # a NaN INSIDE a group
            (jnp.arange(g.shape[0]) < jnp.sum(sizes))[:, None], g, 0.0)))
        return poison(d_lhs, sizes), jnp.where(bad, jnp.nan, d_rhs), None

    poisoned.defvjp(fwd, bwd)
    return poisoned


@pytest.mark.parametrize("T,held,boost", [
    (24, 8, 0.0),       # a buffer of every pair: one pass, no loop
    (256, 2, 0.0),      # the held pairs fit the compact buffer
    (256, 2, 6.0),      # they do not: further chunks of the sorted pairs run
], ids=["whole", "fits", "overflows"])
def test_what_ragged_dot_leaves_in_a_row_of_no_group_reaches_nothing(
        monkeypatch, T, held, boost):
    """A share's unheld pairs sort behind the last group. The TPU's grouped
    kernels never visit such a row: the product's output there, and the
    cotangent its transpose hands back, are what the buffer held (found on
    the chip, PR 37: d(x) off by 1e4 of its norm). Here such rows are
    POISONED with NaN, forward and backward: the output and every gradient
    must still equal what the clean product gives."""
    from deepspeed_tpu.moe import dropless

    key = jax.random.PRNGKey(0)
    D, F, k, router, first = 16, 8, 4, 16, 4
    x = jax.random.normal(key, (T, D))
    router_w = jax.random.normal(jax.random.fold_in(key, 1), (D, router))
    bias = jnp.zeros(router).at[first:first + held].set(boost)
    gate_w, up_w = (0.3 * jax.random.normal(
        jax.random.fold_in(key, i), (held, D, F)) for i in (2, 3))
    down_w = 0.3 * jax.random.normal(jax.random.fold_in(key, 4), (held, F, D))

    def out(x, router_w, gate_w, up_w, down_w):
        _, w, e = dropless.route_topk(x, router_w, k, True, "sigmoid", 2.0,
                                      bias=bias)
        y, sizes = dropless.routed_mlp(x, w, e, gate_w, up_w, down_w,
                                       first=first, n_experts=router)
        return jnp.sum(y * jnp.cos(jnp.arange(D))), sizes

    step = jax.value_and_grad(out, argnums=(0, 1, 2, 3, 4), has_aux=True)
    (want_out, sizes), want = step(x, router_w, gate_w, up_w, down_w)
    assert bool(dropless.share_overflowed(sizes, T * k, router)) == (boost > 0)
    monkeypatch.setattr(jax.lax, "ragged_dot", _poisoned_ragged_dot())
    (got_out, _), got = step(x, router_w, gate_w, up_w, down_w)
    np.testing.assert_allclose(got_out, want_out, rtol=1e-6)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


# --------------------- a share moves only the rows of the experts it holds
def _full_size_form(x, weights, experts, gate_w, up_w, down_w, first=None):
    """The routed MLP as it stood before a share's rows were compacted (PR
    37's ``_routed_mlp``, the ``ragged_dot`` branch, verbatim): row buffers
    of ALL tokens x k pairs. What ``first is None`` must still lower to, and
    what a share must still compute."""
    T, D = x.shape
    k = experts.shape[1]
    E = gate_w.shape[-3]
    flat = experts.reshape(-1)
    if first is not None:
        held = (flat >= first) & (flat < first + E)
        flat = jnp.where(held, flat - first, E)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    dot = lambda rows, w: jax.lax.ragged_dot(rows, w.astype(rows.dtype), sizes)
    rows = x[order // k]
    if first is None:
        grouped = lambda a: a
    else:
        in_group = (jnp.arange(T * k) < jnp.sum(sizes))[:, None]
        grouped = lambda a: jnp.where(in_group, a, jnp.zeros_like(a))
    rows = grouped(rows)
    h = jax.nn.silu(grouped(dot(rows, gate_w))) * grouped(dot(rows, up_w))
    y = dot(h, down_w)[jnp.argsort(order)]
    if first is not None:
        y = jnp.where(held[:, None], y, jnp.zeros_like(y))
    out = jnp.einsum("tk,tkd->td", weights, y.reshape(T, k, D).astype(
        jnp.float32))
    return out.astype(x.dtype), sizes


def _expert_by_expert(x, weights, experts, gate_w, up_w, down_w, first):
    """The plain reference: every held expert over every token, in float32,
    weighted by what the router gave the pair (zero where it chose another)."""
    x32 = x.astype(jnp.float32)
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(gate_w.shape[0]):
        g, u, d = (w[e].astype(jnp.float32) for w in (gate_w, up_w, down_w))
        w = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=-1)
        out = out + w[:, None] * ((jax.nn.silu(x32 @ g) * (x32 @ u)) @ d)
    return out


SHARE = dict(pairs=1024, D=32, F=16, router=16, first=6, held=2)    # E/n = 1/8


def _share_case(k, held_pairs, dtype, one_expert=False, seed=0):
    """Seeded inputs of a call in which exactly ``held_pairs`` of the 1,024
    (token, j) pairs fall on the share's experts (6 and 7 of 16: the middle
    of the router's range), alternately or all on ONE; the others on experts
    on both sides of the share."""
    c = SHARE
    T = c["pairs"] // k
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    flat = rng.choice([0, 3, 5, 8, 15], c["pairs"]).astype(np.int32)
    at = rng.permutation(c["pairs"])[:held_pairs]
    flat[at] = c["first"] + (0 if one_expert else np.arange(held_pairs) % 2)
    x = jax.random.normal(key, (T, c["D"])).astype(dtype)
    weights = jax.random.uniform(jax.random.fold_in(key, 1), (T, k),
                                 minval=0.1)
    gate_w, up_w = (0.3 * jax.random.normal(
        jax.random.fold_in(key, i), (c["held"], c["D"], c["F"])).astype(dtype)
        for i in (2, 3))
    down_w = 0.3 * jax.random.normal(
        jax.random.fold_in(key, 4), (c["held"], c["F"], c["D"])).astype(dtype)
    return (x, weights, gate_w, up_w, down_w), jnp.asarray(flat.reshape(T, k))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("k,count,how", [
    (8, "none", ""), (8, "one", ""), (8, "capacity", ""),
    (8, "capacity+1", ""), (8, "all", "one_expert"), (8, "half", ""),
    (1, "one", ""), (1, "capacity+1", ""), (1, "all", "one_expert"),
    (8, "capacity", "checkpoint"), (8, "capacity+1", "checkpoint"),
    (8, "capacity+1", "poisoned"),
], ids=lambda v: str(v) or "plain")
def test_a_share_moves_only_its_held_rows_and_computes_every_pair(
        monkeypatch, dtype, k, count, how):
    """The share's ``ragged_dot`` path moves ``share_capacity`` rows (twice
    the even share) through the three products and runs what a call holds
    beyond them chunk by chunk in a loop of its own VJP: at ANY held count
    the output and the gradient in x, the weights and the three expert
    leaves are those of the full-size form it replaced (all T x k rows) and
    of the plain reference."""
    from deepspeed_tpu.moe import dropless

    pairs = SHARE["pairs"]
    C = dropless.share_capacity(pairs, SHARE["held"], SHARE["router"])
    assert C == 256
    n = {"none": 0, "one": 1, "capacity": C, "capacity+1": C + 1,
         "half": pairs // 2, "all": pairs}[count]
    args, experts = _share_case(k, n, dtype, one_expert=how == "one_expert")
    first, router = SHARE["first"], SHARE["router"]
    probe = jnp.cos(jnp.arange(SHARE["D"], dtype=jnp.float32))

    def scalar(form):
        def f(*a):
            out, sizes = form(a[0], a[1], experts, *a[2:])
            return jnp.sum(out.astype(jnp.float32) * probe), (out, sizes)
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True)

    share = lambda *a: dropless.routed_mlp(*a, first=first, n_experts=router)
    if how == "checkpoint":
        share = jax.checkpoint(share)
    full = scalar(lambda *a: _full_size_form(*a, first=first))(*args)
    plain = scalar(lambda *a: (_expert_by_expert(*a, first=first), None))(
        *(a.astype(jnp.float32) for a in args))
    if how == "poisoned":
        monkeypatch.setattr(jax.lax, "ragged_dot", _poisoned_ragged_dot())
    got = jax.jit(scalar(share))(*args)

    (_, (out, sizes)), grads = got
    (_, (full_out, full_sizes)), full_grads = full
    (_, (plain_out, _)), plain_grads = plain
    assert int(jnp.sum(sizes)) == n and (sizes == full_sizes).all()
    assert bool(dropless.share_overflowed(sizes, pairs, router)) == (n > C)
    assert out.dtype == dtype

    def close(a, b, tol):
        a, b = (np.asarray(v, np.float32) for v in (a, b))
        assert np.isfinite(a).all()
        scale = max(np.linalg.norm(b), 1e-30)
        assert np.linalg.norm(a - b) <= tol * scale, (
            np.linalg.norm(a - b) / scale)

    # the same products in the same precision, summed in another order
    same, ref = (2e-6, 2e-5) if dtype == jnp.float32 else (6e-3, 2e-2)
    for a, b, p in zip((out,) + grads, (full_out,) + full_grads,
                       (plain_out,) + plain_grads):
        close(a, b, same)
        close(a, p, ref)


def test_every_expert_held_lowers_to_the_text_it_always_had():
    """``first is None`` (a whole routed model: OLMoE's training, most CPU
    tests) is decided statically and keeps the full-size form, op for op."""
    from deepspeed_tpu.moe import dropless

    args, experts = _share_case(8, 0, jnp.bfloat16)

    def lowered(form):
        def routed(x, weights, experts, gate_w, up_w, down_w):
            return form(x, weights, experts, gate_w, up_w, down_w)
        return jax.jit(routed).lower(args[0], args[1], experts % 2,
                                     *args[2:]).as_text()

    assert lowered(dropless.routed_mlp) == lowered(_full_size_form)
    assert lowered(lambda *a: dropless.routed_mlp(
        *a, first=0, n_experts=16)) != lowered(_full_size_form)


# ----------------------------- the share in the model, and the step's counter
@pytest.fixture(scope="module")
def narrow_share():
    """The tiny model holding 2 of its router's 16 experts: 2 x 64 tokens x
    top-4 = 512 pairs a routed layer against a buffer of 128 rows."""
    model = LlamaModel(config(experts_held=(4, 2)))
    params = model.init_params(jax.random.PRNGKey(3))
    ids = np.random.default_rng(1).integers(0, 256, (2, 64), dtype=np.int32)
    return model, params, {"input_ids": ids}


def _with_bias(params, model, value):
    """``params`` with the selection bias of the HELD experts at ``value``:
    far above the scores every token chooses them, far below none does."""
    first, count = model.config.experts_held
    bias = params["blocks"]["router_bias"]
    return {**params, "blocks": {**params["blocks"], "router_bias":
                                 bias.at[:, first:first + count].set(value)}}


@pytest.mark.parametrize("bias", [None, 9.0], ids=["fits", "overflows"])
def test_the_share_inside_the_layer_scan_is_the_full_size_form(
        monkeypatch, narrow_share, bias):
    """Under the block's ``jax.checkpoint`` (remat 'attn') inside
    ``layer_scan``: the loss and every gradient of the model are what the
    full-size form gives in the same place."""
    from deepspeed_tpu.moe import dropless

    model, params, batch = narrow_share
    if bias is not None:
        params = _with_bias(params, model, bias)
    step = lambda: jax.jit(jax.value_and_grad(
        lambda p: model.loss_and_aux(p, batch), has_aux=True))(params)
    ((loss, aux), grads) = step()
    assert int(aux["overflow_calls"]) == (0 if bias is None else 4)

    def full(x, weights, experts, gate_w, up_w, down_w, layer, first, n):
        assert layer is None and first == 4 and n == 16
        return _full_size_form(x, weights, experts, gate_w, up_w, down_w,
                               first=first)

    monkeypatch.setattr(dropless, "_routed_mlp", full)
    ((want_loss, want_aux), want) = step()
    assert (np.asarray(aux["held_pairs"]) ==
            np.asarray(want_aux["held_pairs"])).all()
    np.testing.assert_allclose(loss, want_loss, rtol=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        g, w = (np.asarray(v, np.float32) for v in (g, w))
        assert np.linalg.norm(g - w) <= 2e-2 * max(np.linalg.norm(w), 1e-12), \
            jax.tree_util.keystr(path)


@pytest.fixture
def registry(tmp_path):
    """A telemetry session's registry (without one every counter is the
    no-op); the span recorder stays the process's ring."""
    from deepspeed_tpu.runtime.config import TelemetryConfig

    telemetry.configure(TelemetryConfig(enabled=True, trace=False,
                                        output_dir=str(tmp_path)))
    yield telemetry.get_registry()
    telemetry.deconfigure()


def test_a_call_that_outgrows_the_shares_buffer_is_counted(narrow_share,
                                                           registry):
    """``moe/share_overflow_calls`` and the ``moe/expert_tokens`` instant's
    ``overflow_calls``: of a step's routed-layer calls, those that held more
    pairs than ``share_capacity`` rows, by the rule ``_routed_mlp`` sizes
    its buffer with. A step whose every token chooses the two held experts
    (4 layers x 256 pairs against 128 rows), then one in which none does."""
    from deepspeed_tpu.moe.dropless import share_capacity

    model, params, batch = narrow_share
    assert share_capacity(2 * 64 * 4, 2, 16) == 128
    counter = registry.counter("moe/share_overflow_calls")
    for step, (bias, calls) in enumerate([(9.0, 4), (-9.0, 0)], start=1):
        before = counter.value
        _, aux = jax.jit(model.loss_and_aux)(
            _with_bias(params, model, bias), batch)
        model.report_aux(step, jax.device_get(aux))
        event = [s for s in telemetry.get_tracer().snapshot()
                 if s.name == "moe/expert_tokens"][-1]
        assert event.args["step"] == step
        assert event.args["overflow_calls"] == calls
        assert counter.value - before == calls
        held = np.asarray(event.args["counts"]).sum(axis=-1)
        assert (held == (256 if calls else 0)).all()
