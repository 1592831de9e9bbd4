"""The program's names for its device work (``telemetry/scopes.py``), and the
door's two reads of a compiled program (``sharding/jit.py``): the classifier
on ``op_name`` strings written by hand, the models and the engine compiled
on the CPU (every vocabulary name a model uses is in its compiled text, with
its pass), ``instruction_scopes()`` and ``memory()`` on a real record.
Nothing here is a time: the names are metadata of the compiled program.
"""

import collections
import gc
import re

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
from deepspeed_tpu.sharding import jit as door
from deepspeed_tpu.telemetry import scopes
from deepspeed_tpu.telemetry.scopes import classify

STEP = "jit(step_fn)/jit(main)/"


# --------------------------------------------------------- the classifier
@pytest.mark.parametrize("op_name,instruction,want", [
    # forward of a block inside the layer scan
    (STEP + "jvp(layers)/while/body/closed_call/mlp/dot_general", "fusion.3",
     ("mlp", "fwd")),
    # the scan's own ops: innermost scope is the one around the scan
    (STEP + "jvp(layers)/while/body/dynamic_slice", "dynamic-slice.4",
     ("layers", "fwd")),
    (STEP + "transpose(jvp(layers))/while/body/dynamic_update_slice",
     "fusion.9", ("layers", "bwd")),
    # backward of a block, under the checkpoint
    (STEP + "transpose(jvp(layers))/while/body/closed_call/checkpoint/mlp/mul",
     "fusion.12", ("mlp", "bwd")),
    # a scope entered outside a transform is written INSIDE its brackets
    (STEP + "transpose(jvp(head))/mul", "fusion.1", ("head", "bwd")),
    (STEP + "jvp(embed)/gather", "fusion.2", ("embed", "fwd")),
    # jax.checkpoint's re-run, inside the backward
    (STEP + "transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/qkv/dot_general", "fusion.7",
     ("attn/qkv", "recompute")),
    # XLA's own rematerialization: the INSTRUCTION's name says it
    (STEP + "jvp(layers)/while/body/closed_call/mlp/mlp/up/dot_general",
     "convolution_add_fusion.13.remat", ("mlp/up", "recompute")),
    # nested scopes: the innermost vocabulary name wins, with its finer name
    (STEP + "accumulate/while/body/closed_call/jvp(layers)/while/body/"
     "closed_call/attn/core/flash_fwd", "flash_fwd.3", ("attn/core", "fwd")),
    (STEP + "accumulate/while/body/add", "fusion.5", ("accumulate", "none")),
    (STEP + "optimizer/update/mul", "fusion.8", ("optimizer/update", "none")),
    # a served program: no transform
    ("jit(prefill)/jit(main)/layers/while/body/moe/experts/ragged_dot",
     "fusion.2", ("moe/experts", "none")),
    ("jit(decode_chunk)/jit(main)/while/body/layers/while/body/kda/mul",
     "fusion.4", ("kda", "none")),
    # no scope at all; a jitted FUNCTION called like a scope is not one
    (STEP + "jvp()/slice", "slice.1", ("", "fwd")),
    (STEP + "jit(head)/mul", "fusion.6", ("", "none")),
    ("", "copy-done.12", ("", "none")),
    # a name after a scope that is no finer name is an op, not a sub-scope
    (STEP + "optimizer/sqrt", "fusion.11", ("optimizer", "none")),
])
def test_classify_reads_scope_and_pass(op_name, instruction, want):
    assert classify(op_name, instruction) == want


@pytest.mark.parametrize("name", ["ffn", "attn/softmax", "", "core"])
def test_a_name_outside_the_vocabulary_is_refused(name):
    with pytest.raises(ValueError, match="scope"):
        scopes.scope(name)


def test_the_helper_is_a_named_scope_and_nothing_else():
    def f(x):
        with scopes.scope("mlp/up"):
            return x * 2

    text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "mlp/up/mul" in text


# -------------------------------------------- the models, compiled on the CPU
def op_names(compiled):
    """[(instruction, opcode, op_name)] of the non-fused computations."""
    table = door._instruction_scopes(compiled.as_text())
    rows, fused = [], False
    for line in compiled.as_text().splitlines():
        head = door._COMPUTATION.match(line)
        if head:
            fused = "fused_computation" in head.group(1)
        m = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = \S+ ([\w\-]+)\(", line)
        if m and not fused:
            rows.append((m.group(1), m.group(2), table[m.group(1)]))
    return rows


def by_class(compiled):
    """{scope: passes} over every instruction of every computation (a
    one-token embedding lookup lives inside a fusion of the next scope)."""
    found = collections.defaultdict(set)
    for name, op_name in door._instruction_scopes(compiled.as_text()).items():
        scope, pass_ = classify(op_name, name)
        found[scope.split("/")[0]].add(pass_)
    return found


def scoped_share(compiled, kinds=("dot", "fusion", "convolution")):
    """Share of the ``kinds`` instructions the PROGRAM traced that carry a
    scope. Let through, because they are the CPU compiler's and not the
    program's: an instruction with no ``op_name`` at all (the ``wrapped_*``
    fusions around a bare reduce, the ``dot`` its batch-dot rewrite leaves
    and the ``*_bitcast_fusion`` layout changes around it), and a hoisted
    cast of a float32 test parameter, which carries the ARGUMENT's name
    (``p['blocks']['q_w']``) and not a ``jit(...)`` path."""
    rows = [(n, op) for n, kind, op in op_names(compiled)
            if kind in kinds and op.startswith("jit(")]
    assert len(rows) > 30
    return sum(bool(classify(op, n)[0]) for n, op in rows) / len(rows)


LLAMA = dict(vocab_size=512, n_positions=128, n_embd=64, n_head=4,
             n_kv_head=2, remat="attn", use_flash_attention=False)
MODELS = {
    "gpt2": (GPT2Model(GPT2Config(
        vocab_size=512, n_positions=128, n_embd=64, n_layer=2, n_head=4,
        remat="attn")), {"embed", "attn", "mlp", "head", "layers"}),
    "llama-dense": (LlamaModel(LlamaConfig(
        **LLAMA, n_layer=2, intermediate_size=128)),
        {"embed", "attn", "mlp", "head", "layers"}),
    "llama-routed": (LlamaModel(LlamaConfig(
        **LLAMA, n_layer=2, intermediate_size=32, n_experts=8,
        n_experts_per_tok=2, n_shared_experts=1, router_aux_loss_coef=0.01)),
        {"embed", "attn", "moe", "head", "layers"}),
    "llama-kda": (LlamaModel(LlamaConfig(
        **dict(LLAMA, n_layer=4, head_dim=16), intermediate_size=128,
        use_rope=False, attn_gate=True, gqa_layers=(0,), kda_heads=4,
        kda_head_dim=16)), {"embed", "attn", "kda", "mlp", "head", "layers"}),
}


@pytest.fixture(scope="module", params=sorted(MODELS))
def model_case(request):
    model, names = MODELS[request.param]
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    return model, params, names


def test_train_step_carries_every_name_the_model_uses(model_case):
    model, params, names = model_case
    grad = jax.jit(jax.value_and_grad(
        lambda p, ids: model.loss(p, {"input_ids": ids})))
    compiled = grad.lower(
        params, jax.ShapeDtypeStruct((2, 64), jnp.int32)).compile()
    found = by_class(compiled)
    assert names <= set(found), sorted(found)
    # remat "attn": a block's forward, its backward and its re-run
    mixer = "kda" if "kda" in names else "attn"
    mlp = "moe" if "moe" in names else "mlp"
    for scope in (mixer, mlp):
        assert {"fwd", "bwd", "recompute"} <= found[scope], (scope, found)
    assert scoped_share(compiled) >= 0.9


@pytest.mark.parametrize("program", ["prefill", "decode_step"])
def test_served_programs_carry_the_names_too(model_case, program):
    model, params, names = model_case
    cache = jax.eval_shape(lambda: model.init_cache(1, 64))
    if program == "prefill":
        args = (params, jax.ShapeDtypeStruct((1, 32), jnp.int32), cache)
    else:
        args = (params, jax.ShapeDtypeStruct((1,), jnp.int32), cache)
    compiled = jax.jit(getattr(model, program)).lower(*args).compile()
    found = by_class(compiled)
    assert names <= set(found), sorted(found)
    assert all(passes == {"none"} for s, passes in found.items() if s)
    assert scoped_share(compiled) >= 0.9


# -------------------------------------------------- the door's two reads
FUSED_TEXT = '''HloModule jit_f, entry_computation_layout={()->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %multiply.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/attn/qkv/mul" source_file="x.py" source_line=3}
  ROOT %add.2 = f32[4]{0} add(%multiply.1, %param_0), metadata={op_name="jit(f)/mlp/up/add"}
}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %negate.3 = f32[4]{0} negate(%param_0.1), metadata={op_name="jit(f)/head/neg"}
  %negate.4 = f32[4]{0} negate(%negate.3), metadata={op_name="jit(f)/head/neg"}
  %abs.5 = f32[4]{0} abs(%negate.4), metadata={op_name="jit(f)/embed/abs"}
  ROOT %copy.6 = f32[4]{0} copy(%abs.5)
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %fusion.1 = f32[4]{0} fusion(%fusion), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/optimizer/mul"}
  ROOT %copy.7 = f32[4]{0} copy(%fusion.2)
}
'''


def test_instruction_scopes_resolves_a_fusion_through_its_computation():
    table = door._instruction_scopes(FUSED_TEXT)
    assert table["fusion"] == "jit(f)/mlp/up/add"           # the ROOT's
    assert table["fusion.1"] == "jit(f)/head/neg"           # the commonest
    assert table["fusion.2"] == "jit(f)/optimizer/mul"      # its own
    # what the compiler made itself: its first named operand's, or none
    assert table["copy.7"] == "jit(f)/optimizer/mul" and table["x"] == ""
    assert table["copy.6"] == "jit(f)/embed/abs"
    assert table["multiply.1"] == "jit(f)/attn/qkv/mul"     # every computation


@pytest.fixture(scope="module")
def step_record():
    """The real engine's train step at gpt2-tiny, gas 2, dispatched once."""
    door.reset_program_table()
    cfg = GPT2Config(vocab_size=512, n_positions=64, n_embd=64, n_layer=2,
                     n_head=4, remat="attn")
    engine, *_ = deepspeed_tpu.initialize(model=GPT2Model(cfg), config={
        "train_batch_size": 2 * jax.device_count(),
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True}, "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 1}, "steps_per_print": 0})
    ids = jnp.zeros((2 * jax.device_count(), 32), jnp.int32)
    engine.train_batch({"input_ids": ids})
    record = door.program_table()["engine/train_batch[gas=2]"]
    yield engine, record
    door.reset_program_table()


def test_the_engine_step_names_its_own_phases(step_record):
    _, record = step_record
    found = by_class(record.compiled())
    assert {"embed", "attn", "mlp", "head", "layers", "accumulate",
            "optimizer"} <= set(found)
    assert found["optimizer"] == {"none"} and "none" in found["accumulate"]
    finer = {classify(op, n)[0] for n, _, op in op_names(record.compiled())}
    assert {"optimizer/gnorm", "optimizer/update", "optimizer/cast"} <= finer


def test_instruction_scopes_covers_the_compiled_text(step_record):
    _, record = step_record
    table = record.instruction_scopes()
    text = record.compiled().as_text()
    names = re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", text, re.M)
    assert set(names) == set(table) and len(names) > 500
    assert sum("attn/qkv" in v for v in table.values()) > 10


def test_memory_is_the_compilers_count(step_record):
    _, record = step_record
    m = record.compiled().memory_analysis()
    got = record.memory()
    assert got == {
        "argument": m.argument_size_in_bytes,
        "output": m.output_size_in_bytes, "alias": m.alias_size_in_bytes,
        "temp": m.temp_size_in_bytes,
        "generated_code": m.generated_code_size_in_bytes,
        "total": m.argument_size_in_bytes + m.output_size_in_bytes
        - m.alias_size_in_bytes + m.temp_size_in_bytes
        + m.generated_code_size_in_bytes}
    assert got["alias"] > 0 and got["total"] > got["argument"]   # donated


def test_one_compile_serves_both_reads(step_record):
    _, record = step_record
    first = record.compiled()
    assert record.memory() and record.instruction_scopes()
    assert record.compiled() is first


def test_a_record_never_dispatched_reads_nothing():
    fn = door.sharded_jit(lambda x: x + 1, label="test/never_called",
                          in_shardings=door.INHERIT,
                          out_shardings=door.INHERIT, donate_argnums=())
    record = fn.program_record
    assert record.compiled() is None
    assert record.instruction_scopes() is None and record.memory() is None


def test_a_collected_record_reads_nothing():
    fn = door.sharded_jit(lambda x: x * 3, label="test/collected",
                          in_shardings=door.INHERIT,
                          out_shardings=door.INHERIT, donate_argnums=())
    fn(jnp.ones(4))
    record = fn.program_record
    assert record.memory()["total"] > 0
    del fn
    gc.collect()
    assert record.jitted is None
    assert record.compiled() is None
    assert record.instruction_scopes() is None and record.memory() is None


def test_the_call_path_is_untouched():
    """``_ShardedProgram.__call__`` does what it did: capture once, call,
    read the specialization count. What the door says of a program is
    computed when asked."""
    import inspect

    src = inspect.getsource(door._ShardedProgram.__call__)
    assert "compiled" not in src and "scopes" not in src


def test_the_flops_profilers_per_scope_table_has_the_scopes_to_show():
    """``profiling/flops_profiler`` walks the jaxpr: a matmul's FLOPs go to
    the scope its equation was traced in, under the scan that holds it."""
    from deepspeed_tpu.profiling.flops_profiler.profiler import \
        count_jaxpr_flops

    model, _ = MODELS["gpt2"]
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    total, per_scope = count_jaxpr_flops(
        model.apply, params, jax.ShapeDtypeStruct((2, 64), jnp.int32))
    assert sum(per_scope.values()) == total
    d, layers, tokens = 64, 2, 2 * 64
    assert per_scope["layers/attn/qkv"] == 2 * tokens * d * 3 * d * layers
    assert per_scope["layers/mlp/mlp/up"] == 2 * tokens * d * 4 * d * layers
    assert per_scope["head"] == 2 * tokens * d * 512
    assert {k.split("/")[0] for k in per_scope} == {"layers", "head"}
