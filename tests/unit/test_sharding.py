"""Unit tests for the GSPMD-native sharding core (deepspeed_tpu/sharding/).

Covers: the process-global mesh cache (one object per topology — the
device-order guarantee), the spec registry (ShardingPlan is a view over
it), the sharded_jit contract (mandatory in/out shardings + donation,
program table records), and the ds_doctor ``sharding/unspecified-jit``
lint — ZERO findings on the migrated tree is asserted here, in tier-1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.sharding import (INHERIT, ShardingRegistry,
                                    ensure_global_mesh, global_mesh,
                                    mesh_axes_string, program_table,
                                    render_program_table,
                                    reset_program_table, sharded_jit)
from deepspeed_tpu.sharding import mesh as smesh


def _dims(**kw):
    base = {"pipe": 1, "data": 1, "mics": 1, "expert": 1, "seq": 1, "tensor": 1}
    base.update(kw)
    return base


# ------------------------------------------------------------- global mesh
class TestGlobalMesh:
    def test_same_dims_returns_same_object(self):
        m1 = ensure_global_mesh(axis_dims=_dims(data=4, tensor=2))
        m2 = ensure_global_mesh(axis_dims=_dims(data=4, tensor=2))
        assert m1 is m2
        assert global_mesh() is m1

    def test_different_dims_rebuilds(self):
        m1 = ensure_global_mesh(axis_dims=_dims(data=8))
        m2 = ensure_global_mesh(axis_dims=_dims(data=4, tensor=2))
        assert m1 is not m2
        assert dict(m2.shape)["tensor"] == 2

    def test_engine_and_inference_share_the_mesh(self):
        """The deadlock precondition removed: initialize() and a matching
        init_inference build THE SAME mesh object."""
        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

        cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=16, n_layer=1,
                         n_head=2, use_flash_attention=False)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2Model(cfg),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "tpu": {"data": 4, "tensor": 2}, "steps_per_print": 0})
        assert eng.mesh is global_mesh()

    def test_mesh_axes_string(self):
        m = ensure_global_mesh(axis_dims=_dims(data=4, tensor=2))
        assert mesh_axes_string(m) == "data=4×tensor=2"
        assert mesh_axes_string(None) == "unmeshed"

    def test_rng_is_sharding_invariant(self):
        """The partitionable-threefry pin: a draw compiled with sharded
        out_shardings equals the eager draw (on jax 0.4.x the default was
        False and a pipe-sharded init silently drew DIFFERENT weights)."""
        mesh = ensure_global_mesh(axis_dims=_dims(pipe=2, data=4))
        key = jax.random.PRNGKey(7)

        def draw():
            return jax.random.normal(key, (4, 8, 8), jnp.float32)

        eager = np.asarray(draw())
        with mesh:
            sharded = np.asarray(
                sharded_jit(draw, label="test/draw", donate_argnums=(),
                            in_shardings=(), mesh=mesh,
                            out_shardings=NamedSharding(mesh, P("pipe")))())
        np.testing.assert_allclose(eager, sharded, atol=1e-7)


# ---------------------------------------------------------------- registry
class TestRegistry:
    def test_register_and_shardings(self):
        mesh = ensure_global_mesh(axis_dims=_dims(data=4, tensor=2))
        reg = ShardingRegistry(mesh)
        reg.register("params", {"w": P("tensor", ("data",)), "b": P()})
        sh = reg.shardings("params")
        assert sh["w"].spec == P("tensor", ("data",))
        assert isinstance(sh["b"], NamedSharding)
        with pytest.raises(KeyError):
            reg.spec("grads")

    def test_batch_spec_clamps_per_rank(self):
        mesh = ensure_global_mesh(axis_dims=_dims(data=4, seq=2))
        reg = ShardingRegistry(mesh)
        reg.register("batch", P(("data",), "seq"))
        assert reg.batch_spec(1) == P(("data",))
        assert reg.batch_spec(3) == P(("data",), "seq", None)
        sh = reg.batch_shardings({"ids": np.zeros((8, 16)),
                                  "mask": np.zeros((8,))})
        assert sh["ids"].spec == P(("data",), "seq")
        assert sh["mask"].spec == P(("data",))

    def test_ids_sharding_divisibility_fallback(self):
        mesh = ensure_global_mesh(axis_dims=_dims(data=4, tensor=2))
        reg = ShardingRegistry(mesh)
        reg.register("batch", P(("data",)))
        assert reg.ids_sharding(batch_size=8).spec == P(("data",))
        # a batch the dp world does not divide is EXPLICITLY replicated
        assert reg.ids_sharding(batch_size=3).spec == P()

    def test_plan_is_a_view_over_the_registry(self):
        from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
        from deepspeed_tpu.runtime.zero.partition import plan_sharding

        mesh = ensure_global_mesh(axis_dims=_dims(data=8))
        shapes = jax.eval_shape(lambda: {"w": jnp.zeros((64, 64))})
        plan = plan_sharding(shapes, mesh,
                             zero_config=DeepSpeedZeroConfig(stage=3))
        assert plan.registry.spec("params") is plan.param_specs
        assert plan.registry.spec("batch") is plan.batch_spec
        # opt-state specs land in the registry when mapped
        opt_shapes = jax.eval_shape(
            lambda: {"w": jnp.zeros((64, 64))})
        plan.map_opt_state_specs(opt_shapes, shapes)
        assert plan.registry.has("opt_state")

    # the cache folds the heads into its rows (L, B, S, W): heads go over
    # 'tensor' where the rows carry no pad columns, and stay whole where
    # equal shards of a padded row would cut through heads
    @pytest.mark.parametrize("n_embd,heads", [(256, "tensor"), (16, None)])
    def test_cache_shardings_one_source(self, n_embd, heads):
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

        mesh = ensure_global_mesh(axis_dims=_dims(data=4, tensor=2))
        reg = ShardingRegistry(mesh)
        m = GPT2Model(GPT2Config(vocab_size=64, n_positions=32, n_embd=n_embd,
                                 n_layer=1, n_head=2,
                                 use_flash_attention=False))
        sh = reg.cache_shardings(m)
        assert sh["k"].spec == sh["v"].spec == P(None, None, None, heads)
        assert reg.has("kv_cache")


# -------------------------------------------------------------- sharded_jit
class TestShardedJit:
    def test_mandatory_shardings(self):
        ensure_global_mesh(axis_dims=_dims(data=8))
        with pytest.raises(TypeError):
            sharded_jit(lambda x: x, label="t", donate_argnums=(),
                        in_shardings=None, out_shardings=INHERIT)
        with pytest.raises(TypeError):
            sharded_jit(lambda x: x, label="t", donate_argnums=(),
                        in_shardings=INHERIT, out_shardings=None)
        with pytest.raises(TypeError):
            # donate_argnums is keyword-REQUIRED
            sharded_jit(lambda x: x, label="t",
                        in_shardings=INHERIT, out_shardings=INHERIT)

    def test_program_table_records(self):
        reset_program_table()
        mesh = ensure_global_mesh(axis_dims=_dims(data=4, tensor=2))
        sh = NamedSharding(mesh, P("data"))
        f = sharded_jit(lambda x: x + 1, label="test/add",
                        donate_argnums=(), mesh=mesh,
                        in_shardings=(sh,), out_shardings=sh)
        with mesh:
            out = f(jax.device_put(jnp.arange(8.0), sh))
        assert float(out[0]) == 1.0
        rec = program_table()["test/add"]
        assert rec.mesh_axes == "data=4×tensor=2"
        assert "P('data',)" in rec.in_desc
        assert rec.donate == ()
        assert "test/add" in render_program_table(mesh)
        assert f.program_record is rec

    def test_inherit_is_explicit(self):
        reset_program_table()
        mesh = ensure_global_mesh(axis_dims=_dims(data=8))
        f = sharded_jit(lambda x: x * 2, label="test/inherit",
                        donate_argnums=(), mesh=mesh,
                        in_shardings=INHERIT, out_shardings=INHERIT)
        assert float(f(jnp.float32(2.0))) == 4.0
        rec = program_table()["test/inherit"]
        assert rec.inherited_in and rec.inherited_out
        assert rec.in_desc == "inherit"

    def test_donation_passes_through(self):
        mesh = ensure_global_mesh(axis_dims=_dims(data=8))
        sh = NamedSharding(mesh, P("data"))
        f = sharded_jit(lambda x: x + 1, label="test/donate",
                        donate_argnums=(0,), mesh=mesh,
                        in_shardings=(sh,), out_shardings=sh)
        x = jax.device_put(jnp.arange(8.0), sh)
        with mesh:
            f(x)
        assert x.is_deleted()


# ------------------------------------------------------ unspecified-jit lint
class TestUnspecifiedJitLint:
    def test_zero_findings_on_the_migrated_tree(self):
        """THE acceptance assertion: no engine program enters jax.jit
        outside sharded_jit anywhere in the package."""
        from deepspeed_tpu.analysis.jit_lint import lint_unspecified_jit

        findings = lint_unspecified_jit()
        assert findings == [], "\n".join(
            f"{f.citation}: {f.message[:100]}" for f in findings)

    def test_bare_jit_is_flagged(self):
        from deepspeed_tpu.analysis.jit_lint import lint_jit_source

        src = ("import jax\n"
               "def compile_step(fn):\n"
               "    return jax.jit(fn)\n")
        fs = lint_jit_source(src, "runtime/somewhere.py")
        assert len(fs) == 1
        assert fs[0].rule == "sharding/unspecified-jit"
        assert "compile_step" in fs[0].message
        assert fs[0].citation == "runtime/somewhere.py:3"
        assert fs[0].severity == "error"

    def test_allowlisted_files_pass(self):
        from deepspeed_tpu.analysis.jit_lint import lint_jit_source

        src = "import jax\nprobe = jax.jit(lambda x: x)\n"
        assert lint_jit_source(src, "sharding/jit.py") == []
        assert lint_jit_source(src, "env_report.py") == []
        assert lint_jit_source(src, "runtime/engine.py") != []

    def test_program_table_lint_clean_after_engine(self):
        """Runtime layer: after building a real engine on a multi-axis
        mesh, the program table holds no unspecified entries."""
        import deepspeed_tpu
        from deepspeed_tpu.analysis.jit_lint import lint_program_table
        from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2Model,
                                               synthetic_lm_batch)

        reset_program_table()
        cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=16, n_layer=1,
                         n_head=2, use_flash_attention=False)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2Model(cfg),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 3},
                    "tpu": {"data": 4, "tensor": 2}, "steps_per_print": 0})
        eng.train_batch(synthetic_lm_batch(8, 16, cfg.vocab_size))
        assert len(program_table()) >= 2      # init_state + train_batch
        assert lint_program_table() == []

    def test_doctor_sharding_pass_runs_the_lint(self):
        """run_doctor's sharding pass includes the jit lint without a
        model fixture."""
        from deepspeed_tpu.analysis.doctor import run_doctor

        report = run_doctor({}, passes=("sharding",))
        bad = [f for f in report.findings
               if f.rule == "sharding/unspecified-jit"]
        assert bad == []
