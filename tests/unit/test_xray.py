"""ds_xray tests — post-GSPMD compiled-HLO static analysis.

Tier-1 keeps the cheap spine: the pure HLO-text parser/comm-model units,
ONE gpt2-small ZeRO-3 engine on the 8-device mesh (zero findings on the
current tree + params/master/opt_state actually 1/8-sharded in the
compiled HLO + the PR-12 deadlock reproduced as a lint when a generate
program reverts to inherited shardings), the synthetic static-comm gate
regression, and the bin/ script-lint extension. The full
family/topology matrix, the injected replicated-spec regression, the
dropped-donation fixture and the engine-hook drive are in
tests/slow_tests.txt (each costs whole AOT compiles).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model, synthetic_lm_batch


def _reset():
    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.sharding import mesh as smesh
    from deepspeed_tpu.sharding.jit import reset_program_table

    comm.cdb = None
    smesh.reset_global_mesh()
    reset_program_table()


def _mk_engine(stage=3, tpu=None, extra=None, bs=8, n_embd=64, n_layer=2):
    cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=n_embd,
                     n_layer=n_layer, n_head=4, use_flash_attention=False)
    dcfg = {"train_batch_size": bs,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": stage,
                                  "stage3_param_persistence_threshold": 0}
            if stage >= 3 else {"stage": stage},
            "tpu": tpu or {"data": 8}, "steps_per_print": 0}
    dcfg.update(extra or {})
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2Model(cfg),
                                               config=dcfg)
    return engine, cfg


# ------------------------------------------------------------ hlo_model units
@pytest.mark.analysis
class TestHloModel:
    def test_replica_group_decode(self):
        from deepspeed_tpu.analysis.hlo_model import parse_replica_groups

        assert parse_replica_groups("[1,8]<=[8]") == (tuple(range(8)),)
        assert parse_replica_groups("[4,2]<=[8]") == (
            (0, 1), (2, 3), (4, 5), (6, 7))
        # iota with transpose: arange(8).reshape(4,2).T flattened
        assert parse_replica_groups("[2,4]<=[4,2]T(1,0)") == (
            (0, 2, 4, 6), (1, 3, 5, 7))
        assert parse_replica_groups("{{0,1},{2,3}}") == ((0, 1), (2, 3))
        assert parse_replica_groups("{}") == ()

    def test_shape_bytes(self):
        from deepspeed_tpu.analysis.hlo_model import shape_bytes

        assert shape_bytes("f32[4,256]{1,0}") == 4 * 256 * 4
        assert shape_bytes("bf16[8]") == 16
        assert shape_bytes("(f32[2,2], s32[3])") == 16 + 12
        assert shape_bytes("pred[]") == 1

    def test_wire_model(self):
        from deepspeed_tpu.analysis.hlo_model import (CollectiveOp,
                                                      collective_wire_bytes)

        groups = ((0, 1, 2, 3),)
        ag = CollectiveOp(kind="all-gather", name="x", index=0, bytes=4096,
                          channel_id=1, replica_groups=groups)
        ar = CollectiveOp(kind="all-reduce", name="x", index=1, bytes=4096,
                          channel_id=2, replica_groups=groups)
        rs = CollectiveOp(kind="reduce-scatter", name="x", index=2,
                          bytes=1024, channel_id=3, replica_groups=groups)
        assert collective_wire_bytes(ag) == 4096 * 3 // 4
        assert collective_wire_bytes(ar) == 2 * 4096 * 3 // 4
        assert collective_wire_bytes(rs) == 1024 * 3

    def test_async_start_tiled_layout_parse(self):
        """TPU dumps: async collectives carry tuple shapes with tiled
        layouts (`{0:T(256)}`); the -start op must parse, count ONLY the
        result element (not operand+result), and the -done op is skipped."""
        from deepspeed_tpu.analysis.hlo_model import parse_hlo_module

        text = ("HloModule m, is_scheduled=true, num_partitions=8\n"
                "  %ar = (f32[128]{0:T(256)}, f32[128]{0:T(256)}) "
                "all-reduce-start(f32[128]{0:T(256)} %x), channel_id=1, "
                "replica_groups=[1,8]<=[8], use_global_device_ids=true, "
                "to_apply=%add\n"
                "  %ard = f32[128]{0:T(256)} all-reduce-done("
                "(f32[128]{0:T(256)}, f32[128]{0:T(256)}) %ar)\n")
        m = parse_hlo_module(text)
        assert len(m.collectives) == 1
        op = m.collectives[0]
        assert op.kind == "all-reduce"
        assert op.bytes == 128 * 4          # result element only, not 2x
        assert op.replica_groups == (tuple(range(8)),)

    def test_header_alias_and_layout_parse(self):
        from deepspeed_tpu.analysis.hlo_model import parse_hlo_module

        text = ("HloModule jit_step, is_scheduled=true, input_output_alias="
                "{ {0}: (0, {}, may-alias), {2}: (1, {}, must-alias) }, "
                "entry_computation_layout={(f32[32,64]{1,0}, f32[32,64]{1,0},"
                " f32[4,256]{1,0})->(f32[32,64]{1,0}, bf16[32,64]{1,0}, "
                "f32[])}, num_partitions=8\n"
                "  %all-reduce = f32[4]{0} all-reduce(f32[4]{0} %x), "
                "channel_id=1, replica_groups=[2,4]<=[8], "
                "use_global_device_ids=true, to_apply=%add\n")
        m = parse_hlo_module(text)
        assert m.num_partitions == 8
        assert m.aliases == {0: 0, 2: 1}
        assert m.parameter_bytes == [32 * 64 * 4, 32 * 64 * 4, 4 * 256 * 4]
        assert m.result_bytes == [32 * 64 * 4, 32 * 64 * 2, 4]
        assert len(m.collectives) == 1
        assert m.collectives[0].replica_groups == ((0, 1, 2, 3), (4, 5, 6, 7))


# ------------------------------------------------- the tier-1 gpt2-small case
@pytest.fixture(scope="module")
def zero3_xray():
    """ONE 8-dev ZeRO-3 engine + one step + one xray, shared by the
    tier-1 assertions (each extra engine costs whole compiles). The
    conftest autouse reset clears the process-global program table after
    every test, so the RECORDS are snapshotted here and later tests
    x-ray the snapshot, not the table."""
    from deepspeed_tpu.analysis.xray import run_xray, static_comm_for_engine
    from deepspeed_tpu.sharding import program_table

    _reset()
    engine, cfg = _mk_engine()
    batch = synthetic_lm_batch(8, 32, cfg.vocab_size, seed=0)
    engine.train_batch(batch)
    records = [r for r in program_table().values() if r.can_lower()]
    static = static_comm_for_engine(engine)
    result = run_xray(records, plan=engine.plan)
    yield engine, cfg, result, records, static
    _reset()


@pytest.mark.analysis
class TestXrayZero3:
    def test_zero_findings_on_current_tree(self, zero3_xray):
        """THE tier-1 acceptance: the migrated tree x-rays clean."""
        _, _, result, _, _ = zero3_xray
        bad = [f for f in result.findings if f.severity != "info"]
        assert not bad, "\n".join(str(f) for f in bad)

    def test_zero3_actually_one_eighth_sharded(self, zero3_xray):
        """params/master/opt_state 1/8-sharded in the COMPILED HLO —
        GSPMD's actual buffers, not the registry's promise."""
        _, _, result, _, _ = zero3_xray
        tr = result.program("engine/train_batch")
        assert tr is not None
        fams = tr.family_sharding()
        for family in ("params", "master", "opt_state"):
            assert fams[family]["min_factor"] == 8, (family, fams[family])
            assert fams[family]["sharded_leaves"] >= \
                fams[family]["leaves"] - 1      # scalar step-counters exempt

    def test_static_comm_model(self, zero3_xray):
        """The ZeRO-3 step moves real bytes: all-gather (params) and
        all-reduce/reduce-scatter (grads) both present, totals > 0,
        and the engine-attribution helper agrees with the table."""
        _, _, result, _, static = zero3_xray
        c = result.comm["engine/train_batch[gas=1]"]
        assert c["total_bytes"] > 0 and c["collectives"] > 0
        assert "all-gather" in c["by_kind"] and "all-reduce" in c["by_kind"]
        assert static["static_comm_bytes"] == c["total_bytes"]

    def test_train_donation_survives_compile(self, zero3_xray):
        """The engine's donate_argnums=(0,) actually aliases: no
        donation-dropped finding, and the alias table is non-empty."""
        _, _, result, _, _ = zero3_xray
        tr = result.program("engine/train_batch")
        assert tr.model.aliases, "train step produced no input-output alias"
        assert not [f for f in result.findings
                    if f.rule == "xray/donation-dropped"]

    def test_deadlock_revert_fixture_fires(self, zero3_xray):
        """THE PR-12 deadlock as a permanent lint: a generate-shaped
        program compiled with INHERITED shardings over operands committed
        to a differently-ordered mesh (the seed-era hybrid ``generate()``
        had no in_shardings, so placement — and the collective device
        order — came from wherever its operands happened to live) makes
        ``xray/collective-order`` fire naming BOTH programs and their
        replica groups; restoring explicit shardings on THE mesh makes it
        clean again."""
        engine, _, _, records, _ = zero3_xray
        from deepspeed_tpu.analysis.xray import run_xray
        from deepspeed_tpu.sharding import INHERIT, sharded_jit
        from deepspeed_tpu.sharding.jit import _LOCK, _PROGRAMS

        perm = list(range(8))
        perm[1], perm[5] = perm[5], perm[1]
        scrambled = Mesh(np.array(jax.devices())[perm].reshape(8), ("data",))

        def gen_like(w, ids):
            h = jnp.ones((ids.shape[0], w.shape[0]), jnp.float32) \
                * ids.sum().astype(jnp.float32)
            return (h @ w).sum(axis=-1)

        w = jax.device_put(jnp.ones((256, 64)),
                           NamedSharding(scrambled, P("data")))
        ids = jax.device_put(jnp.ones((8, 4), jnp.int32),
                             NamedSharding(scrambled, P()))
        bad = sharded_jit(gen_like, label="hybrid/generate[reverted]",
                          donate_argnums=(), mesh=scrambled,
                          in_shardings=INHERIT, out_shardings=INHERIT)
        try:
            bad(w, ids)
            result = run_xray(records + [bad.program_record],
                              plan=engine.plan)
            hits = [f for f in result.findings
                    if f.rule == "xray/collective-order"]
            assert hits, "reverted-shardings generate did not fire"
            joined = " ".join(f.message for f in hits)
            assert "hybrid/generate[reverted]" in joined
            assert "engine/train_batch[gas=1]" in joined
            assert "{" in joined      # replica groups are named
        finally:
            with _LOCK:
                _PROGRAMS.pop("hybrid/generate[reverted]", None)
        # the fix (explicit shardings on THE mesh) is the tree we run on:
        # with the reverted program gone, the fleet is clean again
        clean = run_xray(records, plan=engine.plan)
        assert not [f for f in clean.findings
                    if f.rule == "xray/collective-order"]


# -------------------------------------------------------- static-comm gate
@pytest.mark.analysis
@pytest.mark.perf
class TestStaticCommGate:
    def _entry(self, bytes_, value=0.5):
        return {"metric": "m pretrain MFU (x)", "value": value,
                "unit": "MFU", "samples": [0.1, 0.1, 0.1],
                "fingerprint": "f", "headline": True,
                "attribution": {"static_comm_bytes": bytes_}}

    def test_compare_flags_growth_past_floor(self):
        from deepspeed_tpu.perf.ledger import compare

        r = compare(self._entry(10 << 20), self._entry(30 << 20))
        assert r["static_comm_regressed"]
        # sub-floor growth is not a regression
        r2 = compare(self._entry(10 << 20), self._entry((10 << 20) + 1024))
        assert not r2["static_comm_regressed"]
        # improvement direction never flags
        r3 = compare(self._entry(30 << 20), self._entry(10 << 20))
        assert not r3["static_comm_regressed"]

    def test_gate_cli_fails_synthetic_regression(self, tmp_path):
        from deepspeed_tpu.perf.cli import main as perf_main

        base = tmp_path / "base.jsonl"
        cand = tmp_path / "cand.jsonl"
        base.write_text(json.dumps(self._entry(10 << 20)) + "\n")
        cand.write_text(json.dumps(self._entry(40 << 20)) + "\n")
        rc = perf_main(["gate", "--baseline", str(base), "--candidate",
                        str(cand), "--metric", "static_comm_bytes"])
        assert rc == 2
        ok = perf_main(["gate", "--baseline", str(base), "--candidate",
                        str(base), "--metric", "static_comm_bytes"])
        assert ok == 0

    def test_gate_missing_attribution_is_missing_not_pass(self, tmp_path):
        from deepspeed_tpu.perf.cli import main as perf_main

        base = tmp_path / "base.jsonl"
        cand = tmp_path / "cand.jsonl"
        base.write_text(json.dumps(self._entry(10 << 20)) + "\n")
        bare = self._entry(0)
        del bare["attribution"]
        cand.write_text(json.dumps(bare) + "\n")
        rc = perf_main(["gate", "--baseline", str(base), "--candidate",
                        str(cand), "--metric", "static_comm_bytes"])
        assert rc == 3


# ------------------------------------------------------- script-lint satellite
@pytest.mark.analysis
class TestScriptLint:
    def test_repo_scripts_are_covered(self):
        """bin/* is the unspecified-jit lint's script scan set
        (the zero-findings assertion over the whole set lives in
        tests/unit/test_sharding.py)."""
        import deepspeed_tpu as pkg
        from deepspeed_tpu.analysis.jit_lint import repo_script_paths

        root = os.path.dirname(os.path.abspath(pkg.__file__))
        names = {os.path.basename(p) for p in repo_script_paths(root)}
        assert {"ds_perf", "ds_doctor", "ds_multichip"} <= names
        assert names <= set(os.listdir(os.path.join(
            os.path.dirname(root), "bin")))

    def test_bare_jit_in_script_flagged(self):
        from deepspeed_tpu.analysis.jit_lint import lint_jit_source

        src = "import jax\n\ndef run():\n    return jax.jit(lambda x: x)\n"
        fs = lint_jit_source(src, "bin/ds_example")
        assert fs and fs[0].rule == "sharding/unspecified-jit"
        assert "run" in fs[0].message


# ------------------------------------------------------------- slow matrix
@pytest.mark.analysis
@pytest.mark.multichip
class TestXrayMatrix:
    """Zero-false-positive matrix over the family fixtures and the
    pipe/SP gate topologies + the injected-regression/dropped-donation
    drills (full AOT lowering per case — tests/slow_tests.txt)."""

    def _xray_engine(self, engine, batch, **kw):
        from deepspeed_tpu.analysis.xray import run_xray

        engine.train_batch(batch)
        return run_xray(plan=getattr(engine, "plan", None), **kw)

    def test_family_matrix_zero_findings(self):
        from deepspeed_tpu.models.registry import resolve_family

        for preset in ("gpt2-tiny", "llama-tiny", "bert-tiny"):
            _reset()
            model_cls, make_batch, presets = resolve_family(preset)
            mcfg = presets[preset]
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=model_cls(mcfg),
                config={"train_batch_size": 8,
                        "optimizer": {"type": "AdamW",
                                      "params": {"lr": 1e-3}},
                        "bf16": {"enabled": True},
                        "zero_optimization": {
                            "stage": 3,
                            "stage3_param_persistence_threshold": 0},
                        "tpu": {"data": 8}, "steps_per_print": 0})
            batch = make_batch(8, 32, mcfg.vocab_size)
            result = self._xray_engine(engine, batch)
            bad = [f for f in result.findings if f.severity != "info"]
            assert not bad, (preset, [str(f) for f in bad])
            tr = result.program("engine/train_batch")
            assert tr is not None and tr.total_comm_bytes > 0, preset

    def test_moe_expert_parallel_zero_findings(self):
        from deepspeed_tpu.models.gpt2_moe import MoEGPT2

        _reset()
        cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                         n_layer=2, n_head=4, remat=True,
                         use_flash_attention=False)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=MoEGPT2(cfg, num_experts=8, ep_size=4),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {
                        "stage": 3,
                        "stage3_param_persistence_threshold": 0},
                    "tpu": {"data": 2, "expert": 4}, "steps_per_print": 0})
        result = self._xray_engine(
            engine, synthetic_lm_batch(8, 32, cfg.vocab_size, seed=2))
        bad = [f for f in result.findings if f.severity != "info"]
        assert not bad, [str(f) for f in bad]
        c = result.comm["engine/train_batch[gas=1]"]
        assert "all-to-all" in c["by_kind"]     # the ep dispatch is visible
        _reset()

    def test_pipe_and_ring_sp_zero_findings(self):
        from deepspeed_tpu.models.gpt2_pipe import PipelinedGPT2

        _reset()
        pcfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                          n_layer=4, n_head=4, remat=True,
                          use_flash_attention=False, rotary_pct=0.25,
                          parallel_residual=True)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=PipelinedGPT2(pcfg, num_stages=2, num_micro=4,
                                schedule="1f1b"),
            config={"train_batch_size": 16,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {
                        "stage": 3,
                        "stage3_param_persistence_threshold": 0},
                    "tpu": {"pipe": 2, "tensor": 2, "data": 2},
                    "steps_per_print": 0})
        result = self._xray_engine(
            engine, synthetic_lm_batch(16, 32, pcfg.vocab_size, seed=1))
        bad = [f for f in result.findings if f.severity != "info"]
        assert not bad, [str(f) for f in bad]
        c = result.comm["engine/train_batch[gas=1]"]
        assert "collective-permute" in c["by_kind"]   # the stage shifts

        _reset()
        scfg = GPT2Config(vocab_size=256, n_positions=128, n_embd=64,
                          n_layer=2, n_head=4, remat=True,
                          use_flash_attention=False,
                          sequence_parallel="ring")
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2Model(scfg),
            config={"train_batch_size": 4,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "bf16": {"enabled": True},
                    "zero_optimization": {"stage": 1},
                    "tpu": {"data": 2, "seq": 4}, "steps_per_print": 0})
        result = self._xray_engine(
            engine, synthetic_lm_batch(4, 128, scfg.vocab_size, seed=3))
        bad = [f for f in result.findings if f.severity != "info"]
        assert not bad, [str(f) for f in bad]
        _reset()

    def test_injected_replicated_spec_regression_caught(self):
        """THE acceptance drill: a train program whose state shardings
        regressed to replicated (registry spec regression or call-site
        override) is caught by xray/promise-vs-actual — the stage
        promises dp-partitioned state, the compiled HLO says replicated."""
        from deepspeed_tpu.analysis.xray import run_xray
        from deepspeed_tpu.runtime.engine import TrainState
        from deepspeed_tpu.sharding import INHERIT, sharded_jit

        _reset()
        engine, cfg = _mk_engine()
        batch = synthetic_lm_batch(8, 32, cfg.vocab_size, seed=0)
        engine.train_batch(batch)
        repl = engine.sharding.replicated()
        is_sh = lambda x: x is None or hasattr(x, "spec")
        repl_state = jax.tree.map(lambda s: repl, engine.state_shardings,
                                  is_leaf=is_sh)
        fn = engine._build_train_batch_fn(1)
        injected = sharded_jit(
            fn, label="engine/train_batch[injected]",
            donate_argnums=(), mesh=engine.mesh,
            in_shardings=(repl_state, INHERIT),
            out_shardings=(repl_state, repl),
            meta={"state_argnum": 0,
                  "state_fields": list(TrainState._fields)})
        state_repl = jax.device_put(engine.state, repl_state)
        with engine.mesh:
            injected(state_repl, engine._shard_batch(batch))
        result = run_xray([injected.program_record], plan=engine.plan,
                          min_replicated_elements=1000)
        hits = [f for f in result.findings
                if f.rule == "xray/promise-vs-actual"]
        assert hits, "replicated-spec regression not caught"
        joined = " ".join(f.message for f in hits)
        assert "replicated" in joined and "ZeRO stage 3" in joined
        _reset()

    def test_donation_dropped_fixture(self):
        """A donated buffer whose every output changed dtype produces no
        alias — xray/donation-dropped names the program and the bytes."""
        from deepspeed_tpu.analysis.xray import run_xray
        from deepspeed_tpu.sharding import sharded_jit
        from deepspeed_tpu.sharding.mesh import ensure_global_mesh

        _reset()
        mesh = ensure_global_mesh(axis_dims={"data": 8})
        sh = NamedSharding(mesh, P("data"))

        def step(w, x):
            return (w + 1).astype(jnp.bfloat16), x.sum()

        prog = sharded_jit(step, label="fixture/dropped_donation",
                           donate_argnums=(0,), mesh=mesh,
                           in_shardings=(sh, sh),
                           out_shardings=(sh, NamedSharding(mesh, P())))
        w = jax.device_put(jnp.ones((1024, 256), jnp.float32), sh)
        x = jax.device_put(jnp.ones((8, 8), jnp.float32), sh)
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            prog(w, x)
        result = run_xray([prog.program_record], min_donate_bytes=1024)
        hits = [f for f in result.findings
                if f.rule == "xray/donation-dropped"]
        assert hits and "fixture/dropped_donation" in hits[0].message
        _reset()

    def test_engine_hook_runs_xray_when_named(self):
        """analysis.passes=[..., "xray"] runs the pass after the FIRST
        train_batch and stamps engine._xray_result; the default pass set
        never does (one AOT compile per program is opt-in)."""
        _reset()
        engine, cfg = _mk_engine(extra={"analysis": {
            "passes": ["schema", "sharding", "graph", "collectives",
                       "xray"]}})
        batch = synthetic_lm_batch(8, 32, cfg.vocab_size, seed=0)
        engine.train_batch(batch)
        assert engine._analysis_xray_done
        result = getattr(engine, "_xray_result", None)
        assert result is not None
        assert result.program("engine/train_batch") is not None

        _reset()
        engine, cfg = _mk_engine(extra={"analysis": {}})
        engine.train_batch(synthetic_lm_batch(8, 32, cfg.vocab_size))
        assert not engine._analysis_xray_done
        assert getattr(engine, "_xray_result", None) is None
        _reset()
