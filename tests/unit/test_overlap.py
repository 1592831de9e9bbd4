"""Overlap-engine tests (runtime/overlap.py + the ``overlap`` ds_config
block): the serial (measured un-overlapped) schedule must not change the
math and must expose the ZeRO-3 gather as comm spans the fused step keeps
inside one program, billed at the bytes of exactly the sharded leaves; the
collective fingerprints must cover the step; the async checkpoint snapshot
must survive the next step's donation; the keys of the prefetch ring that
PR 44 removed must be refused by name — and the block, absent or present
with ``schedule: "overlapped"``, must leave the train step's text alone."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model, synthetic_lm_batch

# micro GPT-2: every dim divisible by the 8-way dp world, seconds to
# compile on the CPU test mesh
MCFG = GPT2Config(vocab_size=256, n_positions=32, n_embd=32, n_layer=2,
                  n_head=2, remat=False, use_flash_attention=False)
SEQ, BS = 32, 8


def base_config(**over):
    cfg = {
        "train_batch_size": BS,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 0},
        "steps_per_print": 0,
    }
    cfg.update(over)
    return cfg


def make_engine(**over):
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2Model(MCFG),
                                               config=base_config(**over))
    return engine


def lm_batch(seed=0):
    return synthetic_lm_batch(BS, SEQ, MCFG.vocab_size, seed=seed)


def train_losses(engine, steps=3):
    batch = lm_batch()
    return [float(engine.train_batch(batch)) for _ in range(steps)]


# ---------------------------------------------------------------------------
# engine schedules: numerics, the gather phase's bill
# ---------------------------------------------------------------------------
@pytest.mark.overlap
class TestEngineSchedules:
    def test_schedules_match_baseline_losses(self):
        l_base = train_losses(make_engine())
        l_over = train_losses(make_engine(overlap={}))
        l_serial = train_losses(make_engine(overlap={"schedule": "serial"}))
        # the block alone changes no program; the serial schedule is the
        # same math in two programs: only float reassociation (gathered vs
        # sharded reduction order) may differ
        assert l_base == l_over
        np.testing.assert_allclose(l_base, l_over, rtol=2e-3)
        np.testing.assert_allclose(l_base, l_serial, rtol=2e-3)

    @pytest.mark.parametrize("family", ["gpt2", "llama-routed"])
    def test_serial_gather_phase_bills_the_sharded_leaves(self, family):
        """The ``zero3_gather`` span's bytes: every leaf the plan's rule
        (``LayerGathers``) names, over all its layers, plus the sharded
        leaves outside the stacks — and no other leaf."""
        from deepspeed_tpu.runtime.zero.partition import stacked_param_keys

        if family == "gpt2":
            model = GPT2Model(MCFG)
        else:
            from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

            model = LlamaModel(LlamaConfig(
                vocab_size=256, n_positions=32, n_embd=32, n_layer=3,
                n_head=2, intermediate_size=64, n_experts=4,
                n_experts_per_tok=2, n_dense_layers=1, remat=False,
                use_flash_attention=False))
        engine, *_ = deepspeed_tpu.initialize(model=model, config=base_config(
            zero_optimization={"stage": 3,
                               "stage3_param_persistence_threshold": 200},
            overlap={"schedule": "serial", "scheduler_flags": False,
                     "async_checkpoint": False}))
        engine.train_batch(lm_batch())
        rule = engine.plan.layer_gathers
        assert rule is not None and engine._layer_gathers is None
        nbytes = lambda x: int(np.prod(x.shape)) * x.dtype.itemsize
        keys = stacked_param_keys(model)
        assert len(keys) == (1 if family == "gpt2" else 2)
        want = named = 0
        for key, sub in engine.state.params.items():
            flat = jax.tree_util.tree_flatten_with_path(sub)[0]
            specs = jax.tree.leaves(engine.plan.param_specs[key],
                                    is_leaf=lambda x: isinstance(x, P))
            for (path, leaf), spec in zip(flat, specs):
                if key in keys:
                    forms = rule.leaves.get(path[-1].key, ())
                    hit = any(tuple(leaf.shape[1:]) == f[0] for f in forms)
                    named += hit
                else:
                    hit = "data" in str(spec)
                want += nbytes(leaf) if hit else 0
        # the threshold splits a stack: weights named, norms and biases not
        assert 0 < named < sum(
            len(jax.tree.leaves(engine.state.params[k])) for k in keys)
        assert engine._overlap._gather_bytes == want
        total = sum(nbytes(x) for x in jax.tree.leaves(engine.state.params))
        assert want < total

    def test_serial_degrades_when_nothing_sharded(self, tmp_path):
        """schedule='serial' below stage 3 has no gather to expose: the
        engine runs the fused step instead of dispatching empty phases."""
        from deepspeed_tpu import telemetry

        engine = make_engine(
            zero_optimization={"stage": 1},
            overlap={"schedule": "serial"},
            telemetry={"enabled": True, "output_dir": str(tmp_path / "t"),
                       "prometheus": False, "flush_interval": 100000})
        try:
            losses = train_losses(engine, steps=2)
            assert losses[1] < losses[0]
            assert engine._overlap.schedule == "overlapped"
            assert not [e for e in telemetry.get_session().tracer.events
                        if e.get("cat") == "comm"]
        finally:
            telemetry.deconfigure()

    def test_serial_gather_registers_with_doctor(self):
        """PR 4 collective fingerprints cover the fused step with the
        block present: deterministic across engines of the same config,
        different from a step's that states no gather (ZeRO-2; what the
        ZeRO-3 step states: tests/unit/test_zero3_gather.py)."""
        fps = []
        for _ in range(2):
            e = make_engine(overlap={}, analysis={"fail_on": "error"})
            e.train_batch(lm_batch())
            assert e._collective_fingerprint is not None
            fps.append(e._collective_fingerprint)
        assert fps[0] == fps[1]
        e = make_engine(analysis={"fail_on": "error"},
                        zero_optimization={"stage": 2})
        e.train_batch(lm_batch())
        assert e._collective_fingerprint != fps[0]

    def test_collective_mismatch_chaos_drills_overlapped_schedule(self):
        """The deadlock detector still names a divergent rank when the
        sequence is the fused step's gather records (the layer stack's
        rule: the block being present changes nothing of them)."""
        from deepspeed_tpu.analysis.collectives import (diff_sequences,
                                                        record_collectives)
        from deepspeed_tpu.resilience.chaos import ChaosInjector

        def records(engine):
            fn = engine._build_train_batch_fn(1)
            abstract = lambda tree: jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
            batch = engine._shard_batch(lm_batch())
            with engine.mesh:
                with record_collectives(apply_chaos=False) as rec:
                    jax.make_jaxpr(fn)(abstract(engine.state),
                                       abstract(batch))
            return rec

        rec = records(make_engine(overlap={}))
        assert any(r.op == "zero3_gather" for r in rec.records)
        assert [(r.op, r.shape, r.axes) for r in rec.records] == \
            [(r.op, r.shape, r.axes) for r in records(make_engine()).records]
        inj = ChaosInjector(seed=3, collective_mismatch=True)
        perturbed = inj.perturb_collectives(rec.records, rank=1)
        findings = diff_sequences({0: list(rec.records), 1: perturbed})
        assert findings and findings[0].rule == "collectives/sequence-mismatch"


# ---------------------------------------------------------------------------
# THE acceptance: the serial schedule exposes as comm spans what the default
# fused step keeps inside one program
# ---------------------------------------------------------------------------
@pytest.mark.overlap
class TestExposedCommDelta:
    def _run(self, tmp_path, name, schedule, ledger):
        from deepspeed_tpu import telemetry

        engine = make_engine(
            overlap={"schedule": schedule},
            telemetry={"enabled": True, "output_dir": str(tmp_path / name),
                       "prometheus": False, "flush_interval": 100000},
            goodput={},
            perf={"ledger_path": str(ledger)})
        try:
            for _ in range(4):
                engine.train_batch(lm_batch())
            events = list(telemetry.get_session().tracer.events)
            entry = engine.perf_record(
                f"overlap-drill ({schedule})", 1.0, "MFU",
                config={"schedule": schedule}, timed_steps=3)
        finally:
            telemetry.deconfigure()
        return events, entry

    def test_serial_vs_overlapped(self, tmp_path):
        ledger = tmp_path / "led.jsonl"
        ev_s, e_serial = self._run(tmp_path, "serial", "serial", ledger)
        ev_o, e_over = self._run(tmp_path, "over", "overlapped", ledger)

        # the serial schedule's gather phase lands as rank-matchable comm
        # spans with the (op, seq, group) identity ds_prof merge aligns on
        comm = [e for e in ev_s if e.get("cat") == "comm"]
        assert comm and all(e["args"]["op"] == "zero3_gather" for e in comm)
        assert {e["args"]["seq"] for e in comm} == set(range(len(comm)))
        assert comm[0]["args"]["bytes"] > 0
        assert not [e for e in ev_o if e.get("cat") == "comm"]

        exp_s = (e_serial["attribution"] or {})["exposed_comm_us_per_step"]
        exp_o = (e_over["attribution"] or {})["exposed_comm_us_per_step"]
        assert exp_s > 0.0
        assert exp_o < exp_s, (exp_o, exp_s)

        # the goodput block prices it too: exposed_comm badput > 0 only
        # on the serial side
        gp_s = e_serial["attribution"]["goodput"]["buckets_us"]
        gp_o = e_over["attribution"]["goodput"]["buckets_us"]
        assert gp_s.get("exposed_comm", 0.0) > 0.0
        assert gp_o.get("exposed_comm", 0.0) == 0.0

        # the same number through ds_prof merge's fleet math
        from deepspeed_tpu.profiling.aggregate import FleetTrace

        ft = FleetTrace()
        ft.add_rank(0, ev_s)
        summary = ft.exposed_comm_summary(align=False)
        assert summary["avg_us_per_step"] > 0

        # two ledger entries on disk, gateable: growing exposed comm back
        # (overlapped -> serial) fails `ds_perf gate --metric exposed_comm`
        from deepspeed_tpu.perf import ledger as led

        entries = led.load_entries(str(ledger))
        assert len(entries) == 2
        r = led.compare(entries[1], entries[0])   # new = serial
        assert r["exposed_comm_regressed"]
        r2 = led.compare(entries[0], entries[1])  # new = overlapped
        assert not r2["exposed_comm_regressed"]

    def test_gate_metric_exposed_comm_cli(self, tmp_path):
        from deepspeed_tpu.perf.cli import main as perf_main

        def entry(exposed, fname):
            e = {"metric": "drill MFU (x)", "value": 1.0, "unit": "MFU",
                 "samples": [1.0, 1.0, 1.0], "fingerprint": "f",
                 "attribution": {"exposed_comm_us_per_step": exposed},
                 "headline": True}
            p = tmp_path / fname
            p.write_text(json.dumps(e) + "\n")
            return str(p)

        good = entry(0.0, "good.jsonl")
        bad = entry(20000.0, "bad.jsonl")
        assert perf_main(["gate", "--baseline", good, "--candidate", bad,
                          "--metric", "exposed_comm"]) == 2
        assert perf_main(["gate", "--baseline", bad, "--candidate", good,
                          "--metric", "exposed_comm"]) == 0
        # gating ON the metric with no attribution recorded = missing, not
        # a silent pass
        plain = tmp_path / "plain.jsonl"
        plain.write_text(json.dumps({"metric": "drill MFU (x)", "value": 1.0,
                                     "unit": "MFU", "headline": True}) + "\n")
        assert perf_main(["gate", "--baseline", good,
                          "--candidate", str(plain),
                          "--metric", "exposed_comm"]) == 3


# ---------------------------------------------------------------------------
# chaos `collective` target
# ---------------------------------------------------------------------------
@pytest.mark.overlap
@pytest.mark.chaos
class TestChaosCollectiveTarget:
    def test_delay_inflates_eager_collective_span(self, tmp_path):
        from deepspeed_tpu import comm as dist
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.resilience import chaos as chaos_mod
        from deepspeed_tpu.runtime.config import TelemetryConfig

        dist.init_distributed(verbose=False)
        session = telemetry.configure(TelemetryConfig(
            enabled=True, output_dir=str(tmp_path / "t"), prometheus=False,
            flush_interval=100000))
        inj = chaos_mod.ChaosInjector(delay_at={"collective": [1]},
                                      max_delay_s=0.15)
        chaos_mod.install_chaos(inj)
        try:
            x = np.ones((8, 4), np.float32)
            dist.all_reduce(jnp.asarray(x))
            spans = [e for e in session.tracer.events
                     if e.get("cat") == "comm"]
            assert spans and spans[0]["dur"] >= 0.15 * 1e6
            assert any(op == "collective" and "delay" in act
                       for op, act, _ in inj.log)
        finally:
            chaos_mod.uninstall_chaos()
            telemetry.deconfigure()

    def test_fires_without_telemetry(self):
        """A watchdog drill without a telemetry block must still inject:
        the target fires on the untimed eager path too."""
        from deepspeed_tpu import comm as dist
        from deepspeed_tpu.resilience import chaos as chaos_mod

        dist.init_distributed(verbose=False)
        inj = chaos_mod.ChaosInjector(delay_at={"collective": [1]},
                                      max_delay_s=0.01)
        chaos_mod.install_chaos(inj)
        try:
            dist.all_reduce(jnp.ones((8, 4), jnp.float32))
            assert any(op == "collective" and "delay" in act
                       for op, act, _ in inj.log)
        finally:
            chaos_mod.uninstall_chaos()

    def test_serial_gather_phase_takes_the_delay(self, tmp_path):
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.resilience import chaos as chaos_mod

        engine = make_engine(
            overlap={"schedule": "serial"},
            telemetry={"enabled": True, "output_dir": str(tmp_path / "t"),
                       "prometheus": False, "flush_interval": 100000})
        inj = chaos_mod.ChaosInjector(delay_at={"collective": [3]},
                                      max_delay_s=0.5)
        chaos_mod.install_chaos(inj)
        try:
            engine.train_batch(lm_batch())   # collective #1: dispatch warm-up
            engine.train_batch(lm_batch())   # collective #2: warm baseline
            engine.train_batch(lm_batch())   # collective #3: +0.5s delay
            spans = [e for e in telemetry.get_session().tracer.events
                     if e.get("cat") == "comm"]
            assert len(spans) == 3
            # warm-vs-warm comparison: collective #1 pays one-time dispatch
            # cost (>0.1 s under a loaded suite) and must not be the baseline
            assert spans[2]["dur"] - spans[1]["dur"] >= 0.3 * 1e6
        finally:
            chaos_mod.uninstall_chaos()
            telemetry.deconfigure()


# ---------------------------------------------------------------------------
# async checkpoint snapshot
# ---------------------------------------------------------------------------
@pytest.mark.overlap
class TestAsyncCheckpointSnapshot:
    def test_roundtrip_survives_donation(self, tmp_path):
        from deepspeed_tpu.runtime.checkpoint_engine.engine import \
            wait_for_pending_saves

        engine = make_engine(overlap={})
        l1 = float(engine.train_batch(lm_batch()))
        engine.save_checkpoint(str(tmp_path / "ck"), tag="t1")
        # the NEXT step donates the live state's buffers while the
        # background thread is still copying/writing the snapshot
        l2 = float(engine.train_batch(lm_batch()))
        wait_for_pending_saves()
        assert os.path.exists(tmp_path / "ck" / "latest")
        assert os.path.exists(tmp_path / "ck" / "t1" / "manifest.json")
        path, _ = engine.load_checkpoint(str(tmp_path / "ck"))
        assert path is not None and int(engine.state.step) == 1
        # replaying the step from the restored snapshot reproduces it
        l2b = float(engine.train_batch(lm_batch()))
        assert l2b == pytest.approx(l2, rel=1e-5)

    def test_background_span_not_charged_as_badput(self, tmp_path):
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.goodput.taxonomy import span_bucket
        from deepspeed_tpu.runtime.checkpoint_engine.engine import \
            wait_for_pending_saves

        engine = make_engine(
            overlap={},
            telemetry={"enabled": True, "output_dir": str(tmp_path / "t"),
                       "prometheus": False, "flush_interval": 100000})
        try:
            engine.train_batch(lm_batch())
            engine.save_checkpoint(str(tmp_path / "ck"))
            engine.train_batch(lm_batch())
            wait_for_pending_saves()
            events = list(telemetry.get_session().tracer.events)
        finally:
            telemetry.deconfigure()
        bg = [e for e in events if e.get("name") == "checkpoint_commit_async"]
        assert bg and all(span_bucket(e) is None for e in bg)
        # the on-path save_checkpoint span is the snapshot copy only —
        # still classified as checkpoint, but it no longer contains the
        # device->host transfer or the filesystem write
        on_path = [e for e in events if e.get("name") == "save_checkpoint"]
        assert on_path and span_bucket(on_path[0]) == "checkpoint"
        assert on_path[0]["dur"] < bg[0]["dur"] + on_path[0]["dur"]

    def test_sync_path_untouched_without_async(self, tmp_path):
        engine = make_engine(overlap={"async_checkpoint": False})
        engine.train_batch(lm_batch())
        engine.save_checkpoint(str(tmp_path / "ck"), tag="t1")
        from deepspeed_tpu.runtime.checkpoint_engine.engine import \
            wait_for_pending_saves

        wait_for_pending_saves()
        path, _ = engine.load_checkpoint(str(tmp_path / "ck"))
        assert path is not None


# ---------------------------------------------------------------------------
# strict no-op + config surface
# ---------------------------------------------------------------------------
@pytest.mark.overlap
class TestStrictNoOp:
    def test_block_absent_never_imports_module(self):
        mods = [m for m in list(sys.modules)
                if m == "deepspeed_tpu.runtime.overlap"]
        saved = {m: sys.modules.pop(m) for m in mods}
        try:
            engine = make_engine()
            engine.train_batch(lm_batch())
            assert engine._overlap is None
            assert "deepspeed_tpu.runtime.overlap" not in sys.modules
        finally:
            sys.modules.update(saved)

    def test_block_absent_step_is_byte_identical(self):
        """The compiled-step cache key contract: an engine without the
        block and one with ``enabled: false`` lower the EXACT same step
        program (same HLO text), and ``layer_scan`` traces identically to
        a direct ``lax.scan``."""
        import jax.numpy as jnp

        from deepspeed_tpu.models import common as mcommon

        def body(c, x):
            return c + x, None

        xs = jnp.arange(6.0).reshape(3, 2)
        j1 = jax.make_jaxpr(
            lambda xs: mcommon.layer_scan(body, jnp.zeros(2), xs))(xs)
        j2 = jax.make_jaxpr(
            lambda xs: jax.lax.scan(body, jnp.zeros(2), xs))(xs)
        assert str(j1) == str(j2)

        t_absent = self._lowered(make_engine())
        t_disabled = self._lowered(make_engine(overlap={"enabled": False}))
        assert t_absent == t_disabled

    @staticmethod
    def _lowered(engine):
        abstract = lambda tree: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), tree)
        batch = engine._shard_batch(lm_batch())
        with engine.mesh:
            return engine._get_compiled_train_batch(1).lower(
                abstract(engine.state), abstract(batch)).as_text()

    @pytest.mark.parametrize("stage", [0, 2, 3])
    def test_block_present_overlapped_step_is_the_step_without_it(self, stage):
        """``schedule: "overlapped"`` names the engine's one fused step:
        the block arms the scheduler preset and the async snapshot, and
        the train step's text is the text without the block (at stage 3
        the same stated gather; until PR 44 the block swapped it)."""
        zero = {"stage": stage, "stage3_param_persistence_threshold": 0}
        engine = make_engine(zero_optimization=zero, overlap={})
        assert engine._overlap.schedule == "overlapped"
        assert engine._layer_gathers is engine.plan.layer_gathers
        assert (engine._layer_gathers is not None) == (stage == 3)
        assert self._lowered(engine) == \
            self._lowered(make_engine(zero_optimization=zero))

    def test_enabled_false_is_noop(self):
        engine = make_engine(overlap={"enabled": False})
        engine.train_batch(lm_batch())
        assert engine._overlap is None

    def test_unknown_key_rejected_with_hint(self):
        with pytest.raises(ValueError, match="did you mean 'schedule'"):
            make_engine(overlap={"schedul": "serial"})

    @pytest.mark.parametrize("key,block", [
        ("param_prefetch", {"overlap": {"param_prefetch": 1}}),
        ("grad_reduce", {"overlap": {"grad_reduce": "post"}}),
        ("remat_gather", {"overlap": {"remat_gather": False}}),
        ("wire", {"wire": {"weight_quant_bits": 8}})])
    def test_removed_keys_refused_at_parse(self, key, block):
        """The prefetch ring's keys and the ``wire`` block that rode it
        went at PR 44: a config that still sets one is refused when it is
        parsed, by name, before any engine exists."""
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        with pytest.raises(ValueError, match=repr(key)):
            DeepSpeedConfig(base_config(**block))
        assert DeepSpeedConfig(base_config(overlap={})).overlap.model_dump() \
            == {"enabled": True, "schedule": "overlapped",
                "scheduler_flags": True, "async_checkpoint": True}

    def test_schema_cross_fields(self):
        from deepspeed_tpu.analysis.schema import walk_config

        findings, _ = walk_config(base_config(
            overlap={"schedule": "serial"}), world_size=8)
        assert any("telemetry" in f.citation and "overlap" in f.citation
                   for f in findings)
        findings, _ = walk_config(base_config(
            zero_optimization={"stage": 3, "offload_param": {"device": "cpu"}},
            overlap={}), world_size=8)
        assert any(f.citation == "overlap vs zero_optimization.offload_param"
                   and "serial schedule" in f.message for f in findings)
        # a removed key is an unknown key to the doctor too, and the two
        # rules above are all the block has
        findings, _ = walk_config(base_config(
            zero_optimization={"stage": 1},
            overlap={"param_prefetch": 2}), world_size=8)
        assert any("param_prefetch" in f.message
                   and f.rule == "config/unknown-key" for f in findings)
        findings, _ = walk_config(base_config(
            zero_optimization={"stage": 1}, overlap={}), world_size=8)
        assert not [f for f in findings if "overlap" in f.citation]
        findings, _ = walk_config(base_config(
            overlap={"schedul": "serial"}), world_size=8)
        assert any("schedule" in f.message and f.rule == "config/unknown-key"
                   for f in findings)

    def test_invalid_schedule_rejected(self):
        with pytest.raises(ValueError, match="overlapped"):
            make_engine(overlap={"schedule": "sideways"})


# ---------------------------------------------------------------------------
# partition_report one-chip blind spot
# ---------------------------------------------------------------------------
@pytest.mark.overlap
def test_partition_report_explains_one_chip():
    from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
    from deepspeed_tpu.runtime.zero.partition import (partition_report,
                                                      plan_sharding)

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    shapes = jax.eval_shape(lambda: {"w": jnp.zeros((64, 64))})
    plan = plan_sharding(shapes, mesh,
                         zero_config=DeepSpeedZeroConfig(stage=3))
    msg = partition_report(plan, shapes)
    assert "world size 1" in msg
    assert "not a sharding bug" in msg
    assert "0.0% dp-sharded over axes ()" not in msg


@pytest.mark.overlap
def test_partition_report_normal_mesh_unchanged():
    from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
    from deepspeed_tpu.runtime.zero.partition import (partition_report,
                                                      plan_sharding)

    mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
    shapes = jax.eval_shape(lambda: {"w": jnp.zeros((64, 64))})
    plan = plan_sharding(
        shapes, mesh,
        zero_config=DeepSpeedZeroConfig(
            **{"stage": 3, "stage3_param_persistence_threshold": 0}))
    assert "100.0% dp-sharded over axes ('data',)" in \
        partition_report(plan, shapes)


# ---------------------------------------------------------------------------
# scheduler flags + ds_report
# ---------------------------------------------------------------------------
@pytest.mark.overlap
class TestSchedulerFlags:
    def test_not_applied_off_tpu(self, monkeypatch):
        from deepspeed_tpu.runtime import overlap as ov

        before = os.environ.get("XLA_FLAGS", "")
        assert ov.apply_scheduler_flags() == []
        assert os.environ.get("XLA_FLAGS", "") == before

    def test_applied_on_tpu_env(self, monkeypatch):
        from deepspeed_tpu.runtime import overlap as ov

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
        added = ov.apply_scheduler_flags()
        assert added and all(f.split("=")[0] in os.environ["XLA_FLAGS"]
                             for f in ov.SCHEDULER_FLAG_PRESET)
        # idempotent
        assert ov.apply_scheduler_flags() == []

    def test_ds_report_section(self):
        from deepspeed_tpu.env_report import overlap_report

        rows = dict(overlap_report())
        assert rows["backend"] == "cpu"
        assert "tpu_enable_latency_hiding_scheduler" in rows


# ---------------------------------------------------------------------------
# the serial schedule's ledger entry (the CI-measurable delta, end to end)
# ---------------------------------------------------------------------------
@pytest.mark.overlap
@pytest.mark.perf
def test_serial_schedule_entry_prices_exposed_comm(tmp_path, tiny_ledger_run):
    """gpt2-tiny as a real ZeRO-3 job over 4 simulated devices under
    `overlap.schedule: "serial"`: the gather phase lands as comm spans, so
    the engine's ledger entry carries a nonzero exposed-comm attribution."""
    _, entry = tiny_ledger_run(tmp_path, devices=4,
                               extra={"overlap": {"schedule": "serial"}})
    assert entry["mesh_axes"] == "data=4"
    assert entry["attribution"].get("exposed_comm_us_per_step", 0) > 0


# ---------------------------------------------------------------------------
# what went with the ring
# ---------------------------------------------------------------------------
def test_the_wire_module_is_gone_and_nothing_names_it():
    import importlib
    import pathlib

    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("deepspeed_tpu.runtime.wire")
    root = pathlib.Path(deepspeed_tpu.__file__).parent
    names = ("runtime.wire", "runtime/wire", "import wire")
    assert not [str(p.relative_to(root)) for p in sorted(root.rglob("*.py"))
                if any(n in p.read_text() for n in names)]
