"""ds_wire tests (runtime/wire.py + the ``wire`` ds_config block): the
quantizer's padded-group accounting, quantize/dequant roundtrip bounds,
qgZ hierarchical-vs-flat numerics and error-feedback convergence, the
strict no-op + byte-identical-HLO contract, THE 8-dev static_comm_bytes
on/off acceptance (inter-host all-gather + reduce-scatter ≥3× lower at
``wire: full`` with losses within the pinned tolerance), ds_xray zero
findings on the rewritten programs, quantized collective-fingerprint
stability, the chaos ``collective`` drill on the quantized serial gather,
and the perf-ledger ``wire_mode`` identity."""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model, synthetic_lm_batch

# the ACCEPTANCE fixture: weight-dominated gpt2 (params >> activations, so
# the ZeRO-3 weight gathers are the comm story, as they are at real scale)
ACFG = GPT2Config(vocab_size=128, n_positions=8, n_embd=256, n_layer=2,
                  n_head=2, remat=False, use_flash_attention=False)
AB, AT = 8, 8

# the micro fixture for cheap engine drills
MCFG = GPT2Config(vocab_size=128, n_positions=16, n_embd=64, n_layer=2,
                  n_head=2, remat=False, use_flash_attention=False)
MB, MT = 8, 16


def wire_config(model_cfg=ACFG, bs=AB, *, wire=None, tpu=None, overlap=None,
                **over):
    cfg = {
        "train_batch_size": bs,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 0},
        "steps_per_print": 0,
    }
    if overlap is not None:
        cfg["overlap"] = overlap
    if tpu is not None:
        cfg["tpu"] = tpu
    if wire is not None:
        cfg["wire"] = wire
    cfg.update(over)
    return cfg


def make_engine(cfg, model_cfg=ACFG):
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2Model(model_cfg),
                                               config=cfg)
    return engine


WIRE_FULL = {"weight_quant_bits": 8, "secondary_partition": True,
             "secondary_size": 4, "grad_quant_bits": 4}
TPU_2x4 = {"data": 2, "ici": 4}


# ---------------------------------------------------------------------------
# ops/quantizer.py — padded-group accounting (the satellite fix, pinned)
# ---------------------------------------------------------------------------
@pytest.mark.wire
class TestQuantizerPadding:
    def test_group_layout_pads_instead_of_collapsing(self):
        from deepspeed_tpu.ops.quantizer import quant_group_layout

        assert quant_group_layout(100, 64) == (64, 2, 128)
        assert quant_group_layout(128, 64) == (64, 2, 128)
        assert quant_group_layout(37, 16) == (16, 3, 48)
        # group >= dim: one whole-dim group, nothing padded
        assert quant_group_layout(48, 64) == (48, 1, 48)
        assert quant_group_layout(48, 0) == (48, 1, 48)

    def test_nbytes_bills_padded_wire_bytes(self):
        """static_comm_bytes bills what actually crosses the wire: the
        PADDED codes (+ scales), not the logical element count."""
        from deepspeed_tpu.ops.quantizer import quantize_tensor

        w = jnp.asarray(np.random.RandomState(0).randn(100, 8),
                        jnp.float32)
        qt = quantize_tensor(w, num_bits=8, group_size=64)
        assert qt.q.shape == (2, 64, 8)          # 2 groups of 64, padded
        assert qt.scale.shape == (2, 8)
        assert qt.nbytes == 2 * 64 * 8 + 2 * 8 * 4
        assert qt.nbytes > 100 * 8               # > logical int8 bytes

    @pytest.mark.parametrize("shape,gs", [((100, 8), 64), ((37,), 16),
                                          ((3, 100, 8), 32)])
    def test_roundtrip_exact_shape_and_bounded_error(self, shape, gs):
        from deepspeed_tpu.ops.quantizer import (dequantize_tensor,
                                                 quantize_tensor)

        w = jnp.asarray(np.random.RandomState(1).randn(*shape), jnp.float32)
        qt = quantize_tensor(w, num_bits=8, group_size=gs)
        back = dequantize_tensor(qt)
        assert back.shape == w.shape
        # per-group symmetric int8: |err| <= group absmax / 127 / 2 + round
        bound = float(jnp.max(jnp.abs(w))) / 127.0 * 0.51 * 2
        assert float(jnp.max(jnp.abs(back - w))) <= max(bound, 2e-2)

    def test_int4_roundtrip_padded(self):
        from deepspeed_tpu.ops.quantizer import (dequantize_tensor,
                                                 quantize_tensor)

        w = jnp.asarray(np.random.RandomState(2).randn(100, 4), jnp.float32)
        qt = quantize_tensor(w, num_bits=4, group_size=64)
        assert qt.q.shape == (2, 32, 4)          # nibble-packed, padded
        back = dequantize_tensor(qt)
        assert back.shape == w.shape
        assert float(jnp.max(jnp.abs(back - w))) <= \
            float(jnp.max(jnp.abs(w))) / 7.0 * 0.51 * 2 + 1e-3


# ---------------------------------------------------------------------------
# spec surgery
# ---------------------------------------------------------------------------
@pytest.mark.wire
class TestSpecSurgery:
    def _mesh(self):
        return Mesh(np.asarray(jax.devices()).reshape(1, 2, 1, 4, 1, 1, 1),
                    ("pipe", "data", "mics", "ici", "expert", "seq",
                     "tensor"))

    def test_secondary_spec_swaps_dp_for_ici(self):
        from deepspeed_tpu.runtime.wire import secondary_spec

        sp = secondary_spec(P(None, ("data", "ici")), 2, ("data", "ici"))
        assert tuple(sp) == (None, "ici")
        sp = secondary_spec(P("tensor", ("data", "ici")), 2, ("data", "ici"))
        assert tuple(sp) == ("tensor", "ici")
        # no dp on the leaf: unchanged
        sp = secondary_spec(P(None, "tensor"), 2, ("data", "ici"))
        assert tuple(sp) == (None, "tensor")

    def test_plan_leaf_wire_maps_out_dim_sharding(self):
        from deepspeed_tpu.runtime.wire import plan_leaf_wire

        mesh = self._mesh()
        lw = plan_leaf_wire(mesh, (64, 256), P(None, ("data", "ici")),
                            ("data", "ici"), bits=8, group_size=64,
                            secondary=True)
        assert lw is not None
        assert lw.gs == 64 and lw.view_shape == (64, 256)
        assert tuple(lw.s_q.spec) == (None, None, ("data", "ici"))
        assert tuple(lw.g_q.spec) == (None, None, None)
        assert tuple(lw.sec_q.spec) == (None, None, None, "ici")  # stacked
        # codes + scales wire bytes: 64*256 int8 + 1*256 f32 scales
        assert lw.wire_nbytes == 64 * 256 + 256 * 4

    def test_plan_leaf_wire_skips_unmappable(self):
        from deepspeed_tpu.runtime.wire import plan_leaf_wire

        mesh = self._mesh()
        # 1-D bias sharded on its only dim: G=2 not divisible by dp world 8
        assert plan_leaf_wire(mesh, (128,), P(("data", "ici"),),
                              ("data", "ici"), bits=8, group_size=64,
                              secondary=False) is None
        # int4 needs an even group
        assert plan_leaf_wire(mesh, (33, 256), P(None, ("data", "ici")),
                              ("data", "ici"), bits=4, group_size=33,
                              secondary=False) is None


# ---------------------------------------------------------------------------
# qgZ — hierarchical quantized exchange numerics (pure, shard_map)
# ---------------------------------------------------------------------------
def _qgz_mesh():
    return Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "ici"))


@pytest.mark.wire
class TestQGZNumerics:
    def test_hierarchical_matches_flat_and_exact_mean(self):
        from deepspeed_tpu.runtime.wire import (
            hierarchical_quantized_allreduce, qgz_state_shapes)

        mesh = _qgz_mesh()
        n, W = 1000, 8
        rng = np.random.RandomState(0)
        xs = jnp.asarray(rng.randn(W, n), jnp.float32)
        exact = np.asarray(jnp.mean(xs, axis=0))

        def run(inner):
            wl, sl = qgz_state_shapes(n, 4 if inner else 1,
                                      2 if inner else 8)
            we = jnp.zeros((W, wl), jnp.float32)
            se = jnp.zeros((W, sl), jnp.float32)

            def k(x, we, se):
                out, nwe, nse = hierarchical_quantized_allreduce(
                    x[0], we[0], se[0],
                    outer_axis="data" if inner else ("data", "ici"),
                    inner_axis="ici" if inner else None, bits=8,
                    group_size=64)
                return out[None], nwe[None], nse[None]

            fn = jax.shard_map(
                k, mesh=mesh,
                in_specs=(P(("data", "ici")), P(("data", "ici")),
                          P(("data", "ici"))),
                out_specs=(P(("data", "ici")), P(("data", "ici")),
                           P(("data", "ici"))),
                check_vma=False)
            out, _, _ = fn(xs, we, se)
            return np.asarray(out)

        hier = run(inner=True)
        flat = run(inner=False)
        # every device agrees, and both schemes track the exact mean with
        # bounded quantization error (two quantization hops)
        scale = np.abs(exact).max() + 1.0
        for out in (hier, flat):
            assert np.allclose(out, out[0:1], atol=1e-6)
            assert np.max(np.abs(out[0] - exact)) < 0.1 * scale

    def test_error_feedback_residuals_compensate(self):
        """int4 with persistent residuals: the time-averaged reconstruction
        converges to the true mean (the error-feedback contract the 1-bit
        family relies on), while a residual-free int4 reconstruction keeps
        its bias."""
        from deepspeed_tpu.runtime.wire import (
            hierarchical_quantized_allreduce, qgz_state_shapes)

        mesh = _qgz_mesh()
        n, W, steps = 256, 8, 24
        rng = np.random.RandomState(3)
        xs = jnp.asarray(rng.randn(W, n), jnp.float32)
        exact = np.asarray(jnp.mean(xs, axis=0))
        wl, sl = qgz_state_shapes(n, 4, 2)

        def k(x, we, se):
            out, nwe, nse = hierarchical_quantized_allreduce(
                x[0], we[0], se[0], outer_axis="data", inner_axis="ici",
                bits=4, group_size=64)
            return out[None], nwe[None], nse[None]

        fn = jax.shard_map(
            k, mesh=mesh,
            in_specs=(P(("data", "ici")),) * 3,
            out_specs=(P(("data", "ici")),) * 3, check_vma=False)
        fn = jax.jit(fn)
        we = jnp.zeros((W, wl), jnp.float32)
        se = jnp.zeros((W, sl), jnp.float32)
        acc = np.zeros(n)
        for _ in range(steps):
            out, we, se = fn(xs, we, se)
            acc += np.asarray(out)[0]
        err_avg = np.abs(acc / steps - exact).max()
        one_shot, *_ = fn(xs, jnp.zeros_like(we), jnp.zeros_like(se))
        err_one = np.abs(np.asarray(one_shot)[0] - exact).max()
        assert err_avg < 0.5 * max(err_one, 1e-9) or err_avg < 5e-3


# ---------------------------------------------------------------------------
# config surface + schema cross-fields
# ---------------------------------------------------------------------------
@pytest.mark.wire
class TestWireConfigSurface:
    def test_unknown_key_rejected_with_hint(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        with pytest.raises(ValueError, match="weight_quant_bits"):
            DeepSpeedConfig(wire_config_dict({"weight_quant_bit": 8}))

    def test_bad_bits_rejected(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        with pytest.raises(ValueError, match="4 or 8"):
            DeepSpeedConfig(wire_config_dict({"weight_quant_bits": 6}))

    def test_cross_fields(self):
        from deepspeed_tpu.analysis.schema import walk_config

        # wire below ZeRO-3: warning (nothing to shrink)
        findings, _ = walk_config(
            wire_config_dict({}, stage=1, overlap=True), world_size=8)
        assert any(f.rule == "config/cross-field" and f.severity == "warning"
                   and "stage" in f.citation for f in findings)
        # wire without overlap: warning (the gather rides the overlap scan)
        findings, _ = walk_config(
            wire_config_dict({}, overlap=False), world_size=8)
        assert any("wire vs overlap" == f.citation for f in findings)
        # grad quant + 1-bit optimizer: error (both own the exchange)
        cfg = wire_config_dict({"grad_quant_bits": 8}, stage=0, overlap=True)
        cfg["optimizer"] = {"type": "OneBitAdam", "params": {"lr": 1e-3}}
        findings, _ = walk_config(cfg, world_size=8)
        assert any(f.severity == "error" and
                   "wire.grad_quant_bits vs optimizer.type" == f.citation
                   for f in findings)
        # hpZ with no explicit host factoring: INFO, not an error
        findings, _ = walk_config(
            wire_config_dict({"secondary_partition": True}, overlap=True),
            world_size=8)
        hits = [f for f in findings
                if f.citation == "wire.secondary_partition vs tpu.ici"]
        assert hits and all(f.severity == "info" for f in hits)

    def test_ledger_compare_flags_wire_mode_change(self):
        from deepspeed_tpu.perf.cli import _world_tag
        from deepspeed_tpu.perf.ledger import compare

        old = {"metric": "m (x)", "value": 1.0, "wire_mode": "off",
               "world_size": 8, "mesh_axes": "data=2×ici=4"}
        new = dict(old, wire_mode="qwz+hpz")
        r = compare(old, new)
        assert r["world_changed"] and r["fingerprint_changed"]
        assert "wire changed off -> qwz+hpz" in _world_tag(r)
        # entries predating the key read as "off" (no spurious flag)
        r2 = compare({"metric": "m (x)", "value": 1.0},
                     dict(old, wire_mode="off"))
        assert not r2["world_changed"]


def wire_config_dict(wire, stage=3, overlap=False):
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage},
        "wire": dict(wire),
        "steps_per_print": 0,
    }
    if overlap:
        cfg["overlap"] = {}
    return cfg


# ---------------------------------------------------------------------------
# strict no-op + byte-identical HLO
# ---------------------------------------------------------------------------
@pytest.mark.wire
class TestStrictNoOp:
    def test_block_absent_never_imports_module(self):
        mods = [m for m in list(sys.modules)
                if m == "deepspeed_tpu.runtime.wire"]
        saved = {m: sys.modules.pop(m) for m in mods}
        try:
            engine = make_engine(wire_config(MCFG, MB, overlap={}), MCFG)
            engine.train_batch(synthetic_lm_batch(MB, MT, MCFG.vocab_size))
            assert engine._wire is None
            assert "deepspeed_tpu.runtime.wire" not in sys.modules
        finally:
            sys.modules.update(saved)

    def test_block_absent_step_is_byte_identical(self):
        """An engine without the block and one with ``enabled: false``
        lower the EXACT same step program — the wire rewrites leave zero
        residue when off."""
        def lowered(engine):
            abstract = lambda tree: jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), tree)
            batch = engine._shard_batch(
                synthetic_lm_batch(MB, MT, MCFG.vocab_size))
            with engine.mesh:
                return engine._get_compiled_train_batch(1).lower(
                    abstract(engine.state), abstract(batch)).as_text()

        t_absent = lowered(make_engine(wire_config(MCFG, MB, overlap={}),
                                       MCFG))
        t_disabled = lowered(make_engine(
            wire_config(MCFG, MB, overlap={},
                        wire={"enabled": False, "weight_quant_bits": 8}),
            MCFG))
        assert t_absent == t_disabled


# ---------------------------------------------------------------------------
# THE acceptance: ≥3× lower inter-host AG+RS wire bytes, losses pinned
# ---------------------------------------------------------------------------
def _acceptance_engine(wire, ledger=None, tmp_path=None, name=""):
    cfg = wire_config(ACFG, AB, wire=wire, tpu=dict(TPU_2x4),
                      overlap={"grad_reduce": "post"})
    if ledger is not None:
        cfg["telemetry"] = {"enabled": True,
                            "output_dir": str(tmp_path / f"tel_{name}"),
                            "prometheus": False, "flush_interval": 1_000_000}
        cfg["perf"] = {"ledger_path": str(ledger)}
    engine = make_engine(cfg, ACFG)
    batch = synthetic_lm_batch(AB, AT, ACFG.vocab_size, seed=0)
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    from deepspeed_tpu.analysis.xray import static_comm_for_engine

    sc = static_comm_for_engine(engine)
    entry = None
    if ledger is not None:
        entry = engine.perf_record(f"wire-drill ({name})", 1.0, "MFU",
                                   config={"wire": name}, timed_steps=2)
    return engine, losses, sc, entry


@pytest.mark.wire
@pytest.mark.perf
class TestStaticCommAcceptance:
    def test_full_vs_off_inter_gather_scatter_3x(self, tmp_path):
        from deepspeed_tpu.analysis.xray import inter_host_bytes, run_xray
        from deepspeed_tpu.perf.cli import main as perf_main

        ledger = tmp_path / "led.jsonl"
        e0, l0, sc0, ent0 = _acceptance_engine(None, ledger, tmp_path, "off")
        e1, l1, sc1, ent1 = _acceptance_engine(WIRE_FULL, ledger, tmp_path,
                                               "full")
        # --- the acceptance number: inter-host all-gather + reduce-scatter
        inter0 = inter_host_bytes(sc0["by_kind"])
        inter1 = inter_host_bytes(sc1["by_kind"])
        assert inter0 == sc0["inter_gather_scatter_bytes"]
        assert inter1 >= 1  # the quantized build gather still crosses hosts
        assert inter0 / inter1 >= 3.0, (inter0, inter1)
        # total static comm improves too (the gate's headline metric)
        assert sc1["static_comm_bytes"] < sc0["static_comm_bytes"]
        # --- losses within the pinned tolerance of the fp-exact step
        assert max(abs(a - b) for a, b in zip(l0, l1)) < 0.02
        # --- exposed comm no worse than the overlapped baseline (both are
        # fused overlapped programs: nothing exposed on the host timeline)
        exp0 = (ent0["attribution"] or {}).get("exposed_comm_us_per_step", 0)
        exp1 = (ent1["attribution"] or {}).get("exposed_comm_us_per_step", 0)
        assert exp1 <= exp0 + 1.0
        # --- the ledger pair carries the identity + the gate enforces it
        assert ent0["wire_mode"] == "off"
        assert ent1["wire_mode"] == "qwz+hpz+qgz"
        assert ent0["mesh_axes"] == ent1["mesh_axes"]
        base = tmp_path / "off.jsonl"
        cand = tmp_path / "full.jsonl"
        base.write_text(json.dumps(ent0) + "\n")
        cand.write_text(json.dumps(ent1) + "\n")
        assert perf_main(["gate", "--baseline", str(base),
                          "--candidate", str(cand),
                          "--metric", "static_comm_bytes"]) == 0
        assert perf_main(["gate", "--baseline", str(cand),
                          "--candidate", str(base),
                          "--metric", "static_comm_bytes"]) == 2
        # --- ds_xray collective-order + promise-vs-actual: zero findings
        # on the rewritten (quantized) program
        result = run_xray(plan=e1.plan)
        errors = [f for f in result.findings if f.severity == "error"]
        assert not errors, [str(f) for f in errors]

    def test_qwz_quantized_gather_fingerprints_stable(self):
        """PR 4 collective fingerprints hash the quantized op identity
        stably: same config ⇒ same fingerprint, and it differs from the
        full-width schedule's."""
        fps = []
        for _ in range(2):
            cfg = wire_config(MCFG, MB, wire={"weight_quant_bits": 8},
                              tpu=dict(TPU_2x4), overlap={},
                              analysis={"fail_on": "error"})
            e = make_engine(cfg, MCFG)
            e.train_batch(synthetic_lm_batch(MB, MT, MCFG.vocab_size))
            assert e._collective_fingerprint is not None
            fps.append(e._collective_fingerprint)
        assert fps[0] == fps[1]
        cfg = wire_config(MCFG, MB, tpu=dict(TPU_2x4), overlap={},
                          analysis={"fail_on": "error"})
        e = make_engine(cfg, MCFG)
        e.train_batch(synthetic_lm_batch(MB, MT, MCFG.vocab_size))
        assert e._collective_fingerprint != fps[0]


# ---------------------------------------------------------------------------
# chaos `collective` drill on the quantized serial gather
# ---------------------------------------------------------------------------
@pytest.mark.wire
@pytest.mark.chaos
def test_chaos_delay_inflates_quantized_serial_gather(tmp_path):
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.resilience import chaos as chaos_mod

    cfg = wire_config(MCFG, MB, wire={"weight_quant_bits": 8},
                      tpu=dict(TPU_2x4),
                      overlap={"schedule": "serial"},
                      telemetry={"enabled": True,
                                 "output_dir": str(tmp_path / "t"),
                                 "prometheus": False,
                                 "flush_interval": 1_000_000})
    engine = make_engine(cfg, MCFG)
    batch = synthetic_lm_batch(MB, MT, MCFG.vocab_size)
    inj = chaos_mod.ChaosInjector(delay_at={"collective": [3]},
                                  max_delay_s=0.5)
    chaos_mod.install_chaos(inj)
    try:
        for _ in range(3):
            engine.train_batch(batch)
        spans = [e for e in telemetry.get_session().tracer.events
                 if e.get("cat") == "comm"]
        assert len(spans) == 3
        # the quantized gather phase carries its (smaller) wire bytes and
        # the injected delay inflates the SAME timed span
        from deepspeed_tpu.ops.quantizer import quantized_nbytes  # noqa

        dense = sum(int(np.prod(l.shape)) * 2
                    for l in jax.tree.leaves(engine.state.params))
        assert 0 < spans[0]["args"]["bytes"] < dense
        assert spans[2]["dur"] - spans[1]["dur"] >= 0.3 * 1e6
        assert any(op == "collective" and "delay" in act
                   for op, act, _ in inj.log)
    finally:
        chaos_mod.uninstall_chaos()
        telemetry.deconfigure()


# ---------------------------------------------------------------------------
# qgZ engine path — stage-0 shard-mapped step with residuals in opt state
# ---------------------------------------------------------------------------
@pytest.mark.wire
class TestQGZEngine:
    def test_qgz_grad_sync_trains(self):
        cfg = wire_config(
            MCFG, MB, wire={"grad_quant_bits": 8, "weight_quant_bits": 0},
            tpu=dict(TPU_2x4),
            zero_optimization={"stage": 0})
        engine = make_engine(cfg, MCFG)
        from deepspeed_tpu.runtime.wire import QGZAdam

        assert isinstance(engine.optimizer, QGZAdam)
        assert engine._onebit        # rides the shard-mapped step protocol
        batch = synthetic_lm_batch(MB, MT, MCFG.vocab_size, seed=0)
        losses = [float(engine.train_batch(batch)) for _ in range(4)]
        assert losses[-1] < losses[0]
        # the error-feedback residuals ride the optimizer state,
        # per-worker (leading world dim), and become nonzero once the
        # quantizer has clipped something
        st = engine.state.opt_state
        we = jax.tree.leaves(st.worker_error)
        assert all(w.shape[0] == 8 for w in we)
        assert any(float(jnp.max(jnp.abs(w))) > 0 for w in we)

    def test_qgz_with_onebit_refused(self):
        cfg = wire_config(MCFG, MB, wire={"grad_quant_bits": 8},
                          zero_optimization={"stage": 0})
        cfg["optimizer"] = {"type": "OneBitAdam", "params": {"lr": 1e-3}}
        with pytest.raises(ValueError, match="1-bit"):
            make_engine(cfg, MCFG)

    def test_qgz_inert_at_stage3(self):
        cfg = wire_config(MCFG, MB,
                          wire={"grad_quant_bits": 8,
                                "weight_quant_bits": 0},
                          overlap={})
        engine = make_engine(cfg, MCFG)
        from deepspeed_tpu.runtime.wire import QGZAdam

        assert not isinstance(engine.optimizer, QGZAdam)
        assert not engine._onebit


# ---------------------------------------------------------------------------
# the full wire mode's ledger entry, end to end
# ---------------------------------------------------------------------------
@pytest.mark.wire
@pytest.mark.perf
def test_wire_full_entry_splits_intra_host_bytes(tmp_path, tiny_ledger_run):
    """gpt2-tiny as a real ZeRO-3 job over 8 simulated devices on the
    ici-factored mesh with qwZ + hpZ + qgZ armed: the engine's ledger
    entry stamps wire_mode and the host-split static comm."""
    _, entry = tiny_ledger_run(tmp_path, devices=None, extra={
        "tpu": {"data": -1, "ici": 4},
        "overlap": {},
        "wire": {"weight_quant_bits": 8, "secondary_partition": True,
                 "secondary_size": 4, "grad_quant_bits": 4}})
    assert entry["wire_mode"] == "qwz+hpz+qgz"
    assert entry["mesh_axes"] == "data=2×ici=4"
    by_kind = entry["attribution"]["static_comm"]["by_kind"]
    assert any(k.endswith("/intra") for k in by_kind)
