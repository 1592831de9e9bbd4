"""ds_sentry tests — silent-data-corruption defense.

All CPU-only and deterministic on the faked 8-device mesh (TPU-grade
determinism holds on the CPU backend too: same compiled program + same
inputs = same bits, which is the property the whole subsystem spends).
The matrix the acceptance criteria name:

* fold primitives: host/device checksums see exactly one flipped bit,
  are dtype-agnostic (raw bytes) and key-order stable;
* blame bisection: every single-culprit case converges to the right
  device with a log-length probe trail;
* the hardened agreement proto: mixed version bytes raise
  ``desync(kind=proto)`` before any digest vote; the sdc checksum rides
  the digest as ``extra`` bytes;
* strict no-op: without the ``sdc`` block the module is never imported
  and the lowered step HLO is byte-identical;
* clean audits advance the audited-clean watermark; the poison-free
  ladder stamps/verifies ring checksums and skips condemned entries;
* THE drills: a chaos ``bitflip`` on device 5 is detected by the next
  replay audit, blamed to device 5, and either rewound in place
  (quarantine off) with losses bitwise re-trodden, or evicted via a
  fleet shrink 8->6 under the elastic agent with the event priced in
  ``ds_prof goodput`` and the ``ds_metrics`` sdc footer;
* the randomized bitflip sweep and the audit overhead-pricing run
  (both in tests/slow_tests.txt).
"""

import itertools
import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.elasticity import DSElasticAgent
from deepspeed_tpu.models.simple import SimpleModel
from deepspeed_tpu.resilience import (ChaosInjector, install_chaos,
                                      uninstall_chaos)

pytestmark = pytest.mark.sdc

HIDDEN = 16
TBS = 24                # divides 8 and 6 — the evict-drill worlds
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SDC_MOD = "deepspeed_tpu.resilience.sdc"


@pytest.fixture(autouse=True)
def _clean_slate():
    """Fresh chaos, fresh tier-0 ring, full fleet, untouched handlers."""
    orig = {s: signal.getsignal(s) for s in (signal.SIGTERM, signal.SIGINT)}
    yield
    uninstall_chaos()
    rw = sys.modules.get("deepspeed_tpu.resilience.rewind")
    if rw is not None:
        rw.clear_ram_snapshots()
    rz = sys.modules.get("deepspeed_tpu.elasticity.resize")
    if rz is not None:
        rz.clear_fleet_events()
    for s, h in orig.items():
        signal.signal(s, h)


def plain_engine(rewind=None, extra=None, model=None):
    """An engine over the FULL backend mesh."""
    comm.cdb = None
    cfg = {"train_batch_size": TBS,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "steps_per_print": 0}
    if rewind is not None:
        cfg["rewind"] = rewind
    if extra:
        cfg.update(extra)
    engine, *_ = deepspeed_tpu.initialize(
        model=model or SimpleModel(hidden_dim=HIDDEN, nlayers=2), config=cfg)
    return engine


def survivor_engine(rewind=None, extra=None):
    """An engine whose dp mesh spans the simulated fleet's survivors,
    with the elastic resize path armed — what the evict drill's factory
    builds after a membership change."""
    import types

    from deepspeed_tpu.elasticity import resize as rz

    comm.cdb = None
    cfg = {"train_batch_size": TBS,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "steps_per_print": 0,
           "elasticity": {"resize": {"enabled": True}}}
    if rewind is not None:
        cfg["rewind"] = rewind
    if extra:
        cfg.update(extra)
    engine, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=HIDDEN, nlayers=2), config=cfg,
        mpu=types.SimpleNamespace(mesh=rz.survivor_mesh()))
    return engine


def batch(seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(TBS, HIDDEN).astype(np.float32),
            rng.randn(TBS, HIDDEN).astype(np.float32))


def batch_seq():
    """Deterministic per-position batch stream (attempt N's k-th yield
    equals attempt M's k-th yield)."""
    return (batch(seed=i) for i in itertools.count())


def run_by_step(engine, until, record=None, guard=24):
    """Drive ``train_batch`` feeding the STEP-INDEXED batch, so a run
    that rewinds mid-loop automatically re-treads the right data."""
    n = 0
    while getattr(engine, "_host_step", 0) < until:
        n += 1
        assert n < guard, "drill did not converge (rewind loop?)"
        step = getattr(engine, "_host_step", 0) + 1
        loss = float(engine.train_batch(batch(step)))
        if record is not None:
            record[step] = loss
    return record


# ------------------------------------------------------------------- folds
class TestFolds:
    def test_host_fold_sees_one_flipped_bit(self):
        from deepspeed_tpu.resilience.sdc import fold_host_array

        a = np.arange(64, dtype=np.float32) / 7.0
        b = a.copy()
        b.view(np.uint32)[17] ^= np.uint32(1 << 12)
        assert fold_host_array(a) == fold_host_array(a.copy())
        assert fold_host_array(a) != fold_host_array(b)

    def test_host_fold_is_dtype_agnostic_raw_bytes(self):
        """bf16 (ml_dtypes) arrays fold as raw bytes — a view, never a
        cast, so sub-float32 representations keep their exact bits."""
        import jax.numpy as jnp

        from deepspeed_tpu.resilience.sdc import fold_host_array

        x = np.asarray(jnp.linspace(0, 1, 16, dtype=jnp.bfloat16))
        assert x.dtype.itemsize == 2
        v = fold_host_array(x)
        assert isinstance(v, int) and 0 <= v < (1 << 32)
        y = x.copy()
        y.view(np.uint8)[5] ^= 1
        assert fold_host_array(y) != v

    def test_flat_fold_is_key_order_stable(self):
        from deepspeed_tpu.resilience.sdc import fold_host_flat

        a = np.arange(8, dtype=np.float32)
        b = np.arange(8, dtype=np.int32)
        assert fold_host_flat({"p/w": a, "opt/m": b}) == \
            fold_host_flat({"opt/m": b, "p/w": a})
        tampered = a.copy()
        tampered.view(np.uint32)[0] ^= np.uint32(1)
        assert fold_host_flat({"p/w": tampered, "opt/m": b}) != \
            fold_host_flat({"p/w": a, "opt/m": b})

    def test_device_fold_deterministic_and_bit_sensitive(self):
        import jax.numpy as jnp

        from deepspeed_tpu.resilience.sdc import fold_state

        tree = {"w": jnp.arange(32, dtype=jnp.float32) * 0.5,
                "n": jnp.arange(4, dtype=jnp.int32)}
        f = jax.jit(fold_state)
        v = int(f(tree))
        assert int(f(jax.tree.map(jnp.copy, tree))) == v
        flipped = np.asarray(tree["w"]).copy()
        flipped.view(np.uint32)[11] ^= np.uint32(1 << 12)
        assert int(f({"w": jnp.asarray(flipped), "n": tree["n"]})) != v


# ------------------------------------------------------------------- blame
class TestBisectBlame:
    def test_every_single_culprit_converges(self):
        from deepspeed_tpu.resilience.sdc import bisect_blame

        devs = list(range(8))
        for d in devs:
            culprit, probes, suspects = bisect_blame(devs, [d])
            assert culprit == d
            assert suspects == [d]
            assert len(probes) == 3          # log2(8) halvings
            for p in probes:
                assert set(p) == {"window", "left_half", "left_half_dirty"}

    def test_multi_suspect_blames_lowest_indexed(self):
        from deepspeed_tpu.resilience.sdc import bisect_blame

        culprit, _, suspects = bisect_blame(list(range(8)), [6, 2])
        assert culprit == 2
        assert suspects == [2, 6]

    def test_unsorted_device_list_is_normalized(self):
        from deepspeed_tpu.resilience.sdc import bisect_blame

        culprit, probes, _ = bisect_blame([3, 1, 0, 2], [2])
        assert culprit == 2
        assert len(probes) == 2


# -------------------------------------------------- hardened agreement proto
class TestAgreementProto:
    @staticmethod
    def _rows(digests, versions):
        return np.stack([
            np.frombuffer(bytes([v]) + bytes.fromhex(d), dtype=np.uint8)
            for v, d in zip(versions, digests)])

    def test_mixed_versions_raise_proto_desync_before_any_vote(self):
        from deepspeed_tpu.resilience.consistency import (PROTO_VERSION,
                                                          DesyncError,
                                                          check_row_agreement,
                                                          step_digest)

        d = step_digest(3, 1.5)
        rows = self._rows([d] * 4,
                          [PROTO_VERSION, PROTO_VERSION,
                           PROTO_VERSION - 1, PROTO_VERSION])
        with pytest.raises(DesyncError, match=r"kind=proto"):
            check_row_agreement(rows, step=3)

    def test_uniform_versions_vote_on_the_digest_columns(self):
        from deepspeed_tpu.resilience.consistency import (PROTO_VERSION,
                                                          check_row_agreement,
                                                          step_digest)

        good = step_digest(3, 1.5)
        bad = step_digest(3, 1.5000001)
        rows = self._rows([good, good, bad, good], [PROTO_VERSION] * 4)
        assert check_row_agreement(rows, step=3) == [2]
        clean = self._rows([good] * 4, [PROTO_VERSION] * 4)
        assert check_row_agreement(clean, step=3) == []

    def test_extra_agreement_bytes_change_the_digest(self):
        """The ds_sentry state checksum rides the digest: two ranks with
        the same loss but divergent STATE must disagree."""
        from deepspeed_tpu.resilience.consistency import step_digest

        base = step_digest(7, 0.25)
        assert step_digest(7, 0.25, extra=b"\x01\x02\x03\x04") != base
        assert step_digest(7, 0.25, extra=b"\x01\x02\x03\x05") != \
            step_digest(7, 0.25, extra=b"\x01\x02\x03\x04")


# ------------------------------------------------------------ config lint
class TestConfigValidation:
    def test_bitflip_armed_without_rate_refused(self):
        with pytest.raises(ValueError, match="flip probability"):
            plain_engine(extra={"resilience": {
                "chaos": {"enabled": True, "bitflip_at_step": 3}}})

    def test_bitflip_bad_target_refused(self):
        with pytest.raises(ValueError, match="bitflip_target"):
            plain_engine(extra={"resilience": {
                "chaos": {"enabled": True, "bitflip_at_step": 3,
                          "bitflip_rate": 1.0, "bitflip_target": "loss"}}})

    def test_audit_interval_zero_refused(self):
        with pytest.raises(ValueError, match="audit_interval"):
            plain_engine(extra={"sdc": {"audit_interval": 0}})

    def test_unknown_sdc_key_did_you_mean(self):
        with pytest.raises(ValueError, match="audit_interval"):
            plain_engine(extra={"sdc": {"audit_intervall": 5}})

    def test_schema_pass_knows_the_block(self):
        from deepspeed_tpu.analysis.schema import walk_config

        base = {"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
        # did-you-mean on a typo'd sdc key
        findings, _ = walk_config({**base, "sdc": {"audit_intervall": 5}})
        assert any("audit_interval" in f.message for f in findings)
        # sdc without the rewind block: nothing clean to rewind to
        findings, _ = walk_config({**base, "sdc": {}})
        assert any("sdc vs rewind" in f.citation for f in findings)
        findings, _ = walk_config({**base, "rewind": {}, "sdc": {}})
        assert not any("sdc vs rewind" in f.citation for f in findings)
        # an audit cadence tighter than the consistency crossing
        findings, _ = walk_config(
            {**base, "rewind": {}, "sdc": {"audit_interval": 5},
             "watchdog": {"consistency_interval": 50}})
        assert any("sdc.audit_interval vs watchdog.consistency_interval"
                   in f.citation for f in findings)


# ------------------------------------------------------------ strict no-op
class TestStrictNoOp:
    def _without_module(self):
        return {m: sys.modules.pop(m) for m in list(sys.modules)
                if m == SDC_MOD}

    def test_block_absent_never_imports_module(self):
        saved = self._without_module()
        try:
            engine = plain_engine()
            engine.train_batch(batch())
            assert engine._sdc is None
            assert engine._last_metrics.checksum is None
            assert SDC_MOD not in sys.modules
        finally:
            sys.modules.update(saved)

    def test_enabled_false_never_imports_module(self):
        saved = self._without_module()
        try:
            engine = plain_engine(extra={"sdc": {"enabled": False}})
            engine.train_batch(batch())
            assert engine._sdc is None
            assert SDC_MOD not in sys.modules
        finally:
            sys.modules.update(saved)

    def test_block_absent_step_is_byte_identical(self):
        """Absent block == enabled:false, down to the lowered HLO bytes;
        an ARMED block differs (the checksum fold rides the program)."""
        def lowered(extra):
            engine = plain_engine(extra=extra)
            b = engine._shard_batch(batch())
            abstract = lambda t: jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), t)
            with engine.mesh:
                return engine._get_compiled_train_batch(1).lower(
                    abstract(engine.state), abstract(b)).as_text()

        absent = lowered(None)
        off = lowered({"sdc": {"enabled": False}})
        armed = lowered({"sdc": {"audit_interval": 10}})
        assert absent == off
        assert armed != absent


# ------------------------------------------------------------- clean audits
class TestCleanAudit:
    def test_clean_run_audits_and_advances_the_watermark(self):
        engine = plain_engine(extra={"sdc": {"audit_interval": 2}})
        mgr = engine._sdc
        assert mgr is not None and mgr.active and mgr.checksum_armed
        for i in range(1, 5):
            engine.train_batch(batch(i))
        assert mgr.audits == 2                     # steps 2 and 4
        assert mgr.verdicts == 0
        assert mgr.last_clean_step == 4
        # the online checksum rode the step and feeds the agreement digest
        cs = engine._last_metrics.checksum
        assert cs is not None
        assert 0 <= int(np.asarray(cs)) < (1 << 32)
        assert len(mgr.agreement_bytes(engine._last_metrics)) == 4
        # the per-device fold table covers the whole backend
        from deepspeed_tpu.resilience.sdc import device_fold_table

        table = device_fold_table(engine.state)
        assert sorted(table) == list(range(8))

    def test_stash_dropped_on_step_mismatch(self):
        """A rewind/restart under a pending stash must drop it — replaying
        inputs against a different step's outputs would be a false
        verdict."""
        engine = plain_engine(extra={"sdc": {"audit_interval": 2}})
        mgr = engine._sdc
        assert mgr.maybe_stash(2, batch(), 1) is True
        assert mgr.maybe_stash(3, batch(), 1) is False   # not an audit step
        mgr.after_step(3, engine._last_metrics)          # stash is for step 2
        assert mgr._stash is None
        assert mgr.audits == 0 and mgr.verdicts == 0

    def test_checksum_off_keeps_metrics_clean(self):
        engine = plain_engine(
            extra={"sdc": {"audit_interval": 2, "checksum": False}})
        engine.train_batch(batch())
        assert engine._last_metrics.checksum is None
        assert engine._sdc.agreement_bytes(engine._last_metrics) == b""

    def test_serial_overlap_stands_down_loudly(self):
        """The serial schedule's step is two programs with a host phase
        between — not one replayable unit. The sentry must stand down
        (no audits), never audit garbage."""
        engine = plain_engine(
            extra={"sdc": {"audit_interval": 1},
                   "zero_optimization": {
                       "stage": 3, "stage3_param_persistence_threshold": 0},
                   "overlap": {"schedule": "serial"}})
        mgr = engine._sdc
        assert mgr is not None and not mgr.active
        assert not mgr.checksum_armed
        engine.train_batch(batch())
        assert mgr.audits == 0
        assert engine._last_metrics.checksum is None


# ------------------------------------------------------ poison-free ladder
class TestPoisonLadder:
    def test_ring_checksums_stamped_and_host_rot_skipped(self):
        from deepspeed_tpu.resilience import rewind as rw

        engine = plain_engine(rewind={"ram_interval": 1, "keep": 4},
                              extra={"sdc": {"audit_interval": 100}})
        assert engine._rewind.checksummer is not None   # ring_verify armed
        for i in range(1, 4):
            engine.train_batch(batch(i))
        snaps = rw.ram_snapshots()
        assert [s.step for s in snaps] == [1, 2, 3]
        assert all(s.checksum is not None for s in snaps)
        # rot the newest snapshot's host copy: the restore walk must
        # condemn it and land on @2
        key = next(k for k in sorted(snaps[-1].flat)
                   if np.asarray(snaps[-1].flat[k]).size > 1)
        rotted = np.array(snaps[-1].flat[key], copy=True)
        rotted.reshape(-1).view(np.uint8)[0] ^= 1
        snaps[-1].flat[key] = rotted
        info = engine._rewind.restore_from_ram()
        assert info is not None and info["snapshot_step"] == 2
        assert snaps[-1].poisoned

    def test_newest_skips_poisoned_entries(self):
        from deepspeed_tpu.resilience import rewind as rw

        engine = plain_engine(rewind={"ram_interval": 1, "keep": 4},
                              extra={"sdc": {"audit_interval": 100}})
        for i in range(1, 4):
            engine.train_batch(batch(i))
        snaps = rw.ram_snapshots()
        snaps[-1].poisoned = True
        assert engine._rewind.newest().step == 2


# ----------------------------------------------------- rewind-only drill
@pytest.mark.chaos
class TestRewindOnlyDrill:
    def test_bitflip_detected_blamed_rewound_retrodden(self):
        """Quarantine off: a flip on device 5 at audit step 4 is caught
        by the replay audit, blamed to device 5 by bisection, the
        newer-than-clean ring entry is poisoned, the run rewinds to the
        audited-clean @2 — and the re-trodden steps reproduce the clean
        oracle's losses BITWISE (the flip is spent, determinism holds)."""
        from deepspeed_tpu.resilience import chaos as chaos_mod
        from deepspeed_tpu.resilience import rewind as rw

        sdc_cfg = {"sdc": {"audit_interval": 2, "quarantine": False}}
        oracle = plain_engine(rewind={"ram_interval": 1, "keep": 8},
                              extra=sdc_cfg)
        want = run_by_step(oracle, until=5, record={})
        assert oracle._sdc.verdicts == 0

        rw.clear_ram_snapshots()
        engine = plain_engine(
            rewind={"ram_interval": 1, "keep": 8},
            extra={**sdc_cfg,
                   "resilience": {"chaos": {
                       "enabled": True, "seed": 7, "bitflip_at_step": 4,
                       "bitflip_rate": 1.0, "bitflip_device": 5}}})
        got = run_by_step(engine, until=5, record={})

        mgr = engine._sdc
        assert mgr.verdicts == 1
        v = mgr.last_verdict
        assert v.step == 4 and v.device == 5
        assert v.evidence["suspect_devices"] == [5]
        assert v.evidence["last_clean_step"] == 2
        assert len(v.evidence["probes"]) == 3
        # recovery: in-place rewind to the newest audited-clean snapshot
        rec = engine._last_recovery
        assert rec["reason"] == "sdc"
        assert rec["tier"] == "ram" and rec["snapshot_step"] == 2
        assert any(s.poisoned for s in rw.ram_snapshots())
        # the injector actually fired, exactly once
        log = chaos_mod.active_injector().log
        assert any("bitflip dev5" in a for _, a, _ in log)
        # re-trodden audit at step 4 came back clean
        assert mgr.last_clean_step == 4
        # losses bitwise-match the clean oracle, step for step
        assert got == want

    def test_max_verdicts_escalates_to_sdc_error(self):
        from deepspeed_tpu.resilience.sdc import SdcError

        engine = plain_engine(
            extra={"sdc": {"audit_interval": 2, "quarantine": False,
                           "max_verdicts": 0},
                   "resilience": {"chaos": {
                       "enabled": True, "seed": 3, "bitflip_at_step": 2,
                       "bitflip_rate": 1.0, "bitflip_device": 3}}})
        engine.train_batch(batch(1))
        with pytest.raises(SdcError, match="max_verdicts"):
            engine.train_batch(batch(2))
        # the verdict was still recorded before giving up
        assert engine._sdc.last_verdict.device == 3


# ------------------------------------------------------- THE evict drill
@pytest.mark.chaos
class TestEvictDrill:
    @pytest.mark.incident_drill(device=5)
    def test_THE_drill_bitflip_blamed_evicted_8_to_6_priced(
            self, tmp_path, incident_forensics):
        """The acceptance drill, end to end: 8-device run, chaos flips a
        bit on device 5 at audit step 6 — detected by the replay audit,
        blamed to device 5, quarantined via a chaos-shrink-shaped
        FleetResizeEvent (24 % 7 != 0, so the survivor world steps down
        to 6), resumed resharded from the clean @4 ring snapshot, losses
        bitwise-matching a clean oracle continuation — and the whole
        event priced in `ds_prof goodput` and the `ds_metrics` footer."""
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.elasticity import resize as rz
        from deepspeed_tpu.resilience import rewind as rw

        save = str(tmp_path / "ckpt")
        tel = str(tmp_path / "tel")
        sdc_cfg = {"sdc": {"audit_interval": 2}}

        # ---- oracle: replicate the pre-verdict phase, evict device 5 by
        # hand, record the clean 6-survivor continuation losses
        eng8 = survivor_engine(rewind={"ram_interval": 2, "keep": 2},
                               extra=sdc_cfg)
        seq = batch_seq()
        for _ in range(4):
            eng8.train_batch(next(seq))              # ring snapshots @2, @4
        rz.quarantine_device(5)
        rz.set_fleet_target(6)
        eng6 = survivor_engine(rewind={"ram_interval": 2, "keep": 2},
                               extra=sdc_cfg)
        path, _ = eng6.load_checkpoint(save)         # empty dir: RAM tier
        assert str(path) == "ram://step4"
        assert 5 not in [d.id for d in eng6.mesh.devices.flatten()]
        oracle_seq = batch_seq()
        oracle_losses = [float(eng6.train_batch(next(oracle_seq)))
                         for _ in range(6)]
        rz.clear_fleet_events()                      # quarantine cleared too
        rw.clear_ram_snapshots()
        comm.cdb = None

        # ---- THE drill, under the elastic agent with telemetry on
        def factory():
            return survivor_engine(
                rewind={"ram_interval": 2, "keep": 2},
                extra={**sdc_cfg,
                       # the verdict is an error-severity blackbox event:
                       # the flight recorder must dump an incident bundle
                       # the incident_forensics teardown merges + blames
                       "blackbox": {},
                       "telemetry": {"enabled": True, "output_dir": tel,
                                     "prometheus": False, "trace": True,
                                     "flush_interval": 1}})

        install_chaos(ChaosInjector(seed=7, bitflip_at=6, bitflip_rate=1.0,
                                    bitflip_device=5))
        losses = []
        agent = DSElasticAgent(factory, save, checkpoint_interval=100,
                               max_restarts=2, install_signal_handlers=False)
        try:
            out = agent.run(batch_seq, num_steps=10,
                            step_callback=lambda s, l: losses.append(
                                (s, float(l))))
        finally:
            telemetry.flush()
            telemetry.deconfigure()
        assert out["status"] == "complete"
        assert out["final_step"] == 10
        assert out["restarts"] == 1
        # resumed resharded on the 6 survivors — WITHOUT the blamed chip
        assert dict(agent.engine.mesh.shape)["data"] == 6
        assert 5 not in [d.id for d in agent.engine.mesh.devices.flatten()]
        drill = out["restart_log"][0]
        assert "FleetResizeEvent" in drill["error"]
        assert drill["tier"] == "ram"
        assert drill["resize"] == {"kind": "shrink", "from_world": 8,
                                   "to_world": 6}
        assert drill["steps_lost"] is not None
        assert drill["steps_lost"] <= 2              # <= ram_interval
        # the verdict landed in the shared restart_log.jsonl timeline
        with open(os.path.join(tel, "restart_log.jsonl")) as f:
            recs = [json.loads(l) for l in f if l.strip()]
        verdicts = [r for r in recs if r.get("event") == "sdc_verdict"]
        assert len(verdicts) == 1
        assert verdicts[0]["step"] == 6 and verdicts[0]["device"] == 5
        assert verdicts[0]["evidence"]["suspect_devices"] == [5]
        # losses bitwise-continue from the restored step: the re-trodden
        # window equals the clean 6-survivor oracle
        post = [l for _, l in losses[-6:]]
        assert post == oracle_losses

        # ---- PRICED: ds_prof goodput annotates the restart, ds_metrics
        # renders the sdc footer line
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_prof"),
             "goodput", tel], capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        assert "restart" in proc.stdout
        assert "shrink 8->6 resharded" in proc.stdout
        assert "recovered from ram tier" in proc.stdout
        proc2 = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_metrics"), tel],
            capture_output=True, text=True)
        assert proc2.returncode == 0, proc2.stderr
        assert "sdc:" in proc2.stdout
        assert "dev5" in proc2.stdout
        assert "evicted 1 device(s)" in proc2.stdout


# ----------------------------------------------------------- observability
class TestObservability:
    def test_render_sdc_line(self):
        from deepspeed_tpu.goodput.tail import render_sdc_line

        assert render_sdc_line({}, {}) is None
        line = render_sdc_line(
            {"sdc/audit_interval": 50.0, "sdc/last_clean_step": 200.0,
             "sdc/last_verdict_step": 250.0, "sdc/last_verdict_device": 5.0},
            {"sdc/verdicts{device=5}": 1.0, "sdc/evictions{device=5}": 1.0,
             "sdc/poisoned_snapshots": 2.0,
             "resilience/sdc_rewinds{tier=ram}": 1.0})
        assert "sdc:" in line
        assert "audit every 50 step(s)" in line
        assert "last clean @step 200" in line
        assert "VERDICTS 1 (1x dev5)" in line
        assert "last blamed dev5 @step 250" in line
        assert "evicted 1 device(s)" in line
        assert "poisoned 2 snapshot(s)" in line
        assert "sdc rewinds 1" in line

    def test_render_sdc_line_quiet_run(self):
        from deepspeed_tpu.goodput.tail import render_sdc_line

        line = render_sdc_line({"sdc/audit_interval": 50.0,
                                "sdc/last_clean_step": 100.0},
                               {"sdc/audits": 2.0})
        assert "no verdicts" in line

    def test_ds_top_frame_has_sdc_line(self):
        from deepspeed_tpu.goodput.top import render_frame

        records = [
            {"kind": "gauge", "name": "sdc/audit_interval", "value": 50.0},
            {"kind": "gauge", "name": "sdc/last_clean_step", "value": 150.0,
             "step": 7},
            {"kind": "counter", "name": "sdc/verdicts",
             "labels": {"device": "5"}, "value": 1.0},
        ]
        frame = render_frame(records)
        assert "sdc:" in frame
        assert "VERDICTS 1" in frame


# ------------------------------------------------------- randomized sweep
def test_randomized_bitflip_sweep():
    """Slow sweep (tests/slow_tests.txt): seeded random device/bit/step
    flips — every one is detected at its audit step, blamed to the
    injected device, and recovered from with the run completing."""
    from deepspeed_tpu.resilience import rewind as rw

    for seed in range(3):
        rng = np.random.RandomState(seed)
        uninstall_chaos()
        rw.clear_ram_snapshots()
        device = int(rng.randint(0, 8))
        bit = int(rng.randint(5, 26))
        at_step = int(rng.randint(2, 6))
        target = ["params", "opt_state", "grads"][int(rng.randint(0, 3))]
        engine = plain_engine(
            rewind={"ram_interval": 1, "keep": 8},
            extra={"sdc": {"audit_interval": 1, "quarantine": False},
                   "resilience": {"chaos": {
                       "enabled": True, "seed": seed + 11,
                       "bitflip_at_step": at_step, "bitflip_rate": 1.0,
                       "bitflip_device": device, "bitflip_bit": bit,
                       "bitflip_target": target}}})
        got = run_by_step(engine, until=6, record={})
        ctx = (seed, device, bit, at_step, target)
        mgr = engine._sdc
        assert mgr.verdicts == 1, ctx
        assert mgr.last_verdict.step == at_step, ctx
        assert mgr.last_verdict.device == device, ctx
        assert engine._last_recovery["reason"] == "sdc", ctx
        assert mgr.last_clean_step == 6, ctx
        assert all(np.isfinite(l) for l in got.values()), ctx


# ------------------------------------------------- audit overhead pricing
def test_audit_overhead_under_budget(tmp_path, tiny_ledger_run):
    """gpt2-tiny with the sentry armed at audit_interval 2 (the three
    timed steps must hold an audit): the ledger entry PRICES the defence
    — an `audit` goodput bucket over the timed window and an
    `sdc_overhead` attribution under the audit_interval^-1 budget (each
    audit replays about one step per interval)."""
    interval = 2
    _, entry = tiny_ledger_run(
        tmp_path, extra={"sdc": {"audit_interval": interval}})
    att = entry["attribution"]
    so = att.get("sdc_overhead")
    assert so is not None, "sdc armed but the entry carries no sdc_overhead"
    assert att["goodput"]["buckets_us"].get("audit", 0.0) > 0.0, \
        "sdc armed but no audit bucket landed in the timed window"
    budget = 1.0 / interval
    assert 0.0 < so < budget, (
        f"sdc_overhead {so:.3f} exceeds the audit_interval^-1 budget "
        f"{budget:.3f}")
