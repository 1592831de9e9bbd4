"""ds_roofline tests — analytic roofline over the compiled HLO.

Tier-1 keeps the cheap spine: the hlo_model compute-op units (dot /
fusion / tuple-fusion / convolution / while-body-once / convert — the
HloCostAnalysis counting conventions, probe-calibrated), the chips
table pinned against the accelerator's peak dicts, the pure analysis
math (bound classification, mfu ceiling, decode MBU units), ONE
gpt2-tiny ZeRO-3 engine on the 8-device mesh (regex flops vs
``compiled.cost_analysis()`` within 5%, the ledger hoist, the top
memory-bound fusion named), the mfu_gap gate matrix, the no-jax
``bin/ds_roofline`` subprocess, the schema cross-fields, and the strict
no-op sys.modules assertion.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model, synthetic_lm_batch

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ROOF_MOD = "deepspeed_tpu.analysis.roofline"
CHIPS_MOD = "deepspeed_tpu.analysis.chips"


def _reset():
    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.sharding import mesh as smesh
    from deepspeed_tpu.sharding.jit import reset_program_table

    comm.cdb = None
    smesh.reset_global_mesh()
    reset_program_table()


def _mk_engine(extra=None, stage=3, bs=8):
    cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                     n_layer=2, n_head=4, use_flash_attention=False)
    dcfg = {"train_batch_size": bs,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": stage,
                                  "stage3_param_persistence_threshold": 0},
            "tpu": {"data": 8}, "steps_per_print": 0}
    dcfg.update(extra or {})
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2Model(cfg),
                                               config=dcfg)
    return engine, cfg


# A hand-written post-GSPMD-shaped module: one dot (annotated contracting
# dims), one fusion calling an add+tanh computation, one convert root.
DOT_FUSION_TEXT = """\
HloModule test_module, is_scheduled=true, entry_computation_layout=\
{(f32[64,128]{1,0}, f32[128,64]{1,0})->bf16[64,64]{1,0}}, num_partitions=8

%fused_add_tanh (p0.1: f32[64,64], p1.1: f32[64,64]) -> f32[64,64] {
  %p0.1 = f32[64,64]{1,0} parameter(0)
  %p1.1 = f32[64,64]{1,0} parameter(1)
  %add.1 = f32[64,64]{1,0} add(f32[64,64]{1,0} %p0.1, f32[64,64]{1,0} %p1.1)
  ROOT %tanh.1 = f32[64,64]{1,0} tanh(f32[64,64]{1,0} %add.1)
}

ENTRY %main (a: f32[64,128], b: f32[128,64]) -> bf16[64,64] {
  %a = f32[64,128]{1,0} parameter(0)
  %b = f32[128,64]{1,0} parameter(1)
  %dot.2 = f32[64,64]{1,0} dot(f32[64,128]{1,0} %a, f32[128,64]{1,0} %b), \
lhs_contracting_dims={1}, rhs_contracting_dims={0}, \
metadata={op_name="jit(step)/dot_general" source_file="model.py" \
source_line=42}
  %fusion.1 = f32[64,64]{1,0} fusion(f32[64,64]{1,0} %dot.2, \
f32[64,64]{1,0} %dot.2), kind=kLoop, calls=%fused_add_tanh
  ROOT %convert.3 = bf16[64,64]{1,0} convert(f32[64,64]{1,0} %fusion.1)
}
"""


# -------------------------------------------------- hlo_model compute units
@pytest.mark.analysis
class TestHloComputeModel:
    def _ops(self, text):
        from deepspeed_tpu.analysis.hlo_model import parse_hlo_module

        m = parse_hlo_module(text)
        return m, {op.name: op for op in m.compute_ops}

    def test_dot_fusion_convert_costs(self):
        """The probe-calibrated conventions: dot = 2·out·contract (from
        lhs_contracting_dims over the lhs OPERAND shape), fusion rolls up
        its called computation's flops/transcendentals but only EXTERNAL
        bytes, convert is 1 flop/element (mixed-precision ZeRO-3 carries
        millions of cast elements — omitting it once put the model 16%
        under XLA), tanh is a transcendental and NEVER flops."""
        m, ops = self._ops(DOT_FUSION_TEXT)
        assert set(ops) == {"dot.2", "fusion.1", "convert.3"}
        dot = ops["dot.2"]
        assert dot.flops == 2 * 64 * 64 * 128
        assert dot.bytes == (64 * 64 * 4) + (64 * 128 * 4 + 128 * 64 * 4)
        assert dot.metadata_op == "jit(step)/dot_general"
        assert dot.source_line == "model.py:42"
        fus = ops["fusion.1"]
        assert fus.flops == 64 * 64            # the fused add
        assert fus.transcendentals == 64 * 64  # the fused tanh
        assert fus.bytes == 3 * (64 * 64 * 4)  # 2 operands + result ONLY
        conv = ops["convert.3"]
        assert conv.flops == 64 * 64
        assert conv.bytes == 64 * 64 * 4 + 64 * 64 * 2
        assert m.total_flops() == dot.flops + fus.flops + conv.flops
        assert m.total_transcendentals() == 64 * 64
        # fused-computation interiors never appear as their own regions
        assert all(op.computation == "main" for op in m.compute_ops)

    def test_tuple_result_fusion(self):
        """A multi-output fusion: tuple result bytes, callee flops and
        transcendentals both roll up."""
        text = """\
HloModule tup, num_partitions=1

%fused_two (p: f32[128]) -> (f32[128], f32[128]) {
  %p = f32[128]{0} parameter(0)
  %m = f32[128]{0} multiply(f32[128]{0} %p, f32[128]{0} %p)
  %e = f32[128]{0} exponential(f32[128]{0} %p)
  ROOT %t = (f32[128]{0}, f32[128]{0}) tuple(f32[128]{0} %m, f32[128]{0} %e)
}

ENTRY %main2 (x: f32[128]) -> (f32[128], f32[128]) {
  %x = f32[128]{0} parameter(0)
  ROOT %fusion.9 = (f32[128]{0}, f32[128]{0}) fusion(f32[128]{0} %x), \
kind=kLoop, calls=%fused_two
}
"""
        _, ops = self._ops(text)
        [fus] = ops.values()
        assert fus.opcode == "fusion"
        assert fus.flops == 128 and fus.transcendentals == 128
        assert fus.bytes == 2 * 128 * 4 + 128 * 4   # tuple result + operand

    def test_convolution_dim_labels(self):
        """conv = 2 · out_elems · (kernel_elems / out_features), the
        output-feature position read from dim_labels."""
        text = """\
HloModule conv, num_partitions=1

ENTRY %c (in: f32[1,8,8,16], k: f32[3,3,16,32]) -> f32[1,8,8,32] {
  %in = f32[1,8,8,16]{3,2,1,0} parameter(0)
  %k = f32[3,3,16,32]{3,2,1,0} parameter(1)
  ROOT %conv = f32[1,8,8,32]{3,2,1,0} convolution(f32[1,8,8,16]{3,2,1,0} \
%in, f32[3,3,16,32]{3,2,1,0} %k), window={size=3x3 pad=1_1x1_1}, \
dim_labels=b01f_01io->b01f
}
"""
        _, ops = self._ops(text)
        # 2 * (1*8*8*32) * (3*3*16) = 589824
        assert ops["conv"].flops == 2 * 2048 * 144

    def test_while_body_counted_once(self):
        """while itself is zero-cost; its body/cond computations appear
        as regions ONCE (HloCostAnalysis shares the convention, so the
        live cross-check stays a ratio of like with like)."""
        text = """\
HloModule wh, num_partitions=1

%body (s: (s32[], f32[256])) -> (s32[], f32[256]) {
  %s = (s32[], f32[256]{0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[256]{0}) %s), index=0
  %v = f32[256]{0} get-tuple-element((s32[], f32[256]{0}) %s), index=1
  %one = s32[] constant(1)
  %i2 = s32[] add(s32[] %i, s32[] %one)
  %v2 = f32[256]{0} multiply(f32[256]{0} %v, f32[256]{0} %v)
  ROOT %r = (s32[], f32[256]{0}) tuple(s32[] %i2, f32[256]{0} %v2)
}

%cond (s2: (s32[], f32[256])) -> pred[] {
  %s2 = (s32[], f32[256]{0}) parameter(0)
  %i3 = s32[] get-tuple-element((s32[], f32[256]{0}) %s2), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %i3, s32[] %n), direction=LT
}

ENTRY %main3 (x0: (s32[], f32[256])) -> (s32[], f32[256]) {
  %x0 = (s32[], f32[256]{0}) parameter(0)
  ROOT %w = (s32[], f32[256]{0}) while((s32[], f32[256]{0}) %x0), \
condition=%cond, body=%body
}
"""
        m, _ = self._ops(text)
        assert m.total_flops() == 1 + 256 + 1   # add + multiply + compare
        comps = {op.computation for op in m.compute_ops}
        assert comps == {"body", "cond"}

    def test_collectives_still_parse_alongside(self):
        """The compute extension must not disturb the ds_xray spine."""
        from deepspeed_tpu.analysis.hlo_model import parse_hlo_module

        text = ("HloModule m, is_scheduled=true, num_partitions=8\n"
                "ENTRY %e (x: f32[128]) -> f32[128] {\n"
                "  %x = f32[128]{0} parameter(0)\n"
                "  %n = f32[128]{0} negate(f32[128]{0} %x)\n"
                "  ROOT %ar = f32[128]{0} all-reduce(f32[128]{0} %n), "
                "channel_id=1, replica_groups=[1,8]<=[8], "
                "use_global_device_ids=true, to_apply=%add\n}\n")
        m = parse_hlo_module(text)
        assert len(m.collectives) == 1
        assert m.collectives[0].kind == "all-reduce"
        assert m.total_flops() == 128           # the negate

    def test_live_probe_matches_cost_analysis(self):
        """One single-device compile: the regex model's flops land
        within 0.1% of ``cost_analysis()`` and transcendentals match
        EXACTLY (dot + elementwise + tanh + convert fusions — shared
        counting conventions, not approximate agreement; the flops side
        tolerates XLA's off-by-one on scalar-reduce corner cases)."""
        import jax.numpy as jnp

        from deepspeed_tpu.analysis.hlo_model import parse_hlo_module

        def f(a, b):
            h = jnp.tanh(a @ b)
            return (h.astype(jnp.bfloat16).astype(jnp.float32) * 2.0).sum()

        c = jax.jit(f).lower(jnp.ones((32, 64)), jnp.ones((64, 16))).compile()
        ca = c.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        m = parse_hlo_module(c.as_text())
        xla_flops = float(ca.get("flops", 0))
        assert xla_flops > 0
        assert abs(m.total_flops() - xla_flops) <= 0.001 * xla_flops
        assert m.total_transcendentals() == int(ca.get("transcendentals", 0))


# ----------------------------------------------------------------- chips
@pytest.mark.analysis
class TestChips:
    def test_accelerator_reads_the_one_table(self, monkeypatch):
        """The live accelerator has no peak numbers of its own: it reads
        its device's row of chips.py, and a device_kind the table does not
        list raises instead of defaulting to some chip."""
        import jax

        from deepspeed_tpu.accelerator import get_accelerator
        from deepspeed_tpu.analysis.chips import resolve_chip

        acc = get_accelerator()
        cpu = resolve_chip("cpu-sim")
        assert acc.peak_flops() == cpu.peak_flops
        assert acc.peak_flops("fp32") == cpu.peak_flops / 2
        assert acc.memory_bandwidth() == cpu.hbm_bytes_per_s
        assert acc.hbm_bytes() == cpu.hbm_bytes     # CPU: no memory_stats

        class Dev:
            platform, device_kind = "tpu", "TPU v5 lite"

            def memory_stats(self):
                return None

        monkeypatch.setattr(jax, "local_devices", lambda: [Dev()])
        assert acc.peak_flops() == resolve_chip("v5e").peak_flops == 197e12
        assert acc.hbm_bytes() == 16 * 1024 ** 3
        Dev.device_kind = "TPU v9 mystery"
        for read in (acc.peak_flops, acc.memory_bandwidth, acc.hbm_bytes):
            with pytest.raises(KeyError, match="TPU v9 mystery"):
                read()

    def test_aliases_and_unknown(self):
        from deepspeed_tpu.analysis.chips import resolve_chip

        assert resolve_chip("v5litepod").name == "v5e"
        assert resolve_chip("V5E").name == "v5e"
        assert resolve_chip("cpu").name == "cpu-sim"
        with pytest.raises(KeyError, match="v5e"):
            resolve_chip("h100")

    def test_detect_and_fp32_halving(self):
        from deepspeed_tpu.analysis.chips import (detect_chip_name,
                                                  resolve_chip)

        assert detect_chip_name("TPU v5 lite", "tpu") == "v5e"
        assert detect_chip_name("TPU v6 lite", "tpu") == "v6e"
        assert detect_chip_name("", "cpu") == "cpu-sim"
        with pytest.raises(KeyError, match="not in the peak table"):
            detect_chip_name("NVIDIA H100", "gpu")
        spec = resolve_chip("v4")
        assert spec.peak_flops_for("float32") == spec.peak_flops / 2
        assert spec.peak_flops_for("bf16") == spec.peak_flops


# --------------------------------------------------------- analysis math
@pytest.mark.analysis
class TestRooflineMath:
    def test_bound_classification_and_ceiling(self):
        from deepspeed_tpu.analysis.roofline import analyze_hlo_text

        rep = analyze_hlo_text(DOT_FUSION_TEXT, chip="v5e",
                               program="fixture")
        by = {r.name: r for r in rep.regions}
        # dot intensity 1M flops / 80KB = 12.8 fl/B < v5e ridge (~240):
        # everything here is memory-bound on a real chip
        assert by["dot.2"].bound == "memory"
        assert rep.top_memory_bound() is not None
        assert 0.0 < rep.mfu_ceiling <= 1.0
        assert rep.predicted_step_s > 0
        assert abs(rep.memory_bound_share() - 1.0) < 1e-9
        # regions sorted by predicted time, the dot's bytes dominate
        assert rep.regions[0].name == "dot.2"
        # render names the program, the chip, and the top region
        text = rep.render(top_k=2)
        assert "fixture" in text and "v5e" in text and "dot.2" in text
        assert "mfu_ceiling" in text

    def test_compute_bound_on_slow_hbm(self):
        """Same program, a chip with proportionally slower HBM: a
        high-intensity dot flips compute-bound."""
        from deepspeed_tpu.analysis.roofline import analyze_hlo_text

        text = """\
HloModule big, num_partitions=1

ENTRY %m (a: f32[1024,1024], b: f32[1024,1024]) -> f32[1024,1024] {
  %a = f32[1024,1024]{1,0} parameter(0)
  %b = f32[1024,1024]{1,0} parameter(1)
  ROOT %dot = f32[1024,1024]{1,0} dot(f32[1024,1024]{1,0} %a, \
f32[1024,1024]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
        rep = analyze_hlo_text(text, chip="cpu-sim")
        # intensity = 2*1024^3 / 12MB ≈ 170 fl/B > cpu-sim ridge (10)
        assert rep.regions[0].bound == "compute"
        assert rep.memory_bound_share() == 0.0

    def test_decode_mbu_ceiling_units(self):
        from deepspeed_tpu.analysis.roofline import decode_mbu_ceiling

        # pure bandwidth-bound step, zero overhead: ceiling is 1.0
        assert decode_mbu_ceiling(1e9, chip="v5e") == 1.0
        # uncredited overhead halves it
        assert abs(decode_mbu_ceiling(1e9, overhead_bytes=1e9,
                                      chip="v5e") - 0.5) < 1e-9
        # a compute-bound fat batch caps it below 1.0
        capped = decode_mbu_ceiling(1e6, flops=1e12, chip="v5e")
        assert 0.0 < capped < 1.0
        assert decode_mbu_ceiling(0.0, chip="v5e") == 0.0

    def test_summary_dict_shape(self):
        from deepspeed_tpu.analysis.roofline import analyze_hlo_text

        s = analyze_hlo_text(DOT_FUSION_TEXT, chip="v4").summary()
        assert s["chip"] == "v4" and s["regions"] == 3
        assert set(s) >= {"program", "predicted_step_us", "mfu_ceiling",
                          "total_flops", "total_bytes",
                          "memory_bound_share", "top_region"}
        assert "flops_vs_xla" not in s       # no live cross-check on text


# -------------------------------------------- the tier-1 gpt2 ZeRO-3 case
@pytest.fixture(scope="module")
def zero3_roofline():
    """ONE 8-dev ZeRO-3 engine under {perf, roofline}: the engine hook
    runs the pass after the first train_batch; everything later tests
    assert on is snapshotted HERE (the conftest autouse reset clears the
    program table after every test)."""
    _reset()
    engine, cfg = _mk_engine(extra={"perf": {}, "roofline": {}})
    batch = synthetic_lm_batch(8, 32, cfg.vocab_size, seed=0)
    engine.train_batch(batch)
    rep = engine._roofline_result
    entry = engine.perf_record("train_mfu", 0.05, "MFU")
    yield engine, rep, entry
    _reset()


@pytest.mark.analysis
@pytest.mark.perf
class TestRooflineZero3:
    def test_regex_flops_within_5pct_of_cost_analysis(self, zero3_roofline):
        """THE acceptance: on the sharded, optimizer-fused, mixed-
        precision train program the regex model and HloCostAnalysis
        count the same flops within 5%."""
        _, rep, _ = zero3_roofline
        assert rep is not None
        agree = rep.flops_agreement()
        assert agree is not None
        assert 0.95 <= agree <= 1.05, agree

    def test_report_names_top_memory_bound_fusion(self, zero3_roofline):
        _, rep, _ = zero3_roofline
        top = rep.top_memory_bound()
        assert top is not None and top.bound == "memory"
        assert top.name in rep.render(top_k=8)
        assert rep.num_partitions == 8
        assert 0.0 < rep.mfu_ceiling < 1.0
        assert rep.memory_bound_share() > 0.5   # tiny model: HBM-dominated

    def test_ledger_entry_hoists_ceiling_and_gap(self, zero3_roofline):
        """An MFU entry recorded under {perf, roofline} carries hoisted
        mfu_ceiling and mfu_gap (= ceiling − measured, clamped at 0) plus
        the attribution summary — what `ds_perf gate --metric mfu_gap`
        reads."""
        _, rep, entry = zero3_roofline
        assert entry["mfu_ceiling"] == round(rep.mfu_ceiling, 4)
        assert entry["mfu_gap"] == round(max(0.0, rep.mfu_ceiling - 0.05), 4)
        roof = entry["attribution"]["roofline"]
        assert roof["chip"] == "cpu-sim"
        assert roof["top_region"]["name"]
        assert roof["memory_bound_share"] > 0.5

    def test_gauges_for_ds_top(self, zero3_roofline):
        """The roofline/* gauges feed the ds_top / ds_metrics line."""
        from deepspeed_tpu.goodput.tail import render_roofline_line

        _, rep, _ = zero3_roofline
        gauges = {"roofline/mfu_ceiling": rep.mfu_ceiling,
                  "roofline/predicted_step_us": 1e6 * rep.predicted_step_s,
                  "roofline/memory_bound_share": rep.memory_bound_share(),
                  "goodput/mfu": 0.05}
        line = render_roofline_line(gauges, {})
        assert line and "mfu ceiling" in line and "memory-bound" in line
        assert render_roofline_line({"goodput/mfu": 0.05}, {}) is None


# ----------------------------------------------------------- mfu_gap gate
@pytest.mark.perf
class TestMfuGapGate:
    def _entry(self, gap, value=0.3):
        return {"metric": "m pretrain MFU (x)", "value": value,
                "unit": "MFU", "samples": [value] * 3,
                "fingerprint": "f", "headline": True,
                "mfu_ceiling": value + gap, "mfu_gap": gap,
                "attribution": {"mfu_ceiling": value + gap}}

    def test_compare_rider_floor_and_direction(self):
        from deepspeed_tpu.perf.ledger import compare

        r = compare(self._entry(0.05), self._entry(0.12))
        assert r["mfu_gap_regressed"] and r["mfu_gap_delta"] > 0
        # sub-floor growth (< 2 MFU points) is noise, not a regression
        assert not compare(self._entry(0.05),
                           self._entry(0.06))["mfu_gap_regressed"]
        # the improvement direction never flags
        assert not compare(self._entry(0.12),
                           self._entry(0.05))["mfu_gap_regressed"]
        # absent on either side: no verdict keys at all
        bare = self._entry(0.05)
        del bare["mfu_gap"]
        assert "mfu_gap_regressed" not in compare(bare, self._entry(0.05))

    def test_gate_exit2_on_synthetic_regression(self, tmp_path):
        from deepspeed_tpu.perf.cli import main as perf_main

        base = tmp_path / "base.jsonl"
        cand = tmp_path / "cand.jsonl"
        base.write_text(json.dumps(self._entry(0.05)) + "\n")
        cand.write_text(json.dumps(self._entry(0.12)) + "\n")
        rc = perf_main(["gate", "--baseline", str(base), "--candidate",
                        str(cand), "--metric", "mfu_gap"])
        assert rc == 2
        ok = perf_main(["gate", "--baseline", str(base), "--candidate",
                        str(base), "--metric", "mfu_gap"])
        assert ok == 0

    def test_gate_exit3_when_attribution_missing(self, tmp_path):
        from deepspeed_tpu.perf.cli import main as perf_main

        base = tmp_path / "base.jsonl"
        cand = tmp_path / "cand.jsonl"
        base.write_text(json.dumps(self._entry(0.05)) + "\n")
        bare = self._entry(0.05)
        del bare["mfu_gap"], bare["mfu_ceiling"], bare["attribution"]
        cand.write_text(json.dumps(bare) + "\n")
        rc = perf_main(["gate", "--baseline", str(base), "--candidate",
                        str(cand), "--metric", "mfu_gap"])
        assert rc == 3
        # --allow-missing downgrades to a warning
        ok = perf_main(["gate", "--baseline", str(base), "--candidate",
                        str(cand), "--metric", "mfu_gap",
                        "--allow-missing"])
        assert ok == 0


# ------------------------------------------------------------- CLI no-jax
@pytest.mark.analysis
class TestCliNoJax:
    def test_report_on_saved_dump_without_jax(self, tmp_path):
        """The ds_prof contract: a saved .hlo dump prices on a box with
        no jax (the bin/ script file-loads the stdlib modules)."""
        blocker = tmp_path / "nojax"
        blocker.mkdir()
        (blocker / "jax.py").write_text(
            "raise ImportError('no jax on this box')\n")
        dump = tmp_path / "step.hlo"
        dump.write_text(DOT_FUSION_TEXT)
        env = {**os.environ, "PYTHONPATH": str(blocker)}
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_roofline"),
             "report", "--hlo", str(dump), "--chip", "v5e"],
            capture_output=True, text=True, env=env)
        assert proc.returncode == 0, proc.stderr
        assert "roofline[" in proc.stdout and "dot.2" in proc.stdout

        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_roofline"),
             "report", "--hlo", str(dump), "--json"],
            capture_output=True, text=True, env=env)
        assert proc.returncode == 0, proc.stderr
        [rep] = json.loads(proc.stdout)
        assert rep["total_flops"] == 2 * 64 * 64 * 128 + 2 * 64 * 64
        assert rep["top_regions"][0]["name"] == "dot.2"

    def test_chips_subcommand_and_unknown_chip(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_roofline"),
             "chips"], capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        for chip in ("v4", "v5e", "v5p", "cpu-sim"):
            assert chip in proc.stdout
        dump = tmp_path / "s.hlo"
        dump.write_text(DOT_FUSION_TEXT)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "ds_roofline"),
             "report", "--hlo", str(dump), "--chip", "h100"],
            capture_output=True, text=True)
        assert proc.returncode == 2
        assert "v5e" in proc.stderr        # the known-chips hint


# ------------------------------------------------------------ config schema
@pytest.mark.analysis
class TestSchemaRoofline:
    BASE = {"train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "steps_per_print": 0}

    def test_unknown_chip_is_error(self):
        from deepspeed_tpu.analysis.schema import walk_config

        findings, _ = walk_config(
            {**self.BASE, "perf": {}, "roofline": {"chip": "h100"}},
            world_size=1)
        hits = [f for f in findings if f.severity == "error"
                and "roofline.chip" in f.citation]
        assert hits and "h100" in hits[0].message

    def test_roofline_without_perf_warns(self):
        from deepspeed_tpu.analysis.schema import walk_config

        findings, _ = walk_config({**self.BASE, "roofline": {}},
                                  world_size=1)
        assert any(f.severity == "warning" and f.citation == "roofline vs perf"
                   for f in findings)
        findings, _ = walk_config({**self.BASE, "perf": {},
                                   "roofline": {"chip": "v5e"}},
                                  world_size=1)
        assert not [f for f in findings if "roofline" in f.citation]

    def test_top_level_did_you_mean(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        with pytest.raises(ValueError, match="roofline"):
            DeepSpeedConfig({**self.BASE, "rooflin": {}}, world_size=1)

    def test_block_typo_did_you_mean(self):
        from deepspeed_tpu.runtime.config import RooflineConfig

        with pytest.raises(ValueError, match="did you mean 'chip'"):
            RooflineConfig(chp="v5e")


# ------------------------------------------------------------ strict no-op
@pytest.mark.analysis
class TestStrictNoOp:
    def _without_modules(self):
        return {m: sys.modules.pop(m) for m in list(sys.modules)
                if m in (ROOF_MOD, CHIPS_MOD)}

    def test_block_absent_never_imports_module(self):
        saved = self._without_modules()
        try:
            _reset()
            engine, cfg = _mk_engine()
            engine.train_batch(synthetic_lm_batch(8, 32, cfg.vocab_size))
            assert not engine._roofline_done
            assert engine._roofline_result is None
            assert ROOF_MOD not in sys.modules
            assert CHIPS_MOD not in sys.modules
        finally:
            sys.modules.update(saved)
            _reset()

    def test_enabled_false_never_imports_module(self):
        saved = self._without_modules()
        try:
            _reset()
            engine, cfg = _mk_engine(extra={"roofline": {"enabled": False}})
            engine.train_batch(synthetic_lm_batch(8, 32, cfg.vocab_size))
            assert not engine._roofline_done
            assert ROOF_MOD not in sys.modules
        finally:
            sys.modules.update(saved)
            _reset()

    def test_perf_entry_without_block_has_no_roofline_keys(self):
        saved = self._without_modules()
        try:
            _reset()
            engine, cfg = _mk_engine(extra={"perf": {}})
            engine.train_batch(synthetic_lm_batch(8, 32, cfg.vocab_size))
            entry = engine.perf_record("train_mfu", 0.05, "MFU")
            assert "mfu_ceiling" not in entry
            assert "mfu_gap" not in entry
            assert "roofline" not in entry.get("attribution", {})
            assert ROOF_MOD not in sys.modules
        finally:
            sys.modules.update(saved)
            _reset()
