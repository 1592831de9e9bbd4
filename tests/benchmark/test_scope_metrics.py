"""The by-scope readers of a train step (``benchmark/scope_metrics.py``), each
through its own data file under ``benchmark/train_scope_metrics`` as
``benchmark/trace_scope_metrics.py`` reads it: on an ``op_text_seconds`` and
a door's table made by hand, with known answers; the identities the notes
carry; and the three places a reader must return None and not raise. No
number here is a time: the device's seconds are invented.
"""

import types

import pytest

from benchmark import manifest as mf
from benchmark import scope_metrics as sm
from benchmark import trace_scope_metrics as tsm
from deepspeed_tpu.sharding import jit as door

NAMES = sorted(p.stem for p in tsm.SCOPE_DIR.glob("*.json"))
FWD = "jit(step_fn)/jit(main)/jvp(layers)/while/body/closed_call/"
BWD = "jit(step_fn)/jit(main)/transpose(jvp(layers))/while/body/closed_call/" \
      "checkpoint/"
# instruction -> (op_name in the door's table or None for "not in it",
#                 device self seconds over TWO steps)
OPS = {
    "fusion.1": (FWD + "attn/qkv/dot_general", 0.040),
    "flash_fwd.3": (FWD + "attn/core/flash_fwd/pallas_call", 0.020),
    "fusion.2": (BWD + "mlp/mlp/up/dot_general", 0.060),
    "fusion.3": (BWD + "rematted_computation/mlp/mlp/up/dot_general", 0.030),
    "convolution_add_fusion.13.remat": (FWD + "attn/qkv/dot_general", 0.010),
    "fusion.4": ("jit(step_fn)/jit(main)/jvp(head)/dot_general", 0.016),
    "fusion.5": ("jit(step_fn)/jit(main)/transpose(jvp(embed))/scatter-add",
                 0.004),
    "fusion.6": ("jit(step_fn)/jit(main)/optimizer/cast/convert_element_type",
                 0.024),
    "fusion.7": ("jit(step_fn)/jit(main)/accumulate/while/body/add", 0.006),
    "dynamic-slice_bitcast_fusion.1": (
        "jit(step_fn)/jit(main)/transpose(jvp(layers))/while/body/squeeze",
        0.008),
    "while.2": ("jit(step_fn)/jit(main)/jvp(layers)/while", 0.002),
    "fusion.8": (FWD + "moe/moe/experts/ragged_dot", 0.010),
    "fusion.9": (FWD + "kda/mul", 0.005),
    "copy-done.5": ("", 0.003),         # the compiler's own: no metadata
    "fusion.99": (None, 0.001),         # an op of another program
}
TOTAL = sum(sec for _, sec in OPS.values())
MEMORY = {"argument": 10 << 30, "output": 10 << 30, "alias": 10 << 30,
          "temp": 4 << 30, "generated_code": 1 << 20,
          "total": (14 << 30) + (1 << 20)}
WANT = {
    "train.attn_s_per_step": (0.040 + 0.020 + 0.010 + 0.005) / 2,
    "train.mlp_s_per_step": (0.060 + 0.030 + 0.010) / 2,
    "train.head_s_per_step": (0.016 + 0.004) / 2,
    "train.optimizer_s_per_step": (0.024 + 0.006) / 2,
    "train.layer_scan_s_per_step": (0.008 + 0.002) / 2,
    "train.recompute_s_per_step": (0.030 + 0.010) / 2,
    "train.unscoped_frac": 100.0 * (0.003 + 0.001) / TOTAL,
    "train.step_hbm_frac": 100.0 * MEMORY["total"] / (16 << 30),
}


def text(name):
    return (f"%{name} = bf16[8,1024,1536]{{2,1,0:T(8,128)(2,1)}} "
            f"fusion(bf16[8,1024,1536]{{2,1,0}} %p.1), kind=kOutput")


class Record:
    """What the readers use of a ``ProgramRecord``."""

    def __init__(self, name="step_fn", table=True, raises=None):
        self.jitted = types.SimpleNamespace(__name__=name)
        self.table = {n: op for n, (op, _) in OPS.items() if op is not None} \
            if table else None
        self.raises, self.lowered = raises, 0

    def instruction_scopes(self):
        self.lowered += 1
        if self.raises:
            raise self.raises
        return self.table

    def memory(self):
        return MEMORY if self.table is not None else None


def make_ctx(n_devices=1):
    return types.SimpleNamespace(
        notes={}, peaks={"hbm_bytes": 16 << 30},
        trace={"n_devices": n_devices, "busy_s": TOTAL,
               "op_text_seconds": {text(n): s for n, (_, s) in OPS.items()},
               "modules": {"jit_step_fn(7)": [0.12, 0.12],
                           "jit_draw(3)": [0.5]}})


def read(name, ctx):
    spec, custom = tsm.metric_spec_from(tsm.SCOPE_DIR, mf.metric_spec)(
        "per_layer", name)
    assert spec["moves"] == "train_tok_s_chip"
    return custom(ctx, spec["params"])


@pytest.fixture
def step(monkeypatch):
    record = Record()
    monkeypatch.setattr(door, "program_table", lambda: {
        "engine/init_state": Record("init"),
        "engine/train_batch[gas=1]": record})
    return record


def test_the_eight_files_are_there():
    assert NAMES == sorted(WANT)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_made_step(name, step):
    assert read(name, make_ctx()) == pytest.approx(WANT[name], rel=1e-12)


def test_the_notes_carry_the_table_and_its_identities(step):
    ctx = make_ctx()
    for name in NAMES:
        read(name, ctx)
    assert step.lowered == 1            # one re-lower a run, not one a metric
    n = ctx.notes
    assert n["scope_relower_s"] >= 0
    assert n["scope_matched_share"] == pytest.approx(
        100.0 * (TOTAL - 0.001) / TOTAL)
    assert n["scope_residual_s"] == pytest.approx(0.0, abs=1e-12)
    assert n["step_program_memory"] == MEMORY
    by = n["device_by_scope"]           # ms a step, the finer names kept
    assert by["attn/qkv"] == {"fwd": 20.0, "recompute": 5.0}
    assert by["mlp/up"] == {"bwd": 30.0, "recompute": 15.0}
    assert by["layers"] == {"bwd": 4.0, "fwd": 1.0}
    assert by["optimizer/cast"] == {"none": 12.0}
    assert by[sm.UNSCOPED] == {"none": 2.0}
    assert [k for k, _ in n["unscoped_top"]] == [
        "copy-done bf16[8,1024,1536]", "fusion bf16[8,1024,1536]"]
    # the five scope metrics and the unscoped time tile the busy time
    five = sum(WANT[f"train.{g}_s_per_step"] for g in sm.GROUPS)
    assert five + (0.003 + 0.001) / 2 == pytest.approx(TOTAL / 2)


def test_times_are_averaged_over_the_steps_of_the_step_program_alone(step):
    ctx = make_ctx()
    ctx.trace["modules"]["jit_step_fn(7)"] = [0.12] * 4
    assert read("train.attn_s_per_step", ctx) == pytest.approx(
        WANT["train.attn_s_per_step"] / 2)


@pytest.mark.parametrize("name", sorted(WANT))
def test_no_device_plane_reads_nothing(name, step):
    ctx = make_ctx(n_devices=0)
    assert read(name, ctx) is None
    ctx = make_ctx()
    ctx.trace = None
    assert read(name, ctx) is None and ctx.notes == {} and not step.lowered


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_door_without_the_method_reads_nothing(name, step, monkeypatch):
    """The benchmark's files are laid over the commit BEFORE this one too:
    its ``ProgramRecord`` has no ``instruction_scopes``."""
    monkeypatch.delattr(door.ProgramRecord, "instruction_scopes")
    ctx = make_ctx()
    assert read(name, ctx) is None and not step.lowered


@pytest.mark.parametrize("case", ["collected", "refused", "no_step_program",
                                  "no_step_in_window"])
def test_a_record_that_cannot_be_lowered_reads_nothing(case, monkeypatch):
    record = {"collected": Record(table=False),
              "refused": Record(raises=RuntimeError("Ran out of memory")),
              "no_step_program": Record("generate"),
              "no_step_in_window": Record()}[case]
    monkeypatch.setattr(door, "program_table", lambda: {"x": record})
    ctx = make_ctx()
    if case == "no_step_in_window":
        del ctx.trace["modules"]["jit_step_fn(7)"]
    assert [read(name, ctx) for name in NAMES] == [None] * len(NAMES)
    assert record.lowered <= 1
    if case == "refused":
        assert "Ran out of memory" in ctx.notes["scope_relower_error"]


def test_the_script_adds_the_files_for_a_train_cell_alone():
    real = mf.load_manifest()
    grown = tsm.with_scope_metrics(real, "gpt2-760m.train.z1.gas4")
    assert grown["per_layer"][:len(real["per_layer"])] == real["per_layer"]
    added = grown["per_layer"][len(real["per_layer"]):]
    assert [m["name"] for m in added] == NAMES
    assert all(m["workloads"] == ["gpt2-760m.train.z1.gas4"] for m in added)
    assert {(m["layer"], m["source"]) for m in added} == {
        ("models", "device_trace"), ("train engine", "device_trace"),
        ("device", "device_trace"), ("device", "program_counter")}
    assert tsm.with_scope_metrics(real, "gpt2-xl.serve.doc.c1")["per_layer"] \
        == real["per_layer"]
    # the files wait OUTSIDE layer_metrics: an older test pins that no
    # waiting file there moves what a train cell reports
    from benchmark.trace_metric_files import with_metric_files
    assert with_metric_files(real, "gpt2-760m.train.z1")["per_layer"] == \
        real["per_layer"]


def test_the_real_door_has_both_reads():
    """What ``step_record`` asks of the program, so that a rename there
    fails here first."""
    assert callable(door.ProgramRecord.instruction_scopes)
    assert callable(door.ProgramRecord.memory)
    assert callable(door.program_table)
