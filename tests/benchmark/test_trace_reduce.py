"""The trace reduction: on a synthetic trace whose answers are known by
construction, and on small traces recorded on the v5e (one and four chips)."""

from pathlib import Path

import pytest

from benchmark import stats, trace_reduce
from tests.benchmark.xspace_text import xspace

FIXTURES = Path(__file__).parent / "fixtures"

# One device, a window of 1000 us. Two program runs (100-400, 600-900).
# In the first: a while (100-300) that holds fusion.1 (100-180) and
# all-gather.1 (180-300), of which fusion.2 on... nothing overlaps: exposed.
# In the second: all-reduce.7 (600-700) wholly under... ops on one line are
# serial, so overlap comes from the second device below.
DEV0_OPS = [("while.3", 100, 200), ("fusion.1", 100, 80),
            ("all-gather.1", 180, 120), ("fusion.2", 300, 100),
            ("fusion.9", 600, 150), ("all-reduce.7", 750, 100),
            ("copy.4", 860, 40)]
DEV0_MODULES = [("jit_train_step(123)", 100, 300), ("jit_train_step(123)", 600, 300)]
HOST = [("bench/window", 0, 1000), ("bench/feed_batch", 20, 60),
        ("bench/train_step", 90, 330), ("bench/feed_batch", 430, 100),
        ("bench/train_step", 580, 330), ("other/noise", 0, 5)]


@pytest.fixture(scope="module")
def synthetic():
    import jax

    text = xspace({
        "/device:TPU:0": {"XLA Ops": DEV0_OPS, "XLA Modules": DEV0_MODULES,
                          "Steps": [("0", 100, 300)]},
        "/device:TPU:0 SparseCore": {"XLA Ops": [("ignored", 0, 1000)]},
        "/host:CPU": {"python": HOST},
    })
    return trace_reduce.load(jax.profiler.ProfileData.from_text_proto(text))


@pytest.fixture(scope="module")
def summary(synthetic):
    return trace_reduce.reduce(
        synthetic, [("feed_batch", "feed_batch"), ("train_step", "in_step")],
        "between_steps")


def us(x):
    return pytest.approx(x * 1e-6, abs=1e-12)


def test_planes_lines_and_annotations_are_found(synthetic):
    assert [d.name for d in synthetic.devices] == ["/device:TPU:0"]
    assert len(synthetic.devices[0].ops) == 7
    assert len(synthetic.devices[0].modules) == 2
    assert [a[0] for a in synthetic.annotations] == [
        "window", "feed_batch", "train_step", "feed_batch", "train_step"]
    assert synthetic.window == (us(0), us(1000))


def test_busy_and_idle(summary):
    # busy: 100-400 and 600-850 and 860-900
    assert summary["window_s"] == us(1000)
    assert summary["busy_s"] == us(300 + 250 + 40)
    assert summary["idle_frac"] == pytest.approx(0.41)


def test_per_op_sums_are_self_time(summary):
    ops = summary["op_seconds"]
    assert ops["while"] == us(0)            # its body covers it whole
    assert ops["fusion"] == us(80 + 100 + 150)
    assert ops["all-gather"] == us(120)
    assert ops["all-reduce"] == us(100)
    assert ops["copy"] == us(40)
    assert sum(ops.values()) == us(590)     # no double count of the while


def test_collectives_on_a_serial_line_are_all_exposed(summary):
    assert summary["collective_s"] == us(220)
    assert summary["collective_exposed_s"] == us(220)


def test_idle_gaps_are_named_by_what_the_host_was_in(summary):
    gaps = summary["idle_gaps"]
    # inside program runs: 850-860
    assert gaps["inside_program"] == us(10)
    # between runs: 0-100 and 400-600 and 900-1000
    assert gaps["feed_batch"] == us(60 + 100)
    # host inside train_step, device not yet (90-100, 580-600) or done
    # (400-420, 900-910)
    assert gaps["in_step"] == us(10 + 20 + 20 + 10)
    assert gaps["between_steps"] == us(20 + 10 + 10 + 50 + 90)
    assert sum(gaps.values()) == us(410)


def test_modules_and_breakdown(summary):
    assert summary["modules"] == {"jit_train_step(123)": [us(300), us(300)]}
    b = trace_reduce.breakdown(summary)
    assert b["device_ops"][0] == ["fusion", us(330)]
    assert b["idle_gaps"][0][0] == "between_steps"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_exposed_collective_time_is_what_no_compute_covers():
    """Two 'lines' worth of overlap folded into leaf intervals: a collective
    from 0-100 with compute from 40-70 under it leaves 70 exposed."""
    import jax

    text = xspace({"/device:TPU:0": {"XLA Ops": [
        ("all-gather-start.1", 0, 100), ("fusion.1", 40, 30)],
        "XLA Modules": [("jit_f(1)", 0, 100)]}})
    tr = trace_reduce.load(jax.profiler.ProfileData.from_text_proto(text))
    # fusion.1 is nested in the collective's interval: as a child it is the
    # leaf, and the collective's own (self) time is the exposed part
    s = trace_reduce.reduce(tr)
    assert s["op_seconds"]["all-gather-start"] == us(70)
    assert s["busy_s"] == us(100) and s["idle_frac"] == pytest.approx(0.0)


def test_two_devices_are_averaged():
    import jax

    text = xspace({
        "/device:TPU:0": {"XLA Ops": [("fusion.1", 0, 100)],
                          "XLA Modules": [("jit_f(1)", 0, 100)]},
        "/device:TPU:1": {"XLA Ops": [("fusion.1", 0, 50),
                                      ("all-reduce.2", 50, 50)],
                          "XLA Modules": [("jit_f(1)", 0, 100)]},
        "/host:CPU": {"python": [("bench/window", 0, 200)]}})
    s = trace_reduce.reduce(
        trace_reduce.load(jax.profiler.ProfileData.from_text_proto(text)))
    assert s["n_devices"] == 2 and s["busy_s"] == us(100)
    assert s["idle_frac"] == pytest.approx(0.5)
    assert s["collective_exposed_s"] == us(25)
    assert s["idle_gaps"] == {"host_other": us(100)}


@pytest.mark.parametrize("name,key", [
    ("%fusion.123", "fusion"), ("all-gather-start.4.1", "all-gather-start"),
    ("copy", "copy"), ("custom-call.12", "custom-call"), ("7", "7")])
def test_op_key(name, key):
    assert trace_reduce.op_key(name) == key


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)])])
def test_interval_subtraction(a, b, want):
    assert stats.subtract(a, b) == want


def test_union_total_clip():
    u = stats.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)] and stats.total(u) == 6
    assert stats.clip(u, 2, 6) == [(2, 3), (5, 6)]


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4], 50, 2.5), ([5], 90, 5.0), ([], 50, None),
    ([1, 2, 3, 4, 5], 90, 4.6), ([3, 1, 2], 50, 2.0)])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == want


def test_find_xplane_wants_a_trace(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(str(tmp_path))
    d = tmp_path / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(b"")
    assert trace_reduce.find_xplane(str(tmp_path)).endswith("vm.xplane.pb")


# ------------------------------------------- names as the TPU writes them
FUSION = ('%fusion.66 = bf16[1600]{0:T(1024)(128)(2,1)S(1)} fusion(bf16[48,6400,'
          '1600]{2,1,0:T(8,128)(2,1)} %get-tuple-element.1521, bf16[6400]{0} '
          '%all-gather.3), kind=kLoop, calls=%fused_computation.1.clone.clone')
MOSAIC = ('%checkpoint.18 = (bf16[128,1024,96]{2,1,0:T(8,128)(2,1)}, bf16[128,'
          '1024,96]{2,1,0}) custom-call(bf16[128,1024,96]{2,1,0} %bitcast.382), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
GATHER = ('%all-gather-start.4 = (bf16[2,8]{1,0}, bf16[8,8]{1,0}) '
          'all-gather-start(bf16[2,8]{1,0} %fusion.9), replica_groups={}')


@pytest.mark.parametrize("text,name,key,collective", [
    (FUSION, "fusion.66", "fusion bf16[1600]", False),   # an operand's name
    (MOSAIC, "checkpoint.18", "checkpoint bf16[128,1024,96]", False),
    (GATHER, "all-gather-start.4", "all-gather-start bf16[2,8]", True),
    ("%while.15 = (s32[]{:T(128)}, bf16[8,1024]{1,0}) while(", "while.15",
     "while s32[]", False),
    ("all-reduce.7", "all-reduce.7", "all-reduce", True)])
def test_hlo_text_names(text, name, key, collective):
    assert trace_reduce.instruction_name(text) == name
    assert trace_reduce.op_key(text) == key
    assert trace_reduce.is_collective(text) is collective


def test_a_metrics_pattern_is_tried_on_the_whole_instruction():
    import jax

    text = xspace({"/device:TPU:0": {
        "XLA Ops": [(FUSION, 0, 40), (MOSAIC, 40, 60), (GATHER, 100, 10)],
        "XLA Modules": [("jit_step_fn(9)", 0, 110)],
        "Async XLA Ops": [(GATHER, 100, 50)]}})
    s = trace_reduce.reduce(
        trace_reduce.load(jax.profiler.ProfileData.from_text_proto(text)))
    assert s["op_text_seconds"][MOSAIC] == us(60)
    assert s["op_seconds"]["checkpoint bf16[128,1024,96]"] == us(60)
    assert s["collective_exposed_s"] == us(10)      # not the fusion that reads it
    assert s["collective_inflight_s"] == us(10)     # clipped to the window


# --------------------------------------------- recorded on the v5e (PR 23)
@pytest.fixture(scope="module")
def recorded_1chip():
    """Three runs of a small jitted scan on ONE v5e chip under
    ``bench/window``, ``bench/feed_batch`` (2 ms sleeps) and
    ``bench/train_step`` annotations; 25 KB (my chip run, PR 23)."""
    return trace_reduce.load(str(FIXTURES / "v5e_1chip.xplane.pb"))


def test_recorded_trace_planes_and_names(recorded_1chip):
    tr = recorded_1chip
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    dev = tr.devices[0]
    assert [m[2].split("(")[0] for m in dev.modules] == ["jit_step"] * 3
    assert len(dev.ops) == 30 and all(n.startswith("%") for _, _, n in dev.ops)
    assert [a[0] for a in tr.annotations] == ["window"] + [
        "feed_batch", "train_step"] * 3
    w0, w1 = tr.window
    assert 0.005 < w1 - w0 < 0.1


def test_recorded_trace_reduces_to_sane_numbers(recorded_1chip):
    s = trace_reduce.reduce(
        recorded_1chip, [("feed_batch", "feed_batch"),
                         ("train_step", "in_step")], "between_steps")
    assert s["n_devices"] == 1 and 0 < s["busy_s"] < s["window_s"]
    # a 3 us program three times in an 11 ms window: the chip idles
    assert 0.99 < s["idle_frac"] < 1.0
    assert sum(s["op_seconds"].values()) == pytest.approx(s["busy_s"], rel=1e-4)
    assert "convolution_tanh_fusion bf16[8,256]" in s["op_seconds"]
    assert "while s32[]" in s["op_seconds"]          # self time only: ~0
    assert s["op_seconds"]["while s32[]"] < 1e-6
    assert s["collective_s"] == 0.0
    gaps = s["idle_gaps"]
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-6)
    assert gaps["feed_batch"] > 0.006                # three 2 ms sleeps
    assert max(gaps, key=gaps.get) == "feed_batch"
    assert len(s["modules"]) == 1
    assert len(next(iter(s["modules"].values()))) == 3


@pytest.fixture(scope="module")
def recorded_4chip():
    """The same program with its batch sharded over the FOUR chips of a
    v5e host: the sum at the end is an all-reduce; 58 KB (my chip run,
    PR 23)."""
    return trace_reduce.load(str(FIXTURES / "v5e_4chip.xplane.pb"))


def test_recorded_four_chip_trace_has_a_plane_per_chip(recorded_4chip):
    tr = recorded_4chip
    assert [d.name for d in tr.devices] == [f"/device:TPU:{i}" for i in range(4)]
    assert all(len(d.ops) == 33 and len(d.modules) == 3 for d in tr.devices)
    names = {trace_reduce.instruction_name(n) for d in tr.devices
             for _, _, n in d.ops}
    assert "all-reduce" in names
    assert [n for n in names if trace_reduce.is_collective(n)] == ["all-reduce"]


def test_recorded_four_chip_collective_is_exposed(recorded_4chip):
    s = trace_reduce.reduce(
        recorded_4chip, [("feed_batch", "feed_batch"),
                         ("train_step", "in_step")], "between_steps")
    assert s["n_devices"] == 4
    # the core's own line is serial: while the all-reduce op is on it
    # nothing else runs there, so all of it is exposed
    assert s["collective_s"] > 0
    assert s["collective_exposed_s"] == pytest.approx(s["collective_s"])
    assert s["op_seconds"]["all-reduce bf16[]"] == pytest.approx(s["collective_s"])
    assert max(s["op_seconds"], key=s["op_seconds"].get) == "all-reduce bf16[]"
    assert sum(s["op_seconds"].values()) == pytest.approx(s["busy_s"], rel=1e-4)
    assert sum(s["idle_gaps"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-6)
    assert 0.99 < s["idle_frac"] < 1.0
