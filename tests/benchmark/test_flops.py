"""The GPT-2 family's operation and byte counts against counts made by hand
for both configurations."""

import pytest

from benchmark import manifest as mf
from benchmark.families import gpt2 as flops
from benchmark.readers import roofline

M = mf.load_manifest()
M760 = mf.load_json(mf.config_path(M, "gpt2-760m"))
XL = mf.load_json(mf.config_path(M, "gpt2-xl"))
PEAKS = mf.load_json(mf.BENCH_DIR / "peaks.json")["TPU v5 lite"]


@pytest.mark.parametrize("cfg,layers_part,head_part", [
    # 24 x 12 x 1536^2, 1536 x 50257
    (M760, 679_477_248, 77_194_752),
    # 48 x 12 x 1600^2, 1600 x 50257
    (XL, 1_474_560_000, 80_411_200)])
def test_matmul_parameters(cfg, layers_part, head_part):
    assert flops.matmul_params(cfg) == layers_part + head_part


@pytest.mark.parametrize("cfg,per_token", [
    # 6 x 756,672,000 + 3 x (24 x 2 x 1024^2 x 1536) / 1024
    (M760, 6 * 756_672_000 + 3 * 75_497_472),
    # 6 x 1,554,971,200 + 3 x (48 x 2 x 1024^2 x 1600) / 1024
    (XL, 6 * 1_554_971_200 + 3 * 157_286_400)])
def test_train_flops_per_token_at_1024(cfg, per_token):
    assert flops.train_flops_per_token(cfg, 1024) == pytest.approx(per_token, rel=1e-12)


def test_stricter_than_the_programs_count():
    """GPT2Config.flops_per_token = 6N (N with wte and wpe rows) + the full
    attention square: 5.005e9 for gpt2-760m (PERF.md, PR 21)."""
    ours = flops.train_flops_per_token(M760, 1024)
    assert ours == pytest.approx(4.7665e9, rel=1e-3)
    assert ours < 5.005e9


def test_mfu_at_prs_22_rate_is_about_49_percent():
    assert 100 * flops.train_flops_per_token(M760, 1024) * 20155.4 \
        / PEAKS["bf16_flops_per_s"] == pytest.approx(48.77, abs=0.05)


def test_flash_counts_forward_two_and_backward_five_half_squares():
    fwd = 24 * 2 * 1024 * 1024 * 1536          # layers x (QK^T + PV) / 2 x 2
    assert flops.attention_flops_fwd(M760, 1024) == fwd
    assert flops.flash_flops_per_sequence(M760, 1024, backward=False) == fwd
    assert flops.flash_flops_per_sequence(M760, 1024) == 3.5 * fwd
    assert flops.flash_bytes_per_sequence(M760, 1024) == 24 * 12 * 1024 * 1536 * 2
    least, bound = roofline(
        8 * flops.flash_flops_per_sequence(M760, 1024),
        8 * flops.flash_bytes_per_sequence(M760, 1024), PEAKS)
    assert bound == "compute" and least == pytest.approx(10.99e-3, rel=1e-2)


def test_decode_step_bytes_are_weights_plus_the_cache_attended():
    biases_ln = 48 * 13 * 1600 + 2 * 1600
    assert flops.weight_bytes(XL) == 2 * (1_554_971_200 + biases_ln)
    assert flops.kv_bytes_per_position(XL) == 48 * 2 * 1600 * 2
    assert flops.decode_bytes_per_token(XL, 270) == \
        flops.weight_bytes(XL) + 270 * 307_200
    least, bound = roofline(flops.decode_flops_per_token(XL),
                                  flops.decode_bytes_per_token(XL, 270), PEAKS)
    assert bound == "memory"
    # PR 22 read 58.3% at 6.70 ms of device time a token: 3.9 ms least
    assert least == pytest.approx(3.9e-3, rel=0.01)
