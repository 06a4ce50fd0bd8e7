"""The FLOP rules against hand counts, and the peak table."""
from bench_fixtures import REPO, cell_of_files  # first: the checkout on sys.path

import json
import os

import pytest

from bench.cell import load_cell, load_module
from bench.peaks import peaks


def test_lstm_forward_flops_match_a_hand_count():
    cell = load_cell("lstm-asr.nghf-mpe.sausage")
    # (80+1000)x4000 + (1000+1000)x4000 + 1000x1000 + 1000x6000 weights
    weights = 4_320_000 + 8_000_000 + 1_000_000 + 6_000_000
    assert weights == 19_320_000
    assert cell.forward_flops_per_frame() == 2 * weights


def test_tdnn_forward_flops_match_a_hand_count():
    cell = cell_of_files("tdnn-asr.nghf-mpe.dag")
    # 80x5x1000 + 3 x 1000x2x1000 + 1000x1x1000 + 1000x6000 weights
    weights = 400_000 + 3 * 2_000_000 + 1_000_000 + 6_000_000
    assert weights == 13_400_000
    assert cell.forward_flops_per_frame() == 2 * weights


@pytest.mark.parametrize("config,mix,grad_frames,cg_frames", [
    ("lstm-asr", "nghf-mpe.sausage", 64 * 512, 16 * 512),
    ("tdnn-asr", "nghf-mpe.dag", 128 * 512, 32 * 512),
    ("lstm-asr", "nghf-mpe.sausage.dp4", 256 * 512, 64 * 512),
])
def test_nghf_update_flops_match_a_hand_count(config, mix, grad_frames,
                                              cg_frames):
    with open(os.path.join(REPO, "bench", "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(REPO, "bench", "traffic", f"{mix}.json")) as f:
        traffic = json.load(f)
    F = load_module(REPO, "flops", cfg["kind"]).forward_flops_per_frame(cfg)
    # gradient stage 3F per frame; 4 + 8 products at 3F, 8 candidates and
    # the base at 1F, one primal at 1F per CG frame
    want = 3 * F * grad_frames + cg_frames * F * (12 * 3 + 9 + 1)
    assert load_module(REPO, "flops", "nghf").update_flops(F, traffic) == want


def test_lstm_update_flops_are_18_36_tflop():
    cell = load_cell("lstm-asr.nghf-mpe.sausage")
    assert cell.update_flops() == pytest.approx(18.36e12, rel=1e-3)


def test_peaks_of_a_v5e_chip():
    assert peaks("TPU v5 lite")["matmul_flops_per_s"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_peaks_of_an_unknown_device_kind_raise(kind):
    with pytest.raises(KeyError, match="no peaks"):
        peaks(kind)
