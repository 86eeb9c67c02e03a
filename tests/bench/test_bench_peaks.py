"""The peak table and the kernels' least-work counts."""

import pytest

from bench import roofline
from bench.peaks import PEAKS, peaks_for


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v99")
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert all(row["source"] for row in PEAKS.values())


def test_probe_counts_one_bit_per_query_entry():
    lanes, nprobe, cap, U = 8, 10, 182, 16384
    flops, bytes_ = roofline.ivf_probe_work(lanes, nprobe, cap, U)
    rows = lanes * nprobe * cap
    # rows at U/8 bytes each, plus the f32 probe block and f32 scores
    assert bytes_ == rows * U / 8 + lanes * U * 4 + rows * 4
    assert flops == 2 * rows * U
    # an f32 or int8 table would read 32x or 8x the counted row bytes
    assert bytes_ < rows * U * 1 / 4
    t, bound = roofline.least_seconds(flops, bytes_, peaks_for("TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(bytes_ / 819e9)


def test_step_counts_f32_state_and_a_one_bit_winner_row():
    flops, bytes_ = roofline.mwem_step_work(8, 16384)
    assert bytes_ == 8 * 16384 * 4 * 7 + 8 * 16384 / 8
    _, bound = roofline.least_seconds(flops, bytes_, peaks_for("TPU v5 lite"))
    assert bound == "bytes"
