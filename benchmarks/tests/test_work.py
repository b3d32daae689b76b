"""work.py against hand-worked shapes; the table of peaks."""

import pytest

from benchmarks.harness import work
from benchmarks.harness.peaks import peaks_for


def test_topk_counts():
    # 8 queries over 1,000 rows of 4 numbers, 10 results
    assert work.topk_flops(8, 1000, 4) == 2 * 8 * 1000 * 4 == 64000
    # rows at bf16 (8,000) + mask (1,000) + queries f32 (128) + results (640)
    assert work.topk_bytes(8, 1000, 4, 10) == 8000 + 1000 + 128 + 640


def test_encoder_flops():
    # one block of width 2 on 3 tokens: 24*3*4 + 4*9*2 = 288 + 72
    assert work.encoder_flops(3, 2, 1) == 360
    assert work.encoder_flops(3, 2, 5) == 5 * 360


@pytest.mark.parametrize(
    "flops, nbytes, seconds, bound",
    [(197e12, 1.0, 1.0, "compute"), (1.0, 819e9, 1.0, "memory"), (197e12, 2 * 819e9, 2.0, "memory")],
)
def test_least_time(flops, nbytes, seconds, bound):
    got = work.least_time(flops, nbytes, peaks_for("TPU v5 lite"))
    assert got == (pytest.approx(seconds), bound)


def test_retrieve_scan_is_memory_bound_below_some_hundred_queries():
    peaks = peaks_for("TPU v5 lite")
    rows, dim = 2_097_152, 384
    small = work.least_time(work.topk_flops(32, rows, dim), work.topk_bytes(32, rows, dim, 10), peaks)
    large = work.least_time(work.topk_flops(512, rows, dim), work.topk_bytes(512, rows, dim, 10), peaks)
    assert small[1] == "memory" and large[1] == "compute"
    assert small[0] == pytest.approx((rows * dim * 2 + rows + 32 * dim * 4 + 32 * 10 * 8) / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks on record"):
        peaks_for("TPU v9 imaginary")
