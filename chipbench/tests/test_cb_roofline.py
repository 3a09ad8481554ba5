"""The roofline count at a shape worked out by hand, and the peaks table."""
import numpy as np
import pytest

from chipbench import roofline

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_work_of_one_call_by_hand():
    # 2 queries x 3 probes over grains of 200, 100 and 50 rows; the
    # probes price 600 rows, the 4 distinct grains hold 700
    ops, nbytes = roofline.fused_select_work(
        queries=2, probes=3, probed_rows=600, distinct_rows=700, k=32, s=8,
        pool=20)
    assert ops == 600 * 2 * (32 + 8)                          # 48,000
    panel = 700 * (2 * 32 + 8 + 4 * 3)                        # 58,800
    assert nbytes == panel + 2 * 3 * (32 + 8 + 2) * 4 + 2 * 20 * 8


def test_least_time_names_its_bound():
    t, bound = roofline.least_time(197e12, 819e9 / 2, PEAKS)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = roofline.least_time(197e12 / 4, 819e9, PEAKS)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_peaks_are_keyed_by_device_kind():
    v5e = roofline.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")


def test_routed_rows_count_real_grain_sizes_over_nonempty_grains():
    cents = np.array([[0.0, 0.0], [1.0, 0.0], [0.0, 1.0], [5.0, 5.0]],
                     np.float32)
    sizes = np.array([300, 200, 0, 100])
    q = np.array([[0.1, 0.1], [0.9, 0.1], [0.1, 0.9]], np.float32)
    # top-2 over grains 0, 1 and 3 (grain 2 is empty): {0, 1} each time,
    # 3 x (300 + 200) probed rows, 500 distinct
    assert roofline.routed_rows(cents, sizes, q, 2) == (1500, 500)
    # top-3 adds grain 3 to every query
    assert roofline.routed_rows(cents, sizes, q, 3) == (1800, 600)
