"""The roofline arithmetic and the peak table."""
import pytest

from bench import roofline


def test_iteration_work_counts_live_entries_and_potentials():
    b, ops = roofline.iteration_work(nnz=1000, n=10, m=20)
    assert b == 2 * 16 * 1000 + 2 * 4 * 30
    assert ops == 2 * 5 * 1000


def test_least_time_takes_the_larger_bound():
    t, bound = roofline.least_time(10**6, 2**14, 2**14, 100, "TPU v5 lite")
    b, _ = roofline.iteration_work(10**6, 2**14, 2**14)
    assert bound == "bandwidth"
    assert t == pytest.approx(100 * b / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.least_time(10, 4, 4, 1, "TPU v9 imaginary")
