"""Operation and byte counts of the event kernel at hand-checked shapes."""

import pytest

from kernels.event_finish import bytes_moved, ops, roofline_share


def test_counts_by_hand():
    # two lanes of 10 and 30 chunks over P=20 PEs: 40 steps x (20 + 3) ops;
    # 40 x 8 bytes of costs and owners, 2 x (12 * 20 + 12) per lane
    assert ops([10, 30], 20) == 40 * 23
    assert bytes_moved([10, 30], 20) == 40 * 8 + 2 * 252


def test_empty_lane_moves_only_its_state():
    assert ops([0], 128) == 0
    assert bytes_moved([0], 128) == 12 * 128 + 12


def test_roofline_share_and_bound():
    # 1e6 steps at P=128: 131e6 ops, 8e6 + 1548 bytes
    calls = [([1_000_000], 128)]
    share, bound = roofline_share(calls, kernel_s=1.0, flops_per_s=197e12,
                                  bytes_per_s=819e9)
    assert bound == "memory"
    assert share == pytest.approx(100.0 * (8e6 + 1548) / 819e9)
    share, bound = roofline_share(calls, kernel_s=1.0, flops_per_s=1e9,
                                  bytes_per_s=819e9)
    assert bound == "compute"
    assert share == pytest.approx(100.0 * 131e6 / 1e9)
