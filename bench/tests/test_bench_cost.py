"""The yardstick's bytes and operations, against values worked by hand."""

import pytest

from bench_tiny import harness

cost = harness().cost
PAPER = (1000, 100, 5000)


def test_plane_bytes_at_the_paper_shape():
    # K = 1001 bins x 100 x 5000, two planes; vectors in (5000) and out
    # (100), two planes each, per bin
    planes_s, planes_h = 2 * 1001 * 100 * 5000 * 4, 2 * 1001 * 100 * 5000 * 2
    assert planes_s == 4_004_000_000 and planes_h == 2_002_000_000
    assert cost.phase3_bytes(*PAPER, "sssss") == planes_s + 2 * 1001 * 5100 * 4
    assert cost.phase3_bytes(*PAPER, "shhss") == planes_h + 2 * 1001 * 5100 * 2


def test_phase3_flops_at_the_paper_shape():
    # one complex multiply-add (8 real operations) per plane entry
    assert cost.phase3_flops(*PAPER) == 8 * 1001 * 100 * 5000 == 4_004_000_000


def test_roofline_is_bound_by_hbm_at_one_flop_per_byte():
    peak = harness().PEAKS["TPU v5 lite"]
    t, bound = cost.roofline_s(cost.phase3_flops(*PAPER),
                               cost.phase3_bytes(*PAPER, "sssss"), peak)
    assert bound == "hbm"
    assert t == pytest.approx(4_044_840_800 / 819e9)      # 4.94 ms
    t, bound = cost.roofline_s(197e12, 1.0, peak)
    assert bound == "flops" and t == pytest.approx(1.0)
