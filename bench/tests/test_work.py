"""The yardstick's work counts against the paper's Table 6.

Run from the repository root: ``python -m pytest bench/tests``.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import work  # noqa: E402

TABLE6 = [
    ("32C3-32C3-P3-10C3-10", 28, 1, 20568),
    ("1C3-32C3-32C3-P3-64C3-64C3-P3-128C3-128C3-10", 32, 3, 297990),
    ("32C3-32C3-P3-64C3-64C3-P3-128C3-128C3-128C3-10", 32, 3, 446122),
]
CIFAR10 = TABLE6[2][:3]


@pytest.mark.parametrize("spec,hw,c,params", TABLE6)
def test_parameter_counts_match_table6(spec, hw, c, params):
    assert work.param_count(work.parse(spec, hw, c)) == params


def test_cifar10_macs_per_time_step():
    net = work.parse(*CIFAR10)
    assert work.macs_per_step(net) == 19_180_800
    assert work.flops_per_image(net, 4) == 2 * 19_180_800 * 4


def test_cifar10_geometry():
    net = work.parse(*CIFAR10)
    assert [(s.hw, s.c_in, s.c_out, s.pool) for s in net.stages] == [
        (32, 3, 32, 0), (32, 32, 32, 3), (10, 32, 64, 0), (10, 64, 64, 3),
        (3, 64, 128, 0), (3, 128, 128, 0), (3, 128, 128, 0)]
    assert [s.analog for s in net.stages] == [True] + [False] * 6
    assert (net.head_in, net.head_out) == (3 * 3 * 128, 10)


def test_conv2_least_time_is_bound_by_bytes():
    """The 32x32x32 stage on a 64-bucket: 4.83 GFLOP against 42 MB."""
    s = work.parse(*CIFAR10).stages[1]
    t, bound = work.stage_least_s(s, 4, 64, 197e12, 819e9)
    assert bound == "bytes"
    moved = 4 * 9 * 32 * 32 + 64 * (4 * 1024 * 32 + 4 * 4 * 1024 * 32)
    assert t == pytest.approx(moved / 819e9)
    assert 50e-6 < t < 53e-6


@pytest.mark.parametrize("bad", ["", "P3-10", "32C3-P3-P3-10", "32C3-x-10",
                                 "32C3-32C3"])
def test_malformed_specs_are_refused(bad):
    with pytest.raises(ValueError):
        work.parse(bad, 32, 3)
