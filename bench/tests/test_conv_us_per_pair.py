"""``conv_us_per_pair.throughput`` on a recorded profile and on none."""
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402
from window import Batch  # noqa: E402

# two steps of cifar10-event-saturated on one TPU v5e chip, and the conv
# stages of its plan (as in test_trace_reduce.py)
RECORDED = os.path.join(BENCH, "testdata", "event-saturated-2steps.xplane.pb")
EVENT_PLAN = ("jit_run_queue_pallas_batch", {
    "add_compare_fusion.1", "fusion.55",
    *(f"fused_spike_accum_pallas.{i}" for i in range(6, 12))})
CIFAR10 = work.parse("32C3-32C3-P3-64C3-64C3-P3-128C3-128C3-128C3-10", 32, 3)


def read(run):
    return harness.load_module(
        os.path.join(BENCH, "metrics", "conv_us_per_pair.throughput.py"),
        "metric_conv_us_per_pair").read(run)


def run_of(trace, batches, net=CIFAR10, chips=1):
    return types.SimpleNamespace(trace=trace, config={"T": 4}, net=net,
                                 chips=chips,
                                 window=types.SimpleNamespace(batches=batches))


def test_recorded_steps_cost_per_pair():
    host, _, _ = trace_reduce.load(RECORDED)
    batches = [Batch(a, b, 64, 64, 0.0, 0.0, 0.0)
               for n, a, b in host if n == "bench.step"]
    s = trace_reduce.reduce(RECORDED, {"T": 4}, CIFAR10, batches, {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, [EVENT_PLAN])
    # 64 images x 4 steps x (32 + 32 + 64 + 64 + 128 + 128) input channels
    # of the five spike-fed stages, twice
    pairs = 2 * 64 * 4 * 448
    assert read(run_of(s, batches)) == pytest.approx(
        1e6 * s.conv_op_s / pairs)
    # the fixture was recorded before the vector drain: several µs a pair
    assert 5 < read(run_of(s, batches)) < 20


def test_pairs_count_the_net_not_the_program():
    vgg = work.parse("64C3-64C3-P2-128C3-128C3-P2-256C3-256C3-256C3-P2-"
                     "512C3-512C3-512C3-P2-512C3-512C3-512C3-P2-10", 32, 3)
    t = types.SimpleNamespace(conv_op_s=0.950272)
    batches = [Batch(0.0, 1.0, 64, 64, 0.0, 0.0, 0.0)]
    # 950,272 pairs in a 64-bucket of VGG-16: 1 us each
    assert read(run_of(t, batches, vgg)) == pytest.approx(1.0)


def test_no_trace_or_no_steps_reads_nothing():
    assert read(run_of(None, [Batch(0.0, 1.0, 64, 64, 0.0, 0.0, 0.0)])) \
        is None
    assert read(run_of(types.SimpleNamespace(conv_op_s=1.0), [])) is None
