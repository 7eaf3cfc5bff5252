"""The traffic generators' schedules and the shapes they warm."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import harness  # noqa: E402

poisson = harness.load_module(os.path.join(BENCH, "loadgen", "poisson.py"),
                              "poisson")
backlog = harness.load_module(os.path.join(BENCH, "loadgen", "backlog.py"),
                              "backlog")


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2**33 + 1])
def test_every_seed_offers_the_same_work_in_another_order(seed):
    ref = poisson.schedule(72.0, 30.0, 1)
    due = poisson.schedule(72.0, 30.0, seed)
    assert len(due) == len(ref) == 72 * 30
    assert due[0] == 0.0 and due[-1] == pytest.approx(30.0)
    assert np.all(np.diff(due) > 0)
    np.testing.assert_allclose(np.sort(np.diff(due)), np.sort(np.diff(ref)))
    if seed != 1:
        assert not np.allclose(np.diff(due), np.diff(ref))


def test_gaps_are_exponential():
    gaps = np.diff(poisson.schedule(100.0, 100.0, 3))
    assert gaps.mean() == pytest.approx(100.0 / (10000 - 1), rel=1e-9)
    # an exponential's median is ln 2 times its mean
    assert np.median(gaps) == pytest.approx(0.01 * np.log(2), rel=0.01)


def test_reachable_counts_follow_the_batcher():
    from repro.serve.batching import BucketPolicy

    policy = BucketPolicy((1, 4, 16, 64))
    counts = harness.reachable_counts(policy.bucket_sizes, policy.select)
    assert counts == {1: [1], 4: [3, 4], 16: list(range(9, 17)),
                      64: list(range(33, 65))}
    assert poisson.shapes({}, (1, 4, 16, 64), policy.select) == counts
    assert backlog.shapes({}, (1, 4, 16, 64), policy.select) == {64: [64]}
