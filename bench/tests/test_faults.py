"""A run with the timed path broken underneath must come out not correct.

These drive the whole of a run but the look for a chip: set-up, the
window of the cell's own traffic, and the comparison with the reference,
on the CPU at the cell's own sizes (the engine runs the event stages'
XLA realization off the chip). Each fault is planted where the answer is
produced, in ``ModelHandle.run_bucket``:

- every answer's logits altered (two classes swapped);
- every answer's per-layer events off by one spike in one layer.

A sound run of the same cell is checked too, so that a comparison that
fails everything cannot pass for one that catches faults.

Run from the repository root: ``python -m pytest bench/tests``. Each case
takes some tens of seconds.
"""
import json
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

CELLS = ["cifar10-event-saturated", "cifar10-dense-saturated"]


def swap_two_classes(logits, stats):
    return logits[:, [1, 0] + list(range(2, logits.shape[1]))], stats


def one_spike_more(logits, stats):
    return logits, stats._replace(events_in=stats.events_in.at[:, 2].add(1))


def run_cell(monkeypatch, name, fault):
    import jax

    import harness
    import stats
    from repro.serve import registry

    monkeypatch.setattr(stats, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    if fault is not None:
        sound = registry.ModelHandle.run_bucket

        def broken(self, images, n_valid):
            return fault(*sound(self, images, n_valid))

        monkeypatch.setattr(registry.ModelHandle, "run_bucket", broken)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == name)
    return harness.run_cell(bench, cell, 2**31 + 11, 2.0, False,
                            time.perf_counter(), jax.devices())


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(monkeypatch, name):
    out = run_cell(monkeypatch, name, None)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("fault", [swap_two_classes, one_spike_more])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(monkeypatch, name, fault):
    out = run_cell(monkeypatch, name, fault)
    assert not out["correct"], out["compared"]
