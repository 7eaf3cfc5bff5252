"""The split of the device's idle time by the serve step's phase spans."""
import math
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import spans  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402
from window import Batch  # noqa: E402

OUT = spans.OUTSIDE
# Profiles of a few steps of each cell on one TPU v5e chip, recorded with
# the Python tracer off by ``bench/tools/record_steps.py``.
RECORDED = {
    "cifar10-event-saturated": "event-saturated-2steps-spans.xplane.pb",
    "cifar10-dense-saturated": "dense-saturated-2steps.xplane.pb"}
# recorded before the program wrote serve.* spans (test_trace_reduce.py)
NO_SPANS = "event-saturated-2steps.xplane.pb"
READERS = {"idle_in_wait_ms.throughput": "wait",
           "idle_in_launch_ms.throughput": "launch",
           "idle_in_metering_ms.throughput": "metering",
           "programs_per_step.throughput": None}


def fixture(name):
    return os.path.join(BENCH, "testdata", name)


def test_innermost_tiles_the_window_by_the_deepest_span():
    nested = [("serve.step", 1, 9), ("serve.form", 1, 3),
              ("serve.execute", 3, 7), ("serve.wait", 4, 6),
              ("serve.step", 9.5, 12)]
    assert spans.innermost(nested, 0, 10) == [
        (0, 1, OUT), (1, 3, "serve.form"), (3, 4, "serve.execute"),
        (4, 6, "serve.wait"), (6, 7, "serve.execute"), (7, 9, "serve.step"),
        (9, 9.5, OUT), (9.5, 10, "serve.step")]


def test_idle_is_split_by_overlap_not_by_midpoint():
    idle = spans.idle_intervals([(0.5, 2), (1, 3.5), (5, 5.5), (11, 12)],
                                0, 10)
    assert idle == [(0, 0.5), (3.5, 5), (5.5, 10)]
    pieces = spans.innermost([("serve.step", 1, 9), ("serve.wait", 4, 6)],
                             0, 10)
    by = spans.split_idle(pieces, idle)
    assert by == pytest.approx({OUT: 1.5, "serve.step": 3.5,
                                "serve.wait": 1.5})
    assert sum(by.values()) == pytest.approx(sum(b - a for a, b in idle))


def run_of(tmp_path, monkeypatch, cell, name):
    """A traced run of ``cell`` whose profile is the recorded ``name``."""
    trace = tmp_path / cell / "trace"
    trace.mkdir(parents=True)
    (trace / "x.xplane.pb").write_bytes(open(fixture(name), "rb").read())
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    spans._split_cached.cache_clear()
    return types.SimpleNamespace(trace=object(), cell={"name": cell})


def read(metric, run):
    return harness.load_module(os.path.join(BENCH, "metrics",
                                            metric + ".py"),
                               "metric_" + metric.replace(".", "_")).read(run)


@pytest.mark.parametrize("cell", RECORDED)
def test_readers_are_finite_on_recorded_steps(tmp_path, monkeypatch, cell):
    run = run_of(tmp_path, monkeypatch, cell, RECORDED[cell])
    values = {m: read(m, run) for m in READERS}
    assert all(isinstance(v, float) and math.isfinite(v) and v >= 0
               for v in values.values()), values
    assert values["idle_in_launch_ms.throughput"] > 0
    assert values["idle_in_metering_ms.throughput"] > 0
    # the plan, and at least the pricing's programs, in every step
    assert values["programs_per_step.throughput"] >= 2
    s = spans.split(fixture(RECORDED[cell]))
    assert 2 <= s.steps <= 3
    text = spans.report(s)
    assert all(n in text for n in (spans.STEP, *spans.PHASES, OUT))


@pytest.mark.parametrize("cell", RECORDED)
def test_the_split_adds_up_to_the_window_idle(cell):
    """wait + launch + metering + idle outside serve.step is the window's
    idle time as ``trace_reduce`` counts it, within 1 %."""
    path = fixture(RECORDED[cell])
    s = spans.split(path)
    net = work.parse("32C3-32C3-P3-64C3-64C3-P3-128C3-128C3-128C3-10", 32, 3)
    batches = [Batch(0, 0, 64, 64, 0.0, 0.0, 0.0)] * s.steps
    r = trace_reduce.reduce(path, {"T": 4}, net, batches, {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, [])
    assert s.window_s == pytest.approx(r.window_s)
    idle = r.window_s - r.busy_s
    assert sum(s.idle_s.values()) == pytest.approx(idle, rel=1e-9)
    parts = sum(s.idle_ms(g) for g in spans.GROUPS) * s.steps * 1e-3 \
        + s.idle_s.get(OUT, 0.0)
    assert parts == pytest.approx(idle, rel=0.01)


def test_a_program_without_serve_spans_reads_nothing(tmp_path, monkeypatch):
    assert spans.split(fixture(NO_SPANS)) is None
    run = run_of(tmp_path, monkeypatch, "cifar10-event-saturated", NO_SPANS)
    assert all(read(m, run) is None for m in READERS)
    untraced = types.SimpleNamespace(trace=None, cell=run.cell)
    assert spans.of_run(untraced) is None
    assert spans.of_run(types.SimpleNamespace(
        trace=object(), cell={"name": "no-such-cell"})) is None
