"""Arithmetic shared by the metric readers in ``metrics/``.

Each reader is one file named after its metric, with one function
``read(run) -> float | None``; metrics that differ only in the end-to-end
metric they move share the arithmetic here.
"""
from __future__ import annotations

import stats
import work


def images_per_s(run) -> float | None:
    """Images served in the window over all of the window's time."""
    w = run.window
    if not w.batches:
        return None
    return w.served / (w.t1 - w.t0)


def latency_ms(run, q: float) -> float | None:
    if not run.window.latency_s:
        return None
    return stats.percentile(run.window.latency_s, q) * 1e3


def host_ms_per_batch(run) -> float | None:
    """Mean of the runtime's batch-form and price clock reads per step."""
    b = run.window.batches
    if not b:
        return None
    return sum(x.batch_form_s + x.price_s for x in b) / len(b) * 1e3


def padded_slot_share(run) -> float | None:
    b = run.window.batches
    if not b:
        return None
    return 100.0 * sum(x.bucket - x.n for x in b) / sum(x.bucket for x in b)


def flops_per_image(run) -> int:
    return work.flops_per_image(run.net, run.config["T"])


def step_mfu_rate(run) -> float | None:
    """Dense-equivalent FLOP/s of the served images over the chips' peak."""
    rate = images_per_s(run)
    if rate is None:
        return None
    peak = run.chips * run.peaks["bf16_flops_per_s"]
    return 100.0 * flops_per_image(run) * rate / peak


def step_mfu_busy(run) -> float | None:
    """The same FLOPs over the time the steps spent executing."""
    b = run.window.batches
    if not b:
        return None
    peak = run.chips * run.peaks["bf16_flops_per_s"]
    execute_s = sum(x.service_s for x in b)
    return 100.0 * flops_per_image(run) * run.window.served \
        / (execute_s * peak)


def device_idle(run) -> float | None:
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
