"""Order statistics the benchmark reports.

``percentile`` is the estimator of the program's ``obs.percentiles``
(numpy's linear interpolation), copied so that the yardstick stays with
the benchmark; it works in float64, since latencies here are compared
at the microsecond. ``spread`` is the distance between the quartiles of
``statistics.quantiles`` as a share of the median, the measure the
benchmark's bounds are set from.
"""
from __future__ import annotations

import json
import os
import statistics

import numpy as np


def percentile(values, q: float) -> float:
    a = np.asarray(values, np.float64)
    if a.size == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(a, q))


def spread(values) -> float:
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
