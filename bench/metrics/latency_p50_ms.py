"""Median of every window request's latency, due time to answer."""
from readings import latency_ms


def read(run):
    return latency_ms(run, 50)
