"""Closed loop with a standing backlog: throughput at saturation.

Before every step the queue is topped up to ``backlog_buckets`` full
max-buckets of requests, cycling through the pool of seeded images, so
every step forms a full max-bucket. The window starts at the first step
and takes no new step once ``seconds`` have passed; it ends when the last
step returns, so every image in it is counted over all the time it took.

Parameters: ``pool`` (images made from the seed), ``backlog_buckets``.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

from window import Window


def shapes(mix: dict, ladder: tuple, select) -> dict:
    """Only full max-buckets are ever formed."""
    return {max(ladder): [max(ladder)]}


def run(served, mix: dict, seconds: float, seed: int) -> Window:
    rt, model, pool = served.runtime, served.model, served.pool
    depth = mix["backlog_buckets"] * max(served.buckets)
    clock = time.perf_counter
    nxt = 0
    w = Window(t0=clock())
    end = w.t0 + seconds
    with TraceAnnotation("bench.window"):
        while not w.batches or clock() < end:
            with TraceAnnotation("bench.submit"):
                while rt.pending() < depth:
                    rid = rt.submit(pool[nxt % len(pool)], model)
                    w.image_of[rid] = nxt % len(pool)
                    nxt += 1
            t0 = clock()
            with TraceAnnotation("bench.step"):
                responses = rt.step()
            w.record(t0, clock(), responses)
    w.attempted = len(w.answers)
    return w
