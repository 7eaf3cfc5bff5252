"""Open loop on the wall clock: independent users at a fixed rate.

``rate_per_s`` x ``seconds`` requests fall due in the window, at gaps that
are the quantiles of an exponential distribution (a Poisson process's
gaps) in an order drawn from the seed, scaled so that the last falls due
as the window closes. So every seed offers the same work at the same mean
rate, in another order. The loop submits every request whose due time has
passed, steps the runtime, and sleeps when the queue is empty until the
next one is due; after the last is due it steps until all have answered.

A request's latency runs from its due time to the return of the step that
served it, so a late generator or a slow step counts against it; how late
the generator submitted is recorded beside it.

Parameters: ``pool`` (images made from the seed), ``rate_per_s``.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

import reference
from window import Window


def shapes(mix: dict, ladder: tuple, select) -> dict:
    """A partly filled queue can hold any count, so every bucket and every
    count the batcher serves in it."""
    from harness import reachable_counts

    return reachable_counts(ladder, select)


def schedule(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times from the window's start, in seconds; the first is 0."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n - 1) + 0.5) / (n - 1) if n > 1 else np.zeros(0)
    gaps = -np.log1p(-q)
    rng = np.random.default_rng(list(reference.seed_words(seed)) + [2])
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)])
    return due * (seconds / due[-1]) if n > 1 else due


def run(served, mix: dict, seconds: float, seed: int) -> Window:
    rt, model, pool = served.runtime, served.model, served.pool
    clock = time.perf_counter
    offsets = schedule(mix["rate_per_s"], seconds, seed)
    w = Window(t0=clock())
    due = w.t0 + offsets
    due_of = {}
    nxt = 0
    with TraceAnnotation("bench.window"):
        while nxt < len(due) or rt.pending():
            now = clock()
            if nxt < len(due) and due[nxt] <= now:
                with TraceAnnotation("bench.submit"):
                    while nxt < len(due) and due[nxt] <= now:
                        rid = rt.submit(pool[nxt % len(pool)], model,
                                        arrival_s=float(due[nxt]))
                        w.image_of[rid] = nxt % len(pool)
                        due_of[rid] = due[nxt]
                        w.lateness_s.append(clock() - due[nxt])
                        nxt += 1
            if not rt.pending():
                with TraceAnnotation("bench.idle"):
                    time.sleep(max(0.0, due[nxt] - clock()))
                continue
            t0 = clock()
            with TraceAnnotation("bench.step"):
                responses = rt.step()
            t1 = clock()
            w.record(t0, t1, responses)
            w.latency_s.extend(t1 - due_of[r.rid] for r in responses)
    w.attempted = nxt
    w.failed = nxt - len(w.answers)
    return w
