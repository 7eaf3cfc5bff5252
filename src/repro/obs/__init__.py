"""repro.obs — low-overhead structured tracing + metrics for every layer.

One module-level switch gates the buffered trace and the metrics:

    from repro import obs
    obs.enable()                      # or REPRO_TRACE=/path/trace.jsonl
    with obs.span("serve.execute", bucket=8):
        ...
    obs.counter("engine.jit_miss")
    obs.observe("serve.bucket_occupancy", 0.75)
    obs.save_jsonl("trace.jsonl")     # or obs.save_chrome_trace("t.json")

Every span also writes to the profiler: while a ``jax.profiler`` session
records (``jax.profiler.start_trace`` or a profiler server's capture),
``span()`` opens a ``jax.profiler.TraceAnnotation`` of the same name, with
the span's attributes as its arguments, whether or not ``enable()`` is on.
The span then sits in the profiler's trace on the profiler's clock, next
to the device ops it waited for.

Design rules (pinned by tests and the ``obs-in-jit`` audit rule):

* **Zero-cost when disabled** — with no profiler session, ``span()``
  returns one shared no-op object and the metric calls return before
  touching any lock; the disabled per-span overhead is bounded by
  ``tests/test_obs.py``.
* **Host-side only** — obs is imported without jax (it looks up the
  profiler's annotation on a span, and only once the process has
  imported jax), and obs calls are banned inside jit-traced code, so
  instrumentation can never perturb traced computations or their
  bit-exactness.
* **Deterministic under test** — ``enable(clock=...)`` injects the time
  source used for every span/event timestamp.

Setting ``REPRO_TRACE=<path>`` in the environment enables tracing at
import time and writes the JSONL trace (spans + events + a trailing
metrics snapshot) to ``<path>`` at interpreter exit — that is how CI
captures a trace from an unmodified example run.
"""
from __future__ import annotations

import atexit
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

from . import export
from .metrics import DEFAULT_QS, Histogram, Metrics, percentiles
from .trace import (NOOP_SPAN, EventRecord, NoopSpan, ProfilerSpan, Span,
                    SpanRecord, Tracer)

__all__ = [
    "enable", "disable", "enabled", "reset", "span", "event", "counter",
    "gauge", "observe", "spans", "events", "metrics_snapshot",
    "save_jsonl", "save_chrome_trace", "percentiles", "Histogram",
    "Metrics", "Tracer", "SpanRecord", "EventRecord", "Span", "NoopSpan",
    "ProfilerSpan", "NOOP_SPAN", "DEFAULT_QS",
]

_enabled: bool = False
_tracer: Tracer = Tracer()
_metrics: Metrics = Metrics()
_annotation_cls = None   # jax.profiler.TraceAnnotation, once jax is imported


def enable(clock: Optional[Callable[[], float]] = None) -> None:
    """Turn tracing on; optionally inject the clock (``() -> float`` in
    seconds) used for every subsequent span and event timestamp."""
    global _enabled
    if clock is not None:
        _tracer.clock = clock
    _enabled = True


def disable() -> None:
    """Turn tracing off. Buffered records stay readable until reset()."""
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def reset() -> None:
    """Drop all buffered spans/events/metrics (keeps the enabled flag)."""
    _tracer.clear()
    _metrics.clear()


def _annotation(name: str, attrs: Dict[str, Any]):
    """The profiler's annotation for a span, or None when no profiler
    session records (a TraceMe started then would record nothing)."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        if "jax" not in sys.modules:
            return None     # no jax, no device trace to join
        from jax.profiler import TraceAnnotation

        cls = _annotation_cls = TraceAnnotation
    if not cls.is_enabled():
        return None
    return cls(name, **attrs)


def span(name: str, **attrs: Any):
    """Context manager timing a named region, in the buffered trace when
    enabled and in the profiler's trace while a profiler session records.
    No-op when neither."""
    ann = _annotation(name, attrs)
    if not _enabled:
        return NOOP_SPAN if ann is None else ProfilerSpan(ann)
    return _tracer.span(name, ann, **attrs)


def event(name: str, **attrs: Any) -> None:
    """Record an instant event (evictions, cache decisions, markers)."""
    if not _enabled:
        return
    _tracer.event(name, **attrs)


def counter(name: str, n: float = 1) -> None:
    if not _enabled:
        return
    _metrics.counter_inc(name, n)


def gauge(name: str, value: float) -> None:
    if not _enabled:
        return
    _metrics.gauge_set(name, value)


def observe(name: str, value: float) -> None:
    """Add one sample to the named histogram."""
    if not _enabled:
        return
    _metrics.observe(name, value)


def spans() -> List[SpanRecord]:
    return _tracer.spans()


def events() -> List[EventRecord]:
    return _tracer.events()


def metrics_snapshot() -> Dict[str, Any]:
    return _metrics.snapshot()


def save_jsonl(path: str) -> None:
    export.write_jsonl(path, _tracer.spans(), _tracer.events(),
                       _metrics.snapshot())


def save_chrome_trace(path: str) -> None:
    export.write_chrome_trace(path, _tracer.spans(), _tracer.events())


_env_trace = os.environ.get("REPRO_TRACE", "")
if _env_trace:
    enable()
    atexit.register(save_jsonl, _env_trace)
