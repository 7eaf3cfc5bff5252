"""The device's idle time split by the serve step's phase spans.

    python3 bench/spans.py <profile dir or .xplane.pb>   # idle by span

The program's ``obs`` spans write into the profiler's trace while it
records: ``serve.step`` and, inside it, ``serve.form``, ``serve.execute``
(over ``serve.upload``, ``serve.dispatch``, ``serve.wait`` and
``serve.slice``), ``serve.fetch``, ``serve.price`` and ``serve.respond``,
on the profiler's clock beside the device ops. This module loads from a
traced run's ``.xplane.pb`` the window (``bench.window``), the first
chip's device ops and program runs, and the ``serve.*`` spans of the
thread that ran the steps. It cuts every interval of the window in which
no op ran on the device at the span boundaries, and gives each piece to
the innermost span that covers it: an exact split by overlap, where
``trace_reduce`` gives each whole gap to the span around its midpoint.

A trace without ``serve.step`` spans (a program that does not write them)
splits into nothing: ``of_run`` and the readers then return None.
"""
from __future__ import annotations

import functools
import os
import sys
from dataclasses import dataclass

import trace_reduce

OUTSIDE = "outside serve.step"
STEP = "serve.step"
PHASES = ("serve.form", "serve.execute", "serve.upload", "serve.dispatch",
          "serve.wait", "serve.slice", "serve.fetch", "serve.price",
          "serve.respond")
#: what each idle metric sums: the innermost spans of its pieces
GROUPS = {"wait": ("serve.wait",),
          "launch": ("serve.form", "serve.upload", "serve.dispatch"),
          "metering": ("serve.slice", "serve.fetch", "serve.price",
                       "serve.respond")}


@dataclass
class Split:
    steps: int          # serve.step spans inside the window
    window_s: float
    idle_s: dict        # innermost span (or OUTSIDE) -> device-idle seconds
    programs: int       # device program runs that start inside a serve.step

    def idle_ms(self, group: str) -> float:
        """Device-idle ms per step under the group's spans."""
        return sum(self.idle_s.get(n, 0.0) for n in GROUPS[group]) \
            / self.steps * 1e3


def _device_index(plane_name: str) -> int:
    return int(plane_name.rsplit(":", 1)[1])


def load(path: str):
    """(windows [(t0, t1)], the step thread's serve.* spans [(name, t0, t1)],
    the first chip's ops [(t0, t1)] and program runs [(t0, t1)])."""
    from jax.profiler import ProfileData

    windows, threads, chips = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    name = ev.name
                    if name.startswith("serve."):
                        spans.append((name, ev.start_ns * 1e-9,
                                      ev.end_ns * 1e-9))
                    elif name == "bench.window":
                        windows.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9))
                if spans:
                    threads.append(spans)
        elif trace_reduce.DEVICE_PLANE.match(plane.name):
            chips[plane.name] = {
                line.name: [(ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                            for ev in line.events]
                for line in plane.lines
                if line.name in (trace_reduce.OPS_LINE,
                                 trace_reduce.MODULES_LINE)}
    # the thread that ran the steps: the one with most serve.step spans
    spans = max(threads, key=lambda s: sum(n == STEP for n, _, _ in s),
                default=[])
    first = chips[min(chips, key=_device_index)] if chips else {}
    return (windows, spans, first.get(trace_reduce.OPS_LINE, []),
            first.get(trace_reduce.MODULES_LINE, []))


def innermost(spans, w0: float, w1: float) -> list:
    """[(t0, t1, name)] tiling [w0, w1]: over each piece, the innermost of
    the properly nested ``spans`` [(name, t0, t1)] that covers it, else
    ``OUTSIDE``."""
    clipped = sorted(((n, max(a, w0), min(b, w1)) for n, a, b in spans
                      if b > w0 and a < w1), key=lambda s: (s[1], -s[2]))
    out, stack, t = [], [], w0
    for name, a, b in clipped + [(None, w1, w1)]:
        while stack and stack[-1][1] <= a:
            top, end = stack.pop()
            if end > t:
                out.append((t, end, top))
                t = end
        if a > t:
            out.append((t, a, stack[-1][0] if stack else OUTSIDE))
            t = a
        if name is not None:
            stack.append((name, b))
    return out


def idle_intervals(ops, w0: float, w1: float) -> list:
    """The pieces of [w0, w1] in which no op ran."""
    busy = trace_reduce._union((max(a, w0), min(b, w1)) for a, b in ops
                               if b > w0 and a < w1)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def split_idle(pieces, idle) -> dict:
    """Seconds of ``idle`` [(t0, t1)] under each name of the tiling
    ``pieces`` [(t0, t1, name)]; both sorted, by a merge."""
    out, i = {}, 0
    for a, b, name in pieces:
        while i < len(idle) and idle[i][1] <= a:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < b:
            s = min(b, idle[j][1]) - max(a, idle[j][0])
            if s > 0:
                out[name] = out.get(name, 0.0) + s
            j += 1
    return out


def split(path: str) -> Split | None:
    """The window's device-idle time by innermost serve span, or None when
    the trace holds no serve.step span inside the window."""
    windows, spans, ops, modules = load(path)
    steps = sorted((a, b) for n, a, b in spans if n == STEP)
    if windows:
        w0, w1 = windows[0]
    elif steps:
        w0, w1 = steps[0][0], steps[-1][1]
    else:
        return None
    steps = [(a, b) for a, b in steps if w0 <= a and b <= w1]
    if not steps:
        return None
    idle = split_idle(innermost(spans, w0, w1), idle_intervals(ops, w0, w1))
    programs = sum(trace_reduce._inside(a, steps) for a, _ in modules)
    return Split(len(steps), w1 - w0, idle, programs)


@functools.lru_cache(maxsize=1)
def _split_cached(path: str, mtime: float) -> Split | None:
    return split(path)


def of_run(run) -> Split | None:
    """The split of a traced run's profile, read once for every reader."""
    if run.trace is None:
        return None
    from harness import OUT_DIR

    try:
        path = trace_reduce.find_xplane(
            os.path.join(OUT_DIR, run.cell["name"], "trace"))
    except FileNotFoundError:
        return None
    return _split_cached(path, os.path.getmtime(path))


def idle_ms(run, group: str) -> float | None:
    s = of_run(run)
    return None if s is None else s.idle_ms(group)


def programs_per_step(run) -> float | None:
    s = of_run(run)
    return None if s is None else s.programs / s.steps


def report(s: Split) -> str:
    per = 1e3 / s.steps
    rows = [f"{s.steps} steps in a {s.window_s * 1e3:.3f} ms window; "
            "device-idle ms per step by innermost span:"]
    names = [STEP, *PHASES, OUTSIDE]
    names += sorted(set(s.idle_s) - set(names))
    for n in names:
        rows.append(f"  {n:<22} {s.idle_s.get(n, 0.0) * per:10.4f}")
    rows.append(f"  {'total':<22} {sum(s.idle_s.values()) * per:10.4f}")
    for g in GROUPS:
        rows.append(f"idle_in_{g}_ms {s.idle_ms(g):.4f}")
    rows.append(f"programs_per_step {s.programs / s.steps:.4f}")
    return "\n".join(rows)


if __name__ == "__main__":
    found = split(trace_reduce.find_xplane(sys.argv[1]))
    print(report(found) if found else "no serve.step span in the window")
