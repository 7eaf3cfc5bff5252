"""From a profiler trace of the window to the benchmark's device numbers.

    python3 bench/trace_reduce.py <profile dir or .xplane.pb>   # a summary

A traced run records one ``.xplane.pb`` (``jax.profiler``). It holds the
host's threads, with the benchmark's ``jax.profiler.TraceAnnotation``
spans (``bench.window`` around the measured window; ``bench.submit``,
``bench.step`` and ``bench.idle`` inside it), and one plane per TPU chip,
whose ``XLA Ops`` line holds every operation that ran on the device, on
the host's time base. This module reduces it to:

- ``busy_s``: the union of the device-op intervals inside the window,
  averaged over the chips that ran any, and ``window_s``;
- ``op_s``: device time by op name, and ``conv_op_s``: the time of the ops
  that realize the conv stages (XLA convolution fusions, and Mosaic
  custom calls: the Pallas kernels);
- ``conv_least_s``: the least time of the conv stages' work in the
  window's steps, by ``work.stage_least_s`` and the chip's peaks;
- ``breakdown``: the device ops that took most time, and the device's idle
  time by what the host was doing meanwhile (the innermost ``bench.*``
  span around each gap).
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from dataclasses import dataclass

import work

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_INSTR = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = .*? "
                    r"(fusion|custom-call|convolution)\(")
_NAME = re.compile(r"^%?([\w.\-]+)")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_MOSAIC = 'custom_call_target="tpu_custom_call"'
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_SPANS = ("bench.window", "bench.submit", "bench.step", "bench.idle")


@dataclass
class Summary:
    busy_s: float
    window_s: float
    op_s: dict
    conv_op_s: float
    conv_least_s: float
    conv_bound: str
    breakdown: dict


def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def stage_ops(hlo_text: str) -> tuple[str, set]:
    """(module name, names of the ops that realize conv stages) of a plan.

    Read from the compiled plan's HLO: every instruction of the entry
    computation that is a convolution, a fusion whose computation holds
    one, or a Mosaic kernel (``custom_call_target="tpu_custom_call"``).
    Those names are what the trace's ops are called.
    """
    comps, body, entry = {}, None, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = line.split()[1] if line.startswith("ENTRY") \
                else line.split()[0]
            body = comps.setdefault(name.lstrip("%"), [])
            if line.startswith("ENTRY"):
                entry = name.lstrip("%")
        elif body is not None:
            body.append(line)
    module = hlo_text.split(None, 2)[1].rstrip(",")
    ops = set()
    for line in comps.get(entry, []):
        m = _INSTR.match(line)
        if m is None:
            continue
        name, op = m.groups()
        if op == "convolution" or (
                op == "custom-call" and _MOSAIC in line):
            ops.add(name)
        elif op == "fusion":
            called = _CALLS.search(line)
            if called and any("convolution(" in x
                              for x in comps.get(called.group(1), [])):
                ops.add(name)
    return module, ops


def op_name(event_name: str) -> str:
    """The HLO instruction's name: a TPU op event is named by its HLO text
    (``%fusion.3 = f32[...] fusion(...), ...``)."""
    m = _NAME.match(event_name)
    return m.group(1) if m else event_name


def _union(intervals) -> list:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def load(path: str):
    """Host spans [(name, t0, t1)] and, per TPU plane, its ops
    [(name, t0, t1)] and its module runs [(name, t0, t1)]."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, ops, modules = [], {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                            for ev in line.events if ev.name in HOST_SPANS)
        elif DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                into = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if into is not None:
                    into[plane.name] = [
                        (op_name(ev.name), ev.start_ns * 1e-9,
                         ev.end_ns * 1e-9) for ev in line.events]
    return host, ops, modules


def _inside(t, spans) -> bool:
    """Whether time ``t`` falls in one of the sorted ``spans``."""
    i = bisect.bisect_right(spans, (t, float("inf"))) - 1
    return i >= 0 and spans[i][0] <= t <= spans[i][1]


def reduce(path: str, config: dict, net: work.Net, batches, peaks,
           plans: list) -> Summary:
    """``plans``: (module name, conv-stage op names) of each compiled plan
    the window ran (``stage_ops``)."""
    host, ops, modules = load(path)
    windows = [(a, b) for n, a, b in host if n == "bench.window"]
    every = [o for evs in ops.values() for o in evs]
    if windows:
        w0, w1 = windows[0]
    elif every:
        w0, w1 = min(o[1] for o in every), max(o[2] for o in every)
    else:
        raise ValueError(f"{path}: no window span and no device ops")
    plan_modules = {m for m, _ in plans}
    stage_names = set().union(*(names for _, names in plans))

    busy, op_s, conv_s = [], {}, 0.0
    first_busy = None
    for plane, evs in ops.items():
        inside = [(max(a, w0), min(b, w1), n) for n, a, b in evs
                  if b > w0 and a < w1]
        if not inside:
            continue
        merged = _union((a, b) for a, b, _ in inside)
        busy.append(sum(b - a for a, b in merged))
        first_busy = first_busy or merged
        # a plan's op names are unique only within its module
        runs = sorted((a, b) for n, a, b in modules.get(plane, [])
                      if n.split("(")[0] in plan_modules)
        for a, b, n in inside:
            op_s[n] = op_s.get(n, 0.0) + (b - a)
            if n in stage_names and (not runs or _inside((a + b) / 2, runs)):
                conv_s += b - a
    n_chips = max(1, len(busy))
    conv_s /= n_chips

    T = config["T"]
    least, by = 0.0, {"flops": 0.0, "bytes": 0.0}
    for b in batches:
        for s in net.stages:
            t, bound = work.stage_least_s(s, T, b.bucket,
                                          peaks["bf16_flops_per_s"],
                                          peaks["hbm_bytes_per_s"])
            least += t
            by[bound] += t
    # a step's bucket is spread over the chips it ran on
    least /= n_chips

    idle = {}
    edges = [w0] + [x for iv in first_busy or [] for x in iv] + [w1]
    for gap0, gap1 in zip(edges[::2], edges[1::2]):
        if gap1 <= gap0:
            continue
        mid = (gap0 + gap1) / 2
        around = [(t1 - t0, n) for n, t0, t1 in host
                  if t0 <= mid <= t1 and n != "bench.window"]
        name = min(around)[1] if around else "bench.window"
        idle[name] = idle.get(name, 0.0) + (gap1 - gap0)

    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    breakdown = {
        "device_ops": [[n, s / n_chips] for n, s in top],
        "idle_gaps": sorted(([n, s] for n, s in idle.items()),
                            key=lambda kv: -kv[1])[:10]}
    return Summary(
        busy_s=sum(busy) / n_chips if busy else 0.0, window_s=w1 - w0,
        op_s={n: s / n_chips for n, s in op_s.items()}, conv_op_s=conv_s,
        conv_least_s=least, conv_bound=max(by, key=by.get),
        breakdown=breakdown)


def describe(path: str, top: int = 40) -> dict:
    """The trace's layout, for reading by hand: planes, lines, top events
    with the stats of their first occurrence."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            tot, first = {}, {}
            n = 0
            for ev in line.events:
                n += 1
                tot[ev.name] = tot.get(ev.name, 0) + ev.duration_ns
                if ev.name not in first:
                    first[ev.name] = {k: str(v)[:160] for k, v in ev.stats}
            lines.append({"line": line.name, "events": n, "top": [
                [k, v * 1e-9, first[k]]
                for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]})
        out.append({"plane": plane.name, "lines": lines})
    return {"file": path, "bytes": os.path.getsize(path), "planes": out}


if __name__ == "__main__":
    json.dump(describe(find_xplane(sys.argv[1])), sys.stdout, indent=1)
    print()
