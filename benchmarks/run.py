"""Benchmark entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only tableX|figY|kernel]
                                            [--json BENCH_YYYYMMDD.json]

Prints ``name,us_per_call,derived`` CSV rows. Timing columns are CPU wall
times (interpret-mode for Pallas kernels); `derived` carries the model
metrics (energy, FPS/W) that constitute the reproduction.

``--json OUT`` additionally writes a machine-readable perf snapshot
(name -> us_per_call + parsed derived metrics) so the perf trajectory
accumulates across PRs — diff two snapshots to see what moved.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
import traceback


def _parse_derived(derived: str) -> dict:
    """'a=1;b=[2;3]' -> {'a': 1.0, 'b': '[2;3]'} (numbers parsed if possible).

    Values may themselves contain ';' (decile/range metrics like
    '[344;846]'), so split only at separators that start a new key=.
    """
    import re

    out = {}
    for part in re.split(r";(?=[\w./-]+=)", derived):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return out


def write_snapshot(path: str, failures: int) -> None:
    from .common import RESULTS

    snap = {
        "schema": "bench-v1",
        "generated_unix": time.time(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "failures": failures,
        "rows": {
            r["name"]: {
                "us_per_call": r["us_per_call"],
                "derived": r["derived"],
                "metrics": _parse_derived(r["derived"]),
            }
            for r in RESULTS
        },
    }
    with open(path, "w") as f:
        json.dump(snap, f, indent=2, sort_keys=True)
    print(f"# wrote {len(snap['rows'])} rows to {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="substring filter on benchmark function names")
    ap.add_argument("--json", default="", metavar="OUT",
                    help="write a BENCH_*.json perf snapshot to OUT")
    args = ap.parse_args()

    # benchmarks.coldstart_bench is not a suite here: it spawns worker
    # processes that need the accelerator, which this process holds once a
    # suite has run — run it from its own command line
    from . import break_even, distributions, kernel_bench, memory_study, \
        paper_tables, parallel_bench, serve_bench

    suites = (paper_tables.ALL + distributions.ALL + memory_study.ALL +
              kernel_bench.ALL + break_even.ALL + serve_bench.ALL +
              parallel_bench.ALL)

    print("name,us_per_call,derived")
    failures = 0
    for fn in suites:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — record, keep the suite going
            failures += 1
            # through emit() so the row also lands in the --json snapshot:
            # a vanished row would be indistinguishable from a removed bench
            from .common import emit
            emit(fn.__name__, 0.0, f"ERROR={type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if args.json:
        write_snapshot(args.json, failures)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
