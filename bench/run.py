#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell, its configuration, its traffic mix
and its metrics are looked up by name in ``BENCHMARK.json``. The run needs
as many TPU chips as the cell asks for and fails, printing no result,
without them. It runs in this one process (a chip belongs to the process
that first touches it).

Standard error ends with the numbers that decided ``correct``, each beside
its limit; the last line of standard output is the result as one JSON
object. ``--trace 1`` records a profile of the window and reports the
cell's per-layer metrics; ``--trace 0`` its end-to-end metrics.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def fail(msg: str, code: int = 1) -> int:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return fail(f"no src/repro under {ROOT}: run from a checkout of the "
                    "repository", 2)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        return fail(f"unknown workload {args.workload!r}; cells: "
                    f"{sorted(cells)}", 2)
    cell = cells[args.workload]
    sys.path[:0] = [BENCH, src]
    # libtpu's logs would otherwise go to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX found no TPU (platform {devices[0].platform!r}); "
                    "the benchmark runs only on the chip")
    if len(devices) < cell["chips"]:
        return fail(f"{args.workload} needs {cell['chips']} chips, JAX "
                    f"found {len(devices)}")

    import harness

    out = harness.run_cell(bench, cell, args.seed, args.seconds,
                           bool(args.trace), T_PROCESS0, devices)
    report(out)
    return 0


def report(out: dict) -> None:
    """The compared numbers on standard error, then the result line."""
    for name, (value, limit) in out["compared"].items():
        print(f"[bench] compared {name}: {value!r} limit {limit!r}",
              file=sys.stderr)
    print(f"[bench] correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    raise SystemExit(main())
