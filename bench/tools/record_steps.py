#!/usr/bin/env python3
"""Record a profile of a few steps of a cell, small enough to keep as a
test fixture.

    python3 bench/tools/record_steps.py --workload cifar10-dense-saturated \
        --seconds 0.03 --seed 2147484102 \
        --out bench/testdata/dense-saturated-2steps.xplane.pb

One process on the chip: the cell is built and warmed as a run builds it
(``harness.build``), then its generator runs one short window under
``jax.profiler`` with the Python tracer off, so the profile holds the
benchmark's and the program's spans and the device's ops, and not every
Python call. The window takes no new step after ``--seconds``. The
``.xplane.pb`` is copied to ``--out`` and the steps it holds are printed.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    import harness
    import trace_reduce

    if jax.devices()[0].platform != "tpu":
        print("record_steps runs on the chip", file=sys.stderr)
        return 1
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    config = harness.load_json(ROOT, next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    mix = harness.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    gen = harness.load_module(os.path.join(BENCH, "loadgen",
                                           mix["kind"] + ".py"), "gen")
    harness.configure_cache()
    served = harness.build(config, mix, gen, args.seed, cell["chips"])

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir, profiler_options=options)
        window = gen.run(served, mix, args.seconds, args.seed)
        jax.profiler.stop_trace()
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        shutil.copyfile(trace_reduce.find_xplane(log_dir), args.out)
    print(json.dumps({"out": args.out, "bytes": os.path.getsize(args.out),
                      "steps": len(window.batches),
                      "window_s": window.t1 - window.t0}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
