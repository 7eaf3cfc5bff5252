#!/usr/bin/env python3
"""Read the two ends a comparison limit is set between, on the chip.

    python3 bench/tools/control.py --workload cifar10-event-saturated \
        --seeds 11,12,13 --seconds 6

For every seed, in one process: set the cell up as a run does, drive its
traffic for a short window, and compare a seeded sample of the answers
with the reference, as ``correct`` is decided (the lower reading, from the
program). Then compute the reference on the same sample at the precisions
below the configuration's, each in the program's place (the control's
upper readings): ``HIGH`` (three bf16 passes) is the nearest below
``HIGHEST``; ``DEFAULT`` (one bf16 pass) is what the chip does unasked.
Each reading is printed as a JSON line and all are written to ``--out``.
Runs of the benchmark never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

CONTROLS = ("HIGH", "DEFAULT")


def readings(bench, cell, seed: int, seconds: float) -> dict:
    import harness

    config = harness.load_json(ROOT, next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    mix = harness.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    gen = harness.load_module(os.path.join(BENCH, "loadgen",
                                           mix["kind"] + ".py"), "gen")
    served = harness.build(config, mix, gen, seed, cell["chips"])
    window = gen.run(served, mix, seconds, seed)
    served.runtime = None
    images, logits, events = harness.sample_answers(served, window, seed)
    ref = harness.reference_on(config, served, images)
    out = {"seed": seed, "sampled": len(images), "served": window.served,
           "program": harness.compare(logits, events, *ref)}
    for prec in CONTROLS:
        ctl = harness.reference_on(config, served, images, prec)
        out[prec] = harness.compare(*ctl, *ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    import harness

    if jax.devices()[0].platform != "tpu":
        print("control readings are taken on the chip", file=sys.stderr)
        return 1
    harness.configure_cache()
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(readings(bench, cell, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    out = args.out or os.path.join(ROOT, "chiprun_out",
                                   f"control-{args.workload}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
