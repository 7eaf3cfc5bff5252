#!/usr/bin/env python3
"""Find the knee of an open-loop mix once: the highest rate whose queue
does not grow.

    python3 bench/tools/knee_sweep.py --config cifar10-queue_pallas \
        --traffic open-4of5 --rates 60,70,80,90,100 --seconds 20 --seed 5

One process on one chip: the runtime is built and warmed once, then the
mix's generator runs one window per rate (the mix's own rate is replaced).
For each rate it prints the latency median and 95th percentile, the mean
bucket fill, and the drain: how long after the last request fell due the
last answer came. A queue that keeps up drains in about one or two steps;
one that grows drains in a time that grows with the window. The knee goes
into the mix's file as a number; the sweep is not part of any run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "knee.json"))
    args = ap.parse_args(argv)

    import jax

    import harness
    import readings
    import stats

    if jax.devices()[0].platform != "tpu":
        print("knee_sweep runs on the chip", file=sys.stderr)
        return 1
    harness.configure_cache()
    config = harness.load_json(BENCH, "configs", args.config + ".json")
    mix = harness.load_json(BENCH, "traffic", args.traffic + ".json")
    gen = harness.load_module(os.path.join(BENCH, "loadgen",
                                           mix["kind"] + ".py"), "gen")
    served = harness.build(config, mix, gen, args.seed, 1)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        w = gen.run(served, dict(mix, rate_per_s=rate), args.seconds,
                    args.seed)
        lat = w.latency_s
        row = {"rate_per_s": rate, "served": w.served,
               "served_per_s": readings.images_per_s(
                   type("R", (), {"window": w})),
               "p50_ms": stats.percentile(lat, 50) * 1e3,
               "p95_ms": stats.percentile(lat, 95) * 1e3,
               "fill": w.served / sum(b.bucket for b in w.batches),
               "drain_s": w.t1 - (w.t0 + args.seconds),
               "batches": len(w.batches),
               "step_s": (sum(b.t1 - b.t0 for b in w.batches)
                          / len(w.batches))}
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
