"""Cold-start-to-first-response: cold vs warm replica start (ROADMAP 3).

The paper-side motivation: an FPGA accelerator is servable seconds after
its (pre-built) bitstream loads, while a fresh JAX process re-traces and
re-compiles everything. This bench measures what the persistence layer
(``repro.serve.persist`` + the persistent compilation cache) buys:

- **cold**: a worker process facing an empty artifact dir and an empty
  compilation cache — builds the model, AOT-compiles the bucket ladder,
  serves. This is what every replica paid before PR 10.
- **warm**: the same worker facing the artifacts the cold run left behind —
  restores the checkpointed registry (params + ``jax.export`` plan blobs),
  warms execute-only against the shared compilation cache, serves.

Both rows time the *serve path*: worker-process entry to first response.
Interpreter + ``import jax`` time (~2.5 s, identical in both phases and
untouched by this layer) is excluded so the ratio isolates what the
persistence layer controls; the spawn-measured wall time is recorded in
each row's derived metrics as ``spawn_to_first_s``.

    # CI shape: two invocations, one shared dir, then the paired-row gate
    python -m benchmarks.coldstart_bench --quick --phase cold --json coldstart.json
    python -m benchmarks.coldstart_bench --quick --phase warm --json coldstart.json
    python scripts/check_bench_regression.py coldstart.json coldstart.json --coldstart-min-speedup 5

The artifact dir is fixed (``--cache-dir``, default ``.coldstart-cache`` in
the checkout) and the compilation cache is the one every entry point uses
(``repro.core.compile_cache``). The cold phase empties both first; it
refuses to empty a non-empty cache that ``JAX_COMPILATION_CACHE_DIR``
names, since that directory is not this bench's to delete. The cold phase
leaves warm artifacts behind (checkpoint + cache entries, including one
discarded populate run so the restored-plan programs are cached, not just
the AOT ones), which is exactly the fleet deployment story: the first
replica ever pays cold, all later replicas pay warm.

Each phase spawns worker processes, so it runs from its own command line
and never from a parent that has already touched jax.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

from repro.core import compile_cache

MANIFEST = "registry.json"  # mirror of repro.serve.persist.MANIFEST
DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".coldstart-cache")


def _worker(cache_dir: str, *, requests: int, quick: bool,
            build: bool = False, save: bool = False, trace: str = "",
            spawn_t0: bool = True) -> dict:
    """Run one fleet worker subprocess; return its parsed result line."""
    cmd = [sys.executable, "-m", "repro.serve.fleet", "--worker",
           "--cache-dir", cache_dir, "--requests", str(requests)]
    if quick:
        cmd.append("--quick")
    if build:
        cmd.append("--build")
    if save:
        cmd.append("--save")
    if trace:
        cmd += ["--trace", trace]
    env = dict(os.environ)
    if spawn_t0:
        env["REPRO_FLEET_T0"] = repr(time.time())
    proc = subprocess.run(cmd, env=env, text=True, capture_output=True)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        raise RuntimeError(f"coldstart worker failed ({proc.returncode})")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _row(result: dict, extra: str = "") -> tuple[float, str]:
    serve_s = result["serve_path_s"]
    derived = (f"serve_path_s={serve_s:.3f}"
               f";spawn_to_first_s={result['first_response_s']:.3f}"
               f";restore_s={result['restore_s']:.3f}"
               f";warmup_s={result['warmup_s']:.3f}"
               f";compiles={result['compile_count']}"
               f";n={result['n']}")
    if extra:
        derived += ";" + extra
    return serve_s * 1e6, derived


def _empty_for_cold(cache_dir: str) -> None:
    """Empty the artifact dir and the compilation cache (see module doc)."""
    xla_dir = compile_cache.resolve_dir()
    if (os.environ.get(compile_cache.ENV_DIR) and os.path.isdir(xla_dir)
            and os.listdir(xla_dir)):
        raise SystemExit(
            f"--phase cold needs an empty compilation cache, but "
            f"{compile_cache.ENV_DIR}={xla_dir} holds entries — empty it or "
            "unset the variable (cold numbers from a warm cache would be a "
            "lie)")
    for d in (cache_dir, xla_dir):
        shutil.rmtree(d, ignore_errors=True)
    os.makedirs(cache_dir)


def run_cold(cache_dir: str, *, requests: int, quick: bool,
             trace: str = "") -> tuple[float, str]:
    """Measure the cold phase, then leave warm artifacts behind."""
    _empty_for_cold(cache_dir)
    cold = _worker(cache_dir, requests=requests, quick=quick,
                   build=True, save=True, trace=trace)
    # populate pass (discarded): the restored-plan programs differ from the
    # AOT programs the cold build cached, so one warm run seeds their cache
    # entries — mirroring a fleet, where replica 2 warms the dir replica 1
    # built and replica 3+ get pure hits
    _worker(cache_dir, requests=requests, quick=quick)
    return _row(cold)


def run_warm(cache_dir: str, *, requests: int, quick: bool,
             trace: str = "", cold_us: float | None = None
             ) -> tuple[float, str]:
    marker = os.path.join(cache_dir, "registry", MANIFEST)
    if not os.path.exists(marker):
        raise SystemExit(
            f"--phase warm needs the cold phase's artifacts, but {marker} "
            "is missing — run --phase cold against this dir first")
    warm = _worker(cache_dir, requests=requests, quick=quick, trace=trace)
    if warm["compile_count"]:
        raise SystemExit(
            f"warm worker AOT-compiled {warm['compile_count']} plans — the "
            "checkpoint restore fell back to re-lowering; warm numbers "
            "would not measure the restore path")
    extra = ""
    if cold_us:
        extra = f"speedup_vs_cold={cold_us / (warm['serve_path_s'] * 1e6):.1f}"
    return _row(warm, extra)


# ---------------------------------------------------------------------------
# Standalone CLI (the CI coldstart job: cold and warm as separate invocations)
# ---------------------------------------------------------------------------

def _merge_snapshot(path: str, rows: dict) -> None:
    """Merge rows into a bench-v1 snapshot at ``path`` (create or update)."""
    from .run import _parse_derived

    snap = {"schema": "bench-v1", "failures": 0, "rows": {}}
    if os.path.exists(path):
        with open(path) as f:
            snap = json.load(f)
    for name, (us, derived) in rows.items():
        snap.setdefault("rows", {})[name] = {
            "us_per_call": us, "derived": derived,
            "metrics": _parse_derived(derived)}
    snap["generated_unix"] = time.time()
    with open(path, "w") as f:
        json.dump(snap, f, indent=2, sort_keys=True)
    print(f"# wrote {sorted(rows)} into {path}", file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="cold vs warm start-to-first-response bench")
    ap.add_argument("--phase", choices=("cold", "warm", "both"),
                    default="both")
    ap.add_argument("--cache-dir", default=DEFAULT_DIR,
                    help="shared artifact dir; the cold phase empties it")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--json", default="", metavar="OUT",
                    help="write/merge a bench-v1 snapshot (cold and warm "
                         "invocations share OUT; the paired-row gate in "
                         "scripts/check_bench_regression.py reads it)")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="obs trace of the measured worker (CI artifact)")
    args = ap.parse_args(argv)

    rows = {}
    cache_dir = os.path.abspath(args.cache_dir)
    if args.phase in ("cold", "both"):
        us, derived = run_cold(cache_dir, requests=args.requests,
                               quick=args.quick, trace=args.trace)
        rows["coldstart/first_response_cold"] = (us, derived)
        print(f"coldstart/first_response_cold,{us:.1f},{derived}")
    if args.phase in ("warm", "both"):
        cold_us = rows.get("coldstart/first_response_cold", (None, ""))[0]
        if cold_us is None and args.json and os.path.exists(args.json):
            with open(args.json) as f:
                prior = json.load(f).get("rows", {})
            cold_us = prior.get("coldstart/first_response_cold",
                                {}).get("us_per_call")
        us, derived = run_warm(cache_dir, requests=args.requests,
                               quick=args.quick, trace=args.trace,
                               cold_us=cold_us)
        rows["coldstart/first_response_warm"] = (us, derived)
        print(f"coldstart/first_response_warm,{us:.1f},{derived}")

    if args.json:
        _merge_snapshot(args.json, rows)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
