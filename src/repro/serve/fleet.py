"""Replica fleet: N worker processes serving one checkpointed registry.

The software analogue of loading the same bitstream onto N FPGAs
(DeepFire2's SLR replication, see PAPERS.md): one process builds the model
and checkpoints it (``serve/persist.py``), then every replica cold-starts
from the shared artifacts —

    python -m repro.serve.fleet --replicas 4 --cache-dir /var/repro

``--cache-dir D`` holds the shared artifacts: ``D/registry`` (the params +
plan checkpoint) and ``D/study`` (train/convert artifacts when
``--trained``). Every process keeps its compiles in the one persistent
compilation cache (``core/compile_cache.py``: ``JAX_COMPILATION_CACHE_DIR``
or the checkout's fixed default).

**One process per chip.** An accelerator belongs to the one process that
first touches it, so the parent never imports jax: a prepare child builds
the checkpoint (if missing), reports the platform, and exits before any
replica starts. Replicas do not pin a device, so each would claim every
chip of a TPU host; the parent therefore refuses to start more than one
replica process on a TPU (pinning one replica per chip is not built yet).

Workers are plain subprocesses of this
module with ``--worker``; each restores the registry, warms the bucket
ladder (execute-only after a plan restore), serves the same deterministic
request set, and reports one JSON line: time-to-first-response measured
from *parent-side spawn time* (so interpreter + import cost is charged,
exactly what a scale-out event pays), plus every response's energy.

The parent then asserts the replies agree **bit-identically** — same
preds, same float32 per-request energy on every replica — which is the
serving-layer restatement of the repo's determinism contract: a restored
registry serves the same numbers as the registry that built it, however
many processes it is spread across. Any worker that hangs past
``--timeout`` gets the whole fleet killed and a non-zero exit (CI runs
this as a smoke step; see .github/workflows/ci.yml).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from .. import obs
from ..core import compile_cache

_T0_ENV = "REPRO_FLEET_T0"


def _dirs(cache_dir: str) -> tuple[str, str]:
    cache_dir = os.path.abspath(cache_dir)
    return (os.path.join(cache_dir, "registry"),
            os.path.join(cache_dir, "study"))


def _spec(args):
    from .bench import serve_spec

    # --quick trims the request set, never the net: cold-start numbers are
    # only meaningful for the paper-sized model (a toy net compiles so fast
    # there is nothing for the persistence layer to save)
    return serve_spec(args.dataset, backend=args.backend)


def _buckets(args) -> tuple:
    return tuple(int(b) for b in args.buckets.split(","))


def _build_registry(args, ck_dir: str, study_dir: str, *, save: bool):
    """Build (train if ``--trained``) + warm up + optionally checkpoint."""
    from . import persist
    from .bench import build_runtime

    cache = None
    if args.trained:
        from ..study import StudyCache

        cache = StudyCache(dir=study_dir)
    runtime, _ = build_runtime(_spec(args), _buckets(args),
                               trained=args.trained, cache=cache)
    if save:
        persist.save_registry(runtime.registry, ck_dir)
    return runtime.registry


def run_prepare(args) -> int:
    """Prepare child: checkpoint the registry if missing, report the
    platform as one JSON line, exit (releasing the accelerator)."""
    import jax

    from . import persist

    ck_dir, study_dir = _dirs(args.cache_dir)
    compile_cache.configure()
    built = not os.path.exists(os.path.join(ck_dir, persist.MANIFEST))
    if built:
        _build_registry(args, ck_dir, study_dir, save=True)
    devices = jax.devices()
    print(json.dumps({"platform": devices[0].platform,
                      "devices": len(devices), "built": built}), flush=True)
    return 0


def replica_limit(platform: str) -> int | None:
    """Most replica processes the fleet may start (None = no limit).

    On a TPU every unpinned process claims all chips of the host, so two
    replicas would contend for the same chips: one process at most.
    """
    return 1 if platform == "tpu" else None


# ---------------------------------------------------------------------------
# Worker
# ---------------------------------------------------------------------------

def run_worker(args) -> int:
    """One replica: restore -> warm up -> serve -> report a JSON line."""
    import numpy as np

    from . import persist
    from .batching import BucketPolicy
    from .bench import request_images
    from .runtime import ServeRuntime

    # audit: allow[host-sync] cold-start metering: first-response time is
    # charged from parent-side spawn (interpreter + imports included)
    now = time.time()
    t0 = float(os.environ.get(_T0_ENV, now))
    if args.trace:
        obs.enable()
    ck_dir, study_dir = _dirs(args.cache_dir)
    compile_cache.configure()

    restored = os.path.exists(os.path.join(ck_dir, persist.MANIFEST))
    with obs.span("coldstart.restore", restored=restored):
        if restored:
            registry = persist.load_registry(ck_dir)
        elif args.build:
            # cold path (no checkpoint yet): build everything in-process;
            # build_runtime warms the ladder, so skip the warmup below
            registry = _build_registry(args, ck_dir, study_dir,
                                       save=args.save)
        else:
            raise persist.CheckpointError(
                f"no registry checkpoint under {ck_dir!r} — run the fleet "
                "parent (or pass --build) first")
    # audit: allow[host-sync] phase timing for the cold-start breakdown
    t_restore = time.time()

    buckets = _buckets(args)
    if restored:
        with obs.span("coldstart.warmup", buckets=str(buckets)):
            for name in registry.names():
                registry.get(name).warmup(buckets)
    # audit: allow[host-sync] phase timing for the cold-start breakdown
    t_warm = time.time()

    runtime = ServeRuntime(registry, BucketPolicy(buckets))
    images = request_images(_spec(args), args.requests, seed=args.seed)
    for img in images:
        runtime.submit(img)
    with obs.span("coldstart.first_execute"):
        responses = runtime.step()
    # audit: allow[host-sync] the measurement itself: first response is out
    t_first = time.time()
    responses += runtime.run_until_drained()
    responses.sort(key=lambda r: r.rid)
    # audit: allow[host-sync] total serve wall time for the report
    t_done = time.time()

    name = registry.names()[0]
    result = {
        "replica": args.replica,
        "restored": restored,
        "model": name,
        "n": len(responses),
        "first_response_s": round(t_first - t0, 4),
        "serve_path_s": round(t_first - now, 4),
        "restore_s": round(t_restore - now, 4),
        "warmup_s": round(t_warm - t_restore, 4),
        "total_s": round(t_done - t0, 4),
        "compile_count": registry.get(name).compile_count,
        "preds": [int(r.pred) for r in responses],
        # float32 energies pass through float() exactly, so JSON round-trips
        # them bit-identically for the parent's cross-replica comparison
        "energies": [float(np.float32(r.energy_j)) for r in responses],
    }
    if args.trace:
        obs.save_jsonl(args.trace)
    print(json.dumps(result), flush=True)
    return 0


# ---------------------------------------------------------------------------
# Parent
# ---------------------------------------------------------------------------

def _child_cmd(args, role: str) -> list[str]:
    """Command line of a prepare (``--prepare-worker``) or replica child."""
    cmd = [sys.executable, "-m", "repro.serve.fleet", role,
           "--cache-dir", args.cache_dir,
           "--requests", str(args.requests), "--seed", str(args.seed),
           "--buckets", args.buckets, "--dataset", args.dataset,
           "--backend", args.backend]
    if args.quick:
        cmd.append("--quick")
    if args.trained:
        cmd.append("--trained")
    return cmd


def _worker_cmd(args, replica: int) -> list[str]:
    cmd = _child_cmd(args, "--worker") + ["--replica", str(replica)]
    if args.trace:
        root, ext = os.path.splitext(args.trace)
        cmd += ["--trace", f"{root}.r{replica}{ext or '.jsonl'}"]
    return cmd


def run_fleet(args) -> int:
    # the parent stays off jax: the prepare child touches the accelerator
    # and exits before any replica needs it
    try:
        with obs.span("coldstart.prepare"):
            prep = subprocess.run(_child_cmd(args, "--prepare-worker"),
                                  text=True, capture_output=True,
                                  timeout=args.timeout)
    except subprocess.TimeoutExpired:
        print(f"fleet: prepare step exceeded --timeout={args.timeout}s",
              file=sys.stderr, flush=True)
        return 124
    if prep.returncode != 0:
        sys.stderr.write(prep.stderr)
        print(f"fleet: prepare step exited {prep.returncode}",
              file=sys.stderr, flush=True)
        return prep.returncode or 1
    info = json.loads(prep.stdout.strip().splitlines()[-1])
    if info["built"]:
        print(f"fleet: registry checkpoint written under {args.cache_dir}",
              flush=True)
    if args.prepare_only:
        return 0
    limit = replica_limit(info["platform"])
    if limit is not None and args.replicas > limit:
        print(f"fleet: refusing to start {args.replicas} replica processes "
              f"on a {info['platform']} host with {info['devices']} "
              f"device(s): replicas are not pinned to a chip, so every one "
              f"would claim all of them; at most {limit} can run here",
              file=sys.stderr, flush=True)
        return 2

    procs = []
    for i in range(args.replicas):
        # audit: allow[host-sync] spawn timestamp: the base of each
        # worker's cold-start-to-first-response measure
        env = dict(os.environ, **{_T0_ENV: repr(time.time())})
        procs.append(subprocess.Popen(
            _worker_cmd(args, i), env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    print(f"fleet: launched {args.replicas} replicas "
          f"(shared cache: {args.cache_dir})", flush=True)

    # audit: allow[host-sync] fleet-wide teardown deadline
    deadline = time.time() + args.timeout
    results = []
    for i, p in enumerate(procs):
        try:
            # audit: allow[host-sync] remaining-budget computation
            out, err = p.communicate(timeout=max(1.0, deadline - time.time()))
        except subprocess.TimeoutExpired:
            for q in procs:   # tear the whole fleet down, reap everything
                q.kill()
            for q in procs:
                q.communicate()
            print(f"fleet: replica {i} exceeded --timeout={args.timeout}s; "
                  "killed all replicas", file=sys.stderr, flush=True)
            return 124
        if p.returncode != 0:
            sys.stderr.write(err)
            print(f"fleet: replica {i} exited {p.returncode}",
                  file=sys.stderr, flush=True)
            return p.returncode or 1
        try:
            results.append(json.loads(out.strip().splitlines()[-1]))
        except (IndexError, json.JSONDecodeError):
            sys.stderr.write(err)
            print(f"fleet: replica {i} produced no result line",
                  file=sys.stderr, flush=True)
            return 1

    print(f"\n  replica  restored  first_response_s  restore_s  warmup_s  "
          f"total_s  compiles")
    for r in results:
        print(f"  {r['replica']:7d}  {str(r['restored']):>8}  "
              f"{r['first_response_s']:16.2f}  {r['restore_s']:9.2f}  "
              f"{r['warmup_s']:8.2f}  {r['total_s']:7.2f}  "
              f"{r['compile_count']:8d}")

    ref = results[0]
    for r in results[1:]:
        if r["preds"] != ref["preds"] or r["energies"] != ref["energies"]:
            from .api import ServeError

            raise ServeError(
                f"replica {r['replica']} disagrees with replica "
                f"{ref['replica']} on the same request set — preds equal: "
                f"{r['preds'] == ref['preds']}, energies equal: "
                f"{r['energies'] == ref['energies']}. The restored registry "
                "broke bit-exactness; see docs/SERVING.md")
    total_j = sum(ref["energies"])
    print(f"\nfleet: {len(results)} replicas served {ref['n']} requests "
          f"each — preds and per-request energies bit-identical "
          f"(total {total_j * 1e6:.1f} uJ/replica)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="replica fleet over one checkpointed model registry")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--cache-dir", required=True,
                    help="shared artifact dir: registry checkpoint, "
                         "study cache")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: small net, small request set")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--seed", type=int, default=2026)
    ap.add_argument("--buckets", default="1,4")
    ap.add_argument("--dataset", default="mnist")
    ap.add_argument("--backend", default="queue_pallas")
    ap.add_argument("--trained", action="store_true",
                    help="serve the study-trained net (shares train/convert "
                         "artifacts via the study cache) instead of "
                         "initialized weights")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="parent-side deadline; a late worker kills the fleet")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="per-replica obs traces (PATH.rN.jsonl)")
    ap.add_argument("--prepare-only", action="store_true",
                    help="build + checkpoint the registry, then exit")
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--prepare-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--build", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--save", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--replica", type=int, default=0, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.worker:
        return run_worker(args)
    if args.prepare_worker:
        return run_prepare(args)
    return run_fleet(args)


if __name__ == "__main__":
    raise SystemExit(main())
