#!/usr/bin/env python3
"""Chip smoke: serve the paper's CIFAR-10 SNN once on one TPU chip.

Runs from the repository root, in one process (a chip belongs to the one
process that first touches it), with weights and request images made from
``--seed``:

  (a) serve    ``cifar10`` at published widths through
               ``serve.bench.build_runtime`` -> ``ModelRegistry`` ->
               ``ServeRuntime`` on ``queue_pallas``, bucket ladder
               (1, 4, 16, 64); every event-driven conv stage must see
               events and none may be saturated;
  (b) compare  the served logits and per-layer events against the
               ``dense`` backend under ``jax.default_matmul_precision
               ("highest")`` on the same images;
  (c) sparse   ``engine.infer_batch(backend="queue_sparse")`` on the first
               64 images, checked against the same reference;
  (d) persist  ``save_registry`` -> ``load_registry`` -> serve again: no
               plan may fall back to re-lowering, and the restored
               registry may compile nothing;
  (e) kernels  the compiled ``pallas`` and ``sparse_pallas`` kernels ran,
               and no interpreter, oracle or XLA realization did.

``--chips 4`` runs only the data-parallel path instead: the sharded batch
executor on a four-device ``("data",)`` mesh against one device, for
``dense`` and ``queue_pallas``, and one mesh-backed serve step.

The queue depth is 256, above every queue's window count (at most 121), so
no event is dropped and the dense backend computes the same function as
the event path. The engine pins its f32 convolutions and matmuls to
``Precision.HIGHEST`` (``core.snn_layers.PRECISION``), so the chip and the
reference differ only in f32 summation order, and a spike flips only where
a membrane potential sits within ~1e-7 of its threshold. The tolerances
leave room for a few such flips and their spread through later layers, and
no more: at the TPU's default (bf16) precision this net agrees with the
reference on only ~57 % of predictions, which they reject.

  PRED_AGREE_MIN    share of images whose argmax agrees (>= 0.98);
  EVENTS_REL_MAX    |served - reference| / reference, events per layer
                    summed over the images (<= 1e-3);
  LOGIT_DIFF_MAX    max |served - reference| logit, as a share of the
                    reference's largest |logit| (<= 0.25).

Times printed on the way are a smoke reading (one run, compile included
where it says so), not benchmark numbers. The last line of standard output
is one JSON object; the exit code is 0 only if every phase passed on a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

BUCKETS = (1, 4, 16, 64)
DEPTH = 256
# 5 full 64-buckets, then one request for each smaller bucket
N_REQUESTS = 5 * 64 + 16 + 4 + 1
SPARSE_BATCH = 64

PRED_AGREE_MIN = 0.98
EVENTS_REL_MAX = 1e-3
LOGIT_DIFF_MAX = 0.25


class SmokeFailure(Exception):
    """A phase's check failed."""


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Phases (callable on the CPU at a tiny size as well)
# ---------------------------------------------------------------------------

def serve_phase(spec, images, buckets, seed):
    """(a) Build, warm and serve ``images``; returns (runtime, name, responses)."""
    from repro.serve.bench import build_runtime

    t0 = time.perf_counter()
    runtime, name = build_runtime(spec, buckets, init_seed=seed)
    t1 = time.perf_counter()
    for img in images:
        runtime.submit(img, name)
    responses = sorted(runtime.run_until_drained(), key=lambda r: r.rid)
    t2 = time.perf_counter()
    _log(f"(a) serve: build+warmup (compiles {len(buckets)} buckets) "
         f"{t1 - t0:.3f}s, {len(images)} requests {t2 - t1:.3f}s "
         f"[smoke reading]; buckets used "
         f"{dict(runtime.stats_summary()['bucket_histogram'])}")
    return runtime, name, responses


def served_arrays(responses):
    """(logits (N, n_out), events_in (N, L)) of responses in rid order."""
    import numpy as np

    return (np.stack([r.logits for r in responses]),
            np.concatenate([r.stats.events_in for r in responses]))


def check_activity(cfg, events):
    """Every event-driven conv stage has events and none is saturated.

    ``events`` is (N, L) ``events_in``; saturation means every neuron of
    the stage's input spiked at every time step.
    """
    from repro.core import engine

    plan = engine.compile_plan(cfg.spec, cfg.input_hw, cfg.input_c,
                               cfg.compressed)
    mean = events.mean(0)  # one row per weighted layer, convs first
    shares = []
    for i, cp in enumerate(plan.convs):
        if i == 0:
            continue  # the first conv is fed analog current, not events
        full = cfg.T * cp.in_hw * cp.in_hw * cp.in_c
        share = float(mean[i]) / full
        shares.append(share)
        _log(f"(a) conv {i} ({cp.in_hw}x{cp.in_hw}x{cp.in_c}): "
             f"{float(mean[i]):.1f} events/image, {share:.4f} of "
             f"saturation")
        if share == 0.0:
            raise SmokeFailure(f"conv stage {i} saw no events: the "
                               "comparison with the reference would be "
                               "vacuous")
        if share >= 0.99:
            raise SmokeFailure(f"conv stage {i} is saturated ({share:.4f})")
    return shares


def reference(handle, images, chunk):
    """The ``dense`` backend at highest matmul precision, in chunks."""
    import jax
    import numpy as np

    from repro.core import engine

    logits, events = [], []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(images), chunk):
            lg, st = engine.infer_batch(handle.params, handle.thresholds,
                                        handle.cfg, images[i:i + chunk],
                                        backend="dense")
            logits.append(np.asarray(lg))
            events.append(np.asarray(st.events_in))
    return np.concatenate(logits), np.concatenate(events)


def compare(tag, logits, events, ref_logits, ref_events):
    """Agreement of a path with the reference; raises past the tolerances."""
    import numpy as np

    agree = float((logits.argmax(1) == ref_logits.argmax(1)).mean())
    diff = float(np.abs(logits - ref_logits).max())
    scale = float(np.abs(ref_logits).max())
    ev, ev_ref = events.sum(0), ref_events.sum(0)
    rel = [float(abs(a - b) / b) if b else float(a != b)
           for a, b in zip(ev, ev_ref)]
    out = {"pred_agree": agree, "max_logit_diff": diff,
           "ref_max_abs_logit": scale, "events_rel_diff": rel}
    _log(f"{tag} vs dense@highest: {json.dumps(out)}")
    problems = []
    if agree < PRED_AGREE_MIN:
        problems.append(f"prediction agreement {agree} < {PRED_AGREE_MIN}")
    if diff > LOGIT_DIFF_MAX * scale:
        problems.append(f"max logit diff {diff} > {LOGIT_DIFF_MAX} x {scale}")
    if max(rel) > EVENTS_REL_MAX:
        problems.append(f"per-layer events differ by up to {max(rel)} > "
                        f"{EVENTS_REL_MAX}")
    if problems:
        raise SmokeFailure(f"{tag}: " + "; ".join(problems))
    return out


def sparse_phase(handle, images):
    """(c) The occupancy-gated backend on one batch."""
    import jax
    import numpy as np

    from repro.core import engine

    t0 = time.perf_counter()
    lg, st = engine.infer_batch(handle.params, handle.thresholds, handle.cfg,
                                images, backend="queue_sparse")
    lg = np.asarray(jax.block_until_ready(lg))
    _log(f"(c) queue_sparse B={len(images)}: {time.perf_counter() - t0:.3f}s "
         "incl. compiles [smoke reading]")
    return lg, np.asarray(st.events_in)


def roundtrip_phase(runtime, name, images, buckets, root, served_logits):
    """(d) Save, restore, serve again; no fallback and no compile."""
    import numpy as np

    from repro import obs
    from repro.serve import ServeRuntime, load_registry, save_registry
    from repro.serve.batching import BucketPolicy

    shutil.rmtree(root, ignore_errors=True)
    before = dict(obs.metrics_snapshot()["counters"])
    t0 = time.perf_counter()
    save_registry(runtime.registry, root)
    registry = load_registry(root)
    restored = ServeRuntime(registry, BucketPolicy(buckets))
    for img in images:
        restored.submit(img, name)
    responses = sorted(restored.run_until_drained(), key=lambda r: r.rid)
    _log(f"(d) save+load+serve {len(images)} requests: "
         f"{time.perf_counter() - t0:.3f}s [smoke reading]")
    after = obs.metrics_snapshot()["counters"]
    counts = {k: after.get(k, 0) - before.get(k, 0) for k in (
        "persist.plan_export", "persist.plan_export_skipped",
        "persist.plan_restore", "persist.plan_restore_fallback",
        "persist.plan_restore_skipped")}
    compiles = registry.get(name).compile_count
    logits = np.stack([r.logits for r in responses])
    diff = float(np.abs(logits - served_logits).max())
    out = dict(counts, restored_compiles=compiles, max_logit_diff=diff)
    _log(f"(d) persist: {json.dumps(out)}")
    problems = [f"{k}={v}" for k, v in counts.items()
                if v and k in ("persist.plan_export_skipped",
                               "persist.plan_restore_fallback",
                               "persist.plan_restore_skipped")]
    if counts["persist.plan_restore"] != len(buckets):
        problems.append(f"restored {counts['persist.plan_restore']} of "
                        f"{len(buckets)} plans")
    if compiles:
        problems.append(f"the restored registry compiled {compiles} plans")
    if diff:
        problems.append(f"restored plans changed the logits by {diff}")
    if problems:
        raise SmokeFailure("(d) persist: " + "; ".join(problems))
    return out


def dispatch_phase(counts, required, forbidden):
    """(e) Which realizations ran, from ``kernels.ops.dispatch_counts``."""
    got = {k: v for k, v in counts.items() if v}
    _log(f"(e) dispatch counts: {json.dumps(got, sort_keys=True)}")
    missing = [k for k in required if not got.get(k)]
    bad = sorted(k for k in got if k.split(":", 1)[1] in forbidden)
    if missing or bad:
        raise SmokeFailure(f"(e) kernels: missing {missing}, ran {bad}")
    return got


def run_one_chip(seed, *, dataset="cifar10", n_requests=N_REQUESTS,
                 buckets=BUCKETS, sparse_batch=SPARSE_BATCH,
                 required=("fused:pallas", "fused:sparse_pallas"),
                 forbidden=("xla", "sparse", "ref", "pallas_interpret",
                            "sparse_pallas_interpret"),
                 workdir=None):
    """Phases (a)-(e); returns a summary dict or raises SmokeFailure."""
    import numpy as np

    from repro import obs
    from repro.kernels import ops as kops
    from repro.serve.bench import request_images, serve_spec

    obs.enable()
    kops.dispatch_counts.clear()
    spec = serve_spec(dataset, backend="queue_pallas", depth=DEPTH)
    images = np.asarray(request_images(spec, n_requests, seed=seed))

    runtime, name, responses = serve_phase(spec, images, buckets, seed)
    handle = runtime.registry.get(name)
    logits, events = served_arrays(responses)
    overflow = int(sum(int(r.stats.overflow.sum()) for r in responses))
    if overflow:
        raise SmokeFailure(f"(a) {overflow} events dropped at depth {DEPTH}")
    shares = check_activity(handle.cfg, events)

    t0 = time.perf_counter()
    ref_logits, ref_events = reference(handle, images, max(buckets))
    _log(f"(b) dense@highest reference: {time.perf_counter() - t0:.3f}s "
         "incl. compile [smoke reading]")
    served = compare("(b) queue_pallas", logits, events, ref_logits,
                     ref_events)

    n = min(sparse_batch, len(images))
    sp_logits, sp_events = sparse_phase(handle, images[:n])
    sparse = compare("(c) queue_sparse", sp_logits, sp_events,
                     ref_logits[:n], ref_events[:n])

    root = os.path.join(workdir or os.path.join(ROOT, ".chip_smoke"),
                        "registry")
    persist = roundtrip_phase(runtime, name, images, buckets, root, logits)
    kernels = dispatch_phase(kops.dispatch_counts, required, forbidden)
    return {"served": served, "sparse": sparse, "persist": persist,
            "kernels": kernels, "activity": shares}


def run_four_chips(seed, *, dataset="cifar10", batch=64, n_devices=4):
    """The data-parallel path across ``n_devices`` against one device."""
    import jax
    import numpy as np

    from repro import parallel
    from repro.core import engine, snn_model
    from repro.serve import ModelRegistry
    from repro.serve.bench import request_images, serve_spec

    mesh = parallel.data_mesh(n_devices)
    spec = serve_spec(dataset, depth=DEPTH)
    cfg = spec.snn_config()
    params = snn_model.init_params(jax.random.PRNGKey(seed), spec.net,
                                   spec.input_hw, spec.input_c)
    th = tuple([1.0] * len(snn_model.parse_spec(spec.net)))
    images = np.asarray(request_images(spec, batch, seed=seed))
    out = {}
    for backend in ("dense", "queue_pallas"):
        one, _ = engine.infer_batch(params, th, cfg, images, backend=backend)
        t0 = time.perf_counter()
        many, _ = parallel.infer_batch_sharded(params, th, cfg, images,
                                               backend=backend, mesh=mesh)
        many = jax.block_until_ready(many)
        _log(f"sharded {backend} B={batch} over {n_devices} devices: "
             f"{time.perf_counter() - t0:.3f}s incl. compile "
             "[smoke reading]")
        runner = parallel.batch_runner_sharded(cfg, backend, mesh)
        compiled = runner.lower(params, th, jax.ShapeDtypeStruct(
            images.shape, images.dtype)).compile()
        param_sh = jax.tree.leaves(compiled.input_shardings[0][0])[0]
        devices = many.sharding.device_set
        diff = float(np.abs(np.asarray(many) - np.asarray(one)).max())
        out[backend] = {"devices": len(devices), "max_logit_diff": diff,
                        "params_replicated": bool(
                            param_sh.is_fully_replicated
                            and len(param_sh.device_set) == n_devices)}
        _log(f"{backend}: {json.dumps(out[backend])}")
        if len(devices) != n_devices or not out[backend]["params_replicated"]:
            raise SmokeFailure(f"{backend}: sharding wrong: {out[backend]}")
        if not np.array_equal(np.asarray(many).argmax(1),
                              np.asarray(one).argmax(1)):
            raise SmokeFailure(f"{backend}: sharded predictions differ "
                               f"from one device (max diff {diff})")

    registry = ModelRegistry()
    handle = registry.register("smoke", params, th, cfg,
                               backend="queue_pallas")
    single = np.asarray(handle.run_bucket(images, batch)[0])
    registry.set_mesh(mesh)
    served, _ = handle.run_bucket(images, batch)
    devices = handle.plan_for(batch).output_shardings[0].device_set
    diff = float(np.abs(np.asarray(served) - single).max())
    out["serve"] = {"devices": len(devices), "max_logit_diff": diff}
    _log(f"mesh-backed serve step: {json.dumps(out['serve'])}")
    if len(devices) != n_devices or not np.array_equal(
            np.asarray(served).argmax(1), single.argmax(1)):
        raise SmokeFailure(f"mesh-backed serve step wrong: {out['serve']}")
    return out


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the request images")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel path on four chips")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        _log(f"no src/repro beside {__file__}: run from a repository checkout")
        return 2
    sys.path.insert(0, src)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        _log(f"JAX found no TPU (platform {platform!r}); this smoke runs "
             "only on the chip")
        return 1
    if len(devices) < args.chips:
        _log(f"--chips {args.chips} needs {args.chips} devices, found "
             f"{len(devices)}")
        return 1

    from repro.core import compile_cache

    _log(f"devices: {len(devices)} x {devices[0].device_kind}; compile "
         f"cache {compile_cache.configure()}")
    try:
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
    except SmokeFailure as e:
        _log(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
