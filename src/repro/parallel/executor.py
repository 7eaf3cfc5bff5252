"""Data-parallel SNN execution: ``engine.infer_batch`` over a device mesh.

``shard_map`` splits the batch axis of the engine's batched plan across the
mesh's ``data`` axis: every device walks the *same* compiled layer plan over
its local batch shard (params and thresholds replicated), and the outputs —
logits and the per-sample :class:`~repro.core.engine.SNNStats` rows — come
back concatenated in batch order. Because the engine's mask contract
guarantees the batch axis is sample-independent in every backend (row ``i``
is bit-identical no matter which or how many other samples share the batch),
the sharded result is **bit-exact** equal to the single-device call — logits
AND stats, including AEQ overflow in the drop regime. ``tests/test_parallel``
pins this at B ∈ {1, 3, 16, 64} on ``dense`` and ``queue_pallas``.

Batch sizes that do not divide the mesh reuse the serving layer's padding
trick: the batch is zero-padded to the next multiple of the mesh size and
the valid prefix sliced back out (``engine.slice_valid``) — exactly the
``infer_batch_masked`` contract applied at mesh granularity. Whether a
shape needs the fallback is decided by
:func:`repro.parallel.mesh.batch_partition_spec`.

:func:`use_mesh` installs the sharded path as the engine's batch dispatch,
so everything built on ``engine.infer_batch`` — the study ``collect`` stage,
the sweep runner — runs sharded without code changes; ``repro.serve`` wires
the mesh explicitly through its compiled-plan cache (see
``serve.registry.ModelHandle``).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .. import obs
from ..core import engine
from ..core.neuron import _on_registry_change
from .mesh import DATA_AXIS, batch_partition_spec, data_mesh, mesh_size

# (config, backend, mesh) -> jitted sharded executable. A plain dict (not
# lru_cache): Mesh objects are hashable, and data_mesh() returns cached
# instances, so keys stay stable across calls.
_RUNNERS: dict = {}

# a re-registered neuron mode must invalidate sharded executables too (the
# same rule engine._runner follows), or a cached shard_map would keep
# executing the old fire function and break sharded == single-device
_on_registry_change.append(_RUNNERS.clear)


def batch_runner_sharded(cfg, backend_name: str, mesh: Mesh):
    """The jit-compiled data-parallel executable for (config, backend, mesh).

    The sharded analogue of ``engine.batch_runner``: one ``shard_map`` of
    the engine's batched program — the backend's native batched plan when it
    declares ``supports_batch``, the vmapped per-sample program otherwise —
    with params/thresholds replicated and the batch axis sharded over
    ``data``. The caller must pass a batch divisible by the mesh size
    (:func:`infer_batch_sharded` handles the pad-to-divisible fallback).
    """
    key = (cfg, backend_name, mesh)
    cached = _RUNNERS.get(key)
    if cached is not None:
        return cached

    if getattr(engine.get_backend(backend_name), "host_dispatch", False):
        raise ValueError(
            f"backend {backend_name!r} dispatches on host-side occupancy "
            "totals and cannot be traced into one shard_map program; "
            "infer_batch_sharded falls back to the local runner for it")

    backend = engine.get_backend(backend_name)
    plan = engine.compile_plan(cfg.spec, cfg.input_hw, cfg.input_c,
                               cfg.compressed)
    if getattr(backend, "supports_batch", False):
        def run(params, thresholds, images):
            return engine._execute_batch(plan, backend, cfg, params,
                                         tuple(thresholds), images)
    else:
        def run_one(params, thresholds, image):
            return engine._execute(plan, backend, cfg, params,
                                   tuple(thresholds), image)

        run = jax.vmap(run_one, in_axes=(None, None, 0))

    # check_vma=False: outputs are all batch-sharded (nothing claims
    # replication), and several engine primitives lack replication rules
    sharded = jax.shard_map(run, mesh=mesh,
                            in_specs=(P(), P(), P(DATA_AXIS)),
                            out_specs=P(DATA_AXIS), check_vma=False)
    fn = jax.jit(sharded)
    _RUNNERS[key] = fn
    return fn


def infer_batch_sharded(params, thresholds, cfg, images, *,
                        backend: str = "dense", mesh: Mesh | None = None):
    """Run a (B, H, W, C) batch sharded over ``mesh``; bit-exact vs 1 device.

    ``mesh=None`` takes :func:`data_mesh` over every visible device; a
    single-device mesh degenerates to the engine's own cached runner. When
    B does not divide the mesh size, the batch is zero-padded to the next
    multiple and the valid prefix sliced back out — padded rows are
    bit-inert per the engine mask contract, so the fallback costs padding
    compute but never exactness.
    """
    mesh = data_mesh() if mesh is None else mesh
    if getattr(engine.get_backend(backend), "host_dispatch", False):
        # Occupancy-gated backends (queue_sparse) pick their event bucket
        # from a host-side scalar between layers — untraceable under
        # shard_map. The local runner is bit-exact (same mask contract), so
        # inside use_mesh() these backends transparently run unsharded.
        return engine._runner(cfg, backend, True)(params, tuple(thresholds),
                                                  images)
    n = mesh_size(mesh)
    if n <= 1:
        return engine._runner(cfg, backend, True)(params, tuple(thresholds),
                                                  images)

    images = jnp.asarray(images)
    B = images.shape[0]
    spec = batch_partition_spec(mesh, images.shape)
    runner = batch_runner_sharded(cfg, backend, mesh)
    # host-side span around the sharded launch (host callbacks inside the
    # shard_map program are banned by the audit's host-sync rule): one span
    # per call with the shard geometry, not one per device
    if spec[0] is None:
        # the batch does not divide the mesh: pad to divisible
        pad = (-B) % n
        with obs.span("parallel.shard_execute", backend=backend, B=B,
                      devices=n, shard_B=(B + pad) // n, padded=pad):
            padded = jnp.concatenate(
                [images, jnp.zeros((pad,) + images.shape[1:], images.dtype)])
            logits, stats = runner(params, tuple(thresholds), padded)
            return engine.slice_valid(logits, stats, B)
    with obs.span("parallel.shard_execute", backend=backend, B=B,
                  devices=n, shard_B=B // n, padded=0):
        return runner(params, tuple(thresholds), images)


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    """Route every ``engine.infer_batch`` in the block through ``mesh``.

    Installs :func:`infer_batch_sharded` as the engine's batch dispatch
    override (restored on exit, exception-safe). Because sharded results
    are bit-exact, callers above the engine — ``study.collect``, its
    content-hash cache, the sweep runner — need no awareness of the mesh:
    cached artifacts are interchangeable between sharded and single-device
    runs. ``mesh=None`` is a no-op block (the single-device path), so
    callers can thread an optional mesh without branching.
    """
    if mesh is None:
        yield None
        return

    def dispatch(params, thresholds, cfg, images, *, backend):
        return infer_batch_sharded(params, thresholds, cfg, images,
                                   backend=backend, mesh=mesh)

    prev = engine._batch_dispatch
    engine._batch_dispatch = dispatch
    try:
        yield mesh
    finally:
        engine._batch_dispatch = prev
