"""Registry persistence: checkpointed models + serialized AOT plans.

The serving layer's cold-start cost is (import) + (params) + (trace/lower)
+ (XLA compile) per bucket. This module removes everything after import for
a restarted replica:

- **params/thresholds** round-trip through ``repro.checkpoint.checkpoint``
  — the fault-tolerant sharded writer the seed shipped for training loops,
  put to work here for the SNN serving path: atomic commit markers,
  per-leaf content hashes, loud verification on load.
- **plans** serialize via ``jax.export``: each warmed bucket's batched
  program is exported to a StableHLO blob next to the params. A restored
  plan is ``jax.jit`` of the deserialized call — its XLA compile is then
  absorbed by the persistent compilation cache
  (``repro.core.compile_cache``), so a warm replica never re-traces and
  never re-compiles. Where export or re-import is unsupported (mesh-sharded
  plans, jax version drift), the entry degrades to *persistent-cache-warmed
  re-lowering*: the handle just compiles lazily as before, hitting the
  shared cache.
- **keys**: every model entry carries ``study.cache.content_key`` over its
  actual params, thresholds, config, and backend — the same content-hash
  function the study cache uses — so a checkpoint can never silently serve
  stale or edited artifacts: :func:`load_registry` recomputes the key and
  raises :class:`StaleCheckpointError` on mismatch. Byte-identical params
  in, byte-identical logits and stats out (pinned by
  ``tests/test_coldstart.py``).

Checkpoint layout::

    <root>/registry.json                    # manifest (schema, keys, cfg)
    <root>/models/<dir>/step_000000000/     # repro.checkpoint params
    <root>/plans/<dir>/bucket_<B>.jaxexp    # jax.export StableHLO blobs

Errors are loud and typed: :class:`CheckpointError` (missing/unusable),
:class:`StaleCheckpointError` (content-key mismatch),
:class:`CorruptCheckpointError` (damaged shard or plan blob).
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import tempfile
import warnings

import jax
from jax import export as jax_export

from .. import obs
from ..checkpoint import checkpoint as ckpt
from ..core import engine
from ..study.cache import content_key
from .api import ServeError
from .registry import ModelRegistry

SCHEMA = "registry-ckpt-v1"
MANIFEST = "registry.json"


class CheckpointError(ServeError):
    """Registry checkpoint missing or structurally unusable."""


class StaleCheckpointError(CheckpointError):
    """Restored content no longer matches the manifest's content key.

    Raised when the recomputed ``content_key`` over (params, thresholds,
    config, backend) differs from the key recorded at save time — an edited
    manifest, swapped shard, or spec drift. Never served silently.
    """


class CorruptCheckpointError(CheckpointError):
    """A shard or plan blob failed integrity verification."""


_export_types_registered = False


def _register_export_types() -> None:
    """Teach ``jax.export`` the engine's output pytree (idempotent).

    The batched plan returns ``(logits, SNNStats)``; NamedTuples are not
    serializable until given a stable name — without this both serialize
    and deserialize refuse the plan.
    """
    global _export_types_registered
    if _export_types_registered:
        return
    try:
        jax_export.register_namedtuple_serialization(
            engine.SNNStats, serialized_name="repro.core.engine.SNNStats")
    except ValueError:
        pass  # an earlier caller in this process already registered it
    _export_types_registered = True


def registry_key(params, thresholds, cfg, backend: str) -> str:
    """Content key of one servable model, study-cache-consistent.

    Same ``content_key`` function (and therefore the same collision
    behaviour and key format) as the study pipeline's artifact cache:
    hashing the *actual* arrays plus the exact config/backend values is
    what lets a restore assert bit-exactness instead of trusting names.
    """
    return content_key("serve-registry-v1", list(params), list(thresholds),
                       tuple(cfg), backend)


def _safe_dir(name: str, taken: set) -> str:
    base = re.sub(r"[^-._a-zA-Z0-9]", "_", name) or "model"
    out, i = base, 1
    while out in taken:
        out, i = f"{base}.{i}", i + 1
    taken.add(out)
    return out


def _blob_hash(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


# ---------------------------------------------------------------------------
# Save
# ---------------------------------------------------------------------------

def _export_plan(handle, bucket: int) -> bytes:
    """Serialize the bucket's batched program to a ``jax.export`` blob.

    Export re-traces from the jit function (it needs StableHLO, which the
    compiled executable no longer carries) — cheap relative to XLA compile,
    and save-time only.
    """
    _register_export_types()
    runner = engine.batch_runner(handle.cfg, handle.backend)
    exp = jax_export.export(runner)(
        handle.params, handle.thresholds, handle._image_struct(bucket))
    return exp.serialize()


def save_registry(registry: ModelRegistry, root: str, *,
                  buckets=None, plans: bool = True) -> str:
    """Checkpoint every registered model (params + plans) under ``root``.

    ``buckets`` selects which plan shapes to serialize (default: each
    handle's already-warmed ``cached_buckets()``); ``plans=False`` saves
    params only. Plan export failures degrade that entry to the
    re-lowering fallback (recorded in the manifest, counted on
    ``persist.plan_export_skipped``) — params always save or the call
    raises. Returns ``root``.
    """
    os.makedirs(root, exist_ok=True)
    taken: set = set()
    models = {}
    with obs.span("persist.save", root=root, models=len(registry)):
        for name in registry.names():
            handle = registry.get(name)
            d = _safe_dir(name, taken)
            tree = {"params": [{k: v for k, v in layer.items()}
                               for layer in handle.params],
                    "thresholds": list(handle.thresholds)}
            ckpt.save(os.path.join(root, "models", d), 0, tree)

            plan_entries = {}
            if plans and handle.mesh is None:
                want = buckets if buckets is not None \
                    else handle.cached_buckets()
                for b in want:
                    try:
                        blob = _export_plan(handle, int(b))
                    except Exception as e:  # noqa: BLE001 — degrade, don't die
                        obs.counter("persist.plan_export_skipped")
                        warnings.warn(
                            f"model {name!r} bucket {b}: plan export failed "
                            f"({e!r}); a restore will re-lower it",
                            RuntimeWarning, stacklevel=2)
                        plan_entries[str(int(b))] = {
                            "format": "none", "reason": repr(e)[:200]}
                        continue
                    rel = os.path.join("plans", d, f"bucket_{int(b)}.jaxexp")
                    path = os.path.join(root, rel)
                    os.makedirs(os.path.dirname(path), exist_ok=True)
                    with open(path, "wb") as f:
                        f.write(blob)
                    obs.counter("persist.plan_export")
                    plan_entries[str(int(b))] = {
                        "format": "jax_export", "file": rel,
                        "sha256": _blob_hash(blob)}

            models[name] = {
                "dir": d,
                "key": registry_key(handle.params, handle.thresholds,
                                    handle.cfg, handle.backend),
                "backend": handle.backend,
                "vmem_resident": handle.vmem_resident,
                "source_key": handle.source_key,
                "cfg": handle.cfg._asdict(),
                "params_tree": [sorted(layer) for layer in handle.params],
                "n_thresholds": len(handle.thresholds),
                "plans": plan_entries,
            }

        manifest = {"schema": SCHEMA, "jax_version": jax.__version__,
                    "models": models}
        fd, tmp = tempfile.mkstemp(dir=root, suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(manifest, f, indent=1)
        os.replace(tmp, os.path.join(root, MANIFEST))
    return root


# ---------------------------------------------------------------------------
# Load
# ---------------------------------------------------------------------------

def read_manifest(root: str) -> dict:
    path = os.path.join(root, MANIFEST)
    if not os.path.exists(path):
        raise CheckpointError(
            f"no registry checkpoint under {root!r} (missing {MANIFEST})")
    try:
        with open(path) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise CorruptCheckpointError(
            f"unreadable registry manifest {path!r}: {e}") from e
    if manifest.get("schema") != SCHEMA:
        raise CheckpointError(
            f"unsupported registry checkpoint schema "
            f"{manifest.get('schema')!r} (expected {SCHEMA!r})")
    return manifest


def _restore_plan(handle, root: str, bucket: int, entry: dict) -> bool:
    """Deserialize + adopt one plan blob; False = use lazy fallback."""
    if entry.get("format") != "jax_export":
        obs.counter("persist.plan_restore_skipped")
        return False
    path = os.path.join(root, entry["file"])
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError as e:
        raise CorruptCheckpointError(
            f"plan blob {path!r} unreadable: {e}") from e
    if _blob_hash(blob) != entry["sha256"]:
        raise CorruptCheckpointError(
            f"plan blob {path!r} failed its content hash — checkpoint is "
            "damaged; delete it and re-save")
    _register_export_types()
    try:
        exp = jax_export.deserialize(blob)
    except Exception as e:  # noqa: BLE001 — version drift: fall back, don't die
        # intact blob that this jax can't re-import (serialization version
        # drift): the handle re-lowers lazily against the warm persistent
        # cache instead — slower first call, identical numbers
        obs.counter("persist.plan_restore_fallback")
        warnings.warn(f"plan blob {path!r} did not deserialize ({e!r}); "
                      "the plan will be re-lowered", RuntimeWarning,
                      stacklevel=2)
        return False
    handle.adopt_plan(bucket, jax.jit(exp.call))
    obs.counter("persist.plan_restore")
    return True


def load_registry(root: str, *, names=None, plans: bool = True,
                  capacity: int | None = None,
                  plan_cache_size: int | None = None,
                  mesh=None) -> ModelRegistry:
    """Rebuild a :class:`ModelRegistry` from a :func:`save_registry` dir.

    Every model's content key is recomputed from the restored bytes and
    checked against the manifest (:class:`StaleCheckpointError` on
    mismatch); damaged shards and plan blobs raise
    :class:`CorruptCheckpointError` (via the checkpoint layer's per-leaf
    hashes). With ``plans=True`` (and no ``mesh``) the serialized plans are
    adopted into each handle, so a following ``handle.warmup(buckets)``
    is execute-only — ``compile_count`` stays 0 and first-response cost is
    one cache-hit XLA compile per bucket instead of a full trace+compile.
    """
    from ..core.snn_model import SNNConfig

    manifest = read_manifest(root)
    entries = manifest["models"]
    if names is not None:
        missing = sorted(set(names) - set(entries))
        if missing:
            raise CheckpointError(
                f"models {missing} not in checkpoint {root!r} "
                f"(has {sorted(entries)})")
        entries = {n: entries[n] for n in names}

    registry = ModelRegistry(
        capacity=capacity if capacity is not None else max(4, len(entries)),
        plan_cache_size=plan_cache_size or 8, mesh=mesh)

    for name, entry in entries.items():
        with obs.span("coldstart.restore_params", model=name):
            template = {
                "params": [{k: 0 for k in layer}
                           for layer in entry["params_tree"]],
                "thresholds": [0] * entry["n_thresholds"],
            }
            try:
                tree, _ = ckpt.restore(
                    os.path.join(root, "models", entry["dir"]), template)
            except (IOError, FileNotFoundError) as e:
                raise CorruptCheckpointError(
                    f"model {name!r}: no intact params checkpoint under "
                    f"{root!r} ({e})") from e

        cfg = SNNConfig(**entry["cfg"])
        got = registry_key(tree["params"], tree["thresholds"], cfg,
                           entry["backend"])
        if got != entry["key"]:
            raise StaleCheckpointError(
                f"model {name!r}: restored content hashes to {got} but the "
                f"manifest pins {entry['key']} — the checkpoint no longer "
                "matches what was saved (edited manifest, swapped shard, "
                "or config drift); refusing to serve it")

        handle = registry.register(
            name, tree["params"], tree["thresholds"], cfg,
            backend=entry["backend"], vmem_resident=entry["vmem_resident"])
        handle.source_key = entry.get("source_key")

        if plans and mesh is None:
            with obs.span("coldstart.restore_plans", model=name,
                          n=len(entry["plans"])):
                for b, pentry in sorted(entry["plans"].items(),
                                        key=lambda kv: int(kv[0])):
                    _restore_plan(handle, root, int(b), pentry)
    return registry
