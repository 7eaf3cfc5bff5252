"""repro.serve — the SNN inference serving runtime.

Turns the batch-native execution engine (``core/engine.py``) into a
load-servable inference service:

- :class:`~repro.serve.batching.BucketPolicy` — dynamic batching into
  padded power-of-two buckets, so the per-(config, backend, B) compiled
  plans are reused instead of recompiling per request;
- :class:`~repro.serve.registry.ModelRegistry` — named models
  (dataset spec × backend) with an LRU-bounded compiled-plan cache and
  warmup;
- :class:`~repro.serve.runtime.ServeRuntime` — the admission queue +
  batcher + per-request energy metering (every response carries logits,
  its own :class:`~repro.study.artifacts.StatsRecord` row, and the
  energy/latency estimate priced via ``repro.study.price_record``);
- ``repro.serve.persist`` — registry checkpoints: params through
  ``repro.checkpoint``, AOT plans through ``jax.export``, content-hash
  keys shared with the study cache (:func:`save_registry` /
  :func:`load_registry`);
- ``repro.serve.fleet`` — N replica processes serving one checkpointed
  registry behind a shared compilation cache
  (``python -m repro.serve.fleet --replicas N --cache-dir D``);
- ``repro.serve.bench`` — closed/open-loop load generation
  (``python -m repro.serve.bench``).

See ``docs/SERVING.md`` for architecture, policies, and the cold-start
path.
"""
import importlib as _importlib

# name -> submodule, resolved on first access: importing ``repro.serve``
# must not import jax, so the fleet parent (``python -m repro.serve.fleet``)
# can stay off the accelerator while its replica processes use it
_EXPORTS = {
    "InferRequest": "api", "InferResponse": "api", "ServeError": "api",
    "BucketPolicy": "batching", "DEFAULT_BUCKETS": "batching",
    "ModelHandle": "registry", "ModelRegistry": "registry",
    "ServeRuntime": "runtime",
    "CheckpointError": "persist", "StaleCheckpointError": "persist",
    "CorruptCheckpointError": "persist", "save_registry": "persist",
    "load_registry": "persist",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    try:
        module = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    return getattr(_importlib.import_module(f".{module}", __name__), name)
