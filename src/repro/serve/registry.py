"""Multi-model registry: named serving handles + compiled-plan caching.

A :class:`ModelHandle` owns one servable SNN — converted params, per-layer
thresholds, the engine :class:`~repro.core.snn_model.SNNConfig`, a backend
name, and the pricing options its responses are metered under. Per padded
bucket size it AOT-lowers the engine's batched executable
(``engine.batch_runner(...).lower(...).compile()``) into an LRU-bounded
compiled-plan cache, so serving never pays a trace after warmup and an
abandoned bucket size eventually frees its executable.

The :class:`ModelRegistry` LRU-bounds the handles themselves (a box serving
MNIST/SVHN/CIFAR-10 × backend variants holds ``capacity`` models hot);
``register_study`` builds a handle straight from the study pipeline's
train → convert stages so a registered model is exactly the SNN a
:class:`~repro.study.StudySpec` studies.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from .. import obs
from ..audit.contracts import BackendContract
from ..core import engine
from .api import ServeError
from .batching import DEFAULT_BUCKETS

# Declared trace intent of the serving layer, verified by
# ``python -m repro.audit`` (see docs/CONTRACTS.md): the served plans are
# the engine's batched programs (zero cross-batch reductions — the mask
# contract is what makes padded buckets safe), and the one deliberate host
# sync is the per-bucket block-until-ready in ``run_bucket`` (latency
# metering needs the device done before the response timestamp).
CONTRACT = BackendContract(name="serve",
                           allowed_host_syncs=("serve-block-until-ready",))


class ModelHandle:
    """One servable model: artifacts + per-bucket compiled plans."""

    def __init__(self, name: str, params, thresholds, cfg, *,
                 backend: str = "queue_pallas", vmem_resident: bool = True,
                 plan_cache_size: int = 8, mesh=None):
        b = engine.get_backend(backend)      # fail fast on unknown names
        if getattr(b, "host_dispatch", False):
            raise ValueError(
                f"backend {backend!r} dispatches on host-side occupancy "
                "totals, so its plan cannot be AOT-lowered per bucket; "
                "serve with 'queue_pallas' (same semantics, static plan)")
        if plan_cache_size < 1:
            raise ValueError(                # 0 would recompile every batch
                f"plan_cache_size must be >= 1, got {plan_cache_size}")
        self.name = name
        self.params = [{k: jnp.asarray(v) for k, v in layer.items()}
                       for layer in params]
        self.thresholds = tuple(jnp.asarray(t) for t in thresholds)
        self.cfg = cfg
        self.backend = backend
        self.vmem_resident = vmem_resident
        self.plan_cache_size = plan_cache_size
        self.mesh = mesh                     # data mesh for divisible buckets
        # bucket B -> compiled executable, insertion-ordered for LRU
        self._plans: collections.OrderedDict = collections.OrderedDict()
        # AOT compilations performed (cache misses in plan_for): the
        # observable the warmup recompilation guard asserts stays flat —
        # AOT plans bypass the jit cache, so the jit-cache counter the
        # audit harness uses for the engine cannot see them
        self.compile_count = 0
        # study provenance: the convert-stage content key when this handle
        # came through register_study (None for directly registered params);
        # persisted into registry checkpoints (serve/persist.py) so a
        # restored model keeps its link back to the study cache entry
        self.source_key: str | None = None

    def set_mesh(self, mesh) -> None:
        """(Re)point this handle at a device mesh; drops compiled plans.

        The cached executables are shape- *and* placement-specific, so a
        mesh change invalidates them; the next ``plan_for`` recompiles
        against the new placement. Results stay bit-exact either way (the
        engine mask contract makes batch sharding inert), so flipping a
        live handle between meshes never changes served numbers.
        """
        if mesh is not self.mesh:
            self.mesh = mesh
            self._plans.clear()

    def _bucket_sharded(self, bucket: int) -> bool:
        """Sharded plan iff a real mesh is set and the bucket divides it.

        Small buckets that don't divide (B=1 on a 4-way mesh) stay on the
        single-device plan — padding them up would buy no throughput; big
        buckets (B=64) are where data parallelism pays.
        """
        if self.mesh is None:
            return False
        from .. import parallel

        n = parallel.mesh_size(self.mesh)
        return n > 1 and bucket % n == 0

    def _image_struct(self, bucket: int):
        cfg = self.cfg
        return jax.ShapeDtypeStruct(
            (bucket, cfg.input_hw, cfg.input_hw, cfg.input_c), jnp.float32)

    def plan_for(self, bucket: int):
        """The compiled batched executable for this bucket size (LRU-cached).

        AOT lowering pins the full program — plan walk, backend, batch axis
        in the kernel grid — at this exact (config, backend, B) shape; a
        cache hit is a plain dict lookup. Eviction drops the least recently
        used executable (jax frees it with the reference).

        With a mesh set (:meth:`set_mesh`), buckets divisible by the mesh
        size compile the *data-parallel* program instead
        (``parallel.batch_runner_sharded``) — batch rows striped across
        devices, results bit-exact vs the local plan — so the big buckets
        (B=64) run sharded while B=1 stays on one device.
        """
        if bucket in self._plans:
            self._plans.move_to_end(bucket)
            obs.counter("serve.plan_hit")
            return self._plans[bucket]
        obs.counter("serve.plan_compile")
        with obs.span("serve.aot_compile", model=self.name,
                      backend=self.backend, bucket=bucket,
                      sharded=self._bucket_sharded(bucket),
                      **engine.plan_size(self.cfg, bucket)):
            if self._bucket_sharded(bucket):
                from .. import parallel

                runner = parallel.batch_runner_sharded(self.cfg, self.backend,
                                                       self.mesh)
            else:
                runner = engine.batch_runner(self.cfg, self.backend)
            plan = runner.lower(self.params, self.thresholds,
                                self._image_struct(bucket)).compile()
        self.compile_count += 1
        self._plans[bucket] = plan
        while len(self._plans) > self.plan_cache_size:
            evicted, _ = self._plans.popitem(last=False)
            obs.event("serve.plan_evict", model=self.name, bucket=evicted)
            obs.counter("serve.plan_evictions")
        return plan

    def cached_buckets(self) -> tuple:
        return tuple(self._plans)

    def adopt_plan(self, bucket: int, plan) -> None:
        """Install a restored executable for ``bucket`` (checkpoint path).

        ``serve/persist.py`` deserializes ``jax.export`` plan blobs and
        hands them here: the plan enters the same LRU the AOT path fills,
        but does **not** bump ``compile_count`` — a restore is a cache hit
        by construction, so the warmup recompilation guard keeps working
        unchanged on a registry restored from disk (warmup-from-disk must
        be all hits). ``plan`` takes ``(params, thresholds, images)``
        exactly like a ``plan_for`` executable.
        """
        self._plans.pop(bucket, None)
        self._plans[bucket] = plan
        obs.counter("serve.plan_adopt")
        while len(self._plans) > self.plan_cache_size:
            evicted, _ = self._plans.popitem(last=False)
            obs.event("serve.plan_evict", model=self.name, bucket=evicted)
            obs.counter("serve.plan_evictions")

    def run_bucket(self, images, n_valid: int):
        """Execute one padded bucket; return the valid prefix (see engine
        mask contract). ``images`` is the already-padded (B, H, W, C) array.

        Its phases are ``obs`` spans: ``serve.upload`` (the batch to the
        device), ``serve.dispatch`` (the plan's lookup and call, until it
        returns), ``serve.wait`` (until the device is done) and
        ``serve.slice`` (the valid prefix)."""
        with obs.span("serve.upload", bytes=images.nbytes):
            images = jnp.asarray(images)
        with obs.span("serve.dispatch"):
            logits, stats = self.plan_for(images.shape[0])(
                self.params, self.thresholds, images)
        with obs.span("serve.wait"):
            # audit: allow[host-sync] serve latency metering: the response
            # timestamp must not be taken before the device is done
            jax.block_until_ready(logits)
        with obs.span("serve.slice"):
            return engine.slice_valid(logits, stats, n_valid)

    def warmup(self, buckets=DEFAULT_BUCKETS) -> None:
        """Compile (and once-execute) each bucket so serving never traces.

        The execute matters: it forces any lazily initialized backend state
        and faults the executable's working set before the first request.

        **Recompilation guard**: after the first pass compiled every bucket,
        a second pass over the same bucket sizes must be all cache hits —
        ``compile_count`` flat. Growth means some Python value (mesh
        placement, params identity, a closed-over scalar) is specializing
        per call, i.e. production would re-trace on live traffic; that is
        the unbounded-specialization hazard ``repro.audit``'s harness
        checks statically at the engine layer, caught here at runtime.
        """
        for b in buckets:
            zeros = jnp.zeros((b, self.cfg.input_hw, self.cfg.input_hw,
                               self.cfg.input_c), jnp.float32)
            self.run_bucket(zeros, b)
        if len(set(buckets)) > self.plan_cache_size:
            return  # LRU eviction makes second-pass recompiles legitimate
        compiled = self.compile_count
        for b in buckets:
            zeros = jnp.zeros((b, self.cfg.input_hw, self.cfg.input_hw,
                               self.cfg.input_c), jnp.float32)
            self.run_bucket(zeros, b)
        if self.compile_count != compiled:
            raise ServeError(
                f"model {self.name!r}: warmup second pass recompiled "
                f"({compiled} -> {self.compile_count} compilations for "
                f"buckets {tuple(buckets)}) — the compiled-plan cache is "
                "not keying on bucket size alone")


class ModelRegistry:
    """Name -> :class:`ModelHandle`, LRU-bounded to ``capacity`` models."""

    def __init__(self, capacity: int = 4, plan_cache_size: int = 8,
                 mesh=None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if plan_cache_size < 1:
            raise ValueError(
                f"plan_cache_size must be >= 1, got {plan_cache_size}")
        self.capacity = capacity
        self.plan_cache_size = plan_cache_size
        self.mesh = mesh
        self._models: collections.OrderedDict = collections.OrderedDict()

    def set_mesh(self, mesh) -> None:
        """Point the registry — and every registered handle — at ``mesh``.

        Future registrations inherit it; existing handles drop their
        compiled plans and recompile lazily against the new placement
        (see :meth:`ModelHandle.set_mesh`).
        """
        self.mesh = mesh
        for handle in self._models.values():
            handle.set_mesh(mesh)

    def register(self, name: str, params, thresholds, cfg, *,
                 backend: str = "queue_pallas",
                 vmem_resident: bool = True) -> ModelHandle:
        """Register converted artifacts under ``name`` (replaces any old)."""
        handle = ModelHandle(name, params, thresholds, cfg, backend=backend,
                             vmem_resident=vmem_resident,
                             plan_cache_size=self.plan_cache_size,
                             mesh=self.mesh)
        self._models.pop(name, None)
        self._models[name] = handle
        while len(self._models) > self.capacity:
            evicted, _ = self._models.popitem(last=False)
            obs.event("serve.model_evict", model=evicted)
            obs.counter("serve.model_evictions")
        return handle

    def register_study(self, name: str, spec, *, cache=None,
                       vmem_resident: bool | None = None) -> ModelHandle:
        """Train + convert ``spec`` through the study stages, then register.

        The served model is byte-identical to what ``study.collect`` would
        execute for the same spec (same converted params, thresholds,
        config, and backend), so serving-side energy metering and a study
        over the same inputs price the same stats.
        """
        from ..study import stages

        trained = stages.train(spec, cache=cache)
        converted = stages.convert(spec, trained, cache=cache)
        handle = self.register(
            name, converted.snn_params, converted.thresholds,
            spec.snn_config(), backend=spec.backend,
            vmem_resident=(spec.vmem_resident if vmem_resident is None
                           else vmem_resident))
        handle.source_key = converted.key
        return handle

    def get(self, name: str) -> ModelHandle:
        try:
            handle = self._models[name]
        except KeyError:
            raise ServeError(
                f"unknown model {name!r}; registered models: "
                f"{sorted(self._models)}") from None
        self._models.move_to_end(name)
        return handle

    def names(self) -> tuple:
        return tuple(self._models)

    def __contains__(self, name: str) -> bool:
        return name in self._models

    def __len__(self) -> int:
        return len(self._models)
