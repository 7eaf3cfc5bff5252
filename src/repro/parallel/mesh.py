"""Device-mesh construction for data-parallel SNN execution.

The paper's accelerators scale by replicating compute slices and striping
work across them (DeepFire2's layer-parallel SLR partitioning, the survey's
PE arrays); the jax analogue for the *batch* dimension is a 1-D
``jax.sharding.Mesh`` whose single axis the batch is sharded over. This
module builds that mesh:

- On real multi-device hardware (TPU/GPU), ``data_mesh()`` takes the
  devices jax already sees.
- On CPU boxes — including CI — jax exposes one device unless
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` is set *before*
  jax initializes. With it, the same code paths run against N virtual host
  devices, which is how the sharded tests run everywhere (see
  ``docs/PARALLEL.md``).

:func:`batch_partition_spec` is the one sharding rule: the batch dimension
goes over the ``"data"`` axis when it divides it, and stays whole otherwise.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec

DATA_AXIS = "data"


def device_count() -> int:
    """Visible device count (virtual host devices included)."""
    return len(jax.devices())


@functools.lru_cache(maxsize=None)
def _cached_mesh(n: int) -> Mesh:
    return Mesh(np.asarray(jax.devices()[:n]), (DATA_AXIS,))


def data_mesh(n_devices: int | None = None) -> Mesh:
    """A 1-D ``("data",)`` mesh over the first ``n_devices`` devices.

    ``None`` takes every visible device. Meshes are cached per device
    count, so repeated calls return the *same* object — which is what keeps
    the sharded-executable caches (keyed on the mesh) from recompiling.
    """
    avail = device_count()
    n = avail if n_devices is None else n_devices
    if not isinstance(n, int) or n < 1:
        raise ValueError(f"n_devices must be a positive int, got {n!r}")
    if n > avail:
        raise ValueError(
            f"n_devices={n} but only {avail} device(s) visible; on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before the process starts")
    return _cached_mesh(n)


def mesh_size(mesh: Mesh | None) -> int:
    """Total devices in ``mesh`` (1 for ``None`` — the no-mesh fallback)."""
    return 1 if mesh is None else int(np.prod(list(mesh.shape.values())))


def batch_partition_spec(mesh: Mesh, shape) -> PartitionSpec:
    """Spec for a batch-leading array: dimension 0 over ``DATA_AXIS`` when
    the batch divides that axis, every dimension unsharded otherwise (the
    caller pads to divisible; ``parallel.infer_batch_sharded`` does)."""
    batch = DATA_AXIS if shape[0] % mesh.shape[DATA_AXIS] == 0 else None
    return PartitionSpec(batch, *(None,) * (len(shape) - 1))
