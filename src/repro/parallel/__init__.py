"""Data-parallel SNN execution on a device mesh.

    from repro.parallel import data_mesh, infer_batch_sharded, use_mesh

    mesh = data_mesh()                       # 1-D "data" mesh, all devices
    logits, stats = infer_batch_sharded(params, th, cfg, images,
                                        backend="queue_pallas", mesh=mesh)
    with use_mesh(mesh):                     # or: route infer_batch itself
        report = study.run(spec)

Sharded execution is **bit-exact** against single-device ``infer_batch``
(logits and stats — the engine mask contract makes batch rows sample-
independent), so meshes are purely a throughput knob: caches, studies and
serving responses are interchangeable with the single-device path. See
``docs/PARALLEL.md`` for mesh setup (including the CPU
``--xla_force_host_platform_device_count`` trick) and the sweep runner
built on top (``python -m repro.study.sweep``).
"""
from .executor import (batch_runner_sharded, infer_batch_sharded,  # noqa: F401
                       use_mesh)
from .mesh import (DATA_AXIS, batch_partition_spec, data_mesh,  # noqa: F401
                   device_count, mesh_size)

__all__ = [
    "DATA_AXIS", "batch_partition_spec", "data_mesh", "device_count",
    "mesh_size",
    "batch_runner_sharded", "infer_batch_sharded", "use_mesh",
]
