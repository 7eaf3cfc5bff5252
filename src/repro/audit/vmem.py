"""Pallas VMEM footprint estimator: BlockSpec shapes x dtype x buffering.

Each Pallas kernel module declares its per-grid-cell resident buffers via a
``vmem_blocks(**geometry)`` helper next to its ``CONTRACT`` — the same
shapes its BlockSpecs/scratch_shapes construct, as data. The estimator
evaluates those at every geometry the served nets launch (each conv
stage of each ``PAPER_SPECS`` spec and of ``VGG16``, at the study's
default queue depth) and flags any kernel whose resident bytes —
pipelined blocks counted twice for double-buffering — exceed the per-core
VMEM budget.

This is a *static* gate: it catches a BlockSpec edit that would OOM on TPU
without needing a TPU (Mosaic would only report it at compile time, and CI
has no TPU to compile on).
"""
from __future__ import annotations

import importlib
import math
import os

from .contracts import DOUBLE_BUFFER_FACTOR, VMEM_BUDGET_BYTES
from .findings import Finding

#: The kernel modules that declare ``CONTRACT`` + ``vmem_blocks``.
KERNEL_MODULES = (
    "repro.kernels.spike_pipeline",
    "repro.kernels.spike_sparse",
    "repro.kernels.event_accum",
)

#: Queue depth the studies run at (SNNConfig default) — the worst case the
#: estimator must clear, since depth sizes the segment scratch.
DEFAULT_DEPTH = 256


def estimate_bytes(blocks) -> int:
    """Total resident bytes for ``vmem_blocks`` output: a list of
    ``(name, shape, bytes_per_elem, double_buffered)`` tuples."""
    total = 0
    for _, shape, elem_bytes, double_buffered in blocks:
        n = math.prod(shape) * elem_bytes
        total += n * (DOUBLE_BUFFER_FACTOR if double_buffered else 1)
    return total


def paper_geometries(depth: int = DEFAULT_DEPTH):
    """Every (dataset, ConvPlan-derived geometry) the served nets launch."""
    from .. import configs
    from ..core import engine

    for dataset, d in {**configs.PAPER_SPECS, "vgg16": configs.VGG16}.items():
        plan = engine.compile_plan(d["spec"], d["hw"], d["c"])
        for cp in plan.convs:
            yield dataset, dict(K=cp.kernel, n_win=cp.fmt.n_win,
                                depth=depth, H=cp.in_hw, W=cp.in_hw,
                                C_in=cp.in_c, C_out=cp.out_c)


def module_anchor(module, root: str) -> tuple[str, int]:
    """(repo-relative file, CONTRACT line) of a kernel module."""
    path = module.__file__
    rel = (os.path.relpath(path, root)
           if path.startswith(root.rstrip(os.sep) + os.sep) else path)
    try:
        with open(path) as fh:
            for i, ln in enumerate(fh, 1):
                if ln.startswith("CONTRACT"):
                    return rel, i
    except OSError:  # pragma: no cover
        pass
    return rel, 1


def check_vmem(root: str, depth: int = DEFAULT_DEPTH,
               budget: int = VMEM_BUDGET_BYTES) -> list[Finding]:
    """``vmem-budget``: every paper geometry of every kernel fits VMEM."""
    out = []
    geoms = list(paper_geometries(depth))
    for mod_name in KERNEL_MODULES:
        module = importlib.import_module(mod_name)
        rel, line = module_anchor(module, root)
        worst = (0, None)
        for dataset, geom in geoms:
            total = estimate_bytes(module.vmem_blocks(**geom))
            if total > worst[0]:
                worst = (total, (dataset, geom))
            if total > budget:
                out.append(Finding(
                    "vmem-budget", "error", rel, line,
                    f"{mod_name}: {total / 2**20:.1f} MiB resident per grid "
                    f"cell at {dataset} geometry {geom} exceeds the "
                    f"{budget / 2**20:.0f} MiB VMEM budget"))
    return out


def kernel_footprint(mod_name: str, **geometry) -> int:
    """Resident bytes of one kernel at an explicit geometry (test hook)."""
    module = importlib.import_module(mod_name)
    return estimate_bytes(module.vmem_blocks(**geometry))
