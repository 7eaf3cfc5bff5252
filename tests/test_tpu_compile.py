"""Compile-only checks of the main-path Pallas kernels for a TPU v5e chip.

Interpret mode runs a kernel body on the CPU but never asks Mosaic to
lower it, so tiling, SMEM/VMEM and scalar-extraction refusals only show
when the kernel is compiled for the chip. These tests compile each kernel
for one described (not attached) v5e device at every event-driven conv
geometry of ``configs.PAPER_SPECS`` (the fused kernel at ``configs.VGG16``'s
too), at the serving batch (64 images x T=4 steps) and the serving and
default queue depths. Nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and every
pytest worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from conftest import event_geometries
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import engine
from repro.kernels.quant_matmul import quant_matmul
from repro.kernels.spike_pipeline import fused_spike_accum_pallas
from repro.kernels.spike_sparse import fused_spike_accum_sparse_pallas

SERVE_BATCH, T = 64, 4
DEPTHS = (64, 256)  # serve/bench.py serving depth, SNNConfig default


GEOMETRIES = event_geometries(configs.PAPER_SPECS.values())
#: VGG-16's eight: C_out up to 512 (four lane tiles), maps down to 2x2
VGG16_GEOMETRIES = event_geometries([configs.VGG16])


def _geom_id(cp):
    return f"hw{cp.in_hw}-cin{cp.in_c}-cout{cp.out_c}"


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the persistent
    # cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_fused(kernel, cp, depth, sharding, **extra):
    K2 = cp.kernel * cp.kernel
    P = cp.fmt.n_win * cp.fmt.n_win
    occ = _spec((SERVE_BATCH * T, cp.in_c, K2, P), jnp.int32, sharding)
    w = _spec((cp.kernel, cp.kernel, cp.in_c, cp.out_c), jnp.float32,
              sharding)
    fn = functools.partial(
        kernel, K=cp.kernel, n_win=cp.fmt.n_win, depth=depth, H=cp.in_hw,
        W=cp.in_hw, interpret=False, **extra)
    compiled = jax.jit(fn).lower(occ, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("cp", GEOMETRIES + VGG16_GEOMETRIES, ids=_geom_id)
def test_fused_spike_accum_pallas_compiles(one_chip, cp, depth):
    _compile_fused(fused_spike_accum_pallas, cp, depth, one_chip)


@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("cp", GEOMETRIES, ids=_geom_id)
def test_fused_spike_accum_sparse_pallas_compiles(one_chip, cp, depth):
    # the ragged row grid and the int8 accumulate at the largest depth
    extra = dict(bits=cp.fmt.bits_coord, invalid=cp.fmt.invalid_word)
    if depth == max(DEPTHS):
        extra.update(n_rows=SERVE_BATCH * T // 2, weight_bits=8)
    _compile_fused(fused_spike_accum_sparse_pallas, cp, depth, one_chip,
                   **extra)


@pytest.mark.parametrize("name", sorted(configs.PAPER_SPECS))
def test_quant_matmul_head_compiles(one_chip, name):
    """The int8 output head (``engine._quant_head``) at the serving batch."""
    d = configs.PAPER_SPECS[name]
    out = engine.compile_plan(d["spec"], d["hw"], d["c"]).out
    a = _spec((SERVE_BATCH, out.n_in), jnp.int8, one_chip)
    b = _spec((out.n_in, out.n_out), jnp.int8, one_chip)
    s = _spec((), jnp.float32, one_chip)
    compiled = jax.jit(functools.partial(quant_matmul, interpret=False)
                       ).lower(a, b, s, s).compile()
    assert "tpu_custom_call" in compiled.as_text()
