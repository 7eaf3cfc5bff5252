"""Data-parallel execution (``repro.parallel``): sharded == single device.

The acceptance property of the parallel layer: sharding the batch axis over
a device mesh is **bit-exact** against the single-device engine — logits and
every stat, including AEQ overflow in the drop regime — at B ∈ {1, 3, 16,
64} (1 and 3 exercise the pad-to-divisible fallback on a 4-way mesh), on
both the ``dense`` and ``queue_pallas`` backends.

Multi-device cases need more than one visible device; on CPU that means

    XLA_FLAGS=--xla_force_host_platform_device_count=4 pytest tests/test_parallel.py

which is exactly what the CI ``devices: 4`` matrix leg sets (see
``docs/PARALLEL.md``). Under a single device those tests skip; the
mesh plumbing tests run everywhere.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import parallel
from repro.core import engine, snn_model
from repro.parallel import batch_partition_spec

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >1 device: run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4")

SPEC = "6C3-P2-4C3-8"
HW, C = 10, 1
N_LAYERS = len(engine.parse_spec(SPEC))


@pytest.fixture(scope="module")
def net():
    params = snn_model.init_params(jax.random.PRNGKey(7), SPEC, HW, C)
    th = [jnp.asarray(0.5)] * N_LAYERS
    imgs = np.random.default_rng(3).random((64, HW, HW, C)).astype(np.float32)
    return params, th, imgs


def _assert_bit_exact(got, ref, label):
    gl, gs = got
    rl, rs = ref
    np.testing.assert_array_equal(np.asarray(gl), np.asarray(rl),
                                  err_msg=f"{label}: logits")
    for f in rs._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(gs, f)), np.asarray(getattr(rs, f)),
            err_msg=f"{label}: stats.{f}")


# ---------------------------------------------------------------------------
# Bit-exactness: the tentpole acceptance criterion
# ---------------------------------------------------------------------------

@multi_device
@pytest.mark.parametrize("backend", ["dense", "queue_pallas"])
@pytest.mark.parametrize("B", [1, 3, 16, 64])
def test_sharded_bit_exact_vs_single_device(net, make_snn_config, backend, B):
    """Sharded logits AND stats == single-device, incl. overflow at small
    depth (depth=8 forces AEQ drops, so the drop rule itself is compared)."""
    params, th, imgs = net
    cfg = make_snn_config(SPEC, HW, C, T=3, depth=8, mode="mttfs_cont",
                          input_mode="binary")
    batch = jnp.asarray(imgs[:B])
    mesh = parallel.data_mesh()

    ref = engine.infer_batch(params, th, cfg, batch, backend=backend)
    got = parallel.infer_batch_sharded(params, th, cfg, batch,
                                       backend=backend, mesh=mesh)
    _assert_bit_exact(got, ref, f"{backend}/B={B}")
    if B >= 16:
        # the small queue depth must actually be in the drop regime, or the
        # overflow comparison above proves nothing
        assert int(np.asarray(ref[1].overflow).sum()) > 0


@multi_device
def test_sharded_analog_input_mode(net, make_snn_config):
    """The analog (constant-current) encoding shards bit-exactly too."""
    params, th, imgs = net
    cfg = make_snn_config(SPEC, HW, C, T=3, mode="mttfs_cont",
                          input_mode="analog")
    batch = jnp.asarray(imgs[:16])
    ref = engine.infer_batch(params, th, cfg, batch, backend="dense")
    got = parallel.infer_batch_sharded(params, th, cfg, batch,
                                       backend="dense",
                                       mesh=parallel.data_mesh())
    _assert_bit_exact(got, ref, "analog/dense")


@multi_device
def test_use_mesh_routes_engine_infer_batch(net, make_snn_config):
    """Inside ``use_mesh`` the engine entry point itself is sharded (same
    bits), and the dispatch hook is restored on exit — exception included."""
    params, th, imgs = net
    cfg = make_snn_config(SPEC, HW, C, T=3, depth=8, mode="mttfs_cont",
                          input_mode="binary")
    batch = jnp.asarray(imgs[:6])   # 6 % 4 != 0: fallback path under mesh
    ref = engine.infer_batch(params, th, cfg, batch, backend="dense")

    assert engine._batch_dispatch is None
    with parallel.use_mesh(parallel.data_mesh()):
        assert engine._batch_dispatch is not None
        got = engine.infer_batch(params, th, cfg, batch, backend="dense")
    assert engine._batch_dispatch is None
    _assert_bit_exact(got, ref, "use_mesh/dense")

    with pytest.raises(RuntimeError, match="boom"):
        with parallel.use_mesh(parallel.data_mesh()):
            raise RuntimeError("boom")
    assert engine._batch_dispatch is None       # restored despite the raise

    with parallel.use_mesh(None):               # None is a no-op block
        assert engine._batch_dispatch is None


# ---------------------------------------------------------------------------
# Serving over a mesh
# ---------------------------------------------------------------------------

@multi_device
def test_serve_runtime_mesh_responses_bit_equal(net):
    """A mesh-backed runtime serves the same logits/energies as a local one,
    and only mesh-divisible buckets compile the sharded plan."""
    from repro.serve import BucketPolicy, ModelRegistry, ServeRuntime

    params, th, imgs = net
    cfg = snn_model.SNNConfig(spec=SPEC, input_hw=HW, input_c=C, T=3,
                              depth=16, mode="mttfs_cont",
                              input_mode="binary")
    mesh = parallel.data_mesh()
    n = parallel.mesh_size(mesh)

    def serve_all(mesh):
        registry = ModelRegistry()
        registry.register("toy", params, th, cfg, backend="queue_pallas")
        rt = ServeRuntime(registry, BucketPolicy((1, 4, 16)), mesh=mesh)
        for im in imgs[:9]:
            rt.submit(im)
        return sorted(rt.run_until_drained(), key=lambda r: r.rid)

    local = serve_all(None)
    sharded = serve_all(mesh)
    assert len(local) == len(sharded) == 9
    for a, b in zip(local, sharded):
        np.testing.assert_array_equal(a.logits, b.logits)
        assert a.energy_j == b.energy_j            # float-exact metering
        assert a.model_latency_s == b.model_latency_s
        assert (a.pred, a.bucket) == (b.pred, b.bucket)

    handle = ModelRegistry(mesh=mesh).register("t", params, th, cfg)
    for b in (1, 4, 16):
        assert handle._bucket_sharded(b) == (b % n == 0)


@multi_device
def test_registry_set_mesh_drops_compiled_plans(net):
    from repro.serve import ModelRegistry

    params, th, _ = net
    cfg = snn_model.SNNConfig(spec=SPEC, input_hw=HW, input_c=C, T=2,
                              depth=16, mode="mttfs_cont")
    registry = ModelRegistry()
    handle = registry.register("toy", params, th, cfg, backend="dense")
    handle.plan_for(4)
    assert handle.cached_buckets() == (4,)
    registry.set_mesh(parallel.data_mesh())      # re-equips live handles
    assert handle.mesh is not None
    assert handle.cached_buckets() == ()         # placement-stale plans gone
    handle.plan_for(4)                           # recompiles sharded, runs
    zeros = np.zeros((4, HW, HW, C), np.float32)
    logits, _ = handle.run_bucket(zeros, 4)
    assert logits.shape == (4, engine.parse_spec(SPEC)[-1][1])


# ---------------------------------------------------------------------------
# Mesh plumbing (runs on any device count)
# ---------------------------------------------------------------------------

def test_data_mesh_shape_and_caching():
    mesh = parallel.data_mesh()
    assert tuple(mesh.axis_names) == (parallel.DATA_AXIS,)
    assert parallel.mesh_size(mesh) == len(jax.devices())
    assert parallel.data_mesh() is mesh          # cached: stable cache keys
    assert parallel.mesh_size(None) == 1
    with pytest.raises(ValueError, match="host_platform_device_count"):
        parallel.data_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError):
        parallel.data_mesh(0)


def test_batch_partition_spec_divisibility_fallback():
    # the executor's rule: shard iff B divides the mesh
    devs = np.array(jax.devices() * 4)[:4]
    mesh = Mesh(devs, ("data",))
    assert batch_partition_spec(mesh, (8, 10, 10, 1))[0] == "data"
    assert batch_partition_spec(mesh, (6, 10, 10, 1))[0] is None
    assert batch_partition_spec(mesh, (3, 28, 28, 1))[0] is None


def test_single_device_mesh_falls_back_to_engine(net, make_snn_config):
    """mesh of one device == the engine's own runner (no shard_map at all)."""
    params, th, imgs = net
    cfg = make_snn_config(SPEC, HW, C, T=2, depth=16, mode="mttfs_cont")
    batch = jnp.asarray(imgs[:4])
    ref = engine.infer_batch(params, th, cfg, batch, backend="dense")
    got = parallel.infer_batch_sharded(params, th, cfg, batch,
                                       backend="dense",
                                       mesh=parallel.data_mesh(1))
    _assert_bit_exact(got, ref, "1-device mesh")
