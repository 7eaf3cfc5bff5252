"""Checkpoint/restore and cold-start: ``repro.serve.persist`` + friends.

The persistence layer's contract, pinned here:

1. **Bit-exactness** — a registry restored from disk serves the *same
   numbers* as the registry that built it: logits, every SNNStats field,
   and per-request energies, all bit-for-bit.
2. **No recompilation on the warm path** — after ``load_registry`` with
   plan blobs, warming the bucket ladder is execute-only
   (``compile_count == 0``); the restored plans ARE the plans.
3. **Failures are loud and named** — a tampered manifest raises
   ``StaleCheckpointError``, damaged bytes raise ``CorruptCheckpointError``
   (params shard and plan blob alike), a missing checkpoint raises
   ``CheckpointError``. Nothing silently serves wrong numbers.
4. **Degrade, don't die** — when plan export is impossible (version drift,
   exotic backend), params still checkpoint and the restored registry
   re-lowers lazily with identical numbers.

Also covers the study-side ``stages.export_artifact`` bridge and the
cold/warm paired bench gate in ``scripts/check_bench_regression.py``.
"""
import importlib.util
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import snn_model
from repro.serve import (BucketPolicy, CheckpointError,
                         CorruptCheckpointError, ModelRegistry,
                         ServeRuntime, StaleCheckpointError, load_registry,
                         save_registry)
from repro.serve import persist
from repro.study import stages
from repro.study.artifacts import ConvertArtifact

SPEC = "4C3-P2-8"
HW, C = 8, 1
BUCKETS = (1, 4)


def make_cfg(**overrides):
    kw = dict(spec=SPEC, input_hw=HW, input_c=C, T=3, depth=16,
              mode="mttfs_cont", input_mode="binary")
    kw.update(overrides)
    return snn_model.SNNConfig(**kw)


@pytest.fixture(scope="module")
def net():
    params = snn_model.init_params(jax.random.PRNGKey(7), SPEC, HW, C)
    th = [jnp.asarray(0.5)] * len(params)
    imgs = np.random.default_rng(3).random((6, HW, HW, C)).astype(np.float32)
    return params, th, imgs


def build_registry(net, **cfg_overrides):
    params, th, _ = net
    reg = ModelRegistry()
    reg.register("toy", params, th, make_cfg(**cfg_overrides),
                 backend="queue_pallas")
    return reg


def serve_all(registry, imgs):
    """Run every image through a fresh runtime; responses sorted by rid."""
    rt = ServeRuntime(registry, BucketPolicy(BUCKETS))
    for img in imgs:
        rt.submit(img)
    responses = rt.step() + rt.run_until_drained()
    responses.sort(key=lambda r: r.rid)
    return responses


@pytest.fixture(scope="module")
def saved(net, tmp_path_factory):
    """One canonical save: (checkpoint root, reference responses)."""
    params, th, imgs = net
    reg = build_registry(net)
    root = str(tmp_path_factory.mktemp("ckpt") / "registry")
    save_registry(reg, root, buckets=BUCKETS)
    return root, serve_all(reg, imgs)


def copy_ckpt(saved, tmp_path):
    """Private mutable copy for corruption tests."""
    dst = str(tmp_path / "registry")
    shutil.copytree(saved[0], dst)
    return dst


# ---------------------------------------------------------------------------
# Round trip: bit-exactness + the no-recompile warm path
# ---------------------------------------------------------------------------

def test_restore_serves_bit_exact(net, saved):
    _, _, imgs = net
    root, ref = saved
    restored = load_registry(root)
    got = serve_all(restored, imgs)

    assert [r.rid for r in got] == [r.rid for r in ref]
    for a, b in zip(ref, got):
        assert np.array_equal(a.logits, b.logits)
        assert a.pred == b.pred
        # float64 equality on the float-cast energies is exactly the
        # cross-replica comparison the fleet parent performs
        assert a.energy_j == b.energy_j
        for f_a, f_b in zip(a.stats, b.stats):
            assert np.array_equal(np.asarray(f_a), np.asarray(f_b))


def test_restore_plans_then_warmup_never_compiles(saved):
    root, _ = saved
    restored = load_registry(root)
    h = restored.get("toy")
    # the plan blobs were adopted at load time for the whole saved ladder
    assert set(h.cached_buckets()) == set(BUCKETS)
    assert h.compile_count == 0
    h.warmup(BUCKETS)            # execute-only: restored plans are hits
    assert h.compile_count == 0


def test_restored_handle_keeps_provenance(saved):
    root, _ = saved
    manifest = persist.read_manifest(root)
    entry = manifest["models"]["toy"]
    restored = load_registry(root)
    h = restored.get("toy")
    assert entry["key"] == persist.registry_key(
        h.params, h.thresholds, h.cfg, h.backend)
    assert entry["backend"] == "queue_pallas"
    assert set(entry["plans"]) == {str(b) for b in BUCKETS}
    assert all(p["format"] == "jax_export" for p in entry["plans"].values())


# ---------------------------------------------------------------------------
# Named failures
# ---------------------------------------------------------------------------

def test_missing_checkpoint_raises_named_error(tmp_path):
    with pytest.raises(CheckpointError, match="no registry checkpoint"):
        load_registry(str(tmp_path / "nowhere"))


def test_tampered_manifest_raises_stale(saved, tmp_path):
    root = copy_ckpt(saved, tmp_path)
    path = os.path.join(root, persist.MANIFEST)
    with open(path) as f:
        manifest = json.load(f)
    manifest["models"]["toy"]["cfg"]["T"] += 1     # silent config drift
    with open(path, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(StaleCheckpointError, match="no longer matches"):
        load_registry(root)


def _flip_byte(path, offset=100):
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0xFF]))


def test_corrupted_params_shard_raises(saved, tmp_path):
    root = copy_ckpt(saved, tmp_path)
    shards = [os.path.join(dp, fn)
              for dp, _, fns in os.walk(os.path.join(root, "models"))
              for fn in fns if fn.endswith(".npz")]
    assert shards
    _flip_byte(shards[0])
    with pytest.raises(CorruptCheckpointError):
        load_registry(root)


def test_corrupted_plan_blob_raises(saved, tmp_path):
    root = copy_ckpt(saved, tmp_path)
    blob = os.path.join(root, "plans", "toy",
                        f"bucket_{BUCKETS[0]}.jaxexp")
    assert os.path.exists(blob)
    _flip_byte(blob)
    with pytest.raises(CorruptCheckpointError, match="content hash"):
        load_registry(root)


def test_unreadable_manifest_raises_corrupt(saved, tmp_path):
    root = copy_ckpt(saved, tmp_path)
    with open(os.path.join(root, persist.MANIFEST), "w") as f:
        f.write("{ not json")
    with pytest.raises(CorruptCheckpointError, match="unreadable"):
        load_registry(root)


# ---------------------------------------------------------------------------
# Degrade-don't-die: export impossible -> params-only checkpoint
# ---------------------------------------------------------------------------

def test_plan_export_failure_degrades_to_lazy_relower(
        net, tmp_path, monkeypatch):
    params, th, imgs = net
    reg = build_registry(net)
    ref = serve_all(reg, imgs)

    def boom(handle, bucket):
        raise RuntimeError("export unavailable in this environment")

    monkeypatch.setattr(persist, "_export_plan", boom)
    root = str(tmp_path / "registry")
    with pytest.warns(RuntimeWarning, match="plan export failed"):
        save_registry(reg, root, buckets=BUCKETS)

    entry = persist.read_manifest(root)["models"]["toy"]
    assert all(p["format"] == "none" for p in entry["plans"].values())

    monkeypatch.undo()
    restored = load_registry(root)
    h = restored.get("toy")
    assert h.cached_buckets() == ()          # nothing adopted
    got = serve_all(restored, imgs)          # lazily re-lowers
    assert h.compile_count > 0
    for a, b in zip(ref, got):
        assert np.array_equal(a.logits, b.logits)
        assert a.energy_j == b.energy_j


# ---------------------------------------------------------------------------
# Keys
# ---------------------------------------------------------------------------

def test_registry_key_is_content_stable(net):
    params, th, _ = net
    k1 = persist.registry_key(params, th, make_cfg(), "queue_pallas")
    k2 = persist.registry_key(params, th, make_cfg(), "queue_pallas")
    assert k1 == k2
    assert k1 != persist.registry_key(params, th, make_cfg(T=4),
                                      "queue_pallas")
    assert k1 != persist.registry_key(params, th, make_cfg(), "dense")
    bumped = [dict(layer) for layer in params]
    key0 = sorted(bumped[0])[0]
    bumped[0][key0] = bumped[0][key0] + 1e-3
    assert k1 != persist.registry_key(bumped, th, make_cfg(), "queue_pallas")


# ---------------------------------------------------------------------------
# Study-side export bridge
# ---------------------------------------------------------------------------

def test_export_artifact_round_trip(net, tmp_path):
    params, th, _ = net
    art = ConvertArtifact([dict(p) for p in params], list(th), "stage-key")
    root = str(tmp_path / "export")
    stages.export_artifact(art, root)
    back = stages.load_artifact(root)
    assert isinstance(back, ConvertArtifact)
    assert back.key == "stage-key"
    for a, b in zip(art.snn_params, back.snn_params):
        for k in a:
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
    for a, b in zip(art.thresholds, back.thresholds):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_export_artifact_detects_swapped_params(net, tmp_path):
    params, th, _ = net
    art = ConvertArtifact([dict(p) for p in params], list(th), "k")
    root = str(tmp_path / "export")
    manifest_path = stages.export_artifact(art, root)
    other = snn_model.init_params(jax.random.PRNGKey(8), SPEC, HW, C)
    swapped = ConvertArtifact([dict(p) for p in other], list(th), "k")
    root2 = str(tmp_path / "export2")
    stages.export_artifact(swapped, root2)
    # graft the other export's shards under the first manifest
    with open(manifest_path) as f:
        manifest = json.load(f)
    shutil.rmtree(root)
    shutil.copytree(root2, root)
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(ValueError, match="stale or tampered"):
        stages.load_artifact(root)


def test_export_artifact_missing_manifest(tmp_path):
    with pytest.raises(FileNotFoundError):
        stages.load_artifact(str(tmp_path))


# ---------------------------------------------------------------------------
# The paired cold/warm bench gate
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gate():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "check_bench_regression.py")
    spec = importlib.util.spec_from_file_location("check_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_coldstart_pair_gate_passes_fast_warm(gate):
    rows = {"serve/coldstart_cold": {"us_per_call": 3.0e6},
            "serve/coldstart_warm": {"us_per_call": 0.3e6}}
    pairs, errors = gate.check_coldstart_pairs(rows, min_speedup=5.0)
    assert errors == []
    assert pairs == [("serve/coldstart", 3.0e6, 0.3e6, 10.0)]


def test_coldstart_pair_gate_fails_slow_warm(gate):
    rows = {"serve/coldstart_cold": {"us_per_call": 1.0e6},
            "serve/coldstart_warm": {"us_per_call": 0.5e6}}
    _, errors = gate.check_coldstart_pairs(rows, min_speedup=5.0)
    assert len(errors) == 1
    assert "not paying for itself" in errors[0]


def test_coldstart_pair_gate_flags_untimed_pair(gate):
    rows = {"x_cold": {"us_per_call": 0.0}, "x_warm": {"us_per_call": 1.0}}
    _, errors = gate.check_coldstart_pairs(rows, min_speedup=1.0)
    assert errors and "untimed" in errors[0]


def test_coldstart_pair_gate_ignores_unpaired_rows(gate):
    rows = {"solo_cold": {"us_per_call": 5.0},
            "other_bench": {"us_per_call": 9.0}}
    pairs, errors = gate.check_coldstart_pairs(rows, min_speedup=5.0)
    assert pairs == [] and errors == []


# ---------------------------------------------------------------------------
# One process per chip, one compile-cache directory
# ---------------------------------------------------------------------------

def test_fleet_parent_imports_no_jax():
    """The fleet parent must stay off the accelerator its replicas need."""
    import subprocess
    import sys

    code = "import sys, repro.serve.fleet; print('jax' in sys.modules)"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True)
    assert out.stdout.strip() == "False"


def test_fleet_starts_one_unpinned_replica_per_tpu_host():
    from repro.serve import fleet

    assert fleet.replica_limit("tpu") == 1
    assert fleet.replica_limit("cpu") is None


def test_compile_cache_dir_is_jax_variable_or_fixed_default(monkeypatch):
    from repro.core import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.resolve_dir() == compile_cache.DEFAULT_DIR
    assert os.path.basename(compile_cache.DEFAULT_DIR) == ".jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.resolve_dir() == "/elsewhere/cache"
