"""Checkpointing: atomicity, integrity, corruption fallback, async, GC."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ck


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(rng.normal(size=(4, 8)), jnp.float32),
        "nested": {"b": jnp.asarray(rng.integers(0, 9, (3,)), jnp.int32)},
    }


def test_save_restore_roundtrip(tmp_path):
    tree = _tree()
    ck.save(str(tmp_path), 7, tree)
    restored, step = ck.restore(str(tmp_path), tree)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(tree["a"]), restored["a"])
    np.testing.assert_array_equal(np.asarray(tree["nested"]["b"]),
                                  restored["nested"]["b"])


def test_latest_step_ignores_uncommitted(tmp_path):
    ck.save(str(tmp_path), 5, _tree())
    # fake a crashed save: directory without COMMITTED marker
    os.makedirs(tmp_path / "step_000000009")
    assert ck.latest_step(str(tmp_path)) == 5


def test_corrupt_checkpoint_falls_back(tmp_path):
    tree = _tree()
    ck.save(str(tmp_path), 1, tree)
    ck.save(str(tmp_path), 2, jax.tree.map(lambda x: x + 1, tree))
    # corrupt the newest shard
    shard = tmp_path / "step_000000002" / "shard_00000.npz"
    shard.write_bytes(b"garbage")
    restored, step = ck.restore(str(tmp_path), tree)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(tree["a"]), restored["a"])


def test_hash_mismatch_detected(tmp_path):
    tree = _tree()
    path = ck.save(str(tmp_path), 3, tree)
    man = json.load(open(os.path.join(path, "manifest.json")))
    next(iter(man["leaves"].values()))["hash"] = "deadbeef"
    json.dump(man, open(os.path.join(path, "manifest.json"), "w"))
    with pytest.raises(IOError):
        ck.restore(str(tmp_path), tree)


def test_async_checkpointer_and_gc(tmp_path):
    acp = ck.AsyncCheckpointer(str(tmp_path), keep=2)
    for s in [10, 20, 30, 40]:
        acp.save(s, _tree(s))
    acp.wait()
    acp._gc()
    steps = sorted(int(n[5:-10]) for n in os.listdir(tmp_path)
                   if n.endswith(".COMMITTED"))
    assert steps == [30, 40]


def test_namedtuple_state_roundtrip(tmp_path):
    """A NamedTuple state (the AdamW state over the SNN's parameter tree)
    comes back as the same NamedTuple, field by field."""
    from repro.core.snn_model import init_params
    from repro.training.optimizer import AdamWState, adamw_init

    params = init_params(jax.random.PRNGKey(1), "4C3-P2-6", 8, 1)
    state = adamw_init(params)._replace(
        step=jnp.asarray(5, jnp.int32),
        mu=jax.tree.map(lambda p: p + 1.0, params))
    ck.save(str(tmp_path), 5, state)
    restored, _ = ck.restore(str(tmp_path), state)
    assert isinstance(restored, AdamWState)
    assert int(restored.step) == 5
    np.testing.assert_array_equal(np.asarray(state.mu[0]["w"]),
                                  restored.mu[0]["w"])


def test_snn_params_with_empty_pool_slots_roundtrip(tmp_path):
    """The engine's parameter pytree (list of per-layer dicts where pool
    layers are EMPTY dicts) round-trips bit-exactly — the tree fit_snn
    checkpoints between direct-training epochs, alongside its AdamW state."""
    from repro.core.snn_model import init_params
    from repro.training.optimizer import adamw_init

    params = init_params(jax.random.PRNGKey(0), "4C3-P2-6", 8, 1)
    assert params[1] == {}  # the pool slot really is an empty dict
    state = (params, adamw_init(params))
    ck.save(str(tmp_path), 2, state)
    restored, step = ck.restore(str(tmp_path), state)
    assert step == 2
    r_params, r_opt = restored
    assert r_params[1] == {}  # empty slot survives the flatten/unflatten
    for orig, back in zip(params, r_params):
        assert orig.keys() == back.keys()
        for k in orig:
            np.testing.assert_array_equal(np.asarray(orig[k]),
                                          np.asarray(back[k]))
    np.testing.assert_array_equal(np.asarray(state[1].mu[0]["w"]),
                                  np.asarray(r_opt.mu[0]["w"]))
