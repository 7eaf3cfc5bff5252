"""The staged Study API: golden equivalence, repricing, caching, validation.

The load-bearing guarantees of the ``repro.study`` refactor:

1. **Golden**: the staged pipeline and the ``run_study`` shim reproduce the
   frozen pre-refactor monolith (``tests/_legacy_study.py``) *exactly* —
   every scalar equal, every array bit-identical.
2. **Repricing**: a pricing sweep (compressed / vmem_resident / weight_bits)
   equals a fresh monolith run per variant while executing the collect
   stage exactly once (pinned by the stage counter).
3. **Caching**: train/convert artifacts round-trip through disk with exact
   content, keyed by content hashes (config changes can never alias).
4. **Validation**: bad dataset/backend/mode names raise named errors.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from _legacy_study import legacy_run_study
from _report_compare import assert_reports_identical as _assert_identical

from repro import study as study_api
from repro.core.engine import SpecError
from repro.study import (StudyCache, StudySpec, StudySpecError,
                         UnknownBackendError, UnknownDatasetError,
                         UnknownInputModeError, UnknownNeuronModeError)

# tiny-but-real scenario: one conv + fused pool + classifier, 2 epochs
SMALL = StudySpec(dataset="mnist", net="6C3-P2-8", input_hw=28, input_c=1,
                  n_train=256, epochs=2, n_eval=48, eval_seed=99, n_calib=64,
                  T=3, depth=64, mode="mttfs_cont", balance=True)


@pytest.fixture(scope="module")
def small_study():
    cache = StudyCache()
    trained = study_api.train(SMALL, cache=cache)
    return cache, trained


def _legacy_kwargs(spec, trained, **overrides):
    eval_images, eval_labels = spec.load_eval()
    kw = dict(T=spec.T, depth=spec.depth, compressed=spec.compressed,
              input_mode=spec.input_mode, mode=spec.mode,
              balance=spec.balance, backend=spec.backend,
              weight_bits=spec.weight_bits,
              vmem_resident=spec.vmem_resident, batch=spec.batch)
    kw.update(overrides)
    return (trained.params, spec.net, spec.dataset,
            jnp.asarray(eval_images), jnp.asarray(eval_labels),
            jnp.asarray(trained.train_images[: spec.n_calib])), kw


# ---------------------------------------------------------------------------
# 1. golden: staged == shim == frozen monolith
# ---------------------------------------------------------------------------

def test_staged_and_shim_match_legacy_monolith(small_study):
    cache, trained = small_study
    staged = study_api.run(SMALL, cache=cache)

    args, kw = _legacy_kwargs(SMALL, trained)
    legacy = legacy_run_study(*args, **kw)
    _assert_identical(staged, legacy)

    from repro.core.comparison import run_study

    with pytest.deprecated_call():
        shim = run_study(*args, **kw)
    _assert_identical(shim, legacy)
    assert shim.spec is not None  # the Report carries its StudySpec


# ---------------------------------------------------------------------------
# 2. repricing: sweep == fresh run per variant, inference exactly once
# ---------------------------------------------------------------------------

def test_pricing_sweep_reprices_exactly_with_one_collect():
    variants = [
        dict(compressed=True, vmem_resident=True),
        dict(compressed=True, vmem_resident=False),
        dict(compressed=False, vmem_resident=False),
        dict(weight_bits=4),
    ]
    sweep_cache = StudyCache()  # cold below the train stage
    trained = study_api.train(SMALL, cache=sweep_cache)
    study_api.reset_stage_counts()
    reports = study_api.sweep(SMALL, variants, cache=sweep_cache)

    # the acceptance criterion: the whole sweep ran SNN inference ONCE
    assert study_api.stage_counts["collect"] == 1
    assert study_api.stage_counts["convert"] == 1
    assert study_api.stage_counts["train"] == 0

    for variant, rep in zip(variants, reports):
        args, kw = _legacy_kwargs(SMALL, trained, **variant)
        _assert_identical(rep, legacy_run_study(*args, **kw))


def test_depth_change_re_collects_but_converts_once():
    study_api.reset_stage_counts()
    cold = StudyCache()
    study_api.run(SMALL, cache=cold)
    study_api.run(SMALL.replace(depth=16), cache=cold)
    assert study_api.stage_counts["collect"] == 2  # depth is a collect field
    assert study_api.stage_counts["convert"] == 1  # balance ignores depth
    assert study_api.stage_counts["train"] == 1


# ---------------------------------------------------------------------------
# 3. cache round-trips
# ---------------------------------------------------------------------------

def test_train_convert_disk_cache_roundtrip(tmp_path):
    study_api.reset_stage_counts()
    disk = StudyCache(dir=str(tmp_path))
    t1 = study_api.train(SMALL, cache=disk)
    c1 = study_api.convert(SMALL, t1, cache=disk)
    executed = dict(study_api.stage_counts)

    # fresh cache object, same dir: memory is cold, disk must hit
    disk2 = StudyCache(dir=str(tmp_path))
    t2 = study_api.train(SMALL, cache=disk2)
    c2 = study_api.convert(SMALL, t2, cache=disk2)
    assert dict(study_api.stage_counts) == executed  # nothing re-executed

    for l1, l2 in zip(t1.params, t2.params):
        for k in l1:
            np.testing.assert_array_equal(np.asarray(l1[k]),
                                          np.asarray(l2[k]))
    for l1, l2 in zip(c1.snn_params, c2.snn_params):
        for k in l1:
            np.testing.assert_array_equal(np.asarray(l1[k]),
                                          np.asarray(l2[k]))
    for th1, th2 in zip(c1.thresholds, c2.thresholds):
        np.testing.assert_array_equal(np.asarray(th1), np.asarray(th2))
    assert t1.key == t2.key and c1.key == c2.key

    # content keying: a config change changes the key (no stale aliasing —
    # the bug the old name-keyed benchmark cache had)
    t3_key = study_api.train(SMALL.replace(epochs=1), cache=disk2).key
    assert t3_key != t1.key
    assert study_api.stage_counts["train"] == executed["train"] + 1


def test_convert_requires_calib_for_caller_params(small_study):
    _, trained = small_study
    with pytest.raises(ValueError, match="calib_images"):
        study_api.convert(SMALL, study_api.from_params(trained.params))


def test_collect_memory_tier_is_lru_bounded():
    cache = StudyCache(mem_caps={"collect": 2})
    for i in range(3):
        cache.get_or_build("collect", f"k{i}", lambda i=i: i)
    cache.get_or_build("collect", "k1", lambda: "rebuilt?")  # hit: refreshes
    cache.get_or_build("collect", "k3", lambda: 3)           # evicts k2
    kept = [k for kind, k in cache._mem if kind == "collect"]
    assert kept == ["k1", "k3"]
    assert cache.get_or_build("collect", "k1", lambda: "rebuilt?") == 1
    # unbounded kinds are never evicted
    for i in range(5):
        cache.get_or_build("train", f"t{i}", lambda i=i: i)
    assert sum(1 for kind, _ in cache._mem if kind == "train") == 5


# ---------------------------------------------------------------------------
# 4. StudySpec validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("changes, err", [
    (dict(backend="verilog"), UnknownBackendError),
    (dict(mode="lif_nope"), UnknownNeuronModeError),
    (dict(input_mode="rate"), UnknownInputModeError),
    (dict(T=0), StudySpecError),
    (dict(depth=-4), StudySpecError),
    (dict(n_eval=0), StudySpecError),
    (dict(training="distill"), StudySpecError),
    (dict(surrogate="heaviside"), StudySpecError),
    (dict(loss_target="ttfs"), StudySpecError),
    (dict(snn_epochs=0), StudySpecError),
    (dict(snn_batch=-1), StudySpecError),
])
def test_spec_validation_named_errors(changes, err):
    kw = dict(dataset="mnist", net="6C3-P2-8", input_hw=28, input_c=1)
    kw.update(changes)
    with pytest.raises(err):
        StudySpec(**kw)


def test_unknown_dataset_named_error():
    # needs the paper zoo to resolve defaults -> immediate named error
    with pytest.raises(UnknownDatasetError, match="imagenet"):
        StudySpec(dataset="imagenet")
    # explicit geometry tolerates a free-form label (the shim's use case:
    # caller-provided data under an arbitrary name) ...
    spec = StudySpec(dataset="my-variant", net="6C3-P2-8",
                     input_hw=28, input_c=1)
    # ... until it is asked to load registry data
    with pytest.raises(UnknownDatasetError, match="my-variant"):
        spec.load_eval()
    with pytest.raises(UnknownDatasetError, match="my-variant"):
        study_api.train(spec)


def test_spec_validation_bad_net_is_spec_error():
    with pytest.raises(SpecError):  # even kernel — engine grammar error
        StudySpec(dataset="mnist", net="6C4-8", input_hw=28, input_c=1)
    with pytest.raises(SpecError):  # pool window exceeds feature map
        StudySpec(dataset="mnist", net="6C31-P29-8", input_hw=28, input_c=1)


def test_spec_defaults_resolve_from_paper_zoo():
    spec = StudySpec(dataset="cifar10")
    from repro.configs import PAPER_SPECS

    assert spec.net == PAPER_SPECS["cifar10"]["spec"]
    assert (spec.input_hw, spec.input_c) == (32, 3)
    # frozen + hashable (sweepable via dataclasses.replace)
    assert hash(spec) == hash(dataclasses.replace(spec))
    assert spec.replace(compressed=False) != spec


# ---------------------------------------------------------------------------
# use_queues deprecation shim
# ---------------------------------------------------------------------------

def test_use_queues_maps_to_queue_backend_with_warning(small_study):
    cache, trained = small_study
    from repro.core.comparison import run_study

    args, kw = _legacy_kwargs(SMALL, trained, balance=False)
    args = args[:3] + (args[3][:8], args[4][:8], args[5])  # 8 samples: queue path is slow
    kw.pop("backend")
    with pytest.warns(DeprecationWarning, match="use_queues"):
        res_q = run_study(*args, **kw, use_queues=True)
    res_named = run_study(*args, **kw, backend="queue")
    _assert_identical(res_q, res_named)


def test_report_json_and_sweep_rows(small_study):
    cache, trained = small_study
    rep = study_api.run(SMALL, cache=cache)
    j = rep.to_json()
    assert j["dataset"] == "mnist" and j["n_samples"] == SMALL.n_eval
    assert len(j["snn_energy_j_deciles"]) == 7
    assert j["pricing"] == {"compressed": True, "vmem_resident": True,
                            "weight_bits": 8}

    reports = study_api.sweep(SMALL, [dict(vmem_resident=True),
                                      dict(vmem_resident=False)], cache=cache)
    rows = study_api.sweep_rows(reports)
    assert len(rows) == 2 and rows[0][0] != rows[1][0]
    assert rows[1][1]["median_energy_j"] > rows[0][1]["median_energy_j"]
