"""Optimizer, schedules, and direct SNN training."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.training.optimizer import (adamw_init, adamw_update,
                                      cosine_schedule, global_norm)


def test_adamw_minimizes_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw_init(params)
    for _ in range(300):
        grads = jax.tree.map(lambda p: 2 * p, params)
        params, state = adamw_update(params, grads, state, lr=5e-2)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_cosine_schedule_shape():
    lr0 = float(cosine_schedule(0, base_lr=1.0, warmup=10, total=100))
    lrw = float(cosine_schedule(10, base_lr=1.0, warmup=10, total=100))
    lre = float(cosine_schedule(100, base_lr=1.0, warmup=10, total=100))
    assert lr0 == 0.0 and abs(lrw - 1.0) < 1e-6 and abs(lre - 0.1) < 1e-6


def test_grad_clip_bounds_update():
    params = {"w": jnp.zeros(3)}
    state = adamw_init(params)
    big = {"w": jnp.asarray([1e6, -1e6, 1e6])}
    new, _ = adamw_update(params, big, state, lr=1e-3, grad_clip=1.0)
    assert float(global_norm(jax.tree.map(lambda a, b: a - b, params, new))) < 1e-2


# ---------------------------------------------------------------------------
# direct SNN training (repro.training.surrogate)
# ---------------------------------------------------------------------------

def _digits(n, seed=0):
    from repro.data.synthetic import make_digits

    return make_digits(n, seed=seed)


def _params_equal(a, b):
    for la, lb in zip(a, b):
        assert la.keys() == lb.keys()
        for k in la:
            np.testing.assert_array_equal(np.asarray(la[k]),
                                          np.asarray(lb[k]))


def test_fit_snn_is_deterministic():
    """Same seed, same data => bit-identical parameters (single host)."""
    from repro.training.surrogate import fit_snn

    imgs, labels = _digits(96)
    kw = dict(T=2, epochs=1, batch=48, lr=5e-3, rate_reg=0.01, init_seed=3)
    p1, th1, l1 = fit_snn("4C3-P2-6", imgs, labels, **kw)
    p2, th2, l2 = fit_snn("4C3-P2-6", imgs, labels, **kw)
    _params_equal(p1, p2)
    assert float(l1) == float(l2) or (np.isnan(float(l1))
                                      and np.isnan(float(l2)))
    assert len(th1) == len(th2) == 3
    # a different seed trains a genuinely different net
    p3, _, _ = fit_snn("4C3-P2-6", imgs, labels,
                       **{**kw, "init_seed": 4})
    assert any(
        not np.array_equal(np.asarray(a["w"]), np.asarray(b["w"]))
        for a, b in zip(p1, p3) if "w" in a)


def test_train_snn_stage_cache_hit_runs_zero_steps():
    """Second train_snn() with the same spec: ZERO optimizer steps.

    The direct analogue of the study's "pricing sweep runs inference once"
    pin — surrogate.step_counts is the training-side execution counter."""
    from repro.study import StudyCache, StudySpec, stages
    from repro.training import surrogate as S

    spec = StudySpec(dataset="mnist", net="4C3-P2-6", input_hw=28, input_c=1,
                     n_train=96, epochs=1, n_eval=16, n_calib=24, T=2,
                     depth=32, mode="mttfs_cont", balance=False,
                     training="direct", snn_epochs=1, snn_batch=48)
    cache = StudyCache()
    stages.reset_stage_counts()
    S.reset_step_counts()
    a1 = stages.train_snn(spec, cache=cache)
    steps_first = S.step_counts["steps"]
    assert steps_first > 0
    assert stages.stage_counts["train_snn"] == 1

    a2 = stages.train_snn(spec, cache=cache)
    assert S.step_counts["steps"] == steps_first  # zero new steps
    assert stages.stage_counts["train_snn"] == 1
    assert a2.key == a1.key
    _params_equal(a1.snn_params, a2.snn_params)

    # recipe fields invalidate the key (a different training problem)
    assert stages.train_snn(
        spec.replace(snn_lr=1e-3), cache=cache).key != a1.key
    assert S.step_counts["steps"] > steps_first


def test_train_snn_disk_roundtrip(tmp_path):
    """A fresh cache over the same dir loads the artifact from disk."""
    from repro.study import StudyCache, StudySpec, stages
    from repro.training import surrogate as S

    spec = StudySpec(dataset="mnist", net="4C3-P2-6", input_hw=28, input_c=1,
                     n_train=64, epochs=1, n_eval=16, n_calib=24, T=2,
                     depth=32, mode="mttfs_cont", balance=False,
                     training="direct", snn_epochs=1, snn_batch=32)
    a1 = stages.train_snn(spec, cache=StudyCache(dir=str(tmp_path)))
    S.reset_step_counts()
    a2 = stages.train_snn(spec, cache=StudyCache(dir=str(tmp_path)))
    assert S.step_counts["steps"] == 0  # loaded, not retrained
    assert a2.key == a1.key
    _params_equal(a1.snn_params, a2.snn_params)
    for t1, t2 in zip(a1.thresholds, a2.thresholds):
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))


def test_fit_snn_checkpoint_resume_is_bit_exact(tmp_path):
    """Kill after epoch 2 of 3, resume: identical to the uninterrupted run."""
    from repro.training.surrogate import fit_snn

    imgs, labels = _digits(96)
    kw = dict(T=2, epochs=3, batch=48, lr=5e-3, init_seed=0)

    # uninterrupted reference
    p_ref, _, _ = fit_snn("4C3-P2-6", imgs, labels, **kw)

    # "killed" run: stop after 2 epochs, checkpointing as it goes...
    ck = str(tmp_path / "ck")
    fit_snn("4C3-P2-6", imgs, labels, **{**kw, "epochs": 2}, ckpt_dir=ck)
    from repro.checkpoint.checkpoint import latest_step
    assert latest_step(ck) == 2

    # ...then resume to the full 3 epochs from the same directory
    p_res, _, _ = fit_snn("4C3-P2-6", imgs, labels, **kw, ckpt_dir=ck)
    _params_equal(p_ref, p_res)
