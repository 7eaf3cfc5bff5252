"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script itself refuses to run anywhere but a TPU; these tests call its
phase functions directly with the CPU's realizations in place of the
compiled kernels, and check its refusal and its four-device path (on
virtual CPU devices, in a child process).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

CPU_REALIZATIONS = dict(
    required=("fused:xla", "fused:sparse"),
    forbidden=("pallas", "sparse_pallas", "ref", "pallas_interpret",
               "sparse_pallas_interpret"))


@pytest.fixture
def obs_off_afterwards():
    from repro import obs

    yield
    obs.disable()
    obs.reset()


def test_phases_pass_on_cpu_at_tiny_size(tmp_path, obs_off_afterwards):
    out = chip_smoke.run_one_chip(0, dataset="mnist", n_requests=6,
                                  buckets=(1, 4), sparse_batch=4,
                                  workdir=str(tmp_path), **CPU_REALIZATIONS)
    # CPU convolutions are exact f32: the served path IS the reference
    assert out["served"]["pred_agree"] == 1.0
    assert out["served"]["max_logit_diff"] == 0.0
    assert out["sparse"]["events_rel_diff"] == [0.0] * 4
    assert out["persist"]["persist.plan_restore"] == 2
    assert out["persist"]["restored_compiles"] == 0
    assert all(0.0 < s < 0.99 for s in out["activity"])


def test_dispatch_phase_rejects_an_oracle_or_a_missing_kernel():
    with pytest.raises(chip_smoke.SmokeFailure, match="ran"):
        chip_smoke.dispatch_phase(
            {"fused:pallas": 3, "fused:sparse_pallas": 1, "fused:ref": 1},
            ("fused:pallas", "fused:sparse_pallas"), ("ref",))
    with pytest.raises(chip_smoke.SmokeFailure, match="missing"):
        chip_smoke.dispatch_phase({"fused:pallas": 3},
                                  ("fused:pallas", "fused:sparse_pallas"), ())


def test_compare_fails_past_the_tolerances():
    ref = np.array([[1.0, 0.0], [0.0, 1.0], [1.0, 0.0], [0.0, 1.0]])
    ev = np.array([[100, 50]] * 4)
    chip_smoke.compare("same", ref, ev, ref, ev)
    with pytest.raises(chip_smoke.SmokeFailure, match="agreement"):
        chip_smoke.compare("flipped", ref[:, ::-1], ev, ref, ev)
    with pytest.raises(chip_smoke.SmokeFailure, match="events"):
        chip_smoke.compare("events", ref, ev * 2, ref, ev)


def test_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    assert capsys.readouterr().out == ""


def test_four_device_path_on_virtual_cpus():
    code = ("import json, chip_smoke; print(json.dumps("
            "chip_smoke.run_four_chips(0, dataset='mnist', batch=8)))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for backend in ("dense", "queue_pallas"):
        assert out[backend]["devices"] == 4
        assert out[backend]["params_replicated"]
        assert out[backend]["max_logit_diff"] == 0.0
    assert out["serve"]["devices"] == 4
