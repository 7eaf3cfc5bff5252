"""The control: the reference in the program's place, one precision down,
must come out not correct under each configuration's limits.

On the chip the control is ``bench/tools/control.py``: the reference at
``Precision.HIGH`` (three bf16 passes) and ``DEFAULT`` (one). A CPU
computes every f32 contraction in f32 whatever the precision asked, so
here the passes are spelled out: each operand is split into bf16 parts,
the parts' products are exact in f32, and the control sums the three
(``HIGH``) or the one (``DEFAULT``) that the chip's passes sum. The sample
is the size a run compares, 256 answers, at the cell's own widths.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import harness  # noqa: E402
import reference  # noqa: E402
import work  # noqa: E402

EXACT = jax.lax.Precision.HIGHEST
CONFIGS = ["cifar10-queue_pallas", "cifar10-dense"]


def _parts(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def passes(op, n_passes):
    """``op`` as the chip's ``n_passes`` bf16 passes with f32 accumulation."""
    def run(x, w, precision):
        (xh, xl), (wh, wl) = _parts(x), _parts(w)
        out = op(xh, wh, EXACT)
        if n_passes == 3:
            out = out + op(xh, wl, EXACT) + op(xl, wh, EXACT)
        return out
    return run


@pytest.fixture
def lowered(monkeypatch):
    conv, matmul = reference._conv, reference._matmul

    def lower(n_passes):
        reference.forward_fn.cache_clear()
        monkeypatch.setattr(reference, "_conv", passes(conv, n_passes))
        monkeypatch.setattr(reference, "_matmul", passes(matmul, n_passes))

    yield lower
    reference.forward_fn.cache_clear()


@pytest.mark.parametrize("n_passes", [3, 1])
@pytest.mark.parametrize("name", CONFIGS)
def test_control_fails_the_limits(lowered, name, n_passes):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    seed = 2**31 + 99
    net = work.parse(config["spec"], config["input_hw"], config["input_c"])
    params = reference.make_params(net, seed)
    images = reference.make_images(harness.SAMPLE, seed)
    kw = dict(T=config["T"], threshold=config["threshold"],
              v_init=config["v_init_frac"], precision=config["precision"])
    ref = reference.forward(net, params, images, **kw)
    lowered(n_passes)
    control = reference.forward(net, params, images, **kw)
    numbers = harness.compare(*control, *ref)
    failed = [k for k, v in numbers.items() if v > config["limits"][k]]
    assert failed, numbers
    assert np.all(np.isfinite(list(numbers.values())))
