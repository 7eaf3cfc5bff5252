"""VGG-16 on CIFAR-10 through the engine: kernels wider than their map.

``configs.VGG16`` pools a 32x32 map four times before its last three 3x3
convs, so they run on a 2x2 map with one window (n_win = 1), and its
stages from 8x8 down carry 256 and 512 output channels, wider than one
128-lane vector register. These tests pin, on the CPU, that the plan
accepts it and that the event backends compute the zero-padded SAME
convolution there:

- ``dense`` (XLA convs), ``queue_pallas`` with the Pallas kernel body
  under the interpreter (the chip's kernel logic; its compiled default off
  the chip is the fused XLA program), and ``queue_ref`` (the scatter
  oracle) each agree with a plain f32 ``jax.numpy`` forward pass written
  here, importing nothing of the engine;
- on VGG-16's topology at 1/16 of its widths, and on one wide stage
  (4x4, 256->512) and one 2x2 stage (512->512) at full width.

Tolerances: logits to rtol/atol 1e-4, because the event path sums each
membrane's charge in another order than an XLA conv (f32 rounding of
about 1e-7 of each partial sum, over at most a few thousand terms);
``events_in`` exactly, because a spike count moves only when a membrane
lies within that rounding of its threshold, which no membrane of these
seeded draws does.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import engine
from repro.kernels import ops

T = 4
HIGHEST = jax.lax.Precision.HIGHEST
#: VGG-16's conv widths at 1/16: (4, 4, 8, 8, 16 x 3, 32 x 6)
VGG16_16TH = ("4C3-4C3-P2-8C3-8C3-P2-16C3-16C3-16C3-P2-32C3-32C3-32C3-P2-"
              "32C3-32C3-32C3-P2-10")


def test_compile_plan_accepts_vgg16():
    d = configs.VGG16
    plan = engine.compile_plan(d["spec"], d["hw"], d["c"])
    assert len(plan.convs) == 13
    assert [cp.in_hw for cp in plan.convs] == [32] * 2 + [16] * 2 + [8] * 3 \
        + [4] * 3 + [2] * 3
    for cp in plan.convs[-3:]:
        assert cp.kernel > cp.in_hw and cp.fmt.n_win == 1
        assert cp.in_c == cp.out_c == 512
    assert plan.convs[-1].out_hw == 1
    assert (plan.out.n_in, plan.out.n_out) == (512, 10)
    params = sum(cp.kernel ** 2 * cp.in_c * cp.out_c + cp.out_c
                 for cp in plan.convs) + (plan.out.n_in + 1) * plan.out.n_out
    assert params == d["params"] == 14_719_818


def _init(spec, hw, c, seed):
    """Seeded He-normal weights in the engine's layout ({} at each pool)."""
    plan = engine.compile_plan(spec, hw, c)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    params = [{} for _ in range(plan.n_layers)]
    for cp in plan.convs:
        fan_in = cp.kernel * cp.kernel * cp.in_c
        params[cp.index] = {
            "w": jax.random.normal(next(keys), (cp.kernel, cp.kernel,
                                                cp.in_c, cp.out_c))
            * math.sqrt(2.0 / fan_in),
            "b": jnp.full((cp.out_c,), 0.05, jnp.float32)}
    n_in = plan.out.n_in
    params[plan.out.index] = {
        "w": jax.random.normal(next(keys), (n_in, plan.out.n_out))
        * math.sqrt(2.0 / n_in),
        "b": jnp.zeros((plan.out.n_out,), jnp.float32)}
    images = jax.random.uniform(next(keys), (2, hw, hw, c))
    return params, images


def plain_forward(spec, params, images, threshold=1.0, v_init=0.5):
    """The served net in plain jax.numpy, f32 at HIGHEST.

    The image is a constant current into the first conv; each conv adds
    its SAME convolution of the incoming spikes plus its bias to a
    membrane that starts at ``v_init`` x threshold and spikes at every step
    it is above threshold, no reset; a pool passes the OR of its window;
    the head sums its matmul over the steps plus T x its bias. Returns
    (logits, events_in per weighted layer).
    """
    B = images.shape[0]
    tokens = spec.split("-")
    raster, events = None, []
    for i, tok in enumerate(tokens[:-1]):
        if tok.startswith("P"):
            continue
        p = params[i]
        pool = int(tokens[i + 1][1:]) if tokens[i + 1].startswith("P") \
            else 0
        inputs = images[None] if raster is None else raster
        events.append(jnp.zeros((B,), jnp.int32) if raster is None
                      else raster.sum((0, 2, 3, 4)).astype(jnp.int32))
        cur = jax.vmap(lambda x: jax.lax.conv_general_dilated(
            x, p["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HIGHEST))(inputs) + p["b"]
        cur = jnp.broadcast_to(cur, (T,) + cur.shape[1:])
        v = jnp.full(cur.shape[1:], v_init * threshold)
        frames = []
        for t in range(T):
            v = v + cur[t]
            s = (v > threshold).astype(jnp.float32)
            if pool:
                _, H, W, C = s.shape
                s = s.reshape(B, H // pool, pool, W // pool, pool, C
                              ).max((2, 4))
            frames.append(s)
        raster = jnp.stack(frames)              # (T, B, H', W', C')
    head = params[-1]
    events.append(raster.sum((0, 2, 3, 4)).astype(jnp.int32))
    flat = jnp.moveaxis(raster, 0, 1).reshape(B, T, -1)
    logits = jnp.matmul(flat, head["w"], precision=HIGHEST).sum(1) \
        + T * head["b"]
    return logits, jnp.stack(events, 1)


BACKENDS = ("dense", "queue_pallas", "queue_ref")


def _served(spec, hw, c, backend, params, images, monkeypatch):
    if backend == "queue_pallas":
        # the chip's kernel body, under the interpreter
        monkeypatch.setattr(ops, "default_spike_impl",
                            lambda: "pallas_interpret")
    cfg = engine.SNNConfig(spec=spec, input_hw=hw, input_c=c, T=T,
                           mode="mttfs_cont", depth=256, v_init_frac=0.5)
    thresholds = [1.0] * len(spec.split("-"))
    return engine.infer_batch(params, thresholds, cfg, images,
                              backend=backend)


def _agree(spec, hw, c, seed, backend, monkeypatch):
    params, images = _init(spec, hw, c, seed)
    ref_logits, ref_events = plain_forward(spec, params, images)
    logits, stats = _served(spec, hw, c, backend, params, images,
                            monkeypatch)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(stats.events_in),
                                  np.asarray(ref_events))
    # the deepest event stage takes spikes in: the check is not vacuous
    assert int(np.asarray(ref_events)[:, -2].min()) > 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_vgg16_topology_matches_plain_forward(backend, monkeypatch):
    _agree(VGG16_16TH, 32, 3, 16, backend, monkeypatch)


@pytest.mark.parametrize("backend", BACKENDS)
def test_wide_stage_matches_plain_forward(backend, monkeypatch):
    """4x4, 256->512: C_out spans four 128-lane tiles."""
    _agree("256C3-512C3-10", 4, 3, 4, backend, monkeypatch)


@pytest.mark.parametrize("backend", BACKENDS)
def test_stage_on_a_map_narrower_than_its_kernel_matches(backend,
                                                         monkeypatch):
    """2x2, 512->512, 3x3 kernels: one window, most phases empty."""
    _agree("512C3-512C3-10", 2, 3, 2, backend, monkeypatch)
