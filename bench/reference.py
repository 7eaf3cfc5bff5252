"""The plain reference: the served SNN in straightforward jax.numpy.

It follows the paper's converted-SNN dynamics as the configuration states
them and imports nothing of the program:

- the first conv stage is fed the image as a constant current, the same
  at every time step;
- each conv stage adds its input current (conv of the incoming spikes,
  plus the bias) to a membrane that starts at ``v_init`` x threshold, and
  a neuron spikes at every step its membrane is above threshold, with no
  reset (``mttfs_cont``);
- a pool after a conv passes the OR of its window at every step;
- the head sums the final spikes' matmul over the time steps, plus T x
  its bias.

It also makes what both sides are fed: the weights, from the seed, on the
device in one jitted call, and the request images. Everything is float32;
``precision`` is that of every conv and matmul (HIGHEST is what the
configurations state; a lower one is the control).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from work import Net


def seed_words(seed: int) -> tuple[int, int]:
    """Two 32-bit words from a seed of any size (the driver's exceed 2**31)."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return int(a), int(b)


#: the draw every seed's weights and images are a reordering of
BASE_SEED = 0


def _key(seed: int):
    a, b = seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(a), b)


def make_params(net: Net, seed: int):
    """He-normal weights and the configurations' biases, made on the device.

    Conv stages: w ~ N(0, 2 / fan_in), bias 0.05; the head: w ~ N(0, 2 /
    n_in), bias 0. Every seed gets the same draw (``BASE_SEED``) with the
    output channels of each conv stage in an order of its own, and the
    next layer's inputs reordered to match: the same net up to the order
    of its channels, so every seed fires the same spikes and gives the
    event path the same work, summed in another order. One jitted call;
    the result is a list of {"w", "b"} per weighted layer, convs in order,
    then the head.
    """
    return _init(net)(_key(BASE_SEED), _key(seed))


@functools.lru_cache(maxsize=None)
def _init(net: Net):
    @jax.jit
    def init(key, order_key):
        keys = jax.random.split(key, len(net.stages) + 1)
        orders = jax.random.split(order_key, len(net.stages))
        layers, prev = [], None
        for s, k, o in zip(net.stages, keys, orders):
            fan_in = s.k * s.k * s.c_in
            w = jax.random.normal(k, (s.k, s.k, s.c_in, s.c_out),
                                  jnp.float32) * math.sqrt(2.0 / fan_in)
            if prev is not None:
                w = w[:, :, prev, :]
            prev = jax.random.permutation(o, s.c_out)
            layers.append({"w": w[..., prev],
                           "b": jnp.full((s.c_out,), 0.05, jnp.float32)})
        last = net.stages[-1]
        w = jax.random.normal(keys[-1], (net.head_in, net.head_out),
                              jnp.float32) * math.sqrt(2.0 / net.head_in)
        w = w.reshape(last.out_hw, last.out_hw, last.c_out, net.head_out)
        layers.append({"w": w[:, :, prev, :].reshape(net.head_in,
                                                     net.head_out),
                       "b": jnp.zeros((net.head_out,), jnp.float32)})
        return layers

    return init


def _shape_mask(rng, kind: int, hw: int) -> np.ndarray:
    yy, xx = np.mgrid[0:hw, 0:hw].astype(np.float32)
    cy, cx = rng.uniform(hw * 0.35, hw * 0.65, size=2)
    r = rng.uniform(hw * 0.2, hw * 0.38)
    d2 = (yy - cy) ** 2 + (xx - cx) ** 2
    ay, ax = abs(yy - cy), abs(xx - cx)
    masks = (
        d2 < r * r,                                            # disc
        (ay < r) & (ax < r),                                   # square
        (ay + ax) < r,                                         # diamond
        (ay < r / 3) | (ax < r / 3),                           # cross
        (d2 > (r * 0.5) ** 2) & (d2 < r * r),                  # ring
        ((yy - cy) > -r) & ((yy - cy) < 0) & (ax < (yy - cy + r)),
        (np.sin(yy / 2) * np.sin(xx / 2)) > 0.3,               # texture
        (ay < r) & (ax < r / 3),                               # bar
        (ay < r / 3) & (ax < r),                               # bar
        (((yy + xx) % 8) < 3) & (d2 < r * r),                  # stripes
    )
    return masks[kind].astype(np.float32)


def make_images(n: int, seed: int, hw: int = 32) -> np.ndarray:
    """CIFAR-like images: 10 shape and texture classes, coloured, noisy.

    (n, hw, hw, 3) float32 in [0, 1]: the ``BASE_SEED`` draw of ``n``
    images, in an order drawn from the seed.
    """
    rng = np.random.default_rng(list(seed_words(BASE_SEED)))
    imgs = np.empty((n, hw, hw, 3), np.float32)
    for i, kind in enumerate(rng.integers(0, 10, size=n)):
        mask = _shape_mask(rng, int(kind), hw)
        bg = rng.uniform(0.0, 0.45, size=3).astype(np.float32)
        fg = rng.uniform(0.55, 1.0, size=3).astype(np.float32)
        img = bg[None, None] + mask[..., None] * (fg - bg)[None, None]
        img += rng.normal(0, 0.1, (hw, hw, 3)).astype(np.float32)
        imgs[i] = np.clip(img, 0, 1)
    order = np.random.default_rng(list(seed_words(seed))).permutation(n)
    return imgs[order]


def _conv(x, w, precision):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision)


def _matmul(x, w, precision):
    return jnp.matmul(x, w, precision=precision)


def _pool(s, win):
    B, H, W, C = s.shape
    ho, wo = H // win, W // win
    s = s[:, :ho * win, :wo * win]
    return s.reshape(B, ho, win, wo, win, C).max(axis=(2, 4))


@functools.lru_cache(maxsize=None)
def forward_fn(net: Net, T: int, threshold: float, v_init: float,
               precision: str):
    """Jitted (params, images (B, H, W, C)) -> (logits (B, n), events (B, L)).

    ``events[:, i]`` counts the spikes that weighted layer ``i`` takes in
    over all time steps (0 for the analog first stage); L is the number of
    conv stages plus the head.
    """
    prec = jax.lax.Precision[precision]

    @jax.jit
    def run(params, images):
        B = images.shape[0]
        events = []
        raster = None                      # T arrays of (B, H, W, C) spikes
        for s, p in zip(net.stages, params):
            if s.analog:
                c = _conv(images, p["w"], prec) + p["b"]
                currents = [c] * T
                events.append(jnp.zeros((B,), jnp.int32))
            else:
                events.append(sum(r.sum((1, 2, 3)) for r in raster)
                              .astype(jnp.int32))
                c = _conv(jnp.concatenate(raster), p["w"], prec) + p["b"]
                currents = jnp.split(c, T)
            v = jnp.full(currents[0].shape, v_init * threshold, jnp.float32)
            raster = []
            for cur in currents:
                v = v + cur
                spikes = (v > threshold).astype(jnp.float32)
                raster.append(_pool(spikes, s.pool) if s.pool else spikes)
        head = params[len(net.stages)]
        events.append(sum((r > 0).sum((1, 2, 3)) for r in raster)
                      .astype(jnp.int32))
        flat = jnp.stack([r.reshape(B, -1) for r in raster], 1)
        logits = _matmul(flat, head["w"], prec).sum(1) + head["b"] * T
        return logits, jnp.stack(events, 1)

    return run


def forward(net: Net, params, images, *, T: int, threshold: float,
            v_init: float, precision: str = "HIGHEST", block: int = 64):
    """The reference over ``images`` in blocks of ``block`` rows (numpy out)."""
    run = forward_fn(net, T, threshold, v_init, precision)
    logits, events = [], []
    for i in range(0, len(images), block):
        chunk = np.asarray(images[i:i + block], np.float32)
        n = len(chunk)
        if n < block:                       # one compiled shape per block
            chunk = np.concatenate(
                [chunk, np.zeros((block - n,) + chunk.shape[1:], np.float32)])
        lg, ev = run(params, chunk)
        logits.append(np.asarray(lg)[:n])
        events.append(np.asarray(ev)[:n])
    return np.concatenate(logits), np.concatenate(events)
