"""Event-driven SNN layers (queue-in -> membrane accumulation -> queue-out).

The math identity underpinning everything (and property-tested):

    event_conv2d(AEQ(spike_map), W)  ==  conv2d(spike_map, W)     (SAME pad)

i.e. processing the sparse queue is exactly the dense convolution restricted
to the nonzero inputs — work is proportional to the number of events, which
is the accelerator's whole value proposition (Sec. 2.1.1).

The pure-JAX path below is the *reference semantics*; kernels/event_accum.py
is the Pallas TPU hot-loop with the interlaced VMEM layout. Both are tested
against the dense oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .aeq import AEQ, decode_positions
from .encoding import AEFormat

# Precision of every f32 conv and matmul the engine runs. A TPU's default
# rounds f32 operands to bf16; a membrane potential near its threshold then
# fires a step early or late, and the flip spreads through later layers (on
# the paper's cifar10 net at seeded random weights, about 45 % of the
# predictions changed). HIGHEST keeps the chip on the function the CPU runs.
PRECISION = jax.lax.Precision.HIGHEST


def event_conv2d(
    v_mem: jnp.ndarray,       # (H, W, C_out) membrane potentials (SAME geometry)
    weights: jnp.ndarray,     # (K, K, C_in, C_out)
    aeq: AEQ,
    fmt: AEFormat,
    t: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Accumulate all events of time step ``t`` into ``v_mem``.

    A spike at input position (y, x) in channel c contributes
    ``w[dy, dx, c, :]`` to output neuron (y - dy + pad, x - dx + pad) for
    every kernel offset — K*K multiplier-free vector adds per event.

    Returns (new_v_mem, n_ops) where n_ops counts scalar additions performed
    (for the energy model; invalid/out-of-bounds lanes don't count).
    """
    K = fmt.kernel
    pad = K // 2
    H, W, C_out = v_mem.shape
    C_in = aeq.words.shape[1]

    words_t = aeq.words[t]                                  # (C, K2, D)
    y, x, valid = jax.vmap(lambda w: decode_positions(fmt, w))(words_t)
    cidx = jnp.broadcast_to(
        jnp.arange(C_in, dtype=jnp.int32)[:, None, None], y.shape
    )
    y, x, valid, cidx = (a.reshape(-1) for a in (y, x, valid, cidx))

    n_ops = jnp.zeros((), jnp.int32)
    for dy in range(K):
        for dx in range(K):
            ty = y - dy + pad
            tx = x - dx + pad
            ok = valid & (ty >= 0) & (ty < H) & (tx >= 0) & (tx < W)
            wvec = weights[dy, dx][cidx]                    # (N, C_out)
            contrib = wvec * ok[:, None].astype(wvec.dtype)
            v_mem = v_mem.at[
                jnp.clip(ty, 0, H - 1), jnp.clip(tx, 0, W - 1), :
            ].add(contrib, mode="promise_in_bounds")
            n_ops = n_ops + ok.sum().astype(jnp.int32) * C_out
    return v_mem, n_ops


def event_dense(
    v_mem: jnp.ndarray,       # (N_out,)
    weights: jnp.ndarray,     # (N_in, N_out)
    spikes: jnp.ndarray,      # (N_in,) 0/1 — dense layers take the flat raster
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Event-driven fully-connected accumulation.

    Each spiking input neuron adds its weight row; the masked matmul below is
    the same arithmetic (zeros select nothing), with n_ops counting only the
    adds a spike-driven engine would issue.
    """
    v_mem = v_mem + spikes @ weights
    n_ops = (spikes > 0).sum().astype(jnp.int32) * weights.shape[1]
    return v_mem, n_ops


def spike_maxpool(
    spikes: jnp.ndarray,      # (C, H, W) 0/1 spikes at one time step
    window: int,
    latch: jnp.ndarray,       # (C, H_out, W_out) bool — already-fired outputs
    *,
    latch_once: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """OR-pooling (spike max-pool for converted nets).

    ``latch_once``: a pooling output fires only the first time any input in
    its window fires (m-TTFS spike-once semantics); with continuous emission
    (Han & Roy m-TTFS) the OR passes through every step.
    VALID pooling with stride == window (floor division), matching the paper
    models' geometry (e.g. 28 -> 9 for P3).
    """
    C, H, W = spikes.shape
    Ho, Wo = H // window, W // window
    s = spikes[:, : Ho * window, : Wo * window]
    s = s.reshape(C, Ho, window, Wo, window).max(axis=(2, 4))
    if latch_once:
        fired = (s > 0) & ~latch
    else:
        fired = s > 0
    return fired.astype(spikes.dtype), latch | (s > 0)


def spike_maxpool_hwc(
    spikes: jnp.ndarray,      # (H, W, C) 0/1 spikes at one time step
    window: int,
    latch: jnp.ndarray,       # (H_out, W_out, C) bool — already-fired outputs
    *,
    latch_once: bool = True,
    straight_through: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`spike_maxpool` in the engine's channels-last layout.

    Same OR-pooling semantics; HWC avoids the per-step transpose on the
    engine's hot path (XLA CPU/TPU convs are channels-last native).

    ``straight_through`` keeps the pooled output differentiable for the
    surrogate-gradient training path: with exact-0/1 float input spikes the
    windowed max *is* the OR (identical values), and the spike-once gate
    multiplies by ``1 - latch`` instead of masking through a boolean — the
    latch itself stays hard (bool, no gradient), matching the surrogate
    fire functions in ``core/neuron.py``.
    """
    H, W, C = spikes.shape
    Ho, Wo = H // window, W // window
    s = spikes[: Ho * window, : Wo * window, :]
    s = s.reshape(Ho, window, Wo, window, C).max(axis=(1, 3))
    if straight_through:
        fired = s * (1.0 - latch.astype(s.dtype)) if latch_once else s
        return fired, latch | (s > 0)
    if latch_once:
        fired = (s > 0) & ~latch
    else:
        fired = s > 0
    return fired.astype(spikes.dtype), latch | (s > 0)


def dense_conv_hwc(spike_map: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """Dense SAME conv of an (H, W, C) map -> (H, W, C_out), channels-last
    end to end (the engine's native layout)."""
    out = jax.lax.conv_general_dilated(
        spike_map[None].astype(weights.dtype),
        weights,
        window_strides=(1, 1),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=PRECISION,
    )
    return out[0]


def dense_conv_oracle(spike_map: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """Dense SAME conv of a (C, H, W) spike map -> (H, W, C_out). Oracle for
    event_conv2d (tests assert allclose)."""
    x = spike_map[None].astype(weights.dtype)               # NCHW
    out = jax.lax.conv_general_dilated(
        x,
        weights,                                            # HWIO
        window_strides=(1, 1),
        padding="SAME",
        dimension_numbers=("NCHW", "HWIO", "NHWC"),
        precision=PRECISION,
    )
    return out[0]
