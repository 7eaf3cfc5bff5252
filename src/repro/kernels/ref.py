"""Pure-jnp oracles for every Pallas kernel (the correctness contracts).

tests/test_kernels.py sweeps shapes/dtypes and asserts allclose between each
kernel (interpret=True on CPU) and its oracle here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def event_accum_ref(words, counts, weights, v_mem, *, K, n_win, bits):
    """Oracle for kernels.event_accum: decode events densely, then SAME-conv.

    words (C_in, K2, D), counts (C_in, K2), weights (K,K,C_in,C_out),
    v_mem (H, W, C_out). Decodes the queues to a dense spike map and adds
    conv2d(spikes, weights) — the identity the whole design rests on.
    """
    C_in, K2, D = words.shape
    H, W, C_out = v_mem.shape
    mask = (1 << bits) - 1

    i_c = (words >> bits) & mask
    j_c = words & mask
    slot = jnp.arange(D, dtype=jnp.int32)
    valid = (i_c < n_win) & (slot[None, None, :] < counts[..., None])

    ph = jnp.arange(K2, dtype=jnp.int32)[None, :, None]
    y = i_c * K + ph // K
    x = j_c * K + ph % K

    side = n_win * K
    spikes = jnp.zeros((C_in, side, side), v_mem.dtype)
    cidx = jnp.broadcast_to(jnp.arange(C_in)[:, None, None], y.shape)
    spikes = spikes.at[
        cidx.reshape(-1),
        jnp.where(valid, y, 0).reshape(-1),
        jnp.where(valid, x, 0).reshape(-1),
    ].add(valid.reshape(-1).astype(v_mem.dtype))
    spikes = spikes[:, :H, :W]

    out = jax.lax.conv_general_dilated(
        spikes[None], weights, (1, 1), "SAME",
        dimension_numbers=("NCHW", "HWIO", "NHWC"),
    )[0]
    return v_mem + out


def spike_compact_ref(occ, *, n_win, bits, depth, invalid):
    """Oracle for kernels.spike_compact: cumsum-based compaction per row."""
    R, P = occ.shape
    occ = occ > 0
    pos = jnp.arange(P, dtype=jnp.int32)
    wy, wx = pos // n_win, pos % n_win
    packed = (wy << bits) | wx

    slot = jnp.cumsum(occ.astype(jnp.int32), axis=1) - 1
    target = jnp.where(occ & (slot < depth), slot, depth)

    flat = jnp.full((R, depth + 1), invalid, jnp.int32)
    rows = jnp.broadcast_to(jnp.arange(R)[:, None], (R, P))
    flat = flat.at[rows.reshape(-1), target.reshape(-1)].set(
        jnp.broadcast_to(packed[None], (R, P)).reshape(-1)
    )
    words = flat[:, :depth]
    counts = occ.sum(axis=1).astype(jnp.int32)
    return words, counts


def fused_spike_accum_ref(occ, weights, *, K, n_win, depth, H, W):
    """Oracle for kernels.spike_pipeline.fused_spike_accum: per-event scatter.

    occ (N, C_in, K2, P) int32 occupancy, weights (K, K, C_in, C_out) ->
    (N, H, W, C_out). Applies the compact-stage drop rule explicitly (events
    beyond ``depth`` per (c, phase) queue dropped in window-row-major order),
    then accumulates each surviving event with K*K offset scatters — a
    genuinely different computation from both the Pallas kernel (in-VMEM
    queue walk) and the XLA path (masked raster + one conv).
    """
    N, C_in, K2, P = occ.shape
    C_out = weights.shape[-1]
    pad = K // 2

    fired = occ > 0
    slot = jnp.cumsum(fired.astype(jnp.int32), axis=-1) - 1
    fired = fired & (slot < depth)

    pos = jnp.arange(P, dtype=jnp.int32)
    wy, wx = pos // n_win, pos % n_win                     # (P,)
    ph = jnp.arange(K2, dtype=jnp.int32)[:, None]
    y = wy[None, :] * K + ph // K                          # (K2, P)
    x = wx[None, :] * K + ph % K

    out = jnp.zeros((N, H, W, C_out), weights.dtype)
    nidx = jnp.broadcast_to(jnp.arange(N)[:, None, None, None], fired.shape)
    cidx = jnp.broadcast_to(jnp.arange(C_in)[None, :, None, None], fired.shape)
    yb = jnp.broadcast_to(y[None, None], fired.shape)
    xb = jnp.broadcast_to(x[None, None], fired.shape)
    nf, cf, yf, xf, ff = (a.reshape(-1) for a in (nidx, cidx, yb, xb, fired))
    for dy in range(K):
        for dx in range(K):
            ty = yf - dy + pad
            tx = xf - dx + pad
            ok = ff & (ty >= 0) & (ty < H) & (tx >= 0) & (tx < W)
            contrib = weights[dy, dx][cf] * ok[:, None].astype(weights.dtype)
            out = out.at[
                nf, jnp.clip(ty, 0, H - 1), jnp.clip(tx, 0, W - 1), :
            ].add(contrib, mode="promise_in_bounds")
    return out


def fused_spike_accum_quant_ref(occ, weights, *, K, n_win, depth, H, W,
                                weight_bits=8):
    """Quantized-weight variant of :func:`fused_spike_accum_ref`.

    Same event set and scatter order; the weights are symmetric-quantized to
    ``weight_bits`` integers, every contribution is accumulated *exactly* in
    int32, and one fp32 dequant scales the result — the ``quant_matmul``
    contract (int8 operands, exact integer product, fp32 dequant) applied to
    the event accumulate. This is the parity anchor for the sparse
    realization's ``weight_bits`` path.
    """
    from ..core.quantization import quantize_symmetric

    N, C_in, K2, P = occ.shape
    pad = K // 2
    w_q, w_scale = quantize_symmetric(weights, weight_bits)
    w_i = w_q.astype(jnp.int32)
    C_out = weights.shape[-1]

    fired = occ > 0
    slot = jnp.cumsum(fired.astype(jnp.int32), axis=-1) - 1
    fired = fired & (slot < depth)

    pos = jnp.arange(P, dtype=jnp.int32)
    wy, wx = pos // n_win, pos % n_win
    ph = jnp.arange(K2, dtype=jnp.int32)[:, None]
    y = wy[None, :] * K + ph // K
    x = wx[None, :] * K + ph % K

    acc = jnp.zeros((N, H, W, C_out), jnp.int32)
    nidx = jnp.broadcast_to(jnp.arange(N)[:, None, None, None], fired.shape)
    cidx = jnp.broadcast_to(jnp.arange(C_in)[None, :, None, None], fired.shape)
    yb = jnp.broadcast_to(y[None, None], fired.shape)
    xb = jnp.broadcast_to(x[None, None], fired.shape)
    nf, cf, yf, xf, ff = (a.reshape(-1) for a in (nidx, cidx, yb, xb, fired))
    for dy in range(K):
        for dx in range(K):
            ty = yf - dy + pad
            tx = xf - dx + pad
            ok = ff & (ty >= 0) & (ty < H) & (tx >= 0) & (tx < W)
            contrib = w_i[dy, dx][cf] * ok[:, None].astype(jnp.int32)
            acc = acc.at[
                nf, jnp.clip(ty, 0, H - 1), jnp.clip(tx, 0, W - 1), :
            ].add(contrib, mode="promise_in_bounds")
    return acc.astype(jnp.float32) * w_scale


def quant_matmul_ref(a_q, b_q, a_scale, b_scale):
    """Oracle for kernels.quant_matmul: exact int32 product, fp32 dequant."""
    prod = jnp.matmul(
        a_q.astype(jnp.int32), b_q.astype(jnp.int32)
    ).astype(jnp.float32)
    return prod * (a_scale * b_scale)

