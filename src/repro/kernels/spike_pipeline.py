"""Fused, batch-native Pallas spike pipeline: drop rule + accumulation.

PR 1 split the event path into two kernels with an HBM round-trip between
them: ``spike_compact`` (Thresholding Unit encoder: occupancy -> packed AE
words) and ``event_accum`` (queue words -> membrane charge). DeepFire2
(arXiv 2305.05187) and Gerlinghoff et al. (arXiv 2206.02495) both get their
wins from *not* doing that — the event stream stays on-chip between the
encoder and the accumulator. This module is that fusion:

    per-phase occupancy ──drop rule──▶ surviving spike maps ──drain──▶ charge
                                        (VMEM, one block per row)

Design points:

- **Batch axis in the kernel grid.** The grid is ``(N, C_in)`` where
  ``N = B * T`` — every (sample, time-step) segment is an independent grid
  row. Queue backends previously reached batch > 1 only through an outer
  ``jax.vmap`` of the whole single-sample program.
- **A vector drain over the interlaced banks.** The K² phase queues of a
  channel are the paper's memory interlacing: the events of one phase lie
  K apart, so at one kernel offset no two of them touch the same membrane
  pixel. The kernel keeps the membrane interlaced, one bank per phase, and
  adds each (phase, offset) pair's events as one masked vector update of
  ``spikes ⊗ w[dy, dx, :]`` over the whole window grid: K⁴ slab updates
  per (row, channel) cell, none per event. A phase whose queue is empty is
  skipped; that skip is the event-driven part of the work. The membrane
  is de-interlaced once, after the last input channel.
- **Same drop semantics as ``core.aeq.compact_spikes``.** Events beyond
  ``depth`` per (channel, phase) queue are dropped in window-row-major
  order (a prefix count, statically elided when ``depth >= P``), so the
  surviving event set is bit-identical to the unfused AEQ model.

Three interchangeable implementations (differentially tested):

- :func:`fused_spike_accum_pallas` — the Pallas TPU kernel described above.
- :func:`fused_spike_accum_xla`    — the same semantics as one fused XLA
  program: drop-mask the occupancy, rebuild the surviving 0/1 spike map,
  and accumulate with a single batched SAME conv (event accumulation of a
  spike raster == dense convolution of it). This is the **non-interpret
  default off-TPU** — the engine's ``queue_pallas`` backend never runs the
  Python-loop Pallas interpreter on its hot path.
- ``ref.fused_spike_accum_ref``    — pure-jnp oracle in ``kernels/ref.py``.

Inputs/outputs (all impls):

    occ     (N, C_in, K², P) int32   per-phase window occupancy (0/1); P =
                                     n_win² window positions, row-major
    weights (K, K, C_in, C_out)
    returns (N, H, W, C_out)         membrane charge ("currents") of the
                                     surviving events
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ..audit.contracts import KernelContract
from ..core.aeq import segment_keep
from ..core.snn_layers import PRECISION

# Declared resource/dtype intent, verified by ``python -m repro.audit``
# (see docs/CONTRACTS.md): fp32 accumulate (no quant path here), no host
# syncs, and the VMEM footprint below against the per-core budget.
CONTRACT = KernelContract(name="fused_spike_accum_pallas",
                          module=__name__, accum_dtype="float32")


def vmem_blocks(*, K, n_win, H, W, C_in, C_out, **_unused):
    """Per-grid-cell resident buffers as data, for ``audit.vmem``.

    Mirrors :func:`fused_spike_accum_pallas`'s VMEM BlockSpecs and scratch
    exactly — ``(name, block shape, bytes per element, double-buffered)``;
    pipelined in/out blocks are double-buffered by the Mosaic emitter. The
    per-phase event counts live in SMEM (:func:`fused_spike_accum_pallas`).
    """
    wx = _win_cols(n_win)
    tile, J = _lane_tiles(C_out)
    return [
        ("spike_block", (K * K, n_win, wx, C_in), 4, True),
        ("w_block", (K, K, J * tile), 4, True),
        ("out_block", _tiled(J, (H, W, tile)), 4, True),
        ("membrane", _tiled(J, (K, K, n_win + 2, wx, tile)), 4, False),
    ]


def _win_cols(n_win: int) -> int:
    """Window columns of a bank with its one-window halo, to whole sublanes."""
    return -(-(n_win + 2) // 8) * 8


#: lanes of a vector register: the widest C_out one membrane bank holds
LANES = 128


def _lane_tiles(C_out: int) -> tuple[int, int]:
    """(lanes a tile, tiles): C_out up to 128 is one tile of its own
    width; a wider C_out is split into 128-lane tiles (zero-padded up to
    a whole number of them)."""
    if C_out <= LANES:
        return C_out, 1
    return LANES, -(-C_out // LANES)


def _tiled(J: int, shape: tuple) -> tuple:
    """A buffer's shape with the lane-tile axis in front, when there are
    several tiles."""
    return shape if J == 1 else (J,) + shape


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
#
# Mosaic layout of one grid cell (row n, input channel c):
#
# - the spike maps of the K2 phases sit in one VMEM block (K2, n_win, wx,
#   C_in): window row leading, window column on sublanes (one zero column
#   of halo before it), channels on lanes. The block index ignores c, so
#   it is fetched once per row; channel c is picked by a lane compare and
#   a lane reduction, and broadcast back across a tile's lanes;
# - the membrane is a VMEM scratch in interlaced layout (K, K, n_win + 2,
#   wx, C_out): bank (ry, rx) holds pixels (wy * K + ry, wx * K + rx) at
#   [wy + 1, wx + 1], so every (phase, offset) update is one slab of whole
#   tiles, shifted by at most one window (a leading index for rows, a
#   sublane roll for columns) and cut by the halo;
# - a C_out wider than 128 lanes is split into J tiles of 128: membrane
#   and output block gain a leading tile axis, so each strided store of
#   the de-interlace sees a 128-lane last dim, and every slab update is
#   made once per tile, with that tile's lanes of the weights;
# - the per-(channel, phase) event counts sit in SMEM, one row per n;
# - the (K, K, C_out) weight slice is a VMEM block, one row per offset.
#
# A map narrower than the kernel (H < K) has one window (n_win = 1): only
# its phases ky < H, kx < W hold events, only its banks ry < H, rx < W are
# read, and only updates that stay in the window (no shift) reach them, so
# the rest is left out statically.

def _kernel(cnt_ref, spk_ref, w_ref, out_ref, mem_ref, *, K, n_win, H, W,
            tile, J):
    """Accumulate channel c of row n; de-interlace after the last channel.

    Each phase's events lie K apart, so for one kernel offset no two of
    them reach the same membrane pixel: the phase's whole contribution at
    that offset is one conflict-free masked vector update. A phase with no
    event is skipped.
    """
    c = pl.program_id(1)
    K2 = K * K
    pad = K // 2
    _, _, wx, C_in = spk_ref.shape
    narrow = H < K

    def at(j, *idx):  # index into tile j of the membrane or the output
        return idx if J == 1 else (j,) + idx

    def lanes(j):
        return slice(j * tile, (j + 1) * tile)

    @pl.when(c == 0)
    def _init():
        mem_ref[...] = jnp.zeros_like(mem_ref)

    lane = jax.lax.broadcasted_iota(jnp.int32, (n_win, wx, C_in), 2)
    for ph in range(K2):  # static unroll: the K2 interlaced queues
        ky, kx = divmod(ph, K)
        if narrow and (ky >= H or kx >= W):
            continue  # no pixel of the map lies in this phase

        @pl.when(cnt_ref[0, c * K2 + ph] > 0)
        def _phase(ph=ph, ky=ky, kx=kx):
            col = jnp.max(jnp.where(lane == c, spk_ref[ph], 0.0), axis=-1,
                          keepdims=True)
            spikes = jnp.broadcast_to(col, (n_win, wx, tile))
            for dx in range(K):  # static unroll: kernel offsets
                sx, rx = divmod(kx - dx + pad, K)
                if narrow and (sx or rx >= W):
                    continue
                row = pltpu.roll(spikes, sx % wx, 1) if sx else spikes
                for dy in range(K):
                    sy, ry = divmod(ky - dy + pad, K)
                    if narrow and (sy or ry >= H):
                        continue
                    for j in range(J):  # static unroll: lane tiles
                        mem_ref[at(j, ry, rx, slice(1 + sy, 1 + sy + n_win))
                                ] += row * w_ref[dy, pl.ds(dx, 1), lanes(j)]

    @pl.when(c == C_in - 1)
    def _deinterlace():
        for ry in range(K):
            for rx in range(K):
                ny, nx = -(-(H - ry) // K), -(-(W - rx) // K)
                if ny <= 0 or nx <= 0:
                    continue  # a bank that holds no pixel of the map
                for j in range(J):
                    out_ref[at(j, pl.ds(ry, ny, stride=K),
                               pl.ds(rx, nx, stride=K), slice(None))] = (
                        mem_ref[at(j, ry, rx, slice(1, 1 + ny),
                                   pl.ds(1, nx), slice(None))])


@functools.partial(jax.jit, static_argnames=(
    "K", "n_win", "depth", "H", "W", "interpret"))
def fused_spike_accum_pallas(
    occ: jnp.ndarray,      # (N, C_in, K2, P) int32 occupancy
    weights: jnp.ndarray,  # (K, K, C_in, C_out)
    *,
    K: int,
    n_win: int,
    depth: int,
    H: int,
    W: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused drop rule + accumulate, grid (N, C_in) with N = batch * time.

    The drop rule (``aeq.segment_keep``) is a prefix count over each
    (channel, phase) queue, statically elided when ``depth >= P``; the
    surviving events reach the kernel as 0/1 spike maps in its layout,
    with their counts.
    """
    N, C_in, K2, P = occ.shape
    C_out = weights.shape[-1]
    wx = _win_cols(n_win)
    tile, J = _lane_tiles(C_out)
    fired = segment_keep(occ, depth)
    counts = fired.sum(-1, dtype=jnp.int32).reshape(N, 1, C_in * K2)
    # (N, C, K2, wy, wx) -> (N, K2, wy, wx, C), one halo column before wx
    spikes = fired.reshape(N, C_in, K2, n_win, n_win).transpose(0, 2, 3, 4, 1)
    spikes = jnp.pad(spikes.astype(weights.dtype),
                     ((0, 0), (0, 0), (0, 0), (1, wx - n_win - 1), (0, 0)))
    weights = jnp.transpose(weights, (2, 0, 1, 3))
    if J * tile != C_out:
        weights = jnp.pad(weights, ((0, 0),) * 3 + ((0, J * tile - C_out),))
    out = pl.pallas_call(
        functools.partial(_kernel, K=K, n_win=n_win, H=H, W=W, tile=tile,
                          J=J),
        grid=(N, C_in),
        in_specs=[
            pl.BlockSpec((None, 1, C_in * K2), lambda n, c: (n, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, K2, n_win, wx, C_in),
                         lambda n, c: (n, 0, 0, 0, 0)),
            pl.BlockSpec((None, K, K, J * tile), lambda n, c: (c, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None,) + _tiled(J, (H, W, tile)),
                               lambda n, c: (n,) + (0,) * (3 + (J > 1))),
        out_shape=jax.ShapeDtypeStruct((N,) + _tiled(J, (H, W, tile)),
                                       weights.dtype),
        scratch_shapes=[pltpu.VMEM(_tiled(J, (K, K, n_win + 2, wx, tile)),
                                   weights.dtype)],
        interpret=interpret,
    )(counts, spikes, weights)
    if J == 1:
        return out
    # (N, J, H, W, 128) -> (N, H, W, C_out)
    out = out.transpose(0, 2, 3, 1, 4).reshape(N, H, W, J * tile)
    return out[..., :C_out]


# ---------------------------------------------------------------------------
# Compiled XLA realization (the non-interpret default off-TPU)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("K", "n_win", "depth", "H", "W"))
def fused_spike_accum_xla(
    occ: jnp.ndarray,      # (N, C_in, K2, P) int32 occupancy
    weights: jnp.ndarray,  # (K, K, C_in, C_out)
    *,
    K: int,
    n_win: int,
    depth: int,
    H: int,
    W: int,
) -> jnp.ndarray:
    """Identical semantics as one fused XLA program (batched SAME conv).

    Drops over-depth events exactly like ``compact_spikes`` (window-row-major
    per (channel, phase) queue), rebuilds the surviving 0/1 spike map, and
    accumulates it with a single conv over the fused (batch*time) axis —
    event-driven accumulation of a spike raster is dense convolution of it.
    When ``depth >= P`` no queue can ever overflow and the drop mask is
    statically elided: the fused path then costs exactly one batched conv.
    """
    N, C_in, K2, P = occ.shape
    fired = occ > 0
    if depth < P:
        slot = jnp.cumsum(fired.astype(jnp.int32), axis=-1) - 1
        fired = fired & (slot < depth)
    # inverse phase split: (N, C, ky, kx, wy, wx) -> (N, y, x, C)
    m = fired.reshape(N, C_in, K, K, n_win, n_win).astype(weights.dtype)
    m = m.transpose(0, 4, 2, 5, 3, 1).reshape(N, n_win * K, n_win * K, C_in)
    m = m[:, :H, :W, :]
    return jax.lax.conv_general_dilated(
        m, weights, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=PRECISION)
