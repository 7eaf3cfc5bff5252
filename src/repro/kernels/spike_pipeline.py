"""Fused, batch-native Pallas spike pipeline: drop rule + accumulation.

PR 1 split the event path into two kernels with an HBM round-trip between
them: ``spike_compact`` (Thresholding Unit encoder: occupancy -> packed AE
words) and ``event_accum`` (queue words -> membrane charge). DeepFire2
(arXiv 2305.05187) and Gerlinghoff et al. (arXiv 2206.02495) both get their
wins from *not* doing that — the event stream stays on-chip between the
encoder and the accumulator. This module is that fusion:

    per-phase occupancy ──drop rule──▶ surviving spike maps ──MXU──▶ charge
                                        (VMEM, one block per row)

Design points:

- **Batch axis in the kernel grid.** The grid is ``(J, N)`` where
  ``N = B * T`` — every (sample, time-step) segment is an independent grid
  row — and ``J`` is the number of 128-lane tiles of C_out. The tile is the
  outer axis, so a tile's weights stay in VMEM while every row runs.
- **The channel sum on the MXU.** The K² phase queues of a channel are the
  paper's memory interlacing: the events of one phase lie K apart, so at
  one kernel offset no two of them touch the same membrane pixel. A grid
  cell holds one row's surviving 0/1 spike maps of every phase with the
  input channels on lanes, and for each kernel offset (dy, dx) contracts
  them with ``w[dy, dx]`` in one matmul: the charge of every phase at that
  offset, summed over all input channels. Each phase's slice of it is then
  one conflict-free slab update of an interlaced membrane, one bank per
  phase: K⁴ slab updates per grid cell, none per channel or event. An
  empty queue costs zero lanes of the matmul. The membrane is
  de-interlaced at the end of the cell.
- **f32 from bf16 parts.** The spikes are exactly 0 or 1, and the weights
  reach the kernel as three bf16 parts whose sum is the f32 weight, laid
  side by side on the contraction axis: every MXU product is exact and the
  MXU accumulates in f32, so the contraction is as exact as the f32 conv
  (Mosaic's default precision rounds f32 operands to one bf16 part).
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
    pipelined in/out blocks are double-buffered by the Mosaic emitter.
    """
    wx = _win_cols(n_win)
    tile, _ = _lane_tiles(C_out)
    depth_c = _contraction(C_in)
    return [
        ("spike_block", (_live_phases(K, H, W) * n_win * wx, depth_c), 2,
         True),
        ("w_block", (K, K, depth_c, tile), 2, True),
        ("out_block", (H, W, tile), 4, True),
        ("membrane", (K, K, n_win + 2, wx, tile), 4, False),
    ]


def _win_cols(n_win: int) -> int:
    """Window columns of a bank with its one-window halo, to whole sublanes."""
    return -(-(n_win + 2) // 8) * 8


#: lanes of a vector register: the widest C_out one membrane bank holds,
#: and the MXU's contraction width
LANES = 128

#: bf16 parts of an f32 weight: 3 x 8 significand bits hold its 24
W_PARTS = 3


def _lane_tiles(C_out: int) -> tuple[int, int]:
    """(lanes a tile, tiles): C_out up to 128 is one tile of its own
    width; a wider C_out is split into 128-lane tiles (zero-padded up to
    a whole number of them)."""
    if C_out <= LANES:
        return C_out, 1
    return LANES, -(-C_out // LANES)


def _contraction(C_in: int) -> int:
    """Contraction width: a copy of the channels per weight part,
    zero-padded to whole MXU passes."""
    return -(-W_PARTS * C_in // LANES) * LANES


def _live_phases(K: int, H: int, W: int) -> int:
    """Phases the spike block holds: all K², or on a map narrower than the
    kernel the prefix up to its last live phase (ky < H, kx < W)."""
    return K * K if H >= K else (H - 1) * K + W


def _bf16_parts(weights: jnp.ndarray) -> jnp.ndarray:
    """(..., C_in, C_out) f32 -> (..., 3 C_in, C_out) bf16 whose three C_in
    blocks sum to the weights exactly: each part is the top 16 bits of what
    the parts before it left.

    The cut is made on the bits, not by a round trip through bf16: XLA may
    keep an f32 -> bf16 -> f32 round trip in f32 (excess precision), which
    would leave the second and third parts zero.
    """
    parts, rest = [], weights.astype(jnp.float32)
    for _ in range(W_PARTS):
        bits = jax.lax.bitcast_convert_type(rest, jnp.uint32)
        part = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
        parts.append(part.astype(jnp.bfloat16))
        rest = rest - part
    return jnp.concatenate(parts, axis=-2)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
#
# Mosaic layout of one grid cell (lane tile j, row n):
#
# - the spike block (phases * n_win * wx, contraction) holds the row's
#   surviving 0/1 spike maps in bf16: phase, window row and window column
#   (one zero column of halo before it, to whole sublanes) on sublanes,
#   and the input channels on lanes, once per weight part, zero-padded to
#   whole 128-lane passes. Its block index ignores j, and J is small;
# - the weight block (K, K, contraction, tile) holds tile j's lanes of the
#   three bf16 weight parts (stacked on the contraction axis, matching the
#   spike copies). Its block index ignores n, so it is fetched once per
#   tile, not once per row;
# - per kernel offset, one matmul of the spike block with that offset's
#   weights gives every phase's charge at the offset, summed over the
#   input channels and the weight parts in f32;
# - the membrane is a VMEM scratch in interlaced layout (K, K, n_win + 2,
#   wx, tile): bank (ry, rx) holds pixels (wy * K + ry, wx * K + rx) at
#   [wy + 1, wx + 1], so each phase's slice of an offset's charge is one
#   slab of whole tiles, shifted by at most one window (a leading index for
#   rows, a sublane roll for columns) and cut by the halo;
# - a C_out wider than 128 lanes is split into J tiles of 128 on the grid:
#   each strided store of the de-interlace sees a 128-lane last dim, and
#   the output block is tile j's lanes of the (N, H, W, C_out) output.
#
# A map narrower than the kernel (H < K) has one window (n_win = 1): only
# its phases ky < H, kx < W hold events (the spike block stops at the last
# of them), only its banks ry < H, rx < W are read, and only updates that
# stay in the window (no shift) reach them, so the rest is left out
# statically.

def _kernel(spk_ref, w_ref, out_ref, mem_ref, *, K, n_win, H, W):
    """Contract row n's spike maps with tile j's weights, offset by offset;
    add each phase's share to its bank, then de-interlace."""
    pad = K // 2
    wx, tile = mem_ref.shape[-2:]
    win = n_win * wx  # spike-block rows a phase
    narrow = H < K

    mem_ref[...] = jnp.zeros_like(mem_ref)
    spikes = spk_ref[...]
    for dy in range(K):  # static unroll: kernel offsets
        for dx in range(K):
            charge = jnp.dot(spikes, w_ref[dy, dx],
                             preferred_element_type=jnp.float32)
            for ph in range(spk_ref.shape[0] // win):  # the live phases
                ky, kx = divmod(ph, K)
                sy, ry = divmod(ky - dy + pad, K)
                sx, rx = divmod(kx - dx + pad, K)
                if narrow and (kx >= W or sy or sx or ry >= H or rx >= W):
                    continue  # reaches no pixel of the map
                part = charge[ph * win:(ph + 1) * win].reshape(n_win, wx,
                                                               tile)
                if sx:
                    part = pltpu.roll(part, sx % wx, 1)
                mem_ref[ry, rx, 1 + sy:1 + sy + n_win] += part

    for ry in range(K):
        for rx in range(K):
            ny, nx = -(-(H - ry) // K), -(-(W - rx) // K)
            if ny <= 0 or nx <= 0:
                continue  # a bank that holds no pixel of the map
            out_ref[pl.ds(ry, ny, stride=K), pl.ds(rx, nx, stride=K), :] = (
                mem_ref[ry, rx, 1:1 + ny, pl.ds(1, nx), :])


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
    """Fused drop rule + accumulate, grid (J, N) with N = batch * time.

    The drop rule (``aeq.segment_keep``) is a prefix count over each
    (channel, phase) queue, statically elided when ``depth >= P``; the
    surviving events reach the kernel as 0/1 spike maps in its layout.
    """
    N, C_in, K2, P = occ.shape
    C_out = weights.shape[-1]
    wx = _win_cols(n_win)
    tile, J = _lane_tiles(C_out)
    n_ph = _live_phases(K, H, W)
    depth_c = _contraction(C_in)
    fired = segment_keep(occ, depth)[:, :, :n_ph]
    # (N, C, ph, wy, wx) -> (N, ph, wy, wx, C), one halo column before wx
    spikes = fired.reshape(N, C_in, n_ph, n_win, n_win).transpose(
        0, 2, 3, 4, 1)
    spikes = jnp.concatenate([spikes.astype(jnp.bfloat16)] * W_PARTS, -1)
    spikes = jnp.pad(spikes, ((0, 0),) * 3 + ((1, wx - n_win - 1),
                                              (0, depth_c - W_PARTS * C_in)))
    spikes = spikes.reshape(N, n_ph * n_win * wx, depth_c)
    w = jnp.pad(_bf16_parts(weights),
                ((0, 0), (0, 0), (0, depth_c - W_PARTS * C_in),
                 (0, J * tile - C_out)))
    out = pl.pallas_call(
        functools.partial(_kernel, K=K, n_win=n_win, H=H, W=W),
        grid=(J, N),
        in_specs=[
            pl.BlockSpec((None,) + spikes.shape[1:], lambda j, n: (n, 0, 0)),
            pl.BlockSpec((K, K, depth_c, tile), lambda j, n: (0, 0, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, H, W, tile), lambda j, n: (n, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((N, H, W, J * tile), weights.dtype),
        scratch_shapes=[pltpu.VMEM((K, K, n_win + 2, wx, tile),
                                   jnp.float32)],
        interpret=interpret,
    )(spikes, w)
    return out if J * tile == C_out else out[..., :C_out]


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
