"""Fused, batch-native Pallas spike pipeline: compaction + accumulation.

PR 1 split the event path into two kernels with an HBM round-trip between
them: ``spike_compact`` (Thresholding Unit encoder: occupancy -> packed AE
words) and ``event_accum`` (queue words -> membrane charge). DeepFire2
(arXiv 2305.05187) and Gerlinghoff et al. (arXiv 2206.02495) both get their
wins from *not* doing that — the event stream stays on-chip between the
encoder and the accumulator. This module is that fusion:

    per-phase occupancy ──compact──▶ AE words in SMEM ──accumulate──▶ charge
                          (never leaves the chip)

Design points (vs. the unfused kernels):

- **Batch axis in the kernel grid.** The grid is ``(N, C_in)`` where
  ``N = B * T`` — every (sample, time-step) segment is an independent grid
  row. Queue backends previously reached batch > 1 only through an outer
  ``jax.vmap`` of the whole single-sample program.
- **Double-buffered fixed-depth segments.** The queue depth ``D`` is split
  into segments of ``seg`` words held in a ``2·K²·seg``-word SMEM scratch:
  while segment ``s`` drains into the membrane map, segment ``s+1`` is
  compacted into the other buffer — the paper's Fig. 3 segmented AEQ as a
  software pipeline. The packed words never materialize in HBM.
- **Same drop semantics as ``core.aeq.compact_spikes``.** Events beyond
  ``depth`` per (channel, phase) queue are dropped in window-row-major
  order, so overflow counts and the surviving event set are bit-identical
  to the unfused AEQ model.

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
from ..core.snn_layers import PRECISION

# Declared resource/dtype intent, verified by ``python -m repro.audit``
# (see docs/CONTRACTS.md): fp32 accumulate (no quant path here), no host
# syncs, and the VMEM footprint below against the per-core budget.
CONTRACT = KernelContract(name="fused_spike_accum_pallas",
                          module=__name__, accum_dtype="float32")


def vmem_blocks(*, K, H, W, C_out, **_unused):
    """Per-grid-cell resident buffers as data, for ``audit.vmem``.

    Mirrors :func:`fused_spike_accum_pallas`'s VMEM BlockSpecs exactly —
    ``(name, block shape, bytes per element, double-buffered)``; pipelined
    in/out blocks are double-buffered by the Mosaic emitter. The occupancy
    block and the queue segments live in SMEM (:func:`kernel_specs`).
    """
    return [
        ("w_block", (K, K, C_out), 4, True),
        ("out_block", (H, W, C_out), 4, True),
    ]


def _default_seg(depth: int, n_win: int) -> int:
    """Segment length: <= depth, <= the max events a phase can hold (P)."""
    return max(1, min(64, depth, n_win * n_win))


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
#
# Mosaic layout of one grid cell:
#
# - the (K2, P) occupancy block sits in SMEM, flattened to (1, K2*P) so its
#   two minor dimensions are full: the encoder reads it one scalar at a time
#   (a vector walk over positions would be a load at a dynamic lane offset);
# - the AE queue segments are SMEM scratch, written and read as scalars;
# - the (K, K, C_out) weight slice and the (H, W, C_out) membrane map are
#   VMEM blocks whose two minor dimensions are full, and each event updates
#   one C_out-wide row of the map.

def _pipeline(occ_ref, w_ref, cur_ref, buf_ref, *, n_pos, n_seg, K, n_win,
              bits, depth, seg, H, W, invalid):
    """Compact + accumulate one (sample*step, channel) cell.

    ``n_pos`` bounds the encoder's position walk and ``n_seg(totals)`` the
    segment loop; both may be traced scalars (the gated kernel in
    ``spike_sparse`` passes occupancy-derived bounds).
    """
    K2 = K * K
    P = n_win * n_win
    pad = K // 2
    mask = (1 << bits) - 1

    def slot(bs, ph, i):
        return (bs * K2 + ph) * seg + i

    def fill(s, bs):
        """Compact events with queue slot in [s*seg, (s+1)*seg) into buf[bs].

        Sequential append with a running per-phase count — the hardware
        queue write pointer, exactly as in kernels/spike_compact.py, but
        the destination is on-chip scratch instead of an HBM output.
        Returns the per-phase totals capped at ``depth`` (identical to
        AEQ.counts for this segment set): the drain-side liveness bound.
        """
        base = s * seg

        def clear(i, _):
            for ph in range(K2):
                buf_ref[slot(bs, ph, i)] = jnp.int32(invalid)
            return 0

        jax.lax.fori_loop(0, seg, clear, 0)

        def body(p, cnt):
            word = ((p // n_win) << bits) | (p % n_win)
            out = []
            for ph in range(K2):  # static unroll: the K2 interlaced queues
                fired = occ_ref[0, ph * P + p] > 0
                sl = cnt[ph] - base

                @pl.when(fired & (sl >= 0) & (sl < seg) & (cnt[ph] < depth))
                def _append():
                    buf_ref[slot(bs, ph, sl)] = word

                out.append(cnt[ph] + fired.astype(jnp.int32))
            return tuple(out)

        cnt = jax.lax.fori_loop(0, n_pos, body,
                                tuple(jnp.int32(0) for _ in range(K2)))
        return tuple(jnp.minimum(c, depth) for c in cnt)

    def drain(s, bs, totals):
        """Accumulate segment ``s`` (resident in buf[bs]) into the charge map."""
        base = s * seg

        def dbody(d, _):
            for ph in range(K2):  # static unroll: one event per phase, no
                ky, kx = ph // K, ph % K  # write conflicts (interlacing)
                word = buf_ref[slot(bs, ph, d)]
                i_c = (word >> bits) & mask
                j_c = word & mask
                y = i_c * K + ky
                x = j_c * K + kx

                @pl.when((base + d < totals[ph]) & (i_c < n_win))
                def _event():
                    for dy in range(K):  # static unroll: kernel offsets
                        for dx in range(K):
                            ty = y - dy + pad
                            tx = x - dx + pad

                            @pl.when((ty >= 0) & (ty < H)
                                     & (tx >= 0) & (tx < W))
                            def _add():
                                cur_ref[ty, pl.ds(tx, 1), :] += (
                                    w_ref[dy, pl.ds(dx, 1), :])
            return 0

        jax.lax.fori_loop(0, seg, dbody, 0)

    # software pipeline over double-buffered segments: compact s+1 while s
    # drains (on hardware the fill is the encoder writing ahead of the
    # accumulator; sequentialized here, the structure is what lowers)
    totals = fill(0, 0)

    def sbody(s, _):
        bs = jax.lax.rem(s, 2)

        @pl.when(s + 1 < n_seg(totals))
        def _prefetch():
            fill(s + 1, jax.lax.rem(s + 1, 2))

        drain(s, bs, totals)
        return 0

    jax.lax.fori_loop(0, n_seg(totals), sbody, 0)


def _kernel(occ_ref, w_ref, cur_ref, buf_ref, *,
            K, n_win, bits, depth, seg, H, W, invalid):
    """One grid step: all events of one (sample*step, channel) queue set."""
    P = n_win * n_win
    n_seg = -(-min(depth, P) // seg)  # ceil; slots beyond P never fill

    @pl.when(pl.program_id(1) == 0)
    def _init():
        cur_ref[...] = jnp.zeros_like(cur_ref)

    _pipeline(occ_ref, w_ref, cur_ref, buf_ref, n_pos=P,
              n_seg=lambda totals: n_seg, K=K, n_win=n_win, bits=bits,
              depth=depth, seg=seg, H=H, W=W, invalid=invalid)


def kernel_specs(N, C_in, K, P, H, W, C_out, seg):
    """BlockSpecs and scratch shared by both Pallas realizations.

    The wrapper passes the occupancy as (N, C_in, 1, K2*P) and the weights
    as (C_in, K, K, C_out) so that every block's two minor dimensions are
    the array's own.
    """
    K2 = K * K
    in_specs = [
        pl.BlockSpec((None, None, 1, K2 * P), lambda n, c: (n, c, 0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((None, K, K, C_out), lambda n, c: (c, 0, 0, 0)),
    ]
    out_spec = pl.BlockSpec((None, H, W, C_out), lambda n, c: (n, 0, 0, 0))
    scratch = [pltpu.SMEM((2 * K2 * seg,), jnp.int32)]
    return in_specs, out_spec, scratch


def kernel_operands(occ, weights):
    """(N, C_in, K2, P) occupancy and HWIO weights in the kernels' layout."""
    N, C_in, K2, P = occ.shape
    return (occ.reshape(N, C_in, 1, K2 * P),
            jnp.transpose(weights, (2, 0, 1, 3)))


@functools.partial(jax.jit, static_argnames=(
    "K", "n_win", "bits", "depth", "seg", "H", "W", "invalid", "interpret"))
def fused_spike_accum_pallas(
    occ: jnp.ndarray,      # (N, C_in, K2, P) int32 occupancy
    weights: jnp.ndarray,  # (K, K, C_in, C_out)
    *,
    K: int,
    n_win: int,
    bits: int,
    depth: int,
    H: int,
    W: int,
    invalid: int,
    seg: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused compact+accumulate, grid (N, C_in) with N = batch * time."""
    N, C_in, K2, P = occ.shape
    C_out = weights.shape[-1]
    seg = _default_seg(depth, n_win) if seg is None else min(seg, depth)
    in_specs, out_spec, scratch = kernel_specs(N, C_in, K, P, H, W, C_out,
                                               seg)
    return pl.pallas_call(
        functools.partial(_kernel, K=K, n_win=n_win, bits=bits, depth=depth,
                          seg=seg, H=H, W=W, invalid=invalid),
        grid=(N, C_in),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((N, H, W, C_out), weights.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*kernel_operands(occ, weights))


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
