"""Occupancy-gated sparse spike pipeline: work proportional to events.

The fused pipeline (``spike_pipeline.py``) made the event path *compiled*,
but not *sparse*: its realizations do the same work per ``(sample*step,
channel)`` grid cell whatever the queue occupancy, short of skipping an
empty phase, so latency is nearly flat in spike rate — the very thing the
paper's event-driven argument says should not happen. This module is the
sparse realization:

1. **Event-list accumulation** (:func:`fused_spike_accum_sparse`): apply the
   AEQ drop rule, compact the surviving events into a static-capacity event
   list via a prefix-sum index map, and accumulate only those ``e_cap``
   events with K² offset scatter-adds — work ∝ ``e_cap``, not ∝ feature-map
   size. ``e_cap`` is static per compiled program; the dispatcher
   (``engine``'s ``queue_sparse`` backend) measures the true event total
   with :func:`kept_event_count`, pulls ONE scalar to the host, and rounds
   up to a power-of-two bucket (:func:`event_bucket`) so the number of
   distinct compilations stays logarithmic. This host-side *occupancy gate*
   is how a static-shape XLA program gets measured latency that drops with
   spike rate.

2. **Occupancy-gated Pallas kernel** (:func:`fused_spike_accum_sparse_pallas`):
   a double-buffered walk of fixed-depth AE queue segments held as SMEM
   scalars (one event and one C_out-wide row at a time), with per-cell
   ``pl.when`` early-exit on empty ``(row, channel)`` cells,
   occupancy-bounded fill/drain loops (traced ``fori_loop`` bounds instead
   of static worst-case ones), and a ragged dispatch path that compacts the
   ``(N, …)`` grid to only-active rows via the same prefix-sum index map
   before kernel launch (``n_rows``).

3. **Int-quantized accumulate** (``weight_bits=8``): the drain step fuses
   the seed's ``quant_matmul`` arithmetic — int8 weights, exact integer
   accumulation, one fp32 dequant of the accumulator — so the study's
   ``weight_bits`` pricing axis has a measured kernel behind it.

Every realization is pinned against the scatter oracle in ``kernels/ref.py``
(bit-exact for the fp32 event list: compaction preserves the oracle's
flattened event order, and the masked-out zero addends of the oracle cannot
perturb a float accumulation), see ``tests/test_sparse.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..audit.contracts import KernelContract, QuantContract
from ..core.quantization import quantize_symmetric

# Declared resource/dtype intent, verified by ``python -m repro.audit``
# (see docs/CONTRACTS.md): with ``weight_bits`` the accumulate is int8
# weights -> exact int32 -> ONE fp32 dequant; the dispatcher's bucket pull
# is the repo's declared 'occupancy-gate' host sync (marked in engine.py).
CONTRACT = KernelContract(name="fused_spike_accum_sparse",
                          module=__name__, accum_dtype="int32",
                          quant=QuantContract(),
                          allowed_host_syncs=("occupancy-gate",))


def vmem_blocks(*, K, H, W, C_out, **_unused):
    """Per-grid-cell VMEM buffers of the gated Pallas kernel, as data.

    The weight slice and the membrane map; the occupancy block, the queue
    segments and the gate pair (cell total + fill bound) sit in SMEM. See
    ``audit.vmem``.
    """
    return [
        ("w_block", (K, K, C_out), 4, True),
        ("out_block", (H, W, C_out), 4, True),
    ]


# ---------------------------------------------------------------------------
# The occupancy gate (host-side dispatch helpers)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("depth",))
def kept_event_count(occ: jnp.ndarray, *, depth: int) -> jnp.ndarray:
    """Total events surviving the depth-``depth`` drop rule — () int32.

    The one scalar the dispatcher pulls to the host to pick the event
    bucket. Capping per (…, phase) queue at ``depth`` mirrors
    ``aeq.compact_spikes`` exactly, so the budget can never under-count what
    the sparse accumulator must hold.
    """
    tot = (occ > 0).sum(-1)
    return jnp.minimum(tot, depth).sum().astype(jnp.int32)


def event_bucket(n_events: int, cap: int) -> int:
    """Round a host-side event count up to a power-of-two capacity.

    Buckets keep the number of distinct ``e_cap`` specializations (and thus
    jit compilations) logarithmic in the dynamic range of spike counts,
    exactly like the serving runtime's padded batch buckets. ``cap`` is the
    static worst case (every queue full), which also bounds the bucket.
    """
    n = max(int(n_events), 1)
    b = 1
    while b < n:
        b <<= 1
    return min(b, max(int(cap), 1))


def max_kept_events(occ_shape, depth: int) -> int:
    """Static worst-case surviving events for an occupancy shape."""
    n, c, k2, p = occ_shape
    return n * c * k2 * min(depth, p)


# ---------------------------------------------------------------------------
# Event-list realization (compiled XLA; work proportional to e_cap)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "K", "n_win", "depth", "H", "W", "e_cap", "weight_bits"))
def fused_spike_accum_sparse(
    occ: jnp.ndarray,      # (N, C_in, K2, P) int32 occupancy
    weights: jnp.ndarray,  # (K, K, C_in, C_out)
    *,
    K: int,
    n_win: int,
    depth: int,
    H: int,
    W: int,
    e_cap: int,
    weight_bits: int | None = None,
) -> jnp.ndarray:
    """Sparse fused compact+accumulate over an ``e_cap``-event list.

    Same drop semantics as every other realization; the accumulation runs
    over exactly ``e_cap`` compacted event slots (padded slots contribute
    strict zeros), so the dominant cost — ``K² · e_cap`` scatter-adds of
    C_out-wide rows — scales with occupancy instead of geometry. The caller
    must pass ``e_cap >= kept_event_count(occ)``; the engine's dispatcher
    guarantees it via :func:`event_bucket`.

    Compaction is order-preserving over the oracle's flattened
    ``(n, c, phase, position)`` event order and padded slots add exact
    zeros, so the fp32 output is **bit-identical** to
    ``ref.fused_spike_accum_ref`` (same addends, same order, same scatter
    loop). With ``weight_bits`` the weights are symmetric-quantized to
    integers, accumulated exactly in int32, and dequantized once in fp32 —
    the ``quant_matmul`` contract fused into the drain step; bit-identical
    to ``ref.fused_spike_accum_quant_ref``.
    """
    N, C_in, K2, P = occ.shape
    C_out = weights.shape[-1]
    pad = K // 2

    fired = occ > 0
    if depth < P:  # the drop rule; statically elided when no queue can fill
        slot = jnp.cumsum(fired.astype(jnp.int32), axis=-1) - 1
        fired = fired & (slot < depth)

    # prefix-sum index map: each surviving event's slot in the compacted
    # list (flattened row-major, i.e. the oracle's event order). Events past
    # e_cap and non-events land in a scratch slot that is dropped.
    keptf = fired.reshape(-1)
    pos = jnp.cumsum(keptf.astype(jnp.int32)) - 1
    idx = jnp.where(keptf & (pos < e_cap), pos, e_cap)
    ev = jnp.full((e_cap + 1,), -1, jnp.int32)
    ev = ev.at[idx].set(jnp.arange(keptf.shape[0], dtype=jnp.int32))
    ev = ev[:e_cap]                                   # (e_cap,) flat or -1

    valid = ev >= 0
    f = jnp.maximum(ev, 0)
    p_ = f % P
    ph = (f // P) % K2
    c = (f // (P * K2)) % C_in
    n = f // (P * K2 * C_in)
    y = (p_ // n_win) * K + ph // K
    x = (p_ % n_win) * K + ph % K

    if weight_bits is not None:
        w_q, w_scale = quantize_symmetric(weights, weight_bits)
        w_use = w_q.astype(jnp.int32)
        acc = jnp.zeros((N, H, W, C_out), jnp.int32)
        ok_dtype = jnp.int32
    else:
        w_use = weights
        acc = jnp.zeros((N, H, W, C_out), weights.dtype)
        ok_dtype = weights.dtype

    for dy in range(K):
        for dx in range(K):
            ty = y - dy + pad
            tx = x - dx + pad
            ok = valid & (ty >= 0) & (ty < H) & (tx >= 0) & (tx < W)
            contrib = w_use[dy, dx][c] * ok[:, None].astype(ok_dtype)
            acc = acc.at[
                n, jnp.clip(ty, 0, H - 1), jnp.clip(tx, 0, W - 1), :
            ].add(contrib, mode="promise_in_bounds")

    if weight_bits is not None:
        return acc.astype(jnp.float32) * w_scale
    return acc


# ---------------------------------------------------------------------------
# Occupancy-gated Pallas kernel (per-cell early exit + ragged row dispatch)
# ---------------------------------------------------------------------------

# The scalar event walk: the AE queues are filled and drained one SMEM
# scalar at a time, bounded by the gate's traced per-cell counts.
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
    segment loop; both are traced scalars derived from the cell's gate.
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


def _default_seg(depth: int, n_win: int) -> int:
    """Segment length: <= depth, <= the max events a phase can hold (P)."""
    return max(1, min(64, depth, n_win * n_win))


def _sparse_kernel(occ_ref, w_ref, gate_ref, cur_ref, buf_ref, *,
                   K, n_win, bits, depth, seg, H, W, invalid):
    """The scalar segment walk of :func:`_pipeline` under occupancy gates.

    ``gate_ref`` is this cell's SMEM pair (total events, 1 + last active
    position). Against an ungated walk over every position and segment:

    - the whole fill/drain pipeline sits under ``pl.when(cell_total > 0)``,
      so an empty ``(row, channel)`` grid cell costs only the accumulator
      init;
    - the fill loop walks positions ``[0, pmax)`` instead of all P;
    - the segment loop walks only the segments the deepest phase queue
      actually fills (a traced ``fori_loop`` bound), instead of the static
      worst case ``ceil(min(depth, P) / seg)``.
    """
    @pl.when(pl.program_id(1) == 0)
    def _init():
        cur_ref[...] = jnp.zeros_like(cur_ref)

    def n_seg(totals):  # segments the fullest queue actually reaches
        deepest = functools.reduce(jnp.maximum, totals)
        return jax.lax.div(deepest + seg - 1, seg)

    @pl.when(gate_ref[0, 0] > 0)
    def _work():
        _pipeline(occ_ref, w_ref, cur_ref, buf_ref, n_pos=gate_ref[0, 1],
                  n_seg=n_seg, K=K, n_win=n_win, bits=bits, depth=depth,
                  seg=seg, H=H, W=W, invalid=invalid)


@functools.partial(jax.jit, static_argnames=(
    "K", "n_win", "bits", "depth", "seg", "H", "W", "invalid", "n_rows",
    "weight_bits", "interpret"))
def fused_spike_accum_sparse_pallas(
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
    n_rows: int | None = None,
    weight_bits: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Occupancy-gated Pallas variant of the fused pipeline.

    ``n_rows`` enables the ragged dispatch path: rows (sample*step entries)
    of the ``(N, …)`` grid are reordered active-first via a prefix-sum index
    map, the kernel launches on the leading ``n_rows`` only, and results are
    scattered back — all-empty rows never even enter the grid. The caller
    must pass ``n_rows >=`` the number of active rows (host-bucketed like
    ``e_cap``); ``None`` keeps the full grid (per-cell gating still applies).

    ``weight_bits`` fuses the int-quantized accumulate: weights are
    symmetric-quantized, the drain accumulates the integer values exactly
    (int8 magnitudes are exact in fp32 far beyond any feature-map fan-in),
    and one fp32 dequant scales the result — bit-identical to
    ``ref.fused_spike_accum_quant_ref``.
    """
    N, C_in, K2, P = occ.shape
    C_out = weights.shape[-1]
    seg = _default_seg(depth, n_win) if seg is None else min(seg, depth)

    w_scale = None
    if weight_bits is not None:
        w_q, w_scale = quantize_symmetric(weights, weight_bits)
        weights = w_q.astype(jnp.float32)

    row_order = None
    if n_rows is not None and n_rows < N:
        # ragged dispatch: compact active rows first (prefix-sum index map,
        # stable, same mechanism as the event list) and launch on them only
        row_act = (occ > 0).any((1, 2, 3))                 # (N,)
        act_i = row_act.astype(jnp.int32)
        pos_a = jnp.cumsum(act_i) - 1
        pos_i = jnp.cumsum(1 - act_i) - 1 + act_i.sum()
        slot = jnp.where(row_act, pos_a, pos_i)            # target position
        row_order = jnp.zeros((N,), jnp.int32).at[slot].set(
            jnp.arange(N, dtype=jnp.int32))
        occ = occ[row_order[:n_rows]]
        N_run = n_rows
    else:
        N_run = N

    # per-(row, channel) gate scalars: total events and 1 + last active
    # position (the fill-loop bound), one SMEM pair per grid cell
    fired_any = occ > 0
    cell_tot = fired_any.sum((-1, -2)).astype(jnp.int32)   # (N_run, C_in)
    p_idx = jnp.arange(P, dtype=jnp.int32)
    cell_pmax = jnp.max(
        jnp.where(fired_any.any(-2), p_idx[None, None] + 1, 0), -1
    ).astype(jnp.int32)                                    # (N_run, C_in)
    gates = jnp.stack([cell_tot, cell_pmax], -1)[:, :, None]

    # the occupancy as (N, C_in, 1, K2*P) and the weights as (C_in, K, K,
    # C_out), so that every block's two minor dimensions are the array's own
    out = pl.pallas_call(
        functools.partial(_sparse_kernel, K=K, n_win=n_win, bits=bits,
                          depth=depth, seg=seg, H=H, W=W, invalid=invalid),
        grid=(N_run, C_in),
        in_specs=[
            pl.BlockSpec((None, None, 1, K2 * P), lambda n, c: (n, c, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, K, K, C_out), lambda n, c: (c, 0, 0, 0)),
            pl.BlockSpec((None, None, 1, 2), lambda n, c: (n, c, 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((None, H, W, C_out),
                               lambda n, c: (n, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N_run, H, W, C_out), weights.dtype),
        scratch_shapes=[pltpu.SMEM((2 * K2 * seg,), jnp.int32)],
        interpret=interpret,
    )(occ.reshape(N_run, C_in, 1, K2 * P),
      jnp.transpose(weights, (2, 0, 1, 3)), gates)

    if row_order is not None:
        # scatter the active-row results back into the full (N, …) output;
        # rows beyond n_rows were all-empty, so zeros are exact
        full = jnp.zeros((N, H, W, C_out), out.dtype)
        out = full.at[row_order[:N_run]].set(out)
    if w_scale is not None:
        out = out * w_scale
    return out
