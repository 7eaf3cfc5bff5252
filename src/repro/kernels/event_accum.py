"""Pallas TPU kernel: event-driven membrane-potential accumulation.

This is the accelerator's inner loop (paper Sec. 3.1) re-designed for the TPU
memory hierarchy:

FPGA original                          TPU kernel (here)
------------------------------------   ------------------------------------
K^2 BRAM banks, one event/bank/cycle    K^2 phase queues; one event per phase
                                        processed per grid step (the same
                                        conflict-freedom argument: same-phase
                                        events have distinct positions, so for
                                        a fixed kernel offset their targets
                                        never collide)
1 neuron word per BRAM port             a full C_out vector per accumulate —
                                        the VPU's 128-lane axis replaces the
                                        paper's P replicated cores
membrane potentials in BRAM             membrane map resident in VMEM for the
                                        whole layer pass (BlockSpec maps the
                                        entire (H, W, C_out) array; paper-scale
                                        maps are <= 32*32*128*4B = 512 KiB)
weights in dedicated BRAM               (K, K, C_out) weight slice in VMEM

Grid: (C_in, D) — channel-serial (the paper's channel-by-channel schedule),
queue-depth-serial; each step applies <= K^2 events (one per phase) with K^2
static kernel offsets each.

Alignment note: C_out is zero-padded to a multiple of 128 by ops.py so every
accumulate is a full-lane VREG op; H*W rows are the sublane axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..audit.contracts import KernelContract

# Declared resource/dtype intent, verified by ``python -m repro.audit``
# (see docs/CONTRACTS.md): fp32 accumulate, no host syncs, and the whole
# membrane map resident in VMEM (the design note above) within budget.
CONTRACT = KernelContract(name="event_accum", module=__name__,
                          accum_dtype="float32")


def vmem_blocks(*, K, H, W, C_out, **_unused):
    """Per-grid-cell resident buffers as data, for ``audit.vmem``.

    Mirrors :func:`event_accum`'s BlockSpecs: the packed-word and count
    slices, the weight slice, and the full membrane map both as input and
    output (the kernel keeps it VMEM-resident across the whole layer).
    """
    K2 = K * K
    return [
        ("words_block", (K2, 1), 4, True),
        ("counts_block", (K2,), 4, True),
        ("w_block", (K, K, C_out), 4, True),
        ("vm_in_block", (H, W, C_out), 4, True),
        ("out_block", (H, W, C_out), 4, True),
    ]


def _kernel(words_ref, counts_ref, w_ref, vm_in_ref, vm_ref, *, K, n_win, bits, H, W):
    """One grid step: d-th event of every phase queue for channel c."""
    d = pl.program_id(1)
    K2 = K * K
    mask = (1 << bits) - 1
    pad = K // 2

    @pl.when(pl.program_id(0) == 0)
    def _init():
        @pl.when(d == 0)
        def _copy():
            vm_ref[...] = vm_in_ref[...]

    for ph in range(K2):  # static unroll: the K^2 interlaced queues
        ky, kx = ph // K, ph % K
        word = words_ref[ph, 0]
        i_c = (word >> bits) & mask
        j_c = word & mask
        live = (i_c < n_win) & (d < counts_ref[ph])
        y = i_c * K + ky
        x = j_c * K + kx
        for dy in range(K):  # static unroll: kernel offsets
            for dx in range(K):
                ty = y - dy + pad
                tx = x - dx + pad
                ok = live & (ty >= 0) & (ty < H) & (tx >= 0) & (tx < W)
                tyc = jnp.clip(ty, 0, H - 1)
                txc = jnp.clip(tx, 0, W - 1)
                wv = w_ref[dy, dx, :]
                vm_ref[tyc, txc, :] += jnp.where(ok, wv, jnp.zeros_like(wv))


@functools.partial(jax.jit, static_argnames=("K", "n_win", "bits", "interpret"))
def event_accum(
    words: jnp.ndarray,    # (C_in, K2, D) int32 packed AE words (one time step)
    counts: jnp.ndarray,   # (C_in, K2) int32
    weights: jnp.ndarray,  # (K, K, C_in, C_out)
    v_mem: jnp.ndarray,    # (H, W, C_out) fp32
    *,
    K: int,
    n_win: int,
    bits: int,
    interpret: bool = True,
) -> jnp.ndarray:
    """Apply all queued events of one time step to the membrane map."""
    C_in, K2, D = words.shape
    H, W, C_out = v_mem.shape

    grid = (C_in, D)
    return pl.pallas_call(
        functools.partial(_kernel, K=K, n_win=n_win, bits=bits, H=H, W=W),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, K2, 1), lambda c, d: (c, 0, d)),
            pl.BlockSpec((None, K2), lambda c, d: (c, 0)),
            pl.BlockSpec((K, K, None, C_out), lambda c, d: (0, 0, c, 0)),
            pl.BlockSpec((H, W, C_out), lambda c, d: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((H, W, C_out), lambda c, d: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W, C_out), v_mem.dtype),
        interpret=interpret,
    )(words, counts, weights, v_mem)
