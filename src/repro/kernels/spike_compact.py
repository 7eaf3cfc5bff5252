"""Pallas TPU kernel: thresholding-unit event encoder (paper Fig. 2, right).

Takes the per-phase window occupancy of newly fired neurons and compacts it
into packed AE queue words — the hardware Thresholding Unit's "encode new
address events into the queues" step. Sequential append with a running count
(an SMEM scalar), exactly like the FPGA's queue write pointer; one grid step
per (channel, phase) queue, which are independent (interlacing) and hence
parallel across the grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(occ_ref, words_ref, count_ref, *, n_win, bits, depth, invalid):
    P = n_win * n_win
    words_ref[...] = jnp.full((depth,), invalid, jnp.int32)

    def body(p, cnt):
        fired = occ_ref[p] > 0
        wy = p // n_win
        wx = p % n_win
        word = (wy << bits) | wx
        slot = jnp.minimum(cnt, depth - 1)  # clamp; overflow tracked by count
        cur = words_ref[pl.ds(slot, 1)]
        words_ref[pl.ds(slot, 1)] = jnp.where(
            fired & (cnt < depth), jnp.full((1,), word, jnp.int32), cur)
        return cnt + fired.astype(jnp.int32)

    total = jax.lax.fori_loop(0, P, body, jnp.int32(0))
    count_ref[...] = total  # caller derives overflow = max(total - depth, 0)


@functools.partial(jax.jit, static_argnames=("n_win", "bits", "depth", "invalid", "interpret"))
def spike_compact(
    occ: jnp.ndarray,  # (R, n_win*n_win) int32/bool occupancy rows (R = C*K2)
    *,
    n_win: int,
    bits: int,
    depth: int,
    invalid: int,
    interpret: bool = True,
):
    """Compact occupancy rows into packed queues -> (words (R, depth), counts (R,))."""
    R, P = occ.shape
    assert P == n_win * n_win
    words, counts = pl.pallas_call(
        functools.partial(_kernel, n_win=n_win, bits=bits, depth=depth, invalid=invalid),
        grid=(R,),
        in_specs=[pl.BlockSpec((None, P), lambda r: (r, 0))],
        out_specs=[
            pl.BlockSpec((None, depth), lambda r: (r, 0)),
            pl.BlockSpec((None,), lambda r: (r,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, depth), jnp.int32),
            jax.ShapeDtypeStruct((R,), jnp.int32),
        ],
        interpret=interpret,
    )(occ.astype(jnp.int32))
    return words, counts
