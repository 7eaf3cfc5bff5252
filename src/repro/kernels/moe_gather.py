"""Pallas TPU kernel: MoE dispatch row-gather driven by packed routing words.

The paper-technique transfer (DESIGN.md §Arch-applicability): MoE dispatch is
address-event processing — a routing word names which token ("event") a given
expert-capacity slot consumes, with an in-band invalid code for empty slots,
exactly like the compressed AE encoding's spare patterns.

Grid: one step per block of capacity slots; token indices arrive via scalar
prefetch (PrefetchScalarGridSpec) so the index arithmetic happens before the
block's DMA — the TPU-idiomatic equivalent of the FPGA queue's address port.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, x_ref, o_ref, *, block_rows):
    r0 = pl.program_id(0) * block_rows
    for r in range(block_rows):  # static unroll within the block
        tok = idx_ref[r0 + r]
        ok = tok >= 0
        row = x_ref[pl.ds(jnp.maximum(tok, 0), 1), :]
        o_ref[pl.ds(r, 1), :] = jnp.where(ok, row, jnp.zeros_like(row))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def moe_gather(
    x: jnp.ndarray,        # (T, d) token activations
    indices: jnp.ndarray,  # (S,) int32 token index per capacity slot, -1 = empty
    *,
    block_rows: int = 8,
    interpret: bool = True,
) -> jnp.ndarray:
    """Gather token rows into expert-capacity slots; empty slots are zeros."""
    S = indices.shape[0]
    T, d = x.shape
    pad = (-S) % block_rows
    idx_p = jnp.pad(indices, (0, pad), constant_values=-1)

    out = pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=((S + pad) // block_rows,),
            in_specs=[pl.BlockSpec((T, d), lambda i, idx: (0, 0))],
            out_specs=pl.BlockSpec((block_rows, d), lambda i, idx: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((S + pad, d), x.dtype),
        interpret=interpret,
    )(idx_p, x)
    return out[:S]
