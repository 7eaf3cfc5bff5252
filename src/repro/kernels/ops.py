"""Public jit'd entry points for the Pallas kernels.

The platform decides how a Pallas kernel runs: compiled by Mosaic on a
TPU, under the Pallas interpreter everywhere else (the kernel *body* runs,
validating the logic). ``backend='ref'`` selects the pure-jnp oracle used
for differential testing. On a TPU neither the interpreter nor an oracle
may stand in for a kernel: :func:`fused_spike_accum` and
:func:`quant_matmul` raise rather than run one there.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from . import ref as _ref
from .event_accum import event_accum as _event_accum
from .quant_matmul import quant_matmul as _quant_matmul
from .spike_compact import spike_compact as _spike_compact
from .spike_pipeline import (fused_spike_accum_pallas as _fused_pallas,
                             fused_spike_accum_xla as _fused_xla)
from .spike_sparse import (fused_spike_accum_sparse as _fused_sparse,
                           fused_spike_accum_sparse_pallas as
                           _fused_sparse_pallas)

# realization-dispatch tallies: which impl actually ran, counted at the
# dispatch layer (not inside jit), so wiring tests can pin e.g. "a
# weight_bits=8 queue_sparse study cell dispatches the sparse kernel AND
# quant_matmul" without tracing internals
dispatch_counts: collections.Counter = collections.Counter()


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# realizations that only validate a kernel: never run in place of one on TPU
_OFF_CHIP_ONLY = ("ref", "pallas_interpret", "sparse_pallas_interpret")


def _refuse_on_tpu(kernel: str, impl: str) -> None:
    if impl in _OFF_CHIP_ONLY and jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{kernel}: realization {impl!r} is an oracle or the Pallas "
            "interpreter and does not run on a TPU; use the compiled kernel "
            "(the default)")


def default_spike_impl() -> str:
    """Default implementation of the fused spike pipeline — never interpret.

    'pallas' (compiled Mosaic) on TPU; 'xla' (the fused-conv realization of
    the same semantics) everywhere else — keyed off the actual jax backend.
    The Pallas *interpreter* is only reachable by explicit request
    (``impl='pallas_interpret'``) off the chip — it is a logic-validation
    tool, not an execution path.
    """
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def default_sparse_impl() -> str:
    """Default realization of the *sparse* (occupancy-gated) pipeline.

    'sparse_pallas' (the occupancy-gated Mosaic kernel, ragged row grid) on
    TPU; 'sparse' (the compiled event-list XLA program) everywhere else.
    Like :func:`default_spike_impl`, the interpreter is never a default.
    """
    return "sparse_pallas" if jax.default_backend() == "tpu" else "sparse"


def fused_spike_accum(occ, weights, *, K, n_win, bits, depth, H, W,
                      invalid=0, seg=None, impl=None, e_cap=None,
                      n_rows=None, weight_bits=None):
    """Fused compact+accumulate: (N, C_in, K2, P) occupancy -> (N, H, W, C_out).

    ``impl``: None -> :func:`default_spike_impl`; explicit 'xla', 'pallas',
    'pallas_interpret', or 'ref' select a realization (all bit-compatible in
    which events they accumulate; float summation order differs). The sparse
    realizations — 'sparse' (event-list XLA, requires ``e_cap``),
    'sparse_pallas' / 'sparse_pallas_interpret' (occupancy-gated kernel,
    optional ragged ``n_rows``) — do work proportional to occupancy and
    additionally accept ``weight_bits`` for the int-quantized accumulate
    (also honored by 'ref', which then anchors the quant parity tests).
    ``bits``, ``invalid`` (the AE word format) and ``seg`` (its queue
    segment) reach only the sparse Pallas kernel, the one scalar event walk.
    """
    impl = impl or default_spike_impl()
    _refuse_on_tpu("fused_spike_accum", impl)
    dispatch_counts[f"fused:{impl}"] += 1
    if impl == "ref":
        if weight_bits is not None:
            return _ref.fused_spike_accum_quant_ref(
                occ, weights, K=K, n_win=n_win, depth=depth, H=H, W=W,
                weight_bits=weight_bits)
        return _ref.fused_spike_accum_ref(occ, weights, K=K, n_win=n_win,
                                          depth=depth, H=H, W=W)
    if impl == "sparse":
        if e_cap is None:
            raise ValueError("impl='sparse' needs an e_cap event budget "
                             "(see spike_sparse.event_bucket)")
        return _fused_sparse(occ, weights, K=K, n_win=n_win, depth=depth,
                             H=H, W=W, e_cap=e_cap, weight_bits=weight_bits)
    if impl in ("sparse_pallas", "sparse_pallas_interpret"):
        return _fused_sparse_pallas(
            occ, weights, K=K, n_win=n_win, bits=bits, depth=depth, H=H, W=W,
            invalid=invalid, seg=seg, n_rows=n_rows, weight_bits=weight_bits,
            interpret=(impl == "sparse_pallas_interpret"))
    if weight_bits is not None:
        raise ValueError(
            f"impl {impl!r} has no int-quantized accumulate path "
            "(use 'sparse', 'sparse_pallas', or 'ref')")
    if impl == "xla":
        return _fused_xla(occ, weights, K=K, n_win=n_win, depth=depth,
                          H=H, W=W)
    if impl in ("pallas", "pallas_interpret"):
        return _fused_pallas(occ, weights, K=K, n_win=n_win, depth=depth,
                             H=H, W=W, interpret=(impl == "pallas_interpret"))
    raise ValueError(
        f"unknown fused_spike_accum impl {impl!r} "
        "(expected 'xla', 'pallas', 'pallas_interpret', 'sparse', "
        "'sparse_pallas', 'sparse_pallas_interpret', or 'ref')")


def event_accum(words, counts, weights, v_mem, *, K, n_win, bits, backend="pallas"):
    if backend == "ref":
        return _ref.event_accum_ref(words, counts, weights, v_mem,
                                    K=K, n_win=n_win, bits=bits)
    return _event_accum(words, counts, weights, v_mem,
                        K=K, n_win=n_win, bits=bits, interpret=_interpret())


def spike_compact(occ, *, n_win, bits, depth, invalid, backend="pallas"):
    if backend == "ref":
        return _ref.spike_compact_ref(occ, n_win=n_win, bits=bits,
                                      depth=depth, invalid=invalid)
    return _spike_compact(occ, n_win=n_win, bits=bits, depth=depth,
                          invalid=invalid, interpret=_interpret())


def default_quant_impl() -> str:
    """Default realization of the int8 matmul — never the interpreter.

    'pallas' (the tiled Mosaic kernel) on TPU; 'ref' (one compiled int32
    ``jnp.matmul`` + fp32 dequant — identical arithmetic) elsewhere. The
    engine's quantized output head dispatches through this, so the hot path
    never pays the Python-loop Pallas interpreter.
    """
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def quant_matmul(a_q, b_q, a_scale, b_scale, *, backend=None, **blocks):
    backend = backend or default_quant_impl()
    _refuse_on_tpu("quant_matmul", backend)
    dispatch_counts[f"quant_matmul:{backend}"] += 1
    if backend == "ref":
        return _ref.quant_matmul_ref(a_q, b_q, a_scale, b_scale)
    return _quant_matmul(a_q, b_q, a_scale, b_scale,
                         interpret=_interpret(), **blocks)

