"""Layer-1 rules: walk traced jaxprs and verify the declared contracts.

Every rule takes a (name, ClosedJaxpr) pair produced by ``audit.probe`` and
returns :class:`~repro.audit.findings.Finding` objects anchored — via the
jaxpr's source info — at the repo line that created the offending equation.

Rules:

- ``dtype-f64``       any f64/c128 abstract value in a library trace (the
                      repo is strictly x64-free; an f64 means a Python-float
                      promotion leaked past ``jnp.float32`` discipline).
- ``quant-accum``     the int8-weight discipline against a
                      :class:`~repro.audit.contracts.QuantContract`: integer
                      dots/scatters must accumulate in the declared dtype,
                      int8 must never convert straight to float, and the
                      trace must contain exactly the declared number of
                      accumulator->float dequants.
- ``quant-dequant``   (whole-plan variant) int8 -> float converts anywhere
                      in a backend trace — the weaker invariant that holds
                      even for traces with incidental int->float stat casts.
- ``host-sync``       callback-family primitives inside a jitted trace (the
                      deliberate host pulls live *outside* jit, marked with
                      ``# audit: allow[host-sync]`` and checked by the AST
                      layer; inside a trace there is no legitimate one).
- ``batch-purity``    reductions that eliminate a batch-sized axis, counted
                      against the backend contract's declared
                      ``cross_batch_reductions`` — the structural form of
                      the mask contract (a padded row can only leak into
                      another row through a cross-batch reduction).
"""
from __future__ import annotations

import os

import jax.numpy as jnp

from .contracts import QuantContract
from .findings import Finding

_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
})
_BANNED_DTYPES = frozenset({"float64", "complex128"})


# ---------------------------------------------------------------------------
# jaxpr traversal + source anchoring
# ---------------------------------------------------------------------------

def all_jaxprs(closed):
    """Yield the top-level jaxpr and every nested one (pjit/scan/pallas/...)."""
    seen = set()
    stack = [getattr(closed, "jaxpr", closed)]
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        yield j
        for eqn in j.eqns:
            for v in eqn.params.values():
                vs = v if isinstance(v, (tuple, list)) else (v,)
                for x in vs:
                    inner = getattr(x, "jaxpr", x)
                    if hasattr(inner, "eqns"):
                        stack.append(inner)


def eqn_anchor(eqn, root: str) -> tuple[str, int]:
    """Best-effort ``(repo-relative file, line)`` for one equation."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is not None:
            f = frame.file_name
            if os.path.isabs(f) and f.startswith(root.rstrip(os.sep) + os.sep):
                f = os.path.relpath(f, root)
            return f, int(frame.start_line)
    except Exception:  # pragma: no cover - jax-internal API drift
        pass
    return "-", 0


def _vars(jaxpr):
    yield from jaxpr.invars
    yield from jaxpr.constvars
    for eqn in jaxpr.eqns:
        yield from eqn.outvars


def _dtype_of(v):
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    return None if dt is None else jnp.dtype(dt)


def _is_int(dt) -> bool:
    return dt is not None and jnp.issubdtype(dt, jnp.integer)


def _is_float(dt) -> bool:
    return dt is not None and jnp.issubdtype(dt, jnp.floating)


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

def check_dtypes(name: str, closed, root: str) -> list[Finding]:
    """``dtype-f64``: no f64/c128 aval anywhere in the trace."""
    out = []
    for jaxpr in all_jaxprs(closed):
        hit_eqns = set()
        for eqn in jaxpr.eqns:
            if any(str(_dtype_of(v)) in _BANNED_DTYPES for v in eqn.outvars):
                hit_eqns.add(eqn)
        for eqn in hit_eqns:
            f, line = eqn_anchor(eqn, root)
            out.append(Finding(
                "dtype-f64", "error", f, line,
                f"{name}: {eqn.primitive.name} produces "
                f"{[str(_dtype_of(v)) for v in eqn.outvars]} — f64/c128 "
                "must never appear in a library trace"))
        for v in jaxpr.invars + jaxpr.constvars:
            if str(_dtype_of(v)) in _BANNED_DTYPES:
                out.append(Finding(
                    "dtype-f64", "error", "-", 0,
                    f"{name}: trace input/const has dtype {_dtype_of(v)}"))
    return _dedupe(out)


def check_host_sync(name: str, closed, root: str) -> list[Finding]:
    """``host-sync``: no callback-family primitive inside a jitted trace."""
    out = []
    for jaxpr in all_jaxprs(closed):
        for eqn in jaxpr.eqns:
            if "callback" in eqn.primitive.name:
                f, line = eqn_anchor(eqn, root)
                out.append(Finding(
                    "host-sync", "error", f, line,
                    f"{name}: {eqn.primitive.name} inside a jitted library "
                    "path — host round-trips belong outside jit, marked "
                    "with '# audit: allow[host-sync]'"))
    return _dedupe(out)


def _eliminated_sizes(eqn):
    """Axis sizes a reduction-like equation eliminates (empty if none)."""
    p = eqn.primitive.name
    if p in _REDUCE_PRIMS:
        axes = eqn.params.get("axes", ())
        shape = getattr(eqn.invars[0].aval, "shape", ())
        return [shape[a] for a in axes if a < len(shape)]
    if p == "dot_general":
        (lc, _), _ = eqn.params["dimension_numbers"]
        shape = getattr(eqn.invars[0].aval, "shape", ())
        return [shape[a] for a in lc if a < len(shape)]
    return []


def check_batch_purity(name: str, closed, tainted_sizes, declared: int,
                       root: str) -> list[Finding]:
    """``batch-purity``: cross-batch reductions vs. the declared count.

    ``tainted_sizes`` are axis sizes only the batch (or batch*time) axis can
    have in the probe trace (see ``probe.batch_tainted_sizes``); every
    reduction/contraction eliminating one is a point where one sample's
    numbers could reach another's. The backend contract declares how many
    such points exist by design (0 for every traced backend; 2 for the
    sparse backend's occupancy-gate stats fn). More than declared breaks the
    mask contract; fewer than declared means the declaration is stale.
    """
    hits = []
    for jaxpr in all_jaxprs(closed):
        for eqn in jaxpr.eqns:
            sizes = _eliminated_sizes(eqn)
            if any(s in tainted_sizes for s in sizes):
                hits.append(eqn)
    out = []
    if len(hits) > declared:
        for eqn in hits:
            f, line = eqn_anchor(eqn, root)
            out.append(Finding(
                "batch-purity", "error", f, line,
                f"{name}: {eqn.primitive.name} eliminates a batch-sized "
                f"axis ({len(hits)} cross-batch reduction(s) found, "
                f"{declared} declared) — the mask contract requires every "
                "cross-batch reduction to be declared in the backend "
                "CONTRACT"))
    elif len(hits) < declared:
        out.append(Finding(
            "batch-purity", "warning", "-", 0,
            f"{name}: contract declares {declared} cross-batch "
            f"reduction(s) but the trace contains {len(hits)} — stale "
            "declaration"))
    return _dedupe(out)


def check_quant(name: str, closed, contract: QuantContract,
                root: str) -> list[Finding]:
    """``quant-accum``: int operands accumulate in the declared dtype, with
    exactly the declared number of accumulator->float dequants and no
    direct int8->float convert anywhere."""
    out = []
    dequants = []
    for jaxpr in all_jaxprs(closed):
        for eqn in jaxpr.eqns:
            p = eqn.primitive.name
            if p == "convert_element_type":
                src, dst = _dtype_of(eqn.invars[0]), _dtype_of(eqn.outvars[0])
                shape = getattr(getattr(eqn.invars[0], "aval", None),
                                "shape", ())
                if shape == ():
                    # scalar converts are weak-typed Python constants
                    # (clip bounds, loop counters), not accumulator data
                    continue
                if _is_int(src) and _is_float(dst):
                    if str(src) == contract.weight_dtype:
                        f, line = eqn_anchor(eqn, root)
                        out.append(Finding(
                            "quant-accum", "error", f, line,
                            f"{name}: direct {src}->{dst} convert — "
                            f"quantized values must pass through the "
                            f"{contract.accum_dtype} accumulator before the "
                            "declared dequant"))
                    else:
                        dequants.append((eqn, src, dst))
            elif p in ("dot_general", "scatter-add", "scatter_add"):
                ops = ([eqn.invars[0], eqn.invars[2]] if "scatter" in p
                       and len(eqn.invars) > 2 else eqn.invars[:2])
                in_dts = [_dtype_of(v) for v in ops]
                if any(_is_int(dt) for dt in in_dts):
                    o = _dtype_of(eqn.outvars[0])
                    if str(o) != contract.accum_dtype:
                        f, line = eqn_anchor(eqn, root)
                        out.append(Finding(
                            "quant-accum", "error", f, line,
                            f"{name}: {p} over integer operands "
                            f"accumulates in {o}, contract requires "
                            f"{contract.accum_dtype}"))
    if len(dequants) != contract.dequants:
        where = "; ".join(
            "{}:{} ({}->{})".format(*eqn_anchor(eqn, root), s, d)
            for eqn, s, d in dequants) or "none found"
        out.append(Finding(
            "quant-accum", "error", "-", 0,
            f"{name}: {len(dequants)} int->float dequant(s), contract "
            f"declares exactly {contract.dequants} ({where})"))
    return _dedupe(out)


def check_no_int8_dequant(name: str, closed, root: str) -> list[Finding]:
    """``quant-dequant``: whole-plan variant — int8 never converts straight
    to float (stat casts int32->float are incidental and allowed here)."""
    out = []
    for jaxpr in all_jaxprs(closed):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            src, dst = _dtype_of(eqn.invars[0]), _dtype_of(eqn.outvars[0])
            if str(src) == "int8" and _is_float(dst):
                f, line = eqn_anchor(eqn, root)
                out.append(Finding(
                    "quant-dequant", "error", f, line,
                    f"{name}: int8->{dst} convert — int8 weights/counts "
                    "must accumulate in int32 before any float conversion"))
    return _dedupe(out)


def _dedupe(findings: list[Finding]) -> list[Finding]:
    seen, out = set(), []
    for f in findings:
        key = (f.fingerprint, f.line)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out
