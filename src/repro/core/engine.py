"""Compiled, backend-pluggable SNN execution engine.

The paper's accelerator (Figs. 3-5) is a *layer pipeline*: a static per-layer
plan (geometry, queue formats, thresholds) drives interchangeable compute
units. This module is the software mirror of that structure:

1. ``compile_plan`` turns a spec string ("32C3-P2-32C3-P2-10") into a static
   :class:`LayerPlan` — validated once, hashable, cached — shared by the SNN
   backends, the CNN baseline (``cnn_baseline``), ANN->SNN conversion
   (``conversion``) and the energy model (``energy.snn_static_costs``).

2. Neuron dynamics come from the step-function registry in ``core/neuron.py``
   (``get_neuron_model``); there is no per-mode branching anywhere in the
   execution paths, so a new neuron variant is a one-file change.

3. Backends implement one hook — how a conv layer turns incoming events into
   membrane charge — and everything else (spec walk, input encoding, fused
   pooling, the output layer, stats accounting) is shared engine code:

   - ``dense``          : per-layer currents via one T-batched XLA conv, time
                          loop as ``jax.lax.scan`` (fast reference; what the
                          studies and benchmarks use).
   - ``dense_unrolled`` : the seed implementation's unrolled per-step Python
                          loop, kept as a tracing/benchmark reference.
   - ``queue``          : hardware-faithful AEQ path (``core/aeq`` +
                          ``snn_layers.event_conv2d``), word-level reference.
   - ``queue_pallas``   : same schedule through the *fused* spike pipeline
                          (``kernels/spike_pipeline``): compaction +
                          accumulation in one compiled, batch-native kernel
                          (Pallas on TPU, fused-conv XLA elsewhere — never
                          the Pallas interpreter). Declares
                          ``supports_batch``, so ``infer_batch`` runs one
                          batched plan with the batch axis in the kernel
                          grid instead of an outer ``jax.vmap``.

Entry points ``infer`` / ``infer_batch`` are jit-compiled once per
(config, backend, batched) triple and cached; ``snn_model.snn_infer`` /
``snn_dense_infer`` are thin wrappers over them. ``infer_batch_masked``
is the padded-bucket entry the serving runtime (``repro.serve``) uses —
see the mask contract on ``infer_batch``.
"""
from __future__ import annotations

import functools
import re
from typing import NamedTuple, Protocol

import jax
import jax.numpy as jnp

from .. import obs
from ..audit.contracts import BackendContract, QuantContract
from . import compile_cache, encoding
from .aeq import (AEQ, aeq_from_raster, phase_occupancy, segment_keep,
                  span_map)
from .encoding import AEFormat, encode_ttfs
from .neuron import (NeuronModel, _on_registry_change, get_neuron_model,
                     surrogate_model)
from .snn_layers import (PRECISION, dense_conv_hwc, event_conv2d,
                         spike_maxpool_hwc)

# Persistent compilation cache (docs/SERVING.md "Cold start"): every entry
# point imports the engine, so with JAX_COMPILATION_CACHE_DIR exported this
# is where the repo's floors and hit/miss counters join JAX's own cache.
# No env var, no behaviour change.
compile_cache.configure_from_env()

# Engine-internal raster layout: (T, H, W, C) — channels-last end to end, so
# the dense path runs transpose-free (XLA convs are NHWC-native); the queue
# backend moves to the AEQ's (T, C, H, W) view only at its queue boundary.


class SpecError(ValueError):
    """A malformed or structurally invalid model spec string."""


# ---------------------------------------------------------------------------
# Spec parsing + validation (paper Table 6 grammar)
# ---------------------------------------------------------------------------

_CONV_RE = re.compile(r"^(\d+)C(\d+)$")
_POOL_RE = re.compile(r"^P(\d+)$")
_DENSE_RE = re.compile(r"^(\d+)$")


def parse_spec(spec: str) -> list[tuple]:
    """'32C3-32C3-P3-10C3-10' -> [('conv',32,3), ..., ('pool',3), ('dense',10)].

    Grammar (paper Table 6): ``nCk`` conv (n kernels of k x k, SAME, stride
    1), ``Pn`` max-pool (n x n, stride n, fused into the preceding conv's
    emission), trailing ``n`` fully connected. Raises :class:`SpecError` with
    the offending token on malformed input instead of failing deep inside
    inference.
    """
    if not isinstance(spec, str) or not spec.strip():
        raise SpecError(f"empty model spec {spec!r}")
    tokens = spec.split("-")
    layers: list[tuple] = []
    seen_conv = False
    for pos, tok in enumerate(tokens):
        if tok == "":
            where = ("leading" if pos == 0 else
                     "trailing" if pos == len(tokens) - 1 else "doubled")
            raise SpecError(f"{where} '-' in spec {spec!r}")
        if layers and layers[-1][0] == "dense":
            raise SpecError(
                f"token {tok!r} after the dense output layer in {spec!r} "
                "(the classifier must be the final token)")
        if m := _CONV_RE.match(tok):
            n, k = int(m.group(1)), int(m.group(2))
            if n < 1 or k < 1:
                raise SpecError(f"conv token {tok!r} in {spec!r}: "
                                "channels and kernel must be >= 1")
            if k % 2 == 0:
                raise SpecError(
                    f"conv token {tok!r} in {spec!r}: even kernels are not "
                    "supported (SAME padding and the AEQ phase interlacing "
                    "assume an odd kernel)")
            layers.append(("conv", n, k))
            seen_conv = True
        elif m := _POOL_RE.match(tok):
            if not seen_conv:
                raise SpecError(
                    f"pool token {tok!r} in {spec!r} before any conv layer "
                    "(pooling is fused into a preceding conv's emission)")
            if layers[-1][0] != "conv":
                raise SpecError(
                    f"pool token {tok!r} in {spec!r} must directly follow a "
                    "conv layer (back-to-back pools cannot be fused)")
            win = int(m.group(1))
            if win < 1:
                raise SpecError(f"pool token {tok!r} in {spec!r}: "
                                "window must be >= 1")
            layers.append(("pool", win))
        elif m := _DENSE_RE.match(tok):
            n = int(m.group(1))
            if n < 1:
                raise SpecError(f"dense token {tok!r} in {spec!r}: "
                                "width must be >= 1")
            layers.append(("dense", n))
        else:
            raise SpecError(
                f"malformed token {tok!r} in spec {spec!r} "
                "(expected nCk, Pn, or a trailing integer)")
    return layers


def layer_geometry(spec_layers, input_hw: int, input_c: int):
    """Static shape walk: per layer -> (type, in_hw, in_c, out_hw, out_c)."""
    hw, c = input_hw, input_c
    geo = []
    for ly in spec_layers:
        if ly[0] == "conv":
            geo.append(("conv", hw, c, hw, ly[1], ly[2]))
            c = ly[1]
        elif ly[0] == "pool":
            out = hw // ly[1]
            geo.append(("pool", hw, c, out, c, ly[1]))
            hw = out
        else:
            n_in = hw * hw * c
            geo.append(("dense", n_in, ly[1]))
    return geo


# ---------------------------------------------------------------------------
# The compiled layer plan
# ---------------------------------------------------------------------------

class ConvPlan(NamedTuple):
    """One conv stage (with its optional fused pool) of the pipeline."""

    index: int          # token index in the spec == params/thresholds slot
    in_hw: int          # input (== conv output) feature-map side
    in_c: int
    out_c: int
    kernel: int
    pool: int           # fused pool window (0 = no pool)
    out_hw: int         # side after the fused pool
    fmt: AEFormat       # AE word format of the *incoming* event queue


class OutPlan(NamedTuple):
    """The final fully-connected classifier (accumulates Vm, no threshold)."""

    index: int
    n_in: int
    n_out: int


class LayerPlan(NamedTuple):
    """Static execution plan for a spec — hashable, cached, backend-agnostic."""

    spec: str
    input_hw: int
    input_c: int
    compressed: bool
    n_layers: int                  # spec token count == len(params)
    convs: tuple[ConvPlan, ...]
    out: OutPlan


@functools.lru_cache(maxsize=None)
def compile_plan(
    spec: str, input_hw: int, input_c: int, compressed: bool = True
) -> LayerPlan:
    """Compile + validate ``spec`` for a given input geometry, once.

    The result is a pure-static NamedTuple (ints and formats only), so it is
    hashable and safely shared across jit traces, backends, and modules.
    A conv's kernel may be wider than its map (VGG-16's 2x2 stages): SAME
    padding makes it the zero-padded convolution, and the map is one
    window of the AE format (``n_win = 1``). A pool window wider than its
    map is a :class:`SpecError`.
    """
    layers = parse_spec(spec)
    if layers[-1][0] != "dense":
        raise SpecError(
            f"spec {spec!r} must end with a dense classifier layer")
    if layers[0][0] != "conv":
        raise SpecError(f"spec {spec!r} must start with a conv layer")

    hw, c = input_hw, input_c
    convs: list[ConvPlan] = []
    li = 0
    while li < len(layers) - 1:
        ly = layers[li]
        # parse_spec guarantees only conv (+ directly-following pool) here
        cout, k = ly[1], ly[2]
        pool = 0
        if li + 1 < len(layers) - 1 and layers[li + 1][0] == "pool":
            pool = layers[li + 1][1]
            if pool > hw:
                raise SpecError(
                    f"spec {spec!r} layer {li + 1}: pool window {pool} "
                    f"exceeds the {hw}x{hw} feature map")
        out_hw = hw // pool if pool else hw
        convs.append(ConvPlan(
            index=li, in_hw=hw, in_c=c, out_c=cout, kernel=k,
            pool=pool, out_hw=out_hw,
            fmt=encoding.make_format(hw, k, compressed=compressed),
        ))
        c = cout
        hw = out_hw
        li += 2 if pool else 1

    n_in = hw * hw * c
    out = OutPlan(index=len(layers) - 1, n_in=n_in, n_out=layers[-1][1])
    return LayerPlan(
        spec=spec, input_hw=input_hw, input_c=input_c, compressed=compressed,
        n_layers=len(layers), convs=tuple(convs), out=out,
    )


# ---------------------------------------------------------------------------
# Configuration + statistics
# ---------------------------------------------------------------------------

class SNNConfig(NamedTuple):
    spec: str
    input_hw: int
    input_c: int
    T: int = 4                 # algorithmic time steps (paper: T=4)
    mode: str = "mttfs"        # neuron model variant (core/neuron.py registry)
    depth: int = 256           # AEQ depth D per (t, c, phase) segment
    compressed: bool = True    # compressed AE encoding (Sec. 5.2)
    input_mode: str = "analog" # 'analog' (snntoolbox current) | 'binary' (TTFS events)
    input_theta: float = 0.1   # threshold for binary input encoding
    v_init_frac: float = 0.5   # initial charge as a fraction of V_t (Rueckauer:
                               # centers the spike-count quantizer, round-vs-floor)
    weight_bits: int | None = None
                               # deployed integer weight width on the event
                               # path. None = fp32 everywhere (every pre-
                               # existing config). When set, the sparse
                               # realization (queue_sparse; ref-anchored by
                               # queue_ref) runs the int-quantized conv
                               # accumulate and the shared output layer runs
                               # the int8 quant_matmul head; other conv
                               # backends keep fp32 convs regardless.


class SNNStats(NamedTuple):
    """Per-sample accounting used by the energy model and Figs. 7-9/12-15."""

    events_in: jnp.ndarray    # (L,) events consumed per conv layer (all t)
    spikes_out: jnp.ndarray   # (L,) spikes emitted per layer
    add_ops: jnp.ndarray      # (L,) scalar accumulations performed
    overflow: jnp.ndarray     # () dropped events across all AEQs
    queue_words: jnp.ndarray  # (L,) peak words resident per layer queue


class LayerStats(NamedTuple):
    """One stats row (one weighted layer); stacked into :class:`SNNStats`."""

    events_in: jnp.ndarray
    spikes_out: jnp.ndarray
    add_ops: jnp.ndarray
    queue_words: jnp.ndarray
    overflow: jnp.ndarray


def _zero() -> jnp.ndarray:
    return jnp.zeros((), jnp.int32)


# ---------------------------------------------------------------------------
# Shared stat helpers (identical numbers on every backend)
# ---------------------------------------------------------------------------

def _valid_offsets_map(hw: int, K: int) -> jnp.ndarray:
    """(hw, hw) map: number of in-bounds kernel offsets per spike position."""
    ones = jnp.ones((1, 1, hw, hw))
    kern = jnp.ones((K, K, 1, 1))
    return jax.lax.conv_general_dilated(
        ones, kern, (1, 1), "SAME", dimension_numbers=("NCHW", "HWIO", "NHWC"),
        precision=PRECISION)[0, :, :, 0]


def _segment_occupancy(fmt: AEFormat, raster: jnp.ndarray) -> jnp.ndarray:
    """(T, H, W, C) raster -> (T, K, K, C) per-(t, phase, c) event counts.

    A spike at (y, x) lands in phase (y mod K)*K + (x mod K), so the segment
    occupancy is one pad + reshape + sum over the window grid — no per-map
    phase splitting (this sits on the hot dense path; ``aeq._phase_split``
    remains the word-level model the queues use).
    """
    K, n = fmt.kernel, fmt.n_win
    T, H, W, C = raster.shape
    m = jnp.pad(raster,
                ((0, 0), (0, n * K - H), (0, n * K - W), (0, 0)))
    occ = m.reshape(T, n, K, n, K, C).sum(axis=(1, 3))      # (T, K, K, C)
    return occ.astype(jnp.int32)


# (the analytic per-event op counter lives in ``aeq.span_map``: adds per
# surviving event = in-bounds kernel offsets * C_out, shared by the fused
# batched queue path below and anything else that cannot count in-kernel)


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------

class Backend(Protocol):
    """A compute unit for one conv stage of the layer pipeline.

    ``conv_layer`` receives the static :class:`ConvPlan`, this layer's
    parameters, and the incoming activity — either a (T, H, W, C) spike
    ``raster`` or an ``analog`` (H, W, C) constant-current image (exactly one
    is non-None) — and returns the emitted (T, H', W', C_out) raster plus its
    :class:`LayerStats` row. Neuron dynamics MUST come from
    ``neuron.get_neuron_model(cfg.mode)`` so all backends stay in lockstep.

    A backend may additionally declare ``supports_batch = True`` and provide
    ``conv_layer_batch`` with the same signature over (B, T, H, W, C) /
    (B, H, W, C) activity and per-sample (B,)-shaped stats; ``infer_batch``
    then executes one batched plan instead of vmapping the per-sample
    program (see :func:`_execute_batch`).
    """

    name: str

    def conv_layer(
        self, cp: ConvPlan, w, b, vth, cfg: SNNConfig, raster, analog
    ) -> tuple[jnp.ndarray, LayerStats]:
        ...


def _conv_step(cp: ConvPlan, model: NeuronModel, vth):
    """Shared per-time-step body: integrate -> fire -> (fused) pool.

    Returns ``step(carry, current) -> (carry, spikes_hwc)``, where
    ``current`` already includes the bias term; used by the scanned dense
    backend and the event-queue backends alike — the neuron/pool semantics
    exist once.
    """

    def step(carry, cur_t):
        if cp.pool:
            v, latch, p_latch = carry
        else:
            v, latch = carry
        v = v + cur_t
        v, sp, latch = model.fire(v, latch, vth)
        sp = sp.astype(v.dtype)                            # (H, W, C_out)
        if cp.pool:
            sp, p_latch = spike_maxpool_hwc(
                sp, cp.pool, p_latch, latch_once=model.pool_latch_once,
                straight_through=model.straight_through)
            return (v, latch, p_latch), sp
        return (v, latch), sp

    return step


def _init_carry(cp: ConvPlan, cfg: SNNConfig, vth, dtype):
    v = jnp.full((cp.in_hw, cp.in_hw, cp.out_c),
                 cfg.v_init_frac * jnp.asarray(vth, dtype), dtype)
    latch = jnp.zeros((cp.in_hw, cp.in_hw, cp.out_c), jnp.bool_)
    if cp.pool:
        p_latch = jnp.zeros((cp.out_hw, cp.out_hw, cp.out_c), jnp.bool_)
        return (v, latch, p_latch)
    return (v, latch)


def _init_carry_batch(cp: ConvPlan, cfg: SNNConfig, vth, dtype, B: int):
    """The per-sample carry with a leading batch axis (same init values)."""
    return tuple(jnp.broadcast_to(a, (B,) + a.shape)
                 for a in _init_carry(cp, cfg, vth, dtype))


class DenseBackend:
    """Dense-dynamics reference: one T-batched conv + ``lax.scan`` time loop.

    Identical mathematics to the queue path (event-driven accumulation of a
    spike raster == dense convolution of it), so every queue statistic is
    *derivable* from the rasters: events = spike counts, add_ops = sum over
    spikes of in-bounds kernel offsets * C_out, queue words/overflow = per-
    (t, c, phase) segment occupancy vs. depth. ~100x faster on CPU; what
    studies and benchmarks use.

    The time loop is ``jax.lax.scan`` over the T-batched currents with
    ``scan_unroll`` steps inlined per loop iteration (default: fully
    unrolled at the XLA level) — one traced body regardless of T, with the
    cross-step fusion of hand-unrolled code. ``unroll=True`` instead
    reproduces the seed's per-step Python loop + per-step convs (kept as
    the tracing/benchmark reference).
    """

    def __init__(self, unroll: bool = False, scan_unroll: int | bool = True):
        self.unroll = unroll
        self.scan_unroll = scan_unroll
        self.name = "dense_unrolled" if unroll else "dense"

    def conv_layer(self, cp, w, b, vth, cfg, raster, analog):
        model = get_neuron_model(cfg.mode)
        T = cfg.T

        if raster is not None:
            occ = _segment_occupancy(cp.fmt, raster)
            q_words = occ.sum().astype(jnp.int32)
            ovf = jnp.maximum(occ - cfg.depth, 0).sum().astype(jnp.int32)
            ev = raster.sum().astype(jnp.int32)
            per_spike = _valid_offsets_map(cp.in_hw, cp.kernel)
            ops = ((raster * per_spike[None, :, :, None]).sum()
                   * cp.out_c).astype(jnp.int32)
        else:
            q_words, ovf, ev = _zero(), _zero(), _zero()
            ops = jnp.int32(
                T * analog.size * cp.out_c * cp.kernel * cp.kernel)

        step = _conv_step(cp, model, vth)
        carry = _init_carry(cp, cfg, vth, w.dtype)

        if self.unroll:
            # seed-style: one conv trace per time step, Python-unrolled
            frames = []
            for t in range(T):
                cur_t = (dense_conv_hwc(raster[t], w)
                         if raster is not None else dense_conv_hwc(analog, w))
                carry, sp = step(carry, cur_t + b)
                frames.append(sp)
            out_raster = jnp.stack(frames)
        else:
            if raster is not None:
                # all T steps in one batched conv (T is the batch axis)
                cur = jax.lax.conv_general_dilated(
                    raster.astype(w.dtype), w, (1, 1), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    precision=PRECISION) + b
            else:
                c1 = dense_conv_hwc(analog, w) + b
                cur = jnp.broadcast_to(c1, (T,) + c1.shape)
            _, out_raster = jax.lax.scan(step, carry, cur,
                                         unroll=self.scan_unroll)

        row = LayerStats(ev, out_raster.sum().astype(jnp.int32), ops,
                         q_words, ovf)
        return out_raster, row


class QueueBackend:
    """Hardware-faithful path: events flow through per-(t, c, phase) AEQs.

    Faithful points (paper Sec. 3.1/4): spike-once latches via the neuron
    registry, no reset, bias as constant input current each step, pooling
    fused into emission, segmented fixed-depth queues, layer-by-layer
    T-repetition schedule.

    ``accum='jax'`` (the ``queue`` backend) is the word-level reference: it
    materializes every AEQ (``core/aeq``) and accumulates event by event
    through ``snn_layers.event_conv2d``. ``accum='pallas'`` (the
    ``queue_pallas`` backend) runs the *fused* spike pipeline instead —
    ``kernels/spike_pipeline`` compacts and accumulates in one compiled,
    batch-native kernel (Pallas on TPU, the fused-conv XLA realization
    elsewhere; never the Pallas interpreter), and declares
    ``supports_batch`` so ``infer_batch`` executes one batched plan with the
    batch axis in the kernel grid rather than an outer ``jax.vmap``. Both
    drop over-depth events identically, so logits and every stat stay
    bit-compatible with the reference.

    ``accum='ref'`` (the ``queue_ref`` backend) routes the same batched plan
    through the ``kernels/ref.py`` scatter oracle — slow, but the engine-
    level parity anchor the ``queue_sparse`` backend is pinned bit-exact
    against (and the only non-sparse accum honoring ``cfg.weight_bits``).
    """

    def __init__(self, accum: str = "jax"):
        if accum not in ("jax", "pallas", "ref"):
            raise ValueError(
                f"accum must be 'jax', 'pallas', or 'ref', got {accum!r}")
        self.accum = accum
        self.name = {"jax": "queue", "pallas": "queue_pallas",
                     "ref": "queue_ref"}[accum]

    @property
    def supports_batch(self) -> bool:
        """Fused accumulation is batch-native; the word-level path is not."""
        return self.accum != "jax"

    def conv_layer(self, cp, w, b, vth, cfg, raster, analog):
        if self.accum != "jax":
            # single sample == batch of one through the fused pipeline
            out, row = self.conv_layer_batch(
                cp, w, b, vth, cfg,
                None if raster is None else raster[None],
                None if analog is None else analog[None])
            return out[0], LayerStats(*(f[0] for f in row))

        model = get_neuron_model(cfg.mode)
        T = cfg.T

        if raster is not None:
            # the AEQ's segmented view is (T, C, K2, depth): move to the
            # channel-major raster only at the queue boundary
            q = aeq_from_raster(cp.fmt, jnp.moveaxis(raster, -1, 1),
                                cfg.depth)
            ev = q.counts.sum().astype(jnp.int32)
            q_words = ev
            ovf = q.overflow.astype(jnp.int32)
        else:
            q = None
            ev, q_words, ovf = _zero(), _zero(), _zero()

        step = _conv_step(cp, model, vth)
        carry = _init_carry(cp, cfg, vth, w.dtype)
        ops = _zero()
        frames = []
        for t in range(T):
            if q is not None:
                # event-driven: accumulate queued spikes into the membrane,
                # then step with just the constant bias current
                v, n = event_conv2d(carry[0], w, q, cp.fmt, t)
                carry = (v, *carry[1:])
                cur_t = jnp.broadcast_to(b, v.shape)
                ops = ops + n
            else:
                cur_t = dense_conv_hwc(analog, w) + b
                ops = ops + jnp.int32(
                    analog.size * cp.out_c * cp.kernel * cp.kernel)
            carry, sp = step(carry, cur_t)
            frames.append(sp)
        out_raster = jnp.stack(frames)

        row = LayerStats(ev, out_raster.sum().astype(jnp.int32), ops,
                         q_words, ovf)
        return out_raster, row

    def conv_layer_batch(self, cp, w, b, vth, cfg, raster, analog):
        """Fused batch-native plan: raster (B, T, H, W, C) in one kernel call.

        All B*T queue-segment sets go through ONE fused compact+accumulate
        call (the batch axis lives in the kernel grid), stats are derived
        analytically from the occupancy with the exact drop rule of
        ``compact_spikes`` (bit-identical to the word-level queue path), and
        the neuron/pool semantics come from the shared ``_conv_step`` body.
        """
        from ..kernels import ops as kops

        model = get_neuron_model(cfg.mode)
        T = cfg.T
        fmt = cp.fmt
        B = (raster if raster is not None else analog).shape[0]

        if raster is not None:
            occ = phase_occupancy(fmt, raster)         # (B, T, C, K2, P)
            keep = segment_keep(occ, cfg.depth)
            tot = (occ > 0).sum(-1)                    # (B, T, C, K2)
            capped = jnp.minimum(tot, cfg.depth)
            ev = capped.sum((1, 2, 3)).astype(jnp.int32)       # (B,)
            q_words = ev
            ovf = (tot - capped).sum((1, 2, 3)).astype(jnp.int32)

            spans = span_map(fmt, cp.in_hw)            # (K2, P) static
            ops = ((keep * spans[None, None, None]).sum((1, 2, 3, 4))
                   * cp.out_c).astype(jnp.int32)

            K2, P = occ.shape[-2:]
            # accum='ref' pins the scatter oracle as an *engine* backend —
            # the parity anchor the sparse realization is tested against —
            # and is the only non-sparse accum honoring cfg.weight_bits
            # (the quant scatter oracle)
            cur = kops.fused_spike_accum(
                occ.reshape(B * T, cp.in_c, K2, P), w,
                K=cp.kernel, n_win=fmt.n_win, bits=fmt.bits_coord,
                depth=cfg.depth, H=cp.in_hw, W=cp.in_hw,
                invalid=fmt.invalid_word,
                impl="ref" if self.accum == "ref" else None,
                weight_bits=(cfg.weight_bits if self.accum == "ref"
                             else None))
            cur = cur.reshape(B, T, cp.in_hw, cp.in_hw, cp.out_c) + b
        else:
            z = jnp.zeros((B,), jnp.int32)
            ev, q_words, ovf = z, z, z
            per_sample = analog.shape[1] * analog.shape[2] * analog.shape[3]
            ops = jnp.full((B,), T * per_sample * cp.out_c
                           * cp.kernel * cp.kernel, jnp.int32)
            c1 = jax.lax.conv_general_dilated(
                analog.astype(w.dtype), w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=PRECISION) + b
            cur = jnp.broadcast_to(c1[:, None], (B, T) + c1.shape[1:])

        step = jax.vmap(_conv_step(cp, model, vth))
        carry = _init_carry_batch(cp, cfg, vth, w.dtype, B)
        _, frames = jax.lax.scan(step, carry, jnp.moveaxis(cur, 1, 0),
                                 unroll=True)
        out_raster = jnp.moveaxis(frames, 0, 1)        # (B, T, H', W', C')

        row = LayerStats(ev, out_raster.sum((1, 2, 3, 4)).astype(jnp.int32),
                         ops, q_words, ovf)
        return out_raster, row


# --- the occupancy-gated sparse backend -----------------------------------
#
# The per-layer programs are jitted *individually* (not as one whole-plan
# jit) because the backend's dispatch is data-dependent: it measures each
# layer's surviving-event total, pulls that ONE scalar to the host, and
# dispatches the program specialized to the matching power-of-two event
# bucket. lru caches keyed on the hashable static parts (ConvPlan,
# SNNConfig, bucket) play the role engine._runner's cache plays for the
# traced backends.

@functools.lru_cache(maxsize=None)
def _sparse_stats_fn(cp: ConvPlan, depth: int):
    """Jitted occupancy/stats pass for one conv stage (the gate's input)."""
    spans = span_map(cp.fmt, cp.in_hw)

    @jax.jit
    def f(raster):                                 # (B, T, H, W, C)
        occ = phase_occupancy(cp.fmt, raster)      # (B, T, C, K2, P)
        tot = (occ > 0).sum(-1)
        capped = jnp.minimum(tot, depth)
        ev = capped.sum((1, 2, 3)).astype(jnp.int32)
        ovf = (tot - capped).sum((1, 2, 3)).astype(jnp.int32)
        keep = segment_keep(occ, depth)
        ops_ = ((keep * spans[None, None, None]).sum((1, 2, 3, 4))
                * cp.out_c).astype(jnp.int32)
        total = capped.sum().astype(jnp.int32)     # the occupancy gate
        n_act = (occ > 0).any((2, 3, 4)).sum().astype(jnp.int32)
        return occ, ev, ovf, ops_, total, n_act

    return f


@functools.lru_cache(maxsize=None)
def _sparse_layer_fn(cp: ConvPlan, cfg: SNNConfig, impl: str,
                     e_cap: int, n_rows: int | None):
    """Jitted sparse accumulate + neuron scan, specialized per event bucket."""
    from ..kernels import ops as kops

    model = get_neuron_model(cfg.mode)

    @jax.jit
    def f(occ, w, b, vth):
        B = occ.shape[0]
        K2, P = occ.shape[-2:]
        cur = kops.fused_spike_accum(
            occ.reshape(B * cfg.T, cp.in_c, K2, P), w,
            K=cp.kernel, n_win=cp.fmt.n_win, bits=cp.fmt.bits_coord,
            depth=cfg.depth, H=cp.in_hw, W=cp.in_hw,
            invalid=cp.fmt.invalid_word, impl=impl, e_cap=e_cap,
            n_rows=n_rows, weight_bits=cfg.weight_bits)
        cur = cur.reshape(B, cfg.T, cp.in_hw, cp.in_hw, cp.out_c) + b
        step = jax.vmap(_conv_step(cp, model, vth))
        carry = _init_carry_batch(cp, cfg, vth, w.dtype, B)
        _, frames = jax.lax.scan(step, carry, jnp.moveaxis(cur, 1, 0),
                                 unroll=True)
        return jnp.moveaxis(frames, 0, 1)          # (B, T, H', W', C')

    return f


@functools.lru_cache(maxsize=None)
def _sparse_analog_fn(cp: ConvPlan, cfg: SNNConfig):
    """Jitted analog (constant-current) first-layer body — no events yet."""
    model = get_neuron_model(cfg.mode)

    @jax.jit
    def f(analog, w, b, vth):
        B = analog.shape[0]
        c1 = jax.lax.conv_general_dilated(
            analog.astype(w.dtype), w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=PRECISION) + b
        cur = jnp.broadcast_to(c1[:, None], (B, cfg.T) + c1.shape[1:])
        step = jax.vmap(_conv_step(cp, model, vth))
        carry = _init_carry_batch(cp, cfg, vth, w.dtype, B)
        _, frames = jax.lax.scan(step, carry, jnp.moveaxis(cur, 1, 0),
                                 unroll=True)
        return jnp.moveaxis(frames, 0, 1)

    return f


class SparseQueueBackend:
    """Occupancy-gated sparse realization: measured work drops with rate.

    Same queue semantics (drop rule, stats, neuron registry) as the fused
    ``queue_pallas`` plan, but the accumulate runs over a compacted event
    list (``kernels/spike_sparse``) whose static capacity is picked *per
    layer, per call* from the measured surviving-event total — the
    occupancy gate. That pull of one scalar per layer to the host is what
    ``host_dispatch = True`` declares: the plan walk cannot live inside one
    whole-program jit (``_runner`` returns a Python driver instead), and
    shard_map-based data parallelism falls back to the local runner
    (``repro.parallel`` detects the flag; bit-exact per the mask contract).

    ``cfg.weight_bits`` selects the int-quantized accumulate (int8 weights,
    exact integer accumulation, fp32 dequant — the revived ``quant_matmul``
    contract) in both the conv stages and the shared output head.

    Parity: logits and stats are pinned **bit-exact** against the
    ``queue_ref`` scatter-oracle backend (and to float tolerance against
    ``dense``/``queue``) across modes × encodings × batch sizes, including
    the small-depth overflow regime — see ``tests/test_sparse.py``.
    """

    name = "queue_sparse"
    supports_batch = True
    host_dispatch = True

    def conv_layer(self, cp, w, b, vth, cfg, raster, analog):
        out, row = self.conv_layer_batch(
            cp, w, b, vth, cfg,
            None if raster is None else raster[None],
            None if analog is None else analog[None])
        return out[0], LayerStats(*(f[0] for f in row))

    def conv_layer_batch(self, cp, w, b, vth, cfg, raster, analog):
        from ..kernels import ops as kops
        from ..kernels.spike_sparse import event_bucket, max_kept_events

        B = (raster if raster is not None else analog).shape[0]
        if raster is None:
            z = jnp.zeros((B,), jnp.int32)
            per_sample = analog.shape[1] * analog.shape[2] * analog.shape[3]
            ops_ = jnp.full((B,), cfg.T * per_sample * cp.out_c
                            * cp.kernel * cp.kernel, jnp.int32)
            out = _sparse_analog_fn(cp, cfg)(analog, w, b, vth)
            row = LayerStats(z, out.sum((1, 2, 3, 4)).astype(jnp.int32),
                             ops_, z, z)
            return out, row

        occ, ev, ovf, ops_, total, n_act = _sparse_stats_fn(
            cp, cfg.depth)(raster)

        # THE occupancy gate: one scalar to the host, then dispatch the
        # program specialized to the matching power-of-two bucket
        N = B * cfg.T
        K2, P = occ.shape[-2:]
        impl = kops.default_sparse_impl()
        # audit: allow[host-sync] the occupancy gate — ONE declared scalar
        # pull per layer picks the power-of-two event bucket
        total_host = int(jax.device_get(total))
        # audit: allow[host-sync] same gate: active-row count for the
        # ragged Pallas grid
        n_act_host = int(jax.device_get(n_act))
        e_cap = event_bucket(
            total_host, max_kept_events((N, cp.in_c, K2, P), cfg.depth))
        n_rows = (min(event_bucket(n_act_host, N), N)
                  if impl.startswith("sparse_pallas") else None)
        out = _sparse_layer_fn(cp, cfg, impl, e_cap, n_rows)(occ, w, b, vth)

        row = LayerStats(ev, out.sum((1, 2, 3, 4)).astype(jnp.int32),
                         ops_, ev, ovf)
        return out, row


_BACKENDS: dict[str, Backend] = {}


def register_backend(name: str, backend: Backend, *, overwrite: bool = False):
    if name in _BACKENDS and not overwrite:
        raise ValueError(f"backend {name!r} already registered")
    _BACKENDS[name] = backend
    _runner.cache_clear()  # a new backend may shadow a cached name
    _jit_seen.clear()      # ...so first-call tracking must restart too
    return backend


def get_backend(name: str) -> Backend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered backends: "
            f"{sorted(_BACKENDS)}"
        ) from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


# ---------------------------------------------------------------------------
# Shared execution driver
# ---------------------------------------------------------------------------

def _output_layer(params_out, T: int, raster: jnp.ndarray,
                  weight_bits: int | None = None):
    """Final dense layer: accumulate Vm over all T steps, no thresholding.

    Shared verbatim by every backend — the event-driven accumulation of the
    spike raster and the vectorized matmul are the same arithmetic, and the
    stats (events = spikes arriving, adds = events * N_out) are identical.

    ``weight_bits`` (the deployed integer weight width, ``cfg.weight_bits``)
    switches the matmul to the revived ``kernels.quant_matmul`` path: binary
    spikes summed over time are exact small integers (≤ T, so int8 holds
    them whenever T ≤ 127), the weights are symmetric-quantized, the product
    accumulates exactly in int32, and one fp32 dequant scales the logits.
    """
    w, b = params_out["w"], params_out["b"]
    flat = raster.reshape(T, -1)                        # (T, HWC order)
    if weight_bits is not None and T <= 127:
        logits = _quant_head(flat.sum(0)[None], w, weight_bits)[0] + b * T
    else:
        logits = jnp.matmul(flat, w, precision=PRECISION).sum(0) + b * T
    ev = (flat > 0).sum().astype(jnp.int32)
    row = LayerStats(ev, _zero(), ev * jnp.int32(w.shape[1]), _zero(), _zero())
    return logits, row


def _quant_head(counts, w, weight_bits: int):
    """Shared int-quantized output matmul: (B, F) spike counts -> (B, N).

    Spike counts are already integers, so their "quantization" is exact
    (scale 1); only the weights lose precision. Bias and stats are left to
    the caller — only the matmul arithmetic changes.
    """
    from ..kernels import ops as kops
    from .quantization import quantize_symmetric

    w_q, w_scale = quantize_symmetric(w, weight_bits)
    return kops.quant_matmul(
        counts.astype(jnp.int8), w_q, jnp.float32(1.0), w_scale)


def _encode_input(cfg: SNNConfig, image: jnp.ndarray):
    # (H, W, C) stays channels-last: encodings are elementwise
    if cfg.input_mode == "binary":
        return encode_ttfs(image, cfg.T, cfg.input_theta), None
    if cfg.input_mode == "analog":
        return None, image
    raise ValueError(
        f"unknown input_mode {cfg.input_mode!r} (expected 'analog' or 'binary')")


def _encode_input_batch(cfg: SNNConfig, images: jnp.ndarray):
    # (B, H, W, C): the encodings are elementwise, so batching is a
    # broadcast + axis move (encode_ttfs emits time-major (T, B, ...))
    if cfg.input_mode == "binary":
        raster = encode_ttfs(images, cfg.T, cfg.input_theta)
        return jnp.moveaxis(raster, 0, 1), None
    if cfg.input_mode == "analog":
        return None, images
    raise ValueError(
        f"unknown input_mode {cfg.input_mode!r} (expected 'analog' or 'binary')")


def _execute(plan: LayerPlan, backend: Backend, cfg: SNNConfig,
             params, thresholds, image):
    if len(params) != plan.n_layers:
        raise ValueError(
            f"params pytree has {len(params)} layers but spec "
            f"{plan.spec!r} has {plan.n_layers}")
    if len(thresholds) != plan.n_layers:
        raise ValueError(
            f"thresholds list has {len(thresholds)} entries but spec "
            f"{plan.spec!r} has {plan.n_layers} layers")

    raster, analog = _encode_input(cfg, image)
    rows: list[LayerStats] = []
    for cp in plan.convs:
        w, b = params[cp.index]["w"], params[cp.index]["b"]
        raster, row = backend.conv_layer(
            cp, w, b, thresholds[cp.index], cfg, raster, analog)
        analog = None
        rows.append(row)

    logits, row = _output_layer(params[plan.out.index], cfg.T, raster,
                                cfg.weight_bits)
    rows.append(row)

    stats = SNNStats(
        events_in=jnp.stack([r.events_in for r in rows]),
        spikes_out=jnp.stack([r.spikes_out for r in rows]),
        add_ops=jnp.stack([r.add_ops for r in rows]),
        overflow=sum((r.overflow for r in rows), _zero()),
        queue_words=jnp.stack([r.queue_words for r in rows]),
    )
    return logits, stats


def _output_layer_batch(params_out, T: int, raster: jnp.ndarray,
                        weight_bits: int | None = None):
    """:func:`_output_layer` over a (B, T, ...) raster — same math, batched."""
    w, b = params_out["w"], params_out["b"]
    B = raster.shape[0]
    flat = raster.reshape(B, T, -1)
    if weight_bits is not None and T <= 127:
        logits = _quant_head(flat.sum(1), w, weight_bits) + b * T
    else:
        logits = jnp.matmul(flat, w, precision=PRECISION).sum(1) + b * T
    ev = (flat > 0).sum(axis=(1, 2)).astype(jnp.int32)
    z = jnp.zeros((B,), jnp.int32)
    row = LayerStats(ev, z, ev * jnp.int32(w.shape[1]), z, z)
    return logits, row


def _execute_batch(plan: LayerPlan, backend: Backend, cfg: SNNConfig,
                   params, thresholds, images):
    """The batched execution plan: one plan walk over (B, ...) activity.

    Same structure as :func:`_execute`, but every conv stage runs the
    backend's ``conv_layer_batch`` hook — for the fused queue pipeline that
    means the batch axis sits in the kernel grid instead of an outer
    ``jax.vmap`` — and stats come out with a leading per-sample axis
    (events_in (B, L), overflow (B,), ...), matching the vmapped layout.
    """
    if len(params) != plan.n_layers:
        raise ValueError(
            f"params pytree has {len(params)} layers but spec "
            f"{plan.spec!r} has {plan.n_layers}")
    if len(thresholds) != plan.n_layers:
        raise ValueError(
            f"thresholds list has {len(thresholds)} entries but spec "
            f"{plan.spec!r} has {plan.n_layers} layers")

    raster, analog = _encode_input_batch(cfg, images)
    rows: list[LayerStats] = []
    for cp in plan.convs:
        w, b = params[cp.index]["w"], params[cp.index]["b"]
        raster, row = backend.conv_layer_batch(
            cp, w, b, thresholds[cp.index], cfg, raster, analog)
        analog = None
        rows.append(row)

    logits, row = _output_layer_batch(params[plan.out.index], cfg.T, raster,
                                      cfg.weight_bits)
    rows.append(row)

    B = logits.shape[0]
    stats = SNNStats(
        events_in=jnp.stack([r.events_in for r in rows], axis=1),
        spikes_out=jnp.stack([r.spikes_out for r in rows], axis=1),
        add_ops=jnp.stack([r.add_ops for r in rows], axis=1),
        overflow=sum((r.overflow for r in rows), jnp.zeros((B,), jnp.int32)),
        queue_words=jnp.stack([r.queue_words for r in rows], axis=1),
    )
    return logits, stats


# ---------------------------------------------------------------------------
# Differentiable plan walk (direct SNN training — repro.training.surrogate)
# ---------------------------------------------------------------------------

def _execute_diff(plan: LayerPlan, model: NeuronModel, cfg: SNNConfig,
                  params, thresholds, image):
    """Per-sample grad-capable walk of the dense plan.

    Runs the exact dynamics of ``DenseBackend`` (one T-batched conv +
    ``lax.scan`` time loop per conv stage, shared ``_conv_step`` body) under
    a surrogate :class:`~repro.core.neuron.NeuronModel`, skipping the
    integer stats accounting that would sit dead in a gradient. Returns
    ``(step_out, rates)``:

    - ``step_out`` (T, n_out): the output layer's per-time-step membrane
      contribution; its sum over T equals the inference logits, and its
      time resolution is what the ``train``/``latency`` loss targets need.
    - ``rates`` (n_convs,): mean float spike rate per conv layer — the
      differentiable event count behind the spike-rate regularizer (the
      recorded int stats are casts and carry no gradient).
    """
    raster, analog = _encode_input(cfg, image)
    rates = []
    for cp in plan.convs:
        w, b = params[cp.index]["w"], params[cp.index]["b"]
        if raster is not None:
            cur = jax.lax.conv_general_dilated(
                raster.astype(w.dtype), w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=PRECISION) + b
        else:
            c1 = dense_conv_hwc(analog, w) + b
            cur = jnp.broadcast_to(c1, (cfg.T,) + c1.shape)
        step = _conv_step(cp, model, thresholds[cp.index])
        carry = _init_carry(cp, cfg, thresholds[cp.index], w.dtype)
        _, raster = jax.lax.scan(step, carry, cur)
        analog = None
        rates.append(raster.mean())

    out = params[plan.out.index]
    flat = raster.reshape(cfg.T, -1)
    step_out = flat @ out["w"] + out["b"]            # (T, n_out)
    return step_out, jnp.stack(rates)


def train_forward(params, thresholds, cfg: SNNConfig, images, *,
                  surrogate: str = "superspike", beta: float = 10.0):
    """Batched differentiable forward through the engine's dense plan.

    ``jax.grad`` of any scalar built from the outputs flows back through
    the ``lax.scan`` time loop via the surrogate spike derivative
    registered for ``cfg.mode`` (``neuron.surrogate_model``); the forward
    values are bit-identical to the hard dynamics, so the net being
    trained is exactly the net ``infer_batch`` will execute.

    Returns ``(step_logits (B, T, n_out), rates (B, n_convs))``. Traceable
    (compose under jit/grad); deliberately not jitted here — the training
    step owns the compilation boundary.
    """
    plan = compile_plan(cfg.spec, cfg.input_hw, cfg.input_c, cfg.compressed)
    model = surrogate_model(cfg.mode, surrogate, beta)
    walk = functools.partial(_execute_diff, plan, model, cfg)
    return jax.vmap(walk, in_axes=(None, None, 0))(
        params, tuple(thresholds), images)


@functools.lru_cache(maxsize=None)
def _runner(cfg: SNNConfig, backend_name: str, batched: bool):
    """One jit-compiled executable per (config, backend, batched) triple.

    Batched execution prefers a backend's native batched plan
    (``supports_batch`` + ``conv_layer_batch``) — the fused queue pipeline —
    and falls back to ``jax.vmap`` of the per-sample program otherwise.
    """
    backend = get_backend(backend_name)
    plan = compile_plan(cfg.spec, cfg.input_hw, cfg.input_c, cfg.compressed)

    if getattr(backend, "host_dispatch", False):
        # Occupancy-gated backends pull a scalar to the host between layers
        # to pick the event bucket, so the plan walk cannot be traced as one
        # program. Return a plain Python driver; each per-layer program is
        # individually jitted and bucket-cached inside the backend.
        if batched and getattr(backend, "supports_batch", False):
            def run(params, thresholds, images):
                return _execute_batch(plan, backend, cfg, params,
                                      tuple(thresholds), images)
        else:
            def run_one(params, thresholds, image):
                return _execute(plan, backend, cfg, params, tuple(thresholds),
                                image)

            if batched:
                def run(params, thresholds, images):
                    outs = [run_one(params, thresholds, im) for im in images]
                    return jax.tree.map(lambda *a: jnp.stack(a), *outs)
            else:
                run = run_one
        return run

    if batched and getattr(backend, "supports_batch", False):
        def run(params, thresholds, images):
            return _execute_batch(plan, backend, cfg, params,
                                  tuple(thresholds), images)
    else:
        def run(params, thresholds, image):
            return _execute(plan, backend, cfg, params, tuple(thresholds),
                            image)

        if batched:
            run = jax.vmap(run, in_axes=(None, None, 0))
    # Stable, backend-qualified program name: the persistent compilation
    # cache (compile_cache.py) keys on the serialized HLO, whose module
    # name comes from here — a deterministic name keeps the key identical
    # across processes (no lambda/line-number noise) and makes cache
    # entries and profiles attributable to their backend.
    suffix = "_batch" if batched else ""
    run.__name__ = f"run_{backend_name}{suffix}"
    run.__qualname__ = run.__name__
    return jax.jit(run)


# Cold-start observability: jax's jit cache compiles lazily on the first
# call per input *shape*, so the engine tracks first-calls per
# (config, backend, B) itself — ``engine.jit_compile`` spans time that
# first call (trace + XLA compile + dispatch: the cold-start number
# ROADMAP item 3 needs as its baseline) and the hit/miss counters expose
# the cache behaviour load tests care about. Host-side bookkeeping only;
# the traced programs are untouched.
_jit_seen: set = set()


def _first_call(key) -> bool:
    if key in _jit_seen:
        return False
    _jit_seen.add(key)
    return True


def plan_size(cfg: SNNConfig, B: int) -> dict:
    """The static size of the plan a call of ``B`` samples compiles.

    ``stages``: its conv stages; ``event_pairs``: the (sample x time step,
    input channel) pairs its spike-fed conv stages take in, sum of
    ``B * T * in_c`` (the first stage is fed the image as a current unless
    the input is spike-encoded). Attributes of the compile spans, so a
    traced set-up puts compile time down to the plan that cost it.
    """
    plan = compile_plan(cfg.spec, cfg.input_hw, cfg.input_c, cfg.compressed)
    fed = plan.convs if cfg.input_mode == "binary" else plan.convs[1:]
    return {"stages": len(plan.convs),
            "event_pairs": B * cfg.T * sum(cp.in_c for cp in fed)}


def infer(params, thresholds, cfg: SNNConfig, image, *,
          backend: str = "dense"):
    """Run one (H, W, C) sample; returns ``(logits, SNNStats)``."""
    run = _runner(cfg, backend, False)
    if _first_call((cfg, backend, None)):
        obs.counter("engine.jit_miss")
        with obs.span("engine.jit_compile", backend=backend, B=0,
                      spec=cfg.spec, **plan_size(cfg, 1)):
            return run(params, tuple(thresholds), image)
    obs.counter("engine.jit_hit")
    return run(params, tuple(thresholds), image)


# Batch dispatch override, installed (and restored) by
# ``repro.parallel.use_mesh``: when set, ``infer_batch`` routes through the
# data-parallel sharded executor instead of the local cached runner. The
# override MUST be bit-exact vs the local path (the mask contract makes the
# sharded one so), which is why callers above the engine — the study collect
# cache in particular — never need to know whether a mesh was active.
_batch_dispatch = None


def infer_batch(params, thresholds, cfg: SNNConfig, images, *,
                backend: str = "dense"):
    """Run a (N, H, W, C) batch; returns batched (logits, stats).

    Backends with a native batched plan (``queue_pallas``) execute it here —
    batch axis in the kernel grid; everything else is vmapped. Either way
    stats come back with a leading per-sample axis.

    **Mask contract** (what ``repro.serve``'s padded buckets rely on): the
    batch axis is sample-independent in every backend — convs batch over B,
    the time loop is vmapped/batched per sample, and the fused queue kernel
    grids index (b, t) pairs independently — so row ``i`` of a batch is
    bit-identical no matter which (or how many) other samples share the
    batch. Padding a batch with junk rows and slicing the valid prefix
    (:func:`infer_batch_masked`) therefore equals the unpadded call exactly,
    logits AND stats; ``tests/test_serving.py`` pins this per bucket size.
    The same independence is what makes data-parallel sharding safe:
    ``repro.parallel`` splits the batch axis over a device mesh bit-exactly,
    and inside a ``parallel.use_mesh(mesh)`` block this function routes
    through that sharded executor automatically.
    """
    if _batch_dispatch is not None:
        return _batch_dispatch(params, thresholds, cfg, images,
                               backend=backend)
    run = _runner(cfg, backend, True)
    B = images.shape[0]
    if _first_call((cfg, backend, B)):
        obs.counter("engine.jit_miss")
        with obs.span("engine.jit_compile", backend=backend, B=B,
                      spec=cfg.spec, **plan_size(cfg, B)):
            return run(params, tuple(thresholds), images)
    obs.counter("engine.jit_hit")
    return run(params, tuple(thresholds), images)


def batch_runner(cfg: SNNConfig, backend: str = "dense"):
    """The cached jit executable behind :func:`infer_batch`.

    Exposed so callers that manage their own compiled-plan caches
    (``repro.serve.registry`` AOT-lowers one executable per padded bucket
    size) can reach the exact program ``infer_batch`` would run.
    """
    return _runner(cfg, backend, True)


def _check_n_valid(n_valid, B: int) -> None:
    if not isinstance(n_valid, int) or not 0 < n_valid <= B:
        raise ValueError(
            f"n_valid must be an int in [1, {B}], got {n_valid!r}")


def slice_valid(logits, stats, n_valid: int):
    """Drop padded slots: keep the first ``n_valid`` rows of batched output.

    ``n_valid`` must be a host-side int (the slice happens outside jit, so
    bucketed callers never trigger a retrace).
    """
    _check_n_valid(n_valid, logits.shape[0])
    if n_valid == logits.shape[0]:
        return logits, stats
    return logits[:n_valid], jax.tree.map(lambda a: a[:n_valid], stats)


def infer_batch_masked(params, thresholds, cfg: SNNConfig, images, n_valid, *,
                       backend: str = "dense"):
    """Run a padded (B, H, W, C) bucket; return only the valid prefix.

    The serving entry point: ``images`` is a power-of-two-sized bucket whose
    first ``n_valid`` rows are real requests and whose tail is padding. Per
    the mask contract on :func:`infer_batch`, the returned logits/stats are
    bit-identical to an unpadded ``infer_batch`` over ``images[:n_valid]``
    while hitting the (config, backend, B)-shaped jit cache of the bucket.
    """
    _check_n_valid(n_valid, images.shape[0])   # before spending the batch
    logits, stats = infer_batch(params, thresholds, cfg, images,
                                backend=backend)
    return slice_valid(logits, stats, n_valid)


register_backend("dense", DenseBackend())
register_backend("dense_unrolled", DenseBackend(unroll=True))
register_backend("queue", QueueBackend())
register_backend("queue_pallas", QueueBackend(accum="pallas"))
register_backend("queue_ref", QueueBackend(accum="ref"))
register_backend("queue_sparse", SparseQueueBackend())

# Declared trace intent per backend, verified by ``python -m repro.audit``
# (see docs/CONTRACTS.md). ``cross_batch_reductions`` is the mask contract
# stated structurally: the number of reductions over the batch axis the
# backend's jitted programs may contain — zero for every traced backend
# (padded rows must be bit-inert), and exactly two for the sparse backend's
# occupancy-gate stats pass (the global event total and the active-row
# count, both feeding the bucket choice, never the numerics). A backend
# registered without a contract fails the audit at lookup time.
BACKEND_CONTRACTS: dict[str, BackendContract] = {
    # dense additionally owns the differentiable training walk
    # (``train_forward``); its loss forward reduces over the batch exactly
    # twice by design — the batch-mean classification loss and the
    # batch-mean spike-rate regularizer (see ``audit.probe.trace_train_step``)
    "dense": BackendContract(name="dense", train_loss_reductions=2),
    "dense_unrolled": BackendContract(name="dense_unrolled"),
    "queue": BackendContract(name="queue"),
    "queue_pallas": BackendContract(name="queue_pallas"),
    "queue_ref": BackendContract(name="queue_ref", quant=QuantContract()),
    "queue_sparse": BackendContract(
        name="queue_sparse", cross_batch_reductions=2, host_dispatch=True,
        quant=QuantContract(), allowed_host_syncs=("occupancy-gate",)),
}

# a re-registered neuron mode must invalidate compiled runners too, or a
# cached executable would keep executing the old fire function — including
# the sparse backend's per-layer bucket caches, which close over the model
_on_registry_change.append(_runner.cache_clear)
_on_registry_change.append(_sparse_layer_fn.cache_clear)
_on_registry_change.append(_sparse_analog_fn.cache_clear)
_on_registry_change.append(_jit_seen.clear)
