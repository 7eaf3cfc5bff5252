"""Pallas kernel microbenchmarks.

CPU wall times are interpret-mode numbers (the kernel body in Python) — they
validate logic, not TPU speed; the derived column carries the structural
metrics that matter for the TPU roofline: events/step, adds/event, bytes
moved per event word.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aeq, encoding
from repro.kernels import ops, ref

from .common import emit, timed


def event_accum_bench():
    fmt = encoding.make_format(28, 3)
    rng = np.random.default_rng(0)
    raster = (rng.random((1, 4, 28, 28)) < 0.15).astype(np.float32)
    q = aeq.aeq_from_raster(fmt, jnp.asarray(raster), 64)
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 32)), jnp.float32)
    vm = jnp.zeros((28, 28, 32), jnp.float32)
    kw = dict(K=3, n_win=fmt.n_win, bits=fmt.bits_coord)

    us_ref = timed(lambda: ops.event_accum(
        q.words[0], q.counts[0], w, vm, backend="ref", **kw))
    n_ev = int(q.counts[0].sum())
    emit("kernel/event_accum_ref", us_ref,
         f"events={n_ev};adds_per_event={9 * 32};"
         f"phase_parallel=9;lanes=32")

    # interpret-mode Pallas timing on a reduced tile (the Python-loop
    # interpreter is ~10^4x slower than the lowered kernel; logic-only)
    fmt_s = encoding.make_format(12, 3)
    raster_s = (rng.random((1, 2, 12, 12)) < 0.15).astype(np.float32)
    q_s = aeq.aeq_from_raster(fmt_s, jnp.asarray(raster_s), 16)
    w_s = jnp.asarray(rng.normal(size=(3, 3, 2, 8)), jnp.float32)
    vm_s = jnp.zeros((12, 12, 8), jnp.float32)
    kw_s = dict(K=3, n_win=fmt_s.n_win, bits=fmt_s.bits_coord)
    us_k = timed(lambda: ops.event_accum(q_s.words[0], q_s.counts[0],
                                         w_s, vm_s, **kw_s),
                 repeats=1, warmup=1)
    emit("kernel/event_accum_pallas_interp", us_k,
         f"events={int(q_s.counts[0].sum())};"
         f"vmem_tile_bytes={12 * 12 * 8 * 4}")


def spike_compact_bench():
    fmt = encoding.make_format(28, 3)
    rng = np.random.default_rng(1)
    occ = (rng.random((32, fmt.n_win ** 2)) < 0.25).astype(np.int32)
    kw = dict(n_win=fmt.n_win, bits=fmt.bits_coord, depth=64,
              invalid=fmt.invalid_word)
    us = timed(lambda: ops.spike_compact(jnp.asarray(occ), backend="ref", **kw))
    emit("kernel/spike_compact_ref", us,
         f"rows={occ.shape[0]};events={int(occ.sum())};word_bits={fmt.word_bits}")


def quant_matmul_bench():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.integers(-127, 127, (256, 256)), jnp.int8)
    b = jnp.asarray(rng.integers(-127, 127, (256, 256)), jnp.int8)
    s = jnp.float32(0.01)
    us_ref = timed(lambda: ops.quant_matmul(a, b, s, s, backend="ref"))
    macs = 256 ** 3
    emit("kernel/quant_matmul_ref", us_ref,
         f"macs={macs};mxu_blocks=128x128x128;"
         f"tput_gmacs={macs / us_ref / 1e3:.2f}")


def snn_engine_scan_bench():
    """Engine dense backend (one T-batched conv per layer + ``lax.scan``
    time loop) vs the seed implementation's unrolled per-step Python loop
    (the ``dense_unrolled`` reference backend), on the MNIST-class spec.

    Both numbers go through the same engine, so the delta isolates the time
    loop: trace+compile cost (the unrolled loop traces T copies of every
    layer; the scan traces one body) and steady-state batch latency. Timing
    uses min-of-N, the standard noise-robust estimator for shared boxes.
    """
    import time

    from repro.core import engine, snn_model
    from repro.core.snn_model import SNNConfig

    spec = "32C3-P2-32C3-P2-10"
    params = snn_model.init_params(jax.random.PRNGKey(0), spec, 28, 1)
    th = [jnp.asarray(1.0)] * len(snn_model.parse_spec(spec))
    rng = np.random.default_rng(4)
    imgs = jnp.asarray(rng.random((16, 28, 28, 1)), jnp.float32)

    from ._seed_reference import seed_dense_infer_batch

    for T in (4, 16):
        cfg = SNNConfig(spec=spec, input_hw=28, input_c=1, T=T, depth=256,
                        mode="mttfs_cont")
        seed_fn = jax.jit(
            lambda ims: seed_dense_infer_batch(params, th, cfg, ims))
        fns = {
            "dense": lambda: engine.infer_batch(params, th, cfg, imgs,
                                                backend="dense"),
            "dense_unrolled": lambda: engine.infer_batch(
                params, th, cfg, imgs, backend="dense_unrolled"),
            "seed": lambda: seed_fn(imgs),
        }
        first, mins = {}, {}
        for name, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())      # trace + compile + first run
            first[name] = (time.perf_counter() - t0) * 1e3
            mins[name] = float("inf")
        for _ in range(12):                  # interleaved: same load for all
            for name, fn in fns.items():
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                mins[name] = min(mins[name], time.perf_counter() - t0)
        for name in fns:
            emit(f"kernel/snn_engine_{name}_T{T}", mins[name] * 1e6,
                 f"spec={spec};batch=16;first_call_ms={first[name]:.0f}")

        emit(f"kernel/snn_engine_scan_speedup_T{T}", 0.0,
             f"steady_vs_seed_x={mins['seed'] / mins['dense']:.2f};"
             f"first_call_vs_seed_x={first['seed'] / first['dense']:.2f};"
             f"steady_vs_unrolled_x="
             f"{mins['dense_unrolled'] / mins['dense']:.2f}")


def snn_engine_queue_bench():
    """The fused batch-native queue pipeline vs its two predecessors.

    Two comparisons, both interleaved min-of-N (the box is load-noisy;
    min-of-N under interleaving is the noise-robust estimator):

    1. Kernel level, at paper scale (28x28 first conv of the MNIST net,
       D=256): the fused compiled pipeline vs the retired interpreter path
       (``kernels/event_accum`` with interpret=True — what ``queue_pallas``
       executed before the fusion). This is the ``vs_interp`` speedup row
       the event path's "real fast path" claim rests on.
    2. Engine level, full MNIST spec at batch 16: ``queue_pallas`` (one
       batched plan, batch axis in the kernel grid) vs ``dense`` and vs the
       word-level ``queue`` reference under its outer per-sample vmap.
    """
    import time

    from repro.core import aeq, encoding, engine, snn_model
    from repro.core.snn_model import SNNConfig
    from repro.kernels import ops

    def interleaved_min(fns, rounds, first_out=None):
        mins = {}
        for name, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())      # trace + compile + first run
            if first_out is not None:
                first_out[name] = (time.perf_counter() - t0) * 1e3
            mins[name] = float("inf")
        for _ in range(rounds):              # interleaved: same load for all
            for name, fn in fns.items():
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                mins[name] = min(mins[name], time.perf_counter() - t0)
        return mins

    # --- 1. kernel level: fused compiled vs interpreter, paper scale ------
    hw, c_in, c_out, depth = 28, 1, 32, 256
    fmt = encoding.make_format(hw, 3)
    rng = np.random.default_rng(7)
    raster = (rng.random((1, c_in, hw, hw)) < 0.15).astype(np.float32)
    q = aeq.aeq_from_raster(fmt, jnp.asarray(raster), depth)
    occ = aeq.phase_occupancy(
        fmt, jnp.moveaxis(jnp.asarray(raster), 1, -1))   # (1, C, K2, P)
    w = jnp.asarray(rng.normal(size=(3, 3, c_in, c_out)), jnp.float32)
    vm = jnp.zeros((hw, hw, c_out), jnp.float32)
    kw = dict(K=3, n_win=fmt.n_win, bits=fmt.bits_coord)

    from repro.kernels.event_accum import event_accum as raw_event_accum

    mins = interleaved_min({
        "fused": lambda: ops.fused_spike_accum(
            occ, w, depth=depth, H=hw, W=hw, invalid=fmt.invalid_word, **kw),
        # interpret=True pinned explicitly: this row IS the interpreter
        # baseline, whatever the platform
        "interp": lambda: raw_event_accum(q.words[0], q.counts[0], w, vm,
                                          interpret=True, **kw),
    }, rounds=4)
    emit("kernel/snn_queue_fused_paper_scale", mins["fused"] * 1e6,
         f"hw={hw};c_out={c_out};depth={depth};"
         f"events={int(q.counts.sum())};impl={ops.default_spike_impl()}")
    emit("kernel/snn_queue_interp_paper_scale", mins["interp"] * 1e6,
         f"hw={hw};c_out={c_out};depth={depth};impl=pallas_interpret")
    emit("kernel/snn_queue_fused_vs_interp", 0.0,
         f"steady_vs_interp_x={mins['interp'] / mins['fused']:.1f};"
         f"paper_scale=28x28xC{c_in}toC{c_out}_D{depth}")

    # --- 2. engine level: batched plan vs dense and vmapped queue ---------
    spec = "32C3-P2-32C3-P2-10"
    params = snn_model.init_params(jax.random.PRNGKey(0), spec, 28, 1)
    th = [jnp.asarray(1.0)] * len(snn_model.parse_spec(spec))
    imgs = jnp.asarray(np.random.default_rng(8).random((16, 28, 28, 1)),
                       jnp.float32)
    cfg = SNNConfig(spec=spec, input_hw=28, input_c=1, T=4, depth=256,
                    mode="mttfs_cont", input_mode="binary")
    first, fns = {}, {
        "fused_batch": lambda: engine.infer_batch(
            params, th, cfg, imgs, backend="queue_pallas"),
        "queue_vmap": lambda: engine.infer_batch(
            params, th, cfg, imgs, backend="queue"),
        "dense": lambda: engine.infer_batch(
            params, th, cfg, imgs, backend="dense"),
    }
    mins = interleaved_min(fns, rounds=8, first_out=first)
    for name in fns:
        emit(f"kernel/snn_queue_engine_{name}_T4", mins[name] * 1e6,
             f"spec={spec};batch=16;first_call_ms={first[name]:.0f}")
    emit("kernel/snn_queue_engine_speedup_T4", 0.0,
         f"steady_vs_queue_vmap_x={mins['queue_vmap'] / mins['fused_batch']:.2f};"
         f"steady_vs_dense_x={mins['dense'] / mins['fused_batch']:.2f}")


def snn_sparse_rate_sweep_bench():
    """Measured latency vs spike rate on the occupancy-gated sparse kernel.

    The success metric of the sparse realization: because the event budget
    (``e_cap``) is a power-of-two bucket over the *measured* surviving-event
    total, the dispatched program's work shrinks with activity, so measured
    ``us_per_call`` must fall monotonically from rate 0.6 to 0.02 — where
    the dense-work fused realization stays flat on the same occupancies.
    One interleaved min-of-N run shared with ``break_even`` (which reads the
    sparse-vs-dense crossing off the same rows).
    """
    from .common import sparse_rate_sweep

    rows = sparse_rate_sweep()
    for r in rows:
        emit(f"kernel/sparse_rate_sweep/rate_{r['rate']:.3f}",
             r["sparse_us"],
             f"events={r['events']};e_cap={r['e_cap']};"
             f"dense_us={r['dense_us']:.1f};impl={r['sparse_impl']}")

    times = [r["sparse_us"] for r in rows]        # rates descend hi -> lo
    dense = [r["dense_us"] for r in rows]
    decreasing = all(a > b for a, b in zip(times, times[1:]))
    emit("kernel/sparse_rate_sweep/monotonic", 0.0,
         f"strictly_decreasing={decreasing};"
         f"hi_lo_speedup_x={times[0] / times[-1]:.2f};"
         f"dense_flat_x={max(dense) / min(dense):.2f}")


ALL = [event_accum_bench, spike_compact_bench, quant_matmul_bench,
       snn_engine_scan_bench, snn_engine_queue_bench,
       snn_sparse_rate_sweep_bench]
