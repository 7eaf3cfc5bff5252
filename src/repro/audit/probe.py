"""Probe traces: tiny, collision-free jaxprs of every engine path.

The jaxpr rules reason about *sizes* (e.g. "a reduction eliminated a
batch-sized axis"), so the probe geometry is chosen so no program dimension
can collide with a batch dimension:

- spec ``4C3-P2-6`` at 8x8x1 input, T=4, depth=8 — every static dim the
  trace can contain is in {1, 2, 3, 4, 6, 8, 9, 16, 64};
- batch size ``B_PROBE = 13`` (prime), so the batch-tainted sizes are
  exactly {13, 52 = B*T} and a size-13/52 axis in a trace *must* be the
  batch (or the fused batch*time) axis.

All probe inputs are zeros — the traces are never executed, only walked
(the recompile harness in ``audit.harness`` is the one place the audit
runs code, and it builds its own inputs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import engine

B_PROBE = 13
PROBE_SPEC = "4C3-P2-6"
PROBE_HW = 8
PROBE_C = 1
PROBE_T = 4
PROBE_DEPTH = 8


def probe_config(**overrides) -> engine.SNNConfig:
    cfg = engine.SNNConfig(spec=PROBE_SPEC, input_hw=PROBE_HW,
                           input_c=PROBE_C, T=PROBE_T, depth=PROBE_DEPTH)
    return cfg._replace(**overrides) if overrides else cfg


def batch_tainted_sizes(cfg: engine.SNNConfig, B: int = B_PROBE) -> frozenset:
    """Axis sizes that can only come from the batch (or batch*time) axis."""
    return frozenset({B, B * cfg.T})


def probe_params(plan: engine.LayerPlan):
    """Zero params pytree matching the plan (pool slots are empty dicts)."""
    params: list[dict] = [{} for _ in range(plan.n_layers)]
    for cp in plan.convs:
        params[cp.index] = {
            "w": jnp.zeros((cp.kernel, cp.kernel, cp.in_c, cp.out_c),
                           jnp.float32),
            "b": jnp.zeros((cp.out_c,), jnp.float32),
        }
    params[plan.out.index] = {
        "w": jnp.zeros((plan.out.n_in, plan.out.n_out), jnp.float32),
        "b": jnp.zeros((plan.out.n_out,), jnp.float32),
    }
    return params


def probe_thresholds(plan: engine.LayerPlan):
    return tuple(jnp.float32(1.0) for _ in range(plan.n_layers))


def probe_images(cfg: engine.SNNConfig, B: int = B_PROBE):
    return jnp.zeros((B, cfg.input_hw, cfg.input_hw, cfg.input_c),
                     jnp.float32)


def trace_backend(backend_name: str, cfg: engine.SNNConfig | None = None,
                  B: int = B_PROBE):
    """ClosedJaxpr of the engine's batched plan for one traced backend."""
    cfg = cfg or probe_config()
    plan = engine.compile_plan(cfg.spec, cfg.input_hw, cfg.input_c,
                               cfg.compressed)
    runner = engine.batch_runner(cfg, backend_name)
    return jax.make_jaxpr(runner)(
        probe_params(plan), probe_thresholds(plan), probe_images(cfg, B))


def trace_sparse_pieces(cfg: engine.SNNConfig | None = None,
                        B: int = B_PROBE) -> dict:
    """The host-dispatch backend's individually-jitted per-layer programs.

    ``queue_sparse`` cannot be traced as one batched plan (its plan walk
    pulls the occupancy total to the host between layers), so the audit
    walks each jitted piece: the stats/gate pass (which owns the only two
    declared cross-batch reductions), one bucket specialization of the
    sparse layer fn, and the analog first-layer body.
    """
    cfg = cfg or probe_config()
    plan = engine.compile_plan(cfg.spec, cfg.input_hw, cfg.input_c,
                               cfg.compressed)
    cp = plan.convs[0]
    fmt = cp.fmt
    K2 = cp.kernel * cp.kernel
    P = fmt.n_win * fmt.n_win
    raster = jnp.zeros((B, cfg.T, cp.in_hw, cp.in_hw, cp.in_c), jnp.float32)
    occ = jnp.zeros((B, cfg.T, cp.in_c, K2, P), jnp.int32)
    w = jnp.zeros((cp.kernel, cp.kernel, cp.in_c, cp.out_c), jnp.float32)
    b = jnp.zeros((cp.out_c,), jnp.float32)
    vth = jnp.float32(1.0)
    analog = jnp.zeros((B, cp.in_hw, cp.in_hw, cp.in_c), jnp.float32)
    return {
        "engine._sparse_stats_fn": jax.make_jaxpr(
            engine._sparse_stats_fn(cp, cfg.depth))(raster),
        "engine._sparse_layer_fn": jax.make_jaxpr(
            engine._sparse_layer_fn(cp, cfg, "sparse", 64, None))(
                occ, w, b, vth),
        "engine._sparse_analog_fn": jax.make_jaxpr(
            engine._sparse_analog_fn(cp, cfg))(analog, w, b, vth),
    }


def trace_train_step(cfg: engine.SNNConfig | None = None,
                     B: int = B_PROBE) -> dict:
    """Traces of the direct-training path (``repro.training.surrogate``).

    Two programs at the probe geometry:

    - ``training.loss_fn`` — the loss *forward* (surrogate spike dynamics +
      count target + rate regularizer). Batch purity runs against
      ``BackendContract.train_loss_reductions`` on this one: the loss's own
      batch-mean reductions are the only legal batch eliminations.
    - ``training.train_step`` — the full value_and_grad + AdamW update.
      Only dtype/host-sync rules apply: the backward pass legitimately
      contracts the batch axis into every weight gradient.
    """
    from ..training.surrogate import make_snn_train_step
    from ..training.optimizer import adamw_init

    cfg = cfg or probe_config()
    plan = engine.compile_plan(cfg.spec, cfg.input_hw, cfg.input_c,
                               cfg.compressed)
    params = probe_params(plan)
    step, loss_fn = make_snn_train_step(
        cfg, probe_thresholds(plan), target="count", rate_reg=0.01)
    images = probe_images(cfg, B)
    labels = jnp.zeros((B,), jnp.int32)
    opt = adamw_init(params)
    return {
        "training.loss_fn[count+rate_reg]": jax.make_jaxpr(loss_fn)(
            params, images, labels),
        "training.train_step": jax.make_jaxpr(step)(
            params, opt, images, labels),
    }


def trace_quant_kernels(cfg: engine.SNNConfig | None = None) -> dict:
    """Traces of every int8-weight path, checked against QuantContract."""
    from ..kernels import ref as kref
    from ..kernels.spike_sparse import fused_spike_accum_sparse

    cfg = cfg or probe_config(weight_bits=8)
    plan = engine.compile_plan(cfg.spec, cfg.input_hw, cfg.input_c,
                               cfg.compressed)
    cp = plan.convs[0]
    K2 = cp.kernel * cp.kernel
    P = cp.fmt.n_win * cp.fmt.n_win
    N = B_PROBE * cfg.T
    occ = jnp.zeros((N, cp.in_c, K2, P), jnp.int32)
    w = jnp.zeros((cp.kernel, cp.kernel, cp.in_c, cp.out_c), jnp.float32)
    geo = dict(K=cp.kernel, n_win=cp.fmt.n_win, depth=cfg.depth,
               H=cp.in_hw, W=cp.in_hw)
    a_q = jnp.zeros((B_PROBE, plan.out.n_in), jnp.int8)
    b_q = jnp.zeros((plan.out.n_in, plan.out.n_out), jnp.int8)
    one = jnp.float32(1.0)
    return {
        "kernels.fused_spike_accum_sparse[q8]": jax.make_jaxpr(
            functools.partial(fused_spike_accum_sparse, e_cap=64,
                              weight_bits=8, **geo))(occ, w),
        "kernels.ref.fused_spike_accum_quant_ref": jax.make_jaxpr(
            functools.partial(kref.fused_spike_accum_quant_ref,
                              weight_bits=8, **geo))(occ, w),
        "kernels.ref.quant_matmul_ref": jax.make_jaxpr(
            kref.quant_matmul_ref)(a_q, b_q, one, one),
        "engine._quant_head[q8]": jax.make_jaxpr(
            functools.partial(engine._quant_head, weight_bits=8))(
                jnp.zeros((B_PROBE, plan.out.n_in), jnp.float32),
                jnp.zeros((plan.out.n_in, plan.out.n_out), jnp.float32)),
    }


def trace_pallas_kernels(cfg: engine.SNNConfig | None = None) -> dict:
    """jaxprs containing each Pallas kernel's ``pallas_call`` equation.

    Tracing (``make_jaxpr``) builds the kernel jaxpr without executing or
    Mosaic-lowering anything, so this works on any host with the
    ``pallas.tpu`` module importable; hosts without it get an empty dict
    (the caller emits an info note instead of findings).
    """
    from ..kernels import event_accum as ea
    from ..kernels import spike_pipeline as sp
    from ..kernels import spike_sparse as ss

    cfg = cfg or probe_config()
    plan = engine.compile_plan(cfg.spec, cfg.input_hw, cfg.input_c,
                               cfg.compressed)
    cp = plan.convs[0]
    K = cp.kernel
    K2 = K * K
    P = cp.fmt.n_win * cp.fmt.n_win
    N = B_PROBE * cfg.T
    occ = jnp.zeros((N, cp.in_c, K2, P), jnp.int32)
    w = jnp.zeros((K, K, cp.in_c, cp.out_c), jnp.float32)
    geo = dict(K=K, n_win=cp.fmt.n_win, depth=cfg.depth, H=cp.in_hw,
               W=cp.in_hw)
    words = jnp.zeros((cp.in_c, K2, cfg.depth), jnp.int32)
    counts = jnp.zeros((cp.in_c, K2), jnp.int32)
    vm = jnp.zeros((cp.in_hw, cp.in_hw, cp.out_c), jnp.float32)
    traces = {}
    try:
        traces["kernels.spike_pipeline.fused_spike_accum_pallas"] = (
            jax.make_jaxpr(functools.partial(
                sp.fused_spike_accum_pallas, **geo))(occ, w))
        traces["kernels.spike_sparse.fused_spike_accum_sparse_pallas"] = (
            jax.make_jaxpr(functools.partial(
                ss.fused_spike_accum_sparse_pallas, bits=cp.fmt.bits_coord,
                invalid=cp.fmt.invalid_word, **geo))(occ, w))
        traces["kernels.event_accum.event_accum"] = (
            jax.make_jaxpr(functools.partial(
                ea.event_accum, K=K, n_win=cp.fmt.n_win,
                bits=cp.fmt.bits_coord))(words, counts, w, vm))
    except RuntimeError:  # pragma: no cover - pallas-tpu unavailable
        return {}
    return traces
