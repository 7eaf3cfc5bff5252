"""TPU energy/latency model — the re-target of the paper's Vivado power study.

The paper's energy numbers come from vector-based Vivado estimation: count
what actually toggles (BRAM reads, signals) for *each input sample*. Our
analogue counts what actually executes per sample — SNN work is
event-proportional (SNNStats), CNN work is static — and prices it with
energy-per-operation constants.

Constants (order-of-magnitude, documented sources):
  - Horowitz, "Computing's energy problem", ISSCC 2014 (45 nm: fp32 add
    0.9 pJ, int32 add 0.1 pJ, DRAM ~20-40 pJ/B, SRAM ~1-2 pJ/B for MB-scale)
  - TPU-generation scaling (~7 nm): logic ~8x cheaper than 45 nm
  - HBM2e interface energy ~2-5 pJ/bit -> we use 15 pJ/B end-to-end
  - Jouppi et al., TPUv4 ISCA 2023 for system-level sanity (~1 pJ/FLOP wall)

Absolute joules are model outputs, not measurements; all *comparisons*
(SNN vs CNN, compressed vs not, HBM- vs VMEM-resident) hold under any
constant set with HBM >> VMEM >> register and mult > add — the same
qualitative structure the paper's Table 4 shows (BRAM dominates).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

# --- energy constants [pJ] -------------------------------------------------
E_FP32_ADD = 0.11       # membrane potential accumulate (SNN is add-only)
E_BF16_MAC = 0.25       # dense MXU multiply-accumulate
E_INT8_MAC = 0.07       # quantized MXU multiply-accumulate
E_HBM_BYTE = 15.0       # HBM read/write per byte
E_VMEM_BYTE = 0.8       # on-chip vector memory per byte
E_REG_BYTE = 0.05       # register file per byte

# --- TPU v5e machine constants -------------------------------------------
PEAK_BF16_FLOPS = 197e12
PEAK_INT8_OPS = 394e12
HBM_BW = 819e9
CLOCK_HZ = 940e6
STATIC_POWER_W = 60.0   # per-chip baseline (idle+leakage share), for FPS/W


class EnergyBreakdown(NamedTuple):
    compute_pj: jnp.ndarray
    hbm_pj: jnp.ndarray
    vmem_pj: jnp.ndarray
    total_pj: jnp.ndarray
    latency_s: jnp.ndarray

    @property
    def total_j(self):
        return self.total_pj * 1e-12

    def fps_per_w(self):
        """Frames/s/W at the latency-implied power (paper's FPS/W metric)."""
        power = self.total_j / self.latency_s
        return 1.0 / (self.latency_s * (power + STATIC_POWER_W))


def snn_energy(
    stats,
    *,
    word_bytes: int = 1,
    mem_bytes: int = 4,
    vmem_resident: bool = True,
    events_per_cycle: int = 9,
    lanes: int = 128,
) -> EnergyBreakdown:
    """Energy/latency for one SNN inference from its SNNStats.

    - every add_op is a fp32 accumulate (multiplier-less, Sec. 2.1.1)
    - every event is written once + read once from the queue memory
      (word_bytes: 1 with compressed encoding, 2/4 unpacked — Sec. 5.2)
    - membrane potentials live in VMEM (vmem_resident=True, the analogue of
      the paper's LUTRAM move) or HBM (BRAM-like spill)
    - throughput: events_per_cycle events/cycle (the K^2 conflict-free
      phases), each driving `lanes` output-channel accumulates

    The arithmetic follows its input: numpy counts are priced in numpy on
    the host, jax counts in jax. Each per-sample sum keeps the count's own
    dtype, so int32 counts wrap in int32 either way.
    """
    xp = np if isinstance(stats.add_ops, np.ndarray) else jnp

    def total(counts):
        return counts.sum(-1, dtype=counts.dtype).astype(xp.float32)

    adds = total(stats.add_ops)
    events = total(stats.events_in)
    spikes = total(stats.spikes_out)

    compute = adds * E_FP32_ADD
    queue_bytes = (events + spikes) * word_bytes
    mem_traffic = adds * mem_bytes  # each accumulate reads+writes a potential
    if vmem_resident:
        hbm = queue_bytes * E_HBM_BYTE * 0.0  # queues stay on-chip too
        vmem = (queue_bytes + mem_traffic) * E_VMEM_BYTE
    else:
        hbm = (queue_bytes + mem_traffic) * E_HBM_BYTE
        vmem = xp.zeros_like(hbm)

    cycles = xp.maximum(adds / (events_per_cycle * lanes), events)
    latency = cycles / CLOCK_HZ
    return EnergyBreakdown(compute, hbm, vmem, compute + hbm + vmem, latency)


def reprice(
    stats,
    *,
    word_bytes: int = 1,
    mem_bytes: int = 4,
    vmem_resident: bool = True,
    events_per_cycle: int = 9,
    lanes: int = 128,
) -> EnergyBreakdown:
    """Price *recorded* stats on the host — the study pipeline's repricing
    entry point.

    Accepts the study package's :class:`~repro.study.artifacts.StatsRecord`,
    a stats tuple holding plain numpy arrays, or a live
    :class:`~repro.core.snn_model.SNNStats` (its counts are pulled to the
    host), and prices the counts with :func:`snn_energy` in numpy: no
    device program runs. Each per-sample sum accumulates in the count's own
    dtype, so int32 counts keep the int32 wrap a live jax pricing has. All
    inputs are integer counts, so repricing is exact — what lets encoding /
    residency / bit-width sweeps run SNN inference once and re-derive every
    energy number from the record, and what lets the serving runtime meter
    a bucket from the one host copy it fetched.
    """
    stats = stats._replace(
        **{f: np.asarray(getattr(stats, f))
           for f in ("events_in", "spikes_out", "add_ops")})
    return snn_energy(stats, word_bytes=word_bytes, mem_bytes=mem_bytes,
                      vmem_resident=vmem_resident,
                      events_per_cycle=events_per_cycle, lanes=lanes)


class SNNStaticCosts(NamedTuple):
    """Input-independent SNN memory footprint, derived from the LayerPlan.

    The analogue of the paper's Eq. 3-5 BRAM sizing, re-targeted: how many
    bytes of queue (AEQ capacity) and membrane state each conv stage pins in
    VMEM. Shares the compiled plan with the execution engine so sizing and
    execution can never disagree about geometry.
    """

    queue_bytes: tuple      # per conv stage: T * C_in * K^2 * depth * word
    state_bytes: tuple      # per conv stage: H * W * C_out * 4 (fp32 Vm)
    total_queue_bytes: int
    total_state_bytes: int


def snn_static_costs(plan, *, T: int, depth: int, word_bytes: int = 1,
                     state_bytes_per_neuron: int = 4) -> SNNStaticCosts:
    """Static queue/membrane sizing for a compiled ``engine.LayerPlan``."""
    q = tuple(T * cp.in_c * cp.kernel * cp.kernel * depth * word_bytes
              for cp in plan.convs)
    s = tuple(cp.in_hw * cp.in_hw * cp.out_c * state_bytes_per_neuron
              for cp in plan.convs)
    return SNNStaticCosts(q, s, sum(q), sum(s))


def cnn_energy(
    costs,
    *,
    bits: int = 8,
    mxu_utilization: float = 0.5,
) -> EnergyBreakdown:
    """Energy/latency for one dense CNN inference (input-independent)."""
    macs = jnp.asarray(costs.macs, jnp.float32)
    e_mac = E_INT8_MAC if bits <= 8 else E_BF16_MAC
    peak = PEAK_INT8_OPS if bits <= 8 else PEAK_BF16_FLOPS

    compute = macs * e_mac
    hbm = jnp.asarray(costs.weight_bytes, jnp.float32) * E_HBM_BYTE
    vmem = jnp.asarray(costs.act_bytes, jnp.float32) * E_VMEM_BYTE * 2  # r+w

    latency = jnp.maximum(
        2.0 * macs / (peak * mxu_utilization),
        costs.weight_bytes / HBM_BW,
    )
    latency = jnp.asarray(latency, jnp.float32)
    return EnergyBreakdown(
        compute, hbm, vmem, compute + hbm + vmem,
        jnp.broadcast_to(latency, compute.shape) if compute.shape else latency,
    )
