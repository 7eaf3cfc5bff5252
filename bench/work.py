"""Work counts of a paper net, from its spec string alone.

The benchmark's yardstick for operations and bytes. It parses the spec
grammar of the paper's Table 6 itself (``nCk`` conv with SAME padding,
``Pn`` max-pool fused into the conv before it, a trailing ``n`` dense
head) and never asks the program's plan, so a change to the program
cannot change what a stage is counted to cost.

Counts are dense-equivalent: every conv stage is counted as the dense
convolution it computes, at every one of the ``T`` time steps, whatever
realization (event queues or XLA convs) runs it.
"""
from __future__ import annotations

import re
from typing import NamedTuple

_CONV = re.compile(r"^(\d+)C(\d+)$")
_POOL = re.compile(r"^P(\d+)$")
_DENSE = re.compile(r"^(\d+)$")


class Stage(NamedTuple):
    """One conv stage: a conv and the pool fused after it (0 = none)."""

    hw: int        # input side == conv output side (SAME, stride 1)
    c_in: int
    c_out: int
    k: int
    pool: int
    out_hw: int    # side after the pool
    analog: bool   # fed the image as a constant current, not spikes


class Net(NamedTuple):
    stages: tuple
    head_in: int
    head_out: int


def parse(spec: str, hw: int, c: int) -> Net:
    """'32C3-32C3-P3-...-10' at an ``hw`` x ``hw`` x ``c`` input."""
    tokens = spec.split("-")
    stages = []
    for i, tok in enumerate(tokens[:-1]):
        if m := _CONV.match(tok):
            c_out, k = int(m.group(1)), int(m.group(2))
            nxt = _POOL.match(tokens[i + 1])
            pool = int(nxt.group(1)) if nxt else 0
            out_hw = hw // pool if pool else hw
            stages.append(Stage(hw, c, c_out, k, pool, out_hw,
                                analog=not stages))
            hw, c = out_hw, c_out
        elif not (_POOL.match(tok) and i and _CONV.match(tokens[i - 1])):
            raise ValueError(f"token {tok!r} of {spec!r}: expected nCk, "
                             "or Pn right after a conv")
    head = _DENSE.match(tokens[-1])
    if head is None or not stages:
        raise ValueError(f"{spec!r} must hold convs and end in a dense head")
    return Net(tuple(stages), hw * hw * c, int(head.group(1)))


def param_count(net: Net) -> int:
    """Weights and biases, as Table 6 counts them."""
    n = sum(s.k * s.k * s.c_in * s.c_out + s.c_out for s in net.stages)
    return n + net.head_in * net.head_out + net.head_out


def stage_macs(s: Stage) -> int:
    """Multiply-accumulates of the stage's dense conv at one time step."""
    return s.hw * s.hw * s.k * s.k * s.c_in * s.c_out


def macs_per_step(net: Net) -> int:
    """Every conv stage and the head, for one image at one time step."""
    return sum(stage_macs(s) for s in net.stages) + net.head_in * net.head_out


def flops_per_image(net: Net, T: int) -> int:
    """Dense-equivalent FLOPs of one image: 2 x MACs x T."""
    return 2 * macs_per_step(net) * T


def stage_flops(s: Stage, T: int) -> int:
    return 2 * stage_macs(s) * T


def stage_bytes(s: Stage, T: int) -> int:
    """Bytes any realization of the stage must move, for one image.

    The weights in f32, the input once per time step (a spike at one byte;
    the analog stage's image in f32, read once, since it does not change
    over time), and the output charge in f32 at every time step.
    """
    weights = 4 * s.k * s.k * s.c_in * s.c_out
    n_in = s.hw * s.hw * s.c_in
    inputs = 4 * n_in if s.analog else T * n_in
    charge = 4 * T * s.hw * s.hw * s.c_out
    return weights + inputs + charge


def stage_least_s(s: Stage, T: int, images: int, peak_flops: float,
                  peak_bytes_s: float) -> tuple[float, str]:
    """Least time of the stage over ``images`` images, and what bounds it.

    The weights are read once per call, whatever the batch; inputs and
    outputs scale with it.
    """
    weights = 4 * s.k * s.k * s.c_in * s.c_out
    t_flops = images * stage_flops(s, T) / peak_flops
    t_bytes = (weights + images * (stage_bytes(s, T) - weights)) \
        / peak_bytes_s
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
