"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import functools

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import event_geometries

from repro import configs
from repro.core import aeq, encoding
from repro.kernels import ops, ref
from repro.kernels.spike_pipeline import fused_spike_accum_pallas


@pytest.mark.parametrize("hw,c_in,c_out,depth", [
    (9, 1, 8, 16), (12, 3, 16, 32), (28, 4, 32, 64), (28, 2, 128, 24),
])
def test_event_accum_sweep(hw, c_in, c_out, depth):
    fmt = encoding.make_format(hw, 3)
    rng = np.random.default_rng(hw * depth)
    raster = (rng.random((1, c_in, hw, hw)) < 0.15).astype(np.float32)
    q = aeq.aeq_from_raster(fmt, jnp.asarray(raster), depth)
    w = jnp.asarray(rng.normal(size=(3, 3, c_in, c_out)), jnp.float32)
    vm = jnp.asarray(rng.normal(size=(hw, hw, c_out)), jnp.float32)

    kw = dict(K=3, n_win=fmt.n_win, bits=fmt.bits_coord)
    out_k = ops.event_accum(q.words[0], q.counts[0], w, vm, **kw)
    out_r = ref.event_accum_ref(q.words[0], q.counts[0], w, vm, **kw)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("rows,n_win,depth", [(4, 4, 8), (9, 10, 40), (18, 10, 16)])
def test_spike_compact_sweep(rows, n_win, depth):
    fmt = encoding.make_format(n_win * 3, 3)
    rng = np.random.default_rng(rows)
    occ = (rng.random((rows, n_win * n_win)) < 0.3).astype(np.int32)
    kw = dict(n_win=n_win, bits=fmt.bits_coord, depth=depth,
              invalid=fmt.invalid_word)
    wk, ck = ops.spike_compact(jnp.asarray(occ), **kw)
    wr, cr = ref.spike_compact_ref(jnp.asarray(occ), **kw)
    np.testing.assert_array_equal(np.asarray(wk), np.asarray(wr))
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))


@pytest.mark.parametrize("m,k,n", [(16, 32, 8), (100, 200, 60), (128, 128, 128),
                                   (130, 257, 64)])
def test_quant_matmul_sweep(m, k, n):
    # backend pinned: the *default* resolves to 'ref' off-TPU
    # (ops.default_quant_impl), which would make this Pallas-vs-oracle
    # differential a tautology
    rng = np.random.default_rng(m + k + n)
    a = rng.integers(-127, 127, (m, k)).astype(np.int8)
    b = rng.integers(-127, 127, (k, n)).astype(np.int8)
    got = ops.quant_matmul(jnp.asarray(a), jnp.asarray(b),
                           jnp.float32(0.013), jnp.float32(0.021),
                           backend="pallas", block_m=64, block_n=64,
                           block_k=64)
    want = ref.quant_matmul_ref(jnp.asarray(a), jnp.asarray(b),
                                jnp.float32(0.013), jnp.float32(0.021))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_quant_matmul_default_is_compiled():
    """The engine's quant output head must never hit the interpreter."""
    assert ops.default_quant_impl() in ("pallas", "ref")
    if jax.default_backend() != "tpu":
        assert ops.default_quant_impl() == "ref"


def _occupancy(hw, c_in, T, seed, p_fire=0.25):
    """Random (N=T, C, K2, P) occupancy via the real raster->phase split."""
    rng = np.random.default_rng(seed)
    raster = (rng.random((T, hw, hw, c_in)) < p_fire).astype(np.float32)
    fmt = encoding.make_format(hw, 3)
    return fmt, aeq.phase_occupancy(fmt, jnp.asarray(raster))


#: every spike-fed stage (in_hw, C_in, C_out) of the served nets: the
#: paper's three and VGG-16, eight distinct stages each
SERVED_STAGES = [(cp.in_hw, cp.in_c, cp.out_c) for cp in event_geometries(
    [*configs.PAPER_SPECS.values(), configs.VGG16])]


@pytest.mark.parametrize("hw,c_in,c_out,depth", [
    (9, 1, 8, 16), (12, 3, 16, 4), (28, 4, 32, 64), (10, 2, 8, 2),
] + [(*stage, depth) for stage in SERVED_STAGES for depth in (2, 256)])
def test_fused_spike_accum_xla_matches_ref(hw, c_in, c_out, depth):
    """The compiled XLA realization == the scatter oracle, incl. small-depth
    drop regimes and the non-compressed word format (hw=10), at every
    spike-fed stage of the served nets: the realization that runs
    ``queue_pallas`` off the chip. Depth 256 drops nothing; depth 2 is
    below a phase segment's occupied windows from 8x8 maps up."""
    fmt, occ = _occupancy(hw, c_in, 3, seed=hw * depth)
    if depth == 2 and hw >= 8:
        # the drop rule is exercised: some (step, channel, phase) queue
        # holds more occupied windows than the depth
        assert int((np.asarray(occ) > 0).sum(-1).max()) > depth
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(3, 3, c_in, c_out)), jnp.float32)
    kw = dict(K=3, n_win=fmt.n_win, bits=fmt.bits_coord, depth=depth,
              H=hw, W=hw, invalid=fmt.invalid_word)
    out_x = ops.fused_spike_accum(occ, w, impl="xla", **kw)
    out_r = ops.fused_spike_accum(occ, w, impl="ref", **kw)
    np.testing.assert_allclose(np.asarray(out_x), np.asarray(out_r),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("hw,c_in,c_out,depth,p_fire", [
    (6, 1, 4, 16, 0.25), (9, 2, 8, 4, 0.25), (10, 1, 8, 3, 0.25),
    # the cifar10 event geometries (n_win 11, 4, 1) at their C_out
    (32, 2, 32, 256, 0.25), (10, 2, 64, 256, 0.25), (3, 2, 128, 256, 0.5),
    # queues that overflow at n_win 11 (P = 121)
    (32, 2, 32, 3, 0.25), (32, 2, 32, 64, 0.9),
    # every phase skipped; every window fired
    (32, 2, 32, 256, 0.0), (10, 2, 64, 256, 1.0),
    # C_out in 128-lane tiles (VGG-16's 8x8 and 4x4 stages; one padded);
    # maps narrower than the kernel (VGG-16's 2x2, and 1x1)
    (8, 2, 256, 256, 0.5), (4, 2, 512, 256, 0.5), (4, 2, 160, 256, 0.5),
    (2, 2, 512, 256, 0.5), (1, 2, 8, 256, 1.0),
    # input channels as wide as the MXU contraction and wider: cifar10's
    # full 3x3 128->128 and 32x32 32->32, VGG-16's 4x4 256->512 and 2x2
    # 512->512; a C_in that is no multiple of 8; overflow at C_in 128
    (3, 128, 128, 256, 0.5), (32, 32, 32, 256, 0.4),
    (4, 256, 512, 256, 0.5), (2, 512, 512, 256, 0.5),
    (10, 5, 64, 256, 0.5), (10, 128, 64, 3, 0.5),
])
def test_fused_spike_accum_pallas_interp_matches_ref(hw, c_in, c_out,
                                                     depth, p_fire):
    """The Pallas kernel body (interpret mode): the per-offset contraction
    over the input channels and the slab updates of the interlaced banks
    accumulate exactly the surviving events, with queues that overflow,
    phases that are empty and windows that all fire."""
    fmt, occ = _occupancy(hw, c_in, 2, seed=hw + depth, p_fire=p_fire)
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(3, 3, c_in, c_out)), jnp.float32)
    kw = dict(K=3, n_win=fmt.n_win, bits=fmt.bits_coord, depth=depth,
              H=hw, W=hw, invalid=fmt.invalid_word)
    out_p = ops.fused_spike_accum(occ, w, impl="pallas_interpret", **kw)
    out_r = ops.fused_spike_accum(occ, w, impl="ref", **kw)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               atol=1e-4, rtol=1e-4)


def _run_to_pallas(fn, *args):
    """Run ``fn``'s program eagerly up to its ``pallas_call``: yields each
    equation with its operand values, the ``pallas_call`` last (not run)."""
    (call,) = jax.make_jaxpr(fn)(*args).jaxpr.eqns  # the entry point's jit
    body = call.params["jaxpr"]
    env = dict(zip(body.jaxpr.invars, args))
    env.update(zip(body.jaxpr.constvars, body.consts))

    def read(v):
        return v.val if isinstance(v, jax.extend.core.Literal) else env[v]

    for eqn in body.jaxpr.eqns:
        ins = [read(v) for v in eqn.invars]
        yield eqn, ins
        if eqn.primitive.name == "pallas_call":
            return
        outs = eqn.primitive.bind(*ins, **eqn.params)
        env.update(zip(eqn.outvars,
                       outs if eqn.primitive.multiple_results else [outs]))
    raise AssertionError("no pallas_call traced")


def _dots(jaxpr):
    """Every dot_general of a jaxpr, nested."""
    found = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _dots(sub)
    return found


@pytest.mark.parametrize("hw,c_in,c_out", [
    (10, 5, 64), (4, 256, 512), (2, 512, 512),
])
def test_fused_spike_accum_pallas_contracts_at_f32(hw, c_in, c_out):
    """Every contraction in the kernel body is as exact as f32: it asks for
    HIGHEST, or multiplies 0/1 spikes in bf16 by bf16 weight parts into
    f32, and the parts that reach the body sum to the f32 weights.

    On a TPU, Mosaic's default precision rounds an f32 operand to one bf16
    part; the CPU (and interpret mode) computes f32 whatever the
    precision, so only the program shows it.
    """
    fmt, occ = _occupancy(hw, c_in, 2, seed=hw, p_fire=0.5)
    w = jnp.asarray(np.random.default_rng(3).normal(size=(3, 3, c_in, c_out)),
                    jnp.float32)
    fn = functools.partial(fused_spike_accum_pallas, K=3, n_win=fmt.n_win,
                           depth=256, H=hw, W=hw, interpret=True)
    for call, ins in _run_to_pallas(fn, occ, w):
        if (call.primitive.name == "convert_element_type"
                and call.params["new_dtype"] == jnp.bfloat16
                and ins[0].dtype == jnp.float32):
            # exact, so no rounding is left that XLA's excess precision
            # could skip
            low = np.asarray(ins[0]).view(np.uint32) & 0xFFFF
            assert not low.any(), "an f32 -> bf16 rounding"
    spikes, parts = ins
    dots = _dots(call.params["jaxpr"])
    assert dots, "no contraction in the kernel body"
    highest = jax.lax.Precision.HIGHEST
    for d in dots:
        exact_parts = (all(v.aval.dtype == jnp.bfloat16 for v in d.invars)
                       and d.params["preferred_element_type"] == jnp.float32)
        assert exact_parts or d.params["precision"] == (highest, highest), d
    # one copy of the 0/1 spikes per weight part, then zero padding
    spikes = np.asarray(spikes, np.float32)
    assert set(np.unique(spikes)) <= {0.0, 1.0}
    copies = np.split(spikes[..., :3 * c_in], 3, axis=-1)
    for copy in copies[1:]:
        np.testing.assert_array_equal(copy, copies[0])
    assert not spikes[..., 3 * c_in:].any()
    # three bf16 parts on the contraction axis that sum to the weights
    assert parts.dtype == jnp.bfloat16
    split = np.asarray(parts, np.float32)[:, :, :3 * c_in, :c_out]
    np.testing.assert_array_equal(
        split.reshape(3, 3, 3, c_in, c_out).sum(2), np.asarray(w))
    assert not np.asarray(parts, np.float32)[:, :, 3 * c_in:].any()


def test_fused_spike_accum_default_is_compiled():
    """The engine's hot path must never fall back to the interpreter."""
    assert ops.default_spike_impl() in ("xla", "pallas")


def test_fused_spike_accum_matches_unfused_kernels():
    """Fusion closure: compact_spikes -> event_accum (the PR-1 two-kernel
    path, words round-tripping through 'HBM') == one fused call."""
    hw, c_in, c_out, depth = 12, 2, 8, 16
    fmt, occ = _occupancy(hw, c_in, 1, seed=3)
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.normal(size=(3, 3, c_in, c_out)), jnp.float32)

    raster = np.zeros((1, c_in, hw, hw), np.float32)  # rebuild from occ
    q = None
    # decode occupancy back to a (T=1, C, H, W) raster via the AEQ model
    occ_np = np.asarray(occ)[0]                       # (C, K2, P)
    n = fmt.n_win
    for c in range(c_in):
        for ph in range(9):
            ky, kx = ph // 3, ph % 3
            for p in range(n * n):
                if occ_np[c, ph, p]:
                    raster[0, c, (p // n) * 3 + ky, (p % n) * 3 + kx] = 1.0
    q = aeq.aeq_from_raster(fmt, jnp.asarray(raster), depth)

    vm = jnp.zeros((hw, hw, c_out), jnp.float32)
    kw = dict(K=3, n_win=fmt.n_win, bits=fmt.bits_coord)
    out_two = ops.event_accum(q.words[0], q.counts[0], w, vm,
                              backend="ref", **kw)
    out_fused = ops.fused_spike_accum(
        occ, w, depth=depth, H=hw, W=hw, invalid=fmt.invalid_word, **kw)[0]
    np.testing.assert_allclose(np.asarray(out_fused), np.asarray(out_two),
                               atol=1e-4, rtol=1e-4)


def test_kernels_dtype_bf16_event_accum():
    fmt = encoding.make_format(12, 3)
    rng = np.random.default_rng(0)
    raster = (rng.random((1, 2, 12, 12)) < 0.2).astype(np.float32)
    q = aeq.aeq_from_raster(fmt, jnp.asarray(raster), 32)
    w = jnp.asarray(rng.normal(size=(3, 3, 2, 8)), jnp.bfloat16)
    vm = jnp.zeros((12, 12, 8), jnp.bfloat16)
    kw = dict(K=3, n_win=fmt.n_win, bits=fmt.bits_coord)
    out_k = ops.event_accum(q.words[0], q.counts[0], w, vm, **kw)
    out_r = ref.event_accum_ref(q.words[0], q.counts[0],
                                w.astype(jnp.float32),
                                vm.astype(jnp.float32), **kw)
    np.testing.assert_allclose(np.asarray(out_k, dtype=np.float32),
                               np.asarray(out_r), atol=0.1)


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret",
                                  "sparse_pallas_interpret"])
def test_no_oracle_or_interpreter_on_tpu(monkeypatch, impl):
    """On a TPU only a compiled kernel may run; an oracle or the
    interpreter standing in for it raises instead."""
    fmt, occ = _occupancy(6, 1, 1, seed=0)
    w = jnp.zeros((3, 3, 1, 4), jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="does not run on a TPU"):
        ops.fused_spike_accum(occ, w, impl=impl, K=3, n_win=fmt.n_win,
                              bits=fmt.bits_coord, depth=8, H=6, W=6,
                              invalid=fmt.invalid_word)
    with pytest.raises(RuntimeError, match="does not run on a TPU"):
        ops.quant_matmul(jnp.zeros((2, 2), jnp.int8),
                         jnp.zeros((2, 2), jnp.int8), jnp.float32(1.0),
                         jnp.float32(1.0), backend="ref")
