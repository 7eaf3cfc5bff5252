"""The execution engine: plan compilation, spec validation, backend parity.

The structural guarantee this file pins down: ``snn_infer`` (queue backend)
and ``snn_dense_infer`` (scanned dense backend) are two backends of ONE
engine, so logits agree to float tolerance and every SNNStats field agrees
exactly — across all registered neuron modes and both input encodings.
The fused batch-native queue pipeline (``queue_pallas`` +
``kernels/spike_pipeline``) additionally pins *bit-exact* logits/stats
against both references at B in {1, 3, 16}, including the overflow regime.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, neuron, snn_model
from repro.core.engine import SpecError, compile_plan, parse_spec


SPEC = "6C3-P2-4C3-8"
HW, C = 10, 1


@pytest.fixture(scope="module")
def net():
    params = snn_model.init_params(jax.random.PRNGKey(7), SPEC, HW, C)
    th = [jnp.asarray(0.5)] * len(parse_spec(SPEC))
    img = jnp.asarray(
        np.random.default_rng(11).random((HW, HW, C)), jnp.float32)
    return params, th, img


def _stats_equal(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a.events_in),
                                  np.asarray(b.events_in), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(a.spikes_out),
                                  np.asarray(b.spikes_out), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(a.add_ops),
                                  np.asarray(b.add_ops), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(a.queue_words),
                                  np.asarray(b.queue_words), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(a.overflow),
                                  np.asarray(b.overflow), err_msg=msg)


# ---------------------------------------------------------------------------
# Backend parity (the acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", neuron.MODES)
@pytest.mark.parametrize("input_mode", ["analog", "binary"])
def test_queue_and_dense_backends_agree(net, make_snn_config, mode, input_mode):
    """Identical logits and identical SNNStats, every mode x input encoding."""
    params, th, img = net
    cfg = make_snn_config(SPEC, HW, C, T=3, mode=mode,
                          input_mode=input_mode)
    lq, sq = snn_model.snn_infer(params, th, cfg, img)
    ld, sd = snn_model.snn_dense_infer(params, th, cfg, img)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                               atol=1e-4, rtol=1e-4)
    _stats_equal(sq, sd, msg=f"{mode}/{input_mode}")
    assert int(sq.overflow) == 0  # parity regime: nothing dropped


def test_scan_equals_unrolled(net, make_snn_config):
    """lax.scan time loop == the seed's unrolled per-step loop."""
    params, th, img = net
    cfg = make_snn_config(SPEC, HW, C, T=4, mode="mttfs_cont")
    ls, ss = engine.infer(params, th, cfg, img, backend="dense")
    lu, su = engine.infer(params, th, cfg, img, backend="dense_unrolled")
    np.testing.assert_allclose(np.asarray(ls), np.asarray(lu),
                               atol=1e-5, rtol=1e-5)
    _stats_equal(ss, su)


def test_pallas_queue_backend_matches_dense(make_snn_config):
    """The fused kernels/spike_pipeline path is a drop-in queue accumulator."""
    spec = "4C3-6"
    params = snn_model.init_params(jax.random.PRNGKey(3), spec, 6, 1)
    th = [jnp.asarray(0.4)] * 2
    img = jnp.asarray(np.random.default_rng(5).random((6, 6, 1)), jnp.float32)
    cfg = make_snn_config(spec, 6, depth=16, T=2, mode="mttfs_cont",
                          input_mode="binary")
    lp, sp = engine.infer(params, th, cfg, img, backend="queue_pallas")
    ld, sd = engine.infer(params, th, cfg, img, backend="dense")
    np.testing.assert_allclose(np.asarray(lp), np.asarray(ld),
                               atol=1e-4, rtol=1e-4)
    _stats_equal(sp, sd)


def test_pallas_backend_is_batch_native_and_non_interpret():
    """The fused queue pipeline: batched plan, never the Pallas interpreter."""
    from repro.kernels import ops

    assert engine.get_backend("queue_pallas").supports_batch is True
    assert engine.get_backend("queue").supports_batch is False
    # default impl is compiled on every platform (xla off-TPU, pallas on TPU)
    assert ops.default_spike_impl() in ("xla", "pallas")


def _float_contractions(jaxpr):
    """(primitive, precision) of every float conv/dot in a jaxpr, nested."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("conv_general_dilated", "dot_general"):
            if jnp.issubdtype(eqn.invars[1].aval.dtype, jnp.floating):
                found.append((eqn.primitive.name, eqn.params["precision"]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _float_contractions(sub)
    return found


@pytest.mark.parametrize("backend", ["dense", "queue_pallas", "queue_ref"])
def test_plan_contractions_run_at_highest_precision(net, make_snn_config,
                                                    backend):
    """Every f32 conv and matmul of a served plan asks for HIGHEST.

    A TPU's default precision rounds f32 operands to bf16, and the
    thresholds turn that rounding into different spikes; the CPU ignores
    the setting, so only the program text can show it here.
    """
    params, th, _ = net
    cfg = make_snn_config(SPEC, HW, C, T=2, mode="mttfs_cont",
                          input_mode="analog")
    imgs = jnp.zeros((2, HW, HW, C), jnp.float32)
    closed = jax.make_jaxpr(engine.batch_runner(cfg, backend))(params, th,
                                                              imgs)
    found = _float_contractions(closed.jaxpr)
    assert found, "no float contraction traced"
    highest = jax.lax.Precision.HIGHEST
    assert all(p == (highest, highest) for _, p in found), found


@pytest.mark.parametrize("B", [1, 3, 16])  # 3, 16: non-divisible + lane-wide
def test_fused_batched_queue_parity(net, make_snn_config, B):
    """infer_batch(queue_pallas) == per-sample dense AND queue, bit-exact.

    The batched plan (batch axis in the kernel grid) must be a pure
    performance change: logits and every SNNStats field identical to both
    the dense oracle and the word-level queue reference, sample by sample.
    """
    params, th, img = net
    cfg = make_snn_config(SPEC, HW, C, T=3, mode="mttfs_cont",
                          input_mode="binary")
    rng = np.random.default_rng(B)
    imgs = jnp.asarray(rng.random((B, HW, HW, C)), jnp.float32)

    lb, sb = engine.infer_batch(params, th, cfg, imgs, backend="queue_pallas")
    for i in range(B):
        for ref_backend in ("dense", "queue"):
            lr, sr = engine.infer(params, th, cfg, imgs[i],
                                  backend=ref_backend)
            np.testing.assert_array_equal(
                np.asarray(lb[i]), np.asarray(lr),
                err_msg=f"logits sample {i} vs {ref_backend}")
            _stats_equal(
                SNNStatsView(sb, i), sr,
                msg=f"sample {i} vs {ref_backend}")


class SNNStatsView:
    """One sample's slice of batched SNNStats (duck-typed for _stats_equal)."""

    def __init__(self, stats, i):
        self.events_in = stats.events_in[i]
        self.spikes_out = stats.spikes_out[i]
        self.add_ops = stats.add_ops[i]
        self.queue_words = stats.queue_words[i]
        self.overflow = stats.overflow[i]


@pytest.mark.parametrize("mode", neuron.MODES)
@pytest.mark.parametrize("input_mode", ["analog", "binary"])
def test_fused_batched_all_modes_encodings(net, make_snn_config, mode,
                                           input_mode):
    """The fused plan holds parity across every neuron mode x encoding."""
    params, th, img = net
    cfg = make_snn_config(SPEC, HW, C, T=3, mode=mode, input_mode=input_mode)
    imgs = jnp.stack([img, img * 0.6, img * 0.2])

    lb, sb = engine.infer_batch(params, th, cfg, imgs, backend="queue_pallas")
    ld, sd = engine.infer_batch(params, th, cfg, imgs, backend="dense")
    np.testing.assert_array_equal(np.asarray(lb), np.asarray(ld),
                                  err_msg=f"{mode}/{input_mode}")
    _stats_equal(sb, sd, msg=f"{mode}/{input_mode}")


def test_fused_overflow_stats_match_queue(net, make_snn_config):
    """Small queue depth: drops happen, and the fused path drops the SAME
    events as the word-level queues — overflow, events, ops, and logits all
    stay bit-identical (the drop rule is part of the AEQ contract).

    (dense is no oracle here: it counts *uncapped* events and processes
    dropped ones, which is exactly why this regression test pins vs queue.)
    """
    params, th, img = net
    cfg = make_snn_config(SPEC, HW, C, T=3, depth=2, mode="mttfs_cont",
                          input_mode="binary")
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.random((3, HW, HW, C)), jnp.float32)

    lb, sb = engine.infer_batch(params, th, cfg, imgs, backend="queue_pallas")
    assert int(np.asarray(sb.overflow).min()) > 0  # the regime is exercised
    for i in range(3):
        lq, sq = engine.infer(params, th, cfg, imgs[i], backend="queue")
        np.testing.assert_array_equal(np.asarray(lb[i]), np.asarray(lq))
        _stats_equal(SNNStatsView(sb, i), sq, msg=f"overflow sample {i}")


def test_batch_infer_matches_per_sample(net, make_snn_config):
    params, th, img = net
    cfg = make_snn_config(SPEC, HW, C, T=3)
    imgs = jnp.stack([img, img * 0.5])
    lb, sb = engine.infer_batch(params, th, cfg, imgs, backend="dense")
    l0, s0 = engine.infer(params, th, cfg, imgs[1], backend="dense")
    np.testing.assert_allclose(np.asarray(lb[1]), np.asarray(l0),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(sb.spikes_out[1]),
                                  np.asarray(s0.spikes_out))


def test_runner_is_jit_cached(net, make_snn_config):
    params, th, img = net
    cfg = make_snn_config(SPEC, HW, C, T=3)
    f1 = engine._runner(cfg, "dense", False)
    f2 = engine._runner(cfg, "dense", False)
    assert f1 is f2  # one compiled executable per (cfg, backend, batched)
    assert engine._runner(cfg, "queue", False) is not f1


# ---------------------------------------------------------------------------
# Plan compilation
# ---------------------------------------------------------------------------

def test_compile_plan_geometry():
    plan = compile_plan("32C3-32C3-P3-10C3-10", 28, 1)
    assert plan.n_layers == 5
    assert [cp.index for cp in plan.convs] == [0, 1, 3]
    assert plan.convs[1].pool == 3 and plan.convs[1].out_hw == 9
    assert plan.convs[2].in_hw == 9 and plan.convs[2].in_c == 32
    assert plan.out.n_in == 9 * 9 * 10 and plan.out.n_out == 10
    # cached: same args -> same object
    assert compile_plan("32C3-32C3-P3-10C3-10", 28, 1) is plan


def test_plan_shared_with_cnn_and_conversion():
    """CNN forward, conversion, and the SNN walk one LayerPlan."""
    from repro.core import cnn_baseline, conversion

    spec = "4C3-P2-6"
    params = snn_model.init_params(jax.random.PRNGKey(0), spec, 8, 1)
    imgs = jnp.asarray(np.random.default_rng(0).random((4, 8, 8, 1)),
                       jnp.float32)
    logits = cnn_baseline.cnn_forward(params, spec, imgs)
    assert logits.shape == (4, 6)
    snn_params, th = conversion.convert(params, spec, imgs)
    assert len(snn_params) == len(params) == 3
    assert snn_params[1] == {}  # pool slot stays empty


# ---------------------------------------------------------------------------
# Spec validation (clear errors instead of deep-inference ValueErrors)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad, fragment", [
    ("", "empty"),
    ("-32C3-10", "leading"),
    ("32C3-10-", "trailing"),
    ("32C3--10", "doubled"),
    ("P2-32C3-10", "before any conv"),
    ("32C3-P2-P2-10", "directly follow"),
    ("32C-10", "malformed"),
    ("32c3-10", "malformed"),
    ("C3-10", "malformed"),
    ("32C3-x-10", "malformed"),
    ("0C3-10", ">= 1"),
    ("32C4-10", "even kernel"),
    ("10-32C3-10", "after the dense output"),
])
def test_parse_spec_rejects(bad, fragment):
    with pytest.raises(SpecError) as e:
        parse_spec(bad)
    assert fragment in str(e.value)


def test_parse_spec_accepts_paper_specs():
    from repro.configs import PAPER_SPECS

    for meta in PAPER_SPECS.values():
        layers = parse_spec(meta["spec"])
        assert layers[-1][0] == "dense"


@pytest.mark.parametrize("bad, hw, fragment", [
    ("32C3", 28, "end with a dense"),
    ("32C3-P2-32C3", 28, "end with a dense"),
    ("2C5-P4-4", 3, "pool window 4 exceeds"),  # a kernel may exceed it
    ("2C3-P9-4", 6, "pool window 9 exceeds"),
])
def test_compile_plan_rejects(bad, hw, fragment):
    with pytest.raises(SpecError) as e:
        compile_plan(bad, hw, 1)
    assert fragment in str(e.value)


def test_execute_rejects_mismatched_params(net, make_snn_config):
    params, th, img = net
    cfg = make_snn_config(SPEC, HW, C, T=2)
    with pytest.raises(ValueError, match="layers"):
        engine.infer(params[:-1], th, cfg, img, backend="dense")


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------

def test_unknown_neuron_mode_lists_registered(net, make_snn_config):
    params, th, img = net
    cfg = make_snn_config(SPEC, HW, C, T=2, mode="nope")
    with pytest.raises(ValueError, match="mttfs"):
        snn_model.snn_dense_infer(params, th, cfg, img)


def test_unknown_backend_lists_registered():
    with pytest.raises(ValueError, match="dense"):
        engine.get_backend("nope")


def test_custom_neuron_mode_runs_through_both_backends(net, make_snn_config):
    """Adding a neuron model is a one-file change: register and run."""
    params, th, img = net

    def fire_never(v, latch, vth):
        crossed = v > jnp.asarray(vth, v.dtype)
        return v, jnp.zeros_like(crossed), latch | crossed

    try:
        neuron.register_neuron_model("test_silent", fire_never)
        cfg = make_snn_config(SPEC, HW, C, T=2, mode="test_silent")
        for backend in ("dense", "queue"):
            logits, stats = engine.infer(params, th, cfg, img,
                                         backend=backend)
            assert int(stats.spikes_out.sum()) == 0
        with pytest.raises(ValueError, match="already registered"):
            neuron.register_neuron_model("test_silent", fire_never)

        # overwrite must invalidate the compiled-runner cache: the same cfg
        # must execute the NEW dynamics, not a stale jitted executable
        def fire_always(v, latch, vth):
            crossed = v > jnp.asarray(vth, v.dtype)
            return v, jnp.ones_like(crossed), latch | crossed

        neuron.register_neuron_model("test_silent", fire_always,
                                     overwrite=True)
        _, stats = engine.infer(params, th, cfg, img, backend="dense")
        assert int(stats.spikes_out.sum()) > 0
    finally:
        neuron.unregister_neuron_model("test_silent")
    with pytest.raises(ValueError, match="unknown neuron mode"):
        engine.infer(params, th, cfg, img, backend="dense")


def test_static_costs_from_plan():
    from repro.core.energy import snn_static_costs

    plan = compile_plan("32C3-32C3-P3-10C3-10", 28, 1)
    costs = snn_static_costs(plan, T=4, depth=64, word_bytes=1)
    assert len(costs.queue_bytes) == 3
    assert costs.queue_bytes[0] == 4 * 1 * 9 * 64 * 1
    assert costs.state_bytes[0] == 28 * 28 * 32 * 4
    assert costs.total_queue_bytes == sum(costs.queue_bytes)


# ---------------------------------------------------------------------------
# Differentiable training walk (engine.train_forward)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("mode", neuron.MODES)
@pytest.mark.parametrize("input_mode", ["analog", "binary"])
def test_train_forward_grad_finite_all_modes(net, make_snn_config, mode,
                                             input_mode, B):
    """jax.grad through the batched dense plan: finite for every weight,
    every registered neuron mode x input encoding, B in {1, 3}.

    The engine-level differentiability contract behind direct training: the
    surrogate models registered in core/neuron.py must let gradients flow
    through the lax.scan time loop without NaN/Inf, whatever the dynamics."""
    params, th, img = net
    cfg = make_snn_config(SPEC, HW, C, T=3, mode=mode, input_mode=input_mode)
    rng = np.random.default_rng(B)
    imgs = jnp.asarray(rng.random((B, HW, HW, C)), jnp.float32)

    def loss(p):
        step_out, rates = engine.train_forward(p, tuple(th), cfg, imgs)
        return step_out.sum(axis=1).std() + rates.mean()

    grads = jax.grad(loss)(params)
    leaves = jax.tree.leaves(grads)
    assert leaves, "no differentiable parameters reached"
    for g in leaves:
        assert np.isfinite(np.asarray(g)).all(), f"{mode}/{input_mode}/B={B}"


def test_direct_trained_net_backend_parity(make_snn_config):
    """A surrogate-trained net infers bit-identically on dense vs the fused
    queue_pallas plan — direct training produces ordinary engine nets, with
    no backend-visible residue of how the weights were obtained."""
    from repro.data.synthetic import make_digits
    from repro.training.surrogate import fit_snn

    imgs, labels = make_digits(64, seed=0)
    params, th, _ = fit_snn("4C3-P2-6", imgs, labels, T=2, mode="mttfs_cont",
                            epochs=1, batch=32, lr=5e-3, rate_reg=0.01)
    cfg = make_snn_config("4C3-P2-6", 28, T=2, depth=128, mode="mttfs_cont")
    eval_imgs = jnp.asarray(imgs[:8])
    ld, sd = engine.infer_batch(params, th, cfg, eval_imgs, backend="dense")
    lp, sp = engine.infer_batch(params, th, cfg, eval_imgs,
                                backend="queue_pallas")
    np.testing.assert_array_equal(np.asarray(lp), np.asarray(ld))
    _stats_equal(sp, sd, msg="direct-trained net dense vs queue_pallas")
