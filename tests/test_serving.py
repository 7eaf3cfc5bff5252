"""Serving: the SNN serving runtime (``repro.serve``).

The structural guarantees pinned here:

1. **Mask contract / bucket parity** — for every bucket size, logits and
   stats of a padded batch sliced to the valid prefix are *bit-exact* equal
   to an unpadded ``infer_batch`` over the same samples, on both the
   ``queue_pallas`` (fused batch-native) and ``dense`` backends.
2. **Per-request metering** — energies the runtime attaches to responses
   are elementwise bit-equal to a one-shot ``study.collect`` +
   ``price_record`` over the same inputs, and their float32 sums match.
3. **Batcher/registry policy** — bucket selection, model isolation within
   a batch, LRU bounds on models and compiled plans.

Checkpoint/restore and cold-start guarantees live in
``tests/test_coldstart.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoding, energy, engine, snn_model
from repro.serve import (BucketPolicy, ModelRegistry, ServeError,
                         ServeRuntime)
from repro.study import StudyCache, StudySpec, price_record, stages
from repro.study.artifacts import ConvertArtifact, StatsRecord

SPEC = "6C3-P2-4C3-8"
HW, C = 10, 1
N_LAYERS = len(engine.parse_spec(SPEC))
# stats carry one row per *weighted* layer: each conv stage + the classifier
N_STAT_ROWS = len(engine.compile_plan(SPEC, HW, C).convs) + 1


@pytest.fixture(scope="module")
def net():
    params = snn_model.init_params(jax.random.PRNGKey(7), SPEC, HW, C)
    th = [jnp.asarray(0.5)] * N_LAYERS
    imgs = np.random.default_rng(11).random((9, HW, HW, C)).astype(np.float32)
    return params, th, imgs


def make_runtime(params, th, *, backend="queue_pallas", buckets=(1, 4, 16),
                 name="toy", input_mode="binary", **registry_kw):
    cfg = snn_model.SNNConfig(spec=SPEC, input_hw=HW, input_c=C, T=3,
                              depth=16, mode="mttfs_cont",
                              input_mode=input_mode)
    registry = ModelRegistry(**registry_kw)
    registry.register(name, params, th, cfg, backend=backend)
    return ServeRuntime(registry, BucketPolicy(buckets)), cfg


# ---------------------------------------------------------------------------
# Bucket policy
# ---------------------------------------------------------------------------

def test_bucket_selection():
    p = BucketPolicy((1, 4, 16, 64))
    assert p.select(1) == 1
    assert p.select(2) == 1              # would pad 4 half-empty: round down
    assert p.select(3) == 4              # pads 1 slot (< half): round up
    assert p.select(4) == 4
    assert p.select(5) == 4              # 5 would leave 16 mostly padding
    assert p.select(9) == 16             # > half of 16: pad up
    assert p.select(16) == 16
    assert p.select(17) == 16            # round down: full 16 now, 1 queued
    assert p.select(33) == 64
    assert p.select(1000) == 64          # capped: batcher takes max_bucket
    assert p.max_bucket == 64
    # no smaller bucket exists -> must round up however empty
    assert BucketPolicy((8, 32)).select(1) == 8
    with pytest.raises(ValueError):
        p.select(0)


@pytest.mark.parametrize("bad", [(), (4, 1), (2, 2, 4), (0, 4), (3.0, 8)])
def test_bucket_policy_rejects_malformed_ladders(bad):
    with pytest.raises(ValueError):
        BucketPolicy(bad)


def test_pad_appends_zero_rows():
    p = BucketPolicy((4,))
    imgs = np.ones((2, HW, HW, C), np.float32)
    padded = p.pad(imgs, 4)
    assert padded.shape == (4, HW, HW, C)
    np.testing.assert_array_equal(padded[:2], imgs)
    assert not padded[2:].any()
    with pytest.raises(ValueError):
        p.pad(np.ones((5, HW, HW, C), np.float32), 4)


# ---------------------------------------------------------------------------
# Mask contract: padded-bucket parity, every bucket size (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["queue_pallas", "dense"])
@pytest.mark.parametrize("bucket", [1, 4, 16])
def test_padded_bucket_parity_bit_exact(net, make_snn_config, backend,
                                        bucket):
    """Padded batch sliced to the valid prefix == unpadded call, bit-exact."""
    params, th, imgs = net
    cfg = make_snn_config(SPEC, HW, C, T=3, depth=16, mode="mttfs_cont",
                          input_mode="binary")
    n_valid = max(1, min(bucket - 1, len(imgs)))  # genuinely padded for B>1
    valid = jnp.asarray(imgs[:n_valid])

    ref_l, ref_s = engine.infer_batch(params, th, cfg, valid, backend=backend)
    padded = jnp.concatenate(
        [valid, jnp.ones((bucket - n_valid, HW, HW, C), jnp.float32)])
    got_l, got_s = engine.infer_batch_masked(params, th, cfg, padded,
                                             n_valid, backend=backend)

    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(ref_l))
    for f in ref_s._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got_s, f)), np.asarray(getattr(ref_s, f)),
            err_msg=f"{backend}/B={bucket}/{f}")


def test_slice_valid_rejects_bad_prefix(net, make_snn_config):
    params, th, imgs = net
    cfg = make_snn_config(SPEC, HW, C, T=2, depth=16, mode="mttfs_cont")
    batch = jnp.asarray(imgs[:4])
    for bad in (0, 5, -1, jnp.int32(2)):
        with pytest.raises(ValueError):
            engine.infer_batch_masked(params, th, cfg, batch, bad)


# ---------------------------------------------------------------------------
# The serving runtime
# ---------------------------------------------------------------------------

def test_serve_end_to_end_matches_engine(net):
    """Served responses == direct infer_batch: preds, logits, stats rows."""
    params, th, imgs = net
    rt, cfg = make_runtime(params, th)
    rids = [rt.submit(im) for im in imgs]
    responses = rt.run_until_drained()
    assert sorted(r.rid for r in responses) == rids
    assert rt.pending() == 0

    ref_l, ref_s = engine.infer_batch(params, th, cfg, jnp.asarray(imgs),
                                      backend="queue_pallas")
    for r in sorted(responses, key=lambda r: r.rid):
        np.testing.assert_array_equal(r.logits, np.asarray(ref_l)[r.rid])
        assert r.pred == int(np.argmax(np.asarray(ref_l)[r.rid]))
        np.testing.assert_array_equal(
            r.stats.events_in[0], np.asarray(ref_s.events_in)[r.rid])
        assert r.stats.events_in.shape == (1, N_STAT_ROWS)
        assert r.energy_j > 0 and r.model_latency_s > 0
        assert r.bucket == 16 and r.batch_valid == 9   # 9 reqs -> bucket 16
        assert r.latency_s >= r.service_s > 0

    summary = rt.stats_summary()
    assert summary["batches"] == 1 and summary["served"] == 9
    assert summary["bucket_histogram"] == {16: 1}


def test_per_request_pricing_matches_one_shot_collect_price(net):
    """Serving meters == one-shot collect+price: bit-exact rows and sums."""
    params, th, imgs = net
    rt, cfg = make_runtime(params, th, buckets=(4,))   # forces 3 batches
    for im in imgs:
        rt.submit(im)
    responses = sorted(rt.run_until_drained(), key=lambda r: r.rid)

    # one-shot reference through the study pipeline's stages, chunked
    # differently (batch=8) than the buckets the runtime used (4)
    spec = StudySpec(dataset="serve-parity", net=SPEC, input_hw=HW,
                     input_c=C, T=3, depth=16, mode="mttfs_cont",
                     input_mode="binary", backend="queue_pallas", batch=8)
    converted = ConvertArtifact(params, list(th), "serve-parity-key")
    collected = stages.collect(spec, converted, images=jnp.asarray(imgs),
                               cache=StudyCache())
    e = price_record(collected.stats, input_hw=HW, compressed=True,
                     vmem_resident=True)
    ref = np.asarray(e.total_j, np.float32)

    served = np.asarray([r.energy_j for r in responses], np.float32)
    np.testing.assert_array_equal(served, ref)
    assert np.float32(np.sum(served)) == np.float32(np.sum(ref))
    for r in responses:
        np.testing.assert_array_equal(
            r.stats.add_ops[0], collected.stats.add_ops[r.rid])


@pytest.mark.parametrize("magnitude", [100_000, 600_000_000])
@pytest.mark.parametrize("rows", [1, 13, 64])
@pytest.mark.parametrize("vmem_resident", [True, False])
def test_host_pricing_bit_equals_jax_pricing(vmem_resident, rows, magnitude):
    """Pricing a numpy record stays on the host (numpy out, no jax.Array)
    and bit-equals ``snn_energy`` over the same counts as jax arrays —
    int32 wrap of the per-sample sums included."""
    rng = np.random.default_rng(rows)
    low = magnitude - magnitude // 10
    counts = {f: rng.integers(low, magnitude, (rows, 4), dtype=np.int32)
              for f in ("events_in", "spikes_out", "add_ops", "queue_words")}
    record = StatsRecord(**counts, overflow=np.zeros(rows, np.int32))
    host = price_record(record, input_hw=32, compressed=True,
                        vmem_resident=vmem_resident)
    fmt = encoding.make_format(32, 3, compressed=True)
    live = snn_model.SNNStats(**{f: jnp.asarray(a)
                                 for f, a in record._asdict().items()})
    ref = energy.snn_energy(live, word_bytes=encoding.word_nbytes(fmt),
                            vmem_resident=vmem_resident)
    for name, got, want in zip(("compute", "hbm", "vmem", "total",
                                "latency", "total_j"),
                               (*host, host.total_j), (*ref, ref.total_j)):
        assert type(got) is np.ndarray and got.dtype == np.float32, name
        np.testing.assert_array_equal(got, np.asarray(want), err_msg=name)
    # at 6e8 a sample's four layers sum past 2**31: the int32 sum wraps
    # negative, exactly as the jax path's does
    wraps = record.add_ops.sum(-1, dtype=np.int64) >= 2**31
    assert wraps.all() == (magnitude > 2**31 // 4)
    np.testing.assert_array_equal(host.compute_pj < 0, wraps)


def test_served_step_runs_no_device_program_after_the_plan(net):
    """A warmed full bucket serves without a compile: the metering phase
    (one fetch, numpy pricing) runs no device program of its own."""
    params, th, imgs = net
    rt, _ = make_runtime(params, th, buckets=(4,))
    jax.clear_caches()       # no program warm from an earlier test's step
    rt.registry.get("toy").warmup((4,))
    for im in imgs[:4]:
        rt.submit(im)
    compiles = []

    def on_duration(event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        responses = rt.step()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert [r.bucket for r in responses] == [4] * 4
    assert compiles == []


def test_sustained_stream_cannot_starve_other_model(net):
    """Batcher rotation: a deep backlog for one model must not block another."""
    params, th, imgs = net
    cfg = snn_model.SNNConfig(spec=SPEC, input_hw=HW, input_c=C, T=2,
                              depth=16, mode="mttfs_cont",
                              input_mode="binary")
    reg = ModelRegistry()
    reg.register("a", params, th, cfg, backend="dense")
    reg.register("b", params, th, cfg, backend="dense")
    rt = ServeRuntime(reg, BucketPolicy((1, 4)))
    for im in imgs[:8]:                  # a deep backlog for model 'a'...
        rt.submit(im, model="a")
    rt.submit(imgs[8], model="b")        # ...with one 'b' request behind it
    first = rt.step()                    # batch 1: 'a' (head of line)
    second = rt.step()                   # batch 2 must rotate to 'b'
    assert {r.model for r in first} == {"a"}
    assert [r.model for r in second] == ["b"]
    rest = rt.run_until_drained()
    assert all(r.model == "a" for r in rest)


def test_evicted_model_rejects_loudly_without_wedging_others(net):
    """An evicted model's requests are rejected by rid; others still serve."""
    params, th, imgs = net
    cfg = snn_model.SNNConfig(spec=SPEC, input_hw=HW, input_c=C, T=2,
                              depth=16, mode="mttfs_cont")
    reg = ModelRegistry(capacity=1)
    reg.register("old", params, th, cfg, backend="dense")
    rt = ServeRuntime(reg, BucketPolicy((1, 4)))
    dead_rid = rt.submit(imgs[0], model="old")
    reg.register("new", params, th, cfg, backend="dense")   # evicts 'old'
    live_rid = rt.submit(imgs[1], model="new")
    with pytest.raises(ServeError,
                       match=rf"no longer registered.*\[{dead_rid}\]"):
        rt.step()
    # the dead model's request is gone (named in the error), the healthy
    # model's request is untouched and serves on the next step
    assert rt.pending() == 1
    responses = rt.run_until_drained()
    assert [r.rid for r in responses] == [live_rid]
    assert responses[0].model == "new"


def test_drain_failure_preserves_completed_responses(net):
    """A mid-drain failure must surface already-served work, not lose it."""
    params, th, imgs = net
    cfg = snn_model.SNNConfig(spec=SPEC, input_hw=HW, input_c=C, T=2,
                              depth=16, mode="mttfs_cont")
    reg = ModelRegistry(capacity=1)
    reg.register("old", params, th, cfg, backend="dense")
    rt = ServeRuntime(reg, BucketPolicy((1, 4)))
    rt.submit(imgs[0], model="old")
    rt.step()                            # one 'old' batch serves fine
    rt.submit(imgs[1], model="old")      # ...but this one will be orphaned
    reg.register("new", params, th, cfg, backend="dense")   # evicts 'old'
    live_rid = rt.submit(imgs[2], model="new")
    with pytest.raises(ServeError) as exc:
        # rotation serves 'new' first (last served was 'old'), then hits
        # the evicted 'old': the exception must carry the served response
        rt.run_until_drained()
    assert [r.rid for r in exc.value.completed] == [live_rid]
    assert rt.pending() == 0             # the dead request was rejected


def test_plan_cache_size_must_be_positive(net):
    params, th, _ = net
    cfg = snn_model.SNNConfig(spec=SPEC, input_hw=HW, input_c=C, T=2,
                              depth=16, mode="mttfs_cont")
    with pytest.raises(ValueError, match="plan_cache_size"):
        ModelRegistry(plan_cache_size=0)
    reg = ModelRegistry()
    from repro.serve import ModelHandle
    with pytest.raises(ValueError, match="plan_cache_size"):
        ModelHandle("x", params, th, cfg, backend="dense",
                    plan_cache_size=0)


def test_warmup_compiles_once_per_bucket(net):
    """Warmup's recompilation guard: two passes over the bucket ladder, one
    AOT compile per unique bucket — the compiled-plan cache keys on bucket
    size alone (the serving-layer analogue of repro.audit's jit-cache
    harness, which cannot see AOT plans)."""
    params, th, _ = net
    cfg = snn_model.SNNConfig(spec=SPEC, input_hw=HW, input_c=C, T=2,
                              depth=16, mode="mttfs_cont")
    from repro.serve import ModelHandle
    h = ModelHandle("w", params, th, cfg, backend="dense")
    h.warmup((1, 2, 2, 1))
    assert h.compile_count == 2          # unique buckets only, flat on pass 2


def test_warmup_guard_catches_unstable_plan_cache(net):
    """If plans stop being cache hits on identical buckets (the unbounded
    respecialization hazard), warmup must fail loudly, not serve slowly."""
    params, th, _ = net
    cfg = snn_model.SNNConfig(spec=SPEC, input_hw=HW, input_c=C, T=2,
                              depth=16, mode="mttfs_cont")
    from repro.serve import ModelHandle
    h = ModelHandle("w", params, th, cfg, backend="dense")
    orig = h.plan_for

    def evicting_plan_for(bucket):  # simulates a cache not keyed on bucket
        h._plans.clear()
        return orig(bucket)

    h.plan_for = evicting_plan_for
    with pytest.raises(ServeError, match="second pass recompiled"):
        h.warmup((1, 2))


def test_warmup_guard_skips_when_ladder_exceeds_plan_cache(net):
    """LRU eviction on a ladder longer than the plan cache makes second-pass
    recompiles legitimate — the guard must not false-positive there."""
    params, th, _ = net
    cfg = snn_model.SNNConfig(spec=SPEC, input_hw=HW, input_c=C, T=2,
                              depth=16, mode="mttfs_cont")
    from repro.serve import ModelHandle
    h = ModelHandle("w", params, th, cfg, backend="dense",
                    plan_cache_size=1)
    h.warmup((1, 2))                     # would recompile; guard skipped
    assert h.compile_count == 2


def test_round_down_serves_full_bucket_then_remainder(net):
    """5 waiting on ladder (1,4,16): a full 4-batch now, 1 queued — no pad."""
    params, th, imgs = net
    rt, _ = make_runtime(params, th, buckets=(1, 4, 16))
    for im in imgs[:5]:
        rt.submit(im)
    responses = sorted(rt.run_until_drained(), key=lambda r: r.rid)
    assert [r.bucket for r in responses] == [4, 4, 4, 4, 1]
    assert [r.batch_valid for r in responses] == [4, 4, 4, 4, 1]
    assert rt.stats_summary()["padded_slot_fraction"] == 0.0


def test_submit_validates_shape_and_model(net):
    params, th, _ = net
    rt, _ = make_runtime(params, th)
    with pytest.raises(ServeError):
        rt.submit(np.zeros((HW + 1, HW + 1, C), np.float32))
    with pytest.raises(ServeError):
        rt.submit(np.zeros((HW, HW, C), np.float32), model="nope")


# ---------------------------------------------------------------------------
# Registry: LRU bounds + multi-model isolation
# ---------------------------------------------------------------------------

def test_registry_lru_evicts_models(net):
    params, th, _ = net
    cfg = snn_model.SNNConfig(spec=SPEC, input_hw=HW, input_c=C, T=2,
                              depth=16, mode="mttfs_cont")
    reg = ModelRegistry(capacity=2)
    reg.register("a", params, th, cfg, backend="dense")
    reg.register("b", params, th, cfg, backend="dense")
    reg.get("a")                          # touch: 'b' is now least recent
    reg.register("c", params, th, cfg, backend="dense")
    assert set(reg.names()) == {"a", "c"}
    with pytest.raises(ServeError, match="unknown model 'b'"):
        reg.get("b")


def test_plan_cache_lru_bounds_compiled_buckets(net):
    params, th, _ = net
    cfg = snn_model.SNNConfig(spec=SPEC, input_hw=HW, input_c=C, T=2,
                              depth=16, mode="mttfs_cont")
    reg = ModelRegistry(plan_cache_size=2)
    h = reg.register("toy", params, th, cfg, backend="dense")
    assert h.plan_for(1) is h.plan_for(1)            # cache hit
    h.plan_for(2)
    h.plan_for(1)                                    # touch: 2 is LRU
    h.plan_for(4)                                    # evicts bucket 2
    assert h.cached_buckets() == (1, 4)


def test_batches_never_mix_models(net):
    """Interleaved submissions to two models: per-batch model isolation."""
    params, th, imgs = net
    cfg = snn_model.SNNConfig(spec=SPEC, input_hw=HW, input_c=C, T=3,
                              depth=16, mode="mttfs_cont",
                              input_mode="binary")
    reg = ModelRegistry()
    reg.register("qp", params, th, cfg, backend="queue_pallas")
    reg.register("dn", params, th, cfg, backend="dense")
    rt = ServeRuntime(reg, BucketPolicy((1, 4)))

    names = ["qp", "dn"] * 3
    for im, name in zip(imgs, names):
        rt.submit(im, model=name)
    with pytest.raises(ServeError):
        rt.submit(imgs[0])               # ambiguous: two models registered
    responses = sorted(rt.run_until_drained(), key=lambda r: r.rid)
    assert [r.model for r in responses] == names
    # the batcher gathers the head model's 3 requests (skipping the other
    # model without reordering it), so exactly two single-model batches of
    # batch_valid=3 run — never a mixed one
    assert rt.stats_summary()["batches"] == 2
    assert all(r.batch_valid == 3 for r in responses)
    # skipped-over requests kept FIFO order within their model
    for name in ("qp", "dn"):
        rids = [r.rid for r in responses if r.model == name]
        assert rids == sorted(rids)
