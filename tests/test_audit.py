"""repro.audit: every rule fires on a seeded violation, stays quiet on the
clean repo, and the baseline mechanism round-trips.

Each fixture here *constructs* the hazard a rule exists to catch — an f64
promotion, a vmap over a queue entry point, an unmarked host sync, an
undeclared cross-batch reduction, an int8 path skipping its int32
accumulator, a VMEM-overflowing geometry, a jit cache that grows on repeat
shapes — and asserts the finding's rule, severity, and anchor. The final
tests run the real collectors over the repo and require zero errors.
"""
import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.audit import (ast_rules, cli, harness, jaxpr_rules, probe,
                         reachability, vmem)
from repro.audit.contracts import QuantContract, VMEM_BUDGET_BYTES
from repro.audit.findings import Baseline, BaselineError, Finding

ROOT = cli.repo_root()


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# jaxpr layer: seeded violations
# ---------------------------------------------------------------------------

def test_dtype_rule_fires_on_f64_promotion():
    with jax.enable_x64():
        closed = jax.make_jaxpr(lambda x: jnp.sin(x) * 2.0)(
            jnp.zeros((3,), jnp.float64))
    found = jaxpr_rules.check_dtypes("fixture", closed, ROOT)
    assert found and all(f.rule == "dtype-f64" for f in found)
    assert all(f.severity == "error" for f in found)


def test_dtype_rule_quiet_on_f32():
    closed = jax.make_jaxpr(lambda x: jnp.sin(x) * 2.0)(
        jnp.zeros((3,), jnp.float32))
    assert jaxpr_rules.check_dtypes("fixture", closed, ROOT) == []


def test_host_sync_rule_fires_on_callback_in_trace():
    def f(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v),
            jax.ShapeDtypeStruct((3,), jnp.float32), x)
        return y + 1.0

    closed = jax.make_jaxpr(f)(jnp.zeros((3,), jnp.float32))
    found = jaxpr_rules.check_host_sync("fixture", closed, ROOT)
    assert found and found[0].rule == "host-sync"
    assert found[0].severity == "error"


def test_batch_purity_fires_on_undeclared_cross_batch_reduction():
    B = probe.B_PROBE
    tainted = frozenset({B})
    closed = jax.make_jaxpr(lambda x: x.sum(axis=0))(jnp.zeros((B, 8)))
    found = jaxpr_rules.check_batch_purity("fixture", closed, tainted, 0,
                                           ROOT)
    assert found and found[0].rule == "batch-purity"
    assert found[0].severity == "error"
    # the anchor points into this test file (the reduction's call site)
    assert "test_audit" in found[0].file


def test_batch_purity_honors_declared_count_and_flags_stale():
    B = probe.B_PROBE
    tainted = frozenset({B})
    closed = jax.make_jaxpr(lambda x: x.sum(axis=0))(jnp.zeros((B, 8)))
    assert jaxpr_rules.check_batch_purity("f", closed, tainted, 1, ROOT) == []
    stale = jaxpr_rules.check_batch_purity("f", closed, tainted, 2, ROOT)
    assert stale and stale[0].severity == "warning"
    assert "stale" in stale[0].message


def test_batch_purity_ignores_program_sized_reductions():
    """Reducing a non-batch axis (size 8, a program dim) never fires."""
    B = probe.B_PROBE
    tainted = frozenset({B, B * 4})
    closed = jax.make_jaxpr(lambda x: x.sum(axis=1))(jnp.zeros((B, 8)))
    assert jaxpr_rules.check_batch_purity("f", closed, tainted, 0, ROOT) == []


def test_quant_rule_fires_on_direct_int8_dequant():
    def bad(a_q, b, scale):  # int8 -> float straight, no int32 accumulate
        return (a_q.astype(jnp.float32) @ b) * scale

    closed = jax.make_jaxpr(bad)(
        jnp.zeros((4, 6), jnp.int8), jnp.zeros((6, 2), jnp.float32),
        jnp.float32(1.0))
    found = jaxpr_rules.check_quant("fixture", closed, QuantContract(), ROOT)
    assert any("direct" in f.message and f.rule == "quant-accum"
               for f in found)
    found2 = jaxpr_rules.check_no_int8_dequant("fixture", closed, ROOT)
    assert found2 and found2[0].rule == "quant-dequant"


def test_quant_rule_fires_on_wrong_accumulator_dtype():
    def bad(a_q, b_q):  # int8 x int8 accumulated in int8: overflow city
        return a_q @ b_q

    closed = jax.make_jaxpr(bad)(
        jnp.zeros((4, 6), jnp.int8), jnp.zeros((6, 2), jnp.int8))
    found = jaxpr_rules.check_quant("fixture", closed, QuantContract(), ROOT)
    assert any("accumulates in" in f.message for f in found)


def test_quant_rule_accepts_the_contracted_shape():
    def good(a_q, b_q, scale):  # int32 accumulate, exactly one dequant
        acc = a_q.astype(jnp.int32) @ b_q.astype(jnp.int32)
        return acc.astype(jnp.float32) * scale

    closed = jax.make_jaxpr(good)(
        jnp.zeros((4, 6), jnp.int8), jnp.zeros((6, 2), jnp.int8),
        jnp.float32(1.0))
    assert jaxpr_rules.check_quant("fixture", closed, QuantContract(),
                                   ROOT) == []


def test_quant_rule_counts_missing_dequant():
    def never_dequants(a_q, b_q):
        return a_q.astype(jnp.int32) @ b_q.astype(jnp.int32)

    closed = jax.make_jaxpr(never_dequants)(
        jnp.zeros((4, 6), jnp.int8), jnp.zeros((6, 2), jnp.int8))
    found = jaxpr_rules.check_quant("fixture", closed, QuantContract(), ROOT)
    assert any("0 int->float dequant(s)" in f.message for f in found)


# ---------------------------------------------------------------------------
# AST layer: seeded violations (each via a real temp file)
# ---------------------------------------------------------------------------

def _lint(tmp_path, source):
    p = tmp_path / "fixture_mod.py"
    p.write_text(textwrap.dedent(source))
    return ast_rules.check_file(str(p), str(tmp_path))


def test_ast_f64_fires(tmp_path):
    found = _lint(tmp_path, """
        import jax.numpy as jnp
        X = jnp.zeros((3,), jnp.float64)
        """)
    assert _rules(found) == ["ast-f64"]
    assert found[0].line == 3 and found[0].severity == "error"


def test_ast_np_in_jit_fires_only_inside_jit(tmp_path):
    found = _lint(tmp_path, """
        import jax
        import numpy as np

        MEAN = np.mean([1, 2])          # host math outside jit: fine

        @jax.jit
        def traced(x):
            return x + np.float32(np.pi)  # host math inside jit: flagged
        """)
    assert _rules(found) == ["ast-np-in-jit"]
    assert all(f.line == 9 for f in found)


def test_vmap_over_queue_fires(tmp_path):
    found = _lint(tmp_path, """
        import jax
        from repro.kernels.ops import fused_spike_accum

        def per_sample(occ, w):
            return jax.vmap(lambda o: fused_spike_accum(o[None], w))(occ)
        """)
    assert _rules(found) == ["vmap-over-queue"]
    assert found[0].line == 6 and found[0].severity == "error"


def test_vmap_of_plain_fn_is_fine(tmp_path):
    found = _lint(tmp_path, """
        import jax

        def batched(f, xs):
            return jax.vmap(f)(xs)
        """)
    assert found == []


def test_banned_import_fires(tmp_path):
    found = _lint(tmp_path, """
        from tests import conftest
        import benchmarks.memory_study
        """)
    assert _rules(found) == ["banned-import"]
    assert {f.line for f in found} == {2, 3}


def test_host_sync_marker_fires_without_marker(tmp_path):
    found = _lint(tmp_path, """
        import jax

        def gate(total):
            return int(total.item())
        """)
    assert _rules(found) == ["host-sync-marker"]
    assert found[0].line == 5


def test_host_sync_marker_accepts_multiline_comment_block(tmp_path):
    found = _lint(tmp_path, """
        import jax

        def gate(total):
            # audit: allow[host-sync] the occupancy gate: one scalar pull
            # per layer, by design (see docs/CONTRACTS.md)
            return int(jax.device_get(total))
        """)
    assert found == []


def test_obs_in_jit_fires_on_obs_call_inside_jit(tmp_path):
    found = _lint(tmp_path, """
        import jax
        from repro import obs

        obs.counter("host.side")         # outside jit: fine

        @jax.jit
        def traced(x):
            obs.counter("lies.once")     # fires at trace time only
            with obs.span("worse"):      # times the *trace*, not the run
                return x + 1
        """)
    assert _rules(found) == ["obs-in-jit"]
    assert {f.line for f in found} == {9, 10}
    assert all(f.severity == "error" for f in found)


def test_obs_in_jit_fires_on_clock_read_inside_jit(tmp_path):
    found = _lint(tmp_path, """
        import time
        import jax

        @jax.jit
        def traced(x):
            t0 = time.perf_counter()     # constant-folds to trace time
            return x * t0
        """)
    assert _rules(found) == ["obs-in-jit"]
    assert found[0].line == 7
    # no marker escape inside jit: the construct is never correct there
    marked = _lint(tmp_path, """
        import time
        import jax

        @jax.jit
        def traced(x):
            # audit: allow[host-sync] trying to talk my way past the rule
            t0 = time.perf_counter()
            return x * t0
        """)
    assert _rules(marked) == ["obs-in-jit"]


def test_clock_marker_requires_annotation_outside_jit(tmp_path):
    found = _lint(tmp_path, """
        import time

        def measure(fn):
            t0 = time.perf_counter()
            fn()
            return time.perf_counter() - t0
        """)
    assert _rules(found) == ["clock-marker"]
    assert {f.line for f in found} == {5, 7}
    marked = _lint(tmp_path, """
        import time

        def measure(fn):
            # audit: allow[host-sync] deliberate timing site
            t0 = time.perf_counter()
            fn()
            return time.perf_counter() - t0  # audit: allow[host-sync]
        """)
    assert marked == []


def test_clock_marker_ignores_injectable_clock_references(tmp_path):
    """``clock=time.perf_counter`` default args (the sanctioned injectable-
    clock indirection) and ``self.clock()`` calls never flag."""
    found = _lint(tmp_path, """
        import time

        class Timed:
            def __init__(self, clock=time.perf_counter):
                self.clock = clock

            def now(self):
                return self.clock()
        """)
    assert found == []


def test_audit_package_excluded_from_self_lint(tmp_path):
    pkg = tmp_path / "audit"
    pkg.mkdir()
    (pkg / "rules.py").write_text("BANNED = 'float64'\n")
    (tmp_path / "lib.py").write_text("OK = 1\n")
    files = list(ast_rules.iter_source_files(str(tmp_path)))
    assert files == [str(tmp_path / "lib.py")]


# ---------------------------------------------------------------------------
# Reachability
# ---------------------------------------------------------------------------

def test_dead_module_fires_on_orphan(tmp_path):
    src = tmp_path / "src"
    pkg = src / "pkg"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("from . import used\n")
    (pkg / "used.py").write_text("X = 1\n")
    (pkg / "orphan.py").write_text("Y = 2\n")
    (pkg / "cli.py").write_text(
        'if __name__ == "__main__":\n    print(1)\n')
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "test_x.py").write_text("import pkg\n")
    found = reachability.check_reachability(str(tmp_path), str(src))
    assert [f.rule for f in found] == ["dead-module"]
    assert "pkg.orphan" in found[0].message
    assert found[0].severity == "warning"


# ---------------------------------------------------------------------------
# VMEM estimator
# ---------------------------------------------------------------------------

def test_vmem_overflow_detected_at_absurd_geometry():
    huge = vmem.kernel_footprint(
        "repro.kernels.spike_pipeline",
        K=3, n_win=342, depth=256, H=1024, W=1024, C_in=1024, C_out=1024)
    assert huge > VMEM_BUDGET_BYTES


def test_vmem_rule_fires_under_a_tiny_budget():
    found = vmem.check_vmem(ROOT, budget=1024)
    assert found and all(f.rule == "vmem-budget" for f in found)
    # anchored at each kernel module's CONTRACT line
    assert all(f.file.startswith("src/repro/kernels/") and f.line > 1
               for f in found)


def test_vmem_paper_geometries_fit_the_real_budget():
    assert vmem.check_vmem(ROOT) == []


# ---------------------------------------------------------------------------
# Recompilation harness
# ---------------------------------------------------------------------------

def test_second_pass_flat_on_real_engine_runner():
    from repro.core import engine

    cfg = probe.probe_config()
    plan = engine.compile_plan(cfg.spec, cfg.input_hw, cfg.input_c,
                               cfg.compressed)
    runner = engine.batch_runner(cfg, "dense")
    assert harness.second_pass_flat(
        runner, probe.probe_params(plan), probe.probe_thresholds(plan),
        probe.probe_images(cfg, 2))


def test_second_pass_flat_catches_growing_cache():
    class Respecializing:
        """A runner whose 'cache' grows every call (the seeded hazard)."""

        def __init__(self):
            self.calls = 0

        def __call__(self, params, thresholds, images):
            self.calls += 1
            return jnp.zeros(()), None

        def _cache_size(self):
            return self.calls

    assert not harness.second_pass_flat(Respecializing(), None, None, None)


# ---------------------------------------------------------------------------
# Baseline mechanism
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_and_split(tmp_path):
    f1 = Finding("dead-module", "warning", "src/a.py", 1, "m1")
    f2 = Finding("dead-module", "warning", "src/b.py", 1, "m2")
    path = tmp_path / "audit_baseline.json"
    Baseline.from_findings([f1], justification="known quirk").save(str(path))
    bl = Baseline.load(str(path))
    fresh, matched, stale = bl.split([f1, f2])
    assert (fresh, matched, stale) == ([f2], [f1], [])
    # fingerprint is line-insensitive: a shifted line still matches
    moved = Finding("dead-module", "warning", "src/a.py", 99, "m1")
    assert moved in bl


def test_baseline_requires_justification(tmp_path):
    path = tmp_path / "audit_baseline.json"
    path.write_text(json.dumps({"findings": [
        {"rule": "r", "file": "f", "message": "m", "justification": "  "}]}))
    with pytest.raises(BaselineError, match="justification"):
        Baseline.load(str(path))


def test_finding_rejects_unknown_severity():
    with pytest.raises(ValueError, match="severity"):
        Finding("r", "fatal", "f", 1, "m")


# ---------------------------------------------------------------------------
# The real repo is clean
# ---------------------------------------------------------------------------

def test_static_layer_clean_on_repo():
    """AST + reachability over src/: zero findings of any severity (the
    dead-code warnings the auditor first raised were fixed by deletion)."""
    findings = cli.collect_static(ROOT)
    assert findings == [], [f.render() for f in findings]


def test_cli_no_trace_exits_zero():
    assert cli.main(["--no-trace", "--strict"]) == 0


def test_traced_backend_probes_clean():
    """One traced backend + the sparse pieces + the quant kernels: the
    expensive full sweep runs in CI via `python -m repro.audit --strict`;
    this keeps a fast representative slice in the tier-1 suite."""
    from repro.core import engine

    cfg = probe.probe_config()
    tainted = probe.batch_tainted_sizes(cfg)

    closed = probe.trace_backend("queue_pallas", cfg)
    assert jaxpr_rules.check_dtypes("backend:queue_pallas", closed, ROOT) == []
    assert jaxpr_rules.check_batch_purity(
        "backend:queue_pallas", closed, tainted, 0, ROOT) == []

    pieces = probe.trace_sparse_pieces(cfg)
    stats = pieces["engine._sparse_stats_fn"]
    declared = engine.BACKEND_CONTRACTS["queue_sparse"].cross_batch_reductions
    assert jaxpr_rules.check_batch_purity(
        "stats", stats, tainted, declared, ROOT) == []

    for name, closed in probe.trace_quant_kernels().items():
        assert jaxpr_rules.check_quant(name, closed, QuantContract(),
                                       ROOT) == [], name


def test_train_step_trace_loss_purity_and_full_step_dtypes():
    """The direct-training traces obey their declared contract.

    The loss forward owns exactly ``train_loss_reductions`` batch-axis
    eliminations (batch-mean CE + batch-mean rate regularizer); declaring
    one fewer must fire batch-purity, which proves the rule actually walks
    the surrogate dynamics. The full grad step — whose backward contracts
    the batch into every weight gradient, hence no purity count — still
    passes dtype and host-sync discipline."""
    from repro.core import engine

    cfg = probe.probe_config()
    tainted = probe.batch_tainted_sizes(cfg)
    declared = engine.BACKEND_CONTRACTS["dense"].train_loss_reductions
    assert declared == 2

    traces = probe.trace_train_step(cfg)
    loss = traces["training.loss_fn[count+rate_reg]"]
    assert jaxpr_rules.check_batch_purity(
        "training.loss_fn", loss, tainted, declared, ROOT) == []
    under = jaxpr_rules.check_batch_purity(
        "training.loss_fn", loss, tainted, declared - 1, ROOT)
    assert under and all(f.rule == "batch-purity" for f in under)
    assert all(f.severity == "error" for f in under)

    step = traces["training.train_step"]
    for closed, name in ((loss, "loss"), (step, "step")):
        assert jaxpr_rules.check_dtypes(f"training.{name}", closed, ROOT) == []
        assert jaxpr_rules.check_host_sync(f"training.{name}", closed,
                                           ROOT) == []
