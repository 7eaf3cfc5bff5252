"""One run of one benchmark cell: set-up, the measured window, the check.

``run.py`` finds the cell's configuration, traffic mix and metrics by the
names in ``BENCHMARK.json`` and hands them here. Nothing in this module
knows a cell by name:

- the configuration file says which net is served, on which engine
  backend, with which bucket ladder and at which precision, and holds the
  limits of the comparison that decides ``correct``;
- the traffic file names a generator in ``loadgen/`` by its ``kind`` and
  holds that generator's parameters;
- each metric is a reader in ``metrics/<name>.py`` with one function,
  ``read(run)``, that returns a number or None.

The system under test is the program's serving path: ``ServeRuntime``
(``serve/runtime.py``) over a ``ModelRegistry`` handle, whose compiled
per-bucket plan runs the engine's batched plan on the configured backend.
The benchmark makes the weights and the request images from the seed
(``reference.py``) and feeds the same arrays to the program and to the
reference.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

import reference  # noqa: E402  (bench/ is on sys.path: run.py puts it there)
import work  # noqa: E402

#: where a traced run leaves its profile, inside the checkout
OUT_DIR = os.path.join(ROOT, ".bench_out")

#: requests whose served answers are compared with the reference, per run
SAMPLE = 256


def load_module(path: str, name: str):
    """Import a benchmark file by path (metric and generator names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# Compile accounting
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts JAX's compile requests, process-wide.

    ``jax.monitoring`` reports each program that goes to the persistent
    compilation cache, and whether it was found there; a request inside the
    measured window means a program was traced and compiled (or loaded)
    there.
    """

    EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses",
              "/jax/compilation_cache/compile_requests_use_cache":
                  "compile_requests"}

    def __init__(self):
        import jax.monitoring

        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._event)

    def _event(self, name, **_):
        if name in self.EVENTS:
            self.counts[self.EVENTS[name]] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def configure_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout.

    ``JAX_COMPILATION_CACHE_DIR`` wins where it is set (JAX reads it
    itself); otherwise ``.jax_cache/`` at the checkout's root, the
    directory the program's own ``compile_cache`` uses. Every program is
    kept, however small or quick to compile.
    """
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    os.makedirs(directory, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

@dataclass
class Served:
    """The program's serving stack for one configuration, warmed."""

    runtime: object
    model: str
    buckets: tuple
    net: work.Net
    layers: list           # the benchmark's weights (reference layout)
    pool: np.ndarray       # (P, H, W, C) request images
    warm: dict = field(default_factory=dict)


def program_params(spec: str, layers):
    """The program's layout: one entry per spec token, {} at each pool."""
    it = iter(layers)
    return [{} if tok.startswith("P") else next(it)
            for tok in spec.split("-")]


def reachable_counts(ladder, select) -> dict:
    """bucket -> the request counts the batcher can serve in it, for a
    queue that may hold any number of requests."""
    out = {}
    for n in range(1, max(ladder) + 1):
        b = select(n)
        out.setdefault(b, set()).add(min(n, b))
    return {b: sorted(ns) for b, ns in out.items()}


def build(config: dict, traffic: dict, loadgen, seed: int,
          chips: int) -> Served:
    """Weights and images from the seed, the runtime, and its warm-up.

    Warms only what the cell's traffic reaches, as its generator says
    (``loadgen.<kind>.shapes``): the buckets it forms and, in each, the
    request counts the batcher serves there, since the program slices and
    prices each count as a shape of its own. Each compiled plan runs once.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import engine
    from repro.serve import ModelRegistry, ServeRuntime
    from repro.serve.batching import BucketPolicy
    from repro.study.artifacts import StatsRecord
    from repro.study.stages import price_record

    net = work.parse(config["spec"], config["input_hw"], config["input_c"])
    layers = reference.make_params(net, seed)
    pool = reference.make_images(traffic["pool"], seed, config["input_hw"])
    buckets = tuple(config["buckets"])
    policy = BucketPolicy(buckets)

    cfg = engine.SNNConfig(
        spec=config["spec"], input_hw=config["input_hw"],
        input_c=config["input_c"], T=config["T"], mode=config["mode"],
        depth=config["depth"], v_init_frac=config["v_init_frac"])
    registry = ModelRegistry(plan_cache_size=max(8, len(buckets)))
    model = config["name"]
    registry.register(model, program_params(config["spec"], layers),
                      [config["threshold"]] * len(config["spec"].split("-")),
                      cfg, backend=config["backend"])
    mesh = None
    if chips > 1:
        from repro import parallel

        mesh = parallel.data_mesh(chips)
    runtime = ServeRuntime(registry, policy, mesh=mesh)
    handle = registry.get(model)

    warm = {}
    for b, counts in loadgen.shapes(traffic, buckets, policy.select).items():
        t0 = time.perf_counter()
        images = jnp.zeros((b, cfg.input_hw, cfg.input_hw, cfg.input_c),
                           jnp.float32)
        logits, stats = handle.plan_for(b)(handle.params, handle.thresholds,
                                           images)
        jax.block_until_ready(logits)
        for n in counts:
            lg, st = engine.slice_valid(logits, stats, n)
            np.asarray(lg)
            rec = StatsRecord(*(np.asarray(getattr(st, f)) for f in (
                "events_in", "spikes_out", "add_ops", "queue_words",
                "overflow")))
            e = price_record(rec, input_hw=cfg.input_hw,
                             compressed=cfg.compressed,
                             vmem_resident=handle.vmem_resident)
            np.asarray(e.total_j)
            np.asarray(e.latency_s)
        warm[b] = time.perf_counter() - t0
    return Served(runtime, model, buckets, net, layers, pool, warm)


# ---------------------------------------------------------------------------
# The comparison that decides ``correct``
# ---------------------------------------------------------------------------

def compare(served_logits, served_events, ref_logits, ref_events) -> dict:
    """The numbers that are held to the configuration's limits.

    - ``logit_gap``: the widest |served - reference| logit over the sample,
      as a share of the reference's largest |logit| there;
    - ``events_gap``: per weighted layer, |served - reference| of the
      events summed over the sample, over the reference's sum; the widest
      layer;
    - ``events_mismatch``: the share of sampled requests whose events in
      any layer differ from the reference's.
    """
    scale = float(np.abs(ref_logits).max())
    gap = float(np.abs(served_logits - ref_logits).max()) / scale
    s = served_events.sum(0).astype(np.float64)
    r = ref_events.sum(0).astype(np.float64)
    layer_gaps = [abs(a - b) / b for a, b in zip(s, r) if b > 0]
    return {"logit_gap": gap, "events_gap": float(max(layer_gaps)),
            "events_mismatch": float((served_events != ref_events)
                                     .any(1).mean())}


def sample_answers(served: Served, window, seed: int):
    """A seeded sample of the window's answers: (images, logits, events)."""
    rids = sorted(window.answers)
    rng = np.random.default_rng(list(reference.seed_words(seed)) + [1])
    pick = rng.choice(len(rids), size=min(SAMPLE, len(rids)), replace=False)
    rids = [rids[i] for i in sorted(pick)]
    return (served.pool[[window.image_of[r] for r in rids]],
            np.stack([window.answers[r][0] for r in rids]),
            np.stack([window.answers[r][1] for r in rids]))


def reference_on(config: dict, served: Served, images,
                 precision: str | None = None):
    """The reference at the configuration's precision, or at ``precision``."""
    return reference.forward(
        served.net, served.layers, images, T=config["T"],
        threshold=config["threshold"], v_init=config["v_init_frac"],
        precision=precision or config["precision"])


def check(config: dict, served: Served, window, seed: int):
    """Returns (correct, {name: [value, limit]}) over the seeded sample."""
    images, logits, events = sample_answers(served, window, seed)
    numbers = compare(logits, events, *reference_on(config, served, images))
    table = {k: [v, config["limits"][k]] for k, v in numbers.items()}
    ok = all(np.isfinite(v) and v <= lim for v, lim in table.values())
    log(f"compared {len(images)} sampled answers with the reference")
    return ok, table


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclass
class Run:
    """What the metric readers read."""

    cell: dict
    config: dict
    traffic: dict
    chips: int
    device_kind: str
    peaks: dict
    net: work.Net
    setup_s: float
    window: object
    trace: object = None


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, t_process0: float, devices) -> dict:
    """Set up, measure, check; returns the result line as a dict."""
    import jax

    import stats
    import trace_reduce

    config = load_json(ROOT, next(c["file"] for c in bench["configs"]
                                  if c["name"] == cell["config"]))
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    loadgen = load_module(os.path.join(BENCH, "loadgen",
                                       traffic["kind"] + ".py"),
                          "loadgen_" + traffic["kind"])
    chips = cell["chips"]
    used = devices[:chips]
    kind = used[0].device_kind
    peaks = stats.peaks(kind)

    counter = CompileCounter()
    cache_dir = configure_cache()
    served = build(config, traffic, loadgen, seed, chips)
    set_up = counter.snapshot()
    setup_s = time.perf_counter() - t_process0
    log(f"set-up {setup_s:.3f}s; compile cache {cache_dir}: {set_up}; "
        f"warm-up by bucket (s): {served.warm}")

    profile_dir = os.path.join(OUT_DIR, cell["name"], "trace")
    if trace:
        import shutil

        shutil.rmtree(profile_dir, ignore_errors=True)
        jax.profiler.start_trace(profile_dir)
    window = loadgen.run(served, traffic, seconds, seed)
    if trace:
        jax.profiler.stop_trace()
    in_window = {k: v - set_up[k] for k, v in counter.snapshot().items()}
    log(f"window {window.t1 - window.t0:.3f}s: {window.summary()}; "
        f"compiles in the window: {in_window}")

    mem = [d.memory_stats() or {} for d in used]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    trace_summary = None
    if trace:
        handle = served.runtime.registry.get(served.model)
        plans = [trace_reduce.stage_ops(handle.plan_for(b).as_text())
                 for b in served.warm]
        trace_summary = trace_reduce.reduce(
            trace_reduce.find_xplane(profile_dir), config, served.net,
            window.batches, peaks, plans)

    run = Run(cell, config, traffic, chips, kind, peaks, served.net, setup_s,
              window, trace_summary)
    # the program's state goes before the reference runs
    served.runtime = None
    gc.collect()
    correct, table = check(config, served, window, seed)

    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench[section]:
        if not applies(bench, m, cell["name"], section):
            continue
        reader = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                             "metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": used[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": window.attempted,
           "failed": window.failed, "metrics": metrics, "device": device}
    if trace_summary is not None:
        device["busy_s"] = trace_summary.busy_s
        device["window_s"] = trace_summary.window_s
        out["breakdown"] = trace_summary.breakdown
    out["compared"] = table
    return out


def applies(bench: dict, metric: dict, cell: str, section: str) -> bool:
    """Whether ``metric`` is reported in ``cell``.

    A ``workloads`` list names the cells; without it an end-to-end metric
    is in every cell, and a per-layer metric in every cell that reports
    the end-to-end metric it ``moves``.
    """
    if "workloads" in metric:
        return cell in metric["workloads"]
    if section == "end_to_end":
        return True
    moved = next(m for m in bench["end_to_end"] if m["name"] == metric["moves"])
    return applies(bench, moved, cell, "end_to_end")
