"""Persistent XLA compilation-cache wiring (cold-start, ROADMAP item 3).

The engine's jit programs and the serving layer's AOT plans both bottom out
in XLA compiles, and by default those die with the process — every fresh
replica re-pays minutes of compilation the previous one already did. JAX
ships a content-addressed persistent cache (keyed on the HLO module, the
compile options, and the jaxlib version); this module is the one place the
repo turns it on:

- :func:`configure` turns the cache on in the directory JAX's own
  ``JAX_COMPILATION_CACHE_DIR`` names, and only where that is unset in one
  fixed, git-ignored directory of the checkout (:data:`DEFAULT_DIR`). It
  drops the size/time floors so *every* executable persists (the paper
  nets are small; the default floors would skip them all). No code chooses
  any other directory: the path is part of what the cache is looked up by,
  so a directory that moves never hits.
- ``core.engine`` calls :func:`configure_from_env` at import, so any entry
  point (pytest, benches, the serve fleet) that runs with
  ``JAX_COMPILATION_CACHE_DIR`` exported gets the floors and counters too.
  Entry points that always want the cache (the fleet's workers, the
  cold-start bench, ``chip_smoke.py``) call :func:`configure` themselves.
- Hit/miss/put counters: JAX does not expose cache statistics, so
  :func:`configure` wraps the internal get/put hooks
  (``jax._src.compilation_cache``) and bumps both the module-level
  :data:`counters` and the obs counters ``compile_cache.hit`` /
  ``compile_cache.miss`` / ``compile_cache.put``. The wrap is best-effort:
  if a future jax moves the private hooks, caching still works and only
  the counts go dark (``counters["instrumented"]`` says which).
- ``REPRO_CACHE_STATS=<path>``: at process exit, append one JSON line of
  counters + cache-dir totals — how CI prints per-leg hit/miss counts
  (``python -m repro.core.compile_cache summarize <path>``) without
  enabling full tracing.

Cache keys are content hashes, so a shared directory can never serve a
stale executable for changed code — a miss just recompiles (see
docs/SERVING.md, "Cold start").
"""
from __future__ import annotations

import atexit
import json
import os

from .. import obs

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
ENV_STATS = "REPRO_CACHE_STATS"

#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

#: process-wide cache statistics, live-updated once :func:`configure` ran;
#: ``instrumented`` records whether the private-hook wrap succeeded.
counters = {"hits": 0, "misses": 0, "puts": 0, "instrumented": False}

_state = {"dir": None, "wrapped": False, "atexit": False}


def cache_dir() -> str | None:
    """The configured persistent-cache directory (None = not configured)."""
    return _state["dir"]


def resolve_dir() -> str:
    """The directory :func:`configure` uses (does not import jax)."""
    return os.environ.get(ENV_DIR) or DEFAULT_DIR


def configure() -> str:
    """Enable the persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX already reads it, so it is
    left alone), else :data:`DEFAULT_DIR`. Idempotent.
    """
    import jax

    directory = resolve_dir()
    os.makedirs(directory, exist_ok=True)
    if not os.environ.get(ENV_DIR):
        jax.config.update("jax_compilation_cache_dir", directory)
    # persist everything: the paper nets compile in well under the default
    # 1s floor, and the default min-entry-size would skip them silently
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _state["dir"] = directory
    _instrument()
    if os.environ.get(ENV_STATS) and not _state["atexit"]:
        _state["atexit"] = True
        atexit.register(_dump_stats, os.environ[ENV_STATS])
    return directory


def configure_from_env() -> str | None:
    """:func:`configure` iff ``$JAX_COMPILATION_CACHE_DIR`` is set."""
    if os.environ.get(ENV_DIR):
        return configure()
    return None


def _instrument() -> None:
    """Wrap jax's internal cache get/put so hits/misses are countable."""
    if _state["wrapped"]:
        return
    try:
        from jax._src import compilation_cache as cc

        real_get = cc.get_executable_and_time
        real_put = cc.put_executable_and_time
    except (ImportError, AttributeError):
        return  # private API moved: cache still works, counts go dark

    def counted_get(*a, **kw):
        out = real_get(*a, **kw)
        executable = out[0] if isinstance(out, tuple) else out
        hit = executable is not None
        counters["hits" if hit else "misses"] += 1
        obs.counter("compile_cache.hit" if hit else "compile_cache.miss")
        return out

    def counted_put(*a, **kw):
        counters["puts"] += 1
        obs.counter("compile_cache.put")
        return real_put(*a, **kw)

    cc.get_executable_and_time = counted_get
    cc.put_executable_and_time = counted_put
    counters["instrumented"] = True
    _state["wrapped"] = True


def stats() -> dict:
    """Counters + on-disk totals for the active cache directory."""
    out = dict(counters, dir=_state["dir"], entries=0, bytes=0)
    d = _state["dir"]
    if d and os.path.isdir(d):
        for base, _, files in os.walk(d):
            for f in files:
                try:
                    out["bytes"] += os.path.getsize(os.path.join(base, f))
                    out["entries"] += 1
                except OSError:
                    continue  # concurrent writer renamed a tmp file
    return out


def _dump_stats(path: str) -> None:
    """Append this process's cache stats as one JSON line (fleet-safe)."""
    try:
        line = json.dumps(dict(stats(), pid=os.getpid()))
        with open(path, "a") as f:
            f.write(line + "\n")
    except OSError:
        pass  # stats are advisory; never fail a run over them


# ---------------------------------------------------------------------------
# CLI: aggregate REPRO_CACHE_STATS lines into a markdown table (CI summary)
# ---------------------------------------------------------------------------

def summarize(paths: list[str]) -> str:
    """Markdown table over the JSONL stat lines in ``paths``."""
    rows = []
    for path in paths:
        if not os.path.exists(path):
            continue
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        rows.append(json.loads(line))
                    except json.JSONDecodeError:
                        continue
    if not rows:
        return ("## Compilation cache\n\nno stats recorded (is "
                f"`{ENV_STATS}` set and `{ENV_DIR}` configured?)\n")
    hits = sum(r.get("hits", 0) for r in rows)
    misses = sum(r.get("misses", 0) for r in rows)
    puts = sum(r.get("puts", 0) for r in rows)
    total = hits + misses
    rate = f"{hits / total:.0%}" if total else "n/a"
    last = rows[-1]
    lines = [
        "## Compilation cache",
        "",
        "| processes | hits | misses | puts | hit rate | entries | size |",
        "|---:|---:|---:|---:|---:|---:|---:|",
        f"| {len(rows)} | {hits} | {misses} | {puts} | {rate} "
        f"| {last.get('entries', 0)} | {last.get('bytes', 0) / 1e6:.1f} MB |",
        "",
        f"dir: `{last.get('dir')}`",
        "",
    ]
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="summarize REPRO_CACHE_STATS JSONL dumps")
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("summarize",
                       help="aggregate stat lines into a markdown table")
    s.add_argument("paths", nargs="+")
    s.add_argument("--summary", default="", metavar="FILE",
                   help="also append the table to FILE "
                        "(e.g. $GITHUB_STEP_SUMMARY)")
    args = ap.parse_args(argv)

    table = summarize(args.paths)
    print(table)
    if args.summary:
        with open(args.summary, "a") as f:
            f.write(table)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
