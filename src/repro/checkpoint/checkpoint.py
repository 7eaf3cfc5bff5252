"""Fault-tolerant checkpointing: atomic, sharded, mesh-agnostic, async.

Layout of a checkpoint directory:

    <root>/step_000123/
        manifest.json      # leaf paths, shapes, dtypes, shard files, hashes
        shard_00000.npz    # one file per host (sharded-by-host save)
    <root>/step_000123.COMMITTED   # atomic commit marker (rename-based)

Guarantees:
- **atomicity**: data is written to ``step_X.tmp-<nonce>`` and renamed; a
  checkpoint without its COMMITTED marker is ignored by ``latest_step`` and
  garbage-collected — a killed writer can never corrupt restore.
- **integrity**: every shard carries a content hash in the manifest;
  ``restore`` verifies before use and falls back to the previous checkpoint.
- **mesh-agnostic restore**: arrays are saved unsharded and restored as
  host arrays, so a process on any device count or mesh can place them.
- **async**: ``AsyncCheckpointer`` snapshots device arrays to host then
  writes on a background thread — the training loop never blocks on disk.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import tempfile
import threading
import time
from typing import Any

import jax
import numpy as np


def _flatten(tree, prefix=""):
    """Flatten a pytree of arrays to {path: leaf} with stable paths."""
    flat = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            flat.update(_flatten(tree[k], f"{prefix}/{k}"))
    elif hasattr(tree, "_fields"):
        for k, v in zip(tree._fields, tree):
            flat.update(_flatten(v, f"{prefix}/{k}"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            flat.update(_flatten(v, f"{prefix}/{i}"))
    else:
        flat[prefix] = tree
    return flat


def _unflatten_like(template, flat, prefix=""):
    if isinstance(template, dict):
        return {k: _unflatten_like(template[k], flat, f"{prefix}/{k}")
                for k in template}
    if hasattr(template, "_fields"):
        return type(template)(*[
            _unflatten_like(v, flat, f"{prefix}/{k}")
            for k, v in zip(template._fields, template)])
    if isinstance(template, (list, tuple)):
        return type(template)(
            _unflatten_like(v, flat, f"{prefix}/{i}")
            for i, v in enumerate(template))
    return flat[prefix]


def _hash(arr: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(arr).tobytes()).hexdigest()[:16]


def save(root: str, step: int, tree, *, process_index: int = 0,
         num_processes: int = 1) -> str:
    """Synchronous sharded save. Returns the committed directory path."""
    os.makedirs(root, exist_ok=True)
    final = os.path.join(root, f"step_{step:09d}")
    tmp = tempfile.mkdtemp(prefix=f"step_{step:09d}.tmp-", dir=root)

    flat = {k: np.asarray(v) for k, v in _flatten(tree).items()}
    paths = sorted(flat)
    mine = [p for i, p in enumerate(paths) if i % num_processes == process_index]

    shard_file = f"shard_{process_index:05d}.npz"
    np.savez(os.path.join(tmp, shard_file),
             **{p.replace("/", "|"): flat[p] for p in mine})

    manifest = {
        "step": step,
        "num_processes": num_processes,
        "leaves": {
            p: {
                "shape": list(flat[p].shape),
                "dtype": str(flat[p].dtype),
                "shard": f"shard_{paths.index(p) % num_processes:05d}.npz",
                "hash": _hash(flat[p]),
            }
            for p in paths
        },
    }
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)

    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    # commit marker: rename is atomic on POSIX
    open(final + ".COMMITTED", "w").close()
    return final


def latest_step(root: str) -> int | None:
    """Newest *committed and intact* checkpoint step (or None)."""
    if not os.path.isdir(root):
        return None
    steps = []
    for name in os.listdir(root):
        if name.startswith("step_") and name.endswith(".COMMITTED"):
            steps.append(int(name[len("step_"):-len(".COMMITTED")]))
    for s in sorted(steps, reverse=True):
        if _verify(os.path.join(root, f"step_{s:09d}")):
            return s
    return None


def _verify(path: str) -> bool:
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        for shard in {m["shard"] for m in manifest["leaves"].values()}:
            if not os.path.exists(os.path.join(path, shard)):
                return False
        return True
    except (OSError, json.JSONDecodeError, KeyError):
        return False


def restore(root: str, template, *, step: int | None = None,
            verify_hashes: bool = True):
    """Restore into the structure of ``template``. Returns (tree, step).

    Tries checkpoints newest-first; a corrupt one (missing shard / bad hash)
    is skipped with a warning — node-failure-mid-save never bricks the job.
    """
    candidates = ([step] if step is not None else [])
    if step is None:
        if not os.path.isdir(root):
            raise FileNotFoundError(root)
        candidates = sorted({
            int(n[len("step_"):-len(".COMMITTED")])
            for n in os.listdir(root)
            if n.startswith("step_") and n.endswith(".COMMITTED")
        }, reverse=True)

    last_err = None
    for s in candidates:
        path = os.path.join(root, f"step_{s:09d}")
        try:
            with open(os.path.join(path, "manifest.json")) as f:
                manifest = json.load(f)
            shards = {}
            for shard in {m["shard"] for m in manifest["leaves"].values()}:
                shards[shard] = np.load(os.path.join(path, shard))
            flat = {}
            for p, meta in manifest["leaves"].items():
                arr = shards[meta["shard"]][p.replace("/", "|")]
                if verify_hashes and _hash(arr) != meta["hash"]:
                    raise IOError(f"hash mismatch for {p}")
                flat[p] = arr
            return _unflatten_like(template, flat), s
        except Exception as e:  # noqa: BLE001 — any corruption => try older
            last_err = e
            continue
    raise IOError(f"no restorable checkpoint under {root}: {last_err}")


class AsyncCheckpointer:
    """Snapshot-then-write-in-background; at most one write in flight."""

    def __init__(self, root: str, *, keep: int = 3):
        self.root = root
        self.keep = keep
        self._thread: threading.Thread | None = None
        self.last_saved: int | None = None

    def save(self, step: int, tree):
        self.wait()
        host_tree = jax.tree.map(np.asarray, tree)  # device->host snapshot

        def _write():
            save(self.root, step, host_tree)
            self.last_saved = step
            self._gc()

        self._thread = threading.Thread(target=_write, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self):
        steps = sorted({
            int(n[len("step_"):-len(".COMMITTED")])
            for n in os.listdir(self.root)
            if n.startswith("step_") and n.endswith(".COMMITTED")
        })
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.root, f"step_{s:09d}"),
                          ignore_errors=True)
            try:
                os.remove(os.path.join(self.root, f"step_{s:09d}.COMMITTED"))
            except OSError:
                pass
        # sweep orphaned tmp dirs (killed writers)
        for n in os.listdir(self.root):
            if ".tmp-" in n:
                shutil.rmtree(os.path.join(self.root, n), ignore_errors=True)
