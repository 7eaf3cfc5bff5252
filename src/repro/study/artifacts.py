"""Typed artifacts flowing between the Study pipeline stages.

Each stage produces exactly one artifact type; every artifact carries the
content key it was cached under, so provenance survives across the memory
and disk tiers. All bulk payloads are numpy (framework-free pickles); the
stages rehydrate them to jax arrays where a device computes on them, and
price a :class:`StatsRecord` on the host as it is.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class TrainArtifact(NamedTuple):
    """Output of the ``train`` stage (or a wrapper around caller params)."""

    params: list            # per-layer {'w','b'} pytree (jax arrays)
    train_images: np.ndarray | None   # None when params came from the caller
    train_labels: np.ndarray | None
    key: str


class ConvertArtifact(NamedTuple):
    """Output of the ``convert`` stage: the m-TTFS SNN."""

    snn_params: list        # normalized weights (same pytree layout)
    thresholds: list        # per-layer V_t (balanced when spec.balance)
    key: str


class DirectTrainArtifact(NamedTuple):
    """Output of the ``train_snn`` stage: the surrogate-gradient-trained SNN.

    Field-compatible with :class:`ConvertArtifact` on purpose — ``collect``
    (and everything downstream) consumes ``snn_params``/``thresholds``
    without knowing whether the net was converted or trained directly.
    """

    snn_params: list        # directly trained weights (same pytree layout)
    thresholds: list        # unit thresholds (the net is trained to them)
    key: str


class StatsRecord(NamedTuple):
    """Raw per-sample SNNStats, stacked over the eval set (N samples).

    This is the paper's per-sample toggle accounting in recordable form:
    everything the energy model needs, nothing it has to re-measure. All
    fields are integer counts, so repricing from a record is *exact* —
    pricing a record equals pricing a fresh inference bit-for-bit.
    """

    events_in: np.ndarray    # (N, L) events consumed per layer
    spikes_out: np.ndarray   # (N, L) spikes emitted per layer
    add_ops: np.ndarray      # (N, L) scalar accumulations
    queue_words: np.ndarray  # (N, L) peak words resident per layer queue
    overflow: np.ndarray     # (N,)  dropped events per sample


class CollectArtifact(NamedTuple):
    """Output of the ``collect`` stage: one batched SNN inference pass."""

    images: np.ndarray       # (N, H, W, C) — kept so pricing variants can
                             # re-evaluate the *CNN* side (bit-width sweeps)
    snn_logits: np.ndarray   # (N, n_out)
    snn_pred: np.ndarray     # (N,) argmax, computed at collect time
    stats: StatsRecord
    key: str
