"""What a load generator records in the measured window."""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class Batch:
    """One ``ServeRuntime.step``: host-clock times and the runtime's reads."""

    t0: float              # the benchmark's clock before the step
    t1: float              # ... after it returned
    bucket: int
    n: int                 # requests served (the rest of the bucket padded)
    batch_form_s: float    # the runtime's own clock reads of the step
    service_s: float
    price_s: float


@dataclass
class Window:
    t0: float                               # first due request
    t1: float = 0.0                         # last response
    batches: list = field(default_factory=list)
    answers: dict = field(default_factory=dict)   # rid -> (logits, events)
    image_of: dict = field(default_factory=dict)  # rid -> pool index
    latency_s: list = field(default_factory=list) # open loop: due -> done
    lateness_s: list = field(default_factory=list)  # submit - due
    attempted: int = 0
    failed: int = 0

    def record(self, t0, t1, responses) -> None:
        """Keep one step's responses; every request of it completes at t1."""
        if not responses:
            return
        r = responses[0]
        self.batches.append(Batch(t0, t1, r.bucket, len(responses),
                                  r.batch_form_s, r.service_s, r.price_s))
        for r in responses:
            self.answers[r.rid] = (np.asarray(r.logits),
                                   np.asarray(r.stats.events_in[0]))
        self.t1 = t1

    @property
    def served(self) -> int:
        return sum(b.n for b in self.batches)

    def summary(self) -> dict:
        out = {"sent": self.attempted, "succeeded": len(self.answers),
               "failed": self.failed, "batches": len(self.batches),
               "buckets": {}}
        for b in self.batches:
            out["buckets"][b.bucket] = out["buckets"].get(b.bucket, 0) + 1
        if self.lateness_s:
            late = np.asarray(self.lateness_s)
            out["generator_late_ms"] = {
                "p50": float(np.percentile(late, 50)) * 1e3,
                "p99": float(np.percentile(late, 99)) * 1e3,
                "max": float(late.max()) * 1e3}
        return out
