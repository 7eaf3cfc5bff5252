import os

# Tests must see the single real CPU device.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest


@pytest.fixture
def make_snn_config():
    """Factory for the ``SNNConfig(spec=..., input_hw=..., ...)`` boilerplate.

    Defaults the fields almost every test repeats (``input_c=1``,
    ``depth=64``); anything else is a keyword override:

        cfg = make_snn_config("6C3-P2-4C3-8", 10, T=3, mode="mttfs")
    """
    from repro.core.snn_model import SNNConfig

    def make(spec: str, input_hw: int, input_c: int = 1, *, depth: int = 64,
             **overrides) -> SNNConfig:
        return SNNConfig(spec=spec, input_hw=input_hw, input_c=input_c,
                         depth=depth, **overrides)

    return make


def event_geometries(specs):
    """Distinct conv stages fed by spike events: every conv after the
    analog-input first layer, one ConvPlan per (in_hw, in_c, out_c).

    ``specs`` are ``configs.PAPER_SPECS``-style dicts. A plain function,
    not a fixture, because test modules parametrize over it at collection.
    """
    from repro.core import engine

    seen = {}
    for d in specs:
        plan = engine.compile_plan(d["spec"], d["hw"], d["c"])
        for cp in plan.convs[1:]:
            seen.setdefault((cp.in_hw, cp.in_c, cp.out_c), cp)
    return [seen[k] for k in sorted(seen)]

# hypothesis is a dev extra (see pyproject.toml); the suite must collect and
# run without it — property-based tests import through tests/_prop.py, which
# degrades @given into a skip marker when the package is absent.
try:
    from hypothesis import settings
except ImportError:
    pass
else:
    settings.register_profile("ci", max_examples=20, deadline=None)
    settings.load_profile("ci")
