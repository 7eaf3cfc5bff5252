"""Device program runs (the first chip's ``XLA Modules`` line) that start
inside a ``serve.step`` span, per step (``spans.py``)."""
from spans import programs_per_step as read  # noqa: F401
