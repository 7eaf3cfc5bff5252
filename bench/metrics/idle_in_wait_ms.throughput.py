"""Device-idle ms per step while the innermost span is ``serve.wait``
(``block_until_ready`` on the plan's results): gaps inside the plan's own
run (``spans.py``)."""
import spans


def read(run):
    return spans.idle_ms(run, "wait")
