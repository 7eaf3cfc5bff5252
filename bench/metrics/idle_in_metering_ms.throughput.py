"""Device-idle ms per step while the innermost span is ``serve.slice``,
``serve.fetch``, ``serve.price`` or ``serve.respond``: the host slicing,
pulling, pricing and answering a finished batch (``spans.py``)."""
import spans


def read(run):
    return spans.idle_ms(run, "metering")
