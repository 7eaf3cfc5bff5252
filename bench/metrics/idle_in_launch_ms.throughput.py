"""Device-idle ms per step while the innermost span is ``serve.form``,
``serve.upload`` or ``serve.dispatch``: the host forming the batch, moving
it to the device and calling the plan (``spans.py``)."""
import spans


def read(run):
    return spans.idle_ms(run, "launch")
