"""The conv stages' least time over the device time of the ops that run
them: each stage's least time is the larger of its dense-equivalent FLOPs
over the bf16 peak and the bytes any realization must move over HBM
bandwidth (``work.stage_least_s``), summed over the traced steps; the ops
are those ``trace_reduce`` assigns to the conv stages."""


def read(run):
    t = run.trace
    if t is None or not t.conv_op_s:
        return None
    return 100.0 * t.conv_least_s / t.conv_op_s
