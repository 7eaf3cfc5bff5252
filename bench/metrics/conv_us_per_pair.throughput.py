"""Device microseconds of the conv stages per (sample x time step, input
channel) pair of the net's spike-fed conv stages: ``trace_reduce``'s
``conv_op_s`` over the window's steps, divided by the sum over those steps
of bucket x T x the sum of ``c_in`` over every conv stage after the analog
first one, counted from ``work.Net`` and never from the program. The cost
of one (row, channel) cell of the event kernel's work, in a unit that does
not depend on how a kernel lays out its grid."""


def read(run):
    t, batches = run.trace, run.window.batches
    if t is None or not t.conv_op_s or not batches:
        return None
    per_image = run.config["T"] * sum(s.c_in for s in run.net.stages
                                      if not s.analog)
    pairs = sum(b.bucket for b in batches) * per_image
    # conv_op_s is one chip's time; the steps' pairs are spread over chips
    return 1e6 * t.conv_op_s * run.chips / pairs
