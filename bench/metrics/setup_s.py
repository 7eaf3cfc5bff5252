"""Process start to the first due request: weights and images from the
seed, the runtime, and the warm-up of the cell's buckets."""


def read(run):
    return run.setup_s
