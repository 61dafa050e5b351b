"""admit_share.batch: the share of the device's busy time spent outside
the decode step's program (prefill, splice, argmax: the batcher's
admission), in the traced window."""

from benchmarks.chip import trace_reduce


def read(run):
    return trace_reduce.admit_share(run.trace)
