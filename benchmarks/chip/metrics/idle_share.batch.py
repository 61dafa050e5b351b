"""idle_share.batch: the share of the traced window in which no
operation ran on the device (1 - busy union / window)."""

from benchmarks.chip import trace_reduce


def read(run):
    return trace_reduce.idle_share(run.trace)
