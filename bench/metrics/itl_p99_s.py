"""99th percentile of every inter-token gap in the window, pooled as for
``itl_p95_s``: it lies inside the some 5% of gaps that waited on a prefill
or a slot insertion, so it reads what such a wait costs a decode step,
steadily from run to run, where the p95, at the edge of those gaps, jumps
between the step time and the wait."""
from bench.records import itls, quantile


def read(run):
    return quantile(itls(run.requests, run.w0, run.w1), 0.99)
