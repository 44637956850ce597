"""Median of every inter-token gap in the window, pooled as for
``itl_p95_s``: the decode step's own cadence.  The some 5% of gaps that
waited on a prefill or a slot insertion leave it alone, so it reads the
step time to about 1% from run to run, where the p95, at the edge of
those gaps, can jump to the wait.  It shows a change of the decode step
that ``itl_p95_s``'s wider bound lets pass."""
from bench.records import itls, quantile


def read(run):
    return quantile(itls(run.requests, run.w0, run.w1), 0.5)
