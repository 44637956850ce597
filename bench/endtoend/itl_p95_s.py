"""95th percentile of every inter-token gap in the window: the gaps
between consecutive output tokens of one request, both emitted inside the
window, pooled over all requests (finished or not).  About one gap in ten
is a decode step that waited on a prefill or a slot insertion; the p95
sits inside that cluster, so it reads what co-location costs the decode
cadence, and not the jump at its lower edge near the p91.  A host stall
lengthens one gap of each request then decoding, a few of some fifteen
thousand, so it barely moves the p95."""
from bench.records import itls, quantile


def read(run):
    return quantile(itls(run.requests, run.w0, run.w1), 0.95)
