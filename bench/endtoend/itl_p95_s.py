"""95th percentile of every inter-token gap in the window: the gaps
between consecutive output tokens of one request, both emitted inside the
window, pooled over all requests (finished or not).  Some 5% of the gaps
(4.7-5.5% on a v5e) are decode steps that waited on a prefill or a slot
insertion, about twice the step time; the p95 sits at the edge of that
cluster, so it reads the step time in most runs and the wait in those
where the share passes 5%.  A host stall lengthens only one gap of each
request then decoding, but one of some seconds leaves a backlog of
prefills behind it whose waits can carry the share past 5%.
``itl_p50_s`` and ``itl_p99_s`` read either side steadily."""
from bench.records import itls, quantile


def read(run):
    return quantile(itls(run.requests, run.w0, run.w1), 0.95)
