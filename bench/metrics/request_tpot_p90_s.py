"""90th percentile over requests of each request's mean gap between
consecutive output tokens emitted inside the window (every request with at
least two tokens there, finished or not).  A host stall of 0.1 s lands on
the 8 requests then decoding and can lift several past the p90, so it
swings from run to run with where the stalls fall; ``itl_p95_s`` is the
end-to-end reading of the decode cadence."""
from bench.records import quantile, tpots


def read(run):
    return quantile(tpots(run.requests, run.w0, run.w1), 0.9)
