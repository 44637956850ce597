"""Host time per decode step: the median, over consecutive decode steps
of replica 0 dispatched in the window with no other launch between them,
of the time from one step's ``flex.exec.wait`` end to the next step's
``flex.exec.call`` start (``bench.spans.decode_host_gaps``)."""
from bench.records import quantile
from bench.spans import decode_host_gaps


def read(run):
    return quantile(decode_host_gaps(run.timeline, run.w0, run.w1), 0.5)
