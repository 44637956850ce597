"""The phase arbitration's part of ``prefill_wait_p90_s``: the 90th
percentile, over the requests due in the window, of the time from their
prefill's ``flex.enqueue`` end to its ``flex.exec.call`` start
(``bench.spans.prefill_queue``)."""
from bench.records import due_in, quantile
from bench.spans import prefill_queue


def read(run):
    return quantile(prefill_queue(run.timeline, due_in(run.requests, run.w0,
                                                       run.w1)), 0.9)
