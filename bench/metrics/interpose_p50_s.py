"""The daemon's own host time per op: the median, over the launches
completed in the window, of ``flex.enqueue`` + ``flex.select`` +
``flex.handoff`` + the self time of ``flex.complete``
(``bench.spans.interposition``)."""
from bench.records import quantile
from bench.spans import interposition


def read(run):
    return quantile(interposition(run.timeline, run.w0, run.w1), 0.5)
