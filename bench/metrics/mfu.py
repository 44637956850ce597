"""The whole step's share of the chip's peak, in percent: model FLOPs of
the prefill and decode steps that completed in the traced window over the
window's length x chips x peak (``records.step_mfu``).  Beside
``decode_step_roofline``, it moves ``itl_p95_s``."""
from bench.records import step_mfu


def read(run):
    return step_mfu(run)
