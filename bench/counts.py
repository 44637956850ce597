"""The work the algorithm needs for one served step, from its shapes.

Kept with the benchmark, not taken from the program, so that a change to
the program cannot change what a roofline or ``mfu`` is measured against.
Each family's module counts its own steps (``decode_step`` and
``prefill`` of ``bench/arch/<arch>.py``, from the ``model`` part of a
configuration file of ``bench/configs``); this module holds what they
share.

Not counted: empty slots, positions past a sequence's length, and copies
the program makes that the algorithm does not need (an undonated cache).
So a program that stops making them can only raise its share.
"""
from __future__ import annotations

from typing import Dict

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}
LOGIT_BYTES = 4


def least_time(flops: float, byts: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops_per_s"], byts / peak["hbm_bytes_per_s"])
