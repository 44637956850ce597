"""Arithmetic over the run's records: request token times, the engine's
per-op Timeline and the reduced device trace.  Every end-to-end and
per-layer metric is computed here or in a reader that calls into here.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from bench import arch
from repro.serving.request import Request


@dataclasses.dataclass
class TimedRequest(Request):
    """A request that keeps the time of every token the engine emits, and
    the time it was due (``arrival_time`` is preset to it)."""
    due: float = 0.0
    wanted: int = 0
    token_times: List[float] = dataclasses.field(default_factory=list)
    span: object = dataclasses.field(default=None, repr=False)

    def record_token(self, now: float) -> None:
        if self.span is not None:
            with self.span("bench.token"):
                self._record(now)
        else:
            self._record(now)

    def _record(self, now: float) -> None:
        super().record_token(now)
        self.token_times.append(now)


def quantile(xs, q: float) -> Optional[float]:
    """The q-quantile by linear interpolation (``None`` when empty)."""
    xs = sorted(xs)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def due_in(reqs, w0: float, w1: float) -> List[TimedRequest]:
    return [r for r in reqs if w0 <= r.due < w1]


def ttfts(reqs, w0: float, w1: float, closed: float) -> List[float]:
    """First-token time minus due time, over the requests due inside the
    window.  One that had no first token by ``closed`` (the end of the
    wait after the window) enters with the time it had waited by then."""
    out = []
    for r in due_in(reqs, w0, w1):
        t = r.token_times[0] if r.token_times else closed
        out.append(min(t, closed) - r.due)
    return out


def tpots(reqs, w0: float, w1: float) -> List[float]:
    """Each request's mean gap between consecutive tokens emitted inside
    the window, over every request with at least two tokens there."""
    out = []
    for r in reqs:
        ts = [t for t in r.token_times if w0 <= t <= w1]
        if len(ts) >= 2:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return out


def itls(reqs, w0: float, w1: float) -> List[float]:
    """Every gap between two consecutive tokens of one request, both
    emitted inside the window, pooled over all requests."""
    out = []
    for r in reqs:
        ts = [t for t in r.token_times if w0 <= t <= w1]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in(reqs, w0: float, w1: float) -> int:
    """Every token emitted inside the window, whichever request it
    belongs to."""
    return sum(1 for r in reqs for t in r.token_times if w0 <= t <= w1)


def served_tokens_in(reqs, w0: float, w1: float) -> int:
    """Tokens emitted inside the window by the requests due inside it.
    Tokens of requests due before the window are left out: a faster
    engine emits more of them before the window opens, so counting them
    would read the same work, finished sooner, as lost throughput."""
    return tokens_in(due_in(reqs, w0, w1), w0, w1)


def decode_steps(reqs) -> Dict[float, List[Tuple[TimedRequest, int]]]:
    """Decode steps rebuilt from token times: the engine stamps every
    sequence of one step with the same clock reading.  Value: the step's
    (request, context length including the token the step wrote)."""
    steps: Dict[float, List[Tuple[TimedRequest, int]]] = {}
    for r in reqs:
        for i, t in enumerate(r.token_times[1:], start=1):
            steps.setdefault(t, []).append((r, r.prompt_len + i))
    return steps


def step_mfu(run) -> Optional[float]:
    """Model FLOPs of the prefill and decode steps that completed in the
    traced window, over the window's length x chips x peak FLOP/s, in
    percent: the whole step's share of the chip's peak, host work and idle
    time included."""
    tr = run.device
    if tr is None or run.peak is None or not tr.chips:
        return None
    a = arch.of(run.model)
    flops = 0.0
    for t, seqs in decode_steps(run.requests).items():
        if tr.t0 <= t <= tr.t1:
            flops += a.decode_step(run.model, [c for _, c in seqs])[0]
    for r in run.requests:
        if r.token_times and tr.t0 <= r.token_times[0] <= tr.t1:
            flops += a.prefill(run.model, r.prompt_len)[0]
    if flops == 0:
        return None
    return 100.0 * flops / (tr.window_s * run.chips
                            * run.peak["flops_per_s"])
