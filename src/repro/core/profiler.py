"""Runtime profiler (paper §3.3): lightweight per-phase statistics.

Collected from the interception layer and daemon:
  * EWMA operator execution time per phase,
  * per-phase token throughput,
  * memory-bandwidth pressure of decode (bytes touched / exec time / HBM peak),
  * device utilization (busy fraction over a sliding horizon).

These are 'coarse but useful' signals (the paper's words) — the scheduler
reads them to steer the prefill/decode dispatch ratio.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import json
import os
import threading
import time
from typing import Deque, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.core.api import OpDescriptor, OpType, Phase

HBM_BW_BYTES = 819e9        # TPU v5e HBM bandwidth (DESIGN.md hardware model)


@dataclasses.dataclass
class PhaseStats:
    ewma_exec: float = 0.0          # seconds
    ewma_bytes: float = 0.0         # bytes touched per op
    ops_completed: int = 0
    tokens_done: int = 0

    def bandwidth_util(self) -> float:
        """Estimated HBM pressure of this phase's ops (0..1)."""
        if self.ewma_exec <= 0:
            return 0.0
        return min(1.0, self.ewma_bytes / self.ewma_exec / HBM_BW_BYTES)


class Profiler:
    def __init__(self, alpha: float = 0.2, horizon: float = 10.0):
        self.alpha = alpha
        self.horizon = horizon
        self.stats: Dict[Phase, PhaseStats] = {p: PhaseStats() for p in Phase}
        self._busy_events: Deque[Tuple[float, float]] = collections.deque()
        self._window_start = 0.0

    def _ewma(self, old: float, new: float) -> float:
        if old == 0.0:
            return new
        return (1 - self.alpha) * old + self.alpha * new

    def on_complete(self, op: OpDescriptor) -> None:
        s = self.stats[op.phase]
        s.ewma_exec = self._ewma(s.ewma_exec, op.exec_time)
        if "bytes" in op.meta:
            s.ewma_bytes = self._ewma(s.ewma_bytes, float(op.meta["bytes"]))
        s.ops_completed += 1
        s.tokens_done += int(op.meta.get("tokens", 0))
        if op.op == OpType.LAUNCH:
            self._busy_events.append((op.dispatch_time, op.complete_time))

    def device_utilization(self, now: float) -> float:
        """Busy fraction over the trailing horizon."""
        lo = now - self.horizon
        while self._busy_events and self._busy_events[0][1] < lo:
            self._busy_events.popleft()
        busy = sum(min(e, now) - max(s, lo) for s, e in self._busy_events
                   if min(e, now) > max(s, lo))
        return min(1.0, busy / self.horizon) if self.horizon > 0 else 0.0

    def decode_bandwidth_util(self) -> float:
        return self.stats[Phase.DECODE].bandwidth_util()


# --------------------------------------------------------------- timeline
def profile_enabled() -> bool:
    """``FLEX_PROFILE=1`` turns on per-op timeline capture (PR 9)."""
    return os.environ.get("FLEX_PROFILE", "") == "1"


def profile_dir() -> str:
    """Where ``Session.close`` writes trace files (``FLEX_PROFILE_DIR``,
    default: current directory)."""
    return os.environ.get("FLEX_PROFILE_DIR", ".")


_TRACE_IDS = itertools.count(1)


class Timeline:
    """Per-op timeline recorder → Chrome-trace JSON (PR 9, opt-in).

    One Timeline spans a session (like the hazard sanitizer): every
    daemon's ``mark_complete`` appends one complete event per op, and
    ``Session.close`` dumps ``flextrace-<pid>-<n>.json`` into
    :func:`profile_dir`.  Load the file in ``chrome://tracing`` or
    Perfetto: rows are (device, execution queue), one slice per op with
    dispatch→complete extents and the op's phase/type/meta in ``args``.

    Beside the ops, the threaded drive over the real backend records
    **host spans** (:meth:`span`, :meth:`add`) and **counters**
    (:meth:`counter`) into the same list, on the same ``time.monotonic``
    clock: a span is an ``X`` event on its thread's row (``tid`` is the
    thread's name), a counter a ``C`` event.  Span names hold no ``:``,
    so a reader that takes ``"<phase>:<op>"`` as an op never mistakes one.

    Capture is OFF unless ``FLEX_PROFILE=1`` — the hot path pays only a
    ``None`` check — and recording is one dict append under a lock, so
    turning it on perturbs (wall-clock) timing but never simulated time.
    """

    def __init__(self):
        self._lk = threading.Lock()
        self._events: List[dict] = []
        # per thread: the op_id of each open span that carries one
        self._local = threading.local()

    def record(self, device_id: int, op: OpDescriptor) -> None:
        q = op.meta.get("_queue")
        tid = f"{q[0]}:{q[1]}" if q else str(op.meta.get("_engine", "?"))
        ev = {
            "name": f"{op.phase.value}:{op.op.value}",
            "ph": "X",                           # complete event
            "ts": op.dispatch_time * 1e6,        # trace units are µs
            "dur": max(op.exec_time, 0.0) * 1e6,
            "pid": device_id,
            "tid": tid,
            "args": {"op_id": op.op_id, "vstream": op.vstream,
                     "queue_delay_us": max(op.queue_delay, 0.0) * 1e6},
        }
        for k in ("tokens", "bytes", "flops", "instance", "req_id",
                  "ctx", "chunk", "step"):
            if k in op.meta:
                ev["args"][k] = op.meta[k]
        with self._lk:
            self._events.append(ev)

    def span(self, name: str, *, device: int = 0, **args) -> "_Span":
        """Context manager: one ``X`` event from enter to exit on this
        thread's row of ``device``, with ``args``.  While open it also
        holds a ``jax.profiler.TraceAnnotation(name)``, so the span shows
        on its host thread in any ``jax.profiler`` trace.  A span given
        no ``op_id`` takes that of the innermost span open around it on
        its thread: work run by an op's completion names that op."""
        return _Span(self, name, device, args)

    def add(self, name: str, start: float, end: float, *, device: int = 0,
            **args) -> None:
        """Record a span whose ends were read elsewhere (``time.monotonic``
        seconds), such as one that starts on another thread.  It shows in
        the Chrome trace only."""
        self._append_span(name, start, end, device, args)

    def counter(self, name: str, *, device: int = 0, **values) -> None:
        """Record a ``C`` event: each of ``values`` is one series of the
        counter ``name`` on ``device``'s row, read now."""
        ev = {"name": name, "ph": "C", "ts": time.monotonic() * 1e6,
              "pid": device, "args": values}
        with self._lk:
            self._events.append(ev)

    def _append_span(self, name: str, start: float, end: float,
                     device: int, args: dict) -> None:
        ev = {"name": name, "ph": "X", "ts": start * 1e6,
              "dur": max(end - start, 0.0) * 1e6, "pid": device,
              "tid": threading.current_thread().name, "args": args}
        with self._lk:
            self._events.append(ev)

    def _open_ops(self) -> List[int]:
        stack = getattr(self._local, "ops", None)
        if stack is None:
            stack = self._local.ops = []
        return stack

    def events(self) -> List[dict]:
        with self._lk:
            return list(self._events)

    def dump(self, path: Optional[str] = None) -> str:
        """Write the Chrome-trace file; returns the path written."""
        if path is None:
            path = os.path.join(
                profile_dir(),
                f"flextrace-{os.getpid()}-{next(_TRACE_IDS)}.json")
        with self._lk:
            doc = {"traceEvents": self._events,
                   "displayTimeUnit": "ms",
                   "otherData": {"source": "repro.core.profiler.Timeline"}}
            with open(path, "w") as f:
                json.dump(doc, f)
        return path


class _Span:
    """One open :meth:`Timeline.span`."""

    __slots__ = ("_tl", "_name", "_device", "_args", "_pushed", "_ann",
                 "_t0")

    def __init__(self, tl: Timeline, name: str, device: int, args: dict):
        self._tl = tl
        self._name = name
        self._device = device
        self._args = args

    def __enter__(self) -> "_Span":
        ops = self._tl._open_ops()
        self._pushed = "op_id" in self._args
        if self._pushed:
            ops.append(self._args["op_id"])
        elif ops:
            self._args["op_id"] = ops[-1]
        self._ann = TraceAnnotation(self._name)
        self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.monotonic()
        self._ann.__exit__(*exc)
        if self._pushed:
            self._tl._open_ops().pop()
        self._tl._append_span(self._name, self._t0, t1, self._device,
                              self._args)


_NO_SPAN = contextlib.nullcontext()


def op_ids(op: OpDescriptor) -> Dict[str, object]:
    """What a host span of ``op`` names it by: its ``op_id``, and the
    request (``req_id``) or decode ``step`` it runs for."""
    ids: Dict[str, object] = {"op_id": op.op_id}
    for k in ("req_id", "step"):
        if k in op.meta:
            ids[k] = op.meta[k]
    return ids


def span(timeline: Optional[Timeline], name: str, *,
         op: Optional[OpDescriptor] = None, **args):
    """``timeline.span(name, **args)``, named also by ``op``'s
    :func:`op_ids` where given; or one shared do-nothing context when
    ``timeline`` is None (``FLEX_PROFILE`` unset): the call sites of the
    hot path then build no event and open no annotation."""
    if timeline is None:
        return _NO_SPAN
    if op is not None:
        args.update(op_ids(op))
    return timeline.span(name, **args)
