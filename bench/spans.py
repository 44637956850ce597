"""Arithmetic over the program's host spans in the engine's Timeline.

With ``FLEX_PROFILE=1`` the daemon (``flex.*``) and ``RealEngine``
(``engine.*``) record host spans beside the per-op events, on the same
``time.monotonic`` clock (``repro.core.profiler.Timeline``).  Each is a
Chrome-trace ``X`` event (``ts`` and ``dur`` in microseconds) whose
``args`` name the op (``op_id``) and the request (``req_id``) or decode
``step`` it ran for.  The functions here find spans by name, take self
times, and pair spans with the op events by ``op_id``.  On a program that
records no spans each of them finds nothing.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional


def start(ev: dict) -> float:
    return ev["ts"] * 1e-6


def end(ev: dict) -> float:
    return (ev["ts"] + ev["dur"]) * 1e-6


def seconds(ev: dict) -> float:
    return ev["dur"] * 1e-6


def named(events: Optional[Iterable[dict]], name: str) -> List[dict]:
    """The spans called ``name``."""
    return [e for e in events or () if e.get("ph") == "X"
            and e["name"] == name]


def by_op(events, name: str) -> Dict[int, dict]:
    """The span called ``name`` of each op, by ``op_id``."""
    return {e["args"]["op_id"]: e for e in named(events, name)
            if "op_id" in e.get("args", {})}


def self_time(span: dict, inner: Iterable[dict]) -> float:
    """Seconds of ``span`` outside the ``inner`` spans it holds."""
    return seconds(span) - sum(seconds(c) for c in inner)


def launches(events, pid: Optional[int] = None) -> List[dict]:
    """The op events of launches (``"<phase>:launch"``), of one device's
    row where ``pid`` is given, in dispatch order."""
    out = [e for e in events or () if e.get("ph") == "X"
           and e["name"].endswith(":launch")
           and (pid is None or e["pid"] == pid)]
    return sorted(out, key=lambda e: e["ts"])


def decode_host_gaps(events, w0: float, w1: float) -> List[float]:
    """Host time per decode step: over consecutive decode launches of the
    first decode device (replica 0), both dispatched in [w0, w1] and with
    no other launch between them, the time from one's ``flex.exec.wait``
    end to the next one's ``flex.exec.call`` start."""
    decode = [e for e in launches(events) if e["name"] == "decode:launch"]
    if not decode:
        return []
    ops = launches(events, min(e["pid"] for e in decode))
    wait = by_op(events, "flex.exec.wait")
    call = by_op(events, "flex.exec.call")
    gaps = []
    for a, b in zip(ops, ops[1:]):
        if a["name"] != "decode:launch" or b["name"] != "decode:launch":
            continue
        if not (w0 <= start(a) <= w1 and w0 <= start(b) <= w1):
            continue
        ia, ib = a["args"]["op_id"], b["args"]["op_id"]
        if ia in wait and ib in call:
            gaps.append(start(call[ib]) - end(wait[ia]))
    return gaps


def interposition(events, w0: float, w1: float) -> List[float]:
    """The daemon's own host time per launch completed in [w0, w1]:
    ``flex.enqueue`` + ``flex.select`` + ``flex.handoff`` + the self time
    of ``flex.complete`` (without the ``flex.callbacks`` it holds, which
    run the engine's work)."""
    parts = {n: by_op(events, n) for n in (
        "flex.enqueue", "flex.select", "flex.handoff", "flex.complete",
        "flex.callbacks")}
    out = []
    for op in launches(events):
        i = op["args"]["op_id"]
        if not w0 <= end(op) <= w1 or any(i not in p for p in
                                           parts.values()):
            continue
        out.append(seconds(parts["flex.enqueue"][i])
                   + seconds(parts["flex.select"][i])
                   + seconds(parts["flex.handoff"][i])
                   + self_time(parts["flex.complete"][i],
                               [parts["flex.callbacks"][i]]))
    return out


def prefill_queue(events, reqs) -> List[float]:
    """Per request of ``reqs``: from its prefill's ``flex.enqueue`` end to
    its ``flex.exec.call`` start, the phase arbitration's wait."""
    op_of = {e["args"]["req_id"]: e["args"]["op_id"]
             for e in launches(events) if e["name"] == "prefill:launch"
             and "req_id" in e["args"]}
    enq = by_op(events, "flex.enqueue")
    call = by_op(events, "flex.exec.call")
    out = []
    for r in reqs:
        i = op_of.get(r.req_id)
        if i in enq and i in call:
            out.append(start(call[i]) - end(enq[i]))
    return out
