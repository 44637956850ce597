"""FlexNPU per-device daemon (paper §3.1-§3.2).

Owns the virtual->physical handle tables, the **phase-aware dispatch queues**,
the per-stream ordering state, and the dispatch loop for one (logical) NPU
device.  The same daemon object is driven two ways, sharing every line of
queue/policy/ordering/bookkeeping code:

  * **threaded** (real backend): ``start()`` spawns the dispatch thread which
    executes ops on the in-process JAX backend, stamping wall-clock times;
  * **stepped** (simulation): the discrete-event simulator asks
    ``select_next(now)`` whenever the simulated device frees up and calls
    ``mark_complete(op, t)`` when the modeled duration elapses.

Dependency-aware readiness (v2): ``select_next`` only ever returns an op that
is *ready* — it is the oldest pending op of its virtual stream, no earlier op
of that stream is still in flight, and every event edge it waits on has been
satisfied.  The scheduler policy arbitrates **between phases of the ready
set**, so phase-aware time slicing and stream-ordered dispatch compose: the
policy decides *which stream head* runs next, never *whether* program order
within a stream is respected.

Execution queues (v4): every stream belongs to an execution-queue **class**
— ``compute`` (default) or ``copy`` (the DMA engine) — and each device
exposes a configurable number of queues per class (``repro.core.queues``;
default ``compute x 1, copy x 1``, the v3 engine-slot semantics).  The
daemon allows one op in flight *per queue*, so a copy-engine memcpy
overlaps with a compute launch, and on a multi-queue device two compute
ops (a prefill chunk and a decode step) overlap too: the threaded loop
dispatches each queue on its own worker thread, and ``select_next`` hands
the stepped simulator up to one ready op per free queue.  A stream may be
**pinned** to one queue of its class (``create_stream(queue=i)`` /
``bind_stream_queue``); unpinned streams dispatch on any free queue of
their class.  Events may also be **session-scoped** (negative handles from
a ``SharedEventTable``): a record completing on device A releases a wait
queued on device B, which is how cross-device KV transfers are ordered.

Op effects (``memcpy`` payload movement, event signalling, synchronize
markers) are applied inside ``mark_complete`` so threaded and stepped drive
modes share one implementation — the simulator models *when* an op finishes,
the daemon owns *what* it does.

This mirrors the paper's data-plane/policy-plane split: enqueue/dispatch is
the data plane; the policy object (scheduler) and profiler are the policy
plane and never block the critical path.
"""
from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional

import jax
import numpy as np

from repro.core.api import (CONTROL_OPS, ENGINE_COMPUTE, Future,
                            MemcpyKind, OpDescriptor, OpType, Phase,
                            memcpy_model_time)
from repro.core.handles import HandleTable, SharedEventTable
from repro.core.queues import (QueueId, parse_queue_spec, queue_key,
                               validate_queue_binding)
from repro.core.profiler import Profiler, op_ids, span
# import from the submodules, not the repro.sched package: the daemon loads
# while repro.sched's own __init__ may still be executing (sched.cluster ->
# repro.core.api -> this module), and submodule imports break that cycle
# flexlint: ignore[layering] -- the one upward edge the core keeps: the daemon
from repro.sched.context import PolicyContext
# flexlint: ignore[layering] -- consumes the policy plane (cycle-break above)
from repro.sched.dispatch import DispatchPolicy as SchedulerPolicy
# flexlint: ignore[layering] -- consumes the policy plane (cycle-break above)
from repro.sched.dispatch import FIFOPolicy


class RealBackend:
    """Executes launches in-process on one JAX device (``device``; None
    means JAX's default device).  With a ``timeline``, each launch records
    the host spans ``flex.exec.call`` (the call returning: host-side
    dispatch) and ``flex.exec.wait`` (``block_until_ready``) on the row of
    session device ``device_id``."""

    def __init__(self, device=None, timeline=None, device_id: int = 0):
        self.device = device
        self.timeline = timeline
        self.device_id = device_id

    def now(self) -> float:
        return time.monotonic()

    def run(self, fn: Callable, args=(), kwargs=None,
            op: Optional[OpDescriptor] = None) -> Any:
        """Call ``fn`` with this device as the default and wait for its
        result like a device stream sync, so exec_time is honest.  An
        asynchronous device error (an HBM OOM, say) is raised here.
        ``op``, where given, names the spans."""
        tl = self.timeline
        with jax.default_device(self.device):
            with span(tl, "flex.exec.call", op=op, device=self.device_id):
                out = fn(*args, **(kwargs or {}))
        with span(tl, "flex.exec.wait", op=op, device=self.device_id):
            return jax.block_until_ready(out)

    def execute(self, op: OpDescriptor) -> Any:
        if op.fn is None:
            return None
        return self.run(op.fn, op.args, op.kwargs, op)

    def estimate(self, op: OpDescriptor) -> float:
        return float(op.meta.get("est_duration", 1e-4))


def _payload_copy(src) -> Any:
    """Defensive copy of a host payload into/out of a backend buffer."""
    if isinstance(src, (bytes, bytearray, memoryview)):
        return bytes(src)
    return np.array(src, copy=True)


def _payload_nbytes(payload) -> int:
    if payload is None:
        return 0
    if isinstance(payload, (bytes, bytearray, memoryview)):
        return len(payload)
    return int(np.asarray(payload).nbytes)


class _ReadyView:
    """Policy-facing view of one phase queue.

    Truthiness/indexing/iteration expose only the READY ops (dispatchable
    now: stream heads with satisfied event edges, FIFO order), which is what
    a policy may pick from.  ``len()`` reports the FULL backlog including
    blocked ops, so depth-based pressure signals (DynamicPDPolicy's
    prefill/decode load) keep seeing real queue depth."""

    __slots__ = ("ready", "backlog")

    def __init__(self, ready: List[OpDescriptor], backlog: int):
        self.ready = ready
        self.backlog = backlog

    def __bool__(self) -> bool:
        return bool(self.ready)

    def __len__(self) -> int:
        return self.backlog

    def __getitem__(self, i):
        return self.ready[i]

    def __iter__(self):
        return iter(self.ready)


class FlexDaemon:
    def __init__(self, device_id: int, backend,
                 policy: Optional[SchedulerPolicy] = None,
                 profiler: Optional[Profiler] = None,
                 shared_events: Optional[SharedEventTable] = None,
                 queues=None, sanitizer=None, timeline=None):
        self.device_id = device_id
        self.backend = backend
        self.policy = policy or FIFOPolicy()
        self.profiler = profiler or Profiler()
        # opt-in per-op Chrome-trace recorder (FLEX_PROFILE=1; one per
        # session, see repro.core.profiler.Timeline) — None means off
        self.timeline = timeline
        # where host spans go: the timeline once the threaded drive over
        # the real backend starts (start()); the stepped drive runs on
        # virtual time, and its trace holds the ops alone
        self._spans = None
        self._policy_state: Optional[Dict[str, float]] = None
        self.queues: Dict[Phase, Deque[OpDescriptor]] = {  # guarded-by: _cv
            p: deque() for p in Phase}
        self.streams = HandleTable("stream")
        self.events = HandleTable("event")
        self.memory = HandleTable("memory")
        self.shared_events = shared_events    # session-scoped (may be None)
        # opt-in happens-before checker (repro.analysis.hazards; one per
        # session) — None means every hook below is skipped
        self.sanitizer = sanitizer
        self.allocated_bytes = 0              # guarded-by: _cv
        self.peak_bytes = 0                   # guarded-by: _cv
        self.allocated_by_instance: Dict[str, int] = {}  # guarded-by: _cv
        self.failed = False                   # guarded-by: _cv
        self.closed = False      # set by Session.close(): reject new work
        self.last_heartbeat = 0.0
        # optional LinkModel.stats provider — the cluster wires this in so
        # dispatch policies see link-queueing pressure (PolicyContext v3)
        self.link_stats_fn = None
        self._cv = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        self._stop = False                    # guarded-by: _cv
        # dispatched-not-yet-complete
        self._inflight: set = set()           # guarded-by: _cv
        # --- execution queues (v4): one op in flight per queue.  The
        # default spec (compute x 1, copy x 1) is the v3 engine-slot
        # behavior: copy-engine memcpys overlap compute launches; extra
        # compute queues let compute ops overlap each other too.
        self.queue_slots: Dict[str, int] = parse_queue_spec(queues)
        # immutable after init — lets the select fast path answer
        # "every queue busy?" in O(1) instead of rebuilding free lists
        self._total_slots = sum(self.queue_slots.values())
        self._queue_inflight: Dict[QueueId, OpDescriptor] = {}  # guarded-by: _cv
        self._queue_workers: Dict[QueueId, "queue.Queue"] = {}
        self._queue_threads: List[threading.Thread] = []
        # --- ordering state (v2) ---
        # per-vstream FIFO of enqueued-not-yet-dispatched ops
        self._stream_pending: Dict[int, Deque[OpDescriptor]] = {}  # guarded-by: _cv
        # per-vstream count of dispatched-not-yet-complete ops
        self._stream_inflight: Dict[int, int] = {}  # guarded-by: _cv
        # per-event [records_enqueued, records_completed]: a wait snapshots
        # records_enqueued at ITS enqueue and is satisfied once that many
        # records completed — records issued after the wait never block it
        # (CUDA/ACL semantics)
        self._event_state: Dict[int, list] = {}  # guarded-by: _cv
        # per-memory-handle count of queued/in-flight memcpys referencing it
        # (free refuses while nonzero so a stream-ordered copy can't lose
        # its buffer underneath it)
        self._mem_refs: Dict[int, int] = {}   # guarded-by: _cv

    # ------------------------------------------------------------ enqueue
    def enqueue(self, op: OpDescriptor) -> Future:
        with span(self._spans, "flex.enqueue", op=op,
                  device=self.device_id):
            return self._enqueue(op)

    def _enqueue(self, op: OpDescriptor) -> Future:
        # fast-path rejection; the authoritative check re-runs under _cv
        # below, after the (lock-free) size/ref preamble
        # flexlint: ignore[lock-discipline] -- advisory read; re-checked under _cv
        failed = self.failed
        if failed or self.closed:
            op.future.set_error(RuntimeError(
                f"device {self.device_id} "
                + ("failed" if failed else "closed")))
            return op.future
        op.enqueue_time = self.backend.now()
        # Control-plane ops that only mutate handle tables complete inline —
        # they never wait behind compute (cheap bookkeeping, paper §3.2).
        if op.op in CONTROL_OPS:
            self._control_op(op)
            return op.future
        if op.op in (OpType.RECORD_EVENT, OpType.WAIT_EVENT):
            ev = op.vhandles[0]
            if ev < 0:  # session-scoped (shared) event
                if self.shared_events is None or ev not in self.shared_events:
                    op.future.set_error(KeyError(
                        f"shared event: unknown handle {ev}"))
                    return op.future
            else:
                try:
                    self.events.resolve(ev)
                except KeyError as e:
                    op.future.set_error(e)
                    return op.future
        if op.op == OpType.MEMCPY_PEER:
            # take the DESTINATION daemon's memcpy ref before our own lock
            # (sequenced, never nested: two daemons peer-copying into each
            # other must not deadlock on each other's condition variables)
            dst_daemon = op.meta.get("_dst_daemon")
            dst_h = op.meta.get("dst_handle")
            if dst_daemon is not None and dst_h is not None:
                with dst_daemon._cv:
                    dst_daemon._mem_refs[dst_h] = \
                        dst_daemon._mem_refs.get(dst_h, 0) + 1
        if op.op == OpType.MEMCPY and not op.meta.get("nbytes"):
            # default the size from the source buffer so cost billing and
            # the capacity check see the real transfer size
            kind = MemcpyKind(op.meta.get("kind", MemcpyKind.D2D))
            src_h = None
            if kind == MemcpyKind.D2H and op.vhandles:
                src_h = op.vhandles[0]
            elif kind == MemcpyKind.D2D and len(op.vhandles) == 2:
                src_h = op.vhandles[1]
            if src_h is not None:
                try:
                    nb = int(self.memory.resolve(src_h)["nbytes"])
                except KeyError as e:
                    op.future.set_error(e)
                    return op.future
                op.meta.update(nbytes=nb, bytes=nb,
                               est_duration=memcpy_model_time(kind, nb))
        reject: Optional[str] = None
        with self._cv:
            if self.failed or self.closed:
                # fail()/close() landed since the unlocked head check and
                # already drained the queues — appending now would wedge
                # the op forever (nothing will ever dispatch it)
                reject = "failed" if self.failed else "closed"
            else:
                if op.op == OpType.RECORD_EVENT:
                    ev = op.vhandles[0]
                    if ev < 0:
                        with self.shared_events.lock:
                            self.shared_events.state[ev][0] += 1
                    else:
                        st = self._event_state.setdefault(ev, [0, 0])
                        st[0] += 1
                elif op.op == OpType.WAIT_EVENT:
                    ev = op.vhandles[0]
                    if ev < 0:
                        with self.shared_events.lock:
                            st = self.shared_events.state.get(ev)
                    else:
                        st = self._event_state.get(ev)
                    op.meta["wait_target"] = st[0] if st else 0
                elif op.op in (OpType.MEMCPY, OpType.MEMCPY_PEER):
                    for h in op.vhandles:
                        self._mem_refs[h] = self._mem_refs.get(h, 0) + 1
                if self.sanitizer is not None:
                    self.sanitizer.on_enqueue(self, op)
                self.queues[op.phase].append(op)
                self._stream_pending.setdefault(op.vstream,
                                                deque()).append(op)
                self._cv.notify()
        if reject is not None:
            if op.op == OpType.MEMCPY_PEER:
                self._drop_dst_ref(op)        # undo the peer ref taken above
            op.future.set_error(RuntimeError(
                f"device {self.device_id} {reject}"))
        return op.future

    def _control_op(self, op: OpDescriptor) -> None:
        now = self.backend.now()
        op.dispatch_time = op.complete_time = now
        try:
            op.future.set_result(self._apply_control(op))
        except BaseException as e:
            op.future.set_error(e)

    def _apply_control(self, op: OpDescriptor):
        instance = op.meta.get("instance", "")
        if op.op == OpType.MALLOC:
            nbytes = int(op.meta.get("nbytes", 0))
            h = self.memory.create({"nbytes": nbytes,
                                    "tag": op.meta.get("tag", ""),
                                    "instance": instance,
                                    "data": None})
            with self._cv:
                # control ops run inline on caller threads: two clients
                # allocating concurrently must not lose an accounting
                # update (read-modify-write on the ledger counters)
                self.allocated_bytes += nbytes
                self.peak_bytes = max(self.peak_bytes, self.allocated_bytes)
                self.allocated_by_instance[instance] = \
                    self.allocated_by_instance.get(instance, 0) + nbytes
            if self.sanitizer is not None:
                self.sanitizer.on_malloc(self, h)
            return h
        if op.op == OpType.FREE:
            h = op.vhandles[0]
            rec = self.memory.resolve(h)
            owner = rec.get("instance", "")
            # owned buffers are freeable only by their owner; untagged
            # buffers (owner "") are shared
            if owner and instance != owner:
                raise PermissionError(
                    f"instance {instance!r} cannot free buffer owned by "
                    f"{owner!r} (handle isolation)")
            with self._cv:
                # ref check + release + accounting are ONE atom: a memcpy
                # enqueue taking a ref between the check and the release
                # could otherwise lose its buffer underneath it
                if self._mem_refs.get(h):
                    raise RuntimeError(
                        f"free({h}): buffer has pending memcpy work")
                self.memory.release(h)
                self.allocated_bytes -= rec["nbytes"]
                self.allocated_by_instance[owner] = \
                    self.allocated_by_instance.get(owner, 0) - rec["nbytes"]
            if self.sanitizer is not None:
                self.sanitizer.on_free(self, h)
            return None
        if op.op == OpType.CREATE_STREAM:
            engine = op.meta.get("engine", ENGINE_COMPUTE)
            q = op.meta.get("queue")
            validate_queue_binding(self.queue_slots, engine, q)
            return self.streams.create(
                {"phase": op.meta.get("phase", Phase.OTHER),
                 "engine": engine,
                 "queue": None if q is None else int(q),
                 "instance": instance})
        if op.op == OpType.BIND_STREAM_QUEUE:
            vs = op.vhandles[0]
            rec = self.streams.resolve(vs)
            q = op.meta.get("queue")
            validate_queue_binding(self.queue_slots, rec.get(
                "engine", ENGINE_COMPUTE), q)
            with self._cv:
                rec["queue"] = None if q is None else int(q)
                self._cv.notify_all()   # a re-pin may unblock pending heads
            return None
        if op.op == OpType.DESTROY_STREAM:
            vs = op.vhandles[0]
            with self._cv:
                if self._stream_pending.get(vs) or \
                        self._stream_inflight.get(vs):
                    raise RuntimeError(
                        f"destroy_stream({vs}): stream has pending work")
                self._stream_pending.pop(vs, None)
                self._stream_inflight.pop(vs, None)
            self.streams.release(vs)
            return None
        if op.op == OpType.CREATE_EVENT:
            return self.events.create({})
        if op.op == OpType.DESTROY_EVENT:
            ev = op.vhandles[0]
            with self._cv:
                st = self._event_state.get(ev)
                if st and st[0] > st[1]:
                    raise RuntimeError(
                        f"destroy_event({ev}): event has a pending record")
                self._event_state.pop(ev, None)
            self.events.release(ev)
            return None
        raise ValueError(f"not a control op: {op.op}")

    # --------------------------------------------------- stepped interface
    def pending_count(self) -> int:  # holds: _cv
        return sum(len(q) for q in self.queues.values())

    def oldest_pending_time(self, phase: Optional[Phase] = None) \
            -> Optional[float]:
        """Enqueue time of the oldest pending op (optionally one phase's).
        Locked: cluster policies read this from other threads."""
        with self._cv:
            qs = [self.queues[phase]] if phase is not None \
                else list(self.queues.values())
            times = [q[0].enqueue_time for q in qs if q]
        return min(times) if times else None

    def backlog(self, phase: Phase) -> int:
        """Pending-op depth of one phase queue (cheap, thread-safe)."""
        # flexlint: ignore[lock-discipline] -- advisory probe; deque len is atomic
        return len(self.queues[phase])

    def stream_engine(self, vstream: int) -> str:
        """Engine class of a stream (unknown/default streams are compute)."""
        try:
            return self.streams.resolve(vstream).get("engine", ENGINE_COMPUTE)
        except KeyError:
            return ENGINE_COMPUTE

    def stream_queue(self, vstream: int) -> Optional[int]:
        """The queue index a stream is pinned to (None = any free queue
        of its engine class)."""
        try:
            return self.streams.resolve(vstream).get("queue")
        except KeyError:
            return None

    # ----------------------------------------------------- queue occupancy
    def _free_queues(self) -> Dict[str, List[int]]:  # holds: _cv
        """Free queue indices per class.  Caller holds ``_cv``."""
        return {cls: [i for i in range(n)
                      if (cls, i) not in self._queue_inflight]
                for cls, n in self.queue_slots.items()}

    def _engine_free(self) -> Dict[str, int]:  # holds: _cv
        """Free dispatch slots per class.  Caller holds ``_cv``."""
        busy: Dict[str, int] = {}
        for (cls, _i) in self._queue_inflight:
            busy[cls] = busy.get(cls, 0) + 1
        return {cls: n - busy.get(cls, 0)
                for cls, n in self.queue_slots.items()}

    def _queue_occupancy_locked(self) -> Dict[str, Optional[str]]:  # holds: _cv
        """Queue key -> phase of the op in flight there (None = idle).
        Caller holds ``_cv``."""
        return {queue_key(cls, i):
                (self._queue_inflight[(cls, i)].phase.value
                 if (cls, i) in self._queue_inflight else None)
                for cls, n in self.queue_slots.items()
                for i in range(n)}

    def queue_occupancy(self) -> Dict[str, Optional[str]]:
        """Locked snapshot of :meth:`_queue_occupancy_locked` (policy
        views and telemetry read this from other threads)."""
        with self._cv:
            return self._queue_occupancy_locked()

    def _remote_edge_pending(self) -> bool:  # holds: _cv
        """True if any stream head waits on a session-scoped event — its
        release may come from a PEER daemon, which never notifies our cv
        (the threaded dispatcher polls only in that case).  Caller holds
        ``_cv``."""
        for q in self._stream_pending.values():
            if q and q[0].op == OpType.WAIT_EVENT and q[0].vhandles[0] < 0:
                return True
        return False

    def _event_progress(self, vevent: int) -> Optional[list]:  # holds: _cv
        """[enqueued, completed] for a local or session-scoped event."""
        if vevent < 0:
            if self.shared_events is None:
                return None
            with self.shared_events.lock:
                st = self.shared_events.state.get(vevent)
                return list(st) if st is not None else None
        return self._event_state.get(vevent)

    def _ready_heads(self) -> List[OpDescriptor]:  # holds: _cv
        """Heads of all streams whose next op may legally dispatch now."""
        heads = []
        free = self._free_queues()
        for vs, q in self._stream_pending.items():
            if not q or self._stream_inflight.get(vs, 0):
                continue
            free_cls = free.get(self.stream_engine(vs), [0])
            pinned = self.stream_queue(vs)
            if (not free_cls) if pinned is None else (pinned not in free_cls):
                continue  # no free queue this stream may dispatch on
            op = q[0]
            if op.op == OpType.WAIT_EVENT:
                st = self._event_progress(op.vhandles[0])
                # a destroyed/unknown event satisfies the wait (st is None);
                # otherwise the snapshot target must have completed
                if st is not None and st[1] < op.meta.get("wait_target", 0):
                    continue  # happens-before edge not yet satisfied
            heads.append(op)
        heads.sort(key=lambda o: o.op_id)  # preserve per-phase arrival order
        return heads

    def select_next(self, now: float) -> Optional[OpDescriptor]:
        """Pop the next *ready* op per policy (simulator / loop driver).

        May be called repeatedly before any completion: it hands out at most
        one op per free execution queue, so a driver that loops until
        ``None`` gets a compute op AND a copy-engine op (and, on a
        multi-queue device, several compute ops) to run concurrently.

        The policy's ``select`` is consulted on EVERY call — including
        calls where nothing is dispatchable — so observing policies see
        the full context stream (the v4 contract)."""
        with self._cv:
            return self._select_locked(now, fast=False)

    def select_ready(self, now: float) -> List[OpDescriptor]:
        """Advance to the next decision point: pop EVERY op the device's
        free queues can legally take, in the same order a
        ``select_next``-until-``None`` loop would hand them out, under one
        lock round-trip (PR 9 batched stepped drive).

        Unlike ``select_next``, iterations where no op can dispatch skip
        the policy machinery entirely (``fast=True``): dispatch policies
        are pure on an empty ready set (``pick()`` returns None without
        touching state — see sched/dispatch.py), so the popped op
        sequence is identical and only no-op ``select`` observations are
        elided from the hot path."""
        out: List[OpDescriptor] = []
        with self._cv:
            while True:
                op = self._select_locked(now, fast=True)
                if op is None:
                    return out
                out.append(op)

    def _select_locked(self, now: float,  # holds: _cv
                       fast: bool = False) -> Optional[OpDescriptor]:
        if self.failed:
            return None
        # fast out before any policy machinery: every queue occupied —
        # nothing could dispatch regardless of what the policy says
        if fast and len(self._queue_inflight) >= self._total_slots:
            return None
        heads = self._ready_heads()
        if fast and not heads:
            return None
        ready: Dict[Phase, _ReadyView] = {
            p: _ReadyView([o for o in heads if o.phase is p],
                          len(self.queues[p]))
            for p in Phase}
        ctx = PolicyContext(
            queues=ready, prof=self.profiler, now=now,
            engine_free=self._engine_free(),
            engine_slots=dict(self.queue_slots),
            queue_occupancy=self._queue_occupancy_locked(),
            link_stats_fn=self.link_stats_fn)
        phase = self.policy.select(ctx)
        if phase is None or not ready[phase]:
            return None
        view = ready[phase]
        # v9: ordering-aware policies pick WHICH ready op of the phase
        # dispatches (predicted-SJF).  Any ready op is its own stream's
        # head, so the stream-pending popleft below stays valid.  The
        # single-op path skips the hook call — the dominant case.
        op = view[0] if len(view.ready) == 1 \
            else self.policy.choose(view.ready, ctx)
        self.queues[op.phase].remove(op)
        self._stream_pending[op.vstream].popleft()
        self._stream_inflight[op.vstream] = \
            self._stream_inflight.get(op.vstream, 0) + 1
        eng = self.stream_engine(op.vstream)
        pinned = self.stream_queue(op.vstream)
        idx = pinned if pinned is not None else \
            min(i for i in range(self.queue_slots.get(eng, 1))
                if (eng, i) not in self._queue_inflight)
        self._queue_inflight[(eng, idx)] = op
        # resolved once: survives stream destroy / re-binding
        op.meta["_engine"] = eng
        op.meta["_queue"] = (eng, idx)
        op.dispatch_time = now
        self.policy.on_dispatch(op, self.backend.estimate(op))
        self._inflight.add(op)
        return op

    def mark_complete(self, op: OpDescriptor, now: float,
                      result: Any = None, error: Optional[BaseException] = None):
        op.complete_time = now
        self.last_heartbeat = now
        if error is None:
            try:  # op effects are shared between threaded and stepped drive
                result = self._apply_effect(op, result)
            except BaseException as e:
                error = e
            else:
                if self.sanitizer is not None:
                    # effect applied = the op's buffer/event footprint is
                    # final: stamp clocks + check happens-before edges
                    self.sanitizer.on_complete(self, op)
        self.profiler.on_complete(op)
        if self.timeline is not None:
            self.timeline.record(self.device_id, op)
        # Free the STREAM before resolving the future: completion callbacks
        # routinely enqueue follow-up work on the same stream and must find
        # it dispatchable (continuous batching relies on this).  The drain
        # marker (_inflight) clears only AFTER the future resolves, so
        # drain()/synchronize(None) never returns with the last op's future
        # still unresolved.
        with self._cv:
            n = self._stream_inflight.get(op.vstream, 0)
            if n > 1:
                self._stream_inflight[op.vstream] = n - 1
            else:
                self._stream_inflight.pop(op.vstream, None)
            self._cv.notify_all()
        with span(self._spans, "flex.callbacks", op=op,
                  device=self.device_id):
            if error is not None:
                op.future.set_error(error)
            else:
                op.future.set_result(result)
        # The execution QUEUE frees only after the future's callbacks ran:
        # callbacks enqueue follow-up work (continuous batching), and the
        # threaded dispatcher must not race ahead of them and pick from a
        # queue that is about to receive the follow-up — policy decisions
        # would otherwise see stale per-phase state (the stepped drivers
        # call select_next after mark_complete returns, same property).
        with self._cv:
            qid = op.meta.get("_queue")
            if qid is not None and self._queue_inflight.get(qid) is op:
                del self._queue_inflight[qid]
            self._inflight.discard(op)
            self._cv.notify_all()

    # ----------------------------------------------------------- effects
    @staticmethod
    def _drop_dst_ref(op: OpDescriptor) -> None:
        """Release the DESTINATION daemon's memcpy ref of a peer copy
        (taken at enqueue; sequenced under the peer's cv, never nested)."""
        dst_daemon = op.meta.get("_dst_daemon")
        dst_h = op.meta.get("dst_handle")
        if dst_daemon is None or dst_h is None:
            return
        with dst_daemon._cv:
            n = dst_daemon._mem_refs.get(dst_h, 0)
            if n > 1:
                dst_daemon._mem_refs[dst_h] = n - 1
            else:
                dst_daemon._mem_refs.pop(dst_h, None)

    def _release_mem_refs(self, op: OpDescriptor) -> None:
        with self._cv:
            for h in op.vhandles:
                n = self._mem_refs.get(h, 0)
                if n > 1:
                    self._mem_refs[h] = n - 1
                else:
                    self._mem_refs.pop(h, None)
        self._drop_dst_ref(op)

    def _apply_effect(self, op: OpDescriptor, result: Any) -> Any:
        if op.op == OpType.RECORD_EVENT:
            ev = op.vhandles[0]
            if ev < 0:
                with self.shared_events.lock:
                    st = self.shared_events.state.get(ev)
                    if st:
                        st[1] += 1
            else:
                with self._cv:
                    st = self._event_state.get(ev)
                    if st:
                        st[1] += 1
            return None
        if op.op in (OpType.MEMCPY, OpType.MEMCPY_PEER):
            try:
                if op.op == OpType.MEMCPY_PEER:
                    return self._do_memcpy_peer(op)
                return self._do_memcpy(op)
            finally:
                self._release_mem_refs(op)
        return result  # LAUNCH result / WAIT_EVENT / SYNCHRONIZE markers

    def _do_memcpy(self, op: OpDescriptor) -> Any:
        """Move a payload through backend-owned buffers (H2D/D2H/D2D).

        Payload-less descriptors (no handles bound) model transfer cost only
        — the simulator's KV-transfer path uses these."""
        kind = MemcpyKind(op.meta.get("kind", MemcpyKind.D2D))
        if not op.vhandles:
            return None
        nbytes = int(op.meta.get("nbytes", 0))
        if kind == MemcpyKind.H2D:
            rec = self.memory.resolve(op.vhandles[0])
            payload = op.args[0] if op.args else None
            if nbytes > rec["nbytes"]:
                raise MemoryError(
                    f"memcpy h2d: {nbytes} B into {rec['nbytes']} B buffer")
            rec["data"] = _payload_copy(payload)
            return None
        if kind == MemcpyKind.D2H:
            rec = self.memory.resolve(op.vhandles[0])
            return None if rec["data"] is None else _payload_copy(rec["data"])
        # D2D: vhandles = (dst, src)
        dst = self.memory.resolve(op.vhandles[0])
        src = self.memory.resolve(op.vhandles[1])
        if nbytes > dst["nbytes"]:
            raise MemoryError(
                f"memcpy d2d: {nbytes} B into {dst['nbytes']} B buffer")
        dst["data"] = None if src["data"] is None \
            else _payload_copy(src["data"])
        return None

    def _do_memcpy_peer(self, op: OpDescriptor) -> Any:
        """Move a payload from this device's buffer into a PEER device's
        buffer (the cross-device KV-transfer data path).

        Payload-less descriptors (no handles bound) model transfer cost
        only — the cluster simulator's KV movement uses these."""
        dst_daemon = op.meta.get("_dst_daemon")
        if not op.vhandles or dst_daemon is None:
            return None
        src = self.memory.resolve(op.vhandles[0])
        dst = dst_daemon.memory.resolve(op.meta["dst_handle"])
        nbytes = int(op.meta.get("nbytes", 0))
        if nbytes > dst["nbytes"]:
            raise MemoryError(
                f"memcpy_peer: {nbytes} B into {dst['nbytes']} B buffer on "
                f"device {dst_daemon.device_id}")
        dst["data"] = None if src["data"] is None \
            else _payload_copy(src["data"])
        return None

    # ---------------------------------------------------------- fail/drain
    def abandon_inflight(self, op: OpDescriptor) -> None:
        """Settle the CROSS-DEVICE side effects of an op this (failed)
        device will never perform: credit shared-event records so waiters
        on peer devices don't wedge forever (device-loss semantics: waits
        are released), and drop the destination daemon's memcpy ref so the
        peer can free its buffer.  The op's own result stays void.

        Called for drained queue entries by ``fail()`` and by stepped
        drivers for the op that was already dispatched when the fault hit
        (the threaded loop instead runs ``mark_complete`` to completion)."""
        if op.op == OpType.RECORD_EVENT and op.vhandles and \
                op.vhandles[0] < 0 and self.shared_events is not None:
            with self.shared_events.lock:
                st = self.shared_events.state.get(op.vhandles[0])
                if st:
                    st[1] += 1
        elif op.op == OpType.MEMCPY_PEER:
            self._drop_dst_ref(op)

    def fail(self, requeue_sink: Optional[Callable] = None):
        """Simulated device failure: error every queued op (the engine's
        fault-tolerance layer re-queues them elsewhere)."""
        with self._cv:
            # the flag flips under the SAME lock that drains: an enqueue
            # racing this method either sees failed (and rejects) or
            # appends before the drain below sweeps it up — never both
            self.failed = True
            drained = []
            for q in self.queues.values():
                drained.extend(q)
                q.clear()
            self._stream_pending.clear()
            self._stream_inflight.clear()
            self._queue_inflight.clear()
            self._event_state.clear()
            self._mem_refs.clear()
            self._cv.notify_all()
        for op in drained:
            self.abandon_inflight(op)
            if requeue_sink is not None:
                requeue_sink(op)
            else:
                op.future.set_error(RuntimeError(
                    f"device {self.device_id} failed"))

    # -------------------------------------------------------- thread drive
    def start(self):
        with self._cv:
            self._stop = False
        if isinstance(self.backend, RealBackend):
            self._spans = self.timeline
        # one executor thread per execution queue: ops on different queues
        # (compute vs copy, or two compute queues) execute concurrently;
        # ops sharing a queue serialize
        qids = [(cls, i) for cls, n in self.queue_slots.items()
                for i in range(n)]
        self._queue_workers = {qid: queue.Queue() for qid in qids}
        self._queue_threads = [
            threading.Thread(target=self._queue_loop, args=(qid,),
                             daemon=True,
                             name=f"flexd-{self.device_id}-{qid[0]}{qid[1]}")
            for qid in qids]
        for t in self._queue_threads:
            t.start()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"flexd-{self.device_id}")
        self._thread.start()

    def stop(self):
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5)
        for q in self._queue_workers.values():
            q.put(None)                       # workers drain, then exit
        for t in self._queue_threads:
            t.join(timeout=5)
        self._queue_threads = []

    def _loop(self):
        """Dispatcher: pops ready ops and routes each to its queue worker."""
        while True:
            with self._cv:
                while not self._stop and self.pending_count() == 0:
                    self._cv.wait(0.05)
                if self._stop and self.pending_count() == 0:
                    return
            now = self.backend.now()
            op = self.select_next(now)
            if op is None:
                # Pending work exists but every stream head is blocked on an
                # event edge or a busy engine.  Local unblocks (enqueue,
                # completion) notify the cv, so wait long; a head waiting on
                # a SHARED event may be released by a record completing on a
                # PEER daemon — no local notify — so poll fast only then.
                # On stop, abandon the blocked work instead of spinning.
                with self._cv:
                    if self._stop:
                        return
                    self._cv.wait(
                        0.001 if self._remote_edge_pending() else 0.1)
                continue
            if self._spans is not None:
                self._trace_select(op, now)
            self._queue_workers[op.meta["_queue"]].put(op)

    def _trace_select(self, op: OpDescriptor, start: float) -> None:
        """Spans of the dispatcher: ``flex.select`` (``select_next``,
        policy included), the start of ``flex.handoff`` (closed by the
        queue worker), and a ``flex.policy`` counter whenever the
        policy's state changed."""
        tl = self._spans
        tl.add("flex.select", start, time.monotonic(),
               device=self.device_id, **op_ids(op))
        state = self.policy.debug_state()
        if state and state != self._policy_state:
            self._policy_state = state
            tl.counter("flex.policy", device=self.device_id, **state,
                       decode_bw=self.profiler.decode_bandwidth_util(),
                       prefill_backlog=self.backlog(Phase.PREFILL),
                       decode_backlog=self.backlog(Phase.DECODE))
        op.meta["_handoff"] = time.monotonic()

    def _queue_loop(self, qid: QueueId):
        q = self._queue_workers[qid]
        while True:
            op = q.get()
            if op is None:
                return
            if self._spans is not None:
                self._spans.add("flex.handoff", op.meta.pop("_handoff"),
                                time.monotonic(), device=self.device_id,
                                **op_ids(op))
            if op.op == OpType.LAUNCH:
                try:
                    result = self.backend.execute(op)
                except BaseException as e:  # propagate into the future
                    self._complete(op, self.backend.now(), error=e)
                    continue
                self._complete(op, self.backend.now(), result)
            else:
                # non-launch data-plane ops (memcpy, event markers): the
                # effect itself is applied inside mark_complete.  A backend
                # may pace the op first (the real-time sim drive blocks the
                # engine thread for the modeled duration; the real backend
                # has no pace — payload movement is the actual work)
                pace = getattr(self.backend, "pace", None)
                if pace is not None:
                    pace(op)
                self._complete(op, self.backend.now())

    def _complete(self, op: OpDescriptor, now: float, result: Any = None,
                  error: Optional[BaseException] = None) -> None:
        """``mark_complete`` on the threaded drive, inside the span
        ``flex.complete`` (up to the queue's release)."""
        with span(self._spans, "flex.complete", op=op,
                  device=self.device_id):
            self.mark_complete(op, now, result, error)

    def drain(self, timeout: float = 30.0):
        """Block until all queued work is done (thread mode)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            # read queue depth and in-flight state under the lock so the
            # dispatch thread can't be observed mid-handoff (op popped from
            # its queue but not yet marked in flight)
            with self._cv:
                if self.pending_count() == 0 and not self._inflight:
                    return
            time.sleep(0.001)
        raise TimeoutError("daemon did not drain")
