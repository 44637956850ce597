"""FlexNPU client library (paper §3.2) and the passthrough baseline.

``FlexClient`` is the LD_PRELOAD-library analogue: the serving engine calls
the narrow RuntimeAPI verbs; the client packages each call into a compact
``OpDescriptor`` (virtual handles + metadata, never tensor payloads) and
forwards it to the per-device daemon over an in-process channel standing in
for the paper's shared-memory transport.  Async launches return a Future
immediately — the paper's 'asynchronous proxying' that lets the inference
worker overlap host work with NPU execution.

``PassthroughClient`` implements the same interface by executing directly —
the paper's 'native passthrough' baseline.  Engine code is byte-identical
under either client; that is the transparency property.

Both clients implement the **complete v2 verb vocabulary** (see api.py):
memory (malloc/free/memcpy), streams (create/destroy), events
(create/destroy/record/wait), launch, and per-stream synchronize.  Clients
are normally obtained from ``repro.core.connect(...)`` — constructing them
directly remains supported for single-device use.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional

from repro.core.api import (ENGINE_COMPUTE, ENGINE_COPY, Future, MemcpyKind,
                            OpDescriptor, OpType, Phase, RuntimeAPI,
                            infer_memcpy_kind, memcpy_model_time)
from repro.core.daemon import (FlexDaemon, RealBackend, _payload_copy,
                               _payload_nbytes)


class FlexClient(RuntimeAPI):
    def __init__(self, daemon: FlexDaemon, instance: str = ""):
        self.daemon = daemon
        self.instance = instance
        self._copy_stream: Optional[int] = None
        self._copy_stream_lock = threading.Lock()

    # -- memory -------------------------------------------------------------
    def malloc(self, nbytes: int, *, tag: str = "") -> int:
        op = OpDescriptor(OpType.MALLOC, meta={"nbytes": nbytes, "tag": tag,
                                               "instance": self.instance})
        return self.daemon.enqueue(op).result()

    def free(self, vhandle: int) -> None:
        op = OpDescriptor(OpType.FREE, vhandles=(vhandle,),
                          meta={"instance": self.instance})
        self.daemon.enqueue(op).result()

    def memcpy(self, dst, src, nbytes: Optional[int] = None, *,
               kind: Optional[MemcpyKind] = None, vstream: int = 0,
               meta: Optional[Dict] = None) -> Future:
        kind = MemcpyKind(kind) if kind is not None \
            else infer_memcpy_kind(dst, src)
        args = ()
        if kind == MemcpyKind.H2D:
            vhandles = (dst,)
            args = (src,)
            nbytes = nbytes if nbytes is not None else _payload_nbytes(src)
        elif kind == MemcpyKind.D2H:
            vhandles = (src,)
            nbytes = nbytes or 0
        else:
            vhandles = (dst, src) if dst is not None else ()
            nbytes = nbytes or 0
        m = dict(meta or {}, kind=kind, nbytes=nbytes, bytes=nbytes,
                 instance=self.instance,
                 est_duration=memcpy_model_time(kind, nbytes))
        op = OpDescriptor(OpType.MEMCPY, vstream=vstream, vhandles=vhandles,
                          meta=m, args=args)
        return self.daemon.enqueue(op)

    def memcpy_peer(self, dst_device, dst, src, nbytes: Optional[int] = None,
                    *, vstream: Optional[int] = None, link=None,
                    meta: Optional[Dict] = None) -> Future:
        """Cross-device copy on THIS device's copy engine.

        ``dst_device`` is the destination FlexDaemon (or a FlexClient, whose
        daemon is used).  With ``dst``/``src`` vhandles the payload moves
        from our buffer into the peer's; with both None the op is cost-only
        (the simulator's KV-transfer path).  Defaults to the copy-engine
        vstream so the transfer overlaps with compute launches."""
        dst_daemon = getattr(dst_device, "daemon", dst_device)
        if vstream is None:
            vstream = self.copy_engine_stream()
        vhandles = (src,) if isinstance(src, int) else ()
        if nbytes is None:
            nbytes = int(self.daemon.memory.resolve(src)["nbytes"]) \
                if isinstance(src, int) else 0
        m = dict(meta or {}, kind=MemcpyKind.P2P, nbytes=nbytes, bytes=nbytes,
                 link=link, dst_handle=dst if isinstance(dst, int) else None,
                 instance=self.instance,
                 est_duration=memcpy_model_time(MemcpyKind.P2P, nbytes))
        m["_dst_daemon"] = dst_daemon
        op = OpDescriptor(OpType.MEMCPY_PEER, vstream=vstream,
                          vhandles=vhandles, meta=m)
        return self.daemon.enqueue(op)

    # -- streams ------------------------------------------------------------
    def create_stream(self, *, phase: Phase = Phase.OTHER,
                      engine: str = ENGINE_COMPUTE,
                      queue: Optional[int] = None) -> int:
        op = OpDescriptor(OpType.CREATE_STREAM,
                          meta={"phase": phase, "engine": engine,
                                "queue": queue,
                                "instance": self.instance})
        return self.daemon.enqueue(op).result()

    def bind_stream_queue(self, vstream: int,
                          queue: Optional[int]) -> None:
        op = OpDescriptor(OpType.BIND_STREAM_QUEUE, vhandles=(vstream,),
                          meta={"queue": queue, "instance": self.instance})
        self.daemon.enqueue(op).result()

    def copy_engine_stream(self) -> int:
        """This client's dedicated copy-engine vstream (created lazily).

        Locked: callers routinely race here from Future completion
        callbacks on different engine-worker threads, and a check-then-set
        race would leak the loser's stream handle."""
        with self._copy_stream_lock:
            if self._copy_stream is None:
                self._copy_stream = self.create_stream(phase=Phase.OTHER,
                                                       engine=ENGINE_COPY)
            return self._copy_stream

    def destroy_stream(self, vstream: int) -> None:
        op = OpDescriptor(OpType.DESTROY_STREAM, vhandles=(vstream,),
                          meta={"instance": self.instance})
        self.daemon.enqueue(op).result()
        with self._copy_stream_lock:
            if vstream == self._copy_stream:
                self._copy_stream = None  # recreate lazily if needed again

    # -- events -------------------------------------------------------------
    def create_event(self) -> int:
        return self.daemon.enqueue(OpDescriptor(OpType.CREATE_EVENT)).result()

    def destroy_event(self, vevent: int) -> None:
        op = OpDescriptor(OpType.DESTROY_EVENT, vhandles=(vevent,))
        self.daemon.enqueue(op).result()

    def record_event(self, vevent: int, vstream: int) -> Future:
        op = OpDescriptor(OpType.RECORD_EVENT, vstream=vstream,
                          vhandles=(vevent,), meta={"est_duration": 0.0})
        return self.daemon.enqueue(op)

    def wait_event(self, vevent: int, vstream: int) -> Future:
        op = OpDescriptor(OpType.WAIT_EVENT, vstream=vstream,
                          vhandles=(vevent,), meta={"est_duration": 0.0})
        return self.daemon.enqueue(op)

    # -- execution ----------------------------------------------------------
    def launch(self, vstream: int, fn: Optional[Callable], *args,
               phase: Phase = Phase.OTHER, meta: Optional[Dict] = None,
               **kwargs) -> Future:
        op = OpDescriptor(OpType.LAUNCH, phase=phase, vstream=vstream,
                          meta=dict(meta or {}, instance=self.instance),
                          fn=fn, args=args, kwargs=kwargs)
        return self.daemon.enqueue(op)

    def synchronize(self, vstream: Optional[int] = None) -> None:
        if vstream is None:
            self.daemon.drain()
            return
        # Stream-ordered marker: completes only after everything previously
        # enqueued on this stream has, in either drive mode.
        op = OpDescriptor(OpType.SYNCHRONIZE, vstream=vstream,
                          meta={"est_duration": 0.0})
        self.daemon.enqueue(op).result()


class PassthroughClient(RuntimeAPI):
    """Native passthrough baseline: direct device submission with NO
    interception machinery — no descriptors, no handle translation, no
    phase queues, no policy.  A single FIFO submission thread stands in for
    the device stream (so async submission semantics match real AscendCL /
    TPU streams, isolating FlexNPU's *interposition* cost in Table 1).

    All verbs are supported; because there is one physical stream, every
    virtual stream maps onto it and event edges reduce to FIFO order."""

    def __init__(self, backend: Optional[RealBackend] = None):
        self.backend = backend or RealBackend()
        self._buffers: Dict[int, Dict[str, Any]] = {}
        self._mem_refs: Dict[int, int] = {}
        self._streams: Dict[int, Phase] = {}
        self._events: Dict[int, bool] = {}
        self._next_handle = 0
        self._lock = threading.Lock()
        # in-flight tracking: _unfinished counts ops submitted but not yet
        # completed by the worker (q.empty() alone races with the op that the
        # worker has dequeued but is still executing)
        self._unfinished = 0
        self._done_cv = threading.Condition(self._lock)
        import queue
        self._q: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="passthrough-stream")
        self._thread.start()

    def _worker(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            fn, args, kwargs, fut = item
            try:
                out, err = self.backend.run(fn, args, kwargs), None
            except BaseException as e:  # propagate into the future
                out, err = None, e
            # resolve the future BEFORE waking synchronize(): a caller that
            # synchronizes then inspects futures must see them done
            if err is None:
                fut.set_result(out)
            else:
                fut.set_error(err)
            with self._done_cv:
                self._unfinished -= 1
                self._done_cv.notify_all()

    def _submit(self, fn, args=(), kwargs=None) -> Future:
        f = Future()
        with self._done_cv:
            self._unfinished += 1
        self._q.put((fn, args, kwargs or {}, f))
        return f

    def close(self):
        """Stop the stream thread.  Joining it releases what its last op
        referenced (device buffers included) before close returns."""
        self._q.put(None)
        if threading.current_thread() is not self._thread:
            self._thread.join(timeout=5)

    def _handle(self) -> int:
        with self._lock:
            self._next_handle += 1
            return self._next_handle

    # -- memory -------------------------------------------------------------
    def malloc(self, nbytes: int, *, tag: str = "") -> int:
        h = self._handle()
        self._buffers[h] = {"nbytes": nbytes, "tag": tag, "data": None}
        return h

    def free(self, vhandle: int) -> None:
        # strict like the daemon path: engines must behave identically
        # under either client (transparency), including on a double free
        # or a free racing a queued memcpy
        with self._lock:
            if self._mem_refs.get(vhandle):
                raise RuntimeError(
                    f"free({vhandle}): buffer has pending memcpy work")
        if vhandle not in self._buffers:
            raise KeyError(f"memory: unknown virtual handle {vhandle}")
        del self._buffers[vhandle]

    def memcpy(self, dst, src, nbytes: Optional[int] = None, *,
               kind: Optional[MemcpyKind] = None, vstream: int = 0,
               meta: Optional[Dict] = None) -> Future:
        kind = MemcpyKind(kind) if kind is not None \
            else infer_memcpy_kind(dst, src)
        handles = [h for h in (dst, src) if isinstance(h, int)]
        with self._lock:
            for h in handles:
                self._mem_refs[h] = self._mem_refs.get(h, 0) + 1

        def copy():
            try:
                if kind == MemcpyKind.H2D:
                    rec = self._buffers[dst]
                    nb = nbytes if nbytes is not None else _payload_nbytes(src)
                    if nb > rec["nbytes"]:
                        raise MemoryError(
                            f"memcpy h2d: {nb} B into {rec['nbytes']} B "
                            f"buffer")
                    rec["data"] = _payload_copy(src)
                    return None
                if kind == MemcpyKind.D2H:
                    data = self._buffers[src]["data"]
                    return None if data is None else _payload_copy(data)
                if dst is not None:
                    rec = self._buffers[dst]
                    src_rec = self._buffers[src]
                    nb = nbytes if nbytes is not None else src_rec["nbytes"]
                    if nb > rec["nbytes"]:
                        raise MemoryError(
                            f"memcpy d2d: {nb} B into {rec['nbytes']} B "
                            f"buffer")
                    data = src_rec["data"]
                    rec["data"] = None if data is None else _payload_copy(data)
                return None
            finally:
                with self._lock:
                    for h in handles:
                        n = self._mem_refs.get(h, 0)
                        if n > 1:
                            self._mem_refs[h] = n - 1
                        else:
                            self._mem_refs.pop(h, None)

        return self._submit(copy)

    def memcpy_peer(self, dst_device, dst, src, nbytes: Optional[int] = None,
                    *, vstream: Optional[int] = None, link=None,
                    meta: Optional[Dict] = None) -> Future:
        """Direct host-side copy into a peer PassthroughClient's buffer —
        no copy engine, no link model (the native baseline)."""
        dst_client = dst_device

        def copy():
            if not isinstance(src, int) or not isinstance(dst, int):
                return None
            data = self._buffers[src]["data"]
            rec = dst_client._buffers[dst]
            nb = nbytes if nbytes is not None else self._buffers[src]["nbytes"]
            if nb > rec["nbytes"]:
                raise MemoryError(
                    f"memcpy_peer: {nb} B into {rec['nbytes']} B buffer")
            rec["data"] = None if data is None else _payload_copy(data)
            return None

        return self._submit(copy)

    # -- streams ------------------------------------------------------------
    def create_stream(self, *, phase: Phase = Phase.OTHER,
                      engine: str = ENGINE_COMPUTE,
                      queue: Optional[int] = None) -> int:
        h = self._handle()
        self._streams[h] = phase
        return h

    def bind_stream_queue(self, vstream: int,
                          queue: Optional[int]) -> None:
        pass  # one physical stream backs every vstream: binding is moot

    def destroy_stream(self, vstream: int) -> None:
        self._streams.pop(vstream, None)

    # -- events -------------------------------------------------------------
    def create_event(self) -> int:
        h = self._handle()
        self._events[h] = False
        return h

    def destroy_event(self, vevent: int) -> None:
        self._events.pop(vevent, None)

    def record_event(self, vevent: int, vstream: int) -> Future:
        return self._submit(lambda: self._events.__setitem__(vevent, True))

    def wait_event(self, vevent: int, vstream: int) -> Future:
        # Single physical stream: any record issued before this wait has
        # already executed by the time the worker reaches the marker, so the
        # wait never blocks (unrecorded events are a no-op, CUDA semantics).
        return self._submit(lambda: None)

    # -- execution ----------------------------------------------------------
    def launch(self, vstream: int, fn: Optional[Callable], *args,
               phase: Phase = Phase.OTHER, meta: Optional[Dict] = None,
               **kwargs) -> Future:
        return self._submit(fn if fn is not None else (lambda *a, **k: None),
                            args, kwargs)

    def synchronize(self, vstream: Optional[int] = None) -> None:
        # One physical stream backs every vstream, so per-stream sync and
        # device sync coincide: wait for ALL submitted ops to finish
        # (including the one the worker is currently executing).
        with self._done_cv:
            while self._unfinished > 0:
                self._done_cv.wait(0.1)
