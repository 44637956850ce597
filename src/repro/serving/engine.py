"""Real-execution serving engine (JAX on the TPU, or on the CPU in tests).

Continuous batching over slot-structured dense KV caches.  ALL device work is
issued through the session-based v2 ``RuntimeAPI`` verbs: the engine opens a
``repro.core.connect(...)`` session and speaks only to its device-scoped
clients — it is byte-identical under ``mode="passthrough"`` (paper's native
passthrough) and the interposed FlexDaemon modes, which is the transparency
claim of the paper made concrete.

Modes:
  * ``passthrough``     — direct execution (Table 1 baseline).
  * ``static_colocate`` — one FIFO queue, prefill admission gated on a free
                          decode slot (head-of-line blocking; Table 4 baseline).
  * ``dynamic_pd``      — FlexNPU: prefill and decode as separate logical
                          instances over one daemon with DynamicPDPolicy.
  * ``disagg``          — static PD disaggregation over a 2-device pair:
                          prefill on one device, decode on the other, and
                          the KV cache moved between them by ``memcpy_peer``
                          on the copy-engine stream, ordered by a
                          cross-device (shared) event — the real-execution
                          analogue of the cluster simulator's disagg
                          deployments.

Data parallelism (v4): the engine is **multi-device** — ``replicas=R``
opens ONE session spanning R replicas (R devices, or R prefill/decode
device pairs under disagg), each with its own slot cache and decode batch.
Requests are routed to replicas by a :class:`~repro.sched.ClusterPolicy`
from the v3 registry (``cluster_policy="least_loaded"`` by default), so
the same routing layer fronts the real engine and the cluster simulator.
``replicas=1`` (the default) is the v3 single-device engine, byte-for-byte.
Each replica's weights, slot cache and step inputs live on the JAX device
its session device executes on (``Session.jax_device``); under disagg the
unpacked KV lands on the decode device.

Execution queues (v4): each device exposes ``compute_queues`` compute
queues (plus a copy queue).  With more than one, decode is PINNED to the
highest-index compute queue and prefill launches round-robin over streams
bound to the remaining queues — prefills of different requests overlap
each other and never block decode.  Real-model prompt chunking is not
micro-batched here (the dense prefill writes its KV from position 0, so a
prompt is one launch — per-request outputs stay byte-identical); the
cluster simulator's ``chunk_prefill_tokens`` models intra-request
micro-batching.

Prefill and decode each run on their own virtual stream; the daemon
enforces per-stream FIFO order while the phase policy arbitrates between
the stream heads (stream-ordered dispatch, daemon v2).
"""
from __future__ import annotations

import functools
import logging
import math
import threading
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.core.api import Phase
from repro.core.session import connect
# The engine consumes the sched policy plane by design; the layering rank
# exists to ban the reverse direction (sched importing serving).
# flexlint: ignore[layering] -- serving -> sched policy-plane use is the API
from repro.sched import (AdmissionPolicy, AdmissionView, ClusterPolicy,
                         DynamicPDConfig, DynamicPDPolicy, FIFOPolicy,
                         GatedAdmission, RouteContext, UngatedAdmission,
                         make_policy, policy_kind)
from repro.models.model import Model
from repro.serving.request import Request, RequestState, summarize

_log = logging.getLogger(__name__)


# The jitted steps are shared by every engine of the process (the model is
# a static argument): engines and replicas of one model compile each shape
# once per device.
@functools.partial(jax.jit, static_argnums=0)
def _prefill_step(model: Model, params, toks, cache):
    return model.prefill(params, {"tokens": toks}, cache)


@functools.partial(jax.jit, static_argnums=0)
def _decode_step(model: Model, params, toks, cache, lens):
    return model.decode(params, toks, cache, lens)


def _empty_cache(model: Model, batch: int, max_len: int, device):
    """A zeroed KV cache created directly on ``device``."""
    with jax.default_device(device):
        return model.init_cache(batch, max_len)


def _pack_cache(cache):
    """Flatten a KV-cache pytree into one contiguous byte blob (+ recipe)."""
    leaves, treedef = jax.tree.flatten(cache)
    arrs = [np.asarray(x) for x in leaves]
    spec = [(a.shape, a.dtype) for a in arrs]
    blob = np.concatenate(
        [np.frombuffer(a.tobytes(), np.uint8) for a in arrs]) \
        if arrs else np.zeros(0, np.uint8)
    return blob, treedef, spec


def _unpack_cache(blob, treedef, spec, device):
    """Rebuild a packed KV-cache pytree on ``device``."""
    buf = bytes(blob) if not isinstance(blob, (bytes, bytearray)) else blob
    leaves, off = [], 0
    for shape, dtype in spec:
        n = int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
        leaves.append(jax.device_put(
            np.frombuffer(buf[off:off + n], dtype=dtype).reshape(shape),
            device))
        off += n
    return jax.tree.unflatten(treedef, leaves)


def _insert_slot(full_cache, one_cache, slot):
    """Insert a [*, 1, ...] single-sequence cache into batch axis 1."""
    def one(full, single):
        return jax.lax.dynamic_update_index_in_dim(
            full, single[:, 0] if single.ndim == full.ndim else single,
            slot, 1)
    return jax.tree.map(one, full_cache, one_cache)


class _Replica:
    """One data-parallel replica: a session device (or a prefill/decode
    device PAIR under disagg) with its own streams, slot cache, and decode
    batch.  Duck-types the routing view a :class:`ClusterPolicy` expects
    (``failed`` / ``ewma_step`` / ``load()``), so cluster policies route
    real-engine replicas exactly like simulator instances."""

    def __init__(self, engine: "RealEngine", index: int, p_dev: int,
                 d_dev: int):
        self.engine = engine
        self.index = index
        self.name = f"replica{index}"
        sess = engine.session
        self.client = sess.device(p_dev)      # prefill-side client
        self.daemon = sess.daemon(p_dev)
        self.client_d = sess.device(d_dev)    # decode-side (disagg: peer)
        self.daemon_d = sess.daemon(d_dev)
        client, client_d = self.client, self.client_d
        # the JAX devices each side executes on, and the weights there
        self.chip_p = sess.jax_device(p_dev)
        self.chip_d = sess.jax_device(d_dev)
        self.params_p = jax.device_put(engine.params, self.chip_p)
        self.params_d = self.params_p if self.chip_d == self.chip_p \
            else jax.device_put(engine.params, self.chip_d)
        cq = engine.compute_queues
        if cq > 1:
            # decode owns the last compute queue outright; prefill streams
            # spread over the rest, requests round-robining across them
            self.streams_p = [client.create_stream(phase=Phase.PREFILL,
                                                   queue=i)
                              for i in range(cq - 1)]
            self.stream_d = client_d.create_stream(phase=Phase.DECODE,
                                                   queue=cq - 1)
        else:
            self.streams_p = [client.create_stream(phase=Phase.PREFILL)]
            self.stream_d = client_d.create_stream(phase=Phase.DECODE)
        self.stream_p = self.streams_p[0]
        self._rr = 0
        # device state
        self.slot_cache = _empty_cache(engine.model, engine.max_num_seqs,
                                       engine.max_len, self.chip_d)
        self.lengths = np.zeros((engine.max_num_seqs,), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * engine.max_num_seqs
        self.next_tokens = np.zeros((engine.max_num_seqs,), np.int32)
        self.decode_pending: List[tuple] = []   # (req, single_cache, tok)
        self.prefilling_count = 0               # admitted, prefill running
        self.active_count = 0
        self.decode_inflight = False
        # routing view (ClusterPolicy duck-typing)
        self.failed = False
        self.ewma_step = 0.0

    def load(self) -> float:
        """Router load signal: work resident on this replica."""
        return float(self.prefilling_count + len(self.decode_pending)
                     + self.active_count)

    def observe_step(self, dur: float) -> None:
        self.ewma_step = 0.8 * self.ewma_step + 0.2 * dur \
            if self.ewma_step else dur

    def next_prefill_stream(self) -> int:
        s = self.streams_p[self._rr % len(self.streams_p)]
        self._rr += 1
        return s


class RealEngine:
    def __init__(self, model: Model, params, *, mode: str = "dynamic_pd",
                 max_num_seqs: int = 4, max_len: int = 256,
                 policy=None, admission: Optional[AdmissionPolicy] = None,
                 sample: str = "greedy", kv_chunk_layers: int = 0,
                 replicas: int = 1, cluster_policy=None,
                 compute_queues: int = 1):
        self.model = model
        self.params = params
        self.mode = mode
        self.max_num_seqs = max_num_seqs
        self.max_len = max_len
        self.sample = sample
        self.compute_queues = max(1, int(compute_queues))
        # disagg KV transport: split the packed cache into this many
        # layer-group chunks pipelined over memcpy_peer (0 = one blob).
        # Chunks ride the same copy-engine stream, so they serialize on
        # the DMA engine while the destination's readback starts as soon
        # as the cross-device event edge for the LAST chunk resolves —
        # outputs stay byte-identical to the one-blob path.
        self.kv_chunk_layers = int(kv_chunk_layers)
        if replicas < 1:
            raise ValueError("the engine needs at least one replica")
        self.n_replicas = int(replicas)
        self._lock = threading.RLock()
        self._all_done = threading.Condition(self._lock)  # lock-alias: _lock
        # control plane (v3): dispatch policies resolve through the registry
        # by name; admission is a shared AdmissionPolicy (the same object
        # type the cluster simulator uses — no copy-pasted gating)
        if isinstance(policy, str):
            if policy_kind(policy) != "dispatch":
                raise ValueError(
                    f"policy {policy!r} is a {policy_kind(policy)} policy; "
                    f"RealEngine's policy= takes a dispatch policy "
                    f"(fifo, static_slice, dynamic_pd, ...)")
            policy = make_policy(policy)
        self.admission = admission or (
            GatedAdmission() if mode == "static_colocate"
            else UngatedAdmission())
        # replica routing (v4): the same ClusterPolicy layer the simulator
        # uses, resolved through the registry by name
        if cluster_policy is None or isinstance(cluster_policy, str):
            name = cluster_policy or "least_loaded"
            if policy_kind(name) != "cluster":
                raise ValueError(
                    f"policy {name!r} is a {policy_kind(name)} policy; "
                    f"RealEngine's cluster_policy= takes a cluster policy "
                    f"(least_loaded, least_contended, ...)")
            self.router: ClusterPolicy = make_policy(name)
        else:
            self.router = cluster_policy
        self.router.bind(self)

        queues = {"compute": self.compute_queues, "copy": 1}
        if mode == "passthrough":
            self.session = connect(mode="passthrough",
                                   devices=self.n_replicas)
        elif mode == "disagg":
            # each replica is a device PAIR: device 2i prefills, 2i+1
            # decodes; each side is single-phase so FIFO order suffices
            # (the simulator's disagg instances too)
            self.session = connect(mode="flex", devices=2 * self.n_replicas,
                                   policy=policy or FIFOPolicy(),
                                   instance="engine", queues=queues)
        else:
            policy = policy or (FIFOPolicy() if mode == "static_colocate"
                                else DynamicPDPolicy(
                                    DynamicPDConfig(ttft_guard_s=0.05,
                                                    adjust_interval_s=0.01)))
            self.session = connect(mode="flex", devices=self.n_replicas,
                                   policy=policy, instance="engine",
                                   queues=queues)
        self.replicas: List[_Replica] = []
        for r in range(self.n_replicas):
            if mode == "disagg":
                p_dev, d_dev = 2 * r, 2 * r + 1
            else:
                p_dev = d_dev = r
            self.replicas.append(_Replica(self, r, p_dev, d_dev))
        # single-replica conveniences (the v3 attribute names)
        self.client = self.replicas[0].client
        self.daemon = self.replicas[0].daemon
        self.client_d = self.replicas[0].client_d
        self.stream_p = self.replicas[0].stream_p
        self.stream_d = self.replicas[0].stream_d

        # engine-level queues
        self.waiting_admission: List[Request] = []  # guarded-by: _lock
        self.outstanding = 0                        # guarded-by: _lock
        self.finished: List[Request] = []           # guarded-by: _lock
        # honest rejection telemetry (v5): requests the admission policy
        # shed — they end REJECTED and count toward run() accounting
        self.rejected: List[Request] = []           # guarded-by: _lock
        # terminal-transition hook (v5): called with each request as it
        # ends (done/failed/rejected) — closed-loop traffic generators
        # plug in here, same contract as the cluster simulator's
        self.on_request_done = None

    # ------------------------------------------------------------- public
    def submit(self, req: Request) -> None:
        with self._lock:
            self.outstanding += 1
            req.arrival_time = req.arrival_time or time.monotonic()
            self.waiting_admission.append(req)
            self._drain_admission_locked()

    def run(self, requests: List[Request], timeout: float = 300.0) -> Dict:
        """Submit per arrival offsets (relative seconds) and wait."""
        t0 = time.monotonic()
        for r in sorted(requests, key=lambda r: r.arrival_time):
            delay = t0 + r.arrival_time - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            r.arrival_time = time.monotonic()
            self.submit(r)
        with self._all_done:
            deadline = time.monotonic() + timeout
            while self.outstanding > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"{self.outstanding} requests unfinished")
                self._all_done.wait(min(remaining, 0.1))
        return summarize(requests)

    def shutdown(self):
        try:  # release the engine's stream handles (leak-free tables)
            for rep in self.replicas:
                rep.client.synchronize(None)
                if rep.client_d is not rep.client:
                    rep.client_d.synchronize(None)
                rep.client_d.destroy_stream(rep.stream_d)
                for s in rep.streams_p:
                    rep.client.destroy_stream(s)
                for c in (rep.client, rep.client_d):
                    if getattr(c, "_copy_stream", None) is not None:
                        c.destroy_stream(c._copy_stream)
        except Exception:
            pass  # dirty shutdown (timeout/fault): session teardown suffices
        self.session.close()

    # ------------------------------------------------------------ prefill
    def _admission_view(self, rep, idx: int = 0) -> AdmissionView:  # holds: _lock
        cand = self.waiting_admission[idx] \
            if idx < len(self.waiting_admission) else None
        return AdmissionView(
            waiting=len(self.waiting_admission),
            next_prompt_len=cand.prompt_len if cand else 0,
            active=rep.active_count,
            decode_pending=len(rep.decode_pending),
            prefilling=rep.prefilling_count,
            max_num_seqs=self.max_num_seqs,
            kv_free=None,      # dense slot caches: no token accounting
            next_tenant=cand.tenant if cand else "",
            next_priority=cand.priority if cand else 0)

    def _drain_admission_locked(self):  # holds: _lock
        # load shedding first (v5): doomed requests end REJECTED with
        # honest telemetry — the same policy hooks the simulator drives
        for r in self.admission.shed(self.waiting_admission,
                                     time.monotonic()):
            if r in self.waiting_admission:
                self.waiting_admission.remove(r)
                self._reject_locked(r)
        while self.waiting_admission:
            # pick the candidate (FIFO for v3/v4 policies, priority +
            # weighted-fair for slo_aware), route it, then gate against
            # the TARGET replica's occupancy — one admission
            # implementation for any replica count
            i = self.admission.pick_next(self.waiting_admission)
            # v6+ routing signature, called directly (the v5 two-argument
            # adapter was removed in v9; the real engine has no prefix
            # caches yet, so the context only carries clock and loads)
            rep = self.router.route_prefill(
                self.waiting_admission[i], self.replicas,
                RouteContext(now=time.monotonic(),
                             loads={r.name: r.load()
                                    for r in self.replicas}))
            if rep is None or not self.admission.admit(
                    self._admission_view(rep, i)):
                return
            req = self.waiting_admission.pop(i)
            self.admission.on_admit(req)
            rep.prefilling_count += 1
            self._launch_prefill(rep, req)

    def _reject_locked(self, req: Request) -> None:  # holds: _lock
        req.state = RequestState.REJECTED
        req.finish_time = time.monotonic()
        self.rejected.append(req)
        self.outstanding -= 1
        if self.on_request_done is not None:
            self.on_request_done(req)
        self._all_done.notify_all()

    def _launch_prefill(self, rep: _Replica, req: Request) -> None:  # holds: _lock
        req.state = RequestState.PREFILLING
        req.instance = rep.name
        toks = jax.device_put(
            np.asarray(req.prompt_tokens, np.int32)[None, :], rep.chip_p)
        cache = _empty_cache(self.model, 1, self.max_len, rep.chip_p)
        t0 = time.monotonic()
        fut = rep.client.launch(
            rep.next_prefill_stream(), _prefill_step, self.model,
            rep.params_p, toks, cache, phase=Phase.PREFILL,
            meta={"tokens": req.prompt_len, "req_id": req.req_id})
        fut.add_done_callback(
            lambda f, r=req, rp=rep, t=t0: self._prefill_done(rp, r, f, t))

    def _prefill_done(self, rep: _Replica, req: Request, fut,
                      t0: float) -> None:
        try:
            logits, single_cache, lens = fut.result()
        except Exception:
            _log.exception("prefill of request %d failed", req.req_id)
            with self._lock:
                rep.prefilling_count = max(0, rep.prefilling_count - 1)
                self._fail_locked(req)
            return
        tok = int(np.argmax(np.asarray(logits[0])))
        now = time.monotonic()
        with self._lock:
            rep.prefilling_count = max(0, rep.prefilling_count - 1)
            rep.observe_step(now - t0)
            req.record_token(now)
            req.output_tokens.append(tok)
            if req.done_decoding:
                self._finish_locked(req)
                return
        if self.mode == "disagg":
            self._transfer_kv(rep, req, single_cache, tok)
            return
        with self._lock:
            rep.decode_pending.append((req, single_cache, tok))
            self._fill_slots_locked(rep)
            self._ensure_decode_locked(rep)

    # --------------------------------------------- disagg: KV cache transfer
    def _kv_chunk_bounds(self, blob_nbytes: int, spec) -> List[tuple]:
        """(offset, nbytes) per chunk: the packed blob split on LAYER
        boundaries (pack order is the cache pytree's leaf order) into up
        to ``kv_chunk_layers`` near-even groups — never mid-array."""
        if self.kv_chunk_layers <= 1 or len(spec) <= 1:
            return [(0, blob_nbytes)]
        sizes = [int(np.prod(shape, dtype=np.int64))
                 * np.dtype(dtype).itemsize for shape, dtype in spec]
        n = min(self.kv_chunk_layers, len(sizes))
        per = max(1, math.ceil(len(sizes) / n))
        bounds, off = [], 0
        for i in range(0, len(sizes), per):
            nb = sum(sizes[i:i + per])
            bounds.append((off, nb))
            off += nb
        return bounds

    def _transfer_kv(self, rep: _Replica, req: Request, single_cache,
                     tok: int) -> None:
        """Move the prefilled KV cache from the replica's prefill device to
        its decode device through backend-owned buffers: H2D on the
        source, ``memcpy_peer`` on the copy-engine stream — chunked on
        layer boundaries when ``kv_chunk_layers`` > 1, so the chunks
        pipeline on the copy engine — then ONE cross-device (shared) event
        after the last chunk orders the decode side's D2H readbacks after
        every peer copy (the daemons' happens-before graph spans both
        devices)."""
        blob, treedef, spec = _pack_cache(single_cache)
        cp, cd = rep.client, rep.client_d
        sp, sd = cp.copy_engine_stream(), cd.copy_engine_stream()
        ev = self.session.create_shared_event()
        bounds = self._kv_chunk_bounds(blob.nbytes, spec)
        handles = []
        for i, (off, nb) in enumerate(bounds):
            h_src = cp.malloc(nb, tag="kv-transfer")
            h_dst = cd.malloc(nb, tag="kv-transfer")
            handles.append((h_src, h_dst))
            cp.memcpy(h_src, blob[off:off + nb], vstream=sp)
            cp.memcpy_peer(rep.daemon_d, h_dst, h_src, nb,
                           vstream=sp,
                           meta={"req_id": req.req_id, "kv_chunk": i,
                                 "kv_chunks": len(bounds)})
        cp.record_event(ev, sp)
        cd.wait_event(ev, sd)               # released by the source's record
        # same-stream FIFO: the LAST readback completes last, with every
        # earlier chunk's future already resolved
        futs = [cd.memcpy(None, h_dst, nb, vstream=sd)
                for (_, h_dst), (_, nb) in zip(handles, bounds)]
        futs[-1].add_done_callback(
            lambda f: self._kv_arrived(rep, req, tok, treedef, spec,
                                       handles, ev, futs))

    def _kv_arrived(self, rep: _Replica, req: Request, tok: int, treedef,
                    spec, handles, ev: int, futs) -> None:
        try:
            parts = [np.asarray(f.result(), dtype=np.uint8) for f in futs]
            blob = parts[0] if len(parts) == 1 else np.concatenate(parts)
            cache = _unpack_cache(blob, treedef, spec, rep.chip_d)
        except Exception:
            _log.exception("KV transfer of request %d failed", req.req_id)
            with self._lock:
                self._fail_locked(req)
            return
        finally:
            try:  # the peer copies completed before the readbacks (event edge)
                for h_src, h_dst in handles:
                    rep.client.free(h_src)
                    rep.client_d.free(h_dst)
                self.session.destroy_shared_event(ev)
            except Exception:
                pass  # teardown race on shutdown: session close cleans up
        with self._lock:
            rep.decode_pending.append((req, cache, tok))
            self._fill_slots_locked(rep)
            self._ensure_decode_locked(rep)

    # ------------------------------------------------------------- decode
    def _fill_slots_locked(self, rep: _Replica):  # holds: _lock
        if rep.decode_inflight:
            # the in-flight decode holds a snapshot of slot_cache; inserting
            # now would be overwritten when it completes (lost update)
            return
        for slot in range(self.max_num_seqs):
            if not rep.decode_pending:
                break
            if rep.slot_req[slot] is not None:
                continue
            req, single_cache, tok = rep.decode_pending.pop(0)
            rep.slot_cache = _insert_slot(rep.slot_cache, single_cache, slot)
            rep.slot_req[slot] = req
            rep.lengths[slot] = req.prompt_len
            rep.next_tokens[slot] = tok
            req.slot = slot
            req.state = RequestState.DECODING
            rep.active_count += 1

    def _ensure_decode_locked(self, rep: _Replica):  # holds: _lock
        if rep.decode_inflight or rep.active_count == 0:
            return
        rep.decode_inflight = True
        toks = jax.device_put(rep.next_tokens.copy(), rep.chip_d)
        lens = jax.device_put(rep.lengths.copy(), rep.chip_d)
        t0 = time.monotonic()
        fut = rep.client_d.launch(
            rep.stream_d, _decode_step, self.model, rep.params_d, toks,
            rep.slot_cache, lens, phase=Phase.DECODE,
            meta={"tokens": rep.active_count})
        fut.add_done_callback(
            lambda f, rp=rep, t=t0: self._decode_done(rp, f, t))

    def _decode_done(self, rep: _Replica, fut, t0: float) -> None:
        try:
            logits, new_cache = fut.result()
        except Exception:
            _log.exception("decode step failed on %s", rep.name)
            with self._lock:
                # the step's requests end FAILED; requests still waiting
                # for a slot go on to the next step
                rep.decode_inflight = False
                for slot, req in enumerate(rep.slot_req):
                    if req is not None:
                        rep.slot_req[slot] = None
                        rep.lengths[slot] = 0
                        rep.active_count -= 1
                        self._fail_locked(req)
                self._fill_slots_locked(rep)
                self._ensure_decode_locked(rep)
            return
        now = time.monotonic()
        toks = np.argmax(np.asarray(logits), axis=-1)
        with self._lock:
            rep.slot_cache = new_cache
            rep.decode_inflight = False
            rep.observe_step(now - t0)
            for slot in range(self.max_num_seqs):
                req = rep.slot_req[slot]
                if req is None:
                    continue
                rep.lengths[slot] += 1
                tok = int(toks[slot])
                req.record_token(now)
                req.output_tokens.append(tok)
                rep.next_tokens[slot] = tok
                if req.done_decoding:
                    rep.slot_req[slot] = None
                    rep.lengths[slot] = 0
                    rep.active_count -= 1
                    self._finish_locked(req)
            self._drain_admission_locked()
            self._fill_slots_locked(rep)
            self._ensure_decode_locked(rep)

    def _finish_locked(self, req: Request):  # holds: _lock
        req.state = RequestState.DONE
        req.finish_time = time.monotonic()
        self.finished.append(req)
        self.outstanding -= 1
        if self.on_request_done is not None:
            self.on_request_done(req)
        # a finished sequence releases its slot claim: gated admission may
        # now let the next request in (also covers requests that finish at
        # prefill, which never reach the decode-completion drain)
        self._drain_admission_locked()
        self._all_done.notify_all()

    def _fail_locked(self, req: Request):  # holds: _lock
        """Terminal FAILED with full ledger release: finish_time stamped,
        the outstanding count dropped, admission re-drained (a failed
        prefill/transfer releases its slot claim exactly like a finished
        one), and run() waiters woken."""
        req.state = RequestState.FAILED
        req.finish_time = time.monotonic()
        self.outstanding -= 1
        if self.on_request_done is not None:
            self.on_request_done(req)
        self._drain_admission_locked()
        self._all_done.notify_all()
