"""v2 session API: multi-device routing, full verb set, memcpy payloads,
and the dispatch-ordering contract (same-vstream FIFO + cross-stream event
edges) under BOTH drive modes — the threaded daemon and the discrete-event
simulator."""
import threading

import numpy as np
import pytest

from repro.core import (DynamicPDPolicy, FIFOPolicy, MemcpyKind, Phase,
                        StaticTimeSlicePolicy, connect)
from repro.serving.simulator import EventLoop, SimBackend


# ---------------------------------------------------------------- sessions
def test_connect_modes_and_device_count():
    for mode, devices in (("flex", 2), ("passthrough", 1), ("sim", 3)):
        kw = {}
        if mode == "sim":
            kw["backend"] = SimBackend(EventLoop().clock)
        sess = connect(mode=mode, devices=devices, **kw)
        try:
            assert sess.device_count() == devices
            with pytest.raises(IndexError):
                sess.device(devices)
            with pytest.raises(IndexError):
                sess.set_device(-1)
        finally:
            sess.close()
    with pytest.raises(ValueError):
        connect(mode="nope")
    with pytest.raises(ValueError):
        connect(mode="sim")  # stepped mode requires a clock-bearing backend


@pytest.mark.parametrize("mode", ["flex", "passthrough"])
def test_virtual_devices_execute_on_jax_devices(mode):
    """Virtual device i runs on jax.devices()[i]; the CPU's devices all
    share host memory, so a larger session wraps around them."""
    import jax
    import jax.numpy as jnp
    chips = jax.devices()
    n = len(chips) + 2
    with connect(mode=mode, devices=n) as sess:
        assert [sess.jax_device(i) for i in range(n)] == \
            [chips[i % len(chips)] for i in range(n)]
        dev = min(2, len(chips) - 1)
        sess.set_device(dev)
        s = sess.create_stream()
        out = sess.launch(s, lambda: jnp.zeros(3)).result(10.0)
        assert out.devices() == {chips[dev]}
        sess.destroy_stream(s)


def test_session_larger_than_the_accelerator_host_raises(monkeypatch):
    import jax

    class Chip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Chip()])
    for mode in ("flex", "passthrough"):
        with pytest.raises(ValueError, match="needs 2 tpu devices"):
            connect(mode=mode, devices=2)
        with connect(mode=mode, devices=1) as sess:
            assert isinstance(sess.jax_device(0), Chip)


def test_multi_device_routing_and_isolation():
    """Each device has its own daemon, handle tables, and accounting."""
    with connect(mode="flex", devices=2) as sess:
        sess.set_device(0)
        h0a = sess.malloc(1 << 20, tag="d0")
        h0b = sess.malloc(1 << 20, tag="d0")
        sess.set_device(1)
        h1 = sess.malloc(1 << 10, tag="d1")
        assert sess.daemon(0).allocated_bytes == 2 << 20
        assert sess.daemon(1).allocated_bytes == 1 << 10
        # handles are device-local: h0b exists only on device 0
        assert h0b not in sess.daemon(1).memory.live_handles()
        with pytest.raises(KeyError):
            sess.free(h0b)  # still on device 1
        sess.set_device(0)
        sess.free(h0a), sess.free(h0b)
        sess.set_device(1)
        sess.free(h1)
        assert sess.stats()[0]["allocated_bytes"] == 0
        assert sess.stats()[1]["allocated_bytes"] == 0


def test_policy_prototype_copied_per_device():
    proto = DynamicPDPolicy()
    with connect(mode="flex", devices=2, policy=proto) as sess:
        assert sess.daemon(0).policy is proto
        assert sess.daemon(1).policy is not proto
        assert isinstance(sess.daemon(1).policy, DynamicPDPolicy)


def test_instance_handle_isolation():
    """Co-located logical instances must not free each other's buffers."""
    from repro.core import FlexClient
    with connect(mode="flex", instance="prefill") as sess:
        d = sess.daemon(0)
        other = FlexClient(d, instance="decode")
        h = sess.malloc(4096, tag="kv")
        with pytest.raises(PermissionError):
            other.free(h)
        assert d.allocated_by_instance["prefill"] == 4096
        sess.free(h)
        assert d.allocated_by_instance["prefill"] == 0


# ----------------------------------------------------------------- memcpy
@pytest.mark.parametrize("mode", ["flex", "passthrough"])
def test_memcpy_roundtrip_h2d_d2h(mode):
    data = np.arange(256, dtype=np.float32)
    with connect(mode=mode) as sess:
        s = sess.create_stream()
        h = sess.malloc(data.nbytes)
        sess.memcpy(h, data, vstream=s).result(5)
        out = sess.memcpy(None, h, data.nbytes, vstream=s).result(5)
        np.testing.assert_array_equal(out, data)
        # D2D into a second buffer, then read it back
        h2 = sess.malloc(data.nbytes)
        sess.memcpy(h2, h, data.nbytes, vstream=s).result(5)
        out2 = sess.memcpy(None, h2, data.nbytes, vstream=s).result(5)
        np.testing.assert_array_equal(out2, data)
        sess.free(h), sess.free(h2)
        sess.destroy_stream(s)


def test_memcpy_kind_inference_and_cost_meta():
    with connect(mode="flex") as sess:
        h = sess.malloc(1 << 20)
        fut = sess.memcpy(h, np.zeros(1 << 10, np.uint8))
        fut.result(5)
        # the enqueued descriptor was billed at the modeled H2D link cost
        prof = sess.daemon(0).profiler.stats[Phase.OTHER]
        assert prof.ewma_bytes == 1 << 10
        sess.free(h)


@pytest.mark.parametrize("mode", ["flex", "passthrough"])
def test_memcpy_overflow_errors(mode):
    """Capacity checks hold under BOTH clients (transparency)."""
    with connect(mode=mode) as sess:
        h = sess.malloc(16)
        with pytest.raises(MemoryError):
            sess.memcpy(h, np.zeros(64, np.float32)).result(5)
        sess.free(h)


def test_memcpy_kinds_infer():
    from repro.core.api import infer_memcpy_kind
    assert infer_memcpy_kind(3, np.zeros(4)) == MemcpyKind.H2D
    assert infer_memcpy_kind(None, 3) == MemcpyKind.D2H
    assert infer_memcpy_kind(3, 4) == MemcpyKind.D2D


# ------------------------------------------------- ordering: threaded mode
def test_same_stream_fifo_under_threaded_daemon():
    """Ops on ONE vstream complete in enqueue order even when their phases
    would let a biased policy reorder them."""
    order = []
    with connect(mode="flex", policy=StaticTimeSlicePolicy(0.95)) as sess:
        d = sess.daemon(0)
        d.stop()  # enqueue everything first so queues are contended
        s = sess.create_stream()
        futs = []
        for i in range(16):
            phase = Phase.DECODE if i % 2 else Phase.PREFILL
            futs.append(sess.launch(
                s, lambda i=i: order.append(i), phase=phase,
                meta={"est_duration": 1e-3}))
        d.start()
        for f in futs:
            f.result(10)
    assert order == list(range(16))


def test_cross_stream_runs_out_of_order_without_event():
    """Control: with no event edge, a decode-biased policy reorders across
    streams (proves the FIFO test above is testing the stream, not luck)."""
    order = []
    with connect(mode="flex", policy=StaticTimeSlicePolicy(0.99)) as sess:
        d = sess.daemon(0)
        d.stop()
        sp = sess.create_stream(phase=Phase.PREFILL)
        sd = sess.create_stream(phase=Phase.DECODE)
        futs = [sess.launch(sp, lambda: order.append("p"),
                            phase=Phase.PREFILL, meta={"est_duration": 1e-3})]
        for i in range(4):
            futs.append(sess.launch(sd, lambda i=i: order.append("d"),
                                    phase=Phase.DECODE,
                                    meta={"est_duration": 1e-3}))
        d.start()
        for f in futs:
            f.result(10)
    assert order[0] == "d"  # decode bias won: prefill enqueued first, ran later


def test_cross_stream_event_edge_under_threaded_daemon():
    """record_event/wait_event builds a real happens-before edge: the decode
    stream's op must not run before the gated prefill op completes."""
    order = []
    gate = threading.Event()
    with connect(mode="flex") as sess:
        sp = sess.create_stream(phase=Phase.PREFILL)
        sd = sess.create_stream(phase=Phase.DECODE)
        ev = sess.create_event()
        sess.launch(sp, lambda: (gate.wait(5), order.append("prefill"))[1],
                    phase=Phase.PREFILL)
        sess.record_event(ev, sp)
        sess.wait_event(ev, sd)
        fut = sess.launch(sd, lambda: order.append("decode"),
                          phase=Phase.DECODE)
        assert not fut.done()
        gate.set()
        fut.result(10)
        assert order == ["prefill", "decode"]
        sess.synchronize(sp)
        sess.destroy_event(ev)
        sess.destroy_stream(sp), sess.destroy_stream(sd)


def test_wait_on_unrecorded_event_is_noop():
    with connect(mode="flex") as sess:
        s = sess.create_stream()
        ev = sess.create_event()
        sess.wait_event(ev, s).result(5)  # CUDA/ACL semantics: completes
        sess.destroy_event(ev)
        sess.destroy_stream(s)


# -------------------------------------------- ordering: discrete-event mode
def _stepped_driver(loop, daemon):
    """Minimal SimInstance-style device: one op in flight, modeled duration."""
    state = {"busy": False}

    def kick():
        if state["busy"]:
            return
        op = daemon.select_next(loop.clock.t)
        if op is None:
            return
        state["busy"] = True

        def complete(o=op):
            state["busy"] = False
            daemon.mark_complete(o, loop.clock.t)
            kick()
        loop.after(float(op.meta.get("est_duration", 1e-3)), complete)
    return kick


def test_same_stream_fifo_under_stepped_simulator():
    loop = EventLoop()
    sess = connect(mode="sim", backend=SimBackend(loop.clock),
                   policy=StaticTimeSlicePolicy(0.95))
    client, daemon = sess.device(0), sess.daemon(0)
    s = client.create_stream()
    done = []
    for i in range(12):
        phase = Phase.DECODE if i % 2 else Phase.PREFILL
        client.launch(s, None, phase=phase, meta={"est_duration": 0.01}) \
            .add_done_callback(lambda f, i=i: done.append(i))
    kick = _stepped_driver(loop, daemon)
    loop.at(0.0, kick)
    loop.run()
    assert done == list(range(12))
    assert daemon.pending_count() == 0
    sess.close()


def test_cross_stream_event_edge_under_stepped_simulator():
    """A cheap decode op behind a wait_event must complete AFTER the long
    prefill op that records the event — on the virtual clock."""
    loop = EventLoop()
    sess = connect(mode="sim", backend=SimBackend(loop.clock),
                   policy=DynamicPDPolicy())
    client, daemon = sess.device(0), sess.daemon(0)
    sp = client.create_stream(phase=Phase.PREFILL)
    sd = client.create_stream(phase=Phase.DECODE)
    ev = client.create_event()
    times = {}
    client.launch(sp, None, phase=Phase.PREFILL,
                  meta={"est_duration": 1.0}) \
        .add_done_callback(lambda f: times.setdefault("prefill", loop.clock.t))
    client.record_event(ev, sp)
    client.wait_event(ev, sd)
    client.launch(sd, None, phase=Phase.DECODE,
                  meta={"est_duration": 0.001}) \
        .add_done_callback(lambda f: times.setdefault("decode", loop.clock.t))
    kick = _stepped_driver(loop, daemon)
    loop.at(0.0, kick)
    loop.run()
    assert times["prefill"] >= 1.0
    assert times["decode"] > times["prefill"]
    sess.close()


def test_stepped_wait_before_record_program_order():
    """wait enqueued BEFORE any record completes only after the record that
    was pending at wait time finishes (program-order happens-before)."""
    loop = EventLoop()
    sess = connect(mode="sim", backend=SimBackend(loop.clock))
    client, daemon = sess.device(0), sess.daemon(0)
    s1 = client.create_stream()
    s2 = client.create_stream()
    ev = client.create_event()
    client.launch(s1, None, meta={"est_duration": 0.5})
    client.record_event(ev, s1)
    waited = []
    client.wait_event(ev, s2).add_done_callback(
        lambda f: waited.append(loop.clock.t))
    kick = _stepped_driver(loop, daemon)
    loop.at(0.0, kick)
    loop.run()
    assert waited and waited[0] >= 0.5
    sess.close()


# -------------------------------------------------------- engine lifecycle
def test_engine_session_handles_do_not_leak():
    """RealEngine goes through the session API exclusively and releases its
    stream handles at shutdown (no table leaks)."""
    import jax
    from repro.configs import get_config
    from repro.distributed.sharding import unbox
    from repro.models import build_model
    from repro.serving.engine import RealEngine
    from repro.serving.request import Request

    cfg = get_config("olmo-1b").reduced()
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    reqs = [Request(prompt_len=8, max_new_tokens=4,
                    prompt_tokens=rng.integers(0, cfg.vocab_size, 8).tolist(),
                    arrival_time=0.0) for _ in range(2)]
    eng = RealEngine(model, params, mode="dynamic_pd", max_num_seqs=2,
                     max_len=32)
    assert eng.session.stats()[0]["streams"] == 2
    try:
        res = eng.run(reqs, timeout=120)
        assert res["completed"] == 2
    finally:
        eng.shutdown()
    st = eng.session.stats()[0]
    assert st["streams"] == 0 and st["events"] == 0 and st["buffers"] == 0


def test_cluster_session_spans_all_instances():
    """The simulator's 384-card story rides the session API: one session,
    one stepped daemon per instance."""
    from repro.configs import get_config
    from repro.serving import Cluster, deployment_6p2d, make_workload
    cluster = Cluster(get_config("mixtral-8x7b"), deployment_6p2d())
    assert cluster.session.device_count() == len(cluster.instances) == 8
    assert all(cluster.session.daemon(i) is inst.daemon
               for i, inst in enumerate(cluster.instances))
    res = cluster.run(make_workload(40, 256, 128, rate=100.0, seed=9),
                      until=36000)
    assert res["completed"] == 40


def test_closed_session_rejects_new_work():
    sess = connect(mode="flex")
    sess.close()
    with pytest.raises(RuntimeError, match="closed"):
        sess.launch(0, lambda: 42).result(1)
    sess.close()  # idempotent


def test_untagged_client_cannot_free_owned_buffer():
    from repro.core import FlexClient
    with connect(mode="flex", instance="engine") as sess:
        h = sess.malloc(64, tag="kv")
        anon = FlexClient(sess.daemon(0))  # instance=""
        with pytest.raises(PermissionError):
            anon.free(h)
        sess.free(h)


# --------------------------------------------- code-review regression tests
def test_wait_ignores_records_enqueued_after_it():
    """CUDA/ACL semantics: a wait snapshots the records issued BEFORE it; a
    record enqueued later (behind a slow op) must not block the waiter."""
    loop = EventLoop()
    sess = connect(mode="sim", backend=SimBackend(loop.clock))
    client, daemon = sess.device(0), sess.daemon(0)
    s1, s2 = client.create_stream(), client.create_stream()
    ev = client.create_event()
    waited = []
    client.wait_event(ev, s2).add_done_callback(
        lambda f: waited.append(loop.clock.t))
    client.launch(s1, None, meta={"est_duration": 5.0})
    client.record_event(ev, s1)   # issued AFTER the wait
    state = {"busy": False}

    def kick():
        if state["busy"]:
            return
        op = daemon.select_next(loop.clock.t)
        if op is None:
            return
        state["busy"] = True

        def complete(o=op):
            state["busy"] = False
            daemon.mark_complete(o, loop.clock.t)
            kick()
        loop.after(float(op.meta.get("est_duration", 1e-3)), complete)
    loop.at(0.0, kick)
    loop.run()
    assert waited and waited[0] < 5.0, waited
    sess.close()


def test_free_refused_while_memcpy_pending():
    """A queued stream-ordered memcpy must not lose its buffer to an inline
    free racing ahead of it."""
    with connect(mode="flex") as sess:
        d = sess.daemon(0)
        d.stop()                       # keep the copy queued
        s = sess.create_stream()
        h = sess.malloc(64)
        fut = sess.memcpy(h, np.zeros(16, np.uint8), vstream=s)
        with pytest.raises(RuntimeError, match="pending memcpy"):
            sess.free(h)
        d.start()
        fut.result(5)
        sess.free(h)                   # copy done: free succeeds


def test_memcpy_default_nbytes_from_buffer():
    """D2H/D2D memcpys without an explicit size bill the real buffer size
    (not zero) so modeled cost and capacity checks are meaningful."""
    from repro.core import memcpy_model_time, MemcpyKind
    with connect(mode="flex") as sess:
        h = sess.malloc(1 << 20)
        sess.memcpy(h, np.zeros(1 << 18, np.float32)).result(5)  # fill 1 MiB
        d = sess.daemon(0)
        d.stop()
        fut = sess.memcpy(None, h)     # no nbytes given
        op = d.queues[Phase.OTHER][-1] if d.queues[Phase.OTHER] else None
        assert op is not None and op.meta["nbytes"] == 1 << 20
        assert op.meta["est_duration"] == pytest.approx(
            memcpy_model_time(MemcpyKind.D2H, 1 << 20))
        d.start()
        fut.result(5)
        sess.free(h)


def test_double_free_raises_under_both_clients():
    for mode in ("flex", "passthrough"):
        with connect(mode=mode) as sess:
            h = sess.malloc(32)
            sess.free(h)
            with pytest.raises(KeyError):
                sess.free(h)


def test_policy_sees_full_backlog_depth():
    """The ready view restricts WHAT may dispatch, not the depth signals:
    len() must report the whole per-phase backlog (DynamicPDPolicy's load
    pressure inputs)."""
    from repro.core.daemon import FlexDaemon
    seen = {}

    class Spy(FIFOPolicy):
        def pick(self, ctx):
            seen["depth"] = len(ctx.queues[Phase.PREFILL])
            seen["ready"] = sum(1 for _ in ctx.queues[Phase.PREFILL])
            return super().pick(ctx)

    class Tick:
        t = 0.0

        def now(self):
            return self.t

        def estimate(self, op):
            return 1e-3

    d = FlexDaemon(0, Tick(), Spy())
    from repro.core import FlexClient
    c = FlexClient(d)
    s = c.create_stream(phase=Phase.PREFILL)
    for _ in range(5):
        c.launch(s, None, phase=Phase.PREFILL)
    assert d.select_next(0.0) is not None
    assert seen["depth"] == 5 and seen["ready"] == 1


def test_wait_on_destroyed_event_unblocks():
    """Destroying an event whose records all completed must not wedge a
    still-queued wait: the wait treats a missing event as satisfied."""
    loop = EventLoop()
    sess = connect(mode="sim", backend=SimBackend(loop.clock))
    client, daemon = sess.device(0), sess.daemon(0)
    s1, s2 = client.create_stream(), client.create_stream()
    ev = client.create_event()
    client.record_event(ev, s1)                       # completes first
    client.launch(s2, None, meta={"est_duration": 1.0})
    w = client.wait_event(ev, s2)                     # queued behind slow
    state = {"busy": False}

    def kick():
        if state["busy"]:
            return
        op = daemon.select_next(loop.clock.t)
        if op is None:
            return
        state["busy"] = True

        def complete(o=op):
            state["busy"] = False
            daemon.mark_complete(o, loop.clock.t)
            kick()
        loop.after(float(op.meta.get("est_duration", 1e-3)), complete)
    loop.at(0.0, kick)
    loop.at(0.5, lambda: client.destroy_event(ev))  # record done: legal
    loop.run()
    assert w.done() and daemon.pending_count() == 0
    sess.close()
