"""FlexNPU core: daemon, client, handle virtualization, policies, profiler."""
import threading
import time

import pytest

from repro.core import (DynamicPDConfig, DynamicPDPolicy, FIFOPolicy,
                        FlexClient, FlexDaemon, OpDescriptor, OpType,
                        PassthroughClient, Phase, Profiler, RealBackend,
                        StaticTimeSlicePolicy, connect)


def make_daemon(policy=None):
    d = FlexDaemon(0, RealBackend(), policy or FIFOPolicy())
    d.start()
    return d


def test_transparency_same_results_both_clients():
    """The engine-visible contract: identical results under passthrough and
    FlexNPU interposition (the paper's transparency property)."""
    work = lambda x: x * x + 1
    d = make_daemon()
    flex = FlexClient(d)
    passthrough = PassthroughClient()
    s = flex.create_stream(phase=Phase.DECODE)
    a = [flex.launch(s, work, i, phase=Phase.DECODE).result()
         for i in range(20)]
    b = [passthrough.launch(0, work, i).result() for i in range(20)]
    assert a == b
    d.stop()
    passthrough.close()


def test_handle_virtualization():
    d = make_daemon()
    c = FlexClient(d)
    s1 = c.create_stream(phase=Phase.PREFILL)
    s2 = c.create_stream(phase=Phase.DECODE)
    assert s1 != s2
    h1 = c.malloc(1 << 20, tag="kv")
    h2 = c.malloc(1 << 10, tag="scratch")
    assert h1 != h2
    assert d.allocated_bytes == (1 << 20) + (1 << 10)
    c.free(h1)
    assert d.allocated_bytes == (1 << 10)
    assert d.peak_bytes == (1 << 20) + (1 << 10)
    d.stop()


def test_async_launch_returns_before_completion():
    d = make_daemon()
    c = FlexClient(d)
    ev = threading.Event()
    fut = c.launch(0, lambda: (ev.wait(1.0), 42)[1], phase=Phase.PREFILL)
    assert not fut.done()       # async proxying: control returned immediately
    ev.set()
    assert fut.result(2.0) == 42
    d.stop()


def test_failed_device_errors_futures():
    d = make_daemon()
    c = FlexClient(d)
    d.stop()
    d.fail()
    fut = c.launch(0, lambda: 1, phase=Phase.DECODE)
    with pytest.raises(RuntimeError):
        fut.result(1.0)


class _FailsOnSync:
    """A launch result whose device execution failed asynchronously: like
    a TPU array after an HBM OOM, it raises only when it is waited for."""

    def block_until_ready(self):
        raise RuntimeError("asynchronous device fault")


@pytest.mark.parametrize("mode", ["flex", "passthrough"])
def test_error_raised_after_dispatch_fails_the_future(mode):
    with connect(mode=mode) as sess:
        s = sess.create_stream(phase=Phase.DECODE)
        fut = sess.launch(s, _FailsOnSync, phase=Phase.DECODE)
        with pytest.raises(RuntimeError, match="asynchronous device fault"):
            fut.result(5.0)
        sess.destroy_stream(s)


def test_profiler_phase_stats():
    d = make_daemon()
    c = FlexClient(d)
    for i in range(10):
        c.launch(0, lambda: time.sleep(0.002), phase=Phase.DECODE,
                 meta={"tokens": 4, "bytes": 1e9, "flops": 1e9}).result()
    st = d.profiler.stats[Phase.DECODE]
    assert st.ops_completed == 10
    assert st.tokens_done == 40
    assert st.ewma_exec > 0.001
    assert 0.0 < st.bandwidth_util() <= 1.0
    d.stop()


def _run_policy_mix(policy, n=60, exec_s=0.001):
    """Feed interleaved prefill/decode ops; returns realized decode share."""
    d = FlexDaemon(0, RealBackend(), policy)
    c = FlexClient(d)
    futs = []
    for i in range(n):
        phase = Phase.DECODE if i % 2 else Phase.PREFILL
        futs.append(c.launch(0, lambda: time.sleep(exec_s), phase=phase,
                             meta={"est_duration": exec_s}))
    d.start()          # start AFTER enqueue so both queues are contended
    for f in futs:
        f.result(30.0)
    d.stop()
    spent = policy._spent
    total = sum(spent.values())
    return spent[Phase.DECODE] / total


@pytest.mark.parametrize("share", [0.05, 0.5, 0.95])
def test_static_timeslice_work_conserving_completion(share):
    """Even at extreme shares every op completes (work conservation): when
    the favored queue drains, the other phase gets the device.  Share
    convergence itself is tested deterministically in test_props.py."""
    realized = _run_policy_mix(StaticTimeSlicePolicy(share))
    assert 0.0 < realized < 1.0


def test_dynamic_policy_bounds():
    pol = DynamicPDPolicy(DynamicPDConfig(min_share=0.1, max_share=0.9))
    _run_policy_mix(pol)
    assert 0.1 <= pol.decode_share <= 0.9


def test_fifo_is_arrival_ordered():
    d = FlexDaemon(0, RealBackend(), FIFOPolicy())
    c = FlexClient(d)
    order = []
    futs = []
    for i in range(12):
        phase = Phase.DECODE if i % 3 else Phase.PREFILL
        futs.append(c.launch(0, lambda i=i: order.append(i), phase=phase))
    d.start()
    for f in futs:
        f.result(10.0)
    d.stop()
    assert order == sorted(order)


# ---------------------------------------------------------------- v2 verbs
def test_destroy_stream_and_event_end_to_end():
    d = make_daemon()
    c = FlexClient(d)
    s = c.create_stream(phase=Phase.PREFILL)
    ev = c.create_event()
    assert len(d.streams) == 1 and len(d.events) == 1
    c.launch(s, lambda: 1, phase=Phase.PREFILL).result(5)
    c.destroy_event(ev)
    c.destroy_stream(s)
    assert len(d.streams) == 0 and len(d.events) == 0
    # destroyed handles are gone: re-destroying a stream with pending work
    s2 = c.create_stream()
    gate = threading.Event()
    fut = c.launch(s2, lambda: gate.wait(5))
    with pytest.raises(RuntimeError):
        c.destroy_stream(s2)       # stream busy: refuse, don't corrupt
    gate.set()
    fut.result(5)
    c.synchronize(s2)
    c.destroy_stream(s2)
    assert len(d.streams) == 0
    d.stop()


def test_destroy_event_with_pending_record_refused():
    d = FlexDaemon(0, RealBackend())      # not started: record stays queued
    c = FlexClient(d)
    ev = c.create_event()
    c.record_event(ev, 0)
    with pytest.raises(RuntimeError):
        c.destroy_event(ev)
    d.start()
    d.drain()
    c.destroy_event(ev)
    assert len(d.events) == 0
    d.stop()


def test_passthrough_synchronize_waits_for_inflight_op():
    """Regression: q.empty() is true while the worker still executes the
    dequeued op — synchronize must track in-flight state."""
    c = PassthroughClient()
    done = []
    c.launch(0, lambda: (time.sleep(0.25), done.append(1)))
    c.synchronize(0)               # honors the vstream argument too
    assert done == [1]
    c.close()


class _TickBackend:
    """Minimal stepped backend for driving a daemon by hand."""

    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t

    def estimate(self, op):
        return float(op.meta.get("est_duration", 1e-3))


def test_flex_synchronize_marker_is_stream_scoped():
    """The SYNCHRONIZE marker completes once ITS stream drains — work still
    queued on a sibling stream does not gate it (stepped drive, so dispatch
    order is fully deterministic under a decode-biased policy)."""
    from repro.core.api import OpDescriptor, OpType
    d = FlexDaemon(0, _TickBackend(), StaticTimeSlicePolicy(0.99))
    c = FlexClient(d)
    s1 = c.create_stream(phase=Phase.PREFILL)
    s2 = c.create_stream(phase=Phase.DECODE)
    slow = c.launch(s1, None, phase=Phase.PREFILL,
                    meta={"est_duration": 100.0})
    fast = c.launch(s2, None, phase=Phase.DECODE,
                    meta={"est_duration": 0.001})
    marker = OpDescriptor(OpType.SYNCHRONIZE, vstream=s2)
    d.enqueue(marker)
    op = d.select_next(0.0)            # decode bias: fast, not slow
    assert op.future is fast
    d.mark_complete(op, 0.001)
    op = d.select_next(0.002)          # marker now heads s2; OTHER preempts
    assert op.op == OpType.SYNCHRONIZE
    d.mark_complete(op, 0.002)
    assert marker.future.done() and fast.done()
    assert not slow.done() and d.pending_count() == 1  # s1 never gated s2


# -------------------------------------------------------------- fault paths
def test_fail_without_sink_errors_queued_futures():
    d = FlexDaemon(0, RealBackend())      # stepped: ops stay queued
    c = FlexClient(d)
    futs = [c.launch(0, lambda: 1, phase=Phase.DECODE) for _ in range(4)]
    d.fail()
    for f in futs:
        with pytest.raises(RuntimeError):
            f.result(1.0)
    assert d.pending_count() == 0


def test_fail_with_requeue_sink_hands_ops_over():
    d = FlexDaemon(0, RealBackend())
    c = FlexClient(d)
    futs = [c.launch(0, lambda: 1, phase=Phase.PREFILL) for _ in range(3)]
    salvaged = []
    d.fail(requeue_sink=salvaged.append)
    assert len(salvaged) == 3
    assert all(not f.done() for f in futs)  # sink owns them now, not errored
    assert d.pending_count() == 0


def test_enqueue_after_fail_errors_immediately():
    d = FlexDaemon(0, RealBackend())
    c = FlexClient(d)
    d.fail()
    fut = c.launch(0, lambda: 1, phase=Phase.DECODE)
    with pytest.raises(RuntimeError):
        fut.result(0.1)
    with pytest.raises(RuntimeError):
        c.malloc(64)


def test_fail_clears_ordering_state():
    d = FlexDaemon(0, RealBackend())
    c = FlexClient(d)
    ev = c.create_event()
    c.launch(0, lambda: 1, phase=Phase.PREFILL)
    c.record_event(ev, 0)
    d.fail(requeue_sink=lambda op: None)
    assert not d._stream_pending and not d._event_state
    assert d.select_next(0.0) is None     # failed daemon dispatches nothing
