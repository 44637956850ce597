"""The readers of the program's host spans (``bench.spans``): on a
hand-built timeline whose answers are known, on the chip sample recorded
without spans (they find nothing), and on the one recorded with them."""
import json
import os

import pytest

from bench import harness, spans
from bench.records import TimedRequest

DATA = os.path.join(os.path.dirname(__file__), "..", "data")
READERS = ("decode_host_gap_p50_s", "interpose_p50_s", "prefill_queue_p90_s")


def x(name, start_ms, dur_ms, pid=0, **args):
    return {"name": name, "ph": "X", "ts": start_ms * 1e3,
            "dur": dur_ms * 1e3, "pid": pid, "tid": "t", "args": args}


def launch(events, phase, op_id, at_ms, dur_ms, host_ms, pid=0,
           enqueue_ms=0.1, queue_ms=0.5, **meta):
    """One launch as the program records it: enqueued ``queue_ms`` before
    its dispatch at ``at_ms``, then its flex.* spans, ``host_ms`` of
    daemon time after the wait (half of it inside flex.callbacks)."""
    ids = dict(op_id=op_id, **meta)
    t = at_ms - queue_ms
    events += [
        x(f"{phase}:launch", at_ms, dur_ms, pid, **ids),
        x("flex.enqueue", t - enqueue_ms, enqueue_ms, pid, **ids),
        x("flex.select", at_ms, 0.05, pid, **ids),
        x("flex.handoff", at_ms + 0.05, 0.03, pid, **ids),
        x("flex.exec.call", at_ms + 0.08, 0.2, pid, **ids),
        x("flex.exec.wait", at_ms + 0.28, dur_ms - 0.28, pid, **ids),
        x("flex.complete", at_ms + dur_ms, host_ms, pid, **ids),
        x("flex.callbacks", at_ms + dur_ms, host_ms / 2, pid, **ids),
    ]


def built():
    """Decode steps 1-2 (gap 3 ms), a prefill, steps 3-4 (gap 5 ms), a
    step after the window, and a decode on another device."""
    ev = []
    launch(ev, "decode", 1, 1000.0, 20.0, 1.0, step=1)
    launch(ev, "decode", 2, 1023.0, 20.0, 1.0, step=2)     # 3 ms after 1
    launch(ev, "prefill", 3, 1046.0, 30.0, 2.0, req_id=7, queue_ms=12.0)
    launch(ev, "decode", 4, 1080.0, 20.0, 1.0, step=3)
    launch(ev, "decode", 5, 1105.0, 20.0, 1.0, step=4)     # 5 ms after 4
    launch(ev, "decode", 6, 2500.0, 20.0, 1.0, step=5)     # out of window
    launch(ev, "decode", 7, 1030.0, 20.0, 9.0, pid=1, step=1)
    ev.append({"name": "engine.queue", "ph": "C", "ts": 1.0e6, "pid": 0,
               "args": {"waiting": 1}})
    return ev


def run_on(events, w0, w1, reqs=()):
    return harness.Run(cell="olmo-1b.chat", config={}, model={}, mix={},
                       chips=1, peak=None, w0=w0, w1=w1, closed=w1,
                       requests=list(reqs), compiles=[], timeline=events)


def test_decode_gaps_skip_a_prefill_between_steps():
    gaps = spans.decode_host_gaps(built(), 0.9, 2.0)
    # 1->2 and 4->5; 2->prefill->4 is skipped, and 6 lies past the window
    # (each from a wait's end to the next call, 0.08 ms after dispatch)
    assert gaps == pytest.approx([0.00308, 0.00508])
    assert spans.decode_host_gaps([], 0.0, 1.0) == []


def test_interposition_is_the_daemons_own_time_per_op():
    per_op = spans.interposition(built(), 0.9, 2.0)
    # enqueue 0.1 + select 0.05 + handoff 0.03 + complete's self time
    # (host / 2): 0.68 ms per decode, 1.18 ms for the prefill, 4.68 ms
    # on device 1
    assert sorted(per_op) == pytest.approx(
        [0.68e-3] * 4 + [1.18e-3, 4.68e-3])


def test_prefill_queue_runs_from_enqueue_to_the_call():
    req = TimedRequest(prompt_len=8, max_new_tokens=1, req_id=7)
    other = TimedRequest(prompt_len=8, max_new_tokens=1, req_id=8)
    # enqueued 12 ms before its dispatch; the call starts 0.08 ms after
    assert spans.prefill_queue(built(), [req, other]) == \
        pytest.approx([0.01208])


def test_self_time_leaves_out_the_inner_spans():
    outer = x("flex.complete", 0.0, 2.0)
    assert spans.self_time(outer, [x("flex.callbacks", 0.5, 1.5)]) == \
        pytest.approx(0.5e-3)


def test_readers_on_a_built_timeline():
    req = TimedRequest(prompt_len=8, max_new_tokens=1, req_id=7, due=1.0)
    run = run_on(built(), 0.9, 2.0, [req])
    got = {n: harness.load_reader("metrics", n)(run) for n in READERS}
    assert got["decode_host_gap_p50_s"] == pytest.approx(0.00408)
    assert got["interpose_p50_s"] == pytest.approx(0.68e-3)
    assert got["prefill_queue_p90_s"] == pytest.approx(0.01208)


def sample_run(name):
    with open(os.path.join(DATA, name)) as f:
        meta = json.load(f)
    reqs = []
    for r in meta["requests"]:
        q = TimedRequest(prompt_len=r["prompt_len"], max_new_tokens=1,
                         req_id=r["req_id"],
                         due=r["token_times"][0] - 0.05)
        q.token_times = r["token_times"]
        reqs.append(q)
    return run_on(meta["timeline"], meta["t0"], meta["t1"], reqs)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_where_the_program_records_no_spans(name):
    """The chip sample of a program without spans: each reader finds
    nothing, and the result line leaves the metric out."""
    assert harness.load_reader("metrics", name)(sample_run("sample.json")) \
        is None


def test_readers_on_the_chip_sample_with_spans():
    run = sample_run("sample-spans.json")
    gap = harness.load_reader("metrics", "decode_host_gap_p50_s")(run)
    ours = harness.load_reader("metrics", "interpose_p50_s")(run)
    assert 0 < ours < gap < 0.02
    waits = spans.prefill_queue(run.timeline, run.requests)
    assert waits and all(0 <= w < 0.2 for w in waits)
