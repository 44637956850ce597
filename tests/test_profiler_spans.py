"""Host spans and counters of the Timeline (``FLEX_PROFILE=1``): their
event format, that tracing off records and calls nothing, the spans of a
RealEngine(dynamic_pd) run in order, and that the latency predictor's
samples do not see them."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.profiler import Timeline, span
from repro.distributed.sharding import unbox
from repro.models import build_model
from repro.predict.features import samples_from_events
from repro.serving.engine import RealEngine
from repro.serving.request import Request

PREFILL_CHAIN = ("engine.admit", "engine.prefill.stage", "flex.enqueue",
                 "flex.select", "flex.handoff", "flex.exec.call",
                 "flex.exec.wait", "flex.complete", "flex.callbacks",
                 "engine.prefill.readback")


def test_span_and_counter_write_chrome_events_on_the_monotonic_clock():
    tl = Timeline()
    t0 = time.monotonic()
    with tl.span("outer.op", device=2, op_id=7, req_id=3):
        with tl.span("inner.work", device=2, slot=1):
            time.sleep(0.002)
    tl.add("cross.thread", t0, t0 + 0.001, device=1, op_id=9)
    tl.counter("queue.depth", device=1, waiting=4, active=2)
    t1 = time.monotonic()
    evs = {e["name"]: e for e in tl.events()}
    assert set(evs) == {"outer.op", "inner.work", "cross.thread",
                        "queue.depth"}
    outer, inner = evs["outer.op"], evs["inner.work"]
    for ev in (outer, inner, evs["cross.thread"]):
        assert ev["ph"] == "X" and ":" not in ev["name"]
        assert t0 * 1e6 <= ev["ts"] <= ev["ts"] + ev["dur"] <= t1 * 1e6
    assert outer["pid"] == 2 and outer["tid"] == \
        threading.current_thread().name
    assert outer["args"] == {"op_id": 7, "req_id": 3}
    # a span given no op_id names the op of the span open around it
    assert inner["args"] == {"slot": 1, "op_id": 7}
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert inner["dur"] >= 2000
    c = evs["queue.depth"]
    assert c["ph"] == "C" and c["pid"] == 1
    assert c["args"] == {"waiting": 4, "active": 2}
    assert t0 * 1e6 <= c["ts"] <= t1 * 1e6


def test_span_without_a_timeline_is_one_shared_null_context():
    a, b = span(None, "x.y", op_id=1), span(None, "z")
    assert a is b
    with a as got:
        assert got is None


@pytest.fixture(scope="module")
def model():
    cfg = get_config("olmo-1b").reduced()
    m = build_model(cfg)
    return cfg, m, unbox(m.init(jax.random.PRNGKey(0)))


def serve(model, n=4):
    cfg, m, params = model
    reqs = [Request(prompt_len=8 + 4 * s, max_new_tokens=5,
                    prompt_tokens=np.random.default_rng(s).integers(
                        0, cfg.vocab_size, 8 + 4 * s).tolist(),
                    arrival_time=s * 0.01) for s in range(n)]
    eng = RealEngine(m, params, mode="dynamic_pd", max_num_seqs=2,
                     max_len=64)
    try:
        eng.run(reqs, timeout=300)
        tl = eng.session.timeline
        events = tl.events() if tl is not None else None
    finally:
        eng.shutdown()
    return reqs, events


def test_tracing_off_records_nothing_and_calls_no_span(model, monkeypatch):
    monkeypatch.delenv("FLEX_PROFILE", raising=False)

    def refuse(*a, **k):
        raise AssertionError("a recorder was called with tracing off")
    for name in ("span", "add", "counter", "record"):
        monkeypatch.setattr(Timeline, name, refuse)
    reqs, events = serve(model)
    assert events is None
    assert all(len(r.output_tokens) == 5 for r in reqs)


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("FLEX_PROFILE", "1")
        mp.setenv("FLEX_PROFILE_DIR", str(tmp_path_factory.mktemp("tl")))
        return serve(model)


def test_every_prefill_shows_its_spans_in_order(traced):
    reqs, events = traced
    ops = {e["args"]["req_id"]: e for e in events
           if e["name"] == "prefill:launch"}
    for r in reqs:
        mine = {e["name"]: e for e in events
                if e["ph"] == "X" and e["args"].get("req_id") == r.req_id
                and e["name"] in PREFILL_CHAIN}
        assert set(mine) == set(PREFILL_CHAIN), r.req_id
        chain = [mine[n] for n in PREFILL_CHAIN]
        for a, b in zip(chain, chain[1:]):
            assert a["ts"] <= b["ts"], (a["name"], b["name"])
        # flex.complete holds flex.callbacks, which holds the readback
        for outer, inner in zip(chain[-3:], chain[-2:]):
            assert outer["tid"] == inner["tid"]
            assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        op_id = ops[r.req_id]["args"]["op_id"]
        assert all(e["args"]["op_id"] == op_id for e in chain[2:])


def test_every_decode_step_shows_its_readback_and_staging(traced):
    _, events = traced
    steps = [e for e in events if e["name"] == "decode:launch"]
    assert steps
    spans = {(e["name"], e["args"]["step"]): e for e in events
             if e["name"].startswith("engine.decode.")}
    for op in steps:
        k = op["args"]["step"]
        assert 0 < op["args"]["ctx"] < 64
        stage = spans[("engine.decode.stage", k)]
        readback = spans[("engine.decode.readback", k)]
        assert stage["ts"] + stage["dur"] <= op["ts"] + 1
        assert readback["ts"] >= op["ts"]
        assert readback["args"]["op_id"] == op["args"]["op_id"]
    counters = {e["name"] for e in events if e["ph"] == "C"}
    assert counters == {"engine.queue", "flex.policy"}


def test_predictor_samples_ignore_the_spans(traced):
    _, events = traced
    ops = [e for e in events if ":" in e["name"]]
    assert len(ops) < len(events)
    assert samples_from_events(events) == samples_from_events(ops)
    assert samples_from_events(ops)
