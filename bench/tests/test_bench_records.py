"""The end-to-end metric arithmetic on synthetic request records."""
import os
import types

import pytest

from bench import records, traffic
from bench.records import TimedRequest

BENCH = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def req(due, times, prompt_len=10):
    r = TimedRequest(prompt_len=prompt_len, max_new_tokens=len(times),
                     due=due, arrival_time=due)
    for t in times:
        r.record_token(t)
    return r


def test_timed_request_keeps_every_token_time():
    r = req(1.0, [1.5, 1.6, 1.8, 2.0])
    assert r.token_times == [1.5, 1.6, 1.8, 2.0]
    assert r.generated == 4 and r.first_token_time == 1.5
    spans = []

    class Span:
        def __init__(self, name):
            spans.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False
    r.span = Span
    r.record_token(2.1)
    assert spans == ["bench.token"] and r.token_times[-1] == 2.1


def test_ttft_is_timed_from_the_due_time():
    # window [10, 20); the engine's arrival_time plays no part
    rs = [req(9.0, [9.5]),            # due before the window: left out
          req(10.0, [10.3, 10.4]),
          req(12.0, [13.0]),
          req(19.5, []),              # no first token by the close at 21
          req(20.0, [20.1])]          # due at the window's end: left out
    rs[1].arrival_time = 10.2         # a late submit does not hide the wait
    got = records.ttfts(rs, 10.0, 20.0, closed=21.0)
    assert got == pytest.approx([0.3, 1.0, 1.5])


def test_first_token_after_the_close_counts_as_waited_so_far():
    rs = [req(10.0, [25.0])]
    assert records.ttfts(rs, 10.0, 20.0, closed=22.0) == \
        pytest.approx([12.0])


def test_tpot_counts_only_gaps_inside_the_window():
    rs = [req(0.0, [9.0, 10.0, 10.1, 10.3, 20.5]),   # in window: 3 tokens
          req(0.0, [15.0]),                          # one token: left out
          req(11.0, [12.0, 12.5, 13.0, 13.5])]       # finished inside
    assert records.tpots(rs, 10.0, 20.0) == pytest.approx([0.15, 0.5])


def test_itl_pools_every_gap_inside_the_window():
    rs = [req(0.0, [9.0, 10.0, 10.1, 10.3, 20.5]),   # two gaps inside
          req(0.0, [15.0]),                          # one token: no gap
          req(11.0, [12.0, 12.5, 13.0, 13.5])]       # three gaps
    assert sorted(records.itls(rs, 10.0, 20.0)) == \
        pytest.approx([0.1, 0.2, 0.5, 0.5, 0.5])


def test_itl_p95_shrugs_off_a_stall_that_moves_the_tpot_p90():
    """A 1 s stall in the middle of the window lengthens one gap of each
    of the 8 requests then decoding: their mean gaps pass the p90 over
    requests, while the pooled p95 over gaps stays at the step time."""
    def run(stall):
        rs = []
        for i in range(40):
            t0 = 10.0 + 0.2 * i
            ts = [t0 + 0.02 * k for k in range(50)]
            if i >= 32:   # decoding when the stall comes
                ts = [t + stall if t > ts[0] else t for t in ts]
            rs.append(req(t0, ts))
        return rs
    calm, stalled = run(0.0), run(1.0)
    tpot = [records.quantile(records.tpots(rs, 10.0, 30.0), 0.9)
            for rs in (calm, stalled)]
    itl = [records.quantile(records.itls(rs, 10.0, 30.0), 0.95)
           for rs in (calm, stalled)]
    assert tpot[1] > 1.5 * tpot[0]
    assert itl[1] == pytest.approx(itl[0])


@pytest.mark.parametrize("waited", [0.04, 0.06])
def test_itl_p50_and_p99_hold_where_the_p95_jumps(waited):
    """Gaps that waited on a prefill, 4% or 6% of them, put the pooled p95
    on one side of their edge or the other; the p50 reads the step and the
    p99 the wait."""
    from bench import harness
    rs = []
    for i in range(20):
        t, ts = 10.0 + 0.5 * i, []
        for k in range(50):
            t += 0.03 if (50 * i + k) % 100 < 100 * waited else 0.011
            ts.append(t)
        rs.append(req(10.0 + 0.5 * i, ts))
    run = types.SimpleNamespace(requests=rs, w0=10.0, w1=30.0)
    p50 = harness.load_reader("metrics", "itl_p50_s")(run)
    p95 = harness.load_reader("endtoend", "itl_p95_s")(run)
    p99 = harness.load_reader("metrics", "itl_p99_s")(run)
    assert p50 == pytest.approx(0.011) and p99 == pytest.approx(0.03)
    assert p95 == pytest.approx(0.011 if waited < 0.05 else 0.03)


def test_tokens_in_the_window_include_unfinished_requests():
    rs = [req(0.0, [9.0, 10.0, 10.1]), req(0.0, [19.9, 20.0, 20.1])]
    assert records.tokens_in(rs, 10.0, 20.0) == 4


def test_served_tokens_count_only_the_windows_own_requests():
    rs = [req(9.0, [9.5, 10.0, 10.1]),         # due before the window
          req(12.0, [12.1, 19.9, 20.0, 20.1]),  # due inside, unfinished
          req(20.0, [20.0])]                    # due at the window's end
    assert records.tokens_in(rs, 10.0, 20.0) == 6
    assert records.served_tokens_in(rs, 10.0, 20.0) == 3


def chat_at(tpot, ttft=0.025):
    """The chat mix's fixed trace, each request served at a fixed TTFT and
    a constant TPOT, with no batching; returns (requests, w0, w1)."""
    mix = traffic.load_mix(os.path.join(BENCH, "traffic", "chat.json"))
    rs = []
    for p in traffic.plan(mix, 51):
        r = req(p.due, [])
        r.token_times = [p.due + ttft + i * tpot
                         for i in range(p.output_len)]
        rs.append(r)
    return rs, mix["lead_in_s"], mix["lead_in_s"] + 51


TPOTS = [0.008, 0.012, 0.017, 0.022, 0.024, 0.030, 0.040]


@pytest.mark.parametrize("faster,slower", zip(TPOTS, TPOTS[1:]),
                         ids=lambda t: f"{1000 * t:g}ms")
def test_served_tokens_never_fall_as_decode_gets_faster(faster, slower):
    fast, slow = (records.served_tokens_in(*chat_at(t))
                  for t in (faster, slower))
    assert fast >= slow


def test_every_token_in_the_window_falls_as_decode_gets_faster():
    """The artifact ``served_tokens_in`` removes: the lead-in ends with two
    long answers, and a faster decode emits more of them before the window
    opens than its own requests gain at the window's end."""
    counts = {tpot: records.tokens_in(*chat_at(tpot))
              for tpot in (0.012, 0.022)}
    assert counts[0.012] < counts[0.022]


def test_quantile_interpolates():
    assert records.quantile([], 0.9) is None
    assert records.quantile([3.0], 0.9) == 3.0
    assert records.quantile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 0.9) == \
        pytest.approx(9.1)
    assert records.quantile([4, 1, 3, 2], 0.5) == pytest.approx(2.5)


def test_decode_steps_group_sequences_by_the_engine_stamp():
    a = req(0.0, [1.0, 2.0, 3.0], prompt_len=100)
    b = req(0.0, [1.5, 2.0, 3.0], prompt_len=7)
    steps = records.decode_steps([a, b])
    # first tokens come from prefills and start no decode step
    assert sorted(steps) == [2.0, 3.0]
    assert sorted(c for _, c in steps[2.0]) == [8, 101]
    assert sorted(c for _, c in steps[3.0]) == [9, 102]


def test_step_mfu_counts_steps_in_the_traced_window():
    from bench import arch
    m = {"arch": "dense", "num_layers": 1, "d_model": 4, "num_heads": 1,
         "num_kv_heads": 1, "head_dim": 4, "d_ff": 8, "vocab_size": 16, "activation": "gelu",
         "norm": "nonparam_ln", "tie_embeddings": True,
         "param_dtype": "bfloat16", "kv_dtype": "bfloat16"}
    a = req(0.0, [1.0, 2.0, 3.0], prompt_len=5)
    dev = types.SimpleNamespace(t0=1.5, t1=2.5, chips=1, window_s=1.0)
    run = types.SimpleNamespace(device=dev, requests=[a], model=m, chips=1,
                                peak={"flops_per_s": 1e3})
    want = arch.load("dense").decode_step(m, [6])[0]
    assert records.step_mfu(run) == pytest.approx(100 * want / 1e3)
    run.peak = None
    assert records.step_mfu(run) is None
