"""RealEngine integration: determinism across scheduling modes + Table-4
behaviour (dynamic PD slashes TTFT under backlog, same outputs)."""
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.sharding import unbox
from repro.models import build_model
from repro.serving import engine as E
from repro.serving.engine import RealEngine
from repro.serving.request import Request, RequestState


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("olmo-1b").reduced()
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def mk_requests(cfg, n=6, prompt=12, out=8, gap=0.01):
    return [Request(prompt_len=prompt, max_new_tokens=out,
                    prompt_tokens=np.random.default_rng(s).integers(
                        0, cfg.vocab_size, prompt).tolist(),
                    arrival_time=s * gap)
            for s in range(n)]


def reference_outputs(cfg, model, params, reqs, max_len=64):
    import jax.numpy as jnp
    outs = []
    for r in reqs:
        cache = model.init_cache(1, max_len)
        toks = np.asarray(r.prompt_tokens, np.int32)[None]
        lg, cache, _ = model.prefill(params, {"tokens": toks}, cache)
        seq = [int(np.argmax(np.asarray(lg[0])))]
        L = r.prompt_len
        for _ in range(r.max_new_tokens - 1):
            lg, cache = model.decode(params, jnp.asarray([seq[-1]], jnp.int32),
                                     cache, jnp.asarray([L], jnp.int32))
            seq.append(int(np.argmax(np.asarray(lg[0]))))
            L += 1
        outs.append(seq)
    return outs


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["passthrough", "static_colocate",
                                  "dynamic_pd"])
def test_engine_matches_reference(setup, mode):
    cfg, model, params = setup
    reqs = mk_requests(cfg)
    ref = reference_outputs(cfg, model, params, reqs)
    eng = RealEngine(model, params, mode=mode, max_num_seqs=2, max_len=64)
    try:
        res = eng.run(reqs, timeout=300)
    finally:
        eng.shutdown()
    assert res["completed"] == len(reqs)
    assert [r.output_tokens for r in reqs] == ref
    # metrics sanity
    assert res["ttft_mean_s"] > 0 and res["tpot_mean_s"] > 0


@pytest.mark.slow
@pytest.mark.timing
def test_dynamic_pd_improves_ttft_under_backlog(setup):
    """Table 4's qualitative claim on the REAL engine: with a deep backlog,
    dynamic PD co-location yields far lower TTFT than static co-location at
    similar throughput.  Wall-clock thresholds scale with FLEX_TIMING_SLACK
    (the ``timing`` marker: false-fails under CPU contention otherwise)."""
    from conftest import timing_slack
    slack = timing_slack()
    cfg, model, params = setup
    results = {}
    # short prompts + long outputs: decode occupancy (not prefill cost) is
    # what blocks waiting requests under static admission gating
    for mode in ["static_colocate", "dynamic_pd"]:
        reqs = mk_requests(cfg, n=6, prompt=8, out=32, gap=0.0)  # burst
        eng = RealEngine(model, params, mode=mode, max_num_seqs=2, max_len=64)
        try:
            results[mode] = (eng.run(reqs, timeout=300),
                             [r.ttft for r in reqs])
        finally:
            eng.shutdown()
    static_ttft = results["static_colocate"][0]["ttft_mean_s"]
    dyn_ttft = results["dynamic_pd"][0]["ttft_mean_s"]
    assert dyn_ttft < static_ttft * min(0.95, 0.8 * slack), \
        (dyn_ttft, static_ttft, slack)
    # throughput comparable (within 40% on noisy CPU timing)
    st_tp = results["static_colocate"][0]["output_tokens_per_s"]
    dy_tp = results["dynamic_pd"][0]["output_tokens_per_s"]
    assert dy_tp > 0.6 / slack * st_tp, (dy_tp, st_tp, slack)


@pytest.mark.parametrize("mode", ["passthrough", "dynamic_pd"])
def test_failing_decode_step_fails_its_requests(setup, monkeypatch, mode):
    """A decode step that errors ends its requests FAILED with finish_time
    stamped, and run() returns instead of waiting for its timeout."""
    def broken(*args):
        raise RuntimeError("decode fault")

    monkeypatch.setattr(E, "_decode_step", broken)
    cfg, model, params = setup
    reqs = mk_requests(cfg, n=3, gap=0.0)
    eng = RealEngine(model, params, mode=mode, max_num_seqs=2, max_len=64)
    try:
        res = eng.run(reqs, timeout=60)
    finally:
        eng.shutdown()
    assert res["failed"] == len(reqs)
    assert all(r.state == RequestState.FAILED and r.finish_time > 0
               for r in reqs)


def _fault(logits):
    raise RuntimeError("decode fault inside the step")


@functools.partial(jax.jit, static_argnums=0, donate_argnums=3)
def _failing_step(model, params, toks, cache, lens):
    """The engine's decode step, failing while it runs: after its cache
    was donated."""
    logits, new = model.decode(params, toks, cache, lens)
    return jax.pure_callback(_fault, logits, logits), new


@pytest.mark.parametrize("mode", ["passthrough", "dynamic_pd"])
def test_decode_step_failing_after_donation_keeps_the_replica_serving(
        setup, monkeypatch, mode):
    """A decode step that fails inside the jitted call has consumed the
    slot cache; its requests end FAILED and the replica serves the next
    requests on a fresh cache, with the reference's tokens."""
    given, real = [], E._decode_step

    def first_fails(model, params, toks, cache, lens):
        given.append(cache)
        step = _failing_step if len(given) == 1 else real
        return step(model, params, toks, cache, lens)

    monkeypatch.setattr(E, "_decode_step", first_fails)
    cfg, model, params = setup
    eng = RealEngine(model, params, mode=mode, max_num_seqs=2, max_len=64)
    try:
        first = eng.run(mk_requests(cfg, n=2, gap=0.0), timeout=60)
        reqs = mk_requests(cfg, n=3)
        res = eng.run(reqs, timeout=120)
    finally:
        eng.shutdown()
    assert first["failed"] >= 1
    assert all(x.is_deleted() for x in jax.tree.leaves(given[0]))
    assert res["completed"] == len(reqs)
    assert [r.output_tokens for r in reqs] == \
        reference_outputs(cfg, model, params, reqs)


def _devices_of(tree):
    return set().union(*(x.devices() for x in jax.tree.leaves(tree)))


@pytest.mark.parametrize("mode,replicas", [("dynamic_pd", 4), ("disagg", 2)])
def test_replicas_bind_to_distinct_devices(setup, monkeypatch, mode,
                                           replicas):
    """On four devices, replicas=4 keeps each replica's weights and slot
    cache on its own device, and disagg lands the prefilled KV on the decode
    device; the tokens equal one-device passthrough's."""
    cfg, model, params = setup
    assert len(jax.devices()) >= 4      # conftest gives the CPU four
    landed = []
    unpack = E._unpack_cache

    def spy(blob, treedef, spec, device):
        cache = unpack(blob, treedef, spec, device)
        landed.append((device, _devices_of(cache)))
        return cache

    monkeypatch.setattr(E, "_unpack_cache", spy)
    outputs, placement = {}, None
    for m, r in (("passthrough", 1), (mode, replicas)):
        reqs = mk_requests(cfg, n=8, gap=0.0)
        eng = RealEngine(model, params, mode=m, replicas=r, max_num_seqs=2,
                         max_len=64)
        try:
            res = eng.run(reqs, timeout=300)
            placement = [(rep.chip_p, rep.chip_d, _devices_of(rep.params_p),
                          _devices_of(rep.params_d),
                          _devices_of(rep.slot_cache))
                         for rep in eng.replicas]
        finally:
            eng.shutdown()
        assert res["completed"] == len(reqs)
        outputs[m] = [q.output_tokens for q in reqs]
    assert outputs[mode] == outputs["passthrough"]
    assert len({c for p, d, *_ in placement for c in (p, d)}) == 4
    for chip_p, chip_d, params_p, params_d, slot_cache in placement:
        assert params_p == {chip_p}
        assert params_d == slot_cache == {chip_d}
    if mode == "disagg":
        decode_chips = {d for _, d, *_ in placement}
        assert len(landed) == 8
        assert all(dev in decode_chips and devs == {dev}
                   for dev, devs in landed)
