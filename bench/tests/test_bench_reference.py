"""The plain reference against the program, and the checks that decide
``correct`` against a timed path broken underneath.

These check the reference and the comparison, not the yardstick's numbers:
they run at ``.reduced()``-like widths on the CPU."""
import functools
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, check, harness, reference, weights

BENCH = os.path.join(os.path.dirname(__file__), "..")


def small(name, window=16):
    """A configuration file of ``bench/configs`` cut to CPU size."""
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    m = cfg["model"]
    m.update(num_layers=2, d_model=128, num_heads=4,
             num_kv_heads=min(4, m["num_kv_heads"]), head_dim=32, d_ff=256,
             vocab_size=256)
    if m["sliding_window"]:
        m["sliding_window"] = window
    cfg["engine"].update(max_len=128)
    cfg["check"].update(sample_tokens=96)
    return cfg


def program_logits(cfg, params, toks):
    from repro.models import build_model
    model = build_model(arch.of(cfg["model"]).program_config(cfg))
    x, _ = model.forward(params, {"tokens": toks[None]})
    return np.asarray(model._logits(params, x)[0])


@pytest.mark.parametrize("name", ["olmo-1b", "starcoder2-3b"])
def test_reference_agrees_with_the_program(name):
    cfg = small(name)
    m = cfg["model"]
    params = weights.make_params(m, 3)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, 512),
                       jnp.int32)
    a = arch.of(m)
    ours = np.asarray(a.logits(m, params, a.hidden(m, params, toks)))
    theirs = program_logits(cfg, params, toks)
    # the program computes in bfloat16: 2^-8 relative per rounding, over
    # two layers; logits here have a standard deviation near 1
    assert np.abs(ours - theirs).max() < 0.15
    assert (ours.argmax(1) == theirs.argmax(1)).mean() > 0.9


@pytest.mark.parametrize("name", ["olmo-1b", "starcoder2-3b"])
def test_fp8_control_reads_far_above_the_program(name):
    """The control at a size a test run holds: the fp8 reference's widest
    gap over 512 positions is many times the program's."""
    cfg = small(name)
    m = cfg["model"]
    params = weights.make_params(m, 5)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, 512),
                       jnp.int32)
    a = arch.of(m)
    lr = np.asarray(a.logits(m, params, a.hidden(m, params, toks)))
    lc = np.asarray(a.logits(m, params, a.hidden(m, params, toks, fp8=True),
                             fp8=True))
    lp = program_logits(cfg, params, toks)

    def widest(pick):
        return float((lr.max(1) - np.take_along_axis(
            lr, pick[:, None], 1)[:, 0]).max())
    assert widest(lc.argmax(1)) > 3 * widest(lp.argmax(1))
    limit = cfg["check"]["logit_gap_limit"]
    assert check.passes(check.judge(widest(lp.argmax(1)), 0, 0, limit))
    assert not check.passes(check.judge(widest(lc.argmax(1)), 0, 0, limit))


def test_gap_functions_read_the_served_positions():
    cfg = small("olmo-1b")
    m = cfg["model"]
    params = weights.make_params(m, 2)
    prompt = list(np.random.default_rng(2).integers(0, 256, 20))
    toks = jnp.asarray(prompt + [0] * (512 - 20), jnp.int32)
    a = arch.of(m)
    lr = np.asarray(a.logits(m, params, a.hidden(m, params, toks)))
    best = int(lr[19].argmax())
    served = np.zeros(8, np.int32)
    served[0] = best
    fn, _ = reference.gap_fns(m, 8)
    g = np.asarray(fn(params, toks, np.int32(19), jnp.asarray(served)))
    assert g[0] == pytest.approx(0.0, abs=1e-6)
    served[0] = int(lr[19].argmin())
    g = np.asarray(fn(params, toks, np.int32(19), jnp.asarray(served)))
    assert g[0] == pytest.approx(lr[19].max() - lr[19].min(), rel=1e-5)


def test_sample_keeps_the_longest_and_is_drawn_from_the_seed():
    reqs = [types.SimpleNamespace(req_id=i, prompt_len=10 * i,
                                  output_tokens=[0] * 5) for i in range(1, 30)]
    a = check.sample(reqs, 4, 12, 8)
    assert a[0].req_id == 29 and len(a) == 3
    assert [r.req_id for r in a] == [r.req_id for r in
                                     check.sample(reqs, 4, 12, 8)]
    assert len(check.sample(reqs, 4, 1000, 8)) == 8


# --------------------------------------------------------------- faults
def run_small(tmp_path, monkeypatch, decode=None):
    """One whole run of a CPU-sized cell, with no look for a chip."""
    from bench.run import harness_run
    cfg = small("olmo-1b")
    # a light load and a lead-in: the sample holds only requests that ran
    # to their own end, and a loaded CPU decodes slowly
    mix = {"loop": "open", "arrival": "poisson", "rate_per_s": 4,
           "lead_in_s": 1.5, "drain_s": 5, "shape_seed": 0,
           "prompt": {"dist": "lognormal", "mean": 40, "cv": 1, "min": 8,
                      "max": 64, "grid": [16, 64]},
           "output": {"dist": "lognormal", "mean": 20, "cv": 1, "min": 16,
                      "max": 32}}
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    (tmp_path / "mix.json").write_text(json.dumps(mix))
    if decode is not None:
        from repro.serving import engine
        monkeypatch.setattr(engine, "_decode_step", decode(
            engine._decode_step))
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    args = types.SimpleNamespace(seed=2**33 + 3, seconds=3.0, trace=0)
    return harness_run(harness, args, {"name": "olmo-1b.chat", "chips": 1},
                       str(tmp_path / "cfg.json"), str(tmp_path / "mix.json"),
                       bench["end_to_end"], bench["per_layer"],
                       require_tpu=False, cache=False)


def test_a_sound_run_is_correct(tmp_path, monkeypatch):
    res = run_small(tmp_path, monkeypatch)
    assert res["correct"], res["compared"]
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"ttft_p50_s", "ttft_p90_s", "itl_p95_s",
                                   "served_tokens_per_s", "setup_s"}


def altered_token(step):
    """A decode step whose tokens are altered where they are produced:
    the logits shifted by one along the vocabulary."""
    def broken(model, params, toks, cache, lens):
        logits, new = step(model, params, toks, cache, lens)
        return jnp.roll(logits, 1, axis=-1), new
    return broken


def state_unchanged(step):
    """A decode step that returns the cache it was given, jitted with the
    cache donated as the step is, so that the engine can go on using it."""
    @functools.partial(jax.jit, static_argnums=0, donate_argnums=3)
    def broken(model, params, toks, cache, lens):
        logits, _ = step(model, params, toks, cache, lens)
        return logits, cache
    return broken


def test_control_readings_fail_the_runs_comparison(tmp_path):
    """``bench/control.py``'s path on a CPU-sized run: the program's
    reading passes the run's comparison, the fp8 control's does not."""
    from bench import control
    cfg = small("olmo-1b")
    cfg["check"].update(sample_tokens=400, sample_requests=8)
    # fp8 rounding grows with width: its widest gap over a run's sample
    # reads 0.27-0.38 at d_model 128 and 0.43-0.52 at 256 (CPU), against
    # 0.75-0.98 at the cell's 2048 on the chip
    cfg["model"].update(d_model=256, head_dim=64, d_ff=512)
    # a light load and a long window: the sample holds only requests that
    # ran to their own end, and a loaded CPU decodes slowly
    mix = {"loop": "open", "arrival": "poisson", "rate_per_s": 4,
           "lead_in_s": 1.0, "drain_s": 5, "shape_seed": 0,
           "prompt": {"dist": "lognormal", "mean": 40, "cv": 1, "min": 8,
                      "max": 64, "grid": [16, 64]},
           "output": {"dist": "lognormal", "mean": 48, "cv": 0.3,
                      "min": 32, "max": 64}}
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    (tmp_path / "mix.json").write_text(json.dumps(mix))
    seed = 2**33 + 5
    cell = harness.Cell("olmo-1b.chat", str(tmp_path / "cfg.json"),
                        str(tmp_path / "mix.json"), seed, require_tpu=False,
                        cache=False)
    cell.warm_up()
    reqs, w0, w1, _, _, _ = harness.drive(cell, 4.0)
    stopped = harness.stop(cell, reqs)
    params = cell.params
    out = control.readings(cfg, cfg["model"], mix, params, reqs, w0, w1,
                           stopped, seed)
    cell.close()
    assert out["served_tokens"] >= 200
    assert out["program_correct"], out["program_compared"]
    assert not out["control_correct"], out["control_compared"]


@pytest.mark.parametrize("fault", [altered_token, state_unchanged])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    res = run_small(tmp_path, monkeypatch, decode=fault)
    assert res["correct"] is False
    assert res["compared"]["failed_requests"]["value"] == 0
    assert res["compared"]["logit_gap"]["value"] > \
        res["compared"]["logit_gap"]["limit"]
