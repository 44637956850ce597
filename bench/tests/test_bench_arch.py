"""The per-family modules of ``bench/arch``: the dense family reproduces,
bit for bit, the weights, reference logits and counts that the benchmark
read before the modules existed, and a new family is taken from new files
alone."""
import hashlib
import json
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, check, harness, records, weights
from bench.tests.test_bench_reference import small

BENCH = os.path.join(os.path.dirname(__file__), "..")


def digest(tree) -> str:
    """The first 16 hex digits of a SHA-256 over every leaf's path, dtype,
    shape and bytes."""
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = np.asarray(x)
        h.update(f"{jax.tree_util.keystr(path)} {x.dtype} {x.shape};"
                 .encode())
        h.update(x.tobytes())
    return h.hexdigest()[:16]


# Read on the CPU from the tree before ``bench/arch`` existed, through the
# same calls at the same sizes (``small``; 512 positions of tokens drawn
# from ``default_rng(0)``, the weights of seed 3).
PARENT_WEIGHTS = {
    ("olmo-1b", 3): "83566ca132ded207",
    ("olmo-1b", 2**33 + 5): "bf2fc5dec42366cf",
    ("starcoder2-3b", 3): "c46ce6c0b0956940",
    ("starcoder2-3b", 2**33 + 5): "8e1890b51c11646c",
}
PARENT_LOGITS = {
    ("olmo-1b", False): "71c3263048345cc5",
    ("olmo-1b", True): "654b1ecadf25eaa3",
    ("starcoder2-3b", False): "4e2cbdacd48a7911",
    ("starcoder2-3b", True): "95a4284d8a3cad08",
}
# full width: decode over contexts [1, 100, 2048, 6000], prefills of 5000
# and of 64 tokens, each (FLOPs, bytes)
PARENT_COUNTS = {
    "olmo-1b": [(10482221056.0, 3422963712.0),
                (12376351965184.0, 3009090048.0),
                (137917628416.0, 2362118656.0)],
    "starcoder2-3b": [(26536845312.0, 6252926976.0),
                      (33241838911488.0, 6186196992.0),
                      (369496424448.0, 6062333952.0)],
}


@pytest.mark.parametrize("name,seed", sorted(PARENT_WEIGHTS))
def test_dense_weights_are_the_parents(name, seed):
    m = small(name)["model"]
    assert digest(weights.make_params(m, seed)) == \
        PARENT_WEIGHTS[name, seed]


@pytest.mark.parametrize("name,fp8", sorted(PARENT_LOGITS))
def test_dense_reference_logits_are_the_parents(name, fp8):
    m = small(name)["model"]
    a = arch.of(m)
    params = weights.make_params(m, 3)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, 512),
                       jnp.int32)
    lg = a.logits(m, params, a.hidden(m, params, toks, fp8), fp8)
    assert digest(lg) == PARENT_LOGITS[name, fp8]


@pytest.mark.parametrize("name", sorted(PARENT_COUNTS))
def test_dense_counts_are_the_parents(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        m = json.load(f)["model"]
    a = arch.of(m)
    assert [a.decode_step(m, [1, 100, 2048, 6000]), a.prefill(m, 5000),
            a.prefill(m, 64)] == PARENT_COUNTS[name]


def test_a_model_part_without_arch_is_an_error():
    m = dict(small("olmo-1b")["model"])
    del m["arch"]
    with pytest.raises(ValueError, match="names no arch"):
        arch.of(m)
    with pytest.raises(ValueError, match="no module"):
        arch.load("no_such_family")
    with pytest.raises(ValueError, match="not a module name"):
        arch.load("../dense")


RESIDUAL = 'x = x + linear(o, a["wo"], 2, fp8)'
RECORD = '''

CALLS = set()


def _recorded(f):
    def call(*args, **kwargs):
        CALLS.add(f.__name__)
        return f(*args, **kwargs)
    return call


(program_config, shapes, nest, hidden, logits, decode_step,
 prefill) = map(_recorded, (program_config, shapes, nest, hidden, logits,
                            decode_step, prefill))
'''


def test_a_new_family_needs_new_files_only(tmp_path, monkeypatch):
    """A family added as one file in the arch directory, with no other
    file touched, is what the harness, the check, the counts and
    ``step_mfu`` use: a dense copy whose attention residual is doubled."""
    src = open(os.path.join(arch.DIR, "dense.py")).read()
    assert RESIDUAL in src
    shutil.copy(os.path.join(arch.DIR, "dense.py"), tmp_path)
    (tmp_path / "toy.py").write_text(
        src.replace(RESIDUAL, 'x = x + 2 * linear(o, a["wo"], 2, fp8)')
        + RECORD)
    monkeypatch.setattr(arch, "DIR", str(tmp_path))
    toy = arch.load("toy")
    cfg = small("olmo-1b")
    cfg["model"]["arch"] = "toy"
    m = cfg["model"]
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    (tmp_path / "mix.json").write_text(json.dumps({
        "loop": "open", "arrival": "poisson", "rate_per_s": 4,
        "lead_in_s": 1.0, "drain_s": 5, "shape_seed": 0,
        "prompt": {"dist": "lognormal", "mean": 40, "cv": 1, "min": 8,
                   "max": 64, "grid": [16, 64]},
        "output": {"dist": "lognormal", "mean": 20, "cv": 1, "min": 8,
                   "max": 16}}))
    cell = harness.Cell("olmo-1b.chat", str(tmp_path / "cfg.json"),
                        str(tmp_path / "mix.json"), 7, require_tpu=False,
                        cache=False)
    cell.close()
    assert {"program_config", "shapes", "nest"} <= toy.CALLS

    dense = dict(m, arch="dense")
    params = weights.make_params(m, 7)
    assert digest(params) == digest(weights.make_params(dense, 7))
    prompt = [int(t) for t in np.random.default_rng(3).integers(0, 256, 20)]
    req = types.SimpleNamespace(req_id=0, prompt_len=20,
                                prompt_tokens=prompt,
                                output_tokens=list(range(1, 9)))
    ours = check.gaps(m, params, [req], 128, 8)
    assert {"hidden", "logits"} <= toy.CALLS
    assert ours != check.gaps(dense, params, [req], 128, 8)

    timed = types.SimpleNamespace(token_times=[1.0, 2.0, 3.0], prompt_len=5)
    run = types.SimpleNamespace(
        device=types.SimpleNamespace(t0=0.5, t1=3.5, chips=1, window_s=3.0),
        requests=[timed], model=m, chips=1, peak={"flops_per_s": 1e15})
    assert records.step_mfu(run) > 0
    assert {"decode_step", "prefill"} <= toy.CALLS
