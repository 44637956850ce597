"""Everything ``BENCHMARK.json`` names is found by name and loads, and the
harness refuses to run without a TPU."""
import json
import os
import re
import subprocess
import sys

import jax
import pytest

from bench import arch, harness, traffic, weights
from repro.distributed.sharding import unbox
from repro.models import build_model

BENCH = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


CONFIG_FILES = sorted(os.listdir(os.path.join(BENCH, "configs")))
MIX_FILES = sorted(os.listdir(os.path.join(BENCH, "traffic")))


@pytest.mark.parametrize("path", CONFIG_FILES,
                         ids=lambda p: p.rsplit(".", 1)[0])
def test_configuration_files_load_and_build(path):
    data = harness.load_json(os.path.join(BENCH, "configs", path))
    assert path == f"{data['name']}.json"
    for cfg in SPEC["configs"]:
        if cfg["name"] == data["name"]:
            assert cfg["file"] == f"bench/configs/{path}"
            assert data["source"] == cfg["source"]
            assert sorted(cfg["reduced"]) == sorted(data["reduced"])
    # the family's module lays the weights out as the program's model
    # does, at full width (shapes only: nothing is made)
    m = data["model"]
    assert os.path.isfile(os.path.join(arch.DIR, f"{m['arch']}.py"))
    a = arch.of(m)
    model = build_model(a.program_config(data))
    weights.check_layout(weights.layout(m), jax.eval_shape(
        lambda: unbox(model.init(jax.random.PRNGKey(0)))))
    assert 0 < data["check"]["logit_gap_limit"]


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cells_name_a_config_and_a_mix_file(cell):
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    mix = traffic.load_mix(os.path.join(BENCH, "traffic",
                                        f"{cell['traffic']}.json"))
    assert traffic.plan(mix, SPEC["run_seconds"])
    assert cell["chips"] in (1, 4)


@pytest.mark.parametrize("path", MIX_FILES, ids=lambda p: p.rsplit(".", 1)[0])
def test_every_mix_file_plans_a_run(path):
    """Mixes kept for later cells load and plan a whole run too."""
    mix = traffic.load_mix(os.path.join(BENCH, "traffic", path))
    planned = traffic.plan(mix, SPEC["run_seconds"])
    grid = traffic.prompt_grid(mix)
    assert planned and all(p.prompt_len in grid for p in planned)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_readers_load_by_name(metric):
    kind = "endtoend" if metric in SPEC["end_to_end"] else "metrics"
    assert callable(harness.load_reader(kind, metric["name"]))
    assert NAME.match(metric["name"])
    assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", metric["unit"])
    for w in metric.get("workloads", []):
        assert w in {c["name"] for c in SPEC["workloads"]}


def test_every_cell_reports_what_its_metrics_move():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        for cell in m.get("workloads", [c["name"] for c in
                                        SPEC["workloads"]]):
            moved = e2e[m["moves"]]
            assert cell in moved.get("workloads", [cell]), (m["name"], cell)


def test_the_compile_cache_is_the_programs(monkeypatch, tmp_path):
    """The harness keeps JAX's cache where the program does: the given
    ``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``."""
    import jax
    from repro.launch import compile_cache
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert harness.use_compile_cache(jax) == str(tmp_path)
    assert updates == {"jax_persistent_cache_min_compile_time_secs": 0}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert harness.use_compile_cache(jax) == os.path.join(ROOT, ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == os.path.join(
        ROOT, ".jax_cache")
    assert str(compile_cache.CHECKOUT) == ROOT


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
