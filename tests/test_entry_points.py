"""Entry points: ``chip_smoke.py`` refuses to run without a TPU, and the
persistent compilation cache goes where ``use_compile_cache`` says."""
import importlib.util
import pathlib
import sys

import jax
import pytest

from repro.launch.compile_cache import use_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_chip_smoke_fails_without_a_tpu(monkeypatch, capsys, argv):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"] + argv)
    with pytest.raises(SystemExit) as exited:
        smoke.main()
    assert exited.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = str(ROOT / ".jax_cache")
    assert use_compile_cache() == path
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path,
                                           restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets nothing
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir
