"""Entry-point contracts that need no chip: ``chip_smoke.py`` refuses to
run anywhere but on a TPU, the compile cache stays where it is put, and
the scaling bench never spawns children from a process that holds an
accelerator."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


def _run_smoke(script: Path, tmp_path: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, str(script)], env=env,
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=120)


def test_chip_smoke_refuses_cpu(tmp_path):
    proc = _run_smoke(ROOT / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_fails(tmp_path):
    """Without the repository beside it the script cannot import the
    engine, and must not print a result."""
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", alone)
    proc = _run_smoke(alone, tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defers_to_env(monkeypatch, cache_config, tmp_path):
    from repro.compile_cache import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    cache_config):
    from repro.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert enable_compile_cache() == first


def test_run_scaling_refuses_an_accelerator_parent(monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))
    from benchmarks import serve_bench
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the tpu devices"):
        serve_bench.run_scaling()
