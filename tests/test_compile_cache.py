"""Where `enable_compile_cache` puts JAX's persistent compilation cache."""
from __future__ import annotations

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_places_the_cache(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_is_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.enable_compile_cache() == root / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_importing_the_package_sets_no_cache():
    """Only entry points place the cache; a library import changes nothing."""
    import subprocess
    import sys

    code = ("import jax, repro, repro.core.pipeline, repro.serving.emvs_stream;"
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, check=True,
        env={"PYTHONPATH": str(Path(__file__).resolve().parents[1] / "src"),
             "JAX_PLATFORMS": "cpu", "PATH": ""})
    assert out.stdout.strip() == "None"
