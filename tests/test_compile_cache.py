"""Where the entry points put JAX's persistent compilation cache."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import place_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore the process-wide cache setting whatever the test did."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_exported_dir_is_honoured_and_nothing_is_set(monkeypatch, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert place_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_under_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = place_compile_cache()
    assert first == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert place_compile_cache() == first


def test_importing_the_package_sets_no_cache_dir():
    """Library import must leave the cache alone (entry points opt in)."""
    import subprocess
    import sys

    code = ("import jax, repro.serving, repro.core.pipeline, "
            "repro.launch.compile_cache; "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        check=True, timeout=120,
        env={"PATH": "", "PYTHONPATH": str(CHECKOUT / "src"),
             "JAX_PLATFORMS": "cpu"},
    )
    assert out.stdout.strip() == "None"
