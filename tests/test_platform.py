"""The one module that decides platform choices (repro.platform)."""
import pathlib

import jax
import pytest

from repro import platform
from repro.kernels import ops


def test_cpu_runs_kernels_interpreted_without_donation():
    assert jax.default_backend() == "cpu"
    assert platform.interpret_mode() is True
    assert ops.interpret_mode is platform.interpret_mode
    assert platform.donate_default() is False


def test_unknown_backend_is_an_error(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        platform.interpret_mode()


def test_compile_cache_follows_env(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(platform.CACHE_ENV, "/elsewhere/cache")
    assert platform.setup_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv(platform.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = platform.setup_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert pathlib.Path(path) == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
