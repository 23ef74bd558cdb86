"""The one place that decides platform-dependent choices.

Every function here is lazy: importing this module never touches jax
device state (launchers that force host devices must set ``XLA_FLAGS``
before the backend initializes).

* :func:`interpret_mode` — Pallas kernels compile to Mosaic on a TPU and run
  in the Pallas interpreter on the CPU (tests, tiny rehearsals). Any other
  backend is an error, never a silent interpreter fallback.
* :func:`donate_default` — buffer donation is on off-CPU (CPU jax warns and
  copies on donation).
* :func:`setup_compile_cache` — JAX's persistent compilation cache. Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
  set here; otherwise the cache lives in the fixed ``.jax_cache/`` at the
  checkout root (the path is part of the cache key, so it never moves).
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: src/repro/platform.py -> checkout root
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def interpret_mode() -> bool:
    """True on the CPU (Pallas interpreter), False on a TPU (Mosaic)."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here target the TPU (or the CPU interpreter); "
        f"backend {backend!r} has neither")


def donate_default() -> bool:
    """Whether jitted steps donate their carried buffers by default."""
    return jax.default_backend() != "cpu"


def setup_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
