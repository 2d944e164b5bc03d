"""Where JAX's persistent compilation cache lives.

Every machine the chip tool hands out starts with no compiled code, and a
cache entry is only found again under the directory it was written to — so
the directory is either the one the environment names or one fixed path in
the checkout, never one built from a session directory, a pid or a
tempfile.
"""

from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def ensure_compile_cache() -> str:
    """Place the compilation cache and return its directory.

    For every process that compiles for the chip (a ``tpu`` worker before
    its first use of jax, ``tools/chip_kernels.py``) and for nothing else.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself —
    workers inherit it through their raylet — and this sets nothing."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR


def compile_cache_dir():
    """The directory this process keeps jax's compilation cache in, as
    `ensure_compile_cache` or the environment placed it; None in a process
    that keeps none (a test, a tool that compiles for a described chip).
    What is remembered BESIDE the compiled programs (`models/layers.py`:
    the plan a recomputed stack was seen to fit under) goes below it and
    nowhere else: no cache, nothing remembered."""
    import jax

    return jax.config.jax_compilation_cache_dir or None
