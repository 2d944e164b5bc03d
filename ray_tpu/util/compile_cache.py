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
