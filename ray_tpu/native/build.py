"""Lazy build of the native components.

Shared libraries are compiled on first use (and cached next to the
sources, with the hash of the source they were built from: a copy of the
tree resets mtimes, so only content says whether a binary lying there is
the committed source's).  We deliberately avoid setuptools here: the native
runtime has no Python-API dependency (pure ``extern "C"`` + ctypes), so a
single g++ invocation per library suffices and works in hermetic
environments.

Two callers with different failure policies share this module:

  * the shm object store (``lib_path("store")``) — a hard dependency of
    the data plane; build failures propagate as ``NativeBuildError``.
  * the frame codec (``lib_path("codec")``) — a pure optimization of the
    control plane; ``try_lib_path`` returns None (with a one-time warning)
    so callers fall back to the pure-Python codec when g++ is absent.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()

# name -> (source file under src/, output .so)
_LIBS = {
    "store": ("object_store.cc", "librt_store.so"),
    "codec": ("frame_codec.cc", "librt_codec.so"),
}

_warned: set = set()


class NativeBuildError(RuntimeError):
    pass


def _source_hash(src: str) -> str:
    with open(src, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _built_from(lib: str) -> str:
    """Hash of the source ``lib`` was built from ('' when unrecorded)."""
    try:
        with open(lib + ".src-sha256") as f:
            return f.read().strip()
    except OSError:
        return ""


def _build(src: str, lib: str):
    # Per-pid temp name: two processes racing to build must not scribble
    # over each other's half-written .so (os.replace keeps the swap atomic).
    tmp = f"{lib}.tmp{os.getpid()}"
    digest = _source_hash(src)
    cmd = [
        "g++", "-std=c++17", "-O3", "-fPIC", "-shared", "-pthread",
        "-o", tmp, src,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True)
    except (FileNotFoundError, OSError) as e:
        raise NativeBuildError(f"native build failed ({e}): {' '.join(cmd)}")
    if proc.returncode != 0:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise NativeBuildError(
            f"native build failed: {' '.join(cmd)}\n{proc.stderr}"
        )
    os.replace(tmp, lib)
    with open(tmp, "w") as f:
        f.write(digest + "\n")
    os.replace(tmp, lib + ".src-sha256")


def lib_path(name: str = "store") -> str:
    """Return path to the named native library, building if stale/missing.

    Raises ``NativeBuildError`` when the compiler is unavailable or the
    build fails.
    """
    try:
        src_name, lib_name = _LIBS[name]
    except KeyError:
        raise NativeBuildError(f"unknown native library {name!r}") from None
    src = os.path.join(_DIR, "src", src_name)
    lib = os.path.join(_DIR, lib_name)
    with _lock:
        if (not os.path.exists(lib)
                or _built_from(lib) != _source_hash(src)):
            _build(src, lib)
    return lib


def try_lib_path(name: str) -> "str | None":
    """``lib_path`` that degrades to None (warn once) instead of raising —
    for native components with a pure-Python fallback."""
    try:
        return lib_path(name)
    except NativeBuildError as e:
        if name not in _warned:
            _warned.add(name)
            sys.stderr.write(
                f"[ray_tpu] native {name} library unavailable, using "
                f"pure-Python fallback: {e}\n")
        return None
