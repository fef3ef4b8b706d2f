"""Build/load the native runtime shared library.

Compiles ``csrc/dear_runtime.cpp`` with the system C++ toolchain on first
use (no pybind11 in this environment — plain C ABI + ctypes) and caches the
.so next to the package. Thread-safe; a failure is recorded in
`load_error` and `load()` returns None — `runtime.pipeline.Pipeline` then
raises (callers wanting numpy construct `NumpyPipeline` themselves).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_load_error: Optional[str] = None

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "csrc", "dear_runtime.cpp",
)
_BUILD_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_build")


class Segment(ctypes.Structure):
    """Mirror of the C Segment struct (csrc/dear_runtime.cpp)."""

    _fields_ = [
        ("offset", ctypes.c_uint64),
        ("count", ctypes.c_uint64),
        ("kind", ctypes.c_int32),
        ("p0", ctypes.c_double),
        ("p1", ctypes.c_double),
    ]


KIND_NORMAL_F32 = 0
KIND_UNIFORM_I32 = 1
KIND_CONST_I32 = 2
KIND_UNIFORM_F32 = 3
KIND_BERNOULLI_MASKED_I32 = 4


def _compile(force: bool = False) -> Optional[str]:
    global _load_error
    if not os.path.exists(_SRC):
        _load_error = f"source missing: {_SRC}"
        return None
    os.makedirs(_BUILD_DIR, exist_ok=True)
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:12]
    so = os.path.join(_BUILD_DIR, f"dear_runtime_{tag}.so")
    if os.path.exists(so) and not force:
        # a cached .so that failed to load (e.g. prebuilt against a newer
        # glibc than this container ships) is worse than none: force=True
        # recompiles with the local toolchain; the os.replace below
        # atomically supersedes the stale binary only once the rebuild
        # succeeded, so a failed rebuild never destroys the artifact
        return so
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
           _SRC, "-o", so + ".tmp"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(so + ".tmp", so)
        return so
    except (OSError, subprocess.SubprocessError) as exc:
        _load_error = f"compile failed: {exc}"
        return None


def _is_loader_mismatch(exc: OSError) -> bool:
    """A dlopen failure caused by the cached binary, not by our code — a
    stale prebuilt .so linked against a different libc/libstdc++ than the
    running system (e.g. ``version `GLIBC_2.34' not found`` on a glibc
    2.31 container). Recoverable by recompiling from source."""
    s = str(exc)
    return ("GLIBC" in s or "GLIBCXX" in s or "version `" in s
            or "invalid ELF header" in s or "wrong ELF class" in s)


def load_error() -> Optional[str]:
    """Why the native library is unavailable (None when it loaded, or was
    never attempted). `tests/test_runtime.py::test_native_library_builds`
    skips (instead of failing) when this reports an environmental loader
    mismatch that the local toolchain couldn't rebuild past."""
    return _load_error


def _dlopen(so: str) -> Optional[ctypes.CDLL]:
    """CDLL with stale-binary recovery: a loader mismatch on the cached
    .so triggers one forced recompile with the local toolchain; any
    remaining failure is recorded in `load_error` instead of crashing
    the import path."""
    global _load_error
    try:
        return ctypes.CDLL(so)
    except OSError as exc:
        if not _is_loader_mismatch(exc):
            _load_error = f"dlopen failed: {exc}"
            return None
        rebuilt = _compile(force=True)
        if rebuilt is None:
            _load_error = (_load_error
                           or f"loader mismatch, rebuild failed: {exc}")
            return None
        try:
            lib = ctypes.CDLL(rebuilt)
        except OSError as exc2:
            _load_error = f"loader mismatch persists after rebuild: {exc2}"
            return None
        _load_error = None
        return lib


def load() -> Optional[ctypes.CDLL]:
    """The native library, or None if unbuildable (see `load_error`)."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        so = _compile()
        if so is None:
            return None
        lib = _dlopen(so)
        if lib is None:
            return None
        lib.dear_now_ns.restype = ctypes.c_uint64
        lib.dear_pipeline_create.restype = ctypes.c_void_p
        lib.dear_pipeline_create.argtypes = [
            ctypes.c_uint64, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
            ctypes.POINTER(Segment), ctypes.c_int,
        ]
        lib.dear_pipeline_acquire.restype = ctypes.c_int
        lib.dear_pipeline_acquire.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
        ]
        lib.dear_pipeline_release.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.dear_pipeline_produced.restype = ctypes.c_uint64
        lib.dear_pipeline_produced.argtypes = [ctypes.c_void_p]
        lib.dear_pipeline_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib
