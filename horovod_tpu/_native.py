"""ctypes binding for the native dynamic engine (native/engine.cc).

Loads ``horovod_tpu/lib/libhvd_core.so``, compiling it from ``native/`` on
demand when missing or stale (single g++ invocation, zero third-party
dependencies — the reference needs CMake + flatbuffers + boost for the same
components, ``/root/reference/horovod/CMakeLists.txt``).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_NATIVE_DIR = os.path.join(os.path.dirname(_PKG_DIR), "native")
_LIB_DIR = os.path.join(_PKG_DIR, "lib")
_LIB_PATH = os.path.join(_LIB_DIR, "libhvd_core.so")

_SOURCES = ("engine.cc", "timeline.cc")
_HEADERS = ("hvd_core.h", "message.h", "wire.h", "timeline.h")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None


class NativeBuildError(RuntimeError):
    """The engine sources could not be compiled (no g++, compile error)."""


def _needs_build() -> bool:
    if not os.path.exists(_LIB_PATH):
        return True
    so_mtime = os.path.getmtime(_LIB_PATH)
    for f in _SOURCES + _HEADERS:
        src = os.path.join(_NATIVE_DIR, f)
        if os.path.exists(src) and os.path.getmtime(src) > so_mtime:
            return True
    return False


def _build() -> None:
    srcs = [os.path.join(_NATIVE_DIR, s) for s in _SOURCES]
    missing = [s for s in srcs if not os.path.exists(s)]
    if missing:
        raise NativeBuildError(f"engine sources not found: {missing}")
    os.makedirs(_LIB_DIR, exist_ok=True)
    cxx = os.environ.get("CXX", "g++")
    tmp = _LIB_PATH + f".tmp.{os.getpid()}"
    cmd = [cxx, "-O2", "-fPIC", "-std=c++17", "-pthread", "-shared",
           *srcs, "-o", tmp]
    try:
        proc = subprocess.run(cmd, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(f"failed to run {cxx}: {e}") from e
    if proc.returncode != 0:
        raise NativeBuildError(
            f"native engine compile failed (rc={proc.returncode}):\n"
            f"{proc.stdout[-4000:]}")
    os.replace(tmp, _LIB_PATH)  # atomic: concurrent builders can't corrupt


def _declare(lib: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.hvd_engine_create.restype = ctypes.c_void_p
    lib.hvd_engine_create.argtypes = [
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_double, ctypes.c_double]
    lib.hvd_engine_destroy.argtypes = [ctypes.c_void_p]
    lib.hvd_engine_enqueue.restype = ctypes.c_int32
    lib.hvd_engine_enqueue.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int32, ctypes.c_int32, ctypes.c_double, ctypes.c_double,
        ctypes.c_int32]
    for name in ("hvd_engine_pop_requests", "hvd_engine_compute_responses",
                 "hvd_engine_cache_bits", "hvd_engine_stall_report"):
        fn = getattr(lib, name)
        fn.restype = ctypes.c_int32
        fn.argtypes = [ctypes.c_void_p, ctypes.POINTER(u8p),
                       ctypes.POINTER(ctypes.c_size_t)]
    lib.hvd_engine_ingest.restype = ctypes.c_int32
    lib.hvd_engine_ingest.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, u8p, ctypes.c_size_t]
    lib.hvd_engine_commit_cache_bits.restype = ctypes.c_int32
    lib.hvd_engine_commit_cache_bits.argtypes = [
        ctypes.c_void_p, u8p, ctypes.c_size_t]
    lib.hvd_engine_register_group.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32]
    lib.hvd_engine_abandon.restype = ctypes.c_int32
    lib.hvd_engine_abandon.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.hvd_engine_pending_count.restype = ctypes.c_int32
    lib.hvd_engine_pending_count.argtypes = [ctypes.c_void_p]
    lib.hvd_engine_cache_size.restype = ctypes.c_int32
    lib.hvd_engine_cache_size.argtypes = [ctypes.c_void_p]
    # coordinator ResponseCache gates
    lib.hvd_engine_cache_has.restype = ctypes.c_int32
    lib.hvd_engine_cache_has.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.hvd_engine_join_pending.restype = ctypes.c_int32
    lib.hvd_engine_join_pending.argtypes = [ctypes.c_void_p]
    lib.hvd_timeline_start.restype = ctypes.c_int32
    lib.hvd_timeline_start.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.hvd_timeline_stop.argtypes = [ctypes.c_void_p]
    lib.hvd_timeline_record.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int32,
        ctypes.c_int64]
    lib.hvd_core_version.restype = ctypes.c_char_p


def build_native(force: bool = False) -> str:
    """Build the native library from ``native/`` sources, returning the
    library path. ``force=True`` rebuilds unconditionally — used by the CI
    gate so a stale or foreign-arch binary can never be what ships."""
    if force or _needs_build():
        _build()
    return _LIB_PATH


def load() -> ctypes.CDLL:
    """Load (building if needed) the native engine library."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        if _needs_build():
            _build()
        lib = ctypes.CDLL(_LIB_PATH)
        _declare(lib)
        _lib = lib
        return _lib


def available() -> bool:
    """True when the native engine can be loaded (or built)."""
    try:
        load()
        return True
    except (NativeBuildError, OSError):
        return False


def version() -> str:
    return load().hvd_core_version().decode()
