"""Compile-on-first-import of the native PS/embedding-cache library.

The reference ships prebuilt ``libps.so`` / ``hetu_cache`` modules via cmake
(CMakeLists.txt:19-31); here the single-file C++ core is compiled lazily with
g++ into the package directory and loaded with ctypes (the image has no
pybind11 — see ``src/python_binding.cc:8-151`` for the reference's C-ABI
precedent).
"""
import ctypes
import os
import subprocess

from ..obs.lock_witness import make_lock as _make_lock

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "native", "ps_store.cc")

_lock = _make_lock("ps.build._lock")
_lib = None


def _so_path():
    """The library's path carries a hash of the source it was built from:
    a stale ``.so`` (left by an older checkout, or carried along when the
    tree is copied to another machine, where mtimes mean nothing) has
    another name and is never loaded."""
    import hashlib
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, "native", f"libhetu_ps.{digest}.so")


def _compile(so):
    """Compile to a temp name then atomically rename, under a cross-process
    file lock, so concurrent importers never dlopen a half-written .so."""
    import fcntl
    lock_path = so + ".lock"
    with open(lock_path, "w") as lk:
        fcntl.flock(lk, fcntl.LOCK_EX)
        try:
            if os.path.exists(so):
                return  # another process built it while we waited
            tmp = f"{so}.tmp.{os.getpid()}"
            cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                   "-pthread", _SRC, "-o", tmp]
            subprocess.run(cmd, check=True, capture_output=True)
            os.rename(tmp, so)
        finally:
            fcntl.flock(lk, fcntl.LOCK_UN)


def get_lib():
    """Load (building it from the committed source if this source was
    never built here) the native library; None — with a warning — if it
    cannot be built.  Callers that must not run on the numpy store check
    :func:`hetu_tpu.ps.store_kind`."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        try:
            so = _so_path()
            if not os.path.exists(so):
                _compile(so)
            lib = ctypes.CDLL(so)
        except (OSError, subprocess.CalledProcessError) as e:
            import warnings
            warnings.warn(f"hetu_tpu.ps: native core unavailable ({e}); "
                          "falling back to the slow numpy store")
            return None
        c = ctypes
        P, F, I, L, U = (c.c_void_p, c.c_float, c.c_int, c.c_int64, c.c_uint64)
        FP = c.POINTER(c.c_float)
        LP = c.POINTER(c.c_int64)
        sigs = {
            "hetu_ps_create": (P, []),
            "hetu_ps_destroy": (None, [P]),
            "hetu_ps_init_table": (L, [P, L, I, I, F, F, F, F, U, F]),
            "hetu_ps_set_data": (None, [P, L, FP]),
            "hetu_ps_get_data": (None, [P, L, FP]),
            "hetu_ps_rows": (L, [P, L]),
            "hetu_ps_width": (I, [P, L]),
            "hetu_ps_pull": (None, [P, L, LP, L, FP]),
            "hetu_ps_push": (None, [P, L, LP, L, FP, F]),
            "hetu_ps_push_pull": (None, [P, L, LP, L, FP, F, LP, L, FP]),
            "hetu_ps_dense_push": (None, [P, L, FP, F]),
            "hetu_ps_versions": (None, [P, L, LP, L, LP]),
            "hetu_ps_save": (I, [P, L, c.c_char_p]),
            "hetu_ps_load": (I, [P, L, c.c_char_p]),
            "hetu_ps_ssp_init": (None, [P, I]),
            "hetu_ps_clock": (None, [P, I]),
            "hetu_ps_clock_value": (L, [P, I]),
            "hetu_ps_ssp_sync": (I, [P, I, I, I]),
            "hetu_cache_create": (P, [P, L, L, I, L, L]),
            "hetu_cache_destroy": (None, [P]),
            "hetu_cache_set_bounds": (None, [P, L, L]),
            "hetu_cache_bypass": (None, [P, I]),
            "hetu_cache_size": (L, [P]),
            "hetu_cache_lookup": (None, [P, LP, L, FP]),
            "hetu_cache_update": (None, [P, LP, L, FP]),
            "hetu_cache_push_pull": (None, [P, LP, L, FP, LP, L, FP]),
            "hetu_cache_flush": (None, [P]),
            "hetu_cache_perf": (None, [P, LP]),
        }
        for name, (res, args) in sigs.items():
            fn = getattr(lib, name)
            fn.restype = res
            fn.argtypes = args
        _lib = lib
        return _lib


def store_kind():
    """Which store serves embedding tables in this process: ``"native"``
    (the C++ core) or ``"numpy"`` (the slow fallback ``get_lib`` warned
    about).  A measurement says which one it ran on."""
    return "native" if get_lib() is not None else "numpy"

