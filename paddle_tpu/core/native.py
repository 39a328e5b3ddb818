"""ctypes bindings to the native runtime (csrc/ → libpaddle_tpu.so).

The native layer provides the framework runtime the reference implements in
C++ (SURVEY.md §2.1/§2.3): flags registry (platform/flags.cc), stat monitor
(platform/monitor.h), host arena allocator (memory/allocation/
auto_growth_best_fit_allocator.cc), DataLoader queues/collate
(fluid/reader.py native queues), and the ProgramDesc graph IR
(framework/framework.proto).

Build model: compile-on-first-use with a file lock (like the reference's
cpp_extension JIT path), cached in csrc/build/. `load()` returns the
ctypes.CDLL or raises NativeUnavailable; all wrappers degrade gracefully so
pure-Python paths keep working where the toolchain is absent.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
_SO = os.path.join(_CSRC, "build", "libpaddle_tpu.so")

_lock = threading.Lock()
_lib = None
_load_error: Exception | None = None


class NativeUnavailable(RuntimeError):
    pass


def _sources():
    return [os.path.join(_CSRC, f) for f in
            ("common.h", "graph_ir.h", "flags.cc", "stats.cc", "memory.cc",
             "io.cc", "graph.cc", "executor.cc")]


def _stale() -> bool:
    if not os.path.exists(_SO):
        return True
    so_mtime = os.path.getmtime(_SO)
    return any(os.path.getmtime(s) > so_mtime for s in _sources()
               if os.path.exists(s))


def _build() -> None:
    os.makedirs(os.path.dirname(_SO), exist_ok=True)
    lockfile = _SO + ".lock"
    # cross-process guard (pytest-xdist / DataLoader workers)
    import fcntl
    with open(lockfile, "w") as lf:
        fcntl.flock(lf, fcntl.LOCK_EX)
        try:
            if not _stale():
                return
            srcs = [s for s in _sources() if s.endswith(".cc")]
            cmd = ["g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-Wall",
                   "-pthread", "-o", _SO] + srcs
            subprocess.run(cmd, check=True, capture_output=True, text=True,
                           cwd=_CSRC)
        finally:
            fcntl.flock(lf, fcntl.LOCK_UN)


EXEC_CALLBACK = ctypes.CFUNCTYPE(None, ctypes.c_int32, ctypes.c_void_p)


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    i32, i64, f64 = c.c_int32, c.c_int64, c.c_double
    p, cp = c.c_void_p, c.c_char_p

    def sig(name, restype, argtypes):
        fn = getattr(lib, name)
        fn.restype = restype
        fn.argtypes = argtypes

    sig("pt_last_error", cp, [])
    sig("pt_last_error_code", i32, [])
    sig("pt_flag_define", i32, [cp, i32, cp, cp])
    sig("pt_flag_set", i32, [cp, cp])
    sig("pt_flag_get", cp, [cp])
    sig("pt_flag_type", i32, [cp])
    sig("pt_flag_list", cp, [])
    sig("pt_stat_add", None, [cp, i64])
    sig("pt_stat_get", i64, [cp])
    sig("pt_stat_list", cp, [])
    sig("pt_arena_create", p, [i64])
    sig("pt_arena_destroy", None, [p])
    sig("pt_arena_alloc", p, [p, i64])
    sig("pt_arena_free", i32, [p, p])
    sig("pt_arena_stats", i32, [p, c.POINTER(i64), c.POINTER(i64),
                                c.POINTER(i64)])
    sig("pt_queue_create", p, [i64])
    sig("pt_queue_destroy", None, [p])
    sig("pt_queue_push", i32, [p, p, i64, i64, i64])
    sig("pt_queue_pop", i32, [p, c.POINTER(p), c.POINTER(i64),
                              c.POINTER(i64), i64])
    sig("pt_queue_close", None, [p])
    sig("pt_queue_size", i64, [p])
    sig("pt_collate_stack", i32, [p, c.POINTER(p), i64, i64])
    sig("pt_prog_create", p, [])
    sig("pt_prog_destroy", None, [p])
    sig("pt_prog_add_block", i32, [p, i32])
    sig("pt_prog_num_blocks", i32, [p])
    sig("pt_block_add_var", i32, [p, i32, cp, i32, c.POINTER(i64), i32, i32])
    sig("pt_block_add_op", i32, [p, i32, cp])
    sig("pt_op_add_input", i32, [p, i32, i32, cp, cp])
    sig("pt_op_add_output", i32, [p, i32, i32, cp, cp])
    sig("pt_op_set_attr_int", i32, [p, i32, i32, cp, i64])
    sig("pt_op_set_attr_bool", i32, [p, i32, i32, cp, i32])
    sig("pt_op_set_attr_float", i32, [p, i32, i32, cp, f64])
    sig("pt_op_set_attr_str", i32, [p, i32, i32, cp, cp])
    sig("pt_op_set_attr_ints", i32, [p, i32, i32, cp, c.POINTER(i64), i32])
    sig("pt_op_set_attr_floats", i32, [p, i32, i32, cp, c.POINTER(f64), i32])
    sig("pt_block_num_ops", i32, [p, i32])
    sig("pt_block_num_vars", i32, [p, i32])
    sig("pt_block_topo_order", i32, [p, i32, c.POINTER(i32)])
    sig("pt_prog_dce", i32, [p, i32, cp])
    sig("pt_prog_serialize", i64, [p, c.c_char_p, i64])
    sig("pt_prog_deserialize", p, [c.c_char_p, i64])
    sig("pt_prog_to_json", i64, [p, c.c_char_p, i64])
    sig("pt_exec_create", p, [i32])
    sig("pt_exec_destroy", None, [p])
    sig("pt_exec_run", i32, [p, p, i32, EXEC_CALLBACK, p])
    sig("pt_exec_levels", i32, [p, i32, c.POINTER(i32), i32])


def load() -> ctypes.CDLL:
    """Load (building if needed) the native runtime library."""
    global _lib, _load_error
    if _lib is not None:
        return _lib
    if _load_error is not None:
        raise NativeUnavailable(str(_load_error)) from _load_error
    with _lock:
        if _lib is not None:
            return _lib
        try:
            if _stale():
                _build()
            lib = ctypes.CDLL(_SO)
            _declare(lib)
            _lib = lib
            return _lib
        except Exception as e:  # toolchain absent / build failure
            _load_error = e
            raise NativeUnavailable(str(e)) from e


def available() -> bool:
    try:
        load()
        return True
    except NativeUnavailable:
        return False


def try_load() -> ctypes.CDLL | None:
    """load() with graceful degradation: None when the toolchain is absent.
    May block on first call to compile csrc/ — call at session setup, not on
    hot paths; hot paths should consult a cached result."""
    try:
        return load()
    except NativeUnavailable:
        return None


def check(rc, lib=None):
    """Raise the typed enforce exception from native thread-local error state
    (csrc ErrorCode -> framework.errors taxonomy, error_codes.proto parity)."""
    if rc is None or (isinstance(rc, int) and rc < 0):
        lib = lib or _lib
        from ..framework.errors import raise_from_code
        if lib is None:
            raise_from_code(0, "paddle_tpu native: native error")
        msg = lib.pt_last_error().decode()
        code = int(lib.pt_last_error_code())
        raise_from_code(code, f"paddle_tpu native: {msg}")
    return rc


class HostArena:
    """Python handle over the native slab arena (csrc/memory.cc pt_arena_* —
    the host-side analog of memory/allocation/buddy_allocator). Used for
    pinned host staging buffers; stats feed paddle.device.memory_stats()."""

    def __init__(self, slab_bytes=1 << 22):
        self._lib = load()
        self._h = self._lib.pt_arena_create(slab_bytes)

    def alloc(self, nbytes):
        return self._lib.pt_arena_alloc(self._h, nbytes)

    def free(self, ptr):
        return self._lib.pt_arena_free(self._h, ptr)

    def stats(self):
        import ctypes as c
        in_use = c.c_int64()
        peak = c.c_int64()
        slabs = c.c_int64()
        self._lib.pt_arena_stats(self._h, c.byref(in_use), c.byref(peak),
                                 c.byref(slabs))
        return in_use.value, peak.value, slabs.value

    def __del__(self):
        try:
            self._lib.pt_arena_destroy(self._h)
        except Exception:
            pass


_default_arena = None


def default_arena():
    """Lazily-created process-wide host arena, or None when the native
    runtime is unavailable."""
    global _default_arena
    if _default_arena is None:
        try:
            _default_arena = HostArena()
        except NativeUnavailable:
            return None
    return _default_arena
