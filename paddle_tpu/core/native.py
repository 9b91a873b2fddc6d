"""ctypes binding for the native runtime tier (native/*.cc ->
paddle_tpu/lib/libpaddle_tpu_native.so).

The native components mirror the reference's C++ runtime pieces kept native
per SURVEY §2.4: TCPStore (store/tcp_store.h), host tracer + chrome trace
(platform/profiler), allocator stats (phi/core/memory/stats.h), and the
shared-memory DataLoader transport (mmap_allocator.cc). If the .so is
missing we build it on first import (g++, ~2s); pure-Python fallbacks exist
for every component, so `available()` gates usage.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading

_LIB = None
_LOCK = threading.Lock()

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SO_PATH = os.path.join(_REPO_ROOT, "lib", "libpaddle_tpu_native.so")
_SRC_DIR = os.path.join(os.path.dirname(_REPO_ROOT), "native")


def _declare(lib):
    c = ctypes
    lib.pt_store_server_start.restype = c.c_void_p
    lib.pt_store_server_start.argtypes = [c.c_int]
    lib.pt_store_server_port.restype = c.c_int
    lib.pt_store_server_port.argtypes = [c.c_void_p]
    lib.pt_store_server_stop.argtypes = [c.c_void_p]
    lib.pt_store_client_connect.restype = c.c_void_p
    lib.pt_store_client_connect.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.pt_store_client_close.argtypes = [c.c_void_p]
    lib.pt_store_set.restype = c.c_int
    lib.pt_store_set.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p,
                                 c.c_int64]
    lib.pt_store_get.restype = c.c_int
    lib.pt_store_get.argtypes = [c.c_void_p, c.c_char_p,
                                 c.POINTER(c.c_char_p), c.POINTER(c.c_int64)]
    lib.pt_store_add.restype = c.c_int
    lib.pt_store_add.argtypes = [c.c_void_p, c.c_char_p, c.c_int64,
                                 c.POINTER(c.c_int64)]
    lib.pt_store_wait.restype = c.c_int
    lib.pt_store_wait.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.pt_store_check.restype = c.c_int
    lib.pt_store_check.argtypes = [c.c_void_p, c.c_char_p]
    lib.pt_store_delete.restype = c.c_int
    lib.pt_store_delete.argtypes = [c.c_void_p, c.c_char_p]
    lib.pt_free.argtypes = [c.c_void_p]

    lib.pt_trace_enable.argtypes = [c.c_int]
    lib.pt_trace_enabled.restype = c.c_int
    lib.pt_trace_event.argtypes = [c.c_char_p, c.c_char_p, c.c_int64,
                                   c.c_int64, c.c_int64]
    lib.pt_trace_count.restype = c.c_int64
    lib.pt_trace_dump_json.restype = c.c_int
    lib.pt_trace_dump_json.argtypes = [c.c_char_p, c.c_int]

    lib.pt_stats_alloc.argtypes = [c.c_int, c.c_int64]
    lib.pt_stats_free.argtypes = [c.c_int, c.c_int64]
    lib.pt_stats_allocated.restype = c.c_int64
    lib.pt_stats_allocated.argtypes = [c.c_int]
    lib.pt_stats_peak.restype = c.c_int64
    lib.pt_stats_peak.argtypes = [c.c_int]
    lib.pt_stats_alloc_count.restype = c.c_int64
    lib.pt_stats_alloc_count.argtypes = [c.c_int]
    lib.pt_stats_reset_peak.argtypes = [c.c_int]

    lib.pt_ring_create.restype = c.c_void_p
    lib.pt_ring_create.argtypes = [c.c_char_p, c.c_uint64]
    lib.pt_ring_open.restype = c.c_void_p
    lib.pt_ring_open.argtypes = [c.c_char_p]
    lib.pt_ring_push.restype = c.c_int
    lib.pt_ring_push.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64,
                                 c.c_int64]
    lib.pt_ring_pop.restype = c.c_int64
    lib.pt_ring_pop.argtypes = [c.c_void_p, c.c_void_p, c.c_uint64, c.c_int64]
    lib.pt_ring_close.argtypes = [c.c_void_p]
    lib.pt_ring_free.argtypes = [c.c_void_p]
    return lib


def _build():
    if not os.path.isdir(_SRC_DIR):
        return False
    try:
        subprocess.run(["make", "-C", _SRC_DIR], check=True,
                       capture_output=True, timeout=120)
        return os.path.exists(_SO_PATH)
    except (OSError, subprocess.SubprocessError) as e:
        # the pure-Python fallbacks take over, but not silently
        detail = getattr(e, "stderr", None) or b""
        sys.stderr.write(
            "paddle_tpu: native tier not built (make -C %s): %s\n%s"
            % (_SRC_DIR, e, detail.decode(errors="replace")[-2000:]))
        return False


def get_lib(allow_build: bool = True):
    """Load (building once with make if needed) the native library.
    ``allow_build=False`` only loads an already-built .so — used by
    read-only query paths that must not shell out to g++."""
    global _LIB
    with _LOCK:
        if _LIB is not None:
            return _LIB or None
        if not os.path.exists(_SO_PATH):
            if not allow_build:
                return None
            if not _build():
                _LIB = False
                return None
        try:
            _LIB = _declare(ctypes.CDLL(_SO_PATH))
        except AttributeError:
            if not allow_build:
                # stale .so, not allowed to rebuild here: do NOT poison the
                # cache — a later allow_build=True caller should rebuild
                return None
            # stale prebuilt .so missing a newer symbol: rebuild once
            # (unlink first so make relinks and dlopen loads fresh)
            try:
                os.unlink(_SO_PATH)
            except OSError:
                pass
            if _build():
                try:
                    _LIB = _declare(ctypes.CDLL(_SO_PATH))
                    return _LIB
                except (OSError, AttributeError):
                    pass
            _LIB = False
            return None
        except OSError:
            _LIB = False
            return None
        return _LIB


def available() -> bool:
    return get_lib() is not None


# ------------------------------------------------------------- store
class NativeStoreServer:
    def __init__(self, port: int):
        lib = get_lib()
        self._lib = lib
        self._h = lib.pt_store_server_start(port)
        if not self._h:
            raise OSError(f"native TCPStore cannot bind port {port}")
        self.port = lib.pt_store_server_port(self._h)

    def stop(self):
        if self._h:
            self._lib.pt_store_server_stop(self._h)
            self._h = None


class NativeStoreClient:
    def __init__(self, host: str, port: int, timeout: float):
        lib = get_lib()
        self._lib = lib
        self._h = lib.pt_store_client_connect(
            host.encode(), port, int(timeout * 1000))
        if not self._h:
            raise ConnectionError(
                f"cannot connect to TCPStore {host}:{port}")

    def set(self, key: bytes, value: bytes):
        if self._lib.pt_store_set(self._h, key, value, len(value)) != 0:
            raise ConnectionError("store set failed")

    def get(self, key: bytes) -> bytes:
        buf = ctypes.c_char_p()
        n = ctypes.c_int64()
        if self._lib.pt_store_get(self._h, key, ctypes.byref(buf),
                                  ctypes.byref(n)) != 0:
            raise ConnectionError("store get failed")
        try:
            return ctypes.string_at(buf, n.value)
        finally:
            self._lib.pt_free(buf)

    def add(self, key: bytes, delta: int) -> int:
        out = ctypes.c_int64()
        if self._lib.pt_store_add(self._h, key, delta,
                                  ctypes.byref(out)) != 0:
            raise ConnectionError("store add failed")
        return out.value

    def wait(self, key: bytes, timeout_ms: int) -> bool:
        r = self._lib.pt_store_wait(self._h, key, timeout_ms)
        if r < 0:
            raise ConnectionError("store wait failed")
        return r == 1

    def check(self, key: bytes) -> bool:
        r = self._lib.pt_store_check(self._h, key)
        if r < 0:
            raise ConnectionError("store check failed")
        return r == 1

    def delete(self, key: bytes) -> bool:
        r = self._lib.pt_store_delete(self._h, key)
        if r < 0:
            raise ConnectionError("store delete failed")
        return r == 1

    def close(self):
        if self._h:
            self._lib.pt_store_client_close(self._h)
            self._h = None


# ------------------------------------------------------------- tracer
def trace_enable(on: bool):
    lib = get_lib()
    if lib:
        lib.pt_trace_enable(1 if on else 0)


def trace_event(name: str, cat: str, start_ns: int, dur_ns: int, tid: int):
    lib = get_lib()
    if lib:
        lib.pt_trace_event(name.encode(), cat.encode(), start_ns, dur_ns, tid)


def trace_count() -> int:
    lib = get_lib()
    return lib.pt_trace_count() if lib else 0


def trace_clear():
    lib = get_lib()
    if lib:
        lib.pt_trace_clear()


def trace_dump_json(path: str, pid: int) -> bool:
    lib = get_lib()
    return bool(lib) and lib.pt_trace_dump_json(path.encode(), pid) == 0


# ------------------------------------------------------------- stats
def stats_alloc(dev: int, nbytes: int):
    lib = get_lib()
    if lib:
        lib.pt_stats_alloc(dev, nbytes)


def stats_free(dev: int, nbytes: int):
    lib = get_lib()
    if lib:
        lib.pt_stats_free(dev, nbytes)


def stats_allocated(dev: int) -> int:
    lib = get_lib(allow_build=False)
    return lib.pt_stats_allocated(dev) if lib else 0


def stats_peak(dev: int) -> int:
    lib = get_lib(allow_build=False)
    return lib.pt_stats_peak(dev) if lib else 0


def stats_reset_peak(dev: int):
    lib = get_lib()
    if lib:
        lib.pt_stats_reset_peak(dev)


# ------------------------------------------------------------- shm ring
class ShmRing:
    """Single-producer/single-consumer shared-memory ring buffer."""

    def __init__(self, name: str, capacity: int = 0, create: bool = False):
        lib = get_lib()
        if lib is None:
            raise OSError("native library unavailable")
        self._lib = lib
        self.name = name
        if create:
            self._h = lib.pt_ring_create(name.encode(), capacity)
        else:
            self._h = lib.pt_ring_open(name.encode())
        if not self._h:
            raise OSError(f"cannot {'create' if create else 'open'} "
                          f"shm ring {name}")

    def push(self, data: bytes, timeout: float = 60.0):
        r = self._lib.pt_ring_push(self._h, data, len(data),
                                   int(timeout * 1000))
        if r == -1:
            raise TimeoutError("shm ring push timed out")
        if r == -2:
            raise BrokenPipeError("shm ring closed")
        if r == -3:
            raise ValueError("message larger than ring capacity")

    def pop(self, timeout: float = 60.0) -> bytes:
        # phase 1: learn size
        n = self._lib.pt_ring_pop(self._h, None, 0, int(timeout * 1000))
        if n == -1:
            raise TimeoutError("shm ring pop timed out")
        if n == -2:
            raise BrokenPipeError("shm ring closed")
        buf = ctypes.create_string_buffer(n)
        m = self._lib.pt_ring_pop(self._h, buf, n, int(timeout * 1000))
        if m < 0:
            raise BrokenPipeError("shm ring closed mid-read")
        return buf.raw[:m]

    def close(self):
        if self._h:
            self._lib.pt_ring_close(self._h)

    def free(self):
        if self._h:
            self._lib.pt_ring_free(self._h)
            self._h = None
