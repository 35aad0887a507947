"""ctypes bindings for the native runtime
(`analytics_zoo_tpu/native/src/*.cpp`).

The reference ships native code as JNI `.so`s in `zoo-core-dist-all`
(SURVEY.md §2.11); here the C++ ships as package data (`native/src/`) and is
built on first use with g++ (no pybind11 in the image — plain C ABI +
ctypes). The library is built from the shipped sources or not used: a
binary older than its sources is rebuilt, never loaded. Every
consumer has a pure-Python fallback for where a toolchain is missing.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger("analytics_zoo_tpu")

# sources ship as package data (src/); the .so is built next to them
# on first use, so pip-installed copies work without a build step
_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "src")
_SO_PATH = os.path.join(_NATIVE_DIR, "libzoo_native.so")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False


_SRCS = [os.path.join(_NATIVE_DIR, f)
         for f in ("host_arena.cpp", "serving_queue.cpp",
                   "serving_http.cpp")]


def _build() -> bool:
    """Compile the sources into ``_SO_PATH``; atomic (tmp + rename),
    so a concurrent process never loads a half-written binary."""
    tmp = f"{_SO_PATH}.tmp.{os.getpid()}"
    cmd = ["g++", "-O2", "-fPIC", "-std=c++17", "-shared", "-o",
           tmp] + _SRCS + ["-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True,
                       timeout=120)
        os.replace(tmp, _SO_PATH)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        logger.warning("native: build failed (%s: %s) %s",
                       type(e).__name__, e,
                       getattr(e, "stderr", b"") or b"")
        if os.path.exists(tmp):
            os.unlink(tmp)
        return False


def _stale() -> bool:
    """No binary, or one older than any of its sources."""
    if not os.path.exists(_SO_PATH):
        return True
    built = os.path.getmtime(_SO_PATH)
    return any(os.path.getmtime(src) > built for src in _SRCS)


def load_native() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if
    unavailable. A binary older than its sources is never loaded: it
    is rebuilt, and if that fails the library is not used."""
    global _lib, _build_failed
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        if _stale() and not _build():
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError:
            _build_failed = True
            return None
        # signatures
        lib.arena_create.restype = ctypes.c_void_p
        lib.arena_create.argtypes = [ctypes.c_size_t]
        lib.arena_destroy.argtypes = [ctypes.c_void_p]
        lib.arena_alloc.restype = ctypes.c_size_t
        lib.arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                    ctypes.c_size_t]
        lib.arena_base.restype = ctypes.c_void_p
        lib.arena_base.argtypes = [ctypes.c_void_p]
        lib.arena_used.restype = ctypes.c_size_t
        lib.arena_used.argtypes = [ctypes.c_void_p]
        lib.arena_capacity.restype = ctypes.c_size_t
        lib.arena_capacity.argtypes = [ctypes.c_void_p]
        lib.arena_reset.argtypes = [ctypes.c_void_p]
        lib.arena_copy.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                   ctypes.c_void_p, ctypes.c_size_t]
        lib.squeue_create.restype = ctypes.c_void_p
        lib.squeue_destroy.argtypes = [ctypes.c_void_p]
        lib.squeue_put.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.squeue_take.restype = ctypes.c_int
        lib.squeue_take.argtypes = [ctypes.c_void_p, ctypes.c_long]
        lib.squeue_size.restype = ctypes.c_int
        lib.squeue_size.argtypes = [ctypes.c_void_p]
        lib.zoo_http_create.restype = ctypes.c_void_p
        lib.zoo_http_create.argtypes = [ctypes.c_int, ctypes.c_long]
        lib.zoo_http_port.restype = ctypes.c_int
        lib.zoo_http_port.argtypes = [ctypes.c_void_p]
        lib.zoo_http_set_health.argtypes = [ctypes.c_void_p,
                                            ctypes.c_char_p]
        lib.zoo_http_next.restype = ctypes.c_long
        lib.zoo_http_next.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_long,
            ctypes.c_long, ctypes.POINTER(ctypes.c_long),
            ctypes.c_char_p, ctypes.c_long]
        lib.zoo_http_respond.restype = ctypes.c_int
        lib.zoo_http_respond.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_long]
        lib.zoo_http_respond_hdr.restype = ctypes.c_int
        lib.zoo_http_respond_hdr.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_long, ctypes.c_char_p]
        lib.zoo_http_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


class HostArena:
    """Bump-arena sample cache (PersistentMemoryAllocator analog).

    `put(array) -> offset`; `view(offset, shape, dtype)` returns a
    zero-copy numpy view into arena memory.
    """

    def __init__(self, capacity_bytes: int):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._handle = lib.arena_create(capacity_bytes)
        if not self._handle:
            raise MemoryError(f"arena_create({capacity_bytes}) failed")
        self.capacity = capacity_bytes

    def put(self, arr: np.ndarray) -> int:
        arr = np.ascontiguousarray(arr)
        off = self._lib.arena_alloc(self._handle, arr.nbytes, 64)
        if off == ctypes.c_size_t(-1).value:
            raise MemoryError("arena full")
        self._lib.arena_copy(self._handle, off,
                             arr.ctypes.data_as(ctypes.c_void_p),
                             arr.nbytes)
        return off

    def view(self, offset: int, shape, dtype) -> np.ndarray:
        base = self._lib.arena_base(self._handle)
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        buf = (ctypes.c_char * nbytes).from_address(base + offset)
        return np.frombuffer(buf, dtype=dtype).reshape(shape)

    @property
    def used(self) -> int:
        return self._lib.arena_used(self._handle)

    def reset(self):
        self._lib.arena_reset(self._handle)

    def close(self):
        if self._handle:
            self._lib.arena_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class ServingQueue:
    """Blocking pool of slot ids (LinkedBlockingQueue analog)."""

    def __init__(self):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._handle = lib.squeue_create()

    def put(self, slot: int):
        self._lib.squeue_put(self._handle, slot)

    def take(self, timeout_ms: int = -1) -> int:
        """Returns a slot id, or -1 on timeout."""
        return self._lib.squeue_take(self._handle, timeout_ms)

    def size(self) -> int:
        return self._lib.squeue_size(self._handle)

    def close(self):
        if self._handle:
            self._lib.squeue_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class PyServingQueue:
    """Pure-Python fallback with the same surface."""

    def __init__(self):
        import queue
        self._q = queue.Queue()

    def put(self, slot: int):
        self._q.put(slot)

    def take(self, timeout_ms: int = -1) -> int:
        import queue as _queue
        try:
            timeout = None if timeout_ms < 0 else timeout_ms / 1000.0
            return self._q.get(timeout=timeout)
        except _queue.Empty:
            return -1

    def size(self) -> int:
        return self._q.qsize()

    def close(self):
        pass


def make_serving_queue():
    try:
        return ServingQueue()
    except RuntimeError:
        return PyServingQueue()


class NativeHttpServer:
    """C++ HTTP front-end (`src/serving_http.cpp`): accept/parse/queue
    run native (no GIL contention with the compute thread); Python
    pulls request bytes and posts response bytes."""

    def __init__(self, port: int = 0, max_body: int = 16 << 20):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._max_body = max_body
        self._handle = lib.zoo_http_create(port, max_body)
        if not self._handle:
            raise OSError(f"zoo_http_create({port}) failed")
        self._port = lib.zoo_http_port(self._handle)
        self._tls = threading.local()  # per-thread request buffers

    @property
    def port(self) -> int:
        return self._port

    def set_health(self, payload_json: str):
        if self._handle:
            self._lib.zoo_http_set_health(self._handle,
                                          payload_json.encode())

    def next_request(self, timeout_ms: int = -1):
        """Returns (req_id, path, body_bytes, trace_id_or_None), or
        None on timeout, or raises StopIteration after close().
        ``trace_id`` is the request's X-Zoo-Trace-Id header when the
        C++ side captured one (it rides the path buffer after a
        ``\\n``).
        Buffers are per-THREAD (reused across polls — no 16MB alloc
        churn), so concurrent worker pulls never share a buffer."""
        if not self._handle:
            raise StopIteration
        if not hasattr(self._tls, "buf"):
            self._tls.buf = ctypes.create_string_buffer(self._max_body)
            self._tls.path = ctypes.create_string_buffer(1024)
        buf, path = self._tls.buf, self._tls.path
        rid = ctypes.c_long()
        n = self._lib.zoo_http_next(
            self._handle, buf, len(buf), timeout_ms,
            ctypes.byref(rid), path, len(path))
        if n == -1:
            return None
        if n == -2:
            raise StopIteration
        route, _, trace = path.value.decode().partition("\n")
        return rid.value, route, buf.raw[:n], trace or None

    def respond(self, req_id: int, status: int, body: bytes,
                trace_id: "Optional[str]" = None) -> bool:
        if not self._handle:
            return False
        if trace_id:
            return self._lib.zoo_http_respond_hdr(
                self._handle, req_id, status, body, len(body),
                trace_id.encode()) == 0
        return self._lib.zoo_http_respond(
            self._handle, req_id, status, body, len(body)) == 0

    def close(self):
        if self._handle:
            self._lib.zoo_http_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
