"""Native runtime components (C++), loaded via ctypes.

The reference implements its runtime substrate in C++ (TCPStore
paddle/phi/core/distributed/store/tcp_store.h, shared-memory dataloader
queues, HostEventRecorder paddle/fluid/platform/profiler/).  This package
builds `libpaddle_tpu_native.so` from src/*.cc at first import (g++, cached
by source hash) and exposes:

- TCPStoreServer / TCPStoreClient — rendezvous bootstrap store
- ShmRing — process-shared ring buffer (DataLoader worker transport)
- HostEventRecorder — low-overhead profiler span buffer

If the build fails (no compiler, a compile error) the attribute `AVAILABLE`
is False, `BUILD_ERROR` says why, and callers fall back to pure-Python
equivalents.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import random
import subprocess
import threading
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src")

AVAILABLE = False
BUILD_ERROR: str | None = None  # why AVAILABLE is False, when a build failed
_lib = None


def _build() -> str | None:
    srcs = sorted(
        os.path.join(_SRC, f) for f in os.listdir(_SRC) if f.endswith(".cc")
    )
    h = hashlib.sha256()
    for s in srcs:
        h.update(open(s, "rb").read())
    tag = h.hexdigest()[:16]
    cache_dir = os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu")
    os.makedirs(cache_dir, exist_ok=True)
    out = os.path.join(cache_dir, f"libpaddle_tpu_native-{tag}.so")
    if os.path.exists(out):
        return out
    tmp = f"{out}.{os.getpid()}.tmp"  # per-process name: concurrent first
    # builds (multi-rank launch) must not interleave writes to one file
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread", *srcs, "-o", tmp, "-lrt"]
    global BUILD_ERROR
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        stderr = getattr(e, "stderr", None) or b""
        BUILD_ERROR = (f"{type(e).__name__}: {e} "
                       f"{stderr.decode(errors='replace')[-400:]}").strip()
        return None
    os.replace(tmp, out)
    return out


def _load():
    global _lib, AVAILABLE
    path = _build()
    if path is None:
        return
    lib = ctypes.CDLL(path)
    c = ctypes
    lib.pts_server_start.restype = c.c_void_p
    lib.pts_server_start.argtypes = [c.c_int, c.POINTER(c.c_int)]
    lib.pts_server_stop.argtypes = [c.c_void_p]
    lib.pts_client_connect.restype = c.c_void_p
    lib.pts_client_connect.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.pts_client_close.argtypes = [c.c_void_p]
    lib.pts_set.restype = c.c_int
    lib.pts_set.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p, c.c_uint32]
    lib.pts_get.restype = c.c_int64
    lib.pts_get.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p, c.c_uint32, c.c_int64]
    lib.pts_add.restype = c.c_int64
    lib.pts_add.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]

    lib.ptr_ring_create.restype = c.c_void_p
    lib.ptr_ring_create.argtypes = [c.c_char_p, c.c_uint64]
    lib.ptr_ring_attach.restype = c.c_void_p
    lib.ptr_ring_attach.argtypes = [c.c_char_p]
    lib.ptr_ring_push.restype = c.c_int
    lib.ptr_ring_push.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64, c.c_int]
    lib.ptr_ring_pop.restype = c.c_int64
    lib.ptr_ring_pop.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64, c.c_int]
    lib.ptr_ring_next_size.restype = c.c_uint64
    lib.ptr_ring_next_size.argtypes = [c.c_void_p]
    lib.ptr_ring_close.argtypes = [c.c_void_p]
    lib.ptr_ring_destroy.argtypes = [c.c_void_p]

    lib.phe_create.restype = c.c_void_p
    lib.phe_destroy.argtypes = [c.c_void_p]
    lib.phe_now_ns.restype = c.c_uint64
    lib.phe_intern.restype = c.c_uint32
    lib.phe_intern.argtypes = [c.c_void_p, c.c_char_p]
    lib.phe_record.argtypes = [c.c_void_p, c.c_uint32, c.c_uint64, c.c_uint64, c.c_uint64]
    lib.phe_count.restype = c.c_uint64
    lib.phe_count.argtypes = [c.c_void_p]
    lib.phe_dump.restype = c.c_uint64
    lib.phe_dump.argtypes = [
        c.c_void_p,
        c.POINTER(c.c_uint32),
        c.POINTER(c.c_uint64),
        c.POINTER(c.c_uint64),
        c.POINTER(c.c_uint64),
        c.c_uint64,
        c.c_int,
    ]
    lib.phe_name.restype = c.c_uint32
    lib.phe_name.argtypes = [c.c_void_p, c.c_uint32, c.c_char_p, c.c_uint32]
    _lib = lib
    AVAILABLE = True


_load()


def _retry_until(deadline, attempt_fn, fail_msg, base_s=0.02, cap_s=0.5):
    """Run `attempt_fn` until it returns a truthy handle or `deadline`
    (time.monotonic seconds) passes, sleeping capped-exponential-backoff
    with jitter between attempts.  Startup races — a worker outracing the
    server's bind, or a ring consumer attaching before the producer's
    shm_open — are ordinary under load, so first-refusal failure is the
    wrong contract for constructors; a deadline is."""
    delay = base_s
    while True:
        h = attempt_fn()
        if h:
            return h
        if time.monotonic() >= deadline:
            raise ConnectionError(fail_msg)
        # full jitter: concurrent workers spread their retries instead of
        # stampeding the just-started server in lockstep
        time.sleep(random.uniform(0, min(delay, cap_s)))
        delay *= 2


class TCPStoreServer:
    def __init__(self, port=0):
        p = ctypes.c_int(0)
        self._h = _lib.pts_server_start(port, ctypes.byref(p))
        if not self._h:
            raise OSError(f"TCPStore server failed to bind port {port}")
        self.port = p.value

    def stop(self):
        if self._h:
            _lib.pts_server_stop(self._h)
            self._h = None

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass


class TCPStoreClient:
    """Reference TCPStore client API: set/get/add/wait (tcp_store.h:121)."""

    def __init__(self, host="127.0.0.1", port=0, timeout_ms=30000):
        # Retry with backoff until timeout_ms instead of failing on the
        # first refusal: each attempt uses a FRESH socket (a connect() that
        # failed can leave the fd in an unusable state, so retrying inside
        # one pts_client_connect call is weaker than reconnecting), with a
        # short per-attempt timeout so the deadline stays shared.
        deadline = time.monotonic() + timeout_ms / 1000.0
        attempt_ms = max(1, min(200, int(timeout_ms)))
        self._h = _retry_until(
            deadline,
            lambda: _lib.pts_client_connect(host.encode(), port, attempt_ms),
            f"cannot reach TCPStore at {host}:{port} "
            f"within {timeout_ms}ms")
        self._lock = threading.Lock()

    def set(self, key: str, value: bytes):
        if _lib.pts_set(self._h, key.encode(), value, len(value)) != 0:
            raise OSError("TCPStore set failed")

    def get(self, key: str, timeout_ms=30000) -> bytes:
        cap = 1 << 20
        buf = ctypes.create_string_buffer(cap)
        n = _lib.pts_get(self._h, key.encode(), buf, cap, timeout_ms)
        if n == -2:
            raise TimeoutError(f"TCPStore get('{key}') timed out")
        if n < 0:
            raise OSError("TCPStore get failed")
        if n > cap:
            buf = ctypes.create_string_buffer(int(n))
            n = _lib.pts_get(self._h, key.encode(), buf, int(n), timeout_ms)
        return buf.raw[: int(n)]

    def add(self, key: str, delta: int) -> int:
        v = _lib.pts_add(self._h, key.encode(), delta)
        if v == -(2**63):
            raise OSError("TCPStore add failed")
        return int(v)

    def wait(self, keys, timeout_ms=30000):
        """Block until EVERY key exists, under ONE shared deadline.

        `timeout_ms` bounds the whole call, not each key: each get() is
        given only the remaining budget, and an exhausted budget raises
        TimeoutError immediately (the server treats a non-positive
        timeout as wait-forever, so it must never be forwarded)."""
        deadline = time.monotonic() + timeout_ms / 1000.0
        for k in keys if isinstance(keys, (list, tuple)) else [keys]:
            remaining_ms = int((deadline - time.monotonic()) * 1000)
            if remaining_ms <= 0:
                raise TimeoutError(
                    f"TCPStore wait timed out after {timeout_ms}ms with "
                    f"key '{k}' (and possibly later ones) still unset")
            self.get(k, remaining_ms)

    def close(self):
        if self._h:
            _lib.pts_client_close(self._h)
            self._h = None


class ShmRing:
    def __init__(self, name: str, capacity: int = 64 << 20, create=True,
                 attach_timeout_ms: int = 0):
        """attach_timeout_ms (attach side only): retry a failed attach
        with capped exponential backoff until the deadline — a consumer
        process routinely outraces the producer's shm_open under load.
        0 keeps the historical fail-on-first-refusal behavior."""
        self.name = name
        if create:
            self._h = _lib.ptr_ring_create(name.encode(), capacity)
        elif attach_timeout_ms > 0:
            deadline = time.monotonic() + attach_timeout_ms / 1000.0
            self._h = _retry_until(
                deadline,
                lambda: _lib.ptr_ring_attach(name.encode()),
                f"shm ring attach failed: {name} "
                f"(not created within {attach_timeout_ms}ms)")
        else:
            self._h = _lib.ptr_ring_attach(name.encode())
        if not self._h:
            raise OSError(f"shm ring {'create' if create else 'attach'} failed: {name}")

    def push(self, data: bytes, timeout_ms=-1):
        rc = _lib.ptr_ring_push(self._h, data, len(data), timeout_ms)
        if rc == -1:
            raise BrokenPipeError("ring closed")
        if rc == -2:
            raise TimeoutError("ring push timed out")
        if rc == -3:
            raise ValueError("item larger than ring capacity")
        if rc == -5:
            raise BrokenPipeError("ring poisoned (a peer died mid-operation)")

    def pop(self, timeout_ms=-1) -> bytes | None:
        size = _lib.ptr_ring_next_size(self._h)
        cap = max(int(size), 1 << 16)
        buf = ctypes.create_string_buffer(cap)
        n = _lib.ptr_ring_pop(self._h, buf, cap, timeout_ms)
        while n == -4:  # buffer too small; header not consumed — re-query size
            cap = max(int(_lib.ptr_ring_next_size(self._h)), cap * 2)
            buf = ctypes.create_string_buffer(cap)
            n = _lib.ptr_ring_pop(self._h, buf, cap, timeout_ms)
        if n == -2:
            raise TimeoutError("ring pop timed out")
        if n == -5:
            raise BrokenPipeError("ring poisoned (a peer died mid-operation)")
        if n == 0:
            return None  # closed and drained
        return buf.raw[: int(n)]

    def close(self):
        _lib.ptr_ring_close(self._h)

    def destroy(self):
        if self._h:
            _lib.ptr_ring_destroy(self._h)
            self._h = None


class HostEventRecorder:
    def __init__(self):
        self._h = _lib.phe_create()
        self._names = {}

    def intern(self, name: str) -> int:
        nid = self._names.get(name)
        if nid is None:
            nid = _lib.phe_intern(self._h, name.encode())
            self._names[name] = nid
        return nid

    def now_ns(self) -> int:
        return int(_lib.phe_now_ns())

    def record(self, name_id: int, start_ns: int, end_ns: int, tid: int = 0):
        _lib.phe_record(self._h, name_id, start_ns, end_ns, tid)

    def dump(self, clear=True):
        import numpy as np

        n = int(_lib.phe_count(self._h))
        if n == 0:
            return []
        ids = np.zeros(n, np.uint32)
        st = np.zeros(n, np.uint64)
        en = np.zeros(n, np.uint64)
        tid = np.zeros(n, np.uint64)
        got = int(
            _lib.phe_dump(
                self._h,
                ids.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                st.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                en.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                tid.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                n,
                1 if clear else 0,
            )
        )
        rev = {v: k for k, v in self._names.items()}
        out = []
        for i in range(got):
            name = rev.get(int(ids[i]))
            if name is None:
                buf = ctypes.create_string_buffer(256)
                ln = _lib.phe_name(self._h, int(ids[i]), buf, 256)
                name = buf.raw[:ln].decode()
            out.append((name, int(st[i]), int(en[i]), int(tid[i])))
        return out

    def __del__(self):
        try:
            if self._h:
                _lib.phe_destroy(self._h)
        except Exception:
            pass
