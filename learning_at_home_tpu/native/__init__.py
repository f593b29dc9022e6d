"""ctypes bindings for the native (C++) server data plane.

``FramePump`` wraps ``framepump.cpp`` — a GIL-free epoll thread that owns
all socket work for the framed tensor RPC protocol (wire-compatible with
``utils/serialization.py``).  The shared library is built on demand with
the toolchain baked into the image (g++) and cached next to the source
under a name that carries the source's content hash: a library is reused
only if it was built from exactly this source (file times mean nothing
after a checkout or a copy).

Falls back cleanly: ``native_available()`` returns False when compilation
fails (no compiler, non-Linux), and ``Server(transport="native")`` raises
a clear error while the default asyncio transport keeps working.
"""

from __future__ import annotations

import contextlib
import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

from learning_at_home_tpu.utils import sanitizer

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "framepump.cpp")

_lib = None
_lib_lock = sanitizer.lock("native.lib")


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"_framepump.{digest}.so")


def _build() -> Optional[str]:
    """Compile the pump, safely under concurrent processes: an exclusive
    flock on the source serializes builders (a multi-server swarm starts N
    processes at once) and the compiler writes to a temp path that is
    atomically renamed into place, so no process can ever dlopen a
    half-written .so."""
    so = _so_path()
    if os.path.exists(so):
        return so
    import fcntl
    import glob

    tmp = f"{so[:-3]}.{os.getpid()}.tmp.so"  # *.so: git-ignored if orphaned
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-pthread", _SRC, "-o", tmp]
    try:
        with open(_SRC, "rb") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            # another process may have finished the build while we waited
            if os.path.exists(so):
                return so
            r = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
            if r.returncode != 0:
                logger.warning(
                    "native framepump build failed:\n%s", r.stderr[-2000:]
                )
                return None
            os.replace(tmp, so)
            for stale in glob.glob(os.path.join(_HERE, "_framepump.*.so")):
                if stale != so:  # built from a source that no longer exists
                    with contextlib.suppress(OSError):
                        os.unlink(stale)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning("native framepump build failed to run: %s", e)
        return None
    finally:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
    return so


def _load():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        so = _build()
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        lib.lah_pump_create.restype = ctypes.c_void_p
        lib.lah_pump_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int)
        ]
        lib.lah_pump_next.restype = ctypes.c_int
        lib.lah_pump_next.argtypes = [
            ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.lah_pump_send.restype = ctypes.c_int
        lib.lah_pump_send.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64
        ]
        lib.lah_pump_buffree.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
        lib.lah_pump_shutdown.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


class FramePump:
    """GIL-free epoll data plane; Python sees only whole frames."""

    def __init__(self, host: str = "0.0.0.0", port: int = 0):
        lib = _load()
        if lib is None:
            raise RuntimeError(
                "native framepump unavailable (g++ build failed); "
                "use transport='asyncio'"
            )
        self._lib = lib
        # the C side binds with inet_addr (numeric only): resolve names here
        import socket as _socket

        try:
            host = _socket.gethostbyname(host)
        except OSError:
            pass  # let bind() produce the error for truly bad hosts
        out_port = ctypes.c_int(0)
        self._h = lib.lah_pump_create(host.encode(), port, ctypes.byref(out_port))
        if not self._h:
            raise OSError(f"framepump could not bind {host}:{port}")
        self.port = out_port.value
        self._closed = False
        # serializes send vs shutdown: a reply arriving on another thread
        # during shutdown must either be queued on live C state or see
        # _closed — never call into freed memory.  next() is NOT guarded
        # (it blocks); callers must stop calling next() before shutdown().
        self._call_lock = sanitizer.lock("native.pump_call")

    def next(self, timeout: float = 0.2) -> Optional[tuple[int, bytes]]:
        """Next complete inbound frame as (conn_id, payload).

        None on timeout; raises ``EOFError`` after shutdown."""
        conn = ctypes.c_uint64(0)
        buf = ctypes.POINTER(ctypes.c_uint8)()
        length = ctypes.c_uint64(0)
        rc = self._lib.lah_pump_next(
            self._h, int(timeout * 1000), ctypes.byref(conn),
            ctypes.byref(buf), ctypes.byref(length),
        )
        if rc == 0:
            return None
        if rc < 0:
            raise EOFError("framepump stopped")
        try:
            payload = ctypes.string_at(buf, length.value)
        finally:
            self._lib.lah_pump_buffree(buf)
        return conn.value, payload

    def send(self, conn_id: int, payload: bytes) -> bool:
        """Queue a reply frame; False if the peer is gone (disconnected or
        not reading replies — its queue cap was hit)."""
        with self._call_lock:
            if self._closed:
                return False
            rc = self._lib.lah_pump_send(
                self._h, conn_id, payload, len(payload)
            )
        if rc == -2:
            raise ValueError("frame exceeds MAX_FRAME_BYTES")
        return rc == 0

    def shutdown(self) -> None:
        with self._call_lock:
            if self._closed:
                return
            self._closed = True
        self._lib.lah_pump_shutdown(self._h)

    def __del__(self):  # best-effort; explicit shutdown preferred
        try:
            self.shutdown()
        # lah-lint: ignore[R6] finalizer: logging machinery may already
        # be torn down at interpreter shutdown — swallow is the only
        # safe behavior in __del__
        except Exception:
            pass
