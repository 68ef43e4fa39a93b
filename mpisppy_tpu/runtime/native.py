"""Build + ctypes bindings for the native runtime (runtime/exchange.cpp).

Compiles the shared library from exchange.cpp on first use with g++
(toolchain is part of the target environment), caching by source
mtime; the built .so is never committed.  If no compiler
is available the import still succeeds, `available()` returns False,
and NativeWindow transparently delegates to PySeqlockWindow — a pure
numpy-over-mmap implementation of the SAME memory layout (24-byte
{seq, write_id, length} int64 header + float64 payload), so the wheel
runs on boxes without g++ and the two implementations interoperate on
one mmap file.  The native path stays preferred: the fallback only
engages when the library cannot be built or loaded.
"""

from __future__ import annotations

import ctypes
import mmap
import os
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "exchange.cpp")
_LIB = os.path.join(_HERE, "libexchange.so")

_lock = threading.Lock()
_lib = None
_tried = False


def _build():
    """Compile exchange.cpp (the only source of the library: the .so is
    never committed).  Built under a private name and renamed into
    place, so concurrent first uses never load a half-written file."""
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           "-o", tmp, _SRC]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, _LIB)


def _load():
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            if (not os.path.exists(_LIB)
                    or os.path.getmtime(_LIB) < os.path.getmtime(_SRC)):
                _build()
            try:
                lib = ctypes.CDLL(_LIB)
            except OSError:
                # a stale .so may target another toolchain/ABI; one
                # rebuild from source is authoritative
                _build()
                lib = ctypes.CDLL(_LIB)
        except (OSError, subprocess.CalledProcessError):
            return None
        lib.exch_create.restype = ctypes.c_void_p
        lib.exch_create.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                    ctypes.c_int]
        lib.exch_close.argtypes = [ctypes.c_void_p]
        lib.exch_write.restype = ctypes.c_int64
        lib.exch_write.argtypes = [ctypes.c_void_p,
                                   ctypes.POINTER(ctypes.c_double),
                                   ctypes.c_int64, ctypes.c_int64]
        lib.exch_read.restype = ctypes.c_int64
        lib.exch_read.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_double),
                                  ctypes.c_int64]
        lib.exch_write_id.restype = ctypes.c_int64
        lib.exch_write_id.argtypes = [ctypes.c_void_p]
        lib.exch_kill.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available():
    """True iff the COMPILED exchange library is loadable — the
    fallback below keeps NativeWindow working either way, but callers
    that specifically exercise the C++ path (tests) key off this."""
    return _load() is not None


class PySeqlockWindow:
    """Pure-Python mmap seqlock with exchange.cpp's exact memory
    layout: int64 {seq, write_id, length} header then `length`
    float64s.  Writers bump seq to odd, copy the payload, store the
    write_id (auto-increment when None, KILL=-1 from send_kill), and
    bump seq back to even; readers retry while seq is odd or changed
    underneath the copy — so a process using this class and one using
    the C++ library can share a single window file."""

    KILL = -1
    _HDR = 24                       # 3 x int64, matches struct Header

    def __init__(self, length: int, path: str | None = None,
                 reset: bool = False):
        if length <= 0:
            raise ValueError("window length must be positive")
        self.length = int(length)
        nbytes = self._HDR + 8 * self.length
        self._fd = -1
        if path is None:
            self._mm = mmap.mmap(-1, nbytes)
            fresh = True
        else:
            fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o644)
            st = os.fstat(fd).st_size
            fresh = st == 0
            if fresh:
                os.ftruncate(fd, nbytes)
            elif st != nbytes:
                # exchange.cpp's exch_create refuses a file whose size
                # disagrees with the requested length; growing it here
                # would tear a reader already attached at the old size
                os.close(fd)
                raise RuntimeError("exch_create failed: length mismatch")
            self._mm = mmap.mmap(fd, nbytes)
            self._fd = fd
        self._hdr = np.frombuffer(self._mm, dtype=np.int64, count=3)
        self._data = np.frombuffer(self._mm, dtype=np.float64,
                                   count=self.length, offset=self._HDR)
        if not fresh and self._hdr[2] not in (0, self.length):
            raise RuntimeError("exch_create failed: length mismatch")
        if fresh or self._hdr[2] == 0 or reset:
            self._hdr[0] = 0
            self._hdr[1] = 0
            self._hdr[2] = self.length
        self._lock = threading.Lock()

    @property
    def write_id(self):
        return int(self._hdr[1])

    def write(self, values, write_id=None):
        values = np.ascontiguousarray(values, dtype=np.float64)
        if values.shape != (self.length,):
            raise ValueError(
                f"window expects shape ({self.length},), "
                f"got {values.shape}")
        with self._lock:
            s = int(self._hdr[0])
            self._hdr[0] = s + 1
            self._data[:] = values
            wid = (int(self._hdr[1]) + 1 if write_id is None
                   else int(write_id))
            self._hdr[1] = wid
            self._hdr[0] = s + 2
            return wid

    def read(self):
        while True:
            s0 = int(self._hdr[0])
            if s0 & 1:
                continue
            out = self._data.copy()
            wid = int(self._hdr[1])
            if int(self._hdr[0]) == s0:
                return out, wid

    def send_kill(self):
        with self._lock:
            self._hdr[1] = self.KILL

    def close(self):
        if getattr(self, "_mm", None) is not None:
            # drop the numpy views FIRST: mmap.close raises BufferError
            # while buffer exports are alive
            self._hdr = None
            self._data = None
            self._mm.close()
            self._mm = None
            if self._fd >= 0:
                os.close(self._fd)
                self._fd = -1

    def __del__(self):                                  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


class NativeWindow:
    """Drop-in for cylinders.spcommunicator.Window backed by the C++
    seqlock exchange; pass `path` for a cross-process (mmap file)
    window — the DCN-gateway layout."""

    KILL = -1

    def __init__(self, length: int, path: str | None = None,
                 reset: bool = False):
        """reset=True reinitializes an existing mmap file (owners pass
        it; attaching readers must not).  When the compiled library is
        unavailable (no g++, broken ABI) this delegates to the
        layout-compatible PySeqlockWindow instead of raising, so the
        wheel's native-backend paths keep working toolchain-free."""
        lib = _load()
        self.length = int(length)
        if lib is None:
            self._lib = None
            self._h = None
            self._py = PySeqlockWindow(self.length, path=path,
                                       reset=reset)
            return
        self._py = None
        self._lib = lib
        p = path.encode() if path is not None else None
        self._h = lib.exch_create(p, self.length, 1 if reset else 0)
        if not self._h:
            raise RuntimeError("exch_create failed")

    @property
    def write_id(self):
        if self._py is not None:
            return self._py.write_id
        return int(self._lib.exch_write_id(self._h))

    def write(self, values, write_id=None):
        if self._py is not None:
            return self._py.write(values, write_id=write_id)
        values = np.ascontiguousarray(values, dtype=np.float64)
        if values.shape != (self.length,):
            raise ValueError(
                f"window expects shape ({self.length},), "
                f"got {values.shape}")
        wid = -1 if write_id is None else int(write_id)
        out = self._lib.exch_write(
            self._h, values.ctypes.data_as(
                ctypes.POINTER(ctypes.c_double)),
            self.length, wid)
        if out == -2:
            raise RuntimeError("native window length mismatch")
        return int(out)

    def read(self):
        if self._py is not None:
            return self._py.read()
        out = np.empty(self.length, dtype=np.float64)
        wid = self._lib.exch_read(
            self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            self.length)
        if wid == -2:
            raise RuntimeError("native window length mismatch")
        return out, int(wid)

    def send_kill(self):
        if self._py is not None:
            return self._py.send_kill()
        self._lib.exch_kill(self._h)

    def close(self):
        if getattr(self, "_py", None) is not None:
            self._py.close()
            self._py = None
        if getattr(self, "_h", None):
            self._lib.exch_close(self._h)
            self._h = None

    def __del__(self):                                  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass
