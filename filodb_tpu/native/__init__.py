"""Optional C++ acceleration library loader.

Builds are produced by `make -C filodb_tpu/native` (see Makefile /
filodb_native.cc); on first import the loader attempts one quiet build if
the shared object is missing and a compiler is available.  When the shared
object is absent, `lib` is None and pure-Python fallbacks are used
everywhere, so the framework never hard-depends on a compiled artifact
(the reference has the same shape: lz4-java falls back from native XXHash
to a safe JVM implementation).
"""
from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

lib = None

_DIR = os.path.dirname(__file__)
_SO = os.path.join(_DIR, "libfilodb_native.so")
_BUILD_MARKER = os.path.join(_DIR, ".build_failed")


class _NativeLib:
    def __init__(self, cdll: ctypes.CDLL):
        self._c = cdll
        c = self._c
        c.filodb_xxhash32.restype = ctypes.c_uint32
        c.filodb_xxhash32.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                      ctypes.c_uint32]
        c.filodb_xxhash64.restype = ctypes.c_uint64
        c.filodb_xxhash64.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                      ctypes.c_uint64]
        c.filodb_nibble_pack.restype = ctypes.c_long
        c.filodb_nibble_pack.argtypes = [
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t]
        c.filodb_nibble_pack_rows.restype = ctypes.c_long
        c.filodb_nibble_pack_rows.argtypes = [
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t,
            ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_size_t, ctypes.POINTER(ctypes.c_int64)]
        c.filodb_nibble_unpack.restype = ctypes.c_long
        c.filodb_nibble_unpack.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t]

    def xxhash32(self, data: bytes, seed: int = 0) -> int:
        return self._c.filodb_xxhash32(data, len(data), seed)

    def xxhash64(self, data: bytes, seed: int = 0) -> int:
        return self._c.filodb_xxhash64(data, len(data), seed)

    def nibble_pack(self, values: np.ndarray) -> bytes:
        vals = np.ascontiguousarray(values, dtype=np.uint64)
        n = len(vals)
        cap = ((n + 7) // 8) * 66
        out = np.empty(cap, dtype=np.uint8)
        written = self._c.filodb_nibble_pack(
            vals.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), n,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap)
        if written < 0:
            raise ValueError("nibble_pack: output buffer overflow")
        return out[:written].tobytes()

    def nibble_pack_rows(self, values: np.ndarray) -> list:
        """nibble_pack of every row of a [R, n] array, in one call."""
        vals = np.ascontiguousarray(values, dtype=np.uint64)
        rows, n = vals.shape
        cap = rows * ((n + 7) // 8) * 66
        out = np.empty(cap, dtype=np.uint8)
        ends = np.empty(rows, dtype=np.int64)
        written = self._c.filodb_nibble_pack_rows(
            vals.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), rows, n,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap,
            ends.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        if written < 0:
            raise ValueError("nibble_pack_rows: output buffer overflow")
        buf = out[:written].tobytes()
        ends = ends.tolist()
        return [buf[a:b] for a, b in zip([0] + ends[:-1], ends)]

    def nibble_unpack(self, data: bytes, count: int) -> np.ndarray:
        buf = np.frombuffer(data, dtype=np.uint8)
        out = np.empty(count, dtype=np.uint64)
        consumed = self._c.filodb_nibble_unpack(
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(buf),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), count)
        if consumed < 0:
            raise ValueError("nibble_unpack: truncated input")
        return out


def _try_build() -> None:  # pragma: no cover - environment dependent
    if os.path.exists(_BUILD_MARKER):
        return
    try:
        subprocess.run(["make", "-C", _DIR], capture_output=True, timeout=120,
                       check=True)
    except Exception:
        try:
            with open(_BUILD_MARKER, "w") as f:
                f.write("native build failed; using pure-Python fallbacks\n")
        except OSError:
            pass


def _try_load():  # pragma: no cover - depends on local build
    try:
        return _NativeLib(ctypes.CDLL(_SO))
    except Exception:   # missing file, bad arch, or stale .so w/o symbols
        return None


if not os.path.exists(_SO):
    _try_build()
lib = _try_load()
if lib is None and os.path.exists(_SO):
    # a stale .so from an older source revision lacks newer symbols;
    # make rebuilds when the source is newer than the artifact
    _try_build()
    lib = _try_load()
