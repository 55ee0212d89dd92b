"""Database controllers: the key-value abstraction under repositories.

Reference analog: DatabaseController (db/src/controller/interface.ts)
with LevelDbController (db/src/controller/level.ts:28). Two
implementations: the native C++ ordered store (csrc/kvstore.cc, the
classic-level analog) and an in-memory dict for tests — the same swap
point the reference uses (SURVEY.md §4 fake backends).
"""

from __future__ import annotations

import ctypes
import os
from pathlib import Path

from ..utils import native_build

_SRC = native_build.REPO / "csrc" / "kvstore.cc"

OP_PUT = 1
OP_DEL = 2


class DatabaseController:
    """Interface: get/put/delete/batch, ordered range scans."""

    def get(self, key: bytes) -> bytes | None:
        raise NotImplementedError

    def put(self, key: bytes, value: bytes) -> None:
        raise NotImplementedError

    def delete(self, key: bytes) -> None:
        raise NotImplementedError

    def batch(self, ops: list[tuple[str, bytes, bytes | None]]) -> None:
        """ops: [("put", key, value) | ("del", key, None)]"""
        for op, k, v in ops:
            if op == "put":
                self.put(k, v)
            else:
                self.delete(k)

    def range(
        self,
        start: bytes = b"",
        end: bytes = b"",
        reverse: bool = False,
        limit: int = 0,
    ) -> list[tuple[bytes, bytes]]:
        """Entries with start <= key < end (end=b'' → unbounded)."""
        raise NotImplementedError

    def close(self) -> None:
        pass


class MemoryDatabaseController(DatabaseController):
    def __init__(self):
        self._d: dict[bytes, bytes] = {}

    def get(self, key):
        return self._d.get(key)

    def put(self, key, value):
        self._d[key] = bytes(value)

    def delete(self, key):
        self._d.pop(key, None)

    def range(self, start=b"", end=b"", reverse=False, limit=0):
        keys = sorted(
            k
            for k in self._d
            if k >= start and (not end or k < end)
        )
        if reverse:
            keys.reverse()
        if limit:
            keys = keys[:limit]
        return [(k, self._d[k]) for k in keys]


_lib = None


def _load_lib():
    global _lib
    if _lib is not None:
        return _lib
    lib_path = native_build.build(
        _SRC, [os.environ.get("CXX", "c++"), "-O2", "-std=c++17"]
    )
    lib = ctypes.CDLL(str(lib_path))
    lib.kv_open.restype = ctypes.c_void_p
    lib.kv_open.argtypes = [ctypes.c_char_p]
    lib.kv_put.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.c_char_p,
        ctypes.c_uint32,
    ]
    lib.kv_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
    lib.kv_batch.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
    lib.kv_get.restype = ctypes.POINTER(ctypes.c_char)
    lib.kv_get.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint32),
    ]
    lib.kv_range.restype = ctypes.POINTER(ctypes.c_char)
    lib.kv_range.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.c_int,
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.kv_count.restype = ctypes.c_uint64
    lib.kv_count.argtypes = [ctypes.c_void_p]
    lib.kv_flush.argtypes = [ctypes.c_void_p]
    lib.kv_compact.argtypes = [ctypes.c_void_p]
    lib.kv_close.argtypes = [ctypes.c_void_p]
    lib.kv_free.argtypes = [ctypes.POINTER(ctypes.c_char)]
    _lib = lib
    return lib


class NativeDatabaseController(DatabaseController):
    """C++ ordered store with WAL persistence (csrc/kvstore.cc)."""

    def __init__(self, path: str | Path):
        self._lib = _load_lib()
        Path(path).mkdir(parents=True, exist_ok=True)
        self._h = self._lib.kv_open(str(path).encode())
        if not self._h:
            raise OSError(f"kv_open failed for {path}")

    def get(self, key):
        vl = ctypes.c_uint32()
        p = self._lib.kv_get(self._h, key, len(key), ctypes.byref(vl))
        if not p:
            return None
        try:
            return ctypes.string_at(p, vl.value)
        finally:
            self._lib.kv_free(p)

    def put(self, key, value):
        self._lib.kv_put(self._h, key, len(key), value, len(value))

    def delete(self, key):
        self._lib.kv_delete(self._h, key, len(key))

    def batch(self, ops):
        import struct

        parts = []
        for op, k, v in ops:
            v = v or b""
            code = OP_PUT if op == "put" else OP_DEL
            parts.append(struct.pack("<BII", code, len(k), len(v)) + k + v)
        buf = b"".join(parts)
        rc = self._lib.kv_batch(self._h, buf, len(buf))
        if rc != 0:
            raise OSError("kv_batch failed")

    def range(self, start=b"", end=b"", reverse=False, limit=0):
        import struct

        out_len = ctypes.c_uint64()
        out_count = ctypes.c_uint64()
        p = self._lib.kv_range(
            self._h,
            start,
            len(start),
            end,
            len(end),
            1 if reverse else 0,
            limit,
            ctypes.byref(out_len),
            ctypes.byref(out_count),
        )
        try:
            buf = ctypes.string_at(p, out_len.value)
        finally:
            self._lib.kv_free(p)
        out = []
        off = 0
        for _ in range(out_count.value):
            kl, vl = struct.unpack_from("<II", buf, off)
            off += 8
            out.append((buf[off : off + kl], buf[off + kl : off + kl + vl]))
            off += kl + vl
        return out

    def __len__(self):
        return self._lib.kv_count(self._h)

    def flush(self):
        self._lib.kv_flush(self._h)

    def compact(self):
        self._lib.kv_compact(self._h)

    def close(self):
        if self._h:
            self._lib.kv_close(self._h)
            self._h = None
