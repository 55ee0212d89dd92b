"""Snappy codec (native C) + the two eth2 wire encodings.

Reference analog: snappyjs + Lodestar's frame codec
(reqresp/src/encodingStrategies/sszSnappy/snappyFrames/uncompress.ts:5,
network/gossip/encoding.ts:69). Two formats exist on the wire:
  - gossip payloads: raw snappy BLOCK format
  - reqresp `ssz_snappy`: snappy STREAM framing (stream id chunk +
    compressed/uncompressed chunks with masked CRC32C)
The block codec + CRC32C live in csrc/snappy.c; framing is assembled
here.
"""

from __future__ import annotations

import ctypes
import os
import struct

from . import native_build

_SRC = native_build.REPO / "csrc" / "snappy.c"

_lib = None


def _load():
    global _lib
    if _lib is not None:
        return _lib
    path = native_build.build(_SRC, [os.environ.get("CC", "cc"), "-O2"])
    lib = ctypes.CDLL(str(path))
    lib.snappy_max_compressed_length.restype = ctypes.c_uint64
    lib.snappy_max_compressed_length.argtypes = [ctypes.c_uint64]
    lib.snappy_compress.restype = ctypes.c_int
    lib.snappy_compress.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.snappy_uncompress.restype = ctypes.c_int
    lib.snappy_uncompress.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.snappy_uncompressed_length.restype = ctypes.c_int
    lib.snappy_uncompressed_length.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.snappy_crc32c.restype = ctypes.c_uint32
    lib.snappy_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    _lib = lib
    return lib


class SnappyError(ValueError):
    pass


def compress(data: bytes) -> bytes:
    """Snappy block format."""
    lib = _load()
    cap = lib.snappy_max_compressed_length(len(data))
    out = ctypes.create_string_buffer(cap)
    n = ctypes.c_uint64(cap)
    if lib.snappy_compress(data, len(data), out, ctypes.byref(n)) != 0:
        raise SnappyError("compress failed")
    return out.raw[: n.value]


def uncompress(data: bytes, max_len: int = 1 << 30) -> bytes:
    lib = _load()
    total = ctypes.c_uint64()
    if (
        lib.snappy_uncompressed_length(
            data, len(data), ctypes.byref(total)
        )
        != 0
        or total.value > max_len
    ):
        raise SnappyError("bad snappy preamble")
    out = ctypes.create_string_buffer(max(1, total.value))
    n = ctypes.c_uint64(total.value)
    if lib.snappy_uncompress(data, len(data), out, ctypes.byref(n)) != 0:
        raise SnappyError("corrupt snappy data")
    return out.raw[: n.value]


def crc32c(data: bytes) -> int:
    return _load().snappy_crc32c(data, len(data))


def _masked_crc(data: bytes) -> int:
    c = crc32c(data)
    return ((((c >> 15) | (c << 17)) + 0xA282EAD8) & 0xFFFFFFFF)


_STREAM_ID = b"\xff\x06\x00\x00sNaPpY"
_CHUNK_COMPRESSED = 0x00
_CHUNK_UNCOMPRESSED = 0x01
_MAX_CHUNK = 65536  # uncompressed bytes per frame chunk


def frame_compress(data: bytes) -> bytes:
    """Snappy stream framing (ssz_snappy reqresp payloads)."""
    out = [_STREAM_ID]
    for i in range(0, max(len(data), 1), _MAX_CHUNK):
        chunk = data[i : i + _MAX_CHUNK]
        crc = struct.pack("<I", _masked_crc(chunk))
        comp = compress(chunk)
        if len(comp) < len(chunk):
            body = crc + comp
            out.append(
                struct.pack("<I", (len(body) << 8) | _CHUNK_COMPRESSED)
            )
        else:
            body = crc + chunk
            out.append(
                struct.pack("<I", (len(body) << 8) | _CHUNK_UNCOMPRESSED)
            )
        out.append(body)
        if not data:
            break
    return b"".join(out)


def frame_uncompress(data: bytes, max_len: int = 1 << 30) -> bytes:
    """Decode a snappy-framed stream; verifies chunk CRCs."""
    if not data.startswith(_STREAM_ID):
        raise SnappyError("missing snappy stream identifier")
    off = len(_STREAM_ID)
    out = []
    total = 0
    while off < len(data):
        if off + 4 > len(data):
            raise SnappyError("truncated chunk header")
        hdr = struct.unpack_from("<I", data, off)[0]
        off += 4
        ctype = hdr & 0xFF
        clen = hdr >> 8
        if off + clen > len(data):
            raise SnappyError("truncated chunk body")
        body = data[off : off + clen]
        off += clen
        if ctype == _CHUNK_COMPRESSED or ctype == _CHUNK_UNCOMPRESSED:
            if clen < 4:
                raise SnappyError("chunk too short")
            want_crc = struct.unpack("<I", body[:4])[0]
            payload = body[4:]
            if ctype == _CHUNK_COMPRESSED:
                payload = uncompress(payload, max_len)
            if _masked_crc(payload) != want_crc:
                raise SnappyError("crc mismatch")
            total += len(payload)
            if total > max_len:
                raise SnappyError("stream exceeds max length")
            out.append(payload)
        elif 0x80 <= ctype <= 0xFE:
            continue  # skippable padding chunk
        else:
            raise SnappyError(f"unknown chunk type {ctype:#x}")
    return b"".join(out)
