"""ctypes binding for the native batched SHA-256 merkleizer.

Reference analog: @chainsafe/as-sha256's batch hash entry points
(SURVEY.md §2.1). Compiles csrc/sha256_merkle.c once per machine into
a cached shared object (no pip deps; cc toolchain is baked in) and
exposes:

  - hash64_batch(data: bytes[64*n]) -> bytes[32*n]
  - merkleize(chunks: bytes, count, limit) -> 32-byte root

Falls back silently (AVAILABLE=False) when no compiler is present;
ssz.core keeps its hashlib path.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from hashlib import sha256
from pathlib import Path

from ..utils import native_build

_SRC = native_build.REPO / "csrc" / "sha256_merkle.c"

_lib = None
AVAILABLE = False

# zero_hashes[i] = root of a zero subtree of depth i. 65 entries: SSZ
# list limits reach depth 40+ (VALIDATOR_REGISTRY_LIMIT = 2^40), match
# ssz.core's 64-deep table.
_ZERO = [b"\x00" * 32]
for _ in range(64):
    _ZERO.append(sha256(_ZERO[-1] + _ZERO[-1]).digest())
_ZERO_BUF = b"".join(_ZERO)


def _build() -> Path | None:
    try:
        return native_build.build(_SRC, [os.environ.get("CC", "cc"), "-O3"])
    except (OSError, subprocess.SubprocessError):
        return None


def _load():
    global _lib, AVAILABLE
    if _lib is not None or AVAILABLE:
        return _lib
    path = _build()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(str(path))
        lib.hash64_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_size_t,
        ]
        lib.hash_small_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_char_p,
            ctypes.c_size_t,
        ]
        lib.merkle_root.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_char_p,
        ]
        _lib = lib
        AVAILABLE = True
        return lib
    except Exception:
        return None


def hash64_batch(data: bytes) -> bytes:
    """Hash n concatenated 64-byte inputs -> n concatenated digests."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native sha256 hasher unavailable (no compiler?)")
    n = len(data) // 64
    out = ctypes.create_string_buffer(32 * n)
    lib.hash64_batch(data, out, n)
    return out.raw


def hash_small_batch(data: bytes, msg_len: int) -> bytes:
    """Hash n concatenated fixed-length (<= 55 byte) messages -> n
    concatenated 32-byte digests. One padded SHA-256 block per message
    (the swap-or-not decision-hash shape: 37 bytes)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native sha256 hasher unavailable (no compiler?)")
    if msg_len > 55:
        raise ValueError("msg_len > 55 needs multi-block hashing")
    n = len(data) // msg_len
    out = ctypes.create_string_buffer(32 * n)
    lib.hash_small_batch(data, msg_len, out, n)
    return out.raw


def merkleize_packed(chunks: bytes, count: int, depth: int) -> bytes:
    """Merkle root of `count` 32-byte chunks padded with zero subtrees
    to depth `depth` (depth <= 64)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native sha256 hasher unavailable (no compiler?)")
    if depth > 64:
        raise ValueError("depth > 64")
    scratch = ctypes.create_string_buffer(32 * (count + 1))
    out = ctypes.create_string_buffer(32)
    lib.merkle_root(chunks, count, depth, _ZERO_BUF, scratch, out)
    return out.raw


def available() -> bool:
    _load()
    return AVAILABLE
