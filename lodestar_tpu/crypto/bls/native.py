"""ctypes bindings for the native BLS12-381 backend (csrc/bls381.c).

Reference analog: the node-gyp binding layer of @chainsafe/blst —
prebuilt native crypto behind a narrow byte-oriented API. Points cross
the boundary as affine big-endian bytes (G1 96B, G2 192B, all-zero =
infinity); ints<->bytes conversion helpers keep the pure-Python oracle
(fields/curve/pairing modules) interchangeable for differential tests.

Set LODESTAR_TPU_NO_NATIVE=1 to force the pure-Python paths.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

from ...utils import native_build

_SRC = native_build.REPO / "csrc" / "bls381.c"
_HDR = native_build.REPO / "csrc" / "bls381_constants.h"

_lib = None
_load_failed = False


def available() -> bool:
    if os.environ.get("LODESTAR_TPU_NO_NATIVE") == "1":
        return False
    return _load() is not None


def _load():
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    try:
        path = native_build.build(
            _SRC, [os.environ.get("CC", "cc"), "-O2"], deps=(_HDR,)
        )
        lib = ctypes.CDLL(str(path))
        for name, res in (
            ("blsn_g1_decompress", ctypes.c_int),
            ("blsn_g2_decompress", ctypes.c_int),
            ("blsn_g1_subgroup_check", ctypes.c_int),
            ("blsn_g2_subgroup_check", ctypes.c_int),
            ("blsn_pairing_product_is_one", ctypes.c_int),
            ("blsn_miller_loop", ctypes.c_int),
            ("blsn_g1_msm", ctypes.c_int),
        ):
            getattr(lib, name).restype = res
        _lib = lib
    except (OSError, subprocess.SubprocessError):
        _load_failed = True
        _lib = None
    return _lib


# --- int-tuple <-> byte codecs (oracle interop) -------------------------


def g1_to_bytes(pt) -> bytes:
    if pt is None:
        return b"\x00" * 96
    return pt[0].to_bytes(48, "big") + pt[1].to_bytes(48, "big")


def g1_from_bytes_affine(b: bytes):
    if b == b"\x00" * 96:
        return None
    return (
        int.from_bytes(b[:48], "big"),
        int.from_bytes(b[48:], "big"),
    )


def g2_to_bytes(pt) -> bytes:
    if pt is None:
        return b"\x00" * 192
    (x0, x1), (y0, y1) = pt
    return (
        x1.to_bytes(48, "big")
        + x0.to_bytes(48, "big")
        + y1.to_bytes(48, "big")
        + y0.to_bytes(48, "big")
    )


def g2_from_bytes_affine(b: bytes):
    if b == b"\x00" * 192:
        return None
    x1, x0, y1, y0 = (
        int.from_bytes(b[i * 48 : (i + 1) * 48], "big") for i in range(4)
    )
    return ((x0, x1), (y0, y1))


# --- API ---------------------------------------------------------------


class NativeError(ValueError):
    pass


def g1_decompress(compressed: bytes):
    """48B -> affine ints with on-curve + subgroup checks; None for the
    (valid-encoding) identity; raises NativeError for bad points."""
    if len(compressed) != 48:
        raise NativeError("G1 compressed point must be 48 bytes")
    lib = _load()
    out = ctypes.create_string_buffer(96)
    rc = lib.blsn_g1_decompress(compressed, out)
    if rc == 2:
        return None
    if rc != 1:
        raise NativeError("invalid G1 point")
    return g1_from_bytes_affine(out.raw)


def g2_decompress(compressed: bytes):
    if len(compressed) != 96:
        raise NativeError("G2 compressed point must be 96 bytes")
    lib = _load()
    out = ctypes.create_string_buffer(192)
    rc = lib.blsn_g2_decompress(compressed, out)
    if rc == 2:
        return None
    if rc != 1:
        raise NativeError("invalid G2 point")
    return g2_from_bytes_affine(out.raw)


def hash_to_g2(message: bytes, dst: bytes):
    lib = _load()
    out = ctypes.create_string_buffer(192)
    lib.blsn_hash_to_g2(message, len(message), dst, len(dst), out)
    return g2_from_bytes_affine(out.raw)


def pairing_product_is_one(pairs) -> bool:
    """pairs: [(g1_pt, g2_pt)] as oracle int tuples."""
    lib = _load()
    g1s = b"".join(g1_to_bytes(p) for p, _ in pairs)
    g2s = b"".join(g2_to_bytes(q) for _, q in pairs)
    rc = lib.blsn_pairing_product_is_one(g1s, g2s, len(pairs))
    if rc < 0:
        raise NativeError("invalid pairing input")
    return rc == 1


def g1_msm(pts, scalars) -> "tuple | None":
    """Pippenger multi-scalar multiplication: sum_i scalars[i]*pts[i].
    pts: list of oracle int tuples (None = infinity); scalars: ints."""
    lib = _load()
    n = len(pts)
    buf = b"".join(g1_to_bytes(p) for p in pts)
    sc = b"".join((int(k) % R_ORDER).to_bytes(32, "big") for k in scalars)
    out = ctypes.create_string_buffer(96)
    if lib.blsn_g1_msm(buf, sc, n, out) != 1:
        raise NativeError("invalid G1 point in MSM")
    return g1_from_bytes_affine(out.raw)


R_ORDER = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001


def g1_mul(pt, k: int):
    lib = _load()
    out = ctypes.create_string_buffer(96)
    lib.blsn_g1_mul(
        g1_to_bytes(pt), (k % (1 << 256)).to_bytes(32, "big"), out
    )
    return g1_from_bytes_affine(out.raw)


def g2_mul(pt, k: int):
    lib = _load()
    out = ctypes.create_string_buffer(192)
    lib.blsn_g2_mul(
        g2_to_bytes(pt), (k % (1 << 256)).to_bytes(32, "big"), out
    )
    return g2_from_bytes_affine(out.raw)


def g1_add(a, b):
    lib = _load()
    out = ctypes.create_string_buffer(96)
    if lib.blsn_g1_add(g1_to_bytes(a), g1_to_bytes(b), out) != 1:
        raise NativeError("invalid G1 point in add")
    return g1_from_bytes_affine(out.raw)


def g2_add(a, b):
    lib = _load()
    out = ctypes.create_string_buffer(192)
    if lib.blsn_g2_add(g2_to_bytes(a), g2_to_bytes(b), out) != 1:
        raise NativeError("invalid G2 point in add")
    return g2_from_bytes_affine(out.raw)


def g1_compress(pt) -> bytes:
    lib = _load()
    out = ctypes.create_string_buffer(48)
    lib.blsn_g1_compress(g1_to_bytes(pt), out)
    return out.raw


def g1_subgroup_check(pt) -> bool:
    """On-curve + r-subgroup membership (native does both)."""
    if pt is None:
        return True
    return bool(_load().blsn_g1_subgroup_check(g1_to_bytes(pt)))


def g2_subgroup_check(pt) -> bool:
    if pt is None:
        return True
    return bool(_load().blsn_g2_subgroup_check(g2_to_bytes(pt)))
