"""Vectorized Fq (BLS12-381 base field) ops on the limb representation.

Thin layer over ops/limbs.py: multiplication = convolution + fold-mod-P
normalization; inversion and square roots are fixed-exponent powers
driven by `lax.scan` over the (public) exponent bits so the compiled
graph stays small. Exact in-graph equality goes through full
canonicalization (strict digits + binary conditional-subtract ladder).
All functions broadcast over leading batch dims.

Reference analog: blst's fp arithmetic (@chainsafe/blst, SURVEY.md
§2.1); correctness oracle: lodestar_tpu/crypto/bls/fields.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto.bls.fields import P
from . import limbs as L
from .limbs import Lv
from ..utils import chip

add = L.add
sub = L.sub
neg = L.neg
mul_small = L.mul_small
normalize = L.normalize
const = L.const
conv = L.conv

# Limb backend knob (VPU int32 vs MXU int8 — ops/limbs.py): mul/sqr/
# pow chains and the towers inherit the selection through conv +
# normalize, so these re-exports are the whole integration surface.
set_limb_backend = L.set_backend
get_limb_backend = L.get_backend
limb_backend = L.limb_backend

# The value of any canonical-profile Lv is non-negative and < 1037*P
# (limbs <= B+1 over 390 bits plus the small carry limb) < 2^11 * P, so
# a 12-step binary conditional-subtract ladder fully reduces it.
_NDIG = L.NCANON + 1  # exact digit count for values < 2^400


def mul(a: Lv, b: Lv) -> Lv:
    return L.normalize(L.conv(a, b))


def sqr(a: Lv) -> Lv:
    return mul(a, a)


def select(mask: jax.Array, a: Lv, b: Lv) -> Lv:
    """Elementwise choice: where mask is True take a. mask = batch shape."""
    n = max(a.n, b.n)
    a, b = L._pad_to(a, n), L._pad_to(b, n)
    lo = tuple(min(x, y) for x, y in zip(a.lo, b.lo))
    hi = tuple(max(x, y) for x, y in zip(a.hi, b.hi))
    return Lv(jnp.where(mask[..., None], a.v, b.v), lo, hi)


def pow_const(a: Lv, e: int) -> Lv:
    """a^e for a fixed public exponent. On TPU with a 1-D batch the
    whole square-and-multiply chain runs as ONE fused Pallas kernel
    with the limb state VMEM-resident (ops/pallas_chain.py — measured
    0.6 ms vs 452 ms for the XLA scan at batch 2048, 379-bit
    exponent). Elsewhere: a scan over the exponent bits (LSB first),
    graph size O(1) in the exponent length."""
    assert e >= 0
    if e == 0:
        return const(1, a.v.shape[:-1])
    if e > 1 and a.v.ndim == 2 and chip.on_tpu():
        from . import pallas_chain

        return pallas_chain.pow_const(a, e)
    bits = jnp.asarray(
        np.array([(e >> i) & 1 for i in range(e.bit_length())], np.bool_)
    )
    a = L.normalize(a)
    batch = a.v.shape[:-1]

    def body(carry, bit):
        result, base = carry
        result = select(jnp.broadcast_to(bit, batch), mul(result, base), result)
        return (result, sqr(base)), None

    one = const(1, batch).widen(L.CANON_LO, L.CANON_HI)
    (result, _), _ = jax.lax.scan(body, (one, a), bits)
    return result


def inv(a: Lv) -> Lv:
    """Field inverse via Fermat (a^(P-2)); 0 -> 0."""
    return pow_const(a, P - 2)


def sqrt_candidate(a: Lv) -> Lv:
    """a^((P+1)/4): the square root when a is a QR (P = 3 mod 4).
    Callers check cand^2 == a via eq()."""
    return pow_const(a, (P + 1) // 4)


# ---------------------------------------------------------------------------
# Exact canonicalization and equality
# ---------------------------------------------------------------------------


def _digits_of(m: int, n: int = _NDIG) -> np.ndarray:
    out = np.zeros(n, np.int32)
    for i in range(n):
        out[i] = m & (L.B - 1)
        m >>= L.BITS
    assert m == 0
    return out


@functools.lru_cache(maxsize=None)
def _ladder(k: int) -> np.ndarray:
    """Constant (1 << k) * P as strict digits. Cached as a NUMPY array:
    caching a jnp array created during a jit trace would capture that
    trace's tracer and leak it into later traces; jnp ops convert the
    numpy constant per-trace."""
    return _digits_of((1 << k) * P)


def _strict_carry(v: jax.Array) -> jax.Array:
    """Sequential signed carry leaving exact digits in [0, B). The value
    must be non-negative and < 2^(10*ndigits). One lax.scan over the
    limb axis (a Python loop here would add ~160 ops per call site —
    canon_digits runs 12 of these)."""

    def body(carry, x):
        t = x + carry
        c = t >> L.BITS
        return c, t - (c << L.BITS)

    vt = jnp.moveaxis(v, -1, 0)
    _, out = jax.lax.scan(
        body, jnp.zeros(v.shape[:-1], jnp.int32), vt
    )
    return jnp.moveaxis(out, 0, -1)


def canon_digits(a: Lv) -> jax.Array:
    """Exact base-2^10 digits of (a mod P) in [0, P) — (..., 41) int32."""
    x = normalize(a)  # non-negative canonical profile
    v = jnp.pad(x.v, [(0, 0)] * (x.v.ndim - 1) + [(0, _NDIG - x.n)])
    v = _strict_carry(v)  # value in [0, 1037*P) < 2^12 * P
    for k in reversed(range(12)):
        m = _ladder(k)
        d = v - m
        nz = d != 0
        idx = (_NDIG - 1) - jnp.argmax(nz[..., ::-1], axis=-1)
        msd = jnp.take_along_axis(d, idx[..., None], axis=-1)[..., 0]
        ge = msd >= 0  # all-zero diff -> equal -> subtract (gives 0)
        v = _strict_carry(v - jnp.where(ge[..., None], m, 0))
    return v


def is_zero(a: Lv) -> jax.Array:
    return jnp.all(canon_digits(a) == 0, axis=-1)


def eq(a: Lv, b: Lv) -> jax.Array:
    return is_zero(L.sub(a, b))


def to_int(a: Lv):
    """Host-side canonical integer(s)."""
    return L.to_ints(a)
