"""Device-side signature-set ingestion: G2 decompression + hash-to-G2.

Why this exists: the deployment host has ONE CPU core; decompressing a
signature there costs ~0.5 ms and hashing a message to G2 ~1.8 ms, so
host prep caps the verifier at a few hundred sets/s no matter how fast
the pairing kernels get (VERDICT r2 weak #2 follow-up). Both steps are
pure field arithmetic, so they move onto the TPU as batched programs;
the host keeps only byte parsing, canonicality checks, and
expand_message_xmd (SHA-256, microseconds).

Reference analog: blst's sgn0/decompress + hash_to_curve
(@chainsafe/blst; consensus p2p spec BLS12-381 G2 point encoding;
RFC 9380 BLS12381G2_XMD:SHA-256_SSWU_RO_). Correctness oracles:
crypto/bls/curve.g2_from_bytes and crypto/bls/hash_to_curve.

Algorithms, chosen for chain economy (fixed-exponent Fp scans are the
dominant cost; Fp chains are ~3x cheaper than Fq2 chains):

- fq2 sqrt by the complex method: for a = a0 + a1*u with u^2 = -1,
  sqrt(a) = t + (a1/(2t))*u where t^2 = (a0 ± sqrt(a0^2+a1^2))/2.
  Four Fp chains (norm sqrt, two delta sqrts with the a1==0 special
  case folded into the bases by selects, one inversion), all
  candidates verified by squaring — the validity flag doubles as the
  QR test, so adversarial non-points are rejected on device.
- subgroup check via psi: Q in G2 iff psi(Q) == [x]Q (Bowe);
  the 64-bit |x| ladder is a scan with CONSTANT bits.
- cofactor clearing via the psi decomposition (RFC 9380 App. G.4),
  same as the host C backend (csrc/bls381.c).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto.bls import fields as OF
from ..crypto.bls.fields import P
from . import curve as C
from . import fq
from . import limbs as L
from . import tower
from ..utils import chip

# BLS parameter |x| (the curve's generator parameter, not a coordinate)
X_ABS = 0xD201000000010000

# SSWU constants on E2': y^2 = x^3 + A'x + B' (hash_to_curve.py:22-24)
A_PRIME = (0, 240)
B_PRIME = (1012, 1012)
Z_SSWU = (-2 % P, -1 % P)

# psi coefficients — derived from the oracle at import (curve.py:187)
_PSI_X = OF.fq2_inv(OF.fq2_pow(OF.XI, (P - 1) // 3))
_PSI_Y = OF.fq2_inv(OF.fq2_pow(OF.XI, (P - 1) // 2))

_HALF_MODP = (P - 1) // 2


def _c2(v, batch=()):
    return tower.fq2_const(v, batch)


@functools.lru_cache(maxsize=None)
def _x_bits():
    # numpy, not jnp: a cached device array created during a jit trace
    # would leak that trace's tracer (same pitfall as fq._ladder)
    return np.array(
        [(X_ABS >> (63 - i)) & 1 for i in range(64)], np.bool_
    )


# ---------------------------------------------------------------------------
# fq2 square root (flagged)
# ---------------------------------------------------------------------------


def _sqrt_bases(a0, a1_zero, delta, delta2):
    """Fold the a1==0 special case into the candidate bases:
      base_a = a0    (y = (sqrt(a0), 0) when a0 is a QR)
      base_b = -a0   (y = (0, sqrt(-a0)) otherwise; -1 is a non-QR)
    SHARED by the XLA scan path and the Pallas finisher — this select
    tree must never drift between them."""
    base_a = fq.select(a1_zero, fq.normalize(a0), delta)
    base_b = fq.select(
        a1_zero, fq.normalize(fq.neg(a0)), delta2
    )
    return base_a, base_b


def _sqrt_assemble(a, a1_zero, ok_a, ta, tb, y1_gen):
    """Candidate assembly + exact verification (the validity flag
    doubles as the QR test). SHARED by both sqrt paths."""
    a0, a1 = a
    zero = fq.const(0, ())
    t = fq.select(ok_a, ta, tb)
    cand_y0 = fq.select(a1_zero, fq.select(ok_a, ta, zero), t)
    cand_y1 = fq.select(a1_zero, fq.select(ok_a, zero, tb), y1_gen)
    y = (fq.normalize(cand_y0), fq.normalize(cand_y1))
    sq = tower.fq2_sqr(y)
    is_square = jnp.logical_and(
        fq.eq(sq[0], a0), fq.eq(sq[1], a1)
    )
    return y, is_square


def fq2_sqrt_flagged(a):
    """(y, is_square): y with y^2 == a when is_square; branch-free.

    Complex method over u^2 = -1; the a1 == 0 case folds into the two
    delta chains by selecting the bases (see module docstring)."""
    a0, a1 = a
    a1_zero = fq.is_zero(a1)
    n = fq.add(fq.sqr(a0), fq.sqr(a1))
    s = fq.pow_const(n, (P + 1) // 4)
    inv2 = fq.const((P + 1) // 2, ())  # 1/2 mod P
    delta = fq.mul(fq.add(a0, s), inv2)
    delta2 = fq.mul(fq.sub(a0, s), inv2)
    base_a, base_b = _sqrt_bases(a0, a1_zero, delta, delta2)
    ta = fq.pow_const(base_a, (P + 1) // 4)
    tb = fq.pow_const(base_b, (P + 1) // 4)
    # one inversion serves y1 = a1 / (2t) for both t candidates;
    # select the t that squares to its base (guard zero with 1)
    ok_a = fq.eq(fq.sqr(ta), base_a)
    t = fq.select(ok_a, ta, tb)
    one = fq.const(1, ())
    t_guard = fq.select(fq.is_zero(t), one, t)
    y1_gen = fq.mul(a1, fq.inv(fq.mul_small(t_guard, 2)))
    return _sqrt_assemble(a, a1_zero, ok_a, ta, tb, y1_gen)


# ---------------------------------------------------------------------------
# lexicographic "greater than (P-1)/2" for the compression sign bit
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _half_digits():
    return fq._digits_of(_HALF_MODP)  # numpy (see _x_bits note)


def _gt_half(x: L.Lv) -> jax.Array:
    """value(x) mod P > (P-1)/2, elementwise."""
    d = fq.canon_digits(x)
    diff = d - jnp.asarray(_half_digits())
    nz = diff != 0
    ndig = d.shape[-1]
    idx = (ndig - 1) - jnp.argmax(nz[..., ::-1], axis=-1)
    msd = jnp.take_along_axis(diff, idx[..., None], axis=-1)[..., 0]
    return msd > 0


def _sgn0(y) -> jax.Array:
    """RFC 9380 sgn0 for m=2 (fields.py:104)."""
    d0 = fq.canon_digits(y[0])
    s0 = (d0[..., 0] & 1).astype(bool)
    z0 = jnp.all(d0 == 0, axis=-1)
    d1 = fq.canon_digits(y[1])
    s1 = (d1[..., 0] & 1).astype(bool)
    return jnp.logical_or(s0, jnp.logical_and(z0, s1))


# ---------------------------------------------------------------------------
# psi endomorphism + subgroup check + cofactor clearing
# ---------------------------------------------------------------------------


def _fq2_conj(a):
    return (a[0], fq.normalize(fq.neg(a[1])))


def jac_psi(p: C.JacPoint) -> C.JacPoint:
    """(X, Y, Z) -> (CX*conj(X), CY*conj(Y), conj(Z))."""
    batch = ()
    cx = _c2(_PSI_X, batch)
    cy = _c2(_PSI_Y, batch)
    return C.JacPoint(
        tower.fq2_mul(_fq2_conj(p.x), cx),
        tower.fq2_mul(_fq2_conj(p.y), cy),
        _fq2_conj(p.z),
        p.inf,
    )


def jac_neg(p: C.JacPoint) -> C.JacPoint:
    return C.JacPoint(
        p.x,
        (fq.normalize(fq.neg(p.y[0])), fq.normalize(fq.neg(p.y[1]))),
        p.z,
        p.inf,
    )


def _mul_x_abs(p: C.JacPoint, batch) -> C.JacPoint:
    """[|x|]P via the constant-bit scan ladder."""
    bits = jnp.broadcast_to(
        jnp.asarray(_x_bits()), tuple(batch) + (64,)
    )
    # scalar_mul takes affine inputs; p is jacobian from upstream.
    # Use a dedicated jacobian ladder instead.
    bits_t = jnp.moveaxis(bits, -1, 0)
    acc0 = C.jac_infinity(C.FQ2_OPS, tuple(batch))

    def body(acc, bit):
        acc = C.jac_double(C.FQ2_OPS, acc)
        added = C.jac_add(C.FQ2_OPS, acc, p)
        return C.jac_select(C.FQ2_OPS, bit, added, acc), None

    acc, _ = jax.lax.scan(body, acc0, bits_t)
    return acc


def _jac_to_affine(p: C.JacPoint):
    """Batched jacobian -> affine via one Fermat inversion (a single
    fused Pallas chain on TPU). Infinity slots produce garbage coords
    (Z may be 0 -> inv gives 0) — callers carry p.inf."""
    zinv = tower.fq2_inv(p.z)
    zinv2 = tower.fq2_sqr(zinv)
    x = tower.fq2_mul(p.x, zinv2)
    y = tower.fq2_mul(p.y, tower.fq2_mul(zinv2, zinv))
    return tower.fq2_norm(x), tower.fq2_norm(y)


def _mul_x(p: C.JacPoint, batch) -> C.JacPoint:
    """[x]P for the (negative) parameter x.

    TPU: one Fermat inversion to affine (fused Pallas chain), then the
    VMEM-resident Pallas ladder — the XLA scan ladder round-trips the
    jacobian state through HBM on all 64 steps and measured ~550 ms at
    batch 2048 (two of them dominated the cofactor stage, round-4
    profile). Elsewhere: the jacobian scan ladder."""
    if chip.on_tpu() and len(tuple(batch)) == 1:
        from . import pallas_ladder as PL

        ax, ay = _jac_to_affine(p)
        return jac_neg(
            PL.g2_scalar_mul_static(ax, ay, X_ABS, p.inf)
        )
    return jac_neg(_mul_x_abs(p, batch))


def jac_eq(a: C.JacPoint, b: C.JacPoint) -> jax.Array:
    """Jacobian equality (cross-multiplied), infinity-aware."""
    za2 = tower.fq2_sqr(a.z)
    zb2 = tower.fq2_sqr(b.z)
    xl = tower.fq2_mul(a.x, zb2)
    xr = tower.fq2_mul(b.x, za2)
    za3 = tower.fq2_mul(za2, a.z)
    zb3 = tower.fq2_mul(zb2, b.z)
    yl = tower.fq2_mul(a.y, zb3)
    yr = tower.fq2_mul(b.y, za3)
    eq_xy = jnp.logical_and(
        jnp.logical_and(fq.eq(xl[0], xr[0]), fq.eq(xl[1], xr[1])),
        jnp.logical_and(fq.eq(yl[0], yr[0]), fq.eq(yl[1], yr[1])),
    )
    both_inf = jnp.logical_and(a.inf, b.inf)
    either_inf = jnp.logical_or(a.inf, b.inf)
    return jnp.where(either_inf, both_inf, eq_xy)


def g2_in_subgroup(p: C.JacPoint, batch) -> jax.Array:
    """psi(Q) == [x]Q (Bowe's fast check; csrc analog). Callers pass
    an AFFINE-constructed point (jac_from_affine), so on TPU the |x|
    ladder runs as the fused Pallas kernel."""
    if chip.on_tpu() and len(tuple(batch)) == 1:
        from . import pallas_ladder as PL

        xq = jac_neg(
            PL.g2_scalar_mul_static(p.x, p.y, X_ABS, p.inf)
        )
        return jac_eq(jac_psi(p), xq)
    return jac_eq(jac_psi(p), _mul_x(p, batch))


def g2_clear_cofactor(p: C.JacPoint, batch) -> C.JacPoint:
    """RFC 9380 App. G.4: (x^2-x-1)P + (x-1)psi(P) + psi^2(2P)."""
    ops = C.FQ2_OPS
    t1 = _mul_x(p, batch)
    t2 = jac_psi(p)
    t3 = jac_psi(jac_psi(C.jac_double(ops, p)))
    t3 = C.jac_add(ops, t3, jac_neg(t2))
    t2 = _mul_x(C.jac_add(ops, t1, t2), batch)
    t3 = C.jac_add(ops, t3, t2)
    t3 = C.jac_add(ops, t3, jac_neg(t1))
    return C.jac_add(ops, t3, jac_neg(p))


# ---------------------------------------------------------------------------
# G2 decompression
# ---------------------------------------------------------------------------


def g2_sqrt_with_sign(x, sign_bit):
    """First half of decompression: y from the curve equation + QR
    flag, sign selected per the spec's lexicographic rule. Shared by
    g2_decompress and the kernels stage split (bls/kernels.py
    _stage_g2_sqrt) so the sign rule cannot drift between copies."""
    x = tower.fq2_norm(x)
    b = _c2((4, 4))  # rhs = x^3 + 4(1+u)
    rhs = tower.fq2_add(
        tower.fq2_mul(tower.fq2_sqr(x), x), b
    )
    y, is_qr = fq2_sqrt_flagged(tower.fq2_norm(rhs))
    # spec sign: flag == (y_im > half) unless y_im == 0, then y_re
    im_zero = fq.is_zero(y[1])
    computed = jnp.where(im_zero, _gt_half(y[0]), _gt_half(y[1]))
    flip = computed != sign_bit
    y_neg = (fq.normalize(fq.neg(y[0])), fq.normalize(fq.neg(y[1])))
    y = tower.fq2_select(flip, y_neg, y)
    return x, y, is_qr


def g2_decompress(x, sign_bit, batch):
    """x: fq2 limb batch (canonical, already checked < P on host);
    sign_bit: (batch,) bool (the compressed encoding's a_flag).
    Returns (JacPoint, valid): valid covers on-curve (QR) and G2
    subgroup membership."""
    x, y, is_qr = g2_sqrt_with_sign(x, sign_bit)
    q = C.jac_from_affine(C.FQ2_OPS, x, y)
    valid = jnp.logical_and(is_qr, g2_in_subgroup(q, batch))
    return q, valid


# ---------------------------------------------------------------------------
# hash-to-G2 (device part: SSWU + isogeny + cofactor; host does
# expand_message_xmd -> u0, u1)
# ---------------------------------------------------------------------------


def _g_prime(x):
    """g(x) on E2': x^3 + A'x + B'."""
    a = _c2(A_PRIME)
    b = _c2(B_PRIME)
    return tower.fq2_add(
        tower.fq2_add(
            tower.fq2_mul(tower.fq2_sqr(x), x), tower.fq2_mul(a, x)
        ),
        b,
    )


def _sswu(u):
    """u -> (x, y) on E2' (hash_to_curve.py map_to_curve_sswu),
    branch-free: both gx1 and gx2 square roots computed, selects pick
    the square one. The tv==0 exceptional case selects the constant
    x1 = B'/(Z*A')."""
    z = _c2(Z_SSWU)
    u2 = tower.fq2_sqr(u)
    z_u2 = tower.fq2_mul(z, u2)
    tv = tower.fq2_norm(tower.fq2_add(tower.fq2_sqr(z_u2), z_u2))
    tv_zero = tower.fq2_is_zero(tv)
    tv_guard = tower.fq2_select(
        tv_zero, _c2((1, 0)), tv
    )
    tv1 = tower.fq2_inv(tv_guard)
    # x1 = (-B/A)(1 + tv1); exceptional: B/(Z A)
    neg_b_over_a = _c2(
        OF.fq2_mul(OF.fq2_neg(B_PRIME), OF.fq2_inv(A_PRIME))
    )
    x1_gen = tower.fq2_mul(
        neg_b_over_a, tower.fq2_add(_c2((1, 0)), tv1)
    )
    x1_exc = _c2(
        OF.fq2_mul(B_PRIME, OF.fq2_inv(OF.fq2_mul(Z_SSWU, A_PRIME)))
    )
    x1 = tower.fq2_select(tv_zero, x1_exc, x1_gen)
    gx1 = tower.fq2_norm(_g_prime(x1))
    y1, ok1 = fq2_sqrt_flagged(gx1)
    x2 = tower.fq2_mul(z_u2, x1)
    gx2 = tower.fq2_norm(_g_prime(x2))
    y2, _ok2 = fq2_sqrt_flagged(gx2)
    x = tower.fq2_select(ok1, x1, x2)
    y = tower.fq2_select(ok1, y1, y2)
    # sgn0 correction
    flip = _sgn0(u) != _sgn0(y)
    y = tower.fq2_select(
        flip,
        (fq.normalize(fq.neg(y[0])), fq.normalize(fq.neg(y[1]))),
        y,
    )
    return x, y


def _iso_consts():
    # constants materialize per trace (cached jnp would leak tracers)
    from ..crypto.bls.hash_to_curve import _K1, _K2, _K3, _K4

    return tuple(
        tuple(_c2(c) for c in k) for k in (_K1, _K2, _K3, _K4)
    )


def _horner(coeffs, x):
    acc = coeffs[-1]
    for c in reversed(coeffs[:-1]):
        acc = tower.fq2_add(tower.fq2_mul(acc, x), c)
    return tower.fq2_norm(acc)


def _iso_map(x, y):
    """3-isogeny E2' -> E2 with ONE shared inversion for both
    denominators (hash_to_curve.py iso_map_g2)."""
    k1, k2, k3, k4 = _iso_consts()
    x_num = _horner(k1, x)
    x_den = _horner(k2, x)
    y_num = _horner(k3, x)
    y_den = _horner(k4, x)
    prod = tower.fq2_mul(x_den, y_den)
    inv_prod = tower.fq2_inv(prod)
    xo = tower.fq2_mul(x_num, tower.fq2_mul(inv_prod, y_den))
    yo = tower.fq2_mul(
        y, tower.fq2_mul(y_num, tower.fq2_mul(inv_prod, x_den))
    )
    return tower.fq2_norm(xo), tower.fq2_norm(yo)


def _cat_lv(a: L.Lv, b: L.Lv) -> L.Lv:
    a, b = L.normalize(a), L.normalize(b)
    return L.Lv(jnp.concatenate([a.v, b.v], 0), a.lo, a.hi)


def _split_lv(lv: L.Lv, n: int):
    return (
        L.Lv(lv.v[:n], lv.lo, lv.hi),
        L.Lv(lv.v[n:], lv.lo, lv.hi),
    )


def _finish_sswu_from_candidates(u, x1, x2, gx1, gx2, parts1, parts2):
    """Exact-arithmetic tail of the SSWU map over kernel-computed
    candidates: the select tree of fq2_sqrt_flagged (a1==0 folding, QR
    candidate check, sgn0 correction) — only is_zero/eq/sgn0 and a few
    elementwise selects run here."""

    def sqrt_sel(g, parts):
        # same base-fold + assembly trees as fq2_sqrt_flagged (shared
        # helpers); only the candidate POWERS came from the kernel
        g0, g1v = tower.fq2_norm(g)
        s, ta_gen, tb_gen, ta_z, tb_z, y1a, y1b = parts
        a1_zero = fq.is_zero(g1v)
        inv2 = fq.const((P + 1) // 2, ())
        delta = fq.mul(fq.add(g0, s), inv2)
        delta2 = fq.mul(fq.sub(g0, s), inv2)
        base_a, _base_b = _sqrt_bases(g0, a1_zero, delta, delta2)
        ta = fq.select(a1_zero, ta_z, ta_gen)
        tb = fq.select(a1_zero, tb_z, tb_gen)
        ok_a = fq.eq(fq.sqr(ta), base_a)
        y1_gen = fq.select(ok_a, y1a, y1b)
        return _sqrt_assemble(
            (g0, g1v), a1_zero, ok_a, ta, tb, y1_gen
        )

    y1_, ok1 = sqrt_sel(gx1, parts1)
    y2_, _ok2 = sqrt_sel(gx2, parts2)
    x = tower.fq2_select(ok1, x1, x2)
    y = tower.fq2_select(ok1, y1_, y2_)
    flip = _sgn0(u) != _sgn0(y)
    y = tower.fq2_select(
        flip,
        (fq.normalize(fq.neg(y[0])), fq.normalize(fq.neg(y[1]))),
        y,
    )
    return x, y


def _sswu_iso_sum_tpu(u0, u1) -> C.JacPoint:
    """Pallas path: both draws batched through kernel S (chains +
    candidate field work VMEM-resident), the exact select tree in XLA,
    both isogenies through kernel I, one complete jacobian add."""
    from . import pallas_sswu as PS

    u0 = tower.fq2_norm(u0)
    u1 = tower.fq2_norm(u1)
    n = u0[0].v.shape[0]
    ucat = (_cat_lv(u0[0], u1[0]), _cat_lv(u0[1], u1[1]))
    d = PS.sswu_candidates(ucat)

    def half(i: int, name: str) -> L.Lv:
        return _split_lv(d[name], n)[i]

    def fin(i: int, u):
        x1 = (half(i, "x1_0"), half(i, "x1_1"))
        x2 = (half(i, "x2_0"), half(i, "x2_1"))
        gx1 = (half(i, "g1_0"), half(i, "g1_1"))
        gx2 = (half(i, "g2_0"), half(i, "g2_1"))
        parts1 = [
            half(i, k)
            for k in (
                "s_1", "ta_gen_1", "tb_gen_1", "ta_z_1", "tb_z_1",
                "y1a_1", "y1b_1",
            )
        ]
        parts2 = [
            half(i, k)
            for k in (
                "s_2", "ta_gen_2", "tb_gen_2", "ta_z_2", "tb_z_2",
                "y1a_2", "y1b_2",
            )
        ]
        return _finish_sswu_from_candidates(
            u, x1, x2, gx1, gx2, parts1, parts2
        )

    xa, ya = fin(0, u0)
    xb, yb = fin(1, u1)
    (xo_a, yo_a), (xo_b, yo_b) = PS.iso_map_pair(xa, ya, xb, yb)
    q0 = C.jac_from_affine(
        C.FQ2_OPS, tower.fq2_norm(xo_a), tower.fq2_norm(yo_a)
    )
    q1 = C.jac_from_affine(
        C.FQ2_OPS, tower.fq2_norm(xo_b), tower.fq2_norm(yo_b)
    )
    return C.jac_add(C.FQ2_OPS, q0, q1)


def sswu_iso_sum(u0, u1) -> C.JacPoint:
    """Both SSWU maps + isogeny + point add (pre-cofactor half of
    hash-to-G2; shared with bls/kernels.py _stage_sswu_iso). On TPU
    with 1-D batches the field core runs as the fused Pallas kernels
    (ops/pallas_sswu.py)."""
    if (
        chip.on_tpu()
        and u0[0].v.ndim == 2
    ):
        return _sswu_iso_sum_tpu(u0, u1)
    x0, y0 = _sswu(tower.fq2_norm(u0))
    x1, y1 = _sswu(tower.fq2_norm(u1))
    q0 = C.jac_from_affine(C.FQ2_OPS, *_iso_map(x0, y0))
    q1 = C.jac_from_affine(C.FQ2_OPS, *_iso_map(x1, y1))
    return C.jac_add(C.FQ2_OPS, q0, q1)


def hash_to_g2_device(u0, u1, batch) -> C.JacPoint:
    """(u0, u1) field draws -> G2 point (jacobian). The two SSWU maps
    and the isogeny run batched; the result is cofactor-cleared."""
    return g2_clear_cofactor(sswu_iso_sum(u0, u1), batch)


# ---------------------------------------------------------------------------
# host-side byte parsing (the only CPU work left per signature/message)
# ---------------------------------------------------------------------------


def parse_g2_compressed(raw: bytes):
    """96-byte compressed G2 -> (x_c0, x_c1, sign, ok). Pure int work,
    ~microseconds; rejects bad flag bits, non-canonical coordinates,
    and the infinity encoding (an identity signature is invalid for
    verification — api.decompress_signature semantics)."""
    if len(raw) != 96:
        return (0, 0, False, False)
    b0 = raw[0]
    if not (b0 & 0x80):  # compression bit must be set
        return (0, 0, False, False)
    if b0 & 0x40:  # infinity
        return (0, 0, False, False)
    sign = bool(b0 & 0x20)
    xc1 = int.from_bytes(
        bytes([b0 & 0x1F]) + raw[1:48], "big"
    )
    xc0 = int.from_bytes(raw[48:96], "big")
    if xc1 >= P or xc0 >= P:
        return (0, 0, False, False)
    return (xc0, xc1, sign, True)


def message_to_field_draws(message: bytes, dst: bytes):
    """expand_message_xmd + reduction: the host half of hash-to-G2
    (RFC 9380 hash_to_field, m=2, count=2)."""
    from ..crypto.bls.hash_to_curve import hash_to_field_fq2

    u0, u1 = hash_to_field_fq2(message, dst, 2)
    return u0, u1
