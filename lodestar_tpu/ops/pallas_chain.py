"""Pallas TPU kernel: fused fixed-exponent Fp power chains.

Why: the ingest pipeline's sqrt/inverse chains are ~381-step
square-and-multiply loops. As XLA scans, every step round-trips the
(batch, 40)-limb state through HBM (~0.15 ms/step at batch 2048 —
bandwidth-bound), so one chain costs ~60+ ms and the ingest stages
stack up ~16 of them. This kernel runs the WHOLE chain with the limb
state resident in VMEM: per step only register/VMEM traffic, turning
the chain compute-bound (~100 vector ops per modular multiply).

Layout: limbs on SUBLANES (40 rows, statically indexed — no lane
shuffles, the failure mode of earlier Pallas attempts), batch on
LANES (128 per grid block). The exponent is a static python int baked
into the kernel via an SMEM bit array + fori_loop.

Used by ops/ingest.py when running on a real TPU; the XLA scan
(fq.pow_const) remains the fallback and the differential oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto.bls.fields import P
from . import limbs as L

NLIMB = 39  # value limbs (see ops/limbs.py)
ROWS = 40  # canonical row count (39 + carry)
PAD_ROWS = 80  # product accumulator rows (79 used, padded to 8k)
LANES = 128  # batch elements per grid block


FOLD_ROWS = 48  # 41 used (limbs 40..80), padded to a sublane multiple


@functools.lru_cache(maxsize=None)
def _fold_rows() -> np.ndarray:
    """(48, 40) int32: row k = canonical limbs of 2^(10*(40+k)) mod P.
    Rows 0..39 fold product limbs 40..79; row 40 folds the explicit
    carry captured out of accumulator row 79 (weight 2^800)."""
    out = np.zeros((FOLD_ROWS, ROWS), np.int32)
    for k in range(41):
        out[k, :NLIMB] = L.int_to_limbs(pow(2, L.BITS * (40 + k), P))
    return out


def _carry(acc, passes: int):
    """Parallel carry passes: limb = limb&1023 + incoming carry.
    Non-negative inputs only. Keeps shape; carries out of the top row
    are folded by the caller's fold step (values stay < 2^31)."""
    for _ in range(passes):
        hi = acc >> L.BITS
        lo = acc - (hi << L.BITS)
        shifted = jnp.concatenate(
            [jnp.zeros((1, acc.shape[1]), jnp.int32), hi[:-1, :]],
            axis=0,
        )
        acc = lo + shifted
    return acc


def _fold_contract_vpu(lo, hi, extra, fold_const):
    """Fold rows 40..79 + the explicit top carry through the constant
    2^(10k) mod P rows as 41 broadcast MACs on the VPU."""
    for k in range(ROWS):
        lo = lo + fold_const[k].reshape(ROWS, 1) * hi[k : k + 1, :]
    return lo + fold_const[ROWS].reshape(ROWS, 1) * extra


def _fold_contract_mxu(lo, hi, extra, f_lo8, f_hi8):
    """The same fold as THREE int8 x int8 -> int32 dot_generals on the
    MXU (quantized-GEMM shape; contraction over the 48-row axis of the
    CONSTANT fold matrix, so the systolic array sees shared weights).

    Exactness (static): hi rows are <= ~1088 and the captured top
    carry <= 64 (see _modmul_core's carry analysis), so the value-side
    hi slice is <= 8; fold rows are canonical limbs < 2^10, so the
    matrix-side hi slice is <= 7. All three accumulations stay far
    inside int32 (<= 96*127*127 < 2^21 per column) and the shifted
    recombination peaks below 2^26 — the same bound as the VPU fold
    sum. The per-lane schoolbook product CANNOT move to the MXU (both
    operands vary per lane — there is no shared contraction matrix);
    the fold is the kernel's one matmul-shaped contraction."""
    s = L.MXU_SLICE_BITS
    W = hi.shape[-1]
    # value side: rows 40..79 + explicit carry, zero-padded to the
    # fold matrix's 48 rows (rows 41..47 of the matrix are zero too)
    hi_all = jnp.concatenate(
        [hi, extra, jnp.zeros((FOLD_ROWS - ROWS - 1, W), jnp.int32)],
        axis=0,
    )
    h_lo, h_hi = L._slice8(hi_all)

    def dg(m8, v8):
        return jax.lax.dot_general(
            m8,
            v8,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    c0 = dg(f_lo8, h_lo)
    c1 = dg(
        jnp.concatenate([f_lo8, f_hi8], axis=0),
        jnp.concatenate([h_hi, h_lo], axis=0),
    )
    c2 = dg(f_hi8, h_hi)
    return lo + c0 + ((c1 + (c2 << s)) << s)


def make_modmul(fold_const):
    """Modular-multiply closure over a loaded fold-constant block,
    with the fold contraction picked by the limb backend at TRACE
    time (ops/limbs.get_backend): int8 MXU dots for "mxu", broadcast
    VPU MACs for "vpu". The int8 constant slices are hoisted out of
    the returned closure so chained calls (power chains run hundreds)
    share them."""
    f8 = L._slice8(fold_const) if L.get_backend() == "mxu" else (None, None)

    def mm(a, b):
        return _modmul_call(a, b, fold_const, *f8)

    return mm


@jax.jit
def _modmul_call(a, b, fold_const, f_lo8, f_hi8):
    """_modmul_core as one jitted function (MXU fold when the int8
    slices are given). A kernel body traces it once per shape, and each
    of its hundreds of call sites is one call equation: tracing the
    main path's kernels takes a quarter of the time it took with the
    multiply inlined at every site. Pallas inlines the call when it
    lowers the kernel, so the kernel's code is the same."""
    if f_lo8 is None:

        def fold(lo, hi, extra):
            return _fold_contract_vpu(lo, hi, extra, fold_const)

    else:

        def fold(lo, hi, extra):
            return _fold_contract_mxu(lo, hi, extra, f_lo8, f_hi8)

    return _modmul_core(a, b, fold_const, fold)


def _modmul_core(a, b, fold_const, fold):
    """(40, W) x (40, W) canonical non-negative limbs -> (40, W) for
    any lane width W (128 for full blocks; the lane-halving product
    reduction calls at 64..1).

    Schoolbook product into an 80-row accumulator via 40 broadcast
    MACs (static sublane slices), parallel carries, `fold` contraction
    of limbs 40..78 (VPU MACs or MXU int8 dots — see make_modmul),
    final carry + one-row refold."""
    W = b.shape[-1]
    # Schoolbook accumulation as a sum of zero-padded shifted terms:
    # Mosaic lowers neither scatter-add nor value dynamic_slice, but
    # static concatenation + adds vectorize cleanly.
    acc = jnp.zeros((PAD_ROWS, W), jnp.int32)
    for i in range(ROWS):
        term = a[i : i + 1, :] * b  # (40, W)
        parts = []
        if i:
            parts.append(jnp.zeros((i, W), jnp.int32))
        parts.append(term)
        parts.append(
            jnp.zeros((PAD_ROWS - ROWS - i, W), jnp.int32)
        )
        acc = acc + jnp.concatenate(parts, axis=0)
    # limbs <= 40 * 1025^2 < 2^26. Pass 1 brings them <= 1023 + 2^16
    # without losing anything (row 79 only RECEIVES carry in pass 1).
    acc = _carry(acc, 1)
    # Pass 2 with the row-79 outgoing carry captured explicitly: its
    # weight is limb 80 and it folds through fold row 40.
    hi2 = acc >> L.BITS
    lo2 = acc - (hi2 << L.BITS)
    extra = hi2[PAD_ROWS - 1 : PAD_ROWS, :]  # <= 64, weight 2^800
    acc = lo2 + jnp.concatenate(
        [jnp.zeros((1, W), jnp.int32), hi2[:-1, :]], axis=0
    )
    lo = acc[:ROWS, :]
    hi = acc[ROWS:, :]  # rows 40..79, limbs <= ~1088
    lo = fold(lo, hi, extra)
    # fold sum < 41 * 1088 * 1023 < 2^26. Reduce with capture-and-fold
    # rounds: every carry pass captures the row-39 outgoing carry
    # (weight = limb 40) and folds it straight back through fold row 0
    # — a plain carry would silently DROP it. Four rounds bring the
    # worst case down to a canonical-profile value.
    fold0 = fold_const[0].reshape(ROWS, 1)
    for _ in range(4):
        hi_ = lo >> L.BITS
        lo = lo - (hi_ << L.BITS)
        top = hi_[ROWS - 1 : ROWS, :]
        lo = (
            lo
            + jnp.concatenate(
                [jnp.zeros((1, W), jnp.int32), hi_[:-1, :]],
                axis=0,
            )
            + fold0 * top
        )
    return lo


WINDOW = 4  # fixed-window width for pow chains (15-entry table)


def window_schedule(e: int, w: int) -> np.ndarray:
    """MSB-first `w`-bit windows of e, zero-padded at the top so the
    first window is the leading 1..w bits (always nonzero)."""
    nb = e.bit_length()
    nwin = -(-nb // w)
    padded = nwin * w
    return np.array(
        [(e >> (padded - w * (i + 1))) & ((1 << w) - 1)
         for i in range(nwin)],
        np.int32,
    )


def make_windowed_powc(mm, window: int):
    """Windowed fixed-exponent power chain for in-kernel use.

    Square-and-multiply costs 2 modmuls per exponent bit (the multiply
    runs even for 0 bits, then a select drops it). Fixed `window`-bit
    windows cost `window` squarings + ONE table multiply per window:
    ~1.25 modmuls/bit at window 4 — a ~1.55x cut on the chain-dominated
    ingest stages. The table select is a (2^w-1)-way jnp.where chain on
    (ROWS, W) planes, trivial next to a modmul; table[0] is the
    canonical 1 so zero windows multiply by one instead of branching.

    Returns powc(base, win_ref, n_windows) where win_ref holds the
    int32 window values (SMEM) computed by window_schedule()."""

    def powc(base, win_ref, n_windows):
        W = base.shape[-1]
        one = jnp.concatenate(
            [jnp.ones((1, W), jnp.int32),
             jnp.zeros((base.shape[0] - 1, W), jnp.int32)],
            axis=0,
        )
        table = [one, base]
        for _ in range(2, 1 << window):
            table.append(mm(table[-1], base))

        def sel(wv):
            acc = table[0]
            for k in range(1, 1 << window):
                acc = jnp.where(wv == k, table[k], acc)
            return acc

        def body(i, acc):
            for _ in range(window):
                acc = mm(acc, acc)
            return mm(acc, sel(win_ref[i]))

        return jax.lax.fori_loop(1, n_windows, body, sel(win_ref[0]))

    return powc


def _chain_kernel(win_ref, fold_ref, base_ref, out_ref, *, nwin: int):
    fold_const = fold_ref[:]
    base = base_ref[:]
    mm = make_modmul(fold_const)
    powc = make_windowed_powc(mm, WINDOW)
    out_ref[:] = powc(base, win_ref, nwin)


@functools.lru_cache(maxsize=None)
def _chain_call(e: int, n_blocks: int):
    wins = window_schedule(e, WINDOW)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel = functools.partial(_chain_kernel, nwin=len(wins))

    @jax.jit
    def run(base):  # base: (40, n_blocks*128), limbs on sublanes
        return pl.pallas_call(
            kernel,
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(
                    (FOLD_ROWS, ROWS),
                    lambda i: (0, 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (ROWS, LANES), lambda i: (0, i), memory_space=pltpu.VMEM
                ),
            ],
            out_specs=pl.BlockSpec(
                (ROWS, LANES), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
            out_shape=jax.ShapeDtypeStruct(
                (ROWS, n_blocks * LANES), jnp.int32
            ),
        )(jnp.asarray(wins), jnp.asarray(_fold_rows()), base)

    return run


def pow_const(a: L.Lv, e: int) -> L.Lv:
    """Drop-in for fq.pow_const on TPU: a^(e) for batched canonical
    values, whole chain fused in one Pallas kernel. Batch must be 1-D;
    padded to a multiple of 128 lanes."""
    assert e > 0
    x = L.normalize(a)
    v = x.v  # (batch, NCANON)
    batch = v.shape[0]
    n_blocks = -(-batch // LANES)
    padded = n_blocks * LANES
    vt = jnp.transpose(
        jnp.pad(v, ((0, padded - batch), (0, 0)))
    )  # (40, padded) limbs-on-sublanes
    out = _chain_call(e, n_blocks)(vt)
    res = jnp.transpose(out)[:batch, :]
    # HONEST bounds: the kernel's final capture-and-fold rounds leave
    # limbs <= ~1025 everywhere INCLUDING row 39 (fold rows have zero
    # top limbs, but row 39 still receives ordinary carries), so the
    # value can exceed the canonical-profile claim. Downstream
    # normalize()/canon_digits stay sound because the interval
    # machinery sees these wider bounds and reduces accordingly.
    hi = tuple([L.B + 2] * L.NCANON)
    return L.Lv(res, tuple([0] * L.NCANON), hi)
