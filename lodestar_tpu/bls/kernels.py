"""TPU batch-verification kernels for BLS signatures.

Reference analog: the blst entry points Lodestar's BLS pool calls
(SURVEY.md §2.3): `verifyMultipleAggregateSignatures` (random
linear-combination batch verify, chain/bls/maybeBatch.ts:17) and
`aggregateWithRandomness` (same-message aggregation,
chain/bls/multithread/jobItem.ts:73 — the measured main-thread
bottleneck, ~2 min/epoch on CPU). Both become staged device programs:
64-bit random-weighted scalar ladders, a log-depth aggregate tree, a
batched Miller loop, and one shared final exponentiation.

The pipeline used to be jitted in eight stages (XLA's compile time
punishes one giant graph superlinearly), which kept each compile small
but cost ~2 ms of host dispatch glue per seam — ~16 ms per wave. The
default composition is now the FUSED one (ISSUE 16): each wave runs
≤3 jit programs — prepare (ingest decompress + hash-to-G2 + ladders +
assembly), pairing (miller + product), and final-exp+verdict (batch
shape (), compiled once for every bucket size) — with
`jax.named_scope` regions preserving per-sub-stage attribution inside
the fused graphs and the persistent disk cache (utils/jaxcache.py) +
background warmup amortizing the bigger compiles. The per-stage
programs remain as the differential oracle, the rollback lever
(`LODESTAR_TPU_FUSED_STAGES=0` / `set_fused_stages(False)`), and the
CPU-emulation default (the fused graphs take XLA's single-core
compiler many minutes, so fusion defaults on only for TPU). Callers
pad to a bucket size and pass a mask (SURVEY.md §7 hard part 2: padded
static shapes avoid recompiles). All stages broadcast over a leading
batch axis; the whole-bucket mesh entries (`run_verify_*_mesh`) shard
it so each chip owns whole sub-buckets and the only collective is one
verdict psum.
"""

from __future__ import annotations

import functools
import os
import random
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto.bls import curve as oc
from ..metrics import device as _telemetry
from ..ops import curve as C
from ..ops import fq, pairing, tower
from ..ops import limbs as L
from ..utils import chip, jaxcache

RAND_BITS = 64  # blst's randomness width for batch verify

# The fused kernels compile multi-minute programs; every entry point in
# this module must hit the persistent cache, so enable it at import.
jaxcache.enable()


def _g1_neg_gen(batch=()):
    """-G1 generator as canonical device coords."""
    x, y = oc.g1_neg(oc.G1_GEN)
    return (
        L.normalize(L.const(x, batch)),
        L.normalize(L.const(y, batch)),
    )


def _to_affine(ops, p: C.JacPoint):
    """Jacobian -> affine on device via one batched Fermat inversion.
    Infinity slots yield garbage coords — callers mask them."""
    if ops is C.FQ_OPS:
        zinv = fq.inv(p.z)
        zinv2 = fq.sqr(zinv)
        return fq.mul(p.x, zinv2), fq.mul(p.y, fq.mul(zinv2, zinv))
    zinv = tower.fq2_inv(p.z)
    zinv2 = tower.fq2_sqr(zinv)
    x = tower.fq2_mul(p.x, zinv2)
    y = tower.fq2_mul(p.y, tower.fq2_mul(zinv2, zinv))
    return C.FQ2_OPS.norm(x), C.FQ2_OPS.norm(y)


# Performance state: see COVERAGE.md's "Device stage budget" table for
# the LIVE per-stage numbers (that file is re-measured every round;
# this module's comments are not). The stage split below is the part
# that stays true by construction.
#
# --- staged device programs ------------------------------------------------
#
# Round-1 ran six jitted stages with EAGER glue between them (concats,
# normalizes, constants) — ~1 s of per-op host->device dispatches per
# verify. Round-2 first fused everything into ONE jit,
# which removed the glue but exploded XLA compile time (>10 min on the
# real chip; the driver's bench timed out). Measured per-piece compile
# on the chip: ladders ~9 s, unrolled jac_sum tree ~30 s, Miller loop
# ~94 s, product+final-exp ~357 s. The per-stage split below — all
# glue inside a stage, ~1 ms dispatch between stages — with scan-based
# reductions (curve.jac_sum_scan, pairing._fq12_masked_product,
# pairing._pow_u) keeps each compile bounded, and the final-exp stage
# has batch shape () so it compiles exactly once for every bucket
# size. The DEFAULT composition is now the 3-program fused one (see
# the fused-stage section below): each stage body lives in an
# un-jitted `*_impl` the fused programs, the per-stage programs, and
# the whole-bucket mesh programs all share, so the two compositions
# cannot drift and the per-stage path stays available as the
# differential oracle + rollback (`LODESTAR_TPU_FUSED_STAGES=0`).

# Fused vs per-stage composition knob. The fused programs are the
# bigger compiles the round-2 comment above warns about; on TPU the
# persistent cache + background warmup pay them once per host. On the
# CPU emulation backend XLA's single-core compile of the fused graphs
# runs to many minutes (the slow-compile alarm fires), so the default
# there stays per-stage; an explicit LODESTAR_TPU_FUSED_STAGES=1 (or
# set_fused_stages(True)) still opts in anywhere. None = not decided
# yet: the platform default is taken at the first wave, not at import
# (asking the backend binds the chip).
_FUSED_STAGES: bool | None = (
    os.environ["LODESTAR_TPU_FUSED_STAGES"] != "0"
    if "LODESTAR_TPU_FUSED_STAGES" in os.environ
    else None
)


def fused_stages_on() -> bool:
    """Whether waves dispatch the fused ≤3-program composition."""
    global _FUSED_STAGES
    if _FUSED_STAGES is None:
        _FUSED_STAGES = chip.on_tpu()
    return _FUSED_STAGES


def set_fused_stages(on: bool) -> None:
    """Flip the fused/per-stage composition at runtime (both program
    families can coexist in the jit caches; no invalidation needed)."""
    global _FUSED_STAGES
    _FUSED_STAGES = bool(on)


def _prepare_batch_impl(pk: C.JacPoint, hx, hy, sig: C.JacPoint, bits, mask):
    """Random-weighted ladders + masked G2 aggregation + batched
    affine conversion + pairing-input assembly (n+1 pairs). On TPU the
    G2 ladder (the expensive one) runs as the fused Pallas kernel
    (ops/pallas_ladder.py: 160 ms vs scan at batch 2048)."""
    if chip.on_tpu() and bits.ndim == 2:
        from ..ops import pallas_ladder as PL

        rpk = PL.g1_scalar_mul(pk.x, pk.y, bits, pk.inf)
        rsig = PL.g2_scalar_mul(sig.x, sig.y, bits, sig.inf)
    else:
        rpk = C.scalar_mul(C.FQ_OPS, pk.x, pk.y, bits, pk.inf)
        rsig = C.scalar_mul(C.FQ2_OPS, sig.x, sig.y, bits, sig.inf)
    rsig = C.jac_select(
        C.FQ2_OPS, mask, rsig, C.jac_infinity(C.FQ2_OPS, mask.shape)
    )
    if chip.on_tpu() and bits.ndim == 2:
        from ..ops import pallas_pairing as PP

        s = PP.g2_sum(rsig)
    else:
        s = C.jac_sum_scan(C.FQ2_OPS, rsig)
    s_aff = _to_affine(C.FQ2_OPS, s)
    rpk_aff = _to_affine(C.FQ_OPS, rpk)
    ngx, ngy = _g1_neg_gen((1,))
    px = _cat_fq(rpk_aff[0], ngx)
    py = _cat_fq(rpk_aff[1], ngy)
    qx = _cat_fq2((hx[0], hx[1]), s_aff[0])
    qy = _cat_fq2((hy[0], hy[1]), s_aff[1])
    full_mask = jnp.concatenate([mask, jnp.asarray([True])])
    return px, py, qx, qy, full_mask


_stage_prepare_batch = jax.jit(_prepare_batch_impl)


# Device ingest is gated by bucket size: each ingest stage is a
# multi-minute XLA compile per bucket size, so compiling it for the
# tiny 4..128 retry buckets would multiply warmup cost for no
# throughput (small buckets are host-prep-affordable: 128 sets x
# ~2.5 ms). The gate is a KNOB (LODESTAR_TPU_INGEST_MIN_BUCKET /
# set_ingest_min_bucket): the default admits the mid {256, 512}
# buckets the verifier's rolling gossip accumulator flushes, whose
# compiles warmup_ingest() pre-warms in the background through the
# persistent cache (utils/jaxcache.py). Tests lower it further to
# exercise the device path on small CPU batches.
INGEST_MIN_BUCKET = int(
    os.environ.get("LODESTAR_TPU_INGEST_MIN_BUCKET", 256)
)


def ingest_min_bucket() -> int:
    """The live device-ingest gate (module attr so tests can patch)."""
    return INGEST_MIN_BUCKET


def set_ingest_min_bucket(n: int, rewarm: bool = True) -> None:
    """Move the device-ingest gate at runtime.

    LOWERING the gate makes rungs eligible whose ingest pipelines were
    never compiled: warmup_progress() recomputes eligibility from the
    live gate at every scrape (so the `lodestar_jax_warmup_*` gauges
    drop honestly instead of reporting the old, fully-warm set), and —
    when a warmup ran in this process — the newly eligible COLD rungs
    are re-warmed on the background thread, otherwise a cold-fallback
    verifier would route them host_cold forever (nothing else marks a
    size warm without a live ingest dispatch, which the fallback
    prevents). rewarm=False skips the kick (tests, tools that manage
    warmup themselves)."""
    global INGEST_MIN_BUCKET
    old = INGEST_MIN_BUCKET
    INGEST_MIN_BUCKET = int(n)
    if not rewarm or INGEST_MIN_BUCKET >= old:
        return
    if not _WARMUP_STARTED:
        # no warmup policy in this process (bench/test/mesh node):
        # kicking multi-minute compiles behind a setter would be rude
        return
    newly = tuple(
        b
        for b in default_warmup_sizes(INGEST_MIN_BUCKET)
        if b < old and not ingest_is_warm(b)
    )
    if newly:
        warmup_ingest(newly)


def _g2_sqrt_impl(sig_x, sig_sign):
    """Ingest sub-stage 1: y from the curve equation + QR flag + spec
    sign selection (shared impl: ops/ingest.g2_sqrt_with_sign). Split
    from the subgroup check so each per-stage compiled graph stays
    small (compile time is superlinear in op count — an early fused
    ingest stage compiled >58 min on the chip; the fused composition
    below re-pays that once through the persistent cache)."""
    from ..ops import ingest

    return ingest.g2_sqrt_with_sign(sig_x, sig_sign)


def _g2_subgroup_impl(x, y, is_qr, mask):
    """Ingest sub-stage 2: psi subgroup check; returns the point and
    the combined validity conjunction (padding auto-valid)."""
    from ..ops import ingest

    q = C.jac_from_affine(C.FQ2_OPS, x, y)
    valid = jnp.logical_and(
        is_qr, ingest.g2_in_subgroup(q, mask.shape)
    )
    return q, jnp.all(jnp.logical_or(valid, ~mask))


def _g2_decompress_impl(sig_x, sig_sign, mask):
    x, y, is_qr = _g2_sqrt_impl(sig_x, sig_sign)
    return _g2_subgroup_impl(x, y, is_qr, mask)


def _sswu_iso_impl(u0, u1):
    """Ingest sub-stage 3: both SSWU maps + isogeny + point add
    (shared impl: ops/ingest.sswu_iso_sum)."""
    from ..ops import ingest

    return ingest.sswu_iso_sum(u0, u1)


def _cofactor_impl(s, mask):
    """Ingest sub-stage 4: psi cofactor clearing + affine conversion."""
    from ..ops import ingest

    h = ingest.g2_clear_cofactor(s, mask.shape)
    return _to_affine(C.FQ2_OPS, h)


def _hash_to_g2_impl(u0, u1, mask):
    return _cofactor_impl(_sswu_iso_impl(u0, u1), mask)


_stage_g2_sqrt = jax.jit(_g2_sqrt_impl)
_stage_g2_subgroup = jax.jit(_g2_subgroup_impl)
_stage_sswu_iso = jax.jit(_sswu_iso_impl)
_stage_cofactor = jax.jit(_cofactor_impl)


def _stage_g2_decompress(sig_x, sig_sign, mask):
    x, y, is_qr = _stage_g2_sqrt(sig_x, sig_sign)
    return _stage_g2_subgroup(x, y, is_qr, mask)


def _stage_hash_to_g2(u0, u1, mask):
    return _stage_cofactor(_stage_sswu_iso(u0, u1), mask)


@jax.jit
def _stage_final_with_valid(prod, all_valid):
    """Final exponentiation AND the ingest validity conjunction.
    Calls the UNINSTRUMENTED final-exp impl: this body runs at trace
    time inside its own jit, and routing it through the telemetry
    wrapper would record the tracer's call as a dispatch and poison
    the retrace detector's seen-signature set for stage 'final'."""
    return jnp.logical_and(_final_expo_impl(prod), all_valid)


def run_verify_batch_ingest_async(
    pk: C.JacPoint, sig_x, sig_sign, u0, u1, rand_bits, mask
):
    """Batch verify with device-side ingestion; returns the device ()
    bool WITHOUT readback (see run_verify_batch_async). Default: the
    fused 3-program composition (prepare / pairing / final). With
    fused stages off, composes the per-stage programs so each compiled
    artifact stays small."""
    jaxcache.enable()
    if fused_stages_on():
        _note_donation(_INGEST_BATCH_DONATED + _PAIRING_DONATED)
        px, py, qx, qy, pair_mask, all_valid = _fused_ingest_batch(
            pk, sig_x, sig_sign, u0, u1, rand_bits, mask
        )
        prod = _fused_pairing(px, py, qx, qy, pair_mask)
        return _stage_final_with_valid(prod, all_valid)
    sig, all_valid = _stage_g2_decompress(sig_x, sig_sign, mask)
    hx, hy = _stage_hash_to_g2(u0, u1, mask)
    px, py, qx, qy, pair_mask = _stage_prepare_batch(
        pk, hx, hy, sig, rand_bits, mask
    )
    f = _stage_miller(px, py, qx, qy)
    prod = _stage_product(f, pair_mask)
    return _stage_final_with_valid(prod, all_valid)


def run_verify_same_message_ingest_async(
    pk: C.JacPoint, h, sig_x, sig_sign, rand_bits, mask
):
    """Same-message verify with device-side signature decompression
    (the message is hashed once on host — amortized across the whole
    group by the attData-keyed queue)."""
    jaxcache.enable()
    if fused_stages_on():
        _note_donation(_INGEST_SAME_MSG_DONATED + _PAIRING_DONATED)
        px, py, qx, qy, pair_mask, all_valid = (
            _fused_ingest_same_message(
                pk, h[0], h[1], sig_x, sig_sign, rand_bits, mask
            )
        )
        prod = _fused_pairing(px, py, qx, qy, pair_mask)
        return _stage_final_with_valid(prod, all_valid)
    sig, all_valid = _stage_g2_decompress(sig_x, sig_sign, mask)
    px, py, qx, qy, pair_mask = _stage_prepare_same_message(
        pk, h[0], h[1], sig, rand_bits, mask
    )
    f = _stage_miller(px, py, qx, qy)
    prod = _stage_product(f, pair_mask)
    return _stage_final_with_valid(prod, all_valid)


def _prepare_same_message_impl(
    pk: C.JacPoint, hx, hy, sig: C.JacPoint, bits, mask
):
    """Both random-weighted MSMs (aggregateWithRandomness on device —
    the reference's measured main-thread bottleneck, jobItem.ts:60-75)
    + pairing-input assembly (2 pairs)."""
    if chip.on_tpu() and bits.ndim == 2:
        from ..ops import pallas_ladder as PL

        rpk = PL.g1_scalar_mul(pk.x, pk.y, bits, pk.inf)
        rsig = PL.g2_scalar_mul(sig.x, sig.y, bits, sig.inf)
    else:
        rpk = C.scalar_mul(C.FQ_OPS, pk.x, pk.y, bits, pk.inf)
        rsig = C.scalar_mul(C.FQ2_OPS, sig.x, sig.y, bits, sig.inf)
    rpk = C.jac_select(
        C.FQ_OPS, mask, rpk, C.jac_infinity(C.FQ_OPS, mask.shape)
    )
    rsig = C.jac_select(
        C.FQ2_OPS, mask, rsig, C.jac_infinity(C.FQ2_OPS, mask.shape)
    )
    apk_aff = _to_affine(C.FQ_OPS, C.jac_sum_scan(C.FQ_OPS, rpk))
    asig_aff = _to_affine(C.FQ2_OPS, C.jac_sum_scan(C.FQ2_OPS, rsig))
    ngx, ngy = _g1_neg_gen((1,))
    px = _cat_fq(apk_aff[0], ngx)
    py = _cat_fq(apk_aff[1], ngy)
    qx = _cat_fq2((hx[0], hx[1]), asig_aff[0])
    qy = _cat_fq2((hy[0], hy[1]), asig_aff[1])
    return px, py, qx, qy, jnp.asarray([True, True])


_stage_prepare_same_message = jax.jit(_prepare_same_message_impl)


def _miller_impl(px, py, qx, qy):
    """Miller loop body with the Pallas/XLA split resolved at TRACE
    time — shared by the per-stage jit, the fused pairing program,
    and the whole-bucket mesh programs."""
    if _pallas_pairing_on():
        from ..ops import pallas_pairing as PP

        return PP.miller_loop(px, py, qx, qy)
    return pairing.miller_loop(px, py, qx, qy)


def _product_impl(f, mask):
    if _pallas_pairing_on():
        from ..ops import pallas_pairing as PP

        return PP.fq12_masked_product(f, mask)
    return pairing._fq12_masked_product(f, mask)


def _final_expo_impl(prod):
    if _pallas_pairing_on():
        from ..ops import pallas_pairing as PP

        return pairing.fq12_is_one(PP.final_exponentiation(prod))
    return pairing.fq12_is_one(pairing.final_exponentiation(prod))


_stage_miller_xla = jax.jit(pairing.miller_loop)
_stage_product_xla = jax.jit(pairing._fq12_masked_product)


@jax.jit
def _stage_product_pallas(f, mask):
    from ..ops import pallas_pairing as PP

    return PP.fq12_masked_product(f, mask)


def _stage_product(f, mask):
    """Masked pairing-product reduction: lane-halving VMEM kernel on
    TPU for big buckets, XLA scan+tree elsewhere."""
    if _pallas_pairing_on():
        return _stage_product_pallas(f, mask)
    return _stage_product_xla(f, mask)


def _pallas_pairing_on() -> bool:
    """The fused Miller/final-exp kernels run only on real TPUs (the
    XLA scan path stays as CPU fallback + differential oracle)."""
    return chip.on_tpu()


@jax.jit
def _stage_miller_pallas(px, py, qx, qy):
    from ..ops import pallas_pairing as PP

    return PP.miller_loop(px, py, qx, qy)


def _stage_miller(px, py, qx, qy):
    """Miller loop: VMEM-resident Pallas ladder on TPU (the round-3
    device-time wall — 63 scan steps round-tripping the Fq12 state
    through HBM), XLA scan elsewhere."""
    if _pallas_pairing_on():
        return _stage_miller_pallas(px, py, qx, qy)
    return _stage_miller_xla(px, py, qx, qy)


# --- fused stage programs ---------------------------------------------------
#
# The ≤3-program wave composition (ISSUE 16): prepare (decompress +
# hash-to-G2 + ladders + aggregation + pairing-input assembly),
# pairing (miller + product), final (batch shape (), shared with the
# per-stage path). Each fused body is a composition of the SAME
# un-jitted `*_impl` functions the per-stage programs jit, wrapped in
# `jax.named_scope` regions so profiler captures keep per-sub-stage
# attribution inside the fused graphs. Input buffers are DONATED to
# the fused programs on TPU (`donate_argnums`): a wave's limb tensors
# are built fresh per dispatch and never reused by the host, so XLA
# may reuse their device memory for outputs — which is what lets the
# double-buffered verifier keep depth>1 waves in flight without 2x
# peak HBM. Donation is skipped off-TPU where the CPU backend ignores
# it with a per-dispatch warning.

# decided when the fused programs are first built (None until then):
# asking the backend at import would bind the chip
_DONATION_ARMED: bool | None = None
# donated argument positions per fused entry (the big per-wave limb
# tensors; small masks/signs stay undonated)
_INGEST_BATCH_DONATE = (0, 1, 3, 4, 5)  # pk, sig_x, u0, u1, bits
_INGEST_SAME_MSG_DONATE = (0, 2, 5)  # pk, sig_x, bits
_PAIRING_DONATE = (0, 1, 2, 3)  # px, py, qx, qy
_INGEST_BATCH_DONATED = len(_INGEST_BATCH_DONATE)
_INGEST_SAME_MSG_DONATED = len(_INGEST_SAME_MSG_DONATE)
_PAIRING_DONATED = len(_PAIRING_DONATE)


def donation_armed() -> bool:
    """Whether fused dispatches donate their input buffers (TPU)."""
    global _DONATION_ARMED
    if _DONATION_ARMED is None:
        _DONATION_ARMED = chip.on_tpu()
    return _DONATION_ARMED


def _note_donation(n: int) -> None:
    """Count donated-buffer reuse opportunities handed to XLA (feeds
    lodestar_jax_donated_buffer_reuse_total; honest 0 off-TPU)."""
    if donation_armed():
        t = _telemetry.get_telemetry()
        if t is not None:
            t.note_donation(n)


def _fused_program(fn, donate: tuple[int, ...]):
    """`fn` jitted at its first call, donating `donate` only where
    donation is armed (decided then, not at import)."""
    built: list = []

    def call(*args):
        if not built:
            built.append(
                jax.jit(fn, donate_argnums=donate if donation_armed() else ())
            )
        return built[0](*args)

    call.__name__ = fn.__name__
    return call


def _fused_ingest_batch_fn(pk, sig_x, sig_sign, u0, u1, bits, mask):
    with jax.named_scope("g2_decompress"):
        sig, all_valid = _g2_decompress_impl(sig_x, sig_sign, mask)
    with jax.named_scope("hash_to_g2"):
        hx, hy = _hash_to_g2_impl(u0, u1, mask)
    with jax.named_scope("prepare"):
        px, py, qx, qy, pair_mask = _prepare_batch_impl(
            pk, hx, hy, sig, bits, mask
        )
    return px, py, qx, qy, pair_mask, all_valid


def _fused_ingest_same_message_fn(
    pk, hx, hy, sig_x, sig_sign, bits, mask
):
    with jax.named_scope("g2_decompress"):
        sig, all_valid = _g2_decompress_impl(sig_x, sig_sign, mask)
    with jax.named_scope("prepare"):
        px, py, qx, qy, pair_mask = _prepare_same_message_impl(
            pk, hx, hy, sig, bits, mask
        )
    return px, py, qx, qy, pair_mask, all_valid


def _fused_pairing_fn(px, py, qx, qy, pair_mask):
    with jax.named_scope("miller"):
        f = _miller_impl(px, py, qx, qy)
    with jax.named_scope("product"):
        return _product_impl(f, pair_mask)


_fused_ingest_batch = _fused_program(
    _fused_ingest_batch_fn, _INGEST_BATCH_DONATE
)
_fused_ingest_same_message = _fused_program(
    _fused_ingest_same_message_fn, _INGEST_SAME_MSG_DONATE
)
_fused_pairing = _fused_program(_fused_pairing_fn, _PAIRING_DONATE)


# --- device telemetry instrumentation --------------------------------------
#
# Every jit entry point of the pipeline is wrapped so the telemetry
# layer (metrics/device.py) can attribute backend compiles to a stage,
# detect retraces (a compile for an argument signature the entry point
# already served — the fingerprint of a clear_caches / backend-switch
# storm), and time dispatches. With no telemetry installed each
# wrapper is a single attribute check, so benches and tools measure
# the bare pipeline unless they opt in. Only HOST-side entry points
# are wrapped; a stage that another stage calls from INSIDE a jit
# must call an UN-instrumented impl (_final_expo_impl, the fused
# bodies' sub-stage impls) or the tracer's call would be recorded as
# a dispatch. The fused programs are instrumented under the 3-row
# stage names of COVERAGE.md's re-cut budget table: "prepare" (both
# fused ingest entries — distinct arg signatures keep the retrace
# detector honest), "pairing", "final" (shared with the per-stage
# path).

_fused_ingest_batch = _telemetry.instrument_stage(
    "prepare", _fused_ingest_batch
)
_fused_ingest_same_message = _telemetry.instrument_stage(
    "prepare", _fused_ingest_same_message
)
_fused_pairing = _telemetry.instrument_stage("pairing", _fused_pairing)

_stage_prepare_batch = _telemetry.instrument_stage(
    "prepare_batch", _stage_prepare_batch
)
_stage_prepare_same_message = _telemetry.instrument_stage(
    "prepare_same_message", _stage_prepare_same_message
)
_stage_g2_sqrt = _telemetry.instrument_stage("g2_sqrt", _stage_g2_sqrt)
_stage_g2_subgroup = _telemetry.instrument_stage(
    "g2_subgroup", _stage_g2_subgroup
)
_stage_sswu_iso = _telemetry.instrument_stage("sswu_iso", _stage_sswu_iso)
_stage_cofactor = _telemetry.instrument_stage("cofactor", _stage_cofactor)
_stage_miller = _telemetry.instrument_stage("miller", _stage_miller)
_stage_product = _telemetry.instrument_stage("product", _stage_product)
_stage_final_with_valid = _telemetry.instrument_stage(
    "final", _stage_final_with_valid
)


def _run_pipeline(prepare, pk, h, sig, rand_bits, mask):
    px, py, qx, qy, pair_mask = prepare(
        pk, h[0], h[1], sig, rand_bits, mask
    )
    if fused_stages_on():
        # host-prepped waves still land in ≤3 programs: per-stage
        # prepare + fused pairing + final
        _note_donation(_PAIRING_DONATED)
        prod = _fused_pairing(px, py, qx, qy, pair_mask)
    else:
        f = _stage_miller(px, py, qx, qy)
        prod = _stage_product(f, pair_mask)
    # host-decompressed signatures are valid by construction; the
    # ingest waves' final program serves both paths (one compile)
    return _stage_final_with_valid(prod, jnp.asarray(True))


# --- host-orchestrated kernels --------------------------------------------


def run_verify_batch_async(
    pk: C.JacPoint, h, sig: C.JacPoint, rand_bits, mask
):
    """Like run_verify_batch but returns the device () bool WITHOUT
    reading it back, so callers that can batch verdicts submit many
    verifies and read once — the same amortization the reference's
    100 ms gossip buffering makes (index.ts:59-74)."""
    jaxcache.enable()
    return _run_pipeline(_stage_prepare_batch, pk, h, sig, rand_bits, mask)


def run_verify_batch(pk: C.JacPoint, h, sig: C.JacPoint, rand_bits, mask) -> bool:
    """Random-linear-combination batch verify of n (pk, msg, sig) sets:

      prod_i e(r_i*pk_i, H_i) * e(-g1, sum_i r_i*sig_i) == 1

    pk: G1 affine batch (n,); h: (hx, hy) G2 Fq2 batches (n,);
    sig: G2 affine batch (n,). rand_bits: (n, RAND_BITS) bool MSB-first,
    r_i != 0. mask: (n,) bool — False slots are padding. Reference:
    blst verifyMultipleAggregateSignatures (maybeBatch.ts:17-44); a
    batch failure means callers retry per set (index.ts:552-563).
    """
    jaxcache.enable()
    if not np.any(np.asarray(mask)):
        return True  # all-padding call is vacuously true
    return bool(
        _run_pipeline(_stage_prepare_batch, pk, h, sig, rand_bits, mask)
    )


def run_verify_same_message(pk: C.JacPoint, h, sig: C.JacPoint, rand_bits, mask) -> bool:
    """Same-message batch verify: n (pk_i, sig_i) on ONE message H:

      e(sum r_i*pk_i, H) * e(-g1, sum r_i*sig_i) == 1

    `aggregateWithRandomness` + one pairing check fused on device — the
    reference computes the MSMs on the main thread (jobItem.ts:60-75),
    its documented scaling limit. h: (hx, hy) with batch shape (1,).
    """
    jaxcache.enable()
    if not np.any(np.asarray(mask)):
        return True
    return bool(
        _run_pipeline(
            _stage_prepare_same_message, pk, h, sig, rand_bits, mask
        )
    )


# --- whole-bucket mesh programs ---------------------------------------------
#
# Multi-chip verify where each chip owns WHOLE sub-buckets (ISSUE 16):
# the local body below is the same `*_impl` composition as the fused
# single-chip programs, traced per shard by parallel.whole_bucket_verify
# with collective-free local shapes; the only collective in the whole
# program is one () psum at the verdict. Programs are cached per
# (kind, mesh) — jit also specializes on shardings, so these are
# distinct executables from the single-host ones and mesh verifiers
# never consult the warm registry (see the warmup section).


def _verify_batch_local(pk, hx, hy, sig, bits, mask):
    """Per-shard collective-free batch verify (host-hashed path)."""
    px, py, qx, qy, pair_mask = _prepare_batch_impl(
        pk, hx, hy, sig, bits, mask
    )
    f = _miller_impl(px, py, qx, qy)
    return _final_expo_impl(_product_impl(f, pair_mask))


def _verify_same_message_local(pk, hx, hy, sig_x, sig_sign, bits, mask):
    """Per-shard same-message verify; the (1,)-batch hash point is
    replicated (every shard pairs its aggregate against the same H)."""
    sig, all_valid = _g2_decompress_impl(sig_x, sig_sign, mask)
    px, py, qx, qy, pair_mask = _prepare_same_message_impl(
        pk, hx, hy, sig, bits, mask
    )
    f = _miller_impl(px, py, qx, qy)
    ok = _final_expo_impl(_product_impl(f, pair_mask))
    return jnp.logical_and(ok, all_valid)


def _verify_ingest_local(pk, sig_x, sig_sign, u0, u1, bits, mask):
    """Per-shard verify with device-side ingest (decompress + hash)."""
    sig, all_valid = _g2_decompress_impl(sig_x, sig_sign, mask)
    hx, hy = _hash_to_g2_impl(u0, u1, mask)
    px, py, qx, qy, pair_mask = _prepare_batch_impl(
        pk, hx, hy, sig, bits, mask
    )
    f = _miller_impl(px, py, qx, qy)
    ok = _final_expo_impl(_product_impl(f, pair_mask))
    return jnp.logical_and(ok, all_valid)


_MESH_LOCALS = {
    "batch": (_verify_batch_local, 6, ()),
    "same_message": (_verify_same_message_local, 7, (1, 2)),
    "ingest_batch": (_verify_ingest_local, 7, ()),
}


@functools.lru_cache(maxsize=8)
def _mesh_program(kind: str, mesh):
    from .. import parallel

    local, n_args, repl = _MESH_LOCALS[kind]
    return jax.jit(
        parallel.whole_bucket_verify(mesh, local, n_args, repl)
    )


def _run_mesh(kind, mesh, *args):
    return _mesh_program(kind, mesh)(*args)


# one stage for all three kinds: the kind string enters the retrace
# detector's signature, so per-kind compiles stay distinguishable
_run_mesh = _telemetry.instrument_stage("mesh_verify", _run_mesh)


def run_verify_batch_mesh(mesh, pk, h, sig, rand_bits, mask):
    """Whole-bucket mesh batch verify; returns the device () bool
    without readback. Batch args must be placed with
    parallel.shard_batch (leading axis divisible by the mesh size)."""
    jaxcache.enable()
    return _run_mesh("batch", mesh, pk, h[0], h[1], sig, rand_bits, mask)


def run_verify_same_message_mesh(mesh, pk, h, sig_x, sig_sign, rand_bits, mask):
    jaxcache.enable()
    return _run_mesh(
        "same_message", mesh, pk, h[0], h[1], sig_x, sig_sign, rand_bits, mask
    )


def run_verify_batch_ingest_mesh(mesh, pk, sig_x, sig_sign, u0, u1, rand_bits, mask):
    jaxcache.enable()
    return _run_mesh(
        "ingest_batch", mesh, pk, sig_x, sig_sign, u0, u1, rand_bits, mask
    )


# --- small helpers ---------------------------------------------------------


def _cat_fq(a: L.Lv, b: L.Lv) -> L.Lv:
    a, b = L.normalize(a), L.normalize(b)
    return L.Lv(jnp.concatenate([a.v, b.v], 0), a.lo, a.hi)


def _cat_fq2(a, b):
    return (_cat_fq(a[0], b[0]), _cat_fq(a[1], b[1]))


# The one bucket ladder: retry-chunk rungs (<=128, reference job
# granularity), the rolling-accumulator ingest rungs {256, 512}, and
# the bulk-wave TOP rung. bucket_size, default_warmup_sizes, and the
# verifier's warmup all derive from the LIVE tuple — add a rung and
# warmup covers it automatically. The top rung is a KNOB
# (set_ladder_top): the device autotuner (device/autotune.py) may
# trade the 2048 bulk bucket for 1024 on hosts where the bigger
# compile/dispatch does not pay for its padding.
_MID_RUNGS = (4, 8, 16, 32, 64, 128, 256, 512)
LADDER_TOPS = (1024, 2048)  # autotune-selectable top rungs
BUCKET_LADDER = _MID_RUNGS + (2048,)
# On the chip every Pallas kernel works in blocks of 128 lanes, so a
# bucket below one block pays for a full block in the kernels, while
# each extra rung is one more cold compile of its programs (about two
# minutes on a v5e host): there the ladder starts at one block, and
# the smaller rungs serve the CPU emulation only. tools/rung_latency.py
# times a bucket per rung on the chip (PERF.md).
CHIP_MIN_BUCKET = 128


def ladder_top() -> int:
    """The live top (bulk-wave) bucket rung."""
    return BUCKET_LADDER[-1]


def set_ladder_top(n: int, rewarm: bool = True) -> None:
    """Swap the bulk-wave top rung of the ladder. Sizes that fall out
    of the ladder are dropped from the warm registry — they can no
    longer be dispatched, and counting them warm would overstate the
    `lodestar_jax_warmup_*` gauges. An INCOMING top rung was never
    compiled: when a warmup policy exists in this process, kick the
    background warmup for every cold ingest-eligible rung, or a
    cold-fallback verifier would route the bulk bucket host_cold
    forever (nothing else warms a size the fallback never
    dispatches). rewarm=False defers that to a caller that re-warms
    once for a whole batch of knob changes (autotune.apply_config)."""
    global BUCKET_LADDER
    n = int(n)
    if n < _MID_RUNGS[-1]:
        raise ValueError(
            f"ladder top {n} below the largest mid rung {_MID_RUNGS[-1]}"
        )
    BUCKET_LADDER = tuple(b for b in _MID_RUNGS if b < n) + (n,)
    live = set(BUCKET_LADDER)
    stale = {k for k in _INGEST_WARM if k[1] not in live}
    _INGEST_WARM.difference_update(stale)
    if rewarm and _WARMUP_STARTED:
        newly = tuple(
            b for b in default_warmup_sizes() if not ingest_is_warm(b)
        )
        if newly:
            warmup_ingest(newly)


def ladder() -> tuple[int, ...]:
    """The live bucket ladder; on the chip it starts at
    CHIP_MIN_BUCKET."""
    if chip.on_tpu():
        return tuple(b for b in BUCKET_LADDER if b >= CHIP_MIN_BUCKET)
    return BUCKET_LADDER


def bucket_size(n: int, buckets=None) -> int:
    """Smallest bucket >= n. Small sizes mirror the reference's <=128
    sets/job chunks (chain/bls/multithread/index.ts:48-56). The mid
    sizes {256, 512} are the device-ingest-eligible rungs the
    verifier's rolling gossip accumulator flushes into — without them
    the ladder jumped 128 -> 2048 and steady-state trickle traffic
    either rode the slow host decompress/hash path or paid 16x
    padding. Above 512 whole waves pack into one top-rung device
    bucket (per-op device cost is batch-flat to ~2048, so padding
    there is nearly free; each extra bucket size is an extra
    multi-minute XLA compile, pre-warmed by warmup_ingest). `buckets`
    defaults to the LIVE ladder so a set_ladder_top() retune is seen
    by every later call."""
    if buckets is None:
        buckets = ladder()
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


# --- ingest warmup ----------------------------------------------------------
#
# Each ingest bucket size is its own multi-minute XLA compile (per
# stage, per shape). A node that waits for the first gossip lull to
# pay that compile stalls its verify pipeline, so the verifier can
# (a) pre-warm the ingest sizes on a background thread at start and
# (b) route buckets whose compile is still cold to the host
# decompress/hash path (TpuBlsVerifier host_fallback_when_cold). The
# registry below tracks which (pipeline, size) pairs are warm — the
# batch and same-message ingest paths are DISTINCT jit programs, so a
# dispatch on one must not mark the other's cold compile as warm. A
# pair also becomes warm the first time a live dispatch completes at
# it. The ingest marks describe the UNSHARDED single-host
# executables: jit also specializes on input shardings, so mesh
# verifiers never route by them (TpuBlsVerifier.start_warmup disables
# their cold fallback and they dispatch directly, paying each size's
# compile inline once). The registry also marks the host-path programs
# ("host") and the mesh programs ("mesh_batch", "mesh_host"): the
# verifier's wave watchdog does not watch a dispatch that compiles.

_INGEST_WARM: set[tuple[str, int]] = set()
_WARMUP_LOCK = threading.Lock()
_WARMUP_THREAD: threading.Thread | None = None
# has warmup_ingest ever run in this process? Gates the automatic
# re-warm on live retunes (set_ingest_min_bucket / backend switches):
# processes that never opted into warmup never get background compiles
# sprung on them by a knob change.
_WARMUP_STARTED = False
# sizes requested while a warmup thread was already running; the
# thread drains this set before exiting (guarded by _WARMUP_LOCK)
_WARMUP_WANT: set[int] = set()

# maintenance gate (device/executor.py): the node wires this to the
# executor's maintenance_checkpoint so the warmup thread YIELDS the
# device between compiles whenever a deadline client (live gossip)
# has work pending — node-start warmup no longer races live traffic.
# None = no executor: warmup runs back-to-back, the pre-executor
# behavior (tests, tools, standalone verifiers).
_MAINT_GATE = None


def set_maintenance_gate(gate) -> None:
    """Install (or clear, with None) the between-compiles yield hook
    called by warmup_ingest's warm loop."""
    global _MAINT_GATE
    _MAINT_GATE = gate


def _maintenance_checkpoint() -> None:
    """Invoke the installed maintenance gate, tolerating any failure:
    yielding is an optimization — a broken gate must never kill the
    warmup thread (a size left cold rides the host fallback forever)."""
    gate = _MAINT_GATE
    if gate is not None:
        try:
            gate()
        except Exception:
            pass


# health gate (device/health.py): the node wires this to the health
# tracker's device_allowed so warmup SUSPENDS while the device is
# quarantined — a compile storm is the last thing a sick chip needs,
# and the probes need the device to themselves. Sizes left cold are
# re-warmed by the reinstatement warmup kick. None = no tracker:
# warmup runs unconditionally (tests, tools, standalone verifiers).
_HEALTH_GATE = None


def set_health_gate(gate) -> None:
    """Install (or clear, with None) the device-allowed predicate
    consulted before each warmup compile."""
    global _HEALTH_GATE
    _HEALTH_GATE = gate


def _device_dispatch_allowed() -> bool:
    """Consult the installed health gate, tolerating any failure (a
    broken gate must never block warmup — fail open, like the
    maintenance gate fails silent)."""
    gate = _HEALTH_GATE
    if gate is None:
        return True
    try:
        return bool(gate())
    except Exception:
        return True


def ingest_is_warm(b: int, kind: str = "batch") -> bool:
    return (kind, b) in _INGEST_WARM


def mark_ingest_warm(b: int, kind: str = "batch") -> None:
    _INGEST_WARM.add((kind, b))




# generation counter for the warm registry: invalidation bumps it so
# a warmup dispatch that STARTED under the previous generation (its
# executable died with the cache clear) cannot land a stale mark when
# it completes. The check-and-mark and the bump-and-clear each run
# under the lock, or a mark could slip in between them.
_WARM_GEN = 0
_WARM_GEN_LOCK = threading.Lock()


def invalidate_ingest_warm(rewarm: bool = True) -> None:
    """Drop every warm mark. Called when a limb-backend switch clears
    the jit caches (ops/limbs.set_backend): the compiled executables
    the marks described are gone, and a cold-fallback verifier
    trusting a stale mark would dispatch a live bucket straight into
    the recompile the mark claimed was paid. When a warmup ran in this
    process, re-warm the eligible rungs in the background (persistent
    cache makes a switch back near-free). The registry also carries
    the KZG MSM rung marks (kind "msm", ops/msm.py) whose executables
    the same cache clear killed — their rewarm is kicked through the
    msm module's own warmup policy, or the DA workload would ride the
    host fallback for the rest of the process."""
    global _WARM_GEN
    with _WARM_GEN_LOCK:
        _WARM_GEN += 1
        _INGEST_WARM.clear()
    if rewarm and _WARMUP_STARTED:
        warmup_ingest()
    if rewarm:
        import sys

        m = sys.modules.get("lodestar_tpu.ops.msm")
        if m is not None:
            m.rewarm_async()


WARMUP_PIPELINES = ("batch", "same_message")


def warmup_progress(gate: int | None = None) -> dict[str, tuple[int, int]]:
    """Per-pipeline warmup progress: {pipeline: (warm, eligible)}.
    Feeds the `lodestar_jax_warmup_*` gauges (metrics/device.py) so a
    warmup that never finishes is visible instead of looking like a
    slow TPU (cold sizes ride the host fallback forever)."""
    sizes = default_warmup_sizes(gate)
    return {
        kind: (
            sum(1 for b in sizes if (kind, b) in _INGEST_WARM),
            len(sizes),
        )
        for kind in WARMUP_PIPELINES
    }


def default_warmup_sizes(gate: int | None = None) -> tuple[int, ...]:
    """Every ingest-eligible rung of the ladder (gate defaults to the
    module knob; verifiers pass their own override)."""
    if gate is None:
        gate = ingest_min_bucket()
    return tuple(b for b in BUCKET_LADDER if b >= gate)


@functools.cache
def _pad_ints():
    """One valid set (secret key 7) as host values: the inputs every
    warmup dispatch and compile rehearsal pads its bucket with."""
    from ..crypto.bls.signature import sign, sk_to_pk
    from . import api

    msg = b"\x5a" * 32
    sig = sign(7, msg)
    xc0, xc1, s_sign, ok = api.parse_signature(sig)
    assert ok
    return {
        "pk": api.decompress_pubkey(sk_to_pk(7)),
        "sig_x": (xc0, xc1),
        "sig_sign": s_sign,
        "draws": api.message_draws(msg),
        "h": api.message_to_g2(msg),
        "sig": api.decompress_signature(sig),
    }


def _warm_bits(b: int):
    """Distinct blinding scalars for a bucket of b identical sets: with
    equal scalars the partial sums of the aggregate collide, which the
    incomplete additions of the reduction (curve.jac_add_incomplete)
    do not handle, and a valid bucket would verify False."""
    rng = random.Random(b)
    return C.scalars_to_bits(
        [rng.getrandbits(RAND_BITS) | 1 for _ in range(b)], RAND_BITS
    )


def ingest_batch_args(b: int) -> tuple:
    """Arguments of run_verify_batch_ingest_async for a bucket of b
    valid sets, built as the verifier builds them."""
    from ..ops import tower

    p = _pad_ints()
    return (
        C.g1_batch_from_ints([p["pk"]] * b),
        tower.fq2_from_ints([p["sig_x"]] * b),
        jnp.asarray([p["sig_sign"]] * b),
        tower.fq2_from_ints([p["draws"][0]] * b),
        tower.fq2_from_ints([p["draws"][1]] * b),
        _warm_bits(b),
        jnp.asarray([True] * b),
    )


def host_batch_args(b: int) -> tuple:
    """Arguments of run_verify_batch_async (the host-decompressed
    path) for a bucket of b valid sets."""
    p = _pad_ints()
    h = C.g2_batch_from_ints([p["h"]] * b)
    return (
        C.g1_batch_from_ints([p["pk"]] * b),
        (h.x, h.y),
        C.g2_batch_from_ints([p["sig"]] * b),
        _warm_bits(b),
        jnp.asarray([True] * b),
    )


def _warm_one(b: int, same_message: bool) -> None:
    """Compile (or load from the persistent cache) the ingest pipeline
    for bucket size b by running one padded dispatch to completion."""
    pk_dev, sig_x, sig_sign, u0, u1, bits, mask = ingest_batch_args(b)
    if same_message:
        h_dev = C.g2_batch_from_ints([_pad_ints()["h"]])
        out = run_verify_same_message_ingest_async(
            pk_dev, (h_dev.x, h_dev.y), sig_x, sig_sign, bits, mask
        )
    else:
        out = run_verify_batch_ingest_async(
            pk_dev, sig_x, sig_sign, u0, u1, bits, mask
        )
    if not bool(out):  # blocks until the compile + run completes
        raise RuntimeError(f"ingest warmup verify failed at bucket {b}")


def warm_bucket(b: int, kind: str = "batch", mesh=None) -> None:
    """Compile (or load from the persistent cache) the programs one
    bucket of b sets dispatches, by running one padded dispatch to
    completion, and mark (kind, b) warm. kind: "batch" is the device
    ingest pipeline, "same_message" its same-message twin, "host" the
    host-decompressed path of the rungs below the ingest gate (the
    verifier's retries); with `mesh`, the batch or host programs
    sharded over it, marked "mesh_batch" / "mesh_host". Raises if the
    dispatch fails or its valid bucket verifies False. The mark is
    generation-guarded: if the registry was invalidated while the
    dispatch ran (a backend switch killed the executable it compiled),
    the stale mark does not land."""
    gen = _WARM_GEN
    if mesh is None and kind in ("batch", "same_message"):
        _warm_one(b, same_message=kind == "same_message")
    elif kind in ("batch", "host"):
        ingest = kind == "batch"
        args = ingest_batch_args(b) if ingest else host_batch_args(b)
        if mesh is None:
            run = (
                run_verify_batch_ingest_async
                if ingest
                else run_verify_batch_async
            )
        else:
            from .. import parallel

            run = functools.partial(
                run_verify_batch_ingest_mesh
                if ingest
                else run_verify_batch_mesh,
                mesh,
            )
            args = [parallel.shard_batch(mesh, a) for a in args]
            kind = "mesh_" + kind
        if not bool(run(*args)):
            raise RuntimeError(f"{kind} warmup verify failed at bucket {b}")
    else:
        raise ValueError(f"no {kind!r} warmup for mesh={mesh!r}")
    with _WARM_GEN_LOCK:
        if _WARM_GEN == gen:
            mark_ingest_warm(b, kind)


def default_host_warmup_sizes(gate: int | None = None) -> tuple[int, ...]:
    """The host-path rungs below the ingest gate that warmup compiles
    on the chip: a failed bucket's per-job and per-set retries
    dispatch them, and a cold one would hold its wave for minutes of
    compile. The chip's ladder holds one such rung (CHIP_MIN_BUCKET);
    off the chip none is warmed."""
    if not chip.on_tpu():
        return ()
    if gate is None:
        gate = ingest_min_bucket()
    return tuple(b for b in ladder() if b < gate)


def warmup_ingest(
    sizes: tuple[int, ...] | None = None,
    block: bool = False,
    same_message: bool = True,
    host_sizes: tuple[int, ...] = (),
) -> threading.Thread | None:
    """Pre-compile the device-ingest pipeline for the given bucket
    sizes (default: every ingest-eligible rung) on a background
    thread, marking each size warm as it completes; the host-path
    rungs in `host_sizes` go first (default_host_warmup_sizes: the
    retry rungs, which a node's first bad signature needs). The
    persistent compilation cache (utils/jaxcache.py) makes this a
    disk load on every process after the first. Idempotent;
    block=True runs synchronously (tests, tools)."""
    global _WARMUP_THREAD, _WARMUP_STARTED
    jaxcache.enable()
    _WARMUP_STARTED = True
    want = tuple(sizes) if sizes is not None else default_warmup_sizes()

    def warm_one_marked(b, kind, log, msg):
        """One warmup dispatch + generation-guarded mark (warm_bucket):
        a size whose mark did not land re-warms on the next kick."""
        try:
            warm_bucket(b, kind)
        except Exception as e:
            # warmup is an optimization: the size stays cold and the
            # verifier keeps its host fallback — but say so, or the
            # node silently runs degraded forever
            log.warn(msg, {"bucket": b, "err": repr(e)})

    def warm_sizes(seq, log):
        for b in sorted(set(seq)):
            if not _device_dispatch_allowed():
                # device quarantined (device/health.py): suspend —
                # the remaining sizes stay cold and the reinstatement
                # warmup kick re-runs this loop when the device comes
                # back. Warming THROUGH a quarantine would race the
                # known-answer probes for a chip being judged.
                log.warn(
                    "warmup suspended: device path quarantined",
                    {"remaining": sorted(set(seq))},
                )
                return
            if not ingest_is_warm(b, "batch"):
                # yield the device to pending deadline work before
                # each compile (maintenance-class discipline,
                # device/executor.py) — a multi-second compile must
                # not start in front of a queued gossip wave
                _maintenance_checkpoint()
                # only the batch pipeline becomes warm here — the
                # same-message program is a different compile
                warm_one_marked(
                    b,
                    "batch",
                    log,
                    "ingest warmup failed; bucket stays on host path",
                )
            if same_message and not ingest_is_warm(b, "same_message"):
                _maintenance_checkpoint()
                warm_one_marked(
                    b,
                    "same_message",
                    log,
                    "same-message ingest warmup failed",
                )

    def run():
        global _WARMUP_THREAD
        from ..logger import get_logger

        log = get_logger("bls-warmup")
        for b in sorted(set(host_sizes)):
            if not _device_dispatch_allowed():
                break  # quarantined: the ingest loop below logs it
            if not ingest_is_warm(b, "host"):
                _maintenance_checkpoint()
                warm_one_marked(b, "host", log, "host-path warmup failed")
        warm_sizes(want, log)
        # drain sizes enqueued while this thread ran (a live retune —
        # gate lowered or backend switched — kicks warmup again; the
        # request must not be lost just because a thread was active).
        # The emptiness check and the thread deregistration happen
        # under ONE lock hold: an enqueue serialized before it is
        # drained here; one after it sees no live thread and spawns.
        while True:
            with _WARMUP_LOCK:
                extra = sorted(_WARMUP_WANT)
                _WARMUP_WANT.clear()
                if not extra:
                    if _WARMUP_THREAD is threading.current_thread():
                        _WARMUP_THREAD = None
                    return
            warm_sizes(extra, log)

    if block:
        run()
        return None
    with _WARMUP_LOCK:
        if _WARMUP_THREAD is not None and _WARMUP_THREAD.is_alive():
            _WARMUP_WANT.update(want)
            return _WARMUP_THREAD
        _WARMUP_THREAD = threading.Thread(
            target=run, name="bls-ingest-warmup", daemon=True
        )
        _WARMUP_THREAD.start()
        return _WARMUP_THREAD
