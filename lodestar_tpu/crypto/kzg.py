"""KZG polynomial commitments for EIP-4844 blob sidecars.

Reference analog: the `c-kzg` native library loaded at node startup
(beacon-node: node/nodejs.ts:162-165 initCKZG/loadEthereumTrustedSetup)
and used by blob validation (chain/validation/blobSidecar.ts) and block
production (produceBlock/validateBlobsAndKzgCommitments.ts). Fresh
implementation of consensus-specs deneb/polynomial-commitments.md.

The multi-scalar multiplications — the pairing-heavy core of both the
4096-point Lagrange lincombs and the batch-verify random lincombs —
run on a THREE-TIER backend (`set_msm_backend` /
LODESTAR_TPU_KZG_MSM_BACKEND; per-path dispatch counters mirror the
BLS verifier's):

  1. **device** — the TPU bucketed Pippenger (`ops/msm.py`): batched
     limb tensors, one dispatch for a whole blob batch's lincombs. The
     default "auto" mode routes here on a TPU host once the rung's
     compile is warm (the kernels warm registry, kind "msm");
  2. **native** — the host C Pippenger (csrc/bls381.c `blsn_g1_msm`),
     the cold-rung / off-TPU fallback and the differential oracle;
  3. **oracle** — the pure-Python double-and-add lincomb, always
     available, the last-resort tier and the slow reference.

Other group arithmetic stays on native-with-oracle-fallback.
Scalar-field (Fr) arithmetic is plain Python ints with Montgomery
batch inversion — except the batch-verify barycentric evaluations,
which ride a TWO-TIER backend (`set_fr_backend` /
LODESTAR_TPU_KZG_FR_BACKEND): **device** dispatches every blob's
4096-point evaluation + Montgomery batch inversion as ONE limb-kernel
program (`ops/fr.py`, bit-exact vs the ints; z-equals-root blobs are
special-cased on host exactly like the Python path), **python** is
the oracle below; "auto" routes to the device on a TPU host and
falls back (counted) on any device error.

Trusted setup: `load_trusted_setup(path)` reads the standard JSON
format ({"g1_lagrange": [...48B hex...], "g2_monomial": [...]}), so the
ceremony output used in production drops in. For tests/dev,
`dev_trusted_setup()` generates an **INSECURE** setup from a known
secret tau (the whole point of the ceremony is that tau is unknown —
never use the dev setup outside tests), cached on disk after first
generation.
"""

from __future__ import annotations

import json
import os
from concurrent import futures
from hashlib import sha256
from pathlib import Path

from . import bls as _bls  # noqa: F401  (package init side effects)
from .bls import curve as oc
from .bls import native
from ..utils import chip, native_build

BLS_MODULUS = (
    0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
)
PRIMITIVE_ROOT_OF_UNITY = 7
BYTES_PER_FIELD_ELEMENT = 32
FIELD_ELEMENTS_PER_BLOB = 4096
BYTES_PER_BLOB = BYTES_PER_FIELD_ELEMENT * FIELD_ELEMENTS_PER_BLOB

FIAT_SHAMIR_PROTOCOL_DOMAIN = b"FSBLOBVERIFY_V1_"
RANDOM_CHALLENGE_KZG_BATCH_DOMAIN = b"RCKZGBATCH___V1_"

G1_POINT_AT_INFINITY_COMPRESSED = b"\xc0" + b"\x00" * 47


class KzgError(ValueError):
    pass


# ---------------------------------------------------------------------------
# Fr helpers
# ---------------------------------------------------------------------------


def _fr_inv(a: int) -> int:
    return pow(a, BLS_MODULUS - 2, BLS_MODULUS)


def _fr_batch_inv(xs: list[int]) -> list[int]:
    """Montgomery trick: one inversion + 3n multiplications."""
    n = len(xs)
    prefix = [1] * (n + 1)
    for i, x in enumerate(xs):
        if x == 0:
            raise KzgError("division by zero in batch inversion")
        prefix[i + 1] = prefix[i] * x % BLS_MODULUS
    inv = _fr_inv(prefix[n])
    out = [0] * n
    for i in range(n - 1, -1, -1):
        out[i] = prefix[i] * inv % BLS_MODULUS
        inv = inv * xs[i] % BLS_MODULUS
    return out


def _bit_reversal_permutation(seq: list) -> list:
    n = len(seq)
    bits = n.bit_length() - 1
    assert 1 << bits == n, "length must be a power of two"
    return [seq[int(format(i, f"0{bits}b")[::-1], 2)] for i in range(n)]


def compute_roots_of_unity(order: int = FIELD_ELEMENTS_PER_BLOB) -> list[int]:
    assert (BLS_MODULUS - 1) % order == 0
    root = pow(PRIMITIVE_ROOT_OF_UNITY, (BLS_MODULUS - 1) // order, BLS_MODULUS)
    out = [1]
    for _ in range(order - 1):
        out.append(out[-1] * root % BLS_MODULUS)
    return out


_ROOTS_BRP: list[int] | None = None


def _roots_brp() -> list[int]:
    global _ROOTS_BRP
    if _ROOTS_BRP is None:
        _ROOTS_BRP = _bit_reversal_permutation(
            compute_roots_of_unity(FIELD_ELEMENTS_PER_BLOB)
        )
    return _ROOTS_BRP


# ---------------------------------------------------------------------------
# Group helpers (native with oracle fallback); points as oracle tuples
# ---------------------------------------------------------------------------


# oc.* auto-dispatches to the native backend when available


def _g1_decompress(b: bytes):
    """48B compressed -> point (on-curve + subgroup checked inside)."""
    return oc.g1_from_bytes(bytes(b))


def _g1_compress(pt) -> bytes:
    return oc.g1_to_bytes(pt)


_g1_add = oc.g1_add
_g1_mul = oc.g1_mul
_g2_add = oc.g2_add
_g2_mul = oc.g2_mul


# --- three-tier MSM backend (device / native / oracle) ---------------------

MSM_BACKENDS = ("auto", "device", "native", "oracle")

_msm_backend = os.environ.get("LODESTAR_TPU_KZG_MSM_BACKEND", "auto")
if _msm_backend not in MSM_BACKENDS:
    raise ValueError(
        f"LODESTAR_TPU_KZG_MSM_BACKEND={_msm_backend!r} not in "
        f"{MSM_BACKENDS}"
    )

# per-path dispatch counters (the BLS verifier's dispatch_by_path
# discipline): one entry per _g1_lincomb_many call, by the tier that
# served it; device_fallbacks counts auto-mode dispatches that WANTED
# the device but found the rung cold (or the dispatch erroring) and
# fell back to a host tier. Sampled at scrape by
# bind_kzg_collectors (lodestar_kzg_* series).
_MSM_DISPATCH: dict[str, int] = {"device": 0, "native": 0, "oracle": 0}
_MSM_DEVICE_FALLBACKS = 0
_BATCH_HIST = None  # bound lodestar_kzg_batch_verify_blobs histogram

# node-wide device executor (device/executor.py): when wired, device
# MSM/Fr dispatches ride its BULK lane — they queue behind pending
# deadline (gossip verdict) work at every wave boundary, and under
# overload the executor sheds them (bounded bulk queue) so this module
# falls back to its host tier instead of piling onto the chip.
_EXECUTOR = None


def set_executor(executor) -> None:
    """Install (or clear, with None) the node DeviceExecutor this
    module's device dispatches route through as bulk-class jobs."""
    global _EXECUTOR
    _EXECUTOR = executor


def _submit_bulk(fn):
    """Run a device dispatch, through the executor's bulk lane when
    one is wired. Returns (served, result): served=False means the
    executor SHED the job — the caller rides its host fallback tier
    (counted as a device fallback, like any other device miss).
    Dispatch exceptions propagate to the caller's existing handler."""
    ex = _EXECUTOR
    if ex is None:
        return True, fn()
    fut = ex.submit("bulk", fn)
    if fut is None:
        return False, None
    try:
        return True, fut.result()
    except futures.CancelledError:
        # executor closed under us (node shutdown): treat like a
        # shed — the host tier still answers the caller
        return False, None


# device fault domain (device/health.py): while the tracker
# quarantines the device, MSM/Fr ride their host tiers (counted per
# client on lodestar_device_failover_dispatches_total), and dispatch
# exceptions route through the error taxonomy instead of a blanket
# swallow — programming errors re-raise as the bugs they are.
_HEALTH = None
_LOG = None


def set_health_tracker(tracker) -> None:
    """Install (or clear, with None) the DeviceHealthTracker this
    module's device tiers consult before dispatching."""
    global _HEALTH
    _HEALTH = tracker


def _klog():
    global _LOG
    if _LOG is None:
        from ..logger import get_logger

        _LOG = get_logger("kzg")
    return _LOG


def _device_blocked(client: str) -> bool:
    """True while the health tracker quarantines the device path.
    Counts the failed-over dispatch and logs once per state
    transition (not per call — a quarantined node sees thousands)."""
    h = _HEALTH
    if h is None or h.device_allowed():
        return False
    if h.note_failover(client):
        _klog().warn(
            "device quarantined: dispatches riding host tier",
            {"client": client, "state": h.state.value},
        )
    return True


def _report_device_fault(e: BaseException, client: str) -> None:
    """Taxonomy routing for a device-dispatch exception: classify,
    report to the tracker, log once per transition. PROGRAMMING
    errors (TypeError/KeyError from our own code) re-raise — they
    must surface as the bugs they are, not masquerade as device
    flakiness absorbed by a fallback counter."""
    from ..device.health import classify_device_error

    kind = classify_device_error(e)
    if kind == "programming":
        raise e
    h = _HEALTH
    if h is not None:
        h.record_fault(kind, client=client)
        if h.should_log(client):
            _klog().warn(
                "device dispatch failed; host tier serves",
                {"client": client, "kind": kind, "err": repr(e)},
            )


def msm_backend() -> str:
    """The live MSM backend mode."""
    return _msm_backend


def set_msm_backend(name: str) -> None:
    global _msm_backend
    if name not in MSM_BACKENDS:
        raise ValueError(
            f"unknown kzg msm backend {name!r}; want {MSM_BACKENDS}"
        )
    _msm_backend = name


def msm_path_counts() -> dict:
    """Snapshot of the per-path dispatch counters (tests, /metrics)."""
    return dict(_MSM_DISPATCH, device_fallbacks=_MSM_DEVICE_FALLBACKS)


def bind_kzg_collectors(metrics) -> None:
    """Wire the m.kzg registry namespace (metrics/beacon.py) to sample
    the module counters at scrape — the addCollect pattern every other
    service uses (node.py)."""
    global _BATCH_HIST
    _BATCH_HIST = getattr(metrics, "batch_verify_blobs", None)
    metrics.msm_dispatch_total.add_collect(
        lambda g: [
            g.set(v, path=p) for p, v in _MSM_DISPATCH.items()
        ]
    )
    metrics.msm_device_fallback_total.add_collect(
        lambda g: g.set(_MSM_DEVICE_FALLBACKS)
    )
    metrics.fr_dispatch_total.add_collect(
        lambda g: [
            g.set(v, path=p) for p, v in _FR_DISPATCH.items()
        ]
    )
    metrics.fr_device_fallback_total.add_collect(
        lambda g: g.set(_FR_DEVICE_FALLBACKS)
    )


# --- two-tier Fr backend (device / python) ---------------------------------

FR_BACKENDS = ("auto", "device", "python")

_fr_backend = os.environ.get("LODESTAR_TPU_KZG_FR_BACKEND", "auto")
if _fr_backend not in FR_BACKENDS:
    raise ValueError(
        f"LODESTAR_TPU_KZG_FR_BACKEND={_fr_backend!r} not in "
        f"{FR_BACKENDS}"
    )

# per-path counters for the batch-verify barycentric evaluations,
# mirroring the MSM tier's discipline: one entry per
# _evaluate_polynomials_batch call by the tier that served it;
# fr_device_fallbacks counts dispatches that wanted the device but
# errored and fell back to the Python ints.
_FR_DISPATCH: dict[str, int] = {"device": 0, "python": 0}
_FR_DEVICE_FALLBACKS = 0
_FR_ROOTS_DEV = None  # cached device limb array of the brp'd domain


def fr_backend() -> str:
    """The live Fr-evaluation backend mode."""
    return _fr_backend


def set_fr_backend(name: str) -> None:
    global _fr_backend
    if name not in FR_BACKENDS:
        raise ValueError(
            f"unknown kzg fr backend {name!r}; want {FR_BACKENDS}"
        )
    _fr_backend = name


def fr_path_counts() -> dict:
    """Snapshot of the Fr-evaluation dispatch counters."""
    return dict(_FR_DISPATCH, device_fallbacks=_FR_DEVICE_FALLBACKS)


def _fr_roots_dev():
    global _FR_ROOTS_DEV
    if _FR_ROOTS_DEV is None:
        import jax.numpy as jnp

        from ..ops import fr as _fr

        _FR_ROOTS_DEV = jnp.asarray(_fr.fr_from_ints(_roots_brp()))
    return _FR_ROOTS_DEV


def _evaluate_polynomials_batch(
    polys: list[list[int]], zs: list[int]
) -> list[int]:
    """ys for m (poly, z) pairs — the batch-verify evaluation seam.
    The device tier packs every z-outside-the-domain evaluation into
    ONE ops/fr barycentric dispatch (the Montgomery batch inversion
    runs on device too); z-equals-root blobs read the coefficient on
    host exactly like the Python oracle. Any device error falls back
    to the Python ints (counted), never fails the caller."""
    global _FR_DEVICE_FALLBACKS
    mode = _fr_backend
    use_device = mode == "device"
    if mode == "auto":
        use_device = chip.on_tpu()
    if use_device and _device_blocked("kzg_fr"):
        use_device = False  # quarantined: Python ints serve exactly
    if use_device:
        roots = _roots_brp()
        ys: list[int | None] = [None] * len(zs)
        live = []
        for i, (p, z) in enumerate(zip(polys, zs)):
            if z in roots:
                ys[i] = p[roots.index(z)]
            else:
                live.append(i)
        try:
            served = True
            if live:

                def _dispatch():
                    import jax.numpy as jnp
                    import numpy as np

                    from ..ops import fr as _fr

                    pd = jnp.asarray(
                        np.stack(
                            [_fr.fr_from_ints(polys[i]) for i in live]
                        )
                    )
                    zd = jnp.asarray(
                        _fr.fr_from_ints([zs[i] for i in live])
                    )
                    return _fr.fr_to_ints(
                        _fr.eval_barycentric_batch(
                            pd, _fr_roots_dev(), zd
                        )
                    )

                # bulk-class dispatch: behind pending gossip verdicts
                # at the wave boundary; a shed rides the Python tier
                served, out = _submit_bulk(_dispatch)
                if served:
                    for i, y in zip(live, out):
                        ys[i] = y
            if served:
                _FR_DISPATCH["device"] += 1
                return ys
            _FR_DEVICE_FALLBACKS += 1
        except Exception as e:
            # taxonomy (device/health.py): classify + report; a
            # programming error re-raises inside, everything else
            # stays a counted fallback onto the Python ints
            _FR_DEVICE_FALLBACKS += 1
            _report_device_fault(e, "kzg_fr")
    _FR_DISPATCH["python"] += 1
    return [
        evaluate_polynomial_in_evaluation_form(p, z)
        for p, z in zip(polys, zs)
    ]


def _device_msm_ready(n: int) -> bool:
    """Should auto mode route an n-point lincomb to the device? Only
    on a TPU host, and only once the rung's compile is warm — a cold
    rung rides the host C path (counted as a fallback) the way the
    BLS verifier's host_fallback_when_cold keeps cold buckets off
    multi-minute compiles."""
    global _MSM_DEVICE_FALLBACKS
    if not chip.on_tpu():
        return False
    from ..ops import msm as _msm

    if _msm.msm_is_warm(_msm.msm_rung(n)):
        return True
    _MSM_DEVICE_FALLBACKS += 1
    return False


def _resolve_msm_path(n: int) -> str:
    mode = _msm_backend
    if mode == "device":
        return "device"
    if mode == "oracle":
        return "oracle"
    if mode == "native":
        return "native" if native.available() else "oracle"
    if _device_msm_ready(n):
        return "device"
    return "native" if native.available() else "oracle"


def _g1_lincomb_many(tasks):
    """Batched lincombs: [(points, scalars), ...] -> [point | None].
    On the device tier every task rides ONE dispatch (batch axis over
    lincombs — ops/msm.g1_msm_many); host tiers loop. A device error
    falls back to the host tiers (counted), never fails the caller."""
    global _MSM_DEVICE_FALLBACKS
    if not tasks:
        return []
    for pts, ks in tasks:
        assert len(pts) == len(ks)
    path = _resolve_msm_path(max(len(p) for p, _ in tasks))
    if path == "device" and _device_blocked("kzg_msm"):
        # quarantined: the host tiers serve bit-exactly (the
        # differential suite proves device == native == oracle)
        path = "native" if native.available() else "oracle"
    if path == "device":
        from ..ops import msm as _msm

        try:
            # bulk-class dispatch (device/executor.py): queues behind
            # pending gossip verdicts; an admission-control shed
            # falls back to the host tiers like any device miss
            served, out = _submit_bulk(
                lambda: _msm.g1_msm_many(tasks)
            )
            if served:
                _MSM_DISPATCH["device"] += 1
                return out
            _MSM_DEVICE_FALLBACKS += 1
            path = "native" if native.available() else "oracle"
        except Exception as e:
            # taxonomy (device/health.py): classify + report; a
            # programming error re-raises inside, device kinds stay
            # counted fallbacks onto the host tiers
            _MSM_DEVICE_FALLBACKS += 1
            _report_device_fault(e, "kzg_msm")
            path = "native" if native.available() else "oracle"
    if path == "native":
        _MSM_DISPATCH["native"] += 1
        return [native.g1_msm(pts, ks) for pts, ks in tasks]
    _MSM_DISPATCH["oracle"] += 1
    out = []
    for pts, ks in tasks:
        acc = None
        for p, s in zip(pts, ks):
            acc = oc.g1_add(acc, oc.g1_mul(p, s % BLS_MODULUS))
        out.append(acc)
    return out


def _g1_lincomb(points, scalars):
    """sum_i scalars[i] * points[i] through the three-tier backend."""
    return _g1_lincomb_many([(points, scalars)])[0]


def _pairings_one(pairs) -> bool:
    if native.available():
        return native.pairing_product_is_one(pairs)
    from .pairing import pairing_product_is_one as _oc_pairs

    return _oc_pairs(pairs)


# ---------------------------------------------------------------------------
# Trusted setup
# ---------------------------------------------------------------------------


class TrustedSetup:
    """g1_lagrange_brp: blob-width lagrange-basis G1 points, bit-reversal
    permuted (the order polynomials-in-evaluation-form use);
    g2_monomial_1: tau*G2."""

    def __init__(self, g1_lagrange: list, g2_monomial: list):
        if len(g1_lagrange) != FIELD_ELEMENTS_PER_BLOB:
            raise KzgError(
                f"setup has {len(g1_lagrange)} G1 points, "
                f"need {FIELD_ELEMENTS_PER_BLOB}"
            )
        if len(g2_monomial) < 2:
            raise KzgError("setup needs >= 2 G2 monomial points")
        self.g1_lagrange_brp = _bit_reversal_permutation(g1_lagrange)
        self.g2_monomial_1 = g2_monomial[1]


_ACTIVE_SETUP: TrustedSetup | None = None


def load_trusted_setup(path: str | os.PathLike) -> TrustedSetup:
    """Load + activate a setup in the standard JSON format."""
    data = json.loads(Path(path).read_text())
    g1 = [
        _g1_decompress(bytes.fromhex(h.removeprefix("0x")))
        for h in data["g1_lagrange"]
    ]
    g2 = [
        oc.g2_from_bytes(bytes.fromhex(h.removeprefix("0x")))
        for h in data["g2_monomial"][:2]
    ]
    setup = TrustedSetup(g1, g2)
    activate_trusted_setup(setup)
    return setup


def activate_trusted_setup(setup: TrustedSetup) -> None:
    global _ACTIVE_SETUP
    _ACTIVE_SETUP = setup


def _setup() -> TrustedSetup:
    if _ACTIVE_SETUP is None:
        activate_trusted_setup(dev_trusted_setup())
    return _ACTIVE_SETUP


_DEV_TAU_SEED = b"lodestar_tpu INSECURE dev trusted setup tau v1"


def dev_trusted_setup(cache_dir: str | None = None) -> TrustedSetup:
    """Generate (or load the cached) **INSECURE** dev setup.

    tau is derived from a public seed, so anyone can forge proofs
    against this setup — tests and dev chains only. Production must
    `load_trusted_setup` with the ceremony output.
    """
    d = Path(cache_dir) if cache_dir else native_build.LIB_DIR
    d.mkdir(parents=True, exist_ok=True)
    cache = d / f"dev_trusted_setup_{FIELD_ELEMENTS_PER_BLOB}.json"
    if cache.exists():
        try:
            data = json.loads(cache.read_text())
            g1 = [oc_from_hex(h) for h in data["g1_lagrange"]]
            g2 = [g2_from_json(v) for v in data["g2_monomial"]]
            return TrustedSetup(g1, g2)
        except (ValueError, KeyError, OSError):
            # corrupt/stale cache data (bad JSON, missing keys, bad
            # hex/point bytes, unreadable file): regenerate below.
            # Anything else — a programming error in the parse path —
            # re-raises instead of silently burning the cache.
            cache.unlink()

    tau = int.from_bytes(sha256(_DEV_TAU_SEED).digest(), "big") % BLS_MODULUS
    n = FIELD_ELEMENTS_PER_BLOB
    roots = compute_roots_of_unity(n)
    # L_i(tau) = w^i * (tau^n - 1) / (n * (tau - w^i))
    tau_n_minus_1 = (pow(tau, n, BLS_MODULUS) - 1) % BLS_MODULUS
    denoms = _fr_batch_inv([(tau - w) % BLS_MODULUS for w in roots])
    n_inv = _fr_inv(n)
    scalars = [
        w * tau_n_minus_1 % BLS_MODULUS * d % BLS_MODULUS * n_inv % BLS_MODULUS
        for w, d in zip(roots, denoms)
    ]
    g1 = [_g1_mul(oc.G1_GEN, s) for s in scalars]
    g2 = [oc.G2_GEN, _g2_mul(oc.G2_GEN, tau)]
    cache.write_text(
        json.dumps(
            {
                "g1_lagrange": [oc_to_hex(p) for p in g1],
                "g2_monomial": [g2_to_json_val(p) for p in g2],
            }
        )
    )
    return TrustedSetup(g1, g2)


def oc_to_hex(p) -> str:
    return native.g1_to_bytes(p).hex()


def oc_from_hex(h: str):
    return native.g1_from_bytes_affine(bytes.fromhex(h))


def g2_to_json_val(p) -> str:
    return native.g2_to_bytes(p).hex()


def g2_from_json(h: str):
    return native.g2_from_bytes_affine(bytes.fromhex(h))


# ---------------------------------------------------------------------------
# Blob <-> polynomial
# ---------------------------------------------------------------------------


def bytes_to_bls_field(b: bytes) -> int:
    x = int.from_bytes(b, "big")
    if x >= BLS_MODULUS:
        raise KzgError("field element >= BLS modulus")
    return x


def hash_to_bls_field(data: bytes) -> int:
    return int.from_bytes(sha256(data).digest(), "big") % BLS_MODULUS


def blob_to_polynomial(blob: bytes) -> list[int]:
    if len(blob) != BYTES_PER_BLOB:
        raise KzgError(f"blob must be {BYTES_PER_BLOB} bytes")
    return [
        bytes_to_bls_field(blob[i * 32 : (i + 1) * 32])
        for i in range(FIELD_ELEMENTS_PER_BLOB)
    ]


def _validate_g1(b: bytes):
    """48B compressed -> point, with curve+subgroup checks."""
    if len(b) != 48:
        raise KzgError("compressed G1 must be 48 bytes")
    try:
        return _g1_decompress(bytes(b))
    except Exception as e:
        raise KzgError(f"invalid G1 point: {e}") from e


# ---------------------------------------------------------------------------
# Core spec functions
# ---------------------------------------------------------------------------


def blob_to_kzg_commitment(blob: bytes) -> bytes:
    poly = blob_to_polynomial(blob)
    return _g1_compress(_g1_lincomb(_setup().g1_lagrange_brp, poly))


def evaluate_polynomial_in_evaluation_form(poly: list[int], z: int) -> int:
    """Barycentric evaluation over the brp'd domain."""
    width = FIELD_ELEMENTS_PER_BLOB
    roots = _roots_brp()
    if z in roots:
        return poly[roots.index(z)]
    inv = _fr_batch_inv([(z - w) % BLS_MODULUS for w in roots])
    acc = 0
    for p_i, w, iv in zip(poly, roots, inv):
        acc = (acc + p_i * w % BLS_MODULUS * iv) % BLS_MODULUS
    zn_minus_1 = (pow(z, width, BLS_MODULUS) - 1) % BLS_MODULUS
    return acc * zn_minus_1 % BLS_MODULUS * _fr_inv(width) % BLS_MODULUS


def compute_kzg_proof_impl(poly: list[int], z: int) -> tuple[bytes, int]:
    """Proof that poly(z) == y; returns (proof48, y)."""
    roots = _roots_brp()
    y = evaluate_polynomial_in_evaluation_form(poly, z)
    numers = [(p - y) % BLS_MODULUS for p in poly]
    if z in roots:
        m = roots.index(z)
        # quotient value at the domain point itself
        # (compute_quotient_eval_within_domain)
        q = [0] * FIELD_ELEMENTS_PER_BLOB
        inv = _fr_batch_inv(
            [
                (w - z) % BLS_MODULUS if i != m else 1
                for i, w in enumerate(roots)
            ]
        )
        qm = 0
        z_inv = _fr_inv(z)
        for i, (num, w, iv) in enumerate(zip(numers, roots, inv)):
            if i == m:
                continue
            q[i] = num * iv % BLS_MODULUS
            # spec compute_quotient_eval_within_domain:
            # += (p_i - y) * w_i / (z * (z - w_i)) — note (z - w_i)
            qm = (
                qm
                - num * w % BLS_MODULUS * iv % BLS_MODULUS * z_inv
            ) % BLS_MODULUS
        q[m] = qm
    else:
        inv = _fr_batch_inv([(w - z) % BLS_MODULUS for w in roots])
        q = [n * iv % BLS_MODULUS for n, iv in zip(numers, inv)]
    proof = _g1_compress(_g1_lincomb(_setup().g1_lagrange_brp, q))
    return proof, y


def compute_kzg_proof(blob: bytes, z_bytes: bytes) -> tuple[bytes, bytes]:
    poly = blob_to_polynomial(blob)
    proof, y = compute_kzg_proof_impl(poly, bytes_to_bls_field(z_bytes))
    return proof, int(y).to_bytes(32, "big")


def verify_kzg_proof(
    commitment_bytes: bytes, z_bytes: bytes, y_bytes: bytes, proof_bytes: bytes
) -> bool:
    return verify_kzg_proof_impl(
        _validate_g1(commitment_bytes),
        bytes_to_bls_field(z_bytes),
        bytes_to_bls_field(y_bytes),
        _validate_g1(proof_bytes),
    )


def verify_kzg_proof_impl(commitment, z: int, y: int, proof) -> bool:
    """e(C - y*G1, -G2) * e(proof, tau*G2 - z*G2) == 1."""
    s = _setup()
    p_minus_y = _g1_add(commitment, _g1_mul(oc.G1_GEN, (-y) % BLS_MODULUS))
    x_minus_z = _g2_add(
        s.g2_monomial_1, _g2_mul(oc.G2_GEN, (-z) % BLS_MODULUS)
    )
    return _pairings_one(
        [(p_minus_y, oc.g2_neg(oc.G2_GEN)), (proof, x_minus_z)]
    )


def compute_challenge(blob: bytes, commitment_bytes: bytes) -> int:
    # KZG_ENDIANNESS='big' (deneb polynomial-commitments spec; c-kzg
    # writes the 16-byte degree big-endian)
    degree = FIELD_ELEMENTS_PER_BLOB.to_bytes(16, "big")
    return hash_to_bls_field(
        FIAT_SHAMIR_PROTOCOL_DOMAIN + degree + blob + commitment_bytes
    )


def compute_blob_kzg_proof(blob: bytes, commitment_bytes: bytes) -> bytes:
    _validate_g1(commitment_bytes)
    z = compute_challenge(blob, commitment_bytes)
    proof, _ = compute_kzg_proof_impl(blob_to_polynomial(blob), z)
    return proof


def verify_blob_kzg_proof(
    blob: bytes, commitment_bytes: bytes, proof_bytes: bytes
) -> bool:
    commitment = _validate_g1(commitment_bytes)
    z = compute_challenge(blob, commitment_bytes)
    y = evaluate_polynomial_in_evaluation_form(blob_to_polynomial(blob), z)
    return verify_kzg_proof_impl(commitment, z, y, _validate_g1(proof_bytes))


def verify_blob_kzg_proof_batch(
    blobs: list[bytes],
    commitment_bytes_list: list[bytes],
    proof_bytes_list: list[bytes],
) -> bool:
    """Random-linear-combination batch verification (spec
    verify_kzg_proof_batch): one 2-pairing check for n blobs, the
    three verification lincombs batched into ONE device dispatch on
    the device MSM tier. The length check comes first — a
    proofs/commitments mismatch must raise, not be zip-truncated into
    a verdict about a batch nobody submitted — and the empty batch
    short-circuits True without touching the trusted setup."""
    n = len(blobs)
    if not (n == len(commitment_bytes_list) == len(proof_bytes_list)):
        raise KzgError(
            f"batch length mismatch: {n} blobs, "
            f"{len(commitment_bytes_list)} commitments, "
            f"{len(proof_bytes_list)} proofs"
        )
    if n == 0:
        return True
    if _BATCH_HIST is not None:
        _BATCH_HIST.observe(n)
    commitments = [_validate_g1(c) for c in commitment_bytes_list]
    proofs = [_validate_g1(p) for p in proof_bytes_list]
    zs, polys = [], []
    for blob, cb in zip(blobs, commitment_bytes_list):
        zs.append(compute_challenge(blob, cb))
        polys.append(blob_to_polynomial(blob))
    # the whole batch's barycentric math in one device dispatch on
    # the Fr device tier (python tier loops the oracle)
    ys = _evaluate_polynomials_batch(polys, zs)
    # Fiat-Shamir the whole statement into one scalar; use its powers
    data = RANDOM_CHALLENGE_KZG_BATCH_DOMAIN
    data += FIELD_ELEMENTS_PER_BLOB.to_bytes(8, "big")
    data += n.to_bytes(8, "big")
    for cb, z, y, pb in zip(commitment_bytes_list, zs, ys, proof_bytes_list):
        data += bytes(cb) + z.to_bytes(32, "big") + y.to_bytes(32, "big")
        data += bytes(pb)
    r = hash_to_bls_field(data)
    r_powers = [pow(r, i, BLS_MODULUS) for i in range(n)]

    c_minus_y = [
        _g1_add(c, _g1_mul(oc.G1_GEN, (-y) % BLS_MODULUS))
        for c, y in zip(commitments, ys)
    ]
    proof_lincomb, proof_z_lincomb, c_minus_y_lincomb = _g1_lincomb_many(
        [
            (proofs, r_powers),
            (
                proofs,
                [rp * z % BLS_MODULUS for rp, z in zip(r_powers, zs)],
            ),
            (c_minus_y, r_powers),
        ]
    )
    lhs = _g1_add(c_minus_y_lincomb, proof_z_lincomb)
    return _pairings_one(
        [
            (lhs, oc.g2_neg(oc.G2_GEN)),
            (proof_lincomb, _setup().g2_monomial_1),
        ]
    )
