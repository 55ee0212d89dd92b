"""TRUE per-stage device budget of the production 2048-set pipeline.

profile_bucket.py forces each stage with a FULL-tensor device_get,
which adds the transfer of every stage's outputs — fine for ranking,
useless as a budget. This tool instead times
cumulative PREFIXES of the stage chain, reducing each prefix's output
to one scalar on device (a tiny extra jit) so the readback is ()-
shaped; stage cost = prefix[k] - prefix[k-1]. All heavy stages hit the
same compiled artifacts production uses.
"""

from __future__ import annotations

import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from lodestar_tpu.bls import kernels  # noqa: E402
from lodestar_tpu.bls import api as bls_api  # noqa: E402
from lodestar_tpu.bls.verifier import _rand_scalars  # noqa: E402
from lodestar_tpu.crypto.bls import curve as oc  # noqa: E402
from lodestar_tpu.crypto.bls.hash_to_curve import hash_to_g2  # noqa: E402
from lodestar_tpu.ops import curve as C  # noqa: E402
from lodestar_tpu.ops import limbs as L  # noqa: E402
from lodestar_tpu.params import BLS_DST_SIG  # noqa: E402

N = 2048
KEYS = 256

# --limb-backend {vpu,mxu}: stage-budget either limb backend through
# the same compiled artifacts (regressions between backends must be
# attributable per stage). --n M shrinks the bucket for CPU smokes.
if "--limb-backend" in sys.argv:
    L.set_backend(sys.argv[sys.argv.index("--limb-backend") + 1])
if "--n" in sys.argv:
    N = int(sys.argv[sys.argv.index("--n") + 1])
    KEYS = min(KEYS, N)


@jax.jit
def _scalarize(tree):
    """Reduce any pytree of arrays to one int32 scalar on device."""
    leaves = jax.tree.leaves(tree)
    acc = jnp.int32(0)
    for leaf in leaves:
        acc = acc + jnp.sum(leaf.astype(jnp.int32) & 0xFF)
    return acc


def timeit(label, fn, reps=3):
    out = fn()
    np.asarray(jax.device_get(out))  # warm (stages already cached)
    t0 = time.perf_counter()
    for _ in range(reps):
        np.asarray(jax.device_get(fn()))
    dt = (time.perf_counter() - t0) / reps
    print(f"{label}: {dt * 1000:.1f} ms", flush=True)
    return dt


def main() -> None:
    print(
        f"platform={jax.default_backend()} N={N} "
        f"limb_backend={L.get_backend()}",
        flush=True,
    )
    pks, sig_parts, draws = [], [], []
    key_pts = {}
    for i in range(N):
        sk = 10_000 + (i % KEYS)
        if sk not in key_pts:
            key_pts[sk] = oc.g1_mul(oc.G1_GEN, sk)
        msg = i.to_bytes(32, "little")
        h = hash_to_g2(msg, BLS_DST_SIG)
        pks.append(key_pts[sk])
        xc0, xc1, sgn, ok = bls_api.parse_signature(
            oc.g2_to_bytes(oc.g2_mul(h, sk))
        )
        assert ok
        sig_parts.append((xc0, xc1, sgn))
        draws.append(bls_api.message_draws(msg))

    pk = C.g1_batch_from_ints(pks)
    sig_x = (
        L.from_ints([s[0] for s in sig_parts]),
        L.from_ints([s[1] for s in sig_parts]),
    )
    sign_arr = jnp.asarray(
        np.asarray([s[2] for s in sig_parts], np.int32)
    )
    u0 = (
        L.from_ints([d[0][0] for d in draws]),
        L.from_ints([d[0][1] for d in draws]),
    )
    u1 = (
        L.from_ints([d[1][0] for d in draws]),
        L.from_ints([d[1][1] for d in draws]),
    )
    mask = jnp.ones(N, bool)
    bits = C.scalars_to_bits(_rand_scalars(N), kernels.RAND_BITS)

    K = kernels

    def p1():
        return _scalarize(K._stage_g2_sqrt(sig_x, sign_arr))

    def p2():
        x, y, qr = K._stage_g2_sqrt(sig_x, sign_arr)
        return _scalarize(K._stage_g2_subgroup(x, y, qr, mask))

    def p3():
        x, y, qr = K._stage_g2_sqrt(sig_x, sign_arr)
        sig, av = K._stage_g2_subgroup(x, y, qr, mask)
        return _scalarize((av, K._stage_sswu_iso(u0, u1)))

    def p4():
        x, y, qr = K._stage_g2_sqrt(sig_x, sign_arr)
        sig, av = K._stage_g2_subgroup(x, y, qr, mask)
        iso = K._stage_sswu_iso(u0, u1)
        return _scalarize((av, K._stage_cofactor(iso, mask)))

    def p5():
        x, y, qr = K._stage_g2_sqrt(sig_x, sign_arr)
        sig, av = K._stage_g2_subgroup(x, y, qr, mask)
        iso = K._stage_sswu_iso(u0, u1)
        hx, hy = K._stage_cofactor(iso, mask)
        return _scalarize(
            (av, K._stage_prepare_batch(pk, hx, hy, sig, bits, mask))
        )

    def p6():
        x, y, qr = K._stage_g2_sqrt(sig_x, sign_arr)
        sig, av = K._stage_g2_subgroup(x, y, qr, mask)
        iso = K._stage_sswu_iso(u0, u1)
        hx, hy = K._stage_cofactor(iso, mask)
        px, py, qx, qy, fm = K._stage_prepare_batch(
            pk, hx, hy, sig, bits, mask
        )
        return _scalarize((av, K._stage_miller(px, py, qx, qy)))

    def p7():
        x, y, qr = K._stage_g2_sqrt(sig_x, sign_arr)
        sig, av = K._stage_g2_subgroup(x, y, qr, mask)
        iso = K._stage_sswu_iso(u0, u1)
        hx, hy = K._stage_cofactor(iso, mask)
        px, py, qx, qy, fm = K._stage_prepare_batch(
            pk, hx, hy, sig, bits, mask
        )
        f = K._stage_miller(px, py, qx, qy)
        return _scalarize((av, K._stage_product(f, fm)))

    def p8():
        return K.run_verify_batch_ingest_async(
            pk, sig_x, sign_arr, u0, u1, bits, mask
        )

    labels = [
        "sqrt", "subgroup", "sswu+iso", "cofactor", "prepare",
        "miller", "product", "final(FULL)",
    ]
    prefixes = [p1, p2, p3, p4, p5, p6, p7, p8]
    times = []
    for lbl, fn in zip(labels, prefixes):
        times.append(timeit(f"prefix..{lbl}", fn))
    print("\n-- per-stage (differences) --", flush=True)
    prev = 0.0
    for lbl, tt in zip(labels, times):
        print(f"{lbl}: {(tt - prev) * 1000:.1f} ms", flush=True)
        prev = tt
    print(
        f"TOTAL {times[-1] * 1000:.1f} ms  "
        f"-> {N / times[-1]:.0f} sets/s device ceiling",
        flush=True,
    )


if __name__ == "__main__":
    main()


