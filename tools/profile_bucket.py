"""Per-stage timing of the PRODUCTION 2048-set ingest pipeline with
forced readbacks (device_get on a leaf). Run after bench.py so all
stages hit the persistent compile cache."""

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
from lodestar_tpu.params import BLS_DST_SIG  # noqa: E402

N = 2048
KEYS = 256


def force(x):
    """Force + wait: read one scalar back from the device."""
    leaves = jax.tree.leaves(x)
    for leaf in leaves:
        np.asarray(jax.device_get(leaf))
    return x


def t(label, fn, reps=2):
    force(fn())  # warm/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = force(fn())
    dt = (time.perf_counter() - t0) / reps
    print(f"{label}: {dt * 1000:.1f} ms", flush=True)
    return out


def main() -> None:
    print(f"platform={jax.default_backend()} N={N}", flush=True)
    # build N sets over KEYS distinct keys, like bench.py
    pks, hs, sig_bytes = [], [], []
    key_pts = {}
    for i in range(N):
        sk = 10_000 + (i % KEYS)
        if sk not in key_pts:
            key_pts[sk] = oc.g1_mul(oc.G1_GEN, sk)
        msg = i.to_bytes(32, "little")
        h = hash_to_g2(msg, BLS_DST_SIG)
        pks.append(key_pts[sk])
        hs.append((msg, h))
        sig_bytes.append(oc.g2_to_bytes(oc.g2_mul(h, sk)))

    t0 = time.perf_counter()
    pk = C.g1_batch_from_ints(pks)
    sig_x0, sig_x1, sig_sign = [], [], []
    u0l, u1l = [], []
    for (msg, _h), sb in zip(hs, sig_bytes):
        xc0, xc1, sgn, ok = bls_api.parse_signature(sb)
        assert ok
        sig_x0.append(xc0)
        sig_x1.append(xc1)
        sig_sign.append(sgn)
        d = bls_api.message_draws(msg)
        u0l.append(d[0])
        u1l.append(d[1])
    from lodestar_tpu.ops import limbs as L

    sig_x = (L.from_ints(sig_x0), L.from_ints(sig_x1))
    sign_arr = jnp.asarray(np.asarray(sig_sign, np.int32))
    u0 = (L.from_ints([u[0] for u in u0l]), L.from_ints([u[1] for u in u0l]))
    u1 = (L.from_ints([u[0] for u in u1l]), L.from_ints([u[1] for u in u1l]))
    mask = jnp.ones(N, bool)
    bits = C.scalars_to_bits(_rand_scalars(N), kernels.RAND_BITS)
    print(f"host prep: {(time.perf_counter() - t0) * 1000:.0f} ms", flush=True)

    sqrt_out = t(
        "g2_sqrt (pallas chains)",
        lambda: kernels._stage_g2_sqrt(sig_x, sign_arr),
    )
    x, y, is_qr = sqrt_out
    sub_out = t(
        "g2_subgroup",
        lambda: kernels._stage_g2_subgroup(x, y, is_qr, mask),
    )
    sig, all_valid = sub_out
    iso = t("sswu+iso", lambda: kernels._stage_sswu_iso(u0, u1))
    cof = t("cofactor+affine", lambda: kernels._stage_cofactor(iso, mask))
    hx, hy = cof
    prep = t(
        "prepare (ladders+aggregate+affine)",
        lambda: kernels._stage_prepare_batch(pk, hx, hy, sig, bits, mask),
    )
    px, py, qx, qy, pair_mask = prep
    f = t("miller", lambda: kernels._stage_miller(px, py, qx, qy))
    prod = t("product", lambda: kernels._stage_product(f, pair_mask))
    t("final_exp", lambda: kernels._stage_final_with_valid(prod, all_valid))

    # end-to-end async pipeline (what the verifier dispatches)
    def full():
        return kernels.run_verify_batch_ingest_async(
            pk, sig_x, sign_arr, u0, u1, bits, mask
        )

    t("FULL pipeline", full)


if __name__ == "__main__":
    main()
