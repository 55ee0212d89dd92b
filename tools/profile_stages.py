"""Per-stage steady-state timing of the batch-verify pipeline at bucket
128 on the default platform. Run after bench.py has warmed the cache."""

from __future__ import annotations

import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from lodestar_tpu.bls import kernels  # noqa: E402
from lodestar_tpu.bls.verifier import _rand_scalars  # noqa: E402
from lodestar_tpu.crypto.bls import curve as oc  # noqa: E402
from lodestar_tpu.crypto.bls.hash_to_curve import hash_to_g2  # noqa: E402
from lodestar_tpu.ops import curve as C  # noqa: E402
from lodestar_tpu.params import BLS_DST_SIG  # noqa: E402

N = 128


def t(label, fn, reps=3):
    fn()  # warm
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
        jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps
    print(f"{label}: {dt * 1000:.2f} ms", flush=True)
    return out


def main() -> None:
    print(f"platform={jax.default_backend()}", flush=True)
    pks, hs, sigs = [], [], []
    for i in range(N):
        sk = 10_000 + i
        h = hash_to_g2(i.to_bytes(32, "little"), BLS_DST_SIG)
        pks.append(oc.g1_mul(oc.G1_GEN, sk))
        hs.append(h)
        sigs.append(oc.g2_mul(h, sk))
    pk = C.g1_batch_from_ints(pks)
    h = C.g2_batch_from_ints(hs)
    sig = C.g2_batch_from_ints(sigs)
    mask = jnp.ones(N, bool)

    t0 = time.perf_counter()
    bits = C.scalars_to_bits(_rand_scalars(N), kernels.RAND_BITS)
    jax.block_until_ready(bits)
    print(f"host rand+bits: {(time.perf_counter() - t0) * 1000:.2f} ms")

    prep = t(
        "stage prepare",
        lambda: kernels._stage_prepare_batch(pk, h.x, h.y, sig, bits, mask),
    )
    px, py, qx, qy, full_mask = prep
    f = t("stage miller", lambda: kernels._stage_miller(px, py, qx, qy))
    prod = t("stage product", lambda: kernels._stage_product(f, full_mask))
    t("stage final", lambda: kernels._stage_final_with_valid(prod, True))

    def whole():
        b = C.scalars_to_bits(_rand_scalars(N), kernels.RAND_BITS)
        return kernels.run_verify_batch(pk, (h.x, h.y), sig, b, mask)

    t0 = time.perf_counter()
    for _ in range(3):
        assert whole() is True
    print(
        f"whole verify: {(time.perf_counter() - t0) / 3 * 1000:.2f} ms",
        flush=True,
    )


if __name__ == "__main__":
    main()
