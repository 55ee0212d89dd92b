"""Fine-grained timing of run_verify_batch glue at bucket 128."""

from __future__ import annotations

import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from lodestar_tpu.bls import kernels  # noqa: E402
from lodestar_tpu.bls.verifier import _rand_scalars  # noqa: E402
from lodestar_tpu.crypto.bls import curve as oc  # noqa: E402
from lodestar_tpu.crypto.bls.hash_to_curve import hash_to_g2  # noqa: E402
from lodestar_tpu.ops import curve as C  # noqa: E402
from lodestar_tpu.params import BLS_DST_SIG  # noqa: E402

N = 2048


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
    bits0 = C.scalars_to_bits(_rand_scalars(N), kernels.RAND_BITS)

    # warm everything
    ok = kernels.run_verify_batch(pk, (h.x, h.y), sig, bits0, mask)
    print("warm ok:", ok, flush=True)

    for rep in range(3):
        t0 = time.perf_counter()
        scalars = _rand_scalars(N)
        t1 = time.perf_counter()
        bits = C.scalars_to_bits(scalars, kernels.RAND_BITS)
        jax.block_until_ready(bits)
        t2 = time.perf_counter()
        anym = bool(np.any(np.asarray(mask)))
        t3 = time.perf_counter()
        out1 = kernels._stage_prepare_batch(pk, h.x, h.y, sig, bits, mask)
        jax.block_until_ready(out1)
        t4 = time.perf_counter()
        f = kernels._stage_miller(*out1[:4])
        jax.block_until_ready(f)
        t5 = time.perf_counter()
        prod = kernels._stage_product(f, out1[4])
        jax.block_until_ready(prod)
        t6 = time.perf_counter()
        fin = kernels._stage_final_with_valid(prod, True)
        ok = bool(fin)
        t7 = time.perf_counter()
        print(
            f"rep{rep}: rand={1e3 * (t1 - t0):.1f} bits={1e3 * (t2 - t1):.1f} "
            f"anymask={1e3 * (t3 - t2):.1f} prepare={1e3 * (t4 - t3):.1f} "
            f"miller={1e3 * (t5 - t4):.1f} product={1e3 * (t6 - t5):.1f} "
            f"final+bool={1e3 * (t7 - t6):.1f} total={1e3 * (t7 - t0):.1f} ms",
            flush=True,
        )


if __name__ == "__main__":
    main()
