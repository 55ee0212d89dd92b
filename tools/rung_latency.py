"""Device latency of one host-path bucket (prepare_batch, pairing,
final, verdict readback) at the given ladder rungs, on the chip.

The per-job and per-set retries of a failed bucket dispatch this path
at the ladder's smallest rungs; on the chip the ladder starts at
kernels.CHIP_MIN_BUCKET. This measures what a smaller rung would save.

    python tools/rung_latency.py --rungs 4 128 --reps 30

Prints one JSON line per rung: the first call (compile or persistent
cache load) and the median, min and max of the later calls, in ms.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

sys.path.insert(0, ".")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rungs", type=int, nargs="+", default=[4, 128])
    ap.add_argument("--reps", type=int, default=30)
    args = ap.parse_args()

    import jax

    from lodestar_tpu.bls import kernels

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"rung_latency: needs a TPU, JAX's platform is {platform!r}",
              file=sys.stderr)
        return 1
    for b in args.rungs:
        batch = kernels.host_batch_args(b)
        t0 = time.perf_counter()
        if not bool(kernels.run_verify_batch_async(*batch)):
            print(f"rung_latency: bucket {b} verified False", file=sys.stderr)
            return 1
        first = time.perf_counter() - t0
        ts = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            bool(kernels.run_verify_batch_async(*batch))
            ts.append(1e3 * (time.perf_counter() - t0))
        print(json.dumps({
            "rung": b,
            "first_s": first,
            "median_ms": statistics.median(ts),
            "min_ms": min(ts),
            "max_ms": max(ts),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
