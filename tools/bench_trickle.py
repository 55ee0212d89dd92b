"""Gossip-shaped trickle benchmark: the small-bucket steady state.

bench.py measures bulk waves (16x128-set jobs); the PRODUCTION
steady state is the opposite shape — same-message groups of a few
dozen sigs flushed by the attData-keyed queues, plus single
aggregate-and-proof sets dripping in between. This drives
`TpuBlsVerifier` with exactly that arrival pattern and reports, per
group size {1, 16, 32, 128}:

  - sustained sigs/s over the whole trickle
  - p50 / p99 submit-to-verdict latency (caller-observed, which
    includes the gossip buffer + rolling-bucket wait by design)

plus the verifier's per-bucket-size / per-path dispatch counters —
the proof of whether trickle traffic coalesced into device-ingest
buckets (continuous batching) or fell down the host-path cliff.

Default mode is sized for this container's CPU XLA (no TPU attached:
absolute numbers measure a 1-core host emulating the device and are
committed as the honest artifact this environment can produce; see
the caveat field in the JSON). `--real` runs the production shape on
an attached TPU. `--no-rolling` disables continuous batching
(latency budget 0) for an A/B pair.

  python tools/bench_trickle.py --json-out BENCH_trickle.json
  python tools/bench_trickle.py --real --json-out BENCH_trickle.json
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

sys.path.insert(0, ".")


def _build_single_sets(n: int):
    """n independent 1-set jobs (gossip aggregate-and-proof shape)."""
    from lodestar_tpu.bls import SignatureSet
    from lodestar_tpu.crypto.bls import curve as oc
    from lodestar_tpu.crypto.bls import native
    from lodestar_tpu.params import BLS_DST_SIG

    dst = bytes(BLS_DST_SIG)
    out = []
    for i in range(n):
        sk = 3 + i % 512
        msg = (900_000 + i).to_bytes(32, "little")
        h = native.hash_to_g2(msg, dst)
        pk = oc.g1_to_bytes(native.g1_mul(oc.G1_GEN, sk))
        s = oc.g2_to_bytes(native.g2_mul(h, sk))
        out.append([SignatureSet(pk, msg, s)])
    return out


def _build_same_message_group(size: int, tag: int):
    """One attData-keyed group: `size` (pubkey, signature) pairs on a
    shared message (unaggregated-attestation shape)."""
    from lodestar_tpu.bls import SameMessageSet
    from lodestar_tpu.crypto.bls import curve as oc
    from lodestar_tpu.crypto.bls import native
    from lodestar_tpu.params import BLS_DST_SIG

    msg = (800_000 + tag).to_bytes(32, "little")
    h = native.hash_to_g2(msg, bytes(BLS_DST_SIG))
    pairs = []
    for i in range(size):
        sk = 7 + (tag * size + i) % 512
        pairs.append(
            SameMessageSet(
                oc.g1_to_bytes(native.g1_mul(oc.G1_GEN, sk)),
                oc.g2_to_bytes(native.g2_mul(h, sk)),
            )
        )
    return pairs, msg


async def _run_trickle(
    v,
    singles,
    groups,
    gap_s: float,
):
    """Submit the schedule as a trickle (one item every gap_s) and
    gather caller-observed latencies per group size."""
    lat: dict[int, list[float]] = {}
    t_start = time.perf_counter()
    tasks = []

    async def one_single(sets):
        t0 = time.perf_counter()
        ok = await v.verify_signature_sets(sets, batchable=True)
        lat.setdefault(1, []).append(time.perf_counter() - t0)
        return ok

    async def one_group(pairs, msg):
        t0 = time.perf_counter()
        res = await v.verify_signature_sets_same_message(pairs, msg)
        lat.setdefault(len(pairs), []).append(
            time.perf_counter() - t0
        )
        return all(res)

    # interleave: groups spaced through the single-set drip
    schedule: list = [("s", s) for s in singles]
    stride = max(1, len(schedule) // max(1, len(groups)))
    for i, g in enumerate(groups):
        schedule.insert(min(len(schedule), (i + 1) * stride), ("g", g))
    for kind, item in schedule:
        if kind == "s":
            tasks.append(asyncio.ensure_future(one_single(item)))
        else:
            tasks.append(
                asyncio.ensure_future(one_group(item[0], item[1]))
            )
        await asyncio.sleep(gap_s)
    oks = await asyncio.gather(*tasks)
    wall = time.perf_counter() - t_start
    if not all(oks):
        raise RuntimeError("trickle verify returned False on valid sigs")
    return lat, wall


def _quantile(xs: list[float], q: float) -> float:
    ys = sorted(xs)
    if not ys:
        return 0.0
    i = min(len(ys) - 1, int(q * len(ys)))
    return ys[i]


async def _bench(args) -> dict:
    from lodestar_tpu.bls import TpuBlsVerifier
    from lodestar_tpu.bls import kernels as K

    if args.autotune_from:
        # replay a recorded autotune decision (device/autotune.py):
        # the trickle then measures the tuner's configuration. Like
        # --ingest-min-bucket below, an EXPLICIT --latency-budget-ms
        # wins over the replayed value (A/B runs against the tuned
        # config must be possible).
        from lodestar_tpu.device import autotune as _at

        cfg = _at.apply_decision(_at.load_decision(args.autotune_from))
        if args.latency_budget_ms is None:
            args.latency_budget_ms = cfg.latency_budget_ms
    if args.latency_budget_ms is None:
        args.latency_budget_ms = 50
    if args.ingest_min_bucket is not None:
        K.set_ingest_min_bucket(args.ingest_min_bucket)

    group_sizes = (
        (16, 32, 128) if args.real else tuple(args.group_sizes)
    )
    n_singles = args.singles
    reps = args.group_reps
    singles = _build_single_sets(n_singles)
    groups = []
    tag = 0
    for _ in range(reps):
        for gs in group_sizes:
            groups.append(_build_same_message_group(gs, tag))
            tag += 1

    v = TpuBlsVerifier(
        latency_budget_ms=0 if args.no_rolling else args.latency_budget_ms,
        pipeline_depth=args.pipeline_depth,
    )
    if args.warmup:
        v.start_warmup(block=True)

    # warmup pass: compile every bucket shape this schedule touches so
    # the measured trickle sees a WARM node (production steady state)
    warm_lat, _ = await _run_trickle(
        v,
        _build_single_sets(min(8, n_singles)),
        [
            _build_same_message_group(gs, 10_000 + i)
            for i, gs in enumerate(group_sizes)
        ],
        args.gap_ms / 1000.0,
    )
    m = v.metrics
    # reset counters so the report covers only the measured run
    from lodestar_tpu.bls.verifier import LatencyHistogram

    m.dispatch_by_bucket = {}
    m.dispatch_by_path = {k: 0 for k in m.dispatch_by_path}
    m.rolling_flushes = {k: 0 for k in m.rolling_flushes}
    m.verify_latency = LatencyHistogram()
    m.same_message_latency = LatencyHistogram()

    lat, wall = await _run_trickle(
        v, singles, groups, args.gap_ms / 1000.0
    )
    depth = v.pipeline_depth()
    await v.close()

    total_sigs = n_singles + reps * sum(group_sizes)
    # overlapped-vs-sync A/B: when the measured run overlapped waves
    # (depth > 1), repeat the SAME schedule synchronously (depth 1,
    # every program already warm) so the report carries both columns
    sync_wall = None
    if depth > 1:
        v_sync = TpuBlsVerifier(
            latency_budget_ms=(
                0 if args.no_rolling else args.latency_budget_ms
            ),
            pipeline_depth=1,
        )
        _, sync_wall = await _run_trickle(
            v_sync, singles, groups, args.gap_ms / 1000.0
        )
        await v_sync.close()
    per_size = {}
    for size in sorted(lat):
        xs = lat[size]
        sigs = size * len(xs)
        per_size[str(size)] = {
            "requests": len(xs),
            "sigs": sigs,
            "p50_ms": round(_quantile(xs, 0.5) * 1e3, 2),
            "p99_ms": round(_quantile(xs, 0.99) * 1e3, 2),
        }
    import jax

    from lodestar_tpu.utils.provenance import provenance

    pipeline: dict = {"depth": depth}
    if sync_wall:
        pipeline["sync_sigs_per_sec"] = round(
            total_sigs / sync_wall, 2
        )
        pipeline["overlap_speedup"] = round(sync_wall / wall, 4)
    return {
        "metric": "bls_trickle_gossip_shaped",
        "provenance": provenance(),
        "platform": jax.default_backend(),
        "devices": len(jax.devices()),
        "rolling_enabled": not args.no_rolling,
        "pipeline": pipeline,
        "latency_budget_ms": args.latency_budget_ms,
        "ingest_min_bucket": K.ingest_min_bucket(),
        "gap_ms": args.gap_ms,
        "total_sigs": total_sigs,
        "wall_s": round(wall, 3),
        "sigs_per_sec": round(total_sigs / wall, 2),
        "per_group_size": per_size,
        "dispatch_by_bucket": {
            str(k): c
            for k, c in sorted(m.dispatch_by_bucket.items())
        },
        "dispatch_by_path": dict(m.dispatch_by_path),
        "rolling_flushes": dict(m.rolling_flushes),
        "verifier_latency": m.verify_latency.snapshot(),
        "same_message_latency": m.same_message_latency.snapshot(),
        "caveat": (
            "real TPU attached; production trickle shape"
            if jax.default_backend() == "tpu"
            else "NO TPU in this container: CPU XLA emulates the "
            "device on one host core, so absolute sigs/s and "
            "latency measure the emulation, not the chip; the "
            "arrival shape, coalescing behavior, and counters are "
            "real. Run with --real on TPU hardware for the chip "
            "numbers."
        ),
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--real", action="store_true",
                   help="production sizes (requires an attached TPU "
                   "for meaningful numbers)")
    p.add_argument("--singles", type=int, default=24,
                   help="number of 1-set aggregate jobs in the trickle")
    p.add_argument("--group-sizes", type=int, nargs="+",
                   default=[16, 32, 128],
                   help="same-message group sizes to interleave")
    p.add_argument("--group-reps", type=int, default=2,
                   help="repetitions of each group size")
    p.add_argument("--gap-ms", type=float, default=20.0,
                   help="arrival gap between trickle items")
    p.add_argument("--latency-budget-ms", type=int, default=None,
                   help="rolling-bucket latency budget (default 50; "
                   "an explicit value wins over --autotune-from)")
    p.add_argument("--ingest-min-bucket", type=int, default=None)
    p.add_argument("--pipeline-depth", type=int, default=None,
                   help="verifier wave-overlap depth (1 = synchronous "
                   "dispatch; default: verifier default). A depth > 1 "
                   "adds a second sync run for an A/B pair")
    p.add_argument("--no-rolling", action="store_true",
                   help="disable continuous batching (A/B reference)")
    p.add_argument("--warmup", action="store_true",
                   help="block on full ingest warmup before measuring")
    p.add_argument("--autotune-from", default=None,
                   help="replay a recorded autotune decision JSON "
                   "(AUTOTUNE.json) before measuring")
    p.add_argument("--json-out", default=None)
    args = p.parse_args()
    if args.real:
        args.singles = max(args.singles, 64)
        args.group_reps = max(args.group_reps, 8)
    out = asyncio.run(_bench(args))
    line = json.dumps(out, indent=2)
    print(line)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
