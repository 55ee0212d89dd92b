"""Multi-chip batch-axis scaling sweep: sets/s vs device count.

COVERAGE.md's mesh-scaling claim must be backed by a measurement, not
an assertion (VERDICT r3+): this tool runs the SAME batch-verify
kernels production uses (bls/kernels.run_verify_batch) with the
signature batch axis sharded over a 1/2/4/8-device mesh
(lodestar_tpu/parallel) and reports sets/s + parallel efficiency per
device count.

Modes:
  parent (default): re-execs itself once per device count with
    JAX_PLATFORMS=cpu + --xla_force_host_platform_device_count=D when
    the host has fewer than D real devices (the same dance as
    __graft_entry__.dryrun_multichip — the flags must be set before
    jax import). With >= D real TPU chips the child inherits them and
    the numbers are real scaling; on the CPU fallback the curve
    validates sharding correctness and collective lowering, not
    absolute throughput (one host core executes all virtual devices).
  child (--child): builds n valid sets, shards them over a D-device
    mesh, warms the compile, times reps of the full verify pipeline,
    prints one JSON line.

The workload is FIXED across device counts (strong scaling): the same
n sets are split D ways, so ideal scaling is rate_D == D * rate_1 and
efficiency = rate_D / (D * rate_1).

tests/test_mesh_sweep.py smoke-runs run_workload() on the 8-virtual-
device tier-1 mesh so mesh-sharding breakage is caught by `-m 'not
slow'`, not only by TPU runs.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_COUNTS = (1, 2, 4, 8)


def build_inputs(n: int):
    """n valid (pk, H(msg), sig) sets as device batches + rand bits.
    Small scalars keep fixture cost low; verify cost is scalar-blind."""
    import jax.numpy as jnp

    from lodestar_tpu.bls import kernels
    from lodestar_tpu.crypto.bls import curve as oc
    from lodestar_tpu.ops import curve as C

    hs = [oc.g2_mul(oc.G2_GEN, 7 + i) for i in range(n)]
    pks, sigs = [], []
    for i, h in enumerate(hs):
        sk = 100 + i
        pks.append(oc.g1_mul(oc.G1_GEN, sk))
        sigs.append(oc.g2_mul(h, sk))
    pk_dev = C.g1_batch_from_ints(pks)
    h_pt = C.g2_batch_from_ints(hs)
    h_dev = (h_pt.x, h_pt.y)  # affine coords, as the verifier passes h
    sig_dev = C.g2_batch_from_ints(sigs)
    rand = [
        ((0x9E3779B97F4A7C15 ^ (i * 0x5851F42D4C957F2D)) & (2**64 - 1)) | 1
        for i in range(n)
    ]
    bits = C.scalars_to_bits(rand, kernels.RAND_BITS)
    mask = jnp.ones(n, bool)
    return pk_dev, h_dev, sig_dev, bits, mask


def run_workload(n_devices: int, n_sets: int, reps: int = 1):
    """Verify n_sets sharded over an n_devices mesh; returns
    (sets_per_sec, all_valid). Compile excluded (one warmup rep).
    reps=0 is smoke mode: only the warmup correctness run executes
    and the rate is reported as 0.0."""
    import jax

    from lodestar_tpu import parallel
    from lodestar_tpu.bls import kernels

    assert n_sets % n_devices == 0, "batch axis must divide the mesh"
    mesh = parallel.make_mesh(n_devices)
    pk_dev, h_dev, sig_dev, bits, mask = build_inputs(n_sets)
    pk_dev = parallel.shard_batch(mesh, pk_dev)
    h_dev = parallel.shard_batch(mesh, h_dev)
    sig_dev = parallel.shard_batch(mesh, sig_dev)
    bits = parallel.shard_batch(mesh, bits)
    mask = parallel.shard_batch(mesh, mask)

    def once() -> bool:
        return bool(
            jax.device_get(
                kernels.run_verify_batch_async(
                    pk_dev, h_dev, sig_dev, bits, mask
                )
            )
        )

    ok = once()  # warmup: compile + correctness gate
    if reps == 0:
        return 0.0, ok
    t0 = time.perf_counter()
    for _ in range(reps):
        ok = once() and ok
    dt = time.perf_counter() - t0
    return n_sets * reps / dt, ok


def _child(args) -> None:
    import jax

    from lodestar_tpu.ops import limbs as L

    if args.autotune_from:
        # replay the recorded decision in EVERY child: the sweep then
        # measures the tuner's backend/ladder at each mesh size
        from lodestar_tpu.device import autotune as AT

        AT.apply_decision(AT.load_decision(args.autotune_from))
    if args.limb_backend:
        L.set_backend(args.limb_backend)
    rate, ok = run_workload(args.devices, args.sets, args.reps)
    print(
        json.dumps(
            {
                "devices": args.devices,
                "sets": args.sets,
                "reps": args.reps,
                "platform": jax.default_backend(),
                "limb_backend": L.get_backend(),
                "sets_per_sec": round(rate, 2),
                "ok": ok,
            }
        )
    )


def _spawn(d: int, args) -> dict:
    env = dict(os.environ)
    # scrub accelerator bindings unless the host really has d devices
    # (same scrub list as __graft_entry__.dryrun_multichip)
    if not args.real:
        for k in list(env):
            if k.startswith(("TPU_", "PJRT_", "LIBTPU")):
                env.pop(k)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={d}"
        ).strip()
    cmd = [
        sys.executable,
        os.path.abspath(__file__),
        "--child",
        "--devices",
        str(d),
        "--sets",
        str(args.sets),
        "--reps",
        str(args.reps),
    ]
    if args.limb_backend:
        cmd += ["--limb-backend", args.limb_backend]
    if args.autotune_from:
        cmd += ["--autotune-from", os.path.abspath(args.autotune_from)]
    res = subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=3600
    )
    if res.returncode != 0:
        raise RuntimeError(
            f"sweep child (devices={d}) failed:\n{res.stdout[-2000:]}\n"
            f"{res.stderr[-4000:]}"
        )
    return json.loads(res.stdout.strip().splitlines()[-1])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument(
        "--counts",
        default=",".join(map(str, DEFAULT_COUNTS)),
        help="device counts to sweep (parent mode)",
    )
    ap.add_argument("--sets", type=int, default=64)
    ap.add_argument("--reps", type=int, default=1)
    ap.add_argument(
        "--real",
        action="store_true",
        help="use the ambient (TPU) devices instead of virtual CPU ones",
    )
    ap.add_argument(
        "--limb-backend", choices=("vpu", "mxu"), default=None
    )
    ap.add_argument(
        "--autotune-from", default=None,
        help="replay a recorded autotune decision JSON in every "
        "sweep child before measuring",
    )
    ap.add_argument(
        "--json-out", default=None, help="write the sweep table here"
    )
    args = ap.parse_args()
    if args.child:
        _child(args)
        return
    counts = [int(c) for c in args.counts.split(",")]
    rows = [_spawn(d, args) for d in counts]
    base = rows[0]["sets_per_sec"] / rows[0]["devices"]
    for r in rows:
        # base is 0.0 in reps=0 smoke mode (no timed rep ran)
        r["efficiency"] = (
            round(r["sets_per_sec"] / (base * r["devices"]), 3)
            if base > 0
            else None
        )
    from lodestar_tpu.utils.provenance import provenance

    out = {
        "workload": f"{args.sets} sets x {args.reps} reps, fixed batch",
        "provenance": provenance(),
        "platform": rows[0]["platform"],
        "limb_backend": rows[0]["limb_backend"],
        "rows": rows,
    }
    print(json.dumps(out, indent=2))
    print("\n| devices | sets/s | efficiency | ok |")
    print("|---|---|---|---|")
    for r in rows:
        print(
            f"| {r['devices']} | {r['sets_per_sec']} | "
            f"{r['efficiency']} | {r['ok']} |"
        )
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2)


if __name__ == "__main__":
    main()
