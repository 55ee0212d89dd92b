"""Chip smoke: BLS batch verification on the TPU, through the node's stack.

Drives `TpuBlsVerifier.verify_signature_sets` — the `IBlsVerifier` seam
block import calls — at the production 2048-set bucket: host prep, the
three fused device programs and the Pallas kernels, with the device
executor, the health tracker and the armed wave watchdog wired as
`BeaconNode.init` wires them. Every job's verdict is checked against the
per-set native C check, including three planted bad sets, and no bucket
may leave the device path.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the whole-bucket mesh path on four
                                      # chips against its one-chip twin

With `--chips 4` the same waves go through a verifier meshed over the
four chips (the 2048-set bucket runs the whole-bucket ingest program,
512 sets on each chip; the retries its host-decompressed program) and
through its one-chip twin (`mesh=False`); the verdicts must agree.

Off a TPU, or on any failed check, it exits non-zero and prints no
result. Otherwise the last line of standard output is the result,
`{"ok": true, "device": {...}}`. The wall times it prints are smoke
timing, not a benchmark.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import importlib.metadata
import json
import random
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

N_JOBS = 16  # concurrent verify jobs per wave (block-import shaped)
SETS_PER_JOB = 128  # reference MAX_SIGNATURE_SETS_PER_JOB
GOOD_WAVES = 2
BULK_BUCKET = N_JOBS * SETS_PER_JOB  # one wave fills the production bucket
BAD_KINDS = ("wrong_message", "other_key", "no_decompress")
# jax.monitoring durations the warm phase sums
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend compile",
}


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def say(line: str) -> None:
    print(line, flush=True)


# ---------------------------------------------------------------------------
# the checks (pure: tests/test_chip_smoke.py drives them with stubs)
# ---------------------------------------------------------------------------


def check_platform(platform: str) -> None:
    if platform != "tpu":
        raise SmokeFailure(
            f"needs a TPU, and JAX's device platform is {platform!r}"
        )


def check_verdicts(name: str, got: list, want: list) -> None:
    wrong = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    if len(got) != len(want) or wrong:
        raise SmokeFailure(
            f"{name}: job verdicts differ from the native check at jobs"
            f" {wrong} (got {len(got)} verdicts, want {len(want)})"
        )


def check_device_path(
    name: str,
    paths: dict,
    buckets: dict,
    bulk_waves: int,
    watchdog_trips: dict,
    health_state: str,
) -> None:
    """Every bulk wave rode device ingest in a bucket of BULK_BUCKET,
    no bucket left the device path, the watchdog never fired and the
    device stayed online."""
    problems = []
    if buckets.get(BULK_BUCKET, 0) < bulk_waves:
        problems.append(
            f"{buckets.get(BULK_BUCKET, 0)} buckets of {BULK_BUCKET}"
            f" for {bulk_waves} bulk waves"
        )
    if paths.get("ingest", 0) < bulk_waves:
        problems.append(
            f"ingest {paths.get('ingest', 0)} < {bulk_waves} bulk buckets"
        )
    for path in ("failover", "host_cold"):
        if paths.get(path, 0):
            problems.append(f"{path} {paths[path]}")
    trips = {k: v for k, v in watchdog_trips.items() if v}
    if trips:
        problems.append(f"watchdog trips {trips}")
    if health_state != "online":
        problems.append(f"health {health_state}")
    if problems:
        raise SmokeFailure(f"{name}: " + "; ".join(problems))


def result_line(platform: str, kind: str, count: int) -> str:
    return json.dumps(
        {
            "ok": True,
            "device": {"platform": platform, "kind": kind, "count": count},
        }
    )


# ---------------------------------------------------------------------------
# signature sets, made from the seed
# ---------------------------------------------------------------------------


def _make_set(sk: int, msg: bytes):
    from lodestar_tpu.bls import SignatureSet
    from lodestar_tpu.crypto.bls import curve as oc
    from lodestar_tpu.crypto.bls import native
    from lodestar_tpu.params import BLS_DST_SIG

    h = native.hash_to_g2(msg, bytes(BLS_DST_SIG))
    pk = oc.g1_to_bytes(native.g1_mul(oc.G1_GEN, sk))
    return SignatureSet(pk, msg, oc.g2_to_bytes(native.g2_mul(h, sk)))


def _undecompressable(sig: bytes) -> bytes:
    """A well-formed compressed G2 encoding (flags and coordinates
    parse) whose x has no point on the curve: it reaches the device,
    and decompression there fails."""
    from lodestar_tpu.bls import api
    from lodestar_tpu.crypto.bls import native

    for k in range(1, 256):
        cand = sig[:-1] + bytes([sig[-1] ^ k])
        if not api.parse_signature(cand)[3]:
            continue
        try:
            native.g2_decompress(cand)
        except native.NativeError:
            return cand
    raise SmokeFailure("found no undecompressable signature encoding")


def plant(job: list, kind: str, rng: random.Random) -> None:
    """Replace job[0] with a bad set of the given kind."""
    from lodestar_tpu.bls import SignatureSet

    s = job[0]
    if kind == "wrong_message":
        job[0] = SignatureSet(s.pubkey, rng.randbytes(32), s.signature)
    elif kind == "other_key":
        other = _make_set(rng.randrange(1, 2**255), s.message)
        job[0] = SignatureSet(s.pubkey, s.message, other.signature)
    elif kind == "no_decompress":
        job[0] = SignatureSet(
            s.pubkey, s.message, _undecompressable(s.signature)
        )
    else:
        raise ValueError(kind)


def bad_jobs(n_jobs: int) -> list[int]:
    """Where the planted sets go: one job early, one mid, one late."""
    return [(2 * i + 1) * n_jobs // 6 for i in range(len(BAD_KINDS))]


def build_waves(rng: random.Random, shapes, pool) -> list[list[list]]:
    """Waves of jobs of distinct valid sets; shapes: (n_jobs,
    sets_per_job) per wave. Keys and messages come from `rng`."""
    specs = [
        [
            [(rng.randrange(1, 2**255), rng.randbytes(32)) for _ in range(k)]
            for _ in range(n)
        ]
        for n, k in shapes
    ]
    flat = [spec for wave in specs for job in wave for spec in job]
    sets = iter(pool.map(lambda a: _make_set(*a), flat))
    return [[[next(sets) for _ in job] for job in wave] for wave in specs]


def native_set_ok(s) -> bool:
    """The per-set native C check the device's verdicts must equal."""
    from lodestar_tpu.crypto.bls import curve as oc
    from lodestar_tpu.crypto.bls import native
    from lodestar_tpu.params import BLS_DST_SIG

    try:
        pk = native.g1_decompress(s.pubkey)
        sig = native.g2_decompress(s.signature)
    except native.NativeError:
        return False
    if pk is None or sig is None:
        return False
    h = native.hash_to_g2(s.message, bytes(BLS_DST_SIG))
    return native.pairing_product_is_one(
        [(pk, h), (oc.g1_neg(oc.G1_GEN), sig)]
    )


def expected_verdicts(wave, pool) -> list[bool]:
    oks = list(pool.map(native_set_ok, [s for job in wave for s in job]))
    out, i = [], 0
    for job in wave:
        out.append(all(oks[i : i + len(job)]))
        i += len(job)
    return out


# ---------------------------------------------------------------------------
# the stack, wired as BeaconNode.init wires it
# ---------------------------------------------------------------------------


class Stack:
    """Device executor + health tracker + armed wave watchdog, shared
    by the verifiers attached to it (node.py, device fault domain)."""

    def __init__(self):
        from lodestar_tpu.bls import kernels
        from lodestar_tpu.device import executor as dexec
        from lodestar_tpu.device import health
        from lodestar_tpu.logger import get_logger

        self.executor = dexec.DeviceExecutor()
        kernels.set_maintenance_gate(self.executor.maintenance_checkpoint)
        self.tracker = health.DeviceHealthTracker(
            logger=get_logger("device-health")
        )
        kernels.set_health_gate(self.tracker.device_allowed)
        self.executor.set_health_tracker(
            self.tracker, deadlines=health.default_watchdog_deadlines()
        )
        self.tracker.set_probe(
            health.make_device_probe(executor=self.executor)
        )
        self.verifiers = []

    def verifier(self, mesh):
        """A verifier as the CLI builds it, joined to the stack. The
        cold-compile host fallback is on for one device and off for a
        mesh, as `TpuBlsVerifier.start_warmup` sets it on a node."""
        from lodestar_tpu.bls import TpuBlsVerifier

        v = TpuBlsVerifier(
            mesh=mesh, host_fallback_when_cold=mesh is None or mesh is False
        )
        v.attach_executor(self.executor)
        v.attach_health(self.tracker)  # None: the deadline-class default
        self.verifiers.append(v)
        return v

    def check(self, name: str, v, bulk_waves: int) -> None:
        buckets, paths = v.metrics.snapshot_dispatch()
        trips = dict(self.tracker.watchdog_trips)
        for cls, n in self.executor.watchdog_trips.items():
            trips[f"executor:{cls}"] = n
        say(
            f"# {name}: dispatch_by_path {paths}; dispatch_by_bucket"
            f" {dict(sorted(buckets.items()))}; watchdog trips {trips};"
            f" health {self.tracker.state.value}"
        )
        check_device_path(
            name, paths, buckets, bulk_waves, trips, self.tracker.state.value
        )

    async def close(self) -> None:
        from lodestar_tpu.bls import kernels

        for v in self.verifiers:
            await v.close()
        self.executor.close()
        kernels.set_maintenance_gate(None)
        kernels.set_health_gate(None)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def describe(devs) -> None:
    import jax

    from lodestar_tpu.bls import kernels
    from lodestar_tpu.crypto.bls import native
    from lodestar_tpu.ops import limbs
    from lodestar_tpu.utils import chip, jaxcache

    jaxcache.enable()
    versions = {
        p: importlib.metadata.version(p) for p in ("jax", "jaxlib", "libtpu")
    }
    say(f"# device: {devs[0].device_kind} x {len(devs)}")
    say(f"# versions: {versions}")
    say(f"# compile cache: {jax.config.jax_compilation_cache_dir}")
    on = {
        "limb_backend": limbs.get_backend(),
        "fused_stages": kernels.fused_stages_on(),
        "pallas_kernels": chip.on_tpu(),
        "native_c": native.available(),
    }
    say(f"# paths: {on}")
    if not (on["fused_stages"] and on["pallas_kernels"] and on["native_c"]):
        raise SmokeFailure(f"a chip path is off: {on}")


def _merge(intervals: list) -> list:
    """The union of (start, end) intervals, as disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def warm(tasks: dict) -> None:
    """Compile every program the waves will dispatch, in parallel
    threads (XLA compiles release the interpreter lock); any failure
    fails the run. Prints the seconds JAX reports tracing, lowering
    and compiling, summed over the threads."""
    import jax

    from lodestar_tpu.metrics import device as telemetry

    # (label, thread) -> [(start, end)]: a jit traced inside another
    # one reports its own duration, so each thread's spans are merged
    spans = collections.defaultdict(list)

    def on_duration(event: str, secs: float, **kw) -> None:
        if event in COMPILE_EVENTS:
            end = time.perf_counter()
            key = (COMPILE_EVENTS[event], threading.get_ident())
            spans[key].append((end - secs, end))

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    def timed(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    with ThreadPoolExecutor(len(tasks)) as pool:
        futs = {name: pool.submit(timed, fn) for name, fn in tasks.items()}
        walls = {name: f.result() for name, f in futs.items()}
    for name, s in walls.items():
        say(f"# warm {name}: {s:.1f} s wall")
    for label in COMPILE_EVENTS.values():
        merged = [
            _merge(v) for (lab, _), v in list(spans.items()) if lab == label
        ]
        say(
            f"# warm {label}: {sum(len(m) for m in merged)} spans,"
            f" {sum(e - s for m in merged for s, e in m):.1f} s"
            " summed over threads"
        )
    tel = telemetry.get_telemetry()
    compiles, seconds, _ = tel.snapshot_compiles()
    for stage in sorted(seconds):
        say(
            f"# compile {stage}: {compiles[stage]} programs,"
            f" {seconds[stage]:.1f} s"
        )
    say(
        f"# persistent cache: {tel.cache_hits} hits,"
        f" {tel.cache_misses} misses"
    )


def warm_pairing(b: int) -> None:
    """Compile the pairing and final programs of a b-set ingest bucket
    on inputs of their shapes, in parallel with its prepare program
    (which the bucket's own warm dispatch compiles first)."""
    import jax.numpy as jnp

    from lodestar_tpu.bls import kernels
    from lodestar_tpu.ops import curve as C

    p = kernels._pad_ints()
    g1 = C.g1_batch_from_ints([p["pk"]] * (b + 1))
    g2 = C.g2_batch_from_ints([p["h"]] * (b + 1))
    prod = kernels._fused_pairing(
        g1.x, g1.y, g2.x, g2.y, jnp.ones(b + 1, bool)
    )
    bool(kernels._stage_final_with_valid(prod, jnp.asarray(True)))


def warm_tasks(mesh=None) -> dict:
    """What the waves dispatch: the bulk bucket's ingest programs (its
    pairing and final compiled beside its prepare), and the
    host-decompressed programs of the retry rung; with `mesh`, the
    same buckets sharded over it too."""
    from lodestar_tpu.bls import kernels

    tasks = {
        f"ingest@{BULK_BUCKET}": lambda: kernels.warm_bucket(BULK_BUCKET),
        f"pairing+final@{BULK_BUCKET}": lambda: warm_pairing(BULK_BUCKET),
    }
    if mesh is not None:
        tasks[f"mesh ingest@{BULK_BUCKET}"] = lambda: kernels.warm_bucket(
            BULK_BUCKET, "batch", mesh
        )
    # per-job, then per-set retries (one rung on the chip)
    for r in {kernels.bucket_size(SETS_PER_JOB), kernels.bucket_size(1)}:
        tasks[f"host@{r}"] = lambda r=r: kernels.warm_bucket(r, "host")
        if mesh is not None:
            tasks[f"mesh host@{r}"] = lambda r=r: kernels.warm_bucket(
                r, "host", mesh
            )
    return tasks


async def run_wave(name: str, v, wave) -> list[bool]:
    t0 = time.perf_counter()
    got = await asyncio.gather(*(v.verify_signature_sets(j) for j in wave))
    say(
        f"# smoke timing, not a benchmark: {name}"
        f" {sum(map(len, wave))} sets in {len(wave)} jobs,"
        f" {time.perf_counter() - t0:.2f} s"
    )
    return [bool(g) for g in got]


def planned_waves(rng, pool, sets_per_job: int):
    """GOOD_WAVES valid waves, then one with three planted bad sets,
    and the native check's job verdicts for each."""
    waves = build_waves(
        rng, [(N_JOBS, sets_per_job)] * (GOOD_WAVES + 1), pool
    )
    for j, kind in zip(bad_jobs(N_JOBS), BAD_KINDS):
        plant(waves[-1][j], kind, rng)
    want = [expected_verdicts(w, pool) for w in waves]
    if want[-1].count(False) != len(BAD_KINDS) or not all(
        all(w) for w in want[:-1]
    ):
        raise SmokeFailure(f"the native check disagrees with the plan: {want}")
    return waves, want


async def one_chip(rng, pool) -> None:
    waves, want = planned_waves(rng, pool, SETS_PER_JOB)
    stack = Stack()
    v = stack.verifier(mesh=None)
    try:
        warm(warm_tasks())
        for i, wave in enumerate(waves):
            name = "bad wave" if i == len(waves) - 1 else f"wave {i}"
            check_verdicts(name, await run_wave(name, v, wave), want[i])
        say(f"# verdicts equal the native check: {want[-1]}")
        stack.check("verifier", v, len(waves))
    finally:
        await stack.close()


async def four_chips(rng, pool, devs) -> None:
    """The whole-bucket mesh path and its one-chip twin on the same
    sets; equal verdicts, and one all-reduce in the mesh program."""
    from lodestar_tpu import parallel
    from lodestar_tpu.bls import kernels

    waves, want = planned_waves(rng, pool, SETS_PER_JOB)
    stack = Stack()
    mesh = parallel.make_mesh(len(devs))
    mesh_v = stack.verifier(mesh=mesh)
    twin = stack.verifier(mesh=False)
    try:
        warm(warm_tasks(mesh))
        for i, wave in enumerate(waves):
            name = "bad wave" if i == len(waves) - 1 else f"wave {i}"
            got_mesh = await run_wave(f"mesh {name}", mesh_v, wave)
            got_twin = await run_wave(f"twin {name}", twin, wave)
            check_verdicts(f"mesh {name}", got_mesh, want[i])
            check_verdicts(f"twin {name}", got_twin, got_mesh)
        say(f"# mesh and twin verdicts equal the native check: {want[-1]}")
        stack.check("mesh verifier", mesh_v, len(waves))
        stack.check("twin verifier", twin, len(waves))
        args = [
            parallel.shard_batch(mesh, a)
            for a in kernels.ingest_batch_args(BULK_BUCKET)
        ]
        text = kernels._mesh_program("ingest_batch", mesh).lower(
            *args
        ).as_text()
        n = text.count("all_reduce")
        say(f"# all-reduces in the lowered mesh program: {n}")
        if n != 1:
            raise SmokeFailure(f"{n} all-reduces in the mesh program, want 1")
    finally:
        await stack.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: the whole-bucket mesh path against its one-chip twin",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    check_platform(devs[0].platform)
    if len(devs) != args.chips:
        raise SmokeFailure(f"--chips {args.chips}, and JAX sees {len(devs)}")

    from lodestar_tpu.metrics import device as telemetry

    telemetry.install()
    describe(devs)
    rng = random.Random(args.seed)
    t0 = time.perf_counter()
    with ThreadPoolExecutor(8) as pool:
        if args.chips == 4:
            asyncio.run(four_chips(rng, pool, devs))
        else:
            asyncio.run(one_chip(rng, pool))
    say(f"# smoke wall time: {time.perf_counter() - t0:.1f} s")
    say(result_line(devs[0].platform, devs[0].device_kind, len(devs)))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
