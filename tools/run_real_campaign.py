#!/usr/bin/env python
"""The first TPU-attached measurement round, in one command.

Every perf lever since round 6 — the MXU int8 limb backend, the mid
bucket-ladder rungs, continuous batching, mesh sharding, and now the
device auto-tuner — was built and CI-guarded on a CPU-only container;
COVERAGE.md states plainly which numbers measure the 1-core emulation
instead of the chip. This tool is the payoff script for the first
round that runs WITH hardware: it executes the whole campaign in
dependency order and leaves one artifact per step, so the post-MXU
stage budget and the chip-scaling curve land in a single run.

Steps (see REAL_CAMPAIGN.md for the runbook):

  1. preflight      — platform/device/persistent-cache check
  2. autotune       — DeviceAutotuner startup tune on the real chip
                      (full grid, generous budget) -> AUTOTUNE_real.json
  3. bench          — bench.py --autotune-from (headline sets/s under
                      the tuned config) -> BENCH_real.json
  4. pipeline       — bench.py --pipeline-depth 1,2,4 (overlapped
                      wave pipeline depth sweep: sync vs double/
                      quad buffering) -> BENCH_pipeline_real.json
  5. stage_budget   — tools/profile_prefix.py per backend: the
                      post-MXU per-stage budget that updates
                      COVERAGE.md's table -> STAGE_BUDGET_real.json
  6. trickle        — tools/bench_trickle.py --real --autotune-from
                      (gossip-shaped steady state) -> BENCH_trickle_real.json
  7. blobs          — tools/bench_blobs.py --real --autotune-from
                      (peak-DA KZG batch verify through the device
                      Pippenger MSM) -> BENCH_blobs_real.json
  8. mesh           — tools/bench_mesh_sweep.py --real --autotune-from
                      (the chip-scaling curve) -> MULTICHIP_real.json
  9. executor_contention
                    — gossip trickle (deadline class) through the
                      node DeviceExecutor while a KZG blob firehose
                      saturates the bulk lane: deadline p50/p99 with
                      vs without contention, bulk sheds, deferral
                      counts -> EXECUTOR_CONTENTION_real.json
  10. fault_drill   — the device fault domain end to end: injected
                      wave hang -> watchdog trip -> quarantine ->
                      bit-identical host failover -> probe
                      reinstatement (the device_loss_under_load
                      scenario, full profile, per-SLO verdicts)
                      -> FAULT_DRILL_real.json
  11. serving_flood — the serving fault domain under a light-client
                      read flood (tools/bench_flood.py): duty p99
                      vs quiet baseline, typed 429/503 sheds on the
                      cheap classes, Retry-After compliance, cache
                      hit ratio — machine-evaluated checks
                      -> BENCH_flood_real.json

`--dry-run` emits the full campaign plan (commands, artifacts,
prerequisites) as JSON without executing anything — reviewable on
this CPU container, runnable on the TPU host. `--steps` selects a
subset; a failed step aborts the remainder (later steps consume
earlier artifacts). The `_real` artifact suffix is reserved for
TPU-attached runs: off-TPU (`--allow-cpu` smoke) every step writes
`*_cpu.json` instead, so an emulation rehearsal can never be
mistaken for the hardware measurement.

One process per chip: this launcher never imports JAX. It asks a
child for the platform, and every step, the in-process ones included,
runs in a child of its own that holds the chip until it exits.

Usage:
  python tools/run_real_campaign.py --dry-run
  python tools/run_real_campaign.py                 # on the TPU host
  python tools/run_real_campaign.py --steps autotune,bench
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PY = sys.executable

_SUFFIX: str | None = None


def artifact_suffix() -> str:
    """`real` on a TPU-attached host, `cpu` anywhere else. The
    `_real` artifact suffix is a provenance claim — these numbers
    measured hardware — so an `--allow-cpu` smoke run must never be
    able to produce a `*_real.json` file: a CPU-emulation drill
    committed under the real-campaign name records a robustness
    guarantee as demonstrated when it was only rehearsed (the exact
    emulation-vs-chip confusion COVERAGE.md exists to prevent)."""
    global _SUFFIX
    if _SUFFIX is None:
        try:
            import jax

            _SUFFIX = "real" if jax.default_backend() == "tpu" else "cpu"
        except Exception:
            _SUFFIX = "cpu"
    return _SUFFIX


def probe_suffix() -> str:
    """artifact_suffix() of this host, asked of a child process (the
    launcher must not bind the chip its steps need)."""
    out = subprocess.run(
        [PY, "-c", "import jax; print(jax.devices()[0].platform)"],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    return "real" if out.stdout.strip() == "tpu" else "cpu"


def build_plan(args, sfx: str) -> list[dict]:
    """The campaign as data: each step is {name, why, cmd | fn,
    artifact, needs}. Commands are plain argv lists so the dry-run
    plan is copy-pasteable. Artifact names carry the host's suffix
    (probe_suffix): on the TPU host this plan writes the *_real.json
    files the runbook commits; off-TPU every name degrades to
    *_cpu.json."""
    at = args.autotune_artifact or f"AUTOTUNE_{sfx}.json"
    args.autotune_artifact = at
    return [
        {
            "name": "preflight",
            "why": "fail fast off-TPU; confirm the persistent "
            "compile cache is writable (a cold cache turns every "
            "later step into a multi-minute compile festival)",
            "fn": "preflight",
            "artifact": None,
            "needs": [],
        },
        {
            "name": "autotune",
            "why": "derive THIS host's config: limb backend x ingest "
            "gate x ladder top x latency budget, measured on the "
            "real chip at real ladder rungs (batch-flat cost makes "
            "the probes exact there)",
            "fn": "autotune",
            "artifact": at,
            "needs": ["preflight"],
        },
        {
            "name": "bench",
            "why": "the headline production-path sets/s under the "
            "tuned config (the number COVERAGE.md's 'Measured "
            "performance' table tracks; 10x north star ~22,200)",
            "cmd": [PY, "bench.py", "--autotune-from", at],
            "stdout": f"BENCH_{sfx}.json",
            "artifact": f"BENCH_{sfx}.json",
            "needs": ["autotune"],
        },
        {
            "name": "pipeline",
            "why": "overlapped-pipeline depth sweep on the chip: how "
            "much host prep the double-buffered dispatch (depth 2) "
            "actually hides behind device waves vs synchronous "
            "depth 1, and whether depth 4 buys anything beyond it — "
            "the seam BENCH_pipeline.json could only emulate on CPU",
            "cmd": [
                PY,
                "bench.py",
                "--autotune-from",
                at,
                "--pipeline-depth",
                "1,2,4",
            ],
            "stdout": f"BENCH_pipeline_{sfx}.json",
            "artifact": f"BENCH_pipeline_{sfx}.json",
            "needs": ["autotune"],
        },
        {
            "name": "stage_budget",
            "why": "the post-MXU per-stage device budget: the offline "
            "counterpart of the live lodestar_jax_stage_device_"
            "seconds histograms and the drift monitor's shares — "
            "updates COVERAGE.md's stage-budget table",
            "cmd": [
                PY,
                "tools/profile_prefix.py",
                "--limb-backend",
                "mxu",
                "--n",
                "2048",
            ],
            "stdout": f"STAGE_BUDGET_{sfx}.txt",
            "artifact": f"STAGE_BUDGET_{sfx}.txt",
            "needs": ["autotune"],
        },
        {
            "name": "trickle",
            "why": "gossip-shaped steady state on the chip: proves "
            "the 50ms-budget rolling bucket coalesces real arrival "
            "gaps onto the device-ingest path (BENCH_trickle's CPU "
            "caveat finally retired)",
            "cmd": [
                PY,
                "tools/bench_trickle.py",
                "--real",
                "--autotune-from",
                at,
                "--json-out",
                f"BENCH_trickle_{sfx}.json",
            ],
            "artifact": f"BENCH_trickle_{sfx}.json",
            "needs": ["autotune"],
        },
        {
            "name": "blobs",
            "why": "the DA chip curve next to the BLS one: peak "
            "max-blobs-per-block KZG batch verification through the "
            "device Pippenger MSM (ops/msm.py) under the tuned "
            "msm_window — the second workload sharing the chip, "
            "never yet measured on hardware",
            "cmd": [
                PY,
                "tools/bench_blobs.py",
                "--real",
                "--backend",
                "auto",
                "--autotune-from",
                at,
                "--json-out",
                f"BENCH_blobs_{sfx}.json",
            ],
            "artifact": f"BENCH_blobs_{sfx}.json",
            "needs": ["autotune"],
        },
        {
            "name": "mesh",
            "why": "the chip-scaling curve (strong scaling over the "
            "attached chips) — the multi-chip arm of the 10x path, "
            "never yet measured on hardware (MULTICHIP_SWEEP.json "
            "is virtual devices)",
            "cmd": [
                PY,
                "tools/bench_mesh_sweep.py",
                "--real",
                "--autotune-from",
                at,
                "--sets",
                "2048",
                "--reps",
                "3",
                "--json-out",
                f"MULTICHIP_{sfx}.json",
            ],
            "artifact": f"MULTICHIP_{sfx}.json",
            "needs": ["autotune"],
        },
        {
            "name": "executor_contention",
            "why": "the QoS guarantee under real contention: gossip "
            "verdict latency (deadline class) while a blob KZG "
            "firehose saturates the executor's bulk lane — deadline "
            "p99 should hold near its quiet baseline (~one wave), "
            "with the pressure showing up as bulk sheds and "
            "deferrals instead (device/executor.py)",
            "fn": "executor_contention",
            "artifact": f"EXECUTOR_CONTENTION_{sfx}.json",
            "needs": ["autotune"],
        },
        {
            "name": "fault_drill",
            "why": "the robustness guarantee next to the perf "
            "numbers: a hung device mid-wave must cost the node its "
            "speed-up, never its correctness — wave-watchdog trip -> "
            "quarantine -> bit-identical host failover -> autotuner "
            "frozen -> probe reinstatement, each an SLO row "
            "(device/health.py; scenario device_loss_under_load)",
            "fn": "fault_drill",
            "artifact": f"FAULT_DRILL_{sfx}.json",
            "needs": ["preflight"],
        },
        {
            "name": "serving_flood",
            "why": "the serving-tier robustness guarantee next to "
            "the device one: a light-client read flood against the "
            "REST tier must shed typed 429/503s on the cheap QoS "
            "classes (Retry-After on every refusal, zero 500s) while "
            "duty p99 holds within 2x quiet and the head-keyed "
            "cache absorbs the hot reads (api/overload.py; the "
            "bench's checks are machine-evaluated and a failed "
            "check fails the step)",
            "cmd": [
                PY,
                "tools/bench_flood.py",
                "--json-out",
                f"BENCH_flood_{sfx}.json",
            ],
            "artifact": f"BENCH_flood_{sfx}.json",
            "needs": ["preflight"],
        },
    ]


def step_preflight(args) -> dict:
    import jax

    from lodestar_tpu.utils import jaxcache
    from lodestar_tpu.utils.provenance import provenance

    jaxcache.enable()
    platform = jax.default_backend()
    devs = jax.devices()
    info = {
        "platform": platform,
        "devices": len(devs),
        "device_kind": str(getattr(devs[0], "device_kind", "")),
        "provenance": provenance(),
    }
    if platform != "tpu" and not args.allow_cpu:
        raise SystemExit(
            f"preflight: platform is {platform!r}, not 'tpu'. This "
            "campaign measures hardware; run it on the TPU host "
            "(--allow-cpu to force a smoke run whose numbers are "
            "emulation, not measurement)."
        )
    return info


def step_autotune(args) -> dict:
    from lodestar_tpu.device.autotune import DeviceAutotuner

    tuner = DeviceAutotuner(
        budget_ms=args.autotune_budget_ms,
        # anchored to the repo: the later subprocess steps resolve
        # the artifact against REPO (cwd=REPO), and so does the
        # resume check — a cwd-relative write from $HOME would strand
        # the expensive tune's output where nothing reads it
        artifact_path=os.path.join(REPO, args.autotune_artifact),
        mode="startup",
    )
    return tuner.tune(trigger="campaign")


def step_executor_contention(args) -> dict:
    """Deadline QoS under bulk pressure, measured in-process: the
    same gossip trickle runs twice through a node DeviceExecutor —
    once quiet, once with a KZG blob-batch firehose hammering the
    bulk lane from a second thread — and the artifact records the
    caller-observed verdict p50/p99 of both phases next to the
    executor's own accounting (bulk throughput, sheds, deadline
    deferrals). The acceptance shape: contended deadline p99 holds
    near the quiet baseline, and the pressure is visible as
    bulk-class sheds/deferrals instead of verdict latency."""
    import asyncio
    import threading

    from lodestar_tpu.bls import TpuBlsVerifier
    from lodestar_tpu.crypto import kzg
    from lodestar_tpu.device import autotune as at
    from lodestar_tpu.device.executor import DeviceExecutor
    from lodestar_tpu.utils import jaxcache
    from lodestar_tpu.utils.provenance import provenance

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_blobs as BB
    import bench_trickle as BT

    jaxcache.enable()
    dec_path = os.path.join(REPO, args.autotune_artifact)
    if os.path.exists(dec_path):
        with open(dec_path) as f:
            at.apply_decision(json.load(f))

    kzg.activate_trusted_setup(kzg.dev_trusted_setup())
    blobs, comms, proofs = BB.build_batch(args.contention_blobs)
    gap_s = args.contention_gap_ms / 1000.0

    async def phase(firehose: bool) -> dict:
        ex = DeviceExecutor()
        kzg.set_executor(ex)
        v = TpuBlsVerifier()
        v.attach_executor(ex)
        singles = BT._build_single_sets(args.contention_sets)
        stop = threading.Event()
        fired = {"batches": 0}

        def pump():
            # the bulk client: blob batches back to back; each MSM
            # rides the executor's bulk lane (or sheds to the host
            # tiers when admission control says no — also the point)
            while not stop.is_set():
                kzg.verify_blob_kzg_proof_batch(blobs, comms, proofs)
                fired["batches"] += 1

        th = None
        if firehose:
            th = threading.Thread(
                target=pump, name="blob-firehose", daemon=True
            )
            th.start()
        try:
            lat, wall = await BT._run_trickle(v, singles, [], gap_s)
        finally:
            stop.set()
            if th is not None:
                th.join(timeout=30.0)
            await v.close()
            kzg.set_executor(None)
            ex.close()
        xs = lat.get(1, [])
        return {
            "firehose": firehose,
            "gossip_jobs": len(xs),
            "deadline_p50_ms": BT._quantile(xs, 0.50) * 1000.0,
            "deadline_p99_ms": BT._quantile(xs, 0.99) * 1000.0,
            "wall_s": wall,
            "bulk_batches": fired["batches"],
            "bulk_blobs_per_batch": args.contention_blobs,
            "deadline_deferrals": ex.deadline_deferrals,
            "executor_sheds": {
                f"{cls}/{reason}": n
                for (cls, reason), n in sorted(
                    ex.shed_counts().items()
                )
            },
            "msm_paths": kzg.msm_path_counts(),
        }

    quiet = asyncio.run(phase(firehose=False))
    contended = asyncio.run(phase(firehose=True))
    out = {
        "workload": "gossip trickle (deadline) vs blob KZG firehose "
        "(bulk) through one DeviceExecutor",
        "quiet": quiet,
        "contended": contended,
        "provenance": provenance(),
    }
    with open(
        os.path.join(
            REPO, f"EXECUTOR_CONTENTION_{artifact_suffix()}.json"
        ),
        "w",
    ) as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    return out


def step_fault_drill(args) -> dict:
    """The device fault domain exercised end to end: the
    device_loss_under_load scenario at the full profile — an injected
    mid-wave hang trips the wave watchdog, quarantines the device,
    fails the remaining buckets over to the host path (verdicts
    bit-identical), freezes the autotuner, then reinstates via
    known-answer probes — with every guarantee an explicit SLO row in
    the artifact. Deterministic (injected faults + manual breaker
    clock), so the same drill gates tier-1 on CPU; on the TPU host it
    proves the failover seams against the real dispatch stack and
    writes FAULT_DRILL_real.json. Off-TPU (--allow-cpu) the identical
    drill is a rehearsal against the emulated dispatch stack and
    writes FAULT_DRILL_cpu.json — never the real-campaign name. A
    failed SLO row fails the step (and so the campaign)."""
    from lodestar_tpu.sim.scenarios import run_scenario
    from lodestar_tpu.utils.provenance import provenance

    res = run_scenario(
        "device_loss_under_load", profile="full", seed=args.drill_seed
    )
    out = dict(res.to_dict())
    out["provenance"] = provenance()
    artifact = f"FAULT_DRILL_{artifact_suffix()}.json"
    with open(os.path.join(REPO, artifact), "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    if res.error:
        raise RuntimeError(
            f"fault drill crashed:\n{res.error}"
        )
    failed = [s.name for s in res.slos if not s.passed]
    if failed:
        raise RuntimeError(
            f"fault drill SLO rows failed: {failed} "
            f"(see {artifact})"
        )
    return out


STEP_FNS = {
    "preflight": step_preflight,
    "autotune": step_autotune,
    "executor_contention": step_executor_contention,
    "fault_drill": step_fault_drill,
}


def run(args) -> int:
    sfx = probe_suffix()
    plan = build_plan(args, sfx)
    want = (
        [s.strip() for s in args.steps.split(",") if s.strip()]
        if args.steps
        else [st["name"] for st in plan]
    )
    unknown = set(want) - {st["name"] for st in plan}
    if unknown:
        print(f"unknown steps: {sorted(unknown)}", file=sys.stderr)
        return 2
    if args.dry_run:
        out = {
            "campaign": "first TPU-attached measurement round",
            "runbook": "REAL_CAMPAIGN.md",
            "cwd": REPO,
            "platform": "tpu" if sfx == "real" else "cpu",
            "artifact_suffix": sfx,
            "note": "artifact names reflect THIS host: on the TPU "
            "host they are *_real.json; off-TPU every step writes "
            "*_cpu.json so an emulation run can never masquerade as "
            "a hardware measurement",
            "steps": [
                {
                    "name": st["name"],
                    "selected": st["name"] in want,
                    "why": st["why"],
                    "command": (
                        " ".join(st["cmd"])
                        + (
                            f" > {st['stdout']}"
                            if st.get("stdout")
                            else ""
                        )
                        if "cmd" in st
                        else f"<in-process: {st['fn']}>"
                    ),
                    "artifact": st["artifact"],
                    "needs": st["needs"],
                }
                for st in plan
            ],
        }
        print(json.dumps(out, indent=2))
        return 0
    done: set[str] = set()
    for st in plan:
        if st["name"] not in want:
            continue
        missing = [n for n in st["needs"] if n not in done]
        if missing:
            # a skipped prerequisite is fine when its artifact
            # already exists on disk (resuming a campaign). A
            # prerequisite WITHOUT an artifact (preflight) can only
            # be satisfied by running it: letting `--steps
            # fault_drill` skip preflight is how a CPU run once
            # produced an artifact under the real-campaign name.
            for n in missing:
                art = next(
                    p["artifact"] for p in plan if p["name"] == n
                )
                if art is None:
                    print(
                        f"step {st['name']}: prerequisite {n} "
                        "leaves no artifact and must run in this "
                        f"invocation — use --steps {n},{st['name']}",
                        file=sys.stderr,
                    )
                    return 1
                if not os.path.exists(os.path.join(REPO, art)):
                    print(
                        f"step {st['name']}: prerequisite {n} not "
                        f"run and artifact {art} absent",
                        file=sys.stderr,
                    )
                    return 1
        print(f"==> {st['name']}", file=sys.stderr)
        try:
            if "fn" in st:
                subprocess.run(
                    [
                        PY,
                        os.path.abspath(__file__),
                        *sys.argv[1:],
                        "--autotune-artifact",
                        args.autotune_artifact,
                        "--child",
                        st["fn"],
                    ],
                    cwd=REPO,
                    check=True,
                )
            elif st.get("stdout"):
                with open(os.path.join(REPO, st["stdout"]), "w") as f:
                    subprocess.run(
                        st["cmd"], cwd=REPO, check=True, stdout=f
                    )
            else:
                subprocess.run(st["cmd"], cwd=REPO, check=True)
        except Exception as e:
            print(
                f"step {st['name']} FAILED: {e!r} — aborting the "
                "remainder (later steps consume earlier artifacts)",
                file=sys.stderr,
            )
            return 1
        done.add(st["name"])
    print(
        json.dumps(
            {
                "completed": sorted(done),
                "artifacts": [
                    st["artifact"]
                    for st in plan
                    if st["name"] in done and st["artifact"]
                ],
            },
            indent=2,
        )
    )
    return 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--dry-run",
        action="store_true",
        help="emit the campaign plan as JSON without executing",
    )
    p.add_argument(
        "--steps",
        default=None,
        help="comma-separated subset of steps to run",
    )
    p.add_argument(
        "--autotune-budget-ms",
        type=float,
        default=1_200_000.0,
        help="tune budget on the real chip (default 20 min: first "
        "run pays real compiles; repeats ride the persistent cache)",
    )
    p.add_argument(
        "--autotune-artifact",
        default=None,
        help="tune-decision artifact name (default AUTOTUNE_real"
        ".json on the TPU host, AUTOTUNE_cpu.json elsewhere)",
    )
    p.add_argument(
        "--contention-sets",
        type=int,
        default=128,
        help="gossip jobs per executor-contention phase",
    )
    p.add_argument(
        "--contention-blobs",
        type=int,
        default=6,
        help="blobs per firehose batch in the executor-contention "
        "step (6 = max blobs per block)",
    )
    p.add_argument(
        "--contention-gap-ms",
        type=float,
        default=20.0,
        help="gossip arrival gap in the executor-contention step",
    )
    p.add_argument(
        "--drill-seed",
        type=int,
        default=20260807,
        help="scenario seed for the fault_drill step (matches the "
        "scenario fleet's default; the drill is deterministic, so "
        "one seed reproduces one transcript)",
    )
    p.add_argument(
        "--allow-cpu",
        action="store_true",
        help="let preflight pass off-TPU (smoke only; numbers "
        "measure the CPU emulation)",
    )
    p.add_argument(
        "--child",
        choices=sorted(STEP_FNS),
        default=None,
        help=argparse.SUPPRESS,  # one in-process step, run by run()
    )
    args = p.parse_args()
    if args.child:
        print(json.dumps(STEP_FNS[args.child](args), default=str))
        return 0
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
