"""Scenario fleet: mainnet-shaped adversity with pass/fail SLO contracts.

Reference analog: crucible's multi-client sim matrix plus the ops
runbook regimes every mainnet node eventually meets. Each scenario is
a NAMED, DETERMINISTIC (seeded rng, bounded slot counts) adversity
run with an explicit SLO contract evaluated from real telemetry
surfaces — sim/assertions.py evaluators over chain state, the
block-import trace ring (metrics/tracing.py), the device executor's
shed ledger (lodestar_device_sheds_total operands), gossip
seen-cache counters, and the drift monitor's re-tune ledger — never
ad-hoc asserts sprinkled through the run.

Contract shape: a scenario records `SloResult` rows through its
`ScenarioContext`; `run_scenario` wraps the run into a
`ScenarioResult` whose `passed` is the conjunction. Every scenario
also asserts its faults actually FIRED (sim/faults.FaultRegistry) —
a fault window that never delivered makes every downstream SLO
vacuous, so delivery itself is an SLO row.

Regimes (SCENARIOS registry, also tabulated in SCENARIOS.md):

* sustained_nonfinality — attestation-gossip blackout stalls
  justification for whole epochs while blocks keep flowing; memory
  surfaces (op pools, state caches) must stay bounded and finality
  must resume promptly once attestations return.
* reorg_storm — a node's block publications arrive late so peers
  attest to the stale head; the network must re-converge within a
  bounded number of slots and propose cleanly afterwards.
* equivocation_flood — a faulty proposer emits a conflicting sibling
  of its own head plus a duplicate-block flood; peers' seen-caches
  absorb the copies, imports stay under the stage budget, and the
  honest chain keeps finalizing.
* mainnet_gossip_burst — an attestation firehose through the
  NetworkProcessor while the verifier briefly refuses work; every
  verdict future resolves, the deadline-class p99 stays bounded, and
  sheds land only on the bounded backpressure classes.
* blob_firehose_under_load — the PR-17 contention contract at the
  device executor: bulk blob work overflows its queue bound while
  deadline verdicts keep flowing; every shed is counted and fed a
  host fallback (never silent), deadline work preempts bulk, AND the
  cross-regime invariant: the drift monitor trips mid-incident but
  the autotuner HOLDS STILL (retunes_blocked grows, applied config
  unchanged) until the device quiesces.
* checkpoint_thundering_herd — most of the network restarts and
  catches up at once; catch-up completes (caught_up_blocks matches
  what was missed), the surviving node's duties never stop, and
  finality resumes.
* device_loss_under_load — the ISSUE-19 fault drill: a mid-wave
  device hang trips the wave watchdog, quarantines the device, and
  the remaining gossip fails over to the host path bit-identically;
  the autotuner freezes while quarantined, and known-answer probes
  reinstate the device live (warmup re-kicked).
* lightclient_flood — the ISSUE-20 serving drill: a light-client
  read flood + SSE subscriber swarm hits the REST tier while the
  chain keeps importing; duty-class p99 holds near its quiet
  baseline, every shed is a typed 429/503 + Retry-After on the
  cheap classes, the head-keyed cache absorbs the hot reads, and
  slow SSE consumers are evicted with their drops counted.

`tools/run_scenarios.py` is the operator CLI (runs the registry,
emits a provenance-stamped SCENARIOS.json); tests/test_scenarios.py
pins every smoke profile green and slow-marks the full profiles for
tier 2.
"""

from __future__ import annotations

import asyncio
import random
import time
import traceback
from dataclasses import dataclass, field

from ..params import preset

FAR = 2**64 - 1

_TYPES = None


def _types():
    """Process-cached ssz types: scenarios in one run share the
    (expensive) type build just like the test suite's module fixture."""
    global _TYPES
    if _TYPES is None:
        from ..types import ssz_types

        _TYPES = ssz_types()
    return _TYPES


def _cfg(**forks):
    from ..config.chain_config import ChainConfig

    base = dict(
        ALTAIR_FORK_EPOCH=FAR,
        BELLATRIX_FORK_EPOCH=FAR,
        CAPELLA_FORK_EPOCH=FAR,
        DENEB_FORK_EPOCH=FAR,
        ELECTRA_FORK_EPOCH=FAR,
        SHARD_COMMITTEE_PERIOD=0,
    )
    base.update(forks)
    return ChainConfig(**base)


# ---------------------------------------------------------------------------
# SLO records + scenario engine
# ---------------------------------------------------------------------------


@dataclass
class SloResult:
    """One machine-evaluated pass/fail row of a scenario's contract."""

    name: str
    passed: bool
    observed: object
    threshold: object
    detail: str = ""

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "passed": bool(self.passed),
            "observed": repr(self.observed),
            "threshold": repr(self.threshold),
            "detail": self.detail,
        }


@dataclass
class ScenarioResult:
    name: str
    profile: str
    seed: int
    slos: list = field(default_factory=list)
    faults_injected: dict = field(default_factory=dict)
    duration_s: float = 0.0
    error: str | None = None

    @property
    def passed(self) -> bool:
        return self.error is None and all(s.passed for s in self.slos)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "profile": self.profile,
            "seed": self.seed,
            "passed": self.passed,
            "slos": [s.to_dict() for s in self.slos],
            "faults_injected": dict(self.faults_injected),
            "duration_s": round(self.duration_s, 3),
            "error": self.error,
        }

    def summary(self) -> str:
        mark = "PASS" if self.passed else "FAIL"
        lines = [
            f"{mark} {self.name} [{self.profile}] "
            f"({self.duration_s:.1f}s, "
            f"faults={self.faults_injected})"
        ]
        for s in self.slos:
            lines.append(
                f"  {'ok  ' if s.passed else 'FAIL'} {s.name}: "
                f"observed={s.observed!r} want={s.threshold!r}"
            )
        if self.error:
            lines.append(f"  ERROR {self.error.splitlines()[-1]}")
        return "\n".join(lines)


class ScenarioContext:
    """Per-run state a scenario body writes its contract through."""

    def __init__(self, profile: str, seed: int):
        from .faults import FaultRegistry

        self.profile = profile
        self.seed = seed
        self.rng = random.Random(seed)
        self.registry = FaultRegistry()
        self.slos: list[SloResult] = []

    @property
    def smoke(self) -> bool:
        return self.profile == "smoke"

    def slo(self, name, passed, observed, threshold, detail="") -> bool:
        self.slos.append(
            SloResult(name, bool(passed), observed, threshold, detail)
        )
        return bool(passed)

    def slo_le(self, name, observed, bound, detail="") -> bool:
        return self.slo(name, observed <= bound, observed,
                        f"<= {bound}", detail)

    def slo_ge(self, name, observed, bound, detail="") -> bool:
        return self.slo(name, observed >= bound, observed,
                        f">= {bound}", detail)

    def slo_true(self, name, observed, detail="") -> bool:
        return self.slo(name, bool(observed), observed, True, detail)

    def slo_faults_fired(self, *kinds: str) -> None:
        """Delivery-is-an-SLO: each scheduled fault kind must have a
        positive delivered count in the registry."""
        counts = self.registry.counts()
        for kind in kinds:
            self.slo_ge(f"fault_fired:{kind}", counts.get(kind, 0), 1,
                        "a fault that never fired makes the run vacuous")


@dataclass
class ScenarioSpec:
    name: str
    fn: object
    summary: str
    faults: tuple
    slo_names: tuple


SCENARIOS: dict[str, ScenarioSpec] = {}


def scenario(name: str, summary: str, faults=(), slos=()):
    def deco(fn):
        SCENARIOS[name] = ScenarioSpec(
            name=name, fn=fn, summary=summary, faults=tuple(faults),
            slo_names=tuple(slos),
        )
        return fn

    return deco


def run_scenario(name: str, profile: str = "smoke",
                 seed: int = 20260807) -> ScenarioResult:
    """Run one registered scenario to a ScenarioResult. Never raises
    for an SLO miss (that's a failed row); scenario-body crashes land
    in `error` with the traceback."""
    if name not in SCENARIOS:
        raise KeyError(
            f"unknown scenario {name!r}; registered: "
            f"{sorted(SCENARIOS)}"
        )
    if profile not in ("smoke", "full"):
        raise ValueError(f"profile must be smoke|full, got {profile!r}")
    spec = SCENARIOS[name]
    ctx = ScenarioContext(profile, seed)
    t0 = time.monotonic()
    error = None
    try:
        asyncio.run(spec.fn(ctx))
    except Exception:
        error = traceback.format_exc()
    return ScenarioResult(
        name=name,
        profile=profile,
        seed=seed,
        slos=ctx.slos,
        faults_injected=ctx.registry.counts(),
        duration_s=time.monotonic() - t0,
        error=error,
    )


def run_all(profile: str = "smoke", seed: int = 20260807,
            only=None) -> list[ScenarioResult]:
    names = list(SCENARIOS)
    if only:
        unknown = [n for n in only if n not in SCENARIOS]
        if unknown:
            raise KeyError(
                f"unknown scenario(s) {unknown}; registered: "
                f"{sorted(SCENARIOS)}"
            )
        names = [n for n in names if n in set(only)]
    return [run_scenario(n, profile=profile, seed=seed) for n in names]


# ---------------------------------------------------------------------------
# regime 1: sustained non-finality
# ---------------------------------------------------------------------------


@scenario(
    "sustained_nonfinality",
    "attestation-gossip blackout stalls finality for whole epochs; "
    "memory stays bounded and finality resumes on recovery",
    faults=("gossip_drop",),
    slos=("finality_frozen_during_outage", "op_pool_bounded",
          "state_caches_bounded", "blocks_flow_during_outage",
          "finality_resumes", "heads_consistent"),
)
async def sustained_nonfinality(ctx: ScenarioContext) -> None:
    from . import assertions as A
    from .faults import FaultSchedule, GossipFaultInjector
    from .simulation import Simulation
    from ..chain.chain import MAX_CACHED_BLOCKS, MAX_CACHED_STATES

    spe = preset().SLOTS_PER_EPOCH
    outage_epochs = 1 if ctx.smoke else 3
    sim = Simulation(_cfg(), _types(), n_nodes=2, n_validators=16)
    await sim.start()
    try:
        sched = FaultSchedule(sim)
        injectors: list = []
        start = spe + 1  # one healthy epoch first
        end = start + outage_epochs * spe - 1

        def enter():
            # both nodes lose attestation gossip only: each proposer
            # pools just its own partial attestations (~50% of stake)
            # so justification stalls while blocks still flow
            for node in sim.nodes:
                injectors.append(ctx.registry.track(GossipFaultInjector(
                    node.network.gossip, rng=ctx.rng, drop=1.0,
                    topics=("beacon_attestation",),
                )))

        def exit_():
            for inj in injectors:
                inj.detach()

        sched.window(start, end, enter, exit_)
        await sim.run_until_slot(start - 1)
        fin_before = max(A.finalized_epochs(sim).values())
        await sim.run_until_slot(end)

        fin_during = A.finalized_epochs(sim)
        ctx.slo(
            "finality_frozen_during_outage",
            max(fin_during.values()) <= fin_before + 1,
            fin_during,
            f"<= {fin_before + 1}",
            "at most one in-flight justification may land after the "
            "blackout starts; more means the regime never took hold",
        )
        # the memory contract: pools prune on the SLOT clock and the
        # state/block caches are hard-capped, so a finality stall
        # cannot grow either without bound
        ctx.slo_le("op_pool_bounded",
                   max(A.op_pool_sizes(sim).values()), 8 * spe,
                   "aggregated attestation pool prunes by slot, "
                   "not by finality")
        caches = A.state_cache_sizes(sim)
        ctx.slo(
            "state_caches_bounded",
            all(s <= MAX_CACHED_STATES and b <= MAX_CACHED_BLOCKS
                for s, b in caches.values()),
            caches,
            f"<= ({MAX_CACHED_STATES}, {MAX_CACHED_BLOCKS})",
        )
        missed = A.missed_slots(sim, start, end)
        ctx.slo(
            "blocks_flow_during_outage",
            all(len(m) <= outage_epochs for m in missed.values()),
            missed,
            f"<= {outage_epochs} missed per node",
            "non-finality must not stop block production",
        )

        recover_epochs = 2 if ctx.smoke else 3
        await sim.run_until_slot(end + recover_epochs * spe)
        fin_after = A.finalized_epochs(sim)
        ctx.slo(
            "finality_resumes",
            min(fin_after.values()) >= max(fin_during.values()) + 1,
            fin_after,
            f">= {max(fin_during.values()) + 1}",
            "two healthy epochs after the blackout must finalize",
        )
        ctx.slo_true("heads_consistent", A.heads_consistent(sim))
        ctx.slo_faults_fired("gossip_drop")
    finally:
        await sim.stop()


def _head_slot(node) -> int:
    """Slot of the node's head block; 0 while the head is still the
    (blockless) genesis anchor."""
    blk = node.chain.get_block(node.chain.head_root)
    if blk is None:
        return 0
    return int(getattr(blk, "message", blk).slot)


# ---------------------------------------------------------------------------
# regime 2: reorg storm
# ---------------------------------------------------------------------------


@scenario(
    "reorg_storm",
    "late-delivered blocks make peers attest to stale heads; the "
    "network re-converges within bounded slots and proposes cleanly",
    faults=("late_block",),
    slos=("head_reconvergence_slots", "no_missed_blocks_after_storm",
          "chain_advanced_through_storm"),
)
async def reorg_storm(ctx: ScenarioContext) -> None:
    from . import assertions as A
    from .faults import FaultSchedule, LateBlockReplayer
    from .simulation import Simulation

    spe = preset().SLOTS_PER_EPOCH
    storm_slots = 4 if ctx.smoke else 2 * spe
    sim = Simulation(_cfg(), _types(), n_nodes=2, n_validators=16)
    await sim.start()
    try:
        sched = FaultSchedule(sim)
        replayers: list = []
        start = spe + 1
        end = start + storm_slots - 1

        def enter():
            # every proposal arrives ~2 slots late at the peer: it has
            # already attested to the stale head, so competing forks
            # build up for the whole window
            for node in sim.nodes:
                replayers.append(ctx.registry.track(
                    LateBlockReplayer(node, delay_s=0.5)
                ))

        def exit_():
            for r in replayers:
                r.detach()

        sched.window(start, end, enter, exit_)
        await sim.run_until_slot(start - 1)
        head_before = max(_head_slot(n) for n in sim.nodes)
        await sim.run_until_slot(end)

        # convergence latency: run slot by slot until every alive
        # node reports one head (late blocks still in flight land
        # during the first extra slot)
        max_wait = 8 if ctx.smoke else 12
        converged_at = None
        for extra in range(1, max_wait + 1):
            await sim.run_slot()
            if A.heads_consistent(sim):
                converged_at = extra
                break
        ctx.slo(
            "head_reconvergence_slots",
            converged_at is not None and converged_at <= max_wait,
            converged_at,
            f"<= {max_wait} slots",
            "slots from storm end until every node reports one head",
        )

        # zero wrong-head proposals once converged: a proposer still
        # on a minority fork would orphan its own block and leave a
        # canonical gap
        mark = sim.slot
        await sim.run_until_slot(mark + spe)
        missing = A.missed_slots(sim, mark + 1)
        ctx.slo(
            "no_missed_blocks_after_storm",
            all(not m for m in missing.values()),
            missing,
            "no canonical gaps",
        )
        head_after = max(_head_slot(n) for n in sim.nodes)
        ctx.slo_ge("chain_advanced_through_storm",
                   head_after - head_before, storm_slots // 2,
                   "the storm may orphan blocks but must not halt "
                   "the chain")
        ctx.slo_faults_fired("late_block")
    finally:
        await sim.stop()


# ---------------------------------------------------------------------------
# regime 3: equivocation flood
# ---------------------------------------------------------------------------


@scenario(
    "equivocation_flood",
    "a faulty proposer emits conflicting siblings of its own head "
    "plus a duplicate-block flood; gossip absorbs it, imports stay "
    "under budget, the honest chain keeps finalizing",
    faults=("equivocating_block", "duplicate_block"),
    slos=("duplicates_absorbed_by_seen_cache", "import_under_budget",
          "heads_consistent", "finality_advances"),
)
async def equivocation_flood(ctx: ScenarioContext) -> None:
    from . import assertions as A
    from .faults import propose_equivocation, republish_head_block
    from .simulation import Simulation
    from ..metrics.tracing import Tracer

    spe = preset().SLOTS_PER_EPOCH
    flood_slots = 4 if ctx.smoke else 2 * spe
    sim = Simulation(_cfg(), _types(), n_nodes=2, n_validators=16)
    await sim.start()
    try:
        # slow_ms=0: EVERY import trace lands in the ring buffer, so
        # the budget SLO reads real per-import telemetry
        for node in sim.nodes:
            node.chain.tracer = Tracer(slow_ms=0.0, buffer_size=512)
        start = spe + 1
        end = start + flood_slots - 1

        async def flood(slot: int):
            # sibling of the previous slot's block: whichever node
            # holds that proposer's key equivocates against itself
            for node in sim.nodes:
                root = await propose_equivocation(node)
                if root is not None:
                    ctx.registry.record("equivocating_block")
                    break
            n = await republish_head_block(
                sim.nodes[slot % len(sim.nodes)], times=3
            )
            ctx.registry.record("duplicate_block", n)

        def hook(slot: int):
            if start <= slot <= end:
                return flood(slot)
            return None

        sim.on_slot_hooks.append(hook)
        # flood, then calm slots; end past the FOURTH epoch boundary.
        # phase0 finality needs two consecutive justified epochs, and
        # the flood forks split attestations across siblings for the
        # whole flood epoch — that epoch routinely misses
        # justification, so the first finalizable pair is the two
        # clean epochs after it (finalized lands at the next boundary)
        await sim.run_until_slot(max(end + 2 * spe, 4 * spe + 1))

        dups = sum(
            n.network.gossip.duplicates_received for n in sim.nodes
        )
        ctx.slo_ge(
            "duplicates_absorbed_by_seen_cache", dups, 1,
            "republished blocks must be counted (and dropped) by the "
            "peers' gossip seen-cache, not re-imported",
        )
        worst_ms = max(A.max_import_ms(n) for n in sim.nodes)
        ctx.slo_le(
            "import_under_budget", round(worst_ms, 1), 8000.0,
            "equivocating siblings are full imports and must not "
            "stall the import path (bound sized for the pure-python "
            "CPU sim: epoch-boundary imports run whole-state "
            "transitions; a flood-induced stall would blow far past "
            "it)",
        )
        ctx.slo_true("heads_consistent", A.heads_consistent(sim))
        fin = A.finalized_epochs(sim)
        ctx.slo_ge("finality_advances", min(fin.values()), 1,
                   "the honest chain outweighs the equivocator")
        ctx.slo_faults_fired("equivocating_block", "duplicate_block")
    finally:
        await sim.stop()


# ---------------------------------------------------------------------------
# regime 4: mainnet-rate gossip burst
# ---------------------------------------------------------------------------


class _GatedVerifier:
    """Backpressure shim for the burst scenario: the processor's
    can_accept_work gate flips to False for the incident phase, then
    reopens. Everything else proxies to the real verifier."""

    def __init__(self, inner):
        self._inner = inner
        self.accepting = True

    def can_accept_work(self) -> bool:
        if not self.accepting:
            return False
        probe = getattr(self._inner, "can_accept_work", None)
        return probe is None or bool(probe())

    def __getattr__(self, name):
        return getattr(self._inner, name)


@scenario(
    "mainnet_gossip_burst",
    "an attestation firehose through the NetworkProcessor while the "
    "verifier refuses work mid-burst; every verdict resolves, p99 "
    "stays bounded, sheds land only on bounded classes",
    faults=("gossip_burst", "verifier_stall"),
    slos=("all_verdicts_resolved", "verdict_p99_bounded",
          "no_rejects", "sheds_only_bounded_classes"),
)
async def mainnet_gossip_burst(ctx: ScenarioContext) -> None:
    from ..chain import DevNode
    from ..chain.validation import AttestationValidator, GossipAction
    from ..device.executor import DeviceExecutor
    from ..network import NetworkProcessor

    cfg = _cfg()
    types = _types()
    node = DevNode(cfg, types, 32, verify_attestations=False)
    executor = DeviceExecutor()
    loop = asyncio.get_running_loop()
    try:
        await node.run_until(2)
        validator = AttestationValidator(
            cfg, types, node.chain, node.chain.verifier
        )
        validator.on_slot(node.slot)
        gate = _GatedVerifier(node.chain.verifier)
        proc = NetworkProcessor(
            node.chain, validator, gate, executor=executor
        )
        proc.start()

        atts = _burst_attestations(node, types, node.slot)
        n_unique = len(atts)
        copies = 40 if ctx.smoke else 150
        stall_s = 0.25 if ctx.smoke else 0.6

        # incident: the verifier refuses work while the firehose
        # lands — the pump must defer (bounded shed classes), never
        # drop a verdict on the floor
        gate.accepting = False
        ctx.registry.record("verifier_stall")
        latencies: list[float] = []
        futs = []
        n_sent = 0
        for i in range(copies):
            for att in atts:
                fut = proc.on_gossip_attestation(att)
                t0 = loop.time()
                fut.add_done_callback(
                    lambda f, t0=t0: latencies.append(loop.time() - t0)
                )
                futs.append(fut)
                n_sent += 1
        ctx.registry.record("gossip_burst", n_sent)
        await asyncio.sleep(stall_s)
        gate.accepting = True
        results = await asyncio.gather(*futs)
        await proc.drain()
        await proc.stop()

        resolved = sum(1 for r in results if r is not None)
        ctx.slo(
            "all_verdicts_resolved",
            resolved == n_sent and len(latencies) == n_sent,
            resolved, n_sent,
            "every gossip verdict future must resolve, burst or not",
        )
        p99 = _quantile(latencies, 0.99)
        ctx.slo_le(
            "verdict_p99_bounded", round(p99, 3), stall_s + 2.0,
            "p99 verdict latency across the burst, including the "
            "stall the backpressure gate imposed",
        )
        rejects = sum(1 for r in results if r == GossipAction.REJECT)
        ctx.slo(
            "no_rejects",
            rejects == 0 and proc.accepted >= n_unique,
            {"rejected": rejects, "accepted": proc.accepted,
             "ignored": proc.ignored, "dropped": proc.dropped},
            f"0 rejects, >= {n_unique} accepted",
            "duplicates dedupe to IGNORE; nothing mis-classifies",
        )
        allowed = {("deadline", "work_queue_backpressure"),
                   ("deadline", "att_queue_overflow")}
        sheds = executor.shed_counts()
        ctx.slo(
            "sheds_only_bounded_classes",
            sum(sheds.values()) > 0 and set(sheds) <= allowed,
            dict(sheds),
            f"non-empty subset of {sorted(allowed)}",
            "the stall must surface as accounted deadline-class "
            "deferrals, nowhere else",
        )
        ctx.slo_faults_fired("gossip_burst", "verifier_stall")
    finally:
        executor.close()
        await node.close()


def _burst_attestations(node, types, slot):
    """All committee validators of `slot` as single-bit signed gossip
    attestations on the current head (the mainnet firehose shape)."""
    from ..chain.devnode import DOMAIN_BEACON_ATTESTER
    from ..crypto.bls.signature import sign
    from ..statetransition import util
    from ..statetransition.block import compute_signing_root, get_domain

    head_root = node.chain.head_root
    st = node.chain.get_state(head_root).state
    epoch = util.compute_epoch_at_slot(slot)
    sh = util.EpochShuffling(st, epoch)
    try:
        target_root = util.get_block_root(st, epoch)
    except ValueError:
        target_root = head_root
    out = []
    for ci, committee in enumerate(sh.committees_at_slot(slot)):
        if not len(committee):
            continue
        data = types.AttestationData.default()
        data.slot = slot
        data.index = ci
        data.beacon_block_root = head_root
        data.source = st.current_justified_checkpoint
        tgt = types.Checkpoint.default()
        tgt.epoch = epoch
        tgt.root = target_root
        data.target = tgt
        domain = get_domain(node.cfg, st, DOMAIN_BEACON_ATTESTER, epoch)
        root = compute_signing_root(types.AttestationData, data, domain)
        for pos, v in enumerate(committee):
            att = types.Attestation.default()
            att.data = data
            bits = [False] * len(committee)
            bits[pos] = True
            att.aggregation_bits = bits
            att.signature = sign(node.sks[int(v)], root)
            out.append(att)
    return out


def _quantile(values, q: float) -> float:
    if not values:
        return 0.0
    ordered = sorted(values)
    idx = min(len(ordered) - 1, int(q * len(ordered)))
    return ordered[idx]


# ---------------------------------------------------------------------------
# regime 5: blob firehose under gossip load (+ the autotuner-holds-
# still cross-regime invariant)
# ---------------------------------------------------------------------------


@scenario(
    "blob_firehose_under_load",
    "bulk blob work overflows its executor bound while deadline "
    "verdicts flow: sheds counted + host fallbacks (never silent), "
    "deadline preempts bulk, and the drift monitor defers re-tunes "
    "until the device quiesces",
    faults=("bulk_overload", "drift_signal"),
    slos=("sheds_counted_never_silent", "deadline_preempts_bulk",
          "deadline_never_shed", "deadline_p99_bounded",
          "autotuner_holds_still", "config_unchanged_mid_incident",
          "retune_lands_after_quiesce"),
)
async def blob_firehose_under_load(ctx: ScenarioContext) -> None:
    from types import SimpleNamespace

    from ..bls import kernels as K
    from ..device import autotune as AT
    from ..device.executor import DeviceExecutor
    from ..ops import limbs as L
    from ..ops import msm as M

    # the re-tune at the end drives the REAL knob setters — snapshot
    # and restore so a scenario run leaves the process untouched
    # (the same discipline as test_autotune's _restore_knobs)
    gate = K.INGEST_MIN_BUCKET
    ladder = K.BUCKET_LADDER
    warm = set(K._INGEST_WARM)
    started = K._WARMUP_STARTED
    backend = L.get_backend()
    applied = AT._APPLIED
    window = M.msm_window()

    executor = DeviceExecutor(
        queue_bounds={"bulk": 8}, drain_timeout_s=0.15
    )
    try:
        deadline_busy = {"flag": True}
        executor.register_deadline_probe(lambda: deadline_busy["flag"])

        quiet_log = SimpleNamespace(
            info=lambda *a, **k: None, warn=lambda *a, **k: None
        )
        bench = lambda backend, bucket: AT.Measurement(
            backend=backend, bucket=bucket, pipeline="batch",
            seconds_per_dispatch=bucket / 400.0, sets_per_sec=400.0,
            runs=3, warm_seconds=0.0,
        )
        verifier = _KnobVerifier()
        tuner = AT.DeviceAutotuner(
            verifier=verifier, grid=AT.parse_grid("backend=vpu"),
            bench=bench, artifact_path=None, logger=quiet_log,
        )
        tel = _StageTelemetry()
        mon = AT.DriftMonitor(
            tuner, tel, verifier=verifier, windows=2, cooldown_s=0.0,
            executor=executor,
        )
        tel.add_window(dict(AT.budget_shares()))
        mon.sample()  # baseline window

        n_bulk = 60 if ctx.smoke else 300
        submitted = 0
        fallbacks = 0
        deadline_done: list[float] = []
        deadline_futs = []

        def bulk_blob_job():
            time.sleep(0.002)
            return "device"

        # firehose: bulk blob jobs slam the bounded lane while a
        # trickle of deadline verdicts keeps the device "mid-wave"
        # (the deadline probe holds the incident open)
        for i in range(n_bulk):
            fut = executor.submit("bulk", bulk_blob_job)
            if fut is None:
                # the PR-17 contract: a shed bulk job falls back to
                # the host tier, counted — never silently dropped
                fallbacks += 1
            else:
                submitted += 1
            if i % 4 == 0:
                t0 = time.monotonic()
                df = executor.submit("deadline", lambda: "verdict")
                if df is not None:
                    df.add_done_callback(
                        lambda f, t0=t0: deadline_done.append(
                            time.monotonic() - t0
                        )
                    )
                    deadline_futs.append(df)
            if i in (5, 10, 15):
                # drift windows sampled MID-INCIDENT: the pairing
                # stage departs its budget share past the threshold
                tel.add_window(_drifted_shares(AT))
                mon.sample()
            if i % 16 == 0:
                await asyncio.sleep(0)
        ctx.registry.record("bulk_overload", fallbacks)
        ctx.registry.record("drift_signal", 1)

        # cross-regime invariant: the monitor HAS a pending re-tune
        # but the device is mid-incident — the autotuner must hold
        # still (blocked + counted), with the applied config frozen
        pending = mon.pending_stage
        cfg_before = (K.ingest_min_bucket(), K.ladder_top(),
                      L.get_backend(), M.msm_window())
        fired = mon.maybe_retune()
        cfg_after = (K.ingest_min_bucket(), K.ladder_top(),
                     L.get_backend(), M.msm_window())
        ctx.slo(
            "autotuner_holds_still",
            pending is not None and fired is False
            and mon.retunes_blocked >= 1 and mon.retunes == 0,
            {"pending_stage": pending, "fired": fired,
             "retunes_blocked": mon.retunes_blocked,
             "retunes": mon.retunes},
            "pending re-tune deferred while the device is busy",
        )
        ctx.slo(
            "config_unchanged_mid_incident",
            cfg_before == cfg_after,
            {"before": cfg_before, "after": cfg_after},
            "knobs frozen mid-incident",
        )

        # incident ends: deadline lane quiets, bulk drains
        deadline_busy["flag"] = False
        end_by = time.monotonic() + 10.0
        while time.monotonic() < end_by:
            if (all(v == 0 for v in executor.queue_depths().values())
                    and all(f.done() for f in deadline_futs)):
                break
            await asyncio.sleep(0.01)

        sheds = executor.shed_counts()
        bulk_shed = sheds.get(("bulk", "queue_full"), 0)
        ctx.slo(
            "sheds_counted_never_silent",
            fallbacks > 0 and bulk_shed == fallbacks
            and submitted + fallbacks == n_bulk,
            {"fallbacks": fallbacks, "ledger": bulk_shed,
             "submitted": submitted},
            "every overflow is in the shed ledger AND ran a host "
            "fallback",
        )
        ctx.slo_ge(
            "deadline_preempts_bulk", executor.deadline_deferrals, 1,
            "bulk work deferred while deadline verdicts were due",
        )
        deadline_shed = [k for k in sheds if k[0] == "deadline"]
        ctx.slo(
            "deadline_never_shed", not deadline_shed, deadline_shed,
            "[]", "the deadline lane is never load-shed by bulk "
            "pressure",
        )
        ctx.slo_le(
            "deadline_p99_bounded",
            round(_quantile(deadline_done, 0.99), 3), 2.0,
            "deadline verdict turnaround under the blob firehose",
        )

        # quiesced: the SAME pending drift trigger must now land
        fired = mon.maybe_retune()
        ctx.slo(
            "retune_lands_after_quiesce",
            fired is True and mon.retunes == 1,
            {"fired": fired, "retunes": mon.retunes,
             "blocked": mon.retunes_blocked},
            "deferred re-tune fires once the device quiesces",
        )
        ctx.slo_faults_fired("bulk_overload", "drift_signal")
    finally:
        executor.close()
        K.INGEST_MIN_BUCKET = gate
        K.BUCKET_LADDER = ladder
        K._INGEST_WARM.clear()
        K._INGEST_WARM.update(warm)
        K._WARMUP_STARTED = started
        if L.get_backend() != backend:
            L.set_backend(backend)
        AT._APPLIED = applied
        M.set_msm_window(window)


class _KnobVerifier:
    """Verifier-shaped knob sink for the firehose scenario's tuner:
    accepts the real setters without owning a device pipeline (the
    executor, not the verifier, models busyness here)."""

    def __init__(self):
        self.budget_ms = 50.0
        self.depth = 0

    def set_latency_budget_ms(self, ms):
        self.budget_ms = ms

    def latency_budget_ms(self):
        return self.budget_ms

    def can_accept_work(self):
        return True

    def is_quiescent(self):
        return True

    def pipeline_depth(self):
        return self.depth

    def set_pipeline_depth(self, depth):
        self.depth = depth


class _StageTelemetry:
    """Cumulative per-stage device seconds in the snapshot shape the
    drift monitor consumes (telemetry.snapshot_stage_seconds)."""

    def __init__(self):
        self.dev: dict[str, float] = {}

    def snapshot_stage_seconds(self):
        return {}, dict(self.dev)

    def add_window(self, shares: dict, total_s: float = 1.0) -> None:
        for s, share in shares.items():
            self.dev[s] = self.dev.get(s, 0.0) + share * total_s


def _drifted_shares(AT, stage: str = "pairing", delta: float = 0.16):
    """One drift window: `stage` departs its budget share by +delta
    (past the 0.15 threshold); the loss spreads over the other stages
    capped below threshold so only `stage` trips the monitor."""
    shares = dict(AT.budget_shares())
    shares[stage] += delta
    remaining = delta
    for s in sorted((k for k in shares if k != stage),
                    key=lambda k: -shares[k]):
        give = min(0.13, shares[s], remaining)
        shares[s] -= give
        remaining -= give
    return shares


# ---------------------------------------------------------------------------
# regime 5b: device loss under live gossip load (the device fault
# domain end-to-end: watchdog -> taxonomy -> quarantine -> host
# failover -> probe reinstatement)
# ---------------------------------------------------------------------------


@scenario(
    "device_loss_under_load",
    "every device dispatch hangs mid-gossip: the wave watchdog trips, "
    "verdicts ride the bit-identical host oracle (zero lost, zero "
    "wrong), the autotuner freezes while QUARANTINED, and a "
    "known-answer probe sequence reinstates the device path",
    faults=("device_hang",),
    slos=("verdicts_none_lost", "verdicts_bit_identical",
          "watchdog_tripped", "device_quarantined",
          "failover_served_gossip", "failover_p99_bounded",
          "autotuner_frozen_while_quarantined",
          "probe_reinstates_device"),
)
async def device_loss_under_load(ctx: ScenarioContext) -> None:
    from types import SimpleNamespace

    from ..bls import SignatureSet, kernels as K
    from ..bls.verifier import TpuBlsVerifier
    from ..crypto.bls import signature as sig
    from ..device import autotune as AT
    from ..device.health import DeviceHealthTracker, HealthState
    from ..resilience.clock import ManualClock
    from .faults import device_hang

    def mk_sets(tag: int, n: int = 2, good: bool = True):
        out = []
        for i in range(n):
            sk = 4200 + tag * 8 + i
            msg = bytes([tag, i]) + b"\x00" * 30
            s = sig.sign(sk, msg)
            if not good and i == n - 1:
                b = bytearray(s)
                b[20] ^= 0xFF
                s = bytes(b)
            out.append(SignatureSet(sig.sk_to_pk(sk), msg, s))
        return out

    n_calls = 8 if ctx.smoke else 16
    bad_call = 3  # one tampered job proves failover verdicts can say NO
    calls = [
        (mk_sets(t, good=(t != bad_call)), t != bad_call)
        for t in range(n_calls)
    ]

    clock = ManualClock()
    kicked: list[int] = []
    tracker = DeviceHealthTracker(
        name="scenario-device",
        clock=clock,
        failure_threshold=2,
        quarantine_reset_s=0.05,
        probe_successes=2,
        ladder_shrink=lambda: False,  # no OOM here; never touch knobs
        warmup_kick=lambda: kicked.append(1),
        logger=SimpleNamespace(
            info=lambda *a, **k: None, warn=lambda *a, **k: None
        ),
    )
    verifier = TpuBlsVerifier(max_buffer_wait_ms=5, mesh=False)
    # short real-clock wave deadline: the hang must trip it, not the
    # test runner's patience (the watchdog rides asyncio.wait_for, so
    # the injected ManualClock only drives the probe backoff)
    verifier.attach_health(tracker, wave_timeout_s=0.35)
    # the device served before it hung: a wave that compiles its
    # program is not watched, so the drill's rung is compiled first
    await verifier.verify_signature_sets(mk_sets(n_calls))
    injector = ctx.registry.track(device_hang())
    try:
        results: list[bool] = []
        failover_lat: list[float] = []
        saw_quarantined = False
        for sets, _want in calls:
            pre_failover = not tracker.device_allowed()
            t0 = time.monotonic()
            ok = await verifier.verify_signature_sets(sets)
            dt = time.monotonic() - t0
            results.append(bool(ok))
            if pre_failover:
                # post-quarantine calls short-circuit to the host
                # oracle — the failover latency the SLO bounds
                failover_lat.append(dt)
            saw_quarantined = (
                saw_quarantined
                or tracker.state is HealthState.quarantined
            )

        ctx.slo(
            "verdicts_none_lost",
            len(results) == n_calls,
            {"resolved": len(results), "submitted": n_calls},
            f"{n_calls} resolved",
            "every gossip verdict resolves despite the hung device",
        )
        expected = [want for _, want in calls]
        ctx.slo(
            "verdicts_bit_identical",
            results == expected,
            {"wrong": [i for i, (r, w) in
                       enumerate(zip(results, expected)) if r != w]},
            "[]",
            "host-failover verdicts match the known ground truth "
            "(including the tampered job's False)",
        )
        ctx.slo_ge(
            "watchdog_tripped",
            tracker.watchdog_trips.get("deadline", 0), 1,
            "the wave watchdog fired on the hung dispatch",
        )
        ctx.slo(
            "device_quarantined",
            saw_quarantined and tracker.quarantines >= 1,
            {"saw_quarantined": saw_quarantined,
             "quarantines": tracker.quarantines},
            "quarantined >= once",
            "consecutive watchdog trips opened the breaker",
        )
        ctx.slo(
            "failover_served_gossip",
            tracker.failover_dispatches.get("bls", 0) >= 1
            and verifier.metrics.dispatch_by_path["failover"] >= 1
            and len(failover_lat) >= 1,
            {"failovers": tracker.failover_dispatches,
             "path": dict(verifier.metrics.dispatch_by_path),
             "failover_calls": len(failover_lat)},
            "failover dispatches > 0",
            "post-quarantine buckets rode the host oracle",
        )
        ctx.slo_le(
            "failover_p99_bounded",
            round(_quantile(failover_lat, 0.99), 3), 2.0,
            "host-failover verdict turnaround (no watchdog wait)",
        )

        # frozen-config invariant: a tune attempted while QUARANTINED
        # must suspend — no probes, no knob movement
        quiet_log = SimpleNamespace(
            info=lambda *a, **k: None, warn=lambda *a, **k: None
        )
        bench = lambda backend, bucket: AT.Measurement(
            backend=backend, bucket=bucket, pipeline="batch",
            seconds_per_dispatch=bucket / 400.0, sets_per_sec=400.0,
            runs=3, warm_seconds=0.0,
        )
        tuner = AT.DeviceAutotuner(
            verifier=_KnobVerifier(), grid=AT.parse_grid("backend=vpu"),
            bench=bench, artifact_path=None, logger=quiet_log,
            health=tracker,
        )
        cfg_before = (K.ingest_min_bucket(), K.ladder_top())
        decision = tuner.tune(trigger="drift:scenario")
        cfg_after = (K.ingest_min_bucket(), K.ladder_top())
        ctx.slo(
            "autotuner_frozen_while_quarantined",
            decision.get("source") == "suspended"
            and tuner.suspended_runs >= 1
            and cfg_before == cfg_after,
            {"source": decision.get("source"),
             "suspended_runs": tuner.suspended_runs,
             "before": cfg_before, "after": cfg_after},
            "source=suspended, knobs frozen",
            "no probe and no knob movement while QUARANTINED",
        )

        # reinstatement: restore the kernels FIRST (the probe's device
        # would still hang), then drive the backoff + probe sequence
        injector.detach()
        clock.advance(0.06)  # past quarantine_reset_s
        first = tracker.maybe_probe(lambda: True)
        second = tracker.maybe_probe(lambda: True)
        ctx.slo(
            "probe_reinstates_device",
            first is True and second is True
            and tracker.state is HealthState.online
            and tracker.device_allowed()
            and tracker.reinstatements == 1
            and len(kicked) == 1,
            {"probes": tracker.probes, "state": tracker.state.value,
             "reinstatements": tracker.reinstatements,
             "warmup_kicks": len(kicked)},
            "2 probe successes -> ONLINE + warmup re-kick",
            "the known-answer probe sequence reopened the device path",
        )
        ctx.slo_faults_fired("device_hang")
    finally:
        # detach is idempotent; it also releases any dispatch still
        # hung in the default executor so asyncio.run can shut its
        # thread pool down instead of joining a wedged thread forever
        injector.detach()
        await verifier.close()


# ---------------------------------------------------------------------------
# regime 6: checkpoint-sync thundering herd
# ---------------------------------------------------------------------------


@scenario(
    "checkpoint_thundering_herd",
    "most of the network restarts and catches up at once; catch-up "
    "completes, the surviving node's duties never stop, finality "
    "resumes",
    faults=("node_kill", "node_restart"),
    slos=("survivor_duties_continue", "herd_catch_up_completes",
          "heads_consistent_after_recovery", "finality_resumes",
          "no_missed_blocks_after_recovery"),
)
async def checkpoint_thundering_herd(ctx: ScenarioContext) -> None:
    from . import assertions as A
    from .faults import kill_node, restart_node
    from .simulation import Simulation

    spe = preset().SLOTS_PER_EPOCH
    sim = Simulation(_cfg(), _types(), n_nodes=3, n_validators=24)
    await sim.start()
    try:
        await sim.run_until_slot(spe)

        # the herd goes down: 2 of 3 nodes at once
        for idx in (1, 2):
            await kill_node(sim, idx)
            ctx.registry.record("node_kill")
        survivor = sim.nodes[0]
        proposed_before = survivor.blocks_proposed
        outage_start = sim.slot
        max_outage = (2 if ctx.smoke else 4) * spe
        # run until the survivor demonstrably kept proposing (its 1/3
        # of proposer slots), bounded so a pathological shuffle can't
        # hang the scenario
        while (survivor.blocks_proposed == proposed_before
               and sim.slot < outage_start + max_outage):
            await sim.run_slot()
        await sim.run_slot()
        survivor_blocks = survivor.blocks_proposed - proposed_before
        ctx.slo_ge(
            "survivor_duties_continue", survivor_blocks, 1,
            "the healthy node's proposals must not miss while the "
            "herd is down",
        )

        # thundering herd: both nodes restart and catch up AT ONCE
        restart_slot = sim.slot
        imported = []
        for idx in (1, 2):
            imported.append(await restart_node(sim, idx, resync_from=0))
            ctx.registry.record("node_restart")
        ctx.slo(
            "herd_catch_up_completes",
            all(n == survivor_blocks for n in imported),
            imported, survivor_blocks,
            "each restarted node imports exactly the canonical blocks "
            "it missed (caught_up_blocks)",
        )

        # phase0 finality needs two consecutive fully-justified
        # epochs AFTER the herd returns, plus the epoch the restart
        # landed in (partial participation) — three epochs out is the
        # earliest slot finalized can have advanced past its
        # at-restart value
        recover_epochs = 3 if ctx.smoke else 4
        fin_restart = max(A.finalized_epochs(sim).values())
        target = ((restart_slot // spe) + recover_epochs) * spe + 1
        await sim.run_until_slot(target)
        ctx.slo_true("heads_consistent_after_recovery",
                     A.heads_consistent(sim))
        fin = A.finalized_epochs(sim)
        ctx.slo_ge("finality_resumes", min(fin.values()),
                   fin_restart + 1,
                   "full participation after the herd returns must "
                   "finalize again")
        missing = A.missed_slots(sim, restart_slot + 3)
        ctx.slo(
            "no_missed_blocks_after_recovery",
            all(not m for m in missing.values()),
            missing, "no canonical gaps",
        )
        ctx.slo_faults_fired("node_kill", "node_restart")
    finally:
        await sim.stop()


# ---------------------------------------------------------------------------
# regime 8: light-client read flood against the serving tier
# ---------------------------------------------------------------------------


class _StubScenarioVerifier:
    """Signature stub: the flood regime measures the SERVING tier, so
    block-import BLS (pure python off-device) is stubbed to keep the
    altair dev chain seconds-fast, same as tests/test_lightclient.py."""

    async def verify_signature_sets(self, sets, **kw):
        return True

    async def verify_signature_sets_same_message(self, sets, message):
        return [True] * len(sets)

    def can_accept_work(self):
        return True

    async def close(self):
        pass


def _http_get(url: str, timeout: float = 10.0):
    """(status, headers, body) — HTTPError is a response, not a crash."""
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        body = e.read()
        return e.code, dict(e.headers), body


@scenario(
    "lightclient_flood",
    "a light-client read flood + SSE subscriber swarm against the "
    "REST serving tier while the chain keeps importing: duty p99 "
    "unharmed, sheds confined to cheap classes, zero 500s, cache "
    "hit-ratio floor, slow SSE consumers evicted",
    faults=("read_flood", "sse_slow_consumer"),
    slos=("duty_p99_unharmed", "sheds_only_cheap_classes",
          "zero_500s", "cache_hit_ratio_floor",
          "refusals_carry_retry_after",
          "sse_drops_counted_and_evicted"),
)
async def lightclient_flood(ctx: ScenarioContext) -> None:
    import threading
    import time as _time

    from ..api.impl import BeaconApiImpl
    from ..api.overload import (
        CLS_ADMIN,
        CLS_CONN,
        CLS_DUTY,
        CLS_LIGHT,
        ClassBudget,
        LoopLagProbe,
        ServingOverload,
    )
    from ..api.server import BeaconRestApiServer
    from ..chain import DevNode
    from ..lightclient import LightClientServer

    spe = preset().SLOTS_PER_EPOCH
    cfg = _cfg(ALTAIR_FORK_EPOCH=0)
    types = _types()
    node = DevNode(
        cfg, types, 32, verifier=_StubScenarioVerifier(),
        verify_attestations=False,
    )
    node.chain.light_client_server = LightClientServer(
        cfg, types, node.chain
    )
    # tight light-class budget so the flood's sheds are observable at
    # scenario scale; duty stays wide open — the contract under test
    budgets = {
        CLS_DUTY: ClassBudget(10000.0, 4000.0, 32, 5.0),
        CLS_LIGHT: ClassBudget(150.0, 30.0, 8, 0.05),
    }
    overload = ServingOverload(
        budgets=budgets, pool_workers=24, sse_max_subscribers=3
    )
    overload.cache.attach(node.chain.events)
    ladder = overload.ladder
    probe = LoopLagProbe(ladder, interval=0.05)
    impl = BeaconApiImpl(cfg, types, node.chain)
    server = BeaconRestApiServer(
        impl, port=0, loop=asyncio.get_running_loop(),
        overload=overload,
    )
    port = server.start()
    base = f"http://127.0.0.1:{port}"
    probe.start(asyncio.get_running_loop())
    try:
        # altair from genesis: the optimistic update exists after the
        # first imported sync aggregate, so smoke only warms a few
        # slots (each costs pure-python sync-committee signing)
        warm_slots = 4 if ctx.smoke else 2 * spe + 2
        await node.run_until(warm_slots)

        def duty_url():
            return (
                f"{base}/eth/v1/validator/attestation_data"
                f"?slot={node.slot}&committee_index=0"
            )

        # -- quiet baseline: duty-class latency with nothing else on
        n_quiet = 30 if ctx.smoke else 120
        quiet: list[float] = []
        for _ in range(n_quiet):
            t0 = _time.monotonic()
            status, _h, _b = _http_get(duty_url())
            quiet.append(_time.monotonic() - t0)
            assert status == 200, f"quiet duty request got {status}"
        quiet_p99 = _quantile(quiet, 0.99)

        # prime the hot cacheable routes once while the bucket is full
        _http_get(f"{base}/eth/v1/beacon/light_client/optimistic_update")
        _http_get(f"{base}/eth/v1/beacon/headers/head")

        # -- the flood: reader threads + SSE swarm while slots import
        stop = threading.Event()
        statuses: list[tuple[int, bool]] = []  # (status, retry_after?)
        st_lock = threading.Lock()

        # fixed per-thread request counts with a tiny think time:
        # enough pressure to drain the light-class bucket, throttled
        # enough that the flood doesn't starve the import loop's GIL
        # share outright (the real adversary is remote; this one
        # shares a core with the node)
        reqs_per_thread = 150 if ctx.smoke else 500

        def flood_reader(i: int):
            rng = random.Random(1000 + i)
            for _ in range(reqs_per_thread):
                if stop.is_set():
                    break
                if rng.random() < 0.7:
                    # hot identical read: the cache's job
                    url = (f"{base}/eth/v1/beacon/light_client/"
                           "optimistic_update")
                else:
                    # varied historical read: misses the cache, lands
                    # on admission every time
                    vid = rng.randrange(32)
                    url = (f"{base}/eth/v1/beacon/states/head/"
                           f"validators/{vid}")
                status, headers, _b = _http_get(url)
                with st_lock:
                    statuses.append(
                        (status, "Retry-After" in headers)
                    )
                _time.sleep(0.002)

        duty_flood: list[float] = []

        def duty_reader():
            while not stop.is_set():
                t0 = _time.monotonic()
                status, _h, _b = _http_get(duty_url())
                duty_flood.append(_time.monotonic() - t0)
                with st_lock:
                    statuses.append((status, False))
                _time.sleep(0.01)

        # SSE swarm: the cap is 3, so the extras must be refused with
        # Retry-After, not queued
        sse_threads = []
        sse_refused: list = []

        def sse_stream(frames: list):
            import http.client

            conn = http.client.HTTPConnection(
                "127.0.0.1", port, timeout=30
            )
            conn.request(
                "GET", "/eth/v1/events?topics=head,block"
            )
            resp = conn.getresponse()
            if resp.status != 200:
                sse_refused.append(
                    (resp.status,
                     resp.getheader("Retry-After") is not None)
                )
                conn.close()
                return
            try:
                while not stop.is_set():
                    chunk = resp.fp.readline()
                    if not chunk:
                        break
                    if chunk.startswith(b"event:"):
                        frames.append(chunk)
            except OSError:
                pass
            finally:
                conn.close()

        # a slow consumer on the same emitter with a tiny queue: it
        # never drains, so the broadcast fan-out must evict it and
        # count the drops instead of blocking block import
        node.chain.events.max_queued = 4
        slow_sub = node.chain.events.subscribe(("head", "block"))
        ctx.registry.record("sse_slow_consumer")
        assert slow_sub is not None

        sse_frames: list = []
        for _ in range(5):
            t = threading.Thread(
                target=sse_stream, args=(sse_frames,), daemon=True
            )
            t.start()
            sse_threads.append(t)
        _time.sleep(0.2)  # let streams attach before the flood

        n_flood_threads = 4 if ctx.smoke else 8
        readers = [
            threading.Thread(
                target=flood_reader, args=(i,), daemon=True
            )
            for i in range(n_flood_threads)
        ]
        duty_t = threading.Thread(target=duty_reader, daemon=True)
        for t in readers:
            t.start()
        duty_t.start()

        flood_slots = 3 if ctx.smoke else spe
        for _ in range(flood_slots):
            await node.advance_slot()
            await asyncio.sleep(0.05)
        # readers drain their fixed budgets, then everything stops
        while any(t.is_alive() for t in readers):
            await asyncio.sleep(0.1)
        stop.set()
        for t in readers:
            t.join(timeout=10)
        duty_t.join(timeout=10)
        n_reads = len(statuses)
        ctx.registry.record("read_flood", n_reads)

        # -- the contract -------------------------------------------
        flood_p99 = _quantile(duty_flood, 0.99)
        bound = max(2 * quiet_p99, 0.25)
        ctx.slo_le(
            "duty_p99_unharmed", round(flood_p99, 4), round(bound, 4),
            "duty-class p99 under flood within 2x of the quiet "
            "baseline (absolute floor absorbs timer noise)",
        )

        sheds = overload.shed_counts()
        total_sheds = sum(sheds.values())
        cheap = {CLS_LIGHT, CLS_ADMIN, CLS_CONN}
        cheap_sheds = sum(
            n for (cls, _r), n in sheds.items() if cls in cheap
        )
        duty_sheds = sum(
            n for (cls, _r), n in sheds.items() if cls == CLS_DUTY
        )
        ctx.slo(
            "sheds_only_cheap_classes",
            total_sheds > 0
            and duty_sheds == 0
            and cheap_sheds / total_sheds >= 0.95,
            {k: v for k, v in sorted(sheds.items())},
            ">= 95% of sheds on light/admin/conn, zero on duty",
            "the flood must land on the classes built to absorb it",
        )

        responses = overload.response_counts()
        server_5xx = sum(
            n for s, n in responses.items() if s in (500, 501, 502)
        )
        client_500 = sum(
            1 for s, _ra in statuses if s in (500, 501, 502)
        )
        ctx.slo(
            "zero_500s",
            server_5xx == 0 and client_500 == 0,
            {"server": server_5xx, "client": client_500,
             "responses": responses},
            "no internal errors — refusals are typed 429/503 sheds "
            "with Retry-After, 504 only on bridge timeout",
        )

        ratio = overload.cache.hit_ratio()
        floor = 0.5
        ctx.slo_ge(
            "cache_hit_ratio_floor", round(ratio, 3), floor,
            "hot identical reads must be served from the head-keyed "
            "cache (fresh or stale), not recomputed",
        )

        refused = [
            (s, ra) for s, ra in statuses if s in (429, 503)
        ] + [(s, ra) for s, ra in sse_refused]
        ctx.slo(
            "refusals_carry_retry_after",
            len(refused) > 0 and all(ra for _s, ra in refused),
            {"refusals": len(refused),
             "with_retry_after": sum(1 for _s, ra in refused if ra)},
            "every 429/503 carries Retry-After",
            "clients must learn the backoff from the wire",
        )

        emitter = node.chain.events
        dropped = sum(emitter.dropped.values())
        ctx.slo(
            "sse_drops_counted_and_evicted",
            dropped >= 1 and emitter.evictions >= 1
            and slow_sub.evicted and len(sse_frames) > 0,
            {"dropped": dropped, "evictions": emitter.evictions,
             "slow_sub_evicted": slow_sub.evicted,
             "frames_delivered": len(sse_frames)},
            "drops counted + slow consumer evicted while healthy "
            "subscribers keep their stream",
            "lossy-by-design is only acceptable when accounted",
        )
        ctx.slo_faults_fired("read_flood", "sse_slow_consumer")
    finally:
        probe.stop()
        server.stop()
        await node.close()
