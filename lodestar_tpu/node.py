"""BeaconNode: full node assembly.

Reference analog: BeaconNode.init (beacon-node/src/node/nodejs.ts:143)
— wires db -> metrics -> chain -> network processor -> sync -> api ->
metrics server around one asyncio loop, with graceful close in reverse
order; plus the NodeNotifier status line (notifier.ts).
"""

from __future__ import annotations

import asyncio

from .api.impl import BeaconApiImpl
from .api.server import BeaconRestApiServer
from .chain.chain import BeaconChain
from .chain.oppools import AggregatedAttestationPool, OpPool
from .chain.validation import AttestationValidator
from .config.beacon_config import BeaconConfig
from .db.beacon import BeaconDb
from .lightclient import LightClientServer
from .logger import get_logger
from .metrics import (
    MetricsServer,
    RegistryMetricCreator,
    create_lodestar_metrics,
)
from .network.processor import NetworkProcessor
from .network.reqresp import InProcessTransport, ReqResp
from .params import ForkSeq, preset
from .sync import RangeSync, SyncServer
from .utils import chip


class BeaconNode:
    def __init__(
        self,
        cfg,
        types,
        anchor_state_view=None,
        db: BeaconDb | None = None,
        verifier=None,
        api_port: int = 0,
        api_workers: int = 16,
        metrics_port: int | None = None,
        peer_id: str = "node",
        transport: InProcessTransport | None = None,
        logger=None,
        # -- wire stack (None = in-process transport only) --
        tcp_port: int | None = None,
        udp_port: int = 0,
        bootnodes: list[tuple[str, int]] | None = None,
        # isolation is the production default, matching the
        # reference's useWorker=true (network/options.ts:36)
        network_isolated: bool = True,
        # -- execution layer --
        execution_url: str | None = None,
        jwt_secret: bytes | None = None,
        eth1_provider=None,
        builder_url: str | None = None,
        # -- kzg --
        trusted_setup_path: str | None = None,
        # -- monitoring --
        monitoring_endpoint: str | None = None,
        monitored_validators: list[int] | None = None,
        # -- checkpoint sync (initBeaconState.ts) --
        checkpoint_sync_url: str | None = None,
        wss_state_root: bytes | None = None,
        # -- bls verifier warmup (bls/kernels.warmup_ingest) --
        bls_warmup: bool = True,
        # -- block-import span tracing (metrics/tracing.py) --
        # imports slower than this land in the slow-trace ring buffer
        # behind /eth/v1/lodestar/block_import_traces; 0 records every
        # import (debugging / sims)
        trace_slow_slot_ms: float = 500.0,
        trace_buffer_size: int = 64,
        # -- device telemetry (metrics/device.py) --
        # "dispatch" times stage calls + attributes compiles/retraces;
        # "sync" adds block_until_ready deltas (serializes the host
        # against each stage — debugging, not steady-state); "off"
        # reduces every kernel hook to one attribute check
        device_timing: str = "dispatch",
        # POST /eth/v1/lodestar/device_trace capture-length ceiling
        device_trace_max_ms: float = 5000.0,
        device_trace_dir: str | None = None,
        # -- device auto-tuning (device/autotune.py) --
        # "startup": micro-bench the candidate grid once at init and
        # apply the winner through the live setters; "adaptive" adds
        # the drift monitor (budget-share watch + bounded re-tunes);
        # "off" leaves every knob wherever env/CLI put it
        autotune: str = "off",
        autotune_budget_ms: float = 30_000.0,
        autotune_grid: str | None = None,
        autotune_artifact: str | None = "AUTOTUNE.json",
        # -- node-wide device executor (device/executor.py) --
        # QoS-classed scheduling for every accelerator client:
        # deadline (gossip verdicts) ahead of bulk (blob batches)
        # at every wave boundary, maintenance (warmup / autotune)
        # aged so bulk can't starve it, bounded per-class queues
        # shedding bulk/maintenance under overload
        device_executor: bool = True,
        executor_bulk_queue: int = 64,
        executor_maintenance_queue: int = 32,
        executor_aging_ms: float = 2000.0,
        # -- device fault domain (device/health.py) --
        # wave watchdog + error taxonomy + circuit-broken host
        # failover + live probe reinstatement. Watchdog deadlines
        # (multiples of the fused stage budget) arm only on a real
        # accelerator: CPU dispatches legitimately dwarf the budget
        device_health: bool = True,
        health_probe_interval_s: float = 5.0,
    ):
        self.cfg = cfg
        self.types = types
        self.log = logger or get_logger("node")
        self.metrics_registry = RegistryMetricCreator()
        self.metrics = create_lodestar_metrics(self.metrics_registry)
        from .resilience import create_resilience_metrics

        # retry counters + breaker/engine-state gauges on /metrics
        self.resilience_metrics = create_resilience_metrics(
            self.metrics_registry
        )
        self.db = db
        self.anchor = anchor_state_view
        self.verifier = verifier
        self.api_port = api_port
        self.api_workers = api_workers
        self.metrics_port = metrics_port
        self.peer_id = peer_id
        self.transport = transport or InProcessTransport()
        self.chain: BeaconChain | None = None
        self.api_server = None
        self.loop_lag_probe = None
        self.metrics_server = None
        self.processor = None
        self.range_sync = None
        self.att_pool = None
        self.op_pool = None
        self.tcp_port = tcp_port
        self.udp_port = udp_port
        self.network_isolated = network_isolated
        self.bootnodes = bootnodes or []
        self.execution_url = execution_url
        self.jwt_secret = jwt_secret
        self.eth1_provider = eth1_provider
        self.builder_url = builder_url
        self.trusted_setup_path = trusted_setup_path
        self.monitoring_endpoint = monitoring_endpoint
        self.monitored_validators = monitored_validators or []
        self.checkpoint_sync_url = checkpoint_sync_url
        self.wss_state_root = wss_state_root
        self.bls_warmup = bls_warmup
        self.device_trace_max_ms = device_trace_max_ms
        self.device_trace_dir = device_trace_dir
        if autotune not in ("off", "startup", "adaptive"):
            raise ValueError(
                f"autotune mode {autotune!r} not in"
                " ('off', 'startup', 'adaptive')"
            )
        self.autotune_mode = autotune
        self.autotune_budget_ms = autotune_budget_ms
        self.autotune_grid = autotune_grid
        self.autotune_artifact = autotune_artifact
        self.autotuner = None
        self.drift_monitor = None
        self._drift_task: asyncio.Task | None = None
        self.device_executor_enabled = device_executor
        self.executor_bulk_queue = executor_bulk_queue
        self.executor_maintenance_queue = executor_maintenance_queue
        self.executor_aging_ms = executor_aging_ms
        self.executor = None
        self.device_health_enabled = device_health
        self.health_probe_interval_s = health_probe_interval_s
        self.health_tracker = None
        self._probe_task: asyncio.Task | None = None
        # device/compiler telemetry: singleton installed here so the
        # jax.monitoring listeners and the kernels' instrumented stage
        # wrappers route into THIS node's registry
        from .metrics import device as _device_telemetry

        self.device_telemetry = _device_telemetry.install(
            metrics=self.metrics.device, timing=device_timing
        )
        from .metrics import Tracer

        self.tracer = Tracer(
            metrics=self.metrics.tracing,
            slow_ms=trace_slow_slot_ms,
            buffer_size=trace_buffer_size,
        )
        self.metrics.tracing.trace_buffer_size.add_collect(
            lambda g: g.set(len(self.tracer.buffer))
        )
        self.network = None
        self.builder = None
        self.monitoring = None
        self.unknown_block_sync = None
        self.backfill = None
        self.historical = None
        self.reprocess = None
        self.prepare_next_slot = None
        self.checkpoint_states = None
        self.clock = None
        self._altair_topics_on = False
        self._prepare_tasks: set = set()

    def _monitor_slot_tick(self, slot: int) -> None:
        """Validator-monitor wall-clock duties: missed-proposal
        detection for the previous slot, and per-epoch balance capture
        + rollup at epoch starts (validatorMonitor onceEverySlot /
        onceEveryEndOfEpoch)."""
        vm = self.chain.validator_monitor
        if vm is None or not vm.count:
            return
        p = preset()
        try:
            prev = slot - 1
            if prev > 0:
                head = self.chain.fork_choice.proto.get_node(
                    self.chain.head_root
                )
                if head is not None and head.slot < prev:
                    # no canonical block at prev: was one of ours due?
                    # The proposer was recorded when the next-slot
                    # scheduler prepared prev's state (slot-seeded, so
                    # only the advanced state answers exactly)
                    pns = self.prepare_next_slot
                    proposer = (
                        pns.expected_proposers.get(prev)
                        if pns is not None
                        else None
                    )
                    if proposer is not None:
                        vm.on_missed_block(proposer, prev)
            if slot % p.SLOTS_PER_EPOCH == 0 and slot > 0:
                epoch = slot // p.SLOTS_PER_EPOCH
                st = self.chain.head_state.state
                vm.on_balances(st, epoch - 1)
                sc = getattr(st, "current_sync_committee", None)
                if sc is not None:
                    from .statetransition.util import PubkeyIndexView

                    pk2i = PubkeyIndexView(st)
                    members = [
                        i
                        for i in (
                            pk2i.get(bytes(pk)) for pk in sc.pubkeys
                        )
                        if i is not None and i in vm.validators
                    ]
                    if members:
                        vm.on_sync_committee_membership(
                            members, epoch - 1
                        )
                vm.on_epoch_summary(epoch - 1)
        except Exception:
            pass  # monitoring must never break the clock tick

    def _maybe_subscribe_altair_topics(self, epoch: int) -> None:
        """Sync-committee + LC update topics exist from altair
        (gossip/interface.ts:24-69). Called at assembly AND on every
        slot tick so a node started pre-altair subscribes when the
        fork activates, not only at restart."""
        if self._altair_topics_on or self.network is None:
            return
        from .statetransition.slot import fork_at_epoch

        head_seq = self.chain.head_state.fork_seq
        clock_fork = fork_at_epoch(self.cfg, epoch)
        if head_seq >= ForkSeq.altair or ForkSeq[clock_fork] >= ForkSeq.altair:
            self.network.subscribe_sync_committee_topics()
            self.network.subscribe_light_client_topics(
                self.chain.light_client_server
            )
            self._altair_topics_on = True

    @classmethod
    async def init(cls, **kwargs) -> "BeaconNode":
        """Assemble and start all services (nodejs.ts:143-300)."""
        node = cls(**kwargs)
        log = node.log
        # checkpoint sync: fetch the anchor from a trusted endpoint
        # (initBeaconState.ts checkpoint-sync path) — takes precedence
        # over genesis but not over a resumable db
        if (
            node.anchor is None
            and node.checkpoint_sync_url is not None
            and (node.db is None or node.db.meta.get_raw("head_root") is None)
        ):
            import functools

            from .sync.checkpoint import fetch_checkpoint_state

            if node.wss_state_root is None:
                # the endpoint's state is trusted wholesale (fork/clock
                # checks only) — surface the trade-off at runtime, not
                # just in docs (ADVICE r3)
                log.warn(
                    "checkpoint sync WITHOUT --wss-state-root: trusting "
                    "the endpoint's state unverified",
                    {"url": node.checkpoint_sync_url},
                )
            # blocking urllib fetch off the event loop
            node.anchor = await asyncio.get_running_loop().run_in_executor(
                None,
                functools.partial(
                    fetch_checkpoint_state,
                    node.checkpoint_sync_url,
                    node.cfg,
                    node.types,
                    expected_root=node.wss_state_root,
                ),
            )
            log.info(
                "checkpoint sync anchor fetched",
                {
                    "url": node.checkpoint_sync_url,
                    "slot": int(node.anchor.state.slot),
                    "fork": node.anchor.fork,
                },
            )
        # chain: resume from db when it has an anchor, else fresh
        if node.anchor is None:
            if node.db is None:
                raise ValueError("need anchor_state_view or a db to resume")
            log.info("resuming chain from db")
            node.chain = await BeaconChain.from_db(
                node.cfg, node.types, node.db, verifier=node.verifier
            )
        else:
            node.chain = BeaconChain(
                node.cfg,
                node.types,
                node.anchor,
                verifier=node.verifier,
                db=node.db,
            )
        # block-import span tracing: every import now produces the
        # per-stage trace; slow slots are ring-buffered for the admin
        # debug route (api/impl.get_block_import_traces)
        node.chain.tracer = node.tracer
        node.chain.regen.metrics = node.metrics.regen
        # node-wide device executor: the QoS scheduler every
        # accelerator client joins. Constructed BEFORE autotune and
        # warmup so both run as maintenance-class clients from their
        # very first dispatch: the verifier registers its deadline
        # probes, kzg's MSM/Fr device tiers ride the bulk lane, the
        # warmup thread yields between compiles, and the drift
        # monitor's re-tune becomes an executor drain (zero
        # hold_intake calls).
        if node.device_executor_enabled:
            from .bls import kernels as _kernels
            from .crypto import kzg as _kzg_exec
            from .device import executor as _dexec

            node.executor = _dexec.DeviceExecutor(
                queue_bounds={
                    "bulk": node.executor_bulk_queue,
                    "maintenance": node.executor_maintenance_queue,
                },
                aging_ms=node.executor_aging_ms,
            )
            if hasattr(node.chain.verifier, "attach_executor"):
                node.chain.verifier.attach_executor(node.executor)
            _kernels.set_maintenance_gate(
                node.executor.maintenance_checkpoint
            )
            _kzg_exec.set_executor(node.executor)
            _dexec.bind_executor_collectors(
                node.metrics.device_executor, node.executor
            )
            log.info(
                "device executor up",
                {
                    "bulk_queue": node.executor_bulk_queue,
                    "maintenance_queue": (
                        node.executor_maintenance_queue
                    ),
                    "aging_ms": node.executor_aging_ms,
                },
            )
        # device fault domain: one tracker is the single source of
        # truth every accelerator client consults. Wired AFTER the
        # executor (the watchdog + probe ride it) and BEFORE autotune
        # (a tune against a quarantined device must suspend).
        if node.device_health_enabled:
            from .bls import kernels as _kernels_h
            from .crypto import kzg as _kzg_h
            from .device import health as _health

            warm_kick = None
            if node.bls_warmup and hasattr(
                node.chain.verifier, "start_warmup"
            ):
                warm_kick = node.chain.verifier.start_warmup
            node.health_tracker = _health.DeviceHealthTracker(
                warmup_kick=warm_kick,
                logger=get_logger("device-health"),
            )
            on_accel = chip.on_tpu()
            # warmup suspends while quarantined; kzg MSM/Fr ride
            # their host tiers; the verifier's buckets short-circuit
            # to the bit-identical host oracle
            _kernels_h.set_health_gate(
                node.health_tracker.device_allowed
            )
            _kzg_h.set_health_tracker(node.health_tracker)
            if hasattr(node.chain.verifier, "attach_health"):
                node.chain.verifier.attach_health(
                    node.health_tracker,
                    # None adopts the fused-budget deadline; 0 leaves
                    # the wave watchdog unarmed (CPU backends)
                    wave_timeout_s=None if on_accel else 0,
                )
            if node.executor is not None:
                node.executor.set_health_tracker(
                    node.health_tracker,
                    deadlines=(
                        _health.default_watchdog_deadlines()
                        if on_accel
                        else None
                    ),
                )
            node.health_tracker.set_probe(
                _health.make_device_probe(executor=node.executor)
            )
            node._probe_task = asyncio.ensure_future(
                node._health_probe_loop()
            )
            _health.bind_health_collectors(
                node.metrics.device_health, node.health_tracker
            )
            log.info(
                "device fault domain up",
                {
                    "watchdog_armed": on_accel,
                    "probe_interval_s": (
                        node.health_probe_interval_s
                    ),
                },
            )
        # device auto-tuning: close the telemetry->knobs loop. The
        # startup tune micro-benches the candidate grid through the
        # persistent compilation cache and applies the winner via the
        # real setters BEFORE traffic arrives; adaptive mode adds the
        # drift monitor (budget-share watch, quiescence-gated bounded
        # re-tunes). Runs in an executor: the probes block on device
        # work and must not stall the event loop during assembly.
        # Ordered BEFORE warmup so the background warmup compiles the
        # TUNED gate/ladder eligibility, not rungs about to change.
        if node.autotune_mode != "off":
            from .device import autotune as _autotune

            node.autotuner = _autotune.DeviceAutotuner(
                verifier=node.chain.verifier,
                budget_ms=node.autotune_budget_ms,
                grid=_autotune.parse_grid(node.autotune_grid),
                artifact_path=node.autotune_artifact,
                mode=node.autotune_mode,
                logger=get_logger("autotune"),
                executor=node.executor,
                health=node.health_tracker,
            )
            await asyncio.get_running_loop().run_in_executor(
                None, node.autotuner.tune
            )
            if node.autotune_mode == "adaptive":
                node.drift_monitor = _autotune.DriftMonitor(
                    node.autotuner,
                    node.device_telemetry,
                    verifier=node.chain.verifier,
                    executor=node.executor,
                    health=node.health_tracker,
                )
                node._drift_task = asyncio.ensure_future(
                    node.drift_monitor.run()
                )
            _autotune.bind_autotune_collectors(
                node.metrics.autotune,
                node.autotuner,
                monitor=node.drift_monitor,
            )
        # pre-warm the device-ingest compiles (every eligible ladder
        # rung at the — possibly just tuned — gate) on a background
        # thread through the persistent cache so steady-state gossip
        # never pays a cold multi-minute XLA compile; until a size is
        # warm the verifier serves it from the host path
        # (host_fallback_when_cold)
        if node.bls_warmup and hasattr(
            node.chain.verifier, "start_warmup"
        ):
            if node.chain.verifier.start_warmup() is not None:
                log.info("bls ingest warmup started in background")
        gvr = bytes(
            node.chain.head_state.state.genesis_validators_root
        )
        node.beacon_cfg = BeaconConfig(node.cfg, gvr)
        node.chain.light_client_server = LightClientServer(
            node.cfg, node.types, node.chain
        )
        # kzg trusted setup (initCKZG + loadEthereumTrustedSetup,
        # nodejs.ts:162-165): dev setup unless a ceremony file is given
        from .crypto import kzg as _kzg

        if node.trusted_setup_path is not None:
            _kzg.load_trusted_setup(node.trusted_setup_path)
            log.info("trusted setup loaded",
                     {"path": str(node.trusted_setup_path)})
        elif (
            node.cfg.DENEB_FORK_EPOCH != 2**64 - 1
            and node.cfg.CONFIG_NAME not in ("minimal", "dev")
        ):
            # The dev setup's tau derives from a public seed — anyone can
            # forge blob proofs against it. A deneb+ production network
            # must run the ceremony setup (ref always loads it at startup,
            # nodejs.ts:162-165).
            log.warn(
                "INSECURE: no --trusted-setup given on a deneb-enabled "
                "network; falling back to the DEV trusted setup whose tau "
                "is publicly derivable. Blob KZG proofs can be FORGED. "
                "Provide the Ethereum KZG ceremony file for production.",
                {"config": node.cfg.CONFIG_NAME},
            )
        # pre-warm the device MSM rungs the DA path dispatches (blob
        # batch-verify + blob-width lincombs) on a background thread —
        # only where the auto backend will actually route them (TPU);
        # until a rung is warm, lincombs ride the host C Pippenger
        # (counted as lodestar_kzg_msm_device_fallback_total)
        if (
            node.bls_warmup
            and _kzg.msm_backend() in ("auto", "device")
            and chip.on_tpu()
        ):
            import threading

            from .ops import msm as _msm

            threading.Thread(
                target=_msm.warmup_msm,
                name="kzg-msm-warmup",
                daemon=True,
            ).start()
            log.info("kzg msm warmup started in background")
        # execution engine (engine API over JSON-RPC + JWT), wrapped in
        # the resilience layer: classified retries in the RPC client,
        # engine-state tracking + fail-fast breaker around every call
        if node.execution_url is not None:
            from .execution.http import ExecutionEngineHttp, JsonRpcHttpClient
            from .execution.engine import ResilientEngine
            from .resilience import bind_breaker, bind_engine_tracker

            rpc = JsonRpcHttpClient(
                node.execution_url,
                jwt_secret=node.jwt_secret,
                retries=2,
                metrics=node.resilience_metrics,
            )
            engine = ResilientEngine(
                ExecutionEngineHttp(rpc, types=node.types)
            )
            bind_breaker(engine.breaker, node.resilience_metrics)
            bind_engine_tracker(engine.tracker, node.resilience_metrics)
            node.chain.execution_engine = engine
            node.chain.trusted_execution = False
            log.info("execution engine attached",
                     {"url": node.execution_url})
        # eth1 deposit tracker
        if node.eth1_provider is not None:
            from .eth1 import Eth1DepositDataTracker

            node.chain.eth1 = Eth1DepositDataTracker(
                node.cfg, node.types, node.eth1_provider
            )
        # external builder (MEV-boost relay) behind the fault-
        # inspection-window circuit breaker
        if node.builder_url is not None:
            from .execution.builder import ExecutionBuilderHttp
            from .resilience import bind_breaker

            node.builder = ExecutionBuilderHttp(
                node.builder_url, node.types,
                metrics=node.resilience_metrics,
            )
            bind_breaker(
                node.builder.circuit_breaker, node.resilience_metrics
            )
        # chain auxiliaries
        from .chain.historical import HistoricalStateRegen
        from .chain.prepare_next_slot import PrepareNextSlotScheduler
        from .chain.reprocess import ReprocessController
        from .chain.state_cache import CheckpointStateCache
        from .metrics.validator_monitor import ValidatorMonitor

        node.checkpoint_states = CheckpointStateCache(
            node.types, db=node.db
        )
        node.historical = HistoricalStateRegen(node.chain)
        node.reprocess = ReprocessController(node.chain)
        node.prepare_next_slot = PrepareNextSlotScheduler(node.chain)
        vm = ValidatorMonitor(node.metrics_registry)
        for idx in node.monitored_validators:
            vm.register_local_validator(idx)
        node.chain.validator_monitor = vm
        if node.monitoring_endpoint is not None:
            from .metrics.monitoring import MonitoringService

            node.monitoring = MonitoringService(
                node.monitoring_endpoint, chain=node.chain
            )
            node.monitoring.start()
        node.att_pool = AggregatedAttestationPool(node.types)
        node.op_pool = OpPool(node.types)
        from .chain.oppools import (
            AttestationPool,
            SyncCommitteeMessagePool,
            SyncContributionAndProofPool,
        )

        # unaggregated per-subnet pool feeding getAggregatedAttestation
        # (attestationPool.ts:66) + the sync-committee pools
        node.unagg_pool = AttestationPool(node.types)
        node.sync_msg_pool = SyncCommitteeMessagePool(node.types)
        node.contrib_pool = SyncContributionAndProofPool(node.types)
        # gossip ingest
        validator = AttestationValidator(
            node.cfg, node.types, node.chain, node.chain.verifier
        )
        node.attestation_validator = validator
        from .chain.validation import (
            AggregateAndProofValidator,
            GossipBlockValidator,
            SyncCommitteeValidator,
        )

        node.aggregate_validator = AggregateAndProofValidator(
            node.cfg, node.types, node.chain, node.chain.verifier,
            validator,
        )
        node.block_validator = GossipBlockValidator(
            node.cfg, node.types, node.chain, node.chain.verifier
        )
        node.sync_validator = SyncCommitteeValidator(
            node.cfg, node.types, node.chain, node.chain.verifier
        )
        node.processor = NetworkProcessor(
            node.chain,
            validator,
            node.chain.verifier,
            att_pool=node.att_pool,
            metrics=node.metrics,
            aggregate_validator=node.aggregate_validator,
            block_validator=node.block_validator,
            sync_validator=node.sync_validator,
            unagg_pool=node.unagg_pool,
            sync_msg_pool=node.sync_msg_pool,
            contrib_pool=node.contrib_pool,
            executor=node.executor,
        )
        node.processor.start()
        # wall-clock slot driver: the gossip validators' slot-window
        # checks and seen-cache pruning track real time (the reference
        # clock feeds every validator via chain.clock). Without this
        # clock_slot would stay 0 and every gossip block past slot 1
        # would be IGNOREd as a future slot.
        from .chain.clock import Clock

        node.clock = Clock(
            node.cfg, int(node.chain.head_state.state.genesis_time)
        )

        def _on_clock_slot(slot: int) -> None:
            validator.on_slot(slot)
            node.block_validator.on_slot(slot)
            node.sync_validator.on_slot(slot)
            fin = node.chain.fork_choice.finalized_checkpoint
            node.aggregate_validator.prune(int(fin.epoch))
            node.block_validator.prune(
                int(fin.epoch) * preset().SLOTS_PER_EPOCH
            )
            node._maybe_subscribe_altair_topics(
                slot // preset().SLOTS_PER_EPOCH
            )
            node._monitor_slot_tick(slot)
            # precompute next slot's state + payload attributes + epoch
            # shuffling off the critical path (prepareNextSlot.ts).
            # Only when the wall clock tracks the head: a node behind
            # (syncing, or a dev chain whose genesis_time is synthetic)
            # must not advance a clone across thousands of empty slots
            if node.prepare_next_slot is not None:
                head = node.chain.fork_choice.proto.get_node(
                    node.chain.head_root
                )
                if (
                    head is not None
                    and 0 <= slot + 1 - head.slot
                    <= preset().SLOTS_PER_EPOCH
                ):
                    task = asyncio.ensure_future(
                        node.prepare_next_slot.prepare(slot + 1)
                    )
                    node._prepare_tasks.add(task)
                    task.add_done_callback(node._prepare_tasks.discard)

        node.clock.on_slot(_on_clock_slot)
        _on_clock_slot(node.clock.current_slot)
        node.clock.start()
        # wire stack: real TCP/UDP network when a port is requested,
        # else the in-process transport (tests, embedded use)
        if node.tcp_port is not None:
            from .network.facade import Network
            from .sync import BackfillSync, UnknownBlockSync

            node.network = Network(
                node.chain,
                node.beacon_cfg,
                node.types,
                processor=node.processor,
                peer_id=node.peer_id,
                isolated=node.network_isolated,
            )
            node.network.op_pool = node.op_pool
            await node.network.start(
                tcp_port=node.tcp_port, udp_port=node.udp_port
            )
            node._maybe_subscribe_altair_topics(
                node.clock.current_epoch
            )
            for host, port in node.bootnodes:
                node.network.discovery.add_bootnode(host, port)
            node.reqresp = node.network.reqresp
            node.unknown_block_sync = UnknownBlockSync(
                node.chain, node.beacon_cfg, node.network.reqresp
            )
            node.backfill = BackfillSync(
                node.chain,
                node.beacon_cfg,
                node.types,
                node.network.reqresp,
                node.chain.verifier,
            )
            log.info(
                "network listening",
                {
                    "tcp": node.network.host.port,
                    "udp": node.network.discovery.record.udp_port,
                },
            )
        else:
            node.reqresp = ReqResp(node.peer_id, node.transport)
        def _metadata():
            # seq_number bumps on subnet changes (MetadataController,
            # network/metadata.ts:34); attnets = live subscription set
            net = node.network
            if net is None:
                return (0, set(), set())
            return (
                net.metadata_seq,
                set(net.subscribed_subnets),
                set(),
            )

        SyncServer(
            node.chain,
            node.beacon_cfg,
            node.types,
            metadata_fn=_metadata,
        ).register(node.reqresp)
        node.range_sync = RangeSync(
            node.chain, node.beacon_cfg, node.types, node.reqresp
        )
        if node.network is not None:
            # feed every connected peer into the sync components and
            # head-check it (BeaconSync's status-driven mode switch,
            # sync.ts:19): behind a peer -> range sync toward its head
            main_loop = asyncio.get_running_loop()

            def _on_new_peer(peer_id: str) -> None:
                # fires on the network-core thread under isolation —
                # marshal the chain-side bookkeeping to the chain loop
                def _add() -> None:
                    node.range_sync.add_peer(peer_id)
                    node.unknown_block_sync.add_peer(peer_id)
                    node.backfill.add_peer(peer_id)
                    asyncio.ensure_future(node._head_check(peer_id))

                try:
                    running = asyncio.get_running_loop()
                except RuntimeError:
                    running = None
                if running is main_loop:
                    _add()
                else:
                    main_loop.call_soon_threadsafe(_add)

            node.network.peer_manager.on_new_peer = _on_new_peer
            node.network.on_unknown_parent = (
                node.unknown_block_sync.on_unknown_block
            )
        # REST API behind the serving fault domain (api/overload.py):
        # bounded pool + per-class admission, brownout ladder fed by
        # the loop-lag probe, and the head-keyed response cache
        # invalidated straight off the chain event bus
        from .api.overload import (
            LoopLagProbe,
            ServingOverload,
            bind_api_collectors,
        )

        impl = BeaconApiImpl(node.cfg, node.types, node.chain, node)
        overload = ServingOverload(pool_workers=node.api_workers)
        overload.cache.attach(node.chain.events)
        node.api_server = BeaconRestApiServer(
            impl,
            port=node.api_port,
            loop=asyncio.get_event_loop(),
            overload=overload,
            metrics=node.metrics.api,
        )
        node.loop_lag_probe = LoopLagProbe(
            overload.ladder,
            histogram=node.metrics.clock.event_loop_lag,
        )
        node.loop_lag_probe.start(asyncio.get_event_loop())
        bind_api_collectors(
            node.metrics.api, overload, node.chain.events
        )
        port = node.api_server.start()
        log.info("rest api listening", {"port": port})
        # metrics: sampled gauges collect live values at scrape time
        # (reference addCollect pattern, registryMetricCreator.ts)
        mm = node.metrics
        if node.network is not None:
            mm.network.peers.add_collect(
                lambda g: g.set(len(node.network.host.conns))
            )
            mm.network.gossip_mesh_peers.add_collect(
                lambda g: [
                    g.set(len(peers), type=topic.rsplit("/", 2)[-2])
                    for topic, peers in node.network.gossip.mesh.items()
                ]
            )
            # gossip mesh health: duplicates / graft-prune churn /
            # forward volume / peer-score spread, sampled at scrape
            gos = node.network.gossip
            mm.network.gossip_duplicates_total.add_collect(
                lambda g: g.set(gos.duplicates_received)
            )
            mm.network.gossip_mesh_grafts_total.add_collect(
                lambda g: g.set(gos.grafts_total)
            )
            mm.network.gossip_mesh_prunes_total.add_collect(
                lambda g: g.set(gos.prunes_total)
            )
            mm.network.gossip_forwarded_total.add_collect(
                lambda g: g.set(gos.messages_forwarded)
            )

            def _score_stats(g):
                # zero when no peers remain — stale last-known scores
                # would mask a total peer loss on the dashboard
                vals = [sc.value for sc in gos.scores.values()] or [0.0]
                g.set(min(vals), stat="min")
                g.set(max(vals), stat="max")
                g.set(sum(vals) / len(vals), stat="avg")

            mm.network.gossip_peer_score.add_collect(_score_stats)
        mm.regen.state_cache_size.add_collect(
            lambda g: g.set(len(node.chain._states))
        )
        mm.regen.queue_length.add_collect(
            lambda g: g.set(node.chain.regen._pending)
        )
        cps = node.checkpoint_states
        mm.regen.checkpoint_cache_size.add_collect(
            lambda g: g.set(len(cps._mem))
        )
        mm.regen.cp_cache_hits_total.add_collect(
            lambda g: g.set(cps.hits)
        )
        mm.regen.cp_cache_misses_total.add_collect(
            lambda g: g.set(cps.misses)
        )
        mm.regen.cp_cache_spills_total.add_collect(
            lambda g: g.set(cps.spills)
        )
        mm.regen.cp_cache_reloads_total.add_collect(
            lambda g: g.set(cps.reloads)
        )
        mm.op_pool.attestation_pool_size.add_collect(
            lambda g: g.set(
                sum(len(v) for v in node.att_pool._groups.values())
            )
        )
        mm.op_pool.unagg_attestation_pool_size.add_collect(
            lambda g: g.set(
                sum(len(v) for v in node.unagg_pool._groups.values())
            )
        )
        mm.op_pool.sync_committee_message_pool_size.add_collect(
            lambda g: g.set(len(node.sync_msg_pool._groups))
        )
        mm.op_pool.sync_contribution_pool_size.add_collect(
            lambda g: g.set(len(node.contrib_pool._best))
        )
        mm.op_pool.voluntary_exit_pool_size.add_collect(
            lambda g: g.set(len(node.op_pool.voluntary_exits))
        )
        mm.op_pool.attester_slashing_pool_size.add_collect(
            lambda g: g.set(len(node.op_pool.attester_slashings))
        )
        mm.op_pool.proposer_slashing_pool_size.add_collect(
            lambda g: g.set(len(node.op_pool.proposer_slashings))
        )
        mm.op_pool.bls_to_execution_change_pool_size.add_collect(
            lambda g: g.set(len(node.op_pool.bls_changes))
        )
        def _wall_slot(g):
            import time as _t

            gt = node.chain.genesis_time
            sps = node.cfg.SECONDS_PER_SLOT
            slot = max(0, int((_t.time() - gt) // sps))
            g.set(slot)

        # bridge the verifier service's wave stats into the registry
        # (dashboards/lodestar_tpu_bls_verifier.json panels)
        vm = getattr(node.chain.verifier, "metrics", None)
        if vm is not None:
            tv = mm.tpu_verifier
            tv.queue_length.add_collect(
                lambda g: g.set(vm.queue_length)
            )
            tv.waves_total.add_collect(lambda g: g.set(vm.waves))
            tv.buckets_dispatched_total.add_collect(
                lambda g: g.set(vm.buckets_dispatched)
            )
            tv.wave_sets_total.add_collect(
                lambda g: g.set(vm.wave_sets_total)
            )
            tv.last_wave_sets.add_collect(
                lambda g: g.set(vm.last_wave_sets)
            )
            tv.last_wave_duration_seconds.add_collect(
                lambda g: g.set(vm.last_wave_duration_s)
            )
            tv.device_time_seconds_total.add_collect(
                lambda g: g.set(vm.total_device_time_s)
            )
            tv.batch_sigs_success_total.add_collect(
                lambda g: g.set(vm.batch_sigs_success)
            )
            tv.batch_retries_total.add_collect(
                lambda g: g.set(vm.batch_retries)
            )
            tv.dispatch_by_bucket_total.add_collect(
                lambda g: [
                    g.set(c, bucket=str(b))
                    for b, c in sorted(
                        vm.snapshot_dispatch()[0].items()
                    )
                ]
            )
            tv.dispatch_by_path_total.add_collect(
                lambda g: [
                    g.set(c, path=p)
                    for p, c in vm.snapshot_dispatch()[1].items()
                ]
            )
            tv.rolling_flush_total.add_collect(
                lambda g: [
                    g.set(c, reason=r)
                    for r, c in vm.rolling_flushes.items()
                ]
            )
            tv.rolling_bucket_sets.add_collect(
                lambda g: g.set(vm.rolling_sets)
            )
            tv.host_invalid_jobs_total.add_collect(
                lambda g: g.set(vm.host_invalid_jobs)
            )
            tv.verify_latency_p50_seconds.add_collect(
                lambda g: g.set(vm.verify_latency.quantile(0.5))
            )
            tv.verify_latency_p99_seconds.add_collect(
                lambda g: g.set(vm.verify_latency.quantile(0.99))
            )
            tv.same_message_latency_p50_seconds.add_collect(
                lambda g: g.set(
                    vm.same_message_latency.quantile(0.5)
                )
            )
            tv.same_message_latency_p99_seconds.add_collect(
                lambda g: g.set(
                    vm.same_message_latency.quantile(0.99)
                )
            )
        # device / XLA compiler telemetry: compile + cache counters,
        # warmup progress, memory, transfers — sampled at scrape from
        # the telemetry singleton (dashboards/lodestar_tpu_device.json)
        from .metrics import device as _dm

        _dm.bind_collectors(
            mm.device,
            node.device_telemetry,
            verifier=node.chain.verifier,
        )
        # kzg / DA MSM backend counters (crypto/kzg.py three tiers)
        from .crypto import kzg as _kzg_metrics

        _kzg_metrics.bind_kzg_collectors(mm.kzg)
        # fork choice / eth1 / light-client server sampled gauges
        mm.forkchoice.nodes.add_collect(
            lambda g: g.set(len(node.chain.fork_choice.proto.nodes))
        )
        mm.forkchoice.indices.add_collect(
            lambda g: g.set(len(node.chain.fork_choice.proto.indices))
        )
        mm.forkchoice.votes.add_collect(
            lambda g: g.set(len(node.chain.fork_choice.votes))
        )
        node.chain.fork_choice.metrics = mm.forkchoice
        if getattr(node.chain, "eth1", None) is not None:
            node.chain.eth1.metrics = mm.eth1
            mm.eth1.deposit_tree_size.add_collect(
                lambda g: g.set(len(node.chain.eth1.tree))
            )
        lcs = node.chain.light_client_server
        if lcs is not None:
            mm.lightclient_server.best_updates.add_collect(
                lambda g: g.set(len(lcs.best_update_by_period))
            )
            mm.lightclient_server.latest_finality_slot.add_collect(
                lambda g: g.set(
                    int(
                        lcs.latest_finality_update.attested_header.beacon.slot
                    )
                    if lcs.latest_finality_update is not None
                    else 0
                )
            )
            mm.lightclient_server.latest_optimistic_slot.add_collect(
                lambda g: g.set(
                    int(
                        lcs.latest_optimistic_update.attested_header.beacon.slot
                    )
                    if lcs.latest_optimistic_update is not None
                    else 0
                )
            )
        if node.reqresp is not None:
            node.reqresp.metrics = mm.reqresp
        mm.clock.slot.add_collect(_wall_slot)
        mm.clock.epoch.add_collect(
            lambda g: g.set(
                max(
                    0,
                    int(
                        (__import__("time").time() - node.chain.genesis_time)
                        // node.cfg.SECONDS_PER_SLOT
                    ),
                )
                // preset().SLOTS_PER_EPOCH
            )
        )
        if node.metrics_port is not None:
            node.metrics_server = MetricsServer(
                node.metrics_registry, port=node.metrics_port
            )
            mport = node.metrics_server.start()
            log.info("metrics listening", {"port": mport})
        head = node.chain.fork_choice.proto.get_node(node.chain.head_root)
        log.info(
            "node ready",
            {
                "head_slot": head.slot if head else 0,
                "finalized_epoch": node.chain.finalized_checkpoint.epoch,
                "validators": len(node.chain.head_state.state.validators),
            },
        )
        return node

    async def _head_check(self, peer_id: str) -> None:
        """Status handshake a fresh peer; range-sync toward its head
        when we're behind (sync.ts head/range mode switch)."""
        try:
            remote = await self.range_sync.status_handshake(peer_id)
            local = self.chain.fork_choice.proto.get_node(
                self.chain.head_root
            )
            local_slot = local.slot if local else 0
            if int(remote.head_slot) > local_slot:
                await self.range_sync.sync_to(int(remote.head_slot))
        except Exception:
            self.network.peer_manager.penalize(
                peer_id, "reqresp error"
            )

    def notify_status(self) -> None:
        """NodeNotifier one-liner (notifier.ts)."""
        head = self.chain.fork_choice.proto.get_node(self.chain.head_root)
        self.log.info(
            "status",
            {
                "slot": head.slot if head else 0,
                "head": self.chain.head_root,
                "finalized": self.chain.finalized_checkpoint.epoch,
                "justified": self.chain.justified_checkpoint.epoch,
                "queue": 0
                if self.processor is None
                else len(self.processor.att_queue),
            },
        )
        c = self.metrics.chain
        c.head_slot.set(head.slot if head else 0)
        c.finalized_epoch.set(self.chain.finalized_checkpoint.epoch)
        c.current_justified_epoch.set(
            self.chain.justified_checkpoint.epoch
        )

    async def _health_probe_loop(self) -> None:
        """Reinstatement driver: while the device path is closed, run
        the maintenance-class known-answer probe on the tracker's
        backoff schedule. The probe blocks on device work, so it runs
        in an executor thread; the tracker itself decides whether a
        probe is due (breaker backoff), this loop only supplies the
        cadence."""
        loop = asyncio.get_running_loop()
        while True:
            await asyncio.sleep(self.health_probe_interval_s)
            tracker = self.health_tracker
            if tracker is None or tracker.device_allowed():
                continue
            try:
                await loop.run_in_executor(None, tracker.maybe_probe)
            except Exception as e:  # the loop must outlive any probe
                self.log.warn(
                    "device health probe loop error", {"err": repr(e)}
                )

    async def close(self) -> None:
        """Reverse-order shutdown (graceful SIGINT path)."""
        if self._drift_task is not None:
            self._drift_task.cancel()
            self._drift_task = None
        if self._probe_task is not None:
            self._probe_task.cancel()
            self._probe_task = None
        if self.health_tracker is not None:
            # detach the module-level health hooks (other nodes or
            # tests in this process must not consult a dead tracker)
            from .bls import kernels as _kernels_health
            from .crypto import kzg as _kzg_health

            _kernels_health.set_health_gate(None)
            _kzg_health.set_health_tracker(None)
            self.health_tracker = None
        if self.executor is not None:
            # detach the module-level hooks FIRST (other nodes or
            # tests in this process must not route through a closed
            # executor), then stop the worker — queued bulk futures
            # cancel and their callers ride the host tiers
            from .bls import kernels as _kernels
            from .crypto import kzg as _kzg_exec

            _kernels.set_maintenance_gate(None)
            _kzg_exec.set_executor(None)
            self.executor.close()
            self.executor = None
        if self.clock is not None:
            self.clock.stop()
        if self.monitoring is not None:
            await self.monitoring.stop()
        if getattr(self, "loop_lag_probe", None) is not None:
            self.loop_lag_probe.stop()
            self.loop_lag_probe = None
        if self.api_server is not None:
            self.api_server.stop()
        if self.metrics_server is not None:
            self.metrics_server.stop()
        if self.network is not None:
            await self.network.stop()
        if self.processor is not None:
            await self.processor.stop()
        if self.chain is not None:
            await self.chain.close()
        if self.db is not None:
            self.db.close()
