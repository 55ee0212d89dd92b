"""Command-line interface.

Reference analog: packages/cli (yargs program, src/cmds/): `beacon`
(run a node from db or genesis), `dev` (instant-genesis local chain
with in-process validators, cli/src/cmds/dev/), `lightclient`, and
`validator` utilities (slashing-protection interchange import/export).

Usage: python -m lodestar_tpu <cmd> [flags]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="lodestar-tpu",
        description="TPU-native Ethereum consensus client",
    )
    p.add_argument(
        "--preset",
        choices=("mainnet", "minimal"),
        default=None,
        help="compile-time preset (defaults to LODESTAR_PRESET env)",
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    dev = sub.add_parser("dev", help="instant-genesis local dev chain")
    dev.add_argument("--validators", type=int, default=32)
    dev.add_argument("--slots", type=int, default=32)
    dev.add_argument("--altair-epoch", type=int, default=2**64 - 1)
    dev.add_argument("--bellatrix-epoch", type=int, default=2**64 - 1)
    dev.add_argument("--db", default=None, help="persist chain to this dir")
    dev.add_argument("--api-port", type=int, default=None)
    dev.add_argument(
        "--api-workers",
        type=int,
        default=16,
        help="REST worker-pool size (api/overload.py admission "
        "control bounds everything else)",
    )
    dev.add_argument("--metrics-port", type=int, default=None)
    dev.add_argument(
        "--real-time",
        action="store_true",
        help="advance with the wall clock instead of as fast as possible",
    )

    beacon = sub.add_parser("beacon", help="run a beacon node from a db")
    beacon.add_argument("--db", required=True)
    beacon.add_argument("--api-port", type=int, default=9596)
    beacon.add_argument(
        "--api-workers",
        type=int,
        default=16,
        help="REST worker-pool size (api/overload.py admission "
        "control bounds everything else)",
    )
    beacon.add_argument("--metrics-port", type=int, default=None)
    beacon.add_argument(
        "--port", type=int, default=None,
        help="TCP listen port for the p2p network (0 = ephemeral; "
        "omit to run without networking)",
    )
    beacon.add_argument("--discovery-port", type=int, default=0)
    beacon.add_argument(
        "--network-core-thread",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="run the wire stack on a dedicated thread "
        "(networkCoreWorker analog; default ON, matching the "
        "reference's useWorker=true — network/options.ts:36; "
        "--no-network-core-thread for in-loop)",
    )
    beacon.add_argument(
        "--bootnodes", default=None,
        help="comma-separated host:udp_port discovery bootstrap list",
    )
    beacon.add_argument(
        "--execution-url", default=None,
        help="engine API endpoint of the execution client",
    )
    beacon.add_argument(
        "--jwt-secret", default=None,
        help="hex file with the engine API JWT secret",
    )
    beacon.add_argument(
        "--builder-url", default=None, help="MEV-boost relay endpoint"
    )
    beacon.add_argument(
        "--trusted-setup", default=None,
        help="KZG trusted setup JSON (ceremony output); dev setup "
        "otherwise",
    )
    beacon.add_argument(
        "--monitoring-endpoint", default=None,
        help="push client-stats to this URL",
    )
    beacon.add_argument(
        "--checkpoint-sync-url", default=None,
        help="trusted beacon API to fetch the finalized anchor state "
        "from on first start (initBeaconState.ts checkpoint sync)",
    )
    beacon.add_argument(
        "--wss-state-root", default=None,
        help="hex weak-subjectivity state root the checkpoint anchor "
        "must match",
    )
    beacon.add_argument(
        "--config", default=None,
        help="chain config JSON for a FRESH db (required with "
        "--checkpoint-sync-url on first start)",
    )
    # -- BLS verifier / continuous-batching knobs ---------------------
    beacon.add_argument(
        "--bls-verifier", choices=("auto", "tpu", "oracle"),
        default="auto",
        help="signature verification backend: 'tpu' runs the batched "
        "device verifier (bls/verifier.py), 'oracle' the single-"
        "threaded host reference; 'auto' picks tpu when a TPU is "
        "attached",
    )
    beacon.add_argument(
        "--bls-ingest-min-bucket", type=int, default=None,
        help="smallest device-ingest-eligible bucket size (default: "
        "LODESTAR_TPU_INGEST_MIN_BUCKET env var, else 256) — smaller "
        "buckets ride the host decompress/hash path",
    )
    beacon.add_argument(
        "--bls-latency-budget-ms", type=int, default=50,
        help="how long the rolling gossip bucket may hold a batchable "
        "job past queue admission before a deadline flush (0 disables "
        "continuous batching)",
    )
    beacon.add_argument(
        "--bls-warmup",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="pre-compile the device-ingest pipeline for every "
        "eligible bucket size on a background thread at start "
        "(persistent-cached; --no-bls-warmup to skip)",
    )
    # -- device auto-tuning (device/autotune.py) ----------------------
    beacon.add_argument(
        "--autotune", choices=("off", "startup", "adaptive"),
        default="off",
        help="device self-tuning: 'startup' micro-benches the limb-"
        "backend x ingest-gate x ladder-top x latency-budget grid "
        "once at init (riding the persistent compile cache) and "
        "applies the winner through the live setters; 'adaptive' "
        "adds the drift monitor that re-tunes (bounded, quiescence-"
        "gated) when a stage departs its COVERAGE.md budget share; "
        "'off' keeps the env/CLI knobs as given",
    )
    beacon.add_argument(
        "--autotune-budget-ms", type=float, default=30_000.0,
        help="wall-clock ceiling for one tune; candidates that do "
        "not fit are skipped (decision source becomes 'partial')",
    )
    beacon.add_argument(
        "--autotune-grid", default=None,
        help="restrict the candidate grid, e.g. "
        "'backend=vpu,mxu;gate=256,512;top=2048;budget=50' "
        "(omitted axes keep their defaults)",
    )
    beacon.add_argument(
        "--autotune-artifact", default="AUTOTUNE.json",
        help="where the tuner records its decision JSON (replayable "
        "by bench.py/tools/bench_* --autotune-from; empty to skip)",
    )
    # -- node-wide device executor (device/executor.py) ---------------
    beacon.add_argument(
        "--device-executor",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="QoS-classed scheduling for every accelerator client: "
        "deadline (gossip verdicts) dispatches ahead of bulk (blob "
        "batches) at every wave boundary, maintenance (warmup / "
        "autotune probes) yields to deadline and ages past bulk, "
        "bounded per-class queues shed bulk/maintenance under "
        "overload (lodestar_device_sheds_total); "
        "--no-device-executor restores ad-hoc contention",
    )
    beacon.add_argument(
        "--executor-bulk-queue", type=int, default=64,
        help="bulk-class admission bound: queued KZG/blob device "
        "jobs beyond this are shed to their host fallback tier",
    )
    beacon.add_argument(
        "--executor-maintenance-queue", type=int, default=32,
        help="maintenance-class admission bound (warmup compiles, "
        "autotune probes)",
    )
    beacon.add_argument(
        "--executor-aging-ms", type=float, default=2000.0,
        help="a queued maintenance job runs no later than this even "
        "under continuous bulk pressure (anti-starvation)",
    )
    # -- device fault domain (device/health.py) -----------------------
    beacon.add_argument(
        "--device-health",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="device fault domain: wave watchdog (deadlines derived "
        "from the fused stage budget, armed on real accelerators), "
        "error taxonomy (OOM shrinks the bucket ladder before "
        "quarantining; compile failures quarantine one stage "
        "program; device-lost quarantines the device), node-wide "
        "host failover with bit-identical verdicts, and live "
        "reinstatement via known-answer probes; "
        "--no-device-health leaves device errors to their callers",
    )
    beacon.add_argument(
        "--health-probe-interval-s", type=float, default=5.0,
        help="cadence of the reinstatement probe loop while the "
        "device is quarantined (the tracker's exponential backoff "
        "decides which ticks actually probe)",
    )
    # -- observability knobs ------------------------------------------
    beacon.add_argument(
        "--monitored-validators", default=None,
        help="comma-separated validator indices the validator monitor "
        "tracks (inclusion distance, head/target correctness, "
        "sync-committee hit/miss, proposals)",
    )
    beacon.add_argument(
        "--trace-slow-slot-ms", type=float, default=500.0,
        help="block imports slower than this land in the slow-trace "
        "ring buffer served by /eth/v1/lodestar/block_import_traces "
        "(0 records every import)",
    )
    beacon.add_argument(
        "--trace-buffer-size", type=int, default=64,
        help="how many slow block-import traces the ring buffer keeps",
    )
    beacon.add_argument(
        "--device-timing", choices=("off", "dispatch", "sync"),
        default="dispatch",
        help="device telemetry depth (metrics/device.py): 'dispatch' "
        "times stage calls and attributes XLA compiles/retraces; "
        "'sync' adds per-stage dispatch-to-ready deltas via "
        "block_until_ready (serializes the host against each stage — "
        "debugging only); 'off' disables the kernel hooks",
    )
    beacon.add_argument(
        "--device-trace-max-ms", type=float, default=5000.0,
        help="upper bound a POST /eth/v1/lodestar/device_trace capture "
        "may request (jax.profiler runs for the requested window; one "
        "capture at a time)",
    )
    beacon.add_argument(
        "--device-trace-dir", default=None,
        help="directory for on-demand device trace captures (default: "
        "a fresh temp dir per capture)",
    )

    lc = sub.add_parser(
        "lightclient",
        help="run a light client against a beacon REST endpoint",
    )
    lc.add_argument(
        "--beacon-api-url", required=True,
        help="beacon node REST endpoint to sync from",
    )
    lc.add_argument(
        "--checkpoint-root", required=True,
        help="trusted finalized block root (0x..) for bootstrap",
    )
    lc.add_argument(
        "--poll-seconds", type=float, default=12.0,
        help="finality/optimistic update poll interval",
    )
    lc.add_argument(
        "--max-polls", type=int, default=0,
        help="exit after N polls (0 = run forever)",
    )

    bn = sub.add_parser(
        "bootnode",
        help="run a standalone discovery bootnode (no chain)",
    )
    bn.add_argument("--discovery-port", type=int, default=9000)
    bn.add_argument(
        "--max-seconds", type=float, default=0,
        help="exit after this long (0 = run forever)",
    )

    vc = sub.add_parser("validator", help="validator client utilities")
    vc.add_argument(
        "--vc-db",
        required=True,
        help="validator client database file (signing history)",
    )
    vcsub = vc.add_subparsers(dest="vc_cmd", required=True)
    imp = vcsub.add_parser(
        "slashing-protection-import", help="import EIP-3076 interchange"
    )
    imp.add_argument("file")
    exp = vcsub.add_parser(
        "slashing-protection-export", help="export EIP-3076 interchange"
    )
    exp.add_argument("file")
    return p


def _set_preset(name: str | None) -> None:
    if name:
        import os

        os.environ["LODESTAR_PRESET"] = name


async def _run_dev(args) -> int:
    from .chain.devnode import DevNode
    from .config.chain_config import ChainConfig
    from .db.beacon import BeaconDb
    from .logger import get_logger
    from .types import ssz_types

    log = get_logger("dev")
    FAR = 2**64 - 1
    cfg = ChainConfig(
        ALTAIR_FORK_EPOCH=args.altair_epoch,
        BELLATRIX_FORK_EPOCH=args.bellatrix_epoch,
        CAPELLA_FORK_EPOCH=FAR,
        DENEB_FORK_EPOCH=FAR,
        ELECTRA_FORK_EPOCH=FAR,
        SHARD_COMMITTEE_PERIOD=0,
    )
    types = ssz_types()
    db = BeaconDb.open(args.db, types) if args.db else None
    node = DevNode(
        cfg, types, args.validators, verify_attestations=False, db=db
    )
    api_server = None
    if args.api_port is not None:
        from .api.impl import BeaconApiImpl
        from .api.overload import ServingOverload
        from .api.server import BeaconRestApiServer

        impl = BeaconApiImpl(cfg, types, node.chain)
        overload = ServingOverload(pool_workers=args.api_workers)
        overload.cache.attach(node.chain.events)
        api_server = BeaconRestApiServer(
            impl,
            port=args.api_port,
            loop=asyncio.get_event_loop(),
            overload=overload,
        )
        log.info("rest api", {"port": api_server.start()})
    metrics_server = None
    if args.metrics_port is not None:
        from .metrics import MetricsServer, RegistryMetricCreator

        reg = RegistryMetricCreator()
        metrics_server = MetricsServer(reg, port=args.metrics_port)
        log.info("metrics", {"port": metrics_server.start()})
    for s in range(1, args.slots + 1):
        if args.real_time:
            await asyncio.sleep(cfg.SECONDS_PER_SLOT)
        root = await node.advance_slot()
        log.info(
            "slot advanced",
            {
                "slot": node.slot,
                "root": root,
                "justified": node.chain.justified_checkpoint.epoch,
                "finalized": node.chain.finalized_checkpoint.epoch,
            },
        )
    log.info(
        "dev chain done",
        {
            "head_slot": node.slot,
            "finalized_epoch": node.chain.finalized_checkpoint.epoch,
        },
    )
    if api_server is not None:
        api_server.stop()
    if metrics_server is not None:
        metrics_server.stop()
    await node.close()
    if db is not None:
        db.controller.flush()
        db.close()
    return 0


async def _run_beacon(args) -> int:
    from .config.chain_config import chain_config_from_json
    from .db.beacon import BeaconDb
    from .node import BeaconNode
    from .types import ssz_types

    types = ssz_types()
    db = BeaconDb.open(args.db, types)
    # the db records the config it was created with (fork schedule must
    # match or state/block SSZ decode goes wrong)
    raw_cfg = db.meta.get_raw("chain_config")
    if raw_cfg is None:
        if args.config:
            from pathlib import Path

            from .config.chain_config import chain_config_to_json

            cfg = chain_config_from_json(Path(args.config).read_text())
            db.meta.put_raw(
                "chain_config", chain_config_to_json(cfg).encode()
            )
        else:
            print(
                "error: db has no chain_config metadata "
                "(pass --config for a fresh db)",
                file=sys.stderr,
            )
            return 1
    else:
        cfg = chain_config_from_json(raw_cfg.decode())
    jwt_secret = None
    if args.jwt_secret:
        from pathlib import Path

        jwt_secret = bytes.fromhex(
            Path(args.jwt_secret).read_text().strip().removeprefix("0x")
        )
    bootnodes = []
    if args.bootnodes:
        for entry in args.bootnodes.split(","):
            host, _, port = entry.strip().rpartition(":")
            bootnodes.append((host, int(port)))
    # BLS verifier selection: the TPU service when a device is
    # attached (or forced), else the host oracle
    verifier = None
    mode = args.bls_verifier
    if mode == "auto":
        from .logger import get_logger
        from .utils import chip

        mode = "tpu" if chip.on_tpu() else "oracle"
        get_logger("cli").warn(
            "--bls-verifier auto chose a verifier", {"verifier": mode}
        )
    if mode == "tpu":
        from .bls import TpuBlsVerifier
        from .bls import kernels as _bls_kernels

        if args.bls_ingest_min_bucket is not None:
            _bls_kernels.set_ingest_min_bucket(
                args.bls_ingest_min_bucket
            )
        # warmup is started by BeaconNode.init (after the chain
        # exists) so the node controls its lifecycle; the cold-compile
        # host fallback is left unset so start_warmup picks the policy
        # that fits the topology (on for single-device warmup, off for
        # mesh verifiers where an unsharded warmup can't pre-compile
        # the sharded programs)
        verifier = TpuBlsVerifier(
            latency_budget_ms=args.bls_latency_budget_ms,
        )
    node = await BeaconNode.init(
        cfg=cfg,
        types=types,
        db=db,
        api_port=args.api_port,
        api_workers=args.api_workers,
        metrics_port=args.metrics_port,
        tcp_port=args.port,
        udp_port=args.discovery_port,
        network_isolated=getattr(args, "network_core_thread", True),
        bootnodes=bootnodes,
        execution_url=args.execution_url,
        jwt_secret=jwt_secret,
        builder_url=args.builder_url,
        trusted_setup_path=args.trusted_setup,
        monitoring_endpoint=args.monitoring_endpoint,
        checkpoint_sync_url=args.checkpoint_sync_url,
        wss_state_root=(
            bytes.fromhex(args.wss_state_root.removeprefix("0x"))
            if args.wss_state_root
            else None
        ),
        verifier=verifier,
        bls_warmup=args.bls_warmup,
        monitored_validators=(
            [
                int(i)
                for i in args.monitored_validators.split(",")
                if i.strip()
            ]
            if args.monitored_validators
            else None
        ),
        trace_slow_slot_ms=args.trace_slow_slot_ms,
        trace_buffer_size=args.trace_buffer_size,
        device_timing=args.device_timing,
        device_trace_max_ms=args.device_trace_max_ms,
        device_trace_dir=args.device_trace_dir,
        autotune=args.autotune,
        autotune_budget_ms=args.autotune_budget_ms,
        autotune_grid=args.autotune_grid,
        autotune_artifact=args.autotune_artifact or None,
        device_executor=args.device_executor,
        executor_bulk_queue=args.executor_bulk_queue,
        executor_maintenance_queue=args.executor_maintenance_queue,
        executor_aging_ms=args.executor_aging_ms,
        device_health=args.device_health,
        health_probe_interval_s=args.health_probe_interval_s,
    )
    node.notify_status()
    try:
        while True:
            await asyncio.sleep(cfg.SECONDS_PER_SLOT)
            node.notify_status()
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        await node.close()
    return 0


def _run_validator(args) -> int:
    import os

    from .validator import SlashingProtection

    # the VC db IS an interchange-format JSON file (persistent store)
    sp = SlashingProtection()
    if os.path.exists(args.vc_db):
        with open(args.vc_db) as f:
            sp.import_interchange(f.read())
    if args.vc_cmd == "slashing-protection-import":
        with open(args.file) as f:
            n = sp.import_interchange(f.read())
        with open(args.vc_db, "w") as f:
            json.dump(sp.export_interchange(), f, indent=2)
        print(f"imported {n} records into {args.vc_db}")
        return 0
    if args.vc_cmd == "slashing-protection-export":
        with open(args.file, "w") as f:
            json.dump(sp.export_interchange(), f, indent=2)
        print(f"wrote {args.file}")
        return 0
    return 1


async def _run_lightclient(args) -> int:
    """Bootstrap from a trusted root over REST, then follow finality /
    optimistic updates (reference: packages/light-client running
    against the beacon API transport)."""
    from .api.client import ApiClient
    from .api.json_codec import from_json
    from .config.beacon_config import BeaconConfig
    from .config.chain_config import ChainConfig
    from .lightclient import LightClient
    from .logger import get_logger

    log = get_logger("lightclient")
    client = ApiClient(args.beacon_api_url)
    loop = asyncio.get_running_loop()

    def call(op, params=None):
        return client.call(op, params)

    genesis = await loop.run_in_executor(None, call, "getGenesis")
    gvr = bytes.fromhex(
        genesis["genesis_validators_root"].removeprefix("0x")
    )
    # fork schedule from the endpoint: domains must match the serving
    # chain, not this host's defaults
    spec = await loop.run_in_executor(None, call, "getSpec")
    cfg = ChainConfig(
        **{
            k: int(spec[k])
            for k in (
                "ALTAIR_FORK_EPOCH",
                "BELLATRIX_FORK_EPOCH",
                "CAPELLA_FORK_EPOCH",
                "DENEB_FORK_EPOCH",
                "ELECTRA_FORK_EPOCH",
            )
            if k in spec
        }
    )
    bc = BeaconConfig(cfg, gvr)
    from .types import ssz_types

    types = ssz_types()
    root = args.checkpoint_root.removeprefix("0x")
    boot_json = await loop.run_in_executor(
        None, call, "getLightClientBootstrap", {"block_root": "0x" + root}
    )
    bootstrap = from_json(types.LightClientBootstrap, boot_json)
    lc = LightClient(bc, types, bootstrap, bytes.fromhex(root))
    log.info(
        "light client bootstrapped",
        {"slot": int(bootstrap.header.beacon.slot)},
    )
    def _to_full(u, has_finality: bool):
        # process_update consumes full LightClientUpdate shapes; wrap
        # finality/optimistic updates with empty committee fields
        full = types.LightClientUpdate.default()
        full.attested_header = u.attested_header
        full.sync_aggregate = u.sync_aggregate
        full.signature_slot = u.signature_slot
        if has_finality:
            full.finalized_header = u.finalized_header
            full.finality_branch = u.finality_branch
        return full

    polls = 0
    applied = 0
    while args.max_polls == 0 or polls < args.max_polls:
        for op, t, fin in (
            (
                "getLightClientFinalityUpdate",
                types.LightClientFinalityUpdate,
                True,
            ),
            (
                "getLightClientOptimisticUpdate",
                types.LightClientOptimisticUpdate,
                False,
            ),
        ):
            try:
                upd = await loop.run_in_executor(None, call, op)
                lc.process_update(_to_full(from_json(t, upd), fin))
                applied += 1
                log.info(
                    "update applied",
                    {
                        "op": op,
                        "head_slot": int(
                            lc.optimistic_header.beacon.slot
                        ),
                    },
                )
            except Exception as e:
                log.warn("update poll failed", {"op": op, "err": repr(e)})
        polls += 1
        await asyncio.sleep(args.poll_seconds)
    # bounded runs report failure when NO update ever applied — a
    # wrong fork schedule or dead endpoint must not exit 0
    return 0 if applied else 1


async def _run_bootnode(args) -> int:
    """Discovery-only node: answers FINDNODE walks so fresh nodes can
    bootstrap peer discovery (reference: the standalone bootnode cmd,
    cli/src/cmds/bootnode)."""
    from .network.discovery import Discovery, NodeRecord
    from .logger import get_logger

    log = get_logger("bootnode")
    disc = Discovery(
        NodeRecord(
            peer_id="bootnode",
            host="0.0.0.0",
            tcp_port=0,
            udp_port=args.discovery_port,
            fork_digest="00000000",
        )
    )
    await disc.listen()
    log.info("bootnode listening", {"udp": args.discovery_port})
    import time as _t

    t0 = _t.time()
    try:
        while not args.max_seconds or _t.time() - t0 < args.max_seconds:
            await asyncio.sleep(1.0)
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    await disc.close()
    return 0


def main(argv=None) -> int:
    args = _build_parser().parse_args(argv)
    _set_preset(args.preset)
    if args.cmd == "dev":
        return asyncio.run(_run_dev(args))
    if args.cmd == "beacon":
        return asyncio.run(_run_beacon(args))
    if args.cmd == "validator":
        return _run_validator(args)
    if args.cmd == "lightclient":
        return asyncio.run(_run_lightclient(args))
    if args.cmd == "bootnode":
        return asyncio.run(_run_bootnode(args))
    return 1


if __name__ == "__main__":
    sys.exit(main())
