"""TPU-backed BLS verifier service — the reference's north-star seam.

Reference analog: `IBlsVerifier` + `BlsMultiThreadWorkerPool`
(chain/bls/interface.ts:25-68, chain/bls/multithread/index.ts:113,
SURVEY.md §2.3). The pool's contract is kept exactly:

  - `verify_signature_sets(sets, batchable, priority)` — batchable sets
    are buffered up to MAX_BUFFER_WAIT_MS / MAX_BUFFERED_SIGS and merged
    with other callers' work (index.ts:59-74, 320-339); a failed batch
    is re-verified job-by-job then set-by-set so one bad signature only
    fails its own caller (interface.ts:4-12, worker.ts:88-103).
  - `verify_signature_sets_same_message(sets, message)` — random-
    weighted aggregation + one pairing check; on failure, per-signature
    retry fan-out (jobItem.ts:96-125, index.ts:552-563).
  - `can_accept_work()` — backpressure for the gossip processor
    (index.ts:149-155, network/processor/index.ts).

What changes vs the reference's worker pool (index.ts:183-199,
519-534): instead of ≤128-set chunks round-robined to N-1 CPU threads,
each drain of the queue becomes a WAVE — every queued job's sets packed
into device buckets of up to DEVICE_BUCKET_MAX (per-op device cost is
batch-flat to ~2048, so big buckets are nearly free), all buckets
dispatched asynchronously, and ONE stacked verdict readback per wave.
Host-side set preparation (decompression, hash-to-G2 — C calls that
release the GIL) runs on a thread pool and overlaps the device's
execution of the previous wave. With more than one device the
bucket batch axis is sharded over a `jax.sharding.Mesh`
(lodestar_tpu/parallel) — the SPMD replacement for the reference's
worker fan-out.

CONTINUOUS BATCHING (the small-bucket gossip path): bulk waves pack
themselves, but the production steady state is trickle traffic —
single aggregates and 32-sig buffer flushes. Those small batchable
jobs accumulate in a ROLLING device bucket shared across waves
(inference-server discipline: coalesce until the batch is worth the
hardware) and flush when the bucket reaches the device-ingest gate,
when non-batchable work dispatches anyway, or when the oldest job's
latency budget (default 50 ms past queue admission, on top of the
100 ms gossip buffer) expires — so trickle coalesces into
device-ingest-sized buckets without unbounded latency. Dispatches are
counted per bucket size and per path (ingest / host / host_cold), and
submit-to-verdict latency feeds p50/p99 histograms on /metrics.

OVERLAPPED WAVE PIPELINE (ISSUE 16): waves are DOUBLE-BUFFERED — each
wave's host prep + dispatch runs as its own task, so while wave N
executes on the device, wave N+1's prep (decompression, padding, limb
packing) runs on the thread pool and its dispatch queues behind N via
JAX async dispatch (donated input buffers on TPU let XLA reuse wave
N's freed device memory). The pipeline depth is a knob
(`pipeline_depth` / LODESTAR_TPU_PIPELINE_DEPTH, default 2; 1 = the
pre-pipeline synchronous behavior), `is_quiescent`/`close()` extend
over the prefetch window so autotune re-tunes and shutdown cannot
race an in-flight prep, and occupancy (fraction of wall time with ≥1
wave in flight) plus prep-overlap-hidden seconds are exported on
/metrics.
"""

from __future__ import annotations

import asyncio
import contextlib
import os
import secrets
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import jax.numpy as jnp

from ..crypto.bls import curve as oc

# The latency histogram became an executor-level primitive when the
# node-wide QoS scheduler landed (device/executor.py tracks per-class
# submit-to-completion latency with the same class); re-exported here
# because the verifier's own histograms predate it and tests/tools
# reach it as verifier.LatencyHistogram.
from ..device.executor import LatencyHistogram  # noqa: F401
from ..device.health import classify_device_error, watchdog_deadline_s
from ..metrics import device as _device
from ..ops import curve as C
from . import api, kernels

MAX_BUFFER_WAIT_MS = 100  # index.ts:74
MAX_BUFFERED_SIGS = 32  # index.ts:65
MAX_SIGNATURE_SETS_PER_JOB = 128  # index.ts:56 (job granularity for retries)
DEVICE_BUCKET_MAX = 2048  # sets per device dispatch (batch-flat cost)
QUEUE_MAX_LENGTH = 512  # canAcceptWork threshold, index.ts:149-155
# Continuous batching: small batchable jobs accumulate in a ROLLING
# device bucket shared across waves, flushed when it reaches the
# device-ingest gate or when the oldest job has waited this long past
# its queue admission — ~50 ms on top of the 100 ms gossip buffer.
LATENCY_BUDGET_MS = 50
# Overlapped wave pipeline: how many waves may be in the prep+dispatch
# window at once. Depth d admits wave N+1's host prep while wave N
# still executes on device; 1 restores the synchronous pre-pipeline
# behavior (prep of N+1 starts only after N is dispatched).
PIPELINE_DEPTH = int(os.environ.get("LODESTAR_TPU_PIPELINE_DEPTH", "2"))


def _rand_scalars(n: int):
    """Nonzero 64-bit blinding scalars (blst batch-verify width)."""
    return [secrets.randbits(kernels.RAND_BITS) | 1 for _ in range(n)]


_PAD_CACHE: list = []


def _pad_prepared() -> "_PreparedSet":
    """A valid prepared set used for bucket padding (mask False slots
    still flow through the device sqrt/ladder chains)."""
    if not _PAD_CACHE:
        from ..crypto.bls.signature import sign, sk_to_pk

        msg = b"\x5a" * 32
        sig = sign(7, msg)
        xc0, xc1, s, ok = api.parse_signature(sig)
        assert ok
        _PAD_CACHE.append(
            _PreparedSet(
                api.decompress_pubkey(sk_to_pk(7)),
                (xc0, xc1),
                s,
                api.message_draws(msg),
            )
        )
    return _PAD_CACHE[0]


@dataclass
class _PreparedSet:
    """Host-light prepared set for DEVICE ingestion: the pubkey is
    decompressed on host (cached — validators recur), but signatures
    stay as compressed x-coordinates and messages as hash_to_field
    draws; sqrt/subgroup/SSWU run batched on the TPU
    (kernels.run_verify_batch_ingest_async, composing the
    _stage_g2_* and _stage_sswu_*/_stage_cofactor sub-stages)."""

    pk: tuple  # affine G1 ints (cache-decompressed)
    sig_x: tuple  # (xc0, xc1) compressed signature x
    sig_sign: bool
    draws: tuple  # (u0, u1) Fq2 hash_to_field draws
    sig_raw: bytes = b""  # compressed bytes (small-bucket host path)
    msg_raw: bytes = b""  # signing root (small-bucket host path)


@dataclass
class _Job:
    sets: list  # raw api.SignatureSet list
    future: asyncio.Future
    batchable: bool
    created_at: float = 0.0  # caller submit time (latency histogram)
    enqueued_at: float = 0.0
    prepared: list | None = None
    # dispatch-to-readback time of the wave that carried this job's
    # verdict — grafted under the caller's bls_verify_job span as a
    # backdated device child (metrics/tracing.attach_completed_span)
    device_s: float = 0.0


# -- host-oracle failover verdicts (device/health.py fault domain) -----
# Exact per-set pairing checks — the same math OracleBlsVerifier runs,
# so verdicts are bit-identical to the device path (the differential
# suite proves all three agree). Used while the device is quarantined;
# they deliberately touch NO jax arrays (jnp.asarray on a sick TPU
# could hang the failover itself).


def _host_oracle_sets(sets) -> bool:
    """Verdict for raw api.SignatureSet items (a _Job's .sets)."""
    from ..crypto.bls import pairing as op

    try:
        for s in sets:
            pk = api.decompress_pubkey(s.pubkey)
            h = api.message_to_g2(s.message)
            sig = api.decompress_signature(s.signature)
            if sig is None:
                return False
            if not op.pairing_product_is_one(
                [(pk, h), (oc.g1_neg(oc.G1_GEN), sig)]
            ):
                return False
        return True
    except api.InvalidPointError:
        return False


def _host_oracle_prepared(sets) -> bool:
    """Verdict for _PreparedSet items (a packed bucket): the pubkey is
    already decompressed; signature/message reconstruct from the raw
    bytes (or the parsed x-coordinate / hash draws)."""
    from ..crypto.bls import pairing as op

    try:
        for s in sets:
            sig = (
                api.decompress_signature(s.sig_raw)
                if s.sig_raw
                else api.decompress_signature_parsed(
                    s.sig_x, s.sig_sign
                )
            )
            if sig is None:
                return False
            h = (
                api.message_to_g2(s.msg_raw)
                if s.msg_raw
                else api.draws_to_g2(s.draws)
            )
            if not op.pairing_product_is_one(
                [(s.pk, h), (oc.g1_neg(oc.G1_GEN), sig)]
            ):
                return False
        return True
    except api.InvalidPointError:
        return False


def _host_oracle_same_message(pairs, h) -> bool:
    """Verdict for same-message (pk, sig_x, sig_sign) triples against
    one already-hashed G2 point h."""
    from ..crypto.bls import pairing as op

    try:
        for pk, sx, sg in pairs:
            sig = api.decompress_signature_parsed(sx, sg)
            if sig is None:
                return False
            if not op.pairing_product_is_one(
                [(pk, h), (oc.g1_neg(oc.G1_GEN), sig)]
            ):
                return False
        return True
    except api.InvalidPointError:
        return False


class BlsVerifierMetrics:
    """Counter names mirror lodestar_bls_thread_pool_* so the reference
    Grafana dashboard maps 1:1 (metrics/metrics/lodestar.ts:403-506)."""

    def __init__(self):
        self.job_groups_started = 0
        self.jobs_started = 0
        self.sig_sets_started = 0
        self.batch_retries = 0
        self.batch_sigs_success = 0
        self.same_message_retries = 0
        self.queue_length = 0
        self.total_job_wait_s = 0.0
        self.total_device_time_s = 0.0
        self.waves = 0
        self.buckets_dispatched = 0
        # last-wave stats for the TPU verifier dashboard
        self.last_wave_sets = 0
        self.last_wave_duration_s = 0.0
        self.wave_sets_total = 0
        # overlapped pipeline: host prep seconds that ran while
        # another wave was already in flight — work the pipeline hid
        # behind device execution instead of serializing ahead of it
        self.prep_overlap_hidden_s = 0.0
        # continuous batching: per-bucket-size device dispatches, path
        # split (device ingest vs host decompress/hash vs cold-compile
        # host fallback), rolling-bucket flush triggers, and the
        # submit-to-verdict latency histogram
        self.dispatch_by_bucket: dict[int, int] = {}
        self.dispatch_by_path = {
            "ingest": 0,
            "host": 0,
            "host_cold": 0,
            # device path quarantined (device/health.py): the bucket
            # rode the bit-identical host oracle instead
            "failover": 0,
        }
        # dispatches count from executor threads; scrapes copy under
        # the same lock so iteration never races an insertion
        self.dispatch_lock = threading.Lock()
        self.rolling_flushes = {"full": 0, "deadline": 0, "merged": 0}
        self.rolling_sets = 0  # current rolling-bucket occupancy
        self.host_invalid_jobs = 0
        self.verify_latency = LatencyHistogram()
        self.same_message_latency = LatencyHistogram()

    def snapshot_dispatch(self) -> tuple[dict[int, int], dict[str, int]]:
        """Copies of the dispatch counters taken under the dispatch
        lock, so a scrape never iterates a dict an executor thread is
        inserting into."""
        with self.dispatch_lock:
            return dict(self.dispatch_by_bucket), dict(self.dispatch_by_path)


class TpuBlsVerifier:
    """`IBlsVerifier` over TPU pairing kernels.

    mesh: None = auto (make a Mesh over all local devices when more
    than one is visible); pass an explicit `jax.sharding.Mesh` to pin
    (tests use the 8-device CPU mesh), or `False` to force single-device.
    """

    def __init__(
        self,
        max_buffer_wait_ms: int = MAX_BUFFER_WAIT_MS,
        max_buffered_sigs: int = MAX_BUFFERED_SIGS,
        queue_max: int = QUEUE_MAX_LENGTH,
        mesh=None,
        prep_workers: int | None = None,
        ingest_min_bucket: int | None = None,
        latency_budget_ms: int = LATENCY_BUDGET_MS,
        warmup: bool = False,
        host_fallback_when_cold: bool | None = None,
        pipeline_depth: int | None = None,
    ):
        """Continuous-batching knobs:

        ingest_min_bucket: device-ingest gate override (None = the
          kernels module knob / LODESTAR_TPU_INGEST_MIN_BUCKET).
        latency_budget_ms: how long the rolling gossip bucket may hold
          a batchable job past queue admission before a deadline flush
          (0 disables the rolling bucket — every wave dispatches
          immediately, the pre-round-6 behavior).
        pipeline_depth: overlapped-wave pipeline depth (None = the
          LODESTAR_TPU_PIPELINE_DEPTH env default, 2). Depth d lets
          up to d-1 waves prep/dispatch ahead of the wave executing
          on device; 1 = synchronous pre-pipeline behavior.
        warmup: pre-compile the ingest pipeline for every eligible
          bucket size on a background thread (node start).
        host_fallback_when_cold: route ingest-eligible buckets to the
          host decompress/hash path while their XLA compile is still
          cold (default: enabled iff warmup is — tests and benches
          without warmup keep the deterministic device path).
        """
        self.metrics = BlsVerifierMetrics()
        self._max_wait = max_buffer_wait_ms / 1000.0
        self._max_buffered = max_buffered_sigs
        self._max_sets_per_job = MAX_SIGNATURE_SETS_PER_JOB
        self._queue_max = queue_max
        self._ingest_min = ingest_min_bucket
        self._latency_budget = latency_budget_ms / 1000.0
        # None = unset: follows warmup, and start_warmup() (the node
        # calls it on any verifier it's handed) turns the fallback on
        # so in-flight compiles never stall a live wave. An explicit
        # True/False always wins.
        self._cold_fallback_explicit = host_fallback_when_cold
        self._cold_fallback = (
            warmup
            if host_fallback_when_cold is None
            else host_fallback_when_cold
        )
        self._pipeline_depth = max(
            1,
            int(
                pipeline_depth
                if pipeline_depth is not None
                else PIPELINE_DEPTH
            ),
        )
        self._rolling: list[_Job] = []
        self._rolling_sets = 0
        self._rolling_task: asyncio.Task | None = None
        self._dispatching = 0  # waves between job pop and finalizer
        # overlapped pipeline: in-flight prep+dispatch tasks, and the
        # occupancy clock (cumulative seconds with >=1 wave in flight)
        self._wave_tasks: set[asyncio.Task] = set()
        self._born = time.monotonic()
        self._busy_since: float | None = None
        self._busy_total = 0.0
        self._intake_held = 0  # hold_intake() nesting depth
        self._buffer: list[_Job] = []
        self._buffer_task: asyncio.Task | None = None
        # priority queue: (priority_class, seq) keeps FIFO within class;
        # priority jobs jump the queue (reference jobs.unshift,
        # chain/bls/interface.ts:19-22)
        self._queue: asyncio.PriorityQueue = asyncio.PriorityQueue()
        self._seq = 0
        self._runner: asyncio.Task | None = None
        self._finalizers: set[asyncio.Task] = set()
        self._closed = False
        self._executor = None  # node DeviceExecutor (attach_executor)
        # device fault domain (device/health.py, attach_health):
        # while the tracker quarantines the device every bucket rides
        # the bit-identical host oracle, and — when a wave timeout is
        # armed — a wave stuck on a hung device fails over instead of
        # leaving its verdict futures pending forever
        self._health = None
        self._wave_timeout_s: float | None = None
        self._log = None
        if mesh is None:
            import jax

            devs = jax.devices()
            if len(devs) > 1:
                from .. import parallel

                mesh = parallel.make_mesh()
            else:
                mesh = False
        self._mesh = mesh or None
        # Host prep (decompression + hash-to-G2) is ctypes C that
        # releases the GIL — a pool genuinely parallelizes it across
        # cores and overlaps it with device execution.
        self._prep_pool = ThreadPoolExecutor(
            prep_workers
            if prep_workers is not None
            else min(8, os.cpu_count() or 4),
            thread_name_prefix="bls-prep",
        )
        if warmup:
            self.start_warmup()

    # -- continuous-batching configuration -----------------------------

    def _ingest_gate(self) -> int:
        """The live device-ingest bucket gate."""
        return (
            self._ingest_min
            if self._ingest_min is not None
            else kernels.ingest_min_bucket()
        )

    def _bucket_cap(self) -> int:
        """Sets per device dispatch: the ladder's live top rung,
        bounded by the hard DEVICE_BUCKET_MAX. Reads the ladder every
        wave so a set_ladder_top() retune (device/autotune.py) applies
        to the next packing without a restart."""
        return min(DEVICE_BUCKET_MAX, kernels.ladder_top())

    def set_latency_budget_ms(self, ms: float) -> None:
        """Live retune of the rolling-bucket latency budget (the
        autotuner's fourth knob). Applies to the NEXT deadline arming;
        an already-armed deadline keeps its schedule — the budget is
        an upper bound on added wait, and rescheduling mid-flight
        could extend a promise already made to a queued job."""
        self._latency_budget = max(0.0, float(ms)) / 1000.0

    def latency_budget_ms(self) -> float:
        return self._latency_budget * 1000.0

    def set_pipeline_depth(self, depth: int) -> None:
        """Live retune of the overlapped-pipeline depth (autotune's
        fifth knob). Applies to the NEXT wave admission; waves already
        in the prefetch window keep their slot."""
        self._pipeline_depth = max(1, int(depth))

    def pipeline_depth(self) -> int:
        return self._pipeline_depth

    # -- overlapped-pipeline bookkeeping -------------------------------

    def _inflight(self) -> int:
        """Waves anywhere in the pipeline: prepping/dispatching
        (_wave_tasks) or on device awaiting readback (_finalizers)."""
        return len(self._wave_tasks) + len(self._finalizers)

    def _occupancy_mark(self) -> None:
        """Record a possible busy/idle transition of the pipeline.
        Called whenever _wave_tasks/_finalizers membership changes;
        the event loop is single-threaded, so no lock is needed."""
        now = time.monotonic()
        if self._inflight() > 0:
            if self._busy_since is None:
                self._busy_since = now
        elif self._busy_since is not None:
            self._busy_total += now - self._busy_since
            self._busy_since = None

    def pipeline_occupancy(self) -> float:
        """Fraction of this verifier's wall time with >=1 wave in
        flight (lodestar_jax_pipeline_occupancy). High occupancy with
        depth >= 2 means the overlap is keeping the device fed."""
        now = time.monotonic()
        total = now - self._born
        if total <= 0.0:
            return 0.0
        busy = self._busy_total
        if self._busy_since is not None:
            busy += now - self._busy_since
        return min(1.0, busy / total)

    def is_quiescent(self) -> bool:
        """No queued, buffered, rolling, or in-flight work — the gate
        the drift monitor (device/autotune.py) requires before a
        re-tune may touch live knobs (a backend switch mid-wave would
        drop the very traces the wave is executing). `_dispatching`
        covers the prep-and-dispatch window: jobs are already popped
        from the queue but the finalizer task is not yet registered,
        so none of the other indicators would show the wave.
        `_wave_tasks` covers the overlapped pipeline's PREFETCH window
        (ISSUE 16 quiescence bugfix): a wave whose prep is running as
        a pipeline task is invisible to `_dispatching` once
        _dispatch_wave has returned, and a re-tune that cleared jit
        caches mid-prefetch would recompile — or worse, retune knobs
        — under a wave that already sampled them."""
        return (
            self._dispatching == 0
            and self._queue.empty()
            and not self._buffer
            and not self._rolling
            and not self._wave_tasks
            and not self._finalizers
        )

    def has_pending_deadline_work(self) -> bool:
        """Deadline work WAITING for the device: queued/buffered/
        rolling jobs, or a wave inside the prep-and-dispatch window.
        This is the executor's deadline probe — while True, the
        executor defers bulk/maintenance picks so the next wave
        boundary belongs to gossip verdicts. Deliberately narrower
        than `not is_quiescent()`: a wave already EXECUTING on device
        (`_finalizers`) does not defer bulk — the chip is busy either
        way, and deferring on in-flight waves would starve blob
        batches under any sustained gossip."""
        return bool(
            self._dispatching
            or not self._queue.empty()
            or self._buffer
            or self._rolling
            or self._wave_tasks
        )

    def attach_executor(self, executor) -> None:
        """Join the node-wide DeviceExecutor (device/executor.py) as
        its deadline-class client: register the pending-work and
        quiescence probes, and gate this verifier's intake on the
        executor's (a drain closes can_accept_work here with no
        hold_intake call). The wave pipeline itself stays in this
        class — verdicts are bit-identical and depth semantics are
        unchanged; the executor schedules AROUND it."""
        self._executor = executor
        if executor is not None:
            executor.register_deadline_probe(
                self.has_pending_deadline_work
            )
            executor.register_quiescence_probe(self.is_quiescent)

    def attach_health(self, tracker, wave_timeout_s=None) -> None:
        """Join the device fault domain (device/health.py): while the
        tracker quarantines the device, every bucket short-circuits to
        the bit-identical host oracle (verdicts exact per-set pairing
        checks — the differential tests prove identity), device errors
        report through the taxonomy, and `wave_timeout_s` arms a wave
        watchdog — a wave stuck past the deadline trips the tracker
        and resolves every pending verdict via host failover (zero
        lost, zero wrong). wave_timeout_s=None adopts the
        deadline-class default derived from the fused stage budget;
        pass 0/False to leave the wave watchdog unarmed (CPU
        emulation, where the TPU budget means nothing)."""
        self._health = tracker
        if wave_timeout_s is None:
            self._wave_timeout_s = watchdog_deadline_s("deadline")
        elif wave_timeout_s:
            self._wave_timeout_s = float(wave_timeout_s)
        else:
            self._wave_timeout_s = None

    def _health_log(self):
        if self._log is None:
            from ..logger import get_logger

            self._log = get_logger("bls-verifier")
        return self._log

    def _flush_target(self) -> int:
        """Rolling-bucket full threshold: the smallest device-ingest-
        eligible bucket size."""
        return max(4, self._ingest_gate())

    def _use_ingest(self, b: int, kind: str = "batch") -> bool:
        """Device ingest for a bucket of size b on the given pipeline
        (batch / same_message — distinct jit programs)? Gated by size
        and — when the cold-compile host fallback is on — by whether
        that pipeline's compile for b is already warm."""
        if b < self._ingest_gate():
            return False
        if self._cold_fallback and not kernels.ingest_is_warm(b, kind):
            return False
        return True

    def _count_dispatch(self, b: int, use_ingest: bool, failover: bool = False):
        """Per-bucket-size and per-path dispatch counters (the proof
        that trickle traffic coalesces into device-ingest buckets).
        Runs on executor threads — the lock keeps concurrent
        read-modify-write increments from losing counts (scrapers
        read via snapshot copies, see metrics bridging in node.py)."""
        m = self.metrics
        with m.dispatch_lock:
            m.dispatch_by_bucket[b] = (
                m.dispatch_by_bucket.get(b, 0) + 1
            )
            if failover:
                path = "failover"  # device quarantined: host oracle
            elif use_ingest:
                path = "ingest"
            elif b >= self._ingest_gate():
                path = "host_cold"  # eligible, but compile still cold
            else:
                path = "host"
            m.dispatch_by_path[path] += 1

    def start_warmup(self, block: bool = False):
        """Pre-compile the ingest pipeline for every eligible bucket
        size through the persistent cache (background thread unless
        block). While a size is cold the verifier serves it from the
        host path — starting warmup turns host_fallback_when_cold on
        (unless it was explicitly disabled), otherwise the first live
        ingest bucket would dispatch straight into the very compile
        the warmup thread is paying for."""
        if self._mesh is not None:
            # jit specializes on input shardings: the warmup's
            # unsharded dispatches would compile a DIFFERENT
            # executable than this verifier's mesh-sharded buckets
            # and falsely mark the sizes warm. Cold fallback would
            # route ingest-eligible buckets to the host path FOREVER
            # (only unsharded dispatches mark them warm), so mesh
            # verifiers keep direct dispatch: the first live bucket
            # per size pays the compile inline, once, persistent-
            # cached and not watched (the registry's mesh marks;
            # kernels.warm_bucket(mesh=...) warms them ahead).
            if self._cold_fallback_explicit is None:
                self._cold_fallback = False
            return None
        if self._cold_fallback_explicit is None:
            self._cold_fallback = True
        gate = self._ingest_gate()
        return kernels.warmup_ingest(
            kernels.default_warmup_sizes(gate),
            block=block,
            host_sizes=kernels.default_host_warmup_sizes(gate),
        )

    # -- IBlsVerifier surface ------------------------------------------

    def can_accept_work(self) -> bool:
        # with a node executor attached, its intake state is part of
        # this verifier's: an executor drain (the re-tune window)
        # closes the processor-fed path exactly like hold_intake did
        if self._executor is not None and not self._executor.can_accept_work(
            "deadline"
        ):
            return False
        return (
            not self._closed
            and not self._intake_held
            and self._queue.qsize()
            + len(self._buffer)
            + len(self._rolling)
            < self._queue_max
        )

    @contextlib.contextmanager
    def hold_intake(self):
        """Backpressure gossip intake (can_accept_work -> False) for
        the duration of the block. The drift monitor wraps a re-tune
        in this so the quiescence it checked once keeps holding for
        the processor-fed path; callers that bypass can_accept_work
        (block import) can still submit — a mid-tune wave then pays
        recompile latency, never wrong verdicts (the cleared caches
        re-trace deterministically)."""
        self._intake_held += 1
        try:
            yield
        finally:
            self._intake_held -= 1

    @property
    def in_flight_waves(self) -> int:
        """Waves dispatched to the device and not yet finalized — the
        device dispatch-queue depth (lodestar_jax_dispatch_queue_depth)."""
        return len(self._finalizers)

    async def verify_signature_sets(
        self,
        sets: list[api.SignatureSet],
        batchable: bool = False,
        priority: bool = False,
    ) -> bool:
        """True iff every set verifies. Malformed points -> False
        (maybeBatch.ts:17-44 semantics). Decompression/hashing is
        deferred to the wave's prep stage (thread pool), keeping the
        event loop free.

        When the caller runs inside a block-import trace (the chain's
        sig_verify stage, metrics/tracing.py), this job's submit-to-
        verdict interval lands as a nested span in the trace tree —
        the contextvar copied at task spawn carries the parent."""
        from ..metrics.tracing import attach_completed_span, child_span

        with child_span("bls_verify_job"):
            self._ensure_runner()
            fut = asyncio.get_event_loop().create_future()
            job = _Job(list(sets), fut, batchable, time.monotonic())
            self.metrics.sig_sets_started += len(job.sets)
            if batchable and len(job.sets) < self._max_buffered:
                self._buffer.append(job)
                buffered = sum(len(j.sets) for j in self._buffer)
                if buffered >= self._max_buffered:
                    self._flush_buffer()
                elif self._buffer_task is None:
                    self._buffer_task = asyncio.ensure_future(
                        self._flush_after_wait()
                    )
            else:
                self._enqueue([job], priority)
            ok = await fut
            # device-side child span under this job's span: the wave's
            # dispatch-to-readback interval, learned at finalize
            attach_completed_span("device_wave", job.device_s)
            return ok

    async def verify_signature_sets_same_message(
        self, sets: list[api.SameMessageSet], message: bytes
    ) -> list[bool]:
        """Per-set verdicts for k (pubkey, signature) pairs on one
        message (jobItem.ts:50-92)."""
        t0 = time.monotonic()
        try:
            return await self._verify_same_message_timed(sets, message)
        finally:
            self.metrics.same_message_latency.observe(
                time.monotonic() - t0
            )

    async def _verify_same_message_timed(
        self, sets: list[api.SameMessageSet], message: bytes
    ) -> list[bool]:
        self._ensure_runner()
        loop = asyncio.get_event_loop()

        def prep():
            # ONE host hash for the whole group (amortized by the
            # attData-keyed queue); signatures stay compressed — the
            # device decompresses them
            h = api.message_to_g2(message)
            draws = api.message_draws(message)
            out = []
            for s in sets:
                try:
                    pk = api.decompress_pubkey(s.pubkey)
                except api.InvalidPointError:
                    out.append(None)
                    continue
                xc0, xc1, sign, ok = api.parse_signature(s.signature)
                out.append(
                    ((pk, (xc0, xc1), sign)) if ok else None
                )
            return h, draws, out

        h, draws, prepared = await loop.run_in_executor(
            self._prep_pool, prep
        )
        live = [i for i, p in enumerate(prepared) if p is not None]
        if not live:
            return [False] * len(sets)
        results = [False] * len(sets)
        ok = await self._run_same_message(
            [prepared[i] for i in live], h
        )
        if ok:
            for i in live:
                results[i] = True
            return results
        # batch failed: per-signature retry fan-out (index.ts:552-563)
        self.metrics.same_message_retries += 1
        singles = await self._verdict_wave(
            [
                [
                    _PreparedSet(
                        prepared[i][0],
                        prepared[i][1],
                        prepared[i][2],
                        draws,
                    )
                ]
                for i in live
            ]
        )
        for i, r in zip(live, singles):
            results[i] = r
        return results

    async def close(self):
        """Reject all pending work (the reference rejects queued jobs on
        worker termination, index.ts:311-318) and stop the runner."""
        self._closed = True
        if self._buffer_task:
            self._buffer_task.cancel()
            self._buffer_task = None
        if self._rolling_task:
            self._rolling_task.cancel()
            self._rolling_task = None
        err = RuntimeError("BLS verifier closed")
        for j in self._buffer:
            if not j.future.done():
                j.future.set_exception(err)
        self._buffer = []
        for j in self._rolling:
            if not j.future.done():
                j.future.set_exception(err)
        self._rolling = []
        self._rolling_sets = 0
        self.metrics.rolling_sets = 0
        while not self._queue.empty():
            _, _, jobs = self._queue.get_nowait()
            for j in jobs:
                if not j.future.done():
                    j.future.set_exception(err)
        if self._runner:
            self._runner.cancel()
            self._runner = None
        # cancel the prefetch window first: a wave task cancelled here
        # fails its jobs (see _run_wave / _wave_done), never leaves a
        # caller awaiting a future its wave will no longer resolve
        for t in list(self._wave_tasks):
            t.cancel()
        for t in list(self._finalizers):
            t.cancel()
        self._prep_pool.shutdown(wait=False)

    # -- internals ------------------------------------------------------

    def _prepare(self, s: api.SignatureSet) -> _PreparedSet | None:
        """Host prep: pubkey cache + byte parsing + message expansion.
        None = malformed on host (bad flags / non-canonical / infinity
        signature) -> the job resolves False without device work
        (maybeBatch.ts:17-44 semantics)."""
        pk = api.decompress_pubkey(s.pubkey)
        xc0, xc1, sign, ok = api.parse_signature(s.signature)
        if not ok:
            return None
        draws = api.message_draws(s.message)
        return _PreparedSet(
            pk, (xc0, xc1), sign, draws, s.signature, s.message
        )

    def _ensure_runner(self):
        if self._closed:
            # the reference rejects work after termination (index.ts:311-318)
            raise RuntimeError("BLS verifier closed")
        if self._runner is None or self._runner.done():
            self._runner = asyncio.ensure_future(self._run_loop())

    def _enqueue(self, jobs: list[_Job], priority: bool = False):
        self.metrics.job_groups_started += 1
        now = time.monotonic()
        for j in jobs:
            j.enqueued_at = now
        self._seq += 1
        self._queue.put_nowait((0 if priority else 1, self._seq, jobs))
        self.metrics.queue_length = self._queue.qsize()

    def _flush_buffer(self):
        if self._buffer_task:
            self._buffer_task.cancel()
            self._buffer_task = None
        jobs, self._buffer = self._buffer, []
        if jobs:
            self._enqueue(jobs)

    async def _flush_after_wait(self):
        try:
            await asyncio.sleep(self._max_wait)
        except asyncio.CancelledError:
            return
        self._buffer_task = None
        self._flush_buffer()

    async def _run_loop(self):
        """Drain-everything wave loop with CONTINUOUS BATCHING. Each
        iteration collects ALL queued job groups. Batchable jobs join
        the ROLLING device bucket shared across waves; it flushes when
        it reaches the device-ingest gate (full), when non-batchable
        work dispatches anyway (merged — the trickle rides along for
        free), or when the oldest job's latency budget expires
        (deadline task). Flushed waves are prepped + dispatched, then
        finalized (readback + retries) in a separate task so the next
        wave's host prep overlaps this wave's device execution — the
        TPU analog of prepareWork re-filling idle workers
        (index.ts:357-534)."""
        while not self._closed:
            _, _, jobs = await self._queue.get()
            # from here until the jobs land in _rolling or in
            # _dispatch_wave they live only in this local — count the
            # window as dispatching so a cross-thread is_quiescent()
            # (drift monitor) can never see a falsely idle verifier
            self._dispatching += 1
            try:
                jobs = list(jobs)
                while True:
                    try:
                        _, _, more = self._queue.get_nowait()
                    except asyncio.QueueEmpty:
                        break
                    jobs.extend(more)
                self.metrics.queue_length = self._queue.qsize()
                immediate: list[_Job] = []
                for j in jobs:
                    if j.batchable and self._latency_budget > 0:
                        self._rolling.append(j)
                        self._rolling_sets += len(j.sets)
                    else:
                        immediate.append(j)
                self.metrics.rolling_sets = self._rolling_sets
                if immediate:
                    if self._rolling:
                        self.metrics.rolling_flushes["merged"] += 1
                    await self._dispatch_wave(
                        immediate + self._take_rolling()
                    )
                elif self._rolling_sets >= self._flush_target():
                    self.metrics.rolling_flushes["full"] += 1
                    await self._dispatch_wave(self._take_rolling())
                elif self._rolling:
                    self._arm_rolling_deadline()
            finally:
                self._dispatching -= 1

    def _take_rolling(self) -> list[_Job]:
        jobs, self._rolling = self._rolling, []
        self._rolling_sets = 0
        self.metrics.rolling_sets = 0
        if self._rolling_task is not None:
            self._rolling_task.cancel()
            self._rolling_task = None
        return jobs

    def _arm_rolling_deadline(self):
        """Schedule the deadline flush for the OLDEST rolling job:
        enqueue time + latency budget (the budget rides on top of the
        100 ms gossip buffer the job may already have waited in)."""
        if self._rolling_task is not None:
            return
        oldest = min(j.enqueued_at for j in self._rolling)
        delay = max(
            0.0, oldest + self._latency_budget - time.monotonic()
        )
        self._rolling_task = asyncio.ensure_future(
            self._rolling_flush_after(delay)
        )

    async def _rolling_flush_after(self, delay: float):
        try:
            await asyncio.sleep(delay)
        except asyncio.CancelledError:
            return
        self._rolling_task = None
        if self._rolling:
            self.metrics.rolling_flushes["deadline"] += 1
            await self._dispatch_wave(self._take_rolling())

    async def _dispatch_wave(self, jobs: list[_Job]):
        """Admit one wave into the overlapped pipeline. The wave's
        prep + dispatch runs as its own task (_run_wave) so the run
        loop returns to draining the queue immediately — wave N+1's
        host prep overlaps wave N's device execution. Admission is
        bounded by the pipeline-depth knob: depth d allows d-1 waves
        in the prefetch window ahead of the finalizing wave; depth 1
        awaits the wave inline (the pre-pipeline synchronous
        behavior)."""
        if not jobs:
            return
        self.metrics.waves += 1
        t0 = time.monotonic()
        for j in jobs:
            self.metrics.total_job_wait_s += t0 - j.enqueued_at
        self._dispatching += 1
        try:
            depth = self._pipeline_depth
            try:
                while depth > 1 and len(self._wave_tasks) >= depth - 1:
                    await asyncio.wait(
                        set(self._wave_tasks),
                        return_when=asyncio.FIRST_COMPLETED,
                    )
            except asyncio.CancelledError:
                self._fail_jobs(
                    jobs, RuntimeError("BLS verifier closed")
                )
                raise
            task = asyncio.ensure_future(self._run_wave(jobs, t0))
            self._wave_tasks.add(task)
            task.add_done_callback(self._wave_done(jobs))
            self._occupancy_mark()
            if depth <= 1:
                await task
        finally:
            self._dispatching -= 1

    def _wave_done(self, jobs: list[_Job]):
        """Done-callback for a pipeline wave task: drop it from the
        prefetch window, and fail its jobs if the task was cancelled
        before its own CancelledError handler could run (close() can
        cancel a task that never started executing)."""

        def cb(task: asyncio.Task):
            self._wave_tasks.discard(task)
            if task.cancelled():
                self._fail_jobs(
                    jobs, RuntimeError("BLS verifier closed")
                )
            self._occupancy_mark()

        return cb

    def _finalizer_done(self, task: asyncio.Task):
        self._finalizers.discard(task)
        self._occupancy_mark()

    async def _run_wave(self, jobs: list[_Job], t0: float):
        """Prep + dispatch one wave; finalize (readback + retries) in
        a separate task so readback of wave N overlaps compute of
        N+1. Prep seconds spent while another wave was already in
        flight are credited to prep_overlap_hidden_s — host time the
        pipeline hid behind device execution instead of serializing
        ahead of it."""
        overlapped = self._inflight() > 1  # this task counts as one
        tp = time.monotonic()
        try:
            wave = await self._prep_and_dispatch(jobs)
        except asyncio.CancelledError:
            self._fail_jobs(jobs, RuntimeError("BLS verifier closed"))
            raise
        except asyncio.TimeoutError:
            # wave watchdog: the dispatch overran the deadline-class
            # budget (a hung device program). Trip the tracker and
            # resolve every pending verdict on the host oracle — the
            # callers get correct verdicts, not a timeout error.
            if self._health is not None:
                self._health.note_watchdog_trip("deadline")
            await self._failover_jobs(jobs)
            return
        except Exception as e:
            # device-error taxonomy (device/health.py): classify;
            # programming errors propagate to the waiters (our bug,
            # not the device's), device kinds report to the tracker
            # and the waiters get host-oracle verdicts instead
            if not await self._handle_wave_error(e, jobs):
                self._fail_jobs(jobs, e)
            return
        if overlapped:
            self.metrics.prep_overlap_hidden_s += (
                time.monotonic() - tp
            )
        task = asyncio.ensure_future(self._finalize_wave(wave, t0))
        self._finalizers.add(task)
        task.add_done_callback(self._finalizer_done)

    def _fail_jobs(self, jobs, err):
        for j in jobs:
            if not j.future.done():
                j.future.set_exception(err)

    def _resolve_job(self, j: _Job, ok: bool):
        """Resolve a job's future and record submit-to-verdict
        latency (the continuous-batching SLO: gossip buffer + rolling
        wait + device time, end to end)."""
        if not j.future.done():
            j.future.set_result(ok)
            if j.created_at:
                self.metrics.verify_latency.observe(
                    time.monotonic() - j.created_at
                )

    # -- device fault domain (device/health.py) -------------------------

    async def _await_device(self, coro, buckets: int, sizes=()):
        """Apply the armed wave-watchdog deadline (attach_health) to
        one device-bound await of `buckets` buckets — the deadline per
        bucket times their number, since the device runs them one after
        another. `sizes` are the set counts of the buckets the await
        dispatches: when one of them is the first dispatch of its
        program in this process, it compiles (minutes on the chip) and
        the await is not watched, like maintenance-class work.
        start_warmup compiles every shape a live wave dispatches.
        Pass-through when unarmed."""
        if (
            self._wave_timeout_s is None
            or self._health is None
            or self._compiles(sizes)
        ):
            return await coro
        return await asyncio.wait_for(
            coro, timeout=self._wave_timeout_s * max(1, buckets)
        )

    def _program_kind(self, b: int, use_ingest: bool) -> str:
        """The warm-registry kind (kernels.ingest_is_warm) of the
        program a bucket of b sets dispatches: device ingest ("batch")
        or host-decompressed ("host"), sharded over the mesh or not."""
        kind = "batch" if use_ingest else "host"
        mesh = self._mesh
        if mesh is not None and b % mesh.devices.size == 0:
            return "mesh_" + kind
        return kind

    def _compiles(self, sizes) -> bool:
        """Would buckets of these set counts compile a program?"""
        for n in sizes:
            b = kernels.bucket_size(n)
            kind = self._program_kind(b, self._use_ingest(b))
            if not kernels.ingest_is_warm(b, kind):
                return True
        return False

    def _bucket_sizes(self, groups) -> list[int]:
        """Set counts of the buckets _verdict_wave dispatches."""
        cap = self._bucket_cap()
        return [
            min(cap, len(g) - i)
            for g in groups
            for i in range(0, len(g), cap)
        ]

    async def _handle_wave_error(self, e, jobs) -> bool:
        """Taxonomy routing for a failed wave: returns True when the
        jobs were resolved via host failover, False when the caller
        must propagate the error to the waiters (programming errors —
        TypeError/KeyError from our own code must surface as the bug
        they are — or no tracker attached, the legacy behavior)."""
        health = self._health
        if health is None:
            return False
        kind = classify_device_error(e)
        if kind == "programming":
            return False
        try:
            health.record_fault(kind, client="bls")
        except ValueError:
            return False
        if health.should_log("bls"):
            self._health_log().warn(
                "device wave failed; verdicts riding host oracle",
                {"kind": kind, "err": repr(e)},
            )
        await self._failover_jobs(jobs)
        return True

    async def _failover_jobs(self, jobs) -> None:
        """Resolve every still-pending job with HOST-ORACLE verdicts
        computed from its raw signature sets — exact per-set pairing
        checks, bit-identical to OracleBlsVerifier (and to the device
        path: the differential suite proves all three agree). Zero
        lost verdicts, zero wrong verdicts; runs in the prep pool so
        the ~ms-per-set pairing math stays off the event loop."""
        loop = asyncio.get_event_loop()
        live = [j for j in jobs if not j.future.done()]
        if not live:
            return
        if self._health is not None:
            self._health.note_failover("bls")

        def verdicts():
            return [_host_oracle_sets(j.sets) for j in live]

        out = await loop.run_in_executor(self._prep_pool, verdicts)
        for j, ok in zip(live, out):
            self._resolve_job(j, bool(ok))

    async def _prep_and_dispatch(self, jobs: list[_Job]):
        """Host prep (thread pool, parallel per job) + bucket packing +
        STREAMING async device dispatch: buckets are packed by set
        counts up front (no prep needed), and each bucket is built and
        dispatched the moment its jobs' preps complete — so host prep
        of bucket k+1 overlaps device execution of bucket k instead of
        serializing ahead of the whole wave (the round-4 wave prepped
        ALL jobs before the first dispatch, leaving the device idle for
        the entire prep phase). Returns (buckets, device verdicts)."""
        loop = asyncio.get_event_loop()

        def prep_job(j: _Job):
            try:
                prepared = [self._prepare(s) for s in j.sets]
            except api.InvalidPointError:
                return None
            if any(p is None for p in prepared):
                return None
            return prepared

        # first device dispatch of the wave: the device interval the
        # jobs' device_wave spans report starts here, not at wave t0
        # (host prep ahead of it must not masquerade as device time)
        first_dispatch: list[float | None] = [None]

        prep_futs: dict[int, asyncio.Future] = {}
        live: list[_Job] = []
        for j in jobs:
            if len(j.sets) == 0:
                # empty set list: vacuously true, and it would carry no
                # bucket parts — _finalize_wave would never resolve it
                self._resolve_job(j, True)
                continue
            prep_futs[id(j)] = loop.run_in_executor(
                self._prep_pool, prep_job, j
            )
            live.append(j)
        # pack into device buckets by COUNT, preserving job boundaries;
        # a job larger than one bucket (a 64-block sync segment carries
        # ~8,000 sets, index.ts:51) is split into parts whose verdicts
        # AND together
        packing: list[list[tuple[_Job, int, int]]] = []  # (job, off, n)
        cur: list[tuple[_Job, int, int]] = []
        cur_n = 0
        cap = self._bucket_cap()
        for j in live:
            total, off = len(j.sets), 0
            while off < total:
                take = min(total - off, cap - cur_n)
                if take == 0:
                    packing.append(cur)
                    cur, cur_n = [], 0
                    continue
                cur.append((j, off, take))
                cur_n += take
                off += take
                if cur_n >= cap:
                    packing.append(cur)
                    cur, cur_n = [], 0
        if cur:
            packing.append(cur)
        self.metrics.jobs_started += len(live)

        async def run_bucket(plan):
            parts: list[tuple[_Job, list]] = []
            for j, off, take in plan:
                p = await prep_futs[id(j)]
                if p is None:
                    # malformed on host -> the job fails without
                    # device work (maybeBatch.ts:17-44 semantics)
                    self._resolve_job(j, False)
                    continue
                if j.future.done():
                    # already failed by another bucket's host
                    # pre-validation — don't ship dead sets
                    continue
                j.prepared = p
                parts.append((j, p[off : off + take]))
            if not parts:
                return None
            sets = [s for _, part in parts for s in part]
            b = kernels.bucket_size(len(sets))
            if not self._use_ingest(b) and len(parts) > 1:
                # HOST-path bucket spanning several jobs: one
                # signature that fails host decompression (no sqrt /
                # not in subgroup) used to scalar-False the WHOLE
                # bucket, fanning every innocent job out through the
                # per-job/per-set retry ladder. Pre-validate with the
                # same cached decompression the host path runs anyway:
                # fail only the owning jobs, dispatch the rest.
                def find_bad() -> set[int]:
                    out: set[int] = set()
                    for j, part in parts:
                        if id(j) in out:
                            continue
                        if any(
                            not self._host_sig_valid(s)
                            for s in part
                        ):
                            out.add(id(j))
                    return out

                # cached C decompression, but still ~0.5 ms/sig cold —
                # keep it off the event loop
                bad = await loop.run_in_executor(
                    self._prep_pool, find_bad
                )
                if bad:
                    self.metrics.host_invalid_jobs += len(bad)
                    for j, _part in parts:
                        if id(j) in bad:
                            self._resolve_job(j, False)
                    parts = [
                        (j, p) for j, p in parts if id(j) not in bad
                    ]
                    if not parts:
                        return None
                    sets = [s for _, part in parts for s in part]
            if first_dispatch[0] is None:
                first_dispatch[0] = time.monotonic()
            ok = await loop.run_in_executor(
                None, self._submit_bucket, sets
            )
            self.metrics.buckets_dispatched += 1
            return parts, ok

        results = await self._await_device(
            asyncio.gather(*(run_bucket(plan) for plan in packing)),
            len(packing),
            [sum(take for _, _, take in plan) for plan in packing],
        )
        buckets = [r[0] for r in results if r is not None]
        oks = [r[1] for r in results if r is not None]
        return buckets, oks, first_dispatch[0]

    def _host_sig_valid(self, s: "_PreparedSet") -> bool:
        """Does this set's signature survive host decompression? Uses
        the same lru-cached decompression the host dispatch path runs,
        so pre-validation costs nothing extra."""
        try:
            sig = (
                api.decompress_signature(s.sig_raw)
                if s.sig_raw
                else api.decompress_signature_parsed(
                    s.sig_x, s.sig_sign
                )
            )
        except api.InvalidPointError:
            return False
        return sig is not None

    async def _finalize_wave(self, wave, t0: float):
        """One readback for the whole wave; failed buckets retry
        per job, then per set (worker.ts:88-103 isolation)."""
        buckets, oks, t_dispatch = wave
        try:
            verdicts = await self._await_device(
                self._readback(oks), len(oks)
            )
            # verdicts are on host: the device work for every job in
            # the wave is done — stamp the first-dispatch-to-readback
            # interval on each job so its awaiting caller can graft a
            # device child span (host prep ahead of the first dispatch
            # is excluded: it must not masquerade as device time)
            if t_dispatch is not None:
                dt_dev = time.monotonic() - t_dispatch
                for b in buckets:
                    for j, _part in b:
                        j.device_s = dt_dev
            # a job's direct verdict is the AND over every bucket part
            # that carried its sets
            job_ok: dict[int, bool] = {}
            job_of: dict[int, _Job] = {}
            shared: set[int] = set()  # jobs whose bucket carried others
            for b, ok in zip(buckets, verdicts):
                for j, _part in b:
                    jid = id(j)
                    job_of[jid] = j
                    job_ok[jid] = job_ok.get(jid, True) and ok
                    if len(b) > 1:
                        shared.add(jid)
            retry: list[_Job] = []
            for jid, ok in job_ok.items():
                j = job_of[jid]
                if j.future.done():
                    # pre-failed by host pre-validation — even if its
                    # other (all-good) buckets came back True, the job
                    # is failed and its sets must not count as batch
                    # successes
                    pass
                elif ok:
                    self.metrics.batch_sigs_success += len(j.prepared)
                    self._resolve_job(j, True)
                elif len(j.prepared) == 1 and jid not in shared:
                    # alone in its bucket: the aggregate verdict IS the
                    # job's own. In a SHARED bucket (rolling/merged
                    # trickle) a failed aggregate says nothing about
                    # this job — retry it like any multi-set job, or
                    # one bad pairing fails every innocent 1-set rider.
                    self._resolve_job(j, False)
                else:
                    retry.append(j)
            if retry:
                self.metrics.batch_retries += 1
                groups = [j.prepared for j in retry]
                sizes = self._bucket_sizes(groups)
                verdicts = await self._await_device(
                    self._verdict_wave(groups), len(sizes), sizes
                )
                per_set: list[_Job] = []
                for j, ok in zip(retry, verdicts):
                    if ok:
                        self._resolve_job(j, True)
                    elif len(j.prepared) == 1:
                        self._resolve_job(j, False)
                    else:
                        per_set.append(j)
                if per_set:
                    flat = [
                        [s]
                        for j in per_set
                        for s in j.prepared
                    ]
                    sizes = self._bucket_sizes(flat)
                    singles = await self._await_device(
                        self._verdict_wave(flat), len(sizes), sizes
                    )
                    i = 0
                    for j in per_set:
                        n = len(j.prepared)
                        self._resolve_job(
                            j, all(singles[i : i + n])
                        )
                        i += n
        except asyncio.CancelledError:
            self._fail_jobs(
                [j for b in buckets for j, _ in b],
                RuntimeError("BLS verifier closed"),
            )
            raise
        except asyncio.TimeoutError:
            # wave watchdog: readback (or a retry dispatch) stuck past
            # the deadline-class budget — trip the tracker and resolve
            # the pending verdicts on the host oracle
            if self._health is not None:
                self._health.note_watchdog_trip("deadline")
            await self._failover_jobs(
                [j for b in buckets for j, _ in b]
            )
        except Exception as e:
            # taxonomy routing (device/health.py): device kinds fail
            # over to host-oracle verdicts; programming errors (and
            # tracker-less verifiers) propagate to the waiters
            jobs = [j for b in buckets for j, _ in b]
            if not await self._handle_wave_error(e, jobs):
                self._fail_jobs(jobs, e)
        finally:
            dt = time.monotonic() - t0
            self.metrics.total_device_time_s += dt
            n_sets = sum(
                len(part) for b in buckets for _, part in b
            )
            self.metrics.last_wave_sets = n_sets
            self.metrics.wave_sets_total += n_sets
            self.metrics.last_wave_duration_s = dt

    def _submit_bucket(self, sets: list[_PreparedSet]):
        """Pad to a bucket size, build device arrays (sharded over the
        mesh when even), dispatch WITHOUT readback. Returns the device
        () bool. Signatures/messages ship compressed — decompression
        and hash-to-G2 run inside the device program."""
        from ..ops import tower

        n = len(sets)
        b = kernels.bucket_size(n)
        health = self._health
        if health is not None and not health.device_allowed():
            # device quarantined: bit-identical host-oracle verdict,
            # no jax array is built (touching a sick TPU could hang
            # the failover itself). Plain bool — _readback handles it.
            self._count_dispatch(b, False, failover=True)
            if health.note_failover("bls"):
                self._health_log().warn(
                    "device quarantined: buckets riding host oracle",
                    {"state": health.state.value},
                )
            return _host_oracle_prepared(sets)
        pad = b - n
        pad_set = _pad_prepared()
        full = sets + [pad_set] * pad
        rand = _rand_scalars(b)
        pk_dev = C.g1_batch_from_ints([s.pk for s in full])
        bits = C.scalars_to_bits(rand, kernels.RAND_BITS)
        mask = jnp.asarray([True] * n + [False] * pad)
        mesh = self._mesh
        shard = (
            mesh is not None and b % mesh.devices.size == 0
        )
        use_ingest = self._use_ingest(b)
        self._count_dispatch(b, use_ingest)
        if use_ingest:
            # device ingest: compressed signatures + field draws in
            sig_x = tower.fq2_from_ints([s.sig_x for s in full])
            sig_sign = jnp.asarray([s.sig_sign for s in full])
            u0 = tower.fq2_from_ints([s.draws[0] for s in full])
            u1 = tower.fq2_from_ints([s.draws[1] for s in full])
            if shard:
                # WHOLE-BUCKET mesh path (ISSUE 16): each chip runs
                # the complete collective-free verify on the
                # sub-bucket it owns; the only collective is the one
                # verdict psum inside the shard_map program. Mesh
                # programs are marks of their own in the warm
                # registry (distinct executables from the single-host
                # ones).
                from .. import parallel

                pk_dev = parallel.shard_batch(mesh, pk_dev)
                sig_x = parallel.shard_batch(mesh, sig_x)
                sig_sign = parallel.shard_batch(mesh, sig_sign)
                u0 = parallel.shard_batch(mesh, u0)
                u1 = parallel.shard_batch(mesh, u1)
                bits = parallel.shard_batch(mesh, bits)
                mask = parallel.shard_batch(mesh, mask)
                _device.record_transfer(
                    "h2d", pk_dev, sig_x, sig_sign, u0, u1, bits, mask
                )
                out = kernels.run_verify_batch_ingest_mesh(
                    mesh, pk_dev, sig_x, sig_sign, u0, u1, bits, mask
                )
                kernels.mark_ingest_warm(b, "mesh_batch")
                return out
            _device.record_transfer(
                "h2d", pk_dev, sig_x, sig_sign, u0, u1, bits, mask
            )
            out = kernels.run_verify_batch_ingest_async(
                pk_dev, sig_x, sig_sign, u0, u1, bits, mask
            )
            # the jit compile (or persistent-cache load) happened on
            # this dispatch — later buckets of size b are warm
            kernels.mark_ingest_warm(b)
            return out
        # small buckets: host decompression/hashing (cached C calls —
        # affordable at this scale, and it avoids compiling the ingest
        # stages for every small bucket size)
        hs, sigs = [], []
        ok = True
        for s in full:
            try:
                sig = (
                    api.decompress_signature(s.sig_raw)
                    if s.sig_raw
                    else api.decompress_signature_parsed(
                        s.sig_x, s.sig_sign
                    )
                )
            except api.InvalidPointError:
                sig = None
            if sig is None:
                ok = False
                sig = oc.G2_GEN
            sigs.append(sig)
            hs.append(
                api.message_to_g2(s.msg_raw)
                if s.msg_raw
                else api.draws_to_g2(s.draws)
            )
        if not ok:
            # an invalid signature fails the bucket without device work
            import jax.numpy as _jnp

            return _jnp.asarray(False)
        h_dev = C.g2_batch_from_ints(hs)
        sig_dev = C.g2_batch_from_ints(sigs)
        h = (h_dev.x, h_dev.y)
        if shard:
            # whole-bucket mesh verify (one collective: verdict psum)
            from .. import parallel

            pk_dev = parallel.shard_batch(mesh, pk_dev)
            h = parallel.shard_batch(mesh, h)
            sig_dev = parallel.shard_batch(mesh, sig_dev)
            bits = parallel.shard_batch(mesh, bits)
            mask = parallel.shard_batch(mesh, mask)
            _device.record_transfer(
                "h2d", pk_dev, h, sig_dev, bits, mask
            )
            out = kernels.run_verify_batch_mesh(
                mesh, pk_dev, h, sig_dev, bits, mask
            )
            kernels.mark_ingest_warm(b, "mesh_host")
            return out
        _device.record_transfer("h2d", pk_dev, h, sig_dev, bits, mask)
        out = kernels.run_verify_batch_async(pk_dev, h, sig_dev, bits, mask)
        kernels.mark_ingest_warm(b, "host")
        return out

    async def _readback(self, oks) -> list[bool]:
        """ONE host transfer for a wave of device verdicts."""
        loop = asyncio.get_event_loop()

        def read():
            import numpy as np

            if not oks:
                return []
            if all(isinstance(v, bool) for v in oks):
                # all-failover wave: the verdicts are host bools
                # already — don't build a device array just to read
                # it back (and don't touch a quarantined chip at all)
                return list(oks)
            _device.record_transfer("d2h", oks)
            if len(oks) == 1:
                return [bool(oks[0])]
            return [bool(v) for v in np.asarray(jnp.stack(oks))]

        return await loop.run_in_executor(None, read)

    async def _verdict_wave(
        self, groups: list[list[_PreparedSet]]
    ) -> list[bool]:
        """Verify each group as its own bucket; all dispatched before
        one readback."""
        loop = asyncio.get_event_loop()
        out: list[bool] = []
        # split oversized groups (a 64-block sync segment can carry
        # ~8,000 sets, index.ts:51) into AND-ed device buckets
        plan: list[tuple[int, int]] = []  # (group idx, n buckets)
        buckets: list[list[_PreparedSet]] = []
        cap = self._bucket_cap()
        for gi, g in enumerate(groups):
            parts = [
                g[i : i + cap] for i in range(0, len(g), cap)
            ] or [[]]
            plan.append((gi, len(parts)))
            buckets.extend(parts)

        def dispatch():
            return [
                self._submit_bucket(b) if b else None
                for b in buckets
            ]

        oks = await loop.run_in_executor(None, dispatch)
        live = [o for o in oks if o is not None]
        verdicts_flat = await self._readback(live) if live else []
        it = iter(verdicts_flat)
        flat = [True if o is None else next(it) for o in oks]
        i = 0
        for _, nparts in plan:
            out.append(all(flat[i : i + nparts]))
            i += nparts
        return out

    async def _run_batch(self, sets: list[_PreparedSet]) -> bool:
        return (await self._verdict_wave([sets]))[0]

    async def _run_same_message(self, pairs, h) -> bool:
        """One fused aggregate+pairing check; splits above the device
        cap and ANDs (random weights keep each part sound). pairs:
        (pk_ints, (xc0, xc1), sign) triples — signature decompression
        happens on device."""
        cap = self._bucket_cap()
        if len(pairs) > cap:
            parts = [
                pairs[i : i + cap] for i in range(0, len(pairs), cap)
            ]
            verdicts = await asyncio.gather(
                *(self._run_same_message(p, h) for p in parts)
            )
            return all(verdicts)
        loop = asyncio.get_event_loop()

        def dispatch():
            from ..ops import tower

            n = len(pairs)
            b = kernels.bucket_size(n)
            health = self._health
            if health is not None and not health.device_allowed():
                # quarantined: exact host pairing checks against the
                # one already-hashed message point (bit-identical)
                self._count_dispatch(b, False, failover=True)
                if health.note_failover("bls"):
                    self._health_log().warn(
                        "device quarantined: same-message riding"
                        " host oracle",
                        {"state": health.state.value},
                    )
                return _host_oracle_same_message(pairs, h)
            pad = b - n
            pad_set = _pad_prepared()
            rand = _rand_scalars(b)
            pks = [p for p, _, _ in pairs] + [pad_set.pk] * pad
            pk_dev = C.g1_batch_from_ints(pks)
            h_dev = C.g2_batch_from_ints([h])  # batch (1,)
            bits = C.scalars_to_bits(rand, kernels.RAND_BITS)
            mask = jnp.asarray([True] * n + [False] * pad)
            use_ingest = self._use_ingest(b, "same_message")
            self._count_dispatch(b, use_ingest)
            if use_ingest:
                sxs = [x for _, x, _ in pairs] + [
                    pad_set.sig_x
                ] * pad
                sgs = [s for _, _, s in pairs] + [
                    pad_set.sig_sign
                ] * pad
                sig_x = tower.fq2_from_ints(sxs)
                sig_sign = jnp.asarray(sgs)
                mesh = self._mesh
                if (
                    mesh is not None
                    and b % mesh.devices.size == 0
                ):
                    # whole-bucket mesh: the (1,)-batch hash point is
                    # replicated (every shard pairs its aggregate
                    # against the same H); one verdict psum
                    from .. import parallel

                    pk_s = parallel.shard_batch(mesh, pk_dev)
                    sig_x_s = parallel.shard_batch(mesh, sig_x)
                    sign_s = parallel.shard_batch(mesh, sig_sign)
                    bits_s = parallel.shard_batch(mesh, bits)
                    mask_s = parallel.shard_batch(mesh, mask)
                    h_r = parallel.replicate(
                        mesh, (h_dev.x, h_dev.y)
                    )
                    _device.record_transfer(
                        "h2d", pk_s, h_r, sig_x_s, sign_s,
                        bits_s, mask_s,
                    )
                    return kernels.run_verify_same_message_mesh(
                        mesh, pk_s, h_r, sig_x_s, sign_s,
                        bits_s, mask_s,
                    )
                _device.record_transfer(
                    "h2d", pk_dev, h_dev, sig_x, sig_sign, bits, mask
                )
                out = kernels.run_verify_same_message_ingest_async(
                    pk_dev,
                    (h_dev.x, h_dev.y),
                    sig_x,
                    sig_sign,
                    bits,
                    mask,
                )
                kernels.mark_ingest_warm(b, "same_message")
                return out
            # small groups: host decompression (cached C), avoiding a
            # per-bucket-size ingest-stage compile on the gossip path
            sigs = []
            for _, sx, sg in pairs:
                sig = api.decompress_signature_parsed(sx, sg)
                if sig is None:
                    return jnp.asarray(False)
                sigs.append(sig)
            sigs += [
                api.decompress_signature_parsed(
                    pad_set.sig_x, pad_set.sig_sign
                )
            ] * pad
            sig_dev = C.g2_batch_from_ints(sigs)
            _device.record_transfer(
                "h2d", pk_dev, h_dev, sig_dev, bits, mask
            )
            return kernels.run_verify_same_message(
                pk_dev, (h_dev.x, h_dev.y), sig_dev, bits, mask
            )

        ok = await loop.run_in_executor(None, dispatch)
        return bool((await self._readback([ok]))[0])


class OracleBlsVerifier:
    """Single-threaded oracle-backed verifier — same interface, used in
    tests and as the differential reference (reference analog:
    BlsSingleThreadVerifier, chain/bls/singleThread.ts:8)."""

    def can_accept_work(self) -> bool:
        return True

    async def verify_signature_sets(
        self, sets, batchable=False, priority=False
    ) -> bool:
        from ..crypto.bls import pairing as op
        from ..metrics.tracing import child_span

        try:
            with child_span("bls_verify_job"):
                for s in sets:
                    pk = api.decompress_pubkey(s.pubkey)
                    h = api.message_to_g2(s.message)
                    sig = api.decompress_signature(s.signature)
                    if sig is None:
                        return False
                    ok = op.pairing_product_is_one(
                        [(pk, h), (oc.g1_neg(oc.G1_GEN), sig)]
                    )
                    if not ok:
                        return False
                return True
        except api.InvalidPointError:
            return False

    async def verify_signature_sets_same_message(self, sets, message):
        from ..crypto.bls import pairing as op

        h = api.message_to_g2(message)
        out = []
        for s in sets:
            try:
                pk = api.decompress_pubkey(s.pubkey)
                sig = api.decompress_signature(s.signature)
            except api.InvalidPointError:
                out.append(False)
                continue
            if sig is None:
                out.append(False)
                continue
            out.append(
                op.pairing_product_is_one(
                    [(pk, h), (oc.g1_neg(oc.G1_GEN), sig)]
                )
            )
        return out

    async def close(self):
        pass
