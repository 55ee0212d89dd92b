"""Node-wide device executor (lodestar_tpu/device/executor.py).

The OFFLINE stub-fast suite — no real compiles, no real kernel math
enters tier-1 through this file (the verifier-integration tests stub
every device entry point the way test_bls_verifier_trickle does).
Covered, per the issue's satellite list:

  * QoS ordering at wave boundaries: a deadline job submitted while a
    bulk job occupies the worker dispatches at the next boundary
    ahead of any further bulk — including under a FULL bulk queue
  * admission control: per-class shedding at the bound, deadline
    never shed under overload, note_shed external accounting
  * maintenance aging: bulk cannot starve maintenance forever
    (job-count trip and wall-clock trip)
  * maintenance_checkpoint + the warmup-yields-between-compiles
    regression (stubbed kernels, satellite bugfix)
  * drain-for-retune replacing hold_intake: the drift monitor's
    executor path re-tunes with ZERO hold_intake calls; the legacy
    path survives for executor-less verifiers
  * close() semantics: running job completes, queued futures cancel
    (counted as sheds), post-close submits shed
  * metric exposition (lodestar_device_sheds_total + the
    lodestar_device_executor_* family)
  * verifier integration: bulk defers to pending gossip work, and
    depth-2 verdicts are bit-identical with and without an executor
  * processor shed accounting at the can_accept_work rejection sites
"""

import asyncio
import threading
import time
from types import SimpleNamespace

import pytest

from lodestar_tpu.bls import SignatureSet, TpuBlsVerifier
from lodestar_tpu.bls import kernels as K
from lodestar_tpu.bls import verifier as V
from lodestar_tpu.device import autotune as AT
from lodestar_tpu.device import executor as X
from lodestar_tpu.device.executor import (
    QOS_BULK,
    QOS_DEADLINE,
    QOS_MAINTENANCE,
    DeviceExecutor,
)


@pytest.fixture(autouse=True)
def _restore_device_hooks():
    """Executor tests install module-level hooks (the kernels
    maintenance gate, the kzg executor); restore them so no other
    test file sees a wired process."""
    from lodestar_tpu.crypto import kzg as KZ

    warm = set(K._INGEST_WARM)
    started = K._WARMUP_STARTED
    gate = K._MAINT_GATE
    kz_ex = KZ._EXECUTOR
    msm_backend = KZ.msm_backend()
    yield
    K._INGEST_WARM.clear()
    K._INGEST_WARM.update(warm)
    K._WARMUP_STARTED = started
    K.set_maintenance_gate(gate)
    KZ.set_executor(kz_ex)
    KZ.set_msm_backend(msm_backend)


@pytest.fixture
def make_executor():
    """Executor factory that closes every instance at teardown (the
    worker is a daemon thread, but tests should not leak pollers)."""
    made = []

    def mk(**kw):
        ex = DeviceExecutor(**kw)
        made.append(ex)
        return ex

    yield mk
    for ex in made:
        ex.close(timeout_s=1.0)


def _wait_for(pred, timeout=2.0, interval=0.002):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def _block_worker(ex, cls=QOS_BULK):
    """Occupy the worker with a job that holds until released —
    the 'bulk blob batch occupies the pipeline' fixture. Returns
    (release_event, running_event, future)."""
    gate = threading.Event()
    running = threading.Event()

    def job():
        running.set()
        gate.wait(5.0)
        return "gated"

    fut = ex.submit(cls, job)
    assert fut is not None
    assert running.wait(2.0), "worker never started the gate job"
    return gate, running, fut


class TestAdmissionAndShedding:
    def test_submit_runs_and_reports_latency(self, make_executor):
        ex = make_executor()
        assert ex.submit(QOS_BULK, lambda: 41 + 1).result(2.0) == 42
        assert ex.completed[QOS_BULK] == 1
        assert ex.latency[QOS_BULK].count == 1

    def test_unknown_class_rejected(self, make_executor):
        ex = make_executor()
        with pytest.raises(ValueError):
            ex.submit("interactive", lambda: 1)
        with pytest.raises(ValueError):
            ex.can_accept_work("interactive")

    def test_overload_sheds_bulk_and_maintenance_never_deadline(
        self, make_executor
    ):
        """The acceptance criterion: under synthetic overload the
        executor sheds ONLY bulk/maintenance. Deadline admission is
        unbounded by design — its stream is bounded upstream by the
        verifier's own queue_max, where the processor counts drops."""
        ex = make_executor(
            queue_bounds={"bulk": 2, "maintenance": 1}
        )
        gate, _, _ = _block_worker(ex)
        try:
            bulk = [ex.submit(QOS_BULK, lambda: 1) for _ in range(5)]
            maint = [
                ex.submit(QOS_MAINTENANCE, lambda: 1)
                for _ in range(3)
            ]
            dead = [
                ex.submit(QOS_DEADLINE, lambda: 1) for _ in range(50)
            ]
            assert sum(f is None for f in bulk) == 3
            assert sum(f is None for f in maint) == 2
            assert all(f is not None for f in dead)
            sheds = ex.shed_counts()
            assert sheds[(QOS_BULK, "queue_full")] == 3
            assert sheds[(QOS_MAINTENANCE, "queue_full")] == 2
            assert not any(
                cls == QOS_DEADLINE for cls, _ in sheds
            ), "deadline must never be shed under overload"
            assert not ex.can_accept_work(QOS_BULK)
            assert not ex.can_accept_work(QOS_MAINTENANCE)
            assert ex.can_accept_work(QOS_DEADLINE)
        finally:
            gate.set()

    def test_note_shed_external_accounting(self, make_executor):
        ex = make_executor()
        ex.note_shed(QOS_DEADLINE, "gossip_aggregate")
        ex.note_shed(QOS_DEADLINE, "gossip_aggregate")
        ex.note_shed(QOS_BULK, "blob_backfill")
        sheds = ex.shed_counts()
        assert sheds[(QOS_DEADLINE, "gossip_aggregate")] == 2
        assert sheds[(QOS_BULK, "blob_backfill")] == 1


class TestQosOrdering:
    def test_deadline_ahead_of_bulk_at_wave_boundary(
        self, make_executor
    ):
        """THE tentpole ordering guarantee: a deadline job submitted
        while a bulk job occupies the worker runs at the next wave
        boundary ahead of every bulk job queued before it."""
        ex = make_executor()
        order = []
        gate, _, _ = _block_worker(ex)
        for i in range(3):
            ex.submit(QOS_BULK, lambda i=i: order.append(f"bulk{i}"))
        d = ex.submit(QOS_DEADLINE, lambda: order.append("deadline"))
        gate.set()
        d.result(2.0)
        assert order[0] == "deadline"
        assert _wait_for(lambda: len(order) == 4)
        assert order == ["deadline", "bulk0", "bulk1", "bulk2"]

    def test_deadline_ahead_of_bulk_under_full_bulk_queue(
        self, make_executor
    ):
        """Satellite: the priority holds when the bulk queue is at
        its admission bound — a full bulk backlog neither blocks nor
        outruns deadline work."""
        ex = make_executor(queue_bounds={"bulk": 2})
        order = []
        gate, _, _ = _block_worker(ex)
        assert ex.submit(QOS_BULK, lambda: order.append("b0")) is not None
        assert ex.submit(QOS_BULK, lambda: order.append("b1")) is not None
        assert ex.submit(QOS_BULK, lambda: 1) is None  # bound hit
        d = ex.submit(QOS_DEADLINE, lambda: order.append("deadline"))
        assert d is not None, "full bulk queue must not shed deadline"
        gate.set()
        d.result(2.0)
        assert order[0] == "deadline"

    def test_deadline_probe_defers_bulk(self, make_executor):
        """A deadline CLIENT (the verifier lane) holds the boundary
        through its probe: queued bulk waits while the probe reports
        pending work, runs when it clears, and the deferral is
        counted."""
        ex = make_executor()
        pending = [True]
        ex.register_deadline_probe(lambda: pending[0])
        ran = []
        f = ex.submit(QOS_BULK, lambda: ran.append("bulk"))
        time.sleep(0.08)
        assert ran == [], "bulk must defer to a pending deadline probe"
        pending[0] = False
        f.result(2.0)
        assert ran == ["bulk"]
        assert ex.deadline_deferrals >= 1

    def test_broken_probe_does_not_stall_bulk(self, make_executor):
        ex = make_executor()

        def bad_probe():
            raise RuntimeError("probe died")

        ex.register_deadline_probe(bad_probe)
        assert ex.submit(QOS_BULK, lambda: 7).result(2.0) == 7


class TestMaintenanceAging:
    def test_bulk_count_trip_promotes_maintenance(self, make_executor):
        """Bulk never starves maintenance forever: after
        max_bulk_between_maintenance consecutive bulk jobs the
        maintenance head runs even with bulk still queued."""
        ex = make_executor(
            aging_ms=60_000.0, max_bulk_between_maintenance=3
        )
        order = []
        gate, _, _ = _block_worker(ex)
        for i in range(8):
            ex.submit(QOS_BULK, lambda i=i: order.append(("bulk", i)))
        m = ex.submit(
            QOS_MAINTENANCE, lambda: order.append(("maint", 0))
        )
        gate.set()
        m.result(2.0)
        assert _wait_for(lambda: len(order) == 9)
        pos = order.index(("maint", 0))
        assert pos <= 3, (
            f"maintenance ran after {pos} bulk jobs; the count trip"
            " is 3"
        )
        assert ex.maintenance_aged >= 1

    def test_wall_clock_trip_promotes_maintenance(self, make_executor):
        ex = make_executor(
            aging_ms=30.0, max_bulk_between_maintenance=10_000
        )
        order = []
        gate, _, _ = _block_worker(ex)
        m = ex.submit(
            QOS_MAINTENANCE, lambda: order.append("maint")
        )
        ex.submit(QOS_BULK, lambda: order.append("bulk"))
        time.sleep(0.08)  # age the maintenance head past 30ms
        gate.set()
        m.result(2.0)
        assert order[0] == "maint", order

    def test_fresh_maintenance_waits_behind_bulk(self, make_executor):
        """The other side of aging: un-aged maintenance yields to
        queued bulk (bulk is still the higher class)."""
        ex = make_executor(
            aging_ms=60_000.0, max_bulk_between_maintenance=10_000
        )
        order = []
        gate, _, _ = _block_worker(ex)
        ex.submit(QOS_MAINTENANCE, lambda: order.append("maint"))
        ex.submit(QOS_BULK, lambda: order.append("bulk"))
        gate.set()
        assert _wait_for(lambda: len(order) == 2)
        assert order == ["bulk", "maint"]


class TestMaintenanceCheckpoint:
    def test_checkpoint_yields_while_deadline_pending(
        self, make_executor
    ):
        ex = make_executor()
        evt = threading.Event()
        ex.register_deadline_probe(lambda: not evt.is_set())
        threading.Timer(0.08, evt.set).start()
        t0 = time.monotonic()
        yielded = ex.maintenance_checkpoint(timeout_s=2.0)
        waited = time.monotonic() - t0
        assert yielded
        assert waited >= 0.05, "checkpoint must block while pending"
        assert ex.maintenance_yields == 1

    def test_checkpoint_noop_when_quiet(self, make_executor):
        ex = make_executor()
        t0 = time.monotonic()
        assert ex.maintenance_checkpoint(timeout_s=2.0) is False
        assert time.monotonic() - t0 < 0.5
        assert ex.maintenance_yields == 0

    def test_checkpoint_timeout_bounds_the_wait(self, make_executor):
        ex = make_executor()
        ex.register_deadline_probe(lambda: True)  # never clears
        t0 = time.monotonic()
        assert ex.maintenance_checkpoint(timeout_s=0.05) is True
        assert time.monotonic() - t0 < 1.0


class TestWarmupYieldsToDeadline:
    def test_warmup_waits_for_pending_deadline_between_compiles(
        self, monkeypatch, make_executor
    ):
        """Satellite bugfix regression (stubbed kernels): node-start
        warmup wired as a maintenance client yields between compiles
        while deadline work is queued — each compile starts only
        after the live traffic it would have raced has cleared."""
        ex = make_executor()
        evt = threading.Event()
        ex.register_deadline_probe(lambda: not evt.is_set())
        K.set_maintenance_gate(ex.maintenance_checkpoint)
        monkeypatch.setattr(K, "_INGEST_WARM", set())
        warmed = []
        monkeypatch.setattr(
            K,
            "_warm_one",
            lambda b, same_message: warmed.append(
                (b, same_message, evt.is_set())
            ),
        )
        threading.Timer(0.08, evt.set).start()
        K.warmup_ingest(sizes=(8, 16), block=True)
        assert len(warmed) == 4  # batch + same_message per size
        assert all(cleared for _, _, cleared in warmed), (
            "a compile started while deadline work was pending:"
            f" {warmed}"
        )
        assert ex.maintenance_yields >= 1

    def test_warmup_runs_immediately_with_no_gate(self, monkeypatch):
        K.set_maintenance_gate(None)
        monkeypatch.setattr(K, "_INGEST_WARM", set())
        warmed = []
        monkeypatch.setattr(
            K,
            "_warm_one",
            lambda b, same_message: warmed.append(b),
        )
        K.warmup_ingest(sizes=(8,), block=True, same_message=False)
        assert warmed == [8]

    def test_broken_gate_never_kills_warmup(self, monkeypatch):
        def bad_gate():
            raise RuntimeError("gate died")

        K.set_maintenance_gate(bad_gate)
        monkeypatch.setattr(K, "_INGEST_WARM", set())
        warmed = []
        monkeypatch.setattr(
            K,
            "_warm_one",
            lambda b, same_message: warmed.append(b),
        )
        K.warmup_ingest(sizes=(8,), block=True, same_message=False)
        assert warmed == [8]


class _CountingHoldVerifier:
    """Verifier stub that counts hold_intake entries (the legacy
    drift-monitor path) and reports quiescence."""

    def __init__(self, quiet=True):
        self.quiet = quiet
        self.holds = 0

    def hold_intake(self):
        import contextlib

        self.holds += 1
        return contextlib.nullcontext()

    def is_quiescent(self):
        return self.quiet

    def can_accept_work(self):
        return True


def _mk_monitor(executor=None, verifier=None, tuned=None):
    sink = tuned if tuned is not None else []
    tuner = SimpleNamespace(
        tune=lambda trigger: sink.append(trigger),
        verifier=verifier,
    )
    return AT.DriftMonitor(
        tuner,
        telemetry=None,
        verifier=verifier,
        shares={"stage": 1.0},
        clock=time.monotonic,
        executor=executor,
    )


class TestDrainForRetune:
    def test_retune_through_drain_zero_hold_intake(
        self, make_executor
    ):
        """THE acceptance criterion: with an executor wired, a drift
        re-tune completes through executor drain with zero calls to
        hold_intake — and intake reopens afterward."""
        ex = make_executor()
        v = _CountingHoldVerifier(quiet=True)
        ex.register_quiescence_probe(v.is_quiescent)
        tuned = []
        mon = _mk_monitor(executor=ex, verifier=v, tuned=tuned)
        mon.pending_stage = "stage"
        assert mon.maybe_retune() is True
        assert tuned == ["drift:stage"]
        assert v.holds == 0, "executor path must never hold_intake"
        assert mon.retunes == 1
        assert ex.drains == 1
        assert ex.intake_open()

    def test_retune_blocked_until_quiescent(self, make_executor):
        ex = make_executor(drain_timeout_s=0.05)
        v = _CountingHoldVerifier(quiet=False)
        ex.register_quiescence_probe(v.is_quiescent)
        tuned = []
        mon = _mk_monitor(executor=ex, verifier=v, tuned=tuned)
        mon.pending_stage = "stage"
        assert mon.maybe_retune() is False
        assert tuned == []
        assert mon.retunes_blocked == 1
        assert mon.pending_stage == "stage"  # stays pending
        assert ex.drains_blocked == 1
        assert ex.intake_open()
        # the device quiets down: the retry fires
        v.quiet = True
        assert mon.maybe_retune() is True
        assert tuned == ["drift:stage"]
        assert v.holds == 0

    def test_drain_closes_every_intake_and_sheds_counted(
        self, make_executor
    ):
        ex = make_executor()
        with ex.drained(timeout_s=1.0) as quiet:
            assert quiet
            for cls in X.QOS_CLASSES:
                assert not ex.can_accept_work(cls)
            assert ex.submit(QOS_BULK, lambda: 1) is None
        assert ex.shed_counts()[(QOS_BULK, "drain")] == 1
        for cls in X.QOS_CLASSES:
            assert ex.can_accept_work(cls)

    def test_legacy_hold_intake_path_without_executor(self):
        v = _CountingHoldVerifier(quiet=True)
        tuned = []
        mon = _mk_monitor(executor=None, verifier=v, tuned=tuned)
        mon.pending_stage = "stage"
        assert mon.maybe_retune() is True
        assert tuned == ["drift:stage"]
        assert v.holds == 1, "executor-less monitors keep hold_intake"


class TestCloseSemantics:
    def test_running_job_completes_queued_jobs_shed(self):
        ex = DeviceExecutor()
        gate, _, gated = _block_worker(ex)
        queued = ex.submit(QOS_BULK, lambda: 1)
        ex.close(timeout_s=0.05)  # worker still on the gate job
        gate.set()
        assert gated.result(2.0) == "gated"
        assert _wait_for(queued.cancelled)
        assert ex.shed_counts()[(QOS_BULK, "closed")] >= 1

    def test_submit_after_close_sheds(self):
        ex = DeviceExecutor()
        ex.close(timeout_s=1.0)
        assert ex.submit(QOS_DEADLINE, lambda: 1) is None
        assert not ex.can_accept_work(QOS_DEADLINE)
        assert ex.shed_counts()[(QOS_DEADLINE, "closed")] == 1
        ex.close(timeout_s=1.0)  # idempotent


class TestExecutorMetrics:
    def test_collectors_populate_registry(self, make_executor):
        from lodestar_tpu.metrics import (
            RegistryMetricCreator,
            create_lodestar_metrics,
        )

        reg = RegistryMetricCreator()
        m = create_lodestar_metrics(reg)
        ex = make_executor()
        X.bind_executor_collectors(m.device_executor, ex)
        ex.submit(QOS_BULK, lambda: 1).result(2.0)
        ex.note_shed(QOS_DEADLINE, "gossip_aggregate")
        text = reg.expose()
        assert (
            'lodestar_device_sheds_total{cls="deadline",'
            'reason="gossip_aggregate"} 1' in text
        )
        assert (
            'lodestar_device_executor_completed_total{cls="bulk"} 1'
            in text
        )
        assert (
            'lodestar_device_executor_queue_depth{cls="deadline"} 0'
            in text
        )
        assert (
            'lodestar_device_executor_latency_p99_seconds{cls="bulk"}'
            in text
        )
        assert "lodestar_device_executor_intake_open 1" in text
        assert "lodestar_device_executor_drains_total 0" in text


# ---------------------------------------------------------------------------
# verifier integration (stubbed kernels, trickle-test style)
# ---------------------------------------------------------------------------


def _mk_sets(n, msg_prefix=b"dx_"):
    from lodestar_tpu.crypto.bls import signature as sig

    out = []
    for i in range(n):
        sk = 7000 + i
        msg = msg_prefix + bytes([i]) + b"\x00" * (
            32 - len(msg_prefix) - 1
        )
        out.append(
            SignatureSet(sig.sk_to_pk(sk), msg, sig.sign(sk, msg))
        )
    return out


def _stub_ingest(monkeypatch, calls):
    """Shape-recording stubs for every entry point the verifier can
    dispatch to — single-host AND mesh (conftest forces 8 virtual
    devices, so divisible buckets route to the mesh programs)."""
    import jax.numpy as jnp

    monkeypatch.setattr(K, "_INGEST_WARM", set())

    def fake_batch(pk, sig_x, sig_sign, u0, u1, bits, mask):
        calls.append(("batch", int(mask.shape[0])))
        return jnp.asarray(True)

    def fake_same_message(pk, h, sig_x, sig_sign, bits, mask):
        calls.append(("same_message", int(mask.shape[0])))
        return jnp.asarray(True)

    def fake_batch_mesh(mesh, pk, sig_x, sig_sign, u0, u1, bits, mask):
        calls.append(("batch", int(mask.shape[0])))
        return jnp.asarray(True)

    def fake_same_message_mesh(mesh, pk, h, sig_x, sig_sign, bits, mask):
        calls.append(("same_message", int(mask.shape[0])))
        return jnp.asarray(True)

    monkeypatch.setattr(K, "run_verify_batch_ingest_async", fake_batch)
    monkeypatch.setattr(
        K, "run_verify_same_message_ingest_async", fake_same_message
    )
    monkeypatch.setattr(
        K, "run_verify_batch_ingest_mesh", fake_batch_mesh
    )
    monkeypatch.setattr(
        K, "run_verify_same_message_mesh", fake_same_message_mesh
    )


class TestVerifierIntegration:
    def test_latency_histogram_reexport(self):
        assert V.LatencyHistogram is X.LatencyHistogram

    def test_bulk_defers_while_verifier_has_pending_work(
        self, monkeypatch, make_executor
    ):
        """The cross-client acceptance shape: while a gossip job sits
        in the verifier's rolling bucket (deadline work pending), a
        bulk job submitted to the executor does NOT run; it runs
        after the deadline flush clears the verifier."""
        calls = []
        _stub_ingest(monkeypatch, calls)
        ex = make_executor()
        ran = []

        async def go():
            v = TpuBlsVerifier(
                max_buffer_wait_ms=1,
                ingest_min_bucket=4,
                latency_budget_ms=250,
            )
            v.attach_executor(ex)
            # 2 sets stay under the 4-set flush target: the job waits
            # in the rolling bucket for the deadline flush
            fut = asyncio.ensure_future(
                v.verify_signature_sets(_mk_sets(2), batchable=True)
            )
            # let the job land in the rolling bucket
            deadline = time.monotonic() + 2.0
            while (
                not v.has_pending_deadline_work()
                and time.monotonic() < deadline
            ):
                await asyncio.sleep(0.002)
            assert v.has_pending_deadline_work()
            bulk = ex.submit(QOS_BULK, lambda: ran.append("bulk"))
            assert bulk is not None
            await asyncio.sleep(0.05)
            assert ran == [], (
                "bulk must defer while the verifier holds pending"
                " deadline work"
            )
            ok = await fut  # deadline flush fires, verdict lands
            assert ok is True
            bulk.result(2.0)
            assert ran == ["bulk"]
            await v.close()

        asyncio.run(go())
        assert ex.deadline_deferrals >= 1

    def test_depth2_verdicts_bit_identical_with_executor(
        self, monkeypatch, make_executor
    ):
        """Porting the verifier onto the executor must not change a
        single verdict: the same jobs through a depth-2 pipeline with
        and without an executor attached produce identical results
        and identical dispatch accounting."""

        async def run_jobs(attach):
            calls = []
            _stub_ingest(monkeypatch, calls)
            # ingest_min_bucket=2: every bucket (2/4/8) rides the
            # stubbed ingest entry points — no host-path cold compile
            v = TpuBlsVerifier(
                max_buffer_wait_ms=1,
                ingest_min_bucket=2,
                latency_budget_ms=0,
                pipeline_depth=2,
            )
            ex = None
            if attach:
                ex = make_executor()
                v.attach_executor(ex)
            jobs = [
                v.verify_signature_sets(_mk_sets(3, b"a_"), batchable=True),
                v.verify_signature_sets(_mk_sets(8, b"b_"), batchable=False),
                v.verify_signature_sets(_mk_sets(2, b"c_"), batchable=True),
            ]
            results = await asyncio.gather(*jobs)
            by_bucket, by_path = v.metrics.snapshot_dispatch()
            await v.close()
            return results, by_bucket, by_path, sorted(calls)

        r_plain = asyncio.run(run_jobs(attach=False))
        r_exec = asyncio.run(run_jobs(attach=True))
        assert r_exec[0] == r_plain[0] == [True, True, True]
        assert r_exec[1] == r_plain[1], "dispatch buckets diverged"
        assert r_exec[2] == r_plain[2], "dispatch paths diverged"
        assert r_exec[3] == r_plain[3], "kernel call shapes diverged"


# ---------------------------------------------------------------------------
# processor shed accounting (satellite bugfix)
# ---------------------------------------------------------------------------


class _RefusingVerifier:
    def can_accept_work(self):
        return False


def _mk_processor(executor):
    from lodestar_tpu.network.processor import NetworkProcessor

    fake_validator = SimpleNamespace(
        att_data_key=lambda data: "key"
    )
    return NetworkProcessor(
        chain=SimpleNamespace(),
        attestation_validator=fake_validator,
        verifier=_RefusingVerifier(),
        aggregate_validator=object(),
        sync_validator=object(),
        executor=executor,
    )


class TestProcessorShedAccounting:
    def test_rejection_sites_report_sheds(self, make_executor):
        """The four silent-drop sites now land on the executor's
        per-class accounting: every refusal is a deadline-class shed
        with a reason naming the site."""
        ex = make_executor()

        async def go():
            p = _mk_processor(ex)
            agg = SimpleNamespace(
                message=SimpleNamespace(aggregate=object())
            )
            await p.process_aggregate(agg)
            await p.process_sync_committee_message(object(), 0)
            await p.process_sync_contribution(object())
            # backpressure deferral: only counted with work waiting
            p.att_queue.add((SimpleNamespace(data=object()), None))
            assert await p._execute_work() is False

        asyncio.run(go())
        sheds = ex.shed_counts()
        assert sheds[(QOS_DEADLINE, "gossip_aggregate")] == 1
        assert sheds[(QOS_DEADLINE, "gossip_sync_message")] == 1
        assert sheds[(QOS_DEADLINE, "gossip_sync_contribution")] == 1
        assert sheds[(QOS_DEADLINE, "work_queue_backpressure")] == 1

    def test_no_executor_keeps_working(self):
        """Executor-less processors (tests, lean deployments) keep
        the old behavior: refusals count gossip metrics only."""

        async def go():
            p = _mk_processor(None)
            agg = SimpleNamespace(
                message=SimpleNamespace(aggregate=object())
            )
            action = await p.process_aggregate(agg)
            assert action.name == "IGNORE"
            assert p.ignored == 1

        asyncio.run(go())


# ---------------------------------------------------------------------------
# kzg bulk lane (crypto/kzg.py device tiers through the executor)
# ---------------------------------------------------------------------------


class TestKzgBulkLane:
    def test_device_msm_rides_bulk_lane(
        self, monkeypatch, make_executor
    ):
        from lodestar_tpu.crypto import kzg as KZ
        from lodestar_tpu.ops import msm as M

        ex = make_executor()
        KZ.set_executor(ex)
        KZ.set_msm_backend("device")
        threads = []

        def fake_msm_many(tasks):
            threads.append(threading.current_thread().name)
            return [pts[0] for pts, _ in tasks]

        monkeypatch.setattr(M, "g1_msm_many", fake_msm_many)
        from lodestar_tpu.crypto.bls import curve as oc

        before = KZ.msm_path_counts()["device"]
        out = KZ._g1_lincomb_many([([oc.G1_GEN], [1])])
        assert out == [oc.G1_GEN]
        assert threads == ["device-executor"], (
            "device MSM must execute on the executor's bulk lane"
        )
        assert KZ.msm_path_counts()["device"] == before + 1
        assert ex.completed[QOS_BULK] == 1

    def test_shed_bulk_falls_back_to_host_tier(
        self, monkeypatch, make_executor
    ):
        """An admission-control shed (bulk bound hit) must not fail
        the caller: the lincomb falls back to the host tiers and the
        fallback is counted like any device miss."""
        from lodestar_tpu.crypto import kzg as KZ

        ex = make_executor(queue_bounds={"bulk": 0})  # shed everything
        KZ.set_executor(ex)
        KZ.set_msm_backend("device")
        from lodestar_tpu.crypto.bls import curve as oc

        before = KZ.msm_path_counts()["device_fallbacks"]
        out = KZ._g1_lincomb_many([([oc.G1_GEN], [2])])
        assert out == [oc.g1_mul(oc.G1_GEN, 2)]
        assert (
            KZ.msm_path_counts()["device_fallbacks"] == before + 1
        )
        assert ex.shed_counts()[(QOS_BULK, "queue_full")] == 1
