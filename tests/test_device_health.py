"""Device fault domain (device/health.py): wave watchdog, error
taxonomy, circuit-broken quarantine with host failover, and live
probe reinstatement.

Every clocked assertion runs on the injectable ManualClock
(resilience/clock.py) — nothing here sleeps out a backoff. The
executor's watchdog is driven through its public `watchdog_check()`
instead of the poll thread for the same reason.
"""

import threading
import time
from concurrent.futures import CancelledError

import pytest

from lodestar_tpu.device.executor import DeviceExecutor
from lodestar_tpu.device.health import (
    DeviceHealthTracker,
    DeviceTimeout,
    HealthState,
    classify_device_error,
    default_ladder_shrink,
    default_watchdog_deadlines,
    watchdog_deadline_s,
)
from lodestar_tpu.resilience.clock import ManualClock


def _quiet_tracker(**kw):
    from types import SimpleNamespace

    kw.setdefault(
        "logger",
        SimpleNamespace(
            info=lambda *a, **k: None, warn=lambda *a, **k: None
        ),
    )
    kw.setdefault("ladder_shrink", lambda: False)
    return DeviceHealthTracker(**kw)


# ---------------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------------


class TestTaxonomy:
    def test_message_marker_routing(self):
        cases = {
            "RESOURCE_EXHAUSTED: Out of memory while trying to "
            "allocate 2G": "oom",
            "Mosaic compilation failed: unsupported lowering":
                "compile",
            "INTERNAL: device lost: TPU runtime halted":
                "device_lost",
            "UNAVAILABLE: TPU is preempted": "device_lost",
            "something nobody has seen before": "unknown",
        }
        for msg, want in cases.items():
            assert classify_device_error(RuntimeError(msg)) == want, msg

    def test_timeout_and_programming_types_win_over_markers(self):
        # a DeviceTimeout mentioning OOM is still a timeout; a
        # TypeError mentioning INTERNAL is still our bug
        assert (
            classify_device_error(DeviceTimeout("oom-ish wording"))
            == "timeout"
        )
        assert (
            classify_device_error(TypeError("INTERNAL: not really"))
            == "programming"
        )
        assert (
            classify_device_error(KeyError("pairing")) == "programming"
        )

    def test_record_fault_rejects_programming_errors(self):
        t = _quiet_tracker()
        with pytest.raises(ValueError):
            t.record_fault(TypeError("bug in our own prep code"))
        # nothing counted, nothing tripped
        assert t.faults == {} and t.state is HealthState.online

    def test_injected_faults_classify_like_real_ones(self):
        from lodestar_tpu.sim.faults import (
            _DEVICE_ERROR_MESSAGES,
            InjectedDeviceError,
        )

        for kind, msg in _DEVICE_ERROR_MESSAGES.items():
            if kind == "unknown":
                continue
            got = classify_device_error(InjectedDeviceError(msg))
            assert got == kind, (kind, msg, got)


# ---------------------------------------------------------------------------
# tracker state machine
# ---------------------------------------------------------------------------


class TestTrackerStateMachine:
    def test_consecutive_faults_quarantine(self):
        t = _quiet_tracker(failure_threshold=3)
        for _ in range(2):
            t.record_fault("device_lost")
            assert t.device_allowed()
        t.record_fault("device_lost")
        assert t.state is HealthState.quarantined
        assert not t.device_allowed()
        assert t.quarantines == 1
        assert t.faults["device_lost"] == 3

    def test_success_resets_consecutive_count(self):
        # flaky device: fault, success, fault, ... never quarantines
        t = _quiet_tracker(failure_threshold=2)
        for _ in range(5):
            t.record_fault("device_lost")
            t.record_success()
        assert t.device_allowed()
        assert t.quarantines == 0

    def test_oom_shrinks_ladder_before_quarantining(self):
        shrinks = [True, True, False]
        t = _quiet_tracker(
            failure_threshold=1,
            ladder_shrink=lambda: shrinks.pop(0),
        )
        # two OOMs are absorbed by ladder shrinks -> DEGRADED only
        t.record_fault("oom")
        t.record_fault("oom")
        assert t.state is HealthState.degraded
        assert t.device_allowed()
        assert t.oom_shrinks == 2
        # nothing left to shrink: the third OOM quarantines
        t.record_fault("oom")
        assert t.state is HealthState.quarantined
        assert t.oom_shrinks == 2

    def test_default_ladder_shrink_steps_down_one_rung(self):
        from lodestar_tpu.bls import kernels as K

        ladder, top = K.BUCKET_LADDER, K.ladder_top()
        try:
            K.set_ladder_top(2048, rewarm=False)
            assert default_ladder_shrink() is True
            assert K.ladder_top() == 1024
            assert default_ladder_shrink() is True
            assert K.ladder_top() == 512
            # at the floor: nothing left to give back
            assert default_ladder_shrink() is False
            assert K.ladder_top() == 512
        finally:
            K.BUCKET_LADDER = ladder
            K.set_ladder_top(top, rewarm=False)

    def test_compile_failure_quarantines_only_the_program(self):
        t = _quiet_tracker(failure_threshold=1)
        t.record_fault("compile", client="bls", program="pairing")
        assert t.program_quarantined("pairing")
        assert not t.program_quarantined("prepare")
        # the device itself stays live (degraded, not quarantined)
        assert t.state is HealthState.degraded
        assert t.device_allowed()

    def test_failover_logs_once_per_transition(self):
        t = _quiet_tracker(failure_threshold=1)
        t.record_fault("device_lost")
        assert t.note_failover("bls") is True  # first after transition
        assert t.note_failover("bls") is False  # same epoch: silent
        assert t.note_failover("kzg_msm") is True  # per-client gate
        assert t.failover_dispatches == {"bls": 2, "kzg_msm": 1}


# ---------------------------------------------------------------------------
# probe reinstatement
# ---------------------------------------------------------------------------


class TestProbeReinstatement:
    def _quarantined(self, clock, **kw):
        kw.setdefault("failure_threshold", 1)
        kw.setdefault("quarantine_reset_s", 1.0)
        kw.setdefault("probe_successes", 2)
        t = _quiet_tracker(clock=clock, **kw)
        t.record_fault("device_lost")
        assert t.state is HealthState.quarantined
        return t

    def test_probe_waits_out_the_backoff(self):
        clock = ManualClock()
        t = self._quarantined(clock)
        assert t.maybe_probe(lambda: True) is None  # backoff running
        clock.advance(1.1)
        assert t.maybe_probe(lambda: True) is True

    def test_success_streak_reinstates_and_rekicks_warmup(self):
        clock = ManualClock()
        kicked = []
        t = self._quarantined(clock, warmup_kick=lambda: kicked.append(1))
        clock.advance(1.1)
        assert t.maybe_probe(lambda: True) is True
        assert t.state is HealthState.probing  # 1 of 2 successes
        assert not t.device_allowed()  # live waves stay off the chip
        assert t.maybe_probe(lambda: True) is True
        assert t.state is HealthState.online
        assert t.device_allowed()
        assert t.reinstatements == 1
        assert kicked == [1]
        assert t.probes == {"success": 2, "failure": 0}

    def test_probe_failure_retrips_and_doubles_backoff(self):
        clock = ManualClock()
        t = self._quarantined(clock, max_backoff_s=3.0)
        clock.advance(1.1)

        def boom():
            raise RuntimeError("INTERNAL: still dead")

        assert t.maybe_probe(boom) is False
        assert t.state is HealthState.quarantined
        assert t.breaker.reset_timeout == 2.0  # doubled
        assert t.maybe_probe(lambda: True) is None  # new backoff
        clock.advance(2.1)
        assert t.maybe_probe(lambda: True) is True
        # a failure mid-streak resets the streak
        def late_boom():
            raise RuntimeError("ABORTED: flaked mid-probe")

        assert t.maybe_probe(late_boom) is False
        clock.advance(3.1)  # capped at max_backoff_s=3.0
        assert t.maybe_probe(lambda: True) is True
        assert t.maybe_probe(lambda: True) is True
        assert t.state is HealthState.online
        # reinstatement restores the base backoff for the next incident
        assert t.breaker.reset_timeout == 1.0


# ---------------------------------------------------------------------------
# executor watchdog
# ---------------------------------------------------------------------------


class TestExecutorWatchdog:
    def test_deadlines_derive_from_fused_budget(self):
        d = default_watchdog_deadlines()
        assert d["maintenance"] is None
        assert d["bulk"] == watchdog_deadline_s("bulk")
        assert 0 < d["deadline"] < d["bulk"]

    def test_trip_fails_future_and_replaces_worker(self):
        clock = ManualClock()
        tracker = _quiet_tracker(failure_threshold=5)
        ex = DeviceExecutor(
            clock=clock.monotonic,
            watchdog_deadlines={"bulk": 5.0},
        )
        ex.set_health_tracker(tracker)
        started, release = threading.Event(), threading.Event()

        def hung():
            started.set()
            release.wait(10.0)
            return "late"

        try:
            fut = ex.submit("bulk", hung)
            assert started.wait(2.0)
            assert ex.watchdog_check() == []  # within deadline: clear
            clock.advance(10.0)
            assert ex.watchdog_check() == ["bulk"]
            with pytest.raises(DeviceTimeout):
                fut.result(timeout=2.0)
            assert ex.watchdog_trips["bulk"] == 1
            assert tracker.watchdog_trips["bulk"] == 1
            assert tracker.faults.get("timeout") == 1
            # the replacement worker keeps the queue moving while the
            # stuck thread is still blocked inside fn
            nxt = ex.submit("bulk", lambda: 42)
            assert nxt.result(timeout=2.0) == 42
        finally:
            release.set()
            ex.close()

    def test_late_return_of_abandoned_job_is_discarded(self):
        clock = ManualClock()
        ex = DeviceExecutor(clock=clock.monotonic)
        started, release = threading.Event(), threading.Event()

        def hung():
            started.set()
            release.wait(10.0)
            return "late"

        try:
            # per-job deadline override (no per-class config needed)
            fut = ex.submit("deadline", hung, timeout_s=1.0)
            assert started.wait(2.0)
            clock.advance(2.0)
            assert ex.watchdog_check() == ["deadline"]
            with pytest.raises(DeviceTimeout):
                fut.result(timeout=2.0)
            # the hung fn now returns: first writer (the watchdog)
            # won — the late result must not clobber the DeviceTimeout
            release.set()
            time.sleep(0.05)
            with pytest.raises(DeviceTimeout):
                fut.result(timeout=2.0)
        finally:
            release.set()
            ex.close()

    def test_close_survives_permanently_hung_job(self):
        ex = DeviceExecutor()
        started = threading.Event()
        release = threading.Event()

        def hung():
            started.set()
            release.wait(30.0)

        try:
            ex.submit("bulk", hung)
            assert started.wait(2.0)
            queued = ex.submit("bulk", lambda: "never-runs")
            t0 = time.monotonic()
            ex.close(timeout_s=0.2)
            assert time.monotonic() - t0 < 5.0  # returned, not wedged
            assert ex.close_timeouts == 1
            # queued futures were cancelled here, not leaked as
            # forever-pending behind the hung worker
            with pytest.raises(CancelledError):
                queued.result(timeout=0.5)
        finally:
            release.set()


# ---------------------------------------------------------------------------
# node-wide failover: bit-identical verdicts off a quarantined device
# ---------------------------------------------------------------------------


def _mk_sets(n, msg_prefix=b"dh_", good=True):
    from lodestar_tpu.bls import SignatureSet
    from lodestar_tpu.crypto.bls import signature as sig

    out = []
    for i in range(n):
        sk = 7000 + i
        msg = msg_prefix + bytes([i]) + b"\x00" * (
            32 - len(msg_prefix) - 1
        )
        s = sig.sign(sk, msg)
        if not good and i == n - 1:
            b = bytearray(s)
            b[20] ^= 0xFF
            s = bytes(b)
        out.append(SignatureSet(sig.sk_to_pk(sk), msg, s))
    return out


class TestQuarantineFailover:
    def _quarantined_tracker(self):
        t = _quiet_tracker(failure_threshold=1)
        t.record_fault("device_lost", client="bls")
        assert not t.device_allowed()
        return t

    def test_batch_verdicts_bit_identical_to_oracle(self):
        import asyncio

        from lodestar_tpu.bls import OracleBlsVerifier, TpuBlsVerifier

        tracker = self._quarantined_tracker()

        async def go(sets):
            tpu = TpuBlsVerifier(max_buffer_wait_ms=5, mesh=False)
            tpu.attach_health(tracker, wave_timeout_s=0)
            orc = OracleBlsVerifier()
            a = await tpu.verify_signature_sets(sets)
            b = await orc.verify_signature_sets(sets)
            paths = dict(tpu.metrics.dispatch_by_path)
            await tpu.close()
            return a, b, paths

        a, b, paths = asyncio.run(go(_mk_sets(3)))
        assert a is b is True
        # every bucket rode the failover path — zero device dispatches
        assert paths["failover"] >= 1
        assert paths["ingest"] == 0 and paths["host"] == 0

        a, b, _ = asyncio.run(go(_mk_sets(3, good=False)))
        assert a is b is False

    def test_same_message_verdicts_bit_identical_to_oracle(self):
        import asyncio

        from lodestar_tpu.bls import (
            OracleBlsVerifier,
            SameMessageSet,
            TpuBlsVerifier,
        )
        from lodestar_tpu.crypto.bls import signature as sig

        tracker = self._quarantined_tracker()
        msg = b"same-message-failover".ljust(32, b"\x00")
        pairs = []
        for i in range(4):
            sk = 7100 + i
            # index 2 signed by the wrong key: valid point, wrong sig
            s = sig.sign(sk + 1 if i == 2 else sk, msg)
            pairs.append(SameMessageSet(sig.sk_to_pk(sk), s))

        async def go():
            tpu = TpuBlsVerifier(max_buffer_wait_ms=5, mesh=False)
            tpu.attach_health(tracker, wave_timeout_s=0)
            orc = OracleBlsVerifier()
            a = await tpu.verify_signature_sets_same_message(pairs, msg)
            b = await orc.verify_signature_sets_same_message(pairs, msg)
            await tpu.close()
            return a, b

        a, b = asyncio.run(go())
        assert a == b == [True, True, False, True]

    def test_kzg_health_gate_blocks_device_tier(self):
        from lodestar_tpu.crypto import kzg

        tracker = self._quarantined_tracker()
        kzg.set_health_tracker(tracker)
        try:
            # the MSM/Fr device tiers consult the gate before
            # dispatching; a blocked dispatch is a counted failover
            assert kzg._device_blocked("kzg_msm") is True
            assert kzg._device_blocked("kzg_fr") is True
            assert tracker.failover_dispatches == {
                "kzg_msm": 1, "kzg_fr": 1,
            }
            # programming errors re-raise at the call site; device
            # errors feed the taxonomy and keep counting fallbacks
            with pytest.raises(TypeError):
                kzg._report_device_fault(
                    TypeError("our own bug"), "kzg_msm"
                )
            kzg._report_device_fault(
                RuntimeError("INTERNAL: device lost"), "kzg_msm"
            )
            assert tracker.faults["device_lost"] == 2
        finally:
            kzg.set_health_tracker(None)
        assert kzg._device_blocked("kzg_msm") is False


# ---------------------------------------------------------------------------
# autotune freeze
# ---------------------------------------------------------------------------


class TestAutotuneFreeze:
    def test_tune_suspends_while_quarantined(self):
        from types import SimpleNamespace

        from lodestar_tpu.device import autotune as AT

        t = _quiet_tracker(failure_threshold=1)
        t.record_fault("device_lost")
        quiet = SimpleNamespace(
            info=lambda *a, **k: None, warn=lambda *a, **k: None
        )
        bench = lambda backend, bucket: AT.Measurement(
            backend=backend, bucket=bucket, pipeline="batch",
            seconds_per_dispatch=0.01, sets_per_sec=400.0,
            runs=3, warm_seconds=0.0,
        )
        tuner = AT.DeviceAutotuner(
            grid=AT.parse_grid("backend=vpu"), bench=bench,
            artifact_path=None, logger=quiet, health=t,
        )
        d = tuner.tune(trigger="startup")
        assert d["source"] == "suspended"
        assert tuner.suspended_runs == 1
        assert tuner.candidates_measured == 0  # no probe touched it

    def test_drift_retune_defers_then_lands(self):
        from types import SimpleNamespace

        from lodestar_tpu.device import autotune as AT

        t = _quiet_tracker(
            failure_threshold=1, quarantine_reset_s=1.0,
            probe_successes=1, clock=ManualClock(),
        )
        quiet = SimpleNamespace(
            info=lambda *a, **k: None, warn=lambda *a, **k: None
        )
        bench = lambda backend, bucket: AT.Measurement(
            backend=backend, bucket=bucket, pipeline="batch",
            seconds_per_dispatch=0.01, sets_per_sec=400.0,
            runs=3, warm_seconds=0.0,
        )

        class Knobs:
            budget = 50.0

            def set_latency_budget_ms(self, ms):
                self.budget = ms

            def latency_budget_ms(self):
                return self.budget

            def is_quiescent(self):
                return True

            def pipeline_depth(self):
                return 2

            def set_pipeline_depth(self, d):
                pass

        from lodestar_tpu.bls import kernels as K
        from lodestar_tpu.device.autotune import _APPLIED
        from lodestar_tpu.ops import limbs as L
        from lodestar_tpu.ops import msm as M

        gate, warm = K.INGEST_MIN_BUCKET, set(K._INGEST_WARM)
        ladder, started = K.BUCKET_LADDER, K._WARMUP_STARTED
        backend, window = L.get_backend(), M.msm_window()
        try:
            tuner = AT.DeviceAutotuner(
                verifier=Knobs(), grid=AT.parse_grid("backend=vpu"),
                bench=bench, artifact_path=None, logger=quiet,
                health=t,
            )
            mon = AT.DriftMonitor(
                tuner, SimpleNamespace(
                    snapshot_stage_seconds=lambda: ({}, {})
                ), verifier=Knobs(),
            )
            mon.pending_stage = "pairing"
            t.record_fault("device_lost")
            assert mon.maybe_retune() is False  # deferred, not lost
            assert mon.retunes_blocked == 1
            assert mon.pending_stage == "pairing"
            # reinstate, then the SAME pending re-tune lands
            t.clock.advance(1.1)
            assert t.maybe_probe(lambda: True) is True
            assert t.device_allowed()
            assert mon.maybe_retune() is True
            assert mon.retunes == 1
        finally:
            K.INGEST_MIN_BUCKET = gate
            K.BUCKET_LADDER = ladder
            K._INGEST_WARM.clear()
            K._INGEST_WARM.update(warm)
            K._WARMUP_STARTED = started
            if L.get_backend() != backend:
                L.set_backend(backend)
            import lodestar_tpu.device.autotune as _at

            _at._APPLIED = _APPLIED
            M.set_msm_window(window)


# ---------------------------------------------------------------------------
# warmup gate
# ---------------------------------------------------------------------------


class TestWarmupGate:
    def test_warmup_suspends_while_quarantined(self):
        from lodestar_tpu.bls import kernels as K

        t = _quiet_tracker(failure_threshold=1)
        t.record_fault("device_lost")
        K.set_health_gate(t.device_allowed)
        try:
            assert K._device_dispatch_allowed() is False
            t2 = _quiet_tracker()
            K.set_health_gate(t2.device_allowed)
            assert K._device_dispatch_allowed() is True
        finally:
            K.set_health_gate(None)
        assert K._device_dispatch_allowed() is True


# ---------------------------------------------------------------------------
# wave watchdog: hung device work trips it, a compile does not
# ---------------------------------------------------------------------------


class TestWatchdogSparesCompiles:
    """A program's first dispatch at a bucket shape compiles inside the
    dispatch — minutes on the chip — and the retry rungs a node's first
    bad signature dispatches may be cold. The watchdog does not watch a
    wave that compiles, gives a wave of many buckets the deadline of
    each, and still trips on a dispatch that hangs once its program is
    compiled."""

    def _verify(self, monkeypatch, slow_calls, delay=0.8, jobs=None,
                false_calls=()):
        import asyncio

        import jax.numpy as jnp

        from lodestar_tpu.bls import TpuBlsVerifier
        from lodestar_tpu.bls import kernels as K

        calls = []

        def device(*args):
            calls.append(1)
            if len(calls) in slow_calls:
                time.sleep(delay)  # one bucket's device time
            return jnp.asarray(len(calls) not in false_calls)

        monkeypatch.setattr(K, "run_verify_batch_async", device)
        # every program of this process starts cold
        monkeypatch.setattr(K, "_INGEST_WARM", set())
        tracker = _quiet_tracker()
        if jobs is None:
            jobs = [_mk_sets(2, msg_prefix=p) for p in (b"wd1", b"wd2")]

        async def go():
            v = TpuBlsVerifier(max_buffer_wait_ms=5, mesh=False)
            v.attach_health(tracker, wave_timeout_s=0.25)
            oks = [await v.verify_signature_sets(j) for j in jobs]
            paths = dict(v.metrics.dispatch_by_path)
            await v.close()
            return oks, paths

        oks, paths = asyncio.run(go())
        return oks, paths, tracker

    def test_first_call_compile_does_not_trip(self, monkeypatch):
        oks, paths, tracker = self._verify(monkeypatch, slow_calls={1})
        assert oks == [True, True]
        assert paths["failover"] == 0 and paths["host"] == 2
        assert tracker.watchdog_trips.get("deadline", 0) == 0
        assert tracker.state is HealthState.online

    def test_hang_of_a_compiled_program_trips(self, monkeypatch):
        oks, paths, tracker = self._verify(monkeypatch, slow_calls={2})
        # the hung wave's verdict came from the host oracle
        assert oks == [True, True]
        assert tracker.watchdog_trips.get("deadline", 0) == 1

    def test_many_bucket_wave_gets_a_deadline_per_bucket(
        self, monkeypatch
    ):
        """A six-set job whose bucket and per-job retry fail fans out
        into six one-set buckets of one retry wave, 0.1 s each on the
        device: past one bucket's 0.25 s deadline, inside six. A first
        job compiles the one-set rung beforehand."""
        oks, paths, tracker = self._verify(
            monkeypatch,
            slow_calls=set(range(4, 10)),
            delay=0.1,
            jobs=[
                _mk_sets(2, msg_prefix=b"wd_warm"),
                _mk_sets(6, msg_prefix=b"wd_fan"),
            ],
            false_calls={2, 3},
        )
        assert oks == [True, True]
        assert paths["failover"] == 0
        assert tracker.watchdog_trips.get("deadline", 0) == 0
