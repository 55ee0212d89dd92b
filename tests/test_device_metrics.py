"""Device/compiler telemetry (metrics/device.py, ISSUE 10).

Acceptance coverage:
  * a FORCED RETRACE (recompile of an argument signature a stage
    entry point already served) is visibly distinguished on /metrics
    (`lodestar_jax_retraces_total{stage}`);
  * a COLD vs WARM persistent compilation cache is visibly
    distinguished (`lodestar_jax_persistent_cache_{hits,misses}_total`);
  * the warmup-progress gauge tracks the kernels' warm registry with
    stubbed state;
  * `POST /eth/v1/lodestar/device_trace` returns a capture (profiler
    stubbed in tier 1 — a real CPU capture costs ~30 s and runs in
    the slow tier).
"""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import pytest

from lodestar_tpu.metrics import (
    RegistryMetricCreator,
    Tracer,
    create_lodestar_metrics,
)
from lodestar_tpu.metrics import device as D


@pytest.fixture()
def telemetry():
    """Fresh singleton + bound registry; the previous singleton is
    restored so tests never see each other's compiles."""
    prev = D.get_telemetry()
    reg = RegistryMetricCreator()
    m = create_lodestar_metrics(reg)
    tele = D.set_telemetry(D.DeviceTelemetry())
    D.install(metrics=m.device)
    D.bind_collectors(m.device, tele)
    try:
        yield tele, reg, m
    finally:
        D.set_telemetry(prev)


class TestRetraceDetection:
    def test_first_compile_is_not_a_retrace(self, telemetry):
        tele, reg, m = telemetry
        f = D.instrument_stage(
            "rt_stage", jax.jit(lambda x: x * 2.0 + 1.0)
        )
        f(jnp.ones((4,)))
        f(jnp.ones((4,)))  # in-memory cache hit: no compile
        compiles, _, retraces = tele.snapshot_compiles()
        assert compiles.get("rt_stage") == 1
        assert retraces == {}

    def test_forced_retrace_lands_on_metrics(self, telemetry):
        """The acceptance scenario: the same entry point recompiling
        an already-served signature (what a clear_caches() or limb
        backend switch storm looks like) increments
        lodestar_jax_retraces_total{stage} — a NEW shape does not."""
        tele, reg, m = telemetry
        inner = jax.jit(lambda x: x * 3.0 - 1.0)
        f = D.instrument_stage("rt_forced", inner)
        f(jnp.ones((4,)))
        inner.clear_cache()  # the forced retrace
        f(jnp.ones((4,)))
        f(jnp.ones((8,)))  # fresh signature: compile, NOT a retrace
        compiles, _, retraces = tele.snapshot_compiles()
        assert compiles.get("rt_forced") == 3
        assert retraces.get("rt_forced") == 1
        text = reg.expose()
        assert (
            'lodestar_jax_retraces_total{stage="rt_forced"} 1' in text
        )
        assert (
            'lodestar_jax_compiles_total{stage="rt_forced"} 3' in text
        )

    def test_backend_switch_counted(self, telemetry):
        tele, reg, m = telemetry
        from lodestar_tpu.ops import limbs

        # flip to the same backend: not a switch
        limbs.set_backend(limbs.get_backend())
        assert tele.backend_switches == 0
        tele.note_backend_switch()
        assert "lodestar_jax_backend_switches_total 1" in reg.expose()

    def test_disabled_telemetry_is_passthrough(self, telemetry):
        tele, reg, m = telemetry
        tele.set_timing("off")
        f = D.instrument_stage("off_stage", jax.jit(lambda x: x + 1))
        f(jnp.ones((2,)))
        assert "off_stage" not in tele.snapshot_compiles()[0]
        assert "off_stage" not in tele.dispatch_count


class TestPersistentCacheCounters:
    def test_cold_then_warm_cache_distinguished(self, telemetry, tmp_path):
        """Acceptance: a cold persistent cache shows misses and zero
        hits; after the in-memory executable is dropped the SAME
        compile is served from disk and shows as a hit."""
        tele, reg, m = telemetry
        cfg = jax.config
        prev_dir = cfg.jax_compilation_cache_dir
        prev_min = cfg.jax_persistent_cache_min_compile_time_secs
        prev_size = cfg.jax_persistent_cache_min_entry_size_bytes
        from jax._src.compilation_cache import reset_cache

        try:
            cfg.update("jax_compilation_cache_dir", str(tmp_path))
            cfg.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            cfg.update("jax_persistent_cache_min_entry_size_bytes", 0)
            reset_cache()
            inner = jax.jit(lambda x: x * 5.0 + 2.0)
            f = D.instrument_stage("pc_stage", inner)
            f(jnp.ones((16,)))
            cold = reg.expose()
            assert tele.cache_misses >= 1 and tele.cache_hits == 0
            assert (
                "lodestar_jax_persistent_cache_misses_total "
                f"{tele.cache_misses}" in cold
            )
            assert "lodestar_jax_persistent_cache_hits_total 0" in cold
            inner.clear_cache()  # drop the in-memory executable only
            f(jnp.ones((16,)))  # compile request served from disk
            assert tele.cache_hits >= 1
            warm = reg.expose()
            assert (
                "lodestar_jax_persistent_cache_hits_total "
                f"{tele.cache_hits}" in warm
            )
        finally:
            cfg.update("jax_compilation_cache_dir", prev_dir)
            cfg.update(
                "jax_persistent_cache_min_compile_time_secs", prev_min
            )
            cfg.update(
                "jax_persistent_cache_min_entry_size_bytes", prev_size
            )
            reset_cache()

    def test_jaxcache_enable_failure_is_counted(
        self, telemetry, tmp_path, monkeypatch
    ):
        """Satellite: utils/jaxcache.enable() must not no-op silently —
        an unwritable cache dir increments
        lodestar_jax_persistent_cache_errors_total."""
        tele, reg, m = telemetry
        from lodestar_tpu.utils import jaxcache

        blocker = tmp_path / "not_a_dir"
        blocker.write_text("file where the cache dir should go")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jaxcache, "_enabled", False)
        monkeypatch.setattr(jaxcache, "DEFAULT_DIR", str(blocker))
        jaxcache.enable()  # makedirs fails
        assert tele.cache_errors == 1
        assert (
            "lodestar_jax_persistent_cache_errors_total 1"
            in reg.expose()
        )
        # enable() still latches so later callers don't retry-spam
        assert jaxcache._enabled

    def test_jaxcache_enable_keeps_env_cache_dir(
        self, tmp_path, monkeypatch
    ):
        """With JAX_COMPILATION_CACHE_DIR set, JAX has taken its cache
        directory from the environment and enable() sets none of its
        own over it."""
        from lodestar_tpu.utils import jaxcache

        outside = str(tmp_path / "placed_outside")
        prev_dir = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        monkeypatch.setattr(jaxcache, "_enabled", False)
        try:
            jax.config.update("jax_compilation_cache_dir", outside)
            jaxcache.enable()
            assert jax.config.jax_compilation_cache_dir == outside
        finally:
            jax.config.update("jax_compilation_cache_dir", prev_dir)
            from jax.experimental.compilation_cache import (
                compilation_cache,
            )

            compilation_cache.reset_cache()

    def test_pending_cache_errors_absorbed_by_install(self):
        """Errors recorded before any telemetry exists (import-time
        enable()) surface on the next install()."""
        prev = D.get_telemetry()
        try:
            D.set_telemetry(None)
            D.record_cache_error()
            tele = D.install()
            assert tele.cache_errors >= 1
        finally:
            D._PENDING_CACHE_ERRORS = 0
            D.set_telemetry(prev)


class TestWarmupProgress:
    def test_progress_tracks_warm_registry(self, telemetry, monkeypatch):
        tele, reg, m = telemetry
        from lodestar_tpu.bls import kernels as K

        monkeypatch.setattr(K, "_INGEST_WARM", set())
        monkeypatch.setattr(K, "INGEST_MIN_BUCKET", 256)
        sizes = K.default_warmup_sizes()
        assert sizes == (256, 512, 2048)
        prog = K.warmup_progress()
        assert prog == {"batch": (0, 3), "same_message": (0, 3)}
        reg.expose()  # trigger collect
        assert m.device.warmup_progress.get(pipeline="batch") == 0.0
        assert (
            m.device.warmup_eligible_buckets.get(pipeline="batch") == 3
        )
        K.mark_ingest_warm(256)
        K.mark_ingest_warm(512, "same_message")
        K.mark_ingest_warm(2048, "same_message")
        reg.expose()
        assert m.device.warmup_warm_buckets.get(pipeline="batch") == 1
        assert m.device.warmup_progress.get(
            pipeline="batch"
        ) == pytest.approx(1 / 3)
        assert m.device.warmup_progress.get(
            pipeline="same_message"
        ) == pytest.approx(2 / 3)


class TestStageTiming:
    def test_dispatch_histogram_populates(self, telemetry):
        tele, reg, m = telemetry
        f = D.instrument_stage("dt_stage", jax.jit(lambda x: x * 2))
        f(jnp.ones((4,)))
        f(jnp.ones((4,)))
        assert tele.dispatch_count["dt_stage"] == 2
        assert tele.dispatch_seconds["dt_stage"] > 0
        assert (
            m.device.stage_dispatch_seconds.get_count(stage="dt_stage")
            == 2
        )
        # device histogram untouched in "dispatch" mode
        assert (
            m.device.stage_device_seconds.get_count(stage="dt_stage")
            == 0
        )

    def test_sync_mode_times_device_and_nests_span(self, telemetry):
        tele, reg, m = telemetry
        tele.set_timing("sync")
        tracer = Tracer(metrics=m.tracing, slow_ms=0)
        f = D.instrument_stage("sync_stage", jax.jit(lambda x: x + 3))
        with tracer.span("sig_verify") as parent:
            f(jnp.ones((4,)))
        assert tele.device_count["sync_stage"] == 1
        assert (
            m.device.stage_device_seconds.get_count(stage="sync_stage")
            == 1
        )
        names = [c.name for c in parent.children]
        assert "device:sync_stage" in names

    def test_transfer_accounting(self, telemetry):
        tele, reg, m = telemetry
        x = jnp.ones((128,))
        n = D.tree_nbytes(x, [x, (x, 7)])
        assert n == 3 * x.nbytes
        D.record_transfer("h2d", x, [x, (x, 7)])
        D.record_transfer("d2h", x)
        snap = tele.snapshot_transfers()
        assert snap["h2d"] == n and snap["d2h"] == x.nbytes
        text = reg.expose()
        assert (
            f'lodestar_jax_transfer_bytes_total{{direction="h2d"}} {n}'
            in text
        )

    def test_transfer_byte_walk_skipped_when_uninstalled(self):
        prev = D.get_telemetry()
        try:
            D.set_telemetry(None)
            # must not raise and must not require array arguments to
            # be walked — the uninstalled path is one None check
            D.record_transfer("h2d", jnp.ones((4,)))
        finally:
            D.set_telemetry(prev)

    def test_device_memory_cpu_fallback(self, telemetry):
        tele, reg, m = telemetry
        keep = jnp.ones((2048,))  # a live buffer the fallback must see
        rows = D.device_memory_snapshot()
        assert rows, "no devices visible"
        # CPU backend reports no allocator stats -> live-array fallback
        assert rows[0]["source"] in ("memory_stats", "live_arrays")
        n, total = D.live_buffer_stats()
        assert n >= 1 and total >= keep.nbytes
        text = reg.expose()
        assert "lodestar_jax_live_buffer_bytes" in text
        assert 'lodestar_jax_device_bytes_in_use{device="0"}' in text


class TestVerifierDeviceSpans:
    def test_device_wave_span_grafts_under_job_span(self, monkeypatch):
        """The TpuBlsVerifier's wave device-time lands as a backdated
        `device_wave` child under the caller's bls_verify_job span."""
        from lodestar_tpu.bls import SignatureSet, TpuBlsVerifier
        from lodestar_tpu.bls import kernels as K
        from lodestar_tpu.crypto.bls import signature as sig

        monkeypatch.setattr(K, "_INGEST_WARM", set())

        def fake_ingest(pk, sig_x, sig_sign, u0, u1, bits, mask):
            return jnp.asarray(True)

        monkeypatch.setattr(
            K, "run_verify_batch_ingest_async", fake_ingest
        )
        tracer = Tracer(slow_ms=0)
        sk, msg = 7001, b"\x11" * 32
        s = SignatureSet(sig.sk_to_pk(sk), msg, sig.sign(sk, msg))

        async def go():
            v = TpuBlsVerifier(
                mesh=False, ingest_min_bucket=1, latency_budget_ms=0
            )
            with tracer.span("sig_verify") as parent:
                ok = await v.verify_signature_sets([s])
            await v.close()
            return ok, parent

        ok, parent = asyncio.run(go())
        assert ok is True
        jobs = [c for c in parent.children if c.name == "bls_verify_job"]
        assert jobs, "bls_verify_job span missing"
        waves = [c.name for c in jobs[0].children]
        assert "device_wave" in waves

    def test_attach_completed_span_no_trace_is_noop(self):
        from lodestar_tpu.metrics.tracing import attach_completed_span

        assert attach_completed_span("device_wave", 0.5) is None

    def test_attach_completed_span_duration(self):
        from lodestar_tpu.metrics.tracing import attach_completed_span

        tracer = Tracer(slow_ms=0)
        with tracer.span("outer") as outer:
            span = attach_completed_span("device_wave", 0.25)
        assert span is not None
        assert span.parent is outer
        assert span.duration == pytest.approx(0.25, abs=1e-6)


class TestDeviceTraceRoute:
    def _impl(self, max_ms=50.0, trace_dir=None):
        from types import SimpleNamespace

        from lodestar_tpu.api.impl import BeaconApiImpl

        node = SimpleNamespace(
            device_trace_max_ms=max_ms, device_trace_dir=trace_dir
        )
        return BeaconApiImpl(None, None, None, node)

    def test_route_registered(self):
        from lodestar_tpu.api.routes import match_route

        matched = match_route("POST", "/eth/v1/lodestar/device_trace")
        assert matched is not None
        route, _ = matched
        assert route.impl_name == "device_trace"
        assert route.query_params == ("duration_ms",)

    def test_capture_returns_trace_dir(self, telemetry, monkeypatch):
        tele, reg, m = telemetry
        started = []
        monkeypatch.setattr(
            jax.profiler, "start_trace", lambda d: started.append(d)
        )
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        impl = self._impl(max_ms=50.0)
        out = asyncio.run(impl.device_trace("5000"))
        # knob bound: 5000 requested, 50 allowed
        assert out["duration_ms"] == 50.0
        assert out["trace_dir"] == started[0]
        assert tele.trace_captures == 1
        assert tele.last_trace_dir == out["trace_dir"]
        assert not tele.trace_capture_active
        assert (
            "lodestar_jax_device_trace_captures_total 1" in reg.expose()
        )

    def test_one_capture_at_a_time(self, telemetry, monkeypatch):
        from lodestar_tpu.api.impl import ApiError

        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        impl = self._impl()
        assert D._capture_lock.acquire(blocking=False)
        try:
            with pytest.raises(ApiError) as e:
                asyncio.run(impl.device_trace("5"))
            assert e.value.status == 409
        finally:
            D._capture_lock.release()

    def test_bad_duration_is_400(self, telemetry):
        from lodestar_tpu.api.impl import ApiError

        impl = self._impl()
        with pytest.raises(ApiError) as e:
            asyncio.run(impl.device_trace("not-a-number"))
        assert e.value.status == 400

    @pytest.mark.slow
    def test_real_profiler_capture(self, telemetry, tmp_path):
        """Real jax.profiler capture (heavy on CPU: ~30 s of profiler
        session setup/teardown) — the trace directory must contain an
        xplane artifact."""
        out = D.profiler_capture(50.0, str(tmp_path))
        assert out["trace_dir"] == str(tmp_path)
        files = [
            p for p in tmp_path.rglob("*") if p.is_file()
        ]
        assert files, "profiler capture produced no artifacts"


class TestDevnodeE2E:
    def test_compile_metrics_and_trace_route_on_devnode(
        self, telemetry, monkeypatch
    ):
        """Devnode e2e: with telemetry installed and collectors bound,
        a running dev chain plus instrumented device work populates
        the compile series on the exposition, and the admin route
        POST /eth/v1/lodestar/device_trace returns a capture (the real
        kernels' multi-minute CPU compiles are out of tier-1 budget —
        a small instrumented jit stands in for the device pipeline;
        the profiler itself runs stubbed here and for real in the
        slow-marked capture test)."""
        from lodestar_tpu.api.impl import BeaconApiImpl
        from lodestar_tpu.api.routes import match_route
        from lodestar_tpu.chain import DevNode
        from lodestar_tpu.config.chain_config import ChainConfig
        from lodestar_tpu.types import ssz_types

        tele, reg, m = telemetry
        far = 2**64 - 1
        cfg = ChainConfig(
            ALTAIR_FORK_EPOCH=far,
            BELLATRIX_FORK_EPOCH=far,
            CAPELLA_FORK_EPOCH=far,
            DENEB_FORK_EPOCH=far,
            ELECTRA_FORK_EPOCH=far,
            SHARD_COMMITTEE_PERIOD=0,
        )
        types = ssz_types()
        node = DevNode(cfg, types, 16, verify_attestations=False)
        f = D.instrument_stage(
            "e2e_stage", jax.jit(lambda x: x * 7.0 + 1.0)
        )

        async def go():
            await node.run_until(2)
            f(jnp.ones((8,)))  # device work during the chain run
            await node.close()

        asyncio.run(go())
        text = reg.expose()
        assert (
            'lodestar_jax_compiles_total{stage="e2e_stage"} 1' in text
        )
        assert "lodestar_jax_warmup_progress" in text
        assert "lodestar_jax_live_buffer_bytes" in text
        # the admin route end-to-end through the route table
        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        matched = match_route("POST", "/eth/v1/lodestar/device_trace")
        assert matched is not None
        route, params = matched
        impl = BeaconApiImpl(cfg, types, node.chain)
        out = asyncio.run(
            getattr(impl, route.impl_name)(**params, duration_ms="10")
        )
        assert out["trace_dir"]
        assert tele.trace_captures == 1
        assert "lodestar_jax_device_trace_captures_total 1" in reg.expose()


class TestProvenanceStamp:
    def test_provenance_fields(self):
        from lodestar_tpu.utils.provenance import provenance

        stamp = provenance()
        assert stamp["jax"] == jax.__version__
        assert stamp["platform"] == jax.default_backend()
        assert stamp["device_count"] >= 1
        assert stamp["limb_backend"] in ("vpu", "mxu")
        assert isinstance(stamp["ingest_min_bucket"], int)
        assert "timestamp" in stamp
        # git_rev is best-effort (None outside a checkout)
        assert "git_rev" in stamp
