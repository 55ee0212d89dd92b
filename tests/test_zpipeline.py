"""Overlapped wave pipeline (ISSUE 16): double-buffered dispatch and
fused stage programs (bls/verifier.py + bls/kernels.py).

Covers the tentpole's equivalence and structure guarantees:

  * depth-2 (double-buffered) verdicts are BIT-IDENTICAL to depth-1
    (synchronous) over mixed valid/invalid jobs
  * the deadline flush still fires and settles correctly while the
    pipeline overlaps waves
  * fused dispatch collapses the ingest pipeline's 8 per-stage XLA
    programs into exactly 3 (structural, recording stubs) and the
    host path's 4 into 3 (real execution, instrument_stage counters)
  * pipeline occupancy / prep-overlap metrics stay sane
  * slow-marked: REAL fused-vs-per-stage execution differential

Host-path buckets run the real device pipeline at the in-process-warm
bucket-4 shape (same discipline as test_bls_verifier_trickle); the
fused INGEST program is never executed here — its single-core CPU
compile is prohibitive (the reason fused stages default off on CPU),
so its structure is checked with stubs and its numerics by composing
the same *_impl bodies the per-stage jits execute.
"""

import asyncio

import pytest

from lodestar_tpu.bls import SignatureSet, TpuBlsVerifier
from lodestar_tpu.bls import kernels as K
from lodestar_tpu.crypto.bls import signature as sig
from lodestar_tpu.metrics import device as D


@pytest.fixture(autouse=True)
def _restore_pipeline_knobs():
    """Fused-stage mode and telemetry are process-global; leave no
    trace for other test files."""
    fused = K.fused_stages_on()
    tel = D.get_telemetry()
    yield
    K.set_fused_stages(fused)
    D.set_telemetry(tel)


def _mk_sets(n, prefix=b"zp", good=True):
    """n signature sets; with good=False the LAST one is signed by
    the wrong key — a valid G2 point that fails the pairing check on
    device (not a host-parse reject)."""
    out = []
    for i in range(n):
        sk = 6000 + i
        msg = prefix + bytes([i]) + b"\x00" * (32 - len(prefix) - 1)
        signer = sk + 1 if (not good and i == n - 1) else sk
        out.append(
            SignatureSet(sig.sk_to_pk(sk), msg, sig.sign(signer, msg))
        )
    return out


def _run(coro):
    return asyncio.run(coro)


def _stub_ingest(monkeypatch, calls):
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
    # whole-bucket mesh entries: conftest's 8 virtual devices give the
    # verifier an auto-mesh, so buckets divisible by 8 route here
    monkeypatch.setattr(
        K, "run_verify_batch_ingest_mesh", fake_batch_mesh
    )
    monkeypatch.setattr(
        K, "run_verify_same_message_mesh", fake_same_message_mesh
    )


# ---------------------------------------------------------------------------
# depth-2 == depth-1 (acceptance: overlapping must not change verdicts)
# ---------------------------------------------------------------------------


class TestDepthEquivalence:
    def _verdicts(self, depth):
        jobs = [
            _mk_sets(3, prefix=b"ok1"),
            _mk_sets(3, prefix=b"bad", good=False),
            _mk_sets(2, prefix=b"ok2"),
            _mk_sets(1, prefix=b"bd2", good=False),
        ]

        async def go():
            v = TpuBlsVerifier(pipeline_depth=depth)
            assert v.pipeline_depth() == depth
            res = await asyncio.gather(
                *(v.verify_signature_sets(j) for j in jobs)
            )
            occ = v.pipeline_occupancy()
            hidden = v.metrics.prep_overlap_hidden_s
            await v.close()
            return res, occ, hidden

        return _run(go())

    def test_depth2_bit_identical_to_depth1_mixed_verdicts(self):
        sync, _, _ = self._verdicts(1)
        overlapped, occ, hidden = self._verdicts(2)
        assert sync == overlapped == [True, False, True, False]
        assert 0.0 <= occ <= 1.0
        assert hidden >= 0.0

    def test_depth4_bit_identical_too(self):
        assert self._verdicts(4)[0] == [True, False, True, False]

    def test_depth_is_live_tunable_and_clamped(self):
        async def go():
            v = TpuBlsVerifier(pipeline_depth=2)
            v.set_pipeline_depth(4)
            assert v.pipeline_depth() == 4
            v.set_pipeline_depth(0)  # clamped to the sync floor
            assert v.pipeline_depth() == 1
            ok = await v.verify_signature_sets(_mk_sets(2))
            await v.close()
            return ok

        assert _run(go()) is True

    def test_quiescence_covers_prefetched_waves(self):
        """ISSUE 16 bugfix shape: a task parked in _wave_tasks (a
        wave still prepping/dispatching) makes the verifier
        non-quiescent even with empty finalizer/rolling state."""

        async def go():
            v = TpuBlsVerifier(pipeline_depth=2)
            assert v.is_quiescent()
            gate = asyncio.Event()

            async def wave():
                await gate.wait()

            t = asyncio.ensure_future(wave())
            v._wave_tasks.add(t)
            quiet_during = v.is_quiescent()
            gate.set()
            await t
            v._wave_tasks.discard(t)
            quiet_after = v.is_quiescent()
            await v.close()
            return quiet_during, quiet_after

        during, after = _run(go())
        assert during is False and after is True


# ---------------------------------------------------------------------------
# deadline flush under overlap
# ---------------------------------------------------------------------------


class TestDeadlineFlushUnderOverlap:
    def test_deadline_flush_fires_with_wave_in_flight(
        self, monkeypatch
    ):
        """A lone batchable job submitted while a non-batchable wave
        is already in the (depth-2) pipeline must still be flushed by
        its deadline and settle True — the overlap window must not
        swallow or reorder the rolling bucket's timer."""
        calls = []
        _stub_ingest(monkeypatch, calls)
        nb = _mk_sets(3, prefix=b"nb")
        single = _mk_sets(1, prefix=b"sg")

        async def go():
            v = TpuBlsVerifier(
                pipeline_depth=2,
                max_buffer_wait_ms=5,
                ingest_min_bucket=4,
                latency_budget_ms=60,
            )
            t_nb = asyncio.ensure_future(v.verify_signature_sets(nb))
            # let the non-batchable wave get past buffering and into
            # the pipeline before the trickle job arrives
            await asyncio.sleep(0.03)
            ok_s = await v.verify_signature_sets(
                single, batchable=True
            )
            ok_nb = await t_nb
            m = v.metrics
            await v.close()
            return ok_s, ok_nb, m

        ok_s, ok_nb, m = _run(go())
        assert ok_s is True and ok_nb is True
        # the single coalesced nowhere (nb had already dispatched):
        # only its deadline could flush it
        assert m.rolling_flushes["deadline"] == 1
        assert ("batch", 4) in calls


# ---------------------------------------------------------------------------
# fused program count (acceptance: 8-9 dispatches -> <= 3)
# ---------------------------------------------------------------------------


class _Rec:
    """Recording stand-in for a jitted stage program."""

    def __init__(self, calls, name, ret):
        self.calls, self.name, self.ret = calls, name, ret

    def __call__(self, *a, **k):
        self.calls.append(self.name)
        return self.ret


class TestFusedProgramCount:
    def _stub_all_stages(self, monkeypatch, calls):
        for name, ret in [
            # legacy per-stage ingest chain (8 programs)
            ("_stage_g2_sqrt", ("x", "y", "qr")),
            ("_stage_g2_subgroup", ("sig", "valid")),
            ("_stage_sswu_iso", "s"),
            ("_stage_cofactor", ("hx", "hy")),
            ("_stage_prepare_batch", ("px", "py", "qx", "qy", "pm")),
            (
                "_stage_prepare_same_message",
                ("px", "py", "qx", "qy", "pm"),
            ),
            ("_stage_miller", "f"),
            ("_stage_product", "prod"),
            ("_stage_final_with_valid", True),
            # fused composition (3 programs)
            (
                "_fused_ingest_batch",
                ("px", "py", "qx", "qy", "pm", "valid"),
            ),
            (
                "_fused_ingest_same_message",
                ("px", "py", "qx", "qy", "pm", "valid"),
            ),
            ("_fused_pairing", "prod"),
        ]:
            monkeypatch.setattr(K, name, _Rec(calls, name, ret))

    def test_fused_ingest_batch_is_exactly_three_programs(
        self, monkeypatch
    ):
        calls = []
        self._stub_all_stages(monkeypatch, calls)
        K.set_fused_stages(True)
        K.run_verify_batch_ingest_async(*(None,) * 7)
        assert calls == [
            "_fused_ingest_batch",
            "_fused_pairing",
            "_stage_final_with_valid",
        ]

    def test_fused_ingest_same_message_is_exactly_three_programs(
        self, monkeypatch
    ):
        calls = []
        self._stub_all_stages(monkeypatch, calls)
        K.set_fused_stages(True)
        K.run_verify_same_message_ingest_async(
            None, ("h0", "h1"), None, None, None, None
        )
        assert calls == [
            "_fused_ingest_same_message",
            "_fused_pairing",
            "_stage_final_with_valid",
        ]

    def test_legacy_ingest_batch_is_eight_programs(self, monkeypatch):
        calls = []
        self._stub_all_stages(monkeypatch, calls)
        K.set_fused_stages(False)
        K.run_verify_batch_ingest_async(*(None,) * 7)
        assert calls == [
            "_stage_g2_sqrt",
            "_stage_g2_subgroup",
            "_stage_sswu_iso",
            "_stage_cofactor",
            "_stage_prepare_batch",
            "_stage_miller",
            "_stage_product",
            "_stage_final_with_valid",
        ]
        assert len(calls) == 8

    def test_host_path_fused_is_three_programs(self, monkeypatch):
        calls = []
        self._stub_all_stages(monkeypatch, calls)
        K.set_fused_stages(True)
        K._run_pipeline(
            K._stage_prepare_batch, None, ("h0", "h1"), None, None, None
        )
        assert calls == [
            "_stage_prepare_batch",
            "_fused_pairing",
            "_stage_final_with_valid",
        ]


class TestFusedInstrumentCounters:
    """ACCEPTANCE: 8-9 per-stage dispatches -> <= 3 fused programs,
    asserted through the instrument_stage dispatch counters the drift
    monitor and /metrics read. Stage programs are stubs RE-WRAPPED in
    instrument_stage under their production stage names, so the
    counters tick through the real telemetry path with no compile."""

    def _instrumented_stubs(self, monkeypatch, tel):
        D.set_telemetry(tel)
        for name, stage, ret in [
            ("_stage_g2_sqrt", "g2_sqrt", ("x", "y", "qr")),
            ("_stage_g2_subgroup", "g2_subgroup", ("sig", "valid")),
            ("_stage_sswu_iso", "sswu_iso", "s"),
            ("_stage_cofactor", "cofactor", ("hx", "hy")),
            (
                "_stage_prepare_batch",
                "prepare_batch",
                ("px", "py", "qx", "qy", "pm"),
            ),
            ("_stage_miller", "miller", "f"),
            ("_stage_product", "product", "prod"),
            ("_stage_final_with_valid", "final", True),
            (
                "_fused_ingest_batch",
                "prepare",
                ("px", "py", "qx", "qy", "pm", "valid"),
            ),
            ("_fused_pairing", "pairing", "prod"),
        ]:
            monkeypatch.setattr(
                K,
                name,
                D.instrument_stage(stage, _Rec([], name, ret)),
            )

    def test_fused_wave_counts_three_dispatches(self, monkeypatch):
        tel = D.DeviceTelemetry(timing="dispatch")
        self._instrumented_stubs(monkeypatch, tel)
        K.set_fused_stages(True)
        K.run_verify_batch_ingest_async(*(None,) * 7)
        assert dict(tel.dispatch_count) == {
            "prepare": 1,
            "pairing": 1,
            "final": 1,
        }
        assert sum(tel.dispatch_count.values()) == 3

    def test_legacy_wave_counts_eight_dispatches(self, monkeypatch):
        tel = D.DeviceTelemetry(timing="dispatch")
        self._instrumented_stubs(monkeypatch, tel)
        K.set_fused_stages(False)
        K.run_verify_batch_ingest_async(*(None,) * 7)
        assert sum(tel.dispatch_count.values()) == 8
        assert set(tel.dispatch_count) == {
            "g2_sqrt",
            "g2_subgroup",
            "sswu_iso",
            "cofactor",
            "prepare_batch",
            "miller",
            "product",
            "final",
        }


# ---------------------------------------------------------------------------
# slow: real fused execution differential (host path)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestFusedRealDifferential:
    def test_fused_host_path_verdicts_match_per_stage(self):
        """Execute the SAME mixed jobs with fused stages on and off;
        verdicts must be bit-identical (the fused bodies compose the
        exact *_impl functions the per-stage jits compile)."""
        jobs = [
            _mk_sets(3, prefix=b"fd1"),
            _mk_sets(3, prefix=b"fd2", good=False),
        ]

        def verdicts(fused):
            K.set_fused_stages(fused)

            async def go():
                v = TpuBlsVerifier()
                res = await asyncio.gather(
                    *(v.verify_signature_sets(j) for j in jobs)
                )
                await v.close()
                return res

            return _run(go())

        assert verdicts(False) == verdicts(True) == [True, False]
