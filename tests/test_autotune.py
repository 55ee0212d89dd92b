"""Self-tuning device configuration (lodestar_tpu/device/autotune.py).

The OFFLINE unit suite: every tuner/drift test here runs with stubbed
probes — no XLA compile enters tier-1 through this file (the two
host-path dispatches in TestDeadlineFlushAcrossGateChange reuse the
bucket-4 pipeline shape other tier-1 verifier tests already compile,
persistent-cached). Covered:

  * bucket-ladder edge cases under a shifted gate / swapped top rung
  * the live-retune satellite: gate lowering re-kicks warmup for the
    newly eligible rungs; a backend switch invalidates stale warm
    marks
  * select_config's knob logic (pure, stubbed measurements)
  * DeviceAutotuner end to end with a stubbed bench: real setters
    applied, budget enforcement, artifact write + replay
  * the drift monitor: share windows vs the COVERAGE.md budget,
    streaks, quiescence gating, cooldown/cap bounds — including the
    acceptance-criteria loop (drift -> bounded re-tune -> knobs move)
  * verifier deadline-flush behavior when the ingest gate changes
    between job admission and flush
  * provenance embedding of the active tuned config
"""

import asyncio
import json
import time
from types import SimpleNamespace

import pytest

from lodestar_tpu.bls import SignatureSet, TpuBlsVerifier
from lodestar_tpu.bls import kernels as K
from lodestar_tpu.device import autotune as AT
from lodestar_tpu.ops import limbs as L


@pytest.fixture(autouse=True)
def _restore_knobs():
    """Every test here may move the live knobs through the real
    setters; restore the module state so no other test file sees a
    tuned process."""
    from lodestar_tpu.ops import msm as M

    gate = K.INGEST_MIN_BUCKET
    ladder = K.BUCKET_LADDER
    warm = set(K._INGEST_WARM)
    started = K._WARMUP_STARTED
    backend = L.get_backend()
    applied = AT._APPLIED
    window = M.msm_window()
    yield
    K.INGEST_MIN_BUCKET = gate
    K.BUCKET_LADDER = ladder
    K._INGEST_WARM.clear()
    K._INGEST_WARM.update(warm)
    K._WARMUP_STARTED = started
    if L.get_backend() != backend:
        L.set_backend(backend)
    AT._APPLIED = applied
    M.set_msm_window(window)


def _quiet_log():
    return SimpleNamespace(
        info=lambda *a, **k: None, warn=lambda *a, **k: None
    )


def _measurement(backend, sets_per_sec, bucket=4, dispatch=None):
    d = dispatch if dispatch is not None else bucket / sets_per_sec
    return AT.Measurement(
        backend=backend,
        bucket=bucket,
        pipeline="batch",
        seconds_per_dispatch=d,
        sets_per_sec=sets_per_sec,
        runs=3,
        warm_seconds=0.0,
    )


# ---------------------------------------------------------------------------
# bucket ladder edge cases under shifted gate / top (satellite)
# ---------------------------------------------------------------------------


class TestBucketLadder:
    def test_n_exactly_at_a_rung(self):
        for rung in K.BUCKET_LADDER:
            assert K.bucket_size(rung) == rung

    def test_n_between_rungs_rounds_up(self):
        assert K.bucket_size(129) == 256
        assert K.bucket_size(257) == 512
        assert K.bucket_size(513) == K.ladder_top()

    def test_n_above_top_clamps_to_top(self):
        assert K.bucket_size(K.ladder_top() + 1) == K.ladder_top()
        assert K.bucket_size(1_000_000) == K.ladder_top()

    def test_set_ladder_top_1024(self):
        K.set_ladder_top(1024)
        assert K.ladder_top() == 1024
        assert K.BUCKET_LADDER[-2:] == (512, 1024)
        # bucket_size reads the LIVE ladder (not a bound default)
        assert K.bucket_size(600) == 1024
        assert K.bucket_size(2048) == 1024
        assert K.bucket_size(1024) == 1024

    def test_set_ladder_top_back_to_2048(self):
        K.set_ladder_top(1024)
        K.set_ladder_top(2048)
        assert K.BUCKET_LADDER[-2:] == (512, 2048)
        assert K.bucket_size(2000) == 2048

    @pytest.mark.parametrize(
        "n, want", [(1, 128), (4, 128), (128, 128), (129, 256)]
    )
    def test_chip_ladder_starts_at_one_lane_block(self, n, want,
                                                  monkeypatch):
        from lodestar_tpu.utils import chip

        monkeypatch.setattr(chip, "on_tpu", lambda: True)
        assert K.CHIP_MIN_BUCKET == 128
        assert K.bucket_size(n) == want

    @pytest.mark.parametrize(
        "on_chip, gate, want",
        [(True, 256, (128,)), (True, 128, ()), (False, 256, ())],
    )
    def test_host_warmup_sizes_are_the_chip_retry_rungs(
        self, on_chip, gate, want, monkeypatch
    ):
        from lodestar_tpu.utils import chip

        monkeypatch.setattr(chip, "on_tpu", lambda: on_chip)
        assert K.default_host_warmup_sizes(gate) == want

    def test_warmup_compiles_host_rungs_first(self, monkeypatch):
        """The retry rungs go before the multi-minute ingest rungs, and
        each warm dispatch marks its own kind."""
        order = []
        monkeypatch.setattr(
            K, "warm_bucket", lambda b, kind: order.append((kind, b))
        )
        monkeypatch.setattr(K, "_INGEST_WARM", set())
        K.warmup_ingest(
            (512,), block=True, same_message=False, host_sizes=(128,)
        )
        assert order == [("host", 128), ("batch", 512)]

    def test_set_ladder_top_below_mid_rungs_rejected(self):
        with pytest.raises(ValueError):
            K.set_ladder_top(256)

    def test_set_ladder_top_drops_stale_warm_marks(self):
        K.mark_ingest_warm(2048, "batch")
        K.mark_ingest_warm(512, "batch")
        K.set_ladder_top(1024)
        # 2048 left the ladder: counting it warm would overstate the
        # warmup gauges for a size that can never be dispatched
        assert not K.ingest_is_warm(2048)
        assert K.ingest_is_warm(512)

    def test_set_ladder_top_rewarms_cold_incoming_rung(
        self, monkeypatch
    ):
        """A re-tuned top rung was never compiled: with a warmup
        policy in place the swap must kick warmup for it, or a
        cold-fallback verifier routes every bulk bucket host_cold
        until restart."""
        calls = []
        monkeypatch.setattr(K, "_WARMUP_STARTED", True)
        monkeypatch.setattr(K, "INGEST_MIN_BUCKET", 256)
        monkeypatch.setattr(
            K, "warmup_ingest", lambda sizes=None, **kw: calls.append(
                tuple(sizes) if sizes is not None else None
            )
        )
        K._INGEST_WARM.clear()
        for b in (256, 512, 2048):
            K.mark_ingest_warm(b, "batch")
            K.mark_ingest_warm(b, "same_message")
        K.set_ladder_top(1024)
        assert calls == [(1024,)]

    def test_apply_config_rewarms_retuned_top_without_switch(
        self, monkeypatch, tmp_path
    ):
        """The drift-re-tune shape the review flagged: ladder top
        changes, backend does not — apply_config must leave the new
        top on the warmup path, not cold forever."""
        calls = []
        monkeypatch.setattr(K, "_WARMUP_STARTED", True)
        monkeypatch.setattr(K, "INGEST_MIN_BUCKET", 256)
        monkeypatch.setattr(
            K, "warmup_ingest", lambda sizes=None, **kw: calls.append(
                tuple(sizes) if sizes is not None else None
            )
        )
        K._INGEST_WARM.clear()
        for b in (256, 512, 2048):
            K.mark_ingest_warm(b, "batch")
            K.mark_ingest_warm(b, "same_message")
        AT.apply_config(
            AT.TunedConfig("vpu", 256, 1024, 50.0)
        )
        assert calls == [(1024,)]

    def test_gate_above_all_rungs_leaves_nothing_eligible(self):
        # a gate above the whole ladder means: no device ingest at all
        assert K.default_warmup_sizes(K.ladder_top() + 1) == ()
        v = TpuBlsVerifier(
            mesh=False, ingest_min_bucket=K.ladder_top() + 1
        )
        for b in K.BUCKET_LADDER:
            assert not v._use_ingest(b)

    def test_gate_above_mid_rungs_only_top_eligible(self):
        assert K.default_warmup_sizes(513) == (K.ladder_top(),)

    def test_warmup_progress_follows_shifted_gate(self):
        K._INGEST_WARM.clear()
        K.mark_ingest_warm(512, "batch")
        warm, elig = K.warmup_progress(512)["batch"]
        assert (warm, elig) == (1, 2)  # {512, 2048}
        # lowering the gate ADDS eligible rungs that are not warm —
        # the gauges must drop, not keep reporting the old full set
        warm2, elig2 = K.warmup_progress(128)["batch"]
        assert elig2 == 4 and warm2 == 1


# ---------------------------------------------------------------------------
# live-retune warmup satellite
# ---------------------------------------------------------------------------


class TestGateRetuneRewarm:
    def test_lowering_gate_kicks_warmup_for_new_rungs(self, monkeypatch):
        calls = []
        monkeypatch.setattr(K, "_WARMUP_STARTED", True)
        monkeypatch.setattr(K, "INGEST_MIN_BUCKET", 512)
        monkeypatch.setattr(
            K, "warmup_ingest", lambda sizes=None, **kw: calls.append(
                tuple(sizes) if sizes is not None else None
            )
        )
        K._INGEST_WARM.clear()
        K.mark_ingest_warm(512, "batch")
        K.set_ingest_min_bucket(128)
        assert calls == [(128, 256)]

    def test_raising_gate_does_not_kick_warmup(self, monkeypatch):
        calls = []
        monkeypatch.setattr(K, "_WARMUP_STARTED", True)
        monkeypatch.setattr(K, "INGEST_MIN_BUCKET", 128)
        monkeypatch.setattr(
            K, "warmup_ingest", lambda *a, **kw: calls.append(a)
        )
        K.set_ingest_min_bucket(512)
        assert calls == []

    def test_no_warmup_policy_means_no_kick(self, monkeypatch):
        """Processes that never opted into warmup (tests, benches)
        must not have multi-minute compiles sprung on them by a
        setter call."""
        calls = []
        monkeypatch.setattr(K, "_WARMUP_STARTED", False)
        monkeypatch.setattr(K, "INGEST_MIN_BUCKET", 512)
        monkeypatch.setattr(
            K, "warmup_ingest", lambda *a, **kw: calls.append(a)
        )
        K.set_ingest_min_bucket(128)
        assert calls == []

    def test_rewarm_false_skips_kick(self, monkeypatch):
        calls = []
        monkeypatch.setattr(K, "_WARMUP_STARTED", True)
        monkeypatch.setattr(K, "INGEST_MIN_BUCKET", 512)
        monkeypatch.setattr(
            K, "warmup_ingest", lambda *a, **kw: calls.append(a)
        )
        K.set_ingest_min_bucket(128, rewarm=False)
        assert calls == []

    def test_backend_switch_invalidates_warm_marks(self, monkeypatch):
        """A limb-backend switch clears every jit trace; warm marks
        describing the dead executables must go with them (and warmup
        re-kicks when a warmup policy exists)."""
        calls = []
        monkeypatch.setattr(K, "_WARMUP_STARTED", True)
        monkeypatch.setattr(
            K, "warmup_ingest", lambda *a, **kw: calls.append(a)
        )
        K.mark_ingest_warm(256, "batch")
        K.mark_ingest_warm(256, "same_message")
        L.set_backend("mxu")
        try:
            assert not K.ingest_is_warm(256)
            assert not K.ingest_is_warm(256, "same_message")
            assert len(calls) == 1
        finally:
            L.set_backend("vpu")

    def test_probe_switch_suppresses_rewarm_kick(self, monkeypatch):
        """set_backend(rewarm=False) — the autotuner's transient
        probe switches — still invalidates stale marks but must NOT
        launch a background compile storm for a candidate backend."""
        calls = []
        monkeypatch.setattr(K, "_WARMUP_STARTED", True)
        monkeypatch.setattr(
            K, "warmup_ingest", lambda *a, **kw: calls.append(a)
        )
        K.mark_ingest_warm(256, "batch")
        L.set_backend("mxu", rewarm=False)
        try:
            assert not K.ingest_is_warm(256)
            assert calls == []
        finally:
            L.set_backend("vpu", rewarm=False)

    def test_invalidation_during_warmup_dispatch_blocks_stale_mark(
        self, monkeypatch
    ):
        """Generation guard: a warmup dispatch that STARTED before an
        invalidation (backend switch killed its executable) must not
        land its warm mark when it completes — a cold-fallback
        verifier trusting it would dispatch straight into the
        recompile the mark claimed was paid."""
        K._INGEST_WARM.clear()

        def warm_then_invalidate(b, same_message):
            # the invalidation lands WHILE this dispatch is in flight
            K.invalidate_ingest_warm(rewarm=False)

        monkeypatch.setattr(K, "_warm_one", warm_then_invalidate)
        K.warmup_ingest((64,), block=True, same_message=False)
        assert not K.ingest_is_warm(64)
        # ...and a post-invalidation warmup marks normally again
        monkeypatch.setattr(K, "_warm_one", lambda b, same_message: None)
        K.warmup_ingest((64,), block=True, same_message=False)
        assert K.ingest_is_warm(64)

    def test_new_thread_spawns_after_previous_drained(
        self, monkeypatch
    ):
        """The drain loop deregisters the thread under the lock: a
        kick arriving after the thread died must spawn a fresh one,
        not enqueue sizes nobody will ever drain."""
        monkeypatch.setattr(K, "_WARMUP_THREAD", None)
        monkeypatch.setattr(K, "_WARMUP_WANT", set())
        warmed = []
        monkeypatch.setattr(
            K,
            "_warm_one",
            lambda b, same_message: warmed.append(b),
        )
        t1 = K.warmup_ingest((64,), same_message=False)
        t1.join(5)
        assert not t1.is_alive()
        assert K._WARMUP_THREAD is None  # deregistered itself
        t2 = K.warmup_ingest((32,), same_message=False)
        assert t2 is not t1
        t2.join(5)
        assert set(warmed) == {64, 32}

    def test_warmup_requests_not_lost_while_thread_alive(
        self, monkeypatch
    ):
        """A second warmup_ingest() while the thread is running must
        enqueue its sizes, not silently drop them (the rewarm kick
        path)."""
        import threading

        release = threading.Event()
        monkeypatch.setattr(K, "_WARMUP_THREAD", None)
        monkeypatch.setattr(K, "_WARMUP_WANT", set())
        warmed = []

        def fake_warm_one(b, same_message):
            if not same_message:
                release.wait(5)
                warmed.append(b)

        monkeypatch.setattr(K, "_warm_one", fake_warm_one)
        t = K.warmup_ingest((64,), same_message=False)
        t2 = K.warmup_ingest((32,), same_message=False)
        assert t2 is t  # merged into the running thread
        release.set()
        t.join(5)
        assert set(warmed) == {64, 32}


# ---------------------------------------------------------------------------
# grid + selection (pure)
# ---------------------------------------------------------------------------


class TestParseGrid:
    def test_default(self):
        g = AT.parse_grid(None)
        assert g == {
            k: tuple(v) for k, v in AT.DEFAULT_GRID.items()
        }

    def test_spec(self):
        g = AT.parse_grid("backend=vpu;gate=256,512;budget=50")
        assert g["backend"] == ("vpu",)
        assert g["gate"] == (256, 512)
        assert g["budget_ms"] == (50,)
        assert g["top"] == AT.DEFAULT_GRID["top"]

    def test_latency_alias(self):
        assert AT.parse_grid("latency=25")["budget_ms"] == (25,)

    def test_unknown_axis_rejected(self):
        with pytest.raises(ValueError):
            AT.parse_grid("bucket=4")

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError):
            AT.parse_grid("backend=gpu8")

    def test_invalid_knob_values_rejected_up_front(self):
        """A value the setters would refuse must fail at parse time,
        not after the probe budget is spent inside apply_config."""
        with pytest.raises(ValueError):
            AT.parse_grid("top=256")  # below the largest mid rung
        with pytest.raises(ValueError):
            AT.parse_grid("gate=100")  # not a ladder rung
        with pytest.raises(ValueError):
            AT.parse_grid("budget=0")


class TestSelectConfig:
    GRID = {
        "backend": ("vpu", "mxu"),
        "gate": (128, 256, 512),
        "top": (1024, 2048),
        "budget_ms": (25, 50, 100),
    }

    def test_fastest_backend_wins(self):
        ms = [
            _measurement("vpu", 1000.0),
            _measurement("mxu", 4000.0),
        ]
        cfg, rationale = AT.select_config(self.GRID, ms, 5e-4, "tpu")
        assert cfg.limb_backend == "mxu"
        assert rationale["backend"]["chosen"] == "mxu"
        assert rationale["backend"]["skipped"] == []

    def test_gate_crossover_device_wins_early(self):
        # flat 10 ms bucket on TPU vs 0.5 ms/set host prep: the
        # device beats host prep from 20 sets up -> smallest rung 128
        ms = [_measurement("vpu", 400.0, bucket=4, dispatch=0.010)]
        cfg, _ = AT.select_config(self.GRID, ms, 5e-4, "tpu")
        assert cfg.ingest_min_bucket == 128

    def test_gate_stays_high_when_host_prep_wins(self):
        # device dispatch so slow (or host prep so fast) the crossover
        # never happens inside the grid -> keep traffic on the host
        # path via the LARGEST gate
        ms = [_measurement("vpu", 40.0, bucket=4, dispatch=0.1)]
        cfg, _ = AT.select_config(self.GRID, ms, 1e-6, "tpu")
        assert cfg.ingest_min_bucket == 512

    def test_top_steps_down_on_slow_linear_host(self):
        # CPU model: time scales linearly with the batch; a 10 ms
        # probe at 4 -> 2.56 s at 1024 > the 1 s deadline -> even the
        # small top misses, choose the smallest available
        ms = [_measurement("vpu", 400.0, bucket=4, dispatch=0.010)]
        cfg, rationale = AT.select_config(self.GRID, ms, 5e-4, "cpu")
        assert cfg.ladder_top == 1024
        assert rationale["top"]["est_bucket_seconds"][2048] > 1.0

    def test_top_stays_max_on_batch_flat_tpu(self):
        ms = [_measurement("vpu", 400.0, bucket=4, dispatch=0.010)]
        cfg, _ = AT.select_config(self.GRID, ms, 5e-4, "tpu")
        assert cfg.ladder_top == 2048

    def test_latency_budget_covers_gate_dispatch(self):
        # 10 ms flat gate bucket -> need >= 20 ms -> smallest grid
        # budget >= that is 25
        ms = [_measurement("vpu", 400.0, bucket=4, dispatch=0.010)]
        cfg, _ = AT.select_config(self.GRID, ms, 5e-4, "tpu")
        assert cfg.latency_budget_ms == 25.0
        # 40 ms bucket -> need 80 -> budget 100
        ms = [_measurement("vpu", 100.0, bucket=4, dispatch=0.040)]
        cfg, _ = AT.select_config(self.GRID, ms, 5e-4, "tpu")
        assert cfg.latency_budget_ms == 100.0

    def test_empty_measurements_rejected(self):
        with pytest.raises(ValueError):
            AT.select_config(self.GRID, [], 5e-4, "cpu")


class TestMsmWindowKnob:
    """The DA workload's knob on the grid (ops/msm.py Pippenger
    window): parse/validate, the platform cost models, live apply,
    and replay compatibility with pre-MSM decision artifacts."""

    def test_parse_grid_axis_and_alias(self):
        assert AT.parse_grid("msm_window=8,12")["msm_window"] == (8, 12)
        assert AT.parse_grid("window=16")["msm_window"] == (16,)

    def test_parse_grid_rejects_unsupported_window(self):
        with pytest.raises(ValueError):
            AT.parse_grid("msm_window=5")

    def test_tpu_model_minimizes_sequential_depth(self):
        # batch-flat per-step cost: the bucket-reduction scan
        # (2^(w-1) steps) dominates large windows -> smallest wins
        w, rat = AT.select_msm_window((8, 12, 16), "tpu")
        assert w == 8
        assert rat["estimates"][8] < rat["estimates"][16]
        assert "sequential" in rat["model"]

    def test_cpu_model_minimizes_total_adds(self):
        w, rat = AT.select_msm_window((8, 12, 16), "cpu")
        assert w == min(
            rat["estimates"], key=rat["estimates"].get
        )
        assert "total point adds" in rat["model"]

    def test_select_config_carries_window_and_rationale(self):
        ms = [_measurement("vpu", 400.0, bucket=4, dispatch=0.010)]
        grid = dict(TestSelectConfig.GRID, msm_window=(8, 16))
        cfg, rationale = AT.select_config(grid, ms, 5e-4, "tpu")
        assert cfg.msm_window == 8
        assert rationale["msm_window"]["chosen"] == 8
        assert set(rationale["msm_window"]["estimates"]) == {8, 16}

    def test_apply_config_moves_live_window(self, monkeypatch):
        from lodestar_tpu.ops import msm as M

        monkeypatch.setattr(K, "_WARMUP_STARTED", False)
        target = 12 if M.msm_window() != 12 else 8
        AT.apply_config(
            AT.TunedConfig("vpu", 256, 2048, 50.0, msm_window=target)
        )
        assert M.msm_window() == target

    def test_apply_config_zero_leaves_window_alone(self, monkeypatch):
        from lodestar_tpu.ops import msm as M

        monkeypatch.setattr(K, "_WARMUP_STARTED", False)
        before = M.msm_window()
        AT.apply_config(AT.TunedConfig("vpu", 256, 2048, 50.0))
        assert M.msm_window() == before

    def test_replay_of_pre_msm_artifact_keeps_live_window(
        self, monkeypatch
    ):
        from lodestar_tpu.ops import msm as M

        monkeypatch.setattr(K, "_WARMUP_STARTED", False)
        before = M.msm_window()
        decision = {
            "mode": "startup",
            "config": {
                "limb_backend": "vpu",
                "ingest_min_bucket": 256,
                "ladder_top": 2048,
                "latency_budget_ms": 50.0,
                # no msm_window key: a pre-MSM AUTOTUNE.json
            },
        }
        cfg = AT.apply_decision(decision)
        assert cfg.msm_window == 0
        assert M.msm_window() == before

    def test_window_switch_invalidates_msm_warm_marks(
        self, monkeypatch
    ):
        from lodestar_tpu.ops import msm as M

        monkeypatch.setattr(K, "_WARMUP_STARTED", False)
        K.mark_ingest_warm(64, "msm")
        target = 16 if M.msm_window() != 16 else 8
        AT.apply_config(
            AT.TunedConfig("vpu", 256, 2048, 50.0, msm_window=target)
        )
        assert not K.ingest_is_warm(64, "msm")

    def test_current_config_reports_live_window(self):
        from lodestar_tpu.ops import msm as M

        assert AT.current_config().msm_window == M.msm_window()

    def test_tune_records_window_rationale_in_artifact(
        self, tmp_path, monkeypatch
    ):
        """The AUTOTUNE.json satellite: a (stubbed) tune's decision
        artifact carries the chosen msm_window AND the cost-model
        rationale that picked it."""
        monkeypatch.setattr(K, "_WARMUP_STARTED", False)
        tuner = _mk_tuner(
            tmp_path, lambda b, n: _measurement(b, 1000.0), "backend=vpu"
        )
        tuner.tune()
        d = json.loads((tmp_path / "AUTOTUNE.json").read_text())
        assert d["config"]["msm_window"] in (8, 12, 16)
        assert d["rationale"]["msm_window"]["chosen"] == (
            d["config"]["msm_window"]
        )
        assert "model" in d["rationale"]["msm_window"]
        from lodestar_tpu.ops import msm as M

        # the decision was APPLIED: the live window moved with it
        assert M.msm_window() == d["config"]["msm_window"]


class TestPipelineDepthKnob:
    """The overlapped-pipeline knob on the grid (bls/verifier.py wave
    double buffering, ISSUE 16): parse/validate, the platform cost
    models, live apply, and replay compatibility with pre-pipeline
    decision artifacts. Mirrors TestMsmWindowKnob."""

    def test_parse_grid_axis_and_alias(self):
        g = AT.parse_grid("pipeline_depth=1,2")
        assert g["pipeline_depth"] == (1, 2)
        assert AT.parse_grid("depth=4")["pipeline_depth"] == (4,)

    def test_parse_grid_rejects_depth_below_one(self):
        with pytest.raises(ValueError):
            AT.parse_grid("depth=0")

    def test_tpu_model_takes_smallest_overlapping_depth(self):
        # one prefetched wave hides host prep; deeper queues only
        # add latency -> smallest candidate >= 2
        d, rat = AT.select_pipeline_depth((1, 2, 4), "tpu")
        assert d == 2
        assert rat["candidates"] == [1, 2, 4]
        assert "hides host prep" in rat["model"]

    def test_cpu_model_takes_min_depth(self):
        # one core preps AND executes: overlap hides nothing
        d, rat = AT.select_pipeline_depth((1, 2, 4), "cpu")
        assert d == 1
        assert "overlap" in rat["model"]

    def test_select_config_carries_depth_and_rationale(self):
        ms = [_measurement("vpu", 400.0, bucket=4, dispatch=0.010)]
        grid = dict(TestSelectConfig.GRID, pipeline_depth=(1, 2, 4))
        cfg, rationale = AT.select_config(grid, ms, 5e-4, "tpu")
        assert cfg.pipeline_depth == 2
        assert rationale["pipeline_depth"]["chosen"] == 2

    def test_apply_config_moves_verifier_depth(self, monkeypatch):
        monkeypatch.setattr(K, "_WARMUP_STARTED", False)
        v = _FakeVerifier()
        AT.apply_config(
            AT.TunedConfig("vpu", 256, 2048, 50.0, pipeline_depth=4),
            verifier=v,
        )
        assert v.depth == 4

    def test_apply_config_zero_leaves_depth_alone(self, monkeypatch):
        monkeypatch.setattr(K, "_WARMUP_STARTED", False)
        v = _FakeVerifier()
        v.depth = 2
        AT.apply_config(
            AT.TunedConfig("vpu", 256, 2048, 50.0), verifier=v
        )
        assert v.depth == 2

    def test_replay_of_pre_pipeline_artifact_keeps_depth(
        self, monkeypatch
    ):
        monkeypatch.setattr(K, "_WARMUP_STARTED", False)
        v = _FakeVerifier()
        v.depth = 2
        decision = {
            "mode": "startup",
            "config": {
                "limb_backend": "vpu",
                "ingest_min_bucket": 256,
                "ladder_top": 2048,
                "latency_budget_ms": 50.0,
                # no pipeline_depth key: a pre-pipeline AUTOTUNE.json
            },
        }
        cfg = AT.apply_decision(decision, verifier=v)
        assert cfg.pipeline_depth == 0
        assert v.depth == 2

    def test_current_config_reports_live_depth(self):
        v = _FakeVerifier()
        v.depth = 4
        assert AT.current_config(v).pipeline_depth == 4
        # verifiers without the knob (oracle) report 0 = unknown
        assert AT.current_config(None).pipeline_depth == 0

    def test_real_verifier_depth_setter_roundtrip(self):
        v = TpuBlsVerifier(mesh=False, pipeline_depth=4)
        assert v.pipeline_depth() == 4
        v.set_pipeline_depth(1)
        assert v.pipeline_depth() == 1
        v.set_pipeline_depth(0)  # clamped: depth is at least 1
        assert v.pipeline_depth() == 1


# ---------------------------------------------------------------------------
# the tuner, offline (stubbed bench — no compile in tier-1)
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _mk_tuner(tmp_path, bench, grid=None, verifier=None, **kw):
    return AT.DeviceAutotuner(
        verifier=verifier,
        grid=AT.parse_grid(grid),
        bench=bench,
        artifact_path=str(tmp_path / "AUTOTUNE.json"),
        logger=_quiet_log(),
        **kw,
    )


class _FakeVerifier:
    def __init__(self):
        self.budget_ms = 50.0
        self.quiet = True
        self.accepting = True
        self.depth = 0

    def set_latency_budget_ms(self, ms):
        self.budget_ms = ms

    def latency_budget_ms(self):
        return self.budget_ms

    def can_accept_work(self):
        return self.accepting

    def is_quiescent(self):
        return self.quiet

    def pipeline_depth(self):
        return self.depth

    def set_pipeline_depth(self, depth):
        self.depth = depth


class TestDeviceAutotuner:
    def test_startup_tune_applies_through_real_setters(self, tmp_path):
        """The acceptance shape: tune() -> select -> APPLY via the
        real setters (kernels gate + ladder, verifier budget) ->
        decision artifact with provenance."""
        v = _FakeVerifier()
        bench = lambda backend, bucket: _measurement(
            backend, 400.0, bucket=bucket, dispatch=0.010
        )
        # vpu-only grid: the backend setter is a no-op, so this test
        # never drops the process's jit caches
        tuner = _mk_tuner(
            tmp_path, bench, grid="backend=vpu", verifier=v
        )
        decision = tuner.tune()
        assert decision["source"] == "measured"
        cfg = decision["config"]
        # applied LIVE, not just reported
        assert K.ingest_min_bucket() == cfg["ingest_min_bucket"]
        assert K.ladder_top() == cfg["ladder_top"]
        assert v.budget_ms == cfg["latency_budget_ms"]
        assert L.get_backend() == cfg["limb_backend"] == "vpu"
        assert tuner.runs == 1
        assert tuner.candidates_measured == 1
        assert tuner.best_sets_per_sec == 400.0
        # artifact on disk, stamped, replayable
        art = json.loads((tmp_path / "AUTOTUNE.json").read_text())
        assert art["config"] == cfg
        assert "provenance" in art and "rationale" in art
        assert AT.applied_decision()["config"] == cfg

    def test_budget_skips_late_candidates(self, tmp_path, monkeypatch):
        clock = _FakeClock()

        def bench(backend, bucket):
            clock.t += 10.0  # each candidate costs 10 "seconds"
            return _measurement(backend, 100.0, bucket=bucket)

        tuner = _mk_tuner(
            tmp_path,
            bench,
            grid="backend=vpu,mxu",
            budget_ms=12_000.0,
            clock=clock,
        )
        # pretend we are on TPU so the mxu candidate is admitted by
        # policy and the BUDGET is what cuts it
        monkeypatch.setattr(tuner, "_platform", lambda: "tpu")
        decision = tuner.tune()
        # first candidate always measured; the second would blow the
        # budget (10s spent + 10x cross-backend estimate > 12s)
        assert len(decision["measurements"]) == 1
        assert decision["source"] == "partial"
        assert decision["rationale"]["backend"]["skipped"] == ["mxu"]

    def test_cpu_policy_excludes_mxu_probe(self, tmp_path):
        """Off-TPU the mxu probe is a multi-minute cache-clearing
        recompile toward a foregone conclusion (more MACs, no matrix
        unit) — policy skips it, records why, and the decision still
        counts as fully measured for this platform."""
        probed = []

        def bench(backend, bucket):
            probed.append(backend)
            return _measurement(backend, 100.0, bucket=bucket)

        tuner = _mk_tuner(tmp_path, bench, grid="backend=vpu,mxu")
        decision = tuner.tune()  # platform: cpu (conftest)
        assert probed == ["vpu"]
        assert decision["source"] == "measured"
        assert "mxu" in (
            decision["rationale"]["backend"]["policy_skipped"]
        )
        assert decision["config"]["limb_backend"] == "vpu"

    def test_explicit_mxu_only_grid_overrides_policy(
        self, tmp_path, monkeypatch
    ):
        probed = []

        def bench(backend, bucket):
            probed.append(backend)
            return _measurement(backend, 100.0, bucket=bucket)

        # stub the backend setter: this test is about candidate
        # policy, and the real setter's jax.clear_caches() would
        # evict every other test's traces twice over
        switched = []
        monkeypatch.setattr(
            L, "set_backend", lambda n, **kw: switched.append(n)
        )
        tuner = _mk_tuner(tmp_path, bench, grid="backend=mxu")
        decision = tuner.tune()  # platform: cpu, but mxu is pinned
        assert probed == ["mxu"]
        assert decision["config"]["limb_backend"] == "mxu"
        assert switched == ["mxu"]

    def test_all_probes_failing_keeps_live_config(self, tmp_path):
        def bench(backend, bucket):
            raise RuntimeError("no device")

        prev_gate = K.ingest_min_bucket()
        prev_top = K.ladder_top()
        tuner = _mk_tuner(tmp_path, bench, grid="backend=vpu")
        decision = tuner.tune()
        assert decision["source"] == "default"
        assert K.ingest_min_bucket() == prev_gate
        assert K.ladder_top() == prev_top

    def test_replay_decision(self, tmp_path):
        v = _FakeVerifier()
        bench = lambda backend, bucket: _measurement(
            backend, 400.0, bucket=bucket, dispatch=0.010
        )
        tuner = _mk_tuner(tmp_path, bench, grid="backend=vpu")
        tuner.tune()
        # fresh process simulation: knobs moved away, then replayed
        K.set_ingest_min_bucket(512, rewarm=False)
        K.set_ladder_top(2048)
        d = AT.load_decision(str(tmp_path / "AUTOTUNE.json"))
        cfg = AT.apply_decision(d, verifier=v)
        assert K.ingest_min_bucket() == cfg.ingest_min_bucket
        assert K.ladder_top() == cfg.ladder_top
        assert v.budget_ms == cfg.latency_budget_ms
        assert AT.provenance_fields()["autotune_source"] == "replay"

    def test_load_decision_rejects_non_artifact(self, tmp_path):
        p = tmp_path / "x.json"
        p.write_text('{"metric": "something_else"}')
        with pytest.raises(ValueError):
            AT.load_decision(str(p))


# ---------------------------------------------------------------------------
# drift monitor (acceptance: drift -> bounded re-tune)
# ---------------------------------------------------------------------------


class _FakeTelemetry:
    def __init__(self):
        self.dev: dict[str, float] = {}

    def snapshot_stage_seconds(self):
        return {}, dict(self.dev)

    def add_window(self, shares: dict[str, float], total_s: float = 1.0):
        for s, share in shares.items():
            self.dev[s] = self.dev.get(s, 0.0) + share * total_s


def _budget_window():
    return dict(AT.budget_shares())


def _drifted_window(stage="pairing", delta=0.16):
    """The target stage departs its budget share by +delta (past the
    0.15 threshold); the loss is spread over the OTHER stages capped
    at 0.13 each, so ONLY the drifted stage trips the monitor. (The
    fused 3-row budget is prepare-dominant — a proportional rescale
    of the remainder would drag `prepare` past the threshold too.)"""
    shares = dict(AT.budget_shares())
    shares[stage] += delta
    remaining = delta
    rest = [s for s in shares if s != stage]
    for s in sorted(rest, key=lambda s: -shares[s]):
        give = min(0.13, shares[s], remaining)
        shares[s] -= give
        remaining -= give
    assert remaining < 1e-9, "drift helper could not balance shares"
    return shares


class TestDriftMonitor:
    def _monitor(self, tuner, telemetry, verifier=None, **kw):
        kw.setdefault("windows", 3)
        kw.setdefault("cooldown_s", 0.0)
        return AT.DriftMonitor(
            tuner, telemetry, verifier=verifier, **kw
        )

    def test_in_budget_windows_never_trigger(self):
        tel = _FakeTelemetry()
        tuner = SimpleNamespace(
            tune=lambda trigger: pytest.fail("must not retune"),
            verifier=None,
            log=_quiet_log(),
        )
        mon = self._monitor(tuner, tel)
        tel.add_window(_budget_window())
        mon.sample()  # baseline
        for _ in range(6):
            tel.add_window(_budget_window())
            shares = mon.sample()
            assert shares  # signal present
        assert all(v == 0 for v in mon.streaks.values())
        assert mon.pending_stage is None

    def test_drift_triggers_retune_after_n_windows(self):
        """ACCEPTANCE: a stage departing its COVERAGE.md budget share
        for N windows triggers a re-tune through the real tuner with
        stubbed kernels — the full closed loop, no compiles."""
        tel = _FakeTelemetry()
        v = _FakeVerifier()
        bench = lambda backend, bucket: _measurement(
            backend, 400.0, bucket=bucket, dispatch=0.010
        )
        tuner = AT.DeviceAutotuner(
            verifier=v,
            grid=AT.parse_grid("backend=vpu"),
            bench=bench,
            artifact_path=None,
            logger=_quiet_log(),
        )
        mon = self._monitor(tuner, tel, verifier=v)
        tel.add_window(_budget_window())
        mon.sample()  # baseline
        for i in range(3):
            tel.add_window(_drifted_window("pairing"))
            mon.sample()
            assert mon.streaks["pairing"] == i + 1
        assert mon.pending_stage == "pairing"
        assert mon.maybe_retune() is True
        assert mon.retunes == 1
        assert tuner.runs == 1
        assert tuner.drift_retunes == 1
        assert tuner.last_decision["trigger"] == "drift:pairing"
        # knobs moved through the real setters
        cfg = tuner.last_decision["config"]
        assert K.ingest_min_bucket() == cfg["ingest_min_bucket"]
        assert mon.streaks["pairing"] == 0  # streaks reset post-tune

    def test_retune_blocked_until_verifier_quiescent(self):
        tel = _FakeTelemetry()
        v = _FakeVerifier()
        v.quiet = False  # a wave is in flight
        tunes = []
        tuner = SimpleNamespace(
            tune=lambda trigger: tunes.append(trigger),
            verifier=v,
            log=_quiet_log(),
        )
        mon = self._monitor(tuner, tel, verifier=v)
        tel.add_window(_budget_window())
        mon.sample()
        for _ in range(3):
            tel.add_window(_drifted_window("prepare"))
            mon.sample()
        assert mon.pending_stage == "prepare"
        assert mon.maybe_retune() is False  # NEVER mid-wave
        assert mon.retunes_blocked == 1
        assert tunes == []
        v.quiet = True
        assert mon.maybe_retune() is True
        assert tunes == ["drift:prepare"]

    def test_retune_holds_verifier_intake_for_its_duration(self):
        """The quiescence checked before a re-tune must keep holding
        while the (multi-second) tune runs: maybe_retune wraps the
        tune in the verifier's intake hold, so can_accept_work
        backpressures the gossip path for the whole switch."""
        tel = _FakeTelemetry()
        v = TpuBlsVerifier(mesh=False)
        during = {}

        def tune(trigger):
            during["accepting"] = v.can_accept_work()

        tuner = SimpleNamespace(
            tune=tune, verifier=v, log=_quiet_log()
        )
        mon = self._monitor(tuner, tel, verifier=v, windows=1)
        tel.add_window(_budget_window())
        mon.sample()
        tel.add_window(_drifted_window("pairing"))
        mon.sample()
        assert v.can_accept_work()  # held only DURING the tune
        assert mon.maybe_retune() is True
        assert during["accepting"] is False
        assert v.can_accept_work()  # released after

    def test_retune_blocked_mid_prefetch_defers(self):
        """ISSUE 16 regression: with the overlapped pipeline a wave
        can be IN FLIGHT (prefetched, not yet finalized) while the
        rolling buckets and finalizer set are empty. is_quiescent now
        accounts for those wave tasks, so a drift re-tune arriving
        mid-prefetch DEFERS (retunes_blocked counts it) instead of
        switching knobs under a dispatched wave; the pending trigger
        fires once the wave drains."""
        tel = _FakeTelemetry()
        v = TpuBlsVerifier(mesh=False, pipeline_depth=2)
        tunes = []
        tuner = SimpleNamespace(
            tune=lambda trigger: tunes.append(trigger),
            verifier=v,
            log=_quiet_log(),
        )
        mon = self._monitor(tuner, tel, verifier=v, windows=1)
        tel.add_window(_budget_window())
        mon.sample()
        tel.add_window(_drifted_window("pairing"))
        mon.sample()
        assert mon.pending_stage == "pairing"

        async def scenario():
            gate = asyncio.Event()

            async def wave():
                await gate.wait()

            t = asyncio.ensure_future(wave())
            v._wave_tasks.add(t)
            try:
                assert not v.is_quiescent()
                assert mon.maybe_retune() is False
                assert mon.retunes_blocked == 1
                assert mon.pending_stage == "pairing"  # still pending
                assert tunes == []
            finally:
                gate.set()
                await t
                v._wave_tasks.discard(t)
            assert v.is_quiescent()
            assert mon.maybe_retune() is True

        asyncio.run(scenario())
        assert tunes == ["drift:pairing"]
        assert mon.retunes_blocked == 1

    def test_cooldown_and_cap_bound_retunes(self):
        tel = _FakeTelemetry()
        clock = _FakeClock()
        tunes = []
        tuner = SimpleNamespace(
            tune=lambda trigger: tunes.append(trigger),
            verifier=None,
            log=_quiet_log(),
        )
        mon = self._monitor(
            tuner,
            tel,
            windows=1,
            cooldown_s=100.0,
            max_retunes=2,
            clock=clock,
        )
        tel.add_window(_budget_window())
        mon.sample()

        def drift_once():
            tel.add_window(_drifted_window("final"))
            mon.sample()
            return mon.maybe_retune()

        assert drift_once() is True
        # inside the cooldown: drift seen, but no re-tune scheduled
        assert drift_once() is False
        assert mon.pending_stage is None
        clock.t += 101.0
        assert drift_once() is True
        # cap reached: never again
        clock.t += 101.0
        assert drift_once() is False
        assert len(tunes) == 2

    def test_idle_windows_carry_no_signal(self):
        tel = _FakeTelemetry()
        tuner = SimpleNamespace(
            tune=lambda trigger: None, verifier=None, log=_quiet_log()
        )
        mon = self._monitor(tuner, tel)
        tel.add_window(_budget_window())
        mon.sample()
        tel.add_window(_drifted_window("pairing"))
        mon.sample()
        assert mon.streaks["pairing"] == 1
        # an idle node (window total below min_window_s) must neither
        # extend nor produce drift streaks off noise
        tel.add_window(_drifted_window("pairing"), total_s=0.001)
        assert mon.sample() == {}
        assert mon.streaks["pairing"] == 1


# ---------------------------------------------------------------------------
# metric bridging (the lodestar_autotune_* family)
# ---------------------------------------------------------------------------


class TestAutotuneMetrics:
    def test_collectors_populate_registry(self, tmp_path):
        from lodestar_tpu.metrics import (
            RegistryMetricCreator,
            create_lodestar_metrics,
        )

        reg = RegistryMetricCreator()
        m = create_lodestar_metrics(reg)
        v = _FakeVerifier()
        bench = lambda backend, bucket: _measurement(
            backend, 400.0, bucket=bucket, dispatch=0.010
        )
        tuner = _mk_tuner(
            tmp_path, bench, grid="backend=vpu", verifier=v
        )
        tel = _FakeTelemetry()
        mon = AT.DriftMonitor(tuner, tel, verifier=v)
        AT.bind_autotune_collectors(m.autotune, tuner, monitor=mon)
        tuner.tune()
        tel.add_window(_budget_window())
        mon.sample()
        tel.add_window(_budget_window())
        mon.sample()
        text = reg.expose()
        assert "lodestar_autotune_runs_total 1" in text
        assert (
            'lodestar_autotune_selected{knob="ingest_min_bucket"}'
            in text
        )
        assert 'backend="vpu"' in text
        assert 'mode="startup"' in text
        assert 'source="measured"' in text
        assert "lodestar_autotune_stage_share{" in text
        assert "lodestar_autotune_stage_budget_share{" in text


# ---------------------------------------------------------------------------
# verifier behavior across a live gate change (satellite)
# ---------------------------------------------------------------------------


def _mk_sets(n, msg_prefix=b"at_"):
    from lodestar_tpu.crypto.bls import signature as sig

    out = []
    for i in range(n):
        sk = 6000 + i
        msg = msg_prefix + bytes([i]) + b"\x00" * (
            32 - len(msg_prefix) - 1
        )
        out.append(
            SignatureSet(sig.sk_to_pk(sk), msg, sig.sign(sk, msg))
        )
    return out


def _stub_ingest(monkeypatch, calls):
    """Stub BOTH kernel entry points the verifier can dispatch to —
    these tests are about scheduling and path routing, and a real
    host-path dispatch would drag a bucket-4 pipeline compile into
    tier-1 through this file."""
    import jax.numpy as jnp

    monkeypatch.setattr(K, "_INGEST_WARM", set())

    def fake_ingest(pk, sig_x, sig_sign, u0, u1, bits, mask):
        calls.append(("batch", int(mask.shape[0])))
        return jnp.asarray(True)

    def fake_host(pk, h, sig, bits, mask):
        calls.append(("host", int(mask.shape[0])))
        return jnp.asarray(True)

    monkeypatch.setattr(K, "run_verify_batch_ingest_async", fake_ingest)
    monkeypatch.setattr(K, "run_verify_batch_async", fake_host)


class TestDeadlineFlushAcrossGateChange:
    def test_gate_raised_between_admission_and_flush(self, monkeypatch):
        """A job admitted under a low gate whose deadline fires after
        the gate was raised: the flush must still happen on schedule,
        and the bucket routes to the path the NEW gate prescribes
        (host — it is no longer ingest-eligible)."""
        calls = []
        _stub_ingest(monkeypatch, calls)
        monkeypatch.setattr(K, "INGEST_MIN_BUCKET", 4)
        sets = _mk_sets(1, b"gr_")

        async def go():
            v = TpuBlsVerifier(
                mesh=False,
                max_buffer_wait_ms=1,
                latency_budget_ms=150,
            )
            fut = asyncio.ensure_future(
                v.verify_signature_sets(sets, batchable=True)
            )
            await asyncio.sleep(0.05)  # admitted + rolling
            assert v.metrics.rolling_sets == 1
            K.set_ingest_min_bucket(2048, rewarm=False)
            ok = await fut
            m = v.metrics
            await v.close()
            return ok, m

        ok, m = asyncio.run(go())
        assert ok is True
        assert m.rolling_flushes["deadline"] == 1
        # the NEW gate decides the path: one HOST dispatch at the
        # bucket-4 rung, no ingest call
        assert calls == [("host", 4)]
        assert m.dispatch_by_path["host"] == 1
        assert m.dispatch_by_bucket == {4: 1}

    def test_gate_lowered_between_admission_and_flush(self, monkeypatch):
        """The mirror image: admitted while host-bound, gate lowered
        (the autotuner applying a winner) before the deadline — the
        flush rides the device-ingest path."""
        calls = []
        _stub_ingest(monkeypatch, calls)
        monkeypatch.setattr(K, "INGEST_MIN_BUCKET", 2048)
        sets = _mk_sets(2, b"gl_")

        async def go():
            v = TpuBlsVerifier(
                mesh=False,
                max_buffer_wait_ms=1,
                latency_budget_ms=150,
            )
            fut = asyncio.ensure_future(
                v.verify_signature_sets(sets, batchable=True)
            )
            await asyncio.sleep(0.05)
            K.set_ingest_min_bucket(4, rewarm=False)
            ok = await fut
            m = v.metrics
            await v.close()
            return ok, m

        ok, m = asyncio.run(go())
        assert ok is True
        assert m.rolling_flushes["deadline"] == 1
        assert calls == [("batch", 4)]
        assert m.dispatch_by_path["ingest"] == 1

    def test_live_latency_budget_retune(self):
        v = TpuBlsVerifier(mesh=False, latency_budget_ms=50)
        assert v.latency_budget_ms() == 50.0
        v.set_latency_budget_ms(100.0)
        assert v._latency_budget == pytest.approx(0.1)
        v.set_latency_budget_ms(-5)
        assert v._latency_budget == 0.0

    def test_not_quiescent_during_dispatch_window(self, monkeypatch):
        """Between the wave's job pop and its finalizer registration
        the queue/buffer/rolling/finalizer indicators are all empty —
        `_dispatching` must cover that window or the drift monitor
        could switch backends mid-wave (the exact case the quiescence
        gate exists for)."""
        async def go():
            v = TpuBlsVerifier(mesh=False, latency_budget_ms=0)
            gate = asyncio.Event()
            seen = {}

            async def slow_prep(jobs):
                seen["quiet_during_prep"] = v.is_quiescent()
                await gate.wait()
                for j in jobs:
                    v._resolve_job(j, True)
                return [], [], None

            monkeypatch.setattr(v, "_prep_and_dispatch", slow_prep)
            fut = asyncio.ensure_future(
                v.verify_signature_sets(_mk_sets(1, b"dw_"))
            )
            await asyncio.sleep(0.05)  # wave popped, prep in flight
            mid = v.is_quiescent()
            gate.set()
            ok = await fut
            await asyncio.sleep(0.05)  # let the finalizer finish
            quiet_after = v.is_quiescent()
            await v.close()
            return seen["quiet_during_prep"], mid, ok, quiet_after

        during_prep, mid, ok, after = asyncio.run(go())
        assert during_prep is False
        assert mid is False
        assert ok is True
        assert after is True

    def test_is_quiescent_reflects_rolling_work(self):
        sets = _mk_sets(1, b"qq_")

        async def go():
            v = TpuBlsVerifier(
                mesh=False,
                max_buffer_wait_ms=1,
                latency_budget_ms=60_000,
            )
            assert v.is_quiescent()
            fut = asyncio.ensure_future(
                v.verify_signature_sets(sets, batchable=True)
            )
            await asyncio.sleep(0.05)
            assert not v.is_quiescent()  # job rolling: NOT quiet
            await v.close()
            with pytest.raises(RuntimeError):
                await fut

        asyncio.run(go())


# ---------------------------------------------------------------------------
# provenance embedding (satellite)
# ---------------------------------------------------------------------------


class TestProvenanceTunedConfig:
    def test_stamp_carries_knobs_and_autotune_state(self, tmp_path):
        from lodestar_tpu.utils.provenance import provenance

        stamp = provenance()
        assert stamp["ladder_top"] == K.ladder_top()
        assert stamp["ingest_min_bucket"] == K.ingest_min_bucket()
        assert stamp["autotune_mode"] == "off"
        assert stamp["autotune_source"] == "env"
        # after a tune the stamp names the decision that set the knobs
        bench = lambda backend, bucket: _measurement(
            backend, 400.0, bucket=bucket, dispatch=0.010
        )
        tuner = _mk_tuner(tmp_path, bench, grid="backend=vpu")
        tuner.tune()
        stamp = provenance()
        assert stamp["autotune_mode"] == "startup"
        assert stamp["autotune_source"] == "measured"
        assert stamp["autotune_config"]["ingest_min_bucket"] == (
            K.ingest_min_bucket()
        )
