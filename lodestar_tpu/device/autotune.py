"""Self-tuning device configuration: telemetry -> knobs, closed loop.

Every lever on the 10x path has been a MANUAL knob an operator must
set per host: the vpu/mxu limb backend (`ops/limbs.set_backend`), the
device-ingest gate (`bls/kernels.set_ingest_min_bucket`), the bucket
ladder's top rung (`bls/kernels.set_ladder_top`), and the rolling
bucket's latency budget (`TpuBlsVerifier.set_latency_budget_ms`). The
device telemetry layer (metrics/device.py, PR 10) can already SEE a
mistuned node — a stage departing its COVERAGE.md budget share, a
retrace storm, a warmup that never finishes — but nothing acted on
it. This module is the actuator:

  * `DeviceAutotuner` — at node start (after `jaxcache.enable()`, so
    repeat starts load compiled probes from the persistent cache and
    the whole tune is near-free), micro-benchmark a candidate grid
    {limb backend} x {ingest gate} x {ladder top} x {latency budget}
    using the real `bls/kernels.py` pipelines on synthetic sets,
    select a config (`select_config` — a pure function, unit-tested
    with stubbed measurements), apply it LIVE through the real
    setters, export `lodestar_autotune_*` gauges + a config-info
    series, and record a JSON artifact with the provenance stamp.
  * `DriftMonitor` — a background task that diffs the per-stage
    device/dispatch histograms into windowed stage shares, compares
    them against the COVERAGE.md "Device stage budget" table, and
    when a stage departs its share beyond a threshold for N
    consecutive windows schedules a BOUNDED re-tune — never mid-wave
    (gated through the verifier's `can_accept_work` / `is_quiescent`
    quiescence), never more often than the cooldown, never more than
    `max_retunes` times.

Grounding: the pipelined stage-scheduling of the BLS12-381 pairing
crypto-processor (PAPERS.md, arXiv 2201.07496) fixes a per-stage
budget at design time; a reconfigurable host must instead re-derive
it per deployment, which is exactly what the startup tune does. The
load model the grid is sized for is the committee-based consensus
signature stream of arXiv 2302.00418 (trickle aggregates + bulk
waves — the gate and ladder-top knobs trade between the two).

Measurement honesty: the probe pipeline is the real staged device
program (`run_verify_batch_async` -> prepare/miller/product/final);
ingest-stage probes would be multi-minute XLA compiles per bucket on
CPU, so off-TPU the tuner probes a small ladder rung and extrapolates
the gate/top/budget knobs through an explicit cost model
(`est_bucket_seconds`) whose assumptions are recorded per knob in the
decision's `rationale`. On a TPU host with budget, the probe runs at
real ladder rungs (batch-flat device cost makes the model exact
there). The decision artifact says which happened (`source`:
"measured" when every grid backend was probed, "partial" when the
budget cut the sweep short, "replay" for `--autotune-from`).

Cost of the tune itself, measured: the persistent cache removes the
XLA COMPILE share of the probe (where the TPU pays minutes —
final-exp alone compiled 357 s on the chip — repeat starts really
are near-free). What no cache can remove is jaxpr TRACING of the
interval-machinery-heavy stages, which dominates on CPU: a cold
probe on this 1-core container ran ~100 s (~39 s compile, the rest
trace), and a warm one ~99 s. So `--autotune startup` costs a TPU
node seconds after its first boot, and a CPU node ~2 min every boot
— which is why the mode defaults to off and the probe runs at the
smallest ladder rung off-TPU. Each measurement records its
`warm_seconds` so the artifact shows this share.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from dataclasses import asdict, dataclass

from ..utils import chip

# ---------------------------------------------------------------------------
# COVERAGE.md stage budget (the offline table's live counterpart)
# ---------------------------------------------------------------------------

# Per-stage device budget in ms for the 2048-set production bucket —
# COVERAGE.md "Device stage budget" (the post window/static-ladder
# column, measured round 5 by tools/profile_prefix.py on one v5e,
# re-cut for the FUSED dispatch default of ISSUE 16: the eight
# per-stage rows collapse into the three fused programs — prepare =
# g2_sqrt + g2_subgroup + sswu_iso + cofactor + prepare_batch,
# pairing = miller + product, final unchanged; named sub-scopes
# keep the finer attribution inside the profiler). The drift monitor
# compares each stage's SHARE of windowed device time against these
# shares: absolute times shift with host and backend, but a stage
# whose share balloons past its budgeted fraction has regressed
# relative to its pipeline — the live analog of re-running the
# offline prefix budget. On hosts running the per-stage rollback
# composition the fused names accrue no time and the monitor sees
# no signal — it never false-fires there.
STAGE_BUDGET_MS = {
    "prepare": 288.0,  # 98.7 + 24.6 + 87.0 + 54.2 + 23.5
    "pairing": 78.4,  # 49.4 + 29.0
    "final": 16.2,
}


def budget_shares() -> dict[str, float]:
    """Each stage's budgeted fraction of total device time."""
    total = sum(STAGE_BUDGET_MS.values())
    return {s: ms / total for s, ms in STAGE_BUDGET_MS.items()}


# ---------------------------------------------------------------------------
# Candidate grid
# ---------------------------------------------------------------------------

DEFAULT_GRID = {
    "backend": ("vpu", "mxu"),
    "gate": (128, 256, 512),
    "top": (1024, 2048),
    "budget_ms": (25, 50, 100),
    "msm_window": (8, 12, 16),
    "pipeline_depth": (1, 2, 4),
}

# bulk (block-import / sync) buckets must clear well inside a slot;
# beyond this the top rung steps down (the measured v5e 2048 bucket
# runs 0.383 s — comfortably inside)
TOP_BUCKET_DEADLINE_S = 1.0


def parse_grid(spec: str | None) -> dict:
    """Parse an `--autotune-grid` spec into a grid dict.

    Format: semicolon-separated axes, comma-separated values:
      "backend=vpu;gate=128,256;top=2048;budget=50"
    Unnamed axes keep their DEFAULT_GRID values; unknown axes or
    values raise (a typo'd grid silently tuning the wrong space is
    worse than failing startup)."""
    grid = {k: tuple(v) for k, v in DEFAULT_GRID.items()}
    if not spec:
        return grid
    alias = {
        "budget": "budget_ms",
        "latency": "budget_ms",
        "window": "msm_window",
        "depth": "pipeline_depth",
    }
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        key, _, vals = part.partition("=")
        key = alias.get(key.strip(), key.strip())
        if key not in grid:
            raise ValueError(
                f"unknown autotune grid axis {key!r}; want "
                f"{sorted(grid)} (aliases: budget, latency)"
            )
        items = [v.strip() for v in vals.split(",") if v.strip()]
        if not items:
            raise ValueError(f"empty autotune grid axis {key!r}")
        if key == "backend":
            from ..ops import limbs

            for v in items:
                if v not in limbs.LIMB_BACKENDS:
                    raise ValueError(
                        f"unknown limb backend {v!r} in autotune grid"
                    )
            grid[key] = tuple(items)
        else:
            grid[key] = tuple(int(v) for v in items)
    _validate_grid_values(grid)
    return grid


def _validate_grid_values(grid: dict) -> None:
    """Reject knob values the setters would refuse — NOW, not after
    the probe budget is spent (an invalid `--autotune-grid top` that
    only explodes in apply_config aborts node startup minutes in)."""
    from ..bls import kernels

    for g in grid["gate"]:
        if g not in kernels._MID_RUNGS:
            raise ValueError(
                f"autotune grid gate {g} is not a ladder rung "
                f"{kernels._MID_RUNGS}"
            )
    for t in grid["top"]:
        if t < kernels._MID_RUNGS[-1]:
            raise ValueError(
                f"autotune grid top {t} below the largest mid rung "
                f"{kernels._MID_RUNGS[-1]}"
            )
    for b in grid["budget_ms"]:
        if b <= 0:
            raise ValueError(
                f"autotune grid latency budget {b} must be positive"
            )
    from ..ops import msm as _msm

    for w in grid["msm_window"]:
        if w not in _msm.SUPPORTED_WINDOWS:
            raise ValueError(
                f"autotune grid msm_window {w} not in "
                f"{_msm.SUPPORTED_WINDOWS}"
            )
    for d in grid["pipeline_depth"]:
        if d < 1:
            raise ValueError(
                f"autotune grid pipeline_depth {d} must be >= 1 "
                "(1 = synchronous dispatch)"
            )


@dataclass(frozen=True)
class TunedConfig:
    """One point of the knob space — everything apply() touches.
    msm_window == 0 means "leave the live window alone" (the default
    keeps pre-MSM decision artifacts replayable); pipeline_depth == 0
    the same for the verifier's wave-overlap depth."""

    limb_backend: str
    ingest_min_bucket: int
    ladder_top: int
    latency_budget_ms: float
    msm_window: int = 0
    pipeline_depth: int = 0

    def to_dict(self) -> dict:
        return asdict(self)


def current_config(verifier=None) -> TunedConfig:
    """The LIVE knob values (the tune's fallback and `previous`)."""
    from ..bls import kernels
    from ..ops import limbs

    from ..ops import msm

    budget_ms = 50.0
    fn = getattr(verifier, "latency_budget_ms", None)
    if fn is not None:
        budget_ms = float(fn())
    depth = 0
    dfn = getattr(verifier, "pipeline_depth", None)
    if dfn is not None:
        depth = int(dfn())
    return TunedConfig(
        limb_backend=limbs.get_backend(),
        ingest_min_bucket=kernels.ingest_min_bucket(),
        ladder_top=kernels.ladder_top(),
        latency_budget_ms=budget_ms,
        msm_window=msm.msm_window(),
        pipeline_depth=depth,
    )


@dataclass
class Measurement:
    """One probed (backend, bucket) point of the grid."""

    backend: str
    bucket: int
    pipeline: str  # which entry point was probed
    seconds_per_dispatch: float
    sets_per_sec: float
    runs: int
    warm_seconds: float  # first call: compile or persistent-cache load

    def to_dict(self) -> dict:
        return asdict(self)


# ---------------------------------------------------------------------------
# Selection (pure — unit-testable without a device or a compile)
# ---------------------------------------------------------------------------


def est_bucket_seconds(
    dispatch_s: float, probe_bucket: int, bucket: int, platform: str
) -> float:
    """Cost model extrapolating a measured per-dispatch time to other
    bucket sizes. On TPU per-dispatch device cost is batch-flat to
    ~2048 (COVERAGE.md; padding is nearly free), so time(b) ~= the
    probe time. On CPU XLA one core executes every lane, so cost is
    linear in the batch. Scaling DOWN is flat everywhere (fixed
    dispatch overhead dominates small buckets)."""
    if bucket <= probe_bucket:
        return dispatch_s
    if platform == "tpu":
        return dispatch_s
    return dispatch_s * bucket / probe_bucket


def select_config(
    grid: dict,
    measurements: list[Measurement],
    host_prep_s_per_set: float,
    platform: str,
) -> tuple[TunedConfig, dict]:
    """Pick the winning knob values from probe measurements.

    backend  — argmax sets/s among probed backends.
    gate     — smallest grid rung where a device bucket beats host
               prep of the same sets (est time(g) <= host_per_set*g);
               if the device never wins, the LARGEST rung (keep
               traffic on the host path it is better at).
    top      — largest grid top whose estimated bucket time fits
               TOP_BUCKET_DEADLINE_S; else the smallest.
    budget   — smallest grid latency budget >= 2x the gate bucket's
               estimated time (a deadline flush should not fire while
               an equivalent dispatch is still in flight); else the
               largest.

    Returns (config, rationale) where rationale records per knob what
    drove the choice — the artifact must be auditable."""
    if not measurements:
        raise ValueError("select_config needs at least one measurement")
    by_backend: dict[str, Measurement] = {}
    for m in measurements:
        cur = by_backend.get(m.backend)
        if cur is None or m.sets_per_sec > cur.sets_per_sec:
            by_backend[m.backend] = m
    best = max(by_backend.values(), key=lambda m: m.sets_per_sec)
    rationale: dict = {
        "backend": {
            "chosen": best.backend,
            "sets_per_sec": {
                b: round(m.sets_per_sec, 2)
                for b, m in sorted(by_backend.items())
            },
            "probed": sorted(by_backend),
            "skipped": sorted(
                set(grid["backend"]) - set(by_backend)
            ),
        }
    }
    est = lambda b: est_bucket_seconds(
        best.seconds_per_dispatch, best.bucket, b, platform
    )
    gates = sorted(grid["gate"])
    gate = next(
        (g for g in gates if est(g) <= host_prep_s_per_set * g),
        gates[-1],
    )
    rationale["gate"] = {
        "chosen": gate,
        "host_prep_s_per_set": round(host_prep_s_per_set, 6),
        "est_bucket_seconds": {
            g: round(est(g), 6) for g in gates
        },
        "model": "crossover: device bucket vs host prep of g sets"
        + ("" if platform == "tpu" else " (CPU linear-cost model)"),
    }
    tops = sorted(grid["top"])
    top = next(
        (t for t in reversed(tops) if est(t) <= TOP_BUCKET_DEADLINE_S),
        tops[0],
    )
    rationale["top"] = {
        "chosen": top,
        "deadline_s": TOP_BUCKET_DEADLINE_S,
        "est_bucket_seconds": {t: round(est(t), 6) for t in tops},
    }
    budgets = sorted(grid["budget_ms"])
    need_ms = 2.0 * est(gate) * 1000.0
    budget = next((b for b in budgets if b >= need_ms), budgets[-1])
    rationale["budget_ms"] = {
        "chosen": budget,
        "needed_ms": round(need_ms, 3),
        "model": "2x estimated gate-bucket dispatch time",
    }
    msm_window, msm_rationale = select_msm_window(
        grid.get("msm_window", DEFAULT_GRID["msm_window"]), platform
    )
    rationale["msm_window"] = msm_rationale
    depth, depth_rationale = select_pipeline_depth(
        grid.get("pipeline_depth", DEFAULT_GRID["pipeline_depth"]),
        platform,
    )
    rationale["pipeline_depth"] = depth_rationale
    cfg = TunedConfig(
        limb_backend=best.backend,
        ingest_min_bucket=gate,
        ladder_top=top,
        latency_budget_ms=float(budget),
        msm_window=msm_window,
        pipeline_depth=depth,
    )
    return cfg, rationale


def select_pipeline_depth(
    candidates, platform: str
) -> tuple[int, dict]:
    """Pick the verifier wave-overlap depth (bls/verifier.py).

    TPU: host prep (pubkey packing, limb conversion) and device
    execution run on different hardware, so any depth >= 2 hides the
    prep behind the in-flight wave; deeper queues only add latency
    and buffer footprint, so take the SMALLEST candidate >= 2.
    CPU emulation: the single core both preps and executes, there is
    no overlap to win — depth > 1 just reorders work and widens the
    flush window, so take the minimum candidate."""
    cands = sorted(set(int(d) for d in candidates))
    if platform == "tpu":
        chosen = next((d for d in cands if d >= 2), cands[-1])
        model = (
            "smallest depth >= 2: one prefetched wave hides host "
            "prep; deeper queues only add latency"
        )
    else:
        chosen = cands[0]
        model = (
            "min depth: one core preps AND executes, overlap "
            "hides nothing"
        )
    return chosen, {
        "chosen": chosen,
        "candidates": cands,
        "model": model,
    }


def select_msm_window(
    candidates, platform: str, rung: int | None = None
) -> tuple[int, dict]:
    """Pick the Pippenger window for the KZG MSM workload (ops/msm.py)
    from an explicit cost model of the device program at the dominant
    rung (the blob-width Lagrange lincomb).

    TPU: per-step device cost is batch-flat (COVERAGE.md), so the
    objective is SEQUENTIAL DEPTH — scatter steps (rung/PAR) + bucket
    reduction (2^(w-1)) + window combination (~255 doubles + nwin
    adds); small windows win until the bucket scan is negligible.
    CPU XLA: one core executes every lane, so the objective is TOTAL
    point adds — rung*nwin (scatter) + 2^w*nwin (reduction); the
    optimum sits near w = log2(rung). Both models and every
    candidate's estimate land in the rationale."""
    from ..ops import msm as _msm

    rung = rung or _msm.MSM_RUNGS[-1]
    cands = sorted(set(int(w) for w in candidates))

    def seq_steps(w):
        nwin = _msm.num_windows(w)
        return rung // _msm.PAR + (1 << (w - 1)) + 255 + nwin

    def total_adds(w):
        nwin = _msm.num_windows(w)
        return rung * nwin + (1 << w) * nwin

    model = seq_steps if platform == "tpu" else total_adds
    chosen = min(cands, key=model)
    return chosen, {
        "chosen": chosen,
        "rung": rung,
        "model": (
            "sequential device steps (batch-flat per-step cost)"
            if platform == "tpu"
            else "total point adds (CPU linear per-lane cost)"
        ),
        "estimates": {w: model(w) for w in cands},
    }


# ---------------------------------------------------------------------------
# Applied-decision module state (provenance + bench replay)
# ---------------------------------------------------------------------------

_APPLIED: dict | None = None
_APPLY_LOCK = threading.Lock()


def applied_decision() -> dict | None:
    """The last decision applied in this process (None = knobs came
    from env/CLI, untouched by the tuner)."""
    return _APPLIED


def provenance_fields() -> dict:
    """Tuned-config fields for the bench provenance stamp
    (utils/provenance.py): every BENCH_*/MULTICHIP_* artifact must
    record what configuration produced it."""
    d = _APPLIED
    out: dict = {
        "autotune_mode": d.get("mode", "off") if d else "off",
        "autotune_source": d.get("source", "env") if d else "env",
    }
    if d:
        out["autotune_trigger"] = d.get("trigger")
    return out


def _record_applied(decision: dict) -> None:
    global _APPLIED
    with _APPLY_LOCK:
        _APPLIED = decision


def apply_config(config: TunedConfig, verifier=None) -> None:
    """Push a config through the REAL setters, re-warming exactly
    ONCE against the FINAL eligibility: both bucket knobs apply with
    their own rewarm kicks deferred (a kick between them would
    compile rungs of a half-applied config, possibly on the outgoing
    backend), then either the backend switch re-warms (its
    warm-registry invalidation kicks at the now-final gate/ladder)
    or, with no switch, one explicit kick covers whatever the knob
    changes left cold — e.g. a re-tuned ladder top that was never
    compiled, which a cold-fallback verifier would otherwise route
    host_cold forever."""
    from ..bls import kernels
    from ..ops import limbs
    from ..ops import msm as _msm

    switching = limbs.get_backend() != config.limb_backend
    kernels.set_ladder_top(config.ladder_top, rewarm=False)
    kernels.set_ingest_min_bucket(
        config.ingest_min_bucket, rewarm=False
    )
    if config.msm_window:
        # rewarm deferred like the bucket knobs: a kick here would
        # compile MSM programs against a limb backend the switch
        # below is about to clear-caches away
        _msm.set_msm_window(config.msm_window, rewarm=False)
    if switching:
        # the switch's registry invalidation re-kicks BOTH workloads'
        # warmups (BLS ingest + MSM rungs) at the final knob state
        limbs.set_backend(config.limb_backend)
    else:
        if kernels._WARMUP_STARTED:
            newly = tuple(
                b
                for b in kernels.default_warmup_sizes()
                if not kernels.ingest_is_warm(b)
            )
            if newly:
                kernels.warmup_ingest(newly)
        # cold MSM rungs (a re-tuned window) re-warm when the process
        # opted in; warm rungs make this a no-op
        _msm.rewarm_async()
    fn = getattr(verifier, "set_latency_budget_ms", None)
    if fn is not None:
        fn(config.latency_budget_ms)
    if config.pipeline_depth:
        # 0 = leave the live overlap depth alone (pre-pipeline
        # decision artifacts stay replayable)
        dfn = getattr(verifier, "set_pipeline_depth", None)
        if dfn is not None:
            dfn(config.pipeline_depth)


def load_decision(path: str) -> dict:
    """Read a recorded autotune decision artifact (AUTOTUNE*.json)."""
    with open(path) as f:
        d = json.load(f)
    if "config" not in d:
        raise ValueError(f"{path}: not an autotune decision artifact")
    return d


def apply_decision(
    decision: dict, verifier=None, source: str = "replay"
) -> TunedConfig:
    """Replay a recorded decision (bench.py / tools/bench_*
    --autotune-from): apply its config through the real setters and
    mark this process's provenance as a replay."""
    c = decision["config"]
    cfg = TunedConfig(
        limb_backend=c["limb_backend"],
        ingest_min_bucket=int(c["ingest_min_bucket"]),
        ladder_top=int(c["ladder_top"]),
        latency_budget_ms=float(c["latency_budget_ms"]),
        # pre-MSM artifacts carry no window; 0 leaves the live one
        msm_window=int(c.get("msm_window", 0)),
        pipeline_depth=int(c.get("pipeline_depth", 0)),
    )
    apply_config(cfg, verifier=verifier)
    _record_applied(
        {
            **{
                k: decision.get(k)
                for k in ("mode", "trigger")
                if k in decision
            },
            "source": source,
            "config": cfg.to_dict(),
        }
    )
    return cfg


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------

DEFAULT_BUDGET_MS = 30_000.0
ARTIFACT_PATH = "AUTOTUNE.json"


class DeviceAutotuner:
    """Micro-benchmark the candidate grid and apply the winner.

    verifier: the live TpuBlsVerifier (None = tune kernel knobs only).
    budget_ms: wall-clock ceiling for one tune() — the FIRST backend
      is always measured (otherwise the tuner could never decide);
      later candidates are skipped when the remaining budget cannot
      cover a candidate the size of the last one (source: "partial").
    grid: parse_grid() output (None = DEFAULT_GRID).
    bench: injectable (backend, bucket) -> Measurement for tests —
      the offline unit suite stubs this so NO compile enters tier-1.
    probe_bucket: ladder rung the probes run at (None = auto: 4 off
      TPU where per-lane cost is linear and compiles are slow; the
      smallest grid gate on TPU where batch-flat cost makes bigger
      probes exact and the persistent cache makes them cheap).
    """

    def __init__(
        self,
        verifier=None,
        budget_ms: float = DEFAULT_BUDGET_MS,
        grid: dict | None = None,
        bench=None,
        probe_bucket: int | None = None,
        artifact_path: str | None = ARTIFACT_PATH,
        mode: str = "startup",
        clock=time.monotonic,
        logger=None,
        executor=None,
        health=None,
    ):
        self.verifier = verifier
        # device/health.py DeviceHealthTracker: while the device is
        # quarantined every tune is a no-op — the tuner must neither
        # probe a sick device nor mutate the knob config the host
        # failover path was frozen under
        self.health = health
        # node DeviceExecutor (device/executor.py): probes are
        # maintenance-class work — between candidates the tuner
        # yields the device to pending deadline traffic
        self.executor = executor
        self.budget_ms = float(budget_ms)
        self.grid = grid or parse_grid(None)
        self._bench = bench or self._measure_real
        self._probe_bucket = probe_bucket
        self.artifact_path = artifact_path
        self.mode = mode
        self._clock = clock
        if logger is None:
            from ..logger import get_logger

            logger = get_logger("autotune")
        self.log = logger
        self._lock = threading.Lock()
        self._probe_inputs_cache: dict[int, tuple] = {}
        # gauges (bind_autotune_collectors samples these at scrape)
        self.runs = 0
        self.drift_retunes = 0
        self.candidates_measured = 0
        self.last_duration_s = 0.0
        self.best_sets_per_sec = 0.0
        self.suspended_runs = 0
        self.last_decision: dict | None = None

    # -- probing --------------------------------------------------------

    def _platform(self) -> str:
        import jax

        return "tpu" if chip.on_tpu() else jax.default_backend()

    def probe_bucket(self) -> int:
        if self._probe_bucket is not None:
            return self._probe_bucket
        return (
            min(self.grid["gate"])
            if self._platform() == "tpu"
            else 4
        )

    def _probe_inputs(self, n: int):
        """n valid (pk, H, sig) device batches + rand bits + mask —
        the legacy-pipeline shape (host-hashed, like
        tools/bench_mesh_sweep.build_inputs). Cached per bucket: the
        fixture is backend-independent host data."""
        if n in self._probe_inputs_cache:
            return self._probe_inputs_cache[n]
        import jax.numpy as jnp

        from ..bls import kernels
        from ..crypto.bls import curve as oc
        from ..ops import curve as C

        hs = [oc.g2_mul(oc.G2_GEN, 7 + i) for i in range(n)]
        pks, sigs = [], []
        for i, h in enumerate(hs):
            sk = 100 + i
            pks.append(oc.g1_mul(oc.G1_GEN, sk))
            sigs.append(oc.g2_mul(h, sk))
        pk_dev = C.g1_batch_from_ints(pks)
        h_pt = C.g2_batch_from_ints(hs)
        sig_dev = C.g2_batch_from_ints(sigs)
        rand = [(0x9E37 + 2 * i) | 1 for i in range(n)]
        bits = C.scalars_to_bits(rand, kernels.RAND_BITS)
        mask = jnp.ones(n, bool)
        out = (pk_dev, (h_pt.x, h_pt.y), sig_dev, bits, mask)
        self._probe_inputs_cache[n] = out
        return out

    def _measure_real(self, backend: str, bucket: int) -> Measurement:
        """Probe the REAL staged pipeline (prepare/miller/product/
        final — the per-set device math the backend choice changes)
        at `bucket`, through the persistent compilation cache."""
        from ..bls import kernels
        from ..ops import limbs
        from ..utils import jaxcache

        jaxcache.enable()
        if limbs.get_backend() != backend:
            # transient probe switch: invalidate warm marks but do
            # NOT kick a background warmup for a candidate that may
            # lose — the compile storm would also run concurrently
            # with the timing loop and skew the measurement
            limbs.set_backend(backend, rewarm=False)
        inputs = self._probe_inputs(bucket)
        t0 = self._clock()
        ok = bool(kernels.run_verify_batch_async(*inputs))
        warm_s = self._clock() - t0
        if not ok:
            raise RuntimeError(
                f"autotune probe verify failed (backend={backend})"
            )
        times = []
        for _ in range(3):
            t0 = self._clock()
            bool(kernels.run_verify_batch_async(*inputs))
            times.append(self._clock() - t0)
        per_dispatch = min(times)
        return Measurement(
            backend=backend,
            bucket=bucket,
            pipeline="batch",
            seconds_per_dispatch=per_dispatch,
            sets_per_sec=bucket / per_dispatch if per_dispatch else 0.0,
            runs=len(times),
            warm_seconds=warm_s,
        )

    def _measure_host_prep(self) -> float:
        """Host-path per-set cost (decompression + hash-to-G2, the
        work a device-ingest bucket replaces) — the other arm of the
        gate crossover. Distinct messages/signatures defeat the lru
        caches so this measures cold cost, like live traffic."""
        from ..bls import api
        from ..crypto.bls.signature import sign

        k = 6
        fixtures = []
        for i in range(k):
            msg = bytes([0xA0 + i]) * 32
            fixtures.append((sign(211 + i, msg), msg))
        t0 = self._clock()
        for sig_bytes, msg in fixtures:
            api.decompress_signature(sig_bytes)
            api.message_to_g2(msg)
        return max(1e-9, (self._clock() - t0) / k)

    # -- the tune -------------------------------------------------------

    def _maintenance_checkpoint(self) -> None:
        """Between candidate probes, yield the device to pending
        deadline work through the executor's maintenance gate (no
        executor wired = no-op, the pre-executor behavior). A startup
        tune inside the drift monitor's drain window sees no pending
        deadline work by construction and does not stall."""
        ex = self.executor
        if ex is not None:
            try:
                ex.maintenance_checkpoint()
            except Exception:
                pass

    def tune(self, trigger: str = "startup") -> dict:
        """Measure, select, APPLY, export, record. Returns the
        decision dict (also written to `artifact_path`)."""
        with self._lock:
            return self._tune_locked(trigger)

    def _backend_candidates(
        self, prev: TunedConfig, platform: str
    ) -> tuple[list[str], dict[str, str]]:
        """The backends worth probing on this platform. Off-TPU the
        int8 'mxu' decomposition is KNOWN slower — strictly more MACs
        with no matrix unit to pay for them (COVERAGE.md limb-backend
        study) — and its probe costs a multi-minute cache-clearing
        recompile, so policy excludes it rather than measuring the
        foregone conclusion. An operator who pins the grid to mxu
        alone gets it probed anyway (explicit wins over policy)."""
        backends = list(self.grid["backend"])
        policy: dict[str, str] = {}
        if platform != "tpu" and len(backends) > 1:
            for b in list(backends):
                if b == "mxu":
                    backends.remove(b)
                    policy[b] = (
                        f"no matrix unit on {platform!r}: int8 "
                        "decomposition is strictly more MACs "
                        "(COVERAGE.md limb-backend study)"
                    )
        # probe the live backend first: its traces may already be warm
        backends.sort(key=lambda b: b != prev.limb_backend)
        return backends, policy

    def _tune_locked(self, trigger: str) -> dict:
        if (
            self.health is not None
            and not self.health.device_allowed()
        ):
            # frozen-config invariant: a quarantined device gets no
            # probes and the live config stays exactly as it was at
            # quarantine time (scenario fabric asserts this)
            self.suspended_runs += 1
            decision = {
                "source": "suspended",
                "trigger": trigger,
                "reason": "device quarantined",
                "state": self.health.state.value,
                # the frozen live config — collectors index ["config"]
                "config": current_config(self.verifier).to_dict(),
            }
            self.last_decision = decision
            self.log.warn(
                "autotune suspended: device quarantined",
                {"trigger": trigger},
            )
            return decision
        t_start = self._clock()
        prev = current_config(self.verifier)
        platform = self._platform()
        probe = self.probe_bucket()
        spent_ms = lambda: (self._clock() - t_start) * 1000.0
        host_prep = self._measure_host_prep()
        measurements: list[Measurement] = []
        backends, policy_skipped = self._backend_candidates(
            prev, platform
        )
        last_cost_ms = 0.0
        for b in backends:
            # a candidate on another backend pays a cache-clearing
            # recompile of every probe trace — estimate it an order
            # above the last (warm-ish) candidate so the budget check
            # errs toward skipping rather than blowing the ceiling
            est_ms = last_cost_ms * (
                10.0 if b != prev.limb_backend else 1.0
            )
            if measurements and (spent_ms() + est_ms > self.budget_ms):
                self.log.warn(
                    "autotune budget exhausted; skipping backend",
                    {"backend": b, "spent_ms": round(spent_ms(), 1)},
                )
                continue
            self._maintenance_checkpoint()
            t_c = self._clock()
            try:
                m = self._bench(b, probe)
            except Exception as e:
                self.log.warn(
                    "autotune probe failed; backend skipped",
                    {"backend": b, "err": repr(e)},
                )
                continue
            last_cost_ms = (self._clock() - t_c) * 1000.0
            measurements.append(m)
            self.candidates_measured += 1
        if measurements:
            config, rationale = select_config(
                self.grid, measurements, host_prep, platform
            )
            # "measured" is judged against the backends worth probing
            # on this platform; policy exclusions are recorded, not
            # counted as a budget shortfall
            source = (
                "measured"
                if {m.backend for m in measurements} >= set(backends)
                else "partial"
            )
            if policy_skipped:
                rationale["backend"]["policy_skipped"] = policy_skipped
            self.best_sets_per_sec = max(
                m.sets_per_sec for m in measurements
            )
        else:
            # nothing measured inside the budget: keep the live knobs
            config, rationale = prev, {
                "fallback": "no candidate fit the budget"
            }
            source = "default"
        # apply_config re-warms once at the final eligibility — that
        # also repairs whatever the probes' rewarm-suppressed backend
        # switches left invalidated
        apply_config(config, verifier=self.verifier)
        self.runs += 1
        if trigger.startswith("drift"):
            self.drift_retunes += 1
        self.last_duration_s = (self._clock() - t_start)
        decision = {
            "mode": self.mode,
            "trigger": trigger,
            "source": source,
            "platform": platform,
            "probe_bucket": probe,
            "config": config.to_dict(),
            "previous": prev.to_dict(),
            "host_prep_seconds_per_set": round(host_prep, 6),
            "measurements": [m.to_dict() for m in measurements],
            "rationale": rationale,
            "budget_ms": self.budget_ms,
            "spent_ms": round(spent_ms(), 1),
            "grid": {k: list(v) for k, v in self.grid.items()},
        }
        _record_applied(decision)
        self.last_decision = decision
        self._write_artifact(decision)
        self.log.info(
            "autotune applied",
            {
                "trigger": trigger,
                "source": source,
                "backend": config.limb_backend,
                "gate": config.ingest_min_bucket,
                "top": config.ladder_top,
                "latency_budget_ms": config.latency_budget_ms,
                "spent_ms": decision["spent_ms"],
            },
        )
        return decision

    def _write_artifact(self, decision: dict) -> None:
        if not self.artifact_path:
            return
        try:
            from ..utils.provenance import provenance

            payload = dict(decision, provenance=provenance())
            with open(self.artifact_path, "w") as f:
                json.dump(payload, f, indent=2)
                f.write("\n")
        except Exception as e:
            # the artifact is a record, not a dependency — a read-only
            # filesystem must not fail the tune that already applied
            self.log.warn(
                "autotune artifact write failed",
                {"path": self.artifact_path, "err": repr(e)},
            )


# ---------------------------------------------------------------------------
# Drift monitor
# ---------------------------------------------------------------------------


class DriftMonitor:
    """Watch the live per-stage times against the COVERAGE.md budget
    shares; schedule a bounded re-tune when a stage drifts.

    Sampling: each window diffs the telemetry's cumulative per-stage
    seconds (`snapshot_stage_seconds`) — device (block_until_ready)
    seconds when `--device-timing sync` populates them, else dispatch
    wall seconds. Windows with less than `min_window_s` of total
    budgeted-stage time carry no signal and are skipped (an idle node
    must not retune itself off noise).

    Trigger: a stage whose share deviates from its budget share by
    more than `threshold` (absolute) for `windows` CONSECUTIVE
    windows. Bounded: at most `max_retunes` drift re-tunes, at least
    `cooldown_s` apart, and NEVER mid-wave — the re-tune only fires
    when the verifier is quiescent (`can_accept_work` and
    `is_quiescent`); while it is not, the trigger stays pending and
    `retunes_blocked` counts the deferrals."""

    def __init__(
        self,
        tuner: DeviceAutotuner,
        telemetry,
        verifier=None,
        shares: dict[str, float] | None = None,
        threshold: float = 0.15,
        windows: int = 3,
        interval_s: float = 30.0,
        cooldown_s: float = 600.0,
        max_retunes: int = 8,
        min_window_s: float = 0.05,
        clock=time.monotonic,
        executor=None,
        health=None,
    ):
        self.tuner = tuner
        # device/health.py: a pending re-tune DEFERS while the device
        # is quarantined (pending_stage is kept, so the re-tune lands
        # after reinstatement instead of being lost)
        self.health = (
            health
            if health is not None
            else getattr(tuner, "health", None)
        )
        self.telemetry = telemetry
        self.verifier = (
            verifier if verifier is not None else tuner.verifier
        )
        # node DeviceExecutor (device/executor.py): when wired, a
        # re-tune runs inside executor.drained() — intake closes for
        # EVERY device client and quiescence is awaited centrally,
        # with zero calls to the verifier's hold_intake. Without one
        # the legacy hold_intake/is_quiescent dance below still works
        # (standalone verifiers, tests).
        self.executor = (
            executor
            if executor is not None
            else getattr(tuner, "executor", None)
        )
        self.shares = shares or budget_shares()
        self.threshold = threshold
        self.windows = windows
        self.interval_s = interval_s
        self.cooldown_s = cooldown_s
        self.max_retunes = max_retunes
        self.min_window_s = min_window_s
        self._clock = clock
        self._last_cum: dict[str, float] = {}
        self._last_retune_t: float | None = None
        self._task = None
        # gauges (bind_autotune_collectors)
        self.last_shares: dict[str, float] = {}
        self.streaks: dict[str, int] = {s: 0 for s in self.shares}
        self.windows_sampled = 0
        self.retunes = 0
        self.retunes_blocked = 0
        self.pending_stage: str | None = None

    def _cumulative(self) -> dict[str, float]:
        disp, dev = self.telemetry.snapshot_stage_seconds()
        picked = dev if any(s in dev for s in self.shares) else disp
        return {s: picked.get(s, 0.0) for s in self.shares}

    def sample(self) -> dict[str, float]:
        """One drift window. Returns the observed shares ({} = no
        signal this window)."""
        cum = self._cumulative()
        if not self._last_cum:
            self._last_cum = cum
            return {}
        delta = {
            s: max(0.0, cum[s] - self._last_cum.get(s, 0.0))
            for s in self.shares
        }
        self._last_cum = cum
        total = sum(delta.values())
        if total < self.min_window_s:
            return {}
        shares = {s: d / total for s, d in delta.items()}
        self.last_shares = shares
        self.windows_sampled += 1
        for s, share in shares.items():
            if abs(share - self.shares[s]) > self.threshold:
                self.streaks[s] += 1
            else:
                self.streaks[s] = 0
        for s, n in self.streaks.items():
            if n >= self.windows and self.pending_stage is None:
                if self.retunes >= self.max_retunes:
                    continue
                now = self._clock()
                if (
                    self._last_retune_t is not None
                    and now - self._last_retune_t < self.cooldown_s
                ):
                    continue
                self.pending_stage = s
        return shares

    def _verifier_quiet(self) -> bool:
        """No in-flight/queued verifier work. Prefers is_quiescent
        (valid inside the intake hold); can_accept_work is only the
        fallback for verifiers without it — it must not be consulted
        under hold_intake, which forces it False by design."""
        v = self.verifier
        if v is None:
            return True
        quiet = getattr(v, "is_quiescent", None)
        if quiet is not None:
            return bool(quiet())
        accept = getattr(v, "can_accept_work", None)
        return accept is None or bool(accept())

    def maybe_retune(self) -> bool:
        """Fire the pending re-tune if the device is quiescent.
        Returns True when a re-tune ran. BLOCKING (the tune probes
        the device) — the async loop runs it in an executor thread.
        With a node DeviceExecutor wired the whole window is one
        `executor.drained()`: intake closes for every device client,
        quiescence (including the verifier's probe) is awaited
        centrally, and `hold_intake` is never called. Without one,
        the quiescence checked here is HELD for the tune's duration
        via the verifier's intake hold (can_accept_work -> False), so
        the processor-fed gossip path cannot start waves under the
        knob switches; direct callers (block import) can still land a
        wave mid-tune, which costs recompile latency, not
        correctness."""
        stage = self.pending_stage
        if stage is None:
            return False
        if (
            self.health is not None
            and not self.health.device_allowed()
        ):
            # defer, don't drop: pending_stage survives quarantine so
            # the re-tune fires once the device is reinstated
            self.retunes_blocked += 1
            return False
        if self.executor is not None:
            # executor path: one drain closes intake for EVERY device
            # client (verifier, kzg bulk, warmup) and awaits their
            # quiescence probes — the hold_intake/is_quiescent dance
            # is the executor's job now
            with self.executor.drained() as quiet:
                if not quiet:
                    self.retunes_blocked += 1
                    return False
                self.pending_stage = None
                self.tuner.tune(trigger=f"drift:{stage}")
        else:
            hold = getattr(self.verifier, "hold_intake", None)
            ctx = (
                hold() if hold is not None else contextlib.nullcontext()
            )
            with ctx:
                # quiescence is checked INSIDE the hold: a wave
                # admitted between an outside check and the hold
                # engaging would run under the tune's knob switches
                if not self._verifier_quiet():
                    self.retunes_blocked += 1
                    return False
                self.pending_stage = None
                self.tuner.tune(trigger=f"drift:{stage}")
        self.retunes += 1
        self._last_retune_t = self._clock()
        self.streaks = {s: 0 for s in self.shares}
        # the tune's own probe dispatches went through the
        # instrumented stage entry points — drop the accumulated
        # baseline so the next window diffs from POST-tune state
        # instead of reading the probe's bucket-4 profile as drift
        self._last_cum = {}
        return True

    async def run(self):
        """Background loop (node.py spawns this as a task in adaptive
        mode; cancel to stop)."""
        import asyncio

        loop = asyncio.get_running_loop()
        while True:
            await asyncio.sleep(self.interval_s)
            try:
                self.sample()
                if self.pending_stage is not None:
                    await loop.run_in_executor(None, self.maybe_retune)
            except asyncio.CancelledError:
                raise
            except Exception as e:
                self.tuner.log.warn(
                    "drift monitor window failed", {"err": repr(e)}
                )


# ---------------------------------------------------------------------------
# /metrics bridging (the addCollect pattern every service uses)
# ---------------------------------------------------------------------------


def bind_autotune_collectors(
    metrics, tuner: DeviceAutotuner, monitor: DriftMonitor | None = None
) -> None:
    """Wire the m.autotune registry namespace (metrics/beacon.py) to
    sample the tuner/monitor at scrape time."""
    metrics.runs_total.add_collect(lambda g: g.set(tuner.runs))
    metrics.retunes_total.add_collect(
        lambda g: g.set(tuner.drift_retunes)
    )
    metrics.candidates_measured_total.add_collect(
        lambda g: g.set(tuner.candidates_measured)
    )
    metrics.last_duration_seconds.add_collect(
        lambda g: g.set(tuner.last_duration_s)
    )
    metrics.best_sets_per_sec.add_collect(
        lambda g: g.set(tuner.best_sets_per_sec)
    )

    def _selected(g):
        d = tuner.last_decision or applied_decision()
        cfg = (
            d["config"]
            if d is not None
            else current_config(tuner.verifier).to_dict()
        )
        g.set(cfg["ingest_min_bucket"], knob="ingest_min_bucket")
        g.set(cfg["ladder_top"], knob="ladder_top")
        g.set(cfg["latency_budget_ms"], knob="latency_budget_ms")
        # 0 = decision predates the knob / left the live window alone
        g.set(cfg.get("msm_window") or 0, knob="msm_window")
        g.set(cfg.get("pipeline_depth") or 0, knob="pipeline_depth")

    metrics.selected.add_collect(_selected)

    info_seen: set[tuple] = set()

    def _info(g):
        d = tuner.last_decision or applied_decision()
        cfg = (
            d["config"]
            if d is not None
            else current_config(tuner.verifier).to_dict()
        )
        key = (
            cfg["limb_backend"],
            tuner.mode,
            (d or {}).get("source", "env"),
        )
        # a re-tune that changes backend/source must retire the old
        # info series (the registry keeps every label tuple ever set
        # — two series at 1 would make the live config ambiguous)
        for old in info_seen - {key}:
            g.set(0, backend=old[0], mode=old[1], source=old[2])
        info_seen.add(key)
        g.set(1, backend=key[0], mode=key[1], source=key[2])

    metrics.config_info.add_collect(_info)

    def _shares(g):
        if monitor is None:
            return
        for s, share in monitor.last_shares.items():
            g.set(share, stage=s)

    def _budget_shares(g):
        if monitor is None:
            return
        for s, share in monitor.shares.items():
            g.set(share, stage=s)

    def _streaks(g):
        if monitor is None:
            return
        for s, n in monitor.streaks.items():
            g.set(n, stage=s)

    metrics.stage_share.add_collect(_shares)
    metrics.stage_budget_share.add_collect(_budget_shares)
    metrics.drift_windows.add_collect(_streaks)
    metrics.retunes_blocked_total.add_collect(
        lambda g: g.set(monitor.retunes_blocked if monitor else 0)
    )
