"""Device fault domain: health state machine, wave watchdog
deadlines, and the JAX device-error taxonomy.

Until this module the accelerator was an ASSUMED-HEALTHY component: a
hung device program blocked the executor worker forever
(device/executor.py ran job.fn() with no deadline), and device errors
were swallowed by bare ``except Exception`` fallbacks with zero
classification (crypto/kzg.py, bls/verifier.py) — a sick or preempted
TPU degraded the node invisibly and was retried on every single call.
This module makes the device a failure-isolated dependency behind the
same contract the engine API already has (resilience/breaker.py):

  ONLINE ──fault──▶ DEGRADED ──faults──▶ QUARANTINED ──backoff──▶
  PROBING ──N successes──▶ ONLINE (warmup re-kicked)
                  ╰──probe failure──▶ QUARANTINED (backoff doubles)

* `DeviceHealthTracker` — the state machine, composed over the
  half-open `CircuitBreaker` with injectable clocks (closed=ONLINE/
  DEGRADED, open=QUARANTINED, half_open=PROBING). Every device client
  reports faults through `record_fault` and consults
  `device_allowed()` before dispatching.

* Error taxonomy (`classify_device_error`) — XlaRuntimeError
  RESOURCE_EXHAUSTED is an OOM: shrink the bucket ladder's top rung
  before quarantining (a smaller footprint often fits). A compile
  failure quarantines only that stage program (the registry keeps the
  rest of the pipeline live). Device-lost / INTERNAL / watchdog
  timeouts count toward the breaker — enough of them quarantine the
  whole device. Programming errors (TypeError/KeyError from our own
  code) are NOT device faults: call sites must re-raise them instead
  of letting them masquerade as hardware flakiness.

* Wave watchdog deadlines — per-QoS-class deadlines derived from
  COVERAGE.md's fused stage budget (autotune.STAGE_BUDGET_MS: prepare
  288.0 + pairing 78.4 + final 16.2 ≈ 382.6 ms for the 2048-set
  production bucket). The executor's watchdog thread marks overruns,
  fails the job's future with `DeviceTimeout`, and trips this tracker
  without wedging the worker (device/executor.py spawns a replacement
  worker and abandons the stuck one).

* Node-wide failover — on quarantine every client rides its host
  tier: the BLS verifier routes buckets to the host oracle (verdicts
  bit-identical — per-set exact pairing checks), KZG MSM/Fr ride
  their existing host tiers, warmup suspends (bls/kernels health
  gate), the autotuner suspends and the drift monitor defers (the
  frozen-config invariant the scenario fleet proves for incidents).
  `note_failover(client)` counts every failed-over dispatch and
  answers whether this client should LOG the transition (once per
  state change, not per call).

* PROBING reinstates live — `maybe_probe` runs a maintenance-class
  known-answer dispatch at the smallest warm rung once the breaker's
  backoff elapses; `probe_successes` consecutive successes reopen the
  device path and re-kick warmup (`warmup_kick`), one failure re-trips
  with the backoff doubled (bounded by `max_backoff_s`).

Grounding: 2G2T (PAPERS.md, arXiv 2602.23464) argues an outsourced
verifier must never be silently trusted — the failover keeps verdict
obligations on the bit-exact host tiers; the committee signature-load
model (arXiv 2302.00418) is why gossip verdicts keep their deadline
obligations through the incident instead of erroring out.
"""

from __future__ import annotations

import threading
from enum import Enum

from ..resilience.breaker import BreakerState, CircuitBreaker
from ..resilience.clock import SYSTEM_CLOCK
from .autotune import STAGE_BUDGET_MS


class DeviceTimeout(RuntimeError):
    """A device dispatch overran its watchdog deadline. The job's
    future fails with this; the worker that ran it is abandoned (the
    underlying device call may never return) and replaced."""


class HealthState(str, Enum):
    online = "online"
    degraded = "degraded"
    quarantined = "quarantined"
    probing = "probing"


# stable gauge encoding (lodestar_device_health_state)
HEALTH_STATE_INDEX = {
    HealthState.online: 0,
    HealthState.degraded: 1,
    HealthState.quarantined: 2,
    HealthState.probing: 3,
}


# ---------------------------------------------------------------------------
# Watchdog deadlines (COVERAGE.md fused stage budget -> per-class)
# ---------------------------------------------------------------------------

# The fused three-program budget for the 2048-set production bucket
# (COVERAGE.md "Device stage budget", re-exported by autotune):
# prepare 288.0 + pairing 78.4 + final 16.2 ms.
FUSED_BUDGET_MS = sum(STAGE_BUDGET_MS.values())

# Per-class multiples of the fused budget. These are HANG detectors,
# not latency SLOs: a healthy wave finishes in ~1 budget; prep jitter,
# queueing, and retry ladders legitimately stack a few more, so the
# deadline class trips only past 8x (~3.1 s) and bulk (blob batches,
# host-prep-heavy) past 16x (~6.1 s). Maintenance is None — warmup /
# autotune compiles legitimately run minutes cold; probes pass their
# own explicit per-job timeout instead.
WATCHDOG_BUDGET_MULTIPLES = {
    "deadline": 8.0,
    "bulk": 16.0,
    "maintenance": None,
}


def watchdog_deadline_s(cls: str) -> float | None:
    """The watchdog deadline for one QoS class, in seconds (None =
    unbounded; see WATCHDOG_BUDGET_MULTIPLES)."""
    scale = WATCHDOG_BUDGET_MULTIPLES.get(cls)
    if scale is None:
        return None
    return FUSED_BUDGET_MS * scale / 1000.0


def default_watchdog_deadlines() -> dict[str, float | None]:
    """Per-class watchdog deadlines for DeviceExecutor wiring."""
    return {
        cls: watchdog_deadline_s(cls)
        for cls in WATCHDOG_BUDGET_MULTIPLES
    }


# ---------------------------------------------------------------------------
# Error taxonomy
# ---------------------------------------------------------------------------

# fault kinds record_fault understands
FAULT_KINDS = (
    "oom", "compile", "device_lost", "timeout", "unknown",
)

# exception types that are OUR bugs, never the device's. A TypeError
# out of a dispatch lambda means the code is wrong; counting it as a
# device fault would quarantine healthy hardware and hide the bug.
_PROGRAMMING_ERRORS = (
    TypeError,
    KeyError,
    AttributeError,
    NameError,
    IndexError,
    AssertionError,
)

_OOM_MARKERS = ("resource_exhausted", "out of memory", "oom")
_COMPILE_MARKERS = ("compilation", "compile", "xla_compile")
_DEVICE_LOST_MARKERS = (
    "device lost", "device_lost", "internal:", "internal error",
    "data_loss", "aborted", "unavailable", "failed_precondition",
    "deadline_exceeded", "halted", "preempted",
)


def classify_device_error(exc: BaseException) -> str:
    """Map an exception from a device dispatch onto the taxonomy:
    'oom' | 'compile' | 'device_lost' | 'timeout' | 'programming' |
    'unknown'. Matches the XlaRuntimeError type by NAME (jaxlib moves
    it between modules across versions) and falls back to status-code
    markers in the message, so injected faults (sim/faults.py) and
    real chips classify identically."""
    if isinstance(exc, DeviceTimeout):
        return "timeout"
    if isinstance(exc, _PROGRAMMING_ERRORS):
        return "programming"
    names = {t.__name__ for t in type(exc).__mro__}
    msg = str(exc).lower()
    is_xla = "XlaRuntimeError" in names or "JaxRuntimeError" in names
    if any(m in msg for m in _OOM_MARKERS):
        return "oom"
    if any(m in msg for m in _COMPILE_MARKERS):
        return "compile"
    if any(m in msg for m in _DEVICE_LOST_MARKERS):
        return "device_lost"
    if is_xla:
        # an XLA error we can't bucket finer still indicts the device
        return "device_lost"
    return "unknown"


# ---------------------------------------------------------------------------
# The tracker
# ---------------------------------------------------------------------------


class DeviceHealthTracker:
    """ONLINE → DEGRADED → QUARANTINED → PROBING on a CircuitBreaker.

    Thread model: faults arrive from executor/prep/asyncio threads;
    everything mutating holds one re-entrant lock. Callbacks
    (`on_transition`, `warmup_kick`, `ladder_shrink`) run outside any
    caller-visible invariant but inside the lock — keep them cheap
    and non-reentrant.

    clock: injectable (resilience/clock.py ManualClock in tests).
    failure_threshold: consecutive breaker-counted faults that open
      the breaker (quarantine the device).
    quarantine_reset_s: base backoff before the first probe; doubles
      on every failed probe round up to `max_backoff_s`, resets on
      reinstatement.
    probe_successes: consecutive known-answer probe successes that
      reopen the device path.
    ladder_shrink: () -> bool — shrink the bucket ladder/top rung on
      OOM; True = shrunk (the OOM is absorbed as DEGRADED), False =
      nothing left to shrink (the OOM counts toward quarantine).
      Default: `default_ladder_shrink` (bls/kernels.set_ladder_top to
      the next rung down).
    warmup_kick: () -> None — re-kick warmup on reinstatement (the
      node wires verifier.start_warmup).
    """

    def __init__(
        self,
        name: str = "device",
        clock=None,
        failure_threshold: int = 3,
        quarantine_reset_s: float = 10.0,
        max_backoff_s: float = 300.0,
        probe_successes: int = 3,
        ladder_shrink=None,
        warmup_kick=None,
        on_transition=None,  # fn(old: HealthState, new: HealthState)
        logger=None,
    ):
        self.name = name
        self.clock = clock or SYSTEM_CLOCK
        self._base_reset_s = float(quarantine_reset_s)
        self.max_backoff_s = float(max_backoff_s)
        self.probe_successes = max(1, int(probe_successes))
        self._ladder_shrink = (
            ladder_shrink
            if ladder_shrink is not None
            else default_ladder_shrink
        )
        self._warmup_kick = warmup_kick
        self._on_transition = on_transition
        if logger is None:
            from ..logger import get_logger

            logger = get_logger("device-health")
        self.log = logger
        self._lock = threading.RLock()
        self.breaker = CircuitBreaker(
            name=name,
            failure_threshold=max(1, int(failure_threshold)),
            reset_timeout=self._base_reset_s,
            half_open_max=1,
            clock=self.clock,
            on_transition=self._breaker_moved,
        )
        self._degraded = False
        self._probe_fn = None
        self._probe_streak = 0
        # epoch bumps on EVERY state transition — the log-once-per-
        # transition key clients consult through should_log()
        self.epoch = 0
        self._logged: dict[str, int] = {}
        # -- telemetry (bind_health_collectors samples at scrape) ----
        self.faults: dict[str, int] = {}
        self.watchdog_trips: dict[str, int] = {}
        self.failover_dispatches: dict[str, int] = {}
        self.probes = {"success": 0, "failure": 0}
        self.quarantines = 0
        self.reinstatements = 0
        self.oom_shrinks = 0
        self.quarantined_programs: set[str] = set()
        # full audit trail of (time, old, new) — scenarios assert it
        self.transitions: list[tuple[float, HealthState, HealthState]] = []

    # -- state ----------------------------------------------------------

    @property
    def state(self) -> HealthState:
        """The current state. Pure read — the open→probing move
        happens in maybe_probe (via breaker.allows), never here."""
        s = self.breaker.state
        if s is BreakerState.open:
            return HealthState.quarantined
        if s is BreakerState.half_open:
            return HealthState.probing
        return (
            HealthState.degraded
            if self._degraded
            else HealthState.online
        )

    def state_index(self) -> int:
        return HEALTH_STATE_INDEX[self.state]

    def device_allowed(self) -> bool:
        """May a client dispatch to the device right now? False while
        QUARANTINED or PROBING — during probing only the probe itself
        touches the chip (a live wave racing the probe would make the
        known-answer check unreadable)."""
        return self.breaker.state is BreakerState.closed

    def program_quarantined(self, program: str) -> bool:
        """Is ONE stage program quarantined (compile-failure
        isolation) while the rest of the device stays live?"""
        with self._lock:
            return program in self.quarantined_programs

    # -- fault intake ---------------------------------------------------

    def record_fault(
        self,
        kind_or_exc,
        client: str = "unknown",
        program: str | None = None,
    ) -> str:
        """Report one device fault; returns the taxonomy kind.
        Accepts a kind string or the exception itself. Programming
        errors are REJECTED (ValueError) — the call site must re-raise
        them, not feed them here."""
        if isinstance(kind_or_exc, BaseException):
            kind = classify_device_error(kind_or_exc)
        else:
            kind = str(kind_or_exc)
        if kind == "programming":
            raise ValueError(
                "programming errors are not device faults; re-raise "
                "them at the call site"
            )
        if kind not in FAULT_KINDS:
            kind = "unknown"
        with self._lock:
            self.faults[kind] = self.faults.get(kind, 0) + 1
            if kind == "oom":
                self._on_oom(client)
            elif kind == "compile":
                # quarantine only the failing stage program; the rest
                # of the pipeline keeps the device
                self.quarantined_programs.add(program or client)
                if not self._degraded:
                    self._degraded = True
                    self._bump_epoch(
                        HealthState.online, HealthState.degraded
                    )
            else:
                # timeout / device_lost / unknown indict the device
                self.breaker.on_failure()
        return kind

    def _on_oom(self, client: str) -> None:
        """RESOURCE_EXHAUSTED: shrink the bucket ladder before
        quarantining — a smaller top rung often fits the remaining
        HBM (preemption neighbors, fragmentation)."""
        shrunk = False
        try:
            shrunk = bool(self._ladder_shrink())
        except Exception as e:
            self.log.warn(
                "ladder shrink failed on device OOM",
                {"client": client, "err": repr(e)},
            )
        if shrunk:
            self.oom_shrinks += 1
            if not self._degraded:
                self._degraded = True
                self._bump_epoch(
                    HealthState.online, HealthState.degraded
                )
        else:
            # nothing left to shrink: the OOM counts like any other
            # device fault and can open the breaker
            self.breaker.on_failure()

    def note_watchdog_trip(self, cls: str) -> None:
        """A wave watchdog overrun in QoS class `cls` (the executor's
        watchdog thread, or the verifier's wave deadline for the
        deadline class). Counts per class and feeds the breaker as a
        'timeout' fault."""
        with self._lock:
            self.watchdog_trips[cls] = (
                self.watchdog_trips.get(cls, 0) + 1
            )
        self.record_fault("timeout", client=f"watchdog:{cls}")

    def record_success(self) -> None:
        """A live device dispatch completed while the path was open —
        resets the consecutive-failure count (flaky devices only
        quarantine on CONSECUTIVE faults, the breaker contract)."""
        with self._lock:
            if self.breaker.state is BreakerState.closed:
                self.breaker.consecutive_failures = 0

    # -- failover accounting -------------------------------------------

    def note_failover(self, client: str) -> bool:
        """One dispatch served by a host tier because the device path
        is closed. Returns True when this client should LOG the event
        (once per state transition, not per call — a quarantined node
        sees thousands of failovers per second)."""
        with self._lock:
            self.failover_dispatches[client] = (
                self.failover_dispatches.get(client, 0) + 1
            )
            return self._should_log_locked(client)

    def should_log(self, client: str) -> bool:
        """Log-once-per-transition gate for clients that classify and
        fall back without counting a failover dispatch."""
        with self._lock:
            return self._should_log_locked(client)

    def _should_log_locked(self, client: str) -> bool:
        if self._logged.get(client) == self.epoch:
            return False
        self._logged[client] = self.epoch
        return True

    # -- probing / reinstatement ---------------------------------------

    def set_probe(self, fn) -> None:
        """Install the known-answer probe: () -> bool (True = the
        device answered the smallest warm rung correctly). The node
        wires a maintenance-class executor dispatch with an explicit
        per-job timeout."""
        self._probe_fn = fn

    def maybe_probe(self, probe_fn=None):
        """Drive reinstatement: when QUARANTINED and the backoff has
        elapsed, run one probe (open→PROBING via the breaker's
        half-open gate). `probe_successes` consecutive successes
        reopen the device path (warmup re-kicked); one failure
        re-trips QUARANTINED with the backoff doubled. Returns the
        probe outcome (bool) or None when no probe ran."""
        fn = probe_fn or self._probe_fn
        if fn is None:
            return None
        with self._lock:
            if self.breaker.state is BreakerState.closed:
                return None
            if not self.breaker.allows():
                return None  # backoff not elapsed / probe budget used
        try:
            ok = bool(fn())
        except Exception:
            ok = False
        with self._lock:
            if ok:
                self.probes["success"] += 1
                self._probe_streak += 1
                if self._probe_streak >= self.probe_successes:
                    self.breaker.on_success()  # -> closed: reinstated
                else:
                    # hand the probe slot back so the NEXT maybe_probe
                    # is allowed without waiting out another backoff
                    self.breaker.release_probe()
            else:
                self.probes["failure"] += 1
                self._probe_streak = 0
                self.breaker.reset_timeout = min(
                    self.max_backoff_s, self.breaker.reset_timeout * 2
                )
                self.breaker.on_failure()  # half_open -> open
        return ok

    # -- transitions ----------------------------------------------------

    def _breaker_moved(self, name, old: BreakerState, new) -> None:
        """Breaker transition -> health transition + side effects.
        Runs under the tracker lock for every path that mutates the
        breaker through this tracker."""
        before = _STATE_OF_BREAKER[old]
        if before is None:  # closed: online/degraded split
            before = (
                HealthState.degraded
                if self._degraded
                else HealthState.online
            )
        after = _STATE_OF_BREAKER[new]
        if after is None:
            after = HealthState.online  # reinstatement clears degraded
        if new is BreakerState.open:
            self.quarantines += 1
            self._probe_streak = 0
        if new is BreakerState.closed:
            # reinstated: clear degradation marks, restore the base
            # backoff, re-kick warmup for whatever went cold
            self.reinstatements += 1
            self._degraded = False
            self.quarantined_programs.clear()
            self.breaker.reset_timeout = self._base_reset_s
        self._bump_epoch(before, after)
        if new is BreakerState.closed and self._warmup_kick is not None:
            try:
                self._warmup_kick()
            except Exception as e:
                self.log.warn(
                    "warmup re-kick failed after reinstatement",
                    {"err": repr(e)},
                )

    def _bump_epoch(self, old: HealthState, new: HealthState) -> None:
        self.epoch += 1
        self.transitions.append((self.clock.monotonic(), old, new))
        self.log.info(
            "device health transition",
            {"from": old.value, "to": new.value, "epoch": self.epoch},
        )
        if self._on_transition is not None:
            try:
                self._on_transition(old, new)
            except Exception:
                pass

    # -- introspection --------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "state": self.state.value,
                "epoch": self.epoch,
                "faults": dict(self.faults),
                "watchdog_trips": dict(self.watchdog_trips),
                "failover_dispatches": dict(self.failover_dispatches),
                "probes": dict(self.probes),
                "quarantines": self.quarantines,
                "reinstatements": self.reinstatements,
                "oom_shrinks": self.oom_shrinks,
                "quarantined_programs": sorted(
                    self.quarantined_programs
                ),
            }


# breaker state -> health state (None = closed, resolved against the
# degraded flag at transition time)
_STATE_OF_BREAKER = {
    BreakerState.closed: None,
    BreakerState.open: HealthState.quarantined,
    BreakerState.half_open: HealthState.probing,
}


# ---------------------------------------------------------------------------
# Default wiring helpers
# ---------------------------------------------------------------------------


def default_ladder_shrink() -> bool:
    """Shrink the bucket ladder's top rung one step down the
    selectable tops (2048 -> 1024 -> 512). Returns True when a shrink
    happened; False when the top is already at the floor (the OOM
    then counts toward quarantine). rewarm=False: the device just
    OOMed — a background compile storm is the last thing it needs;
    reinstatement re-kicks warmup."""
    from ..bls import kernels

    top = kernels.ladder_top()
    floor = kernels._MID_RUNGS[-1]
    if top <= floor:
        return False
    # the live BUCKET_LADDER only carries the CURRENT top above the
    # mid rungs; the shrink steps through the selectable tops
    rungs = sorted(set(kernels.LADDER_TOPS) | {floor})
    lower = [b for b in rungs if b < top]
    if not lower:
        return False
    kernels.set_ladder_top(max(lower), rewarm=False)
    return True


def make_device_probe(executor=None, timeout_s: float = 30.0):
    """Build the known-answer probe: one real staged verify at the
    smallest rung of the live ladder — the rung the per-set retries
    dispatch, so its programs are compiled — over valid synthetic
    sets (the answer is True by construction), dispatched
    maintenance-class through the executor when one is wired (with an
    explicit per-job watchdog deadline so a still-hung device fails
    the probe instead of wedging it)."""

    def probe() -> bool:
        def dispatch() -> bool:
            from ..bls import kernels

            args = kernels.host_batch_args(kernels.bucket_size(1))
            return bool(kernels.run_verify_batch_async(*args))

        if executor is None:
            return dispatch()
        fut = executor.submit(
            "maintenance", dispatch, timeout_s=timeout_s
        )
        if fut is None:
            return False  # shed/closed: the device never answered
        return bool(fut.result(timeout=timeout_s * 2))

    return probe


# ---------------------------------------------------------------------------
# /metrics bridging (the addCollect pattern every service uses)
# ---------------------------------------------------------------------------


def bind_health_collectors(
    metrics, tracker: DeviceHealthTracker
) -> None:
    """Wire the m.device_health registry namespace
    (metrics/beacon.py) to sample the tracker at scrape time."""
    metrics.state.add_collect(
        lambda g: g.set(tracker.state_index())
    )

    def _trips(g):
        for cls, n in dict(tracker.watchdog_trips).items():
            g.set(n, cls=cls)

    metrics.watchdog_trips_total.add_collect(_trips)

    def _failovers(g):
        for client, n in dict(tracker.failover_dispatches).items():
            g.set(n, client=client)

    metrics.failover_dispatches_total.add_collect(_failovers)
    metrics.probe_total.add_collect(
        lambda g: [
            g.set(n, outcome=o) for o, n in tracker.probes.items()
        ]
    )

    def _faults(g):
        for kind, n in dict(tracker.faults).items():
            g.set(n, kind=kind)

    metrics.faults_total.add_collect(_faults)
    metrics.quarantines_total.add_collect(
        lambda g: g.set(tracker.quarantines)
    )
    metrics.reinstatements_total.add_collect(
        lambda g: g.set(tracker.reinstatements)
    )
