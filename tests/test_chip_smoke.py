"""chip_smoke.py's own checks, driven with stubbed run results.

The smoke is the quickest proof that the verifier stack still runs on
the chip, so a check that lets a host fallback, a watchdog trip or a
wrong verdict pass would let a broken chip path pass for a working one.
Nothing here compiles or touches a device.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import pytest

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py"
)
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

BULK = smoke.BULK_BUCKET

# a clean one-chip run: three bulk waves, each one ingest bucket of
# 2048, plus the bad wave's host-path retries, per job and per set, at
# the chip's smallest rung of 128
CLEAN = {
    "paths": {"ingest": 3, "host": 400, "host_cold": 0, "failover": 0},
    "buckets": {BULK: 3, 128: 400},
    "bulk_waves": 3,
    "watchdog_trips": {"deadline": 0, "executor:bulk": 0},
    "health_state": "online",
}


def _with(**changes):
    run = {k: (dict(v) if isinstance(v, dict) else v) for k, v in CLEAN.items()}
    for key, value in changes.items():
        if isinstance(value, dict):
            run[key].update(value)
        else:
            run[key] = value
    return run


def test_clean_run_passes_and_prints_the_result():
    smoke.check_platform("tpu")
    smoke.check_device_path("verifier", **CLEAN)
    smoke.check_verdicts("wave", [True, False, True], [True, False, True])
    line = smoke.result_line("tpu", "TPU v5 lite", 1)
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


@pytest.mark.parametrize(
    "run",
    [
        pytest.param(_with(paths={"failover": 1}), id="failover"),
        pytest.param(_with(paths={"host_cold": 2}), id="host_cold"),
        pytest.param(
            _with(watchdog_trips={"deadline": 1}), id="watchdog_trip"
        ),
        pytest.param(
            _with(watchdog_trips={"executor:bulk": 1}),
            id="executor_watchdog_trip",
        ),
        pytest.param(_with(health_state="quarantined"), id="not_online"),
        pytest.param(
            _with(paths={"ingest": 2}, buckets={BULK: 2}),
            id="bulk_bucket_off_ingest",
        ),
    ],
)
def test_device_path_check_refuses(run):
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_device_path("verifier", **run)


@pytest.mark.parametrize(
    "paths, ok",
    [
        ({"ingest": 3, "host": 400, "host_cold": 0, "failover": 0}, True),
        ({"ingest": 3, "host": 0, "host_cold": 0, "failover": 400}, False),
    ],
    ids=["clean", "failover"],
)
def test_mesh_verifier_is_held_to_the_same_checks(paths, ok):
    """--chips 4: the mesh verifier's bulk waves ride its ingest
    program, and a bad wave's retries may not fail over."""
    run = _with(paths=paths)
    if ok:
        smoke.check_device_path("mesh verifier", **run)
    else:
        with pytest.raises(smoke.SmokeFailure, match="failover"):
            smoke.check_device_path("mesh verifier", **run)


def test_nested_compile_spans_count_once():
    """A jit traced inside another reports its own duration: the warm
    phase's sums take the union of each thread's spans."""
    spans = [(0.0, 10.0), (2.0, 3.0), (9.0, 10.5), (11.0, 12.0)]
    assert smoke._merge(spans) == [(0.0, 10.5), (11.0, 12.0)]


def test_one_wrong_verdict_is_refused():
    want = [True] * 16
    got = list(want)
    got[7] = not got[7]
    with pytest.raises(smoke.SmokeFailure, match=r"\[7\]"):
        smoke.check_verdicts("bad wave", got, want)


def test_non_tpu_platform_is_refused():
    with pytest.raises(smoke.SmokeFailure, match="'cpu'"):
        smoke.check_platform("cpu")


def test_main_off_the_chip_prints_no_result(capsys):
    """Under the tests JAX runs on the CPU: main() refuses before any
    phase, naming the platform, and prints nothing on stdout."""
    with pytest.raises(smoke.SmokeFailure, match="'cpu'"):
        smoke.main([])
    assert capsys.readouterr().out == ""
