#!/usr/bin/env python
"""Run the scenario fleet (lodestar_tpu/sim/scenarios.py) and emit a
provenance-stamped SCENARIOS.json.

Each scenario is a named, deterministic adversity regime with a
machine-evaluated SLO contract; this CLI runs a profile of the
registry and exits non-zero when any SLO row (or scenario body)
failed — the CI shape: tier 1 runs a fast smoke slice through
tools/run_tests.sh, tier 2 runs the full profiles.

Usage:
  python tools/run_scenarios.py                        # all, smoke
  python tools/run_scenarios.py --profile full
  python tools/run_scenarios.py --only reorg_storm,blob_firehose_under_load
  python tools/run_scenarios.py --list
  python tools/run_scenarios.py --json SCENARIOS.json  # artifact path
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# Same hermetic setup as tests/conftest.py: the fleet's slot counts
# and committee shapes assume the minimal preset, and the runs must
# be reproducible on the virtual CPU backend.
os.environ.setdefault("LODESTAR_PRESET", "minimal")
os.environ["JAX_PLATFORMS"] = "cpu"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--profile", choices=("smoke", "full"),
                    default="smoke")
    ap.add_argument("--only", default=None,
                    help="comma-separated scenario names")
    ap.add_argument("--seed", type=int, default=20260807)
    ap.add_argument("--json", dest="json_path", default=None,
                    help="write the result artifact here "
                         "(default: <repo>/SCENARIOS.json)")
    ap.add_argument("--no-json", action="store_true",
                    help="skip writing the artifact")
    ap.add_argument("--list", action="store_true",
                    help="list registered scenarios and exit")
    args = ap.parse_args(argv)

    from lodestar_tpu.sim.scenarios import SCENARIOS, run_all

    if args.list:
        for name, spec in SCENARIOS.items():
            print(f"{name}: {spec.summary}")
            print(f"    faults: {', '.join(spec.faults)}")
            print(f"    slos:   {', '.join(spec.slo_names)}")
        return 0

    only = (
        [n.strip() for n in args.only.split(",") if n.strip()]
        if args.only
        else None
    )
    results = run_all(profile=args.profile, seed=args.seed, only=only)
    for res in results:
        print(res.summary())
        if res.error:
            print(res.error, file=sys.stderr)

    n_pass = sum(1 for r in results if r.passed)
    print(f"\n{n_pass}/{len(results)} scenarios passed "
          f"[{args.profile}, seed={args.seed}]")

    if not args.no_json:
        from lodestar_tpu.utils.provenance import provenance

        artifact = {
            "profile": args.profile,
            "seed": args.seed,
            "passed": n_pass == len(results),
            "results": [r.to_dict() for r in results],
            "provenance": provenance(),
        }
        path = Path(args.json_path) if args.json_path else (
            REPO / "SCENARIOS.json"
        )
        path.write_text(json.dumps(artifact, indent=2) + "\n")
        print(f"wrote {path}")

    return 0 if n_pass == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
