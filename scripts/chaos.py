#!/usr/bin/env python
"""graft-chaos CLI: run seeded fault-injection scenarios.

    python scripts/chaos.py list
    python scripts/chaos.py schedule --scenario smoke --seed 42
    python scripts/chaos.py run --scenario smoke --seed 42 [--json]

``run`` exits 0 when every invariant holds, 1 otherwise; ``schedule``
prints the resolved fault plan WITHOUT booting a cluster (two
invocations with the same seed print identical plans — the replay
contract, cheap to eyeball).  Scenarios with durable stores get a
temporary directory that is removed afterwards.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    from ceph_tpu.utils import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    sub.add_parser("list", help="list built-in scenarios")
    for name in ("schedule", "run"):
        p = sub.add_parser(name)
        p.add_argument("--scenario", required=True)
        p.add_argument("--seed", type=int, default=1)
        p.add_argument("--json", action="store_true")
        p.add_argument("--scale", type=float, default=1.0,
                       help="storm-scenario size factor: 1.0 = the "
                            "full acceptance shape (slow), small "
                            "fractions run the same code paths at "
                            "tier-1 size (e.g. --scale 0.06)")
        if name == "run":
            p.add_argument("--postmortem", default=".", metavar="DIR",
                           help="directory for triggered "
                                "POSTMORTEM_*.json bundles (default .)")
            p.add_argument("--no-postmortem", action="store_true",
                           help="disable the flight recorder / "
                                "postmortem bundles for this run")
    args = ap.parse_args()

    from ceph_tpu.chaos.balance import (
        ElasticScenario,
        build_elastic_plan,
        elastic_scenarios,
        run_elastic,
    )
    from ceph_tpu.chaos.frontdoor import (
        FrontdoorScenario,
        frontdoor_scenarios,
        run_frontdoor,
    )
    from ceph_tpu.chaos.integrity import (
        FillScenario,
        build_fill_plan,
        integrity_scenarios,
        run_fill_drain,
    )
    from ceph_tpu.chaos.scenario import (
        build_schedule,
        builtin_scenarios,
        run_scenario,
        storm_scenarios,
    )

    scenarios = builtin_scenarios()
    scenarios.update(frontdoor_scenarios(1.0))
    scenarios.update(integrity_scenarios(1.0))
    scenarios.update(elastic_scenarios(1.0))
    if getattr(args, "scale", 1.0) != 1.0:
        scenarios.update(storm_scenarios(args.scale))
        scenarios.update(frontdoor_scenarios(args.scale))
        scenarios.update(integrity_scenarios(args.scale))
        scenarios.update(elastic_scenarios(args.scale))
    if args.cmd == "list":
        for name, sc in sorted(scenarios.items()):
            print(f"{name:24s} osds={sc.osds} rounds={sc.rounds} "
                  f"store={sc.store} invariants={','.join(sc.invariants)}")
        return 0
    sc = scenarios.get(args.scenario)
    if sc is None:
        print(f"unknown scenario {args.scenario!r} "
              f"(try: {', '.join(sorted(scenarios))})", file=sys.stderr)
        return 2
    if args.cmd == "schedule":
        if isinstance(sc, FillScenario):
            print(json.dumps(build_fill_plan(sc, args.seed), indent=2))
        elif isinstance(sc, ElasticScenario):
            print(json.dumps(build_elastic_plan(sc, args.seed),
                             indent=2))
        else:
            print(json.dumps(build_schedule(sc, args.seed), indent=2))
        return 0
    if not args.no_postmortem:
        # graft-blackbox on by default for CLI runs: a conviction (or a
        # fired crash point / HEALTH_ERR edge) auto-produces a bundle
        from dataclasses import replace

        sc = replace(sc, config=tuple(sc.config) + (
            ("blackbox_enabled", 1),
            ("blackbox_dir", os.path.abspath(args.postmortem))))
    tmpdir = None
    try:
        if sc.store != "mem":
            tmpdir = tempfile.mkdtemp(prefix="graft_chaos_")
        if isinstance(sc, FrontdoorScenario):
            verdict = asyncio.run(run_frontdoor(sc, args.seed,
                                                tmpdir=tmpdir))
        elif isinstance(sc, FillScenario):
            verdict = asyncio.run(run_fill_drain(sc, args.seed,
                                                 tmpdir=tmpdir))
        elif isinstance(sc, ElasticScenario):
            verdict = asyncio.run(run_elastic(sc, args.seed,
                                              tmpdir=tmpdir))
        else:
            verdict = asyncio.run(run_scenario(sc, args.seed,
                                               tmpdir=tmpdir))
    finally:
        if tmpdir is not None:
            import shutil

            shutil.rmtree(tmpdir, ignore_errors=True)
    if args.json:
        print(json.dumps(verdict.as_dict(), indent=2))
    else:
        print(f"scenario {verdict.name} seed={verdict.seed}: "
              f"{'PASS' if verdict.passed else 'FAIL'} "
              f"({verdict.acked_objects} acked objects, "
              f"faults={verdict.counters})")
        for f in verdict.failures:
            print(f"  FAIL {f}")
        if getattr(verdict, "postmortem", None):
            print(f"  postmortem: {verdict.postmortem}")
    return 0 if verdict.passed else 1


if __name__ == "__main__":
    sys.exit(main())
