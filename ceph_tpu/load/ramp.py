"""Saturation search: step the offered rate until an SLO gate breaks.

The knee is the highest offered rate at which EVERY gate still passes
— the number a capacity planner actually wants, and the one the
reference's production deployments size clusters by (PAPER.md L5/L6).
One cluster is booted and reused across steps (counter deltas make each
window self-contained), the offered rate doubles per step, and the
sweep stops at the first failing step (or when the scale list runs
out).

The result is a ``LOAD_r*.json`` artifact beside the BENCH records,
carrying the trust-model stamps of those records: mode
``cluster_vstart``, a NULL ``vs_baseline`` (load artifacts are never a
baseline ratio), and ``session_only: true`` — the dev host is
load-sensitive (BENCH_NOTES round 12), so absolute knee numbers only
compare WITHIN one session; cross-session judgments use gate verdicts,
not ops/s.
"""

from __future__ import annotations

import asyncio
import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence

from ceph_tpu.load.driver import LoadContext, LoadSpec, run_load

DEFAULT_SCALES: Sequence[float] = (1, 2, 4, 8, 16, 32, 64)

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


async def ramp(spec: LoadSpec, seed: int,
               scales: Sequence[float] = DEFAULT_SCALES,
               tmpdir: Optional[str] = None) -> Dict:
    """Run the sweep; returns the artifact document (unwritten)."""
    ctx = await LoadContext.create(spec, seed, tmpdir=tmpdir)
    steps: List[Dict] = []
    knee: Optional[Dict] = None
    try:
        for scale in scales:
            step_spec = spec.scaled(scale)
            result, report = await run_load(step_spec, seed, ctx=ctx)
            offered_rate = result.offered / max(1e-6, step_spec.duration)
            p99_row = next((r for r in report.rows
                            if r["gate"] == "p99"), {})
            goodput_row = next((r for r in report.rows
                                if r["gate"] == "goodput"), {})
            step = {
                "scale": scale,
                "offered_ops_s": round(offered_rate, 1),
                "offered_ops": result.offered,
                "acked_ops_scraped": goodput_row.get("value"),
                "p99_ms": p99_row.get("value"),
                "passed": report.passed,
                "gates": report.as_rows(),
                # traceability (round 17): the failing gates' observed
                # vs threshold values and the graft-blackbox bundle a
                # failed judgment triggered — the artifact alone
                # diagnoses a failed step
                "failed_gates": report.failing_gates(),
                "postmortem": report.postmortem,
                "client": result.as_dict(),
            }
            steps.append(step)
            if report.passed:
                knee = {"scale": scale,
                        "offered_ops_s": step["offered_ops_s"],
                        "acked_ops_scraped": step["acked_ops_scraped"],
                        "p99_ms": step["p99_ms"]}
            else:
                break
            # quiesce between steps so one window's stragglers don't
            # bleed into the next window's scrape delta
            await asyncio.sleep(0.5)
    finally:
        await ctx.close()
    return {
        "kind": "graft-load ramp",
        "spec": spec.name,
        "seed": seed,
        "mode": "cluster_vstart",
        "vs_baseline": None,
        "baseline_src": "unmeasured",
        "session_only": True,
        "load_sensitive_host": True,
        "excluded_from_vs_baseline": True,
        "steps": steps,
        "knee": knee,
    }


def next_round() -> int:
    """Artifact numbering follows the existing BENCH/LOAD trajectory:
    one past the highest round on disk."""
    rounds = [0]
    for pat in ("BENCH_r*.json", "LOAD_r*.json"):
        for path in glob.glob(os.path.join(_REPO, pat)):
            m = re.search(r"_r(\d+)\.json$", path)
            if m:
                rounds.append(int(m.group(1)))
    return max(rounds) + 1


def write_artifact(doc: Dict, out: Optional[str] = None) -> str:
    path = out or os.path.join(_REPO, f"LOAD_r{next_round():02d}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    return path


def format_table(doc: Dict) -> str:
    """The worked ramp table (README / `scripts/load.py report`)."""
    lines = [f"ramp {doc['spec']} seed={doc['seed']} "
             f"(mode={doc['mode']}, session-only numbers)",
             f"{'scale':>6} {'offered/s':>10} {'acked':>8} "
             f"{'p99 ms':>9}  gates"]
    for s in doc["steps"]:
        failed = [r["gate"] for r in s["gates"] if not r["passed"]]
        lines.append(
            f"{s['scale']:>6g} {s['offered_ops_s']:>10} "
            f"{s['acked_ops_scraped'] if s['acked_ops_scraped'] is not None else '-':>8} "
            f"{s['p99_ms'] if s['p99_ms'] is not None else '-':>9}  "
            + ("ALL PASS" if s["passed"] else
               "FAIL: " + ",".join(failed)))
    knee = doc.get("knee")
    lines.append("knee: " + (
        f"{knee['offered_ops_s']} offered ops/s (scale {knee['scale']})"
        if knee else "NONE — no step passed every gate"))
    return "\n".join(lines)
