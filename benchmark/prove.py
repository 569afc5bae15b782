#!/usr/bin/env python
"""Run one cell several times, one process each, one after the other, and
keep each run's lines: a cell's proof in ONE chip call.

    chiprun --timeout 1800 -- python benchmark/prove.py \
        --workload k2m1_write_4m_t16 --seeds 11,12,13,14,15,16 --sets 2

Every run is ``benchmark/run.py`` as the driver starts it.  This parent
never imports JAX (a chip belongs to one process).  Output goes under
``chiprun_out/<workload>/``: ``<tag>.log`` per run and ``results.jsonl``
with each run's last line; a table of the spreads is printed at the end.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def spread(values):
    """Distance between the quartiles as a share of the median (None for
    a metric whose median is 0: a traced run's `hb_late_share.write`)."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q3 - q1) / median if median else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated; one run per seed and set")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: BENCHMARK.json's run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run benchmark/control.py (one guarantee broken) "
                         "in place of run.py; every run must come out "
                         "NOT correct")
    ap.add_argument("--keep-trace", action="store_true",
                    help="copy the last traced run's xplane file out")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    seconds = args.seconds or spec["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    for s in range(args.sets):
        for seed in seeds:
            tag = f"{'control' if args.control else 't%d' % args.trace}" \
                  f"_set{s}_seed{seed}"
            cmd = [sys.executable, os.path.join(
                       HERE, "control.py" if args.control else "run.py"),
                   "--workload", args.workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(args.trace)]
            t0 = time.monotonic()
            log = os.path.join(out_dir, tag + ".log")
            # the driver's limits: 360 s a run, 1200 s for a cell's first
            limit = 1200 if not rows else 360
            with open(log, "w", encoding="utf-8") as f:
                try:
                    rc = subprocess.run(cmd, cwd=ROOT, stdout=f,
                                        stderr=subprocess.STDOUT,
                                        timeout=limit).returncode
                except subprocess.TimeoutExpired:
                    rc = 124
            wall = time.monotonic() - t0
            with open(log, encoding="utf-8") as f:
                lines = [ln for ln in f.read().splitlines()
                         if ln.startswith("{")]
            try:
                result = json.loads(lines[-1]) if lines else None
                if "correct" not in result:
                    result = None
            except (json.JSONDecodeError, TypeError):
                result = None
            row = {"tag": tag, "set": s, "seed": seed, "rc": rc,
                   "wall_s": wall, "result": result}
            rows.append(row)
            with open(os.path.join(out_dir, "results.jsonl"), "a",
                      encoding="utf-8") as f:
                f.write(json.dumps(row) + "\n")
            res = result or {}
            brief = {k: v["value"]
                     for k, v in res.get("metrics", {}).items()}
            if args.trace:
                # a traced run's own end-to-end readings are on a line
                # before the last: show those, not its per-layer metrics
                traced = [json.loads(ln)["end_to_end_of_traced_run"]
                          for ln in lines
                          if ln.startswith('{"end_to_end_of_traced_run"')]
                brief = {k: v["value"]
                         for k, v in (traced[-1] if traced else {}).items()}
            checks = res.get("checks", {})
            room = {k: checks[k]["value"] for k in
                    ("store_fill_peak", "host_mem_available_gib")
                    if k in checks}
            # what the run said on earlier lines: whom set-up killed (a
            # cell degraded in set-up), and whether its window compiled
            for ln in lines:
                if '"kill_in_set_up"' in ln:
                    room["victims"] = json.loads(ln)["victims"]
                elif '"compiles_in_window"' in ln:
                    room["compiles_in_window"] = \
                        json.loads(ln)["compiles_in_window"]
            row["victims"] = room.get("victims")
            not_ok = {} if res.get("correct", True) else {
                "checks": checks, "errors": res.get("errors")}
            print(json.dumps({"tag": tag, "rc": rc,
                              "wall_s": round(wall, 1),
                              "correct": res.get("correct"),
                              "failed": res.get("failed"),
                              **brief, **room, **not_ok}), flush=True)
            if rc != 0:
                with open(log, encoding="utf-8") as f:
                    print(f.read()[-3000:], flush=True)
    if args.keep_trace:
        import glob
        import shutil
        for path in glob.glob(os.path.join(
                ROOT, ".bench_trace", "plugins", "profile", "*", "*.pb")):
            shutil.copy(path, os.path.join(out_dir, "trace.xplane.pb"))

    # spreads per set, leaving out the very first run (it compiles)
    for s in range(args.sets):
        good = [r for r in rows if r["set"] == s and r["result"]]
        names = sorted({n for r in good for n in r["result"]["metrics"]})
        for n in names:
            vals = [r["result"]["metrics"][n]["value"] for r in good
                    if n in r["result"]["metrics"]]
            if n == "setup_s" and s == 0:
                vals = vals[1:]
            if len(vals) >= 2:
                print(json.dumps({
                    "set": s, "metric": n, "n": len(vals),
                    "median": statistics.median(vals),
                    "min": min(vals), "max": max(vals),
                    "iqr_share": spread(vals) if len(vals) >= 3 else None}),
                    flush=True)
    # a cell degraded in set-up: its end-to-end metrics by victim
    by_victim = {}
    for r in rows:
        if r.get("victims") and r["result"] and not args.trace:
            by_victim.setdefault(str(r["victims"]), []).append(r)
    for victims, runs in sorted(by_victim.items()):
        for n in sorted({n for r in runs for n in r["result"]["metrics"]}):
            vals = [r["result"]["metrics"][n]["value"] for r in runs]
            print(json.dumps({"victims": victims, "metric": n,
                              "n": len(vals),
                              "median": statistics.median(vals),
                              "min": min(vals), "max": max(vals)}),
                  flush=True)
    want = not args.control
    bad = [r["tag"] for r in rows if r["rc"] != 0 or r["result"] is None
           or r["result"].get("correct") is not want]
    print(json.dumps({"runs": len(rows), "control": args.control,
                      "not_as_expected": bad}))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
