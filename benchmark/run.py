#!/usr/bin/env python
"""The benchmark: one process, one cell, once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs on a TPU only: it exits non-zero, printing no result, unless JAX
finds a TPU whose kind is in the peaks table and as many chips as the
cell asks for.  The last line of standard output is the result (one JSON
object; its last keys are ``errors`` and ``checks``, every number compared
beside its limit), and the checks are also the last lines of standard
error; everything else (phase times, counters) is on earlier lines.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics.
"""

from __future__ import annotations

import time

STARTED_AT = time.monotonic()

import argparse     # noqa: E402
import asyncio      # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def say(**row) -> None:
    print(json.dumps(row), flush=True)


def result_line(out: dict, device: dict, trace: bool) -> dict:
    """The last line of standard output, from what ``CellRun.run``
    returned.  ``errors`` (the first few things that ops and reads said
    when they failed) and ``checks`` (every number compared beside its
    limit) come last, ``checks`` the very last."""
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": out["metrics"],
              "device": device}
    if trace:
        summary = out["trace"]
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["errors"] = out["errors"]
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in out["checks"]}
    return result


def main(argv=None, before_run=None) -> int:
    """``before_run`` is the control's seam (benchmark/control.py): it is
    called once the chip is found and before the cell is built."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import peaks
    from benchmark.harness.cell import CellRun
    from benchmark.harness.loader import BenchmarkError, load_cell

    try:
        cell = load_cell(args.workload)
    except BenchmarkError as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 2

    import jax

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if platform != "tpu" or kind not in peaks.PEAKS:
        print(f"benchmark: needs a TPU in the peaks table "
              f"{sorted(peaks.PEAKS)}; JAX reports platform {platform!r}, "
              f"kind {kind!r}.  There is no CPU fallback.", file=sys.stderr)
        return 3
    if len(devs) < cell.chips:
        print(f"benchmark: cell {cell.name} asks for {cell.chips} chips, "
              f"JAX reports {len(devs)}", file=sys.stderr)
        return 3

    from ceph_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    say(workload=cell.name, config=cell.config_name,
        traffic=cell.traffic_name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, platform=platform, device_kind=kind,
        device_count=len(devs), jax=jax.__version__,
        compile_cache_dir=cache_dir,
        import_s=time.monotonic() - STARTED_AT)

    if before_run is not None:
        before_run(say)
    run = CellRun(cell, args.seed, args.seconds, bool(args.trace),
                  STARTED_AT, say,
                  trace_dir=os.path.join(ROOT, ".bench_trace"),
                  device_kind=kind)
    try:
        out = asyncio.run(run.run())
    except BenchmarkError as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 2

    device = {"platform": platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": max(
                  int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in devs[:cell.chips])}
    if args.trace:
        say(end_to_end_of_traced_run=out["end_to_end"])
    result = result_line(out, device, bool(args.trace))
    # each number compared beside its limit: the last lines of standard
    # error, and the last key of the result
    for e in out["errors"]:
        print(f"error {e}", file=sys.stderr)
    for c in out["checks"]:
        print(f"check {c['name']} {c['value']} {c['rule']} {c['limit']} "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
