#!/usr/bin/env python
"""Device idle gaps by host cause: the benchmark's own traced run, and
then the program's tick records laid over its device trace.

    python benchmark/gaps.py --workload <name> --seed <n> --seconds <s> \
        [--keep <dir>] [--slack-ms <ms>]

Runs ``benchmark/run.py`` with ``--trace 1`` unchanged, in this process,
so the program's ring of tick records (``ceph_tpu/trace/tick.py``) is
still in memory when it returns and ``.bench_trace/`` still holds the
profiled slice.  Both are stamped in Unix nanoseconds (the trace's
``profile_start_time``), so ``ceph_tpu.trace.gapjoin.join`` needs no host
tracer.  After the run's result line this prints: whether the ring still
covers the slice, the causality check, idle time by host cause, the ten
longest gaps with their causes, and a tick's three transfer means.  Its
last line is all of that as one JSON object; ``--keep`` also copies the
trace and writes the ring's ticks there; ``--slack-ms`` (1) is how far a
program event may lie outside its tick's window before the causality
check counts it (PERF.md: within one session the device's timeline has
wandered up to 2.6 ms against the host's clock).  The driver never runs this
file; it is a by-hand tool like ``describe_trace.py``.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a share of program events outside every tick above this says the two
# clocks do not agree: no table is printed
CAUSALITY_LIMIT = 0.01


class _Tee:
    """Standard output, with the last line that holds a result kept."""

    def __init__(self, out):
        self.out = out
        self.result = None
        self._buf = ""

    def write(self, text: str) -> int:
        self._buf += text
        *lines, self._buf = self._buf.split("\n")
        for line in lines:
            if line.startswith('{"correct"'):
                self.result = json.loads(line)
        return self.out.write(text)

    def __getattr__(self, name):
        return getattr(self.out, name)


def session_bounds(path: str):
    """(profile_start_time, profile_stop_time) in Unix ns."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        import gzip
        raw = gzip.decompress(raw)
    data = ProfileData.from_serialized_xspace(raw)
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            return (int(stats["profile_start_time"]),
                    int(stats["profile_stop_time"]))
    raise KeyError(f"no 'Task Environment' plane in {path}")


def _ms(ns: float) -> str:
    return f"{ns / 1e6:.3f} ms"


def report(path: str, ticks, capacity: int, device: dict,
           slack_ns: int = 1_000_000) -> dict:
    """Print the join of ``ticks`` over the trace at ``path`` and return
    it.  ``device`` is the run's ``device`` entry (``window_s`` and
    ``busy_s``: what ``device_idle.write`` was computed from);
    ``slack_ns`` is how far a program event may lie outside its tick's
    window before the causality check counts it."""
    from benchmark.harness import xplane
    from ceph_tpu.trace import gapjoin

    start, stop = session_bounds(path)
    out = {"ring_ticks": len(ticks), "ring_capacity": capacity,
           "profile_start_ns": start, "profile_stop_ns": stop}
    covered = bool(ticks) and (len(ticks) < capacity
                               or ticks[0].opened_ns <= start)
    out["ring_covers_slice"] = covered
    print(f"\nring: {len(ticks)} of {capacity} ticks; "
          + ("it covers the slice" if covered else
             "its oldest tick is NEWER than the slice's start: no table"))
    if not covered:
        return out
    for dev in xplane.load(path):
        joined = gapjoin.join(dev.lines.get(xplane.OPS_LINE, []),
                              dev.lines.get(xplane.MODULES_LINE, []),
                              start, stop, ticks, slack_ns=slack_ns)
        out[dev.plane] = joined
        check = joined["causality"]
        print(f"{dev.plane}: {check['program_events']} program events, "
              f"{check['outside_every_tick']} inside no tick's window "
              f"({100 * check['outside_share']:.2f}%); "
              f"{check['planar_tiled_events']} jit__planar_tiled events, "
              f"{check['tick_windows_in_trace']} ticks with their device "
              f"window in the traced span")
        for ev in check["outside_events"]:
            print(f"  outside: {ev['name'].split('(')[0]} at "
                  f"+{ev['at_ns'] / 1e9:.6f} s for {_ms(ev['dur_ns'])}: "
                  f"starts {_ms(ev['starts_before_window_ns'])} before "
                  f"and ends {_ms(ev['ends_after_window_ns'])} after the "
                  f"window of tick {ev['tick']}")
        if check["outside_share"] > CAUSALITY_LIMIT:
            print(f"the clocks do not agree to +- {_ms(slack_ns)} (the "
                  f"worst event misses by {_ms(check['worst_miss_ns'])}): "
                  f"no table")
            continue
        idle = joined["idle_ns"]
        as_metric = 1e9 * (device["window_s"] - device["busy_s"])
        print(f"session {joined['session_ns'] / 1e9:.6f} s: "
              f"{_ms(joined['lead_ns'])} before the first device event "
              f"and {_ms(joined['tail_ns'])} after the last one are not "
              f"cut (idle or not traced); between them "
              f"{joined['traced_ns'] / 1e9:.6f} s, idle {idle / 1e9:.6f} "
              f"s; device_idle.write's slice gives "
              f"{as_metric / 1e9:.6f} s idle "
              f"({100 * (idle - as_metric) / as_metric:+.2f}%)")
        print("idle time by host cause:")
        for cause, ns in joined["idle_by_cause_ns"].items():
            print(f"  {cause:16s} {ns / 1e9:10.6f} s  "
                  f"{100 * ns / idle:6.2f}%")
        print("longest gaps:")
        for gap in joined["longest_gaps"]:
            by_cause: dict = {}
            for cause, ns in gap["causes"]:
                by_cause[cause] = by_cause.get(cause, 0) + ns
            causes = ", ".join(
                f"{c} {_ms(ns)}" for c, ns in
                sorted(by_cause.items(), key=lambda kv: -kv[1]))
            print(f"  {_ms(gap['ns'])} at +{gap['at_ns'] / 1e9:.3f} s: "
                  f"{causes}")
        per = joined["per_tick"]
        if per["ticks"]:
            print(f"per tick (mean of {per['ticks']}): dispatch -> device "
                  f"start {_ms(per['dispatch_to_device_start_ns'])}, "
                  f"device {_ms(per['device_ns'])}, device end -> "
                  f"readback return "
                  f"{_ms(per['device_end_to_readback_return_ns'])}; "
                  f"transfer-and-runtime share "
                  f"{100 * per['transfer_and_runtime_share']:.2f}% of "
                  f"{_ms(per['window_ns'])}")
    return out


def main(argv=None) -> int:
    from benchmark import run
    from benchmark.harness import xplane
    from ceph_tpu.trace.tick import TICKS

    argv = list(sys.argv[1:] if argv is None else argv)
    own = {"--keep": None, "--slack-ms": "1"}
    for flag in own:            # the rest is run.py's
        if flag in argv:
            at = argv.index(flag)
            own[flag] = argv[at + 1]
            del argv[at:at + 2]
    keep = own["--keep"]
    tee = _Tee(sys.stdout)
    sys.stdout = tee
    try:
        rc = run.main(argv + ["--trace", "1"])
    finally:
        sys.stdout = tee.out
    if rc != 0 or tee.result is None:
        return rc or 1
    path = xplane.find_xplane(os.path.join(run.ROOT, ".bench_trace"))
    ticks = list(TICKS.ring)
    if keep is not None:
        import shutil

        os.makedirs(keep, exist_ok=True)
        shutil.copy(path, os.path.join(keep, "trace.xplane.pb"))
        with open(os.path.join(keep, "ticks.json"), "w",
                  encoding="utf-8") as f:
            json.dump([{"name": t.name, "daemon": t.daemon, "seq": t.seq,
                        "op_ids": t.op_ids, "stripes": t.stripes,
                        "bucket": t.bucket, "calls": t.calls,
                        "thread": t.thread, "t": list(t.t)}
                       for t in ticks], f)
    out = report(path, ticks, TICKS.ring.maxlen, tee.result["device"],
                 slack_ns=int(float(own["--slack-ms"]) * 1e6))
    print(json.dumps({"gaps": out}))
    return 0 if out["ring_covers_slice"] else 1


if __name__ == "__main__":
    sys.exit(main())
