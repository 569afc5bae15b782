"""graft-trace: device idle gaps by host cause — ticks laid over a device
trace.

A JAX profiler trace (xplane) stamps device events in ns from the session's
``profile_start_time`` (Unix ns, the ``Task Environment`` plane), and
``trace/tick.py`` stamps every tick phase in Unix ns, so the two lie on
one axis with no host tracer.  :func:`join` is pure: device events +
the session's start and stop + tick records in, and out

(a) every device idle gap between the first and the last device event
    cut into pieces by what the host was doing: the phase of the tick
    covering it, or ``no_tick`` when no tick was open in the process.
    Where ticks overlap, the piece goes to the tick that was OPENED
    FIRST.  The pieces of a gap sum to it exactly.
(b) per tick, from the program events (``XLA Modules``) between its
    ``to_planar`` start and its last readback's end: dispatch -> device
    start, device time, device end -> readback return.  What is not
    device time is the tick's transfer-and-runtime share.  An event
    inside several ticks' windows goes to the one opened first that
    does not hold that program yet (the device queue is FIFO, and a
    tick launches each program once).
(c) the causality check: the share of program events that lie inside NO
    tick's window (+- ``slack_ns``), and the count of kernel events
    against the count of tick windows that touch the traced span.  If
    the share is not ~0 the clocks do not agree and (a) and (b) mean
    nothing.

No jax import here: the caller reads the trace (``benchmark/gaps.py``).
"""

from __future__ import annotations

from bisect import bisect_right
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, int, int]        # name, start_ns from profile start, dur_ns
Segment = Tuple[int, int, str]      # start, end (Unix ns), label

NO_TICK = "no_tick"


def host_timeline(ticks: Sequence) -> List[Segment]:
    """What the host's tick threads were doing, as non-overlapping
    segments in time order.  Overlapping ticks: the one opened first
    holds the time, a later one gets what it leaves."""
    out: List[Segment] = []
    covered = 0
    for tick in sorted(ticks, key=lambda t: t.opened_ns):
        for name, t0, t1, _calls in tick.segments():
            t0 = max(t0, covered)
            if t1 > t0:
                out.append((t0, t1, name))
        covered = max(covered, tick.closed_ns)
    return out


def idle_gaps(events: Sequence[Event], lo: int, hi: int
              ) -> List[Tuple[int, int]]:
    """[lo, hi] minus the union of the events' intervals (all on one
    axis): the lead before the first event and the tail after the last
    one included."""
    gaps, at = [], lo
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        if start > at:
            gaps.append((at, min(start, hi)))
        at = max(at, start + dur)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return gaps


def cut_gap(gap: Tuple[int, int], timeline: List[Segment],
            ends: List[int]) -> List[Tuple[str, int]]:
    """One gap -> [(label, ns), ...] in time order, summing to it.
    ``ends`` is ``[seg[1] for seg in timeline]``."""
    at, hi = gap
    pieces: List[Tuple[str, int]] = []

    def add(label: str, ns: int) -> None:
        if pieces and pieces[-1][0] == label:
            pieces[-1] = (label, pieces[-1][1] + ns)
        else:
            pieces.append((label, ns))

    i = bisect_right(ends, at)
    while at < hi:
        if i == len(timeline) or timeline[i][0] >= hi:
            add(NO_TICK, hi - at)
            break
        t0, t1, label = timeline[i]
        if t0 > at:
            add(NO_TICK, t0 - at)
            at = t0
        stop = min(t1, hi)
        add(label, stop - at)
        at = stop
        i += 1
    return pieces


def _assign(modules: Sequence[Event], profile_start_ns: int,
            ticks: Sequence, slack_ns: int):
    """Program events -> the tick each belongs to.  Returns
    ({index into ``windows``: [(start, end), ...]}, windows, the events
    inside no window as (name, start, end) in Unix ns)."""
    windows = sorted(
        ((w[0], w[1], tick) for tick in ticks
         for w in (tick.device_window(),) if w is not None),
        key=lambda w: w[:2])
    held: Dict[int, set] = {}
    got: Dict[int, List[Tuple[int, int]]] = {}
    outside: List[Tuple[str, int, int]] = []
    active: List[int] = []
    nxt = 0
    for name, start, dur in sorted(modules, key=lambda e: e[1]):
        a = profile_start_ns + start
        b = a + dur
        while nxt < len(windows) and windows[nxt][0] - slack_ns <= a:
            active.append(nxt)
            nxt += 1
        active = [i for i in active if windows[i][1] + slack_ns >= a]
        inside = [i for i in active if b <= windows[i][1] + slack_ns]
        if not inside:
            outside.append((name, a, b))
            continue
        program = name.split("(", 1)[0]
        free = [i for i in inside if program not in held.get(i, ())]
        i = (free or inside)[0]         # windows are in opening order
        held.setdefault(i, set()).add(program)
        got.setdefault(i, []).append((a, b))
    return got, windows, outside


def join(ops: Sequence[Event], modules: Sequence[Event],
         profile_start_ns: int, profile_stop_ns: int, ticks: Sequence,
         slack_ns: int = 1_000_000, top: int = 10) -> Dict:
    """See the module's docstring.  ``ops`` (``XLA Ops``) cut the idle
    gaps, ``modules`` (``XLA Modules``) feed (b) and (c); both count
    from ``profile_start_ns``.  Times out are ns."""
    lo, hi = profile_start_ns, profile_stop_ns
    ticks = [t for t in ticks if t.closed_ns > lo and t.opened_ns < hi]
    # The session's edges are not cut: a profiler session opens before
    # and closes after the device is traced (on the chip ~0.05 s and
    # ~0.4 s), so before the first device event and after the last one
    # "idle" and "not traced" cannot be told apart.
    events = sorted(((n, lo + s, d) for n, s, d in ops),
                    key=lambda e: e[1])
    first = events[0][1] if events else hi
    last = max((s + d for _n, s, d in events), default=hi)

    # (a)
    timeline = host_timeline(ticks)
    ends = [seg[1] for seg in timeline]
    by_cause: Dict[str, int] = {}
    cut = []
    for gap in idle_gaps(events, first, last):
        pieces = cut_gap(gap, timeline, ends)
        cut.append((gap[1] - gap[0], gap[0] - lo, pieces))
        for label, ns in pieces:
            by_cause[label] = by_cause.get(label, 0) + ns
    cut.sort(key=lambda g: -g[0])

    # (b) over the ticks whose whole window the session saw
    got, windows, outside = _assign(modules, lo, ticks, slack_ns)
    rows = []
    for i, spans in got.items():
        w0, w1, _tick = windows[i]
        if w0 < first or w1 > last:
            continue
        rows.append((min(a for a, _b in spans) - w0,
                     sum(b - a for a, b in spans),
                     w1 - max(b for _a, b in spans),
                     w1 - w0))
    n = len(rows)
    per_tick = {"ticks": n}
    if n:
        for j, key in enumerate(("dispatch_to_device_start_ns",
                                 "device_ns",
                                 "device_end_to_readback_return_ns",
                                 "window_ns")):
            per_tick[key] = sum(r[j] for r in rows) / n
        per_tick["transfer_and_runtime_share"] = \
            1.0 - per_tick["device_ns"] / per_tick["window_ns"]

    # (c)
    # ticks whose device window touches the traced span: as many as there
    # are kernel events, give or take one cut by either edge
    touching = sum(1 for w0, w1, _t in windows
                   if w1 + slack_ns >= first and w0 - slack_ns <= last)

    def miss(event) -> Dict:
        """By how much an event misses the window nearest to it."""
        name, a, b = event
        w0, w1, tick = min(windows, key=lambda w: max(w[0] - a, b - w[1]))
        return {"name": name, "at_ns": a - lo, "dur_ns": b - a,
                "tick": tick.seq, "starts_before_window_ns": w0 - a,
                "ends_after_window_ns": b - w1,
                "miss_ns": max(w0 - a, b - w1)}

    missed = sorted((miss(e) for e in outside if windows),
                    key=lambda m: -m["miss_ns"])
    return {
        "session_ns": hi - lo,
        "lead_ns": first - lo,
        "tail_ns": hi - last,
        "traced_ns": last - first,
        "idle_ns": sum(g[0] for g in cut),
        "idle_by_cause_ns": dict(sorted(by_cause.items(),
                                        key=lambda kv: -kv[1])),
        "longest_gaps": [
            {"ns": ns, "at_ns": at, "causes": pieces}
            for ns, at, pieces in cut[:top]],
        "per_tick": per_tick,
        "causality": {
            "program_events": len(modules),
            "outside_every_tick": len(outside),
            "outside_share": len(outside) / len(modules) if modules
            else 0.0,
            "slack_ns": slack_ns,
            # the slack that would have taken every event in: how far the
            # two clocks disagree, if the ticks are all there
            "worst_miss_ns": missed[0]["miss_ns"] if missed else 0,
            "outside_events": missed[:top],
            "planar_tiled_events": sum(
                1 for e in modules if e[0].startswith("jit__planar_tiled")),
            "tick_windows_in_trace": touching,
        },
    }
