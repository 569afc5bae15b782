"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to what the
trace-sourced metrics read: per device, the union of the intervals in
which an operation ran, the time per program and per operation, and the
longest idle gaps."""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[str, int, int]        # name, start_ns, duration_ns


@dataclass
class DeviceTrace:
    plane: str
    lines: Dict[str, List[Event]] = field(default_factory=dict)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, plane_prefix: str = DEVICE_PLANE_PREFIX
         ) -> List[DeviceTrace]:
    """The device planes of one trace file, events in time order."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        import gzip
        raw = gzip.decompress(raw)
    data = ProfileData.from_serialized_xspace(raw)
    out = []
    for plane in data.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        dev = DeviceTrace(plane.name)
        for line in plane.lines:
            dev.lines[line.name] = sorted(
                ((e.name, int(e.start_ns), int(e.duration_ns))
                 for e in line.events), key=lambda ev: ev[1])
        out.append(dev)
    return out


def busy_ns(events: List[Event]) -> int:
    """Length of the union of the events' intervals."""
    total, end = 0, None
    for _name, start, dur in events:
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def totals(events: List[Event]) -> Dict[str, Tuple[int, int]]:
    """name -> (count, total duration in ns)."""
    out: Dict[str, Tuple[int, int]] = {}
    for name, _start, dur in events:
        n, t = out.get(name, (0, 0))
        out[name] = (n + 1, t + dur)
    return out


def idle_gaps(events: List[Event], top: int = 10
              ) -> List[Tuple[str, int]]:
    """The longest gaps between device operations, each labelled by the
    operation that ended before it (the trace carries no host span to say
    what the host was doing)."""
    gaps, end, last = [], None, None
    for name, start, dur in events:
        if end is not None and start > end:
            gaps.append((f"after {short_name(last)}", start - end))
        if end is None or start + dur > end:
            end, last = start + dur, name
    gaps.sort(key=lambda g: -g[1])
    return gaps[:top]


def short_name(name: str, limit: int = 96) -> str:
    """An XLA-op event is named by its whole HLO line; keep the op's name
    and the shape it produces."""
    op, sep, rest = name.partition(" = ")
    if sep:
        name = f"{op} {rest.split('{', 1)[0].split('(', 1)[0].strip()}"
    return name[:limit]


def matching(events: List[Event], patterns: List[str]) -> List[Event]:
    return [e for e in events if any(p in e[0] for p in patterns)]


@dataclass
class TraceSummary:
    """What the trace-sourced readers see.  ``busy_s`` is averaged over
    the device planes found (the chips used)."""
    window_s: float
    devices: List[DeviceTrace]

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(busy_ns(d.lines.get(OPS_LINE, []))
                   for d in self.devices) / len(self.devices) / 1e9

    def events(self, line: str) -> List[Event]:
        """Events of one line over all devices."""
        return [e for d in self.devices for e in d.lines.get(line, [])]

    def breakdown(self, top: int = 10) -> dict:
        ops = totals([(short_name(n), s, d)
                      for n, s, d in self.events(OPS_LINE)])
        ranked = sorted(ops.items(), key=lambda kv: -kv[1][1])[:top]
        gaps = sorted((g for d in self.devices
                       for g in idle_gaps(d.lines.get(OPS_LINE, []), top)),
                      key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, t / 1e9] for n, (_c, t) in ranked],
                "idle_gaps": [[n, t / 1e9] for n, t in gaps]}
