"""The per-layer metric readers.  Each metric is a file
``layer_metrics/<name>.json`` that names one of five source kinds and what
that kind reads; a reader that finds nothing to read returns None and the
metric is left out of the result line.

    attribution_ms    mean ms per op spent in the listed stages of the
                      program's op timelines (``dump_op_attribution``),
                      over the ops of one kind completed in the window
    counter_ratio     ratio of the growth of two ``KERNELS`` counters
                      over the window (``scale`` multiplies, e.g. 100)
    trace_idle_share  100 * (1 - device busy / profiled slice)
    trace_program_ms  mean device ms per event whose name matches
    trace_roofline    least time the chip needs for the matching events'
                      work (a cost function of a counter's growth over
                      the profiled slice) over their device time, in %
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional

from . import peaks, xplane
from .loader import BenchmarkError


@dataclass
class Readings:
    """What a traced run hands the readers."""
    config: dict
    device_kind: str
    attribution: Dict[str, dict]        # op kind -> merged report
    counters: Dict[str, float]          # growth over the window
    slice_counters: Dict[str, float]    # growth over the profiled slice
    trace: Optional[xplane.TraceSummary]


def _attribution_ms(reader: dict, r: Readings) -> Optional[float]:
    rep = r.attribution.get(reader["op"])
    if not rep or not rep.get("ops"):
        return None
    stages = rep["stages"]
    return 1e3 * sum(stages[s]["s"] for s in reader["stages"]
                     if s in stages) / rep["ops"]


def _counter_ratio(reader: dict, r: Readings) -> Optional[float]:
    den = r.counters.get(reader["denominator"], 0)
    if not den:
        return None
    return float(reader.get("scale", 1)) * \
        r.counters.get(reader["numerator"], 0) / den


def _trace_idle_share(reader: dict, r: Readings) -> Optional[float]:
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)


def _matching(reader: dict, r: Readings):
    if r.trace is None:
        return []
    return xplane.matching(r.trace.events(reader["line"]),
                           reader["patterns"])


def _trace_program_ms(reader: dict, r: Readings) -> Optional[float]:
    evs = _matching(reader, r)
    if not evs:
        return None
    return sum(e[2] for e in evs) / 1e6 / len(evs)


def _trace_roofline(reader: dict, r: Readings) -> Optional[float]:
    evs = _matching(reader, r)
    work = r.slice_counters.get(reader["counter"], 0)
    if not evs or not work:
        return None
    cost = peaks.COST_FUNCTIONS[reader["cost_function"]]
    ops, moved = cost(r.config, work)
    share, _bound = peaks.roofline_share(
        r.device_kind, ops, moved, sum(e[2] for e in evs) / 1e9)
    return share


KINDS: Dict[str, Callable[[dict, Readings], Optional[float]]] = {
    "attribution_ms": _attribution_ms,
    "counter_ratio": _counter_ratio,
    "trace_idle_share": _trace_idle_share,
    "trace_program_ms": _trace_program_ms,
    "trace_roofline": _trace_roofline,
}


def read_metric(name: str, reader: dict, readings: Readings
                ) -> Optional[float]:
    try:
        kind = KINDS[reader["kind"]]
    except KeyError:
        raise BenchmarkError(
            f"per-layer metric {name!r}: source kind "
            f"{reader.get('kind')!r}; the harness has {sorted(KINDS)}"
        ) from None
    return kind(reader, readings)
