"""graft-trace: the coalesced device tick, opened.

``span.py`` follows an OP across daemons.  A tick is not an op: it
serves 1-8 of them on an executor thread, and no op event is stamped
inside it (the ops get ``batch_tick`` / ``batch_encoded`` afterwards,
from the tick's window).  This module records the tick itself: one
:class:`Tick` per coalesced device round trip, opened by the batcher's
drain loop (cluster/batcher.py), run on the worker thread through
``OSD._compute``, closed when the coroutine resumes, and — in between —
cut into host phases at the lines of ``ec/stripe.py`` where the work is:

    executor_wait  tick opened on the loop -> fn starts on a worker thread
    fill           zero + fill the host batch, pad to the bucket
    to_planar      host time inside codec.to_planar (staging, host->device,
                   dispatch of the ingest program)
    encode_dispatch  host time inside codec.encode_planar
    readback       each blocking np.asarray: waits for the device, then
                   device->host
    slice          the contiguous copy per op, out of the two readbacks
    crc            what exists only because of the shard crcs.  Device
                   path: the chunk-crc program's launch (behind the
                   encode, before the first readback), then the readback
                   of its words + the per-op fold.  Host path: every
                   crc32c_planar_rows group.  A dumped span says which
    wake           fn returns on the thread -> the drain loop resumes
    other          what the phases leave of the thread's wall

Every stamp is ``time.time_ns()``: Unix nanoseconds are the clock of the
device trace too (an xplane's ``Task Environment`` plane carries
``profile_start_time`` in Unix ns and every device event counts from
it), so ``trace/gapjoin.py`` can lay ticks over device events with no
host tracer (on the chip the two agreed to within 3 ms inside a session:
PERF.md).  NOT ``osd.clock`` (chaos-skewed) and not ``perf_counter``.

Always on.  Nothing here syncs with the device: a phase times what the
thread does today.  Outside a tick (tests and tools calling
``encode_planes_multi`` directly) :func:`phase` returns the
shared :data:`NULL_PHASE` and :func:`device_calls` / :func:`annotate`
do nothing.  A dumped tick is a list of dicts with ``Span.dump()``'s
fields, so ``assemble_tree`` and ``perfetto.chrome_trace_from_spans``
take it unchanged.
"""

from __future__ import annotations

import itertools
import threading
import time
from array import array
from collections import deque
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from ceph_tpu.utils.perf import KERNELS, PerfCounters

ENCODE_TICK = "encode_tick"

# newest ticks kept, process-wide: at the 64 KiB cell's ~128 ticks/s a
# whole 51 s window plus its verification
RING_TICKS = 8192

# in-thread phase -> the KERNELS counter that sums it over encode ticks,
# fed once per tick at close (all divide by ``ec_coalesced_ticks``)
_PHASE_COUNTERS = {
    "fill": ("ec_tick_fill_ns", "host batch build + bucket pad"),
    "to_planar": ("ec_tick_to_planar_ns", "host time inside "
                  "codec.to_planar (staging, host->device, ingest "
                  "dispatch)"),
    "encode_dispatch": ("ec_tick_dispatch_ns", "host time inside "
                        "codec.encode_planar"),
    "readback": ("ec_tick_readback_ns", "blocking readbacks (wait for "
                 "the device + device->host)"),
    "slice": ("ec_tick_slice_ns", "per-op contiguous copies"),
    "crc": ("ec_tick_crc_ns", "the shard crcs: device program launch, "
            "readback of its words and fold, or crc32c over the ops' "
            "plane groups on the host"),
}
# the phases a Tick can record, by index into its flat array
PHASES = tuple(_PHASE_COUNTERS)
_PHASE_INDEX = {name: i for i, name in enumerate(PHASES)}

_OTHER_COUNTERS = (
    ("ec_tick_wall_ns", "ns", "worker thread start -> return"),
    ("ec_tick_cpu_ns", "ns", "the worker thread's CPU time "
     "(time.thread_time_ns) between those two stamps: what is left of "
     "the wall it waited (for the device, for the GIL)"),
    ("ec_tick_handoff_ns", "ns", "loop -> executor thread start, plus "
     "thread return -> drain loop resumes"),
    ("ec_tick_device_calls", "calls", "jitted program launches + explicit "
     "host<->device transfers issued"),
    ("ec_tick_any_active_ns", "ns", "wall time with >= 1 encode tick "
     "thread running (process-wide)"),
    ("ec_tick_multi_active_ns", "ns", "wall time with >= 2 encode tick "
     "threads running (process-wide)"),
    ("ec_tick_crc_device_ticks", "ticks", "encode ticks whose shard crcs "
     "all came from a device program"),
)


def declare_counters(counters: PerfCounters) -> None:
    """The tick counters' schema."""
    for name, desc in _PHASE_COUNTERS.values():
        counters.add_u64(name, unit="ns", desc=f"encode tick: {desc}")
    for name, unit, desc in _OTHER_COUNTERS:
        counters.add_u64(name, unit=unit, desc=f"encode tick: {desc}")


class _NullPhase:
    """The phase outside a tick: every operation is a no-op.  One shared
    instance, as ``NULL_SPAN`` is."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def __bool__(self) -> bool:
        return False


NULL_PHASE = _NullPhase()

# the tick running on THIS worker thread (run_in_executor carries no
# contextvars, and a tick is a thread's, not a task's)
_CURRENT = threading.local()


class Tick:
    """One coalesced device tick: a flat record.  ``t`` holds every stamp
    in Unix ns: [opened, thread start, thread return, closed] and then
    four numbers per phase: [phase index, start, end, device calls]."""

    __slots__ = ("log", "name", "daemon", "seq", "op_ids", "stripes",
                 "bucket", "payload_bytes", "layout", "thread", "calls",
                 "cpu_ns", "t", "_phase", "_phase_t0", "_phase_calls")

    def __init__(self, log: "TickLog", name: str, daemon: str, seq: int,
                 op_ids: Sequence):
        self.log = log
        self.name = name
        self.daemon = daemon
        self.seq = seq
        self.op_ids = tuple(op_ids)
        self.stripes = 0
        self.bucket = 0
        self.payload_bytes = 0
        self.layout = ""        # the planes' serialization, once annotated
        self.thread = 0
        self.calls = 0
        self.cpu_ns = 0         # the worker thread's CPU time inside run
        self.t = array("q", (log.clock(), 0, 0, 0))
        self._phase = 0

    # -- the worker thread's side ---------------------------------------

    def run(self, fn, *args):
        """Run ``fn(*args)`` as this tick's thread work."""
        log = self.log
        encode = self.name == ENCODE_TICK
        self.thread = threading.get_ident()
        self.t[1] = log._thread_edge(+1) if encode else log.clock()
        cpu0 = time.thread_time_ns()
        _CURRENT.tick = self
        try:
            return fn(*args)
        finally:
            _CURRENT.tick = None
            self.cpu_ns = time.thread_time_ns() - cpu0
            self.t[2] = log._thread_edge(-1) if encode else log.clock()

    def phase(self, name: str) -> "Tick":
        self._phase = _PHASE_INDEX[name]
        return self

    def __enter__(self) -> "Tick":
        self._phase_calls = self.calls
        self._phase_t0 = self.log.clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.t.extend((self._phase, self._phase_t0, self.log.clock(),
                       self.calls - self._phase_calls))
        return False

    # -- the loop's side --------------------------------------------------

    def close(self) -> None:
        """The drain loop resumed: stamp, record, feed the counters.  A
        tick whose thread never returned (cancelled mid-flight) is
        dropped."""
        if self.t[3] or not self.t[2]:
            return
        self.t[3] = self.log.clock()
        self.log._closed(self)

    # -- reading ------------------------------------------------------------

    @property
    def opened_ns(self) -> int:
        return self.t[0]

    @property
    def closed_ns(self) -> int:
        return self.t[3]

    def phases(self) -> Iterator[Tuple[str, int, int, int]]:
        """(name, start_ns, end_ns, device calls) as recorded."""
        t = self.t
        for i in range(4, len(t), 4):
            yield PHASES[t[i]], t[i + 1], t[i + 2], t[i + 3]

    def phase_ns(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for name, t0, t1, _calls in self.phases():
            out[name] = out.get(name, 0) + (t1 - t0)
        return out

    def segments(self) -> List[Tuple[str, int, int, int]]:
        """The root tiled: executor_wait, the phases with ``other``
        between them, wake.  (name, start_ns, end_ns, device calls); the
        pieces' lengths sum to the root's exactly."""
        opened, start, end, closed = self.t[:4]
        out = [("executor_wait", opened, start, 0)]
        at = start
        for name, t0, t1, calls in self.phases():
            if t0 > at:
                out.append(("other", at, t0, 0))
            out.append((name, t0, t1, calls))
            at = t1
        if end > at:
            out.append(("other", at, end, 0))
        out.append(("wake", end, closed, 0))
        return out

    def device_window(self) -> Optional[Tuple[int, int]]:
        """[to_planar start, end of the last readback, the crc words'
        included]: where this tick's device work has to lie.  None for a
        tick that recorded neither."""
        lo = hi = None
        for name, t0, t1, calls in self.phases():
            if name == "to_planar" and lo is None:
                lo = t0
            elif name == "readback" or (name == "crc" and calls):
                hi = t1
        return None if lo is None or hi is None else (lo, hi)

    def crc_on_device(self) -> bool:
        """Did every ``crc`` phase of this tick make a device call (and
        was there one)?"""
        crc = [calls for name, _t0, _t1, calls in self.phases()
               if name == "crc"]
        return bool(crc) and all(crc)

    def dump(self) -> List[Dict]:
        """Root + children as ``Span.dump()`` dicts (one trace)."""
        trace_id = f"{self.daemon}:tick{self.seq}"
        root_id = f"{trace_id}:s0"

        def span(span_id, parent_id, name, t0, t1, meta):
            meta.update(start_ns=t0, dur_ns=t1 - t0)
            return {"trace_id": trace_id, "span_id": span_id,
                    "parent_id": parent_id, "name": name,
                    "daemon": self.daemon, "start": t0 / 1e9,
                    "dur": (t1 - t0) / 1e9, "meta": meta}

        out = [span(root_id, None, self.name, self.t[0], self.t[3], {
            "seq": self.seq, "ops": len(self.op_ids),
            "op_ids": list(self.op_ids), "stripes": self.stripes,
            "bucket": self.bucket, "payload_bytes": self.payload_bytes,
            "thread": self.thread, "device_calls": self.calls})]
        for i, (name, t0, t1, calls) in enumerate(self.segments(), 1):
            meta: Dict = {"device_calls": calls} if calls else {}
            if name == "crc":
                meta["path"] = "device" if calls else "host"
            if name in ("crc", "to_planar") and self.layout:
                meta["layout"] = self.layout
            out.append(span(f"{trace_id}:s{i}", root_id, name, t0, t1,
                            meta))
        return out


class TickLog:
    """The ring of closed ticks, the occupancy clock of the encode tick
    threads, and the feed into the tick counters.  The process has one
    (:data:`TICKS`, as it has one ``KERNELS``); tests make their own with
    an injected clock."""

    def __init__(self, keep: int = RING_TICKS, clock=time.time_ns,
                 counters: PerfCounters = KERNELS):
        self.clock = clock
        self.counters = counters
        self.ring: "deque[Tick]" = deque(maxlen=keep)
        self._seq = itertools.count(1)
        self._lock = threading.Lock()
        self._active = 0        # encode tick threads running now
        self._since = 0         # ... since this stamp
        declare_counters(counters)

    def open(self, name: str, daemon: str, op_ids: Sequence = ()) -> Tick:
        return Tick(self, name, daemon, next(self._seq), op_ids)

    def _thread_edge(self, delta: int) -> int:
        """An encode tick's thread starts (+1) or returns (-1): book the
        time since the last edge by how many were running.  The stamp is
        taken under the lock, so edges are ordered as they are booked."""
        with self._lock:
            now = self.clock()
            held = max(0, now - self._since)
            if self._active >= 1:
                self.counters.inc("ec_tick_any_active_ns", held)
            if self._active >= 2:
                self.counters.inc("ec_tick_multi_active_ns", held)
            self._active += delta
            self._since = now
            return now

    def _closed(self, tick: Tick) -> None:
        self.ring.append(tick)
        if tick.name != ENCODE_TICK:
            return
        inc = self.counters.inc
        opened, start, end, closed = tick.t[:4]
        spent = tick.phase_ns()
        inc("ec_tick_wall_ns", end - start)
        inc("ec_tick_cpu_ns", tick.cpu_ns)
        inc("ec_tick_handoff_ns", (start - opened) + (closed - end))
        for phase, (counter, _desc) in _PHASE_COUNTERS.items():
            inc(counter, spent.get(phase, 0))
        inc("ec_tick_device_calls", tick.calls)
        if tick.crc_on_device():
            inc("ec_tick_crc_device_ticks")

    def dump(self, daemon: str, n: int = 20) -> Dict[str, List[Dict]]:
        """``daemon``'s newest ``n`` ticks, oldest first, each one trace
        of span dumps (the shape of ``Tracer.dump_recent``)."""
        if n <= 0:
            return {}
        newest = list(itertools.islice(
            (t for t in reversed(self.ring) if t.daemon == daemon), n))
        out: Dict[str, List[Dict]] = {}
        for tick in reversed(newest):
            spans = tick.dump()
            out[spans[0]["trace_id"]] = spans
        return out


TICKS = TickLog()


def phase(name: str):
    """Context manager timing one phase of the tick open on this thread;
    :data:`NULL_PHASE` when none is."""
    tick = getattr(_CURRENT, "tick", None)
    return NULL_PHASE if tick is None else tick.phase(name)


def device_calls(n: int = 1) -> None:
    """``n`` jitted program launches or explicit host<->device transfers
    were just issued, by the tick open on this thread if any."""
    tick = getattr(_CURRENT, "tick", None)
    if tick is not None:
        tick.calls += n


def annotate(stripes: int, bucket: int, payload_bytes: int,
             layout: str = "") -> None:
    """What the open tick (if any) encodes: stripes before padding, the
    bucket after it, the client bytes, and the serialization its planes
    are in (``bitpack`` / ``packet``: the ``to_planar`` and ``crc`` spans
    of a dump say it)."""
    tick = getattr(_CURRENT, "tick", None)
    if tick is not None:
        tick.stripes = stripes
        tick.bucket = bucket
        tick.payload_bytes = payload_bytes
        tick.layout = layout
