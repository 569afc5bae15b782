"""graft-trace: cross-daemon span tracing + event-loop profiling.

The observability instrument for ROADMAP items 1-2 (the ~1000x
cluster/device gap): one client op becomes one cross-daemon tree of
timed spans, its event timeline rolls up into a per-stage wall-time
breakdown, and an asyncio profiler watches the loop the whole daemon
runs on.  Everything is a provable no-op at default config — the same
contract the chaos injectors honor — so the load-sensitive bench trust
model (BENCH_NOTES) is untouched.

- ``span``        Tracer/Span/NULL_SPAN, header propagation, tree assembly.
- ``attribution`` event timeline -> per-stage latency attribution.
- ``loopmon``     sampled event-loop lag + task queue/wall profiling.
- ``perfetto``    chrome://tracing / Perfetto JSON export.
- ``flight``      graft-blackbox per-daemon flight-recorder rings.
- ``postmortem``  triggered POSTMORTEM_* bundles + breach attribution.
- ``tick``        one span per coalesced device tick with its host
                  phases, on the device trace's clock (always on).
- ``gapjoin``     device idle gaps cut by host cause: ticks laid over a
                  device trace's events.
- ``loopacct``    the loop's account: busy and parked, on and off the
                  CPU, in send, recv, store and pickle, and who ran
                  what no stamp covers (always on).
"""

from ceph_tpu.trace.span import (  # noqa: F401
    CURRENT_SPAN,
    NULL_SPAN,
    Span,
    Tracer,
    assemble_tree,
)
from ceph_tpu.trace.attribution import (  # noqa: F401
    aggregate,
    aggregate_tracker,
    attribute_events,
    spans_from_events,
    stage_for,
)
from ceph_tpu.trace.loopmon import LoopProfiler  # noqa: F401
from ceph_tpu.trace.flight import (  # noqa: F401
    NULL_FLIGHT,
    FlightRecorder,
    merged_timeline,
)
