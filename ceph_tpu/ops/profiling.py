"""Honest device-kernel timing: the on-device scan + slope harness.

This is the BENCH_NOTES.md round-5 methodology as a library: a single
timed call carries the fixed cost of its dispatch and readback, and a
harness that does not wait for the device measures the enqueue rate.
The figure used here is the SLOPE between two ``lax.scan``
programs chaining L1 and L2 iterations of the workload inside one
dispatch (each iteration feeding a cheap xor of its output back into
the next so nothing can be hoisted), completion forced by a one-element
host readback — the dispatch/readback floor cancels exactly.

``device_loop_slope`` is that harness for ad-hoc profiling, and a
``tag`` records the honest per-step seconds into
the process-wide KERNELS registry (``t_<tag>`` time counters) so
``perf dump`` carries real device timings next to the invocation/byte
counters.
"""

from __future__ import annotations

import statistics
import time
from typing import Optional

from ceph_tpu.utils.perf import KERNELS


def record_planar_matmul(payload_bytes: int, groups: int = 1) -> None:
    """Device-kernel telemetry for the bit-planar GF(2) matmul path.

    Counts invocations and payload bytes separately from the byte-path
    ``ec_matmul`` counters so a perf dump shows how much traffic rides the
    new layout, and records the K-stacking factor (``planar_stack_groups``
    / ``planar_matmul_calls`` > 1 says the Pallas kernel ran, not the XLA
    route).
    """
    KERNELS.inc("planar_matmul_calls")
    KERNELS.inc("planar_matmul_bytes", int(payload_bytes))
    KERNELS.inc("planar_stack_groups", int(groups))


def record_planar_convert(direction: str, payload_bytes: int) -> None:
    """Layout-conversion telemetry: ``direction`` is ``to_planar`` or
    ``to_bytes``.  The layout contract promises at most one conversion
    each way per client op — a perf dump where convert bytes rival
    planar_matmul bytes means the contract is being violated somewhere."""
    KERNELS.inc(f"planar_convert_{direction}_calls")
    KERNELS.inc(f"planar_convert_{direction}_bytes", int(payload_bytes))
    KERNELS.inc("planar_convert_bytes", int(payload_bytes))


def record_planar_at_rest(event: str, payload_bytes: int) -> None:
    """Planar AT-REST conversion telemetry (round 19).

    With ``osd_ec_planar_at_rest=1`` shards are stored as packed
    bit-planes, so layout conversions may happen ONLY at the sanctioned
    seams.  ``event`` names which seam booked the conversion:

    - ``ingest``:  client bytes -> planes at the coalesced encode (the
      one unavoidable conversion per write tick);
    - ``egress``:  planes -> logical client bytes at the read assemble
      (the one unavoidable conversion per read);
    - ``relayout``: a mixed-generation transition (byte-at-rest object
      met a planar write or vice versa after the config gate flipped) —
      legal but expected to be rare;
    - ``unseamed``: a byte view materialized OUTSIDE the seams (e.g. a
      raw ``store.read`` of a planar object).  The steady-state
      contract pins this counter to ZERO; tests assert it stays there
      across write/read/RMW/recovery/deep-scrub.
    """
    KERNELS.inc(f"ec_planar_{event}_conversions")
    KERNELS.inc(f"ec_planar_{event}_bytes", int(payload_bytes))


def device_loop_slope(step, feedback, data, repeats: int = 3,
                      L1: int = 300, L2: int = 1200,
                      tag: Optional[str] = None):
    """Seconds-per-step of ``step`` with the repeat loop ON DEVICE.

    Builds two jitted scan programs chaining L1 and L2 iterations —
    each iteration feeds its output back into the next via ``feedback``
    (a cheap xor, <2% of the workload) — and forces completion with a
    one-element readback.  The per-iteration time is the slope
    ``(t_L2 - t_L1) / (L2 - L1)``.  Returns (median, best, worst)
    across conservative pairings of the repeat samples; ``tag`` also
    tincs the median into KERNELS as ``t_<tag>``.

    Lint contract: graftlint's jax-hygiene rule treats the ``step`` and
    ``feedback`` callables passed to THIS FUNCTION (matched by the
    names ``device_loop_slope`` / ``_bench_device_loop``) as traced
    code and statically rejects host syncs inside them — the measured
    region's timing trust model (BENCH_NOTES.md).  Renaming this
    function requires updating analysis/jax_hygiene.py or coverage is
    silently lost.
    """
    import jax
    import numpy as np

    tinyfn = jax.jit(lambda d: jax.tree_util.tree_leaves(d)[0].ravel()[:1])

    def make(L):
        @jax.jit
        def loop(d0):
            def body(d, _):
                out = step(d)
                return feedback(d, out), ()

            d, _ = jax.lax.scan(body, d0, None, length=L)
            return d

        return loop

    loops = {L: make(L) for L in (L1, L2)}

    def run(L):
        np.asarray(tinyfn(loops[L](data)))

    ts = {}
    for L in (L1, L2):
        run(L)  # compile + warm
        samples = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            run(L)
            samples.append(time.perf_counter() - t0)
        ts[L] = samples
    dL = L2 - L1
    # clamp against timing noise driving a slope to <= 0 (a negative or
    # infinite rate must never become the number of record)
    med = max((statistics.median(ts[L2]) - statistics.median(ts[L1])) / dL,
              1e-12)
    best = max((min(ts[L2]) - max(ts[L1])) / dL, 1e-12)
    worst = max((max(ts[L2]) - min(ts[L1])) / dL, 1e-12)
    if tag is not None:
        KERNELS.tinc(f"t_{tag}", med)
    return med, best, worst
