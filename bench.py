#!/usr/bin/env python
"""Benchmark harness mirroring the reference's ceph_erasure_code_benchmark.

The reference tool (src/test/erasure-code/ceph_erasure_code_benchmark.cc)
times plugin encode/decode over an object of --size for --iterations and
prints seconds + KiB.  This harness runs the same configs (BASELINE.json)
against the TPU batch engine and prints one JSON line per metric; the LAST
line is always the headline (north-star) metric:

    {"metric": ..., "value": GB/s, "unit": "GB/s", "vs_baseline": x}

Measurement methodology (round 5 — see BENCH_NOTES.md for the full
investigation): the repeat loop runs ON DEVICE.  `lax.scan` chains L
iterations of the workload inside one dispatch, each iteration feeding a
cheap xor of its output back into the next so nothing can be hoisted,
and the figure is the SLOPE between an L1-scan and an L2-scan (which
cancels dispatch/readback floors exactly).  Completion is forced by
reading one element back to the host.

Why: the harnesses of rounds 1-4 (blocking r1-r2, pipelined r3-r4) did
not wait for the device and sampled the host's enqueue rate.  That
fiction produced 539 GB/s (r3) and 381 GB/s (r4) on identical code — the
entire r3->r4 "regression" was noise — where the true device throughput
was ~50 GB/s.  The slope also cancels the fixed cost of a dispatch and a
readback, which a single timed call cannot.

The measured regions are lint-guarded: `scripts/graftlint.py` (rule
family jax-hygiene, a tier-1 gate) statically rejects host syncs —
np.asarray/float()/.block_until_ready()/time.* — and tracer branching
inside every jitted function, scan body, and the step/feedback
callables handed to `_bench_device_loop`, so the device loop cannot
silently degrade into per-iteration host round-trips (see
BENCH_NOTES.md "graftlint guards the device-loop timing trust model").

Baselines (round 4): vs_baseline denominators are MEASURED on this host —
scripts/cpu_baseline/ implements the reference's SIMD EC kernels
(gf-complete split-table + isa-l GFNI paths, best-of), its 3-way hardware
crc32c, and times the reference's own CRUSH C core linked out-of-tree;
run.sh writes BASELINE_MEASURED.json, loaded here per config.  The old
BASELINE_GBPS = 5.0 literature constant remains only as a fallback when
that file is absent.
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

BASELINE_GBPS = 5.0  # fallback only; see BASELINE_MEASURED.json

_MEASURED_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "BASELINE_MEASURED.json")


def _measured_baselines():
    """config-name -> measured denominator (GB/s, or mappings/s for crush)."""
    out = {}
    try:
        with open(_MEASURED_PATH) as f:
            doc = json.load(f)
        for row in doc.get("results", []):
            val = row.get("gbps") or row.get("mappings_per_s") \
                or row.get("mbps")
            if val:
                out[row["config"]] = float(val)
    except (OSError, ValueError, KeyError, TypeError, AttributeError):
        return {}
    return out


MEASURED = _measured_baselines()


def _vs(value, config_key, fallback=BASELINE_GBPS):
    """(vs_baseline, baseline_row_fields): ratio against the measured
    denominator, with explicit provenance so a fallback ratio can never
    masquerade as a measured one.  fallback=None -> no ratio at all when
    unmeasured (used for non-GB/s metrics where 5.0 is meaningless)."""
    base = MEASURED.get(config_key)
    if base:
        return round(value / base, 3), {"baseline": base,
                                        "baseline_src": "measured"}
    if fallback is None:
        return None, {"baseline": None, "baseline_src": "unmeasured"}
    return round(value / fallback, 3), {"baseline": fallback,
                                        "baseline_src": "fallback_constant"}


def _metric_row(metric, value, unit, ratio, prov, mode,
                lo=None, hi=None, **extra):
    """One result row, enforcing the timing trust model.

    ``pipelined_untrusted`` timings sample the host's enqueue rate, not
    device throughput (BENCH_NOTES.md round 5) — those rows are emitted
    with ``"untrusted": true`` and a NULL ``vs_baseline`` so a dishonest
    number can never masquerade as a headline result.  Only ``device_loop``
    (and ordinary host-timed modes) rows may carry a baseline ratio.
    """
    row = {"metric": metric, "value": value, "unit": unit,
           "vs_baseline": ratio, **prov, "mode": mode}
    if mode == "pipelined_untrusted":
        row["vs_baseline"] = None
        row["untrusted"] = True
    if lo is not None:
        row["min"] = lo
    if hi is not None:
        row["max"] = hi
    row.update(extra)
    return row


def _bench(fn, args, iters, repeats=5, warmup=2):
    """Median seconds-per-call over `repeats` pipelined timing windows.

    Returns (median, min, max) of the per-call time.  Each window enqueues
    `iters` async dispatches and blocks once, so per-call dispatch latency
    is amortized and the device queue stays full (sustained throughput,
    which is what the reference tool's bytes/seconds accounting reports for
    a hot CPU loop, ceph_erasure_code_benchmark.cc:180-187).
    """
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / iters)
    return statistics.median(times), min(times), max(times)


def _bench_device_loop(step, feedback, data, repeats=3, L1=300, L2=1200,
                       tag=None):
    """Seconds-per-step with the repeat loop ON DEVICE, floor-cancelled.

    The scan + slope harness now lives in ceph_tpu.ops.profiling
    (device_loop_slope) so library code and ad-hoc profiling share one
    honest-timing implementation; ``tag`` records the median into the
    process-wide device-kernel counters (KERNELS ``t_<tag>``)."""
    from ceph_tpu.ops.profiling import device_loop_slope

    return device_loop_slope(step, feedback, data, repeats=repeats,
                             L1=L1, L2=L2, tag=tag)


def bench_ec(profile, batch, chunk, workload="encode", erasures=(0,), iters=20,
             repeats=3):
    """Returns (median, min, max) GB/s of input data processed (matching the
    reference tool's accounting: object bytes per iteration / seconds,
    ceph_erasure_code_benchmark.cc:187).

    Prefers the on-device scan loop (`_bench_device_loop`); codecs whose
    batch path cannot trace (host-side data conversions) fall back to the
    pipelined dispatch harness (whose numbers are enqueue-rate, not device
    throughput — flagged by the caller via the returned mode).
    """
    import jax.numpy as jnp

    from ceph_tpu.ec import factory

    codec = factory(dict(profile))
    k = codec.get_data_chunk_count()
    n = codec.get_chunk_count()
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.integers(0, 256, (batch, k, chunk), dtype=np.uint8))
    nbytes = batch * k * chunk

    def feedback(d, out):
        # chain iterations: xor one output row (broadcast) into the input
        return d ^ out[:, :1, : d.shape[2]]

    def planar_feedback(planes, out):
        # same chaining in the planar domain, but through ONE plane row
        # (in-place dynamic-update on the scan carry): the matmul reads
        # every row, so depending on row 0 already forbids hoisting, and
        # the feedback traffic stays negligible next to the now-fast
        # planar kernel (a full-array xor would be ~40% of its HBM)
        return planes.at[:1, :].set(
            planes[:1, :] ^ out[:1, : planes.shape[1]])

    # Round-6 layout contract: stripe batches live on device in bit-planar
    # form between host boundaries, so the steady-state loop measures the
    # planar encode/decode (pure matmul, no per-call 8x expansion/pack).
    # The one-time byte->planar conversion happens OUTSIDE the timed loop
    # and is recorded in the KERNELS planar_convert counters.
    planar = (hasattr(codec, "encode_planar")
              and getattr(codec, "planar_supported",
                          lambda s: False)(chunk))

    mode = "device_loop"
    path = "planar" if planar else "byte"
    if workload == "encode":
        med = None
        if planar:
            try:
                pb = codec.to_planar(data)

                def step(planes):
                    return codec.encode_planar(
                        pb.with_planes(planes, k)).planes

                med, lo, hi = _bench_device_loop(
                    step, planar_feedback, pb.planes, repeats,
                    tag="ec_encode")
            except Exception as e:
                # a planar-path failure must be visible in the run log:
                # the byte fallback still reports device_loop and would
                # otherwise hide exactly the regression this round's
                # acceptance criterion depends on
                print(json.dumps({"planar_path_error": repr(e),
                                  "workload": workload}), file=sys.stderr)
                path = "byte"
                med = None
        if med is None:
            try:
                med, lo, hi = _bench_device_loop(
                    codec.encode_batch, feedback, data, repeats,
                    tag="ec_encode")
            except Exception:
                mode = "pipelined_untrusted"
                med, lo, hi = _bench(codec.encode_batch, (data,), iters,
                                     repeats)
    else:
        parity = codec.encode_batch(data)
        full = jnp.concatenate([data, jnp.asarray(parity)], axis=1)
        # pre-warm the codec's decode-matrix caches EAGERLY: the cached
        # bitmats are device constants, and populating them inside the
        # scan trace would leak tracers into the cache
        codec.decode_batch(tuple(erasures), full)
        med = None
        if planar and hasattr(codec, "decode_planar"):
            try:
                pbf = codec.to_planar(full)
                codec.decode_planar(tuple(erasures), pbf)  # warm plan cache

                def step(planes):
                    return codec.decode_planar(
                        tuple(erasures), pbf.with_planes(planes, n)).planes

                med, lo, hi = _bench_device_loop(
                    step, planar_feedback, pbf.planes, repeats,
                    tag="ec_decode")
            except Exception as e:
                print(json.dumps({"planar_path_error": repr(e),
                                  "workload": workload}), file=sys.stderr)
                path = "byte"
                med = None
        else:
            path = "byte"
        if med is None:
            try:
                med, lo, hi = _bench_device_loop(
                    lambda c: codec.decode_batch(tuple(erasures), c),
                    feedback, full, repeats, tag="ec_decode")
            except Exception:
                mode = "pipelined_untrusted"
                med, lo, hi = _bench(
                    codec.decode_batch, (tuple(erasures), full), iters,
                    repeats)
    return (nbytes / med / 1e9, nbytes / hi / 1e9, nbytes / lo / 1e9,
            mode, path)


def bench_crush(n_osds=10_000, n_pgs=1_000_000, repeats=3):
    """Whole-map PG->OSD placement throughput (mappings/s), measured with
    the on-device scan loop over the mapper's compiled rule VM."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.crush.mapper import TensorMapper
    from ceph_tpu.crush.types import build_three_level

    n_racks = max(1, n_osds // 256)
    cmap, rule = build_three_level(
        n_racks=n_racks, hosts_per_rack=16, osds_per_host=16, numrep=3)
    # 16 Ki lanes per dispatch measured fastest per-mapping on v5e (see
    # BENCH_NOTES.md); the reported rate extrapolates to the full 1M PGs
    mapper = TensorMapper(cmap, chunk=1 << 14)
    n = min(n_pgs, mapper.chunk)
    xs = jnp.arange(n, dtype=jnp.uint32)
    fn, tensors = mapper.compiled_rule(rule, 3)
    # closures hold HOST numpy only: a jit must not close over a
    # device array (JAX bakes it into the program as a constant — see
    # the mapper._TENSOR_ATTRS note); numpy lifts as a constant too, but
    # without pinning a device buffer
    weights_np = np.full(cmap.max_devices, 0x10000, dtype=np.uint32)
    tensors_np = jax.tree_util.tree_map(np.asarray, tensors)

    def step(x):
        res, lens = fn(x, weights_np, tensors_np)
        return res

    def feedback(x, res):
        # chain iterations through the first mapped OSD of each pg
        return x ^ res[:, 0].astype(jnp.uint32)

    # L tuned down: one iteration maps `n` pgs (a lot of work already)
    med, lo, hi = _bench_device_loop(step, feedback, xs, repeats,
                                     L1=10, L2=40, tag="crush_map")
    return n / med, n / hi, n / lo


def bench_crc32c(batch=4096, length=4096, repeats=3):
    """Batched device crc32c GB/s (reference src/common/crc32c.cc asm path)."""
    import jax.numpy as jnp

    from ceph_tpu.ops.crc32c import crc32c_batch

    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.integers(0, 256, (batch, length), dtype=np.uint8))
    crc32c_batch(data)  # pre-warm the cached message bitmat eagerly

    def feedback(d, crcs):
        return d ^ (crcs & 0xFF).astype(jnp.uint8)[:, None]

    med, lo, hi = _bench_device_loop(crc32c_batch, feedback, data, repeats,
                                     tag="crc32c_batch")
    nbytes = batch * length
    return nbytes / med / 1e9, nbytes / hi / 1e9, nbytes / lo / 1e9


EC_CONFIGS = [
    # (name, baseline_key, profile, kwargs) — BASELINE.md metric table
    # configs; baseline_key indexes BASELINE_MEASURED.json.
    ("ec_encode_jerasure_rsvan_k4m2_1M", "jer_rsvan_k4m2_encode",
     {"plugin": "jerasure", "technique": "reed_sol_van", "k": "4", "m": "2"},
     dict(batch=16, chunk=262144, workload="encode")),
    ("ec_decode_jerasure_rsvan_k4m2_1M_e2", "jer_rsvan_k4m2_decode_e05",
     {"plugin": "jerasure", "technique": "reed_sol_van", "k": "4", "m": "2"},
     dict(batch=16, chunk=262144, workload="decode", erasures=(0, 5))),
    ("ec_encode_lrc_k4m2l3", "lrc_k4m2l3_encode",
     {"plugin": "lrc", "k": "4", "m": "2", "l": "3"},
     dict(batch=1024, chunk=4096, workload="encode")),
    ("ec_decode_lrc_k4m2l3_e1", "lrc_k4m2l3_decode_e1",
     {"plugin": "lrc", "k": "4", "m": "2", "l": "3"},
     dict(batch=1024, chunk=4096, workload="decode", erasures=(1,))),
    ("ec_decode_shec_643_e3", "shec_643_decode_e037",
     {"plugin": "shec", "k": "6", "m": "4", "c": "3"},
     dict(batch=1024, chunk=4096, workload="decode", erasures=(0, 3, 7))),
    ("ec_decode_isa_k8m4_4k_e1", "isa_k8m4_decode_e2",
     {"plugin": "isa", "k": "8", "m": "4"},
     dict(batch=4096, chunk=512, workload="decode", erasures=(2,))),
]


def bench_cluster_io(secs_write=4.0, secs_read=3.0, perf_dump=False,
                     attribute=False, concurrency=16, legacy=False):
    """End-to-end cluster I/O (the reference `rados bench` run,
    src/tools/rados/rados.cc:103): a live 3-OSD vstart cluster with an
    EC k2m1 pool, measured through the full client->primary->EC
    encode(TPU)->replicate pipeline.  Returns a list of metric rows.

    ``attribute``: roll completed write traces into a per-stage
    wall-time breakdown (graft-trace, `dump_op_attribution`) — the
    instrument for the cluster/device 1000x gap (ROADMAP items 1-2).
    The mode widens the op-history window so the whole timing window is
    attributable; the DEFAULT bench config leaves tracing off and is
    bit-identical to previous rounds (BENCH_NOTES zero-overhead
    contract).

    Round 10: the stage table knows the overload regime — client
    congestion-window waits book as ``throttle_wait``, dequeue-shed ops
    as ``shed``, EC straggler hedges as ``hedge`` — so the wall_coverage
    >= 0.90 trust floor holds with admission backpressure enabled, and
    the attribution row carries the shed/pushback counters for the run
    (all zero at default budgets)."""
    import asyncio

    from ceph_tpu.cluster.vstart import _fast_config, start_cluster
    from ceph_tpu.tools.rados import bench as rados_bench

    async def scenario():
        config = _fast_config()
        if legacy:
            # the seed-equivalent per-op path (round-10 dispatch/encode,
            # the bisection anchor): what the measured cluster baseline
            # in BASELINE_MEASURED.json is captured against
            config.osd_op_shards = 0
            config.osd_batch_tick_ops = 0
            config.objecter_batch_tick_ops = 0
        if attribute:
            # every write of the timing window must stay in the history
            # ring to be attributable (4s at cluster_io rates is well
            # under 4096 ops)
            config.osd_op_history_size = 4096
        cluster = await start_cluster(3, config=config)
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "bench_ec", "erasure", pg_num=8,
                ec_profile={"plugin": "jerasure",
                            "technique": "reed_sol_van",
                            "k": "2", "m": "1"})
            io = client.ioctx(pool)
            # warm the codec compile caches before the timing window so
            # the window measures steady-state I/O, not XLA compiles
            for i in range(3):
                await io.write_full(f"warm_{i}", b"\xa5" * (1 << 20))
                await io.read(f"warm_{i}")
            if attribute:
                from ceph_tpu.trace.attribution import flush_op_history

                await flush_op_history(cluster, 4096)
                client.objecter.drain_op_tails()  # discard warm-up
            w = await rados_bench(io, secs_write, "write",
                                  concurrency=concurrency,
                                  block_size=1 << 20,
                                  cleanup=False)
            attribution = None
            if attribute:
                # collect BEFORE the read bench so the breakdown is the
                # write workload's; match= isolates write_full ops.
                # Every OSD's report is merged: primaries spread across
                # the acting sets, so each tracker holds a disjoint
                # slice of the bench ops
                from ceph_tpu.trace.attribution import (aggregate,
                                                        merge_reports)

                wall_s = w["lat_avg_ms"] / 1e3
                reports = []
                for oid in cluster.osds:
                    reports.append(await cluster.daemon_command(
                        f"osd.{oid}",
                        {"prefix": "dump_op_attribution",
                         "args": {"match": "write_full"}}))
                # reply-leg tails (round 11): per-op reply flight +
                # client wakeup recorded objecter-side.  They EXTEND the
                # same ops the OSD reports already count, so the tail
                # report contributes seconds but not ops to the
                # per-op-average coverage math
                tails = aggregate(client.objecter.drain_op_tails())
                tails["ops"] = 0
                reports.append(tails)
                attribution = merge_reports(reports,
                                            measured_wall_s=wall_s)
                # backpressure context for the artifact: nonzero only
                # when admission budgets are configured for the run
                attribution["overload"] = {
                    name: sum(o.perf.get(name)
                              for o in cluster.osds.values())
                    for name in ("osd_throttle_rejects",
                                 "osd_ops_shed_expired",
                                 "osd_qos_preempted",
                                 "osd_ec_hedged_reads")}
            r = await rados_bench(io, secs_read, "rand",
                                  concurrency=concurrency,
                                  block_size=1 << 20)
            dumps = {}
            if perf_dump:
                # each daemon's perf dump rides the bench artifact so
                # BENCH_r*.json trajectories carry counter context
                # (kernel invocations, op latencies, histograms)
                for oid, osd in cluster.osds.items():
                    dumps[f"osd.{oid}"] = osd.perfcoll.dump()
                dumps["mon"] = cluster.mon.perf.dump()
            return w, r, dumps, attribution
        finally:
            await cluster.stop()

    w, r, dumps, attribution = asyncio.run(scenario())
    rows = []
    for tag, rep in (("write", w), ("rand_read", r)):
        metric = f"cluster_io_{tag}_ec_k2m1_1MiB_t{concurrency}"
        # measured cluster baseline (round 11): the denominator is the
        # seed-equivalent per-op path captured in BASELINE_MEASURED.json
        # on this host (--cluster-legacy run); no fallback constant —
        # an unmeasured row stays explicitly unmeasured
        ratio, prov = _vs(rep["mbps"], metric, fallback=None)
        row = {
            "metric": metric,
            "value": round(rep["mbps"], 2), "unit": "MB/s",
            "vs_baseline": ratio, **prov, "mode": "cluster_vstart",
            "lat_p50_ms": round(rep["lat_p50_ms"], 2),
            "lat_p95_ms": round(rep["lat_p95_ms"], 2),
            "iops": round(rep["iops"], 1)}
        if legacy:
            # a baseline-capture run must never pose as the batched
            # data plane's number (and never ratio against itself)
            row["legacy_path"] = True
            row["vs_baseline"] = None
        rows.append(row)
    if attribution is not None:
        rows.append({
            "metric": f"cluster_io_write_ec_k2m1_1MiB_"
                      f"t{concurrency}_attribution",
            "unit": "json", "mode": "cluster_vstart",
            "vs_baseline": None, "baseline": None,
            "baseline_src": "unmeasured",
            "attribution": attribution})
    if perf_dump:
        rows.append({"metric": "cluster_perf_dump", "unit": "json",
                     "dumps": dumps})
    return rows


def _device_stamp():
    """What every row is stamped with: the device JAX ran it on."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "device_count": len(devs)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--all", action="store_true",
                    help="compat alias: the full metric set is the default now")
    ap.add_argument("--headline-only", action="store_true",
                    help="skip the full metric set, print only the headline")
    ap.add_argument("--iterations", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--perf-dump", action="store_true",
                    help="append daemon perf dumps + device-kernel "
                         "counters to the artifact")
    ap.add_argument("--attribute", action="store_true",
                    help="per-stage wall-time attribution of the "
                         "cluster_io write bench (graft-trace)")
    ap.add_argument("--cluster-legacy", action="store_true",
                    help="run cluster_io on the per-op legacy path "
                         "(osd_op_shards=0, osd_batch_tick_ops=0): the "
                         "measured-baseline capture mode")
    ap.add_argument("--cluster-concurrency", type=int, default=16,
                    help="cluster_io client concurrency (t1 checks "
                         "single-op latency; t16 is the headline)")
    args = ap.parse_args()

    from ceph_tpu.utils import compile_cache

    compile_cache.enable()
    device = _device_stamp()
    results = []
    failed = []

    def rows_of(metric, make_rows):
        """Run one metric; a failure is reported, remembered, and turns
        the run's exit code non-zero after the good rows are printed."""
        try:
            results.extend(make_rows())
        except Exception as e:
            failed.append(metric)
            print(json.dumps({"metric": metric, "error": repr(e),
                              **device}), file=sys.stderr)

    def ec_row(name, base_key, profile, kw):
        med, lo, hi, mode, path = bench_ec(
            profile, iters=args.iterations, repeats=args.repeats, **kw)
        ratio, prov = _vs(med, base_key)
        return [_metric_row(name, round(med, 3), "GB/s", ratio, prov, mode,
                            round(lo, 3), round(hi, 3), layout_path=path)]

    def crc_row():
        med, lo, hi = bench_crc32c(repeats=args.repeats)
        ratio, prov = _vs(med, "crc32c_4096x4KiB", fallback=None)
        return [_metric_row(
            "crc32c_batch_4096x4KiB", round(med, 3), "GB/s", ratio,
            prov, "device_loop", round(lo, 3), round(hi, 3))]

    def crush_row():
        pg_per_s, pg_lo, pg_hi = bench_crush(repeats=args.repeats)
        ratio, prov = _vs(pg_per_s, "crush_10kosd_1Mpg", fallback=None)
        return [_metric_row(
            "crush_map_10kosd_1Mpg", round(pg_per_s), "mappings/s",
            ratio, prov, "device_loop", round(pg_lo), round(pg_hi))]

    if not args.headline_only:
        for name, base_key, profile, kw in EC_CONFIGS:
            rows_of(name, lambda: ec_row(name, base_key, profile, kw))
        rows_of("crc32c_batch_4096x4KiB", crc_row)
        rows_of("crush_map_10kosd_1Mpg", crush_row)
        rows_of("cluster_io", lambda: bench_cluster_io(
            perf_dump=args.perf_dump, attribute=args.attribute,
            concurrency=args.cluster_concurrency,
            legacy=args.cluster_legacy))
        if args.perf_dump:
            # process-wide kernel counters accumulated across every
            # bench above (calls, bytes, padding waste, honest t_* from
            # the device-loop harness)
            from ceph_tpu.utils.perf import KERNELS

            results.append({"metric": "device_kernel_counters",
                            "unit": "json", "counters": KERNELS.dump()})

    # headline metric (always the LAST line): north-star encode config
    rows_of("ec_encode_isa_k8m4_4KiB_stripe_batch4096", lambda: ec_row(
        "ec_encode_isa_k8m4_4KiB_stripe_batch4096", "isa_k8m4_encode",
        {"plugin": "isa", "k": "8", "m": "4"},
        dict(batch=4096, chunk=512, workload="encode")))
    for r in results:
        print(json.dumps({**r, **device}))
    if failed:
        sys.exit(f"bench: {len(failed)} metric(s) failed: {failed}")


if __name__ == "__main__":
    main()
