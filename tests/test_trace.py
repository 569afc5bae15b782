"""graft-trace gates: span tracer semantics, stage attribution math,
the asyncio loop profiler, Perfetto export, the zero-overhead-when-
disabled contract, and the cross-daemon e2e smoke (one traced op
through vstart with the span tree + attribution asserted).
"""

import asyncio
import json
import os
import subprocess
import sys
import time

import pytest

from ceph_tpu.trace import (
    LoopProfiler,
    NULL_SPAN,
    Tracer,
    aggregate,
    assemble_tree,
    attribute_events,
    spans_from_events,
    stage_for,
)
from ceph_tpu.trace.perfetto import (
    chrome_trace_from_dumps,
    chrome_trace_from_spans,
)
from ceph_tpu.utils.perf import PerfCounters

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(coro):
    return asyncio.run(coro)


# ------------------------------------------------------------ span tracer


def test_disabled_tracer_is_provably_null():
    """The zero-overhead contract: disabled tracing allocates nothing,
    retains nothing, and never grows a message header."""
    t = Tracer("osd.0", enabled=False)
    s1 = t.start("a")
    s2 = t.start("b", trace_id="x", parent_id="y")
    assert s1 is NULL_SPAN and s2 is NULL_SPAN  # the shared singleton
    with s1:
        assert t.context() is None  # no header field, ever
        s1.annotate(k=1)
    s1.finish()
    assert t.dump_recent() == {}
    assert not s1  # falsy: `if span:` guards stay cheap


def test_span_tree_parenting_and_assembly():
    t = Tracer("client.x", enabled=True)
    u = Tracer("osd.1", enabled=True)
    with t.start("op_submit", trace_id="T") as root:
        ctx = t.context()
        assert ctx == {"id": "T", "span": root.span_id}
        # another daemon parents under the propagated span id
        with u.start("osd_op", trace_id=ctx["id"],
                     parent_id=ctx["span"]) as osd_span:
            with u.start("ec_sub_write"):  # nests via CURRENT_SPAN
                pass
    spans = t.dump_trace("T") + u.dump_trace("T")
    assert len(spans) == 3
    roots = assemble_tree(spans)
    assert len(roots) == 1 and roots[0]["name"] == "op_submit"
    assert roots[0]["children"][0]["name"] == "osd_op"
    assert roots[0]["children"][0]["children"][0]["name"] == "ec_sub_write"
    assert roots[0]["children"][0]["span_id"] == osd_span.span_id
    for s in spans:
        assert s["dur"] is not None and s["dur"] >= 0


def test_tracer_ring_bounded():
    t = Tracer("osd.0", enabled=True, keep=3)
    for i in range(10):
        t.start("op", trace_id=f"T{i}").finish()
    rec = t.dump_recent(99)
    assert len(rec) == 3
    assert set(rec) == {"T7", "T8", "T9"}  # newest kept


# ------------------------------------------------------------ attribution


def _synthetic_events():
    return [
        (-0.005, "objecter:submit"),
        (-0.004, "objecter:send"),
        (-0.003, "msgr:client.1:send"),
        (-0.001, "msgr:osd.0:recv"),
        (0.0, "initiated"),
        (0.0001, "dispatched"),
        (0.0002, "lock_wait:pg.lock"),
        (0.0012, "lock_acquired:pg.lock"),
        (0.002, "ec_encode"),
        (0.010, "ec_encoded"),
        (0.0105, "store:commit"),
        (0.011, "ec_sub_write_sent"),
        (0.015, "sub_write_acked"),
        (0.0151, "commit"),
        (0.0152, "done"),
    ]


def test_attribution_sums_exactly_and_maps_stages():
    stages, total = attribute_events(_synthetic_events())
    # every traced nanosecond lands in exactly one bucket
    assert abs(sum(stages.values()) - total) < 1e-12
    assert abs(total - 0.0202) < 1e-9
    assert abs(stages["lock:pg.lock"] - 0.001) < 1e-9
    assert abs(stages["device_encode"] - 0.008) < 1e-9
    assert abs(stages["sub_write_wait"] - 0.004) < 1e-9
    assert "wire" in stages and "dispatch_queue" in stages
    # aggregation with a measured wall computes the coverage metric
    agg = aggregate([_synthetic_events()], measured_wall_s=0.021)
    assert agg["ops"] == 1
    assert agg["wall_coverage"] == pytest.approx(0.0202 / 0.021, abs=1e-3)
    fracs = sum(row["frac"] for row in agg["stages"].values())
    assert fracs == pytest.approx(1.0, abs=0.01)


def test_merge_reports_sums_disjoint_daemon_slices():
    """Primaries spread across OSDs, so per-daemon reports are
    disjoint slices: the merged artifact must SUM them, not keep the
    biggest one."""
    from ceph_tpu.trace.attribution import merge_reports

    a = aggregate([_synthetic_events()])
    merged = merge_reports([a, a, {"ops": 0}], measured_wall_s=0.021)
    assert merged["ops"] == 2
    assert merged["traced_total_s"] == \
        pytest.approx(2 * a["traced_total_s"], abs=1e-6)
    assert merged["stages"]["device_encode"]["s"] == \
        pytest.approx(0.016, abs=1e-6)
    # per-op mean is unchanged by merging identical slices
    assert merged["wall_coverage"] == pytest.approx(0.0202 / 0.021,
                                                    abs=1e-3)
    empty = merge_reports([{"ops": 0}])
    assert empty == {"ops": 0, "traced_total_s": 0.0, "stages": {}}


def test_stage_mapping_rules():
    assert stage_for("msgr:osd.2:recv") == "wire"
    assert stage_for("msgr:osd.2:send") == "messenger_send"
    assert stage_for("msgr:flushed") == "messenger_send"
    assert stage_for("lock_acquired:messenger.session") == \
        "lock:messenger.session"
    assert stage_for("lock_wait:pg.lock") == "exec"
    assert stage_for("never_seen_before") == "other:never_seen_before"


def test_spans_from_events_rebased():
    spans = spans_from_events(_synthetic_events())
    assert spans[0]["start"] == 0.0
    assert all(sp["dur"] >= 0 for sp in spans)
    assert any(sp["stage"] == "device_encode" for sp in spans)


# --------------------------------------------------------------- perfetto


def test_chrome_trace_from_dumps_structure():
    op = {"trace_id": "T1", "description": "osd_op(...)",
          "duration": 0.02,
          "type_data": {"events": [
              {"time": t, "event": e} for t, e in _synthetic_events()]}}
    doc = chrome_trace_from_dumps({"osd.0": {"num_ops": 1, "ops": [op]}})
    evs = doc["traceEvents"]
    assert any(e["ph"] == "M" and e["args"]["name"] == "osd.0"
               for e in evs)
    slices = [e for e in evs if e["ph"] == "X"]
    assert slices and all(e["dur"] >= 0 and e["ts"] >= 0 for e in slices)
    json.dumps(doc)  # serializable


def test_chrome_trace_from_spans_structure():
    t = Tracer("osd.0", enabled=True)
    with t.start("osd_op", trace_id="T"):
        pass
    doc = chrome_trace_from_spans(t.dump_trace("T"))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 1 and xs[0]["name"] == "osd_op"


# ----------------------------------------------------------- lockdep hook


def test_lockdep_hook_marks_current_op():
    from ceph_tpu.cluster.optracker import CURRENT_OP, OpTracker
    from ceph_tpu.utils.lockdep import DepLock

    async def scenario():
        tr = OpTracker()
        op = tr.create("osd_op(test)")
        token = CURRENT_OP.set(op)
        try:
            async with DepLock("hook.test"):
                pass
        finally:
            CURRENT_OP.reset(token)
        op.finish()
        names = [e for _, e in op.events]
        assert "lock_wait:hook.test" in names
        assert "lock_acquired:hook.test" in names
        # and outside an op the hook is a no-op (nothing raised)
        async with DepLock("hook.idle"):
            pass

    run(scenario())


def test_event_ordering_inherited_stamps_never_drift_past_arrival():
    """The round-9 ordering fix: a wall-clock header stamp racing the
    op's monotonic start must still sort before 'initiated'."""
    from ceph_tpu.cluster.optracker import OpTracker

    tr = OpTracker()
    future_stamp = time.time() + 0.050  # wall/monotonic sampling skew
    op = tr.create("osd_op(x)", trace={
        "id": "T", "events": [("objecter:submit", time.time() - 0.01),
                              ("msgr:osd.0:recv", future_stamp)]})
    op.mark("dispatched")
    op.finish()
    d = op.dump()
    names = [e["event"] for e in d["type_data"]["events"]]
    assert names.index("msgr:osd.0:recv") < names.index("initiated") \
        < names.index("dispatched")
    times = [e["time"] for e in d["type_data"]["events"]]
    assert times == sorted(times)
    # completed ops expose the derived stage spans (satellite: optracker
    # and graft-trace agree on one op timeline)
    assert d["spans"] and all("stage" in sp for sp in d["spans"])


# ------------------------------------------------------------ loop profiler


def test_loop_profiler_catches_a_stall_and_wraps_tasks():
    perf = PerfCounters("t")
    mon = LoopProfiler(perf, interval=0.01, prefix="loop")

    async def scenario():
        loop = asyncio.get_event_loop()
        sampler = loop.create_task(mon.sample())
        try:
            # converge-poll (round-13 deflake convention): wait until
            # the sampler has provably taken a sample, so the stall
            # lands inside a measurement window
            deadline = loop.time() + 5.0
            while loop.time() < deadline and \
                    perf.dump()["t"]["loop_lag"]["avgcount"] < 1:
                await asyncio.sleep(0.005)

            async def stall():
                # deliberate loop stall — the exact bug class the
                # profiler exists to expose; the duration IS the test
                # stimulus, not a convergence wait
                # graftlint: ignore[asyncio-blocking] graftlint: ignore[fixed-sleep-in-tests]
                time.sleep(0.08)

            await mon.wrap(stall())
            # converge-poll until the sampler observed the stall (a
            # fixed post-stall sleep flakes on a loaded host)
            deadline = loop.time() + 5.0
            while loop.time() < deadline and mon.window_max < 0.05:
                await asyncio.sleep(0.005)
        finally:
            sampler.cancel()

    run(scenario())
    assert mon.window_max >= 0.05
    dump = perf.dump()["t"]
    assert dump["loop_lag"]["avgcount"] >= 1
    assert dump["loop_lag"]["max"] >= 0.05
    assert dump["loop_task_spawns"] == 1
    assert dump["loop_task_wall"]["avgcount"] == 1
    mon.reset_window()
    assert mon.window_max == 0.0
    assert mon.lag_report() is not None


def test_loop_profiler_disabled_is_identity():
    perf = PerfCounters("t")
    mon = LoopProfiler(perf, interval=0.0)
    assert not mon.enabled
    assert mon.lag_report() is None

    async def coro():
        return 7

    c = coro()
    assert mon.wrap(c) is c  # untouched coroutine
    assert run(_consume(c)) == 7
    assert perf.dump()["t"] == {}  # nothing declared


async def _consume(c):
    return await c


def test_loop_lag_flows_to_prometheus_and_daemonperf():
    """Satellite: the lag counters ride the existing exporter paths."""
    from ceph_tpu.cluster.mgr import render_prometheus
    from ceph_tpu.tools.ceph import _rate_rows

    perf = PerfCounters("osd.0")
    mon = LoopProfiler(perf, interval=0.01, prefix="osd_loop")
    assert mon.enabled
    perf.tinc("osd_loop_lag", 0.02)
    counters = perf.dump()["osd.0"]
    text = render_prometheus({"osd.0": counters})
    assert "ceph_osd_loop_lag_sum" in text
    assert "ceph_osd_loop_lag_count" in text
    prev = {"osd.0": {"osd_loop_lag": {"avgcount": 0, "sum": 0.0}}}
    rows = _rate_rows(prev, {"osd.0": counters}, 1.0)
    assert any("osd_loop_lag" in name for name, _ in rows)


# ------------------------------------------------------------ CLI (convert)


def test_trace_cli_exit_codes(tmp_path):
    """scripts/trace.py exit codes, tested like the chaos CLI: 0 on a
    good convert, 1 on bad input, 2 on usage errors."""
    script = os.path.join(REPO, "scripts", "trace.py")
    dump = {"num_ops": 1, "ops": [{
        "trace_id": "T1", "description": "osd_op",
        "type_data": {"events": [
            {"time": t, "event": e} for t, e in _synthetic_events()]}}]}
    df = tmp_path / "dump.json"
    df.write_text(json.dumps(dump))
    out = tmp_path / "trace.json"
    proc = subprocess.run(
        [sys.executable, script, "convert", str(df), "-o", str(out)],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(out.read_text())
    assert doc["traceEvents"]
    # missing input -> 1
    proc = subprocess.run(
        [sys.executable, script, "convert", str(tmp_path / "nope.json")],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert proc.returncode == 1
    # a bare JSON array (not a dump payload) -> clean 1, no traceback
    dfa = tmp_path / "array.json"
    dfa.write_text(json.dumps([1, 2, 3]))
    proc = subprocess.run(
        [sys.executable, script, "convert", str(dfa)],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert proc.returncode == 1
    assert "Traceback" not in proc.stderr
    # empty dump -> 1
    df2 = tmp_path / "empty.json"
    df2.write_text(json.dumps({"num_ops": 0, "ops": []}))
    proc = subprocess.run(
        [sys.executable, script, "convert", str(df2)],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert proc.returncode == 1
    # usage error -> 2 (argparse)
    proc = subprocess.run(
        [sys.executable, script, "bogus"],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert proc.returncode == 2


# ------------------------------------------------------------------- e2e


def _trace_config():
    from ceph_tpu.cluster.vstart import _fast_config

    config = _fast_config()
    config.trace_enabled = 1
    config.osd_op_history_size = 200
    return config


EC_PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
              "k": "2", "m": "1"}


def test_traced_op_cross_daemon_smoke():
    """Tier-1 smoke (satellite 6): one traced EC write through vstart —
    span tree shape, unified optracker timeline, and attribution
    coverage against the client-measured wall."""
    from ceph_tpu.cluster.vstart import start_cluster

    async def scenario():
        cluster = await start_cluster(3, config=_trace_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create("tr", "erasure", pg_num=4,
                                            ec_profile=EC_PROFILE)
            io = client.ioctx(pool)
            await io.write_full("warm", b"w" * 8192)  # compile warmup
            t0 = time.perf_counter()
            await io.write_full("traced", b"\xa5" * 65536)
            wall = time.perf_counter() - t0
            tracer = client.objecter.tracer
            tid = list(tracer._traces)[-1]
            # --- span tree across daemons (admin `trace dump`) ---
            spans = tracer.dump_trace(tid)
            for oid in cluster.osds:
                spans += await cluster.daemon_command(
                    f"osd.{oid}", {"prefix": "trace dump",
                                   "args": {"trace_id": tid}})
            roots = assemble_tree(spans)
            assert len(roots) == 1, [s["name"] for s in spans]
            root = roots[0]
            assert root["name"] == "op_submit"
            assert root["daemon"].startswith("client.")
            osd_ops = [c for c in root["children"]
                       if c["name"] == "osd_op"]
            assert len(osd_ops) == 1
            subs = [c for c in osd_ops[0]["children"]
                    if c["name"] == "ec_sub_write"]
            assert len(subs) == 2  # k2m1 on 3 osds: two peer shards
            assert {s["daemon"] for s in subs} & \
                {f"osd.{o}" for o in cluster.osds}
            # --- the optracker timeline carries the same trace id ---
            found = None
            for oid in cluster.osds:
                hist = await cluster.daemon_command(
                    f"osd.{oid}", "dump_historic_ops")
                for op in hist["ops"]:
                    if op.get("trace_id") == tid:
                        found = op
            assert found is not None
            names = [e["event"] for e in found["type_data"]["events"]]
            assert "objecter:submit" in names       # client-side stamps
            assert any(n.startswith("msgr:") and n.endswith(":recv")
                       for n in names)              # wire arrival
            # device-encode evidence: the coalesced tick marks
            assert ("batch_parked" in names and "batch_tick" in names
                    and "batch_encoded" in names)
            assert "store:commit" in names
            assert "ec_sub_write_sent" in names
            assert "sub_write_acked" in names
            assert "lock_acquired:pg.lock" in names  # lockdep hook
            times = [e["time"] for e in found["type_data"]["events"]]
            assert times == sorted(times)           # monotone timeline
            assert found["spans"]                   # unified spans view
            # --- attribution coverage vs the measured wall ---
            evs = [(e["time"], e["event"])
                   for e in found["type_data"]["events"]]
            stages, total = attribute_events(evs)
            assert abs(sum(stages.values()) - total) < 1e-9
            assert total >= 0.85 * wall, (total, wall, stages)
            # device work books as the amortized coalesced-tick stage
            assert "batch_encode" in stages
            # the admin aggregation agrees
            primary = client.objecter._target_osd(
                client.objecter.object_pgid(pool, "traced"))
            rep = await cluster.daemon_command(
                f"osd.{primary}",
                {"prefix": "dump_op_attribution",
                 "args": {"match": "write_full",
                          "measured_wall_s": wall}})
            assert rep["ops"] >= 1
            assert rep["wall_coverage"] >= 0.85
        finally:
            await cluster.stop()

    run(scenario())


def test_trace_survives_reconnect_and_daemon_restart():
    """Satellite: trace propagation survives a chaos-dropped (and
    retransmitted) frame and a primary daemon restart — the header
    rides the replayed frame, so the op's timeline stays whole."""
    from ceph_tpu.cluster.vstart import start_cluster

    async def scenario():
        cluster = await start_cluster(3, config=_trace_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create("tr2", "replicated",
                                            pg_num=4, size=2)
            io = client.ioctx(pool)
            await io.write_full("pre", b"x")
            # seeded drops on the CLIENT's outgoing frames: sends gate,
            # reconnect+replay carries the pickled trace header whole
            client.objecter.config.injectargs(
                {"chaos_seed": 7, "chaos_net_drop": 0.25})
            for i in range(6):
                await io.write_full(f"dropped_{i}", bytes([i]) * 512)
            client.objecter.config.injectargs({"chaos_net_drop": 0.0})

            async def traced_ids():
                out = set()
                for oid in cluster.osds:
                    hist = await cluster.daemon_command(
                        f"osd.{oid}", "dump_historic_ops")
                    for op in hist["ops"]:
                        if op.get("trace_id"):
                            names = [e["event"]
                                     for e in op["type_data"]["events"]]
                            assert "objecter:submit" in names
                            out.add(op["trace_id"])
                return out

            # every write that rode a dropped+retransmitted frame still
            # carries its full client trace (the header replays with
            # the pickled frame)
            assert len(await traced_ids()) >= 7
            # a restarted primary (fresh in-memory tracker) keeps
            # absorbing headers from the replayed client sessions
            pgid = client.objecter.object_pgid(pool, "after_restart")
            primary = client.objecter._target_osd(pgid)
            await cluster.restart_osd(primary)
            await io.write_full("after_restart", b"z" * 512)
            newest = list(client.objecter.tracer._traces)[-1]
            assert newest in await traced_ids()
        finally:
            await cluster.stop()

    run(scenario())


def test_tracing_disabled_bit_identical_ec_write():
    """Satellite: tracing enabled vs disabled produces bit-identical
    stored EC shards — the instrument can never perturb data."""
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster

    payloads = {f"obj_{i}": bytes([i * 17 % 251]) * (4096 * (i + 1))
                for i in range(3)}

    async def run_one(trace_on: bool):
        config = _fast_config()
        config.trace_enabled = 1 if trace_on else 0
        cluster = await start_cluster(3, config=config)
        try:
            client = await cluster.client()
            pool = await client.pool_create("bit", "erasure", pg_num=4,
                                            ec_profile=EC_PROFILE)
            io = client.ioctx(pool)
            for oid, data in payloads.items():
                await io.write_full(oid, data)
            state = {}
            for osd_id, osd in cluster.osds.items():
                for coll in osd.store.list_collections():
                    if not coll.startswith(f"pg_{pool}_"):
                        continue
                    for name in osd.store.list_objects(coll):
                        if name not in payloads:
                            continue
                        state[(osd_id, coll, name)] = (
                            bytes(osd.store.read(coll, name)),
                            osd.store.getattr(coll, name, "shard"),
                            osd.store.getattr(coll, name, "hinfo_crc"),
                        )
            return state
        finally:
            await cluster.stop()

    on = run(run_one(True))
    off = run(run_one(False))
    assert on and on == off


def test_loop_lag_health_warning_raises_and_clears():
    """Satellite: sustained loop lag raises LOOP_LAG beside SLOW_OPS
    (beacon-fed) and clears once the loop drains."""
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster

    async def scenario():
        config = _fast_config()
        config.loop_profile_interval = 0.02
        config.loop_lag_warn = 0.05
        cluster = await start_cluster(2, config=config)
        try:
            client = await cluster.client()
            # drive one op so the profiler-wrapped dispatch drainers run
            pool = await client.pool_create("ll", "replicated",
                                            pg_num=2, size=2)
            await client.ioctx(pool).write_full("o", b"x")
            spawns = walls = 0
            for oid in cluster.osds:
                d = await cluster.daemon_command(f"osd.{oid}",
                                                 "perf dump")
                spawns += d[f"osd.{oid}"]["osd_loop_task_spawns"]
                walls += d[f"osd.{oid}"]["osd_loop_task_wall"]["avgcount"]
            # per-task profiling is wired into the real dispatch path
            assert spawns >= 1 and walls >= 1

            async def stall():
                # block the shared loop long enough for a sample to
                # overshoot the warn threshold — the duration IS the
                # test stimulus, not a convergence wait
                # graftlint: ignore[asyncio-blocking] graftlint: ignore[fixed-sleep-in-tests]
                time.sleep(0.12)

            await stall()
            deadline = asyncio.get_event_loop().time() + 5.0
            seen = False
            while asyncio.get_event_loop().time() < deadline:
                health = await client.objecter.mon_command(
                    {"prefix": "health"})
                if "LOOP_LAG" in health["checks"]:
                    seen = True
                    break
                await asyncio.sleep(0.05)
            assert seen, health
            # drained: later beacons carry a clean window and it clears
            deadline = asyncio.get_event_loop().time() + 5.0
            while asyncio.get_event_loop().time() < deadline:
                health = await client.objecter.mon_command(
                    {"prefix": "health"})
                if "LOOP_LAG" not in health["checks"]:
                    return
                await asyncio.sleep(0.05)
            raise AssertionError(f"LOOP_LAG never cleared: {health}")
        finally:
            await cluster.stop()

    run(scenario())
