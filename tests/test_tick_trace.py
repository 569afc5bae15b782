"""The encode tick, opened (trace/tick.py, trace/gapjoin.py): one record
per coalesced device tick, tiled by its host phases on the device trace's
clock, the counters fed from it, the admin surface, and the join that
cuts device idle gaps by host cause.

The served-burst tests force the device branch of ``encode_planes_multi``
the way ``test_device_branches_serve_write_read_recover`` does (the host
GF engine switched off), because on a CPU backend the tick would
otherwise never reach ``to_planar`` / ``encode_planar`` / the readbacks.
"""

import asyncio
import json
import os
import threading

import numpy as np
import pytest

# the counter growth the benchmark's ``window_counters`` is made with
from benchmark.harness.cell import grew as _grew
from benchmark.harness.cell import kernel_counters as _kernels
from ceph_tpu.cluster.vstart import _fast_config, start_cluster
from ceph_tpu.trace import assemble_tree, gapjoin, perfetto
from ceph_tpu.trace import tick as ticktrace
from ceph_tpu.utils.perf import KERNELS, PerfCounters

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLICE = os.path.join(ROOT, "benchmark", "tests", "data",
                     "k2m1_write_slice.xplane.pb.gz")

N_WRITES = 12
# read off the code, device branch: the host->device copy of the batch
# and the ingest program (codec.to_planar), the planar matmul
# (codec.encode_planar), the chunk-crc program
# (ops/crc32c.planar_chunk_crcs), the two plane readbacks and the
# readback of the crc words (ec/stripe.py)
DEVICE_CALLS_PER_TICK = 2 + 1 + 1 + 2 + 1


@pytest.fixture(scope="module")
def burst():
    """One served burst on the device branch: 12 concurrent 64 KiB
    write_fulls to a k2m1 pool, then the same objects read back with one
    OSD down (decode ticks)."""
    from ceph_tpu.ec import stripe

    async def scenario():
        cluster = await start_cluster(3, config=_fast_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "ticks", "erasure", pg_num=8,
                ec_profile={"plugin": "jerasure",
                            "technique": "reed_sol_van",
                            "k": "2", "m": "1"})
            io = client.ioctx(pool)
            rng = np.random.default_rng(26)
            objs = {f"t_{i}": rng.integers(0, 256, 64 << 10,
                                           dtype=np.uint8).tobytes()
                    for i in range(N_WRITES)}
            seq0 = max((t.seq for t in ticktrace.TICKS.ring), default=0)
            before = _kernels()
            await asyncio.gather(*(io.write_full(n, d)
                                   for n, d in objs.items()))
            grew = _grew(_kernels(), before)
            ticks = [t for t in ticktrace.TICKS.ring if t.seq > seq0]
            dumps, written = {}, {}
            for osd in range(3):
                name = f"osd.{osd}"
                dumps[name] = await cluster.daemon_command(
                    name, {"prefix": "dump_ticks", "n": 64})
                hist = await cluster.daemon_command(
                    name, "dump_historic_ops")
                written[name] = {
                    op["seq"] for op in hist["ops"]
                    if "write_full" in op["description"]}
            one = await cluster.daemon_command(
                "osd.0", {"prefix": "dump_ticks", "args": {"n": 1}})
            await cluster.kill_osd(2)
            await cluster.wait_down(2)
            before = _kernels()
            got = await asyncio.gather(*(io.read(n) for n in objs))
            assert all(g == d for g, d in zip(got, objs.values()))
            after_reads = _grew(_kernels(), before)
            read_ticks = [t for t in ticktrace.TICKS.ring
                          if t.seq > seq0 and t not in ticks]
            return {"grew": grew, "ticks": ticks, "dumps": dumps,
                    "written": written, "one": one,
                    "after_reads": after_reads, "read_ticks": read_ticks}
        finally:
            await cluster.stop()

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(stripe, "_host_engine_ok", lambda codec: False)
        return asyncio.run(scenario())


# ------------------------------------------------------------ the record


def test_one_tick_record_per_coalesced_tick(burst):
    ticks = burst["ticks"]
    assert all(t.name == ticktrace.ENCODE_TICK for t in ticks)
    assert len(ticks) == burst["grew"]["ec_coalesced_ticks"] >= 1
    assert sum(len(t.op_ids) for t in ticks) == N_WRITES \
        == burst["grew"]["ec_coalesced_ops"]
    assert len({t.seq for t in ticks}) == len(ticks)


def test_tick_names_the_ops_that_caused_it(burst):
    """The op ids on a tick are the OpTracker ids of the write_fulls its
    daemon served: every write in exactly one tick."""
    for daemon, seqs in burst["written"].items():
        ids = [i for t in burst["ticks"] if t.daemon == daemon
               for i in t.op_ids]
        assert None not in ids
        assert sorted(ids) == sorted(seqs), daemon
    assert sum(len(s) for s in burst["written"].values()) == N_WRITES


def test_tick_root_says_what_it_encoded(burst):
    for t in burst["ticks"]:
        assert t.stripes == 8 * len(t.op_ids)       # 64 KiB / (2 x 4 KiB)
        assert t.bucket >= t.stripes and t.bucket & (t.bucket - 1) == 0
        assert t.payload_bytes == (64 << 10) * len(t.op_ids)
        assert t.thread and t.thread != threading.get_ident()


def test_phases_in_order_and_tiling_the_root(burst):
    # one "slice" since PR 51: an op's block is written once, out of the
    # two readbacks, and the tick's planes are no longer stacked first
    want = ["executor_wait", "fill", "to_planar", "encode_dispatch",
            "crc", "readback", "readback", "crc", "slice", "wake"]
    for t in burst["ticks"]:
        segs = t.segments()
        assert [s[0] for s in segs if s[0] != "other"] == want
        assert segs[0][1] == t.opened_ns and segs[-1][2] == t.closed_ns
        for (_n0, _a0, end, _c0), (_n1, start, _b1, _c1) in zip(segs,
                                                                segs[1:]):
            assert end == start
        assert all(b >= a for _n, a, b, _c in segs)
        assert sum(b - a for _n, a, b, _c in segs) \
            == t.closed_ns - t.opened_ns


@pytest.mark.parametrize("branch", ["host", "device"])
@pytest.mark.parametrize("sizes", [[65536], [65536, 65536],
                                   [65536, 65536, 65536],
                                   [65536, 3 * 8192 - 100, 0, 8192]],
                         ids=["one", "two", "three_padded_to_four",
                              "mixed_and_empty"])
def test_each_op_of_a_tick_gets_a_block_of_its_own(monkeypatch, branch,
                                                   sizes):
    """Since PR 51 the slice writes every op's planes once, out of the
    tick's data and parity planes, without stacking the tick first, and
    the batch is allocated at its bucket's size: what an op is handed is
    what a tick of that op alone hands it, contiguous, writable, and
    nobody else's memory."""
    from ceph_tpu.ec import factory, stripe

    codec = factory({"plugin": "jerasure", "technique": "reed_sol_van",
                     "k": "2", "m": "1"})
    sinfo = stripe.StripeInfo(2, 4096)
    if branch == "device":
        monkeypatch.setattr(stripe, "_host_engine_ok", lambda codec: False)
    rng = np.random.default_rng(51)
    datas = [rng.integers(0, 256, sz, dtype=np.uint8).tobytes()
             for sz in sizes]
    want = [True] * len(datas)
    pad0 = KERNELS.dump()["device_kernels"].get("ec_stripe_pad_bytes", 0)
    together = stripe.encode_planes_multi(codec, sinfo, datas, want)
    pad = KERNELS.dump()["device_kernels"]["ec_stripe_pad_bytes"] - pad0
    stripes = [sinfo.object_stripes(sz) for sz in sizes]
    bb = sum(stripes) if branch == "host" else stripe._bucket(sum(stripes))
    assert pad == bb * sinfo.stripe_width - sum(sizes)
    for i, d in enumerate(datas):
        [(alone, crcs)] = stripe.encode_planes_multi(codec, sinfo, [d],
                                                     [True])
        planes, got = together[i]
        assert planes.shape == (3, 8, stripes[i] * 4096 // 8)
        assert np.array_equal(planes, alone) and list(got) == list(crcs)
        assert planes.flags.c_contiguous and planes.flags.writeable
        for j in range(i):
            assert not np.shares_memory(planes, together[j][0])


def test_device_calls_per_tick_equal_the_count_read_off_the_code(burst):
    for t in burst["ticks"]:
        assert t.calls == DEVICE_CALLS_PER_TICK
        by_phase = {}
        for name, _a, _b, calls in t.phases():
            by_phase[name] = by_phase.get(name, 0) + calls
        assert by_phase == {"fill": 0, "to_planar": 2,
                            "encode_dispatch": 1, "readback": 2,
                            "slice": 0, "crc": 2}
        # the launch, then the words' readback: one call each
        assert [calls for name, _a, _b, calls in t.phases()
                if name == "crc"] == [1, 1]
    assert burst["grew"]["ec_tick_device_calls"] \
        == DEVICE_CALLS_PER_TICK * len(burst["ticks"])


def test_counters_are_fed_once_per_tick_from_the_record(burst):
    grew, ticks = burst["grew"], burst["ticks"]
    wall = sum(t.t[2] - t.t[1] for t in ticks)
    assert grew["ec_tick_wall_ns"] == wall
    assert grew["ec_tick_handoff_ns"] == sum(
        (t.t[1] - t.t[0]) + (t.t[3] - t.t[2]) for t in ticks)
    phases = ["ec_tick_fill_ns", "ec_tick_to_planar_ns",
              "ec_tick_dispatch_ns", "ec_tick_readback_ns",
              "ec_tick_slice_ns", "ec_tick_crc_ns"]
    assert all(grew[c] > 0 for c in phases)
    assert sum(grew[c] for c in phases) <= wall
    assert grew["ec_tick_to_planar_ns"] == sum(
        t.phase_ns()["to_planar"] for t in ticks)
    # every tick's crcs came from the device program
    assert grew["ec_tick_crc_device_ticks"] == len(ticks)
    assert all(t.crc_on_device() for t in ticks)
    # occupancy: never more than the wall between the first thread's
    # start and the last one's return, and two or more is a part of any
    span = max(t.t[2] for t in ticks) - min(t.t[1] for t in ticks)
    assert 0 < grew["ec_tick_any_active_ns"] <= span
    assert grew.get("ec_tick_multi_active_ns", 0) \
        <= grew["ec_tick_any_active_ns"]


def test_decode_ticks_get_root_and_handoff_from_the_shared_seam(burst):
    """Degraded reads: ``decode_planes_multi`` ticks are recorded with
    executor_wait and wake (no inner phases asked for), and feed none of
    the encode tick's counters."""
    reads = [t for t in burst["read_ticks"] if t.name == "decode_tick"]
    assert len(reads) == burst["after_reads"]["ec_coalesced_read_ticks"] > 0
    for t in reads:
        assert [s[0] for s in t.segments()] \
            == ["executor_wait", "other", "wake"]
        assert t.device_window() is None
        assert all(i is not None for i in t.op_ids)
    assert not any(k.startswith("ec_tick_") for k in burst["after_reads"])


# ----------------------------------------------------- operator's surface


def test_dump_ticks_over_the_admin_path(burst):
    for daemon, dump in burst["dumps"].items():
        mine = [t for t in burst["ticks"] if t.daemon == daemon]
        assert len(dump) >= len(mine)
        for spans in dump.values():
            assert {s["daemon"] for s in spans} == {daemon}
        newest = list(dump.values())[-len(mine):] if mine else []
        assert [spans[0]["meta"]["seq"] for spans in newest] \
            == [t.seq for t in mine]
    # {"args": {"n": 1}}: the newest one only
    [(trace_id, spans)] = burst["one"].items()
    assert trace_id.startswith("osd.0:tick")
    json.dumps(burst["dumps"])          # plain data: it crossed the wire


def test_a_dumped_tick_is_a_span_tree_the_exporters_take(burst):
    """``Span.dump()``'s fields, so ``assemble_tree`` and
    ``perfetto.chrome_trace_from_spans`` take it unchanged."""
    # the newest tick of a daemon that ticked in this burst: the ring
    # is the process's, and an earlier test file's ticks of an "osd.0"
    # (host crcs among them) may still lead a dump
    spans = list(burst["dumps"][burst["ticks"][-1].daemon].values())[-1]
    for s in spans:
        assert {"trace_id", "span_id", "parent_id", "name", "daemon",
                "start", "dur", "meta"} <= set(s)
    [root] = assemble_tree(spans)
    assert root["name"] == ticktrace.ENCODE_TICK
    assert root["meta"]["ops"] == len(root["meta"]["op_ids"])
    kids = root["children"]
    assert kids[0]["name"] == "executor_wait" and kids[-1]["name"] == "wake"
    assert sum(k["meta"]["dur_ns"] for k in kids) == root["meta"]["dur_ns"]
    assert [k["meta"]["path"] for k in kids if k["name"] == "crc"] \
        == ["device", "device"]
    doc = perfetto.chrome_trace_from_spans(spans)
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(slices) == len(spans)
    assert {e["name"] for e in slices} >= {"encode_tick", "to_planar",
                                           "readback", "wake"}


# ------------------------------------------------ outside a tick: nothing


def test_outside_a_tick_the_phase_is_one_shared_noop():
    from ceph_tpu.ec import factory
    from ceph_tpu.ec.stripe import StripeInfo, encode_planes_multi

    assert ticktrace.phase("fill") is ticktrace.NULL_PHASE
    assert ticktrace.phase("crc") is ticktrace.phase("readback")
    assert not ticktrace.NULL_PHASE
    with ticktrace.phase("to_planar") as p:
        assert p is ticktrace.NULL_PHASE
    ticktrace.device_calls(3)
    ticktrace.annotate(1, 1, 1)
    # a direct call (tests, tools) records no tick and
    # feeds none of its counters
    codec = factory({"plugin": "jerasure", "technique": "reed_sol_van",
                     "k": "2", "m": "1"})
    n0, before = len(ticktrace.TICKS.ring), _kernels()
    encode_planes_multi(codec, StripeInfo(2, 4096), [b"x" * 8192], [True])
    grew = _grew(_kernels(), before)
    assert grew["ec_coalesced_ticks"] == 1
    assert not any(k.startswith("ec_tick_") for k in grew)
    assert len(ticktrace.TICKS.ring) == n0


@pytest.mark.parametrize("profile", [
    pytest.param({"plugin": "jerasure", "technique": "reed_sol_van",
                  "k": "2", "m": "1"}, id="k2m1"),
    # a code that is not MDS passes through the tick as every tick does
    pytest.param({"plugin": "lrc", "k": "4", "m": "2", "l": "3"},
                 id="lrc_k4m2l3"),
])
@pytest.mark.parametrize("branch", ["device", "host"])
def test_crc_device_ticks_counter_and_window(monkeypatch, branch, profile):
    """One encode tick run by hand on each branch of
    ``encode_planes_multi``: ``ec_tick_crc_device_ticks`` grows by one
    where the device program made the crcs and by nothing on the host
    branch, and the device window reaches past the plane readbacks to
    the readback of the crc words."""
    from ceph_tpu.ec import factory, stripe
    from ceph_tpu.ec.stripe import StripeInfo, encode_planes_multi

    if branch == "device":
        monkeypatch.setattr(stripe, "_host_engine_ok", lambda codec: False)
    codec = factory(dict(profile))
    k, n = codec.get_data_chunk_count(), codec.get_chunk_count()
    counters = PerfCounters("synthetic")
    log = ticktrace.TickLog(keep=4, counters=counters)
    tick = log.open(ticktrace.ENCODE_TICK, "osd.0", (1, 2))
    # 2 + 1 stripes: two shard lengths
    datas = [bytes(range(256)) * 32 * k, b"y" * 4096 * k]
    out = tick.run(encode_planes_multi, codec, StripeInfo(k, 4096), datas,
                   [True, True])
    tick.close()
    assert all(len(crcs) == n for _planes, crcs in out)
    spans = [s for s in tick.dump() if s["name"] == "crc"]
    assert [s["meta"]["path"] for s in spans] == \
        ["device" if branch == "device" else "host"] * 2
    got = counters.dump()["synthetic"]
    crc = [(t0, t1, calls) for name, t0, t1, calls in tick.phases()
           if name == "crc"]
    if branch == "host":
        assert got["ec_tick_crc_device_ticks"] == 0
        assert not tick.crc_on_device() and tick.device_window() is None
        assert [c for _a, _b, c in crc] == [0, 0]   # two shard lengths
        assert tick.dump()[0]["meta"]["device_calls"] == 0
        return
    assert got["ec_tick_crc_device_ticks"] == 1 and tick.crc_on_device()
    assert got["ec_tick_device_calls"] == DEVICE_CALLS_PER_TICK
    readbacks = [t1 for name, _t0, t1, _c in tick.phases()
                 if name == "readback"]
    lo, hi = tick.device_window()
    # launched before the first readback, read after the last
    assert crc[0][1] <= min(readbacks) <= max(readbacks) <= crc[1][0]
    assert hi == crc[1][1] and lo < crc[0][0]


# --------------------------------------------- injected clock: occupancy


class _Clock:
    """Hands out the listed stamps, one per read."""

    def __init__(self, stamps):
        self.stamps = list(stamps)

    def __call__(self):
        return self.stamps.pop(0)


def _scripted_tick(log, clock, name, daemon, opened, start, phases, end,
                   closed, op_ids=(1,)):
    """A closed tick with exactly these stamps (phases: (name, t0, t1))."""
    clock.stamps = [opened, start] + [t for _n, a, b in phases
                                      for t in (a, b)] + [end, closed]
    tick = log.open(name, daemon, op_ids)

    def work():
        for pname, _a, _b in phases:
            with ticktrace.phase(pname):
                pass

    tick.run(work)
    tick.close()
    assert not clock.stamps
    return tick


def test_occupancy_counters_on_two_overlapping_ticks():
    """A runs 100..200, B runs 130..170 on another thread: 100 ns with at
    least one tick thread running, 40 ns with two."""
    clock = _Clock([10, 20,          # A, B opened
                    100, 130, 170, 200,     # A starts, B starts, B, A end
                    210, 220])       # B, A closed
    counters = PerfCounters("synthetic")
    log = ticktrace.TickLog(keep=4, clock=clock, counters=counters)
    a = log.open(ticktrace.ENCODE_TICK, "osd.0", (1, 2))
    b = log.open(ticktrace.ENCODE_TICK, "osd.1", (7,))
    b_started, b_done = threading.Event(), threading.Event()

    def work_b():
        b_started.set()

    def run_b():
        b.run(work_b)
        b_done.set()

    def work_a():
        threading.Thread(target=run_b).start()
        assert b_done.wait(10)

    a.run(work_a)
    assert b_started.is_set()
    b.close()
    a.close()
    got = counters.dump()["synthetic"]
    assert got["ec_tick_any_active_ns"] == 100
    assert got["ec_tick_multi_active_ns"] == 40
    assert got["ec_tick_wall_ns"] == (200 - 100) + (170 - 130)
    assert got["ec_tick_handoff_ns"] == (100 - 10) + (220 - 200) \
        + (130 - 20) + (210 - 170)
    assert [t.seq for t in log.ring] == [b.seq, a.seq]
    # the process-wide log was not touched
    assert a not in ticktrace.TICKS.ring


def test_other_tick_kinds_do_not_feed_the_encode_counters():
    clock = _Clock([])
    counters = PerfCounters("synthetic")
    log = ticktrace.TickLog(clock=clock, counters=counters)
    _scripted_tick(log, clock, "decode_tick", "osd.0", 0, 5, [], 50, 60)
    got = counters.dump()["synthetic"]
    assert all(v == 0 for v in got.values())
    assert len(log.ring) == 1


def test_ring_is_bounded_and_a_tick_cut_short_is_dropped():
    clock = _Clock([])
    log = ticktrace.TickLog(keep=3, clock=clock,
                            counters=PerfCounters("synthetic"))
    made = [_scripted_tick(log, clock, ticktrace.ENCODE_TICK, "osd.0",
                           t, t + 1, [("fill", t + 2, t + 3)], t + 4, t + 5)
            for t in range(0, 50, 10)]
    assert list(log.ring) == made[-3:]
    assert list(log.dump("osd.0", 2)) == [
        f"osd.0:tick{t.seq}" for t in made[-2:]]
    assert log.dump("osd.9") == {} and log.dump("osd.0", 0) == {}
    # closed before its thread returned (a cancelled drain loop)
    clock.stamps = [100]
    cut = log.open(ticktrace.ENCODE_TICK, "osd.0")
    cut.close()
    assert cut not in log.ring and cut.closed_ns == 0
    assert ticktrace.TICKS.ring.maxlen == ticktrace.RING_TICKS == 8192


def test_tick_counters_are_declared_with_unit_and_description():
    schema = KERNELS.dump_schema()["device_kernels"]
    names = [c for c, _d in ticktrace._PHASE_COUNTERS.values()] + [
        "ec_tick_wall_ns", "ec_tick_handoff_ns", "ec_tick_any_active_ns",
        "ec_tick_multi_active_ns", "ec_tick_device_calls",
        "ec_tick_crc_device_ticks"]
    assert len(set(names)) == 12
    units = {"ec_tick_device_calls": "calls",
             "ec_tick_crc_device_ticks": "ticks"}
    for name in names:
        assert schema[name]["type"] == "u64"
        assert schema[name]["unit"] == units.get(name, "ns")
        assert schema[name]["description"]


# -------------------------------------------------------------- the join


def _recorded_slice():
    from benchmark.harness import xplane

    [dev] = xplane.load(SLICE)
    return dev.lines[xplane.OPS_LINE], dev.lines[xplane.MODULES_LINE]


# the recorded session (its ``Task Environment`` plane, Unix ns)
PROFILE_START = 1790522430363766601
PROFILE_STOP = 1790522434795369550


def _ticks_over(modules, start):
    """One synthetic encode tick around each (ingest, kernel) pair of the
    recorded program events: to_planar opens 2 ms before the ingest
    program starts, the last readback returns 1 ms after the kernel
    ends."""
    clock = _Clock([])
    log = ticktrace.TickLog(clock=clock, counters=PerfCounters("synthetic"))
    ingest = [e for e in modules
              if e[0].startswith("jit__batch_to_planes_bitpack")]
    kernel = [e for e in modules if e[0].startswith("jit__planar_tiled")]
    assert len(ingest) == len(kernel) == 12
    ticks = []
    for i, ((_n, s0, _d0), (_m, s1, d1)) in enumerate(zip(ingest, kernel)):
        lo = start + s0 - 2_000_000
        hi = start + s1 + d1 + 1_000_000
        ticks.append(_scripted_tick(
            log, clock, ticktrace.ENCODE_TICK, f"osd.{i % 3}",
            lo - 500_000, lo - 400_000,
            [("fill", lo - 300_000, lo - 100_000),
             ("to_planar", lo, lo + 1_500_000),
             ("encode_dispatch", lo + 1_500_000, lo + 1_600_000),
             ("readback", lo + 1_600_000, hi - 100),
             ("readback", hi - 100, hi),
             ("slice", hi, hi + 200_000),
             ("crc", hi + 250_000, hi + 900_000)],
            hi + 950_000, hi + 1_000_000))
    return ticks


def test_join_pieces_sum_to_each_gap_and_to_the_idle_time():
    from benchmark.harness import xplane

    ops, modules = _recorded_slice()
    ticks = _ticks_over(modules, PROFILE_START)
    out = gapjoin.join(ops, modules, PROFILE_START, PROFILE_STOP, ticks)
    session = PROFILE_STOP - PROFILE_START
    assert out["session_ns"] == session
    # the session's edges (before the first device event, after the last
    # one) are set aside: idle or not traced, the trace cannot say
    first = min(s for _n, s, _d in ops)
    last = max(s + d for _n, s, d in ops)
    assert out["lead_ns"] == first and out["tail_ns"] == session - last
    assert out["lead_ns"] + out["traced_ns"] + out["tail_ns"] == session
    assert out["idle_ns"] == out["traced_ns"] - xplane.busy_ns(ops)
    assert sum(out["idle_by_cause_ns"].values()) == out["idle_ns"]
    for gap in out["longest_gaps"]:
        assert sum(ns for _c, ns in gap["causes"]) == gap["ns"]
    assert len(out["longest_gaps"]) == 10
    assert out["longest_gaps"][0]["ns"] >= out["longest_gaps"][-1]["ns"]
    # every gap, not only the ten longest
    timeline = gapjoin.host_timeline(ticks)
    ends = [seg[1] for seg in timeline]
    gaps = gapjoin.idle_gaps([(n, PROFILE_START + s, d)
                              for n, s, d in ops],
                             PROFILE_START, PROFILE_STOP)
    assert len(gaps) > 50
    assert sum(b - a for a, b in gaps) == session - xplane.busy_ns(ops)
    for gap in gaps:
        pieces = gapjoin.cut_gap(gap, timeline, ends)
        assert sum(ns for _c, ns in pieces) == gap[1] - gap[0]


def test_join_books_no_tick_where_none_was_open():
    ops, modules = _recorded_slice()
    ticks = _ticks_over(modules, PROFILE_START)
    out = gapjoin.join(ops, modules, PROFILE_START, PROFILE_STOP, ticks)
    causes = out["idle_by_cause_ns"]
    # the ticks are ~5 ms each and 0.05-0.9 s apart: most of the idle
    # time has no tick open
    assert causes[gapjoin.NO_TICK] > 0.9 * out["idle_ns"]
    assert out["lead_ns"] > 1_500_000_000     # first event at +1.6 s
    assert max(out["longest_gaps"][0]["causes"], key=lambda c: c[1])[0] \
        == gapjoin.NO_TICK
    # the device works between to_planar's start and the readback's
    # return, so its idle inside a tick lies in those phases
    assert causes["to_planar"] > 0 and causes["readback"] > 0
    assert set(causes) <= {gapjoin.NO_TICK, "executor_wait", "other",
                           "wake", *ticktrace.PHASES}
    # with no tick at all every gap is no_tick
    bare = gapjoin.join(ops, modules, PROFILE_START, PROFILE_STOP, [])
    assert bare["idle_by_cause_ns"] == {gapjoin.NO_TICK: bare["idle_ns"]}
    assert bare["idle_ns"] == out["idle_ns"]
    assert bare["causality"]["outside_share"] == 1.0
    assert bare["causality"]["outside_every_tick"] == 24


def test_join_per_tick_transfer_means_and_causality():
    ops, modules = _recorded_slice()
    ticks = _ticks_over(modules, PROFILE_START)
    out = gapjoin.join(ops, modules, PROFILE_START, PROFILE_STOP, ticks)
    check = out["causality"]
    assert check["program_events"] == 24
    assert check["outside_every_tick"] == 0 and check["outside_share"] == 0
    assert check["planar_tiled_events"] == 12 \
        == check["tick_windows_in_trace"]
    per = out["per_tick"]
    # the first and the last tick reach over the traced span's edges:
    # only ticks whose whole window was traced are averaged
    assert per["ticks"] == 10
    assert per["dispatch_to_device_start_ns"] == 2_000_000
    assert per["device_end_to_readback_return_ns"] == 1_000_000
    inner = sorted(modules, key=lambda e: e[1])[2:-2]
    assert per["device_ns"] == sum(d for _n, _s, d in inner) / 10
    assert 0 < per["transfer_and_runtime_share"] < 1
    assert per["window_ns"] >= per["device_ns"] + 3_000_000


def test_causality_check_trips_on_a_shifted_clock():
    """Ticks stamped on a clock 50 ms off the trace's: the program events
    fall outside every tick's window."""
    ops, modules = _recorded_slice()
    ticks = _ticks_over(modules, PROFILE_START + 50_000_000)
    out = gapjoin.join(ops, modules, PROFILE_START, PROFILE_STOP, ticks)
    assert out["causality"]["outside_share"] > 0.5
    # ... and says by how much: each event starts ~48 ms (50 - the 2 ms
    # lead the ticks were given) before the window nearest to it
    for ev in out["causality"]["outside_events"]:
        assert 40_000_000 < ev["starts_before_window_ns"] < 50_000_000
    assert out["causality"]["worst_miss_ns"] == 48_000_000
    # ... a slack that wide takes them all in again
    out = gapjoin.join(ops, modules, PROFILE_START, PROFILE_STOP, ticks,
                       slack_ns=48_000_000)
    assert out["causality"]["outside_share"] == 0
    # ... and 0.5 ms off is inside the +- 1 ms the check allows
    near = _ticks_over(modules, PROFILE_START + 500_000)
    out = gapjoin.join(ops, modules, PROFILE_START, PROFILE_STOP, near)
    assert out["causality"]["outside_share"] == 0


def test_gaps_tool_reports_the_join_of_the_recorded_slice(capsys):
    """``benchmark/gaps.py`` past its run: it reads the session's bounds
    from the trace itself, says whether the ring covers the slice, and
    prints no table when it does not or when the clocks disagree."""
    from benchmark import gaps

    assert gaps.session_bounds(SLICE) == (PROFILE_START, PROFILE_STOP)
    _ops, modules = _recorded_slice()
    ticks = _ticks_over(modules, PROFILE_START)
    device = {"window_s": 4.4, "busy_s": 0.02}
    out = gaps.report(SLICE, ticks, 8192, device)
    text = capsys.readouterr().out
    assert out["ring_covers_slice"] and "it covers the slice" in text
    joined = out["/device:TPU:0"]
    assert joined["causality"]["outside_share"] == 0
    assert "idle time by host cause:" in text and "no_tick" in text
    assert "per tick (mean of 10)" in text
    json.dumps(out)
    # a full ring whose oldest tick is newer than the slice's start
    out = gaps.report(SLICE, ticks, len(ticks), device)
    text = capsys.readouterr().out
    assert not out["ring_covers_slice"] and "no table" in text
    assert "/device:TPU:0" not in out
    # ticks on another clock
    off = _ticks_over(modules, PROFILE_START + 50_000_000)
    gaps.report(SLICE, off, 8192, device)
    text = capsys.readouterr().out
    assert "the clocks do not agree to +- 1.000 ms" in text
    assert "no table" in text
    assert "idle time by host cause:" not in text


def test_overlapping_ticks_share_a_gap_by_who_opened_first():
    """A opened at 0 and closed at 100, B opened at 40 and closed at 160:
    [0, 100) is A's phases, [100, 160) B's, after that no tick."""
    clock = _Clock([])
    log = ticktrace.TickLog(clock=clock, counters=PerfCounters("synthetic"))
    a = _scripted_tick(log, clock, ticktrace.ENCODE_TICK, "osd.0", 0, 10,
                       [("fill", 10, 30), ("to_planar", 30, 60),
                        ("readback", 60, 90)], 90, 100)
    b = _scripted_tick(log, clock, ticktrace.ENCODE_TICK, "osd.1", 40, 50,
                       [("fill", 50, 80), ("to_planar", 80, 120),
                        ("readback", 120, 150)], 150, 160)
    timeline = gapjoin.host_timeline([b, a])
    assert timeline == [
        (0, 10, "executor_wait"), (10, 30, "fill"), (30, 60, "to_planar"),
        (60, 90, "readback"), (90, 100, "wake"),
        (100, 120, "to_planar"), (120, 150, "readback"),
        (150, 160, "wake")]
    ends = [s[1] for s in timeline]
    assert gapjoin.cut_gap((20, 200), timeline, ends) == [
        ("fill", 10), ("to_planar", 30), ("readback", 30), ("wake", 10),
        ("to_planar", 20), ("readback", 30), ("wake", 10),
        (gapjoin.NO_TICK, 40)]
    # one device event 35..45 and one 125..130, session 0..200: the first
    # lies in both windows and goes to A (opened first), the second to B
    modules = [("jit__planar_tiled(1)", 35, 10),
               ("jit__planar_tiled(2)", 125, 5)]
    ops = [("early", 1, 1)] + modules + [("late", 190, 5)]
    out = gapjoin.join(ops, modules, 0, 200, [a, b], slack_ns=0)
    assert (out["lead_ns"], out["traced_ns"], out["tail_ns"]) \
        == (1, 194, 5)
    assert out["idle_ns"] == 194 - 21
    assert out["idle_by_cause_ns"] == {
        "readback": 30 + 5 + 20, "to_planar": 5 + 15 + 20,
        gapjoin.NO_TICK: 30, "fill": 20, "wake": 10 + 10,
        "executor_wait": 8}
    assert out["causality"]["outside_every_tick"] == 0
    assert out["per_tick"]["ticks"] == 2
    assert out["per_tick"]["dispatch_to_device_start_ns"] \
        == ((35 - 30) + (125 - 80)) / 2
    assert out["per_tick"]["device_end_to_readback_return_ns"] \
        == ((90 - 45) + (150 - 130)) / 2


# ------------------------------------------- the benchmark's nine readers


NINE = {
    "tick_ms.write": 500.0,
    "tick_fill_share.write": 10.0,
    "tick_ingest_share.write": 20.0,
    "tick_readback_share.write": 30.0,
    "tick_slice_share.write": 5.0,
    "tick_crc_share.write": 25.0,
    "tick_handoff_ms.write": 2.0,
    "dispatches_per_tick.write": 5.0,
    "tick_overlap_share.write": 60.0,
}
# PR 27's, read the same way: every tick's crcs came from the device
TICK_READERS = {**NINE, "tick_crc_device_share.write": 100.0}


def _benchmark_cells():
    """The cells the benchmark lists, then those that wait under
    ``benchmark/pending/`` (loaded as their entries would list them)."""
    from tests._pending import waiting_cells

    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return [w["name"] for w in json.load(f)["workloads"]] + \
            waiting_cells()


@pytest.mark.parametrize("cell_name", _benchmark_cells())
def test_every_cell_loads_and_the_nine_tick_readers_read(cell_name, tmp_path):
    """A missing or disagreeing metric file fails here, on the CPU, and
    not on the driver's chip (``benchmark/tests/`` is not collected)."""
    from benchmark.harness import layers
    from benchmark.harness.loader import load_cell
    from tests._pending import root_of

    cell = load_cell(cell_name, root=root_of(cell_name, tmp_path))
    if "read" in cell.traffic_name:
        # a cell that only reads has no encode tick: its twelve readers
        # are the read path's, each with a file that agrees (the loader)
        assert not set(TICK_READERS) & set(cell.per_layer)
        assert len(cell.per_layer) == 12
        assert all(name.endswith(".read") for name in cell.per_layer)
        assert cell.end_to_end == ["setup_s", "read_MBps", "read_p95_ms"]
        return
    assert set(TICK_READERS) <= set(cell.per_layer)
    ticks = 200
    wall = 500_000_000 * ticks
    counters = {
        "ec_coalesced_ticks": ticks, "ec_coalesced_ops": 460,
        "ec_tick_wall_ns": wall,
        "ec_tick_fill_ns": wall * 0.10,
        "ec_tick_to_planar_ns": wall * 0.20,
        "ec_tick_dispatch_ns": wall * 0.001,
        "ec_tick_readback_ns": wall * 0.30,
        "ec_tick_slice_ns": wall * 0.05,
        "ec_tick_crc_ns": wall * 0.25,
        "ec_tick_handoff_ns": 2_000_000 * ticks,
        "ec_tick_device_calls": 5 * ticks,
        "ec_tick_crc_device_ticks": ticks,
        "ec_tick_any_active_ns": 40_000_000_000,
        "ec_tick_multi_active_ns": 24_000_000_000,
    }
    readings = layers.Readings(
        config=cell.config, device_kind="TPU v5 lite", attribution={},
        counters=counters, slice_counters={}, trace=None)
    for name, want in TICK_READERS.items():
        got = layers.read_metric(name, cell.per_layer[name], readings)
        assert got == pytest.approx(want), name
    # a program without the counters (the parent commit): nothing raises
    bare = layers.Readings(
        config=cell.config, device_kind="TPU v5 lite", attribution={},
        counters={}, slice_counters={}, trace=None)
    for name in TICK_READERS:
        assert layers.read_metric(name, cell.per_layer[name], bare) is None


def test_the_nine_entries_agree_with_their_files():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    entries = {m["name"]: m for m in spec["per_layer"]}
    # later PRs append behind them: in order and together, wherever
    names = list(entries)
    first = names.index(next(iter(TICK_READERS)))
    assert names[first:first + len(TICK_READERS)] == list(TICK_READERS)
    declared = set(KERNELS.dump()["device_kernels"])
    for name in TICK_READERS:
        entry = entries[name]
        assert entry["source"] == "program_counter"
        assert entry["moves"] == "write_MBps"
        assert entry["better"] == ("lower" if name in NINE else "higher")
        assert entry["workloads"] == ["k2m1_write_4m_t16",
                                      "k2m1_write_64k_t16",
                                      "k4m2_write_4m_t16",
                                      "k8m4_write_4m_t16",
                                      "lrc_k4m2l3_write_4m_t16",
                                      "shec_k6m4c3_write_4m_t16",
                                      "cauchy_k4m2_write_4m_t16"]
        path = os.path.join(ROOT, "benchmark", "layer_metrics",
                            name + ".json")
        with open(path, encoding="utf-8") as f:
            reader = json.load(f)
        assert reader["kind"] == "counter_ratio"
        # what the file reads is a counter the program declares
        assert reader["numerator"] in declared, name
        assert reader["denominator"] in declared | {"ec_coalesced_ticks"}


# ------------------------------------- names the benchmark's files match


def test_program_names_the_benchmark_matches_are_pinned():
    """The XLA module of a jitted function is ``jit_`` + its ``__name__``:
    ``planar_roofline.write.json`` matches ``jit__planar_tiled`` and
    ``trace/gapjoin.py`` counts the same events."""
    from ceph_tpu.ec import planar
    from ceph_tpu.ops import crc32c, gf8_pallas

    assert gf8_pallas._planar_tiled.__name__ == "_planar_tiled"
    # the chunk-crc program is another program: its device time is not
    # the encode matmul's (planar_roofline.write matches by substring)
    crc_program = "jit_" + crc32c._chunk_crcs_jit().__name__
    assert crc_program == "jit__chunk_crcs_planes"
    assert "jit__planar_tiled" not in crc_program
    assert planar._batch_to_planes_bitpack.__name__ \
        == "_batch_to_planes_bitpack"
    path = os.path.join(ROOT, "benchmark", "layer_metrics",
                        "planar_roofline.write.json")
    with open(path, encoding="utf-8") as f:
        assert "jit_" + gf8_pallas._planar_tiled.__name__ \
            in json.load(f)["patterns"]
    _ops, modules = _recorded_slice()
    assert {e[0].split("(")[0] for e in modules} == {
        "jit_" + gf8_pallas._planar_tiled.__name__,
        "jit_" + planar._batch_to_planes_bitpack.__name__}


def test_no_profiler_import_and_no_sync_under_the_program():
    """``jax.profiler`` stays the benchmark's (fact 1: the host tracer is
    off there, a TraceAnnotation would write nothing)."""
    import subprocess

    hits = subprocess.run(
        ["grep", "-rlE", r"jax\.profiler|TraceAnnotation",
         os.path.join(ROOT, "ceph_tpu"), "--include=*.py"],
        capture_output=True, text=True).stdout.split()
    assert hits == []
    for mod in ("ceph_tpu/trace/tick.py", "ceph_tpu/trace/gapjoin.py"):
        with open(os.path.join(ROOT, mod), encoding="utf-8") as f:
            src = f.read()
        assert "block_until_ready" not in src and "device_get" not in src
        assert "import jax" not in src
