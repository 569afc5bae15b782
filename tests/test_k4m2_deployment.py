"""The benchmark's wider deployments — the jerasure k=4 m=2 pool on 8
OSDs (``benchmark/configs/rados_k4m2_8osd.json``) and the ISA k=8 m=4
pool on a host's dozen (``rados_isa_k8m4_12osd.json``), one case each of
every served-against-reference test — against a plain reference, at
small size on the CPU with the device branches forced, and what lets
them serve without a stall: failure detection that survives a stalled
loop, a stop that is bounded, placement at a map change that does not
hold the loop (PR 33), the counters and the metric files that read them.

The reference: for what is served, name -> payload; for what is stored,
a scalar GF(2^8) Vandermonde encode written here from the field's
polynomial alone (no table, matrix or code of ``ceph_tpu``), with the
coding matrix of the golden vectors, and first held to the independent
C oracle's chunks itself.  Every comparison is exact.

Each cluster scenario runs under a bound of its own (``bounded``): a
regression fails, it does not hang the suite.
"""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from ceph_tpu.cluster import messages as M
from ceph_tpu.cluster.vstart import _fast_config, start_cluster
from ceph_tpu.ops import crc32c as crcmod
from ceph_tpu.utils import KERNELS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _deployment(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


DEPLOYMENTS = {name: _deployment(name)
               for name in ("rados_k4m2_8osd", "rados_isa_k8m4_12osd")}
DEPLOYMENT = DEPLOYMENTS["rados_k4m2_8osd"]     # the failure-detection cases
WIDE = DEPLOYMENTS["rados_isa_k8m4_12osd"]      # the placement cases
SIZES = {"4k": 4096, "64k+1": 65537, "1m": 1 << 20}
# the benchmark's cells that WRITE (they report the write readers), in the
# order it lists them; the one cell that reads waits under
# benchmark/pending/ for a ``benchmark`` PR (it brings end-to-end metrics)
# and goes behind these when it is listed (tests/_pending.py)
CELLS = ("k2m1_write_4m_t16", "k2m1_write_64k_t16", "k4m2_write_4m_t16",
         "k8m4_write_4m_t16", "lrc_k4m2l3_write_4m_t16",
         "shec_k6m4c3_write_4m_t16", "cauchy_k4m2_write_4m_t16")
READ_CELL = "k2m1_degraded_randread_4m_t16"


def bounded(coro, seconds):
    async def _run():
        return await asyncio.wait_for(coro, seconds)
    return asyncio.run(_run())


def counters():
    return dict(KERNELS.dump()["device_kernels"])


# ------------------------------------------------------ the plain reference

def _gf_mul_tables():
    """256 x 256 products in GF(2^8) modulo x^8+x^4+x^3+x^2+1 (0x11d),
    by shift and reduce."""
    table = np.zeros((256, 256), dtype=np.uint8)
    for a in range(256):
        for b in range(256):
            x, y, p = a, b, 0
            while y:
                if y & 1:
                    p ^= x
                x <<= 1
                if x & 0x100:
                    x ^= 0x11d
                y >>= 1
            table[a, b] = p
    return table


def _golden_case(profile, k, m):
    with open(os.path.join(ROOT, "tests", "golden", "ec_golden.jsonl"),
              encoding="utf-8") as f:
        for line in f:
            case = json.loads(line)
            if (case["plugin"], case["technique"], case["k"], case["m"],
                    case.get("w", 8), case["packetsize"]) == \
                    (profile["plugin"], profile["technique"], k, m, 8, 0):
                return case
    raise AssertionError(f"no golden vector for {profile}")


class Reference:
    def __init__(self, deployment):
        self.k, self.m = deployment["k"], deployment["m"]
        self.unit = deployment["stripe_unit"]
        self.case = _golden_case(deployment["ec_profile"], self.k, self.m)
        self.matrix = np.array(self.case["matrix"],
                               dtype=np.uint8).reshape(self.m, self.k)
        self.mul = _gf_mul_tables()

    def parity(self, chunks):
        """chunks: (k, n) bytes -> (m, n) parity bytes, scalar products
        looked up per byte and XORed."""
        out = np.zeros((self.m, chunks.shape[1]), dtype=np.uint8)
        for j in range(self.m):
            for c in range(self.k):
                out[j] ^= self.mul[self.matrix[j, c]][chunks[c]]
        return out

    def shards(self, payload: bytes):
        """The k+m shards as stored: the object zero-padded to whole
        stripes of k x unit, shard i = chunk i of every stripe in turn."""
        k, unit = self.k, self.unit
        stripes = -(-len(payload) // (k * unit))
        padded = np.zeros(stripes * k * unit, dtype=np.uint8)
        padded[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
        data = padded.reshape(stripes, k, unit).transpose(1, 0, 2) \
            .reshape(k, stripes * unit)
        return [bytes(r) for r in data] + \
            [bytes(r) for r in self.parity(data)]


@pytest.fixture(scope="module", params=list(DEPLOYMENTS))
def deployment(request):
    return DEPLOYMENTS[request.param]


@pytest.fixture(scope="module")
def reference(deployment):
    return Reference(deployment)


def test_reference_agrees_with_the_independent_oracle(reference):
    """The scalar encode above against gen.c's chunks for this profile:
    the reference is itself held to something outside the repo's code."""
    from test_ec_golden import _fnv1a64, _lcg_bytes

    case = reference.case
    payload = _lcg_bytes(case["seed"], case["object_size"])
    n = case["chunk_size"]
    data = np.frombuffer(payload, dtype=np.uint8).reshape(reference.k, n)
    chunks = [bytes(r) for r in data] + \
        [bytes(r) for r in reference.parity(data)]
    for got, want in zip(chunks, case["chunks"]):
        assert _fnv1a64(got) == want["fnv1a64"]
        assert got[:16].hex() == want["head"]


# ---------------------------------------------- served against the reference

async def _serve(deployment):
    """One cluster, everything the cases below look at."""
    cluster = await start_cluster(deployment["osds"])
    out = {"stored": {}, "reads": {}, "holders": {}}
    try:
        client = await cluster.client()
        pool = await client.pool_create(
            "pool", deployment["pool_type"], pg_num=deployment["pg_num"],
            ec_profile=dict(deployment["ec_profile"]))
        io = client.ioctx(pool)
        rng = np.random.default_rng(28)
        payloads = {
            f"{label}_{i}": rng.integers(0, 256, size,
                                         dtype=np.uint8).tobytes()
            for label, size in SIZES.items() for i in range(3)}
        out["payloads"] = payloads
        before = counters()
        await asyncio.gather(*(io.write_full(n, d, timeout=120)
                               for n, d in payloads.items()))
        out["write_grew"] = {k: v - before.get(k, 0)
                             for k, v in counters().items()
                             if isinstance(v, (int, float))}

        def acting(name):
            pgid = client.objecter.object_pgid(pool, name)
            return pgid, client.objecter.osdmap.pg_to_up_acting_osds(
                pgid)[2]

        for name in payloads:
            pgid, holders = acting(name)
            out["holders"][name] = list(holders)
            coll = f"pg_{pgid.pool}_{pgid.seed}"
            rows = []
            for shard, osd in enumerate(holders):
                store = cluster.osds[osd].store
                rows.append((
                    int(store.getattr(coll, name, "shard")), shard,
                    bytes(store.read(coll, name)),
                    int(store.getattr(coll, name, "hinfo_crc"))))
            out["stored"][name] = rows

        async def read_all(state):
            got = await asyncio.gather(*(io.read(n, timeout=120)
                                         for n in payloads))
            out["reads"][state] = dict(zip(payloads, got))

        await read_all("healthy")
        # two holders of DATA shards of one 1 MiB object: with the first
        # down its reads decode from one parity row, with both down from
        # the two (m >= 2: what k2m1 cannot show)
        _, holders = acting("1m_0")
        victims = [holders[1], holders[2]]
        before = counters()
        await cluster.kill_osd(victims[0])
        await cluster.wait_down(victims[0])
        await read_all("one_down")
        await cluster.kill_osd(victims[1])
        await cluster.wait_down(victims[1])
        await read_all("two_down")
        out["decode_ticks"] = counters().get(
            "ec_coalesced_read_ticks", 0) - before.get(
            "ec_coalesced_read_ticks", 0)
        out["two_down_objects"] = sum(
            1 for n in payloads
            if len(set(out["holders"][n]) & set(victims)) == 2)
    finally:
        await cluster.stop()
    return out


@pytest.fixture(scope="module")
def served(deployment):
    from ceph_tpu.ec import stripe

    sound = stripe._host_engine_ok
    stripe._host_engine_ok = lambda codec: False    # the device branches
    before = counters()
    try:
        out = bounded(_serve(deployment), 420)
    finally:
        stripe._host_engine_ok = sound
    out["host_engine_calls"] = sum(
        counters().get(c, 0) - before.get(c, 0)
        for c in ("ec_host_matmul_calls", "ec_host_planar_matmul_calls"))
    return out


@pytest.mark.parametrize("label", list(SIZES))
def test_stored_shards_equal_the_scalar_reference(served, reference, label):
    """Every stored shard, parity above all, is the reference's bytes,
    on the holder the acting set names for it."""
    for name, payload in served["payloads"].items():
        if not name.startswith(label + "_"):
            continue
        want = reference.shards(payload)
        rows = served["stored"][name]
        assert len(rows) == reference.k + reference.m
        for shard_attr, shard, stored, _crc in rows:
            assert shard_attr == shard
            assert stored == want[shard], (name, shard)


@pytest.mark.parametrize("label", list(SIZES))
def test_stored_shard_crcs_equal_crc32c_of_the_reference(served, reference,
                                                         label):
    """The crcs the device's chunk-crc program made and the host folded
    are crc32c of the reference's shard bytes."""
    for name, payload in served["payloads"].items():
        if not name.startswith(label + "_"):
            continue
        want = reference.shards(payload)
        for _attr, shard, _stored, crc in served["stored"][name]:
            assert crc == crcmod.crc32c(0xFFFFFFFF, want[shard]), \
                    (name, shard)


@pytest.mark.parametrize("state", ["healthy", "one_down", "two_down"])
def test_reads_equal_the_payload(served, state):
    got = served["reads"][state]
    for name, payload in served["payloads"].items():
        assert got[name] == payload, (state, name)


def test_the_device_engine_served_and_decoded(served):
    grew = served["write_grew"]
    assert grew.get("planar_matmul_calls", 0) > 0
    assert grew.get("ec_coalesced_ticks", 0) > 0
    assert grew.get("ec_tick_crc_device_ticks", 0) \
        == grew["ec_coalesced_ticks"]
    assert served["decode_ticks"] > 0
    assert served["two_down_objects"] >= 1
    assert served["host_engine_calls"] == 0
    # heartbeats were answered while it served, on their own lane
    assert grew.get("osd_hb_replies", 0) > 0
    assert grew.get("osd_hb_rtt_ns", 0) > 0
    assert grew.get("osd_hb_failure_reports", 0) == 0


# ------------------------------------------------- failure detection, stop

def _stall(seconds):
    # a deliberate loop stall: the duration IS the stimulus
    # graftlint: ignore[asyncio-blocking] graftlint: ignore[fixed-sleep-in-tests]
    time.sleep(seconds)


def _mon_perf(cluster, name):
    return next(iter(cluster.mon.perf.dump().values())).get(name, 0)


def test_no_false_down_when_the_loop_stalls_past_the_old_grace():
    """The product configuration under loop stalls of 2 s (the old grace
    was 1.5 s, one reporter, and a beacon grace of 1.5 s at the mon):
    nobody is reported, nobody is marked down, the map does not move."""
    async def scenario():
        cluster = await start_cluster(4)
        try:
            client = await cluster.client()
            pool = await client.pool_create("p", "replicated", pg_num=4,
                                            size=3)
            io = client.ioctx(pool)
            await io.write_full("o", b"x" * 4096)
            # the pool's log lines land with the mon's next ticks: the
            # epoch is read once it has stood still for half a second
            epoch, since = cluster.mon.osdmap.epoch, time.monotonic()
            while time.monotonic() - since < 0.5:
                await asyncio.sleep(0.05)
                if cluster.mon.osdmap.epoch != epoch:
                    epoch, since = cluster.mon.osdmap.epoch, \
                        time.monotonic()
            before = counters()
            for _ in range(3):
                _stall(2.0)
                # the heartbeat loops and the mon's tick run between the
                # stalls; then three heartbeat rounds more: a false
                # report would be made and counted inside them
                # graftlint: ignore[fixed-sleep-in-tests]
                await asyncio.sleep(0.3)
            # graftlint: ignore[fixed-sleep-in-tests]
            await asyncio.sleep(1.5)
            assert await io.read("o") == b"x" * 4096
            assert _mon_perf(cluster, "mon_osd_marked_down") == 0
            assert cluster.mon.osdmap.epoch == epoch
            assert all(cluster.mon.osdmap.osd_up[:4])
            for o, osd in cluster.osds.items():
                assert osd.perf.dump()[f"osd.{o}"].get(
                    "osd_failure_reports", 0) == 0
            grew = counters()
            assert grew.get("osd_hb_failure_reports", 0) \
                == before.get("osd_hb_failure_reports", 0)
            # and heartbeats went on being answered
            assert grew.get("osd_hb_replies", 0) \
                > before.get("osd_hb_replies", 0)
        finally:
            await cluster.stop()

    bounded(scenario(), 90)


def test_a_killed_osd_is_marked_down_by_its_peers_at_once():
    """Really dead: its peers' pings are refused, two of them say so,
    and the mon marks it down inside the benchmark's ``wait_down``
    (20 s) with room: no grace has to run out."""
    async def scenario():
        cluster = await start_cluster(DEPLOYMENT["osds"])
        try:
            before = counters()
            t0 = time.monotonic()
            await cluster.kill_osd(5)
            await cluster.wait_down(5, timeout=20.0)
            took = time.monotonic() - t0
            cfg = cluster.config
            assert took < min(cfg.osd_heartbeat_grace,
                              cfg.mon_osd_beacon_grace) / 2, took
            # the line reaches the log with the mon's next tick
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                lines = [str(e) for e in cluster.mon.cluster_log]
                if any("osd.5" in ln for ln in lines[8:]):
                    break
                await asyncio.sleep(0.05)
            assert any("osd.5 failed (" in ln and "reporters) -> marked "
                       "down" in ln for ln in lines), lines[-5:]
            assert not any("beacon grace expired" in ln for ln in lines)
            assert _mon_perf(cluster, "mon_osd_marked_down") == 1
            assert counters().get("osd_hb_failure_reports", 0) \
                - before.get("osd_hb_failure_reports", 0) >= 2
            # not marked out: its PGs stay degraded, nothing remaps
            assert cluster.mon.osdmap.osd_weight[5] > 0
        finally:
            await cluster.stop()

    bounded(scenario(), 90)


def test_a_hung_peer_is_reported_after_the_grace_with_its_evidence():
    """A peer that keeps its socket and answers nothing (no refusal to
    go by) is reported once a ping has waited out the grace in force —
    set short by THIS test, as a test that needs sub-second detection
    does — by two reporters, and each report leaves a flight-recorder
    event that explains itself."""
    async def scenario():
        cfg = _fast_config()
        cfg.osd_heartbeat_interval = 0.1
        cfg.osd_heartbeat_grace = 0.6
        cfg.blackbox_enabled = 1
        cluster = await start_cluster(4, config=cfg)
        try:
            before = counters()
            # first a peer that is slow, not dead: osd.1 answers the first
            # ping of each connection 0.4 s late, past half the grace in
            # force and inside it: late replies, and no report
            slow, seen = cluster.osds[1], set()
            sound = slow.ms_dispatch

            async def slow_once(conn, msg):
                if isinstance(msg, M.MPing) and not msg.reply \
                        and id(conn) not in seen:
                    seen.add(id(conn))
                    # the delay IS the stimulus
                    # graftlint: ignore[fixed-sleep-in-tests]
                    await asyncio.sleep(0.4)
                return await sound(conn, msg)

            slow.ms_dispatch = slow_once
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                grew = counters()
                if grew.get("osd_hb_late_replies", 0) \
                        > before.get("osd_hb_late_replies", 0):
                    break
                await asyncio.sleep(0.05)
            assert grew.get("osd_hb_late_replies", 0) \
                > before.get("osd_hb_late_replies", 0)
            assert grew.get("osd_hb_failure_reports", 0) \
                == before.get("osd_hb_failure_reports", 0)
            # then one that keeps its sockets and handles nothing more
            hung = cluster.osds[3]
            hung._stopped = True
            await cluster.wait_down(3, timeout=20.0)
            assert counters()["osd_hb_failure_reports"] \
                - before.get("osd_hb_failure_reports", 0) >= 2
            events = [e for o in (0, 1, 2)
                      for e in cluster.osds[o].flight.events()
                      if e[2] == "failure_report"]
            assert len(events) >= 2
            for _seq, _ts, _kind, data in events:
                assert data["peer"] == 3 and data["why"] == "grace"
                assert data["age"] > data["grace"] == 0.6
                assert "loop_lag_window_max" in data
        finally:
            await cluster.stop()

    bounded(scenario(), 90)


def test_a_withdrawn_report_does_not_pair_up_with_a_later_one():
    """One reporter is never enough among three or more OSDs, and a
    report its reporter withdrew (the peer answered again) does not wait
    at the mon for a second stray one."""
    async def scenario():
        cluster = await start_cluster(4)
        try:
            mon = cluster.mon
            await mon._handle_failure(M.MOSDFailure(failed_osd=2,
                                                    reporter=0))
            assert mon.failure_reports[2] == {0}
            await mon._handle_failure(M.MOSDFailure(
                failed_osd=2, reporter=0, alive=True))
            await mon._handle_failure(M.MOSDFailure(failed_osd=2,
                                                    reporter=1))
            # past the mon's failure coalesce window and its tick: a
            # markdown would have been committed by then
            # graftlint: ignore[fixed-sleep-in-tests]
            await asyncio.sleep(0.5)
            assert mon.osdmap.osd_up[2]
            assert _mon_perf(cluster, "mon_osd_marked_down") == 0
        finally:
            await cluster.stop()

    bounded(scenario(), 60)


def test_cluster_stop_is_bounded_with_a_peer_connection_wedged():
    """An OSD that has stopped reading one client connection, with
    megabytes queued towards it: a graceful close would wait for that
    buffer to flush, forever (``wait_closed`` in ``messenger.shutdown``:
    three chip runs ended so).  ``Cluster.stop`` returns all the same."""
    async def scenario():
        cluster = await start_cluster(3)
        client = await cluster.client()
        pool = await client.pool_create("p", "replicated", pg_num=4,
                                        size=3)
        await client.ioctx(pool).write_full("o", b"x" * 4096)
        wedged = 0
        for osd in cluster.osds.values():
            for conn in osd.messenger._accepted:
                if conn.peer is not None and conn.peer.type == "client" \
                        and not conn.closed:
                    conn.stream.transport.pause_reading()
                    wedged += 1
        assert wedged
        for conn in client.objecter.messenger._out.values():
            if conn.peer_addr in {tuple(o.messenger.my_addr)
                                  for o in cluster.osds.values()}:
                conn.stream.write([b"\0" * (32 << 20)])
        t0 = time.monotonic()
        await cluster.stop()
        return time.monotonic() - t0

    took = bounded(scenario(), 60)
    assert took < 20.0, took


# ------------------------------------- the objecter: no resend on a timer

def test_a_slow_op_is_not_sent_again_while_its_target_stands():
    """An op whose reply takes longer than ``osd_client_op_timeout + 2``
    is waited for, not sent again: the target is up in the client's map
    and the connection that carried the op is alive.  One send, one
    execution, one acknowledgement."""
    async def scenario():
        cfg = _fast_config()
        cfg.osd_client_op_timeout = 0.2     # a look every 2.2 s
        cluster = await start_cluster(3, config=cfg)
        try:
            client = await cluster.client()
            pool = await client.pool_create("p", "replicated", pg_num=4,
                                            size=3)
            io = client.ioctx(pool)
            await io.write_full("warm", b"w")
            sends = []
            sound_send = client.objecter._send_op

            async def counted(msg, addr):
                sends.append(msg.oid)
                return await sound_send(msg, addr)

            client.objecter._send_op = counted
            served = []
            for osd in cluster.osds.values():
                sound = osd._handle_client_op

                async def slow(conn, msg, sound=sound):
                    served.append(msg.oid)
                    # two looks of the client (2.2 s each) pass: the
                    # duration IS the stimulus
                    # graftlint: ignore[fixed-sleep-in-tests]
                    await asyncio.sleep(5.0)
                    return await sound(conn, msg)

                osd._handle_client_op = slow
            await io.write_full("slow", b"s" * 100)
            assert sends.count("slow") == 1
            assert served.count("slow") == 1
            assert await io.read("slow") == b"s" * 100
        finally:
            await cluster.stop()

    bounded(scenario(), 90)


# ------------------------------------- placement at a map change (PR 33)
#
# The wide pool takes 12 of 12 hosts ``indep``: ~46 bucket draws a PG on
# the scalar chain, 18 ms a PG, and before PR 33 every OSD walked every
# PG of every pool at every epoch on the one loop: a pool create of 32
# PGs took 9 s and a killed OSD was marked down after 5.

def _wide_map(pg_num):
    """The map a 12-OSD vstart cluster holds after the wide pool's
    create, built the way ``start_cluster`` and the mon build it."""
    from ceph_tpu.crush.types import (
        RULE_CHOOSELEAF_INDEP, RULE_EMIT, RULE_SET_CHOOSELEAF_TRIES,
        RULE_SET_CHOOSE_TRIES, RULE_TAKE, Rule, build_hierarchy)
    from ceph_tpu.osdmap.osdmap import OSDMap, PGPool, POOL_TYPE_ERASURE

    n = WIDE["osds"]
    cmap, _ = build_hierarchy(n, 1, numrep=3)
    root = max(cmap.buckets.values(), key=lambda b: b.type).id
    rule = cmap.add_rule(Rule(steps=[
        (RULE_SET_CHOOSELEAF_TRIES, 5, 0), (RULE_SET_CHOOSE_TRIES, 100, 0),
        (RULE_TAKE, root, 0), (RULE_CHOOSELEAF_INDEP, n, 1),
        (RULE_EMIT, 0, 0)], type=POOL_TYPE_ERASURE))
    m = OSDMap(cmap, max_osd=n)
    m.add_pool(PGPool(pool_id=1, type=POOL_TYPE_ERASURE, size=n,
                      min_size=WIDE["k"], pg_num=pg_num, pgp_num=pg_num,
                      crush_rule=rule))
    return m


def _one_down(m):
    m.mark_down(5)


def _one_out(m):
    m.mark_out(7)


def _pg_temp(m):
    from ceph_tpu.osdmap.osdmap import PGid

    m.mark_out(3)
    m.pg_temp[PGid(1, 4)] = [11, 10, 9, 8, 7, 6, 5, 4, 3, 2, 1, 0]
    m.primary_temp[PGid(1, 9)] = 6


@pytest.mark.parametrize("pg_num", [32, 128])
@pytest.mark.parametrize("change", [None, _one_down, _one_out, _pg_temp],
                         ids=["healthy", "one_down", "one_out", "pg_temp"])
def test_the_engine_an_advance_picks_equals_the_scalar_chain(change,
                                                             pg_num):
    """``placement_snapshot`` as ``_advance_pgs`` calls it — the host
    walk for this pool — against ``pg_to_up_acting_osds``, the scalar
    reference, seed for seed; and with upstream's tries every slot of
    every PG is filled while all twelve are in."""
    from ceph_tpu.crush.types import CRUSH_ITEM_NONE
    from ceph_tpu.osdmap.osdmap import (PGid, _norm_placement,
                                        placement_snapshot)

    m = _wide_map(pg_num)
    if change:
        change(m)
    assert m.placement_engine(1) == "host"
    before = m.scalar_walks
    snap = placement_snapshot(m, 1)
    assert snap.mode == "host"
    # the walk itself asked the scalar chain only for the temp seeds
    assert m.scalar_walks - before == len(snap.resolved) <= 2
    for seed in range(pg_num):
        want = _norm_placement(12, False,
                               *m.pg_to_up_acting_osds(PGid(1, seed)))
        assert snap.resolve(seed) == want, seed
    if change is None:
        assert not (snap.up == CRUSH_ITEM_NONE).any()
        assert sorted(set(snap.up.reshape(-1).tolist())) == list(range(12))


def test_a_walk_is_kept_until_what_crush_reads_changes():
    """Marking an OSD down, a pg_temp or another pool's arrival ask
    CRUSH nothing new: the pool's raw walk is reused (and an unrelated
    change leaves even its ``placement_key`` alone); a weight, a bucket
    or the pool's own pg_num walk again."""
    import copy

    from ceph_tpu.osdmap.osdmap import PGid, PGPool

    m = _wide_map(32)
    key = m.placement_key(1)
    m.pool_mapping(1)
    walk = m._walks[1]
    m.add_pool(PGPool(pool_id=2, size=3, pg_num=8, pgp_num=8,
                      crush_rule=0))
    m.pg_temp[PGid(2, 1)] = [1, 2, 3]
    m.flags.add("nearfull")
    m.osd_addrs[3] = ("127.0.0.1", 1)
    assert m.placement_key(1) == key
    m.mark_down(5)
    m.pg_temp[PGid(1, 4)] = list(range(12))
    assert m.placement_key(1) != key
    m.pool_mapping(1)
    assert m._walks[1] is walk
    for change in (lambda x: x.mark_out(7),
                   lambda x: x.invalidate_mappers(),
                   lambda x: setattr(x.pools[1], "pg_num", 64)):
        m2 = copy.deepcopy(m)
        m2._walks = dict(m._walks)
        change(m2)
        assert m2.placement_key(1) != m.placement_key(1)
        m2.pool_mapping(1)
        assert m2._walks[1] is not walk


def _map_counters():
    return {k: v for k, v in counters().items()
            if k.startswith("osd_map_")}


def test_an_epoch_walks_only_the_pools_it_can_have_moved():
    """The test that COUNTS.  On the wide deployment: the pool's create
    resolves each PG once an OSD, by the host walk, with no scalar
    ``do_rule``; an epoch that adds an unrelated pool resolves 0 PGs of
    the wide pool; an epoch that marks an OSD down costs each survivor
    at most one resolution a PG, and again no scalar walk of the wide
    pool; the kill is seen within a second or two, not five."""
    n, pg_num = WIDE["osds"], WIDE["pg_num"]

    async def scenario():
        cluster = await start_cluster(n)
        try:
            client = await cluster.client()
            c0 = _map_counters()
            t0 = time.monotonic()
            wide = await client.pool_create(
                "wide", "erasure", pg_num=pg_num,
                ec_profile=dict(WIDE["ec_profile"]))
            create_s = time.monotonic() - t0
            io = client.ioctx(wide)
            await io.write_full("a", b"x" * 65536)
            c1 = _map_counters()
            snaps = {o: osd._placement_cache[wide]
                     for o, osd in cluster.osds.items()}
            assert {s.mode for s in snaps.values()} == {"host"}
            assert c1["osd_map_pgs_resolved"] \
                - c0.get("osd_map_pgs_resolved", 0) == n * pg_num
            assert c1.get("osd_map_scalar_walks", 0) \
                == c0.get("osd_map_scalar_walks", 0)
            assert c1["osd_map_advances"] > c0.get("osd_map_advances", 0)
            assert c1["osd_map_advance_ns"] > c0.get("osd_map_advance_ns",
                                                     0)

            other = await client.pool_create("other", "replicated",
                                             pg_num=8, size=3)
            await client.ioctx(other).write_full("b", b"y" * 4096)
            c2 = _map_counters()
            # the wide pool's snapshot is the very object it was
            for o, osd in cluster.osds.items():
                assert osd._placement_cache[wide] is snaps[o]
            assert c2["osd_map_pgs_resolved"] \
                - c1["osd_map_pgs_resolved"] == n * 8

            t0 = time.monotonic()
            await cluster.kill_osd(1)
            await cluster.wait_down(1)
            down_s = time.monotonic() - t0
            deadline = time.monotonic() + 10
            while any(osd.osdmap.epoch < cluster.mon.osdmap.epoch
                      for o, osd in cluster.osds.items() if o != 1):
                assert time.monotonic() < deadline
                await asyncio.sleep(0.05)   # the epoch reaches every OSD
            c3 = _map_counters()
            walked = c3["osd_map_pgs_resolved"] - c2["osd_map_pgs_resolved"]
            assert 0 < walked <= (n - 1) * (pg_num + 8)
            # the 8-PG pool is the scalar chain's; the wide one asked
            # CRUSH nothing (an OSD going down moves no weight)
            assert c3.get("osd_map_scalar_walks", 0) \
                - c2.get("osd_map_scalar_walks", 0) <= (n - 1) * 8
            assert await io.read("a") == b"x" * 65536
            return create_s, down_s
        finally:
            await cluster.stop()

    create_s, down_s = bounded(scenario(), 120)
    # 9.1 s and 4.9 s before PR 33 on the CPU dev host (0.5 and 0.6
    # after); generous, so that only a return of the walk fails
    assert create_s < 4.0, create_s
    assert down_s < 3.0, down_s


def test_a_dozen_osds_with_128_pgs_create_and_serve_under_a_wall_bound():
    """Upstream's ~100 PGs an OSD for this pool: 39 s of placement
    before PR 33 (past the heartbeat grace), 0.6 s after on the CPU dev
    host.  The bound is generous: a return of the per-PG walk fails
    loudly, not slowly."""
    async def scenario():
        cluster = await start_cluster(WIDE["osds"])
        try:
            client = await cluster.client()
            t0 = time.monotonic()
            pool = await client.pool_create(
                "wide", "erasure", pg_num=128,
                ec_profile=dict(WIDE["ec_profile"]))
            create_s = time.monotonic() - t0
            io = client.ioctx(pool)
            await io.write_full("a", b"z" * 65536, timeout=60)
            assert await io.read("a") == b"z" * 65536
            return create_s
        finally:
            await cluster.stop()

    assert bounded(scenario(), 150) < 15.0


def test_the_first_tick_of_a_size_compiles_a_full_ticks_buckets(monkeypatch):
    """A burst of 16 over a dozen OSDs warms the buckets of 1-4 objects;
    a window later coalesces 5 (PR 33, on the chip: 1 run in 15 compiled
    for 3 s inside a served op).  Told how many ops its caller
    coalesces, the first tick of ops of a size runs the buckets a full
    tick of them would meet, on zeros, on a thread of its own, off the
    counters, once, and never past the largest batch a tick has run."""
    import threading

    from ceph_tpu.ec import factory, stripe

    monkeypatch.setattr(stripe, "_host_engine_ok", lambda codec: False)
    monkeypatch.setattr(stripe, "_WARM_BUCKETS", set())
    monkeypatch.setattr(stripe, "_WARM_SIZES", set())
    chains, done, run = [], threading.Event(), stripe._warm_buckets

    def spy(codec, sinfo, shape, chain, crcs):
        met = {b for s, b in stripe._WARM_BUCKETS if s == shape}
        run(codec, sinfo, shape, chain, crcs)
        chains.append((chain, sorted(met)))
        done.set()

    monkeypatch.setattr(stripe, "_warm_buckets", spy)
    codec = factory(dict(WIDE["ec_profile"]))
    sinfo = stripe.StripeInfo(WIDE["k"], WIDE["stripe_unit"])

    def tick(stripes, ops=1, max_ops=4):
        done.clear()
        before = counters().get("planar_matmul_calls", 0)
        out = stripe.encode_planes_multi(
            codec, sinfo, [bytes(stripes * sinfo.stripe_width)] * ops,
            [True] * ops, max_ops)
        assert len(out) == ops and out[0][0].shape \
            == (12, 8, stripes * sinfo.chunk_size // 8)
        return before

    before = tick(2)            # ops of 2 stripes, up to 4 a tick: 2, 4, 8
    assert done.wait(120)
    assert chains == [([2, 4, 8], [2])]     # 2 was the tick's own
    assert {b for _s, b in stripe._WARM_BUCKETS} == {2, 4, 8}
    # the tick's matmul; the warm's are off the counters
    assert counters()["planar_matmul_calls"] - before == 1
    tick(2)                     # the size is known: nothing new,
    tick(2, ops=3)              # whatever the tick's own bucket
    assert not done.wait(0.3) and len(chains) == 1
    tick(16)                    # another size: its own chain, once
    assert done.wait(120) and chains[1][0] == [16, 32, 64]
    monkeypatch.setattr(stripe, "_WARM_MAX_BYTES", 256 * sinfo.stripe_width)
    tick(128)                   # 512 would pass the largest batch
    assert done.wait(120) and chains[2][0] == [128, 256]
    done.clear()                # a caller that names no cap warms nothing
    stripe.encode_planes_multi(codec, sinfo, [bytes(1024 * 32768)], [True])
    assert not done.wait(0.3) and len(chains) == 3


def test_msgr_frames_counts_one_a_message():
    from ceph_tpu.cluster import messenger

    before = counters().get("msgr_frames", 0)
    messenger._encode(M.MPing(stamp=1.0))
    messenger._encode(M.MPing(stamp=2.0, reply=True))
    assert counters()["msgr_frames"] - before == 2


# ----------------------------------------------------- the metric readers

HB_READERS = {"hb_rtt_ms.write": 2.5, "hb_late_share.write": 0.5}


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_hb_metric_files_read_the_hand_worked_values(cell_name):
    """4000 replies whose round trips sum to 10 s, 20 of them late:
    2.5 ms and 0.5 %, through the accepted ``counter_ratio`` reader; a
    program without the counters (the parent commit) reads nothing and
    nothing raises."""
    from benchmark.harness import layers
    from benchmark.harness.loader import load_cell

    cell = load_cell(cell_name)
    growth = {"osd_hb_replies": 4000, "osd_hb_rtt_ns": 10_000_000_000,
              "osd_hb_late_replies": 20}
    for readings, want in (
            (layers.Readings(config=cell.config, device_kind="TPU v5 lite",
                             attribution={}, counters=growth,
                             slice_counters={}, trace=None), HB_READERS),
            (layers.Readings(config=cell.config, device_kind="TPU v5 lite",
                             attribution={}, counters={},
                             slice_counters={}, trace=None),
             dict.fromkeys(HB_READERS))):
        for name, value in want.items():
            got = layers.read_metric(name, cell.per_layer[name], readings)
            assert got == (pytest.approx(value) if value is not None
                           else None), name


SHAPE_READERS = {"frames_per_op.write": 46.0, "stack_groups.write": 2.0}


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_shape_metric_files_read_the_hand_worked_values(cell_name):
    """1400 ops coalesced while the messengers framed 64400 messages: 46
    frames an op; 1200 planar matmul calls of 2 K-stacking groups each:
    2.0 — through the accepted ``counter_ratio`` reader.  A program
    without ``msgr_frames`` (the parent commit) raises nothing: that
    reader leaves a metric out only when its DENOMINATOR did not grow,
    so it reads 0.0 there (PERF.md section 3 says so); with no op
    coalesced it reads nothing."""
    from benchmark.harness import layers
    from benchmark.harness.loader import load_cell

    cell = load_cell(cell_name)
    growth = {"msgr_frames": 64400, "ec_coalesced_ops": 1400,
              "planar_stack_groups": 2400, "planar_matmul_calls": 1200}
    parent = {k: v for k, v in growth.items() if k != "msgr_frames"}
    for counters_, want in (
            (growth, SHAPE_READERS),
            (parent, {"frames_per_op.write": 0.0,
                      "stack_groups.write": 2.0}),
            ({}, dict.fromkeys(SHAPE_READERS))):
        readings = layers.Readings(
            config=cell.config, device_kind="TPU v5 lite", attribution={},
            counters=counters_, slice_counters={}, trace=None)
        for name, value in want.items():
            got = layers.read_metric(name, cell.per_layer[name], readings)
            assert got == (pytest.approx(value) if value is not None
                           else None), name


def test_a_write_burst_carries_its_acks():
    """The counter twin of ``ack_carried_share.write``: a burst of k=4
    m=2 writes on the deployment's 8 OSDs ends with at least four fifths
    of the session frames' acks carried by the frames that went back
    anyway (the sub-write's commit reply, the op's reply, the next
    sub-write), and every write reads back.  A change that brings the
    ack frames back fails here, not only in a metric."""
    async def scenario():
        cluster = await start_cluster(DEPLOYMENT["osds"],
                                      config=_fast_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "pool", DEPLOYMENT["pool_type"], pg_num=32,
                ec_profile=dict(DEPLOYMENT["ec_profile"]))
            io = client.ioctx(pool)
            rng = np.random.default_rng(35)
            payloads = {f"burst_{i}": rng.integers(
                0, 256, 65537, dtype=np.uint8).tobytes()
                for i in range(96)}
            before = counters()
            names = iter(payloads)

            async def caller():
                for name in names:
                    await io.write_full(name, payloads[name], timeout=120)

            await asyncio.gather(*(caller() for _ in range(16)))
            grew = {k: counters().get(k, 0) - before.get(k, 0)
                    for k in ("msgr_acks_owed", "msgr_acks_carried",
                              "msgr_frames", "ec_coalesced_ops")}
            got = await asyncio.gather(*(io.read(n, timeout=120)
                                         for n in payloads))
            assert dict(zip(payloads, got)) == payloads
            return grew
        finally:
            await cluster.stop()

    grew = bounded(scenario(), 240)
    assert grew["ec_coalesced_ops"] == 96
    # an op is its own frame, its reply, five sub-writes and their
    # commits (fewer where the coalescers batched them)
    assert grew["msgr_acks_owed"] >= 96 * 4
    assert grew["msgr_acks_carried"] >= 0.8 * grew["msgr_acks_owed"], grew


def test_every_cell_of_the_benchmark_loads_through_the_loader(tmp_path):
    """ROADMAP C10: every cell's files exist and agree with their
    entries (the loader raises otherwise), every name in a ``workloads``
    list is a cell, every cell reports ``setup_s``, another end-to-end
    metric and a per-layer metric, and each per-layer metric moves an
    end-to-end metric its cells report."""
    from benchmark.harness.loader import load_cell

    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    cells = [w["name"] for w in spec["workloads"]]
    from tests._pending import root_of, waiting_cells
    assert tuple(cells[:7]) == CELLS
    assert cells[7:] + waiting_cells() == [READ_CELL]
    assert len(spec["configs"]) == 6
    # the read cell, as its pending entries list it: PR 46's two
    # end-to-end metrics and twelve readers, behind every cell that is
    read = load_cell(READ_CELL, root=root_of(READ_CELL, tmp_path))
    assert read.end_to_end == ["setup_s", "read_MBps", "read_p95_ms"]
    assert len(read.per_layer) == 12
    assert all(name.endswith(".read") for name in read.per_layer)
    # every cell that writes reports every write reader
    writers = [load_cell(name) for name in CELLS]
    # (but one: the reader whose two counters only a shard that reaches
    # the store's pool grows reports nothing where nothing grew, so it
    # lists its own cells, and those are who reports it: PR 51)
    listed = {m["name"]: m["workloads"] for m in spec["per_layer"]
              if m["name"] == "store_pool_calls_per_spare.write"}
    assert all(set(c.per_layer) == set(writers[0].per_layer)
               - {name for name, its in listed.items() if c.name not in its}
               for c in writers)
    assert set(listed) <= set(writers[0].per_layer)
    assert all(name.endswith(".write") for name in writers[0].per_layer)
    configs = {c["name"]: c for c in spec["configs"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for metric in spec["end_to_end"] + spec["per_layer"]:
        for name in metric.get("workloads", ()):
            assert name in cells, (metric["name"], name)
    for w in spec["workloads"]:
        cell = load_cell(w["name"])
        assert cell.config_name in configs
        assert os.path.join("benchmark", "configs",
                            cell.config_name + ".json") \
            == configs[cell.config_name]["file"]
        assert set(configs[cell.config_name]["reduced"]) \
            == set(cell.config["reduced"])
        assert cell.config["osds"] >= cell.config["k"] + cell.config["m"]
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
        for metric in spec["per_layer"]:
            if w["name"] in metric.get("workloads", cells):
                assert metric["name"] in cell.per_layer
                assert metric["moves"] in cell.end_to_end
                assert metric["moves"] in e2e
    # the wider deployments: the accepted traffic file on their own
    # config, one chip, every per-layer metric of the first cell, and
    # the counter metrics PR 28 and PR 33 brought in all four cells
    old = load_cell("k2m1_write_4m_t16")
    for name, config in (("k4m2_write_4m_t16", "rados_k4m2_8osd"),
                         ("k8m4_write_4m_t16", "rados_isa_k8m4_12osd")):
        new = load_cell(name)
        assert (new.config_name, new.traffic_name, new.chips) \
            == (config, "write_4m_t16", 1)
        assert set(new.per_layer) == set(old.per_layer)
        assert new.traffic == old.traffic
    for readers, layer_moves in (
            (HB_READERS, {"hb_rtt_ms.write": ("wire", "write_p95_ms",
                                              "lower"),
                          "hb_late_share.write": ("wire", "write_p95_ms",
                                                  "lower")}),
            (SHAPE_READERS, {"frames_per_op.write": ("wire", "write_MBps",
                                                     "lower"),
                             "stack_groups.write": ("kernels",
                                                    "write_MBps",
                                                    "higher")})):
        found = [m for m in spec["per_layer"] if m["name"] in readers]
        assert len(found) == len(readers)
        for metric in found:
            assert metric["workloads"] == list(CELLS)
            assert (metric["layer"], metric["moves"], metric["better"]) \
                == layer_moves[metric["name"]]
            assert metric["source"] == "program_counter"
