"""Divergent-log rewind + EC rollback (round-4 item 5).

Reference: PGLog::rewind_divergent_log (src/osd/PGLog.cc:287), the EC
rollback design (doc/dev/osd_internals/erasure_coding/ecbackend.rst:
10-27), and find_best_info's require_rollback MIN-last_update election —
an un-acked partial-stripe write applied on some shards only must be
ROLLED BACK during peering (restoring the exact pre-write shard bytes),
never blessed or object-copied forward.
"""

import asyncio
import pickle
import random

from tests._flaky import contention_retry
import pytest

from ceph_tpu.cluster import messages as M
from ceph_tpu.cluster import pglog
from ceph_tpu.cluster.osd import OSDDaemon
from ceph_tpu.cluster.pg import PGRB
from ceph_tpu.cluster.vstart import _fast_config, start_cluster
from ceph_tpu.ops import crc32c as crcmod

EC_PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
              "k": "2", "m": "1"}


def run(coro):
    return asyncio.run(coro)


def _shard_crc(osd, coll, oid):
    data = osd.store.read(coll, oid)
    return crcmod.crc32c(0xFFFFFFFF, bytes(data))


@contention_retry()
def test_ec_partial_write_rolls_back():
    """Primary applies its shard + log entry but the sub-writes never
    reach the replicas (crash mid-write).  Peering must elect the
    replicas' shorter log (min-rule) and REWIND the primary's divergent
    entry, restoring its pre-write shard bytes exactly (verified via
    per-shard crc), not copy objects around."""
    async def scenario():
        cfg = _fast_config()
        cfg.osd_client_op_timeout = 1.0   # the doomed write times out fast
        # load-deflake (round 11): under suite load a starved event loop
        # misses heartbeats/beacons, a false down-mark churns the map,
        # and peering rewinds the divergent entry EARLY — racing the
        # intermediate asserts below (seen as last_update "never
        # advancing": it had already been rewound).  Generous graces pin
        # peering to the explicit _recover_pg call; the invariants
        # stay strict.
        cfg.osd_heartbeat_grace = 30.0
        cfg.mon_osd_beacon_grace = 30.0
        # ... and pin BACKGROUND recovery out of the window too: an
        # incomplete boot-time round arms a delayed retry that can
        # fire mid-doomed-write and rewind the divergent entry before
        # the intermediate asserts observe it (round 12 retries rounds
        # more eagerly).  The test drives peering explicitly.
        cfg.osd_recovery_delay_start = 300.0
        cluster = await start_cluster(3, config=cfg)
        try:
            client = await cluster.client()
            pool = await client.pool_create("rwnd", "erasure", pg_num=4,
                                            ec_profile=dict(EC_PROFILE))
            io = client.ioctx(pool)
            v1 = bytes(range(256)) * 32
            await io.write_full("victim", v1)

            pgid = client.objecter.object_pgid(pool, "victim")
            coll = f"pg_{pgid.pool}_{pgid.seed}"
            _, _, acting, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)
            # converge-poll (not a fixed beat): every member's shard
            # apply must land before the crc/log snapshot below
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline and \
                    any(cluster.osds[o].store.stat(coll, "victim")
                        is None for o in acting):
                await asyncio.sleep(0.05)
            posd = cluster.osds[primary]
            st = posd.pgs[pgid]
            lu_before = st.last_update
            crc_before = _shard_crc(posd, coll, "victim")

            # crash-mid-write model: the sub-writes VANISH (sent into the
            # void, no error) — exactly what a primary death after the
            # local apply looks like; the op times out un-acked
            orig_send = posd._send_osd

            async def drop_subwrites(osd, msg):
                if isinstance(msg, M.MOSDECSubOpWrite):
                    return  # swallowed: replicas never see it
                return await orig_send(osd, msg)

            posd._send_osd = drop_subwrites
            pobj = posd.osdmap.pools[pool]
            r = await posd._op_write_full(pobj, st, "victim", b"Z" * 8192)
            posd._send_osd = orig_send
            assert r == -110, "doomed write must time out un-acked"
            # local shard applied + logged, replicas never saw it
            assert st.last_update > lu_before
            assert _shard_crc(posd, coll, "victim") != crc_before
            assert st.last_complete < st.last_update
            rb = posd.store.omap_get(coll, PGRB)
            assert rb, "no rollback record captured for the shard write"

            # peering (what the restarted primary runs): the replicas'
            # log wins under the EC min-rule; our entry rewinds
            await posd._recover_pg(st)
            assert st.last_update == lu_before, "divergent entry survived"
            assert _shard_crc(posd, coll, "victim") == crc_before, \
                "rewind did not restore the pre-write shard bytes"
            # the object still reads back as v1 for clients
            assert await io.read("victim", timeout=60) == v1
        finally:
            await cluster.stop()

    run(scenario())


def test_ec_divergent_replica_rewinds_on_instruction():
    """A REPLICA holding a divergent entry (it applied a sub-write the
    other members never got, then the primary's log moved on without it)
    is rolled back by the primary's rewind instruction during peering."""
    async def scenario():
        cluster = await start_cluster(3)
        try:
            client = await cluster.client()
            pool = await client.pool_create("rwnd2", "erasure", pg_num=4,
                                            ec_profile=dict(EC_PROFILE))
            io = client.ioctx(pool)
            v1 = b"stable-state" * 100
            await io.write_full("obj", v1)
            pgid = client.objecter.object_pgid(pool, "obj")
            coll = f"pg_{pgid.pool}_{pgid.seed}"
            _, _, acting, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)
            # converge-poll: the replica's shard + log entry must land
            # before crc_before/lu snapshot below (fixed beat flaked)
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline and \
                    any(cluster.osds[o].store.stat(coll, "obj") is None
                        for o in acting):
                await asyncio.sleep(0.05)
            replica = next(o for o in acting if o != primary)
            rosd = cluster.osds[replica]
            rst = rosd.pgs[pgid]
            crc_before = _shard_crc(rosd, coll, "obj")
            lu = rst.last_update

            # forge a divergent sub-write on the replica only (the shard
            # apply + entry the reference's crashed primary would have
            # fanned out to just this member)
            fake_v = (rosd.osdmap.epoch, lu[1] + 1)
            shard = int(rosd.store.getattr(coll, "obj", "shard"))
            rosd._apply_shard(pgid, "obj", shard, b"G" * 1024, 0, 1024,
                              {"size": 2048, "version": fake_v[1]})
            rosd._log_mutation(rst, "modify", "obj", fake_v)
            assert rst.last_update == fake_v
            assert _shard_crc(rosd, coll, "obj") != crc_before

            # primary peers: sees the replica ahead, instructs rewind
            posd = cluster.osds[primary]
            await posd._recover_pg(posd.pgs[pgid])
            for _ in range(50):
                if rst.last_update == lu:
                    break
                await asyncio.sleep(0.1)
            assert rst.last_update == lu, "replica kept divergent entry"
            assert _shard_crc(rosd, coll, "obj") == crc_before, \
                "replica shard bytes not restored"
            assert await io.read("obj", timeout=60) == v1
        finally:
            await cluster.stop()

    run(scenario())


def test_stale_primary_shard_serves_committed_group():
    """A primary whose OWN shard is a stale older generation — the state
    an interrupted recovery pull leaves behind when no further map
    change retriggers peering — must serve reads from the newest
    COMMITTED shard group at the GROUP's size, never the group's bytes
    truncated to the local size attr (graft-chaos: obj read back as g2
    bytes at g1's length).  Scrub must then flag + rebuild the stale
    shard even though its crc is self-consistent.

    Round 16: automatic READ-repair would heal the stale shard before
    the scrub half of this test could see it (that path has its own
    coverage in tests/test_integrity.py), so this anchor runs with
    osd_read_repair=0 — detection-only — to keep exercising the scrub
    generation-divergence machinery."""
    from ceph_tpu.cluster.store import Transaction

    async def scenario():
        cfg = _fast_config()
        cfg.osd_read_repair = 0
        cluster = await start_cluster(4, config=cfg)
        try:
            client = await cluster.client()
            pool = await client.pool_create("stale", "erasure", pg_num=4,
                                            ec_profile=dict(EC_PROFILE))
            io = client.ioctx(pool)
            g1 = b"g1-" * 340                 # 1020 bytes
            g2 = b"g2-xyz" * 180              # 1080 bytes
            await io.write_full("obj", g1)
            pgid = client.objecter.object_pgid(pool, "obj")
            coll = f"pg_{pgid.pool}_{pgid.seed}"
            _, _, acting, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)
            posd = cluster.osds[primary]
            # capture the primary's complete g1 shard state
            old_bytes = bytes(posd.store.read(coll, "obj"))
            old_attrs = {k: posd.store.getattr(coll, "obj", k)
                         for k in ("shard", "size", "hinfo_crc")}
            old_ver = posd.store.get_version(coll, "obj")
            await io.write_full("obj", g2)    # acked: every shard at g2

            # surgically regress ONLY the primary's shard back to g1
            # (bytes + attrs + version all self-consistent, crc clean)
            txn = (Transaction()
                   .write(coll, "obj", 0, old_bytes)
                   .truncate(coll, "obj", len(old_bytes)))
            for k, v in old_attrs.items():
                txn.setattr(coll, "obj", k, v)
            txn.set_version(coll, "obj", old_ver)
            posd.store.queue_transaction(txn)

            # read must be the committed generation, whole — not g2
            # bytes cut to g1's 1020
            assert await io.read("obj", timeout=60) == g2

            # scrub sees the generation divergence and rebuilds the
            # stale shard from the committed group
            st = posd.pgs[pgid]
            rep = await posd.scrub_pg(st)
            assert "obj" in rep["inconsistent"], \
                "scrub missed the stale (old-generation) shard"
            assert "obj" in rep["repaired"]
            assert posd.store.getattr(coll, "obj", "size") == \
                str(len(g2)).encode()
            assert await io.read("obj", timeout=60) == g2
        finally:
            await cluster.stop()

    run(scenario())


@pytest.mark.chaos
@pytest.mark.slow
def test_thrash_primaries_mid_ec_write():
    """Thrasher variant bouncing OSDs mid-write on an EC pool (round-4
    item 5 gate), now a seeded chaos scenario: restart events race the
    write bursts on a deterministic schedule; afterwards every acked
    object must hold SOME whole submitted payload (at-least-once — a
    timed-out write may land after its client gave up, but torn or
    mixed-generation bytes never pass) and a full scrub pass finds zero
    silent shard divergence."""
    from ceph_tpu.chaos.scenario import builtin_scenarios, run_scenario

    v = run(run_scenario(builtin_scenarios()["thrash-ec-midwrite"], 11))
    assert v.passed, v.failures
    assert v.counters.get("daemon_restarts") == 3


def _info(lu, lc=pglog.ZERO):
    return pglog.PGInfo(last_update=lu, last_complete=lc)


@pytest.mark.parametrize("infos,k,want", [
    # an empty returning primary (osd 2) must not outvote two survivors
    # whose watermark still trails the one acked write they both hold
    ({0: _info((6, 1)), 1: _info((6, 1)), 2: _info(pglog.ZERO)}, 2, 0),
    # fewer than k holders: the first write reached one shard only and
    # cannot be decoded — the history-less member wins, it rolls back
    ({0: _info((6, 1)), 1: _info(pglog.ZERO), 2: _info(pglog.ZERO)}, 2, 1),
    # nobody has history: a new PG, any member will do
    ({0: _info(pglog.ZERO), 1: _info(pglog.ZERO)}, 2, 0),
    # among holders the rule is unchanged: MIN last_update at or above
    # the watermark, so the un-acked (6, 3) on osd 0 rolls back
    ({0: _info((6, 3), (6, 2)), 1: _info((6, 2), (6, 2)),
      2: _info(pglog.ZERO)}, 2, 1),
    # the guard is EC's: without `decodable` the old election stands
    ({0: _info((6, 1)), 1: _info((6, 1)), 2: _info(pglog.ZERO)}, 0, 2),
])
def test_ec_election_skips_members_without_history(infos, k, want):
    assert pglog.choose_authoritative(
        infos, require_rollback=True, decodable=k) == want


@contention_retry()
def test_acked_single_write_survives_an_empty_primary_bounce():
    """One write per PG leaves every replica's watermark at ZERO (it
    rides the NEXT write).  Kill the primary, revive it on an empty
    store before the interim primary's peering round has rolled the
    watermark forward: the returning primary used to elect its own empty
    log and order the acked write rewound on both survivors (ENOENT on
    an acknowledged object; found by PR 23's chip bring-up)."""
    async def scenario():
        cluster = await start_cluster(3)
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "bounce", "erasure", pg_num=8,
                ec_profile={"plugin": "jerasure",
                            "technique": "reed_sol_van",
                            "k": "2", "m": "1"})
            io = client.ioctx(pool)
            names = [f"o{i}" for i in range(12)]
            for n in names:
                await io.write_full(n, n.encode() * 4096, timeout=60)
            await cluster.kill_osd(2)
            await cluster.wait_down(2)
            await cluster.revive_osd(2)
            # let the returning primary's peering rounds run their course
            # before judging: the rewind it used to order lands then
            deadline = asyncio.get_event_loop().time() + 30
            while cluster.mon._health_data()["status"] != "HEALTH_OK":
                assert asyncio.get_event_loop().time() < deadline
                await asyncio.sleep(0.1)
            for n in names:
                assert await io.read(n, timeout=60) == n.encode() * 4096
        finally:
            await cluster.stop()

    asyncio.run(scenario())
