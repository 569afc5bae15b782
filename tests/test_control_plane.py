"""Control plane at scale (round 14): vectorized epoch deltas, bounded
delta chains, mon-side markdown coalescing, peering storm control, and
the storm scenarios that prove the cluster survives mass churn.

The acceptance gates live here:

- ``affected_pgs`` (whole-pool array diff) selects EXACTLY the PG set
  the per-PG scalar scan would re-peer, across mark down/out/in, weight
  change, pg_num growth, and upmap edits — in both snapshot modes;
- an OSD facing an over-long incremental chain skips to a full map
  instead of unpickling the chain on its dispatch loop;
- N simultaneous failure reports coalesce into few map epochs;
- the storm scenarios (rolling-restart-100 / mon-bounce-under-churn)
  pass seeded at tier-1 scale with deterministic schedules (full-size
  runs are slow-marked, with full bit-identical verdict replay).
"""

import asyncio
import copy
import dataclasses

import pytest

from ceph_tpu.osdmap.osdmap import (
    PGid,
    POOL_TYPE_ERASURE,
    POOL_TYPE_REPLICATED,
    affected_pgs,
    affected_pgs_scalar,
    build_simple_osdmap,
)


def run(coro):
    return asyncio.run(coro)


# ------------------------------------------------- vectorized delta oracle


def _mutations(m):
    """(name, mutated-map) cases: every class the issue names."""
    cases = []
    m2 = copy.deepcopy(m)
    m2.mark_down(5)
    cases.append(("mark_down", m2))
    m3 = copy.deepcopy(m)
    m3.mark_out(9)
    cases.append(("mark_out", m3))
    m4 = copy.deepcopy(m)
    m4.mark_in(9, 0x10000)
    cases.append(("mark_in", m4))
    m5 = copy.deepcopy(m)
    m5.mark_in(3, 0x8000)          # weight change (half weight)
    cases.append(("weight", m5))
    m6 = copy.deepcopy(m)
    m6.pools[1] = dataclasses.replace(m6.pools[1], pg_num=96)
    cases.append(("pg_num_growth", m6))
    m7 = copy.deepcopy(m)
    pg = PGid(1, 7)
    up = m7.pg_to_up_acting_osds(pg)[0]
    dst = next(o for o in range(16) if o not in up)
    m7.pg_upmap_items[pg] = [(up[0], dst)]
    cases.append(("upmap_items", m7))
    m8 = copy.deepcopy(m)
    m8.pg_upmap[PGid(1, 3)] = [1, 5, 9]
    cases.append(("upmap_full", m8))
    m9 = copy.deepcopy(m)
    m9.pg_temp[PGid(1, 11)] = [1, 2, 6]
    cases.append(("pg_temp", m9))
    return cases


@pytest.mark.parametrize("ptype", [POOL_TYPE_REPLICATED,
                                   POOL_TYPE_ERASURE],
                         ids=["replicated", "erasure"])
def test_affected_pgs_bit_identical_to_scalar_scan(ptype):
    """THE tier-1 acceptance gate: the vectorized whole-pool diff and
    the per-PG scalar scan select the identical affected-PG set for
    every mutation class, in the scalar-snapshot mode (small pools).
    The batched-array mode is covered separately to bound device time."""
    m = build_simple_osdmap(n_osds=16, osds_per_host=4, pg_num=48,
                            pool_type=ptype, size=3)
    for name, m2 in _mutations(m):
        want = affected_pgs_scalar(m, m2, 1)
        got = affected_pgs(m, m2, 1, engine="scalar")
        assert got == want, (name, sorted(got - want), sorted(want - got))
        # a mutation must actually affect something (or the case is
        # vacuous) — except mark_in back to the current weight
        if name not in ("mark_in",):
            assert want, name
        # identity diff: no epoch, no affected PGs
        assert affected_pgs(m, m, 1, engine="scalar") == set()


def test_affected_pgs_batched_mode_matches_scalar_scan():
    """The batched-array diff path (pool_mapping snapshots + numpy row
    compare) agrees with the scalar scan too — one pool type suffices;
    the row semantics themselves are cross-checked pool-type-wide by
    test_osdmap.test_batched_matches_scalar."""
    m = build_simple_osdmap(n_osds=16, osds_per_host=4, pg_num=48,
                            pool_type=POOL_TYPE_REPLICATED, size=3)
    for name, m2 in _mutations(m):
        want = affected_pgs_scalar(m, m2, 1)
        got = affected_pgs(m, m2, 1, engine="device")
        assert got == want, (name, sorted(got - want), sorted(want - got))


# ---------------------------------------------- osd/mon chain + coalesce


def test_inc_chain_cap_skips_to_full_and_failures_coalesce():
    """Two control-plane bounds on one cluster: (a) an OSD handed an
    incremental chain past osd_map_max_inc_chain requests a full map
    instead of applying it; (b) simultaneous failure reports coalesce
    into few epochs (mon_osd_failure_coalesce window); (c) a no-op
    epoch re-peers nothing (the vectorized delta's whole point)."""
    import pickle

    from ceph_tpu.cluster import messages as M
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster
    from ceph_tpu.osdmap.osdmap import Incremental

    async def scenario():
        cfg = _fast_config()
        cfg.mon_osd_failure_coalesce = 0.5
        cfg.osd_map_max_inc_chain = 2
        # the beacon-staleness tick must not win the markdown race:
        # this test proves the failure-REPORT aggregation path
        cfg.mon_osd_beacon_grace = 30.0
        cluster = await start_cluster(6, config=cfg)
        try:
            client = await cluster.client()
            await client.pool_create("cp", "replicated", pg_num=8,
                                     size=3)
            await cluster.wait_for_epoch(cluster.mon.osdmap.epoch,
                                         timeout=10)
            osd = cluster.osds[0]

            # (c) a placement-neutral epoch (clog-only inc) must not
            # re-peer anything on a vectorized-delta OSD
            repeered0 = osd.perf.get("osd_pgs_repeered")
            mon = cluster.mon
            async with mon._map_mutex:
                inc = mon._new_inc()
                inc.new_log_entries = (("test", 0.0, "INF", "noop"),)
                await mon._commit_inc(inc)
            await cluster.wait_for_epoch(mon.osdmap.epoch, timeout=10)
            assert osd.perf.get("osd_pgs_repeered") == repeered0

            # (a) synthetic over-long chain -> skip-to-full request
            base = osd.osdmap.epoch
            blobs = [pickle.dumps(Incremental(epoch=base + 1 + i))
                     for i in range(3)]
            skips0 = osd.perf.get("osd_map_skip_to_full")
            await osd._handle_inc_map(M.MOSDIncMapMsg(
                prev_epoch=base, epoch=base + 3, inc_blobs=blobs))
            assert osd.perf.get("osd_map_skip_to_full") == skips0 + 1
            # the chain was NOT applied; the mon's full-map reply (the
            # since=0 re-subscribe) re-syncs the daemon
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline:
                if osd.osdmap.epoch >= mon.osdmap.epoch:
                    break
                await asyncio.sleep(0.05)
            assert osd.osdmap.epoch >= mon.osdmap.epoch

            # (b) three dead OSDs -> their markdowns share epochs
            epoch0 = mon.osdmap.epoch
            for victim in (3, 4, 5):
                await cluster.kill_osd(victim)
            deadline = asyncio.get_event_loop().time() + 20
            while asyncio.get_event_loop().time() < deadline:
                if all(not mon.osdmap.osd_up[v] for v in (3, 4, 5)):
                    break
                await asyncio.sleep(0.05)
            assert all(not mon.osdmap.osd_up[v] for v in (3, 4, 5))
            assert mon.perf.get("mon_failures_coalesced") >= 1
            # 3 markdowns + their clog flushes in well under 3+3 epochs
            assert mon.osdmap.epoch - epoch0 <= 4, \
                (epoch0, mon.osdmap.epoch)
        finally:
            await cluster.stop()

    run(scenario())


# ----------------------------------------------------- storm scenarios


def _scaled_storms():
    from ceph_tpu.chaos.scenario import storm_scenarios

    return storm_scenarios(0.06)


@pytest.mark.chaos
def test_storm_rolling_restart_scaled(tmp_path):
    """Tier-1 storm gate: the rolling-restart storm at --scale 0.06
    (the same code paths as the 100-bounce acceptance run: load-driver
    traffic, staggered+overlapping bounces, the HEALTH_OK and epochs/s
    gates, durability/frontier/acting invariants) passes seeded, and
    its fault schedule is seed-deterministic."""
    from ceph_tpu.chaos.scenario import build_schedule, run_scenario

    sc = _scaled_storms()["rolling-restart-100"]
    assert build_schedule(sc, 7) == build_schedule(sc, 7)
    v = run(run_scenario(sc, 7, tmpdir=str(tmp_path)))
    assert v.passed, v.failures
    assert v.counters.get("daemon_restarts", 0) >= 4
    assert v.counters.get("epochs_generated", 0) > 0


@pytest.mark.chaos
def test_storm_mon_bounce_scaled(tmp_path):
    """Tier-1 storm gate: the Paxos leader killed mid-epoch-burst at
    tier-1 scale — the quorum fails over, keeps committing markdowns/
    boots, the killed mon revives into the quorum, and every invariant
    plus the HEALTH_OK gate holds."""
    from ceph_tpu.chaos.scenario import run_scenario

    sc = _scaled_storms()["mon-bounce-under-churn"]
    v = run(run_scenario(sc, 11, tmpdir=str(tmp_path)))
    assert v.passed, v.failures
    assert v.counters.get("daemon_kills", 0) >= 1      # the leader
    assert v.counters.get("daemon_revives", 0) >= 0


@pytest.mark.chaos
@pytest.mark.slow
def test_storm_rolling_restart_full_replay(tmp_path):
    """The full acceptance shape: ~100 staggered+overlapping OSD
    bounces under sustained load-driver traffic, epochs/s floor and
    bounded time-to-HEALTH_OK enforced, replayed bit-identically."""
    from ceph_tpu.chaos.scenario import run_scenario, storm_scenarios

    sc = storm_scenarios(1.0)["rolling-restart-100"]
    v1 = run(run_scenario(sc, 42, tmpdir=str(tmp_path / "a")))
    assert v1.passed, v1.failures
    assert v1.counters.get("daemon_restarts", 0) >= 90
    v2 = run(run_scenario(sc, 42, tmpdir=str(tmp_path / "b")))
    assert v1.replay_key() == v2.replay_key()


@pytest.mark.chaos
@pytest.mark.slow
def test_storm_mon_bounce_full(tmp_path):
    """Full-size mon-bounce-under-churn: leader killed mid-burst with
    a dozen OSD bounces churning epochs through Paxos."""
    from ceph_tpu.chaos.scenario import run_scenario, storm_scenarios

    sc = storm_scenarios(1.0)["mon-bounce-under-churn"]
    v = run(run_scenario(sc, 42, tmpdir=str(tmp_path)))
    assert v.passed, v.failures
    assert v.counters.get("daemon_kills", 0) >= 1


# ------------------------------------------------- anchor-mode parity


def test_anchor_mode_converges_identically():
    """osd_map_vectorized_delta=0 (the per-PG-scan anchor) still
    converges a bounce to the same healthy end state — the bisection
    contract for the whole round-14 path."""
    from ceph_tpu.chaos.invariants import check_acting, check_health
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster

    async def scenario():
        cfg = _fast_config()
        cfg.osd_map_vectorized_delta = 0
        cluster = await start_cluster(4, config=cfg)
        try:
            client = await cluster.client()
            pool = await client.pool_create("anchor", "replicated",
                                            pg_num=8, size=3)
            io = client.ioctx(pool)
            for i in range(6):
                await io.write_full(f"a{i}", b"anchor" * 40)
            await cluster.restart_osd(1)
            fails = await check_acting(cluster, timeout=30)
            fails += await check_health(cluster, timeout=30)
            assert not fails, fails
            for i in range(6):
                assert await io.read(f"a{i}") == b"anchor" * 40
        finally:
            await cluster.stop()

    run(scenario())
