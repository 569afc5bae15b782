"""Round-12 crash-safe batched data plane: batch-aware fault injection,
tick-boundary crash points, frontier recovery, and the per-item failure
semantics of the sub-write batcher.

Tier-1 pieces are structural (unit semantics + the seeded batch-smoke
scenario with its replay contract); the heavier crash-point matrix and
the rolling-restart soak are slow-marked.
"""

import asyncio

import pytest

from ceph_tpu.chaos.counters import CHAOS
from ceph_tpu.chaos.net import NetInjector
from ceph_tpu.chaos.rng import stream
from ceph_tpu.cluster import messages as M
from ceph_tpu.cluster.vstart import _fast_config, start_cluster


def run(coro):
    return asyncio.run(coro)


def _counters():
    return dict(CHAOS.dump()["chaos"])


# ------------------------------------------------ batch-frame injection


def _frame(n):
    return M.MOSDECSubOpWriteBatch(
        items=[M.MOSDECSubOpWrite(reqid=("c", i), shard=i % 3)
               for i in range(n)],
        epoch=1)


def test_batch_item_drop_partial_and_deterministic():
    """Item drop delivers a PARTIAL frame (never empties it), counts
    the loss, and replays bit-identically from the same seed."""
    before = _counters().get("net_batch_item_drops", 0)
    inj = NetInjector(stream(5, "t"), batch_item_drop=0.5)
    frame = _frame(12)
    inj.mutate_batch(frame)
    assert 1 <= len(frame.items) < 12
    dropped = 12 - len(frame.items)
    assert _counters()["net_batch_item_drops"] == before + dropped
    # same seed, same frame shape -> identical surviving membership
    frame2 = _frame(12)
    NetInjector(stream(5, "t"), batch_item_drop=0.5).mutate_batch(frame2)
    assert [it.reqid for it in frame2.items] == \
        [it.reqid for it in frame.items]
    # extreme rate still leaves one item (whole-frame loss is
    # chaos_net_drop's job, which keeps retransmission semantics)
    frame3 = _frame(6)
    NetInjector(stream(1, "x"), batch_item_drop=1.0).mutate_batch(frame3)
    assert len(frame3.items) == 1


def test_batch_ack_dup_and_reorder():
    inj = NetInjector(stream(9, "a"), batch_ack_dup=1.0)
    reply = M.MOSDECSubOpWriteBatchReply(
        results=[(("c", i), 0, i) for i in range(4)])
    inj.mutate_batch(reply)
    assert len(reply.results) == 8  # every entry duplicated
    inj2 = NetInjector(stream(9, "b"), batch_ack_reorder=1.0)
    reply2 = M.MOSDECSubOpWriteBatchReply(
        results=[(("c", i), 0, i) for i in range(8)])
    orig = list(reply2.results)
    inj2.mutate_batch(reply2)
    assert sorted(reply2.results) == sorted(orig)  # same set, any order


def test_injector_none_with_only_batch_rates_off():
    from ceph_tpu.utils import Config

    cfg = Config()
    assert NetInjector.from_config(cfg, "osd.0") is None
    cfg.chaos_net_batch_item_drop = 0.3
    inj = NetInjector.from_config(cfg, "osd.0")
    assert inj is not None and inj.batch_item_drop == 0.3


@pytest.mark.parametrize("fault", ["dup", "drop", "reorder"])
def test_wire_fault_on_out_of_band_batch_frames(fault):
    """Every frame of a run of 2 MiB sub-write batches meets the fault
    (rate 1.0) on its way out.  The frame's parts list — pickle plus
    references to the out-of-band buffers — is what ``dup`` writes
    twice, what ``drop`` leaves in the replay buffer for the
    retransmission, and what ``reorder`` hands to the late send: every
    batch arrives at least once with the bytes it was sent with."""
    import os

    from ceph_tpu.cluster.messenger import (
        Dispatcher, EntityName, Messenger)
    from ceph_tpu.utils import Config
    from ceph_tpu.utils.perf import KERNELS

    async def scenario():
        got = []

        class Sink(Dispatcher):
            async def ms_dispatch(self, conn, msg):
                if isinstance(msg, M.MOSDECSubOpWriteBatch):
                    got.append((msg.epoch, type(msg.items[0].data),
                                bytes(msg.items[0].data)))
                    return True
                return False

        rx = Messenger(EntityName("osd", 1))
        rx.add_dispatcher(Sink())
        addr = await rx.bind()
        cfg = Config(chaos_seed=29, **{f"chaos_net_{fault}": 1.0})
        tx = Messenger(EntityName("osd", 2), config=cfg)
        assert tx.chaos is not None
        counter = {"dup": "net_dups", "drop": "net_drops",
                   "reorder": "net_reorders"}[fault]
        before = _counters().get(counter, 0)
        oob_before = KERNELS.get("msgr_oob_bytes")
        datas = [os.urandom(2 << 20) for _ in range(5)]
        try:
            for i, data in enumerate(datas):
                await tx.send_message(M.MOSDECSubOpWriteBatch(
                    items=[M.MOSDECSubOpWrite(reqid=("c", i), shard=1,
                                              data=data)],
                    epoch=i), addr)
            loop = asyncio.get_event_loop()
            deadline = loop.time() + 20.0
            want = 2 * len(datas) if fault == "dup" else len(datas)
            while (len(got) < want or
                   {e for e, _k, _d in got} != set(range(len(datas)))) \
                    and loop.time() < deadline:
                await asyncio.sleep(0.02)
            assert {e for e, _k, _d in got} == set(range(len(datas)))
            assert all(kind is memoryview and blob == datas[e]
                       for e, kind, blob in got)
            assert _counters()[counter] - before >= len(datas)
            # framed once each: a duplicate, a replay or a late send
            # writes the parts again, it does not encode again
            assert KERNELS.get("msgr_oob_bytes") - oob_before == \
                sum(len(d) for d in datas)
            if fault == "dup":
                assert len(got) >= 2 * len(datas)
            if fault != "reorder":
                firsts = []
                for e, _k, _d in got:
                    if e not in firsts:
                        firsts.append(e)
                assert firsts == sorted(firsts)
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


# ------------------------------- sub-write batcher per-item semantics


class _FakeOSD:
    """Just enough OSD for SubWriteBatcher: recordable sends with
    per-target failure injection."""

    def __init__(self):
        from ceph_tpu.utils import Config, PerfCounters

        self._stopped = False
        self.config = Config(osd_batch_tick_ops=16)
        self.perf = PerfCounters("osd.fake")
        self.sent = []          # (target, type-name, n_items)
        self.fail_targets = set()
        self.gate = None        # optional: holds sends until released

        class _Map:
            epoch = 7

        self.osdmap = _Map()
        self._tasks = set()

    def _track(self, task):
        from ceph_tpu.utils.tasks import track_task

        return track_task(self._tasks, task)

    def _chaos_point(self, name):
        pass

    async def _send_osd(self, target, msg):
        if self.gate is not None:
            await self.gate.wait()
        if target in self.fail_targets:
            raise ConnectionError(f"peer osd.{target} dead")
        n = len(msg.items) if hasattr(msg, "items") else 1
        self.sent.append((target, type(msg).__name__, n))


def test_subwrite_batcher_failure_unacks_only_affected_ops():
    """THE per-item failure contract: a failed send of one peer's frame
    must fail exactly the ops whose sub-writes rode it — the other
    peer's frames (other ops' shards) deliver, and nothing waits
    forever."""
    from ceph_tpu.cluster.batcher import SubWriteBatcher

    async def scenario():
        osd = _FakeOSD()
        b = SubWriteBatcher(osd)
        osd.fail_targets = {1}

        async def op(name):
            # one op fans out to peers 1 and 2, like an EC stripe
            results = await asyncio.gather(
                b.send(1, M.MOSDECSubOpWrite(reqid=(name, 1), shard=0)),
                b.send(2, M.MOSDECSubOpWrite(reqid=(name, 1), shard=1)),
                return_exceptions=True)
            return results

        rx, ry = await asyncio.gather(op("x"), op("y"))
        for res in (rx, ry):
            assert isinstance(res[0], ConnectionError)  # peer 1 leg
            assert res[1] is None                       # peer 2 leg
        # peer 2 actually received both ops' sub-writes
        assert sum(n for t, _k, n in osd.sent if t == 2) == 2
        # a transient failure must not wedge the path: heal peer 1 and
        # a NEW send succeeds (the worker re-arms; nothing waits
        # forever behind the dead frame)
        osd.fail_targets = set()
        ok = await asyncio.wait_for(
            b.send(1, M.MOSDECSubOpWrite(reqid=("z", 1), shard=0)),
            timeout=5.0)
        assert ok is None
        assert any(t == 1 for t, _k, _n in osd.sent)

    run(scenario())


def test_subwrite_batcher_coalesces_same_target_into_one_frame():
    """Items queued while a frame is in flight ride the NEXT frame
    together: one MOSDECSubOpWriteBatch, one transport ack."""
    from ceph_tpu.cluster.batcher import SubWriteBatcher

    async def scenario():
        osd = _FakeOSD()
        osd.gate = asyncio.Event()
        b = SubWriteBatcher(osd)
        first = asyncio.ensure_future(
            b.send(3, M.MOSDECSubOpWrite(reqid=("a", 1), shard=0)))
        await asyncio.sleep(0)  # worker parks inside the gated send
        rest = [asyncio.ensure_future(
            b.send(3, M.MOSDECSubOpWrite(reqid=(f"b{i}", 1), shard=0)))
            for i in range(3)]
        await asyncio.sleep(0)
        osd.gate.set()
        await asyncio.gather(first, *rest)
        kinds = [(k, n) for _t, k, n in osd.sent]
        # first item went alone (self-clocking); the 3 queued behind it
        # shared ONE multi-item frame
        assert ("MOSDECSubOpWrite", 1) in kinds
        assert ("MOSDECSubOpWriteBatch", 3) in kinds

    run(scenario())


# ----------------------------------------------- crash points (cluster)


def test_crash_point_fires_and_cluster_recovers():
    """Arm commit_pre_fanout on a primary: the daemon power-cuts itself
    mid-write (after frontier open + local apply, before any sub-write
    leaves), the cluster's bookkeeping absorbs the crash, and after a
    revive every acked write reads back bit-exact — the write caught by
    the crash either fails or lands whole via client retry, never
    torn."""

    async def scenario():
        import os

        cluster = await start_cluster(4, config=_fast_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "cp", "erasure", pg_num=4,
                ec_profile={"plugin": "jerasure",
                            "technique": "reed_sol_van",
                            "k": "2", "m": "1"})
            io = client.ioctx(pool)
            datas = {f"o{i}": os.urandom(8192) for i in range(4)}
            for oid, d in datas.items():
                await io.write_full(oid, d)
            pgid = client.objecter.object_pgid(pool, "o0")
            _, _, _, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)
            before = _counters().get("crash_points_fired", 0)
            cluster.osds[primary].config.injectargs(
                {"chaos_crash_point": "commit_pre_fanout"})
            # the overwrite that trips the crash retries onto the
            # post-peering acting set and must land whole
            new = os.urandom(8192)
            await io.write_full("o0", new, timeout=60)
            datas["o0"] = new
            await cluster.drain_chaos()
            assert _counters()["crash_points_fired"] == before + 1
            assert primary not in cluster.osds  # bookkeeping coherent
            await cluster.revive_osd(primary)
            deadline = asyncio.get_event_loop().time() + 30
            while asyncio.get_event_loop().time() < deadline:
                if cluster.mon.osdmap.osd_up[primary]:
                    break
                await asyncio.sleep(0.1)
            for oid, d in datas.items():
                got = None
                err = None
                while asyncio.get_event_loop().time() < deadline:
                    try:
                        got = await io.read(oid, timeout=30)
                        err = None
                    except (IOError, OSError) as e:
                        err = e
                        await asyncio.sleep(0.25)
                        continue
                    if got == d:
                        break
                    await asyncio.sleep(0.25)
                assert got == d, (oid, err)
        finally:
            await cluster.stop()

    run(scenario())


# ------------------------------------------------- builtin scenarios


@pytest.mark.chaos
def test_batch_smoke_scenario(tmp_path):
    """Tier-1 batch-chaos gate: seeded partial-frame drops + dup'd/
    shuffled batched acks + one tick-boundary crash point under
    concurrent EC writes on FileStore — zero durability/frontier
    violations, and the fault SCHEDULE (crash point, victim, skip
    count) resolves bit-identically from the seed.  (The double-run
    verdict-replay gate is the slow-marked twin below — one scenario
    run keeps the load-sensitive tier-1 budget honest.)"""
    from ceph_tpu.chaos.scenario import (
        build_schedule,
        builtin_scenarios,
        run_scenario,
    )

    sc = builtin_scenarios()["batch-smoke"]
    s1, s2 = build_schedule(sc, 31), build_schedule(sc, 31)
    assert s1 == s2
    cp = [e for e in s1 if e["action"] == "crash_point"]
    assert cp and cp[0]["args"]["point"] == "commit_mid_fanout"
    assert "at" in cp[0]["args"]  # seed-resolved deterministic timing
    # schedules vary across seeds (seed-driven, not hardcoded)
    assert any(build_schedule(sc, s) != s1 for s in range(8))
    v1 = run(run_scenario(sc, 31, tmpdir=str(tmp_path / "a")))
    assert v1.passed, v1.failures
    assert v1.schedule == s1


@pytest.mark.chaos
@pytest.mark.slow
def test_batch_smoke_scenario_replays_bit_identical(tmp_path):
    """The full replay contract: batch-smoke TWICE from one seed —
    identical schedule, identical PASS verdict, and the injected
    per-item batch faults provably fired."""
    from ceph_tpu.chaos.scenario import builtin_scenarios, run_scenario

    sc = builtin_scenarios()["batch-smoke"]
    v1 = run(run_scenario(sc, 31, tmpdir=str(tmp_path / "a")))
    v2 = run(run_scenario(sc, 31, tmpdir=str(tmp_path / "b")))
    assert v1.passed, v1.failures
    assert v2.passed, v2.failures
    assert v1.replay_key() == v2.replay_key()
    # the injected batch faults actually fired (frame composition is
    # transport-timing dependent, so judged across the two runs; the
    # mutator's per-item semantics are unit-proven deterministically)
    drops = v1.counters.get("net_batch_item_drops", 0) + \
        v2.counters.get("net_batch_item_drops", 0)
    assert drops > 0, (v1.counters, v2.counters)


@pytest.mark.chaos
@pytest.mark.slow
def test_batch_kill_midtick_scenario(tmp_path):
    """Crash points across the commit pipeline (peer mid-batch-apply,
    post-encode, pre-frontier-done) + per-item drops: durability +
    frontier + scrub all hold."""
    from ceph_tpu.chaos.scenario import builtin_scenarios, run_scenario

    v = run(run_scenario(builtin_scenarios()["batch-kill-midtick"], 17,
                         tmpdir=str(tmp_path)))
    assert v.passed, v.failures
    assert v.counters.get("crash_points_fired", 0) >= 1


@pytest.mark.chaos
@pytest.mark.slow
def test_rolling_restart_sharded_scenario(tmp_path):
    """ROADMAP item-5 flavor: bounce several OSDs under sustained
    writes on the sharded WQ — bounded time-to-HEALTH_OK (the health
    invariant inside converge_timeout) with zero durability/frontier
    violations, and the frontier watermark monotone across every
    store-preserving bounce."""
    from ceph_tpu.chaos.scenario import builtin_scenarios, run_scenario

    v = run(run_scenario(
        builtin_scenarios()["rolling-restart-sharded"], 13,
        tmpdir=str(tmp_path)))
    assert v.passed, v.failures
    assert v.counters.get("daemon_restarts") == 4


# ------------------------------------- tick composition determinism


def test_sharded_wq_tick_composition_is_seed_stable():
    """Chaos replays on the sharded WQ: PG->shard placement is a pure
    function (same pgid, same shard, across runs and processes), so a
    seeded scenario's ops meet the same shard queues both runs; the
    fault side (schedules, batch mutations, crash skip counts) derives
    from seeded streams — together the replay contract of
    test_batch_smoke_scenario_replays_bit_identical."""
    from ceph_tpu.cluster.sharded_wq import ShardedOpWQ
    from ceph_tpu.osdmap.osdmap import PGid

    class _O:
        class config:
            osd_op_queue = "fifo"
            osd_batch_tick_ops = 16

    a = ShardedOpWQ(_O(), 4)
    b = ShardedOpWQ(_O(), 4)
    for pool in range(3):
        for seed in range(32):
            assert a.shard_for(PGid(pool, seed)).idx == \
                b.shard_for(PGid(pool, seed)).idx
    # and the batch mutator consumes per-frame draws deterministically
    inj1 = NetInjector(stream(3, "net:osd.1"), batch_item_drop=0.4)
    inj2 = NetInjector(stream(3, "net:osd.1"), batch_item_drop=0.4)
    for n in (4, 7, 2, 9):
        f1, f2 = _frame(n), _frame(n)
        inj1.mutate_batch(f1)
        inj2.mutate_batch(f2)
        assert [i.reqid for i in f1.items] == [i.reqid for i in f2.items]
