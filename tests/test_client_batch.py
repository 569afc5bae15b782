"""Round-18 client-edge batching: per-(session, OSD) op-frame
coalescing with batched replies.

Unit level: the objecter's OpBatcher coalesces a tick's ops to one OSD
into ONE MOSDOpBatch frame (a lone op ships the plain MOSDOp),
and the reply-batch scatter resolves each item's future individually —
per-item ``throttled`` flags preserved, a reqid ABSENT from the reply
tick left pending (the SubWriteBatcher un-ack rule at the client edge).

Cluster level: a mid-batch THROTTLED item shrinks only its own op's
window accounting while its tick-mates ack through, and a mid-batch
expired-deadline item is shed OSD-side with zero acked-past-deadline.
"""

import asyncio

from tests._flaky import contention_retry

from ceph_tpu.cluster import messages as M
from ceph_tpu.cluster.objecter import Objecter
from ceph_tpu.cluster.vstart import _fast_config, start_cluster
from ceph_tpu.utils import Config


def run(coro):
    return asyncio.run(coro)


def _mk_objecter(**cfg) -> Objecter:
    """An objecter with a live event loop but no cluster: the unit
    seams (OpBatcher, reply scatter) never touch the wire."""
    return Objecter("cbt", ("127.0.0.1", 1), config=Config(**cfg))


# ------------------------------------------------------------- unit level


def test_reply_batch_scatters_per_item_preserving_throttled_and_absence():
    """One MOSDOpReplyBatch resolves each item's future with ITS reply
    (throttled flag intact); an inflight reqid absent from the tick
    stays PENDING — its op's own timeout/resend covers it."""

    async def scenario():
        obj = _mk_objecter()
        loop = asyncio.get_event_loop()
        futs = {i: loop.create_future() for i in range(4)}
        for i, fut in futs.items():
            obj._inflight[("c", i)] = fut
        await obj.ms_dispatch(None, M.MOSDOpReplyBatch(items=[
            M.MOSDOpReply(reqid=("c", 0), result=0, data=b"a"),
            M.MOSDOpReply(reqid=("c", 1), result=M.THROTTLED,
                          throttled=True),
            M.MOSDOpReply(reqid=("c", 2), result=-2),
            # reqid 3 deliberately absent: shed on the OSD
        ]))
        assert futs[0].result().result == 0
        assert futs[0].result().data == b"a"
        assert futs[1].result().throttled is True
        assert futs[1].result().result == M.THROTTLED
        assert futs[2].result().result == -2
        assert not futs[3].done(), "absent item must stay un-acked"
        assert ("c", 3) in obj._inflight
        fc = obj.flow_counters()
        assert fc["client_batch_reply_frames"] == 1
        assert fc["client_batch_reply_items"] == 3

    run(scenario())


def test_op_batcher_coalesces_per_osd_and_lone_op_ships_plain_frame():
    """Concurrent sends to one OSD pack into MOSDOpBatch frames (with
    the amortized client_batch_wait/send trace stamps); a lone op to
    another OSD ships the plain MOSDOp, unstamped."""

    async def scenario():
        obj = _mk_objecter(objecter_batch_tick_ops=8)
        sent = []

        async def fake_send(msg, addr):
            sent.append((addr, msg))

        obj.messenger.send_message = fake_send
        addr_a, addr_b = ("10.0.0.1", 1), ("10.0.0.2", 2)

        def op(tid):
            m = M.MOSDOp(reqid=("c", tid), pgid=None, oid=f"o{tid}",
                         ops=[("write_full", {"data": b"x"})], epoch=7)
            m.trace = {"id": f"t{tid}", "events": []}
            return m

        await asyncio.gather(*[obj._send_op(op(i), addr_a)
                               for i in range(5)],
                             obj._send_op(op(99), addr_b))
        a_frames = [m for a, m in sent if a == addr_a]
        b_frames = [m for a, m in sent if a == addr_b]
        # OSD b saw a lone op: the plain frame, no batch stamps
        assert len(b_frames) == 1 and isinstance(b_frames[0], M.MOSDOp)
        assert all(name not in ("objecter:batch_tick",
                                "objecter:batch_sent")
                   for name, _ in b_frames[0].trace["events"])
        # OSD a saw >= 1 frame covering all 5 ops; the multi-item ones
        # are MOSDOpBatch with per-item amortized stamps
        items = []
        for m in a_frames:
            if isinstance(m, M.MOSDOpBatch):
                assert m.epoch == 7
                for it in m.items:
                    names = [n for n, _ in it.trace["events"]]
                    assert "objecter:batch_tick" in names
                    assert "objecter:batch_sent" in names
                items.extend(m.items)
            else:
                items.append(m)
        assert {it.reqid[1] for it in items} == set(range(5))
        fc = obj.flow_counters()
        assert fc["client_batch_ticks"] >= 1
        assert fc["client_batch_ops"] >= 2
        await obj.stop()

    run(scenario())


def test_op_batcher_cap_of_one_ships_every_op_in_its_own_plain_frame():
    """objecter_batch_tick_ops=1 (the per-op reference): every op ships
    its own plain MOSDOp frame, unstamped, and no tick is counted."""

    async def scenario():
        obj = _mk_objecter(objecter_batch_tick_ops=1)
        sent = []

        async def fake_send(msg, addr):
            sent.append(msg)

        obj.messenger.send_message = fake_send
        await asyncio.gather(*[
            obj._send_op(M.MOSDOp(reqid=("c", i), pgid=None, oid="o",
                                  ops=[("read", {})], epoch=1),
                         ("10.0.0.1", 1))
            for i in range(4)])
        assert len(sent) == 4
        assert all(isinstance(m, M.MOSDOp) for m in sent)
        assert obj.flow_counters()["client_batch_ticks"] == 0
        await obj.stop()

    run(scenario())


def test_op_batcher_send_failure_fails_only_that_tick():
    """A frame-send failure surfaces on every op OF THAT TICK (their
    resend machinery owns recovery); later ticks send normally."""

    async def scenario():
        obj = _mk_objecter(objecter_batch_tick_ops=8)
        calls = []

        async def flaky_send(msg, addr):
            calls.append(msg)
            if len(calls) == 1:
                raise ConnectionError("wire down")

        obj.messenger.send_message = flaky_send

        def op(tid):
            return M.MOSDOp(reqid=("c", tid), pgid=None, oid="o",
                            ops=[("read", {})], epoch=1)

        results = await asyncio.gather(
            *[obj._send_op(op(i), ("10.0.0.1", 1)) for i in range(3)],
            return_exceptions=True)
        assert any(isinstance(r, ConnectionError) for r in results)
        # the batcher recovered: a fresh op rides a fresh tick
        await obj._send_op(op(9), ("10.0.0.1", 1))
        assert len(calls) >= 2
        await obj.stop()

    run(scenario())


def test_client_batch_attribution_stage_math():
    """The client-edge amortized marks: client_batch_wait +
    client_batch_send partition the send->tick window exactly like
    batch_wait/batch_encode, and stage sums equal the traced total."""
    from ceph_tpu.trace.attribution import attribute_events

    # op sent to the coalescer at t=1.0; its tick built 2.0 -> 2.6
    # packing 3 ops: the op books (2.6-2.0)/3 as its send share
    share = (2.6 - 2.0) / 3
    evs = [(0.0, "objecter:submit"), (1.0, "objecter:send"),
           (2.6 - share, "objecter:batch_tick"),
           (2.6, "objecter:batch_sent"),
           (2.7, "msgr:osd.0:recv"), (2.9, "done")]
    stages, total = attribute_events(evs)
    assert abs(sum(stages.values()) - total) < 1e-9
    assert abs(stages["client_batch_send"] - share) < 1e-9
    assert abs(stages["client_batch_wait"] - (1.6 - share)) < 1e-9
    assert stages["wire"] > 0


def test_fast_config_differs_from_config_only_in_its_named_timings():
    """``_fast_config()`` is ``Config()`` plus the timing keys its
    docstring names, and nothing else: no data-plane switch hides in
    it."""
    import re

    fast, base = _fast_config().show(), Config().show()
    differs = {k for k in base if fast[k] != base[k]}
    named = set(re.findall(r"``(\w+)``", _fast_config.__doc__)) & set(base)
    assert differs <= named, differs - named
    # the one named key whose value is also the default
    assert named - differs == {"osd_heartbeat_interval"}
    assert all(k.endswith(("_interval", "_grace", "_timeout",
                           "_delay_start", "_reporters"))
               for k in named), named


# ---------------------------------------------------------- cluster level


@contention_retry()
def test_mid_batch_throttled_item_does_not_collapse_tick_mates():
    """Tight OSD admission under client batching: THROTTLED pushback
    arrives per ITEM inside the batched reply, so tick-mates ack
    normally — every write eventually succeeds, pushbacks are counted,
    and the window is pushback-per-item (far fewer pushbacks than if
    each throttled reply frame marked its whole tick)."""

    async def scenario():
        cfg = _fast_config()
        cfg.osd_op_throttle_ops = 2
        cluster = await start_cluster(3, config=cfg)
        try:
            client = await cluster.client()
            pool = await client.pool_create("cbt", pg_num=8, size=3)
            io = client.ioctx(pool)
            await asyncio.gather(*[
                io.write_full(f"o{i}", bytes([i]) * 2048, timeout=60)
                for i in range(16)])
            # all acked: nothing was lost to a frame-wide pushback
            datas = await asyncio.gather(*[io.read(f"o{i}")
                                           for i in range(16)])
            assert all(datas[i] == bytes([i]) * 2048
                       for i in range(16))
            fc = client.objecter.flow_counters()
            return fc
        finally:
            await cluster.stop()

    fc = run(scenario())
    assert fc["client_batch_ticks"] > 0, "ops never coalesced"
    assert fc["client_cwnd_pushbacks"] > 0, \
        "throttle budget never pushed back (test lost its pressure)"
    # per-item accounting: acks >= the 32 data ops + their retries'
    # successes; window recovered (additive increase after the acks)
    assert fc["client_ops_acked"] >= 32
    assert fc["client_cwnd"] >= 1


@contention_retry()
def test_mid_batch_expired_item_unacks_only_itself():
    """Six coalesced writes to one hot object through a 2 op/s mclock
    limit: the queue tail expires mid-batch, the OSD sheds those at
    dequeue so they are ABSENT from the reply tick (only their clients
    time out), and zero ops ack past their deadline — the round-18
    per-item un-ack rule under real pacing."""

    async def scenario():
        config = _fast_config()
        config.osd_op_queue = "mclock"
        cluster = await start_cluster(3, config=config)
        try:
            client = await cluster.client()
            pool = await client.pool_create("cbx", pg_num=4, size=3)
            io = client.ioctx(pool)
            await io.write_full("hot", b"warm")
            entity = client.objecter.client_name.split("#", 1)[0]
            for osd in cluster.osds.values():
                osd.set_qos(entity, reservation=0.0, weight=1.0,
                            limit=2.0)
            loop = asyncio.get_event_loop()
            deadline_s = 1.2
            late_acks = []

            async def put(i):
                t0 = loop.time()
                try:
                    await io.write_full("hot", bytes([i]) * 512,
                                        timeout=deadline_s)
                except (IOError, OSError, TimeoutError):
                    return 0
                if loop.time() - t0 > deadline_s + 0.25:
                    late_acks.append(i)
                return 1

            acked = sum(await asyncio.gather(
                *[put(i) for i in range(6)]))
            deadline = loop.time() + 10.0
            shed = 0
            while loop.time() < deadline:
                shed = sum(o.perf.get("osd_ops_shed_expired")
                           for o in cluster.osds.values())
                if shed > 0:
                    break
                await asyncio.sleep(0.05)
            fc = client.objecter.flow_counters()
            return acked, shed, late_acks, fc
        finally:
            await cluster.stop()

    acked, shed, late_acks, fc = run(scenario())
    assert fc["client_batch_ticks"] > 0, "ops never coalesced"
    assert late_acks == [], f"ops acked past deadline: {late_acks}"
    assert shed > 0, "expired queued ops executed instead of shed"
    assert acked >= 1  # the head of the queue still made it
