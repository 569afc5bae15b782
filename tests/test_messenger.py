"""Messenger reliability: sessions, reconnect, ordered replay.

Reference semantics: AsyncConnection out_seq/out_q replay after a session
reset (src/msg/async/AsyncConnection.cc) — ordered at-least-once delivery
toward idempotent handlers.
"""

import asyncio
import mmap
import os

from tests._flaky import contention_retry
import pytest

from ceph_tpu.cluster import messages as M
from ceph_tpu.cluster import messenger as msgr
from ceph_tpu.cluster.messenger import (
    Connection,
    Dispatcher,
    EntityName,
    Message,
    Messenger,
)
from ceph_tpu.utils.perf import KERNELS
from dataclasses import dataclass, field
from typing import List


@dataclass
class Num(Message):
    n: int = 0


class Collector(Dispatcher):
    """Numbers as they arrive; of a shard sub-write its shard number
    (and in ``blobs`` what it carried); ``resets`` counts the
    connections the messenger tore down."""

    def __init__(self):
        self.got: List[int] = []
        self.blobs: List[tuple] = []
        self.msgs: List[Message] = []
        self.resets = 0

    async def ms_dispatch(self, conn: Connection, msg) -> bool:
        if isinstance(msg, Num):
            self.got.append(msg.n)
            return True
        if isinstance(msg, M.MOSDECSubOpWrite):
            self.got.append(msg.shard)
            self.blobs.append((msg.shard, bytes(msg.data)))
            self.msgs.append(msg)
            return True
        if isinstance(msg, (M.MOSDECSubOpWriteBatch, M.MOSDOp)):
            self.msgs.append(msg)
            return True
        return False

    async def ms_handle_reset(self, conn: Connection) -> None:
        self.resets += 1


async def _until(cond, timeout: float = 10.0) -> bool:
    """Converge-poll: ``cond()`` within ``timeout`` seconds."""
    deadline = asyncio.get_event_loop().time() + timeout
    while not cond() and asyncio.get_event_loop().time() < deadline:
        await asyncio.sleep(0.02)
    return cond()


def _oob_bytes() -> int:
    return KERNELS.get("msgr_oob_bytes")


def run(coro):
    return asyncio.run(coro)


def _payload(i: int, size: int) -> bytes:
    return bytes([i % 251]) * size


# the ack delay of the two cases of a reset: no ack has left when the
# connection dies (every frame since the start is replayed), or every
# ack left at the next turn of the loop (about what the parent did)
ACKS = {"acks_owed": 30.0, "acks_sent": 0.0}


@pytest.fixture(params=list(ACKS))
def acks(request, monkeypatch):
    monkeypatch.setattr(msgr, "_ACK_DELAY_S", ACKS[request.param])
    return request.param


@pytest.mark.parametrize("size", [0, 200_000],
                         ids=["in_band", "out_of_band"])
def test_reconnect_replays_unacked_in_order(size, acks):
    """Kill the TCP connection mid-stream: every message still arrives,
    in order (duplicates allowed — at-least-once), nothing lost.  With
    ``size`` the frames are sub-writes whose data rides out of band: the
    replay buffer holds the pickle and references to the buffers, and
    what is replayed are the bytes the first send carried.  With acks
    owed at the reset the replay is the whole stream so far."""
    async def scenario():
        rx = Messenger(EntityName("osd", 1))
        coll = Collector()
        rx.add_dispatcher(coll)
        addr = await rx.bind()
        tx = Messenger(EntityName("osd", 2))
        # a daemon listens: its session is known again on a new connection
        await tx.bind()
        try:
            total = 60
            for i in range(total):
                if i in (20, 40):
                    # hard-drop the transport under the sender's feet
                    conn = tx._out.get(tuple(addr))
                    if conn:
                        conn.stream.close()
                await tx.send_message(
                    M.MOSDECSubOpWrite(shard=i, data=_payload(i, size))
                    if size else Num(n=i), addr)
            # converge-poll: reconnect + replay land asynchronously
            deadline = asyncio.get_event_loop().time() + 10.0
            while set(coll.got) < set(range(total)) and \
                    asyncio.get_event_loop().time() < deadline:
                await asyncio.sleep(0.02)
            # completeness: every n delivered at least once
            assert set(coll.got) == set(range(total)), \
                sorted(set(range(total)) - set(coll.got))
            # order: the dedup'ed sequence is exactly 0..N-1
            dedup = []
            for n in coll.got:
                if not dedup or n > dedup[-1]:
                    dedup.append(n)
            assert dedup == list(range(total))
            if acks == "acks_owed":
                # each reset replayed more than the frame that met it
                assert len(coll.got) >= total + 20
            if size:
                assert all(blob == _payload(i, size)
                           for i, blob in coll.blobs), \
                    [i for i, blob in coll.blobs
                     if blob != _payload(i, size)]
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


def test_reconnect_survives_receiver_restart(acks):
    """The receiving endpoint dies completely and comes back on the same
    port: the unacked tail replays to the new incarnation.  An old one
    that died owing its acks has freed nothing of the sender's buffer,
    and nothing the new one says frees a frame it has not taken."""
    async def scenario():
        rx = Messenger(EntityName("osd", 1))
        coll = Collector()
        rx.add_dispatcher(coll)
        addr = await rx.bind()
        tx = Messenger(EntityName("osd", 2))
        try:
            for i in range(10):
                await tx.send_message(Num(n=i), addr)
            # converge-poll: let the first batch drain before the kill
            deadline = asyncio.get_event_loop().time() + 10.0
            while set(coll.got) < set(range(10)) and \
                    asyncio.get_event_loop().time() < deadline:
                await asyncio.sleep(0.02)
            sess = tx._sessions[tuple(addr)]
            if acks == "acks_owed":
                assert list(sess.unacked) == list(range(1, 11))
            await rx.shutdown()

            rx2 = Messenger(EntityName("osd", 1))
            coll2 = Collector()
            rx2.add_dispatcher(coll2)
            await rx2.bind(host=addr[0], port=addr[1])
            try:
                for i in range(10, 20):
                    await tx.send_message(Num(n=i), addr)
                # converge-poll: the tail replays to the new incarnation
                deadline = asyncio.get_event_loop().time() + 10.0
                while not set(range(10, 20)) <= set(coll2.got) and \
                        asyncio.get_event_loop().time() < deadline:
                    await asyncio.sleep(0.02)
                got = set(coll2.got)
                # the new incarnation received at least the new tail; any
                # unacked old frames replayed too (at-least-once)
                assert set(range(10, 20)) <= got, sorted(got)
                if acks == "acks_owed":
                    assert list(sess.unacked) == list(range(1, 21))
                    dedup = sorted(set(coll2.got))
                    assert [n for i, n in enumerate(coll2.got)
                            if n not in coll2.got[:i]] == dedup
            finally:
                await rx2.shutdown()
        finally:
            await tx.shutdown()

    run(scenario())


def test_unreachable_peer_raises_after_retries():
    async def scenario():
        tx = Messenger(EntityName("client", 9))
        try:
            with pytest.raises((ConnectionError, OSError)):
                await tx.send_message(Num(n=1), ("127.0.0.1", 1))
        finally:
            await tx.shutdown()

    run(scenario())


@contention_retry()
def test_ec_write_survives_connection_drops():
    """Cluster-level: EC writes while the primary's osd-osd connections
    are repeatedly hard-dropped — no silent shard divergence: every
    object remains readable and every acting shard holder converges."""
    async def scenario():
        from ceph_tpu.cluster.vstart import start_cluster

        cluster = await start_cluster(4)
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "ecdrop", "erasure", pg_num=4,
                ec_profile={"plugin": "jerasure",
                            "technique": "reed_sol_van",
                            "k": "2", "m": "1"})
            io = client.ioctx(pool)
            payloads = {}
            for i in range(12):
                oid = f"obj{i}"
                payloads[oid] = f"drop-{i}-".encode() * 120
                if i % 3 == 1:
                    # sever every osd-to-osd connection in the cluster
                    for osd in cluster.osds.values():
                        for conn in list(osd.messenger._out.values()):
                            conn.stream.close()
                await io.write_full(oid, payloads[oid], timeout=60)
            for oid, data in payloads.items():
                assert await io.read(oid, timeout=60) == data, oid

            # shard-level convergence: every acting member holds its
            # shard (replays after the drops land asynchronously —
            # converge-poll, then assert)
            def _all_shards_present() -> bool:
                for oid in payloads:
                    pgid = client.objecter.object_pgid(pool, oid)
                    _, _, acting, _ = \
                        client.objecter.osdmap.pg_to_up_acting_osds(pgid)
                    for o in acting:
                        if o >= 0 and o in cluster.osds and \
                                cluster.osds[o].store.stat(
                                    f"pg_{pgid.pool}_{pgid.seed}",
                                    oid) is None:
                            return False
                return True

            deadline = asyncio.get_event_loop().time() + 15.0
            while not _all_shards_present() and \
                    asyncio.get_event_loop().time() < deadline:
                await asyncio.sleep(0.05)
            for oid in payloads:
                pgid = client.objecter.object_pgid(pool, oid)
                _, _, acting, _ = \
                    client.objecter.osdmap.pg_to_up_acting_osds(pgid)
                for o in acting:
                    if o >= 0 and o in cluster.osds:
                        assert cluster.osds[o].store.stat(
                            f"pg_{pgid.pool}_{pgid.seed}", oid) is not None, \
                            (oid, o)
        finally:
            await cluster.stop()

    run(scenario())


def test_signed_cluster_end_to_end_and_rejects_unsigned():
    """cephx-lite: a secret-keyed cluster serves I/O normally; unsigned
    or tampered frames never reach a dispatcher."""
    async def scenario():
        from ceph_tpu.cluster.vstart import _fast_config, start_cluster

        cfg = _fast_config()
        cfg.auth_shared_secret = "sekrit"
        cluster = await start_cluster(3, config=cfg)
        try:
            client = await cluster.client()
            pool = await client.pool_create("authp", "replicated",
                                            pg_num=8, size=2)
            io = client.ioctx(pool)
            await io.write_full("obj", b"signed-payload" * 50)
            assert await io.read("obj") == b"signed-payload" * 50

            # an UNSIGNED client cannot talk to the signed cluster
            from ceph_tpu.cluster.objecter import RadosClient
            from ceph_tpu.utils import Config

            rogue = RadosClient(cluster.mon_addr, name="rogue",
                                config=Config())
            with pytest.raises((asyncio.TimeoutError, ConnectionError,
                                OSError, TimeoutError)):
                await asyncio.wait_for(rogue.connect(), timeout=3)
            await rogue.shutdown()
        finally:
            await cluster.stop()

    run(scenario())


def test_tampered_frame_rejected():
    async def scenario():
        rx = Messenger(EntityName("osd", 1), secret=b"k")
        coll = Collector()
        rx.add_dispatcher(coll)
        addr = await rx.bind()
        tx = Messenger(EntityName("osd", 2), secret=b"k")
        try:
            await tx.send_message(Num(n=1), addr)
            # converge-poll: the signed frame lands first
            deadline = asyncio.get_event_loop().time() + 10.0
            while coll.got != [1] and \
                    asyncio.get_event_loop().time() < deadline:
                await asyncio.sleep(0.02)
            # flip a byte inside the next frame by writing raw garbage on
            # a fresh socket (wrong signature)
            import pickle as p
            import struct

            reader, writer = await asyncio.open_connection(*addr)
            m = Num(n=666)
            m.src = EntityName("osd", 3)
            payload = p.dumps(m) + b"\x00" * 16
            writer.write(struct.pack("<I", len(payload)) + payload)
            await writer.drain()
            # negative-condition window: give the rx loop the chance to
            # (wrongly) dispatch the forged frame — there is no positive
            # state to converge on when asserting an absence
            await asyncio.sleep(0.2)  # graftlint: ignore[fixed-sleep-in-tests]
            writer.close()
            assert coll.got == [1]      # forged 666 never dispatched
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


from dataclasses import dataclass as _dataclass

from ceph_tpu.cluster.messenger import Message as _Message


@_dataclass
class _Blob(_Message):
    data: bytes = b""


def test_byte_throttle_backpressure():
    """VERDICT r4 weak #6: per-peer-type byte-budget backpressure — a
    slow dispatcher makes fast senders WAIT (socket drain stops) instead
    of growing an unbounded queue (reference osd_client_message_size_cap
    throttle, ceph_osd.cc:511-525)."""
    import asyncio

    from ceph_tpu.cluster.messenger import (
        EntityName, Messenger, Dispatcher, Policy, Throttle)

    async def scenario():
        gate = asyncio.Event()
        in_dispatch = []

        class Slow(Dispatcher):
            async def ms_dispatch(self, conn, msg):
                if isinstance(msg, _Blob):
                    in_dispatch.append(len(msg.data))
                    await gate.wait()
                    return True
                return False

        server = Messenger(EntityName("osd", 0))
        server.add_dispatcher(Slow())
        # budget admits ONE 64 KiB frame at a time
        server.set_policy("client", Policy(
            lossy=True, throttle=Throttle(100_000)))
        addr = await server.bind()
        senders = [Messenger(EntityName("client", i)) for i in (1, 2, 3)]
        try:
            for s in senders:
                await s.send_message(_Blob(data=b"x" * 65536), addr)
            loop = asyncio.get_event_loop()
            deadline = loop.time() + 10.0
            while len(in_dispatch) < 1 and loop.time() < deadline:
                await asyncio.sleep(0.02)
            # negative-condition window: the OTHER two frames must NOT
            # enter dispatch while the byte budget is held — an absence
            # has no positive state to converge on
            await asyncio.sleep(0.3)  # graftlint: ignore[fixed-sleep-in-tests]
            # only one frame admitted into dispatch; the rest backpressure
            assert len(in_dispatch) == 1, in_dispatch
            gate.set()
            deadline = loop.time() + 10.0
            while len(in_dispatch) < 3 and loop.time() < deadline:
                await asyncio.sleep(0.02)
            assert len(in_dispatch) == 3, in_dispatch
        finally:
            gate.set()
            for s in senders:
                await s.shutdown()
            await server.shutdown()

    asyncio.run(scenario())


# --------------------------------- the ack is state, not a frame (PR 35)

@dataclass
class Rep(Message):
    n: int = 0


class Replier(Collector):
    """Answers every ``Num`` with a ``Rep``: over its own session to the
    sender's listening address, as ``osd.py::_reply_osd`` does, or raw
    on the connection the ``Num`` came in on, as an op's reply goes."""

    def __init__(self, messenger, lane):
        super().__init__()
        self.messenger, self.lane = messenger, lane
        self.reps: List[int] = []

    async def ms_dispatch(self, conn, msg) -> bool:
        if isinstance(msg, Rep):
            self.reps.append(msg.n)
            return True
        if isinstance(msg, Num) and self.lane == "session":
            await self.messenger.send_message(Rep(n=msg.n), msg.src_addr)
        elif isinstance(msg, Num) and self.lane == "raw":
            await conn.send(Rep(n=msg.n))
        return await super().ms_dispatch(conn, msg)


def _ack_counters(since=(0, 0, 0)):
    """Frames made, session frames received, those whose ack was
    carried: as they stand, or their growth ``since``."""
    return [KERNELS.get(c) - was for c, was in zip(
        ("msgr_frames", "msgr_acks_owed", "msgr_acks_carried"), since)]


async def _bound_pair(lane):
    """Two messengers that both listen, each answering the other."""
    a, b = Messenger(EntityName("osd", 1)), Messenger(EntityName("osd", 2))
    da, db = Replier(a, lane), Replier(b, lane)
    a.add_dispatcher(da)
    b.add_dispatcher(db)
    return a, b, da, db, await a.bind(), await b.bind()


@pytest.mark.parametrize("lane", ["session", "raw"])
def test_request_and_reply_frame_no_ack(lane, monkeypatch):
    """Twenty requests, each answered: forty frames and not one
    ``_MsgAck``; every ack but the last reply's left inside the frame
    that went back anyway, and both replay buffers are trimmed by them.
    The last one goes alone once nothing has carried it for the ack
    delay: one frame more."""
    monkeypatch.setattr(msgr, "_ACK_DELAY_S", 0.3)

    async def scenario():
        a, b, da, db, addr_a, addr_b = await _bound_pair(lane)
        try:
            total = 20
            before = _ack_counters()
            for i in range(total):
                await a.send_message(Num(n=i), addr_b)
                assert await _until(lambda: len(da.reps) == i + 1)
                # the reply brought the request's ack
                assert not a._sessions[addr_b].unacked
            grew = _ack_counters(before)
            if lane == "session":
                # the replies are session frames too: each request
                # carries the ack of the reply before it
                assert grew == [2 * total, 2 * total, 2 * total - 1]
                assert list(b._sessions[addr_a].unacked) == [total]
                assert await _until(
                    lambda: not b._sessions[addr_a].unacked, 5.0)
                assert _ack_counters(before)[0] == 2 * total + 1
            else:
                assert grew == [2 * total, total, total]
                assert addr_a not in b._sessions
        finally:
            await a.shutdown()
            await b.shutdown()

    run(scenario())


def test_replayed_tail_is_answered_with_one_ack(monkeypatch):
    """Ten frames taken and their acks owed when the connection dies:
    the next send replays all ten before its own, the receiver knows
    the session again by its sid and listening address, and the first
    replayed frame is answered at once with ONE ``_MsgAck`` that frees
    the ten; all are dispatched again (at-least-once)."""
    monkeypatch.setattr(msgr, "_ACK_DELAY_S", 60.0)

    async def scenario():
        rx, tx, coll, addr = await _pair()
        await tx.bind()
        try:
            for i in range(10):
                await tx.send_message(Num(n=i), addr)
            assert await _until(lambda: len(coll.got) == 10)
            sess = tx._sessions[tuple(addr)]
            assert len(sess.unacked) == 10
            before = _ack_counters()
            tx._out[tuple(addr)].stream.close()
            await tx.send_message(Num(n=10), addr)
            assert await _until(lambda: list(sess.unacked) == [11])
            assert coll.got == list(range(10)) + list(range(11))
            assert _ack_counters(before) == [2, 11, 0]
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


def test_one_way_stream_is_acked_within_the_delay(monkeypatch):
    """Nothing goes back: the stream's acks are owed until the delay has
    passed, then ONE ``_MsgAck`` frees all of it."""
    monkeypatch.setattr(msgr, "_ACK_DELAY_S", 0.4)

    async def scenario():
        rx, tx, coll, addr = await _pair()
        try:
            before = _ack_counters()
            for i in range(10):
                await tx.send_message(Num(n=i), addr)
            assert await _until(lambda: len(coll.got) == 10)
            sess = tx._sessions[tuple(addr)]
            assert len(sess.unacked) == 10
            assert await _until(lambda: not sess.unacked, 5.0)
            assert _ack_counters(before) == [11, 10, 0]
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


@pytest.mark.parametrize("replies", ["none", "session"])
def test_replay_buffer_is_bounded_under_shard_sized_frames(replies,
                                                           monkeypatch):
    """Sub-writes of 1 MiB, eight in flight, with an ack delay nothing
    reaches.  Unanswered, the bytes threshold frees them: an ack alone
    every ``_ACK_BYTES``, so the sender never holds more than that plus
    what a connection has in flight (its two socket buffers and the
    receiver's queue) and one frame.  Answered as an OSD answers, the
    replies carry the acks and the buffer never passes the window."""
    monkeypatch.setattr(msgr, "_ACK_DELAY_S", 60.0)
    size, total, window = 1 << 20, 64, 8

    class Committer(Collector):
        def __init__(self, messenger):
            super().__init__()
            self.messenger = messenger

        async def ms_dispatch(self, conn, msg) -> bool:
            if isinstance(msg, M.MOSDECSubOpWrite):
                self.got.append(msg.shard)
                if replies == "session":
                    await self.messenger.send_message(
                        Rep(n=msg.shard), msg.src_addr)
                return True
            return False

    async def scenario():
        a, b, da, _, addr_a, addr_b = await _bound_pair("session")
        commits = Committer(b)
        b.dispatchers.insert(0, commits)
        try:
            before = _ack_counters()
            held = []
            for i in range(total):
                await a.send_message(
                    M.MOSDECSubOpWrite(shard=i, data=_payload(i, size)),
                    addr_b)
                if replies == "session":
                    assert await _until(
                        lambda: len(da.reps) > i - window)
                unacked = a._sessions[addr_b].unacked
                held.append((len(unacked), sum(
                    len(p) + sum(len(x) for x in bufs)
                    for p, bufs in unacked.values())))
            assert await _until(lambda: len(commits.got) == total, 30.0)
            grew = _ack_counters(before)
            frame = size + 1024
            if replies == "none":
                in_flight = 2 * msgr._SOCK_BUF + msgr._STREAM_LIMIT
                bound = msgr._ACK_BYTES + in_flight + frame
                # an ack alone for every threshold's worth, no more
                assert grew == [total + total * size // msgr._ACK_BYTES,
                                total, 0]
                # (the last threshold's ack may still be on its way: a
                # sender thread's frame can be taken in the turn its
                # drain returns in)
                assert await _until(
                    lambda: len(a._sessions[addr_b].unacked)
                    < msgr._ACK_BYTES // size)
            else:
                bound = window * frame
                assert await _until(lambda: len(da.reps) == total)
                assert _ack_counters(before)[0] == 2 * total
            assert max(n for n, _ in held) <= bound // size, held
            assert max(nbytes for _, nbytes in held) <= bound, held
        finally:
            await a.shutdown()
            await b.shutdown()

    run(scenario())


def test_frames_owed_near_the_buffer_bound_are_acked_at_once(monkeypatch):
    """A one-way stream of small frames, faster than any delay: the ack
    goes alone every ``_Owed.MAX_FRAMES``, a quarter of what the
    sender's buffer holds, so a stream nothing answers never overflows
    it."""
    monkeypatch.setattr(msgr, "_ACK_DELAY_S", 60.0)

    async def scenario():
        rx, tx, coll, addr = await _pair()
        try:
            every = msgr._Owed.MAX_FRAMES
            total = 2 * msgr._Session.MAX_UNACKED
            sess = None
            for i in range(total):
                await tx.send_message(Num(n=i), addr)
                await asyncio.sleep(0)      # a sender that lets others run
                sess = sess or tx._sessions[tuple(addr)]
                assert not sess.overflowed
            assert await _until(lambda: len(coll.got) == total)
            assert await _until(lambda: len(sess.unacked) < every)
            assert coll.got == list(range(total))
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


def test_ack_naming_a_dead_incarnation_trims_nothing(monkeypatch):
    """A messenger is replaced by a new one on the same address while
    its peer still owes the old one's session acks.  The peer's next
    frame to that address carries them, named by the old ``sid``: the
    new incarnation's buffer, whose sequence numbers they would cover,
    keeps every frame; an ack that names ITS session trims."""
    monkeypatch.setattr(msgr, "_ACK_DELAY_S", 60.0)

    async def scenario():
        rx, old, coll, addr = await _pair()
        host, port = await old.bind()
        new = Messenger(EntityName("osd", 2))
        got = Collector()
        new.add_dispatcher(got)
        try:
            for i in range(5):
                await old.send_message(Num(n=i), addr)
            assert await _until(lambda: len(coll.got) == 5)
            owed = rx._owed_to[(host, port)]
            assert owed.ack() == (old.sid, 5)
            await old.shutdown()
            await new.bind(host, port)
            # the new incarnation's own session to rx, three frames that
            # rx has not answered: hold them back from rx's notice of the
            # new sid by writing them into the buffer only
            sess = new._sessions[tuple(addr)] = msgr._Session()
            for seq in (1, 2, 3):
                sess.seq = seq
                sess.buffer(seq, msgr._encode(Num(n=100 + seq)))
            # what rx sends next to that address carries the old ack
            await rx.send_message(Num(n=7), (host, port))
            assert await _until(lambda: got.got == [7])
            assert list(sess.unacked) == [1, 2, 3]
            assert owed.ack() is None       # it left, and was not taken
            # the control: the same frame naming the session that is there
            conn = await rx.connect((host, port))
            named = Num(n=8)
            named.src, named.src_addr = rx.name, rx.my_addr
            named.ack = (new.sid, 2)
            conn.stream.write(msgr._frame_parts(None, msgr._encode(named)))
            assert await _until(lambda: got.got == [7, 8])
            assert list(sess.unacked) == [3]
        finally:
            await new.shutdown()
            await old.shutdown()
            await rx.shutdown()

    run(scenario())


def test_ack_of_a_frame_that_did_not_set_out_stays_owed(monkeypatch):
    """The peer cannot be reached at its listening address (a one-way
    partition): the session frame that would have carried the ack fails,
    and the ack is not lost with it — the raw reply on the connection
    the request came in on takes it (``_reply_osd``'s fallback)."""
    monkeypatch.setattr(msgr, "_ACK_DELAY_S", 60.0)

    async def scenario():
        a, b, da, db, addr_a, addr_b = await _bound_pair("none")
        try:
            await a.send_message(Num(n=1), addr_b)
            assert await _until(lambda: db.got == [1])
            owed = b._owed_to[addr_a]
            assert owed.ack() == (a.sid, 1)

            async def refuse(addr):
                raise ConnectionRefusedError("partitioned")

            monkeypatch.setattr(b, "_open", refuse)
            with pytest.raises(ConnectionError):
                await b.send_message(Rep(n=1), addr_a)
            assert owed.ack() == (a.sid, 1)
            assert list(a._sessions[addr_b].unacked) == [1]
            await b._accepted[0].send(Rep(n=1))
            assert await _until(lambda: da.reps == [1])
            assert owed.ack() is None
            assert not a._sessions[addr_b].unacked
        finally:
            await a.shutdown()
            await b.shutdown()

    run(scenario())


@pytest.mark.parametrize("cell_name", [
    "k2m1_write_4m_t16", "k2m1_write_64k_t16", "k4m2_write_4m_t16",
    "k8m4_write_4m_t16"])
def test_ack_carried_share_reads_the_hand_worked_value(cell_name):
    """48000 session frames received of which 45600 had their ack
    carried: 95 %, through the accepted ``counter_ratio`` reader; a
    program without the counters (the parent commit) reads nothing and
    nothing raises."""
    from benchmark.harness import layers
    from benchmark.harness.loader import load_cell

    cell = load_cell(cell_name)
    name = "ack_carried_share.write"
    for growth, want in (
            ({"msgr_acks_owed": 48000, "msgr_acks_carried": 45600},
             pytest.approx(95.0)),
            ({"msgr_acks_owed": 100}, 0.0),
            ({"msgr_frames": 64400}, None)):
        readings = layers.Readings(
            config=cell.config, device_kind="TPU v5 lite", attribution={},
            counters=growth, slice_counters={}, trace=None)
        assert layers.read_metric(name, cell.per_layer[name],
                                  readings) == want


# ------------------------------------- frames without copies (PR 29)

OOB = msgr._OOB_MIN


async def _pair(**kw):
    """A receiving and a sending messenger, the receiver bound."""
    rx = Messenger(EntityName("osd", 1), **kw.get("rx", {}))
    coll = Collector()
    rx.add_dispatcher(coll)
    addr = await rx.bind()
    tx = Messenger(EntityName("osd", 2), **kw.get("tx", {}))
    return rx, tx, coll, addr


@pytest.mark.parametrize("size", [0, 1, OOB - 1, OOB, 4 << 20])
def test_frame_round_trip_by_size(size):
    """Bytes equal at every size; the data leaves the pickle only at or
    over the threshold, and then arrives as a read-only view of the
    buffer the frame was received into: structurally uncopied."""
    async def scenario():
        rx, tx, coll, addr = await _pair()
        try:
            data = os.urandom(size)
            before = _oob_bytes()
            framed = KERNELS.get("msgr_frame_bytes")
            await tx.send_message(M.MOSDECSubOpWrite(shard=3, data=data),
                                  addr)
            assert await _until(lambda: coll.msgs)
            got = coll.msgs[0].data
            assert got == data and len(got) == size
            assert KERNELS.get("msgr_frame_bytes") - framed >= size
            if size < OOB:
                assert _oob_bytes() == before
                assert type(got) is bytes
                return
            assert _oob_bytes() - before == size
            assert type(got) is memoryview and got.readonly
            # the view's owner is the frame: the pickle and the data in
            # one buffer, longer than the data alone
            assert len(got.obj) > size
            if size > msgr._RECV_SCRATCH:
                # a frame of its own buffer, filled by recv_into
                assert type(got.obj) is bytearray
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


def _shape_batch_many():
    datas = [os.urandom(100_000 + i) for i in range(3)]
    msg = M.MOSDECSubOpWriteBatch(items=[
        M.MOSDECSubOpWrite(shard=i, data=d) for i, d in enumerate(datas)])
    return msg, lambda m: [it.data for it in m.items], datas, \
        [memoryview] * 3


def _shape_batch_mixed():
    datas = [os.urandom(100_000), b"tiny", os.urandom(70_000),
             bytearray(os.urandom(100_000))]
    msg = M.MOSDECSubOpWriteBatch(items=[
        M.MOSDECSubOpWrite(shard=i, data=d) for i, d in enumerate(datas)])
    # a bytearray can change after the send returned: it is copied into
    # the pickle, whatever its length, and arrives as what it was
    return msg, lambda m: [it.data for it in m.items], datas, \
        [memoryview, bytes, memoryview, bytearray]


def _shape_mosdop():
    datas = [os.urandom(1 << 20), os.urandom(100_000)]
    msg = M.MOSDOp(oid="o", ops=[
        ("write_full", {"data": datas[0]}),
        ("setxattr", {"name": "k", "value": datas[1]})])
    return msg, lambda m: [m.ops[0][1]["data"], m.ops[1][1]["value"]], \
        datas, [memoryview, bytes]


@pytest.mark.parametrize("shape", [_shape_batch_many, _shape_batch_mixed,
                                   _shape_mosdop],
                         ids=["batch_many", "batch_mixed", "mosdop"])
def test_frame_round_trip_by_shape(shape):
    """Several out-of-band buffers in one frame, a mix with in-band
    items, and an MOSDOp whose data sits in ``ops[i][1]["data"]``."""
    async def scenario():
        rx, tx, coll, addr = await _pair()
        try:
            msg, fields, datas, kinds = shape()
            before = _oob_bytes()
            await tx.send_message(msg, addr)
            assert await _until(lambda: coll.msgs)
            got = fields(coll.msgs[0])
            assert [type(g) for g in got] == kinds
            assert all(g == d for g, d in zip(got, datas))
            views = [g for g in got if type(g) is memoryview]
            assert _oob_bytes() - before == sum(len(v) for v in views)
            # one frame, one buffer: every view has the same owner
            assert len({id(v.obj) for v in views}) == 1
            # the sender's message is as it was
            assert all(type(f) is type(d) for f, d in
                       zip(fields(msg), datas))
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


def _signed():
    return {"rx": {"secret": b"k"}, "tx": {"secret": b"k"}}


def _cephx():
    from ceph_tpu.cluster.auth import CephxContext

    master = b"m" * 32
    return {"rx": {"auth": CephxContext("osd.1", master=master)},
            "tx": {"auth": CephxContext("osd.2", master=master)}}


@pytest.mark.parametrize("mode", [_signed, _cephx],
                         ids=["signed", "cephx"])
def test_flipped_out_of_band_byte_is_refused(mode):
    """The signature covers every out-of-band buffer: one flipped bit
    in a buffer is refused before dispatch and resets the connection,
    as a flipped bit in a pickle is; the session then reconnects."""
    async def scenario():
        rx, tx, coll, addr = await _pair(**mode())
        try:
            await tx.send_message(
                M.MOSDECSubOpWrite(shard=1, data=_payload(1, 200_000)),
                addr)
            assert await _until(lambda: coll.got == [1])
            conn = await tx.connect(addr)
            bad = M.MOSDECSubOpWrite(shard=666, data=_payload(2, 200_000))
            bad.src = tx.name
            parts = msgr._frame_parts(conn._sign_key(),
                                      msgr._encode(bad))
            assert parts[0][4] == msgr._FT_MSG_OOB and len(parts) == 4
            flipped = bytearray(parts[2])
            flipped[100_000] ^= 1
            parts[2] = bytes(flipped)
            conn.stream.write(parts)
            assert await _until(lambda: coll.resets >= 1)
            assert 666 not in coll.got
            # the torn-down connection is replaced and traffic goes on
            await tx.send_message(
                M.MOSDECSubOpWrite(shard=3, data=_payload(3, 200_000)),
                addr)
            assert await _until(lambda: 3 in coll.got)
            assert 666 not in coll.got
            assert coll.blobs[-1] == (3, _payload(3, 200_000))
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


UNPICKLED: List[int] = []


def _touch():
    UNPICKLED.append(1)


class _Boom:
    def __reduce__(self):
        return _touch, ()


def test_out_of_band_frame_on_unauthenticated_cephx_connection():
    """cephx: a connection that has not presented an authorizer may
    send handshake frames only; an out-of-band data frame is refused
    BEFORE any deserialization and the connection is closed."""
    import pickle

    async def scenario():
        rx, tx, coll, addr = await _pair(**_cephx())
        try:
            frame = (pickle.dumps(_Boom(), protocol=5),
                     (memoryview(b"x" * OOB),))
            reader, writer = await asyncio.open_connection(*addr)
            writer.writelines(msgr._frame_parts(None, frame))
            await writer.drain()
            # the server closes on us: EOF, not a reply
            assert await asyncio.wait_for(reader.read(), 10.0) == b""
            writer.close()
            assert UNPICKLED == [] and coll.msgs == []
            assert await _until(lambda: coll.resets >= 1)
            # the control: deserialized, that pickle does leave its mark
            pickle.loads(frame[0])
            assert UNPICKLED == [1]
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


def test_throttle_backpressure_stops_the_socket_drain():
    """One connection, more bytes than the receiver's queue and both
    socket buffers hold, a dispatcher that does not return: the stream
    stops reading its socket, its queue stays bounded, and the SENDER
    stalls in drain — TCP backpressure reached the peer.  When the
    dispatcher lets go, everything arrives in order."""
    from ceph_tpu.cluster.messenger import Policy, Throttle

    async def scenario():
        gate = asyncio.Event()
        seen = []

        class Slow(Dispatcher):
            async def ms_dispatch(self, conn, msg):
                if isinstance(msg, M.MOSDECSubOpWrite):
                    seen.append(msg.shard)
                    await gate.wait()
                    return True
                return False

        rx = Messenger(EntityName("osd", 0))
        rx.add_dispatcher(Slow())
        rx.set_policy("client", Policy(throttle=Throttle(600_000)))
        addr = await rx.bind()
        tx = Messenger(EntityName("client", 1))
        total, size = 64, 512 << 10      # 32 MiB
        sent = []

        async def send_all():
            for i in range(total):
                await tx.send_message(
                    M.MOSDECSubOpWrite(shard=i, data=_payload(i, size)),
                    addr)
                sent.append(i)

        task = asyncio.get_event_loop().create_task(send_all())
        try:
            assert await _until(lambda: seen == [0])
            assert await _until(
                lambda: rx._accepted and
                not rx._accepted[0].stream.transport.is_reading())
            # negative-condition window: nothing more may be admitted,
            # read or sent while the budget is out
            await asyncio.sleep(0.3)  # graftlint: ignore[fixed-sleep-in-tests]
            stream = rx._accepted[0].stream
            assert seen == [0]
            assert not stream.transport.is_reading()
            assert stream._queued < msgr._STREAM_LIMIT + size + 4096
            assert not task.done() and len(sent) < total
            gate.set()
            await asyncio.wait_for(task, 30.0)
            assert await _until(lambda: len(seen) == total, 30.0)
            assert seen == list(range(total))
        finally:
            gate.set()
            task.cancel()
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


@pytest.mark.parametrize("verb", ["write", "write_planar"])
def test_store_copies_what_it_is_given(verb):
    """The store never aliases a frame: what it keeps of a view of a
    receive buffer is a copy of its own (``Transaction.write`` takes it
    at once, ``write_planar`` leaves it to the store's apply, PR 32),
    so a later change of that buffer is not seen and the frame is let
    go."""
    from ceph_tpu.cluster.store import MemStore, Transaction

    frame = bytearray(os.urandom(4096))
    view = memoryview(frame)[1024:3072].toreadonly()
    want = bytes(view)
    txn = Transaction().create_collection("c")
    if verb == "write":
        txn.write("c", "o", 0, view)
        assert type(txn.ops[1][4]) is bytes
    else:
        txn.write_planar("c", "o", 0, view, 256)
    store = MemStore()
    store.queue_transaction(txn)
    del txn, view
    frame[:] = bytes(4096)
    frame.extend(b"\0")     # BufferError while anything views the frame
    obj = store._colls["c"]["o"]
    assert type(obj.data) is bytearray and obj.data == want


def test_stored_object_does_not_alias_the_frame():
    """End to end: a ``write_full`` whose data crossed the wire out of
    band (client op and shard sub-writes alike) is stored as bytes the
    store owns, on every shard holder, and reads back."""
    async def scenario():
        from ceph_tpu.cluster.vstart import _fast_config, start_cluster

        cluster = await start_cluster(3, config=_fast_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "ec", "erasure", pg_num=4,
                ec_profile={"plugin": "jerasure", "k": "2", "m": "1",
                            "technique": "reed_sol_van"})
            io = client.ioctx(pool)
            data = os.urandom(1 << 20)
            before = _oob_bytes()
            await io.write_full("big", data)
            # the client op (1 MiB) and two sub-writes (512 KiB each)
            assert _oob_bytes() - before >= 2 << 20
            held = 0
            for osd in cluster.osds.values():
                for objs in osd.store._colls.values():
                    obj = objs.get("big")
                    if obj is not None:
                        held += 1
                        # its own populated mapping (512 KiB: PR 43)
                        assert type(obj.data.obj) is mmap.mmap
                        assert not obj.data.readonly
                        assert len(obj.data) == 512 << 10
            assert held == 3
            got = await io.read("big")
            assert type(got) is bytes and got == data
        finally:
            await cluster.stop()

    run(scenario())


@pytest.mark.parametrize("cell_name", [
    "k2m1_write_4m_t16", "k2m1_write_64k_t16", "k4m2_write_4m_t16",
    "k8m4_write_4m_t16"])
def test_wire_oob_share_reads_the_hand_worked_value(cell_name):
    """8 GB framed of which 6 GB out of band: 75 %, through the accepted
    ``counter_ratio`` reader; a program without the counters (the parent
    commit) reads nothing and nothing raises."""
    from benchmark.harness import layers
    from benchmark.harness.loader import load_cell

    cell = load_cell(cell_name)
    name = "wire_oob_share.write"
    for growth, want in (
            ({"msgr_frame_bytes": 8_000_000_000,
              "msgr_oob_bytes": 6_000_000_000}, pytest.approx(75.0)),
            ({"msgr_frame_bytes": 1_000_000}, 0.0),
            ({}, None)):
        readings = layers.Readings(
            config=cell.config, device_kind="TPU v5 lite", attribution={},
            counters=growth, slice_counters={}, trace=None)
        assert layers.read_metric(name, cell.per_layer[name],
                                  readings) == want


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_frame_stream_reassembles_any_chunking(seed):
    """The receiver alone, fed as a transport feeds it (``get_buffer``,
    then ``buffer_updated`` with what arrived): frames of every kind —
    tiny, split length prefixes, just fitting the scratch buffer, just
    not fitting, large — in reads of random sizes come out whole, equal
    and in order."""
    import random
    import struct

    class Transport:
        def pause_reading(self):
            pass

        def resume_reading(self):
            pass

    async def scenario():
        rng = random.Random(seed)
        scratch = msgr._RECV_SCRATCH
        sizes = [1, 100, 70_000, 3, scratch - 4, 5, scratch - 3,
                 300_000, 2, 2 << 20, 64, 64, scratch - 4, 40_000]
        rng.shuffle(sizes)
        frames = [rng.randbytes(n) for n in sizes]
        wire = b"".join(struct.pack("<I", len(f)) + f for f in frames)
        stream = msgr._FrameStream()
        stream.transport = Transport()
        pos = 0
        while pos < len(wire):
            buf = stream.get_buffer(-1)
            assert len(buf) > 0
            take = min(len(buf), len(wire) - pos,
                       rng.choice([1, 3, 7, 1000, 70_000, 1 << 20]))
            buf[:take] = wire[pos:pos + take]
            stream.buffer_updated(take)
            pos += take
        got = [await stream.read_frame() for _ in frames]
        assert [bytes(g) for g in got] == frames
        # only what cannot fit the scratch buffer got one of its own
        assert [type(g) is bytearray for g in got] == \
            [4 + len(f) > scratch for f in frames]
        assert stream._queued == 0 and not stream._frames

    run(scenario())


@pytest.mark.parametrize("prefix", [0, msgr._MAX_FRAME + 1, 0xFFFFFFFF])
def test_bad_length_prefix_closes_the_connection(prefix):
    """An empty frame, or a length no frame has: nothing is allocated
    for it, nothing behind it is framed, the connection is reset."""
    import struct

    async def scenario():
        rx, tx, coll, addr = await _pair()
        try:
            reader, writer = await asyncio.open_connection(*addr)
            good = msgr._frame_parts(None, msgr._encode(Num(n=7)))
            writer.write(struct.pack("<I", prefix) + b"".join(good))
            await writer.drain()
            assert await asyncio.wait_for(reader.read(), 10.0) == b""
            writer.close()
            assert await _until(lambda: coll.resets >= 1)
            assert coll.got == []
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


# ------------------------------- large frames on sender threads (PR 50)

IO_MIN = msgr._IO_MIN
IO_COUNTERS = ("msgr_io_send_frames", "msgr_io_send_bytes",
               "msgr_io_call_ns", "msgr_io_wait_ns", "msgr_io_fallback")


def _io(since=None):
    now = {name: KERNELS.get(name) for name in IO_COUNTERS}
    return now if since is None else \
        {name: now[name] - since[name] for name in now}


def _parts(msg):
    """``msg`` framed as a raw send frames it, and the frame's bytes."""
    msg.src = EntityName("osd", 2)
    parts = msgr._frame_parts(None, msgr._encode(msg))
    return parts, sum(len(p) for p in parts)


def _sub_write(i: int, size: int):
    return M.MOSDECSubOpWrite(shard=i, data=_payload(i, size))


def _small_buffers(monkeypatch, nbytes: int = 16 << 10):
    """Every connection made from here on has socket buffers of
    ``nbytes`` (the kernel doubles them), far under a large frame."""
    monkeypatch.setattr(msgr, "_SOCK_BUF", nbytes)


async def _stop_reading(rx, tx, addr):
    """The receiver's end of ``tx``'s connection to it, which reads its
    socket no more (a wedged or throttled read loop) until
    ``resume_reading``: a first small frame makes the connection."""
    await tx.send_message(Num(n=-1), addr)
    assert await _until(lambda: rx._accepted)
    transport = rx._accepted[-1].stream.transport
    transport.pause_reading()
    return transport


def _in_order_at_least_once(got, total):
    assert set(got) >= set(range(total)), sorted(set(range(total)) - set(got))
    dedup = []
    for n in got:
        if n >= 0 and (not dedup or n > dedup[-1]):
            dedup.append(n)
    assert dedup == list(range(total)), got


def test_large_and_small_frames_leave_in_write_order():
    """Small, LARGE, small, small, LARGE, LARGE, small written in one
    turn of the loop: the first large frame goes to a sender thread at
    once and everything behind it queues on the stream, small ones too
    (two threads never write one socket); all arrive in ``write`` order,
    byte for byte; the threads' bytes are exactly the large frames', no
    large frame fell back to the loop, and ``drain`` returns only when
    nothing is queued or with a thread."""
    async def scenario():
        rx, tx, coll, addr = await _pair()
        try:
            conn = await tx.connect(addr)
            stream = conn.stream
            sizes = [10, 1 << 20, 100, 70_000, 4 << 20, IO_MIN, 5]
            before = _io()
            large = 0
            for i, size in enumerate(sizes):
                parts, n = _parts(_sub_write(i, size))
                stream.write(parts)
                if n >= IO_MIN:
                    large += n
                if i == 1:
                    assert stream._io_busy and not stream._pending
            # behind the frame at the thread: five writes, in order
            assert [big for big, _ in stream._pending] == \
                [False, False, True, True, False]
            await stream.drain()
            assert not stream._io_busy and not stream._pending
            assert await _until(lambda: len(coll.got) == len(sizes))
            assert coll.got == list(range(len(sizes)))
            assert coll.blobs == [(i, _payload(i, size))
                                  for i, size in enumerate(sizes)]
            grew = _io(before)
            assert grew["msgr_io_send_frames"] == 3
            assert grew["msgr_io_send_bytes"] == large
            assert grew["msgr_io_fallback"] == 0
            assert grew["msgr_io_call_ns"] > 0 and grew["msgr_io_wait_ns"] > 0
            # the socket the threads wrote to is the stream's own, and
            # no loop account's
            assert type(stream._io_sock) is msgr.socket.socket
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


def test_a_large_frame_waits_for_the_transports_buffer(monkeypatch):
    """Small frames that the transport still buffers (the peer reads
    nothing, the socket is full) and a large one written behind them:
    it goes to no thread while a byte of theirs is with the transport
    (its thread would write into the middle of them), the small frame
    written after it waits behind it, and when the peer reads again
    everything arrives in order."""
    async def scenario():
        _small_buffers(monkeypatch)
        rx, tx, coll, addr = await _pair()
        try:
            peer = await _stop_reading(rx, tx, addr)
            stream = tx._out[tuple(addr)].stream
            n = 0
            while not stream.transport.get_write_buffer_size():
                stream.write(_parts(_sub_write(n, 100_000))[0])
                n += 1
                assert n < 200
            before = _io()
            stream.write(_parts(_sub_write(n, 1 << 20))[0])
            stream.write(_parts(_sub_write(n + 1, 10))[0])
            assert not stream._io_busy and stream._flushing
            assert [big for big, _ in stream._pending] == [True, False]
            await asyncio.sleep(0.1)  # graftlint: ignore[fixed-sleep-in-tests]
            assert not stream._io_busy and len(stream._pending) == 2
            peer.resume_reading()
            await asyncio.wait_for(stream.drain(), 10.0)
            assert not stream._flushing
            # the transport's own limits are back
            assert stream.transport.get_write_buffer_limits() == \
                (16 << 10, 64 << 10)
            assert await _until(lambda: len(coll.got) == 1 + n + 2)
            assert coll.got == list(range(-1, n + 2))
            assert _io(before)["msgr_io_send_frames"] == 1
            assert _io(before)["msgr_io_fallback"] == 0
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


def test_a_large_frame_through_a_small_socket_buffer_arrives_whole(
        monkeypatch):
    """4 MiB of random bytes through socket buffers of 16 KiB: the
    thread's ``sendmsg`` takes a piece at a time and it waits for room
    in between (``poll``), many times over; the frame arrives whole and
    byte for byte, on one thread's account."""
    async def scenario():
        _small_buffers(monkeypatch)
        calls = []
        sendmsg = msgr._sendmsg

        def counted(sock, parts):
            n = sendmsg(sock, parts)
            calls.append(n)
            return n

        monkeypatch.setattr(msgr, "_sendmsg", counted)
        rx, tx, coll, addr = await _pair()
        try:
            data = os.urandom(4 << 20)
            before = _io()
            await tx.send_message(M.MOSDECSubOpWrite(shard=7, data=data),
                                  addr)
            assert await _until(lambda: coll.blobs)
            assert coll.blobs == [(7, data)]
            grew = _io(before)
            assert grew["msgr_io_send_frames"] == 1
            assert grew["msgr_io_send_bytes"] == sum(calls) > len(data)
            assert len(calls) > 8 and max(calls) < len(data)
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


def test_rest_drops_whole_buffers_and_slices_the_one_the_count_ends_in():
    parts = [b"abcd", memoryview(b"efghij"), b"kl"]
    flat = b"abcdefghijkl"
    for n in range(len(flat) + 1):
        rest = msgr._rest(parts, n)
        assert b"".join(rest) == flat[n:], n
        assert all(len(p) for p in rest)
    assert parts == [b"abcd", memoryview(b"efghij"), b"kl"]


def test_a_peer_that_stops_reading_fails_the_drain_and_does_not_hang(
        monkeypatch):
    """The peer's read loop is wedged: the thread's wait for room runs
    to ``_IO_STALL_S`` and the stream fails as on a transport's write
    error: ``drain`` waits that long and then raises
    ``ConnectionResetError``; it does not return and does not hang."""
    async def scenario():
        _small_buffers(monkeypatch)
        monkeypatch.setattr(msgr, "_IO_STALL_S", 0.4)
        rx, tx, coll, addr = await _pair()
        try:
            await _stop_reading(rx, tx, addr)
            stream = tx._out[tuple(addr)].stream
            t0 = asyncio.get_event_loop().time()
            stream.write(_parts(_sub_write(0, 8 << 20))[0])
            with pytest.raises(ConnectionResetError):
                await asyncio.wait_for(stream.drain(), 10.0)
            took = asyncio.get_event_loop().time() - t0
            assert 0.4 <= took < 5.0, took
            assert not stream._io_busy and stream._io_sock is None
            assert stream.transport.is_closing()
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


def test_connection_killed_under_a_sender_thread_replays_in_order(
        monkeypatch):
    """The connection dies while a thread holds a frame of it (half
    written: the peer had stopped reading): ``send_message`` replays the
    unacked tail, this frame included, in order on a new connection,
    and the receiver sees every frame at least once and none out of
    order (the half frame is dropped by its ``_FrameStream``)."""
    async def scenario():
        _small_buffers(monkeypatch)
        monkeypatch.setattr(msgr, "_ACK_DELAY_S", 30.0)
        rx, tx, coll, addr = await _pair()
        await tx.bind()
        try:
            total, size = 12, 1 << 20
            sent = []

            async def send_all():
                for i in range(total):
                    await tx.send_message(_sub_write(i, size), addr)
                    sent.append(i)

            for i in range(3):
                await tx.send_message(Num(n=-1), addr)
            peer = rx._accepted[-1].stream.transport
            peer.pause_reading()
            task = asyncio.get_event_loop().create_task(send_all())
            stream = tx._out[tuple(addr)].stream
            assert await _until(lambda: stream._io_busy)
            await asyncio.sleep(0.1)  # graftlint: ignore[fixed-sleep-in-tests]
            assert stream._io_busy and not sent      # stuck half way
            stream.transport.abort()
            await asyncio.wait_for(task, 30.0)
            assert sent == list(range(total))
            assert await _until(
                lambda: set(coll.got) >= set(range(total)))
            _in_order_at_least_once(coll.got, total)
            assert dict(coll.blobs) == {i: _payload(i, size)
                                        for i in range(total)}
            # the thread let the dead stream's socket go
            assert stream._closed.done() and stream._io_sock is None
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


def test_many_streams_keep_their_order_under_a_short_switch_interval():
    """Eight connections, on each a run of large and small frames
    written as fast as ``drain`` allows, two sender threads between
    them and an interpreter that switches threads every 10 us: every
    stream's frames arrive in its own ``write`` order and byte for
    byte, the threads' bytes are exactly the large frames' (a lost
    update of ``_io_busy`` or of a queue would show as a frame out of
    place, twice, or never), and every stream ends idle."""
    import sys

    async def scenario():
        streams, frames = 8, 24
        sizes = [IO_MIN, 50, 1 << 20, 70_000, 3, IO_MIN + 1]
        rxs = []
        tx = Messenger(EntityName("osd", 99))
        before = _io()
        try:
            for s in range(streams):
                rx = Messenger(EntityName("osd", s))
                coll = Collector()
                rx.add_dispatcher(coll)
                rxs.append((rx, coll, await rx.bind()))
            large = [0]

            async def feed(s, addr):
                conn = await tx.connect(addr)
                for i in range(frames):
                    size = sizes[(i + s) % len(sizes)]
                    parts, n = _parts(_sub_write(i, size))
                    if n >= IO_MIN:
                        large[0] += n
                    conn.stream.write(parts)
                    if i % 3 == 2:
                        await conn.stream.drain()
                await conn.stream.drain()
                assert not conn.stream._io_busy and not conn.stream._pending

            await asyncio.wait_for(asyncio.gather(*(
                feed(s, addr) for s, (_, _, addr) in enumerate(rxs))), 60.0)
            assert await _until(lambda: all(
                len(coll.got) == frames for _, coll, _ in rxs), 30.0)
            for s, (_, coll, _) in enumerate(rxs):
                assert coll.got == list(range(frames)), s
                assert coll.blobs == [
                    (i, _payload(i, sizes[(i + s) % len(sizes)]))
                    for i in range(frames)], s
            grew = _io(before)
            assert grew["msgr_io_send_bytes"] == large[0]
            assert grew["msgr_io_send_frames"] == \
                streams * frames * 3 // len(sizes)
            assert grew["msgr_io_fallback"] == 0
        finally:
            await tx.shutdown()
            for rx, _, _ in rxs:
                await rx.shutdown()

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        run(scenario())
    finally:
        sys.setswitchinterval(was)


class _Fated:
    """A chaos injector that gives the ``at``-th session frame one
    fate and every other frame none."""

    def __init__(self, at: int, **fate):
        self.at, self.fate, self.n = at, fate, 0

    def check_connect(self, addr):
        pass

    def mutate_batch(self, msg):
        pass

    def on_frame(self, addr):
        from ceph_tpu.chaos.net import FrameFate

        self.n += 1
        return FrameFate(**self.fate) if self.n == self.at else FrameFate()


FATES = {"dup": {"dup": True}, "drop": {"drop": True, "retransmit": 0.05},
         "reorder": {"reorder": 0.2}, "reset": {"reset": True}}


@pytest.mark.parametrize("size", [100, 1 << 20], ids=["small", "large"])
@pytest.mark.parametrize("fate", list(FATES))
def test_a_chaos_fate_on_a_large_frame_is_as_on_a_small_one(fate, size):
    """One frame of ten meets the fate; what the receiver sees is the
    same whether the frames go through the transport or through a
    sender thread: a duplicate arrives twice running, a dropped frame
    after its retransmission timer (and what was sent behind it, held
    back by the gate), a reordered one after its successors, and a
    reset costs nothing: everything at least once, in order but for
    the reordered frame."""
    async def scenario():
        rx, tx, coll, addr = await _pair()
        await tx.bind()
        try:
            total, at = 10, 5
            before = _io()
            tx.chaos = _Fated(at + 1, **FATES[fate])
            for i in range(total):
                await tx.send_message(_sub_write(i, size), addr)
            assert await _until(lambda: set(coll.got) >= set(range(total)))
            if fate == "reorder":
                assert await _until(lambda: coll.got.count(at) >= 1)
                late = [n for n in coll.got if n != at]
                assert [n for n in late if n < at] + \
                    sorted(set(n for n in late if n > at)) == \
                    [n for n in range(total) if n != at]
                assert coll.got.index(at) > coll.got.index(at + 1)
            else:
                _in_order_at_least_once(coll.got, total)
            if fate == "dup":
                assert coll.got == [*range(at + 1), *range(at, total)]
            if fate == "reset":
                assert await _until(lambda: coll.resets >= 1)
            assert all(blob == _payload(i, size) for i, blob in coll.blobs)
            grew = _io(before)
            if size >= IO_MIN:
                assert grew["msgr_io_send_frames"] >= total
                assert grew["msgr_io_fallback"] == 0
            else:
                assert grew["msgr_io_send_frames"] == 0
        finally:
            tx.chaos = None
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


@pytest.mark.parametrize("how", ["close", "shutdown"])
def test_close_with_a_frame_at_a_sender_thread_is_bounded(how, monkeypatch):
    """A thread waits for room in a socket whose peer reads nothing:
    ``Connection.close`` and ``Messenger.shutdown`` give the frame up,
    do not return while the thread still holds the socket, and return
    well within ``_CLOSE_WAIT_S``."""
    async def scenario():
        _small_buffers(monkeypatch)
        rx, tx, coll, addr = await _pair()
        try:
            await _stop_reading(rx, tx, addr)
            conn = tx._out[tuple(addr)]
            stream = conn.stream
            stream.write(_parts(_sub_write(0, 8 << 20))[0])
            assert stream._io_busy
            await asyncio.sleep(0.1)  # graftlint: ignore[fixed-sleep-in-tests]
            assert stream._io_busy
            t0 = asyncio.get_event_loop().time()
            if how == "close":
                await conn.close()
            else:
                await tx.shutdown()
            took = asyncio.get_event_loop().time() - t0
            assert took < msgr._CLOSE_WAIT_S, took
            assert not stream._io_busy and stream._io_sock is None
            assert stream._closed.done()
            with pytest.raises(ConnectionResetError):
                await stream.drain()
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


def test_a_frame_one_byte_under_the_line_stays_on_the_loop():
    """``_IO_MIN - 1`` bytes of frame go to the transport as every frame
    did; ``_IO_MIN`` go to a thread."""
    async def scenario():
        rx, tx, coll, addr = await _pair()
        try:
            conn = await tx.connect(addr)
            _, overhead = _parts(_sub_write(0, IO_MIN - 1000))
            overhead -= IO_MIN - 1000
            for i, want in enumerate((IO_MIN - 1, IO_MIN)):
                parts, n = _parts(_sub_write(i, want - overhead))
                assert n == want
                before = _io()
                conn.stream.write(parts)
                assert conn.stream._io_busy == (want >= IO_MIN)
                await conn.stream.drain()
                assert _io(before)["msgr_io_send_frames"] == \
                    (want >= IO_MIN)
            assert await _until(lambda: coll.got == [0, 1])
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


def test_a_cluster_that_sends_no_large_frame_starts_no_thread(monkeypatch):
    """Ops of 64 KiB and sub-writes of 32 KiB on a k2m1 pool, one op at
    a time, never make a frame of ``_IO_MIN``: the sender threads are
    not started and nothing is counted; the first 4 MiB op starts both.
    (The LENGTH decides, not the op: sixteen such ops in flight at once
    are batched by the client and the sub-write batcher into frames of
    up to 1 MiB and 512 KiB, which do go to a thread, in the 64 KiB
    cell too.)"""
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster

    async def scenario():
        senders = msgr._Senders()
        monkeypatch.setattr(msgr, "_IO", senders)
        cluster = await start_cluster(3, config=_fast_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "p", "erasure", pg_num=8, ec_profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
            io = client.ioctx(pool)
            before = _io()
            small = {f"s{i}": os.urandom(64 << 10) for i in range(16)}
            for n, d in small.items():
                await io.write_full(n, d, timeout=120)
            assert [await io.read(n) for n in small] == list(small.values())
            assert senders.threads == [] and senders.jobs.empty()
            assert _io(before) == dict.fromkeys(IO_COUNTERS, 0)
            big = os.urandom(4 << 20)
            await io.write_full("big", big, timeout=120)
            assert await io.read("big") == big
            assert sorted(t.name for t in senders.threads) == \
                [f"msgr-send-{i}" for i in range(msgr._IO_THREADS)]
            assert all(t.daemon for t in senders.threads)
            grew = _io(before)
            # the op to the primary and a 2 MiB shard to each of the
            # two other holders, at least (the reads' replies too)
            assert grew["msgr_io_send_frames"] >= 3
            assert grew["msgr_io_send_bytes"] >= 8 << 20
            assert grew["msgr_io_fallback"] == 0
        finally:
            await cluster.stop()

    run(scenario())


def test_a_stream_with_no_descriptor_to_write_to_falls_back_to_the_loop(
        monkeypatch):
    """A transport that gives no socket to duplicate: the loop sends the
    large frame itself, as before PR 50, and ``msgr_io_fallback`` says
    so (a cell must read 0 there)."""
    async def scenario():
        monkeypatch.setattr(msgr._FrameStream, "_io_socket",
                            lambda self: None)
        rx, tx, coll, addr = await _pair()
        try:
            before = _io()
            for i, size in enumerate((1 << 20, 10, 2 << 20)):
                await tx.send_message(_sub_write(i, size), addr)
            assert await _until(lambda: coll.got == [0, 1, 2])
            grew = _io(before)
            assert grew["msgr_io_fallback"] == 2
            assert grew["msgr_io_send_frames"] == 0
        finally:
            await tx.shutdown()
            await rx.shutdown()

    run(scenario())


IO_METRICS = {
    "io_send_share.write": ("%", "higher", "msgr_io_send_bytes",
                            "msgr_frame_bytes", 100),
    "io_wait_ms_per_op.write": ("ms", "lower", "msgr_io_wait_ns",
                                "ec_coalesced_ops", 1e-6),
}


@pytest.mark.parametrize("name", list(IO_METRICS))
@pytest.mark.parametrize("cell_name", [
    "k2m1_write_4m_t16", "k2m1_write_64k_t16", "k4m2_write_4m_t16",
    "k8m4_write_4m_t16", "lrc_k4m2l3_write_4m_t16",
    "shec_k6m4c3_write_4m_t16", "cauchy_k4m2_write_4m_t16"])
def test_the_sender_threads_metrics_read_the_hand_worked_value(cell_name,
                                                               name):
    """Through the loader and the accepted ``counter_ratio`` reader, in
    every cell that writes: 3 of the numerator over 4 of the denominator
    read 0.75 x scale; a window in which no frame reached ``_IO_MIN``
    (the 64 KiB cell) or of a program without the counters (the parent)
    reads 0.0, since frames and ops grow all the same; and with neither
    counter nothing is read and nothing raises."""
    import json

    from benchmark.harness import layers
    from benchmark.harness.loader import ROOT, load_cell

    unit, better, numerator, denominator, scale = IO_METRICS[name]
    cell = load_cell(cell_name)
    reader = cell.per_layer[name]
    assert (reader["kind"], reader["layer"], reader["unit"],
            reader["numerator"], reader["denominator"], reader["scale"],
            reader["moves"], reader["source"]) == \
        ("counter_ratio", "wire", unit, numerator, denominator, scale,
         "write_MBps", "program_counter")
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["better"] == better
    assert cell_name in entry[0]["workloads"]
    for growth, want in (
            ({numerator: 3_000_000, denominator: 4_000_000},
             pytest.approx(0.75 * scale)),
            ({denominator: 4_000_000}, 0.0),
            ({}, None)):
        readings = layers.Readings(
            config=cell.config, device_kind="TPU v5 lite", attribution={},
            counters=growth, slice_counters={}, trace=None)
        assert layers.read_metric(name, reader, readings) == want
