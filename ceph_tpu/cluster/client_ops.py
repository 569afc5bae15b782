"""Client op dispatch: QoS queue drain, dup detection, op execution
(reference PrimaryLogPG::do_op / do_osd_ops dispatch seam).

Split out of osd.py: everything between "a client message arrived" and
"a backend mutation/read runs" — targeting checks, the dmClock queue,
reqid duplicate detection (pg_log dups analog), and the op interpreter
for data/xattr/omap/exec/watch/notify verbs."""

from __future__ import annotations

import asyncio
import time
from typing import List, Set

from ceph_tpu.cluster import messages as M
from ceph_tpu.cluster.messenger import Connection
from ceph_tpu.cluster.pg import PGMETA, PGState, _coll
from ceph_tpu.cluster.store import Transaction
from ceph_tpu.trace import loopacct


class _BatchConn:
    """Reply router for ops that arrived inside an MOSDOpBatch (round
    18): their MOSDOpReply acks coalesce through the OSD's
    ClientReplyBatcher into MOSDOpReplyBatch ticks; every other send
    (watch/notify pushes, map frames) forwards to the raw connection
    untouched.  Only batch-arrived ops get batched replies — a plain
    MOSDOp frame keeps its plain reply."""

    def __init__(self, osd, raw):
        self._osd = osd
        self._raw = raw

    def __getattr__(self, name):
        return getattr(self._raw, name)

    async def send(self, reply):
        if isinstance(reply, M.MOSDOpReply):
            self._osd._reply_batcher.send(self._raw, reply)
        else:
            await self._raw.send(reply)


class ClientOpsMixin:

    # ----------------------------------------------- admission control
    #
    # Layered admission ahead of dispatch (reference: the osd op/byte
    # throttles feeding ShardedOpWQ): an op beyond the configured
    # budgets is pushed back THROTTLED (-EBUSY) instead of queueing
    # unboundedly — the explicit signal the objecter's AIMD congestion
    # window runs against.  Budgets of 0 (default) admit everything.

    @staticmethod
    def _qos_entity(reqid0) -> str:
        """QoS identity = the STABLE entity name: reqids carry a
        per-incarnation nonce after '#' (dup-cache uniqueness), but
        dmClock shares/limits/budgets attach to the entity."""
        return str(reqid0).split("#", 1)[0]

    @classmethod
    def _qos_background(cls, name) -> bool:
        """osd-internal client traffic (tier agent flush/promote,
        copy-from pulls) is the background class: under admission
        pressure it is shed first, yielding to real clients."""
        return cls._qos_entity(name).startswith("osd.")

    @staticmethod
    def _op_cost_bytes(msg: M.MOSDOp) -> int:
        return sum(len(args.get("data", b"")) for _op, args in msg.ops)

    @staticmethod
    def _is_control_op(msg: M.MOSDOp) -> bool:
        """Pure-control vectors (notify_ack: resolves an existing
        waiter, zero payload) are exempt from admission AND from every
        shed point: dropping one blocks its waiter for a full timeout —
        more dead work than serving the one-line ack.  The single
        definition all three exemption sites share."""
        return all(o[0] == "notify_ack" for o in msg.ops)

    def _claim_throttle(self, msg) -> None:
        """Dispatch-byte ownership: the messenger's per-frame byte
        throttle (osd_client_message_size_cap) stays held until the op
        is SERVED, not just enqueued — the cap bounds bytes in dispatch
        like the reference's message throttle (held until the Message
        is destroyed), and a blocked sender resumes exactly when the
        queue drains.  Claimed only for ADMITTED ops: a rejected op is
        never served, so its budget must return via the read loop."""
        if getattr(msg, "_throttle", None) is not None:
            msg._throttle_held = True

    def _admit_op(self, msg: M.MOSDOp) -> bool:
        cap_ops = self.config.osd_op_throttle_ops
        cap_bytes = self.config.osd_op_throttle_bytes
        if not cap_ops and not cap_bytes:
            # admission disabled (default): provable no-op — no
            # accounting, no gauges, nothing for release to undo
            self._claim_throttle(msg)
            return True
        cost = self._op_cost_bytes(msg)
        if cap_ops and self._admit_ops + 1 > cap_ops:
            return False
        # a single op larger than the whole byte budget must not wedge:
        # it is admitted alone (the Throttle.acquire clamp, upstream)
        if cap_bytes and self._admit_bytes + cost > cap_bytes and \
                self._admit_bytes > 0:
            return False
        msg._admitted = cost
        self._admit_ops += 1
        self._admit_bytes += cost
        self.perf.set("osd_admit_ops_in_use", self._admit_ops)
        self.perf.set("osd_admit_bytes_in_use", self._admit_bytes)
        self._claim_throttle(msg)
        return True

    def _admit_release_accounting(self, msg):
        """Synchronous half of the release: return the budget NOW (no
        suspension point, so a caller can re-admit atomically) and hand
        back the messenger-throttle claim to release asynchronously.
        Returns (throttle, bytes) or None.  Budget accounting exists
        only when admission is configured (_admitted set); the throttle
        claim is independent (made for every admitted op)."""
        cost = getattr(msg, "_admitted", None)
        if cost is not None:
            msg._admitted = None
            self._admit_ops = max(0, self._admit_ops - 1)
            self._admit_bytes = max(0, self._admit_bytes - cost)
            self.perf.set("osd_admit_ops_in_use", self._admit_ops)
            self.perf.set("osd_admit_bytes_in_use", self._admit_bytes)
        thr = getattr(msg, "_throttle", None)
        if thr is not None and getattr(msg, "_throttle_held", False):
            msg._throttle_held = False
            return (thr, msg._throttle_bytes)
        return None

    async def _admit_release(self, msg) -> None:
        claim = self._admit_release_accounting(msg)
        if claim is not None:
            await claim[0].release(claim[1])

    def _would_admit_after_evicting(self, msg, victim) -> bool:
        """Would shedding ``victim`` actually admit ``msg``?  Dropping
        background work that doesn't buy admission (e.g. the byte
        budget is the constraint and the victim is tiny) would pay the
        eviction for nothing."""
        cap_ops = self.config.osd_op_throttle_ops
        cap_bytes = self.config.osd_op_throttle_bytes
        cost = self._op_cost_bytes(msg)
        v_cost = getattr(victim, "_admitted", None) or 0
        if cap_ops and self._admit_ops > cap_ops:  # -1 victim +1 msg
            return False
        bytes_after = max(0, self._admit_bytes - v_cost)
        if cap_bytes and bytes_after + cost > cap_bytes and \
                bytes_after > 0:
            return False
        return True

    async def _admit_or_pushback(self, conn, msg, m) -> bool:
        """Admission decision for one arriving client op.  On pressure,
        mclock's tags decide WHAT yields: a client-class arrival may
        evict a queued background-class op (QoS-enforced shedding);
        everything else gets the explicit THROTTLED pushback."""
        if self._is_control_op(msg):
            return True  # control acks bypass admission (see helper)
        if self._admit_op(msg):
            return True
        evq = self._qos_evict_source()
        if evq is not None and \
                not self._qos_background(msg.reqid[0]):
            victim = evq.peek_evict(self._qos_background)
            evicted = evq.evict(self._qos_background) \
                if victim is not None and \
                self._would_admit_after_evicting(msg, victim[1]) else None
            if evicted is not None:
                e_conn, e_msg, _stamp = evicted
                self._queued_depth = max(0, self._queued_depth - 1)
                self.perf.set("osd_dispatch_queue_depth",
                              self._queued_depth)
                # return the victim's budget and take it for THIS op
                # with no await in between: a suspension here would let
                # a concurrent arrival steal the freed slot, wasting
                # the eviction AND pushing this op back
                claim = self._admit_release_accounting(e_msg)
                admitted = self._admit_op(msg)
                self.perf.inc("osd_qos_preempted")
                # the raw dmclock eviction stat rides the perf path
                # (round 13): scrape-visible, not just dump_dmclock
                self.perf.set("osd_qos_evicted", evq.evicted_total())
                if claim is not None:
                    await claim[0].release(claim[1])
                try:
                    # prompt pushback: the background submitter backs
                    # off instead of burning its full op timeout
                    await e_conn.send(M.MOSDOpReply(
                        reqid=e_msg.reqid, result=M.THROTTLED,
                        throttled=True, epoch=m.epoch))
                except (ConnectionError, OSError, RuntimeError):
                    pass
                if admitted:
                    return True
        self.perf.inc("osd_throttle_rejects")
        await conn.send(M.MOSDOpReply(
            reqid=msg.reqid, result=M.THROTTLED, throttled=True,
            epoch=m.epoch))
        return False

    def _qos_default_for(self, qos_client: str):
        """First-sight QoS spec for a client class: the configured
        default, or the background override for osd-internal traffic
        (no reservation, a fraction of spare capacity, first in line
        for eviction)."""
        from ceph_tpu.cluster.dmclock import QoSSpec

        if self._qos_background(qos_client):
            return QoSSpec(
                reservation=0.0,
                weight=self.config.osd_mclock_background_weight,
                limit=self.config.osd_mclock_background_limit)
        return self._opq_default

    def _qos_evict_source(self):
        """The queue QoS-enforced shedding evicts from under admission
        pressure: the sharded queues (each shard owns a DmClockQueue).
        None without mclock."""
        return self._shardedq if self._shardedq.use_mclock else None

    def _shed_if_expired(self, msg: M.MOSDOp) -> bool:
        """Dead-work shedding at dequeue: an op past its client-stamped
        deadline has nobody awaiting the reply — burning device time on
        it only delays live ops.  Counted and kept in the historic ring
        so attribution shows where the shed op's wall time went.  Reads
        the skewable daemon clock (chaos clock-skew reaches it); pure
        control acks are exempt, mirroring their admission bypass."""
        dl = getattr(msg, "deadline", None)
        if dl is None or self.clock.time() <= dl:
            return False
        if self._is_control_op(msg):
            return False
        self.perf.inc("osd_ops_shed_expired")
        top = self.tracker.create(
            f"osd_op({msg.reqid[0]}:{msg.reqid[1]} {msg.oid} "
            f"SHED expired)", trace=getattr(msg, "trace", None))
        top.mark("shed_expired")
        top.finish()
        return True

    # -------------------------------------------------------- client ops

    async def _resolve_client_op(self, conn: Connection, msg: M.MOSDOp):
        """Map/pool/PG/primary checks for a client op; replies and
        returns None when the op cannot be served here."""
        m = self.osdmap
        if m is None:
            await conn.send(M.MOSDOpReply(reqid=msg.reqid, result=-11))
            return None
        pool = m.pools.get(msg.pgid.pool)
        if pool is None:
            await conn.send(M.MOSDOpReply(reqid=msg.reqid, result=-2))
            return None
        st = self.pgs.get(msg.pgid)
        if st is None or st.primary != self.osd_id:
            # not primary (anymore): tell client to refresh its map
            await conn.send(M.MOSDOpReply(
                reqid=msg.reqid, result=-11, epoch=m.epoch))
            self.perf.inc("osd_misdirected_ops")
            return None
        return m, pool, st

    async def _handle_client_op(self, conn: Connection, msg: M.MOSDOp) -> None:
        resolved = await self._resolve_client_op(conn, msg)
        if resolved is None:
            return
        m, pool, st = resolved
        # admission ahead of dispatch: budgets, QoS-aware eviction, or
        # explicit pushback — the end of unbounded queueing
        if not await self._admit_or_pushback(conn, msg, m):
            return
        # the shard owns queueing, shedding and the dispatch tick, off
        # the messenger read loop; PG-affine hashing keeps per-object
        # ordering inside one shard
        qos_client = None
        default = None
        if self._shardedq.use_mclock:
            qos_client = self._qos_entity(msg.reqid[0])
            default = self._qos_default_for(qos_client)
        self._shardedq.enqueue(conn, msg, qos_client, default)

    def _batch_conn(self, conn):
        """The STABLE reply-routing wrapper for one client connection:
        the shards' per-group FIFOs key on (id(conn), pgid, oid), so
        every batch item from one connection must see the SAME wrapper
        object across frames (a fresh wrapper per frame would fork
        per-object ordering).  Keyed by id() with an identity re-check,
        so a recycled id after a reconnect can never serve a stale
        wrap."""
        key = id(conn)
        wrapped = self._batch_conns.get(key)
        if wrapped is None or wrapped._raw is not conn:
            wrapped = self._batch_conns[key] = _BatchConn(self, conn)
        return wrapped

    async def _handle_client_op_batch(self, conn, batch) -> None:
        """Unpack one client tick's MOSDOpBatch: every item is a
        complete MOSDOp, resolved/admitted/queued individually through
        the very seam per-op frames use — the sharded WQ receives the
        whole tick in ONE dispatch, so the EncodeBatcher's next tick
        sees it pre-coalesced instead of dribbling in op-by-op.  Faults
        stay per item (the SubWriteBatcher rule): a failing item
        answers -5/-28 alone and its tick-mates proceed; a THROTTLED or
        shed-expired item simply never joins the reply tick, leaving
        only ITS client un-acked."""
        self.perf.inc("osd_client_batch_frames")
        self.perf.inc("osd_client_batch_items", len(batch.items))
        # the messenger's recv hop stamped the FRAME, not the items:
        # restamp each traced item here so its timeline's wire stage
        # closes at unpack, exactly where a per-op frame's recv lands
        now = time.time()
        arrival = f"msgr:{self.messenger.name}:recv"
        for msg in batch.items:
            tr = getattr(msg, "trace", None)
            if tr is not None:
                tr.setdefault("events", []).append((arrival, now))
        bconn = self._batch_conn(conn)
        for msg in batch.items:
            try:
                await self._handle_client_op(bconn, msg)
            except asyncio.CancelledError:
                raise
            except Exception as e:
                # ms_dispatch's error contract, applied per ITEM: the
                # failing op's client gets a prompt error, everyone
                # else's dispatch continues
                enospc = isinstance(e, OSError) and \
                    getattr(e, "errno", 0) == 28
                if enospc:
                    self.perf.inc("osd_full_rejects")
                else:
                    self.perf.inc("osd_dispatch_errors")
                    self.perf.inc("osd_client_batch_item_errors")
                try:
                    await bconn.send(M.MOSDOpReply(
                        reqid=msg.reqid, result=-28 if enospc else -5,
                        data=repr(e)))
                except (ConnectionError, OSError, RuntimeError):
                    pass

    @loopacct.root("osd_op")
    async def _serve_admitted(self, conn, msg) -> None:
        """Serve one admitted op, returning its admission budget (and
        the messenger byte-throttle claim) however it exits — incl. the
        deadline shed, which runs HERE, at dequeue, so expired ops never
        reach the backend."""
        try:
            if not self._shed_if_expired(msg):
                await self._serve_queued_op(conn, msg)
        finally:
            await self._admit_release(msg)

    async def _serve_queued_op(self, conn, msg) -> None:
        try:
            resolved = await self._resolve_client_op(conn, msg)
            if resolved is None:
                return
            m, pool, st = resolved
            await self._dispatch_client_op(conn, msg, m, pool, st)
        except Exception as e:
            # mirror ms_dispatch's error contract: the client gets a
            # prompt error instead of a timeout.  A store-level ENOSPC
            # (the capacity backstop beneath the mon's full flag, which
            # can lag a beacon interval behind a fast filler) surfaces
            # as the REAL -28, so the client sees "cluster full" either
            # way, never a generic EIO.
            if isinstance(e, OSError) and getattr(e, "errno", 0) == 28:
                self.perf.inc("osd_full_rejects")
                result = -28
            else:
                self.perf.inc("osd_dispatch_errors")
                result = -5
            try:
                await conn.send(M.MOSDOpReply(
                    reqid=msg.reqid, result=result, data=repr(e)))
            except (ConnectionError, OSError, RuntimeError):
                pass

    def set_qos(self, client: str, reservation: float = 0.0,
                weight: float = 1.0, limit: float = 0.0) -> None:
        """Live per-client QoS update (mclock profile analog)."""
        from ceph_tpu.cluster.dmclock import QoSSpec

        spec = QoSSpec(reservation=reservation, weight=weight,
                       limit=limit)
        if self._shardedq.use_mclock:
            self._shardedq.set_client(client, spec)

    # ops whose effects are not idempotent under at-least-once delivery;
    # a resend must return the cached original reply (reference pg_log
    # dup detection, PGLog dups / osd_pg_log_dups_tracked)
    _MUTATING_OPS = M.MUTATING_OPS
    # mutations still admitted while the cluster carries the FULL flag:
    # they can only free space, and refusing them would wedge a full
    # cluster forever (the reference admits deletes under
    # CEPH_OSDMAP_FULL for exactly this reason)
    _FULL_ADMITTED_OPS = frozenset({"delete", "rmxattr", "omap_rmkeys"})

    def _full_rejects(self, msg: M.MOSDOp) -> bool:
        """Should this op vector be refused ENOSPC under the map's full
        flag?  Only vectors that could GROW data; reads and the
        space-freeing verbs always pass (round 16 cluster-full
        protection — the flag is the mon's commitment, enforced here at
        every primary from its own map copy)."""
        m = self.osdmap
        if m is None or "full" not in getattr(m, "flags", set()):
            return False
        return any(o[0] in self._MUTATING_OPS
                   and o[0] not in self._FULL_ADMITTED_OPS
                   for o in msg.ops)
    _REQID_DUPS_TRACKED = 3000
    # ops that gate the rest of their vector (CEPH_OSD_OP_CMPXATTR etc.)
    _GUARD_OPS = frozenset({"cmpxattr"})

    def _compound_write_guard(self, pool, st: PGState, oid: str):
        """Object-lock guard for compound EC mutations that commit
        UNDER st.lock (copy_from, rollback): an in-flight pipelined RMW
        reads-merges under only the object lock — a compound data
        commit slipping inside that window would be overwritten by the
        RMW's merged full stripe (lost update).  Acquired BEFORE
        st.lock (the pg.objlock -> pg.lock order).  Replicated pools
        need no guard (their commits and RMW reads share st.lock
        already)."""
        if pool.is_erasure():
            return self._obj_write_lock(st, oid)
        import contextlib

        return contextlib.nullcontext()

    async def _dispatch_client_op(self, conn, msg, m, pool, st) -> None:
        caps = getattr(conn, "peer_caps", None)
        if caps is not None:
            # cephx session: enforce OSD caps at dispatch (OSDCap analog)
            from ceph_tpu.cluster import auth as authmod

            need = "rw" if any(o[0] in self._MUTATING_OPS
                               for o in msg.ops) else "r"
            if not authmod.allows(caps, "osd", need):
                self.perf.inc("osd_eperm")
                await conn.send(M.MOSDOpReply(
                    reqid=msg.reqid, result=-1, epoch=m.epoch))
                return
        self.perf.inc("osd_client_ops")
        # absorb the client-side trace header so this op's historic dump
        # shows the objecter/messenger timeline ahead of OSD events
        top = self.tracker.create(
            f"osd_op({msg.reqid[0]}:{msg.reqid[1]} {msg.oid} "
            f"{[o[0] for o in msg.ops]})",
            trace=getattr(msg, "trace", None))
        top.mark("dispatched")
        in_bytes = sum(len(args.get("data", b""))
                       for opname, args in msg.ops
                       if opname in self._MUTATING_OPS)
        if in_bytes:
            self.perf.hinc("osd_op_in_bytes_hist", in_bytes)
        from ceph_tpu.cluster.optracker import CURRENT_OP
        from ceph_tpu.cluster.pg import CURRENT_OP_DEADLINE

        # graft-trace: this daemon's dispatch span parents under the
        # client's root via the header's span id; entering it installs
        # CURRENT_SPAN so sub-op fan-out parents under it in turn
        # (NULL_SPAN when tracing is off — no allocation, no retention)
        tr = getattr(msg, "trace", None) or {}
        token = CURRENT_OP.set(top)
        # sub-writes/sub-reads fanned out under this op inherit its
        # client deadline, so replicas can shed the dead legs too
        dl_token = CURRENT_OP_DEADLINE.set(getattr(msg, "deadline", None))
        try:
            with self.tracer.start("osd_op", trace_id=tr.get("id"),
                                   parent_id=tr.get("span")) as ospan:
                ospan.annotate(oid=msg.oid, pg=str(msg.pgid))
                if any(o[0] in self._MUTATING_OPS for o in msg.ops):
                    await self._execute_mutation_dedup(conn, msg, m, pool,
                                                      st, top)
                else:
                    await self._execute_client_ops(conn, msg, m, pool, st,
                                                   top)
        finally:
            CURRENT_OP_DEADLINE.reset(dl_token)
            CURRENT_OP.reset(token)
            top.finish()
            if top.duration is not None:
                self.perf.tinc("osd_op_lat", top.duration)
                self.perf.hinc("osd_op_lat_hist", top.duration)
                if self.flight:
                    self.flight.op_sample(
                        top.desc, top.duration,
                        slow=0 < self.tracker.slow_threshold
                        <= top.duration)

    async def _execute_mutation_dedup(self, conn, msg, m, pool, st, top):
        reqid = tuple(msg.reqid)
        cached = st.reqid_replies.get(reqid)
        if cached is None and reqid in st.reqid_inflight:
            # dup racing its first instance: wait for it, then answer
            # from its replies
            await asyncio.shield(st.reqid_inflight[reqid])
            cached = st.reqid_replies.get(reqid)
        if cached is not None:
            self.perf.inc("osd_dup_ops")
            top.mark("dup_reply_from_cache")
            for reply in cached:
                await conn.send(reply)
            return
        # the in-memory cache is primary-local; the pg log is not.  A
        # resend that survived a primary change finds its reqid in the
        # replicated log entries (reference pg_log_entry_t::reqid dups)
        # and must NOT re-execute — reply success (the recorded effect is
        # applied; per-op out data is not reconstructible from the log).
        # Durability gate: only entries at-or-below the commit watermark
        # may dup-ack — a logged-but-un-acked entry (sub-writes lost
        # around a bounce) can still rewind during peering, and
        # dup-acking it would bless a write that then vanishes (surfaced
        # by graft-chaos mid-write restarts).  Above the watermark we
        # WAIT for peering's verdict rather than guess: if the entry
        # survives and the watermark catches up (roll-forward) it is
        # durable — dup-ack; if peering rewound it the effects are
        # undone — re-execute; if neither resolves in time, -11 sends
        # the client back for a map refresh + retry (re-executing
        # blindly would double-apply non-idempotent ops like append).
        logged = st.log.reqid_version(reqid)
        if logged is not None and logged > st.last_complete:
            loop = asyncio.get_event_loop()
            # wait only HALF the client's own attempt window: the -11
            # retry hint must reach a waiter that hasn't already timed
            # out and resent, or every unresolved resend burns a full
            # timeout before learning anything
            deadline = loop.time() + self.config.osd_client_op_timeout / 2
            while (loop.time() < deadline
                   and st.log.reqid_version(reqid) is not None
                   and st.last_complete < logged):
                await asyncio.sleep(0.05)
            logged = st.log.reqid_version(reqid)
            if logged is not None and logged > st.last_complete:
                top.mark("dup_unresolved_retry")
                await conn.send(M.MOSDOpReply(
                    reqid=msg.reqid, result=-11, epoch=m.epoch))
                return
        if logged is not None and logged <= st.last_complete:
            self.perf.inc("osd_dup_ops_from_log")
            top.mark("dup_refused_from_log")
            await conn.send(M.MOSDOpReply(
                reqid=msg.reqid, result=0, epoch=m.epoch))
            return
        # cluster-full reject AFTER the dup resolution above: a resend
        # of an already-committed mutation must get its original ack
        # even while the map carries the full flag — ENOSPC-ing a
        # durably-applied write would be exactly the acked-then-lost
        # confusion the full protection exists to prevent.  A genuinely
        # NEW growing write still rejects promptly (never a timeout).
        if self._full_rejects(msg):
            self.perf.inc("osd_full_rejects")
            top.mark("full_reject")
            await conn.send(M.MOSDOpReply(
                reqid=msg.reqid, result=-28, epoch=m.epoch))
            return
        fut: asyncio.Future = asyncio.get_event_loop().create_future()
        st.reqid_inflight[reqid] = fut

        sent: List = []

        class _RecordingConn:
            """Forwards sends while capturing replies for the dup cache."""

            def __init__(self, inner):
                self._inner = inner

            def __getattr__(self, name):
                return getattr(self._inner, name)

            async def send(self, reply):
                sent.append(reply)
                await self._inner.send(reply)

        from ceph_tpu.cluster.pg import CURRENT_CLIENT_REQID

        token = CURRENT_CLIENT_REQID.set(reqid)
        try:
            await self._execute_client_ops(
                _RecordingConn(conn), msg, m, pool, st, top)
            st.reqid_replies[reqid] = sent
            while len(st.reqid_replies) > self._REQID_DUPS_TRACKED:
                st.reqid_replies.popitem(last=False)
            if pool.is_tier() and sent and \
                    getattr(sent[-1], "result", -1) == 0:
                await self._tier_mark_dirty_after_write(pool, st, msg)
        finally:
            CURRENT_CLIENT_REQID.reset(token)
            st.reqid_inflight.pop(reqid, None)
            if not fut.done():
                fut.set_result(None)

    def _resolve_snap_read(self, pool, st, oid: str):
        """Map (oid, msg.snapid) -> the store object serving the read
        (reference find_object_context): the head, a clone, or ENOENT."""
        from ceph_tpu.cluster import snaps as snapmod

        coll = _coll(st.pgid)
        ss = snapmod.load_snapset(self.store, coll, oid)
        head_exists = self.store.stat(coll, oid) is not None
        return ss, coll, head_exists

    def _snap_read_oid(self, pool, st, oid: str, snapid) -> str:
        from ceph_tpu.cluster import snaps as snapmod

        if snapid is None:
            return oid
        if snapid in pool.removed_snaps:
            # a trimmed snap no longer exists; resolving it against the
            # shrunk SnapSet would silently serve head data
            raise FileNotFoundError(f"{oid}@{snapid}: snap removed")
        ss, coll, head_exists = self._resolve_snap_read(pool, st, oid)
        kind, cid = ss.resolve_read(snapid, head_exists)
        if kind == "head":
            return oid
        if kind == "clone":
            return snapmod.clone_oid(oid, cid)
        raise FileNotFoundError(f"{oid}@{snapid}")

    async def _execute_client_ops(self, conn, msg, m, pool, st, top):
        """Run the op vector like the reference do_osd_ops loop
        (`while (!bp.end() && !result)`, PrimaryLogPG.cc): stop at the
        FIRST failing op — a cmpxattr mismatch really gates the writes
        behind it — and send ONE terminal MOSDOpReply for the whole
        vector (ADVICE r4 medium: per-op replies produced multiple
        replies for one reqid)."""
        if any(o[0] == "notify" for o in msg.ops):
            if len(msg.ops) != 1:
                await conn.send(M.MOSDOpReply(
                    reqid=msg.reqid, result=-22, epoch=m.epoch))
                return
            # off the connection's dispatch loop: a notifier that also
            # watches the object acks over this same connection, which
            # must keep reading while the notify gathers acks
            args = msg.ops[0][1]

            async def _notify_bg(reqid=msg.reqid, oid=msg.oid,
                                 a=args, epoch=m.epoch):
                ackers = await self._op_notify(st, oid, a)
                try:
                    await conn.send(M.MOSDOpReply(
                        reqid=reqid, result=0, data=ackers,
                        epoch=epoch))
                except (ConnectionError, OSError):
                    pass

            self._track(
                asyncio.get_event_loop().create_task(_notify_bg()))
            return
        # cache-pool admission (promote / proxy / forward /
        # delete-through).  Runs INSIDE the dedup wrapper so a resent
        # mutation answers from the reqid cache before it can forward or
        # delete-through a second time.
        if pool.is_tier() and await self._tier_intercept(
                conn, msg, m, pool, st):
            return
        # two-phase, approximating the reference's discard-txn-on-error
        # atomicity: GUARD ops run first (in their vector order), the rest
        # of the vector runs second in order — so a mutation can never
        # land ahead of a failing guard, while read/write ordering within
        # the vector is preserved.  (librados vectors are read-ops OR
        # write-ops, never mixed, so guards-first matches the patterns the
        # reference APIs generate.)  Mutations still apply sequentially: a
        # failure mid-way leaves earlier mutations of the same vector
        # applied, reported via the terminal result.
        result = 0
        outs: List = [None] * len(msg.ops)
        phases = (
            [(i, o) for i, o in enumerate(msg.ops)
             if o[0] in self._GUARD_OPS],
            [(i, o) for i, o in enumerate(msg.ops)
             if o[0] not in self._GUARD_OPS],
        )
        for phase in phases:
            for i, (opname, args) in phase:
                r, data = await self._do_one_op(conn, msg, m, pool, st,
                                                opname, args)
                outs[i] = data
                if r < 0:
                    result = r
                    break
            if result < 0:
                break
        data = outs[0] if len(msg.ops) == 1 else outs
        reply = M.MOSDOpReply(
            reqid=msg.reqid, result=result, data=data, epoch=m.epoch)
        tr = getattr(msg, "trace", None)
        if tr is not None:
            # reply-leg trace (round 11): the messengers stamp the
            # send/recv hops and the objecter closes with its wakeup —
            # the previously-untraced tail of wall_coverage
            reply.trace = {"id": tr.get("id"), "events": []}
        await conn.send(reply)

    async def _do_one_op(self, conn, msg, m, pool, st, opname, args):
        """One op of the vector -> (result, out_data).

        The hot mutation verbs (write_full, write, zero, append,
        truncate, delete, create) commit through ONE pipelined frontier
        path for both pool kinds — prepare under the object write lock
        (EC read-merge-encode) or the PG lock (replicated txn build),
        ordered commit section under the PG lock, ack wait with
        everything released.  Compound read-modify verbs (copy_from,
        rollback, exec, xattr/omap) keep the serial shape — they still
        register with the same commit frontier via _replicate_txn."""
        if opname == "write_full":
            if pool.is_erasure():
                # encode outside the PG lock, ordered commit under it,
                # ack wait after release — the PG admits the next write
                # while this one's shards commit
                r = await self._ec_write_pipelined(
                    pool, st, msg.oid, args["data"], None,
                    snapc=msg.snapc)
                return r, None
            r = await self._rep_mutate_pipelined(
                st, msg.oid,
                lambda version: self._txn_write_full(
                    st, msg.oid, args["data"], msg.snapc, version))
            return r, None
        if opname in ("write", "zero"):
            data = args["data"] if opname == "write" \
                else b"\0" * args["length"]
            offset = args["offset"]
            if pool.is_erasure():
                r = await self._ec_write_pipelined(
                    pool, st, msg.oid, data, offset, snapc=msg.snapc)
            else:
                r = await self._rep_mutate_pipelined(
                    st, msg.oid,
                    lambda version: self._txn_write(
                        st, msg.oid, offset, data, msg.snapc, version))
            return r, None
        if opname == "read":
            try:
                oid = self._snap_read_oid(pool, st, msg.oid, msg.snapid)
                data = await self._op_read(
                    pool, st, oid,
                    args.get("offset", 0), args.get("length"))
                return 0, data
            except FileNotFoundError:
                return -2, None
        if opname == "delete":
            r = await self._op_delete_pipelined(pool, st, msg.oid,
                                                snapc=msg.snapc)
            return r, None
        if opname == "append":
            # CEPH_OSD_OP_APPEND: a write at the CURRENT size — atomic
            # under the object write lock (EC; concurrent appends
            # serialize per object, do_osd_ops:4917 case) or the PG
            # lock (replicated: the size is read inside the commit
            # section)
            if pool.is_erasure():
                async with self._obj_write_lock(st, msg.oid):
                    size = self._head_size(pool, st, msg.oid)
                    token = await self._ec_start_objlocked(
                        pool, st, msg.oid, args["data"], size,
                        msg.snapc)
                r = await self._ec_commit_finish(st, token)
                return r, size
            sizebox = []

            def _build(version):
                sizebox.append(self._head_size(pool, st, msg.oid))
                return self._txn_write(st, msg.oid, sizebox[0],
                                       args["data"], msg.snapc, version)

            r = await self._rep_mutate_pipelined(st, msg.oid, _build)
            return r, sizebox[0] if sizebox else 0
        if opname == "truncate":
            if pool.is_erasure():
                r = await self._ec_truncate_pipelined(
                    pool, st, msg.oid, args["size"], snapc=msg.snapc)
                return r, None
            r = await self._rep_mutate_pipelined(
                st, msg.oid,
                lambda version: self._txn_truncate(
                    st, msg.oid, args["size"], msg.snapc, version))
            return r, None
        if opname == "create":
            # exclusive create (CEPH_OSD_OP_CREATE + EXCL flag): the
            # exists-check must be atomic with the commit start, so it
            # holds the object lock (EC) / PG lock (replicated) across
            # both
            if pool.is_erasure():
                async with self._obj_write_lock(st, msg.oid):
                    if self._head_size(pool, st, msg.oid,
                                       missing=None) is not None:
                        return -17, None  # EEXIST
                    token = await self._ec_start_objlocked(
                        pool, st, msg.oid, b"", None, msg.snapc)
                r = await self._ec_commit_finish(st, token)
                return r, None
            async with st.lock:
                if self._head_size(pool, st, msg.oid,
                                   missing=None) is not None:
                    return -17, None  # EEXIST
                version = self._next_version(st)
                txn = self._txn_write_full(st, msg.oid, b"",
                                           msg.snapc, version)
                token = await self._replicate_txn_start(
                    st, txn, "modify", msg.oid, version)
            r = await self._replicate_txn_finish(st, token)
            return r, None
        if opname == "cmpxattr":
            # CEPH_OSD_OP_CMPXATTR (eq): gate for compound client
            # ops; mismatch -> -ECANCELED like the reference
            cur = self.store.getattr(_coll(st.pgid), msg.oid,
                                     "_" + args["name"])
            return (0 if cur == args["value"] else -125), None
        if opname == "stat":
            try:
                oid = self._snap_read_oid(pool, st, msg.oid, msg.snapid)
            except FileNotFoundError:
                oid = None
            size = None
            if oid is not None:
                size = self.store.stat(_coll(st.pgid), oid)
                if pool.is_erasure():
                    xs = self.store.getattr(_coll(st.pgid), oid, "size")
                    size = int(xs) if xs else \
                        (None if size is None else size)
            return (0 if size is not None else -2), size
        if opname == "list":
            from ceph_tpu.cluster import snaps as snapmod

            names = [o for o in self._list_pg_objects(st.pgid)
                     if not snapmod.is_snap_key(o)]
            return 0, names
        if opname in ("getxattr", "getxattrs", "omap_get"):
            # snap-aware like "read": resolve the serving clone first
            try:
                moid = self._snap_read_oid(pool, st, msg.oid, msg.snapid)
            except FileNotFoundError:
                return -2, None
            return self._op_read_meta(st, moid, opname, args)
        if opname in ("setxattr", "rmxattr", "omap_set", "omap_rmkeys"):
            async with st.lock:
                r = await self._op_write_meta(st, msg.oid, opname, args,
                                              snapc=msg.snapc, pool=pool)
            return r, None
        if opname == "exec":
            async with st.lock:
                return await self._op_exec(st, msg.oid, args,
                                           snapc=msg.snapc, pool=pool)
        if opname == "watch":
            self._watchers.setdefault((st.pgid, msg.oid), {})[
                (str(msg.src), args["cookie"])] = conn
            self.perf.inc("osd_watches")
            return 0, None
        if opname == "unwatch":
            self._watchers.get((st.pgid, msg.oid), {}).pop(
                (str(msg.src), args["cookie"]), None)
            return 0, None
        if opname == "copy_from":
            # CEPH_OSD_OP_COPY_FROM (reference PrimaryLogPG.cc:3113
            # do_osd_ops COPY_FROM -> start_copy): the DESTINATION
            # primary pulls the source object — data, user xattrs, omap —
            # through its own internal client (works cross-pool and
            # across pool types) and REPLACES the destination wholesale
            src_pool = args.get("src_pool", st.pgid.pool)
            src_oid = args["src_oid"]
            src_snapid = args.get("src_snapid")
            reply = await self.internal_op(
                src_pool, src_oid,
                [("read", {}), ("getxattrs", {}), ("omap_get", {})],
                snapid=src_snapid)
            if reply.result < 0:
                return reply.result, None
            data, xattrs, omap = reply.data
            async with self._compound_write_guard(pool, st, msg.oid):
                async with st.lock:
                    r = await self._op_write_full(pool, st, msg.oid,
                                                  data,
                                                  snapc=msg.snapc)
                    if r < 0:
                        return r, None
                    r = await self._replace_meta(st, msg.oid,
                                                 xattrs or {},
                                                 omap or {})
            return (r, None) if r < 0 else (0, len(data))
        if opname == "rollback":
            # CEPH_OSD_OP_ROLLBACK (reference PrimaryLogPG::_rollback_to):
            # make the head IDENTICAL to the object's state at ``snapid``
            # — the restore runs through the normal write path, so the
            # CURRENT head still COWs into its own clone first
            snapid = args["snapid"]
            try:
                src = self._snap_read_oid(pool, st, msg.oid, snapid)
            except FileNotFoundError:
                return -2, None
            if src == msg.oid:
                return 0, None  # head already carries the snap state
            data = await self._op_read(pool, st, src, 0, None)
            coll = _coll(st.pgid)
            xattrs = {k[1:]: v for k, v in
                      self.store.get_xattrs(coll, src).items()
                      if k.startswith("_")}
            omap = self.store.omap_get(coll, src)
            async with self._compound_write_guard(pool, st, msg.oid):
                async with st.lock:
                    r = await self._op_write_full(pool, st, msg.oid,
                                                  data,
                                                  snapc=msg.snapc)
                    if r < 0:
                        return r, None
                    r = await self._replace_meta(st, msg.oid, xattrs,
                                                 omap)
            return (r, None) if r < 0 else (0, None)
        if opname == "notify_ack":
            entry = self._notifies.get(args["notify_id"])
            if entry is not None:
                fut, acked = entry
                acked.add(str(msg.src))
                if not fut.done() and len(acked) >= fut.needed:  # type: ignore[attr-defined]
                    fut.set_result(None)
            return 0, None
        return -95, None

    # ------------------------------------------------- xattr/omap/exec ops
    #
    # User xattrs are stored with a "_" prefix, exactly like the reference
    # object store's user-attr namespace, so they never collide with the
    # internal shard/size/hinfo attrs.

    async def _replace_meta(self, st: PGState, oid: str,
                            xattrs: Dict, omap: Dict) -> int:
        """Make the object's user xattrs and omap IDENTICAL to the given
        sets (copy-from/rollback are wholesale replacements, never
        merges): stale head keys absent from the source are removed."""
        coll = _coll(st.pgid)
        cur_x = {k[1:] for k in self.store.get_xattrs(coll, oid)
                 if k.startswith("_")}
        for name in cur_x - set(xattrs):
            r = await self._op_write_meta(st, oid, "rmxattr",
                                          {"name": name})
            if r < 0:
                return r
        for name, value in xattrs.items():
            r = await self._op_write_meta(st, oid, "setxattr",
                                          {"name": name, "value": value})
            if r < 0:
                return r
        stale = set(self.store.omap_get(coll, oid)) - set(omap)
        if stale:
            r = await self._op_write_meta(st, oid, "omap_rmkeys",
                                          {"keys": sorted(stale)})
            if r < 0:
                return r
        if omap:
            r = await self._op_write_meta(st, oid, "omap_set",
                                          {"kv": omap})
            if r < 0:
                return r
        return 0

    def _op_read_meta(self, st: PGState, oid: str, opname: str, args):
        coll = _coll(st.pgid)
        if self.store.stat(coll, oid) is None:
            return -2, None
        if opname == "getxattr":
            v = self.store.getattr(coll, oid, "_" + args["name"])
            return (0, v) if v is not None else (-61, None)  # ENODATA
        if opname == "getxattrs":
            return 0, {k[1:]: v for k, v in
                       self.store.get_xattrs(coll, oid).items()
                       if k.startswith("_")}
        if opname == "omap_get":
            return 0, self.store.omap_get(coll, oid)
        return -95, None

    async def _op_write_meta(self, st: PGState, oid: str, opname: str,
                             args, snapc=None, pool=None) -> int:
        """Metadata mutations ride the same logged+replicated transaction
        path as data writes (reference do_osd_ops xattr/omap cases write
        into the op's transaction, PrimaryLogPG.cc:4917).  ``snapc``
        clone-on-writes the object first like data mutations do — omap
        and xattr state snapshot with the object (the CephFS dirfrag
        snapshots ride this)."""
        coll = _coll(st.pgid)
        txn = Transaction()
        if snapc is not None:
            txn.ops.extend(self._cow_pre_ops(
                st, oid, snapc,
                erasure=bool(pool is not None and pool.is_erasure())))
        txn.touch(coll, oid)
        if opname == "setxattr":
            txn.setattr(coll, oid, "_" + args["name"], args["value"])
        elif opname == "rmxattr":
            txn.rmattr(coll, oid, "_" + args["name"])
        elif opname == "omap_set":
            txn.omap_set(coll, oid, args["kv"])
        elif opname == "omap_rmkeys":
            txn.omap_rmkeys(coll, oid, list(args["keys"]))
        version = self._next_version(st)
        txn.set_version(coll, oid, version[1])
        return await self._replicate_txn(st, txn, "modify", oid, version)

    async def _op_exec(self, st: PGState, oid: str, args, snapc=None,
                       pool=None):
        """Object-class execution (reference do_osd_ops CEPH_OSD_OP_CALL):
        the method's reads hit the store, its writes collect into a txn
        that commits + replicates atomically with the op.  ``snapc``
        clone-on-writes first, so cls-mutated state (dirfrags, bucket
        indexes) snapshots like plain data."""
        from ceph_tpu.cluster.objclass import (
            ClassRegistry, ClsError, MethodContext,
        )

        coll = _coll(st.pgid)
        txn = Transaction()
        if snapc is not None:
            txn.ops.extend(self._cow_pre_ops(
                st, oid, snapc,
                erasure=bool(pool is not None and pool.is_erasure())))
        txn.touch(coll, oid)
        base_ops = len(txn.ops)
        ctx = MethodContext(self.store, coll, oid, txn)
        try:
            out = ClassRegistry.instance().call(
                args["cls"], args["method"], ctx, args.get("indata", b""))
        except ClsError as e:
            return e.errno, str(e)
        self.perf.inc("osd_cls_calls")
        if len(txn.ops) > base_ops:  # method added mutations to commit
            version = self._next_version(st)
            txn.set_version(coll, oid, version[1])
            r = await self._replicate_txn(st, txn, "modify", oid, version)
            if r != 0:
                return r, None
        return 0, out

    async def _op_notify(self, st: PGState, oid: str, args):
        """Fan a notify out to every watcher and gather acks within the
        timeout (reference PrimaryLogPG::do_osd_op_effects + Notify)."""
        watchers = self._watchers.get((st.pgid, oid), {})
        live = {k: c for k, c in watchers.items() if not c.closed}
        self._watchers[(st.pgid, oid)] = live
        if not live:
            return []
        self._notify_id += 1
        nid = self._notify_id
        fut = asyncio.get_event_loop().create_future()
        fut.needed = len(live)  # type: ignore[attr-defined]
        acked: Set[str] = set()
        self._notifies[nid] = (fut, acked)
        for (watcher, cookie), conn in live.items():
            try:
                await conn.send(M.MWatchNotify(
                    pool=st.pgid.pool, oid=oid, notify_id=nid,
                    cookie=cookie, payload=args.get("payload", b"")))
            except (ConnectionError, OSError, RuntimeError):
                fut.needed -= 1  # type: ignore[attr-defined]
                if len(acked) >= fut.needed and not fut.done():  # type: ignore[attr-defined]
                    fut.set_result(None)
        try:
            if not fut.done() and fut.needed > 0:  # type: ignore[attr-defined]
                await asyncio.wait_for(
                    fut, timeout=args.get("timeout",
                                          self.config.osd_client_op_timeout))
        except asyncio.TimeoutError:
            pass
        finally:
            self._notifies.pop(nid, None)
        self.perf.inc("osd_notifies")
        return sorted(acked)
