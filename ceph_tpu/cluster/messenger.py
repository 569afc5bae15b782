"""Async messenger: Connection / Dispatcher / sessions over asyncio TCP.

Structural mirror of the reference messenger abstraction (src/msg/
Messenger.h, Dispatcher.h; AsyncMessenger event loops): entity-named
endpoints, per-peer Connections with ordered delivery and reconnect,
dispatchers receiving typed messages.  Transport is asyncio TCP on
loopback (the reference's tier-3 standalone tests run the same way:
N daemons x 1 host over real sockets; every frame crosses a socket,
also between daemons of one process).  Frames are length-prefixed and
typed: ordinary messages are pickles — an internal trust boundary, like
the reference's cephx-signed native encoding is within a cluster —
while the cephx handshake frames use FIXED struct encodings so that no
unauthenticated byte ever reaches the deserializer (in cephx mode, data
frames on a connection without a session key are rejected outright).

Integrity (reference cephx message signing, src/auth/cephx/): when the
messenger holds a cluster secret, every frame carries a truncated
HMAC-SHA256 over the payload (the pickle AND every out-of-band
buffer); receivers verify before unpickling and
reset the connection on mismatch, so a byte-flipped or forged frame can
never reach a dispatcher.  auth "none" (no secret) stays the default,
like the reference's auth_supported=none dev mode.

Copies (PR 29).  A frame is ``<u32 len><type><body>``.  Sending: the
message is pickled with protocol 5; a field that carries object data
(``messages.py`` says which, through ``oob``) and is read-only and at
least ``_OOB_MIN`` long stays OUT of the pickle, and header, pickle,
those buffers and the signature go out as separate buffers, one
``sendmsg`` when the socket has room: through the transport
(``writelines``) on the loop thread, or, a frame of ``_IO_MIN`` =
256 KiB or more since PR 50, through one of the process's two sender
threads, which writes the same buffers to the connection itself (the
send is the kernel's copy into the socket, GIL released: the one
thread every daemon waits for no longer stands in it).  A stream's
bytes leave in the order ``write`` was called, whoever writes them,
and ``drain`` returns once the frame has left user space, so
``Connection.send`` and ``send_message`` hold their locks as before
(``_FrameStream``).  A payload byte is not
copied in user space on the sending side; the replay buffer keeps
references.  Receiving: ``_FrameStream`` has the transport
``recv_into`` one ``bytearray`` per large frame, and verification,
unpickling and the message's out-of-band fields are all views of that
buffer.  The ONE user-space copy a payload byte takes per hop is its
consumer's, at its own door: the store's (``store.py``: it owns what
it keeps, a ``bytearray`` of its own or, of a full shard of 256 KiB or
more since PR 43, a populated mapping of its own; ``Transaction.write``
takes its ``bytes`` at once, ``write_planar`` keeps the view it is given
and ``MemStore`` copies it when the op is applied), ``MOSDOpReply.own_data``
(the client API returns ``bytes``), the encode tick's fill of its host
batch.  For ``write_planar`` that holds since PR 32, and of a write
that replaces the whole shard (``plane_off`` 0 and a window of
``total_cols`` columns: every ``write_full``); a partial window (an
append, an RMW, one clipped to the shard) is spliced into the old plane
matrix as before: a zero fill of the shard and two more copies of it.
The EC fan-out's sub-writes set out the same way: their ``data`` is a
flat read-only view of the encode tick's planes
(``backend_ec._shard_bytes``), not ``tobytes()``, and so is what the
primary's own store is given.  Exceptions, all bounded: a frame that
fits the ``_RECV_SCRATCH`` buffer (256 KiB) is read into it and cut out of it
(one more copy; for these a read less is worth more than a copy
less), as is the head of a large frame that came in the same read as
its length prefix (at most ``_RECV_PEEK`` = 4 KiB within a run of
large frames); a buffer under ``_OOB_MIN``, or one
that could change under the replay buffer (``bytearray``, a writable
array), is copied into the pickle and out of it as before.

Reliability (reference AsyncConnection reconnect/replay semantics):
outgoing traffic runs over per-peer SESSIONS with monotonically
increasing sequence numbers; sent frames stay buffered until the peer
acks them, and a dropped TCP connection is transparently re-opened with
the unacked tail replayed IN ORDER.  Delivery is therefore ordered
at-least-once — handlers are idempotent by design (absolute-offset
writes, versioned log appends), exactly like the reference's lossless
osd-osd policy replaying out_q after a session reset.

The ack (PR 35) is state, not a frame; a message costs ONE frame.  A
session frame names its session: ``sid`` (its messenger's) and
``src_addr`` (where that messenger listens, ``my_addr``: the address
the maps publish and peers dial; carried by every session frame rather
than learnt per connection, so a replayed frame opens a connection like
any other).  The receiver notes the newest ``seq`` it has taken of that
session (``_owe``: an ``_Owed`` on the connection the frames come in
on, found again under the listening address) and dispatches at once.
Whatever frame it next writes to that peer takes the ack along in its
header field ``ack`` = ``(sid, seq)``: a session frame of its own to
that listening address (``send_message``: an OSD's commit reply carries
the sub-write's ack, the next sub-write or reply carries the reply's)
or a raw send on that connection (``Connection.send``: the op's reply
carries the client op's).  The ack counts as gone only when its frame
was written; one in a frame that did not set out stays owed.  Whoever
receives a frame with an ``ack`` that names its own ``sid`` trims its
session to the frame's sender from the front, everything up to ``seq``:
acks are cumulative, which is sound because a session's frames arrive
in order (a gated session sends nothing past a dropped frame); one that
names another ``sid`` was owed to a messenger that had the address
before and trims nothing.  An ack goes ALONE, as a ``_MsgAck``
(``_ack_alone``, the only place one is made), in four cases: nothing
carried it for ``_ACK_DELAY_S`` (the messenger's one timer,
``_flush_acks``; no timer per connection); ``_ACK_BYTES`` are owed to
one session (large frames pin their payloads in the sender's replay
buffer); ``_Owed.MAX_FRAMES`` frames are (a quarter of what that buffer
holds before it overflows); or a frame arrived AGAIN (``seq`` not above
what was taken: a replay, a duplicate — the sender is recovering and is
told at once, once per replayed tail; the frame is dispatched all the
same, at-least-once).  Acks owed on a connection that dies are void:
the sender replays more than it had to, never less.  The constants are
derived where they are defined, beside ``_OOB_MIN``; none is an option.
``KERNELS`` ``msgr_acks_owed`` counts the session frames received,
``msgr_acks_carried`` those whose ack left inside another frame.  The
heartbeat lane has no session and no acks.
"""

from __future__ import annotations

import asyncio
import collections
import copyreg
import itertools
import os
import pickle
import queue
import select
import socket
import struct
import threading
import hmac as _hmac
import hashlib
import time as _time

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Set, Tuple

from ceph_tpu.cluster.optracker import mark_current
from ceph_tpu.trace import loopacct
from ceph_tpu.utils.lockdep import DepLock
from ceph_tpu.utils.perf import KERNELS

Addr = Tuple[str, int]

_SID = itertools.count(1)

# how many bytes of complete, not yet consumed frames a connection holds
# before it stops reading its socket (the read loop resumes it as it
# takes frames): room for a few large frames, so a burst of shard
# sub-writes is received while the one before is in dispatch.  Socket
# buffers are sized alike so the burst drains in few syscalls
# (TCP_NODELAY is asyncio's default already).
_STREAM_LIMIT = 4 << 20
_SOCK_BUF = 2 << 20
# the buffer a connection reads into while it is between large frames.
# A read takes whatever the socket holds, up to the room that is left:
# length prefixes and every frame that fits (acks, pings, replies, the
# 64 KiB cell's ops, sub-writes and client batches) are cut out of it,
# as many as the read brought, and a frame that fits but has not
# arrived whole waits in it for the next read.  For these a turn of the
# loaded loop costs more than a copy: a first version that gave every
# split frame a buffer of its own took a read more per frame and read
# 3 ms more `wire_ms` and -2.6% on the 64 KiB cell (PR 29).  Only a
# frame that CANNOT fit gets a buffer of its own (_FrameStream), whose
# body takes several reads anyway.  Right after such a frame only
# _RECV_PEEK bytes are offered: such frames come in runs (a data lane's
# sub-writes one way, their replies the other), and what is read beside the
# next one's length prefix is moved once more
_RECV_SCRATCH = 256 << 10
_RECV_PEEK = 4 << 10
# the same line on the sending side: a frame of at least _IO_MIN is
# written to its socket by one of the process's _IO_THREADS sender
# threads and not by the loop thread (_FrameStream.write, _Senders).
# Every cell is bound by the one loop, and at 4 MiB a third of the
# loop's op was the wall inside its own sendmsg calls, GIL released, in
# the kernel (`loop_send_ms_per_op.write` 3.6-7.7 ms of 12.7-23.6:
# ledger, PR 48).  What chose the numbers (the builder's chip runs of a
# refused PR 49, scratch switches on `k4m2_write_4m_t16`, same-seed
# pairs of 51 s; PERF.md section 5 (14)): the send half on two threads
# +5.5 to +8.2% `write_MBps` in six pairs of six (`loop_send` 5.5-6.2 ->
# 2.0-2.2 ms an op); ONE thread -9 to -11% (the loop 74-76% busy, waiting
# for the one sender); the receive half on threads nothing (what left
# the recv stamp came back as transport time), and both halves on four
# threads -4 to +4%: beside them the store's refill thread starved and
# the populate came back on the loop (`store_pooled_share` 100 -> 54%).
# So two threads, sends only.  The length: a hand-over has a fixed price
# (a wake of the loop through its self-pipe and a handle of its own for
# the completion, ~1.2 ms of `io_wait` a frame on the loaded chip host),
# which the 64 KiB cell's frames (ops of 64 KiB, sub-writes of 32 KiB),
# commit replies, acks, pings and map traffic would pay for nothing: a
# send of theirs is a copy of a few KiB.  At 4 MiB every sub-write
# (2 MiB, 1 MiB, 684 KiB, 512 KiB) and the client's op is over it.
# This tree's own same-seed pairs (PR 50's builder, PERF.md section 6):
# `k4m2_write_4m_t16` +4.7 to +9.9% in six of six; and the line itself,
# on `k8m4_write_4m_t16`, whose sub-writes are the smallest that engage:
# +5.0% at 256 KiB against +1.3% at 1 MiB (only the client's op engages
# then), so the 512 KiB frames pay and the line is where a frame gets a
# buffer of its own; the 64 KiB cell is the same at either (7 frames of
# a window's 57000 reach 256 KiB)
_IO_MIN = 256 << 10
_IO_THREADS = 2
# how long a sender thread waits for a socket to take ANY more bytes
# before it fails the stream (a peer that stopped reading: a wedged or
# throttled read loop), and the slices it waits in, at whose edges it
# sees that the stream was closed under it.  A thread that waits serves
# no other stream, so the wait is bounded where the loop's own (drain:
# flow control) is not: by the heartbeat grace of a vstart cluster
# (10 s), the time after which a peer that answers nothing is taken for
# dead anyway; the session then reconnects and replays, as after a reset.
# The slice bounds how long a close waits for the thread to let go, far
# under _CLOSE_WAIT_S
_IO_STALL_S = 10.0
_IO_POLL_S = 0.05
# sendmsg takes at most IOV_MAX buffers a call
_IOV_MAX = 1024
# a length prefix over this is a corrupt or hostile one, not a frame: a
# frame's buffer is allocated when its prefix is read, before any of it
# has arrived (the largest frames are client batches of 16 x 4 MiB)
_MAX_FRAME = 1 << 30
# a buffer at least this long leaves the pickle and rides the frame out
# of band (oob, _encode).  The crossing point on a scratch micro-run
# (CPU dev host; one sub-write per frame, send + receive + the store's
# copy): out of band costs ~2 us more per frame in header, PickleBuffer
# and view slices, which the two saved copies give back between 48 and
# 64 KiB (+0.2 us at 48 KiB, -0.8 us at 64 KiB, -5 us at 128 KiB, -360 us
# at 1 MiB).  The 64 KiB cell's client ops sit on it, its 32 KiB
# sub-writes stay in band.
_OOB_MIN = 64 << 10
# how long a closing endpoint waits for its transport to flush before it
# aborts it (Connection.close, Messenger.shutdown)
_CLOSE_WAIT_S = 1.0
# when an owed ack goes alone (module docstring, "The ack").  The delay:
# above the time a frame that goes back anyway takes to set out (a
# sub-write's commit reply 70-150 ms, a client op's reply the op's own
# 0.5-0.7 s at the widest pool's p95: ledger, PR 33), far under anything
# that waits on a peer (heartbeat grace 10 s, op timeouts 5 s and up);
# what it costs is a second of one-way traffic held in the sender's
# replay buffer, and replayed once more if the connection dies.  The
# bytes: big frames pin their payloads in that buffer (a sub-write's
# shard is a view of the whole encode tick's planes), so a stream that
# nothing answers is acknowledged every 16 MiB: eight of the largest
# shards (2 MiB at k=2), four client ops of 4 MiB, twice what a
# connection holds in flight anyway (two socket buffers of _SOCK_BUF
# and _STREAM_LIMIT of received frames = 8 MiB), so the ack that a
# reply carries is not beaten to it by a closed loop's burst.  The
# frames: a quarter of what the sender's buffer holds before it
# overflows, so three quarters are left for what is in flight.
_ACK_DELAY_S = 1.0
_ACK_BYTES = 16 << 20

# whose a read loop's time is in the loop's account (trace/loopacct.py),
# by its messenger's entity type: an OSD's dispatch, a client's, or
# (a mon's, a mgr's, an mds's) "other"
_READER_BUCKET = {"osd": "msgr", "client": "client"}


def _tune_socket(stream: "_FrameStream") -> None:
    sock = stream.transport.get_extra_info("socket")
    if sock is None:
        return
    try:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, _SOCK_BUF)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, _SOCK_BUF)
    except OSError:  # pragma: no cover - exotic transports
        pass


# the socket's own C method: a sender thread's call is nobody's to time
# (loopacct.TimedSocket is the LOOP thread's account)
_sendmsg = socket.socket.sendmsg


def _rest(parts: list, n: int) -> list:
    """``parts`` less their first ``n`` bytes: whole buffers dropped, the
    one the count ends in sliced (a view: nothing is copied)."""
    for i, part in enumerate(parts):
        size = len(part)
        if n < size:
            rest = list(parts[i:])
            if n:
                rest[0] = memoryview(part)[n:]
            return rest
        n -= size
    return []


class _Senders:
    """The process's sender threads: ``_IO_THREADS`` daemon threads,
    started by the first large frame, that take ``(stream, parts, t0)``
    off one queue and run ``stream._io_send``.  A stream has at most one
    frame here (``_FrameStream._io_busy``), so the queue's order is no
    stream's concern."""

    def __init__(self):
        self.jobs: "queue.SimpleQueue" = queue.SimpleQueue()
        self.threads: List[threading.Thread] = []

    def submit(self, stream: "_FrameStream", parts: list, t0: int) -> None:
        if not self.threads:
            self.threads = [
                threading.Thread(target=self._run, name=f"msgr-send-{i}",
                                 daemon=True)
                for i in range(_IO_THREADS)]
            for thread in self.threads:
                thread.start()
        self.jobs.put((stream, parts, t0))

    def _run(self) -> None:
        take = self.jobs.get
        while True:
            stream, parts, t0 = take()
            stream._io_send(parts, t0)


_IO = _Senders()


class _FrameStream(asyncio.BufferedProtocol):
    """One TCP connection, both halves, as the messenger uses it:
    ``read_frame`` / ``write`` + ``drain`` / ``close`` + ``wait_closed``.

    Receive: the transport ``recv_into``s the buffer ``get_buffer``
    hands it.  A frame too large for the scratch buffer gets ONE
    ``bytearray(n)`` of its own and the socket is read straight into
    it until it is full: no growing buffer, no slice copy, no memmove.
    Between such frames reads go to the fixed scratch buffer, out of
    which the length prefixes and the frames that fit are cut (one copy
    each, and a partial one is moved to the front first when frames
    before it were taken; of a large frame, the head that came in the
    same read as its length prefix is moved so: at most ``_RECV_PEEK``
    bytes when the frame before it was large too).
    Complete frames queue for ``read_frame``; past ``_STREAM_LIMIT``
    queued bytes the socket is not read until the read loop has taken
    some, so a reader that waits (for a ``Throttle``, in dispatch)
    stops the drain and TCP pushes back on the peer.

    Send: ``write`` hands a frame's parts on as they are, nothing
    joined or copied.  A frame under ``_IO_MIN`` goes to the transport
    (``writelines``: one ``sendmsg`` on the loop thread), as every frame
    did before PR 50.  A frame of ``_IO_MIN`` or more is written by one
    of the process's sender threads (``_Senders``), to a duplicate of the
    connection's descriptor that is the stream's own (``_io_socket``: the
    transport may close ITS descriptor whenever the peer resets, and the
    number must not be another connection's while a thread still writes
    to it), with ``socket.socket``'s C ``sendmsg`` until the parts are
    gone, waiting for room itself (``poll``).  The bytes leave in the
    order ``write`` was called: a stream has ONE frame with a thread at
    a time, while it has, every later ``write`` (small ones too) queues
    behind it in ``_pending``, and a large frame is handed over only
    once the transport has flushed what it buffered of the small ones
    before it, so two threads never write one socket at once.  The
    thread's completion (``_io_done``, on the loop) sends what queued
    up: small parts through the transport, the next large frame back to
    a thread.  ``drain`` returns when nothing of the stream is queued,
    with a thread, or over the transport's high-water mark: the frame
    has left user space, as before.  A thread's ``OSError``, its wait
    for room running to ``_IO_STALL_S``, or the stream closed under it
    end the connection as a transport's write error does (``abort``:
    the drains raise ``ConnectionResetError``, the session replays on a
    new connection; the receiver drops the partial frame), and
    ``wait_closed`` does not return while a thread holds the socket."""

    def __init__(self, on_connect=None):
        self._loop = asyncio.get_running_loop()
        self._on_connect = on_connect
        self.transport: Optional[asyncio.Transport] = None
        self._scratch = bytearray(_RECV_SCRATCH)
        self._have = 0                       # unparsed bytes in scratch
        self._peek = False                   # the last frame was large
        self._frame: Optional[bytearray] = None   # the frame being filled
        self._pos = 0
        self._frames: collections.deque = collections.deque()
        self._queued = 0
        self._reader: Optional[asyncio.Future] = None
        self._error: Optional[Exception] = None
        self._write_paused = False
        self._drainers: collections.deque = collections.deque()
        # the send side's order (class docstring): (large, parts) of the
        # writes that wait behind a frame with a sender thread, or, a
        # large one at their head, for the transport to flush
        # (_flushing: its limits are at 0 until it has); whether a
        # thread has a frame of this stream; the descriptor the threads
        # write to; and what tells a thread to let go
        self._pending: collections.deque = collections.deque()
        self._flushing = False
        self._io_busy = False
        self._io_sock: Optional[socket.socket] = None
        self._io_halt = False
        self._lost = False
        # done once connection_lost ran and no sender thread holds the
        # socket
        self._closed = self._loop.create_future()

    # -- transport callbacks ------------------------------------------------

    def connection_made(self, transport) -> None:
        self.transport = transport
        if self._on_connect is not None:
            self._accept_task = self._loop.create_task(
                self._on_connect(self))

    def get_buffer(self, sizehint: int):
        if self._frame is not None:
            return memoryview(self._frame)[self._pos:]
        if self._peek and self._have < 4:
            return memoryview(self._scratch)[
                self._have:self._have + _RECV_PEEK]
        return memoryview(self._scratch)[self._have:]

    def buffer_updated(self, nbytes: int) -> None:
        if self._error is not None:
            return      # a broken stream frames nothing more
        if self._frame is not None:
            self._pos += nbytes
            if self._pos == len(self._frame):
                frame, self._frame = self._frame, None
                self._peek = True
                self._deliver(frame)
            return
        have = self._have + nbytes
        scratch = memoryview(self._scratch)
        off = 0
        while have - off >= 4:
            (n,) = struct.unpack_from("<I", scratch, off)
            if not 1 <= n <= _MAX_FRAME:
                self._fail(ConnectionError(f"bad frame length {n}"))
                return
            end = off + 4 + n
            if end <= have:
                self._peek = False
                self._deliver(bytes(scratch[off + 4:end]))
                off = end
                continue
            if 4 + n > len(scratch):
                # it cannot fit: the rest goes straight into its own
                # buffer
                got = have - off - 4
                self._frame = bytearray(n)
                self._frame[:got] = scratch[off + 4:have]
                self._pos = got
                off = have
            break
        if off:
            # what is left (a split prefix, a frame that fits and is not
            # whole yet) goes to the front: room for the rest of it
            scratch[:have - off] = scratch[off:have]
        self._have = have - off

    def _deliver(self, frame) -> None:
        self._frames.append(frame)
        self._queued += len(frame)
        if self._queued >= _STREAM_LIMIT:
            self.transport.pause_reading()      # no-op while paused
        self._wake_reader()

    def _wake_reader(self) -> None:
        if self._reader is not None and not self._reader.done():
            self._reader.set_result(None)

    def _fail(self, exc: Exception) -> None:
        if self._error is None:
            self._error = exc
        self._wake_reader()

    def eof_received(self):
        self._fail(ConnectionResetError("connection closed by peer"))
        return False    # the transport closes itself

    def connection_lost(self, exc) -> None:
        self._lost = self._io_halt = True
        self._pending.clear()
        self._fail(exc if isinstance(exc, ConnectionError)
                   else ConnectionResetError("connection lost"))
        if not self._io_busy:
            self._finish_close()
        # else the sender thread's completion does: it lets go at its
        # next look (_io_send), within _IO_POLL_S

    def _finish_close(self) -> None:
        if self._io_sock is not None:
            self._io_sock.close()
            self._io_sock = None
        self._closed.set_result(None)
        self._wake_drainers()

    def pause_writing(self) -> None:
        self._write_paused = True

    def resume_writing(self) -> None:
        self._write_paused = False
        if self._flushing:
            # the transport's buffer is empty: the large frame that
            # waited for it can go
            self._flushing = False
            self.transport.set_write_buffer_limits()
            self._pump()
        self._wake_drainers()

    def _wake_drainers(self) -> None:
        for waiter in self._drainers:
            if not waiter.done():
                waiter.set_result(None)

    # -- what the messenger calls -------------------------------------------

    async def read_frame(self):
        """The next complete frame (type byte first, length prefix
        off): a ``bytearray`` of its own, or ``bytes`` cut out of the
        scratch buffer.  Frames already received are handed out before
        the connection's end is raised."""
        while not self._frames:
            if self._error is not None:
                raise self._error
            self._reader = self._loop.create_future()
            try:
                await self._reader
            finally:
                self._reader = None
        frame = self._frames.popleft()
        self._queued -= len(frame)
        if self._queued < _STREAM_LIMIT:
            self.transport.resume_reading()     # no-op unless paused
        return frame

    def write(self, parts: list) -> None:
        # a closing transport takes nothing more (as StreamWriter.write
        # drops it); drain() is where the sender learns of it
        if self.transport.is_closing():
            return
        large = sum(map(len, parts)) >= _IO_MIN
        if not (large or self._io_busy or self._pending):
            self.transport.writelines(parts)
            return
        self._pending.append((large, parts))
        self._pump()

    def _pump(self) -> None:
        """Send what is queued, in order, as far as it can go now: up to
        a large frame, which goes to a sender thread if the transport
        buffers nothing, and else waits for that (the transport tells
        when its buffer falls to its low-water mark, so the mark is 0
        for as long)."""
        pending = self._pending
        transport = self.transport
        if transport.is_closing():
            pending.clear()     # as write() drops it
            return
        while pending and not self._io_busy:
            large, parts = pending[0]
            if not large:
                transport.writelines(parts)
            elif transport.get_write_buffer_size():
                if not self._flushing:
                    self._flushing = True
                    transport.set_write_buffer_limits(high=0)
                return
            else:
                self._hand_over(parts)
            pending.popleft()

    def _io_socket(self) -> Optional[socket.socket]:
        """The socket the sender threads write this stream's frames to:
        a duplicate of the transport's descriptor (same connection, same
        buffers, same non-blocking mode), closed by ``_finish_close``
        alone, and a plain ``socket.socket``, not the loop account's."""
        sock = self.transport.get_extra_info("socket")
        if sock is None:
            return None
        try:
            fd = os.dup(sock.fileno())
        except OSError:
            return None
        dup = socket.socket(fileno=fd)
        dup.setblocking(False)
        return dup

    def _hand_over(self, parts: list) -> None:
        if self._io_sock is None:
            self._io_sock = self._io_socket()
        if self._io_sock is None:
            # no descriptor to write to (none left to duplicate, or a
            # transport that has no socket): the loop sends it after all
            KERNELS.inc("msgr_io_fallback")
            self.transport.writelines(parts)
            return
        self._io_busy = True
        _IO.submit(self, parts, _time.perf_counter_ns())

    def _io_send(self, parts: list, t0: int) -> None:
        """ON A SENDER THREAD: write ``parts`` to the socket until they
        are gone, then tell the loop.  No Python a byte: a call takes
        what the socket's buffer has room for, and the wait for more
        room is a ``poll`` of the one descriptor."""
        sock = self._io_sock
        sent = 0
        error = None
        began = _time.perf_counter_ns()
        try:
            poller = None
            while parts:
                if self._io_halt:
                    raise ConnectionAbortedError(
                        "stream closed under its frame")
                try:
                    n = _sendmsg(sock, parts[:_IOV_MAX])
                except (BlockingIOError, InterruptedError):
                    n = 0
                if n:
                    sent += n
                    parts = _rest(parts, n)
                    if not parts:
                        break
                # the socket's buffer is full: wait for room, in slices,
                # at whose edges a closed stream is seen
                if poller is None:
                    poller = select.poll()
                    poller.register(sock.fileno(), select.POLLOUT)
                stalled = 0.0
                while not (poller.poll(_IO_POLL_S * 1e3) or self._io_halt):
                    stalled += _IO_POLL_S
                    if stalled >= _IO_STALL_S:
                        raise TimeoutError(
                            f"peer took no byte for {_IO_STALL_S} s")
        except Exception as exc:
            error = exc
        took = _time.perf_counter_ns() - began
        try:
            self._loop.call_soon_threadsafe(
                self._io_done, error, sent, took, t0)
        except RuntimeError:
            pass    # the loop is closed: nobody is left to tell

    def _io_done(self, error: Optional[Exception], sent: int, took: int,
                 t0: int) -> None:
        """A sender thread is through with the stream's frame (on the
        loop): what queued up behind it goes on, or, after an error,
        the connection ends."""
        self._io_busy = False
        KERNELS.inc_many({
            "msgr_io_send_frames": 1, "msgr_io_send_bytes": sent,
            "msgr_io_call_ns": took,
            "msgr_io_wait_ns": _time.perf_counter_ns() - t0})
        if error is not None and not isinstance(error, OSError):
            # not the connection's fault: a bug in the sender's own code
            import logging

            logging.getLogger("ceph_tpu.msgr").error(
                "sender thread failed on a frame", exc_info=error)
        if self._lost:
            self._finish_close()
        elif error is not None:
            self._pending.clear()
            self.transport.abort()      # connection_lost wakes the drains
        else:
            self._pump()
            self._wake_drainers()

    async def drain(self) -> None:
        if self.transport.is_closing():
            # let connection_lost run, so that a write into a transport
            # that is going away surfaces here and not a frame later
            await asyncio.sleep(0)
        while (self._write_paused or self._io_busy or self._pending) \
                and not self._closed.done():
            waiter = self._loop.create_future()
            self._drainers.append(waiter)
            try:
                # flow control, not an RPC: the transport's
                # resume_writing or connection_lost ends it
                await waiter  # graftlint: ignore[rpc-timeout]
            finally:
                self._drainers.remove(waiter)
        if self._closed.done():
            raise ConnectionResetError("connection lost")

    def close(self) -> None:
        # a frame with a sender thread is given up: whoever closes
        # beside a send in flight voids it (its drain raises)
        self._io_halt = True
        self.transport.close()

    async def wait_closed(self) -> None:
        await asyncio.shield(self._closed)


@dataclass(frozen=True)
class EntityName:
    type: str  # mon | osd | client | mgr
    num: int

    def __str__(self):
        return f"{self.type}.{self.num}"


@dataclass
class Message:
    """Base message; src/seq/sid/src_addr/ack are stamped by the sending
    messenger.  A session frame (``sid`` set) says in ``src_addr`` where
    its messenger listens, which is how the peer's own frames to that
    address find the ack they can carry; ``ack`` is such a carried ack:
    ``(sid, seq)``, "the messenger ``sid``'s session to me: everything
    up to ``seq`` has arrived" (module docstring, "The ack").

    ``trace`` is the op-lifecycle trace header (round 6 telemetry): a
    {"id", "events": [(name, wall_ts), ...]} dict minted by the objecter
    and stamped by each messenger hop, absorbed into the receiving
    daemon's TrackedOp so dump_historic_ops shows the op's cross-daemon
    timeline (reference: the OpRequest's event list + blkin-style trace
    propagation)."""

    src: Optional[EntityName] = field(default=None, init=False)
    seq: int = field(default=0, init=False)
    sid: int = field(default=0, init=False)
    src_addr: Optional[Addr] = field(default=None, init=False)
    ack: Optional[Tuple[int, int]] = field(default=None, init=False)
    trace: Optional[dict] = field(default=None, init=False)


@dataclass
class _MsgAck(Message):
    """An owed ack that nothing carried: a frame that is its header's
    ``ack`` and nothing else (``Messenger._ack_alone`` makes it)."""


@dataclass
class _MsgAuth(Message):
    """Connection authorizer (cephx mode): MUST be the first frame on a
    connection; carries the sealed ticket + session-key possession proof
    (reference CephXAuthorizer in the connection handshake)."""

    authorizer: bytes = b""


@dataclass
class _MsgAuthRequest(Message):
    """Client -> mon ticket request (reference CEPH_AUTH_CEPHX
    MAuth): entity + proof of the per-entity key."""

    entity: str = ""
    nonce: bytes = b""
    proof: bytes = b""


@dataclass
class _MsgAuthReply(Message):
    """Mon -> client: sealed ticket + session key sealed under the
    entity key (result != 0 -> refused)."""

    result: int = 0
    ticket_blob: bytes = b""
    sealed_key: bytes = b""
    ttl: float = 3600.0
    error: str = ""


class _Session:
    """Per-peer outgoing session: seq numbering + unacked replay buffer
    (reference AsyncConnection out_seq/out_q)."""

    MAX_UNACKED = 512

    def __init__(self):
        self.conn: Optional["Connection"] = None
        self.seq = 0
        # seq -> the frame as _encode made it (pickle, out-of-band
        # buffers): unsigned, so a replay signs with the new
        # connection's key
        self.unacked: "OrderedDict[int, _Frame]" = OrderedDict()
        self.overflowed = False
        # set by a chaos frame drop: NO later frame may go out until the
        # tail is replayed — the peer's acks are CUMULATIVE (ack of N
        # trims everything <= N), which is only sound while delivery is
        # in-order, so a skipped frame must block the session until
        # retransmission restores order
        self.needs_replay = False
        # unique attribute name on purpose: graftlint's static lock
        # resolver binds attr -> lock name, and PGState already owns
        # the bare attr `lock`
        self.order_lock = DepLock("messenger.session")

    def buffer(self, seq: int, frame: "_Frame") -> None:
        self.unacked[seq] = frame
        while len(self.unacked) > self.MAX_UNACKED:
            # cannot trim silently and still promise at-least-once: mark
            # the session broken so the next reconnect FAILS loudly
            # instead of replaying an incomplete tail
            self.overflowed = True
            self.unacked.popitem(last=False)

    def ack(self, seq: int) -> None:
        # the buffer is in seq order (buffer appends, _late_send sorts
        # what it puts back), so what an ack frees is its front
        unacked = self.unacked
        while unacked and next(iter(unacked)) <= seq:
            unacked.popitem(last=False)
        if not unacked:
            self.overflowed = False  # fully acked: contract restored


class _Owed:
    """What this messenger owes ONE peer session, as received on one
    connection: the newest sequence number taken, the newest whose ack
    has left (in a frame or alone), and of what lies between them the
    frames, the bytes and when it goes alone; and the ``taken`` at which
    a frame that arrived again was last answered."""

    __slots__ = ("conn", "sid", "taken", "acked", "count", "nbytes", "due",
                 "told")

    # owed frames at which the ack goes alone: the sender's buffer
    # overflows at MAX_UNACKED, in flight included
    MAX_FRAMES = _Session.MAX_UNACKED // 4

    def __init__(self, conn: "Connection", sid: int, taken: int):
        self.conn: Optional["Connection"] = conn
        self.sid = sid
        self.taken = self.acked = taken
        self.count = 0
        self.nbytes = 0
        self.due = 0.0
        self.told = -1

    def ack(self) -> Optional[Tuple[int, int]]:
        """What a frame to the peer says of it: nothing if nothing is
        owed."""
        return (self.sid, self.taken) if self.taken > self.acked else None


class Connection:
    def __init__(self, messenger: "Messenger", stream: _FrameStream,
                 peer: Optional[EntityName] = None,
                 peer_addr: Optional[Addr] = None):
        self.messenger = messenger
        self.stream = stream
        self.peer = peer
        self.peer_addr = peer_addr
        self._send_lock = DepLock("messenger.conn_send")
        self._seq = 0
        self.closed = False
        # the acks owed to the session whose frames this connection
        # brings in (Messenger._owe); a raw send on it carries them
        self.owed: Optional[_Owed] = None
        # cephx session state (set by the authorizer handshake):
        # subsequent frames both ways sign with the session key, and
        # dispatchers consult peer_caps for authorization
        self.session_key: Optional[bytes] = None
        self.peer_entity: Optional[str] = None
        self.peer_caps: Optional[Dict[str, str]] = None

    def _sign_key(self) -> Optional[bytes]:
        return self.session_key if self.session_key is not None \
            else self.messenger.secret

    async def send(self, msg: Message) -> None:
        msg.src = self.messenger.name
        async with self._send_lock:
            self._seq += 1
            msg.seq = self._seq
            # not a session frame, whatever a hop before this one made of
            # the object: its seq numbers this connection, not a session
            msg.sid, msg.src_addr = 0, None
            if msg.trace is not None:
                # hop stamp for replies riding raw connections (the
                # reply-leg half of op attribution; send_message stamps
                # session traffic the same way)
                msg.trace.setdefault("events", []).append(
                    (f"msgr:{self.messenger.name}:send", _time.time()))
            hs = _encode_hs(msg)
            ack = None
            if hs is not None:
                # handshake: fixed struct, pre-session, unsigned
                parts = [struct.pack("<I", len(hs)), hs]
            else:
                # the reply on the connection the request came in on
                # takes the request's ack with it
                msg.ack = ack = self.owed and self.owed.ack()
                parts = _frame_parts(self._sign_key(), _encode(msg))
            try:
                self.stream.write(parts)
                self.messenger._ack_left(self.owed, ack)
                await self.stream.drain()
            except (ConnectionError, RuntimeError):
                self.closed = True
                raise

    async def close(self) -> None:
        """A close that returns: a graceful close first flushes what the
        transport still buffers, and a peer that has stopped reading
        (a throttled or wedged read loop behind megabytes of frames)
        never lets it finish — ``wait_closed`` then waits forever, and
        with it every ``shutdown`` up to ``Cluster.stop``.  After
        ``_CLOSE_WAIT_S`` the transport is aborted: the bytes a closing
        endpoint still owed were void anyway.  A large frame that a
        sender thread is writing is given up at once, and the close
        returns when the thread has let the socket go (within
        ``_IO_POLL_S``: ``_FrameStream``)."""
        self.closed = True
        try:
            self.stream.close()
            await asyncio.wait_for(self.stream.wait_closed(),
                                   _CLOSE_WAIT_S)
        except asyncio.TimeoutError:
            self.stream.transport.abort()
        except (ConnectionError, OSError, RuntimeError):
            pass  # best-effort close of an already-dying transport


class Dispatcher:
    async def ms_dispatch(self, conn: Connection, msg: Message) -> bool:
        """Return True if handled."""
        return False

    async def ms_handle_reset(self, conn: Connection) -> None:
        ...


class Throttle:
    """Byte-budget backpressure (reference Throttle bound to the
    messenger policies, src/ceph_osd.cc:511-525 client-throttler): a
    reader acquires its frame's bytes before dispatch and releases
    after; when the budget is exhausted the reader WAITS — it stops
    draining its socket, so TCP backpressure propagates to the peer
    instead of the daemon queueing unboundedly."""

    def __init__(self, max_bytes: int):
        self.max = max_bytes
        self.cur = 0
        self.waiting = 0
        self._cond = asyncio.Condition()

    async def acquire(self, n: int) -> bool:
        """Returns True when the caller had to WAIT for budget — the
        signal the read loop stamps into the op's trace header so
        throttle wait shows up in per-stage attribution."""
        n = min(n, self.max)  # a single oversized frame must not wedge
        waited = False
        async with self._cond:
            self.waiting += 1
            try:
                while self.cur + n > self.max:
                    waited = True
                    await self._cond.wait()
            finally:
                self.waiting -= 1
            self.cur += n
        return waited

    async def release(self, n: int) -> None:
        n = min(n, self.max)
        async with self._cond:
            self.cur = max(0, self.cur - n)
            self._cond.notify_all()


@dataclass
class Policy:
    """Per-peer-type connection policy (reference Messenger::Policy):
    ``lossy`` sessions do NOT replay their unacked tail across a reset —
    the send fails and the peer re-requests (stateless client policy;
    enforced in _reconnect_replay); ``throttle`` bounds bytes
    concurrently in dispatch from peers of this type (backpressure in
    _read_loop)."""

    lossy: bool = False
    throttle: Optional[Throttle] = None


SIG_LEN = 16

# frame-type bytes: every frame is <u32 len><type><body>.  Type 0 is a
# pickled Message (signed when a key is bound); types 1-3 are the cephx
# handshake in FIXED struct encodings, so no unauthenticated byte ever
# reaches the pickle deserializer (the r4 advisor's high finding: the
# old handshake pickled first and authenticated after).  Type 4 is a
# Message whose large buffers ride out of band of its pickle:
#   <u16 nbufs><u32 pickle_len><u32 buf_len>*nbufs <pickle> <buf>* [sig]
# signed over everything from the type byte to the last buffer, so the
# type, the lengths and every payload byte are under the signature.
_FT_MSG, _FT_AUTH, _FT_AUTH_REQ, _FT_AUTH_REPLY, _FT_MSG_OOB = 0, 1, 2, 3, 4

# a message as it is framed: (pickle, the buffers it refers to in order).
# No buffers: an in-band frame (type 0), the pickle and nothing else.
_Frame = Tuple[bytes, tuple]


def _sign(secret: bytes, *parts) -> bytes:
    mac = _hmac.new(secret, digestmod=hashlib.sha256)
    for part in parts:
        mac.update(part)
    return mac.digest()[:SIG_LEN]


def oob(data, protocol: int):
    """What a message's ``__reduce_ex__`` puts in place of a field that
    carries object data: a ``PickleBuffer`` over it when it is worth
    offering to the frame (pickle protocol 5: ``bytes`` of at least
    ``_OOB_MIN``, or a ``memoryview``, which is how such a field arrives
    and which no protocol pickles as it is), else the field itself.
    Whether the buffer then leaves the pickle is ``_encode``'s call; a
    pickler without a buffer callback writes it in band as ``bytes``."""
    kind = type(data)
    if kind is memoryview:
        return pickle.PickleBuffer(data) if protocol >= 5 else bytes(data)
    if kind is bytes and protocol >= 5 and len(data) >= _OOB_MIN:
        return pickle.PickleBuffer(data)
    return data


def reduce_with(msg: "Message", **fields):
    """``object.__reduce_ex__``'s result for a message (new object, then
    its ``__dict__``) with ``fields`` in place of the attributes of the
    same names: the message itself is left as it is."""
    return copyreg.__newobj__, (type(msg),), {**msg.__dict__, **fields}


def _encode(msg: "Message") -> _Frame:
    """Pickle ``msg``.  A buffer that is offered (``oob``, or numpy's own
    protocol-5 reduce) leaves the pickle only if it is read-only and at
    least ``_OOB_MIN`` long: the frame stays in the replay buffer and
    may go out again, so it must never carry bytes that can change
    after the send returned; anything else is copied into the pickle
    here, as before."""
    bufs = []

    def in_band(buf: pickle.PickleBuffer) -> bool:
        raw = buf.raw()
        if raw.readonly and raw.nbytes >= _OOB_MIN:
            bufs.append(raw)
            return False
        return True

    acct = loopacct.ACCOUNT
    t0 = _time.perf_counter_ns() if acct is not None and acct.timing \
        else 0
    payload = pickle.dumps(msg, protocol=5, buffer_callback=in_band)
    out = sum(len(b) for b in bufs)
    KERNELS.inc("msgr_frames")
    KERNELS.inc("msgr_frame_bytes", len(payload) + out)
    if out:
        KERNELS.inc("msgr_oob_bytes", out)
    if t0:
        acct.codec_done(t0)
    return payload, tuple(bufs)


def _frame_parts(key: Optional[bytes], frame: _Frame) -> list:
    """The frame as the list of buffers that goes to the transport:
    header, pickle, the out-of-band buffers as they are, signature.
    Nothing is joined, so a payload byte is not copied on its way
    out."""
    payload, bufs = frame
    sig_len = SIG_LEN if key is not None else 0
    if not bufs:
        parts = [struct.pack("<IB", 1 + len(payload) + sig_len, _FT_MSG),
                 payload]
        if key is not None:
            parts.append(_sign(key, payload))
        return parts
    lens = [len(b) for b in bufs]
    head = struct.pack(
        f"<IBHI{len(bufs)}I",
        7 + 4 * len(bufs) + len(payload) + sum(lens) + sig_len,
        _FT_MSG_OOB, len(bufs), len(payload), *lens)
    parts = [head, payload, *bufs]
    if key is not None:
        parts.append(_sign(key, memoryview(head)[4:], *parts[1:]))
    return parts


def _decode_oob(body: memoryview) -> "Message":
    """Unpickle a type-4 frame's body (type byte and signature off,
    ALREADY verified): the buffers reach the message as read-only views
    of the frame's own buffer, which they keep alive."""
    try:
        nbufs, plen = struct.unpack_from("<HI", body)
        lens = struct.unpack_from(f"<{nbufs}I", body, 6)
    except struct.error:
        raise ConnectionError("malformed out-of-band frame")
    pos = 6 + 4 * nbufs
    if pos + plen + sum(lens) != len(body):
        raise ConnectionError("malformed out-of-band frame")
    payload = body[pos:pos + plen]
    pos += plen
    views = []
    for n in lens:
        views.append(body[pos:pos + n])
        pos += n
    return pickle.loads(payload, buffers=views)


def _encode_hs(msg: Message) -> Optional[bytes]:
    """Handshake frame body (type byte + fixed struct), or None for
    ordinary messages."""
    if isinstance(msg, _MsgAuth):
        return bytes([_FT_AUTH]) + msg.authorizer
    if isinstance(msg, _MsgAuthRequest):
        e = msg.entity.encode()
        return (bytes([_FT_AUTH_REQ]) + struct.pack("<H", len(e)) + e +
                struct.pack("<B", len(msg.nonce)) + msg.nonce +
                struct.pack("<B", len(msg.proof)) + msg.proof)
    if isinstance(msg, _MsgAuthReply):
        err = msg.error.encode()
        return (bytes([_FT_AUTH_REPLY]) +
                struct.pack("<idII", msg.result, msg.ttl,
                            len(msg.ticket_blob), len(msg.sealed_key)) +
                msg.ticket_blob + msg.sealed_key +
                struct.pack("<H", len(err)) + err)
    return None


def _decode_hs(ftype: int, body: bytes) -> Message:
    try:
        if ftype == _FT_AUTH:
            return _MsgAuth(authorizer=body)
        if ftype == _FT_AUTH_REQ:
            (el,) = struct.unpack_from("<H", body)
            off = 2
            entity = body[off:off + el].decode()
            off += el
            nl = body[off]
            nonce = body[off + 1:off + 1 + nl]
            off += 1 + nl
            pl = body[off]
            proof = body[off + 1:off + 1 + pl]
            if off + 1 + pl != len(body):
                raise ValueError("trailing bytes")
            return _MsgAuthRequest(entity=entity, nonce=nonce, proof=proof)
        if ftype == _FT_AUTH_REPLY:
            result, ttl, tl, kl = struct.unpack_from("<idII", body)
            off = struct.calcsize("<idII")
            blob = body[off:off + tl]
            key = body[off + tl:off + tl + kl]
            off += tl + kl
            (el,) = struct.unpack_from("<H", body, off)
            err = body[off + 2:off + 2 + el].decode()
            if off + 2 + el != len(body) or len(blob) != tl or len(key) != kl:
                raise ValueError("trailing bytes")
            return _MsgAuthReply(result=result, ttl=ttl, ticket_blob=blob,
                                 sealed_key=key, error=err)
    except (struct.error, IndexError, UnicodeDecodeError, ValueError) as e:
        raise ConnectionError(f"malformed handshake frame: {e}")
    raise ConnectionError(f"unknown frame type {ftype}")


class Messenger:
    def __init__(self, name: EntityName, secret: bytes = None, auth=None,
                 config=None):
        self.name = name
        self.secret = secret
        # cephx mode (auth = auth.CephxContext): per-connection session
        # keys replace the global secret; secret must be None then
        self.auth = auth
        if auth is not None:
            self.secret = None
        # chaos net injector (ceph_tpu/chaos/net.py), rebuilt whenever
        # the owning daemon's chaos_net_* options change (injectargs
        # seam, like the reference's ms_inject_socket_failures).  None
        # when disabled: the send path pays one `is None` test.
        self.config = config
        self.chaos = None
        if config is not None:
            config.add_observer(self._chaos_observer)
            self._chaos_reconfig()
        # mon-side hook: callable(_MsgAuthRequest) -> _MsgAuthReply
        self.auth_server = None
        self.sid = next(_SID)
        self.dispatchers: List[Dispatcher] = []
        self._server: Optional[asyncio.base_events.Server] = None
        self._out: Dict[Addr, Connection] = {}
        # the heartbeat lane: one more connection per peer, which carries
        # pings and their replies and nothing else (send_heartbeat)
        self._hb_out: Dict[Addr, Connection] = {}
        self._sessions: Dict[Addr, _Session] = {}
        # acks owed (module docstring, "The ack"): by the address the
        # owed session's messenger listens on, which is where
        # send_message finds what its frame can carry; and those that
        # owe anything now, which is what the one flush timer walks
        self._owed_to: Dict[Addr, _Owed] = {}
        self._owing: Set[_Owed] = set()
        self._ack_timer: Optional[asyncio.TimerHandle] = None
        self._accepted: List[Connection] = []
        # live-task registry: completed tasks self-discard, or a chaos
        # run would grow one dead Task per dropped/reordered frame for
        # the daemon's lifetime
        self._tasks: Set[asyncio.Task] = set()
        self._auth_waiters: Dict[int, asyncio.Future] = {}
        self._closing = False
        self.my_addr: Optional[Addr] = None
        # per-peer-type policies (reference Messenger::set_policy, bound
        # in ceph_osd.cc:511-525); key None = default
        self._policies: Dict[Optional[str], Policy] = {}

    def _chaos_observer(self, name: str, value) -> None:
        if name.startswith("chaos_net") or name == "chaos_seed":
            self._chaos_reconfig()

    def _chaos_reconfig(self) -> None:
        from ceph_tpu.chaos.net import NetInjector

        keep = self.chaos.partitions if self.chaos is not None else None
        self.chaos = NetInjector.from_config(
            self.config, str(self.name), keep_partitions=keep)

    def set_policy(self, peer_type: Optional[str], policy: Policy) -> None:
        """Bind a Policy for connections whose peer entity has ``type``
        (e.g. 'client', 'osd'); ``None`` sets the default."""
        self._policies[peer_type] = policy

    def policy_for(self, conn: "Connection") -> Optional[Policy]:
        ptype = conn.peer.type if conn.peer is not None else None
        return self._policies.get(ptype, self._policies.get(None))

    def add_dispatcher(self, d: Dispatcher) -> None:
        self.dispatchers.append(d)

    async def bind(self, host: str = "127.0.0.1", port: int = 0) -> Addr:
        loop = asyncio.get_running_loop()

        def factory():
            return _FrameStream(on_connect=self._accept)

        # where the loop keeps an account (trace/loopacct.py) the
        # listening socket is of the account's kind, and so are those it
        # accepts: their sends and reads are timed.  Same options, same
        # transport
        acct = loopacct.of(loop)
        if acct is None:
            self._server = await loop.create_server(factory, host, port)
        else:
            sock = acct.listen(host, port)
            try:
                self._server = await loop.create_server(factory, sock=sock)
            except BaseException:
                sock.close()
                raise
        self.my_addr = self._server.sockets[0].getsockname()[:2]
        return self.my_addr

    async def _open(self, addr: Addr) -> _FrameStream:
        loop = asyncio.get_running_loop()
        acct = loopacct.of(loop)
        if acct is None:
            _, stream = await loop.create_connection(
                _FrameStream, addr[0], addr[1])
            return stream
        sock = await acct.connect(addr)
        try:
            _, stream = await loop.create_connection(_FrameStream, sock=sock)
        except BaseException:
            sock.close()
            raise
        return stream

    async def _accept(self, stream: _FrameStream) -> None:
        _tune_socket(stream)
        conn = Connection(self, stream)
        if self._closing:
            # a peer raced our shutdown: refuse, or the read loop would
            # keep Server.wait_closed() (which since py3.12 awaits every
            # handler) hanging until the PEER closes — a distributed
            # shutdown deadlock when that peer stops after us
            await conn.close()
            return
        self._accepted.append(conn)
        task = asyncio.current_task()
        if task is not None:
            self._track(task)
            loopacct.tag(task, _READER_BUCKET.get(self.name.type, "other"))
        await self._read_loop(conn)

    async def _read_loop(self, conn: Connection) -> None:
        try:
            while True:
                frame = await conn.stream.read_frame()
                n = len(frame)
                # verification, signature strip and unpickle all run on
                # views of the buffer the socket was read into; an
                # out-of-band field of the message is one more such
                # view, so a payload byte has not been copied in user
                # space when the message reaches its dispatcher
                view = memoryview(frame)
                ftype, payload = frame[0], view[1:]
                if ftype not in (_FT_MSG, _FT_MSG_OOB):
                    # handshake frames: fixed struct decode, no pickle
                    # (tiny; decoded from a plain bytes copy)
                    msg = _decode_hs(ftype, bytes(payload))
                    if self.auth is None or not await \
                            self._handle_auth_frame(conn, msg):
                        raise ConnectionError(
                            f"unexpected handshake frame type {ftype}")
                    continue
                if self.auth is not None and conn.session_key is None:
                    # cephx mode: nothing but the handshake may ride an
                    # unauthenticated connection — reject BEFORE any
                    # deserialization
                    raise ConnectionError("unauthenticated data frame")
                verify_key = conn.session_key if conn.session_key \
                    is not None else self.secret
                if verify_key is not None:
                    # verify BEFORE unpickling: unauthenticated bytes
                    # must never reach the deserializer.  An out-of-band
                    # frame is signed from its type byte on (pickle,
                    # lengths and every buffer), an in-band one over
                    # its pickle: a flipped type byte fails both
                    signed = view[:-SIG_LEN] if ftype == _FT_MSG_OOB \
                        else payload[:-SIG_LEN]
                    if len(payload) < SIG_LEN or not _hmac.compare_digest(
                            _sign(verify_key, signed), view[-SIG_LEN:]):
                        raise ConnectionError("bad message signature")
                    payload = payload[:-SIG_LEN]
                acct = loopacct.ACCOUNT
                t0 = _time.perf_counter_ns() if acct is not None \
                    and acct.timing else 0
                msg = _decode_oob(payload) if ftype == _FT_MSG_OOB \
                    else pickle.loads(payload)
                if t0:
                    acct.codec_done(t0)
                    acct.cut(type(msg).__name__)
                if conn.peer is None:
                    conn.peer = msg.src
                if msg.trace is not None:
                    # receive-side hop stamp: the trace header records
                    # when this endpoint took the message off the wire
                    # (arrival, before any dispatch queueing) — the
                    # "wire" stage boundary in op attribution
                    msg.trace.setdefault("events", []).append(
                        (f"msgr:{self.name}:recv", _time.time()))
                ack = msg.ack
                if ack is not None and ack[0] == self.sid:
                    # whatever the frame is, it says how far our session
                    # to its sender has arrived: on a connection we
                    # opened that is the session to its address, on an
                    # accepted one the sender's frame says where it
                    # listens.  An ack that names another sid was owed
                    # to a messenger that had this address before us
                    sess = self._sessions.get(conn.peer_addr or msg.src_addr)
                    if sess is not None:
                        sess.ack(ack[1])
                if isinstance(msg, _MsgAck):
                    continue
                if msg.sid:
                    # session traffic: the sender trims its replay
                    # buffer by the ack it is now owed
                    self._owe(conn, msg, n)
                pol = self.policy_for(conn)
                thr = pol.throttle if pol is not None else None
                if thr is not None:
                    # byte-budget backpressure: while this waits no
                    # frame is taken from the stream, which stops reading
                    # its socket once _STREAM_LIMIT bytes of frames
                    # queue: TCP backpressure reaches the peer
                    if await thr.acquire(n) and msg.trace is not None:
                        # the wait was real: stamp it so attribution
                        # books the delta as throttle_wait, not wire
                        msg.trace.setdefault("events", []).append(
                            (f"throttle:{self.name}:acquired",
                             _time.time()))
                    # dispatch handoff seam: a dispatcher that QUEUES the
                    # message (the OSD's ShardedOpWQ analog) takes
                    # ownership by setting _throttle_held and releases
                    # after serving — the cap then bounds bytes in
                    # dispatch, not merely in enqueue
                    msg._throttle = thr
                    msg._throttle_bytes = n
                try:
                    for d in self.dispatchers:
                        if await d.ms_dispatch(conn, msg):
                            break
                finally:
                    if thr is not None and \
                            not getattr(msg, "_throttle_held", False):
                        await thr.release(n)
        except (ConnectionError, asyncio.CancelledError):
            # actually CLOSE the socket (not just flag it): a signature
            # mismatch must tear the TCP stream down so the peer's session
            # sees the failure and reconnect+replay engages, instead of
            # writing into a blackholed socket until overflow
            await conn.close()
            owed = conn.owed
            if owed is not None:
                # what was owed on it is void: the session's next send
                # finds the connection dead and replays its tail.  What
                # it has taken stays known under the session's address,
                # without the connection and its buffers
                self._ack_left(owed, (owed.sid, owed.taken), carried=False)
                owed.conn = conn.owed = None
            for d in self.dispatchers:
                try:
                    await d.ms_handle_reset(conn)
                except Exception:
                    # a broken reset hook must not kill the read loop,
                    # but it is a BUG in the dispatcher — surface it
                    import logging

                    logging.getLogger("ceph_tpu.msgr").exception(
                        "%s: ms_handle_reset hook failed", self.name)

    def _owe(self, conn: Connection, msg: Message, nbytes: int) -> None:
        """A session frame arrived: its ack is owed, not sent."""
        KERNELS.inc("msgr_acks_owed")
        owed = conn.owed
        if owed is None:
            # a connection brings in one session's frames: its opener's
            prev = self._owed_to.get(msg.src_addr)
            # the same session on a new connection: what it replays of
            # what the old one brought is known
            owed = conn.owed = _Owed(
                conn, msg.sid, prev.taken if prev is not None
                and prev.sid == msg.sid else msg.seq - 1)
            if msg.src_addr is not None:
                self._owed_to[msg.src_addr] = owed
        if msg.seq <= owed.taken:
            # a frame again (a replay, a duplicate, one that was
            # overtaken): the sender is recovering, tell it at once how
            # far it got — once, not for every frame of a replayed tail
            if owed.told != owed.taken:
                owed.told = owed.taken
                self._ack_alone(owed)
            return
        if owed.taken == owed.acked:
            owed.due = _time.monotonic() + _ACK_DELAY_S
            self._owing.add(owed)
        owed.taken = msg.seq
        owed.count += 1
        owed.nbytes += nbytes
        if owed.nbytes >= _ACK_BYTES or owed.count >= owed.MAX_FRAMES:
            self._ack_alone(owed)
        elif self._ack_timer is None and not self._closing:
            self._ack_timer = asyncio.get_running_loop().call_later(
                _ACK_DELAY_S, self._flush_acks)

    def _ack_left(self, owed: Optional[_Owed],
                  ack: Optional[Tuple[int, int]],
                  carried: bool = True) -> None:
        """``ack``, if the frame had one, has been written to ``owed``'s
        peer: inside a frame (``carried``) or alone.  What arrived since
        it was made is still owed."""
        if ack is None or ack[1] <= owed.acked:
            return      # nothing, or another frame took it meanwhile
        seq = ack[1]
        done = seq == owed.taken
        n = owed.count if done else min(owed.count, seq - owed.acked)
        if carried:
            KERNELS.inc("msgr_acks_carried", n)
        owed.acked = seq
        owed.count -= n
        if done:
            owed.nbytes = 0
            self._owing.remove(owed)

    def _ack_alone(self, owed: _Owed) -> None:
        """The one place a ``_MsgAck`` is made: on the connection the
        frames came in on, written and not drained (it is a hundred
        bytes, and a peer that does not read must not hold up the
        others' acks)."""
        conn = owed.conn
        ack = _MsgAck()
        ack.src = self.name
        ack.ack = owed.sid, owed.taken
        if not conn.closed:
            conn.stream.write(_frame_parts(conn._sign_key(), _encode(ack)))
        self._ack_left(owed, ack.ack, carried=False)

    def _flush_acks(self) -> None:
        """The messenger's one timer: every owed ack that nothing carried
        within ``_ACK_DELAY_S`` goes alone, and the timer is set for the
        next one due."""
        self._ack_timer = None
        now = _time.monotonic()
        for owed in [o for o in self._owing if o.due <= now]:
            self._ack_alone(owed)
        if self._owing and not self._closing:
            self._ack_timer = asyncio.get_running_loop().call_later(
                min(o.due for o in self._owing) - now, self._flush_acks)

    async def _handle_auth_frame(self, conn: Connection, msg) -> bool:
        """cephx transport frames (already struct-decoded — the pickle
        deserializer never sees unauthenticated bytes; the authorizer's
        pickled interior sits behind the sealed ticket's MAC)."""
        from ceph_tpu.cluster import auth as authmod

        if isinstance(msg, _MsgAuth):
            if self.auth.master is None:
                raise ConnectionError("no master key to verify authorizer")
            try:
                t = authmod.verify_authorizer(self.auth.master,
                                              msg.authorizer)
            except ValueError as e:
                # malformed/forged authorizer must tear the connection
                # down through the normal reset path (close +
                # ms_handle_reset), not kill the read-loop task
                raise ConnectionError(f"bad authorizer: {e}")
            conn.session_key = t.session_key
            conn.peer_entity = t.entity
            conn.peer_caps = t.caps
            return True
        if isinstance(msg, _MsgAuthRequest):
            if self.auth_server is None:
                raise ConnectionError("not an auth server")
            reply = self.auth_server(msg)
            await conn.send(reply)
            return True
        if isinstance(msg, _MsgAuthReply):
            fut = self._auth_waiters.pop(id(conn), None)
            if fut is not None and not fut.done():
                fut.set_result(msg)
            return True
        return False

    async def cephx_bootstrap(self, mon_addr: Addr) -> None:
        """Client ticket bootstrap (reference MAuth round-trip): prove
        the entity key to a monitor, adopt the returned ticket."""
        import os as _os

        from ceph_tpu.cluster import auth as authmod

        nonce = _os.urandom(16)
        proof = _hmac.new(self.auth.entity_secret,
                          b"authreq:" + self.auth.entity.encode() + nonce,
                          hashlib.sha256).digest()[:SIG_LEN]
        conn = Connection(self, await self._open(mon_addr),
                          peer_addr=tuple(mon_addr))
        fut = asyncio.get_event_loop().create_future()
        self._auth_waiters[id(conn)] = fut
        task = asyncio.get_event_loop().create_task(self._read_loop(conn))
        loopacct.tag(task, _READER_BUCKET.get(self.name.type, "other"))
        self._track(task)
        try:
            await conn.send(_MsgAuthRequest(entity=self.auth.entity,
                                            nonce=nonce, proof=proof))
            reply = await asyncio.wait_for(fut, timeout=10.0)
            if reply.result != 0:
                raise PermissionError(
                    f"auth refused for {self.auth.entity}: {reply.error}")
            self.auth.adopt(reply.ticket_blob, reply.sealed_key,
                            ttl_hint=getattr(reply, "ttl", 3600.0))
        finally:
            self._auth_waiters.pop(id(conn), None)
            await conn.close()

    async def connect(self, addr: Addr, lane: Optional[
            Dict[Addr, Connection]] = None) -> Connection:
        """The live connection to ``addr`` in ``lane`` (the data lane
        ``_out`` unless the heartbeat lane is named), opened on demand."""
        if lane is None:
            lane = self._out
        if self.chaos is not None:
            # asymmetric partition: OUR connects to that peer fail like
            # a blackholed TCP connect; their path to us is untouched
            self.chaos.check_connect(addr)
        conn = lane.get(tuple(addr))
        if conn is not None and not conn.closed:
            return conn
        stream = await self._open(addr)
        _tune_socket(stream)
        conn = Connection(self, stream, peer_addr=tuple(addr))
        if self.auth is not None:
            # authorizer-first (reference connection handshake): present
            # the ticket before any session traffic; the session key
            # signs everything after
            from ceph_tpu.cluster import auth as authmod

            self.auth.ensure_ticket()
            await conn.send(_MsgAuth(authorizer=authmod.make_authorizer(
                self.auth.ticket_blob, self.auth.session_key)))
            conn.session_key = self.auth.session_key
        lane[tuple(addr)] = conn
        task = asyncio.get_event_loop().create_task(self._read_loop(conn))
        loopacct.tag(task, _READER_BUCKET.get(self.name.type, "other"))
        self._track(task)
        return conn

    async def send_heartbeat(self, msg: Message, addr: Addr) -> None:
        """Send a ping on the heartbeat lane: a connection of its own per
        peer, so that neither the ping nor its reply (which the peer
        writes back on the connection the ping came in on) ever waits
        behind megabyte data frames in a socket buffer, behind the data
        lane's session lock and drain, or behind the inline dispatch of
        queued sub-writes in the peer's read loop (reference: the OSD's
        dedicated hb_front/hb_back messengers, src/ceph_osd.cc).  No
        session and no replay: a lost ping is not worth resending, the
        next one asks the same question.  A peer that listens no more
        raises ``ConnectionRefusedError``: evidence of a dead daemon that
        needs no grace (reference osd_fast_fail_on_connection_refused)."""
        conn = await self.connect(tuple(addr), self._hb_out)
        await conn.send(msg)

    async def send_message(self, msg: Message, addr: Addr) -> None:
        """Session send: ordered at-least-once with reconnect + replay of
        the unacked tail (reference AsyncConnection replay)."""
        addr = tuple(addr)
        sess = self._sessions.get(addr)
        if sess is None:
            sess = self._sessions[addr] = _Session()
        async with sess.order_lock:
            sess.seq += 1
            msg.src = self.name
            msg.seq = sess.seq
            msg.sid = self.sid
            msg.src_addr = self.my_addr
            # a frame to the address a session reaches us from takes
            # that session's ack with it
            owed = self._owed_to.get(addr)
            msg.ack = ack = owed and owed.ack()
            if msg.trace is not None:
                # messenger hop stamp: the trace header records when this
                # endpoint put the message on the wire
                msg.trace.setdefault("events", []).append(
                    (f"msgr:{self.name}:send", _time.time()))
            if self.chaos is not None:
                # batch-frame faults mutate the message BEFORE pickling
                # so the buffered replay frame carries the same partial
                # tick — the item loss is real, not racing replay
                self.chaos.mutate_batch(msg)
            frame = _encode(msg)
            # buffer the UNSIGNED frame and sign at write time with the
            # connection's key: a cephx ticket renewal mints a new session
            # key for NEW connections, while frames replayed over a fresh
            # connection must carry the fresh key's signature (signing at
            # buffer time would wedge the replay after every renewal).
            # What is buffered are the pickle and REFERENCES to the
            # out-of-band buffers, which are read-only (_encode): a
            # replay sends the bytes the first send did
            sess.buffer(sess.seq, frame)
            fate = None
            if self.chaos is not None:
                fate = self.chaos.on_frame(addr)
                if fate.delay:
                    await asyncio.sleep(fate.delay)
                if fate.drop:
                    # drop + socket failure (reference
                    # ms_inject_socket_failures): the frame stays in
                    # unacked, the connection dies, and the session is
                    # GATED (needs_replay) until a retransmission timer
                    # or the next send replays the tail in order —
                    # packet loss under retransmission, not silent
                    # erasure (under a partition the replayed reconnect
                    # fails too and the loss is real)
                    sess.needs_replay = True
                    old = self._out.pop(addr, None)
                    if old is not None:
                        await old.close()
                    self._track(
                        asyncio.get_event_loop().create_task(
                            self._replay_later(sess, addr,
                                               fate.retransmit)))
                    return
                if fate.reorder and not sess.needs_replay:
                    # a gated session must not leak frames around the
                    # replay: the peer's acks are cumulative, so a late
                    # frame delivered past the gate would trim the
                    # still-undelivered dropped frame from the replay
                    # buffer — silent erasure, not reordering
                    self._track(
                        asyncio.get_event_loop().create_task(
                            self._late_send(sess, addr, sess.seq,
                                            frame, fate.reorder)))
                    return
            try:
                if sess.needs_replay:
                    # a chaos drop gated this session: replay the whole
                    # unacked tail (this frame is buffered, so it rides
                    # the replay) before anything newer goes out
                    await self._reconnect_replay(sess, addr)
                    self._ack_left(owed, ack)
                    return
                conn = await self.connect(addr)
                parts = _frame_parts(conn._sign_key(), frame)
                conn.stream.write(parts)
                # only now: an ack in a frame that did not set out (a
                # chaos fate above, a peer that cannot be reached) stays
                # owed to the next frame
                self._ack_left(owed, ack)
                if fate is not None and fate.dup:
                    conn.stream.write(parts)  # duplicate delivery:
                    # handlers are idempotent by contract — prove it
                await conn.stream.drain()
                # flush boundary on the CURRENT op's timeline (sub-op
                # fan-out runs under the op context; no-op otherwise)
                mark_current("msgr:flushed")
                if fate is not None and fate.reset:
                    # injected session reset AFTER the bytes left: the
                    # peer sees a clean close; our next send reconnects
                    # and replays the unacked tail
                    self._out.pop(addr, None)
                    await conn.close()
            except (ConnectionError, OSError, RuntimeError):
                if self._closing:
                    raise
                await self._reconnect_replay(sess, addr)
                self._ack_left(owed, ack)

    async def _replay_later(self, sess: _Session, addr: Addr,
                            delay: float) -> None:
        """Chaos retransmission timer: replay the session's unacked tail
        after a dropped frame gated the session.  A failure here leaves
        the gate set — the next send retries the replay."""
        await asyncio.sleep(delay)
        if self._closing or not sess.needs_replay:
            return
        try:
            async with sess.order_lock:
                if sess.needs_replay:
                    await self._reconnect_replay(sess, addr, retries=1)
        except (ConnectionError, OSError, RuntimeError):
            pass

    async def _late_send(self, sess: _Session, addr: Addr, seq: int,
                         frame: _Frame, delay: float) -> None:
        """Chaos reorder: this frame goes out AFTER traffic that was
        sent later (ordered-delivery violation, deliberately).  A
        failure here is a DROP, and by then the cumulative ack of later
        traffic may already have trimmed the frame from the replay
        buffer — so it is re-buffered (in seq order) and the session
        gated, turning the failure into packet loss under
        retransmission rather than silent erasure."""
        await asyncio.sleep(delay)
        try:
            conn = await self.connect(addr)
            conn.stream.write(_frame_parts(conn._sign_key(), frame))
            await conn.stream.drain()
        except (ConnectionError, OSError, RuntimeError):
            if self._closing:
                return
            async with sess.order_lock:
                if seq not in sess.unacked:
                    sess.unacked[seq] = frame
                    for s in sorted(sess.unacked):
                        sess.unacked.move_to_end(s)
                sess.needs_replay = True
            self._track(
                asyncio.get_event_loop().create_task(
                    self._replay_later(sess, addr, delay)))

    def _track(self, task: asyncio.Task) -> asyncio.Task:
        from ceph_tpu.utils.tasks import track_task

        return track_task(self._tasks, task)

    async def _reconnect_replay(self, sess: _Session, addr: Addr,
                                retries: int = 3) -> None:
        """Re-open the peer connection and replay every unacked frame in
        order; raises when the peer stays unreachable."""
        if sess.overflowed:
            # frames were evicted while unacked: an in-order replay is no
            # longer possible — fail the send and reset the session so
            # future traffic starts from a clean (acked-empty) state
            sess.unacked.clear()
            sess.overflowed = False
            sess.needs_replay = False
            raise ConnectionError(
                f"session to {addr} lost unacked frames (overflow); "
                "cannot replay")
        old_conn = self._out.get(addr)
        if old_conn is not None:
            pol = self.policy_for(old_conn)
            if pol is not None and pol.lossy:
                # lossy peer policy (reference stateless client policy):
                # no replay across a reset — drop the unacked tail and
                # surface the failure so the caller re-requests
                sess.unacked.clear()
                sess.needs_replay = False
                raise ConnectionError(
                    f"lossy session to {addr} reset; not replaying")
        last: Optional[Exception] = None
        # capped exponential backoff with jitter between attempts (was:
        # immediate linear retry) — seeded via chaos_seed so scenario
        # retry timing replays with the fault schedule
        from ceph_tpu.utils.backoff import ExpBackoff

        backoff = ExpBackoff(base=0.02, cap=0.5, rng=self._backoff_rng())
        for attempt in range(retries):
            old = self._out.pop(addr, None)
            if old is not None:
                await old.close()
            try:
                conn = await self.connect(addr)
                for frame in sess.unacked.values():
                    conn.stream.write(
                        _frame_parts(conn._sign_key(), frame))
                await conn.stream.drain()
                sess.needs_replay = False
                return
            except (ConnectionError, OSError, RuntimeError) as e:
                last = e
                await asyncio.sleep(backoff.next())
        # keep the session gated while undelivered frames remain: a later
        # send must replay them BEFORE anything newer, or the peer's
        # cumulative acks could trim a frame it never saw
        sess.needs_replay = bool(sess.unacked)
        raise last or ConnectionError(f"reconnect to {addr} failed")

    def _backoff_rng(self):
        """Seeded jitter stream when the daemon carries a chaos seed
        (deterministic scenario replay); fresh entropy otherwise."""
        if self.config is not None and self.config.chaos_seed:
            from ceph_tpu.chaos.rng import stream

            return stream(self.config.chaos_seed,
                          f"backoff:{self.name}:{self.sid}")
        return None

    async def shutdown(self) -> None:
        self._closing = True
        if self.config is not None:
            # the config outlives this messenger (daemon bounces reuse
            # it): leave no observer behind to pin dead incarnations
            self.config.remove_observer(self._chaos_observer)
        if self._ack_timer is not None:
            self._ack_timer.cancel()
            self._ack_timer = None
        if self._server:
            self._server.close()
        # together, not in turn: each close is bounded (_CLOSE_WAIT_S),
        # and so is their sum
        await asyncio.gather(*(conn.close() for conn in (
            *self._out.values(), *self._hb_out.values(), *self._accepted)))
        # cancel + drain reader/handler tasks BEFORE wait_closed: since
        # py3.12 wait_closed() awaits every connection handler, and a
        # handler blocked in its read loop only exits via EOF or cancel
        pending = [t for t in self._tasks if not t.done()]
        for t in pending:
            t.cancel()
        if pending:
            # teardown drain of just-cancelled reader tasks; their
            # results are void by definition
            await asyncio.gather(*pending, return_exceptions=True)  # graftlint: ignore[swallowed-async-error]
        if self._server:
            try:
                await asyncio.wait_for(self._server.wait_closed(),
                                       _CLOSE_WAIT_S)
            except asyncio.TimeoutError:
                pass  # a handler that outlives its cancel must not
                # hold the daemon's stop
