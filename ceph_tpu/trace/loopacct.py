"""graft-trace: the loop's account.

Every daemon of a vstart cluster and its clients run on ONE asyncio
loop, and every cell of the benchmark is bound by it.  ``attribution``
says how long an op WAITS for that loop, stage by stage; this module
says what the loop DOES with its time:

    wall = busy + parked    parked: inside the selector's ``select``
    busy = on the CPU + off it    ``time.thread_time_ns()`` of the loop
                            thread, less what it burnt parked; the rest
                            is the thread standing runnable behind the
                            GIL after a syscall, or in a page fault
    and of busy, the wall inside
      send, recv            every ``sendmsg`` / ``send`` / ``recv_into``
                            on a messenger socket, deferred writes
                            (the transport's ``_write_ready``) included
      store                 ``MemStore.queue_transaction``: all of an
                            op's k+m commits, replicas' too
      codec                 ``messenger._encode`` and the unpickle of
                            ``_read_loop``
    what no stamp covers (dispatch, PG and log work, asyncio's own turn)
    is busy minus those four, by subtraction.

How.  ``install(loop)`` (``vstart.start_cluster`` calls it) puts a
``_TimedSelector`` in place of the loop's own selector: a proxy whose
``select`` reads the wall clock on its way in and out, two reads a turn.
The messenger gives the loop sockets of the account's own kind
(``LoopAccount.listen`` / ``connect``: a ``socket.socket`` that is a
``TimedSocket``, its three data calls timed, in a timed turn and a
``BareSocket`` in the others, and whose ``accept`` returns its own
kind), so nothing of asyncio is touched and both lanes are covered.
The other stamps ask ``ACCOUNT``, the account of the process's loop, as
the tick's phases ask ``tick._CURRENT``, whether this turn is timed.

Which loops.  A vstart cluster on asyncio's selector loop, which is what
Linux and macOS give ``asyncio.run``: every product run and the whole
benchmark.  ``install`` leans on three private names, checked on
CPython 3.12, the installation's:
``BaseSelectorEventLoop._selector`` and ``_ready``, and
``socket.socket._accept``.  A loop that lacks the first two (a proactor,
uvloop), and a messenger bound with no cluster around it (unit tests),
get no account: the messenger then takes asyncio's own sockets, nothing
is counted, and every metric whose denominator is the account's reads
nothing.

What it costs.  One turn of the loop in ``_EVERY`` is timed, at random
strides, and what it gathers is booked ``_EVERY`` times; a bare turn
pays two clock reads at the selector and an attribute test a frame and
a transaction (``_EVERY``: why).  What has gathered goes into ``KERNELS`` under ONE
take of its lock (``inc_many``) when a ``select`` returns and
``_FOLD_NS`` have passed since the last time, so a reader of ``KERNELS``
sees the account as of at most that long ago (and a turn).  The thread's CPU clock is
a real syscall (6.2 us on the chip host): it is read at a fold, and
around a ``select`` that may sleep; a saturated loop only polls
(``select(0)``: all 15576 turns of a 64 KiB window, PR 40's chip call 2)
and pays one read a fold.  Always on, as the tick record is; no option.

The counters (``declare_counters``) and who reads them: PERF.md §3.
"""

from __future__ import annotations

import functools
import random
import socket
import threading
import time
import weakref
from typing import Dict, Optional, Tuple

from ceph_tpu.utils.perf import KERNELS, PerfCounters

_clock = time.perf_counter_ns
_cpu = time.thread_time_ns

# how stale the account in KERNELS may be: a window of the benchmark is
# 51 s, so 100 ms at an edge is 0.2%.  A fold is one thread_time_ns (6 us
# on the chip host, a real syscall there), one lock and sixteen dict
# adds: ~10 us idle, and Python on the loop thread costs 15 to 40 times
# its idle price there while tick threads want the GIL (PR 40's chip
# calls 4-5), so a fold every turn of a k8m4 window (18 ms a turn) was
# priced at 1-2% of the loop
_FOLD_NS = 100_000_000

# one turn of the loop in _EVERY is a timed one: every socket call, frame
# and store transaction inside it is timed and counted, and booked
# _EVERY times; in the other turns those calls run bare.  Why turns and
# not calls: what costs on the chip host is not the clock (0.1 us) but a
# Python frame around a call, once tick threads want the GIL: ~10 us a
# wrapped socket call there (PR 40's chip call 4: timing one CALL in 16,
# with every call counted in a wrapper, cost k8m4_write_4m_t16 the same
# 5-7% as timing every call had).  So the bare turns must run the
# sockets' own C methods and nothing around a pickle or a transaction
# but one attribute test.  An op's work falls into turns in no fixed
# order, but what is periodic in the traffic is periodic in turns too:
# the distance to the next timed turn is drawn at random from 1 to
# 2 * _EVERY - 1, so its mean is _EVERY and a turn is timed with
# probability 1/_EVERY whatever the period (a table of strides, however
# shuffled, has a period of its own: its sum).  A 51 s window has 2800
# (k8m4) to 15000 (64 KiB) turns: 175 to 1000 timed ones
_EVERY = 16

_COUNTERS = (
    ("loop_wall_ns", "ns", "wall time of the loop thread since the "
     "account was installed: busy + parked in select"),
    ("loop_busy_ns", "ns", "loop thread wall time NOT parked in the "
     "selector's select"),
    ("loop_busy_cpu_ns", "ns", "thread CPU time of the loop thread over "
     "its busy intervals: read at the account's folds, less what the "
     "selects that could sleep burnt (read at their edges); a poll's own "
     "entry and exit stay in"),
    ("loop_busy_offcpu_ns", "ns", "busy and not running: loop_busy_ns - "
     "loop_busy_cpu_ns (waiting for the GIL after a syscall, page "
     "faults, preemption); on a host whose thread clock ticks coarsely "
     "a single fold may book less than nothing, a window does not"),
    ("loop_sock_ns", "ns", "loop_sock_send_ns + loop_sock_recv_ns"),
    ("loop_sock_send_ns", "ns", "wall inside sendmsg / send on messenger "
     "sockets, deferred writes included (the timed turns' x 16)"),
    ("loop_sock_recv_ns", "ns", "wall inside recv_into on messenger "
     "sockets (the timed turns' x 16)"),
    ("loop_sock_send_bytes", "bytes", "bytes those sends took (x 16)"),
    ("loop_sock_recv_bytes", "bytes", "bytes those reads brought (x 16)"),
    ("loop_sock_send_calls", "calls", "sendmsg / send calls, one that "
     "would block included (x 16)"),
    ("loop_sock_recv_calls", "calls", "recv_into calls (x 16)"),
    ("loop_store_ns", "ns", "wall inside MemStore.queue_transaction on "
     "the loop thread: every shard commit, replicas' too (the timed "
     "turns' x 16)"),
    ("loop_store_calls", "calls", "those queue_transaction calls (x 16)"),
    ("loop_codec_ns", "ns", "wall inside messenger._encode and the "
     "unpickle of a received frame (the timed turns' x 16)"),
    ("loop_turns", "turns", "turns of the loop (select calls)"),
    ("loop_callbacks", "handles", "handles ready when a turn's select was "
     "entered + I/O events it returned (timer handles not counted)"),
)


def declare_counters(counters: PerfCounters) -> None:
    """The account's schema."""
    for name, unit, desc in _COUNTERS:
        counters.add_u64(name, unit=unit, desc=f"loop account: {desc}")


_sendmsg = socket.socket.sendmsg
_send = socket.socket.send
_recv_into = socket.socket.recv_into


class BareSocket(socket.socket):
    """A messenger socket of an accounted loop between timed turns: the
    data calls are ``socket.socket``'s own.  ``LoopAccount.set_timing``
    makes it a ``TimedSocket`` and back (``__class__``: same layout)."""

    __slots__ = ("acct",)

    def accept(self):
        """``socket.accept`` with the connection on this account."""
        fd, addr = self._accept()
        conn = self.acct.adopt(self.family, self.type, self.proto, fd)
        if socket.getdefaulttimeout() is None and self.gettimeout():
            conn.setblocking(True)
        return conn, addr


class TimedSocket(BareSocket):
    """The same socket in a timed turn: the wall and bytes of its data
    calls go to the account.  A call that would block raises as it does
    on any socket and is booked all the same: the loop spent that time."""

    __slots__ = ()

    def sendmsg(self, buffers, *args):
        a = self.acct
        n = 0
        t0 = _clock()
        try:
            n = _sendmsg(self, buffers, *args)
            return n
        finally:
            a.send_ns += _clock() - t0
            a.send_bytes += n
            a.send_calls += 1

    def send(self, data, *args):
        a = self.acct
        n = 0
        t0 = _clock()
        try:
            n = _send(self, data, *args)
            return n
        finally:
            a.send_ns += _clock() - t0
            a.send_bytes += n
            a.send_calls += 1

    def recv_into(self, buffer, *args):
        a = self.acct
        n = 0
        t0 = _clock()
        try:
            n = _recv_into(self, buffer, *args)
            return n
        finally:
            a.recv_ns += _clock() - t0
            a.recv_bytes += n
            a.recv_calls += 1


class _TimedSelector:
    """The loop's selector with ``select`` timed.  Everything else
    (``register``, ``modify``, ``get_key``, ``close`` ...) is the
    selector's own bound method, kept here at its first use."""

    def __init__(self, selector, acct: "LoopAccount", ready):
        self._selector = selector
        self._select = selector.select
        self._acct = acct
        self._ready = ready

    def __getattr__(self, name):
        attr = getattr(self._selector, name)
        setattr(self, name, attr)
        return attr

    def select(self, timeout=None):
        a = self._acct
        t0 = _clock()
        a.busy_ns += t0 - a._edge
        a.callbacks += len(self._ready)
        if timeout == 0:
            # handles are ready (a saturated loop's every turn): a poll,
            # whose own entry and exit are all the CPU it burns
            events = self._select(timeout)
        else:
            # the loop may sleep, so it has the time for two reads of
            # the thread's CPU clock: what waiting burnt is not its work
            cpu = _cpu()
            events = self._select(timeout)
            a.parked_cpu_ns += _cpu() - cpu
        a._edge = t1 = _clock()
        a.parked_ns += t1 - t0
        a.callbacks += len(events)
        a.turns += 1
        a._turn_left -= 1
        if not a._turn_left or a.timing:
            a.turn()
        if t1 >= a._fold_at:
            a.fold()
        return events


class LoopAccount:
    """What one loop thread spent its time on since ``install``: plain
    integers, added to on that thread alone, folded into ``counters``.
    ``timing`` says whether the turn that runs is a timed one: the
    stamps outside this module (``messenger._encode``, ``_read_loop``,
    ``MemStore.queue_transaction``) test it and do nothing else in a
    bare turn."""

    __slots__ = ("loop", "thread", "counters", "timing", "busy_ns",
                 "parked_ns", "parked_cpu_ns", "send_ns", "send_bytes",
                 "send_calls", "recv_ns", "recv_bytes", "recv_calls",
                 "store_ns", "store_calls", "codec_ns", "turns",
                 "callbacks", "_socks", "_stride", "_every", "_turn_left",
                 "_edge", "_cpu_at", "_fold_at")

    def __init__(self, loop, counters: PerfCounters = KERNELS):
        self.loop = loop
        self.thread = threading.get_ident()
        self.counters = counters
        self.timing = False
        self._zero()
        self._socks: "weakref.WeakSet[BareSocket]" = weakref.WeakSet()
        self._every = _EVERY
        self._stride = functools.partial(
            random.Random(40).randrange, 1, 2 * _EVERY)
        self._turn_left = self._stride()
        self._edge = _clock()
        self._cpu_at = _cpu()
        self._fold_at = self._edge + _FOLD_NS
        declare_counters(counters)

    def turn(self) -> None:
        """A turn of the loop begins (its ``select`` returned) whose
        number came up, or that follows a timed one: a timed one in the
        first case, a bare one else."""
        if not self._turn_left:
            self._turn_left = self._stride()
            if not self.timing:
                self.set_timing(True)
        elif self.timing:
            self.set_timing(False)

    def set_timing(self, on: bool) -> None:
        self.timing = on
        kind = TimedSocket if on else BareSocket
        for sock in self._socks:
            sock.__class__ = kind

    def adopt(self, family, type_, proto, fileno=None) -> BareSocket:
        """A socket of the account's kind (of the kind of the turn that
        runs), from now on switched with the others."""
        kind = TimedSocket if self.timing else BareSocket
        sock = kind(family, type_, proto, fileno=fileno)
        sock.acct = self
        self._socks.add(sock)
        return sock

    def codec_done(self, t0: int) -> None:
        """A frame was pickled or unpickled since ``t0``."""
        self.codec_ns += _clock() - t0

    def store_done(self, t0: int) -> None:
        """A store transaction ran on the loop thread since ``t0``."""
        self.store_ns += _clock() - t0
        self.store_calls += 1

    def _zero(self) -> None:
        self.busy_ns = self.parked_ns = self.parked_cpu_ns = 0
        self.send_ns = self.send_bytes = self.send_calls = 0
        self.recv_ns = self.recv_bytes = self.recv_calls = 0
        self.store_ns = self.store_calls = self.codec_ns = 0
        self.turns = self.callbacks = 0

    def fold(self) -> None:
        """What has gathered up to the last edge of ``select`` goes into
        the counters, under one take of their lock.  On the loop thread,
        whose CPU clock it reads."""
        cpu = _cpu()
        busy_cpu = cpu - self._cpu_at - self.parked_cpu_ns
        every = self._every
        grown: Dict[str, int] = {
            "loop_wall_ns": self.busy_ns + self.parked_ns,
            "loop_busy_ns": self.busy_ns,
            "loop_busy_cpu_ns": busy_cpu,
            "loop_busy_offcpu_ns": self.busy_ns - busy_cpu,
            "loop_sock_ns": (self.send_ns + self.recv_ns) * every,
            "loop_sock_send_ns": self.send_ns * every,
            "loop_sock_recv_ns": self.recv_ns * every,
            "loop_sock_send_bytes": self.send_bytes * every,
            "loop_sock_recv_bytes": self.recv_bytes * every,
            "loop_sock_send_calls": self.send_calls * every,
            "loop_sock_recv_calls": self.recv_calls * every,
            "loop_store_ns": self.store_ns * every,
            "loop_store_calls": self.store_calls * every,
            "loop_codec_ns": self.codec_ns * every,
            "loop_turns": self.turns,
            "loop_callbacks": self.callbacks,
        }
        self._cpu_at = cpu
        self._zero()
        self._fold_at = self._edge + _FOLD_NS
        self.counters.inc_many(grown)

    # -- sockets of the account's kind, for the messenger -------------------

    def _socket(self, host: str) -> BareSocket:
        # proto as getaddrinfo gives it to asyncio's own sockets: the
        # transport sets TCP_NODELAY only on a socket that says it is TCP
        sock = self.adopt(
            socket.AF_INET6 if ":" in host else socket.AF_INET,
            socket.SOCK_STREAM, socket.IPPROTO_TCP)
        sock.setblocking(False)
        return sock

    def listen(self, host: str, port: int) -> BareSocket:
        """A bound socket for ``loop.create_server(sock=...)``, set up as
        ``create_server(host, port)`` sets its own up."""
        sock = self._socket(host)
        try:
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, True)
            if sock.family == socket.AF_INET6:
                sock.setsockopt(socket.IPPROTO_IPV6, socket.IPV6_V6ONLY, True)
            sock.bind((host, port))
        except BaseException:
            sock.close()
            raise
        return sock

    async def connect(self, addr: Tuple[str, int]) -> BareSocket:
        """A connected socket for ``loop.create_connection(sock=...)``;
        raises what ``create_connection(host, port)`` would."""
        sock = self._socket(addr[0])
        try:
            await self.loop.sock_connect(sock, tuple(addr))
        except BaseException:
            sock.close()
            raise
        return sock


# the account of the process's loop (a process runs one: every daemon
# and client of a vstart cluster); None until one was installed
ACCOUNT: Optional[LoopAccount] = None


def install(loop) -> Optional[LoopAccount]:
    """Give ``loop`` (the running one, on its own thread) an account and
    time its selector; the account it already has if it has one; None,
    and nothing installed, where the loop has no selector to time."""
    global ACCOUNT
    if ACCOUNT is not None and ACCOUNT.loop is loop:
        return ACCOUNT
    selector = getattr(loop, "_selector", None)
    ready = getattr(loop, "_ready", None)
    if ready is None or not callable(getattr(selector, "select", None)):
        return None
    ACCOUNT = acct = LoopAccount(loop)
    loop._selector = _TimedSelector(selector, acct, ready)
    return acct


def of(loop) -> Optional[LoopAccount]:
    """``loop``'s account, if it has one."""
    acct = ACCOUNT
    return acct if acct is not None and acct.loop is loop else None
