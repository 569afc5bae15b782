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
    and what no stamp covers, by WHO RAN: asyncio runs a turn's
    handles one after another out of ``loop._ready``, so the moments it
    takes them are a complete, exclusive partition of the turn.  A
    handle's OWN time is the wall from its ``popleft`` to the next (the
    last one ends where ``select`` is entered) less what the four
    stamps booked inside it, and goes to one bucket:
      transport             not a task's step: asyncio's selector
                            transport (``_read_ready``, ``_write_ready``)
                            and the ``_FrameStream`` calls they make
      msgr                  a step of an OSD messenger's ``_read_loop``:
                            verify, ``_owe``, throttle and all that
                            ``ms_dispatch`` runs inline
      osd_op                a step of a task rooted in ``sharded_wq.py``
                            or spawned by the OSD for an op
      tick                  a step of one of ``batcher.py``'s drains
      client                a step of a task rooted outside the daemons
                            (the benchmark's callers), in the client's
                            modules, or of a client messenger's read loop
      other                 anything else: future plumbing, heartbeats,
                            mon and mgr tasks, timers
      turn                  the timed busy wall outside any handle:
                            asyncio's ``_run_once`` itself and the
                            account's own work at the turn's edge
    so that in the timed turns, exactly,
    sum of the seven + send + recv + store + codec = busy.

How.  ``install(loop)`` (``vstart.start_cluster`` calls it) puts a
``_TimedSelector`` in place of the loop's own selector: a proxy whose
``select`` reads the wall clock on its way in and out, two reads a turn.
The messenger gives the loop sockets of the account's own kind
(``LoopAccount.listen`` / ``connect``: a ``socket.socket`` that is a
``TimedSocket``, its three data calls timed, in a timed turn and a
``BareSocket`` in the others, and whose ``accept`` returns its own
kind), so both lanes are covered.  ``loop._ready`` becomes a ``deque``
of the account's (``BareReady``: no method of its own, so a bare turn
calls ``collections.deque.popleft`` itself; ``TimedReady`` in a
timed turn, whose ``popleft`` closes the running span and opens the
next).
Who a task is: ``tag(task, bucket)`` where it is made (the messenger's
read loops, by their owner's entity type), else what the module that
owns its root coroutine said of it at import (``@root(bucket)`` on the
coroutine or on its class: by code object), kept on the task (an
``asyncio.Task`` takes attributes); a plain callback by its function.
Beside the buckets the account keeps, for timed turns, the table
``rows``: (bucket, root or callback, message class) -> handles, wall,
own; ``cut`` in ``_read_loop`` closes the running span and opens one
under the frame's message class, so a step that takes three frames
books three rows (admin command ``dump_loop_account``).
The other stamps ask ``ACCOUNT``, the account of the process's loop, as
the tick's phases ask ``tick._CURRENT``, whether this turn is timed.

Which loops.  A vstart cluster on asyncio's selector loop, which is what
Linux and macOS give ``asyncio.run``: every product run and the whole
benchmark.  ``install`` leans on private names, checked on CPython
3.12.12, the installation's: ``BaseSelectorEventLoop._selector``;
``_ready`` as a ``collections.deque`` that ``_run_once`` looks up anew
for every ``popleft`` and that may be replaced; ``Handle._callback``,
whose ``__self__`` is the ``asyncio.Task`` for a task's step and wakeup
alike; and ``socket.socket._accept``.  A loop that lacks the first two
(a proactor, uvloop), and a messenger bound with no cluster around it
(unit tests), get no account: the messenger then takes asyncio's own
sockets, nothing is counted, and every metric whose denominator is the
account's reads nothing.  A loop whose ``_ready`` is not a plain
``deque`` gets the account without handle spans: the seven read 0.0.

What it costs.  One turn of the loop in ``_EVERY`` is timed, at random
strides, and what it gathers is booked ``_EVERY`` times; a bare turn
pays two clock reads at the selector and an attribute test a frame and
a transaction (``_EVERY``: why); a timed turn pays besides, per handle,
two Python frames, a clock read, a few attribute lookups and a dozen
adds (PERF.md §6, PR 41: what that costs on the chip host).  That work
lies INSIDE the spans it measures: a timed turn is longer than a bare
one by it, so the seven are biased high, by at most ``floor_ns``
(``table``) a handle; on the chip host ``loop_timed_busy_ns`` read
0.97-1.12 of ``loop_busy_ns`` a window, 1.07-1.12 on eleven windows
of sixteen (PERF.md §5).  What has gathered goes
into ``KERNELS`` under ONE take of its lock (``inc_many``) when a
``select`` returns and ``_FOLD_NS`` have passed since the last time, so
a reader of ``KERNELS`` sees the account as of at most that long ago
(and a turn).  The thread's CPU clock is a real syscall (6.2 us on the chip host): it is read at a fold, and
around a ``select`` that may sleep; a saturated loop only polls
(``select(0)``: all 15576 turns of a 64 KiB window, PR 40's chip call 2)
and pays one read a fold.  Always on, as the tick record is; no option.

The counters (``declare_counters``) and who reads them: PERF.md §3.
"""

from __future__ import annotations

import asyncio
import collections
import functools
import inspect
import os
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
    *((f"loop_own_{bucket}_ns", "ns", f"own time of the handles that "
       f"{who}: their wall from one popleft of loop._ready to the next, "
       f"less what the send, recv, store and codec stamps booked inside "
       f"it (the timed turns' x 16; the account's own work a handle "
       f"lies inside: reads high by up to the table's floor_ns a handle)")
      for bucket, who in (
        ("transport", "are no task's step and run asyncio's selector "
         "transport or the messenger's _FrameStream"),
        ("msgr", "step an OSD messenger's read loop (inline dispatch "
         "included)"),
        ("osd_op", "step a task the OSD made for an op (the sharded queue's "
         "drains, the serving of an admitted op, the fan-out's sends)"),
        ("tick", "step one of batcher.py's drains"),
        ("client", "step a task rooted outside the daemons, in the "
         "client's modules or at a client messenger's read loop"),
        ("other", "are none of those (future plumbing, heartbeats, mon "
         "and mgr tasks, timers)"))),
    ("loop_own_turn_ns", "ns", "timed busy wall outside any handle: "
     "asyncio's _run_once (events, timer heap) and the account's own work "
     "at the turn's edge (the timed turns' x 16)"),
    ("loop_timed_busy_ns", "ns", "busy wall of the timed turns of a loop "
     "whose handles are spanned, x 16: exactly the seven loop_own_*_ns + "
     "loop_sock_send_ns + loop_sock_recv_ns + loop_store_ns + "
     "loop_codec_ns; biased high against loop_busy_ns by what the "
     "account itself runs in a timed turn (0.97-1.12 of it a window on "
     "the chip host, mostly 1.07-1.12)"),
    ("loop_handles", "handles", "handles taken off loop._ready in the "
     "timed turns, timers' included (x 16)"),
)

# who ran: the six buckets a handle's own time goes to and, last, the
# turn's, which takes what lies between handles
BUCKETS = ("transport", "msgr", "osd_op", "tick", "client", "other", "turn")

# a task nobody tagged, by its root coroutine's code object: what the
# module that owns the coroutine said of it at import (``root``)
_ROOT_BUCKETS: Dict[object, str] = {}
_PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__))) + os.sep
_ASYNCIO = os.path.dirname(os.path.abspath(asyncio.__file__)) + os.sep

# a callback of these classes is the transport's
_TRANSPORT = ("_SelectorTransport.", "_SelectorSocketTransport.",
              "_FrameStream.")


def root(bucket: str):
    """Decorator: a task whose root is this coroutine function is
    ``bucket``'s; on a class, every coroutine function it defines that
    has not said otherwise itself.  Said once, at import, by the module
    that owns the name; nothing runs for it in any turn."""
    assert bucket in BUCKETS, bucket

    def register(what):
        if isinstance(what, type):
            for fn in vars(what).values():
                if inspect.iscoroutinefunction(fn):
                    _ROOT_BUCKETS.setdefault(fn.__code__, bucket)
        else:
            _ROOT_BUCKETS[what.__code__] = bucket
        return what
    return register


def tag(task: "asyncio.Task", bucket: str) -> None:
    """``task``'s handles are ``bucket``'s, whatever its root says (from
    its next one on, where it has run already): for a task whose root
    cannot say whose it is (``Messenger._read_loop`` is an OSD's, a
    client's, a mon's), where it is made."""
    task.loopacct_bucket = bucket
    task.loopacct_row = None


def bucket_of_root(code) -> str:
    """The bucket of an untagged task whose root coroutine has ``code``:
    what its module registered; else "other" for this package's code
    and asyncio's own, and "client" for a root outside both: a caller
    of the cluster (benchmark, scripts, tests)."""
    bucket = _ROOT_BUCKETS.get(code)
    if bucket is None:
        path = code.co_filename
        bucket = "other" if path.startswith((_PKG, _ASYNCIO)) else "client"
    return bucket


class _Row:
    """One row of the account's table: the handles of one (bucket, root
    coroutine or callback, message class), in timed turns, unscaled.
    ``task`` says which of the two ``name`` is."""

    __slots__ = ("acct", "bucket", "name", "msg", "task", "handles",
                 "wall_ns", "own_ns")

    def __init__(self, acct: "LoopAccount", bucket: str, name: str,
                 msg: str, task: bool):
        self.acct = acct
        self.bucket = bucket
        self.name = name
        self.msg = msg
        self.task = task
        self.handles = self.wall_ns = self.own_ns = 0


def table(every: int, rows) -> dict:
    """``rows`` (dicts as ``LoopAccount.dump`` makes them) heaviest
    first, with their own time summed by bucket.  A row's own time holds
    the ``popleft`` that opened it: ``floor_ns``, the smallest mean own
    time of a row of at least 100 handles, is the most the observer can
    have added to any handle."""
    rows = sorted((r for r in rows if r["handles"]),
                  key=lambda r: -r["own_ns"])
    own = dict.fromkeys(BUCKETS, 0)
    for r in rows:
        own[r["bucket"]] += r["own_ns"]
    means = [r["own_ns"] // r["handles"] for r in rows
             if r["handles"] >= 100 and r["bucket"] != "turn"]
    return {"every": every, "own_ns": own,
            "timed_busy_ns": sum(r["wall_ns"] for r in rows),
            "floor_ns": min(means) if means else None, "rows": rows}


def window(after: dict, before: dict) -> dict:
    """What the table grew by between two dumps, as a dump."""
    was = {(r["bucket"], r["name"], r["msg"]): r for r in before["rows"]}
    rows = []
    for r in after["rows"]:
        b = was.get((r["bucket"], r["name"], r["msg"]))
        rows.append({**r, **{f: r[f] - b[f] for f in (
            "handles", "wall_ns", "own_ns")}} if b else r)
    return table(after["every"], rows)


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


class BareReady(collections.deque):
    """``loop._ready`` of an accounted loop between timed turns: no
    method of its own, so ``_run_once`` calls ``collections.deque``'s C
    ``popleft``.  ``LoopAccount.set_timing`` makes it a ``TimedReady``
    and back (``__class__``: same layout), with the sockets."""

    __slots__ = ("acct",)


class TimedReady(BareReady):
    """The same deque in a timed turn: taking a handle off it closes
    the span of the handle before and opens this one's."""

    __slots__ = ()

    def popleft(self):
        handle = _popleft(self)
        a = self.acct
        callback = handle._callback
        if callback.__class__ is functools.partial:
            callback = callback.func
        owner = getattr(callback, "__self__", None)
        if isinstance(owner, asyncio.Task):
            # a task keeps the row its steps begin under (and its tag)
            # as attributes of its own: they go when it goes, and a
            # lookup is no Python frame
            row = getattr(owner, "loopacct_row", None)
            if row is None or row.acct is not a:
                row = a._task_row(owner)
        else:
            fn = getattr(callback, "__func__", callback)
            key = getattr(fn, "__code__", None) or \
                getattr(fn, "__qualname__", None) or type(fn).__qualname__
            row = a._callback_rows.get(key)
            if row is None:
                row = a._callback_row(key, fn)
        a.handles += 1
        row.handles += 1
        a._mark(row)
        return handle


_popleft = collections.deque.popleft


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
        if a._spanning:
            # the turn's last handle ends here; what follows is parked
            a._mark(a._turn)
            t0 = a._at
        else:
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
                 "callbacks", "timed_busy_ns", "handles", "own", "rows",
                 "_callback_rows", "_ready", "_spanning",
                 "_turn", "_row", "_at", "_inside", "_socks", "_stride",
                 "_every", "_turn_left", "_edge", "_cpu_at", "_fold_at")

    def __init__(self, loop, counters: PerfCounters = KERNELS):
        self.loop = loop
        self.thread = threading.get_ident()
        self.counters = counters
        self.timing = False
        self._zero()
        # the table behind the buckets, kept for the account's life:
        # (bucket, root or callback, message class) -> its row, and the
        # rows that callbacks already seen begin under (a task keeps
        # its own)
        self.rows: Dict[Tuple[str, str, str], _Row] = {}
        self._callback_rows: Dict[object, _Row] = {}
        # the loop's deque of ready handles, once ``install`` made it the
        # account's, and whether this turn's handles are spanned (a
        # timed turn of a loop that has such a deque); the row of the
        # span that runs (between handles the turn's own), where it
        # began and what the four stamps read there
        self._ready: Optional[BareReady] = None
        self._spanning = False
        self._turn = self._row = self._row_of("turn", "_run_once", False)
        self._at = 0
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
            if self._spanning:
                # the turn's wall begins here, not where its select
                # returned: the switches of the sockets' and the deque's
                # classes are paid twice in ``_EVERY`` turns and would
                # be booked for every one
                self._turn.handles += 1
                self._row = self._turn
                self._at = _clock()
                self._inside = self.send_ns + self.recv_ns \
                    + self.store_ns + self.codec_ns
        elif self.timing:
            self.set_timing(False)

    def set_timing(self, on: bool) -> None:
        self.timing = on
        kind = TimedSocket if on else BareSocket
        for sock in self._socks:
            sock.__class__ = kind
        if self._ready is not None:
            self._spanning = on
            self._ready.__class__ = TimedReady if on else BareReady

    # -- who ran: the spans of a timed turn's handles -----------------------

    def _mark(self, row: _Row) -> None:
        """The span that runs ends here, and one of ``row``'s begins
        (``_turn``: what follows is no handle's).  Its wall goes to the
        timed busy time, and its own time (the wall less what the four
        stamps booked since it began) to its row and bucket."""
        now = _clock()
        inside = self.send_ns + self.recv_ns + self.store_ns + self.codec_ns
        wall = now - self._at
        own = wall - (inside - self._inside)
        self.timed_busy_ns += wall
        was = self._row
        was.wall_ns += wall
        was.own_ns += own
        self.own[was.bucket] += own
        self._row = row
        self._at = now
        self._inside = inside

    def cut(self, msg: str) -> None:
        """The handle that runs took a frame that carries a ``msg``:
        what it does from here on is booked under that message class,
        one row a frame."""
        was = self._row
        if was is not self._turn:
            row = self._row_of(was.bucket, was.name, was.task, msg)
            row.handles += 1
            self._mark(row)

    def _row_of(self, bucket: str, name: str, task: bool,
                msg: str = "") -> _Row:
        key = (bucket, name, msg)
        row = self.rows.get(key)
        if row is None:
            row = self.rows[key] = _Row(self, bucket, name, msg, task)
        return row

    def _task_row(self, task: "asyncio.Task") -> _Row:
        """The row a task's steps begin under, from now on found on the
        task: its tag's bucket, or its root's."""
        coro = task.get_coro()
        code = getattr(coro, "cr_code", None) or getattr(coro, "gi_code",
                                                         None)
        bucket = getattr(task, "loopacct_bucket", None) or (
            bucket_of_root(code) if code is not None else "other")
        name = code.co_qualname if code is not None \
            else type(coro).__qualname__
        row = task.loopacct_row = self._row_of(bucket, name, True)
        return row

    def _callback_row(self, key, fn) -> _Row:
        """The row of a handle that steps no task: asyncio's selector
        transports' and the messenger's ``_FrameStream``'s are the
        transport, whatever else is "other" (the loop's own
        ``_read_from_self``, by which another thread wakes it, too)."""
        name = getattr(fn, "__qualname__", None) or type(fn).__qualname__
        bucket = "transport" if name.startswith(_TRANSPORT) else "other"
        row = self._callback_rows[key] = self._row_of(bucket, name, False)
        return row

    def dump(self) -> dict:
        """The table, for the operator: what the handles of the timed
        turns cost since the account was made, unscaled (one turn in
        ``every`` was timed), with the sums by bucket beside it
        (``table``).  What it grew by between two moments:
        ``window(after, before)``."""
        return table(self._every, [
            {"bucket": r.bucket, "name": r.name, "msg": r.msg,
             "task": r.task, "handles": r.handles, "wall_ns": r.wall_ns,
             "own_ns": r.own_ns} for r in self.rows.values()])

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
        self.timed_busy_ns = self.handles = 0
        # own ns by bucket, and the four stamps' sum where the span that
        # runs began (they are zero again)
        self.own: Dict[str, int] = dict.fromkeys(BUCKETS, 0)
        self._inside = 0

    def fold(self) -> None:
        """What has gathered goes into the counters, under one take of
        their lock.  On the loop thread, whose CPU clock it reads."""
        cpu = _cpu()
        # the busy time and the span that runs are booked up to here
        # and go on, so that what a fold takes adds up, and the CPU
        # time lies inside the busy time, wherever it is called (the
        # account's own fold is at a select's edge; a test's is not)
        if self._spanning:
            self._mark(self._row)
            now = self._at
        else:
            now = _clock()
        self.busy_ns += now - self._edge
        self._edge = now
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
            "loop_timed_busy_ns": self.timed_busy_ns * every,
            "loop_handles": self.handles * every,
        }
        for bucket, own in self.own.items():
            grown[f"loop_own_{bucket}_ns"] = own * every
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
    if type(ready) is collections.deque:
        # the loop's own handles move over; ``_run_once`` looks
        # ``_ready`` up for every handle it takes, and so does
        # ``call_soon_threadsafe`` (no thread calls it this early)
        acct._ready = BareReady()
        acct._ready.acct = acct
        loop._ready = acct._ready
        while ready:
            acct._ready.append(ready.popleft())
        ready = acct._ready
    loop._selector = _TimedSelector(selector, acct, ready)
    return acct


def of(loop) -> Optional[LoopAccount]:
    """``loop``'s account, if it has one."""
    acct = ACCOUNT
    return acct if acct is not None and acct.loop is loop else None
