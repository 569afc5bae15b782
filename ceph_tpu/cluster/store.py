"""ObjectStore + Transaction, with a MemStore implementation.

Mirrors the reference's storage contract (src/os/ObjectStore.h:1470-1498):
every mutation is an ordered, atomic Transaction of typed ops applied to
collections of objects (data + xattrs + omap), and MemStore
(src/os/memstore/MemStore.cc) is the in-RAM implementation backing tests
and the dev cluster.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import mmap
import os
import pickle
import queue
import shutil
import subprocess
import tempfile
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

from ceph_tpu.cluster.optracker import mark_current
from ceph_tpu.ec import planar_store
from ceph_tpu.trace import loopacct
from ceph_tpu.utils import compile_cache
from ceph_tpu.utils.perf import KERNELS


# Where ``MemStore`` lands a full shard of at least ``_MAP_MIN`` bytes
# (PR 43): in a mapping of its own, mapped ``MAP_POPULATE``.  What a store
# retains is new memory, and every page of it is touched for the first
# time on the loop thread: a fault a page (~1.0-1.2 ms a MiB on the chip
# host) where malloc hands it out, 0.73-0.80 ms a MiB where the kernel
# populates a mapping in bulk (PERF.md section 5, "First touches").  One
# mapping a shard: it goes when its object goes, so nothing is stranded
# and the store's memory is what ``statfs`` says; malloc maps a block of
# this size by itself too (``M_MMAP_THRESHOLD``), one fault a page.  A
# smaller shard is a ``bytearray`` as before.
_MAP_MIN = 256 << 10

# The spares (PR 45).  Nothing in a mapping's first touch depends on the
# shard that will land in it, so ONE thread a process maps and populates
# mappings ahead of the writes, off the loop thread, and ``_land`` takes a
# ready one of the blob's exact length and is left with the copy; it
# maps inline, as above, when none is ready.  ``_POOL_MAX`` bounds the
# spare bytes (mapped, populated, not yet an object's) of the whole
# process: ten k2m1 ops' worth of shards, more than one tick of eight
# objects commits at once.  ``statfs`` is logical and does not count
# them.  ``_POOL_PIECE`` is how much the kernel is asked to populate in
# one call: everybody who maps or faults waits that long for the address
# space.
#
# Who walks a spare's pieces (PR 51): ``populate_pieces`` of
# ``native/store_pool/populate_pieces.c``, ONE foreign call a spare, so
# that the thread lets the GIL go and asks for it back once a shard and
# not once a piece (beside a saturated loop, a tick thread an OSD and two
# senders each return cost it ~0.3 ms, and the one thread stopped keeping
# up: PERF.md section 5, "First touches off the loop").  No binary is
# committed: the refill thread, when it starts, builds the source with
# the host's ``cc`` into ``compile_cache.native_dir()`` (in the checkout,
# beside ``.jax_cache``; named by the source's hash, so built once a
# checkout and loaded from then on) and, where there is no compiler or
# the build or the load fails, walks the pieces in Python as before and
# says so in the log when it starts.  ``store_pool_calls`` over
# ``store_pool_spares`` (``KERNELS``) says which walk served: at most 2
# foreign calls a spare with the C walk, 1 + a call a piece without.
#
# ``_POOL_WALK_MAX`` is the longest spare the C walk takes; a longer one
# (k2m1's 2 MiB shards) is walked in Python as since PR 45.  The length
# stands for what the store cannot see, whether the loop or the ticks set
# a pool's pace.  Where the loop does (every pool measured with shards of
# 1 MiB and less, three OSDs or twelve) the C walk bought 2-17% of rate
# and no tail.  Where the ticks do (both pools measured with 2 MiB
# shards) a faster loop moves the queue to the coalescer: k2m1's rate
# rose 9% and its p95 22% in ten same-seed pairs, half of each with the
# walk cut to 1 MiB a call, and a sleep or a ``sched_yield`` between the
# pieces bought nothing back.  The line goes when a tick no longer
# allocates its buffers afresh (ROADMAP A2 (a); PERF.md section 6,
# PR 51).
_POOL_MAX = 64 << 20
_POOL_PIECE = 256 << 10
_POOL_WALK_MAX = 1 << 20
_MAP_FIXED = 0x10                       # Linux, every architecture
_NATIVE_SRC = os.path.join(compile_cache.CHECKOUT, "native", "store_pool",
                           "populate_pieces.c")

_libc_mmap = ctypes.CDLL(None, use_errno=True).mmap
_libc_mmap.restype = ctypes.c_void_p
_libc_mmap.argtypes = [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int,
                       ctypes.c_int, ctypes.c_int, ctypes.c_long]


def _native_walk():
    """``populate_pieces(base, n, piece)`` -> 0 or an errno, the C walk
    over a spare's pieces, built on first use; None where this host
    cannot build or load it (said in the log: once a process, whose one
    refill thread asks when it starts).  It forks a compiler, so it is
    that thread's to call and never the loop's.  The library is written
    under a temporary name and renamed, so a process that finds the name
    finds all of it."""
    try:
        with open(_NATIVE_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        cache = compile_cache.native_dir()
        lib = os.path.join(cache, f"populate_pieces-{digest}.so")
        if not os.path.exists(lib):
            cc = shutil.which("cc")
            if cc is None:
                raise OSError("no C compiler (cc) on PATH")
            os.makedirs(cache, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=cache, suffix=".tmp")
            os.close(fd)
            try:
                subprocess.run(
                    [cc, "-O2", "-shared", "-fPIC", "-o", tmp, _NATIVE_SRC],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, lib)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        walk = ctypes.CDLL(lib, use_errno=True).populate_pieces
    except (OSError, subprocess.SubprocessError, AttributeError) as exc:
        logging.getLogger("ceph_tpu.store").warning(
            "store pool: no native walk (%s): the refill thread walks a "
            "spare's pieces in Python", exc)
        return None
    walk.restype = ctypes.c_int
    walk.argtypes = [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t]
    return walk


def _make_spare(n: int, walk=None) -> memoryview:
    """A private anonymous mapping of ``n`` bytes that the kernel
    populated, for the refill thread: ``_POOL_PIECE`` at a time, each
    piece one ``mmap`` call.  A populate holds the address space's lock
    for as long as it lasts, and everybody who maps, unmaps or faults
    waits for it, the tick threads first: 2 MiB in one call on this
    thread cost k2m1 a fifth to a half of its p95 and 512 KiB a quarter,
    and pages faulted in one by one (a ``memset``, a write a page) cost
    it a seventh and are three times as dear to copy into afterwards
    (PERF.md section 5, "First touches off the loop").  A mapping of one
    piece or less is asked for populated; a longer one is mapped lazily
    and populated in place, piece by piece (``MAP_FIXED`` over its own
    range: the mapping stays ``block``'s, which unmaps all of it when it
    goes): by ``walk`` (``_native_walk``) in one foreign call that lets
    the GIL go once, or, with no ``walk`` and for a spare longer than
    ``_POOL_WALK_MAX``, by a Python loop that lets it go once a piece.
    The foreign calls made are counted."""
    flags = mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS
    if n <= _POOL_PIECE:
        block = mmap.mmap(-1, n, flags=flags | mmap.MAP_POPULATE)
        calls = 1
    else:
        block = mmap.mmap(-1, n, flags=flags)
        base = ctypes.addressof(ctypes.c_char.from_buffer(block))
        if walk is not None and n <= _POOL_WALK_MAX:
            calls = 2
            refused = walk(base, n, _POOL_PIECE)
            if refused:
                raise OSError(refused, "mmap")
        else:
            pieces = range(0, n, _POOL_PIECE)
            calls = 1 + len(pieces)
            flags |= mmap.MAP_POPULATE | _MAP_FIXED
            for off in pieces:
                if _libc_mmap(base + off, min(_POOL_PIECE, n - off),
                              mmap.PROT_READ | mmap.PROT_WRITE, flags, -1,
                              0) != base + off:
                    raise OSError(ctypes.get_errno(), "mmap")
    KERNELS.inc_many({"store_pool_calls": calls, "store_pool_spares": 1})
    return memoryview(block)


class _Pool:
    """Spare mappings for ``MemStore._land``: private, anonymous, one
    shard long, every page populated, never an object's before.  The
    loop thread only ever pops a deque and puts a token; everything
    else here runs on the refill thread, which takes no store's lock
    and never sees a byte of an object."""

    def __init__(self, bound: int = _POOL_MAX):
        self.bound = bound
        # length -> its spares, the newest at the left: ``_land`` takes
        # there (the pages populated last are the warmest) and the thread
        # evicts at the right.  ``deque`` and ``dict`` calls are atomic
        self.ready: Dict[int, deque] = {}
        # what ``_land`` tells the thread, one token a shard: n = it took
        # a spare of n bytes, -n = it found none of n bytes
        self.taken: queue.SimpleQueue = queue.SimpleQueue()
        self.thread: Optional[threading.Thread] = None
        # the thread's own: the lengths it knows, least recently taken
        # first, and the spare bytes as the tokens tell them (never under
        # what is there: a take is known here only after it happened)
        self._order: Dict[int, None] = {}
        self._reckoned = 0
        self._starting = threading.Lock()

    def miss(self, n: int) -> None:
        """``_land`` found no spare of ``n`` bytes: the thread learns the
        length, or makes room for it; the first miss starts the thread.
        A shard longer than the bound is nobody's to make ready: nothing
        is learnt and nothing evicted for it."""
        if n > self.bound:
            return
        if self.thread is None:
            self.start()
        self.taken.put(-n)

    def start(self) -> None:
        with self._starting:
            if self.thread is None:
                self.thread = threading.Thread(
                    target=self._refill, daemon=True, name="store-pool")
                self.thread.start()

    def stop(self) -> None:
        """Every token put so far is served, then the thread ends; the
        spares stay (``start`` goes on from them)."""
        if self.thread is not None:
            self.taken.put(None)
            self.thread.join()
            self.thread = None

    def spare_bytes(self) -> int:
        return sum(n * len(spares) for n, spares in list(self.ready.items()))

    def _drop(self, m: int, until: int) -> None:
        """Unmap spares of length ``m``, the coldest first, while the
        pool holds more than ``until`` bytes."""
        spares = self.ready.get(m)
        while spares and self._reckoned > until:
            try:
                spares.pop()            # the mapping goes with its view
            except IndexError:          # ``_land`` took the last one
                return
            self._reckoned -= m

    def _refill(self) -> None:
        ready, order, get = self.ready, self._order, self.taken.get
        clock = time.perf_counter_ns
        walk = _native_walk()           # here: it may fork a compiler
        while True:
            n = get()                   # parked here while the pool is full
            if n is None:
                return
            if n > 0:
                self._reckoned -= n
            else:
                n = -n
            if n not in order:
                # learnt: a length that never comes again ends here,
                # with one miss and no spare
                order[n] = None
                continue
            del order[n]
            order[n] = None             # the most recently taken
            spares = ready.get(n)
            if spares is None:          # before ``_land`` can see it: empty
                spares = ready[n] = deque()
            room = self.bound - n
            if not spares and self._reckoned > room:
                # a length with nothing ready needs room: spares of the
                # length least recently taken go first
                for m in order:
                    if m != n:
                        self._drop(m, room)
                    if self._reckoned <= room:
                        break
            while self._reckoned <= room:
                t0 = clock()
                try:
                    # no name of the thread's keeps the mapping: it goes
                    # when the object it becomes lets it go
                    spares.appendleft(_make_spare(n, walk))
                except OSError:
                    break
                KERNELS.inc("store_pool_touch_ns", clock() - t0)
                self._reckoned += n


_POOL = _Pool()


@dataclass
class Obj:
    # the store's own ``bytearray``, or the writable flat view of a
    # populated mapping of its own (``MemStore._land``: a full shard of
    # ``_MAP_MIN`` bytes or more), unmapped when the object lets it go.
    # A view has a fixed length: whatever may resize an object goes
    # through ``_own`` first.
    data: Union[bytearray, memoryview] = field(default_factory=bytearray)
    xattrs: Dict[str, bytes] = field(default_factory=dict)
    omap: Dict[str, bytes] = field(default_factory=dict)
    version: int = 0
    # at-rest data layout: None = classic bytes; a planar tag
    # (``planar_store.is_planar``: ``planar8``, ``packet8.<p>``) means
    # ``data`` holds the shard's (8, L/8) matrix of packed GF(2) rows in
    # that serialization, row-major (round 19).  Same byte length either
    # way, so _used/statfs/stat need no layout awareness.
    layout: Optional[str] = None


class Transaction:
    """Ordered op list; atomic at queue_transaction."""

    def __init__(self):
        self.ops: List[Tuple] = []

    def create_collection(self, coll: str):
        self.ops.append(("create_collection", coll))
        return self

    def remove_collection(self, coll: str):
        self.ops.append(("remove_collection", coll))
        return self

    def write(self, coll: str, oid: str, offset: int, data: bytes):
        self.ops.append(("write", coll, oid, offset, bytes(data)))
        return self

    def write_planar(self, coll: str, oid: str, plane_off: int,
                     data: bytes, total_cols: int,
                     layout: str = planar_store.LAYOUT_PLANAR):
        """Planar-at-rest shard write (round 19): land ``data`` — an
        (8, wc) plane-column window serialized row-major — at plane
        column ``plane_off`` (= byte offset / 8) and size the object to
        exactly ``total_cols`` columns (= shard bytes / 8).  One op
        covers the byte path's write+truncate pair, and the object's
        layout becomes ``layout``, the tag of the window's serialization.

        ``data`` (``bytes``, a ``memoryview``, a contiguous array) is
        kept as it is given and must not change before the transaction
        is queued: the store takes its one copy of it when the op is
        applied, and a journal its ``bytes`` in ``encode``."""
        self.ops.append(("write_planar", coll, oid, plane_off,
                         data, total_cols, layout))
        return self

    def truncate(self, coll: str, oid: str, size: int):
        self.ops.append(("truncate", coll, oid, size))
        return self

    def remove(self, coll: str, oid: str):
        self.ops.append(("remove", coll, oid))
        return self

    def clone(self, coll: str, src: str, dst: str):
        """Full-object copy (data + xattrs + omap), the COW primitive of
        the snapshot axis (reference ObjectStore::Transaction::clone)."""
        self.ops.append(("clone", coll, src, dst))
        return self

    def rb_capture(self, coll: str, oid: str, rb_oid: str, key: str):
        """Snapshot THIS store's current state of ``oid`` into the
        rollback journal object's omap under ``key`` — evaluated locally
        by each member so a fanned-out transaction captures each member's
        OWN pre-op bytes (EC shards differ per member; the reference
        attaches rollback info to the local transaction the same way,
        ecbackend.rst:10-27)."""
        self.ops.append(("rb_capture", coll, oid, rb_oid, key))
        return self

    def setattr(self, coll: str, oid: str, name: str, value: bytes):
        self.ops.append(("setattr", coll, oid, name, bytes(value)))
        return self

    def rmattr(self, coll: str, oid: str, name: str):
        self.ops.append(("rmattr", coll, oid, name))
        return self

    def omap_set(self, coll: str, oid: str, kv: Dict[str, bytes]):
        self.ops.append(("omap_set", coll, oid, dict(kv)))
        return self

    def omap_rmkeys(self, coll: str, oid: str, keys: List[str]):
        self.ops.append(("omap_rmkeys", coll, oid, list(keys)))
        return self

    def touch(self, coll: str, oid: str):
        self.ops.append(("touch", coll, oid))
        return self

    def set_version(self, coll: str, oid: str, version: int):
        self.ops.append(("set_version", coll, oid, version))
        return self

    def encode(self) -> bytes:
        # a planar window may still be a view of a frame or of a tick's
        # planes (write_planar), which no pickle takes
        return pickle.dumps([
            (*op[:4], bytes(op[4]), *op[5:])
            if op[0] == "write_planar" and type(op[4]) is not bytes
            else op for op in self.ops])

    @classmethod
    def decode(cls, blob: bytes) -> "Transaction":
        t = cls()
        t.ops = pickle.loads(blob)
        return t


class ObjectStore:
    # disk fault injector (ceph_tpu/chaos/disk.py DiskInjector), the
    # filestore_debug_inject_read_err analog; None (the default) keeps
    # every hot path to a single `is None` test
    chaos = None

    def mount(self) -> None: ...

    def umount(self) -> None: ...

    def debug_bitrot(self, coll: str, oid: str, bit: int) -> None:
        """Flip one stored bit WITHOUT touching any checksum — the
        silent-corruption seam the disk injector drives."""
        raise NotImplementedError

    def statfs(self) -> Tuple[int, int]:
        """(total_bytes, used_bytes) — reference ObjectStore::statfs."""
        return (0, 0)

    def queue_transaction(self, txn: Transaction) -> None:
        raise NotImplementedError

    def read(self, coll: str, oid: str, offset: int = 0,
             length: Optional[int] = None) -> bytes:
        raise NotImplementedError

    def read_planar(self, coll: str, oid: str) -> bytes:
        raise NotImplementedError

    def object_layout(self, coll: str, oid: str) -> Optional[str]:
        """At-rest layout tag (None = bytes / missing / unsupported)."""
        return None

    def stat(self, coll: str, oid: str) -> Optional[int]:
        raise NotImplementedError


def _own(o: Obj, keep: bool = True) -> bytearray:
    """``o.data`` as a ``bytearray``, for whatever may RESIZE an object:
    a view of a mapping (``Obj.data``) cannot, so it is copied out first
    (``keep``), or let go where every byte is about to be replaced."""
    if type(o.data) is not bytearray:
        o.data = bytearray(o.data if keep else b"")
    return o.data


class MemStore(ObjectStore):
    # full shards land in populated mappings (``_land``).  False where
    # the platform has no ``MAP_POPULATE``, and on a store that
    # serialises its objects (``FileStore`` pickles them)
    populates = hasattr(mmap, "MAP_POPULATE")

    def __init__(self, device_bytes: int = 1 << 30):
        self._colls: Dict[str, Dict[str, Obj]] = {}
        self._lock = threading.RLock()
        # advertised AND enforced capacity (memstore_device_bytes
        # analog): statfs reports against it, and (round 16) a
        # transaction whose net data growth would exceed it is refused
        # whole with ENOSPC — the store-level backstop beneath the
        # mon's full-flag protection.  Used bytes are maintained
        # incrementally (_used) so neither statfs nor admission pays an
        # all-objects scan on the hot path.
        self.device_bytes = device_bytes
        self._used = 0

    # -- transaction application (atomic under lock) -----------------------

    def _txn_growth(self, txn: Transaction) -> int:
        """Net DATA bytes this transaction would add (write extensions,
        upward truncates, clones), credited for its own removes/shrinks
        — so a delete-and-rewrite txn admits whenever its net effect
        fits.  Attr/omap bytes are not counted, matching statfs."""
        grow = 0
        sizes: Dict[Tuple[str, str], int] = {}

        def cur(coll: str, oid: str) -> int:
            key = (coll, oid)
            if key not in sizes:
                o = self._colls.get(coll, {}).get(oid)
                sizes[key] = len(o.data) if o is not None else 0
            return sizes[key]

        for op in txn.ops:
            kind = op[0]
            if kind == "write":
                _, coll, oid, offset, data = op
                new = max(cur(coll, oid), offset + len(data))
                grow += new - sizes[(coll, oid)]
                sizes[(coll, oid)] = new
            elif kind == "write_planar":
                _, coll, oid, _plane_off, _data, total_cols = op[:6]
                # one op fixes the final size exactly: 8 plane rows of
                # total_cols packed bytes == the shard's byte length, so
                # planar admission counts TRUE plane bytes (satellite:
                # same ENOSPC behavior as the byte anchor)
                new = 8 * total_cols
                grow += new - cur(coll, oid)
                sizes[(coll, oid)] = new
            elif kind == "truncate":
                _, coll, oid, size = op
                grow += size - cur(coll, oid)
                sizes[(coll, oid)] = size
            elif kind == "clone":
                _, coll, src, dst = op
                grow += cur(coll, src) - cur(coll, dst)
                sizes[(coll, dst)] = sizes[(coll, src)]
            elif kind == "remove":
                _, coll, oid = op
                grow -= cur(coll, oid)
                sizes[(coll, oid)] = 0
            elif kind == "remove_collection":
                for oid, o in self._colls.get(op[1], {}).items():
                    grow -= len(o.data)
                    sizes[(op[1], oid)] = 0
        return grow

    def _check_capacity(self, txn: Transaction) -> None:
        """Refuse a transaction whose net data growth would exceed the
        enforced capacity — WHOLE, before any byte lands (atomicity,
        like the injected ENOSPC).  Deletes and shrinks (grow <= 0)
        always admit, so a full store can dig itself out.  Shared by
        MemStore and the journal-backed FileStore subclass (which must
        check BEFORE journaling, or replay would re-meet the frame)."""
        if not self.device_bytes:
            return
        with self._lock:
            grow = self._txn_growth(txn)
            if grow > 0 and self._used + grow > self.device_bytes:
                raise OSError(
                    28, f"store full: {self._used} used + "
                        f"{grow} > {self.device_bytes}")

    def queue_transaction(self, txn: Transaction) -> None:
        # the loop's account (trace/loopacct.py): every commit made on
        # the loop thread, a replica's as much as the primary's
        acct = loopacct.ACCOUNT
        t0 = time.perf_counter_ns() if acct is not None and acct.timing \
            and acct.thread == threading.get_ident() else 0
        if self.chaos is not None:
            # injected ENOSPC refuses the WHOLE txn before any byte
            # lands (atomicity preserved)
            self.chaos.on_write(txn)
        self._check_capacity(txn)
        self._commit(txn)
        if self.chaos is not None:
            self.chaos.maybe_rot(self, txn)
        if t0:
            acct.store_done(t0)
        # store-commit boundary on the current op's timeline (no-op
        # outside a tracked dispatch — recovery, replicas, scrub)
        mark_current("store:commit")

    def _commit(self, txn: Transaction) -> None:
        with self._lock:
            for op in txn.ops:
                self._apply(op)

    def _apply(self, op: Tuple) -> None:
        kind = op[0]
        if kind == "create_collection":
            self._colls.setdefault(op[1], {})
        elif kind == "remove_collection":
            dropped = self._colls.pop(op[1], None)
            if dropped:
                self._used -= sum(len(o.data) for o in dropped.values())
        elif kind == "touch":
            self._coll(op[1]).setdefault(op[2], Obj())
        elif kind == "write":
            _, coll, oid, offset, data = op
            o = self._coll(coll).setdefault(oid, Obj())
            old = len(o.data)
            end = offset + len(data)
            if planar_store.is_planar(o.layout):
                # byte write onto a planar object: the object leaves
                # planar-at-rest.  A full rewrite just drops the layout;
                # a partial overlay must land on LOGICAL bytes, so
                # materialize once (counted relayout) before splicing.
                if not (offset == 0 and old <= end):
                    logical = planar_store.planes_to_shard(
                        planar_store.blob_to_planes(bytes(o.data)),
                        seam="relayout", layout=o.layout)
                    _own(o, keep=False)[:] = logical
                o.layout = None
            if offset == 0 and len(o.data) <= end:
                # full rewrite/extend from 0 (the EC full-shard write):
                # one copy, no zero-fill of bytes about to be replaced
                _own(o, keep=False)[:] = data
            else:
                own = _own(o)
                if len(own) < end:
                    own.extend(b"\0" * (end - len(own)))
                own[offset:end] = data
            o.version += 1
            self._used += len(o.data) - old
        elif kind == "write_planar":
            _, coll, oid, plane_off, data, total_cols = op[:6]
            layout = planar_store.op_layout(op)
            o = self._coll(coll).setdefault(oid, Obj())
            old = len(o.data)
            blob = memoryview(data)
            KERNELS.inc("store_planar_write_bytes", blob.nbytes)
            if plane_off == 0 and blob.nbytes == 8 * total_cols:
                # the window IS the new shard (every full-shard write):
                # nothing of the old object survives it, so it is not
                # read, and the store's own copy is the only one made:
                # into a populated mapping, or as before
                landed = self._land(blob) if self.populates else None
                if landed is None:
                    _own(o, keep=False)[:] = blob
                else:
                    o.data = landed
                KERNELS.inc("store_planar_direct_bytes", blob.nbytes)
            else:
                # a partial (or overshooting) window lands in the old
                # plane matrix, zero-extended or cut to total_cols
                window = planar_store.blob_to_planes(blob)
                if o.data:
                    # the object's own serialization is a reshape.  A
                    # planar write landing on a byte-at-rest object: the
                    # config gate flipped mid-life — convert once,
                    # counted (zero-pad to the packing quantum; EC
                    # shards are stripe-unit aligned so this is a
                    # non-EC-object guard).  The other planar
                    # serialization is refused by name.
                    raw = bytes(o.data)
                    q = planar_store.quantum(layout)
                    if len(raw) % q:
                        raw += b"\0" * (q - len(raw) % q)
                    cur = planar_store.planes_as(raw, o.layout, layout)
                else:
                    cur = None
                merged = planar_store.planes_to_blob(
                    planar_store.splice_columns(
                        cur, plane_off, window, total_cols))
                _own(o, keep=False)[:] = merged
            o.layout = layout
            o.version += 1
            self._used += len(o.data) - old
        elif kind == "truncate":
            _, coll, oid, size = op
            o = self._coll(coll).setdefault(oid, Obj())
            old = len(o.data)
            if planar_store.is_planar(o.layout) and old != size:
                # byte truncate of a planar object cuts PLANE ROWS, not
                # logical bytes — leave planar first (counted relayout)
                logical = planar_store.planes_to_shard(
                    planar_store.blob_to_planes(bytes(o.data)),
                    seam="relayout", layout=o.layout)
                _own(o, keep=False)[:] = logical
                o.layout = None
            own = _own(o)
            if len(own) > size:
                del own[size:]
            else:
                own.extend(b"\0" * (size - len(own)))
            o.version += 1
            self._used += len(o.data) - old
        elif kind == "remove":
            dropped = self._coll(op[1]).pop(op[2], None)
            if dropped is not None:
                self._used -= len(dropped.data)
        elif kind == "clone":
            _, coll, src, dst = op
            s = self._coll(coll).get(src)
            if s is not None:
                prev = self._coll(coll).get(dst)
                self._used += len(s.data) - \
                    (len(prev.data) if prev is not None else 0)
                self._coll(coll)[dst] = Obj(
                    data=bytearray(s.data), xattrs=dict(s.xattrs),
                    omap=dict(s.omap), version=s.version,
                    layout=s.layout)
        elif kind == "rb_capture":
            _, coll, oid, rb_oid, key = op
            o = self._coll(coll).get(oid)
            rec = {
                "oid": oid, "existed": o is not None, "chunk_off": 0,
                "old_range": bytes(o.data) if o else b"",
                "old_total": len(o.data) if o else 0,
                "old_attrs": ({k: o.xattrs.get(k)
                               for k in ("shard", "size", "hinfo_crc")}
                              if o else {}),
                "old_version": o.version if o else 0,
                # at-rest layout travels with the rollback record so a
                # rewind restores planar objects AS planar (pg.py
                # rewind_divergent_log dispatches on it)
                "layout": o.layout if o else None,
            }
            self._coll(coll).setdefault(rb_oid, Obj()).omap[key] = \
                pickle.dumps(rec)
        elif kind == "setattr":
            _, coll, oid, name, value = op
            self._coll(coll).setdefault(oid, Obj()).xattrs[name] = value
        elif kind == "rmattr":
            _, coll, oid, name = op
            o = self._coll(coll).get(oid)
            if o is not None:
                o.xattrs.pop(name, None)
        elif kind == "omap_set":
            _, coll, oid, kv = op
            self._coll(coll).setdefault(oid, Obj()).omap.update(kv)
        elif kind == "omap_rmkeys":
            _, coll, oid, keys = op
            o = self._coll(coll).get(oid)
            if o is not None:
                for k in keys:
                    o.omap.pop(k, None)
        elif kind == "set_version":
            _, coll, oid, version = op
            self._coll(coll).setdefault(oid, Obj()).version = version
        else:
            raise ValueError(f"unknown transaction op {kind}")

    def _coll(self, coll: str) -> Dict[str, Obj]:
        return self._colls.setdefault(coll, {})

    @staticmethod
    def _land(blob: memoryview) -> Optional[memoryview]:
        """``blob`` copied into a mapping of its own whose pages are
        there already: the view that becomes the object.  The mapping is
        a spare of the pool's when one of the blob's length is ready (the
        copy is all that runs here); else the kernel populates one on the
        calling thread, as since PR 43, and the pool is told.  None (the
        caller copies into a ``bytearray`` as before) for a blob under
        ``_MAP_MIN`` and when the kernel refuses the mapping."""
        n = blob.nbytes
        if n < _MAP_MIN:
            return None
        pool = _POOL
        spares = pool.ready.get(n)
        try:
            view = spares.popleft() if spares else None
        except IndexError:              # the thread dropped the last one
            view = None
        if view is not None:
            pool.taken.put(n)
            view[:] = blob.cast("B")
            KERNELS.inc("store_planar_pooled_bytes", n)
            KERNELS.inc("store_planar_populated_bytes", n)
            return view
        pool.miss(n)
        t0 = time.perf_counter_ns()
        try:
            block = mmap.mmap(-1, n, flags=mmap.MAP_PRIVATE
                              | mmap.MAP_ANONYMOUS | mmap.MAP_POPULATE)
        except OSError:
            return None
        KERNELS.inc("store_populate_ns", time.perf_counter_ns() - t0)
        view = memoryview(block)
        view[:] = blob.cast("B")        # flat bytes, whatever carried them
        KERNELS.inc("store_planar_populated_bytes", n)
        return view

    # -- reads -------------------------------------------------------------

    def read(self, coll: str, oid: str, offset: int = 0,
             length: Optional[int] = None) -> bytes:
        if self.chaos is not None:
            self.chaos.on_read(coll, oid)
        with self._lock:
            o = self._colls.get(coll, {}).get(oid)
            if o is None:
                raise FileNotFoundError(f"{coll}/{oid}")
            if planar_store.is_planar(o.layout) and o.data:
                # byte view of a planar object OUTSIDE the sanctioned
                # seams (egress of last resort): correct, but it books
                # the ``unseamed`` counter the steady-state contract
                # pins to zero — EC hot paths must use read_planar.
                data = planar_store.planes_to_shard(  # graftlint: ignore[planar-conversion-hygiene]
                    planar_store.blob_to_planes(bytes(o.data)),
                    seam="unseamed", layout=o.layout)
                if length is None:
                    return data[offset:]
                return data[offset : offset + length]
            if length is None:
                return bytes(o.data[offset:])
            return bytes(o.data[offset : offset + length])

    def read_planar(self, coll: str, oid: str) -> bytes:
        """The at-rest plane blob of a planar object, as stored — ZERO
        layout conversion.  Callers gate on object_layout first; a
        byte-at-rest object raises (mixed generations are the caller's
        relayout decision, not a silent conversion here)."""
        if self.chaos is not None:
            self.chaos.on_read(coll, oid)
        with self._lock:
            o = self._colls.get(coll, {}).get(oid)
            if o is None:
                raise FileNotFoundError(f"{coll}/{oid}")
            if not planar_store.is_planar(o.layout):
                raise ValueError(f"{coll}/{oid} is not planar-at-rest")
            return bytes(o.data)

    def object_layout(self, coll: str, oid: str) -> Optional[str]:
        """At-rest layout tag (None = bytes / missing object)."""
        with self._lock:
            o = self._colls.get(coll, {}).get(oid)
            return None if o is None else o.layout

    def debug_bitrot(self, coll: str, oid: str, bit: int) -> None:
        """Silent in-place bit flip (no version bump, no attr change):
        only a checksum-verifying reader — deep scrub comparing against
        the stored hinfo crc — can tell."""
        with self._lock:
            o = self._colls.get(coll, {}).get(oid)
            if o is None or not o.data:
                raise FileNotFoundError(f"{coll}/{oid}")
            byte, shift = divmod(bit % (len(o.data) * 8), 8)
            o.data[byte] ^= 1 << shift

    def stat(self, coll: str, oid: str) -> Optional[int]:
        with self._lock:
            o = self._colls.get(coll, {}).get(oid)
            return None if o is None else len(o.data)

    def get_version(self, coll: str, oid: str) -> int:
        with self._lock:
            o = self._colls.get(coll, {}).get(oid)
            return 0 if o is None else o.version

    def getattr(self, coll: str, oid: str, name: str) -> Optional[bytes]:
        with self._lock:
            o = self._colls.get(coll, {}).get(oid)
            return None if o is None else o.xattrs.get(name)

    def omap_get(self, coll: str, oid: str) -> Dict[str, bytes]:
        with self._lock:
            o = self._colls.get(coll, {}).get(oid)
            return {} if o is None else dict(o.omap)

    def get_xattrs(self, coll: str, oid: str) -> Dict[str, bytes]:
        with self._lock:
            o = self._colls.get(coll, {}).get(oid)
            return {} if o is None else dict(o.xattrs)

    def list_objects(self, coll: str) -> List[str]:
        with self._lock:
            return sorted(self._colls.get(coll, {}))

    def list_collections(self) -> List[str]:
        with self._lock:
            return sorted(self._colls)

    def _recount_used(self) -> None:
        """Rebuild the incremental used-bytes counter from the object
        map — for mount paths that restore ``_colls`` wholesale (the
        FileStore checkpoint load) instead of replaying ops."""
        with self._lock:
            self._used = sum(len(o.data) for c in self._colls.values()
                             for o in c.values())

    def statfs(self) -> Tuple[int, int]:
        with self._lock:
            return (self.device_bytes, self._used)
