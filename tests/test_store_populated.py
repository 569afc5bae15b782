"""Full shards land in populated mappings (PR 43).

What ``MemStore`` retains is new memory, and on the chip host its first
touch is what a write costs the loop (PERF.md section 5, "First
touches").  A full-shard ``write_planar`` of at least ``_MAP_MIN`` bytes
therefore lands, with the store's one copy, in a mapping of its own that
the store mapped ``MAP_POPULATE``: the object is the writable view of
it.  What is pinned here: who lands there (the full window of that size
on a ``MemStore``, nothing else, and no store that serialises its
objects); that such an object reads, counts and is written to exactly as
a ``bytearray`` one; that the mapping goes when its object lets it go,
so that nothing is stranded whatever the traffic; that a mapping the
kernel refuses falls back to the copy; that the populate is timed; and,
on tiny clusters, that every shard byte of ``write_full``s is landed so.

Since PR 45 the mapping may be a spare that the process's pool
(``store._POOL``) had ready: one refill thread maps and populates spares
of the lengths ``_land`` asked for, off the calling thread, and ``_land``
takes one and copies, or maps inline as before when none is ready.
Pinned in "the spares": a hit lands what a miss and the ``bytearray``
path land; the spare bytes stay under the bound and the length least
recently taken pays for a new one; a spare goes with its object as an
inline mapping does; who never starts the thread; and that no
transaction needs the thread to run.

Since PR 51 the pieces of a spare are walked by a C function that the
refill thread builds with the host's ``cc`` (``store._native_walk``), in
one foreign call a spare; the Python walk serves where it cannot be
built.  Pinned in "the native walk": it leaves what the Python walk
leaves and asks the kernel for no more than a piece at a time; it costs
the thread two returns to the GIL a spare whatever the length; who
builds it, where the build is kept, and that a host without a compiler
or a source that does not build is served by the Python walk.
"""

import asyncio
import ctypes
import gc
import glob
import logging
import mmap
import os
import shutil
import subprocess
import sys
import threading
import weakref

import numpy as np
import pytest

from ceph_tpu.cluster import store as store_mod
from ceph_tpu.cluster.pg import _coll
from ceph_tpu.cluster.store import MemStore, Transaction, _own
from ceph_tpu.ec import planar_store
from ceph_tpu.utils.perf import KERNELS
from tests._flaky import contention_retry

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS", "") == "",
    reason="run under JAX_PLATFORMS=cpu like the tier-1 lane")

COLS = 512                      # the shard: 8 x 512 = 4096 bytes
NBYTES = 8 * COLS
MAP_MIN = 1024                  # the tiny cases' ``_MAP_MIN``
PLANAR = planar_store.LAYOUT_PLANAR


def run(coro):
    return asyncio.run(coro)


@pytest.fixture
def small_map_min(monkeypatch):
    monkeypatch.setattr(store_mod, "_MAP_MIN", MAP_MIN)


@pytest.fixture(autouse=True)
def pool(monkeypatch):
    """Every test has a pool of its own, empty and with no thread yet, so
    that its first ``_land`` of a length is a miss whatever ran before;
    the thread, if the test started one, ends with the test."""
    fresh = store_mod._Pool()
    monkeypatch.setattr(store_mod, "_POOL", fresh)
    yield fresh
    fresh.stop()


def _planes(cols: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, (8, cols), dtype=np.uint8)


def _counters():
    return tuple(KERNELS.get(f"store_planar_{name}_bytes")
                 for name in ("write", "direct", "populated"))


def _populate_ns() -> int:
    return KERNELS.get("store_populate_ns")


def _grew(before):
    return tuple(now - was for now, was in zip(_counters(), before))


def _as(kind: str, blob: bytes):
    """The window as each caller hands it over, and what it views."""
    if kind == "frame_view":            # messenger._decode_oob
        frame = bytearray(b"head" * 25 + blob)
        return memoryview(frame)[100:].toreadonly(), frame
    if kind == "bytes":                 # a small payload, pickled in band
        return blob, None
    if kind == "bytearray":
        buf = bytearray(blob)
        return buf, buf
    assert kind == "array"              # the tick's (8, cols) block
    arr = np.frombuffer(blob, dtype=np.uint8).reshape(8, -1).copy()
    return arr, None


def _store(pre: str = "new", populates: bool = True):
    """A store with another object beside ``o``, and ``o`` in the named
    pre-state; ``populates`` False is the path as it was before."""
    s = MemStore(device_bytes=1 << 20)
    s.populates = populates
    s.queue_transaction(Transaction().create_collection("c")
                        .write("c", "other", 0, b"x" * 100))
    if pre == "bytes_at_rest":
        s.queue_transaction(Transaction().write("c", "o", 0,
                                                bytes(range(256)) * 4))
    elif pre != "new":
        cols = {"longer_planar": COLS + 128,
                "shorter_planar": COLS - 128}[pre]
        s.queue_transaction(Transaction().write_planar(
            "c", "o", 0, _planes(cols, seed=1).tobytes(), cols))
    return s


def _state(s: MemStore):
    """Everything a store says of itself, through its public reads."""
    out = {"statfs": s.statfs(), "colls": s.list_collections()}
    for coll in s.list_collections():
        for oid in s.list_objects(coll):
            out[coll, oid] = (
                s.stat(coll, oid), s.read(coll, oid),
                s.read(coll, oid, 8, 100), s.object_layout(coll, oid),
                s.read_planar(coll, oid)
                if s.object_layout(coll, oid) == PLANAR else None,
                s.get_version(coll, oid), s.get_xattrs(coll, oid),
                s.omap_get(coll, oid))
    return out


def _mapped(o) -> bool:
    """``o`` is the whole of a writable mapping of its own."""
    return type(o.data) is memoryview and type(o.data.obj) is mmap.mmap \
        and not o.data.readonly and len(o.data.obj) == len(o.data)


# -------------------------------------------------------------- who lands


@pytest.mark.parametrize("kind", ["frame_view", "bytes", "bytearray",
                                  "array"])
@pytest.mark.parametrize("pre", ["new", "longer_planar", "shorter_planar",
                                 "bytes_at_rest"])
def test_a_full_window_lands_in_a_populated_mapping(small_map_min, pre,
                                                    kind):
    """The object is the flat writable view of a mapping of its own,
    whatever the object was before and whatever carried the window; it
    leaves what the ``bytearray`` path leaves, to the last attribute;
    all of its bytes are booked written, direct and populated, and the
    populate is timed; and nothing of the store views the source."""
    blob = _planes(COLS, seed=2).tobytes()
    data, owner = _as(kind, blob)
    s, twin = _store(pre), _store(pre, populates=False)
    before, ns = _counters(), _populate_ns()
    s.queue_transaction(Transaction().write_planar("c", "o", 0, data, COLS))
    assert _grew(before) == (NBYTES, NBYTES, NBYTES)
    assert _populate_ns() > ns
    o = s._colls["c"]["o"]
    assert _mapped(o)
    assert (o.data.ndim, o.data.format, len(o.data)) == (1, "B", NBYTES)
    ns = _populate_ns()
    twin.queue_transaction(
        Transaction().write_planar("c", "o", 0, blob, COLS))
    assert type(twin._colls["c"]["o"].data) is bytearray
    assert _populate_ns() == ns
    assert _state(s) == _state(twin)
    assert s.read_planar("c", "o") == blob
    assert s.statfs() == (1 << 20, 100 + NBYTES)
    del data
    if owner is not None:
        owner[:] = bytes(len(owner))
        owner.extend(b"\0")     # BufferError while anything views it
    assert s.read_planar("c", "o") == blob


@pytest.mark.parametrize("case", ["append", "middle", "overshoot",
                                  "short_of_total"])
def test_any_other_window_is_spliced_into_a_bytearray(small_map_min, case):
    off, wc, total = {"append": (COLS - 128, 128, COLS),
                      "middle": (64, 128, COLS - 128),
                      "overshoot": (0, COLS, COLS - 64),
                      "short_of_total": (0, 64, COLS - 128)}[case]
    blob = _planes(wc, seed=3).tobytes()
    s, twin = _store("shorter_planar"), \
        _store("shorter_planar", populates=False)
    assert _mapped(s._colls["c"]["o"])
    before = _counters()
    s.queue_transaction(
        Transaction().write_planar("c", "o", off, blob, total))
    assert _grew(before) == (8 * wc, 0, 0)
    assert type(s._colls["c"]["o"].data) is bytearray
    twin.queue_transaction(
        Transaction().write_planar("c", "o", off, blob, total))
    assert _state(s) == _state(twin)


# every verb that meets an object in a mapping: (the transaction on a
# store that holds "o", whether "o" is still the view of a mapping
# afterwards; None: it is gone)
_VERBS = {
    "write_full": (lambda t: t.write("c", "o", 0, b"n" * 5000), False),
    "write_in_place": (lambda t: t.write("c", "o", 100, b"n" * 50), False),
    "write_extend": (lambda t: t.write("c", "o", 4000, b"n" * 500), False),
    "write_planar_append": (lambda t: t.write_planar(
        "c", "o", COLS, _planes(64, 4).tobytes(), COLS + 64), False),
    "write_planar_middle": (lambda t: t.write_planar(
        "c", "o", 8, _planes(64, 4).tobytes(), COLS), False),
    "write_planar_full": (lambda t: t.write_planar(
        "c", "o", 0, _planes(COLS, 4).tobytes(), COLS), True),
    "truncate_shorter": (lambda t: t.truncate("c", "o", 1000), False),
    "truncate_longer": (lambda t: t.truncate("c", "o", 5000), False),
    "clone": (lambda t: t.clone("c", "o", "o2"), True),
    "clone_over": (lambda t: t.clone("c", "other", "o"), False),
    "rb_capture": (lambda t: t.rb_capture("c", "o", "rb", "k1"), True),
    "setattr_omap": (lambda t: t.setattr("c", "o", "a", b"v")
                     .omap_set("c", "o", {"k": b"v"}), True),
    "remove": (lambda t: t.remove("c", "o"), None),
    "remove_collection": (lambda t: t.remove_collection("c"), None),
}


@pytest.mark.parametrize("verb", list(_VERBS) + ["debug_bitrot"])
def test_every_verb_gives_what_it_gives_a_bytearray(small_map_min, verb):
    """The same bytes, the same ``_used``, the same everything as on a
    store whose objects are ``bytearray``s; whatever may resize the
    object first makes it one (``_own``), a flipped bit lands in the
    mapping."""
    blob = _planes(COLS, seed=5).tobytes()
    s, twin = _store(), _store(populates=False)
    for store in (s, twin):
        store.queue_transaction(
            Transaction().write_planar("c", "o", 0, blob, COLS))
    if verb == "debug_bitrot":
        for store in (s, twin):
            store.debug_bitrot("c", "o", 12345)
        stays = True
        assert s.read_planar("c", "o") != blob
    else:
        build, stays = _VERBS[verb]
        for store in (s, twin):
            store.queue_transaction(build(Transaction()))
    assert _state(s) == _state(twin)
    assert s._used == twin._used == sum(
        len(o.data) for objs in s._colls.values() for o in objs.values())
    o = s._colls.get("c", {}).get("o")
    if stays is None:
        assert o is None
    elif stays:
        assert _mapped(o)
    else:
        assert type(o.data) is bytearray
    if verb == "clone":
        assert type(s._colls["c"]["o2"].data) is bytearray


def test_own_copies_a_view_once_and_leaves_a_bytearray_alone(small_map_min):
    s = _store()
    blob = _planes(COLS, seed=6).tobytes()
    s.queue_transaction(Transaction().write_planar("c", "o", 0, blob, COLS))
    o = s._colls["c"]["o"]
    own = _own(o)
    assert type(own) is bytearray and own == blob and o.data is own
    assert _own(o) is own
    other = s._colls["c"]["other"].data
    assert _own(s._colls["c"]["other"]) is other
    assert _own(o, keep=False) is own and own == blob   # already its own


# -------------------------------------- mappings: one a shard, let go with it


def _land(s, coll, oid, seed=7, cols=COLS):
    blob = _planes(cols, seed).tobytes()
    s.queue_transaction(Transaction().write_planar(coll, oid, 0, blob, cols))
    return blob


def _pooled() -> int:
    return KERNELS.get("store_planar_pooled_bytes")


def _settle(pool) -> int:
    """Every token put so far is served, so the pool is as full as it
    gets; its spare bytes, which are what the thread reckons."""
    if pool.thread is not None:
        pool.stop()
        pool.start()
    assert pool._reckoned == pool.spare_bytes() <= pool.bound
    return pool.spare_bytes()


def _prime(pool, s, nbytes, coll="c"):
    """Two misses: the first teaches the pool the length, the second
    makes the thread fill it."""
    for i in range(2):
        _land(s, coll, f"prime{nbytes}_{i}", seed=90 + i, cols=nbytes // 8)
    _settle(pool)
    assert pool.ready[nbytes]


def _mappings(s):
    return [o.data.obj for objs in s._colls.values() for o in objs.values()
            if type(o.data) is memoryview]


def test_each_shard_has_a_mapping_of_its_own(small_map_min):
    """Nine shards, nine mappings, each as long as its shard (one that is
    no multiple of a page too): nothing is shared, so nothing can be
    stranded."""
    s = MemStore()
    s.queue_transaction(Transaction().create_collection("c"))
    cols = [COLS, COLS + 8, 2 * COLS] * 3
    blobs = [_land(s, "c", f"o{i}", seed=10 + i, cols=c)
             for i, c in enumerate(cols)]
    maps = _mappings(s)
    assert len(set(map(id, maps))) == 9
    assert sorted(len(m) for m in maps) == sorted(8 * c for c in cols)
    assert all(_mapped(o) for o in s._colls["c"].values())
    assert s._used == sum(8 * c for c in cols)
    assert [s.read_planar("c", f"o{i}") for i in range(9)] == blobs


@pytest.mark.parametrize("nbytes, mapped", [
    (MAP_MIN - 8, False), (MAP_MIN, True), (MAP_MIN + 8, True)])
def test_a_shard_under_the_threshold_is_a_bytearray(small_map_min, nbytes,
                                                    mapped):
    """Under ``_MAP_MIN`` the copy goes into a ``bytearray`` as before and
    books nothing populated and no populate's time."""
    s = _store()
    before, ns = _counters(), _populate_ns()
    blob = _land(s, "c", "o", cols=nbytes // 8)
    assert _grew(before) == (nbytes, nbytes, nbytes if mapped else 0)
    assert (_populate_ns() > ns) == mapped
    o = s._colls["c"]["o"]
    assert _mapped(o) if mapped else type(o.data) is bytearray
    assert s.read_planar("c", "o") == blob


def test_the_threshold_is_a_quarter_of_a_mebibyte():
    """What the cells' shards are measured against: 0.5-2 MiB map, the
    64 KiB cell's 32 KiB do not; and of those that map, the C walk takes
    0.5-1 MiB and leaves k2m1's 2 MiB to the Python walk (PR 51)."""
    assert store_mod._MAP_MIN == 256 << 10
    assert store_mod._POOL_WALK_MAX == 1 << 20


@pytest.mark.parametrize("landed", ["inline", "pooled"])
@pytest.mark.parametrize("how", ["remove", "remove_collection",
                                 "overwritten", "resized", "rewritten"])
def test_a_mapping_goes_with_its_object(small_map_min, pool, how, landed):
    """Whatever takes the object's bytes away takes the mapping with
    them, at once (no collector has to run), and leaves the neighbours'
    alone: ``_used`` is what is mapped.  A mapping that was a spare of
    the pool's goes the same way, and never back to the pool."""
    s = MemStore()
    s.queue_transaction(Transaction().create_collection("c"))
    if landed == "pooled":
        _prime(pool, s, NBYTES)
    pooled = _pooled()
    _land(s, "c", "o", seed=20)
    assert _pooled() - pooled == (NBYTES if landed == "pooled" else 0)
    kept = _land(s, "c", "next", seed=21)
    gone = weakref.ref(s._colls["c"]["o"].data.obj)
    stays = weakref.ref(s._colls["c"]["next"].data.obj)
    if how == "remove":
        s.queue_transaction(Transaction().remove("c", "o"))
    elif how == "overwritten":
        s.queue_transaction(Transaction().write("c", "o", 0, bytes(NBYTES)))
    elif how == "resized":
        s.queue_transaction(Transaction().truncate("c", "o", NBYTES + 8))
    elif how == "rewritten":
        _land(s, "c", "o", seed=22)
        assert _mapped(s._colls["c"]["o"])
    if how == "remove_collection":
        s.queue_transaction(Transaction().remove_collection("c"))
        assert gone() is None and stays() is None and s._used == 0
        return
    assert gone() is None and stays() is not None
    assert s.read_planar("c", "next") == kept
    assert s._used == sum(len(o.data) for o in s._colls["c"].values())


@pytest.mark.parametrize("traffic", ["rewrite", "remove", "byte_overwrite"])
def test_nothing_is_stranded(small_map_min, traffic):
    """Rounds of rewrites, removes or byte overwrites over 32 shards: the
    mappings alive are those of the objects that are views now, each as
    long as its object, and together no more than ``statfs`` says is
    used: a store's memory is what it reports."""
    s = MemStore(device_bytes=1 << 22)
    s.queue_transaction(Transaction().create_collection("c"))
    made = []

    def land(i, seed):
        _land(s, "c", f"o{i}", seed=seed)
        made.append(weakref.ref(s._colls["c"][f"o{i}"].data.obj))

    for i in range(32):
        land(i, i)
    for rnd in range(1, 4):
        for i in range(rnd % 2, 32, 2):
            if traffic == "rewrite":
                land(i, 100 * rnd + i)
            elif traffic == "remove":
                s.queue_transaction(Transaction().remove("c", f"o{i}"))
                if rnd == 2:
                    land(i, 100 * rnd + i)
            else:
                s.queue_transaction(
                    Transaction().write("c", f"o{i}", 8 * rnd, b"x" * 8))
        alive = [m for m in (ref() for ref in made) if m is not None]
        now = _mappings(s)
        assert sorted(map(id, alive)) == sorted(map(id, now))
        assert all(_mapped(o) or type(o.data) is bytearray
                   for o in s._colls["c"].values())
        assert sum(len(m) for m in alive) <= s.statfs()[1] == s._used
        del alive, now


# ------------------------------------------------------ the spares (PR 45)


@pytest.mark.parametrize("how", ["miss", "hit"])
@pytest.mark.parametrize("nbytes", [256 << 10, 700416, 2 << 20])
def test_a_hit_and_a_miss_land_what_the_bytearray_path_lands(pool, nbytes,
                                                             how):
    """At the cells' own lengths and the threshold as it ships: a shard
    that takes a spare and one that maps inline leave the bytes, the
    version, ``_used`` and every other answer of the store that the
    ``populates = False`` twin leaves; the hit is booked pooled AND
    populated and runs no ``mmap`` on the calling thread, the miss is
    booked populated only and times its populate."""
    blob = _planes(nbytes // 8, seed=3).tobytes()
    s, twin = MemStore(1 << 26), MemStore(1 << 26)
    twin.populates = False
    for store in (s, twin):
        store.queue_transaction(Transaction().create_collection("c"))
    if how == "hit":
        _prime(pool, s, nbytes)
        _prime(pool, twin, nbytes)
    before, pooled, ns = _counters(), _pooled(), _populate_ns()
    s.queue_transaction(
        Transaction().write_planar("c", "o", 0, blob, nbytes // 8))
    assert _grew(before) == (nbytes, nbytes, nbytes)
    assert _pooled() - pooled == (nbytes if how == "hit" else 0)
    assert (_populate_ns() > ns) == (how == "miss")
    twin.queue_transaction(
        Transaction().write_planar("c", "o", 0, blob, nbytes // 8))
    o = s._colls["c"]["o"]
    assert _mapped(o) and (o.data.ndim, o.data.format) == (1, "B")
    assert s._used == twin._used and o.version == 1
    assert _state(s) == _state(twin)
    assert s.read_planar("c", "o") == blob


@pytest.mark.parametrize("lengths", [[4096], [1024, 1536, 2048, 4096, 8192]],
                         ids=["one_length", "five_lengths"])
def test_the_spares_stay_under_the_bound(small_map_min, monkeypatch,
                                         lengths):
    """Read while the thread fills and after it has: never more spare
    bytes than the bound, whichever lengths came in whatever order; and
    the thread's own reckoning is what is there."""
    pool = store_mod._Pool(bound=20 << 10)
    monkeypatch.setattr(store_mod, "_POOL", pool)
    s = MemStore(1 << 24)
    s.queue_transaction(Transaction().create_collection("c"))
    rng = np.random.default_rng(5)
    try:
        for i in range(200):
            n = int(rng.choice(lengths))
            _land(s, "c", f"o{i}", seed=i, cols=n // 8)
            assert pool.spare_bytes() <= pool.bound
            if i % 50 == 49:
                assert 0 < _settle(pool) <= pool.bound
                assert pool.bound - pool.spare_bytes() < max(lengths)
        assert set(pool.ready) == set(lengths)
    finally:
        pool.stop()
    assert s._used == sum(len(o.data) for o in s._colls["c"].values())


@pytest.mark.parametrize("stale", ["a", "b"])
def test_the_length_least_recently_taken_makes_the_room(small_map_min,
                                                        monkeypatch, stale):
    """A full pool holds two lengths; a third that finds nothing ready
    is given room by the one taken longest ago, and the other keeps its
    spares."""
    pool = store_mod._Pool(bound=16 << 10)
    monkeypatch.setattr(store_mod, "_POOL", pool)
    a, b, c = 4096, 2048, 3072
    s = MemStore(1 << 24)
    s.queue_transaction(Transaction().create_collection("c"))
    try:
        _prime(pool, s, a)
        _prime(pool, s, b)
        fresh = b if stale == "a" else a
        _land(s, "c", "again", cols=fresh // 8)     # a hit: now the newest
        had = {n: len(pool.ready[n]) for n in (a, b)}
        assert _settle(pool) + min(a, b) > pool.bound and all(had.values())
        _land(s, "c", "c0", cols=c // 8)            # learnt, nothing made
        assert _settle(pool) and c not in pool.ready
        _land(s, "c", "c1", cols=c // 8)
        _settle(pool)
        old = a if stale == "a" else b
        assert len(pool.ready[c]) >= 1
        assert len(pool.ready[old]) < had[old]
        assert len(pool.ready[fresh]) >= had[fresh]
    finally:
        pool.stop()


def test_a_length_that_never_comes_again_costs_one_miss_and_no_spare(
        small_map_min, pool):
    s = _store()
    for i, cols in enumerate((COLS, COLS + 8, COLS + 16)):
        _land(s, "c", f"o{i}", cols=cols)
    assert _settle(pool) == 0 and not pool.ready
    assert sorted(pool._order) == [NBYTES, NBYTES + 64, NBYTES + 128]


@pytest.mark.parametrize("nbytes, served", [(16 << 10, True),
                                            ((16 << 10) + 64, False)],
                         ids=["at_the_bound", "over_the_bound"])
def test_a_shard_over_the_bound_is_nobodys_to_make_ready(
        small_map_min, monkeypatch, nbytes, served):
    """A full pool of one length; a shard as long as the whole bound is
    given all of it, a longer one lands inline every time and costs the
    others nothing: no thread work, nothing learnt, nothing evicted."""
    pool = store_mod._Pool(bound=16 << 10)
    monkeypatch.setattr(store_mod, "_POOL", pool)
    s, twin = _store(), _store(populates=False)
    try:
        _prime(pool, s, NBYTES)
        had = len(pool.ready[NBYTES])
        assert had == 4
        pooled, ns = _pooled(), _populate_ns()
        for i in range(3):
            blob = _planes(nbytes // 8, seed=i).tobytes()
            for store in (s, twin):
                store.queue_transaction(Transaction().write_planar(
                    "c", f"big{i}", 0, blob, nbytes // 8))
            _settle(pool)
            assert _mapped(s._colls["c"][f"big{i}"])
        if served:
            assert _pooled() == pooled + nbytes
            assert not pool.ready[NBYTES] and len(pool.ready[nbytes]) == 1
        else:
            assert _pooled() == pooled and _populate_ns() > ns
            assert len(pool.ready[NBYTES]) == had
            assert nbytes not in pool.ready and nbytes not in pool._order
            assert pool.taken.empty()
        for i in range(3):
            assert s.read_planar("c", f"big{i}") == \
                twin.read_planar("c", f"big{i}")
    finally:
        pool.stop()


def test_no_transaction_needs_the_thread(small_map_min, pool, monkeypatch):
    """With a refill thread that is never scheduled every write is served
    on the calling thread, by misses: no byte of an object and no op of a
    transaction is the thread's to handle."""
    monkeypatch.setattr(store_mod._Pool, "start", lambda self: setattr(
        self, "thread", threading.Thread(target=lambda: None)))
    s, twin = _store(), _store(populates=False)
    before, pooled, ns = _counters(), _pooled(), _populate_ns()
    for i in range(20):
        blob = _planes(COLS, seed=i).tobytes()
        for store in (s, twin):
            store.queue_transaction(
                Transaction().write_planar("c", f"o{i}", 0, blob, COLS))
        assert _populate_ns() > ns
        ns = _populate_ns()
        assert _mapped(s._colls["c"][f"o{i}"])
    assert _grew(before) == (40 * NBYTES, 40 * NBYTES, 20 * NBYTES)
    assert _pooled() == pooled and not pool.ready
    assert _state(s) == _state(twin)
    pool.thread = None                  # it was never started: nothing to join


@pytest.mark.parametrize("who", ["under_the_threshold", "partial_window",
                                 "byte_write", "no_map_populate",
                                 "filestore"])
def test_who_never_starts_the_thread(small_map_min, pool, tmp_path, who):
    """The 64 KiB cell's shards, windows that are spliced, bytes at rest,
    a platform without ``MAP_POPULATE`` and a store that pickles its
    objects: none of them asks the pool for anything."""
    if who == "filestore":
        from ceph_tpu.cluster.filestore import FileStore
        s = FileStore(str(tmp_path / "fs"), checkpoint_every=1 << 20)
        s.mount()
        s.queue_transaction(Transaction().create_collection("c"))
    else:
        s = _store()
    s.populates = s.populates and who != "no_map_populate"
    for i in range(4):
        if who == "under_the_threshold":
            _land(s, "c", f"o{i}", cols=MAP_MIN // 8 - 1)
        elif who == "partial_window":
            s.queue_transaction(Transaction().write_planar(
                "c", "o", COLS * (i + 1), _planes(COLS, i).tobytes(),
                COLS * (i + 2)))
        elif who == "byte_write":
            s.queue_transaction(
                Transaction().write("c", f"o{i}", 0, bytes(NBYTES)))
        else:
            _land(s, "c", f"o{i}")
    assert pool.thread is None and pool.taken.empty() and not pool.ready
    assert "store-pool" not in {t.name for t in threading.enumerate()}
    if who == "filestore":
        s.umount()


def test_two_stores_share_the_pool_and_the_bound(small_map_min,
                                                 monkeypatch):
    """One pool a process: what one store's misses taught it serves the
    other's first shard of that length, the spares of both are counted
    against the one bound, neither ``statfs`` counts them, and no mapping
    is handed out twice."""
    pool = store_mod._Pool(bound=16 << 10)
    monkeypatch.setattr(store_mod, "_POOL", pool)
    one, two = MemStore(1 << 22), MemStore(1 << 22)
    for store in (one, two):
        store.queue_transaction(Transaction().create_collection("c"))
    try:
        _prime(pool, one, NBYTES)
        assert one.statfs()[1] == 2 * NBYTES and two.statfs()[1] == 0
        pooled, blobs = _pooled(), {}
        for i in range(12):
            store = (one, two)[i % 2]
            blobs[store, f"o{i}"] = _land(store, "c", f"o{i}", seed=i)
            assert pool.spare_bytes() <= pool.bound
            if i == 0:
                assert _pooled() - pooled == NBYTES     # ``two``'s first
        _settle(pool)
    finally:
        pool.stop()
    assert _pooled() - pooled >= 4 * NBYTES
    maps = _mappings(one) + _mappings(two)
    assert len(set(map(id, maps))) == len(maps) == 14
    assert all(m is not spare.obj for m in maps
               for spare in pool.ready[NBYTES])
    for (store, oid), blob in blobs.items():
        assert store.read_planar("c", oid) == blob
    assert one.statfs()[1] == 8 * NBYTES and two.statfs()[1] == 6 * NBYTES


def test_pooled_is_within_populated_is_within_written(small_map_min, pool):
    """After every step of a traffic of hits, misses, small shards,
    spliced windows and a store that does not populate."""
    s, plain = _store(), _store(populates=False)
    before, pooled = _counters(), _pooled()
    _prime(pool, s, NBYTES)
    steps = [lambda i: _land(s, "c", f"o{i}"),
             lambda i: _land(s, "c", f"s{i}", cols=MAP_MIN // 8 - 1),
             lambda i: _land(s, "c", f"n{i}", cols=COLS + 8 * (i + 1)),
             lambda i: _land(plain, "c", f"o{i}"),
             lambda i: s.queue_transaction(Transaction().write_planar(
                 "c", "w", COLS * i, _planes(COLS, i).tobytes(),
                 COLS * (i + 1)))]
    for i in range(30):
        steps[i % len(steps)](i)
        written, _direct, populated = _grew(before)
        assert 0 <= _pooled() - pooled <= populated <= written
    _settle(pool)
    assert _pooled() - pooled > 0


def test_a_spare_is_zeros_and_an_objects_mapping_never_returns(
        small_map_min, pool):
    """What ``_land`` takes has held nothing; what an object lets go is
    unmapped, it is not a spare again."""
    s = _store()
    _prime(pool, s, NBYTES)
    assert all(bytes(v) == bytes(NBYTES) for v in pool.ready[NBYTES])
    spares = _settle(pool)
    _land(s, "c", "o", seed=31)
    gone = weakref.ref(s._colls["c"]["o"].data.obj)
    _settle(pool)
    s.queue_transaction(Transaction().remove("c", "o"))
    assert gone() is None
    assert _settle(pool) == spares
    assert all(bytes(v) == bytes(NBYTES) for v in pool.ready[NBYTES])


def _resident(view) -> bool:
    """Every page of ``view``'s mapping is there (``mincore``)."""
    n = len(view)
    pages = -(-n // mmap.PAGESIZE)
    vec = (ctypes.c_ubyte * pages)()
    held = ctypes.c_char.from_buffer(view.obj)
    rc = ctypes.CDLL(None, use_errno=True).mincore(
        ctypes.c_void_p(ctypes.addressof(held)), ctypes.c_size_t(n), vec)
    del held
    assert rc == 0, ctypes.get_errno()
    return all(b & 1 for b in vec)


@pytest.mark.parametrize("piece", [64 << 10, store_mod._POOL_PIECE],
                         ids=["pieces_of_64k", "the_products_piece"])
@pytest.mark.parametrize("nbytes", [256 << 10, (256 << 10) + 8, 700416,
                                    1 << 20, 2 << 20, 8 << 20])
def test_a_spare_is_populated_piece_by_piece(monkeypatch, nbytes, piece):
    """Whatever the length every page of a spare is there when
    ``_make_spare`` returns, the kernel was asked for at most a piece at
    a time, the pieces tile the mapping's own range in order, and what
    comes back is one writable mapping of zeros that unmaps as a whole."""
    monkeypatch.setattr(store_mod, "_POOL_PIECE", piece)
    asked, real = [], store_mod._libc_mmap

    def libc_mmap(addr, n, prot, flags, fd, off):
        asked.append((addr, n, flags))
        return real(addr, n, prot, flags, fd, off)

    monkeypatch.setattr(store_mod, "_libc_mmap", libc_mmap)
    view = store_mod._make_spare(nbytes)
    assert type(view.obj) is mmap.mmap and len(view) == nbytes
    assert not view.readonly and _resident(view)
    assert bytes(view) == bytes(nbytes)
    if nbytes <= piece:
        assert not asked                # asked for populated, in one call
    else:
        want = mmap.MAP_POPULATE | store_mod._MAP_FIXED | mmap.MAP_PRIVATE
        assert all(n <= piece and flags & want == want
                   for _, n, flags in asked)
        base = asked[0][0]
        assert [a - base for a, _, _ in asked] == list(
            range(0, nbytes, piece))
        assert sum(n for _, n, _ in asked) == nbytes
    view[:] = b"\x5a" * nbytes          # the pieces are the block's own
    assert view.obj[nbytes - 1] == 0x5a
    block = view.obj
    view.release()
    block.close()                       # one unmap, nothing exported


def test_a_piece_the_kernel_refuses_ends_the_refill(monkeypatch):
    monkeypatch.setattr(store_mod, "_libc_mmap", lambda *a: None)
    with pytest.raises(OSError):
        store_mod._make_spare(store_mod._POOL_PIECE + 1)


@pytest.mark.parametrize("how", ["python", "native"])
def test_the_populate_lets_the_gil_go(how, request):
    """Held without a clock: with a switch interval of an hour nobody is
    made to give the GIL up, so the main thread gets it back while the
    helper is still inside ``_make_spare`` only if the calls in there let
    it go.  64 MiB, so that the populates last; a loaded host may still
    wake the main thread too late, so any of five goes proves it (were
    the GIL held, none could).  Either walk: the Python loop's ``mmap``
    calls, and the C walk's one call."""
    walk = None
    if how == "native":                 # 64 MiB in the C walk's one call
        walk = request.getfixturevalue("native")
        request.getfixturevalue("monkeypatch").setattr(
            store_mod, "_POOL_WALK_MAX", 64 << 20)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(3600)
    try:
        for _ in range(5):
            entered, done = threading.Event(), threading.Event()

            def helper():
                entered.set()
                store_mod._make_spare(64 << 20, walk)
                done.set()

            thread = threading.Thread(target=helper)
            thread.start()
            entered.wait(60)            # back here once the helper lets go
            inside = not done.is_set()
            thread.join(60)
            assert not thread.is_alive()
            if inside:
                break
    finally:
        sys.setswitchinterval(interval)
    assert inside


def test_takers_on_many_threads_never_share_a_spare(small_map_min,
                                                    monkeypatch):
    """More threads than cores, each with a store of its own, a switch
    interval of 10 us: every shard reads back its own bytes, no mapping
    is two objects', and the bound holds throughout."""
    pool = store_mod._Pool(bound=64 << 10)
    monkeypatch.setattr(store_mod, "_POOL", pool)
    workers, each = 2 * (os.cpu_count() or 4), 60
    stores = [MemStore(1 << 24) for _ in range(workers)]
    over = []

    def work(w):
        s = stores[w]
        s.queue_transaction(Transaction().create_collection("c"))
        for i in range(each):
            _land(s, "c", f"o{i}", seed=1000 * w + i)
            if pool.spare_bytes() > pool.bound:
                over.append((w, i))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(w,))
                   for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        _settle(pool)
    finally:
        sys.setswitchinterval(interval)
        pool.stop()
    assert not over
    maps = [m for s in stores for m in _mappings(s)]
    assert len(set(map(id, maps))) == len(maps) == workers * each
    for w, s in enumerate(stores):
        for i in range(0, each, 7):
            assert s.read_planar("c", f"o{i}") == \
                _planes(COLS, 1000 * w + i).tobytes()


def test_a_mapping_the_kernel_refuses_is_copied_as_before(small_map_min,
                                                          monkeypatch):
    def refuse(*args, **kwargs):
        raise OSError(12, "Cannot allocate memory")

    monkeypatch.setattr(store_mod.mmap, "mmap", refuse)
    s, twin = _store(), _store(populates=False)
    blob = _planes(COLS, seed=8).tobytes()
    before, ns = _counters(), _populate_ns()
    for store in (s, twin):
        store.queue_transaction(
            Transaction().write_planar("c", "o", 0, blob, COLS))
    assert _grew(before) == (2 * NBYTES, 2 * NBYTES, 0)
    assert _populate_ns() == ns
    assert type(s._colls["c"]["o"].data) is bytearray
    assert _state(s) == _state(twin)


def test_the_mapping_is_asked_for_populated(small_map_min, monkeypatch):
    """``MAP_POPULATE`` by its name, for the shard's own length: a
    platform without the flag maps nothing (``populates``) and the share
    metric reads 0 there, it does not read 100 with nothing populated."""
    asked = []
    real = mmap.mmap

    def record(fileno, length, **kwargs):
        asked.append((fileno, length, kwargs))
        return real(fileno, length, **kwargs)

    assert MemStore.populates is hasattr(mmap, "MAP_POPULATE")
    monkeypatch.setattr(store_mod.mmap, "mmap", record)
    s = _store()
    _land(s, "c", "o", cols=COLS + 8)
    assert asked == [(-1, NBYTES + 64, {
        "flags": mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS | mmap.MAP_POPULATE})]
    s.populates = False                     # as where the flag is missing
    before = _counters()
    _land(s, "c", "o2")
    assert len(asked) == 1 and _grew(before) == (NBYTES, NBYTES, 0)


def test_filestore_keeps_bytearrays_and_round_trips(small_map_min, tmp_path):
    """``FileStore`` pickles its objects at a checkpoint: it never lands
    one in a mapping, and the object survives a crash's replay and a
    checkpoint's reload."""
    from ceph_tpu.cluster.filestore import FileStore

    blob = _planes(COLS, seed=9).tobytes()
    s = FileStore(str(tmp_path / "fs"), checkpoint_every=2048)
    s.mount()
    before = _counters()
    s.queue_transaction(Transaction().create_collection("c")
                        .write_planar("c", "o", 0, blob, COLS))
    assert _grew(before) == (NBYTES, NBYTES, 0)
    assert type(s._colls["c"]["o"].data) is bytearray
    s2 = FileStore(str(tmp_path / "fs"), checkpoint_every=2048)
    s2.mount()                          # crash: the journal replays
    assert s2.read_planar("c", "o") == blob
    s2.checkpoint()
    s2.umount()
    s3 = FileStore(str(tmp_path / "fs"), checkpoint_every=2048)
    s3.mount()                          # and from the checkpoint
    assert s3.object_layout("c", "o") == PLANAR
    assert s3.read_planar("c", "o") == blob
    s3.umount()


# ------------------------------------------------ the native walk (PR 51)


@pytest.fixture
def native():
    """The C walk, built as the refill thread builds it, in the checkout's
    own cache.  Skips where the host has no compiler."""
    if shutil.which("cc") is None:
        pytest.skip("no C compiler (cc) on this host")
    walk = store_mod._native_walk()
    assert walk is not None
    return walk


_RECORDER = """
#undef mmap
#include <stddef.h>
#include <sys/mman.h>
#include <sys/types.h>
size_t longest_asked = 0;
void *recorded_mmap(void *at, size_t n, int prot, int flags, int fd,
                    off_t off)
{
    if (n > longest_asked)
        longest_asked = n;
    return mmap(at, n, prot, flags, fd, off);
}
"""


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(the product's source built with its ``mmap`` renamed to a recorder
    of this file's, the longest length the kernel was asked for through
    it): what the walk asks the kernel, seen from outside the product's
    source, which has no hook for it."""
    if shutil.which("cc") is None:
        pytest.skip("no C compiler (cc) on this host")
    where = tmp_path_factory.mktemp("recorded")
    (where / "recorder.c").write_text(_RECORDER)
    lib = str(where / "recorded.so")
    subprocess.run(["cc", "-O2", "-shared", "-fPIC", "-Dmmap=recorded_mmap",
                    "-o", lib, store_mod._NATIVE_SRC,
                    str(where / "recorder.c")], check=True,
                   capture_output=True)
    dll = ctypes.CDLL(lib, use_errno=True)
    walk = dll.populate_pieces
    walk.restype = ctypes.c_int
    walk.argtypes = [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t]
    return walk, ctypes.c_size_t.in_dll(dll, "longest_asked")


def _pool_counts():
    return (KERNELS.get("store_pool_calls"), KERNELS.get("store_pool_spares"))


@pytest.mark.parametrize("piece", [64 << 10, store_mod._POOL_PIECE],
                         ids=["pieces_of_64k", "the_products_piece"])
@pytest.mark.parametrize("nbytes", [256 << 10, (256 << 10) + 8, 700416,
                                    1 << 20, 2 << 20, 8 << 20])
def test_the_native_walk_leaves_what_the_python_walk_leaves(
        monkeypatch, native, recorded, nbytes, piece):
    """The twins of ``test_a_spare_is_populated_piece_by_piece``: every
    page is there when ``_make_spare`` returns, the kernel was asked for
    at most a piece at a time (the same source with its ``mmap``
    recorded), Python asked it for nothing, and what comes back is one
    writable mapping of zeros that unmaps as a whole.  A spare longer
    than ``_POOL_WALK_MAX`` (the 2 MiB and 8 MiB cases) is the Python
    walk's, a call a piece, though the C walk is there."""
    walk = native
    pieces = -(-nbytes // piece)
    c_walk = pieces > 1 and nbytes <= store_mod._POOL_WALK_MAX
    monkeypatch.setattr(store_mod, "_POOL_PIECE", piece)
    if c_walk:
        monkeypatch.setattr(store_mod, "_libc_mmap", lambda *a: pytest.fail(
            "the Python walk ran"))
    calls, spares = _pool_counts()
    view = store_mod._make_spare(nbytes, walk)
    assert type(view.obj) is mmap.mmap and len(view) == nbytes
    assert not view.readonly and _resident(view)
    assert bytes(view) == bytes(nbytes)
    # what the walk asks the kernel for such a range: a piece at a time
    recorded_walk, longest = recorded
    lazy = mmap.mmap(-1, nbytes, flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS)
    longest.value = 0
    assert recorded_walk(ctypes.addressof(ctypes.c_char.from_buffer(lazy)),
                         nbytes, piece) == 0
    assert longest.value == min(piece, nbytes) and _resident(memoryview(lazy))
    assert _pool_counts() == (
        calls + (1 if pieces == 1 else 2 if c_walk else 1 + pieces),
        spares + 1)
    view[:] = b"\x5a" * nbytes          # the pieces are the block's own
    assert view.obj[nbytes - 1] == 0x5a
    block = view.obj
    view.release()
    block.close()                       # one unmap, nothing exported


@pytest.mark.parametrize("nbytes", [256 << 10, (256 << 10) + 8, 700416,
                                    1 << 20, 2 << 20, 8 << 20])
def test_the_python_walk_returns_to_the_gil_once_a_piece(nbytes):
    """What ``store_pool_calls`` over ``store_pool_spares`` says of a
    thread without the C walk: the mapping's own call and one a piece: 9 /
    5 / 4 for the cells' shards of 2 MiB / 1 MiB / 684 KiB, where the C
    walk makes 2 (the twin above)."""
    calls, spares = _pool_counts()
    store_mod._make_spare(nbytes)
    pieces = -(-nbytes // store_mod._POOL_PIECE)
    assert _pool_counts() == (calls + (1 if pieces == 1 else 1 + pieces),
                              spares + 1)


@pytest.mark.parametrize("where", ["through_make_spare", "by_itself"])
def test_a_piece_the_kernel_refuses_raises_from_the_native_walk(
        monkeypatch, native, where):
    """A piece that is no multiple of a page puts the second ``MAP_FIXED``
    on an address the kernel refuses: the walk says which errno, and
    ``_make_spare`` raises it; no spare is counted."""
    walk = native
    spares = _pool_counts()[1]
    if where == "through_make_spare":
        monkeypatch.setattr(store_mod, "_POOL_PIECE", mmap.PAGESIZE + 8)
        with pytest.raises(OSError) as refused:
            store_mod._make_spare(4 * mmap.PAGESIZE, walk)
        assert refused.value.errno == 22
    else:
        block = mmap.mmap(-1, 4 * mmap.PAGESIZE)
        base = ctypes.addressof(ctypes.c_char.from_buffer(block))
        assert walk(base, 4 * mmap.PAGESIZE, mmap.PAGESIZE + 8) == 22
        assert walk(base, 4 * mmap.PAGESIZE, 0) == 22
        assert walk(base, 4 * mmap.PAGESIZE, mmap.PAGESIZE) == 0
    assert _pool_counts()[1] == spares


@pytest.mark.parametrize("cols", [COLS, 4 * COLS, 4 * COLS + 8],
                         ids=["one_piece", "four_pieces", "and_a_bit"])
def test_the_refill_thread_makes_two_calls_a_spare_at_most(
        small_map_min, monkeypatch, pool, native, cols):
    """Through the pool as the product runs it, pieces of a page: the
    thread's spares cost it one foreign call (a shard of one piece) or two
    whatever the length, the counters say so, and a shard that takes one
    reads back."""
    monkeypatch.setattr(store_mod, "_POOL_PIECE", NBYTES)
    s = MemStore(1 << 24)
    s.queue_transaction(Transaction().create_collection("c"))
    calls, spares = _pool_counts()
    _prime(pool, s, 8 * cols)
    pooled = _pooled()
    blob = _land(s, "c", "o", seed=41, cols=cols)
    assert _pooled() - pooled == 8 * cols
    _settle(pool)
    made = _pool_counts()[1] - spares
    assert made >= 2
    assert _pool_counts()[0] - calls == made * (1 if cols == COLS else 2)
    assert s.read_planar("c", "o") == blob and _mapped(s._colls["c"]["o"])


def _compiler_runs(monkeypatch):
    """Every ``subprocess.run`` from ``store``, with the thread it ran on."""
    ran, real = [], subprocess.run

    def run_(cmd, **kwargs):
        ran.append((threading.current_thread().name, list(cmd)))
        return real(cmd, **kwargs)

    monkeypatch.setattr(store_mod.subprocess, "run", run_)
    return ran


def test_the_build_is_the_refill_threads_and_is_kept(small_map_min,
                                                     monkeypatch, tmp_path):
    """The first start of a checkout's refill thread builds the library,
    on that thread and no other, under a temporary name that is gone
    afterwards; the second start, and another process's, find it by the
    source's hash and run no compiler."""
    if shutil.which("cc") is None:
        pytest.skip("no C compiler (cc) on this host")
    monkeypatch.setattr(store_mod.compile_cache, "native_dir",
                        lambda: str(tmp_path / "native"))
    ran = _compiler_runs(monkeypatch)
    s = MemStore(1 << 24)
    s.queue_transaction(Transaction().create_collection("c"))
    for start in range(2):
        pool = store_mod._Pool()
        monkeypatch.setattr(store_mod, "_POOL", pool)
        calls, spares = _pool_counts()
        try:
            _land(s, "c", f"first{start}")      # a miss: starts the thread
            assert not [r for r in ran if r[0] != "store-pool"]
            _prime(pool, s, NBYTES)
        finally:
            pool.stop()
        assert len(ran) == 1 and ran[0][0] == "store-pool"
        kept = os.listdir(tmp_path / "native")
        assert len(kept) == 1 and kept[0].startswith("populate_pieces-") \
            and kept[0].endswith(".so")
        # shards of one piece: one call a spare, either walk
        made = _pool_counts()[1] - spares
        assert made >= 1 and _pool_counts()[0] - calls == made
    assert ran[0][1][0] == shutil.which("cc")
    assert ran[0][1][-1] == store_mod._NATIVE_SRC


@pytest.mark.parametrize("why", ["no_compiler", "the_source_does_not_build",
                                 "no_source"])
def test_without_the_library_the_python_walk_serves(
        small_map_min, monkeypatch, tmp_path, caplog, pool, why):
    """A host without ``cc`` (an empty PATH), a source the compiler
    refuses, a checkout without the source: the thread walks the pieces in
    Python, says so in the log when it starts (once a process: the
    product never stops its thread), leaves nothing behind in the cache,
    and the counters read a call a piece."""
    monkeypatch.setattr(store_mod.compile_cache, "native_dir",
                        lambda: str(tmp_path / "native"))
    monkeypatch.setattr(store_mod, "_POOL_PIECE", NBYTES)
    ran = _compiler_runs(monkeypatch)
    if why == "no_compiler":
        monkeypatch.setenv("PATH", "")
    elif why == "no_source":
        monkeypatch.setattr(store_mod, "_NATIVE_SRC",
                            str(tmp_path / "gone.c"))
    else:
        if shutil.which("cc") is None:
            pytest.skip("no C compiler (cc) on this host")
        broken = tmp_path / "broken.c"
        broken.write_text("int populate_pieces(void *base { return 0; }\n")
        monkeypatch.setattr(store_mod, "_NATIVE_SRC", str(broken))
    s = MemStore(1 << 24)
    s.queue_transaction(Transaction().create_collection("c"))
    calls, spares = _pool_counts()
    with caplog.at_level(logging.WARNING, logger="ceph_tpu.store"):
        assert store_mod._native_walk() is None
        _prime(pool, s, 4 * NBYTES)             # starts the thread, twice
        pooled = _pooled()
        blob = _land(s, "c", "o", seed=43, cols=4 * COLS)
        _settle(pool)
        pool.stop()                     # every start has asked by now
    assert _pooled() - pooled == 4 * NBYTES
    assert s.read_planar("c", "o") == blob
    # asked here and by each of the thread's three starts
    said = [r for r in caplog.records if "no native walk" in r.getMessage()]
    assert len(said) == 4 and {r.name for r in said} == {"ceph_tpu.store"}
    assert len(ran) == (4 if why == "the_source_does_not_build" else 0)
    cache = tmp_path / "native"
    assert not cache.exists() or not os.listdir(cache)
    made = _pool_counts()[1] - spares
    assert made >= 2 and _pool_counts()[0] - calls == 5 * made


# ------------------------------------------------------ tiny clusters, CPU

K2M1 = {"plugin": "jerasure", "technique": "reed_sol_van",
        "k": "2", "m": "1"}
LRC = {"plugin": "lrc", "k": "4", "m": "2", "l": "3"}


def _held(cluster, names):
    """{name: [(osd id, coll, Obj)]} over every store of the cluster."""
    out = {n: [] for n in names}
    for i, osd in cluster.osds.items():
        for coll, objs in osd.store._colls.items():
            for n in names:
                if n in objs:
                    out[n].append((i, coll, objs[n]))
    return out


async def _pool(cluster, profile):
    client = await cluster.client()
    pool = await client.pool_create("ec", "erasure", pg_num=8,
                                    ec_profile=dict(profile))
    return client, pool, client.ioctx(pool)


def _primary(client, pool, name):
    pgid = client.objecter.object_pgid(pool, name)
    _, _, acting, primary = \
        client.objecter.osdmap.pg_to_up_acting_osds(pgid)
    return pgid, acting, primary


@contention_retry()
@pytest.mark.parametrize("profile,n,osds,shard,map_min", [
    (K2M1, 3, 3, 128 << 10, 4096), (LRC, 8, 8, 16 << 10, 4096),
    (K2M1, 3, 3, 128 << 10, None)],
    ids=["k2m1", "lrc_k4m2l3", "k2m1_under_the_threshold"])
def test_served_writes_land_every_shard_in_a_mapping(monkeypatch, profile, n,
                                                     osds, shard, map_min):
    """After N ``write_full``s populated = landed = direct, EXACTLY; the
    primary's shard and the replicas' alike are each the view of a
    mapping of its own; healthy and, with a holder killed, degraded reads
    return the payloads.  With ``_MAP_MIN`` as it ships the same shards
    (128 KiB) are ``bytearray``s and nothing is booked populated."""
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster

    k = int(profile["k"])
    if map_min is not None:
        monkeypatch.setattr(store_mod, "_MAP_MIN", map_min)

    async def scenario():
        cluster = await start_cluster(osds, config=_fast_config())
        try:
            client, pool, io = await _pool(cluster, profile)
            rng = np.random.default_rng(n)
            objs = {f"o{i}": rng.integers(0, 256, k * shard,
                                          dtype=np.uint8).tobytes()
                    for i in range(6)}
            before = _counters()
            await asyncio.gather(*(io.write_full(name, d, timeout=120)
                                   for name, d in objs.items()))
            landed = len(objs) * n * shard
            assert _grew(before) == (landed, landed,
                                     landed if map_min else 0)
            mappings = set()
            for name, holders in _held(cluster, objs).items():
                assert len(holders) == n
                for _osd, _c, o in holders:
                    assert len(o.data) == shard and o.layout == PLANAR
                    if map_min is None:
                        assert type(o.data) is bytearray
                    else:
                        assert _mapped(o)
                        mappings.add(id(o.data.obj))
            assert len(mappings) == (len(objs) * n if map_min else 0)
            for name, d in objs.items():
                assert await io.read(name, timeout=120) == d
            _pgid, acting, primary = _primary(client, pool, "o0")
            victim = next(o for o in acting if o != primary and o >= 0)
            await cluster.kill_osd(victim)
            await cluster.wait_down(victim)
            for name, d in objs.items():
                assert await io.read(name, timeout=120) == d
        finally:
            await cluster.stop()

    run(scenario())


@contention_retry()
@pytest.mark.parametrize("where", ["replica", "primary"])
def test_scrub_finds_rot_in_a_mapping(monkeypatch, where):
    """A bit flipped by ``debug_bitrot`` in a shard that lies in a
    mapping (in place; a neighbour in the same PG is not touched) is
    found by deep scrub and repaired, and the repair lands in a mapping
    again."""
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster

    monkeypatch.setattr(store_mod, "_MAP_MIN", 4096)

    async def scenario():
        cluster = await start_cluster(3, config=_fast_config())
        try:
            client, pool, io = await _pool(cluster, K2M1)
            payload = os.urandom(256 << 10)
            await io.write_full("obj", payload, timeout=120)
            pgid, acting, primary = _primary(client, pool, "obj")
            # a neighbour in the same PG, so in the same stores
            other = next(f"n{i}" for i in range(64) if client.objecter
                         .object_pgid(pool, f"n{i}") == pgid)
            await io.write_full(other, payload[::-1], timeout=120)
            victim = primary if where == "primary" else \
                next(o for o in acting if o != primary and o >= 0)
            vstore = cluster.osds[victim].store
            coll = _coll(pgid)
            good = vstore.read_planar(coll, "obj")
            beside = vstore.read_planar(coll, other)
            assert _mapped(vstore._colls[coll]["obj"])
            assert vstore._colls[coll]["obj"].data.obj is not \
                vstore._colls[coll][other].data.obj
            vstore.debug_bitrot(coll, "obj", 8 * 3 + 1)
            assert _mapped(vstore._colls[coll]["obj"])
            assert vstore.read_planar(coll, "obj") != good
            assert vstore.read_planar(coll, other) == beside
            st = cluster.osds[primary].pgs[pgid]
            report = await cluster.osds[primary].scrub_pg(st)
            assert report["inconsistent"] == ["obj"]
            assert report["repaired"] == ["obj"]
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline and \
                    vstore.read_planar(coll, "obj") != good:
                await asyncio.sleep(0.05)
            assert vstore.read_planar(coll, "obj") == good
            assert _mapped(vstore._colls[coll]["obj"])
            assert await io.read("obj", timeout=60) == payload
            assert await io.read(other, timeout=60) == payload[::-1]
        finally:
            await cluster.stop()

    run(scenario())


@contention_retry()
def test_a_sub_write_that_arrives_twice_lands_the_same_object(monkeypatch):
    """The messenger delivers at least once: with every frame of every
    daemon written twice (``chaos_net_dup`` 1.0) each sub-write is
    applied twice; the second landing takes the object's place and the
    first one's mapping goes: the same object, the same ``_used``, and
    the payload reads back."""
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster

    monkeypatch.setattr(store_mod, "_MAP_MIN", 4096)

    cfg = _fast_config()
    cfg.chaos_seed = 43
    cfg.chaos_net_dup = 1.0
    shard = 128 << 10

    async def scenario():
        cluster = await start_cluster(3, config=cfg)
        try:
            client, pool, io = await _pool(cluster, K2M1)
            objs = {f"o{i}": os.urandom(2 * shard) for i in range(4)}
            before = _counters()
            for name, d in objs.items():
                await io.write_full(name, d, timeout=120)
            # every remote shard lands twice, the primary's once; the
            # second copies land after the op was acknowledged
            want = (len(objs) * 5 * shard,) * 3
            deadline = asyncio.get_event_loop().time() + 10
            while _grew(before) != want and \
                    asyncio.get_event_loop().time() < deadline:
                await asyncio.sleep(0.02)
            assert _grew(before) == want
            for name, holders in _held(cluster, objs).items():
                assert len(holders) == 3
                assert all(_mapped(o) and len(o.data) == shard
                           for _i, _c, o in holders)
            for osd in cluster.osds.values():
                assert osd.store.statfs()[1] == sum(
                    len(o.data) for objs_ in osd.store._colls.values()
                    for o in objs_.values())
            for name, d in objs.items():
                assert await io.read(name, timeout=120) == d
        finally:
            await cluster.stop()

    run(scenario())


# ------------------------------------------------ the metric and its file


CELLS = ["k2m1_write_4m_t16", "k2m1_write_64k_t16", "k4m2_write_4m_t16",
         "k8m4_write_4m_t16", "lrc_k4m2l3_write_4m_t16",
         "shec_k6m4c3_write_4m_t16", "cauchy_k4m2_write_4m_t16"]
# name: (the reader's numerator, denominator, scale; a window's growth
# and what it reads; the growth of a program without the numerator)
_METRICS = {
    "store_populated_share.write": (
        ("store_planar_populated_bytes", "store_planar_write_bytes", 100),
        ({"store_planar_write_bytes": 6_000_000_000,
          "store_planar_populated_bytes": 4_500_000_000}, 75.0),
        {"store_planar_write_bytes": 1_000_000}),
    "store_populate_ms_per_op.write": (
        ("store_populate_ns", "ec_coalesced_ops", 1e-06),
        ({"ec_coalesced_ops": 3000, "store_populate_ns": 13_500_000_000},
         4.5),
        {"ec_coalesced_ops": 3000}),
    # PR 45: 6 GB landed of which 5.4 GB in spares of the pool's; 21 s
    # inside the refill thread's map-and-touch calls over 3000 ops
    "store_pooled_share.write": (
        ("store_planar_pooled_bytes", "store_planar_write_bytes", 100),
        ({"store_planar_write_bytes": 6_000_000_000,
          "store_planar_populated_bytes": 6_000_000_000,
          "store_planar_pooled_bytes": 5_400_000_000}, 90.0),
        {"store_planar_write_bytes": 1_000_000,
         "store_planar_populated_bytes": 1_000_000}),
    "store_pool_touch_ms_per_op.write": (
        ("store_pool_touch_ns", "ec_coalesced_ops", 1e-06),
        ({"ec_coalesced_ops": 3000, "store_pool_touch_ns": 21_000_000_000},
         7.0),
        {"ec_coalesced_ops": 3000}),
}


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("name", list(_METRICS))
def test_the_metric_files_read_the_hand_worked_values(name, cell_name):
    """6 GB of planar shard bytes landed of which 4.5 GB in populated
    mappings: 75 %; 13.5 s inside the mappings' ``mmap`` calls over 3000
    ops: 4.5 ms an op; through the accepted ``counter_ratio`` reader; a
    program without the counter (the parent commit) reads 0.0 where the
    denominator grew and nothing where it did not, and nothing raises."""
    from benchmark.harness import layers
    from benchmark.harness.loader import load_cell

    (num, den, scale), (grown, reads), without = _METRICS[name]
    cell = load_cell(cell_name)
    reader = cell.per_layer[name]
    assert (reader["kind"], reader["numerator"], reader["denominator"],
            reader["scale"], reader["layer"], reader["moves"]) == \
        ("counter_ratio", num, den, scale, "fan-out and store",
         "write_MBps")
    for growth, want in ((grown, pytest.approx(reads)), (without, 0.0),
                         ({}, None)):
        readings = layers.Readings(
            config=cell.config, device_kind="TPU v5 lite", attribution={},
            counters=growth, slice_counters={}, trace=None)
        assert layers.read_metric(name, reader, readings) == want


@pytest.mark.parametrize("cell_name", CELLS)
def test_calls_a_spare_reads_which_walk_served(cell_name):
    """2500 spares in a window: 5000 foreign calls with the C walk read
    2.0, 22500 with the Python walk over 2 MiB shards 9.0; a program
    without the two counters (the parent) grows neither and the accepted
    reader reports nothing, so the parent's line leaves the metric out;
    and so it would where the thread never starts, which is why the
    64 KiB cell does not list it."""
    from benchmark.harness import layers
    from benchmark.harness.loader import load_cell

    name = "store_pool_calls_per_spare.write"
    cell = load_cell(cell_name)
    if cell_name == "k2m1_write_64k_t16":
        assert name not in cell.per_layer
        return
    reader = cell.per_layer[name]
    assert (reader["kind"], reader["numerator"], reader["denominator"],
            reader["scale"], reader["layer"], reader["moves"],
            reader["unit"]) == \
        ("counter_ratio", "store_pool_calls", "store_pool_spares", 1,
         "fan-out and store", "write_MBps", "calls")
    for growth, want in (
            ({"store_pool_calls": 5000, "store_pool_spares": 2500}, 2.0),
            ({"store_pool_calls": 22500, "store_pool_spares": 2500}, 9.0),
            ({"ec_coalesced_ops": 3000,
              "store_planar_write_bytes": 1_000_000}, None),
            ({}, None)):
        readings = layers.Readings(
            config=cell.config, device_kind="TPU v5 lite", attribution={},
            counters=growth, slice_counters={}, trace=None)
        assert layers.read_metric(name, reader, readings) == want
