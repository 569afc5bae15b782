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
"""

import asyncio
import gc
import mmap
import os
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
    64 KiB cell's 32 KiB do not."""
    assert store_mod._MAP_MIN == 256 << 10


@pytest.mark.parametrize("how", ["remove", "remove_collection",
                                 "overwritten", "resized", "rewritten"])
def test_a_mapping_goes_with_its_object(small_map_min, how):
    """Whatever takes the object's bytes away takes the mapping with
    them, at once (no collector has to run), and leaves the neighbours'
    alone: ``_used`` is what is mapped."""
    s = MemStore()
    s.queue_transaction(Transaction().create_collection("c"))
    _land(s, "c", "o", seed=20)
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
         "shec_k6m4c3_write_4m_t16"]
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
