"""One copy from the tick's planes to rest (PR 32).

A planar write that says of itself that it replaces the whole shard
(``plane_off`` 0, a window of ``total_cols`` columns) lands in the store
with ONE copy, the store's own; any other window takes the splice.  The
fan-out hands the wire and the store flat read-only views of the tick's
planes.  What is pinned here: the one-copy path leaves what the splice
leaves, the store owns what it keeps, the views go out of band and
arrive with their length, and the two ``KERNELS`` counters say which
path a write took.
"""

import asyncio
import os
import pickle

import numpy as np
import pytest

from ceph_tpu.cluster import messages as M
from ceph_tpu.cluster import messenger as msgr
from ceph_tpu.cluster.backend_ec import _shard_bytes
from ceph_tpu.cluster.store import MemStore, Transaction
from ceph_tpu.ec import planar_store
from ceph_tpu.utils.perf import KERNELS
from tests._flaky import contention_retry

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS", "") == "",
    reason="run under JAX_PLATFORMS=cpu like the tier-1 lane")

COLS = 512                      # the new shard: 8 x 512 = 4096 bytes


def run(coro):
    return asyncio.run(coro)


def _planes(cols: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, (8, cols), dtype=np.uint8)


def _counters():
    return (KERNELS.get("store_planar_write_bytes"),
            KERNELS.get("store_planar_direct_bytes"))


def _as(kind: str, planes: np.ndarray):
    """The window as each caller hands it over."""
    if kind == "bytes":
        return planes.tobytes()
    if kind == "memoryview":        # a frame's view (messenger._decode_oob)
        return memoryview(bytearray(planes.tobytes())).toreadonly()
    if kind == "bytearray":
        return bytearray(planes.tobytes())
    assert kind == "array"          # the tick's own block
    return np.ascontiguousarray(planes)


def _store_with(pre: str):
    """A store holding object ``o`` in the named pre-state, another
    object beside it, and the (8, cols) planes the splice would start
    from (None: nothing to keep)."""
    s = MemStore(device_bytes=1 << 20)
    s.queue_transaction(Transaction().create_collection("c")
                        .write("c", "other", 0, b"x" * 100))
    if pre == "new":
        return s, None
    if pre == "bytes_at_rest":
        raw = os.urandom(1024)
        s.queue_transaction(Transaction().write("c", "o", 0, raw))
        return s, planar_store.shard_to_planes(raw)
    cols = {"longer_planar": COLS + 128, "shorter_planar": COLS - 128}[pre]
    old = _planes(cols, seed=1)
    s.queue_transaction(Transaction().write_planar(
        "c", "o", 0, old.tobytes(), cols))
    return s, old


# --------------------------------------------------- (a) the store's door


@pytest.mark.parametrize("kind", ["bytes", "memoryview", "array"])
@pytest.mark.parametrize("pre", ["new", "longer_planar", "shorter_planar",
                                 "bytes_at_rest"])
def test_full_shard_write_leaves_what_the_splice_leaves(pre, kind):
    """Object bytes, layout, version, ``_used``, ``read`` and
    ``read_planar`` after a full-shard ``write_planar`` equal what
    ``splice_columns`` over the old object gives, whatever the object
    was before and whatever kind of buffer carried the window; all of
    its bytes are booked as direct."""
    s, old = _store_with(pre)
    window = _planes(COLS, seed=2)
    want = planar_store.planes_to_blob(
        planar_store.splice_columns(old, 0, window, COLS))
    assert want == window.tobytes()     # nothing of the old one survives
    version = s.get_version("c", "o")
    wrote, direct = _counters()
    s.queue_transaction(
        Transaction().write_planar("c", "o", 0, _as(kind, window), COLS))
    o = s._colls["c"]["o"]
    assert type(o.data) is bytearray and bytes(o.data) == want
    assert o.layout == planar_store.LAYOUT_PLANAR
    assert s.object_layout("c", "o") == planar_store.LAYOUT_PLANAR
    assert s.get_version("c", "o") == version + 1
    assert s.stat("c", "o") == 8 * COLS
    assert s.statfs() == (1 << 20, 100 + 8 * COLS)
    assert s.read_planar("c", "o") == want
    assert s.read("c", "o") == planar_store.planes_to_shard(window)
    assert _counters() == (wrote + 8 * COLS, direct + 8 * COLS)


@pytest.mark.parametrize("kind", ["bytes", "memoryview", "array"])
@pytest.mark.parametrize("shape", ["append", "middle", "overshoot",
                                   "short_of_total"])
def test_partial_window_still_splices(shape, kind):
    """A window that is not the whole shard — an append, an RMW in the
    middle, one that overshoots ``total_cols`` and is clipped, one at
    column 0 that is shorter than the shard — lands by the splice as
    before, and none of its bytes is booked as direct."""
    s, old = _store_with("shorter_planar")          # COLS - 128 columns
    off, wc, total = {"append": (COLS - 128, 128, COLS),
                      "middle": (64, 128, COLS - 128),
                      "overshoot": (0, COLS, COLS - 64),
                      "short_of_total": (0, 64, COLS - 128)}[shape]
    window = _planes(wc, seed=3)
    want = planar_store.splice_columns(old, off, window, total)
    wrote, direct = _counters()
    s.queue_transaction(
        Transaction().write_planar("c", "o", off, _as(kind, window), total))
    assert s.read_planar("c", "o") == want.tobytes()
    assert s.read("c", "o") == planar_store.planes_to_shard(want)
    assert s.statfs() == (1 << 20, 100 + 8 * total)
    assert _counters() == (wrote + 8 * wc, direct)


def test_full_shard_write_is_refused_whole_when_the_store_is_full():
    """ENOSPC before any byte lands: the one-copy path sits behind
    ``_check_capacity`` like every other write."""
    s = MemStore(device_bytes=8 * COLS + 50)
    s.queue_transaction(Transaction().create_collection("c")
                        .write("c", "other", 0, b"x" * 100))
    with pytest.raises(OSError) as ei:
        s.queue_transaction(Transaction().write_planar(
            "c", "o", 0, _planes(COLS, 4).tobytes(), COLS))
    assert ei.value.errno == 28
    assert s.stat("c", "o") is None and s.statfs()[1] == 100


@pytest.mark.parametrize("store", ["filestore", "bluestore"])
def test_a_view_still_reaches_the_journal(store, tmp_path):
    """``Transaction.write_planar`` keeps the view it was given, and a
    ``memoryview`` does not pickle: ``encode`` hands the write-ahead
    journals ``bytes``, and the object survives a crash-bounce."""
    from ceph_tpu.cluster.bluestore import BlueStore
    from ceph_tpu.cluster.filestore import FileStore

    def make():
        if store == "filestore":
            return FileStore(str(tmp_path / "fs"), checkpoint_every=2048)
        return BlueStore(str(tmp_path / "bs"), size=8 << 20,
                         checkpoint_every=10_000)

    window = _planes(COLS, seed=5)
    s = make()
    s.mount()
    txn = Transaction().create_collection("c").write_planar(
        "c", "o", 0, _shard_bytes(window), COLS)
    assert type(txn.ops[1][4]) is memoryview
    assert Transaction.decode(txn.encode()).ops[1][4] == window.tobytes()
    s.queue_transaction(txn)
    s2 = make()                 # crash: no umount, the journal replays
    s2.mount()
    assert s2.object_layout("c", "o") == planar_store.LAYOUT_PLANAR
    assert s2.read_planar("c", "o") == window.tobytes()
    s2.umount()


# ------------------------------------------- (b) the store owns its copy


@pytest.mark.parametrize("kind", ["bytearray", "memoryview", "array"])
def test_the_store_owns_what_it_keeps(kind):
    """The source changes after the commit (a caller's ``bytearray``,
    the frame under a view, the tick's planes) and is dropped: the
    object does not change, and nothing of the store views the source."""
    window = _planes(COLS, seed=6)
    want = window.tobytes()
    src = _as(kind, window)
    s = MemStore()
    s.queue_transaction(Transaction().create_collection("c")
                        .write_planar("c", "o", 0, src, COLS))
    owner = src.obj if kind == "memoryview" else src
    if kind == "memoryview":
        src.release()
    del src
    if kind == "array":
        owner[:] = 0
    else:
        owner[:] = bytes(len(owner))
        owner.extend(b"\0")     # BufferError while anything views it
    del owner
    assert bytes(s._colls["c"]["o"].data) == want
    s._colls["c"]["o"].data[0] ^= 0xFF        # and it is the store's to write
    assert s.read_planar("c", "o") != want


# --------------------------------------------------- (c) the sender's end


def _round_trip(msg):
    """``msg`` as the messenger frames it and the receiver unpickles
    it: (the message as it arrives, out-of-band bytes of the frame)."""
    before = KERNELS.get("msgr_oob_bytes")
    payload, bufs = msgr._encode(msg)
    grew = KERNELS.get("msgr_oob_bytes") - before
    assert grew == sum(len(b) for b in bufs)
    if not bufs:
        return pickle.loads(payload), 0
    body = bytearray(b"".join(msgr._frame_parts(None, (payload, bufs))))
    assert body[4] == msgr._FT_MSG_OOB
    return msgr._decode_oob(memoryview(body)[5:].toreadonly()), grew


@pytest.mark.parametrize("cols,rows", [
    (msgr._OOB_MIN // 8, 8), (2 * msgr._OOB_MIN // 8, 8),
    (msgr._OOB_MIN // 16, 8), (2 * msgr._OOB_MIN, 1)],
    ids=["planes_64k", "planes_128k", "planes_32k_in_band", "byte_row"])
def test_a_view_of_the_planes_rides_out_of_band(cols, rows):
    """A sub-write whose ``data`` is ``_shard_bytes`` of one shard of a
    tick's (n, 8, cw) block (or of a byte row): flat, read-only, a view
    and not a copy; at 64 KiB and over it leaves the pickle
    (``msgr_oob_bytes`` grows by its length) and arrives as a view of
    the frame with ``len()`` the shard's bytes; under it, it is pickled
    in band as ``bytes``; either way it lands in a store identical to
    the ``tobytes()`` form."""
    block = np.random.default_rng(7).integers(
        0, 256, (3, rows, cols), dtype=np.uint8)
    if rows == 1:
        block = block.reshape(3, cols)
    shard = block[1]
    nbytes = shard.size
    view = _shard_bytes(shard)
    assert type(view) is memoryview and view.readonly and view.ndim == 1
    assert len(view) == nbytes and view == shard.tobytes()
    assert np.shares_memory(np.frombuffer(view, dtype=np.uint8), block)
    assert block.flags.writeable        # the tick's block is left alone
    got, grew = _round_trip(M.MOSDECSubOpWrite(
        shard=1, data=view, layout=planar_store.LAYOUT_PLANAR))
    assert len(got.data) == nbytes and got.data == shard.tobytes()
    if nbytes >= msgr._OOB_MIN:
        assert grew == nbytes
        assert type(got.data) is memoryview and got.data.readonly
    else:
        assert grew == 0 and type(got.data) is bytes
    if rows == 8:
        landed = []
        for data in (got.data, shard.tobytes()):
            s = MemStore()
            s.queue_transaction(Transaction().create_collection("c")
                                .write_planar("c", "o", 0, data, cols))
            landed.append(s.read_planar("c", "o"))
        assert landed[0] == landed[1] == shard.tobytes()


def test_a_writable_array_is_copied_in_band():
    """What could change under the replay buffer never leaves the
    pickle: a writable view of the same planes is copied into it, and
    a later write to the planes is not seen by the frame."""
    planes = _planes(2 * msgr._OOB_MIN // 8, seed=8)
    want = planes.tobytes()
    before = KERNELS.get("msgr_oob_bytes")
    payload, bufs = msgr._encode(M.MOSDECSubOpWrite(
        shard=0, data=memoryview(planes).cast("B")))
    assert not bufs and KERNELS.get("msgr_oob_bytes") == before
    assert len(payload) > planes.size
    planes[:] = 0
    assert pickle.loads(payload).data == want


# ------------------------------------------------- (d) the served path


@contention_retry()
@pytest.mark.parametrize("k,m,osds", [(2, 1, 3), (4, 2, 6)])
def test_served_writes_take_the_one_copy_path(k, m, osds):
    """EC ``write_full`` on the planar path: every shard byte of whole
    objects is booked direct (``store_planar_direct_bytes`` grows as
    ``store_planar_write_bytes``), the sub-writes' views go out of band,
    each holder's object is the store's own ``bytearray``, the object
    reads back healthy and, with one holder killed, degraded; an RMW
    after it books bytes that are not direct."""
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster

    async def scenario():
        cluster = await start_cluster(osds, config=_fast_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "ec", "erasure", pg_num=4,
                ec_profile={"plugin": "jerasure",
                            "technique": "reed_sol_van",
                            "k": str(k), "m": str(m)})
            io = client.ioctx(pool)
            rng = np.random.default_rng(k)
            size = k * 128 << 10            # 128 KiB shards: out of band
            objs = {f"o{i}": rng.integers(0, 256, size,
                                          dtype=np.uint8).tobytes()
                    for i in range(4)}
            wrote, direct = _counters()
            oob = KERNELS.get("msgr_oob_bytes")
            await asyncio.gather(*(io.write_full(n, d, timeout=120)
                                   for n, d in objs.items()))
            wrote1, direct1 = _counters()
            shard_bytes = len(objs) * (k + m) * (128 << 10)
            assert wrote1 - wrote == direct1 - direct == shard_bytes
            # the client ops and the k+m-1 remote shards of each
            assert KERNELS.get("msgr_oob_bytes") - oob >= \
                len(objs) * (size + (k + m - 1) * (128 << 10))
            held = [o for osd in cluster.osds.values()
                    for objs_ in osd.store._colls.values()
                    for n, o in objs_.items() if n in objs]
            assert len(held) == len(objs) * (k + m)
            assert all(type(o.data) is bytearray and
                       len(o.data) == 128 << 10 and
                       o.layout == planar_store.LAYOUT_PLANAR
                       for o in held)
            for n, d in objs.items():
                assert await io.read(n, timeout=120) == d
            # an RMW in the middle of o0: partial windows, spliced
            patch = os.urandom(4096)
            await io.write("o0", patch, 8192)
            objs["o0"] = objs["o0"][:8192] + patch + \
                objs["o0"][8192 + 4096:]
            wrote2, direct2 = _counters()
            assert wrote2 > wrote1 and direct2 == direct1
            pgid = client.objecter.object_pgid(pool, "o0")
            _, _, acting, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)
            victim = next(o for o in acting if o != primary and o >= 0)
            await cluster.kill_osd(victim)
            await cluster.wait_down(victim)
            for n, d in objs.items():
                assert await io.read(n, timeout=120) == d
        finally:
            await cluster.stop()

    run(scenario())


# ------------------------------------------------ the metric and its file


@pytest.mark.parametrize("cell_name", [
    "k2m1_write_4m_t16", "k2m1_write_64k_t16", "k4m2_write_4m_t16",
    "k8m4_write_4m_t16"])
def test_store_direct_share_reads_the_hand_worked_value(cell_name):
    """6 GB of planar shard bytes landed of which 4.5 GB direct: 75 %,
    through the accepted ``counter_ratio`` reader; a program without
    the counters (the parent commit) reads nothing and nothing raises."""
    from benchmark.harness import layers
    from benchmark.harness.loader import load_cell

    cell = load_cell(cell_name)
    name = "store_direct_share.write"
    for growth, want in (
            ({"store_planar_write_bytes": 6_000_000_000,
              "store_planar_direct_bytes": 4_500_000_000},
             pytest.approx(75.0)),
            ({"store_planar_write_bytes": 1_000_000}, 0.0),
            ({}, None)):
        readings = layers.Readings(
            config=cell.config, device_kind="TPU v5 lite", attribution={},
            counters=growth, slice_counters={}, trace=None)
        assert layers.read_metric(name, cell.per_layer[name],
                                  readings) == want
