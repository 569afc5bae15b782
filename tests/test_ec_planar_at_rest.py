"""Planar at-rest shards (round 19): the tier-1 bit-exactness gate.

The contract under test: with ``osd_ec_planar_at_rest=1`` EC shards
LIVE as packed bit-plane matrices — in the store, on the wire, and
entering the kernels — with ZERO layout conversions on the
steady-state write/read/RMW/recovery/deep-scrub paths (the
``ec_planar_unseamed_conversions`` counter is pinned to 0), while
every client-visible byte, shard crc, and scrub verdict stays
bit-identical to the ``osd_ec_planar_at_rest=0`` byte anchor.
"""

import asyncio
import os

import numpy as np
import pytest

from ceph_tpu.cluster.pg import _coll
from ceph_tpu.cluster.store import MemStore, Transaction
from ceph_tpu.ec import planar_store
from ceph_tpu.ec import stripe as stripemod
from ceph_tpu.ec.registry import factory
from ceph_tpu.ops import crc32c as crcmod
from ceph_tpu.ops.profiling import KERNELS
from tests._flaky import contention_retry

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS", "") == "",
    reason="run under JAX_PLATFORMS=cpu like the tier-1 lane")


def run(coro):
    return asyncio.run(coro)


def _rng(seed=7):
    return np.random.default_rng(seed)


def _profile(k, m):
    return {"plugin": "jerasure", "technique": "reed_sol_van",
            "k": str(k), "m": str(m)}


def _unseamed():
    return KERNELS.get("ec_planar_unseamed_conversions")


# ------------------------------------------------------- layer 0: helpers


def test_planar_blob_roundtrip_and_crc_identity():
    """shard bytes <-> plane matrix <-> serialized blob round-trips,
    and the plane-major crc equals the byte crc for BOTH seeds the
    data plane uses (cumulative hinfo ~0 and append-delta 0)."""
    r = _rng()
    for nbytes in (8, 64, 4096, 8 * 1237):
        shard = r.integers(0, 256, nbytes, dtype=np.uint8).tobytes()
        planes = planar_store.shard_to_planes(shard)
        assert planes.shape == (8, nbytes // 8)
        assert planar_store.planes_to_shard(planes) == shard
        blob = planar_store.planes_to_blob(planes)
        assert len(blob) == nbytes  # layout is accounting-free
        assert np.array_equal(planar_store.blob_to_planes(blob), planes)
        for seed in (0xFFFFFFFF, 0):
            assert crcmod.crc32c_planar_rows(planes, seed=seed)[0] == \
                crcmod.crc32c(seed, shard)


def test_splice_columns_matches_byte_rmw():
    """The store's plane-window splice == the byte path's
    write-at-offset + truncate, for overwrite, append, and extend."""
    r = _rng(11)
    old = r.integers(0, 256, 2048, dtype=np.uint8).tobytes()
    for (off, wlen, total) in ((1024, 512, 2048),   # mid overwrite
                               (2048, 1024, 3072),  # append-extend
                               (0, 2048, 1024)):    # rewrite + shrink
        win = r.integers(0, 256, wlen, dtype=np.uint8).tobytes()
        ref = bytearray(old)
        if len(ref) < total:
            ref.extend(b"\0" * (total - len(ref)))
        ref[off:off + wlen] = win
        ref = bytes(ref[:total])
        merged = planar_store.splice_columns(
            planar_store.shard_to_planes(old), off // 8,
            planar_store.shard_to_planes(win), total // 8)
        assert planar_store.planes_to_shard(merged) == ref


# ------------------------------------- layer 1: stripe-level bit-exactness


@pytest.mark.parametrize("k,m", [(2, 1), (3, 2)])
def test_stripe_planar_vs_byte_anchor_bit_exact(k, m):
    """encode/decode/reencode in the plane domain produce the same
    shard bytes, shard crcs, and logical bytes as the byte anchors."""
    codec = factory(_profile(k, m))
    sinfo = stripemod.StripeInfo(k, 64)
    assert stripemod.planar_at_rest_ok(codec, sinfo.chunk_size)
    r = _rng(13)
    datas = [r.integers(0, 256, n, dtype=np.uint8).tobytes()
             for n in (k * 64, 5 * k * 64, 3 * k * 64 - 17)]
    byte_out = stripemod.encode_stripes_multi(
        codec, sinfo, datas, want_crcs=[True] * len(datas))
    plane_out = stripemod.encode_planes_multi(
        codec, sinfo, datas, want_crcs=[True] * len(datas))
    for (bs, bc), (ps, pc), data in zip(byte_out, plane_out, datas):
        assert pc == bc  # plane-major crcs == byte-anchor crcs
        shards = {}
        for i in range(k + m):
            assert planar_store.planes_to_blob(
                planar_store.shard_to_planes(bs[i].tobytes())) == \
                ps[i].tobytes()
            shards[i] = ps[i]
        # decode with an erasure, planes in -> logical bytes out
        alive = {i: s for i, s in shards.items() if i != 1}
        [logical] = stripemod.decode_planes_multi(
            codec, sinfo, [(alive, len(data))])
        assert logical == data
        # recovery rebuild: full plane matrices back, byte-identical
        [rebuilt] = stripemod.reencode_planes_multi(
            codec, sinfo, [(alive, len(data))])
        for i in range(k + m):
            assert rebuilt[i].tobytes() == ps[i].tobytes()


# ------------------------------------------- layer 2: the store substrate


def test_memstore_planar_accounting_and_enospc_parity():
    """Planar objects count their TRUE plane bytes (== logical bytes:
    the layout is accounting-free) against _used/statfs, and a planar
    store fills to capacity with the same ENOSPC + full-flag behavior
    as the byte anchor."""
    cap = 1 << 14
    outcomes = []
    for planar in (False, True):
        s = MemStore(device_bytes=cap)
        s.queue_transaction(Transaction().create_collection("c"))
        blob = bytes(range(256)) * 16  # 4096 B
        for i in range(4):
            txn = Transaction()
            if planar:
                txn.write_planar(
                    "c", f"o{i}", 0,
                    planar_store.planes_to_blob(
                        planar_store.shard_to_planes(blob)),
                    len(blob) // 8)
            else:
                txn.write("c", f"o{i}", 0, blob)
            s.queue_transaction(txn)
        used, total = s.statfs()
        assert (used, total) == (cap, cap)
        txn = Transaction()
        if planar:
            txn.write_planar("c", "overflow", 0, blob, len(blob) // 8)
        else:
            txn.write("c", "overflow", 0, blob)
        with pytest.raises(OSError) as ei:
            s.queue_transaction(txn)
        outcomes.append((used, ei.value.errno, str(ei.value)))
        if planar:
            assert all(s.object_layout("c", f"o{i}")
                       == planar_store.LAYOUT_PLANAR for i in range(4))
    assert outcomes[0] == outcomes[1]  # byte anchor == planar, exactly


def test_filestore_checkpoint_and_journal_bounce_planar(tmp_path):
    """Planar objects survive a FileStore crash-bounce bit-identical:
    once via checkpoint, once via journal replay alone."""
    from ceph_tpu.cluster.filestore import FileStore

    blob = planar_store.planes_to_blob(
        planar_store.shard_to_planes(bytes(range(256)) * 8))
    for checkpoint_every, tag in ((1, "ckpt"), (2048, "journal")):
        path = str(tmp_path / tag)
        s = FileStore(path, checkpoint_every=checkpoint_every)
        s.mount()
        s.queue_transaction(
            Transaction().create_collection("c")
            .write_planar("c", "obj", 0, blob, len(blob) // 8)
            .setattr("c", "obj", "hinfo_crc", b"123"))
        # crash: NO umount — the rebouncing store must replay
        s2 = FileStore(path)
        s2.mount()
        assert s2.object_layout("c", "obj") == planar_store.LAYOUT_PLANAR
        assert s2.read_planar("c", "obj") == blob
        assert s2.getattr("c", "obj", "hinfo_crc") == b"123"
        s2.umount()


def test_bluestore_wal_bounce_and_bitrot_planar(tmp_path):
    """Planar objects survive a BlueStore WAL crash-bounce
    bit-identical, and the per-block csum still detects bitrot under
    the planar blob."""
    from ceph_tpu.cluster.bluestore import BlueStore

    blob = planar_store.planes_to_blob(
        planar_store.shard_to_planes(bytes(range(256)) * 32))
    path = str(tmp_path / "bs")
    s = BlueStore(path, size=8 << 20, checkpoint_every=10_000)
    s.mount()
    s.queue_transaction(
        Transaction().create_collection("c")
        .write_planar("c", "obj", 0, blob, len(blob) // 8))
    # crash: no umount — WAL replay must rebuild the planar onode
    s2 = BlueStore(path, size=8 << 20)
    s2.mount()
    assert s2.object_layout("c", "obj") == planar_store.LAYOUT_PLANAR
    assert s2.read_planar("c", "obj") == blob
    s2.debug_bitrot("c", "obj", bit=41)
    with pytest.raises(IOError):
        s2.read_planar("c", "obj")
    s2.umount()


# ------------------------------------------ layer 3: the cluster-level A/B

PROFILE = _profile(2, 1)


async def _cluster_workload(planar: int):
    """One full shard life-cycle (write_full, append, RMW, ranged +
    full reads, deep scrub) on a 3-OSD cluster; returns every
    client-visible byte, per-member shard crc, scrub verdict, and the
    planar counter deltas."""
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster

    cfg = _fast_config()
    cfg.osd_ec_planar_at_rest = planar
    cluster = await start_cluster(3, config=cfg)
    out = {}
    try:
        client = await cluster.client()
        pool = await client.pool_create("p", "erasure", pg_num=4,
                                        ec_profile=PROFILE)
        io = client.ioctx(pool)
        base = _unseamed()
        await io.write_full("a", bytes(range(256)) * 40, timeout=60)
        await io.append("a", b"tail-" * 100)
        await io.write("a", b"X" * 777, 1000)          # mid-object RMW
        await io.write_full("b", b"hello world" * 9)
        await io.truncate("b", 37)
        out["reads"] = (await io.read("a"), await io.read("b"),
                        await io.read("a", 500, 2000))
        # per-member shard state: crc + layout, keyed by (oid, shard)
        state = {}
        layouts = set()
        for osd in cluster.osds.values():
            for coll in list(osd.store._colls):
                for oid in ("a", "b"):
                    if oid in osd.store._colls[coll]:
                        sh = osd.store.getattr(coll, oid, "shard")
                        state[(oid, sh)] = osd.store.getattr(
                            coll, oid, "hinfo_crc")
                        layouts.add(osd.store.object_layout(coll, oid))
        out["shard_crcs"] = state
        out["layouts"] = layouts
        # deep scrub the PG holding "a": verdict must be clean
        pgid = client.objecter.object_pgid(pool, "a")
        _, _, _, primary = \
            client.objecter.osdmap.pg_to_up_acting_osds(pgid)
        st = cluster.osds[primary].pgs[pgid]
        report = await cluster.osds[primary].scrub_pg(st)
        out["scrub"] = (sorted(report["inconsistent"]),
                        sorted(report["repaired"]))
        out["unseamed_delta"] = _unseamed() - base
        out["ingest"] = KERNELS.get("ec_planar_ingest_conversions")
        out["egress"] = KERNELS.get("ec_planar_egress_conversions")
    finally:
        await cluster.stop()
    return out


@contention_retry()
def test_cluster_planar_vs_byte_anchor_bit_exact():
    """THE round-19 gate: the same workload under planar=1 and the
    byte anchor yields byte-identical client reads, identical shard
    crcs, and identical (clean) scrub verdicts — while the planar run
    stores every EC object as planes and books ZERO unseamed
    conversions (write, append, RMW, ranged read, deep scrub all
    steady-state conversion-free)."""
    async def scenario():
        p = await _cluster_workload(1)
        b = await _cluster_workload(0)
        assert p["reads"] == b["reads"]
        assert p["shard_crcs"] == b["shard_crcs"]
        assert p["scrub"] == b["scrub"] == ([], [])
        assert p["layouts"] == {planar_store.LAYOUT_PLANAR}
        assert b["layouts"] == {None}
        assert p["unseamed_delta"] == 0, \
            f"unseamed conversions on the steady-state path: " \
            f"{p['unseamed_delta']}"
        assert p["ingest"] > 0 and p["egress"] > 0

    run(scenario())


@contention_retry()
def test_cluster_planar_scrub_repair_and_recovery():
    """Corrupt one member's planar shard: deep scrub detects it over
    plane-major rows, the recovery rebuild re-encodes IN the plane
    domain, the repaired shard lands planar bit-identical — and the
    whole detect/rebuild/land cycle books zero unseamed
    conversions."""
    from ceph_tpu.cluster.vstart import start_cluster

    async def scenario():
        cluster = await start_cluster(3)   # vstart default: planar on
        try:
            client = await cluster.client()
            pool = await client.pool_create("sp", "erasure", pg_num=4,
                                            ec_profile=PROFILE)
            io = client.ioctx(pool)
            payload = b"planar-scrub" * 300
            await io.write_full("obj", payload, timeout=60)
            base = _unseamed()
            pgid = client.objecter.object_pgid(pool, "obj")
            _, _, acting, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)
            victim = next(o for o in acting
                          if o >= 0 and o != primary
                          and o in cluster.osds)
            vstore = cluster.osds[victim].store
            assert vstore.object_layout(_coll(pgid), "obj") \
                == planar_store.LAYOUT_PLANAR
            before = bytes(vstore.read_planar(_coll(pgid), "obj"))
            vstore._colls[_coll(pgid)]["obj"].data[3] ^= 0xFF
            st = cluster.osds[primary].pgs[pgid]
            report = await cluster.osds[primary].scrub_pg(st)
            assert report["inconsistent"] == ["obj"]
            assert report["repaired"] == ["obj"]
            # repair lands asynchronously on the victim: converge-poll
            # against a wall deadline instead of a fixed sleep
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline:
                if bytes(vstore.read_planar(_coll(pgid), "obj")) \
                        == before:
                    break
                await asyncio.sleep(0.05)
            assert bytes(vstore.read_planar(_coll(pgid), "obj")) \
                == before
            assert vstore.object_layout(_coll(pgid), "obj") \
                == planar_store.LAYOUT_PLANAR
            assert await io.read("obj", timeout=60) == payload
            assert _unseamed() - base == 0
        finally:
            await cluster.stop()

    run(scenario())


# ------------------------------------------------- layer 4: observability


def test_planar_counters_ride_prometheus_scrape():
    """The round-19 KERNELS counters surface through the same
    perfcoll.dump() -> render_prometheus path the mgr's scrape and
    exporter serve (Mgr registers KERNELS at construction)."""
    from ceph_tpu.cluster.mgr import render_prometheus
    from ceph_tpu.utils import PerfCountersCollection

    # ensure the counters exist process-wide (any prior planar test
    # already booked them; book explicitly so this test stands alone)
    from ceph_tpu.ops.profiling import record_planar_at_rest

    record_planar_at_rest("ingest", 4096)
    record_planar_at_rest("egress", 4096)
    coll = PerfCountersCollection()
    coll.register(KERNELS)
    text = render_prometheus(
        {n: c["counters"] if "counters" in c else c
         for n, c in coll.dump().items()})
    for name in ("ec_planar_ingest_conversions",
                 "ec_planar_ingest_bytes",
                 "ec_planar_egress_conversions"):
        assert name in text, text[:2000]


def test_attribution_books_planar_convert_stage():
    from ceph_tpu.trace.attribution import stage_for

    assert stage_for("planar_ingest") == "planar_convert"
    assert stage_for("planar_egress") == "planar_convert"


def test_host_layout_helpers_match_the_jitted_layout():
    """rows_to_planes / planes_to_rows (8x8 bit transposes on uint64
    words) against the jitted gf8.bytes_to_planar they mirror, bit for
    bit, ragged row counts and the empty row included."""
    import jax.numpy as jnp

    from ceph_tpu.ops import gf8

    r = _rng(19)
    for c, nbytes in ((1, 8), (2, 64), (3, 8 * 1237), (12, 4096), (2, 0)):
        rows = r.integers(0, 256, (c, nbytes), dtype=np.uint8)
        planes = planar_store.rows_to_planes(rows)
        assert planes.shape == (c * 8, nbytes // 8)
        assert np.array_equal(
            planes, np.asarray(gf8.bytes_to_planar(jnp.asarray(rows))))
        assert np.array_equal(planar_store.planes_to_rows(planes), rows)
