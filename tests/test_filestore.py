"""FileStore durability + OSD restart resume.

Tier-2 store-contract tests (the reference's store_test.cc fixtures run the
same ObjectStore contract against memstore/filestore/bluestore) plus the
tier-3 full-cluster restart: write, stop EVERY osd, restart from disk,
read back with ZERO recovery pushes (reference OSD::init read_superblock/
load_pgs resume, src/osd/OSD.cc:2556,2572).
"""

import asyncio

import pytest

from ceph_tpu.cluster.filestore import FileStore
from ceph_tpu.cluster.store import Transaction


def run(coro):
    return asyncio.run(coro)


def test_filestore_roundtrip(tmp_path):
    s = FileStore(str(tmp_path / "osd0"))
    s.mount()
    s.queue_transaction(
        Transaction()
        .create_collection("c")
        .write("c", "obj", 0, b"hello world")
        .setattr("c", "obj", "k", b"v")
        .omap_set("c", "obj", {"ok": b"ov"})
        .set_version("c", "obj", 7))
    s.umount()

    s2 = FileStore(str(tmp_path / "osd0"))
    s2.mount()
    assert s2.read("c", "obj") == b"hello world"
    assert s2.getattr("c", "obj", "k") == b"v"
    assert s2.omap_get("c", "obj") == {"ok": b"ov"}
    assert s2.get_version("c", "obj") == 7
    s2.umount()


def test_filestore_journal_replay_without_checkpoint(tmp_path):
    """Crash before any checkpoint: journal alone restores state."""
    s = FileStore(str(tmp_path / "osd1"))
    s.mount()
    s.queue_transaction(
        Transaction().create_collection("c").write("c", "o", 0, b"abc"))
    # simulate crash: no umount/checkpoint, just drop the handle
    s._journal.flush()
    s._journal.close()

    s2 = FileStore(str(tmp_path / "osd1"))
    s2.mount()
    assert s2.read("c", "o") == b"abc"
    s2.umount()


def test_filestore_torn_tail_discarded(tmp_path):
    s = FileStore(str(tmp_path / "osd2"))
    s.mount()
    s.queue_transaction(
        Transaction().create_collection("c").write("c", "o", 0, b"good"))
    s._journal.flush()
    s._journal.close()
    # append a torn frame (header promises more bytes than present)
    with open(s._journal_path, "ab") as f:
        f.write(b"\xff\x00\x00\x00partial")

    s2 = FileStore(str(tmp_path / "osd2"))
    s2.mount()  # must not raise; torn tail discarded
    assert s2.read("c", "o") == b"good"
    s2.umount()


def test_filestore_checkpoint_truncates_journal(tmp_path):
    s = FileStore(str(tmp_path / "osd3"), checkpoint_every=4)
    s.mount()
    for i in range(10):
        s.queue_transaction(
            Transaction().create_collection("c").write("c", f"o{i}", 0,
                                                       b"x" * 100))
    import os

    assert os.path.getsize(s._journal_path) < 4 * 300
    s.umount()
    s2 = FileStore(str(tmp_path / "osd3"))
    s2.mount()
    assert len([o for o in s2.list_objects("c")]) == 10
    s2.umount()


def test_cluster_full_restart_zero_pushes(tmp_path):
    """Write to a durable cluster, stop EVERY osd, restart from disk:
    reads succeed and recovery pushes nothing (logs all agree)."""
    async def scenario():
        from ceph_tpu.cluster.osd import OSDDaemon
        from ceph_tpu.cluster.vstart import _fast_config, start_cluster

        cfg = _fast_config()
        cfg.mon_osd_down_out_interval = 120.0
        # every OSD stops at once: nobody is left to report, only the
        # mon's beacon grace marks them down, and the product
        # configuration's 30 s (PR 28) outlasts wait_down
        cfg.mon_osd_beacon_grace = 1.5

        def factory(osd_id):
            return FileStore(str(tmp_path / f"osd{osd_id}"))

        cluster = await start_cluster(3, config=cfg, store_factory=factory)
        try:
            client = await cluster.client()
            rpool = await client.pool_create("repl", "replicated",
                                             pg_num=8, size=3)
            epool = await client.pool_create(
                "ecp", "erasure", pg_num=8,
                ec_profile={"plugin": "jerasure",
                            "technique": "reed_sol_van",
                            "k": "2", "m": "1"})
            rio = client.ioctx(rpool)
            eio = client.ioctx(epool)
            payloads = {f"r{i}": f"repl-{i}".encode() * 100 for i in range(6)}
            epayloads = {f"e{i}": f"ec-{i}".encode() * 200 for i in range(4)}
            for oid, data in payloads.items():
                await rio.write_full(oid, data)
            for oid, data in epayloads.items():
                await eio.write_full(oid, data)

            # full stop of every OSD (mon stays; its durable store is the
            # paxos-mon milestone)
            ids = list(cluster.osds)
            for o in ids:
                osd = cluster.osds.pop(o)
                await osd.stop()
            for o in ids:
                await cluster.wait_down(o)

            for o in ids:
                osd = OSDDaemon(o, cluster.mon_addr, config=cfg,
                                store=factory(o))
                await osd.start()
                cluster.osds[o] = osd
            deadline = asyncio.get_event_loop().time() + 15
            while asyncio.get_event_loop().time() < deadline:
                if all(cluster.mon.osdmap.osd_up[o] for o in ids):
                    break
                await asyncio.sleep(0.05)
            # peering window: converge-poll the first read against a
            # wall deadline instead of a fixed sleep
            deadline = asyncio.get_event_loop().time() + 15
            first = next(iter(payloads))
            while asyncio.get_event_loop().time() < deadline:
                try:
                    if await rio.read(first, timeout=5) \
                            == payloads[first]:
                        break
                except Exception:
                    pass
                await asyncio.sleep(0.05)

            for oid, data in payloads.items():
                assert await rio.read(oid) == data, oid
            for oid, data in epayloads.items():
                assert await eio.read(oid) == data, oid
            pushes = sum(o.perf.get("osd_pushes_sent")
                         for o in cluster.osds.values())
            assert pushes == 0, f"restart resume must not push ({pushes})"
        finally:
            await cluster.stop()

    run(scenario())


def test_whole_cluster_restart_including_mon(tmp_path):
    """THE full durability story: stop mon AND every osd, restart all
    from disk — pools, maps, and data all resume (MonitorDBStore +
    superblock + pg logs)."""
    async def phase1():
        from ceph_tpu.cluster.vstart import _fast_config, start_cluster

        cfg = _fast_config()

        def osd_store(o):
            return FileStore(str(tmp_path / f"osd{o}"))

        def mon_store(r):
            return FileStore(str(tmp_path / f"mon{r}"))

        cluster = await start_cluster(3, config=cfg,
                                      store_factory=osd_store,
                                      mon_store_factory=mon_store)
        try:
            client = await cluster.client()
            pool = await client.pool_create("persist", "replicated",
                                            pg_num=8, size=2)
            io = client.ioctx(pool)
            await io.write_full("survivor", b"across-restarts" * 50)
            return cluster.mon.osdmap.epoch, pool
        finally:
            await cluster.stop()

    epoch, pool = run(phase1())

    async def phase2():
        from ceph_tpu.cluster.mon import Monitor
        from ceph_tpu.cluster.objecter import RadosClient
        from ceph_tpu.cluster.osd import OSDDaemon
        from ceph_tpu.cluster.vstart import _fast_config
        from ceph_tpu.crush.types import build_hierarchy
        from ceph_tpu.osdmap.osdmap import OSDMap

        cfg = _fast_config()
        # the ctor map is a throwaway: start() resumes the persisted one
        cmap, _ = build_hierarchy(3, 1, numrep=3)
        mon = Monitor(OSDMap(cmap, max_osd=3), config=cfg,
                      store=FileStore(str(tmp_path / "mon0")))
        addr = await mon.start()
        assert mon.osdmap.epoch >= epoch          # resumed, not reset
        assert pool in mon.osdmap.pools           # pool survived
        osds = []
        try:
            for o in range(3):
                osd = OSDDaemon(o, addr, config=cfg,
                                store=FileStore(str(tmp_path / f"osd{o}")))
                await osd.start()
                osds.append(osd)
            deadline = asyncio.get_event_loop().time() + 15
            while asyncio.get_event_loop().time() < deadline:
                if all(mon.osdmap.osd_up[o] for o in range(3)):
                    break
                await asyncio.sleep(0.05)
            client = RadosClient(addr, config=cfg)
            await client.connect()
            try:
                io = client.ioctx(pool)
                assert await io.read("survivor") == b"across-restarts" * 50
            finally:
                await client.shutdown()
        finally:
            for osd in osds:
                await osd.stop()
            await mon.stop()

    run(phase2())
