"""Round-11 batched data plane: bit-exactness + unit coverage.

The coalesced tick must be invisible in the bytes: N concurrent writes
through sharded dispatch + per-tick stripe-batch coalescing produce
byte-identical shards (and stored CRCs) to the same writes issued
one at a time through one shard and 1-op ticks — including mixed-profile
ticks and the 1-op-tick degenerate case.  Unit level, the multi-op
encode and the batched row CRC must match their per-op/host
equivalents exactly.
"""

import asyncio

import numpy as np
import pytest

from tests._flaky import contention_retry

from ceph_tpu.cluster.vstart import _fast_config, start_cluster
from ceph_tpu.ec import factory
from ceph_tpu.ec.stripe import (
    StripeInfo,
    encode_stripes,
    encode_stripes_multi,
)
from ceph_tpu.ops import crc32c as crcmod


def run(coro):
    return asyncio.run(coro)


def _coll(pgid):
    return f"pg_{pgid.pool}_{pgid.seed}"


# ------------------------------------------------------------- unit level


def test_encode_stripes_multi_bit_exact_and_crcs():
    """One coalesced dispatch == N per-op dispatches, byte for byte;
    batch CRCs == the host ceph_crc32c each shard row would get."""
    codec = factory({"plugin": "jerasure", "technique": "reed_sol_van",
                     "k": "2", "m": "1"})
    sinfo = StripeInfo(2, 4096)
    rng = np.random.default_rng(11)
    datas = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
             for n in (8192, 40960, 1, 8192, 0, 12345)]
    multi = encode_stripes_multi(codec, sinfo, datas,
                                 want_crcs=[True] * len(datas))
    for data, (shards, crcs) in zip(datas, multi):
        solo = encode_stripes(codec, sinfo, data)
        assert shards.shape == solo.shape
        assert np.array_equal(shards, solo)
        assert crcs is not None and len(crcs) == shards.shape[0]
        for row, crc in zip(shards, crcs):
            assert crc == crcmod.crc32c(0xFFFFFFFF, row.tobytes())


def test_encode_stripes_multi_single_op_degenerate():
    """The 1-op tick: no coalescing partner, still bit-exact."""
    codec = factory({"plugin": "jerasure", "technique": "reed_sol_van",
                     "k": "2", "m": "1"})
    sinfo = StripeInfo(2, 4096)
    data = bytes(range(256)) * 64
    [(shards, crcs)] = encode_stripes_multi(codec, sinfo, [data], [True])
    assert np.array_equal(shards, encode_stripes(codec, sinfo, data))
    assert crcs == [crcmod.crc32c(0xFFFFFFFF, r.tobytes())
                    for r in shards]


def test_crc32c_rows_matches_host():
    rng = np.random.default_rng(7)
    # block-aligned rows: the device batch + vectorized fold path
    rows = rng.integers(0, 256, (5, 3 * 4096), dtype=np.uint8)
    got = crcmod.crc32c_rows(rows)
    assert got == [crcmod.crc32c(0xFFFFFFFF, r.tobytes()) for r in rows]
    # non-multiple length: the per-row host fallback
    odd = rng.integers(0, 256, (3, 1000), dtype=np.uint8)
    assert crcmod.crc32c_rows(odd) == \
        [crcmod.crc32c(0xFFFFFFFF, r.tobytes()) for r in odd]
    # empty rows
    assert crcmod.crc32c_rows(np.zeros((2, 0), dtype=np.uint8)) == \
        [0xFFFFFFFF, 0xFFFFFFFF]


def test_batch_attribution_amortized_stage_math():
    """The coalescer's amortized marks: batch_wait + batch_encode
    partition the parked->encoded window, batch_encode gets exactly
    the tick's wall / batch size, and the stage sums stay equal to the
    traced total (the attribution invariant)."""
    from ceph_tpu.trace.attribution import attribute_events

    # an op parked at t=1.0; tick ran 2.0 -> 5.0 with 3 ops coalesced
    share = (5.0 - 2.0) / 3
    evs = [(0.0, "initiated"), (0.5, "dispatched"),
           (1.0, "batch_parked"),
           (5.0 - share, "batch_tick"), (5.0, "batch_encoded"),
           (5.2, "done")]
    stages, total = attribute_events(evs)
    assert abs(sum(stages.values()) - total) < 1e-9
    assert abs(stages["batch_encode"] - share) < 1e-9
    assert abs(stages["batch_wait"] - (4.0 - share)) < 1e-9
    assert stages["op_prepare"] == pytest.approx(0.5)


def test_commit_frontier_blocks_out_of_order_acks():
    """The pipelined-write watermark invariant: a later write's acks
    arriving first must NOT advance last_complete past an earlier
    still-pending write; a FAILED earlier write unblocks the later one
    (the pre-pipeline skip semantics)."""
    from ceph_tpu.cluster.pg import PGState
    from ceph_tpu.osdmap.osdmap import PGid

    from ceph_tpu.cluster.pg import PGLogMixin

    class _Store:
        def omap_get(self, coll, oid):
            return {}

        def queue_transaction(self, txn):
            pass

    class _Host(PGLogMixin):
        def __init__(self):
            self.store = _Store()

    h = _Host()
    st = PGState(PGid(1, 0))
    zero = st.last_complete
    v5, v6, v7 = (1, 5), (1, 6), (1, 7)
    for v in (v5, v6, v7):
        h._frontier_open(st, v)
    # commit starts log before their acks: the head covers the opens
    # (round 12: the watermark can never pass the log head)
    st.last_update = v7
    # v6 acks first: watermark must NOT move (v5 still pending)
    h._frontier_done(st, v6, ok=True)
    assert st.last_complete == zero
    # direct advances (recovery-style) are clamped below pending too
    h._advance_last_complete(st, v7)
    assert st.last_complete == zero
    # v5 fails: removed without blessing, v6's ack now advances to 6
    h._frontier_done(st, v5, ok=False)
    assert st.last_complete == v6
    # v7 acks: contiguous prefix advances to 7
    h._frontier_done(st, v7, ok=True)
    assert st.last_complete == v7


def test_frontier_rebuild_and_learn():
    """Round-12 crash-restart reconstruction: logged entries above the
    persisted watermark re-register as OPEN frontier entries, a
    post-restart fully-acked write can NOT advance the watermark past
    them, and an authoritative learn (peering roll-forward / primary
    entry stream) resolves them — while a rewind drops them."""
    from ceph_tpu.cluster.pg import PGLogMixin, PGState
    from ceph_tpu.cluster.pglog import LogEntry, PGLog
    from ceph_tpu.osdmap.osdmap import PGid
    from ceph_tpu.utils import PerfCounters

    class _Store:
        def omap_get(self, coll, oid):
            return {}

        def queue_transaction(self, txn):
            pass

    class _Host(PGLogMixin):
        def __init__(self):
            self.store = _Store()
            self.perf = PerfCounters("t")

    h = _Host()
    st = PGState(PGid(1, 0))
    st.last_complete = (1, 5)
    st.log = PGLog(entries=[
        LogEntry(op="modify", oid=f"o{s}", version=(1, s))
        for s in (4, 5, 6, 7, 8)])
    st.last_update = (1, 8)
    h._frontier_rebuild(st)
    # only the entries ABOVE the persisted watermark are open
    assert list(st.pipeline_pending) == [(1, 6), (1, 7), (1, 8)]
    assert st.frontier_recovering == {(1, 6), (1, 7), (1, 8)}
    # a new write fully acks out of order: watermark must NOT move
    h._frontier_open(st, (1, 9))
    st.last_update = (1, 9)
    h._frontier_done(st, (1, 9), ok=True)
    assert st.last_complete == (1, 5)
    # ... but reads may serve the resolved entry (read-your-ack)
    assert st.frontier_acked(9) and not st.frontier_acked(7)
    # peering verified every member holds up to 7: 6,7 resolve; 8 stays
    h._frontier_learn(st, (1, 7))
    assert st.last_complete == (1, 7)
    assert list(st.pipeline_pending) == [(1, 8), (1, 9)]
    assert st.frontier_recovering == {(1, 8)}
    # ... and verifying up to 8 sweeps straight through the resolved 9
    h._frontier_learn(st, (1, 8))
    assert st.last_complete == (1, 9)
    assert not st.pipeline_pending and not st.frontier_recovering

    # the rewind path, on a fresh reconstruction: divergent open
    # entries leave the frontier with the log (they can never ack)
    st2 = PGState(PGid(1, 1))
    st2.last_complete = (1, 2)
    st2.log = PGLog(entries=[
        LogEntry(op="modify", oid=f"r{s}", version=(1, s))
        for s in (3, 4)])
    st2.last_update = (1, 4)
    h._frontier_rebuild(st2)
    assert set(st2.pipeline_pending) == {(1, 3), (1, 4)}
    h.rewind_divergent_log(st2, (1, 3))
    assert list(st2.pipeline_pending) == [(1, 3)]
    assert st2.frontier_recovering == {(1, 3)}


def test_plain_config_is_the_product_data_plane():
    """Plain ``Config()`` selects the served data plane (sharded
    dispatch, both coalescers at a cap above one, planar at rest), so a
    tool or daemon built without a config runs what the cells measure
    (``_fast_config()`` only adds timings: tests/test_client_batch.py)."""
    from ceph_tpu.utils import Config

    plain = Config()
    assert plain.osd_op_shards == 2
    assert plain.osd_batch_tick_ops == 16
    assert plain.objecter_batch_tick_ops == 16
    assert plain.osd_ec_planar_at_rest == 1


@pytest.mark.parametrize("cap", ["osd_op_shards", "osd_batch_tick_ops",
                                 "objecter_batch_tick_ops"])
def test_zero_is_refused_for_every_cap(cap):
    """The "0" of each cap selected a superseded data plane; that plane
    is gone, so zero is refused at construction and at injectargs (a
    cap of one is the per-op reference)."""
    from ceph_tpu.utils import Config

    with pytest.raises(ValueError, match=cap):
        Config(**{cap: 0})
    cfg = Config(**{cap: 1})
    with pytest.raises(ValueError, match=cap):
        cfg.injectargs({cap: 0})
    assert cfg.get(cap) == 1


def test_every_option_is_read_by_some_module():
    """An option nothing reads is a setting that does nothing: every
    ``Option`` of utils/config.py is named somewhere in ``ceph_tpu/``
    outside the table that declares it."""
    import pathlib
    import re

    import ceph_tpu
    from ceph_tpu.utils import config as configmod

    root = pathlib.Path(ceph_tpu.__file__).parent
    here = pathlib.Path(configmod.__file__)
    source = "\n".join(
        p.read_text() for p in sorted(root.rglob("*.py")) if p != here)
    # the schema's own file counts only below the OPTIONS table (the
    # Config methods read the auth keys)
    source += here.read_text().split("\nclass Config:", 1)[1]
    words = set(re.findall(r"\w+", source))
    unread = [o.name for o in configmod.OPTIONS if o.name not in words]
    assert not unread, unread


# ---------------------------------------------------------- cluster level


async def _write_workload(cluster, concurrent: bool):
    """The shared workload: full writes across two EC profiles (a
    mixed-profile tick when concurrent) + an RMW partial write + a
    1-op-tick straggler + a replicated pool (full, partial, append,
    truncate, delete — the round-12 pipelined verbs).  Returns
    {pool_name: (pool_id, [oids])}."""
    client = await cluster.client()
    pool_a = await client.pool_create(
        "bxa", "erasure", pg_num=4,
        ec_profile={"plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
    pool_b = await client.pool_create(
        "bxb", "erasure", pg_num=4,
        ec_profile={"plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "3", "m": "2"})
    pool_r = await client.pool_create("bxr", "replicated", pg_num=4,
                                      size=3)
    io_a = client.ioctx(pool_a)
    io_b = client.ioctx(pool_b)
    io_r = client.ioctx(pool_r)
    rng = np.random.default_rng(42)
    jobs = []
    oids_a, oids_b = [], []
    for i in range(6):
        oid = f"obj_a{i}"
        oids_a.append(oid)
        payload = rng.integers(0, 256, 65536 + i * 4096,
                               dtype=np.uint8).tobytes()
        jobs.append((io_a, oid, payload))
    for i in range(4):
        oid = f"obj_b{i}"
        oids_b.append(oid)
        payload = rng.integers(0, 256, 49152, dtype=np.uint8).tobytes()
        jobs.append((io_b, oid, payload))
    if concurrent:
        await asyncio.gather(*(io.write_full(oid, payload, timeout=120)
                               for io, oid, payload in jobs))
    else:
        for io, oid, payload in jobs:
            await io.write_full(oid, payload, timeout=120)
    # RMW partial overwrite crossing a stripe boundary (no batch crc)
    patch = rng.integers(0, 256, 10000, dtype=np.uint8).tobytes()
    await io_a.write("obj_a0", patch, offset=5000, timeout=120)
    # EC append + truncate: round-12 pipelined compound verbs
    await io_a.append("obj_a1", b"\x5a" * 4096)
    await io_a.truncate("obj_a2", 30000)
    # 1-op tick: a lone write with nothing to coalesce against
    await io_a.write_full("obj_a_solo", b"\xa5" * 20480, timeout=120)
    oids_a.append("obj_a_solo")
    # replicated verbs through the same frontier path
    oids_r = []
    for i in range(3):
        oid = f"obj_r{i}"
        oids_r.append(oid)
        await io_r.write_full(
            oid, rng.integers(0, 256, 16384, dtype=np.uint8).tobytes(),
            timeout=120)
    await io_r.write("obj_r0", b"\x0f" * 777, offset=100, timeout=120)
    await io_r.append("obj_r1", b"\xf0" * 512)
    await io_r.truncate("obj_r2", 5000)
    await io_r.write_full("obj_r_gone", b"bye" * 100, timeout=120)
    await io_r.remove("obj_r_gone")
    oids_r.append("obj_r_gone")  # snapshot proves absence on BOTH paths
    return client, {"bxa": (pool_a, oids_a), "bxb": (pool_b, oids_b),
                    "bxr": (pool_r, oids_r)}


def _shard_snapshot(cluster, client, pools):
    """Every member's stored shard state per object: (bytes, shard,
    size, hinfo_crc) — the on-disk truth the two paths must agree on."""
    out = {}
    for pname, (pool, oids) in pools.items():
        for oid in oids:
            pgid = client.objecter.object_pgid(pool, oid)
            coll = _coll(pgid)
            for osd_id, osd in cluster.osds.items():
                if osd.store.stat(coll, oid) is None:
                    continue
                out[(pname, oid, osd_id)] = (
                    bytes(osd.store.read(coll, oid)),
                    osd.store.getattr(coll, oid, "shard"),
                    osd.store.getattr(coll, oid, "size"),
                    osd.store.getattr(coll, oid, "hinfo_crc"),
                )
    return out


@contention_retry()
def test_coalesced_writes_bit_exact_vs_per_op_path():
    """THE round-11 acceptance invariant: concurrent writes through
    sharded dispatch + coalescing leave every OSD's stored shards and
    CRCs byte-identical to the same writes issued one at a time through
    one shard and 1-op ticks — every op its own dispatch, its own
    encode and its own sub-write frames (mixed-profile ticks + RMW +
    1-op tick included)."""
    async def run_path(coalesced: bool):
        cfg = _fast_config()
        if not coalesced:
            # the per-op reference on the one path: a cap of one
            # everywhere on the OSD, ops issued serially
            cfg.osd_op_shards = 1
            cfg.osd_batch_tick_ops = 1
        cluster = await start_cluster(5, config=cfg)
        try:
            client, pools = await _write_workload(
                cluster, concurrent=coalesced)
            snap = _shard_snapshot(cluster, client, pools)
            ticks = sum(o.perf.get("osd_batch_ticks")
                        for o in cluster.osds.values())
            coalesced_ops = sum(o.perf.get("osd_batch_coalesced_ops")
                                for o in cluster.osds.values())
            if coalesced:
                # every full write really rode the coalescer
                assert ticks > 0 and coalesced_ops >= 12
            else:
                # the reference coalesced nothing: every tick held one op
                assert coalesced_ops == ticks
                assert not any(o.perf.get("osd_subwrite_batches")
                               for o in cluster.osds.values())
            return snap
        finally:
            await cluster.stop()

    batched = run(run_path(True))
    serial = run(run_path(False))
    assert set(batched) == set(serial)
    for key in sorted(serial):
        assert batched[key] == serial[key], key


@contention_retry()
def test_client_batched_frames_bit_exact_vs_per_op_frames():
    """THE round-18 acceptance invariant: the SAME concurrent workload
    through MOSDOpBatch client frames vs one plain MOSDOp frame per op
    (OSD-interior coalescing identical on both sides) leaves every
    OSD's stored shards and CRCs byte-identical — mixed verbs
    (write/RMW/append/truncate/delete), replicated + EC pools, and the
    1-op-tick straggler included."""
    async def run_path(client_batched: bool):
        cfg = _fast_config()
        if not client_batched:
            # the reference: a frame per op, everything else equal
            cfg.objecter_batch_tick_ops = 1
        cluster = await start_cluster(5, config=cfg)
        try:
            client, pools = await _write_workload(
                cluster, concurrent=True)
            snap = _shard_snapshot(cluster, client, pools)
            frames = sum(o.perf.get("osd_client_batch_frames")
                         for o in cluster.osds.values())
            items = sum(o.perf.get("osd_client_batch_items")
                        for o in cluster.osds.values())
            if client_batched:
                # the workload really rode batched client frames
                assert frames > 0 and items >= frames
                assert client.objecter.flow_counters()[
                    "client_batch_ticks"] > 0
            else:
                assert frames == 0 and items == 0
            return snap
        finally:
            await cluster.stop()

    batched = run(run_path(True))
    anchor = run(run_path(False))
    assert set(batched) == set(anchor)
    for key in sorted(anchor):
        assert batched[key] == anchor[key], key


@contention_retry()
def test_coalesced_concurrent_appends_apply_exactly_once():
    """Same-object concurrency under sharded dispatch: every append
    lands exactly once and the object stays readable (per-object
    ordering lives inside one shard by PG affinity)."""
    async def scenario():
        cluster = await start_cluster(3)
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "bxo", "erasure", pg_num=4,
                ec_profile={"plugin": "jerasure",
                            "technique": "reed_sol_van",
                            "k": "2", "m": "1"})
            io = client.ioctx(pool)
            await io.write_full("log", b"", timeout=120)
            pieces = [bytes([65 + i]) * 512 for i in range(8)]
            await asyncio.gather(
                *(io.append("log", p) for p in pieces))
            data = await io.read("log", timeout=120)
            assert len(data) == sum(len(p) for p in pieces)
            for p in pieces:
                assert data.count(p[:1]) == len(p)
        finally:
            await cluster.stop()

    run(scenario())


@contention_retry()
def test_device_branches_serve_write_read_recover(monkeypatch):
    """The TPU product path's control flow, on CPU: with the host GF
    engine switched off, a cluster's write, read, degraded read, recovery
    and read-after-recovery run the device branches of
    ``encode/decode/reencode_planes_multi`` (pad to a bucket,
    ``to_planar``, ``gf8.planar_matmul``, read back) — which no other
    tier-1 test reaches, because every codec ``planar_at_rest_ok`` admits
    also satisfies ``_host_engine_ok`` on a CPU backend.  This is
    ``chip_smoke.py``'s phase 3 at tiny size, through the same code."""
    import chip_smoke
    from ceph_tpu.ec import stripe

    monkeypatch.setattr(stripe, "_host_engine_ok", lambda codec: False)
    report = run(chip_smoke.serve_ec_objects(
        seed=7, n_objects=4, object_size=64 << 10, in_flight=4))
    grew = report["counters"]
    assert grew.get("planar_matmul_calls", 0) > 0
    assert grew.get("ec_coalesced_ticks", 0) > 0
    # every served write took its shard crcs from the chunk-crc program,
    # and the verified reads below re-derived them on the host
    assert grew.get("ec_tick_crc_device_ticks", 0) \
        == grew["ec_coalesced_ticks"]
    assert grew.get("ec_coalesced_read_ticks", 0) > 0, \
        "no degraded read decoded a missing data shard"
    assert grew.get("ec_coalesced_reencode_ticks", 0) > 0, \
        "recovery rebuilt nothing through reencode_planes_multi"
    assert "ec_host_matmul_calls" not in grew
    assert "ec_host_planar_matmul_calls" not in grew
    assert report["shards_rebuilt"] > 0
    assert report["placement_engine"] == "scalar"
    # on CPU the planar matmul takes the XLA route (one stack group per
    # call); the smoke's own check demands the Pallas kernel, so here it
    # must refuse
    with pytest.raises(AssertionError, match="stack-group"):
        chip_smoke.check_device_did_the_work(report)


@contention_retry()
def test_degraded_reads_of_4mib_objects_are_answered():
    """Degraded reads at the rados-bench object size: two surviving OSDs
    of a k2m1 pool answer each other's sub-reads with 2 MiB frames, 16
    reads in flight.  While the sub-read was served inside the
    connection's read loop, both readers waited for their own replies to
    drain, both sockets filled, and client reads came back EIO ("only 1
    of 2 shard ranges") after the sub-op timeout — what stopped
    chip_smoke.py's degraded pass.  At this size the stall was frequent,
    not certain (it was certain at the smoke's 85 stored objects, which
    tier-1 cannot afford); the test also keeps 4 MiB objects, which no
    other tier-1 test writes, on the read egress and decode paths."""
    async def scenario():
        cluster = await start_cluster(3)
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "big", "erasure", pg_num=8,
                ec_profile={"plugin": "jerasure",
                            "technique": "reed_sol_van",
                            "k": "2", "m": "1"})
            io = client.ioctx(pool)
            rng = np.random.default_rng(3)
            objs = {f"big_{i}": rng.integers(0, 256, 4 << 20,
                                            dtype=np.uint8).tobytes()
                    for i in range(16)}
            await asyncio.gather(*(io.write_full(n, d, timeout=120)
                                   for n, d in objs.items()))
            await cluster.kill_osd(2)
            await cluster.wait_down(2)
            got = await asyncio.gather(*(io.read(n, timeout=120)
                                         for n in objs))
            assert all(g == d for g, d in zip(got, objs.values()))
        finally:
            await cluster.stop()

    run(scenario())
