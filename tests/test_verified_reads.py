"""Round-16 verified batched reads: bit-exactness + corruption matrix.

The read-side twin of tests/test_batch_dataplane.py's write gate: the
coalesced decode must be invisible in the bytes — N concurrent reads
through the read coalescer return byte-identical data to the same reads
issued one at a time through 1-op ticks (mixed-profile ticks,
the 1-op tick, degraded fast-k reads, and the recovery reencode
included).  Unit level, the multi decode/reencode must match their
per-op equivalents exactly, and the corruption matrix proves every
shard position's rot is detected by crc and rebuilt bit-identically
from the survivors.
"""

import asyncio
import itertools

import numpy as np
import pytest

from tests._flaky import contention_retry

from ceph_tpu.cluster.vstart import _fast_config, start_cluster
from ceph_tpu.ec import factory
from ceph_tpu.ec.stripe import (
    StripeInfo,
    decode_stripes,
    decode_stripes_multi,
    encode_stripes,
    reencode_stripes,
    reencode_stripes_multi,
)
from ceph_tpu.ops import crc32c as crcmod


def run(coro):
    return asyncio.run(coro)


def _codec(k, m):
    return factory({"plugin": "jerasure", "technique": "reed_sol_van",
                    "k": str(k), "m": str(m)})


# ------------------------------------------------------------- unit level


def test_decode_stripes_multi_bit_exact():
    """One coalesced tick == N per-op decodes, byte for byte — across
    mixed object sizes AND mixed erasure patterns in the same tick."""
    codec = _codec(2, 1)
    sinfo = StripeInfo(2, 4096)
    rng = np.random.default_rng(5)
    reqs = []
    for size in (8192, 40960, 1, 12345, 0):
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        full = encode_stripes(codec, sinfo, data)
        for keep in ((0, 1), (1, 2), (0, 2)):
            reqs.append(({s: full[s] for s in keep}, size, data))
    outs = decode_stripes_multi(codec, sinfo,
                                [(sh, ls) for sh, ls, _d in reqs])
    for (shards, ls, data), got in zip(reqs, outs):
        assert got == decode_stripes(codec, sinfo, shards, ls)
        assert got == data


def test_decode_stripes_multi_single_op_degenerate():
    codec = _codec(2, 1)
    sinfo = StripeInfo(2, 4096)
    data = bytes(range(256)) * 50
    full = encode_stripes(codec, sinfo, data)
    [got] = decode_stripes_multi(codec, sinfo,
                                 [({1: full[1], 2: full[2]}, len(data))])
    assert got == data


def test_reencode_stripes_multi_bit_exact():
    """The recovery rebuild's multi twin: per-op reencode equality for
    every availability pattern of a k3m2 object, all in one call."""
    codec = _codec(3, 2)
    sinfo = StripeInfo(3, 4096)
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, 49152, dtype=np.uint8).tobytes()
    full = encode_stripes(codec, sinfo, data)
    reqs = [({s: full[s] for s in keep}, len(data))
            for keep in itertools.combinations(range(5), 3)]
    outs = reencode_stripes_multi(codec, sinfo, reqs)
    for (shards, ls), got in zip(reqs, outs):
        assert np.array_equal(got, reencode_stripes(codec, sinfo,
                                                    shards, ls))
        assert np.array_equal(got, full)


def test_corruption_matrix_every_shard_position():
    """Synthetic corruption matrix: flip a bit in EACH shard position
    (data and parity), assert (a) the crc catches exactly the flipped
    shard, and (b) the rebuild from the survivors — corrupt shard
    excluded as a decode source — is bit-identical to the original.
    Then every erasure pattern up to m=k-1=2 erasures rebuilds exactly
    (single vs k-1 erasures, data vs parity mixes)."""
    codec = _codec(3, 2)
    sinfo = StripeInfo(3, 4096)
    rng = np.random.default_rng(13)
    data = rng.integers(0, 256, 36864, dtype=np.uint8).tobytes()
    full = encode_stripes(codec, sinfo, data)
    n = full.shape[0]
    crcs = [crcmod.crc32c(0xFFFFFFFF, full[s].tobytes())
            for s in range(n)]
    for bad in range(n):
        rotted = full.copy()
        rotted[bad, 777] ^= 0x40
        # detection: exactly the flipped shard fails its stored crc
        got = crcmod.crc32c_rows(rotted)
        fails = [s for s in range(n) if got[s] != crcs[s]]
        assert fails == [bad]
        # repair: rebuild with the corrupt shard EXCLUDED as a source
        survivors = {s: rotted[s] for s in range(n) if s != bad}
        [rebuilt] = reencode_stripes_multi(
            codec, sinfo, [(survivors, len(data))])
        assert np.array_equal(rebuilt, full), f"shard {bad}"
    # erasure sweep: every 1- and 2-erasure pattern decodes AND
    # rebuilds to the originals
    for nlost in (1, 2):
        for lost in itertools.combinations(range(n), nlost):
            survivors = {s: full[s] for s in range(n) if s not in lost}
            [got] = decode_stripes_multi(
                codec, sinfo, [(survivors, len(data))])
            assert got == data, lost
            [rebuilt] = reencode_stripes_multi(
                codec, sinfo, [(survivors, len(data))])
            assert np.array_equal(rebuilt, full), lost


def test_choose_decode_group_mixed_generation():
    """The pure gather chooser: a member holding an OLDER committed
    generation is flagged stale (read-repair candidate), un-acked
    newer generations never outvote committed ones, and an acked
    generation short of k shards refuses the stale read."""
    from ceph_tpu.cluster.backend_ec import choose_decode_group

    committed = lambda v: v <= 5  # noqa: E731
    # g5 committed on shards 0,1; shard 2 stuck at g3 (missed a write)
    got = {0: (b"a5", 5, 100), 1: (b"b5", 5, 100), 2: (b"c3", 3, 60)}
    shards, size, version, stale = choose_decode_group(got, 2, committed)
    assert version == 5 and size == 100 and set(shards) == {0, 1}
    assert stale == {2}
    # an un-acked g7 on one shard must NOT be chosen over committed g5
    got = {0: (b"a7", 7, 140), 1: (b"b5", 5, 100), 2: (b"c5", 5, 100)}
    shards, size, version, stale = choose_decode_group(got, 2, committed)
    assert version == 5 and set(shards) == {1, 2}
    assert stale == set()      # g7 is in flight, NOT stale
    # acked newest lacking k shards: refuse the stale read
    got = {0: (b"a5", 5, 100), 1: (b"b3", 3, 60), 2: (b"c3", 3, 60)}
    with pytest.raises(IOError):
        choose_decode_group(got, 2, committed)
    # brand-new object: only un-acked state exists — serve it
    got = {0: (b"a9", 9, 20), 1: (b"b9", 9, 20)}
    shards, size, version, stale = choose_decode_group(got, 2, committed)
    assert version == 9 and set(shards) == {0, 1} and not stale


def test_read_batcher_verify_and_fault_isolation():
    """ReadBatcher unit: the verify tick answers per-row pass/fail from
    one crc batch, and a poisoned decode request (too few shards) fails
    ALONE — its tick-mates still decode (per-item fault isolation)."""
    from ceph_tpu.cluster.batcher import ReadBatcher
    from ceph_tpu.utils import Config, PerfCounters

    codec = _codec(2, 1)
    sinfo = StripeInfo(2, 4096)
    data = b"\xa5" * 8192
    full = encode_stripes(codec, sinfo, data)

    class _FakeOSD:
        config = Config(osd_batch_tick_ops=16)
        perf = PerfCounters("t")
        osd_id = 0
        _stopped = False

        class clock:
            @staticmethod
            def monotonic():
                import time

                return time.monotonic()

        async def _compute(self, fn, *args, tick=None):
            return fn(*args)

        def _track(self, task):
            return task

    async def scenario():
        rb = ReadBatcher(_FakeOSD())
        row = full[0].tobytes()
        good_crc = crcmod.crc32c(0xFFFFFFFF, row)
        oks = await rb.verify([row, row], [good_crc, good_crc ^ 1])
        assert oks == [True, False]
        # one under-k request + two good ones, same tick
        results = await asyncio.gather(
            rb.decode(codec, sinfo, {0: full[0], 1: full[1]}, len(data)),
            rb.decode(codec, sinfo, {0: full[0]}, len(data)),
            rb.decode(codec, sinfo, {1: full[1], 2: full[2]}, len(data)),
            return_exceptions=True)
        assert results[0] == data
        assert isinstance(results[1], ValueError)
        assert results[2] == data

    run(scenario())


# ---------------------------------------------------------- cluster level


async def _read_workload(cluster, concurrent: bool):
    """Write a fixed workload (two EC profiles + RMW + a solo object),
    then read every object — concurrently (coalesced ticks) or serially
    (the per-op reference).  Returns {(pool_name, oid): bytes} plus the
    expected payloads."""
    client = await cluster.client()
    pool_a = await client.pool_create(
        "vra", "erasure", pg_num=4,
        ec_profile={"plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
    pool_b = await client.pool_create(
        "vrb", "erasure", pg_num=4,
        ec_profile={"plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "3", "m": "2"})
    io_a, io_b = client.ioctx(pool_a), client.ioctx(pool_b)
    rng = np.random.default_rng(77)
    expect = {}
    for i in range(4):
        payload = rng.integers(0, 256, 32768 + i * 4096,
                               dtype=np.uint8).tobytes()
        await io_a.write_full(f"ra{i}", payload, timeout=120)
        expect[("a", f"ra{i}")] = payload
    for i in range(3):
        payload = rng.integers(0, 256, 24576, dtype=np.uint8).tobytes()
        await io_b.write_full(f"rb{i}", payload, timeout=120)
        expect[("b", f"rb{i}")] = payload
    # RMW overlay crossing a stripe boundary
    patch = rng.integers(0, 256, 9000, dtype=np.uint8).tobytes()
    await io_a.write("ra0", patch, offset=5000, timeout=120)
    base = bytearray(expect[("a", "ra0")])
    base[5000:5000 + len(patch)] = patch
    expect[("a", "ra0")] = bytes(base)

    ios = {"a": io_a, "b": io_b}
    jobs = [(pool_name, oid) for pool_name, oid in expect]
    if concurrent:
        datas = await asyncio.gather(
            *(ios[p].read(oid, timeout=120) for p, oid in jobs))
        got = dict(zip(jobs, datas))
        # sub-range reads coalesce too
        parts = await asyncio.gather(
            *(ios[p].read(oid, offset=100, length=1000, timeout=120)
              for p, oid in jobs))
        got_parts = dict(zip(jobs, parts))
    else:
        got = {}
        got_parts = {}
        for p, oid in jobs:
            got[(p, oid)] = await ios[p].read(oid, timeout=120)
            got_parts[(p, oid)] = await ios[p].read(
                oid, offset=100, length=1000, timeout=120)
    return client, expect, got, got_parts, (pool_a, io_a)


@contention_retry()
def test_batched_reads_bit_exact_vs_per_op_path():
    """THE round-16 read gate: concurrent reads through the read
    coalescer (verify-on-read enabled) return byte-identical data to
    the same reads issued one at a time through one shard and 1-op
    ticks — full and sub-range reads, mixed profiles, plus a degraded
    fast-k read with a shard holder stopped."""
    async def run_path(coalesced: bool):
        cfg = _fast_config()
        if not coalesced:
            # the per-op reference on the one path: a cap of one
            cfg.osd_op_shards = 1
            cfg.osd_batch_tick_ops = 1
        cluster = await start_cluster(5, config=cfg)
        try:
            client, expect, got, got_parts, (pool_a, io_a) = \
                await _read_workload(cluster, concurrent=coalesced)
            for key, payload in expect.items():
                assert got[key] == payload, key
                assert got_parts[key] == payload[100:1100], key
            # degraded fast-k: stop a NON-primary holder of ra1 and
            # read again — correctness never rests on the fast path
            pgid = client.objecter.object_pgid(pool_a, "ra1")
            _, _, acting, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)
            # the victim must hold a DATA shard (k=2: shards 0/1), so
            # the degraded read really exercises a reconstructing
            # decode, not a parity-free assembly
            victim = next(acting[s] for s in range(2)
                          if acting[s] >= 0 and acting[s] != primary)
            await cluster.kill_osd(victim)
            degraded = await io_a.read("ra1", timeout=120)
            assert degraded == expect[("a", "ra1")]
            ticks = sum(o.perf.get("osd_read_batch_ticks")
                        for o in cluster.osds.values())
            if coalesced:
                # healthy reads short-circuit (pure host interleave +
                # inline hw crc); the DEGRADED decode above is what
                # must ride a coalesced tick
                assert ticks > 0
            else:
                # the reference coalesced nothing: every tick held one
                # decode
                assert ticks == sum(
                    o.perf.get("osd_read_batch_coalesced")
                    for o in cluster.osds.values())
            return {k: (got[k], got_parts[k]) for k in expect}, degraded
        finally:
            await cluster.stop()

    batched = run(run_path(True))
    serial = run(run_path(False))
    assert batched == serial


@contention_retry()
def test_recovery_reencode_through_seam_heals_blanked_shard():
    """Recovery rebuild rides the coalescer seam: blank one member's
    shard entirely, let scrub's generation/crc detection rebuild it,
    and assert the healed shard is byte-identical to its pre-damage
    state (the reencode path's end-to-end exactness witness)."""
    async def scenario():
        cluster = await start_cluster(4)
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "vrc", "erasure", pg_num=4,
                ec_profile={"plugin": "jerasure",
                            "technique": "reed_sol_van",
                            "k": "2", "m": "1"})
            io = client.ioctx(pool)
            payload = bytes(range(256)) * 128
            await io.write_full("heal", payload, timeout=120)
            pgid = client.objecter.object_pgid(pool, "heal")
            coll = f"pg_{pgid.pool}_{pgid.seed}"
            _, _, acting, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)
            victim = next(o for o in acting if o >= 0 and o != primary)
            before = bytes(cluster.osds[victim].store.read(coll, "heal"))
            # rot the victim's shard in place (crc now mismatches)
            cluster.osds[victim].store.debug_bitrot(coll, "heal", 999)
            rep = await cluster.osds[primary].scrub_pg(
                cluster.osds[primary].pgs[pgid])
            assert "heal" in rep["repaired"], rep
            # the repair push is fire-and-forget: converge-poll the
            # victim's store to a wall deadline instead of racing it
            deadline = asyncio.get_event_loop().time() + 20.0
            after = None
            while asyncio.get_event_loop().time() < deadline:
                after = bytes(
                    cluster.osds[victim].store.read(coll, "heal"))
                if after == before:
                    break
                await asyncio.sleep(0.05)
            assert after == before
            assert crcmod.crc32c(0xFFFFFFFF, after) == int(
                cluster.osds[victim].store.getattr(coll, "heal",
                                                   "hinfo_crc"))
        finally:
            await cluster.stop()

    run(scenario())
