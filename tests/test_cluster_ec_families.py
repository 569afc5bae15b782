"""Cluster-layer tests for the non-MDS EC plugin families: LRC and SHEC
pools end-to-end, including parity-shard loss and recovery.

The tier-3 analog of qa/standalone/erasure-code/test-erasure-code.sh's
per-plugin pool matrix (reference :21-53 creates EC pools for every
plugin and reads back with injected chunk deletion).
"""

import asyncio

from tests._flaky import contention_retry
import pytest

from ceph_tpu.cluster.vstart import _fast_config, start_cluster


def run(coro):
    return asyncio.run(coro)


def _coll(pgid):
    return f"pg_{pgid.pool}_{pgid.seed}"


@contention_retry()
def test_lrc_pool_end_to_end():
    async def scenario():
        cluster = await start_cluster(8)
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "lrcp", "erasure", pg_num=4,
                ec_profile={"plugin": "lrc", "k": "4", "m": "2", "l": "3"})
            io = client.ioctx(pool)
            payload = b"lrc-payload" * 400
            await io.write_full("obj", payload, timeout=120)
            assert await io.read("obj", timeout=120) == payload

            # a pool of the product plane since PR 36: the flattened
            # layers behind the one engine seam, shards planar at rest
            pgid = client.objecter.object_pgid(pool, "obj")
            layouts = [osd.store.object_layout(_coll(pgid), "obj")
                       for osd in cluster.osds.values()
                       if "obj" in osd.store.list_objects(_coll(pgid))]
            assert layouts == ["planar8"] * 8, layouts

            # kill a shard holder; degraded read must still work
            _, _, acting, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)
            victim = next(o for o in acting if o != primary and o >= 0)
            await cluster.kill_osd(victim)
            await cluster.wait_down(victim)
            assert await io.read("obj", timeout=60) == payload
        finally:
            await cluster.stop()

    run(scenario())


@pytest.mark.parametrize("profile,osds,lost", [
    pytest.param({"plugin": "shec", "k": "4", "m": "3", "c": "2"}, 8, "parity",
                 id="parity"),
    # a DATA holder of SHEC(6,4,3), chunk 3: the first k holders that
    # are left, (0, 1, 2, 4, 5, 6), do not give it (parity 6 covers
    # chunks 0-2), and until PR 44 the degraded read below answered
    # -5: ECError("shec: can't find recover matrix")
    pytest.param({"plugin": "shec", "k": "6", "m": "4", "c": "3"}, 11, 3,
                 id="data"),
])
def test_shec_pool_parity_shard_loss_recovers(profile, osds, lost):
    """Losing a PARITY shard of a shec pool re-protects via the batched
    parity-recovery path (the NotImplementedError hole VERDICT r2 called
    out, reference ErasureCodeShec.cc:526-756); losing a DATA shard reads
    back from the chunks the code names (``decode_sources``) and
    re-protects the same way."""
    async def scenario():
        cfg = _fast_config()
        # the re-protection below needs the dead holder marked OUT: the
        # product configuration's 600 s (PR 28) is not a test's
        cfg.mon_osd_down_out_interval = 2.0
        # one OSD more than shards: a replacement member must exist after
        # the holder dies, or CRUSH can never fill the hole
        cluster = await start_cluster(osds, config=cfg)
        try:
            client = await cluster.client()
            pool = await client.pool_create("shecp", "erasure", pg_num=4,
                                            ec_profile=dict(profile))
            io = client.ioctx(pool)
            payload = b"shec-payload" * 300
            await io.write_full("obj", payload, timeout=120)
            assert await io.read("obj", timeout=120) == payload

            pgid = client.objecter.object_pgid(pool, "obj")
            _, _, acting, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)
            k = int(profile["k"])
            if lost == "parity":
                # shard ids follow acting positions; pick a parity holder
                victim = next(o for i, o in enumerate(acting)
                              if i >= k and o >= 0 and o != primary)
            else:
                victim = acting[lost]
                assert victim >= 0 and victim != primary
            await cluster.kill_osd(victim)
            await cluster.wait_down(victim)

            # degraded read (parity loss doesn't block data; a data loss
            # decodes)
            assert await io.read("obj", timeout=60) == payload

            # after auto-out + remap, recovery must rebuild the parity
            # shard on the replacement member (batched parity decode)
            deadline = asyncio.get_event_loop().time() + 20
            reprotected = False
            while asyncio.get_event_loop().time() < deadline:
                _, _, acting2, _ = \
                    cluster.mon.osdmap.pg_to_up_acting_osds(pgid)
                live = [o for o in acting2 if o >= 0 and o in cluster.osds]
                if victim not in acting2 and len(live) == len(acting):
                    holders = 0
                    for i, o in enumerate(acting2):
                        if o < 0 or o not in cluster.osds:
                            continue
                        osd = cluster.osds[o]
                        if osd.store.stat(_coll(pgid), "obj") is not None:
                            holders += 1
                    if holders == len(acting):
                        reprotected = True
                        break
                await asyncio.sleep(0.2)
            assert reprotected, "shec parity shard was never rebuilt"
            unrecoverable = sum(o.perf.get("osd_unrecoverable")
                                for o in cluster.osds.values())
            assert unrecoverable == 0
            assert await io.read("obj", timeout=60) == payload
        finally:
            await cluster.stop()

    run(scenario())


def test_jerasure_cauchy_pool_end_to_end():
    """A packet-interleaved bit-matrix codec through the cluster stripe
    path (batch layout consistent with single-stripe encode)."""
    async def scenario():
        cfg = _fast_config()
        # stripe unit must be a multiple of w*packetsize for the packet
        # layout; choose packetsize = 64 -> 8*64 = 512 divides 4096
        cluster = await start_cluster(4, config=cfg)
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "cauchyp", "erasure", pg_num=4,
                ec_profile={"plugin": "jerasure", "technique": "cauchy_good",
                            "k": "2", "m": "1", "packetsize": "64"})
            io = client.ioctx(pool)
            payload = b"cauchy-bytes" * 500
            await io.write_full("obj", payload, timeout=120)
            assert await io.read("obj", timeout=120) == payload
            # partial overwrite through the RMW path
            await io.write("obj", b"PATCH" * 100, offset=1000, timeout=120)
            expect = bytearray(payload)
            expect[1000:1000 + 500] = b"PATCH" * 100
            assert await io.read("obj", timeout=120) == bytes(expect)
        finally:
            await cluster.stop()

    run(scenario())
