"""The LRC k=4 m=2 l=3 pool on the product data plane (PR 37).

The plain reference is ``ErasureCodeLrc.encode_chunks`` /
``decode_chunks``: the literal layer walk the golden vectors pin, on
seeded random data.  The code is bytewise, so the walk over whole shards
(every stripe's chunk of a shard, concatenated) is the walk stripe by
stripe.  Under test: the flattened engine behind ``codec.matrix_engine``
that the plane entry points of ``ec/stripe.py`` drive, the chunks a
decode multiplies of those that came (``decode_sources`` ->
``stripe._decode_src``), and the served pool on 8 OSDs.  Whom the read
gather of ``backend_ec.py`` asks first is the code's choice since PR 44
(``tests/test_shec_deployment.py`` freezes it for this pool); peering
and recovery are the parent's.
"""

import asyncio
import itertools
import json

import numpy as np
import pytest

import chip_smoke
from ceph_tpu.cluster.vstart import _fast_config, start_cluster
from ceph_tpu.ec import factory, planar_store
from ceph_tpu.ec import stripe as stripemod
from ceph_tpu.ec.codec import matrix_engine
from ceph_tpu.ec.interface import ECError
from ceph_tpu.ops.crc32c import crc32c
from ceph_tpu.utils.perf import KERNELS
from _flaky import contention_retry

LRC = {"plugin": "lrc", "k": "4", "m": "2", "l": "3"}
K, N, UNIT = 4, 8, 4096
# logical shard ids: data 0-3, then the coding chunks in mapping order
# (DD__DD__ / DDc_DDc_ / DDDc____ / ____DDDc): 4 and 6 the global
# parities, 5 and 7 the local ones; every chunk lies in one group
GROUPS = ({0, 1, 4, 5}, {2, 3, 6, 7})


def run(coro):
    return asyncio.run(coro)


def kernels():
    return {k: v for k, v in KERNELS.dump()["device_kernels"].items()
            if isinstance(v, (int, float))}


def grew(before):
    now = kernels()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


def lrc():
    return factory(dict(LRC))


def walk_shards(codec, data: bytes) -> np.ndarray:
    """(n, shard_len) shard rows of ``data`` by the literal layer walk."""
    return chip_smoke.lrc_walk_shards(codec, stripemod.StripeInfo(K, UNIT),
                                      data)


def walk_decodes(codec, lost, want=None) -> bool:
    """Does the layer walk rebuild ``want`` (default: all of ``lost``)
    from the chunks that are not ``lost``?"""
    pos = codec.chunk_mapping
    have = {pos[s]: np.zeros(8, dtype=np.uint8)
            for s in range(N) if s not in lost}
    decoded = {p: np.zeros(8, dtype=np.uint8) for p in range(N)}
    try:
        codec.decode_chunks({pos[s] for s in (lost if want is None else want)},
                            have, decoded)
    except ECError:
        return False
    return True


def seeded(seed: int, size: int) -> bytes:
    return np.random.default_rng([37, seed, size]).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def planes_of(rows: np.ndarray) -> dict:
    return {s: planar_store.rows_to_planes(rows[s:s + 1])
            for s in range(rows.shape[0])}


# ------------------------------------------------------------ the seam

def test_lrc_takes_the_one_engine_seam():
    codec = lrc()
    eng = matrix_engine(codec)
    assert eng is not None and eng.coding.shape == (4, 4)
    assert eng._enc_bitmat.shape == (32, 32)
    assert matrix_engine(codec) is eng          # kept on the codec
    assert stripemod.planar_at_rest_ok(codec, UNIT)
    assert stripemod._host_engine_ok(codec)      # the suite runs on the CPU
    # the local parities are XORs of their group, global parity included
    walk = np.vstack([np.eye(K, dtype=np.uint8), eng.coding])
    for group, local in zip(GROUPS, (5, 7)):
        rest = np.bitwise_xor.reduce(walk[sorted(group - {local})], axis=0)
        assert np.array_equal(walk[local], rest)


# the k=4 m=2 l=3 shape with a packet-interleaved global layer
_LAYERS_WITH_A_PACKET_LAYER = {
    "plugin": "lrc", "mapping": "DD__DD__", "layers": json.dumps(
        [["DDc_DDc_", {"plugin": "jerasure", "technique": "cauchy_good",
                       "packetsize": "64"}],
         ["DDDc____", ""], ["____DDDc", ""]])}


@pytest.mark.parametrize("profile,unit,planar", [
    # bytewise GF(2^8) matrix codes: the product plane
    pytest.param(LRC, 4096, True, id="lrc_kml"),
    pytest.param({"plugin": "lrc", "mapping": "DD_", "layers":
                  json.dumps([["DDc", ""]])}, 4096, True,
                 id="lrc_layers_of_rs"),
    pytest.param({"plugin": "shec", "k": "4", "m": "3", "c": "2"}, 4096,
                 True, id="shec"),
    pytest.param({"plugin": "jerasure", "technique": "reed_sol_van",
                  "k": "4", "m": "2"}, 4096, True, id="rs_van"),
    pytest.param({"plugin": "isa", "k": "8", "m": "4"}, 4096, True,
                 id="isa"),
    # a w = 8 packet-interleaved bit-matrix code too, since PR 48: its
    # chunks rest as their packet-row matrix (another serialization of
    # the same plane; tests/test_cauchy_deployment.py)
    pytest.param({"plugin": "jerasure", "technique": "cauchy_good",
                  "k": "4", "m": "2"}, 16384, True, id="cauchy_good"),
    # every other code keeps byte-at-rest, by the same one question
    pytest.param({"plugin": "jerasure", "technique": "liberation",
                  "k": "4", "m": "2", "w": "7"}, 14336, False,
                 id="liberation"),
    pytest.param({"plugin": "jerasure", "technique": "reed_sol_van",
                  "k": "4", "m": "2", "w": "16"}, 4096, False, id="rs_w16"),
    pytest.param(_LAYERS_WITH_A_PACKET_LAYER, 4096, False,
                 id="lrc_layers_with_a_packet_layer"),
])
def test_which_codes_pass_the_seam_and_the_layout_each_gets(profile, unit,
                                                            planar):
    codec = factory(dict(profile))
    assert (matrix_engine(codec) is not None) == planar
    assert stripemod.planar_at_rest_ok(codec, unit) == planar
    assert stripemod._host_engine_ok(codec) == planar
    assert not stripemod.planar_at_rest_ok(codec, unit + 4)


@pytest.mark.parametrize("size,count,host", [
    (4096, 1, True), (4096, 16, True),
    (65537, 1, True), (65537, 16, True),
    (4 << 20, 1, True), (4 << 20, 16, True),
    # the device branch of the tick (XLA's planar matmul and the
    # chunk-crc program off the chip), at a size the emulation affords
    (65537, 16, False),
])
def test_planar_tick_is_the_layer_walk(size, count, host, monkeypatch):
    if not host:
        monkeypatch.setattr(stripemod, "_host_engine_ok", lambda c: False)
    codec = lrc()
    sinfo = stripemod.StripeInfo(K, UNIT)
    datas = [seeded(i, size) for i in range(count)]
    before = kernels()
    out = stripemod.encode_planes_multi(codec, sinfo, datas,
                                        [True] * count)
    g = grew(before)
    if host:
        assert g.get("ec_host_planar_matmul_calls", 0) >= 1
        assert not g.get("planar_matmul_calls", 0)
    else:
        assert g.get("planar_matmul_calls", 0) == 1    # ONE launch a tick
        assert not g.get("ec_host_planar_matmul_calls", 0)
    # one walk over every op's stripes, end to end: the code is bytewise
    shard_len = sinfo.shard_size(size)
    want = walk_shards(codec, b"".join(
        d.ljust(sinfo.object_stripes(size) * sinfo.stripe_width, b"\0")
        for d in datas))
    for i, (planes, crcs) in enumerate(out):
        assert planes.shape == (N, 8, shard_len // 8)
        for s in range(N):
            ref = want[s, i * shard_len:(i + 1) * shard_len]
            got = planar_store.planes_to_shard(planes[s], seam=None)
            assert got == ref.tobytes(), (i, s)
            assert int(crcs[s]) == crc32c(0xFFFFFFFF, ref.tobytes()), (i, s)


def test_a_stack_the_seam_refuses_is_served_byte_at_rest_by_the_walk():
    """Explicit ``layers=`` with a packet-interleaved layer: no flat
    generator (the bytes are not the bytewise product of any matrix), so
    the batch paths of ``stripe.py`` get the literal walk over the shard
    rows, at a stripe unit the layer's packets divide.  (The parent's
    flattened batch differed from the walk here.)"""
    codec = factory(dict(_LAYERS_WITH_A_PACKET_LAYER))
    assert matrix_engine(codec) is None
    unit = codec.stripe_unit(4000)
    assert unit == 4096 and unit % (8 * 64) == 0
    assert lrc().stripe_unit(4000) == 4000      # bytewise layers: w bytes
    sinfo = stripemod.StripeInfo(K, unit)
    data = seeded(12, 3 * K * unit + 7)
    rows = chip_smoke.lrc_walk_shards(codec, sinfo, data)
    assert np.array_equal(stripemod.encode_stripes(codec, sinfo, data), rows)
    (got, crcs), _small = stripemod.encode_stripes_multi(
        codec, sinfo, [data, data[:100]], [True, True])
    assert np.array_equal(got, rows)
    assert [int(c) for c in crcs] == \
        [crc32c(0xFFFFFFFF, r.tobytes()) for r in rows]
    for lost in ((0,), (5,), (1, 4), (2, 6)):
        have = {s: rows[s] for s in range(N) if s not in lost}
        assert stripemod.decode_stripes_multi(
            codec, sinfo, [(have, len(data))])[0] == data, lost
        assert np.array_equal(stripemod.reencode_stripes_multi(
            codec, sinfo, [(have, len(data))])[0], rows), lost
    with pytest.raises(ECError):                # the rank-3 group
        stripemod.decode_stripes_multi(
            codec, sinfo, [({s: rows[s] for s in (2, 3, 6, 7)}, len(data))])


def test_the_pools_first_tick_warms_a_full_ticks_buckets(monkeypatch):
    """``_warm_tick_buckets`` meets this pool's buckets as any pool's:
    the first device tick of ops of a size runs the ingest, the
    flattened encode and the chunk-crc program at the buckets a full
    tick would meet, off the counters (0 compiles in a window)."""
    import threading

    monkeypatch.setattr(stripemod, "_host_engine_ok", lambda codec: False)
    monkeypatch.setattr(stripemod, "_WARM_BUCKETS", set())
    monkeypatch.setattr(stripemod, "_WARM_SIZES", set())
    chains, done, real = [], threading.Event(), stripemod._warm_buckets

    def spy(codec, sinfo, shape, chain, crcs):
        real(codec, sinfo, shape, chain, crcs)
        chains.append((shape, chain, crcs))
        done.set()

    monkeypatch.setattr(stripemod, "_warm_buckets", spy)
    failures = []
    monkeypatch.setattr(stripemod.logging.getLogger("ceph_tpu.ec"),
                        "exception", lambda *a, **k: failures.append(a))
    codec = lrc()
    sinfo = stripemod.StripeInfo(K, UNIT)
    before = kernels()
    out = stripemod.encode_planes_multi(
        codec, sinfo, [seeded(1, 2 * sinfo.stripe_width)], [True], 4)
    assert out[0][0].shape == (N, 8, 2 * UNIT // 8)
    assert done.wait(120) and not failures
    (shape, chain, crcs), = chains
    assert shape == (type(codec), K, N, UNIT, True) and crcs
    assert chain == [2, 4, 8]
    assert {b for s, b in stripemod._WARM_BUCKETS if s == shape} == {2, 4, 8}
    # the tick's one matmul; the warm's are off the counters
    assert grew(before)["planar_matmul_calls"] == 1


# --------------------------------------------- decode, in the plane domain

_PATTERNS = [c for r in (1, 2) for c in itertools.combinations(range(N), r)]


@pytest.mark.parametrize("lost", _PATTERNS,
                         ids=["-".join(map(str, p)) for p in _PATTERNS])
def test_erasures_decode_in_the_plane_domain_to_the_walks_answer(lost):
    """Every single erasure and every pair, all the other chunks
    handed over: the plane domain gives the walk's answer, or refuses
    where the walk gives up."""
    codec = lrc()
    sinfo = stripemod.StripeInfo(K, UNIT)
    data = seeded(sum(lost) + 10 * len(lost), 65537)
    rows = walk_shards(codec, data)
    have = {s: p for s, p in planes_of(rows).items() if s not in lost}
    lost_data = set(lost) & set(range(K))
    if not walk_decodes(codec, set(lost), lost_data):
        with pytest.raises(ECError):
            stripemod.reencode_planes_multi(codec, sinfo,
                                            [(have, len(data))])
        return
    before = kernels()
    full = stripemod.reencode_planes_multi(
        codec, sinfo, [(have, len(data))])[0]
    for s in range(N):
        assert planar_store.planes_to_shard(full[s], seam=None) == \
            rows[s].tobytes(), s
    assert stripemod.decode_planes_multi(
        codec, sinfo, [(have, len(data))])[0] == data
    g = grew(before)
    assert "ec_decode_sources_refused" not in g
    assert "ec_planar_relayout_conversions" not in g     # the plane path
    assert "ec_matmul_calls" not in g


def test_every_4_subset_decodes_right_or_is_refused_never_wrong():
    codec = lrc()
    eng = matrix_engine(codec)
    sinfo = stripemod.StripeInfo(K, UNIT)
    data = seeded(4, 3 * K * UNIT + 5)
    rows = walk_shards(codec, data)
    planes = planes_of(rows)
    refused = []
    for src in itertools.combinations(range(N), K):
        have = {s: planes[s] for s in src}
        lost = set(range(N)) - set(src)
        lost_data = lost & set(range(K))
        # the generator's rank over the subset says what CAN decode
        gen = np.vstack([np.eye(K, dtype=np.uint8), eng.coding])[list(src)]
        full_rank = _gf_rank(gen) == K
        # refused exactly where the reference's walk gives up (it gives
        # up on some sets of full rank too: a local parity whose global
        # one is lost helps no layer)
        ok = walk_decodes(codec, lost, lost_data)
        assert not ok or full_rank, src     # never a rank < k accepted
        before = kernels()
        if not ok:
            refused.append(set(src))
            with pytest.raises(ECError):
                eng.decode_matrix(src, tuple(range(K)))
            with pytest.raises(ECError):
                stripemod.decode_planes_multi(
                    codec, sinfo, [(have, len(data))])
            with pytest.raises(ECError):
                stripemod.reencode_planes_multi(
                    codec, sinfo, [(have, len(data))])
            g = grew(before)
            assert g["ec_decode_sources_refused"] == 2, (src, g)
            # refused BEFORE any multiply, on either engine
            assert not any("matmul" in name for name in g), (src, g)
            continue
        assert stripemod.decode_planes_multi(
            codec, sinfo, [(have, len(data))])[0] == data, src
        if lost_data:
            assert "ec_decode_sources_refused" not in grew(before)
    assert len(refused) == 37
    for group in GROUPS:                    # rank 3: refused
        assert group in refused
    assert {1, 2, 3, 5} in refused          # 0 and 4 lost, the first k


def _gf_rank(mat: np.ndarray) -> int:
    from ceph_tpu.ops.gf8 import GF_MUL, gf_inv

    m = [list(map(int, r)) for r in mat]
    rank = 0
    for col in range(len(m[0])):
        piv = next((r for r in range(rank, len(m)) if m[r][col]), None)
        if piv is None:
            continue
        m[rank], m[piv] = m[piv], m[rank]
        inv = int(gf_inv(m[rank][col]))
        m[rank] = [int(GF_MUL[inv][v]) for v in m[rank]]
        for r in range(len(m)):
            if r != rank and m[r][col]:
                f = m[r][col]
                m[r] = [a ^ int(GF_MUL[f][b]) for a, b in zip(m[r], m[rank])]
        rank += 1
    return rank


def test_chunks_1_2_3_5_for_shard_0_are_refused_before_multiplying():
    codec = lrc()
    sinfo = stripemod.StripeInfo(K, UNIT)
    data = seeded(5, 65537)
    planes = planes_of(walk_shards(codec, data))
    have = {s: planes[s] for s in (1, 2, 3, 5)}
    before = kernels()
    with pytest.raises(ECError, match="do not decode"):
        stripemod.decode_planes_multi(codec, sinfo, [(have, len(data))])
    g = grew(before)
    assert g["ec_decode_sources_refused"] == 1
    assert not any("matmul" in name for name in g), g
    # one more chunk of full rank, in any place: the first k of those
    # that came that the layers decode from, and the right bytes
    have[6] = planes[6]
    assert stripemod._decode_src(codec, (0,), (0, 4, 7)) == (1, 2, 3, 6)
    assert stripemod.decode_planes_multi(
        codec, sinfo, [(have, len(data))])[0] == data


def test_the_mesh_adapter_asks_the_same_seam_and_the_codes_sources():
    """``wrap_codec_for_mesh`` wraps what ``matrix_engine`` admits: the
    LRC rides the mesh engine with its flattened generator, and a
    decode multiplies the sources the code chooses by the code's own
    recovery matrix (no survivor-submatrix inversion); a code the seam
    refuses comes back unwrapped."""
    from ceph_tpu.parallel.engine import MeshCodecAdapter, \
        wrap_codec_for_mesh

    codec = lrc()
    mesh = wrap_codec_for_mesh(codec, 2)
    assert isinstance(mesh, MeshCodecAdapter)
    assert not stripemod.planar_at_rest_ok(mesh, UNIT)   # byte batches
    sinfo = stripemod.StripeInfo(K, UNIT)
    data = seeded(8, 5 * sinfo.stripe_width)
    rows = walk_shards(codec, data)
    assert np.array_equal(stripemod.encode_stripes(mesh, sinfo, data), rows)
    full = rows.reshape(N, 5, UNIT).transpose(1, 0, 2).copy()
    for lost in ((0,), (2, 6), (0, 4)):
        broken = full.copy()
        broken[:, list(lost)] = 0
        got = np.asarray(mesh.decode_batch(lost, broken))
        assert np.array_equal(got, full[:, list(lost)]), lost
    with pytest.raises(ECError):
        mesh.decode_batch((0, 4, 6, 7), full, want=(0,))    # {1, 2, 3, 5}
    other = factory({"plugin": "jerasure", "technique": "cauchy_good",
                     "k": "4", "m": "2"})
    assert wrap_codec_for_mesh(other, 2) is other


# ------------------------------- the accepted deployments' rule, frozen

_MDS = {
    "k2m1": ({"plugin": "jerasure", "technique": "reed_sol_van",
              "k": "2", "m": "1"}, 2, 3),
    "k4m2": ({"plugin": "jerasure", "technique": "reed_sol_van",
              "k": "4", "m": "2"}, 4, 6),
    "k8m4": ({"plugin": "isa", "k": "8", "m": "4"}, 8, 12),
}


@pytest.mark.parametrize("name,lost", [
    (name, lost) for name, (_p, _k, n) in _MDS.items() for lost in range(n)])
def test_an_mds_decode_multiplies_the_first_k_present(name, lost,
                                                      monkeypatch):
    """The parent's rule at the four decode sites of ``stripe.py``,
    frozen: with any one holder lost, the chunks a decode multiplies are
    the first k of those that came, and the code has no opinion."""
    profile, k, n = _MDS[name]
    codec = factory(dict(profile))
    sinfo = stripemod.StripeInfo(k, UNIT)
    first_k = tuple(s for s in range(n) if s != lost)[:k]
    assert codec.decode_sources({lost}, [s for s in range(n)
                                         if s != lost]) is None
    assert stripemod._decode_src(codec, (lost,), (lost,)) == first_k
    multiplied = []
    real = stripemod._host_decode_matrix

    def spy(codec, src, want):
        multiplied.append((tuple(src), tuple(want)))
        return real(codec, src, want)

    monkeypatch.setattr(stripemod, "_host_decode_matrix", spy)
    data = seeded(lost, 3 * k * UNIT + 1)
    rows = stripemod.encode_stripes(codec, sinfo, data)
    have = {s: rows[s] for s in range(n) if s != lost}
    before = kernels()
    assert stripemod.decode_stripes_multi(
        codec, sinfo, [(have, len(data))])[0] == data
    assert np.array_equal(stripemod.reencode_stripes_multi(
        codec, sinfo, [(have, len(data))])[0], rows)
    planes = {s: p for s, p in planes_of(rows).items() if s != lost}
    assert stripemod.decode_planes_multi(
        codec, sinfo, [(planes, len(data))])[0] == data
    full = stripemod.reencode_planes_multi(
        codec, sinfo, [(planes, len(data))])[0]
    assert planar_store.planes_to_shard(full[lost], seam=None) == \
        rows[lost].tobytes()
    want = (lost,) if lost < k else ()
    # a lost parity is no decode: the data is there, parity re-encodes
    assert multiplied == ([(first_k, want)] * 4 if want else [])
    assert "ec_decode_sources_refused" not in grew(before)


# ------------------------------------------------------ the served pool

async def _holders(cluster, oid):
    out = {}
    for i, osd in cluster.osds.items():
        for coll in osd.store.list_collections():
            if oid in osd.store.list_objects(coll):
                out[i] = (coll, osd.store.object_layout(coll, oid),
                          int(osd.store.getattr(coll, oid, "shard")))
    return out


async def _health_ok(client, deadline_s=120.0):
    loop = asyncio.get_event_loop()
    t0 = loop.time()
    health = {}
    while loop.time() - t0 < deadline_s:
        health = await client.objecter.mon_command({"prefix": "health"})
        if health["status"] == "HEALTH_OK":
            return
        await asyncio.sleep(0.2)
    raise TimeoutError(f"not HEALTH_OK: {health}")


@contention_retry()
def test_served_pool_planar_at_rest_degraded_reads_recovery_and_rmw():
    """``cell.py``'s entry points: 4 MiB write_full -> planar on all 8
    holders -> read -> each holder killed in turn -> degraded read right
    (the parent's gather, decoded in the plane domain) -> revived empty
    and marked in -> its shard rebuilt, equal to the walk's -> a partial
    overwrite reads back right."""
    payload = seeded(99, 4 << 20)

    async def scenario():
        cluster = await start_cluster(8, config=_fast_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "bench", "erasure", pg_num=16, ec_profile=dict(LRC))
            io = client.ioctx(pool)
            before = kernels()
            await io.write_full("obj", payload, timeout=120)
            g = grew(before)
            # the host GF engine with the flattened matrix, ONE call a
            # tick; no XLA bit-matmul; every shard to rest as planes
            # with the store's one copy: 2.0 bytes a byte ingested
            assert g.get("ec_host_planar_matmul_calls", 0) == 1, g
            assert g["ec_host_planar_matmul_bytes"] == 4 << 20, g
            assert not g.get("planar_matmul_calls", 0), g
            assert not g.get("ec_matmul_calls", 0), g
            assert g["ec_planar_ingest_bytes"] == 4 << 20, g
            assert g["store_planar_write_bytes"] == 8 << 20, g
            assert g["store_planar_direct_bytes"] == 8 << 20, g
            held = await _holders(cluster, "obj")
            assert len(held) == 8 and \
                {ly for _c, ly, _s in held.values()} == \
                {planar_store.LAYOUT_PLANAR}
            assert await io.read("obj", timeout=60) == payload
            rows = walk_shards(lrc(), payload)
            for osd_id, (coll, _ly, shard) in sorted(held.items()):
                await cluster.kill_osd(osd_id)
                await cluster.wait_down(osd_id)
                before = kernels()
                assert await io.read("obj", timeout=60) == payload, osd_id
                g = grew(before)
                if shard < K:       # one decode in the plane domain
                    assert g.get("ec_coalesced_read_ticks", 0) >= 1, g
                    assert g.get("ec_host_planar_matmul_calls", 0) >= 1, g
                assert "ec_decode_sources_refused" not in g, (osd_id, g)
                assert "ec_planar_relayout_conversions" not in g, g
                # recovery: the empty OSD's shard rebuilt
                await cluster.revive_osd(osd_id)
                before = kernels()
                await client.objecter.mon_command(
                    {"prefix": "osd in", "id": osd_id})
                await _health_ok(client)
                store = cluster.osds[osd_id].store
                for _ in range(100):
                    if "obj" in store.list_objects(coll):
                        break
                    await asyncio.sleep(0.1)
                g = grew(before)
                assert g.get("ec_coalesced_reencode_ticks", 0) >= 1, g
                assert "ec_decode_sources_refused" not in g, (osd_id, g)
                assert "ec_planar_relayout_conversions" not in g, g
                assert store.object_layout(coll, "obj") == \
                    planar_store.LAYOUT_PLANAR
                assert bytes(store.read(coll, "obj")) == \
                    rows[shard].tobytes(), (osd_id, shard)
            assert await io.read("obj", timeout=60) == payload
            # RMW of a planar LRC object, unaligned, across stripes
            patch = seeded(7, 40000)
            await io.write("obj", patch, offset=123457, timeout=120)
            want = bytearray(payload)
            want[123457:123457 + len(patch)] = patch
            assert await io.read("obj", timeout=60) == bytes(want)
            held = await _holders(cluster, "obj")
            rows = walk_shards(lrc(), bytes(want))
            for osd_id, (coll, ly, shard) in held.items():
                assert ly == planar_store.LAYOUT_PLANAR
                assert bytes(cluster.osds[osd_id].store.read(coll, "obj")) \
                    == rows[shard].tobytes(), (osd_id, shard)
        finally:
            await cluster.stop()

    run(scenario())


@contention_retry()
@pytest.mark.parametrize("pair", [(0, 4), (1, 4), (2, 3), (0, 1)],
                         ids=["0-4", "1-4", "2-3", "0-1"])
def test_two_holders_down_reads_right(pair):
    """With two holders down the first k shards in shard order can be a
    set the layers do not decode from ((0, 1), (0, 4), (1, 4), (2, 3)
    down).  Until PR 43 the gather's fast path resolved on any k of one
    generation and the served read was then refused (EIO, before a
    multiply: the parent's byte path raised there too).  It now asks the
    code (``decode_sources``) before it resolves, so a k that does not
    decode widens the gather to the holders not heard from, as a short
    one does, and the read returns the bytes.  Since PR 44 the gather
    asks the code whom to ask FIRST (``backend_ec.first_ask``), so these
    reads need no second round; recovery's and peering's choice are
    still ROADMAP B6's."""
    payload = seeded(sum(pair), 1 << 20)

    async def scenario():
        cluster = await start_cluster(8, config=_fast_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "bench", "erasure", pg_num=16, ec_profile=dict(LRC))
            io = client.ioctx(pool)
            await io.write_full("obj", payload, timeout=120)
            held = {shard: osd_id for osd_id, (_c, _ly, shard)
                    in (await _holders(cluster, "obj")).items()}
            for shard in pair:
                await cluster.kill_osd(held[shard])
                await cluster.wait_down(held[shard])
            before = kernels()
            assert await io.read("obj", timeout=60) == payload
            assert "ec_decode_sources_refused" not in grew(before)
        finally:
            await cluster.stop()

    run(scenario())


@contention_retry()
@pytest.mark.parametrize("slow", [(4, 6, 7), (4, 6)],
                         ids=["then-4", "then-7"])
def test_a_hedged_gather_waits_for_shards_that_decode(slow):
    """One holder down (shard 0) and the holders of ``slow`` slow: the
    primary (shard 1) asks 2, 3, 4, the hedge then asks 5, 6, 7, and 5
    answers first.  (1, 2, 3, 5) are k shards of one generation that do
    not give 0 (5 is the local parity of 0, 1, 4): the gather waits for
    the next reply and the read returns the bytes.  Before PR 43 it
    resolved there and the read was refused with EIO: what a stalled
    loop did to two of the LRC cell's sixteen degraded reads (the
    driver's run of seed 200909146, PERF.md section 6).  ``then-7``:
    the next reply is the other group's local parity, and of (1, 2, 3,
    5, 7) the code multiplies (1, 2, 3, 7): 7 gives 6 from 2 and 3, and
    6 gives 0."""
    payload = seeded(7, 1 << 20)

    async def scenario():
        cluster = await start_cluster(8, config=_fast_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "bench", "erasure", pg_num=16, ec_profile=dict(LRC))
            io = client.ioctx(pool)
            await io.write_full("obj", payload, timeout=120)
            held = {shard: osd_id for osd_id, (_c, _ly, shard)
                    in (await _holders(cluster, "obj")).items()}
            await cluster.kill_osd(held[0])
            await cluster.wait_down(held[0])
            primary = cluster.osds[held[1]]
            # the new interval's watermark has to cover the object, or
            # the fast path is not taken at all
            for _ in range(100):
                fast = primary.perf.get("osd_ec_fastk_reads")
                assert await io.read("obj", timeout=60) == payload
                if primary.perf.get("osd_ec_fastk_reads") > fast:
                    break
                await asyncio.sleep(0.1)
            else:
                raise AssertionError("the fast path never resolved")

            def late(handle):
                async def handler(conn, msg):
                    # a slow holder IS the case: twelve hedge delays late
                    # graftlint: ignore[fixed-sleep-in-tests]
                    await asyncio.sleep(0.6)
                    await handle(conn, msg)
                return handler

            for shard in slow:
                osd = cluster.osds[held[shard]]
                osd._handle_ec_read = late(osd._handle_ec_read)
            before = kernels()
            hedged = primary.perf.get("osd_ec_hedged_reads")
            fast = primary.perf.get("osd_ec_fastk_reads")
            assert await io.read("obj", timeout=60) == payload
            assert primary.perf.get("osd_ec_hedged_reads") == hedged + 1
            # resolved by the fast path all the same, one reply later
            assert primary.perf.get("osd_ec_fastk_reads") == fast + 1
            assert "ec_decode_sources_refused" not in grew(before)
        finally:
            await cluster.stop()

    run(scenario())


@contention_retry()
@pytest.mark.parametrize("name,osds", [("k2m1", 3), ("k4m2", 8),
                                       ("k8m4", 12)])
def test_accepted_deployments_send_the_sub_reads_they_sent(name, osds):
    """A degraded read of an MDS pool asks, as the parent does, the
    first k live shards in the primary's order of preference (its own,
    then shard order): k - 1 sub-reads, one decode, nothing refused."""
    profile, k, _n = _MDS[name]
    payload = seeded(k, 65536)

    async def scenario():
        cluster = await start_cluster(osds, config=_fast_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "p", "erasure", pg_num=8, ec_profile=dict(profile))
            io = client.ioctx(pool)
            await io.write_full("obj", payload, timeout=120)
            pgid = client.objecter.object_pgid(pool, "obj")
            _, _, acting, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)
            # a data shard's holder that is not the primary: the read
            # has to decode
            victim = next(o for s, o in enumerate(acting)
                          if s < k and o != primary and o >= 0)

            def sub_reads():
                return sum(o.perf.dump()[f"osd.{i}"].get(
                    "osd_ec_sub_reads", 0) for i, o in cluster.osds.items()
                    if i != victim)

            await cluster.kill_osd(victim)
            await cluster.wait_down(victim)
            before, kbefore = sub_reads(), kernels()
            assert await io.read("obj", timeout=60) == payload
            assert sub_reads() - before == k - 1
            g = grew(kbefore)
            assert g["ec_coalesced_read_ticks"] == 1, g
            assert "ec_decode_sources_refused" not in g
        finally:
            await cluster.stop()

    run(scenario())


@contention_retry()
def test_chip_smokes_lrc_leg_at_tiny_size(monkeypatch):
    """``chip_smoke.py``'s phase 4 through the same code, host GF engine
    off so that the device branches serve: the tick against the walk,
    then write, read, degraded read, recovery and read on 8 OSDs."""
    monkeypatch.setattr(stripemod, "_host_engine_ok", lambda codec: False)
    chip_smoke.lrc_tick_against_the_walk(seed=11, n_objects=3,
                                         object_size=65537)
    monkeypatch.setattr(chip_smoke, "lrc_tick_against_the_walk",
                        lambda seed: None)      # done, at a size for here
    seen = []
    monkeypatch.setattr(chip_smoke, "serve_ec_objects",
                        _recording(chip_smoke.serve_ec_objects, seen))
    # off the chip the planar matmul is XLA's, one stack group a call:
    # the smoke's own rule refuses that, after everything was served
    with pytest.raises(AssertionError, match="stack-group"):
        chip_smoke.phase_lrc(11, n_objects=4, object_size=64 << 10,
                             in_flight=4)
    report, = seen
    c = report["counters"]
    assert c["store_planar_write_bytes"] >= 2 * c["ec_planar_ingest_bytes"]
    assert c.get("ec_tick_crc_device_ticks", 0) == c["ec_coalesced_ticks"]
    assert c.get("ec_coalesced_read_ticks", 0) >= 1
    assert c.get("ec_coalesced_reencode_ticks", 0) >= 1
    assert c.get("planar_matmul_calls", 0) > 0
    assert "ec_host_matmul_calls" not in c
    assert "ec_host_planar_matmul_calls" not in c
    assert "ec_decode_sources_refused" not in c
    assert report["shards_rebuilt"] > 0
    # and the rest of phase 4's rules hold of what was served
    monkeypatch.setattr(chip_smoke, "check_device_did_the_work",
                        lambda report: None)
    monkeypatch.setattr(chip_smoke, "serve_ec_objects",
                        _returning(report))
    chip_smoke.phase_lrc(11)


def _recording(fn, seen):
    async def wrapper(*args, **kwargs):
        out = await fn(*args, **kwargs)
        seen.append(out)
        return out
    return wrapper


def _returning(report):
    async def wrapper(*args, **kwargs):
        return report
    return wrapper


# ------------------------------------------- the cell, rehearsed on the CPU
#
# ``benchmark/harness`` at tiny size, as benchmark/tests/test_cell_rehearsal
# runs the accepted cells by hand: everything of a run but the look for a
# chip, host GF engine off so that the device branches serve.

CELL = "lrc_k4m2l3_write_4m_t16"
TINY = {"object_bytes": 65536, "callers": 4, "payload_pool": 4,
        "lead_in_s": 0.3}


def _run_cell(seed=5, seconds=1.5, trace=False):
    from benchmark.harness import cell as cellmod
    from benchmark.harness.loader import load_cell

    cell = load_cell(CELL)
    cell.traffic = {**cell.traffic, **TINY}
    lines = []
    out = asyncio.run(cellmod.CellRun(
        cell, seed, seconds, trace, started_at=0.0,
        say=lambda **row: lines.append(row)).run())
    out["lines"] = lines
    return out


@contention_retry()
def test_the_cell_serves_verifies_and_stays_on_the_product_plane(
        monkeypatch):
    monkeypatch.setattr(stripemod, "_host_engine_ok", lambda codec: False)
    out = _run_cell(trace=True)
    checks = {r["check"]: r for r in out["lines"] if "check" in r}
    assert out["correct"], checks
    assert out["failed"] == 0
    assert checks["degraded_decode_ticks"]["value"] >= 1
    assert checks["host_engine_calls"]["value"] == 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["rest_bytes_per_byte.write"] == pytest.approx(2.0, abs=0.05)
    # up to 8 OSDs' ticks open when the 1.5 s window closes count on one
    # side only
    assert m["tick_crc_device_share.write"] >= 95.0
    assert m["store_direct_share.write"] == 100.0
    # XLA's planar path off the chip: one group a call (4 on the chip)
    assert m["stack_groups.write"] == 1.0
    counters = next(r["window_counters"] for r in out["lines"]
                    if "window_counters" in r)
    assert not counters.get("ec_decode_sources_refused", 0)


@contention_retry()
def test_on_the_cpu_host_the_cell_is_served_by_the_host_engine():
    """How a non-MDS pool's cell is rehearsed on the CPU host since the
    host GF engine serves it: every byte reads back, and ``correct`` is
    false by the checks that say which engine ran, and no other."""
    out = _run_cell()
    checks = {r["check"]: r for r in out["lines"] if "check" in r}
    assert not out["correct"]
    assert sorted(name for name, r in checks.items() if not r["ok"]) == \
        ["device_matmul_calls", "host_engine_calls", "window_matmul_bytes"]
    assert checks["healthy_mismatches"]["value"] == 0
    assert checks["degraded_mismatches"]["value"] == 0


def test_the_roofline_counts_the_four_coding_rows():
    from benchmark.harness.loader import load_cell
    from benchmark.harness.peaks import planar_matmul_cost

    ops, moved = planar_matmul_cost(load_cell(CELL).config, 1 << 20)
    assert ops == 2.0 * 32 * 8 * (1 << 20)      # 512 int8 ops a byte
    assert moved == 2.0 * (1 << 20)             # 4 rows in, 4 rows out


@pytest.mark.parametrize("cell_name,want", [
    ("k2m1_write_4m_t16", 1.5), ("k2m1_write_64k_t16", 1.5),
    ("k4m2_write_4m_t16", 1.5), ("k8m4_write_4m_t16", 1.5), (CELL, 2.0)])
def test_rest_bytes_per_byte_reads_the_codes_geometry(cell_name, want):
    """Through the accepted ``counter_ratio`` reader, from two counters
    the parent has; a pool off the product plane grows neither, and the
    metric is left out of the line."""
    from benchmark.harness import layers
    from benchmark.harness.loader import load_cell

    cell = load_cell(cell_name)
    reader = cell.per_layer["rest_bytes_per_byte.write"]
    assert (reader["numerator"], reader["denominator"]) == \
        ("store_planar_write_bytes", "ec_planar_ingest_bytes")
    declared = set(KERNELS.dump()["device_kernels"])
    assert {reader["numerator"], reader["denominator"]} <= declared
    ingested = 1000 << 22
    for counters, value in (
            ({"ec_planar_ingest_bytes": ingested,
              "store_planar_write_bytes": int(want * ingested)}, want),
            ({"ec_coalesced_ticks": 3}, None)):
        r = layers.Readings(config=cell.config, device_kind="TPU v5 lite",
                            attribution={}, counters=counters,
                            slice_counters={}, trace=None)
        assert layers.read_metric("rest_bytes_per_byte.write", reader,
                                  r) == value
