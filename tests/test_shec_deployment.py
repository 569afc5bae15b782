"""The SHEC k=6 m=4 c=3 pool as a deployment (PR 44).

The plain reference is ``Reference`` below: a scalar SHEC(6,4,3) written
from upstream's description and independent of ``ec/shec.py`` and
``ops/gf8``: GF(2^8) by shift and reduce modulo 0x11d, jerasure's
Vandermonde coding matrix, the shingle zeros of (m1, c1, m2, c2) =
(2, 1, 2, 2), encode as a bytewise matrix product, decode by Gaussian
elimination over whatever rows are present.  It is held to the golden
vector of exactly this code.  Under test: the plan of ``ec/shec.py``
behind the one engine seam, the chunks a decode multiplies
(``decode_sources`` -> ``stripe._decode_src``), whom the read gather of
``cluster/backend_ec.py`` asks first (``first_ask``), and the served
pool on 10 OSDs.
"""

import asyncio
import itertools
import json
import os

import numpy as np
import pytest

import chip_smoke
from ceph_tpu.cluster import backend_ec
from ceph_tpu.cluster.vstart import _fast_config, start_cluster
from ceph_tpu.ec import factory, planar_store
from ceph_tpu.ec import stripe as stripemod
from ceph_tpu.ec.codec import matrix_engine
from ceph_tpu.ec.interface import ECError
from ceph_tpu.ops.crc32c import crc32c
from ceph_tpu.utils.perf import KERNELS
from _flaky import contention_retry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHEC = {"plugin": "shec", "k": "6", "m": "4", "c": "3"}
K, M, N, UNIT = 6, 4, 10, 4096
CELL = "shec_k6m4c3_write_4m_t16"
POOLS = {
    "k2m1": ({"plugin": "jerasure", "technique": "reed_sol_van",
              "k": "2", "m": "1"}, 2, 3, 3),
    "k4m2": ({"plugin": "jerasure", "technique": "reed_sol_van",
              "k": "4", "m": "2"}, 4, 6, 8),
    "k8m4": ({"plugin": "isa", "k": "8", "m": "4"}, 8, 12, 12),
    "lrc": ({"plugin": "lrc", "k": "4", "m": "2", "l": "3"}, 4, 8, 8),
    "shec": (SHEC, K, N, 10),
}


def bounded(coro, seconds):
    async def _run():
        return await asyncio.wait_for(coro, seconds)
    return asyncio.run(_run())


def kernels():
    return {k: v for k, v in KERNELS.dump()["device_kernels"].items()
            if isinstance(v, (int, float))}


def grew(before):
    now = kernels()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


def seeded(seed: int, size: int) -> bytes:
    return np.random.default_rng([44, seed, size]).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def shec():
    return factory(dict(SHEC))


# ------------------------------------------------------ the plain reference

class Reference:
    """Scalar SHEC(k, m, c), technique multiple, w = 8."""

    def __init__(self, k=K, m=M, split=(2, 1, 2, 2), unit=UNIT):
        self.k, self.m, self.unit = k, m, unit
        self.mul = self._mul_table()
        self.inv = np.zeros(256, dtype=np.uint8)
        for a in range(1, 256):
            self.inv[a] = int(np.flatnonzero(self.mul[a] == 1)[0])
        self.matrix = self._shingle(self._vandermonde(k + m, k)[k:], split)
        self.generator = np.vstack([np.eye(k, dtype=np.uint8), self.matrix])

    @staticmethod
    def _mul_table():
        table = np.zeros((256, 256), dtype=np.uint8)
        for a in range(256):
            for b in range(256):
                x, y, p = a, b, 0
                while y:
                    if y & 1:
                        p ^= x
                    x <<= 1
                    if x & 0x100:
                        x ^= 0x11d
                    y >>= 1
                table[a, b] = p
        return table

    def _vandermonde(self, rows, cols):
        """jerasure's distribution matrix: the extended Vandermonde
        matrix (first row e_0, last row e_last, row i the powers of i)
        brought to identity over its first ``cols`` rows by column
        operations, then its first coding row scaled to ones by columns
        and the first column of the rows below to ones by rows."""
        mul, inv = self.mul, self.inv
        d = np.zeros((rows, cols), dtype=np.uint8)
        d[0, 0] = 1
        d[rows - 1, cols - 1] = 1
        for i in range(1, rows - 1):
            p = 1
            for j in range(cols):
                d[i, j] = p
                p = int(mul[p, i])
        for i in range(1, cols):
            j = next(r for r in range(i, rows) if d[r, i])
            if j != i:
                d[[i, j]] = d[[j, i]]
            if d[i, i] != 1:
                d[:, i] = mul[inv[d[i, i]]][d[:, i]]
            for j in range(cols):
                f = int(d[i, j])
                if j != i and f:
                    d[:, j] ^= mul[f][d[:, i]]
        for j in range(cols):
            f = int(d[cols, j])
            if f != 1:
                d[cols:, j] = mul[inv[f]][d[cols:, j]]
        for i in range(cols + 1, rows):
            f = int(d[i, 0])
            if f != 1:
                d[i] = mul[inv[f]][d[i]]
        return d

    def _shingle(self, coding, split):
        """Rows 0..m1-1 keep c1 shingles of k/m1 columns each, rows
        m1..m-1 keep c2 of k/m2: everything else is zero."""
        m1, c1, m2, c2 = split
        k = self.k
        out = coding.copy()
        for base, rows, c in ((0, m1, c1), (m1, m2, c2)):
            for r in range(rows):
                start = ((r + c) * k // rows) % k
                end = (r * k // rows) % k
                col = start
                while col != end:
                    out[base + r, col] = 0
                    col = (col + 1) % k
        return out

    def parity(self, chunks):
        out = np.zeros((self.m, chunks.shape[1]), dtype=np.uint8)
        for j in range(self.m):
            for c in range(self.k):
                if self.matrix[j, c]:
                    out[j] ^= self.mul[self.matrix[j, c]][chunks[c]]
        return out

    def shards(self, payload: bytes) -> np.ndarray:
        """The k+m shards as stored: the object zero-padded to whole
        stripes of k x unit, shard i = chunk i of every stripe in turn."""
        k, unit = self.k, self.unit
        stripes = -(-len(payload) // (k * unit))
        padded = np.zeros(stripes * k * unit, dtype=np.uint8)
        padded[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
        data = padded.reshape(stripes, k, unit).transpose(1, 0, 2) \
            .reshape(k, stripes * unit)
        return np.vstack([data, self.parity(data)])

    def solve(self, have):
        """Gaussian elimination over the generator rows ``have``: the
        data chunks those rows determine, each as {source: coefficient}."""
        have = sorted(have)
        rows = [(self.generator[s].astype(int).tolist(),
                 {s: 1}) for s in have]
        mul, inv = self.mul, self.inv
        rank, pivots = 0, {}
        for col in range(self.k):
            piv = next((r for r in range(rank, len(rows))
                        if rows[r][0][col]), None)
            if piv is None:
                continue
            rows[rank], rows[piv] = rows[piv], rows[rank]
            f = int(inv[rows[rank][0][col]])
            rows[rank] = ([int(mul[f, v]) for v in rows[rank][0]],
                          {s: int(mul[f, c]) for s, c in
                           rows[rank][1].items()})
            for r in range(len(rows)):
                g = rows[r][0][col]
                if r != rank and g:
                    vec = [a ^ int(mul[g, b])
                           for a, b in zip(rows[r][0], rows[rank][0])]
                    comb = dict(rows[r][1])
                    for s, c in rows[rank][1].items():
                        comb[s] = comb.get(s, 0) ^ int(mul[g, c])
                    rows[r] = (vec, comb)
            pivots[col] = rank
            rank += 1
        out = {}
        for col, r in pivots.items():
            vec = rows[r][0]
            if sum(1 for v in vec if v) == 1:       # e_col: determined
                out[col] = {s: c for s, c in rows[r][1].items() if c}
        return out

    def decode(self, have: dict):
        """``have``: shard id -> bytes.  The data chunks the rows that
        are there determine (a dict; a chunk they do not determine is
        left out)."""
        out = {}
        for col, comb in self.solve(have).items():
            acc = np.zeros(len(next(iter(have.values()))), dtype=np.uint8)
            for s, c in comb.items():
                acc ^= self.mul[c][np.frombuffer(have[s], dtype=np.uint8)]
            out[col] = acc.tobytes()
        return out


@pytest.fixture(scope="module")
def ref():
    return Reference()


def _golden_case():
    with open(os.path.join(ROOT, "tests", "golden", "ec_golden.jsonl"),
              encoding="utf-8") as f:
        for line in f:
            case = json.loads(line)
            if (case["plugin"], case["technique"], case["k"], case["m"],
                    case.get("c"), case.get("w", 8)) == \
                    ("shec", "multiple", K, M, 3, 8):
                return case
    raise AssertionError("no golden vector for SHEC(6,4,3) w=8")


def test_reference_is_the_golden_vector_and_the_codes_matrix(ref):
    """The scalar reference against gen.c's matrix and chunks for exactly
    this code (seed 20), and ``ec/shec.py``'s matrix against both."""
    from test_ec_golden import _fnv1a64, _lcg_bytes

    case = _golden_case()
    golden = np.array(case["matrix"], dtype=np.uint8).reshape(M, K)
    assert np.array_equal(ref.matrix, golden)
    assert golden.tolist()[:2] == [[1, 1, 1, 0, 0, 0],
                                   [0, 0, 0, 172, 82, 200]]
    assert np.array_equal(shec().engine.coding, golden)
    payload = _lcg_bytes(case["seed"], case["object_size"])
    n = case["chunk_size"]
    data = np.frombuffer(payload, dtype=np.uint8).reshape(K, n)
    chunks = [bytes(r) for r in data] + [bytes(r) for r in ref.parity(data)]
    for got, want in zip(chunks, case["chunks"]):
        assert _fnv1a64(got) == want["fnv1a64"]
        assert got[:16].hex() == want["head"]
    # and its decode: any three rows lost, the data comes back
    have = {s: chunks[s] for s in range(N) if s not in (0, 3, 7)}
    back = ref.decode(have)
    assert [back[j] for j in range(K)] == chunks[:K]


# ------------------------------------------------------------ the seam

def test_shec_takes_the_one_engine_seam():
    codec = shec()
    eng = matrix_engine(codec)
    assert eng is not None and eng is codec.engine
    assert eng.coding.shape == (M, K) and eng._enc_bitmat.shape == (32, 48)
    assert stripemod.planar_at_rest_ok(codec, UNIT)
    assert stripemod._host_engine_ok(codec)      # the suite runs on the CPU
    # 4 MiB is 171 stripes (not a power of two: bucket 256) of 24 KiB
    sinfo = stripemod.StripeInfo(K, UNIT)
    assert sinfo.object_stripes(4 << 20) == 171
    assert sinfo.shard_size(4 << 20) == 700416
    assert stripemod._bucket(171) == 256
    # a wider field is no bytewise GF(2^8) code: byte-at-rest, as before
    wide = factory(dict(SHEC, w="16"))
    assert matrix_engine(wide) is None


@pytest.mark.parametrize("size,count,host", [
    (4096, 1, True), (4096, 16, True),
    (65537, 1, True), (65537, 16, True),
    (4 << 20, 1, True), (4 << 20, 16, True),
    # the device branch of the tick (XLA's planar matmul and the
    # chunk-crc program off the chip), at a size the emulation affords
    (65537, 16, False),
])
def test_planar_tick_is_the_reference(size, count, host, ref, monkeypatch):
    if not host:
        monkeypatch.setattr(stripemod, "_host_engine_ok", lambda c: False)
    codec = shec()
    sinfo = stripemod.StripeInfo(K, UNIT)
    datas = [seeded(i, size) for i in range(count)]
    # the host's crc of planes is slow at 64 MiB (``_planar_spread``):
    # at 4 MiB the first object's shards carry the crcs
    crc = [i == 0 or size < (1 << 20) for i in range(count)]
    before = kernels()
    out = stripemod.encode_planes_multi(codec, sinfo, datas, crc)
    g = grew(before)
    if host:
        assert g.get("ec_host_planar_matmul_calls", 0) >= 1
        assert not g.get("planar_matmul_calls", 0)
    else:
        assert g.get("planar_matmul_calls", 0) == 1    # ONE launch a tick
        assert not g.get("ec_host_planar_matmul_calls", 0)
    shard_len = sinfo.shard_size(size)
    for i, (planes, crcs) in enumerate(out):
        assert planes.shape == (N, 8, shard_len // 8)
        want = ref.shards(datas[i])
        for s in range(N):
            got = planar_store.planes_to_shard(planes[s], seam=None)
            assert got == want[s].tobytes(), (i, s)
            if crc[i]:
                assert int(crcs[s]) == crc32c(0xFFFFFFFF, got), (i, s)


# --------------------------------------------- decode, in the plane domain

def planes_of(rows: np.ndarray) -> dict:
    return {s: planar_store.rows_to_planes(rows[s:s + 1])
            for s in range(rows.shape[0])}


@pytest.mark.parametrize("n_lost", [1, 2, 3, 4])
def test_every_erasure_pattern_decodes_to_the_reference_or_is_refused(
        n_lost, ref):
    """Every pattern of one, two and three lost chunks (c = 3: all of
    them decode) gives the reference's bytes in the plane domain, by
    read and by rebuild.  Beyond c, of four lost: right, or refused
    BEFORE a multiply, and never accepted where the rows that are left
    do not determine the data."""
    codec = shec()
    sinfo = stripemod.StripeInfo(K, UNIT)
    data = seeded(n_lost, 2 * K * UNIT + 5)
    rows = ref.shards(data)
    planes = planes_of(rows)
    refused = 0
    for lost in itertools.combinations(range(N), n_lost):
        have = {s: planes[s] for s in range(N) if s not in lost}
        lost_data = [s for s in lost if s < K]
        determined = set(ref.solve(have)) >= set(lost_data)
        before = kernels()
        try:
            got = stripemod.decode_planes_multi(
                codec, sinfo, [(have, len(data))])[0]
        except ECError:
            assert n_lost > 3, lost
            refused += 1
            g = grew(before)
            assert g["ec_decode_sources_refused"] == 1, (lost, g)
            assert not any("matmul" in name for name in g), (lost, g)
            with pytest.raises(ECError):
                stripemod.reencode_planes_multi(
                    codec, sinfo, [(have, len(data))])
            continue
        assert determined, lost             # never a rank < k accepted
        assert got == data, lost
        g = grew(before)
        assert "ec_planar_relayout_conversions" not in g, (lost, g)
        full = stripemod.reencode_planes_multi(
            codec, sinfo, [(have, len(data))])[0]
        for s in lost:
            assert planar_store.planes_to_shard(full[s], seam=None) == \
                rows[s].tobytes(), (lost, s)
    assert (refused > 0) == (n_lost > 3)


@pytest.mark.parametrize("lost", range(N))
def test_one_plan_names_multiplies_and_hands_the_tick_the_same_chunks(
        lost, ref, monkeypatch):
    """``decode_sources``, ``_batch_plan`` / ``_planar_decode_plan``,
    ``stripe._decode_src`` and the matrix the engines multiply by come
    from ONE plan: for a lost chunk 3, 4 or 5 it is the other two and
    parity 7 (parity 6 covers chunks 0-2 only), three chunks, not six."""
    codec = shec()
    sinfo = stripemod.StripeInfo(K, UNIT)
    avail = [s for s in range(N) if s != lost]
    want = (lost,) if lost < K else ()
    shingle = ({0, 1, 2, 6}, {3, 4, 5, 7})
    expect = () if lost >= K else tuple(sorted(
        next(g for g in shingle if lost in g) - {lost}))
    assert tuple(codec.decode_sources(set(range(K)), avail)) == expect
    if not want:
        return      # a lost parity is no decode: the data is there
    assert tuple(codec.decode_sources({lost}, avail)) == expect
    assert stripemod._decode_src(codec, want, (lost,)) == expect
    assert codec._batch_plan((lost,), want)[1] == expect
    assert codec._planar_decode_plan((lost,), want)[1] == expect
    # the engine's matrix over exactly those chunks, and over all nine
    # that are there (the columns of the six it leaves out are zero)
    rmat = matrix_engine(codec).decode_matrix(expect, want)
    assert rmat.shape == (1, 3) and rmat.all()
    wide = matrix_engine(codec).decode_matrix(tuple(avail), want)
    assert [avail[i] for i in np.flatnonzero(wide[0])] == list(expect)
    multiplied = []
    real = stripemod._host_decode_matrix

    def spy(codec, src, want):
        multiplied.append((tuple(src), tuple(want)))
        return real(codec, src, want)

    monkeypatch.setattr(stripemod, "_host_decode_matrix", spy)
    data = seeded(lost, 3 * K * UNIT + 1)
    rows = ref.shards(data)
    have = {s: rows[s] for s in avail}
    assert stripemod.decode_stripes_multi(
        codec, sinfo, [(have, len(data))])[0] == data
    assert np.array_equal(stripemod.reencode_stripes_multi(
        codec, sinfo, [(have, len(data))])[0], rows)
    planes = {s: p for s, p in planes_of(rows).items() if s != lost}
    assert stripemod.decode_planes_multi(
        codec, sinfo, [(planes, len(data))])[0] == data
    stripemod.reencode_planes_multi(codec, sinfo, [(planes, len(data))])
    assert multiplied == [(expect, want)] * 4


def test_what_cannot_decode_is_refused_by_the_code():
    codec = shec()
    # the parent's first k of the holders that are up, chunk 3 lost
    with pytest.raises(ECError):
        codec.decode_sources({3}, [0, 1, 2, 4, 5, 6])
    assert codec.decode_sources({3}, [0, 1, 2, 4, 5, 7]) == [4, 5, 7]
    # nothing to rebuild: no sources, and no error
    assert codec.decode_sources(set(), [0, 1, 2, 4, 5, 6]) == []
    assert codec.decode_sources({0, 1}, list(range(N))) == []
    # a full row serves where the shingle's own parity is gone too
    assert codec.decode_sources({3}, [0, 1, 2, 4, 5, 8]) == \
        [0, 1, 2, 4, 5, 8]


# ------------------------------------------ whom the gather asks first

def _peers(n, down, own):
    return [(s, 100 + s) for s in range(n) if s not in down and s != own]


@pytest.mark.parametrize("name", ["k2m1", "k4m2", "k8m4", "lrc"])
def test_the_accepted_pools_are_asked_as_the_parent_asked(name):
    """Frozen: with any one holder down and the primary on any other
    shard, a degraded read of the three Reed-Solomon pools and of the
    LRC pool asks ``peers[:want]``, the first k in the primary's order
    of preference (its own, then shard order), and keeps the rest as
    the hedge's spares: bit for bit what the parent sent, wherever what
    the parent sent decodes.  It did not in four places of the LRC pool:
    a primary on a local parity (5 or 7) whose group lost a data chunk
    had (1, 2, 3, 5)-like sets and widened in a second round; the code
    now names the group's global parity in the first."""
    profile, k, n, _osds = POOLS[name]
    codec = factory(dict(profile))
    data = set(range(k))
    differ = {}
    for down in range(n):
        for own in range(n):
            if own == down:
                continue
            peers = _peers(n, {down}, own)
            want = k - 1
            up = [own] + [s for s, _o in peers]
            first, spare = backend_ec.first_ask(
                codec, data - set(up), up, peers, want)
            assert sorted(first + spare) == peers
            if (first, spare) == (peers[:want], peers[want:]):
                continue
            parent = [own] + [s for s, _o in peers[:want]]
            # the parent's k did not decode; what is asked now does,
            # with the k - 1 data chunks the read returns in it
            assert not codec._decodable(parent, data - set(up))
            asked = [own] + [s for s, _o in first]
            assert codec._decodable(asked, data - set(up))
            assert len(first) == want + 1
            differ[(down, own)] = [s for s, _o in first]
    if name != "lrc":
        assert not differ
        assert codec.decode_sources({0}, list(range(1, n))) is None
    else:
        assert differ == {(0, 5): [1, 2, 3, 4], (1, 5): [0, 2, 3, 4],
                          (2, 7): [0, 1, 3, 6], (3, 7): [0, 1, 2, 6]}


def test_a_shec_read_is_asked_of_the_plans_sources():
    """Chunk 3's holder down: the parent asked the first k that are up,
    (0, 1, 2, 4, 5, 6), which do not give 3.  The code names (4, 5, 7):
    those and the data chunks that are up are asked, wherever the
    primary sits, and the rest stay spares."""
    codec = shec()
    for own in (0, 4, 7, 6, 9):
        peers = _peers(N, {3}, own)
        up = [own] + [s for s, _o in peers]
        first, spare = backend_ec.first_ask(codec, {3}, up, peers, K - 1)
        asked = {s for s, _o in first} | {own}
        assert asked >= {0, 1, 2, 4, 5, 7}, own
        assert asked - {own} == {0, 1, 2, 4, 5, 7} - {own}, own
        assert [p for p in peers if p not in first] == spare
        # what is asked decodes: the read needs no second round
        assert codec.decode_sources({3}, sorted(asked)) == [4, 5, 7]
    # two down whose shingle's parity is one of them: the full rows
    peers = _peers(N, {3, 7}, 0)
    first, _spare = backend_ec.first_ask(
        codec, {3}, [0] + [s for s, _o in peers], peers, K - 1)
    assert {s for s, _o in first} == {1, 2, 4, 5, 8}
    # more down than c: the code refuses, the gather asks as it did
    peers = _peers(N, {3, 6, 7, 8, 9}, 0)
    assert backend_ec.first_ask(
        codec, {3}, [0] + [s for s, _o in peers], peers, K - 1) == \
        (peers[:K - 1], peers[K - 1:])
    # nothing missing: the first k, and the code is not asked
    peers = _peers(N, {8}, 0)
    assert backend_ec.first_ask(None, set(), list(range(8)) + [9], peers,
                                K - 1) == (peers[:K - 1], peers[K - 1:])


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
        await asyncio.sleep(0.1)
    raise TimeoutError(f"not HEALTH_OK: {health}")


async def _interval_settled(cluster, down):
    """Every PG's primary has peered the interval in which ``down`` are
    gone and its commit watermark covers its log: until then a gather's
    fast path does not resolve (``_viable``: "at/below the commit
    watermark") and widens, whatever it asked first."""
    loop = asyncio.get_event_loop()
    t0 = loop.time()
    while loop.time() - t0 < 60:
        pending = [
            st.pgid for osd in cluster.osds.values()
            for st in osd.pgs.values()
            if st.primary == osd.osd_id and (
                any(o in down for o in st.acting)
                or st.last_complete < st.last_update)]
        if not pending:
            return
        await asyncio.sleep(0.05)
    raise TimeoutError(f"PGs not settled: {pending}")


async def _each_holder_down_in_turn(profile, osds, pg_num, payloads,
                                    check_shards=None):
    """Write ``payloads``; then each OSD killed in turn: every object
    reads back exact in ONE round of sub-reads; the OSD revived empty
    and marked in, its shards rebuilt.  Returns per OSD what grew."""
    cluster = await start_cluster(osds, config=_fast_config())
    out = {}
    try:
        client = await cluster.client()
        pool = await client.pool_create(
            "pool", "erasure", pg_num=pg_num, ec_profile=dict(profile))
        io = client.ioctx(pool)
        await asyncio.gather(*(io.write_full(n, d, timeout=120)
                               for n, d in payloads.items()))
        for victim in range(osds):
            await cluster.kill_osd(victim)
            await cluster.wait_down(victim)
            await _interval_settled(cluster, {victim})
            before = kernels()
            for name, data in payloads.items():
                assert await io.read(name, timeout=60) == data, \
                    (victim, name)
            out[victim] = grew(before)
            await cluster.revive_osd(victim)
            await client.objecter.mon_command(
                {"prefix": "osd in", "id": victim})
            await _health_ok(client)
            if check_shards is not None:
                await check_shards(cluster, victim)
    finally:
        await cluster.stop()
    return out


@contention_retry()
@pytest.mark.parametrize("name", list(POOLS))
def test_one_holder_down_reads_in_one_round_on_every_pool_type(name):
    """All five pool types, each OSD killed in turn: every object reads
    back, and no gather that ended in a decode widened after its first
    ask (``ec_gather_second_rounds`` 0): k - 1 sub-reads each where the
    primary's own shard serves, the code's sources where it does not."""
    profile, k, _n, osds = POOLS[name]
    payloads = {f"o{i}": seeded(i, 65537) for i in range(6)}
    out = bounded(_each_holder_down_in_turn(profile, osds, 8, payloads),
                  300)
    decodes = 0
    for victim, g in out.items():
        assert not g.get("ec_gather_second_rounds", 0), (victim, g)
        assert "ec_decode_sources_refused" not in g, (victim, g)
        d = g.get("ec_gather_decodes", 0)
        decodes += d
        if name == "shec":
            # a primary on a parity the plan leaves out asks k, not k - 1
            assert d * (k - 1) <= g.get("ec_gather_subreads", 0) <= d * k
        else:
            assert g.get("ec_gather_subreads", 0) == d * (k - 1), g
        assert g.get("ec_coalesced_read_ticks", 0) >= (1 if d else 0)
    assert decodes >= 1
    print(name, {v: (g.get("ec_gather_decodes", 0),
                     g.get("ec_gather_subreads", 0),
                     g.get("ec_gather_second_rounds", 0))
                 for v, g in out.items()})


@contention_retry()
@pytest.mark.parametrize("engine", ["host", "device"])
def test_served_pool_each_of_ten_holders_down_and_rebuilt(engine, ref,
                                                          monkeypatch):
    """``start_cluster(10)``, the profile, ``write_full`` / ``read``:
    shards ``planar8`` on all ten OSDs and equal to the reference's; each
    holder killed in turn and every object read back byte for byte in
    ONE round of sub-reads (on the parent the holders of chunks 3-5 gave
    ``-5: ECError("shec: can't find recover matrix")``); revived empty
    and marked in, its shard rebuilt equal to the reference's.  Once on
    the host GF engine (1 MiB), once with it switched off so that the
    device branches serve (64 KiB + 1)."""
    if engine == "device":
        monkeypatch.setattr(stripemod, "_host_engine_ok", lambda c: False)
    size = (1 << 20) if engine == "host" else 65537
    payloads = {f"o{i}": seeded(i, size) for i in range(4)}
    shards = {n: ref.shards(d) for n, d in payloads.items()}

    async def check_shards(cluster, victim):
        store = cluster.osds[victim].store
        loop = asyncio.get_event_loop()
        t0 = loop.time()
        for name in payloads:
            held = await _holders(cluster, name)
            while victim not in held and loop.time() - t0 < 30:
                await asyncio.sleep(0.05)
                held = await _holders(cluster, name)
            assert len(held) == N, (victim, name, sorted(held))
            assert {ly for _c, ly, _s in held.values()} == \
                {planar_store.LAYOUT_PLANAR}
            coll, _ly, shard = held[victim]
            assert bytes(store.read(coll, name)) == \
                shards[name][shard].tobytes(), (victim, name, shard)

    before = kernels()
    out = bounded(_each_holder_down_in_turn(
        SHEC, 10, 16, payloads, check_shards), 300)
    whole = grew(before)
    for victim, g in out.items():
        assert not g.get("ec_gather_second_rounds", 0), (victim, g)
        assert "ec_decode_sources_refused" not in g, (victim, g)
        assert "ec_planar_relayout_conversions" not in g, (victim, g)
    assert sum(g.get("ec_gather_decodes", 0) for g in out.values()) >= 6
    assert whole.get("ec_coalesced_reencode_ticks", 0) >= 1
    if engine == "device":
        assert whole.get("planar_matmul_calls", 0) >= 1
        assert not whole.get("ec_host_planar_matmul_calls", 0)
        assert not whole.get("ec_host_matmul_calls", 0)
    else:
        assert whole.get("ec_host_planar_matmul_calls", 0) >= 1


@contention_retry()
@pytest.mark.parametrize("down", [(3, 4), (3, 6), (0, 7), (3, 4, 5),
                                  (0, 3, 6), (2, 7, 8)],
                         ids=lambda d: "-".join(map(str, d)))
def test_two_and_three_holders_down_read_right(down, ref):
    """c = 3: any two or three holders lost, every byte reads back; and
    a partial overwrite with them down (a read-modify-write whose read
    half decodes) lands right on the holders that are left."""
    payload = seeded(sum(down), 300000)
    patch = seeded(len(down), 30000)

    async def scenario():
        cluster = await start_cluster(10, config=_fast_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "pool", "erasure", pg_num=8, ec_profile=dict(SHEC))
            io = client.ioctx(pool)
            await io.write_full("obj", payload, timeout=120)
            held = {shard: osd_id for osd_id, (_c, _ly, shard)
                    in (await _holders(cluster, "obj")).items()}
            for shard in down:
                await cluster.kill_osd(held[shard])
                await cluster.wait_down(held[shard])
            await _interval_settled(cluster, {held[s] for s in down})
            before = kernels()
            assert await io.read("obj", timeout=60) == payload
            g = grew(before)
            assert "ec_decode_sources_refused" not in g, g
            if any(s < K for s in down):
                assert g.get("ec_gather_decodes", 0) >= 1, g
                assert not g.get("ec_gather_second_rounds", 0), g
            await io.write("obj", patch, offset=12345, timeout=120)
            want = bytearray(payload)
            want[12345:12345 + len(patch)] = patch
            assert await io.read("obj", timeout=60) == bytes(want)
            rows = ref.shards(bytes(want))
            for osd_id, (coll, ly, shard) in \
                    (await _holders(cluster, "obj")).items():
                assert ly == planar_store.LAYOUT_PLANAR
                assert bytes(cluster.osds[osd_id].store.read(coll, "obj")) \
                    == rows[shard].tobytes(), (osd_id, shard)
        finally:
            await cluster.stop()

    bounded(scenario(), 180)


# ------------------------------------------------- chip_smoke's SHEC leg

def test_chip_smokes_shec_leg_at_tiny_size(monkeypatch):
    """``chip_smoke.py``'s SHEC leg through the same code, host GF engine
    off so that the device branches serve: a tick's planar encode and a
    one-erasure decode of chunk 3 against the scalar reference of that
    file.  Off the chip the planar matmul is XLA's, one stack group a
    call: the leg's own rule (2 on the chip) refuses that, after
    everything was compared."""
    monkeypatch.setattr(stripemod, "_host_engine_ok", lambda codec: False)
    with pytest.raises(AssertionError, match="stack-group"):
        chip_smoke.shec_tick_against_the_reference(
            seed=11, n_objects=2, object_size=65537)
    report = chip_smoke.shec_tick_against_the_reference(
        seed=11, n_objects=2, object_size=65537, stack_groups=1)
    assert report["decode_sources"] == [4, 5, 7]
    assert report["shards_compared"] == 2 * N
    # the smoke's reference and this file's are two writings of one code
    assert np.array_equal(chip_smoke.shec_reference_matrix(),
                          Reference().matrix)


# ------------------------------------------- the cell, rehearsed on the CPU

TINY = {"object_bytes": 65536, "callers": 4, "payload_pool": 4,
        "lead_in_s": 0.3}


def _run_cell(seed=5, seconds=1.5, trace=False):
    from benchmark.harness import cell as cellmod
    from benchmark.harness.loader import load_cell

    cell = load_cell(CELL)
    cell.traffic = {**cell.traffic, **TINY}
    lines = []
    out = bounded(cellmod.CellRun(
        cell, seed, seconds, trace, started_at=0.0,
        say=lambda **row: lines.append(row)).run(), 240)
    out["lines"] = lines
    return out


@contention_retry()
def test_the_cell_serves_verifies_and_stays_on_the_product_plane(
        monkeypatch):
    monkeypatch.setattr(stripemod, "_host_engine_ok", lambda codec: False)
    out = _run_cell(seed=2147484044, trace=True)
    checks = {r["check"]: r for r in out["lines"] if "check" in r}
    assert out["correct"], (checks, out["errors"])
    assert out["failed"] == 0
    assert checks["degraded_read_errors"]["value"] == 0
    assert checks["degraded_decode_ticks"]["value"] >= 1
    assert checks["host_engine_calls"]["value"] == 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["store_direct_share.write"] == 100.0
    # XLA's planar path off the chip: one group a call (2 on the chip)
    assert m["stack_groups.write"] == 1.0
    # 64 KiB is 3 stripes of 24 KiB in a bucket of 4: the padding is
    # (8 KiB + 24 KiB) of 72 KiB ingested, and more where a tick holds 3
    assert m["pad_share.write"] >= 44.0


@contention_retry()
def test_on_the_cpu_host_the_cell_is_served_by_the_host_engine():
    out = _run_cell()
    checks = {r["check"]: r for r in out["lines"] if "check" in r}
    assert not out["correct"]
    assert sorted(name for name, r in checks.items() if not r["ok"]) == \
        ["device_matmul_calls", "host_engine_calls", "window_matmul_bytes"]
    assert checks["healthy_mismatches"]["value"] == 0
    assert checks["degraded_mismatches"]["value"] == 0
    assert checks["degraded_read_errors"]["value"] == 0


def test_the_roofline_counts_six_data_and_four_coding_rows():
    from benchmark.harness.loader import load_cell
    from benchmark.harness.peaks import planar_matmul_cost

    ops, moved = planar_matmul_cost(load_cell(CELL).config, 6 << 20)
    assert ops == 2.0 * 32 * 8 * (6 << 20)      # 512 int8 ops a byte
    assert moved == (6 << 20) * (1 + 4 / 6)     # 6 rows in, 4 rows out


def test_rest_bytes_per_byte_reads_ten_chunks_for_six():
    from benchmark.harness import layers
    from benchmark.harness.loader import load_cell

    cell = load_cell(CELL)
    reader = cell.per_layer["rest_bytes_per_byte.write"]
    ingested = 1000 * 4202496
    r = layers.Readings(
        config=cell.config, device_kind="TPU v5 lite", attribution={},
        counters={"ec_planar_ingest_bytes": ingested,
                  "store_planar_write_bytes": 1000 * 10 * 700416},
        slice_counters={}, trace=None)
    assert layers.read_metric("rest_bytes_per_byte.write", reader, r) == \
        pytest.approx(10 / 6)


def test_the_cell_and_its_deployment_are_what_the_issue_names():
    from benchmark.harness.loader import load_cell

    cell = load_cell(CELL)
    cfg = cell.config
    assert (cell.config_name, cell.traffic_name, cell.chips) == \
        ("rados_shec_k6m4c3_10osd", "write_4m_t16", 1)
    assert cfg["ec_profile"] == SHEC
    assert (cfg["k"], cfg["m"], cfg["gf_word_bits"], cfg["stripe_unit"],
            cfg["osds"], cfg["pg_num"], cfg["pool_type"]) == \
        (6, 4, 8, 4096, 10, 32, "erasure")
    assert sorted(cfg["reduced"]) == ["daemons_per_process", "osds",
                                      "pg_num", "store"]
    assert "all 10 shards" in cfg["guarantees"][0]
    assert cell.end_to_end == ["write_MBps", "write_p95_ms", "setup_s"]
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    # no longer the last: PR 48 listed its own cell behind
    cells = [w["name"] for w in spec["workloads"]]
    assert cells.index(CELL) == 5 and cells[:6] == [
        "k2m1_write_4m_t16", "k2m1_write_64k_t16", "k4m2_write_4m_t16",
        "k8m4_write_4m_t16", "lrc_k4m2l3_write_4m_t16", CELL]
    assert [c["name"] for c in spec["configs"]].index(
        "rados_shec_k6m4c3_10osd") == 4
    # seven cells, and the read cell behind them once it is listed
    assert len(spec["workloads"]) in (7, 8) and len(spec["configs"]) == 6


# ------------------------- the stores' room against the chip host's memory

GIB = 1 << 30
CHIP_HOST_MEMTOTAL_GIB = 45.0       # benchmark/host_touch.py, PR 42


def _configs():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return [c["file"] for c in json.load(f)["configs"]]


@pytest.mark.parametrize("path", _configs())
def test_a_deployments_stores_fit_the_chip_host(path):
    """PR 42's rule, which ``benchmark/tests/test_room_and_checks.py``
    holds by hand: 85% of the cluster's devices (nearfull, where a run
    stops being ``correct``) under the chip host's MemTotal less 8 GiB,
    so that the logical limit is met before the physical one; and the
    file says how the size was reckoned."""
    with open(os.path.join(ROOT, path), encoding="utf-8") as f:
        cfg = json.load(f)
    device = cfg["store_bytes_per_osd"]
    assert device % (1 << 20) == 0
    assert 0.85 * device * cfg["osds"] <= (CHIP_HOST_MEMTOTAL_GIB - 8) * GIB
    store_lines = [a for a in cfg["assumed"]
                   if a.startswith("store_bytes_per_osd")]
    assert len(store_lines) == 1
    assert f"store_bytes_per_osd {device / GIB:g} GiB" in store_lines[0]
