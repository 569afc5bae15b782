"""The jerasure cauchy_good k=4 m=2 pool as a deployment (PR 48).

The plain reference is ``Reference`` below: jerasure's packet-interleaved
Cauchy Reed-Solomon code written from its published construction in numpy
alone and independent of ``ceph_tpu/ec/`` and ``ops/gf8``: GF(2^8) by
shift and reduce modulo 0x11d, ``cauchy_original_coding_matrix``
(1 / (i ^ (m + j))), ``cauchy_improve_coding_matrix`` (columns scaled so
that the first row is ones, then each later row divided by the element
that leaves its bit-matrix the fewest ones), ``matrix_to_bitmatrix``,
encode as XORs of whole packets a bit-matrix row, decode by inverting the
survivors' bit-matrix over GF(2).  Under test: the one engine seam and the
serialization it names (``ec/codec.py::engine_layout``), the packet-row
serialization at rest (``ec/planar_store.py``), the plane entry points of
``ec/stripe.py`` on it (both engines), the chunk-crc program in packet
order (``ops/crc32c.py``), the pool's geometry by upstream's rule, and the
served pool on 8 OSDs.
"""

import asyncio
import itertools
import json
import os

import numpy as np
import pytest

from ceph_tpu.cluster.vstart import _fast_config, start_cluster
from ceph_tpu.ec import factory, planar_store
from ceph_tpu.ec import stripe as stripemod
from ceph_tpu.ec.codec import bytewise_engine, engine_layout, matrix_engine
from ceph_tpu.ops import crc32c as crcmod
from ceph_tpu.trace import tick as ticktrace
from ceph_tpu.utils.perf import KERNELS
from _flaky import contention_retry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "cauchy_k4m2_write_4m_t16"
K, M, N, W = 4, 2, 6, 8
CAUCHY = {"plugin": "jerasure", "technique": "cauchy_good", "k": "4",
          "m": "2", "packetsize": "2048"}
# the deployment's code at a packet a test can afford: the alignment rule
# gives k*w*packetsize*4 = 8 KiB, so the 4 KiB default stays 4 KiB
TINY_POOL = {**CAUCHY, "packetsize": "64"}
CODES = {
    # name: (profile, k, m, packetsize, stripe unit of the pool)
    "good_k4m2_p2048": (CAUCHY, 4, 2, 2048, 65536),
    "good_k4m2_p64": (TINY_POOL, 4, 2, 64, 4096),
    "orig_k3m2_p32": ({"plugin": "jerasure", "technique": "cauchy_orig",
                       "k": "3", "m": "2", "packetsize": "32"},
                      3, 2, 32, 4096),
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
    return np.random.default_rng([48, seed, size]).integers(
        0, 256, size, dtype=np.uint8).tobytes()


# ------------------------------------------------------ the plain reference

def _mul(a: int, b: int) -> int:
    """GF(2^8), x^8 + x^4 + x^3 + x^2 + 1: shift and reduce."""
    out = 0
    while b:
        if b & 1:
            out ^= a
        a <<= 1
        if a & 0x100:
            a ^= 0x11D
        b >>= 1
    return out


def _inv(a: int) -> int:
    return next(x for x in range(1, 256) if _mul(a, x) == 1)


def _element_bits(e: int) -> np.ndarray:
    """matrix_to_bitmatrix's 8 x 8 block: column x is the bits of
    e * 2^x, bit l in row l."""
    block = np.zeros((W, W), dtype=np.uint8)
    for x in range(W):
        for row in range(W):
            block[row, x] = (e >> row) & 1
        e = _mul(e, 2)
    return block


def _gf2_inverse(a: np.ndarray) -> np.ndarray:
    n = a.shape[0]
    a = np.concatenate([a.copy() & 1, np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = next(r for r in range(col, n) if a[r, col])
        a[[col, pivot]] = a[[pivot, col]]
        for r in range(n):
            if r != col and a[r, col]:
                a[r] ^= a[col]
    return a[:, n:]


class Reference:
    """jerasure's Cauchy Reed-Solomon code on packets."""

    def __init__(self, k: int, m: int, packetsize: int, good: bool = True):
        self.k, self.m, self.p = k, m, packetsize
        mat = [[_inv(i ^ (m + j)) for j in range(k)] for i in range(m)]
        if good:
            self._improve(mat)
        self.matrix = np.array(mat, dtype=np.uint8)
        self.bits = np.vstack([np.hstack([_element_bits(int(e))
                                          for e in row]) for row in mat])

    @staticmethod
    def _ones(e: int) -> int:
        return int(_element_bits(e).sum())

    def _improve(self, mat) -> None:
        k, m = self.k, self.m
        for j in range(k):
            if mat[0][j] != 1:
                inv = _inv(mat[0][j])
                for i in range(m):
                    mat[i][j] = _mul(mat[i][j], inv)
        for i in range(1, m):
            best, best_j = sum(self._ones(e) for e in mat[i]), -1
            for j in range(k):
                if mat[i][j] != 1:
                    inv = _inv(mat[i][j])
                    total = sum(self._ones(_mul(e, inv)) for e in mat[i])
                    if total < best:
                        best, best_j = total, j
            if best_j != -1:
                inv = _inv(mat[i][best_j])
                mat[i] = [_mul(e, inv) for e in mat[i]]

    # a chunk is super-blocks of 8 packets; row r of a chunk's packet
    # matrix is packet r of every super-block
    def _packets(self, chunk: np.ndarray) -> np.ndarray:
        return chunk.reshape(-1, W, self.p)

    def encode(self, data: np.ndarray) -> np.ndarray:
        """(k, S) data chunks -> (m, S) coding chunks."""
        pk = [self._packets(c) for c in data]
        out = np.zeros((self.m,) + pk[0].shape, dtype=np.uint8)
        for r in range(self.m * W):
            for c in np.nonzero(self.bits[r])[0]:
                out[r // W][:, r % W, :] ^= pk[c // W][:, c % W, :]
        return out.reshape(self.m, -1)

    def shards(self, payload: bytes, unit: int) -> np.ndarray:
        """A client object -> its (k+m, L) shards: striped by ``unit``,
        zero-padded to a stripe, each stripe encoded by itself."""
        width = self.k * unit
        ns = -(-len(payload) // width)
        buf = np.zeros(ns * width, dtype=np.uint8)
        buf[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
        stripes = buf.reshape(ns, self.k, unit)
        rows = [np.vstack([s, self.encode(s)]) for s in stripes]
        return np.concatenate(rows, axis=1) if rows else \
            np.zeros((self.k + self.m, 0), dtype=np.uint8)

    def decode(self, have: dict) -> np.ndarray:
        """{chunk id: (S,) bytes} of any k chunks -> the (k, S) data."""
        k = self.k
        src = sorted(have)[:k]
        gen = np.vstack([np.eye(k * W, dtype=np.uint8), self.bits])
        inv = _gf2_inverse(np.vstack([gen[s * W:(s + 1) * W] for s in src]))
        pk = [self._packets(np.asarray(have[s])) for s in src]
        out = np.zeros((k,) + pk[0].shape, dtype=np.uint8)
        for r in range(k * W):
            for c in np.nonzero(inv[r])[0]:
                out[r // W][:, r % W, :] ^= pk[c // W][:, c % W, :]
        return out.reshape(k, -1)


@pytest.fixture(scope="module", params=list(CODES))
def code(request):
    profile, k, m, p, unit = CODES[request.param]
    return (factory(dict(profile)),
            Reference(k, m, p, good="good" in request.param), unit)


def test_the_reference_is_a_cauchy_code_by_the_rule_and_mds(code):
    codec, ref, _unit = code
    k, m = ref.k, ref.m
    if codec.technique == "cauchy_good":
        assert (ref.matrix[0] == 1).all()
        assert (ref.matrix != 0).all()
    # any k of the k+m chunks give the data back: every survivors' matrix
    # inverts, over GF(2) as over GF(2^8)
    data = np.frombuffer(seeded(1, k * W * ref.p), dtype=np.uint8) \
        .reshape(k, -1)
    full = np.vstack([data, ref.encode(data)])
    for lost in itertools.combinations(range(k + m), m):
        have = {s: full[s] for s in range(k + m) if s not in lost}
        assert np.array_equal(ref.decode(have), data), lost
    # the system's matrices are the reference's, element and bit
    assert np.array_equal(codec.engine.coding, ref.matrix)
    assert np.array_equal(codec._encode_bits(), ref.bits)
    assert np.array_equal(np.asarray(matrix_engine(codec)._enc_bitmat),
                          ref.bits)


# ---------------------------------------------------- the seam and the gate

def test_a_w8_cauchy_code_takes_the_seam_and_names_its_serialization():
    good = factory(dict(CAUCHY))
    assert matrix_engine(good) is good.engine
    assert engine_layout(good) == "packet8.2048"
    assert bytewise_engine(good) is None        # its product is not bytewise
    assert stripemod.planar_at_rest_ok(good, 65536)
    assert stripemod.at_rest_layout(good, 65536) == "packet8.2048"
    # a unit that is not whole super-blocks keeps bytes (8 is not enough)
    assert not stripemod.planar_at_rest_ok(good, 65536 + 8)
    assert not stripemod.planar_at_rest_ok(good, 4096)
    orig = factory(dict(CODES["orig_k3m2_p32"][0]))
    assert stripemod.at_rest_layout(orig, 4096) == "packet8.32"
    rs = factory({"plugin": "jerasure", "technique": "reed_sol_van",
                  "k": "4", "m": "2"})
    assert engine_layout(rs) == planar_store.LAYOUT_PLANAR
    assert bytewise_engine(rs) is matrix_engine(rs)


@pytest.mark.parametrize("profile,unit", [
    ({**CAUCHY, "w": "16"}, 65536 * 2),
    ({**CAUCHY, "w": "32"}, 65536 * 4),
    ({"plugin": "jerasure", "technique": "liberation", "k": "4", "m": "2",
      "w": "7"}, 57344),
    ({"plugin": "jerasure", "technique": "liber8tion", "k": "4", "m": "2"},
     65536),
    ({"plugin": "jerasure", "technique": "blaum_roth", "k": "4", "m": "2",
      "w": "6"}, 49152),
], ids=["cauchy_w16", "cauchy_w32", "liberation", "liber8tion",
        "blaum_roth"])
def test_wider_fields_and_the_liberation_family_keep_bytes(profile, unit):
    codec = factory(dict(profile))
    assert matrix_engine(codec) is None
    assert engine_layout(codec) is None
    assert stripemod.at_rest_layout(codec, unit) is None
    # and still encode through the planar travel format, as before
    sinfo = stripemod.StripeInfo(codec.get_data_chunk_count(), unit)
    payload = seeded(3, sinfo.stripe_width)
    rows = stripemod.encode_stripes(codec, sinfo, payload)
    chunks = codec.encode(range(codec.get_chunk_count()), payload)
    for s in range(codec.get_chunk_count()):
        assert rows[s].tobytes() == bytes(chunks[s])


# --------------------------------------------------- geometry: item 6's rule

def test_the_pools_chunk_is_what_upstreams_alignment_rule_gives():
    """OSDMonitor::prepare_pool_stripe_width: stripe_width = k *
    get_chunk_size(stripe_unit * k); ErasureCodeJerasure::get_chunk_size
    pads to ErasureCodeJerasureCauchy::get_alignment = k*w*packetsize*4."""
    good = factory(dict(CAUCHY))
    assert good.get_alignment() == 4 * 8 * 2048 * 4 == 262144
    assert good.get_chunk_size(4096 * 4) == 65536
    assert good.stripe_unit(4096) == 65536
    sinfo = stripemod.StripeInfo(4, good.stripe_unit(4096))
    assert sinfo.stripe_width == 262144
    assert sinfo.object_stripes(4 << 20) == 16
    assert sinfo.shard_size(4 << 20) == 1 << 20         # no padding
    assert factory(dict(TINY_POOL)).stripe_unit(4096) == 4096
    # the accepted deployments' pools keep their 4 KiB
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    for cfg in spec["configs"]:
        with open(os.path.join(ROOT, cfg["file"]), encoding="utf-8") as f:
            file = json.load(f)
        unit = factory(dict(file["ec_profile"])).stripe_unit(4096)
        assert unit == file["stripe_unit"], cfg["name"]
        assert unit == (65536 if cfg["name"] == "rados_cauchy_k4m2_8osd"
                        else 4096), cfg["name"]


# --------------------------------------- the serialization and its round trip

@pytest.mark.parametrize("p", [32, 64, 2048])
def test_packet_rows_round_trip_and_are_the_shards_packets(p):
    layout = planar_store.packet_layout(p)
    assert planar_store.is_planar(layout)
    assert planar_store.packetsize_of(layout) == p
    assert planar_store.quantum(layout) == 8 * p
    ns = 3
    shard = np.frombuffer(seeded(p, ns * 8 * p), dtype=np.uint8)
    planes = planar_store.shard_to_planes(shard.tobytes(), layout=layout)
    assert planes.shape == (8, ns * p)
    # row t is packet t of every super-block, in order: whole packets
    for t in range(8):
        for b in range(ns):
            assert np.array_equal(
                planes[t, b * p:(b + 1) * p],
                shard[(b * 8 + t) * p:(b * 8 + t + 1) * p])
    assert planar_store.planes_to_shard(planes, layout=layout) \
        == shard.tobytes()
    assert len(planar_store.planes_to_blob(planes)) == shard.size
    # the crc of the byte stream, from the rows
    assert crcmod.crc32c_planar_rows(planes, packetsize=p) == \
        [crcmod.crc32c(0xFFFFFFFF, shard.tobytes())]
    # the two serializations differ on the same bytes
    assert not np.array_equal(
        planes, planar_store.shard_to_planes(shard.tobytes()))
    with pytest.raises(ValueError, match="super-blocks"):
        planar_store.shard_to_planes(shard.tobytes()[:-8], layout=layout)


def test_a_tag_is_refused_by_the_other_serializations_reader():
    p8, pk = planar_store.LAYOUT_PLANAR, planar_store.packet_layout(64)
    blob = seeded(9, 1024)
    assert planar_store.planes_as(blob, pk, pk).shape == (8, 128)
    for have, want in ((p8, pk), (pk, p8), (pk, "packet8.32")):
        with pytest.raises(ValueError, match="none of a"):
            planar_store.planes_as(blob, have, want)
    # bytes (a member still byte-at-rest) take the relayout hop
    before = kernels()
    got = planar_store.planes_as(blob, None, pk)
    assert planar_store.planes_to_shard(got, layout=pk) == blob
    assert grew(before).get("ec_planar_relayout_conversions") == 1
    assert planar_store.as_shard_bytes(
        planar_store.planes_to_blob(got), pk) == blob
    for bad in ("packet8", "packet8.", "packet8.0", "packet8.x", "planar16"):
        assert not planar_store.is_planar(bad) or bad.startswith("packet8")
        with pytest.raises(ValueError, match="no planar serialization"):
            planar_store.packetsize_of(bad)
    assert not planar_store.is_planar(None) and not planar_store.is_planar("")


def test_a_store_keeps_the_tag_and_refuses_a_splice_of_the_other():
    from ceph_tpu.cluster.store import MemStore, Transaction

    pk = planar_store.packet_layout(64)
    shard = seeded(2, 2048)
    planes = planar_store.shard_to_planes(shard, layout=pk)
    store = MemStore()
    store.queue_transaction(Transaction().create_collection("c"))
    store.queue_transaction(Transaction().write_planar(
        "c", "o", 0, planar_store.planes_to_blob(planes), 256, pk))
    assert store.object_layout("c", "o") == pk
    assert store.stat("c", "o") == 2048              # L bytes exactly
    assert store.read_planar("c", "o") == planar_store.planes_to_blob(planes)
    # a journal keeps the tag; an op journaled before there were two reads
    # as planar8
    txn = Transaction().write_planar("c", "o", 0, memoryview(shard), 256, pk)
    assert Transaction.decode(txn.encode()).ops[0][6] == pk
    assert planar_store.op_layout(("write_planar", "c", "o", 0, b"", 0)) \
        == planar_store.LAYOUT_PLANAR
    # a byte read goes through the object's own serialization (unseamed)
    before = kernels()
    assert store.read("c", "o") == shard
    assert grew(before).get("ec_planar_unseamed_conversions") == 1
    # a window of the other serialization does not splice into it
    with pytest.raises(ValueError, match="none of a"):
        store.queue_transaction(Transaction().write_planar(
            "c", "o", 64, bytes(64 * 8), 256))
    # an append of one super-block (64 columns) in its own does
    more = seeded(3, 512)
    store.queue_transaction(Transaction().write_planar(
        "c", "o", 256, planar_store.planes_to_blob(
            planar_store.shard_to_planes(more, layout=pk)), 320, pk))
    assert planar_store.planes_to_shard(
        planar_store.blob_to_planes(store.read_planar("c", "o")),
        layout=pk) == shard + more


# ------------------------- the plane entry points against the reference

def _tick(codec, ref, unit, sizes, host, monkeypatch):
    if not host:
        monkeypatch.setattr(stripemod, "_host_engine_ok", lambda c: False)
    sinfo = stripemod.StripeInfo(ref.k, unit)
    datas = [seeded(i, size) for i, size in enumerate(sizes)]
    before = kernels()
    out = stripemod.encode_planes_multi(codec, sinfo, datas,
                                        [True] * len(datas))
    return sinfo, datas, out, grew(before)


@pytest.mark.parametrize("host", [True, False], ids=["host", "device"])
def test_planar_tick_is_the_reference_and_the_byte_at_rest_bytes(
        code, host, monkeypatch):
    """Egress of what a tick stores = the reference's chunks = what the
    byte-at-rest pool of the parent stored (``encode_stripes``'s rows and
    the plugin's own ``encode``), with the shard crcs of those bytes; on
    the device branch by ONE launch of the product's planar matmul, the
    crcs from the chunk program in packet order."""
    codec, ref, unit = code
    n = ref.k + ref.m
    layout = engine_layout(codec)
    width = ref.k * unit
    sizes = [width * 2, width + 4097, 100, width]
    sinfo, datas, out, g = _tick(codec, ref, unit, sizes, host, monkeypatch)
    if host:
        assert g.get("ec_host_planar_matmul_calls", 0) >= 1
        assert not g.get("planar_matmul_calls", 0)
    else:
        assert g.get("planar_matmul_calls", 0) == 1    # ONE launch a tick
        assert not g.get("ec_host_planar_matmul_calls", 0)
    assert not g.get("ec_matmul_calls", 0) and not g.get(
        "ec_host_matmul_calls", 0)
    padded = sum(-(-s // width) * width for s in sizes)
    assert g["ec_planar_ingest_bytes"] == padded
    assert g["ec_planar_packet_ingest_bytes"] == padded
    assert "ec_planar_unseamed_conversions" not in g
    for data, (planes, crcs) in zip(datas, out):
        want = ref.shards(data, unit)
        assert planes.shape == (n, 8, want.shape[1] // 8)
        got = planar_store.planes_to_rows(planes.reshape(n * 8, -1), layout)
        assert np.array_equal(got, want)
        assert [int(c) for c in crcs] == [
            crcmod.crc32c(0xFFFFFFFF, row.tobytes()) for row in want]
        # the parent's byte-at-rest pool: the byte entry point's rows
        assert np.array_equal(
            stripemod.encode_stripes(codec, sinfo, data), want)
    # ... and the plugin's own encode of one stripe (upstream's API)
    chunks = codec.encode(range(n), datas[3])
    assert [bytes(chunks[s]) for s in range(n)] == \
        [row.tobytes() for row in ref.shards(datas[3], unit)]


@pytest.mark.parametrize("host", [True, False], ids=["host", "device"])
@pytest.mark.parametrize("n_lost", [1, 2])
def test_every_loss_decodes_and_rebuilds_to_the_reference(
        code, n_lost, host, monkeypatch):
    codec, ref, unit = code
    n = ref.k + ref.m
    sinfo, datas, out, _g = _tick(codec, ref, unit,
                                  [ref.k * unit + 33, 5000], host,
                                  monkeypatch)
    for lost in itertools.combinations(range(n), n_lost):
        reqs = [({s: planes[s] for s in range(n) if s not in lost},
                 len(data)) for data, (planes, _c) in zip(datas, out)]
        before = kernels()
        assert stripemod.decode_planes_multi(codec, sinfo, reqs) == datas, \
            lost
        rebuilt = stripemod.reencode_planes_multi(codec, sinfo, reqs)
        g = grew(before)
        assert "ec_planar_relayout_conversions" not in g, (lost, g)
        assert "ec_planar_unseamed_conversions" not in g, (lost, g)
        assert not g.get("ec_matmul_calls", 0)
        for (planes, _c), again in zip(out, rebuilt):
            assert np.array_equal(again, planes), lost
    # the reference's own decode of the stored chunks agrees
    want = ref.shards(datas[0], unit)
    assert np.array_equal(
        ref.decode({s: want[s] for s in range(n_lost, n)}), want[:ref.k])


def test_the_byte_entry_points_of_a_packet_code_do_not_multiply_bytewise(
        code):
    """On the CPU the byte entry points hand a bytewise code to the host
    GF engine; a packet code's chunks are not the bytewise product, so
    they keep the planar travel format there (the gate switched off)."""
    codec, ref, unit = code
    sinfo = stripemod.StripeInfo(ref.k, unit)
    n = ref.k + ref.m
    assert stripemod._host_engine_ok(codec)
    assert not stripemod._host_bytes_ok(codec)
    data = seeded(7, ref.k * unit + 5)
    want = ref.shards(data, unit)
    before = kernels()
    [(rows, crcs)] = stripemod.encode_stripes_multi(codec, sinfo, [data],
                                                    [True])
    assert np.array_equal(rows, want)
    assert list(crcs) == [crcmod.crc32c(0xFFFFFFFF, r.tobytes())
                          for r in want]
    have = {s: want[s] for s in range(n) if s not in (0, n - 1)}
    assert stripemod.decode_stripes_multi(codec, sinfo,
                                          [(have, len(data))]) == [data]
    assert np.array_equal(stripemod.reencode_stripes_multi(
        codec, sinfo, [(have, len(data))])[0], want)
    assert not grew(before).get("ec_host_matmul_calls", 0)


def test_the_ticks_spans_say_the_serialization(monkeypatch):
    """A device tick of a packet pool: ``to_planar`` and both ``crc``
    spans carry ``layout: packet``, the crcs' ``path`` is the device, the
    tick makes its 7 device calls; a bit-plane pool's say ``bitpack``."""
    monkeypatch.setattr(stripemod, "_host_engine_ok", lambda c: False)
    said = {}
    for name, profile, unit in (
            ("packet", TINY_POOL, 4096),
            ("bitpack", {"plugin": "jerasure", "technique": "reed_sol_van",
                         "k": "4", "m": "2"}, 4096)):
        codec = factory(dict(profile))
        sinfo = stripemod.StripeInfo(4, unit)
        log = ticktrace.TickLog(counters=KERNELS)
        tick = log.open(ticktrace.ENCODE_TICK, "osd.0", op_ids=(1,))
        tick.run(stripemod.encode_planes_multi, codec, sinfo,
                 [seeded(1, 65536)], [True])
        tick.close()
        spans = tick.dump()
        said[name] = [(s["name"], s["meta"].get("layout"),
                       s["meta"].get("path")) for s in spans
                      if s["name"] in ("to_planar", "crc")]
        assert tick.calls == 7, (name, tick.calls)
        assert tick.crc_on_device()
        assert all("layout" not in s["meta"] for s in spans
                   if s["name"] not in ("to_planar", "crc"))
    for name in ("packet", "bitpack"):
        assert said[name] == [("to_planar", name, None),
                              ("crc", name, "device"),
                              ("crc", name, "device")]


def test_chunk_program_words_fold_to_the_shards_crcs_in_packet_order():
    """``planar_chunk_crcs`` on packet rows: one word a packet, (n*8,
    bb*ns) of them, each the zero-seeded crc of that packet's bytes;
    ``packet_stream`` + ``fold_chunk_crcs`` join them to the crc of the
    shard's byte stream, at a super-block count that is no power of two."""
    import jax.numpy as jnp

    p, ns, bb, c = 32, 3, 4, 2              # unit = 3 super-blocks of 256 B
    unit = ns * 8 * p
    shards = np.frombuffer(seeded(4, c * bb * unit), dtype=np.uint8) \
        .reshape(c, bb * unit)
    rows = planar_store.rows_to_planes(shards, planar_store.packet_layout(p))
    words = np.asarray(crcmod.planar_chunk_crcs(
        (jnp.asarray(rows[:8]), jnp.asarray(rows[8:])), unit, p))
    assert words.shape == (c * 8, bb * ns)
    for s in range(c):
        for t in range(8):
            for b in range(bb * ns):
                pkt = shards[s, (b * 8 + t) * p:(b * 8 + t + 1) * p]
                assert int(words[s * 8 + t, b]) == \
                    crcmod.crc32c(0, pkt.tobytes())
    got = crcmod.fold_chunk_crcs(crcmod.packet_stream(words), p)
    assert [int(x) for x in got] == [
        crcmod.crc32c(0xFFFFFFFF, row.tobytes()) for row in shards]


# ------------------------------------------------------- the served pool

async def _holders(cluster, oid):
    """osd id -> (collection, layout tag, shard id) of who stores it."""
    out = {}
    for i, osd in cluster.osds.items():
        for coll in osd.store.list_collections():
            if oid in osd.store.list_objects(coll):
                out[i] = (coll, osd.store.object_layout(coll, oid),
                          int(osd.store.getattr(coll, oid, "shard")))
    return out


def _at_rest(cluster, osd_id, coll, oid, layout) -> bytes:
    """A holder's chunk bytes, egressed by hand from what it stores (no
    byte read of the store: that would book ``unseamed``)."""
    blob = cluster.osds[osd_id].store.read_planar(coll, oid)
    return planar_store.planes_to_shard(planar_store.blob_to_planes(blob),
                                        layout=layout)


async def _interval_settled(cluster, down):
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


TINY_LAYOUT = planar_store.packet_layout(64)
TINY_REF = None


def tiny_ref():
    global TINY_REF
    if TINY_REF is None:
        TINY_REF = Reference(4, 2, 64)
    return TINY_REF


@contention_retry()
@pytest.mark.parametrize("engine", ["host", "device"])
def test_served_pool_every_holder_down_in_turn_and_rebuilt(engine,
                                                           monkeypatch):
    """``start_cluster(8)``, the profile at packetsize 64, ``write_full``
    / ``read``: the pool's unit is the rule's, every shard rests tagged
    ``packet8.64`` with the device-or-host crc of the reference's bytes,
    EVERY OSD killed in turn and every object read back byte for byte
    (a primary with and without its own shard among them: 8 OSDs, 6
    holders a PG), revived empty and marked in, its shards rebuilt equal
    to the reference's; nothing unseamed, nothing relaid."""
    if engine == "device":
        monkeypatch.setattr(stripemod, "_host_engine_ok", lambda c: False)
    ref = tiny_ref()
    size = 300000 if engine == "host" else 65537
    payloads = {f"o{i}": seeded(i, size) for i in range(6)}

    async def scenario():
        cluster = await start_cluster(8, config=_fast_config())
        seen = {"primary_held": 0, "primary_lost": 0}
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "pool", "erasure", pg_num=8, ec_profile=dict(TINY_POOL))
            prof = client.objecter.osdmap.pools[pool].ec_profile
            assert prof["stripe_unit"] == "4096"
            io = client.ioctx(pool)
            await asyncio.gather(*(io.write_full(n, d, timeout=120)
                                   for n, d in payloads.items()))
            want = {n: ref.shards(d, 4096) for n, d in payloads.items()}
            for name in payloads:
                held = await _holders(cluster, name)
                assert len(held) == N
                for osd_id, (coll, ly, shard) in held.items():
                    assert ly == TINY_LAYOUT
                    store = cluster.osds[osd_id].store
                    assert store.stat(coll, name) == want[name].shape[1]
                    assert _at_rest(cluster, osd_id, coll, name, ly) == \
                        want[name][shard].tobytes(), (name, shard)
                    assert int(store.getattr(coll, name, "hinfo_crc")) == \
                        crcmod.crc32c(0xFFFFFFFF,
                                      want[name][shard].tobytes())
            for victim in range(8):
                primaries = {
                    n: client.objecter.osdmap.pg_to_up_acting_osds(
                        client.objecter.object_pgid(pool, n))[3]
                    for n in payloads}
                await cluster.kill_osd(victim)
                await cluster.wait_down(victim)
                await _interval_settled(cluster, {victim})
                for name, data in payloads.items():
                    assert await io.read(name, timeout=60) == data, \
                        (victim, name)
                    if primaries[name] == victim:
                        seen["primary_lost"] += 1
                    else:
                        seen["primary_held"] += 1
                await cluster.revive_osd(victim)
                await client.objecter.mon_command(
                    {"prefix": "osd in", "id": victim})
                await _health_ok(client)
                loop = asyncio.get_event_loop()
                t0 = loop.time()
                for name in payloads:
                    held = await _holders(cluster, name)
                    while len(held) < N and loop.time() - t0 < 30:
                        await asyncio.sleep(0.05)
                        held = await _holders(cluster, name)
                    assert len(held) == N, (victim, name, sorted(held))
                    if victim in held:
                        coll, ly, shard = held[victim]
                        assert ly == TINY_LAYOUT
                        assert _at_rest(cluster, victim, coll, name, ly) \
                            == want[name][shard].tobytes(), (victim, name)
        finally:
            await cluster.stop()
        return seen

    before = kernels()
    seen = bounded(scenario(), 400)
    g = grew(before)
    assert seen["primary_lost"] >= 1 and seen["primary_held"] >= 1, seen
    assert "ec_planar_unseamed_conversions" not in g, g
    assert "ec_planar_relayout_conversions" not in g, g
    assert g.get("ec_gather_decodes", 0) >= 6
    assert g.get("ec_coalesced_reencode_ticks", 0) >= 1
    assert not g.get("ec_matmul_calls", 0) and \
        not g.get("ec_host_matmul_calls", 0)
    assert g["ec_planar_packet_ingest_bytes"] == g["ec_planar_ingest_bytes"]
    if engine == "device":
        assert g.get("planar_matmul_calls", 0) >= 1
        assert not g.get("ec_host_planar_matmul_calls", 0)
        assert g["ec_tick_crc_device_ticks"] == g["ec_coalesced_ticks"]
    else:
        assert g.get("ec_host_planar_matmul_calls", 0) >= 1


@contention_retry()
def test_every_pair_of_holders_down_appends_and_overwrites_read_right():
    """m = 2: each of the 15 pairs of an object's six holders down, the
    object reads back; with a pair down an append (whole stripes past
    the end) and an overwrite that is not stripe-aligned (a
    read-modify-write whose read half decodes) land right on the holders
    that are left, in the pool's serialization."""
    ref = tiny_ref()
    payload = seeded(20, 3 * 16384 + 777)            # 4 stripes, padded
    patch = seeded(21, 20000)
    tail = seeded(22, 16384 + 5)

    async def scenario():
        cluster = await start_cluster(8, config=_fast_config())
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "pool", "erasure", pg_num=1, ec_profile=dict(TINY_POOL))
            io = client.ioctx(pool)
            await io.write_full("obj", payload, timeout=120)
            held = {shard: osd_id for osd_id, (_c, _ly, shard)
                    in (await _holders(cluster, "obj")).items()}
            assert sorted(held) == list(range(N))
            want = bytearray(payload)
            for pair in itertools.combinations(range(N), 2):
                down = {held[s] for s in pair}
                for osd in down:
                    await cluster.kill_osd(osd)
                for osd in down:
                    await cluster.wait_down(osd)
                await _interval_settled(cluster, down)
                assert await io.read("obj", timeout=60) == bytes(want), pair
                if pair in ((0, 1), (2, 5)):
                    # an overwrite that begins and ends inside stripes
                    await io.write("obj", patch, offset=12345, timeout=120)
                    want[12345:12345 + len(patch)] = patch
                    assert await io.read("obj", timeout=60) == bytes(want)
                if pair == (1, 4):
                    off = len(want)
                    await io.write("obj", tail, offset=off, timeout=120)
                    want.extend(tail)
                    assert await io.read("obj", timeout=60) == bytes(want)
                for osd in down:
                    await cluster.revive_osd(osd)
                    await client.objecter.mon_command(
                        {"prefix": "osd in", "id": osd})
                await _health_ok(client)
            assert await io.read("obj", timeout=60) == bytes(want)
            rows = ref.shards(bytes(want), 4096)
            now = await _holders(cluster, "obj")
            assert len(now) == N
            for osd_id, (coll, ly, shard) in now.items():
                assert ly == TINY_LAYOUT
                assert _at_rest(cluster, osd_id, coll, "obj", ly) == \
                    rows[shard].tobytes(), (osd_id, shard)
        finally:
            await cluster.stop()

    before = kernels()
    bounded(scenario(), 420)
    g = grew(before)
    assert "ec_planar_unseamed_conversions" not in g, g
    assert g.get("ec_gather_decodes", 0) >= 15


# ------------------------------------------- the cell, rehearsed on the CPU

# one stripe of the deployment's own geometry an object (256 KiB)
TINY = {"object_bytes": 262144, "callers": 4, "payload_pool": 4,
        "lead_in_s": 0.3}


def _run_cell(seed=5, seconds=1.5, trace=False):
    from benchmark.harness import cell as cellmod
    from benchmark.harness.loader import load_cell

    cell = load_cell(CELL)
    cell.traffic = {**cell.traffic, **TINY}
    lines = []
    out = bounded(cellmod.CellRun(
        cell, seed, seconds, trace, started_at=0.0,
        say=lambda **row: lines.append(row)).run(), 300)
    out["lines"] = lines
    return out


@contention_retry()
def test_the_cell_serves_verifies_and_stays_on_the_product_plane(
        monkeypatch):
    monkeypatch.setattr(stripemod, "_host_engine_ok", lambda codec: False)
    out = _run_cell(seed=2147484048, trace=True)
    checks = {r["check"]: r for r in out["lines"] if "check" in r}
    assert out["correct"], (checks, out["errors"])
    assert out["failed"] == 0
    assert checks["degraded_read_errors"]["value"] == 0
    assert checks["degraded_decode_ticks"]["value"] >= 1
    assert checks["host_engine_calls"]["value"] == 0
    assert checks["device_matmul_calls"]["value"] >= 1
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["packet_ingest_share.write"] == 100.0
    assert m["tick_crc_device_share.write"] == pytest.approx(100.0, abs=5)
    assert m["store_direct_share.write"] == 100.0
    # (k+m)/k, but for the ops whose shards straddle the window's edge
    assert m["rest_bytes_per_byte.write"] == pytest.approx(1.5, abs=0.05)
    assert m["stack_groups.write"] == 1.0       # XLA's path off the chip
    # 7 a tick; the window's edges cut ticks in two
    assert m["dispatches_per_tick.write"] == pytest.approx(7.0, abs=0.3)


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


def test_the_cell_and_its_deployment_are_what_the_issue_names():
    from benchmark.harness.loader import load_cell

    cell = load_cell(CELL)
    cfg = cell.config
    assert (cell.config_name, cell.traffic_name, cell.chips) == \
        ("rados_cauchy_k4m2_8osd", "write_4m_t16", 1)
    assert cfg["ec_profile"] == CAUCHY
    assert (cfg["k"], cfg["m"], cfg["gf_word_bits"], cfg["stripe_unit"],
            cfg["osds"], cfg["pg_num"], cfg["pool_type"], cfg["store"]) == \
        (4, 2, 8, 65536, 8, 16, "erasure", "MemStore")
    assert sorted(cfg["reduced"]) == ["daemons_per_process", "osds",
                                      "pg_num", "store"]
    # every size and every guarantee is the neighbour's, word for word
    twin = load_cell("k4m2_write_4m_t16")
    for key in ("store_bytes_per_osd", "osds", "pg_num", "k", "m",
                "guarantees", "chips", "layout", "product_config"):
        assert cfg[key] == twin.config[key], key
    assert cell.traffic == twin.traffic
    assert cell.end_to_end == ["write_MBps", "write_p95_ms", "setup_s"]
    # every reader of the neighbour, and the one that says the layout
    assert set(cell.per_layer) == set(twin.per_layer)
    assert "packet_ingest_share.write" in cell.per_layer
    assert "planar_roofline.write" in cell.per_layer
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    # the seventh cell (the pending read cell goes behind it when listed)
    assert [w["name"] for w in spec["workloads"]][5:7] == \
        ["shec_k6m4c3_write_4m_t16", CELL]
    assert [c["name"] for c in spec["configs"]][-1] == \
        "rados_cauchy_k4m2_8osd"
    assert len(spec["workloads"]) in (7, 8) and len(spec["configs"]) == 6
    entry = spec["configs"][-1]
    assert len(entry["source"]) <= 200
    assert "erasure-code-jerasure.rst" in entry["source"] and \
        "bench.sh" in entry["source"]


def test_packet_ingest_share_reads_which_serialization_the_bytes_took():
    from benchmark.harness import layers
    from benchmark.harness.loader import load_cell

    reader = load_cell(CELL).per_layer["packet_ingest_share.write"]
    for counters, want in (
            ({"ec_planar_ingest_bytes": 9 << 22,
              "ec_planar_packet_ingest_bytes": 9 << 22}, 100.0),
            # a bit-plane pool, and a program without the counter
            ({"ec_planar_ingest_bytes": 9 << 22}, 0.0),
            # a pool off the product plane ingests nothing: left out
            ({}, None)):
        r = layers.Readings(config={}, device_kind="TPU v5 lite",
                            attribution={}, counters=counters,
                            slice_counters={}, trace=None)
        assert layers.read_metric("packet_ingest_share.write", reader, r) \
            == want


def test_the_roofline_counts_the_neighbours_rows():
    """kw = 8k = 32, rw = 8m = 16: the (16, 32) bit-matrix shape of
    ``rados_k4m2_8osd`` at g = 4; the cost function holds unchanged."""
    from benchmark.harness.loader import load_cell
    from benchmark.harness.peaks import planar_matmul_cost
    from ceph_tpu.ops.gf8_pallas import stack_groups

    cfg = load_cell(CELL).config
    assert planar_matmul_cost(cfg, 4 << 20) == planar_matmul_cost(
        load_cell("k4m2_write_4m_t16").config, 4 << 20)
    ops, moved = planar_matmul_cost(cfg, 4 << 20)
    assert ops == 2.0 * 16 * 8 * (4 << 20)      # 256 int8 ops a byte
    assert moved == 6 << 20
    assert stack_groups(8 * cfg["k"]) == 4
    assert matrix_engine(factory(dict(CAUCHY)))._enc_bitmat.shape == (16, 32)
