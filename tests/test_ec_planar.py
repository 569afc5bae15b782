"""Bit-planar layout-contract tests (round 6).

The planar device layout (ceph_tpu/ec/planar.py) is only allowed to exist
because it is invisible at the host boundary: byte -> planar -> byte must
be the identity for every field width and codec geometry, and every
encode/decode routed through the planar path must be bit-identical to the
byte batch path — which the golden corpus pins to the independent C
oracle.  These tests enforce both halves of that contract, including
decode-after-erasure and the RMW/recovery stripe pipelines.
"""

import json
import pathlib

import numpy as np
import pytest

from ceph_tpu.ec import factory
from ceph_tpu.ec.planar import PlanarBatch
from ceph_tpu.ec.stripe import (
    StripeInfo,
    decode_stripes,
    encode_stripes,
    merge_range,
    reencode_stripes,
)
from ceph_tpu.ops import gf8, gfw

GOLDEN = pathlib.Path(__file__).parent / "golden" / "ec_golden.jsonl"


def _golden_cases():
    with open(GOLDEN) as f:
        return [json.loads(line) for line in f if line.strip()]


def _lcg_bytes(seed: int, n: int) -> bytes:
    x = seed & 0x7FFFFFFF
    out = bytearray(n)
    for i in range(n):
        x = (1103515245 * x + 12345) & 0x7FFFFFFF
        out[i] = (x >> 16) & 0xFF
    return bytes(out)


def _fnv1a64(data: bytes) -> str:
    h = 1469598103934665603
    for b in data:
        h ^= b
        h = (h * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    return f"{h:016x}"


# ---------------------------------------------------------------------------
# layout round-trips: byte -> planar -> byte is the identity
# ---------------------------------------------------------------------------

# the (w, chunk_size) shapes the codec families actually use: jerasure
# rsvan w8/16/32, ISA (32-aligned), LRC/SHEC 4 KiB cluster units, plus
# minimal legal sizes
ROUNDTRIP_SHAPES = [
    (8, 32), (8, 512), (8, 1024), (8, 4096),
    (16, 64), (16, 1024), (16, 2048),
    (32, 128), (32, 2048), (32, 4096),
]


@pytest.mark.parametrize("w,s", ROUNDTRIP_SHAPES,
                         ids=[f"w{w}-s{s}" for w, s in ROUNDTRIP_SHAPES])
def test_planar_roundtrip_identity(w, s):
    rng = np.random.default_rng(w * 1000 + s)
    for c in (2, 6, 12):
        d = rng.integers(0, 256, (c, s), dtype=np.uint8)
        p = np.asarray(gfw.bytes_to_planar_w(d, w))
        assert p.shape == (c * w, s // w)
        back = np.asarray(gfw.planar_to_bytes_w(p, w))
        assert np.array_equal(back, d), (w, s, c)


def test_planar_w8_matches_gf8_specialization():
    rng = np.random.default_rng(1)
    d = rng.integers(0, 256, (7, 256), dtype=np.uint8)
    assert np.array_equal(np.asarray(gf8.bytes_to_planar(d)),
                          np.asarray(gfw.bytes_to_planar_w(d, 8)))
    p = np.asarray(gf8.bytes_to_planar(d))
    assert np.array_equal(np.asarray(gf8.planar_to_bytes(p)),
                          np.asarray(gfw.planar_to_bytes_w(p, 8)))


def test_planar_batch_roundtrip_both_layouts():
    rng = np.random.default_rng(2)
    batch = rng.integers(0, 256, (5, 4, 128), dtype=np.uint8)
    pb = PlanarBatch.from_batch(batch, w=8)
    assert np.array_equal(np.asarray(pb.to_batch()), batch)
    # packet flavor (w=2 packets of 16 to keep it small: s = w*p*ns)
    batch2 = rng.integers(0, 256, (3, 5, 2 * 16 * 4), dtype=np.uint8)
    pb2 = PlanarBatch.from_batch(batch2, w=2, layout="packet",
                                 packetsize=16)
    assert np.array_equal(np.asarray(pb2.to_batch()), batch2)


def test_planar_select_and_concat():
    rng = np.random.default_rng(3)
    batch = rng.integers(0, 256, (2, 6, 64), dtype=np.uint8)
    pb = PlanarBatch.from_batch(batch, w=8)
    sub = pb.select((4, 1))
    assert np.array_equal(np.asarray(sub.to_batch()), batch[:, [4, 1], :])
    joined = pb.select((0,)).concat(pb.select((5,)))
    assert np.array_equal(np.asarray(joined.to_batch()),
                          batch[:, [0, 5], :])


def test_planar_matmul_matches_reference_math():
    rng = np.random.default_rng(4)
    m = rng.integers(0, 256, (4, 8), dtype=np.uint8)
    d = rng.integers(0, 256, (8, 512), dtype=np.uint8)
    bm = gf8.expand_bitmatrix(m)
    got = np.asarray(gf8.planar_to_bytes(
        gf8.planar_matmul(bm, gf8.bytes_to_planar(d))))
    assert np.array_equal(got, gf8.gf_matmul_ref(m, d))


def test_planar_supported_geometry_guard():
    assert PlanarBatch.supported(512, 8)
    assert not PlanarBatch.supported(12, 8)
    assert not PlanarBatch.supported(0, 8)
    assert PlanarBatch.supported(2048, 16)
    assert not PlanarBatch.supported(2040, 16)
    assert PlanarBatch.supported(768, 8, "packet", 8)
    assert not PlanarBatch.supported(760, 8, "packet", 8)


# ---------------------------------------------------------------------------
# golden corpus through the planar path, chunk for chunk
# ---------------------------------------------------------------------------

def _case_id(case):
    return (f"{case['plugin']}-{case['technique']}-k{case['k']}m{case['m']}"
            + (f"-w{case['w']}" if case.get("w", 8) != 8 else "")
            + (f"-ps{case['packetsize']}" if case["packetsize"] else ""))


@pytest.mark.parametrize("case", _golden_cases(), ids=_case_id)
def test_golden_encode_through_planar_path(case):
    w = case.get("w", 8)
    profile = {"plugin": case["plugin"], "technique": case["technique"],
               "k": str(case["k"]), "m": str(case["m"]), "w": str(w)}
    if case["packetsize"]:
        profile["packetsize"] = str(case["packetsize"])
    if case.get("c"):
        profile["c"] = str(case["c"])
    codec = factory(profile)
    k = codec.get_data_chunk_count()
    n = codec.get_chunk_count()
    s = case["chunk_size"]
    assert codec.planar_supported(s), (
        "golden geometry must ride the planar layout contract")
    data = _lcg_bytes(case["seed"], case["object_size"])
    prepared = codec.encode_prepare(data)
    batch = np.stack([prepared[codec.chunk_index(i)]
                      for i in range(k)])[None, :, :]        # (1, k, s)
    pb = codec.to_planar(batch)
    parity = np.asarray(codec.encode_planar(pb).to_batch())[0]
    chunks = {codec.chunk_index(i): np.asarray(prepared[codec.chunk_index(i)])
              for i in range(k)}
    for j in range(n - k):
        chunks[codec.chunk_index(k + j)] = parity[j]
    for i in range(n):
        blob = chunks[i].tobytes()
        expect = case["chunks"][i]
        assert blob[:16].hex() == expect["head"], f"chunk {i} head"
        assert _fnv1a64(blob) == expect["fnv1a64"], f"chunk {i} fingerprint"


@pytest.mark.parametrize("case", [c for c in _golden_cases()
                                  if c["m"] >= 2][:8], ids=_case_id)
def test_golden_decode_after_erasure_through_planar_path(case):
    """Erase chunks, reconstruct via decode_planar, compare against the
    golden chunk fingerprints — the full decode side of the contract."""
    w = case.get("w", 8)
    profile = {"plugin": case["plugin"], "technique": case["technique"],
               "k": str(case["k"]), "m": str(case["m"]), "w": str(w)}
    if case["packetsize"]:
        profile["packetsize"] = str(case["packetsize"])
    if case.get("c"):
        profile["c"] = str(case["c"])
    codec = factory(profile)
    n = codec.get_chunk_count()
    data = _lcg_bytes(case["seed"], case["object_size"])
    chunks = codec.encode(range(n), data)
    full = np.stack([np.asarray(chunks[i]) for i in range(n)])[None]
    erasures = (0, n - 1)
    zeroed = full.copy()
    for e in erasures:
        zeroed[:, e] = 0
    got = np.asarray(codec.decode_planar(
        erasures, codec.to_planar(zeroed)).to_batch())[0]
    for idx, e in enumerate(erasures):
        blob = got[idx].tobytes()
        expect = case["chunks"][e]
        assert _fnv1a64(blob) == expect["fnv1a64"], f"rebuilt chunk {e}"


# ---------------------------------------------------------------------------
# stripe pipeline: encode/decode/RMW/recovery through the planar contract
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def isa_codec():
    return factory({"plugin": "isa", "k": "4", "m": "2"})


def test_stripe_rmw_delta_through_planar(isa_codec):
    """The RMW sequence (decode old range, merge delta, re-encode) must be
    byte-identical to encoding the merged logical object directly."""
    sinfo = StripeInfo(4, 32)
    rng = np.random.default_rng(7)
    obj = rng.integers(0, 256, 4 * 32 * 4, dtype=np.uint8).tobytes()
    shards = encode_stripes(isa_codec, sinfo, obj)
    # read-modify-write: overlay 100 bytes at offset 77
    avail = {s: shards[s] for s in (1, 2, 3, 5)}   # lose shard 0 and 4 too
    old = decode_stripes(isa_codec, sinfo, avail, len(obj))
    assert old == obj
    delta = rng.integers(0, 256, 100, dtype=np.uint8).tobytes()
    merged = merge_range(old, len(obj), 77, delta)
    new_shards = encode_stripes(isa_codec, sinfo, merged)
    want = np.frombuffer(merged, dtype=np.uint8)
    back = decode_stripes(isa_codec, sinfo,
                          {s: new_shards[s] for s in range(6)}, len(merged))
    assert back == merged
    assert np.array_equal(np.frombuffer(back, dtype=np.uint8), want)


def test_reencode_stripes_matches_byte_pipeline(isa_codec):
    """Recovery fast path (planar decode+re-encode, one conversion each
    way) == decode_stripes + encode_stripes through logical bytes."""
    sinfo = StripeInfo(4, 32)
    rng = np.random.default_rng(8)
    obj = rng.integers(0, 256, 999, dtype=np.uint8).tobytes()
    shards = encode_stripes(isa_codec, sinfo, obj)
    avail = {s: shards[s] for s in (0, 2, 4, 5)}   # data 1,3 missing
    got = reencode_stripes(isa_codec, sinfo, avail, len(obj))
    data = decode_stripes(isa_codec, sinfo, avail, len(obj))
    want = encode_stripes(isa_codec, sinfo, data)
    assert np.array_equal(got, want)
    # parity-only loss: no decode needed, still one planar round trip
    avail2 = {s: shards[s] for s in (0, 1, 2, 3)}
    got2 = reencode_stripes(isa_codec, sinfo, avail2, len(obj))
    assert np.array_equal(got2, shards)
    with pytest.raises(ValueError):
        reencode_stripes(isa_codec, sinfo,
                         {s: shards[s] for s in (0, 1)}, len(obj))


def test_stripe_encode_planar_equals_non_planar_codec_path():
    """encode_stripes must produce identical shards whether or not the
    codec carries the planar contract (mesh-adapter fallback parity)."""
    codec = factory({"plugin": "isa", "k": "4", "m": "2"})
    sinfo = StripeInfo(4, 32)
    rng = np.random.default_rng(9)
    obj = rng.integers(0, 256, 700, dtype=np.uint8).tobytes()
    want = encode_stripes(codec, sinfo, obj)

    class NoPlanar:
        """Proxy hiding the planar entry points."""

        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, name):
            if name in ("planar_supported", "to_planar", "encode_planar",
                        "decode_planar"):
                raise AttributeError(name)
            return getattr(self._inner, name)

    got = encode_stripes(NoPlanar(codec), sinfo, obj)
    assert np.array_equal(got, want)


def test_lrc_single_erasure_decode_reads_only_local_group():
    """Satellite: the flattened LRC decode matrix must prune to the local
    repair group for a single local erasure (locality = the read-set win
    the reference's minimum_to_decode promises), staying bit-exact."""
    codec = factory({"plugin": "lrc", "k": "4", "m": "2", "l": "3"})
    n = codec.get_chunk_count()
    rng = np.random.default_rng(10)
    data = rng.integers(0, 256, (4, 4, 64), dtype=np.uint8)
    parity = np.asarray(codec.encode_batch(data))
    full = np.concatenate([data, parity], axis=1)
    zeroed = full.copy()
    zeroed[:, 1] = 0
    got = np.asarray(codec.decode_batch((1,), zeroed))
    assert np.array_equal(got[:, 0], full[:, 1])
    _, src_ids = codec._dec_jit[((1,), (1,))]
    assert len(src_ids) <= 3, (
        f"single local erasure should read the local group, got {src_ids}")
    # planar route agrees and shares the pruned plan
    gotp = np.asarray(codec.decode_planar(
        (1,), codec.to_planar(zeroed)).to_batch())
    assert np.array_equal(gotp[:, 0], full[:, 1])
