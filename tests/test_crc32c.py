"""crc32c: known vectors, ceph semantics, combine/zeros, device batch."""

import numpy as np
import pytest

from ceph_tpu.ops import crc32c as c


def _ref_crc(crc, data):
    for b in data:
        crc = int(c.CRC_TABLE[(crc ^ b) & 0xFF]) ^ (crc >> 8)
    return crc


def test_standard_check_value():
    # standard CRC-32C("123456789") with init/final inversion = 0xE3069283
    raw = c.crc32c(0xFFFFFFFF, b"123456789")
    assert (raw ^ 0xFFFFFFFF) == 0xE3069283


def test_matches_bytewise_reference():
    rng = np.random.default_rng(0)
    for n in [0, 1, 7, 255, 4096, 10000]:
        data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        assert c.crc32c(0xFFFFFFFF, data) == _ref_crc(0xFFFFFFFF, data)
        assert c.crc32c(0, data) == _ref_crc(0, data)


def test_zeros_and_null_buffer():
    for n in [1, 5, 100, 4096]:
        want = _ref_crc(0xDEADBEEF, bytes(n))
        assert c.crc32c_zeros(0xDEADBEEF, n) == want
        # ceph null-buffer convention
        assert c.crc32c(0xDEADBEEF, None, n) == want


def test_combine():
    rng = np.random.default_rng(1)
    a = rng.integers(0, 256, 1000, dtype=np.uint8).tobytes()
    b = rng.integers(0, 256, 777, dtype=np.uint8).tobytes()
    crc_a = c.crc32c(0xFFFFFFFF, a)
    crc_b = c.crc32c(0, b)
    assert c.crc32c_combine(crc_a, crc_b, len(b)) == c.crc32c(0xFFFFFFFF, a + b)


def test_device_batch_matches_host():
    rng = np.random.default_rng(2)
    for block in [32, 512]:
        data = rng.integers(0, 256, (64, block), dtype=np.uint8)
        got = np.asarray(c.crc32c_batch(data))
        want = np.array(
            [c.crc32c(0xFFFFFFFF, row.tobytes()) for row in data],
            dtype=np.uint32,
        )
        assert np.array_equal(got, want)
    # non-default seed
    data = rng.integers(0, 256, (8, 64), dtype=np.uint8)
    got = np.asarray(c.crc32c_batch(data, seed=123))
    want = np.array([c.crc32c(123, r.tobytes()) for r in data], dtype=np.uint32)
    assert np.array_equal(got, want)


# ------------------------------------------------------------------------
# the encode tick's shard crcs: per-chunk crcs from the planes a tick
# holds on the device (one program), folded per op on the host
# ------------------------------------------------------------------------


def _planes_of(rows):
    import jax.numpy as jnp

    from ceph_tpu.ops import gf8

    return gf8.bytes_to_planar(jnp.asarray(rows))


# (k, m, stripes of each op, bucket, unit, which ops want crcs)
_CHUNK_CASES = [
    pytest.param(2, 1, [1], 1, 4096, [True], id="one_stripe"),
    pytest.param(2, 1, [5], 8, 512, [True], id="non_power_of_two"),
    pytest.param(4, 2, [3, 8], 16, 64, [True, True],
                 id="two_lengths_bucket_over_total"),
    pytest.param(2, 1, [2, 2, 1, 2], 8, 4096, [True, False, True, True],
                 id="same_lengths_fold_together"),
    pytest.param(2, 2, [7, 0, 2], 16, 128, [True, True, True],
                 id="empty_op_between"),
]


@pytest.mark.parametrize("k,m,counts,bb,unit,want", _CHUNK_CASES)
def test_chunk_crc_program_and_fold_match_scalar(k, m, counts, bb, unit,
                                                 want):
    """The chunk program on the (n*8, bb*unit/8) planes of a bucket, then
    the per-op fold: every shard crc equals ``crc32c(~0, shard bytes)``,
    wherever the op begins and whatever the bucket pads."""
    from ceph_tpu.ec.stripe import _fold_op_crcs

    n, total = k + m, sum(counts)
    rng = np.random.default_rng(n * 1000 + total)
    rows = np.zeros((n, bb * unit), dtype=np.uint8)
    rows[:, :total * unit] = rng.integers(0, 256, (n, total * unit),
                                          dtype=np.uint8)
    planes = _planes_of(rows)
    chunks = np.asarray(c.planar_chunk_crcs(
        (planes[:k * 8], planes[k * 8:]), unit))
    assert chunks.shape == (n, bb) and chunks.dtype == np.uint32
    # each word is the zero-seeded crc of one shard's bytes in one stripe
    for s, j in [(0, 0), (n - 1, bb - 1), (k, total - 1)]:
        assert int(chunks[s, j]) == c.crc32c(
            0, rows[s, j * unit:(j + 1) * unit].tobytes())
    got = _fold_op_crcs(chunks, counts, want, unit)
    assert sorted(got) == [i for i, w in enumerate(want) if w]
    c0 = 0
    for i, ns in enumerate(counts):
        if want[i]:
            assert got[i] == [
                c.crc32c(0xFFFFFFFF,
                         rows[s, c0 * unit:(c0 + ns) * unit].tobytes())
                for s in range(n)], i
        c0 += ns


@pytest.mark.parametrize("profile,sizes", [
    pytest.param({"k": "2", "m": "1"}, [8192, 3 * 8192 - 100, 0, 8192],
                 id="k2m1_padded_last_stripe_and_empty"),
    pytest.param({"k": "4", "m": "2"}, [5 * 16384, 16384 - 1],
                 id="k4m2_two_lengths"),
])
def test_encode_planes_multi_device_crcs_equal_host_crcs(monkeypatch,
                                                         profile, sizes):
    """``encode_planes_multi``: the device branch (crcs from the chunk
    program) returns what the host branch (``crc32c_planar_rows``)
    returns, and both are the crc of the shard's bytes."""
    from ceph_tpu.ec import factory, stripe
    from ceph_tpu.ec import planar_store as pstore
    from ceph_tpu.utils.perf import KERNELS

    codec = factory({"plugin": "jerasure", "technique": "reed_sol_van",
                     **profile})
    sinfo = stripe.StripeInfo(int(profile["k"]), 4096)
    rng = np.random.default_rng(len(sizes))
    datas = [rng.integers(0, 256, sz, dtype=np.uint8).tobytes()
             for sz in sizes]
    want = [True] * len(datas)
    host = stripe.encode_planes_multi(codec, sinfo, datas, want)
    monkeypatch.setattr(stripe, "_host_engine_ok", lambda codec: False)
    before = dict(KERNELS.dump()["device_kernels"])
    dev = stripe.encode_planes_multi(codec, sinfo, datas, want)
    after = KERNELS.dump()["device_kernels"]
    grew = {name: after[name] - before.get(name, 0) for name in after}
    # one chunk program for the tick, booked as crc work and not as an
    # encode matmul (planar_roofline.write reads planar_matmul_bytes)
    bb = stripe._bucket(sum(sinfo.object_stripes(sz) for sz in sizes))
    n = codec.get_chunk_count()
    assert grew["crc32c_planar_calls"] == 1
    assert grew["crc32c_planar_bytes"] == bb * n * 4096
    assert grew["planar_matmul_calls"] == 1
    assert grew["planar_matmul_bytes"] == bb * sinfo.k * 4096
    for (hp, hc), (dp, dc) in zip(host, dev):
        assert np.array_equal(hp, dp)
        assert hc == dc
        assert dc == [c.crc32c(0xFFFFFFFF, pstore.planes_to_shard(p))
                      for p in dp]
