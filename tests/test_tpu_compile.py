"""The served path's device programs, compiled for a TPU v5e that is
described and not attached, plus the Pallas planar kernel bit-exact in
interpret mode.

The chip's compiler is installed on the CPU dev host, so a kernel it
would refuse (tiling, VMEM budget, an unsupported reshape) is caught by
tier-1 at no chip time.  A compile that passes is not a chip run: what
the kernels compute on the device is `chip_smoke.py`'s business.

The topology is described INSIDE a module-scoped fixture, never at
import: only one process may hold libtpu, and every xdist worker imports
every test file.  Keep all such compiles in this one file.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from ceph_tpu.ec import matrices  # noqa: E402
from ceph_tpu.ops import gf8, gf8_pallas  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (rw, kw) of the bit-matrix: k8m4 encode, k2m1 encode, k8 two-erasure
# decode (2 wanted chunks from 8 survivors), the flattened LRC k4m2l3
# generator (4 coding rows) and its one-erasure decode (1 chunk from 4),
# the SHEC k6m4c3 matrix (kw = 48: K = 96 of 128 at g = 2, the first
# width that is not a power of two) and its one-erasure decode (1 chunk
# from 6 that came; the plan's own 3 sources ride kw = 24 at g = 4)
_PALLAS_WIDTHS = [
    pytest.param(32, 64, id="k8m4_encode"),
    pytest.param(8, 16, id="k2m1_encode"),
    pytest.param(16, 64, id="k8_decode_e2"),
    pytest.param(32, 32, id="lrc_k4m2l3_encode"),
    pytest.param(8, 32, id="lrc_k4m2l3_decode_e1"),
    pytest.param(32, 48, id="shec_k6m4c3_encode"),
    pytest.param(8, 48, id="shec_k6_decode_e1"),
    pytest.param(8, 24, id="shec_k6_decode_e1_from_3"),
]


@pytest.mark.parametrize("rw,kw", _PALLAS_WIDTHS)
def test_planar_pallas_kernel_compiles_for_v5e(one_chip, rw, kw):
    g = gf8_pallas.stack_groups(kw)
    npk = 64 * gf8_pallas._TILE_P           # 128 Ki packed columns
    compiled = gf8_pallas._planar_tiled.lower(
        _shape((rw, kw), jnp.uint8, one_chip),
        _shape((kw, npk), jnp.uint8, one_chip), rw, kw, g).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_planar_matmul_xla_compiles_for_v5e(one_chip):
    compiled = gf8.planar_matmul_xla.lower(
        _shape((32, 64), jnp.uint8, one_chip),
        _shape((64, 8 * gf8_pallas._TILE_P), jnp.uint8, one_chip)).compile()
    assert compiled.memory_analysis() is not None


# one k8m4 object, and the largest bucket warmed ahead (ec/stripe.py:
# 8 x 4 MiB objects, _WARM_MAX_BYTES)
@pytest.mark.parametrize("bb,k", [(128, 8), (1024, 8),
                                  # one SHEC k6m4c3 object: 171 stripes
                                  pytest.param(256, 6, id="256-k6")])
def test_batch_to_planes_compiles_for_v5e(one_chip, bb, k):
    from ceph_tpu.ec.planar import _batch_to_planes_bitpack

    _batch_to_planes_bitpack.lower(
        _shape((bb, k, 4096), jnp.uint8, one_chip), 8).compile()


# a cauchy_good k4m2 tick (PR 48): one 4 MiB object is 16 stripes of
# 64 KiB chunks; the largest bucket warmed ahead is 8 objects
@pytest.mark.parametrize("bb", [16, 128])
def test_batch_to_planes_packet_compiles_for_v5e(one_chip, bb):
    from ceph_tpu.ec.planar import _batch_to_planes_packet

    compiled = _batch_to_planes_packet.lower(
        _shape((bb, 4, 65536), jnp.uint8, one_chip), 8, 2048).compile()
    # whole packets change places: no bit is shifted out of a byte
    assert "shift-right-logical" not in compiled.as_text()


def test_chunk_crcs_program_compiles_for_v5e_on_packet_rows(one_chip):
    """The same program on one-row blobs (a packet is 2048 consecutive
    bytes of its row): 8 objects' planes, (48, 128 * 4) words back."""
    from ceph_tpu.ops.crc32c import _chunk_crcs_jit

    p, bb, ns = 2048, 128, 4
    compiled = _chunk_crcs_jit().lower(
        _shape((8 * p, 32), jnp.int8, one_chip),
        (_shape((4 * 8, bb * ns * p), jnp.uint8, one_chip),
         _shape((2 * 8, bb * ns * p), jnp.uint8, one_chip)),
        p, 1).compile()
    text = compiled.as_text()
    assert " while(" in text and "convolution(" in text


def test_crc32c_batch_compiles_for_v5e(one_chip):
    from ceph_tpu.ops.crc32c import _crc32c_batch_jit

    n, block = 4096, 4096
    _crc32c_batch_jit().lower(
        _shape((32, 8 * block), jnp.uint8, one_chip),
        _shape((n, block), jnp.uint8, one_chip),
        _shape((), jnp.uint32, one_chip)).compile()


# the encode tick's largest buckets: 8 x 4 MiB objects (48 MiB of planes)
@pytest.mark.parametrize("k,m,bb", [pytest.param(2, 1, 4096, id="k2m1"),
                                    pytest.param(4, 2, 2048, id="k4m2"),
                                    pytest.param(8, 4, 1024, id="k8m4"),
                                    pytest.param(4, 4, 2048,
                                                 id="lrc_k4m2l3"),
                                    pytest.param(6, 4, 2048,
                                                 id="shec_k6m4c3")])
def test_chunk_crcs_program_compiles_for_v5e(one_chip, k, m, bb):
    from ceph_tpu.ops.crc32c import _chunk_crcs_jit

    unit = 4096
    compiled = _chunk_crcs_jit().lower(
        _shape((8 * unit, 32), jnp.int8, one_chip),
        (_shape((k * 8, bb * unit // 8), jnp.uint8, one_chip),
         _shape((m * 8, bb * unit // 8), jnp.uint8, one_chip)),
        unit).compile()
    # the bucket is walked in groups of stripes: one loop, one matmul
    text = compiled.as_text()
    assert " while(" in text and "convolution(" in text


def test_crush_rule_compiles_for_v5e(one_chip):
    """One rack of the three-level map (256 OSDs); the 10k-OSD map takes
    ~20 s to compile and belongs to chip_smoke.py."""
    from ceph_tpu.crush.mapper import TensorMapper
    from ceph_tpu.crush.types import build_three_level

    cmap, rule = build_three_level(n_racks=1, hosts_per_rack=16,
                                   osds_per_host=16, numrep=3)
    mapper = TensorMapper(cmap, chunk=1 << 14)
    fn, tensors = mapper.compiled_rule(rule, 3)
    t_shapes = jax.tree_util.tree_map(
        lambda a: _shape(a.shape, a.dtype, one_chip), tensors)
    fn.lower(_shape((mapper.chunk,), jnp.uint32, one_chip),
             _shape((cmap.max_devices,), jnp.uint32, one_chip),
             t_shapes).compile()


# (k, m, packed columns): g = 2, 8, 4 and (kw = 48, K = 96 of 128) 2
# stack groups; the second case spans two grid steps
@pytest.mark.parametrize("k,m,npk", [(8, 4, gf8_pallas._TILE_P),
                                     (2, 1, 2 * gf8_pallas._TILE_P),
                                     (4, 2, gf8_pallas._TILE_P),
                                     (6, 4, gf8_pallas._TILE_P)])
def test_planar_kernel_interpret_matches_xla(k, m, npk):
    """`_planar_kernel` (unpack, K-stacked dot, pack) in Pallas interpret
    mode, bit for bit against planar_matmul_xla.  Needs no topology."""
    from jax.experimental import pallas as pl
    import functools

    rng = np.random.default_rng(k * 16 + m)
    bitmat = np.asarray(gf8.expand_bitmatrix(matrices.isa_rs_matrix(k, m)))
    rw, kw = bitmat.shape
    g = gf8_pallas.stack_groups(kw)
    planes = rng.integers(0, 256, (kw, npk), dtype=np.uint8)
    stacked = np.kron(np.eye(g, dtype=np.int8), bitmat.astype(np.int8))
    tp = gf8_pallas._TILE_P
    got = pl.pallas_call(
        functools.partial(gf8_pallas._planar_kernel, g=g, rw=rw),
        out_shape=jax.ShapeDtypeStruct((rw, npk), jnp.uint8),
        grid=(npk // tp,),
        in_specs=[pl.BlockSpec((rw * g, kw * g), lambda i: (0, 0)),
                  pl.BlockSpec((kw, tp), lambda i: (0, i))],
        out_specs=pl.BlockSpec((rw, tp), lambda i: (0, i)),
        interpret=True,
    )(jnp.asarray(stacked), jnp.asarray(planes))
    want = gf8.planar_matmul_xla(jnp.asarray(bitmat), jnp.asarray(planes))
    assert np.array_equal(np.asarray(got), np.asarray(want))
