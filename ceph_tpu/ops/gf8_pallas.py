"""Pallas TPU kernel for the GF(2^8) bit-matrix matmul (alternative path).

Fuses unpack -> MXU int8 matmul -> parity mask -> pack inside VMEM, one
grid program per column tile, with the (small) bit-matrix resident in
VMEM (see /opt/skills/guides/pallas_guide.md for the kernel model).

Round-5 redesign (bit-major layout): the v1 kernel reshaped the unpacked
bits through int32 VMEM (Mosaic only supports minor-dim-inserting
reshapes on 32-bit types), inflating VMEM traffic 4x.  v2 permutes the
bit-matrix rows/columns to BIT-MAJOR order host-side (row' = b*r + j,
col' = b*k + i), so the in-kernel unpack is a plain concatenate of eight
(k, TN) bit slabs and the pack is eight shift-or folds — no reshapes at
all.

MEASURED VERDICT (v5e, ISA k=8,m=4 headline shape, round-5 harness —
on-device scan loop with slope timing, see BENCH_NOTES.md; the round-3
numbers comparing 1,136 vs 167 GB/s both came from a harness that did
not wait for the device):

    XLA fused path        337-414 us / 16.7 MB step
    this kernel (v2)      307-309 us (TN >= 8192)
    v1 kernel (chunk-major, int32 reshapes)  490 us

The kernel wins ~25% on the pre-transposed (k, N) column layout, but the
end-to-end batch path needs the (B,k,S) <-> (k,N) transposes either way
(doing the transpose in-kernel measured 477 us — VMEM int32 transposes
lose to XLA's HBM transpose), which makes the full path a wash.  The
production engines therefore keep the XLA path; this kernel stays as the
validated, benchmarked alternative (bit-exact vs gf8.bitmatrix_matmul on
the real device) and the measurement record.  Both paths sit near two
simultaneous walls: HBM traffic of the materialized bit planes and the
MXU shape-padding floor (K=64, M=32 occupies 1/8 of the 128x128 array —
block-diagonal stacking measured no gain).  Going materially faster
requires bit-planar shard storage end-to-end (future work).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_TILE_N = 16384


@functools.lru_cache(maxsize=64)
def _bitmajor_perm(r8: int, k8: int):
    """Row/col permutations taking a chunk-major bit-matrix (row = j*8+b,
    col = i*8+b, from gf8.expand_bitmatrix) to bit-major order."""
    r, k = r8 // 8, k8 // 8
    rowp = [j * 8 + b for b in range(8) for j in range(r)]
    colp = [i * 8 + b for b in range(8) for i in range(k)]
    return np.asarray(rowp), np.asarray(colp)


def _kernel(bm_ref, d_ref, o_ref, *, k: int, r: int):
    tn = d_ref.shape[-1]
    d32 = d_ref[:].astype(jnp.int32)                      # (k, TN)
    # bit-major unpack: slab b holds bit b of every chunk row — no
    # reshape needed because the matrix columns were permuted to match
    bits = jnp.concatenate(
        [((d32 >> b) & 1).astype(jnp.int8) for b in range(8)], axis=0)
    acc = jax.lax.dot_general(
        bm_ref[:], bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )                                                      # (8r, TN)
    out = jnp.zeros((r, tn), jnp.int32)
    for b in range(8):
        out = out | ((acc[b * r:(b + 1) * r] & 1) << b)
    o_ref[:] = out.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _matmul_tiled(bitmat_bm, data, k: int, r: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = data.shape[1]
    grid = (n // _TILE_N,)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, r=r),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.uint8),
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((r * 8, k * 8), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((k, _TILE_N), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((r, _TILE_N), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
        ),
    )(bitmat_bm, data)


def bitmatrix_matmul(bitmat, data):
    """Drop-in for gf8.bitmatrix_matmul on column counts that tile; the
    ragged tail (n % TILE) falls back to the XLA path and concatenates."""
    from ceph_tpu.ops import gf8

    data = jnp.asarray(data)
    rw, kw = bitmat.shape
    k, r = kw // 8, rw // 8
    rowp, colp = _bitmajor_perm(rw, kw)
    # permute with jnp indexing so device arrays and tracers work without
    # a host round-trip (the matrix is tiny; the gather is trace-safe)
    bm_bm = jnp.asarray(bitmat)[rowp][:, colp].astype(jnp.int8)
    n = data.shape[1]
    main = (n // _TILE_N) * _TILE_N
    parts = []
    if main:
        parts.append(_matmul_tiled(bm_bm, data[:, :main], k, r))
    if main < n:
        parts.append(gf8.bitmatrix_matmul(bitmat, data[:, main:]))
    return parts[0] if len(parts) == 1 else \
        jnp.concatenate(parts, axis=1)


# ---------------------------------------------------------------------------
# v3 (round 6): bit-planar kernel with block-diagonal K-stacking
# ---------------------------------------------------------------------------
#
# Consumes PACKED bit-planes (gf8.bytes_to_planar layout: chunk-major rows
# j*w+t, packed byte i holding source positions 8i..8i+7) and produces
# packed parity planes — the storage format the round-6 layout contract
# keeps stripe batches in end-to-end.  Two changes over v2 attack the two
# measured walls at once:
#
#   * HBM: the {0,1} 8x expansion never leaves VMEM.  Per grid step the
#     kernel reads a (kw, TILE_P) PACKED tile (payload bytes only) and
#     writes (rw, TILE_P) packed parity planes — the byte path's ~270 MB
#     of materialized planes per 16.7 MB step becomes ~25 MB.
#   * MXU: the coding bit-matrix is stacked block-diagonally g =
#     max(1, 128 // kw) times and the tile's packed columns are split
#     into g segments stacked along K, so the dot feeds a g*kw-wide K
#     (128 for the ISA k8m4 headline's kw=64 instead of 64) and g*rw
#     output rows per pass — 2x fewer MXU column passes for the same
#     bytes.  The stacking is a pure reindexing: results are bit-exact
#     with gf8.planar_matmul_xla.
#
# Unpack is 8 shift-and slabs concatenated along LANES (packed byte u-bit
# -> lane u*seg + i), pack is 8 shift-or lane folds — no reshapes, the
# Mosaic lesson from v2 carried over.

_TILE_P = 2048            # packed columns per grid step (= 16 KiB of
                          # source bytes per chunk row)


def stack_groups(kw: int) -> int:
    """Block-diagonal stacking factor: fill the MXU's 128-wide K.

    Rounded DOWN to a power of two so the stacking always divides the
    column tile evenly (kw=24 would otherwise yield g=5 and a ragged
    segment split)."""
    g = max(1, 128 // max(1, kw))
    while g & (g - 1):
        g &= g - 1
    return g


def _planar_kernel(bm_ref, p_ref, o_ref, *, g: int, rw: int):
    tp = p_ref.shape[-1]
    seg = tp // g
    d32 = p_ref[:].astype(jnp.int32)                       # (kw, TILE_P)
    slabs = []
    for h in range(g):
        dh = d32[:, h * seg:(h + 1) * seg]
        slabs.append(jnp.concatenate(
            [((dh >> u) & 1).astype(jnp.int8) for u in range(8)],
            axis=1))                                       # (kw, seg*8)
    op = slabs[0] if g == 1 else jnp.concatenate(slabs, axis=0)
    acc = jax.lax.dot_general(
        bm_ref[:], op,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )                                                      # (g*rw, seg*8)
    outs = []
    for h in range(g):
        a = acc[h * rw:(h + 1) * rw]
        packed = jnp.zeros((rw, seg), jnp.int32)
        for u in range(8):
            packed = packed | ((a[:, u * seg:(u + 1) * seg] & 1) << u)
        outs.append(packed)
    out = outs[0] if g == 1 else jnp.concatenate(outs, axis=1)
    o_ref[:] = out.astype(jnp.uint8)


# The name is read: the XLA module is ``jit_`` + ``__name__``, and
# benchmark/layer_metrics/planar_roofline.write.json and
# trace/gapjoin.py match ``jit__planar_tiled`` in the device trace
# (pinned by tests/test_tick_trace.py).  Do not rename.
@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _planar_tiled(bitmat, planes, rw: int, kw: int, g: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # block-diagonal stack (tiny: (g*rw, g*kw) int8); built inside the jit
    # so the device constant is derived from the ARGUMENT bitmat — a jit
    # must not close over a device array; matrices stay jit arguments
    stacked = jnp.kron(jnp.eye(g, dtype=jnp.int8), bitmat.astype(jnp.int8))
    npk = planes.shape[1]
    grid = (npk // _TILE_P,)
    return pl.pallas_call(
        functools.partial(_planar_kernel, g=g, rw=rw),
        out_shape=jax.ShapeDtypeStruct((rw, npk), jnp.uint8),
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((rw * g, kw * g), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((kw, _TILE_P), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((rw, _TILE_P), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
        ),
    )(stacked, planes)


def planar_matmul(bitmat, planes):
    """Drop-in for gf8.planar_matmul_xla on TPU backends; the ragged tail
    (npk % TILE_P) falls back to the XLA planar path and concatenates."""
    from ceph_tpu.ops import gf8

    planes = jnp.asarray(planes)
    rw, kw = int(bitmat.shape[0]), int(bitmat.shape[1])
    g = stack_groups(kw)
    bm = jnp.asarray(bitmat)
    npk = planes.shape[1]
    main = (npk // _TILE_P) * _TILE_P
    parts = []
    if main:
        parts.append(_planar_tiled(bm, planes[:, :main], rw, kw, g))
    if main < npk:
        parts.append(gf8.planar_matmul_xla(bm, planes[:, main:]))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _runs_on_tpu(kernel, *args) -> bool:
    """False off-TPU.  On a TPU the kernel is run once, and a compiler or
    runtime refusal RAISES: a quiet fall back to the XLA path would hide
    a broken product path."""
    if jax.default_backend() != "tpu":
        return False
    jax.block_until_ready(kernel(*args))
    return True


@functools.lru_cache(maxsize=1)
def planar_available() -> bool:
    """Is the planar kernel this backend's route?"""
    return _runs_on_tpu(
        _planar_tiled, jnp.asarray(np.eye(8, dtype=np.int8)),
        jnp.zeros((8, _TILE_P), dtype=jnp.uint8), 8, 8, stack_groups(8))


@functools.lru_cache(maxsize=1)
def available() -> bool:
    """Same contract for the v2 byte-layout kernel."""
    return _runs_on_tpu(
        _matmul_tiled, jnp.asarray(np.eye(8, dtype=np.int8)),
        jnp.zeros((1, _TILE_N), dtype=jnp.uint8), 1, 1)
