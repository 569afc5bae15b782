"""crc32c (Castagnoli) — host and batched-TPU checksumming.

Behavioral mirror of reference ceph_crc32c (src/include/crc32c.h:43,
src/common/sctp_crc32.c): a raw reflected CRC-32C table update from a caller
seed, with NO pre/post inversion, and the null-buffer convention meaning
"length zero bytes" (src/common/crc32c.cc:214-239 ceph_crc32c_zeros).

TPU-first design: CRC is GF(2)-linear in the message bits —
``update(seed, m) = A^len(seed) XOR L(m)`` — so a batch of fixed-size blocks
is ONE bit-matrix matmul on the MXU, reusing the erasure-code substrate
(ops/gf8.bitmatrix_matmul).  The combine/zero-extend operators are 32x32
GF(2) matrix powers, the same trick the reference's crc32c.cc:54+ uses for
crc_turbo_table.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

CRC32C_POLY_REFLECTED = 0x82F63B78

# hardware/SIMD crc32c when the image ships it (the reference's
# crc32c_intel / sctp_crc32 fast paths): google_crc32c computes the
# STANDARD finalized CRC-32C, which maps to our raw ceph_crc32c update
# exactly as update(seed, m) = extend(seed ^ ~0, m) ^ ~0 (verified in
# tests against the table path).  None -> the numpy table paths below.
try:
    import google_crc32c as _gcrc
except ImportError:  # pragma: no cover - image without the wheel
    _gcrc = None


def _build_table():
    tbl = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ (CRC32C_POLY_REFLECTED if c & 1 else 0)
        tbl[i] = c
    return tbl


CRC_TABLE = _build_table()

# ---------------------------------------------------------------------------
# GF(2) 32x32 matrix algebra (matrices as 32 uint32 columns)
# ---------------------------------------------------------------------------


def _mat_vec(m: np.ndarray, v: int) -> int:
    out = 0
    vv = int(v)
    j = 0
    while vv:
        if vv & 1:
            out ^= int(m[j])
        vv >>= 1
        j += 1
    return out


def _mat_mat(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(a . b)[j] = a . b[j]; vectorized column combine."""
    bits = (b[:, None] >> np.arange(32)[None, :]) & 1      # (col j, bit i)
    sel = np.where(bits.astype(bool), a[None, :, ], 0)
    return np.bitwise_xor.reduce(sel, axis=1).astype(np.uint32)


def _identity():
    return (np.uint32(1) << np.arange(32)).astype(np.uint32)


def _zero_byte_op():
    """A_1: one zero-byte update, crc' = (crc >> 8) ^ tbl[crc & 0xff]."""
    cols = np.zeros(32, dtype=np.uint32)
    for j in range(32):
        e = 1 << j
        cols[j] = ((e >> 8) ^ int(CRC_TABLE[e & 0xFF])) & 0xFFFFFFFF
    return cols


_A1 = _zero_byte_op()


@functools.lru_cache(maxsize=256)
def _zeros_op(length: int) -> bytes:
    """A_1^length, cached (returned as bytes for hashability)."""
    result = _identity()
    sq = _A1.copy()
    n = length
    while n:
        if n & 1:
            result = _mat_mat(sq, result)
        sq = _mat_mat(sq, sq)
        n >>= 1
    return result.tobytes()


def _zeros_mat(length: int) -> np.ndarray:
    return np.frombuffer(_zeros_op(length), dtype=np.uint32)


# ---------------------------------------------------------------------------
# Host path
# ---------------------------------------------------------------------------


def crc32c(crc: int, data: Optional[bytes], length: Optional[int] = None) -> int:
    """ceph_crc32c semantics: raw update from seed; data=None means zeros."""
    crc &= 0xFFFFFFFF
    if data is None:
        if not length:
            return crc
        return crc32c_zeros(crc, length)
    buf = np.frombuffer(memoryview(data), dtype=np.uint8)
    if length is not None:
        buf = buf[:length]
    if len(buf) == 0:
        return crc
    if _gcrc is not None:
        # the C extension accepts only bytes proper: pass the caller's
        # bytes straight through, else one copy — still ~3x the table
        # paths end to end
        raw = data if isinstance(data, bytes) and length is None \
            else buf.tobytes()
        return _gcrc.extend(crc ^ 0xFFFFFFFF, raw) ^ 0xFFFFFFFF
    # block-parallel: split into lanes, CRC each lane vectorized bytewise,
    # then combine with the zero-extension operator
    lane = 4096
    if len(buf) <= lane:
        c = np.uint32(crc)
        for b in buf:
            c = CRC_TABLE[(c ^ b) & np.uint32(0xFF)] ^ (c >> np.uint32(8))
        return int(c)
    n_full = len(buf) // lane
    blocks = buf[: n_full * lane].reshape(n_full, lane)
    cs = np.zeros(n_full, dtype=np.uint32)
    for i in range(lane):
        cs = CRC_TABLE[(cs ^ blocks[:, i]) & np.uint32(0xFF)] ^ (cs >> np.uint32(8))
    # fold lanes left to right: crc = A^lane(crc) ^ lane_crc (lane seeded 0)
    total = crc
    for c in cs:
        total = crc32c_zeros(total, lane) ^ int(c)
    tail = buf[n_full * lane :]
    if len(tail):
        total = crc32c(total, tail.tobytes())
    return total & 0xFFFFFFFF


def crc32c_zeros(crc: int, length: int) -> int:
    """CRC across `length` zero bytes (reference crc32c.cc:214)."""
    return _mat_vec(_zeros_mat(length), crc)


def crc32c_combine(crc_a: int, crc_b: int, len_b: int) -> int:
    """CRC of a||b from crc(a) and crc(b) (b seeded with 0)."""
    return crc32c_zeros(crc_a, len_b) ^ crc_b


# ---------------------------------------------------------------------------
# Device path: batched fixed-size blocks as one GF(2) matmul
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=16)
def _message_bitmat(block: int) -> np.ndarray:
    """(32, 8*block) GF(2) matrix L with update(0, m) = L @ bits(m).

    Column (p, i): contribution of bit i of byte p, i.e.
    A_1^(block-1-p) . tbl[1 << i].
    """
    t_cols = np.array([CRC_TABLE[1 << i] for i in range(8)], dtype=np.uint32)
    m = np.zeros((32, 8 * block), dtype=np.uint8)
    p_op = _identity()
    for p in range(block - 1, -1, -1):
        cols = np.array([_mat_vec(p_op, int(c)) for c in t_cols], dtype=np.uint32)
        bits = (cols[None, :] >> np.arange(32)[:, None]) & 1  # (32, 8)
        m[:, 8 * p : 8 * p + 8] = bits.astype(np.uint8)
        p_op = _mat_mat(_A1, p_op)
    return m


def _crc32c_batch_jit():
    """Build the jitted device path lazily (jax import stays optional)."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.ops import gf8

    @jax.jit
    def fn(bitmat, data, const):
        # bitmatrix_matmul wants (k, n) columns: one block per column;
        # the WHOLE batch CRC is one dispatch — transpose, matmul, and the
        # byte->u32 recombination all inside the jit
        out_bytes = gf8.bitmatrix_matmul(bitmat, data.T)   # (4, N)
        crcs = (
            out_bytes[0].astype(jnp.uint32)
            | (out_bytes[1].astype(jnp.uint32) << 8)
            | (out_bytes[2].astype(jnp.uint32) << 16)
            | (out_bytes[3].astype(jnp.uint32) << 24)
        )
        return crcs ^ const

    return fn


_batch_jit = None


@functools.lru_cache(maxsize=16)
def _message_bitmat_dev(block: int):
    """Device-resident copy of the message matrix, cached per block size —
    re-uploading ~1 MiB per call would defeat the one-dispatch hot path.
    It stays a jit ARGUMENT: a jit must not close over a device array."""
    import jax.numpy as jnp

    return jnp.asarray(_message_bitmat(block))


def crc32c_batch(data, seed: int = 0xFFFFFFFF):
    """(N, B) uint8 blocks -> (N,) uint32 CRCs, computed on device.

    Equivalent to [ceph_crc32c(seed, row) for row in data], as one MXU
    matmul (linearity: update(seed, m) = L(m) ^ update(seed, 0^B)).
    """
    import jax.numpy as jnp

    from ceph_tpu.utils.perf import KERNELS

    global _batch_jit
    if _batch_jit is None:
        _batch_jit = _crc32c_batch_jit()
    data = jnp.asarray(data)
    n, block = data.shape
    KERNELS.inc("crc32c_batch_calls")
    KERNELS.inc("crc32c_batch_bytes", int(n) * int(block))
    bitmat = _message_bitmat_dev(block)
    const = np.uint32(crc32c_zeros(seed, block))
    return _batch_jit(bitmat, data, const)


def _matvec_rows(m: np.ndarray, v: np.ndarray) -> np.ndarray:
    """The GF(2) 32x32 operator applied to a VECTOR of crc words
    (the _mat_vec loop vectorized across rows)."""
    bits = (v[:, None] >> np.arange(32, dtype=np.uint32)[None, :]) & 1
    sel = np.where(bits.astype(bool), m[None, :], 0)
    return np.bitwise_xor.reduce(sel, axis=1).astype(np.uint32)


def _fold_blocks(cs2d: np.ndarray, lane: int) -> np.ndarray:
    """(R, nb) per-block crcs (each seeded 0) -> (R,) ``update(0, row)``
    via a pairwise zero-extension tree: log2(nb) vectorized rounds
    instead of nb sequential folds.  Left-padding with zero crcs is the
    identity (leading zero bytes of a zero-seeded crc stay zero)."""
    r, nb = cs2d.shape
    pow2 = 1 << max(0, nb - 1).bit_length() if nb > 1 else 1
    if pow2 != nb:
        cs2d = np.concatenate(
            [np.zeros((r, pow2 - nb), np.uint32), cs2d], axis=1)
        nb = pow2
    span = 1
    while nb > 1:
        ext = _zeros_mat(lane * span)
        left = np.ascontiguousarray(cs2d[:, 0::2]).reshape(-1)
        right = np.ascontiguousarray(cs2d[:, 1::2]).reshape(-1)
        cs2d = (_matvec_rows(ext, left) ^ right).reshape(r, nb // 2)
        nb //= 2
        span *= 2
    return cs2d[:, 0]


_HOST_LANE = 512


def _block_crcs_host(arr: np.ndarray, lane: int) -> np.ndarray:
    """(R, L) rows -> (R, L/lane) zero-seeded per-block crcs with the
    table loop vectorized across EVERY block of every row: the python
    iteration count is the lane length, amortized over the whole batch
    (the CPU-backend stand-in for the device crc32c_batch matmul)."""
    r, length = arr.shape
    nb = length // lane
    bt = np.ascontiguousarray(arr.reshape(r * nb, lane).T)
    cs = np.zeros(r * nb, dtype=np.uint32)
    for i in range(lane):
        cs = CRC_TABLE[(cs ^ bt[i]) & np.uint32(0xFF)] ^ \
            (cs >> np.uint32(8))
    return cs.reshape(r, nb)


def crc32c_rows(rows, seed: int = 0xFFFFFFFF, block: int = 4096):
    """(R, L) uint8 rows -> list of R ``ceph_crc32c(seed, row)`` values,
    the bulk byte work batched across the whole row set.

    Device backends: rows are cut into fixed ``block`` columns and every
    block of every row rides ONE ``crc32c_batch`` matmul — the coalesced
    EC write path's "one crc32c batch per tick".  CPU backends skip the
    device hop (XLA:CPU emulates the GF(2) bit-matmul far below memory
    bandwidth — BENCH_NOTES round 11) and run the lane-vectorized host
    table loop over the same whole-batch block set.  Either way the
    per-block crcs fold per row with the zero-extension operator tree —
    linearity: ``update(s, a||b) = A^len(b)(update(s, a)) ^
    update(0, b)``.  Row lengths not divisible by the block fall back to
    the per-row host path.
    """
    arr = np.asarray(rows, dtype=np.uint8)
    if arr.ndim != 2:
        raise ValueError("rows must be 2-D")
    r, length = arr.shape
    if r == 0:
        return []
    if _gcrc is not None:
        # hardware crc: the per-row C pass beats any batching scheme
        return [crc32c(seed, row.tobytes()) for row in arr]
    import jax

    host = jax.default_backend() == "cpu"
    lane = _HOST_LANE if host else block
    if length == 0 or length % lane:
        return [crc32c(seed, row.tobytes()) for row in arr]
    if host:
        cs = _block_crcs_host(arr, lane)
    else:
        nb = length // lane
        cs = np.asarray(crc32c_batch(arr.reshape(r * nb, lane),
                                     seed=0)).reshape(r, nb)
    folded = _fold_blocks(cs, lane)
    # update(seed, row) = update(seed, 0^L) ^ update(0, row)
    head = np.uint32(crc32c_zeros(seed, length))
    return [int(c) for c in (folded ^ head)]


# ---------------------------------------------------------------------------
# Planar row view (round 19): CRC the BYTE stream of packed bit-planes
# without materializing it
# ---------------------------------------------------------------------------
#
# An at-rest planar shard (ec/planar_store.py) is its (8, cols) packed
# bit-plane matrix; its logical byte stream D (length M = 8*cols) never
# exists on the steady-state path.  CRC is GF(2)-linear in the message
# bits, and D = XOR_t S_t where S_t is the M-byte "spread" of plane t
# (S_t[8i+u] = bit t of D[8i+u], placed at bit position t), so
#
#   update(seed, D) = XOR_t update(0, S_t) ^ update(seed, 0^M)
#
# (the 8 linear-part constants cancel pairwise — 8 is even).  hinfo CRCs
# of planar shards therefore stay bit-identical to the byte anchor.

# cap on the full-length planar message matrix a device dispatch will
# build ((32, 8*M) uint8); past it the host spread path takes over
_PLANAR_DEV_MAX = 1 << 15


@functools.lru_cache(maxsize=16)
def _planar_message_bitmat_dev(length: int):
    """Device copy of ``_message_bitmat(length)`` column-permuted so it
    applies directly to a plane-group BLOB (8 rows of length/8 packed
    bytes, row-major): blob bit 8*(t*cols+i)+u is D-bit 8*(8i+u)+t."""
    import jax.numpy as jnp

    cols = length // 8
    base = _message_bitmat(length)
    t, i, u = np.meshgrid(np.arange(8), np.arange(cols), np.arange(8),
                          indexing="ij")
    src = (8 * (8 * i + u) + t).reshape(-1)
    return jnp.asarray(base[:, src])


def _planar_spread(planes: np.ndarray) -> np.ndarray:
    """(g8, cols) packed planes -> (g8, 8*cols) spread byte streams S_t
    (row 8g+t spreads plane t of group g)."""
    bits = np.unpackbits(planes, axis=1, bitorder="little")
    shifts = (np.arange(planes.shape[0], dtype=np.uint8) % 8)[:, None]
    return (bits << shifts).astype(np.uint8)


def crc32c_planar_rows(planes, seed: int = 0xFFFFFFFF):
    """(G*8, cols) packed bit-planes -> list of G ``ceph_crc32c(seed,
    byte_view)`` values, one per 8-row plane group, WITHOUT building the
    byte view.

    Rows come in eights (group g = rows 8g..8g+7 = one shard's at-rest
    planes, ec/planar_store.py layout).  Device backends run ONE
    ``crc32c_batch``-style matmul over the raw plane blobs with a
    column-permuted message matrix; host backends CRC the 8 spread
    streams per group through ``crc32c_rows`` and XOR-fold.  Both are
    bit-identical to ``crc32c(seed, planes_to_shard(group))``.
    """
    from ceph_tpu.utils.perf import KERNELS

    arr = np.ascontiguousarray(planes, dtype=np.uint8)
    if arr.ndim != 2 or arr.shape[0] % 8:
        raise ValueError("planes must be (G*8, cols)")
    g8, cols = arr.shape
    g = g8 // 8
    if g == 0:
        return []
    length = 8 * cols
    KERNELS.inc("crc32c_planar_calls")
    KERNELS.inc("crc32c_planar_bytes", g * length)
    if length == 0:
        return [crc32c(seed, b"")] * g
    if _gcrc is None and length <= _PLANAR_DEV_MAX:
        import jax

        if jax.default_backend() != "cpu":
            # one matmul over the at-rest blobs: no spread, no byte view
            global _batch_jit
            if _batch_jit is None:
                _batch_jit = _crc32c_batch_jit()
            import jax.numpy as jnp

            from ceph_tpu.trace import tick as ticktrace

            # host->device copy of the blobs, the crc program, readback;
            # booked on the encode tick open on this thread, if any
            ticktrace.device_calls(3)
            bitmat = _planar_message_bitmat_dev(length)
            const = np.uint32(crc32c_zeros(seed, length))
            blobs = jnp.asarray(arr.reshape(g, length))
            return [int(c) for c in np.asarray(
                _batch_jit(bitmat, blobs, const))]
    parts = np.asarray(crc32c_rows(_planar_spread(arr), seed=0),
                       dtype=np.uint32).reshape(g, 8)
    folded = np.bitwise_xor.reduce(parts, axis=1)
    head = np.uint32(crc32c_zeros(seed, length))
    return [int(c) for c in (folded ^ head)]
