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


@functools.lru_cache(maxsize=32)
def _fold_words(nb: int, unit: int) -> np.ndarray:
    """The join of ``nb`` (a power of two) zero-seeded chunk crcs as ONE
    GF(2) map, (nb*32,) uint32: word 32*j + b is the crc of a row whose
    only set bit is bit b of chunk j's crc, i.e. ``A^((nb-1-j)*unit)``
    applied to ``1 << b``.  A chunk's weight depends only on its
    distance from the END of the row, so the join of any shorter run is
    this map's last words.  Built by doubling: the first half of twice
    the length is this length's words carried over ``p*unit`` more zero
    bytes."""
    words = _identity()
    p = 1
    while p < nb:
        words = np.concatenate(
            [_matvec_rows(_zeros_mat(p * unit), words), words])
        p *= 2
    return words


def fold_chunk_crcs(chunks: np.ndarray, unit: int,
                    seed: int = 0xFFFFFFFF) -> np.ndarray:
    """(R, nb) zero-seeded crcs of each row's consecutive ``unit``-byte
    chunks -> (R,) uint32 ``ceph_crc32c(seed, row)``.  Linearity:
    ``update(s, a||b) = A^len(b)(update(s, a)) ^ update(0, b)``, so a
    row's crc from zero is the XOR of one cached word per set bit of its
    chunk crcs, and ``update(seed, row) = update(seed, 0^L) ^
    update(0, row)``.  Four numpy calls however long the row: on a tick
    thread each call is a chance to lose the GIL to the event loop."""
    chunks = np.ascontiguousarray(chunks, dtype="<u4")
    r, nb = chunks.shape
    head = np.uint32(crc32c_zeros(seed, nb * unit))
    if nb == 0:
        return np.full(r, head, dtype=np.uint32)
    bits = np.unpackbits(chunks.view(np.uint8), axis=1, bitorder="little")
    words = _fold_words(1 << (nb - 1).bit_length(), unit)[-32 * nb:]
    return np.bitwise_xor.reduce(
        np.where(bits.view(bool), words, np.uint32(0)), axis=1) ^ head


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
    return [int(c) for c in fold_chunk_crcs(cs, lane, seed)]


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


def _planar_message_bitmat(length: int) -> np.ndarray:
    """``_message_bitmat(length)`` column-permuted so it applies directly
    to a plane-group BLOB (8 rows of length/8 packed bytes, row-major):
    blob bit 8*(t*cols+i)+u is D-bit 8*(8i+u)+t."""
    cols = length // 8
    base = _message_bitmat(length)
    t, i, u = np.meshgrid(np.arange(8), np.arange(cols), np.arange(8),
                          indexing="ij")
    src = (8 * (8 * i + u) + t).reshape(-1)
    return base[:, src]


@functools.lru_cache(maxsize=16)
def _planar_message_bitmat_dev(length: int):
    """Device copy of ``_planar_message_bitmat(length)``."""
    import jax.numpy as jnp

    return jnp.asarray(_planar_message_bitmat(length))


def _planar_spread(planes: np.ndarray) -> np.ndarray:
    """(g8, cols) packed planes -> (g8, 8*cols) spread byte streams S_t
    (row 8g+t spreads plane t of group g)."""
    bits = np.unpackbits(planes, axis=1, bitorder="little")
    shifts = (np.arange(planes.shape[0], dtype=np.uint8) % 8)[:, None]
    return (bits << shifts).astype(np.uint8)


def crc32c_planar_rows(planes, seed: int = 0xFFFFFFFF,
                       packetsize: int = 0):
    """(G*8, cols) packed bit-planes -> list of G ``ceph_crc32c(seed,
    byte_view)`` values, one per 8-row plane group, WITHOUT building the
    byte view.

    ``packetsize``: the rows are packet rows (``ec/planar_store.py``'s
    ``packet8`` serialization) and the byte stream is their packets,
    super-block major: whole packets change places and no bit moves, so
    the rows' bytes are walked in that order through ``crc32c_rows``.

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
    if packetsize:
        ns = cols // packetsize
        return [int(c) for c in crc32c_rows(
            arr.reshape(g, 8, ns, packetsize).transpose(0, 2, 1, 3)
            .reshape(g, length), seed)]
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


# ---------------------------------------------------------------------------
# Chunk crcs of planes that ARE on the device: the encode tick's shard crcs
# ---------------------------------------------------------------------------
#
# A coalesced encode tick (ec/stripe.py::encode_planes_multi, device
# branch) holds its data and parity planes on the device as (c*8,
# bb*unit/8) matrices: the bytes of shard s, stripe j are the columns
# [j*unit/8, (j+1)*unit/8) of plane rows s*8..s*8+8, one plane-group blob
# of ``unit`` bytes.  One program takes the zero-seeded crc of every such
# blob ((n, bb) words come back), and ``fold_chunk_crcs`` joins an op's
# run of them on the host.  Its shape depends on the bucket alone, never
# on where an op begins.

# unpacked {0,1} bytes the program may hold at once: it walks the bucket
# in groups of stripes sized to this, not all of it (8x the planes)
_CHUNK_TEMP_BYTES = 32 << 20


@functools.lru_cache(maxsize=16)
def _chunk_bitmat_dev(unit: int, rows: int = 8):
    """``_planar_message_bitmat(unit)`` as the (8*unit, 32) int8 right-hand
    side of the chunk program, rows bit-major (row u*unit + p is bit u of
    blob byte p): the program lays a blob's 8 bit positions side by side
    and never interleaves them.  1 MiB at 4 KiB, cached on the device.
    A one-row blob (``rows`` 1: a packet) is its bytes in order, and
    takes the plain message matrix."""
    import jax.numpy as jnp

    mat = (_planar_message_bitmat(unit) if rows == 8
           else _message_bitmat(unit)).reshape(32, unit, 8)
    return jnp.asarray(mat.transpose(2, 1, 0).reshape(8 * unit, 32),
                       dtype=jnp.int8)


@functools.lru_cache(maxsize=1)
def _chunk_crcs_jit():
    """Build the jitted chunk program lazily (jax import stays optional)."""
    import jax
    import jax.numpy as jnp

    # The name is read: the device trace shows this program as
    # ``jit__chunk_crcs_planes``.  It must not contain ``jit__planar_tiled``
    # (planar_roofline.write sums the device time of the programs so named
    # against the encode matmul's bytes; pinned by tests/test_tick_trace.py).
    @functools.partial(jax.jit, static_argnums=(2, 3))
    def _chunk_crcs_planes(bitmat, planes, unit: int, rows: int = 8):
        # a blob is ``unit`` bytes: ``cols`` consecutive columns of
        # ``rows`` plane rows (8: a chunk's plane group; 1: a packet)
        cols = unit // rows
        bb = planes[0].shape[1] // cols
        n = sum(p.shape[0] for p in planes) // rows
        gs = max(1, min(bb, _CHUNK_TEMP_BYTES // (n * 8 * unit)))
        gs = 1 << (gs.bit_length() - 1)
        while bb % gs:              # bb is a power of two, or times ns
            gs //= 2
        groups = jnp.concatenate(
            [p.reshape(-1, rows, bb // gs, gs, cols)
             .transpose(2, 0, 3, 1, 4)
             .reshape(bb // gs, -1, gs, unit) for p in planes],
            axis=1)                             # (G, n, gs, unit) blobs
        weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)

        def group(g):                           # (n, gs, unit) blobs
            b = g.reshape(n * gs, unit)
            bits = jnp.concatenate(
                [(b >> jnp.uint8(u)) & jnp.uint8(1) for u in range(8)],
                axis=1).astype(jnp.int8)        # (n*gs, 8*unit), bit-major
            acc = jax.lax.dot_general(
                bits, bitmat, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            return jnp.sum((acc & 1).astype(jnp.uint32) * weights,
                           axis=1, dtype=jnp.uint32).reshape(n, gs)

        out = jax.lax.map(group, groups)                     # (G, n, gs)
        return out.transpose(1, 0, 2).reshape(n, bb)

    return _chunk_crcs_planes


def planar_chunk_crcs(planes, unit: int, packetsize: int = 0):
    """Device plane matrices ``[(c_i*8, bb*unit/8), ...]`` over one column
    axis -> the DEVICE (sum c_i, bb) uint32 array whose [s, j] is
    ``ceph_crc32c(0, bytes of shard s in stripe j)``.  Launched, not
    waited for, and its words set off for the host as soon as the device
    has them: the caller's ``np.asarray`` finds them there, and folds
    each op's columns with ``fold_chunk_crcs``.

    ``packetsize``: the matrices are packet rows, and what comes back is
    (sum c_i * 8, bb * ns) words, [s*8 + t, j*ns + b] the crc of packet t
    of super-block b of shard s's chunk in stripe j: ``packet_stream``
    puts an op's columns in the byte stream's order for the fold."""
    from ceph_tpu.trace import tick as ticktrace
    from ceph_tpu.utils.perf import KERNELS

    planes = tuple(planes)
    KERNELS.inc("crc32c_planar_calls")
    KERNELS.inc("crc32c_planar_bytes",
                sum(int(p.shape[0]) * int(p.shape[1]) for p in planes))
    ticktrace.device_calls()            # the chunk program
    # a blob: a chunk's 8-row plane group, or one packet of one row
    blob, rows = (packetsize, 1) if packetsize else (unit, 8)
    words = _chunk_crcs_jit()(_chunk_bitmat_dev(blob, rows), planes, blob,
                              rows)
    words.copy_to_host_async()
    return words


def packet_stream(words: np.ndarray) -> np.ndarray:
    """(n*8, c) packet crcs of a run of columns -> (n, c*8): each shard's
    packets in the order its bytes have them (a column's 8 rows, then the
    next column), which is what ``fold_chunk_crcs`` joins."""
    n8, c = words.shape
    return np.ascontiguousarray(
        words.reshape(n8 // 8, 8, c).transpose(0, 2, 1)).reshape(n8 // 8,
                                                                  c * 8)
