"""Host-side helpers for bit-planar AT-REST shards (round 19).

``ec/planar.py`` made packed bit-planes the TRAVEL format of a stripe
batch; this module makes them the format EC shard objects LIVE in.  An
at-rest planar shard of L bytes is stored as an (8, L/8) matrix of
packed GF(2) rows serialized row-major — exactly L bytes, so store
accounting, capacity admission and wire sizes are unchanged.  Two
serializations, one per chunk layout (``ec/planar.py``'s two flavors),
each with a tag of its own that stores, sub-writes and replies carry:

- ``planar8`` (bitpack, the bytewise matrix codes): the packed bit-plane
  matrix with ``gf8.bytes_to_planar`` semantics: plane row t, packed
  byte i holds bit t of shard bytes 8i..8i+7, byte 8i+u at bit u.
- ``packet8.<packetsize>`` (the w = 8 packet-interleaved bit-matrix
  codes): the packet-row matrix.  The shard is super-blocks of 8 packets
  of ``packetsize`` bytes; row t is packet t of every super-block, in
  order.  No bit moves: the conversion is a transpose of whole packets.

Either way a stripe's chunk is ``unit/8`` consecutive COLUMNS, so the
column arithmetic (splices, sub-range slices, per-op slices of a tick)
is one; what differs is the conversion at the seams, the byte quantum a
range has to keep (``quantum``) and the order a crc walks the bytes in.

Everything here is plain numpy on shard-sized payloads (the tiny host
mirror of the jitted gf8 kernels, bit-exact with them by construction):
pack/unpack at the sanctioned ingest/egress seams, the GF(2) plane-row
matmul the CPU-backend steady state runs encode/decode/reencode with,
and the column splice RMW/append deltas land through.  Each helper that
crosses the layout boundary books the ``ec_planar_*`` KERNELS counters
(ops/profiling.record_planar_at_rest) — the steady-state contract is
that ``unseamed`` stays 0, pinned by test.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ceph_tpu.ops.profiling import record_planar_at_rest
from ceph_tpu.utils.perf import KERNELS

# the store/wire layout tags carried by Obj.layout / message ``layout``
# fields; None (or "") means classic byte-at-rest
LAYOUT_PLANAR = "planar8"
_PACKET_TAG = "packet8."

# planar packing quantum in BYTES: one packed plane byte spans 8 shard
# bytes, so every offset/length crossing the planar store API must be a
# multiple of 8 (EC chunk offsets are stripe-unit multiples, and the
# planar gate requires unit % 8 == 0).  A packet shard's is a whole
# super-block: ``quantum(layout)``.
QUANTUM = 8


def packet_layout(packetsize: int) -> str:
    """The tag of the packet-row serialization at ``packetsize``."""
    return f"{_PACKET_TAG}{int(packetsize)}"


def packetsize_of(layout: Optional[str]) -> int:
    """A planar tag's packet size: 0 for ``planar8``.  Any other string
    is no serialization this module knows, and is refused by name."""
    if layout == LAYOUT_PLANAR:
        return 0
    if layout and layout.startswith(_PACKET_TAG):
        p = layout[len(_PACKET_TAG):]
        if p.isdigit() and int(p) > 0:
            return int(p)
    raise ValueError(f"no planar serialization is tagged {layout!r}")


def is_planar(layout: Optional[str]) -> bool:
    """Is this the tag of an (8, L/8) matrix at rest (either one)?"""
    return bool(layout) and (layout == LAYOUT_PLANAR
                             or layout.startswith(_PACKET_TAG))


def quantum(layout: str) -> int:
    """The bytes an offset or length of a planar object keeps to: the
    8 of a packed plane byte, or a packet shard's super-block."""
    return QUANTUM * (packetsize_of(layout) or 1)


def _transpose8(x: np.ndarray) -> np.ndarray:
    """8x8 bit-matrix transpose of every uint64 (bit 8r+c <-> bit 8c+r):
    three masked swaps of ever larger blocks, a handful of whole-array
    ops — the (.., 8, 8) {0,1} expansion it replaces cost ~0.3 s of
    event-loop time per 4 MiB object at the read egress."""
    u = np.uint64
    t = (x ^ (x >> u(7))) & u(0x00AA00AA00AA00AA)
    x = x ^ t ^ (t << u(7))
    t = (x ^ (x >> u(14))) & u(0x0000CCCC0000CCCC)
    x = x ^ t ^ (t << u(14))
    t = (x ^ (x >> u(28))) & u(0x00000000F0F0F0F0)
    return x ^ t ^ (t << u(28))


def _packets(x: np.ndarray, c: int, l: int, p: int,
             to_rows: bool) -> np.ndarray:
    """The packet transpose, either way: (c, L) chunk bytes, super-block
    major, <-> (c*8, L/8) packet rows."""
    if l % (8 * p):
        raise ValueError(f"row length {l} is not super-blocks of 8 x {p}")
    ns = l // (8 * p)
    shape = (c, ns, 8, p) if to_rows else (c, 8, ns, p)
    out = np.ascontiguousarray(x.reshape(shape).transpose(0, 2, 1, 3))
    return out.reshape(c * 8, l // 8) if to_rows else out.reshape(c, l)


def rows_to_planes(rows: np.ndarray,
                   layout: str = LAYOUT_PLANAR) -> np.ndarray:
    """(c, L) uint8 byte rows -> (c*8, L/8) packed GF(2) rows in
    ``layout``'s serialization.

    Host-numpy mirror of the jitted ``gf8.bytes_to_planar`` (same
    LSB-first packing, bit-exact) so the CPU-backend steady state
    never touches the device runtime for a layout change: each group of
    8 source bytes is an 8x8 bit matrix (byte u, bit t) whose transpose
    is the group's 8 plane bytes (plane t, bit u)."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    c, l = rows.shape
    p = packetsize_of(layout)
    if p:
        return _packets(rows, c, l, p, True)
    if l % 8:
        raise ValueError(f"row length {l} not a multiple of 8")
    nb = l // 8
    x = _transpose8(rows.reshape(c, nb, 8).view("<u8"))       # (c, i, 1)
    return np.ascontiguousarray(
        x.view(np.uint8).reshape(c, nb, 8).transpose(0, 2, 1)
    ).reshape(c * 8, nb)


def planes_to_rows(planes: np.ndarray,
                   layout: str = LAYOUT_PLANAR) -> np.ndarray:
    """(c*8, nb) packed rows -> (c, 8*nb) byte rows (inverse)."""
    planes = np.ascontiguousarray(planes, dtype=np.uint8)
    c8, nb = planes.shape
    c = c8 // 8
    p = packetsize_of(layout)
    if p:
        return _packets(planes, c, nb * 8, p, False)
    x = np.ascontiguousarray(
        planes.reshape(c, 8, nb).transpose(0, 2, 1)).view("<u8")  # (c, i, 1)
    return _transpose8(x).view(np.uint8).reshape(c, nb * 8)


# -- single-shard blob views (the store/wire serialization) -----------------

def shard_to_planes(blob, *, seam: Optional[str] = None,
                    layout: str = LAYOUT_PLANAR) -> np.ndarray:
    """Shard BYTES -> its (8, L/8) at-rest matrix in ``layout``.

    This is a layout conversion: callers must name the ``seam`` that
    sanctions it (``ingest``/``egress``/``relayout``/``unseamed``) so
    the conversion books against the right contract counter."""
    row = np.frombuffer(bytes(blob), dtype=np.uint8).reshape(1, -1)
    if seam is not None:
        record_planar_at_rest(seam, row.shape[1])
    return rows_to_planes(row, layout).reshape(8, -1)


def planes_to_shard(planes: np.ndarray, *, seam: Optional[str] = None,
                    layout: str = LAYOUT_PLANAR) -> bytes:
    """(8, nb) matrix at rest in ``layout`` -> the shard's logical BYTES."""
    planes = np.ascontiguousarray(planes, dtype=np.uint8).reshape(8, -1)
    if seam is not None:
        record_planar_at_rest(seam, planes.size)
    return planes_to_rows(planes, layout).tobytes()


def blob_to_planes(blob) -> np.ndarray:
    """At-rest plane BLOB (row-major serialization) -> (8, L/8) view.

    NOT a layout conversion — the blob already is the plane matrix, and
    the result is a view of it (read-only where the blob is): ``bytes``,
    a ``memoryview`` of a frame, a contiguous array."""
    arr = np.frombuffer(blob, dtype=np.uint8)
    if arr.size % 8:
        raise ValueError(f"planar blob size {arr.size} not 8-row")
    return arr.reshape(8, arr.size // 8)


def planes_as(blob, have: Optional[str], want: str) -> np.ndarray:
    """A shard that came tagged ``have`` -> its (8, L/8) matrix in the
    serialization ``want`` that the pool computes in.  The pool's own is
    a reshape; bytes (a member still byte-at-rest) take the one legal
    relayout hop; the OTHER serialization is refused: a pool's code
    never changes, so such a blob is somebody else's."""
    if have == want:
        return blob_to_planes(blob)
    if is_planar(have):
        raise ValueError(f"a {have!r} shard is none of a {want!r} pool's")
    return shard_to_planes(blob, seam="relayout", layout=want)


def as_shard_bytes(blob, have: Optional[str]) -> bytes:
    """A shard that came tagged ``have`` -> its logical bytes, for a
    pool that computes on bytes (a relayout hop where it came planar)."""
    if not is_planar(have):
        return blob
    return planes_to_shard(blob_to_planes(blob), seam="relayout",
                           layout=have)


def op_layout(op) -> str:
    """The serialization a store's ``write_planar`` op lands: its
    seventh field, ``planar8`` for an op journaled before there were
    two."""
    return op[6] if len(op) > 6 else LAYOUT_PLANAR


def planes_to_blob(planes: np.ndarray) -> bytes:
    """(8, nb) plane matrix -> its at-rest serialization (row-major)."""
    return np.ascontiguousarray(planes, dtype=np.uint8).tobytes()


# -- plane-domain compute (CPU-backend steady state) ------------------------

def planar_matmul_host(bitmat: np.ndarray, planes: np.ndarray) -> np.ndarray:
    """GF(2) matmul on packed bit-planes, host numpy.

    ``bitmat`` is a {0,1} bit-matrix from ``gf8.expand_bitmatrix`` (or a
    decode bitmat); packed plane bytes are 8 independent bit columns, so
    the mod-2 row combination is a plain XOR-reduce over the selected
    plane rows — bit-exact with ``gf8.planar_matmul`` by GF(2)
    linearity.  Row counts are (k+m)*8-ish (tiny); columns carry the
    payload."""
    bitmat = np.asarray(bitmat, dtype=np.uint8)
    planes = np.ascontiguousarray(planes, dtype=np.uint8)
    KERNELS.inc("ec_host_planar_matmul_calls")
    KERNELS.inc("ec_host_planar_matmul_bytes", int(planes.size))
    out = np.zeros((bitmat.shape[0], planes.shape[1]), dtype=np.uint8)
    for r in range(bitmat.shape[0]):
        sel = np.nonzero(bitmat[r])[0]
        if sel.size:
            out[r] = np.bitwise_xor.reduce(planes[sel], axis=0)
    return out


def splice_columns(old: Optional[np.ndarray], col_off: int,
                   window: np.ndarray, total_cols: int) -> np.ndarray:
    """Land a plane-column window into an at-rest shard plane matrix.

    ``old`` is the current (8, oc) matrix (None when the object is
    new); ``window`` is the delta's (8, wc) planes landing at column
    ``col_off`` (byte offset / 8); the result is zero-extended or
    truncated to ``total_cols`` — the planar analog of the byte path's
    write+truncate pair.  Pure column ops: no layout conversion."""
    window = np.ascontiguousarray(window, dtype=np.uint8).reshape(8, -1)
    wc = window.shape[1]
    out = np.zeros((8, total_cols), dtype=np.uint8)
    if old is not None and old.size:
        oc = min(old.shape[1], total_cols)
        out[:, :oc] = old[:, :oc]
    end = min(col_off + wc, total_cols)
    if end > col_off:
        out[:, col_off:end] = window[:, : end - col_off]
    return out
