"""Bit-planar stripe-batch contract: the internal device layout for EC.

Round 6 (the layout-contract change BENCH_NOTES.md round 5 concluded was
required): stripe batches live on device in PACKED bit-planar form between
the host boundaries of a client op, so encode -> parity -> decode ->
RMW-delta are pure GF(2) matmuls — the per-call 8x {0,1} expansion and
re-pack that dominated the round-5 HBM traffic happens at most once per
direction per batch, and the Pallas kernel (ops/gf8_pallas.planar_matmul)
feeds the MXU a block-stacked >=128-wide K dimension.

Two planar flavors, matching the two codec families:

- ``bitpack`` (MatrixCodec families — jerasure reed_sol*, ISA, LRC, SHEC):
  planes ``(c*w, B*S/w)`` uint8, chunk-major plane rows (row ``j*w + t`` =
  bit-plane t of chunk j), built by ops/gf8.bytes_to_planar /
  ops/gfw.bytes_to_planar_w over the shard-major ``(c, B*S)`` view.

- ``packet`` (BitmatrixCodec families — cauchy/liberation):  those chunks
  are ALREADY bit-interleaved at packet granularity (jerasure's w packets
  of p bytes per super-block are packed bit-planes), so their planar form
  is the packet-row matrix ``(c*w, B*ns*p)`` of raw bytes and the matmul
  uses the byte-lane-expanded matrix — no second-level packing.

Both flavors occupy exactly the byte-layout footprint.  A PlanarBatch
lazily caches its byte-layout view so converting a batch is idempotent
and at most once in each direction.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ceph_tpu.ops import gf8, gfw
from ceph_tpu.ops.profiling import record_planar_convert


# ---------------------------------------------------------------------------
# jitted layout transforms (batch <-> planes), one dispatch each way
# ---------------------------------------------------------------------------

# The name is read: the device trace shows this program as
# ``jit__batch_to_planes_bitpack`` (PERF.md's device time by program;
# pinned by tests/test_tick_trace.py).  Do not rename.
@functools.partial(jax.jit, static_argnums=1)
def _batch_to_planes_bitpack(batch, w: int):
    """(B, c, S) bytes -> (c*w, B*S/w) packed planes (shard-major cols)."""
    b, c, s = batch.shape
    rows = batch.transpose(1, 0, 2).reshape(c, b * s)
    if w == 8:
        return gf8.bytes_to_planar(rows)
    return gfw.bytes_to_planar_w(rows, w)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _planes_to_batch_bitpack(planes, b: int, c: int, s: int, w: int):
    if w == 8:
        rows = gf8.planar_to_bytes(planes)
    else:
        rows = gfw.planar_to_bytes_w(planes, w)
    return rows.reshape(c, b, s).transpose(1, 0, 2)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _batch_to_planes_packet(batch, w: int, p: int):
    """(B, c, S) packet-interleaved chunks -> (c*w, B*ns*p) packet rows."""
    b, c, s = batch.shape
    ns = s // (w * p)
    return (
        batch.reshape(b, c, ns, w, p)
        .transpose(1, 3, 0, 2, 4)
        .reshape(c * w, b * ns * p)
    )


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _planes_to_batch_packet(rows, b: int, c: int, s: int, w: int, p: int):
    ns = s // (w * p)
    return (
        rows.reshape(c, w, b, ns, p)
        .transpose(2, 0, 3, 1, 4)
        .reshape(b, c, s)
    )


@functools.partial(jax.jit, static_argnums=(1, 2))
def _select_chunk_rows(planes, w: int, ids: Tuple[int, ...]):
    """Gather whole chunks (= w-row blocks) out of a plane matrix."""
    cw, npk = planes.shape
    c = cw // w
    sel = jnp.asarray(list(ids), dtype=jnp.int32)
    return planes.reshape(c, w, npk)[sel].reshape(len(ids) * w, npk)


@functools.partial(jax.jit, static_argnums=(2,))
def _concat_chunk_rows(a, b, w: int):
    """Stack two plane matrices along the chunk axis (data ++ parity)."""
    return jnp.concatenate([a, b], axis=0)


class PlanarBatch:
    """Device-resident EC stripe batch in planar layout.

    ``planes``: the plane matrix (see module docstring for the two
    flavors); ``nstripes``/``nchunks``/``chunk_size`` give the byte-layout
    geometry ``(B, c, S)``; ``layout`` is ``"bitpack"`` or ``"packet"``.
    The byte-layout view is computed lazily and cached (``to_batch``), so
    a batch pays at most one conversion in each direction per client op.
    """

    __slots__ = ("planes", "nstripes", "nchunks", "chunk_size", "w",
                 "layout", "packetsize", "_batch")

    def __init__(self, planes, nstripes: int, nchunks: int, chunk_size: int,
                 w: int = 8, layout: str = "bitpack",
                 packetsize: int = 0, batch=None):
        self.planes = planes
        self.nstripes = nstripes
        self.nchunks = nchunks
        self.chunk_size = chunk_size
        self.w = w
        self.layout = layout
        self.packetsize = packetsize
        self._batch = batch

    # -- construction -------------------------------------------------------

    @staticmethod
    def supported(chunk_size: int, w: int, layout: str = "bitpack",
                  packetsize: int = 0) -> bool:
        """Can this geometry round-trip losslessly?  bitpack needs packed
        groups that don't split field words across chunk boundaries."""
        if chunk_size <= 0:
            return False
        if layout == "packet":
            return packetsize > 0 and chunk_size % (w * packetsize) == 0
        return chunk_size % w == 0

    @classmethod
    def from_batch(cls, batch, w: int = 8, layout: str = "bitpack",
                   packetsize: int = 0) -> "PlanarBatch":
        batch = jnp.asarray(batch)
        b, c, s = (int(x) for x in batch.shape)
        if layout == "packet":
            planes = _batch_to_planes_packet(batch, w, packetsize)
        else:
            planes = _batch_to_planes_bitpack(batch, w)
        record_planar_convert("to_planar", b * c * s)
        # deliberately does NOT retain ``batch``: keeping the byte view
        # alive alongside the planes would double the device footprint
        # for the batch's whole lifetime; a later to_batch() re-derives
        # it (still once, then cached) and the round trip is the
        # identity by contract
        return cls(planes, b, c, s, w, layout, packetsize)

    def with_planes(self, planes, nchunks: Optional[int] = None,
                    chunk_ids=None) -> "PlanarBatch":
        """Derived batch (e.g. parity or reconstructed chunks) sharing
        this batch's geometry; ``chunk_ids`` is only for callers' records,
        the planes' chunk axis is positional."""
        del chunk_ids
        if nchunks is None:
            nchunks = int(planes.shape[0]) // self.w
        return PlanarBatch(planes, self.nstripes, nchunks, self.chunk_size,
                           self.w, self.layout, self.packetsize)

    # -- views --------------------------------------------------------------

    def to_batch(self):
        """Byte-layout (B, c, S) view, converted once and cached."""
        if self._batch is None:
            if self.layout == "packet":
                self._batch = _planes_to_batch_packet(
                    self.planes, self.nstripes, self.nchunks,
                    self.chunk_size, self.w, self.packetsize)
            else:
                self._batch = _planes_to_batch_bitpack(
                    self.planes, self.nstripes, self.nchunks,
                    self.chunk_size, self.w)
            record_planar_convert(
                "to_bytes", self.nstripes * self.nchunks * self.chunk_size)
        return self._batch

    def select(self, ids: Tuple[int, ...]) -> "PlanarBatch":
        """Sub-batch of whole chunks (cheap device row gather)."""
        ids = tuple(int(i) for i in ids)
        return PlanarBatch(
            _select_chunk_rows(self.planes, self.w, ids),
            self.nstripes, len(ids), self.chunk_size, self.w,
            self.layout, self.packetsize)

    def concat(self, other: "PlanarBatch") -> "PlanarBatch":
        """data ++ parity along the chunk axis, staying planar."""
        assert other.layout == self.layout and other.w == self.w
        return PlanarBatch(
            _concat_chunk_rows(self.planes, other.planes, self.w),
            self.nstripes, self.nchunks + other.nchunks, self.chunk_size,
            self.w, self.layout, self.packetsize)
