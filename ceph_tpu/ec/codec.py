"""Matrix and bit-matrix codecs executing on the TPU MXU.

These are the concrete compute engines behind the jerasure/isa/lrc/shec
plugin families.  Where the reference dispatches to native SIMD libraries
(jerasure_matrix_encode, ISA-L ec_encode_data — reference
ErasureCodeJerasure.cc:156, ErasureCodeIsa.cc:128), we lower the identical
math to a single GF(2) matmul on the MXU (see ceph_tpu.ops.gf8).

Two layouts, matching the two native encode styles:

- MatrixCodec: bytewise GF(2^8) matrix codes (reed_sol_van, reed_sol_r6,
  ISA-L vandermonde/cauchy).  Each output byte position is independent.
- BitmatrixCodec: jerasure's packet-interleaved bit-matrix codes (cauchy_orig,
  cauchy_good; the liberation family slots in here once its matrix builders
  land).  Chunks are w-packet interleaved; encode XORs whole packets selected
  by a (m*w, k*w) GF(2) matrix — natively a GF(2) matmul
  (jerasure_schedule_encode semantics, reference ErasureCodeJerasure.cc:260).
"""

from __future__ import annotations

import errno
import functools
from typing import Dict, Mapping, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.ec import matrices
from ceph_tpu.ec.base import ErasureCode
from ceph_tpu.ec.interface import ECError
from ceph_tpu.ec.table_cache import DecodeTableCache
from ceph_tpu.ops import gf8, gfw
from ceph_tpu.trace import tick as ticktrace
from ceph_tpu.utils.perf import KERNELS


def _record_kernel(kind: str, bitmat_shape, nbytes: int) -> None:
    """Device-kernel telemetry: invocation count, payload bytes, and the
    MXU shape-padding waste (a (R, K) GF(2) matmul occupies 128-multiple
    tiles; the unused lanes are throughput the shape leaves on the
    floor — see BENCH_NOTES.md 'where the encode time actually goes')."""
    KERNELS.inc(f"{kind}_calls")
    KERNELS.inc(f"{kind}_bytes", int(nbytes))
    r, k = int(bitmat_shape[0]), int(bitmat_shape[1])
    tiles = (-(-r // 128) * 128) * (-(-k // 128) * 128)
    used = r * k
    if used:
        KERNELS.inc(f"{kind}_mxu_pad_bytes",
                    int(nbytes * (tiles - used) / used))


@functools.lru_cache(maxsize=64)
def _lane_expand(mat_bytes: bytes, shape):
    """Kronecker-expand a 0/1 packet-selection matrix over the 8 byte lanes."""
    m01 = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(shape)
    return jnp.asarray(np.kron(m01, np.eye(8, dtype=np.uint8)))


@jax.jit
def _encode_cols(bitmat, data):
    """bitmat (8r, 8k) x data (k, N) -> (r, N); the device hot path."""
    return gf8.bitmatrix_matmul(bitmat, data)


@jax.jit
def _encode_batch_jit(bitmat, data):
    """data (B, k, S) -> (B, r, S)."""
    b, k, s = data.shape
    cols = data.transpose(1, 0, 2).reshape(k, b * s)
    out = gf8.bitmatrix_matmul(bitmat, cols)
    r = out.shape[0]
    return out.reshape(r, b, s).transpose(1, 0, 2)


@functools.partial(jax.jit, static_argnums=(2,))
def _gather_encode_batch_jit(bitmat, chunks, src):
    """chunks (B, n, S) -> (B, r, S) using only the src rows.

    The row gather is INSIDE the jit so a decode is one device dispatch —
    an eager gather followed by the matmul costs a second round trip
    through the runtime per call, which dominates at small batch shapes."""
    data = chunks[:, list(src), :]
    b, k, s = data.shape
    cols = data.transpose(1, 0, 2).reshape(k, b * s)
    out = gf8.bitmatrix_matmul(bitmat, cols)
    r = out.shape[0]
    return out.reshape(r, b, s).transpose(1, 0, 2)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _gather_encode_batch_w_jit(bitmat, chunks, src, word_bytes: int):
    """Word-generalized variant of _gather_encode_batch_jit."""
    data = chunks[:, list(src), :]
    b, k, s = data.shape
    cols = data.transpose(1, 0, 2).reshape(k, b * s)
    out = gfw.bitmatrix_matmul_w(bitmat, cols, word_bytes)
    r = out.shape[0]
    return out.reshape(r, b, s).transpose(1, 0, 2)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _pkt_batch_apply(lane_mat, data, w: int, p: int, src=None):
    """Packet-interleaved batch apply for bit-matrix codes.

    data (B, c, S) where every chunk is super-blocks of w*p bytes (packet
    row t of super-block s holds bit-plane t); lane_mat is the
    byte-lane-expanded (8rw, 8cw) selection matrix.  One MXU matmul for
    the WHOLE batch (jerasure_schedule_encode semantics over all stripes
    at once, reference ErasureCodeJerasure.cc:260).  ``src`` (static)
    optionally selects source rows inside the jit."""
    if src is not None:
        data = data[:, list(src), :]
    b, c, s = data.shape
    ns = s // (w * p)
    rows = (
        data.reshape(b, c, ns, w, p)
        .transpose(1, 3, 0, 2, 4)
        .reshape(c * w, b * ns * p)
    )
    out = gf8.bitmatrix_matmul(lane_mat, rows)          # (r*w, b*ns*p)
    r = out.shape[0] // w
    return (
        out.reshape(r, w, b, ns, p)
        .transpose(2, 0, 3, 1, 4)
        .reshape(b, r, s)
    )


class _DeviceMatrixEngine:
    """Shared encode/decode engine over a (k+m, k) generator matrix.

    w=8 uses the table-driven gf8 host helpers; w in {16, 32} uses the
    scalar gfw field (matrices are k x m WORDS — still tiny) and the
    word-generalized device matmul.  Either way the data path is ONE MXU
    GF(2) matmul."""

    def __init__(self, k: int, m: int, coding: np.ndarray, w: int = 8):
        self.k = k
        self.m = m
        self.w = w
        self.word_bytes = w // 8
        if w == 8:
            self.coding = coding.astype(np.uint8)
            self._enc_bitmat = jnp.asarray(gf8.expand_bitmatrix(self.coding))
        else:
            self.coding = coding.astype(np.uint64)
            self._enc_bitmat = jnp.asarray(
                gfw.expand_bitmatrix_w(self.coding, w))
        self.generator = matrices.generator_matrix(self.coding)
        self._decode_cache = DecodeTableCache()

    def _apply(self, bitmat, data: np.ndarray) -> np.ndarray:
        _record_kernel("ec_matmul", bitmat.shape, data.size)
        if self.w == 8:
            return np.asarray(_encode_cols(bitmat, jnp.asarray(data)))
        return np.asarray(
            gfw.bitmatrix_matmul_w(bitmat, jnp.asarray(data), self.word_bytes))

    def _apply_batch(self, bitmat, data):
        _record_kernel("ec_matmul", bitmat.shape,
                       int(np.prod(data.shape)))
        if self.w == 8:
            return _encode_batch_jit(bitmat, jnp.asarray(data))
        return gfw.encode_batch_w(bitmat, jnp.asarray(data), self.word_bytes)

    def encode_parity(self, data: np.ndarray) -> np.ndarray:
        """(k, S) -> (m, S) on device."""
        return self._apply(self._enc_bitmat, data)

    def encode_parity_batch(self, data) -> jnp.ndarray:
        """(B, k, S) -> (B, m, S), stays on device."""
        return self._apply_batch(self._enc_bitmat, data)

    def decode_matrix(
        self, src_rows: Tuple[int, ...], out_rows: Tuple[int, ...]
    ) -> np.ndarray:
        """Recovery matrix R with chunk[out] = R @ chunk[src].

        Same construction as ISA-L decode (reference ErasureCodeIsa.cc:274-305):
        invert the k x k survivor submatrix of the generator; erased data rows
        come straight from the inverse, erased parity rows compose the coding
        row with the inverse.
        """
        sub = self.generator[list(src_rows)]
        if self.w == 8:
            inv = gf8.gf_invert_matrix(sub)
            rows = []
            for e in out_rows:
                if e < self.k:
                    rows.append(inv[e])
                else:
                    rows.append(gf8.gf_matmul_ref(
                        self.coding[e - self.k][None, :], inv)[0])
            return np.stack(rows).astype(np.uint8)
        gf = gfw.field(self.w)
        inv = gfw.gfw_invert_matrix(sub, self.w)
        rows = []
        for e in out_rows:
            if e < self.k:
                rows.append(inv[e])
            else:
                crow = [int(x) for x in self.coding[e - self.k]]
                row = []
                for c in range(self.k):
                    acc = 0
                    for t in range(self.k):
                        acc ^= gf.mul(crow[t], int(inv[t][c]))
                    row.append(acc)
                rows.append(np.array(row, dtype=np.uint64))
        return np.stack(rows)

    def decode_bitmat(self, src_rows: Tuple[int, ...], out_rows: Tuple[int, ...]):
        key = (src_rows, out_rows)
        bitmat = self._decode_cache.get(key)
        if bitmat is None:
            rmat = self.decode_matrix(src_rows, out_rows)
            if self.w == 8:
                bitmat = jnp.asarray(gf8.expand_bitmatrix(rmat))
            else:
                bitmat = jnp.asarray(gfw.expand_bitmatrix_w(rmat, self.w))
            self._decode_cache.put(key, bitmat)
        return bitmat

    def reconstruct(
        self, src_rows: Tuple[int, ...], out_rows: Tuple[int, ...], data: np.ndarray
    ) -> np.ndarray:
        """data (k, S) from src_rows -> (len(out_rows), S)."""
        bitmat = self.decode_bitmat(src_rows, out_rows)
        return self._apply(bitmat, data)

    def reconstruct_batch(
        self, src_rows: Tuple[int, ...], out_rows: Tuple[int, ...], data
    ):
        """(B, k, S) from src_rows -> (B, len(out_rows), S), on device."""
        bitmat = self.decode_bitmat(src_rows, out_rows)
        return self._apply_batch(bitmat, data)

    def reconstruct_batch_from(
        self, src_rows: Tuple[int, ...], out_rows: Tuple[int, ...], chunks
    ):
        """Like reconstruct_batch but takes the FULL (B, n, S) chunk array
        and gathers src rows inside one jitted dispatch."""
        bitmat = self.decode_bitmat(src_rows, out_rows)
        chunks = jnp.asarray(chunks)
        _record_kernel("ec_matmul", bitmat.shape,
                       int(np.prod(chunks.shape)))
        if self.w == 8:
            return _gather_encode_batch_jit(bitmat, chunks, tuple(src_rows))
        return _gather_encode_batch_w_jit(
            bitmat, chunks, tuple(src_rows), self.word_bytes)


def matrix_engine(codec):
    """The codec's engine where it is a GF(2^8) matrix code that the
    plane entry points of ``ec/stripe.py`` can drive: ``coding`` ((m, k)
    bytes), ``_enc_bitmat`` (its (8m, 8k) bit-matrix), ``decode_matrix(
    src, want)`` (chunk[want] = R @ chunk[src]; raises where ``src``
    cannot produce ``want``) and ``decode_bitmat``.  None for every
    other code: a wider field, a bit-matrix with no byte matrix behind
    it (the liberation family), no matrix at all.  The ONE place that
    asks; whoever needs to know calls this.  The answer is kept on the
    codec: a pool's codec is asked at every op.

    The same bit-matrix multiplies two chunk layouts, and
    ``engine_layout`` says which one the codec's chunks have: the
    engine's matrices are right for packed GF(2) rows of either, but a
    BYTEWISE product (the host GF engine on byte batches, the mesh
    engine, a flattened LRC stack) is right only for ``planar8``:
    ``bytewise_engine`` is the question those ask."""
    try:
        return codec._matrix_engine
    except AttributeError:
        pass
    eng = getattr(codec, "engine", None)
    if eng is None:
        return None         # none (yet): nothing to remember
    if getattr(eng, "w", 0) != 8 or \
            getattr(eng, "coding", None) is None or \
            not hasattr(eng, "decode_matrix"):
        eng = None
    codec._matrix_engine = eng
    return eng


def engine_layout(codec):
    """The at-rest serialization (``ec/planar_store.py``'s tag) of the
    packed GF(2) rows ``matrix_engine(codec)`` multiplies: ``planar8``
    for bytewise chunks, ``packet8.<packetsize>`` for the w = 8
    packet-interleaved bit-matrix codes (a chunk's w packets a
    super-block ARE packed rows); None where there is no such engine."""
    from ceph_tpu.ec import planar_store

    if matrix_engine(codec) is None:
        return None
    try:
        return codec._engine_layout     # asked at every op, as the engine
    except AttributeError:
        pass
    p = getattr(codec, "packetsize", None)
    codec._engine_layout = planar_store.LAYOUT_PLANAR if p is None \
        else planar_store.packet_layout(p)
    return codec._engine_layout


def bytewise_engine(codec):
    """``matrix_engine(codec)`` where the codec's chunks are the BYTEWISE
    product of its matrix, else None: for whoever multiplies bytes and
    not packed rows."""
    eng = matrix_engine(codec)
    return None if getattr(codec, "packetsize", None) is not None else eng


class _DeviceBitEngine:
    """Engine for NATIVE GF(2) bit-matrix codes (liberation family): the
    code is defined directly by an (m*w, k*w) 0/1 matrix with no byte
    matrix behind it.  Decode inverts the k*w x k*w survivor bit-matrix
    over GF(2) — the same solve jerasure performs on its bit-matrices."""

    def __init__(self, k: int, m: int, w: int, coding_bits: np.ndarray):
        self.k = k
        self.m = m
        self.w = w
        self.coding_bits = np.asarray(coding_bits, dtype=np.uint8)
        self.generator_bits = np.vstack(
            [np.eye(k * w, dtype=np.uint8), self.coding_bits])
        self._decode_cache = DecodeTableCache()

    def decode_bits(self, src: Tuple[int, ...],
                    out: Tuple[int, ...]) -> np.ndarray:
        key = (src, out)
        cached = self._decode_cache.get(key)
        if cached is not None:
            return cached
        k, w = self.k, self.w
        g = np.vstack([
            self.generator_bits[s * w:(s + 1) * w] for s in src])  # (kw, kw)
        inv = gfw.gf2_invert_matrix(g)
        rows = []
        for e in out:
            if e < k:
                rows.append(inv[e * w:(e + 1) * w])
            else:
                block = self.coding_bits[(e - k) * w:(e - k + 1) * w]
                rows.append((block.astype(np.int32) @ inv.astype(np.int32))
                            .astype(np.uint8) & 1)
        rmat = np.vstack(rows)
        self._decode_cache.put(key, rmat)
        return rmat


def _planar_rows_matmul(lane_bitmat, rows):
    """Byte-operand GF(2) matmul for packet-planar rows (the 8x expansion
    rides in the lane-expanded matrix): fused Pallas kernel on TPU
    backends, the XLA path elsewhere.  Bit-exact either way."""
    from ceph_tpu.ops import gf8_pallas

    _record_kernel("ec_matmul", lane_bitmat.shape,
                   int(np.prod(rows.shape)))
    if gf8_pallas.available():
        return gf8_pallas.bitmatrix_matmul(lane_bitmat, rows)
    return _encode_cols(lane_bitmat, rows)


class MatrixCodec(ErasureCode):
    """Bytewise GF(2^w) matrix code; subclasses supply the coding matrix."""

    def __init__(self):
        super().__init__()
        self.engine: _DeviceMatrixEngine = None  # set by prepare()

    def build_coding_matrix(self) -> np.ndarray:
        raise NotImplementedError

    def prepare(self) -> None:
        self.engine = _DeviceMatrixEngine(
            self.k, self.m, self.build_coding_matrix(), w=self.w)

    # -- bit-planar device layout (round 6 layout contract) -----------------
    #
    # Stripe batches stay in packed bit-planar form (ec/planar.py) across
    # encode -> parity -> decode -> RMW; each hop is ONE planar GF(2)
    # matmul (gf8.planar_matmul: K-stacked Pallas kernel on TPU), and the
    # byte layout exists only at the host boundary.

    def planar_supported(self, chunk_size: int) -> bool:
        from ceph_tpu.ec.planar import PlanarBatch

        return PlanarBatch.supported(chunk_size, self.w)

    def to_planar(self, batch) -> "PlanarBatch":
        """(B, k-or-n, S) byte batch -> device PlanarBatch (one convert)."""
        from ceph_tpu.ec.planar import PlanarBatch

        # the host->device copy of the batch + the ingest program
        ticktrace.device_calls(2)
        return PlanarBatch.from_batch(batch, w=self.w)

    def encode_planar(self, pb) -> "PlanarBatch":
        """PlanarBatch of the k data chunks -> PlanarBatch of m parity
        chunks.  No expansion, no pack: one matmul on packed planes."""
        from ceph_tpu.ops import gf8

        ticktrace.device_calls()        # the planar matmul program
        return pb.with_planes(
            gf8.planar_matmul(self.engine._enc_bitmat, pb.planes), self.m)

    def _planar_decode_plan(self, erasures, want):
        """(recovery bit-matrix, source chunk ids) for one erasure
        pattern; MDS codes take the first k available chunks (overridden
        by non-MDS families)."""
        avail = tuple(i for i in range(self.k + self.m)
                      if i not in erasures)
        src = avail[: self.k]
        return self.engine.decode_bitmat(src, tuple(want)), src

    def decode_planar(self, erasures, pb, want=None) -> "PlanarBatch":
        """Planar reconstruction: ``pb`` holds all n chunks (erased rows
        ignored); returns a PlanarBatch of ``want`` (default: erasures)."""
        from ceph_tpu.ec.planar import _select_chunk_rows
        from ceph_tpu.ops import gf8

        if want is None:
            want = tuple(erasures)
        bitmat, src = self._planar_decode_plan(tuple(erasures), tuple(want))
        src_planes = _select_chunk_rows(pb.planes, self.w, tuple(src))
        return pb.with_planes(gf8.planar_matmul(bitmat, src_planes),
                              len(want))

    # -- single-stripe paths (reference-API compatible) ---------------------

    def encode_chunks(self, chunks: Dict[int, np.ndarray]) -> None:
        data = np.stack([chunks[i] for i in range(self.k)])
        if data.shape[1] == 0:
            return
        parity = self.engine.encode_parity(data)
        for i in range(self.m):
            chunks[self.k + i][...] = parity[i]

    def decode_chunks(
        self,
        want_to_read: Set[int],
        chunks: Mapping[int, np.ndarray],
        decoded: Dict[int, np.ndarray],
    ) -> None:
        avail = sorted(chunks)
        if len(avail) < self.k:
            raise ECError(errno.EIO, "not enough chunks to decode")
        erased = tuple(i for i in range(self.k + self.m) if i not in chunks)
        src = tuple(avail[: self.k])
        data = np.stack([np.asarray(chunks[i], dtype=np.uint8) for i in src])
        out = self.engine.reconstruct(src, erased, data)
        for idx, e in enumerate(erased):
            decoded[e][...] = out[idx]

    # -- batched device paths ----------------------------------------------

    def encode_batch(self, data) -> np.ndarray:
        return self.engine.encode_parity_batch(data)

    def stripe_unit(self, default: int) -> int:
        # round to the planar packing quantum (w BYTES: one packed plane
        # byte spans 8 field words) so cluster stripe batches always
        # satisfy the bit-planar layout contract; this is a superset of
        # the old word-size (w/8) alignment
        q = self.w
        return ((default + q - 1) // q) * q

    def decode_batch(self, erasures: Tuple[int, ...], chunks,
                     want: Tuple[int, ...] = None) -> np.ndarray:
        """chunks: (B, k+m, S) with erased positions ignored (zeros ok).

        ``erasures`` lists EVERY unavailable chunk id (they are excluded
        from the source set); ``want`` selects which of them to rebuild
        (default: all).  Returns (B, len(want), S), device-resident.
        """
        if want is None:
            want = tuple(erasures)
        avail = tuple(i for i in range(self.k + self.m) if i not in erasures)
        src = avail[: self.k]
        return self.engine.reconstruct_batch_from(src, tuple(want), chunks)


class BitmatrixCodec(MatrixCodec):
    """Packet-interleaved bit-matrix code (jerasure cauchy + liberation
    families).

    Chunk layout follows jerasure_schedule_encode: a chunk is a sequence of
    super-blocks of w*packetsize bytes; packet-row t of a super-block holds
    bits "t" of the w-bit field elements.  Encode selects and XORs packets
    according to the (m*w, k*w) bit-matrix — on the MXU this is the same
    GF(2) matmul with the bit-matrix Kronecker-expanded over byte lanes.

    Subclasses supply the bit-matrices: the cauchy family derives them from
    a GF(2^8) byte matrix (expand_bitmatrix is a ring homomorphism, so byte
    inversion and bit inversion agree); the liberation family overrides
    ``_encode_bits``/``_decode_bits`` with native GF(2) constructions.
    """

    def __init__(self):
        super().__init__()
        self.packetsize = 2048

    # -- bit-matrix sources (overridden by native bit-matrix codes) ---------

    def _encode_bits(self) -> np.ndarray:
        """(m*w, k*w) GF(2) encode matrix."""
        if self.w == 8:
            return gf8.expand_bitmatrix(self.engine.coding)
        return gfw.expand_bitmatrix_w(self.engine.coding, self.w)

    def _decode_bits(self, src: Tuple[int, ...],
                     out: Tuple[int, ...]) -> np.ndarray:
        """(len(out)*w, k*w) GF(2) recovery matrix over the src chunks."""
        rows = self.engine.decode_matrix(src, out)
        if self.w == 8:
            return gf8.expand_bitmatrix(rows)
        return gfw.expand_bitmatrix_w(rows, self.w)

    # -- packet layout ------------------------------------------------------

    def stripe_unit(self, default: int) -> int:
        """The pool's chunk size by upstream's rule: what
        ``get_chunk_size`` gives for one stripe of ``default``-byte
        units (OSDMonitor::prepare_pool_stripe_width: stripe_width =
        k * get_chunk_size(stripe_unit * k)), with the technique's
        ``get_alignment`` inside it: 64 KiB at k = 4, w = 8, packetsize
        2048, where the 4 KiB default of a bytewise code stays 4 KiB.
        Always whole super-blocks (the alignment is k*w*packetsize
        words)."""
        unit = self.get_chunk_size(default * self.k)
        quantum = self.w * self.packetsize
        return ((unit + quantum - 1) // quantum) * quantum

    def _check_layout(self, s: int) -> None:
        if s % (self.w * self.packetsize):
            raise ECError(
                errno.EINVAL,
                f"chunk size {s} must be a multiple of w*packetsize = "
                f"{self.w * self.packetsize} (choose packetsize/profile "
                "accordingly, reference jerasure blocksize contract)")

    def _layout_rows(self, data: np.ndarray) -> np.ndarray:
        """(c, S) chunks -> (c*w, S/w) packet-row matrix."""
        c, s = data.shape
        w, p = self.w, self.packetsize
        self._check_layout(s)
        ns = s // (w * p)
        return (
            data.reshape(c, ns, w, p).transpose(0, 2, 1, 3).reshape(c * w, ns * p)
        )

    def _unlayout_rows(self, rows: np.ndarray, s: int) -> np.ndarray:
        c8, n = rows.shape
        w, p = self.w, self.packetsize
        c = c8 // w
        ns = n // p
        return rows.reshape(c, w, ns, p).transpose(0, 2, 1, 3).reshape(c, s)

    def _apply_bitmat(self, m01: np.ndarray, rows: np.ndarray) -> np.ndarray:
        lane = _lane_expand(m01.tobytes(), m01.shape)
        _record_kernel("ec_matmul", lane.shape, rows.size)
        return np.asarray(_encode_cols(lane, jnp.asarray(rows)))

    # -- single-stripe paths ------------------------------------------------

    def encode_chunks(self, chunks: Dict[int, np.ndarray]) -> None:
        data = np.stack([chunks[i] for i in range(self.k)])
        rows = self._layout_rows(data)
        prows = self._apply_bitmat(self._encode_bits(), rows)
        parity = self._unlayout_rows(prows, data.shape[1])
        for i in range(self.m):
            chunks[self.k + i][...] = parity[i]

    def decode_chunks(
        self,
        want_to_read: Set[int],
        chunks: Mapping[int, np.ndarray],
        decoded: Dict[int, np.ndarray],
    ) -> None:
        avail = sorted(chunks)
        if len(avail) < self.k:
            raise ECError(errno.EIO, "not enough chunks to decode")
        erased = tuple(i for i in range(self.k + self.m) if i not in chunks)
        src = tuple(avail[: self.k])
        data = np.stack([np.asarray(chunks[i], dtype=np.uint8) for i in src])
        rows = self._layout_rows(data)
        out_rows = self._apply_bitmat(self._decode_bits(src, erased), rows)
        out = self._unlayout_rows(out_rows, data.shape[1])
        for idx, e in enumerate(erased):
            decoded[e][...] = out[idx]

    # -- batched device paths (packet-aware, overriding the bytewise
    #    MatrixCodec versions so batch and single-stripe bytes agree) -------

    def encode_batch(self, data) -> np.ndarray:
        data = jnp.asarray(data)
        self._check_layout(data.shape[2])
        m01 = self._encode_bits()
        lane = _lane_expand(m01.tobytes(), m01.shape)
        _record_kernel("ec_matmul", lane.shape,
                       int(np.prod(data.shape)))
        return _pkt_batch_apply(lane, data, self.w, self.packetsize)

    def decode_batch(self, erasures: Tuple[int, ...], chunks,
                     want: Tuple[int, ...] = None) -> np.ndarray:
        if want is None:
            want = tuple(erasures)
        avail = tuple(i for i in range(self.k + self.m) if i not in erasures)
        src = avail[: self.k]
        chunks = jnp.asarray(chunks)
        self._check_layout(chunks.shape[2])
        m01 = self._decode_bits(src, tuple(want))
        lane = _lane_expand(m01.tobytes(), m01.shape)
        _record_kernel("ec_matmul", lane.shape,
                       int(np.prod(chunks.shape)))
        return _pkt_batch_apply(lane, chunks, self.w, self.packetsize, src)

    # -- packet-planar layout (round 6) --------------------------------------
    #
    # Packet-interleaved chunks are ALREADY bit-planar: jerasure's w packets
    # of p bytes per super-block are packed bit-planes of the w-bit symbols.
    # The planar form is therefore the packet-row matrix (c*w, B*ns*p) of
    # raw bytes: no second-level packing conversion on top, and at w = 8
    # the multiply is ``gf8.planar_matmul`` on those rows as they are.

    def planar_supported(self, chunk_size: int) -> bool:
        from ceph_tpu.ec.planar import PlanarBatch

        return PlanarBatch.supported(chunk_size, self.w, "packet",
                                     self.packetsize)

    def to_planar(self, batch):
        from ceph_tpu.ec.planar import PlanarBatch

        ticktrace.device_calls(2)       # as MatrixCodec.to_planar
        batch = jnp.asarray(batch)
        self._check_layout(int(batch.shape[2]))
        return PlanarBatch.from_batch(batch, w=self.w, layout="packet",
                                      packetsize=self.packetsize)

    def _rows_matmul(self, m01, rows):
        """The (r*w, c*w) GF(2) matrix times packet rows.  At w = 8 with
        a byte matrix behind it (the cauchy techniques: ``matrix_engine``)
        that is the product's own planar matmul: packet rows are packed
        GF(2) rows.  Wider fields and the liberation family keep the
        byte-operand kernel and its lane-expanded matrix."""
        if matrix_engine(self) is not None:
            return gf8.planar_matmul(m01, rows)
        m01 = np.asarray(m01)
        return _planar_rows_matmul(_lane_expand(m01.tobytes(), m01.shape),
                                   rows)

    def encode_planar(self, pb):
        eng = matrix_engine(self)
        m01 = self._encode_bits() if eng is None else eng._enc_bitmat
        ticktrace.device_calls()        # the packet-rows matmul program
        return pb.with_planes(self._rows_matmul(m01, pb.planes), self.m)

    def decode_planar(self, erasures, pb, want=None):
        from ceph_tpu.ec.planar import _select_chunk_rows

        if want is None:
            want = tuple(erasures)
        avail = tuple(i for i in range(self.k + self.m) if i not in erasures)
        src = avail[: self.k]
        eng = matrix_engine(self)
        m01 = self._decode_bits(src, tuple(want)) if eng is None \
            else eng.decode_bitmat(src, tuple(want))
        src_rows = _select_chunk_rows(pb.planes, self.w, src)
        return pb.with_planes(self._rows_matmul(m01, src_rows), len(want))
