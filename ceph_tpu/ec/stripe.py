"""EC stripe tessellation: logical<->chunk offset math + batched codecs.

Behavioral mirror of ECUtil::stripe_info_t (reference src/osd/ECUtil.h:31-84):
an EC object is a sequence of stripes, each stripe_width = k * stripe_unit
logical bytes wide, cut into k data chunks of stripe_unit bytes; shard s of
the object is the concatenation of that shard's chunk from every stripe.

TPU-first design: the stripe axis is the batch axis.  Encoding an object is
ONE device dispatch over (nstripes, k, unit); reading or recovering a range
is one dispatch over the touched stripes.  This is the "long sequence"
tessellation SURVEY §5 maps onto the MXU — where the reference loops
per-stripe through jerasure_matrix_encode, we hand XLA the whole batch.

Batch shapes are bucketed to powers of two so repeated object sizes reuse
compiled executables instead of triggering per-size recompiles.
"""

from __future__ import annotations

import logging
import threading
from typing import Dict, List, Mapping, Optional, Tuple

import numpy as np

from ceph_tpu.ec.interface import ECError


class StripeInfo:
    """stripe_info_t analog: all offset arithmetic for a (k, stripe_unit)
    layout (reference ECUtil.h:31-84)."""

    def __init__(self, k: int, stripe_unit: int):
        if stripe_unit <= 0 or k <= 0:
            raise ValueError("k and stripe_unit must be positive")
        self.k = k
        self.chunk_size = stripe_unit
        self.stripe_width = k * stripe_unit

    def logical_offset_is_stripe_aligned(self, logical: int) -> bool:
        return logical % self.stripe_width == 0

    def logical_to_prev_chunk_offset(self, offset: int) -> int:
        return (offset // self.stripe_width) * self.chunk_size

    def logical_to_next_chunk_offset(self, offset: int) -> int:
        return ((offset + self.stripe_width - 1) // self.stripe_width) \
            * self.chunk_size

    def logical_to_prev_stripe_offset(self, offset: int) -> int:
        return offset - (offset % self.stripe_width)

    def logical_to_next_stripe_offset(self, offset: int) -> int:
        rem = offset % self.stripe_width
        return offset - rem + self.stripe_width if rem else offset

    def aligned_logical_offset_to_chunk_offset(self, offset: int) -> int:
        assert offset % self.stripe_width == 0
        return (offset // self.stripe_width) * self.chunk_size

    def aligned_chunk_offset_to_logical_offset(self, offset: int) -> int:
        assert offset % self.chunk_size == 0
        return (offset // self.chunk_size) * self.stripe_width

    def offset_len_to_stripe_bounds(self, offset: int, length: int) -> Tuple[int, int]:
        """(stripe-aligned offset, stripe-aligned length) covering the range
        (reference offset_len_to_stripe_bounds)."""
        off = self.logical_to_prev_stripe_offset(offset)
        ln = self.logical_to_next_stripe_offset((offset - off) + length)
        return off, ln

    def object_stripes(self, logical_size: int) -> int:
        return (logical_size + self.stripe_width - 1) // self.stripe_width \
            if logical_size else 0

    def shard_size(self, logical_size: int) -> int:
        return self.object_stripes(logical_size) * self.chunk_size


def _bucket(n: int) -> int:
    """Round a stripe count up to a power of two: bounded compile count."""
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def _planar_ok(codec, unit: int) -> bool:
    """Does this codec carry the round-6 bit-planar layout contract for
    this stripe unit?  (Mesh adapters and odd geometries fall back to the
    byte batch path — same math, just without the layout residency.)"""
    sup = getattr(codec, "planar_supported", None)
    return bool(sup and sup(unit))


def encode_stripes(codec, sinfo: StripeInfo, data: bytes) -> np.ndarray:
    """Encode a stripe-aligned-or-padded byte range in one device dispatch.

    Returns (k+m, nstripes * unit) uint8: shard rows, chunk-per-stripe
    concatenated.  ``data`` is zero-padded to the next stripe boundary.
    The stripe batch rides the bit-planar device layout (ec/planar.py):
    ONE conversion in, one parity conversion out at the host boundary.
    """
    k = sinfo.k
    unit = sinfo.chunk_size
    n = codec.get_chunk_count()
    nstripes = sinfo.object_stripes(len(data))
    if nstripes == 0:
        return np.zeros((n, 0), dtype=np.uint8)
    padded = nstripes * sinfo.stripe_width
    buf = np.zeros(padded, dtype=np.uint8)
    buf[: len(data)] = np.frombuffer(data, dtype=np.uint8)
    batch = buf.reshape(nstripes, k, unit)
    bb = _bucket(nstripes)
    if bb != nstripes:
        batch = np.concatenate(
            [batch, np.zeros((bb - nstripes, k, unit), dtype=np.uint8)])
    # padding-waste telemetry: stripe-boundary zero fill + the power-of-2
    # batch bucket rows are bytes the device encodes but nobody stores
    from ceph_tpu.utils.perf import KERNELS

    KERNELS.inc("ec_stripe_pad_bytes",
                (padded - len(data)) + (bb - nstripes) * k * unit)
    if _planar_ok(codec, unit):
        pb = codec.to_planar(batch)
        parity = np.asarray(codec.encode_planar(pb).to_batch())[:nstripes]
    else:
        parity = np.asarray(codec.encode_batch(batch))[:nstripes]
    full = np.concatenate([batch[:nstripes], parity], axis=1)  # (ns, n, unit)
    return full.transpose(1, 0, 2).reshape(n, nstripes * unit)


def _host_engine_ok(codec) -> bool:
    """Should the coalesced encode use the vectorized host GF engine?

    On CPU jax backends XLA's emulation of the packed GF(2) bit-matmul
    (built for the MXU) runs ~100x below memory bandwidth, so the
    coalesced write path computes parity with table-driven numpy GF
    arithmetic instead — bit-exact by construction (same field, same
    coding matrix; the cross-engine equality is a tier-1 test).  Device
    backends keep the planar fused dispatch (BENCH_NOTES round 11)."""
    import jax

    if jax.default_backend() != "cpu":
        return False
    from ceph_tpu.ec.codec import matrix_engine

    return matrix_engine(codec) is not None


def _host_bytes_ok(codec) -> bool:
    """The same for the entry points that hand the host engine BYTE
    batches: its product is bytewise, which a packet-interleaved chunk
    is not (``codec.bytewise_engine``); the plane entry points multiply
    packed rows and take either layout."""
    from ceph_tpu.ec.codec import bytewise_engine

    return _host_engine_ok(codec) and bytewise_engine(codec) is not None


def _gf_apply_host(mat: np.ndarray, batch: np.ndarray) -> np.ndarray:
    """(B, k, S) x (m, k) GF(2^8) matrix -> (B, m, S) via table-driven
    numpy: coefficient-1 terms are pure XOR (the whole of RS m=1),
    others one 256-entry LUT gather per term.  Shared by the coalesced
    host ENCODE (mat = the coding matrix) and the round-16 host DECODE
    (mat = the inverted-survivor recovery matrix) — same field, same
    tables, so either direction is bit-exact with the device path by
    construction."""
    from ceph_tpu.ops.gf8 import GF_MUL
    from ceph_tpu.utils.perf import KERNELS

    m, k = mat.shape
    b, _k, s = batch.shape
    KERNELS.inc("ec_host_matmul_calls")
    KERNELS.inc("ec_host_matmul_bytes", b * k * s)
    out = np.empty((b, m, s), dtype=np.uint8)
    for j in range(m):
        acc = None
        for i in range(k):
            c = int(mat[j, i])
            if c == 0:
                continue
            term = batch[:, i, :] if c == 1 else GF_MUL[c][batch[:, i, :]]
            if acc is None:
                acc = term.copy() if c == 1 else term
            else:
                np.bitwise_xor(acc, term, out=acc)
        out[:, j, :] = acc if acc is not None else 0
    return out


def _encode_parity_host(coding: np.ndarray, batch: np.ndarray) -> np.ndarray:
    """(B, k, S) -> (B, m, S) parity on the host GF engine."""
    return _gf_apply_host(coding, batch)


def encode_stripes_multi(codec, sinfo: StripeInfo, datas,
                         want_crcs=None):
    """Coalesced encode: N ops' stripe ranges in ONE device round trip.

    The tick-level batch of the round-11 data plane: every op's stripe
    batch concatenates along the batch axis, the combined batch pays one
    planar conversion + one fused encode dispatch, and shard rows of
    full-shard writes checksum in one crc32c batch.  Bit-exact with
    per-op ``encode_stripes`` by construction — the code is stripe-local
    (parity of stripe j never depends on other batch rows), so batch
    composition cannot change any op's shards.

    Returns ``[(shards, crcs), ...]`` aligned with ``datas``: ``shards``
    is the per-op (k+m, nstripes*unit) uint8 matrix ``encode_stripes``
    would return; ``crcs`` is the per-shard-row ``ceph_crc32c(~0, row)``
    list for ops whose ``want_crcs`` flag is set (full-shard rewrites),
    else None.
    """
    from ceph_tpu.ops.crc32c import crc32c_rows
    from ceph_tpu.utils.perf import KERNELS

    k = sinfo.k
    unit = sinfo.chunk_size
    n = codec.get_chunk_count()
    if want_crcs is None:
        want_crcs = [False] * len(datas)
    counts = [sinfo.object_stripes(len(d)) for d in datas]
    total = sum(counts)
    out = [None] * len(datas)
    if total == 0:
        for i in range(len(datas)):
            shards = np.zeros((n, 0), dtype=np.uint8)
            out[i] = (shards,
                      crc32c_rows(shards) if want_crcs[i] else None)
        return out
    KERNELS.inc("ec_coalesced_ticks")
    KERNELS.inc("ec_coalesced_ops", len(datas))
    batch = np.zeros((total, k, unit), dtype=np.uint8)
    pad = 0
    ofs = 0
    for d, ns in zip(datas, counts):
        if ns == 0:
            continue
        flat = batch[ofs:ofs + ns].reshape(ns * k * unit)
        flat[: len(d)] = np.frombuffer(d, dtype=np.uint8)
        pad += ns * sinfo.stripe_width - len(d)
        ofs += ns
    if _host_bytes_ok(codec):
        # CPU backend: no layout conversion, no bucket padding — the
        # host GF engine is shape-agnostic and bandwidth-bound
        KERNELS.inc("ec_stripe_pad_bytes", pad)
        parity = _encode_parity_host(codec.engine.coding, batch)
    else:
        bb = _bucket(total)
        if bb != total:
            batch = np.concatenate(
                [batch, np.zeros((bb - total, k, unit), dtype=np.uint8)])
        KERNELS.inc("ec_stripe_pad_bytes",
                    pad + (bb - total) * k * unit)
        if _planar_ok(codec, unit):
            pb = codec.to_planar(batch)
            parity = np.asarray(
                codec.encode_planar(pb).to_batch())[:total]
        else:
            parity = np.asarray(codec.encode_batch(batch))[:total]
    # split parity back per op and assemble each op's shard rows
    crc_rows = []           # (out-index, shard row matrix) for one batch
    ofs = 0
    for i, ns in enumerate(counts):
        full = np.concatenate(
            [batch[ofs:ofs + ns], parity[ofs:ofs + ns]], axis=1)
        shards = full.transpose(1, 0, 2).reshape(n, ns * unit)
        ofs += ns
        out[i] = (shards, None)
        if want_crcs[i]:
            crc_rows.append((i, shards))
    # one crc32c batch per shard length group (a tick's ops usually
    # share object size; mixed sizes split into one dispatch per size)
    by_len = {}
    for i, shards in crc_rows:
        by_len.setdefault(shards.shape[1], []).append((i, shards))
    for _length, group in by_len.items():
        stacked = np.concatenate([s for _i, s in group], axis=0)
        crcs = crc32c_rows(stacked)
        for gi, (i, shards) in enumerate(group):
            out[i] = (out[i][0], crcs[gi * n:(gi + 1) * n])
    return out


def decode_stripes(
    codec,
    sinfo: StripeInfo,
    shards: Mapping[int, np.ndarray],
    logical_size: int,
) -> bytes:
    """Rebuild the logical bytes from >= k shard rows in one dispatch.

    ``shards`` maps shard id -> (nstripes * unit) bytes.  Missing data
    shards are reconstructed batched (one erasure pattern for the whole
    object, reference ECBackend reply aggregation + ECUtil::decode).
    """
    k = sinfo.k
    unit = sinfo.chunk_size
    n = codec.get_chunk_count()
    nstripes = sinfo.object_stripes(logical_size)
    if nstripes == 0:
        return b""
    shard_len = nstripes * unit
    have = sorted(shards)
    data_rows: Dict[int, np.ndarray] = {}
    for s in have:
        arr = np.asarray(shards[s], dtype=np.uint8)
        if arr.shape[0] != shard_len:
            raise ValueError(
                f"shard {s}: {arr.shape[0]} bytes, want {shard_len}")
        if s < k:
            data_rows[s] = arr
    missing = [s for s in range(k) if s not in data_rows]
    if missing:
        if len(have) < k:
            raise ValueError(f"only {len(have)} of {k} shards")
        full = np.zeros((nstripes, n, unit), dtype=np.uint8)
        for s in have:
            full[:, s, :] = np.asarray(
                shards[s], dtype=np.uint8).reshape(nstripes, unit)
        # erasures = every absent shard (absent parity must never be used
        # as a decode source); want = only the missing DATA shards, since
        # this function returns logical bytes — absent parity (possibly
        # simply not requested) is not reconstructed, and non-MDS codecs
        # (shec) don't search for a needlessly hard recovery plan.
        erasures = tuple(s for s in range(n) if s not in shards)
        want = tuple(s for s in range(k) if s not in shards)
        bb = _bucket(nstripes)
        if bb != nstripes:
            full = np.concatenate(
                [full, np.zeros((bb - nstripes, n, unit), dtype=np.uint8)])
        if _planar_ok(codec, unit):
            pb = codec.to_planar(full)
            recovered = np.asarray(
                codec.decode_planar(erasures, pb, want=want)
                .to_batch())[:nstripes]
        else:
            recovered = np.asarray(
                codec.decode_batch(erasures, full, want=want))[:nstripes]
        for idx, e in enumerate(want):
            data_rows[e] = recovered[:, idx, :].reshape(shard_len)
    stacked = np.stack([data_rows[s].reshape(nstripes, unit)
                        for s in range(k)], axis=1)
    return stacked.reshape(nstripes * sinfo.stripe_width)[
        :logical_size].tobytes()


def reencode_stripes(
    codec,
    sinfo: StripeInfo,
    shards: Mapping[int, np.ndarray],
    logical_size: int,
) -> np.ndarray:
    """Recovery fast path: rebuild ALL shard rows from >= k shard rows
    WITHOUT leaving the planar domain between decode and re-encode.

    The batch is converted to bit-planar once, missing data chunks are
    reconstructed planar, parity is re-derived planar, and the result is
    converted back once — so a recovery op transposes the stripe batch
    exactly once in each direction (the ECBackend::run_recovery_op analog
    used to round-trip through logical bytes, paying the layout
    conversion twice more).  Returns (k+m, nstripes * unit) uint8.
    """
    k = sinfo.k
    unit = sinfo.chunk_size
    n = codec.get_chunk_count()
    nstripes = sinfo.object_stripes(logical_size)
    if nstripes == 0:
        return np.zeros((n, 0), dtype=np.uint8)
    if len(shards) < k:
        raise ValueError(f"only {len(shards)} of {k} shards")
    if not _planar_ok(codec, unit):
        data = decode_stripes(codec, sinfo, shards, logical_size)
        return encode_stripes(codec, sinfo, data)
    shard_len = nstripes * unit
    full = np.zeros((nstripes, n, unit), dtype=np.uint8)
    for s in shards:
        arr = np.asarray(shards[s], dtype=np.uint8)
        if arr.shape[0] != shard_len:
            raise ValueError(
                f"shard {s}: {arr.shape[0]} bytes, want {shard_len}")
        full[:, s, :] = arr.reshape(nstripes, unit)
    bb = _bucket(nstripes)
    if bb != nstripes:
        full = np.concatenate(
            [full, np.zeros((bb - nstripes, n, unit), dtype=np.uint8)])
    pb = codec.to_planar(full)
    missing_data = tuple(s for s in range(k) if s not in shards)
    if missing_data:
        erasures = tuple(s for s in range(n) if s not in shards)
        dec = codec.decode_planar(erasures, pb, want=missing_data)
        combined = pb.concat(dec)
        order = tuple(n + missing_data.index(j) if j in missing_data else j
                      for j in range(k))
        data_pb = combined.select(order)
    else:
        data_pb = pb.select(tuple(range(k)))
    parity_pb = codec.encode_planar(data_pb)
    out = np.asarray(data_pb.concat(parity_pb).to_batch())[:nstripes]
    return out.transpose(1, 0, 2).reshape(n, shard_len)


def _assemble_logical(data_rows: Dict[int, np.ndarray], k: int,
                      nstripes: int, unit: int,
                      logical_size: int) -> bytes:
    """Interleave k data shard rows back into logical bytes."""
    stacked = np.stack([data_rows[s].reshape(nstripes, unit)
                        for s in range(k)], axis=1)
    return stacked.reshape(nstripes * k * unit)[:logical_size].tobytes()


def assemble_data_stripes(sinfo: StripeInfo, shards: Mapping[int, object],
                          logical_size: int) -> bytes:
    """The no-erasure decode: every data shard present, so the logical
    bytes are a pure host interleave (zero device work) — the fast path
    ``decode_stripes``/``decode_stripes_multi`` take internally, exposed
    for the read coalescer's non-degraded short circuit."""
    k = sinfo.k
    unit = sinfo.chunk_size
    nstripes = sinfo.object_stripes(logical_size)
    if nstripes == 0:
        return b""
    shard_len = nstripes * unit
    rows: Dict[int, np.ndarray] = {}
    for s in range(k):
        arr = np.asarray(shards[s], dtype=np.uint8)
        if arr.shape[0] != shard_len:
            raise ValueError(
                f"shard {s}: {arr.shape[0]} bytes, want {shard_len}")
        rows[s] = arr
    return _assemble_logical(rows, k, nstripes, unit, logical_size)


def _decode_src(codec, want, erasures) -> Tuple[int, ...]:
    """The chunks a decode of ``want`` multiplies, of the chunks that
    came (all but ``erasures``): the codec's choice
    (``decode_sources``), and for a code with no opinion, for which any
    k will do, the first k.  ECError where the codec says those that
    came cannot produce ``want``: refused here, before any decode, and
    counted."""
    avail = [s for s in range(codec.get_chunk_count())
             if s not in erasures]
    try:
        chosen = codec.decode_sources(set(want), avail)
    except ECError:
        from ceph_tpu.utils.perf import KERNELS

        KERNELS.inc("ec_decode_sources_refused")
        raise
    if chosen is None:
        return tuple(avail[:codec.get_data_chunk_count()])
    return tuple(sorted(chosen))


def _host_decode_matrix(codec, src: Tuple[int, ...],
                        want: Tuple[int, ...]) -> Optional[np.ndarray]:
    """GF(2^8) recovery matrix for the host engine (chunk[want] =
    R @ chunk[src]), or None when this codec is no bytewise matrix code
    or its engine cannot solve the pattern from ``src`` (non-MDS plans
    like SHEC fall back to the codec's own decode machinery)."""
    from ceph_tpu.ec.codec import matrix_engine

    eng = matrix_engine(codec)
    if eng is None:
        return None
    try:
        return np.asarray(eng.decode_matrix(tuple(src), tuple(want)),
                          dtype=np.uint8)
    except Exception:
        return None


def decode_stripes_multi(codec, sinfo: StripeInfo, reqs):
    """Coalesced decode: N read gathers' shard maps in ONE device round
    trip per distinct erasure pattern — the round-16 decode twin of
    ``encode_stripes_multi`` (ROADMAP item 1).

    ``reqs`` is a sequence of ``(shards, logical_size)`` pairs shaped
    exactly like ``decode_stripes`` arguments; returns the list of
    logical byte strings, aligned with ``reqs``.  Ops with every data
    shard present never touch the device (pure host interleave); ops
    missing data shards group by their (erasures, want) pattern and
    each group pays one layout conversion + one fused decode dispatch
    for its whole concatenated stripe batch.  Engine per backend like
    the write side: CPU jax backends reconstruct through the inverted
    survivor submatrix on the table-driven host GF engine (bit-exact —
    same field, same generator), device backends keep the planar fused
    decode.  Bit-exact with per-op ``decode_stripes`` by construction:
    the code is stripe-local, so batch composition cannot change any
    op's bytes (the tier-1 read-exactness gate compares them).
    """
    from ceph_tpu.utils.perf import KERNELS

    k = sinfo.k
    unit = sinfo.chunk_size
    n = codec.get_chunk_count()
    out: List = [None] * len(reqs)
    groups: Dict[Tuple, List] = {}
    for i, (shards, logical_size) in enumerate(reqs):
        nstripes = sinfo.object_stripes(logical_size)
        if nstripes == 0:
            out[i] = b""
            continue
        shard_len = nstripes * unit
        arrs: Dict[int, np.ndarray] = {}
        data_rows: Dict[int, np.ndarray] = {}
        for s in sorted(shards):
            arr = np.asarray(shards[s], dtype=np.uint8)
            if arr.shape[0] != shard_len:
                raise ValueError(
                    f"shard {s}: {arr.shape[0]} bytes, want {shard_len}")
            arrs[s] = arr
            if s < k:
                data_rows[s] = arr
        missing = tuple(s for s in range(k) if s not in data_rows)
        if not missing:
            out[i] = _assemble_logical(data_rows, k, nstripes, unit,
                                       logical_size)
            continue
        if len(arrs) < k:
            raise ValueError(f"only {len(arrs)} of {k} shards")
        erasures = tuple(s for s in range(n) if s not in arrs)
        groups.setdefault((erasures, missing), []).append(
            (i, arrs, data_rows, nstripes, logical_size))
    if not groups:
        return out
    KERNELS.inc("ec_coalesced_read_ticks")
    KERNELS.inc("ec_coalesced_reads",
                sum(len(g) for g in groups.values()))
    host = _host_bytes_ok(codec)
    for (erasures, want), items in groups.items():
        total = sum(ns for _i, _a, _d, ns, _ls in items)
        full = np.zeros((total, n, unit), dtype=np.uint8)
        ofs = 0
        for _i, arrs, _d, ns, _ls in items:
            for s, arr in arrs.items():
                full[ofs:ofs + ns, s, :] = arr.reshape(ns, unit)
            ofs += ns
        recovered = None
        if host:
            src = _decode_src(codec, want, erasures)
            rmat = _host_decode_matrix(codec, src, want)
            if rmat is not None:
                recovered = _gf_apply_host(rmat, full[:, list(src), :])
        if recovered is None:
            bb = _bucket(total)
            batch = full if bb == total else np.concatenate(
                [full, np.zeros((bb - total, n, unit), dtype=np.uint8)])
            if _planar_ok(codec, unit):
                pb = codec.to_planar(batch)
                recovered = np.asarray(
                    codec.decode_planar(erasures, pb, want=want)
                    .to_batch())[:total]
            else:
                recovered = np.asarray(
                    codec.decode_batch(erasures, batch,
                                       want=want))[:total]
        ofs = 0
        for i, _arrs, data_rows, ns, logical_size in items:
            for idx, e in enumerate(want):
                data_rows[e] = recovered[ofs:ofs + ns, idx, :] \
                    .reshape(ns * unit)
            ofs += ns
            out[i] = _assemble_logical(data_rows, k, ns, unit,
                                       logical_size)
    return out


def reencode_stripes_multi(codec, sinfo: StripeInfo, reqs):
    """Coalesced recovery rebuild: N objects' full shard-row matrices in
    one device round trip per distinct missing-data pattern — the multi
    twin of ``reencode_stripes``, sharing its contract (returns the
    per-op (k+m, nstripes*unit) uint8 matrices, aligned with ``reqs``).

    CPU backends reconstruct missing data rows through the inverted
    survivor submatrix and re-derive parity with the coding matrix —
    both table-driven host GF passes, no layout conversion at all.
    Device backends ride the planar grouped round trip (one to_planar,
    one decode + one encode dispatch per pattern group); codecs without
    the planar contract fall back to coalesced decode + coalesced
    encode, which still batches the whole tick.
    """
    from ceph_tpu.utils.perf import KERNELS

    k = sinfo.k
    unit = sinfo.chunk_size
    n = codec.get_chunk_count()
    out: List = [None] * len(reqs)
    groups: Dict[Tuple, List] = {}
    for i, (shards, logical_size) in enumerate(reqs):
        nstripes = sinfo.object_stripes(logical_size)
        if nstripes == 0:
            out[i] = np.zeros((n, 0), dtype=np.uint8)
            continue
        if len(shards) < k:
            raise ValueError(f"only {len(shards)} of {k} shards")
        shard_len = nstripes * unit
        arrs: Dict[int, np.ndarray] = {}
        for s in sorted(shards):
            arr = np.asarray(shards[s], dtype=np.uint8)
            if arr.shape[0] != shard_len:
                raise ValueError(
                    f"shard {s}: {arr.shape[0]} bytes, want {shard_len}")
            arrs[s] = arr
        erasures = tuple(s for s in range(n) if s not in arrs)
        missing = tuple(s for s in range(k) if s not in arrs)
        groups.setdefault((erasures, missing), []).append(
            (i, arrs, nstripes, logical_size))
    if not groups:
        return out
    KERNELS.inc("ec_coalesced_reencode_ticks")
    KERNELS.inc("ec_coalesced_reencodes",
                sum(len(g) for g in groups.values()))
    host = _host_bytes_ok(codec)
    planar = _planar_ok(codec, unit)
    for (erasures, want), items in groups.items():
        total = sum(ns for _i, _a, ns, _ls in items)
        # ONE assembly of the group's (total, n, unit) batch, shared by
        # the host and planar branches (the decode twin's shape)
        full = np.zeros((total, n, unit), dtype=np.uint8)
        ofs = 0
        for _i, arrs, ns, _ls in items:
            for s, arr in arrs.items():
                full[ofs:ofs + ns, s, :] = arr.reshape(ns, unit)
            ofs += ns
        rows = None                     # (total, n, unit) result batch
        if host:
            rmat = None
            if want:
                src = _decode_src(codec, want, erasures)
                rmat = _host_decode_matrix(codec, src, want)
            if not want or rmat is not None:
                if want:
                    rec = _gf_apply_host(rmat, full[:, list(src), :])
                    for idx, e in enumerate(want):
                        full[:, e, :] = rec[:, idx, :]
                data = full[:, :k, :]
                full[:, k:, :] = _gf_apply_host(codec.engine.coding,
                                                data)
                rows = full
        if rows is None and planar:
            bb = _bucket(total)
            if bb != total:
                full = np.concatenate(
                    [full, np.zeros((bb - total, n, unit),
                                    dtype=np.uint8)])
            pb = codec.to_planar(full)
            if want:
                dec = codec.decode_planar(erasures, pb, want=want)
                combined = pb.concat(dec)
                order = tuple(n + want.index(j) if j in want else j
                              for j in range(k))
                data_pb = combined.select(order)
            else:
                data_pb = pb.select(tuple(range(k)))
            parity_pb = codec.encode_planar(data_pb)
            rows = np.asarray(
                data_pb.concat(parity_pb).to_batch())[:total]
        if rows is None:
            # no planar contract and no host matrix: coalesced decode
            # to logical bytes + coalesced encode back to shard rows —
            # still one batched trip per direction for the whole group
            idxs = [i for i, _a, _ns, _ls in items]
            datas = decode_stripes_multi(
                codec, sinfo,
                [(arrs, ls) for _i, arrs, _ns, ls in items])
            encoded = encode_stripes_multi(codec, sinfo, datas)
            for i, (shards_i, _crcs) in zip(idxs, encoded):
                out[i] = shards_i
            continue
        ofs = 0
        for i, _arrs, ns, _ls in items:
            out[i] = rows[ofs:ofs + ns].transpose(1, 0, 2) \
                .reshape(n, ns * unit)
            ofs += ns
    return out


# ---------------------------------------------------------------------------
# Planar AT-REST entry points (round 19): shards enter and leave as packed
# bit-planes (ec/planar_store.py layout) — the steady-state write, read,
# RMW, recovery and scrub paths run below with ZERO byte<->plane layout
# conversions outside the sanctioned ingest (client bytes at encode) and
# egress (logical bytes at read assemble) seams.
# ---------------------------------------------------------------------------


def planar_at_rest_ok(codec, unit: int) -> bool:
    """Can this (codec, stripe_unit) pool store EC shards as packed
    GF(2) rows at rest?  ``at_rest_layout`` with a yes or no."""
    return at_rest_layout(codec, unit) is not None


def at_rest_layout(codec, unit: int) -> Optional[str]:
    """The serialization (``ec/planar_store.py``'s tag) a (codec,
    stripe_unit) pool stores its EC shards in, or None: byte-at-rest.

    Requires a GF(2^8) matrix engine (``codec.matrix_engine``: the
    Reed-Solomon families; SHEC at w = 8, whose decode multiplies the
    chunks its plan names; LRC, whose layers flatten to one generator
    and whose decode composes the layer walk: all ``planar8``; and the
    w = 8 cauchy techniques, whose packet-interleaved chunks rest as
    their packet-row matrix, ``packet8.<packetsize>``) and a stripe
    unit that is a multiple of that serialization's quantum (8 bytes; a
    super-block).  Wider fields, the liberation family (bit-matrices
    with no byte matrix behind them), an LRC stack with a packet layer,
    and mesh adapters keep byte-at-rest; the gate falls back per pool,
    not per cluster.
    """
    from ceph_tpu.ec import planar_store as pstore
    from ceph_tpu.ec.codec import engine_layout

    layout = engine_layout(codec)
    if layout is None or unit <= 0 or unit % pstore.quantum(layout) \
            or not _planar_ok(codec, unit):
        return None
    return layout


def _planes_rows_for(codec, src: Tuple[int, ...],
                     want: Tuple[int, ...],
                     src_planes: np.ndarray) -> Optional[np.ndarray]:
    """Reconstruct ``want`` chunks' plane rows from ``src`` chunks'
    plane rows, engine per backend: host XOR over the expanded recovery
    bit-matrix on CPU, the fused planar matmul elsewhere.  None when the
    pattern has no survivor-submatrix solution (caller falls back to the
    byte machinery)."""
    from ceph_tpu.ec import planar_store as pstore
    from ceph_tpu.ops import gf8

    rmat = _host_decode_matrix(codec, src, want)
    if rmat is None:
        return None
    if _host_engine_ok(codec):
        return pstore.planar_matmul_host(gf8.expand_bitmatrix(rmat),
                                         src_planes)
    import jax.numpy as jnp

    bitmat = codec.engine.decode_bitmat(tuple(src), tuple(want))
    return np.asarray(gf8.planar_matmul(bitmat, jnp.asarray(src_planes)))


def _parity_planes_for(codec, data_planes: np.ndarray) -> np.ndarray:
    """(k*8, cols) data plane rows -> (m*8, cols) parity plane rows."""
    from ceph_tpu.ec import planar_store as pstore
    from ceph_tpu.ops import gf8

    if _host_engine_ok(codec):
        return pstore.planar_matmul_host(
            gf8.expand_bitmatrix(codec.engine.coding), data_planes)
    import jax.numpy as jnp

    return np.asarray(gf8.planar_matmul(codec.engine._enc_bitmat,
                                        jnp.asarray(data_planes)))


def _select_shard_planes(full_planes: np.ndarray,
                         shards: Tuple[int, ...]) -> np.ndarray:
    """Row-select whole shards (8 plane rows each) from a chunk-major
    plane matrix — a pure gather, no layout change."""
    idx = np.concatenate([np.arange(s * 8, s * 8 + 8) for s in shards])
    return full_planes[idx]


# -- a tick's buckets, compiled before a tick needs them --------------------
#
# A tick's first meeting with a bucket compiles three programs inside a
# served op: seconds on the chip, and the ops of every caller queue behind
# it.  Whoever warms a pool (a deployment's first writes, the benchmark's
# bursts) meets the buckets its bursts happen to coalesce into; a burst of
# 16 spread over a dozen OSDs never puts 5 objects into one tick, and a
# window later does (PR 33: 1 run in 15, a 3 s hole).  So the first tick
# of ops of a size runs, on a thread of its own and on zeros, the buckets
# that a tick of as many such ops as the caller coalesces would meet, up
# to the largest batch a tick has been seen to run on the chip.

_WARM_BUCKETS: set = set()      # (shape, bucket): met by a tick or warmed
_WARM_SIZES: set = set()        # (shape, an op's bucket): chain started
_WARM_MAX_BYTES = 32 << 20      # 8 x 4 MiB objects


def _warm_tick_buckets(codec, sinfo: StripeInfo, total: int, bb: int,
                       ops: int, max_ops: int, crcs: bool) -> None:
    """Called by a device tick of ``ops`` ops, ``total`` stripes, that
    ran at bucket ``bb``, from a caller that coalesces ``max_ops``."""
    shape = (type(codec), sinfo.k, codec.get_chunk_count(),
             sinfo.chunk_size, crcs)
    _WARM_BUCKETS.add((shape, bb))
    one = _bucket(-(-total // ops))
    if (shape, one) in _WARM_SIZES:
        return
    _WARM_SIZES.add((shape, one))
    top = min(_bucket(one * max_ops), _WARM_MAX_BYTES // sinfo.stripe_width)
    chain = []
    while one <= top:
        chain.append(one)
        one *= 2
    threading.Thread(target=_warm_buckets, name="ec-warm-buckets",
                     args=(codec, sinfo, shape, chain, crcs),
                     daemon=True).start()


def _warm_buckets(codec, sinfo: StripeInfo, shape, chain, crcs: bool) -> None:
    """Run the tick's device programs once at each bucket of ``chain``
    that no tick has met meanwhile, on zeros and off the counters: they
    say what was served."""
    from ceph_tpu.ops import crc32c as crcmod
    from ceph_tpu.utils.perf import KERNELS

    try:
        for bb in chain:
            if (shape, bb) in _WARM_BUCKETS:
                continue
            _WARM_BUCKETS.add((shape, bb))
            with KERNELS.muted():
                pb = codec.to_planar(np.zeros(
                    (bb, sinfo.k, sinfo.chunk_size), dtype=np.uint8))
                parity_pb = codec.encode_planar(pb)
                if crcs and _device_crcs_ok(pb):
                    np.asarray(crcmod.planar_chunk_crcs(
                        (pb.planes, parity_pb.planes), sinfo.chunk_size,
                        pb.packetsize))
                np.asarray(parity_pb.planes)
    except Exception:   # a warm that fails costs a later tick its compile
        logging.getLogger("ceph_tpu.ec").exception(
            "warming buckets %s failed", chain)


def _device_crcs_ok(pb) -> bool:
    """Can the chunk-crc program take this batch's planes?  Chosen by
    what the batch says of itself: its blob (a chunk's plane group, or a
    packet) within the program's reach; anything else crcs on the host."""
    from ceph_tpu.ops import crc32c as crcmod

    return pb.w == 8 and \
        (pb.packetsize or pb.chunk_size) <= crcmod._PLANAR_DEV_MAX


def encode_planes_multi(codec, sinfo: StripeInfo, datas, want_crcs=None,
                        max_ops: int = 0):
    """Coalesced encode emitting AT-REST PLANES: the planar-at-rest twin
    of ``encode_stripes_multi``.

    Returns ``[(planes, crcs), ...]`` aligned with ``datas``: ``planes``
    is the per-op (n, 8, shard_len/8) uint8 array — ``planes[s]`` is
    shard s's at-rest plane matrix, serialized by ``tobytes()`` — and
    ``crcs`` (when the op's flag is set) are per-shard
    ``ceph_crc32c(~0, byte_view)`` values computed through the planar
    row view, bit-identical to the byte anchor.  Client bytes pack into
    planes exactly ONCE (the sanctioned ingest conversion, booked on
    the ``ec_planar_ingest`` counters); parity is derived in the plane
    domain and shard bytes are never materialized.  ``max_ops``: how
    many ops the caller coalesces into a tick at most (the batcher's
    cap); given, the first device tick of ops of a size compiles the
    buckets such ticks can meet ahead of them (``_warm_tick_buckets``).
    """
    from ceph_tpu.ec import planar_store as pstore
    from ceph_tpu.ec.codec import engine_layout
    from ceph_tpu.ops import crc32c as crcmod
    from ceph_tpu.ops.profiling import record_planar_at_rest
    from ceph_tpu.trace import tick as ticktrace
    from ceph_tpu.utils.perf import KERNELS

    k = sinfo.k
    unit = sinfo.chunk_size
    n = codec.get_chunk_count()
    # the caller passed the gate, so the codec names a serialization
    layout = engine_layout(codec)
    packetsize = pstore.packetsize_of(layout)
    if want_crcs is None:
        want_crcs = [False] * len(datas)
    counts = [sinfo.object_stripes(len(d)) for d in datas]
    total = sum(counts)
    out: List = [None] * len(datas)
    if total == 0:
        for i in range(len(datas)):
            planes = np.zeros((n, 8, 0), dtype=np.uint8)
            out[i] = (planes,
                      crcmod.crc32c_planar_rows(planes.reshape(n * 8, 0))
                      if want_crcs[i] else None)
        return out
    KERNELS.inc("ec_coalesced_ticks")
    KERNELS.inc("ec_coalesced_ops", len(datas))
    host = _host_engine_ok(codec)
    bb = total if host else _bucket(total)
    # the phases below land on the tick open on this thread (the
    # batcher's; trace/tick.py) and are no-ops outside one
    ticktrace.annotate(total, bb, sum(len(d) for d in datas),
                       "packet" if packetsize else "bitpack")
    with ticktrace.phase("fill"):
        # at the bucket's size at once: the stripes past ``total`` are
        # the pad, zeros as allocated
        batch = np.zeros((bb, k, unit), dtype=np.uint8)
        pad = 0
        ofs = 0
        for d, ns in zip(datas, counts):
            if ns == 0:
                continue
            flat = batch[ofs:ofs + ns].reshape(ns * k * unit)
            flat[: len(d)] = np.frombuffer(d, dtype=np.uint8)
            pad += ns * sinfo.stripe_width - len(d)
            ofs += ns
    KERNELS.inc("ec_stripe_pad_bytes", pad + (bb - total) * k * unit)
    # THE sanctioned ingest: client bytes -> planes, once per tick
    record_planar_at_rest("ingest", total * k * unit)
    if packetsize:
        # ... of them as packet rows: whole packets moved, no bit sliced
        KERNELS.inc("ec_planar_packet_ingest_bytes", total * k * unit)
    op_crcs: Dict[int, List[int]] = {}
    if host:
        rows = np.ascontiguousarray(
            batch.transpose(1, 0, 2).reshape(k, total * unit))
        data_planes = pstore.rows_to_planes(rows, layout)
        parity_planes = _parity_planes_for(codec, data_planes)
    else:
        with ticktrace.phase("to_planar"):
            pb = codec.to_planar(batch)
        with ticktrace.phase("encode_dispatch"):
            parity_pb = codec.encode_planar(pb)
        # The shard crcs come from the planes while the device holds
        # them: per-chunk crcs, launched behind the encode and before
        # the first blocking readback, so the device runs ingest ->
        # encode -> crc back to back.  Chosen by what the batch says of
        # itself; any other layout keeps the host crc below.
        chunk_crcs = None
        if any(want_crcs) and _device_crcs_ok(pb):
            with ticktrace.phase("crc"):
                chunk_crcs = crcmod.planar_chunk_crcs(
                    (pb.planes, parity_pb.planes), unit, packetsize)
        # each readback blocks until the device is done, then copies
        # device -> host; a device call apiece
        with ticktrace.phase("readback"):
            ticktrace.device_calls()
            data_planes = np.asarray(pb.planes)
        with ticktrace.phase("readback"):
            ticktrace.device_calls()
            parity_planes = np.asarray(parity_pb.planes)
        if chunk_crcs is not None:
            # (n, bb) words, on their way since the device had them
            with ticktrace.phase("crc"):
                ticktrace.device_calls()
                op_crcs = _fold_op_crcs(np.asarray(chunk_crcs), counts,
                                        want_crcs, unit, packetsize)
        if max_ops:
            _warm_tick_buckets(codec, sinfo, total, bb, len(datas), max_ops,
                               any(want_crcs))
    # per-op at-rest planes slice straight out of the tick's data and
    # parity plane matrices: op columns are contiguous (unit % 8 == 0),
    # shard s is plane rows s*8..s*8+8, the data shards' rows first — no
    # conversion, no transpose of payload, and ONE copy a byte: a stack
    # of the whole tick would write its pad, and every op a second time,
    # into new pages, which is what a tick's host time is made of
    crc_groups: Dict[int, List] = {}
    with ticktrace.phase("slice"):
        c0 = 0
        kr = data_planes.shape[0]
        for i, ns in enumerate(counts):
            cw = ns * unit // 8
            op_planes = np.empty((n * 8, cw), dtype=np.uint8)
            op_planes[:kr] = data_planes[:, c0:c0 + cw]
            op_planes[kr:] = parity_planes[:, c0:c0 + cw]
            op_planes = op_planes.reshape(n, 8, cw)
            c0 += cw
            out[i] = (op_planes, op_crcs.get(i))
            if want_crcs[i] and i not in op_crcs:
                crc_groups.setdefault(cw, []).append((i, op_planes))
    # host crc: one planar pass per shard length group (planar row
    # view: bit-identical to the byte anchor's crc32c_rows)
    for _cw, group in crc_groups.items():
        with ticktrace.phase("crc"):
            stacked = np.concatenate(
                [p.reshape(n * 8, -1) for _i, p in group], axis=0)
            crcs = crcmod.crc32c_planar_rows(stacked,
                                             packetsize=packetsize)
        for gi, (i, p) in enumerate(group):
            out[i] = (p, crcs[gi * n:(gi + 1) * n])
    return out


def _fold_op_crcs(chunk_crcs: np.ndarray, counts, want_crcs,
                  unit: int, packetsize: int = 0) -> Dict[int, List[int]]:
    """A tick's (n, bb) zero-seeded chunk crcs -> {op: its n shard
    ``ceph_crc32c(~0, byte_view)`` values}.  Op i owns the ``counts[i]``
    columns after those of the ops before it (the bucket's padding
    stripes come last and are nobody's); ops of one length fold
    together.  With ``packetsize`` the words are packet crcs, (n*8,
    bb*per): a stripe's chunk is ``per`` super-blocks, and an op's run
    folds in the byte stream's order (``packet_stream``)."""
    from ceph_tpu.ops.crc32c import fold_chunk_crcs, packet_stream

    per = unit // (8 * packetsize) if packetsize else 1
    n = chunk_crcs.shape[0] // (8 if packetsize else 1)
    groups: Dict[int, List[Tuple[int, int]]] = {}
    c0 = 0
    for i, ns in enumerate(counts):
        if want_crcs[i]:
            groups.setdefault(ns, []).append((i, c0))
        c0 += ns
    out: Dict[int, List[int]] = {}
    for ns, members in groups.items():
        runs = [chunk_crcs[:, c * per:(c + ns) * per] for _i, c in members]
        crcs = fold_chunk_crcs(
            np.concatenate([packet_stream(r) for r in runs]), packetsize) \
            if packetsize else fold_chunk_crcs(np.concatenate(runs), unit)
        for gi, (i, _c) in enumerate(members):
            out[i] = [int(c) for c in crcs[gi * n:(gi + 1) * n]]
    return out


def _normalize_planes(shards, cols: int) -> Dict[int, np.ndarray]:
    """Shard map values -> (8, cols) plane matrices (serialized blobs
    reshape in place; already-shaped arrays pass through)."""
    from ceph_tpu.ec import planar_store as pstore

    out: Dict[int, np.ndarray] = {}
    for s, v in shards.items():
        arr = pstore.blob_to_planes(v) if isinstance(v, (bytes, bytearray,
                                                         memoryview)) \
            else np.ascontiguousarray(v, dtype=np.uint8).reshape(8, -1)
        if arr.shape[1] != cols:
            raise ValueError(
                f"shard {s}: {arr.shape[1]} plane cols, want {cols}")
        out[s] = arr
    return out


def _assemble_from_planes(data_planes: Dict[int, np.ndarray], k: int,
                          nstripes: int, unit: int,
                          logical_size: int, layout: str) -> bytes:
    """Planar shards -> logical client bytes: THE sanctioned egress."""
    from ceph_tpu.ec import planar_store as pstore
    from ceph_tpu.ops.profiling import record_planar_at_rest

    stacked = np.vstack([data_planes[s] for s in range(k)])
    record_planar_at_rest("egress", int(stacked.size))
    rows = pstore.planes_to_rows(stacked, layout)   # (k, shard_len)
    return _assemble_logical({s: rows[s] for s in range(k)},
                             k, nstripes, unit, logical_size)


def decode_planes_multi(codec, sinfo: StripeInfo, reqs):
    """Coalesced decode from AT-REST PLANES to logical bytes: the
    planar-at-rest twin of ``decode_stripes_multi``.

    ``reqs`` is a sequence of ``(shard_planes, logical_size)`` pairs;
    ``shard_planes`` maps shard id -> (8, shard_len/8) plane matrix (or
    its serialized blob).  Reconstruction of missing data shards runs in
    the plane domain (grouped by erasure pattern, engine per backend);
    the ONLY conversion is the final planes -> logical-bytes assemble,
    booked as the sanctioned egress.  Patterns without a
    survivor-submatrix solution fall back to the byte machinery through
    a relayout conversion (legal, counted, never on the steady state).
    """
    from ceph_tpu.ec import planar_store as pstore
    from ceph_tpu.ec.codec import engine_layout
    from ceph_tpu.utils.perf import KERNELS

    k = sinfo.k
    unit = sinfo.chunk_size
    n = codec.get_chunk_count()
    layout = engine_layout(codec)
    out: List = [None] * len(reqs)
    groups: Dict[Tuple, List] = {}
    for i, (shards, logical_size) in enumerate(reqs):
        nstripes = sinfo.object_stripes(logical_size)
        if nstripes == 0:
            out[i] = b""
            continue
        cols = nstripes * unit // 8
        arrs = _normalize_planes(shards, cols)
        missing = tuple(s for s in range(k) if s not in arrs)
        if not missing:
            out[i] = _assemble_from_planes(arrs, k, nstripes, unit,
                                           logical_size, layout)
            continue
        if len(arrs) < k:
            raise ValueError(f"only {len(arrs)} of {k} shards")
        erasures = tuple(s for s in range(n) if s not in arrs)
        groups.setdefault((erasures, missing), []).append(
            (i, arrs, nstripes, logical_size))
    if not groups:
        return out
    KERNELS.inc("ec_coalesced_read_ticks")
    KERNELS.inc("ec_coalesced_reads", sum(len(g) for g in groups.values()))
    for (erasures, want), items in groups.items():
        src = _decode_src(codec, want, erasures)
        total_cols = sum(ns for _i, _a, ns, _ls in items) * unit // 8
        src_planes = np.zeros((len(src) * 8, total_cols), dtype=np.uint8)
        c0 = 0
        for _i, arrs, ns, _ls in items:
            cw = ns * unit // 8
            for j, s in enumerate(src):
                src_planes[j * 8:j * 8 + 8, c0:c0 + cw] = arrs[s]
            c0 += cw
        rec = _planes_rows_for(codec, src, want, src_planes)
        if rec is None:
            # unsolvable pattern for the plane engine: relayout to the
            # byte machinery (counted; never the steady state)
            for i, arrs, ns, logical_size in items:
                byte_shards = {
                    s: np.frombuffer(
                        pstore.planes_to_shard(a, seam="relayout",
                                               layout=layout),
                        dtype=np.uint8)
                    for s, a in arrs.items()}
                out[i] = decode_stripes_multi(
                    codec, sinfo, [(byte_shards, logical_size)])[0]
            continue
        c0 = 0
        for i, arrs, ns, logical_size in items:
            cw = ns * unit // 8
            data_planes = {s: arrs[s] for s in range(k) if s in arrs}
            for idx, e in enumerate(want):
                data_planes[e] = rec[idx * 8:idx * 8 + 8, c0:c0 + cw]
            c0 += cw
            out[i] = _assemble_from_planes(data_planes, k, ns, unit,
                                           logical_size, layout)
    return out


def reencode_planes_multi(codec, sinfo: StripeInfo, reqs):
    """Coalesced recovery rebuild in the plane domain: AT-REST planes
    in, AT-REST planes out — ZERO layout conversions (the recovery path
    neither ingests client bytes nor egresses logical bytes).

    ``reqs`` mirrors ``decode_planes_multi``; returns the per-op
    (n, 8, shard_len/8) uint8 arrays, aligned with ``reqs``.  Missing
    chunks' plane rows rebuild through the recovery bit-matrix, parity
    re-derives from the data plane rows, and surviving shards pass
    through untouched.
    """
    from ceph_tpu.ec import planar_store as pstore
    from ceph_tpu.ec.codec import engine_layout
    from ceph_tpu.utils.perf import KERNELS

    k = sinfo.k
    unit = sinfo.chunk_size
    n = codec.get_chunk_count()
    layout = engine_layout(codec)
    out: List = [None] * len(reqs)
    groups: Dict[Tuple, List] = {}
    for i, (shards, logical_size) in enumerate(reqs):
        nstripes = sinfo.object_stripes(logical_size)
        if nstripes == 0:
            out[i] = np.zeros((n, 8, 0), dtype=np.uint8)
            continue
        if len(shards) < k:
            raise ValueError(f"only {len(shards)} of {k} shards")
        cols = nstripes * unit // 8
        arrs = _normalize_planes(shards, cols)
        erasures = tuple(s for s in range(n) if s not in arrs)
        missing = tuple(s for s in range(k) if s not in arrs)
        groups.setdefault((erasures, missing), []).append(
            (i, arrs, nstripes, logical_size))
    if not groups:
        return out
    KERNELS.inc("ec_coalesced_reencode_ticks")
    KERNELS.inc("ec_coalesced_reencodes",
                sum(len(g) for g in groups.values()))
    for (erasures, want), items in groups.items():
        total_cols = sum(ns for _i, _a, ns, _ls in items) * unit // 8
        full = np.zeros((n * 8, total_cols), dtype=np.uint8)
        c0 = 0
        for _i, arrs, ns, _ls in items:
            cw = ns * unit // 8
            for s, a in arrs.items():
                full[s * 8:s * 8 + 8, c0:c0 + cw] = a
            c0 += cw
        if want:
            src = _decode_src(codec, want, erasures)
            rec = _planes_rows_for(codec, src,
                                   want, _select_shard_planes(full, src))
            if rec is None:
                # relayout fallback through the byte reencode
                for i, arrs, ns, logical_size in items:
                    byte_shards = {
                        s: np.frombuffer(
                            pstore.planes_to_shard(a, seam="relayout",
                                                   layout=layout),
                            dtype=np.uint8)
                        for s, a in arrs.items()}
                    rows = reencode_stripes_multi(
                        codec, sinfo, [(byte_shards, logical_size)])[0]
                    out[i] = pstore.rows_to_planes(rows, layout).reshape(
                        n, 8, rows.shape[1] // 8)
                    pstore.record_planar_at_rest(
                        "relayout", int(rows.size))
                continue
            for idx, e in enumerate(want):
                full[e * 8:e * 8 + 8] = rec[idx * 8:idx * 8 + 8]
        full[k * 8:] = _parity_planes_for(codec, full[: k * 8])
        c0 = 0
        for i, _arrs, ns, _ls in items:
            cw = ns * unit // 8
            out[i] = np.ascontiguousarray(
                full[:, c0:c0 + cw]).reshape(n, 8, cw)
            c0 += cw
    return out


def merge_range(old: bytes, old_size: int, offset: int, data: bytes) -> bytes:
    """Overlay ``data`` at ``offset`` onto ``old`` (zero-extending holes);
    returns the new logical object bytes."""
    new_size = max(old_size, offset + len(data))
    buf = np.zeros(new_size, dtype=np.uint8)
    if old:
        buf[: len(old)] = np.frombuffer(old, dtype=np.uint8)
    buf[offset: offset + len(data)] = np.frombuffer(data, dtype=np.uint8)
    return buf.tobytes()
