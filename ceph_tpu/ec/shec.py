"""SHEC: shingled erasure codes (k, m, c).

Behavioral mirror of reference src/erasure-code/shec/ErasureCodeShec.{h,cc}
and ErasureCodePluginShec.cc: a Vandermonde RS matrix with a shingle pattern
of zeros (shec_reedsolomon_coding_matrix, ErasureCodeShec.cc:456), the
(m1, c1, m2, c2) split chosen by the recovery-efficiency metric
(shec_calc_recovery_efficiency1, :415), per-erasure-pattern decode via a
minimal-subset search over parity combinations + GF Gaussian elimination
(shec_make_decoding_matrix, :526), and a decode-table cache keyed by the
(want, avails) pattern (ErasureCodeShecTableCache).

Tolerates up to ``c`` erasures while reading fewer chunks than a full-k MDS
decode — the "shingle" rows overlap so each data chunk is covered by a
cheap local-ish parity.  Encode is the standard bytewise GF(2^8) matrix
multiply, so the TPU MXU bit-matrix path serves it unchanged.  Decode
differs from an MDS code's in two ways, and both are this module's: the
recovery matrix comes from the plan search (on the host, k x k bytes at
most), and WHICH chunks it multiplies is the plan's choice, not "the
first k that came": the first row of SHEC(6,4,3) covers chunks 0-2 only,
so {0, 1, 2, 4, 5, 6} do not give chunk 3 and {4, 5, 7} do.  One plan
(``_recovery``) says it to everyone who asks: ``decode_sources`` (the
read gather of ``cluster/backend_ec.py`` and ``stripe._decode_src``),
``_batch_plan`` / ``_planar_decode_plan`` (the device decode) and the
engine's ``decode_matrix`` (the host GF engine and the plane entry
points).
"""

from __future__ import annotations

import errno
from typing import Dict, List, Mapping, Optional, Set, Tuple

import numpy as np

from ceph_tpu.ec import matrices
from ceph_tpu.ec.codec import MatrixCodec, _DeviceMatrixEngine
from ceph_tpu.ec.interface import ECError, ErasureCodeProfile
from ceph_tpu.ops import gf8, gfw

MULTIPLE = 0
SINGLE = 1

LARGEST_VECTOR_WORDSIZE = 16


def gfw_invert(mat: np.ndarray, w: int) -> np.ndarray:
    """gfw inversion with the gf8 SingularMatrixError contract."""
    try:
        return gfw.gfw_invert_matrix(mat, w)
    except ValueError as e:
        raise gf8.SingularMatrixError(str(e))


def _calc_recovery_efficiency1(k: int, m1: int, m2: int, c1: int, c2: int) -> float:
    """Reference shec_calc_recovery_efficiency1 (ErasureCodeShec.cc:415)."""
    if m1 < c1 or m2 < c2:
        return -1.0
    if (m1 == 0 and c1 != 0) or (m2 == 0 and c2 != 0):
        return -1.0
    r_eff_k = [100000000] * k
    r_e1 = 0.0
    for rr in range(m1):
        start = ((rr * k) // m1) % k
        end = (((rr + c1) * k) // m1) % k
        cc = start
        first = True
        while first or cc != end:
            first = False
            r_eff_k[cc] = min(r_eff_k[cc],
                              ((rr + c1) * k) // m1 - (rr * k) // m1)
            cc = (cc + 1) % k
        r_e1 += ((rr + c1) * k) // m1 - (rr * k) // m1
    for rr in range(m2):
        start = ((rr * k) // m2) % k
        end = (((rr + c2) * k) // m2) % k
        cc = start
        first = True
        while first or cc != end:
            first = False
            r_eff_k[cc] = min(r_eff_k[cc],
                              ((rr + c2) * k) // m2 - (rr * k) // m2)
            cc = (cc + 1) % k
        r_e1 += ((rr + c2) * k) // m2 - (rr * k) // m2
    r_e1 += sum(r_eff_k)
    return r_e1 / (k + m1 + m2)


def shec_coding_matrix(k: int, m: int, c: int, technique: int,
                       w: int = 8) -> np.ndarray:
    """Shingled (m, k) coding matrix (reference
    shec_reedsolomon_coding_matrix, ErasureCodeShec.cc:456): a Vandermonde
    RS matrix over GF(2^w) with shingle-patterned zeros."""
    if technique == MULTIPLE:
        c1_best, m1_best = -1, -1
        min_r_e1 = 100.0
        for c1 in range(c // 2 + 1):
            for m1 in range(m + 1):
                c2 = c - c1
                m2 = m - m1
                if m1 < c1 or m2 < c2:
                    continue
                if (m1 == 0 and c1 != 0) or (m2 == 0 and c2 != 0):
                    continue
                if (m1 != 0 and c1 == 0) or (m2 != 0 and c2 == 0):
                    continue
                r_e1 = _calc_recovery_efficiency1(k, m1, m2, c1, c2)
                if min_r_e1 - r_e1 > np.finfo(float).eps and r_e1 < min_r_e1:
                    min_r_e1 = r_e1
                    c1_best, m1_best = c1, m1
        m1, c1 = m1_best, c1_best
        m2, c2 = m - m1_best, c - c1_best
    else:
        m1, c1 = 0, 0
        m2, c2 = m, c

    if w == 8:
        mat = matrices.reed_sol_vandermonde_coding_matrix(k, m).astype(
            np.uint8)
    else:
        mat = matrices.reed_sol_vandermonde_coding_matrix_w(k, m, w)
    for rr in range(m1):
        end = ((rr * k) // m1) % k
        start = (((rr + c1) * k) // m1) % k
        cc = start
        while cc != end:
            mat[rr, cc] = 0
            cc = (cc + 1) % k
    for rr in range(m2):
        end = ((rr * k) // m2) % k
        start = (((rr + c2) * k) // m2) % k
        cc = start
        while cc != end:
            mat[rr + m1, cc] = 0
            cc = (cc + 1) % k
    return mat


class _ShingledEngine(_DeviceMatrixEngine):
    """The shingled matrix behind the one engine seam
    (``codec.matrix_engine``).  Encode is the base class's one matmul.
    Decode is not a survivor-submatrix inversion: not every k rows of
    the punctured generator invert, and fewer than k rebuild a chunk
    their shingle covers.  ``decode_matrix`` is the codec's plan over
    exactly the chunks it is given (columns of chunks the plan leaves
    out are zero) and RAISES where they cannot produce ``out_rows``."""

    def __init__(self, codec: "ErasureCodeShec"):
        super().__init__(codec.k, codec.m, codec.build_coding_matrix(),
                         w=codec.w)
        self._plan = codec._recovery

    def decode_matrix(self, src_rows: Tuple[int, ...],
                      out_rows: Tuple[int, ...]) -> np.ndarray:
        src_rows = tuple(src_rows)
        rmat, used = self._plan(
            tuple(e for e in range(self.k + self.m) if e not in src_rows),
            tuple(out_rows))
        full = np.zeros((len(out_rows), len(src_rows)), dtype=rmat.dtype)
        full[:, [src_rows.index(s) for s in used]] = rmat
        return full


class ErasureCodeShec(MatrixCodec):
    DEFAULT_K = 4
    DEFAULT_M = 3
    DEFAULT_C = 2

    def __init__(self, technique: int = MULTIPLE):
        super().__init__()
        self.technique = technique
        self.c = 0
        # decode-plan cache keyed by (want, avails) bit patterns
        # (ErasureCodeShecTableCache semantics)
        self._plan_cache: Dict[Tuple, Tuple] = {}
        # per (erasures, want) pattern: the recovery matrix and the
        # chunks it multiplies, and its bit-matrix for the device
        self._recovery_cache: Dict[Tuple, Tuple] = {}
        self._batch_cache: Dict[Tuple, Tuple] = {}

    # -- profile ------------------------------------------------------------

    def parse(self, profile: ErasureCodeProfile) -> None:
        super().parse(profile)
        has = [name in profile and profile[name] for name in ("k", "m", "c")]
        if not any(has):
            self.k, self.m, self.c = self.DEFAULT_K, self.DEFAULT_M, self.DEFAULT_C
        elif not all(has):
            raise ECError(errno.EINVAL, "(k, m, c) must all be chosen")
        else:
            try:
                self.k = int(profile["k"])
                self.m = int(profile["m"])
                self.c = int(profile["c"])
            except ValueError as e:
                raise ECError(errno.EINVAL, f"bad k/m/c: {e}")
        k, m, c = self.k, self.m, self.c
        if k <= 0 or m <= 0 or c <= 0:
            raise ECError(errno.EINVAL, "k, m, c must be positive")
        if m < c:
            raise ECError(errno.EINVAL, f"c={c} must be <= m={m}")
        if k > 12:
            raise ECError(errno.EINVAL, f"k={k} must be <= 12")
        if k + m > 20:
            raise ECError(errno.EINVAL, f"k+m={k+m} must be <= 20")
        if k < m:
            raise ECError(errno.EINVAL, f"m={m} must be <= k={k}")
        w = profile.get("w")
        self.w = 8
        if w:
            try:
                wv = int(w)
            except ValueError:
                wv = 8
            if wv not in (8, 16, 32):
                wv = 8  # reference falls back to the default, no error
            self.w = wv

    def get_alignment(self) -> int:
        # reference ErasureCodeShecReedSolomonVandermonde::get_alignment:
        # k * w * sizeof(int)
        return self.k * self.w * 4

    def get_chunk_size(self, object_size: int) -> int:
        alignment = self.get_alignment()
        tail = object_size % alignment
        padded = object_size + (alignment - tail if tail else 0)
        assert padded % self.k == 0
        return padded // self.k

    def build_coding_matrix(self) -> np.ndarray:
        return shec_coding_matrix(self.k, self.m, self.c, self.technique,
                                  self.w)

    def prepare(self) -> None:
        self.engine = _ShingledEngine(self)

    # -- field-width helpers (gf8 fast path, gfw for w in {16, 32}) ---------

    def _invert(self, mat: np.ndarray) -> np.ndarray:
        if self.w == 8:
            return gf8.gf_invert_matrix(mat.astype(np.uint8))
        return gfw_invert(mat, self.w)

    def _mul(self, a: int, b_row: np.ndarray) -> np.ndarray:
        if self.w == 8:
            return gf8.gf_mul(a, b_row)
        gf = gfw.field(self.w)
        return np.array([gf.mul(a, int(x)) for x in b_row],
                        dtype=np.uint64)

    def _matmul_host(self, rows: np.ndarray, data: np.ndarray) -> np.ndarray:
        """(r, c) words x (c, S) bytes -> (r, S) bytes on host."""
        if self.w == 8:
            return np.asarray(gf8.gf_matmul_ref(rows, data))
        bitmat = gfw.expand_bitmatrix_w(rows, self.w)
        import jax.numpy as jnp

        return np.asarray(gfw.bitmatrix_matmul_w(
            jnp.asarray(bitmat), jnp.asarray(data), self.w // 8))

    # -- decode-plan search (reference shec_make_decoding_matrix, :526) -----

    def _make_decoding_plan(self, want: List[int], avails: List[int]):
        """Returns (srcs, cols, inv, minimum):
        srcs — chunk ids whose values feed the solve (rows of the system),
        cols — data chunk ids solved for (columns),
        inv  — GF inverse of the system matrix (None when nothing to solve),
        minimum — minimal chunk-id set to read.
        Raises ECError(EIO) when the pattern is unrecoverable."""
        k, m = self.k, self.m
        matrix = self.engine.coding
        want = list(want)
        # to re-encode a wanted erased parity, all data in its support is wanted
        for i in range(m):
            if want[k + i] and not avails[k + i]:
                for j in range(k):
                    if matrix[i, j] > 0:
                        want[j] = 1

        key = (tuple(want), tuple(avails))
        cached = self._plan_cache.get(key)
        if cached is not None:
            return cached

        mindup = k + 1
        minp = k + 1
        best_srcs: List[int] = []
        best_cols: List[int] = []
        best_inv = None
        for pp in range(1 << m):
            p = [i for i in range(m) if pp & (1 << i)]
            ek = len(p)
            if ek > minp:
                continue
            if not all(avails[k + i] for i in p):
                continue
            tmprow = [0] * (k + m)
            tmpcolumn = [0] * k
            for i in range(k):
                if want[i] and not avails[i]:
                    tmpcolumn[i] = 1
            for i in p:
                tmprow[k + i] = 1
                for j in range(k):
                    element = int(matrix[i, j])
                    if element != 0:
                        tmpcolumn[j] = 1
                        if avails[j] == 1:
                            tmprow[j] = 1
            dup_row = sum(tmprow)
            dup_column = sum(tmpcolumn)
            if dup_row != dup_column:
                continue
            dup = dup_row
            if dup == 0:
                mindup = 0
                best_srcs, best_cols, best_inv = [], [], None
                break
            if dup < mindup:
                srcs = [i for i in range(k + m) if tmprow[i]]
                cols = [j for j in range(k) if tmpcolumn[j]]
                tmpmat = np.zeros((dup, dup),
                                  dtype=np.uint8 if self.w == 8
                                  else np.uint64)
                for r, i in enumerate(srcs):
                    for cidx, j in enumerate(cols):
                        if i < k:
                            tmpmat[r, cidx] = 1 if i == j else 0
                        else:
                            tmpmat[r, cidx] = matrix[i - k, j]
                try:
                    inv = self._invert(tmpmat)
                except gf8.SingularMatrixError:
                    continue  # singular: determinant is zero, reject
                mindup = dup
                best_srcs, best_cols, best_inv = srcs, cols, inv
                minp = ek

        if mindup == k + 1:
            raise ECError(errno.EIO, "shec: can't find recover matrix")

        minimum = set(best_srcs)
        for i in range(k):
            if want[i] and avails[i]:
                minimum.add(i)
        for i in range(m):
            if want[k + i] and avails[k + i] and (k + i) not in minimum:
                for j in range(k):
                    if matrix[i, j] > 0 and not want[j]:
                        minimum.add(k + i)
                        break

        plan = (best_srcs, best_cols, best_inv, minimum)
        self._plan_cache[key] = plan
        return plan

    # -- interface ----------------------------------------------------------

    def minimum_to_decode(
        self, want_to_read: Set[int], available_chunks: Set[int]
    ) -> Set[int]:
        n = self.k + self.m
        for s in (want_to_read, available_chunks):
            for i in s:
                if i < 0 or i >= n:
                    raise ECError(errno.EINVAL, f"bad chunk id {i}")
        want = [1 if i in want_to_read else 0 for i in range(n)]
        avails = [1 if i in available_chunks else 0 for i in range(n)]
        _, _, _, minimum = self._make_decoding_plan(want, avails)
        return set(minimum)

    def decode_sources(self, want, available) -> Optional[List[int]]:
        """Which of the chunks ``available`` (those that came) a decode
        of the chunks ``want`` multiplies: the sources of the plan
        (``_recovery``), so fewer than k where a shingle covers what is
        lost; ECError(EIO) where ``available`` cannot produce ``want``.
        This code always has an answer (``ErasureCode.decode_sources``):
        the first k of those that came need not decode."""
        have = set(available)
        _rmat, src = self._recovery(
            tuple(e for e in range(self.k + self.m) if e not in have),
            tuple(sorted(c for c in want if c not in have)))
        return list(src)

    def decode_chunks(
        self,
        want_to_read: Set[int],
        chunks: Mapping[int, np.ndarray],
        decoded: Dict[int, np.ndarray],
    ) -> None:
        """Reference shec_matrix_decode (ErasureCodeShec.cc:756): solve the
        minimal system for erased wanted data chunks, then re-encode erased
        wanted parities from the (now complete) data row."""
        k, m = self.k, self.m
        n = k + m
        avails = [1 if i in chunks else 0 for i in range(n)]
        want = [1 if (i in want_to_read and i not in chunks) else 0
                for i in range(n)]
        if not any(want):
            return
        srcs, cols, inv, _ = self._make_decoding_plan(want, avails)
        if inv is not None and srcs:
            src_data = np.stack([
                np.asarray(decoded[i], dtype=np.uint8) for i in srcs
            ])
            # reconstruct only the erased columns; available ones are
            # already in `decoded`
            out_rows = [ci for ci, j in enumerate(cols) if not avails[j]]
            if out_rows:
                rmat = inv[out_rows]
                out = self._matmul_host(rmat, src_data) \
                    if src_data.shape[1] < 4096 or self.w != 8 \
                    else self._device_matmul(rmat, src_data)
                for idx, ci in enumerate(out_rows):
                    decoded[cols[ci]][...] = out[idx]
        # re-encode wanted erased parity chunks from complete data
        parity_want = [i for i in range(m) if want[k + i]]
        if parity_want:
            data = np.stack([
                np.asarray(decoded[i], dtype=np.uint8) for i in range(k)
            ])
            rows = self.engine.coding[parity_want]
            out = self._matmul_host(rows, data) \
                if data.shape[1] < 4096 or self.w != 8 \
                else self._device_matmul(rows, data)
            for idx, i in enumerate(parity_want):
                decoded[k + i][...] = out[idx]

    def _device_matmul(self, rmat: np.ndarray, data: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        from ceph_tpu.ec.codec import _encode_cols

        bitmat = jnp.asarray(gf8.expand_bitmatrix(rmat))
        return np.asarray(_encode_cols(bitmat, jnp.asarray(data)))

    def decode_batch(self, erasures: Tuple[int, ...], chunks,
                     want: Tuple[int, ...] = None) -> np.ndarray:
        """Batched single-pattern reconstruction on device: build the plan
        once, apply ONE recovery matrix to the whole stripe batch.
        ``erasures`` = every unavailable chunk id; ``want`` = the subset to
        rebuild (default all of them).

        Erased parity rows are handled by composing the coding row with the
        data-recovery expressions (same composition the reference performs
        chunk-at-a-time in shec_matrix_decode, ErasureCodeShec.cc:526-756):
        every data chunk j is either an available source (identity row) or a
        solved combination of the plan's sources (its inverse row), so
        parity i = coding[i] @ [data exprs] is itself one row over sources.
        """
        import jax.numpy as jnp

        from ceph_tpu.ec.codec import (_gather_encode_batch_jit,
                                       _gather_encode_batch_w_jit)

        def _apply(bitmat, src_list):
            if self.w == 8:
                return _gather_encode_batch_jit(
                    bitmat, jnp.asarray(chunks), tuple(src_list))
            return _gather_encode_batch_w_jit(
                bitmat, jnp.asarray(chunks), tuple(src_list), self.w // 8)

        if want is None:
            want = tuple(erasures)
        bitmat, src_list = self._batch_plan(tuple(erasures), tuple(want))
        return _apply(bitmat, src_list)

    def _planar_decode_plan(self, erasures, want):
        """Planar decode rides the same non-MDS plan construction (the
        MatrixCodec default of 'first k available' can be singular for
        SHEC's punctured coding matrix)."""
        return self._batch_plan(erasures, want)

    def _batch_plan(self, erasures: Tuple[int, ...],
                    want: Tuple[int, ...]):
        """(recovery bit-matrix, source ids) for one erasure pattern,
        cached like the reference decode tables."""
        cache_key = (erasures, want)
        cached = self._batch_cache.get(cache_key)
        if cached is None:
            import jax.numpy as jnp

            rmat, src = self._recovery(erasures, want)
            if self.w == 8:
                bitmat = jnp.asarray(gf8.expand_bitmatrix(rmat))
            else:
                bitmat = jnp.asarray(gfw.expand_bitmatrix_w(rmat, self.w))
            cached = self._batch_cache[cache_key] = (bitmat, src)
        return cached

    def _recovery(self, erasures: Tuple[int, ...],
                  want: Tuple[int, ...]):
        """THE plan for one erasure pattern: ``(rmat, src)`` with
        chunk[want] = rmat @ chunk[src], ``src`` ascending and every one
        of its chunks multiplied.  ``erasures`` = every chunk that is not
        there, ``want`` = those of them to rebuild.  ECError(EIO) where
        the chunks that are there cannot give ``want``."""
        cache_key = (tuple(erasures), tuple(want))
        cached = self._recovery_cache.get(cache_key)
        if cached is not None:
            return cached
        word_dtype = np.uint8 if self.w == 8 else np.uint64
        n = self.k + self.m
        avails = [0 if i in erasures else 1 for i in range(n)]
        want_vec = [1 if i in want else 0 for i in range(n)]
        srcs, cols, inv, _ = self._make_decoding_plan(want_vec, avails)
        # available data chunks in an erased parity's support feed the
        # composition directly; they are sources too
        src = sorted(set(srcs) | {
            j for e in want if e >= self.k for j in range(self.k)
            if self.engine.coding[e - self.k, j] and avails[j]})
        pos = {s: i for i, s in enumerate(src)}
        S = len(src)

        def data_expr(j: int) -> np.ndarray:
            """Row expressing data chunk j over src."""
            row = np.zeros(S, dtype=word_dtype)
            if avails[j]:
                row[pos[j]] = 1
            else:
                ci = cols.index(j)
                for r_i, s in enumerate(srcs):
                    row[pos[s]] = inv[ci][r_i]
            return row

        rows = []
        for e in want:
            if e < self.k:
                rows.append(data_expr(e))
            else:
                crow = self.engine.coding[e - self.k]
                acc = np.zeros(S, dtype=word_dtype)
                for j in range(self.k):
                    cj = int(crow[j])
                    if cj:
                        acc ^= self._mul(cj, data_expr(j)).astype(word_dtype)
                rows.append(acc)
        rmat = np.stack(rows).astype(word_dtype) if rows \
            else np.zeros((0, S), dtype=word_dtype)
        # a row of the system that no wanted chunk reads is no source
        used = np.flatnonzero(rmat.any(axis=0))
        cached = self._recovery_cache[cache_key] = (
            np.ascontiguousarray(rmat[:, used]),
            tuple(src[int(i)] for i in used))
        return cached


def make_shec(profile: ErasureCodeProfile):
    technique_name = profile.get("technique") or "multiple"
    profile["technique"] = technique_name
    if technique_name == "multiple":
        technique = MULTIPLE
    elif technique_name == "single":
        technique = SINGLE
    else:
        raise ECError(errno.ENOENT,
                      f"technique={technique_name} is not a valid coding technique")
    codec = ErasureCodeShec(technique)
    codec.init(profile)
    return codec
