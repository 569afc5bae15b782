"""The jerasure codec family — all 7 techniques.

Behavioral mirror of reference src/erasure-code/jerasure/
ErasureCodeJerasure.{h,cc} and ErasureCodePluginJerasure.cc:42-56: technique
selection by profile, per-technique alignment/chunk-size rules
(ErasureCodeJerasure.cc:74-97), Vandermonde/RAID-6/Cauchy matrix generation
(:199,245,301), liberation-family bit-matrix preparation (:437-496).

Techniques: reed_sol_van, reed_sol_r6_op (bytewise matrix codes, w in
{8, 16, 32} over gf-complete's default polynomials), cauchy_orig,
cauchy_good (packet-interleaved bit-matrix codes, w in {8,16,32}),
liberation,
blaum_roth, liber8tion (native minimal-density GF(2) bit-matrices with
packetsize semantics — see ceph_tpu.ec.liberation for the constructions
and the liber8tion byte-compat caveat).

Round 6: both halves of the family carry the bit-planar layout contract
(ec/planar.py).  The matrix codes (reed_sol_*) pack chunks into w
bit-planes (``bitpack`` flavor) and their per-technique alignment rules
(k*w*4-byte multiples) already guarantee planar-compatible chunk sizes
for every w in {8, 16, 32}.  The packet-interleaved codes
(cauchy/liberation) ARE bit-planar natively — jerasure's w packets of
``packetsize`` bytes per super-block are packed bit-planes — so their
planar form is the packet-row matrix (``packet`` flavor) and no
second-level packing is applied.  Since PR 48 a w = 8 cauchy pool also
RESTS in that form (``ec/planar_store.py``: ``packet8.<packetsize>``)
and multiplies through the product's planar kernel.
"""

from __future__ import annotations

import errno

import numpy as np

from ceph_tpu.ec import liberation as libmod
from ceph_tpu.ec import matrices
from ceph_tpu.ec.codec import BitmatrixCodec, MatrixCodec, _DeviceBitEngine
from ceph_tpu.ec.interface import ECError, ErasureCodeProfile

LARGEST_VECTOR_WORDSIZE = 16

TECHNIQUES = (
    "reed_sol_van",
    "reed_sol_r6_op",
    "cauchy_orig",
    "cauchy_good",
    "liberation",
    "blaum_roth",
    "liber8tion",
)


class ErasureCodeJerasure(MatrixCodec):
    DEFAULT_K = "2"
    DEFAULT_M = "1"
    DEFAULT_W = "8"

    def __init__(self, technique: str):
        super().__init__()
        self.technique = technique
        self.per_chunk_alignment = False

    def parse(self, profile: ErasureCodeProfile) -> None:
        super().parse(profile)
        self.k = self.to_int("k", profile, self.DEFAULT_K)
        self.m = self.to_int("m", profile, self.DEFAULT_M)
        self.w = self.to_int("w", profile, self.DEFAULT_W)
        if self.chunk_mapping and len(self.chunk_mapping) != self.k + self.m:
            self.chunk_mapping = []
            raise ECError(errno.EINVAL, "bad mapping size")
        self.sanity_check_k(self.k)

    def get_alignment(self) -> int:
        if self.per_chunk_alignment:
            return self.w * LARGEST_VECTOR_WORDSIZE
        alignment = self.k * self.w * 4
        if (self.w * 4) % LARGEST_VECTOR_WORDSIZE:
            alignment = self.k * self.w * LARGEST_VECTOR_WORDSIZE
        return alignment

    def get_chunk_size(self, object_size: int) -> int:
        # reference ErasureCodeJerasure.cc:74-97
        alignment = self.get_alignment()
        if self.per_chunk_alignment:
            chunk_size = (object_size + self.k - 1) // self.k
            modulo = chunk_size % alignment
            if modulo:
                chunk_size += alignment - modulo
            return chunk_size
        tail = object_size % alignment
        padded = object_size + (alignment - tail if tail else 0)
        assert padded % self.k == 0
        return padded // self.k


class ReedSolomonVandermonde(ErasureCodeJerasure):
    def __init__(self):
        super().__init__("reed_sol_van")

    def parse(self, profile: ErasureCodeProfile) -> None:
        super().parse(profile)
        if self.w not in (8, 16, 32):
            profile["w"] = "8"
            self.w = 8
            raise ECError(errno.EINVAL, "w must be in {8, 16, 32}")
        self.per_chunk_alignment = self.to_bool(
            "jerasure-per-chunk-alignment", profile, "false"
        )

    def build_coding_matrix(self) -> np.ndarray:
        if self.w == 8:
            return matrices.reed_sol_vandermonde_coding_matrix(self.k, self.m)
        return matrices.reed_sol_vandermonde_coding_matrix_w(
            self.k, self.m, self.w)


class ReedSolomonRAID6(ErasureCodeJerasure):
    def __init__(self):
        super().__init__("reed_sol_r6_op")

    def parse(self, profile: ErasureCodeProfile) -> None:
        super().parse(profile)
        profile.pop("m", None)
        self.m = 2
        if self.w not in (8, 16, 32):
            profile["w"] = "8"
            self.w = 8
            raise ECError(errno.EINVAL, "w must be in {8, 16, 32}")

    def build_coding_matrix(self) -> np.ndarray:
        if self.w == 8:
            return matrices.reed_sol_r6_coding_matrix(self.k)
        return matrices.reed_sol_r6_coding_matrix_w(self.k, self.w)


class Cauchy(BitmatrixCodec, ErasureCodeJerasure):
    DEFAULT_PACKETSIZE = "2048"
    variant = "orig"

    def __init__(self):
        ErasureCodeJerasure.__init__(self, f"cauchy_{self.variant}")
        self.packetsize = 2048

    def parse(self, profile: ErasureCodeProfile) -> None:
        ErasureCodeJerasure.parse(self, profile)
        self.packetsize = self.to_int("packetsize", profile, self.DEFAULT_PACKETSIZE)
        self.per_chunk_alignment = self.to_bool(
            "jerasure-per-chunk-alignment", profile, "false"
        )
        if self.w not in (8, 16, 32):
            raise ECError(errno.EINVAL,
                          "tpu cauchy supports w in {8, 16, 32}")
        if self.packetsize <= 0 or self.packetsize % 4:
            raise ECError(errno.EINVAL, "packetsize must be a positive multiple of 4")

    def get_alignment(self) -> int:
        # reference ErasureCodeJerasureCauchy::get_alignment
        if self.per_chunk_alignment:
            alignment = self.w * self.packetsize
            modulo = alignment % LARGEST_VECTOR_WORDSIZE
            if modulo:
                alignment += LARGEST_VECTOR_WORDSIZE - modulo
            return alignment
        alignment = self.k * self.w * self.packetsize * 4
        if (self.w * self.packetsize * 4) % LARGEST_VECTOR_WORDSIZE:
            alignment = self.k * self.w * self.packetsize * LARGEST_VECTOR_WORDSIZE
        return alignment

    get_chunk_size = ErasureCodeJerasure.get_chunk_size


class CauchyOrig(Cauchy):
    variant = "orig"

    def build_coding_matrix(self) -> np.ndarray:
        if self.w == 8:
            return matrices.cauchy_original_coding_matrix(self.k, self.m)
        return matrices.cauchy_original_coding_matrix_w(
            self.k, self.m, self.w)


class CauchyGood(Cauchy):
    variant = "good"

    def build_coding_matrix(self) -> np.ndarray:
        if self.w == 8:
            return matrices.cauchy_good_coding_matrix(self.k, self.m)
        return matrices.cauchy_good_coding_matrix_w(self.k, self.m, self.w)


def _is_prime(n: int) -> bool:
    if n < 2:
        return False
    return all(n % d for d in range(2, int(n ** 0.5) + 1))


class Liberation(BitmatrixCodec, ErasureCodeJerasure):
    """Native minimal-density bit-matrix RAID-6 (m=2) code with packetsize
    semantics (reference ErasureCodeJerasureLiberation,
    ErasureCodeJerasure.cc:353-441; bit-matrix from ceph_tpu.ec.liberation).
    """

    DEFAULT_PACKETSIZE = "2048"
    technique_name = "liberation"

    def __init__(self):
        ErasureCodeJerasure.__init__(self, self.technique_name)
        self.DEFAULT_K = "2"
        self.DEFAULT_M = "2"
        self.DEFAULT_W = "7"
        self.packetsize = 0
        self.bit_engine: _DeviceBitEngine = None

    def parse(self, profile: ErasureCodeProfile) -> None:
        ErasureCodeJerasure.parse(self, profile)
        profile.pop("m", None)
        self.m = 2
        self.packetsize = self.to_int(
            "packetsize", profile, self.DEFAULT_PACKETSIZE)
        if not self.check_k():
            raise ECError(errno.EINVAL,
                          f"k={self.k} must be <= w={self.w}")
        if not self.check_w():
            raise ECError(errno.EINVAL,
                          f"w={self.w} must be greater than two and be prime")
        if self.packetsize <= 0 or self.packetsize % 4:
            raise ECError(errno.EINVAL,
                          "packetsize must be a positive multiple of 4")

    def check_k(self) -> bool:
        return self.k <= self.w

    def check_w(self) -> bool:
        # reference ErasureCodeJerasureLiberation::check_w (:371-379)
        return self.w > 2 and _is_prime(self.w)

    def get_alignment(self) -> int:
        # reference ErasureCodeJerasureLiberation::get_alignment (:353-359)
        alignment = self.k * self.w * self.packetsize * 4
        if (self.w * self.packetsize * 4) % LARGEST_VECTOR_WORDSIZE:
            alignment = self.k * self.w * self.packetsize * \
                LARGEST_VECTOR_WORDSIZE
        return alignment

    get_chunk_size = ErasureCodeJerasure.get_chunk_size

    def build_bitmatrix(self) -> np.ndarray:
        return libmod.liberation_coding_bitmatrix(self.k, self.w)

    def prepare(self) -> None:
        self.bit_engine = _DeviceBitEngine(
            self.k, self.m, self.w, self.build_bitmatrix())

    def _encode_bits(self) -> np.ndarray:
        return self.bit_engine.coding_bits

    def _decode_bits(self, src, out) -> np.ndarray:
        return self.bit_engine.decode_bits(tuple(src), tuple(out))


class BlaumRoth(Liberation):
    technique_name = "blaum_roth"

    def check_w(self) -> bool:
        # reference tolerates w=7 for backward compatibility
        # (ErasureCodeJerasure.cc:446-459)
        if self.w == 7:
            return True
        return self.w > 2 and _is_prime(self.w + 1)

    def build_bitmatrix(self) -> np.ndarray:
        return libmod.blaum_roth_coding_bitmatrix(self.k, self.w)


class Liber8tion(Liberation):
    technique_name = "liber8tion"

    def __init__(self):
        super().__init__()
        self.DEFAULT_W = "8"

    def parse(self, profile: ErasureCodeProfile) -> None:
        # reference Liber8tion::parse pins m=2, w=8 (:470-490)
        profile.pop("w", None)
        super().parse(profile)

    def check_w(self) -> bool:
        return self.w == 8

    def build_bitmatrix(self) -> np.ndarray:
        return libmod.liber8tion_coding_bitmatrix(self.k)


def make_jerasure(profile: ErasureCodeProfile):
    """Technique dispatch (reference ErasureCodePluginJerasure.cc:42-56)."""
    technique = profile.get("technique", "reed_sol_van")
    table = {
        "reed_sol_van": ReedSolomonVandermonde,
        "reed_sol_r6_op": ReedSolomonRAID6,
        "cauchy_orig": CauchyOrig,
        "cauchy_good": CauchyGood,
        "liberation": Liberation,
        "blaum_roth": BlaumRoth,
        "liber8tion": Liber8tion,
    }
    if technique not in TECHNIQUES:
        raise ECError(errno.ENOENT, f"unknown technique {technique}")
    codec = table[technique]()
    codec.init(profile)
    return codec
