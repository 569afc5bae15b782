"""GF(2^8) arithmetic as TPU tensor ops.

Behavioral reference: the Galois-field kernels the reference's erasure-code
plugins call into — gf-complete/jerasure ``galois_w08_region_multiply`` /
``jerasure_matrix_encode`` (see reference src/erasure-code/jerasure/
ErasureCodeJerasure.cc:156,164) and ISA-L ``gf_mul``/``gf_inv``/
``ec_encode_data`` (reference src/erasure-code/isa/ErasureCodeIsa.cc:128,
274-305).  Both libraries use GF(2^8) with the primitive polynomial
x^8+x^4+x^3+x^2+1 (0x11d), so one substrate serves every codec family.

TPU-first design
----------------
The hot operation is the "GF matmul": ``C[i, n] = XOR_j gfmul(M[i, j], D[j, n])``
over megabytes of ``D``.  CPU libraries do this with PSHUFB nibble tables
(ISA-L) or log/antilog lookups (jerasure).  Neither maps to the MXU.  Instead
we use the fact that multiplication by a *constant* ``a`` is GF(2)-linear:
there is an 8x8 bit-matrix ``B_a`` with ``bits(a*x) = B_a @ bits(x) (mod 2)``.
Expanding every byte of the coding matrix this way turns the whole encode into
ONE dense GF(2) matmul:

    (8m x 8k bit-matrix) @ (8k x N bit-expanded data)  ->  mod 2  ->  pack

which the MXU executes as an int8 matmul followed by a parity mask.  The same
path serves decode (with an inverted matrix) and the bit-matrix codes
(cauchy/liberation families) natively — they *are* GF(2) matmuls.

Host-side helpers (table construction, matrix inversion for decode) are plain
numpy: they touch k x k bytes, not data.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# x^8 + x^4 + x^3 + x^2 + 1 — the polynomial shared by gf-complete (octal 0435,
# jerasure galois.c) and ISA-L (erasure_code tables).
GF_POLY = 0x11D


def _build_tables():
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[255:510] = exp[0:255]
    return exp, log


GF_EXP, GF_LOG = _build_tables()


def _build_mul_table():
    a = np.arange(256)
    la = GF_LOG[a][:, None]
    lb = GF_LOG[a][None, :]
    prod = GF_EXP[(la + lb) % 255]
    prod[0, :] = 0
    prod[:, 0] = 0
    return prod.astype(np.uint8)


# Full 256x256 product table; 64 KiB, host-resident.
GF_MUL = _build_mul_table()


def gf_mul(a, b):
    """Elementwise GF(2^8) product (numpy, host)."""
    return GF_MUL[np.asarray(a, dtype=np.uint8), np.asarray(b, dtype=np.uint8)]


def gf_inv(a):
    """Multiplicative inverse; a must be nonzero."""
    a = np.asarray(a, dtype=np.uint8)
    if np.any(a == 0):
        raise ZeroDivisionError("gf_inv(0)")
    return GF_EXP[255 - GF_LOG[a]]


def gf_div(a, b):
    return gf_mul(a, gf_inv(b))


def gf_pow(a, n):
    """a**n in GF(2^8)."""
    a = int(a)
    n = int(n)
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(GF_EXP[(GF_LOG[a] * n) % 255])


def gf_matmul_ref(m, d):
    """Reference bytewise GF matmul on host numpy: (r,k) @ (k,n) -> (r,n).

    out[i, n] = XOR_j gfmul(m[i, j], d[j, n]).  Used as the correctness oracle
    for the device path and for tiny host-side work.
    """
    m = np.asarray(m, dtype=np.uint8)
    d = np.asarray(d, dtype=np.uint8)
    prod = GF_MUL[m[:, :, None], d[None, :, :]]
    return np.bitwise_xor.reduce(prod, axis=1)


# ---------------------------------------------------------------------------
# Bit-matrix machinery
# ---------------------------------------------------------------------------

def _build_bitmat_table():
    """BITMAT[a] is the 8x8 GF(2) matrix of multiply-by-a, LSB-first.

    BITMAT[a][t, u] = bit t of gfmul(a, 1 << u).
    """
    a = np.arange(256, dtype=np.uint8)
    basis = (1 << np.arange(8)).astype(np.uint8)          # columns: a * 2^u
    prods = GF_MUL[a[:, None], basis[None, :]]            # (256, 8)
    bits = (prods[:, None, :] >> np.arange(8)[None, :, None]) & 1  # (256, t, u)
    return bits.astype(np.uint8)


GF_BITMAT = _build_bitmat_table()


def expand_bitmatrix(m):
    """Expand a byte matrix (r, k) into its (8r, 8k) GF(2) bit-matrix.

    Block (i, j) is the multiply-by-``m[i, j]`` matrix, so that
    ``bitmatrix @ bits(d) == bits(m @gf d)`` columnwise.  This is the same
    construction jerasure's ``jerasure_matrix_to_bitmatrix`` performs for the
    cauchy/liberation code families (reference ErasureCodeJerasure.cc:301).
    """
    m = np.asarray(m, dtype=np.uint8)
    r, k = m.shape
    blocks = GF_BITMAT[m]                                 # (r, k, 8, 8)
    return blocks.transpose(0, 2, 1, 3).reshape(r * 8, k * 8)


@jax.jit
def unpack_bits(data):
    """(k, n) uint8 -> (8k, n) int8 of {0,1}, LSB-first within each byte."""
    k, n = data.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (data[:, None, :] >> shifts[None, :, None]) & jnp.uint8(1)
    return bits.reshape(k * 8, n).astype(jnp.int8)


@jax.jit
def pack_bits(bits):
    """(8r, n) {0,1} -> (r, n) uint8, LSB-first."""
    r8, n = bits.shape
    b = bits.reshape(r8 // 8, 8, n).astype(jnp.int32)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))[None, :, None]
    return jnp.sum(b * weights, axis=1).astype(jnp.uint8)


@jax.jit
def bitmatrix_matmul(bitmat, data):
    """Device GF matmul via one MXU int8 matmul.

    bitmat: (8r, 8k) {0,1} (from expand_bitmatrix, or a native bit-matrix
            code's matrix).
    data:   (k, n) uint8 — k source chunks of n bytes.
    returns (r, n) uint8 — r output chunks.
    """
    d_bits = unpack_bits(data)
    acc = jax.lax.dot_general(
        bitmat.astype(jnp.int8), d_bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return pack_bits(acc & 1)


def gf_matmul(m, data):
    """Convenience: device GF matmul from a byte matrix (host expand + jit)."""
    from ceph_tpu.utils.perf import KERNELS

    bitmat = jnp.asarray(expand_bitmatrix(m))
    data = jnp.asarray(data)
    KERNELS.inc("gf8_matmul_calls")
    KERNELS.inc("gf8_matmul_bytes", int(np.prod(data.shape)))
    return bitmatrix_matmul(bitmat, data)


# ---------------------------------------------------------------------------
# Bit-planar layout (round 6): the internal device format for EC batches
# ---------------------------------------------------------------------------
#
# A shard row of L bytes is stored as 8 PACKED bit-planes: plane t, packed
# byte i holds bit t of source bytes 8i..8i+7, with byte 8i+u at bit u.
# Rows are chunk-major — plane row j*8+t is bit-plane t of chunk j — which
# matches expand_bitmatrix's row blocks, so the planar GF(2) matmul uses
# the SAME bit-matrix as the byte path, no permutation.  Total size equals
# the byte layout (L bytes per chunk), so keeping batches planar costs no
# HBM capacity; what it buys is that encode/decode between conversions is
# a pure matmul — the per-call 8x {0,1} expansion and re-pack that
# dominated the round-5 HBM traffic (BENCH_NOTES.md) happens at most once
# per client op, at the host boundary.


@jax.jit
def bytes_to_planar(data):
    """(c, L) uint8 bytes -> (8c, L/8) packed bit-planes, chunk-major rows.

    planar[j*8 + t, i] bit u  ==  bit t of data[j, 8i + u].
    """
    c, l = data.shape
    nb = l // 8
    d = data.reshape(c, nb, 8)                               # (c, i, u)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (d[:, None, :, :] >> shifts[None, :, None, None]) & jnp.uint8(1)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))          # weight by u
    planes = jnp.sum(bits.astype(jnp.int32) * weights[None, None, None, :],
                     axis=3)                                 # (c, t, i)
    return planes.reshape(c * 8, nb).astype(jnp.uint8)


@jax.jit
def planar_to_bytes(planes):
    """(8c, nb) packed bit-planes -> (c, 8*nb) bytes (bytes_to_planar^-1)."""
    c8, nb = planes.shape
    c = c8 // 8
    p = planes.reshape(c, 8, nb)                             # (c, t, i)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (p[:, :, :, None] >> shifts[None, None, None, :]) & jnp.uint8(1)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))          # weight by t
    by = jnp.sum(bits.astype(jnp.int32) * weights[None, :, None, None],
                 axis=1)                                     # (c, i, u)
    return by.reshape(c, nb * 8).astype(jnp.uint8)


@jax.jit
def planar_matmul_xla(bitmat, planes):
    """GF(2) matmul directly on packed bit-planes (XLA reference path).

    bitmat: (rw, kw) {0,1} bit-matrix (chunk-major blocks, any w).
    planes: (kw, nb) packed bit-planes; returns (rw, nb) packed planes.
    Bit-exact with the byte path: planar_to_bytes(out) ==
    pack_bits of bitmatrix_matmul on the corresponding byte data.
    """
    kw, nb = planes.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = ((planes[:, :, None] >> shifts[None, None, :]) & jnp.uint8(1))
    bits = bits.reshape(kw, nb * 8).astype(jnp.int8)
    acc = jax.lax.dot_general(
        bitmat.astype(jnp.int8), bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    a = (acc & 1).reshape(acc.shape[0], nb, 8)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))
    return jnp.sum(a * weights[None, None, :], axis=2).astype(jnp.uint8)


def planar_matmul(bitmat, planes):
    """Planar GF(2) matmul entry point: packed bit-planes in AND out.

    Routes to the fused, K-stacked Pallas kernel on real TPU backends
    (gf8_pallas.planar_matmul: block-diagonal matrix stacking feeds the
    MXU a >=128-wide K dimension and the {0,1} expansion lives in VMEM
    only) and to planar_matmul_xla elsewhere.  Both paths are bit-exact.
    Works for any word width w — the operand is bit-rows x packed
    columns, w only determines how the caller packed the planes.
    """
    from ceph_tpu.ops import gf8_pallas
    from ceph_tpu.ops.profiling import record_planar_matmul

    planes = jnp.asarray(planes)
    use_pallas = gf8_pallas.planar_available()
    record_planar_matmul(int(np.prod(planes.shape)),
                         gf8_pallas.stack_groups(int(bitmat.shape[1]))
                         if use_pallas else 1)
    if use_pallas:
        return gf8_pallas.planar_matmul(bitmat, planes)
    return planar_matmul_xla(jnp.asarray(bitmat), planes)


# ---------------------------------------------------------------------------
# Matrix inversion (decode-matrix construction; host, k x k bytes)
# ---------------------------------------------------------------------------

class SingularMatrixError(ValueError):
    pass


def gf_invert_matrix(a):
    """Gauss-Jordan inversion over GF(2^8).

    Behavioral equivalent of ISA-L's ``gf_invert_matrix`` used by the decode
    path (reference src/erasure-code/isa/ErasureCodeIsa.cc:274).  Raises
    SingularMatrixError when not invertible.
    """
    a = np.array(a, dtype=np.uint8, copy=True)
    n = a.shape[0]
    if a.shape != (n, n):
        raise ValueError("square matrix required")
    inv = np.eye(n, dtype=np.uint8)
    for col in range(n):
        pivot = None
        for row in range(col, n):
            if a[row, col] != 0:
                pivot = row
                break
        if pivot is None:
            raise SingularMatrixError(f"singular at column {col}")
        if pivot != col:
            a[[col, pivot]] = a[[pivot, col]]
            inv[[col, pivot]] = inv[[pivot, col]]
        scale = gf_inv(a[col, col])
        a[col] = gf_mul(a[col], scale)
        inv[col] = gf_mul(inv[col], scale)
        for row in range(n):
            if row != col and a[row, col] != 0:
                factor = a[row, col]
                a[row] ^= gf_mul(factor, a[col])
                inv[row] ^= gf_mul(factor, inv[col])
    return inv
