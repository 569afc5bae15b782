"""Peaks of the chips the benchmark may run on, and the functions that
compute a kernel's operations and bytes from its shapes.  A device that is
not in the table is an error, never a default."""

from __future__ import annotations

from typing import Dict, Tuple

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in the "
                       f"benchmark's peaks table {sorted(PEAKS)}") from None


def planar_matmul_cost(config: dict, input_bytes: float
                       ) -> Tuple[float, float]:
    """(int8 operations, HBM bytes) the planar GF(2) ENCODE matmul needs
    for ``input_bytes`` of input planes.

    The input is a (kw, npk) uint8 array of packed bit-planes, kw = 8*k
    bit-rows, each byte holding 8 bit-columns; the bit-matrix is
    (rw, kw), rw = 8*m.  The product has rw x (8*npk) one-bit outputs of
    kw multiply-adds each: 2*rw*kw*8*npk = 2*rw*8*input_bytes operations.
    It must read the input once and write rw/kw of it: input_bytes *
    (kw + rw) / kw bytes.  Operations the MXU spends on the K-stacked
    block-diagonal zeros are not needed by the algorithm and not counted.
    """
    w = int(config["gf_word_bits"])
    kw, rw = w * int(config["k"]), w * int(config["m"])
    ops = 2.0 * rw * 8 * input_bytes
    moved = input_bytes * (kw + rw) / kw
    return ops, moved


def planar_matmul_decode_cost(config: dict, input_bytes: float
                              ) -> Tuple[float, float]:
    """(int8 operations, HBM bytes) the planar GF(2) DECODE matmul needs
    to rebuild ONE lost chunk from ``input_bytes`` of source planes.

    The input is a (kw, npk) array of the planes of the k chunks the
    decode reads, kw = 8*k bit-rows; the recovery bit-matrix is (w, kw):
    the w bit-rows of the one chunk rebuilt.  2*w*kw*8*npk =
    2*w*8*input_bytes operations; read the input once and write 1/k of
    it: input_bytes * (k + 1) / k bytes.  It counts what the algorithm
    needs, not what a kernel does, so that a later kernel is read on the
    same work.  (A code that rebuilds from fewer than k chunks needs a
    cost function of its own: SHEC, LRC's local groups.)
    """
    w, k = int(config["gf_word_bits"]), int(config["k"])
    ops = 2.0 * w * 8 * input_bytes
    moved = input_bytes * (k + 1) / k
    return ops, moved


COST_FUNCTIONS = {"planar_matmul_encode": planar_matmul_cost,
                  "planar_matmul_decode": planar_matmul_decode_cost}


def roofline_share(device_kind: str, ops: float, moved_bytes: float,
                   kernel_s: float) -> Tuple[float, str]:
    """(per cent of the roofline, which bound applies): the least time
    the chip could take over the time the kernel took."""
    pk = peaks_for(device_kind)
    t_ops = ops / pk["int8_ops"]
    t_mem = moved_bytes / pk["hbm_bytes_per_s"]
    bound = "hbm" if t_mem >= t_ops else "int8"
    return 100.0 * max(t_ops, t_mem) / kernel_s, bound
