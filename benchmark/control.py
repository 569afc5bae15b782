#!/usr/bin/env python
"""The control of ``correct``: the benchmark's own run with ONE guarantee
of the configuration broken underneath it, which has to come out as not
correct.

    python benchmark/control.py --workload <name> --seed <n> --seconds <s>

The guarantee broken: "parity and reconstruction are computed exactly".
One coefficient bit of every GF(2) bit-matrix handed to the planar
kernel's entry point (``ceph_tpu.ops.gf8.planar_matmul``) is flipped: the
step a wrong table, a wrong stacking or a lossy kernel would take.  The
code is systematic, so healthy reads still return the client's bytes; only
the degraded sample (one shard holder down, the object rebuilt from wrong
parity or by a wrong decode) can see it.  The benchmark's own runs never
run this; ``benchmark/prove.py --control`` does, on the chip, at the cell's
own size.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def break_parity(say=None) -> None:
    """Flip bit-matrix entry (0, 0) in every planar matmul from now on."""
    import numpy as np

    from ceph_tpu.ops import gf8

    sound = gf8.planar_matmul

    def planar_matmul_one_bit_off(bitmat, planes):
        wrong = np.array(bitmat, copy=True)
        wrong[0, 0] ^= 1
        return sound(wrong, planes)

    gf8.planar_matmul = planar_matmul_one_bit_off
    if say is not None:
        say(control="bit (0, 0) of every planar bit-matrix is flipped")


if __name__ == "__main__":
    from benchmark import run

    sys.exit(run.main(before_run=break_parity))
