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
parity or by a wrong decode) can see it.  In a cell whose pool is degraded
in set-up the populated objects are encoded AND decoded by the broken
matrices, and the one bit can cancel: at k=2 m=1 encode and both decodes
are the same (8, 16) matrix, and a chunk 1 rebuilt from chunk 0 and a parity
that is wrong by chunk 0's first plane comes out right.  There a second bit,
(0, last), is flipped too: it reads the LAST source, so nothing the first
bit did to the first source can undo it, and every decoded read differs.
The benchmark's own runs never run this; ``benchmark/prove.py --control``
does, on the chip, at the cell's own size.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def break_parity(say=None, last_source_too: bool = False) -> None:
    """Flip bit-matrix entry (0, 0) in every planar matmul from now on,
    and entry (0, last) with it where ``last_source_too``."""
    import numpy as np

    from ceph_tpu.ops import gf8

    sound = gf8.planar_matmul
    bits = [(0, 0), (0, -1)] if last_source_too else [(0, 0)]

    def planar_matmul_one_bit_off(bitmat, planes):
        wrong = np.array(bitmat, copy=True)
        for bit in bits:
            wrong[bit] ^= 1
        return sound(wrong, planes)

    gf8.planar_matmul = planar_matmul_one_bit_off
    if say is not None:
        say(control=f"bits {bits} of every planar bit-matrix are flipped")


def main(argv=None) -> int:
    import argparse
    import functools

    from benchmark import run
    from benchmark.harness.loader import BenchmarkError, load_cell

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    name = ap.parse_known_args(argv)[0].workload
    try:
        set_up = load_cell(name).traffic.get("set_up", {}) if name else {}
    except BenchmarkError:
        set_up = {}     # run.main says what is wrong with the name
    return run.main(argv, before_run=functools.partial(
        break_parity,
        last_source_too=bool(set_up.get("kill_shard_holders"))))


if __name__ == "__main__":
    sys.exit(main())
