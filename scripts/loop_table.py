#!/usr/bin/env python
"""Who ran on the loop, row by row: the benchmark's own run of one cell,
and then the loop account's table for its measured window.

    python scripts/loop_table.py --workload <name> --seed <n> \
        --seconds <s> [--trace 1] [--rows 10] [--keep <file.json>]

Runs ``benchmark/run.py`` unchanged, in this process.  The harness reads
``KERNELS`` (``harness.cell.kernel_counters``) at the window's two edges,
among other moments, and prints what the counters grew by between them
(``window_counters``); this tool keeps the account's table (admin
command ``dump_loop_account``) beside every such read and prints, after
the run's result line, what the table grew by between the two reads
whose difference is the growth the harness printed
(``loopacct.window``): own time by bucket in ms an op, the heaviest rows
with us of own time a handle and handles an op, and the observer's
floor.  Its last line is all of that as one JSON object; ``--keep``
writes every row there.  The driver never runs this file; it is a
by-hand tool like ``benchmark/gaps.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class WindowTable:
    """While it is open, every read of ``KERNELS`` the harness makes
    leaves (the counters, the account's table) here, and ``say``, put
    in the place of the run's own, keeps the window's growth as the
    harness prints it; ``table`` is then the table's growth over that
    window."""

    def __init__(self, say):
        self._say = say
        self.taken = []
        self.grew = None

    def __enter__(self):
        from benchmark.harness import cell as cellmod

        self._cellmod = cellmod
        self._counters = counters = cellmod.kernel_counters

        def counters_and_table():
            from ceph_tpu.trace import loopacct

            got = counters()
            if loopacct.ACCOUNT is not None:
                self.taken.append((got, loopacct.ACCOUNT.dump()))
            return got

        cellmod.kernel_counters = counters_and_table
        return self

    def __exit__(self, *exc):
        self._cellmod.kernel_counters = self._counters

    def say(self, **row) -> None:
        if "window_counters" in row:
            self.grew = row["window_counters"]
        self._say(**row)

    def table(self) -> dict:
        """The account's table over the harness's window, per op: a
        dump's rows are unscaled, ``every`` turns stand behind a timed
        one."""
        from ceph_tpu.trace import loopacct

        edges = [(before, after)
                 for j, (c1, after) in enumerate(self.taken)
                 for c0, before in self.taken[:j]
                 if self._cellmod.grew(c1, c0) == self.grew]
        if self.grew is None or len(edges) != 1:
            raise RuntimeError(
                f"loop_table: {len(edges)} pairs of the harness's "
                f"{len(self.taken)} reads of KERNELS grew by its "
                f"window_counters (wanted 1): has the harness changed?")
        grown = loopacct.window(edges[0][1], edges[0][0])
        ops, every = self.grew["ec_coalesced_ops"], grown["every"]
        for r in grown["rows"]:
            r["own_us_per_handle"] = r["own_ns"] / r["handles"] / 1e3
            r["handles_per_op"] = r["handles"] * every / ops
            r["own_ms_per_op"] = r["own_ns"] * every / ops / 1e6
        return {"every": every, "ops": ops,
                "own_ms_per_op": {b: own * every / ops / 1e6
                                  for b, own in grown["own_ns"].items()},
                "floor_us": grown["floor_ns"] and grown["floor_ns"] / 1e3,
                "rows": grown["rows"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=10)
    ap.add_argument("--keep")
    args, run_argv = ap.parse_known_args(argv)

    from benchmark import run as benchrun

    with WindowTable(benchrun.say) as taken:
        benchrun.say = taken.say
        try:
            rc = benchrun.main(run_argv)
        finally:
            benchrun.say = taken._say
    if rc:
        return rc
    table = taken.table()
    for r in table["rows"][:args.rows]:
        print(f'{r["bucket"]:10s} {r["name"][:44]:44s} {r["msg"][:28]:28s} '
              f'{r["own_us_per_handle"]:9.1f} us/handle '
              f'{r["handles_per_op"]:8.2f} handles/op '
              f'{r["own_ms_per_op"]:7.3f} ms/op')
    if args.keep:
        os.makedirs(os.path.dirname(os.path.abspath(args.keep)),
                    exist_ok=True)
        with open(args.keep, "w", encoding="utf-8") as f:
            json.dump(table, f, indent=1)
    table["rows"] = table["rows"][:args.rows]
    print(json.dumps({"loop_table": table}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
