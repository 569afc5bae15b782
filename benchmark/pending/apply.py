#!/usr/bin/env python
"""Make the cells under ``benchmark/pending/`` cells of ``BENCHMARK.json``.

    python benchmark/pending/apply.py [--root DIR]

A pending file holds the ``workloads``, ``end_to_end`` and ``per_layer``
entries of a cell whose files are all there and whose proof on the chip is
made, and which the PR that built it could not list: listing a cell fails
tier-1 tests that pin the list, and a ``benchmark`` PR touches no test.
This appends each pending file's entries to ``BENCHMARK.json``'s lists
(nothing that is there changes) and removes the pending file.  The PR that
runs it updates the tests the pending file names.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
KEYS = ("workloads", "end_to_end", "per_layer")


def merged(spec: dict, pending: dict) -> dict:
    """``spec`` with the pending entries appended to its three lists; a
    name that is already there is an error."""
    out = dict(spec)
    for key in KEYS:
        names = {e["name"] for e in spec[key]}
        twice = [e["name"] for e in pending[key] if e["name"] in names]
        if twice:
            raise ValueError(f"{key}: {twice} are entries already")
        out[key] = spec[key] + pending[key]
    return out


def pending_files(here: str = HERE):
    return sorted(glob.glob(os.path.join(here, "*.json")))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(HERE)),
                    help="the directory that holds BENCHMARK.json")
    ap.add_argument("--keep", action="store_true",
                    help="leave the pending files where they are")
    args = ap.parse_args(argv)
    path = os.path.join(args.root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        spec = json.load(f)
    for name in pending_files():
        with open(name, encoding="utf-8") as f:
            spec = merged(spec, json.load(f))
        if not args.keep:
            os.remove(name)
        print(f"applied {os.path.basename(name)}")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(spec, f, indent=1)
        f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
