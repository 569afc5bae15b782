#!/usr/bin/env python
"""By hand: what each way of making a spare mapping ready costs, on an
idle host, no cluster and no chip (PERF.md section 5, "First touches off
the loop").

    chiprun -- python scripts/pool_touch.py [--mib 2] [--count 128]

ONE helper thread makes ``count`` private anonymous mappings of ``mib``
MiB ready, each way in turn: first with the main thread parked (the
helper's own ms a MiB), after which the main thread copies a shard into
each mapping, as ``MemStore._land`` does on a hit (the copy's ms a MiB
says in what state the way left the pages); then again while the main
thread runs Python (a counting loop that reads the clock): its speed
against its speed alone, and its longest gap (a held GIL or a held
address-space lock shows there).

    pieces    the product's way, ``store._make_spare``: the kernel's
              populate, ``store._POOL_PIECE`` a call, the pieces walked by
              the C function the refill thread builds (one foreign call a
              mapping; the Python loop where the host cannot build it)
    pieces_python  the same pieces from the Python loop (PR 45's walk:
              back at the GIL once a piece)
    populate  mmap.mmap(MAP_POPULATE): the whole mapping in one call
    memset    mmap.mmap lazy + ctypes.memset over all of it
    stride    mmap.mmap lazy + one byte a page written by numpy
    inline    no helper: the main thread maps MAP_POPULATE and copies at
              once (what a miss costs)

It measures the host, not the product: the numbers under load are the
benchmark's (``store_pool_touch_ms_per_op.write``, ``loop_store_ms_per_op
.write``).  The ways that were tried under load and dropped (through
``libc.mmap``, ``mlock``, ``/dev/zero``, two passes) are in CHANGES.md,
PR 45, with what each read.
"""

from __future__ import annotations

import argparse
import ctypes
import json
import mmap
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ceph_tpu.cluster.store import _make_spare, _native_walk  # noqa: E402

MIB = 1 << 20
LAZY = mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS
POPULATE = LAZY | getattr(mmap, "MAP_POPULATE", 0)


def populate(n):
    return memoryview(mmap.mmap(-1, n, flags=POPULATE))


def memset(n):
    block = mmap.mmap(-1, n, flags=LAZY)
    ctypes.memset(ctypes.addressof(ctypes.c_char.from_buffer(block)), 0, n)
    return memoryview(block)


def stride(n):
    # at least 512 writes whatever the length: numpy lets the GIL go
    # for an assignment of more than 500 elements, and holds it under
    block = mmap.mmap(-1, n, flags=LAZY)
    np.frombuffer(block, dtype=np.uint8)[
        ::min(mmap.PAGESIZE, max(n // 512, 1))] = 0
    return memoryview(block)


# beside "pieces", which ``main`` adds: it builds the C walk first
WAYS = {"pieces_python": _make_spare, "populate": populate,
        "memset": memset, "stride": stride}


def main_alone(seconds: float) -> float:
    """The main thread's counting loop with nothing beside it, in
    iterations a second (the loop of ``run_way``, statement for
    statement)."""
    never, gap, iters = threading.Event(), 0.0, 0
    t0 = last = time.perf_counter()
    while not never.is_set() and last - t0 < seconds:
        now = time.perf_counter()
        gap, last, iters = max(gap, now - last), now, iters + 1
    return iters / (last - t0)


def run_way(make, n: int, count: int, blob: bytes, alone: float) -> dict:
    """Twice: with the main thread parked (the helper's own ms a MiB)
    and with it spinning Python (what the helper costs it; the helper's
    calls then wait for the GIL, 5 ms at a time, and read long)."""
    out = {}
    for beside in (False, True):
        views, calls, done = [], [], threading.Event()

        def helper():
            for _ in range(count):
                t0 = time.perf_counter_ns()
                views.append(make(n))
                calls.append(time.perf_counter_ns() - t0)
            done.set()

        thread = threading.Thread(target=helper)
        gap, iters = 0.0, 0
        thread.start()
        t0 = last = time.perf_counter()
        if not beside:
            done.wait()
        while not done.is_set():
            now = time.perf_counter()
            gap, last, iters = max(gap, now - last), now, iters + 1
        wall = time.perf_counter() - t0
        thread.join()
        mib = n * count / MIB
        if beside:
            out.update(main_speed_vs_alone=iters / wall / alone,
                       main_longest_gap_ms=gap * 1e3)
            continue
        src = memoryview(blob)
        c0 = time.perf_counter_ns()
        for view in views:
            view[:] = src
        out.update(helper_ms_per_mib=sum(calls) * 1e-6 / mib,
                   helper_call_ms_max=max(calls) * 1e-6,
                   copy_ms_per_mib=(time.perf_counter_ns() - c0)
                   * 1e-6 / mib)
    return out


def run_inline(n: int, count: int, blob: bytes) -> dict:
    src, kept = memoryview(blob), []
    map_ns = copy_ns = 0
    for _ in range(count):
        t0 = time.perf_counter_ns()
        view = memoryview(mmap.mmap(-1, n, flags=POPULATE))
        t1 = time.perf_counter_ns()
        view[:] = src
        copy_ns += time.perf_counter_ns() - t1
        map_ns += t1 - t0
        kept.append(view)
    mib = n * count / MIB
    return {"map_ms_per_mib": map_ns * 1e-6 / mib,
            "copy_ms_per_mib": copy_ns * 1e-6 / mib}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mib", type=float, default=2.0,
                    help="length of a mapping (a k2m1 shard: 2)")
    ap.add_argument("--count", type=int, default=128)
    ap.add_argument("--rounds", type=int, default=2)
    args = ap.parse_args(argv)
    n = int(args.mib * MIB)
    blob = np.random.default_rng(45).integers(
        0, 256, n, dtype=np.uint8).tobytes()
    alone = main_alone(0.5)
    print(json.dumps({"mapping_bytes": n, "count": args.count,
                      "main_alone_iters_per_s": alone}), flush=True)
    walk = _native_walk()
    ways = {"pieces": lambda n: _make_spare(n, walk), **WAYS}
    for rnd in range(args.rounds):
        for name, make in ways.items():
            row = run_way(make, n, args.count, blob, alone)
            print(json.dumps({"round": rnd, "way": name, **{
                k: round(v, 3) for k, v in row.items()}}), flush=True)
        row = run_inline(n, args.count, blob)
        print(json.dumps({"round": rnd, "way": "inline", **{
            k: round(v, 3) for k, v in row.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
