#!/usr/bin/env python
"""By hand: what a 4 MiB k=2 m=1 write's large blocks cost the host in
first touches, on an idle host, one thread, no cluster and no chip.

    chiprun -- python benchmark/host_touch.py [--ops 300]

One "op" is the allocation pattern of the served path (PERF.md section 5):
a received op frame ``bytearray(4 MiB)`` and two sub-write frames
``bytearray(2 MiB)``, each filled by slice assignment from ``bytes`` (what
``recv_into`` does to them), then three 2 MiB shard copies that the store
KEEPS; the frames are dropped.  Four regimes, each in a process of its own
(``mallopt`` and the heap's state are the process's):

    plain       glibc as it comes
    kept        mallopt(M_MMAP_THRESHOLD, 1 GiB) + mallopt(M_TRIM_THRESHOLD,
                1 GiB): blocks freed stay in the heap
    arena       the store's copies go into ONE private anonymous mmap of
                ops x 6 MiB, untouched
    populated   the same after all of it was populated: by
                madvise(MADV_POPULATE_WRITE), or, where the host's kernel
                refuses that (EINVAL), by libc's memset through ctypes

Times alloc, fill and store apart, in ms an op; then the populate's own ms
a MiB, and whether a populate on a helper thread lets the main thread run
Python (``mmap.madvise``, ``madvise`` and ``memset`` through ctypes).
It reads the host (``MemTotal``, cores, huge-page policy) first: the
stores' sizes in ``configs/`` were set against that ``MemTotal``.
"""

from __future__ import annotations

import argparse
import ctypes
import json
import mmap
import os
import platform
import resource
import subprocess
import sys
import threading
import time

MIB = 1 << 20
REGIMES = ("plain", "kept", "arena", "populated")
MADV_POPULATE_WRITE = 23        # Linux 5.14
M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3


def say(**row) -> None:
    print(json.dumps(row), flush=True)


def meminfo() -> dict:
    """/proc/meminfo as {name: bytes}."""
    out = {}
    with open("/proc/meminfo", encoding="ascii") as f:
        for line in f:
            name, _, rest = line.partition(":")
            parts = rest.split()
            if parts:
                out[name] = int(parts[0]) * (1024 if parts[1:] == ["kB"]
                                             else 1)
    return out


def _read(path: str) -> str:
    try:
        with open(path, encoding="ascii") as f:
            return f.read().strip()
    except OSError as exc:
        return repr(exc)


def host() -> None:
    mem = meminfo()
    say(host=platform.node(), kernel=platform.release(),
        libc=platform.libc_ver(), python=platform.python_version(),
        cores=os.cpu_count(), cores_usable=len(os.sched_getaffinity(0)),
        MemTotal_GiB=mem["MemTotal"] / 2**30,
        MemAvailable_GiB=mem["MemAvailable"] / 2**30,
        thp_enabled=_read("/sys/kernel/mm/transparent_hugepage/enabled"),
        thp_defrag=_read("/sys/kernel/mm/transparent_hugepage/defrag"))


def _anon(n: int) -> mmap.mmap:
    return mmap.mmap(-1, n, flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS)


def _libc():
    libc = ctypes.CDLL(None, use_errno=True)
    libc.madvise.argtypes = (ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int)
    libc.memset.argtypes = (ctypes.c_void_p, ctypes.c_int, ctypes.c_size_t)
    libc.memset.restype = ctypes.c_void_p
    return libc


def _address(m: mmap.mmap) -> int:
    return ctypes.addressof(ctypes.c_char.from_buffer(m))


def populate(m: mmap.mmap) -> str:
    """Fault every page of ``m`` in for writing; returns by what."""
    try:
        m.madvise(MADV_POPULATE_WRITE)
        return "MADV_POPULATE_WRITE"
    except OSError as exc:
        _libc().memset(_address(m), 0, len(m))
        return f"memset ({exc!r} from MADV_POPULATE_WRITE)"


def regime(name: str, ops: int) -> None:
    libc = _libc()
    if name == "kept":
        ok = (libc.mallopt(M_MMAP_THRESHOLD, 1 << 30),
              libc.mallopt(M_TRIM_THRESHOLD, 1 << 30))
        say(regime=name, mallopt_ok=ok)
    payload = os.urandom(4 * MIB)
    shard = payload[:2 * MIB]
    arena = view = None
    populate_ms_per_mib = populated_by = None
    if name in ("arena", "populated"):
        arena = _anon(ops * 6 * MIB)
        if name == "populated":
            t0 = time.perf_counter()
            populated_by = populate(arena)
            populate_ms_per_mib = (time.perf_counter() - t0) * 1e3 \
                / (ops * 6)
        view = memoryview(arena)
    kept = []
    t_alloc = t_fill = t_store = 0.0
    faults0 = _minflt()
    clock = time.perf_counter
    for i in range(ops):
        t0 = clock()
        frames = (bytearray(4 * MIB), bytearray(2 * MIB),
                  bytearray(2 * MIB))
        t1 = clock()
        frames[0][:] = payload
        frames[1][:] = shard
        frames[2][:] = shard
        t2 = clock()
        sources = (memoryview(frames[0])[:2 * MIB], frames[1], frames[2])
        if arena is None:
            kept.append([bytearray(s) for s in sources])
        else:
            off = i * 6 * MIB
            for j, s in enumerate(sources):
                view[off + j * 2 * MIB:off + (j + 1) * 2 * MIB] = s
        t3 = clock()
        del frames, sources
        t_alloc += t1 - t0
        t_fill += t2 - t1
        t_store += t3 - t2
    say(regime=name, ops=ops,
        alloc_ms_per_op=t_alloc * 1e3 / ops,
        fill_8mib_ms_per_op=t_fill * 1e3 / ops,
        store_6mib_ms_per_op=t_store * 1e3 / ops,
        total_ms_per_op=(t_alloc + t_fill + t_store) * 1e3 / ops,
        minor_faults_per_op=(_minflt() - faults0) / ops,
        populate_ms_per_mib=populate_ms_per_mib, populated_by=populated_by)


def _minflt() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


def populate_beside_python(mib: int) -> None:
    """Does a populate on a helper thread hold the GIL?  The main thread
    counts loop iterations while the helper populates ``mib`` MiB:
    through ``mmap.madvise``, through libc's ``madvise`` and through
    libc's ``memset``.  A way the kernel refuses says so in its row."""
    libc = _libc()

    def spin(until: threading.Event) -> int:
        n = 0
        while not until.is_set():
            n += 1
        return n

    t0 = time.perf_counter()
    stop = threading.Event()
    threading.Timer(0.3, stop.set).start()
    alone = spin(stop) / (time.perf_counter() - t0)

    def via_mmap(m):
        m.madvise(MADV_POPULATE_WRITE)

    def via_libc(m):
        if libc.madvise(_address(m), len(m), MADV_POPULATE_WRITE) != 0:
            raise OSError(ctypes.get_errno(), "madvise")

    def via_memset(m):
        libc.memset(_address(m), 0, len(m))

    for label, fn in (("mmap.madvise", via_mmap),
                      ("ctypes madvise", via_libc),
                      ("ctypes memset", via_memset)):
        m = _anon(mib * MIB)
        done = threading.Event()
        took, refused = [], []

        def helper():
            t = time.perf_counter()
            try:
                fn(m)
            except OSError as exc:
                refused.append(repr(exc))
            finally:
                took.append(time.perf_counter() - t)
                done.set()

        th = threading.Thread(target=helper)
        t0 = time.perf_counter()
        th.start()
        n = spin(done)
        wall = time.perf_counter() - t0
        th.join()
        say(populate_on_helper=label, mib=mib, refused=refused or None,
            populate_ms_per_mib=took[0] * 1e3 / mib,
            main_thread_iterations_per_s=n / wall,
            share_of_alone=n / wall / alone)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ops", type=int, default=300)
    ap.add_argument("--regime", choices=REGIMES + ("helper",), default=None,
                    help="run one regime in this process (the parent "
                         "starts one process a regime)")
    args = ap.parse_args(argv)
    if args.regime == "helper":
        populate_beside_python(1024)
        return 0
    if args.regime:
        regime(args.regime, args.ops)
        return 0
    host()
    rc = 0
    for name in REGIMES + ("helper",):
        rc |= subprocess.run([sys.executable, os.path.abspath(__file__),
                              "--regime", name, "--ops", str(args.ops)],
                             timeout=600).returncode
    say(MemAvailable_GiB_after=meminfo()["MemAvailable"] / 2**30)
    return rc


if __name__ == "__main__":
    sys.exit(main())
