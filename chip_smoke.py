#!/usr/bin/env python
"""Does the system still start on the chip?  The quickest proof.

    python chip_smoke.py               # one TPU chip
    python chip_smoke.py --multichip   # four chips: the mesh EC engine only

One process, every daemon inside it (a chip belongs to one process).  It
refuses to run without a TPU: the platform is checked before anything is
built, and there is no CPU fallback.  Phases, each of which raises on a
fault (non-zero exit, no result line):

1. device, versions and the compile-cache directory;
2. the kernels on the device, bit for bit: the K-stacked Pallas planar
   GF(2) matmul against the XLA planar path against the host engine
   (ragged tail included), device crc32c against the scalar crc, device
   CRUSH against ``crush/scalar.py``;
3. the served path through the normal entry points: ``start_cluster`` ->
   ``cluster.client()`` -> an erasure pool on the default profile ->
   ``write_full``/``read`` of seeded 4 MiB objects, 16 in flight (the
   upstream ``rados bench`` shape); read back healthy, with a shard
   holder down (decode on the device) and after recovery (re-encode on
   the device); then the ``KERNELS`` counters must show that the device
   engine, and within it the Pallas kernel, did the work;
4. the LRC k=4 m=2 l=3 pool (PR 37): a tick's flattened planar encode of
   seeded 4 MiB objects against the literal layer walk, shard crcs
   included, then phase 3's drill on 8 OSDs under the same counter
   rules, with every shard landing as planes and the degraded read and
   the recovery decoding in the plane domain;
5. the SHEC k=6 m=4 c=3 code (PR 44), whose k is not a power of two: a
   tick's planar encode of seeded 4 MiB objects (171 stripes in a
   bucket of 256, kw = 48: two stack groups) and a decode with chunk
   3's holder gone, from the three chunks the code names, against a
   scalar product with upstream's matrix.

With ``--multichip`` it runs only the four-chip mesh engine against the
single-device codec.  Wall times printed here are a smoke test's, not
benchmark numbers.  The last stdout line is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import numpy as np

MIB = 1 << 20
DEFAULT_EC_PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
                      "k": "2", "m": "1"}
ISA_K8M4 = {"plugin": "isa", "k": "8", "m": "4"}
LRC_K4M2L3 = {"plugin": "lrc", "k": "4", "m": "2", "l": "3"}
SHEC_K6M4C3 = {"plugin": "shec", "k": "6", "m": "4", "c": "3"}


def say(**row) -> None:
    print(json.dumps(row), flush=True)


def same(name: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        raise AssertionError(f"{name}: device result differs from reference")


# --------------------------------------------------------------- phase 1

def phase_device(cache_dir: str) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    say(phase="device", **device, jax=jax.__version__,
        jaxlib=jaxlib.__version__, libtpu=libtpu, numpy=np.__version__,
        compile_cache_dir=cache_dir)
    return device


# --------------------------------------------------------------- phase 2

def _planar_case(name: str, codec, src, want, nbytes: int, rng) -> None:
    """One bit-matrix, three engines, seeded planes with a ragged tail."""
    import jax.numpy as jnp

    from ceph_tpu.ec import planar_store
    from ceph_tpu.ec.stripe import _host_decode_matrix
    from ceph_tpu.ops import gf8, gf8_pallas

    eng = codec.engine
    if want is None:
        bitmat, gfmat = eng._enc_bitmat, eng.coding
    else:
        bitmat = eng.decode_bitmat(tuple(src), tuple(want))
        gfmat = _host_decode_matrix(codec, tuple(src), tuple(want))
    kw = int(bitmat.shape[1])
    tile = gf8_pallas._TILE_P
    # at least nbytes of planes; the +tile//2+8 keeps the column count off
    # a tile boundary so the XLA tail and the concat run too
    npk = -(-nbytes // kw // tile) * tile + tile // 2 + 8
    planes = rng.integers(0, 256, (kw, npk), dtype=np.uint8)
    dev = jnp.asarray(planes)
    pallas = np.asarray(gf8_pallas.planar_matmul(bitmat, dev))
    xla = np.asarray(gf8.planar_matmul_xla(jnp.asarray(bitmat), dev))
    host = planar_store.planar_matmul_host(gf8.expand_bitmatrix(gfmat),
                                           planes)
    same(f"{name}: pallas vs xla", pallas, xla)
    same(f"{name}: pallas vs host engine", pallas, host)
    say(check="planar_matmul", case=name, bitmat=list(bitmat.shape),
        stack_groups=gf8_pallas.stack_groups(kw), plane_bytes=planes.size,
        ragged_cols=npk % tile, ok=True)


def phase_kernels(seed: int, plane_mib: int = 16, crc_blocks: int = 4096,
                  crush_racks: int = 39, crush_inputs: int = 2048) -> None:
    from ceph_tpu.crush.mapper import TensorMapper
    from ceph_tpu.crush.scalar import ScalarMapper
    from ceph_tpu.crush.types import build_three_level
    from ceph_tpu.ec import factory
    from ceph_tpu.ec.stripe import _decode_src
    from ceph_tpu.ops import gf8_pallas
    from ceph_tpu.ops.crc32c import crc32c, crc32c_batch

    rng = np.random.default_rng(seed)
    # raises on a TPU whose compiler or runtime refuses the kernel
    if not gf8_pallas.planar_available():
        raise AssertionError("the Pallas planar kernel is not in use")
    nbytes = plane_mib * MIB
    isa = factory(dict(ISA_K8M4))
    rs = factory(dict(DEFAULT_EC_PROFILE))
    _planar_case("isa_k8m4_encode", isa, None, None, nbytes, rng)
    _planar_case("jerasure_rsvan_k2m1_encode", rs, None, None, nbytes, rng)
    lost = (1, 6)
    src = tuple(s for s in range(12) if s not in lost)[:8]
    _planar_case("isa_k8m4_decode_e2", isa, src, lost, nbytes, rng)
    lrc = factory(dict(LRC_K4M2L3))
    _planar_case("lrc_k4m2l3_encode", lrc, None, None, nbytes, rng)
    # the composed recovery over the sources a served decode multiplies
    # (ec/stripe.py::_decode_src): one data shard lost, then two
    for lost in ((0,), (0, 2)):
        _planar_case(f"lrc_k4m2l3_decode_e{len(lost)}", lrc,
                     _decode_src(lrc, lost, lost), lost, nbytes, rng)

    blocks = rng.integers(0, 256, (crc_blocks, 4096), dtype=np.uint8)
    got = np.asarray(crc32c_batch(blocks))
    want = np.array([crc32c(0xFFFFFFFF, row.tobytes()) for row in blocks],
                    dtype=np.uint32)
    same("crc32c_batch vs scalar crc32c", got, want)
    say(check="crc32c_batch", blocks=crc_blocks, block_bytes=4096, ok=True)

    cmap, rule = build_three_level(n_racks=crush_racks, hosts_per_rack=16,
                                   osds_per_host=16, numrep=3)
    weights = np.full(cmap.max_devices, 0x10000, dtype=np.uint32)
    weights[rng.integers(0, cmap.max_devices, 40)] = 0
    weights[rng.integers(0, cmap.max_devices, 40)] = 0x8000
    mapper = TensorMapper(cmap, chunk=1 << 14)
    xs = np.arange(mapper.chunk, dtype=np.uint32)
    res, lens = mapper.do_rule_batch(rule, xs, 3, weights)
    res, lens = np.asarray(res), np.asarray(lens)
    scalar = ScalarMapper(cmap)
    wlist = [int(w) for w in weights]
    for x in rng.choice(mapper.chunk, crush_inputs, replace=False):
        ref = scalar.do_rule(rule, int(x), 3, wlist)
        if [int(v) for v in res[x, :lens[x]]] != ref:
            raise AssertionError(f"crush x={x}: device {res[x]} != {ref}")
    say(check="crush_do_rule_batch", osds=cmap.max_devices,
        lanes=mapper.chunk, compared=crush_inputs, ok=True)


# --------------------------------------------------------------- phase 3

async def _in_flight(n: int, jobs) -> list:
    """Run the coroutine factories ``jobs`` with ``n`` in flight."""
    sem = asyncio.Semaphore(n)

    async def one(job):
        async with sem:
            return await job()

    tasks = [asyncio.ensure_future(one(j)) for j in jobs]
    try:
        return await asyncio.gather(*tasks)
    finally:
        for t in tasks:       # after a failure nothing keeps hammering
            t.cancel()        # the cluster while it is taken down


async def _wait_health_ok(client, deadline_s: float) -> None:
    t0 = time.monotonic()
    health = {}
    while time.monotonic() - t0 < deadline_s:
        health = await client.objecter.mon_command({"prefix": "health"})
        if health["status"] == "HEALTH_OK":
            return
        await asyncio.sleep(0.25)
    raise TimeoutError(f"not HEALTH_OK after {deadline_s}s: {health}")


async def serve_ec_objects(seed: int, n_objects: int = 64,
                           object_size: int = 4 * MIB, in_flight: int = 16,
                           n_osds: int = 3,
                           recover_deadline_s: float = 300.0,
                           ec_profile=None, pg_num: int = 8) -> dict:
    """Phase 3: write, read, degraded read, recover, read — through the
    cluster's normal entry points.  Returns the phase's wall times and the
    growth of the ``KERNELS`` counters over it."""
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster
    from ceph_tpu.osdmap.osdmap import placement_snapshot
    from ceph_tpu.utils.perf import KERNELS

    rng = np.random.default_rng(seed)
    before = dict(KERNELS.dump()["device_kernels"])
    times = {}
    cluster = await start_cluster(n_osds, config=_fast_config())
    try:
        client = await cluster.client()
        pool = await client.pool_create(
            "smoke_ec", "erasure", pg_num=pg_num,
            ec_profile=dict(ec_profile or DEFAULT_EC_PROFILE))
        io = client.ioctx(pool)

        async def timed(name, coro):
            t0 = time.monotonic()
            out = await coro
            times[name] = round(time.monotonic() - t0, 3)
            say(step=name, seconds=times[name])
            return out

        # first compiles happen inside served ops: meet the tick's shape
        # buckets before the checked window (as the benchmark's warm-up does)
        warm = rng.integers(0, 256, object_size, dtype=np.uint8).tobytes()
        for width in sorted({1, min(4, in_flight), in_flight}):
            await timed(f"warm_write_x{width}", _in_flight(
                width, [lambda i=i, w=width: io.write_full(f"warm_{w}_{i}",
                                                           warm)
                        for i in range(width)]))

        objs = {f"obj_{i:04d}":
                rng.integers(0, 256, object_size, dtype=np.uint8).tobytes()
                for i in range(n_objects)}

        async def read_all(label):
            async def check(name):
                if await io.read(name) != objs[name]:
                    raise AssertionError(f"{label}: {name} read back "
                                         "different bytes")
            await timed(label, _in_flight(
                in_flight, [lambda n=n: check(n) for n in objs]))

        await timed("write", _in_flight(
            in_flight, [lambda n=n: io.write_full(n, objs[n])
                        for n in objs]))
        await read_all("read")

        # a shard holder that is not the first object's primary; it is
        # the primary of other PGs, which then fail over
        pgid = client.objecter.object_pgid(pool, "obj_0000")
        _, _, acting, primary = \
            client.objecter.osdmap.pg_to_up_acting_osds(pgid)
        victim = next(o for o in acting if o >= 0 and o != primary)
        await cluster.kill_osd(victim)
        await timed("wait_down", cluster.wait_down(victim))
        await read_all("degraded_read")

        await cluster.revive_osd(victim)
        # had it been down past mon_osd_down_out_interval (600 s in the
        # product configuration) it would be marked out, and a boot does
        # not mark it in again: the operator's `ceph osd in`, a no-op here
        await client.objecter.mon_command({"prefix": "osd in", "id": victim})
        await timed("recover_to_health_ok",
                    _wait_health_ok(client, recover_deadline_s))
        # k+m shards on k+m OSDs: the revived, empty OSD should hold a
        # rebuilt shard of every object again.  Reported, and fatal only
        # at zero: HEALTH_OK is the mon's word for "recovered", and a
        # push lost on a stale connection right after the bounce leaves a
        # silent hole (CHANGES.md, PR 23) that is not the device's fault
        store = cluster.osds[victim].store
        held = {o for c in store.list_collections()
                for o in store.list_objects(c)}
        rebuilt = len(set(objs) & held)
        say(step="recovery", shards_rebuilt=rebuilt, shards_expected=len(objs))
        if not rebuilt:
            raise AssertionError(f"recovery rebuilt nothing on osd.{victim}")
        await read_all("read_after_recovery")

        placement_engine = placement_snapshot(cluster.mon.osdmap, pool).mode
    except BaseException as exc:
        # leave evidence on stdout before the traceback: which daemons
        # the mon holds up, and what each OSD counted (flaps, timeouts)
        m = cluster.mon.osdmap
        say(failed_in="cluster", error=repr(exc), steps_done=times,
            mon_epoch=m.epoch,
            osd_up=[bool(u) for u in m.osd_up],
            health=cluster.mon._health_data(),
            osd_counters={
                f"osd.{i}": {k: v for k, v in
                             o.perf.dump()[f"osd.{i}"].items()
                             if v and isinstance(v, (int, float))}
                for i, o in cluster.osds.items()})
        raise
    finally:
        await asyncio.wait_for(cluster.stop(), 120)
    after = KERNELS.dump()["device_kernels"]
    grew = {k: v - before.get(k, 0) for k, v in after.items()
            if isinstance(v, (int, float)) and v != before.get(k, 0)}
    return {"times": times, "counters": grew, "victim": victim,
            "shards_rebuilt": rebuilt, "shards_expected": len(objs),
            "bytes_written": n_objects * object_size,
            "placement_engine": placement_engine}


def check_device_did_the_work(report: dict) -> None:
    """The counters that say which engine ran phase 3."""
    c = report["counters"]
    calls = c.get("planar_matmul_calls", 0)
    if calls <= 0:
        raise AssertionError("no planar matmul reached the device path")
    if c.get("planar_matmul_bytes", 0) < report["bytes_written"] // 2:
        raise AssertionError("planar matmul bytes far below bytes written")
    for host in ("ec_host_matmul_calls", "ec_host_planar_matmul_calls"):
        if c.get(host, 0):
            raise AssertionError(f"{host} = {c[host]}: the host GF engine "
                                 "served ops on a TPU backend")
    if c.get("ec_coalesced_ticks", 0) <= 0:
        raise AssertionError("no coalesced encode tick ran")
    if c.get("planar_stack_groups", 0) / calls <= 1:
        raise AssertionError("mean stack-group factor <= 1: the XLA planar "
                             "path took the calls, not the Pallas kernel")
    if c.get("ec_tick_crc_device_ticks", 0) < 1:
        raise AssertionError("no encode tick took its shard crcs from the "
                             "device chunk-crc program")


def phase_cluster(seed: int, **size) -> None:
    from ceph_tpu.ops import crc32c

    report = asyncio.run(serve_ec_objects(seed, **size))
    say(phase="cluster", **report,
        crc32c_engine="writes: every encode tick's shard crcs come from "
        "the chunk-crc program over the planes the tick holds on the chip, "
        "folded per shard on the host (ec_tick_crc_device_ticks); read "
        "verification, scrub and partial overwrites crc planes that live "
        "on the host, on the host: "
        + ("google_crc32c (hardware instruction)"
           if crc32c._gcrc is not None else "numpy table loop"),
        placement_note="the pool's walk is under osdmap.DEVICE_WALK_MIN_DRAWS "
        "expected draws, so placement ran on the host (placement_engine "
        "says which walk); the CRUSH kernel ran in phase 2 only")
    check_device_did_the_work(report)


# --------------------------------------------------------------- phase 4

def lrc_walk_shards(codec, sinfo, data: bytes) -> np.ndarray:
    """(n, shard_len) shard rows of ``data`` by the literal layer walk
    (``ErasureCodeLrc.encode_chunks``), the reference: the code is
    bytewise, so the walk over whole shards (every stripe's chunk of a
    shard, concatenated) is the walk stripe by stripe."""
    k, n, unit = sinfo.k, codec.get_chunk_count(), sinfo.chunk_size
    ns = sinfo.object_stripes(len(data))
    batch = np.frombuffer(data.ljust(ns * sinfo.stripe_width, b"\0"),
                          dtype=np.uint8).reshape(ns, k, unit)
    pos = codec.chunk_mapping
    chunks = {p: np.zeros(ns * unit, dtype=np.uint8) for p in range(n)}
    for j in range(k):
        chunks[pos[j]] = np.ascontiguousarray(batch[:, j]).reshape(-1)
    codec.encode_chunks(chunks)
    return np.stack([chunks[pos[s]] for s in range(n)])


def lrc_tick_against_the_walk(seed: int, n_objects: int = 4,
                              object_size: int = 4 * MIB) -> None:
    """One tick of seeded objects through ``encode_planes_multi`` (the
    flattened generator, the chunk-crc program) against the layer walk,
    shard crcs included."""
    from ceph_tpu.ec import factory, planar_store
    from ceph_tpu.ec.stripe import StripeInfo, encode_planes_multi
    from ceph_tpu.ops.crc32c import crc32c

    codec = factory(dict(LRC_K4M2L3))
    sinfo = StripeInfo(4, 4096)
    rng = np.random.default_rng(seed)
    datas = [rng.integers(0, 256, object_size, dtype=np.uint8).tobytes()
             for _ in range(n_objects)]
    out = encode_planes_multi(codec, sinfo, datas, [True] * n_objects)
    for i, (data, (planes, crcs)) in enumerate(zip(datas, out)):
        for s, row in enumerate(lrc_walk_shards(codec, sinfo, data)):
            walk = row.tobytes()
            if planar_store.planes_to_shard(planes[s], seam=None) != walk:
                raise AssertionError(f"lrc object {i} shard {s}: the planar "
                                     "tick differs from the layer walk")
            if int(crcs[s]) != crc32c(0xFFFFFFFF, walk):
                raise AssertionError(f"lrc object {i} shard {s}: device "
                                     "crc differs from crc32c of the walk")
    say(check="lrc_tick_vs_layer_walk", objects=n_objects,
        object_bytes=object_size, shards=len(out[0][1]), ok=True)


def phase_lrc(seed: int, **size) -> None:
    lrc_tick_against_the_walk(seed)
    size = {"n_objects": 16, "n_osds": 8, "pg_num": 16, **size}
    report = asyncio.run(serve_ec_objects(
        seed, ec_profile=LRC_K4M2L3, **size))
    say(phase="lrc", **report)
    check_device_did_the_work(report)
    c = report["counters"]
    # 8 MiB of planes to rest for every 4 MiB a tick ingested (recovery
    # pushes come on top); a pool off the product plane ingests nothing
    ingested = c.get("ec_planar_ingest_bytes", 0)
    if not ingested or c.get("store_planar_write_bytes", 0) < 2 * ingested:
        raise AssertionError("an LRC shard went to rest as bytes: the pool "
                             "is off the product plane")
    if c.get("ec_coalesced_read_ticks", 0) < 1 or \
            c.get("ec_coalesced_reencode_ticks", 0) < 1:
        raise AssertionError("no degraded read or no recovery decoded in "
                             "the plane domain")
    if c.get("ec_decode_sources_refused", 0):
        raise AssertionError("a decode refused the chunks that came with "
                             "one holder down")


# --------------------------------------------------------------- phase 5

def shec_reference_matrix() -> np.ndarray:
    """SHEC(6,4,3), technique multiple, w = 8: the coding matrix as
    upstream's plugin builds it (the golden vector of exactly this code,
    ``tests/golden/ec_golden.jsonl``), written out."""
    return np.array([[1, 1, 1, 0, 0, 0],
                     [0, 0, 0, 172, 82, 200],
                     [1, 166, 196, 238, 83, 146],
                     [1, 123, 245, 143, 244, 142]], dtype=np.uint8)


def _gf_products(factor: int) -> np.ndarray:
    """``factor`` times every byte in GF(2^8) modulo 0x11d, by shift and
    reduce."""
    x = np.arange(256, dtype=np.uint16)
    out = np.zeros(256, dtype=np.uint16)
    while factor:
        if factor & 1:
            out ^= x
        x <<= 1
        x ^= np.where(x & 0x100, 0x11d, 0).astype(np.uint16)
        factor >>= 1
    return out.astype(np.uint8)


def shec_tick_against_the_reference(seed: int, n_objects: int = 2,
                                    object_size: int = 4 * MIB,
                                    stack_groups: int = 2) -> dict:
    """One tick of seeded objects through ``encode_planes_multi`` against
    the scalar product with ``shec_reference_matrix``, shard crcs
    included; then every object decoded with chunk 3 gone, from the
    chunks the code names.  ``stack_groups``: what the Pallas kernel
    stacks at kw = 48."""
    from ceph_tpu.ec import factory, planar_store
    from ceph_tpu.ec.stripe import (StripeInfo, decode_planes_multi,
                                    encode_planes_multi)
    from ceph_tpu.ops.crc32c import crc32c
    from ceph_tpu.utils.perf import KERNELS

    def counters():
        c = KERNELS.dump()["device_kernels"]
        return c.get("planar_matmul_calls", 0), \
            c.get("planar_stack_groups", 0)

    codec = factory(dict(SHEC_K6M4C3))
    sinfo = StripeInfo(6, 4096)
    matrix = shec_reference_matrix()
    rng = np.random.default_rng(seed)
    datas = [rng.integers(0, 256, object_size, dtype=np.uint8).tobytes()
             for _ in range(n_objects)]
    calls0, groups0 = counters()
    out = encode_planes_multi(codec, sinfo, datas, [True] * n_objects)
    calls, groups = counters()      # the encode's: the decode's kw is 24
    calls, groups = calls - calls0, groups - groups0
    compared = 0
    for i, (data, (planes, crcs)) in enumerate(zip(datas, out)):
        ns = sinfo.object_stripes(len(data))
        rows = np.frombuffer(data.ljust(ns * sinfo.stripe_width, b"\0"),
                             dtype=np.uint8).reshape(ns, 6, 4096) \
            .transpose(1, 0, 2).reshape(6, -1)
        parity = np.zeros((4, rows.shape[1]), dtype=np.uint8)
        for r in range(4):
            for c in range(6):
                if matrix[r, c]:
                    parity[r] ^= _gf_products(int(matrix[r, c]))[rows[c]]
        for s, row in enumerate(np.vstack([rows, parity])):
            want = row.tobytes()
            if planar_store.planes_to_shard(planes[s], seam=None) != want:
                raise AssertionError(f"shec object {i} shard {s}: the "
                                     "planar tick differs from the scalar "
                                     "product")
            if int(crcs[s]) != crc32c(0xFFFFFFFF, want):
                raise AssertionError(f"shec object {i} shard {s}: device "
                                     "crc differs from crc32c of the "
                                     "reference")
            compared += 1
    # chunk 3's holder gone: the first k that are left (0, 1, 2, 4, 5, 6)
    # do not give it; the code names 4, 5 and parity 7
    have = [s for s in range(10) if s != 3]
    sources = codec.decode_sources({3}, have)
    if sources != [4, 5, 7]:
        raise AssertionError(f"shec: chunk 3 decoded from {sources}")
    got = decode_planes_multi(codec, sinfo, [
        ({s: planes[s] for s in have}, len(data))
        for data, (planes, _crcs) in zip(datas, out)])
    if got != datas:
        raise AssertionError("shec: a decode of chunk 3 differs from the "
                             "object written")
    say(check="shec_tick_and_decode_vs_reference", objects=n_objects,
        object_bytes=object_size, shards_compared=compared,
        decode_sources=sources, planar_matmul_calls=calls,
        planar_stack_groups=groups, ok=True)
    if calls <= 0 or groups != stack_groups * calls:
        raise AssertionError(
            f"mean stack-group factor {groups}/{calls}, not "
            f"{stack_groups}: at kw = 48 the Pallas kernel stacks two")
    return {"shards_compared": compared, "decode_sources": sources,
            "planar_matmul_calls": calls, "planar_stack_groups": groups}


def phase_shec(seed: int) -> None:
    shec_tick_against_the_reference(seed)


# ------------------------------------------------------------- multichip

def _show(name: str, arr) -> None:
    shards = [tuple(s.data.shape) for s in arr.addressable_shards]
    say(array=name, shape=list(arr.shape), sharding=str(arr.sharding),
        devices=len(arr.sharding.device_set), per_device_shapes=shards)
    if len(arr.sharding.device_set) != 4:
        raise AssertionError(f"{name} lives on "
                             f"{len(arr.sharding.device_set)} devices, not 4")


def phase_multichip(seed: int, batch_mib: int = 64, chunk: int = 4096) -> None:
    """The mesh EC engine on four chips against the single-device codec on
    chip 0: k8m4 encode and a one-erasure decode, bit for bit."""
    import jax

    from ceph_tpu.ec import factory
    from ceph_tpu.parallel.engine import MeshECEngine
    from ceph_tpu.parallel.mesh import make_mesh

    if jax.device_count() < 4:
        raise RuntimeError(f"--multichip needs 4 devices, JAX reports "
                           f"{jax.device_count()}")
    codec = factory(dict(ISA_K8M4))
    k, n = codec.get_data_chunk_count(), codec.get_chunk_count()
    mesh = make_mesh(4)
    say(phase="multichip", mesh_shape=dict(mesh.shape),
        mesh_devices=[str(d) for d in mesh.devices.flat])
    engine = MeshECEngine(mesh, k, n - k, np.asarray(codec.engine.coding))
    rng = np.random.default_rng(seed)
    b = batch_mib * MIB // (k * chunk)
    data = rng.integers(0, 256, (b, k, chunk), dtype=np.uint8)

    placed = engine._put(data, engine._data_sh)
    _show("encode_in", placed)
    parity = engine.encode_batch(placed)
    _show("encode_out", parity)
    same("mesh encode vs single-device codec",
         parity, codec.encode_batch(data))

    lost = 2
    chunks = np.concatenate([data, np.asarray(parity)], axis=1)
    chunks[:, lost] = 0
    placed = engine._put(chunks, engine._chunk_sh)
    _show("decode_in", placed)
    rebuilt = engine.decode_batch((lost,), placed)
    _show("decode_out", rebuilt)
    same("mesh decode vs single-device codec",
         rebuilt, codec.decode_batch((lost,), chunks))
    same("mesh decode vs the data written", np.asarray(rebuilt)[:, 0],
         data[:, lost])
    say(check="mesh_ec_engine", batch_bytes=data.size, ok=True)


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=23)
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: run the mesh EC engine against the "
                         "single-device codec, and nothing else")
    args = ap.parse_args(argv)

    import jax

    from ceph_tpu.utils import compile_cache

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              "this smoke runs on the chip only", file=sys.stderr)
        return 1
    device = phase_device(compile_cache.enable())

    phases = [("multichip", phase_multichip)] if args.multichip else \
        [("kernels", phase_kernels), ("cluster", phase_cluster),
         ("lrc", phase_lrc), ("shec", phase_shec)]
    for name, phase in phases:
        t0 = time.monotonic()
        phase(args.seed)
        say(phase=name, ok=True, seconds=round(time.monotonic() - t0, 3))
    say(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
