"""One run of one cell: set the cluster up, warm it, drive the closed loop
through the measured window, verify what it served, reduce the readings.

Everything here goes through the program's normal entry points
(``start_cluster`` -> ``cluster.client()`` -> ``pool_create`` ->
``ioctx.write_full/read``); the product configuration is
``vstart._fast_config()`` as the program defines it."""

from __future__ import annotations

import asyncio
import collections
import os
import shutil
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from . import layers, xplane
from .loader import BenchmarkError, Cell
from .plan import Op, Plan
from .stats import percentile

HISTORY_OPS = 4096      # osd_op_history_size of a traced run
HEALTHY_SAMPLE = 32
DEGRADED_SAMPLE = 16
DRAIN_S = 60.0          # callers may finish their last op after the window
AFTER_WINDOW_S = 200.0  # drain + verify + stop take ~10 s; stuck beyond this
HOST_ENGINE_COUNTERS = ("ec_host_matmul_calls",
                        "ec_host_planar_matmul_calls")
# a run has to meet its stores' logical limit (nearfull, the cluster's own
# ratio) before the host's physical one: under this much MemAvailable at
# the window's end the run is refused by name, before the kernel ends it
HOST_MEM_MIN_GIB = 4.0
ERRORS_KEPT = 5         # of CellRun.errors, in the result's line
SETTLE_S = 30.0         # a set-up kill has to settle inside this, or the run ends
# end-to-end metric prefix -> op kind
E2E_KINDS = {"write": "write_full", "read": "read"}

Record = Tuple[float, float, str, int, bool]    # t0, t1, kind, bytes, ok


def kernel_counters() -> Dict[str, float]:
    from ceph_tpu.utils.perf import KERNELS

    return {k: v for k, v in KERNELS.dump()["device_kernels"].items()
            if isinstance(v, (int, float))}


def grew(after: Dict[str, float], before: Dict[str, float]
         ) -> Dict[str, float]:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


class CompileWatch:
    """Counts XLA compiles and persistent-cache loads as JAX reports them,
    so a run can say whether anything compiled inside its window."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.n = 0

    def install(self) -> None:
        import jax.monitoring

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if event in self.EVENTS:
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def store_fill_peak(statfs) -> float:
    """The fullest OSD's used / total, from each OSD's ``statfs``
    ``(total_bytes, used_bytes)``; a store that states no size is not
    counted."""
    return max((used / total for total, used in statfs if total),
               default=0.0)


def host_mem_available_gib(path: str = "/proc/meminfo") -> float:
    """``MemAvailable`` as the kernel reckons it now, in GiB."""
    with open(path, encoding="ascii") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                return int(line.split()[1]) * 1024 / 2**30
    raise OSError(f"{path} has no MemAvailable line")


async def _in_flight(n: int, jobs) -> list:
    sem = asyncio.Semaphore(n)

    async def one(job):
        async with sem:
            return await job()

    tasks = [asyncio.ensure_future(one(j)) for j in jobs]
    try:
        return await asyncio.gather(*tasks)
    finally:
        for t in tasks:
            t.cancel()


def verification_plan(seed: int, names: List[str], n_osds: int
                      ) -> Tuple[List[str], List[str], int]:
    """(healthy sample, degraded sample, the OSD to kill): drawn from the
    seed alone, so a run can be repeated."""
    rng = np.random.default_rng([int(seed), 3])
    names = sorted(names)

    def sample(n: int) -> List[str]:
        pick = rng.choice(len(names), size=min(n, len(names)),
                          replace=False)
        return [names[i] for i in pick]

    return (sample(HEALTHY_SAMPLE), sample(DEGRADED_SAMPLE),
            int(rng.integers(0, n_osds)))


class CellRun:
    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 started_at: float, say: Callable[..., None],
                 trace_dir: Optional[str] = None,
                 device_kind: str = ""):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace, self.started_at, self.say = trace, started_at, say
        self.trace_dir, self.device_kind = trace_dir, device_kind
        self.plan = Plan(cell.traffic, seed)
        self.pool: Dict[int, List[bytes]] = {}
        # the plain reference: name -> the payload last acknowledged
        self.reference: Dict[str, Tuple[int, int]] = {}
        self.records: List[Record] = []
        self.errors: List[str] = []
        self.window: List[Record] = []  # the records completed in the window
        self.read_mismatches = 0    # reads of the loop that came back wrong
        self.victims: List[int] = []            # killed in set-up
        self.compiles = CompileWatch()

    def expected(self, name: str) -> bytes:
        size, payload = self.reference[name]
        return self.pool[size][payload]

    # ------------------------------------------------------------ set-up

    async def _write(self, io, op: Op) -> None:
        await io.write_full(op.name, self.pool[op.size][op.payload])
        self.reference[op.name] = (op.size, op.payload)

    def _burst_widths(self) -> List[int]:
        widths, w = [], 1
        while w < self.plan.callers:
            widths.append(w)
            w *= 2
        return widths + [self.plan.callers]

    async def _warm_up(self, io) -> None:
        """Meet the shape buckets the window will meet: bursts of 1, 2, 4
        ... ``callers`` objects of each size in flight (a tick coalesces
        what arrives together and pads to a power of two)."""
        for size in self.plan.sizes:
            for w in self._burst_widths():
                t0, c0 = time.monotonic(), self.compiles.n
                await _in_flight(w, [
                    (lambda i=i: io.write_full(f"warm_{size}_{w}_{i}",
                                               self.pool[size][0]))
                    for i in range(w)])
                self.say(step="warm_write", in_flight=w, object_bytes=size,
                         seconds=time.monotonic() - t0,
                         compiles=self.compiles.n - c0)

    async def _populate(self, io) -> None:
        populated = self.plan.populated
        if not populated:
            return
        t0 = time.monotonic()
        await _in_flight(self.plan.callers,
                         [(lambda op=op: self._write(io, op))
                          for op in populated])
        self.say(step="populate", objects=len(populated),
                 bytes=sum(op.size for op in populated),
                 seconds=time.monotonic() - t0)

    # -------------------------------------------- a pool degraded in set-up

    async def _kill_in_set_up(self, cluster, client, pool_id: int) -> None:
        """Kill the traffic file's victims and wait until the pool serves
        degraded: the mon marks them down, every surviving OSD and the
        client hold that epoch, and every PG has a surviving primary that
        has peered the new interval and whose commit watermark covers its
        log (until then a read gathers a second round, or waits).  All of
        it inside ``SETTLE_S``, or the run ends: never a hang."""
        k = int(self.cell.config["k"])
        up, _primary = cluster.mon.osdmap.pool_mapping(pool_id)
        held = [int((np.asarray(up)[:, :k] == o).sum())
                for o in range(len(cluster.osds))]
        self.victims = self.plan.victims(held)
        loop, t0 = asyncio.get_event_loop(), time.monotonic()
        deadline = loop.time() + SETTLE_S
        try:
            for v in self.victims:
                await cluster.kill_osd(v)
            for v in self.victims:
                await cluster.wait_down(v, timeout=deadline - loop.time())
            t_down = time.monotonic()
            epoch = cluster.mon.osdmap.epoch
            await cluster.wait_for_epoch(epoch,
                                         timeout=deadline - loop.time())
            pg_num = int(self.cell.config["pg_num"])
            while True:
                pgs = [st for osd in cluster.osds.values()
                       for st in osd.pgs.values()
                       if st.pgid.pool == pool_id
                       and st.primary == osd.osd_id]
                unsettled = [str(st.pgid) for st in pgs
                             if any(v in st.acting for v in self.victims)
                             or st.last_complete < st.last_update]
                if client.objecter.osdmap.epoch >= epoch \
                        and len(pgs) == pg_num and not unsettled:
                    break
                if loop.time() > deadline:
                    raise TimeoutError(
                        f"client epoch {client.objecter.osdmap.epoch} of "
                        f"{epoch}, {len(pgs)} of {pg_num} PGs have a "
                        f"primary, unsettled {unsettled}")
                await asyncio.sleep(0.02)
        except TimeoutError as exc:
            raise BenchmarkError(
                f"set-up kill of osd {self.victims} did not settle in "
                f"{SETTLE_S:g} s: {exc}") from None
        self.say(step="kill_in_set_up", victims=self.victims,
                 rule=self.plan.victim_rule, data_shard_pgs=held,
                 epoch=epoch, down_s=t_down - t0,
                 seconds=time.monotonic() - t0)

    async def _warm_reads(self, io) -> None:
        """Meet the decode's programs before the loop does.  Read bursts of
        1, 2, 4 ... ``callers`` populated objects in flight warm the read
        path as the program serves it.  A decode tick has no shape bucket
        (``ec/stripe.py::decode_planes_multi`` multiplies as many columns
        as its reads have), so a tick of every size from 1 to ``callers``
        is a program of its own, and which sizes a burst makes is the
        batcher's timing: the kernel's entry point is watched during the
        bursts (passed through untouched), and every multiple of the
        one-object call it saw is then run once on zeros, uncounted."""
        from ceph_tpu.ops import gf8
        from ceph_tpu.utils.perf import KERNELS

        # (matrix shape, plane rows) -> (a matrix, the fewest columns seen:
        # the first burst is one read, so one object's)
        seen: Dict[tuple, tuple] = {}
        sound = gf8.planar_matmul

        def watched(bitmat, planes):
            key = (tuple(bitmat.shape), int(planes.shape[0]))
            cols = int(planes.shape[1])
            if key not in seen or cols < seen[key][1]:
                seen[key] = (bitmat, cols)
            return sound(bitmat, planes)

        names = [op.name for op in self.plan.populated]
        at = 0
        gf8.planar_matmul = watched
        try:
            for w in self._burst_widths():
                t0, c0, k0 = time.monotonic(), self.compiles.n, len(seen)
                burst = [names[(at + i) % len(names)] for i in range(w)]
                at += w
                bad, raised = await self._compare_reads(io, burst,
                                                        "warm_read", w)
                self.read_mismatches += bad
                self.say(step="warm_read", in_flight=w, bad=bad,
                         raised=raised, seconds=time.monotonic() - t0,
                         compiles=self.compiles.n - c0,
                         decode_shapes_seen=len(seen) - k0)
        finally:
            gf8.planar_matmul = sound
        if not seen:
            raise BenchmarkError("no warm-up read reached the device's "
                                 "decode: the pool is not degraded")
        t0, c0 = time.monotonic(), self.compiles.n
        loop = asyncio.get_event_loop()

        def every_tick_size() -> int:
            import jax

            n_programs = 0
            with KERNELS.muted():
                for (_shape, rows), (bitmat, cols) in seen.items():
                    for n in range(1, self.plan.callers + 1):
                        jax.block_until_ready(sound(
                            bitmat, np.zeros((rows, n * cols), np.uint8)))
                        n_programs += 1
            return n_programs

        n_programs = await loop.run_in_executor(None, every_tick_size)
        self.say(step="warm_decode_ticks", programs=n_programs,
                 one_object_columns={f"{m[0]}x{m[1]}": cols for (m, _r),
                                     (_b, cols) in seen.items()},
                 seconds=time.monotonic() - t0,
                 compiles=self.compiles.n - c0)

    # ---------------------------------------------------------- the loop

    async def _caller(self, io, c: int, stop: asyncio.Event) -> None:
        i = 0
        while not stop.is_set():
            op = self.plan.op(c, i)
            i += 1
            data = None
            t0 = time.perf_counter()
            try:
                if op.kind == "write_full":
                    await self._write(io, op)
                    ok = True
                else:
                    data = await io.read(op.name)
                    ok = len(data) == op.size
            except asyncio.CancelledError:
                raise
            except Exception as exc:    # a failed op is a result, counted
                ok = False
                self.errors.append(f"{op.kind} {op.name}: {exc!r}")
            self.records.append((t0, time.perf_counter(), op.kind,
                                 op.size, ok))
            # every read is compared with the reference once its latency
            # is stamped (rados bench rand verifies what it reads unless
            # --no-verify): the timed path's own output decides `correct`
            if data is not None and data != self.expected(op.name):
                self.read_mismatches += 1
                if len(self.errors) < ERRORS_KEPT:
                    self.errors.append(f"read {op.name}: came back and "
                                       "differs from the reference")

    async def _profile_slice(self, loop) -> Tuple[float, Dict[str, float]]:
        """Profile a slice in the middle of the window, Python and host
        tracers off.  Returns the slice's length and counter growth."""
        import jax

        slice_s = min(5.0, self.seconds / 3.0)
        await asyncio.sleep((self.seconds - slice_s) / 2.0)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        await loop.run_in_executor(
            None, lambda: jax.profiler.start_trace(
                self.trace_dir, profiler_options=opts))
        c0, t0 = kernel_counters(), time.perf_counter()
        await asyncio.sleep(slice_s)
        c1, t1 = kernel_counters(), time.perf_counter()
        await loop.run_in_executor(None, jax.profiler.stop_trace)
        return t1 - t0, grew(c1, c0)

    def _arm_watchdog(self, loop) -> asyncio.TimerHandle:
        """A run that sticks after its window (it has happened: PERF.md)
        says where every task sat and exits 4, inside the driver's limit."""
        def stuck() -> None:
            where = collections.Counter()
            for task in asyncio.all_tasks(loop):
                frames = task.get_stack(limit=1)
                if frames:
                    f = frames[-1]
                    where[f"{os.path.basename(f.f_code.co_filename)}:"
                          f"{f.f_lineno} {f.f_code.co_name}"] += 1
            self.say(stuck_after_window_s=AFTER_WINDOW_S,
                     tasks=where.most_common(40),
                     errors=self.errors[:ERRORS_KEPT])
            os._exit(4)

        return loop.call_later(AFTER_WINDOW_S, stuck)

    # ------------------------------------------------------------ verify

    async def _compare_reads(self, io, names: List[str], label: str,
                             in_flight: int) -> Tuple[int, int]:
        """Read ``names`` and compare with the reference; returns how
        many differ, are missing or fail, and how many of those RAISED
        (the rest came back and differed)."""
        async def check(name: str) -> Optional[bool]:
            try:
                return await io.read(name) == self.expected(name)
            except asyncio.CancelledError:
                raise
            except Exception as exc:
                self.errors.append(f"{label} read {name}: {exc!r}")
                return None

        good = await _in_flight(in_flight,
                                [(lambda n=n: check(n)) for n in names])
        return (sum(1 for g in good if not g),
                sum(1 for g in good if g is None))

    async def _read_back(self, io, names: List[str], label: str
                         ) -> Tuple[int, int]:
        t0 = time.monotonic()
        bad, raised = await self._compare_reads(io, names, label,
                                                self.plan.callers)
        self.say(step=label, objects=len(names), bad=bad, raised=raised,
                 seconds=time.monotonic() - t0)
        return bad, raised

    def _samples(self, window_names: List[str], n_osds: int):
        """(names the reference holds, healthy sample, degraded sample, the
        OSD the verification kills where set-up killed none)."""
        names = window_names or [op.name for op in self.plan.populated]
        return (names,) + verification_plan(self.seed, names, n_osds)

    async def _verify(self, cluster, io, window_names: List[str],
                      run_before: Dict[str, float],
                      window_grew: Dict[str, float],
                      healthy_in_set_up: Optional[Tuple[int, int]] = None
                      ) -> Tuple[list, int]:
        """Returns (checks, objects read).  A check is
        ``{"name", "value", "limit", "rule", "ok"}``.  A cell whose set-up
        killed its victims read the healthy sample then, while the pool
        was whole (``healthy_in_set_up``: bad, raised), and kills no
        second holder here: the degraded sample is read with the set-up's
        victims down."""
        names, healthy, degraded, victim = self._samples(
            window_names, len(cluster.osds) + len(self.victims))
        checks = []

        def check(name, value, rule, limit):
            ok = value <= limit if rule == "max" else value >= limit
            checks.append({"name": name, "value": value, "rule": rule,
                           "limit": limit, "ok": bool(ok)})

        # traffic on which no operation fails: a failed op (a full store,
        # a timeout) makes the window's rates meaningless
        check("window_failed_ops",
              sum(1 for r in self.records if not r[4]), "max", 0)
        # the window's objects went to new names and stayed: how near the
        # fullest store came to the ratio at which the mon warns (ten
        # points on it refuses writes), and how near the host to its end
        statfs = [osd.store.statfs() for osd in cluster.osds.values()]
        available = host_mem_available_gib()
        self.say(step="room", host_mem_available_gib=available,
                 stores_used_gib=[used / 2**30 for _total, used in statfs],
                 store_gib=[total / 2**30 for total, _used in statfs])
        check("store_fill_peak", store_fill_peak(statfs),
              "max", cluster.config.mon_osd_nearfull_ratio)
        check("host_mem_available_gib", available, "min", HOST_MEM_MIN_GIB)
        check("objects_to_verify", len(names), "min", 1)
        bad, raised = healthy_in_set_up or \
            await self._read_back(io, healthy, "verify_healthy")
        check("healthy_mismatches", bad, "max", 0)
        check("healthy_read_errors", raised, "max", 0)

        before = kernel_counters()
        if not self.victims:
            t0 = time.monotonic()
            await cluster.kill_osd(victim)
            await cluster.wait_down(victim)
            self.say(step="kill_osd", victim=victim,
                     seconds=time.monotonic() - t0)
        bad, raised = await self._read_back(io, degraded, "verify_degraded")
        check("degraded_mismatches", bad, "max", 0)
        check("degraded_read_errors", raised, "max", 0)
        decoded = grew(kernel_counters(), before)
        # without a decode the degraded sample proves nothing about parity
        check("degraded_decode_ticks",
              decoded.get("ec_coalesced_read_ticks", 0), "min", 1)

        # which engine served: chip_smoke.py::check_device_did_the_work's
        # rule, over the whole run, and over the window where it writes
        run_grew = grew(kernel_counters(), run_before)
        check("device_matmul_calls",
              run_grew.get("planar_matmul_calls", 0), "min", 1)
        check("host_engine_calls",
              sum(run_grew.get(c, 0) for c in HOST_ENGINE_COUNTERS),
              "max", 0)
        written = sum(r[3] for r in self.records
                      if r[2] == "write_full" and r[4])
        if written:
            check("window_encode_ticks",
                  window_grew.get("ec_coalesced_ticks", 0), "min", 1)
            check("window_matmul_bytes",
                  window_grew.get("planar_matmul_bytes", 0), "min",
                  written // 2)
        if any(r[2] == "read" for r in self.records):
            # every read of the warm-up bursts, the lead-in and the window
            # was compared when it came back; a window that reads a
            # degraded pool has to have decoded on the device
            check("window_read_mismatches", self.read_mismatches, "max", 0)
            if self.victims:
                check("window_decode_ticks",
                      window_grew.get("ec_coalesced_read_ticks", 0),
                      "min", 1)
                check("window_decoded_reads",
                      window_grew.get("ec_coalesced_reads", 0), "min",
                      sum(1 for r in self.window
                          if r[2] == "read" and r[4]) // 4)
        return checks, len(healthy) + len(degraded)

    # -------------------------------------------------------- reductions

    async def _attribution(self, cluster, client, window: List[Record]
                           ) -> Dict[str, dict]:
        """op kind -> every OSD's ``dump_op_attribution`` merged, with the
        objecter's reply tails where the mix has one kind of op."""
        from ceph_tpu.trace.attribution import aggregate, merge_reports

        tails = aggregate(client.objecter.drain_op_tails())
        tails["ops"] = 0    # they extend ops the OSDs count
        out = {}
        for kind in self.plan.kinds:
            reports = [await cluster.daemon_command(
                f"osd.{o}", {"prefix": "dump_op_attribution",
                             "args": {"match": kind}})
                       for o in cluster.osds]
            if len(self.plan.kinds) == 1:
                reports.append(tails)
            lats = [r[1] - r[0] for r in window if r[2] == kind]
            out[kind] = merge_reports(
                reports,
                measured_wall_s=sum(lats) / len(lats) if lats else None)
        return out

    def _end_to_end(self, window: List[Record], setup_s: float
                    ) -> Dict[str, dict]:
        metrics: Dict[str, dict] = {}
        for prefix, kind in E2E_KINDS.items():
            done = [r for r in window if r[2] == kind and r[4]]
            if not done:
                continue
            lats = sorted(r[1] - r[0] for r in done)
            self.say(op=kind, window_ops=len(lats),
                     mean_ms=1e3 * sum(lats) / len(lats),
                     p50_ms=1e3 * percentile(lats, 50), max_ms=1e3 * lats[-1])
            for name, value, unit in (
                    (f"{prefix}_MBps",
                     sum(r[3] for r in done) / self.seconds / 1e6, "MB/s"),
                    (f"{prefix}_p95_ms", 1e3 * percentile(lats, 95), "ms")):
                if name in self.cell.end_to_end:
                    metrics[name] = {"value": value, "unit": unit}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        return metrics

    def _say_timeline(self, w0: float) -> None:
        """Completed MB per second of the loop, lead-in included: shows
        whether the window opened in steady state."""
        t_first = min((r[0] for r in self.records), default=w0)
        per_s: Dict[int, float] = collections.defaultdict(float)
        for r in self.records:
            if r[4]:
                per_s[int(r[1] - t_first)] += r[3] / 1e6
        self.say(timeline_MB_per_s=[round(per_s.get(b, 0.0), 1)
                                    for b in range(max(per_s, default=0) + 1)],
                 window_opens_at_s=w0 - t_first)

    # --------------------------------------------------------------- run

    async def run(self) -> dict:
        from ceph_tpu.cluster.vstart import _fast_config, start_cluster
        from ceph_tpu.trace.attribution import flush_op_history

        loop = asyncio.get_event_loop()
        cfg_file = self.cell.config
        self.compiles.install()
        run_before = kernel_counters()
        t0 = time.monotonic()
        self.pool = self.plan.payload_pool()
        self.say(step="payload_pool", seconds=time.monotonic() - t0,
                 buffers=sum(len(v) for v in self.pool.values()))

        config = _fast_config()
        # the size of each OSD's device is the deployment's, not a tuning
        config.memstore_device_bytes = int(cfg_file["store_bytes_per_osd"])
        if self.trace:
            # every op of the window must stay in the history ring
            config.osd_op_history_size = HISTORY_OPS
        t0 = time.monotonic()
        cluster = await start_cluster(int(cfg_file["osds"]), config=config)
        try:
            client = await cluster.client()
            pool_id = await client.pool_create(
                "bench", cfg_file["pool_type"],
                pg_num=int(cfg_file["pg_num"]),
                ec_profile=dict(cfg_file["ec_profile"]))
            io = client.ioctx(pool_id)
            self.say(step="cluster_up", osds=int(cfg_file["osds"]),
                     pg_num=int(cfg_file["pg_num"]),
                     seconds=time.monotonic() - t0)
            await self._warm_up(io)
            await self._populate(io)
            healthy_in_set_up = None
            if self.plan.kill_shard_holders:
                # the healthy sample has to be read while the pool is whole
                _names, healthy, _degraded, _victim = self._samples(
                    [], len(cluster.osds))
                healthy_in_set_up = await self._read_back(
                    io, healthy, "verify_healthy")
                await self._kill_in_set_up(cluster, client, pool_id)
                await self._warm_reads(io)

            stop = asyncio.Event()
            callers = [asyncio.ensure_future(self._caller(io, c, stop))
                       for c in range(self.plan.callers)]
            try:
                await asyncio.sleep(self.plan.lead_in_s)
                if self.trace:
                    await flush_op_history(cluster, HISTORY_OPS)
                    client.objecter.drain_op_tails()
                compiles_before = self.compiles.n
                epoch_before = cluster.mon.osdmap.epoch
                window_before = kernel_counters()
                w0 = time.perf_counter()
                setup_s = time.monotonic() - self.started_at
                w1 = w0 + self.seconds
                slice_s, slice_grew = 0.0, {}
                prof = asyncio.ensure_future(self._profile_slice(loop)) \
                    if self.trace and self.trace_dir else None
                await asyncio.sleep(self.seconds)
                # the loop may hand control back late; the window still
                # closes at w1: later completions are not counted
                late_s = time.perf_counter() - w1
                if prof is not None:
                    slice_s, slice_grew = await prof
                window_grew = grew(kernel_counters(), window_before)
                compiles_in_window = self.compiles.n - compiles_before
                # a map change inside a window is an OSD marked down or up
                epochs_in_window = cluster.mon.osdmap.epoch - epoch_before
                stop.set()
                watchdog = self._arm_watchdog(loop)
                await asyncio.wait_for(asyncio.gather(*callers), DRAIN_S)
            finally:
                for t in callers:
                    t.cancel()
            drain_s = time.perf_counter() - w1 - late_s

            self.window = window = [r for r in self.records
                                    if w0 <= r[1] <= w1]
            attribution = await self._attribution(cluster, client, window) \
                if self.trace else {}
            window_names = [n for n in self.reference
                            if n.startswith("obj_")]
            t0 = time.monotonic()
            checks, verified = await self._verify(
                cluster, io, window_names, run_before, window_grew,
                healthy_in_set_up)
            verify_s = time.monotonic() - t0
        except BaseException as exc:
            # leave evidence before the traceback: which daemons the mon
            # holds up, and what the ops that failed said
            osdmap = cluster.mon.osdmap
            self.say(failed_in_run=repr(exc), mon_epoch=osdmap.epoch,
                     osd_up=[bool(u) for u in osdmap.osd_up],
                     health=cluster.mon._health_data(),
                     ops_done=len(self.records),
                     ops_failed=sum(1 for r in self.records if not r[4]),
                     errors=self.errors[:8])
            raise
        finally:
            try:
                await asyncio.wait_for(cluster.stop(), 120)
            except asyncio.TimeoutError:
                # must not hide the fault that brought us here
                self.say(cluster_stop="timed out after 120 s")
        watchdog.cancel()

        # ---------------------------------------------------- reductions
        metrics = self._end_to_end(window, setup_s)

        summary = None
        if self.trace and self.trace_dir:
            summary = xplane.TraceSummary(
                slice_s, xplane.load(xplane.find_xplane(self.trace_dir)))
        layer_values: Dict[str, dict] = {}
        if self.trace:
            readings = layers.Readings(
                config=cfg_file, device_kind=self.device_kind,
                attribution=attribution, counters=window_grew,
                slice_counters=slice_grew, trace=summary)
            for name, reader in self.cell.per_layer.items():
                value = layers.read_metric(name, reader, readings)
                if value is not None:
                    layer_values[name] = {"value": value,
                                          "unit": reader["unit"]}
            for kind, rep in attribution.items():
                self.say(attribution=kind, ops=rep.get("ops"),
                         wall_coverage=rep.get("wall_coverage"),
                         stages_ms_per_op={
                             s: 1e3 * row["s"] / max(rep.get("ops", 0), 1)
                             for s, row in rep.get("stages", {}).items()})

        self._say_timeline(w0)
        failed_ops = sum(1 for r in window if not r[4])
        bad_reads = sum(c["value"] for c in checks
                        if c["name"].endswith("_mismatches"))
        for c in checks:
            self.say(check=c["name"], value=c["value"], rule=c["rule"],
                     limit=c["limit"], ok=c["ok"])
        self.say(window_s=self.seconds, window_counters=window_grew,
                 compiles_in_window=compiles_in_window,
                 osdmap_epochs_in_window=epochs_in_window,
                 window_closed_late_s=late_s, drain_s=drain_s,
                 verify_s=verify_s, setup_s=setup_s,
                 errors=self.errors[:ERRORS_KEPT])
        return {
            "correct": all(c["ok"] for c in checks),
            "attempted": len(window) + verified,
            "failed": failed_ops + bad_reads,
            "metrics": layer_values if self.trace else metrics,
            "end_to_end": metrics,
            "trace": summary,
            "errors": self.errors[:ERRORS_KEPT],
            "checks": checks,
        }
