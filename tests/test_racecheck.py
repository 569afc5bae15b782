"""graft-race dynamic half: the seeded schedule-perturbation loop
(ceph_tpu/utils/schedfuzz.py), the cross-task write-after-read tracker
(ceph_tpu/analysis/racecheck.py), the `graftlint --race` CLI, and the
tier-1 race smoke.

The two regression anchors at the bottom pin the real bugs this
sanitizer convicted on its first outing (batch-smoke seed 2 at smoke
scale): a drained-but-short commit frontier that nothing ever re-armed,
and a planar-at-rest rewind that restored the rolled-back PLANES while
leaving the divergent write's size/hinfo_crc/version attrs stamped —
old data under a new crc, failing verify-on-read forever.
"""

import asyncio
import importlib.util
import os
import sys

import pytest

from ceph_tpu.analysis import racecheck
from ceph_tpu.analysis.racecheck import (NULL_RACE, RaceTracker, _NullRace,
                                         race_run)
from ceph_tpu.utils.lockdep import DepLock
from ceph_tpu.utils.schedfuzz import SchedFuzzLoop, run_fuzzed
from tests._flaky import contention_retry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- schedfuzz


def _workload(n: int = 6, rounds: int = 4):
    """IO-free N-worker interleaving probe: the recorded (worker, round)
    order IS the interleaving, so digests and results are comparable
    bit for bit (no sockets -> no OS-timing nondeterminism)."""
    order = []

    async def worker(i):
        for r in range(rounds):
            await asyncio.sleep(0)
            order.append((i, r))

    async def main():
        await asyncio.gather(*(worker(i) for i in range(n)))
        return tuple(order)

    return main


def test_schedfuzz_same_seed_replays_bit_identically():
    r1, d1 = run_fuzzed(_workload(), seed=7)
    r2, d2 = run_fuzzed(_workload(), seed=7)
    assert r1 == r2
    assert d1 == d2


def test_schedfuzz_seeds_explore_distinct_interleavings():
    results = {}
    digests = set()
    for seed in range(8):
        r, d = run_fuzzed(_workload(), seed=seed)
        results[seed] = r
        digests.add(d)
    # not every pair need differ, but a seeded explorer that always
    # lands on one schedule explores nothing
    assert len(set(results.values())) > 1
    assert len(digests) > 1


def test_schedfuzz_perturbs_the_fifo_order():
    fifo = asyncio.run(_workload()())
    perturbed = {run_fuzzed(_workload(), seed=s)[0] for s in range(6)}
    assert any(p != fifo for p in perturbed), \
        "six seeds all reproduced FIFO: the shim is not perturbing"


def test_schedfuzz_trace_is_a_valid_decision_record():
    loop = SchedFuzzLoop(seed=11)
    try:
        asyncio.set_event_loop(loop)
        loop.run_until_complete(_workload()())
    finally:
        asyncio.set_event_loop(None)
        loop.close()
    trace = loop.fuzz_trace()
    assert trace, "a 6-worker gather produced zero perturbable ticks"
    last_tick = 0
    for tick, n, perm, deferred in trace:
        assert tick > last_tick
        last_tick = tick
        assert sorted(perm) == list(range(n))   # true permutation
        assert 0 <= deferred <= n
    # the digest is a pure function of the trace
    assert loop.trace_digest() == loop.trace_digest()


# ----------------------------------------------------- NULL_RACE contract


def test_null_race_noop_contract():
    """Default-off is a provable no-op: falsy, slotless (retains
    nothing), constant report, and it IS the module default."""
    assert racecheck.TRACKER is NULL_RACE
    assert not NULL_RACE
    assert NULL_RACE.enabled is False
    assert _NullRace.__slots__ == ()
    with pytest.raises(AttributeError):
        NULL_RACE.anything = 1
    NULL_RACE.note_read(("pg", 0, "1.0"), "self_info")
    NULL_RACE.note_write(("pg", 0, "1.0"), "self_info")
    NULL_RACE.advance_tick()
    assert NULL_RACE.findings() == []
    assert NULL_RACE.report() == {"enabled": False, "seed": 0,
                                  "ticks": 0, "reads": 0, "writes": 0,
                                  "findings": []}


def test_from_config_gates_on_race_check_enabled():
    from ceph_tpu.utils import Config

    cfg = Config()
    assert cfg.race_check_enabled == 0
    assert racecheck.from_config(cfg) is NULL_RACE
    cfg.race_check_enabled = 1
    cfg.race_check_seed = 5
    t = racecheck.from_config(cfg)
    assert isinstance(t, RaceTracker)
    assert t.seed == 5


# ------------------------------------------------------------ the tracker


def test_tracker_convicts_cross_task_write_after_read():
    t = RaceTracker(seed=3)

    async def main():
        wrote = asyncio.Event()

        async def reader():
            t.note_read(("pg", 0, "1.0"), "self_info")
            await wrote.wait()      # finishes WITHOUT re-reading

        async def writer():
            await asyncio.sleep(0)
            t.note_write(("pg", 0, "1.0"), "self_info")
            wrote.set()

        rt = asyncio.get_event_loop().create_task(reader(),
                                                  name="recovery-round")
        wt = asyncio.get_event_loop().create_task(writer(),
                                                  name="commit-entry")
        await asyncio.gather(rt, wt)
        return t.findings()

    found = asyncio.run(main())
    assert len(found) == 1
    f = found[0]
    assert f["rule"] == "write-after-read"
    assert "recovery-round" in f["message"]
    assert "commit-entry" in f["message"]
    # both probes attributed: task, site, stack
    assert f["read"]["task"] == "recovery-round" and f["read"]["stack"]
    assert f["write"]["task"] == "commit-entry" and f["write"]["stack"]


def test_tracker_reread_revalidates():
    """A re-read AFTER the write is exactly what a fix looks like (the
    PR-11 refresh, the PR-9 identity recheck): no conviction."""
    t = RaceTracker()

    async def main():
        wrote = asyncio.Event()

        async def reader():
            t.note_read(("pg", 0, "1.0"), "self_info")
            await wrote.wait()
            t.note_read(("pg", 0, "1.0"), "self_info")   # the refresh

        async def writer():
            await asyncio.sleep(0)
            t.note_write(("pg", 0, "1.0"), "self_info")
            wrote.set()

        await asyncio.gather(asyncio.ensure_future(reader()),
                             asyncio.ensure_future(writer()))
        return t.findings()

    assert asyncio.run(main()) == []


def test_tracker_common_lock_suppresses():
    """Reader and writer holding a shared DepLock at their probes were
    serialized by it — no interleaving to convict."""
    t = RaceTracker()

    async def main():
        wrote = asyncio.Event()

        async def reader():
            DepLock._held[id(asyncio.current_task())] = ["pg:1.0"]
            t.note_read(("pgs", 0, "1.0"), "registry")
            await wrote.wait()

        async def writer():
            await asyncio.sleep(0)
            DepLock._held[id(asyncio.current_task())] = ["pg:1.0"]
            t.note_write(("pgs", 0, "1.0"), "registry")
            wrote.set()

        await asyncio.gather(asyncio.ensure_future(reader()),
                             asyncio.ensure_future(writer()))
        return t.findings()

    assert asyncio.run(main()) == []


def test_tracker_cancelled_reader_never_convicts():
    """Chaos kills cancel in-flight commit tasks; a cancelled reader
    unwound without acting on its snapshot."""
    t = RaceTracker()

    async def main():
        async def reader():
            t.note_read(("pgs", 0, "1.0"), "registry")
            # not a timing guess: park forever so cancel() is the only
            # way out — the cancelled-reader shape under test
            await asyncio.sleep(3600)  # graftlint: ignore[fixed-sleep-in-tests]

        rt = asyncio.get_event_loop().create_task(reader())
        await asyncio.sleep(0)
        t.note_write(("pgs", 0, "1.0"), "registry")
        rt.cancel()
        try:
            await rt
        except asyncio.CancelledError:
            pass
        return t.findings()

    assert asyncio.run(main()) == []


def test_tracker_own_write_neither_convicts_nor_revalidates():
    """A task's own write doesn't convict it (no interleaving), but its
    local snapshot is STILL stale — the record must stand so a later
    cross-task write convicts (the single-task half of the PR-11 bug)."""
    t = RaceTracker()

    async def main():
        wrote = asyncio.Event()

        async def reader():
            t.note_read(("pg", 0, "1.0"), "self_info")
            t.note_write(("pg", 0, "1.0"), "self_info")   # own write
            await wrote.wait()

        async def writer():
            await asyncio.sleep(0)
            t.note_write(("pg", 0, "1.0"), "self_info")
            wrote.set()

        await asyncio.gather(asyncio.ensure_future(reader()),
                             asyncio.ensure_future(writer()))
        return t.findings()

    found = asyncio.run(main())
    assert len(found) == 1, "record was dropped by the task's own write"


# ------------------------- the two lint-corpus bug classes, at runtime


def _recovery_shape(refresh: bool):
    """The PR-11 shape as the probes see it: a recovery round snapshots
    self-info, awaits peer queries, and (fixed) re-reads after the
    await; a concurrent commit advances the log head meanwhile."""
    t = RaceTracker()

    async def main():
        advanced = asyncio.Event()

        async def recovery_round():
            t.note_read(("pg", 0, "1.0"), "self_info")    # round start
            await advanced.wait()                          # peer query
            if refresh:
                t.note_read(("pg", 0, "1.0"), "self_info")  # the fix
            # ... elects an authority from infos and returns

        async def commit():
            await asyncio.sleep(0)
            t.note_write(("pg", 0, "1.0"), "self_info")   # log head +1
            advanced.set()

        await asyncio.gather(asyncio.ensure_future(recovery_round()),
                             asyncio.ensure_future(commit()))
        return t.findings()

    return asyncio.run(main())


def test_pr11_stale_selfinfo_shape_convicts():
    assert len(_recovery_shape(refresh=False)) == 1


def test_pr11_refreshed_selfinfo_shape_is_quiet():
    assert _recovery_shape(refresh=True) == []


def _commit_shape(recheck: bool):
    """The PR-9 shape: a commit opens against the PGState it pulled
    from the registry, awaits acks, and (fixed) re-checks registry
    identity at resolve time; peering replaces the entry meanwhile."""
    t = RaceTracker()

    async def main():
        replaced = asyncio.Event()

        async def commit():
            t.note_read(("pgs", 0, "1.0"), "registry")    # frontier open
            await replaced.wait()                          # ack wait
            if recheck:
                t.note_read(("pgs", 0, "1.0"), "registry")  # _frontier_done
            # ... advances the watermark on the snapshot it held

        async def map_apply():
            await asyncio.sleep(0)
            t.note_write(("pgs", 0, "1.0"), "registry")   # entry replaced
            replaced.set()

        await asyncio.gather(asyncio.ensure_future(commit()),
                             asyncio.ensure_future(map_apply()))
        return t.findings()

    return asyncio.run(main())


def test_pr9_superseded_pgstate_shape_convicts():
    assert len(_commit_shape(recheck=False)) == 1


def test_pr9_identity_recheck_shape_is_quiet():
    assert _commit_shape(recheck=True) == []


# ------------------------------------------------------- race_run + CLI


def test_race_run_unknown_scenario_raises():
    with pytest.raises(KeyError):
        race_run("no-such-scenario", 1)


def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "_graftlint_cli", os.path.join(REPO, "scripts", "graftlint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_graftlint_race_cli_exit_codes(monkeypatch, capsys):
    """--race contract: 0 clean, 1 convictions or scenario failures,
    2 usage errors — CI tells 'found a race' from 'asked wrong'."""
    cli = _load_cli()
    assert cli.main(["--race", "batch-smoke", "--seeds", "bogus"]) == 2
    assert cli.main(["--race", "batch-smoke", "--seeds", ""]) == 2
    assert cli.main(["--race", "definitely-not-a-scenario"]) == 2

    class _Pass:
        passed = True
        failures = []

    class _Fail:
        passed = False
        failures = ["durability: obj3 unreadable"]

    clean = {"enabled": True, "seed": 1, "ticks": 3, "reads": 1,
             "writes": 1, "findings": []}
    dirty = dict(clean, findings=[{"message": "task A raced task B",
                                   "rule": "write-after-read"}])
    monkeypatch.setattr(racecheck, "race_run",
                        lambda *a, **k: (_Pass, clean, "digest"))
    assert cli.main(["--race", "batch-smoke", "--seeds", "1,2"]) == 0
    monkeypatch.setattr(racecheck, "race_run",
                        lambda *a, **k: (_Pass, dirty, "digest"))
    assert cli.main(["--race", "batch-smoke", "--seeds", "1"]) == 1
    monkeypatch.setattr(racecheck, "race_run",
                        lambda *a, **k: (_Fail, clean, "digest"))
    assert cli.main(["--race", "batch-smoke", "--seeds", "1"]) == 1
    capsys.readouterr()


def test_admin_race_report_command():
    """`race report` serves the tracker's report, and the disabled
    payload (never an error) when no tracker is installed — the
    blackbox-dump contract."""
    from ceph_tpu.utils.admin_socket import AdminSocket
    from ceph_tpu.utils.perf import PerfCounters

    sock = AdminSocket()
    sock.register_common(PerfCounters("t"))
    res, data = asyncio.run(sock.dispatch({"prefix": "race report"}))
    assert res == 0 and data["enabled"] is False
    prev = racecheck.install(RaceTracker(seed=9))
    try:
        res, data = asyncio.run(sock.dispatch({"prefix": "race report"}))
        assert res == 0 and data["enabled"] is True and data["seed"] == 9
    finally:
        racecheck.install(prev)


def test_boot_arms_tracker_from_config():
    """`race_check_enabled=1` arms the process-global tracker at
    vstart boot (seeded from `race_check_seed`), live I/O moves the
    probe counters, and `race report` serves them; a default boot
    leaves NULL_RACE installed."""
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster

    async def scenario():
        cfg = _fast_config()
        cfg.set("race_check_enabled", 1)
        cfg.set("race_check_seed", 7)
        cluster = await start_cluster(3, config=cfg)
        try:
            assert racecheck.TRACKER.enabled
            client = await cluster.client()
            pool = await client.pool_create("p", "replicated",
                                            pg_num=8, size=3)
            io = client.ioctx(pool)
            await io.write_full("obj", b"x" * 512)
            assert await io.read("obj") == b"x" * 512
            return await cluster.daemon_command("osd.0", "race report")
        finally:
            await cluster.stop()
            racecheck.uninstall()

    assert racecheck.TRACKER is racecheck.NULL_RACE
    try:
        rep = asyncio.run(scenario())
    finally:
        racecheck.uninstall()
    assert rep["enabled"] is True and rep["seed"] == 7
    assert rep["reads"] > 0 and rep["writes"] > 0
    assert rep["findings"] == [], rep["findings"]
    assert racecheck.TRACKER is racecheck.NULL_RACE


# ------------------------------------------- regression: frontier re-arm


def test_frontier_rearm_when_drained_short():
    """batch-smoke seed 2, wedge #1: every open frontier entry resolved
    (some ok=False — their acks died with a crashed peer) leaves the
    pipeline DRAINED with the watermark short of the log head, and no
    later ack or map change is coming — without a re-arm the primary is
    incomplete forever on an idle pool.  _frontier_done must arm the
    recovery retry exactly then."""
    from ceph_tpu.cluster.pg import PGLogMixin, PGState
    from ceph_tpu.osdmap.osdmap import PGid
    from ceph_tpu.utils import PerfCounters

    class _Store:
        def omap_get(self, coll, oid):
            return {}

        def queue_transaction(self, txn):
            pass

    class _Host(PGLogMixin):
        osd_id = 0

        def __init__(self):
            self.store = _Store()
            self.perf = PerfCounters("t")
            self.retries = []

        def _queue_recovery_retry(self, st):
            self.retries.append(st)

    h = _Host()
    st = PGState(PGid(1, 0))
    st.primary = 0
    for v in ((1, 1), (1, 2)):
        h._frontier_open(st, v)
    st.last_update = (1, 2)
    h._frontier_done(st, (1, 1), ok=True)
    assert h.retries == []          # (1,2) still open: not drained
    h._frontier_done(st, (1, 2), ok=False)   # acks lost: resolves dirty
    assert not st.pipeline_pending
    assert st.last_complete == (1, 1) and st.last_update == (1, 2)
    assert h.retries == [st], "drained-short frontier did not re-arm"

    # watermark AT the head after a clean drain: no spurious re-arm
    h2 = _Host()
    st2 = PGState(PGid(1, 1))
    st2.primary = 0
    h2._frontier_open(st2, (1, 1))
    st2.last_update = (1, 1)
    h2._frontier_done(st2, (1, 1), ok=True)
    assert h2.retries == []

    # a REPLICA never self-arms (peering is primary-driven)
    h3 = _Host()
    st3 = PGState(PGid(1, 2))
    st3.primary = 7
    h3._frontier_open(st3, (1, 1))
    st3.last_update = (1, 1)
    h3._frontier_done(st3, (1, 1), ok=False)
    assert h3.retries == []


# --------------------------------- regression: planar rewind attr restore


def test_planar_rewind_restores_attrs_and_version():
    """batch-smoke seed 2, wedge #2: rewinding a divergent planar-at-rest
    write restored the old PLANES but left the divergent write's
    size/hinfo_crc/version attrs stamped — old data under a new crc, so
    the member failed verify-on-read on every later gather (and with two
    of k+m=3 members rewound, the object was unreadable AND unrepairable).
    Attrs and version must roll back with the bytes."""
    from ceph_tpu.cluster.backend_ec import ECBackendMixin
    from ceph_tpu.cluster.pg import PGLogMixin, PGState
    from ceph_tpu.cluster.pglog import LogEntry, PGLog
    from ceph_tpu.cluster.store import MemStore, Transaction
    from ceph_tpu.ec import planar_store
    from ceph_tpu.osdmap.osdmap import PGid
    from ceph_tpu.utils import PerfCounters

    class _Host(ECBackendMixin, PGLogMixin):
        osd_id = 0

        def __init__(self):
            self.store = MemStore()
            self.perf = PerfCounters("t")

    h = _Host()
    pgid = PGid(1, 0)
    coll = f"pg_{pgid.pool}_{pgid.seed}"
    h.store.queue_transaction(Transaction().create_collection(coll))

    def planar_blob(byte: bytes, n: int) -> bytes:
        return planar_store.planes_to_blob(
            planar_store.shard_to_planes(byte * n, seam=None))

    # v1: the committed generation (64-byte shard, logical size 120)
    h._apply_shard(pgid, "obj", 0, planar_blob(b"A", 64), 0, 64,
                   {"size": 120, "version": 1},
                   layout=planar_store.LAYOUT_PLANAR)
    v1_planes = h.store.read_planar(coll, "obj")
    v1_attrs = {k: h.store.getattr(coll, "obj", k)
                for k in ("shard", "size", "hinfo_crc")}
    assert v1_attrs["hinfo_crc"] is not None

    # v2: the divergent write (different bytes AND size)
    h._apply_shard(pgid, "obj", 0, planar_blob(b"B", 72), 0, 72,
                   {"size": 130, "version": 2},
                   layout=planar_store.LAYOUT_PLANAR)
    assert h.store.getattr(coll, "obj", "size") == b"130"
    assert h.store.getattr(coll, "obj", "hinfo_crc") != \
        v1_attrs["hinfo_crc"]

    st = PGState(pgid)
    st.log = PGLog(entries=[
        LogEntry(op="modify", oid="obj", version=(1, 1)),
        LogEntry(op="modify", oid="obj", version=(1, 2))])
    st.last_update = (1, 2)
    h.rewind_divergent_log(st, (1, 1))

    assert h.store.read_planar(coll, "obj") == v1_planes
    assert h.store.object_layout(coll, "obj") == \
        planar_store.LAYOUT_PLANAR
    for name, want in v1_attrs.items():
        assert h.store.getattr(coll, "obj", name) == want, \
            f"attr {name!r} not rolled back with the planes"
    assert h.store.get_version(coll, "obj") == 1


# ------------------------------------------------------- the race smokes


# the scenario's verdict waits a bounded time for recovery to converge:
# under the suite's six workers that budget was missed once in seven
# whole runs (PR 29: "obj1 unreadable", PG_RECOVERING at the verdict),
# never in 55 runs of the test alone or ten at a time
@pytest.mark.chaos
@contention_retry()
def test_race_smoke_batch_seeds():
    """Tier-1 dynamic gate: shrunk batch-smoke under the perturbed loop
    with the tracker armed, three seeds.  Seed 2 is the one that
    convicted both regression anchors above — green here means the
    fixes hold UNDER the hostile interleavings, not just on FIFO."""
    keys = {}
    for seed in (1, 2, 3):
        verdict, report, digest = race_run("batch-smoke", seed,
                                           shrink=True)
        assert verdict.passed, (seed, verdict.failures)
        assert report["findings"] == [], (seed, report["findings"])
        # the probes flowed: a silently unprobed run would pass forever
        assert report["reads"] > 0 and report["writes"] > 0
        assert racecheck.TRACKER is NULL_RACE    # restored after the run
        keys[seed] = verdict.replay_key()
    # seeded replay: same seed -> same resolved schedule and outcome
    # (trace digests are NOT asserted for cluster runs — select()
    # readiness order is the OS's; the IO-free tests above pin digests)
    v2, _, _ = race_run("batch-smoke", 1, shrink=True)
    assert v2.replay_key() == keys[1]


@pytest.mark.race
@pytest.mark.chaos
@pytest.mark.parametrize("scenario", ["batch-smoke", "overload-smoke",
                                      "smoke"])
def test_race_full_scenarios(scenario):
    """Full-scale sanitizer pass (slow-implied via the race marker):
    whole scenarios under the shim, two seeds each."""
    for seed in (1, 2):
        verdict, report, _ = race_run(scenario, seed)
        assert verdict.passed, (scenario, seed, verdict.failures)
        assert report["findings"] == [], (scenario, seed,
                                          report["findings"])
