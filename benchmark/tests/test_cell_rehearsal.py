"""The harness at tiny size on the CPU: everything of a run but the look
for a chip.  The host GF engine is switched off in the test (as
tests/test_batch_dataplane.py does), so the device branches serve."""

import asyncio
import os
import time

import pytest

from benchmark.harness import cell as cellmod
from benchmark.harness.loader import BENCH_DIR, Cell, _read_json, load_cell

TINY = {"object_bytes": 65536, "callers": 4, "payload_pool": 4,
        "lead_in_s": 0.3}


WRITE_LAYERS = ("client_ms.write", "wire_ms.write", "batch_wait_ms.write",
                "ops_per_tick.write", "batch_encode_ms.write",
                "pad_share.write", "sub_write_wait_ms.write",
                "store_commit_ms.write", "planar_roofline.write",
                "device_idle.write")
READ_LAYERS = ("client_ms.read", "wire_ms.read", "egress_ms.read",
               "sub_read_wait_ms.read", "device_idle.read")
DEGRADED = "k2m1_degraded_randread_4m_t16"
# what a cell with no set_up event says and checks, in this order, as the
# parent's `_verify` did (PR 45's tree)
WRITE_STEPS = ["room", "verify_healthy", "kill_osd", "verify_degraded"]
WRITE_CHECKS = ["window_failed_ops", "store_fill_peak",
                "host_mem_available_gib", "objects_to_verify",
                "healthy_mismatches", "healthy_read_errors",
                "degraded_mismatches", "degraded_read_errors",
                "degraded_decode_ticks", "device_matmul_calls",
                "host_engine_calls", "window_encode_ticks",
                "window_matmul_bytes"]


def tiny_cell(name, **traffic):
    c = load_cell(name)
    c.traffic = {**c.traffic, **TINY, **traffic}
    return c


def cell_from_files(config, traffic, op, layer_names):
    """A cell the benchmark does not list (yet), straight from its files
    under configs/, traffic/ and layer_metrics/, at tiny size."""
    def read(*parts):
        return _read_json(os.path.join(BENCH_DIR, *parts))

    return Cell(f"{config}.{traffic}", 1, config,
                read("configs", config + ".json"), traffic,
                {**read("traffic", traffic + ".json"), **TINY},
                [f"{op}_MBps", f"{op}_p95_ms", "setup_s"],
                {n: read("layer_metrics", n + ".json") for n in layer_names})


def run_cell(cell, seed=5, seconds=1.5, trace=False, say=None):
    lines = []
    run = cellmod.CellRun(cell, seed, seconds, trace, started_at=0.0,
                          say=say or (lambda **row: lines.append(row)))
    out = asyncio.run(run.run())
    out["lines"] = lines
    return out


@pytest.fixture
def device_engine(monkeypatch):
    from ceph_tpu.ec import stripe

    monkeypatch.setattr(stripe, "_host_engine_ok", lambda codec: False)


def k4m2_cell():
    # waits as an open question (PERF.md): it stalls on the chip at full
    # size; its files stay rehearsed here
    return cell_from_files("rados_k4m2_8osd", "write_4m_t16", "write",
                           WRITE_LAYERS)


@pytest.mark.parametrize("make", [lambda: tiny_cell("k2m1_write_4m_t16"),
                                  k4m2_cell], ids=["k2m1", "k4m2"])
def test_write_cell_serves_and_verifies(device_engine, make):
    out = run_cell(make())
    checks = {r["check"]: r for r in out["lines"] if "check" in r}
    assert out["correct"], checks
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"write_MBps", "write_p95_ms", "setup_s"}
    assert out["metrics"]["write_MBps"]["value"] > 0
    assert checks["degraded_decode_ticks"]["value"] >= 1
    assert checks["host_engine_calls"]["value"] == 0
    assert out["attempted"] > cellmod.HEALTHY_SAMPLE
    # the room it had: a tiny window in a deployment's device, this host
    assert 0 < checks["store_fill_peak"]["value"] < 0.01
    assert checks["store_fill_peak"]["limit"] == 0.85
    assert checks["host_mem_available_gib"]["limit"] == 4.0
    assert checks["healthy_read_errors"]["value"] == 0
    assert checks["degraded_read_errors"]["value"] == 0
    assert out["errors"] == []
    assert [c["name"] for c in out["checks"]] == list(checks) == WRITE_CHECKS
    steps = [r["step"] for r in out["lines"] if "step" in r]
    assert steps[-len(WRITE_STEPS):] == WRITE_STEPS
    assert not any(s.startswith(("kill_in", "warm_read", "warm_decode"))
                   for s in steps)


def test_traced_run_reports_layer_metrics(device_engine):
    out = run_cell(tiny_cell("k2m1_write_4m_t16"), trace=True)
    assert out["correct"]
    got = out["metrics"]
    for name in ("client_ms.write", "wire_ms.write", "batch_wait_ms.write",
                 "ops_per_tick.write", "batch_encode_ms.write",
                 "pad_share.write", "sub_write_wait_ms.write",
                 "store_commit_ms.write"):
        assert got[name]["value"] >= 0, name
    # no profiler trace here: the trace readers find nothing and the
    # metrics are left out, not reported as zero
    assert "device_idle.write" not in got
    assert "planar_roofline.write" not in got
    att = [r for r in out["lines"] if r.get("attribution") == "write_full"]
    assert att and att[0]["wall_coverage"] > 0.5


# ------------------------------------------- `correct` can come out false

def test_control_breaks_parity_and_the_degraded_sample_sees_it(
        device_engine, monkeypatch):
    """benchmark/control.py's fault at test size: one bit of the planar
    bit-matrix flipped.  Healthy reads pass (systematic code); only the
    degraded sample can fail."""
    from benchmark import control
    from ceph_tpu.ops import gf8

    monkeypatch.setattr(gf8, "planar_matmul", gf8.planar_matmul)
    control.break_parity()
    out = run_cell(tiny_cell("k2m1_write_4m_t16"))
    checks = {r["check"]: r for r in out["lines"] if "check" in r}
    assert not out["correct"]
    assert checks["healthy_mismatches"]["value"] == 0
    assert checks["degraded_mismatches"]["value"] > 0
    assert out["failed"] == checks["degraded_mismatches"]["value"]
    # they decoded and DIFFERED: no read raised, and nothing was said
    assert checks["degraded_read_errors"]["value"] == 0
    assert out["errors"] == []


def test_one_flipped_stored_bit_makes_correct_false(device_engine,
                                                    monkeypatch):
    """After the window, one bit of one stored shard is flipped on an OSD
    that survives, in an object of the degraded sample: its crc no longer
    matches, with the victim down there is no spare shard to rebuild it
    from, and the read fails."""
    sound_verify = cellmod.CellRun._verify
    flipped = []

    async def rot_then_verify(self, cluster, io, window_names, *rest):
        healthy, degraded, victim = cellmod.verification_plan(
            self.seed, window_names, len(cluster.osds))
        target = next(n for n in degraded if n not in healthy)
        for osd_id, osd in cluster.osds.items():
            if osd_id == victim or flipped:
                continue
            for coll in osd.store.list_collections():
                for oid in osd.store.list_objects(coll):
                    if target in str(oid) and not flipped:
                        osd.store.debug_bitrot(coll, oid, 12345)
                        flipped.append((osd_id, coll, oid))
        return await sound_verify(self, cluster, io, window_names, *rest)

    monkeypatch.setattr(cellmod.CellRun, "_verify", rot_then_verify)
    out = run_cell(tiny_cell("k2m1_write_4m_t16"))
    checks = {r["check"]: r for r in out["lines"] if "check" in r}
    assert len(flipped) == 1
    assert checks["degraded_mismatches"]["value"] == 1
    assert checks["healthy_mismatches"]["value"] == 0
    assert not out["correct"] and out["failed"] == 1
    # that read RAISED: counted in both checks, once in `failed`, and the
    # result says what it said
    assert checks["degraded_read_errors"]["value"] == 1
    assert checks["healthy_read_errors"]["value"] == 0
    assert len(out["errors"]) == 1
    assert out["errors"][0].startswith("verify_degraded read obj_")


def test_a_device_too_small_for_the_window_fails_by_its_fill_alone(
        device_engine, monkeypatch):
    """Objects go to new names and stay.  A fixed number of writes into
    devices sized so that they end 90% full: over nearfull (0.85), where
    the run is no longer ``correct``, under the full ratio (0.95), so no
    write is refused and no other check fails."""
    ops_a_caller, callers, size = 10, TINY["callers"], TINY["object_bytes"]

    async def paced_caller(self, io, c, stop):
        for i in range(ops_a_caller):
            op = self.plan.op(c, i)
            t0 = time.perf_counter()
            await self._write(io, op)
            self.records.append((t0, time.perf_counter(), op.kind, op.size,
                                 True))
            await asyncio.sleep(0.1)    # most of them inside the window
        await stop.wait()

    monkeypatch.setattr(cellmod.CellRun, "_caller", paced_caller)
    cell = tiny_cell("k2m1_write_4m_t16")
    # warm-up bursts of 1, 2, 4, then the callers': each object leaves a
    # shard of half its size on every one of the three OSDs
    objects = 1 + 2 + 4 + callers * ops_a_caller
    used = objects * size // 2
    cell.config = {**cell.config, "store_bytes_per_osd": int(used / 0.9)}
    out = run_cell(cell, seconds=3.0)
    checks = {r["check"]: r for r in out["lines"] if "check" in r}
    assert checks["store_fill_peak"]["value"] == pytest.approx(0.9, abs=1e-3)
    assert not checks["store_fill_peak"]["ok"]
    assert [n for n, c in checks.items() if not c["ok"]] == \
        ["store_fill_peak"]
    assert not out["correct"] and out["failed"] == 0 and out["errors"] == []


def test_a_host_engine_call_makes_correct_false():
    """On a CPU backend the program computes parity on the host GF
    engine: every byte reads back, and ``correct`` is still false."""
    out = run_cell(tiny_cell("k2m1_write_4m_t16"))
    checks = {r["check"]: r for r in out["lines"] if "check" in r}
    assert checks["healthy_mismatches"]["value"] == 0
    assert checks["degraded_mismatches"]["value"] == 0
    assert checks["host_engine_calls"]["value"] > 0
    assert not checks["host_engine_calls"]["ok"]
    assert not out["correct"]


def test_cli_refuses_a_cpu_before_any_cluster_starts():
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "k2m1_write_4m_t16", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""            # no result, no cluster line
    assert "no CPU fallback" in proc.stderr


def test_read_traffic_populates_reads_and_verifies(device_engine):
    """The read path of the harness (no cell uses it yet: PERF.md, open
    questions): population in set-up, uniform reads in the window, the
    populated set verified healthy and degraded."""
    cell = cell_from_files("rados_k2m1_3osd", "randread_4m_t16", "read",
                           READ_LAYERS)
    out = run_cell(cell, trace=True)
    checks = {r["check"]: r for r in out["lines"] if "check" in r}
    assert out["correct"], checks
    assert checks["objects_to_verify"]["value"] == 64
    assert set(out["end_to_end"]) == {"read_MBps", "read_p95_ms", "setup_s"}
    assert out["end_to_end"]["read_MBps"]["value"] > 0
    assert set(out["metrics"]) == {"client_ms.read", "wire_ms.read",
                                   "egress_ms.read", "sub_read_wait_ms.read"}
    window = [r for r in out["lines"] if "window_counters" in r][0]
    # healthy reads never reach the device: the cell would bypass it
    assert "planar_matmul_calls" not in window["window_counters"]



# ------------------------------------------- a pool degraded in set-up

@pytest.fixture
def applied(tmp_path):
    """A root whose BENCHMARK.json has the pending cells' entries, as
    ``benchmark/pending/apply.py`` would leave the repo's."""
    import json

    from benchmark.pending import apply
    from benchmark.harness.loader import ROOT

    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    for name in apply.pending_files():
        with open(name, encoding="utf-8") as f:
            spec = apply.merged(spec, json.load(f))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    os.symlink(BENCH_DIR, tmp_path / "benchmark")
    return str(tmp_path)


@pytest.fixture
def degraded_cell(applied):
    def make(victim="seed", **traffic):
        cell = load_cell(DEGRADED, root=applied)
        cell.traffic = {**cell.traffic, **TINY, "populate_objects": 48,
                        **traffic}
        cell.traffic["set_up"] = {"kill_shard_holders": 1, "victim": victim}
        return cell
    return make


def by_name(out, key):
    return {r[key]: r for r in out["lines"] if key in r}


# the seed draws the victim: 1 -> osd.0, 2 -> osd.1, 6 -> osd.2; the rule
# "most_data_shards" names osd.2 on this map whatever the seed (it holds a
# data shard in 7 PGs of 8; osd.0 in 4, osd.1 in 5)
@pytest.mark.parametrize("seed, rule, victim", [
    (1, "seed", 0), (2, "seed", 1), (6, "seed", 2),
    (1, "most_data_shards", 2)])
def test_degraded_cell_serves_and_verifies_with_each_osd_down(
        device_engine, degraded_cell, seed, rule, victim):
    out = run_cell(degraded_cell(rule), seed=seed, trace=True)
    checks, steps = by_name(out, "check"), by_name(out, "step")
    assert out["correct"], checks
    assert steps["kill_in_set_up"]["victims"] == [victim]
    assert steps["kill_in_set_up"]["data_shard_pgs"] == [4, 5, 7]
    # set-up in this order, each step with its seconds; no second kill
    order = [r["step"] for r in out["lines"] if "step" in r]
    mine = [s for s in order if s in (
        "populate", "verify_healthy", "kill_in_set_up", "warm_read",
        "warm_decode_ticks", "room", "kill_osd", "verify_degraded")]
    assert mine == ["populate", "verify_healthy", "kill_in_set_up",
                    "warm_read", "warm_read", "warm_read",
                    "warm_decode_ticks", "room", "verify_degraded"]
    assert all("seconds" in r for r in out["lines"]
               if r.get("step") in mine and r["step"] != "room")
    assert steps["warm_decode_ticks"]["programs"] == TINY["callers"]
    assert [c["name"] for c in out["checks"]] == [
        "window_failed_ops", "store_fill_peak", "host_mem_available_gib",
        "objects_to_verify", "healthy_mismatches", "healthy_read_errors",
        "degraded_mismatches", "degraded_read_errors",
        "degraded_decode_ticks", "device_matmul_calls", "host_engine_calls",
        "window_read_mismatches", "window_decode_ticks",
        "window_decoded_reads"]
    assert checks["objects_to_verify"]["value"] == 48
    assert checks["window_read_mismatches"]["value"] == 0
    assert checks["window_decode_ticks"]["value"] >= 1
    window = [r for r in out["lines"] if "window_counters" in r][0]
    done = window["window_counters"]["ec_coalesced_reads"]
    assert checks["window_decoded_reads"]["value"] == done
    assert done >= checks["window_decoded_reads"]["limit"] > 0
    # every decode tick's program was met in set-up
    assert window["compiles_in_window"] == 0
    assert window["osdmap_epochs_in_window"] == 0
    assert set(out["end_to_end"]) == {"read_MBps", "read_p95_ms", "setup_s"}
    got = out["metrics"]
    for name in ("client_ms.read", "wire_ms.read", "batch_wait_ms.read",
                 "reads_per_tick.read", "batch_decode_ms.read",
                 "egress_ms.read", "sub_read_wait_ms.read",
                 "subreads_per_decode.read", "second_round_share.read",
                 "loop_busy_share.read"):
        assert got[name]["value"] >= 0, name
    assert got["reads_per_tick.read"]["value"] >= 1
    assert got["subreads_per_decode.read"]["value"] == 1.0     # k - 1
    assert got["second_round_share.read"]["value"] == 0.0
    assert got["batch_decode_ms.read"]["value"] > 0
    assert "planar_roofline.read" not in got and \
        "device_idle.read" not in got       # no profiler trace here
    said = [r for r in out["lines"] if r.get("op") == "read"][0]
    assert out["attempted"] == said["window_ops"] + 32 + 16
    assert out["failed"] == 0


@pytest.mark.parametrize("last_source_too", [False, True])
def test_control_makes_the_degraded_cell_not_correct_by_its_window(
        device_engine, degraded_cell, monkeypatch, last_source_too):
    """The populated objects are encoded AND decoded by the broken
    matrices.  With the one bit alone a rebuilt chunk 1 comes out right
    (the two flips cancel) and only a rebuilt chunk 0 differs; with the
    last source's bit too (what control.py flips in such a cell) every
    decoded read differs."""
    from benchmark import control
    from ceph_tpu.ops import gf8

    monkeypatch.setattr(gf8, "planar_matmul", gf8.planar_matmul)
    control.break_parity(last_source_too=last_source_too)
    out = run_cell(degraded_cell("most_data_shards"))
    checks = by_name(out, "check")
    assert not out["correct"]
    assert checks["healthy_mismatches"]["value"] == 0
    wrong = checks["window_read_mismatches"]["value"]
    assert wrong > 0 and checks["degraded_mismatches"]["value"] > 0
    assert checks["window_failed_ops"]["value"] == 0    # none raised
    window = [r for r in out["lines"] if "window_counters" in r][0]
    decoded = window["window_counters"]["ec_coalesced_reads"]
    if last_source_too:
        assert wrong >= decoded         # the warm-up's and lead-in's too
    else:
        assert wrong < decoded
    assert out["failed"] >= wrong
    assert out["errors"] and "differs from the reference" in out["errors"][0]
    assert len(out["errors"]) <= cellmod.ERRORS_KEPT


def test_a_corrupted_surviving_shard_fails_the_degraded_window(
        device_engine, degraded_cell, monkeypatch):
    """One bit of one stored shard flipped on an OSD that survives, before
    the kill: with the victim down there is no spare shard to rebuild it
    from, every read of that object raises, and the loop's own reads say
    so (`window_failed_ops`)."""
    sound_kill = cellmod.CellRun._kill_in_set_up
    flipped = []

    async def rot_then_kill(self, cluster, client, pool_id):
        await sound_kill(self, cluster, client, pool_id)
        for osd in cluster.osds.values():       # survivors only
            for coll in osd.store.list_collections():
                for oid in osd.store.list_objects(coll):
                    if "pop_000007" in str(oid) and not flipped:
                        osd.store.debug_bitrot(coll, oid, 12345)
                        flipped.append((osd.osd_id, coll, oid))

    monkeypatch.setattr(cellmod.CellRun, "_kill_in_set_up", rot_then_kill)
    # 8 populated objects: the loop cannot miss pop_000007
    out = run_cell(degraded_cell("most_data_shards", populate_objects=8))
    checks = by_name(out, "check")
    assert len(flipped) == 1
    assert not out["correct"]
    assert checks["window_failed_ops"]["value"] >= 1
    assert checks["window_read_mismatches"]["value"] >= 0
    assert checks["healthy_mismatches"]["value"] == 0   # read while whole
    assert any("pop_000007" in e for e in out["errors"])


def test_a_kill_that_does_not_settle_ends_the_run(device_engine,
                                                  degraded_cell,
                                                  monkeypatch):
    from benchmark.harness.loader import BenchmarkError

    monkeypatch.setattr(cellmod, "SETTLE_S", 0.0)
    with pytest.raises(BenchmarkError, match="did not settle in 0 s"):
        run_cell(degraded_cell())
