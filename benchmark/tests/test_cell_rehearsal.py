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
    assert [c["name"] for c in out["checks"]] == list(checks)


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

