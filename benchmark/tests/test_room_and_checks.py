"""Room in the stores, and a result that says which check failed and how:
the helpers behind ``store_fill_peak``, ``host_mem_available_gib`` and the
two ``*_read_errors``, the result's last line, and the sizes the four
deployments ask for.  No cluster, no chip."""

import asyncio
import json
import os

import pytest

from benchmark import run as runmod
from benchmark.harness import cell as cellmod
from benchmark.harness.loader import BENCH_DIR, load_cell

GIB = 1 << 30
# what each deployment's OSD held before PR 42, and the chip host the new
# sizes were set against (MemTotal as benchmark/host_touch.py read it)
DEVICE_GIB_BEFORE = {"rados_k2m1_3osd": 8, "rados_k4m2_8osd": 3,
                     "rados_isa_k8m4_12osd": 2, "rados_lrc_k4m2l3_8osd": 4,
                     "rados_shec_k6m4c3_10osd": 3.5}    # PR 44's first size
CHIP_HOST_MEMTOTAL_GIB = 45.0


# ------------------------------------------------------------------ helpers

def test_the_fill_is_the_fullest_osds():
    statfs = [(8 * GIB, 2 * GIB), (8 * GIB, 6 * GIB), (8 * GIB, 4 * GIB)]
    assert cellmod.store_fill_peak(statfs) == 0.75
    assert cellmod.store_fill_peak(iter(statfs)) == 0.75
    # a store that states no size (ObjectStore's default statfs) is not
    # a full one, and a cluster of none reads 0
    assert cellmod.store_fill_peak([(0, 0), (4 * GIB, GIB)]) == 0.25
    assert cellmod.store_fill_peak([]) == 0.0


def test_host_memory_is_meminfos_memavailable(tmp_path):
    f = tmp_path / "meminfo"
    f.write_text("MemTotal:       47185920 kB\nMemFree:         1048576 kB\n"
                 "MemAvailable:    6291456 kB\nBuffers:               0 kB\n")
    assert cellmod.host_mem_available_gib(str(f)) == 6.0
    f.write_text("MemTotal:       47185920 kB\n")
    with pytest.raises(OSError):
        cellmod.host_mem_available_gib(str(f))
    assert cellmod.host_mem_available_gib() > 0      # this host's own


class _Io:
    """Reads by name: the reference's bytes, other bytes, or a raise."""

    def __init__(self, run, differ=(), fail=()):
        self.run, self.differ, self.fail = run, set(differ), set(fail)

    async def read(self, name):
        await asyncio.sleep(0)
        if name in self.fail:
            raise OSError(5, f"no shard of {name} answered")
        good = self.run.expected(name)
        return b"\0" + good[1:] if name in self.differ else good


def _run_with_reference(names):
    lines = []
    run = cellmod.CellRun(load_cell("k2m1_write_64k_t16"), 1, 1.0, False,
                          0.0, lambda **row: lines.append(row))
    run.pool = {4: [b"abcd", b"efgh"]}
    run.reference = {n: (4, i % 2) for i, n in enumerate(names)}
    return run, lines


def test_a_read_that_raises_counts_twice_one_that_differs_once():
    names = [f"obj_{i}" for i in range(8)]
    run, lines = _run_with_reference(names)
    io = _Io(run, differ={"obj_1", "obj_5"}, fail={"obj_2"})
    bad, raised = asyncio.run(run._read_back(io, names, "verify_degraded"))
    # raised + differed in the first (its meaning before PR 42), raised
    # alone in the second
    assert (bad, raised) == (3, 1)
    assert run.errors == ["verify_degraded read obj_2: "
                          "OSError(5, 'no shard of obj_2 answered')"]
    assert lines[-1]["bad"] == 3 and lines[-1]["raised"] == 1
    run, _ = _run_with_reference(names)
    assert asyncio.run(run._read_back(_Io(run), names, "x")) == (0, 0)


def _out(checks, errors=()):
    return {"correct": all(c["ok"] for c in checks), "attempted": 60,
            "failed": 3, "metrics": {"setup_s": {"value": 20.0, "unit": "s"}},
            "end_to_end": {}, "trace": None, "errors": list(errors),
            "checks": checks}


def test_the_last_line_holds_every_check_beside_its_limit_and_the_errors():
    checks = [
        {"name": "window_failed_ops", "value": 0, "rule": "max", "limit": 0,
         "ok": True},
        {"name": "store_fill_peak", "value": 0.45, "rule": "max",
         "limit": 0.85, "ok": True},
        {"name": "host_mem_available_gib", "value": 21.5, "rule": "min",
         "limit": 4.0, "ok": True},
        {"name": "degraded_mismatches", "value": 3, "rule": "max",
         "limit": 0, "ok": False},
        {"name": "degraded_read_errors", "value": 1, "rule": "max",
         "limit": 0, "ok": False}]
    errors = ["verify_degraded read obj_2: OSError(5, 'x')"]
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    line = runmod.result_line(_out(checks, errors), device, trace=False)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-2:] == ["errors", "checks"]
    assert line["correct"] is False and line["errors"] == errors
    assert line["checks"] == {
        "window_failed_ops": {"value": 0, "limit": 0},
        "store_fill_peak": {"value": 0.45, "limit": 0.85},
        "host_mem_available_gib": {"value": 21.5, "limit": 4.0},
        "degraded_mismatches": {"value": 3, "limit": 0},
        "degraded_read_errors": {"value": 1, "limit": 0}}
    json.dumps(line)                                # it is the last line


def test_a_traced_runs_last_line_keeps_breakdown_before_the_checks():
    class Summary:
        busy_s, window_s = 0.2, 5.0

        def breakdown(self):
            return {"device_ops": [["jit__planar_tiled", 0.1]],
                    "idle_gaps": []}

    out = {**_out([]), "trace": Summary()}
    line = runmod.result_line(out, {"platform": "tpu"}, trace=True)
    assert line["device"] == {"platform": "tpu", "busy_s": 0.2,
                              "window_s": 5.0}
    assert list(line)[-3:] == ["breakdown", "errors", "checks"]


# ------------------------------------------------------------ deployments

@pytest.mark.parametrize("config", sorted(DEVICE_GIB_BEFORE))
def test_a_deployments_device_did_not_shrink_and_meets_the_host_last(config):
    with open(os.path.join(BENCH_DIR, "configs", config + ".json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    device = cfg["store_bytes_per_osd"]
    assert device >= DEVICE_GIB_BEFORE[config] * GIB
    # nearfull (the logical limit, where a run stops being `correct`) has
    # to come before the host's memory does: 85% of the cluster's devices
    # under the chip host's MemTotal less 8 GiB
    assert 0.85 * device * cfg["osds"] <= (CHIP_HOST_MEMTOTAL_GIB - 8) * GIB
    store_lines = [a for a in cfg["assumed"]
                   if a.startswith("store_bytes_per_osd")]
    assert len(store_lines) == 1
    assert f"store_bytes_per_osd {device / GIB:g} GiB" in store_lines[0]
    assert "holds a 51 s window up to" in store_lines[0]
    assert "status" not in cfg                      # every file is a cell's
    assert "does not serve today" not in json.dumps(cfg)


def test_every_cells_device_is_its_configs():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = load_cell(w["name"])
        assert cell.config["store_bytes_per_osd"] >= \
            DEVICE_GIB_BEFORE[cell.config_name] * GIB
