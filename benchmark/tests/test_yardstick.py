"""The yardstick's own arithmetic: loader, plan, percentile, peaks and
cost functions, trace reduction.  No cluster, no chip."""

import json
import os
import shutil

import pytest

from benchmark.harness import layers, peaks, xplane
from benchmark.harness.loader import BENCH_DIR, ROOT, BenchmarkError, load_cell
from benchmark.harness.plan import Plan
from benchmark.harness.stats import percentile

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "k2m1_write_slice.xplane.pb.gz")


# ------------------------------------------------------------------ loader

def test_every_cell_of_the_benchmark_loads():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = load_cell(w["name"])
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) > 1
        assert cell.per_layer
        Plan(cell.traffic, 1)
        for name, reader in cell.per_layer.items():
            assert reader["kind"] in layers.KINDS, name


def test_new_cell_config_traffic_and_metric_are_files_only(tmp_path):
    """A later PR adds a cell by adding files and entries: nothing under
    harness/ is edited (it is not even copied here)."""
    root = tmp_path
    bench = root / "benchmark"
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(BENCH_DIR, sub), bench / sub)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    cfg = json.load(open(bench / "configs" / "rados_k2m1_3osd.json"))
    cfg.update(osds=5, pg_num=32)
    (bench / "configs" / "rados_k2m1_5osd.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "write_64k_t16.json").write_text(json.dumps({
        "loop": "closed", "callers": 16, "ops": {"write_full": 1},
        "object_bytes": 65536, "payload_pool": 16, "lead_in_s": 3.0}))
    (bench / "layer_metrics" / "lock_ms.write.json").write_text(json.dumps({
        "layer": "OSD dispatch and tick batcher", "unit": "ms",
        "kind": "attribution_ms", "op": "write_full",
        "stages": ["lock:pg.lock"]}))
    spec["configs"].append({
        "name": "rados_k2m1_5osd", "source": "x",
        "file": "benchmark/configs/rados_k2m1_5osd.json",
        "reduced": ["osds"], "why": "y"})
    spec["workloads"].append({
        "name": "k2m1_5osd_write_64k_t16", "config": "rados_k2m1_5osd",
        "traffic": "write_64k_t16", "chips": 1, "why": "z"})
    for m in spec["end_to_end"]:
        if "workloads" in m and m["name"].startswith("write_"):
            m["workloads"].append("k2m1_5osd_write_64k_t16")
    spec["per_layer"].append({
        "name": "lock_ms.write", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "OSD dispatch and tick batcher",
        "moves": "write_MBps", "workloads": ["k2m1_5osd_write_64k_t16"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = load_cell("k2m1_5osd_write_64k_t16", root=str(root),
                     bench_dir=str(bench))
    assert cell.config["osds"] == 5
    assert cell.traffic["object_bytes"] == 65536
    assert cell.end_to_end == ["write_MBps", "write_p95_ms", "setup_s"]
    assert list(cell.per_layer) == ["lock_ms.write"]
    rep = {"ops": 4, "stages": {"lock:pg.lock": {"s": 0.02}}}
    readings = layers.Readings(cell.config, "TPU v5 lite",
                               {"write_full": rep}, {}, {}, None)
    assert layers.read_metric("lock_ms.write",
                              cell.per_layer["lock_ms.write"],
                              readings) == pytest.approx(5.0)


def test_loader_refuses_what_it_cannot_find(tmp_path):
    with pytest.raises(BenchmarkError, match="no workload"):
        load_cell("no_such_cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "c", "config": "k", "traffic": "t",
                       "chips": 1}],
        "configs": [{"name": "k", "file": "benchmark/configs/k.json"}],
        "end_to_end": [], "per_layer": []}))
    with pytest.raises(BenchmarkError, match="missing file"):
        load_cell("c", root=str(tmp_path),
                  bench_dir=str(tmp_path / "benchmark"))


# -------------------------------------------------------------------- plan

MIX = {"loop": "closed", "callers": 4, "ops": {"write_full": 1, "read": 3},
       "object_bytes": {"choices": [4096, 65536], "weights": [3, 1]},
       "keys": "zipf", "zipf_alpha": 1.2, "populate_objects": 32,
       "payload_pool": 4}


def _ops(plan, n=300):
    return [plan.op(c, i) for c in range(plan.callers) for i in range(n)]


def test_plan_is_a_pure_function_of_the_seed():
    big = 2**31 + 12345          # the driver's seeds pass 32 signed bits
    a, b = Plan(MIX, big), Plan(MIX, big)
    assert _ops(a) == _ops(b)
    assert a.populated == b.populated
    assert a.payload_pool() == b.payload_pool()
    # asked for out of order, an op is still the same op
    assert Plan(MIX, big).op(2, 1500) == a.op(2, 1500)
    other = Plan(MIX, big + 1)
    assert _ops(other) != _ops(a)
    assert other.payload_pool() != a.payload_pool()


def test_plan_follows_the_traffic_file():
    plan = Plan(MIX, 9)
    ops = _ops(plan, 1000)
    reads = [o for o in ops if o.kind == "read"]
    assert 0.70 < len(reads) / len(ops) < 0.80
    populated = {o.name: o for o in plan.populated}
    assert all(populated[o.name].size == o.size for o in reads)
    # zipf: the first object is read far more often than the last ones
    first = sum(1 for o in reads if o.name == "pop_000000")
    assert first > len(reads) / 8
    writes = [o for o in ops if o.kind == "write_full"]
    assert len({o.name for o in writes}) == len(writes)     # new names
    small = sum(1 for o in writes if o.size == 4096)
    assert 0.65 < small / len(writes) < 0.85
    pool = plan.payload_pool()
    assert {len(b) for b in pool[65536]} == {65536}
    assert len(set(pool[4096])) == 4        # seeded, distinct, not constant
    assert len(set(pool[4096][0])) > 200


def test_plan_refuses_open_loop_and_unknown_ops():
    with pytest.raises(BenchmarkError, match="closed"):
        Plan({**MIX, "loop": "open", "rate": 100}, 1)
    with pytest.raises(BenchmarkError, match="knows"):
        Plan({**MIX, "ops": {"append": 1}}, 1)
    with pytest.raises(BenchmarkError, match="populate_objects"):
        Plan({**MIX, "populate_objects": 0}, 1)


# -------------------------------------------------------------- percentile

def test_percentile_on_known_samples():
    v = list(range(1, 101))
    assert percentile(v, 95) == 95
    assert percentile(v, 50) == 50
    assert percentile(v, 100) == 100
    assert percentile([5.0], 95) == 5.0
    assert percentile([3, 1, 2], 50) == 2
    # 20 samples: the 95th percentile is the 19th, one sample beyond it
    assert percentile(list(range(20)), 95) == 18
    with pytest.raises(ValueError):
        percentile([], 95)
    with pytest.raises(ValueError):
        percentile([1], 0)


# --------------------------------------------------- peaks, cost functions

K2M1 = {"k": 2, "m": 1, "gf_word_bits": 8}
K4M2 = {"k": 4, "m": 2, "gf_word_bits": 8}


def test_planar_cost_on_the_hand_worked_call():
    # k2m1, one 4 MiB object = 512 stripes: kw 16, rw 8, input planes
    # 4 MiB -> 6 MiB moved, 2*8*16*8*(4 MiB/16) operations
    mib = 1 << 20
    ops, moved = peaks.planar_matmul_cost(K2M1, 4 * mib)
    assert moved == 6 * mib
    assert ops == 2 * 8 * 16 * 8 * (4 * mib // 16)
    assert ops / (4 * mib) == 128           # int8 ops per input byte
    ops, moved = peaks.planar_matmul_cost(K4M2, 4 * mib)
    assert moved == 6 * mib
    assert ops / (4 * mib) == 256


def test_roofline_share_and_its_bound():
    mib = 1 << 20
    ops, moved = peaks.planar_matmul_cost(K2M1, 32 * mib)
    t_least = moved / 819e9
    share, bound = peaks.roofline_share("TPU v5 lite", ops, moved,
                                        t_least * 4)
    assert bound == "hbm" and share == pytest.approx(25.0)
    share, bound = peaks.roofline_share("TPU v5 lite", 393e12, 1.0, 2.0)
    assert bound == "int8" and share == pytest.approx(50.0)
    with pytest.raises(KeyError, match="not in the benchmark's peaks"):
        peaks.peaks_for("TPU v9")


# --------------------------------------------------------- trace reduction

def test_interval_arithmetic():
    evs = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 2),
           ("a", 100, 1)]
    assert xplane.busy_ns(evs) == 15 + 5 + 1
    assert xplane.totals(evs)["a"] == (2, 11)
    assert xplane.idle_gaps(evs) == [("after c", 65), ("after b", 15)]
    assert xplane.matching(evs, ["a", "d"]) == [evs[0], evs[3], evs[4]]
    assert xplane.short_name(
        "%copy.1 = u8[2,8]{1,0:T(8,128)} copy(u8[2,8]{0,1} %x)") == \
        "%copy.1 u8[2,8]"
    assert xplane.short_name("jit_f(123)") == "jit_f(123)"


@pytest.fixture(scope="module")
def recorded():
    """A 4 s slice of k2m1_write_4m_t16 on a TPU v5 lite (my chip run,
    PR 25, call 1): 12 encode ticks, read by hand with
    benchmark/describe_trace.py."""
    return xplane.TraceSummary(4.002062898, xplane.load(TRACE))


def test_recorded_trace_reduces_to_what_was_read_by_hand(recorded):
    assert [d.plane for d in recorded.devices] == ["/device:TPU:0"]
    assert len(recorded.events(xplane.MODULES_LINE)) == 24
    assert len(recorded.events(xplane.OPS_LINE)) == 120
    assert recorded.busy_s == pytest.approx(0.019197, abs=1e-6)
    tiled = xplane.matching(recorded.events(xplane.MODULES_LINE),
                            ["jit__planar_tiled"])
    assert len(tiled) == 12
    assert sum(e[2] for e in tiled) / 1e9 == pytest.approx(0.0018, abs=1e-5)
    bd = recorded.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert bd["device_ops"][0][0].startswith("%shift_right_logical.1 u8[")
    assert all(len(n) <= 102 for n, _s in bd["idle_gaps"])
    assert bd["idle_gaps"][0][1] > bd["idle_gaps"][-1][1] > 0


def test_trace_readers_on_the_recorded_slice(recorded):
    # the slice's 12 ticks: 3 of 8 objects, 8 of 1, 1 of 4 (module names
    # by shape) = 36 objects' worth of 4 MiB input planes
    work = {"planar_matmul_bytes": 36 * 4 * (1 << 20)}
    r = layers.Readings(K2M1, "TPU v5 lite", {}, {}, work, recorded)
    idle = layers.read_metric("i", {"kind": "trace_idle_share"}, r)
    assert idle == pytest.approx(100 * (1 - 0.019197 / 4.002063), abs=1e-3)
    roof = layers.read_metric("r", {
        "kind": "trace_roofline", "line": "XLA Modules",
        "patterns": ["jit__planar_tiled"],
        "cost_function": "planar_matmul_encode",
        "counter": "planar_matmul_bytes"}, r)
    # 36 * 6 MiB moved at 819 GB/s = 0.2765 ms, over 1.8 ms of kernels
    assert roof == pytest.approx(100 * 0.0002765 / 0.0018, rel=0.01)
    assert 0 < roof < 100
    per_call = layers.read_metric("p", {
        "kind": "trace_program_ms", "line": "XLA Modules",
        "patterns": ["jit__batch_to_planes_bitpack"]}, r)
    assert per_call == pytest.approx(1e3 * 0.017396 / 12, rel=0.01)
    # nothing to read: the metric is left out, not reported as zero
    empty = layers.Readings(K2M1, "TPU v5 lite", {}, {}, {}, None)
    for reader in ({"kind": "trace_idle_share"},
                   {"kind": "trace_program_ms", "line": "XLA Modules",
                    "patterns": ["x"]},
                   {"kind": "counter_ratio", "numerator": "a",
                    "denominator": "b"},
                   {"kind": "attribution_ms", "op": "read", "stages": []}):
        assert layers.read_metric("m", reader, empty) is None
    with pytest.raises(BenchmarkError, match="source kind"):
        layers.read_metric("m", {"kind": "guess"}, empty)


def test_counter_ratio():
    r = layers.Readings(K2M1, "TPU v5 lite", {},
                        {"ec_coalesced_ops": 87, "ec_coalesced_ticks": 42,
                         "ec_stripe_pad_bytes": 1, "ec_planar_ingest_bytes": 4},
                        {}, None)
    assert layers.read_metric("o", {
        "kind": "counter_ratio", "numerator": "ec_coalesced_ops",
        "denominator": "ec_coalesced_ticks"}, r) == pytest.approx(87 / 42)
    assert layers.read_metric("p", {
        "kind": "counter_ratio", "numerator": "ec_stripe_pad_bytes",
        "denominator": "ec_planar_ingest_bytes", "scale": 100}, r) == 25.0
