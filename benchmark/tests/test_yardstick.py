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


def test_the_pending_cell_becomes_a_cell_by_appending_its_entries(tmp_path):
    """``pending/apply.py``: the accepted entries stay as they are, letter
    for letter, the pending ones come after them, and the cell loads with
    its traffic's set-up event, two read metrics and twelve readers.  As
    committed, BENCHMARK.json lists the six cells tier-1 pins."""
    from benchmark.pending import apply

    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    assert len(spec["workloads"]) == 6
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    os.symlink(BENCH_DIR, tmp_path / "benchmark")
    assert apply.main(["--root", str(tmp_path), "--keep"]) == 0
    after = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for key in apply.KEYS:
        assert after[key][:len(spec[key])] == spec[key]
    assert {k: v for k, v in after.items() if k not in apply.KEYS} == \
        {k: v for k, v in spec.items() if k not in apply.KEYS}
    cell = load_cell("k2m1_degraded_randread_4m_t16", root=str(tmp_path))
    assert (cell.config_name, cell.traffic_name, cell.chips) == \
        ("rados_k2m1_3osd", "degraded_randread_4m_t16", 1)
    assert cell.traffic["set_up"] == {"kill_shard_holders": 1,
                                      "victim": "most_data_shards"}
    assert cell.end_to_end == ["setup_s", "read_MBps", "read_p95_ms"]
    assert len(cell.per_layer) == 12 and \
        all(n.endswith(".read") for n in cell.per_layer)
    bounds = {m["name"]: m["bound"] for m in after["end_to_end"]}
    assert bounds["read_MBps"] == bounds["read_p95_ms"] == 0.25
    assert len(after["workloads"][-1]["why"]) <= 200
    Plan(cell.traffic, 2**31 + 5)
    with pytest.raises(ValueError, match="entries already"):
        apply.main(["--root", str(tmp_path), "--keep"])


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


def test_plan_refuses_a_set_up_key_it_does_not_know_by_name():
    with pytest.raises(BenchmarkError, match=r"\['mark_out'\].*knows"):
        Plan({**MIX, "set_up": {"kill_shard_holders": 1, "mark_out": 1}}, 1)
    with pytest.raises(BenchmarkError, match="victim one of"):
        Plan({**MIX, "set_up": {"kill_shard_holders": 1,
                                "victim": "the_slowest"}}, 1)
    with pytest.raises(BenchmarkError, match="populate_objects"):
        Plan({**MIX, "ops": {"write_full": 1}, "populate_objects": 0,
              "set_up": {"kill_shard_holders": 1}}, 1)
    assert Plan(MIX, 1).kill_shard_holders == 0     # no event, no kill


def test_set_up_victims_by_the_seed_and_by_the_map():
    held = [4, 5, 7]        # rados_k2m1_3osd: PGs an OSD holds data in
    by_seed = {"kill_shard_holders": 1}
    drawn = [Plan({**MIX, "set_up": by_seed}, s).victims(held)
             for s in range(1, 12)]
    assert drawn[:6] == [[0], [1], [0], [0], [1], [2]]
    assert {v[0] for v in drawn} == {0, 1, 2}
    big = 2**31 + 12345
    assert Plan({**MIX, "set_up": by_seed}, big).victims(held) == \
        Plan({**MIX, "set_up": by_seed}, big).victims(held)
    by_map = {"kill_shard_holders": 2, "victim": "most_data_shards"}
    for seed in (1, 2, big):
        assert Plan({**MIX, "set_up": by_map}, seed).victims(held) == [2, 1]
        assert Plan({**MIX, "set_up": by_map}, seed).victims([3, 3, 3]) \
            == [0, 1]                               # a tie: lowest id
    with pytest.raises(BenchmarkError, match="kills 3 of 3"):
        Plan({**MIX, "set_up": {"kill_shard_holders": 3}}, 1).victims(held)
    # the set-up's draw is a stream of its own: the ops do not move
    assert _ops(Plan({**MIX, "set_up": by_seed}, 9), 50) == \
        _ops(Plan(MIX, 9), 50)


# what the parent's generator (PR 45's tree, before `set_up` existed) drew
# for seed 2147520371: callers 0 and 11, ops 0..63 (payload indices as hex
# digits, a digest of the names), the populated set's payloads, two buffers
PINNED_SEED = 2147520371
PINNED = {
    "write_4m_t16": {
        0: ("write_full", "obj_c00_0000000", 12, "obj_c00_0000063", 15,
            "c40d5d4f1bf02d661933048266e29f591907fc1e61b98ce9b966a5046964b5ef",
            "136849cde9ee4fc7"),
        11: ("write_full", "obj_c11_0000000", 7, "obj_c11_0000063", 2,
             "765afc5f6260ccbf7d4b21cf0a400dd35ef489ea7b9ff009d1f804f039f21c82",
             "491625fd8378aec3"),
        "populated": "", "pool": ("546fb7078b5b46c4", "e9b8c6a4bbc0d51a")},
    "write_64k_t16": {
        0: ("write_full", "obj_c00_0000000", 12, "obj_c00_0000063", 15,
            "c40d5d4f1bf02d661933048266e29f591907fc1e61b98ce9b966a5046964b5ef",
            "136849cde9ee4fc7"),
        11: ("write_full", "obj_c11_0000000", 7, "obj_c11_0000063", 2,
             "765afc5f6260ccbf7d4b21cf0a400dd35ef489ea7b9ff009d1f804f039f21c82",
             "491625fd8378aec3"),
        "populated": "", "pool": ("3f2bab8c8adf15a7", "d592de801ebcb5b5")},
    "randread_4m_t16": {
        0: ("read", "pop_000060", 0, "pop_000024", 1,
            "0e4f3d5e7a6485da9a487e0ea46b454b663446f23336b699d982a35697b4ea71",
            "8b74b4cc727d5779"),
        11: ("read", "pop_000052", 10, "pop_000004", 13,
             "ae49ad706a810f0100af70734ea45476b0f380a0e5154132c03472bdc362b03d",
             "64b880bd4e26589d"),
        "populated":
            "3da5daa745d2744a6b5edb1413c57a9e8f6a46642b07246e60a3a69f07100ff8",
        "pool": ("546fb7078b5b46c4", "e9b8c6a4bbc0d51a")},
}


def _digest(data: bytes) -> str:
    import hashlib
    return hashlib.sha256(data).hexdigest()[:16]


@pytest.mark.parametrize("traffic", sorted(PINNED))
def test_the_accepted_traffic_files_draw_what_the_parent_drew(traffic):
    with open(os.path.join(BENCH_DIR, "traffic", traffic + ".json"),
              encoding="utf-8") as f:
        plan = Plan(json.load(f), PINNED_SEED)
    want = PINNED[traffic]
    size = plan.sizes[0]
    for caller in (0, 11):
        kind, first, p_first, last, p_last, payloads, names = want[caller]
        ops = [plan.op(caller, i) for i in range(64)]
        assert {o.kind for o in ops} == {kind}
        assert {o.size for o in ops} == {size}
        assert (ops[0].name, ops[0].payload) == (first, p_first)
        assert (ops[63].name, ops[63].payload) == (last, p_last)
        assert "".join("%x" % o.payload for o in ops) == payloads
        assert _digest(" ".join(o.name for o in ops).encode()) == names
    assert "".join("%x" % o.payload for o in plan.populated[:64]) == \
        want["populated"]
    pool = plan.payload_pool()[size]
    assert (_digest(pool[0]), _digest(pool[15])) == want["pool"]


def test_the_verification_draws_what_the_parent_drew():
    from benchmark.harness.cell import verification_plan

    names = [f"obj_c{c:02d}_{i:07d}" for c in range(16) for i in range(40)]
    healthy, degraded, victim = verification_plan(PINNED_SEED, names, 3)
    assert (healthy[:3], degraded[:3], victim) == (
        ["obj_c04_0000002", "obj_c08_0000016", "obj_c07_0000038"],
        ["obj_c11_0000011", "obj_c09_0000023", "obj_c07_0000026"], 2)
    assert _digest(" ".join(healthy + degraded).encode()) == \
        "16bc398498ae4ee3"


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


def test_planar_decode_cost_on_the_hand_worked_call():
    # k2m1, one 4 MiB object rebuilt from 1 data + 1 parity shard: the
    # call takes 4 MiB of source planes (kw 16), writes the 8 bit-rows of
    # the one lost chunk (2 MiB): 6 MiB moved, 2*8*16*8*(4 MiB/16) ops
    mib = 1 << 20
    ops, moved = peaks.planar_matmul_decode_cost(K2M1, 4 * mib)
    assert moved == 6 * mib
    assert ops == 2 * 8 * 16 * 8 * (4 * mib // 16)
    # k4m2: four chunks in, one out: 5/4 of the input moved, 8 output rows
    # whatever m is (the encode writes m chunks: 256 ops a byte)
    ops, moved = peaks.planar_matmul_decode_cost(K4M2, 4 * mib)
    assert moved == 5 * mib
    assert ops / (4 * mib) == 128
    assert peaks.COST_FUNCTIONS["planar_matmul_decode"] is \
        peaks.planar_matmul_decode_cost


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
    # the read cell's file on the same programs: at k2m1 a decode of one
    # lost chunk needs what the encode needs (6 MiB moved a 4 MiB call)
    with open(os.path.join(BENCH_DIR, "layer_metrics",
                           "planar_roofline.read.json"),
              encoding="utf-8") as f:
        assert layers.read_metric("planar_roofline.read", json.load(f),
                                  r) == pytest.approx(roof)
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
