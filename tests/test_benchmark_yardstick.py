"""The pure by-hand cases of ``benchmark/tests/test_yardstick.py`` that
guard a cell being listed, copied here by PR 48 so that the tier-1 floor
counts them (PERF.md section 7 row 0c (4)): the ``set_up`` key refused by name,
the victims by seed and by map, ``planar_matmul_decode_cost`` on the
hand-worked call, and the accepted traffic files' first ops, populated
sets, payload buffers and verification samples pinned to the generator of
PR 45.  They import from ``benchmark/`` and edit nothing there; the
originals stay where they are (by hand:
``python -m pytest benchmark/tests``).  No cluster, no chip."""

import json
import os

import pytest

from benchmark.harness import peaks
from benchmark.harness.loader import BENCH_DIR, BenchmarkError
from benchmark.harness.plan import Plan

MIX = {"loop": "closed", "callers": 4, "ops": {"write_full": 1, "read": 3},
       "object_bytes": {"choices": [4096, 65536], "weights": [3, 1]},
       "keys": "zipf", "zipf_alpha": 1.2, "populate_objects": 32,
       "payload_pool": 4}


def _ops(plan, n=300):
    return [plan.op(c, i) for c in range(plan.callers) for i in range(n)]


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


K2M1 = {"k": 2, "m": 1, "gf_word_bits": 8}
K4M2 = {"k": 4, "m": 2, "gf_word_bits": 8}


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



def test_the_degraded_cells_traffic_reads_its_populated_set_after_a_kill():
    """``degraded_randread_4m_t16``: 16 callers, every op a ``read`` of
    one of the 512 populated 4 MiB objects, drawn uniformly; the set-up
    kills the holder of most data shards, whatever the seed."""
    with open(os.path.join(BENCH_DIR, "traffic",
                           "degraded_randread_4m_t16.json"),
              encoding="utf-8") as f:
        traffic = json.load(f)
    for seed in (1, PINNED_SEED):
        plan = Plan(traffic, seed)
        assert (plan.callers, plan.kill_shard_holders) == (16, 1)
        assert plan.victims([4, 5, 7]) == [2]       # most_data_shards
        names = {o.name for o in plan.populated}
        assert len(names) == 512
        ops = _ops(plan, 64)
        assert {o.kind for o in ops} == {"read"}
        assert {o.size for o in ops} == {4 << 20}
        assert {o.name for o in ops} <= names
        assert len({o.name for o in ops}) > 400     # uniform, not skewed
