"""The numpy host walk (``ceph_tpu/crush/hostvec.py``) against the scalar
reference (``crush/scalar.py``), x for x: every rule shape it takes,
healthy and reweighted maps, the retry tails of pools that take all of
their hosts, and the maps and rules it has to refuse."""

import random

import numpy as np
import pytest

from ceph_tpu.crush.hostvec import HostVecMapper
from ceph_tpu.crush.scalar import ScalarMapper
from ceph_tpu.crush.types import (
    CRUSH_ITEM_NONE,
    RULE_CHOOSELEAF_FIRSTN,
    RULE_CHOOSELEAF_INDEP,
    RULE_CHOOSE_FIRSTN,
    RULE_CHOOSE_INDEP,
    RULE_EMIT,
    RULE_SET_CHOOSELEAF_TRIES,
    RULE_SET_CHOOSELEAF_VARY_R,
    RULE_SET_CHOOSE_TRIES,
    RULE_TAKE,
    Bucket,
    CrushMap,
    Rule,
    Tunables,
    build_hierarchy,
    build_three_level,
)

# hosts, OSDs a host, firstn, numrep
SHAPES = [
    pytest.param(12, 1, False, 12, id="12x1_indep12"),   # rados_isa_k8m4_12osd
    pytest.param(8, 1, False, 6, id="8x1_indep6"),       # rados_k4m2_8osd
    pytest.param(3, 1, False, 3, id="3x1_indep3"),       # rados_k2m1_3osd
    pytest.param(4, 4, True, 3, id="4x4_firstn3"),
    pytest.param(6, 4, False, 6, id="6x4_indep6"),
    pytest.param(5, 3, True, 5, id="5x3_firstn5"),
    pytest.param(5, 3, False, 7, id="5x3_indep7"),       # more slots than hosts
]


def _weights(kind, n, rng):
    if kind == "healthy":
        return [0x10000] * n
    if kind == "one_out":
        return [0x10000] * (n - 1) + [0]
    return [rng.choice([0x10000, 0x8000, 0, 0x4000]) for _ in range(n)]


def _same(cmap, ruleno, result_max, weights, xs):
    scalar, host = ScalarMapper(cmap), HostVecMapper(cmap)
    res, rlen = host.do_rule_batch(ruleno, xs, result_max, weights)
    assert res.shape == (len(xs), result_max)
    for i, x in enumerate(xs):
        want = scalar.do_rule(ruleno, int(x), result_max, weights)
        assert [int(v) for v in res[i, : rlen[i]]] == want, (int(x), want)


@pytest.mark.parametrize("kind", ["healthy", "one_out", "partial"])
@pytest.mark.parametrize("hosts,per_host,firstn,numrep", SHAPES)
def test_host_walk_equals_the_scalar_walk(hosts, per_host, firstn, numrep,
                                          kind):
    rng = random.Random(hosts * 100 + numrep)
    cmap, leaf_rule = build_hierarchy(hosts, per_host, numrep=numrep,
                                      firstn=firstn)
    root = cmap.rules[leaf_rule].steps[0][1]
    choose = RULE_CHOOSE_FIRSTN if firstn else RULE_CHOOSE_INDEP
    leaf = RULE_CHOOSELEAF_FIRSTN if firstn else RULE_CHOOSELEAF_INDEP
    rules = [
        leaf_rule,
        # hosts, no leaf; devices straight from the root with numrep 0
        cmap.add_rule(Rule(steps=[(RULE_TAKE, root, 0),
                                  (choose, numrep, 1), (RULE_EMIT, 0, 0)])),
        cmap.add_rule(Rule(steps=[(RULE_TAKE, root, 0), (choose, 0, 0),
                                  (RULE_EMIT, 0, 0)])),
        # upstream's EC rule: its set steps, then the leaf choice
        cmap.add_rule(Rule(steps=[(RULE_SET_CHOOSELEAF_TRIES, 5, 0),
                                  (RULE_SET_CHOOSE_TRIES, 100, 0),
                                  (RULE_TAKE, root, 0), (leaf, numrep, 1),
                                  (RULE_EMIT, 0, 0)])),
        cmap.add_rule(Rule(steps=[(RULE_SET_CHOOSELEAF_VARY_R, 0, 0),
                                  (RULE_TAKE, root, 0), (leaf, numrep, 1),
                                  (RULE_EMIT, 0, 0)])),
    ]
    n = hosts * per_host
    # the scalar walk of a reweighted 12-of-12 is most of a second an x
    xs = np.array([rng.getrandbits(32) for _ in range(10)], dtype=np.uint32)
    for ruleno in rules if kind == "healthy" else (leaf_rule, rules[3]):
        _same(cmap, ruleno, numrep, _weights(kind, n, rng), xs)


def test_three_levels_and_other_tunables():
    """A rack level above the hosts, descend_once off and stable off:
    the leaf recursion's own retries and its replica numbering."""
    rng = random.Random(7)
    cmap, rule = build_three_level(n_racks=3, hosts_per_rack=3,
                                   osds_per_host=2, numrep=3)
    cmap.tunables = Tunables(chooseleaf_descend_once=0, chooseleaf_stable=0)
    xs = np.array([rng.getrandbits(32) for _ in range(48)], dtype=np.uint32)
    for kind in ("healthy", "partial"):
        _same(cmap, rule, 3, _weights(kind, 18, rng), xs)


def test_a_result_shorter_than_asked_keeps_its_length():
    """firstn that cannot fill every replica returns fewer; indep
    returns its holes in place."""
    cmap, rule = build_hierarchy(2, 2, numrep=3, firstn=True)
    xs = np.arange(16, dtype=np.uint32)
    res, rlen = HostVecMapper(cmap).do_rule_batch(rule, xs, 3,
                                                  [0x10000] * 4)
    assert set(rlen.tolist()) == {2}
    cmap, rule = build_hierarchy(2, 2, numrep=3, firstn=False)
    res, rlen = HostVecMapper(cmap).do_rule_batch(rule, xs, 3,
                                                  [0x10000] * 4)
    assert set(rlen.tolist()) == {3}
    assert ((res == CRUSH_ITEM_NONE).sum(axis=1) == 1).all()
    _same(cmap, rule, 3, [0x10000] * 4, xs)


def test_what_the_host_walk_refuses():
    """A uniform bucket, legacy tunables, a rule that chains choose
    steps: NotImplementedError, so the caller takes another engine."""
    cmap = CrushMap()
    host = cmap.add_bucket(Bucket(id=0, type=1, alg="uniform", items=[0, 1],
                                  weights=[0x10000, 0x10000]), name="h")
    cmap.add_bucket(Bucket(id=0, type=3, alg="straw2", items=[host],
                           weights=[0x20000]), name="default")
    with pytest.raises(NotImplementedError):
        HostVecMapper(cmap)
    cmap, _ = build_hierarchy(4, 2, numrep=3)
    cmap.tunables = Tunables.legacy()
    with pytest.raises(NotImplementedError):
        HostVecMapper(cmap)
    cmap, rule = build_three_level(n_racks=2, hosts_per_rack=2,
                                   osds_per_host=2, numrep=2)
    root = cmap.rules[rule].steps[0][1]
    chained = cmap.add_rule(Rule(steps=[
        (RULE_TAKE, root, 0), (RULE_CHOOSE_FIRSTN, 2, 2),
        (RULE_CHOOSELEAF_FIRSTN, 1, 1), (RULE_EMIT, 0, 0)]))
    with pytest.raises(NotImplementedError):
        HostVecMapper(cmap).do_rule_batch(chained, np.arange(4), 2,
                                          [0x10000] * 8)


def test_a_refused_rule_still_maps_through_the_osdmap():
    """``OSDMap.pool_mapping`` asked for the host walk on a chained rule
    falls to the scalar chain, silently and equal."""
    from ceph_tpu.osdmap.osdmap import OSDMap, PGid, PGPool

    cmap, rule = build_three_level(n_racks=2, hosts_per_rack=2,
                                   osds_per_host=2, numrep=2)
    root = cmap.rules[rule].steps[0][1]
    chained = cmap.add_rule(Rule(steps=[
        (RULE_TAKE, root, 0), (RULE_CHOOSE_FIRSTN, 2, 2),
        (RULE_CHOOSELEAF_FIRSTN, 1, 1), (RULE_EMIT, 0, 0)]))
    m = OSDMap(cmap, max_osd=8)
    m.add_pool(PGPool(pool_id=1, size=2, min_size=1, pg_num=16, pgp_num=16,
                      crush_rule=chained))
    up, upp, engine = m._pool_mapping(1, "host")
    assert engine == "scalar"
    for seed in range(16):
        want, wantp, _, _ = m.pg_to_up_acting_osds(PGid(1, seed))
        assert [int(o) for o in up[seed] if o != CRUSH_ITEM_NONE] == want
        assert int(upp[seed]) == wantp


@pytest.mark.parametrize("pg_num,size,hosts,per_host,engine", [
    (8, 3, 3, 1, "scalar"),         # a test cluster's pool: 44 draws
    (16, 6, 8, 1, "host"),          # rados_k4m2_8osd: 156
    (32, 12, 12, 1, "host"),        # rados_isa_k8m4_12osd: 1191
    (128, 12, 12, 1, "host"),       # upstream's PG count for it: 4767
    (4096, 3, 16, 4, "host"),       # 13 thousand
    (32768, 3, 16, 4, "device"),    # a hundred thousand
])
def test_the_engine_is_chosen_by_the_work_of_the_walk(pg_num, size, hosts,
                                                      per_host, engine):
    from ceph_tpu.osdmap.osdmap import (OSDMap, PGPool, POOL_TYPE_ERASURE,
                                        POOL_TYPE_REPLICATED)

    firstn = size == 3
    cmap, rule = build_hierarchy(hosts, per_host, numrep=size,
                                 firstn=firstn)
    m = OSDMap(cmap, max_osd=hosts * per_host)
    m.add_pool(PGPool(
        pool_id=1, size=size, pg_num=pg_num, pgp_num=pg_num,
        type=POOL_TYPE_REPLICATED if firstn else POOL_TYPE_ERASURE,
        crush_rule=rule))
    assert m.placement_engine(1) == engine
    n = hosts
    draws = sum(n / (n - i) for i in range(size))
    assert m.walk_draws(m.pools[1]) == pytest.approx(pg_num * draws)


def _golden_scenarios():
    from test_crush_mapper import load_scenarios

    return load_scenarios()


@pytest.mark.parametrize("scen", _golden_scenarios(),
                         ids=lambda s: s["scenario"])
def test_host_walk_matches_the_compiled_reference(scen):
    """The golden vectors (results of the reference C, compiled): every
    scenario the host walk takes comes out equal; the ones it refuses
    (other bucket algorithms, chained choose steps, choose_args) say so
    with NotImplementedError and are the scalar chain's."""
    from test_crush_mapper import build_map

    n = len(scen["results"])
    try:
        if "choose_args" in scen:
            raise NotImplementedError("choose_args")
        res, rlen = HostVecMapper(build_map(scen)).do_rule_batch(
            0, np.arange(n, dtype=np.uint32), scen["result_max"],
            scen["weights"])
    except NotImplementedError as why:
        pytest.skip(f"refused: {why}")
    bad = [(x, want) for x, want in enumerate(scen["results"])
           if [int(v) for v in res[x, : rlen[x]]] != want]
    assert not bad, f"{len(bad)}/{n} mismatches, first: {bad[:3]}"
