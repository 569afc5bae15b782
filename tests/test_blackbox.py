"""graft-blackbox gates: the flight recorder's no-op contract, bounded
memory, the four postmortem trigger kinds, breach attribution coverage,
seeded-replay determinism, and the report CLI's exit codes.

The no-op pin mirrors the NULL_SPAN tracer pin: with
``blackbox_enabled=0`` (the default) every daemon's ``flight`` is the
shared ``NULL_FLIGHT`` singleton — one falsy test per feed site, zero
allocation, zero retention — so the disabled hot path is provably
unchanged.  The trigger matrix proves each trigger kind produces
EXACTLY one parseable ``POSTMORTEM_*.json`` bundle, and the replay test
proves a seeded rerun lands on the same bundle path with a
bit-identical ``replay_key``.
"""

import asyncio
import json
import os
import subprocess
import sys

import pytest

from ceph_tpu.trace import postmortem as pm
from ceph_tpu.trace.flight import (
    NULL_FLIGHT,
    FlightRecorder,
    _NullFlight,
    merged_timeline,
)
from ceph_tpu.utils import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(coro):
    return asyncio.run(coro)


# ------------------------------------------------------- no-op contract


def test_disabled_recorder_is_the_null_singleton():
    """The NULL_SPAN-style pin: blackbox off (the DEFAULT) means every
    from_config call returns the one shared falsy null object — no ring,
    no per-daemon allocation, and feed sites cost one falsy test."""
    cfg = Config()
    assert getattr(cfg, "blackbox_enabled") == 0  # off by default
    for name in ("osd.0", "mon.0", "mgr", "client.x"):
        assert FlightRecorder.from_config(name, cfg) is NULL_FLIGHT
    assert not NULL_FLIGHT
    # every feed is a constant no-op: nothing recorded, nothing retained
    NULL_FLIGHT.record("op", desc="w", dur=1.0)
    NULL_FLIGHT.op_sample("w", 9.9, slow=True)
    assert NULL_FLIGHT.events() == []
    d = NULL_FLIGHT.dump()
    assert d["enabled"] is False and d["events"] == []
    # __slots__ of nothing: the null object CANNOT grow state
    assert _NullFlight.__slots__ == ()


def test_cluster_is_a_provable_noop_when_disabled():
    """Boot a default cluster: every daemon and client holds the
    NULL_FLIGHT singleton (identity, not equality), the admin surface
    serves a disabled payload, and triggers return without collecting."""
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster

    async def scenario():
        cluster = await start_cluster(3, config=_fast_config())
        try:
            client = await cluster.client()
            for osd in cluster.osds.values():
                assert osd.flight is NULL_FLIGHT
            for mon in cluster.mons:
                assert mon.flight is NULL_FLIGHT
            assert client.objecter.flight is NULL_FLIGHT
            d = await cluster.daemon_command("osd.0", "blackbox dump")
            assert d["flight"]["enabled"] is False
            # a trigger with the recorder off is one falsy test
            assert await cluster.blackbox_trigger(
                "slo_gate", "forced") is None
            assert cluster.postmortems == []
        finally:
            await cluster.stop()

    run(scenario())


# ------------------------------------------------------- bounded memory


def test_ring_bounded_under_flood():
    """100 events through a capacity-8 ring: the ring holds exactly the
    newest 8 and counts the 92 it forgot — memory stays bounded under
    any event flood."""
    fr = FlightRecorder("osd.9", capacity=8, sample_every=4)
    for i in range(100):
        fr.record("queue", depth=i)
    assert len(fr.events()) == 8
    assert fr.dropped == 92
    d = fr.dump()
    assert d["capacity"] == 8 and len(d["events"]) == 8
    assert [e["data"]["depth"] for e in d["events"]] == \
        list(range(92, 100))


def test_op_sampling_every_nth_and_slow_always():
    fr = FlightRecorder("osd.8", capacity=64, sample_every=4)
    for i in range(16):
        fr.op_sample(f"op{i}", 0.001)
    assert len(fr.events()) == 4  # every 4th op lands
    fr.op_sample("slowop", 9.9, slow=True)
    last = fr.events()[-1]
    assert last[2] == "op" and last[3]["slow"] is True


def test_merged_timeline_subtracts_recorded_skew():
    """A chaos-skewed daemon's stamps align onto the cluster timeline
    once its recorded offset is subtracted: osd.0 (+100s skew) stamped
    1100 happened AFTER osd.1's unskewed 999."""
    a = {"daemon": "osd.0", "skew": 100.0, "events": [
        {"seq": 1, "t": 1100.0, "kind": "map", "data": {"epoch": 2}}]}
    b = {"daemon": "osd.1", "skew": 0.0, "events": [
        {"seq": 1, "t": 999.0, "kind": "map", "data": {"epoch": 1}}]}
    tl = merged_timeline({"osd.0": a, "osd.1": b})
    assert [e["data"]["epoch"] for e in tl] == [1, 2]
    assert tl[1]["t"] == 1000.0


# ------------------------------------------------------- trigger matrix


def test_slo_gate_failure_produces_postmortem_bundle(tmp_path):
    """Trigger kind 1: a forced SLO-gate failure (unreachable goodput
    floor) auto-produces exactly one parseable bundle whose breach
    attribution explains >= 0.9 of the late ops' wall."""
    from dataclasses import replace

    from ceph_tpu.load.driver import builtin_specs, run_load

    spec = replace(
        builtin_specs()["smoke-micro"], name="bb-slo",
        gates=(("goodput_min_frac", 1e9),),
        config=(("blackbox_enabled", 1),
                ("blackbox_dir", str(tmp_path))))
    _result, report = run(run_load(spec, 7))
    assert not report.passed
    assert any(g["gate"] == "goodput" for g in report.failing_gates())
    assert report.postmortem and os.path.exists(report.postmortem)
    bundle = pm.load_bundle(report.postmortem)
    assert bundle["trigger"]["kind"] == "slo_gate"
    # observed-vs-threshold rows for the failing gates ride the trigger
    det = {g["gate"]: g for g in bundle["trigger"]["detail"]["gates"]}
    assert det["goodput"]["threshold"] >= 1e9
    # breach attribution coverage: the acceptance bar
    breach = bundle["breach"]
    assert breach["breach_ops"] >= 1
    assert breach["attribution"]["wall_coverage"] >= 0.9
    assert breach["suspects"], "top-suspects table must not be empty"
    # client rings rode along (clients have no admin socket)
    assert any(k.startswith("client.") for k in bundle["daemons"])
    # exactly ONE bundle for one failed judgment
    assert len(list(tmp_path.glob("POSTMORTEM_*.json"))) == 1


@pytest.mark.chaos
def test_chaos_conviction_bundle_replays_bit_identical(tmp_path):
    """Trigger kind 2: a forced chaos conviction (unreachable epochs
    floor) produces a bundle, the Verdict records the failing gate's
    observed-vs-threshold row + the bundle path, and a seeded rerun
    lands on the SAME bundle path with a bit-identical replay key."""
    from ceph_tpu.chaos.scenario import Scenario, run_scenario

    sc = Scenario(
        name="bb-conv", osds=3, pool_size=2, pg_num=4, rounds=1,
        objects_per_round=2, payload_repeat=10,
        invariants=("durability",), epochs_floor=1e9,
        config=(("blackbox_enabled", 1),
                ("blackbox_dir", str(tmp_path))),
        converge_timeout=45.0)
    v1 = run(run_scenario(sc, 13))
    assert not v1.passed
    rows = {g["gate"]: g for g in v1.gates}
    assert rows["epochs"]["passed"] is False
    assert rows["epochs"]["threshold"] == 1e9
    assert v1.postmortem and os.path.exists(v1.postmortem)
    b1 = pm.load_bundle(v1.postmortem)
    assert b1["trigger"]["kind"] == "chaos_conviction"
    assert b1["trigger"]["detail"]["gates"]
    # breach attribution coverage holds on the convicted run too
    assert b1["breach"]["attribution"].get("wall_coverage", 0) >= 0.9
    k1 = pm.replay_key(b1)
    # seeded replay: the bundle filename is a pure function of the
    # trigger, so run 2 overwrites run 1's bundle on the same path
    v2 = run(run_scenario(sc, 13))
    assert v2.postmortem == v1.postmortem
    assert pm.replay_key(pm.load_bundle(v2.postmortem)) == k1


def test_crash_point_trigger_produces_one_bundle(tmp_path):
    """Trigger kind 3: an armed chaos crash point power-cuts its daemon
    AND fires a postmortem — the bundle is taken with the victim
    already down (its absence from the daemon set IS evidence)."""
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster

    async def scenario():
        cfg = _fast_config()
        cfg.set("blackbox_enabled", 1)
        cfg.set("blackbox_dir", str(tmp_path))
        cluster = await start_cluster(4, config=cfg)
        try:
            client = await cluster.client()
            pool = await client.pool_create("bb", "replicated",
                                            pg_num=4, size=3)
            io = client.ioctx(pool)
            await io.write_full("o0", b"x" * 4096)
            pgid = client.objecter.object_pgid(pool, "o0")
            _, _, _, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)
            cluster.osds[primary].config.injectargs(
                {"chaos_crash_point": "commit_pre_fanout"})
            # the write that trips the crash retries and lands whole
            await io.write_full("o0", b"y" * 4096, timeout=60)
            await cluster.drain_chaos()
            await cluster.drain_blackbox()
            recs = [r for r in cluster.postmortems
                    if r["kind"] == "crash_point"]
            assert len(recs) == 1, cluster.postmortems
            assert f"osd.{primary}" in recs[0]["reason"]
            assert recs[0]["path"] and os.path.exists(recs[0]["path"])
            bundle = pm.load_bundle(recs[0]["path"])
            assert f"osd.{primary}" not in bundle["daemons"]
            # the survivors' rings carry events (heartbeat queue samples
            # at minimum)
            assert any(d.get("events")
                       for d in bundle["daemons"].values()
                       if isinstance(d, dict))
        finally:
            await cluster.stop()

    run(scenario())


def test_health_err_transition_triggers_one_bundle(tmp_path):
    """Trigger kind 4: the mon's edge INTO HEALTH_ERR (every OSD down)
    fires exactly one bundle, and the mon's bounded health-history ring
    (the satellite) records the raise + status transition and serves
    them over the admin socket."""
    from ceph_tpu.cluster.vstart import _fast_config, start_cluster

    async def scenario():
        cfg = _fast_config()
        cfg.set("blackbox_enabled", 1)
        cfg.set("blackbox_dir", str(tmp_path))
        cfg.set("mon_health_history", 8)
        # the last OSD to die has no peer left to report it: only the
        # mon's beacon grace marks it down, and the product
        # configuration's 30 s (PR 28) is this test's whole deadline
        cfg.set("mon_osd_beacon_grace", 1.5)
        cluster = await start_cluster(2, config=cfg)
        try:
            await cluster.client()  # collection rides a live session
            for osd_id in sorted(cluster.osds):
                await cluster.kill_osd(osd_id)
            loop = asyncio.get_event_loop()
            deadline = loop.time() + 30
            while loop.time() < deadline and not cluster.postmortems:
                await asyncio.sleep(0.1)
            await cluster.drain_blackbox()
            recs = [r for r in cluster.postmortems
                    if r["kind"] == "health_err"]
            assert len(recs) == 1, cluster.postmortems
            bundle = pm.load_bundle(recs[0]["path"])
            assert bundle["trigger"]["detail"]["checks"].get("OSD_DOWN")
            hist = bundle["health_history"]
            assert any(r["check"] == "OSD_DOWN" and r["op"] == "raise"
                       for r in hist)
            # satellite: the mon serves the ring, bounded by config
            served = await cluster.daemon_command("mon.0",
                                                  "health history")
            assert len(served) <= 8
            assert any(r["check"] == "STATUS"
                       and r["severity"] == "HEALTH_ERR"
                       for r in served)
        finally:
            await cluster.stop()

    run(scenario())


# ----------------------------------------------------------- report CLI


def _cli(args, cwd):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "blackbox.py"),
         *args],
        cwd=cwd, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def _fake_bundle():
    return {
        "kind": pm.BUNDLE_KIND,
        "trigger": {"kind": "slo_gate", "reason": "forced",
                    "detail": {"gates": [{"gate": "goodput", "value": 1,
                                          "threshold": 2}],
                               "seed": 7, "spec": "bb"}},
        "daemons": {"osd.0": {"daemon": "osd.0", "skew": 0.0,
                              "dropped": 0, "capacity": 8, "events": [
                                  {"seq": 1, "t": 10.0, "kind": "queue",
                                   "data": {"depth": 3}}]}},
        "historic_ops": {"osd.0": {"ops": {"ops": [
            {"description": "write_full o0 pg=1.2s0",
             "duration": 0.02,
             "type_data": {"events": [
                 {"time": 0.0, "event": "initiated"},
                 {"time": 0.02, "event": "done"}]}}]},
            "slow": {"ops": []}}},
        "health": {"status": "HEALTH_OK", "checks": {}},
        "health_history": [],
        "mgr_scrape": {"error": "no mgr"},
    }


def test_cli_exit_codes(tmp_path):
    """Exit-code contract: 0 success, 1 bundle found but malformed for
    the request, 2 usage / no bundle / not a bundle."""
    # 2: nothing that looks like a bundle anywhere
    empty = tmp_path / "empty"
    empty.mkdir()
    assert _cli(["report"], empty).returncode == 2
    # 2: a JSON file that is not a postmortem bundle
    bad = tmp_path / "POSTMORTEM_x_nota.json"
    bad.write_text(json.dumps({"kind": "something-else"}))
    assert _cli(["key", str(bad)], tmp_path).returncode == 2
    # 0: a well-formed bundle reports, keys, and exports
    good = tmp_path / "POSTMORTEM_slo_gate_abc.json"
    good.write_text(json.dumps(_fake_bundle()))
    r = _cli(["report", str(good)], tmp_path)
    assert r.returncode == 0, r.stderr
    assert "breach set" in r.stdout and "goodput" in r.stdout
    r = _cli(["key", str(good)], tmp_path)
    assert r.returncode == 0 and len(r.stdout.strip()) == 64
    out = tmp_path / "t.trace.json"
    r = _cli(["perfetto", str(good), "--out", str(out)], tmp_path)
    assert r.returncode == 0, r.stderr
    assert json.loads(out.read_text())["traceEvents"]
    # 1: right kind, rotten content (non-numeric event stamps)
    rot = _fake_bundle()
    rot["daemons"]["osd.0"]["events"][0]["t"] = "not-a-stamp"
    rot_p = tmp_path / "POSTMORTEM_slo_gate_rot.json"
    rot_p.write_text(json.dumps(rot))
    assert _cli(["report", str(rot_p)], tmp_path).returncode == 1


def test_replay_key_ignores_wall_stamps():
    """The determinism witness hashes the trigger's deterministic
    projection ONLY: two bundles that differ in every wall stamp,
    duration, and counter still produce one key; changing the trigger
    identity changes it."""
    b1, b2 = _fake_bundle(), _fake_bundle()
    b2["daemons"]["osd.0"]["events"][0]["t"] = 99999.0
    b2["historic_ops"]["osd.0"]["ops"]["ops"][0]["duration"] = 5.0
    assert pm.replay_key(b1) == pm.replay_key(b2)
    b3 = _fake_bundle()
    b3["trigger"]["reason"] = "a different conviction"
    assert pm.replay_key(b3) != pm.replay_key(b1)
