"""Admin/observability surfaces: OpTracker, admin commands, mgr perf
streams, injectargs.

Reference: src/common/TrackedOp.cc (dump_historic_ops), AdminSocket
commands, MgrClient::send_report (src/mgr/MgrClient.cc:232), injectargs.
"""

import asyncio

import pytest

from ceph_tpu.cluster.optracker import OpTracker
from ceph_tpu.cluster.vstart import _fast_config, start_cluster


def run(coro):
    return asyncio.run(coro)


def test_optracker_unit():
    t = OpTracker(history_size=3)
    ops = []
    for i in range(5):
        op = t.create(f"op{i}")
        op.mark("queued")
        op.finish()
        ops.append(op)
    live = t.create("inflight")
    inflight = t.dump_ops_in_flight()
    assert inflight["num_ops"] == 1
    assert inflight["ops"][0]["description"] == "inflight"
    hist = t.dump_historic_ops()
    assert hist["num_ops"] == 3  # ring buffer keeps the newest 3
    assert [o["description"] for o in hist["ops"]] == ["op2", "op3", "op4"]
    assert all(o["duration"] is not None for o in hist["ops"])
    events = hist["ops"][0]["type_data"]["events"]
    assert [e["event"] for e in events] == ["initiated", "queued", "done"]
    live.finish()
    # fast ops never reach the slow ring: the 30s complaint-time default
    # only admits genuinely slow completions (a threshold of 0 used to
    # put EVERY op here — fixed round 6)
    slow = t.dump_historic_slow_ops()
    assert slow["num_ops"] == 0


def test_admin_commands_and_historic_ops():
    async def scenario():
        cluster = await start_cluster(3)
        try:
            client = await cluster.client()
            pool = await client.pool_create("ap", "replicated",
                                            pg_num=8, size=2)
            io = client.ioctx(pool)
            for i in range(5):
                await io.write_full(f"o{i}", b"x" * 100)
                await io.read(f"o{i}")

            pgid = client.objecter.object_pgid(pool, "o0")
            _, _, _, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)
            addr = client.objecter.osdmap.osd_addrs[primary]

            # historic op dump shows real ops with event timelines
            hist = await client.objecter.daemon_command(
                addr, {"prefix": "dump_historic_ops"})
            assert hist["num_ops"] >= 1
            assert any("osd_op" in o["description"] for o in hist["ops"])
            # perf dump over the same channel
            perf = await client.objecter.daemon_command(
                addr, {"prefix": "perf dump"})
            assert perf[f"osd.{primary}"]["osd_client_ops"] >= 1
            # config show
            cfg = await client.objecter.daemon_command(
                addr, {"prefix": "config show"})
            assert "osd_heartbeat_interval" in cfg
            # remote scrub trigger
            rep = await client.objecter.daemon_command(
                addr, {"prefix": "scrub"}, timeout=30)
            assert isinstance(rep, dict)
        finally:
            await cluster.stop()

    run(scenario())


def test_injectargs_via_mon():
    async def scenario():
        cluster = await start_cluster(3)
        try:
            client = await cluster.client()
            before = cluster.osds[1].config.osd_recovery_delay_start
            await client.objecter.mon_command({
                "prefix": "injectargs", "who": "osd.1",
                "args": {"osd_recovery_delay_start": 7.5}})
            deadline = asyncio.get_event_loop().time() + 5
            while asyncio.get_event_loop().time() < deadline:
                if cluster.osds[1].config.osd_recovery_delay_start == 7.5:
                    break
                await asyncio.sleep(0.05)
            assert cluster.osds[1].config.osd_recovery_delay_start == 7.5
            # other osds untouched
            assert cluster.osds[0].config.osd_recovery_delay_start == before
        finally:
            await cluster.stop()

    run(scenario())


from tests._flaky import contention_retry


@contention_retry()
def test_mgr_receives_perf_streams():
    async def scenario():
        cfg = _fast_config()
        cluster = await start_cluster(3, config=cfg, with_mgr=True)
        try:
            client = await cluster.client()
            pool = await client.pool_create("mp", "replicated",
                                            pg_num=8, size=2)
            io = client.ioctx(pool)
            await io.write_full("obj", b"mgr" * 100)
            # wait for reports to stream in (every heartbeat tick)
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline:
                if len(cluster.mgr.daemons) >= 3:
                    break
                await asyncio.sleep(0.1)
            assert len(cluster.mgr.daemons) >= 3

            status = await client.objecter.daemon_command(
                cluster.mgr_addr, {"prefix": "mgr status"})
            assert set(status["daemons"]) >= {"osd.0", "osd.1", "osd.2"}
            # the counter rides the NEXT report after the write: poll
            # instead of trusting one heartbeat tick (load-deflake
            # round 11 — the invariant stays, the clock relaxes)
            total_ops = 0
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline:
                total_ops = await client.objecter.daemon_command(
                    cluster.mgr_addr,
                    {"prefix": "counter sum",
                     "counter": "osd_client_ops"})
                if total_ops >= 1:
                    break
                await asyncio.sleep(0.1)
            assert total_ops >= 1
        finally:
            await cluster.stop()

    run(scenario())


def test_pool_delete_rename_set():
    """Pool lifecycle admin (reference OSDMonitor pool ops): rename,
    set size/min_size, guarded delete that really removes the data."""
    import asyncio

    import pytest

    from ceph_tpu.cluster.vstart import start_cluster

    async def scenario():
        cluster = await start_cluster(3)
        try:
            client = await cluster.client()
            pool = await client.pool_create("adm", "replicated",
                                            pg_num=8, size=3)
            io = client.ioctx(pool)
            await io.write_full("obj", b"data")
            # rename
            await client.pool_rename("adm", "renamed")
            assert "renamed" in client.pool_list()
            assert "adm" not in client.pool_list()
            # set size
            await client.pool_set("renamed", "size", 2)
            assert client.objecter.osdmap.pools[pool].size == 2
            with pytest.raises(RuntimeError):
                await client.pool_set("renamed", "pg_num", 4)  # shrink
            # ADVICE r4: invalid size/min_size must be EINVAL, never
            # committed (they would wedge all writes on the pool)
            for var, val in (("size", 0), ("size", -1), ("min_size", 0),
                             ("min_size", 3), ("size", "garbage")):
                with pytest.raises(RuntimeError):
                    await client.pool_set("renamed", var, val)
            assert client.objecter.osdmap.pools[pool].size == 2
            assert 1 <= client.objecter.osdmap.pools[pool].min_size <= 2
            # delete requires the sure gate
            with pytest.raises(RuntimeError):
                await client.pool_delete("renamed")
            await client.pool_delete("renamed", sure=True)
            assert "renamed" not in client.pool_list()
            # the data is gone from every OSD store — converge-poll to
            # a wall deadline (the deletion rides the map push; a fixed
            # beat raced it under host load)
            def _purged():
                return all(
                    not [c for c in osd.store.list_collections()
                         if c.startswith(f"pg_{pool}_")]
                    and not [p for p in osd.pgs if p.pool == pool]
                    for osd in cluster.osds.values())

            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline and \
                    not _purged():
                await asyncio.sleep(0.05)
            for osd in cluster.osds.values():
                assert not [c for c in osd.store.list_collections()
                            if c.startswith(f"pg_{pool}_")], \
                    f"osd.{osd.osd_id} kept deleted pool data"
                assert not [p for p in osd.pgs if p.pool == pool]
        finally:
            await cluster.stop()

    asyncio.run(scenario())


def test_health_and_df_commands():
    """'ceph health' / 'ceph df' analogs: health checks from the map,
    usage aggregated from OSD beacon statfs."""
    import asyncio

    from ceph_tpu.cluster.vstart import start_cluster

    async def scenario():
        cluster = await start_cluster(3)
        try:
            client = await cluster.client()
            h = await client.objecter.mon_command({"prefix": "health"})
            assert h["status"] == "HEALTH_OK", h
            pool = await client.pool_create("hdf", "replicated",
                                            pg_num=8, size=2)
            io = client.ioctx(pool)
            await io.write_full("obj", b"x" * 100_000)
            # wait for a beacon cycle to carry statfs
            for _ in range(100):
                df = await client.objecter.mon_command({"prefix": "df"})
                if df["used_bytes"] > 0 and len(df["osds"]) == 3:
                    break
                await asyncio.sleep(0.1)
            assert df["total_bytes"] > 0
            assert df["used_bytes"] >= 100_000  # replicated x2 somewhere
            # kill an OSD -> health degrades
            victim = next(iter(cluster.osds))
            await cluster.osds.pop(victim).stop()
            for _ in range(100):
                h = await client.objecter.mon_command({"prefix": "health"})
                # poll for the down mark itself: survivors report
                # transient PG_RECOVERING before the grace expires
                if "OSD_DOWN" in h["checks"]:
                    break
                await asyncio.sleep(0.1)
            assert h["status"] in ("HEALTH_WARN", "HEALTH_ERR")
            assert "OSD_DOWN" in h["checks"]
        finally:
            await cluster.stop()

    asyncio.run(scenario())


def test_unified_telemetry_end_to_end():
    """Round-6 tentpole acceptance: 'ceph daemon osd.N perf dump'
    returns schema'd counters including a histogram; an EC write's
    dump_historic_ops entry carries cross-layer trace events
    (objecter -> messenger -> osd -> store); the mon serves admin
    commands over the same path; the mgr renders Prometheus text."""
    async def scenario():
        cluster = await start_cluster(3, with_mgr=True)
        try:
            client = await cluster.client()
            pool = await client.pool_create(
                "tele", "erasure", pg_num=8,
                ec_profile={"plugin": "jerasure",
                            "technique": "reed_sol_van",
                            "k": "2", "m": "1"})
            io = client.ioctx(pool)
            await io.write_full("traced", b"\xa5" * 20000)
            assert (await io.read("traced"))[:4] == b"\xa5" * 4

            pgid = client.objecter.object_pgid(pool, "traced")
            _, _, _, primary = \
                client.objecter.osdmap.pg_to_up_acting_osds(pgid)

            # perf dump via the 'ceph daemon' path: schema'd counters
            # including at least one histogram, plus the process-wide
            # device-kernel section
            perf = await cluster.daemon_command(
                f"osd.{primary}", "perf dump")
            sec = perf[f"osd.{primary}"]
            assert sec["osd_client_ops"] >= 1
            assert sec["osd_op_lat"]["avgcount"] >= 1
            assert sec["osd_op_lat_hist"]["count"] >= 1
            assert sum(sec["osd_op_lat_hist"]["buckets"]) == \
                sec["osd_op_lat_hist"]["count"]
            assert "device_kernels" in perf
            # round 6: EC pool batches ride the bit-planar layout, so the
            # encode shows up as planar matmul + conversion counters (the
            # byte-path ec_matmul counters remain for non-planar routes)
            dk = perf["device_kernels"]
            # round 11: CPU backends run the coalesced write path on the
            # vectorized host GF engine (ec_host_planar_matmul_* for a
            # planar-at-rest pool, ec_host_matmul_* for a byte one);
            # device backends keep the planar/byte matmul counters
            assert dk.get("planar_matmul_calls", 0) >= 1 \
                or dk.get("ec_matmul_calls", 0) >= 1 \
                or dk.get("ec_host_matmul_calls", 0) >= 1 \
                or dk.get("ec_host_planar_matmul_calls", 0) >= 1
            assert dk.get("planar_convert_to_planar_bytes", 0) >= 1 \
                or dk.get("ec_matmul_bytes", 0) >= 1 \
                or dk.get("ec_host_matmul_bytes", 0) >= 1 \
                or dk.get("ec_host_planar_matmul_bytes", 0) >= 1
            schema = await cluster.daemon_command(
                f"osd.{primary}", "perf schema")
            assert schema[f"osd.{primary}"]["osd_op_lat_hist"]["type"] \
                == "histogram"
            hist = await cluster.daemon_command(
                f"osd.{primary}", "perf histogram dump")
            assert "osd_op_lat_hist" in hist[f"osd.{primary}"]

            # cross-layer trace: the historic entry for the EC write
            # shows client-side + messenger + osd + store events
            ops = await cluster.daemon_command(
                f"osd.{primary}", "dump_historic_ops")
            traced = [o for o in ops["ops"]
                      if "traced" in o["description"] and
                      "write_full" in o["description"]]
            assert traced, ops
            ev = [e["event"]
                  for e in traced[0]["type_data"]["events"]]
            assert "objecter:submit" in ev
            assert any(e.startswith("msgr:") for e in ev)
            assert "dispatched" in ev
            assert "batch_encoded" in ev    # the coalesced tick mark
            assert "store:journal_queued" in ev
            assert "commit" in ev
            assert ev.index("dispatched") < ev.index("batch_encoded") < \
                ev.index("commit")
            assert traced[0].get("trace_id")

            # the mon serves the same admin-command path
            mon_perf = await cluster.daemon_command("mon", "perf dump")
            assert "mon" in mon_perf
            q = await cluster.daemon_command("mon", "quorum_status")
            assert q["is_leader"] is True

            # mgr Prometheus exporter: daemon-labeled counters in text
            # exposition format (admin command + HTTP scrape endpoint)
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline:
                if len(cluster.mgr.daemons) >= 3:
                    break
                await asyncio.sleep(0.1)
            text = await cluster.daemon_command(
                "mgr", "prometheus metrics")
            assert f'ceph_osd_client_ops{{daemon="osd.{primary}"}}' \
                in text
            assert "ceph_osd_op_lat_hist_bucket" in text
            host, port = await cluster.mgr.serve_exporter()
            reader, writer = await asyncio.open_connection(host, port)
            writer.write(b"GET /metrics HTTP/1.1\r\n\r\n")
            await writer.drain()
            raw = await reader.read()
            writer.close()
            assert raw.startswith(b"HTTP/1.1 200")
            assert b"ceph_osd_client_ops" in raw

            # perf reset zeroes values but keeps schemas
            await cluster.daemon_command(f"osd.{primary}", "perf reset")
            perf = await cluster.daemon_command(
                f"osd.{primary}", "perf dump")
            assert perf[f"osd.{primary}"]["osd_client_ops"] == 0
        finally:
            await cluster.stop()

    run(scenario())


def test_slow_ops_health_warning_raises_and_clears():
    """A blocked op past osd_op_complaint_time raises the SLOW_OPS
    health warning ('N slow ops, oldest age X') through the beacon
    stream and the cluster log, and clears once the op completes."""
    async def scenario():
        cfg = _fast_config()
        cfg.osd_op_complaint_time = 0.2
        cluster = await start_cluster(3, config=cfg)
        try:
            client = await cluster.client()
            h = await client.objecter.mon_command({"prefix": "health"})
            assert "SLOW_OPS" not in h["checks"]
            # a deliberately-stuck op on osd.0 (the tracker is the
            # daemon's real blocked-op feed; ops created here age
            # exactly like a wedged client op)
            stuck = cluster.osds[0].tracker.create(
                "osd_op(client.test:1 wedged [write_full])")
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline:
                h = await client.objecter.mon_command(
                    {"prefix": "health"})
                if "SLOW_OPS" in h["checks"]:
                    break
                await asyncio.sleep(0.05)
            assert "SLOW_OPS" in h["checks"], h
            assert h["status"] == "HEALTH_WARN"
            assert "slow ops, oldest age" in h["checks"]["SLOW_OPS"]
            # the complaint reached the Paxos-replicated cluster log
            deadline = asyncio.get_event_loop().time() + 10
            logged = []
            while asyncio.get_event_loop().time() < deadline:
                logged = await client.objecter.mon_command(
                    {"prefix": "log last", "num": 50})
                if any("slow ops" in e["msg"] for e in logged):
                    break
                await asyncio.sleep(0.05)
            assert any("slow ops" in e["msg"] and e["prio"] == "WRN"
                       for e in logged), logged
            # drain: the op completes, the warning clears with the next
            # beacon round
            stuck.finish()
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline:
                h = await client.objecter.mon_command(
                    {"prefix": "health"})
                if "SLOW_OPS" not in h["checks"]:
                    break
                await asyncio.sleep(0.05)
            assert "SLOW_OPS" not in h["checks"], h
            # and the blocked interval is in the slow-op ring
            slow = await cluster.daemon_command(
                "osd.0", "dump_historic_slow_ops")
            assert any("wedged" in o["description"]
                       for o in slow["ops"])
        finally:
            await cluster.stop()

    run(scenario())
