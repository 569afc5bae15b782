"""bench.py timing trust model: untrusted numbers can never be headline.

BENCH_NOTES.md round 5 showed `pipelined_untrusted` timings sample
the host's enqueue rate, not device throughput — rounds 1-4 published
fiction that way.  The guard: a row whose mode is not `device_loop`-class
must carry ``"untrusted": true`` and a NULL ``vs_baseline``, so no
consumer of BENCH_r*.json can mistake an enqueue rate for a measured
speedup.  This test pins the JSON shape of both row classes.
"""

import importlib.util
import json
import pathlib
import sys


def _load_bench():
    path = pathlib.Path(__file__).parent.parent / "bench.py"
    spec = importlib.util.spec_from_file_location("bench_module", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_module"] = mod
    spec.loader.exec_module(mod)
    return mod


bench = _load_bench()
PROV = {"baseline": 10.5, "baseline_src": "measured"}


def test_untrusted_rows_lose_ratio_and_are_flagged():
    row = bench._metric_row("ec_encode_x", 49.8, "GB/s", 4.7, PROV,
                            "pipelined_untrusted", 49.0, 50.0)
    assert row["untrusted"] is True
    assert row["vs_baseline"] is None
    # provenance stays so the reader can see what WOULD have been claimed
    assert row["baseline"] == 10.5
    assert row["mode"] == "pipelined_untrusted"
    # and the row keeps serializing cleanly
    assert json.loads(json.dumps(row)) == row


def test_device_loop_rows_keep_ratio_and_are_not_flagged():
    row = bench._metric_row("ec_encode_x", 49.8, "GB/s", 4.7, PROV,
                            "device_loop", 49.0, 50.0)
    assert "untrusted" not in row
    assert row["vs_baseline"] == 4.7
    assert row["min"] == 49.0 and row["max"] == 50.0


def test_extra_fields_ride_through():
    row = bench._metric_row("cluster_io", 6.18, "MB/s", None,
                            {"baseline": None, "baseline_src": "unmeasured"},
                            "cluster_vstart", iops=5.9)
    assert row["iops"] == 5.9
    assert "untrusted" not in row
    assert row["vs_baseline"] is None
