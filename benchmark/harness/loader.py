"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

A cell names a configuration and a traffic mix; a per-layer metric names
itself.  Each is one data file under ``benchmark/``:

    configs/<config>.json         the deployment as it is run
    traffic/<traffic>.json        the traffic generator's parameters
    layer_metrics/<metric>.json   what the reader of that metric reads

so a later PR adds a cell or a metric by adding files and one entry, and
edits nothing here.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class BenchmarkError(Exception):
    """The benchmark's files do not say what the harness needs."""


def _read_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"missing file {path}") from None


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[str]           # metric names this cell reports
    per_layer: Dict[str, dict]      # metric name -> its reader's file


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in spec["workloads"])
        raise BenchmarkError(f"no workload {name!r}; BENCHMARK.json has: "
                             f"{known}")
    cfg = next((c for c in spec["configs"] if c["name"] == entry["config"]),
               None)
    if cfg is None:
        raise BenchmarkError(f"workload {name!r} names config "
                             f"{entry['config']!r}, which has no entry")
    e2e = [m["name"] for m in spec["end_to_end"] if _applies(m, name)]
    layers = {}
    for m in spec["per_layer"]:
        if not _applies(m, name):
            continue
        reader = _read_json(os.path.join(bench_dir, "layer_metrics",
                                         m["name"] + ".json"))
        # what both files state has to agree (the unit always)
        for key in ("unit", "layer", "moves", "source"):
            if key in reader or key == "unit":
                if reader.get(key) != m[key]:
                    raise BenchmarkError(
                        f"per-layer metric {m['name']!r}: {key} {m[key]!r} "
                        f"in BENCHMARK.json, {reader.get(key)!r} in its file")
        layers[m["name"]] = reader
    return Cell(
        name=name, chips=int(entry["chips"]),
        config_name=cfg["name"],
        config=_read_json(os.path.join(root, cfg["file"])),
        traffic_name=entry["traffic"],
        traffic=_read_json(os.path.join(bench_dir, "traffic",
                                        entry["traffic"] + ".json")),
        end_to_end=e2e, per_layer=layers)
