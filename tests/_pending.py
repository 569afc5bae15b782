"""The cells that wait under ``benchmark/pending/``: built and proven, and
not listed, because they bring end-to-end metrics and only a ``benchmark``
PR may add one, while such a PR may touch no test.  So the tier-1 cases
that pin the list of cells hold in BOTH states: a waiting cell is loaded
from a root whose ``BENCHMARK.json`` has its entries appended, as
``benchmark/pending/apply.py`` would leave the repo's, and once it is
listed (behind every cell that is there) from the repo's own."""

import json
import os

from benchmark.harness.loader import BENCH_DIR, ROOT
from benchmark.pending import apply


def _spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _waiting(spec: dict):
    """The pending files' contents whose cells ``spec`` does not list."""
    listed = {w["name"] for w in spec["workloads"]}
    out = []
    for path in apply.pending_files():
        with open(path, encoding="utf-8") as f:
            pending = json.load(f)
        if not listed & {w["name"] for w in pending["workloads"]}:
            out.append(pending)
    return out


def waiting_cells():
    return [w["name"] for p in _waiting(_spec()) for w in p["workloads"]]


def root_of(cell: str, tmp_path) -> str:
    """The root to give ``load_cell`` for ``cell``: the repo's if it is
    listed, else a copy with the waiting entries appended."""
    spec = _spec()
    if cell in {w["name"] for w in spec["workloads"]}:
        return ROOT
    for pending in _waiting(spec):
        spec = apply.merged(spec, pending)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    os.symlink(BENCH_DIR, tmp_path / "benchmark")
    return str(tmp_path)
