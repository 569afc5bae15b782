"""The one traffic generator: a traffic file's parameters and ``--seed``
give the whole plan — payload pool, populated set, and every caller's
i-th operation — as a pure function.  The program receives only the
generated operations.

A traffic file (``traffic/<name>.json``):

    loop              "closed" (``callers`` workers, each waits for its
                      reply before its next op) or "open" (``rate``,
                      ``arrival``; refused until a cell brings the sweep
                      that finds the rate)
    callers           workers of the closed loop = ops in flight
    ops               {"write_full": w, "read": w}: weights of the mix
    object_bytes      one size, or {"choices": [...], "weights": [...]}
    keys              how a read picks its object from the populated set:
                      "uniform", or "zipf" with ``zipf_alpha``.  A
                      write_full always goes to a new name.
    populate_objects  objects written in set-up for reads to find
    payload_pool      seeded random buffers per object size; every write
                      sends one of them (never a constant byte)
    lead_in_s         closed loop run before the window opens (set-up):
                      the window starts with every caller busy
    set_up            events of set-up, after the populated set is written
                      and its healthy sample compared, before the loop
                      starts (a key the generator does not know is refused
                      by name):
                        kill_shard_holders  n: that many OSDs are killed and
                                            stay down for the rest of the run
                        victim              how they are chosen: "seed"
                                            (drawn from ``--seed``, as the
                                            verification draws its own) or
                                            "most_data_shards" (the OSDs that
                                            hold a data shard in most PGs of
                                            the pool, lowest id first: the
                                            same work on every seed)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from .loader import BenchmarkError

OPS = ("write_full", "read")
SET_UP_KEYS = ("kill_shard_holders", "victim")
VICTIM_RULES = ("seed", "most_data_shards")
_BLOCK = 1024       # ops generated at a time per caller


@dataclass(frozen=True)
class Op:
    kind: str           # "write_full" | "read"
    name: str
    size: int
    payload: int        # index into the pool of that size (writes)


class Plan:
    def __init__(self, traffic: dict, seed: int):
        if traffic.get("loop") != "closed":
            raise BenchmarkError(
                f"traffic loop {traffic.get('loop')!r}: only \"closed\" is "
                "implemented; an open-loop cell must bring the rate sweep "
                "on the chip with it (PERF.md, open questions)")
        self.seed = int(seed)
        self.callers = int(traffic["callers"])
        self.lead_in_s = float(traffic.get("lead_in_s", 0.0))
        mix = traffic["ops"]
        unknown = set(mix) - set(OPS)
        if unknown or not mix:
            raise BenchmarkError(f"traffic ops {sorted(mix)}: the generator "
                                 f"knows {list(OPS)}")
        self.kinds = sorted(mix)
        w = np.array([float(mix[k]) for k in self.kinds])
        self._kind_cdf = np.cumsum(w / w.sum())
        ob = traffic["object_bytes"]
        if isinstance(ob, dict):
            self.sizes = [int(s) for s in ob["choices"]]
            sw = np.array([float(x) for x in ob["weights"]])
        else:
            self.sizes, sw = [int(ob)], np.array([1.0])
        self._size_cdf = np.cumsum(sw / sw.sum())
        self.pool_n = int(traffic.get("payload_pool", 16))
        self.n_populate = int(traffic.get("populate_objects", 0))
        if "read" in mix and not self.n_populate:
            raise BenchmarkError("a traffic mix with reads needs "
                                 "populate_objects > 0")
        keys = traffic.get("keys", "uniform")
        if keys == "uniform":
            self._key_cdf = None
        elif keys == "zipf":
            ranks = np.arange(1, self.n_populate + 1, dtype=np.float64)
            p = ranks ** -float(traffic["zipf_alpha"])
            self._key_cdf = np.cumsum(p / p.sum())
        else:
            raise BenchmarkError(f"traffic keys {keys!r}: \"uniform\" or "
                                 "\"zipf\"")
        set_up = traffic.get("set_up", {})
        unknown = set(set_up) - set(SET_UP_KEYS)
        if unknown:
            raise BenchmarkError(f"traffic set_up {sorted(unknown)}: the "
                                 f"generator knows {list(SET_UP_KEYS)}")
        self.kill_shard_holders = int(set_up.get("kill_shard_holders", 0))
        self.victim_rule = set_up.get("victim", "seed")
        if self.kill_shard_holders < 0 or \
                self.victim_rule not in VICTIM_RULES:
            raise BenchmarkError(
                f"traffic set_up {set_up}: kill_shard_holders >= 0 and "
                f"victim one of {list(VICTIM_RULES)}")
        if self.kill_shard_holders and not self.n_populate:
            raise BenchmarkError("a set_up that kills shard holders needs "
                                 "populate_objects > 0: the degraded pool "
                                 "has to hold something")
        self._blocks: Dict[Tuple[int, int], tuple] = {}
        self.populated = self._populate_ops()

    # ------------------------------------------------------------ data

    def payload_pool(self) -> Dict[int, List[bytes]]:
        """size -> ``pool_n`` seeded random buffers."""
        return {
            size: [np.random.default_rng([self.seed, 0, size, j])
                   .integers(0, 256, size, dtype=np.uint8).tobytes()
                   for j in range(self.pool_n)]
            for size in self.sizes}

    def _draw(self, rng, n):
        size_i = np.searchsorted(self._size_cdf, rng.random(n), "right")
        size_i = np.minimum(size_i, len(self.sizes) - 1)
        return size_i, rng.integers(0, self.pool_n, n)

    def _populate_ops(self) -> List[Op]:
        """The objects set-up writes for reads to find."""
        rng = np.random.default_rng([self.seed, 1])
        size_i, payload = self._draw(rng, self.n_populate)
        return [Op("write_full", f"pop_{i:06d}", self.sizes[size_i[i]],
                   int(payload[i])) for i in range(self.n_populate)]

    def victims(self, data_shards: List[int]) -> List[int]:
        """The OSDs set-up kills.  ``data_shards[o]`` is the number of the
        pool's PGs in which OSD ``o`` holds a data shard (the cell reads it
        from the map before the kill)."""
        n_osds = len(data_shards)
        if self.kill_shard_holders >= n_osds:
            raise BenchmarkError(f"set_up kills {self.kill_shard_holders} "
                                 f"of {n_osds} OSDs")
        if self.victim_rule == "most_data_shards":
            order = sorted(range(n_osds), key=lambda o: (-data_shards[o], o))
        else:
            order = np.random.default_rng([self.seed, 4]).permutation(n_osds)
        return [int(o) for o in order[:self.kill_shard_holders]]

    # ------------------------------------------------------------- ops

    def _block(self, caller: int, b: int):
        key = (caller, b)
        blk = self._blocks.get(key)
        if blk is None:
            rng = np.random.default_rng([self.seed, 2, caller, b])
            kind_i = np.searchsorted(self._kind_cdf, rng.random(_BLOCK),
                                     "right")
            kind_i = np.minimum(kind_i, len(self.kinds) - 1)
            size_i, payload = self._draw(rng, _BLOCK)
            u = rng.random(_BLOCK)
            if not self.n_populate:
                target = np.zeros(_BLOCK, dtype=np.int64)
            elif self._key_cdf is None:
                target = (u * self.n_populate).astype(np.int64)
            else:
                target = np.searchsorted(self._key_cdf, u, "right")
            target = np.minimum(target, max(self.n_populate - 1, 0))
            blk = self._blocks[key] = (kind_i, size_i, payload, target)
        return blk

    def op(self, caller: int, i: int) -> Op:
        """Caller ``caller``'s ``i``-th operation of the run."""
        kind_i, size_i, payload, target = self._block(caller, i // _BLOCK)
        j = i % _BLOCK
        kind = self.kinds[kind_i[j]]
        if kind == "read":
            src = self.populated[target[j]]
            return Op("read", src.name, src.size, src.payload)
        return Op("write_full", f"obj_c{caller:02d}_{i:07d}",
                  self.sizes[size_i[j]], int(payload[j]))
