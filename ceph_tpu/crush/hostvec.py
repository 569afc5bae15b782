"""Host CRUSH walk, vectorised across a pool's PGs and a bucket's items.

The engine for a pool whose walk is too small to be worth a device
dispatch (and its compile) and too large for the per-PG scalar chain: an
EC pool that takes 12 of 12 hosts ``indep`` collides and retries its way
through ~46 bucket choices a PG, 18 ms a PG on the scalar oracle and
seconds an epoch on the loop every daemon shares.  Here every PG is a
lane of a numpy array and the retry loops of mapper.c run once for all
lanes under masks, so an epoch costs a few hundred numpy calls whatever
the PG count.

Same contract as ``TensorMapper``: outputs identical to ``ScalarMapper``
(crush/scalar.py, the reference, which this module follows line by line)
for straw2 maps with zero local retries; anything else raises
``NotImplementedError`` at construction or at ``do_rule_batch`` and the
caller (``OSDMap.pool_mapping``) takes another engine.  Rules are
``take <bucket>; choose|chooseleaf firstn|indep n type t; emit`` groups
(what ``OSDMonitor``'s pool create and ``build_hierarchy`` write); a
rule that chains choose steps is refused too.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ceph_tpu.crush.ln import crush_ln
from ceph_tpu.crush.types import (
    CRUSH_ITEM_NONE,
    CRUSH_ITEM_UNDEF,
    CrushMap,
    RULE_CHOOSELEAF_FIRSTN,
    RULE_CHOOSELEAF_INDEP,
    RULE_CHOOSE_FIRSTN,
    RULE_CHOOSE_INDEP,
    RULE_CHOOSE_OPS,
    RULE_EMIT,
    RULE_SET_CHOOSELEAF_STABLE,
    RULE_SET_CHOOSELEAF_TRIES,
    RULE_SET_CHOOSELEAF_VARY_R,
    RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
    RULE_SET_CHOOSE_LOCAL_TRIES,
    RULE_SET_CHOOSE_TRIES,
    RULE_TAKE,
)
from ceph_tpu.ops import jenkins

S64_MIN = np.iinfo(np.int64).min

_LN_NEG: Optional[np.ndarray] = None


def _ln_neg() -> np.ndarray:
    """2^48 - crush_ln(u) for every 16-bit u (512 KiB, built once a
    process from the scalar ``crush_ln``): a straw2 draw is
    ``-(table[u] // weight)``, C's truncating division of a negative."""
    global _LN_NEG
    if _LN_NEG is None:
        _LN_NEG = np.array([0x1000000000000 - crush_ln(u)
                            for u in range(0x10000)], dtype=np.int64)
    return _LN_NEG


class HostVecMapper:
    @staticmethod
    def unsupported_reason(cmap: CrushMap) -> Optional[str]:
        t = cmap.tunables
        if t.choose_local_tries or t.choose_local_fallback_tries:
            return "legacy tunables (local retries)"
        for b in cmap.buckets.values():
            if b.alg != "straw2":
                return f"non-straw2 bucket ({b.alg})"
            if any(i < 0 and i not in cmap.buckets for i in b.items):
                return "a bucket lists a bucket the map does not have"
        return None

    def __init__(self, cmap: CrushMap):
        why = self.unsupported_reason(cmap)
        if why or not cmap.buckets:
            raise NotImplementedError(why or "no buckets")
        self.map = cmap
        self.max_devices = cmap.max_devices
        ids = sorted(cmap.buckets, reverse=True)        # -1, -2, ...
        width = max(max(b.size for b in cmap.buckets.values()), 1)
        # row of bucket id b is _row[-1 - b]; ids may be sparse
        self._row = np.full(-ids[-1], -1, dtype=np.int64)
        self._items = np.zeros((len(ids), width), dtype=np.int64)
        self._weights = np.zeros((len(ids), width), dtype=np.int64)
        self._sizes = np.zeros(len(ids), dtype=np.int64)
        self._types = np.zeros(len(ids), dtype=np.int64)
        for row, bid in enumerate(ids):
            b = cmap.buckets[bid]
            self._row[-1 - bid] = row
            self._items[row, :b.size] = b.items
            self._weights[row, :b.size] = b.weights
            self._sizes[row] = b.size
            self._types[row] = b.type
        self._pad = np.arange(width)[None, :] >= self._sizes[:, None]
        self._ln = _ln_neg()

    # -- the pieces of mapper.c, one lane a PG ---------------------------

    def _rows(self, items: np.ndarray) -> np.ndarray:
        """Bucket rows of negative ``items`` (all are buckets of the
        map: checked at construction)."""
        return self._row[-1 - items]

    def _straw2(self, rows, x, r) -> np.ndarray:
        """bucket_straw2_choose for each lane's bucket ``rows``: the
        first item of the largest draw; padding and zero weights draw
        S64_MIN, so item 0 wins a bucket of zero weights as in C."""
        width = int(self._sizes[rows].max())
        items = self._items[rows, :width]
        if width == 1:
            return items[:, 0]      # the one item wins whatever it draws
        w = self._weights[rows, :width]
        u = jenkins.hash3(x[:, None], items.astype(np.uint32),
                          r.astype(np.uint32)[:, None]) & np.uint32(0xFFFF)
        draw = -(self._ln[u] // np.maximum(w, 1))
        draw[(w == 0) | self._pad[rows, :width]] = S64_MIN
        return items[np.arange(len(rows)), draw.argmax(axis=1)]

    def _is_out(self, weights: np.ndarray, item, x) -> np.ndarray:
        known = item < len(weights)
        w = weights[np.where(known, item, 0)]
        h = jenkins.hash2(x, item.astype(np.uint32)) & np.uint32(0xFFFF)
        return ~known | (w == 0) | ((w < 0x10000) & (h >= w))

    def _descend(self, rows, x, r, type_: int):
        """Walk each lane from its bucket down to an item of ``type_``
        (r depends on the bucket on the way only for uniform buckets,
        which this engine refuses).  Returns (item, status): 0 an item
        of the type, 1 an empty bucket on the way, 2 a bad item (a
        device id past max_devices, or a device where a bucket was
        wanted)."""
        n = len(rows)
        rows = rows.copy()
        item = np.zeros(n, dtype=np.int64)
        status = np.full(n, -1, dtype=np.int64)
        live = np.arange(n)
        while len(live):
            rw = rows[live]
            empty = self._sizes[rw] == 0
            status[live[empty]] = 1
            live, rw = live[~empty], rw[~empty]
            if not len(live):
                break
            it = self._straw2(rw, x[live], r[live])
            item[live] = it
            isb = it < 0
            itype = np.where(isb, self._types[self._rows(np.where(
                isb, it, -1))], 0)
            bad = (it >= self.max_devices) | ((itype != type_) & ~isb)
            done = ~bad & (itype == type_)
            status[live[bad]] = 2
            status[live[done]] = 0
            deeper = ~bad & ~done
            rows[live[deeper]] = self._rows(it[deeper])
            live = live[deeper]
        return item, status

    def _choose_indep(self, rows, x, weights, width: int, numrep: int,
                      type_: int, tries: int, recurse_tries: int,
                      leaf: bool, parent_r, rep0):
        """crush_choose_indep, one lane a call of the scalar: ``width``
        slots from replica number ``rep0`` (per lane) on.  Returns
        (out, out2), each (lanes, width); out2 is None unless ``leaf``.

        A round (one ``ftotal``) draws every open slot of every lane in
        one descent: a slot's draw depends on (x, r) alone, and only the
        collision check reads what the round has placed so far, so that
        check, and the writes a collision forbids, run slot by slot
        afterwards.  Choosing 12 of 12 takes the unluckiest lane's ~50
        rounds; a round is a few dozen numpy calls however many lanes
        are still open."""
        n = len(rows)
        out = np.full((n, width), CRUSH_ITEM_UNDEF, dtype=np.int64)
        out2 = out.copy() if leaf else None
        left = np.full(n, width, dtype=np.int64)
        for ftotal in range(tries):
            li, col = np.nonzero((left > 0)[:, None]
                                 & (out == CRUSH_ITEM_UNDEF))
            if not len(li):
                break
            r = rep0[li] + col + parent_r[li] + numrep * ftotal
            item, status = self._descend(rows[li], x[li], r, type_)
            good = status == 0
            if leaf:
                found = item.copy()     # a device is its own leaf
                sub = np.nonzero(good & (item < 0))[0]
                if len(sub):
                    found[sub] = self._choose_indep(
                        self._rows(item[sub]), x[li[sub]], weights, 1,
                        numrep, 0, recurse_tries, 0, False, r[sub],
                        rep0[li[sub]] + col[sub])[0][:, 0]
            if type_ == 0:
                good[good] = ~self._is_out(weights, item[good],
                                           x[li[good]])
            for k in range(width):
                at = np.nonzero(col == k)[0]
                if not len(at):
                    continue
                ln, it = li[at], item[at]
                bad = status[at] == 2
                out[ln[bad], k] = CRUSH_ITEM_NONE
                if leaf:
                    out2[ln[bad], k] = CRUSH_ITEM_NONE
                go = (status[at] == 0) & ~(out[ln] == it[:, None]).any(axis=1)
                if leaf:
                    out2[ln[go], k] = found[at][go]
                    go &= found[at] != CRUSH_ITEM_NONE
                go &= good[at]
                out[ln[go], k] = it[go]
                left[ln[bad | go]] -= 1
        for o in (out, out2):
            if o is not None:
                o[o == CRUSH_ITEM_UNDEF] = CRUSH_ITEM_NONE
        return out, out2

    def _choose_firstn(self, rows, x, weights, reps: int, rep0,
                       type_: int, out, outpos, count, tries: int,
                       recurse_tries: int, leaf: bool, vary_r: int,
                       stable: int, out2, parent_r) -> np.ndarray:
        """crush_choose_firstn with zero local retries: ``reps``
        replicas from replica number ``rep0`` on, written into ``out``
        (and the leaves into ``out2``) from ``outpos`` on while
        ``count`` lasts; all per lane.  Returns the lanes' new outpos."""
        outpos, count = outpos.copy(), count.copy()
        cols = np.arange(out.shape[1])[None, :]
        for j in range(reps):
            ftotal = np.zeros(len(rows), dtype=np.int64)
            trying = count > 0
            while trying.any():
                sel = np.nonzero(trying)[0]
                r = rep0[sel] + j + parent_r[sel] + ftotal[sel]
                item, status = self._descend(rows[sel], x[sel], r, type_)
                # neither a collision nor (below) a leaf that cannot be
                # had nor an item that is out: ok
                ok = (status == 0) & ~((out[sel] == item[:, None])
                                       & (cols < outpos[sel, None])).any(axis=1)
                if leaf:
                    sub = np.nonzero(ok & (item < 0))[0]
                    if len(sub):
                        at = sel[sub]
                        leaves, pos = out2[at], outpos[at]
                        ok[sub] = self._choose_firstn(
                            self._rows(item[sub]), x[at], weights, 1,
                            np.zeros_like(pos) if stable else pos, 0,
                            leaves, pos, count[at], recurse_tries, 0, False,
                            vary_r, stable, None,
                            r[sub] >> (vary_r - 1) if vary_r
                            else np.zeros_like(pos)) > pos
                        out2[at] = leaves
                    dev = ok & (item >= 0)
                    out2[sel[dev], outpos[sel[dev]]] = item[dev]
                if type_ == 0:
                    ok[ok] = ~self._is_out(weights, item[ok], x[sel[ok]])
                won = sel[ok]
                out[won, outpos[won]] = item[ok]
                outpos[won] += 1
                count[won] -= 1
                lost = sel[~ok & (status != 2)]     # rejected or collided
                ftotal[lost] += 1
                # placed, a bad item (skip_rep), or out of tries
                trying[sel] = False
                trying[lost[ftotal[lost] < tries]] = True
        return outpos

    # -- the rule VM -----------------------------------------------------

    def do_rule_batch(self, ruleno: int, xs, result_max: int, weights,
                      choose_args=None) -> Tuple[np.ndarray, np.ndarray]:
        """``ScalarMapper.do_rule`` for every x of ``xs`` at once.
        Returns (result (N, result_max) int64, rlen (N,)): row i's first
        rlen[i] entries are do_rule's list (NONE holes included)."""
        if choose_args is not None:
            raise NotImplementedError("choose_args")
        m = self.map
        t = m.tunables
        x = np.asarray(xs).astype(np.uint32)
        n = len(x)
        weights = np.asarray(weights, dtype=np.int64)
        zeros = np.zeros(n, dtype=np.int64)
        choose_tries = t.choose_total_tries + 1
        leaf_tries = 0
        vary_r, stable = t.chooseleaf_vary_r, t.chooseleaf_stable
        result = np.full((n, result_max), CRUSH_ITEM_NONE, dtype=np.int64)
        rlen = zeros.copy()
        take, w = None, None    # w: (items (n, k), their count per lane)
        for op, arg1, arg2 in m.rules[ruleno].steps:
            if op == RULE_TAKE:
                if arg1 not in m.buckets:
                    raise NotImplementedError("take of a device")
                take, w = arg1, None
            elif op == RULE_SET_CHOOSE_TRIES:
                if arg1 > 0:
                    choose_tries = arg1
            elif op == RULE_SET_CHOOSELEAF_TRIES:
                if arg1 > 0:
                    leaf_tries = arg1
            elif op in (RULE_SET_CHOOSE_LOCAL_TRIES,
                        RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES):
                if arg1 > 0:
                    raise NotImplementedError("local retries")
            elif op == RULE_SET_CHOOSELEAF_VARY_R:
                if arg1 >= 0:
                    vary_r = arg1
            elif op == RULE_SET_CHOOSELEAF_STABLE:
                if arg1 >= 0:
                    stable = arg1
            elif op in RULE_CHOOSE_OPS:
                if take is None:
                    if w is not None:
                        raise NotImplementedError("chained choose steps")
                    continue    # nothing taken: wsize == 0
                rows = np.full(n, self._row[-1 - take], dtype=np.int64)
                take = None
                numrep = arg1 if arg1 > 0 else arg1 + result_max
                if numrep <= 0:
                    w = (np.zeros((n, 0), dtype=np.int64), zeros.copy())
                    continue
                leaf = op in (RULE_CHOOSELEAF_FIRSTN, RULE_CHOOSELEAF_INDEP)
                if op in (RULE_CHOOSE_FIRSTN, RULE_CHOOSELEAF_FIRSTN):
                    o = np.zeros((n, result_max), dtype=np.int64)
                    c = np.zeros((n, result_max), dtype=np.int64)
                    if leaf_tries:
                        recurse_tries = leaf_tries
                    elif t.chooseleaf_descend_once:
                        recurse_tries = 1
                    else:
                        recurse_tries = choose_tries
                    got = self._choose_firstn(
                        rows, x, weights, numrep, zeros, arg2, o, zeros,
                        np.full(n, result_max, dtype=np.int64),
                        choose_tries, recurse_tries, leaf, vary_r, stable,
                        c, zeros)
                else:
                    size = min(numrep, result_max)
                    o, c = self._choose_indep(
                        rows, x, weights, size, numrep, arg2, choose_tries,
                        leaf_tries if leaf_tries else 1, leaf, zeros, zeros)
                    got = np.full(n, size, dtype=np.int64)
                w = (c if leaf else o, got)
            elif op == RULE_EMIT:
                if take is not None:    # a bare take emits the bucket
                    raise NotImplementedError("emit of a take")
                if w is not None:
                    vals, cnt = w
                    for i in range(vals.shape[1]):
                        put = np.nonzero((i < cnt) & (rlen < result_max))[0]
                        result[put, rlen[put]] = vals[put, i]
                        rlen[put] += 1
                w = None
            else:
                raise NotImplementedError(f"rule op {op}")
        return result, rlen
