"""Vectorized CRUSH mapper: whole-OSDMap placement as one TPU dispatch.

The TPU-native replacement for per-PG scalar crush_do_rule calls (reference
mapper.c:883): every PG is a lane, and the firstn/indep retry loops become
masked fixed-trip loops (SURVEY §3.3's vectorization plan).  Exactness
contract: identical outputs to ScalarMapper (and therefore to the reference
C) for straw2 maps with zero local retries — the reference's 'optimal'
tunables profile.  Straw2 draws use uint32-pair arithmetic with pack-time
Granlund-Montgomery reciprocals (ops/u64pair.py) instead of emulated s64.

Supported: straw2 buckets; TAKE / CHOOSE(LEAF)_FIRSTN / CHOOSE(LEAF)_INDEP /
EMIT / SET_* steps; vary_r / stable / descend_once semantics.  Uniform/list/
tree/straw buckets and nonzero local-retry tunables fall back to the scalar
oracle at the OSDMap layer.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.crush.ln import LH_TBL, RH_TBL
from ceph_tpu.crush._ll_table import LL_TBL
from ceph_tpu.crush.types import (
    CRUSH_ITEM_NONE,
    CRUSH_ITEM_UNDEF,
    CrushMap,
    RULE_CHOOSELEAF_FIRSTN,
    RULE_CHOOSELEAF_INDEP,
    RULE_CHOOSE_FIRSTN,
    RULE_CHOOSE_INDEP,
    RULE_EMIT,
    RULE_SET_CHOOSELEAF_STABLE,
    RULE_SET_CHOOSELEAF_TRIES,
    RULE_SET_CHOOSELEAF_VARY_R,
    RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
    RULE_SET_CHOOSE_LOCAL_TRIES,
    RULE_SET_CHOOSE_TRIES,
    RULE_TAKE,
)
from ceph_tpu.ops import jenkins, u64pair

U32 = jnp.uint32
I32 = jnp.int32


def _split_u64(vals) -> Tuple[np.ndarray, np.ndarray]:
    v = np.asarray(vals, dtype=np.object_)
    hi = np.array([int(x) >> 32 for x in v], dtype=np.uint32)
    lo = np.array([int(x) & 0xFFFFFFFF for x in v], dtype=np.uint32)
    return hi, lo


class TensorMapper:
    @staticmethod
    def unsupported_reason(cmap: CrushMap):
        """Cheap shape probe: None when this map can run vectorized,
        else the rejection reason — the SAME conditions __init__
        enforces, minus the array/device construction (mon `status`
        answers placement_path with this, not a full build)."""
        t = cmap.tunables
        if t.choose_local_tries or t.choose_local_fallback_tries:
            return "legacy tunables (local retries)"
        ids = sorted(cmap.buckets, reverse=True)
        if ids != [-1 - i for i in range(len(ids))]:
            return "sparse bucket ids"
        for b in cmap.buckets.values():
            if b.alg != "straw2":
                return f"non-straw2 bucket ({b.alg})"
        return None

    def __init__(self, cmap: CrushMap, chunk: int = 1 << 16):
        self.map = cmap
        self.chunk = chunk
        t = cmap.tunables
        if t.choose_local_tries or t.choose_local_fallback_tries:
            raise NotImplementedError(
                "vectorized mapper requires zero local retries (optimal "
                "tunables); use ScalarMapper for legacy profiles")
        ids = sorted(cmap.buckets, reverse=True)
        self.nb = len(ids)
        assert ids == [-1 - i for i in range(self.nb)], "bucket ids must be dense"
        max_sz = max(b.size for b in cmap.buckets.values())
        items = np.zeros((self.nb, max_sz), dtype=np.int32)
        weights = np.zeros((self.nb, max_sz), dtype=np.uint32)
        sizes = np.zeros(self.nb, dtype=np.int32)
        btypes = np.zeros(self.nb, dtype=np.int32)
        recip_hi = np.zeros((self.nb, max_sz), dtype=np.uint32)
        recip_lo = np.zeros((self.nb, max_sz), dtype=np.uint32)
        for bid, b in cmap.buckets.items():
            row = -1 - bid
            if b.alg != "straw2":
                raise NotImplementedError(
                    f"vectorized mapper supports straw2 buckets, not {b.alg}")
            sizes[row] = b.size
            btypes[row] = b.type
            items[row, : b.size] = b.items
            weights[row, : b.size] = b.weights
            for i, w in enumerate(b.weights):
                recip_hi[row, i], recip_lo[row, i] = self._recip_u64(int(w))
        self.items = jnp.asarray(items)
        self.iweights = jnp.asarray(weights)
        self.sizes = jnp.asarray(sizes)
        self.btypes = jnp.asarray(btypes)
        self.recip_hi = jnp.asarray(recip_hi)
        self.recip_lo = jnp.asarray(recip_lo)
        self._items_np = items
        self._iweights_np = weights
        # choose_args override tensors (inactive placeholders; see
        # _activate_choose_args)
        self._ca_active = False
        self._ca_pdim = 1
        self._ca_ids = jnp.zeros((1, 1), dtype=I32)
        self._ca_w = jnp.zeros((1, 1), dtype=U32)
        self._ca_rh = jnp.zeros((1, 1), dtype=U32)
        self._ca_rl = jnp.zeros((1, 1), dtype=U32)
        self._ca_pmax = jnp.zeros(1, dtype=I32)
        self._ca_cache: Dict = {}
        self.max_devices = cmap.max_devices
        self.max_depth = cmap.max_depth()
        rh_hi, rh_lo = _split_u64(RH_TBL)
        lh_hi, lh_lo = _split_u64(LH_TBL)
        ll_hi, ll_lo = _split_u64(LL_TBL)
        self._rh = (jnp.asarray(rh_hi), jnp.asarray(rh_lo))
        self._lh = (jnp.asarray(lh_hi), jnp.asarray(lh_lo))
        self._ll = (jnp.asarray(ll_hi), jnp.asarray(ll_lo))
        self._rh_np = _split_u64(RH_TBL)
        self._lh_np = _split_u64(LH_TBL)
        self._ll_np = _split_u64(LL_TBL)
        # precomputed |ln| table (512 KiB): one gather on the hot path.
        # (A select-tree variant, _ln_neg_tree, is exact and ~14x faster per
        # element but blows up compile time when inlined in the retry loops.
        # A Pallas rewrite was evaluated in round 3 for the sibling gf8
        # matmul and measured ~7x SLOWER than XLA's fusion — see
        # ops/gf8_pallas.py — so the gather path stays; at 239M mappings/s
        # for the 10k-OSD/1M-PG benchmark it is not the bottleneck.)
        from ceph_tpu.crush.ln import crush_ln

        ln_neg = [0x1000000000000 - crush_ln(u) for u in range(0x10000)]
        lnn_hi, lnn_lo = _split_u64(ln_neg)
        self._lnn = (jnp.asarray(lnn_hi), jnp.asarray(lnn_lo))
        self._build_fast_straw2(items, weights, sizes, ln_neg)
        # per-bucket scalar metadata as ONE row-gathered tensor: element
        # gathers (sizes[bno], btypes[bno], ...) scalarize on TPU (~0.5 ms
        # per 64 Ki lanes) while row gathers vectorize (~76 us); packing
        # [size, type, wbase, rep] into one (nb, 4) row costs one row
        # gather where four element gathers used to run
        meta = np.zeros((self.nb, 4), dtype=np.int32)
        meta[:, 0] = sizes
        meta[:, 1] = btypes
        if self._fast:
            meta[:, 2] = (self._wclass_np.astype(np.int64) << 17).astype(
                np.int32)
            meta[:, 3] = np.asarray(self._rep)[self._wclass_np]
        self._meta = jnp.asarray(meta)
        # bound per-dispatch memory: lanes * max_bucket_size * ~32 u32 temps
        self.chunk = max(512, min(chunk, (1 << 24) // max(max_sz, 1)))
        self._compiled: Dict = {}

    # ------------------------------------------------- fast straw2 tables

    _MAX_WEIGHT_CLASSES = 64

    def _build_fast_straw2(self, items, weights, sizes, ln_neg):
        """Precompute the gather-free straw2 path (round 5).

        The honest (on-device-loop) benchmark showed the per-(lane, item)
        gathers from the 64 Ki |ln| table scalarize on TPU and cost ~37 ms
        per straw2 call at 64 Ki lanes — ~100% of rule runtime.  For
        buckets whose item weights are UNIFORM, the winning item can be
        found without evaluating draws at all: draw = div64_s64(ln, w) is
        a non-decreasing function g of u = hash & 0xffff (crush_ln is
        non-decreasing except at the single u = 65535 table anomaly), so
        "first item with draw == max draw" (mapper.c:322-367 keeps the
        first strict maximum) equals "first item whose u lies in the top
        plateau of g".  Host-side, per distinct bucket weight, we build
        the plateau-start table on a doubled domain u' = 2u (u = 65535
        maps to an odd/even representative that is order-isomorphic to
        g(65535), preserving exact tie semantics with the anomaly), and
        the device does: u'max = max(u'), T = P2[u'max], winner = first
        item with u' >= T — ONE lane-sized gather instead of two
        (lane x item)-sized ones.  Bit-exact vs the C semantics by
        construction; golden tests cover it.

        Maps with any non-uniform bucket (e.g. balancer weight_set
        overrides) keep the general |ln|-gather path.
        """
        nb = items.shape[0]
        self._fast = False
        self._wclass_np = None
        # placeholders so _tensor_args stays total on non-fast maps
        self._p2flat = jnp.zeros(1, dtype=I32)
        self._wclass = jnp.zeros(1, dtype=I32)
        self._rep = jnp.zeros(1, dtype=I32)
        # uniform check per bucket (over the first `size` items)
        class_weights = []
        wclass = np.zeros(nb, dtype=np.int32)
        for row in range(nb):
            sz = int(sizes[row])
            ws = weights[row, :sz]
            if sz == 0:
                wclass[row] = 0 if class_weights else -1
                continue
            w0 = int(ws[0])
            if w0 == 0 or not np.all(ws == w0):
                return  # non-uniform bucket: general path for this map
            if w0 not in class_weights:
                class_weights.append(w0)
            wclass[row] = class_weights.index(w0)
        if not class_weights or len(class_weights) > self._MAX_WEIGHT_CLASSES:
            return
        # empty buckets with no class yet: point at class 0 (never drawn)
        wclass[wclass < 0] = 0
        lnn = np.array(ln_neg, dtype=np.uint64)
        # the construction below relies on crush_ln being non-decreasing on
        # [0, 65534] (the single decreasing site is 65534 -> 65535)
        assert np.all(np.diff(lnn[:65535].astype(np.int64)) <= 0)
        p2_all = np.zeros((len(class_weights), 1 << 17), dtype=np.int32)
        rep_all = np.zeros(len(class_weights), dtype=np.int32)
        for ci, w in enumerate(class_weights):
            # g(u) = -draw = ln_neg[u] // w, non-increasing on [0, 65534]
            g = (lnn // np.uint64(w)).astype(np.int64)
            body, g_last = g[:65535], int(g[65535])
            # plateau starts on the monotone body (g non-increasing)
            change = np.empty(65535, dtype=bool)
            change[0] = True
            change[1:] = body[1:] != body[:-1]
            starts = np.maximum.accumulate(
                np.where(change, np.arange(65535), 0))
            p2 = np.zeros(1 << 17, dtype=np.int32)
            p2[0::2][:65535] = 2 * starts
            p2[1::2] = np.arange(1, 1 << 17, 2)  # odd slots: own plateau
            # u = 65535 anomaly: place g_last order-exactly among the body
            # (body is DEscending in u; draws AScend).  Find its plateau.
            asc = body[::-1]  # ascending g
            import bisect

            lo = bisect.bisect_left(asc, g_last)
            hi_i = bisect.bisect_right(asc, g_last)
            if lo != hi_i:
                # ties an existing plateau [a, b] (in u-domain)
                a = 65534 - (hi_i - 1)
                b = 65534 - lo
                rep = 2 * b        # behaves as the plateau's largest u
                p2[rep] = 2 * a    # plateau start covers the anomaly rep
                rep_all[ci] = rep
                p2[2 * 65535] = 2 * a  # if u'max==2*65535 slot ever read
            else:
                # unique value: sits between two plateaus; `lo` entries of
                # the body have g < g_last (draw greater), and they occupy
                # the largest u values, so the first such u-index is:
                a = 65535 - lo
                rep = 2 * a - 1 if a > 0 else -1
                rep_all[ci] = rep
                if rep >= 0:
                    p2[rep] = rep  # its own (singleton) plateau
            p2_all[ci] = p2
        self._fast = True
        self._wclass_np = wclass
        self._p2flat = jnp.asarray(p2_all.reshape(-1))
        self._wclass = jnp.asarray(wclass)
        self._rep = jnp.asarray(rep_all)

    # ------------------------------------------------------- choose_args

    @staticmethod
    def _recip_u64(w: int) -> Tuple[int, int]:
        if w == 1:
            r = 2**64 - 1
        elif w > 1:
            r = 2**64 // w
        else:
            r = 0
        return r >> 32, r & 0xFFFFFFFF

    def _build_ca_tensors(self, cargs) -> Tuple[Dict, int]:
        """Device tensors for a choose_args set (reference crush.h:273-278
        crush_choose_arg: per-bucket weight_set positions + id remaps,
        consumed by bucket_straw2_choose via mapper.c:302-320).

        Layout: ids (nb, S) replace the HASH input (chosen items stay the
        bucket's real items); weights flatten to (nb*P, S) rows indexed by
        bno*P + min(position, pmax[bno]), with precomputed u64 reciprocals
        for the draw division."""
        nb, S = self._items_np.shape
        P = 1
        for a in cargs.values():
            if a.weight_set:
                P = max(P, len(a.weight_set))
        ids = self._items_np.astype(np.int64).copy()
        w = np.repeat(self._iweights_np[:, None, :], P, axis=1).copy()
        pmax = np.zeros(nb, dtype=np.int32)
        for bid, arg in cargs.items():
            row = -1 - bid
            if not (0 <= row < nb):
                continue
            if arg.ids:
                ids[row, :len(arg.ids)] = arg.ids
            if arg.weight_set:
                # positions beyond len(weight_set) are never selected:
                # _straw2 clamps with pmax, so no padding is needed
                for p, ws in enumerate(arg.weight_set):
                    w[row, p, :len(ws)] = ws
                pmax[row] = len(arg.weight_set) - 1
        rh = np.zeros((nb, P, S), dtype=np.uint32)
        rl = np.zeros((nb, P, S), dtype=np.uint32)
        recip_memo: Dict[int, Tuple[int, int]] = {}
        for idx, wv in np.ndenumerate(w):
            wv = int(wv)
            pair = recip_memo.get(wv)
            if pair is None:
                pair = recip_memo[wv] = self._recip_u64(wv)
            rh[idx], rl[idx] = pair
        tensors = {
            "_ca_ids": jnp.asarray(ids.astype(np.int32)),
            "_ca_w": jnp.asarray(w.reshape(nb * P, S).astype(np.uint32)),
            "_ca_rh": jnp.asarray(rh.reshape(nb * P, S)),
            "_ca_rl": jnp.asarray(rl.reshape(nb * P, S)),
            "_ca_pmax": jnp.asarray(pmax),
        }
        return tensors, P

    def _resolve_choose_args(self, choose_args):
        """-> (cache_key, tensors, P) for a name or {bucket_id: ChooseArg}."""
        if isinstance(choose_args, str):
            cargs = self.map.choose_args[choose_args]
            key = choose_args
        else:
            cargs = choose_args
            # content-addressed: a balancer loop passing fresh weights for
            # the same buckets must never hit a stale tensor set
            key = ("dict", tuple(sorted(
                (bid,
                 tuple(a.ids) if a.ids else None,
                 tuple(tuple(ws) for ws in a.weight_set)
                 if a.weight_set else None)
                for bid, a in cargs.items())))
        cached = self._ca_cache.get(key)
        if cached is None:
            cached = self._ca_cache[key] = self._build_ca_tensors(cargs)
            # bound the content-addressed tensor cache (balancer loops
            # mint a fresh weight set per iteration)
            while len(self._ca_cache) > 16:
                self._ca_cache.pop(next(iter(self._ca_cache)))
        return key, cached[0], cached[1]

    # ------------------------------------------------------------------ ln

    @staticmethod
    def _tree_lookup(table: np.ndarray, idx, nbits: int):
        """Constant-select-tree table lookup: TPU gathers scalarize, but a
        log2(N)-deep where-tree over scalar constants fuses into one
        elementwise pass (~14x faster than gather at 16M elements)."""
        n = 1 << nbits
        level = [np.uint32(int(v)) for v in table] + \
                [np.uint32(0)] * (n - len(table))
        bits = [(idx >> b) & 1 for b in range(nbits)]
        for b in range(nbits):
            sel = bits[b] == 1
            level = [jnp.where(sel, level[j + 1], level[j])
                     for j in range(0, len(level), 2)]
        return level[0]

    def _ln_neg_tree(self, u):
        """Gather-free |ln|: arithmetic path with select-tree LUTs."""
        x = (u + 1).astype(U32)
        no_msb = (x & 0x18000) == 0
        bits = (jax.lax.clz((x & 0x1FFFF).astype(U32)).astype(I32) - 16)
        bits = jnp.where(no_msb, bits, 0).astype(U32)
        x = (x << bits).astype(U32)
        iexpon = (15 - bits.astype(I32)).astype(U32)
        k = (x >> 8) - 128
        rh_hi = self._tree_lookup(self._rh_np[0], k, 8)
        rh_lo = self._tree_lookup(self._rh_np[1], k, 8)
        r0 = rh_lo & 0xFFFF
        r1 = rh_lo >> 16
        r2 = rh_hi & 0xFFFF
        r3 = rh_hi >> 16
        p0 = x * r0
        t1 = x * r1 + (p0 >> 16)
        t2 = x * r2 + (t1 >> 16)
        t3 = x * r3 + (t2 >> 16)
        index2 = t3 & 0xFF
        lh = (self._tree_lookup(self._lh_np[0], k, 8),
              self._tree_lookup(self._lh_np[1], k, 8))
        ll = (self._tree_lookup(self._ll_np[0], index2, 8),
              self._tree_lookup(self._ll_np[1], index2, 8))
        s = u64pair.shr(u64pair.add(lh, ll), 4)
        res = u64pair.add((iexpon << 12, jnp.zeros_like(x)), s)
        return u64pair.sub((jnp.full_like(x, 0x10000), jnp.zeros_like(x)), res)

    def _ln_neg(self, u):
        """|ln| = 0x1000000000000 - crush_ln(u), as a uint32 pair.

        Exact mirror of reference mapper.c:248-290 in 32-bit ops.
        """
        x = (u + 1).astype(U32)
        no_msb = (x & 0x18000) == 0
        bits = (jax.lax.clz((x & 0x1FFFF).astype(U32)).astype(I32) - 16)
        bits = jnp.where(no_msb, bits, 0).astype(U32)
        x = (x << bits).astype(U32)
        iexpon = (15 - bits.astype(I32)).astype(U32)
        k = (x >> 8) - 128
        rh_hi = self._rh[0][k]
        rh_lo = self._rh[1][k]
        # xl64 = (x * RH) >> 48 via 16-bit limbs of RH
        r0 = rh_lo & 0xFFFF
        r1 = rh_lo >> 16
        r2 = rh_hi & 0xFFFF
        r3 = rh_hi >> 16
        p0 = x * r0
        t1 = x * r1 + (p0 >> 16)
        t2 = x * r2 + (t1 >> 16)
        t3 = x * r3 + (t2 >> 16)
        index2 = t3 & 0xFF
        s = u64pair.add((self._lh[0][k], self._lh[1][k]),
                        (self._ll[0][index2], self._ll[1][index2]))
        s = u64pair.shr(s, 4)
        res = u64pair.add((iexpon << 12, jnp.zeros_like(x)), s)
        return u64pair.sub((jnp.full_like(x, 0x10000), jnp.zeros_like(x)), res)

    # -------------------------------------------------------------- straw2

    def _straw2(self, bno, x, r, wpos=None):
        """bucket_straw2_choose (mapper.c:322-367) over a lane batch.

        bno (L,), x (L,) uint32, r (L,) int32 -> chosen item (L,) int32.
        ``wpos`` (L,) is the output position selecting the choose_args
        weight_set row (mapper.c:302-320); ignored without choose_args.

        Uniform-weight maps take the gather-free plateau path (see
        _build_fast_straw2); choose_args overrides and non-uniform maps
        evaluate |ln| draws via table gather.
        """
        it = self.items[bno]                      # (L, S)
        meta = self._meta[bno]                    # (L, 4) row gather
        sz = meta[:, 0]
        if self._ca_active:
            # choose_args: alternate ids feed the hash (the chosen item
            # stays the bucket's real item), alternate weights feed the
            # draws
            hash_ids = self._ca_ids[bno]
            if wpos is None:
                wpos = jnp.zeros_like(bno)
            p = jnp.minimum(wpos, self._ca_pmax[bno])
            row = bno * self._ca_pdim + p
            wt = self._ca_w[row]                  # (L, S)
            u = jenkins.hash3(x[:, None], hash_ids.astype(U32),
                              r.astype(U32)[:, None]) & 0xFFFF
            pos = jnp.arange(it.shape[1], dtype=I32)
            invalid = (wt == 0) | (pos[None, :] >= sz[:, None])
            return self._draw_argmin(it, u, wt, self._ca_rh[row],
                                     self._ca_rl[row], invalid)
        u = jenkins.hash3(x[:, None], it.astype(U32), r.astype(U32)[:, None]) & 0xFFFF
        pos = jnp.arange(it.shape[1], dtype=I32)
        if self._fast:
            # uniform weights are nonzero by construction: invalid = padding
            invalid = pos[None, :] >= sz[:, None]
            u2 = jnp.where(u == 65535, meta[:, 3:4], (2 * u).astype(I32))
            u2 = jnp.where(invalid, I32(-1), u2)
            umax = u2.max(axis=1)
            tidx = meta[:, 2] + jnp.clip(umax, 0)
            thresh = self._p2flat[tidx]           # (L,) gather
            win = u2 >= thresh[:, None]
            idx = jnp.argmax(win, axis=1)
            return jnp.take_along_axis(it, idx[:, None], axis=1)[:, 0]
        wt = self.iweights[bno]
        invalid = (wt == 0) | (pos[None, :] >= sz[:, None])
        return self._draw_argmin(it, u, wt, self.recip_hi[bno],
                                 self.recip_lo[bno], invalid)

    def _draw_argmin(self, it, u, wt, rh, rl, invalid):
        """Shared |ln|-draw evaluation + first-occurrence two-level
        argmin (draw > high_draw semantics) over (L, S) lanes."""
        n = (self._lnn[0][u], self._lnn[1][u])
        qh, ql = u64pair.div_by_recip(n, wt, rh, rl)
        qh = jnp.where(invalid, jnp.uint32(0xFFFFFFFF), qh)
        ql = jnp.where(invalid, jnp.uint32(0xFFFFFFFF), ql)
        m1 = qh.min(axis=1, keepdims=True)
        c1 = qh == m1
        ql2 = jnp.where(c1, ql, jnp.uint32(0xFFFFFFFF))
        m2 = ql2.min(axis=1, keepdims=True)
        winner = c1 & (ql2 == m2)
        idx = jnp.argmax(winner, axis=1)
        return jnp.take_along_axis(it, idx[:, None], axis=1)[:, 0]

    # ------------------------------------------------------------- helpers

    def _is_out(self, weights, item, x):
        """is_out (mapper.c:407-421); item (L,) int32 device ids."""
        idx = jnp.clip(item, 0, self.max_devices - 1)
        w = weights[idx]
        over = item >= self.max_devices
        hashed = (jenkins.hash2(x, item.astype(U32)) & 0xFFFF) >= w
        return over | (w == 0) | ((w < 0x10000) & hashed)

    def _descend(self, start, x, r, type_, wpos=None):
        """Descend intervening buckets until an item of type_ (or dead end).

        Returns (item, hit_empty).  Mirrors the retry_bucket descent of
        choose_firstn/indep (same r at every level for straw2 maps).
        """
        cur = start
        hit_empty = jnp.zeros(x.shape, dtype=bool)
        for _ in range(self.max_depth):
            is_b = cur < 0
            bno = jnp.clip(-1 - cur, 0, self.nb - 1)
            meta = self._meta[bno]
            need = is_b & (meta[:, 1] != type_)
            empty = need & (meta[:, 0] == 0)
            hit_empty = hit_empty | empty
            nxt = self._straw2(bno, x, r, wpos)
            cur = jnp.where(need & ~empty, nxt, cur)
        return cur, hit_empty

    def _bad_item(self, cur, type_):
        bno = jnp.clip(-1 - cur, 0, self.nb - 1)
        wrong_bucket = (cur < 0) & (self._meta[bno][:, 1] != type_)
        wrong_dev = (cur >= 0) & ((type_ != 0) | (cur >= self.max_devices))
        return wrong_bucket | wrong_dev

    # -------------------------------------------------------------- firstn

    def _leaf_firstn(self, host, x, inner_rep, sub_r, tries, out2, cnt, act):
        """Recursive chooseleaf descent (single stable rep).

        Mirrors the recursive crush_choose_firstn call at mapper.c:556-573.
        Returns (leaf, ok).
        """
        already = host >= 0  # "we already have a leaf"
        leaf = jnp.where(already, host, CRUSH_ITEM_NONE)
        done = ~act | already
        lftotal = jnp.zeros_like(x, dtype=I32)

        def cond(s):
            leaf, done, lftotal = s
            return jnp.any(~done & (lftotal < tries))

        def body(s):
            leaf, done, lftotal = s
            live = ~done & (lftotal < tries)
            r2 = inner_rep + sub_r + lftotal
            # choose_args position: the recursing slot (scalar passes the
            # outer outpos through to the leaf's bucket_choose)
            cur, hit_empty = self._descend(host, x, r2, 0, cnt)
            bad = self._bad_item(cur, 0) & ~hit_empty
            coll = jnp.any(
                (out2 == cur[:, None])
                & (jnp.arange(out2.shape[1])[None, :] < cnt[:, None]),
                axis=1,
            )
            rej = self._is_out(self._w, cur, x) | hit_empty
            ok = live & ~bad & ~coll & ~rej
            leaf = jnp.where(ok, cur, leaf)
            done = done | ok | (live & bad)  # bad -> inner skip_rep
            lftotal = jnp.where(live & ~ok & ~bad, lftotal + 1, lftotal)
            return leaf, done, lftotal

        leaf, done, _ = jax.lax.while_loop(cond, body, (leaf, done, lftotal))
        ok = act & (already | (leaf != CRUSH_ITEM_NONE))
        return leaf, ok

    def _choose_firstn_vec(self, take, x, numrep, type_, tries, recurse_tries,
                           recurse_to_leaf, vary_r, stable, lane_mask):
        """crush_choose_firstn (mapper.c:443-631), zero local retries."""
        L = x.shape[0]
        out = jnp.full((L, numrep), CRUSH_ITEM_NONE, dtype=I32)
        out2 = jnp.full((L, numrep), CRUSH_ITEM_NONE, dtype=I32)
        cnt = jnp.zeros(L, dtype=I32)
        for rep in range(numrep):
            def cond(s):
                out, out2, cnt, ftotal, done = s
                return jnp.any(~done & (ftotal < tries))

            def body(s, rep=rep):
                out, out2, cnt, ftotal, done = s
                live = ~done & (ftotal < tries)
                r = rep + ftotal
                # choose_args position = the slot being filled (outpos)
                cur, hit_empty = self._descend(take, x, r, type_, cnt)
                bad = live & self._bad_item(cur, type_) & ~hit_empty
                coll = jnp.any(
                    (out == cur[:, None])
                    & (jnp.arange(numrep)[None, :] < cnt[:, None]),
                    axis=1,
                )
                reject = hit_empty
                leaf = cur
                if recurse_to_leaf:
                    sub_r = (r >> (vary_r - 1)) if vary_r else jnp.zeros_like(r)
                    inner_rep = jnp.zeros_like(cnt) if stable else cnt
                    leaf, leaf_ok = self._leaf_firstn(
                        cur, x, inner_rep, sub_r, recurse_tries, out2, cnt,
                        live & ~bad & ~coll & (cur < 0))
                    leaf = jnp.where(cur >= 0, cur, leaf)
                    reject = reject | ((cur < 0) & ~leaf_ok)
                if type_ == 0:
                    reject = reject | self._is_out(self._w, cur, x)
                success = live & ~bad & ~coll & ~reject
                slot = jnp.arange(numrep)[None, :] == cnt[:, None]
                out = jnp.where(slot & success[:, None], cur[:, None], out)
                out2 = jnp.where(slot & success[:, None], leaf[:, None], out2)
                cnt = cnt + success.astype(I32)
                done = done | success | bad
                ftotal = jnp.where(live & ~success & ~bad, ftotal + 1, ftotal)
                return out, out2, cnt, ftotal, done

            ftotal = jnp.zeros(L, dtype=I32)
            done = ~lane_mask
            out, out2, cnt, _, _ = jax.lax.while_loop(
                cond, body, (out, out2, cnt, ftotal, done))
        return (out2 if recurse_to_leaf else out), cnt

    # --------------------------------------------------------------- indep

    def _leaf_indep(self, host, x, rep, numrep, parent_r, tries, act):
        """Recursive chooseleaf for indep (mapper.c:767-786)."""
        already = host >= 0
        leaf = jnp.where(already & act, host, CRUSH_ITEM_UNDEF)
        done = ~act | already

        def cond(s):
            leaf, done, ftotal = s
            return jnp.any(~done & (ftotal < tries))

        def body(s):
            leaf, done, ftotal = s
            live = ~done & (ftotal < tries)
            r = rep + parent_r + numrep * ftotal
            # scalar's indep leaf recursion passes its slot as outpos
            cur, hit_empty = self._descend(
                host, x, r, 0, jnp.full_like(host, rep))
            bad = self._bad_item(cur, 0)
            rej = self._is_out(self._w, cur, x) | hit_empty
            ok = live & ~bad & ~rej
            leaf = jnp.where(ok, cur, leaf)
            leaf = jnp.where(live & bad, CRUSH_ITEM_NONE, leaf)
            done = done | ok | (live & bad)
            ftotal = ftotal + live.astype(I32)
            return leaf, done, ftotal

        leaf, _, _ = jax.lax.while_loop(
            cond, body, (leaf, done, jnp.zeros_like(x, dtype=I32)))
        leaf = jnp.where(leaf == CRUSH_ITEM_UNDEF, CRUSH_ITEM_NONE, leaf)
        return leaf

    def _choose_indep_vec(self, take, x, out_size, numrep, type_, tries,
                          recurse_tries, recurse_to_leaf, lane_mask):
        """crush_choose_indep (mapper.c:638-826), parent_r = 0."""
        L = x.shape[0]
        out = jnp.where(lane_mask[:, None],
                        jnp.full((L, out_size), CRUSH_ITEM_UNDEF, dtype=I32),
                        jnp.full((L, out_size), CRUSH_ITEM_NONE, dtype=I32))
        out2 = out

        def cond(s):
            out, out2, ftotal = s
            return jnp.any((out == CRUSH_ITEM_UNDEF) & (ftotal[:, None] < tries))

        def body(s):
            out, out2, ftotal = s
            lane_live = jnp.any(out == CRUSH_ITEM_UNDEF, axis=1) & (ftotal < tries)
            for rep in range(out_size):
                act = lane_live & (out[:, rep] == CRUSH_ITEM_UNDEF)
                r = rep + numrep * ftotal
                cur, hit_empty = self._descend(take, x, r, type_)
                bad = act & self._bad_item(cur, type_) & ~hit_empty
                coll = jnp.any(out == cur[:, None], axis=1)
                leaf = cur
                leaf_fail = jnp.zeros_like(bad)
                if recurse_to_leaf:
                    leaf = self._leaf_indep(
                        cur, x, rep, numrep, r, recurse_tries,
                        act & ~bad & ~coll & (cur < 0))
                    leaf = jnp.where(cur >= 0, cur, leaf)
                    leaf_fail = (cur < 0) & (leaf == CRUSH_ITEM_NONE)
                rej = jnp.zeros_like(bad)
                if type_ == 0:
                    rej = self._is_out(self._w, cur, x)
                success = act & ~bad & ~coll & ~leaf_fail & ~rej & ~hit_empty
                col = jnp.arange(out_size)[None, :] == rep
                out = jnp.where(col & success[:, None], cur[:, None], out)
                out = jnp.where(col & bad[:, None], CRUSH_ITEM_NONE, out)
                out2 = jnp.where(col & success[:, None], leaf[:, None], out2)
                out2 = jnp.where(col & bad[:, None], CRUSH_ITEM_NONE, out2)
            ftotal = ftotal + lane_live.astype(I32)
            return out, out2, ftotal

        out, out2, _ = jax.lax.while_loop(
            cond, body, (out, out2, jnp.zeros(L, dtype=I32)))
        out = jnp.where(out == CRUSH_ITEM_UNDEF, CRUSH_ITEM_NONE, out)
        out2 = jnp.where(out2 == CRUSH_ITEM_UNDEF, CRUSH_ITEM_NONE, out2)
        return (out2 if recurse_to_leaf else out)

    # ------------------------------------------------------------- rule VM

    # Device-resident map tensors the rule functions need.  They are
    # threaded through jit as ARGUMENTS (run(..., tensors)) with the traced
    # values temporarily bound onto self during tracing — a jit must not
    # close over a device array (JAX would bake the map into the program
    # as a constant and pin its buffers); map tensors stay jit arguments.
    _TENSOR_ATTRS = ("items", "iweights", "sizes", "btypes", "recip_hi",
                     "recip_lo", "_rh", "_lh", "_ll", "_lnn",
                     "_p2flat", "_meta",
                     "_ca_ids", "_ca_w", "_ca_rh", "_ca_rl", "_ca_pmax")

    def _tensor_args(self):
        return {a: getattr(self, a) for a in self._TENSOR_ATTRS}

    def _build_rule_fn(self, ruleno: int, result_max: int,
                       ca_active: bool = False, ca_pdim: int = 1):
        m = self.map
        t = m.tunables
        rule = m.rules[ruleno]

        def run(xs, weights, tensors):
            saved = {a: getattr(self, a) for a in self._TENSOR_ATTRS}
            saved_ca = (self._ca_active, self._ca_pdim)
            for a, v in tensors.items():
                setattr(self, a, v)
            # static choose_args mode must bind at TRACE time (jit traces
            # lazily on first call, not at build)
            self._ca_active, self._ca_pdim = ca_active, ca_pdim
            try:
                return self._run_rule(xs, weights, rule, t, result_max)
            finally:
                self._ca_active, self._ca_pdim = saved_ca
                for a, v in saved.items():
                    setattr(self, a, v)

        return jax.jit(run)

    def _run_rule(self, xs, weights, rule, t, result_max: int):
        self._w = weights
        L = xs.shape[0]
        choose_tries = t.choose_total_tries + 1
        choose_leaf_tries = 0
        vary_r = t.chooseleaf_vary_r
        stable = t.chooseleaf_stable
        w_items = jnp.full((L, result_max), CRUSH_ITEM_NONE, dtype=I32)
        wsize = jnp.zeros(L, dtype=I32)
        result = jnp.full((L, result_max), CRUSH_ITEM_NONE, dtype=I32)
        rlen = jnp.zeros(L, dtype=I32)
        for op, arg1, arg2 in rule.steps:
            if op == RULE_TAKE:
                w_items = w_items.at[:, 0].set(arg1)
                wsize = jnp.full(L, 1, dtype=I32)
            elif op == RULE_SET_CHOOSE_TRIES:
                if arg1 > 0:
                    choose_tries = arg1
            elif op == RULE_SET_CHOOSELEAF_TRIES:
                if arg1 > 0:
                    choose_leaf_tries = arg1
            elif op == RULE_SET_CHOOSELEAF_VARY_R:
                if arg1 >= 0:
                    vary_r = arg1
            elif op == RULE_SET_CHOOSELEAF_STABLE:
                if arg1 >= 0:
                    stable = arg1
            elif op in (RULE_SET_CHOOSE_LOCAL_TRIES,
                        RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES):
                if arg1 > 0:
                    raise NotImplementedError("local retries not vectorized")
            elif op in (RULE_CHOOSE_FIRSTN, RULE_CHOOSELEAF_FIRSTN,
                        RULE_CHOOSE_INDEP, RULE_CHOOSELEAF_INDEP):
                firstn = op in (RULE_CHOOSE_FIRSTN, RULE_CHOOSELEAF_FIRSTN)
                recurse = op in (RULE_CHOOSELEAF_FIRSTN, RULE_CHOOSELEAF_INDEP)
                numrep = arg1
                if numrep <= 0:
                    numrep += result_max
                    if numrep <= 0:
                        continue
                o_items = jnp.full((L, result_max), CRUSH_ITEM_NONE, dtype=I32)
                osize = jnp.zeros(L, dtype=I32)
                # Each W entry gets an independent output segment
                # (reference passes o+osize per input bucket).
                for i in range(result_max):
                    mask = (i < wsize) & (w_items[:, i] < 0)
                    take = w_items[:, i]
                    if firstn:
                        if choose_leaf_tries:
                            recurse_tries = choose_leaf_tries
                        elif t.chooseleaf_descend_once:
                            recurse_tries = 1
                        else:
                            recurse_tries = choose_tries
                        vals, cnt = self._choose_firstn_vec(
                            take, xs, numrep, arg2, choose_tries,
                            recurse_tries, recurse, vary_r, stable, mask)
                        ncols = numrep
                        cnt = jnp.where(mask, cnt, 0)
                    else:
                        # out_size depends on osize only when segments
                        # overflow result_max; clamp below on append
                        vals = self._choose_indep_vec(
                            take, xs, numrep, numrep, arg2, choose_tries,
                            choose_leaf_tries if choose_leaf_tries else 1,
                            recurse, mask)
                        ncols = numrep
                        cnt = jnp.where(mask, numrep, 0)
                    for j in range(ncols):
                        valid = (j < cnt) & (osize < result_max)
                        slot = jnp.arange(result_max)[None, :] == osize[:, None]
                        o_items = jnp.where(
                            slot & valid[:, None], vals[:, j][:, None], o_items)
                        osize = osize + valid.astype(I32)
                w_items = o_items
                wsize = osize
            elif op == RULE_EMIT:
                for j in range(result_max):
                    valid = (j < wsize) & (rlen < result_max)
                    slot = jnp.arange(result_max)[None, :] == rlen[:, None]
                    result = jnp.where(
                        slot & valid[:, None], w_items[:, j][:, None], result)
                    rlen = rlen + valid.astype(I32)
                wsize = jnp.zeros(L, dtype=I32)
            else:
                raise NotImplementedError(f"rule op {op}")
        return result, rlen
    def compiled_rule(self, ruleno: int, result_max: int,
                      choose_args=None):
        """Public seam for external dispatch harnesses (e.g. the mesh
        shard-out in parallel/engine.py): the cached compiled rule fn
        ``(xs, weights, tensors) -> (result, lens)`` plus the map tensor
        args, sharing this mapper's compile cache.  ``choose_args``: a
        name registered in map.choose_args or a {bucket_id: ChooseArg}
        dict — compiles a variant whose straw2 draws use the override
        weights/ids (mapper.c:302-320)."""
        if choose_args is None:
            key = (ruleno, result_max)
            if key not in self._compiled:
                self._compiled[key] = self._build_rule_fn(
                    ruleno, result_max)
            return self._compiled[key], self._tensor_args()
        ca_key, ca_tensors, P = self._resolve_choose_args(choose_args)
        # the compiled fn depends only on (rule, result_max, P) — the
        # override tensors are runtime args — so a balancer loop with
        # fresh weights each iteration reuses one compilation
        key = (ruleno, result_max, "ca", P)
        if key not in self._compiled:
            self._compiled[key] = self._build_rule_fn(
                ruleno, result_max, ca_active=True, ca_pdim=P)
        # tensor-args snapshot with the override tensors swapped in
        saved = {a: getattr(self, a) for a in ca_tensors}
        for a, v in ca_tensors.items():
            setattr(self, a, v)
        try:
            return self._compiled[key], self._tensor_args()
        finally:
            for a, v in saved.items():
                setattr(self, a, v)

    def do_rule_batch(self, ruleno: int, xs, result_max: int, weights,
                      choose_args=None):
        """Map a batch of x values; returns (N, result_max) int32 with
        CRUSH_ITEM_NONE padding, plus lengths, matching crush_do_rule."""
        from ceph_tpu.utils.perf import KERNELS

        fn, tensors = self.compiled_rule(ruleno, result_max, choose_args)
        xs = jnp.asarray(xs, dtype=U32)
        weights = jnp.asarray(weights, dtype=U32)
        n = xs.shape[0]
        KERNELS.inc("crush_map_calls")
        KERNELS.inc("crush_map_pgs", int(n))
        outs = []
        lens = []
        for start in range(0, n, self.chunk):
            part = xs[start : start + self.chunk]
            pad = 0
            if part.shape[0] < self.chunk and n > self.chunk:
                pad = self.chunk - part.shape[0]
                part = jnp.pad(part, (0, pad))
                # padded lanes run the full rule VM for discarded output
                KERNELS.inc("crush_map_pad_lanes", pad)
            res, rl = fn(part, weights, tensors)
            if pad:
                res = res[:-pad]
                rl = rl[:-pad]
            outs.append(res)
            lens.append(rl)
        if len(outs) == 1:
            return outs[0], lens[0]
        return jnp.concatenate(outs), jnp.concatenate(lens)
