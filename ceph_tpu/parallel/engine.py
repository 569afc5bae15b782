"""MeshECEngine: the sharded EC data plane over a jax.sharding.Mesh.

Round-4 generalization of the original demo pipeline (mesh.py kept for
the end-to-end step): arbitrary erasure patterns, delta-based RMW, and
mesh-sharded CRUSH placement — the storage analogs of a model's
sharded forward/backward.  Stripes shard over the ``data`` axis (our
batch axis = independent stripes, the framework's long-context analog)
and EC chunk rows lay out over the ``shard`` axis the way the
reference spreads shards across OSDs (src/osd/ECBackend.cc
handle_sub_write/handle_sub_read:921,986); XLA inserts the ICI
collectives (the decode all-gather is MOSDECSubOpRead's fan-out).

The engine exposes the SAME encode_batch/decode_batch contract as the
single-device codec engines (ec/codec.py), so the cluster's EC backend
can route through it unchanged (osd_ec_mesh config)."""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ceph_tpu.ops import gf8


class MeshECEngine:
    """Sharded GF(2^8) RS engine with the codec batch contract.

    Works for any codec whose engine exposes a ``coding`` matrix over
    GF(2^8) (jerasure reed_sol, ISA) — the same families the cluster's
    EC pools default to."""

    def __init__(self, mesh: Mesh, k: int, m: int,
                 coding: np.ndarray, decode_rows=None):
        """``decode_rows(src, want)``: the codec engine's own recovery
        matrix, for a code whose decode is not the inversion of any k
        survivor rows (the adapter passes it, and the sources)."""
        if decode_rows is not None:
            self._decode_rows = decode_rows
        self.mesh = mesh
        self.k, self.m = k, m
        self.n = k + m
        # host-side numpy: jit-time constants on the MESH backend (a
        # device-committed constant would pin the default backend and
        # poison dispatch, see ops/gf8 notes + memory)
        self.coding = np.asarray(coding, dtype=np.uint8)
        from ceph_tpu.ec import matrices

        self.generator = matrices.generator_matrix(self.coding)
        self._enc_bitmat = gf8.expand_bitmatrix(self.coding)
        self._enc_jit: Dict[Tuple, object] = {}
        self._dec_jit: Dict[Tuple, object] = {}
        self._rmw_jit: Dict[Tuple, object] = {}
        self._data_sh = NamedSharding(mesh, P("data", None, None))
        self._chunk_sh = NamedSharding(mesh, P("data", "shard", None))
        self._repl = NamedSharding(mesh, P())

    @staticmethod
    def _put(x, sharding):
        """Place ``x`` on the mesh WITHOUT touching the default backend.

        jax.device_put takes host numpy directly; routing through
        jnp.asarray first would commit the array to the *default*
        device before the mesh placement — an extra transfer, and a
        cross-backend error when the mesh is not on the default backend
        (what turned the round-4 multichip dryrun red, MULTICHIP_r04)."""
        if not isinstance(x, jax.Array):
            x = np.asarray(x)
        return jax.device_put(x, sharding)

    # -- encode ------------------------------------------------------------

    def _build_encode(self):
        k, m = self.k, self.m
        enc = self._enc_bitmat

        def step(data):
            # ``enc`` stays host numpy: it lifts into the jaxpr as a
            # constant during tracing.  jnp.asarray here would eagerly
            # commit it to the DEFAULT backend mid-trace — a real-TPU
            # touch even when the mesh is the virtual CPU one.
            b, _, chunk = data.shape
            cols = data.transpose(1, 0, 2).reshape(k, b * chunk)
            parity = gf8.bitmatrix_matmul(enc, cols)
            return parity.reshape(m, b, chunk).transpose(1, 0, 2)

        return jax.jit(step, in_shardings=(self._data_sh,),
                       out_shardings=self._data_sh)

    def encode_batch(self, data):
        """(B, k, S) -> (B, m, S) parity, stripes sharded over 'data'."""
        if not self._enc_jit:
            self._enc_jit["fn"] = self._build_encode()
        data = self._put(data, self._data_sh)
        return self._enc_jit["fn"](data)

    # -- decode (arbitrary erasure pattern) --------------------------------

    def _decode_rows(self, src: Tuple[int, ...], want: Tuple[int, ...]):
        """GF coefficient rows mapping survivor rows ``src`` -> rows
        ``want`` (submatrix inversion, ec/codec.py decode_matrix)."""
        sub = self.generator[list(src)]
        inv = gf8.gf_invert_matrix(sub)
        rows = []
        for w in want:
            if w < self.k:
                rows.append(inv[w])
            else:
                # erased parity: compose its coding row with the inverse
                comp = np.zeros(self.k, dtype=np.uint8)
                for j in range(self.k):
                    c = int(self.coding[w - self.k, j])
                    if c:
                        comp ^= np.array(
                            [gf8.gf_mul(c, int(v)) for v in inv[j]],
                            dtype=np.uint8)
                rows.append(comp)
        return np.stack(rows)

    def _build_decode(self, src: Tuple[int, ...], want: Tuple[int, ...]):
        bitmat = gf8.expand_bitmatrix(self._decode_rows(src, want))
        src_arr = np.asarray(src)

        def step(chunks):
            b, _, chunk = chunks.shape
            survivors = chunks[:, src_arr, :]
            cols = survivors.transpose(1, 0, 2).reshape(len(src),
                                                        b * chunk)
            out = gf8.bitmatrix_matmul(bitmat, cols)
            return out.reshape(len(want), b, chunk).transpose(1, 0, 2)

        return jax.jit(step, in_shardings=(self._chunk_sh,),
                       out_shardings=self._data_sh)

    def decode_batch(self, erasures: Tuple[int, ...], chunks,
                     want: Tuple[int, ...] = None,
                     src: Tuple[int, ...] = None):
        """codec contract: chunks (B, k+m, S); rebuild ``want`` (default
        = erasures) from the survivors ``src`` (default: the first k).
        The survivor gather crosses the 'shard' mesh axis — the ICI
        analog of the sub-read fan-out."""
        erasures = tuple(erasures)
        if want is None:
            want = erasures
        want = tuple(want)
        if src is None:
            src = tuple(i for i in range(self.n)
                        if i not in erasures)[: self.k]
        key = (src, want)
        if key not in self._dec_jit:
            self._dec_jit[key] = self._build_decode(src, want)
        chunks = self._put(chunks, self._chunk_sh)
        return self._dec_jit[key](chunks)

    # -- RMW (delta parity update) -----------------------------------------

    def _build_rmw(self, col_start: int, width: int):
        k, m = self.k, self.m
        enc = self._enc_bitmat

        def step(chunks, update):
            # chunks: (B, k+m, S) current; update: (B, k, width) new data
            # columns [col_start, col_start+width).  Linear code =>
            # parity' = parity ^ encode(old_cols ^ new_cols): only the
            # touched columns move over the mesh, the RMW trick
            # ECBackend buys with sub-range reads (ECBackend.cc:1785)
            b = chunks.shape[0]
            old = jax.lax.dynamic_slice_in_dim(
                chunks[:, :k, :], col_start, width, axis=2)
            delta = old ^ update
            dcols = delta.transpose(1, 0, 2).reshape(k, b * width)
            pdelta = gf8.bitmatrix_matmul(enc, dcols)
            pdelta = pdelta.reshape(m, b, width).transpose(1, 0, 2)
            new_data = jax.lax.dynamic_update_slice_in_dim(
                chunks[:, :k, :], update, col_start, axis=2)
            old_parity = jax.lax.dynamic_slice_in_dim(
                chunks[:, k:, :], col_start, width, axis=2)
            new_parity = jax.lax.dynamic_update_slice_in_dim(
                chunks[:, k:, :], old_parity ^ pdelta, col_start, axis=2)
            return jnp.concatenate([new_data, new_parity], axis=1)

        return jax.jit(step, in_shardings=(self._chunk_sh, self._data_sh),
                       out_shardings=self._chunk_sh)

    def rmw_batch(self, chunks, update, col_start: int):
        """Partial-stripe overwrite: replace data columns
        [col_start, col_start+len) with ``update`` (B, k, width) and
        delta-update the parity in place."""
        if not isinstance(update, jax.Array):
            update = np.asarray(update)
        width = update.shape[2]
        key = (col_start, width)
        if key not in self._rmw_jit:
            self._rmw_jit[key] = self._build_rmw(col_start, width)
        chunks = self._put(chunks, self._chunk_sh)
        update = self._put(update, self._data_sh)
        return self._rmw_jit[key](chunks, update)


class MeshCodecAdapter:
    """Wraps a single-device EC codec so the cluster's EC pool batch
    paths (ec/stripe.py encode_stripes/decode_stripes) run on the mesh
    engine instead — the osd_ec_mesh seam.  Every other codec method
    (profiles, chunk math, scalar encode/decode) delegates unchanged.

    Arbitrary cluster batch sizes are padded up to the mesh's data axis
    (zero stripes encode to zero parity — the code is linear — so
    padding never changes real rows)."""

    def __init__(self, codec, mesh: Mesh):
        self._codec = codec
        k = codec.get_data_chunk_count()
        n = codec.get_chunk_count()
        self._k, self._n = k, n
        self._mesh_engine = MeshECEngine(
            mesh, k, n - k, np.asarray(codec.engine.coding),
            decode_rows=codec.engine.decode_matrix)
        self._data_axis = mesh.shape["data"]

    # the bit-planar entry points are single-device (the mesh engine
    # shards BYTE batches); hiding them steers ec/stripe.py's planar
    # routing back to encode_batch/decode_batch so mesh pools keep the
    # multi-chip data plane
    _SINGLE_DEVICE_ONLY = frozenset(
        {"planar_supported", "to_planar", "encode_planar", "decode_planar"})

    def __getattr__(self, name):
        if name in self._SINGLE_DEVICE_ONLY:
            raise AttributeError(name)
        return getattr(self._codec, name)

    def _pad(self, arr):
        b = arr.shape[0]
        pad = (-b) % self._data_axis
        if pad:
            arr = np.concatenate(
                [np.asarray(arr),
                 np.zeros((pad,) + arr.shape[1:], dtype=np.uint8)])
        return arr, b

    def encode_batch(self, data):
        data, b = self._pad(np.asarray(data))
        return self._mesh_engine.encode_batch(data)[:b]

    def decode_batch(self, erasures, chunks, want=None):
        from ceph_tpu.ec.stripe import _decode_src

        chunks, b = self._pad(np.asarray(chunks))
        want = tuple(erasures if want is None else want)
        src = _decode_src(self._codec, want, erasures)
        return self._mesh_engine.decode_batch(erasures, chunks, want,
                                              src=src)[:b]


def mesh_for_codec(codec, n_devices: int = 0) -> Mesh:
    """Mesh whose shard axis divides this codec's k+m (falling back to
    pure data parallelism when no shard split fits)."""
    n_dev = n_devices or len(jax.devices())
    n = codec.get_chunk_count()
    shard_axis = 1
    for s in (4, 3, 2):
        if n_dev % s == 0 and n % s == 0:
            shard_axis = s
            break
    from ceph_tpu.parallel.mesh import make_mesh

    return make_mesh(n_dev, shard_axis=shard_axis)


def wrap_codec_for_mesh(codec, n_devices: int = 0):
    """Return a mesh-routed adapter for codecs with a GF(2^8) coding
    matrix, or the codec unchanged when it cannot ride the mesh engine
    (wide-w / bitmatrix families keep their single-device path)."""
    from ceph_tpu.ec.codec import bytewise_engine

    if bytewise_engine(codec) is None:
        return codec
    return MeshCodecAdapter(codec, mesh_for_codec(codec, n_devices))


def crush_batch_sharded(mesh: Mesh, mapper, ruleno: int, xs, result_max: int,
                        weights):
    """Whole-map CRUSH placement sharded over every mesh device: the
    per-x rule VM is embarrassingly parallel, so sharding xs over the
    flattened mesh scales placement linearly with chips (reference
    crush_do_rule is a per-x scalar loop, src/crush/mapper.c:883)."""
    n_dev = mesh.devices.size
    xs = np.asarray(xs, dtype=np.uint32)
    pad = (-len(xs)) % n_dev
    if pad:
        xs = np.concatenate([xs, np.zeros(pad, dtype=np.uint32)])
    x_sh = NamedSharding(mesh, P(("data", "shard")))
    w_sh = NamedSharding(mesh, P())
    # cache the sharded wrapper + the mesh-replicated map tensors ON the
    # mapper (so the cache dies with the map epoch and an id() reuse can
    # never serve a stale map), keyed by rule/result/mesh — repeat
    # placement calls hit XLA's jit cache instead of retracing +
    # re-transferring the whole map
    cache = getattr(mapper, "_sharded_cache", None)
    if cache is None:
        cache = mapper._sharded_cache = {}
    key = (ruleno, result_max, mesh)
    if key not in cache:
        fn, tensors = mapper.compiled_rule(ruleno, result_max)
        # the mapper's map tensors live on the DEFAULT backend (mapper.py
        # builds them with jnp.asarray); replicate them onto the mesh so
        # the sharded dispatch never mixes backends
        tensors = jax.device_put(tensors, w_sh)
        sharded = jax.jit(
            lambda x, w, t: fn(x, w, t),
            in_shardings=(x_sh, w_sh, None),
            out_shardings=(NamedSharding(mesh, P(("data", "shard"), None)),
                           x_sh),
        )
        cache[key] = (sharded, tensors)
    sharded, tensors = cache[key]
    res, lens = sharded(jax.device_put(xs, x_sh),
                        jax.device_put(
                            np.asarray(weights, dtype=np.uint32), w_sh),
                        tensors)
    if pad:
        res, lens = res[:-pad], lens[:-pad]
    return res, lens
