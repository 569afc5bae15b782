"""Sharded erasure-coding steps over a jax.sharding.Mesh.

The multi-chip execution model for the framework's data plane: stripes are
sharded over the ``data`` axis, EC chunk shards over the ``shard`` axis
(mirroring how the reference spreads EC shards across OSDs,
src/osd/ECBackend.cc handle_sub_write/handle_sub_read), and XLA inserts the
ICI collectives — the all-gather of k survivor shards on decode is the moral
equivalent of ECBackend's MOSDECSubOpRead fan-out/gather (reference
ECBackend.cc:986,1141).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ceph_tpu.ops import gf8


def make_mesh(n_devices: int | None = None, shard_axis: int | None = None) -> Mesh:
    """Build a ('data', 'shard') mesh over the first n devices."""
    devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if len(devices) < n_devices:
        raise ValueError(
            f"need {n_devices} devices, have {len(devices)}"
        )
    devices = np.asarray(devices[:n_devices])
    if shard_axis is None:
        shard_axis = 4 if n_devices % 4 == 0 else (2 if n_devices % 2 == 0 else 1)
    data_axis = n_devices // shard_axis
    return Mesh(devices.reshape(data_axis, shard_axis), axis_names=("data", "shard"))


def distributed_ec_step(mesh: Mesh, k: int, m: int, batch: int, chunk: int):
    """Build a jitted full EC pipeline step over ``mesh``.

    The step is the storage analog of a training step: encode a stripe batch,
    lay chunks out over the shard axis, lose a shard, reconstruct it from k
    survivors, and verify — returning the global mismatch count (a psum-like
    reduction XLA derives from the sharded comparison).

    Shapes must divide the mesh: batch % data_axis == 0 and
    (k + m) % shard_axis == 0.
    """
    n = k + m
    assert batch % mesh.shape["data"] == 0, "batch must divide data axis"
    assert n % mesh.shape["shard"] == 0, "k+m must divide shard axis"

    from ceph_tpu.ec import matrices

    # Keep the matrices as host numpy: they become jit-time constants on the
    # mesh's backend.  jnp.asarray here would commit them to the *default*
    # backend, which may be a different platform than the mesh (the round-1
    # multichip dryrun crashed exactly this way: CPU mesh, TPU default).
    coding = matrices.isa_rs_matrix(k, m)
    enc_bitmat = gf8.expand_bitmatrix(coding)
    generator = matrices.generator_matrix(coding)
    # static single-erasure recovery: lose shard 0, decode from rows 1..k
    src_rows = tuple(range(1, k + 1))
    sub = generator[list(src_rows)]
    inv = gf8.gf_invert_matrix(sub)
    rec_bitmat = gf8.expand_bitmatrix(inv[0][None, :])

    data_sharding = NamedSharding(mesh, P("data", None, None))
    chunk_sharding = NamedSharding(mesh, P("data", "shard", None))

    def step(data):
        # data: (batch, k, chunk) uint8, sharded over the stripe batch
        b = data.shape[0]
        # enc_bitmat/rec_bitmat stay host numpy: they lift into the jaxpr
        # as constants; jnp.asarray here would eagerly commit them to the
        # default backend mid-trace (see MeshECEngine._put).
        cols = data.transpose(1, 0, 2).reshape(k, b * chunk)
        parity = gf8.bitmatrix_matmul(enc_bitmat, cols)
        parity = parity.reshape(m, b, chunk).transpose(1, 0, 2)
        chunks = jnp.concatenate([data, parity], axis=1)
        # distribute shards over the shard axis (Ceph: shards to distinct OSDs)
        chunks = jax.lax.with_sharding_constraint(chunks, chunk_sharding)
        # reconstruct shard 0 from k survivors (XLA gathers across 'shard')
        survivors = chunks[:, 1 : k + 1, :]
        scols = survivors.transpose(1, 0, 2).reshape(k, b * chunk)
        recon = gf8.bitmatrix_matmul(rec_bitmat, scols).reshape(b, chunk)
        mismatches = jnp.sum((recon != chunks[:, 0, :]).astype(jnp.int32))
        return mismatches, chunks

    jitted = jax.jit(
        step,
        in_shardings=(data_sharding,),
        out_shardings=(NamedSharding(mesh, P()), chunk_sharding),
    )
    example = np.random.default_rng(0).integers(
        0, 256, (batch, k, chunk), dtype=np.uint8
    )
    # device_put with the mesh sharding: the example lands on the mesh's
    # devices directly and never touches the default backend.
    return jitted, (jax.device_put(example, data_sharding),)
