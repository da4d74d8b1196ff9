"""shard_map'd storage kernels: multi-volume EC encode + batch hashing.

Maps BASELINE.json config 5 ("multi-volume ec.encode, pmap across pod") onto
`jax.sharding` idioms: volume batches are sharded over the mesh's `dp` axis;
each chip encodes its volumes' RS parity / hashes its blobs independently
(no cross-chip data dependency — parity is per 10-block row), so the only
communication is the output layout XLA chooses.

Compiled callables are cached per (mesh, shape) — shard_map closures are
rebuilt per call otherwise, which would recompile every step.
"""

from __future__ import annotations

import functools

import numpy as np

from seaweedfs_tpu.ops import device, gf256
from seaweedfs_tpu.ops.crc32c_kernel import _block_matrix, _zero_crc
from seaweedfs_tpu.ops.rs_kernel import DATA_SHARDS, PARITY_SHARDS


def _bitplane_encode(jnp, jax, shards, a):
    """shards (10, n) uint8, a (80, 32) int8 -> parity (4, n) uint8.

    The XLA form of the single-chip transform (ops/rs_kernel.py), per volume.
    """
    n = shards.shape[1]
    k = jnp.arange(8, dtype=jnp.uint8)
    bits = ((shards.T[:, :, None] >> k) & jnp.uint8(1)).reshape(n, 80).astype(jnp.int8)
    y = jax.lax.dot_general(
        bits, a, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    ybits = (y & 1).astype(jnp.uint8).reshape(n, PARITY_SHARDS, 8)
    packed = jnp.sum(
        ybits.astype(jnp.int32) << jnp.arange(8, dtype=jnp.int32), axis=-1
    ).astype(jnp.uint8)
    return packed.T


@functools.lru_cache(maxsize=8)
def _parity_bit_matrix_bytes() -> bytes:
    return gf256.bit_matrix(gf256.parity_rows(DATA_SHARDS, PARITY_SHARDS)).tobytes()


@functools.lru_cache(maxsize=64)
def _encode_fn(mesh, n_volumes: int, n: int):
    jax = device.jax()
    jnp = jax.numpy
    from jax.sharding import PartitionSpec as P

    a = jnp.asarray(
        np.frombuffer(_parity_bit_matrix_bytes(), dtype=np.uint8).reshape(80, 32),
        dtype=jnp.int8,
    )

    def per_chip(vols):  # (V/d, 10, n)
        return jax.vmap(lambda s: _bitplane_encode(jnp, jax, s, a))(vols)

    return jax.jit(
        jax.shard_map(
            per_chip, mesh=mesh, in_specs=P("dp", None, None),
            out_specs=P("dp", None, None),
        )
    )


def sharded_encode(mesh, volumes):
    """volumes: (V, 10, n) uint8, V divisible by mesh size. Returns
    (V, 4, n) parity, computed with each chip owning V/num_devices volumes."""
    jax = device.jax()
    jnp = jax.numpy
    from jax.sharding import NamedSharding, PartitionSpec as P

    volumes = jnp.asarray(volumes, dtype=jnp.uint8)
    fn = _encode_fn(mesh, volumes.shape[0], volumes.shape[2])
    volumes = jax.device_put(volumes, NamedSharding(mesh, P("dp", None, None)))
    return fn(volumes)


@functools.lru_cache(maxsize=64)
def _crc_fn(mesh, length: int):
    jax = device.jax()
    jnp = jax.numpy
    from jax.sharding import PartitionSpec as P

    from seaweedfs_tpu.ops.crc32c_kernel import _compiled_batch

    inner = _compiled_batch(length)
    return jax.jit(
        jax.shard_map(lambda b: inner(b), mesh=mesh, in_specs=P("dp", None),
                  out_specs=P("dp"))
    )


def sharded_crc32c(mesh, blocks):
    """blocks: (N, L) uint8, N divisible by mesh size -> (N,) uint32."""
    jax = device.jax()
    jnp = jax.numpy
    from jax.sharding import NamedSharding, PartitionSpec as P

    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    fn = _crc_fn(mesh, blocks.shape[1])
    blocks = jax.device_put(blocks, NamedSharding(mesh, P("dp", None)))
    return fn(blocks)


@functools.lru_cache(maxsize=64)
def _md5_fn(mesh, length: int):
    jax = device.jax()
    from jax.sharding import PartitionSpec as P

    from seaweedfs_tpu.ops.md5_kernel import _compiled_batch

    inner = _compiled_batch(length)
    return jax.jit(
        jax.shard_map(lambda b: inner(b), mesh=mesh, in_specs=P("dp", None),
                  out_specs=P("dp", None))
    )


def sharded_md5(mesh, blobs):
    """blobs: (N, L) uint8, N divisible by mesh size -> (N, 16) uint8."""
    jax = device.jax()
    jnp = jax.numpy
    from jax.sharding import NamedSharding, PartitionSpec as P

    blobs = jnp.asarray(blobs, dtype=jnp.uint8)
    fn = _md5_fn(mesh, blobs.shape[1])
    blobs = jax.device_put(blobs, NamedSharding(mesh, P("dp", None)))
    return fn(blobs)


def pipeline_step(mesh, volumes, blobs):
    """One full data-plane step over the mesh: encode a sharded volume batch
    AND hash a sharded blob batch (CRC32C + MD5) — the storage framework's
    'training step' analog (tests/test_parallel.py)."""
    parity = sharded_encode(mesh, volumes)
    crcs = sharded_crc32c(mesh, blobs)
    digests = sharded_md5(mesh, blobs)
    return parity, crcs, digests
