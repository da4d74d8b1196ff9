"""Fused Pallas TPU kernel for GF(2^8) shard transforms.

One grid step processes a (cols, TILE) byte block entirely in VMEM:
unpack to bit planes (VPU) -> (8*rows, 8*cols)x(8*cols, TILE) int8 matmul
(MXU) -> mod-2 + byte pack (VPU) -> (rows, TILE) output. The 8x bit
expansion never touches HBM — that's the difference from the pure-jnp path
in rs_kernel, where XLA materializes the bits tensor.

Bit-matrix row order here is (k, c) — plane-major — because the kernel
builds the bit tensor by concatenating whole shifted planes along the
sublane axis (cheap block moves); gf256.bit_matrix's (c, k) order is
permuted accordingly on the host.

Works for any coefficient matrix (parity rows for encode, inverted
sub-matrix rows for reconstruct/decode). TPU-only: rs_kernel.gf_matmul_jax
is the one place that decides, by platform, between this kernel and the
XLA form.
"""

from __future__ import annotations

import functools

import numpy as np

from . import device, gf256

TILE = 8192


@functools.lru_cache(maxsize=64)
def _plane_major_bits(matrix_bytes: bytes, rows: int, cols: int) -> bytes:
    """(8*rows, 8*cols) int8: AT[o, k*cols + c] with o = output bit index."""
    m = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(rows, cols)
    a = gf256.bit_matrix(m)  # (cols*8, rows*8), rows ordered (c, k)
    a2 = np.zeros_like(a)
    for c in range(cols):
        for k in range(8):
            a2[k * cols + c] = a[c * 8 + k]
    return np.ascontiguousarray(a2.T.astype(np.int8)).tobytes()  # (rows*8, cols*8)


@functools.lru_cache(maxsize=64)
def _compiled(rows: int, cols: int, at_bytes: bytes, tile: int):
    jax = device.jax()
    jnp = jax.numpy
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    at_np = np.frombuffer(at_bytes, dtype=np.int8).reshape(rows * 8, cols * 8)

    def kernel(at_ref, x_ref, o_ref):
        x = x_ref[:].astype(jnp.int32)  # (cols, tile)
        planes = [((x >> k) & 1) for k in range(8)]
        bits = jnp.concatenate(planes, axis=0).astype(jnp.int8)  # (8*cols, tile)
        y = jax.lax.dot_general(
            at_ref[:],
            bits,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (8*rows, tile)
        yb = y & 1
        out_rows = []
        for r in range(rows):
            acc = yb[r * 8]
            for j in range(1, 8):
                acc = acc | (yb[r * 8 + j] << j)
            out_rows.append(acc.reshape(1, -1))
        o_ref[:] = jnp.concatenate(out_rows, axis=0).astype(jnp.uint8)

    # one name for the jitted program and for the kernel inside it, so the
    # device trace calls them the same after any refactor
    @jax.jit
    def rs_gf_matmul(x):  # (cols, n) with n % tile == 0
        n = x.shape[1]
        return pl.pallas_call(
            kernel,
            name="rs_gf_matmul",
            out_shape=jax.ShapeDtypeStruct((rows, n), jnp.uint8),
            grid=(n // tile,),
            in_specs=[
                pl.BlockSpec(
                    (rows * 8, cols * 8), lambda i: (0, 0), memory_space=pltpu.VMEM
                ),
                pl.BlockSpec((cols, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (rows, tile), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
        )(jnp.asarray(at_np), x)

    return rs_gf_matmul


def gf_matmul_pallas(matrix: np.ndarray, shards, tile: int = TILE):
    """out[r] = XOR_c matrix[r,c] x shards[c] — fused TPU kernel.

    matrix: (rows, cols) uint8 host array; shards: (cols, n) uint8 (device or
    host). n is padded to a tile multiple internally (zero bytes encode to
    zero parity, so the tail slice is exact). Returns device (rows, n).
    """
    jnp = device.jax().numpy

    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    rows, cols = matrix.shape
    at = _plane_major_bits(matrix.tobytes(), rows, cols)
    fn = _compiled(rows, cols, at, tile)
    shards = jnp.asarray(shards, dtype=jnp.uint8)
    n = shards.shape[1]
    pad = (-n) % tile
    # no named scope around the pad and the slice: this is the per-read
    # path, where two scopes cost the read cell a percent (PERF.md, PR 26);
    # the trace knows them as `jit__pad` and `jit_dynamic_slice`
    if pad:
        shards = jnp.pad(shards, ((0, 0), (0, pad)))
    out = fn(shards)
    return out[:, :n] if pad else out

