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
sub-matrix rows for reconstruct/decode). The body only: at which width
bytes reach it, on which side of the transfer a zero tail is written and
when this form runs at all are the door's business (`ops/rs_kernel.py`).
"""

from __future__ import annotations

import functools

import numpy as np

from . import device, gf256


def _plane_major_bits(matrix_bytes: bytes, rows: int, cols: int) -> np.ndarray:
    """(8*rows, 8*cols) int8: AT[o, k*cols + c] with o = output bit index."""
    m = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(rows, cols)
    a = gf256.bit_matrix(m)  # (cols*8, rows*8), rows ordered (c, k)
    a2 = np.zeros_like(a)
    for c in range(cols):
        for k in range(8):
            a2[k * cols + c] = a[c * 8 + k]
    return np.ascontiguousarray(a2.T.astype(np.int8))  # (rows*8, cols*8)


@functools.lru_cache(maxsize=64)
def compiled(rows: int, cols: int, matrix_bytes: bytes, tile: int):
    """The jitted kernel of one (rows, cols) coefficient matrix:
    fn((cols, n) uint8) -> (rows, n) uint8, for n a multiple of `tile`."""
    jax = device.jax()
    jnp = jax.numpy
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    at_np = _plane_major_bits(matrix_bytes, rows, cols)

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
