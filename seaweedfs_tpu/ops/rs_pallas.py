"""Fused Pallas TPU kernel for GF(2^8) shard transforms.

One grid step processes a (cols, TILE) byte block entirely in VMEM, read as
(cols, TILE/4) little-endian 32-bit words: byte p of every word is a
problem of its own, (cols, TILE/4) bytes, since the transform works column
by column. For each p: unpack to bit planes (VPU) -> (8*rows, 8*cols) x
(8*cols, TILE/4) int8 matmul (MXU) -> mod-2 + byte pack (VPU); the four
packed results are ORed back into words, stored as (rows, TILE/512, 128)
— a dense 32-bit block whose row-major bytes are the (rows, TILE) result.
The 8x bit expansion never touches HBM — that's the difference from the
pure-jnp path in rs_kernel, where XLA materializes the bits tensor — and
the result takes on the device just its own bytes, which the host sees as
(rows, n) uint8 through a view.

Bit-matrix row order here is (k, c) — plane-major — because the kernel
builds the bit tensor by concatenating whole shifted planes along the
sublane axis (cheap block moves); gf256.bit_matrix's (c, k) order is
permuted accordingly on the host.

Works for any coefficient matrix (parity rows for encode, inverted
sub-matrix rows for reconstruct/decode), which the program takes as an
operand: one program per (rows, cols) and width serves every matrix. The
body only: at which width bytes reach it, where the matrix's device copy
comes from, on which side of the transfer a zero tail is written and when
this form runs at all are the door's business (`ops/rs_kernel.py`).
"""

from __future__ import annotations

import functools

import numpy as np

from . import device, gf256


def plane_major_bits(m: np.ndarray) -> np.ndarray:
    """The operand of a (rows, cols) uint8 coefficient matrix: (8*rows,
    8*cols) int8, AT[o, k*cols + c] with o = output bit index."""
    rows, cols = m.shape
    a = gf256.bit_matrix(m)  # (cols*8, rows*8), rows ordered (c, k)
    a2 = np.zeros_like(a)
    for c in range(cols):
        for k in range(8):
            a2[k * cols + c] = a[c * 8 + k]
    return np.ascontiguousarray(a2.T.astype(np.int8))  # (rows*8, cols*8)


@functools.lru_cache(maxsize=64)
def compiled(rows: int, cols: int, tile: int):
    """The jitted kernel of every (rows, cols) coefficient matrix:
    fn(at, (cols, n/4) uint32) -> (rows, n/512, 128) uint32, `at` the
    matrix's `plane_major_bits` and the words the bytes of (cols, n) and
    (rows, n) uint8 as little-endian words, for n a multiple of `tile`."""
    jax = device.jax()
    jnp = jax.numpy
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    words = tile // 4  # a block's words a row

    def kernel(at_ref, x_ref, o_ref):
        x = x_ref[:].astype(jnp.int32)  # (cols, words)
        packed = None
        for p in range(4):  # byte p of each word
            xp = (x >> (8 * p)) & 0xFF
            planes = [((xp >> k) & 1) for k in range(8)]
            bits = jnp.concatenate(planes, axis=0).astype(jnp.int8)  # (8*cols, words)
            y = jax.lax.dot_general(
                at_ref[:],
                bits,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )  # (8*rows, words)
            yb = y & 1
            out_rows = []
            for r in range(rows):
                acc = yb[r * 8]
                for j in range(1, 8):
                    acc = acc | (yb[r * 8 + j] << j)
                out_rows.append(acc.reshape(1, -1))
            lane = jnp.concatenate(out_rows, axis=0) << (8 * p)
            packed = lane if packed is None else packed | lane
        o_ref[:] = packed.reshape(rows, words // 128, 128).astype(jnp.uint32)

    # one name for the jitted program and for the kernel inside it, so the
    # device trace calls them the same after any refactor
    @jax.jit
    def rs_gf_matmul(at, x):  # (8*rows, 8*cols) int8; (cols, n/4), n % tile == 0
        w = x.shape[1]
        return pl.pallas_call(
            kernel,
            name="rs_gf_matmul",
            out_shape=jax.ShapeDtypeStruct((rows, w // 128, 128), jnp.uint32),
            grid=(w // words,),
            in_specs=[
                pl.BlockSpec(
                    (rows * 8, cols * 8), lambda i: (0, 0), memory_space=pltpu.VMEM
                ),
                pl.BlockSpec((cols, words), lambda i: (0, i), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (rows, words // 128, 128), lambda i: (0, i, 0),
                memory_space=pltpu.VMEM,
            ),
        )(at, x)

    return rs_gf_matmul
