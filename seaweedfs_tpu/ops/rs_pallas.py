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

import bisect
import functools

import numpy as np

from . import device, gf256

TILE = 8192
# The widths, in tiles, that host bytes of up to one small block (128 tiles =
# SMALL_BLOCK_SIZE) reach the kernel at: every tile multiple up to nine (a
# 64 KiB needle's record and below), then a step of a third or a half, so the
# zero tail stays under a third of what crosses the link. A degraded read of
# any length thus compiles at most these 17 programs per coefficient matrix.
LADDER_TILES = (1, 2, 3, 4, 5, 6, 7, 8, 9, 12, 16, 24, 32, 48, 64, 96, 128)


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


def ladder_width(n: int, tile: int) -> int:
    """The width host bytes of width n go to the kernel at: the next rung of
    `LADDER_TILES`, or beyond the ladder (rows of large blocks) the next
    multiple of `tile`."""
    tiles = -(-n // tile)
    if tiles > LADDER_TILES[-1]:
        return tiles * tile
    return LADDER_TILES[bisect.bisect_left(LADDER_TILES, tiles)] * tile


def zero_tailed(rows, tile: int) -> np.ndarray:
    """`rows` — a (cols, n) array or a sequence of cols (n,) arrays — as one
    C-contiguous (cols, `ladder_width(n, tile)`) uint8 host array, zero
    beyond column n: one copy per row, as `np.stack` makes, and none where
    `rows` is such an array already. The one place that decides at which
    width host bytes reach the kernel, in either of its forms.

    The copies go through a memoryview and so keep the interpreter lock:
    numpy gives it up around each copy of more than 500 bytes, and under
    sixteen reader threads getting it back ten times a read costs several
    times the copies themselves (PERF.md, PR 27)."""
    n = len(rows[0])
    width = ladder_width(n, tile)
    if isinstance(rows, np.ndarray) and width == n:
        return np.ascontiguousarray(rows, dtype=np.uint8)
    buf = bytearray(len(rows) * width)  # zeroed
    flat = memoryview(buf)
    for i, row in enumerate(rows):
        flat[i * width : i * width + n] = row
    return np.frombuffer(buf, dtype=np.uint8).reshape(len(rows), width)


def enqueue(matrix: np.ndarray, shards, tile: int):
    """`gf_matmul_pallas`, and beside its result the number of device
    programs the call enqueued: 1 for the kernel, 1 more for a pad on the
    device, 1 more for a slice on the device."""
    jnp = device.jax().numpy

    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    rows, cols = matrix.shape
    matrix_bytes = matrix.tobytes()
    at = _plane_major_bits(matrix_bytes, rows, cols)
    fn = _compiled(rows, cols, at, tile)
    n = shards.shape[1]
    # the zero tail is written where the bytes are. A host array goes to the
    # jitted program at a rung of the ladder, and the program does its own
    # transfer: no put, and at a rung's width no pad and no slice either
    on_host = isinstance(shards, np.ndarray)
    if on_host:
        shards = zero_tailed(shards, tile)
    else:
        shards = jnp.asarray(shards, dtype=jnp.uint8)
        # no named scope around the pad and the slice: two scopes cost a
        # read a percent (PERF.md, PR 26); the trace knows the two programs
        # as `jit__pad` and `jit_dynamic_slice`
        if n % tile:
            shards = jnp.pad(shards, ((0, 0), (0, (-n) % tile)))
    device.note_kernel_shape(matrix_bytes, rows, cols, shards.shape[1])
    out = fn(shards)
    if shards.shape[1] == n:
        return out, 1
    return out[:, :n], 2 if on_host else 3


def gf_matmul_pallas(matrix: np.ndarray, shards, tile: int = TILE):
    """out[r] = XOR_c matrix[r,c] x shards[c] — fused TPU kernel.

    matrix: (rows, cols) uint8 host array; shards: (cols, n) uint8, on the
    device or on the host, any n. The kernel sees tile multiples only, and of
    host bytes up to one small block only the rungs of `LADDER_TILES`: where
    n is not such a width, a zero tail is written on the side of the transfer
    where the bytes are (zero bytes transform to zero bytes, so the result is
    exact) and taken off again by a slice on the device. A host array at a
    rung's width is one device program and no put of its own: that is what
    the codec's door hands in (`rs_kernel._apply_jax`, which takes the tail
    off on the host). Returns device (rows, n).
    """
    return enqueue(matrix, shards, tile)[0]
