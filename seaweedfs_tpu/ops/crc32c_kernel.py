"""Batched CRC32C on TPU: the checksum as a GF(2) affine map.

CRC is linear over GF(2): for fixed block length L,
    crc(block) = pack32( bits(block) @ M  mod 2 ) ^ crc(zeros(L))
where M[(k*8+j), :] is the 32-bit state contribution of bit j of byte k —
derived from the byte-step transition matrix by repeated multiplication. So a
*batch* of N equal-size blocks (the reference's upload-path hashing of
millions of needles, `weed/storage/needle/crc.go:12`,
`filer_server_handlers_write_upload.go:48`) becomes one (N, L*8) x (L*8, 32)
int8 matmul on the MXU — no per-byte table lookups, no gathers.

Also provides crc32c_combine (matrix-power trick) for stitching streamed
chunk CRCs on the host.
"""

from __future__ import annotations

import functools

import numpy as np

from seaweedfs_tpu.storage import crc as crc_cpu

from . import device

# --- GF(2) 32-bit state algebra (host-side, numpy bool) ---------------------
_POLY = 0x82F63B78


def _u32_to_bits(v: int) -> np.ndarray:
    return np.array([(v >> i) & 1 for i in range(32)], dtype=np.uint8)


def _bits_to_u32(bits: np.ndarray) -> int:
    return int(sum(int(b) << i for i, b in enumerate(bits)))


@functools.lru_cache(maxsize=1)
def _byte_step_matrix() -> bytes:
    """A: state after processing one zero byte, as a (32, 32) GF(2) matrix
    acting on column bit-vectors (A[:, i] = step(e_i))."""
    a = np.zeros((32, 32), dtype=np.uint8)
    for i in range(32):
        r = 1 << i
        # one table-less byte step of the reflected CRC recurrence
        for _ in range(8):
            r = (r >> 1) ^ (_POLY if r & 1 else 0)
        a[:, i] = _u32_to_bits(r)
    return a.tobytes()


def _matmul2(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    return ((x.astype(np.uint32) @ y.astype(np.uint32)) & 1).astype(np.uint8)


@functools.lru_cache(maxsize=32)
def _block_matrix(length: int) -> bytes:
    """M: (length*8, 32) — bit i of byte k contributes A^(L-k) e_i."""
    a = np.frombuffer(_byte_step_matrix(), dtype=np.uint8).reshape(32, 32)
    m = np.zeros((length * 8, 32), dtype=np.uint8)
    # walk backwards: position L-1 uses A^1, L-2 uses A^2, ...
    power = a.copy()
    for k in range(length - 1, -1, -1):
        m[k * 8 : k * 8 + 8, :] = power[:, :8].T  # columns 0..7 = embedded byte bits
        if k > 0:
            power = _matmul2(a, power)
    return m.tobytes()


@functools.lru_cache(maxsize=32)
def _zero_crc(length: int) -> int:
    return crc_cpu.crc32c(b"\x00" * length)


# --- device batch kernel ----------------------------------------------------
@functools.lru_cache(maxsize=16)
def _compiled_batch(length: int):
    jax = device.jax()
    jnp = jax.numpy

    m = jnp.asarray(
        np.frombuffer(_block_matrix(length), dtype=np.uint8).reshape(length * 8, 32),
        dtype=jnp.int8,
    )
    c0 = _zero_crc(length)

    @jax.jit
    def batch_crc(blocks):  # (n, length) uint8 -> (n,) uint32
        n = blocks.shape[0]
        k = jnp.arange(8, dtype=jnp.uint8)
        bits = ((blocks[:, :, None] >> k) & jnp.uint8(1)).reshape(n, length * 8)
        y = jax.lax.dot_general(
            bits.astype(jnp.int8),
            m,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        ybits = (y & 1).astype(jnp.uint32)
        crc = jnp.sum(ybits << jnp.arange(32, dtype=jnp.uint32), axis=1)
        return crc ^ jnp.uint32(c0)

    return batch_crc


def crc32c_batch(blocks, backend: str = "jax") -> np.ndarray:
    """CRC32C of N equal-length blocks. blocks: (n, length) uint8 array.
    Returns (n,) uint32."""
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8)
    n, length = blocks.shape
    if backend == "jax":
        fn = _compiled_batch(length)
        return np.asarray(fn(blocks))
    # CPU reference path
    out = np.empty(n, dtype=np.uint32)
    for i in range(n):
        out[i] = crc_cpu.crc32c(blocks[i].tobytes())
    return out


# --- streaming combine (host) ----------------------------------------------
@functools.lru_cache(maxsize=64)
def _power_matrix(length: int) -> bytes:
    """A^length via square-and-multiply."""
    a = np.frombuffer(_byte_step_matrix(), dtype=np.uint8).reshape(32, 32)
    result = np.eye(32, dtype=np.uint8)
    base = a.copy()
    k = length
    while k:
        if k & 1:
            result = _matmul2(result, base)
        base = _matmul2(base, base)
        k >>= 1
    return result.tobytes()


def crc32c_combine(crc_a: int, crc_b: int, len_b: int) -> int:
    """crc(A||B) from crc(A), crc(B), len(B) — GF(2) matrix power.

    Derivation: R_{A||B} = A^Lb R_A ^ S_B and R_B = A^Lb init ^ S_B, so with
    crc = R ^ F and init == F the init/final xors cancel pairwise, leaving
    crc(A||B) = A^Lb * crc(A) ^ crc(B).
    """
    p = np.frombuffer(_power_matrix(len_b), dtype=np.uint8).reshape(32, 32)
    shifted = _bits_to_u32(_matmul2(p, _u32_to_bits(crc_a)))
    return shifted ^ crc_b
