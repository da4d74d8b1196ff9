"""Batched MD5 on TPU: N independent blobs hashed in lockstep on VPU lanes.

MD5 is strictly sequential per stream (64 rounds per 64-byte block), so the
TPU win is the *batch* dimension (SURVEY.md §2.2 item 3): the reference hashes
millions of independent chunks/needles (ETags,
`weed/server/filer_server_handlers_write_upload.go:48`); here all N states
advance together as (N,) uint32 vectors — every round is 8 VPU ops over the
whole batch. Equal-length blobs per call (pad/bucket at the caller).

Bit-identical to RFC 1321 (cross-checked against hashlib and the native C++
path in tests).
"""

from __future__ import annotations

import functools

import numpy as np

from . import device

_K = np.array(
    [int(abs(__import__("math").sin(i + 1)) * (1 << 32)) & 0xFFFFFFFF for i in range(64)],
    dtype=np.uint32,
)
_S = np.array(
    [7, 12, 17, 22] * 4 + [5, 9, 14, 20] * 4 + [4, 11, 16, 23] * 4 + [6, 10, 15, 21] * 4,
    dtype=np.int32,
)


def _pad_len(blob_len: int) -> int:
    """Total padded length: blob + 0x80 + zeros + 8-byte bit length."""
    return ((blob_len + 8) // 64 + 1) * 64


@functools.lru_cache(maxsize=16)
def _compiled_batch(blob_len: int):
    jax = device.jax()
    jnp = jax.numpy

    padded = _pad_len(blob_len)
    n_blocks = padded // 64

    def rotl(x, s):
        return (x << jnp.uint32(s)) | (x >> jnp.uint32(32 - s))

    @jax.jit
    def md5_batch(blobs):  # (n, blob_len) uint8 -> (n, 16) uint8 digests
        n = blobs.shape[0]
        # build padded message as little-endian uint32 words (n, n_blocks, 16)
        # length trailer computed host-side (blob_len is static) — avoids
        # uint64 truncation and out-of-range uint32 shifts on device
        pad_host = np.zeros(padded - blob_len, dtype=np.uint8)
        pad_host[0] = 0x80
        pad_host[-8:] = np.frombuffer(
            np.uint64(blob_len * 8).tobytes(), dtype=np.uint8
        )
        pad = jnp.broadcast_to(jnp.asarray(pad_host), (n, padded - blob_len))
        msg = jnp.concatenate([blobs, pad], axis=1)
        words = msg.reshape(n, n_blocks, 16, 4).astype(jnp.uint32)
        shifts = jnp.arange(4, dtype=jnp.uint32) * 8
        words = jnp.sum(words << shifts, axis=-1, dtype=jnp.uint32)  # (n, blocks, 16)

        # derive the initial state from the input (x*0 + const) so that under
        # shard_map the scan carry is device-varying like the scanned words
        zero = words[:, 0, 0] * jnp.uint32(0)
        a0 = zero + jnp.uint32(0x67452301)
        b0 = zero + jnp.uint32(0xEFCDAB89)
        c0 = zero + jnp.uint32(0x98BADCFE)
        d0 = zero + jnp.uint32(0x10325476)

        def block_step(state, m):  # m: (n, 16) uint32
            a, b, c, d = state
            aa, bb, cc, dd = a, b, c, d
            for i in range(64):
                if i < 16:
                    f = (bb & cc) | (~bb & dd)
                    g = i
                elif i < 32:
                    f = (dd & bb) | (~dd & cc)
                    g = (5 * i + 1) % 16
                elif i < 48:
                    f = bb ^ cc ^ dd
                    g = (3 * i + 5) % 16
                else:
                    f = cc ^ (bb | ~dd)
                    g = (7 * i) % 16
                tmp = dd
                dd = cc
                cc = bb
                bb = bb + rotl(aa + f + jnp.uint32(int(_K[i])) + m[:, g], int(_S[i]))
                aa = tmp
            return (a + aa, b + bb, c + cc, d + dd), None

        (a, b, c, d), _ = jax.lax.scan(
            block_step, (a0, b0, c0, d0), jnp.moveaxis(words, 1, 0)
        )
        state = jnp.stack([a, b, c, d], axis=1)  # (n, 4)
        out = (state[:, :, None] >> (jnp.arange(4, dtype=jnp.uint32) * 8)).astype(
            jnp.uint8
        )
        return out.reshape(n, 16)

    return md5_batch


def md5_batch(blobs, backend: str = "jax") -> np.ndarray:
    """MD5 digests of N equal-length blobs: (n, L) uint8 -> (n, 16) uint8."""
    blobs = np.ascontiguousarray(blobs, dtype=np.uint8)
    n, length = blobs.shape
    if backend == "jax":
        return np.asarray(_compiled_batch(length)(blobs))
    if backend == "native":
        from seaweedfs_tpu.native import lib

        out = lib.md5_batch(blobs.tobytes(), n, length)
        return np.frombuffer(out, dtype=np.uint8).reshape(n, 16)
    import hashlib

    return np.stack(
        [
            np.frombuffer(hashlib.md5(blobs[i].tobytes()).digest(), dtype=np.uint8)
            for i in range(n)
        ]
    )
