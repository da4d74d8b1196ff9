"""Content-defined chunking (CDC): TPU-parallel gear rolling hash.

New capability vs the reference (BASELINE.md config 4 — the reference has no
dedup). Classic gear-CDC scans bytes serially; this variant is designed for
data-parallel hardware: the XOR-gear window hash

    h_i = XOR_{k=0}^{W-1} ( G[b_{i-k}] << k )      (W = 32, uint32)

depends only on a bounded window, so every position's hash is computable
independently — on TPU it's a 256-entry table gather plus 32 shifted XORs
over the whole buffer at once, instead of a byte-serial loop. Boundaries are
where (h & mask) == 0; min/max chunk bounds are enforced in a cheap host pass
over the (sparse) candidate set.
"""

from __future__ import annotations

import functools

import numpy as np

from . import device

WINDOW = 32

# deterministic gear table (fixed seed so fingerprints are stable across runs)
_GEAR = np.random.RandomState(0x5EAEED).randint(0, 1 << 32, size=256).astype(np.uint32)


def gear_hashes_numpy(data: np.ndarray) -> np.ndarray:
    """(n,) uint32 — h_i for every position i (positions < WINDOW-1 use the
    partial prefix window). Reference implementation for the TPU path."""
    g = _GEAR[data]
    acc = np.zeros(len(data), dtype=np.uint32)
    for k in range(WINDOW):
        shifted = np.zeros_like(acc)
        if k == 0:
            shifted = g
        else:
            shifted[k:] = g[:-k]
        acc ^= shifted << np.uint32(k)
    return acc


def _bucket(n: int) -> int:
    """Round up to a 1MB multiple so streaming callers with ragged segment
    lengths reuse one compiled kernel instead of recompiling per length."""
    step = 1 << 20
    return max(step, ((n + step - 1) // step) * step)


@functools.lru_cache(maxsize=8)
def _compiled_hashes(n: int):
    jax = device.jax()
    jnp = jax.numpy

    gear = jnp.asarray(_GEAR)

    @jax.jit
    def hashes(data):  # (n,) uint8 -> (n,) uint32
        g = jnp.take(gear, data.astype(jnp.int32))
        acc = jnp.zeros(n, dtype=jnp.uint32)
        for k in range(WINDOW):
            if k == 0:
                shifted = g
            else:
                shifted = jnp.concatenate([jnp.zeros(k, dtype=jnp.uint32), g[:-k]])
            acc = acc ^ (shifted << jnp.uint32(k))
        return acc

    return hashes


def gear_hashes(data, backend: str = "jax") -> np.ndarray:
    data = np.ascontiguousarray(data, dtype=np.uint8)
    if backend == "jax":
        n = len(data)
        b = _bucket(n)
        padded = np.zeros(b, dtype=np.uint8)
        padded[:n] = data
        return np.asarray(_compiled_hashes(b)(padded))[:n]
    return gear_hashes_numpy(data)


def pick_backend() -> str:
    """Serving-path default: the C++ serial scan (~1.2 GB/s/core) unless
    overridden — the device kernel pays transfer costs that only win with a
    directly-attached chip and large batches."""
    import os

    env = os.environ.get("SEAWEEDFS_TPU_CDC_BACKEND", "")
    if env:
        return env
    try:
        from seaweedfs_tpu.native import lib

        if lib is not None:
            return "native"
    except Exception:
        pass
    return "numpy"


def find_boundaries(
    data,
    avg_bits: int = 13,
    min_size: int = 2048,
    max_size: int = 65536,
    backend: str = "jax",
) -> list[int]:
    """Cut positions (exclusive ends) for one buffer. avg_bits=13 targets ~8KB
    mean chunks. Always ends with len(data)."""
    data = np.ascontiguousarray(data, dtype=np.uint8)
    n = len(data)
    if n == 0:
        return []
    mask = np.uint32((1 << avg_bits) - 1)
    if backend == "native":
        from seaweedfs_tpu.native import lib

        if lib is not None:
            return [int(c) for c in lib.gear_boundaries(
                data, _GEAR, int(mask), min_size, max_size
            )]
        backend = "numpy"
    h = gear_hashes(data, backend=backend)
    candidates = np.nonzero((h & mask) == 0)[0]
    cuts: list[int] = []
    cur = 0
    ci = 0
    while cur < n:
        lo = cur + min_size
        hi = min(cur + max_size, n)
        ci = int(np.searchsorted(candidates, lo))
        if ci < len(candidates) and candidates[ci] < hi:
            cut = int(candidates[ci]) + 1  # boundary after position i
        else:
            cut = hi
        cuts.append(cut)
        cur = cut
    return cuts


def chunk_stream(
    read_fn,
    avg_bits: int = 13,
    min_size: int = 2048,
    max_size: int = 65536,
    segment: int = 8 * 1024 * 1024,
    backend: str = "jax",
):
    """Yield (offset, length) chunks from a streaming reader. The unchunked
    tail of each segment is carried into the next round (and the final,
    provisional cut of a non-EOF segment is re-chunked with more data), so
    boundaries are identical to chunking the whole stream at once."""
    buf = b""
    base = 0
    eof = False
    target = segment
    while not eof or buf:
        while not eof and len(buf) < target:
            piece = read_fn(target - len(buf))
            if not piece:
                eof = True
                break
            buf += piece
        if not buf:
            return
        data = np.frombuffer(buf, dtype=np.uint8)
        cuts = find_boundaries(
            data, avg_bits=avg_bits, min_size=min_size, max_size=max_size,
            backend=backend,
        )
        if not eof:
            cuts = cuts[:-1]  # last cut may move once more data arrives
            if not cuts:
                target += segment  # buffer too small for a final cut yet
                continue
        target = segment
        prev = 0
        for c in cuts:
            yield (base + prev, c - prev)
            prev = c
        base += prev
        buf = buf[prev:]
        if eof and not buf:
            return
