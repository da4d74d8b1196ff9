"""Plain reference for RS(10,4) sealing, repair and degraded reads.

Table-driven GF(2^8) arithmetic in numpy (polynomial 0x11D, generator 2) and
the systematic Vandermonde-derived 14x10 matrix that klauspost/reedsolomon
(upstream SeaweedFS's codec) builds: vm[r][c] = r**c, times the inverse of its
top 10x10 square. It also knows upstream's shard layout: 1 GiB large-block rows
while more than 10 GiB remain, then 1 MiB small-block rows, the last one
zero-padded. Imports nothing of the program and takes nothing it made except
the volume file whose shards are to be judged.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

DATA, PARITY, TOTAL = 10, 4, 14
LARGE_BLOCK = 1024 * 1024 * 1024
SMALL_BLOCK = 1024 * 1024


def _tables() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= 0x11D
    exp[255:510] = exp[:255]
    a = np.arange(256)
    mul = exp[(log[a][:, None] + log[a][None, :])].astype(np.uint8)
    mul[0, :] = 0
    mul[:, 0] = 0
    return exp, log, mul


EXP, LOG, MUL = _tables()


def gf_mul(a: int, b: int) -> int:
    return int(MUL[a, b])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(int(LOG[a]) * n) % 255])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - int(LOG[a])])


def mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in range(b.shape[1]):
            acc = 0
            for k in range(a.shape[1]):
                acc ^= gf_mul(int(a[i, k]), int(b[k, j]))
            out[i, j] = acc
    return out


def mat_inv(m: np.ndarray) -> np.ndarray:
    """Gauss-Jordan over GF(2^8); raises on a singular matrix."""
    n = m.shape[0]
    work = np.concatenate([m.astype(np.uint8), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = next((r for r in range(col, n) if work[r, col]), None)
        if pivot is None:
            raise ValueError("singular matrix")
        if pivot != col:
            work[[col, pivot]] = work[[pivot, col]]
        work[col] = MUL[gf_inv(int(work[col, col]))][work[col]]
        for r in range(n):
            if r != col and work[r, col]:
                work[r] ^= MUL[int(work[r, col])][work[col]]
    return work[:, n:]


def coding_matrix() -> np.ndarray:
    """(14, 10): identity on top, the four parity rows below."""
    vm = np.array(
        [[gf_pow(r, c) for c in range(DATA)] for r in range(TOTAL)], dtype=np.uint8
    )
    return mat_mul(vm, mat_inv(vm[:DATA]))


def cauchy_matrix() -> np.ndarray:
    """Another MDS code with the same shape: rows 10..13 are 1/(x_r ^ y_c).
    Any 10 of its 14 shards still rebuild the rest, but no shard file is
    byte-identical to upstream's: the control that breaks that guarantee."""
    m = np.eye(TOTAL, DATA, dtype=np.uint8)
    for r in range(DATA, TOTAL):
        for c in range(DATA):
            m[r, c] = gf_inv(r ^ c)
    return m


def apply_matrix(rows: np.ndarray, data: np.ndarray) -> np.ndarray:
    """out[j] = XOR_i rows[j, i] * data[i]; data (k, n) uint8."""
    out = np.zeros((rows.shape[0], data.shape[1]), dtype=np.uint8)
    for j in range(rows.shape[0]):
        acc = out[j]
        for i in range(rows.shape[1]):
            c = int(rows[j, i])
            if c:
                acc ^= np.take(MUL[c], data[i])
    return out


def packed_tables(rows: np.ndarray) -> np.ndarray:
    """(k, 256) uint32 for at most four rows: entry [i, b] holds the products
    rows[j, i] * b of all rows j, one in each of its bytes, so one lookup per
    input byte serves every output row."""
    if rows.shape[0] > 4:
        raise ValueError("at most four rows pack into 32 bits")
    tables = np.zeros((rows.shape[1], 256), dtype=np.uint32)
    for i in range(rows.shape[1]):
        for j in range(rows.shape[0]):
            tables[i] |= MUL[int(rows[j, i])].astype(np.uint32) << np.uint32(8 * j)
    return tables


def apply_packed(tables: np.ndarray, nrows: int, data: np.ndarray) -> np.ndarray:
    """apply_matrix through packed_tables: (nrows, n) uint8. Little-endian
    hosts only, as the byte order of the uint32 decides which row is which."""
    acc = np.take(tables[0], data[0])
    for i in range(1, tables.shape[0]):
        acc ^= np.take(tables[i], data[i])
    return acc.view(np.uint8).reshape(-1, 4).T[:nrows]


def decode_rows(matrix: np.ndarray, present: list[int], targets: list[int]) -> np.ndarray:
    """(len(targets), 10): rows that give each target shard from the first
    ten of `present` (sorted)."""
    use = sorted(present)[:DATA]
    inv = mat_inv(matrix[use])
    return mat_mul(matrix[targets], inv)


def shard_geometry(dat_bytes: int) -> tuple[int, int]:
    """(large-block rows, small-block rows) of a volume of dat_bytes."""
    large_rows = 0
    remaining = dat_bytes
    while remaining > LARGE_BLOCK * DATA:
        large_rows += 1
        remaining -= LARGE_BLOCK * DATA
    small_rows = -(-remaining // (SMALL_BLOCK * DATA)) if remaining > 0 else 0
    return large_rows, small_rows


def shard_file_size(dat_bytes: int) -> int:
    large_rows, small_rows = shard_geometry(dat_bytes)
    return large_rows * LARGE_BLOCK + small_rows * SMALL_BLOCK


def padded_rows(dat_path: str) -> np.ndarray:
    """(rows, 10, 1 MiB) uint8: the volume file cut into small-block rows,
    the last one zero-padded."""
    dat_bytes = os.path.getsize(dat_path)
    large_rows, small_rows = shard_geometry(dat_bytes)
    if large_rows:
        raise ValueError("volumes of more than 10 GiB are not sized for this check")
    padded = np.zeros(small_rows * DATA * SMALL_BLOCK, dtype=np.uint8)
    with open(dat_path, "rb") as f:
        got = f.readinto(memoryview(padded)[:dat_bytes])
    if got != dat_bytes:
        raise IOError(f"{dat_path}: read {got} of {dat_bytes} bytes")
    return padded.reshape(small_rows, DATA, SMALL_BLOCK)


def expected_shards(dat_path: str, matrix: np.ndarray | None = None,
                    threads: int = 8) -> np.ndarray:
    """(14, shard bytes) uint8: every shard file's contents for this volume
    file, row blocks computed in a few threads (numpy's take and xor release
    the interpreter lock)."""
    matrix = coding_matrix() if matrix is None else matrix
    rows = padded_rows(dat_path)
    small_rows = rows.shape[0]
    out = np.empty((TOTAL, small_rows * SMALL_BLOCK), dtype=np.uint8)
    tables = packed_tables(matrix[DATA:])

    def one(r: int) -> None:
        lo = r * SMALL_BLOCK
        out[:DATA, lo:lo + SMALL_BLOCK] = rows[r]
        out[DATA:, lo:lo + SMALL_BLOCK] = apply_packed(tables, PARITY, rows[r])

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(one, range(small_rows)))
    return out


def file_differs(path: str, want: np.ndarray, chunk: int = 16 * 1024 * 1024) -> bool:
    """True unless the file is there and holds exactly `want`."""
    try:
        if os.path.getsize(path) != want.nbytes:
            return True
        buf = np.empty(min(chunk, max(1, want.nbytes)), dtype=np.uint8)
        with open(path, "rb", buffering=0) as f:
            for lo in range(0, want.nbytes, chunk):
                part = want[lo:lo + chunk]
                if f.readinto(memoryview(buf)[:part.nbytes]) != part.nbytes:
                    return True
                if not np.array_equal(buf[:part.nbytes], part):
                    return True
    except OSError:
        return True
    return False


def files_differing(files: list[tuple[str, np.ndarray]], threads: int = 8) -> int:
    """How many of the (path, wanted contents) pairs differ; the reads and
    the comparisons release the interpreter lock, so a few threads share
    them."""
    with ThreadPoolExecutor(max_workers=threads) as pool:
        return sum(pool.map(lambda pw: file_differs(*pw), files))
