"""Seeded payloads, the fill over HTTP, and what the benchmark reads of a
volume's files itself (the sorted index, upstream's shard layout)."""

from __future__ import annotations

import ctypes
import http.client
import os
import struct
import threading

import numpy as np

from . import reference


class Payload:
    """Every needle's bytes of one volume, made from the seed in one call:
    needle i is the i-th slice of one buffer, so a read is compared without
    making it again. `needle_bytes` is one size for every needle, or a list
    of sizes cycled over the needles (`[4194304, 4194304, 2097152]`: a
    10 MiB object as the filer cuts it), the offsets cumulative. Volume 0 of
    a run draws from the stream `[seed, 1]`, volume v >= 1 from
    `[seed, 1, v]`."""

    def __init__(self, seed: int, needles: int, needle_bytes: int | list[int],
                 volume: int = 0) -> None:
        sizes = [needle_bytes] if isinstance(needle_bytes, int) else list(needle_bytes)
        self.needles, self.needle_bytes = needles, needle_bytes
        self._at = [0]  # needle i is bytes [_at[i], _at[i + 1])
        for i in range(needles):
            self._at.append(self._at[-1] + int(sizes[i % len(sizes)]))
        words = -(-self._at[-1] // 8)
        stream = [seed, 1] if volume == 0 else [seed, 1, volume]
        rng = np.random.Generator(np.random.SFC64(stream))
        self._buf = memoryview(
            rng.integers(0, 2**64, size=words, dtype=np.uint64)).cast("B")

    def of(self, i: int) -> memoryview:
        return self._buf[self._at[i]:self._at[i + 1]]


_memcmp = ctypes.CDLL(None).memcmp
_memcmp.argtypes = (ctypes.c_char_p, ctypes.c_void_p, ctypes.c_size_t)
_memcmp.restype = ctypes.c_int


def same_bytes(body: bytes, want: memoryview) -> bool:
    """Whether a body that came back is exactly the payload's slice: every
    byte, at the cost of libc's `memcmp` on the slice where it lies (no copy
    of it is made, and the interpreter lock is not held meanwhile). The one
    comparison of read bodies: the read loop's and the control's."""
    n = len(want)
    return len(body) == n and (n == 0 or _memcmp(
        body, ctypes.addressof(ctypes.c_char.from_buffer(want)), n) == 0)


class Vol:
    """One filled volume of a run: which of the configuration's volumes it is,
    its payload, the fid of its first needle as the master assigned it, the
    id it lives under now (a restore moves it to a new one), and the pair of
    files kept of it as it was acknowledged."""

    def __init__(self, number: int, payload: Payload) -> None:
        self.number, self.payload = number, payload
        self.fid0 = self.cookie = self.kept_base = ""
        self.vid = self.key0 = self.dat_bytes = 0

    def assigned(self, fid0: str) -> None:
        self.fid0 = fid0
        self.vid, self.key0, self.cookie = parse_fid(fid0)

    def fid_of(self, i: int) -> str:
        return f"{self.vid},{self.key0 + i:x}{self.cookie}"


def file_base(directory: str, collection: str, vid: int) -> str:
    """Path without extension of a volume's files in a server's directory."""
    return os.path.join(directory, f"{collection}_{vid}" if collection else str(vid))


def parse_fid(fid: str) -> tuple[int, int, str]:
    """"3,01637037d6" -> (volume 3, key 0x01, cookie "637037d6")."""
    vid, rest = fid.split(",", 1)
    return int(vid), int(rest[:-8], 16), rest[-8:]


def fill(volume_addr: str, fid0: str, payload: Payload, writers: int) -> list[str]:
    """POST every needle under fid0, fid0_1, ...; returns what went wrong."""
    n = payload.needles
    errors: list[str] = []

    def writer(lo: int, hi: int) -> None:
        conn = http.client.HTTPConnection(volume_addr, timeout=120)
        try:
            for i in range(lo, hi):
                fid = fid0 if i == 0 else f"{fid0}_{i}"
                conn.request("POST", "/" + fid, body=payload.of(i),
                             headers={"Content-Type": "application/octet-stream"})
                resp = conn.getresponse()
                body = resp.read()
                if resp.status not in (200, 201):
                    errors.append(f"{fid}: {resp.status} {body[:100]!r}")
        except (OSError, http.client.HTTPException) as e:
            errors.append(f"writer {lo}-{hi}: {type(e).__name__}: {e}")
        finally:
            conn.close()

    step = -(-n // writers)
    threads = [threading.Thread(target=writer, args=(lo, min(n, lo + step)))
               for lo in range(0, n, step)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return errors


def read_index(path: str) -> list[tuple[int, int, int]]:
    """[(key, byte offset, size)] of an .idx/.ecx file: 16-byte entries,
    key (8, big-endian), offset in units of 8 bytes (4), size (4, signed)."""
    with open(path, "rb") as f:
        data = f.read()
    out = []
    for at in range(0, len(data) - 15, 16):
        key, off8, size = struct.unpack_from(">QIi", data, at)
        if size > 0:
            out.append((key, off8 * 8, size))
    return out


def records_on_shards(index: list[tuple[int, int, int]], dat_bytes: int,
                      shards: list[int]) -> dict[int, list[int]]:
    """{key: lengths of the pieces of that needle's record that lie on one of
    `shards`}, for needles that have such a piece. A record runs from its
    offset to the next record's; in small-block rows shard s of row r holds
    the volume's bytes [(10r+s) MiB, (10r+s+1) MiB), and the server reads a
    record as one interval per block it touches."""
    block = reference.SMALL_BLOCK
    by_offset = sorted((off, key) for key, off, _ in index)
    out: dict[int, list[int]] = {}
    for at, (off, key) in enumerate(by_offset):
        end = by_offset[at + 1][0] if at + 1 < len(by_offset) else dat_bytes
        pieces = []
        for b in range(off // block, (end - 1) // block + 1):
            if b % reference.DATA in shards:
                pieces.append(min(end, (b + 1) * block) - max(off, b * block))
        if pieces:
            out[key] = pieces
    return out
