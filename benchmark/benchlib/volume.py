"""Seeded payloads, the fill over HTTP, and what the benchmark reads of a
volume's files itself (the sorted index, upstream's shard layout)."""

from __future__ import annotations

import http.client
import struct
import threading

import numpy as np

from . import reference


class Payload:
    """Every needle's bytes, made from the seed in one call: needle i is the
    i-th slice of one buffer, so a read is compared without making it again."""

    def __init__(self, seed: int, needles: int, needle_bytes: int) -> None:
        self.needles, self.needle_bytes = needles, needle_bytes
        words = -(-needles * needle_bytes // 8)
        rng = np.random.Generator(np.random.SFC64([seed, 1]))
        self._buf = memoryview(
            rng.integers(0, 2**64, size=words, dtype=np.uint64)).cast("B")

    def of(self, i: int) -> memoryview:
        return self._buf[i * self.needle_bytes:(i + 1) * self.needle_bytes]


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
