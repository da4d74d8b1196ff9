"""Chunk sizes of S3 objects as MinIO warp draws them with `--obj.randsize`
and upstream's filer cuts them, for a configuration's literal size list.

warp spreads random object sizes evenly over the doublings (log2) below its
`--obj.size`, eight of them (its generator documents a mean of 0.179 times the
maximum: a log-uniform draw over [max/256, max]); the filer (`-maxMB 4`,
weed/command/filer.go) stores an object of s bytes as floor(s / chunk) full
chunk needles and its remainder. Objects are drawn in order, and their chunks
kept in that order, until the next object's records would pass the volume's
budget (the records start after the 8-byte super block).

    python3 benchmark/benchlib/warp_sizes.py <seed> <records_up_to>

prints the list as JSON.
"""

from __future__ import annotations

import json
import math
import sys

import numpy as np

MAX_OBJECT = 10 * 1024 * 1024  # warp's default --obj.size
DOUBLINGS = 8
CHUNK = 4 * 1024 * 1024        # the filer's -maxMB 4


def record_bytes(n: int) -> int:
    """A v3 needle record of n data bytes and no name or mime: header (16),
    data size (4), data, flags (1), checksum (4), timestamp (8), then 1 to 8
    bytes of padding to the next multiple of 8."""
    return n + 41 - (n + 33) % 8


def chunk_sizes(seed: int, records_up_to: int, max_object: int = MAX_OBJECT,
                doublings: int = DOUBLINGS, chunk: int = CHUNK) -> list[int]:
    """The chunk needles' sizes, in the order the objects were drawn: object
    size int(2**U), U uniform on [log2(max_object) - doublings,
    log2(max_object)] from `SFC64(seed)`."""
    rng = np.random.Generator(np.random.SFC64(seed))
    hi = math.log2(max_object)
    out: list[int] = []
    used = 8
    while True:
        size = int(2 ** rng.uniform(hi - doublings, hi))
        chunks = [chunk] * (size // chunk) + ([size % chunk] if size % chunk else [])
        records = sum(record_bytes(c) for c in chunks)
        if used + records > records_up_to:
            return out
        out += chunks
        used += records


if __name__ == "__main__":
    print(json.dumps(chunk_sizes(int(sys.argv[1]), int(sys.argv[2]))))
