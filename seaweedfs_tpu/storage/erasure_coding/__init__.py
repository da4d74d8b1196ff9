"""Erasure coding: RS(10,4) striped volumes, bit-compatible with the reference.

File family per volume (reference `weed/storage/erasure_coding/`):
  .ec00–.ec13  10 data + 4 parity shards, striped in 1GB large / 1MB small rows
  .ecx         sorted needle index (same 16B entries as .idx, ascending key)
  .ecj         deletion journal: appended 8B needle ids
  .vif         volume info (JSON: version, block sizes for online-EC volumes)
  .ecp         online-EC partial-stripe journal (online.py; live volumes only)

The shard *math* runs through ops.rs_kernel.RSCodec (TPU bit-plane matmul /
C++ / numpy, byte-identical to klauspost as used by the reference).

Importing the package loads the layout alone (`constants`, `geometry`:
standard library only); whoever wants the codec names its module
(`encoder`, `decoder`, `online`, `ec_volume`).
"""

from .geometry import (
    DATA_SHARDS_COUNT,
    LARGE_BLOCK_SIZE,
    PARITY_SHARDS_COUNT,
    SMALL_BLOCK_SIZE,
    TOTAL_SHARDS_COUNT,
    Interval,
    locate_data,
    to_ext,
)

__all__ = [
    "DATA_SHARDS_COUNT",
    "PARITY_SHARDS_COUNT",
    "TOTAL_SHARDS_COUNT",
    "LARGE_BLOCK_SIZE",
    "SMALL_BLOCK_SIZE",
    "Interval",
    "locate_data",
    "to_ext",
]
