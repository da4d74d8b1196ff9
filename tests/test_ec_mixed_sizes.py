"""Chunk needles of mixed sizes, read through `EcVolume.read_needle` with a
shard lost: the layout of the `warmobj` deployment (S3 objects of warp's
random sizes, cut by the filer into chunks of at most 4 MiB, over 1 MiB
blocks) at a small geometry, the twin of `test_ec_read_intervals.py`.

The kernel's tile is brought down to 8 bytes, so a small block of 1,024 bytes
is the ladder's top rung (128 tiles), as 1 MiB is at the real tile. Object
sizes follow warp's `--obj.randsize` rule scaled by the same 1,024: 2**U bytes,
U uniform over the eight doublings below 10,240, each object cut into chunks
of at most four blocks, written in order until the records would pass 960
blocks (1 GiB less 64 MiB at the real scale). With shard 3 lost, the pieces
of the records that lie on it come at every width up to a block, and the two
seeds below reach every rung of `LADDER_TILES` between them.

Every body is held to the bytes that were written, every reconstructed piece
to a plain table decode (`ops.gf256`) made here from the surviving shard
files, and the door's counters
(`SeaweedFS_volume_ec_door_bytes_total{part}`,
`SeaweedFS_volume_ec_door_calls_total{tiles}`) to what the offsets give,
worked out here from the index and not from `geometry` or the kernel.
"""

from __future__ import annotations

import bisect
import math
import os
import shutil

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256, rs_kernel
from seaweedfs_tpu.ops.rs_kernel import RSCodec
from seaweedfs_tpu.storage import idx as idx_mod
from seaweedfs_tpu.storage.erasure_coding import encoder, geometry
from seaweedfs_tpu.storage.erasure_coding.ec_volume import EcVolume
from seaweedfs_tpu.storage.needle import Needle, get_actual_size
from seaweedfs_tpu.storage.volume import Volume
from tests.test_ec_batch_buffers import counted
from tests.test_rs_door_counters import door

TILE = 8
SMALL = rs_kernel.LADDER_TILES[-1] * TILE  # 1,024: one block is the top rung
LARGE = 1024 * SMALL                        # never reached: small rows only
MAX_OBJECT, CHUNK = 10 * SMALL, 4 * SMALL  # warp's 10 MiB, the filer's 4 MiB
RECORDS_UP_TO = 960 * SMALL
DATA, TOTAL, LOST = 10, 14, 3
SEEDS = (2, 3)


def warp_chunks(seed: int) -> list[int]:
    """Chunk sizes of objects drawn by warp's rule, in order, cut at CHUNK,
    until the records would pass RECORDS_UP_TO (after the 8-byte super
    block)."""
    rng = np.random.Generator(np.random.SFC64([42, seed]))
    hi = math.log2(MAX_OBJECT)
    out, used = [], 8
    while True:
        size = int(2 ** rng.uniform(hi - 8, hi))
        chunks = [CHUNK] * (size // CHUNK) + ([size % CHUNK] if size % CHUNK else [])
        records = sum(Needle(data=bytes(c)).disk_size(3) for c in chunks)
        if used + records > RECORDS_UP_TO:
            return out
        out += chunks
        used += records


def rung_of(length: int) -> int:
    tiles = -(-length // TILE)
    return rs_kernel.LADDER_TILES[bisect.bisect_left(rs_kernel.LADDER_TILES, tiles)]


@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """{seed: (directory, {key: bytes written})}: one volume a seed, its
    chunks written in order by one writer, sealed at the small geometry with
    the numpy oracle."""
    out = {}
    for seed in SEEDS:
        d = tmp_path_factory.mktemp(f"warmobj{seed}")
        rng = np.random.Generator(np.random.SFC64([42, seed, 1]))
        v = Volume(str(d), "", 1)
        written = {}
        for i, size in enumerate(warp_chunks(seed)):
            key = 0x100 + i
            written[key] = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
            v.write_needle(Needle(cookie=0x42, id=key, data=written[key]))
        v.close()
        base = str(d / "1")
        encoder.write_ec_files(base, codec=RSCodec(backend="numpy"),
                               large_block_size=LARGE, small_block_size=SMALL)
        encoder.write_sorted_file_from_idx(base)
        encoder.save_volume_info(base + ".vif", version=3)
        out[seed] = d, written
    return out


def lost_pieces(d) -> dict[int, list[tuple[int, int]]]:
    """{key: [(block, length)] of the pieces of its record on the lost shard}
    for the needles that have one: block b of the volume lies on shard
    b mod 10, and a record is read as one interval per block it touches."""
    out: dict[int, list[tuple[int, int]]] = {}
    for key, offset, size in idx_mod.walk_index_file(str(d / "1.idx")):
        end = offset + get_actual_size(size, 3)
        for b in range(offset // SMALL, (end - 1) // SMALL + 1):
            if b % DATA == LOST:
                piece = min(end, (b + 1) * SMALL) - max(offset, b * SMALL)
                out.setdefault(key, []).append((b, piece))
    return out


def reference_block(d, block: int) -> bytes:
    """Block `block` of the volume by a table decode from the first ten
    surviving shard files: no kernel, no cache, no EcVolume."""
    row, shard = divmod(block, DATA)
    present = tuple(s for s in range(TOTAL) if s != LOST)
    rows = []
    for s in present[:DATA]:
        with open(d / f"1{geometry.to_ext(s)}", "rb") as f:
            f.seek(row * SMALL)
            rows.append(np.frombuffer(f.read(SMALL), dtype=np.uint8))
    m = gf256.decode_matrix(DATA, TOTAL - DATA, present, (shard,))
    return gf256.gf_matmul_bytes(m, np.stack(rows))[0].tobytes()


@pytest.mark.parametrize("seed", SEEDS)
def test_mixed_chunk_needles_read_back_through_the_rungs_their_pieces_reach(
        sealed, tmp_path, monkeypatch, seed):
    src, written = sealed[seed]
    monkeypatch.setattr(rs_kernel, "TILE", TILE)
    d = tmp_path / "v"
    shutil.copytree(src, d)
    os.remove(d / f"1{geometry.to_ext(LOST)}")
    ev = EcVolume(str(d), "", 1, codec=RSCodec(backend="jax"),
                  large_block_size=LARGE, small_block_size=SMALL)
    rebuilt: list[tuple[int, int, bytes]] = []
    recover = ev._recover_interval

    def spy(shard, off, size):
        out = recover(shard, off, size)
        rebuilt.append((off, size, out))
        return out

    monkeypatch.setattr(ev, "_recover_interval", spy)
    pieces = lost_pieces(src)
    before = counted()
    try:
        for key in written:
            assert ev.read_needle(key, cookie=0x42).data == written[key]
    finally:
        ev.close()
    lengths = [length for got in pieces.values() for _, length in got]
    calls: dict[str, int] = {}
    for length in lengths:
        calls[str(rung_of(length))] = calls.get(str(rung_of(length)), 0) + 1
    # one door call a lost piece, at the rung its length reaches, and the
    # zeros to that rung as its tail
    assert door(before, counted()) == (
        DATA * sum(lengths),
        DATA * sum(rung_of(n) * TILE - n for n in lengths),
        calls)
    assert sorted(size for _, size, _ in rebuilt) == sorted(lengths)
    for off, size, out in rebuilt:
        row, at = divmod(off, SMALL)
        block = row * DATA + LOST
        assert out == reference_block(d, block)[at:at + size]


def test_the_seeds_reach_every_rung_of_the_ladder_between_them(sealed):
    reached = {rung_of(length) for seed in SEEDS
               for got in lost_pieces(sealed[seed][0]).values()
               for _, length in got}
    assert reached == set(rs_kernel.LADDER_TILES)
    for seed in SEEDS:
        # no seed reaches them all alone: the pair is what covers the ladder
        alone = {rung_of(length) for got in lost_pieces(sealed[seed][0]).values()
                 for _, length in got}
        assert alone < reached
