"""Needles that span five small blocks, read through `EcVolume.read_needle`
with shards lost: the layout of the `warm4m` deployment (4 MiB chunk needles
over 1 MiB blocks) at a small geometry.

The kernel's tile is brought down to 8 bytes, so a small block of 1,024 bytes
is the top of the ladder (128 tiles) as 1 MiB is at the real tile, and a
needle of 4,096 bytes crosses five blocks on five consecutive shards. Every
body is held to the bytes that were written, every reconstructed block to a
plain table decode made here from the surviving shard files, and
`SeaweedFS_volume_ec_read_interval_bytes_total{source}` to the split that the
layout gives (worked out here from offsets, not from `geometry`).
"""

from __future__ import annotations

import os
import shutil

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256, rs_kernel
from seaweedfs_tpu.ops.rs_kernel import RSCodec
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.metrics import default_registry, parse_exposition
from seaweedfs_tpu.storage import idx as idx_mod
from seaweedfs_tpu.storage.erasure_coding import encoder, geometry
from seaweedfs_tpu.storage.erasure_coding.ec_volume import EcVolume
from seaweedfs_tpu.storage.needle import Needle, get_actual_size
from seaweedfs_tpu.storage.volume import Volume

TILE = 8
SMALL = rs_kernel.LADDER_TILES[-1] * TILE  # 1,024: one block is the top rung
LARGE = 1024 * SMALL                        # never reached: small rows only
NEEDLES, NEEDLE_BYTES = 12, 4 * SMALL
DATA, TOTAL = 10, 14


@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """One volume of twelve seeded 4-block needles, sealed at the small
    geometry with the numpy oracle: (directory, {key: bytes written})."""
    d = tmp_path_factory.mktemp("warm4m")
    rng = np.random.Generator(np.random.SFC64([28, 1]))
    v = Volume(str(d), "", 1)
    written = {}
    for i in range(NEEDLES):
        key = 0x100 + i
        written[key] = rng.integers(0, 256, NEEDLE_BYTES, dtype=np.uint8).tobytes()
        v.write_needle(Needle(cookie=0x28, id=key, data=written[key]))
    v.close()
    base = str(d / "1")
    encoder.write_ec_files(base, codec=RSCodec(backend="numpy"),
                           large_block_size=LARGE, small_block_size=SMALL)
    encoder.write_sorted_file_from_idx(base)
    encoder.save_volume_info(base + ".vif", version=3)
    return d, written


def _records(d) -> dict[int, tuple[int, int]]:
    """{key: (offset, bytes of the whole record)} from the volume's index."""
    return {key: (offset, get_actual_size(size, 3))
            for key, offset, size in idx_mod.walk_index_file(str(d / "1.idx"))}


def _blocks(offset: int, length: int) -> range:
    return range(offset // SMALL, (offset + length - 1) // SMALL + 1)


def _split(offset: int, length: int, lost: tuple[int, ...]) -> dict[str, int]:
    """Bytes of the record [offset, offset + length) by the rung that has to
    serve them: block b of the volume lies on shard b mod 10."""
    out = {"local": 0, "reconstruct": 0}
    for b in _blocks(offset, length):
        piece = min(offset + length, (b + 1) * SMALL) - max(offset, b * SMALL)
        out["reconstruct" if b % DATA in lost else "local"] += piece
    return out


def _served() -> dict[str, float]:
    return {labels["source"]: value
            for name, labels, value in parse_exposition(default_registry().render())
            if name == trace.EC_READ_INTERVAL_BYTES}


def _reference_block(d, block: int, lost: tuple[int, ...]) -> bytes:
    """Block `block` of the volume by a table decode from the first ten
    surviving shard files: no kernel, no cache, no EcVolume."""
    row, shard = divmod(block, DATA)
    present = tuple(s for s in range(TOTAL) if s not in lost)
    rows = []
    for s in present[:DATA]:
        with open(d / f"1{geometry.to_ext(s)}", "rb") as f:
            f.seek(row * SMALL)
            rows.append(np.frombuffer(f.read(SMALL), dtype=np.uint8))
    m = gf256.decode_matrix(DATA, TOTAL - DATA, present, (shard,))
    return gf256.gf_matmul_bytes(m, np.stack(rows))[0].tobytes()


def _degraded_copy(src, dst, lost: tuple[int, ...]) -> None:
    shutil.copytree(src, dst)
    for s in lost:
        os.remove(dst / f"1{geometry.to_ext(s)}")


CASES = [((s,), None) for s in range(TOTAL)] + [
    ((3, 4), None), ((3, 6), None), ((2, 12), None), ((0, 9), None),
    ((), None), ((3,), "remote"),
]


@pytest.mark.parametrize(
    "lost,fetch", CASES,
    ids=["lost-" + ("-".join(map(str, lost)) or "none") + ("-remote" if f else "")
         for lost, f in CASES])
def test_five_block_needles_read_back_and_count_their_intervals(
        sealed, tmp_path, monkeypatch, lost, fetch):
    src, written = sealed
    monkeypatch.setattr(rs_kernel, "TILE", TILE)
    d = tmp_path / "v"
    _degraded_copy(src, d, lost)
    ev = EcVolume(str(d), "", 1, codec=RSCodec(backend="jax"),
                  large_block_size=LARGE, small_block_size=SMALL)
    if fetch:
        # another node still holds the shard: its bytes come over the wire
        def fetcher(shard, off, size):
            with open(src / f"1{geometry.to_ext(shard)}", "rb") as f:
                f.seek(off)
                return f.read(size)
        ev.shard_fetcher = fetcher
    records = _records(src)
    dat = (src / "1.dat").read_bytes()
    assert {len(_blocks(*r)) for r in records.values()} == {5}
    want = {"local": 0, "remote": 0, "reconstruct": 0}
    before = _served()
    try:
        for key, (offset, length) in records.items():
            n = ev.read_needle(key, cookie=0x28)
            assert n.data == written[key]
            split = _split(offset, length, tuple(s for s in lost if s < DATA))
            want["local"] += split["local"]
            want["remote" if fetch else "reconstruct"] += split["reconstruct"]
    finally:
        ev.close()
    after = _served()
    grew = {s: after.get(s, 0.0) - before.get(s, 0.0) for s in want}
    assert grew == want
    assert sum(grew.values()) == sum(length for _, length in records.values())
    data_lost = [s for s in lost if s < DATA]
    assert (want["reconstruct"] > 0) == bool(data_lost and not fetch)
    # the first lost block of the volume, whole, by the plain table decode:
    # what the served bytes above were made of
    for s in data_lost:
        block = s + DATA  # second row: inside the volume for every shard
        assert _reference_block(d, block, lost) == dat[block * SMALL:(block + 1) * SMALL]
