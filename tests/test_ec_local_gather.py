"""A degraded read whose ten survivors are on this node: the local survivor
gather of `EcVolume._recover_interval`.

Where ten survivors of a lost shard are local, the interval is decoded here:
each of the first ten readable survivors in shard order is read with
`os.preadv` straight into its row of one `(10, width)` array, at the width the
codec takes as it is (`RSCodec.input_width`: a rung of the kernel's ladder on
the jax backend, the interval's own size on the others), and the array goes to
the codec with the interval's size. Only where fewer than ten can be read does
the read ask the remote partial fan-in (`partial_fetcher`), then the classic
ladder.

Held here at a small geometry (the kernel's tile brought down to 8 bytes, so a
small block of 1,024 bytes is the ladder's top rung), on the XLA door (backend
`jax` on the CPU) and on the native codec: the gathered bytes against a plain
table decode (`ops.gf256`) of the surviving shard files at a rung, between
rungs, at a ragged width, at one byte and at a whole block; which route serves
the read; a survivor whose read comes up short or whose fault seam fires
giving its row to the next; and the counters
(`SeaweedFS_volume_ec_reconstructs_total{via}`, the door's bytes).
"""

from __future__ import annotations

import os
import shutil

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256, rs_kernel
from seaweedfs_tpu.ops.rs_kernel import RSCodec
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.metrics import default_registry
from seaweedfs_tpu.storage.erasure_coding import encoder, geometry
from seaweedfs_tpu.storage.erasure_coding.ec_volume import EcVolume
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.util import faults
from tests.test_trace_phases import _grown, _samples

TILE = 8
SMALL = rs_kernel.LADDER_TILES[-1] * TILE  # 1,024: one block is the top rung
LARGE = 1024 * SMALL                        # never reached: small rows only
DATA, TOTAL, LOST = 10, 14, 3
ROWS = 3                                    # small-block rows of every shard
BACKENDS = ["jax", "native"]
# (offset in the shard, size): on a rung (9 tiles), between two rungs (10
# tiles, read at 12), ragged (not a multiple of 4), one byte, a whole block
INTERVALS = {
    "rung": (SMALL + 40, 9 * TILE),
    "between": (120, 10 * TILE),
    "ragged": (2 * SMALL + 7, 83),
    "one-byte": (SMALL + 1000, 1),
    "block": (2 * SMALL, SMALL),
}
SEAM = "volume.ec.shard.read"


def payloads() -> dict[int, bytes]:
    """{key: the seeded bytes of its needle}."""
    rng = np.random.Generator(np.random.SFC64([43, 1]))
    return {0x100 + i: rng.integers(0, 256, 900, dtype=np.uint8).tobytes()
            for i in range(ROWS * DATA)}


@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """A volume of seeded needles sealed at the small geometry with the
    numpy oracle: ROWS small-block rows in each of the 14 shard files."""
    d = tmp_path_factory.mktemp("gather")
    v = Volume(str(d), "", 1)
    for key, data in payloads().items():
        v.write_needle(Needle(cookie=0x42, id=key, data=data))
    v.close()
    base = str(d / "1")
    encoder.write_ec_files(base, codec=RSCodec(backend="numpy"),
                           large_block_size=LARGE, small_block_size=SMALL)
    encoder.write_sorted_file_from_idx(base)
    encoder.save_volume_info(base + ".vif", version=3)
    assert os.path.getsize(base + geometry.to_ext(0)) == ROWS * SMALL
    return d


@pytest.fixture()
def volume_dir(sealed, tmp_path, monkeypatch):
    """A copy of the sealed volume with shard LOST gone, the kernel's tile
    at TILE, and no fault armed at the shard-read seam afterwards."""
    monkeypatch.setattr(rs_kernel, "TILE", TILE)
    d = tmp_path / "v"
    shutil.copytree(sealed, d)
    os.remove(d / f"1{geometry.to_ext(LOST)}")
    yield d
    faults.disarm(SEAM)


def open_volume(d, backend: str) -> EcVolume:
    return EcVolume(str(d), "", 1, codec=RSCodec(backend=backend),
                    large_block_size=LARGE, small_block_size=SMALL)


def table_decode(d, off: int, size: int, skip=()) -> bytes:
    """Shard LOST's [off, off + size) by a table decode from the first ten
    whole surviving shard files (not `skip`): no kernel, no EcVolume."""
    present = tuple(s for s in range(TOTAL) if s != LOST and s not in skip
                    and os.path.exists(d / f"1{geometry.to_ext(s)}"))
    rows = []
    for s in present[:DATA]:
        with open(d / f"1{geometry.to_ext(s)}", "rb") as f:
            f.seek(off)
            rows.append(np.frombuffer(f.read(size), dtype=np.uint8))
    m = gf256.decode_matrix(DATA, TOTAL - DATA, present, (LOST,))
    return gf256.gf_matmul_bytes(m, np.stack(rows))[0].tobytes()


def counted() -> dict:
    return _samples(default_registry().render())


def routes(before: dict, after: dict) -> dict[str, int]:
    return {via: int(_grown(before, after, trace.EC_RECONSTRUCTS, via=via))
            for via in trace.EC_RECONSTRUCT_ROUTES}


def refuse_fanin(missing, off, size):
    raise AssertionError("the fan-in was asked with ten survivors local")


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("interval", list(INTERVALS))
def test_the_gathered_interval_equals_a_table_decode(volume_dir, backend,
                                                     interval):
    off, size = INTERVALS[interval]
    ev = open_volume(volume_dir, backend)
    ev.partial_fetcher = refuse_fanin
    try:
        got = ev._recover_interval(LOST, off, size)
    finally:
        ev.close()
    assert isinstance(got, bytes) and len(got) == size
    assert got == table_decode(volume_dir, off, size)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("interval", list(INTERVALS))
def test_a_gather_counts_one_reconstruct_and_the_doors_bytes(
        volume_dir, backend, interval):
    off, size = INTERVALS[interval]
    ev = open_volume(volume_dir, backend)
    before = counted()
    try:
        ev._recover_interval(LOST, off, size)
    finally:
        ev.close()
    after = counted()
    assert routes(before, after) == {"gather": 1, "fanin": 0, "ladder": 0}
    payload = int(_grown(before, after, trace.EC_DOOR_BYTES, part="payload"))
    tail = int(_grown(before, after, trace.EC_DOOR_BYTES, part="zero_tail"))
    if backend == "jax":
        # the caller's ten rows of `size` bytes, and the zeros to the rung
        width = rs_kernel.ladder_width(size, TILE)
        assert ev.codec.input_width(size) == width
        assert (payload, tail) == (DATA * size, DATA * (width - size))
    else:  # the native codec takes the rows at their own width, no door
        assert ev.codec.input_width(size) == size
        assert (payload, tail) == (0, 0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_with_nine_survivors_local_the_fanin_is_asked(volume_dir, backend):
    off, size = INTERVALS["between"]
    want = table_decode(volume_dir, off, size)
    for s in (10, 11, 12, 13):  # nine survivors left on this node: 0-2, 4-9
        os.remove(volume_dir / f"1{geometry.to_ext(s)}")
    asked = []

    def fanin(missing, o, n):
        asked.append((missing, o, n))
        return want

    ev = open_volume(volume_dir, backend)
    ev.partial_fetcher = fanin
    before = counted()
    try:
        got = ev._recover_interval(LOST, off, size)
    finally:
        ev.close()
    assert asked == [(LOST, off, size)]
    assert got == want
    assert routes(before, counted()) == {"gather": 0, "fanin": 1, "ladder": 0}


@pytest.mark.parametrize("backend", BACKENDS)
def test_with_nine_survivors_local_and_no_fanin_the_ladder_reads_remote(
        sealed, volume_dir, backend):
    off, size = INTERVALS["ragged"]
    want = table_decode(volume_dir, off, size)
    for s in (10, 11, 12, 13):
        os.remove(volume_dir / f"1{geometry.to_ext(s)}")
    remote = []

    def fetch(shard, o, n):  # a sibling server holding the shards gone here
        remote.append(shard)
        with open(sealed / f"1{geometry.to_ext(shard)}", "rb") as f:
            f.seek(o)
            return f.read(n)

    ev = open_volume(volume_dir, backend)
    ev.partial_fetcher = lambda missing, o, n: None
    ev.shard_fetcher = fetch
    before = counted()
    try:
        got = ev._recover_interval(LOST, off, size)
    finally:
        ev.close()
    assert got == want
    assert remote == [10]  # the tenth survivor, the first one not local
    assert routes(before, counted()) == {"gather": 0, "fanin": 0, "ladder": 1}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("fault", ["short", "error", "torn"])
def test_a_survivor_that_cannot_fill_its_row_gives_it_to_the_next(
        volume_dir, backend, fault):
    off, size = INTERVALS["block"]
    want = table_decode(volume_dir, off, size, skip=(0,))
    if fault == "short":  # shard 0 ends inside the interval
        with open(volume_dir / f"1{geometry.to_ext(0)}", "r+b") as f:
            f.truncate(off + size // 2)
    ev = open_volume(volume_dir, backend)
    ev.partial_fetcher = refuse_fanin
    if fault != "short":  # the seam fires on the first survivor's read alone
        faults.arm(SEAM, fault, count=1)
    before = counted()
    try:
        got = ev._recover_interval(LOST, off, size)
    finally:
        ev.close()
    assert got == want
    assert routes(before, counted()) == {"gather": 1, "fanin": 0, "ladder": 0}


@pytest.mark.parametrize("backend", BACKENDS)
def test_the_corrupt_seam_damages_the_gathered_row_silently(volume_dir,
                                                            backend):
    off, size = INTERVALS["block"]
    want = table_decode(volume_dir, off, size)
    ev = open_volume(volume_dir, backend)
    ev.partial_fetcher = refuse_fanin
    faults.arm(SEAM, "corrupt", frac=0.25, count=1)
    try:
        got = ev._recover_interval(LOST, off, size)
    finally:
        ev.close()
    # one byte of shard 0's row flipped: the decode differs there and only
    # there, and nothing says so (the needle's CRC is what catches it)
    diff = np.flatnonzero(np.frombuffer(got, np.uint8)
                          != np.frombuffer(want, np.uint8))
    assert diff.tolist() == [size // 4]


def test_needles_read_back_through_the_gather(volume_dir):
    """Every needle of the volume, read whole through `read_needle` with the
    shard lost, equals what was written; the reconstructs all gathered."""
    ev = open_volume(volume_dir, "jax")
    ev.partial_fetcher = refuse_fanin
    before = counted()
    try:
        for key, data in payloads().items():
            assert ev.read_needle(key, cookie=0x42).data == data
    finally:
        ev.close()
    grown = routes(before, counted())
    assert grown["gather"] > 0 and grown["fanin"] == grown["ladder"] == 0
