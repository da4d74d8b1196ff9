"""The rebuild's read stage (`encoder._rebuild_ec_files`, the pipeline branch):
each surviving shard's slice of a batch is read in place into its row of the
batch buffer, and a survivor that ends early is an error. Driven through
`rebuild_ec_files` on a non-fused backend over a small seeded volume whose
shard size is not a multiple of `chunk`, so the last batch is short.
"""

import os
import shutil
import sys
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ops.rs_kernel import RSCodec
from seaweedfs_tpu.storage.erasure_coding import encoder, geometry

LARGE, SMALL = 4096, 64
# two large rows, seven small ones and a padded tail: a shard is 8,704 bytes
DAT_BYTES = LARGE * 10 * 2 + SMALL * 10 * 7 + 33
SHARD_BYTES = 2 * LARGE + 8 * SMALL
CHUNK = 1000  # nine batches a shard, the last 704 bytes wide
PIPELINE_THREADS = ("ec-reader", "ec-writer")

LOST = [(i,) for i in range(geometry.TOTAL_SHARDS_COUNT)] + [
    (3, 11),
    (0, 5, 10, 13),
]


ext = geometry.to_ext


@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """A directory with `1.dat` and its fourteen shard files."""
    d = tmp_path_factory.mktemp("sealed")
    rng = np.random.RandomState(31)
    (d / "1.dat").write_bytes(
        rng.randint(0, 256, size=DAT_BYTES, dtype=np.uint8).tobytes()
    )
    encoder.write_ec_files(
        str(d / "1"),
        codec=RSCodec(backend="numpy"),
        large_block_size=LARGE,
        small_block_size=SMALL,
    )
    assert os.path.getsize(d / f"1{ext(0)}") == SHARD_BYTES
    assert SHARD_BYTES % CHUNK
    return d


def shards_without(sealed, tmp_path, lost) -> dict[int, bytes]:
    """Copy the shard files but the lost ones; all fourteen as bytes."""
    originals = {}
    for i in range(geometry.TOTAL_SHARDS_COUNT):
        originals[i] = (sealed / f"1{ext(i)}").read_bytes()
        if i not in lost:
            shutil.copy(sealed / f"1{ext(i)}", tmp_path / f"1{ext(i)}")
    return originals


def pipeline_threads() -> list[str]:
    return [
        t.name for t in threading.enumerate()
        if t.name.startswith(PIPELINE_THREADS)
    ]


@pytest.mark.parametrize("lost", LOST, ids=lambda lost: "-".join(map(str, lost)))
def test_rebuilt_files_are_the_ones_removed(sealed, tmp_path, lost):
    originals = shards_without(sealed, tmp_path, lost)
    rebuilt = encoder.rebuild_ec_files(
        str(tmp_path / "1"), codec=RSCodec(backend="numpy"), chunk=CHUNK
    )
    assert sorted(rebuilt) == sorted(lost)
    for i, want in originals.items():
        assert (tmp_path / f"1{ext(i)}").read_bytes() == want, f"shard {i}"
    assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]
    assert pipeline_threads() == []


def test_only_the_first_ten_survivors_are_read(sealed, tmp_path):
    """Thirteen survive; the three past the tenth hold garbage or nothing,
    and the rebuilt shard is still the one removed."""
    originals = shards_without(sealed, tmp_path, (2,))
    (tmp_path / f"1{ext(11)}").write_bytes(b"\xa5" * SHARD_BYTES)
    (tmp_path / f"1{ext(12)}").write_bytes(b"")
    (tmp_path / f"1{ext(13)}").write_bytes(os.urandom(SHARD_BYTES - 1))
    assert encoder.rebuild_ec_files(
        str(tmp_path / "1"), codec=RSCodec(backend="numpy"), chunk=CHUNK
    ) == [2]
    assert (tmp_path / f"1{ext(2)}").read_bytes() == originals[2]


def test_narrow_batches_under_a_short_switch_interval(sealed, tmp_path):
    """Many narrow batches with the interpreter switching threads as often
    as it can: a batch handed on before its ten rows are filled, or a buffer
    slot taken back while a stage still reads it, shows as a wrong byte."""
    originals = shards_without(sealed, tmp_path, (1, 12))
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(3):
            for i in (1, 12):
                (tmp_path / f"1{ext(i)}").unlink(missing_ok=True)
            assert encoder.rebuild_ec_files(
                str(tmp_path / "1"), codec=RSCodec(backend="numpy"), chunk=61
            ) == [1, 12]
            for i in (1, 12):
                assert (tmp_path / f"1{ext(i)}").read_bytes() == originals[i]
    finally:
        sys.setswitchinterval(before)
    assert pipeline_threads() == []


# the first survivor's size is what every other is held to (`shard_size`), so
# the truncated one is any of the ten read but the first
@pytest.mark.parametrize("backend", ["numpy", "native"])
@pytest.mark.parametrize("short", [1, 6, 10])
def test_a_truncated_survivor_is_an_error_and_leaves_nothing(
    sealed, tmp_path, backend, short
):
    if backend == "native":
        from seaweedfs_tpu.native import lib

        if lib is None:
            pytest.skip("no native lib on this host")
    lost = (3, 11)
    shards_without(sealed, tmp_path, lost)
    os.truncate(tmp_path / f"1{ext(short)}", SHARD_BYTES - 1)
    with pytest.raises(IOError, match=rf"ec shard {short} short read at 8000"):
        encoder.rebuild_ec_files(
            str(tmp_path / "1"), codec=RSCodec(backend=backend), chunk=CHUNK
        )
    left = sorted(os.listdir(tmp_path))
    assert left == sorted(
        f"1{ext(i)}" for i in range(geometry.TOTAL_SHARDS_COUNT) if i not in lost
    )
    assert pipeline_threads() == []
