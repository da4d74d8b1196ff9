"""The Pallas kernel body of ops/rs_pallas.py, executed.

On the CPU the door (`rs_kernel._enqueue`) takes the XLA form, so nothing
else in the suite runs the kernel. Here the test wraps `pl.pallas_call` with `interpret=True`
(the program has no switch for it) and holds the kernel, bit for bit, to the
numpy oracle `ops.gf256.gf_matmul_bytes` for every matrix shape the served
path hands it.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256, rs_kernel, rs_pallas
from tests.test_rs_codec import (
    OFF_THE_LADDER,
    RUNGS,
    check_direct_host_array_goes_to_a_rung,
    check_door_against_oracle,
    check_ladder_widths,
    check_one_bucket_one_program,
    device_programs,
    door_widths,
    rung_neighbours,
    sweep_widths,
    warm_ladder,
)

DATA, PARITY = 10, 4
TILE = 512  # interpret mode walks the grid in Python: keep the steps few


@pytest.fixture()
def interpreted(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(
        pl, "pallas_call", functools.partial(pl.pallas_call, interpret=True)
    )
    rs_pallas.compiled.cache_clear()  # traces made without the wrapper
    yield
    rs_pallas.compiled.cache_clear()  # and the ones made with it


@pytest.fixture()
def pallas_door(interpreted, monkeypatch):
    """The door as it runs on a TPU: over the Pallas form (here
    interpreted), at this file's tile."""
    monkeypatch.setattr(rs_kernel, "transform_kernel", lambda: "pallas")
    monkeypatch.setattr(rs_kernel, "TILE", TILE)


def _rebuild_matrix(missing: tuple[int, ...]) -> np.ndarray:
    present = tuple(i for i in range(DATA + PARITY) if i not in missing)
    return gf256.decode_matrix(DATA, PARITY, present, missing)


def _partial_sum_matrix(k: int) -> np.ndarray:
    """What a repair hop holding k of the ten `use` shards passes to
    RSCodec.apply_matrix: those k columns of the decode matrix."""
    return np.ascontiguousarray(_rebuild_matrix((3,))[:, :k])


CASES = [
    ("encode-4x10", gf256.parity_rows(DATA, PARITY), 4 * TILE),
    ("encode-4x10-padded", gf256.parity_rows(DATA, PARITY), 3 * TILE + 77),
    ("encode-4x10-short", gf256.parity_rows(DATA, PARITY), 5),
    *[
        (f"rebuild-shard-{m:02d}", _rebuild_matrix((m,)), 2 * TILE)
        for m in range(DATA + PARITY)
    ],
    ("rebuild-2-shards", _rebuild_matrix((0, 11)), 2 * TILE),
    ("rebuild-4-shards", _rebuild_matrix((1, 5, 10, 13)), TILE + 1),
    ("partial-sum-1x3", _partial_sum_matrix(3), 2 * TILE),
    ("partial-sum-1x1", _partial_sum_matrix(1), TILE + 9),
    ("partial-sum-1x7", _partial_sum_matrix(7), TILE),
]


@pytest.mark.parametrize(
    "matrix,n", [c[1:] for c in CASES], ids=[c[0] for c in CASES]
)
def test_kernel_matches_numpy_oracle(pallas_door, matrix, n):
    rng = np.random.RandomState(n + matrix.shape[0] * 31 + matrix.shape[1])
    shards = rng.randint(0, 256, size=(matrix.shape[1], n), dtype=np.uint8)
    got = rs_kernel._JaxHandle(rs_kernel.gf_matmul_jax(matrix, shards), n).result()
    want = gf256.gf_matmul_bytes(matrix, shards)
    assert got.shape == want.shape and got.dtype == np.uint8
    assert np.array_equal(got, want)


# --- the codec's door over the Pallas form -------------------------------------
@pytest.mark.parametrize("lost", [3, 11], ids=["lost-data", "lost-parity"])
@pytest.mark.parametrize("n", door_widths(TILE))
def test_door_host_bytes_match_the_oracle(pallas_door, n, lost):
    check_door_against_oracle(n, lost)


def test_door_lengths_of_one_bucket_share_one_program(pallas_door):
    check_one_bucket_one_program(TILE + 40, 2 * TILE - 7)


@pytest.mark.parametrize("form,programs", [("xla", 1), ("pallas", 2)])
@pytest.mark.parametrize("entry", ["apply2d_async", "encode_rows_async"])
def test_device_array_path_is_what_it_was(request, entry, form, programs):
    """The pipelines put their bytes on the device themselves, as words; a
    width that is no tile multiple is then padded there and its tail
    dropped on the host: pad and kernel in the Pallas form, the one program
    of any width in the XLA form."""
    if form == "pallas":
        request.getfixturevalue("pallas_door")
    codec = rs_kernel.RSCodec(backend="jax")
    rows, block = 3, TILE // 2 + 12
    rng = np.random.RandomState(block)
    buf = rng.randint(0, 256, size=rows * DATA * block, dtype=np.uint8)
    data = np.ascontiguousarray(
        buf.reshape(rows, DATA, block).transpose(1, 0, 2)).reshape(DATA, -1)
    assert data.shape[1] % TILE and data.shape[1] % rs_kernel.TILE
    before = device_programs()
    if entry == "apply2d_async":
        got = codec.apply2d_async(gf256.parity_rows(DATA, PARITY), data).result()
    else:
        got = codec.encode_rows_async(buf, block, rows).result()
    assert device_programs() - before == programs
    assert np.array_equal(
        got, gf256.gf_matmul_bytes(gf256.parity_rows(DATA, PARITY), data))


@pytest.mark.parametrize("tiles", OFF_THE_LADDER)
def test_host_array_off_the_ladder_goes_to_a_rung(pallas_door, tiles):
    check_direct_host_array_goes_to_a_rung(tiles, TILE)


# --- the ladder over the Pallas form ---------------------------------------------
@pytest.fixture(scope="module")
def pallas_ladder():
    """The Pallas form, interpreted, at this file's tile, with one reconstruct
    made at every rung (512 bytes to 64 KiB here). Module-scoped, so the
    rungs' programs live as long as the tests below; these come last in the
    file, after every test that clears the kernel's cache."""
    from jax.experimental import pallas as pl

    mp = pytest.MonkeyPatch()
    mp.setattr(pl, "pallas_call",
               functools.partial(pl.pallas_call, interpret=True))
    mp.setattr(rs_kernel, "transform_kernel", lambda: "pallas")
    mp.setattr(rs_kernel, "TILE", TILE)
    rs_pallas.compiled.cache_clear()
    yield warm_ladder(TILE)
    rs_pallas.compiled.cache_clear()
    mp.undo()


@pytest.mark.parametrize("rung", RUNGS)
def test_ladder_rung_neighbours_share_the_rungs_programs(pallas_ladder, rung):
    check_ladder_widths(pallas_ladder, rung_neighbours(rung, TILE))


@pytest.mark.parametrize("seed", range(4))
def test_ladder_sweep_of_widths_up_to_a_block_compiles_nothing(pallas_ladder, seed):
    check_ladder_widths(pallas_ladder, sweep_widths(seed, TILE))
