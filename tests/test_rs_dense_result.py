"""The RS door's result comes back as dense 32-bit words.

The kernel's device result is uint32 words whose row-major bytes are the
(rows, n) uint8 result: `(rows, n/512, 128)` from the Pallas body, which
tiles into 4 KiB of consecutive bytes on a TPU, and `(rows, n/4)` from the
XLA body. `_JaxHandle.result()` views the words as bytes with no copy. Held
here on the CPU, byte for byte against the numpy oracle `ops.gf256`, for
encode (4 rows), rebuild (1 row) and a 2-row decode matrix: at every rung of
`rs_kernel.LADDER_TILES` through the synchronous door (`_apply_jax`, every
degraded read), at a width beyond the ladder and at device widths that are
no tile multiple through the async doors (`apply2d_async`,
`encode_rows_async`: seal, rebuild, the online backlog), in both kernel
forms; and `SeaweedFS_volume_ec_device_copy_bytes_total{side}` grows by the
result's device bytes and its host bytes, once a copy.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256, rs_kernel, rs_pallas
from seaweedfs_tpu.ops.rs_kernel import RSCodec
from seaweedfs_tpu.stats import trace
from tests.test_ec_batch_buffers import counted
from tests.test_trace_phases import _grown

DATA, PARITY = 10, 4
COPY_BYTES = trace.EC_DEVICE_COPY_BYTES
PALLAS_TILE = 512  # interpret mode walks the grid in Python: keep the steps few


def _decode(missing: tuple[int, ...]) -> np.ndarray:
    present = tuple(i for i in range(DATA + PARITY) if i not in missing)
    return gf256.decode_matrix(DATA, PARITY, present, missing)


MATRICES = {
    "encode-4": gf256.parity_rows(DATA, PARITY),
    "rebuild-1": _decode((3,)),
    "decode-2": _decode((3, 12)),
}


def seeded(cols: int, n: int, *seed: int) -> np.ndarray:
    rng = np.random.Generator(np.random.SFC64([41, cols, n, *seed]))
    return rng.integers(0, 256, size=(cols, n), dtype=np.uint8)


def copied(before: dict, after: dict) -> tuple[int, int]:
    return (int(_grown(before, after, COPY_BYTES, side="device")),
            int(_grown(before, after, COPY_BYTES, side="host")))


@pytest.fixture(params=["xla", "pallas"])
def form(request, monkeypatch):
    """The door over either body: the XLA one at the kernel's own tile, or
    the Pallas one, interpreted, at a small tile."""
    if request.param == "pallas":
        from jax.experimental import pallas as pl

        monkeypatch.setattr(pl, "pallas_call",
                            functools.partial(pl.pallas_call, interpret=True))
        monkeypatch.setattr(rs_kernel, "transform_kernel", lambda: "pallas")
        monkeypatch.setattr(rs_kernel, "TILE", PALLAS_TILE)
        rs_pallas.compiled.cache_clear()
        yield request.param
        rs_pallas.compiled.cache_clear()
    else:
        yield request.param


# --- the synchronous door at every rung of the ladder -----------------------------------
@pytest.mark.parametrize("rung", rs_kernel.LADDER_TILES)
@pytest.mark.parametrize("name", MATRICES)
def test_sync_door_at_every_rung_gives_the_oracles_bytes(name, rung):
    m = MATRICES[name]
    n = rung * rs_kernel.TILE - rung  # a zero tail of `rung` bytes to drop
    data = seeded(DATA, n, rung)
    assert rs_kernel.ladder_width(n, rs_kernel.TILE) == rung * rs_kernel.TILE
    got = rs_kernel._apply_jax(m, data)
    assert got.dtype == np.uint8 and got.shape == (m.shape[0], n)
    assert np.array_equal(got, gf256.gf_matmul_bytes(m, data))


# --- the async doors: beyond the ladder, and device widths off the tile -----------------
@pytest.mark.parametrize("name", MATRICES)
@pytest.mark.parametrize("n", [
    2 * 1024 * 1024 + 3 * 8192,  # beyond the ladder, as a pipeline's batch
    3 * PALLAS_TILE + 20,        # words, but no tile multiple: padded on the device
    3 * PALLAS_TILE + 21,        # not even words: a zero tail on the host first
], ids=["beyond-the-ladder", "off-the-tile", "ragged"])
def test_apply2d_async_gives_the_oracles_bytes(form, name, n):
    m = MATRICES[name]
    data = seeded(DATA, n, m.shape[0])
    got = RSCodec(backend="jax").apply2d_async(m, data).result()
    assert got.dtype == np.uint8 and got.shape == (m.shape[0], n)
    assert np.array_equal(got, gf256.gf_matmul_bytes(m, data))


@pytest.mark.parametrize("block,rows", [(PALLAS_TILE // 2 + 12, 3),
                                        (PALLAS_TILE // 2 + 11, 3),
                                        (4096, 9)],
                         ids=["words", "ragged-block", "rows-of-a-batch"])
def test_encode_rows_async_gives_the_oracles_bytes(form, block, rows):
    buf = seeded(1, rows * DATA * block, block)[0]
    data = np.ascontiguousarray(
        buf.reshape(rows, DATA, block).transpose(1, 0, 2)).reshape(DATA, -1)
    got = RSCodec(backend="jax").encode_rows_async(buf, block, rows).result()
    assert got.dtype == np.uint8 and got.shape == (PARITY, rows * block)
    assert np.array_equal(got, gf256.gf_matmul_bytes(MATRICES["encode-4"], data))


# --- the one host view, and the counter -------------------------------------------------
@pytest.mark.parametrize("name", MATRICES)
def test_the_result_is_dense_words_viewed_as_bytes(form, name):
    m = MATRICES[name]
    rows, n = m.shape[0], 5 * PALLAS_TILE - 9
    data = seeded(DATA, n, 7)
    dev = rs_kernel.gf_matmul_jax(m, data)
    width = rs_kernel.ladder_width(n, rs_kernel.TILE)
    assert dev.dtype == np.uint32 and dev.size * 4 == rows * width
    if form == "pallas":
        assert dev.shape == (rows, width // 512, 128)
    assert dev.on_device_size_in_bytes() == rows * width
    handle = rs_kernel._JaxHandle(dev, n)
    before = counted()
    got = handle.result()
    assert copied(before, counted()) == (rows * width, rows * width)
    assert got.dtype == np.uint8 and got.shape == (rows, n)
    assert np.shares_memory(got, np.asarray(dev))  # a view, not a copy
    assert np.array_equal(got, gf256.gf_matmul_bytes(m, data))


@pytest.mark.parametrize("door", ["apply2d_async", "encode_rows_async", "apply_jax"])
def test_each_copy_counts_both_sides_once(form, door):
    codec = RSCodec(backend="jax")
    m, block, rows = MATRICES["encode-4"], 2 * PALLAS_TILE, 2
    buf = seeded(1, rows * DATA * block, 9)[0]
    data = np.ascontiguousarray(
        buf.reshape(rows, DATA, block).transpose(1, 0, 2)).reshape(DATA, -1)
    before = counted()
    for _ in range(3):
        if door == "apply2d_async":
            codec.apply2d_async(m, data).result()
        elif door == "encode_rows_async":
            codec.encode_rows_async(buf, block, rows).result()
        else:
            rs_kernel._apply_jax(m, data)
    # host bytes go to the kernel at a rung; these device ones are a tile
    # multiple of the Pallas body's already, and the XLA body takes any width
    n = rows * block
    each = PARITY * (rs_kernel.ladder_width(n, rs_kernel.TILE) if door == "apply_jax" else n)
    assert copied(before, counted()) == (3 * each, 3 * each)


def test_a_device_array_of_bytes_is_refused():
    """The door reads device arrays as words: bytes would be taken for
    values and give wrong parity, so they are refused."""
    from seaweedfs_tpu.ops import device

    x = device.jax().device_put(seeded(DATA, 64, 1))
    with pytest.raises(TypeError, match="uint32"):
        rs_kernel.gf_matmul_jax(MATRICES["encode-4"], x)
