"""What each call of the RS door carries, counted where it happens.

`rs_kernel._enqueue` counts the caller's `cols x n` bytes and the zeros that
bring them to the program's width under
`SeaweedFS_volume_ec_door_bytes_total{part="payload"|"zero_tail"}`, and the
call itself under `SeaweedFS_volume_ec_door_calls_total{tiles}` by the rung of
`rs_kernel.LADDER_TILES` its program ran at, or `"wide"` beyond the ladder.
Held here on the CPU: host bytes inside each rung, exactly on each rung (no
tail) and beyond the ladder through the synchronous door of every degraded
read (`_apply_jax`), and device arrays through the async door, on the XLA
body (no pad: tail 0) and on the Pallas body interpreted (the device pad is
the tail); and `tools/check_metric_names.py` holds both names and their label
sets.
"""

from __future__ import annotations

import functools
import importlib
import os
import sys

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256, rs_kernel, rs_pallas
from seaweedfs_tpu.ops.rs_kernel import RSCodec
from seaweedfs_tpu.stats import trace
from tests.test_ec_batch_buffers import counted
from tests.test_trace_phases import _grown

DATA, PARITY = 10, 4
TILE = 8  # a rung of 128 tiles is 1,024 bytes: every width compiles quickly
REBUILD = gf256.decode_matrix(DATA, PARITY, tuple(i for i in range(14) if i != 3), (3,))


def door(before: dict, after: dict) -> tuple[int, int, dict[str, int]]:
    """(payload bytes, zero-tail bytes, {tiles: calls}) grown between pages."""
    calls = {}
    for (name, labels), value in after.items():
        if name == trace.EC_DOOR_CALLS:
            grew = int(value - before.get((name, labels), 0.0))
            if grew:
                calls[dict(labels)["tiles"]] = grew
    return (int(_grown(before, after, trace.EC_DOOR_BYTES, part="payload")),
            int(_grown(before, after, trace.EC_DOOR_BYTES, part="zero_tail")),
            calls)


def seeded(cols: int, n: int) -> np.ndarray:
    rng = np.random.Generator(np.random.SFC64([42, cols, n]))
    return rng.integers(0, 256, size=(cols, n), dtype=np.uint8)


@pytest.fixture()
def small_tile(monkeypatch):
    monkeypatch.setattr(rs_kernel, "TILE", TILE)


WIDTHS = [(rung, "inside") for rung in rs_kernel.LADDER_TILES] + [
    (rung, "on") for rung in rs_kernel.LADDER_TILES]


@pytest.mark.parametrize("rung,where", WIDTHS,
                         ids=[f"{w}-rung-{r}" for r, w in WIDTHS])
def test_host_bytes_count_their_payload_tail_and_rung(small_tile, rung, where):
    # inside: one byte over the rung below, the largest tail the rung has
    below = ([0] + list(rs_kernel.LADDER_TILES))[rs_kernel.LADDER_TILES.index(rung)]
    n = rung * TILE if where == "on" else below * TILE + 1
    data = seeded(DATA, n)
    before = counted()
    got = rs_kernel._apply_jax(REBUILD, data)
    assert door(before, counted()) == (
        DATA * n, DATA * (rung * TILE - n), {str(rung): 1})
    assert (DATA * (rung * TILE - n) == 0) == (where == "on")
    assert np.array_equal(got, gf256.gf_matmul_bytes(REBUILD, data))


def test_host_bytes_beyond_the_ladder_count_as_wide(small_tile):
    top = rs_kernel.LADDER_TILES[-1]
    n = (top + 3) * TILE + 5  # beyond the ladder: the next tile multiple
    data = seeded(DATA, n)
    before = counted()
    got = rs_kernel._apply_jax(REBUILD, data)
    assert door(before, counted()) == (
        DATA * n, DATA * ((top + 4) * TILE - n), {"wide": 1})
    assert np.array_equal(got, gf256.gf_matmul_bytes(REBUILD, data))


def test_each_call_counts_once_through_the_codec(small_tile):
    codec = RSCodec(backend="jax")
    rows = {i: seeded(1, 300)[0] for i in range(14) if i != 3}
    before = counted()
    for _ in range(3):
        codec.reconstruct(rows, targets=[3])
    # 300 bytes go to the kernel at 48 tiles (384 bytes)
    assert door(before, counted()) == (3 * DATA * 300, 3 * DATA * 84, {"48": 3})


@pytest.mark.parametrize("n", [5 * TILE + 4, 200 * TILE],
                         ids=["a-rung-above", "beyond-the-ladder"])
def test_a_device_array_on_the_xla_body_has_no_tail(small_tile, n):
    """The XLA body takes any width: no pad, so nothing is added; the call
    counts at the rung its width reaches."""
    assert rs_kernel.transform_kernel() == "xla"
    m = gf256.parity_rows(DATA, PARITY)
    data = seeded(DATA, n)
    before = counted()
    got = RSCodec(backend="jax").apply2d_async(m, data).result()
    tiles = "6" if n < 128 * TILE else "wide"
    assert door(before, counted()) == (DATA * n, 0, {tiles: 1})
    assert np.array_equal(got, gf256.gf_matmul_bytes(m, data))


def test_a_ragged_device_width_counts_the_hosts_words_tail(small_tile):
    """A width words do not divide is brought to one on the host before the
    put: those bytes are zero tail too, the caller's n the payload."""
    m = gf256.parity_rows(DATA, PARITY)
    n = 5 * TILE + 3
    data = seeded(DATA, n)
    before = counted()
    got = RSCodec(backend="jax").apply2d_async(m, data).result()
    assert door(before, counted()) == (DATA * n, DATA * 1, {"6": 1})
    assert np.array_equal(got, gf256.gf_matmul_bytes(m, data))


def test_the_pallas_bodys_device_pad_is_the_tail(monkeypatch):
    from jax.experimental import pallas as pl

    tile = 512  # interpret mode walks the grid in Python: keep the steps few
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(rs_kernel, "transform_kernel", lambda: "pallas")
    monkeypatch.setattr(rs_kernel, "TILE", tile)
    rs_pallas.compiled.cache_clear()
    try:
        m = gf256.parity_rows(DATA, PARITY)
        n = 3 * tile + 20  # words, but no tile multiple: padded on the device
        data = seeded(DATA, n)
        before = counted()
        got = RSCodec(backend="jax").apply2d_async(m, data).result()
        assert door(before, counted()) == (DATA * n, DATA * (4 * tile - n), {"4": 1})
        assert np.array_equal(got, gf256.gf_matmul_bytes(m, data))
    finally:
        rs_pallas.compiled.cache_clear()


# --- the names and their label sets ------------------------------------------------------
def _tool():
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    return importlib.import_module("check_metric_names")


def test_the_name_lint_holds_both_families_and_their_labels():
    tool = _tool()
    kinds, collector_names = tool.collect()
    assert kinds[trace.EC_DOOR_BYTES] == "counter"
    assert kinds[trace.EC_DOOR_CALLS] == "counter"
    assert not tool.violations(kinds, collector_names)
    assert trace.EC_DOOR_PARTS == ("payload", "zero_tail")
    assert trace.EC_DOOR_TILES == tuple(
        str(t) for t in rs_kernel.LADDER_TILES) + ("wide",)
    assert len(trace.EC_DOOR_TILES) <= 18
    assert tool.phase_label_violations() == []


@pytest.mark.parametrize("tiles", [
    ("1", "2", "wide"),                                   # a rung left out
    trace.EC_DOOR_TILES[:-1] + ("beyond",),               # another word
    trace.EC_DOOR_TILES[:9] + ("10",) + trace.EC_DOOR_TILES[9:],  # one too many
], ids=["rung-left-out", "renamed-wide", "nineteen"])
def test_the_name_lint_refuses_a_tiles_set_off_the_ladder(monkeypatch, tiles):
    monkeypatch.setattr(trace, "EC_DOOR_TILES", tiles)
    assert _tool().door_tiles_violations()
