"""A sealed volume that lost one of four volume servers: RS(10,4) at its limit.

`ec.encode` spreads the 14 shards round-robin over the servers
(`shell/commands_ec._spread_plan`, upstream `spreadEcShards`), so on four
servers each holds {s, s+4, s+8, s+12} and losing one loses three or four
shards at once: every survivor is needed, and three or two of the ten are
parity. Here a small volume is sealed by the XLA door (backend `jax` on the
CPU), each server's set is removed in turn, and

  * every needle is read back through `EcVolume.read_needle`, byte for byte
    against what was written, among them needles with two lost pieces in one
    small-block row and across two rows;
  * each reconstructed interval equals a plain table decode
    (`gf256.gf_matmul_bytes` over `gf256.decode_matrix`'s rows of every lost
    data shard at once) of the surviving shard files;
  * a read that rebuilt any interval counts once under
    `SeaweedFS_volume_ec_degraded_needle_reads_total`, however many it rebuilt,
    and a lost piece is one door call.

The kernel's tile is brought down to 8 bytes, so one small block of 1,024
bytes is the ladder's top rung, as in `tests/test_ec_local_gather.py`.
"""

from __future__ import annotations

import os
import shutil

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256, rs_kernel
from seaweedfs_tpu.ops.rs_kernel import RSCodec
from seaweedfs_tpu.shell.commands_ec import _spread_plan
from seaweedfs_tpu.shell.env import ServerView
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.metrics import default_registry
from seaweedfs_tpu.storage.erasure_coding import encoder, geometry
from seaweedfs_tpu.storage.erasure_coding.ec_volume import EcVolume
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume
from tests.test_trace_phases import _grown, _samples

TILE = 8
SMALL = rs_kernel.LADDER_TILES[-1] * TILE  # 1,024: one block is the top rung
LARGE = 1024 * SMALL                        # never reached: small rows only
DATA, PARITY, TOTAL = 10, 4, 14
SERVERS = 4
# what each of four servers holds after ec.encode's round-robin spread
LOSSES = [(0, 4, 8, 12), (1, 5, 9, 13), (2, 6, 10), (3, 7, 11)]


def payloads() -> dict[int, bytes]:
    """{key: seeded bytes}: mostly needles under two blocks; every sixth of
    five to seven blocks, which meets two lost pieces of one row; every
    fifteenth of over twenty blocks, which meets lost pieces in two rows."""
    rng = np.random.Generator(np.random.SFC64([44, 1]))
    out = {}
    for i in range(60):
        if i % 15 == 14:
            n = int(rng.integers(20 * SMALL, 24 * SMALL))
        elif i % 6 == 5:
            n = int(rng.integers(5 * SMALL, 7 * SMALL))
        else:
            n = int(rng.integers(1, 1400))
        out[0x200 + i] = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
    return out


@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """The needles in one volume, sealed at the small geometry by the XLA
    door: the pipeline puts each batch on the device as words."""
    d = tmp_path_factory.mktemp("nodeloss")
    v = Volume(str(d), "", 1)
    for key, data in payloads().items():
        v.write_needle(Needle(cookie=0x44, id=key, data=data))
    v.close()
    base = str(d / "1")
    encoder.write_ec_files(base, codec=RSCodec(backend="jax"),
                           large_block_size=LARGE, small_block_size=SMALL)
    encoder.write_sorted_file_from_idx(base)
    encoder.save_volume_info(base + ".vif", version=3)
    return d


@pytest.fixture(params=LOSSES, ids=["server%d" % s[0] for s in LOSSES])
def lost_node(request, sealed, tmp_path, monkeypatch):
    """(directory, lost shards): a copy of the sealed volume without one
    server's shards, the kernel's tile at TILE."""
    monkeypatch.setattr(rs_kernel, "TILE", TILE)
    d = tmp_path / "v"
    shutil.copytree(sealed, d)
    for s in request.param:
        os.remove(d / f"1{geometry.to_ext(s)}")
    return d, request.param


def open_volume(d) -> EcVolume:
    return EcVolume(str(d), "", 1, codec=RSCodec(backend="jax"),
                    large_block_size=LARGE, small_block_size=SMALL)


def lost_pieces(ev: EcVolume, lost) -> dict[int, list[tuple[int, int, int]]]:
    """{key: [(shard, offset in it, size)] of its intervals on lost shards}."""
    out = {}
    for key in payloads():
        offset, size = ev.find_needle_from_ecx(key)
        pieces = []
        for iv in ev.locate_intervals(offset, size):
            shard, off = iv.to_shard_id_and_offset(LARGE, SMALL)
            if shard in lost:
                pieces.append((shard, off, iv.size))
        if pieces:
            out[key] = pieces
    return out


def table_decode(d, lost) -> dict[int, np.ndarray]:
    """{lost data shard: its whole file} by one table decode of every lost
    data shard from the ten surviving shard files: no kernel, no EcVolume."""
    present = tuple(s for s in range(TOTAL) if s not in lost)
    assert len(present) >= DATA
    rows = [np.fromfile(d / f"1{geometry.to_ext(s)}", dtype=np.uint8)
            for s in present[:DATA]]
    targets = tuple(s for s in lost if s < DATA)
    m = gf256.decode_matrix(DATA, PARITY, present, targets)
    out = gf256.gf_matmul_bytes(m, np.stack(rows))
    return {t: out[i] for i, t in enumerate(targets)}


def test_four_servers_hold_the_round_robin_sets():
    servers = [ServerView("dc", "rack", {"id": f"s{i}", "url": f"s{i}:8080"})
               for i in range(SERVERS)]
    plan = _spread_plan(servers, servers[0])
    assert sorted(tuple(v) for v in plan.values()) == sorted(LOSSES)
    # each set is at most RS(10,4)'s tolerance, and the first is all of it
    assert max(len(s) for s in LOSSES) == PARITY == len(LOSSES[0])


def test_the_layout_has_reads_of_two_lost_pieces_in_a_row_and_across_rows(lost_node):
    d, lost = lost_node
    ev = open_volume(d)
    try:
        pieces = lost_pieces(ev, lost)
    finally:
        ev.close()
    rows = [[off // SMALL for _, off, _ in p] for p in pieces.values()]
    assert any(len(set(r)) < len(r) for r in rows)  # two lost shards of one row
    assert any(len(set(r)) > 1 for r in rows)  # lost shards of two rows


def test_every_needle_reads_back_byte_for_byte(lost_node):
    d, lost = lost_node
    before = _samples(default_registry().render())
    ev = open_volume(d)
    try:
        pieces = lost_pieces(ev, lost)
        for key, data in payloads().items():
            n = ev.read_needle(key, cookie=0x44)
            assert n.data == data, key
    finally:
        ev.close()
    after = _samples(default_registry().render())
    # one update a read that rebuilt anything, and one door call a lost piece
    assert _grown(before, after, trace.EC_DEGRADED_NEEDLE_READS) == len(pieces)
    assert _grown(before, after, trace.EC_DOOR_CALLS) == sum(
        len(p) for p in pieces.values())
    assert _grown(before, after, trace.EC_RECONSTRUCTS, via="gather") == sum(
        len(p) for p in pieces.values())


def test_each_reconstructed_interval_is_the_table_decode(lost_node):
    d, lost = lost_node
    want = table_decode(d, lost)
    ev = open_volume(d)
    try:
        pieces = lost_pieces(ev, lost)
        assert {s for p in pieces.values() for s, _, _ in p} == set(want)
        for p in pieces.values():
            for shard, off, size in p:
                got = ev._recover_interval(shard, off, size)
                assert got == want[shard][off:off + size].tobytes(), (shard, off)
    finally:
        ev.close()


def test_a_read_of_healthy_shards_alone_counts_no_degraded_read(lost_node):
    d, lost = lost_node
    ev = open_volume(d)
    try:
        healthy = [k for k in payloads() if k not in lost_pieces(ev, lost)]
        assert healthy
        before = _samples(default_registry().render())
        for key in healthy:
            ev.read_needle(key)
        after = _samples(default_registry().render())
    finally:
        ev.close()
    assert _grown(before, after, trace.EC_DEGRADED_NEEDLE_READS) == 0
