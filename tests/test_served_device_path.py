"""The served device path held to the oracle, byte for byte.

A volume server with the EC pipeline on the jax backend (the CPU's XLA form
here, `SEAWEEDFS_TPU_EC_BACKEND=jax` as every cell of the benchmark sets it)
seals a volume through the shell's `ec.encode`, loses a data shard and a
parity shard, serves reads that have to reconstruct, and gets both shards
back through `ec.rebuild`. Every shard file is compared with
`ops.gf256`'s numpy oracle over the `.dat`, and `/metrics` and `/status`
have to say that the device side carried the bytes. `benchmark/tests/` makes
the same comparison against its own reference, outside tier-1.
"""

from __future__ import annotations

import io
import os
import shutil

import numpy as np
import pytest

from seaweedfs_tpu.ops import device, gf256
from seaweedfs_tpu.storage import idx as idx_mod
from seaweedfs_tpu.storage.erasure_coding import geometry
from seaweedfs_tpu.storage.needle import CURRENT_VERSION, get_actual_size
from tests.test_trace_phases import _grown, _samples, get_json_text

NEEDLES, NEEDLE_BYTES = 45, 256 * 1024  # 11.8 MB: two rows of small blocks
# a data shard with bytes in both rows (the second ends inside its block, at
# the `.dat`'s end) and a parity shard
LOST = (1, 11)
BLOCK = geometry.SMALL_BLOCK_SIZE
DATA, TOTAL = geometry.DATA_SHARDS_COUNT, geometry.TOTAL_SHARDS_COUNT
ENCODE = "SeaweedFS_volume_ec_encode_bytes_total"
DECODE = "SeaweedFS_volume_ec_decode_bytes_total"


def payload(i: int) -> bytes:
    return np.random.default_rng([30, i]).bytes(NEEDLE_BYTES)


def oracle_shards(dat: bytes) -> np.ndarray:
    """(14, shard size) of a volume of small-block rows: row r keeps shard s
    at `.dat` bytes [(10 r + s) MiB, (10 r + s + 1) MiB), zero beyond the
    `.dat`'s end; parity by the numpy table oracle."""
    rows = -(-len(dat) // (BLOCK * DATA))
    padded = np.zeros(rows * DATA * BLOCK, dtype=np.uint8)
    padded[: len(dat)] = np.frombuffer(dat, dtype=np.uint8)
    data = np.ascontiguousarray(
        padded.reshape(rows, DATA, BLOCK).transpose(1, 0, 2)).reshape(DATA, -1)
    parity = gf256.gf_matmul_bytes(
        gf256.parity_rows(DATA, geometry.PARITY_SHARDS_COUNT), data)
    return np.concatenate([data, parity])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from seaweedfs_tpu.server.httpd import get_json, http_request, post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.shell.shell import run_shell

    from seaweedfs_tpu.ops.rs_kernel import RSCodec

    mp = pytest.MonkeyPatch()
    mp.setenv("SEAWEEDFS_TPU_EC_BACKEND", "jax")
    # an EcVolume's codec picks its backend by platform, not by the override:
    # jax on a TPU, as here
    mp.setattr(RSCodec, "_pick_backend", staticmethod(lambda: "jax"))
    tmp = tmp_path_factory.mktemp("served")
    master = MasterServer(port=0, pulse_seconds=1, volume_size_limit_mb=64)
    master.start()
    vs = VolumeServer([str(tmp / "v0")], master.url, port=0, pulse_seconds=1,
                      max_volume_count=10)
    vs.start()

    def shell(script: str) -> str:
        out = io.StringIO()
        assert run_shell(master.url, script=script, out=out) == 0, out.getvalue()
        return out.getvalue()

    try:
        failures_before = dict(device.report()["selection_failures"])
        before = _samples(get_json_text(vs.url + "/metrics"))
        a = get_json(f"{master.url}/dir/assign?count={NEEDLES}")
        vid = int(a["fid"].split(",")[0])
        fids = [a["fid"]] + [f"{a['fid']}_{i}" for i in range(1, NEEDLES)]
        for i, fid in enumerate(fids):
            st, _, _ = http_request("POST", f"http://{a['url']}/{fid}", payload(i))
            assert st == 201
        base = str(tmp / "v0" / str(vid))
        # the verb deletes the sealed volume's `.dat` at its end
        with open(base + ".dat", "rb") as f:
            dat = f.read()
        assert "shards spread" in shell(f"lock; ec.encode -volumeId {vid}; unlock")
        kept = tmp / "kept"
        kept.mkdir()
        for s in range(TOTAL):
            shutil.copyfile(base + geometry.to_ext(s), kept / f"{s}")

        removed = post_json(f"{vs.url}/admin/ec/delete_shards",
                            {"volume": vid, "collection": "", "shards": list(LOST)})
        assert removed["removed"] == list(LOST)
        assert not any(os.path.exists(base + geometry.to_ext(s)) for s in LOST)
        # the needles whose records reach into the lost data shard, and how
        # many of their bytes lie there: what reconstruction has to supply
        index = list(idx_mod.walk_index_file(base + ".ecx"))
        first_key = min(key for key, _, _ in index)
        degraded, lost_bytes = [], 0
        for key, offset, size in index:
            end = offset + get_actual_size(size, CURRENT_VERSION)
            inside = sum(
                max(0, min(end, lo + BLOCK) - max(offset, lo))
                for lo in range(LOST[0] * BLOCK, len(dat), DATA * BLOCK))
            if inside:
                degraded.append(key - first_key)
                lost_bytes += inside
        bodies = {}
        for i in degraded:
            st, _, bodies[i] = http_request("GET", f"{vs.url}/{fids[i]}")
            assert st == 200
        rebuilt_text = shell(f"lock; ec.rebuild -volumeId {vid}; unlock")
        yield {
            "dat": dat, "base": base, "kept": kept, "oracle": oracle_shards(dat),
            "degraded": degraded, "bodies": bodies, "lost_bytes": lost_bytes,
            "rebuilt_text": rebuilt_text, "before": before,
            "after": _samples(get_json_text(vs.url + "/metrics")),
            "failures_before": failures_before,
            "status": get_json(vs.url + "/status")["ec"],
        }
    finally:
        vs.stop()
        master.stop()
        mp.undo()


def approx(got: float, want: float) -> bool:
    return abs(got - want) <= max(want, 1.0) * 1e-5  # the page renders %g


@pytest.mark.parametrize("shard", range(TOTAL))
def test_sealed_shard_file_is_the_oracles(served, shard):
    want = served["oracle"][shard]
    assert len(want) == geometry.shard_file_size(
        len(served["dat"]), geometry.LARGE_BLOCK_SIZE, BLOCK) == 2 * BLOCK
    with open(served["kept"] / f"{shard}", "rb") as f:
        assert f.read() == want.tobytes()


@pytest.mark.parametrize("shard", LOST, ids=["data", "parity"])
def test_rebuilt_shard_file_is_the_sealed_one(served, shard):
    assert f"rebuilt shards {list(LOST)}" in served["rebuilt_text"]
    with open(served["base"] + geometry.to_ext(shard), "rb") as got, \
            open(served["kept"] / f"{shard}", "rb") as want:
        assert got.read() == want.read()


def test_degraded_reads_return_the_payload(served):
    # two blocks of the lost data shard: four needles or so each, some of
    # them across a block's edge
    assert served["degraded"][0] < 10 < 40 < served["degraded"][-1]
    for i in served["degraded"]:
        assert served["bodies"][i] == payload(i), i


WINDOW = [
    (ENCODE, "pipeline-xla", lambda s: len(s["dat"])),
    # throughput convention of a rebuild: bytes read from ten survivors
    (DECODE, "rebuild-xla", lambda s: DATA * 2 * BLOCK),
    (DECODE, "reconstruct-xla", lambda s: s["lost_bytes"]),
]


@pytest.mark.parametrize("family,kernel,want", WINDOW, ids=[w[1] for w in WINDOW])
def test_windows_bytes_ran_under_the_device_label(served, family, kernel, want):
    before, after = served["before"], served["after"]
    assert approx(_grown(before, after, family, kernel=kernel), want(served))
    host = {key: value - before.get(key, 0.0) for key, value in after.items()
            if key[0] in (ENCODE, DECODE)
            and (dict(key[1])["kernel"] == "fused"
                 or dict(key[1])["kernel"].endswith("-native"))}
    assert not any(host.values()), host


def test_status_says_where_the_bytes_ran(served):
    ec = served["status"]
    assert ec["pipeline"] == {"backend": "jax", "chosen_by": "override"}
    assert ec["jax"]["platform"] == device.platform() == "cpu"
    # the encode's program, the rebuild's and a rung per reconstructed width
    assert ec["kernel_shapes"] >= 3
    assert ec["selection_failures"] == served["failures_before"]
