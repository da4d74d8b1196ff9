"""Deployment `lostnode` and its cell `lostnode.degraded-read`: `warmobj`'s
chunk needles in a sealed volume that lost one of four volume servers, so
shards 0, 4, 8 and 12 are gone and RS(10,4) is at its limit.

The configuration is `warmobj`'s but for the loss: its literal size lists are
`warmobj`'s and come again from the rule; the layout it states follows from
them by the harness's own rule (`volume.records_on_shards`); the entries this
cell and its two metrics added to `BENCHMARK.json` (these alone, wherever
later entries go); the two metrics on hand-made pages; the cell end to end at
the tiny size on the CPU, its planted fault and its control.

On the CPU a degraded read runs the native codec, so the door's calls do not
grow in the window: door calls per degraded read reads 0 there, and the
door's puts in the window 0.
"""

import json
import os

import pytest

from benchlib import cellrun, promtext, volume, warp_sizes
from conftest import ROOT
from test_cells import in_process, run_cli

CELL = "lostnode.degraded-read"
READS = ["warm64k.degraded-read", "warm64k.uniform-read", "warm4m.degraded-read",
         "warmobj.degraded-read", CELL]
NEW = {"door_calls_per_degraded_read.read": ("EC pipeline", "x"),
       "door_matrix_puts_in_window.read": ("jax door", "puts")}
CALLS = "SeaweedFS_volume_ec_door_calls_total"
DEGRADED = "SeaweedFS_volume_ec_degraded_needle_reads_total"
PUTS = "SeaweedFS_volume_ec_door_matrix_puts_total"
SEED = 4
RECORDS_UP_TO = {"real": (1 << 30) - (64 << 20), "tiny": 24 << 20}
LOST = [0, 4, 8, 12]
BLOCK = 1 << 20
# the kernel's width ladder in tiles of 8,192 B (the program's LADDER_TILES)
LADDER = (1, 2, 3, 4, 5, 6, 7, 8, 9, 12, 16, 24, 32, 48, 64, 96, 128)


def config(name: str = "lostnode") -> dict:
    entry = next(c for c in cellrun.load_spec()["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def records(size: str) -> tuple[list[tuple[int, int, int]], int]:
    """([(needle, offset, size)], end of the records): the list laid out in
    order from offset 8, as one fill writer lays it."""
    index, at = [], 8
    for i, n in enumerate(config()["sizes"][size]["needle_bytes"]):
        index.append((i, at, n))
        at += warp_sizes.record_bytes(n)
    return index, at


def blocks_lost(size: str) -> list[list[int]]:
    """For each needle that touches a lost data shard, the small blocks of
    the volume it has on them."""
    index, end = records(size)
    by_offset = sorted((off, key) for key, off, _ in index)
    out = []
    for at, (off, _) in enumerate(by_offset):
        stop = by_offset[at + 1][0] if at + 1 < len(by_offset) else end
        got = [b for b in range(off // BLOCK, (stop - 1) // BLOCK + 1) if b % 10 in LOST]
        if got:
            out.append(got)
    return out


# --- the configuration --------------------------------------------------------------
@pytest.mark.parametrize("size", ["real", "tiny"])
def test_the_needle_list_is_warmobjs_and_comes_again_from_the_rule(size):
    got = config()["sizes"][size]
    assert got == config("warmobj")["sizes"][size]
    assert got["needle_bytes"] == warp_sizes.chunk_sizes(SEED, RECORDS_UP_TO[size])


def test_the_configuration_differs_from_warmobj_in_the_loss_alone():
    cfg, obj = config(), config("warmobj")
    differ = {k for k in set(cfg) | set(obj) if cfg.get(k) != obj.get(k)}
    assert differ == {"name", "source", "deployment", "upstream_nodes", "lost_shards",
                      "assumed", "layout", "guarantees"}
    assert cfg["lost_shards"] == LOST and cfg["upstream_nodes"] == 4
    assert {k: v for k, v in obj["assumed"].items() if k != "lost_shard"}.items() \
        <= cfg["assumed"].items()


def test_the_layout_is_the_one_the_configuration_states():
    """409 needles touch a lost data shard, 489 lost pieces, 80 reads of two
    pieces (23 in one row), every rung reached. The upstream record rule
    (`warp_sizes.record_bytes`: n + 41 - (n + 33) % 8) lays the list two
    needles wider than "n + 40 rounded to 8" would; the runs' `.ecx` gives
    `notes.degraded_needles`."""
    index, end = records("real")
    touching = volume.records_on_shards(index, end, LOST)
    pieces = [p for got in touching.values() for p in got]
    assert (len(touching), len(pieces)) == (409, 489)
    assert pieces.count(BLOCK) == 166
    lost = blocks_lost("real")
    assert len(lost) == 409 and sum(len(b) for b in lost) == 489
    assert {s: sum(1 for b in lost for x in b if x % 10 == s) for s in (0, 4, 8)} == {
        0: 177, 4: 158, 8: 154}
    two = [b for b in lost if len(b) == 2]
    assert len(two) == 80 and sum(1 for b in two if b[0] // 10 == b[1] // 10) == 23
    rungs = {next(r for r in LADDER if r * 8192 >= p) for p in pieces}
    assert rungs == set(LADDER)
    stated = config()["layout"]
    for words in ("409 of the 644", "489 lost pieces", "shard 0: 177", "shard 4: 158",
                  "shard 8: 154", "166 of them whole blocks", "80 reads", "23 of them",
                  "all 17 rungs"):
        assert words in stated
    assert len(volume.records_on_shards(*records("tiny"), LOST)) == 11


def test_the_configuration_entry_matches_its_file():
    spec = cellrun.load_spec()
    entry = next(c for c in spec["configs"] if c["name"] == "lostnode")
    cfg = config()
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    for words in ("spreadEcShards", "4 volume servers", "shards 0,4,8,12",
                  "--obj.randsize", "-maxMB 4"):
        assert words in entry["source"]
    assert entry["reduced"] == cfg["reduced"] == ["volume_bytes", "volumes", "nodes"]
    assert len(entry["why"]) <= 200


# --- the entries in BENCHMARK.json ---------------------------------------------------
def test_the_cell_is_one_chip_of_the_read_cells_traffic():
    cells = {w["name"]: w for w in cellrun.load_spec()["workloads"]}
    cell = cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lostnode", cells["warmobj.degraded-read"]["traffic"], 1)
    assert len(cell["why"]) <= 200


def test_the_cell_reports_the_read_metrics_and_every_read_layer_metric():
    spec = cellrun.load_spec()
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for name in ("degraded_read_p95_ms", "degraded_read_rps"):
        assert CELL in e2e[name]["workloads"]
    for m in spec["per_layer"]:
        if m["name"].endswith(".read"):
            assert CELL in m["workloads"], m["name"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_new_metrics_are_listed_in_the_five_read_cells(name):
    spec = cellrun.load_spec()
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    data = cellrun.load_json(os.path.join(cellrun.HERE, "layer_metrics", name + ".json"))
    assert entry["workloads"][:5] == READS
    for key in ("layer", "unit", "better", "source", "moves"):
        assert entry[key] == data[key]
    assert (data["layer"], data["unit"]) == NEW[name]
    assert (data["better"], data["source"], data["moves"]) == (
        "lower", "program_counter", "degraded_read_p95_ms")


# --- the readers on hand-made pages --------------------------------------------------
BEFORE = promtext.parse(f"""
{CALLS}{{tiles="9"}} 40
{CALLS}{{tiles="wide"}} 3
{DEGRADED} 40
{PUTS}{{device="0"}} 4
""")
AFTER = promtext.parse(f"""
{CALLS}{{tiles="9"}} 400
{CALLS}{{tiles="128"}} 123
{CALLS}{{tiles="wide"}} 3
{DEGRADED} 440
{PUTS}{{device="0"}} 4
""")
PARENT = promtext.parse(f"""
{CALLS}{{tiles="9"}} 40
SeaweedFS_volume_ec_decode_seconds_count{{kernel="reconstruct-pallas"}} 9.0
""")


def window(before, after) -> dict:
    return {"window": {"before": {"metrics": before, "status": {}},
                       "after": {"metrics": after, "status": {}},
                       "seconds": 20.0, "verbs": []}}


def test_door_calls_per_degraded_read_is_the_calls_over_the_reads():
    name = "door_calls_per_degraded_read.read"
    assert cellrun.read_layer_metric(name, window(BEFORE, AFTER)) == 483 / 400
    # a program without the degraded-read counter: nothing to read
    assert cellrun.read_layer_metric(name, window(PARENT, PARENT)) is None


def test_matrix_puts_in_window_is_the_counters_growth():
    name = "door_matrix_puts_in_window.read"
    assert cellrun.read_layer_metric(name, window(BEFORE, AFTER)) == 0.0
    grew = dict(AFTER)
    grew[(PUTS, (("device", "0"),))] = 7.0
    assert cellrun.read_layer_metric(name, window(BEFORE, grew)) == 3.0
    assert cellrun.read_layer_metric(name, window(PARENT, PARENT)) is None


# --- end to end at the tiny size ---------------------------------------------------------
def test_tiny_run_reports_the_cells_end_to_end_metrics():
    proc = run_cli(CELL, trace=0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False  # no chip here
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"degraded_read_p95_ms", "degraded_read_rps", "setup_s"}
    assert result["checks"]["reads_wrong"] == {"value": 0, "limit": 0}
    assert result["notes"]["degraded_needles"] == 11


def test_tiny_traced_run_prints_the_cells_metrics():
    proc = run_cli(CELL, trace=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    metrics = result["metrics"]
    print(json.dumps(metrics, indent=1))
    assert metrics["compiles_in_window.read"]["value"] == 0.0
    assert metrics["door_matrix_puts_in_window.read"]["value"] == 0.0
    # the CPU's reads take the native codec: no door call in the window
    assert metrics["door_calls_per_degraded_read.read"]["value"] == 0.0
    assert "reconstructed_byte_share.read" in metrics


def test_sound_run_is_correct_and_a_corrupt_surviving_shard_is_not():
    sound = in_process(CELL)
    assert sound["correct"] is True, sound["checks"]
    broken = in_process(CELL, fault="corrupt-surviving-shard")
    assert broken["correct"] is False
    checks = broken["checks"]
    assert checks["reads_wrong"]["value"] + checks["reads_failed"]["value"] > 0


def test_control_fails_the_comparison(tmp_path):
    import control

    out = control.control(CELL, 2**31 + 7, "tiny", str(tmp_path / "w"))
    assert out["control_correct"] is False and out["control"]["reads_wrong"]["value"] > 0
    assert out["reference_in_place_correct"] is True
