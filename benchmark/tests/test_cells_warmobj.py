"""Deployment `warmobj` and its cell `warmobj.degraded-read`: the chunk needles
of S3 objects of warp's random sizes in a sealed volume that lost a shard.

The configuration's literal size lists come again from the rule and seed its
`assumed` states (`benchlib/warp_sizes.py`), and their records fit the volume;
the entries this cell and its two metrics added to `BENCHMARK.json` (these
alone, wherever later entries go); the two metrics of the door's counters on
hand-made pages; the cell end to end at the tiny size on the CPU, its
planted fault and its control.

On the CPU a degraded read runs the native codec (the read path picks jax
only where jax has an accelerator), so the door's counters grow in set-up's
seal and not in the window, and both of the door's metrics find nothing to
read there and stay out of the line.
"""

import json
import os

import pytest

from benchlib import cellrun, promtext, volume, warp_sizes
from conftest import ROOT
from test_cells import in_process, run_cli

CELL = "warmobj.degraded-read"
READS = ["warm64k.degraded-read", "warm64k.uniform-read", "warm4m.degraded-read", CELL]
NEW = ("door_zero_tail_share.read", "door_rungs_in_window.read")
BYTES, CALLS = "SeaweedFS_volume_ec_door_bytes_total", "SeaweedFS_volume_ec_door_calls_total"
SEED = 4
RECORDS_UP_TO = {"real": (1 << 30) - (64 << 20), "tiny": 24 << 20}
# the kernel's width ladder in tiles of 8,192 B (the program's LADDER_TILES)
LADDER = (1, 2, 3, 4, 5, 6, 7, 8, 9, 12, 16, 24, 32, 48, 64, 96, 128)


def config() -> dict:
    entry = next(c for c in cellrun.load_spec()["configs"] if c["name"] == "warmobj")
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


# --- the configuration --------------------------------------------------------------
@pytest.mark.parametrize("size", ["real", "tiny"])
def test_the_size_lists_come_again_from_the_stated_rule_and_seed(size):
    cfg = config()
    got = cfg["sizes"][size]
    assert got["needle_bytes"] == warp_sizes.chunk_sizes(SEED, RECORDS_UP_TO[size])
    assert got["needles"] == len(got["needle_bytes"])
    assert f"SFC64({SEED})" in cfg["assumed"]["needle_bytes"]
    assert f"chunk_sizes({SEED}, records_up_to)" in cfg["assumed"]["size_rule"]
    records = 8 + sum(warp_sizes.record_bytes(n) for n in got["needle_bytes"])
    assert records <= RECORDS_UP_TO[size] <= cfg["volume_bytes"]
    # widths of the source: no chunk over 4 MiB, no object under warp's max/256
    assert max(got["needle_bytes"]) <= cfg["filer_max_mb"] << 20
    assert cfg["warp_obj_size_bytes"] >> cfg["warp_doublings"] == 40 * 1024


def layout(size: str) -> dict[int, list[int]]:
    """{needle: lengths of its record's pieces on the lost shard}, the list
    laid out in order from offset 8 as one fill writer lays it."""
    cfg = config()
    index, at = [], 8
    for i, n in enumerate(cfg["sizes"][size]["needle_bytes"]):
        index.append((i, at, n))
        at += warp_sizes.record_bytes(n)
    return volume.records_on_shards(index, at, cfg["lost_shards"])


def test_one_writer_lays_the_list_out_in_order_over_every_upper_rung():
    """The layout the configuration states, as the `.ecx` of every run
    shows it (`notes.degraded_needles`): the same in every run, since one
    writer keeps the list's order."""
    assert config()["assumed"]["fill_writers"] == 1
    touching = layout("real")
    assert len(touching) == 160 and len(layout("tiny")) == 2
    pieces = [p for got in touching.values() for p in got]
    assert len(pieces) == 160 and pieces.count(1 << 20) == 62
    rungs = {next(r for r in LADDER if r * 8192 >= p) for p in pieces}
    assert rungs == set(LADDER) - {1, 5}


def test_the_real_list_is_warps_spread_and_not_one_size():
    sizes = config()["sizes"]["real"]["needle_bytes"]
    assert 600 < len(sizes) < 700
    full = sum(1 for n in sizes if n == 4 << 20)
    assert 0 < full < len(sizes) // 2
    assert min(sizes) < 64 << 10  # a chunk at the bottom doubling


def test_the_configuration_entry_matches_its_file():
    spec = cellrun.load_spec()
    entry = next(c for c in spec["configs"] if c["name"] == "warmobj")
    cfg = config()
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    for words in ("--obj.randsize", "--obj.size 10MiB", "-maxMB 4"):
        assert words in entry["source"]
    assert entry["reduced"] == cfg["reduced"] == ["volume_bytes", "volumes", "nodes"]
    assert cfg["lost_shards"] == [3]
    assert (cfg["small_block_bytes"], cfg["large_block_bytes"], cfg["data_shards"],
            cfg["parity_shards"]) == (1 << 20, 1 << 30, 10, 4)


# --- the entries in BENCHMARK.json ---------------------------------------------------
def test_the_cell_is_one_chip_of_the_first_read_cells_traffic():
    spec = cellrun.load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    cell = cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "warmobj", cells["warm64k.degraded-read"]["traffic"], 1)
    assert len(cell["why"]) <= 200


def test_the_cell_reports_the_read_metrics_and_every_read_layer_metric():
    spec = cellrun.load_spec()
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for name in ("degraded_read_p95_ms", "degraded_read_rps"):
        assert CELL in e2e[name]["workloads"]
    for m in spec["per_layer"]:
        if m["name"].endswith(".read"):
            assert CELL in m["workloads"], m["name"]


@pytest.mark.parametrize("name", NEW)
def test_the_new_metrics_are_the_doors_and_listed_in_the_four_read_cells(name):
    spec = cellrun.load_spec()
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    data = cellrun.load_json(os.path.join(cellrun.HERE, "layer_metrics", name + ".json"))
    assert entry["workloads"][:4] == READS
    for key in ("layer", "unit", "better", "source", "moves"):
        assert entry[key] == data[key]
    assert (data["layer"], data["better"], data["source"], data["moves"]) == (
        "jax door", "lower", "program_counter", "degraded_read_p95_ms")


# --- the readers on hand-made pages --------------------------------------------------
BEFORE = promtext.parse(f"""
{BYTES}{{part="payload"}} 1000
{BYTES}{{part="zero_tail"}} 100
{CALLS}{{tiles="9"}} 4
{CALLS}{{tiles="wide"}} 3
""")
AFTER = promtext.parse(f"""
{BYTES}{{part="payload"}} 11000
{BYTES}{{part="zero_tail"}} 900
{CALLS}{{tiles="9"}} 4
{CALLS}{{tiles="12"}} 2
{CALLS}{{tiles="128"}} 5
{CALLS}{{tiles="96"}} 1
{CALLS}{{tiles="wide"}} 3
""")
PARENT = promtext.parse("""
SeaweedFS_volume_ec_decode_seconds_count{kernel="reconstruct-pallas"} 9.0
""")


def window(before, after) -> dict:
    return {"window": {"before": {"metrics": before, "status": {}},
                       "after": {"metrics": after, "status": {}},
                       "seconds": 20.0, "verbs": []}}


def test_zero_tail_share_is_the_tail_over_the_payload():
    name = "door_zero_tail_share.read"
    assert cellrun.read_layer_metric(name, window(BEFORE, AFTER)) == 8.0
    assert cellrun.read_layer_metric(name, window(PARENT, PARENT)) is None


def test_rungs_in_window_counts_the_tiles_values_that_grew_and_names_them():
    name = "door_rungs_in_window.read"
    # 9 and wide stood still; 12 is new on the second page
    assert cellrun.read_layer_metric(name, window(BEFORE, AFTER)) == (
        3.0, "tiles=12:2,96:1,128:5")
    assert cellrun.read_layer_metric(name, window(AFTER, AFTER)) is None
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
    assert result["notes"]["degraded_needles"] == len(layout("tiny"))


def test_tiny_traced_run_leaves_out_the_doors_metrics_on_the_cpu():
    proc = run_cli(CELL, trace=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    metrics = result["metrics"]
    assert not set(NEW) & set(metrics)
    assert metrics["compiles_in_window.read"]["value"] == 0.0
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

    out = control.control(CELL, 2**31 + 5, "tiny", str(tmp_path / "w"))
    assert out["control_correct"] is False and out["control"]["reads_wrong"]["value"] > 0
    assert out["reference_in_place_correct"] is True
