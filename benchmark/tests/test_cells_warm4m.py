"""PR 28's cells `warm64k.uniform-read` and `warm4m.degraded-read` end to end
at the tiny size on the CPU, as `test_cells.py` rehearses the first three: the
last line's names, a sound run and a planted fault, the control, and the reader
of what the program counts since PR 28 on recorded pages.

On the CPU the metrics that read the device's label or its trace stay out of
the line; the rest must be there.
"""

import json
import os

import pytest

from benchlib import cellrun, promtext
from conftest import ROOT
from test_cells import in_process, run_cli

UNIFORM, READ4M = "warm64k.uniform-read", "warm4m.degraded-read"
CELLS = [UNIFORM, READ4M]
# per-layer metrics that find something to read without a chip
ON_THE_CPU = {"reconstruct_dispatch_ms.read", "reconstruct_device_wait_ms.read",
              "reconstruct_cpu_ms.read", "request_cpu_ms.read",
              "interpreter_busy_share.read", "device_programs_per_reconstruct.read",
              "compiles_in_window.read", "reconstructed_byte_share.read"}
NEEDS_THE_CHIP = {"reconstruct_ms.read", "device_idle_share.read", "rs_roofline.read"}
# needles of the tiny volume that touch the lost shard, as the .ecx has them:
# 17 or 18 of 180 at 64 KiB; 10 of 24 at 4 MiB, each with one whole 1 MiB block
# of its 4 MiB + 40 B record on shard 3
DEGRADED = {UNIFORM: range(15, 21), READ4M: range(10, 11)}
# share of the bytes of all read intervals that came through reconstruction
SHARE = {UNIFORM: (4.0, 12.0), READ4M: (24.9, 25.0)}


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def spec_names(kind: str, workload: str) -> set[str]:
    return {m["name"] for m in load_spec()[kind]
            if workload in m.get("workloads", [workload])}


@pytest.mark.parametrize("cell", CELLS)
def test_the_cell_is_listed_with_the_metrics_of_the_read_cell(cell):
    assert spec_names("end_to_end", cell) == {
        "degraded_read_p95_ms", "degraded_read_rps", "setup_s"}
    assert spec_names("per_layer", cell) == ON_THE_CPU | NEEDS_THE_CHIP
    assert spec_names("per_layer", "warm64k.degraded-read") == spec_names("per_layer", cell)


def test_warm4m_is_a_configuration_with_a_cell_and_traffic_of_the_first_read_cell():
    spec = load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    assert cells[READ4M]["config"] == "warm4m"
    assert cells[READ4M]["traffic"] == cells["warm64k.degraded-read"]["traffic"]
    entry = next(c for c in spec["configs"] if c["name"] == "warm4m")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"] and config["reduced"] == entry["reduced"]
    # the widths are the source's at either size: needle, block, 10 + 4
    assert {s["needle_bytes"] for s in config["sizes"].values()} == {4 << 20}
    assert (config["small_block_bytes"], config["data_shards"],
            config["parity_shards"]) == (1 << 20, 10, 4)


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_reports_the_cells_end_to_end_metrics(cell):
    proc = run_cli(cell, trace=0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False  # no chip here
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == spec_names("end_to_end", cell)
    assert result["checks"]["reads_wrong"] == {"value": 0, "limit": 0}
    assert result["notes"]["degraded_needles"] in DEGRADED[cell]


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_traced_run_holds_every_per_layer_name_it_can_read(cell):
    proc = run_cli(cell, trace=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result["metrics"]) == ON_THE_CPU
    share = result["metrics"]["reconstructed_byte_share.read"]
    assert share["unit"] == "%"
    low, high = SHARE[cell]
    assert low < share["value"] < high
    assert result["metrics"]["compiles_in_window.read"]["value"] == 0.0
    if cell == READ4M:
        # every lost piece is one whole block: one width to warm
        assert result["notes"]["reconstruct_lengths_warmed"] == 1


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_a_corrupt_surviving_shard_is_not(cell):
    sound = in_process(cell)
    assert sound["correct"] is True, sound["checks"]
    broken = in_process(cell, fault="corrupt-surviving-shard")
    assert broken["correct"] is False
    # the server's own CRC check turns an altered answer into a failed read
    checks = broken["checks"]
    assert checks["reads_wrong"]["value"] + checks["reads_failed"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_comparison(cell, tmp_path):
    import control

    out = control.control(cell, 2**31 + 5, "tiny", str(tmp_path / "w"))
    assert out["control_correct"] is False and out["control"]["reads_wrong"]["value"] > 0
    assert out["reference_in_place_correct"] is True


# --- the reader of PR 28's counter, on recorded pages ----------------------------
FAMILY = "SeaweedFS_volume_ec_read_interval_bytes_total"
BEFORE = promtext.parse(f"""
{FAMILY}{{source="local"}} 1000.0
{FAMILY}{{source="reconstruct"}} 200.0
""")
AFTER = promtext.parse(f"""
{FAMILY}{{source="local"}} 4000.0
{FAMILY}{{source="remote"}} 0.0
{FAMILY}{{source="reconstruct"}} 1200.0
""")
PARENT = promtext.parse("""
SeaweedFS_volume_ec_decode_seconds_count{kernel="reconstruct-pallas"} 9.0
""")


def window(before, after) -> dict:
    return {"window": {"before": {"metrics": before, "status": {}},
                       "after": {"metrics": after, "status": {}},
                       "seconds": 20.0, "verbs": []}}


def test_reconstructed_byte_share_is_reconstruct_over_all_sources():
    name = "reconstructed_byte_share.read"
    # 1000 of the 4000 bytes the window served came through reconstruction
    assert cellrun.read_layer_metric(name, window(BEFORE, AFTER)) == 25.0
    # the parent has no such family: nothing, not 0
    assert cellrun.read_layer_metric(name, window(PARENT, PARENT)) is None
