"""PR 33's deployment `ec4x1g-1m` and its cell `ec4x1g.seal`, as
`BENCHMARK.json` now has them: the entries, a rehearsal at the tiny size on
the CPU (one device: the four volumes queue for its lease), and the four new
per-layer metrics on hand-made contexts.

On the CPU the metrics that read the device's trace stay out of the line; the
rest must be there.
"""

import json
import os

import pytest

from benchlib import cellrun, promtext
from conftest import BENCH, ROOT
from test_cells import in_process, run_cli

CELL = "ec4x1g.seal"
LEASE = "SeaweedFS_volume_ec_device_lease_seconds"
# of `ec1g.seal`'s per-layer metrics, those whose reading keeps its meaning
# when four handlers run at once (the last two since PR 34)
SHARED = {"pipeline_stage_busy_share.seal", "write_drain_share.seal", "h2d_put_s.seal",
          "interpreter_busy_share.seal", "compiles_in_window.seal",
          "device_idle_share.seal", "read_stage_s.seal", "batch_buffer_kept_share.seal"}
# they subtract or rank seconds summed over handlers that overlap, or divide
# four volumes' work by one chip's time
NOT_SHARED = {"verb_outside_pipeline_s.seal", "verb_client_s.seal",
              "verb_largest_step_s.seal", "rs_roofline.seal"}
NEW = {"chips_busy.seal", "pipelines_at_once.seal", "device_lease_wait_s.seal",
       "rs_roofline_per_chip.seal"}
NEEDS_THE_CHIP = {"device_idle_share.seal", "chips_busy.seal", "rs_roofline_per_chip.seal"}


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def names(kind: str, workload: str) -> set[str]:
    return {m["name"] for m in load_spec()[kind]
            if workload in m.get("workloads", [workload])}


def test_the_cell_is_listed_with_the_metrics_that_keep_their_meaning():
    assert names("end_to_end", CELL) == {"ec_encode_gbps", "setup_s"}
    assert names("per_layer", CELL) == SHARED | NEW
    assert names("per_layer", "ec1g.seal") == SHARED | NOT_SHARED
    for m in load_spec()["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "ec_encode_gbps"


def test_the_entries_are_the_dry_checks_and_the_only_four_chip_cell():
    spec = load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    assert list(cells)[-1] == CELL and spec["configs"][-1]["name"] == "ec4x1g-1m"
    cell = cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ec4x1g-1m", "seal-collection", 4)
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] == [CELL]
    entry = spec["configs"][-1]
    assert entry["file"] == "benchmark/configs/ec4x1g-1m.json"
    assert entry["reduced"] == ["volume_bytes", "volumes", "chips"]
    assert "BASELINE.json config 5" in entry["source"] and "-collection" in entry["source"]
    for e in (entry, cell):
        assert all(len(str(v)) <= 200 for v in e.values())
    config = cellrun.load_json(os.path.join(ROOT, entry["file"]))
    assert config["source"] == entry["source"] and config["reduced"] == entry["reduced"]
    # the dry check's file plus where the parallelism comes from; no shape cut
    dry = cellrun.load_json(os.path.join(BENCH, "tests", "data", "ec4x1g-1m.json"))
    added = {"max_parallelization": 10,
             "max_parallelization_from": config["assumed"]["max_parallelization_from"]}
    assert config == {**dry, "assumed": {**dry["assumed"], **added}}
    assert (config["data_shards"], config["parity_shards"], config["small_block_bytes"],
            config["large_block_bytes"]) == (10, 4, 1 << 20, 1 << 30)
    assert (config["volumes"], config["baseline_volumes"], config["chips"],
            config["baseline_chips"]) == (4, 256, 4, 8)
    assert config["sizes"]["real"] == {"needles": 1024, "needle_bytes": 1 << 20}


def test_a_sound_rehearsal_is_correct_and_a_flipped_byte_is_not():
    sound = in_process(CELL)
    assert sound["correct"] is True, sound["checks"]
    assert len(sound["notes"]["volumes"]["ids"]) == 4 and sound["failed"] == 0
    broken = in_process(CELL, fault="flip-shard-byte")
    assert broken["correct"] is False
    assert broken["checks"]["shard_files_differing"] == {"value": 1, "limit": 0}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_run_prints_the_cells_metrics(trace):
    proc = run_cli(CELL, trace)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False  # no chip here
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["checks"]["shard_files_differing"] == {"value": 0, "limit": 0}
    if not trace:
        assert set(result["metrics"]) == {"ec_encode_gbps", "setup_s"}
        return
    assert set(result["metrics"]) == (SHARED | NEW) - NEEDS_THE_CHIP
    # one CPU device: four volumes queue for its lease, about one pipeline at
    # a time, and a volume waits whole seals of the ones before it
    assert 0.5 < result["metrics"]["pipelines_at_once.seal"]["value"] <= 1.0
    assert result["metrics"]["device_lease_wait_s.seal"]["value"] > 0.0
    assert result["metrics"]["compiles_in_window.seal"]["value"] == 0.0


# --- the new readers, on hand-made contexts ---------------------------------------------
def read(name: str, ctx: dict):
    return cellrun.read_layer_metric(name, ctx)


def traced(per_chip: list[float], counted: float = 0.0) -> dict:
    used = [s for s in per_chip if s > 0]
    label = '{kernel="pipeline-pallas"}'
    return {
        "trace": {"busy_s": sum(used) / len(used) if used else 0.0,
                  "busy_s_per_chip": per_chip, "window_s": 4.0},
        "span": {"before": promtext.parse(f"SeaweedFS_volume_ec_encode_bytes_total{label} 0"),
                 "after": promtext.parse(
                     f"SeaweedFS_volume_ec_encode_bytes_total{label} {counted}"),
                 "seconds": 4.0},
        "device_kind": "TPU v5e", "peaks_file": os.path.join(BENCH, "peaks.json")}


def test_chips_busy_counts_the_planes_on_which_something_ran():
    value, note = read("chips_busy.seal", traced([0.7, 0.0, 0.6, 0.65]))
    assert value == 3.0 and note == "busy_s_per_chip=0.7,0,0.6,0.65"
    assert read("chips_busy.seal", traced([0.677, 0.0, 0.0, 0.0]))[0] == 1.0
    assert read("chips_busy.seal", traced([0.0, 0.0, 0.0, 0.0]))[0] == 0.0
    # a rehearsal on the CPU has no chip's plane, and a run without a trace none
    assert read("chips_busy.seal", traced([])) is None
    assert read("chips_busy.seal", {}) is None


def test_per_chip_roofline_of_four_equal_chips_is_the_one_chip_share():
    one = traced([0.2], counted=1.0e9)
    four = traced([0.2, 0.2, 0.2, 0.2], counted=4.0e9)
    want, note = read("rs_roofline.seal", one)
    # 1e9 B x 1.4 / 819e9 B/s over 0.2 s
    assert want == pytest.approx(100 * 1.4e9 / 819e9 / 0.2) and "bound=hbm" in note
    assert read("rs_roofline_per_chip.seal", one)[0] == pytest.approx(want)
    assert read("rs_roofline_per_chip.seal", four)[0] == pytest.approx(want)
    # the old metric divides four volumes' work by the mean of one chip
    assert read("rs_roofline.seal", four)[0] == pytest.approx(4 * want)
    # the parent in this cell: one chip did all four volumes' work
    parent = traced([0.8, 0.0, 0.0, 0.0], counted=4.0e9)
    assert read("rs_roofline_per_chip.seal", parent)[0] == pytest.approx(want)
    assert read("rs_roofline_per_chip.seal", traced([], counted=4.0e9)) is None
    assert read("rs_roofline_per_chip.seal", {}) is None


def lease_page(wait: float, held: float, encodes: int, with_family: bool = True) -> dict:
    lines = [f"SeaweedFS_volume_ec_encode_seconds_count{{kernel=\"pipeline-xla\"}} {encodes}"]
    if with_family:
        for dev, share in (("0", 0.5), ("1", 0.25), ("2", 0.25)):
            lines.append(f'{LEASE}_sum{{device="{dev}",state="wait"}} {wait * share}')
            lines.append(f'{LEASE}_sum{{device="{dev}",state="held"}} {held * share}')
    return promtext.parse("\n".join(lines))


def lease_ctx(before: dict, after: dict) -> dict:
    return {"span": {"before": before, "after": after, "seconds": 4.0},
            "window": {"before": {"metrics": before}, "after": {"metrics": after},
                       "seconds": 20.0, "verbs": []}}


def test_lease_metrics_sum_over_the_devices():
    ctx = lease_ctx(lease_page(1.0, 10.0, 8), lease_page(7.0, 22.0, 20))
    # 12 s of held leases in a traced verb of 4 s: three pipelines at once
    assert read("pipelines_at_once.seal", ctx) == pytest.approx(3.0)
    # 6 s waited over 12 volumes sealed in the window
    assert read("device_lease_wait_s.seal", ctx) == pytest.approx(0.5)


def test_lease_metrics_read_nothing_not_zero_from_a_program_without_the_family():
    old = lease_ctx(lease_page(0, 0, 8, with_family=False),
                    lease_page(0, 0, 20, with_family=False))
    assert read("pipelines_at_once.seal", old) is None
    assert read("device_lease_wait_s.seal", old) is None
    # the family is there and nobody waited: 0, which is a reading
    none_waited = lease_ctx(lease_page(0.0, 0.0, 8), lease_page(0.0, 12.0, 20))
    assert read("device_lease_wait_s.seal", none_waited) == 0.0
    assert read("pipelines_at_once.seal", {"window": none_waited["window"]}) is None
