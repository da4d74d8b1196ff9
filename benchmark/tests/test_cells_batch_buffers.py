"""PR 34's three per-layer metrics, all data over readers the benchmark had:
`read_stage_s.seal` (`counter_ratio`, the twin of PR 31's
`read_stage_s.repair`) and `batch_buffer_kept_share.seal` / `.repair`
(`counter_ratio_known` over `SeaweedFS_volume_ec_pipeline_buffers_total`). On
hand-made pages, the entries in `BENCHMARK.json`, and the traced rehearsal of
both one-chip verb cells at the tiny size on the CPU.
"""

import json
import os

import pytest

from benchlib import cellrun, promtext
from test_cells import run_cli

BUFFERS = "SeaweedFS_volume_ec_pipeline_buffers_total"
BEFORE = promtext.parse(f"""
SeaweedFS_volume_ec_pipeline_seconds_sum{{stage="read",state="busy"}} 2.0
SeaweedFS_volume_ec_pipeline_seconds_sum{{stage="read",state="wait"}} 9.0
SeaweedFS_volume_ec_pipeline_seconds_sum{{stage="write",state="busy"}} 3.0
SeaweedFS_volume_ec_encode_seconds_count{{kernel="pipeline-pallas"}} 1
SeaweedFS_volume_ec_decode_seconds_count{{kernel="rebuild-pallas"}} 0
{BUFFERS}{{source="fresh"}} 4
""")
AFTER = promtext.parse(f"""
SeaweedFS_volume_ec_pipeline_seconds_sum{{stage="read",state="busy"}} 5.0
SeaweedFS_volume_ec_pipeline_seconds_sum{{stage="read",state="wait"}} 19.0
SeaweedFS_volume_ec_pipeline_seconds_sum{{stage="write",state="busy"}} 7.0
SeaweedFS_volume_ec_encode_seconds_count{{kernel="pipeline-pallas"}} 9
SeaweedFS_volume_ec_decode_seconds_count{{kernel="rebuild-pallas"}} 3
{BUFFERS}{{source="fresh"}} 5
{BUFFERS}{{source="kept"}} 31
""")
OLD_PROGRAM = {k: v for k, v in AFTER.items() if k[0] != BUFFERS}
NEW = {"read_stage_s.seal": ("s", "lower", "program_span", "ec_encode_gbps",
                             ["ec1g.seal", "ec4x1g.seal"]),
       "batch_buffer_kept_share.seal": ("%", "higher", "program_counter", "ec_encode_gbps",
                                        ["ec1g.seal", "ec4x1g.seal"]),
       "batch_buffer_kept_share.repair": ("%", "higher", "program_counter", "ec_rebuild_gbps",
                                          ["ec1g.repair"])}


def ctx(before, after):
    return {"window": {"before": {"metrics": before}, "after": {"metrics": after},
                       "seconds": 20.0, "verbs": []}}


def test_read_stage_seconds_are_the_windows_busy_seconds_a_volume_sealed():
    # 3.0 s of the read stage busy over eight encode spans: four volumes a
    # verb count four
    assert cellrun.read_layer_metric("read_stage_s.seal", ctx(BEFORE, AFTER)) == 3.0 / 8
    assert cellrun.read_layer_metric("read_stage_s.seal", ctx(AFTER, AFTER)) is None


@pytest.mark.parametrize("name", ["batch_buffer_kept_share.seal",
                                  "batch_buffer_kept_share.repair"])
def test_kept_share_is_kept_over_every_slot_handed_out_in_the_window(name):
    assert cellrun.read_layer_metric(name, ctx(BEFORE, AFTER)) == 100.0 * 31 / 32
    # a window in which every batch found its pages
    warm = dict(AFTER)
    warm[(BUFFERS, (("source", "kept"),))] += 16
    assert cellrun.read_layer_metric(name, ctx(AFTER, warm)) == 100.0
    # the parent has no such family: nothing, not 0
    assert cellrun.read_layer_metric(name, ctx(OLD_PROGRAM, OLD_PROGRAM)) is None
    assert cellrun.read_layer_metric(name, ctx(BEFORE, OLD_PROGRAM)) is None


def test_the_entries_come_last_and_are_what_the_files_say():
    spec = cellrun.load_spec()
    assert [m["name"] for m in spec["per_layer"][-3:]] == list(NEW)
    for m in spec["per_layer"][-3:]:
        unit, better, source, moves, workloads = NEW[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": better, "source": source,
                     "layer": "EC pipeline", "moves": moves, "workloads": workloads}
    kinds = {name: cellrun.load_json(
        os.path.join(cellrun.HERE, "layer_metrics", name + ".json"))["reader"]
        for name in NEW}
    assert kinds == {"read_stage_s.seal": "counter_ratio",
                     "batch_buffer_kept_share.seal": "counter_ratio_known",
                     "batch_buffer_kept_share.repair": "counter_ratio_known"}


@pytest.mark.parametrize("cell,names", [
    ("ec1g.seal", ["read_stage_s.seal", "batch_buffer_kept_share.seal"]),
    ("ec1g.repair", ["read_stage_s.repair", "batch_buffer_kept_share.repair"])])
def test_tiny_traced_rehearsal_prints_them(cell, names):
    proc = run_cli(cell, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    metrics = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
    stage, share = (metrics[n] for n in names)
    assert stage["unit"] == "s" and stage["value"] > 0
    # set-up's first encode left its slots: every batch of the window is read
    # into a kept buffer
    assert share == {"value": 100.0, "unit": "%"}
