"""The seven per-layer metrics of an EC pipeline's edges, all data over a
reader the benchmark had (`counter_ratio_known`): the seconds of an edge a
volume sealed or rebuilt (`pipeline_{setup,head,tail,finish}_s.seal`,
`pipeline_{head,tail}_s.repair`, from
`SeaweedFS_volume_ec_pipeline_edge_seconds_sum{verb,edge}`) and the write
stage thread's CPU seconds a volume sealed (`write_stage_cpu_s.seal`, from
`SeaweedFS_volume_ec_pipeline_cpu_seconds_total{stage}`). On hand-made pages,
their entries in `BENCHMARK.json` (these seven alone, wherever later entries
go), and the traced rehearsal of the three verb cells at the tiny size on
the CPU.
"""

import json
import os

import pytest

from benchlib import cellrun, promtext
from test_cells import run_cli

EDGE = "SeaweedFS_volume_ec_pipeline_edge_seconds_sum"
CPU = "SeaweedFS_volume_ec_pipeline_cpu_seconds_total"
SEAL, REPAIR = ["ec1g.seal", "ec4x1g.seal"], ["ec1g.repair"]
# name: (numerator's labels, source, cells)
NEW = {
    "pipeline_setup_s.seal": ({"verb": "encode", "edge": "setup"}, "program_span", SEAL),
    "pipeline_head_s.seal": ({"verb": "encode", "edge": "head"}, "program_span", SEAL),
    "pipeline_tail_s.seal": ({"verb": "encode", "edge": "tail"}, "program_span", SEAL),
    "pipeline_finish_s.seal": ({"verb": "encode", "edge": "finish"}, "program_span", SEAL),
    "pipeline_head_s.repair": ({"verb": "rebuild", "edge": "head"}, "program_span", REPAIR),
    "pipeline_tail_s.repair": ({"verb": "rebuild", "edge": "tail"}, "program_span", REPAIR),
    "write_stage_cpu_s.seal": ({"stage": "write"}, "program_counter", SEAL),
}
BEFORE = promtext.parse(f"""
SeaweedFS_volume_ec_encode_seconds_count{{kernel="pipeline-pallas"}} 1
SeaweedFS_volume_ec_decode_seconds_count{{kernel="rebuild-pallas"}} 1
{EDGE}{{verb="encode",edge="setup"}} 0.1
{EDGE}{{verb="encode",edge="head"}} 0.2
{EDGE}{{verb="encode",edge="tail"}} 0.3
{EDGE}{{verb="encode",edge="finish"}} 0.4
{EDGE}{{verb="rebuild",edge="head"}} 0.5
{EDGE}{{verb="rebuild",edge="tail"}} 0.6
{CPU}{{stage="write"}} 1.0
{CPU}{{stage="read"}} 1.0
""")
AFTER = promtext.parse(f"""
SeaweedFS_volume_ec_encode_seconds_count{{kernel="pipeline-pallas"}} 5
SeaweedFS_volume_ec_decode_seconds_count{{kernel="rebuild-pallas"}} 3
{EDGE}{{verb="encode",edge="setup"}} 0.9
{EDGE}{{verb="encode",edge="head"}} 1.4
{EDGE}{{verb="encode",edge="tail"}} 3.5
{EDGE}{{verb="encode",edge="finish"}} 0.8
{EDGE}{{verb="rebuild",edge="head"}} 1.3
{EDGE}{{verb="rebuild",edge="tail"}} 1.0
{CPU}{{stage="write"}} 7.0
{CPU}{{stage="read"}} 9.0
""")
WANT = {"pipeline_setup_s.seal": 0.2, "pipeline_head_s.seal": 0.3,
        "pipeline_tail_s.seal": 0.8, "pipeline_finish_s.seal": 0.1,
        "pipeline_head_s.repair": 0.4, "pipeline_tail_s.repair": 0.2,
        "write_stage_cpu_s.seal": 1.5}
# what the parent's server shows: the same denominators, neither family
PARENT_BEFORE = {k: v for k, v in BEFORE.items() if k[0] not in (EDGE, CPU)}
PARENT_AFTER = {k: v for k, v in AFTER.items() if k[0] not in (EDGE, CPU)}


def ctx(before, after):
    return {"window": {"before": {"metrics": before}, "after": {"metrics": after},
                       "seconds": 20.0, "verbs": []}}


def test_the_files_name_the_reader_and_the_counters():
    for name, (labels, source, _) in NEW.items():
        spec = cellrun.load_json(os.path.join(cellrun.HERE, "layer_metrics", name + ".json"))
        seal = name.endswith(".seal")
        assert (spec["layer"], spec["unit"], spec["better"], spec["source"],
                spec["moves"], spec["reader"]) == (
            "EC pipeline", "s", "lower", source,
            "ec_encode_gbps" if seal else "ec_rebuild_gbps", "counter_ratio_known")
        assert spec["args"] == {
            "numerator": {"name": CPU if "stage" in labels else EDGE, "labels": labels},
            # a volume, as read_stage_s.* is
            "denominator": {"name": "SeaweedFS_volume_ec_encode_seconds_count" if seal
                            else "SeaweedFS_volume_ec_decode_seconds_count"},
            "scale": 1.0, "scope": "window"}


def test_the_entries_are_listed_in_order_with_their_cells():
    spec = cellrun.load_spec()
    mine = [m for m in spec["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    for m in mine:
        _, source, cells = NEW[m["name"]]
        assert m == {"name": m["name"], "unit": "s", "better": "lower", "source": source,
                     "layer": "EC pipeline",
                     "moves": "ec_encode_gbps" if m["name"].endswith(".seal")
                     else "ec_rebuild_gbps",
                     "workloads": cells}
    assert all(set(NEW[n][2]) <= {w["name"] for w in spec["workloads"]} for n in NEW)


def test_each_reads_its_seconds_a_volume_and_nothing_on_the_parent():
    for name, want in WANT.items():
        assert cellrun.read_layer_metric(name, ctx(BEFORE, AFTER)) == pytest.approx(want)
        # the parent has neither family: nothing, not 0
        assert cellrun.read_layer_metric(name, ctx(PARENT_BEFORE, PARENT_AFTER)) is None


@pytest.mark.parametrize("cell", ["ec1g.seal", "ec1g.repair", "ec4x1g.seal"])
def test_tiny_traced_rehearsal_prints_them_in_their_cells(cell):
    proc = run_cli(cell, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    metrics = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
    listed = {n for n, (_, _, cells) in NEW.items() if cell in cells}
    assert listed == {n for n in NEW if n.endswith(".seal" if "seal" in cell else ".repair")}
    assert set(metrics) & set(NEW) == listed
    for name in listed:
        assert metrics[name]["unit"] == "s" and metrics[name]["value"] > 0
