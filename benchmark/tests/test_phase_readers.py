"""The readers of the server's phase counters, on recorded pages, and each
cell's traced rehearsal at the tiny size: every per-layer metric that reads
those counters is in the line."""

import json
import os

import pytest

from benchlib import cellrun, promtext
from conftest import ROOT
from test_cells import run_cli

ADMIN = "SeaweedFS_volume_ec_admin_seconds"
BEFORE = promtext.parse("""
SeaweedFS_volume_ec_admin_seconds_sum{op="generate"} 10.0
SeaweedFS_volume_ec_admin_seconds_sum{op="generate.encode"} 9.0
SeaweedFS_volume_ec_admin_seconds_sum{op="generate.ecx"} 0.5
SeaweedFS_volume_ec_admin_seconds_sum{op="mount"} 1.0
SeaweedFS_volume_ec_admin_seconds_sum{op="delete_shards"} 4.0
SeaweedFS_volume_ec_admin_seconds_sum{op="copy"} 0.25
SeaweedFS_process_cpu_seconds_total 100.0
SeaweedFS_volume_ec_device_seconds_sum{kernel="d2h-wait"} 1.0
SeaweedFS_volume_ec_pipeline_seconds_sum{stage="write",state="busy"} 2.0
""")
AFTER = promtext.parse("""
SeaweedFS_volume_ec_admin_seconds_sum{op="generate"} 12.5
SeaweedFS_volume_ec_admin_seconds_sum{op="generate.encode"} 11.0
SeaweedFS_volume_ec_admin_seconds_sum{op="generate.ecx"} 0.75
SeaweedFS_volume_ec_admin_seconds_sum{op="generate.vif"} 0.125
SeaweedFS_volume_ec_admin_seconds_sum{op="mount"} 1.5
SeaweedFS_volume_ec_admin_seconds_sum{op="delete_shards"} 4.0
SeaweedFS_volume_ec_admin_seconds_sum{op="copy"} 0.25
SeaweedFS_volume_ec_admin_seconds_sum{op="readonly"} 0.0625
SeaweedFS_process_cpu_seconds_total 103.0
SeaweedFS_volume_ec_device_seconds_sum{kernel="d2h-wait"} 1.5
SeaweedFS_volume_ec_pipeline_seconds_sum{stage="write",state="busy"} 4.0
""")
OLD_PROGRAM = promtext.parse("""
SeaweedFS_volume_ec_pipeline_seconds_sum{stage="write",state="busy"} 4.0
""")


def ctx(before=BEFORE, after=AFTER, verbs=None):
    verbs = verbs if verbs is not None else [
        {"ok": True, "traced": False, "seconds": 9.0, "cycle_seconds": 9.0},
        {"ok": True, "traced": True, "seconds": 4.0, "cycle_seconds": 4.5},
    ]
    return {"span": {"before": before, "after": after, "seconds": 5.0},
            "window": {"before": {"metrics": before}, "after": {"metrics": after},
                       "seconds": 6.0, "verbs": verbs}}


def read(name, c):
    return cellrun.read_layer_metric(name, c)


def test_verb_steps_client_takes_every_whole_handler_off_the_traced_cycle():
    value, note = read("verb_client_s.seal", ctx())
    # 4.5 s at the client; generate 2.5 + mount 0.5 + readonly 0.0625 in the
    # server; steps, and handlers that did not run in the span, count for nothing
    assert value == pytest.approx(4.5 - 2.5 - 0.5 - 0.0625)
    assert "generate=2.5" in note and "delete_shards" not in note


def test_verb_steps_largest_skips_excluded_steps_and_stepped_handlers():
    value, note = read("verb_largest_step_s.seal", ctx())
    # generate has steps and generate.encode is excluded: mount 0.5 is the
    # largest of generate.ecx 0.25, generate.vif 0.125, mount, readonly
    assert value == pytest.approx(0.5)
    assert note.startswith("largest=mount; ") and "generate.encode=2" in note


@pytest.mark.parametrize("name", ["verb_client_s.seal", "verb_largest_step_s.repair"])
@pytest.mark.parametrize("case", ["old-program", "no-traced-verb", "no-span"])
def test_verb_steps_reads_nothing_where_there_is_nothing(name, case):
    c = ctx()
    if case == "old-program":
        c = ctx(OLD_PROGRAM, OLD_PROGRAM)
    elif case == "no-traced-verb":
        c = ctx(verbs=[{"ok": True, "traced": False, "seconds": 1.0,
                        "cycle_seconds": 1.0}])
    else:
        del c["span"]
    assert read(name, c) is None


def test_counter_per_second_over_the_windows_seconds():
    assert read("interpreter_busy_share.seal", ctx()) == pytest.approx(100 * 3.0 / 6.0)
    assert read("interpreter_busy_share.read", ctx(OLD_PROGRAM, OLD_PROGRAM)) is None


def test_ratio_of_a_counter_the_program_lacks_is_nothing_not_zero():
    assert read("write_drain_share.seal", ctx()) == pytest.approx(100 * 0.5 / 2.0)
    assert read("write_drain_share.seal", ctx(OLD_PROGRAM, OLD_PROGRAM)) is None


NEW = ("verb_client_s.", "verb_largest_step_s.", "write_drain_share.", "h2d_put_s.",
       "interpreter_busy_share.", "request_cpu_ms.", "reconstruct_cpu_ms.",
       "reconstruct_device_wait_ms.")


@pytest.mark.parametrize("workload,count", [
    ("ec1g.seal", 5), ("ec1g.repair", 2), ("warm64k.degraded-read", 4)])
def test_traced_rehearsal_prints_every_new_metric_of_the_cell(workload, count):
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = {m["name"] for m in spec["per_layer"]
            if workload in m["workloads"] and m["name"].startswith(NEW)}
    assert len(mine) == count
    proc = run_cli(workload, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert mine <= set(result["metrics"]), sorted(result["metrics"])
    values = {n: result["metrics"][n]["value"] for n in mine}
    assert all(v >= 0 for v in values.values()), values
    if workload != "warm64k.degraded-read":
        suffix = workload.split(".")[1]
        steps = result["notes"]["metric_notes"]["verb_largest_step_s." + suffix]
        assert steps.startswith("largest=")
