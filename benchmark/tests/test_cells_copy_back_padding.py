"""The three per-layer metrics of how much a copy back carries on the
device for each byte that arrives, all data over a reader the benchmark had
(`counter_ratio_known`): `copy_back_padding.{seal,repair,read}`, the growth of
`SeaweedFS_volume_ec_device_copy_bytes_total{side="device"}` over that of
`{side="host"}` in the window. On hand-made pages, their entries in
`BENCHMARK.json` (these three alone, wherever later entries go), and the
traced rehearsal of the verb cells at the tiny size on the CPU.
"""

import json
import os

import pytest

from benchlib import cellrun, promtext
from test_cells import run_cli

COPY_BYTES = "SeaweedFS_volume_ec_device_copy_bytes_total"
READS = ["warm64k.degraded-read", "warm64k.uniform-read", "warm4m.degraded-read"]
# name: (the end-to-end metric it moves, cells)
NEW = {
    "copy_back_padding.seal": ("ec_encode_gbps", ["ec1g.seal", "ec4x1g.seal"]),
    "copy_back_padding.repair": ("ec_rebuild_gbps", ["ec1g.repair"]),
    "copy_back_padding.read": ("degraded_read_p95_ms", READS),
}
BEFORE = promtext.parse(f"""
SeaweedFS_volume_ec_device_copies_total{{start="enqueue"}} 3
{COPY_BYTES}{{side="device"}} 4096
{COPY_BYTES}{{side="host"}} 1024
""")
AFTER = promtext.parse(f"""
SeaweedFS_volume_ec_device_copies_total{{start="enqueue"}} 7
{COPY_BYTES}{{side="device"}} 20480
{COPY_BYTES}{{side="host"}} 5120
""")
# what the parent's server shows: copies, but no bytes family
PARENT_BEFORE = {k: v for k, v in BEFORE.items() if k[0] != COPY_BYTES}
PARENT_AFTER = {k: v for k, v in AFTER.items() if k[0] != COPY_BYTES}


def ctx(before, after):
    return {"window": {"before": {"metrics": before}, "after": {"metrics": after},
                       "seconds": 20.0, "verbs": []}}


def test_the_files_name_the_reader_and_the_two_sides():
    for name, (moves, _) in NEW.items():
        spec = cellrun.load_json(os.path.join(cellrun.HERE, "layer_metrics", name + ".json"))
        assert (spec["layer"], spec["unit"], spec["better"], spec["source"],
                spec["moves"], spec["reader"]) == (
            "jax door", "x", "lower", "program_counter", moves, "counter_ratio_known")
        assert spec["args"] == {
            "numerator": {"name": COPY_BYTES, "labels": {"side": "device"}},
            "denominator": {"name": COPY_BYTES, "labels": {"side": "host"}},
            "scale": 1.0, "scope": "window"}


def test_the_entries_are_listed_in_order_with_their_cells():
    spec = cellrun.load_spec()
    mine = [m for m in spec["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    for m in mine:
        moves, cells = NEW[m["name"]]
        assert m == {"name": m["name"], "unit": "x", "better": "lower",
                     "source": "program_counter", "layer": "jax door", "moves": moves,
                     "workloads": cells}
    assert all(set(NEW[n][1]) <= {w["name"] for w in spec["workloads"]} for n in NEW)
    # each lists exactly the cells that report the end-to-end metric it moves
    e2e = {m["name"]: set(m["workloads"]) for m in spec["end_to_end"] if "workloads" in m}
    for moves, cells in NEW.values():
        assert set(cells) == e2e[moves]


def test_each_reads_device_over_host_bytes_and_nothing_on_the_parent():
    for name in NEW:
        assert cellrun.read_layer_metric(name, ctx(BEFORE, AFTER)) == 4.0
        # the parent has no such family: nothing, not 0
        assert cellrun.read_layer_metric(name, ctx(PARENT_BEFORE, PARENT_AFTER)) is None


@pytest.mark.parametrize("cell", ["ec1g.seal", "ec1g.repair"])
def test_tiny_traced_rehearsal_prints_one_in_its_cell(cell):
    proc = run_cli(cell, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    metrics = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
    name = "copy_back_padding." + ("seal" if "seal" in cell else "repair")
    assert set(metrics) & set(NEW) == {name}
    # on the CPU a result takes on the device just the bytes that arrive
    assert metrics[name] == {"value": 1.0, "unit": "x"}
