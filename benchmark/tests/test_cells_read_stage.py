"""PR 31's per-layer metric `read_stage_s.repair` in the rehearsal of
`ec1g.repair` at the tiny size on the CPU, beside `test_cells.py`: the line
prints it as a positive number of seconds, and what it divides by (decode
spans, no kernel label) grows by exactly one a verb of the window, so the
number is the read stage's busy seconds of one rebuild.
"""

import json
import time

from benchlib import cellrun, promtext
from test_cells import run_cli

CELL, NAME = "ec1g.repair", "read_stage_s.repair"
BUSY = {"stage": "read", "state": "busy"}


def test_tiny_repair_prints_the_read_stage_seconds():
    proc = run_cli(CELL, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"][NAME]
    assert got["unit"] == "s" and got["value"] > 0


def test_every_decode_span_of_the_window_is_one_rebuild(monkeypatch):
    seen = {}
    context = cellrun.Run.layer_context

    def keep(self, before, after, e2e):
        seen["ctx"] = context(self, before, after, e2e)
        return seen["ctx"]

    monkeypatch.setattr(cellrun.Run, "layer_context", keep)
    run = cellrun.Run(cellrun.load_spec(), CELL, 2**31 + 31, 2.0, True, "tiny",
                      time.monotonic(), need_chip=False)
    result = run.execute()
    window = seen["ctx"]["window"]
    pages = window["before"]["metrics"], window["after"]["metrics"]
    verbs = len(window["verbs"])
    assert verbs >= 1 and result["failed"] == 0
    assert promtext.delta(*pages, "SeaweedFS_volume_ec_decode_seconds_count") == verbs
    busy = promtext.delta(*pages, "SeaweedFS_volume_ec_pipeline_seconds_sum", **BUSY)
    assert result["metrics"][NAME]["value"] == busy / verbs > 0
