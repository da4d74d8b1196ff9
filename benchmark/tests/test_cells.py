"""Each cell end to end at the tiny size on the CPU: the last line's keys, a
run that ends not `correct` for want of a chip, the comparison with the chip
check left out (sound runs pass, planted faults fail), and the control."""

import json
import os
import subprocess
import sys
import time

import pytest

from conftest import BENCH, ROOT

CELLS = ["ec1g.seal", "warm64k.degraded-read", "ec1g.repair"]
FAULT = {"ec1g.seal": "flip-shard-byte", "ec1g.repair": "flip-shard-byte",
         "warm64k.degraded-read": "corrupt-surviving-shard"}


def run_cli(workload: str, trace: int) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", workload,
         "--seed", str(2**31 + 12345), "--seconds", "2", "--trace", str(trace),
         "--size", "tiny"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "BENCH_RUN": "ignored"})


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_run_prints_the_contracts_line_and_is_not_correct(workload, trace):
    proc = run_cli(workload, trace)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in result
    assert list(result)[-1] == "checks"          # the numbers compared come last
    assert result["correct"] is False             # no chip here
    assert result["checks"]["platform_is_tpu_min"] == {"value": 0, "limit": 1}
    assert result["device"]["platform"] == "cpu"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    assert result["attempted"] > 0 and result["failed"] == 0
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    kind = "per_layer" if trace else "end_to_end"
    mine = {m["name"] for m in spec[kind] if workload in m.get("workloads", [workload])}
    assert set(result["metrics"]) <= mine
    for name, m in result["metrics"].items():
        assert isinstance(m["value"], float) and m["unit"]
    if trace:
        assert {"busy_s", "window_s"} <= set(result["device"])
        # nothing ran on a device: no share of a peak may be reported as 0
        assert not any("roofline" in n or "idle" in n for n in result["metrics"])
    else:
        assert "setup_s" in result["metrics"] and len(result["metrics"]) >= 2
    # the last lines on standard error say what was compared
    assert "must be" in proc.stderr.strip().splitlines()[-1]


def in_process(workload: str, fault: str = ""):
    from benchlib import cellrun

    run = cellrun.Run(cellrun.load_spec(), workload, 77, 2.0, False, "tiny",
                      time.monotonic(), fault=fault, need_chip=False)
    return run.execute()


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_planted_fault_is_not(workload):
    """The harness's look for a chip is skipped; the rest of a run is driven."""
    sound = in_process(workload)
    assert sound["correct"] is True, sound["checks"]
    broken = in_process(workload, fault=FAULT[workload])
    assert broken["correct"] is False
    failing = {k for k, c in broken["checks"].items()
               if c["limit"] is not None and not k.endswith("_min") and c["value"] > c["limit"]}
    assert failing & {"shard_files_differing", "reads_wrong", "reads_failed"}


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_comparison(workload, tmp_path):
    import control

    out = control.control(workload, 2**31 + 5, "tiny", str(tmp_path / "w"))
    assert out["control_correct"] is False
    assert out["reference_in_place_correct"] is True


def test_outside_a_checkout_no_result(tmp_path):
    """Only BENCHMARK.json and benchmark/: another exit code than 0, no line."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "ec1g.seal", "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path, capture_output=True, text=True)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
