"""chip_smoke.py's contract with whoever runs it: the last line of stdout.

On a chip the line is `{"ok": true, "device": {...}}`; here, on the CPU, a
rehearsal at the tiny size must run every phase and still end `"ok": false`
with a non-zero exit — the script never passes without a TPU.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke


def test_result_line_has_exactly_the_contracts_keys():
    line = chip_smoke.result_line(True, "tpu", "TPU v5 lite", 1)
    assert "\n" not in line
    parsed = json.loads(line)
    assert list(parsed) == ["ok", "device"]
    assert list(parsed["device"]) == ["platform", "kind", "count"]
    assert parsed == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    failing = json.loads(chip_smoke.result_line(False, "cpu", "cpu", 8))
    assert failing == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 8},
    }


def test_kernel_bytes_reads_one_family():
    text = "\n".join([
        'SeaweedFS_volume_ec_encode_bytes_total{kernel="pipeline-pallas"} 1.07374e+09',
        'SeaweedFS_volume_ec_encode_bytes_total{kernel="fused"} 0',
        'SeaweedFS_volume_ec_decode_bytes_total{kernel="rebuild-pallas"} 5',
        'SeaweedFS_volume_ec_encode_seconds_sum{kernel="fused"} 2',
    ])
    got = chip_smoke.kernel_bytes(text, "SeaweedFS_volume_ec_encode")
    assert got == {"pipeline-pallas": 1.07374e9, "fused": 0.0}
    assert chip_smoke.approx(got["pipeline-pallas"], 1073741824 + 8)
    assert not chip_smoke.approx(got["fused"], 1073741824)


def test_cpu_rehearsal_runs_every_phase_and_fails(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--seed", "3",
         "--size", "tiny"],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode not in (0, 2), proc.stderr[-2000:]
    assert proc.stdout.endswith("\n") and not proc.stdout.endswith("\n\n")
    lines = proc.stdout.splitlines()
    # nothing follows the last line, and it is the failing form of the contract
    last = json.loads(lines[-1])
    assert list(last) == ["ok", "device"] and last["ok"] is False
    assert list(last["device"]) == ["platform", "kind", "count"]
    assert last["device"]["platform"] == "cpu"
    phases = {}
    for line in lines[:-1]:
        obj = json.loads(line)  # every stdout line is one JSON object
        phases[obj["phase"]] = obj
    assert {"setup", "A", "B", "calibration", "failures"} <= set(phases)
    # every phase ran to its end and every comparison of bytes held: what
    # fails on the CPU is only where the bytes ran
    a, b = phases["A"], phases["B"]
    assert phases["setup"]["native"]["loaded"] is True
    assert a["shards_identical_to_host_reference"] == 14
    assert a["oracle"]["tail_row_checked"] and a["oracle"]["rows_checked"] >= 2
    assert a["server"]["pipeline"] == {"backend": "jax", "chosen_by": "override"}
    assert a["server"]["jax"]["platform"] == "cpu"
    # the child took the cache directory from the environment, unchanged
    assert a["server"]["compile_cache"]["dir"] == str(tmp_path / "jax_cache")
    assert a["server"]["compile_cache"]["source"] == "env"
    assert os.listdir(tmp_path / "jax_cache")
    assert a["encode_bytes_by_kernel"] == {"pipeline-xla": a["dat_bytes"]}
    assert a["server_exit_code"] == 0
    assert b["hash_bytes_by_kernel"] == {"batch-jax": b["blobs"] * 4096}
    assert b["compile_requests"] <= b["compile_request_bound"]
    for failure in phases["failures"]["failures"]:
        assert "tpu" in failure or "pallas" in failure or "host kernel" in failure, failure
