"""ops/device.py: the one door to jax — where the compile cache goes, and
what a process reports about its device side. Each case runs in a fresh
interpreter: the module keeps process-wide state by design."""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run(code: str, env_changes: dict) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_report_has_no_device_until_jax_is_started():
    out = _run(
        "import json, sys\n"
        "from seaweedfs_tpu.ops import device\n"
        "device.note_selection_failure('here', ValueError('first'))\n"
        "device.note_selection_failure('here', ValueError('second'))\n"
        "r = device.report()\n"
        "print(json.dumps({'report': r, 'jax_imported': 'jax' in sys.modules}))\n",
        {},
    )
    assert out["jax_imported"] is False
    assert out["report"] == {
        "selection_failures": {"here": "ValueError: second"}
    }


COMPILE = (
    "import json\n"
    "from seaweedfs_tpu.ops import device\n"
    "jax = device.jax()\n"
    "jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7)).block_until_ready()\n"
    "print(json.dumps({'report': device.report(),\n"
    "                  'config_dir': jax.config.jax_compilation_cache_dir,\n"
    "                  'min_secs': jax.config.jax_persistent_cache_min_compile_time_secs}))\n"
)


def test_cache_dir_from_the_environment_is_used_and_found_warm_again(tmp_path):
    cache = str(tmp_path / "cache")
    cold = _run(COMPILE, {"JAX_COMPILATION_CACHE_DIR": cache})
    assert cold["config_dir"] == cache and cold["min_secs"] == 0.0
    assert cold["report"]["compile_cache"] == {
        "dir": cache, "source": "env", "entries_at_start": 0, "warm": False,
    }
    assert cold["report"]["jax"]["platform"] == "cpu"
    assert cold["report"]["compiles"]["requests"] >= 1
    assert cold["report"]["compiles"]["cache_hits"] == 0
    assert os.listdir(cache)  # a sub-second compile was kept
    warm = _run(COMPILE, {"JAX_COMPILATION_CACHE_DIR": cache})
    assert warm["report"]["compile_cache"]["warm"] is True
    assert warm["report"]["compiles"]["cache_hits"] >= 1


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout():
    out = _run(
        "import json\n"
        "from seaweedfs_tpu.ops import device\n"
        "jax = device.jax()\n"
        "print(json.dumps({'config_dir': jax.config.jax_compilation_cache_dir,\n"
        "                  'cache_dir': device.cache_dir()}))\n",
        {"JAX_COMPILATION_CACHE_DIR": None},
    )
    assert out["config_dir"] == str(REPO / ".jax_cache")
    assert out["cache_dir"] == [str(REPO / ".jax_cache"), "checkout"]
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("platform,want", [("tpu", "pallas"), ("cpu", "xla")])
def test_one_place_decides_pallas_or_xla_by_platform(monkeypatch, platform, want):
    from seaweedfs_tpu.ops import device, rs_kernel

    monkeypatch.setattr(device, "platform", lambda: platform)
    assert rs_kernel.transform_kernel() == want
    assert rs_kernel.RSCodec(backend="jax").kernel_label == want
    assert rs_kernel.RSCodec(backend="native").kernel_label == "native"
