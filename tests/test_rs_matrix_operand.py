"""The coefficient matrix as an operand of the RS door's device program.

The door (`ops/rs_kernel._enqueue`) runs one program per (rows, cols), width
and device, whatever the matrix: the matrix's bit form reaches the program as
an operand, put on the program's device once (`rs_kernel._operand`) and kept
in `ops/device.matrices`. Held here on the CPU, over the XLA form and over
the Pallas form (interpreted, as in `tests/test_rs_pallas.py`):

  * several different matrices at one width give the numpy oracle's bytes
    and build one program;
  * each new matrix is one put, counted under
    `SeaweedFS_volume_ec_door_matrix_puts_total{device}`, and a matrix seen
    again puts nothing;
  * a device array's matrix is put on that array's device, once per device
    (four CPU devices, in a child process: a process keeps its device count);
  * inside a caller's own jit the matrix is a constant and nothing is put;
  * the store of matrices is bounded.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from seaweedfs_tpu.ops import device, gf256, rs_kernel, rs_pallas
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.metrics import default_registry
from tests.test_trace_phases import _grown, _samples

ROWS, COLS = 3, 7        # a shape no other file's door calls use
PALLAS_TILE = 512        # interpret mode walks the grid in Python
MATRICES = 5


def random_matrices(seed: int, count: int = MATRICES) -> list[np.ndarray]:
    rng = np.random.Generator(np.random.SFC64([44, seed]))
    return [rng.integers(0, 256, size=(ROWS, COLS), dtype=np.uint8)
            for _ in range(count)]


def page() -> dict:
    return _samples(default_registry().render())


@pytest.fixture(params=["xla", "pallas"])
def form(request, monkeypatch):
    """(body, its jitted function, tile): the door over one form."""
    if request.param == "pallas":
        from jax.experimental import pallas as pl

        monkeypatch.setattr(pl, "pallas_call",
                            functools.partial(pl.pallas_call, interpret=True))
        monkeypatch.setattr(rs_kernel, "transform_kernel", lambda: "pallas")
        monkeypatch.setattr(rs_kernel, "TILE", PALLAS_TILE)
        rs_pallas.compiled.cache_clear()
        yield "pallas", rs_pallas.compiled(ROWS, COLS, PALLAS_TILE), PALLAS_TILE
        rs_pallas.compiled.cache_clear()
    else:
        yield "xla", rs_kernel._compiled_xla(ROWS, COLS), rs_kernel.TILE


def test_several_matrices_at_one_width_share_one_program(form):
    body, fn, tile = form
    n = 3 * tile - 5
    width = rs_kernel.ladder_width(n, tile)
    rng = np.random.Generator(np.random.SFC64([44, 0]))
    data = rng.integers(0, 256, size=(COLS, n), dtype=np.uint8)
    programs, shapes = fn._cache_size(), set(device._kernel_shapes)
    before = page()
    matrices = random_matrices(1 if body == "xla" else 2)
    for m in matrices:
        got = rs_kernel._apply_jax(m, data)
        assert np.array_equal(got, gf256.gf_matmul_bytes(m, data))
    assert fn._cache_size() - programs == 1
    assert set(device._kernel_shapes) - shapes <= {(ROWS, COLS, width, 0)}
    assert (ROWS, COLS, width, 0) in device._kernel_shapes
    puts = _grown(before, page(), trace.EC_DOOR_MATRIX_PUTS, device="0")
    assert puts == len(matrices)
    assert all((m.tobytes(), body, 0) in device.matrices for m in matrices)
    # the same matrices again, and at another width: no put, no new matrix
    held, before = len(device.matrices), page()
    for m in matrices:
        for k in (n, tile + 1):
            got = rs_kernel._apply_jax(m, data[:, :k])
            assert np.array_equal(got, gf256.gf_matmul_bytes(m, data[:, :k]))
    assert _grown(before, page(), trace.EC_DOOR_MATRIX_PUTS) == 0
    assert len(device.matrices) == held == device.report()["matrices"]


def test_the_put_is_timed_as_its_own_device_phase():
    before = page()
    (m,) = random_matrices(3, count=1)
    rs_kernel._apply_jax(m, np.ones((COLS, 40), dtype=np.uint8))
    after = page()
    count = "SeaweedFS_volume_ec_device_seconds_count"
    assert _grown(before, after, count, kernel="matrix-put") == 1
    assert _grown(before, after, count, kernel="dispatch") == 1


def test_inside_a_callers_jit_the_matrix_is_a_constant_and_nothing_is_put():
    jax = device.jax()
    (m,) = random_matrices(4, count=1)
    rng = np.random.Generator(np.random.SFC64([44, 4]))
    data = rng.integers(0, 256, size=(COLS, 4096), dtype=np.uint8)
    words = jax.device_put(data.view(np.uint32))
    before, held = page(), len(device.matrices)
    out = jax.jit(lambda w: rs_kernel.gf_matmul_jax(m, w))(words)
    got = rs_kernel._JaxHandle(out, 4096).result()
    assert np.array_equal(got, gf256.gf_matmul_bytes(m, data))
    assert _grown(before, page(), trace.EC_DOOR_MATRIX_PUTS) == 0
    assert len(device.matrices) == held


def test_the_store_of_matrices_is_bounded(monkeypatch):
    monkeypatch.setattr(device, "matrices", {})
    monkeypatch.setattr(device, "MATRICES_HELD", 2)
    data = np.arange(COLS * 64, dtype=np.uint32).astype(np.uint8).reshape(COLS, 64)
    first, *rest = random_matrices(5, count=3)
    for m in (first, *rest):
        rs_kernel._apply_jax(m, data)
    assert len(device.matrices) == 2
    assert (first.tobytes(), "xla", 0) not in device.matrices  # the oldest went
    before = page()
    got = rs_kernel._apply_jax(first, data)
    assert np.array_equal(got, gf256.gf_matmul_bytes(first, data))
    assert _grown(before, page(), trace.EC_DOOR_MATRIX_PUTS) == 1


CHILD = """
import json
import numpy as np
from seaweedfs_tpu.ops import device, gf256, rs_kernel
from seaweedfs_tpu.stats.metrics import default_registry
from tests.test_trace_phases import _grown, _samples

jax = device.jax()
m = gf256.decode_matrix(10, 4, (1, 2, 3, 5, 6, 7, 9, 10, 11, 13), (0, 4, 8))
data = np.random.Generator(np.random.SFC64([44, 5])).integers(
    0, 256, size=(10, 8192), dtype=np.uint8)
want = gf256.gf_matmul_bytes(m, data)
devices = jax.local_devices()
rounds = []
for _ in range(2):
    before, same = _samples(default_registry().render()), []
    for d in devices:
        out = rs_kernel.gf_matmul_jax(m, jax.device_put(data.view(np.uint32), d))
        same.append(next(iter(out.devices())) == d and bool(np.array_equal(
            rs_kernel._JaxHandle(out, 8192).result(), want)))
    after = _samples(default_registry().render())
    rounds.append({
        "same": same,
        "puts": {str(d.id): _grown(before, after,
                                   "SeaweedFS_volume_ec_door_matrix_puts_total",
                                   device=str(d.id)) for d in devices}})
print(json.dumps({"rounds": rounds, "devices": len(devices),
                  "held": sorted(k[2] for k in device.matrices),
                  "shapes": sorted(device._kernel_shapes)}))
"""


def test_a_device_arrays_matrix_is_put_on_its_device_once():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", CHILD], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4
    first, second = got["rounds"]
    assert first["same"] == second["same"] == [True] * 4
    assert first["puts"] == {str(d): 1 for d in range(4)}
    assert second["puts"] == {str(d): 0 for d in range(4)}
    assert got["held"] == [0, 1, 2, 3]
    assert got["shapes"] == [[3, 10, 8192, d] for d in range(4)]
