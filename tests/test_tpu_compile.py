"""Ask the TPU v5e compiler, with no chip attached, for the device programs of
the served path at the widths it really runs (on-chip-measurement guide,
section 2, rehearsal 3). A compile that passes is not a run: it says nothing
about results or times, only that the chip's compiler takes the program and
that it fits.

Everything that touches the TPU library happens inside the module-scoped
fixtures below, never at import: under several workers only the worker that
is handed this file may load it. All such tests stay in this one file.
"""

from __future__ import annotations

import functools
import os

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256

DATA, PARITY = 10, 4
MiB = 1024 * 1024
# the pipeline's device batch: encoder.DEFAULT_BATCH_DEVICE bytes per shard
DEVICE_BATCH = 32 * MiB


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    import jax

    return jax.jit(fn).lower(*shapes).compile()


def _u8(shape, sharding):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=sharding)


@pytest.fixture()
def pallas_door(monkeypatch):
    """`ops.rs_kernel` with its door over the Pallas body, as on a TPU: here
    `jax.default_backend()` is the CPU, so the test steers the choice."""
    from seaweedfs_tpu.ops import rs_kernel

    monkeypatch.setattr(rs_kernel, "transform_kernel", lambda: "pallas")
    return rs_kernel


def _rebuild_matrix(missing: tuple[int, ...]) -> np.ndarray:
    present = tuple(i for i in range(DATA + PARITY) if i not in missing)
    return gf256.decode_matrix(DATA, PARITY, present, missing)


PALLAS_CASES = [
    # (id, matrix, columns)
    ("encode-4x10-device-batch", gf256.parity_rows(DATA, PARITY), DEVICE_BATCH),
    ("encode-4x10-last-batch", gf256.parity_rows(DATA, PARITY), 7 * MiB),
    ("encode-4x10-not-tile-multiple", gf256.parity_rows(DATA, PARITY),
     MiB + 4321),
    ("rebuild-1x10", _rebuild_matrix((3,)), DEVICE_BATCH),
    ("rebuild-2x10", _rebuild_matrix((3, 12)), DEVICE_BATCH),
    ("rebuild-3x10", _rebuild_matrix((0, 3, 12)), DEVICE_BATCH),
    ("rebuild-4x10", _rebuild_matrix((0, 3, 11, 12)), DEVICE_BATCH),
    # degraded read of a 1 MiB needle: one interval, one missing shard
    ("reconstruct-1x10-interval", _rebuild_matrix((3,)), MiB + 40),
    # degraded read of a 4 MiB chunk needle: one whole small block, the top
    # rung of the door's ladder; and a rung in its middle
    ("reconstruct-1x10-block", _rebuild_matrix((3,)), MiB),
    ("reconstruct-1x10-rung-96k", _rebuild_matrix((3,)), 96 * 1024),
    # partial-sum repair hops: a holder's few columns of the decode matrix
    ("partial-sum-1x1", _rebuild_matrix((3,))[:, :1], 4 * MiB),
    ("partial-sum-1x3", _rebuild_matrix((3,))[:, :3], 4 * MiB),
    ("partial-sum-2x7", _rebuild_matrix((3, 12))[:, :7], 4 * MiB),
]


@pytest.mark.parametrize(
    "matrix,n", [c[1:] for c in PALLAS_CASES], ids=[c[0] for c in PALLAS_CASES]
)
def test_pallas_transform_compiles(one_chip, pallas_door, matrix, n):
    compiled = _compile(
        functools.partial(pallas_door.gf_matmul_jax, matrix),
        _u8((matrix.shape[1], n), one_chip),
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%rs_gf_matmul" in text  # the name the device trace shows


def test_pipeline_encode_rows_compiles(one_chip, pallas_door):
    """The device half of RSCodec.encode_rows_async at the pipeline's batch:
    32 rows of 10 x 1 MiB in .dat order -> reshape/transpose -> kernel."""
    rows, block = DEVICE_BATCH // MiB, MiB
    m = gf256.parity_rows(DATA, PARITY)

    def device_half(flat):
        x = flat.reshape(rows, DATA, block).transpose(1, 0, 2)
        return pallas_door.gf_matmul_jax(m, x.reshape(DATA, -1))

    compiled = _compile(device_half, _u8((rows * DATA * block,), one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 8 * 1024**3  # half of a v5e's 16 GB, for one batch in flight


def test_xla_transform_compiles(one_chip):
    from seaweedfs_tpu.ops import rs_kernel

    m = gf256.parity_rows(DATA, PARITY)
    fn = rs_kernel._compiled_xla(PARITY, DATA, m.tobytes(), rs_kernel.TILE)
    fn.lower(_u8((DATA, 4 * MiB), one_chip)).compile()


@pytest.mark.parametrize("kernel", ["md5", "crc32c"])
def test_hash_kernel_compiles(one_chip, kernel):
    """BASELINE config 3 shape: a batch of 8192 blobs of 4 KB."""
    from seaweedfs_tpu.ops import crc32c_kernel, md5_kernel

    mod = md5_kernel if kernel == "md5" else crc32c_kernel
    mod._compiled_batch(4096).lower(_u8((8192, 4096), one_chip)).compile()
