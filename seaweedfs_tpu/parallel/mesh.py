"""Device mesh helpers."""

from __future__ import annotations


def make_mesh(n_devices: int | None = None, axis: str = "dp"):
    """1-D mesh over the first n devices (default: all). Storage workloads
    shard the volume-batch dimension only, so a single `dp` axis suffices;
    multi-host meshes lay DCN on the outer factor automatically."""
    from seaweedfs_tpu.ops import device

    jax = device.jax()
    from jax.sharding import Mesh

    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, have {len(devices)}"
            )
        devices = devices[:n_devices]
    import numpy as np

    return Mesh(np.array(devices), (axis,))
