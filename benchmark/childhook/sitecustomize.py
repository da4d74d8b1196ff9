"""Put on the server child's PYTHONPATH by benchmark/benchlib/cluster.py.

The chip belongs to the server process, so only it can read the device's
memory statistics, and the program has no route for them yet. On SIGUSR1 this
writes `memory_stats()` of each local device to the file named in
BENCH_MEMSTAT_FILE. It reads an allocator's counters and changes nothing; it
never starts jax or a backend that the program has not started itself.
"""

import json
import os
import signal
import sys


def _dump(signum, frame):
    path = os.environ.get("BENCH_MEMSTAT_FILE")
    if not path:
        return
    out = {"devices": []}
    jax = sys.modules.get("jax")
    try:
        if jax is not None:
            from jax._src import xla_bridge

            if xla_bridge.backends_are_initialized():
                out["devices"] = [d.memory_stats() or {} for d in jax.local_devices()]
    except Exception as e:  # noqa: BLE001 - a probe must not take the server down
        out["error"] = repr(e)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, path)


if os.environ.get("BENCH_MEMSTAT_FILE") and hasattr(signal, "SIGUSR1"):
    signal.signal(signal.SIGUSR1, _dump)
