"""The one door through which every device path gets jax.

`jax()` imports jax on first use and places the persistent compile cache
before anything compiles: where `JAX_COMPILATION_CACHE_DIR` is set jax
reads it itself and no directory is set here; where it is not, the cache
goes to `.jax_cache/` at the root of the checkout (git-ignored; the path is
part of the cache key, so it never moves). The kernels compile in a second
or two, below jax's default threshold for keeping an entry, so the
threshold is dropped to zero.

The module also holds what a process knows about its device side, for
`GET /status` on the volume server (read by `benchmark/run.py` and the tests):
the devices jax sees (only once jax has been started), the compile
counters, how many kernel shapes the RS transform has run, and every
failure that made a backend selection skip a candidate.

A process may have several local devices. An EC pipeline that builds its own
codec borrows one for as long as it runs (`lease()`): one pipeline a device
at a time, so a chip's memory stays what one pipeline takes, and as many
pipelines at once as there are devices. Everything else (a degraded read's
reconstruct) names no device and runs on jax's default one, lease or no.
"""

from __future__ import annotations

import bisect
import contextlib
import os
import threading

from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.util import glog

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_lock = threading.Lock()
_jax = None
_cache: dict = {}
_compiles = {"requests": 0, "seconds": 0.0, "cache_hits": 0}
_selection_failures: dict[str, str] = {}
# (coefficient matrix, rows, cols, width, device) of every call the RS
# transform has made of its jitted programs. The bit matrix is baked into the
# program and a program is built per device, so each is one program built (or
# taken from the compile cache)
_kernel_shapes: set[tuple[bytes, int, int, int, int]] = set()
_leases: "Leases | None" = None


def cache_dir() -> tuple[str, str]:
    """(directory, "env" | "checkout") — where this process's compile cache
    lives and who placed it."""
    env = os.environ.get(CACHE_ENV, "")
    return (env, "env") if env else (CHECKOUT_CACHE_DIR, "checkout")


def cache_entries(path: str) -> int:
    try:
        return sum(1 for name in os.listdir(path) if not name.startswith("."))
    except OSError:
        return 0


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        with _lock:
            _compiles["requests"] += 1
            _compiles["seconds"] += seconds


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        with _lock:
            _compiles["cache_hits"] += 1


def jax():
    """The jax module, imported once with the compile cache configured."""
    global _jax
    if _jax is not None:
        return _jax
    with _lock:
        if _jax is None:
            import jax as jax_mod

            path, source = cache_dir()
            entries = cache_entries(path)
            if source == "checkout":
                jax_mod.config.update("jax_compilation_cache_dir", path)
            jax_mod.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0
            )
            jax_mod.monitoring.register_event_duration_secs_listener(
                _on_duration
            )
            jax_mod.monitoring.register_event_listener(_on_event)
            _cache.update(
                dir=path, source=source, entries_at_start=entries,
                warm=entries > 0,
            )
            _jax = jax_mod
    return _jax


def started() -> bool:
    """Whether anything in this process has started jax through this door."""
    return _jax is not None


def platform() -> str:
    """The platform jax computes on ("tpu", "cpu", ...). Starts jax."""
    return jax().default_backend()


def note_selection_failure(where: str, exc: BaseException) -> None:
    """A backend selection skipped a candidate because of `exc`: log it once
    per place with its cause, and keep it for the status report — a device
    that was dropped must not look like one that was never there."""
    cause = f"{type(exc).__name__}: {exc}"
    with _lock:
        first = where not in _selection_failures
        _selection_failures[where] = cause
    if first:
        glog.warning("backend selection: %s failed: %s", where, cause)


def note_kernel_shape(matrix: bytes, rows: int, cols: int, width: int,
                      dev: int) -> None:
    """The RS transform is about to run the program of its (rows, cols)
    coefficient matrix at `width` on the device of index `dev` (the door,
    `ops/rs_kernel._enqueue`): a set insert, on every call."""
    _kernel_shapes.add((matrix, rows, cols, width, dev))


class Leases:
    """`devices`, lent one at a time each: `lease()` hands out the free one
    of lowest index and blocks while none is free. What a device is is the
    borrower's business (the process's own pool holds `jax.local_devices()`)."""

    def __init__(self, devices) -> None:
        self._devices = tuple(devices)
        self._free = list(range(len(self._devices)))  # ascending
        self._cond = threading.Condition()
        self._granted = [0] * len(self._devices)

    @contextlib.contextmanager
    def lease(self):
        """(index, device) for the length of the `with`, given back however
        it ends. Seconds from the ask to the grant go under
        `SeaweedFS_volume_ec_device_lease_seconds{device,state="wait"}`, from
        the grant to the return under `state="held"` (a `with` left by an
        exception counts none, as every phase)."""
        with trace.phase("ec.device_lease.wait", trace.EC_LEASE_SECONDS) as ask:
            with self._cond:
                while not self._free:
                    self._cond.wait()
                index = self._free.pop(0)
                self._granted[index] += 1
            ask.kernel = (str(index), "wait")
        try:
            with trace.phase("ec.device_lease.held", trace.EC_LEASE_SECONDS,
                             (str(index), "held")):
                yield index, self._devices[index]
        finally:
            with self._cond:
                bisect.insort(self._free, index)
                self._cond.notify()

    def granted(self) -> dict[str, int]:
        """{device index: leases granted so far}, every device listed."""
        with self._cond:
            return {str(i): n for i, n in enumerate(self._granted)}


def lease():
    """Borrow one of this process's local devices (`Leases.lease` over
    `jax.local_devices()`). Starts jax."""
    global _leases
    if _leases is None:
        devices = jax().local_devices()
        with _lock:
            if _leases is None:
                _leases = Leases(devices)
    return _leases.lease()


def pipelines_at_once() -> int:
    """How many pipelines that borrow a device can run at once: one a local
    device (what `lease()` lends); one where nothing has started jax here."""
    return len(_jax.local_devices()) if started() else 1


def _fullest_memory(devices) -> dict | None:
    """`memory_stats()` of the local device whose peak is highest, cut to
    the three numbers a reader sizes a deployment by; None where the backend
    reports none (the CPU's)."""
    best = None
    for d in devices:
        stats = d.memory_stats()
        if stats and (best is None or stats.get("peak_bytes_in_use", 0)
                      > best.get("peak_bytes_in_use", 0)):
            best = stats
    if best is None:
        return None
    return {k: int(best[k]) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit") if k in best}


def report() -> dict:
    """What this process knows about its device side. `jax` and `memory`
    are absent, not guessed, when nothing in the process has started jax;
    `memory` (of the fullest local device) also where the backend reports
    none."""
    with _lock:
        out: dict = {"selection_failures": dict(_selection_failures)}
        compiles = dict(_compiles)
    if not started():
        return out
    devices = _jax.devices()
    out["jax"] = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        # which local devices have worked: leases granted so far, by index
        "leases": _leases.granted() if _leases is not None else {},
    }
    memory = _fullest_memory(_jax.local_devices())
    if memory is not None:
        out["memory"] = memory
    out["compile_cache"] = dict(_cache)
    out["compiles"] = {
        "requests": compiles["requests"],
        "cache_hits": compiles["cache_hits"],
        "seconds": round(compiles["seconds"], 3),
    }
    out["kernel_shapes"] = len(_kernel_shapes)
    return out
