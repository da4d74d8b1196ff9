"""Reduction of a profiler trace (`.xplane.pb`) to device busy time, the
device operations that took most of it, and the longest idle gaps.

The trace is taken by the process that holds the chip (the server's
`GET /debug/pprof/device`); this module only reads the file, through
`jax.profiler.ProfileData`, which starts no backend.
"""

from __future__ import annotations

import io
import re
import tarfile

from . import stats

# the line of a device plane that holds one event per executed operation;
# other lines ("XLA Modules", "Steps", ...) repeat the same time at a
# coarser grain and would hide the gaps inside a program
OPS_LINE = "XLA Ops"


def xplane_from_targz(blob: bytes) -> bytes:
    """The `.xplane.pb` inside the tar.gz the server returns."""
    with tarfile.open(fileobj=io.BytesIO(blob), mode="r:gz") as tf:
        for member in tf.getmembers():
            if member.name.endswith(".xplane.pb"):
                return tf.extractfile(member).read()
    raise ValueError("no .xplane.pb in the trace archive")


def load(serialized: bytes):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(serialized)


_OPCODE = re.compile(r"(?<![A-Za-z0-9_.%])([a-z][a-z\-]*)\(")


def short_name(name: str) -> str:
    """"%run.1 = u8[4,33554432]{...} custom-call(...)" ->
    "%run.1 custom-call u8[4,33554432]": the trace names a device operation
    by its whole HLO line."""
    lhs, sep, rest = name.partition(" = ")
    if not sep:
        return name[:100]
    op = _OPCODE.search(rest)
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{lhs} {op.group(1) if op else '?'} {shape}"[:100]


# "/device:TPU:0": a chip's own plane ("/device:CUSTOM:Megascale Trace" is not)
_CHIP_PLANE = re.compile(r"/device:[A-Za-z]+:\d+$")


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name.split(":")[1]


def profile_times(profile) -> tuple[float, float]:
    """(start, stop) of the profile in unix seconds, from the trace's own
    "Task Environment" plane; (0, 0) where the trace does not say."""
    for plane in profile.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            return (float(stats["profile_start_time"]) * 1e-9,
                    float(stats.get("profile_stop_time", 0)) * 1e-9)
    return 0.0, 0.0


def reduce(profile, lo: float, hi: float, top: int = 10) -> dict:
    """What ran on the device between `lo` and `hi`, seconds from the
    profile's start: {"chips", "busy_s" (mean over chips), "busy_s_per_chip"
    (of every chip's plane in the trace's order, 0.0 for a chip on which
    nothing ran, which the mean leaves out), "window_s" (hi -
    lo), "device_ops": [[name, seconds]...], "idle_gaps": [[what lies around
    it, seconds]...] of the first chip, "planes": what the trace held}.
    `busy_s` is the union of the operation intervals, so operations that
    overlap count once; operations are cut at the window's ends. Only device
    planes are walked: the host's lines can hold millions of events."""
    per_chip: list[list[tuple[float, float]]] = []
    every_chip: list[float] = []
    first_chip: list[tuple[float, float, str]] = []
    by_name: dict[str, float] = {}
    seen: list[str] = []
    for plane in profile.planes:
        lines = list(plane.lines)
        seen.append(f"{plane.name}: {[ln.name for ln in lines][:6]}")
        if not is_device_plane(plane.name):
            continue
        ops_lines = [ln for ln in lines if ln.name == OPS_LINE] or lines
        intervals: list[tuple[float, float]] = []
        for ln in ops_lines:
            for ev in ln.events:
                start = max(lo, ev.start_ns * 1e-9)
                end = min(hi, (ev.start_ns + ev.duration_ns) * 1e-9)
                if end > start:
                    intervals.append((start, end))
                    name = short_name(ev.name)
                    by_name[name] = by_name.get(name, 0.0) + end - start
                    if not per_chip:
                        first_chip.append((start, end, name))
        if _CHIP_PLANE.match(plane.name):
            every_chip.append(stats.union_length(intervals))
        if intervals:
            per_chip.append(intervals)
    busy = [stats.union_length(iv) for iv in per_chip]
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "chips": len(per_chip),
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "busy_s_per_chip": every_chip,
        "window_s": hi - lo,
        "device_ops": [[name, seconds] for name, seconds in ops],
        "idle_gaps": named_gaps(first_chip, lo, hi, top),
        "planes": seen,
    }


def named_gaps(ops: list[tuple[float, float, str]], lo: float, hi: float,
               top: int) -> list[list]:
    """The longest stretches of [lo, hi) in which no operation ran, each
    named by the operations on either side of it (the trace holds no host
    span that says what the host was doing meanwhile)."""
    ops = sorted(ops)
    out: list[tuple[float, str]] = []
    at, last = lo, ""
    for start, end, name in ops:
        if start > at:
            what = f"{last or 'window_start'}..{name}"
            out.append((start - at, what))
        if end > at:
            at, last = end, name
    if hi > at:
        out.append((hi - at, f"{last or 'window_start'}..window_end"))
    longest = sorted(out, key=lambda g: -g[0])[:top]
    return [[what[:200], seconds] for seconds, what in longest]
