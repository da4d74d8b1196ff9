"""The trace reduction on a small synthetic `.xplane.pb`, written here field
by field in protobuf's wire format (XSpace > XPlane > XLine > XEvent)."""

import io
import tarfile

import pytest

from benchlib import xplane


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def plane(name: str, lines: dict[str, list[tuple[str, int, int]]]) -> bytes:
    """lines: {line name: [(event name, start ns, duration ns)]}."""
    names = sorted({ev for evs in lines.values() for ev, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    body = field(2, name)
    for i, (line_name, evs) in enumerate(lines.items()):
        line = field(1, i + 1) + field(2, line_name) + field(3, 0)
        for ev, start_ns, dur_ns in evs:
            # XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3
            line += field(4, field(1, ids[ev]) + field(2, start_ns * 1000)
                          + field(3, dur_ns * 1000))
        body += field(3, line)
    for n, i in ids.items():
        # map<int64, XEventMetadata> event_metadata = 4
        body += field(4, field(1, i) + field(2, field(1, i) + field(2, n)))
    return body


def task_environment(start_ns: int, stop_ns: int) -> bytes:
    """The plane in which the profiler says when it began and ended."""
    body = field(2, "Task Environment")
    for i, (name, value) in enumerate(
            [("profile_start_time", start_ns), ("profile_stop_time", stop_ns)], 1):
        body += field(5, field(1, i) + field(2, field(1, i) + field(2, name)))
        body += field(6, field(1, i) + field(3, value))   # XStat.uint64_value
    return body


def space(planes: list[bytes]) -> bytes:
    return b"".join(field(1, p) for p in planes)


@pytest.fixture
def profile():
    s = 1_000_000_000
    dev = plane("/device:TPU:0", {
        "XLA Modules": [("jit_run", 1 * s, 3 * s)],
        "XLA Ops": [("fusion.1", 1 * s, s // 2), ("kernel", 1 * s + s // 4, s),
                    ("copy", 3 * s, s // 2)],
    })
    host = plane("/host:CPU", {"python": [("f", 0, 5 * s)]})
    env = task_environment(1_700_000_000 * s, 1_700_000_005 * s)
    return xplane.load(space([host, dev, env]))


def test_reduce(profile):
    out = xplane.reduce(profile, 0.0, 5.0)
    assert out["chips"] == 1
    # fusion.1 [1, 1.5) and kernel [1.25, 2.25) overlap: union 1.25; copy 0.5
    assert out["busy_s"] == pytest.approx(1.75)
    assert out["window_s"] == 5.0
    assert out["device_ops"][0] == ["kernel", pytest.approx(1.0)]
    assert {n for n, _ in out["device_ops"]} == {"kernel", "fusion.1", "copy"}
    gaps = {n: s for n, s in out["idle_gaps"]}
    assert gaps["copy..window_end"] == pytest.approx(1.5)
    assert gaps["window_start..fusion.1"] == pytest.approx(1.0)
    assert gaps["kernel..copy"] == pytest.approx(0.75)


def test_reduce_cuts_operations_at_the_windows_ends(profile):
    out = xplane.reduce(profile, 1.25, 3.25)
    # fusion.1 [1.25, 1.5) inside kernel [1.25, 2.25): union 1.0; copy [3, 3.25)
    assert out["busy_s"] == pytest.approx(1.25)
    assert out["window_s"] == pytest.approx(2.0)
    assert dict(out["idle_gaps"]) == {"kernel..copy": pytest.approx(0.75)}


def test_profile_times(profile):
    assert xplane.profile_times(profile) == (1_700_000_000.0, 1_700_000_005.0)


def test_short_name():
    hlo = ('%run.1 = u8[4,33554432]{1,0:T(4,128)(4,1)} custom-call(s8[32,80]{1,0} '
           '%constant.1, u8[10,33554432]{1,0:T(8,128)(4,1)} %x.1), custom_call_target="x"')
    assert xplane.short_name(hlo) == "%run.1 custom-call u8[4,33554432]"
    assert xplane.short_name("kernel") == "kernel"


def test_only_the_ops_line_counts(profile):
    # the module line spans 3 s; counting it would hide the gap inside
    assert xplane.reduce(profile, 0.0, 5.0)["busy_s"] < 2.0


def test_no_device_plane_reads_nothing():
    prof = xplane.load(space([plane("/host:CPU", {"python": [("f", 0, 10)]})]))
    out = xplane.reduce(prof, 0.0, 1.0)
    assert out["chips"] == 0 and out["busy_s"] == 0.0


def test_targz_round_trip():
    raw = space([plane("/device:TPU:0", {"XLA Ops": [("k", 5, 7)]})])
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w:gz") as tf:
        info = tarfile.TarInfo("jax-trace/plugins/profile/x/host.xplane.pb")
        info.size = len(raw)
        tf.addfile(info, io.BytesIO(raw))
    assert xplane.xplane_from_targz(buf.getvalue()) == raw
