"""The phase clock inside the server (PR 26): `stats.trace.phase`, the bridge
from spans and phases to the device trace, the spans of one verb under one
id, and the counters that split a verb, the write stage and a degraded read
where the work happens."""

from __future__ import annotations

import io
import os
import subprocess
import sys
import tarfile
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.stats import profiler, trace
from seaweedfs_tpu.stats.metrics import default_registry, parse_exposition

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _samples(text: str) -> dict:
    """{(name, sorted label pairs): value} of an exposition page."""
    return {(n, tuple(sorted(labels.items()))): v
            for n, labels, v in parse_exposition(text)}


def _grown(before: dict, after: dict, name: str, **labels: str) -> float:
    want = set(labels.items())
    return sum(v - before.get(k, 0.0) for k, v in after.items()
               if k[0] == name and want <= set(k[1]))


def _label_values(page: dict, name: str, label: str) -> set[str]:
    return {dict(k[1]).get(label) for k in page if k[0] == name}


# --- (a) one clock: phases and spans in the device trace -----------------------
@pytest.fixture(scope="module")
def traced_sections():
    """A phase, a span and a kernel span run while `device_trace` is on (CPU
    backend), each with its `time.time()` bounds, and the trace's events."""
    from jax.profiler import ProfileData

    blob: list[bytes] = []
    tracer = threading.Thread(
        target=lambda: blob.append(profiler.device_trace(1.0)))
    tracer.start()
    deadline = time.time() + 60
    while trace._annotation is None and time.time() < deadline:
        time.sleep(0.005)
    assert trace._annotation is not None, "the device trace never started"
    bounds = {}

    def timed(name, cm):
        t0 = time.time()
        with cm:
            time.sleep(0.02)
        bounds[name] = (t0, time.time())

    timed("t.phase", trace.phase("t.phase", trace.EC_DEVICE_SECONDS, "h2d", 7))
    timed("t.bare", trace.phase("t.bare"))
    timed("t.span", trace.span("t.span", role="test"))
    timed("t.kernel", trace.kernel_span(
        "t.kernel", trace.EC_DECODE_SECONDS, "t-kernel"))
    tracer.join(120)
    assert not tracer.is_alive() and blob
    assert trace._annotation is None
    with tarfile.open(fileobj=io.BytesIO(blob[0]), mode="r:gz") as tf:
        member = next(m for m in tf.getmembers() if m.name.endswith(".xplane.pb"))
        profile = ProfileData.from_serialized_xspace(tf.extractfile(member).read())
    began = next(float(dict(p.stats)["profile_start_time"]) * 1e-9
                 for p in profile.planes if "profile_start_time" in dict(p.stats))
    events = {}
    python_frames = 0
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in bounds:
                    events[ev.name] = (began + ev.start_ns * 1e-9,
                                       ev.duration_ns * 1e-9)
                # the Python tracer's events: "$file.py:line function"
                python_frames += ev.name.startswith("$")
    return {"bounds": bounds, "events": events, "python_frames": python_frames}


@pytest.mark.parametrize("name", ["t.phase", "t.bare", "t.span", "t.kernel"])
def test_section_lies_in_the_device_trace_on_the_wall_clock(traced_sections, name):
    assert name in traced_sections["events"], sorted(traced_sections["events"])
    start, seconds = traced_sections["events"][name]
    t0, t1 = traced_sections["bounds"][name]
    assert t0 - 0.005 <= start <= t1 + 0.005
    assert 0.015 <= seconds <= (t1 - t0) + 0.005


def test_device_trace_leaves_the_python_tracer_off(traced_sections):
    # with it on every Python call of the second is an event (megabytes on a
    # busy server). Counted by name, not by the archive's size: the archive
    # also holds the metadata of every XLA program the process has loaded,
    # half a megabyte when tests/test_hash_kernels.py ran in it before
    assert traced_sections["python_frames"] == 0


def test_phase_starts_no_jax_while_no_device_trace_runs():
    code = (
        "import sys\n"
        "from seaweedfs_tpu.stats import trace\n"
        "with trace.phase('p', trace.EC_DEVICE_SECONDS, 'h2d', 1, cpu=True):\n"
        "    pass\n"
        "with trace.span('s', role='shell'):\n"
        "    pass\n"
        "import seaweedfs_tpu.shell.shell\n"
        "assert trace._annotation is None\n"
        "print('jax' in sys.modules)\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split()[-1] == "False"


def test_phase_counts_seconds_bytes_and_cpu_only_on_a_clean_exit():
    fam, kernel = trace.EC_DECODE_SECONDS, "t-phase-unit"
    with trace.phase("u", fam, nbytes=5, cpu=True) as ph:
        sum(range(20000))
        ph.kernel = kernel  # known only mid-flight
    with pytest.raises(ValueError):
        with trace.phase("u", fam, kernel, nbytes=5, cpu=True):
            raise ValueError("no sample for a failed section")
    page = _samples(default_registry().render())
    lab = (("kernel", kernel),)
    assert page[("SeaweedFS_volume_ec_decode_seconds_count", lab)] == 1
    assert page[("SeaweedFS_volume_ec_decode_bytes_total", lab)] == 5
    assert page[("SeaweedFS_volume_ec_decode_cpu_seconds_total", lab)] > 0


# --- (b) pipeline stages as children of the encode span -------------------------
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_pipeline_stages_are_children_of_the_encode_span(tmp_path, backend):
    from seaweedfs_tpu.ops.rs_kernel import RSCodec
    from seaweedfs_tpu.server.httpd import MetricsService, get_json
    from seaweedfs_tpu.storage.erasure_coding import encoder

    base = str(tmp_path / "1")
    payload = np.random.RandomState(5).randint(
        0, 256, size=50_000, dtype=np.uint8).tobytes()
    with open(base + ".dat", "wb") as f:
        f.write(payload)
    with trace.span("t.root", role="test") as root:
        encoder.write_ec_files(
            base, codec=RSCodec(backend=backend),
            large_block_size=10000, small_block_size=100, batch=1000,
        )
    svc = MetricsService(port=0)
    svc.serve_debug_routes()
    svc.start()
    try:
        out = get_json(f"{svc.url}/debug/traces?id={root.trace_id}")
    finally:
        svc.stop()
    spans = out["spans"]
    encode = next(s for s in spans if s["name"] == "ec.encode")
    assert encode["parent_id"] == root.span_id
    stages = [s for s in spans if s["name"].startswith("ec.pipeline.")]
    assert {s["name"] for s in stages} == {
        "ec.pipeline.read", "ec.pipeline.encode", "ec.pipeline.write"}
    assert {s["parent_id"] for s in stages} == {encode["span_id"]}
    assert len({s["attrs"]["thread"] for s in stages}) == 3
    per_stage = {n: [s for s in stages if s["name"] == n]
                 for n in {s["name"] for s in stages}}
    counts = {len(v) for v in per_stage.values()}
    assert len(counts) == 1 and counts.pop() >= 2  # every batch, every stage
    for group in per_stage.values():
        assert sorted(s["attrs"]["batch"] for s in group) == list(range(len(group)))
        assert sum(s["attrs"]["bytes"] for s in group) >= len(payload)


# --- (c), (d) a scripted verb against an in-process cluster -----------------------
@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """master + one volume server with the EC pipeline on the jax backend
    (the CPU's), a few blobs, `lock; ec.encode; unlock` through the shell,
    one shard dropped, every blob read back degraded. Pages and `/status`
    from before and after."""
    from seaweedfs_tpu.server.httpd import get_json, http_request, post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.shell.shell import run_shell

    mp = pytest.MonkeyPatch()
    mp.setenv("SEAWEEDFS_TPU_EC_BACKEND", "jax")
    tmp = tmp_path_factory.mktemp("sealed")
    master = MasterServer(port=0, pulse_seconds=1, volume_size_limit_mb=64)
    master.start()
    vs = VolumeServer([str(tmp / "v0")], master.url, port=0, pulse_seconds=1,
                      max_volume_count=10)
    vs.start()
    try:
        blobs = {}
        vid = None
        for i in range(200):
            a = get_json(f"{master.url}/dir/assign")
            if vid is None:
                vid = int(a["fid"].split(",")[0])
            if int(a["fid"].split(",")[0]) != vid:
                continue
            blobs[a["fid"]] = os.urandom(30_000)
            st, _, _ = http_request(
                "POST", f"http://{a['publicUrl']}/{a['fid']}", blobs[a["fid"]])
            assert st == 201
            if len(blobs) >= 6:
                break
        before = _samples(get_json_text(vs.url + "/metrics"))
        t0 = time.time()
        out = io.StringIO()
        rc = run_shell(master.url, script=f"lock; ec.encode -volumeId {vid}; unlock",
                       out=out)
        t1 = time.time()
        assert rc == 0 and "shards spread" in out.getvalue(), out.getvalue()
        sealed_page = _samples(get_json_text(vs.url + "/metrics"))
        removed = post_json(f"{vs.url}/admin/ec/delete_shards",
                            {"volume": vid, "collection": "", "shards": [0]})
        assert removed["removed"] == [0]
        for fid, data in blobs.items():
            st, _, body = http_request("GET", f"{vs.url}/{fid}")
            assert st == 200 and body == data
        after = _samples(get_json_text(vs.url + "/metrics"))
        yield {"before": before, "sealed": sealed_page, "after": after,
               "status": get_json(vs.url + "/status"), "verb": (t0, t1)}
    finally:
        vs.stop()
        master.stop()
        mp.undo()


def get_json_text(url: str) -> str:
    from seaweedfs_tpu.server.httpd import http_request

    status, _, body = http_request("GET", url)
    assert status == 200
    return body.decode()


def test_spans_of_one_verb_share_the_shell_roots_id(sealed):
    t0, t1 = sealed["verb"]
    ring = [s for t in trace.collector().traces(limit=10_000) for s in t["spans"]
            if t0 <= s["start"] <= t1]
    roots = [s for s in ring if s["name"] == "shell ec.encode"]
    assert len(roots) == 1 and roots[0]["role"] == "shell"
    verb = trace.collector().trace_spans(roots[0]["trace_id"])
    names = {s["name"] for s in verb}
    assert {"POST /admin/volume/readonly", "POST /admin/ec/generate",
            "POST /admin/ec/mount", "POST /admin/ec/delete_volume",
            "ec.encode", "ec.pipeline.read", "ec.pipeline.encode",
            "ec.pipeline.write"} <= names
    # every /admin/ec/* request made while the verb ran is the verb's
    admin = [s for s in ring if s["name"].startswith("POST /admin/ec/")]
    assert admin and {s["trace_id"] for s in admin} == {roots[0]["trace_id"]}
    # the lock and unlock lines are verbs of their own
    assert {"shell lock", "shell unlock"} <= {s["name"] for s in ring}


FAMILIES = [
    ("SeaweedFS_volume_ec_admin_seconds_count", "op",
     {"readonly", "generate", "mount", "delete_volume", "delete_shards",
      "generate.quiesce", "generate.encode", "generate.ecx", "generate.vif"}),
    ("SeaweedFS_volume_ec_device_seconds_count", "kernel",
     {"h2d", "dispatch", "d2h-wait"}),
    ("SeaweedFS_volume_ec_device_bytes_total", "kernel", {"h2d", "d2h-wait"}),
    ("SeaweedFS_volume_ec_device_programs_total", None, None),
    ("SeaweedFS_volume_ec_decode_cpu_seconds_total", "kernel", None),
    # every blob lies in the dropped shard's first block; the other rungs'
    # labels are held to the layout in tests/test_ec_read_intervals.py
    ("SeaweedFS_volume_ec_read_interval_bytes_total", "source", {"reconstruct"}),
    ("SeaweedFS_http_request_cpu_seconds_total", "role", {"volume", "master"}),
    ("SeaweedFS_http_request_cpu_seconds_total", "method", {"GET", "POST"}),
    ("SeaweedFS_process_cpu_seconds_total", None, None),
]


@pytest.mark.parametrize("name,label,values", FAMILIES,
                         ids=[f"{f[0]}-{f[1]}" for f in FAMILIES])
def test_family_is_on_the_metrics_page_with_its_labels(sealed, name, label, values):
    page = sealed["after"]
    assert any(k[0] == name for k in page), name
    assert _grown(sealed["before"], page, name) > 0
    if values is not None:
        assert values <= _label_values(page, name, label)
    elif label is not None:  # whatever reconstructs here, under its own label
        grew = {dict(k[1])[label] for k, v in page.items()
                if k[0] == name and v > sealed["before"].get(k, 0.0)}
        assert grew and all(v.startswith("reconstruct-") for v in grew)


def test_admin_ops_are_declared(sealed):
    ops = _label_values(sealed["after"], "SeaweedFS_volume_ec_admin_seconds_count", "op")
    assert ops <= set(trace.EC_ADMIN_OPS)
    kernels = _label_values(sealed["after"], "SeaweedFS_volume_ec_device_seconds_count",
                            "kernel")
    assert kernels <= set(trace.EC_DEVICE_KERNELS)


def test_the_pieces_add_up(sealed):
    b, a = sealed["before"], sealed["sealed"]

    def admin(op):
        return _grown(b, a, "SeaweedFS_volume_ec_admin_seconds_sum", op=op)

    def device(kernel):
        return _grown(b, a, "SeaweedFS_volume_ec_device_seconds_sum", kernel=kernel)

    def stage(name):
        return _grown(b, a, "SeaweedFS_volume_ec_pipeline_seconds_sum",
                      stage=name, state="busy")

    steps = sum(admin("generate." + s) for s in ("quiesce", "encode", "ecx", "vif"))
    assert 0 < steps <= admin("generate")
    assert device("d2h-wait") <= stage("write")
    assert device("h2d") + device("dispatch") <= stage("encode")
    assert admin("generate.encode") >= stage("write")
    # the pipeline family got no new stage or state
    fam = "SeaweedFS_volume_ec_pipeline_seconds_count"
    assert _label_values(a, fam, "stage") <= {
        "read", "encode", "write", "fused", "online"}
    assert _label_values(a, fam, "state") <= {"busy", "wait"}


def test_reconstruct_cpu_is_within_its_requests_cpu(sealed):
    b, a = sealed["sealed"], sealed["after"]
    decode_cpu = _grown(b, a, "SeaweedFS_volume_ec_decode_cpu_seconds_total")
    decode_wall = sum(
        v - b.get(k, 0.0) for k, v in a.items()
        if k[0] == "SeaweedFS_volume_ec_decode_seconds_sum"
        and dict(k[1])["kernel"].startswith("reconstruct-"))
    request_cpu = _grown(b, a, "SeaweedFS_http_request_cpu_seconds_total",
                         role="volume", method="GET")
    assert 0 < decode_cpu <= request_cpu
    assert decode_cpu <= decode_wall * 1.05 + 0.005


class _FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("stats,want", [
    ([None], None),  # the CPU backend reports none
    ([{"bytes_in_use": 5, "peak_bytes_in_use": 9, "bytes_limit": 100, "x": 1},
      {"bytes_in_use": 7, "peak_bytes_in_use": 8, "bytes_limit": 100}],
     {"bytes_in_use": 5, "peak_bytes_in_use": 9, "bytes_limit": 100}),
    ([{"peak_bytes_in_use": 3}], {"peak_bytes_in_use": 3}),
], ids=["backend-reports-none", "fullest-of-two", "only-what-is-reported"])
def test_memory_of_the_fullest_device(stats, want):
    from seaweedfs_tpu.ops import device

    assert device._fullest_memory([_FakeDevice(s) for s in stats]) == want


def test_status_has_memory_exactly_when_jax_is_started_and_reports_it(
        sealed, monkeypatch):
    from seaweedfs_tpu.ops import device

    ec = sealed["status"]["ec"]
    assert ec["jax"]["platform"] == "cpu" and "memory" not in ec  # none on the CPU
    fake = [_FakeDevice({"bytes_in_use": 1, "peak_bytes_in_use": 2,
                         "bytes_limit": 3})]
    monkeypatch.setattr(device._jax, "local_devices", lambda: fake)
    assert device.report()["memory"] == {
        "bytes_in_use": 1, "peak_bytes_in_use": 2, "bytes_limit": 3}
    monkeypatch.setattr(device, "_jax", None)  # a process that never started jax
    assert "memory" not in device.report() and "jax" not in device.report()
    assert "kernel_shapes" not in device.report()


def test_status_counts_the_kernel_shapes_built(sealed):
    """`ec.kernel_shapes`: the programs the RS transform has run since boot,
    one per coefficient matrix and width. Here at least the pipeline's encode
    and one reconstruct of the dropped shard; six 30 KB blobs reach the
    kernel at a handful of rungs, never one per length."""
    from seaweedfs_tpu.ops import device

    shapes = sealed["status"]["ec"]["kernel_shapes"]
    assert isinstance(shapes, int) and 2 <= shapes <= len(device._kernel_shapes)
