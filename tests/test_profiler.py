"""Sampling stack profiler + EC pipeline attribution + /debug/pprof
surface + the cluster.profile shell verb (stats/profiler.py, PR 3).

Covers: Hz/seconds clamping, collapsed-stack capture and merging, the
self-measured overhead guard (<10% wall on a busy loop at 50 Hz), the
profiler/trace-ring self-metric collectors, every HTTPService role
exposing /debug/pprof/threads (tier-1), 400s on malformed query params,
per-stage busy/wait histograms from the EC pipeline, and a 3-role
cluster.profile merge.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.stats import default_registry, profiler


class TestClamping:
    def test_hz_clamped(self):
        assert profiler.SamplingProfiler(hz=10**9).hz == profiler.MAX_HZ
        assert profiler.SamplingProfiler(hz=0).hz == profiler.MIN_HZ
        assert profiler.SamplingProfiler(hz=-7).hz == profiler.MIN_HZ
        assert profiler.SamplingProfiler(hz=50).hz == 50
        assert profiler.clamp_hz("25") == 25

    def test_seconds_clamped(self):
        assert profiler.clamp_seconds(10**9) == profiler.MAX_SECONDS
        assert profiler.clamp_seconds(0) == profiler.MIN_SECONDS
        assert profiler.clamp_seconds(2.5) == 2.5

    def test_non_finite_seconds_rejected(self):
        # nan/inf parse as floats but must not silently clamp to 120s
        for bad in ("nan", "inf", "-inf", float("nan"), float("inf")):
            with pytest.raises(ValueError):
                profiler.clamp_seconds(bad)


class TestCollapsedStacks:
    def test_merge_with_role_prefix(self):
        merged: dict = {}
        profiler.merge_collapsed(merged, {"a;b": 2, "c": 1}, prefix="master")
        profiler.merge_collapsed(merged, {"a;b": 3}, prefix="master")
        profiler.merge_collapsed(merged, {"a;b": 5}, prefix="volume")
        assert merged == {"master;a;b": 5, "master;c": 1, "volume;a;b": 5}

    def test_merge_without_prefix(self):
        merged = profiler.merge_collapsed({}, {"x;y": 4})
        assert merged == {"x;y": 4}

    def test_render_collapsed_hottest_first(self):
        text = profiler.render_collapsed({"cool;path": 1, "hot;path": 9})
        assert text.splitlines() == ["hot;path 9", "cool;path 1"]

    def test_top_frames_aggregates_leaves(self):
        out = profiler.top_frames(
            {"a;b;leaf": 3, "x;leaf": 2, "y;other": 4}, n=2
        )
        assert out[0] == {"frame": "leaf", "samples": 5, "pct": 55.6}
        assert out[1] == {"frame": "other", "samples": 4, "pct": 44.4}

    def test_profile_captures_busy_thread(self):
        stop = threading.Event()

        def busy_loop_marker():
            while not stop.is_set():
                sum(range(2000))

        t = threading.Thread(target=busy_loop_marker, name="busy-bee",
                             daemon=True)
        t.start()
        try:
            out = profiler.profile(seconds=0.3, hz=100)
        finally:
            stop.set()
            t.join()
        assert out["samples"] > 0
        joined = "\n".join(out["stacks"])
        assert "busy-bee" in joined
        assert "test_profiler.py:busy_loop_marker" in joined
        # collapsed form is thread-name-rooted: every stack names a thread
        for stack in out["stacks"]:
            assert ";" in stack or stack  # non-empty

    def test_threads_dump_includes_caller(self):
        out = profiler.threads_dump()
        assert out
        me = [t for t in out
              if any(f["func"] == "test_threads_dump_includes_caller"
                     for f in t["stack"])]
        assert me, "calling thread's own stack missing from the dump"
        frame = me[0]["stack"][-1]
        assert set(frame) == {"file", "line", "func"}


class TestOverheadGuard:
    def test_busy_loop_overhead_under_10_pct(self):
        # in an interpreter of its own: every busy thread that earlier tests
        # of this process left behind takes a turn at the interpreter lock
        # whenever the sampler wakes, and that is their cost, not the
        # sampler's
        probe = """
import json, time
from seaweedfs_tpu.stats import profiler

def work():
    t0 = time.perf_counter()
    acc = 0
    for _ in range(400):
        acc += sum(range(20000))
    return time.perf_counter() - t0

base = min(work() for _ in range(3))
p = profiler.SamplingProfiler(hz=50)
p.start()
try:
    timed = min(work() for _ in range(3))
finally:
    out = p.stop()
print(json.dumps({"base": base, "timed": timed, "samples": out["samples"],
                  "overhead_ratio": out["overhead_ratio"]}))
"""
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run([sys.executable, "-c", probe], cwd=root,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        out = json.loads(proc.stdout.splitlines()[-1])
        base, timed = out["base"], out["timed"]
        assert out["samples"] > 0
        # the guard's own accounting: sampling duty cycle stayed bounded
        assert out["overhead_ratio"] < profiler.MAX_OVERHEAD
        # and the measured wall cost on the workload stayed under 10%
        # (epsilon absorbs scheduler noise on a busy host)
        assert timed < base * 1.10 + 0.05, (
            f"sampling at 50Hz cost {timed / base - 1:.1%} wall time"
        )

    def test_guard_stretches_wait_on_expensive_samples(self):
        # a sample costing more than the interval must force a wait that
        # keeps duty cycle <= max_overhead: wait >= 9x cost at 10%
        p = profiler.SamplingProfiler(hz=500, max_overhead=0.10)
        interval = 1.0 / p.hz
        cost = 10 * interval
        wait = max(interval - cost, cost * (1.0 / p.max_overhead - 1.0))
        assert wait >= 9 * cost


class TestSelfMetrics:
    def test_profiler_counters_exported(self):
        before = dict_of(default_registry().render())
        profiler.profile(seconds=0.06, hz=50)
        after = dict_of(default_registry().render())
        assert (after["SeaweedFS_stats_profile_runs_total"]
                > before.get("SeaweedFS_stats_profile_runs_total", 0))
        assert (after["SeaweedFS_stats_profile_samples_total"]
                > before.get("SeaweedFS_stats_profile_samples_total", 0))
        assert "SeaweedFS_stats_profile_overhead_seconds_total" in after

    def test_trace_ring_self_metrics(self):
        from seaweedfs_tpu.stats import trace

        col = trace.TraceCollector(max_spans=4)
        for i in range(6):
            sp = col.start_span(f"sm{i}", activate=False)
            col.finish_span(sp)
        assert col.spans_total == 6
        assert col.dropped_total == 2  # 6 spans through a 4-slot ring
        # noise spans without a parent never enter the ring: also a loss
        sp = col.start_span("hb", activate=False, attrs={"noise": True})
        col.finish_span(sp)
        assert col.dropped_total == 3
        # the process-wide collector renders the families on /metrics
        text = default_registry().render()
        assert "SeaweedFS_stats_trace_spans_total" in text
        assert "SeaweedFS_stats_trace_dropped_total" in text
        assert "SeaweedFS_stats_trace_inflight" in text


def dict_of(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or " " not in line:
            continue
        name, _, val = line.rpartition(" ")
        if "{" in name:
            continue
        try:
            out[name] = float(val)
        except ValueError:
            pass
    return out


class TestPipelineStageMetrics:
    def test_pipeline_feeds_stage_histograms(self, tmp_path):
        from seaweedfs_tpu.ops.rs_kernel import RSCodec
        from seaweedfs_tpu.storage.erasure_coding import encoder

        rng = np.random.RandomState(7)
        base = str(tmp_path / "1")
        payload = rng.randint(0, 256, size=40_000, dtype=np.uint8).tobytes()
        with open(base + ".dat", "wb") as f:
            f.write(payload)
        encoder.write_ec_files(
            base, codec=RSCodec(backend="numpy"),
            large_block_size=8000, small_block_size=100,
        )
        text = default_registry().render()
        for stage in ("read", "encode", "write"):
            for state in ("busy", "wait"):
                needle = (
                    "SeaweedFS_volume_ec_pipeline_seconds_sum"
                    f'{{stage="{stage}",state="{state}"}}'
                )
                assert needle in text, needle



@pytest.fixture(scope="class")
def five_role_cluster(tmp_path_factory):
    """master + volume + filer + s3 + webdav in one process, fastlane off
    so every request runs the Python (debug-routed) path."""
    from seaweedfs_tpu.s3api import S3Server
    from seaweedfs_tpu.server.filer import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.server.webdav import WebDavServer

    prev = os.environ.get("SEAWEEDFS_TPU_DISABLE_FASTLANE")
    os.environ["SEAWEEDFS_TPU_DISABLE_FASTLANE"] = "1"
    tmp = tmp_path_factory.mktemp("profstack")
    master = MasterServer(port=0, pulse_seconds=1)
    master.start()
    vol = VolumeServer(
        [str(tmp / "v0")], master.url, port=0, pulse_seconds=1,
        max_volume_count=10,
    )
    vol.start()
    filer = FilerServer(master.url, port=0, chunk_size_mb=1)
    filer.start()
    s3 = S3Server(filer.url, port=0)
    s3.start()
    webdav = WebDavServer(filer.url, port=0)
    webdav.start()
    yield {
        "master": master,
        "volume": vol,
        "filer": filer,
        "s3": s3,
        "webdav": webdav,
    }
    webdav.stop()
    s3.stop()
    filer.stop()
    vol.stop()
    master.stop()
    if prev is None:
        os.environ.pop("SEAWEEDFS_TPU_DISABLE_FASTLANE", None)
    else:
        os.environ["SEAWEEDFS_TPU_DISABLE_FASTLANE"] = prev


class TestPprofEndpoints:
    def test_every_role_exposes_threads(self, five_role_cluster):
        from seaweedfs_tpu.server.httpd import get_json

        for role, srv in five_role_cluster.items():
            out = get_json(srv.service.url + "/debug/pprof/threads")
            assert out["role"] == role
            assert out["threads"], f"{role}: empty thread dump"
            assert all(t["stack"] for t in out["threads"])

    def test_profile_collapsed_and_json(self, five_role_cluster):
        from seaweedfs_tpu.server.httpd import get_json, http_request

        url = five_role_cluster["master"].service.url
        status, _, body = http_request(
            "GET", url + "/debug/pprof/profile?seconds=0.1&hz=50"
        )
        assert status == 200
        lines = body.decode().splitlines()
        assert lines and all(
            line.rsplit(" ", 1)[1].isdigit() for line in lines
        )
        out = get_json(
            url + "/debug/pprof/profile?seconds=0.1&hz=50&format=json"
        )
        assert out["role"] == "master"
        assert out["hz"] == 50 and out["samples"] > 0
        assert isinstance(out["stacks"], dict) and out["stacks"]
        assert out["proc"] == profiler.PROCESS_TOKEN
        # a 0.1s window quantizes to a handful of samples, and a stop right
        # after one expensive sample can't be paid down by a longer wait —
        # allow slack here; the strict <10% wall contract is asserted on
        # the long-window busy-loop test (TestOverheadGuard)
        assert out["overhead_ratio"] < 2 * profiler.MAX_OVERHEAD

    def test_malformed_params_return_400(self, five_role_cluster):
        from seaweedfs_tpu.server.httpd import http_request

        url = five_role_cluster["volume"].service.url
        for path in (
            "/debug/traces?limit=abc",
            "/debug/traces?min_ms=xyz",
            "/debug/traces?min_ms=nan",
            "/debug/requests?limit=many",
            "/debug/pprof/profile?seconds=abc",
            "/debug/pprof/profile?seconds=nan",
            "/debug/pprof/profile?seconds=inf",
            "/debug/pprof/profile?hz=fast",
            "/debug/pprof/device?seconds=abc",
            "/debug/pprof/device?seconds=nan",
        ):
            status, _, body = http_request("GET", url + path)
            assert status == 400, path
            assert b"error" in body, path

    def test_device_endpoint_degrades_cleanly(self, monkeypatch):
        # jax is present in this image but may be absent in others: the
        # contract is DeviceProfilerUnavailable -> HTTP 501, never an
        # unhandled 500. Probing with an importable jax would capture a
        # real (slow) trace, so force the unavailable path instead.
        import builtins

        real_import = builtins.__import__

        def no_jax(name, *a, **k):
            if name == "jax" or name.startswith("jax."):
                raise ImportError("jax disabled for test")
            return real_import(name, *a, **k)

        monkeypatch.setattr(builtins, "__import__", no_jax)
        with pytest.raises(profiler.DeviceProfilerUnavailable):
            profiler.device_trace(0.05)


class TestClusterProfile:
    def test_three_role_merge(self, five_role_cluster, tmp_path):
        from seaweedfs_tpu.shell import CommandEnv, run_command

        master = five_role_cluster["master"]
        env = CommandEnv(master.url)
        # wait for the volume heartbeat + filer registration to land
        deadline = time.time() + 10
        while time.time() < deadline:
            try:
                if env.servers() and env.get(
                    f"{env.master_url}/cluster/ps"
                ).get("filers"):
                    break
            except Exception:
                pass
            time.sleep(0.2)
        out_file = tmp_path / "cluster.collapsed"
        out = run_command(
            env,
            f"cluster.profile -seconds 0.3 -hz 50 -out {out_file}",
        )
        assert "profiled" in out and "samples" in out
        # the whole fixture is ONE process serving 3 discovered roles: the
        # process-identity dedup must merge it once, not once per role
        assert "(1 process(es))" in out
        body = out_file.read_text()
        # one merged collapsed-stack output whose role-prefixed root names
        # master, volume, AND filer (the acceptance criterion)
        prefixes = {line.split(";", 1)[0]
                    for line in body.strip().splitlines()}
        assert prefixes == {"filer+master+volume"}, prefixes
        for line in body.strip().splitlines():
            stack, _, count = line.rpartition(" ")
            assert stack and count.isdigit()

    def test_bad_flags_usage_error(self, five_role_cluster):
        from seaweedfs_tpu.shell import CommandEnv, run_command
        from seaweedfs_tpu.shell.env import ShellError

        env = CommandEnv(five_role_cluster["master"].url)
        for line in (
            "cluster.profile -seconds banana",
            "cluster.profile -seconds nan",
            "cluster.profile -seconds inf",
            "cluster.profile -hz fast",
        ):
            with pytest.raises(ShellError):
                run_command(env, line)


class TestPerRoleSlowThreshold:
    def test_role_override_beats_default(self, tmp_path, monkeypatch):
        from seaweedfs_tpu.stats import trace
        from seaweedfs_tpu.util import glog

        log = tmp_path / "slow_role.log"
        monkeypatch.setattr(glog, "_log_file", str(log))
        monkeypatch.setattr(trace, "_slow_threshold_s", 1e9)  # default: off
        monkeypatch.setitem(trace._slow_threshold_roles, "volume", 1e-9)
        sp = trace.begin_server_span("volume", "GET", "/rolepath", {})
        trace.end_server_span(sp, 200)
        assert log.exists() and "/rolepath" in log.read_text()
        # another role still uses the (huge) default: no log
        log2 = tmp_path / "slow_role2.log"
        monkeypatch.setattr(glog, "_log_file", str(log2))
        sp = trace.begin_server_span("filer", "GET", "/otherrole", {})
        trace.end_server_span(sp, 200)
        assert not log2.exists()

    def test_server_flag_sets_role_threshold(self, monkeypatch):
        from seaweedfs_tpu.stats import trace

        monkeypatch.setattr(trace, "_slow_threshold_roles", {})
        trace.set_slow_threshold_ms(250, role="webdav")
        assert trace.slow_threshold_s("webdav") == 0.25
        assert trace.slow_threshold_s("s3") == trace._slow_threshold_s
