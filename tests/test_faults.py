"""Fault-injection registry (util/faults.py), RetryPolicy math
(util/retry.py), and degraded-read byte-identity (storage/volume.py +
erasure_coding) — the unit half of the robustness PR; the live-cluster
half lives in tests/test_chaos.py."""

import os
import random
import time

import pytest

from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.util import faults
from seaweedfs_tpu.util.retry import RetryPolicy


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.enable()  # opt the test process into runtime POST /debug/faults
    faults.disarm_all()
    yield
    faults.disarm_all()


class TestFaultRegistry:
    def test_register_rejects_undeclared_point(self):
        with pytest.raises(ValueError, match="undeclared fault point"):
            faults.register("totally.made.up")

    def test_arm_error_fires_and_counts(self):
        p = faults.point("volume.read.dat")
        fired_before = p.fired
        faults.arm("volume.read.dat", "error", count=2)
        with pytest.raises(faults.FaultInjected):
            p.hit()
        with pytest.raises(faults.FaultInjected):
            p.hit()
        p.hit()  # count exhausted: auto-disarmed
        assert p.fired == fired_before + 2
        assert "volume.read.dat" not in faults.armed()

    def test_modes(self):
        p = faults.point("master.assign")
        faults.arm("master.assign", "disk_full")
        with pytest.raises(OSError) as ei:
            p.hit()
        import errno

        assert ei.value.errno == errno.ENOSPC
        faults.arm("master.assign", "partition")
        with pytest.raises(ConnectionError):
            p.hit()
        faults.arm("master.assign", "latency", ms=1)
        t0 = time.monotonic()
        p.hit()
        assert time.monotonic() - t0 >= 0.0005

    def test_torn_mangles_payload_only_via_mangle(self):
        p = faults.point("volume.write.dat")
        faults.arm("volume.write.dat", "torn", frac=0.25)
        p.hit()  # torn is byte-level: hit() must not fire/count it
        data = bytes(range(100))
        out = p.mangle(data)
        assert out == data[:75]
        # disarmed: mangle is identity
        faults.disarm("volume.write.dat")
        assert p.mangle(data) == data

    def test_key_scoping(self):
        p = faults.point("volume.heartbeat.send")
        faults.arm("volume.heartbeat.send", "error", key="127.0.0.1:1234")
        p.hit(key="127.0.0.1:9999")  # other node: untouched
        with pytest.raises(faults.FaultInjected):
            p.hit(key="127.0.0.1:1234")
        # a seam that passes no key is never scoped out
        with pytest.raises(faults.FaultInjected):
            p.hit()

    def test_rate_zero_one_bounds(self):
        with pytest.raises(ValueError):
            faults.arm("master.lookup", "error", rate=0.0)
        with pytest.raises(ValueError):
            faults.arm("master.lookup", "error", rate=1.5)
        with pytest.raises(ValueError):
            faults.arm("master.lookup", "wat")
        with pytest.raises(ValueError):
            faults.arm("master.lookup", "error", after=-1)

    def test_after_delays_onset(self):
        """`after=N` lets the first N would-fire draws pass untouched —
        the onset-delay the chaos suite uses to kill a streaming hop
        with chunks already in flight ("die on the 4th chunk")."""
        p = faults.point("volume.read.dat")
        fired_before = p.fired
        faults.arm("volume.read.dat", "error", after=2, count=1)
        p.hit()  # draw 1: passes
        p.hit()  # draw 2: passes
        with pytest.raises(faults.FaultInjected):
            p.hit()  # draw 3: fires
        p.hit()  # count exhausted: disarmed again
        assert p.fired == fired_before + 1
        # key scoping filters BEFORE the onset countdown: other-key
        # draws must not consume the delay
        faults.arm("volume.heartbeat.send", "error", after=1, key="a")
        hp = faults.point("volume.heartbeat.send")
        hp.hit(key="b")  # scoped out: does not consume `after`
        hp.hit(key="a")  # consumes the delay
        with pytest.raises(faults.FaultInjected):
            hp.hit(key="a")
        faults.disarm_all()

    def test_arm_from_spec_grammar(self):
        armed = faults.arm_from_spec(
            "volume.read.dat=error:rate=0.5,count=3;"
            "master.assign=latency:ms=20"
        )
        assert armed == ["volume.read.dat", "master.assign"]
        spec = faults.armed()["volume.read.dat"]
        assert spec.rate == 0.5 and spec.count == 3
        assert faults.armed()["master.assign"].ms == 20.0
        with pytest.raises(ValueError):
            faults.arm_from_spec("volume.read.dat")  # no =mode
        with pytest.raises(ValueError):
            faults.arm_from_spec("volume.read.dat=error:bogus=1")

    def test_snapshot_and_disarm_all(self):
        faults.arm("volume.read.dat", "error")
        faults.arm("master.assign", "latency", ms=5)
        snap = {p["point"]: p for p in faults.snapshot()}
        assert snap["volume.read.dat"]["armed"]["mode"] == "error"
        assert faults.disarm_all() == 2
        assert faults.armed() == {}

    def test_disarmed_is_zero_overhead(self):
        """The acceptance bar: a disarmed point adds no allocation and
        (best-of-3, prewarmed — this box throttles) no measurable cost
        to a hot loop."""
        import tracemalloc

        p = faults.point("volume.read.dat")
        assert p.spec is None
        hit = p.hit
        for _ in range(10000):  # prewarm
            hit()
        # what `hit` itself allocates: threads that earlier tests of this
        # process left running allocate too, in files of their own
        own = [tracemalloc.Filter(True, faults.__file__)]
        tracemalloc.start()
        before = tracemalloc.take_snapshot().filter_traces(own)
        for _ in range(50000):
            hit()
        after = tracemalloc.take_snapshot().filter_traces(own)
        tracemalloc.stop()
        grew = sum(
            s.size_diff for s in after.compare_to(before, "filename")
            if s.size_diff > 0
        )
        # tracemalloc's own bookkeeping allows a little noise; 50k calls
        # allocating anything per-call would dwarf 16KB
        assert grew < 16 * 1024, f"hot loop allocated {grew} bytes"

        def best_of_3(fn, n=200_000):
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(n):
                    fn()
                best = min(best, time.perf_counter() - t0)
            return best

        t_hit = best_of_3(hit)
        # generous absolute guard (microVM): 200k disarmed checks well
        # under a second means ~<5us/call worst case — no real overhead
        assert t_hit < 1.0, f"200k disarmed hits took {t_hit:.3f}s"


class TestRetryPolicy:
    def test_delay_schedule_deterministic(self):
        p = RetryPolicy(base_delay=0.1, multiplier=2.0, max_delay=1.0,
                        jitter=0.0)
        assert p.delay(0) == pytest.approx(0.1)
        assert p.delay(1) == pytest.approx(0.2)
        assert p.delay(2) == pytest.approx(0.4)
        assert p.delay(10) == pytest.approx(1.0)  # capped

    def test_jitter_bounds(self):
        p = RetryPolicy(base_delay=0.1, jitter=0.5)
        rng = random.Random(7)
        for attempt in range(5):
            d = p.delay(attempt, rng)
            base = min(p.max_delay, 0.1 * (2.0 ** attempt))
            assert base * 0.5 <= d <= base * 1.5

    def test_deadline_budget(self):
        p = RetryPolicy(attempts=100, deadline=10.0)
        # plenty of attempts left, but the budget is spent
        assert not p.should_retry(1, start=0.0, now=10.1)
        # budget must also cover the backoff itself
        assert not p.should_retry(1, start=0.0, now=9.5, next_delay=0.6)
        assert p.should_retry(1, start=0.0, now=9.5, next_delay=0.4)
        assert p.remaining(0.0, 4.0) == pytest.approx(6.0)
        assert p.remaining(0.0, 11.0) == 0.0

    def test_attempts_exhausted(self):
        p = RetryPolicy(attempts=3, deadline=1e9)
        assert p.should_retry(1, 0, 0) and p.should_retry(2, 0, 0)
        assert not p.should_retry(3, 0, 0)

    def test_call_retries_then_succeeds(self):
        clock = {"t": 0.0}
        sleeps: list[float] = []

        def now():
            return clock["t"]

        def sleep(d):
            sleeps.append(d)
            clock["t"] += d

        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            if calls["n"] < 3:
                raise IOError("transient")
            return "ok"

        p = RetryPolicy(attempts=5, base_delay=0.1, jitter=0.0,
                        deadline=100.0)
        assert p.call(fn, now=now, sleep=sleep) == "ok"
        assert calls["n"] == 3
        assert sleeps == pytest.approx([0.1, 0.2])

    def test_call_gives_up_on_deadline(self):
        clock = {"t": 0.0}

        def now():
            return clock["t"]

        def sleep(d):
            clock["t"] += d

        def fn():
            clock["t"] += 4.0
            raise IOError("always")

        p = RetryPolicy(attempts=100, base_delay=0.1, jitter=0.0,
                        deadline=10.0)
        with pytest.raises(IOError):
            p.call(fn, now=now, sleep=sleep)
        assert clock["t"] < 15.0  # bounded by the budget, not attempts

    def test_non_retryable_propagates_immediately(self):
        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            raise ValueError("semantic")

        with pytest.raises(ValueError):
            RetryPolicy().call(fn, retry_on=(IOError,))
        assert calls["n"] == 1


def _write_needles(v: Volume, n: int = 6, size: int = 3000) -> dict:
    golden = {}
    for i in range(1, n + 1):
        data = bytes((i * 31 + j) % 251 for j in range(size))
        nd = Needle(cookie=0x1234 + i, id=i, data=data)
        v.write_needle(nd)
        golden[i] = data
    return golden


class TestDegradedReadOnlineEc:
    @pytest.fixture()
    def vol(self, tmp_path):
        from seaweedfs_tpu.storage.erasure_coding.online import OnlineEcWriter

        v = Volume(str(tmp_path), "", 7)
        v.online_ec = OnlineEcWriter(v, block_size=1024)
        yield v
        v.close()

    def test_byte_identity_after_dat_corruption(self, vol):
        golden = _write_needles(vol)
        vol.online_ec.pump(force=True)  # parity covers everything written
        nv = vol.nm.get(3)
        offset, _ = nv
        direct = vol.read_needle(3)
        assert direct.data == golden[3]
        # flip bytes inside needle 3's data region on disk
        path = vol.base_name + ".dat"
        with open(path, "r+b") as f:
            f.seek(offset + 30)
            raw = f.read(64)
            f.seek(offset + 30)
            f.write(bytes(b ^ 0xFF for b in raw))
        from seaweedfs_tpu.storage.volume import degraded_reads_counter

        before = dict(degraded_reads_counter()._values)
        n = vol.read_needle(3, cookie=0x1234 + 3)
        assert n.data == golden[3]  # byte-identical via parity decode
        after = degraded_reads_counter()._values
        assert after.get(("needle_parse",), 0) == \
            before.get(("needle_parse",), 0) + 1
        # untouched needles still read directly
        assert vol.read_needle(5).data == golden[5]

    def test_injected_read_fault_recovers(self, vol):
        golden = _write_needles(vol)
        vol.online_ec.pump(force=True)
        faults.arm("volume.read.dat", "error", count=1)
        try:
            n = vol.read_needle(2)
        finally:
            faults.disarm_all()
        assert n.data == golden[2]

    def test_unrecoverable_raises_original(self, vol):
        golden = _write_needles(vol)
        # parity NOT pumped past the watermark: nothing covers the range
        vol.online_ec.reset()
        nv = vol.nm.get(1)
        with open(vol.base_name + ".dat", "r+b") as f:
            f.seek(nv[0] + 25)
            f.write(b"\x00" * 40)
        from seaweedfs_tpu.storage.needle import CRCError

        with pytest.raises((CRCError, Exception)):
            vol.read_needle(1)
        assert golden  # (the write path itself stayed intact)


class TestDegradedReadSealed:
    def test_byte_identity_from_sealed_shards(self, tmp_path):
        from seaweedfs_tpu.storage.erasure_coding import encoder as ec_encoder

        v = Volume(str(tmp_path), "", 9)
        golden = _write_needles(v, n=4, size=2000)
        v.readonly = True
        ec_encoder.write_ec_files(v.base_name)
        ec_encoder.write_sorted_file_from_idx(v.base_name)
        ec_encoder.save_volume_info(v.base_name + ".vif", version=v.version())
        nv = v.nm.get(2)
        with open(v.base_name + ".dat", "r+b") as f:
            f.seek(nv[0] + 40)
            raw = f.read(32)
            f.seek(nv[0] + 40)
            f.write(bytes(b ^ 0x5A for b in raw))
        n = v.read_needle(2)
        assert n.data == golden[2]
        v.close()


class TestDebugFaultsEndpoint:
    def test_arm_disarm_roundtrip(self):
        from seaweedfs_tpu.server.httpd import (
            HTTPService,
            get_json,
            post_json,
        )

        svc = HTTPService(port=0)
        svc.serve_debug_routes()
        svc.start()
        try:
            out = post_json(f"{svc.url}/debug/faults", {
                "action": "arm", "point": "master.lookup",
                "mode": "latency", "ms": 5,
            })
            assert out["ok"] and out["armed"]["mode"] == "latency"
            state = get_json(f"{svc.url}/debug/faults")
            armed = {p["point"]: p["armed"] for p in state["points"]}
            assert armed["master.lookup"]["ms"] == 5.0
            assert "master.lookup" in state["declared"]
            out = post_json(f"{svc.url}/debug/faults",
                            {"action": "disarm_all"})
            assert out["disarmed"] >= 1
            with pytest.raises(IOError):
                post_json(f"{svc.url}/debug/faults", {
                    "action": "arm", "point": "nope.nope", "mode": "error",
                })
        finally:
            svc.stop()

    def test_runtime_arming_gated_off_by_default(self, monkeypatch):
        """A reachable port must NOT be enough to arm torn writes: the
        mutating route 403s unless the process opted in (-faults flag /
        SEAWEEDFS_TPU_FAULTS=1)."""
        from seaweedfs_tpu.server.httpd import HTTPService, post_json

        monkeypatch.setattr(faults, "_enabled", False)
        monkeypatch.delenv("SEAWEEDFS_TPU_FAULTS", raising=False)
        svc = HTTPService(port=0)
        svc.serve_debug_routes()
        svc.start()
        try:
            with pytest.raises(IOError, match="403|disabled"):
                post_json(f"{svc.url}/debug/faults", {
                    "action": "arm", "point": "master.lookup",
                    "mode": "error",
                })
            assert faults.armed() == {}
        finally:
            svc.stop()


class TestOnlineParityHealthAndRearm:
    def test_lost_parity_detected_and_rearmed(self, tmp_path):
        from seaweedfs_tpu.storage.erasure_coding.online import OnlineEcWriter

        v = Volume(str(tmp_path), "", 11)
        golden = _write_needles(v, n=5, size=2500)
        w = OnlineEcWriter(v, block_size=1024)
        v.online_ec = w
        w.pump(force=True)
        assert w.parity_health() == 0
        # lose one parity shard file out from under the writer
        os.unlink(v.base_name + ".ec11")
        assert w.parity_health() == 1
        rows = w.rearm()
        assert rows > 0
        assert w.parity_health() == 0
        assert w.active and w.fallback_reason is None
        assert os.path.exists(v.base_name + ".ec11")
        # the re-encoded parity actually decodes: corrupt + degraded-read
        nv = v.nm.get(4)
        with open(v.base_name + ".dat", "r+b") as f:
            f.seek(nv[0] + 35)
            f.write(b"\xde\xad\xbe\xef" * 8)
        assert v.read_needle(4).data == golden[4]
        v.close()

    def test_torn_parity_detected(self, tmp_path):
        from seaweedfs_tpu.storage.erasure_coding.online import OnlineEcWriter

        v = Volume(str(tmp_path), "", 12)
        _write_needles(v, n=5, size=2500)
        w = OnlineEcWriter(v, block_size=1024)
        v.online_ec = w
        w.pump(force=True)
        assert w.parity_health() == 0
        faults.arm("volume.ec.parity.write", "torn", frac=1.0, count=1)
        _write_needles(v, n=2, size=4096)
        w.pump(force=True)  # encodes, then the injection tears shard 0
        faults.disarm_all()
        assert w.parity_health() >= 1
        w.rearm()
        assert w.parity_health() == 0
        v.close()
