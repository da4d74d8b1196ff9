"""Autonomous maintenance subsystem: detect -> plan -> heal
(seaweedfs_tpu/maintenance — detectors, scheduler, executors, daemon,
the cluster.maintenance verb, and the shared -dryRun/-apply repair-verb
convention)."""

import random
import time

import pytest

from seaweedfs_tpu import maintenance
from seaweedfs_tpu.maintenance import (
    MaintenanceDaemon,
    RepairScheduler,
    RepairTask,
    TASK_TYPES,
)
from seaweedfs_tpu.maintenance import detectors as det
from seaweedfs_tpu.server.httpd import get_json, http_request, post_json
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume import VolumeServer
from seaweedfs_tpu.shell import CommandEnv, ShellError, run_command
from seaweedfs_tpu.stats import parse_exposition
from seaweedfs_tpu.topology import Topology


def _task(type_="fix_replication", vid=1, node="n1", priority=None, **params):
    return RepairTask(
        type=type_, volume_id=vid, node=node,
        priority=TASK_TYPES[type_].priority if priority is None else priority,
        params=params,
    )


class TestRepairTask:
    def test_unknown_type_rejected(self):
        with pytest.raises(ValueError, match="unknown maintenance task"):
            RepairTask(type="frobnicate")

    def test_key_is_dedup_identity(self):
        # volume-scoped: the node (holder-order-unstable) is NOT part of
        # the identity — the same fault re-detected with reordered
        # holders must still dedup
        assert _task(vid=3).key == ("fix_replication", 3)
        assert _task(vid=3).key == _task(vid=3, node="other").key
        assert _task(vid=3).key == _task(vid=3, reason_differs=True).key
        # node-scoped (no volume): the node IS the identity
        t = RepairTask(type="evacuate", node="n9", priority=2)
        assert t.key == ("evacuate", "n9")


class TestScheduler:
    def test_dedup_and_queue_bound(self):
        s = RepairScheduler(max_queue=2)
        assert s.offer(_task(vid=1), now=0)
        assert not s.offer(_task(vid=1), now=0)  # duplicate key
        assert s.offer(_task(vid=2), now=0)
        assert not s.offer(_task(vid=3), now=0)  # queue full
        assert s.stats["deduped"] == 1 and s.stats["queue_full"] == 1

    def test_priority_order(self):
        s = RepairScheduler(repair_rate=100, repair_burst=100, global_limit=10,
                            per_node_limit=10)
        s.offer(_task("vacuum", vid=1, node="a"), now=0)
        s.offer(_task("fix_replication", vid=2, node="b"), now=0)
        first = s.next_task(now=0)
        assert first.type == "fix_replication"  # lower priority value wins
        assert s.next_task(now=0).type == "vacuum"

    def test_per_type_cap(self):
        s = RepairScheduler(repair_rate=100, repair_burst=100, global_limit=10,
                            per_node_limit=10)
        s.offer(_task("ec_rebuild", vid=1, node="a"), now=0)  # cap 1
        s.offer(_task("ec_rebuild", vid=2, node="b"), now=0)
        t1 = s.next_task(now=0)
        assert t1 is not None and s.next_task(now=0) is None
        s.complete(t1, ok=True, now=0)
        assert s.next_task(now=0).volume_id == 2

    def test_per_node_limit(self):
        s = RepairScheduler(repair_rate=100, repair_burst=100, global_limit=10,
                            per_node_limit=1)
        s.offer(_task("fix_replication", vid=1, node="a"), now=0)
        s.offer(_task("vacuum", vid=2, node="a"), now=0)
        s.offer(_task("vacuum", vid=3, node="b"), now=0)
        got = {s.next_task(now=0).key, s.next_task(now=0).key}
        # node a gets ONE task; node b's runs; a's second stays queued
        assert got == {("fix_replication", 1), ("vacuum", 3)}
        assert s.next_task(now=0) is None
        assert s.stats["max_node_inflight"] == 1

    def test_global_limit(self):
        s = RepairScheduler(repair_rate=100, repair_burst=100, global_limit=2,
                            per_node_limit=10,
                            type_caps={"fix_replication": 10})
        for i in range(4):
            s.offer(_task(vid=i, node=f"n{i}"), now=0)
        assert s.next_task(now=0) and s.next_task(now=0)
        assert s.next_task(now=0) is None  # 2 in flight
        assert s.stats["max_inflight"] == 2

    def test_token_bucket_throttle(self):
        s = RepairScheduler(repair_rate=1.0, repair_burst=1.0,
                            global_limit=10, per_node_limit=10,
                            type_caps={"vacuum": 10})
        for i in range(3):
            s.offer(_task("vacuum", vid=i, node=f"n{i}"), now=0)
        assert s.next_task(now=0) is not None
        assert s.next_task(now=0) is None  # bucket drained
        assert s.next_task(now=1.05) is not None  # refilled at 1/s
        assert s.next_task(now=1.1) is None

    def test_backoff_with_jitter(self):
        s = RepairScheduler(backoff_base=2.0, backoff_max=60.0,
                            rng=random.Random(7),
                            repair_rate=100, repair_burst=100)
        t = _task(vid=1)
        assert s.offer(t, now=0)
        assert s.next_task(now=0) is not None
        d1 = s.complete(t, ok=False, now=0)
        assert 1.0 <= d1 <= 3.0  # 2s base, +-50% jitter
        assert not s.offer(t, now=0.5)  # still backing off
        assert s.stats["backed_off"] == 1
        assert s.offer(t, now=d1 + 0.01)  # past not_before
        assert s.next_task(now=d1 + 0.01) is not None
        d2 = s.complete(t, ok=False, now=10)
        assert 2.0 <= d2 <= 6.0  # doubled
        # success clears the backoff state
        assert s.offer(t, now=10 + d2 + 0.01)
        assert s.next_task(now=10 + d2 + 0.01) is not None
        assert s.complete(t, ok=True, now=20) == 0.0
        assert s.offer(t, now=20.01)

    def test_queue_depths_and_snapshot(self):
        s = RepairScheduler(repair_rate=100, repair_burst=100)
        s.offer(_task("vacuum", vid=1, node="a"), now=0)
        s.offer(_task("vacuum", vid=2, node="a"), now=0)
        t = s.next_task(now=0)
        assert t is not None
        d = s.queue_depths()
        assert d["vacuum"] == {"queued": 1, "in_flight": 1}
        snap = s.snapshot(now=0)
        assert len(snap["queued"]) == 1 and len(snap["in_flight"]) == 1
        assert snap["limits"]["per_node_limit"] == 1


class TestLazyWindow:
    """The lazy-batching window (PR-11 follow-up): single-shard
    ec_rebuild tasks sit queued briefly so co-stripe losses fold into
    one multi-target chain pass — batches within the window, never
    delays past it, urgent pressure bypasses it."""

    def _sched(self, window=2.0):
        return RepairScheduler(repair_rate=100, repair_burst=100,
                               global_limit=10, per_node_limit=10,
                               type_caps={"ec_rebuild": 10},
                               lazy_window=window)

    def _lazy_counts(self):
        from seaweedfs_tpu.stats import default_registry

        out = {}
        for line in default_registry().render().splitlines():
            if line.startswith("SeaweedFS_maintenance_lazy_batch_total{"):
                outcome = line.split('outcome="')[1].split('"')[0]
                out[outcome] = float(line.rsplit(" ", 1)[1])
        return out

    def test_batches_within_window_and_folds_targets(self):
        s = self._sched(window=2.0)
        before = self._lazy_counts()
        assert s.offer(_task("ec_rebuild", vid=7, targets=[3]), now=100.0)
        # inside the window: held, not dispatched (counted "deferred")
        assert s.next_task(now=100.5) is None
        after = self._lazy_counts()
        assert after.get("deferred", 0) > before.get("deferred", 0)
        # a second co-stripe loss detected by a later scan FOLDS into the
        # queued task (the dedup key is effectively the target set)
        assert s.offer(_task("ec_rebuild", vid=7, targets=[9]), now=100.8)
        assert s.stats["folded"] == 1
        # multi-target now: dispatches immediately (counted "batched")
        t = s.next_task(now=100.9)
        assert t is not None
        assert t.params["targets"] == [3, 9]
        assert t.params["missing"] == 2
        assert self._lazy_counts().get("batched", 0) \
            > before.get("batched", 0)

    def test_never_delays_past_window(self):
        s = self._sched(window=2.0)
        before = self._lazy_counts()
        s.offer(_task("ec_rebuild", vid=7, targets=[3]), now=100.0)
        assert s.next_task(now=101.99) is None
        t = s.next_task(now=102.01)  # window elapsed: repair anyway
        assert t is not None and t.volume_id == 7
        assert self._lazy_counts().get("expired", 0) \
            > before.get("expired", 0)
        # the daemon's wake shortener knows the deadline
        s2 = self._sched(window=2.0)
        s2.offer(_task("ec_rebuild", vid=8, targets=[1]), now=50.0)
        d = s2.next_lazy_deadline(now=51.0)
        assert d is not None and abs(d - 1.0) < 1e-6
        # an ALREADY-expired hold must not report a 0.0 deadline: a task
        # some other cap is blocking would otherwise spin the daemon's
        # wait at its 0.05s floor (a 20 Hz full-scan busy loop) for as
        # long as the cap holds — once expired, the ordinary tick
        # dispatches it and no precision wakeup is needed
        assert s2.next_lazy_deadline(now=53.0) is None

    def test_urgent_pressure_bypasses_window(self):
        # alert-driven scans (degraded reads paying for the shard NOW)
        # and operator -now scans offer urgent: no lazy hold
        s = self._sched(window=30.0)
        before = self._lazy_counts()
        s.offer(_task("ec_rebuild", vid=7, targets=[3]), now=100.0,
                urgent=True)
        t = s.next_task(now=100.0)
        assert t is not None and t.volume_id == 7
        assert self._lazy_counts().get("bypassed", 0) \
            > before.get("bypassed", 0)
        # an urgent RE-offer of an already-held task lifts the hold too
        s2 = self._sched(window=30.0)
        s2.offer(_task("ec_rebuild", vid=9, targets=[2]), now=100.0)
        assert s2.next_task(now=100.1) is None
        assert not s2.offer(_task("ec_rebuild", vid=9, targets=[2]),
                            now=100.2, urgent=True)  # deduped, but...
        assert s2.next_task(now=100.3) is not None  # ...urgency stuck

    def test_window_zero_is_todays_behavior(self):
        s = self._sched(window=0.0)
        s.offer(_task("ec_rebuild", vid=7, targets=[3]), now=100.0)
        assert s.next_task(now=100.0) is not None

    def test_multi_target_and_online_skip_the_hold(self):
        s = self._sched(window=30.0)
        s.offer(_task("ec_rebuild", vid=7, targets=[3, 9]), now=100.0)
        assert s.next_task(now=100.0) is not None  # already batched
        s.offer(_task("ec_rebuild", vid=8, targets=[], online=True),
                now=100.0)
        assert s.next_task(now=100.0) is not None  # online rearm: no wait

    def test_other_types_unaffected(self):
        s = self._sched(window=30.0)
        s.offer(_task("vacuum", vid=4, node="a"), now=100.0)
        assert s.next_task(now=100.0) is not None

    def test_pressure_and_snapshot_expose_lazy_state(self):
        s = self._sched(window=5.0)
        s.offer(_task("ec_rebuild", vid=7, targets=[3]), now=100.0)
        p = s.pressure(now=101.0)
        assert p["lazy_window"] == 5.0
        assert p["lazy_held"] == 1
        assert p["queued"] == 1
        snap = s.snapshot(now=101.0)
        lazy = snap["queued"][0]["lazy"]
        assert lazy["held"] is True
        assert 0 < lazy["dispatch_in"] <= 5.0
        assert snap["limits"]["lazy_window"] == 5.0
        # folding replaces the queued entry, not duplicates it
        s.offer(_task("ec_rebuild", vid=7, targets=[5]), now=101.5)
        snap = s.snapshot(now=101.5)
        assert len(snap["queued"]) == 1
        assert snap["queued"][0]["params"]["targets"] == [3, 5]

    def test_fold_dispatches_widened_task_not_stale_heap_entry(self):
        # the heap holds the pre-fold object; the queued map is the
        # authority — dispatch must see the WIDENED target set
        s = self._sched(window=0.0)
        s.offer(_task("ec_rebuild", vid=7, targets=[3]), now=100.0)
        s.offer(_task("ec_rebuild", vid=7, targets=[9]), now=100.0)
        t = s.next_task(now=100.0)
        assert t.params["targets"] == [3, 9]
        assert s.next_task(now=100.0) is None  # stale entry skipped

    def test_in_flight_does_not_fold(self):
        s = self._sched(window=0.0)
        s.offer(_task("ec_rebuild", vid=7, targets=[3]), now=100.0)
        t = s.next_task(now=100.0)
        assert t is not None
        # a loss detected while the repair is IN FLIGHT re-detects after
        # completion (the executor re-plans whatever is missing anyway)
        assert not s.offer(_task("ec_rebuild", vid=7, targets=[9]),
                           now=100.1)
        assert s.stats["folded"] == 0


class _FakeMaster:
    """Just enough master surface for the detectors."""

    def __init__(self, topo, garbage_threshold=0.3):
        self.topo = topo
        self.garbage_threshold = garbage_threshold


def _hb(port, volumes=(), ec=()):
    return {
        "ip": "127.0.0.1", "port": port,
        "public_url": f"127.0.0.1:{port}", "max_volume_count": 10,
        "volumes": list(volumes), "ec_shards": list(ec),
    }


def _vol(vid, size=1000, deleted=0, rp=10, read_only=False):
    return {"id": vid, "size": size, "deleted_byte_count": deleted,
            "replica_placement": rp, "read_only": read_only}


class TestDetectors:
    def test_under_replicated(self):
        topo = Topology(pulse_seconds=1)
        topo.sync_heartbeat(_hb(11, [_vol(1), _vol(2)]))
        topo.sync_heartbeat(_hb(12, [_vol(1)]))  # volume 2: 1/2 replicas
        tasks = det.detect_under_replicated(_FakeMaster(topo))
        assert [t.volume_id for t in tasks] == [2]
        assert tasks[0].type == "fix_replication"
        assert tasks[0].node == "127.0.0.1:11"
        assert tasks[0].params == {"have": 1, "want": 2}

    def test_ec_missing_shards_recoverable_only(self):
        topo = Topology(pulse_seconds=1)
        bits_10 = sum(1 << s for s in range(10))
        bits_4 = sum(1 << s for s in range(4))
        topo.sync_heartbeat(_hb(11, ec=[
            {"id": 5, "collection": "c", "ec_index_bits": bits_10},
            {"id": 6, "collection": "c", "ec_index_bits": bits_4},
        ]))
        tasks = det.detect_ec_missing_shards(_FakeMaster(topo))
        # volume 5: 10 shards left -> rebuildable; volume 6: 4 -> lost
        assert [t.volume_id for t in tasks] == [5]
        assert tasks[0].type == "ec_rebuild"
        assert tasks[0].collection == "c"
        assert tasks[0].params["missing"] == 4
        # the concrete missing shard ids ride along: the scheduler's
        # lazy-batching fold widens queued tasks with them
        assert tasks[0].params["targets"] == [10, 11, 12, 13]

    def test_vacuum_candidates(self):
        topo = Topology(pulse_seconds=1)
        topo.sync_heartbeat(_hb(11, [
            _vol(1, size=1000, deleted=500),
            _vol(2, size=1000, deleted=10),
            _vol(3, size=1000, deleted=900, read_only=True),
        ]))
        tasks = det.detect_vacuum_candidates(_FakeMaster(topo))
        assert [t.volume_id for t in tasks] == [1]  # RO + low-garbage skipped
        assert tasks[0].type == "vacuum"
        assert tasks[0].params["garbage_ratio"] == 0.5

    def test_vacuum_skips_scrub_held_volume(self):
        # PR-14 open note: a volume a scrub pass holds is not offered to
        # vacuum — compaction would swap (nm, dat) under the scanner
        topo = Topology(pulse_seconds=1)
        hb = _hb(11, [_vol(1, size=1000, deleted=500),
                      _vol(2, size=1000, deleted=600)])
        hb["scrub_active"] = [1]
        topo.sync_heartbeat(hb)
        tasks = det.detect_vacuum_candidates(_FakeMaster(topo))
        assert [t.volume_id for t in tasks] == [2]
        # the pass moved on: the garbage is still there next scan
        hb["scrub_active"] = []
        topo.sync_heartbeat(hb)
        tasks = det.detect_vacuum_candidates(_FakeMaster(topo))
        assert sorted(t.volume_id for t in tasks) == [1, 2]

    def test_imbalance(self):
        topo = Topology(pulse_seconds=1)
        topo.sync_heartbeat(_hb(11, [_vol(i, rp=0) for i in range(1, 6)]))
        topo.sync_heartbeat(_hb(12, [_vol(9, rp=0)]))
        tasks = det.detect_imbalance(_FakeMaster(topo))
        assert len(tasks) == 1 and tasks[0].type == "balance"
        assert tasks[0].node == "127.0.0.1:11"
        # within slack: no task
        assert det.detect_imbalance(_FakeMaster(topo), slack=10) == []

    def test_stale_nodes(self):
        topo = Topology(pulse_seconds=1)
        topo.sync_heartbeat(_hb(11, [_vol(1)]))
        topo.sync_heartbeat(_hb(12, [_vol(1)]))
        node = topo.find_node("127.0.0.1:12")
        node.last_seen = time.time() - 4  # > 3x pulse, < 5x expiry
        tasks = det.detect_stale_nodes(_FakeMaster(topo))
        assert [t.node for t in tasks] == ["127.0.0.1:12"]
        assert tasks[0].type == "evacuate"

    def test_scan_runs_selected_detectors(self):
        topo = Topology(pulse_seconds=1)
        topo.sync_heartbeat(_hb(11, [_vol(1, deleted=900)]))
        m = _FakeMaster(topo)
        all_types = {t.type for t in det.scan(m)}
        assert {"fix_replication", "vacuum"} <= all_types
        only = det.scan(m, types=("vacuum",))
        assert {t.type for t in only} == {"vacuum"}


class TestAlertOnFireHook:
    def _engine(self, rules):
        from seaweedfs_tpu.stats import alerts as alerts_mod
        from seaweedfs_tpu.stats.history import MetricsHistory
        from seaweedfs_tpu.stats.metrics import Registry

        reg = Registry()
        h = MetricsHistory(reg, interval=1.0, slots=4)
        return alerts_mod.AlertEngine(history=h, registry=reg, rules=rules)

    def test_fires_once_per_rising_edge(self):
        from seaweedfs_tpu.stats import alerts as alerts_mod

        flag = {"on": False}
        rules = [alerts_mod.Rule(
            "test_rule", "warning", "d",
            lambda h, now, p: (1.0, "boom") if flag["on"] else None,
        )]
        eng = self._engine(rules)
        calls = []
        eng.add_on_fire(lambda name, info: calls.append((name, info)))
        try:
            eng.evaluate(now=1.0)
            assert calls == []
            flag["on"] = True
            eng.evaluate(now=2.0)
            assert len(calls) == 1
            name, info = calls[0]
            assert name == "test_rule" and info["severity"] == "warning"
            assert info["detail"] == "boom"
            eng.evaluate(now=3.0)  # still firing: no new edge
            assert len(calls) == 1
            flag["on"] = False
            eng.evaluate(now=4.0)
            flag["on"] = True
            eng.evaluate(now=5.0)  # resolved then re-fired: second edge
            assert len(calls) == 2
        finally:
            eng.close()

    def test_broken_listener_swallowed_and_removable(self):
        from seaweedfs_tpu.stats import alerts as alerts_mod

        rules = [alerts_mod.Rule(
            "always_on", "critical", "d", lambda h, now, p: (1.0, "x"),
        )]
        eng = self._engine(rules)
        calls = []

        def boom(name, info):
            raise RuntimeError("listener bug")

        eng.add_on_fire(boom)
        eng.add_on_fire(lambda name, info: calls.append(name))
        try:
            eng.evaluate(now=1.0)  # boom must not sink the good listener
            assert calls == ["always_on"]
            assert "always_on" in eng.firing
            eng.remove_on_fire(boom)  # idempotent removal
            eng.remove_on_fire(boom)
        finally:
            eng.close()

    def test_daemon_maps_alerts_to_scans(self):
        topo = Topology(pulse_seconds=1)
        d = MaintenanceDaemon(_FakeMaster(topo))  # not started: unit only
        d._on_alert("disk_near_cap", {})
        assert d._pending_types == {"vacuum", "balance"}
        assert d._wake.is_set()
        d._wake.clear()
        d._on_alert("http_error_ratio", {})  # unmapped: ignored
        assert not d._wake.is_set()


# --- end-to-end: a real 3-node cluster heals itself --------------------------
@pytest.fixture()
def cluster(tmp_path):
    master = MasterServer(port=0, pulse_seconds=1, volume_size_limit_mb=64,
                          maintenance_interval=0.25)
    master.start()
    volumes = []
    for i, rack in enumerate(["r1", "r2", "r3"]):
        vs = VolumeServer(
            [str(tmp_path / f"v{i}")], master.url, port=0, rack=rack,
            pulse_seconds=1, max_volume_count=30,
        )
        vs.start()
        volumes.append(vs)
    env = CommandEnv(master.url)
    yield master, volumes, env
    for vs in volumes:
        vs.stop()
    master.stop()


def write_blobs(master_url, n=10, size=500, **params):
    out = {}
    for i in range(n):
        qs = "&".join(f"{k}={v}" for k, v in params.items())
        a = get_json(f"{master_url}/dir/assign?{qs}")
        url = f"http://{a['publicUrl']}/{a['fid']}"
        data = f"blob-{i}-".encode() * (size // 8)
        status, _, _ = http_request("POST", url, data)
        assert status == 201
        out[url] = data
    return out


def wait_until(fn, timeout=25.0, interval=0.2, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def _gauge_positive(master_url, family):
    _, _, body = http_request("GET", f"{master_url}/metrics", timeout=10)
    return [
        (labels, v)
        for name, labels, v in parse_exposition(body.decode())
        if name == family and v > 0
    ]


class TestSelfHealing:
    def test_replica_loss_detected_and_healed(self, cluster):
        """Acceptance: an injected replica loss heals without operator
        action — the under-replicated gauge returns to 0 and the per-node
        repair concurrency never exceeded the configured cap."""
        master, volumes, env = cluster
        write_blobs(master.url, 4, replication="010")
        replicas = {
            vid: h for vid, h in env.volume_replicas().items() if len(h) == 2
        }
        vid, holders = next(iter(sorted(replicas.items())))
        env.post(f"{holders[0].http}/admin/delete_volume", {"volume": vid})
        assert _gauge_positive(
            master.url, "SeaweedFS_master_volumes_underreplicated")
        post_json(f"{master.url}/maintenance/enable")
        wait_until(
            lambda: len(env.volume_replicas().get(vid, [])) == 2,
            msg=f"volume {vid} re-replication",
        )
        wait_until(
            lambda: not _gauge_positive(
                master.url, "SeaweedFS_master_volumes_underreplicated"),
            msg="underreplicated gauge back to 0",
        )

        def _completed():  # history append trails the heal by a moment
            st = get_json(f"{master.url}/debug/maintenance")
            return [h for h in st["history"]
                    if h["task"]["type"] == "fix_replication"
                    and h["state"] == "completed"]

        wait_until(_completed, timeout=5, msg="fix_replication in history")
        st = get_json(f"{master.url}/debug/maintenance")
        done = _completed()
        assert any("replicated to" in a for h in done
                   for a in h.get("applied", []))
        limits = st["scheduler"]["limits"]
        assert st["scheduler"]["stats"]["max_node_inflight"] \
            <= limits["per_node_limit"]
        assert st["scheduler"]["stats"]["max_inflight"] \
            <= limits["global_limit"]
        # healing is metered
        _, _, body = http_request("GET", f"{master.url}/metrics")
        text = body.decode()
        assert 'SeaweedFS_maintenance_tasks_total{task="fix_replication"' \
            in text
        assert "SeaweedFS_maintenance_queue_depth{" in text

    def test_ec_shard_loss_detected_and_healed(self, cluster):
        """Acceptance: an injected EC-shard deletion is detected and the
        missing shards are rebuilt through the RS(10,4) path."""
        master, volumes, env = cluster
        blobs = write_blobs(master.url, 6, size=2000)
        run_command(env, "lock")
        vid = int(next(iter(blobs)).rsplit("/", 1)[-1].split(",")[0])
        run_command(env, f"ec.encode -volumeId {vid}")
        run_command(env, "unlock")  # daemon repairs take the admin lease
        holders = [sv for sv in env.servers() if vid in sv.ec_shards]
        victim = min(holders, key=lambda sv: len(sv.ec_shards[vid]))
        lost = list(victim.ec_shards[vid])
        assert len(lost) <= 4  # >= 10 shards survive: rebuildable
        env.post(
            f"{victim.http}/admin/ec/delete_shards",
            {"volume": vid, "shards": lost, "delete_index": False},
        )
        assert _gauge_positive(
            master.url, "SeaweedFS_master_ec_missing_shards")
        post_json(f"{master.url}/maintenance/enable")

        def all_shards_back():
            present = {
                s for sv in env.servers() for s in sv.ec_shards.get(vid, [])
            }
            return len(present) == 14

        wait_until(all_shards_back, timeout=30,
                   msg=f"ec volume {vid} shard rebuild")
        wait_until(
            lambda: not _gauge_positive(
                master.url, "SeaweedFS_master_ec_missing_shards"),
            msg="ec_missing_shards gauge back to 0",
        )
        wait_until(  # history append trails the heal by a moment
            lambda: any(
                h["task"]["type"] == "ec_rebuild"
                and h["state"] == "completed"
                for h in get_json(
                    f"{master.url}/debug/maintenance")["history"]
            ),
            timeout=5, msg="ec_rebuild in history",
        )

    def test_vacuum_candidate_detected_and_compacted(self, cluster):
        master, volumes, env = cluster
        post_json(f"{master.url}/maintenance/enable")  # owns vacuum now
        blobs = write_blobs(master.url, 12, size=800)
        vid = int(next(iter(blobs)).rsplit("/", 1)[-1].split(",")[0])
        in_vol = [u for u in blobs if f"/{vid}," in u]
        for url in in_vol[:-1]:  # delete all but one -> garbage over 30%
            status, _, _ = http_request("DELETE", url)
            assert status in (200, 202)  # 202: fastlane async delete
        for vs in volumes:
            vs.heartbeat_once()

        def compacted():
            for sv in env.servers():
                v = sv.volumes.get(vid)
                if v is not None and v.get("garbage", 0) == 0 \
                        and v.get("size", 1) > 0:
                    return True
            return False

        wait_until(compacted, msg=f"volume {vid} vacuum")
        wait_until(  # history append trails the compaction by a moment
            lambda: any(
                h["task"]["type"] == "vacuum" and h["state"] == "completed"
                for h in get_json(
                    f"{master.url}/debug/maintenance")["history"]
            ),
            msg="vacuum in history",
        )
        # the surviving blob is intact post-compaction. Read through a
        # location lookup like a real client: the daemon owns EVERY
        # repair class while enabled, and its balance task may have
        # legitimately MOVED this volume to the other node — the pinned
        # assign-time URL then 404s on the old holder (the pre-existing
        # ~1/8-runs flake this line used to be)
        fid = in_vol[-1].rsplit("/", 1)[-1]
        locs = get_json(f"{master.url}/dir/lookup?volumeId={vid}")
        assert locs.get("locations"), locs
        status, _, body = http_request(
            "GET", f"http://{locs['locations'][0]['url']}/{fid}")
        assert status == 200 and body == blobs[in_vol[-1]]

    def test_dry_run_plans_same_tasks_with_zero_mutations(self, cluster):
        """Acceptance: -maintenance.dryRun detects and plans the same
        repairs but mutates nothing."""
        master, volumes, env = cluster
        write_blobs(master.url, 4, replication="010")
        replicas = {
            vid: h for vid, h in env.volume_replicas().items() if len(h) == 2
        }
        vid, holders = next(iter(sorted(replicas.items())))
        env.post(f"{holders[0].http}/admin/delete_volume", {"volume": vid})
        post_json(f"{master.url}/maintenance/enable", {"dryRun": True})
        wait_until(
            lambda: any(
                h["task"]["type"] == "fix_replication"
                and h["task"]["volume_id"] == vid
                and h["state"] == "planned"
                for h in get_json(
                    f"{master.url}/debug/maintenance")["history"]
            ),
            msg="dry-run plan recorded",
        )
        st = get_json(f"{master.url}/debug/maintenance")
        planned = next(
            h for h in st["history"]
            if h["task"]["type"] == "fix_replication"
            and h["state"] == "planned"
        )
        # the plan names the same copy the real executor would perform,
        # in the exact rendering the verb's -dryRun shows (shared helper)
        assert any(f"volume {vid} (1/2 replicas): copy" in p
                   for p in planned["planned"])
        assert "applied" not in planned
        time.sleep(1.0)  # several scan intervals
        assert len(env.volume_replicas().get(vid, [])) == 1  # NOT healed
        assert _gauge_positive(
            master.url, "SeaweedFS_master_volumes_underreplicated")
        _, _, body = http_request("GET", f"{master.url}/metrics")
        assert 'SeaweedFS_maintenance_tasks_total' \
            '{task="fix_replication",state="planned"}' in body.decode()

    def test_cluster_maintenance_verb(self, cluster):
        master, volumes, env = cluster
        out = run_command(env, "cluster.maintenance")
        assert "not configured" in out
        out = run_command(env, "cluster.maintenance -enable -dryRun")
        assert "enabled" in out and "dry-run" in out
        out = run_command(env, "cluster.maintenance -status")
        assert "ENABLED" in out and "dry-run" in out
        assert "throttle:" in out and "fix_replication" in out
        # the live dispatch view: token bucket + in-flight + lazy window
        assert "pressure:" in out
        out = run_command(
            env, "cluster.maintenance -enable -lazyWindow 3")
        assert "lazy window 3s" in out
        st = get_json(f"{master.url}/debug/maintenance")
        assert st["pressure"]["lazy_window"] == 3.0
        assert "lazy_held" in st["pressure"]
        assert "lazy window 3s" in run_command(
            env, "cluster.maintenance -status")
        # a bare re-enable preserves the lazy window
        run_command(env, "cluster.maintenance -enable")
        assert master.maintenance.scheduler.lazy_window == 3.0
        run_command(env, "cluster.maintenance -enable -lazyWindow 0")
        assert master.maintenance.scheduler.lazy_window == 0.0
        out = run_command(env, "cluster.maintenance -now vacuum")
        assert "scan" in out
        with pytest.raises(ShellError, match="unknown task type"):
            run_command(env, "cluster.maintenance -now frobnicate")
        with pytest.raises(ShellError, match="at most one"):
            run_command(env, "cluster.maintenance -enable -disable")
        out = run_command(env, "cluster.maintenance -disable")
        assert "disabled" in out
        assert "DISABLED" in run_command(env, "cluster.maintenance")
        # a bare re-enable preserves the daemon's dry-run mode; only an
        # explicit -apply flips it into mutating mode
        out = run_command(env, "cluster.maintenance -enable")
        assert "dry-run" in out
        out = run_command(env, "cluster.maintenance -enable -apply")
        assert "dry-run" not in out
        assert master.maintenance.dry_run is False
        with pytest.raises(ShellError, match="only one of"):
            run_command(env, "cluster.maintenance -enable -dryRun -apply")

    def test_daemon_defers_to_operator_admin_lock(self, cluster):
        """Every real repair takes the master's exclusive admin lease:
        while an operator holds `lock`, the daemon's task fails into
        backoff and only heals after `unlock`."""
        master, volumes, env = cluster
        write_blobs(master.url, 4, replication="010")
        replicas = {
            vid: h for vid, h in env.volume_replicas().items() if len(h) == 2
        }
        vid, holders = next(iter(sorted(replicas.items())))
        run_command(env, "lock")  # the operator is mid-surgery
        env.post(f"{holders[0].http}/admin/delete_volume", {"volume": vid})
        post_json(f"{master.url}/maintenance/enable")
        wait_until(
            lambda: any(
                h["task"]["type"] == "fix_replication"
                and h["state"] == "failed"
                and "locked by shell" in h.get("error", "")
                for h in get_json(
                    f"{master.url}/debug/maintenance")["history"]
            ),
            timeout=10, msg="repair deferred while the lock is held",
        )
        assert len(env.volume_replicas()[vid]) == 1  # untouched
        run_command(env, "unlock")
        wait_until(
            lambda: len(env.volume_replicas().get(vid, [])) == 2,
            msg=f"volume {vid} heals after unlock",
        )

    def test_evacuate_executor_precopies_off_stale_node(self, cluster):
        """The evacuate executor copies a (presumed-unreachable) node's
        replicas onto healthy nodes, sourcing from surviving holders."""
        master, volumes, env = cluster
        write_blobs(master.url, 4, replication="010")
        sv = next(s for s in env.servers() if s.volumes)
        task = RepairTask(type="evacuate", node=sv.id, priority=2)
        out = maintenance.execute(task, env, dry_run=True)
        assert out["planned"] and all("copy" in p for p in out["planned"])
        before = {vid: len(h) for vid, h in env.volume_replicas().items()}
        out = maintenance.execute(task, env, dry_run=False)
        assert out["applied"]
        after = env.volume_replicas()
        for vid in sv.volumes:
            # a fresh copy landed on a node that is NOT the stale one
            assert len(after[vid]) == before[vid] + 1
            assert sum(1 for h in after[vid] if h.id != sv.id) >= before[vid]

    def test_debug_maintenance_unconfigured(self, cluster):
        master, _, env = cluster
        st = get_json(f"{master.url}/debug/maintenance")
        assert st == {"configured": False, "enabled": False}


class TestDryRunApplyConvention:
    """Satellite: volume.fix.replication / ec.rebuild / volume.balance /
    volume.vacuum all share one -dryRun/-apply convention."""

    def test_fix_replication_dry_run(self, cluster):
        master, volumes, env = cluster
        write_blobs(master.url, 4, replication="010")
        run_command(env, "lock")
        replicas = {
            vid: h for vid, h in env.volume_replicas().items() if len(h) == 2
        }
        vid, holders = next(iter(sorted(replicas.items())))
        env.post(f"{holders[0].http}/admin/delete_volume", {"volume": vid})
        out = run_command(env, "volume.fix.replication -dryRun")
        assert "dry run" in out and f"volume {vid}" in out and "copy" in out
        assert len(env.volume_replicas()[vid]) == 1  # no mutation
        out = run_command(env, "volume.fix.replication -apply")
        assert "replicated to" in out
        assert len(env.volume_replicas()[vid]) == 2

    def test_vacuum_dry_run(self, cluster):
        master, volumes, env = cluster
        blobs = write_blobs(master.url, 8, size=800)
        vid = int(next(iter(blobs)).rsplit("/", 1)[-1].split(",")[0])
        in_vol = [u for u in blobs if f"/{vid}," in u]
        for url in in_vol[:-1]:
            http_request("DELETE", url)
        for vs in volumes:
            vs.heartbeat_once()
        out = run_command(env, "volume.vacuum -dryRun")
        assert "dry run" in out and f"vacuum volume {vid}" in out
        sv = next(s for s in env.servers() if vid in s.volumes)
        assert sv.volumes[vid]["garbage"] > 0  # untouched

    def test_ec_rebuild_dry_run(self, cluster):
        master, volumes, env = cluster
        blobs = write_blobs(master.url, 6, size=2000)
        run_command(env, "lock")
        vid = int(next(iter(blobs)).rsplit("/", 1)[-1].split(",")[0])
        run_command(env, f"ec.encode -volumeId {vid}")
        holders = [sv for sv in env.servers() if vid in sv.ec_shards]
        victim = min(holders, key=lambda sv: len(sv.ec_shards[vid]))
        lost = list(victim.ec_shards[vid])
        env.post(
            f"{victim.http}/admin/ec/delete_shards",
            {"volume": vid, "shards": lost, "delete_index": False},
        )
        out = run_command(env, f"ec.rebuild -volumeId {vid} -dryRun")
        assert "dry run" in out and "rebuild shards" in out
        present = {s for sv in env.servers()
                   for s in sv.ec_shards.get(vid, [])}
        assert len(present) == 14 - len(lost)  # no mutation
        out = run_command(env, f"ec.rebuild -volumeId {vid}")
        assert "rebuilt" in out
        present = {s for sv in env.servers()
                   for s in sv.ec_shards.get(vid, [])}
        assert len(present) == 14

    def test_balance_dry_run_and_conflict(self, cluster):
        master, volumes, env = cluster
        write_blobs(master.url, 3)
        run_command(env, "lock")
        out = run_command(env, "volume.balance -dryRun")
        assert "dry run" in out or "nothing to balance" in out
        for verb in ("volume.vacuum", "volume.fix.replication",
                     "volume.balance"):
            with pytest.raises(ShellError, match="only one of"):
                run_command(env, f"{verb} -dryRun -apply")
