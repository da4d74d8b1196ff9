"""Admin shell commands driving a real in-process cluster
(ref weed/shell/ — command surface + orchestration sequences)."""

import json
import time

import pytest

from seaweedfs_tpu.server.httpd import get_json, http_request
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume import VolumeServer
from seaweedfs_tpu.shell import CommandEnv, ShellError, run_command


@pytest.fixture()
def cluster(tmp_path):
    master = MasterServer(port=0, pulse_seconds=1, volume_size_limit_mb=64)
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


@pytest.fixture()
def own_alerts():
    """`cluster.check` reports the alert engine of the PROCESS, which every
    test of this worker shares: what earlier tests left in the metrics
    history (5xx storms, requests slowed by a loaded host) would fire
    `slo_burn_fast` on a cluster that is healthy. Give the test a history
    of its own, and take the host's speed out of the latency SLOs by
    moving their thresholds to the histogram's last bound (the burn is
    still computed; only a request slower than 10 s can spend budget).
    Availability SLOs and every other rule stay as they are. Ask for it
    BEFORE `cluster`: a master ships the engine's firing edges in its own
    telemetry frame from the moment it starts, and `cluster.check` reads
    them from there until the master's next frame."""
    from seaweedfs_tpu.stats import alerts as alerts_mod
    from seaweedfs_tpu.stats import history as history_mod

    eng = alerts_mod.engine()
    was = eng.params["slos"]
    eng.configure(slos=tuple(
        alerts_mod.Slo(s.name, s.role, s.kind, s.objective,
                       threshold_s=10.0 if s.kind == "latency" else 0.0,
                       description=s.description)
        for s in was
    ))
    history_mod.default_history().clear()
    eng.evaluate()
    yield
    eng.configure(slos=was)


def write_blobs(master_url, n=10, size=500, **params):
    """Write n blobs; returns {url: data} and the vid of the first one."""
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


class TestBasicCommands:
    def test_help_and_unknown(self, cluster):
        _, _, env = cluster
        assert "volume.list" in run_command(env, "help")
        with pytest.raises(ShellError):
            run_command(env, "no.such.command")

    def test_volume_list_and_cluster_ps(self, cluster):
        master, volumes, env = cluster
        write_blobs(master.url, 3)
        out = run_command(env, "volume.list")
        assert "volume 1" in out or "volume" in out
        ps = run_command(env, "cluster.ps")
        assert "volumeServer" in ps and "master" in ps

    def test_cluster_check_healthy(self, own_alerts, cluster):
        master, volumes, env = cluster
        write_blobs(master.url, 3)
        for vs in volumes:
            vs.heartbeat_once()
        out = run_command(env, "cluster.check")
        assert "healthy" in out
        # the dashboard renders per-node health off the scraped series
        assert "topology: 3 volume servers" in out
        for vs in volumes:
            assert f"node {vs._host}:{vs.data_port}" in out
        assert "disk" in out and "heartbeat" in out
        assert "fastlane native" in out

    def test_cluster_check_fail_mode_on_readonly(self, own_alerts, cluster):
        """Acceptance: a read-only volume makes `cluster.check -fail` exit
        nonzero; without -fail the problems render but the verb returns."""
        master, volumes, env = cluster
        blobs = write_blobs(master.url, 3)
        vid = int(next(iter(blobs)).rsplit("/", 1)[-1].split(",")[0])
        holder = next(sv for sv in env.servers() if vid in sv.volumes)
        env.post(f"{holder.http}/admin/volume/readonly", {"volume": vid})
        target = next(v for v in volumes
                      if f"{v._host}:{v.data_port}" == holder.id)
        target.heartbeat_once()
        out = run_command(env, "cluster.check")
        assert f"volume {vid} read-only" in out
        assert "problem(s)" in out and "healthy" not in out
        with pytest.raises(ShellError, match="read-only"):
            run_command(env, "cluster.check -fail")
        # the shell CLI surfaces that as a nonzero exit for scripting
        import io

        from seaweedfs_tpu.shell.shell import run_shell

        buf = io.StringIO()
        rc = run_shell(master.url, script="cluster.check -fail", out=buf)
        assert rc == 1 and "read-only" in buf.getvalue()
        # healthy path exits 0
        env.post(f"{holder.http}/admin/volume/readonly",
                 {"volume": vid, "readonly": False})
        target.heartbeat_once()
        rc = run_shell(master.url, script="cluster.check -fail",
                       out=io.StringIO())
        assert rc == 0
        # over-threshold path: with the bar at 0% every non-empty volume
        # counts as near-cap and the same -fail exit fires
        with pytest.raises(ShellError, match="cap"):
            run_command(env, "cluster.check -fail -capacityPct 0")

    def test_cluster_trace_shows_fastlane_spans(self, cluster):
        master, volumes, env = cluster
        if all(vs.fastlane is None for vs in volumes):
            pytest.skip("fastlane unavailable")
        write_blobs(master.url, 3)
        for vs in volumes:
            if vs.fastlane is not None:
                vs.fastlane.drain()
        out = run_command(env, "cluster.trace -limit 40")
        assert "fastlane.append" in out

    def test_lock_required(self, cluster):
        _, _, env = cluster
        with pytest.raises(ShellError, match="admin lock"):
            run_command(env, "volume.balance")
        run_command(env, "lock")
        # lock is enforced on the master: second holder is refused
        env2 = CommandEnv(env.master_url, holder="other")
        with pytest.raises(Exception):
            env2.acquire_lock()
        run_command(env, "unlock")

    def test_collection_list(self, cluster):
        master, _, env = cluster
        write_blobs(master.url, 2, collection="photos")
        out = run_command(env, "collection.list")
        assert "photos" in out


class TestVolumeOps:
    def test_volume_move(self, cluster):
        master, volumes, env = cluster
        blobs = write_blobs(master.url, 6)
        run_command(env, "lock")
        replicas = env.volume_replicas()
        vid, holders = next(iter(sorted(replicas.items())))
        src = holders[0]
        dst = next(sv for sv in env.servers() if vid not in sv.volumes)
        out = run_command(
            env, f"volume.move -volumeId {vid} -source {src.id} -target {dst.id}"
        )
        assert "moved" in out
        # data still readable through lookup (new location serves it)
        deadline = time.time() + 5
        for url, data in blobs.items():
            if f"/{vid}," not in url:
                continue
            # old URL points at the old server; use lookup for the new one
            fid = url.rsplit("/", 1)[-1]
            while time.time() < deadline:
                locs = env.locations(vid)
                if locs and locs[0] == dst.id:
                    break
                time.sleep(0.2)
            status, _, body = http_request(f"GET", f"http://{dst.id}/{fid}")
            assert status == 200 and body == data

    def test_volume_fsck(self, cluster):
        master, volumes, env = cluster
        write_blobs(master.url, 6)
        out = run_command(env, "volume.fsck")
        assert "clean" in out

    def test_fix_replication(self, cluster):
        master, volumes, env = cluster
        blobs = write_blobs(master.url, 4, replication="010")
        run_command(env, "lock")
        # kill one replica of some volume by deleting it directly
        replicas = {
            vid: h for vid, h in env.volume_replicas().items() if len(h) == 2
        }
        vid, holders = next(iter(sorted(replicas.items())))
        env.post(f"{holders[0].http}/admin/delete_volume", {"volume": vid})
        out = run_command(env, "volume.fix.replication")
        assert f"volume {vid}: replicated" in out
        assert len(env.volume_replicas()[vid]) == 2

    def test_check_disk_sync(self, cluster):
        master, volumes, env = cluster
        write_blobs(master.url, 4, replication="010")
        run_command(env, "lock")
        replicas = {
            vid: h for vid, h in env.volume_replicas().items() if len(h) == 2
        }
        vid, holders = next(iter(sorted(replicas.items())))
        # write a needle only to ONE replica (simulating a missed write)
        a = get_json(f"{master.url}/dir/assign?replication=010")
        # force it onto our vid by writing directly with a crafted fid
        fid = f"{vid},{'f'*8}deadbeef"
        status, _, _ = http_request(
            "POST", f"http://{holders[0].id}/{fid}?type=replicate", b"lonely needle"
        )
        assert status == 201
        out = run_command(env, "volume.check.disk")
        assert "copied needle" in out
        status, _, body = http_request("GET", f"http://{holders[1].id}/{fid}")
        assert status == 200 and body == b"lonely needle"

    def test_evacuate(self, cluster):
        master, volumes, env = cluster
        write_blobs(master.url, 8)
        run_command(env, "lock")
        victim = env.servers()[0]
        if not victim.volumes:
            pytest.skip("no volumes landed on the victim")
        out = run_command(env, f"volume.server.evacuate -node {victim.id}")
        assert "->" in out
        assert not any(
            sv.id == victim.id and sv.volumes for sv in env.servers()
        )

    def test_balance(self, cluster):
        master, volumes, env = cluster
        write_blobs(master.url, 8)
        run_command(env, "lock")
        out = run_command(env, "volume.balance")
        counts = [len(sv.volumes) for sv in env.servers()]
        assert max(counts) - min(counts) <= 1, (out, counts)


class TestEcCommands:
    def test_ec_encode_balance_rebuild_decode(self, cluster):
        master, volumes, env = cluster
        blobs = write_blobs(master.url, 6, size=2000)
        run_command(env, "lock")
        # encode a volume that actually holds data
        vid = int(next(iter(blobs)).rsplit("/", 1)[-1].split(",")[0])
        in_vol = {u: d for u, d in blobs.items()
                  if u.rsplit("/", 1)[-1].startswith(f"{vid},")}
        assert in_vol

        out = run_command(env, f"ec.encode -volumeId {vid}")
        assert "shards spread" in out
        # all 14 shards mounted across servers, original volume gone
        holders = [sv for sv in env.servers() if vid in sv.ec_shards]
        all_shards = sorted(s for sv in holders for s in sv.ec_shards[vid])
        assert all_shards == list(range(14))
        assert vid not in env.volume_replicas()
        # reads still work through EC (remote-shard reconstruction path)
        for url, data in in_vol.items():
            status, _, body = http_request("GET", url)
            assert status == 200 and body == data, url

        # drop the smallest holder's shards (so >= 10 remain) -> rebuild
        # restores all 14
        victim = min(holders, key=lambda sv: len(sv.ec_shards[vid]))
        lost = list(victim.ec_shards[vid])
        env.post(
            f"{victim.http}/admin/ec/delete_shards",
            {"volume": vid, "shards": lost, "delete_index": False},
        )
        out = run_command(env, f"ec.rebuild -volumeId {vid}")
        assert "rebuilt" in out
        present = sorted(
            {s for sv in env.servers() for s in sv.ec_shards.get(vid, [])}
        )
        assert present == list(range(14))

        # decode back to a normal volume; data readable again
        out = run_command(env, f"ec.decode -volumeId {vid}")
        assert "reconstructed" in out
        deadline = time.time() + 5
        while time.time() < deadline:
            if vid in env.volume_replicas():
                break
            time.sleep(0.2)
        for url, data in in_vol.items():
            fid = url.rsplit("/", 1)[-1]
            locs = env.locations(vid)
            assert locs
            status, _, body = http_request("GET", f"http://{locs[0]}/{fid}")
            assert status == 200 and body == data
