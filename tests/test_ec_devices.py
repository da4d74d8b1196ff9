"""One `ec.encode -collection` seals its volumes side by side, one encode
pipeline a device (PR 33).

The lease alone (`ops.device.Leases`: threads and events, no jax array), the
shell's side-by-side form over a fake cluster, and the whole path through
child processes: a `server` child whose jax has four CPU devices
(`--xla_force_host_platform_device_count=4`) or one, `SEAWEEDFS_TPU_EC_BACKEND=
jax` as every cell of the benchmark sets it, and `shell` children for the
verbs. Every shard file is held to `ops.gf256`'s numpy oracle over the kept
`.dat` (the XLA body: what a CPU child runs; the Pallas body's turn is on the
chip, in the benchmark's cell `ec4x1g.seal`). The servers that need four
devices are children because this process's jax has started with one.
"""

from __future__ import annotations

import io
import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ops import device
from seaweedfs_tpu.server.httpd import get_json, http_request, post_json
from seaweedfs_tpu.shell import commands_ec
from seaweedfs_tpu.shell.env import ShellError
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.storage import idx as idx_mod
from seaweedfs_tpu.storage.erasure_coding import geometry
from tests.test_served_device_path import oracle_shards
from tests.test_trace_phases import _grown, _samples, get_json_text

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOTAL = geometry.TOTAL_SHARDS_COUNT
NEEDLES, NEEDLE_BYTES = 12, 256 * 1024  # 3 MB a volume: one row of small blocks
LEASE = trace.EC_LEASE_SECONDS


# --- the lease alone ---------------------------------------------------------------
class Asker(threading.Thread):
    """Borrows one device of `pool`, says so, and keeps it until told."""

    def __init__(self, pool: device.Leases, holders: dict, fail: bool = False):
        super().__init__(daemon=True)
        self.pool, self.holders, self.fail = pool, holders, fail
        self.entered, self.leave = threading.Event(), threading.Event()
        self.index = self.dev = None

    def run(self) -> None:
        try:
            with self.pool.lease() as (self.index, self.dev):
                # one holder a device: a second one would find the first
                assert self.holders.setdefault(self.index, self) is self
                self.entered.set()
                self.leave.wait(30)
                del self.holders[self.index]
                if self.fail:
                    raise RuntimeError("inside the lease")
        except RuntimeError:
            pass

    def go(self) -> "Asker":
        self.start()
        return self

    def done(self) -> None:
        self.leave.set()
        self.join(30)
        assert not self.is_alive()


def test_lease_hands_out_the_free_device_of_lowest_index():
    pool, holders = device.Leases(["a", "b", "c"]), {}
    first = []
    for want in range(3):  # one after another: 0, 1, 2
        a = Asker(pool, holders).go()
        assert a.entered.wait(30) and (a.index, a.dev) == (want, "abc"[want])
        first.append(a)
    assert set(holders) == {0, 1, 2}  # all three in use while three hold
    first[1].done()
    first[0].done()
    a = Asker(pool, holders).go()  # 0 and 1 are free: the lower one
    assert a.entered.wait(30) and a.index == 0
    for t in (a, first[2]):
        t.done()
    assert pool.granted() == {"0": 2, "1": 1, "2": 1}


def test_lease_blocks_while_none_is_free_and_grants_when_one_returns():
    pool, holders = device.Leases(["a", "b"]), {}
    held = [Asker(pool, holders).go() for _ in range(2)]
    assert all(a.entered.wait(30) for a in held)
    waiters = [Asker(pool, holders).go() for _ in range(3)]
    # both devices are held, and a holder checks that it is alone on its
    # device: a waiter that got in would have failed there
    assert not any(w.entered.is_set() for w in waiters)
    index = held[1].index
    held[1].done()
    # one waiter, and one only, gets the device that came back
    deadline = time.monotonic() + 30
    while not any(w.entered.is_set() for w in waiters):
        assert time.monotonic() < deadline
        time.sleep(0.001)
    got = [w for w in waiters if w.entered.is_set()]
    assert len(got) == 1 and got[0].index == index and len(holders) == 2
    for t in [held[0], *waiters]:
        t.leave.set()
    for t in [held[0], *waiters]:
        t.join(30)
        assert not t.is_alive()
    assert sum(pool.granted().values()) == 5 and not holders


def test_an_exception_inside_the_lease_gives_the_device_back():
    pool, holders = device.Leases(["only"]), {}
    failing = Asker(pool, holders, fail=True).go()
    assert failing.entered.wait(30)
    waiter = Asker(pool, holders).go()
    assert not waiter.entered.is_set()
    failing.done()
    assert waiter.entered.wait(30) and waiter.index == 0
    waiter.done()
    with pytest.raises(KeyError), pool.lease():
        raise KeyError("the caller's own")
    with pool.lease() as (index, dev):
        assert (index, dev) == (0, "only")


def test_lease_under_many_threads_never_lends_a_device_twice():
    pool = device.Leases(range(3))
    inside = [0, 0, 0]
    took = []
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def borrower() -> None:
        for _ in range(50):
            with pool.lease() as (index, dev):
                assert index == dev
                inside[index] += 1
                assert inside[index] == 1
                inside[index] -= 1
            took.append(index)

    try:
        threads = [threading.Thread(target=borrower, daemon=True) for _ in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(was)
    granted = pool.granted()
    assert sum(granted.values()) == len(took) == 32 * 50 and inside == [0, 0, 0]
    assert [granted[str(i)] for i in range(3)] == [took.count(i) for i in range(3)]
    with pool.lease() as (index, _):
        assert index == 0  # all three came back


def registry_page() -> dict:
    from seaweedfs_tpu.stats.metrics import default_registry

    return _samples(default_registry().render())


def test_lease_seconds_go_under_the_device_it_granted():
    before = registry_page()
    pool = device.Leases(["a", "b"])
    with pool.lease() as (first, _):
        with pool.lease() as (second, _):
            assert (first, second) == (0, 1)
    after = registry_page()
    for dev in ("0", "1"):
        for state in ("wait", "held"):
            assert _grown(before, after, LEASE + "_count",
                          device=dev, state=state) == 1


# --- the shell's side-by-side form, over a fake cluster -------------------------------
class FakeEnv:
    """Five volumes of a collection `c` on one server."""

    def __init__(self, vids=(3, 1, 4, 15, 9)) -> None:
        self.vids = list(vids)

    def servers(self):
        class Sv:
            volumes = {v: {"id": v, "collection": "c"} for v in self.vids}

        return [Sv()]


@pytest.fixture()
def encode_one(monkeypatch):
    """`_ec_encode_one` replaced: says on which thread and under which span
    each volume ran, and does what the test tells it to."""
    calls = {}
    lock = threading.Lock()
    state = {"running": 0, "most": 0, "behave": lambda vid: None}

    def fake(env, vid, collection):
        with lock:
            state["running"] += 1
            state["most"] = max(state["most"], state["running"])
        try:
            calls[vid] = (threading.current_thread(), trace.current(), collection)
            state["behave"](vid)
            return f"ec.encode volume {vid}: shards spread x"
        finally:
            with lock:
                state["running"] -= 1

    monkeypatch.setattr(commands_ec, "_ec_encode_one", fake)
    return calls, state


def test_a_collections_volumes_run_side_by_side_up_to_the_limit(encode_one):
    calls, state = encode_one
    both = threading.Barrier(2)  # passes only if two volumes run at once
    state["behave"] = lambda vid: both.wait(30)
    out = commands_ec.cmd_ec_encode(
        FakeEnv((3, 1, 4, 15)), ["-collection", "c", "-maxParallelization", "2"])
    # one line a volume, in the collection's own order
    assert out.splitlines() == [
        f"ec.encode volume {v}: shards spread x" for v in (3, 1, 4, 15)]
    assert state["most"] == 2
    assert all(c[0] is not threading.current_thread() for c in calls.values())


def test_the_default_limit_is_upstreams_ten(encode_one):
    calls, state = encode_one
    vids = tuple(range(1, 13))
    ten = threading.Barrier(10)
    state["behave"] = lambda vid: ten.wait(30) if vid <= 10 else None
    out = commands_ec.cmd_ec_encode(FakeEnv(vids), ["-collection", "c"])
    assert len(out.splitlines()) == 12 and state["most"] == 10
    assert commands_ec.MAX_PARALLELIZATION == 10
    with pytest.raises(ShellError, match="at least 1"):
        commands_ec.cmd_ec_encode(
            FakeEnv(vids), ["-collection", "c", "-maxParallelization", "0"])


def test_volume_id_is_a_collection_of_one_on_the_callers_thread(encode_one):
    calls, _ = encode_one
    out = commands_ec.cmd_ec_encode(FakeEnv(), ["-volumeId", "4"])
    assert out == "ec.encode volume 4: shards spread x"
    assert list(calls) == [4] and calls[4][0] is threading.current_thread()
    assert calls[4][2] == "c"


def test_each_volume_runs_under_a_span_of_the_verbs(encode_one):
    calls, _ = encode_one
    with trace.span("shell ec.encode", role="shell") as root:
        commands_ec.cmd_ec_encode(FakeEnv(), ["-collection", "c"])
    spans = {s["span_id"]: s for s in trace.collector().trace_spans(root.trace_id)}
    assert len(calls) == 5
    for vid, (_, ctx, _) in calls.items():
        # the volume's RPCs carry the context of its own span on
        assert ctx is not None and ctx[0] == root.trace_id
        sp = spans[ctx[1]]
        assert sp["name"] == "ec.encode.volume" and sp["role"] == "shell"
        assert sp["parent_id"] == root.span_id
        assert sp["attrs"] == {"volume": vid, "collection": "c"}


def test_a_volume_that_fails_is_named_and_does_not_stop_the_others(encode_one):
    calls, state = encode_one

    def behave(vid):
        if vid in (1, 15):
            raise ShellError(f"POST /admin/ec/generate: 500 no .dat of {vid}")

    state["behave"] = behave
    with pytest.raises(ShellError) as e:
        commands_ec.cmd_ec_encode(FakeEnv(), ["-collection", "c"])
    said = str(e.value).splitlines()
    assert said[0] == ("ec.encode: 2 of 5 volumes failed: volume 1: POST /admin/ec/generate:"
                       " 500 no .dat of 1; volume 15: POST /admin/ec/generate: 500 no .dat"
                       " of 15")
    # the others were sealed, and their lines are kept
    assert said[1:] == [f"ec.encode volume {v}: shards spread x" for v in (3, 4, 9)]
    assert sorted(calls) == [1, 3, 4, 9, 15]


# --- the whole path, through children --------------------------------------------------
def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def payload(seed: int, i: int) -> bytes:
    return np.random.default_rng([33, seed, i]).bytes(NEEDLE_BYTES)


class Cluster:
    """A `server` child (master + volume) whose jax has `devices` CPU
    devices, and `shell` children against it."""

    def __init__(self, tmp, devices: int) -> None:
        self.dir = str(tmp / "srv")
        os.makedirs(self.dir)
        self.kept = tmp / "kept"
        self.kept.mkdir()
        self.env = dict(os.environ, JAX_PLATFORMS="cpu", SEAWEEDFS_TPU_EC_BACKEND="jax")
        self.env.pop("XLA_FLAGS", None)
        if devices > 1:
            self.env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
        self.master = f"http://127.0.0.1:{free_port()}"
        volume_port = free_port()
        self.volume = f"http://127.0.0.1:{volume_port}"
        self.log = open(tmp / "server.log", "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu.command.main", "server",
             "-dir", self.dir, "-master.port", self.master.rsplit(":", 1)[1],
             "-volume.port", str(volume_port)],
            cwd=ROOT, env=self.env, stdout=self.log, stderr=subprocess.STDOUT,
            stdin=subprocess.DEVNULL)
        self.seed = 0

    def stop(self) -> None:
        self.proc.terminate()
        try:
            self.proc.wait(30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self.log.close()

    def assign(self, collection: str) -> dict:
        deadline = time.monotonic() + 120
        while True:
            assert self.proc.poll() is None, "the server child died"
            try:
                out = get_json(
                    f"{self.master}/dir/assign?count={NEEDLES}&collection={collection}")
                if "fid" in out:
                    return out
            except (OSError, ValueError):
                pass
            assert time.monotonic() < deadline, "the server child did not come up"
            time.sleep(0.1)

    def base(self, collection: str, vid: int) -> str:
        return os.path.join(self.dir, f"{collection}_{vid}")

    def fill(self, collection: str, count: int) -> dict[int, dict]:
        """`count` filled volumes of a collection that holds no other:
        {vid: {"dat": bytes, "ecx": bytes}} as they were acknowledged."""
        taken: dict[int, str] = {}
        while len(taken) < count:
            fid = self.assign(collection)["fid"]
            taken.setdefault(int(fid.split(",")[0]), fid)
        for v in get_json(f"{self.volume}/status")["volumes"]:
            if v["collection"] == collection and v["id"] not in taken:
                post_json(f"{self.volume}/admin/delete_volume", {"volume": v["id"]})
        out = {}
        for vid, fid in taken.items():
            self.seed += 1
            for i in range(NEEDLES):
                st, _, _ = http_request(
                    "POST", f"{self.volume}/{fid}" + (f"_{i}" if i else ""),
                    payload(self.seed, i))
                assert st == 201
            base = self.base(collection, vid)
            with open(base + ".dat", "rb") as f:
                dat = f.read()
            entries = sorted(
                (key, off, size) for key, off, size
                in idx_mod.walk_index_file(base + ".idx"))
            out[vid] = {
                "dat": dat, "fid": fid, "seed": self.seed,
                "ecx": b"".join(idx_mod.entry_to_bytes(*e) for e in entries)}
        return out

    def shell(self, script: str) -> tuple[int, str]:
        proc = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu.command.main", "shell",
             "-master", self.master],
            cwd=ROOT, env=self.env, input=script, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=300)
        return proc.returncode, proc.stdout

    def metrics(self) -> dict:
        return _samples(get_json_text(self.volume + "/metrics"))

    def status(self) -> dict:
        return get_json(self.volume + "/status")

    def spans(self, name: str) -> list[dict]:
        traces = get_json(self.volume + "/debug/traces?limit=1000")["traces"]
        return [s for t in traces for s in t["spans"] if s["name"] == name]

    def seal(self, collection: str, count: int) -> dict:
        """Fills `count` volumes, seals them with one verb, and keeps what a
        test wants to look at."""
        vols = self.fill(collection, count)
        before, seen = self.metrics(), len(self.spans("ec.encode"))
        rc, text = self.shell(f"lock\nec.encode -collection {collection}\nunlock\n")
        return {"vols": vols, "rc": rc, "text": text, "before": before,
                "after": self.metrics(), "status": self.status(),
                "encodes": self.spans("ec.encode")[seen:],
                "generates": self.spans("POST /admin/ec/generate")[-count:],
                "collection": collection}


def held_at_once(encodes: list[dict]) -> dict[int, int]:
    """{device: the most `ec.encode` spans that ran on it at one time}."""
    most: dict[int, int] = {}
    for dev in {s["attrs"]["device"] for s in encodes}:
        edges = []
        for s in encodes:
            if s["attrs"]["device"] == dev:
                edges += [(s["start"], 1), (s["start"] + s["duration_ms"] / 1e3, -1)]
        running = 0
        for _, step in sorted(edges):
            running += step
            most[dev] = max(most.get(dev, 0), running)
    return most


def lease_seconds(sealed: dict, state: str, suffix: str = "_sum") -> dict[str, float]:
    before, after = sealed["before"], sealed["after"]
    return {dict(labels)["device"]: value - before.get((name, labels), 0.0)
            for (name, labels), value in after.items()
            if name == LEASE + suffix and dict(labels)["state"] == state}


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    cluster = Cluster(tmp_path_factory.mktemp("four"), devices=4)
    try:
        yield cluster
    finally:
        cluster.stop()


@pytest.fixture(scope="module")
def four_on_four(four_devices):
    return four_devices.seal("c4", 4)


@pytest.fixture(scope="module")
def six_on_four(four_devices, four_on_four):
    return four_devices.seal("c6", 6)


@pytest.fixture(scope="module")
def four_on_one(tmp_path_factory):
    cluster = Cluster(tmp_path_factory.mktemp("one"), devices=1)
    try:
        yield dict(cluster.seal("c1", 4), cluster=cluster)
    finally:
        cluster.stop()


def oracle_of(vol: dict) -> np.ndarray:
    if "oracle" not in vol:  # once a volume
        vol["oracle"] = oracle_shards(vol["dat"])
    return vol["oracle"]


def check_sealed(cluster_dir: str, sealed: dict, volume: int, shard: int) -> None:
    vid = sorted(sealed["vols"])[volume]
    vol = sealed["vols"][vid]
    base = os.path.join(cluster_dir, f"{sealed['collection']}_{vid}")
    with open(base + geometry.to_ext(shard), "rb") as f:
        assert f.read() == oracle_of(vol)[shard].tobytes()
    if shard == 0:  # once a volume: the index, the info, the original gone
        with open(base + ".ecx", "rb") as f:
            assert f.read() == vol["ecx"]
        with open(base + ".vif") as f:
            assert json.load(f) == {"version": 3}
        assert not os.path.exists(base + ".dat")


def check_verb(sealed: dict) -> None:
    assert sealed["rc"] == 0, sealed["text"]
    lines = [ln for ln in sealed["text"].splitlines() if "ec.encode volume" in ln]
    assert sorted(int(ln.split()[2].rstrip(":")) for ln in lines) == sorted(sealed["vols"])
    assert all(": shards spread" in ln for ln in lines)
    mounted = {e["id"] for e in sealed["status"]["ec_shards"]}
    assert set(sealed["vols"]) <= mounted


@pytest.mark.parametrize("shard", range(TOTAL))
@pytest.mark.parametrize("volume", range(4))
def test_four_volumes_on_four_devices_are_the_oracles(four_devices, four_on_four,
                                                      volume, shard):
    check_sealed(four_devices.dir, four_on_four, volume, shard)


def test_four_volumes_on_four_devices_ran_one_a_device(four_on_four):
    check_verb(four_on_four)
    # `/metrics`: one lease held on each of the four devices, none waited for
    assert lease_seconds(four_on_four, "held", "_count") == {
        "0": 1, "1": 1, "2": 1, "3": 1}
    assert all(s > 0 for s in lease_seconds(four_on_four, "held").values())
    # `/status`: which devices have worked
    jax_seen = four_on_four["status"]["ec"]["jax"]
    assert jax_seen["count"] == 4 and jax_seen["leases"] == {
        "0": 1, "1": 1, "2": 1, "3": 1}
    # `/debug/traces`: the kernel span and the pipeline's spans say where
    encodes = four_on_four["encodes"]
    assert sorted(s["attrs"]["device"] for s in encodes) == [0, 1, 2, 3]
    assert all(s["attrs"]["kernel"] == "pipeline-xla" for s in encodes)
    assert held_at_once(encodes) == {0: 1, 1: 1, 2: 1, 3: 1}


def test_the_verbs_four_volumes_are_one_trace_with_a_span_each(four_devices, four_on_four):
    generates = four_on_four["generates"]
    assert len(generates) == 4
    assert len({s["trace_id"] for s in generates}) == 1
    # each handler's caller is its own `ec.encode.volume` span of the child
    assert len({s["parent_id"] for s in generates}) == 4
    by_parent = {s["parent_id"]: s for s in four_on_four["encodes"]}
    for g in generates:
        assert by_parent[g["span_id"]]["trace_id"] == g["trace_id"]
    stages = [s for name in ("read", "encode", "write")
              for s in four_devices.spans("ec.pipeline." + name)
              if s["trace_id"] == generates[0]["trace_id"]]
    assert {s["attrs"]["device"] for s in stages} == {0, 1, 2, 3}


@pytest.mark.parametrize("shard", range(TOTAL))
@pytest.mark.parametrize("volume", range(6))
def test_six_volumes_on_four_devices_are_the_oracles(four_devices, six_on_four,
                                                     volume, shard):
    check_sealed(four_devices.dir, six_on_four, volume, shard)


def test_six_volumes_on_four_devices_wait_for_a_device(six_on_four):
    check_verb(six_on_four)
    held = lease_seconds(six_on_four, "held", "_count")
    assert sum(held.values()) == 6 and set(held) == {"0", "1", "2", "3"}
    # two of the six found every device held, and waited
    assert sum(lease_seconds(six_on_four, "wait").values()) > 0.0
    assert sum(lease_seconds(six_on_four, "wait", "_count").values()) == 6
    leases = six_on_four["status"]["ec"]["jax"]["leases"]
    assert sum(leases.values()) == 4 + 6 and min(leases.values()) >= 2
    encodes = six_on_four["encodes"]
    assert len(encodes) == 6
    assert held_at_once(encodes) == {0: 1, 1: 1, 2: 1, 3: 1}


@pytest.mark.parametrize("shard", range(TOTAL))
@pytest.mark.parametrize("volume", range(4))
def test_four_volumes_on_one_device_are_the_oracles(four_on_one, volume, shard):
    check_sealed(four_on_one["cluster"].dir, four_on_one, volume, shard)


def test_four_volumes_on_one_device_queue_for_it(four_on_one):
    check_verb(four_on_one)
    assert lease_seconds(four_on_one, "held", "_count") == {"0": 4}
    waited = lease_seconds(four_on_one, "wait")
    assert set(waited) == {"0"} and waited["0"] > 0.0
    jax_seen = four_on_one["status"]["ec"]["jax"]
    assert jax_seen["count"] == 1 and jax_seen["leases"] == {"0": 4}
    encodes = four_on_one["encodes"]
    assert [s["attrs"]["device"] for s in encodes] == [0, 0, 0, 0]
    assert held_at_once(encodes) == {0: 1}


def test_one_volume_of_four_fails_and_the_other_three_are_sealed(four_devices, six_on_four):
    vols = four_devices.fill("cf", 4)
    broken = sorted(vols)[1]
    os.unlink(four_devices.base("cf", broken) + ".dat")
    rc, text = four_devices.shell("lock\nec.encode -collection cf\nunlock\n")
    assert rc == 1
    error = next(ln for ln in text.splitlines() if ln.startswith("error: "))
    assert error.startswith(f"error: ec.encode: 1 of 4 volumes failed: volume {broken}: ")
    assert "/admin/ec/generate" in error
    sound = sorted(set(vols) - {broken})
    assert sorted(int(ln.split()[2].rstrip(":")) for ln in text.splitlines()
                  if ": shards spread" in ln) == sound
    status = four_devices.status()
    mounted = {e["id"]: e for e in status["ec_shards"]}
    for vid in sound:
        assert mounted[vid]["ec_index_bits"] == (1 << TOTAL) - 1
        base = four_devices.base("cf", vid)
        for shard in (0, 9, 13):
            with open(base + geometry.to_ext(shard), "rb") as f:
                assert f.read() == oracle_of(vols[vid])[shard].tobytes()
    # nothing of the failed one is half-made: no shard, no index, not mounted,
    # and the volume itself is still there (read-only, as the verb left it)
    assert broken not in mounted
    base = four_devices.base("cf", broken)
    left = sorted(name for name in os.listdir(four_devices.dir)
                  if name.startswith(f"cf_{broken}."))
    assert left == [f"cf_{broken}.idx"], left
    assert broken in {v["id"] for v in status["volumes"]}
    assert not os.path.exists(base + ".ecx")
    # the lock was given back: the next verb takes it
    rc, text = four_devices.shell("lock\nunlock\n")
    assert rc == 0, text


# --- reads take no lease ---------------------------------------------------------------------
def test_a_degraded_read_is_served_while_a_seal_holds_the_device(tmp_path, monkeypatch):
    """This process's jax has one device. While a pipeline (here: the test)
    holds its lease, a read that has to reconstruct is served, and a second
    pipeline is not let in."""
    from seaweedfs_tpu.ops.rs_kernel import RSCodec
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.shell.shell import run_shell

    monkeypatch.setenv("SEAWEEDFS_TPU_EC_BACKEND", "jax")
    monkeypatch.setattr(RSCodec, "_pick_backend", staticmethod(lambda: "jax"))
    master = MasterServer(port=0, pulse_seconds=1, volume_size_limit_mb=64)
    master.start()
    vs = VolumeServer([str(tmp_path / "v0")], master.url, port=0, pulse_seconds=1,
                      max_volume_count=10)
    vs.start()
    try:
        a = get_json(f"{master.url}/dir/assign?count={NEEDLES}")
        vid = int(a["fid"].split(",")[0])
        for i in range(NEEDLES):
            st, _, _ = http_request(
                "POST", f"http://{a['url']}/{a['fid']}" + (f"_{i}" if i else ""),
                payload(99, i))
            assert st == 201
        out = io.StringIO()
        assert run_shell(master.url, script=f"lock; ec.encode -volumeId {vid}; unlock",
                         out=out) == 0, out.getvalue()
        removed = post_json(f"{vs.url}/admin/ec/delete_shards",
                            {"volume": vid, "collection": "", "shards": [0]})
        assert removed["removed"] == [0]
        before = _samples(get_json_text(vs.url + "/metrics"))
        second = threading.Event()

        def second_pipeline() -> None:
            with device.lease():
                second.set()

        with device.lease() as (index, dev):
            assert index == 0 and dev == device.jax().local_devices()[0]
            t = threading.Thread(target=second_pipeline, daemon=True)
            t.start()
            st, _, body = http_request("GET", f"{vs.url}/{a['fid']}")
            assert st == 200 and body == payload(99, 0)
            after = _samples(get_json_text(vs.url + "/metrics"))
            assert _grown(before, after, "SeaweedFS_volume_ec_decode_bytes_total",
                          kernel="reconstruct-xla") > 0
            assert not second.is_set()
        assert second.wait(30)
        t.join(30)
        assert not t.is_alive()
    finally:
        vs.stop()
        master.stop()


# --- four handlers at once in one volume server ---------------------------------------------
def test_a_beat_walks_a_snapshot_of_the_volume_map(tmp_path):
    """Four `ec.encode`s of a collection end side by side: each deletes its
    volume and beats, on its own thread, while another's beat (or `/status`)
    walks the same map. Here the delete comes in the middle of the walk,
    where it raised `dictionary changed size during iteration`."""
    from seaweedfs_tpu.storage.store import Store

    store = Store([str(tmp_path)])
    try:
        for vid in (1, 2, 3):
            store.add_volume(vid)
        first = store.get_volume(1)
        real = first.max_needle_id

        def while_the_beat_walks():
            if store.get_volume(3) is not None:
                store.delete_volume(3)  # another handler's thread
            return real()

        first.max_needle_id = while_the_beat_walks
        assert [v["id"] for v in store.collect_heartbeat()["volumes"]] == [1, 2, 3]
        # the handler that deleted it beats after its delete, one beat at a time
        assert [v["id"] for v in store.collect_heartbeat()["volumes"]] == [1, 2]
    finally:
        store.close()
