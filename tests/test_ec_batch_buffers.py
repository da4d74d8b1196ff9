"""The EC pipeline's batch buffers outlive the pipeline (PR 34).

`encoder.BatchBuffers`: a pipeline takes its slots from the process's store
of idle batch buffers and gives back, at its end, those that nothing can
still read or write. Held here: (a) stale bytes of a kept buffer never reach
a shard, on every backend and in both kernel forms; (b) the counter
`SeaweedFS_volume_ec_pipeline_buffers_total{source}` says `kept` for every
batch of a process's second verb, and a larger batch regrows a slot once;
(c) the store never holds more than the pipelines that can run at once do,
on a server of four (forced CPU) devices and with a fifth pipeline beside
four; (d) a stage that raises leaves no buffer of an undrained batch behind;
(e) idle buffers go after the idle time, by the volume server's pulse, and
`/status` says so; (f) the online writer's backlog path goes through the same
store. Every shard file is held to `ops.gf256`'s numpy oracle.
"""

from __future__ import annotations

import functools
import os
import shutil
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ops import device, gf256, rs_kernel, rs_pallas
from seaweedfs_tpu.ops.rs_kernel import RSCodec
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.metrics import default_registry
from seaweedfs_tpu.storage.erasure_coding import encoder, geometry
from seaweedfs_tpu.storage.erasure_coding.online import OnlineEcWriter
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume
from tests.test_trace_phases import _grown, _samples

DATA, PARITY, TOTAL = (geometry.DATA_SHARDS_COUNT, geometry.PARITY_SHARDS_COUNT,
                       geometry.TOTAL_SHARDS_COUNT)
LARGE, SMALL = 4096, 64
# a large block is wider than a batch (its rows go through in column slices)
# and fifteen small rows fit one: both of the encode's reads, and a last
# batch of either kind that is shorter than the one before it in its slot
BATCH = 1000
SLOTS = encoder._QUEUE_DEPTH + 2
# two large rows, seven small ones and a padded tail; then a shorter volume
# whose tail is odd
LONG = LARGE * DATA * 2 + SMALL * DATA * 7 + 33
SHORT = LARGE * DATA + SMALL * DATA * 2 + 7
LOST = (3, 11)
BUFFERS = trace.EC_PIPELINE_BUFFERS
ext = geometry.to_ext


def oracle_shards(dat: bytes, large: int = LARGE, small: int = SMALL) -> list[bytes]:
    """The fourteen shard files of `dat` by the reference's row layout (large
    rows while more than one whole large row remains, then small rows, the
    last zero-padded) and the numpy table oracle."""
    cols = [bytearray() for _ in range(DATA)]
    pos = 0
    while pos < len(dat):
        block = large if len(dat) - pos > large * DATA else small
        row = dat[pos:pos + block * DATA].ljust(block * DATA, b"\0")
        for c in range(DATA):
            cols[c] += row[c * block:(c + 1) * block]
        pos += block * DATA
    data = np.array([np.frombuffer(bytes(c), dtype=np.uint8) for c in cols])
    parity = gf256.gf_matmul_bytes(gf256.parity_rows(DATA, PARITY), data)
    return [bytes(r) for r in np.concatenate([data, parity])]


def seeded(n: int, seed: int) -> bytes:
    return np.random.RandomState(seed).randint(0, 256, size=n, dtype=np.uint8).tobytes()


def shard_files(base: str) -> list[bytes]:
    out = []
    for i in range(TOTAL):
        with open(base + ext(i), "rb") as f:
            out.append(f.read())
    return out


def encode(d, dat: bytes, codec: RSCodec, batch: int = BATCH,
           large: int = LARGE, small: int = SMALL) -> str:
    os.makedirs(d, exist_ok=True)
    base = os.path.join(str(d), "1")
    with open(base + ".dat", "wb") as f:
        f.write(dat)
    encoder.write_ec_files(base, codec=codec, large_block_size=large,
                           small_block_size=small, batch=batch)
    return base


def poison(store: encoder.BatchBuffers) -> int:
    """Every idle buffer overwritten with 0xFF; how many there were."""
    with store._lock:
        for buf in store._idle:
            buf.fill(0xFF)
        return len(store._idle)


def counted() -> dict:
    return _samples(default_registry().render())


def sources(before: dict, after: dict) -> tuple[int, int]:
    return (int(_grown(before, after, BUFFERS, source="kept")),
            int(_grown(before, after, BUFFERS, source="fresh")))


@pytest.fixture()
def store(monkeypatch) -> encoder.BatchBuffers:
    """A store of this test's own, in the process's place."""
    own = encoder.BatchBuffers()
    monkeypatch.setattr(encoder, "batch_buffers", own)
    return own


# --- (a) stale bytes never reach a shard ----------------------------------------------
FORMS = ["numpy", "jax-xla", "jax-pallas"]
VERBS = ["encode", "encode-shorter-odd-tail", "rebuild-two", "encode-again"]


@pytest.fixture(scope="module", params=FORMS)
def poisoned_verbs(request, tmp_path_factory):
    """Four verbs in a row through one store, every idle buffer overwritten
    with 0xFF before each: {verb: (shard files got, shard files wanted, idle
    buffers poisoned before it)}."""
    form = request.param
    mp = pytest.MonkeyPatch()
    own = encoder.BatchBuffers()
    mp.setattr(encoder, "batch_buffers", own)
    if form == "jax-pallas":  # the form a TPU runs, interpreted, at a small tile
        from jax.experimental import pallas as pl

        mp.setattr(pl, "pallas_call", functools.partial(pl.pallas_call, interpret=True))
        mp.setattr(rs_kernel, "transform_kernel", lambda: "pallas")
        mp.setattr(rs_kernel, "TILE", 512)
        rs_pallas.compiled.cache_clear()
    codec = RSCodec(backend="numpy" if form == "numpy" else "jax")
    tmp = tmp_path_factory.mktemp("verbs")
    long_dat, short_dat = seeded(LONG, 341), seeded(SHORT, 342)
    out = {}
    try:
        n = poison(own)
        base = encode(tmp / "a", long_dat, codec)
        out["encode"] = (shard_files(base), oracle_shards(long_dat), n)
        n = poison(own)
        short = encode(tmp / "b", short_dat, codec)
        out["encode-shorter-odd-tail"] = (shard_files(short), oracle_shards(short_dat), n)
        for i in LOST:
            os.unlink(base + ext(i))
        n = poison(own)
        assert sorted(encoder.rebuild_ec_files(base, codec=codec, chunk=BATCH)) == list(LOST)
        out["rebuild-two"] = (shard_files(base), oracle_shards(long_dat), n)
        n = poison(own)
        again = encode(tmp / "c", long_dat, codec)
        out["encode-again"] = (shard_files(again), oracle_shards(long_dat), n)
    finally:
        if form == "jax-pallas":
            rs_pallas.compiled.cache_clear()
        mp.undo()
    return out


@pytest.mark.parametrize("verb", VERBS)
def test_a_poisoned_kept_buffer_never_reaches_a_shard(poisoned_verbs, verb):
    got, want, poisoned = poisoned_verbs[verb]
    # the first verb found the store empty, every later one the slots of the
    # verb before it
    assert poisoned == (0 if verb == "encode" else SLOTS)
    assert len(got) == TOTAL
    for i in range(TOTAL):
        assert got[i] == want[i], f"shard {i} after {verb}"


# --- (b) the counter ------------------------------------------------------------------
def rebuild_two(base: str, codec: RSCodec) -> None:
    for i in LOST:
        os.unlink(base + ext(i))
    assert sorted(encoder.rebuild_ec_files(base, codec=codec, chunk=BATCH)) == list(LOST)


@pytest.mark.parametrize("first,second", [
    ("encode", "encode"), ("encode", "rebuild"), ("rebuild", "encode")])
def test_the_second_verb_reads_every_batch_into_a_kept_buffer(store, tmp_path, first, second):
    codec = RSCodec(backend="numpy")
    dat = seeded(LONG, 343)
    sealed = encode(tmp_path / "sealed", dat, codec)
    with store._lock:
        store._idle.clear()  # the first verb below is a process's first

    def verb(kind: str, d: str) -> None:
        if kind == "encode":
            encode(tmp_path / d, dat, codec)
        else:
            shutil.copytree(tmp_path / "sealed", tmp_path / d)
            rebuild_two(os.path.join(str(tmp_path / d), "1"), codec)

    before = counted()
    verb(first, "first")
    kept, fresh = sources(before, counted())
    # an empty store: each slot is new once, then goes round
    assert fresh == SLOTS and kept > 0
    assert store.report()["idle"] == SLOTS
    before = counted()
    verb(second, "second")
    kept, fresh = sources(before, counted())
    assert fresh == 0 and kept >= SLOTS
    assert shard_files(os.path.join(str(tmp_path / "second"), "1")) == shard_files(sealed)


def test_a_larger_batch_after_a_smaller_one_regrows_each_slot_once(store, tmp_path):
    codec = RSCodec(backend="numpy")
    dat = seeded(SMALL * DATA * 40, 344)  # forty small rows and nothing else
    ten_rows, twenty_rows = SMALL * 10, SMALL * 20
    before = counted()
    encode(tmp_path / "a", dat, codec, batch=ten_rows)  # four batches, four slots
    assert sources(before, counted()) == (0, 4)
    assert {b.nbytes for b in store._idle} == {ten_rows * DATA}
    before = counted()
    base = encode(tmp_path / "b", dat, codec, batch=twenty_rows)  # two batches
    assert sources(before, counted()) == (0, 2)
    assert sorted(b.nbytes for b in store._idle) == (
        [ten_rows * DATA] * 2 + [twenty_rows * DATA] * 2)
    before = counted()
    encode(tmp_path / "c", dat, codec, batch=twenty_rows)  # the largest go out first
    assert sources(before, counted()) == (2, 0)
    assert shard_files(base) == oracle_shards(dat)


# --- (c) the bound ---------------------------------------------------------------------
def test_five_pipelines_at_once_leave_the_slots_of_four(store, tmp_path, monkeypatch):
    """Four devices: the store keeps what four pipelines hold. A fifth beside
    them (a caller's own codec takes no lease) finds its buffers dropped."""
    monkeypatch.setattr(device, "pipelines_at_once", lambda: 4)
    assert store.bound() == (4 * SLOTS, 4 * SLOTS * encoder.DEFAULT_BATCH_DEVICE * DATA)
    dat = seeded(LONG, 345)
    all_reading = threading.Barrier(5)
    waited = threading.local()
    pread = encoder._pread_padded

    def pread_together(*args):
        if not getattr(waited, "done", False):
            waited.done = True
            all_reading.wait(60)  # five pipelines each hold a slot by now
        pread(*args)

    monkeypatch.setattr(encoder, "_pread_padded", pread_together)
    errors = []

    def seal(i: int) -> None:
        try:
            encode(tmp_path / str(i), dat, RSCodec(backend="numpy"))
        except BaseException as e:  # noqa: BLE001 - shown below
            errors.append(e)

    peak = []
    threads = [threading.Thread(target=seal, args=(i,), daemon=True) for i in range(5)]
    for t in threads:
        t.start()
    while any(t.is_alive() for t in threads):
        peak.append(store.report())
        time.sleep(0.001)
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    assert not errors, errors
    peak.append(store.report())
    assert all(r["idle"] <= 4 * SLOTS and r["idle_bytes"] <= r["bound_bytes"] for r in peak)
    assert store.report()["idle"] == 4 * SLOTS  # twenty came back
    want = oracle_shards(dat)
    for i in range(5):
        assert shard_files(os.path.join(str(tmp_path / str(i)), "1")) == want


def test_what_comes_back_beyond_the_bound_is_dropped_smallest_first(store, monkeypatch):
    slot = encoder.DEFAULT_BATCH_DEVICE * DATA  # 320 MiB of address space, no page touched
    host = encoder.DEFAULT_BATCH_HOST * DATA
    assert store.bound() == (SLOTS, SLOTS * slot)  # this process's jax has one device
    store.give([np.empty(host, dtype=np.uint8) for _ in range(2)])
    store.give([np.empty(slot, dtype=np.uint8) for _ in range(3)])
    assert sorted(b.nbytes for b in store._idle) == [host, slot, slot, slot]
    # by bytes too: one buffer above a slot's size takes the room of two
    store.give([np.empty(slot + host, dtype=np.uint8)])
    report = store.report()
    assert report == {"idle": 3, "idle_bytes": 3 * slot + host, "bound_bytes": SLOTS * slot}
    assert store.take().nbytes == slot + host  # the largest first
    assert store.report()["idle"] == 2


def test_under_many_threads_no_buffer_is_handed_out_twice(store, monkeypatch):
    import sys

    monkeypatch.setattr(device, "pipelines_at_once", lambda: 2)
    slots = 2 * SLOTS
    held: set[int] = set()
    guard = threading.Lock()
    failures = []
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def pipeline(k: int) -> None:
        try:
            for round_ in range(60):
                mine = [store.take() for _ in range(SLOTS)]
                mine = [b if b is not None else np.empty(64 + k, dtype=np.uint8)
                        for b in mine]
                ids = {id(b) for b in mine}
                with guard:
                    assert len(ids) == SLOTS and not ids & held
                    held.update(ids)
                report = store.report()
                assert report["idle"] <= slots and report["idle_bytes"] <= report["bound_bytes"]
                with guard:
                    held.difference_update(ids)
                store.give(mine)
        except BaseException as e:  # noqa: BLE001 - shown below
            failures.append(e)

    try:
        threads = [threading.Thread(target=pipeline, args=(k,), daemon=True) for k in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(was)
    assert not failures, failures
    assert store.report()["idle"] == slots and not held


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    from tests.test_ec_devices import Cluster

    c = Cluster(tmp_path_factory.mktemp("four"), 4)
    try:
        yield c
    finally:
        c.stop()


def test_four_pipelines_on_four_devices_stay_inside_the_bound(four_devices):
    from tests.test_served_device_path import oracle_shards as served_oracle

    c = four_devices
    polled, done = [], threading.Event()

    def poll() -> None:
        while not done.is_set():
            polled.append(c.status()["ec"]["pipeline_buffers"])
            time.sleep(0.02)

    vols = c.fill("first", 4)
    watcher = threading.Thread(target=poll, daemon=True)
    watcher.start()
    try:
        before = c.metrics()
        rc, text = c.shell("lock\nec.encode -collection first\nunlock\n")
        assert rc == 0, text
        middle = c.metrics()
        more = c.fill("second", 4)
        rc, text = c.shell("lock\nec.encode -collection second\nunlock\n")
        assert rc == 0, text
        after = c.metrics()
    finally:
        done.set()
        watcher.join(30)
    assert not watcher.is_alive()
    status = c.status()["ec"]
    assert status["jax"]["count"] == 4
    polled.append(status["pipeline_buffers"])
    slot = encoder.DEFAULT_BATCH_DEVICE * DATA
    for r in polled:
        assert r["bound_bytes"] in (SLOTS * slot, 4 * SLOTS * slot)  # before jax starts: one
        assert r["idle_bytes"] <= r["bound_bytes"] and r["idle"] <= 4 * SLOTS
    # a volume here is one batch, so a verb hands out four slots: new ones in
    # the first verb (all four where the pipelines overlap, as they do), and
    # in the second every buffer the first one left is handed out again;
    # every buffer ever made is idle at the end, none lost, none dropped
    (kept1, fresh1), (kept2, fresh2) = sources(before, middle), sources(middle, after)
    assert kept1 + fresh1 == 4 and kept2 + fresh2 == 4
    assert fresh1 >= 1 and kept2 >= fresh1
    made = fresh1 + fresh2
    assert polled[-1] == {"idle": made, "idle_bytes": made * slot,
                          "bound_bytes": 4 * SLOTS * slot}
    for collection, filled in (("first", vols), ("second", more)):
        for vid, vol in filled.items():
            want = served_oracle(vol["dat"])
            got = shard_files(c.base(collection, vid))
            assert all(got[i] == want[i].tobytes() for i in range(TOTAL)), (collection, vid)


# --- (d) a stage that raises ------------------------------------------------------------
class Boom(RuntimeError):
    pass


class WatchedCodec:
    """The numpy codec, noting which buffer every batch handed to it lies in
    and whether its handle was drained, and raising where it is told to."""

    def __init__(self, fail_encode_at: int = -1, fail_result_at: int = -1) -> None:
        self.inner = RSCodec(backend="numpy")
        self.backend, self.kernel_label = "numpy", self.inner.kernel_label
        self.data_shards, self.parity_shards = DATA, PARITY
        self.fail_encode_at, self.fail_result_at = fail_encode_at, fail_result_at
        self.lock = threading.Lock()
        self.enqueued = self.results = 0
        self.undrained: dict[int, int] = {}
        self.alive = []  # every array seen: no address is used twice

    def _watch(self, arr: np.ndarray, make):
        address = arr.__array_interface__["data"][0]
        with self.lock:
            self.alive.append(arr)
            self.undrained[address] = self.undrained.get(address, 0) + 1
            n = self.enqueued
            self.enqueued += 1
        if n == self.fail_encode_at:
            raise Boom("encode_job")  # as a device error after the first puts
        handle = make()
        watched = self

        class Handle:
            def result(self):
                with watched.lock:
                    n = watched.results
                    watched.results += 1
                if n == watched.fail_result_at:
                    raise Boom("write_job")
                out = handle.result()
                with watched.lock:
                    watched.undrained[address] -= 1
                return out

        return Handle()

    def encode_rows_async(self, buf, block, nrows):
        return self._watch(buf, lambda: self.inner.encode_rows_async(buf, block, nrows))

    def encode2d_async(self, data):
        return self._watch(data, lambda: self.inner.encode2d_async(data))

    def apply2d_async(self, matrix, data):
        return self._watch(data, lambda: self.inner.apply2d_async(matrix, data))


def fail_nth(monkeypatch, owner, name: str, n: int, what: str) -> None:
    real, calls = getattr(owner, name), [0]

    def failing(*args, **kwargs):
        calls[0] += 1
        if calls[0] == n:
            raise Boom(what)
        return real(*args, **kwargs)

    monkeypatch.setattr(owner, name, failing)


@pytest.mark.parametrize("verb", ["encode", "rebuild"])
@pytest.mark.parametrize("stage", ["read_job", "encode_job", "write_job", "write_job-pwrite"])
def test_a_stage_that_raises_leaves_no_undrained_buffer_behind(
        store, tmp_path, monkeypatch, verb, stage):
    plain = RSCodec(backend="numpy")
    dat = seeded(LONG, 346)
    base = encode(tmp_path / "v", dat, plain)  # leaves its four slots in the store
    want = oracle_shards(dat)
    if verb == "rebuild":
        for i in LOST:
            os.unlink(base + ext(i))
    watched = WatchedCodec(fail_encode_at=3 if stage == "encode_job" else -1,
                           fail_result_at=2 if stage == "write_job" else -1)
    with monkeypatch.context() as mp:
        if stage == "read_job":
            fail_nth(mp, encoder, "_pread_padded" if verb == "encode" else "_pread_exact",
                     12, stage)
        if stage == "write_job-pwrite":
            fail_nth(mp, encoder._ShardWriters, "pwrite", 9, stage)
        with pytest.raises(Boom, match=stage):
            if verb == "encode":
                encoder.write_ec_files(base, codec=watched, large_block_size=LARGE,
                                       small_block_size=SMALL, batch=BATCH)
            else:
                encoder.rebuild_ec_files(base, codec=watched, chunk=BATCH)
    assert not [n for n in os.listdir(tmp_path / "v") if n.endswith(".tmp")]
    assert not [t.name for t in threading.enumerate()
                if t.name in ("ec-reader", "ec-writer")]
    live = {a for a, n in watched.undrained.items() if n}
    with store._lock:
        idle = {b.__array_interface__["data"][0] for b in store._idle}
    assert len(idle) <= SLOTS and not idle & live
    if stage in ("encode_job", "write_job"):
        assert live  # the batch that failed, at the least
    # and the next verb, through what the failed one left, is whole
    poison(store)
    if verb == "encode":
        encoder.write_ec_files(base, codec=plain, large_block_size=LARGE,
                               small_block_size=SMALL, batch=BATCH)
    else:
        assert sorted(encoder.rebuild_ec_files(base, codec=plain, chunk=BATCH)) == list(LOST)
    assert shard_files(base) == want


# --- (e) the idle time -------------------------------------------------------------------
class Clock:
    now = 1000.0

    def __call__(self) -> float:
        return self.now


@pytest.mark.parametrize("touch", ["give", "take"])
def test_idle_buffers_go_a_minute_after_the_last_pipeline(touch):
    clock = Clock()
    store = encoder.BatchBuffers(clock)
    store.give([np.empty(100, dtype=np.uint8) for _ in range(3)])
    clock.now += store.IDLE_SECONDS - 1
    store.expire()
    assert store.report()["idle"] == 3
    if touch == "take":  # a pipeline that starts counts as much as one that ends
        assert store.take().nbytes == 100
    else:
        store.give([])
    left = store.report()["idle"]
    clock.now += store.IDLE_SECONDS - 1
    store.expire()
    assert store.report()["idle"] == left
    clock.now += 1
    store.expire()
    assert store.report() == {"idle": 0, "idle_bytes": 0,
                              "bound_bytes": store.bound()[1]}
    assert store.take() is None


def test_the_servers_pulse_lets_idle_buffers_go_and_status_says_so(tmp_path, monkeypatch):
    from seaweedfs_tpu.server.httpd import get_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer

    clock = Clock()
    store = encoder.BatchBuffers(clock)
    monkeypatch.setattr(encoder, "batch_buffers", store)
    master = MasterServer(port=0, pulse_seconds=1)
    master.start()
    vs = VolumeServer([str(tmp_path / "v")], master.url, port=0, pulse_seconds=1)
    vs.start()

    def buffers() -> dict:
        return get_json(vs.url + "/status")["ec"]["pipeline_buffers"]

    try:
        assert buffers()["idle"] == 0
        encode(tmp_path / "sealed", seeded(LONG, 347), RSCodec(backend="numpy"))
        held = buffers()
        assert held["idle"] == SLOTS and held["idle_bytes"] == SLOTS * BATCH * DATA
        assert 0 < held["idle_bytes"] <= held["bound_bytes"]
        clock.now += store.IDLE_SECONDS - 1
        time.sleep(2.5)  # two pulses: too early, so nothing goes
        assert buffers() == held
        clock.now += 1
        deadline = time.monotonic() + 30
        while buffers()["idle"]:
            assert time.monotonic() < deadline, "the pulse never let the buffers go"
            time.sleep(0.1)
        assert buffers() == {"idle": 0, "idle_bytes": 0, "bound_bytes": held["bound_bytes"]}
    finally:
        vs.stop()
        master.stop()


# --- (f) the online writer's backlog path ------------------------------------------------
ONLINE_BLOCK = 4096


def backlog_volume(d) -> Volume:
    """`tests/test_ec_online.py`'s deep backlog at the depth the writer asks
    for before it catches up through `_run_pipeline` (more than two host
    batches of rows: over 20 MiB), written with no pump."""
    os.makedirs(d)
    v = Volume(str(d), "", 1)
    rng = np.random.default_rng(348)
    for i in range(1, 24):
        v.write_needle(Needle(cookie=0x77, id=i, data=rng.bytes(1 << 20)))
    return v


@pytest.mark.parametrize("before", ["offline-encode", "offline-rebuild"])
def test_the_online_backlog_goes_through_kept_buffers(store, tmp_path, before):
    plain = RSCodec(backend="numpy")
    # an offline verb of a larger batch leaves its slots; the backlog path
    # asks for one host batch a slot and is handed those
    base = encode(tmp_path / "offline", seeded(LONG, 349), plain,
                  batch=encoder.DEFAULT_BATCH_HOST)
    if before == "offline-rebuild":
        for i in LOST:
            os.unlink(base + ext(i))
        encoder.rebuild_ec_files(base, codec=plain)
    idle = poison(store)
    assert idle >= 1
    v = backlog_volume(tmp_path / "online")
    w = OnlineEcWriter(v, block_size=ONLINE_BLOCK, max_lag_stripes=10_000)
    batch_rows = encoder.DEFAULT_BATCH_HOST // ONLINE_BLOCK
    rows = (v.size() - w.watermark) // w.stripe
    assert rows > 2 * batch_rows
    batches = -(-rows // batch_rows)
    counted_before = counted()
    w.pump(force=True)
    # the pipeline ran (nothing else counts), on the offline verb's buffers
    # as far as they went
    assert sources(counted_before, counted()) == (
        min(idle, batches), batches - min(idle, batches))
    w.seal()
    with open(v.base_name + ".dat", "rb") as f:
        dat = f.read()
    assert shard_files(v.base_name) == oracle_shards(dat, ONLINE_BLOCK, ONLINE_BLOCK)
    v.close()
    # and the reverse: what the backlog path left serves an offline verb
    assert poison(store) >= 1
    again = encode(tmp_path / "again", seeded(SHORT, 350), plain)
    assert shard_files(again) == oracle_shards(seeded(SHORT, 350))
