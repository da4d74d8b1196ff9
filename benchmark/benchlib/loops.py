"""The one general traffic generator. A traffic mix is a data file under
benchmark/traffic/ that names a loop kind and its parameters; a cell's window
is that loop repeated in place until the window closes.

Loop kinds:
  seal    one closed-loop sealer: one script that seals every volume of the
          configuration (`ec.encode -volumeId {vid}` of its one volume, or
          `ec.encode -collection {collection}` of its N), then (between
          verbs, inside the window) each volume restored under a new id
  repair  one closed-loop repairer: remove one shard, `ec.rebuild`
  read    N closed-loop readers of needles drawn from the seed

`repair` and `read` drive a configuration of one volume.

Verb loops close on a verb's end: verbs run back to back from the window's
start, the window closes when the first verb finishes at or after the asked
seconds, and the rate is all bytes of completed verbs over all that time.
"""

from __future__ import annotations

import http.client
import os
import threading
import time

import numpy as np

from . import cluster, promtext, reference, stats, volume

ALL_SHARDS = list(range(reference.TOTAL))
PARITY_SHARDS = ALL_SHARDS[reference.DATA:]
# how set-up seals the one volume of a mix that repairs or reads it
SEAL_ONE = "lock\nec.encode -volumeId {vid}\nunlock\n"
CGROUP_MEMORY_STAT = "/sys/fs/cgroup/memory.stat"


def first_encode(traffic: dict) -> str:
    """The script of set-up's first encode: a seal mix's own verb."""
    return traffic["verb"] if traffic["loop"] == "seal" else SEAL_ONE


class Tracer:
    """Asks the server, which holds the chip, to trace itself for `seconds`
    (`GET /debug/pprof/device`) while the loop goes on. Meanwhile it reads
    `/metrics` a few times a second, each page with the wall clock beside it:
    the trace says when it began (in wall-clock time), so the counters can be
    read as they stood at the traced span's own ends."""

    def __init__(self, server: cluster.Server, seconds: float,
                 poll_seconds: float = 0.0) -> None:
        self.server, self.seconds = server, seconds
        self.blob: bytes | None = None
        self.error = ""
        self.pages: list[tuple[float, str]] = [(time.time(), server.metrics())]
        self._done = threading.Event()
        self._thread = threading.Thread(target=self._fetch, daemon=True)
        self._thread.start()
        self._poller = None
        if poll_seconds > 0:
            self._poller = threading.Thread(
                target=self._poll, args=(poll_seconds,), daemon=True)
            self._poller.start()

    def _fetch(self) -> None:
        try:
            status, body = cluster.http_call(
                "GET", self.server.volume,
                f"/debug/pprof/device?seconds={self.seconds:.3f}",
                timeout=self.seconds + 240)
            if status == 200:
                self.blob = body
            else:
                self.error = f"{status} {body[:200]!r}"
        except OSError as e:
            self.error = f"{type(e).__name__}: {e}"
        finally:
            self._done.set()

    def _poll(self, every: float) -> None:
        # only while the profiler is on: the first `seconds` and a little more
        until = time.time() + self.seconds + 1.5
        while not self._done.wait(every) and time.time() < until:
            try:
                self.pages.append((time.time(), self.server.metrics()))
            except (OSError, cluster.RunError):
                return

    def finish(self) -> None:
        self._thread.join(self.seconds + 300)
        if self._poller is not None:
            self._poller.join(30)
        self.pages.append((time.time(), self.server.metrics()))

    def page_at(self, t: float) -> dict:
        """`/metrics` as it stood at wall-clock time t, between the two pages
        read around it."""
        pages = sorted(self.pages)
        at = next((i for i, (pt, _) in enumerate(pages) if pt >= t), len(pages) - 1)
        lo, hi = pages[max(0, at - 1)], pages[at]
        span = hi[0] - lo[0]
        return promtext.interpolate(
            promtext.parse(lo[1]), promtext.parse(hi[1]),
            (t - lo[0]) / span if span > 0 else 1.0)


def page_cache_now() -> dict | None:
    """`file_dirty` and `file_writeback` (bytes) of the runner's cgroup, which
    the server child shares: what a stalled shard write waits behind. None
    where the machine does not show them."""
    try:
        with open(CGROUP_MEMORY_STAT) as f:
            stat = dict(line.split() for line in f)
        return {k: int(stat[k]) for k in ("file_dirty", "file_writeback")}
    except (OSError, KeyError, ValueError):
        return None


class VerbLoop:
    """What seal and repair share: the window rule, the traced verb."""

    compares_shards = True  # the comparison wants the reference's 14 shards

    def __init__(self, run) -> None:
        self.run = run
        self.unit_bytes = run.dat_bytes  # what one completed verb counts for
        self.server: cluster.Server = run.server
        self.traffic: dict = run.traffic
        self.verbs: list[dict] = []
        self.tracer: Tracer | None = None
        self.elapsed = 0.0

    def one(self, k: int) -> dict:  # pragma: no cover - overridden
        raise NotImplementedError

    def window(self, seconds: float, trace: bool) -> None:
        pages = page_cache_now()
        if pages is not None:
            self.run.notes["page_cache"] = {"before_window": pages, "after_verb": []}
        t0 = time.perf_counter()
        k = 0
        while True:
            # a traced run holds one whole verb, the second, inside the
            # traced span, and goes on until it has one
            self._cycle(k, traced=trace and k == 1)
            self.elapsed = time.perf_counter() - t0
            k += 1
            if not self.verbs[-1]["ok"]:
                break
            if self.elapsed >= seconds and not (trace and self.tracer is None):
                break
            self.between(k)

    def _cycle(self, k: int, traced: bool) -> None:
        if traced:
            # as long as the last verb took, with room for the profiler to
            # start and for this verb to run slower while it is traced
            lead = self.traffic.get("trace_lead_seconds", 0.5)
            span = self.verbs[-1]["cycle_seconds"] * 1.15 + lead + 1.0
            self.tracer = Tracer(self.server, span)
            time.sleep(lead)
        t0, wall0 = time.perf_counter(), time.time()
        verb = self.one(k)
        verb["cycle_seconds"] = time.perf_counter() - t0
        verb["traced"] = traced
        self.verbs.append(verb)
        if "page_cache" in self.run.notes:
            self.run.notes["page_cache"]["after_verb"].append(page_cache_now())
        if traced:
            # the traced span the metrics are read over is this verb itself
            self.tracer.wall_span = (wall0, wall0 + verb["cycle_seconds"])
            self.tracer.finish()

    def between(self, k: int) -> None:
        """Inside the window, after verb k-1 and before verb k."""

    def verb(self, k: int, ok: bool, text: str, seconds: float) -> dict:
        if not ok:
            self.run.log(f"verb {k} failed: {text[-400:]!r}")
        return {"ok": ok, "seconds": seconds,
                "volumes": [vol.vid for vol in self.run.vols]}

    def end_to_end(self) -> dict:
        done = [v for v in self.verbs if v["ok"]]
        return {
            "bytes_per_s_1e9": len(done) * self.unit_bytes / self.elapsed / 1e9,
            "verbs": len(done),
            "window_seconds": self.elapsed,
        }

    @property
    def attempted(self) -> int:
        return len(self.verbs)

    @property
    def failed(self) -> int:
        return sum(1 for v in self.verbs if not v["ok"])


class SealLoop(VerbLoop):
    def __init__(self, run) -> None:
        super().__init__(run)
        # (verb index, directory of links, the (volume, shard) files linked)
        self.kept: list[tuple[int, str, list[tuple[volume.Vol, int]]]] = []
        # Two seals are compared after the window: the last, whole, linked
        # once the clock has stopped, and one of the first three, drawn from
        # the seed and linked inside the window. A linked file's written
        # pages cannot be dropped when the restore deletes the seal, so the
        # host has to write them back while the next verb dirties its own:
        # a whole early seal (1.5 GB a volume) stalled the verb after it
        # (PERF.md 5 item 0). What is kept inside a window is therefore
        # bounded in bytes: the four parity shards (what the device computed)
        # of one volume drawn from the seed, 0.43 GB
        self.early = run.seed % 3
        rng = np.random.Generator(np.random.SFC64([run.seed, 5]))
        self.early_volume = int(rng.integers(len(run.vols)))

    def prepare(self) -> None:
        """Set-up after the first encode: bring the volume back."""
        self.restore()

    def restore(self) -> None:
        """Drop every sealed copy, link each kept pair under a new id, mount."""
        srv, run = self.server, self.run
        for vol in run.vols:
            cluster.post_json(srv.volume, "/admin/ec/delete_shards", {
                "volume": vol.vid, "collection": run.collection,
                "shards": ALL_SHARDS, "delete_index": True})
        # ids the master never hands out at this scale: assign grows a few
        # volumes beside the filled ones
        first = max(max(vol.vid for vol in run.vols), 1000) + 1
        for vol in run.vols:
            vol.vid = first + vol.number
            base = volume.file_base(srv.dir, run.collection, vol.vid)
            for ext in (".dat", ".idx"):
                os.link(vol.kept_base + ext, base + ext)
            cluster.post_json(srv.volume, "/admin/volume/mount",
                              {"volume": vol.vid, "collection": run.collection})

    def keep_links(self, k: int, files: list[tuple[volume.Vol, int]]) -> None:
        """Hard links (no byte is written) to those shard files of seal k."""
        d = os.path.join(self.run.workdir, f"seal_{k}")
        os.makedirs(d)
        for vol, s in files:
            base = volume.file_base(self.server.dir, self.run.collection, vol.vid)
            os.link(f"{base}.ec{s:02d}", self.kept_shard(d, vol, s))
        self.kept.append((k, d, files))

    @staticmethod
    def kept_shard(d: str, vol: volume.Vol, shard: int) -> str:
        return os.path.join(
            d, (f"v{vol.number}." if vol.number else "") + f"ec{shard:02d}")

    def one(self, k: int) -> dict:
        return self.verb(k, *self.run.seal_verb(self.traffic["verb"], f"verb_{k}.log"))

    def between(self, k: int) -> None:
        if k - 1 == self.early:
            self.keep_links(k - 1, [(self.run.vols[self.early_volume], s)
                                    for s in PARITY_SHARDS])
        self.restore()

    def after_window(self) -> None:
        if self.verbs and self.verbs[-1]["ok"]:
            self.keep_links(len(self.verbs) - 1, [
                (vol, s) for vol in self.run.vols for s in ALL_SHARDS])
        seals = self.kept_files()
        self.run.notes["kept_seal_files"] = [len(seal) for seal in seals]
        self.run.notes["kept_seal_bytes"] = [
            sum(os.path.getsize(path) for path, _, _ in seal) for seal in seals]

    def kept_files(self) -> list[list[tuple[str, volume.Vol, int]]]:
        """Of each kept seal, its (link, volume, shard)s."""
        return [[(self.kept_shard(d, vol, s), vol, s) for vol, s in files]
                for _, d, files in self.kept]

    def compare(self, want: list[np.ndarray]) -> dict:
        differing = reference.files_differing(
            [(path, want[vol.number][s])
             for seal in self.kept_files() for path, vol, s in seal])
        return {"shard_files_differing": (differing, 0),
                "seals_compared": (len(self.kept), None)}

    def device_bytes_expected(self) -> float:
        return sum(1 for v in self.verbs if v["ok"]) * float(self.unit_bytes)

    def produced_shard_path(self) -> str:
        return self.kept_shard(self.kept[-1][1], self.run.vols[-1], 11)

    def early_parity_path(self) -> str:
        if len(self.kept) < 2:
            raise cluster.RunError("the window kept no early seal")
        return self.kept_shard(self.kept[0][1], self.run.vols[self.early_volume], 11)


def one_volume(run, kind: str) -> volume.Vol:
    if len(run.vols) != 1:
        raise cluster.RunError(f"the `{kind}` loop drives a configuration of one"
                               f" volume; this one holds {len(run.vols)}")
    return run.vols[0]


class RepairLoop(VerbLoop):
    def __init__(self, run) -> None:
        super().__init__(run)
        self.vol = one_volume(run, "repair")
        self.shard_bytes = reference.shard_file_size(run.dat_bytes)
        # the mix's shard ids in an order drawn from the seed. The program
        # compiles a device program for every coefficient matrix, that is for
        # every lost shard, so each id of the mix is rebuilt once in set-up
        shards = [int(s) for s in self.traffic["shards"]]
        rng = np.random.Generator(np.random.SFC64([run.seed, 2]))
        self.order = [shards[int(j)] for j in rng.permutation(len(shards))]

    def prepare(self) -> None:
        for j, shard in enumerate(self.order):
            verb = self.cycle(-1 - j, shard)
            if not verb["ok"]:
                raise cluster.RunError(f"the warm-up ec.rebuild of shard {shard} failed")

    def cycle(self, k: int, shard: int) -> dict:
        removed = cluster.post_json(self.server.volume, "/admin/ec/delete_shards", {
            "volume": self.vol.vid, "collection": self.run.collection,
            "shards": [shard]})
        rc, text, seconds = self.run.shell(self.traffic["verb"], f"verb_{k}.log")
        verb = self.verb(k, rc == 0 and f"rebuilt shards [{shard}]" in text, text, seconds)
        verb["shard"] = shard
        verb["ok"] = verb["ok"] and removed.get("removed") == [shard]
        return verb

    def one(self, k: int) -> dict:
        return self.cycle(k, self.order[k % len(self.order)])

    def after_window(self) -> None:
        pass

    def shard_path(self, shard: int) -> str:
        return volume.file_base(
            self.server.dir, self.run.collection, self.vol.vid) + f".ec{shard:02d}"

    def compare(self, want: list[np.ndarray]) -> dict:
        differing = reference.files_differing(
            [(self.shard_path(s), want[0][s]) for s in ALL_SHARDS])
        rebuilt = {v["shard"] for v in self.verbs if v["ok"]}
        return {"shard_files_differing": (differing, 0),
                "shards_rebuilt_in_window": (len(rebuilt), None)}

    def device_bytes_expected(self) -> float:
        return sum(1 for v in self.verbs if v["ok"]) * float(
            self.shard_bytes * reference.DATA)

    def produced_shard_path(self) -> str:
        return self.shard_path(self.verbs[-1]["shard"])


class NeedleReader:
    """`GET /<fid>` of seeded needles, each body compared with its payload."""

    def __init__(self, run) -> None:
        self.run = run
        self.first_errors: list[str] = []
        self.compare_seconds: list[float] = []  # what each comparison cost

    def get(self, conn: http.client.HTTPConnection, i: int) -> tuple[float, int]:
        """(seconds from send to last byte, 0 right | 1 wrong | 2 failed) of
        needle i, numbered through all the run's volumes."""
        fid, want = self.run.needle(i)
        t0 = time.perf_counter()
        try:
            conn.request("GET", "/" + fid)
            resp = conn.getresponse()
            body = resp.read()
            dt = time.perf_counter() - t0
        except (OSError, http.client.HTTPException) as e:
            conn.close()
            self._note(f"needle {i}: {type(e).__name__}: {e}")
            return time.perf_counter() - t0, 2
        if resp.status != 200:
            self._note(f"needle {i}: {resp.status} {body[:120]!r}")
            return dt, 2
        t0 = time.perf_counter()
        same = volume.same_bytes(body, want)
        self.compare_seconds.append(time.perf_counter() - t0)
        return dt, 0 if same else 1

    def _note(self, what: str) -> None:
        if len(self.first_errors) < 5:
            self.first_errors.append(what)
            self.run.log(f"read failed: {what}")

    def read_all(self, needles: list[int], threads: int) -> int:
        """Reads each needle once; how many were wrong or failed."""
        bad = [0] * threads

        def reader(t: int) -> None:
            conn = http.client.HTTPConnection(self.run.server.volume, timeout=300)
            try:
                for i in needles[t::threads]:
                    bad[t] += self.get(conn, i)[1] != 0
            finally:
                conn.close()

        ts = [threading.Thread(target=reader, args=(t,)) for t in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return sum(bad)


class ReadLoop:
    """N closed-loop readers. Each draws its needles from the seed, times
    every read from send to last byte, and compares the body with the payload
    once the clock has stopped."""

    compares_shards = False  # every body is compared with the payload itself
    verbs: list = []

    def __init__(self, run) -> None:
        self.run = run
        self.server: cluster.Server = run.server
        self.traffic: dict = run.traffic
        self.vol = one_volume(run, "read")
        self.clients = int(self.traffic["clients"])
        self.lost = [int(s) for s in run.config.get("lost_shards", [])]
        self.latencies: list[float] = []
        self.wrong = self.errors = 0
        self.elapsed = 0.0
        self.tracer: Tracer | None = None
        self.pool: list[int] = []  # needle numbers the readers draw from
        self.reader = NeedleReader(run)

    def prepare(self) -> None:
        """Lose the configuration's shards, find the needles the mix reads,
        and read once every interval length they will make the server
        reconstruct: each new length is a new shape to the device path."""
        run, srv, key0 = self.run, self.server, self.vol.key0
        if self.lost:
            removed = cluster.post_json(srv.volume, "/admin/ec/delete_shards", {
                "volume": self.vol.vid, "collection": run.collection,
                "shards": self.lost})
            if removed.get("removed") != self.lost:
                raise cluster.RunError(f"shards {self.lost} not removed: {removed}")
        index = volume.read_index(
            volume.file_base(srv.dir, run.collection, self.vol.vid) + ".ecx")
        touching = volume.records_on_shards(index, run.dat_bytes, self.lost)
        among = self.traffic.get("among", "all")
        if among == "touching-lost-shards":
            keys = sorted(touching)
        elif among == "all":
            keys = sorted(k for k, _, _ in index)
        else:
            raise cluster.RunError(f"traffic: unknown among {among!r}")
        self.pool = [k - key0 for k in keys]
        if not self.pool:
            raise cluster.RunError("the mix selects no needle")
        seen: set[int] = set()
        warm = []
        for key in keys:
            new = set(touching.get(key, ())) - seen
            if new:
                seen |= new
                warm.append(key - key0)
        run.notes["degraded_needles"] = len(touching)
        run.notes["reconstruct_lengths_warmed"] = len(seen)
        bad = self.reader.read_all(warm, threads=min(4, self.clients))
        if bad:
            raise cluster.RunError(f"{bad} warm-up reads failed or differed")
        # a second, short pass with every reader, so connection handling and
        # the readers' threads are warm too
        self.reader.read_all(
            self.pool[:: max(1, len(self.pool) // (4 * self.clients))],
            threads=self.clients)

    def window(self, seconds: float, trace: bool) -> None:
        pool = np.asarray(self.pool)
        results: list[list[tuple[float, int]]] = [[] for _ in range(self.clients)]
        self.reader.compare_seconds.clear()
        cpu0 = time.process_time()
        t0 = time.perf_counter()
        deadline = t0 + seconds

        def reader(c: int) -> None:
            # every seed reads the same kind of needle at the same closed-loop
            # pace; only which needle comes when differs
            rng = np.random.Generator(np.random.SFC64([self.run.seed, 3, c]))
            conn = http.client.HTTPConnection(self.server.volume, timeout=300)
            out = results[c]
            try:
                while time.perf_counter() < deadline:
                    draws = pool[rng.integers(0, len(pool), size=64)]
                    for i in draws:
                        out.append(self.reader.get(conn, int(i)))
                        if time.perf_counter() >= deadline:
                            break
            finally:
                conn.close()

        ts = [threading.Thread(target=reader, args=(c,)) for c in range(self.clients)]
        for t in ts:
            t.start()
        if trace:
            time.sleep(min(self.traffic.get("trace_after_seconds", 2.0), seconds / 3))
            span = min(self.traffic.get("trace_seconds", 3.0), seconds / 2)
            self.tracer = Tracer(self.server, span, poll_seconds=0.2)
            self.tracer.wall_span = None  # the profiler's own first `span` seconds
            self.tracer.finish()
        for t in ts:
            t.join()
        # the window is all the time until the last read that was started in
        # it has come back
        self.elapsed = time.perf_counter() - t0
        # the load generator's own share, so that a window in which it, and
        # not the server, sets the pace says so
        compared = self.reader.compare_seconds
        self.run.notes["generator"] = {
            "compare_ms_per_read": sum(compared) / max(1, len(compared)) * 1e3,
            "runner_cpu_s_per_window_s": (time.process_time() - cpu0) / self.elapsed,
        }
        for out in results:
            for dt, verdict in out:
                self.latencies.append(dt)
                self.wrong += verdict == 1
                self.errors += verdict == 2

    def after_window(self) -> None:
        pass

    def end_to_end(self) -> dict:
        good = len(self.latencies) - self.wrong - self.errors
        return {
            "p95_ms": stats.percentile(self.latencies, 95) * 1e3,
            "p50_ms": stats.percentile(self.latencies, 50) * 1e3,
            "rps": good / self.elapsed,
            "reads": len(self.latencies),
            "window_seconds": self.elapsed,
        }

    @property
    def attempted(self) -> int:
        return len(self.latencies)

    @property
    def failed(self) -> int:
        return self.wrong + self.errors

    def compare(self, want: list[np.ndarray] | None) -> dict:
        return {"reads_wrong": (self.wrong, 0), "reads_failed": (self.errors, 0)}

    def device_bytes_expected(self) -> float:
        return 1.0  # any: every read of this pool reconstructs


KINDS = {"seal": SealLoop, "repair": RepairLoop, "read": ReadLoop}
