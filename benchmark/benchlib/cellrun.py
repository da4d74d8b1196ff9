"""One run of one cell: set-up, window, comparison, metrics."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time

from . import cluster, loops, promtext, reference, volume, xplane

ROOT = cluster.ROOT
HERE = cluster.HERE
DEVICE_SUFFIX = "-pallas"  # kernel labels under which the device carried bytes
# tests' faults that flip one byte of a kept shard file after the window, and
# the loop's method that names the file
FLIPS = {"flip-shard-byte": "produced_shard_path",
         "flip-early-parity-byte": "early_parity_path"}


class NoChip(cluster.RunError):
    """The server's jax does not compute on as many TPU chips as asked."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


class Run:
    def __init__(self, spec: dict, workload: str, seed: int, seconds: float,
                 trace: bool, size: str, t_start: float,
                 fault: str = "", need_chip: bool = True) -> None:
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise cluster.RunError(f"no workload {workload!r} in BENCHMARK.json")
        self.spec, self.cell = spec, cells[workload]
        cfg_entry = next(c for c in spec["configs"] if c["name"] == self.cell["config"])
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = load_json(
            os.path.join(HERE, "traffic", self.cell["traffic"] + ".json"))
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.size_name, self.size = size, self.config["sizes"][size]
        self.t_start = t_start
        self.fault, self.need_chip = fault, need_chip
        self.workdir = os.path.join(ROOT, ".bench_work", workload)
        self.outdir = os.path.join(self.workdir, "logs")
        self.notes: dict = {}
        self.server: cluster.Server | None = None
        # the configuration's volumes, all of one collection ("": the default)
        self.collection = str(self.config.get("collection", ""))
        self.vols: list[volume.Vol] = []
        self.memory_peak = None

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    @property
    def dat_bytes(self) -> int:
        """All the volumes' `.dat` bytes: what one verb over them counts for."""
        return sum(vol.dat_bytes for vol in self.vols)

    def needle(self, g: int) -> tuple[str, memoryview]:
        """(fid, payload) of needle g, numbered through all the volumes:
        needle i of volume v is g = v * needles + i."""
        v, i = divmod(g, self.size["needles"])
        return self.vols[v].fid_of(i), self.vols[v].payload.of(i)

    def shell(self, template: str, log_name: str) -> tuple[int, str, float]:
        """A traffic file's script through one `shell` child, `{vid}` the id
        of volume 0 and `{collection}` the configuration's collection."""
        return self.server.shell(
            template.format(vid=self.vols[0].vid, collection=self.collection),
            os.path.join(self.outdir, log_name))

    def seal_verb(self, template: str, log_name: str) -> tuple[bool, str, float]:
        """One script that has to seal every volume: (ok, output, seconds).
        Ok only if the exit code is 0 and the verb says of each volume, and
        of no other, that its shards are spread."""
        rc, text, seconds = self.shell(template, log_name)
        ok = (rc == 0 and text.count(": shards spread") == len(self.vols) and all(
            f"ec.encode volume {vol.vid}: shards spread" in text for vol in self.vols))
        return ok, text, seconds

    # --- set-up -------------------------------------------------------------
    def setup(self, own_jax_platforms: bool) -> None:
        shutil.rmtree(self.workdir, ignore_errors=True)
        os.makedirs(self.outdir)
        env = cluster.child_env(os.environ, own_jax_platforms)
        self.server = srv = cluster.Server(
            self.workdir, env, os.path.join(self.outdir, "server.log"))
        self.vols = [
            volume.Vol(v, volume.Payload(self.seed, self.size["needles"],
                                         self.size["needle_bytes"], v))
            for v in range(int(self.config.get("volumes", 1)))]
        t0 = time.perf_counter()
        self.assign_volumes()
        self.notes["boot_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        for vol in self.vols:
            errors = volume.fill(srv.volume, vol.fid0, vol.payload,
                                 int(self.config["assumed"]["fill_writers"]))
            if errors:
                raise cluster.RunError(f"{len(errors)} writes failed: {errors[:3]}")
        self.notes["fill_s"] = time.perf_counter() - t0
        if self.traffic.get("sync_after_fill"):
            # a volume at rest: the fill's pages are on disk (and still in the
            # page cache) before anything is timed, so the host does not write
            # them back in the middle of the window
            t0 = time.perf_counter()
            os.sync()
            self.notes["sync_s"] = time.perf_counter() - t0
        # each volume as it was acknowledged, under a second name: what the
        # reference is computed from, and what a restore links back
        for vol in self.vols:
            vol.kept_base = os.path.join(
                self.workdir, f"kept_{vol.number}" if vol.number else "kept")
            base = volume.file_base(srv.dir, self.collection, vol.vid)
            for ext in (".dat", ".idx"):
                os.link(base + ext, vol.kept_base + ext)
            vol.dat_bytes = os.path.getsize(vol.kept_base + ".dat")
        # the first encode: first use of the device, pays jax's start and
        # every compile of the pipeline
        ok, text, seconds = self.seal_verb(
            loops.first_encode(self.traffic), "setup_encode.log")
        if not ok:
            raise cluster.RunError(f"the first ec.encode failed: {text[-600:]!r}")
        self.notes["first_encode_s"] = seconds
        seen = srv.status().get("ec", {}).get("jax", {})
        if self.need_chip and self.size_name == "real" and (
                seen.get("platform") != "tpu"
                or int(seen.get("count", 0)) < int(self.cell["chips"])):
            raise NoChip(f"the server's jax sees {seen}, the cell asks for"
                         f" {self.cell['chips']} tpu chip(s)")
        self.loop = loops.KINDS[self.traffic["loop"]](self)
        t0 = time.perf_counter()
        self.loop.prepare()
        self.notes["prepare_s"] = time.perf_counter() - t0
        if self.traffic.get("sync_after_fill"):
            # set-up ends settled: `os.sync()` after the fill comes back at
            # once in most runs on the chip's machine, and the kept volumes'
            # pages, which no restore can drop, were then written back inside
            # the window (PERF.md 6 PR 37). Each kept file by name, then all
            t0 = time.perf_counter()
            for vol in self.vols:
                for ext in (".dat", ".idx"):
                    fd = os.open(vol.kept_base + ext, os.O_RDONLY)
                    try:
                        os.fsync(fd)
                    finally:
                        os.close(fd)
            os.sync()
            self.notes["sync_before_window_s"] = time.perf_counter() - t0

    def assign_volumes(self) -> None:
        """Has the master grow the collection and hand out one run of keys in
        each of as many volumes as the configuration holds: `/dir/assign`
        picks a volume at random, so it is asked until that many have come
        up, volume 0 taking the first. A configuration that names its
        collection then has it hold those volumes and no other (`ec.encode
        -collection` takes every volume of it): the empty ones that the
        master grew beside them are deleted through the volume server."""
        srv, n = self.server, self.size["needles"]
        taken: dict[int, str] = {}
        for _ in range(200):
            fid = srv.assign(n, self.collection)["fid"]
            taken.setdefault(volume.parse_fid(fid)[0], fid)
            if len(taken) == len(self.vols):
                break
        else:
            raise cluster.RunError(
                f"the master handed out keys in {len(taken)} volumes of"
                f" {len(self.vols)}: one growth is all it makes")
        for vol, fid in zip(self.vols, taken.values()):
            vol.assigned(fid)
        deleted = []
        if "collection" in self.config:
            for v in srv.status().get("volumes", []):
                if v.get("collection", "") == self.collection and v["id"] not in taken:
                    cluster.post_json(srv.volume, "/admin/delete_volume", {"volume": v["id"]})
                    deleted.append(v["id"])
        self.notes["volumes"] = {
            "made": "grown by the master at /dir/assign"
                    + (f"?collection={self.collection}" if self.collection else "")
                    + ", one run of keys assigned in each, filled over HTTP",
            "ids": [vol.vid for vol in self.vols], "collection": self.collection,
            "empty_ones_deleted": deleted}

    # --- the whole run --------------------------------------------------------
    def execute(self, own_jax_platforms: bool = False) -> dict:
        try:
            self.setup(own_jax_platforms)
            srv = self.server
            before = {"metrics": promtext.parse(srv.metrics()), "status": srv.status()}
            if self.fault == "corrupt-surviving-shard":
                self.plant(self.fault)
            setup_s = time.monotonic() - self.t_start
            self.loop.window(self.seconds, self.trace)
            after = {"metrics": promtext.parse(srv.metrics()), "status": srv.status()}
            self.loop.after_window()
            if self.fault in FLIPS:
                self.plant(self.fault)
            sample = self.sample_reads()
            self.memory_peak = srv.memory_peak_bytes()
            exit_code = srv.stop()
            checks = self.compare(before, after, sample, exit_code)
            e2e = dict(self.loop.end_to_end(), setup_s=setup_s)
            if self.loop.verbs:
                self.notes["verb_seconds"] = [v["seconds"] for v in self.loop.verbs]
                self.notes["cycle_seconds"] = [v["cycle_seconds"] for v in self.loop.verbs]
            return self.result(before, after, e2e, checks)
        finally:
            if self.server is not None:
                self.server.stop()
            keep_logs = os.path.join(ROOT, ".bench_work", "last_logs_" + self.cell["name"])
            shutil.rmtree(keep_logs, ignore_errors=True)
            if os.path.isdir(self.outdir):
                shutil.move(self.outdir, keep_logs)
            shutil.rmtree(self.workdir, ignore_errors=True)

    def plant(self, fault: str) -> None:
        """Tests only: break what the timed path produces, underneath the
        comparison. `flip-shard-byte` alters one byte of one shard file that the
        window's last verb left, `flip-early-parity-byte` one of a parity file
        kept of the early seal; `corrupt-surviving-shard` alters, before the
        window, the first block of a shard that degraded reads rebuild from,
        so the server hands out answers altered where they are made."""
        if fault in FLIPS:
            path = getattr(self.loop, FLIPS[fault])()
            at, length = os.path.getsize(path) // 2, 1
        elif fault == "corrupt-surviving-shard":
            path = volume.file_base(
                self.server.dir, self.collection, self.vols[0].vid) + ".ec05"
            at, length = 0, reference.SMALL_BLOCK
        else:
            raise cluster.RunError(f"unknown fault {fault!r}")
        with open(path, "r+b") as f:
            f.seek(at)
            was = f.read(length)
            f.seek(at)
            f.write(bytes(b ^ 0x40 for b in was))

    def sample_reads(self) -> tuple[int, int]:
        """(wrong or failed, read) of a few needles drawn from the seed and
        read back from the volume as the window left it: ties the shards on
        disk to the bytes that were acknowledged."""
        if not self.loop.compares_shards:
            return 0, 0
        import numpy as np

        n = len(self.vols) * self.size["needles"]
        rng = np.random.Generator(np.random.SFC64([self.seed, 4]))
        picks = [int(i) for i in rng.choice(n, size=min(n, 16), replace=False)]
        return loops.NeedleReader(self).read_all(picks, threads=1), len(picks)

    # --- correct ----------------------------------------------------------------
    def compare(self, before: dict, after: dict, sample: tuple[int, int],
                server_exit: int) -> dict:
        """{name: (number, limit)}: a number above its limit (below, for the
        `_min` ones) makes the run not correct. A limit of None is a note."""
        loop = self.loop
        want = None
        t0 = time.perf_counter()
        if loop.compares_shards:
            # every volume's shards from that volume's own kept file
            want = [reference.expected_shards(vol.kept_base + ".dat")
                    for vol in self.vols]
        self.notes["reference_compute_s"] = time.perf_counter() - t0
        checks = dict(loop.compare(want))
        self.notes["reference_s"] = time.perf_counter() - t0
        checks["operations_failed"] = (loop.failed, 0)
        checks["operations_done_min"] = (loop.attempted - loop.failed, 1)
        if sample[1]:
            checks["sample_reads_wrong"] = (sample[0], 0)
        checks["server_exit_code"] = (abs(server_exit), 0)
        if not self.need_chip:
            return checks
        ec = after["status"].get("ec", {})
        jax_seen = ec.get("jax", {})
        checks["platform_is_tpu_min"] = (int(jax_seen.get("platform") == "tpu"), 1)
        checks["selection_failures"] = (len(ec.get("selection_failures", {})), 0)
        fam, label = self.traffic["device_family"], self.traffic["device_label"]
        grew = promtext.by_label(before["metrics"], after["metrics"],
                                 fam + "_bytes_total", "kernel")
        device = grew.get(label, 0.0)
        host = sum(v for k, v in grew.items() if v > 0 and not k.endswith(DEVICE_SUFFIX))
        checks["device_label_bytes_min"] = (
            device, loop.device_bytes_expected() * (1 - 1e-3))
        checks["host_label_bytes"] = (host, 0)
        return checks

    @staticmethod
    def is_correct(checks: dict) -> bool:
        for name, (value, limit) in checks.items():
            if limit is None:
                continue
            if name.endswith("_min"):
                if value < limit:
                    return False
            elif value > limit:
                return False
        return True

    # --- the result ---------------------------------------------------------------
    def result(self, before: dict, after: dict, e2e: dict, checks: dict) -> dict:
        spec, name = self.spec, self.cell["name"]
        status = after["status"].get("ec", {})
        seen = status.get("jax", {})
        device = {
            "platform": seen.get("platform", "none"),
            "kind": seen.get("device_kind", "none"),
            "count": int(seen.get("count", 0)),
            "memory_peak_bytes": self.memory_peak if self.memory_peak is not None else 0,
        }
        out: dict = {"correct": self.is_correct(checks),
                     "attempted": self.loop.attempted, "failed": self.loop.failed}
        mapping = self.traffic["end_to_end"]
        if not self.trace:
            metrics = {}
            for m in spec["end_to_end"]:
                if name not in m.get("workloads", [name]):
                    continue
                key = mapping.get(m["name"], m["name"])
                if key in e2e:
                    metrics[m["name"]] = {"value": e2e[key], "unit": m["unit"]}
            out["metrics"] = metrics
        else:
            ctx = self.layer_context(before, after, e2e)
            metrics, says = {}, {}
            for m in spec["per_layer"]:
                if name not in m.get("workloads", [name]):
                    continue
                got = read_layer_metric(m["name"], ctx)
                if got is None:
                    continue
                value, note = got if isinstance(got, tuple) else (got, "")
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
                if note:
                    says[m["name"]] = note
            out["metrics"] = metrics
            trace = ctx.get("trace")
            if trace:
                device["busy_s"] = trace["busy_s"]
                device["busy_s_per_chip"] = trace["busy_s_per_chip"]
                device["window_s"] = trace["window_s"]
                stage = ctx.get("busiest_stage", "")
                out["breakdown"] = {
                    "device_ops": trace["device_ops"],
                    "idle_gaps": [[(f"stage:{stage}|" if stage else "") + what, s]
                                  for what, s in trace["idle_gaps"]],
                }
            self.notes["metric_notes"] = says
            self.notes["trace_error"] = ctx.get("trace_error", "")
        out["device"] = device
        out["notes"] = {**self.notes, "loop": {
            k: v for k, v in e2e.items()
            if k not in mapping.values() and k != "setup_s"}}
        out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
        return out

    def layer_context(self, before: dict, after: dict, e2e: dict) -> dict:
        loop = self.loop
        ctx: dict = {
            "window": {"before": before, "after": after,
                       "seconds": e2e["window_seconds"],
                       "verbs": loop.verbs},
            "traffic": self.traffic, "config": self.config,
            "device_kind": after["status"].get("ec", {}).get("jax", {}).get(
                "device_kind", ""),
            "peaks_file": os.path.join(HERE, "peaks.json"),
        }
        tracer = loop.tracer
        if tracer is None:
            ctx["trace_error"] = "no trace was asked for"
        elif tracer.blob is None:
            ctx["trace_error"] = tracer.error or "the trace did not come back"
        else:
            try:
                with open(os.path.join(self.outdir, "trace.tar.gz"), "wb") as f:
                    f.write(tracer.blob)  # kept with the last run's logs
                profile = xplane.load(xplane.xplane_from_targz(tracer.blob))
                began, ended = xplane.profile_times(profile)
                if not began:
                    raise ValueError("the trace does not say when it began")
                if tracer.wall_span is None:
                    # the profiler's own first seconds, the counters as they
                    # stood at its ends
                    lo, hi = 0.0, tracer.seconds
                    pages = tracer.page_at(began + lo), tracer.page_at(began + hi)
                else:
                    # one verb, which has to lie inside the profile; counters
                    # move once a verb, so the pages around it are its own
                    lo, hi = tracer.wall_span[0] - began, tracer.wall_span[1] - began
                    self.notes["traced_verb_inside_profile"] = (
                        lo >= 0 and tracer.wall_span[1] <= ended)
                    pages = (promtext.parse(tracer.pages[0][1]),
                             promtext.parse(tracer.pages[-1][1]))
                ctx["trace"] = xplane.reduce(profile, lo, hi)
                ctx["span"] = {"before": pages[0], "after": pages[1],
                               "seconds": hi - lo}
                self.notes["trace_planes"] = ctx["trace"].pop("planes")
                self.notes["profile_seconds"] = ended - began
            except Exception as e:  # noqa: BLE001 - a trace that cannot be read
                # leaves its metrics out; the run's other numbers stand
                ctx["trace_error"] = f"{type(e).__name__}: {e}"
        return ctx


def read_layer_metric(name: str, ctx: dict):
    """The value of one per-layer metric: its file under layer_metrics/ names
    a reader under readers/ and the reader's arguments. None: nothing to read."""
    spec = load_json(os.path.join(HERE, "layer_metrics", name + ".json"))
    path = os.path.join(HERE, "readers", spec["reader"] + ".py")
    mod_spec = importlib.util.spec_from_file_location("reader_" + spec["reader"], path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx, **spec.get("args", {}))
