"""Benchmark: end-to-end shell `ec.encode` (BASELINE config 1), the verb —
not just the kernel.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.

value = GB/s of .dat input erasure-coded to 14 on-disk shards by the real
shell verb (`ec.encode -volumeId N`) against an in-process master+volume
cluster on tmpfs: readonly-mark -> shard generate through the fused
single-pass engine (mmap'd .dat -> GFNI -> NT-stores) -> .ecx/.vif ->
spread/mount/delete, all timed; best of 3.

vs_baseline divides by baseline_seq_gfni_gbps: the reference's exact
architecture (`ec_encoder.go:132-137` — single-threaded 256KB
read->encode->write loop) running the STRONGEST CPU kernel this host has
(GFNI/AVX-512, klauspost-class), end-to-end on the same volume. The r1
scalar-table divisor stays in extra for continuity.

extra also covers the remaining BASELINE configs: ec_rebuild (config 2),
hash_1m_4k (config 3), cdc_dedup on a multi-GiB shifted-repeat stream
(config 4), and small_files write/read req/s vs the reference's published
15,708/47,019 — plus, when jax computes on an accelerator, the on-device
Pallas kernel rate and the device-pipeline e2e rate (what
ops/rs_kernel.pick_pipeline_backend keys on). Everything runs in this one
process, which is therefore the one that holds the chip.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import numpy as np

GiB = 1024 * 1024 * 1024
BENCH_DIR = "/dev/shm/seaweedfs_tpu_bench"
VID = 7


def kernel_gbps_from_metrics(text: str) -> dict:
    """Per-kernel throughput attribution from Prometheus exposition text:
    pairs each SeaweedFS_*_seconds histogram's _sum with its companion
    *_bytes_total counter (stats/trace.py kernel spans) and reports
    bytes/second — so a BENCH run can say how fast each data-plane kernel
    (ec encode/decode, hash paths) actually ran, from /metrics alone."""
    import re

    sum_re = re.compile(
        r'^(SeaweedFS_\w+?)_seconds_sum\{kernel="([^"]*)"\} (\S+)$'
    )
    bytes_re = re.compile(
        r'^(SeaweedFS_\w+?)_bytes_total\{kernel="([^"]*)"\} (\S+)$'
    )
    seconds: dict = {}
    nbytes: dict = {}
    for line in text.splitlines():
        m = sum_re.match(line)
        if m:
            seconds[(m.group(1), m.group(2))] = float(m.group(3))
            continue
        m = bytes_re.match(line)
        if m:
            nbytes[(m.group(1), m.group(2))] = float(m.group(3))
    out = {}
    for key, secs in sorted(seconds.items()):
        family, kernel = key
        b = nbytes.get(key, 0.0)
        if secs <= 0 or b <= 0:
            continue
        short = family.replace("SeaweedFS_", "")
        out[f"{short}:{kernel}"] = {
            "gbps": round(b / secs / 1e9, 3),
            "seconds": round(secs, 3),
            "gb": round(b / 1e9, 3),
        }
    return out


def ec_pipeline_summary_from_metrics(text: str) -> dict:
    """Per-stage EC pipeline attribution off one /metrics scrape (PR-3
    series): busy vs queue-wait seconds per stage from the
    `SeaweedFS_volume_ec_pipeline_seconds{stage,state}` histograms, plus
    utilization = busy/(busy+wait) — so BENCH records WHERE the encode
    pipeline's time went (reader starved? device slow? writer saturated?)
    next to how fast it ran."""
    from seaweedfs_tpu.stats import parse_exposition

    sums: dict = {}
    counts: dict = {}
    for name, labels, value in parse_exposition(text):
        key = (labels.get("stage", ""), labels.get("state", ""))
        if name == "SeaweedFS_volume_ec_pipeline_seconds_sum":
            sums[key] = sums.get(key, 0.0) + value
        elif name == "SeaweedFS_volume_ec_pipeline_seconds_count":
            counts[key] = counts.get(key, 0.0) + value
    out: dict = {}
    for (stage, state), secs in sorted(sums.items()):
        st = out.setdefault(stage, {})
        st[f"{state}_seconds"] = round(secs, 4)
        st[f"{state}_batches"] = counts.get((stage, state), 0.0)
    for st in out.values():
        busy = st.get("busy_seconds", 0.0)
        wait = st.get("wait_seconds", 0.0)
        if busy + wait > 0:
            st["utilization"] = round(busy / (busy + wait), 4)
    return out


def request_rates_summary_from_history(hist, window_sec: float,
                                       now: float | None = None,
                                       eng=None) -> dict:
    """Cluster-level request view off the PR-4 history ring: per-role/
    method HTTP req/s and per-op fastlane req/s + bytes/s over the window
    covering the bench run, plus the alerts that fired during it — so
    BENCH records what the serving surface sustained (and whether anything
    alarmed) next to the kernel attribution."""
    import time as _time

    now = _time.time() if now is None else now
    out: dict = {
        "window_seconds": round(window_sec, 1),
        "http_req_s": {},
        "fastlane_ops": {},
    }
    for labels, rate in hist.rates(
        "SeaweedFS_http_request_total", window_sec, now
    ):
        if not rate:
            continue
        key = f"{labels.get('role', '?')}:{labels.get('method', '?')}"
        out["http_req_s"][key] = round(
            out["http_req_s"].get(key, 0.0) + rate, 2
        )
    for fam, field in (
        ("SeaweedFS_volume_fastlane_requests_total", "req_s"),
        ("SeaweedFS_volume_fastlane_bytes_total", "bytes_s"),
    ):
        for labels, rate in hist.rates(fam, window_sec, now):
            if not rate:
                continue
            op = out["fastlane_ops"].setdefault(labels.get("op", "?"), {})
            op[field] = round(op.get(field, 0.0) + rate, 2)
    if eng is None:
        from seaweedfs_tpu.stats import alerts as alerts_mod

        eng = alerts_mod.engine()
    snap = eng.snapshot()
    out["alerts_fired"] = snap["fired_events"]
    out["alerts_firing"] = snap["firing"]
    return out


def build_volume(staging: str, total_bytes: int = GiB) -> str:
    """A real volume (.dat/.idx via the storage engine) of ~total_bytes."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    os.makedirs(staging, exist_ok=True)
    base = os.path.join(staging, str(VID))
    if os.path.exists(base + ".dat") and os.path.getsize(base + ".dat") >= total_bytes:
        return base
    v = Volume(staging, "", VID)
    rng = np.random.RandomState(11)
    blob = rng.randint(0, 256, size=1024 * 1024, dtype=np.uint8).tobytes()
    key = 1
    while v.size() < total_bytes:
        n = Needle(cookie=0x1234, id=key, data=blob)
        v.write_needle(n)
        key += 1
    v.close()
    return base


def bench_verb(staging_base: str, trials: int = 3) -> tuple[float, dict]:
    """Time the real shell verb on an in-process cluster; returns GB/s."""
    from seaweedfs_tpu.server.httpd import post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.shell import CommandEnv, run_command

    srv_dir = os.path.join(BENCH_DIR, "srv")
    os.makedirs(srv_dir, exist_ok=True)
    master = MasterServer(port=0, pulse_seconds=1, volume_size_limit_mb=2048)
    master.start()
    vs = VolumeServer([srv_dir], master.url, port=0, pulse_seconds=1,
                      max_volume_count=20)
    vs.start()
    env = CommandEnv(master.url)
    run_command(env, "lock")  # ec.encode needs the cluster admin lock
    dat_bytes = os.path.getsize(staging_base + ".dat")
    # Prewarm the guest page pool. This host is a Firecracker microVM with
    # free-page reporting (page_reporting_order=11 on the cmdline): freed
    # guest pages are returned to the hypervisor, and the FIRST touch of any
    # new page costs a host-side refault measured at ~0.15 GB/s — 7s+ for
    # the 1.5GB of shard files, regardless of encode architecture. Touch and
    # free the trial working set once so trial 1 measures the verb, not the
    # balloon refill; raw per-trial times are still reported unedited.
    # Let the server's boot-time backend calibration finish before timing:
    # on a single-core host the jax-init probe thread would otherwise steal
    # cycles from trial 1 (same process, same calibration lock). Run it
    # before the pool prewarm — the hypervisor reclaims freed pages after a
    # delay, so the pool must be freed as close to trial 1 as possible.
    from seaweedfs_tpu.ops.rs_kernel import pick_pipeline_backend

    pick_pipeline_backend()
    pool = np.ones(2 * 1024**3 // 8, dtype=np.int64)
    del pool
    best = 0.0
    times = []
    kernels: dict = {}
    # PR-3: sample this process's stacks across the trials (the overhead
    # guard bounds the sampler's duty cycle, so the timed verb stays
    # honest) — BENCH records the hottest frames next to the rates
    from seaweedfs_tpu.stats import profiler as prof_mod

    sampler = prof_mod.SamplingProfiler(hz=50)
    sampler.start()
    prof_out: dict = {}
    try:
        for _ in range(trials):
            try:  # the server auto-loads volumes found at startup
                post_json(f"{vs.url}/admin/volume/unmount", {"volume": VID})
            except IOError:
                pass
            for ext in (".dat", ".idx"):
                dst = os.path.join(srv_dir, f"{VID}{ext}")
                if os.path.exists(dst):
                    os.remove(dst)
                os.link(staging_base + ext, dst)
            post_json(f"{vs.url}/admin/volume/mount", {"volume": VID})
            t0 = time.perf_counter()
            run_command(env, f"ec.encode -volumeId {VID}")
            dt = time.perf_counter() - t0
            times.append(round(dt, 3))
            best = max(best, dat_bytes / dt / 1e9)
            post_json(f"{vs.url}/admin/ec/unmount", {"volume": VID})
        # per-kernel GB/s attribution straight off the live /metrics surface
        try:
            from seaweedfs_tpu.server.httpd import http_request

            _, _, metrics_text = http_request(
                "GET", f"{vs.service.url}/metrics"
            )
            kernels = kernel_gbps_from_metrics(metrics_text.decode())
        except Exception:
            pass
    finally:
        prof_out = sampler.stop()
        vs.stop()
        master.stop()
    return best, {
        "trial_seconds": times, "volume_bytes": dat_bytes,
        "kernel_gbps": kernels,
        "profile_top_frames": prof_mod.top_frames(
            prof_out.get("stacks", {}), n=10),
        "profile_overhead_ratio": prof_out.get("overhead_ratio"),
    }


def fastlane_summary_from_metrics(text: str) -> dict:
    """Fastlane engine health off one /metrics scrape (PR-2 series):
    native-vs-proxied hit ratio plus per-op p50/p99 latency interpolated
    from the `SeaweedFS_volume_fastlane_request_seconds` fixed buckets —
    so BENCH records how much of the data plane actually ran natively and
    at what latency, next to the kernel_gbps attribution."""
    from seaweedfs_tpu.stats import parse_exposition

    native = proxied = 0.0
    # op -> {le_upper_bound_s: cumulative_count SUMMED across servers} —
    # one process registry can carry several servers' series (the `server`
    # label); summing per-bound keeps the merged histogram cumulative
    # (sum of cumulatives is the cumulative of the sum)
    buckets: dict = {}
    counts: dict = {}
    for name, labels, value in parse_exposition(text):
        if name == "SeaweedFS_volume_fastlane_requests_total":
            native += value
        elif name == "SeaweedFS_volume_fastlane_proxied_total":
            proxied += value
        elif name == "SeaweedFS_volume_fastlane_request_seconds_bucket":
            le = labels.get("le", "")
            bound = float("inf") if le == "+Inf" else float(le)
            per_op = buckets.setdefault(labels.get("op", ""), {})
            per_op[bound] = per_op.get(bound, 0.0) + value
        elif name == "SeaweedFS_volume_fastlane_request_seconds_count":
            op = labels.get("op", "")
            counts[op] = counts.get(op, 0.0) + value

    def quantile(op: str, q: float):
        bs = sorted(buckets.get(op, {}).items())
        total = counts.get(op, 0.0)
        if not bs or total <= 0:
            return None
        rank = q * total
        prev_bound, prev_cum = 0.0, 0.0
        for bound, cum in bs:
            if cum >= rank:
                if bound == float("inf"):
                    return round(prev_bound, 6)  # overflow bucket: lower edge
                # prev_cum < rank <= cum here, so the division is safe
                frac = (rank - prev_cum) / (cum - prev_cum)
                return round(prev_bound + frac * (bound - prev_bound), 6)
            prev_bound, prev_cum = bound, cum
        return round(prev_bound, 6)

    total = native + proxied
    out: dict = {
        "native_requests": native,
        "proxied_requests": proxied,
        "fastlane_native_ratio": round(native / total, 4) if total else None,
        "ops": {},
    }
    for op in sorted(counts):
        if counts.get(op, 0) <= 0:
            continue
        p50, p99 = quantile(op, 0.5), quantile(op, 0.99)
        out["ops"][op] = {
            "count": counts[op],
            "p50_ms": None if p50 is None else round(p50 * 1e3, 3),
            "p99_ms": None if p99 is None else round(p99 * 1e3, 3),
        }
    return out


def bench_sequential_reference_loop(staging_base: str, gfni: bool) -> float:
    """The reference's architecture (`ec_encoder.go:132-137`): one thread,
    256KB batches, read -> encode -> write, no overlap. gfni=False is the
    scalar table kernel."""
    from seaweedfs_tpu.native import lib

    if lib is None:
        return float("nan")
    return max(
        _seq_loop_once(staging_base, gfni) for _ in range(2)
    )  # best-of-2: run 1 may pay the microVM's fresh-page refault cost


def _seq_loop_once(staging_base: str, gfni: bool) -> float:
    from seaweedfs_tpu.native import lib
    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.storage.erasure_coding.geometry import (
        DATA_SHARDS_COUNT,
        LARGE_BLOCK_SIZE,
        SMALL_BLOCK_SIZE,
        TOTAL_SHARDS_COUNT,
        shard_file_size,
        to_ext,
    )

    out_dir = os.path.join(BENCH_DIR, "seq_gfni" if gfni else "seq_table")
    os.makedirs(out_dir, exist_ok=True)
    matrix = gf256.parity_rows(10, 4).tobytes()
    total = os.path.getsize(staging_base + ".dat")
    prev = lib.set_gfni(gfni)
    dat_fd = os.open(staging_base + ".dat", os.O_RDONLY)
    outs = [
        os.open(os.path.join(out_dir, f"1{to_ext(i)}"),
                os.O_RDWR | os.O_CREAT, 0o644)
        for i in range(TOTAL_SHARDS_COUNT)
    ]
    batch = 256 * 1024  # the reference's ecVolumeBatchSize
    buf = np.empty((DATA_SHARDS_COUNT, batch), dtype=np.uint8)
    # Pre-size the outputs: extending a tmpfs file pwrite-by-pwrite measures
    # ~20x slower than writing into a pre-truncated one on this kernel, and
    # that artifact is not part of the encode architecture being compared.
    ssize0 = shard_file_size(total, LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE)
    for fd in outs:
        os.ftruncate(fd, ssize0)
    t0 = time.perf_counter()
    try:
        remaining, processed, shard_off = total, 0, 0
        for block in (LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE):
            row = block * DATA_SHARDS_COUNT
            while (remaining > row) if block == LARGE_BLOCK_SIZE else (remaining > 0):
                done = 0
                while done < block:
                    w = min(batch, block - done)
                    for c in range(DATA_SHARDS_COUNT):
                        got = os.preadv(
                            dat_fd,
                            [memoryview(buf[c])[:w]],
                            processed + c * block + done,
                        )
                        if got < w:
                            buf[c, got:w] = 0
                    parity = lib.gf256_matmul2d(matrix, buf[:, :w])
                    for c in range(DATA_SHARDS_COUNT):
                        os.pwrite(outs[c], buf[c, :w], shard_off + done)
                    for p in range(4):
                        os.pwrite(outs[10 + p], parity[p], shard_off + done)
                    done += w
                remaining -= row
                processed += row
                shard_off += block
    finally:
        ssize = shard_file_size(total, LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE)
        for fd in outs:
            os.ftruncate(fd, ssize)
            os.close(fd)
        os.close(dat_fd)
        lib.set_gfni(prev)
    return total / (time.perf_counter() - t0) / 1e9


def bench_device_kernel(shard_mb: int = 64, trials: int = 3) -> float:
    """On-device Pallas encode rate: device-resident input, one large
    execution, explicit readback drain."""
    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.ops.rs_kernel import _device_put_1d
    from seaweedfs_tpu.ops.rs_pallas import gf_matmul_pallas

    n = shard_mb * 1024 * 1024
    rng = np.random.RandomState(1)
    data_host = rng.randint(0, 256, size=(10, n)).astype(np.uint8)
    data = _device_put_1d(data_host).reshape(10, n)
    matrix = gf256.parity_rows(10, 4)
    out = gf_matmul_pallas(matrix, data)  # compile + warm
    _ = np.asarray(out[0, :8])
    want = gf256.gf_matmul_bytes(matrix, data_host[:, :4096])
    assert np.array_equal(np.asarray(out[:, :4096]), want), "parity mismatch"
    best = 0.0
    for _ in range(trials):
        t0 = time.perf_counter()
        o = gf_matmul_pallas(matrix, data)
        _ = np.asarray(o[0, :8])  # drain the in-order queue
        best = max(best, (10 * n) / (time.perf_counter() - t0) / 1e9)
    return best


def bench_host_kernel(shard_mb: int = 16) -> float:
    from seaweedfs_tpu.native import lib
    from seaweedfs_tpu.ops import gf256

    if lib is None:
        return float("nan")
    n = shard_mb * 1024 * 1024
    rng = np.random.RandomState(2)
    data = rng.randint(0, 256, size=(10, n), dtype=np.uint8)
    matrix = gf256.parity_rows(10, 4).tobytes()
    out = np.empty((4, n), dtype=np.uint8)
    lib.gf256_matmul2d(matrix, data, out)  # warm
    iters = 4
    t0 = time.perf_counter()
    for _ in range(iters):
        lib.gf256_matmul2d(matrix, data, out)
    return (10 * n * iters) / (time.perf_counter() - t0) / 1e9


def bench_device_pipeline(staging_base: str, mb: int = 128) -> float:
    """e2e disk->device->disk encode over the first `mb` MB, jax backend:
    transfers both ways included."""
    import shutil

    from seaweedfs_tpu.ops.rs_kernel import RSCodec
    from seaweedfs_tpu.storage.erasure_coding import encoder

    d = os.path.join(BENCH_DIR, "devpipe")
    os.makedirs(d, exist_ok=True)
    base = os.path.join(d, "1")
    n = mb * 1024 * 1024
    with open(staging_base + ".dat", "rb") as src, open(base + ".dat", "wb") as dst:
        remaining = n
        while remaining > 0:
            piece = src.read(min(64 * 1024 * 1024, remaining))
            if not piece:
                break
            dst.write(piece)
            remaining -= len(piece)
    best = 0.0
    for _ in range(2):
        t0 = time.perf_counter()
        encoder.write_ec_files(base, codec=RSCodec(backend="jax"))
        best = max(best, n / (time.perf_counter() - t0) / 1e9)
    return best


def bench_ec_online(staging: str, total_mb: int = 256,
                    needle_kb: int = 1024) -> dict:
    """Online (write-path) erasure coding through the real ingest path:
    a live Volume with an OnlineEcWriter attached, needles appended via
    write_needle, parity streamed per stripe row. Records:

      * ec_online_encode_gbps — .dat bytes parity-encoded per second of
        read+encode+parity-write time on the ingest path (the number the
        encoder must keep above ingest for online EC to be free);
      * write_amplification — bytes-to-disk / bytes-ingested
        (dat + parity over dat; replication baseline is 2.0x);
      * fallbacks — per-reason degrade counters (steady state must show
        zero pathological reasons: backpressure/encoder_error/journal_io).
    """
    import shutil

    from seaweedfs_tpu.storage.erasure_coding.online import OnlineEcWriter
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    rng = np.random.RandomState(7)
    blob = rng.randint(0, 256, size=needle_kb * 1024,
                       dtype=np.uint8).tobytes()
    total = total_mb * 1024 * 1024
    # best of 3 like bench_verb: a long-running volume server recycles
    # its pages, but this microVM (free-page reporting) hands freed guest
    # pages back to the hypervisor and re-faults the FIRST touch of every
    # fresh page at ~0.15 GB/s. Trial 1 pays the balloon refill for the
    # whole .dat+parity working set; later trials run on recycled pages,
    # i.e. the steady state a server actually sustains. Raw per-trial
    # rates are reported unedited.
    trials = []
    best = None
    for trial in range(3):
        d = os.path.join(staging, "ec_online")
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d, exist_ok=True)
        # refill the guest free list right before the trial (bench_verb's
        # prewarm): freed pages linger in the guest pool briefly before
        # free-page reporting hands them back, so allocate-and-free the
        # working set now and the trial's tmpfs pages come from recycle
        pool = np.ones((total_mb * 3 // 2) * 1024**2 // 8, dtype=np.int64)
        del pool
        v = Volume(d, "", 77)
        w = OnlineEcWriter(v, block_size=1024 * 1024)
        v.online_ec = w  # v.close() then closes the writer's fds/thread
        try:
            key = 1
            t0 = time.perf_counter()
            while v.size() < total:
                v.write_needle(Needle(cookie=0x42, id=key, data=blob))
                key += 1
                if key % 32 == 0:  # the server's drain loop is batchy too
                    w.pump()
            w.pump(force=True)
            wall = time.perf_counter() - t0
            ingested = v.size()
            to_disk = ingested + w.parity_bytes
            gbps = (
                w.encoded_bytes / w.encode_seconds / 1e9
                if w.encode_seconds > 0 else 0.0
            )
            res = {
                "ec_online_encode_gbps": round(gbps, 3),
                "ingest_gbps": round(ingested / wall / 1e9, 3),
                "write_amplification": round(to_disk / max(ingested, 1), 3),
                "bytes_ingested": ingested,
                "bytes_to_disk": to_disk,
                "stripes": w.stripes,
                "block_size": w.block,
                "fallbacks": dict(w.fallbacks),
                "pathological_fallbacks": sum(
                    n for r, n in w.fallbacks.items()
                    if r in ("backpressure", "encoder_error", "journal_io")
                ),
                "active": w.active,
            }
        finally:
            v.close()
            shutil.rmtree(d, ignore_errors=True)
        trials.append(res["ec_online_encode_gbps"])
        if best is None or res["ec_online_encode_gbps"] > \
                best["ec_online_encode_gbps"]:
            best = res
    best["trial_encode_gbps"] = trials
    return best


def bench_rebuild(staging_base: str, trials: int = 3) -> dict:
    """BASELINE config 2: single-missing-shard recovery on the 1GiB volume.
    Rate is source-volume GB/s (same convention as ec.encode: the rebuild
    reads 10 surviving shards = one volume's worth of bytes)."""
    import shutil

    from seaweedfs_tpu.storage.erasure_coding import encoder
    from seaweedfs_tpu.storage.erasure_coding.geometry import to_ext

    d = os.path.join(BENCH_DIR, "rebuild")
    os.makedirs(d, exist_ok=True)
    base = os.path.join(d, "1")
    if not os.path.exists(base + to_ext(13)):
        for ext in (".dat", ".idx"):
            if not os.path.exists(base + ext):
                os.link(staging_base + ext, base + ext)
        encoder.write_ec_files(base)
    dat_bytes = os.path.getsize(staging_base + ".dat")
    # rebuild runs late in the bench: earlier sections freed their pages
    # back to the hypervisor (free-page reporting), and the ~150MB of
    # fresh shard pages a trial writes would pay the ~1.2us/page refault
    # inside trial 1. Same prewarm the verb bench uses.
    pool = np.ones(512 * 1024 * 1024 // 8, dtype=np.int64)
    del pool
    best, times = 0.0, []
    for i in range(trials):
        victim = to_ext(3 if i % 2 == 0 else 12)  # a data and a parity shard
        saved = base + victim + ".orig"
        os.replace(base + victim, saved)
        t0 = time.perf_counter()
        rebuilt = encoder.rebuild_ec_files(base)
        dt = time.perf_counter() - t0
        assert rebuilt, "nothing rebuilt"
        with open(base + victim, "rb") as f_new, open(saved, "rb") as f_old:
            if f_new.read(1 << 20) != f_old.read(1 << 20):
                raise AssertionError("rebuilt shard differs from original")
        os.unlink(saved)
        times.append(round(dt, 3))
        best = max(best, dat_bytes / dt / 1e9)
    return {"gbps": round(best, 3), "trial_seconds": times}


def bench_cdc_dedup(gib: int = 8) -> dict:
    """BASELINE config 4: rolling-hash CDC + content hashing + dedup index
    over a multi-GiB stream, exercised exactly as the filer's dedup write
    path does per upload (find_boundaries -> batched md5 via the hash
    service -> index lookup/insert), minus the blob upload that configs 1-3
    already measure. Uploads alternate fresh random data with byte-SHIFTED
    repeats of earlier data, so dedup only happens when content-defined
    boundaries re-align — the hard case offset-based chunking cannot catch."""
    from seaweedfs_tpu.filer.dedup import DedupIndex
    from seaweedfs_tpu.filer.filer import Filer
    from seaweedfs_tpu.filer.filerstore import MemoryStore
    from seaweedfs_tpu.ops.cdc import find_boundaries, pick_backend
    from seaweedfs_tpu.ops.hash_service import get_hash_service

    seg = 64 * 1024 * 1024
    rng = np.random.RandomState(9)
    base_segs = [
        rng.randint(0, 256, size=seg, dtype=np.uint8) for _ in range(4)
    ]
    backend = pick_backend()
    svc = get_hash_service()
    svc.submit_many([b"warm" * 64] * 32)[0].md5_hex()  # backend calibration
    idx = DedupIndex(Filer(MemoryStore()))

    # materialize every upload before the clock starts: building the
    # byte-shifted repeats costs fresh-page allocation that belongs to the
    # workload generator, not the dedup path being measured
    n_uploads = gib * 1024**3 // seg
    uploads = []
    for i in range(n_uploads):
        if i % 2 == 0:
            uploads.append(base_segs[(i // 2) % len(base_segs)])
        else:
            shift = 1 + 37 * i % 4093  # not a chunk boundary multiple
            src = base_segs[(i // 3) % len(base_segs)]
            uploads.append(np.concatenate([src[shift:], src[:shift]]))
    n_chunks = dup_chunks = dup_bytes = 0
    total = 0
    # per-upload timing with a best-quartile rate: one noisy-neighbor
    # stretch on this host must not define the whole stream's number
    window_rates: list = []
    t0 = time.perf_counter()
    for data in uploads:
        total += data.nbytes
        w0 = time.perf_counter()
        cuts = find_boundaries(
            data, avg_bits=16, min_size=16 * 1024, max_size=512 * 1024,
            backend=backend,
        )
        # the filer's dedup shape (filer.py _upload_chunks_cdc): SW128
        # identity keys for every span, MD5 batched over MISSES only
        # (their upload ETags)
        keys = svc.span_keys(data, cuts, seed=b"\x07" * 16)
        recs = []
        miss_ranges = []
        prev = 0
        for cut, khash in zip(cuts, keys):
            ln = cut - prev
            rec = idx.lookup(f"{khash}-{ln:x}")
            recs.append(rec)
            if rec is None:
                miss_ranges.append((prev, ln))
            prev = cut
        miss_md5s = iter(svc.md5_spans(data, miss_ranges))
        prev = 0
        for cut, khash, rec in zip(cuts, keys, recs):
            ln = cut - prev
            prev = cut
            n_chunks += 1
            if rec is not None:
                dup_chunks += 1
                dup_bytes += ln
            else:
                idx.insert(f"{khash}-{ln:x}",
                           {"fid": f"3,{n_chunks:x}00000000", "size": ln,
                            "etag": next(miss_md5s)})
        # window covers the WHOLE per-upload dedup path incl. index work
        window_rates.append(data.nbytes / (time.perf_counter() - w0))
    dt = time.perf_counter() - t0
    window_rates.sort()
    best_quartile = window_rates[3 * len(window_rates) // 4]
    # headline stays WALL-CLOCK (comparable with earlier rounds' numbers);
    # the p75 window is a companion diagnostic only — the workload mixes
    # cheap duplicate-heavy and expensive unique uploads, so a windowed
    # max would select the easy uploads, not just quiet-host stretches
    return {
        "gib_streamed": round(total / 1024**3, 2),
        "gbps": round(total / dt / 1e9, 3),
        "gbps_p75_window": round(best_quartile / 1e9, 3),
        "chunks": n_chunks,
        "dedup_chunk_pct": round(100.0 * dup_chunks / max(1, n_chunks), 1),
        "dedup_byte_pct": round(100.0 * dup_bytes / max(1, total), 1),
        "backend": backend,
    }


def bench_small_files(n: int = 20000, size: int = 1024, c: int = 16) -> dict:
    """BASELINE.md rows 1-2: small-file write + random read req/s through
    the real master+volume HTTP data plane (`weed benchmark` semantics,
    reference: 15,708 write / 47,019 read req/s on an i7 MacBook).

    Two measurements:
      * engine rate — the fastlane data plane driven by the native epoll
        loadgen (keep-alive, c conns, fids pre-assigned in one batched
        `?count=` call — a documented API the Go client also offers;
        the reference number assigned per-file through its Go master).
        Reads replay the fids shuffled.
      * python_client — the full `weed-tpu benchmark` flow (per-file
        assigns, GIL-bound threaded client); honest lower bound.
    """
    import random

    from seaweedfs_tpu.command.benchmark import run_benchmark
    from seaweedfs_tpu.native import lib as native_lib
    from seaweedfs_tpu.server.httpd import get_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer

    d = os.path.join(BENCH_DIR, "smallfiles")
    os.makedirs(d, exist_ok=True)
    master = MasterServer(port=0, pulse_seconds=1)
    master.start()
    vs = VolumeServer([d], master.url, port=0, pulse_seconds=1,
                      max_volume_count=20)
    vs.start()
    out: dict = {
        "files": n,
        "size": size,
        "concurrency": c,
        "reference_req_s": {"write": 15708, "read": 47019},
    }
    try:
        if vs.fastlane is not None and native_lib is not None:
            a = get_json(master.url + f"/dir/assign?count={n}")
            port = int(a["publicUrl"].rsplit(":", 1)[1])
            fid = a["fid"]
            paths = [f"/{fid}"] + [f"/{fid}_{i}" for i in range(1, n)]
            w = native_lib.loadgen("127.0.0.1", port, c, "POST", paths,
                                   bytes(size))
            random.Random(7).shuffle(paths)
            r = native_lib.loadgen("127.0.0.1", port, c, "GET", paths)
            if w["ok"] > 0 and r["ok"] > 0:  # else python_client carries
                out["write_req_s"] = w["req_per_sec"]
                out["read_req_s"] = r["req_per_sec"]
                out["write_errors"] = w["errors"]
                out["read_errors"] = r["errors"]
                out["engine"] = vs.fastlane.stats()
            try:
                # PR-2 engine metrics: native hit ratio + per-op p50/p99
                # straight off the live /metrics surface
                from seaweedfs_tpu.server.httpd import http_request

                _, _, mtext = http_request(
                    "GET", f"{vs.service.url}/metrics")
                out["fastlane"] = fastlane_summary_from_metrics(
                    mtext.decode())
            except Exception:
                pass
            if master.fastlane is not None:
                # the reference's exact write semantics: EVERY file pays a
                # master /dir/assign round-trip before its volume POST
                aw = native_lib.loadgen_assign_write(
                    "127.0.0.1", master.fastlane.port, c, n, bytes(size))
                if aw["ok"] > 0:
                    out["write_assign_per_file_req_s"] = aw["req_per_sec"]
                    out["write_assign_per_file_errors"] = aw["errors"]
        report = run_benchmark(master.url, n=min(n, 4000), size=size, c=c)
        out["python_client"] = {
            "write_req_s": report["write"]["req_per_sec"],
            "read_req_s": report["read"]["req_per_sec"],
            "write_p99_ms": report["write"].get("p99_ms"),
            "read_p99_ms": report["read"].get("p99_ms"),
        }
        if "write_req_s" not in out:  # no engine: python numbers carry
            out["write_req_s"] = report["write"]["req_per_sec"]
            out["read_req_s"] = report["read"]["req_per_sec"]
    finally:
        vs.stop()
        master.stop()
    return out


def bench_filer_small_files(n: int = 20000, size: int = 1024, c: int = 16) -> dict:
    """Filer-path small files: write/read req/s THROUGH
    the filer (path namespace -> chunk on a volume -> entry in the store),
    driven by the native epoll loadgen so the measurement isn't client-bound.
    The reference's equivalent hot path is
    `weed/server/filer_server_handlers_write_autochunk.go:26-155`."""
    import random

    from seaweedfs_tpu.native import lib as native_lib
    from seaweedfs_tpu.server.filer import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer

    d = os.path.join(BENCH_DIR, "filerfiles")
    os.makedirs(d, exist_ok=True)
    out: dict = {"files": n, "size": size, "concurrency": c}
    master = vs = filer = None
    try:
        master = MasterServer(port=0, pulse_seconds=1)
        master.start()
        vs = VolumeServer([d], master.url, port=0, pulse_seconds=1,
                          max_volume_count=20)
        vs.start()
        filer = FilerServer(master_url=master.url, port=0)
        filer.start()
        if native_lib is None:
            out["error"] = "skipped: native lib unavailable"
            return out
        port = int(filer.url.rsplit(":", 1)[1])
        paths = [f"/bench/f{i}" for i in range(n)]
        w = native_lib.loadgen("127.0.0.1", port, c, "POST", paths,
                               bytes(size))
        random.Random(3).shuffle(paths)
        r = native_lib.loadgen("127.0.0.1", port, c, "GET", paths)
        if w["ok"] > 0 and r["ok"] > 0:  # never publish error-path speed
            out["write_req_s"] = w["req_per_sec"]
            out["read_req_s"] = r["req_per_sec"]
            out["write_errors"] = w["errors"]
            out["read_errors"] = r["errors"]
        else:
            out["error"] = f"loadgen failed: ok w={w['ok']} r={r['ok']}"
        if filer.fastlane is not None:
            out["engine"] = filer.fastlane.stats()
            fm = filer.fastlane.front_metrics()
            if fm is not None:
                out["front_metrics"] = fm
                native = sum(st["native"] for st in fm.values())
                fb = sum(sum(st["fallback"].values()) for st in fm.values())
                out["filer_native_ratio"] = (
                    round(native / (native + fb), 4) if native + fb else None
                )
                # the acceptance bar: the native lease verifiably HELD — no
                # pathological fallbacks (lease/backpressure/upstream)
                from seaweedfs_tpu.storage.fastlane import (
                    PATHOLOGICAL_REASONS,
                )

                out["pathological_fallbacks"] = sum(
                    st["fallback"][r] for st in fm.values()
                    for r in PATHOLOGICAL_REASONS
                )
            out["lease_live"] = filer.fastlane.lease_count()
    finally:
        for s in (filer, vs, master):
            if s is not None:
                s.stop()
    return out


def bench_s3_small_files(n: int = 10000, size: int = 1024, c: int = 16) -> dict:
    """S3-path small objects: write/read req/s THROUGH the gateway
    (sigv4-less open IAM, so the engine's S3 front relays object bytes
    straight to the filer engine — the full millions-of-users path:
    client -> s3 engine -> filer engine -> volume engine, zero GIL hops).
    Reference equivalent: `weed/s3api/s3api_object_handlers*.go`."""
    import random

    from seaweedfs_tpu.native import lib as native_lib
    from seaweedfs_tpu.s3api.s3_server import S3Server
    from seaweedfs_tpu.server.filer import FilerServer
    from seaweedfs_tpu.server.httpd import http_request
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer

    d = os.path.join(BENCH_DIR, "s3files")
    os.makedirs(d, exist_ok=True)
    out: dict = {"objects": n, "size": size, "concurrency": c}
    master = vs = filer = s3 = None
    try:
        master = MasterServer(port=0, pulse_seconds=1)
        master.start()
        vs = VolumeServer([d], master.url, port=0, pulse_seconds=1,
                          max_volume_count=20)
        vs.start()
        filer = FilerServer(master_url=master.url, port=0)
        filer.start()
        s3 = S3Server(filer.url, port=0)
        s3.start()
        if native_lib is None:
            out["error"] = "skipped: native lib unavailable"
            return out
        st, _, _ = http_request("PUT", s3.url + "/bench")  # create bucket
        if st != 200:
            out["error"] = f"bucket create -> {st}"
            return out
        port = int(s3.url.rsplit(":", 1)[1])
        paths = [f"/bench/o{i}" for i in range(n)]
        w = native_lib.loadgen("127.0.0.1", port, c, "PUT", paths,
                               bytes(size))
        random.Random(7).shuffle(paths)
        r = native_lib.loadgen("127.0.0.1", port, c, "GET", paths)
        if w["ok"] > 0 and r["ok"] > 0:
            out["write_req_s"] = w["req_per_sec"]
            out["read_req_s"] = r["req_per_sec"]
            out["write_errors"] = w["errors"]
            out["read_errors"] = r["errors"]
        else:
            out["error"] = f"loadgen failed: ok w={w['ok']} r={r['ok']}"
        if s3.fastlane is not None:
            out["engine"] = s3.fastlane.stats()
            fm = s3.fastlane.front_metrics()
            if fm is not None:
                out["front_metrics"] = fm
                native = sum(st["native"] for st in fm.values())
                fb = sum(sum(st["fallback"].values()) for st in fm.values())
                out["s3_native_ratio"] = (
                    round(native / (native + fb), 4) if native + fb else None
                )
    finally:
        for s in (s3, filer, vs, master):
            if s is not None:
                s.stop()
    return out


def maintenance_summary(trials: int = 2, blobs: int = 8) -> dict:
    """PR-5: the autonomous maintenance subsystem's heal latency. A 3-node
    cluster EC-encodes a volume, then each trial deletes one holder's
    shards and measures wall time until the daemon (scan interval 0.25s)
    has every shard back — plus one injected replica loss. Reports tasks
    executed and mean time-to-heal; arXiv:1207.6744's point is exactly
    that this number, not codec GB/s, is what degraded reads feel."""
    import tempfile

    from seaweedfs_tpu.server.httpd import get_json, http_request, post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.shell import CommandEnv, run_command

    d = os.path.join(BENCH_DIR, "maintenance")
    os.makedirs(d, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=d)
    master = MasterServer(port=0, pulse_seconds=1, volume_size_limit_mb=64,
                          maintenance_interval=0.25)
    master.start()
    vols = []
    out: dict = {"trials": trials}
    try:
        for i in range(3):
            vs = VolumeServer(
                [os.path.join(tmp, f"v{i}")], master.url, port=0,
                rack=f"r{i}", pulse_seconds=1, max_volume_count=30,
            )
            vs.start()
            vols.append(vs)
        env = CommandEnv(master.url)
        fids = []
        for i in range(blobs):
            a = get_json(f"{master.url}/dir/assign")
            url = f"http://{a['publicUrl']}/{a['fid']}"
            http_request("POST", url, b"m" * 4000)
            fids.append(a["fid"])
        run_command(env, "lock")
        vid = int(fids[0].split(",")[0])
        run_command(env, f"ec.encode -volumeId {vid}")
        run_command(env, "unlock")  # daemon repairs take the admin lease
        post_json(f"{master.url}/maintenance/enable")

        def shard_count() -> int:
            return len({
                s for sv in env.servers() for s in sv.ec_shards.get(vid, [])
            })

        heal_times = []
        for _ in range(trials):
            holders = [
                sv for sv in env.servers()
                if sv.ec_shards.get(vid)  # holders with >0 shards
            ]
            victim = min(holders, key=lambda sv: len(sv.ec_shards[vid]))
            # at most 4 of 14: RS(10,4) heals up to 4 lost shards, and the
            # rebuild concentrates shards so a whole-holder wipe on a later
            # trial could push the volume below the 10-shard floor
            lost = list(victim.ec_shards[vid])[:4]
            t0 = time.time()
            env.post(
                f"{victim.http}/admin/ec/delete_shards",
                {"volume": vid, "shards": lost, "delete_index": False},
            )
            # the loss must be topology-visible before the heal is timed —
            # a stale pre-injection snapshot reads as instant healing, and
            # a trial whose loss NEVER surfaces must be skipped, not
            # recorded as a ~10s phantom heal
            seen_loss = False
            deadline = t0 + 10
            while time.time() < deadline:
                if shard_count() < 14:
                    seen_loss = True
                    break
                time.sleep(0.05)
            if not seen_loss:
                continue
            deadline = t0 + 60
            while time.time() < deadline and shard_count() < 14:
                time.sleep(0.1)
            if shard_count() == 14:
                heal_times.append(time.time() - t0)
        if heal_times:
            out["shard_loss_time_to_heal_s"] = round(
                sum(heal_times) / len(heal_times), 3)
            out["shard_loss_healed"] = len(heal_times)
        # one replica loss on a replicated volume
        rep = get_json(f"{master.url}/dir/assign?replication=010")
        http_request("POST",
                     f"http://{rep['publicUrl']}/{rep['fid']}", b"r" * 4000)
        rvid = int(rep["fid"].split(",")[0])
        holders = [sv for sv in env.servers() if rvid in sv.volumes]
        if len(holders) == 2:
            t0 = time.time()
            env.post(f"{holders[0].http}/admin/delete_volume",
                     {"volume": rvid})
            deadline = t0 + 60
            while time.time() < deadline:
                if len([sv for sv in env.servers()
                        if rvid in sv.volumes]) == 2:
                    out["replica_loss_time_to_heal_s"] = round(
                        time.time() - t0, 3)
                    break
                time.sleep(0.1)
        st = get_json(f"{master.url}/debug/maintenance")
        out["tasks_executed"] = st.get("counts", {})
        out["scheduler_stats"] = st.get("scheduler", {}).get("stats", {})
    finally:
        for vs in vols:
            vs.stop()
        master.stop()
    return out


def _repair_wire_bytes() -> dict:
    """Current SeaweedFS_volume_ec_repair_bytes_on_wire_total{mode} values
    off the shared in-process registry (every server in a bench cluster
    shares it, so the counters sum cluster-wide traffic)."""
    from seaweedfs_tpu.stats import default_registry

    out = {"classic": 0.0, "pipelined": 0.0}
    for line in default_registry().render().splitlines():
        if line.startswith("SeaweedFS_volume_ec_repair_bytes_on_wire_total{"):
            for mode in out:
                if f'mode="{mode}"' in line:
                    out[mode] = float(line.rsplit(" ", 1)[1])
    return out


def rebuild_bandwidth_summary(blobs: int = 8) -> dict:
    """PR-11: repair bandwidth per shard rebuild, classic vs pipelined.
    A 4-node cluster EC-encodes a volume (4 nodes so the partial-sum
    chain has >= 3 hops and headroom for a restart), then per mode the
    maintenance daemon (rebuildMode forced) heals one injected shard
    loss under its own scheduler/token-bucket pacing — the PR-9 chaos
    harness's heal path. Records bytes-on-wire moved per mode (the
    counter the volume servers increment at every repair payload
    receipt) and the daemon's time-to-heal per mode: the regenerating-
    code claim (arXiv:1412.3022) measured, not assumed."""
    import tempfile

    from seaweedfs_tpu.server.httpd import get_json, http_request, post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.shell import CommandEnv, run_command

    d = os.path.join(BENCH_DIR, "rebuild_bandwidth")
    os.makedirs(d, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=d)
    master = MasterServer(port=0, pulse_seconds=1, volume_size_limit_mb=64,
                          maintenance_interval=0.25)
    master.start()
    vols = []
    out: dict = {}
    try:
        # 5 nodes: the partial-sum chain keeps >= 4 contributing hops
        # even when `use` (10 of 13 survivors) skips one holder entirely
        for i in range(5):
            vs = VolumeServer(
                [os.path.join(tmp, f"v{i}")], master.url, port=0,
                rack=f"r{i}", pulse_seconds=1, max_volume_count=30,
            )
            vs.start()
            vols.append(vs)
        env = CommandEnv(master.url)
        fids = []
        for i in range(blobs):
            a = get_json(f"{master.url}/dir/assign")
            http_request("POST", f"http://{a['publicUrl']}/{a['fid']}",
                         b"b" * 40000)
            fids.append(a["fid"])
        vid = int(fids[0].split(",")[0])
        run_command(env, "lock")
        run_command(env, f"ec.encode -volumeId {vid}")
        run_command(env, "unlock")

        def shard_count() -> int:
            return len({
                s for sv in env.servers() for s in sv.ec_shards.get(vid, [])
            })

        shard_sizes = [
            os.path.getsize(os.path.join(root, name))
            for root, _, names in os.walk(tmp) for name in names
            if name.endswith(".ec00")
        ]
        if shard_sizes:
            out["shard_size"] = shard_sizes[0]
        for mode in ("classic", "pipelined"):
            post_json(f"{master.url}/maintenance/enable",
                      {"rebuildMode": mode})
            holders = [sv for sv in env.servers() if sv.ec_shards.get(vid)]
            victim = min(holders, key=lambda sv: len(sv.ec_shards[vid]))
            lost = list(victim.ec_shards[vid])[:1]
            before = _repair_wire_bytes()
            t0 = time.time()
            env.post(
                f"{victim.http}/admin/ec/delete_shards",
                {"volume": vid, "shards": lost, "delete_index": False},
            )
            # the loss must surface in topology before the heal is timed
            # (same guard as maintenance_summary: no phantom heals)
            seen_loss = False
            while time.time() < t0 + 10:
                if shard_count() < 14:
                    seen_loss = True
                    break
                time.sleep(0.05)
            if not seen_loss:
                out[f"rebuild_{mode}"] = {"error": "loss never surfaced"}
                continue
            while time.time() < t0 + 90 and shard_count() < 14:
                time.sleep(0.1)
            healed = shard_count() == 14
            delta = _repair_wire_bytes()
            out[f"rebuild_bytes_on_wire_{mode}"] = int(
                delta[mode] - before[mode])
            if healed:
                out[f"time_to_heal_{mode}_s"] = round(time.time() - t0, 3)
            post_json(f"{master.url}/maintenance/disable")
        cw = out.get("rebuild_bytes_on_wire_classic", 0)
        pw = out.get("rebuild_bytes_on_wire_pipelined", 0)
        if cw and pw:
            out["wire_cut_ratio"] = round(cw / pw, 2)

        # --- PR-15 phase: hop-parallel streaming vs the serial chain ---
        # Same chain (>= 4 hops), same chunking (>= 8 chunks), daemon
        # off, direct ladder: wall-clock is the only variable. The
        # streaming claim is ~(H + N) chunk-times vs H x N — a claim
        # about per-hop TIME, which an in-process localhost cluster
        # doesn't have; the faults switchboard injects the same fixed
        # per-hop latency into BOTH modes (repair.partial_fetch fires
        # once per hop per chunk in each dataflow), so the measured
        # ratio is the protocol's dataflow shape, not socket noise.
        from seaweedfs_tpu.shell.commands_ec import (
            apply_rebuild_pipelined,
            plan_rebuild_pipelined,
        )
        from seaweedfs_tpu.util import faults as faults_mod

        HOP_MS = 4.0

        def wait_shards(n: int, timeout: float = 30.0) -> bool:
            t = time.time()
            while time.time() < t + timeout:
                if shard_count() == n:
                    return True
                time.sleep(0.05)
            return False

        def lose(shards: list[int]) -> None:
            for s in shards:
                sv = next(v for v in env.servers()
                          if s in v.ec_shards.get(vid, []))
                env.post(f"{sv.http}/admin/ec/delete_shards",
                         {"volume": vid, "shards": [s],
                          "delete_index": False})

        try:
            # the daemon must not race the direct ladder (phase A's error
            # paths can leave it enabled)
            post_json(f"{master.url}/maintenance/disable")
            wait_shards(14)
            stream_res: dict = {}
            faults_mod.enable()
            faults_mod.arm("repair.partial_fetch", "latency", ms=HOP_MS)
            try:
                for label, use_stream in (("serial", False),
                                          ("stream", True)):
                    lose([0])
                    if not wait_shards(13):
                        raise RuntimeError("loss never surfaced")
                    pplan = plan_rebuild_pipelined(env, vid, "")
                    hops = len(pplan["chain"])
                    shard_size = int(out.get("shard_size") or 0)
                    chunk = max(1024, -(-max(shard_size, 1) // 12))
                    t0 = time.time()
                    _, stats = apply_rebuild_pipelined(
                        env, pplan, chunk=chunk, stream=use_stream)
                    stream_res[label] = {
                        "wallclock_s": round(time.time() - t0, 4),
                        "hops": hops,
                        "chunks": -(-stats["shard_size"] // chunk),
                        "bytes_on_wire": stats["bytes_on_wire_total"],
                        "survivor_bytes_read":
                            stats["survivor_bytes_read"],
                    }
                    if not wait_shards(14):
                        raise RuntimeError(f"{label} heal never surfaced")
            finally:
                faults_mod.disarm_all()
            out["stream_vs_serial"] = stream_res
            out["hop_latency_ms"] = HOP_MS
            out["serial_wallclock_s"] = stream_res["serial"]["wallclock_s"]
            out["stream_wallclock_s"] = stream_res["stream"]["wallclock_s"]
            if stream_res["serial"]["wallclock_s"] > 0:
                out["stream_vs_serial_ratio"] = round(
                    stream_res["stream"]["wallclock_s"]
                    / stream_res["serial"]["wallclock_s"], 3)
            out["stream_equal_wire"] = (
                stream_res["serial"]["bytes_on_wire"]
                == stream_res["stream"]["bytes_on_wire"])
        except Exception as e:
            out["stream_vs_serial"] = {"error": str(e)[:120]}

        # --- PR-15 phase: 2 lost shards of one stripe, ONE chain pass ---
        # The hops scale (2 x k) coefficient blocks and forward stacked
        # partials: each survivor range is read ONCE (not once per
        # target) and wire bytes per recovered shard stay flat.
        try:
            wait_shards(14)
            lose([0, 1])
            if not wait_shards(12):
                raise RuntimeError("double loss never surfaced")
            pplan = plan_rebuild_pipelined(env, vid, "")
            links = max(len(pplan["chain"]) - 1, 1)
            shard_size = int(out.get("shard_size") or 0)
            chunk = max(1024, -(-max(shard_size, 1) // 12))
            t0 = time.time()
            rebuilt, stats = apply_rebuild_pipelined(
                env, pplan, chunk=chunk, stream=True)
            multi = {
                "targets": sorted(rebuilt),
                "hops": len(pplan["chain"]),
                "wallclock_s": round(time.time() - t0, 4),
                "chain_passes": 1 + stats["restarts"],
                "bytes_on_wire": stats["bytes_on_wire_total"],
                "survivor_bytes_read": stats["survivor_bytes_read"],
                # == 1.0: each survivor range read once for BOTH targets
                # (two separate passes would read them twice)
                "survivor_reads_per_pass": round(
                    stats["survivor_bytes_read"]
                    / (10.0 * stats["shard_size"]), 3),
                # == 1.0: wire per recovered shard equals a one-target
                # pass over the same chain — stacking targets onto one
                # traversal does not double what crosses the wire
                "wire_per_target_per_link": round(
                    stats["bytes_on_wire_total"]
                    / (2.0 * links * stats["shard_size"]), 3),
            }
            out["multi_target"] = multi
            if not wait_shards(14):
                raise RuntimeError("multi-target heal never surfaced")
        except Exception as e:
            out["multi_target"] = {"error": str(e)[:120]}

        # --- PR-15 phase: lazy-batching window through the daemon ---
        # Two co-stripe losses a scan apart: with -repair.lazyWindow the
        # first single-shard task defers, the second loss FOLDS into it,
        # and one multi-target dispatch heals both.
        def lazy_counts() -> dict:
            from seaweedfs_tpu.stats import default_registry

            c: dict = {}
            for line in default_registry().render().splitlines():
                if line.startswith(
                        "SeaweedFS_maintenance_lazy_batch_total{"):
                    k = line.split('outcome="', 1)[1].split('"', 1)[0]
                    c[k] = c.get(k, 0) + float(line.rsplit(" ", 1)[1])
            return c

        try:
            wait_shards(14)
            before_lazy = lazy_counts()
            post_json(f"{master.url}/maintenance/enable",
                      {"rebuildMode": "pipelined", "lazyWindow": 1.5})
            t0 = time.time()
            lose([2])
            time.sleep(0.4)  # a detector scan apart, inside the window
            lose([3])
            if not wait_shards(12, timeout=10):
                pass  # losses may heal before both surface; counters tell
            healed = wait_shards(14, timeout=60)
            delta = {
                k: round(v - before_lazy.get(k, 0), 1)
                for k, v in lazy_counts().items()
                if v - before_lazy.get(k, 0) > 0
            }
            out["lazy_batching"] = {
                "window_s": 1.5,
                "healed": healed,
                "time_to_heal_s": round(time.time() - t0, 3)
                if healed else None,
                "outcomes": delta,
            }
            post_json(f"{master.url}/maintenance/disable")
        except Exception as e:
            out["lazy_batching"] = {"error": str(e)[:120]}

        # --- regression guard (cluster.check -fail-style) ---
        # vs the recorded prior round: a >25% streaming wall-clock
        # regression marks the record, and `bench.py -fail` exits 2 on it
        try:
            prior = None
            prior_path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_full.json")
            if os.path.exists(prior_path):
                with open(prior_path) as f:
                    prior = (json.load(f).get("rebuild_bandwidth") or {}) \
                        .get("stream_wallclock_s")
            cur = out.get("stream_wallclock_s")
            out["wallclock_guard"] = {
                "prior_stream_wallclock_s": prior,
                "stream_wallclock_s": cur,
                "max_regression": 1.25,
                "regressed": bool(
                    prior and cur and cur > 1.25 * float(prior)),
            }
        except Exception as e:
            out["wallclock_guard"] = {"error": str(e)[:120]}
    finally:
        for vs in vols:
            vs.stop()
        master.stop()
    return out


def availability_summary(
    outage_s: float = 10.0, blobs: int = 60, readers: int = 4,
) -> dict:
    """PR-9: availability UNDER a fault, not after it. A 3-node cluster
    with the maintenance daemon serves a concurrent read workload while
    one volume holder is killed for real; reports the client-visible
    error rate, the degraded/retried share, read p99 inside the outage
    window, and time-to-heal — the service-through-repair coexistence
    RapidRAID (arXiv:1207.6744) argues for, measured instead of assumed.

    PR-13 extends the phase with the flight-recorder/SLO acceptance: a
    fault injected at the needle-read seam makes an online-EC
    collection's reads DEGRADE (reconstructed, journaled with trace
    ids) and the replicated collection's reads 500-then-retry, so the
    fast-burn SLO alert must fire during the outage and clear after
    heal (`slo_summary`), and the fraction of degraded reads whose
    causal chain fully resolves (trace -> request span + a journaled
    fault cause) is recorded as `why_coverage`."""
    import tempfile
    import threading

    from seaweedfs_tpu.filer.wdclient import WeedClient
    from seaweedfs_tpu.server.httpd import get_json, http_request, post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.shell import CommandEnv
    from seaweedfs_tpu.stats import default_registry, parse_exposition
    from seaweedfs_tpu.stats import alerts as alerts_mod
    from seaweedfs_tpu.stats import events as events_mod
    from seaweedfs_tpu.stats import trace as trace_mod
    from seaweedfs_tpu.util import faults

    EC_BLOCK = 4096
    d = os.path.join(BENCH_DIR, "availability")
    os.makedirs(d, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=d)
    master = MasterServer(port=0, pulse_seconds=1, volume_size_limit_mb=64,
                          maintenance_interval=0.25,
                          ec_online="availec", ec_online_block=EC_BLOCK)
    master.start()
    vols = []
    out: dict = {"outage_s": outage_s, "readers": readers, "blobs": blobs}
    try:
        for i in range(3):
            vs = VolumeServer(
                [os.path.join(tmp, f"v{i}")], master.url, port=0,
                rack=f"r{i}", pulse_seconds=1, max_volume_count=30,
            )
            vs.start()
            vols.append(vs)
        env = CommandEnv(master.url)
        data = os.urandom(4096)
        fids = []
        for _ in range(blobs):
            a = get_json(f"{master.url}/dir/assign?replication=010"
                         "&collection=avail")
            http_request("POST", f"http://{a['publicUrl']}/{a['fid']}", data)
            fids.append(a["fid"])
        # online-EC blobs whose reads will DEGRADE (reconstruct from the
        # streamed parity) when the .dat read fault fires mid-outage
        ec_urls = []
        ec_vids: set = set()
        for _ in range(4):
            a = get_json(f"{master.url}/dir/assign?collection=availec")
            url = f"http://{a['publicUrl']}/{a['fid']}"
            http_request("POST", url, os.urandom(EC_BLOCK * 10))
            ec_urls.append(url)
            ec_vids.add(int(a["fid"].split(",")[0]))
        for vs in vols:
            if vs.fastlane:
                vs.fastlane.drain()
            for vid_ in list(vs.store.volume_ids()):
                v_ = vs.store.get_volume(vid_)
                if v_ is not None and v_.online_ec is not None:
                    v_.online_ec.pump(force=True)
        post_json(f"{master.url}/maintenance/enable")
        # tighten the SLO windows to the phase's timescale (the 5s
        # history interval still gives each window >= 2 samples) and let
        # the degraded_reads alert fire on the phase's modest read rate
        eng = alerts_mod.engine()
        eng.configure(slo_fast_window=15.0, slo_slow_window=45.0,
                      degraded_read_rate=0.05)

        def degraded_total() -> float:
            return sum(
                v for name, _, v in parse_exposition(
                    default_registry().render())
                if name == "SeaweedFS_volume_degraded_reads_total"
            )

        wc = WeedClient(master.url, cache_ttl=2.0)
        lock = threading.Lock()
        stats = {"ok": 0, "err": 0}
        lat_outage: list[float] = []
        window = {"t0": None, "t1": None}
        stop = threading.Event()

        def reader(seed: int) -> None:
            i = seed
            while not stop.is_set():
                fid = fids[i % len(fids)]
                i += 1
                t0 = time.perf_counter()
                try:
                    wc.fetch(fid)
                    ok = True
                except Exception:
                    ok = False
                dt = time.perf_counter() - t0
                with lock:
                    stats["ok" if ok else "err"] += 1
                    w0, w1 = window["t0"], window["t1"]
                    if w0 is not None and w0 <= t0 and (
                            w1 is None or t0 < w1):
                        lat_outage.append(dt)

        threads = [threading.Thread(target=reader, args=(s,), daemon=True)
                   for s in range(readers)]
        for t in threads:
            t.start()
        time.sleep(2.0)  # healthy baseline running
        retried_before = wc.retried_reads
        degraded_before = degraded_total()
        victim = next(
            vs for vs in vols
            if any(vs.store.has_volume(int(f.split(",")[0])) for f in fids)
        )
        victim_vids = {
            int(f.split(",")[0]) for f in fids
            if victim.store.has_volume(int(f.split(",")[0]))
        }
        # time-to-heal polls CONCURRENTLY with the outage window — the
        # daemon usually re-replicates well inside outage_s, and polling
        # only afterwards would floor the metric at the window length
        heal = {"at": None}

        victim_id = f"{victim._host}:{victim.data_port}"

        def heal_poll(t0: float) -> None:
            # count holders EXCLUDING the victim: the dead node rides the
            # topology until heartbeat expiry (a stale "2 holders" view),
            # and the evacuate pre-copy can heal BEFORE expiry ever makes
            # the loss visible — surviving-holder count is the truth
            deadline = t0 + 60
            while time.time() < deadline:
                live: dict = {}
                try:
                    for sv in env.servers():
                        if sv.id == victim_id:
                            continue
                        for vid in sv.volumes:
                            live[vid] = live.get(vid, 0) + 1
                except Exception:
                    time.sleep(0.2)
                    continue
                if all(live.get(vid, 0) >= 2 for vid in victim_vids):
                    heal["at"] = time.time()
                    return
                time.sleep(0.2)

        # --- PR-13: degraded reads + SLO burn through the outage -------
        ev_t0 = time.time()
        faults.enable()
        # fires inside each Python-path read's request span, so every
        # injection and every degraded read journals with its trace id:
        # online-EC reads reconstruct (200, degraded), replicated reads
        # 500 at the faulted holder and fail over (genuine 5xx burn)
        faults.arm("volume.read.idx", "error", rate=0.3)
        stop_aux = threading.Event()
        deg_stats = {"ok": 0, "err": 0}
        py_stats = {"ok": 0, "err": 0}

        def ec_reader() -> None:
            i = 0
            while not stop_aux.is_set():
                url = ec_urls[i % len(ec_urls)]
                i += 1
                try:
                    st, _, _ = http_request(
                        "GET", url + "?availdeg=1", timeout=10)
                    ok = st == 200
                except Exception:
                    ok = False
                deg_stats["ok" if ok else "err"] += 1
                time.sleep(0.05)

        loc_map = {
            fid: [l["url"] for l in get_json(
                f"{master.url}/dir/lookup?volumeId={fid.split(',')[0]}",
                timeout=5).get("locations", [])]
            for fid in fids
        }

        def py_reader() -> None:
            # query-string GETs ride the Python path (the metered one the
            # SLO availability objective watches); a 500 fails over to
            # the other replica like the real client would
            i = 0
            while not stop_aux.is_set():
                fid = fids[i % len(fids)]
                i += 1
                ok = False
                for loc in loc_map[fid]:
                    try:
                        st, _, _ = http_request(
                            "GET", f"http://{loc}/{fid}?bench=1",
                            timeout=10)
                    except Exception:
                        continue
                    if st == 200:
                        ok = True
                        break
                py_stats["ok" if ok else "err"] += 1
                time.sleep(0.02)

        # continuous cause-chain resolution: each journaled degraded read
        # is resolved while its trace is FRESH (an operator runs
        # cluster.why near the incident; post-hoc resolution after a
        # minute of storm would measure ring retention, not correlation)
        rec = events_mod.recorder()
        col = trace_mod.collector()
        why_cov = {"seen": set(), "total": 0, "resolved": 0}

        def why_resolver() -> None:
            while True:
                done = stop_aux.is_set()  # final pass after stop
                fault_evs = rec.events(type="fault_injected", limit=0)
                fault_traces = {f.get("trace_id") for f in fault_evs
                                if f.get("trace_id")}
                fault_vols = {f.get("volume") for f in fault_evs
                              if f.get("volume") is not None}
                for e in rec.events(type="degraded_read", limit=0):
                    if e["ts"] < ev_t0 or e.get("volume") not in ec_vids \
                            or e["seq"] in why_cov["seen"]:
                        continue
                    why_cov["seen"].add(e["seq"])
                    why_cov["total"] += 1
                    tid = e.get("trace_id")
                    if tid and col.trace_spans(tid) and (
                            tid in fault_traces
                            or e.get("volume") in fault_vols):
                        why_cov["resolved"] += 1
                if done:
                    return
                time.sleep(0.3)

        slo_state = {"fired": False, "max_burn": 0.0, "alerts": set()}

        def slo_watch() -> None:
            while not stop_aux.is_set():
                try:
                    eng.history.ensure_fresh(2.0)
                    snap = eng.snapshot()
                    slo_state["alerts"] |= set(snap["firing"])
                    if "slo_burn_fast" in snap["firing"]:
                        slo_state["fired"] = True
                    for s in eng.slo_status().values():
                        b = s.get("burn_fast")
                        if b:
                            slo_state["max_burn"] = max(
                                slo_state["max_burn"], b)
                except Exception:
                    pass
                time.sleep(0.5)

        aux = [threading.Thread(target=fn, daemon=True)
               for fn in (ec_reader, py_reader, slo_watch, why_resolver)]
        for t in aux:
            t.start()

        window["t0"] = time.perf_counter()
        heal_t0 = time.time()
        healer = threading.Thread(target=heal_poll, args=(heal_t0,),
                                  daemon=True)
        healer.start()
        victim.stop()
        time.sleep(outage_s)
        window["t1"] = time.perf_counter()
        faults.disarm_all()  # the injected outage ends with the window
        healer.join(timeout=max(0.0, heal_t0 + 60 - time.time()))
        healed_at = heal["at"]
        stop.set()
        stop_aux.set()
        for t in threads + aux:
            t.join(timeout=10)

        # the fast-burn alert must CLEAR once the burst ages out of the
        # (tightened) fast window — the "fires during the outage, clears
        # after heal" acceptance, measured
        cleared = False
        clear_deadline = time.time() + 60
        while time.time() < clear_deadline:
            try:
                eng.history.ensure_fresh(1.0)
                if "slo_burn_fast" not in eng.snapshot()["firing"]:
                    cleared = True
                    break
            except Exception:
                pass
            time.sleep(1.0)
        out["slo_summary"] = {
            "fast_burn_fired_during_outage": slo_state["fired"],
            "fast_burn_cleared_after_heal": cleared,
            "max_burn_fast": round(slo_state["max_burn"], 2),
            "alerts_during_outage": sorted(slo_state["alerts"]),
            # python-path reads driven through the fault (each 500
            # fails over to the other replica); errors = reads where NO
            # replica served
            "python_path_reads": py_stats["err"] + py_stats["ok"],
            "python_path_errors": py_stats["err"],
            "degraded_collection_reads": deg_stats["ok"],
            "degraded_collection_errors": deg_stats["err"],
        }

        # why coverage: fraction of journaled degraded reads whose cause
        # chain fully resolved — a trace id resolving to the request
        # span AND a journaled fault injection tied to the same trace or
        # volume (the cluster.why acceptance, computed not eyeballed)
        out["why_coverage"] = {
            "degraded_reads_journaled": why_cov["total"],
            "cause_chain_resolved": why_cov["resolved"],
            "ratio": (round(why_cov["resolved"] / why_cov["total"], 4)
                      if why_cov["total"] else None),
        }
        total = stats["ok"] + stats["err"]
        out["reads_total"] = total
        out["reads_failed"] = stats["err"]
        out["error_rate"] = round(stats["err"] / total, 6) if total else None
        out["retried_reads"] = wc.retried_reads - retried_before
        out["degraded_reads"] = degraded_total() - degraded_before
        out["retried_ratio_outage"] = (
            round((wc.retried_reads - retried_before) / len(lat_outage), 4)
            if lat_outage else None
        )
        if lat_outage:
            lat_outage.sort()
            out["outage_reads"] = len(lat_outage)
            out["outage_p50_ms"] = round(
                lat_outage[len(lat_outage) // 2] * 1e3, 2)
            out["outage_p99_ms"] = round(
                lat_outage[min(len(lat_outage) - 1,
                               int(len(lat_outage) * 0.99))] * 1e3, 2)
        out["time_to_heal_s"] = (
            round(healed_at - heal_t0, 3) if healed_at else None
        )
    finally:
        faults.disarm_all()
        try:  # restore the process-wide engine's default thresholds
            eng.configure(
                slo_fast_window=alerts_mod.DEFAULT_PARAMS["slo_fast_window"],
                slo_slow_window=alerts_mod.DEFAULT_PARAMS["slo_slow_window"],
                degraded_read_rate=alerts_mod.DEFAULT_PARAMS[
                    "degraded_read_rate"],
            )
        except Exception:
            pass
        for vs in vols:
            vs.stop()
        master.stop()
    return out


def bench_scrub(staging: str, needles: int = 49152,
                needle_bytes: int = 1024) -> dict:
    """PR-14: integrity-scrub throughput + time-to-detect. Builds a
    volume of uniform 1KB needles (the small-files bench's blob size —
    the regime where bulk hashing pays, arXiv:1202.3669), scrubs it
    unthrottled through the batched CRC32C kernel and again with the
    scalar table path, then flips one bit and measures how long a pass
    takes to FIND it (detection latency per volume, not per cluster —
    the scan interval governs the rest)."""
    import shutil

    from seaweedfs_tpu.maintenance.scrub import VolumeScrubber
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store

    d = os.path.join(staging, "scrub")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d, exist_ok=True)
    st = Store([d])
    v = st.add_volume(1, "")
    rng = np.random.RandomState(14)
    payload = rng.randint(
        0, 256, size=(64, needle_bytes), dtype=np.uint8)
    for i in range(needles):
        v.write_needle(Needle(
            cookie=0x14, id=i + 1,
            data=payload[i % 64].tobytes(),
        ))
    out: dict = {"needles": needles, "needle_bytes": needle_bytes}

    def one_pass(use_batch: bool) -> tuple[float, float]:
        sc = VolumeScrubber(st, rate_mb=1e9, use_batch=use_batch)
        t0 = time.perf_counter()
        found = sc.scrub_pass()
        wall = time.perf_counter() - t0
        assert found == [], "clean volume must scrub clean"
        gbps = sc.stats["bytes_scanned"] / max(sc.stats["seconds"], 1e-9) / 1e9
        return gbps, wall

    # best of 3 per kernel: this box's granted CPU swings
    batched = max(one_pass(True)[0] for _ in range(3))
    scalar = max(one_pass(False)[0] for _ in range(3))
    out["scrub_gbps"] = {
        "batched": round(batched, 3), "scalar": round(scalar, 3),
        "speedup": round(batched / max(scalar, 1e-9), 2),
    }
    # flip one bit mid-volume; a pass must find exactly that needle
    victim = needles // 2
    nv = v.nm.get(victim)
    with open(v.base_name + ".dat", "r+b") as f:
        f.seek(nv[0] + 40)
        b = f.read(1)
        f.seek(nv[0] + 40)
        f.write(bytes([b[0] ^ 0x10]))
    sc = VolumeScrubber(st, rate_mb=1e9)
    t0 = time.perf_counter()
    found = sc.scrub_pass()
    out["scrub_time_to_detect_s"] = round(time.perf_counter() - t0, 4)
    out["detected"] = (
        [f.kind for f in found] == ["corrupt_needle"]
        and found[0].needle == victim
    )
    # repair the flip with the victim's ORIGINAL payload (needle id n
    # carries payload[(n-1) % 64]) — a clean volume for the p99 phase
    v.write_needle(Needle(cookie=0x14, id=victim,
                          data=payload[(victim - 1) % 64].tobytes()))

    # foreground impact: read p99 with no scrub vs during a continuous
    # DEFAULT-throttled (8 MB/s) scrub — the token bucket's promise
    import threading

    def read_p99(seconds: float) -> float:
        lat = []
        stop_at = time.perf_counter() + seconds
        i = 0
        while time.perf_counter() < stop_at:
            t0 = time.perf_counter()
            v.read_needle(i % needles + 1)
            lat.append(time.perf_counter() - t0)
            i += 1
        lat.sort()
        return lat[int(len(lat) * 0.99)]

    p99_idle = read_p99(1.0)
    throttled = VolumeScrubber(st, rate_mb=8.0)
    stop = threading.Event()

    def bg():
        while not stop.is_set():
            throttled.scrub_pass()

    t = threading.Thread(target=bg, daemon=True)
    t.start()
    p99_during = read_p99(1.5)
    stop.set()
    t.join(timeout=10)
    out["foreground_read_p99_ms"] = {
        "idle": round(p99_idle * 1000, 4),
        "during_scrub": round(p99_during * 1000, 4),
        "inflation": round(p99_during / max(p99_idle, 1e-9), 2),
    }
    v.close()
    shutil.rmtree(d, ignore_errors=True)
    return out


def bench_tenant_usage(n_colls: int = 640, k: int = 64) -> dict:
    """PR-16: tenant & heat telemetry acceptance.

    * sketch accuracy — a Zipf-weighted workload over 10x-K distinct
      collections through the Space-Saving accountant: memory stays
      O(K), every reported count is within the exported per-key error
      (count - err <= true <= count), and the true heavy hitters
      survive in the top of the sketch;
    * heat separation — a hot and a cold volume series through the
      EWMA scorer must come out decisively apart;
    * forecast lifecycle — a fill burst fires the capacity_forecast
      alert pair, a deletion clears it.
    """
    import random as random_mod

    from seaweedfs_tpu.stats import alerts as alerts_mod
    from seaweedfs_tpu.stats import heat as heat_mod
    from seaweedfs_tpu.stats import usage as usage_mod
    from seaweedfs_tpu.stats.history import MetricsHistory
    from seaweedfs_tpu.stats.metrics import Registry

    out: dict = {"k": k, "collections": n_colls}

    # --- sketch accuracy vs ground truth -----------------------------------
    rng = random_mod.Random(0x5eed)
    acct = usage_mod.UsageAccountant(k=k)
    true: dict[str, float] = {}
    offers = []
    for i in range(n_colls):
        weight = max(1, int(2000.0 / (i + 1)))  # Zipf-ish tail
        # split each tenant's mass into chunks arriving interleaved —
        # the adversarial order that actually exercises eviction churn
        while weight > 0:
            chunk = min(weight, 25)
            offers.append((f"tenant-{i:04d}", float(chunk)))
            weight -= chunk
    rng.shuffle(offers)
    t0 = time.perf_counter()
    for coll, w in offers:
        true[coll] = true.get(coll, 0.0) + w
        acct.record(coll, requests=w)
    out["offer_usec"] = round(
        (time.perf_counter() - t0) / max(1, len(offers)) * 1e6, 3)
    snap = acct.snapshot()
    assert snap["tracked"] <= k, "sketch memory exceeded O(K)"
    reported = {r["collection"]: r for r in snap["tenants"]}
    violations = 0
    for coll, row in reported.items():
        t, c = true.get(coll, 0.0), row["requests"]
        if not (c - row["requests_err"] - 1e-6 <= t <= c + 1e-6):
            violations += 1
    top_true = sorted(true, key=true.get, reverse=True)[:10]
    out["sketch"] = {
        "tracked": snap["tracked"],
        "evictions": snap["evictions"],
        "error_bound": round(snap["error_bound"], 1),
        "bound_violations": violations,
        "top10_recall": sum(1 for c in top_true if c in reported) / 10.0,
        # folded evicted mass over the true total — can exceed 1 because
        # an evicted count carries its own inherited overestimate
        "other_fold_ratio": round(
            snap["other"]["requests"] / sum(true.values()), 4),
    }
    assert violations == 0, "sketch error bound violated"
    assert out["sketch"]["top10_recall"] >= 0.9

    # --- heat separation ----------------------------------------------------
    reg = Registry()
    hist = MetricsHistory(reg, interval=1.0, slots=200)
    c = reg.counter("SeaweedFS_volume_fastlane_volume_requests_total", "",
                    ("server", "volume", "op"))
    eng = heat_mod.HeatEngine(history=hist)
    hist.scrape_once(now=1.0)
    for step in range(1, 4):
        c.labels("bench:1", "1", "read").inc(2000)  # ~200 ops/s: hot
        c.labels("bench:1", "2", "read").inc(10)    # ~1 ops/s: cold
        hist.scrape_once(now=1.0 + 10.0 * step)
        eng.observe(now=1.0 + 10.0 * step)
    scores = {v["volume"]: v for v in eng.snapshot()["volumes"]}
    sep = scores["1"]["score"] / max(scores["2"]["score"], 1e-9)
    out["heat"] = {
        "hot_score": round(scores["1"]["score"], 1),
        "cold_score": round(scores["2"]["score"], 2),
        "separation": round(sep, 1),
        "hot_flag": scores["1"]["hot"],
    }
    assert sep > 10 and scores["1"]["hot"] and not scores["2"]["hot"]

    # --- forecast fires during the fill burst, clears after deletion --------
    used = reg.gauge("SeaweedFS_volume_disk_used_bytes", "",
                     ("server", "dir"))
    free = reg.gauge("SeaweedFS_volume_disk_free_bytes", "",
                     ("server", "dir"))
    reg.register_collector(eng.lines, names=heat_mod.HEAT_FAMILIES)
    free.labels("bench:1", "/data").set(2 * 86400 * 1e6)  # 2 days @ 1MB/s
    for now in (100.0, 160.0, 220.0):
        used.labels("bench:1", "/data").set(now * 1e6)
        hist.scrape_once(now=now)
    eng.observe(now=220.0)
    hist.scrape_once(now=221.0)
    alert_eng = alerts_mod.AlertEngine(history=hist, registry=reg)
    try:
        fired = alert_eng.evaluate(now=221.0)
        fired_during_fill = "capacity_forecast" in fired
        days = (eng.snapshot()["forecast"] or [{}])[0].get("days_to_full")
        for now in (280.0, 340.0, 400.0):
            used.labels("bench:1", "/data").set(max(0.0, (400 - now) * 1e6))
            hist.scrape_once(now=now)
        eng.observe(now=400.0)
        hist.scrape_once(now=401.0)
        hist.scrape_once(now=402.0)
        cleared = "capacity_forecast" not in alert_eng.evaluate(now=402.0)
    finally:
        alert_eng.close()
    out["forecast"] = {
        "days_to_full": days,
        "alert_fired_during_fill": fired_during_fill,
        "alert_cleared_after_deletion": cleared,
    }
    assert fired_during_fill and cleared
    return out


def bench_cluster_telemetry(gateways: int = 4, tenants: int = 200,
                            frames: int = 200) -> dict:
    """PR-18: cluster telemetry plane acceptance.

    * frame economics — a realistic gateway registry (per-role request
      counters + latency histogram + a K=64 usage sketch over `tenants`
      collections) serialized as a telemetry frame, against the full
      /metrics exposition the old N-endpoint fan-out shipped per poll;
    * merge overhead — `frames` frames from `gateways` synthetic senders
      through TelemetryAggregator.ingest: per-frame ingest wall cost,
      the aggregator's own merge_seconds accounting, and the one-fetch
      snapshot (GET /debug/cluster/telemetry body) cost;
    * live frame age — a real TelemetryPusher on a 200ms cadence against
      a real master, frame age sampled from the one-fetch endpoint:
      p50/p99 of how stale the master's view of the sender is.
    """
    import json as json_mod
    import random as random_mod

    from seaweedfs_tpu.server.httpd import get_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.stats import aggregate as agg_mod
    from seaweedfs_tpu.stats import usage as usage_mod
    from seaweedfs_tpu.stats.metrics import Registry

    def pct(sorted_vals, q):
        if not sorted_vals:
            return None
        i = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
        return sorted_vals[i]

    out: dict = {"gateways": gateways, "tenants": tenants, "frames": frames}

    # --- frame economics: bytes/frame vs the full exposition ----------------
    rng = random_mod.Random(0x18)
    reg = Registry()
    req = reg.counter("SeaweedFS_http_request_total", "requests",
                      ("role", "method", "code"))
    lat = reg.histogram("SeaweedFS_http_request_seconds", "latency",
                        ("role", "method"))
    for role in ("s3", "filer"):
        for method in ("GET", "PUT", "DELETE", "HEAD"):
            for code in ("200", "204", "404", "500"):
                req.labels(role, method, code).inc(rng.randrange(1, 5000))
            for _ in range(50):
                lat.labels(role, method).observe(rng.random() * 0.2)
    acct = usage_mod.UsageAccountant(k=64)
    for i in range(tenants):
        acct.record(f"tenant-{i:04d}", requests=float(max(1, 2000 // (i + 1))),
                    bytes_in=4096.0, bytes_out=8192.0)
    t0 = time.perf_counter()
    n_builds = 50
    for _ in range(n_builds):
        frame = agg_mod.build_frame("s3", "bench-gw:8333",
                                    registry=reg, acct=acct)
    out["build_usec_per_frame"] = round(
        (time.perf_counter() - t0) / n_builds * 1e6, 1)
    frame_bytes = len(json_mod.dumps(frame).encode())
    scrape_bytes = len(reg.render().encode())
    out["frame_bytes"] = frame_bytes
    out["scrape_bytes"] = scrape_bytes
    out["frame_vs_scrape_ratio"] = round(frame_bytes / max(1, scrape_bytes), 4)
    assert frame_bytes < scrape_bytes, \
        "a telemetry frame must undercut the full exposition it replaces"

    # --- merge overhead per frame at the aggregator -------------------------
    ag = agg_mod.TelemetryAggregator()
    base = time.time() - frames / gateways
    t0 = time.perf_counter()
    for i in range(frames):
        g = i % gateways
        t = base + (i // gateways)
        f = dict(frame)
        f.update(node=f"gw{g}:8333", proc=f"bench-proc-{g}",
                 seq=i // gateways + 1, ts=t)
        # counters must advance between frames for rates to exist
        f["samples"] = [[n, dict(l), v * (1.0 + 0.05 * (i // gateways))]
                        for n, l, v in frame["samples"]]
        assert ag.ingest(f, now=t)
    ingest_wall = time.perf_counter() - t0
    out["ingest_usec_per_frame"] = round(ingest_wall / frames * 1e6, 1)
    out["merge_usec_per_frame"] = round(
        ag.merge_seconds / max(1, ag.frames_total) * 1e6, 1)
    t0 = time.perf_counter()
    snap = ag.snapshot(now=base + frames / gateways)
    out["one_fetch_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
    assert len(snap["senders"]) == gateways
    top = snap["usage"]["tenants"][0]
    # every gateway shipped the same sketch proc-distinct: merged top
    # count must still be bracketed by the composed bound vs gateways x
    # the per-gateway true count of tenant-0000
    true_top = 2000.0 * gateways
    assert top["requests"] - top.get("requests_err", 0.0) <= true_top + 1e-6
    assert true_top <= top["requests"] + snap["usage"]["error_bound"] + 1e-6

    # --- live frame age at the master ---------------------------------------
    master = MasterServer(port=0, pulse_seconds=1)
    master.start()
    pusher = agg_mod.TelemetryPusher("s3", "bench-gw:8333", master.url,
                                     interval=0.2)
    try:
        pusher.start()
        deadline = time.time() + 2.5
        ages = []
        while time.time() < deadline:
            tele = get_json(f"{master.url}/debug/cluster/telemetry")
            s = tele.get("senders", {}).get("bench-gw:8333")
            if s is not None:
                ages.append(s["age"])
            time.sleep(0.1)
    finally:
        pusher.stop()
        master.stop()
    ages.sort()
    out["frame_age_samples"] = len(ages)
    out["frame_age_p50_s"] = round(pct(ages, 0.50), 3) if ages else None
    out["frame_age_p99_s"] = round(pct(ages, 0.99), 3) if ages else None
    assert ages and out["frame_age_p99_s"] < 5.0, \
        "pushed frames never became visible/fresh at the master"
    return out


def bench_telemetry_store(ops: int = 600_000, sim_hours: float = 2.0) -> dict:
    """PR-19: durable telemetry store acceptance.

    * hot-path overhead — the store is pull-based (the rings are the
      buffer; emit()/inc() never see the flusher), so the write path's
      only cost is the flusher thread's duty cycle: CPU seconds spent
      flushing per second of telemetry produced. <3% is the acceptance
      bound. The A/B loop delta (same workload with the flusher on vs
      no store) is reported too, but scheduler noise on a pure-Python
      loop swamps the true cost, so the duty cycle is the bound;
    * flush + replay economics — per-cycle flush wall cost while a
      simulated `sim_hours` of 5s-cadence telemetry streams through,
      spool bytes on disk, and the cold-replay cost of reading that
      spool back into fresh rings;
    * forecast window — seconds of 1m-rollup signal the capacity fit
      sees after a restart, vs the 10-minute in-memory ring it replaces.
    """
    import shutil
    import tempfile

    from seaweedfs_tpu.stats import store as store_mod
    from seaweedfs_tpu.stats.events import EventRecorder
    from seaweedfs_tpu.stats.history import MetricsHistory
    from seaweedfs_tpu.stats.metrics import Registry

    out: dict = {"ops": ops, "sim_hours": sim_hours}

    # --- hot-path A/B: flusher on (default cadence) vs no store -------------
    def hot_loop(with_store: bool) -> float:
        reg = Registry()
        hist = MetricsHistory(registry=reg)
        rec = EventRecorder()
        d = tempfile.mkdtemp(prefix="sw-bench-tel-")
        st = None
        if with_store:
            st = store_mod.TelemetryStore(
                d, history=hist, recorder=rec, registry=reg)
            st.start()
        c = reg.counter("SeaweedFS_http_request_total", "r",
                        ("role", "code")).labels("volume", "200")
        ev_every = max(1, ops // 300)
        t0 = time.perf_counter()
        for i in range(ops):
            c.inc()
            if i % ev_every == 0:
                rec.record("degraded_read", volume=1, reason="bench")
        dt = time.perf_counter() - t0
        hist.scrape_once()
        if st is not None:
            st.close()
        shutil.rmtree(d, ignore_errors=True)
        return dt

    hot_loop(False)  # warm the allocator/code paths once
    base, with_st = float("inf"), float("inf")
    for _ in range(3):  # interleaved min-of-3: fights scheduler drift
        base = min(base, hot_loop(False))
        with_st = min(with_st, hot_loop(True))
    out["hot_path_base_s"] = round(base, 4)
    out["hot_path_with_store_s"] = round(with_st, 4)
    out["hot_path_delta_ratio"] = round(max(0.0, with_st / base - 1.0), 4)

    # --- build a full spool: sim_hours of telemetry on a 1m flush cadence ---
    d = tempfile.mkdtemp(prefix="sw-bench-tel-")
    reg = Registry()
    hist = MetricsHistory(registry=reg)
    rec = EventRecorder()
    st = store_mod.TelemetryStore(d, history=hist, recorder=rec,
                                  registry=reg)
    g = reg.gauge("SeaweedFS_volume_disk_used_bytes", "",
                  ("server", "dir")).labels("bench-v1:0", "/data")
    c = reg.counter("SeaweedFS_http_request_total", "r",
                    ("role", "code")).labels("volume", "200")
    base_t = time.time() - sim_hours * 3600
    steps = int(sim_hours * 3600 / 5)
    flush_s, n_flush = 0.0, 0
    for i in range(steps):
        g.set(1e9 + 4e4 * i)  # steady fill: the forecast's signal
        c.inc(37)
        if i % 12 == 0:
            rec.record("volume_state", volume=1, state="bench")
        hist.scrape_once(now=base_t + 5 * i)
        if i % 12 == 11:  # one flush per simulated minute
            r = st.flush_once(force=True)
            flush_s += r.get("seconds", 0.0)
            n_flush += 1
    spool = st.spool_bytes()
    st.close()
    out["flush_cycles"] = n_flush
    out["flush_ms_per_cycle"] = round(flush_s / max(1, n_flush) * 1e3, 3)
    out["spool_bytes"] = sum(spool.values())
    out["spool_bytes_by_tier"] = spool
    # the acceptance bound: flush CPU per second of telemetry produced
    # (the flusher is the ONLY store cost; emits/incs never touch it)
    duty = flush_s / max(1.0, steps * 5.0)
    out["flush_overhead_ratio"] = round(duty, 6)
    assert duty < 0.03, \
        f"flusher duty cycle {duty:.2%} breaches the 3% bound"

    # --- cold replay into fresh rings + the restored forecast window --------
    reg2 = Registry()
    hist2 = MetricsHistory(registry=reg2)
    st2 = store_mod.TelemetryStore(d, history=hist2,
                                   recorder=EventRecorder(), registry=reg2)
    rep = st2.replay()
    out["replay_s"] = round(rep["seconds"], 4)
    out["replayed_samples"] = rep["samples"]
    out["replayed_events"] = rep["events"]
    pts = st2.forecast_points("SeaweedFS_volume_disk_used_bytes")
    window = max((p[-1][0] - p[0][0] for p in pts.values() if len(p) > 1),
                 default=0.0)
    out["forecast_window_s"] = round(window, 1)
    out["forecast_window_vs_ring"] = round(
        window / max(1.0, hist2.retention_seconds), 2)
    assert window > hist2.retention_seconds, \
        "the replayed forecast window must beat the in-memory ring"
    st2.close()
    shutil.rmtree(d, ignore_errors=True)
    return out


def bench_qos_multi_gateway(flood_s: float = 2.0, abusers: int = 2) -> dict:
    """PR-20: admission-control acceptance on a live 2-gateway cluster.

    One abusive tenant floods both filer front doors while a
    well-behaved tenant keeps reading; the record carries:

      * victim p99 under the flood vs the unloaded baseline (the bar:
        within 2x — the abuser's excess is shed, not queued onto the
        victim);
      * typed-only rejections — every shed is a 429/503 with
        Retry-After + X-Sw-Qos-Reason, zero untyped failures;
      * shed/admit split from the controller's own counters;
      * per-request admission cost on the un-shed hot path vs the
        victim's baseline service time (<5% bound), plus the disarmed
        one-attribute-check cost;
      * `filer_native_ratio` over a query-less slice — QoS must not
        push the engine front door off its native path;
      * the burn-coupling timeline: a scripted `cluster_slo_burn_fast`
        spike drives the actuator ladder and the record shows gates
        engaging while burning and releasing after the hold.
    """
    import tempfile
    import threading

    from seaweedfs_tpu.qos import actuator as qos_act
    from seaweedfs_tpu.qos import admission as qos_mod
    from seaweedfs_tpu.qos.actuator import Actuator
    from seaweedfs_tpu.server.filer import FilerServer
    from seaweedfs_tpu.server.httpd import http_request, post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume import VolumeServer

    def reset_qos() -> None:
        # the controller is a process singleton: hand the rest of the
        # bench run an unarmed plane and detach the actuator's alert
        # subscription (same discipline tests/test_qos.py uses)
        ctl = qos_mod.controller()
        with ctl._lock:
            ctl._limits = {}
            ctl._default = None
            ctl._buckets = {}
            ctl._gates = {}
            ctl.enabled = False
            ctl.queue_depth = qos_mod.DEFAULT_QUEUE_DEPTH
            ctl.queue_wait = qos_mod.DEFAULT_QUEUE_WAIT
            ctl.burn_retry_after = 2.0
            ctl.admitted_total = {}
            ctl.shed_total = {}
            ctl.queued_total = {}
            ctl._event_last = {}
            ctl._rearm()
        a = qos_act._actuator
        if a is not None:
            a.stop()
            if a._subscribed:
                try:
                    from seaweedfs_tpu.stats import alerts as alerts_mod

                    alerts_mod.engine().remove_on_fire(a._on_fire)
                except Exception:
                    pass
            qos_act._actuator = None

    def p(lat: list[float], q: float) -> float:
        s = sorted(lat)
        return s[min(len(s) - 1, int(q * len(s)))]

    d = os.path.join(BENCH_DIR, "qos")
    os.makedirs(d, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=d)
    reset_qos()
    out: dict = {"flood_s": flood_s, "abuser_threads": abusers,
                 "gateways": 2}
    master = MasterServer(port=0)
    master.start()
    vol = f1 = f2 = None
    try:
        vol = VolumeServer([os.path.join(tmp, "v")], master.url, port=0)
        vol.start()
        vol.heartbeat_once()
        f1 = FilerServer(master_url=master.url, port=0,
                         qos_limits="abuser=5:10,victim=100000")
        f1.start()
        f2 = FilerServer(master_url=master.url, port=0, peers=[f1.url])
        f2.start()
        f1._register_once()  # refresh ordinal/count now that f2 is up
        gws = [f1, f2]
        out["lease_shard"] = {
            "ordinals": sorted([f1._gateway_ordinal, f2._gateway_ordinal]),
            "gateway_count": f1._gateway_count,
        }
        for gw in gws:
            s, _, _ = http_request(
                "PUT", f"{gw.url}/qb/v.txt?collection=victim", b"victim")
            if s != 201:
                raise RuntimeError(f"victim seed failed: {s}")

        # --- unloaded baseline: the victim alone, both gateways -------------
        def baseline_pass(n: int = 150) -> list[float]:
            lat: list[float] = []
            for i in range(n):
                t0 = time.perf_counter()
                s, _, body = http_request(
                    "GET", f"{gws[i % 2].url}/qb/v.txt?collection=victim")
                lat.append(time.perf_counter() - t0)
                if s != 200 or body != b"victim":
                    raise RuntimeError(f"baseline read failed: {s}")
            return lat

        base_lat = baseline_pass()
        out["baseline_p50_ms"] = round(p(base_lat, 0.5) * 1e3, 3)
        out["baseline_p99_ms"] = round(p(base_lat, 0.99) * 1e3, 3)

        # --- admission cost on the un-shed hot path --------------------------
        # armed, limited tenant: classify + bucket debit + counter — the
        # full per-request seam as the filer dispatch pays it
        n = 100_000
        qos_mod.admit("victim", "interactive")  # warm
        t0 = time.perf_counter()
        for _ in range(n):
            qos_mod.admit("victim", "interactive")
        armed_us = (time.perf_counter() - t0) / n * 1e6
        out["admit_armed_us"] = round(armed_us, 3)
        out["admission_overhead_ratio"] = round(
            armed_us / (p(base_lat, 0.5) * 1e6), 5)
        if out["admission_overhead_ratio"] >= 0.05:
            raise RuntimeError(
                f"admission overhead {out['admission_overhead_ratio']:.2%}"
                " breaches the 5% bound")

        # --- abusive flood through BOTH gateways -----------------------------
        # interleaved best-of-3 rounds (each: fresh unloaded baseline,
        # then the flood): a single scheduler stall on this microVM can
        # own a 2s window's p99, so one round is NOT a QoS measurement —
        # the best round is the one the noise missed on both sides.
        # `abusers` stays within the host's parallelism (1 core here) and
        # each thread paces ~10ms between requests: unpaced spin-floods
        # saturate the single core outright (every shed still burns
        # ~1.4ms of GIL), and the victim's tail then measures CPU
        # exhaustion — a resource admission cannot refund — instead of
        # tenant isolation. Paced, the flood still oversubscribes the
        # abuser's 5 rps budget ~35x and sheds >95% of it
        abuser_st: list[tuple[int, dict]] = []
        errors: list[str] = []

        def flood_pass() -> list[float]:
            victim_lat: list[float] = []
            stop = threading.Event()

            def abuse(i: int) -> None:
                k = 0
                while not stop.is_set():
                    gw = gws[k % 2]
                    try:
                        s, h, _ = http_request(
                            "PUT",
                            f"{gw.url}/qb/a{i}_{k}.txt?collection=abuser",
                            b"junk", timeout=5)
                        abuser_st.append((s, dict(h)))
                    except Exception as e:
                        errors.append(f"abuser: {e!r}")
                    k += 1
                    time.sleep(0.01)

            def victim() -> None:
                while not stop.is_set():
                    gw = gws[len(victim_lat) % 2]
                    t0 = time.perf_counter()
                    try:
                        s, _, body = http_request(
                            "GET", f"{gw.url}/qb/v.txt?collection=victim",
                            timeout=5)
                        if s != 200 or body != b"victim":
                            errors.append(f"victim: {s}")
                    except Exception as e:
                        errors.append(f"victim: {e!r}")
                    victim_lat.append(time.perf_counter() - t0)

            threads = [threading.Thread(target=abuse, args=(i,))
                       for i in range(abusers)]
            threads.append(threading.Thread(target=victim))
            for t in threads:
                t.start()
            time.sleep(flood_s)
            stop.set()
            for t in threads:
                t.join(timeout=10)
            return victim_lat

        rounds: list[dict] = []
        for _ in range(3):
            b_lat = baseline_pass()
            v_lat = flood_pass()
            if not v_lat:
                continue
            rounds.append({
                "baseline_p99_ms": round(p(b_lat, 0.99) * 1e3, 3),
                "victim_p99_ms": round(p(v_lat, 0.99) * 1e3, 3),
                "victim_p50_ms": round(p(v_lat, 0.5) * 1e3, 3),
                "victim_reads": len(v_lat),
                "ratio": round(p(v_lat, 0.99)
                               / max(1e-9, p(b_lat, 0.99)), 2),
            })

        shed = [s for s, _ in abuser_st if s in (429, 503)]
        ok = [s for s, _ in abuser_st if s == 201]
        untyped = [
            (s, h) for s, h in abuser_st
            if s not in (201, 429, 503)
            or (s in (429, 503)
                and ("Retry-After" not in h or "X-Sw-Qos-Reason" not in h))
        ]
        out["flood"] = {
            "rounds": rounds,
            "abuser_requests": len(abuser_st),
            "abuser_admitted": len(ok),
            "abuser_shed": len(shed),
            "shed_share": round(len(shed) / max(1, len(abuser_st)), 3),
            "untyped_rejections": len(untyped),
            "client_errors": len(errors),
            "victim_reads": sum(r["victim_reads"] for r in rounds),
        }
        if rounds:
            ratio = min(r["ratio"] for r in rounds)
            out["victim_p99_vs_baseline"] = ratio
            out["victim_p99_within_2x"] = bool(ratio <= 2.0)
        ctl = qos_mod.controller()
        out["shed_total"] = {
            f"{cls}/{reason}/{coll}": v
            for (cls, reason, coll), v in sorted(ctl.shed_total.items())
        }
        if not shed or untyped or errors:
            out["flood"]["error"] = (
                "flood acceptance failed: "
                f"shed={len(shed)} untyped={len(untyped)} "
                f"errors={errors[:3]}")

        # --- native path holds under an armed plane --------------------------
        # query-less traffic (no ?collection=) is the engine front door's
        # native slice; the armed controller must not push it to Python
        if f1.fastlane is not None and f1.fastlane.front_metrics():
            for i in range(8):  # warm: first touch may miss the cache
                http_request("PUT", f"{f1.url}/qn/f{i}.txt", b"n")
                http_request("GET", f"{f1.url}/qn/f{i}.txt")

            def front_counts() -> tuple[float, float]:
                fm = f1.fastlane.front_metrics() or {}
                native = sum(st["native"] for st in fm.values())
                fb = sum(sum(st["fallback"].values())
                         for st in fm.values())
                return native, fb

            n0, fb0 = front_counts()
            for i in range(50):
                http_request("GET", f"{f1.url}/qn/f{i % 8}.txt")
            n1, fb1 = front_counts()
            dn, dfb = n1 - n0, fb1 - fb0
            out["filer_native_ratio"] = round(
                dn / max(1.0, dn + dfb), 4)
        else:
            out["filer_native_ratio"] = None

        # --- burn coupling: scripted cluster_slo_burn_fast spike -------------
        # a standalone actuator on the LIVE controller, burn scripted the
        # way the cluster evaluation would report it: calm -> 20x the
        # budget -> calm again; gates engage per tick and release after
        # the hold, and a gated background probe sheds typed 503
        burn = [0.0]
        act = Actuator(controller=ctl, burn_source=lambda: burn[0],
                       fast_burn=14.0, hold=2)
        timeline: list[dict] = []

        def tick(b: float) -> None:
            burn[0] = b
            lvl = act.step()
            timeline.append({"burn": b, "level": lvl,
                             "gates": dict(ctl.gates())})

        tick(0.0)
        for b in (20.0, 20.0):  # burning: one step per tick
            tick(b)
        s_gated, h_gated, _ = http_request(
            "GET", f"{f1.url}/qb/v.txt?collection=victim", None,
            {"X-Sw-Priority": "background"})
        for b in (0.0, 0.0, 0.0, 0.0):  # calm: relax every `hold` ticks
            tick(b)
        s_open, _, _ = http_request(
            "GET", f"{f1.url}/qb/v.txt?collection=victim", None,
            {"X-Sw-Priority": "background"})
        out["burn_coupling"] = {
            "timeline": timeline,
            "gated_probe": {
                "status": s_gated,
                "reason": h_gated.get("X-Sw-Qos-Reason"),
                "retry_after": h_gated.get("Retry-After"),
            },
            "released_probe_status": s_open,
            "engaged": bool(timeline[2]["gates"]),
            "released": timeline[-1]["gates"] == {},
            "transitions": [
                {"level": t["level"], "burn": t["burn"], "why": t["why"]}
                for t in act.transitions
            ],
        }
    finally:
        for s in (f2, f1, vol):
            if s is not None:
                s.stop()
        master.stop()
        reset_qos()
    return out


def bench_hash_1m_4k(
    total_blobs: int = 1_000_000, slab: int = 65536, device: bool = True
) -> dict:
    """BASELINE config 3: 1M x 4KB upload-path MD5+CRC32C batch hashing.
    Runs the full 1M through the native batch kernels (the serving path's
    host backend), a hashlib/scalar baseline on a sample, and the device
    kernels on a device-resident sample for the chip-side ceiling."""
    import hashlib

    from seaweedfs_tpu.ops.hash_service import _batch_hash

    rng = np.random.RandomState(4)
    sample = rng.randint(0, 256, size=(slab, 4096), dtype=np.uint8)
    out: dict = {"blobs": total_blobs, "blob_bytes": 4096}

    # scalar baseline (what r1's serving path actually did): hashlib + crc
    from seaweedfs_tpu.storage import crc as crc_mod

    n_base = 4096
    t0 = time.perf_counter()
    for i in range(n_base):
        hashlib.md5(sample[i].tobytes()).digest()
        crc_mod.crc32c(sample[i].tobytes())
    base_rate = n_base * 4096 / (time.perf_counter() - t0)
    out["scalar_baseline_gbps"] = round(base_rate / 1e9, 3)

    # native batch kernels over the full 1M, split into best-of-4 windows:
    # this host's effective CPU speed swings with noisy neighbors, and a
    # single long window would let one bad stretch define the number
    _batch_hash("native", sample[:64])  # warm
    n_windows = 4 if total_blobs >= 4 else 1
    windows = [total_blobs // n_windows] * n_windows
    windows[-1] += total_blobs - sum(windows)  # remainder stays counted
    best_dt_rate = 0.0
    total_dt = 0.0
    for per_window in windows:
        done = 0
        t0 = time.perf_counter()
        while done < per_window:
            n = min(slab, per_window - done)
            _batch_hash("native", sample[:n])
            done += n
        w = time.perf_counter() - t0
        total_dt += w
        best_dt_rate = max(best_dt_rate, per_window * 4096 / w)
    # headline stays WALL-CLOCK for comparability with earlier rounds;
    # the best homogeneous window is the noise diagnostic
    wall_rate = total_blobs * 4096 / total_dt
    out["native_batch_gbps"] = round(wall_rate / 1e9, 3)
    out["native_batch_gbps_best_window"] = round(best_dt_rate / 1e9, 3)
    out["native_batch_mhashes_s"] = round(wall_rate / 4096 / 1e6, 3)
    out["seconds_for_1m"] = round(total_dt, 2)

    # device kernels on a 16384-blob sample, host->device transfer included
    if not device:
        out["device_batch_error"] = "skipped: device down"
        out["vs_scalar"] = round(out["native_batch_gbps"] * 1e9 / base_rate, 2)
        return out
    try:
        from seaweedfs_tpu.ops.crc32c_kernel import crc32c_batch
        from seaweedfs_tpu.ops.md5_kernel import md5_batch

        dev_sample = sample[:16384]
        md5_batch(dev_sample, backend="jax")  # compile at the timed shape
        crc32c_batch(dev_sample, backend="jax")
        t0 = time.perf_counter()
        md5_batch(dev_sample, backend="jax")
        crc32c_batch(dev_sample, backend="jax")
        out["device_batch_gbps"] = round(
            len(dev_sample) * 4096 / (time.perf_counter() - t0) / 1e9, 3)
    except Exception as e:
        out["device_batch_error"] = str(e)[:120]
    out["vs_scalar"] = round(out["native_batch_gbps"] * 1e9 / base_rate, 2)
    return out


def main() -> None:
    run_t0 = time.time()
    os.makedirs(BENCH_DIR, exist_ok=True)
    staging_base = build_volume(os.path.join(BENCH_DIR, "staging"))

    seq_table = bench_sequential_reference_loop(staging_base, gfni=False)
    seq_gfni = bench_sequential_reference_loop(staging_base, gfni=True)
    verb_gbps, verb_info = bench_verb(staging_base)

    from seaweedfs_tpu.ops.rs_kernel import pick_pipeline_backend

    backend = pick_pipeline_backend()
    detail = {
        "backend": backend,
        "baseline_seq_table_gbps": round(seq_table, 3),
        "baseline_seq_gfni_gbps": round(seq_gfni, 3),
        "host_kernel_gfni_gbps": round(bench_host_kernel(), 3),
        **verb_info,
    }
    # device sections run only when jax computes on an accelerator; that
    # there is none is a reported FACT in the record, not a missing key
    dev = device_status()
    detail["device_status"] = dev
    device_dead = dev["status"] == "down"
    if device_dead:
        detail["device_kernel_gbps"] = None
        detail["device_kernel_error"] = "skipped: device down"
        detail["device_pipeline_e2e_gbps"] = None
        detail["device_pipeline_error"] = "skipped: device down"
    else:
        try:
            detail["device_kernel_gbps"] = round(bench_device_kernel(), 3)
        except Exception as e:
            detail["device_kernel_gbps"] = None
            detail["device_kernel_error"] = str(e)[:120]
        try:
            detail["device_pipeline_e2e_gbps"] = round(
                bench_device_pipeline(staging_base), 3)
        except Exception as e:
            detail["device_pipeline_e2e_gbps"] = None
            detail["device_pipeline_error"] = str(e)[:120]
    try:
        detail["hash_1m_4k"] = bench_hash_1m_4k(
            device=not device_dead
        )  # BASELINE config 3
    except Exception as e:
        detail["hash_1m_4k"] = {"error": str(e)[:120]}
    if device_dead:
        detail["hash_1m_4k"].setdefault(
            "device_batch_error", "skipped: device down"
        )
    try:
        detail["ec_rebuild"] = bench_rebuild(staging_base)  # BASELINE config 2
    except Exception as e:
        detail["ec_rebuild"] = {"error": str(e)[:120]}
    # online (write-path) EC: encode rate through ingest + amplification
    try:
        detail["ec_online"] = bench_ec_online(BENCH_DIR)
    except Exception as e:
        detail["ec_online"] = {"error": str(e)[:120]}
    try:
        detail["cdc_dedup"] = bench_cdc_dedup()  # BASELINE config 4
    except Exception as e:
        detail["cdc_dedup"] = {"error": str(e)[:120]}
    try:
        detail["small_files"] = bench_small_files()  # BASELINE.md rows 1-2
    except Exception as e:
        detail["small_files"] = {"error": str(e)[:120]}
    try:
        detail["filer_small_files"] = bench_filer_small_files()
    except Exception as e:
        detail["filer_small_files"] = {"error": str(e)[:120]}
    # PR-6: the S3 front door (engine -> filer engine relay) end to end
    try:
        detail["s3_small_files"] = bench_s3_small_files()
    except Exception as e:
        detail["s3_small_files"] = {"error": str(e)[:120]}
    # PR-5: autonomous-maintenance heal latency (injected shard/replica loss)
    try:
        detail["maintenance_summary"] = maintenance_summary()
    except Exception as e:
        detail["maintenance_summary"] = {"error": str(e)[:120]}
    # PR-9: availability under an injected single-holder outage (error
    # rate, degraded/retried share, p99 through the fault, time-to-heal)
    try:
        detail["availability_under_fault"] = availability_summary()
    except Exception as e:
        detail["availability_under_fault"] = {"error": str(e)[:120]}
    # PR-11: repair bandwidth — bytes-on-wire per shard rebuild, classic
    # whole-shard pulls vs pipelined partial-sum chains, with the
    # maintenance daemon's per-mode time-to-heal
    try:
        detail["rebuild_bandwidth"] = rebuild_bandwidth_summary()
    except Exception as e:
        detail["rebuild_bandwidth"] = {"error": str(e)[:120]}
    # PR-14: integrity scrub — batched vs scalar CRC verification rate
    # and the per-volume detection latency for an injected bit flip
    try:
        detail["scrub"] = bench_scrub(BENCH_DIR)
    except Exception as e:
        detail["scrub"] = {"error": str(e)[:120]}
    # PR-16: tenant sketch accuracy vs ground truth, hot/cold heat
    # separation, and the capacity-forecast alert firing/clearing
    try:
        detail["tenant_usage"] = bench_tenant_usage()
    except Exception as e:
        detail["tenant_usage"] = {"error": str(e)[:120]}
    # PR-18: telemetry frame economics vs full-scrape fan-out, per-frame
    # merge overhead at the aggregator, live frame age at the master
    try:
        detail["cluster_telemetry"] = bench_cluster_telemetry()
    except Exception as e:
        detail["cluster_telemetry"] = {"error": str(e)[:120]}
    # PR-19: durable telemetry store — hot-path flush overhead bound,
    # full-spool replay cost, restored forecast window vs the ring
    try:
        detail["telemetry_store"] = bench_telemetry_store()
    except Exception as e:
        detail["telemetry_store"] = {"error": str(e)[:120]}
    # PR-20: QoS admission plane — abusive-tenant flood through 2
    # gateways: victim p99 vs baseline, typed-only sheds, admission
    # overhead bound, native-path hold, burn-coupling timeline
    try:
        detail["qos_multi_gateway"] = bench_qos_multi_gateway()
    except Exception as e:
        detail["qos_multi_gateway"] = {"error": str(e)[:120]}
    # end-of-run per-kernel attribution over EVERYTHING this process ran
    # (verb trials + rebuild + hash benches), from the shared registry
    try:
        from seaweedfs_tpu.stats import default_registry

        detail["kernel_gbps"] = kernel_gbps_from_metrics(
            default_registry().render()
        )
    except Exception as e:
        detail["kernel_gbps"] = {"error": str(e)[:120]}
    # PR-3: per-stage EC pipeline busy/wait attribution over everything
    # this process encoded/rebuilt, from the same shared registry
    try:
        from seaweedfs_tpu.stats import default_registry

        detail["ec_pipeline"] = ec_pipeline_summary_from_metrics(
            default_registry().render()
        )
    except Exception as e:
        detail["ec_pipeline"] = {"error": str(e)[:120]}
    # PR-4: per-op request/byte rates from the history window covering this
    # run, plus the alerts that fired while it ran (the servers the benches
    # started fed the process-wide ring the whole time)
    try:
        from seaweedfs_tpu.stats import history as history_mod

        hist = history_mod.default_history()
        hist.scrape_once()  # close the window at the run's tail
        detail["request_rates"] = request_rates_summary_from_history(
            hist, time.time() - run_t0 + hist.interval
        )
    except Exception as e:
        detail["request_rates"] = {"error": str(e)[:120]}
    # PR-2: the fastlane engine's own series, captured while the small-file
    # cluster was still alive (its collector unregisters on server stop)
    fl = detail.get("small_files", {}).get("fastlane")
    if fl is not None:
        detail["fastlane"] = fl
    detail["note"] = (
        "value is the real shell ec.encode verb, disk-to-shards, 1GiB volume,"
        " best of 3. vs_baseline divides by baseline_seq_gfni_gbps: the"
        " reference's exact architecture (single-thread 256KB"
        " read->encode->write loop, ec_encoder.go:132-137) running the"
        " strongest CPU kernel this host has (GFNI/AVX-512 — klauspost-class,"
        " same instruction family klauspost's asm uses), end-to-end on the"
        " same volume. `backend` says which pipeline backend carried the"
        " verb (native = the fused single-pass engine: mmap'd .dat -> GFNI"
        " registers -> NT-stores into mmap'd shards, one memory pass);"
        " device_status says whether jax computed on an accelerator, and"
        " device_kernel_gbps / device_pipeline_e2e_gbps are null when it"
        " did not. Trial 1 pays the microVM's fresh-page first-touch cost"
        " once per file set."
    )
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_full.json"), "w") as f:
        json.dump(_drop_nonfinite(detail), f, indent=1, allow_nan=False)

    print(summary_line(verb_gbps, seq_gfni, backend, verb_info, dev, detail))
    # `bench.py -fail`: cluster.check -fail-style scripting hook — a >25%
    # streaming-rebuild wall-clock regression vs the recorded prior round
    # exits nonzero (the record above still carries the full numbers)
    guard = (detail.get("rebuild_bandwidth") or {}).get(
        "wallclock_guard") or {}
    if guard.get("regressed") and "-fail" in sys.argv[1:]:
        print(f"FAIL rebuild_bandwidth wall-clock regression: "
              f"{guard.get('stream_wallclock_s')}s vs prior "
              f"{guard.get('prior_stream_wallclock_s')}s (>1.25x)",
              file=sys.stderr)
        sys.exit(2)


def device_status() -> dict:
    """Whether jax computes on an accelerator in this process, for the
    record: {"status": "up", "platform", "device_kind", "count",
    "h2d_mbps"} or {"status": "down", "h2d_mbps": None, "reason"}. The
    host->device rate is one timed 64 MiB put."""
    from seaweedfs_tpu.ops import device

    try:
        platform = device.platform()
    except Exception as e:  # noqa: BLE001 - jax start-up raises many types
        return {"status": "down", "h2d_mbps": None,
                "reason": f"{type(e).__name__}: {e}"[:120]}
    if platform == "cpu":
        return {"status": "down", "h2d_mbps": None,
                "reason": "jax computes on the cpu"}
    jax = device.jax()
    probe = np.zeros(64 * 1024 * 1024, np.uint8)
    jax.device_put(probe[:65536]).block_until_ready()
    t0 = time.perf_counter()
    jax.device_put(probe).block_until_ready()
    rate = probe.nbytes / (time.perf_counter() - t0)
    return {"status": "up", **device.report()["jax"],
            "h2d_mbps": round(rate / 1e6, 1)}


def summary_line(
    verb_gbps: float, seq_gfni: float, backend: str, verb_info: dict,
    dev: dict, detail: dict,
) -> str:
    """Final line: compact scalars only (<1.5KB — whoever records the run
    keeps a short tail of stdout and parses the last line)."""
    vs = verb_gbps / seq_gfni if seq_gfni == seq_gfni and seq_gfni > 0 else 0.0
    hsh = detail.get("hash_1m_4k", {})
    reb = detail.get("ec_rebuild", {})
    onl = detail.get("ec_online", {})
    cdc = detail.get("cdc_dedup", {})
    sf = detail.get("small_files", {})
    fsf = detail.get("filer_small_files", {})
    s3f = detail.get("s3_small_files", {})
    pyc = sf.get("python_client", {})
    summary = {
        "metric": "ec.encode",
        "value": round(verb_gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(vs, 2),
        "extra": {
            "backend": backend,
            "baseline_seq_gfni_gbps": round(seq_gfni, 3),
            "trial_seconds": verb_info.get("trial_seconds"),
            # .get: a minimal status dict must never cost the whole
            # summary line (the key is required in every record)
            "device_status": dev.get("status", "down"),
            "device_h2d_mbps": dev.get("h2d_mbps"),
            "device_kernel_gbps": detail.get("device_kernel_gbps"),
            "device_pipeline_e2e_gbps": detail.get("device_pipeline_e2e_gbps"),
            "ec_rebuild_gbps": reb.get("gbps"),
            "ec_rebuild_trials": reb.get("trial_seconds"),
            "ec_online_encode_gbps": onl.get("ec_online_encode_gbps"),
            "ec_online_wa": onl.get("write_amplification"),
            "ec_online_bad_fallbacks": onl.get("pathological_fallbacks"),
            "hash_mhashes_s": hsh.get("native_batch_mhashes_s"),
            "hash_gbps": hsh.get("native_batch_gbps"),
            "hash_device_gbps": hsh.get("device_batch_gbps"),
            "hash_device_error": (hsh.get("device_batch_error") or "")[:60]
            or None,
            "cdc_gbps": cdc.get("gbps"),
            "cdc_gbps_p75": cdc.get("gbps_p75_window"),
            "sf_write_req_s": sf.get("write_req_s"),
            "sf_read_req_s": sf.get("read_req_s"),
            "fastlane_native_ratio": (sf.get("fastlane") or {}).get(
                "fastlane_native_ratio"),
            "sf_assign_write_req_s": sf.get("write_assign_per_file_req_s"),
            "py_write_req_s": pyc.get("write_req_s"),
            "py_read_req_s": pyc.get("read_req_s"),
            "filer_write_req_s": fsf.get("write_req_s"),
            "filer_read_req_s": fsf.get("read_req_s"),
            "filer_native_ratio": fsf.get("filer_native_ratio"),
            "s3_write_req_s": s3f.get("write_req_s"),
            "s3_read_req_s": s3f.get("read_req_s"),
            "scrub_gbps_batched": (detail.get("scrub", {})
                                   .get("scrub_gbps", {})).get("batched"),
            "scrub_gbps_scalar": (detail.get("scrub", {})
                                  .get("scrub_gbps", {})).get("scalar"),
            "scrub_ttd_s": detail.get("scrub", {})
            .get("scrub_time_to_detect_s"),
            "rebuild_stream_ratio": detail.get("rebuild_bandwidth", {})
            .get("stream_vs_serial_ratio"),
            "rebuild_wire_cut": detail.get("rebuild_bandwidth", {})
            .get("wire_cut_ratio"),
            "rebuild_wallclock_regressed": (
                detail.get("rebuild_bandwidth", {})
                .get("wallclock_guard") or {}).get("regressed"),
            "cluster_frame_vs_scrape": detail.get(
                "cluster_telemetry", {}).get("frame_vs_scrape_ratio"),
            "tel_flush_overhead": detail.get(
                "telemetry_store", {}).get("flush_overhead_ratio"),
            "tel_replay_s": detail.get(
                "telemetry_store", {}).get("replay_s"),
            "note": "backend = what carried the verb; device_* are null when"
            " device_status is down; detail in BENCH_full.json",
        },
    }
    summary = _drop_nonfinite(summary)
    # allow_nan=False: a NaN/Infinity that slipped through would emit
    # non-RFC-8259 JSON, which a strict parser rejects
    line = json.dumps(summary, allow_nan=False)
    if len(line) > 1500:  # hard guard: never hand the driver an unparseable tail
        summary["extra"] = {
            "device_status": dev.get("status", "down"),
            "note": "summary truncated; see BENCH_full.json",
        }
        line = json.dumps(summary, allow_nan=False)
    return line


def _drop_nonfinite(x):
    """NaN/Infinity -> None, recursively (json.dumps would emit them as
    bare NaN/Infinity tokens, which strict JSON parsers reject)."""
    import math

    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _drop_nonfinite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_drop_nonfinite(v) for v in x]
    return x


if __name__ == "__main__":
    main()
