"""Run the system's main path once on one TPU, through its own entry points.

    python chip_smoke.py --seed 0            # on a machine with one chip
    python chip_smoke.py --seed 0 --size tiny  # rehearsal on the CPU

Phase A (BASELINE configs 1 and 2) starts `python -m seaweedfs_tpu.command.main
server` as a child with the EC pipeline set to the device, fills one volume
with seeded needles over HTTP, and drives `lock`, `ec.encode`, `ec.rebuild`
through `python -m seaweedfs_tpu.command.main shell`; it compares every shard
with a host-side reference and the numpy oracle, every needle read (plain and
reconstructed) with the bytes written, and proves from the server's own
`GET /status` and `/metrics` that the device kernel carried the bytes.
Phase B (BASELINE config 3 shape) hashes seeded 4 KB blobs through
`HashService(backend="jax")` in this process and compares every MD5 and
CRC32C with hashlib and storage/crc.py.

One process per chip: this process starts jax only after the server child has
exited. Children write to files under the output directory, never to this
process's stdout. Each phase prints one JSON object of observations on its
own line; the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 — or `"ok": false` and a non-zero exit if any
comparison failed, any phase raised, the platform is not `tpu` (so always on
the CPU), or a device label carried no bytes. Without `--size tiny` the
phases are not even started when jax finds no accelerator.
"""

from __future__ import annotations

import argparse
import hashlib
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
MiB = 1024 * 1024

SIZES = {
    # needles x needle bytes fill the volume; blobs x 4 KB are hashed
    "real": {"needles": 1024, "needle_bytes": MiB, "blobs": 65536},
    "tiny": {"needles": 45, "needle_bytes": 256 * 1024, "blobs": 2048},
}
BLOB_BYTES = 4096
REMOVED_SHARD = 3
ORACLE_BUDGET_S = 40.0
EC_ENV = "SEAWEEDFS_TPU_EC_BACKEND"


def result_line(ok: bool, platform: str, kind: str, count: int) -> str:
    """The last line of stdout: exactly these keys, this nesting."""
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": platform, "kind": kind, "count": int(count)},
    })


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


class Checks:
    """Every comparison lands here; one failure fails the run."""

    def __init__(self) -> None:
        self.failures: list[str] = []

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            log(f"FAILED: {what}")
        return bool(ok)


# --- payloads ----------------------------------------------------------------
def needle_payload(seed: int, i: int, nbytes: int) -> bytes:
    import numpy as np

    return np.random.default_rng([seed, 1, i]).bytes(nbytes)


# --- http --------------------------------------------------------------------
def http_call(method: str, hostport: str, path: str, body: bytes | None = None,
              timeout: float = 600.0) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection(hostport, timeout=timeout)
    try:
        headers = {"Content-Type": "application/octet-stream"} if body else {}
        conn.request(method, path, body=body, headers=headers)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def get_json(hostport: str, path: str) -> dict:
    status, body = http_call("GET", hostport, path)
    if status != 200:
        raise RuntimeError(f"GET {hostport}{path}: {status} {body[:200]!r}")
    return json.loads(body)


def post_json(hostport: str, path: str, payload: dict) -> dict:
    status, body = http_call("POST", hostport, path, json.dumps(payload).encode())
    if status != 200:
        raise RuntimeError(f"POST {hostport}{path}: {status} {body[:200]!r}")
    return json.loads(body)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def kernel_bytes(metrics_text: str, family: str) -> dict[str, float]:
    """{kernel label: bytes} of one `<family>_bytes_total` counter."""
    out: dict[str, float] = {}
    prefix = family + '_bytes_total{kernel="'
    for line in metrics_text.splitlines():
        if line.startswith(prefix):
            label, _, value = line[len(prefix):].partition('"} ')
            out[label] = float(value)
    return out


def pipeline_seconds(metrics_text: str) -> dict[str, float]:
    """{"<stage>.<busy|wait>": seconds} summed per EC pipeline stage, from
    the server's SeaweedFS_volume_ec_pipeline_seconds histogram."""
    out: dict[str, float] = {}
    prefix = 'SeaweedFS_volume_ec_pipeline_seconds_sum{stage="'
    for line in metrics_text.splitlines():
        if line.startswith(prefix):
            labels, _, value = line[len(prefix):].partition('"} ')
            out[labels.replace('",state="', ".")] = float(value)
    return out


def approx(got: float, want: float) -> bool:
    # /metrics renders with %g: six significant digits
    return abs(got - want) <= max(want, 1.0) * 1e-5


# --- children ----------------------------------------------------------------
def run_python(args: list[str], log_path: str, env: dict, stdin: str = "",
               timeout: float = 900.0) -> tuple[int, str]:
    """Run `python <args>` to its end with stdout+stderr in log_path (never
    this process's stdout). Returns (exit code, what it wrote)."""
    with open(log_path, "w") as out:
        proc = subprocess.Popen(
            [sys.executable, *args], cwd=HERE, env=env, stdout=out,
            stderr=subprocess.STDOUT, stdin=subprocess.PIPE, text=True,
        )
        try:
            proc.communicate(stdin, timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise
    with open(log_path) as f:
        return proc.returncode, f.read()


def probe_device(outdir: str, env: dict) -> dict:
    """What jax sees, asked in a child that exits before anything else needs
    the chip: this process must stay off jax while the server child lives."""
    code = (
        "import json; from seaweedfs_tpu.ops import device; device.jax();"
        " print('PROBE ' + json.dumps(device.report()))"
    )
    rc, text = run_python(["-c", code], os.path.join(outdir, "probe.log"), env,
                          timeout=300)
    for line in text.splitlines():
        if line.startswith("PROBE "):
            return json.loads(line[len("PROBE "):])
    raise RuntimeError(f"device probe exited {rc}: {text[-600:]}")


def shell(master: str, script: str, log_path: str, env: dict) -> str:
    rc, text = run_python(
        ["-m", "seaweedfs_tpu.command.main", "shell", "-master", master],
        log_path, env, stdin=script, timeout=900,
    )
    if rc != 0:
        raise RuntimeError(f"shell {script!r} exited {rc}: {text[-600:]}")
    return text


def stop_child(proc: subprocess.Popen) -> int:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    return proc.returncode


# --- phase A -----------------------------------------------------------------
def phase_a(seed: int, size: dict, workdir: str, outdir: str, env: dict,
            checks: Checks) -> dict:
    from seaweedfs_tpu.ops.rs_kernel import RSCodec
    from seaweedfs_tpu.storage import idx as idx_mod
    from seaweedfs_tpu.storage.erasure_coding import encoder, geometry
    from seaweedfs_tpu.storage.file_id import parse_key_hash_with_delta

    obs: dict = {"phase": "A", "what": "served ec.encode / ec.rebuild"}
    srv_dir = os.path.join(workdir, "srv")
    ref_dir = os.path.join(workdir, "ref")
    os.makedirs(srv_dir)
    os.makedirs(ref_dir)
    master = f"127.0.0.1:{free_port()}"
    child_env = {**env, EC_ENV: "jax"}  # the override that exists
    server_log = open(os.path.join(outdir, "server.log"), "w")
    t_phase = time.perf_counter()
    server = subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu.command.main", "server",
         "-dir", srv_dir, "-master.port", master.rsplit(":", 1)[1],
         "-volume.port", str(free_port())],
        cwd=HERE, env=child_env, stdout=server_log, stderr=subprocess.STDOUT,
        stdin=subprocess.DEVNULL,
    )
    try:
        # --- fill one volume through /dir/assign + POST ----------------------
        n, nbytes = size["needles"], size["needle_bytes"]
        assign = None
        deadline = time.monotonic() + 120
        while assign is None:
            if server.poll() is not None:
                raise RuntimeError(f"server exited {server.returncode} at boot")
            try:
                assign = get_json(master, f"/dir/assign?count={n}")
                if "fid" not in assign:
                    raise RuntimeError(str(assign))
            except (OSError, RuntimeError, ValueError):
                assign = None
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.5)
        vol_addr, fid0 = assign["url"], assign["fid"]
        vid = int(fid0.split(",")[0])
        fids = [fid0] + [f"{fid0}_{i}" for i in range(1, n)]
        t0 = time.perf_counter()
        errors: list[str] = []

        def writer(lo: int, hi: int) -> None:
            conn = http.client.HTTPConnection(vol_addr, timeout=120)
            try:
                for i in range(lo, hi):
                    conn.request(
                        "POST", "/" + fids[i],
                        body=needle_payload(seed, i, nbytes),
                        headers={"Content-Type": "application/octet-stream"},
                    )
                    resp = conn.getresponse()
                    body = resp.read()
                    if resp.status not in (200, 201):
                        errors.append(f"{fids[i]}: {resp.status} {body[:100]!r}")
            except Exception as e:  # noqa: BLE001 - reported below
                errors.append(f"writer {lo}-{hi}: {type(e).__name__}: {e}")
            finally:
                conn.close()

        nthreads = 4
        step = -(-n // nthreads)
        threads = [
            threading.Thread(target=writer, args=(lo, min(n, lo + step)))
            for lo in range(0, n, step)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        checks.check(not errors, f"{len(errors)} needle writes failed: {errors[:3]}")
        obs["fill_seconds"] = round(time.perf_counter() - t0, 3)

        # --- lock + ec.encode through the shell verb ------------------------
        # the verb deletes the source volume at its end: keep the .dat for
        # the host-side reference first (writes are all acknowledged)
        dat_src = os.path.join(srv_dir, f"{vid}.dat")
        dat_bytes = os.path.getsize(dat_src)
        shutil.copyfile(dat_src, os.path.join(ref_dir, f"{vid}.dat"))
        obs.update(volume=vid, needles=n, needle_bytes=nbytes, dat_bytes=dat_bytes)
        t0 = time.perf_counter()
        text = shell(master, f"lock\nec.encode -volumeId {vid}\nunlock\n",
                     os.path.join(outdir, "shell_encode.log"), env)
        obs["ec_encode_verb_seconds"] = round(time.perf_counter() - t0, 3)
        checks.check(f"ec.encode volume {vid}: shards spread" in text,
                     f"ec.encode did not report success: {text[-300:]!r}")
        _, metrics = http_call("GET", vol_addr, "/metrics")
        encode_stages = pipeline_seconds(metrics.decode())
        obs["encode_pipeline_stage_seconds"] = encode_stages

        # --- every shard against the host reference and the numpy oracle ----
        base = os.path.join(srv_dir, str(vid))
        ref_base = os.path.join(ref_dir, str(vid))
        t0 = time.perf_counter()
        encoder.write_ec_files(ref_base, codec=RSCodec(backend="native"))
        obs["host_reference_seconds"] = round(time.perf_counter() - t0, 3)
        shard_size = geometry.shard_file_size(
            dat_bytes, geometry.LARGE_BLOCK_SIZE, geometry.SMALL_BLOCK_SIZE)
        identical = [
            same_file(base + geometry.to_ext(s), ref_base + geometry.to_ext(s),
                      shard_size)
            for s in range(geometry.TOTAL_SHARDS_COUNT)
        ]
        checks.check(all(identical),
                     f"shards differ from the host reference: "
                     f"{[s for s, ok in enumerate(identical) if not ok]}")
        obs["shards_identical_to_host_reference"] = sum(identical)
        obs["oracle"] = oracle_rows(ref_base + ".dat", dat_bytes, base, checks)

        # --- reads from the EC volume ---------------------------------------
        sample = sorted({0, 1, n // 2, n - 2, n - 1})
        bad = [i for i in sample if not read_matches(vol_addr, fids[i], seed, i, nbytes)]
        checks.check(not bad, f"needles read from the EC volume differ: {bad}")
        obs["ec_reads"] = len(sample)

        # --- lose one data shard, read through reconstruction, rebuild ------
        removed = post_json(vol_addr, "/admin/ec/delete_shards", {
            "volume": vid, "collection": "", "shards": [REMOVED_SHARD]})
        lost = base + geometry.to_ext(REMOVED_SHARD)
        checks.check(removed.get("removed") == [REMOVED_SHARD]
                     and not os.path.exists(lost),
                     f"shard {REMOVED_SHARD} was not removed: {removed}")
        # needles whose bytes lie in the removed shard: small-block row r
        # keeps shard s at .dat bytes [(10r+s) MiB, (10r+s+1) MiB), and the
        # .ecx says where each needle landed (the writers ran concurrently)
        key0 = parse_key_hash_with_delta(fid0.split(",")[1])[0]
        row = geometry.SMALL_BLOCK_SIZE * geometry.DATA_SHARDS_COUNT
        mids = [
            r * row + REMOVED_SHARD * geometry.SMALL_BLOCK_SIZE
            + geometry.SMALL_BLOCK_SIZE // 2
            for r in range(min(3, max(1, dat_bytes // row)))
        ]
        degraded = sorted({
            key - key0
            for key, offset, nsize in idx_mod.walk_index_file(base + ".ecx")
            if any(offset <= mid < offset + nsize for mid in mids)
        })
        checks.check(len(degraded) == len(mids),
                     f"found {len(degraded)} needles in the removed shard,"
                     f" wanted {len(mids)}")
        t0 = time.perf_counter()
        bad = [i for i in degraded if not read_matches(vol_addr, fids[i], seed, i, nbytes)]
        obs["degraded_read_seconds"] = round(time.perf_counter() - t0, 3)
        checks.check(not bad, f"reconstructed needle reads differ: {bad}")
        obs["degraded_reads"] = len(degraded)

        t0 = time.perf_counter()
        text = shell(master, f"lock\nec.rebuild -volumeId {vid}\nunlock\n",
                     os.path.join(outdir, "shell_rebuild.log"), env)
        obs["ec_rebuild_verb_seconds"] = round(time.perf_counter() - t0, 3)
        checks.check(f"rebuilt shards [{REMOVED_SHARD}]" in text,
                     f"ec.rebuild did not rebuild the shard: {text[-300:]!r}")
        checks.check(
            os.path.exists(lost) and same_file(
                lost, ref_base + geometry.to_ext(REMOVED_SHARD), shard_size),
            "the rebuilt shard differs from the one removed")

        # --- which side carried the bytes, from the server's own report -----
        status = get_json(vol_addr, "/status")
        ec = status.get("ec", {})
        obs["server"] = ec
        _, metrics = http_call("GET", vol_addr, "/metrics")
        enc = kernel_bytes(metrics.decode(), "SeaweedFS_volume_ec_encode")
        dec = kernel_bytes(metrics.decode(), "SeaweedFS_volume_ec_decode")
        obs["encode_bytes_by_kernel"] = enc
        obs["decode_bytes_by_kernel"] = dec
        obs["rebuild_pipeline_stage_seconds"] = {
            k: round(v - encode_stages.get(k, 0.0), 6)
            for k, v in pipeline_seconds(metrics.decode()).items()
        }
        jax_seen = ec.get("jax", {})
        checks.check(jax_seen.get("platform") == "tpu",
                     f"the server's jax computes on {jax_seen.get('platform')!r},"
                     " not on a tpu")
        checks.check(not ec.get("selection_failures"),
                     f"backend selection failures: {ec.get('selection_failures')}")
        checks.check(approx(enc.get("pipeline-pallas", 0.0), dat_bytes),
                     f"encode bytes under pipeline-pallas: "
                     f"{enc.get('pipeline-pallas', 0.0)} != {dat_bytes}")
        checks.check(
            approx(dec.get("rebuild-pallas", 0.0),
                   shard_size * geometry.DATA_SHARDS_COUNT),
            f"rebuild bytes under rebuild-pallas: "
            f"{dec.get('rebuild-pallas', 0.0)} != {shard_size * 10}")
        checks.check(dec.get("reconstruct-pallas", 0.0) > 0,
                     "no degraded-read bytes under reconstruct-pallas")
        host = {k: v for k, v in {**enc, **dec}.items()
                if v and not k.endswith("-pallas")}
        checks.check(not host, f"bytes under host kernel labels: {host}")
    finally:
        obs["server_exit_code"] = stop_child(server)
        server_log.close()
    checks.check(obs["server_exit_code"] == 0,
                 f"server child exited {obs['server_exit_code']}")
    obs["seconds"] = round(time.perf_counter() - t_phase, 3)
    return obs


def same_file(a: str, b: str, want_size: int) -> bool:
    if os.path.getsize(a) != want_size or os.path.getsize(b) != want_size:
        return False
    with open(a, "rb") as fa, open(b, "rb") as fb:
        while True:
            x, y = fa.read(8 * MiB), fb.read(8 * MiB)
            if x != y:
                return False
            if not x:
                return True


def read_matches(vol_addr: str, fid: str, seed: int, i: int, nbytes: int) -> bool:
    status, body = http_call("GET", vol_addr, "/" + fid)
    return status == 200 and body == needle_payload(seed, i, nbytes)


def oracle_rows(dat_path: str, dat_bytes: int, base: str, checks: Checks) -> dict:
    """Hold the served shards to the numpy oracle row by row: the first
    row, the zero-padded tail row and the last full row first, then the rest
    for as long as the budget lasts. (A volume of this size has small-block
    rows only: a large-block row needs more than 10 GiB.)"""
    import numpy as np

    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.storage.erasure_coding import geometry

    block = geometry.SMALL_BLOCK_SIZE
    data_n, total_n = geometry.DATA_SHARDS_COUNT, geometry.TOTAL_SHARDS_COUNT
    row_bytes = block * data_n
    if dat_bytes > geometry.LARGE_BLOCK_SIZE * data_n:
        raise RuntimeError("oracle_rows expects a volume of small-block rows")
    rows = -(-dat_bytes // row_bytes)
    order = list(dict.fromkeys(
        [0, rows - 1, max(0, rows - 2)] + list(range(rows))))
    m = gf256.parity_rows(data_n, geometry.PARITY_SHARDS_COUNT)
    fds = [os.open(base + geometry.to_ext(s), os.O_RDONLY) for s in range(total_n)]
    t0 = time.perf_counter()
    checked, wrong = [], []
    try:
        with open(dat_path, "rb") as dat:
            for r in order:
                if checked and time.perf_counter() - t0 > ORACLE_BUDGET_S:
                    break
                dat.seek(r * row_bytes)
                buf = dat.read(row_bytes)
                data = np.frombuffer(
                    buf + bytes(row_bytes - len(buf)), dtype=np.uint8
                ).reshape(data_n, block)
                want = np.concatenate([data, gf256.gf_matmul_bytes(m, data)])
                for s in range(total_n):
                    got = os.pread(fds[s], block, r * block)
                    if got != want[s].tobytes():
                        wrong.append((r, s))
                checked.append(r)
    finally:
        for fd in fds:
            os.close(fd)
    checks.check(not wrong, f"shards differ from the numpy oracle at (row, shard) {wrong[:8]}")
    return {
        "rows": rows, "large_block_rows": 0, "rows_checked": len(checked),
        "tail_row_checked": rows - 1 in checked,
        "tail_row_padding_bytes": rows * row_bytes - dat_bytes,
        "seconds": round(time.perf_counter() - t0, 3),
    }


# --- phase B -----------------------------------------------------------------
def phase_b(seed: int, size: dict, checks: Checks) -> dict:
    import numpy as np

    from seaweedfs_tpu.ops import device, hash_service
    from seaweedfs_tpu.stats import default_registry
    from seaweedfs_tpu.storage import crc as crc_mod

    obs: dict = {"phase": "B", "what": "HashService(backend='jax') batches"}
    t_phase = time.perf_counter()
    t0 = time.perf_counter()
    device.jax()
    obs["jax_start_seconds"] = round(time.perf_counter() - t0, 3)
    n = size["blobs"]
    raw = np.random.default_rng([seed, 2]).bytes(n * BLOB_BYTES)
    blobs = [raw[i * BLOB_BYTES:(i + 1) * BLOB_BYTES] for i in range(n)]
    before = kernel_bytes(default_registry().render(), "SeaweedFS_filer_hash")
    compiles0 = device.report()["compiles"]

    svc = hash_service.HashService(backend="jax")
    svc.start()
    results: list = [None] * n
    errors: list[str] = []
    nthreads, group = 8, 512
    halves = []

    def submitter(lo: int, hi: int) -> None:
        try:
            for at in range(lo, hi, group):
                end = min(hi, at + group)
                for j, r in enumerate(svc.submit_many(blobs[at:end])):
                    r.wait(timeout=600)
                    results[at + j] = (r.md5, r.crc)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(f"submitter {lo}-{hi}: {type(e).__name__}: {e}")

    try:
        for half in (0, 1):  # two halves: compilations must not grow with blobs
            h_lo, h_hi = half * n // 2, (half + 1) * n // 2
            step = -(-(h_hi - h_lo) // nthreads)
            threads = [
                threading.Thread(target=submitter, args=(lo, min(h_hi, lo + step)))
                for lo in range(h_lo, h_hi, step)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            c = device.report()["compiles"]
            halves.append({
                "blobs": h_hi - h_lo,
                "seconds": round(time.perf_counter() - t0, 3),
                "compile_requests_so_far": c["requests"] - compiles0["requests"],
                "compile_seconds_so_far": round(c["seconds"] - compiles0["seconds"], 3),
            })
    finally:
        svc.stop()
    checks.check(not errors, f"hash submitters failed: {errors[:3]}")

    wrong_md5 = wrong_crc = 0
    for i, blob in enumerate(blobs):
        got = results[i]
        if got is None or got[0] != hashlib.md5(blob).digest():
            wrong_md5 += 1
        if got is None or got[1] != crc_mod.crc32c(blob):
            wrong_crc += 1
    checks.check(wrong_md5 == 0, f"{wrong_md5} of {n} MD5 digests differ from hashlib")
    checks.check(wrong_crc == 0, f"{wrong_crc} of {n} CRC32C values differ from storage/crc.py")

    after = kernel_bytes(default_registry().render(), "SeaweedFS_filer_hash")
    grew = {k: after[k] - before.get(k, 0.0) for k in after
            if after[k] - before.get(k, 0.0) > 0}
    compiles = device.report()["compiles"]
    requests = compiles["requests"] - compiles0["requests"]
    bound = 2 * len(hash_service._ROW_LADDER) + 4
    checks.check(approx(grew.get("batch-jax", 0.0), n * BLOB_BYTES),
                 f"bytes under batch-jax: {grew.get('batch-jax', 0.0)} != {n * BLOB_BYTES}")
    checks.check(set(grew) == {"batch-jax"},
                 f"hash bytes under other labels than batch-jax: {grew}")
    checks.check(requests <= bound,
                 f"{requests} compile requests for hashing exceed the ladder's bound {bound}")
    obs.update(
        blobs=n, blob_bytes=BLOB_BYTES, halves=halves,
        hash_bytes_by_kernel=grew, compile_requests=requests,
        compile_cache_hits=compiles["cache_hits"] - compiles0["cache_hits"],
        compile_seconds=round(compiles["seconds"] - compiles0["seconds"], 3),
        compile_request_bound=bound,
        compile_cache=device.report()["compile_cache"],
        seconds=round(time.perf_counter() - t_phase, 3),
    )
    return obs


def observe_calibration() -> dict:
    """What pick_pipeline_backend chooses on this machine when left alone,
    with the rate it measured for each candidate. An observation."""
    from seaweedfs_tpu.ops import rs_kernel

    os.environ.pop(EC_ENV, None)
    t0 = time.perf_counter()
    rs_kernel.pick_pipeline_backend()
    return {
        "phase": "calibration", "what": "pick_pipeline_backend left alone",
        **rs_kernel.pipeline_backend_report(),
        "seconds": round(time.perf_counter() - t0, 3),
    }


# --- main ----------------------------------------------------------------------
def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="every payload is made from it")
    p.add_argument("--size", choices=sorted(SIZES), default="real",
                   help="tiny: only to rehearse on the CPU")
    opts = p.parse_args(argv)
    size = SIZES[opts.size]
    sys.path.insert(0, HERE)
    try:
        from seaweedfs_tpu import native
        from seaweedfs_tpu.ops import device
    except ImportError as e:
        log(f"this is not a checkout of the repo: {e}")
        return 2

    outdir = os.path.join(HERE, "chiprun_out", "chip_smoke")
    workdir = os.path.join(HERE, ".chip_smoke_work")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(outdir, exist_ok=True)
    os.makedirs(workdir)
    env = dict(os.environ)
    checks = Checks()
    try:
        # the native library: built on this machine from the sources git holds
        checks.check(native.lib is not None,
                     f"native library not loaded: {native.load_info['error']}")
        cache_path, cache_source = device.cache_dir()
        emit({"phase": "setup", "size": opts.size, "seed": opts.seed,
              "native": {"loaded": native.lib is not None, **native.load_info},
              "compile_cache": {
                  "dir": cache_path, "source": cache_source,
                  "warm": device.cache_entries(cache_path) > 0}})
        seen = probe_device(outdir, env)["jax"]
        log(f"jax sees {seen}")
        if seen["platform"] != "tpu" and opts.size == "real":
            log("no accelerator: the real size runs on a TPU only"
                " (--size tiny rehearses on the CPU)")
            print(result_line(False, seen["platform"], seen["device_kind"],
                              seen["count"]), flush=True)
            return 1

        for name, phase in (
            ("A", lambda: phase_a(opts.seed, size, workdir, outdir, env, checks)),
            ("B", lambda: phase_b(opts.seed, size, checks)),
            ("calibration", observe_calibration),
        ):
            try:
                emit(phase())
            except Exception as e:  # noqa: BLE001 - a phase that raised fails the run
                traceback.print_exc()
                checks.check(False, f"phase {name} raised {type(e).__name__}: {e}")

        # only now, with every child gone, may this process name the device
        device.jax()
        mine = device.report()["jax"]
        checks.check(mine["platform"] == "tpu",
                     f"jax computes on {mine['platform']!r}, not on a tpu")
        if checks.failures:
            emit({"phase": "failures", "failures": checks.failures})
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    sys.stderr.flush()
    print(result_line(not checks.failures, mine["platform"],
                      mine["device_kind"], mine["count"]), flush=True)
    return 0 if not checks.failures else 1


if __name__ == "__main__":
    sys.exit(main())
