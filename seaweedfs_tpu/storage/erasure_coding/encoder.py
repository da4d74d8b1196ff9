"""EC encode/rebuild: .dat -> .ec00–.ec13 (+ .ecx, .vif), and shard recovery.

Produces byte-identical shard files to the reference's
`WriteEcFiles`/`RebuildEcFiles` (`weed/storage/erasure_coding/ec_encoder.go`)
with a redesigned execution pipeline. The reference runs a single-threaded
256KB read -> encode -> write loop (`ec_encoder.go:132-137`); here three
stages overlap:

    reader thread --(bounded queue)--> GF transform --(bounded queue)--> writer thread

* the reader pre-fetches row batches from the .dat into a small ring of
  reusable host buffers (positional preadv straight into the buffer,
  zero-padded past EOF), which a pipeline takes from the process's store
  of idle batch buffers and gives back at its end (`BatchBuffers`), so
  that from a server's second verb on a batch is read into pages that are
  already there; the rebuild's reader fills a batch the same way,
  in place, each surviving shard file's slice into its own row, one file
  after another (a survivor that comes up short is an IOError, never
  padded; ten reads side by side gained nothing, PERF.md §6 PR 31);
* the transform stage submits each batch to the RSCodec pipeline backend —
  on the TPU that is chunked host->HBM puts feeding the Pallas bit-plane
  matmul with async dispatch, on the CPU one GIL-released GFNI/AVX-512
  call — and only PARITY ever crosses back from the device (4/14 of the
  output bytes; data shards are written straight from the read buffer);
* the writer thread blocks on each batch's parity and lays both data and
  parity bytes into the 14 shard files with positional pwrite.

The pipeline backend is chosen by measured end-to-end rate
(ops.rs_kernel.pick_pipeline_backend). The kernel-span label says what
carried the bytes: "fused" (native single pass), or "pipeline-" /
"rebuild-" plus RSCodec.kernel_label ("pallas", "xla", "native", "numpy").
"""

from __future__ import annotations

import contextlib
import json
import os
import queue
import threading
import time

import numpy as np

from seaweedfs_tpu.ops import device
from seaweedfs_tpu.ops.rs_kernel import RSCodec, pick_pipeline_backend
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.storage import idx as idx_mod
from seaweedfs_tpu.storage.types import size_is_valid

from .geometry import (
    DATA_SHARDS_COUNT,
    LARGE_BLOCK_SIZE,
    PARITY_SHARDS_COUNT,
    SMALL_BLOCK_SIZE,
    TOTAL_SHARDS_COUNT,
    shard_file_size,
    to_ext,
)

# Max bytes per shard per pipeline batch (= matmul columns per step),
# per backend. The host path wants the whole (read buffer + parity) working
# set resident in LLC — 1MB/shard = ~14MB touched per step, which measures
# ~75% faster than 16MB batches on a 1-core/260MB-L3 host. The device path
# wants large batches to amortize transfer/dispatch overhead instead.
DEFAULT_BATCH_HOST = 1024 * 1024
DEFAULT_BATCH_DEVICE = 32 * 1024 * 1024


def _default_batch(backend: str) -> int:
    return DEFAULT_BATCH_DEVICE if backend == "jax" else DEFAULT_BATCH_HOST

_QUEUE_DEPTH = 2

# Per-stage pipeline attribution (RapidRAID's lesson — arXiv:1207.6744 —
# is that a pipelined coder lives or dies by per-stage balance): each
# batch contributes a busy observation (doing its stage's work) and a
# wait observation (blocked on the bounded queues / a free buffer slot), so
# /metrics alone answers which stage is the bottleneck and at what
# utilization (busy_sum / (busy_sum + wait_sum)). The write stage's busy
# time includes blocking on the encode handle's parity (device drain):
# that half is SeaweedFS_volume_ec_device_seconds{kernel="d2h-wait"}.
# The fused single-pass engine has no stages; it reports stage="fused".
EC_PIPELINE_SECONDS = "SeaweedFS_volume_ec_pipeline_seconds"

_pipeline_hist_cache = None


def _pipeline_hist():
    global _pipeline_hist_cache
    hist = _pipeline_hist_cache  # GIL-atomic read; registry locks creation
    if hist is None:
        from seaweedfs_tpu.stats.metrics import default_registry

        hist = default_registry().histogram(
            EC_PIPELINE_SECONDS,
            "per-batch busy vs queue-wait seconds per EC pipeline stage",
            ("stage", "state"),
        )
        _pipeline_hist_cache = hist
    return hist


class BatchBuffers:
    """The batch buffers that no pipeline is using, kept from one verb to the
    next: a buffer above glibc's mmap threshold is a fresh anonymous mapping
    whose pages are first touched inside the reader's `preadv` and unmapped
    when it dies, and several pipelines doing that in one address space wait
    for each other in the kernel (PERF.md §6 PR 33, PR 34).

    One store for every caller of `_run_pipeline`, whatever its batch size:
    `take` hands out the largest idle buffer and `_ensure_buf` decides, as it
    always has, whether it holds the batch. What the store holds idle is
    bounded by what the pipelines that can run at once hold while they run
    (`_QUEUE_DEPTH + 2` slots of the device path's batch each, one pipeline
    a local device: `ops.device.pipelines_at_once`); what comes back beyond
    that is dropped, the smallest first. After `IDLE_SECONDS` in which no
    pipeline took or gave a buffer, `expire` (the volume server's pulse)
    lets them all go."""

    IDLE_SECONDS = 60.0

    def __init__(self, clock=time.monotonic) -> None:
        self._lock = threading.Lock()
        self._idle: list[np.ndarray] = []  # ascending by size
        self._clock = clock
        self._touched = 0.0

    @staticmethod
    def bound() -> tuple[int, int]:
        """(slots, bytes) the store may hold idle."""
        slots = (_QUEUE_DEPTH + 2) * device.pipelines_at_once()
        return slots, slots * DEFAULT_BATCH_DEVICE * DATA_SHARDS_COUNT

    def take(self) -> np.ndarray | None:
        with self._lock:
            self._touched = self._clock()
            return self._idle.pop() if self._idle else None

    def give(self, bufs: list[np.ndarray]) -> None:
        """Buffers that nothing can still read or write."""
        slots, nbytes = self.bound()
        with self._lock:
            self._touched = self._clock()
            self._idle = sorted(self._idle + bufs, key=lambda b: b.nbytes)
            while (len(self._idle) > slots
                   or sum(b.nbytes for b in self._idle) > nbytes):
                del self._idle[0]

    def expire(self) -> None:
        with self._lock:
            if self._clock() - self._touched >= self.IDLE_SECONDS:
                self._idle = []

    def report(self) -> dict:
        """`/status` `ec.pipeline_buffers`."""
        with self._lock:
            idle = [b.nbytes for b in self._idle]
        return {"idle": len(idle), "idle_bytes": sum(idle),
                "bound_bytes": self.bound()[1]}


batch_buffers = BatchBuffers()


def _ensure_buf(buf, need: int, cap: int) -> np.ndarray:
    """The slot's buffer where it holds `need` bytes: one this pipeline has
    filled before or one the store kept from an earlier pipeline, its pages
    there already. Else a new one of max(need, cap) bytes, so that slots
    converge on one steady-state size. Either way the caller fills
    `buf[:need]` whole and reads no further: what an earlier batch left
    beyond it never reaches a shard."""
    if isinstance(buf, np.ndarray) and buf.nbytes >= need:
        trace.pipeline_buffers_counter().labels("kept").inc()
        return buf
    trace.pipeline_buffers_counter().labels("fresh").inc()
    return np.empty(max(need, cap), dtype=np.uint8)


def _pread_padded(fd: int, offset: int, size: int, out: np.ndarray) -> None:
    """Zero-copy positional read into out[:size] (preadv straight into the
    numpy buffer), zero-filling past EOF (reference encodeDataOneBatch:166-177
    pads the last batch the same way)."""
    got = os.preadv(fd, [memoryview(out)[:size]], offset)
    if got < size:
        out[got:size] = 0


def _pread_exact(
    fd: int, offset: int, size: int, out: np.ndarray, shard_id: int
) -> None:
    """The same read in place for the rebuild, where a surviving shard that
    ends before offset + size is an error and must never be zero-filled."""
    got = os.preadv(fd, [memoryview(out)[:size]], offset)
    if got != size:
        raise IOError(
            f"ec shard {shard_id} short read at {offset}: {got} != {size}"
        )


def _schedule(total: int, large: int, small: int, batch: int):
    """Yield pipeline work units covering the reference's row layout
    (`ec_encoder.go:198-235`): large rows while more than one full large row
    remains, then small rows (last one zero-padded).

    ("rows", dat_off, shard_off, block, nrows): nrows whole rows read
        contiguously from the .dat.
    ("cols", dat_off, shard_off, block, done, width): a width-column slice
        of one row whose block exceeds the batch budget; data shard c lives
        at dat_off + c*block + done.
    """
    remaining = total
    processed = 0
    shard_off = 0

    def _emit_cols(block: int):
        nonlocal processed, shard_off
        done = 0
        while done < block:
            width = min(batch, block - done)
            yield ("cols", processed, shard_off, block, done, width)
            done += width
        processed += block * DATA_SHARDS_COUNT
        shard_off += block

    large_row = large * DATA_SHARDS_COUNT
    while remaining > large_row:
        if large <= batch:
            nrows_possible = (remaining - 1) // large_row  # full large rows left
            nrows = max(1, min(nrows_possible, batch // large))
            yield ("rows", processed, shard_off, large, nrows)
            processed += nrows * large_row
            shard_off += nrows * large
            remaining -= nrows * large_row
        else:
            yield from _emit_cols(large)
            remaining -= large_row
    small_row = small * DATA_SHARDS_COUNT
    while remaining > 0:
        if small <= batch:
            rows_left = -(-remaining // small_row)  # ceil: last row is padded
            nrows = max(1, min(rows_left, batch // small))
            yield ("rows", processed, shard_off, small, nrows)
            processed += nrows * small_row
            shard_off += nrows * small
            remaining -= nrows * small_row
        else:
            yield from _emit_cols(small)
            remaining -= small_row


class _ShardWriters:
    """14 positional-write fds. Each shard is written under a `.tmp` name,
    pre-sized to the final shard size (file-extending pwrite measures ~20x
    slower than writes into a pre-truncated file on this kernel's tmpfs, and
    the fused mmap path needs the full size mapped up front), and renamed
    into place only in close(). A crashed or aborted encode therefore never
    leaves a full-size shard that looks complete while holding stale bytes —
    only ignorable `.tmp` litter. A pre-existing final shard (re-encode) is
    renamed onto the `.tmp` name first: it was about to be replaced anyway,
    and overwriting its pages in place is far cheaper than allocating fresh
    ones (every byte is rewritten before the rename back). An abort before
    any byte was written (`dirty` still False) renames those originals back;
    a dirty abort deletes the tmps — partially overwritten bytes must never
    reappear under a valid shard name."""

    def __init__(self, base: str, final_size: int, shard_ids=None) -> None:
        self.fds: dict[int, int] = {}
        self.paths: dict[int, str] = {}
        self.tmp_paths: dict[int, str] = {}
        self._recycled: set[int] = set()
        self.final_size = final_size
        self.dirty = False
        try:
            for i in (
                shard_ids if shard_ids is not None else range(TOTAL_SHARDS_COUNT)
            ):
                path = base + to_ext(i)
                self.paths[i] = path
                tmp = path + ".tmp"
                self.tmp_paths[i] = tmp
                # Recycle only a same-size original: its pages are reused in
                # place and a clean abort can restore it bit-for-bit (the
                # ftruncate below is then a no-op). A different-size original
                # stays valid under its real name until close() replaces it.
                try:
                    if os.path.getsize(path) == final_size:
                        os.replace(path, tmp)
                        self._recycled.add(i)
                except OSError:
                    pass
                self.fds[i] = os.open(tmp, os.O_RDWR | os.O_CREAT, 0o644)
                os.ftruncate(self.fds[i], final_size)
        except BaseException:
            self.abort()  # restore any renamed originals, close opened fds
            raise

    def pwrite(self, shard: int, data, offset: int) -> None:
        self.dirty = True
        os.pwrite(self.fds[shard], data, offset)

    def pwritev(self, shard: int, views, offset: int) -> None:
        """Scatter-gather write: one syscall, no host-side concat copy."""
        self.dirty = True
        os.pwritev(self.fds[shard], views, offset)

    def close(self) -> None:
        for i, fd in self.fds.items():
            os.ftruncate(fd, self.final_size)
            os.close(fd)
            os.replace(self.tmp_paths[i], self.paths[i])
        self.fds.clear()

    def abort(self) -> None:
        for fd in self.fds.values():
            os.close(fd)
        self.fds.clear()
        for i, path in self.tmp_paths.items():
            try:
                if not self.dirty and i in self._recycled:
                    os.replace(path, self.paths[i])  # original, untouched
                else:
                    os.unlink(path)
            except OSError:
                pass


def _run_pipeline(jobs, read_job, encode_job, write_job, job_bytes=None,
                  dev: int | None = None) -> None:
    """reader thread -> encode (caller thread) -> writer thread, with
    bounded queues, `_QUEUE_DEPTH + 2` buffer slots that go round between
    writer and reader for backpressure, and a stop flag so a failure in any
    stage unwinds the other two instead of deadlocking on a full/empty
    queue. A slot's buffer comes from `batch_buffers` on the slot's first
    use, and at the pipeline's end every buffer that nothing can still read
    or write goes back there: one the writer returned after
    `handle.result()` (the puts and the kernel that read it are done), or one
    that was read and never enqueued. A buffer whose batch was enqueued and
    not drained when a stage failed is dropped, because an asynchronous
    `device_put` may still be reading it. Every batch feeds the per-stage
    busy/wait histograms (EC_PIPELINE_SECONDS above) and, where the caller
    runs under a span, leaves one ring span per stage as that span's child
    (`ec.pipeline.read`, `.encode`, `.write`; attrs `batch`, `thread`, with
    `job_bytes(job)` `bytes`, and with `dev`, the index of the device the
    pipeline borrowed, `device`), whichever thread did the work."""
    parent = trace.current()  # worker threads carry no context of their own
    batches = {"read": 0, "encode": 0, "write": 0}  # each its own thread's

    def staged(stage: str, fn, job, *args):
        if parent is None:
            return fn(job, *args)
        attrs = {"batch": batches[stage],
                 "thread": threading.current_thread().name}
        if dev is not None:
            attrs["device"] = dev
        batches[stage] += 1
        if job_bytes is not None:
            attrs["bytes"] = job_bytes(job)
        with trace.span("ec.pipeline." + stage, role="volume", parent=parent,
                        **attrs):
            return fn(job, *args)

    read_q: queue.Queue = queue.Queue(maxsize=_QUEUE_DEPTH)
    write_q: queue.Queue = queue.Queue(maxsize=_QUEUE_DEPTH)
    free: queue.Queue = queue.Queue()
    for _ in range(_QUEUE_DEPTH + 2):
        free.put(None)  # buffer slots: None until the reader first uses one
    stop = threading.Event()
    errors: list[BaseException] = []
    hist = _pipeline_hist()
    perf = time.perf_counter

    def _put(q: queue.Queue, item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def reader():
        o_wait = hist.labels("read", "wait")
        o_busy = hist.labels("read", "busy")
        try:
            for job in jobs:
                if stop.is_set():
                    return
                t0 = perf()
                slot = free.get()
                if stop.is_set():
                    free.put(slot)
                    return
                if slot is None:
                    slot = batch_buffers.take()
                t1 = perf()
                buf = staged("read", read_job, job, slot)
                t2 = perf()
                ok = _put(read_q, (job, buf))
                o_wait.observe((t1 - t0) + (perf() - t2))
                o_busy.observe(t2 - t1)
                if not ok:
                    free.put(buf)  # read, never enqueued
                    return
        except BaseException as e:  # noqa: BLE001 - propagated below
            errors.append(e)
            stop.set()
        finally:
            _put(read_q, None) or read_q.put(None)

    def writer():
        o_wait = hist.labels("write", "wait")
        o_busy = hist.labels("write", "busy")
        try:
            while True:
                t0 = perf()
                item = write_q.get()
                t1 = perf()
                if item is None:
                    return
                job, buf, handle = item
                staged("write", write_job, job, buf, handle)
                o_wait.observe(t1 - t0)
                o_busy.observe(perf() - t1)
                free.put(buf)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)
            stop.set()
            # drain so reader/encode never block; these batches were
            # enqueued and are not drained: their buffers are dropped, and
            # an empty slot each wakes a reader waiting for one
            while True:
                item = write_q.get()
                if item is None:
                    return
                free.put(None)

    rt = threading.Thread(target=reader, name="ec-reader", daemon=True)
    wt = threading.Thread(target=writer, name="ec-writer", daemon=True)
    rt.start()
    wt.start()
    o_wait = hist.labels("encode", "wait")
    o_busy = hist.labels("encode", "busy")
    try:
        while True:
            t0 = perf()
            item = read_q.get()
            t1 = perf()
            if item is None:
                break
            job, buf = item
            handle = staged("encode", encode_job, job, buf)
            t2 = perf()
            write_q.put((job, buf, handle))
            o_wait.observe((t1 - t0) + (perf() - t2))
            o_busy.observe(t2 - t1)
    except BaseException as e:  # noqa: BLE001 - e.g. device error mid-encode
        errors.append(e)
        stop.set()
        while True:  # unwedge the reader, then stop consuming
            item = read_q.get()
            if item is None:
                break
            free.put(item[1])  # read, never enqueued
    finally:
        write_q.put(None)
        rt.join()
        wt.join()
        idle = []
        while not free.empty():
            buf = free.get()
            if buf is not None:
                idle.append(buf)
        batch_buffers.give(idle)
    if errors:
        raise errors[0]


def _write_ec_files_fused(
    base_file_name: str, large_block_size: int, small_block_size: int
) -> bool:
    """Single-pass fused encode (sw_ec_encode_volume): the .dat is mmap'd
    (MAP_POPULATE), every 64B line flows dat -> registers -> NT-store into
    the mmap'd shard files while GFNI accumulates parity — no pread/pwrite
    page-cache copies at all. On a single-core host this is ~2.5x the
    staged pipeline, whose three stages serialize on the one CPU. Returns
    False when this host/geometry can't run it (caller uses the pipeline)."""
    try:
        from seaweedfs_tpu.native import lib
    except Exception:  # pragma: no cover - import-gated
        return False
    if lib is None or not hasattr(lib, "ec_encode_volume"):
        return False
    if (
        large_block_size % 64
        or small_block_size % 64
        or small_block_size <= 0
        or large_block_size <= 0
    ):
        return False
    from seaweedfs_tpu.ops import gf256

    dat_path = base_file_name + ".dat"
    total = os.path.getsize(dat_path)
    if total == 0:
        return False
    shard_size = shard_file_size(total, large_block_size, small_block_size)
    matrix = gf256.parity_rows(DATA_SHARDS_COUNT, PARITY_SHARDS_COUNT)
    writers = _ShardWriters(base_file_name, shard_size)
    try:
        dat_fd = os.open(dat_path, os.O_RDONLY)
        try:
            rc = lib.ec_encode_volume(
                matrix.tobytes(),
                PARITY_SHARDS_COUNT,
                DATA_SHARDS_COUNT,
                dat_fd,
                total,
                [writers.fds[i] for i in range(TOTAL_SHARDS_COUNT)],
                shard_size,
                large_block_size,
                small_block_size,
            )
        finally:
            os.close(dat_fd)
        # -1..-4 fail before any store; only 0/-5 may have touched bytes
        writers.dirty = writers.dirty or rc in (0, -5)
    except BaseException:
        writers.dirty = True  # unknown state: never restore over it
        writers.abort()
        raise
    if rc != 0:
        writers.abort()  # no GFNI / mmap failed: pipeline will recreate
        return False
    writers.close()
    return True


@contextlib.contextmanager
def _pipeline_codec(codec: RSCodec | None, backend: str):
    """(codec, index of the device it puts its batches on or None) for the
    length of one pipeline. A codec the caller handed in is used as given.
    One built here for the jax backend borrows a local device for as long as
    the pipeline runs (`ops.device.lease`): one pipeline a device, as many
    at once as the process has devices, the next one waiting."""
    if codec is not None:
        yield codec, None
    elif backend != "jax":
        yield RSCodec(backend=backend), None
    else:
        with device.lease() as (index, dev):
            yield RSCodec(backend=backend, device=dev), index


def _device_attr(dev: int | None) -> dict:
    return {} if dev is None else {"device": dev}


def write_ec_files(
    base_file_name: str,
    codec: RSCodec | None = None,
    large_block_size: int = LARGE_BLOCK_SIZE,
    small_block_size: int = SMALL_BLOCK_SIZE,
    batch: int | None = None,
) -> None:
    """Generate .ec00–.ec13 from .dat (`ec_encoder.go:57,198-235`),
    via the fused native single-pass kernel when the host supports it,
    else the 3-stage pipeline (see module docstring). Both paths run under
    a kernel-timing span feeding SeaweedFS_volume_ec_encode_seconds (+ the
    bytes counter), so /metrics alone yields encode GB/s."""
    dat_path = base_file_name + ".dat"
    total = os.path.getsize(dat_path)
    backend = codec.backend if codec else pick_pipeline_backend()
    if backend == "native":
        with trace.kernel_span(
            "ec.encode", trace.EC_ENCODE_SECONDS, "fused", nbytes=total
        ) as sp:
            t0 = time.perf_counter()
            fused_ok = _write_ec_files_fused(
                base_file_name, large_block_size, small_block_size
            )
            if fused_ok:
                # single-pass engine: no read/encode/write stages exist,
                # but the family must still account for the bytes' time
                _pipeline_hist().labels("fused", "busy").observe(
                    time.perf_counter() - t0
                )
            if not fused_ok:
                # host can't run it: the pipeline span below carries
                # the bytes, and the probe must not count as a fused
                # execution in the histogram
                sp.attrs["bytes"] = 0
                sp.attrs["kernel"] = "fused-unavailable"
        if fused_ok:
            return
    with _pipeline_codec(codec, backend) as (codec, dev):
        if batch is None:
            batch = _default_batch(codec.backend)
        with trace.kernel_span(
            "ec.encode", trace.EC_ENCODE_SECONDS,
            "pipeline-" + codec.kernel_label, nbytes=total,
            **_device_attr(dev),
        ):
            _write_ec_files_pipeline(
                base_file_name, codec, large_block_size, small_block_size,
                batch, total, dev,
            )


def _write_ec_files_pipeline(
    base_file_name: str,
    codec: RSCodec,
    large_block_size: int,
    small_block_size: int,
    batch: int,
    total: int,
    dev: int | None = None,
) -> None:
    dat_path = base_file_name + ".dat"
    shard_size = shard_file_size(total, large_block_size, small_block_size)
    writers = _ShardWriters(base_file_name, shard_size)
    try:
        dat_fd = os.open(dat_path, os.O_RDONLY)
    except BaseException:
        writers.abort()
        raise
    try:
        jobs = _schedule(total, large_block_size, small_block_size, batch)

        def read_job(job, buf):
            if job[0] == "rows":
                _, dat_off, _, block, nrows = job
                need = nrows * block * DATA_SHARDS_COUNT
                buf = _ensure_buf(buf, need, batch * DATA_SHARDS_COUNT)
                _pread_padded(dat_fd, dat_off, need, buf)
                return buf
            _, dat_off, _, block, done, width = job
            need = width * DATA_SHARDS_COUNT
            buf = _ensure_buf(buf, need, batch * DATA_SHARDS_COUNT)
            view = buf[:need].reshape(DATA_SHARDS_COUNT, width)
            for c in range(DATA_SHARDS_COUNT):
                _pread_padded(dat_fd, dat_off + c * block + done, width, view[c])
            return buf

        def encode_job(job, buf):
            if job[0] == "rows":
                _, _, _, block, nrows = job
                need = nrows * block * DATA_SHARDS_COUNT
                return codec.encode_rows_async(buf[:need], block, nrows)
            _, _, _, block, done, width = job
            need = width * DATA_SHARDS_COUNT
            return codec.encode2d_async(
                buf[:need].reshape(DATA_SHARDS_COUNT, width)
            )

        def write_job(job, buf, handle):
            # the two halves of the write stage's busy time: the wait for
            # the device (kernel drain and D2H, counted as `d2h-wait` where
            # the handle is the device's) and the shard writes
            with trace.phase("ec.pipeline.write.drain"):
                parity = handle.result()
            with trace.phase("ec.pipeline.write.pwrite"):
                if job[0] == "rows":
                    _, _, shard_off, block, nrows = job
                    span = nrows * block
                    for p in range(PARITY_SHARDS_COUNT):
                        writers.pwrite(
                            DATA_SHARDS_COUNT + p, parity[p, :span], shard_off
                        )
                    view = buf[: span * DATA_SHARDS_COUNT].reshape(
                        nrows, DATA_SHARDS_COUNT, block
                    )
                    for c in range(DATA_SHARDS_COUNT):
                        if nrows == 1:
                            writers.pwrite(c, view[0, c], shard_off)
                        else:
                            writers.pwritev(
                                c,
                                [view[r, c] for r in range(nrows)],
                                shard_off,
                            )
                else:
                    _, _, shard_off, block, done, width = job
                    view = buf[: width * DATA_SHARDS_COUNT].reshape(
                        DATA_SHARDS_COUNT, width
                    )
                    for c in range(DATA_SHARDS_COUNT):
                        writers.pwrite(c, view[c], shard_off + done)
                    for p in range(PARITY_SHARDS_COUNT):
                        writers.pwrite(
                            DATA_SHARDS_COUNT + p, parity[p, :width],
                            shard_off + done,
                        )

        def job_bytes(job) -> int:
            per_shard = job[3] * job[4] if job[0] == "rows" else job[5]
            return per_shard * DATA_SHARDS_COUNT

        _run_pipeline(jobs, read_job, encode_job, write_job, job_bytes, dev)
    except BaseException:
        writers.abort()
        raise
    else:
        writers.close()
    finally:
        os.close(dat_fd)


def rebuild_ec_files(
    base_file_name: str,
    codec: RSCodec | None = None,
    chunk: int | None = None,
) -> list[int]:
    """Regenerate missing .ecXX files from the surviving >= 10
    (`ec_encoder.go:61,237-291`), through the same three-stage pipeline —
    the GF transform is the inverted-submatrix product on the pipeline
    backend (BASELINE config 2). Returns the rebuilt shard ids."""
    backend = codec.backend if codec else pick_pipeline_backend()
    with _pipeline_codec(codec, backend) as (codec, dev):
        with trace.kernel_span(
            "ec.rebuild", trace.EC_DECODE_SECONDS,
            "rebuild-" + codec.kernel_label, **_device_attr(dev),
        ) as sp:
            return _rebuild_ec_files(base_file_name, codec, chunk, sp, dev)


def _rebuild_ec_files(
    base_file_name: str,
    codec: RSCodec,
    chunk: int | None,
    sp,
    dev: int | None = None,
) -> list[int]:
    from seaweedfs_tpu.ops import gf256

    if chunk is None:
        chunk = _default_batch(codec.backend)
    present_fds: dict[int, int] = {}
    missing: list[int] = []
    try:
        for shard_id in range(TOTAL_SHARDS_COUNT):
            name = base_file_name + to_ext(shard_id)
            if os.path.exists(name):
                present_fds[shard_id] = os.open(name, os.O_RDONLY)
            else:
                missing.append(shard_id)
        if not missing:
            return []
        if len(present_fds) < DATA_SHARDS_COUNT:
            raise ValueError(
                f"cannot rebuild: only {len(present_fds)} shards present"
            )
        present = sorted(present_fds)
        use = present[:DATA_SHARDS_COUNT]
        matrix = gf256.decode_matrix(
            codec.data_shards,
            codec.parity_shards,
            tuple(present),
            tuple(missing),
        )
        shard_size = os.path.getsize(base_file_name + to_ext(use[0]))
        # throughput convention: bytes read from the surviving data shards
        sp.attrs["bytes"] = shard_size * DATA_SHARDS_COUNT
        writers = _ShardWriters(
            base_file_name, shard_size, shard_ids=missing
        )
        # The fused mmap path reads every surviving shard at shard_size; a
        # truncated survivor would SIGBUS past its last page instead of
        # raising, so require exact sizes (mismatch falls through to the
        # pread pipeline, which reports the short read as an IOError).
        sizes_ok = all(
            os.fstat(present_fds[sid]).st_size == shard_size for sid in use
        )
        if codec.backend == "native" and shard_size > 0 and sizes_ok:
            # fused fd-mmap matmul: surviving shards are read straight from
            # the page cache (no pread copies) into the GFNI reconstruct
            try:
                from seaweedfs_tpu.native import lib
            except Exception:  # pragma: no cover - import-gated
                lib = None
            if lib is not None and hasattr(lib, "gf256_matmul_fds"):
                t0 = time.perf_counter()
                try:
                    rc = lib.gf256_matmul_fds(
                        matrix.tobytes(),
                        len(missing),
                        codec.data_shards,
                        [present_fds[sid] for sid in use],
                        shard_size,
                        [writers.fds[sid] for sid in missing],
                    )
                except BaseException:
                    writers.dirty = True
                    writers.abort()
                    raise
                if rc == 0:
                    _pipeline_hist().labels("fused", "busy").observe(
                        time.perf_counter() - t0
                    )
                    writers.dirty = True
                    writers.close()
                    return missing
        try:
            jobs = [
                (off, min(chunk, shard_size - off))
                for off in range(0, shard_size, chunk)
            ]

            def read_job(job, buf):
                off, width = job
                need = width * DATA_SHARDS_COUNT
                buf = _ensure_buf(buf, need, chunk * DATA_SHARDS_COUNT)
                view = buf[:need].reshape(DATA_SHARDS_COUNT, width)
                for i, sid in enumerate(use):
                    _pread_exact(present_fds[sid], off, width, view[i], sid)
                return buf

            def encode_job(job, buf):
                _, width = job
                need = width * DATA_SHARDS_COUNT
                return codec.apply2d_async(
                    matrix, buf[:need].reshape(DATA_SHARDS_COUNT, width)
                )

            def write_job(job, buf, handle):
                off, width = job
                with trace.phase("ec.pipeline.write.drain"):
                    out = handle.result()
                with trace.phase("ec.pipeline.write.pwrite"):
                    for i, sid in enumerate(missing):
                        writers.pwrite(sid, out[i, :width], off)

            _run_pipeline(
                jobs, read_job, encode_job, write_job,
                lambda job: job[1] * DATA_SHARDS_COUNT, dev,
            )
        except BaseException:
            writers.abort()
            raise
        else:
            writers.close()
    finally:
        for fd in present_fds.values():
            os.close(fd)
    return missing


def write_sorted_file_from_idx(base_file_name: str, ext: str = ".ecx") -> None:
    """Generate the sorted .ecx from the .idx — latest entry per key, keys
    ascending, deleted/zero entries dropped (`ec_encoder.go:27-55`)."""
    latest: dict[int, tuple[int, int]] = {}
    for key, offset, size in idx_mod.walk_index_file(base_file_name + ".idx"):
        if offset != 0 and size_is_valid(size):
            latest[key] = (offset, size)
        else:
            latest.pop(key, None)
    with open(base_file_name + ext, "wb") as f:
        for key in sorted(latest):
            offset, size = latest[key]
            f.write(idx_mod.entry_to_bytes(key, offset, size))


def save_volume_info(path: str, version: int = 3, **extra) -> None:
    """.vif — volume info JSON (`weed/storage/volume_info/volume_info.go`,
    protojson of VolumeInfo)."""
    info = {"version": version}
    info.update(extra)
    with open(path, "w") as f:
        json.dump(info, f, indent=2)


def load_volume_info(path: str) -> dict:
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return json.load(f)
