"""Online (write-path) erasure coding: stream-encode on ingest.

Classic EC here (and in the reference) happens when a volume seals:
until then durability costs a full 2x replica fan-out, and the seal pays
a second full read+encode of everything ever written — the
replica->EC double-storage window arXiv:1709.05365 measures on SSD
arrays. That study's conclusion (online EC is viable whenever the
encoder keeps up with ingest) is the premise here: the fused GFNI host
path has to encode faster than a single volume's ingest. RapidRAID (arXiv:1207.6744) supplies the shape:
pipeline the coding work so it overlaps the stream instead of trailing
it.

`OnlineEcWriter` fronts one live Volume:

  * needle appends land in the .dat exactly as before (Python path or
    the fastlane engine — both only ever append);
  * the writer keeps a stripe-aligned watermark. Once a full stripe row
    (DATA_SHARDS x block bytes of .dat) exists past it, the row streams
    read -> encode -> write through the RS codec and ONLY PARITY is
    written out, appended to the open .ec10-.ec13 shard files at the
    row's shard offset. Data shards are pure byte-rearrangements of the
    .dat (geometry.locate_data), so they are never materialized during
    ingest — the .dat IS the data shards. Write amplification:
    1.0 (dat) + parity/data (0.4 for RS(10,4)) = 1.4x, vs 2.0x for
    replication — and no double-storage window at all;
  * a fixed-record journal (`.ecp`) persists the watermark after every
    parity write, so a crash replays cleanly: re-encode from the last
    durable watermark (idempotent — parity bytes are a pure function of
    .dat bytes at fixed offsets, so nothing is lost or double-encoded);
  * trickle writes age out to a timed flush: a partially-filled row is
    encoded zero-padded so parity durability never waits on a full
    stripe; the row is simply re-encoded as it fills (counted under
    the `trickle_flush` fallback reason — visible, not pathological);
  * when the encoder cannot keep up (the un-encoded backlog exceeds
    `max_lag_stripes`), the writer deactivates itself — the volume
    falls back to classic replicate-then-seal-EC automatically, and the
    `backpressure` fallback counter makes the regime visible;
  * seal() finishes the tail row and materializes .ec00-.ec09 with a
    straight sequential copy from the .dat — the seal path never
    re-runs the GF math online ingest already paid for.

Online volumes use a UNIFORM stripe geometry (large == small == block):
for .dat sizes under a large row the classic layout already degenerates
to uniform small rows, and a streaming encoder cannot buffer 10GB
waiting for a 1GB-block row to fill. The block size is recorded in the
volume's `.vif` (`ec_online.block_size` + the `large_block_size` /
`small_block_size` keys EcVolume and the decode path read back), so
sealed shards read identically to offline-encoded ones.
"""

from __future__ import annotations

import ctypes
import mmap
import os
import struct
import threading
import time

import numpy as np

from seaweedfs_tpu.ops.rs_kernel import RSCodec
from seaweedfs_tpu.storage import crc as crc_mod
from seaweedfs_tpu.util import faults

from . import encoder as encoder_mod
from .geometry import (
    DATA_SHARDS_COUNT,
    PARITY_SHARDS_COUNT,
    SMALL_BLOCK_SIZE,
    TOTAL_SHARDS_COUNT,
    shard_file_size,
    to_ext,
)

# fallback/degrade reasons — they ride into the `reason` label of
# SeaweedFS_volume_ec_online_fallbacks_total and are linted by
# tools/check_metric_names.py like the front-door reason set.
FALLBACK_REASONS = (
    "backpressure",     # un-encoded backlog exceeded max_lag_stripes
    "encoder_error",    # the codec/parity write raised
    "trickle_flush",    # timed flush of a partial row (expected for
                        # trickle traffic; the row re-encodes as it fills)
    "journal_io",       # .ecp journal unwritable
    "vacuum_reset",     # compaction rewrote the .dat; parity restarted
    "parity_rearm",     # lost/torn parity shard: restarted + re-encoded
                        # from the durable .dat (the heal, not the fault)
)
# reasons that mean online EC is BROKEN for the volume (bench asserts
# zero of these in steady state); trickle_flush, vacuum_reset and
# parity_rearm are expected operation
PATHOLOGICAL_REASONS = ("backpressure", "encoder_error", "journal_io")

# parity-emit fault seam: `torn` tears the parity file tail (the state a
# crash mid-append leaves); error/disk_full surface as encoder_error
# degrades — exactly what the maintenance rearm path must heal
_FP_PARITY = faults.register("volume.ec.parity.write")

# .ecp journal: fixed 24-byte records, last valid record wins.
# magic u32 | watermark u64 | partial u64 | crc32c u32 (over bytes 0..19)
_JOURNAL_MAGIC = 0x53574550  # "SWEP"
_JOURNAL_REC = struct.Struct("<IQQI")

_DEFAULT_BLOCK = SMALL_BLOCK_SIZE

_metrics_cache = None


def ensure_metrics(registry=None):
    """Register (idempotently) the ec_online families; returns the tuple
    (stripes_total, encode_seconds, bytes_total, buffered_bytes,
    journal_replays_total, fallbacks_total)."""
    global _metrics_cache
    if registry is None and _metrics_cache is not None:
        return _metrics_cache
    from seaweedfs_tpu.stats.metrics import default_registry

    reg = registry if registry is not None else default_registry()
    out = (
        reg.counter(
            "SeaweedFS_volume_ec_online_stripes_total",
            "stripe rows parity-encoded on the ingest path",
            ("volume",),
        ),
        reg.histogram(
            "SeaweedFS_volume_ec_online_encode_seconds",
            "per-batch read+encode+parity-write seconds on the ingest path",
            ("volume",),
        ),
        reg.counter(
            "SeaweedFS_volume_ec_online_bytes_total",
            ".dat bytes parity-encoded online (GB/s = bytes/sum(seconds))",
            ("volume",),
        ),
        reg.gauge(
            "SeaweedFS_volume_ec_online_buffered_bytes",
            "ingested bytes not yet covered by a durable parity watermark",
            ("volume",),
        ),
        reg.counter(
            "SeaweedFS_volume_ec_online_journal_replays_total",
            "partial-stripe journal replays (re-encode from the watermark)",
            ("volume",),
        ),
        reg.counter(
            "SeaweedFS_volume_ec_online_fallbacks_total",
            "online-EC degrade events by reason",
            ("volume", "reason"),
        ),
    )
    if registry is None:
        _metrics_cache = out
    return out


class OnlineEcWriter:
    """Streams one live Volume's appends through the RS encoder,
    emitting parity shards incrementally. See module docstring."""

    def __init__(
        self,
        volume,
        block_size: int | None = None,
        codec: RSCodec | None = None,
        flush_age: float = 2.0,
        max_lag_stripes: int = 256,
    ) -> None:
        self.volume = volume
        info = encoder_mod.load_volume_info(volume.base_name + ".vif")
        oe = dict(info.get("ec_online") or {})
        self.block = int(block_size or oe.get("block_size") or _DEFAULT_BLOCK)
        self.stripe = self.block * DATA_SHARDS_COUNT
        # native/numpy only: a host->device round trip must never sit on
        # the ack path of a live write (pick_pipeline_backend may choose
        # jax for the offline verb, where latency is free)
        self.codec = codec or RSCodec(
            backend="native" if _native_ok() else "numpy"
        )
        self.flush_age = flush_age
        self.max_lag_stripes = max_lag_stripes
        self.active = True
        self.sealed = False
        self.fallback_reason: str | None = None
        self._lock = threading.Lock()
        self._matrix = None  # parity rows, built lazily
        # stats mirrored into the registry families (ensure_metrics) but
        # also kept raw for bench/tests
        self.stripes = 0
        self.encoded_bytes = 0
        self.encode_seconds = 0.0
        self.parity_bytes = 0
        self.journal_replays = 0
        self.fallbacks: dict[str, int] = {}
        # reused stripe read buffer: a fresh bytes per pread would pay
        # this microVM's free-page first-touch cost (~0.15 GB/s) on every
        # batch — the same reason the offline pipeline keeps its batch
        # buffers from one pipeline to the next (encoder.BatchBuffers)
        self._buf: np.ndarray | None = None
        self._parity_rows_sized = 0  # rows the parity fds are truncated to
        # zero-copy fast path (the fused-engine idea applied per stripe):
        # the .dat is mmap'd read-only and the parity files mmap'd shared,
        # and sw_gf256_matmul runs GFNI straight from the .dat's page-cache
        # pages into the parity files' — no pread/pwrite/bounce buffers.
        # Any failure (no native lib, odd backend, mmap error) drops to the
        # buffered pread/pwrite path for that span.
        self._dat_mm = None
        self._dat_mm_arr = None
        self._dat_mm_size = 0
        self._parity_mm: list = [None] * PARITY_SHARDS_COUNT
        self._parity_mm_arr: list = [None] * PARITY_SHARDS_COUNT
        # one helper thread splits each row's byte columns in half: the
        # GF kernel releases the GIL, so two cores run the same stripe
        # concurrently (~2.1 GB/s cold / ~3.3 GB/s on recycled pages vs
        # ~1.65 single-threaded on this 2-core host). Lazy: trickle-only
        # volumes never pay for a thread. Whether the split WINS depends
        # on how much CPU the hypervisor actually grants (this box's
        # capacity swings), so like the encode-backend autotuner the
        # choice is measured, not assumed: early spans alternate
        # threaded/serial and the faster per-byte mode locks in.
        self._pool = None
        self._split_mode: bool | None = None  # None = still probing
        self._split_probe = [0.0, 0.0, 0, 0]  # [t_serial, t_thr, n_s, n_t]
        (self._m_stripes, self._m_seconds, self._m_bytes, self._m_buffered,
         self._m_replays, self._m_fallbacks) = ensure_metrics()
        self._vol_label = str(volume.id)

        if oe.get("block_size") != self.block:
            oe["block_size"] = self.block
            _merge_vif(volume.base_name + ".vif", {"ec_online": oe},
                       version=volume.version())

        # open parity shards (grown incrementally, readable while open)
        self._parity_fds: list[int] = []
        try:
            for p in range(PARITY_SHARDS_COUNT):
                path = volume.base_name + to_ext(DATA_SHARDS_COUNT + p)
                self._parity_fds.append(
                    os.open(path, os.O_RDWR | os.O_CREAT, 0o644)
                )
        except OSError:
            for fd in self._parity_fds:
                os.close(fd)
            raise
        # re-attach: never shrink below what's already on disk (all of it
        # is at or ahead of the replayed watermark)
        self._parity_rows_sized = min(
            os.fstat(fd).st_size for fd in self._parity_fds
        ) // self.block

        # journal replay: resume from the last durable watermark; any
        # .dat bytes past it (a crash between parity write and journal
        # append, or appends the previous process never encoded) are
        # simply re-encoded — parity is a pure function of .dat bytes
        self._journal_path = volume.base_name + ".ecp"
        self.watermark, self._partial = self._load_journal()
        self._journal_fd = os.open(
            self._journal_path, os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644
        )
        self._pending_since: float | None = None
        behind = self._end() - self.watermark
        if behind > 0 and self._journal_existed:
            self.journal_replays += 1
            self._m_replays.labels(self._vol_label).inc()
            self.pump(force=self._partial > 0)

    # --- journal ------------------------------------------------------------
    def _load_journal(self) -> tuple[int, int]:
        self._journal_existed = os.path.exists(self._journal_path)
        watermark, partial = 0, 0
        if not self._journal_existed:
            return 0, 0
        try:
            with open(self._journal_path, "rb") as f:
                blob = f.read()
        except OSError:
            return 0, 0
        n = len(blob) // _JOURNAL_REC.size
        for i in range(n):
            rec = blob[i * _JOURNAL_REC.size:(i + 1) * _JOURNAL_REC.size]
            magic, wm, part, crc = _JOURNAL_REC.unpack(rec)
            if magic != _JOURNAL_MAGIC:
                continue
            if crc_mod.crc32c(rec[:20]) != crc:
                continue  # torn record (crash mid-append): skip
            watermark, partial = wm, part
        return watermark, partial

    def _journal_append(self) -> None:
        body = _JOURNAL_REC.pack(
            _JOURNAL_MAGIC, self.watermark, self._partial, 0
        )[:20]
        rec = body + struct.pack("<I", crc_mod.crc32c(body))
        try:
            os.write(self._journal_fd, rec)
        except OSError:
            self._degrade("journal_io")

    # --- helpers ------------------------------------------------------------
    def _end(self) -> int:
        return self.volume.size()

    def _read_dat(self, offset: int, size: int) -> bytes:
        data = self.volume._dat.read_at(size, offset)
        if len(data) < size:
            data = data + b"\0" * (size - len(data))
        return data

    def _read_dat_into(self, offset: int, size: int, out: np.ndarray) -> None:
        """Positional read into a reused buffer (zero-fill past EOF), the
        encoder._pread_padded idiom — no fresh allocation per batch."""
        fd = getattr(self.volume._dat, "_fd", None)
        if fd is None:  # mmap/remote backend: plain read + copy
            data = self.volume._dat.read_at(size, offset)
            got = len(data)
            out[:got] = np.frombuffer(data, dtype=np.uint8)
        else:
            got = os.preadv(fd, [memoryview(out)[:size]], offset)
        if got < size:
            out[got:size] = 0

    def _size_parity(self, rows_needed: int) -> None:
        """Pre-truncate the parity fds ahead of the write watermark:
        file-extending pwrite measures ~20x slower than writes into a
        pre-sized file on this kernel (the _ShardWriters lesson)."""
        if rows_needed <= self._parity_rows_sized:
            return
        grow_to = max(rows_needed, self._parity_rows_sized + 64)
        for fd in self._parity_fds:
            os.ftruncate(fd, grow_to * self.block)
        self._parity_rows_sized = grow_to
        self._drop_parity_maps()  # stale length: remapped on demand

    # --- zero-copy mmap fast path --------------------------------------------
    def _drop_maps(self) -> None:
        self._dat_mm_arr = None
        if self._dat_mm is not None:
            self._dat_mm.close()
            self._dat_mm = None
        self._dat_mm_size = 0
        self._drop_parity_maps()

    def _drop_parity_maps(self) -> None:
        for i, mm in enumerate(self._parity_mm):
            self._parity_mm_arr[i] = None
            if mm is not None:
                mm.close()
        self._parity_mm = [None] * PARITY_SHARDS_COUNT

    def _dat_addr(self, need_end: int) -> int | None:
        """Base address of a read-only .dat mapping covering
        [0, need_end), remapped as the file grows; None when unmappable."""
        if self._dat_mm is not None and need_end <= self._dat_mm_size:
            return self._dat_mm_arr.ctypes.data
        fd = getattr(self.volume._dat, "_fd", None)
        if fd is None:
            return None
        size = os.fstat(fd).st_size
        if size < need_end:
            return None
        self._dat_mm_arr = None
        if self._dat_mm is not None:
            self._dat_mm.close()
            self._dat_mm = None
        try:
            self._dat_mm = mmap.mmap(fd, size, prot=mmap.PROT_READ)
        except (OSError, ValueError):
            return None
        self._dat_mm_size = size
        self._dat_mm_arr = np.frombuffer(self._dat_mm, dtype=np.uint8)
        return self._dat_mm_arr.ctypes.data

    def _parity_addr(self, p: int) -> int | None:
        """Base address of a shared writable mapping of parity shard p
        (sized to the pre-truncated length)."""
        if self._parity_mm[p] is not None:
            return self._parity_mm_arr[p].ctypes.data
        length = self._parity_rows_sized * self.block
        if length <= 0:
            return None
        try:
            self._parity_mm[p] = mmap.mmap(self._parity_fds[p], length)
        except (OSError, ValueError):
            return None
        self._parity_mm_arr[p] = np.frombuffer(
            self._parity_mm[p], dtype=np.uint8
        )
        return self._parity_mm_arr[p].ctypes.data

    def _encode_rows_mmap(self, offset: int, nrows: int) -> bool:
        """GFNI straight from mapped .dat pages into mapped parity pages
        (sw_gf256_matmul with per-shard pointers) — the pread/pwrite
        copies and their fresh-page first-touch cost disappear. Returns
        False when the fast path is unavailable for this span."""
        if self.codec.backend != "native":
            return False
        try:
            from seaweedfs_tpu.native import lib
        except Exception:  # pragma: no cover - import-gated
            return False
        if lib is None:
            return False
        dat_base = self._dat_addr(offset + nrows * self.stripe)
        if dat_base is None:
            return False
        self._size_parity(offset // self.stripe + nrows)
        parity_bases = [self._parity_addr(p)
                        for p in range(PARITY_SHARDS_COUNT)]
        if any(b is None for b in parity_bases):
            return False
        if self._matrix is None:
            from seaweedfs_tpu.ops import gf256

            self._matrix = gf256.parity_rows(
                DATA_SHARDS_COUNT, PARITY_SHARDS_COUNT
            ).tobytes()
        raw = lib._lib
        cast, vp, cp = ctypes.cast, ctypes.c_void_p, ctypes.c_char_p
        row0 = offset // self.stripe

        def span(dat_off: int, out_off: int, col0: int, width: int) -> None:
            ins = (cp * DATA_SHARDS_COUNT)(*[
                cast(vp(dat_base + dat_off + c * self.block + col0), cp)
                for c in range(DATA_SHARDS_COUNT)
            ])
            outs = (cp * PARITY_SHARDS_COUNT)(*[
                cast(vp(parity_bases[p] + out_off + col0), cp)
                for p in range(PARITY_SHARDS_COUNT)
            ])
            raw.sw_gf256_matmul(
                self._matrix, PARITY_SHARDS_COUNT, DATA_SHARDS_COUNT,
                ins, outs, width,
            )

        # split each row's byte columns across two cores (the transform
        # is independent per column); 64B-aligned halves keep both lanes
        # on full GFNI vectors
        half = (self.block // 2) & ~63
        splittable = half >= 64 * 1024 and (os.cpu_count() or 1) >= 2
        if splittable and self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                1, thread_name_prefix="ec-online"
            )
        for r in range(nrows):
            dat_off = offset + r * self.stripe
            out_off = (row0 + r) * self.block
            threaded = splittable and self._pick_split()
            t0 = time.perf_counter()
            if threaded:
                fut = self._pool.submit(span, dat_off, out_off, 0, half)
                span(dat_off, out_off, half, self.block - half)
                fut.result()
            else:
                span(dat_off, out_off, 0, self.block)
            if splittable and self._split_mode is None:
                self._split_observe(threaded, time.perf_counter() - t0)
        return True

    _SPLIT_PROBE_SPANS = 4  # per mode, then the faster mode locks in

    def _pick_split(self) -> bool:
        if self._split_mode is not None:
            return self._split_mode
        ts, tt, ns, nt = self._split_probe
        if ns < self._SPLIT_PROBE_SPANS:
            return False
        if nt < self._SPLIT_PROBE_SPANS:
            return True
        self._split_mode = tt / nt < ts / ns
        return self._split_mode

    def _split_observe(self, threaded: bool, dt: float) -> None:
        if threaded:
            self._split_probe[1] += dt
            self._split_probe[3] += 1
        else:
            self._split_probe[0] += dt
            self._split_probe[2] += 1

    def _count_fallback(self, reason: str) -> None:
        self.fallbacks[reason] = self.fallbacks.get(reason, 0) + 1
        self._m_fallbacks.labels(self._vol_label, reason).inc()
        from seaweedfs_tpu.stats import events as events_mod

        events_mod.emit("fallback_ec_online", volume=int(self._vol_label),
                        reason=reason)

    def _degrade(self, reason: str) -> None:
        """Leave online mode: the volume reverts to classic
        replicate-then-seal-EC (the server's heartbeat stops reporting
        ec_online, so the master's layout re-applies the volume's real
        replica placement and maintenance can heal it). Idempotent —
        the first reason wins (a journal failure mid-pump must not be
        re-counted as encoder_error by the outer handler)."""
        if not self.active:
            return
        self._count_fallback(reason)
        self.active = False
        self.fallback_reason = reason

    # --- encode -------------------------------------------------------------
    def _encode_span(self, offset: int, nrows: int, span: int) -> None:
        """Encode nrows rows starting at .dat offset `offset` (stripe
        aligned); `span` caps the real bytes (the rest zero-padded —
        only ever for the final partial row). Parity lands at the rows'
        shard offsets in the open .ec10-.ec13 fds."""
        t0 = time.perf_counter()
        need = nrows * self.stripe
        width = nrows * self.block
        # full rows take the zero-copy mapped path; the (rare) padded
        # partial row and any unmappable backend use bounce buffers
        if span < need or not self._encode_rows_mmap(offset, nrows):
            if self._buf is None or self._buf.nbytes < need:
                self._buf = np.empty(need, dtype=np.uint8)
            buf = self._buf[:need]
            real = min(span, need)
            self._read_dat_into(offset, real, buf)
            if real < need:
                buf[real:] = 0
            parity = self.codec.encode_rows_async(
                buf, self.block, nrows
            ).result()
            row = offset // self.stripe
            shard_off = row * self.block
            self._size_parity(row + nrows)
            for p in range(PARITY_SHARDS_COUNT):
                os.pwrite(self._parity_fds[p], parity[p, :width], shard_off)
        dt = time.perf_counter() - t0
        self.encode_seconds += dt
        self.encoded_bytes += need
        self.parity_bytes += width * PARITY_SHARDS_COUNT
        self.stripes += nrows
        self._m_seconds.labels(self._vol_label).observe(dt)
        self._m_bytes.labels(self._vol_label).inc(need)
        self._m_stripes.labels(self._vol_label).inc(nrows)
        # stage attribution in the shared EC pipeline family: the online
        # path is single-pass (mapped read -> GFNI -> mapped parity
        # store), so like the fused engine it reports one busy stage
        encoder_mod._pipeline_hist().labels("online", "busy").observe(dt)

    def _encode_backlog_pipelined(self, offset: int, nrows: int) -> None:
        """Catch-up path for multi-stripe backlogs (drain-tick batches at
        high ingest, journal replay, seal): row batches stream through
        encoder._run_pipeline — reader thread (preadv into the process's
        kept batch buffers) -> GF transform -> writer thread (parity pwrite +
        journal advance) — so read, encode, and write overlap across
        cores instead of serializing per stripe. Stage attribution lands
        in the shared SeaweedFS_volume_ec_pipeline_seconds family."""
        batch_rows = max(1, encoder_mod.DEFAULT_BATCH_HOST // self.block)
        self._size_parity(offset // self.stripe + nrows)
        jobs = [
            (offset + r * self.stripe, min(batch_rows, nrows - r))
            for r in range(0, nrows, batch_rows)
        ]
        t0 = time.perf_counter()

        def read_job(job, buf):
            off, rows = job
            need = rows * self.stripe
            buf = encoder_mod._ensure_buf(
                buf, need, batch_rows * self.stripe
            )
            self._read_dat_into(off, need, buf)
            return buf

        def encode_job(job, buf):
            _, rows = job
            return self.codec.encode_rows_async(
                buf[: rows * self.stripe], self.block, rows
            )

        def write_job(job, buf, handle):
            off, rows = job
            parity = handle.result()
            width = rows * self.block
            shard_off = (off // self.stripe) * self.block
            for p in range(PARITY_SHARDS_COUNT):
                os.pwrite(
                    self._parity_fds[p], parity[p, :width], shard_off
                )
            # jobs complete in order: the watermark only ever covers
            # rows whose parity is fully on disk
            self.watermark = off + rows * self.stripe
            self._partial = 0
            self._journal_append()
            self.stripes += rows
            self.parity_bytes += width * PARITY_SHARDS_COUNT
            self._m_stripes.labels(self._vol_label).inc(rows)

        encoder_mod._run_pipeline(jobs, read_job, encode_job, write_job)
        dt = time.perf_counter() - t0
        need = nrows * self.stripe
        self.encode_seconds += dt
        self.encoded_bytes += need
        self._m_seconds.labels(self._vol_label).observe(dt)
        self._m_bytes.labels(self._vol_label).inc(need)

    def pump(self, now: float | None = None, force: bool = False) -> int:
        """Encode whatever full stripe rows have accumulated past the
        watermark; with `force` (or once a partial row ages past
        flush_age) also flush the zero-padded tail row. Returns rows
        encoded. Called after Python-path writes and from the server's
        fastlane drain loop (native appends never touch Python)."""
        with self._lock:
            return self._pump_locked(now, force)

    def _pump_locked(self, now: float | None, force: bool) -> int:
        if not self.active or self.sealed:
            return 0
        now = time.monotonic() if now is None else now
        end = self._end()
        behind = end - self.watermark
        self._m_buffered.labels(self._vol_label).set(max(0, behind))
        if behind <= 0:
            self._pending_since = None
            return 0
        if behind > self.max_lag_stripes * self.stripe and not force:
            self._degrade("backpressure")
            return 0
        rows_done = 0
        nrows = behind // self.stripe
        try:
            _FP_PARITY.hit(volume=int(self._vol_label))  # error/
            # disk_full degrade like a real emit failure would
            batch_rows = max(1, encoder_mod.DEFAULT_BATCH_HOST // self.block)
            if nrows > max(16, 2 * batch_rows):
                # deep backlog (journal replay, seal catch-up): overlap
                # read/encode/write stages; drain-tick-sized batches stay
                # on the lower-latency single-pass mapped path below
                self._encode_backlog_pipelined(self.watermark, nrows)
                rows_done += nrows
                nrows = 0
            while nrows > 0:
                # small increments: single-pass mapped GFNI per row batch
                take = min(nrows, batch_rows)
                self._encode_span(
                    self.watermark, take, take * self.stripe
                )
                self.watermark += take * self.stripe
                self._partial = 0
                self._journal_append()
                rows_done += take
                nrows -= take
            rem = end - self.watermark
            if rem > 0:
                if self._pending_since is None:
                    self._pending_since = now
                aged = now - self._pending_since >= self.flush_age
                # skip the padded flush when the same partial bytes are
                # already covered (nothing new since the last one)
                if (force or aged) and rem != self._partial:
                    self._encode_span(self.watermark, 1, rem)
                    self._partial = rem
                    self._journal_append()
                    rows_done += 1
                    if not force:
                        self._count_fallback("trickle_flush")
                    self._pending_since = now
            else:
                self._pending_since = None
        except Exception:
            # parity-write/.dat-read/codec failures are encoder errors;
            # a broken JOURNAL already degraded itself inside
            # _journal_append (journal_io), and _degrade keeps the first
            # reason, so the label stays honest either way
            self._degrade("encoder_error")
            return rows_done
        if rows_done:
            spec = _FP_PARITY.spec
            if spec is not None and spec.mode == "torn":
                spec = _FP_PARITY.draw(volume=int(self._vol_label))
                if spec is not None:
                    self._tear_parity(spec.frac)
        self._m_buffered.labels(self._vol_label).set(
            max(0, self._end() - self.watermark)
        )
        return rows_done

    def _tear_parity(self, frac: float) -> None:
        """Torn-parity-write injection: chop the tail off parity shard 0
        — the on-disk state a crash mid-append leaves. Bookkeeping
        follows the cut so the next mapped write cannot SIGBUS past the
        new EOF; the WRITER believes its watermark, which is the point:
        only the heartbeat's parity_health() audit can notice."""
        fd = self._parity_fds[0]
        # cut below the DURABLE watermark's rows: the parity files are
        # pre-sized ahead of the write cursor (_size_parity), so a cut
        # into that slack would tear nothing anyone claimed durable
        need = (self.watermark // self.stripe) * self.block
        cut = max(1, int(self.block * min(max(frac, 0.0), 1.0)))
        new_size = max(0, min(os.fstat(fd).st_size, need) - cut)
        self._drop_parity_maps()
        os.ftruncate(fd, new_size)
        self._parity_rows_sized = min(
            self._parity_rows_sized, new_size // self.block
        )

    def parity_health(self) -> int:
        """Missing-or-short parity shard count, audited against the
        durable watermark (full rows only — a partial flush only ever
        grows a file). Rides the heartbeat so the master's ec_rebuild
        detector can see a LIVE online volume whose parity was lost or
        torn, instead of reporting it healthy. No content scrub: a hole
        backfilled by later growth is out of this audit's reach — loss
        and tail tears (the crash/unlink class) are what it catches."""
        if not self.active or self.sealed:
            return 0
        # under the writer lock: rearm() truncates the parity files a few
        # statements before rewinding the watermark, and an unlocked audit
        # in that window would report phantom damage (queueing a SECOND
        # full re-encode). Bounded acquire: a long re-encode holding the
        # lock must not stall the heartbeat — skip the audit this beat.
        if not self._lock.acquire(timeout=0.2):
            return 0
        try:
            if not self.active or self.sealed:
                return 0
            need = (self.watermark // self.stripe) * self.block
            damaged = 0
            for p in range(PARITY_SHARDS_COUNT):
                path = self.volume.base_name + to_ext(DATA_SHARDS_COUNT + p)
                try:
                    size = os.path.getsize(path)
                except OSError:
                    damaged += 1
                    continue
                if size < need:
                    damaged += 1
            return damaged
        finally:
            self._lock.release()

    def scrub_sample(self, max_rows: int = 4,
                     sample_bytes: int = 4096) -> tuple[int, list[int]]:
        """Integrity scrub: recompute-and-compare a sampled column slice
        of up to `max_rows` durable stripe rows (GF is byte-wise, so a
        slice verifies independently of the rest of the row); a slice
        mismatch escalates to the full-width row before it is reported.
        Returns (bytes_verified, mismatching row indices); the CALLER
        pays its throttle afterwards — this runs under the writer lock,
        and sleeping here would stall the append path. Short parity
        reads are skipped — parity_health() already reports loss/tears;
        this pass is for silent CONTENT damage."""
        with self._lock:
            if not self._parity_fds or self.sealed:
                return 0, []
            rows = self.watermark // self.stripe
            if rows <= 0:
                return 0, []
            picks = sorted({
                int(i) for i in
                np.linspace(0, rows - 1, num=min(max_rows, rows))
            })
            width = min(sample_bytes, self.block)
            checked = 0
            mismatches: list[int] = []
            for row in picks:
                for off, w in ((0, width), (None, None)):
                    if off is None:  # escalation: full width
                        off, w = 0, self.block
                    cost = w * (DATA_SHARDS_COUNT + PARITY_SHARDS_COUNT)
                    data = []
                    for c in range(DATA_SHARDS_COUNT):
                        col_start = row * self.stripe + c * self.block + off
                        data.append(np.frombuffer(
                            self._read_dat(col_start, w), dtype=np.uint8
                        ))
                    parity = {}
                    for p in range(PARITY_SHARDS_COUNT):
                        blk = os.pread(
                            self._parity_fds[p], w, row * self.block + off
                        )
                        if len(blk) == w:
                            parity[p] = np.frombuffer(blk, dtype=np.uint8)
                    checked += cost
                    if not parity:
                        break  # torn/short: parity_health's finding
                    expect = self.codec.encode(np.stack(data))
                    ok = all(
                        np.array_equal(expect[p], blk)
                        for p, blk in parity.items()
                    )
                    if ok:
                        break  # slice verified: next row
                    if w == self.block:  # full width still disagrees
                        mismatches.append(row)
                        break  # recorded: when the sample already spans
                        # the block, the escalation iteration would
                        # re-verify and re-report this same row
            return checked, mismatches

    def reconstruct_range(self, offset: int, size: int) -> bytes | None:
        """Rebuild .dat bytes [offset, offset+size) from parity + the
        other data columns — the degraded-read path for a torn/unreadable
        needle on a live online-EC volume.

        Per stripe row, two regimes:
          * narrow range (<= 4 columns overlapped): treat the overlapped
            columns as erasures and RS-decode them outright;
          * wide range (a needle spanning most of a row): the erasure
            view can't name >4 missing columns, so LOCATE the damage
            instead — recompute parity from the .dat columns; a clean
            match means the row is intact, otherwise try each overlapped
            column as the single corrupt one, reconstruct it, and accept
            the candidate all surviving parity rows verify. (Needle CRC
            re-checks the assembled record at the caller regardless.)

        Data columns are read as they were at encode time (zero past the
        covered watermark) so the tail row's stale-parity window stays
        consistent. Returns None whenever parity cannot prove the range."""
        with self._lock:
            if not self._parity_fds or not self.active:
                return None
            block, stripe = self.block, self.stripe
            covered = self.watermark + self._partial
            if size <= 0 or offset < 0 or offset + size > covered:
                return None  # parity hasn't durably covered the range
            out = bytearray()
            row0 = offset // stripe
            row1 = (offset + size - 1) // stripe
            for row in range(row0, row1 + 1):
                row_start = row * stripe
                lo = max(offset, row_start)
                hi = min(offset + size, row_start + stripe)
                targets = list(range((lo - row_start) // block,
                                     (hi - 1 - row_start) // block + 1))

                def read_col(c: int) -> np.ndarray:
                    col_start = row_start + c * block
                    if col_start >= covered:
                        return np.zeros(block, dtype=np.uint8)
                    take = min(block, covered - col_start)
                    data = self._read_dat(col_start, take)
                    if take < block:
                        data = data + b"\0" * (block - take)
                    return np.frombuffer(data, dtype=np.uint8)

                parity: dict[int, np.ndarray] = {}
                for p in range(PARITY_SHARDS_COUNT):
                    data = os.pread(self._parity_fds[p], block, row * block)
                    if len(data) == block:  # short = torn: unusable
                        parity[p] = np.frombuffer(data, dtype=np.uint8)
                if not parity:
                    return None
                row_data = self._recover_row(
                    targets, read_col, parity, block
                )
                if row_data is None:
                    return None
                pos = lo
                while pos < hi:
                    c = (pos - row_start) // block
                    inner = (pos - row_start) % block
                    take = min(hi - pos, block - inner)
                    out += row_data[c].tobytes()[inner:inner + take]
                    pos += take
            return bytes(out)

    def _recover_row(self, targets, read_col, parity, block):
        """One stripe row's data columns with the damage decoded out;
        None when parity cannot prove a consistent row. See
        reconstruct_range for the two regimes."""
        present_parity = {
            DATA_SHARDS_COUNT + p: blk for p, blk in parity.items()
        }
        if len(targets) <= min(PARITY_SHARDS_COUNT, len(parity)):
            present = {
                c: read_col(c)
                for c in range(DATA_SHARDS_COUNT) if c not in targets
            }
            present.update(present_parity)
            if len(present) < DATA_SHARDS_COUNT:
                return None
            try:
                rec = self.codec.reconstruct(present, targets=targets)
            except Exception:
                return None
            return {
                c: (rec[c] if c in targets else present[c])
                for c in range(DATA_SHARDS_COUNT)
            }
        # wide range: locate the corruption via parity verification
        data = [read_col(c) for c in range(DATA_SHARDS_COUNT)]

        def verifies(cols) -> bool:
            expect = self.codec.encode(np.stack(cols))
            return all(
                np.array_equal(expect[p], blk)
                for p, blk in parity.items()
            )

        try:
            if verifies(data):
                return dict(enumerate(data))  # row is intact as-read
            for suspect in targets:
                present = {
                    c: data[c]
                    for c in range(DATA_SHARDS_COUNT) if c != suspect
                }
                present.update(present_parity)
                rec = self.codec.reconstruct(present, targets=[suspect])
                candidate = list(data)
                candidate[suspect] = rec[suspect]
                if verifies(candidate):
                    return dict(enumerate(candidate))
        except Exception:
            return None
        return None  # multi-column damage in one row: not provable here

    def rearm(self) -> int:
        """Recreate the parity shard files and re-encode everything from
        byte 0 — the ec_rebuild executor's online branch for a LIVE
        volume whose parity was lost or torn. Parity is a pure function
        of the append-only .dat, so a from-scratch re-encode off the
        durable bytes is always correct; it also clears a degraded
        writer (healing back to active is the point). Returns the rows
        re-encoded."""
        with self._lock:
            self._drop_maps()
            for fd in self._parity_fds:
                try:
                    os.close(fd)
                except OSError:
                    pass
            fds = []
            for p in range(PARITY_SHARDS_COUNT):
                path = self.volume.base_name + to_ext(DATA_SHARDS_COUNT + p)
                fds.append(os.open(path, os.O_RDWR | os.O_CREAT, 0o644))
            self._parity_fds = fds
            for fd in fds:
                os.ftruncate(fd, 0)
            self._parity_rows_sized = 0
            self.watermark = 0
            self._partial = 0
            self._pending_since = None
            self.active = True
            self.fallback_reason = None
            self._count_fallback("parity_rearm")
            try:
                os.ftruncate(self._journal_fd, 0)
            except OSError:
                pass
            self._journal_append()
        return self.pump(force=True)

    # --- reads from the open state -------------------------------------------
    def read_shard_range(self, shard_id: int, off: int, size: int) -> bytes | None:
        """Serve a shard byte range from the OPEN state: parity from the
        incrementally-written .ec1x files (None past the encoded
        watermark), data shards straight from the .dat — the uniform
        stripe geometry makes data shard c, row r a view of .dat bytes
        [r*stripe + c*block, +block). Zero-padded past the .dat end,
        exactly as seal() will materialize them. Serialized against the
        pump/reset/close paths: a vacuum reset rewinding the watermark
        and truncating parity mid-read must not hand out short/stale
        bytes as valid parity."""
        if shard_id < 0 or shard_id >= TOTAL_SHARDS_COUNT:
            return None
        with self._lock:
            if not self._parity_fds:
                return None  # closed
            rows_encoded = self.watermark // self.stripe + (
                1 if self._partial else 0
            )
            if shard_id >= DATA_SHARDS_COUNT:
                if off + size > rows_encoded * self.block:
                    return None  # parity not written yet for that range
                data = os.pread(
                    self._parity_fds[shard_id - DATA_SHARDS_COUNT], size, off
                )
                return data if len(data) == size else None
            end = self._end()
            out = bytearray()
            pos = off
            remaining = size
            while remaining > 0:
                row, inner = divmod(pos, self.block)
                take = min(remaining, self.block - inner)
                dat_off = row * self.stripe + shard_id * self.block + inner
                if dat_off >= end:
                    out += b"\0" * take
                else:
                    out += self._read_dat(dat_off, take)
                pos += take
                remaining -= take
            return bytes(out)

    # --- lifecycle ------------------------------------------------------------
    def reset(self) -> None:
        """Restart parity from scratch — the .dat was rewritten under us
        (vacuum compaction). Counted as `vacuum_reset`, not pathological."""
        with self._lock:
            self.watermark = 0
            self._partial = 0
            self._pending_since = None
            self._parity_rows_sized = 0
            self._drop_maps()  # the .dat fd/contents changed under us
            for fd in self._parity_fds:
                os.ftruncate(fd, 0)
            try:
                os.ftruncate(self._journal_fd, 0)
            except OSError:
                pass
            self._count_fallback("vacuum_reset")
            self._journal_append()

    def seal(self) -> None:
        """Finish the volume's shards for EC mount: flush the tail row,
        materialize .ec00-.ec09 by sequential copy from the .dat (no GF
        math — ingest already paid it), size every shard exactly, and
        record the uniform geometry in the .vif for readers."""
        with self._lock:
            if self.sealed:
                return
            self._pump_locked(None, force=True)
            if not self.active:
                raise RuntimeError(
                    f"online ec volume {self.volume.id} degraded"
                    f" ({self.fallback_reason}); seal must re-encode"
                )
            dat_size = self._end()
            rows = -(-dat_size // self.stripe)  # ceil
            shard_size = shard_file_size(dat_size, self.block, self.block)
            assert shard_size == rows * self.block
            blockbuf = np.empty(self.block, dtype=np.uint8)
            for c in range(DATA_SHARDS_COUNT):
                path = self.volume.base_name + to_ext(c)
                tmp = path + ".tmp"
                fd = os.open(tmp, os.O_RDWR | os.O_CREAT | os.O_TRUNC, 0o644)
                try:
                    os.ftruncate(fd, shard_size)
                    for r in range(rows):
                        dat_off = r * self.stripe + c * self.block
                        if dat_off >= dat_size:
                            continue  # stays zero (pre-truncated)
                        take = min(self.block, dat_size - dat_off)
                        self._read_dat_into(dat_off, take, blockbuf)
                        os.pwrite(fd, blockbuf[:take], r * self.block)
                finally:
                    os.close(fd)
                os.replace(tmp, path)
            self._drop_maps()  # before shrinking under a live mapping
            for fd in self._parity_fds:
                os.ftruncate(fd, shard_size)
                os.fsync(fd)
            _merge_vif(
                self.volume.base_name + ".vif",
                {
                    "large_block_size": self.block,
                    "small_block_size": self.block,
                    "ec_online": {"block_size": self.block, "sealed": True},
                },
                version=self.volume.version(),
            )
            self.sealed = True
            try:  # the journal's job is done: shards are complete
                os.unlink(self._journal_path)
            except OSError:
                pass
            self._m_buffered.labels(self._vol_label).set(0)

    def close(self) -> None:
        with self._lock:
            if self._pool is not None:
                self._pool.shutdown(wait=True)
                self._pool = None
            self._drop_maps()
            for fd in self._parity_fds:
                try:
                    os.close(fd)
                except OSError:
                    pass
            self._parity_fds = []
            try:
                os.close(self._journal_fd)
            except OSError:
                pass

    def stats(self) -> dict:
        return {
            "active": self.active,
            "sealed": self.sealed,
            "block_size": self.block,
            "watermark": self.watermark,
            "stripes": self.stripes,
            "encoded_bytes": self.encoded_bytes,
            "encode_seconds": round(self.encode_seconds, 6),
            "parity_bytes": self.parity_bytes,
            "journal_replays": self.journal_replays,
            "fallbacks": dict(self.fallbacks),
            "fallback_reason": self.fallback_reason,
        }


def _native_ok() -> bool:
    try:
        from seaweedfs_tpu.native import lib

        return lib is not None
    except Exception:
        return False


def _merge_vif(path: str, extra: dict, version: int = 3) -> None:
    info = encoder_mod.load_volume_info(path)
    info.setdefault("version", version)
    info.update(extra)
    encoder_mod.save_volume_info(path, **info)


def online_info(base_name: str) -> dict | None:
    """The .vif's ec_online section for a volume base name, or None."""
    info = encoder_mod.load_volume_info(base_name + ".vif")
    oe = info.get("ec_online")
    return dict(oe) if isinstance(oe, dict) else None
