"""Upload-path batch hash service: MD5 + CRC32C through the batch kernels.

The reference hashes every uploaded blob — an MD5 tee in the filer
(`weed/server/filer_server_handlers_write_upload.go:48-49`) and a CRC32C
per needle on the volume server (`weed/storage/needle/needle.go:52`,
`crc.go:12`) — using assembly inside Go libraries. Here the serving path
funnels one-shot blob hashing through this service instead of calling a
scalar hasher inline:

* concurrent requests' blobs are bucketed by length and hashed as ONE batch
  call — `ops.md5_kernel`/`ops.crc32c_kernel` on the TPU (lockstep VPU
  lanes / GF(2) matmul on the MXU), or one GIL-released C++ call
  (`sw_md5_batch`/`sw_crc32c_batch`) on the host;
* a linger window (default 0.5ms) gives in-flight requests a chance to
  coalesce, exactly like an inference micro-batcher; a lone blob under
  min_batch skips the queue and hashes synchronously on the native path
  (no latency tax when the server is idle);
* the backend is picked by measured end-to-end batch rate, transfers
  included, overridable with SEAWEEDFS_TPU_HASH_BACKEND;
* a device batch is padded with zero rows up to a fixed ladder of row
  counts (`_ROW_LADDER`): the kernels are jitted on the whole (n, L) shape
  and the row count is whatever the linger window caught, so without the
  ladder every new count is a new compile.

Streaming whole-file MD5 (one hash spanning a multi-chunk stream) stays on
the CPU per SURVEY.md §7 step 4 — MD5 is sequential per stream; only the
batch dimension parallelizes.
"""

from __future__ import annotations

import binascii
import hashlib
import os
import threading
import time

import numpy as np

from seaweedfs_tpu.ops import device
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.util import glog

_MIN_BATCH = 4  # below this, batching buys nothing — hash synchronously
_MAX_BATCH = 8192
_LINGER_S = 0.0005
# Row counts a device batch is padded up to: powers of four from 16 to
# 16384. A batch larger than the last rung is split at it.
_ROW_LADDER = (16, 64, 256, 1024, 4096, 16384)


class HashResult:
    """Future for one submitted blob."""

    __slots__ = ("_event", "md5", "crc")

    def __init__(self) -> None:
        self._event = threading.Event()
        self.md5: bytes = b""
        self.crc: int = 0

    def _set(self, md5: bytes, crc: int) -> None:
        self.md5 = md5
        self.crc = crc
        self._event.set()

    def wait(self, timeout: float = 30.0) -> "HashResult":
        if not self._event.wait(timeout):
            raise TimeoutError("hash batch never flushed")
        return self

    def md5_hex(self) -> str:
        self.wait()
        return binascii.hexlify(self.md5).decode()


def _native_lib():
    from seaweedfs_tpu.native import lib

    return lib


def _hash_one(data) -> tuple[bytes, int]:
    from seaweedfs_tpu.storage import crc as crc_mod

    return hashlib.md5(data).digest(), crc_mod.crc32c(data)


class HashService:
    def __init__(
        self,
        backend: str = "auto",
        linger_s: float = _LINGER_S,
        min_batch: int = _MIN_BATCH,
        max_batch: int = _MAX_BATCH,
    ) -> None:
        self._backend = backend
        self.linger_s = linger_s
        self.min_batch = min_batch
        self.max_batch = max_batch
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        # length -> list of (data, HashResult)
        self._buckets: dict[int, list[tuple[bytes, HashResult]]] = {}
        self._active_sync = 0  # submits hashing on the caller's thread
        self._stop = False
        self._thread: threading.Thread | None = None

    # --- backend -------------------------------------------------------------
    @property
    def backend(self) -> str:
        if self._backend == "auto":
            self._backend = self._pick_backend()
        return self._backend

    @staticmethod
    def _pick_backend() -> str:
        env = os.environ.get("SEAWEEDFS_TPU_HASH_BACKEND", "")
        if env:
            return env
        candidates = []
        # consider the device path only when this process already runs jax
        # (e.g. the EC pipeline started it): hashing alone never warrants
        # paying jax start-up
        if device.started():
            try:
                if device.platform() != "cpu":
                    candidates.append("jax")
            except Exception as e:  # noqa: BLE001 - jax raises many types
                device.note_selection_failure("hash service: jax platform", e)
        if _native_lib() is not None:
            candidates.append("native")
        if not candidates:
            return "python"
        if len(candidates) == 1:
            return candidates[0]
        # measure true end-to-end batch rate (transfers included) per backend
        rng = np.random.RandomState(0)
        sample = rng.randint(0, 256, size=(256, 4096), dtype=np.uint8)
        best, best_rate = candidates[0], 0.0
        for name in candidates:
            try:
                _batch_hash(name, sample)  # warm/compile
                t0 = time.perf_counter()
                _batch_hash(name, sample)
                rate = sample.nbytes / (time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001 - candidate cannot run
                device.note_selection_failure(
                    f"hash service: {name} candidate", e
                )
                continue
            if rate > best_rate:
                best, best_rate = name, rate
        return best

    # --- lifecycle -----------------------------------------------------------
    def start(self) -> None:
        if self._thread is None:
            self._stop = False
            self._thread = threading.Thread(
                target=self._flusher, name="hash-batcher", daemon=True
            )
            self._thread.start()

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None

    # --- API -----------------------------------------------------------------
    def submit(self, data: bytes) -> HashResult:
        """Enqueue one blob; returns a future. A lone blob on an idle server
        (nothing queued, no other submit in flight) hashes synchronously on
        the caller's thread — no linger/wakeup tax; the queue engages only
        under genuinely concurrent load."""
        r = HashResult()
        if self._thread is None or len(data) == 0:
            r._set(*_hash_one(data))
            return r
        with self._cv:
            idle = not self._buckets and self._active_sync == 0
            if idle:
                self._active_sync += 1
            else:
                # callers hand over immutable bytes slices; only copy when
                # given a mutable view (bench path passes bytes — zero-copy)
                blob = data if isinstance(data, bytes) else bytes(data)
                self._buckets.setdefault(len(data), []).append((blob, r))
                self._cv.notify_all()
        if idle:
            try:
                t0 = time.perf_counter()
                r._set(*_hash_one(data))
                trace.observe_kernel(
                    trace.FILER_HASH_SECONDS, "scalar",
                    time.perf_counter() - t0, len(data),
                )
            finally:
                with self._cv:
                    self._active_sync -= 1
        return r

    def submit_many(self, blobs) -> list[HashResult]:
        """Enqueue a burst from one caller (e.g. every piece of a chunked
        upload) as a group: unlike N submit() calls, the burst always goes
        through the queue so same-length pieces coalesce into batch-kernel
        calls — the idle fast path would otherwise hash each piece scalar
        back-to-back."""
        results = [HashResult() for _ in blobs]
        if self._thread is None:
            for data, r in zip(blobs, results):
                r._set(*_hash_one(data))
            return results
        with self._cv:
            for data, r in zip(blobs, results):
                if len(data) == 0:
                    r._set(*_hash_one(data))
                    continue
                blob = data if isinstance(data, bytes) else bytes(data)
                self._buckets.setdefault(len(blob), []).append((blob, r))
            self._cv.notify_all()
        return results

    def hash_now(self, data: bytes) -> tuple[str, int]:
        """Synchronous convenience: (md5 hex, crc32c)."""
        md5, crc = _hash_one(data)
        return binascii.hexlify(md5).decode(), crc

    def span_keys(self, buf, cuts, seed: bytes = b"") -> list[str]:
        """Dedup identity keys per CDC span, function-prefixed:
        "x<hex32>" = SW128 keyed by the caller's per-store seed (native
        kernel, ~2.5x the MD5 span batch on this host), "f<hex32>" = MD5
        fallback when the native lib is absent. The prefix keeps the two
        key spaces disjoint — a store written by one backend and served by
        the other simply stops cross-deduping instead of mixing hash
        functions under one key."""
        if not cuts:
            return []
        lib = _native_lib()
        if lib is not None and hasattr(lib, "fast128_spans"):
            with trace.kernel_span(
                "hash.sw128_spans", trace.FILER_HASH_SECONDS, "sw128",
                nbytes=int(cuts[-1]), role="filer", spans=len(cuts),
            ):
                digests = lib.fast128_spans(buf, cuts, seed)
            return [
                "x" + binascii.hexlify(digests[i].tobytes()).decode()
                for i in range(len(cuts))
            ]
        return ["f" + h for h, _ in self.hash_spans(buf, cuts)]

    def md5_spans(self, buf, ranges: list[tuple[int, int]]) -> list[str]:
        """MD5 hex per (offset, length) span — one lockstep native batch,
        scalar fallback. The dedup path uses this for index MISSES only."""
        if not ranges:
            return []
        nbytes = sum(n for _, n in ranges)
        lib = _native_lib()
        if lib is not None and hasattr(lib, "md5_spans"):
            with trace.kernel_span(
                "hash.md5_spans", trace.FILER_HASH_SECONDS, "md5_spans",
                nbytes=nbytes, role="filer", spans=len(ranges),
            ):
                digests = lib.md5_spans(buf, [r[0] for r in ranges],
                                        [r[1] for r in ranges])
            return [
                binascii.hexlify(digests[i].tobytes()).decode()
                for i in range(len(ranges))
            ]
        mv = memoryview(buf)
        with trace.kernel_span(
            "hash.md5_spans", trace.FILER_HASH_SECONDS, "md5_spans_scalar",
            nbytes=nbytes, role="filer", spans=len(ranges),
        ):
            return [
                hashlib.md5(bytes(mv[o:o + n])).hexdigest() for o, n in ranges
            ]

    def hash_spans(self, buf, cuts) -> list[tuple[str, int]]:
        """Synchronous batch over CDC spans of one contiguous buffer:
        returns [(md5 hex, crc32c)] per chunk, cuts being exclusive ends.
        One GIL-released native call hashes the whole upload's chunks in
        lockstep with zero per-chunk copies — the dedup write path's shape
        (the future-per-chunk queue costs more in lock churn than the
        hashing itself on a single-core host). Backend "python" (the
        operator escape hatch) hashes scalar; "jax" also uses the native
        span kernel — span batches are host-resident and latency-bound, the
        worst case for a device round-trip."""
        if not cuts:
            return []
        lib = _native_lib() if self.backend in ("native", "jax") else None
        if lib is not None and hasattr(lib, "md5_crc_batch_spans"):
            with trace.kernel_span(
                "hash.spans", trace.FILER_HASH_SECONDS, "md5_crc_spans",
                nbytes=int(cuts[-1]), role="filer", spans=len(cuts),
            ):
                digests, crcs = lib.md5_crc_batch_spans(buf, cuts)
            return [
                (binascii.hexlify(digests[i].tobytes()).decode(), int(crcs[i]))
                for i in range(len(cuts))
            ]
        mv = memoryview(buf)
        out = []
        prev = 0
        t0 = time.perf_counter()
        for c in cuts:
            md5, crc = _hash_one(bytes(mv[prev:c]))
            prev = c
            out.append((binascii.hexlify(md5).decode(), crc))
        trace.observe_kernel(
            trace.FILER_HASH_SECONDS, "md5_crc_spans_scalar",
            time.perf_counter() - t0, int(cuts[-1]),
        )
        return out

    # --- internals -----------------------------------------------------------
    def _flusher(self) -> None:
        while True:
            with self._cv:
                if not self._buckets and not self._stop:
                    self._cv.wait(0.05)
                if self._stop and not self._buckets:
                    return
                if not self._buckets:
                    continue
                deadline = time.monotonic() + self.linger_s
                while (
                    not self._stop
                    and time.monotonic() < deadline
                    and sum(len(b) for b in self._buckets.values())
                    < self.max_batch
                ):
                    self._cv.wait(self.linger_s / 4 or 0.0001)
                work = self._buckets
                self._buckets = {}
            lib = _native_lib() if self.backend == "native" else None
            if lib is not None and hasattr(lib, "md5_crc_batch_var"):
                # variable-length lockstep kernel: one call for the whole
                # drain, length-sorted inside. Content-defined (CDC) chunks
                # have unique lengths, so the per-length buckets would each
                # hold one blob and the batch kernels would never engage.
                items = [it for bucket in work.values() for it in bucket]
                try:
                    t0 = time.perf_counter()
                    digests, crcs = lib.md5_crc_batch_var(
                        [d for d, _ in items]
                    )
                    trace.observe_kernel(
                        trace.FILER_HASH_SECONDS, "batch_var",
                        time.perf_counter() - t0,
                        sum(len(d) for d, _ in items),
                    )
                    for i, (_, r) in enumerate(items):
                        r._set(digests[i].tobytes(), int(crcs[i]))
                except Exception as e:  # noqa: BLE001 - never drop a hash
                    _degrade_to_scalar("batch_var", items, e)
                continue
            for length, items in work.items():
                try:
                    self._flush_bucket(length, items)
                except Exception as e:  # noqa: BLE001 - never drop a hash
                    _degrade_to_scalar("batch-" + self.backend, items, e)

    def _flush_bucket(self, length: int, items) -> None:
        if len(items) < self.min_batch:
            for data, r in items:
                r._set(*_hash_one(data))
            return
        if self.backend == "jax" and len(items) > _ROW_LADDER[-1]:
            for i in range(0, len(items), _ROW_LADDER[-1]):
                self._flush_bucket(length, items[i:i + _ROW_LADDER[-1]])
            return
        n = len(items)
        parts = [d for d, _ in items]
        if self.backend == "jax":
            rows = next(r for r in _ROW_LADDER if r >= n)
            parts.append(bytes((rows - n) * length))  # zero rows, ignored below
        blobs = np.frombuffer(b"".join(parts), dtype=np.uint8).reshape(-1, length)
        t0 = time.perf_counter()
        digests, crcs = _batch_hash(self.backend, blobs)
        trace.observe_kernel(
            trace.FILER_HASH_SECONDS, "batch-" + self.backend,
            time.perf_counter() - t0, n * length,
        )
        for i, (_, r) in enumerate(items):
            r._set(digests[i].tobytes(), int(crcs[i]))


def _degrade_to_scalar(kernel: str, items, exc: BaseException) -> None:
    """A batch kernel failed: hash its blobs one by one so no future is ever
    dropped, and count the batch under `<kernel>-degraded` with the
    exception logged — a batch that failed must not read as one that ran."""
    glog.warning(
        "hash batch %s failed (%s: %s): %d blobs hashed scalar",
        kernel, type(exc).__name__, exc, len(items),
    )
    t0 = time.perf_counter()
    for data, r in items:
        r._set(*_hash_one(data))
    trace.observe_kernel(
        trace.FILER_HASH_SECONDS, kernel + "-degraded",
        time.perf_counter() - t0, sum(len(d) for d, _ in items),
    )


def _batch_hash(backend: str, blobs: np.ndarray):
    """(n, L) uint8 -> ((n, 16) md5 digests, (n,) uint32 crcs)."""
    n, length = blobs.shape
    if backend == "jax":
        from seaweedfs_tpu.ops.crc32c_kernel import crc32c_batch
        from seaweedfs_tpu.ops.md5_kernel import md5_batch

        return md5_batch(blobs, backend="jax"), crc32c_batch(blobs, backend="jax")
    lib = _native_lib()
    if backend == "native" and lib is not None:
        return (
            lib.md5_batch_np(blobs, n, length),
            lib.crc32c_batch(blobs, n, length),
        )
    from seaweedfs_tpu.storage import crc as crc_mod

    digests = np.stack([
        np.frombuffer(hashlib.md5(blobs[i].tobytes()).digest(), dtype=np.uint8)
        for i in range(n)
    ])
    crcs = np.array(
        [crc_mod.crc32c(blobs[i].tobytes()) for i in range(n)], dtype=np.uint32
    )
    return digests, crcs


_SERVICE: HashService | None = None
_SERVICE_MU = threading.Lock()


def get_hash_service() -> HashService:
    """Process-wide singleton used by the filer/volume serving paths."""
    global _SERVICE
    with _SERVICE_MU:
        if _SERVICE is None:
            _SERVICE = HashService()
            _SERVICE.start()
        return _SERVICE
