"""Reed-Solomon GF(2^8) shard transforms as TPU bit-plane matmuls (JAX).

The trick (SURVEY.md §7 step 3): a GF(2^8) multiply-accumulate over shards is
GF(2)-linear in the *bits* of the input bytes. Expanding each coefficient into
an 8x8 GF(2) bit-matrix turns the whole shard transform into

    out_bits(N, R*8) = in_bits(N, C*8) @ A(C*8, R*8)   (mod 2)

— one int8 matrix multiply on the MXU plus cheap VPU unpack/pack, instead of
the byte-wise table lookups (PSHUFB) CPU implementations use. The same kernel
does encode (A from the parity rows), reconstruct (A from inverted sub-matrix)
and decode; only the small host-side matrix differs.

Byte-identical to ops.gf256.gf_matmul_bytes (the numpy oracle), the C++
native path, and therefore klauspost/reedsolomon as used by the reference
(`weed/storage/erasure_coding/ec_encoder.go:202,239`).
"""

from __future__ import annotations

import bisect
import functools
import os
import threading
import time

import numpy as np

from seaweedfs_tpu.stats import trace

from seaweedfs_tpu.storage.erasure_coding.constants import (  # noqa: F401
    DATA_SHARDS,
    PARITY_SHARDS,
    TOTAL_SHARDS,
)

from . import device, gf256, rs_pallas

# The Pallas body's block width, and the unit of every width below.
TILE = 8192
# The widths, in tiles, that host bytes of up to one small block (128 tiles =
# SMALL_BLOCK_SIZE) reach the kernel at: every tile multiple up to nine (a
# 64 KiB needle's record and below), then a step of a third or a half, so the
# zero tail stays under a third of what crosses the link. A degraded read of
# any length thus compiles at most these 17 programs per (rows, cols), whatever
# the coefficient matrix: the program takes the matrix as an operand.
LADDER_TILES = (1, 2, 3, 4, 5, 6, 7, 8, 9, 12, 16, 24, 32, 48, 64, 96, 128)


def transform_kernel() -> str:
    """Which form of the bit-plane transform the jax backend runs here:
    "pallas" (the fused kernel) on a TPU, "xla" everywhere else. The one
    place that decides; the kernel-span labels carry the same word."""
    return "pallas" if device.platform() == "tpu" else "xla"


@functools.lru_cache(maxsize=64)
def _compiled_xla(rows: int, cols: int):
    """The jitted XLA form for every (rows, cols) coefficient matrix:
    fn(a, (cols, w) uint32) -> (rows, w) uint32, `a` the matrix's
    `gf256.bit_matrix` as int8 (cols*8, rows*8) and the words the bytes of
    (cols, 4w) and (rows, 4w) uint8 as little-endian words, for any w
    (`rs_pallas.compiled` takes and returns the same bytes, its operand
    plane-major and its result shaped (rows, w/128, 128))."""
    jax = device.jax()
    jnp = jax.numpy

    @jax.jit
    def transform(a, words):  # (cols*8, rows*8) int8; (cols, w) uint32
        w = words.shape[1]
        k = jnp.arange(8, dtype=jnp.uint32)
        out = jnp.zeros((rows, w), jnp.uint32)
        for p in range(4):  # byte p of each word: the same transform
            xt = ((words >> (8 * p)) & 0xFF).T  # (w, cols)
            bits = (xt[:, :, None] >> k) & 1  # (w, cols, 8)
            bits = bits.reshape(w, cols * 8).astype(jnp.int8)
            y = jax.lax.dot_general(
                bits,
                a,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )  # (w, rows*8)
            ybits = (y & 1).astype(jnp.uint32).reshape(w, rows, 8)
            packed = jnp.sum(ybits << k, axis=-1)  # (w, rows)
            out = out | (packed.T << (8 * p))
        return out

    return transform


def ladder_width(n: int, tile: int) -> int:
    """The width host bytes of width n go to the kernel at: the next rung of
    `LADDER_TILES`, or beyond the ladder (rows of large blocks) the next
    multiple of `tile`."""
    tiles = -(-n // tile)
    if tiles > LADDER_TILES[-1]:
        return tiles * tile
    return LADDER_TILES[bisect.bisect_left(LADDER_TILES, tiles)] * tile


def zero_tailed(rows, tile: int) -> np.ndarray:
    """`rows` — a (cols, n) array or a sequence of cols (n,) arrays — as one
    C-contiguous (cols, `ladder_width(n, tile)`) uint8 host array, zero
    beyond column n: one copy per row, as `np.stack` makes, and none where
    `rows` is such an array already. The one place that decides at which
    width host bytes reach the kernel, in either of its forms.

    The copies go through a memoryview and so keep the interpreter lock:
    numpy gives it up around each copy of more than 500 bytes, and under
    sixteen reader threads getting it back ten times a read costs several
    times the copies themselves (PERF.md, PR 27)."""
    n = len(rows[0])
    width = ladder_width(n, tile)
    if isinstance(rows, np.ndarray) and width == n:
        return np.ascontiguousarray(rows, dtype=np.uint8)
    buf = bytearray(len(rows) * width)  # zeroed
    flat = memoryview(buf)
    for i, row in enumerate(rows):
        flat[i * width : i * width + n] = row
    return np.frombuffer(buf, dtype=np.uint8).reshape(len(rows), width)


# held around a matrix's first put to a device, so it is put once
_OPERAND_LOCK = threading.Lock()


def _bits(matrix: np.ndarray, body: str) -> np.ndarray:
    """The coefficient matrix as the body's program takes it: int8,
    `rs_pallas.plane_major_bits` for "pallas", else `gf256.bit_matrix`."""
    if body == "pallas":
        return rs_pallas.plane_major_bits(matrix)
    return gf256.bit_matrix(matrix).astype(np.int8)


def _operand(matrix: np.ndarray, body: str, words, dev: int):
    """`_bits(matrix, body)` where the program runs: put once on the device
    of `words` (the default device for a host array), kept in
    `device.matrices` by (matrix bytes, body, device index) and taken from
    there by every later call. Each put is counted under
    `SeaweedFS_volume_ec_door_matrix_puts_total{device}` and timed as phase
    `rs.matrix_put`; a call that finds its matrix there updates nothing.
    Inside a caller's own jit (`words` a tracer) the host array is returned,
    a constant of the caller's program."""
    jax = device.jax()
    if isinstance(words, jax.core.Tracer):
        return _bits(matrix, body)
    key = (matrix.tobytes(), body, dev)
    held = device.matrices.get(key)
    if held is not None:
        return held
    target = None if isinstance(words, np.ndarray) else next(iter(words.devices()))
    with _OPERAND_LOCK:
        held = device.matrices.get(key)
        if held is None:
            with trace.phase("rs.matrix_put", trace.EC_DEVICE_SECONDS, "matrix-put"):
                held = jax.device_put(_bits(matrix, body), target)
            trace.door_matrix_puts_counter().labels(str(dev)).inc()
            device.hold_matrix(key, held)
    return held


def _enqueue(matrix: np.ndarray, shards, n: int | None = None):
    """The door: out[r] = XOR_c matrix[r,c] x shards[c] enqueued on the
    device, and beside the device result the number of programs the call
    enqueued — 1 for the kernel, 1 more for a pad on the device.

    matrix: (rows, cols) uint8 host array. shards: the (cols, n) bytes,
    either a uint8 host array (or a sequence of cols (n,) rows), any n, or
    a uint32 device array of (cols, w) little-endian words, n = 4w: bytes
    cross the link as words, since the kernel reads them so. The result is
    uint32 words too, (rows, >= n/4 words) in some shape whose row-major
    bytes are the (rows, >= n) uint8 result (`_JaxHandle` views it so):
    dense on the device, so a copy back carries the result's own bytes.
    The program is one per (rows, cols) and width on a device; the matrix
    is its operand (`_operand`), put on that device once.

    The zero tail is written where the bytes are (zero bytes transform to
    zero bytes, so the result is exact, and the host drops it). A host
    array goes to the jitted program at a rung of the ladder
    (`zero_tailed`; none is written where the width is a rung's already,
    as `_apply_jax` hands it in) as a free view of words, and the program
    does its own transfer: no put and no pad. A device array is padded to
    a tile multiple for the Pallas body, which takes nothing else; the XLA
    body takes any width. Nothing here names a device: a device array's
    program runs where the array lies (the puts below commit it there), a
    host array's on jax's default device.

    `n` is the caller's width in bytes where a zero tail was written before
    the door (`_apply_jax`, a ragged `apply2d_async`); by default the
    array's own. Each call counts the caller's `cols x n` bytes and the
    zeros that brought them to the program's width under
    `SeaweedFS_volume_ec_door_bytes_total{part}`, and itself under
    `SeaweedFS_volume_ec_door_calls_total{tiles}` by the rung of that width
    (`"wide"` beyond the ladder): three updates a call."""
    body = transform_kernel()
    pallas = body == "pallas"
    tile = TILE
    rows, cols = matrix.shape
    fn = rs_pallas.compiled(rows, cols, tile) if pallas else _compiled_xla(rows, cols)
    dev = 0  # the default device's index: where a host array's program runs
    programs = 1
    if isinstance(shards, np.ndarray):
        n = shards.shape[1] if n is None else n
        words = zero_tailed(shards, tile).view(np.uint32)
    else:
        jax = device.jax()
        jnp = jax.numpy
        if shards.dtype != jnp.uint32:
            raise TypeError(
                f"a device array reaches the door as uint32 words, not {shards.dtype}")
        words = shards
        n = 4 * words.shape[1] if n is None else n
        # an array inside a caller's own jit lies nowhere yet
        if not isinstance(words, jax.core.Tracer):
            dev = next(iter(words.devices())).id
        # no named scope around the pad: scopes on the read path cost a
        # read a percent (PERF.md); the trace knows it as `jit__pad`
        w, step = words.shape[1], tile // 4
        if pallas and w % step:
            words = jnp.pad(words, ((0, 0), (0, (-w) % step)))
            programs = 2
    width = 4 * words.shape[1]
    device.note_kernel_shape(rows, cols, width, dev)
    door = trace.door_bytes_counter()
    door.labels("payload").inc(cols * n)
    door.labels("zero_tail").inc(cols * (width - n))
    rung = ladder_width(width, tile) // tile
    trace.door_calls_counter().labels(
        str(rung) if rung <= LADDER_TILES[-1] else "wide").inc()
    return fn(_operand(matrix, body, words, dev), words), programs


def gf_matmul_jax(matrix: np.ndarray, shards):
    """`_enqueue`'s device result alone, uint32 words, for callers outside
    `ops/` (tests: the codec and the pipelines go through `_dispatch`)."""
    return _enqueue(np.ascontiguousarray(matrix, dtype=np.uint8), shards)[0]


def _dispatch(matrix: np.ndarray, shards, n: int | None = None):
    """`_enqueue` as the codec calls it, with the host's seconds in the
    call counted under `dispatch` and the device programs it enqueued under
    `SeaweedFS_volume_ec_device_programs_total`: the kernel alone where
    `shards` is a host array (`_apply_jax`; the program does the transfer)
    or a device array of tile-multiple width; pad and kernel where a device
    array's width is not."""
    with trace.phase("rs.dispatch", trace.EC_DEVICE_SECONDS, "dispatch"):
        out, programs = _enqueue(matrix, shards, n)
    trace.device_programs_counter().inc(programs)
    return out


def _apply_jax(matrix: np.ndarray, rows, n: int | None = None) -> np.ndarray:
    """The transform of host bytes — a (cols, n) array or a sequence of cols
    (n,) arrays — as one device program and one copy back: the width is
    brought to a rung of the kernel's ladder (`LADDER_TILES`) on the host
    and taken back on the host, so nothing compiles per length. `n`, where
    given, is the caller's width in a (cols, `ladder_width(n)`) array whose
    zero tail the caller wrote (`RSCodec.input_width`)."""
    n = len(rows[0]) if n is None else n
    rows = zero_tailed(rows, TILE)
    return _JaxHandle(_dispatch(matrix, rows, n), n).result()


class RSCodec:
    """RS(data, parity) codec with pluggable execution backends.

    backend: "jax" (TPU/accelerator bit-plane matmul), "native" (C++ via
    ctypes), "numpy" (table oracle). Mirrors the reference's pluggable
    `Encoder` boundary from BASELINE.json (klauspost CPU vs TPU sidecar).

    device: the jax device the async pipeline API puts its batches on (a
    pipeline's, from `ops.device.lease`); None is jax's default device. The
    calls that take host arrays (`encode`, `reconstruct`, `apply_matrix`:
    the read path) name none and run on the default device either way.
    """

    def __init__(
        self,
        data_shards: int = DATA_SHARDS,
        parity_shards: int = PARITY_SHARDS,
        backend: str = "auto",
        device=None,
    ) -> None:
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.total_shards = data_shards + parity_shards
        self.device = device
        # "auto" resolves lazily on first use so constructing a codec (e.g.
        # opening an EcVolume that may never reconstruct) doesn't init JAX.
        self._backend = backend

    @property
    def backend(self) -> str:
        if self._backend == "auto":
            self._backend = self._pick_backend()
        return self._backend

    @property
    def kernel_label(self) -> str:
        """The word the kernel-span labels use for what runs the transform:
        "pallas" or "xla" for the jax backend, else the backend's name."""
        return transform_kernel() if self.backend == "jax" else self.backend

    @staticmethod
    def _pick_backend() -> str:
        try:
            if device.platform() != "cpu":
                return "jax"
        except Exception as e:  # noqa: BLE001 - jax start-up raises many types
            device.note_selection_failure("RSCodec: jax start", e)
        return "native" if _native_lib() is not None else "numpy"

    # --- core ---------------------------------------------------------------
    def input_width(self, n: int) -> int:
        """The width of the (cols, width) uint8 host array that
        `apply_matrix(..., n)` takes as it is for rows of n bytes: a rung of
        the kernel's ladder on the jax backend (`ladder_width`), n on the
        others. A caller that fills such an array itself, zero beyond
        column n, spares the door its copy (`EcVolume`'s survivor gather)."""
        return ladder_width(n, TILE) if self.backend == "jax" else n

    def apply_matrix(self, matrix: np.ndarray, shards: np.ndarray,
                     n: int | None = None) -> np.ndarray:
        """Public arbitrary-matrix transform: out[r] = XOR_c matrix[r,c] x
        shards[c] on this codec's backend. The partial-sum repair path
        (erasure_coding/decoder.py) scales a holder's local shards with
        exactly this call — the same kernel encode/reconstruct use. `n`:
        the rows' own width where `shards` is wider, zero beyond it
        (`input_width`); the result is (rows, n) either way."""
        return self._apply(
            np.ascontiguousarray(matrix, dtype=np.uint8),
            np.ascontiguousarray(shards, dtype=np.uint8),
            n,
        )

    def _apply(self, matrix: np.ndarray, shards: np.ndarray,
               n: int | None = None) -> np.ndarray:
        if self.backend == "jax":
            return _apply_jax(matrix, shards, n)
        if n is not None:
            shards = shards[:, :n]
        if self.backend == "native":
            from seaweedfs_tpu.native import lib

            data = np.ascontiguousarray(shards, dtype=np.uint8)
            return lib.gf256_matmul2d(matrix.tobytes(), data)
        return gf256.gf_matmul_bytes(matrix, shards)

    def encode(self, data: np.ndarray) -> np.ndarray:
        """data: (data_shards, n) uint8 -> parity (parity_shards, n) uint8."""
        if data.shape[0] != self.data_shards:
            raise ValueError(f"expected {self.data_shards} data shards")
        m = gf256.parity_rows(self.data_shards, self.parity_shards)
        return self._apply(m, np.ascontiguousarray(data, dtype=np.uint8))

    def encode_all(self, data: np.ndarray) -> np.ndarray:
        """(data_shards, n) -> all (total, n) shards (data rows pass through)."""
        parity = self.encode(data)
        return np.concatenate([np.asarray(data, dtype=np.uint8), parity], axis=0)

    def reconstruct(
        self, shards: dict[int, np.ndarray], targets: list[int] | None = None
    ) -> dict[int, np.ndarray]:
        """Recover missing shards. shards: {shard_id: (n,) uint8} with at
        least data_shards present; targets default to all missing ids."""
        present = sorted(shards)
        if targets is None:
            targets = [i for i in range(self.total_shards) if i not in shards]
        if not targets:
            return {}
        m = gf256.decode_matrix(
            self.data_shards, self.parity_shards, tuple(present), tuple(targets)
        )
        rows = [
            np.asarray(shards[i], dtype=np.uint8)
            for i in present[: self.data_shards]
        ]
        if self.backend == "jax":  # stacks them itself, with a zero tail
            out = _apply_jax(m, rows)
        else:
            out = self._apply(m, np.stack(rows))
        return {t: out[i] for i, t in enumerate(targets)}

    def verify(self, shards: np.ndarray) -> bool:
        """shards: (total, n); recompute parity from data rows and compare."""
        parity = self.encode(shards[: self.data_shards])
        return bool(np.array_equal(parity, shards[self.data_shards :]))

    # --- async pipeline API --------------------------------------------------
    # The EC encode/rebuild pipeline (storage/erasure_coding/encoder.py)
    # overlaps disk reads, the GF transform, and shard writeback. submit
    # returns immediately for the jax backend (device transfers + kernel are
    # dispatched async, and the result's copy back starts behind them);
    # handle.result() blocks until host bytes are ready.

    def apply2d_async(self, matrix: np.ndarray, data: np.ndarray):
        """data: C-contiguous (cols, n) uint8. Handle yields (rows, n)."""
        if self.backend == "jax":
            n = data.shape[1]
            if n % 4:  # the link carries words: a ragged width gets a tail
                data = np.pad(data, ((0, 0), (0, -n % 4)))
            return _JaxHandle(
                _dispatch(matrix, _device_put_2d(data.view(np.uint32), self.device), n),
                n, early=True,
            )
        if self.backend == "native":
            from seaweedfs_tpu.native import lib

            return _ReadyHandle(lib.gf256_matmul2d(matrix.tobytes(), data))
        return _ReadyHandle(gf256.gf_matmul_bytes(matrix, data))

    def encode2d_async(self, data: np.ndarray):
        m = gf256.parity_rows(self.data_shards, self.parity_shards)
        return self.apply2d_async(m, data)

    def encode_rows_async(self, buf: np.ndarray, block: int, row_count: int):
        """buf: flat uint8 of row_count rows x (data_shards * block) bytes in
        .dat order. Handle yields parity (parity_shards, row_count*block)
        with row r's parity in columns [r*block, (r+1)*block) — i.e. exactly
        the bytes each parity shard file appends for those rows."""
        m = gf256.parity_rows(self.data_shards, self.parity_shards)
        if self.backend == "jax" and block % 4:
            # a block that words do not divide is transposed on the host
            x = buf.reshape(row_count, self.data_shards, block).transpose(1, 0, 2)
            return self.apply2d_async(m, np.ascontiguousarray(x).reshape(
                self.data_shards, -1))
        if self.backend == "jax":
            jax = device.jax()
            jnp = jax.numpy
            x = _device_put_1d(buf.view(np.uint32), self.device)
            with jax.named_scope("rs.rows_transpose"):
                x = x.reshape(row_count, self.data_shards, block // 4)
                x = jnp.transpose(x, (1, 0, 2)).reshape(self.data_shards, -1)
            return _JaxHandle(_dispatch(m, x), row_count * block, early=True)
        if self.backend == "native":
            from seaweedfs_tpu.native import lib

            return _ReadyHandle(
                lib.gf256_encode_rows(
                    m.tobytes(), self.parity_shards, self.data_shards,
                    buf, block, row_count,
                )
            )
        x = buf.reshape(row_count, self.data_shards, block)
        x = np.ascontiguousarray(x.transpose(1, 0, 2)).reshape(
            self.data_shards, -1
        )
        return _ReadyHandle(gf256.gf_matmul_bytes(m, x))


class _ReadyHandle:
    def __init__(self, out: np.ndarray) -> None:
        self._out = out

    def result(self) -> np.ndarray:
        return self._out


class _JaxHandle:
    def __init__(self, dev, n: int, early: bool = False) -> None:
        # uint32 words whose row-major bytes are (rows, >= n) uint8: wider
        # by the zero tail of the input
        self._dev = dev
        self._n = n
        # `early`: the copy back is enqueued now, behind the program, for a
        # caller that drains later (the async pipeline API); otherwise it
        # begins in `result()`, as for a caller that waits at once
        self._early = early
        if early:
            dev.copy_to_host_async()

    def result(self) -> np.ndarray:
        """The host's copy, (rows, n) uint8: blocks until the device has
        drained what was enqueued before it and the bytes have come back
        (`d2h-wait`, whose bytes are the ones that crossed), then views the
        words as bytes and drops the tail's columns, with no copy. Counted
        once under `SeaweedFS_volume_ec_device_copies_total{start}` by when
        the copy began, and under
        `SeaweedFS_volume_ec_device_copy_bytes_total{side}` by the bytes the
        result held on the device and the bytes that arrived."""
        with trace.phase(
            "rs.d2h_wait", trace.EC_DEVICE_SECONDS, "d2h-wait"
        ) as ph:
            out = np.asarray(self._dev)
            ph.nbytes = out.nbytes
        trace.device_copies_counter().labels(
            "enqueue" if self._early else "wait").inc()
        copied = trace.device_copy_bytes_counter()
        copied.labels("device").inc(self._dev.on_device_size_in_bytes())
        copied.labels("host").inc(out.nbytes)
        return out.view(np.uint8).reshape(out.shape[0], -1)[:, : self._n]


# Host arrays above this size are put on the device in pieces of this size
# and concatenated there.
H2D_CHUNK = 4 * 1024 * 1024


def _device_put_1d(buf: np.ndarray, dev=None):
    """`buf` on `dev` (None: jax's default device), flat. The concat, and
    whatever is computed from its result, runs where the pieces lie."""
    jax = device.jax()
    jnp = jax.numpy
    flat = buf.reshape(-1)
    step = H2D_CHUNK // flat.itemsize
    # `h2d`: the host's seconds in the puts and in dispatching the concat
    with trace.phase("rs.h2d", trace.EC_DEVICE_SECONDS, "h2d", flat.nbytes):
        if flat.nbytes <= H2D_CHUNK:
            return jax.device_put(flat, dev)
        pieces = [
            jax.device_put(flat[i : i + step], dev)
            for i in range(0, flat.size, step)
        ]
        with jax.named_scope("rs.h2d_concat"):
            return jnp.concatenate(pieces)


def _device_put_2d(data: np.ndarray, dev=None):
    if data.nbytes <= H2D_CHUNK:
        with trace.phase("rs.h2d", trace.EC_DEVICE_SECONDS, "h2d", data.nbytes):
            return device.jax().device_put(data, dev)
    return _device_put_1d(data, dev).reshape(data.shape)


def _native_lib():
    from seaweedfs_tpu.native import lib

    return lib


_PIPELINE_LOCK = threading.Lock()
# How the process-wide pipeline backend was chosen; filled by the first
# pick_pipeline_backend() call that has to choose. See
# pipeline_backend_report().
_PIPELINE_CHOICE: dict = {}


def pick_pipeline_backend(codec: RSCodec | None = None) -> str:
    """Choose the EC pipeline execution backend by measured END-TO-END rate
    (host bytes in -> host bytes out), not peak kernel FLOPs: one encode of
    2 MiB per shard per candidate, the faster one wins. Which one that is
    on a given machine is in pipeline_backend_report().
    Override: SEAWEEDFS_TPU_EC_BACKEND."""
    if codec is not None and codec._backend != "auto":
        return codec._backend
    env = os.environ.get("SEAWEEDFS_TPU_EC_BACKEND", "")
    if env:
        return env
    # one calibration per process: a boot-time warmer and the first encode
    # RPC must not benchmark kernels concurrently
    with _PIPELINE_LOCK:
        if not _PIPELINE_CHOICE:
            _PIPELINE_CHOICE.update(_calibrate_pipeline_backend())
        return _PIPELINE_CHOICE["backend"]


def pipeline_backend_report() -> dict:
    """The pipeline backend in force and how it was chosen, without
    choosing: {"backend", "chosen_by": "override" | "calibration" |
    "only-candidate", "rates_bytes_per_s": {candidate: rate},
    "h2d_bytes_per_s"}; {"backend": None, "chosen_by": "not-yet"} before
    the first call that had to choose."""
    env = os.environ.get("SEAWEEDFS_TPU_EC_BACKEND", "")
    if env:
        return {"backend": env, "chosen_by": "override"}
    with _PIPELINE_LOCK:
        if _PIPELINE_CHOICE:
            return dict(_PIPELINE_CHOICE)
    return {"backend": None, "chosen_by": "not-yet"}


def _calibrate_pipeline_backend() -> dict:
    candidates: list[str] = []
    try:
        if device.platform() != "cpu":
            candidates.append("jax")
    except Exception as e:  # noqa: BLE001 - jax start-up raises many types
        device.note_selection_failure("ec pipeline: jax start", e)
    if _native_lib() is not None:
        candidates.append("native")
    if len(candidates) < 2:
        return {
            "backend": candidates[0] if candidates else "numpy",
            "chosen_by": "only-candidate",
        }

    rng = np.random.RandomState(0)
    sample = rng.randint(0, 256, size=(DATA_SHARDS, 2 * 1024 * 1024)).astype(
        np.uint8
    )
    rates: dict[str, float] = {}
    for name in candidates:
        c = RSCodec(backend=name)
        c.encode2d_async(sample).result()  # warm (jit compile / table init)
        t0 = time.perf_counter()
        c.encode2d_async(sample).result()
        rates[name] = sample.nbytes / (time.perf_counter() - t0)
    # the host->device half alone, as the pipeline puts it: words (warm:
    # the jax candidate above already compiled the device concat)
    t0 = time.perf_counter()
    _device_put_2d(sample.view(np.uint32)).block_until_ready()
    h2d_rate = sample.nbytes / (time.perf_counter() - t0)
    return {
        "backend": max(rates, key=rates.get),
        "chosen_by": "calibration",
        "rates_bytes_per_s": rates,
        "h2d_bytes_per_s": h2d_rate,
    }
