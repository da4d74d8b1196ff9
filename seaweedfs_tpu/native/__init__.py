"""Native C++ CPU kernels loaded via ctypes.

The reference leaned on assembly inside Go libraries for its hot paths
(klauspost/reedsolomon AVX2 GF(2^8), stdlib SSE4.2 CRC32C, asm MD5 —
SURVEY.md §2.2). Here those CPU paths are C++ (`seaweedfs_tpu/native/src`),
compiled on first use into `_seaweed_native.<key>.so` and exposed through
ctypes. The key names the sources' content and the machine the file was
built on (`_build_key`), so a file built from other sources or for another
CPU is never loaded: the library is built where it runs. They serve as (a)
the CPU path when no TPU is attached and (b) the baseline the TPU kernels
are measured against.

If the build or the load fails, `lib` is None, the cause is logged and kept
in `load_info`, and callers use the numpy paths — correctness is preserved,
only throughput drops.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src")

_lock = threading.Lock()


class NativeLib:
    def __init__(self, cdll: ctypes.CDLL) -> None:
        self._lib = cdll
        self._lib.sw_crc32c_update.restype = ctypes.c_uint32
        self._lib.sw_crc32c_update.argtypes = [
            ctypes.c_uint32,
            ctypes.c_char_p,
            ctypes.c_size_t,
        ]
        self._lib.sw_gf256_matmul.restype = None
        self._lib.sw_gf256_matmul.argtypes = [
            ctypes.c_char_p,  # matrix rows*cols
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_char_p),  # input shard pointers [cols]
            ctypes.POINTER(ctypes.c_char_p),  # output shard pointers [rows]
            ctypes.c_size_t,  # shard length
        ]
        self._lib.sw_md5_batch.restype = None
        self._lib.sw_md5_batch.argtypes = [
            ctypes.c_void_p,  # blobs (accepts bytes or a numpy data pointer)
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_void_p,
        ]
        self._lib.sw_gear_boundaries.restype = ctypes.c_size_t
        self._lib.sw_gear_boundaries.argtypes = [
            ctypes.c_void_p,  # data
            ctypes.c_size_t,
            ctypes.c_void_p,  # gear table uint32[256]
            ctypes.c_uint32,  # mask
            ctypes.c_size_t,  # min_size
            ctypes.c_size_t,  # max_size
            ctypes.c_void_p,  # out cuts uint64[max_cuts]
            ctypes.c_size_t,
        ]
        self._lib.sw_crc32c_batch.restype = None
        self._lib.sw_crc32c_batch.argtypes = [
            ctypes.c_void_p,  # blobs (n * blob_len contiguous)
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_void_p,  # out uint32[n]
        ]
        self._lib.sw_md5_batch_var.restype = None
        self._lib.sw_md5_batch_var.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),  # blob pointers [n]
            ctypes.POINTER(ctypes.c_size_t),  # lengths [n]
            ctypes.c_size_t,
            ctypes.c_void_p,  # out (n, 16)
        ]
        self._lib.sw_crc32c_batch_var.restype = None
        self._lib.sw_crc32c_batch_var.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.c_size_t,
            ctypes.c_void_p,  # out uint32[n]
        ]
        self._lib.sw_md5_batch_spans.restype = None
        self._lib.sw_md5_batch_spans.argtypes = [
            ctypes.c_void_p,  # base buffer
            ctypes.c_void_p,  # offs size_t[n]
            ctypes.c_void_p,  # lens size_t[n]
            ctypes.c_size_t,
            ctypes.c_void_p,  # out (n, 16)
        ]
        self._lib.sw_crc32c_batch_spans.restype = None
        self._lib.sw_crc32c_batch_spans.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,  # out uint32[n]
        ]
        self._lib.sw_fast128.restype = None
        self._lib.sw_fast128.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_char_p,
            ctypes.c_void_p,
        ]
        self._lib.sw_fast128_spans.restype = None
        self._lib.sw_fast128_spans.argtypes = [
            ctypes.c_void_p,  # base buffer
            ctypes.c_void_p,  # cuts size_t[n] (exclusive ends)
            ctypes.c_size_t,
            ctypes.c_char_p,  # 16-byte seed or None
            ctypes.c_void_p,  # out (n, 16)
        ]
        self._lib.sw_gf256_matmul2d.restype = None
        self._lib.sw_gf256_matmul2d.argtypes = [
            ctypes.c_char_p,  # matrix rows*cols
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_void_p,  # in (cols, n) row-major
            ctypes.c_void_p,  # out (rows, n) row-major
            ctypes.c_size_t,
        ]
        self._lib.sw_gf256_has_gfni.restype = ctypes.c_int
        self._lib.sw_gf256_has_gfni.argtypes = []
        self._lib.sw_gf256_set_gfni.restype = ctypes.c_int
        self._lib.sw_gf256_set_gfni.argtypes = [ctypes.c_int]
        self._lib.sw_ec_encode_volume.restype = ctypes.c_longlong
        self._lib.sw_ec_encode_volume.argtypes = [
            ctypes.c_char_p,  # matrix rows*cols
            ctypes.c_int,  # parity rows
            ctypes.c_int,  # data cols
            ctypes.c_int,  # dat fd
            ctypes.c_ulonglong,  # total .dat bytes
            ctypes.POINTER(ctypes.c_int),  # shard fds [cols+rows]
            ctypes.c_ulonglong,  # shard size
            ctypes.c_ulonglong,  # large block
            ctypes.c_ulonglong,  # small block
        ]
        self._lib.sw_gf256_matmul_fds.restype = ctypes.c_longlong
        self._lib.sw_gf256_matmul_fds.argtypes = [
            ctypes.c_char_p,  # matrix rows*cols
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_int),  # input shard fds [cols]
            ctypes.c_ulonglong,  # bytes per shard
            ctypes.POINTER(ctypes.c_int),  # output shard fds [rows]
        ]
        self._lib.sw_gf256_encode_rows.restype = None
        self._lib.sw_gf256_encode_rows.argtypes = [
            ctypes.c_char_p,  # matrix rows*cols
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_void_p,  # in: row_count rows of cols*block bytes
            ctypes.c_size_t,  # block
            ctypes.c_int,  # row_count
            ctypes.c_void_p,  # out (rows, row_count*block)
        ]
        self._lib.sw_loadgen_assign_write.restype = ctypes.c_int
        self._lib.sw_loadgen_assign_write.argtypes = [
            ctypes.c_char_p,  # master host
            ctypes.c_int,  # master port
            ctypes.c_int,  # concurrent slots
            ctypes.c_size_t,  # files
            ctypes.c_char_p,  # assign path
            ctypes.c_char_p,  # body
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_ulonglong),
        ]
        self._lib.sw_loadgen.restype = ctypes.c_int
        self._lib.sw_loadgen.argtypes = [
            ctypes.c_char_p,  # host
            ctypes.c_int,  # port
            ctypes.c_int,  # concurrent keep-alive conns
            ctypes.c_char_p,  # method
            ctypes.c_char_p,  # \0-joined paths
            ctypes.c_size_t,  # path count
            ctypes.c_char_p,  # body (POST)
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_ulonglong),  # out: ok, err, ns
        ]

    def has(self, _name: str) -> bool:
        return True

    def crc32c_update(self, crc: int, data) -> int:
        if not isinstance(data, bytes):
            data = bytes(data)
        return int(self._lib.sw_crc32c_update(crc & 0xFFFFFFFF, data, len(data)))

    def gf256_matmul(self, matrix: bytes, rows: int, cols: int, inputs, out_len: int):
        """matrix is rows*cols GF(2^8) coefficients; inputs is a list of
        `cols` byte strings of length out_len; returns list of `rows` outputs."""
        in_arr = (ctypes.c_char_p * cols)(*[bytes(x) for x in inputs])
        outs = [ctypes.create_string_buffer(out_len) for _ in range(rows)]
        out_arr = (ctypes.c_char_p * rows)(
            *[ctypes.cast(o, ctypes.c_char_p) for o in outs]
        )
        self._lib.sw_gf256_matmul(matrix, rows, cols, in_arr, out_arr, out_len)
        return [o.raw for o in outs]

    def gf256_matmul2d(self, matrix: bytes, data, out=None):
        """Zero-copy variant: data is a C-contiguous uint8 numpy array
        (cols, n); writes/returns (rows, n). No per-shard byte copies —
        this is the pipeline hot path (ctypes releases the GIL)."""
        import numpy as np

        rows = len(matrix) // data.shape[0]
        cols, n = data.shape
        if out is None:
            out = np.empty((rows, n), dtype=np.uint8)
        self._lib.sw_gf256_matmul2d(
            matrix, rows, cols,
            data.ctypes.data, out.ctypes.data, n,
        )
        return out

    def gf256_encode_rows(self, matrix: bytes, parity: int, cols: int,
                          buf, block: int, row_count: int, out=None):
        """Row-batched encode (see sw_gf256_encode_rows). buf is a
        C-contiguous uint8 array of row_count*cols*block bytes; returns
        (parity, row_count*block) uint8."""
        import numpy as np

        if out is None:
            out = np.empty((parity, row_count * block), dtype=np.uint8)
        self._lib.sw_gf256_encode_rows(
            matrix, parity, cols, buf.ctypes.data, block, row_count,
            out.ctypes.data,
        )
        return out

    def ec_encode_volume(self, matrix: bytes, parity: int, cols: int,
                         dat_fd: int, total: int, shard_fds, shard_size: int,
                         large_block: int, small_block: int) -> int:
        """Whole-volume fused encode (see sw_ec_encode_volume): mmap'd .dat
        -> GFNI -> NT-stores into the (pre-truncated) mmap'd shard files.
        One GIL-released call; returns 0 on success, <0 => caller falls back
        to the staged pipeline."""
        fds = (ctypes.c_int * len(shard_fds))(*shard_fds)
        return int(self._lib.sw_ec_encode_volume(
            matrix, parity, cols, dat_fd, total, fds, shard_size,
            large_block, small_block,
        ))

    def gf256_matmul_fds(self, matrix: bytes, rows: int, cols: int,
                         in_fds, n: int, out_fds) -> int:
        """Fused matmul with fd-mmapped inputs/outputs (rebuild/decode hot
        path). Returns 0 on success, <0 => caller falls back."""
        ifds = (ctypes.c_int * cols)(*in_fds)
        ofds = (ctypes.c_int * rows)(*out_fds)
        return int(self._lib.sw_gf256_matmul_fds(matrix, rows, cols, ifds, n, ofds))

    def has_gfni(self) -> bool:
        return bool(self._lib.sw_gf256_has_gfni())

    def set_gfni(self, enabled: bool) -> bool:
        return bool(self._lib.sw_gf256_set_gfni(1 if enabled else 0))

    def md5_batch(self, blobs: bytes, n: int, blob_len: int) -> bytes:
        out = ctypes.create_string_buffer(n * 16)
        self._lib.sw_md5_batch(blobs, n, blob_len, ctypes.cast(out, ctypes.c_char_p))
        return out.raw

    def md5_batch_np(self, blobs, n: int, blob_len: int):
        """Zero-copy batch MD5: blobs is a C-contiguous uint8 numpy array
        (n, blob_len); returns (n, 16) uint8."""
        import numpy as np

        out = np.empty((n, 16), dtype=np.uint8)
        self._lib.sw_md5_batch(blobs.ctypes.data, n, blob_len, out.ctypes.data)
        return out

    def md5_crc_batch_var(self, blobs: list) -> tuple:
        """Variable-length batch MD5+CRC32C: blobs is a list of bytes
        objects (zero-copy pointers). Hash the batch LENGTH-SORTED for full
        lane utilization, returning results in the caller's order.
        Returns ((n, 16) uint8 digests, (n,) uint32 crcs)."""
        import numpy as np

        n = len(blobs)
        order = sorted(range(n), key=lambda i: -len(blobs[i]))
        ptrs = (ctypes.c_char_p * n)(*[blobs[i] for i in order])
        lens = (ctypes.c_size_t * n)(*[len(blobs[i]) for i in order])
        dig_s = np.empty((n, 16), dtype=np.uint8)
        crc_s = np.empty(n, dtype=np.uint32)
        self._lib.sw_md5_batch_var(ptrs, lens, n, dig_s.ctypes.data)
        self._lib.sw_crc32c_batch_var(ptrs, lens, n, crc_s.ctypes.data)
        digests = np.empty_like(dig_s)
        crcs = np.empty_like(crc_s)
        digests[order] = dig_s
        crcs[order] = crc_s
        return digests, crcs

    def md5_crc_batch_spans(self, buf, cuts) -> tuple:
        """Zero-copy span hashing: buf is one contiguous uint8 buffer (numpy
        array or bytes), cuts the CDC exclusive chunk ends. No per-chunk
        Python slices — the C side length-sorts and runs the lockstep
        kernels. Returns ((n, 16) uint8 digests, (n,) uint32 crcs)."""
        import numpy as np

        arr = np.frombuffer(buf, dtype=np.uint8) if not isinstance(
            buf, np.ndarray
        ) else buf
        ends = np.asarray(cuts, dtype=np.uintp)
        offs = np.empty_like(ends)
        offs[0] = 0
        offs[1:] = ends[:-1]
        lens = ends - offs
        n = len(ends)
        digests = np.empty((n, 16), dtype=np.uint8)
        crcs = np.empty(n, dtype=np.uint32)
        self._lib.sw_md5_batch_spans(
            arr.ctypes.data, offs.ctypes.data, lens.ctypes.data, n,
            digests.ctypes.data,
        )
        self._lib.sw_crc32c_batch_spans(
            arr.ctypes.data, offs.ctypes.data, lens.ctypes.data, n,
            crcs.ctypes.data,
        )
        return digests, crcs

    def md5_spans(self, buf, offs, lens):
        """MD5 of arbitrary (offset, length) spans of one buffer — the
        dedup path hashes ONLY the chunks that missed the index (their
        upload ETags); identity keys come from fast128_spans."""
        import numpy as np

        arr = np.frombuffer(buf, dtype=np.uint8) if not isinstance(
            buf, np.ndarray
        ) else buf
        o = np.asarray(offs, dtype=np.uintp)
        l = np.asarray(lens, dtype=np.uintp)
        n = len(o)
        digests = np.empty((n, 16), dtype=np.uint8)
        self._lib.sw_md5_batch_spans(
            arr.ctypes.data, o.ctypes.data, l.ctypes.data, n,
            digests.ctypes.data,
        )
        return digests

    def fast128(self, data: bytes, seed: bytes = b"") -> bytes:
        """SW128 of one buffer (16 bytes) — the dedup identity hash.
        seed: per-store 16-byte secret (defends against offline collision
        construction); empty = the unseeded golden form."""
        import numpy as np

        arr = np.frombuffer(data, dtype=np.uint8) if not isinstance(
            data, np.ndarray) else data
        out = np.empty(16, dtype=np.uint8)
        self._lib.sw_fast128(arr.ctypes.data, arr.nbytes, seed or None,
                             out.ctypes.data)
        return out.tobytes()

    def fast128_spans(self, buf, cuts, seed: bytes = b""):
        """SW128 per CDC span of one contiguous buffer (cuts = exclusive
        ends). Returns (n, 16) uint8 — the dedup index identity keys,
        ~2.5x cheaper than the MD5 span batch (ops/hash_service.span_keys)."""
        import numpy as np

        arr = np.frombuffer(buf, dtype=np.uint8) if not isinstance(
            buf, np.ndarray
        ) else buf
        ends = np.asarray(cuts, dtype=np.uintp)
        n = len(ends)
        out = np.empty((n, 16), dtype=np.uint8)
        self._lib.sw_fast128_spans(
            arr.ctypes.data, ends.ctypes.data, n, seed or None,
            out.ctypes.data,
        )
        return out

    def gear_boundaries(self, data, gear, mask: int, min_size: int,
                        max_size: int):
        """Serial gear-CDC cut positions. data: uint8 numpy array; gear:
        uint32[256] numpy. Returns a uint64 numpy array of exclusive ends."""
        import numpy as np

        max_cuts = max(16, len(data) // max(min_size, 1) + 2)
        cuts = np.empty(max_cuts, dtype=np.uint64)
        n = self._lib.sw_gear_boundaries(
            data.ctypes.data, len(data), gear.ctypes.data, mask,
            min_size, max_size, cuts.ctypes.data, max_cuts,
        )
        return cuts[:n]

    def loadgen(self, host: str, port: int, conns: int, method: str,
                paths: list, body: bytes | None = None) -> dict:
        """Drive an HTTP server with keep-alive connections from native code
        (one epoll thread, no GIL in the request loop). Returns ok/err
        counts and req/s — the measuring stick for the fastlane engine."""
        blob = b"".join(
            (p if isinstance(p, bytes) else p.encode()) + b"\0" for p in paths
        )
        out = (ctypes.c_ulonglong * 3)()
        rc = self._lib.sw_loadgen(
            host.encode(), port, conns, method.encode(), blob, len(paths),
            body, len(body) if body else 0, out,
        )
        secs = out[2] / 1e9 if out[2] else 1.0
        result = {
            "ok": int(out[0]),
            "errors": int(out[1]),  # C side accounts every unfinished path
            "seconds": round(secs, 3),
            "req_per_sec": round(out[0] / secs, 1),
        }
        if rc != 0:
            result["error"] = f"sw_loadgen rc={rc} (connect failure)"
        return result

    def loadgen_assign_write(self, host: str, master_port: int, conns: int,
                             files: int, body: bytes,
                             assign_path: str = "/dir/assign") -> dict:
        """Per-file assign -> write load (`weed benchmark` write semantics:
        every file pays a master round-trip for its fid, then a volume
        POST)."""
        out = (ctypes.c_ulonglong * 3)()
        rc = self._lib.sw_loadgen_assign_write(
            host.encode(), master_port, conns, files, assign_path.encode(),
            body, len(body), out,
        )
        secs = out[2] / 1e9 if out[2] else 1.0
        result = {
            "ok": int(out[0]),
            "errors": int(out[1]),
            "seconds": round(secs, 3),
            "req_per_sec": round(out[0] / secs, 1),
        }
        if rc != 0:
            result["error"] = f"rc={rc} (connect failure)"
        return result

    def crc32c_batch(self, blobs, n: int, blob_len: int):
        """blobs: C-contiguous uint8 numpy array (n, blob_len) — zero-copy;
        returns (n,) uint32."""
        import numpy as np

        out = np.empty(n, dtype=np.uint32)
        self._lib.sw_crc32c_batch(
            blobs.ctypes.data, n, blob_len, out.ctypes.data
        )
        return out


def _build_key() -> str:
    """Names the one built file this checkout may load on this machine: a
    digest of every source's name and content, the compiler's version and —
    because the build uses -march=native — this CPU's feature flags."""
    h = hashlib.sha256()
    for name in _sources():
        h.update(name.encode() + b"\0")
        with open(os.path.join(_SRC, name), "rb") as f:
            h.update(f.read())
    try:
        h.update(subprocess.run(
            ["g++", "-dumpfullversion", "-dumpmachine"],
            capture_output=True, timeout=30,
        ).stdout)
    except (OSError, subprocess.SubprocessError):
        pass  # no compiler: the build below reports it
    h.update(platform.machine().encode())
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    h.update(line.encode())
                    break
    except OSError:
        pass
    return h.hexdigest()[:16]


def _sources() -> list[str]:
    return sorted(f for f in os.listdir(_SRC) if f.endswith(".cpp"))


def _build(so_path: str) -> str | None:
    """Compile every source into so_path (written under a temporary name,
    renamed into place). Returns None, or why it failed."""
    tmp = f"{so_path}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-march=native", "-shared", "-fPIC", "-std=c++17",
        "-o", tmp, *(os.path.join(_SRC, f) for f in _sources()),
    ]
    error = None
    for attempt in (cmd, [a for a in cmd if a != "-march=native"]):
        try:
            subprocess.run(attempt, check=True, capture_output=True, timeout=300)
            os.replace(tmp, so_path)
            return None
        except subprocess.CalledProcessError as e:
            error = f"g++ exit {e.returncode}: {e.stderr.decode(errors='replace')[-400:]}"
        except (OSError, subprocess.SubprocessError) as e:
            error = f"{type(e).__name__}: {e}"
    return error


# What happened when the library was loaded: the built file, whether this
# process built it, and why the build or the load failed (None if neither).
load_info: dict = {"path": None, "built_here": False, "error": None}


def _load() -> NativeLib | None:
    from seaweedfs_tpu.util import glog

    with _lock:
        so_path = os.path.join(_HERE, f"_seaweed_native.{_build_key()}.so")
        load_info["path"] = so_path
        if not os.path.exists(so_path):
            error = _build(so_path)
            if error is not None:
                load_info["error"] = "build failed: " + error
                glog.warning("native library %s", load_info["error"])
                return None
            load_info["built_here"] = True
            for name in os.listdir(_HERE):  # built for other sources or CPUs
                if name.startswith("_seaweed_native") and name.endswith(".so") \
                        and os.path.join(_HERE, name) != so_path:
                    try:
                        os.unlink(os.path.join(_HERE, name))
                    except OSError:
                        pass
        try:
            return NativeLib(ctypes.CDLL(so_path))
        except (OSError, AttributeError) as e:
            load_info["error"] = f"load failed: {type(e).__name__}: {e}"
            glog.warning("native library %s", load_info["error"])
            return None


lib: NativeLib | None = None
if os.environ.get("SEAWEEDFS_TPU_DISABLE_NATIVE") != "1":
    lib = _load()
