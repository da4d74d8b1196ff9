"""EcVolume: serve reads/deletes from striped shard files.

Behavioral port of `weed/storage/erasure_coding/ec_volume.go` and the local
half of `weed/storage/store_ec.go`: needle lookup by binary search over the
sorted .ecx, interval math to shard reads, on-miss interval reconstruction
from any >= 10 surviving shards (the TPU codec does the GF math), and
deletion via .ecx tombstone + .ecj journal append.

All file access uses positional os.pread/os.pwrite (the reference uses
ReadAt), so concurrent reads and read+delete are safe.
"""

from __future__ import annotations

import os
import threading

import numpy as np

from seaweedfs_tpu.ops.rs_kernel import RSCodec
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.storage import idx as idx_mod
from seaweedfs_tpu.storage.needle import Needle, get_actual_size
from seaweedfs_tpu.storage.types import (
    NEEDLE_ID_SIZE,
    NEEDLE_MAP_ENTRY_SIZE,
    OFFSET_SIZE,
    TOMBSTONE_FILE_SIZE,
    put_u32,
    put_u64,
    size_is_deleted,
    size_to_u32,
)

from . import encoder
from .geometry import (
    DATA_SHARDS_COUNT,
    LARGE_BLOCK_SIZE,
    SMALL_BLOCK_SIZE,
    TOTAL_SHARDS_COUNT,
    Interval,
    locate_data,
    to_ext,
)


from seaweedfs_tpu.storage.volume import NotFound, degraded_reads_counter
from seaweedfs_tpu.util import faults


class NeedleNotFound(NotFound):
    pass


def _emit_degraded(volume_id: int, missing_shard: int, via: str,
                   collection: str = "") -> None:
    """Journal a sealed-EC reconstruction into the flight recorder
    (cold path — only runs when a shard read already failed)."""
    from seaweedfs_tpu.stats import events as events_mod

    events_mod.emit("degraded_read", volume=volume_id,
                    reason="ec_reconstruct", shard=missing_shard, via=via,
                    collection=collection or "default")


# sealed-shard pread seam: error/latency here exercises the local ->
# remote -> reconstruct ladder below (an injected local-read failure
# must degrade into reconstruction, not a 500)
_FP_SHARD_READ = faults.register("volume.ec.shard.read")


def ec_shard_file_name(collection: str, dir_: str, vid: int) -> str:
    base = f"{collection}_{vid}" if collection else str(vid)
    return os.path.join(dir_, base)


class EcVolume:
    def __init__(
        self,
        dir_: str,
        collection: str,
        volume_id: int,
        dir_idx: str | None = None,
        codec: RSCodec | None = None,
        large_block_size: int = LARGE_BLOCK_SIZE,
        small_block_size: int = SMALL_BLOCK_SIZE,
    ) -> None:
        self.dir = dir_
        self.dir_idx = dir_idx or dir_
        self.collection = collection
        self.volume_id = volume_id
        self.codec = codec or RSCodec()
        self.large_block_size = large_block_size
        self.small_block_size = small_block_size
        self._ecj_lock = threading.Lock()

        # optional remote sourcing hooks, set by the server layer:
        # shard_fetcher(shard_id, offset, size) -> bytes | None mirrors the
        # remote half of `store_ec.go` (readRemoteEcShardInterval);
        # partial_fetcher(missing_shard, offset, size) -> bytes | None
        # reconstructs an interval moving ONE coefficient-scaled partial
        # per remote holder (repair-bandwidth-optimal fan-in) instead of
        # one full range per shard.
        self.shard_fetcher = None
        self.partial_fetcher = None

        self._closed = False
        self.data_base = ec_shard_file_name(collection, self.dir, volume_id)
        self.index_base = ec_shard_file_name(collection, self.dir_idx, volume_id)
        if not os.path.exists(self.index_base + ".ecx"):
            raise FileNotFoundError(self.index_base + ".ecx")
        self._ecx_fd = os.open(self.index_base + ".ecx", os.O_RDWR)
        self.ecx_file_size = os.path.getsize(self.index_base + ".ecx")
        self.ecj_path = self.index_base + ".ecj"
        if not os.path.exists(self.ecj_path):
            open(self.ecj_path, "wb").close()

        info = encoder.load_volume_info(self.data_base + ".vif")
        self.version = int(info.get("version", 3)) or 3
        if not info:
            encoder.save_volume_info(self.data_base + ".vif", version=self.version)
        # online-encoded volumes stripe with a uniform (recorded) block
        # geometry; the .vif is authoritative over the constructor
        # defaults so sealed online shards read correctly everywhere
        # (mount, rebuild source, remote shard fetch)
        if "large_block_size" in info:
            self.large_block_size = int(info["large_block_size"])
        if "small_block_size" in info:
            self.small_block_size = int(info["small_block_size"])

        # local shard fds
        self.shards: dict[int, int] = {}
        self.shard_size = 0
        for shard_id in range(TOTAL_SHARDS_COUNT):
            p = self.data_base + to_ext(shard_id)
            if os.path.exists(p):
                self.shards[shard_id] = os.open(p, os.O_RDONLY)
                self.shard_size = max(self.shard_size, os.path.getsize(p))

    def close(self) -> None:
        # idempotent: an atomic remount defers the old instance's close
        # on a timer, which can race the store's shutdown close
        if self._closed:
            return
        self._closed = True
        os.close(self._ecx_fd)
        for fd in self.shards.values():
            os.close(fd)
        self.shards.clear()

    # --- index ----------------------------------------------------------------
    def find_needle_from_ecx(self, needle_id: int) -> tuple[int, int]:
        """Binary search the sorted .ecx (`ec_volume.go:236-263`).
        Returns (offset, size); raises NeedleNotFound."""
        found, _, offset, size = self._search(needle_id)
        if not found:
            raise NeedleNotFound(f"needle {needle_id:x}")
        return offset, size

    def _search(self, needle_id: int) -> tuple[bool, int, int, int]:
        lo, hi = 0, self.ecx_file_size // NEEDLE_MAP_ENTRY_SIZE
        while lo < hi:
            mid = (lo + hi) // 2
            buf = os.pread(
                self._ecx_fd, NEEDLE_MAP_ENTRY_SIZE, mid * NEEDLE_MAP_ENTRY_SIZE
            )
            key, offset, size = idx_mod.entry_from_bytes(buf)
            if key == needle_id:
                return True, mid, offset, size
            if key < needle_id:
                lo = mid + 1
            else:
                hi = mid
        return False, -1, 0, 0

    # --- reads ------------------------------------------------------------------
    def locate_intervals(self, offset: int, size: int) -> list[Interval]:
        dat_size = DATA_SHARDS_COUNT * self.shard_size
        return locate_data(
            self.large_block_size,
            self.small_block_size,
            dat_size,
            offset,
            get_actual_size(size, self.version),
        )

    def _pread_shard(self, shard_id: int, off: int, size: int) -> bytes | None:
        """Full-length positional read, or None if the shard can't serve it
        (absent or truncated — both are 'missing' to the erasure code)."""
        try:
            _FP_SHARD_READ.hit(volume=self.volume_id)
        except (faults.FaultInjected, OSError):
            return None  # an injected local failure = a missing shard
        fd = self.shards.get(shard_id)
        if fd is None:
            return None
        data = os.pread(fd, size, off)
        if len(data) != size:
            return None
        # `corrupt` mode: silent bit flip on the shard-read seam — the
        # needle CRC (or the scrubber's parity recompute) must catch it
        return _FP_SHARD_READ.mangle(data, volume=self.volume_id)

    def _fetch_remote(self, shard_id: int, off: int, size: int) -> bytes | None:
        if self.shard_fetcher is None:
            return None
        try:
            data = self.shard_fetcher(shard_id, off, size)
        except Exception:
            return None
        if data is not None and len(data) != size:
            return None
        return data

    def _read_interval(self, interval: Interval) -> bytes:
        """local shard -> remote shard -> reconstruct, the `store_ec.go`
        readOneEcShardInterval ladder. The interval's bytes are counted
        under the rung that served it: one counter update an interval."""
        shard_id, off = interval.to_shard_id_and_offset(
            self.large_block_size, self.small_block_size
        )
        served = trace.read_interval_bytes_counter()
        data = self._pread_shard(shard_id, off, interval.size)
        if data is not None:
            served.labels("local").inc(interval.size)
            return data
        data = self._fetch_remote(shard_id, off, interval.size)
        if data is not None:
            served.labels("remote").inc(interval.size)
            return data
        # wall and this thread's CPU seconds of the reconstruction, and how
        # many bytes, under the kernel that reconstructs degraded reads here
        with trace.phase(
            "ec.reconstruct", trace.EC_DECODE_SECONDS, nbytes=interval.size,
            cpu=True,
        ) as ph:
            data = self._recover_interval(shard_id, off, interval.size)
            ph.kernel = "reconstruct-" + self.codec.kernel_label
        served.labels("reconstruct").inc(interval.size)
        return data

    def _recover_interval(self, missing_shard: int, off: int, size: int) -> bytes:
        """Reconstruct one interval from >= 10 surviving shards, local first
        then remote fan-in (`store_ec.go:339-395`
        recoverOneRemoteEcShardInterval). When the server layer attached a
        partial_fetcher, the remote fan-in moves one GF-scaled partial per
        holder (~1x the interval per holder) instead of a full range per
        shard (up to 10x) — byte-identical, any holder failing drops to
        the classic ladder below."""
        if self.partial_fetcher is not None:
            try:
                data = self.partial_fetcher(missing_shard, off, size)
            except Exception:
                data = None
            if data is not None and len(data) == size:
                degraded_reads_counter().labels("ec_reconstruct").inc()
                _emit_degraded(self.volume_id, missing_shard,
                               "partial_fanin", self.collection)
                return data
        present: dict[int, np.ndarray] = {}
        for shard_id in self.shards:
            if shard_id == missing_shard:
                continue
            data = self._pread_shard(shard_id, off, size)
            if data is None:
                continue
            present[shard_id] = np.frombuffer(data, dtype=np.uint8)
            if len(present) >= DATA_SHARDS_COUNT:
                break
        if len(present) < DATA_SHARDS_COUNT:
            for shard_id in range(TOTAL_SHARDS_COUNT):
                if shard_id == missing_shard or shard_id in present:
                    continue
                data = self._fetch_remote(shard_id, off, size)
                if data is None:
                    continue
                present[shard_id] = np.frombuffer(data, dtype=np.uint8)
                if len(present) >= DATA_SHARDS_COUNT:
                    break
        if len(present) < DATA_SHARDS_COUNT:
            raise IOError(
                f"cannot recover shard {missing_shard}: only {len(present)} present"
            )
        out = self.codec.reconstruct(present, targets=[missing_shard])
        degraded_reads_counter().labels("ec_reconstruct").inc()
        _emit_degraded(self.volume_id, missing_shard, "full_decode",
                       self.collection)
        return out[missing_shard].tobytes()

    def read_needle(self, needle_id: int, cookie: int | None = None) -> Needle:
        offset, size = self.find_needle_from_ecx(needle_id)
        if size_is_deleted(size):
            raise NeedleNotFound(f"needle {needle_id:x} deleted")
        blob = b"".join(
            self._read_interval(iv) for iv in self.locate_intervals(offset, size)
        )
        n = Needle.from_bytes(blob, size=size, version=self.version)
        if cookie is not None and n.cookie != cookie:
            raise NeedleNotFound("cookie mismatch")
        return n

    # --- deletes ----------------------------------------------------------------
    def delete_needle(self, needle_id: int) -> None:
        """Tombstone in .ecx + append id to .ecj (`ec_volume_delete.go:27-49`)."""
        found, pos, _, _ = self._search(needle_id)
        if not found:
            return
        os.pwrite(
            self._ecx_fd,
            put_u32(size_to_u32(TOMBSTONE_FILE_SIZE)),
            pos * NEEDLE_MAP_ENTRY_SIZE + NEEDLE_ID_SIZE + OFFSET_SIZE,
        )
        with self._ecj_lock:
            with open(self.ecj_path, "ab") as f:
                f.write(put_u64(needle_id))

    def shard_ids(self) -> list[int]:
        return sorted(self.shards)
