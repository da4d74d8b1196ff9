"""Native filer mode (VERDICT r4 next #3): the engine serves the filer's
hot path — inline writes with zero volume hops, leased-fid chunk uploads,
and a path->location read cache invalidated by the meta-log — while the
Python side stays authoritative via journal replay + drain.

Reference hot path: `weed/server/filer_server_handlers_write_autochunk.go:26-155`.
"""

from __future__ import annotations

import json
import os

import pytest

from seaweedfs_tpu.server.filer import FilerServer
from seaweedfs_tpu.server.httpd import http_request
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume import VolumeServer


@pytest.fixture()
def cluster(tmp_path):
    m = MasterServer(port=0, pulse_seconds=1)
    m.start()
    v = VolumeServer([str(tmp_path / "v")], m.url, port=0, pulse_seconds=1)
    v.start()
    yield m, v, str(tmp_path)
    v.stop()
    m.stop()


def _filer(cluster, **kw):
    m, _, _ = cluster
    f = FilerServer(m.url, port=0, **kw)
    f.start()
    return f


def _wait_cached(f, path, seconds=10.0):
    """Until a GET of `path` is answered from the engine's cache: a read
    that Python serves puts the entry there."""
    import time

    deadline = time.time() + seconds
    while True:
        before = f.fastlane.front_metrics()["read"]["native"]
        st, _, _ = http_request("GET", f.url + path)
        assert st == 200
        if f.fastlane.front_metrics()["read"]["native"] == before + 1:
            return
        assert time.time() < deadline, f"{path} never reached the cache"
        time.sleep(0.02)


class TestNativeFilerPath:
    def test_inline_and_chunk_served_natively(self, cluster):
        f = _filer(cluster)
        if not f._fl_filer_on:
            f.stop()
            pytest.skip("engine unavailable")
        try:
            # inline (<= SMALL_CONTENT_LIMIT): no volume hop at all
            st, _, body = http_request("POST", f.url + "/a/small.txt",
                                       b"tiny", {"Content-Type": "text/plain"})
            assert st == 201
            assert json.loads(body)["md5"]
            st, hdrs, body = http_request("GET", f.url + "/a/small.txt")
            assert st == 200 and body == b"tiny"
            assert hdrs["Content-Type"] == "text/plain"
            # chunk-backed (> inline limit): leased fid + native upload
            payload = os.urandom(64 * 1024)
            st, _, body = http_request("POST", f.url + "/a/big.bin", payload)
            assert st == 201
            md5 = json.loads(body)["md5"]
            st, hdrs, body = http_request("GET", f.url + "/a/big.bin")
            assert st == 200 and body == payload
            assert hdrs["ETag"] == f'"{md5}"'  # entry md5, not the chunk CRC
            assert "Last-Modified" in hdrs
            # ranged read rides the relay
            st, _, body = http_request("GET", f.url + "/a/big.bin",
                                       headers={"Range": "bytes=100-199"})
            assert st == 206 and body == payload[100:200]
            # conditional read short-circuits in the engine
            st, _, _ = http_request("GET", f.url + "/a/big.bin",
                                    headers={"If-None-Match": f'"{md5}"'})
            assert st == 304
            stats = f.fastlane.stats()
            assert stats["native_writes"] == 2
            # one read may take the designed relay-fallback (rare)
            assert stats["native_reads"] >= 3
            # the drained entries are real store entries (metadata surface)
            st, _, body = http_request(
                "GET", f.url + "/a/big.bin?metadata=true")
            d = json.loads(body)
            assert d["attributes"]["file_size"] == len(payload)
            assert len(d["chunks"]) == 1
        finally:
            f.stop()

    def test_hot_chunk_promotion(self, cluster):
        """A small chunk-backed object's first read relays to the volume;
        the full-entity body is then promoted into the filer engine's
        inline cache, so repeat reads never touch the volume again (and
        an overwrite invalidates the promotion via the meta-log)."""
        m, v, _ = cluster
        f = _filer(cluster)
        if not f._fl_filer_on or v.fastlane is None:
            f.stop()
            pytest.skip("engines unavailable")
        try:
            payload = os.urandom(8192)  # > inline limit, <= promotion cap
            st, _, _ = http_request("POST", f.url + "/hot/a.bin", payload)
            assert st == 201
            st, _, body = http_request("GET", f.url + "/hot/a.bin")
            assert st == 200 and body == payload  # relay (volume GET #1)
            # Promotion rides the engine's path-cache entry, whose
            # installation path is bimodal (native-write gate vs
            # meta-log/read-path push with a possibly-cold vid lookup
            # cache) — on a slow box the entry can churn for a few reads
            # before the promotion sticks. Wait until THREE consecutive
            # GETs leave the volume counter untouched: the object is
            # promoted and stays promoted (fcache_put carries inline
            # bytes across same-md5 re-puts, so a refresh cannot demote
            # it), which is the invariant under test.
            import time as _time

            deadline = _time.time() + 10
            quiet = 0
            while quiet < 3:
                before = v.fastlane.stats()["native_reads"]
                st, _, body = http_request("GET", f.url + "/hot/a.bin")
                assert st == 200 and body == payload
                quiet = (
                    quiet + 1
                    if v.fastlane.stats()["native_reads"] == before
                    else 0
                )
                assert _time.time() < deadline, "object never promoted"
            # ranges work on the promoted copy too
            st, _, body = http_request(
                "GET", f.url + "/hot/a.bin",
                headers={"Range": "bytes=100-199"})
            assert st == 206 and body == payload[100:200]
            # overwrite: the meta-log replaces the promotion
            payload2 = os.urandom(9000)
            st, _, _ = http_request("POST", f.url + "/hot/a.bin", payload2)
            assert st == 201
            st, _, body = http_request("GET", f.url + "/hot/a.bin")
            assert st == 200 and body == payload2
        finally:
            f.stop()

    def test_meta_log_invalidates_cache(self, cluster):
        f = _filer(cluster)
        if not f._fl_filer_on:
            f.stop()
            pytest.skip("engine unavailable")
        try:
            st, _, _ = http_request("POST", f.url + "/c/x.bin", b"q" * 5000)
            assert st == 201
            # delete through the Python path: the meta-log subscriber must
            # purge the native cache or reads would serve a ghost
            st, _, _ = http_request("DELETE", f.url + "/c/x.bin")
            assert st in (200, 204)
            st, _, _ = http_request("GET", f.url + "/c/x.bin")
            assert st == 404
            # rename invalidates the old path and serves the new one
            st, _, _ = http_request("POST", f.url + "/c/a.bin", b"r" * 5000)
            assert st == 201
            st, _, _ = http_request(
                "POST", f.url + "/c/b.bin?mv.from=/c/a.bin", b"")
            assert st == 200
            st, _, _ = http_request("GET", f.url + "/c/a.bin")
            assert st == 404
            st, _, body = http_request("GET", f.url + "/c/b.bin")
            assert st == 200 and body == b"r" * 5000
            # overwrite through the native path replaces the cached blob
            st, _, _ = http_request("POST", f.url + "/c/b.bin", b"s" * 4000)
            assert st == 201
            st, _, body = http_request("GET", f.url + "/c/b.bin")
            assert st == 200 and body == b"s" * 4000
        finally:
            f.stop()

    def test_journal_replay_after_crash(self, cluster, tmp_path):
        """An acked native write whose entry never reached the store (the
        process died before the drain) is recovered from the journal —
        the filer analog of .idx replay on volume load."""
        store = str(tmp_path / "filer_store")
        os.makedirs(store, exist_ok=True)
        f1 = _filer(cluster, store_kind="lsm", store_path=store)
        if not f1._fl_filer_on:
            f1.stop()
            pytest.skip("engine unavailable")
        try:
            # simulate a Python stall: nothing drains, entries live only in
            # the engine journal
            f1._fl_filer_on_real = f1._fl_filer_drain
            f1._fl_filer_drain = lambda *a, **k: 0
            st, _, _ = http_request("POST", f1.url + "/crash/keep.txt",
                                    b"survives")
            assert st == 201
            payload = os.urandom(10000)
            st, _, _ = http_request("POST", f1.url + "/crash/keep.bin",
                                    payload)
            assert st == 201
            assert f1.filer.find_entry("/crash/keep.txt") is None  # stalled
        finally:
            f1.stop()  # crash: frames never applied

        f2 = _filer(cluster, store_kind="lsm", store_path=store)
        try:
            e = f2.filer.find_entry("/crash/keep.txt")
            assert e is not None and e.content == b"survives"
            st, _, body = http_request("GET", f2.url + "/crash/keep.bin")
            assert st == 200 and body == payload
        finally:
            f2.stop()

    def test_secured_cluster_stays_native(self, cluster, tmp_path):
        """jwt.signing + jwt.signing.read configured: the filer signs its
        own upload/read tokens (as the reference filer does) and the whole
        filer data path stays on the engines."""
        from seaweedfs_tpu.security import SecurityConfig

        m, v, _ = cluster
        v.stop()
        sec = SecurityConfig(write_key="w-secret", read_key="r-secret")
        v2 = VolumeServer([str(tmp_path / "v2")], m.url, port=0,
                          pulse_seconds=1, security=sec)
        v2.start()
        f = FilerServer(m.url, port=0, security=sec)
        f.start()
        if not f._fl_filer_on:
            f.stop()
            v2.stop()
            pytest.skip("engine unavailable")
        try:
            payload = os.urandom(30000)
            st, _, _ = http_request("POST", f.url + "/sec/x.bin", payload)
            assert st == 201
            st, _, body = http_request("GET", f.url + "/sec/x.bin")
            assert st == 200 and body == payload
            stats = f.fastlane.stats()
            assert stats["native_writes"] >= 1 and stats["native_reads"] >= 1
            # and the volume itself served those natively (JWTs verified
            # in its engine, not the Python proxy)
            vstats = v2.fastlane.stats() if v2.fastlane else {}
            if vstats:
                assert vstats["native_writes"] >= 1
                assert vstats["native_reads"] >= 1
        finally:
            f.stop()
            v2.stop()


class TestNativeDeleteAndFrontDoor:
    def test_native_delete_read_your_deletes(self, cluster):
        """PR-6: DELETE of a cached entry acks natively (journal + cache
        tombstone) and an immediate GET — on any engine core — 404s even
        before the drain lands; the store catches up asynchronously."""
        import time

        f = _filer(cluster)
        if not f._fl_filer_on:
            f.stop()
            pytest.skip("engine unavailable")
        try:
            st, _, _ = http_request("POST", f.url + "/d/i.txt", b"inline")
            assert st == 201
            st, _, _ = http_request("POST", f.url + "/d/c.bin",
                                    os.urandom(20000))
            assert st == 201
            # "a cached entry" is the premise: a write that fell back to
            # Python (no lease yet on a loaded machine) leaves its entry to
            # the first read, and the DELETE would then be Python's too
            for path in ("/d/i.txt", "/d/c.bin"):
                _wait_cached(f, path)
            before = f.fastlane.front_metrics()["delete"]["native"]
            for path in ("/d/i.txt", "/d/c.bin"):
                st, _, _ = http_request("DELETE", f.url + path)
                assert st == 204
                st, _, _ = http_request("GET", f.url + path)
                assert st == 404, f"read-your-deletes violated for {path}"
            assert f.fastlane.front_metrics()["delete"]["native"] == \
                before + 2, "deletes left the native path"
            deadline = time.time() + 5
            while time.time() < deadline and (
                    f.filer.find_entry("/d/i.txt") is not None
                    or f.filer.find_entry("/d/c.bin") is not None):
                time.sleep(0.05)
            assert f.filer.find_entry("/d/i.txt") is None
            assert f.filer.find_entry("/d/c.bin") is None
            # write-after-delete reuses the path cleanly
            st, _, _ = http_request("POST", f.url + "/d/i.txt", b"again")
            assert st == 201
            st, _, body = http_request("GET", f.url + "/d/i.txt")
            assert st == 200 and body == b"again"
        finally:
            f.stop()

    def test_native_write_stays_cached_through_its_own_drain(self, cluster):
        """The drain's apply of a native write leaves the engine's entry
        alone: it is the newer one, and a refresh from the store would
        drop a chunk entry whose volume the filer never looked up (the
        engine wrote it through a lease) — the next read, and a DELETE,
        would be Python's."""
        f = _filer(cluster)
        if not f._fl_filer_on:
            f.stop()
            pytest.skip("engine unavailable")
        try:
            payload = os.urandom(20000)
            with f._fl_drain_mu:  # the loop's drain waits for ours
                st, _, _ = http_request("POST", f.url + "/k/c.bin", payload)
                assert st == 201
                if f.fastlane.front_metrics()["write"]["native"] != 1:
                    pytest.skip("no lease yet: the write was Python's")
            assert f._fl_filer_drain() == 1
            assert f.filer.find_entry("/k/c.bin") is not None
            st, _, body = http_request("GET", f.url + "/k/c.bin")
            assert st == 200 and body == payload
            fm = f.fastlane.front_metrics()["read"]
            assert fm["native"] == 1 and not fm["fallback"]["cache_miss"]
        finally:
            f.stop()

    def test_put_of_the_stores_state_keeps_a_tombstone(self, cluster):
        """A cache refresh that reports the store's state — a read that
        Python serves looks the entry up, then pushes it — can come after
        the engine acked a DELETE of the path and before the drain applied
        it: the store is behind the ack, and the refresh must not put the
        live entry back over the tombstone (a GET would answer 200 for a
        deleted path). Held still here: the drain is locked out and the
        refresh is made by hand."""
        from seaweedfs_tpu.filer import Entry

        f = _filer(cluster)
        if not f._fl_filer_on:
            f.stop()
            pytest.skip("engine unavailable")
        try:
            with f._fl_drain_mu:
                st, _, _ = http_request("POST", f.url + "/t/i.txt", b"inline")
                assert st == 201
                st, _, _ = http_request("DELETE", f.url + "/t/i.txt")
                assert st == 204
                fm = f.fastlane.front_metrics()
                assert fm["write"]["native"] == fm["delete"]["native"] == 1
                stale = Entry(full_path="/t/i.txt", content=b"inline")
                stale.attributes.md5 = "0" * 32
                stale.attributes.file_size = 6
                f._fl_cache_push(stale, blocking_lookup=False)
                st, _, _ = http_request("GET", f.url + "/t/i.txt")
                assert st == 404, "a stale put resurrected a deleted path"
                assert f.fastlane.front_metrics()["read"]["native"] == 1
            # the drain lifts the tombstone; the path is free again
            f._fl_filer_drain()
            assert f.filer.find_entry("/t/i.txt") is None
            st, _, _ = http_request("GET", f.url + "/t/i.txt")
            assert st == 404
            st, _, _ = http_request("POST", f.url + "/t/i.txt", b"again")
            assert st == 201
            st, _, body = http_request("GET", f.url + "/t/i.txt")
            assert st == 200 and body == b"again"
        finally:
            f.stop()

    def test_front_metrics_exported_and_typed(self, cluster):
        """The front-door counters reach the process registry as
        SeaweedFS_filer_fastlane_{native,fallback}_total with typed
        reasons — the fastlane_fallback alert's input."""
        from seaweedfs_tpu.stats import default_registry

        f = _filer(cluster)
        if not f._fl_filer_on:
            f.stop()
            pytest.skip("engine unavailable")
        try:
            st, _, _ = http_request("POST", f.url + "/fm/x.txt", b"hello")
            assert st == 201
            st, _, _ = http_request("GET", f.url + "/fm/x.txt")
            assert st == 200
            # a query read is an EXPECTED fallback with reason=query
            st, _, _ = http_request("GET", f.url + "/fm/x.txt?metadata=true")
            assert st == 200
            fm = f.fastlane.front_metrics()
            assert fm["write"]["native"] >= 1
            assert fm["read"]["native"] >= 1
            assert fm["read"]["fallback"]["query"] >= 1
            text = default_registry().render()
            assert "SeaweedFS_filer_fastlane_native_total" in text
            assert 'reason="query"' in text
        finally:
            f.stop()

    def test_lease_pool_upserts_by_volume(self, cluster):
        """The engine holds one lease PER VOLUME: installs upsert by vid,
        remaining sums the pool, and lease_count reports live entries
        (-1 only for a stopped engine — the r05 shutdown-race signature)."""
        f = _filer(cluster)
        if not f._fl_filer_on:
            f.stop()
            pytest.skip("engine unavailable")
        lib, h = f.fastlane._lib, f.fastlane.handle
        try:
            import time

            # freeze the background refresh loop so the pool arithmetic
            # below can't race a concurrent top-up
            f._fl_lease_backoff_until = time.monotonic() + 300
            time.sleep(0.1)  # let an in-flight refresh finish
            lib.sw_fl_filer_lease_set(h, b"127.0.0.1", 1, 901, 7, 0, 100,
                                      b"", b"")
            lib.sw_fl_filer_lease_set(h, b"127.0.0.1", 1, 902, 7, 0, 50,
                                      b"", b"")
            base = int(lib.sw_fl_filer_lease_remaining(h))
            assert base >= 150 and f.fastlane.lease_count() >= 2
            # re-leasing vid 901 REPLACES its range, not a second entry
            n = f.fastlane.lease_count()
            lib.sw_fl_filer_lease_set(h, b"127.0.0.1", 1, 901, 7, 1000,
                                      1200, b"", b"")
            assert f.fastlane.lease_count() == n
            assert int(lib.sw_fl_filer_lease_remaining(h)) == base + 100
            # typed error strings replace the bare rc
            from seaweedfs_tpu.storage import fastlane as fl_mod

            rc = int(lib.sw_fl_filer_lease_set(
                h, b"not-an-ip.example", 1, 903, 7, 0, 10, b"", b""))
            assert rc == -2
            assert "IPv4" in fl_mod.error_str(lib, rc)
        finally:
            f.stop()
        # a stopped engine reports -1 (not "pool empty"), so the refresh
        # loop can tell shutdown from a spent lease and never re-leases —
        # the exact ambiguity behind r05's bogus "lease rejected" warning
        assert int(lib.sw_fl_filer_lease_count(h)) == -1

    def test_lease_duplicate_grant_keeps_healthy_range(self, cluster):
        """A top-up probe on a cluster with fewer writable volumes than
        the pool target lands on an already-held vid. A healthy (>=5000
        unspent keys) range is KEPT (rc=1) — replacing it would abandon
        the unspent keys on every probe forever — while a nearly-spent
        range is still replaced (rc=0, the low-watermark renewal)."""
        f = _filer(cluster)
        if not f._fl_filer_on:
            f.stop()
            pytest.skip("engine unavailable")
        lib, h = f.fastlane._lib, f.fastlane.handle
        try:
            import time

            f._fl_lease_backoff_until = time.monotonic() + 300
            time.sleep(0.1)  # let an in-flight refresh finish
            rc = int(lib.sw_fl_filer_lease_set(
                h, b"127.0.0.1", 1, 911, 7, 0, 20000, b"", b""))
            assert rc == 0
            base = int(lib.sw_fl_filer_lease_remaining(h))
            # duplicate grant with a SMALLER fresh range: kept, not
            # replaced (remaining would drop by 14000 on a replace)
            rc = int(lib.sw_fl_filer_lease_set(
                h, b"127.0.0.1", 1, 911, 9, 50000, 56000, b"", b""))
            assert rc == 1
            assert int(lib.sw_fl_filer_lease_remaining(h)) == base
            # nearly-spent (< 5000 keys) still replaces: renewal must win
            rc = int(lib.sw_fl_filer_lease_set(
                h, b"127.0.0.1", 1, 912, 7, 0, 1000, b"", b""))
            assert rc == 0
            base = int(lib.sw_fl_filer_lease_remaining(h))
            rc = int(lib.sw_fl_filer_lease_set(
                h, b"127.0.0.1", 1, 912, 7, 30000, 50000, b"", b""))
            assert rc == 0
            assert int(lib.sw_fl_filer_lease_remaining(h)) == base + 19000
        finally:
            f.stop()

    def test_pipelined_request_after_zero_copy_relay(self, cluster):
        """Two GETs pipelined on one connection where the first's relay
        body rides the zero-copy (out2) lane: the backend-completion path
        must drain the second, already-buffered request — pre-fix it
        stalled until the 300s idle sweep closed the connection (the
        completion's single process_buffered pass no-oped while out2 was
        occupied, and no further read event ever arrived)."""
        import re as _re
        import socket
        import urllib.parse as _up

        f = _filer(cluster)
        if not f._fl_filer_on:
            f.stop()
            pytest.skip("engine unavailable")
        try:
            # > promotion cap (65536): every GET relays from the volume
            payload = os.urandom(100 * 1024)
            st, _, _ = http_request("POST", f.url + "/pl/a.bin", payload)
            assert st == 201
            u = _up.urlparse(f.url)
            req = (f"GET /pl/a.bin HTTP/1.1\r\n"
                   f"Host: {u.hostname}\r\n\r\n").encode()

            def read_response(s, buf):
                while b"\r\n\r\n" not in buf:
                    chunk = s.recv(65536)
                    assert chunk, "connection closed mid-response"
                    buf += chunk
                head, _, rest = buf.partition(b"\r\n\r\n")
                n = int(_re.search(rb"content-length:\s*(\d+)", head,
                                   _re.I).group(1))
                while len(rest) < n:
                    chunk = s.recv(65536)
                    assert chunk, "connection closed mid-body"
                    rest += chunk
                return head, rest[:n], rest[n:]

            with socket.create_connection((u.hostname, u.port),
                                          timeout=15) as s:
                s.sendall(req + req)  # both requests in one packet
                head1, body1, buf = read_response(s, b"")
                assert b" 200 " in head1.split(b"\r\n", 1)[0]
                assert body1 == payload
                head2, body2, _ = read_response(s, buf)  # pre-fix: timeout
                assert b" 200 " in head2.split(b"\r\n", 1)[0]
                assert body2 == payload
        finally:
            f.stop()

    def test_filer_relayed_write_joins_caller_trace(self, cluster):
        """Drain-synthesized spans for filer-relayed chunk PUTs carry the
        originating X-Sw-Trace-Id, so cluster.trace shows one end-to-end
        chain instead of an orphaned volume span."""
        import time

        from seaweedfs_tpu.stats import trace as trace_mod

        m, v, _ = cluster
        f = _filer(cluster)
        if not f._fl_filer_on or v.fastlane is None:
            f.stop()
            pytest.skip("engines unavailable")
        try:
            tid = "ab54feedcafe0042"
            st, _, _ = http_request(
                "POST", f.url + "/tr/chunk.bin", os.urandom(20000),
                {"X-Sw-Trace-Id": tid})
            assert st == 201
            deadline = time.time() + 5
            found = None
            while time.time() < deadline and found is None:
                v.fastlane.drain()
                for t in trace_mod.collector().traces(limit=200):
                    if t["trace_id"] == tid and any(
                            s["name"] == "fastlane.append"
                            for s in t["spans"]):
                        found = t
                        break
                time.sleep(0.05)
            assert found is not None, (
                "fastlane.append span never joined the caller's trace")
        finally:
            f.stop()


def test_lease_survives_volume_deletion(cluster):
    """volume.delete.empty (or a move/evacuation) can remove the volume a
    filer's fid lease points at before anything was written to it. The
    failed native upload must fall back to the Python path (the client
    still gets a 201), drop the lease, and re-lease against live
    topology so later writes return to the native path."""
    m, v, _ = cluster
    f = _filer(cluster)
    if not f._fl_filer_on:
        f.stop()
        pytest.skip("engine unavailable")
    try:
        import time

        from seaweedfs_tpu.server.httpd import post_json

        lib, h = f.fastlane._lib, f.fastlane.handle
        for _ in range(50):
            if int(lib.sw_fl_filer_lease_remaining(h)) > 0:
                break
            time.sleep(0.1)
        # delete EVERY volume on the server (they are all empty)
        for vid in list(v.store.volume_ids()):
            post_json(f"{v.url}/admin/delete_volume", {"volume": vid})
        # the lease still points at a deleted volume: the write must
        # succeed anyway (proxy fallback) and drop the lease
        payload = os.urandom(30000)
        st, _, _ = http_request("POST", f.url + "/dead/a.bin", payload)
        assert st == 201
        st, _, body = http_request("GET", f.url + "/dead/a.bin")
        assert st == 200 and body == payload
        # the loop re-leases against live topology and native writes
        # resume. With the lease POOL, other entries may still point at
        # deleted volumes — each such write is an acked (201) fallback
        # that prunes exactly one dead lease, so give it a few writes.
        deadline = time.time() + 15
        native_resumed = False
        i = 0
        while time.time() < deadline and not native_resumed:
            if int(lib.sw_fl_filer_lease_remaining(h)) == 0:
                time.sleep(0.1)
                continue
            before = f.fastlane.stats()["native_writes"]
            st, _, _ = http_request("POST", f.url + f"/dead/b{i}.bin",
                                    os.urandom(30000))
            i += 1
            assert st == 201
            native_resumed = f.fastlane.stats()["native_writes"] > before
        assert native_resumed, "native writes never resumed after re-lease"
    finally:
        f.stop()


def test_fs_configure_rules(cluster):
    """fs.configure (`filer_conf.go`): per-prefix storage rules — TTL and
    collection defaults applied on writes, read-only prefixes rejecting
    writes/deletes, hot-reloaded from /etc/seaweedfs/filer.conf, and the
    engine defers rule-covered writes to Python."""
    from seaweedfs_tpu.shell import CommandEnv, run_command

    m, v, _ = cluster
    f = _filer(cluster)
    try:
        env = CommandEnv(m.url, filer_url=f.url)
        out = run_command(env, "fs.configure")
        assert "locations" in out
        # try-before-apply: nothing saved
        out = run_command(
            env, "fs.configure -locationPrefix /frozen -readOnly")
        assert "not saved" in out
        assert f.filer_conf.match("/frozen/x") is None
        out = run_command(
            env, "fs.configure -locationPrefix /frozen -readOnly -apply")
        assert "(saved)" in out
        # hot-reloaded via the meta-log
        assert (f.filer_conf.match("/frozen/x") or {}).get("read_only")
        st, _, body = http_request("POST", f.url + "/frozen/a.bin",
                                   os.urandom(9000))
        assert st == 403 and b"read-only" in body
        st, _, _ = http_request("DELETE", f.url + "/frozen/a.bin")
        assert st == 403
        # a ttl rule rides onto writes under the prefix
        run_command(env, "fs.configure -locationPrefix /tmpdata"
                         " -ttl 5m -apply")
        st, _, _ = http_request("POST", f.url + "/tmpdata/t.bin",
                                os.urandom(9000))
        assert st == 201
        f._fl_filer_drain()
        e = f.filer.find_entry("/tmpdata/t.bin")
        assert e.attributes.ttl_sec == 300
        # unruled paths stay on the native path
        if f._fl_filer_on:
            before = f.fastlane.stats()["native_writes"]
            st, _, _ = http_request("POST", f.url + "/plain/p.bin",
                                    os.urandom(9000))
            assert st == 201
            assert f.fastlane.stats()["native_writes"] > before
        run_command(env, "fs.configure -locationPrefix /frozen"
                         " -delete -apply")
        st, _, _ = http_request("POST", f.url + "/frozen/b.bin", b"x" * 3000)
        assert st == 201
    finally:
        f.stop()


def test_system_tree_prefix_pinned_in_engine():
    """fastlane.cpp mirrors filer_notify.SYSTEM_TREE_PREFIX as a literal
    (C can't import it): renaming the tree must update both or the
    never-invalidated-cache guard silently stops matching."""
    from seaweedfs_tpu.filer.filer_notify import SYSTEM_TREE_PREFIX

    src = open(os.path.join(os.path.dirname(__file__), "..",
                            "seaweedfs_tpu", "native", "src",
                            "fastlane.cpp")).read()
    needle = f'path.compare(0, {len(SYSTEM_TREE_PREFIX)},' \
             f' "{SYSTEM_TREE_PREFIX}") == 0'
    assert needle in src, needle
