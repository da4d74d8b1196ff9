"""Golden-file format tests against the reference's checked-in binary fixtures.

Strategy mirrors the reference's own tests (SURVEY.md §4): the fixture volume
`erasure_coding/1.dat` + `1.idx` and the standalone `needle/43.dat` /
`test/data/187.idx` files were written by the reference implementation — if we
can parse every needle, verify every CRC, and re-serialize records
byte-identically, the formats match bit-for-bit.
"""

import zlib

import pytest

from seaweedfs_tpu.storage import crc as crc_mod
from seaweedfs_tpu.storage import idx as idx_mod
from seaweedfs_tpu.storage.file_id import FileId, format_needle_id_cookie
from seaweedfs_tpu.storage.needle import (
    CURRENT_VERSION,
    VERSION3,
    Needle,
    get_actual_size,
    needle_body_length,
    padding_length,
)
from seaweedfs_tpu.storage.super_block import SUPER_BLOCK_SIZE, SuperBlock
from seaweedfs_tpu.storage.types import (
    NEEDLE_MAP_ENTRY_SIZE,
    TTL,
    ReplicaPlacement,
    size_is_valid,
)


class TestCRC32C:
    def test_known_vector(self):
        # RFC 3720 test vector: crc32c of 32 zero bytes.
        assert crc_mod.crc32c(b"\x00" * 32) == 0x8A9136AA
        assert crc_mod.crc32c(b"123456789") == 0xE3069283

    def test_streaming_update(self):
        data = bytes(range(256)) * 7
        whole = crc_mod.crc32c(data)
        c = 0
        for i in range(0, len(data), 37):
            c = crc_mod.update(c, data[i : i + 37])
        assert c == whole

    def test_native_matches_numpy(self):
        import os
        import random

        from seaweedfs_tpu import native

        if native.lib is None:
            pytest.skip("native lib unavailable")
        rng = random.Random(42)
        for n in [0, 1, 7, 8, 9, 63, 64, 1000]:
            data = bytes(rng.randrange(256) for _ in range(n))
            os.environ["SEAWEEDFS_TPU_DISABLE_NATIVE"] = "1"
            try:
                native_val = native.lib.crc32c_update(0, data)
                # numpy path, bypassing native:
                saved, crc_mod._native = crc_mod._native, False
                try:
                    np_val = crc_mod.crc32c(data)
                finally:
                    crc_mod._native = saved
            finally:
                del os.environ["SEAWEEDFS_TPU_DISABLE_NATIVE"]
            assert native_val == np_val


class TestNeedleLayout:
    def test_padding_always_1_to_8(self):
        for size in range(0, 64):
            for v in (1, 2, 3):
                p = padding_length(size, v)
                assert 1 <= p <= 8
                total = get_actual_size(size, v)
                assert total % 8 == 0

    def test_round_trip_v3(self):
        n = Needle(cookie=0x12345678, id=0xABCDEF, data=b"hello world")
        n.name = b"file.txt"
        n.set_has_name()
        n.mime = b"text/plain"
        n.set_has_mime()
        n.last_modified = 1700000000
        n.set_has_last_modified()
        n.ttl = TTL.parse("3d")
        n.set_has_ttl()
        n.pairs = b'{"k":"v"}'
        n.set_has_pairs()
        n.append_at_ns = 1700000000123456789
        blob = n.to_bytes(VERSION3)
        assert len(blob) == n.disk_size(VERSION3)

        m = Needle.from_bytes(blob, version=VERSION3)
        assert m.id == n.id and m.cookie == n.cookie
        assert m.data == b"hello world"
        assert m.name == b"file.txt"
        assert m.mime == b"text/plain"
        assert m.last_modified == 1700000000
        assert str(m.ttl) == "3d"
        assert m.pairs == b'{"k":"v"}'
        assert m.append_at_ns == 1700000000123456789

    def test_round_trip_empty_data(self):
        n = Needle(cookie=1, id=2)
        blob = n.to_bytes(VERSION3)
        m = Needle.from_bytes(blob, version=VERSION3)
        assert m.size == 0 and m.data == b""

    def test_round_trip_all_versions(self):
        for v in (1, 2, 3):
            n = Needle(cookie=7, id=99, data=b"x" * 100)
            blob = n.to_bytes(v)
            m = Needle.from_bytes(blob, version=v)
            assert m.data == n.data

    def test_crc_detects_corruption(self):
        n = Needle(cookie=1, id=2, data=b"payload")
        blob = bytearray(n.to_bytes(VERSION3))
        blob[20] ^= 0xFF  # flip a data byte
        with pytest.raises(Exception):
            Needle.from_bytes(bytes(blob), version=VERSION3)


class TestFileId:
    def test_format_parse(self):
        fid = FileId(3, 0x01637037D6, 0xFD8CA931)
        s = str(fid)
        assert s == "3,01637037d6fd8ca931"
        assert FileId.parse(s) == fid

    def test_short_key_keeps_cookie(self):
        s = format_needle_id_cookie(1, 0x12345678)
        assert s == "0112345678"

    def test_delta_suffix(self):
        f = FileId.parse("3,0112345678_2")
        assert f.key == 3


class TestGoldenFixtures:
    def test_walk_187_idx(self, reference_fixtures):
        entries = list(idx_mod.walk_index_file(str(reference_fixtures["idx_187"])))
        size = reference_fixtures["idx_187"].stat().st_size
        assert len(entries) == size // NEEDLE_MAP_ENTRY_SIZE
        assert len(entries) > 0
        # all offsets are 8-byte aligned by construction
        for key, offset, sz in entries:
            assert offset % 8 == 0

    def test_fixture_volume_superblock(self, reference_fixtures):
        data = reference_fixtures["ec_dat"].read_bytes()
        sb = SuperBlock.from_bytes(data[:SUPER_BLOCK_SIZE])
        assert sb.version in (2, 3)

    def test_fixture_volume_needles_parse_and_crc(self, reference_fixtures):
        """Every live needle in the fixture volume must parse with a valid CRC
        and re-serialize to the same record layout."""
        dat = reference_fixtures["ec_dat"].read_bytes()
        sb = SuperBlock.from_bytes(dat[:SUPER_BLOCK_SIZE])
        version = sb.version
        count = 0
        for key, offset, size in idx_mod.walk_index_file(
            str(reference_fixtures["ec_idx"])
        ):
            if not size_is_valid(size):
                continue
            blob = dat[offset : offset + get_actual_size(size, version)]
            n = Needle.from_bytes(blob, size=size, version=version)
            assert n.id == key
            count += 1
        assert count > 0

    def test_fixture_43_dat(self, reference_fixtures):
        """43.dat is a raw volume file with a superblock; scan needles
        sequentially like `weed fix` does."""
        dat = reference_fixtures["needle_dat"].read_bytes()
        sb = SuperBlock.from_bytes(dat[:SUPER_BLOCK_SIZE])
        offset = sb.block_size()
        count = 0
        while offset + 16 <= len(dat):
            n = Needle()
            n.parse_header(dat[offset : offset + 16])
            if n.size < 0:
                break
            body_len = needle_body_length(n.size, sb.version)
            if offset + 16 + body_len > len(dat):
                break
            Needle.from_bytes(
                dat[offset : offset + 16 + body_len], version=sb.version
            )
            offset += 16 + body_len
            count += 1
        assert count > 0
        assert offset == len(dat)  # clean walk to EOF


class TestSuperBlock:
    def test_round_trip(self):
        sb = SuperBlock(
            version=3,
            replica_placement=ReplicaPlacement.parse("010"),
            ttl=TTL.parse("5w"),
            compaction_revision=7,
        )
        b = sb.to_bytes()
        assert len(b) == 8
        sb2 = SuperBlock.from_bytes(b)
        assert sb2.version == 3
        assert str(sb2.replica_placement) == "010"
        assert str(sb2.ttl) == "5w"
        assert sb2.compaction_revision == 7


class TestReplicaPlacement:
    def test_codes(self):
        for code, copies in [("000", 1), ("001", 2), ("010", 2), ("100", 2), ("200", 3), ("110", 3)]:
            rp = ReplicaPlacement.parse(code)
            assert rp.copy_count() == copies
            assert str(rp) == code
            assert ReplicaPlacement.from_byte(rp.to_byte()) == rp


class TestPrometheusExposition:
    """Text-format escaping + registry invariants (stats/metrics.py)."""

    def test_label_values_escaped_per_spec(self):
        from seaweedfs_tpu.stats.metrics import Registry

        reg = Registry()
        c = reg.counter("esc_total", "h", ("path",))
        c.labels('a"b\\c\nd').inc()
        lines = reg.render().splitlines()
        sample = [l for l in lines if l.startswith("esc_total{")][0]
        assert sample == 'esc_total{path="a\\"b\\\\c\\nd"} 1'

    def test_histogram_le_labels_well_formed(self):
        from seaweedfs_tpu.stats.metrics import Registry

        reg = Registry()
        h = reg.histogram("lat_seconds", buckets=(0.5, 1.0))
        h.observe(0.7)
        text = reg.render()
        assert 'lat_seconds_bucket{le="0.5"} 0' in text
        assert 'lat_seconds_bucket{le="1"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 1' in text

    def test_histogram_bucket_mismatch_raises(self):
        from seaweedfs_tpu.stats.metrics import Registry

        reg = Registry()
        reg.histogram("hb_seconds", buckets=(1, 2))
        reg.histogram("hb_seconds", buckets=(2, 1))  # same set: fine
        with pytest.raises(TypeError):
            reg.histogram("hb_seconds", buckets=(1, 2, 3))

    def test_histogram_kind_mismatch_raises(self):
        from seaweedfs_tpu.stats.metrics import Registry

        reg = Registry()
        reg.counter("mixed_total")
        with pytest.raises(TypeError):
            reg.histogram("mixed_total")

    def test_collector_lines_rendered_and_unregistered(self):
        from seaweedfs_tpu.stats.metrics import Registry

        reg = Registry()
        col = reg.register_collector(
            lambda: ['ext_gauge{a="1"} 42'], names=("ext_gauge",))
        assert 'ext_gauge{a="1"} 42' in reg.render()
        assert "ext_gauge" in reg.metric_names()
        reg.unregister_collector(col)
        assert "ext_gauge" not in reg.render()
        assert "ext_gauge" not in reg.metric_names()

    def test_collector_exception_does_not_break_render(self):
        from seaweedfs_tpu.stats.metrics import Registry

        reg = Registry()
        reg.counter("ok_total").inc()

        def boom():
            raise RuntimeError("dying server")

        reg.register_collector(boom, names=("dead_total",))
        assert "ok_total" in reg.render()

    def test_parse_exposition_roundtrip(self):
        from seaweedfs_tpu.stats.metrics import Registry, parse_exposition

        reg = Registry()
        c = reg.counter("rt_total", "h", ("op", "path"))
        c.labels("read", 'we"ird\\p\nath').inc(3)
        h = reg.histogram("rt_seconds", buckets=(0.5, 1.0))
        h.observe(0.7)
        samples = parse_exposition(reg.render())
        assert ("rt_total", {"op": "read", "path": 'we"ird\\p\nath'}, 3.0) \
            in samples
        bucket = [s for s in samples if s[0] == "rt_seconds_bucket"]
        assert ("rt_seconds_bucket", {"le": "+Inf"}, 1.0) in bucket


class TestHistogramBuckets:
    """An observation is one bisect and one increment; the page still shows
    cumulative `le` counts."""

    @pytest.mark.parametrize("values,want", [
        ([], None),
        ([0.05, 0.1], [2, 2, 2, 2]),            # a bound belongs to its bucket
        ([0.1000001, 1.0, 4.9], [0, 2, 3, 3]),
        ([7.0, 1e9], [0, 0, 0, 2]),             # past the largest bound
        ([0.05, 0.5, 2.0, 7.0], [1, 2, 3, 4]),
    ])
    def test_rendered_counts_are_cumulative(self, values, want):
        from seaweedfs_tpu.stats.metrics import Registry, parse_exposition

        reg = Registry()
        h = reg.histogram("t_seconds", "", ("k",), buckets=(0.1, 1.0, 5.0))
        for v in values:
            h.labels("a").observe(v)
        got = [v for n, lab, v in parse_exposition(reg.render())
               if n == "t_seconds_bucket"]
        assert got == (want or [])
        if values:
            page = {(n, lab.get("k")): v for n, lab, v in
                    parse_exposition(reg.render()) if "bucket" not in n}
            assert page[("t_seconds_count", "a")] == len(values)
            assert page[("t_seconds_sum", "a")] == pytest.approx(sum(values))

    def test_label_children_are_kept_and_stringified(self):
        from seaweedfs_tpu.stats.metrics import Registry

        reg = Registry()
        c = reg.counter("t_total", "", ("code",))
        assert c.labels(200) is c.labels(200)
        c.labels(200).inc()
        c.labels("200").inc(2)
        assert 't_total{code="200"} 3' in reg.render()


class TestMetricNameLint:
    """tools/check_metric_names.py — the namespace cannot drift (tier-1)."""

    def _tool(self):
        import importlib
        import pathlib
        import sys

        sys.path.insert(
            0, str(pathlib.Path(__file__).resolve().parent.parent / "tools"))
        return importlib.import_module("check_metric_names")

    def test_registry_and_collector_names_follow_convention(self):
        tool = self._tool()
        kinds, collector_names = tool.collect()
        bad = tool.violations(kinds, collector_names)
        assert not bad, "\n".join(bad)
        # the walk actually saw the PR-2 families, not an empty registry
        assert "SeaweedFS_volume_fastlane_requests_total" in collector_names
        assert "SeaweedFS_master_volume_size_bytes" in collector_names
        assert "SeaweedFS_http_request_total" in kinds
        # PR-3: pipeline attribution + the self-observability collectors
        assert "SeaweedFS_volume_ec_pipeline_seconds" in kinds
        assert kinds["SeaweedFS_volume_ec_pipeline_seconds"] == "histogram"
        assert kinds["SeaweedFS_volume_ec_pipeline_buffers_total"] == "counter"
        assert "SeaweedFS_stats_trace_spans_total" in collector_names
        assert "SeaweedFS_stats_trace_dropped_total" in collector_names
        assert "SeaweedFS_stats_profile_samples_total" in collector_names
        # PR-4: history/alert collector families + process identity gauges
        assert "SeaweedFS_alerts_firing" in collector_names
        assert "SeaweedFS_stats_history_scrapes_total" in collector_names
        assert "SeaweedFS_stats_history_dropped_series_total" \
            in collector_names
        assert kinds["SeaweedFS_alerts_fired_total"] == "counter"
        assert kinds["SeaweedFS_build_info"] == "gauge"
        assert kinds["SeaweedFS_process_start_time_seconds"] == "gauge"
        # every registered alert-rule name passes the rule lint
        assert tool.alert_rule_violations() == []
        # PR-5: the maintenance subsystem's families + task-type registry
        assert "SeaweedFS_maintenance_queue_depth" in collector_names
        assert kinds["SeaweedFS_maintenance_tasks_total"] == "counter"
        assert kinds["SeaweedFS_maintenance_task_seconds"] == "histogram"
        assert kinds["SeaweedFS_maintenance_failures_total"] == "counter"
        assert tool.task_type_violations() == []
        # PR-8: online (write-path) EC families + degrade-reason labels
        assert kinds["SeaweedFS_volume_ec_online_stripes_total"] == "counter"
        assert kinds["SeaweedFS_volume_ec_online_encode_seconds"] \
            == "histogram"
        assert kinds["SeaweedFS_volume_ec_online_buffered_bytes"] == "gauge"
        assert kinds["SeaweedFS_volume_ec_online_journal_replays_total"] \
            == "counter"
        assert kinds["SeaweedFS_volume_ec_online_fallbacks_total"] \
            == "counter"
        assert tool.ec_online_reason_violations() == []
        # PR-9: fault-injection + degraded-read families and the
        # fault-point/reason registries (every declared point registered
        # by a seam AND exercised by tests/test_chaos.py)
        assert kinds["SeaweedFS_faults_injected_total"] == "counter"
        assert kinds["SeaweedFS_volume_degraded_reads_total"] == "counter"
        assert tool.fault_point_violations() == []
        assert tool.degraded_reason_violations() == []
        # PR-13: flight-recorder event registry (every declared type
        # emitted by a seam AND exercised by the tests) + SLO layer
        assert "SeaweedFS_events_recorded_total" in collector_names
        assert "SeaweedFS_events_dropped_total" in collector_names
        assert "SeaweedFS_slo_burn_rate" in collector_names
        assert tool.event_type_violations() == []
        assert tool.slo_violations() == []
        # PR-14: integrity-scrub families + finding-kind registry
        # (unique snake_case, corrupt fault mode exercised in chaos,
        # scrub task type registered with detector + executor)
        assert kinds["SeaweedFS_volume_scrub_bytes_total"] == "counter"
        assert kinds["SeaweedFS_volume_scrub_seconds"] == "histogram"
        assert kinds["SeaweedFS_volume_scrub_findings_total"] == "counter"
        assert kinds["SeaweedFS_volume_scrub_repairs_total"] == "counter"
        assert tool.scrub_violations() == []
        # PR-15: streaming-session chunk states + lazy-batch outcomes
        # (unique snake_case, stream failure reasons typed restart
        # reasons, the whole vocabulary exercised by the suite)
        assert kinds["SeaweedFS_volume_ec_repair_stream_chunks_total"] \
            == "counter"
        assert kinds["SeaweedFS_volume_ec_repair_resumed_bytes_total"] \
            == "counter"
        assert kinds["SeaweedFS_maintenance_lazy_batch_total"] == "counter"
        assert tool.stream_lazy_violations() == []
        # PR-16: tenant usage sketch + heat/forecast collector families,
        # the _other sentinel, the heat event types, and the
        # capacity_forecast alert pair
        assert "SeaweedFS_usage_requests_total" in collector_names
        assert "SeaweedFS_usage_error_bound" in collector_names
        assert "SeaweedFS_volume_heat_score" in collector_names
        assert "SeaweedFS_node_days_to_full" in collector_names
        assert "SeaweedFS_heat_collection_score" in collector_names
        assert tool.usage_heat_violations() == []
        # PR-18: cluster telemetry plane — merged-usage families, the
        # stale/self-observability gauges, and the cluster-scope rules
        assert "SeaweedFS_cluster_usage_requests_total" in collector_names
        assert "SeaweedFS_cluster_usage_error_bound" in collector_names
        assert "SeaweedFS_cluster_slo_burn_rate" in collector_names
        assert "SeaweedFS_cluster_telemetry_stale" in collector_names
        assert "SeaweedFS_cluster_alerts_firing" in collector_names
        assert tool.cluster_telemetry_violations() == []
        # PR-19: durable-telemetry spool families (stats/store.py) —
        # spool gauge/cap pair, flush + replay timers, eviction counter
        assert kinds["SeaweedFS_telemetry_spool_bytes"] == "gauge"
        assert kinds["SeaweedFS_telemetry_spool_cap_bytes"] == "gauge"
        assert kinds["SeaweedFS_telemetry_flush_seconds"] == "histogram"
        assert kinds["SeaweedFS_telemetry_replay_seconds"] == "histogram"
        assert kinds["SeaweedFS_telemetry_segments_evicted_total"] \
            == "counter"
        assert tool.telemetry_violations() == []
        # PR-20: QoS admission families (qos/admission.py) — the three
        # counters, the closed shed-reason/priority-class vocabularies
        # with 429/503 mappings, the qos_shed event seam, and the
        # critical qos_shed_interactive rule
        assert "SeaweedFS_qos_admitted_total" in collector_names
        assert "SeaweedFS_qos_shed_total" in collector_names
        assert "SeaweedFS_qos_queued_total" in collector_names
        assert "SeaweedFS_qos_limit_rps" in collector_names
        assert "SeaweedFS_qos_gate" in collector_names
        assert tool.qos_violations() == []
        # PR-26: the phase families (a verb's handlers and steps, the jax
        # backend's transfers and dispatch), the CPU counters beside wall
        # seconds, and their closed label sets
        assert kinds["SeaweedFS_volume_ec_admin_seconds"] == "histogram"
        assert "SeaweedFS_volume_ec_admin_bytes_total" not in kinds
        assert kinds["SeaweedFS_volume_ec_device_seconds"] == "histogram"
        assert kinds["SeaweedFS_volume_ec_device_bytes_total"] == "counter"
        assert kinds["SeaweedFS_volume_ec_device_programs_total"] == "counter"
        # PR-38: device results' copies back, by when each began
        assert kinds["SeaweedFS_volume_ec_device_copies_total"] == "counter"
        # an EC pipeline's edges and its stage threads' CPU
        assert kinds["SeaweedFS_volume_ec_pipeline_edge_seconds"] == "histogram"
        assert "SeaweedFS_volume_ec_pipeline_edge_bytes_total" not in kinds
        assert kinds["SeaweedFS_volume_ec_pipeline_cpu_seconds_total"] \
            == "counter"
        assert kinds["SeaweedFS_volume_ec_decode_cpu_seconds_total"] \
            == "counter"
        assert kinds["SeaweedFS_http_request_cpu_seconds_total"] == "counter"
        assert "SeaweedFS_process_cpu_seconds_total" in collector_names
        # PR-28: bytes of EC read intervals by the rung that served them
        assert kinds["SeaweedFS_volume_ec_read_interval_bytes_total"] \
            == "counter"
        assert tool.phase_label_violations() == []

    @pytest.mark.parametrize("attr,value,complaint", [
        ("EC_ADMIN_OPS", ("generate", "generate"), "duplicate"),
        ("EC_ADMIN_OPS", ("seal.encode",), "undeclared handler"),
        ("EC_ADMIN_OPS", ("Generate",), "malformed"),
        ("EC_ADMIN_OPS", ("generate", "generate.fsync"), "never writes it"),
        ("EC_DEVICE_KERNELS", ("h2d", "d2h_wait"), "malformed"),
        ("EC_DEVICE_KERNELS", ("h2d", "copy-back"), "never writes it"),
        ("EC_READ_INTERVAL_SOURCES", ("local", "local"), "duplicate"),
        ("EC_READ_INTERVAL_SOURCES", ("local", "page-cache"), "never writes it"),
        ("EC_PIPELINE_BUFFER_SOURCES", ("kept", "kept"), "duplicate"),
        ("EC_PIPELINE_BUFFER_SOURCES", ("kept", "warm"), "never writes it"),
        ("EC_PIPELINE_VERBS", ("encode", "repair"), "never writes it"),
        ("EC_PIPELINE_EDGES", ("setup", "setup"), "duplicate"),
        ("EC_PIPELINE_EDGES", ("head", "Tail"), "malformed"),
        ("EC_PIPELINE_EDGES", ("head", "drain"), "never writes it"),
        ("EC_PIPELINE_STAGES", ("read", "transfer"), "never writes it"),
    ])
    def test_phase_label_lint_catches_violations(
            self, monkeypatch, attr, value, complaint):
        from seaweedfs_tpu.stats import trace

        tool = self._tool()
        monkeypatch.setattr(trace, attr, value)
        assert any(complaint in b for b in tool.phase_label_violations())

    def test_qos_lint_catches_violations(self, monkeypatch):
        from seaweedfs_tpu.qos import admission as qos_mod
        from seaweedfs_tpu.stats import alerts

        tool = self._tool()
        monkeypatch.setattr(
            qos_mod, "QOS_FAMILIES",
            tuple(f for f in qos_mod.QOS_FAMILIES
                  if f != "SeaweedFS_qos_shed_total")
            + ("SeaweedFS_qos_BadName",
               "SeaweedFS_usage_not_qos_total"),
        )
        monkeypatch.setattr(
            qos_mod, "SHED_REASONS",
            qos_mod.SHED_REASONS + ("Not-Snake", "unmapped_reason"),
        )
        orig_rules = alerts.default_rules
        monkeypatch.setattr(
            alerts, "default_rules",
            lambda: [r for r in orig_rules()
                     if r.name != "qos_shed_interactive"],
        )
        bad = tool.qos_violations()
        assert any("SeaweedFS_qos_BadName" in b for b in bad)
        assert any("SeaweedFS_usage_not_qos_total" in b
                   and "subsystem" in b for b in bad)
        assert any("SeaweedFS_qos_shed_total" in b
                   and "missing" in b for b in bad)
        assert any("Not-Snake" in b and "snake_case" in b for b in bad)
        assert any("unmapped_reason" in b and "429/503" in b for b in bad)
        assert any("qos_shed_interactive" in b for b in bad)

    def test_cluster_telemetry_lint_catches_violations(self, monkeypatch):
        from seaweedfs_tpu.stats import aggregate

        tool = self._tool()
        monkeypatch.setattr(
            aggregate, "CLUSTER_FAMILIES",
            tuple(f for f in aggregate.CLUSTER_FAMILIES
                  if f != "SeaweedFS_cluster_telemetry_stale")
            + ("SeaweedFS_cluster_BadName",
               "SeaweedFS_usage_not_cluster_total"),
        )
        monkeypatch.setattr(
            aggregate, "CLUSTER_RULES",
            aggregate.CLUSTER_RULES + (
                ("cluster_slo_burn_fast", "critical"),  # duplicate
                ("slo_burn_fast", "critical"),          # missing prefix
                ("cluster_bad_severity", "page-me"),    # unknown severity
            ),
        )
        bad = tool.cluster_telemetry_violations()
        assert any("SeaweedFS_cluster_BadName" in b for b in bad)
        assert any("SeaweedFS_usage_not_cluster_total" in b
                   and "subsystem" in b for b in bad)
        assert any("SeaweedFS_cluster_telemetry_stale" in b
                   and "missing" in b for b in bad)
        assert any("duplicate" in b for b in bad)
        assert any("slo_burn_fast" in b and "prefix" in b for b in bad)
        assert any("page-me" in b for b in bad)

    def test_telemetry_lint_catches_violations(self, monkeypatch):
        from seaweedfs_tpu.stats import alerts
        from seaweedfs_tpu.stats import store as store_mod

        tool = self._tool()
        monkeypatch.setattr(
            store_mod, "TELEMETRY_FAMILIES",
            tuple(f for f in store_mod.TELEMETRY_FAMILIES
                  if f != "SeaweedFS_telemetry_flush_seconds")
            + ("SeaweedFS_telemetry_BadName",
               "SeaweedFS_spool_not_telemetry_bytes"),
        )
        # drop the 10m tier and unbalance the retention shares
        monkeypatch.setattr(
            store_mod, "TIERS",
            (("raw", "raw", 0.25), ("1m", "m1", 0.25),
             ("events", "ev", 0.25)),
        )
        orig_rules = alerts.default_rules
        monkeypatch.setattr(
            alerts, "default_rules",
            lambda: [r for r in orig_rules()
                     if r.name != "telemetry_spool_near_cap"],
        )
        bad = tool.telemetry_violations()
        assert any("SeaweedFS_telemetry_BadName" in b for b in bad)
        assert any("SeaweedFS_spool_not_telemetry_bytes" in b
                   and "subsystem" in b for b in bad)
        assert any("SeaweedFS_telemetry_flush_seconds" in b
                   and "missing" in b for b in bad)
        assert any("'10m'" in b and "TIERS" in b for b in bad)
        assert any("shares" in b for b in bad)
        assert any("telemetry_spool_near_cap" in b for b in bad)

    def test_usage_heat_lint_catches_violations(self, monkeypatch):
        from seaweedfs_tpu.stats import heat, usage

        tool = self._tool()
        monkeypatch.setattr(
            usage, "USAGE_FAMILIES",
            usage.USAGE_FAMILIES + ("SeaweedFS_usage_BadName",),
        )
        monkeypatch.setattr(usage, "OTHER", "other")  # sentinel must be _-prefixed
        monkeypatch.setattr(usage, "DEFAULT_K", 0)
        bad = tool.usage_heat_violations()
        assert any("SeaweedFS_usage_BadName" in b for b in bad)
        assert any("sentinel" in b for b in bad)
        assert any("DEFAULT_K" in b for b in bad)
        monkeypatch.setattr(
            heat, "HEAT_FAMILIES",
            ("seaweedfs_heat_wrong_prefix",) + heat.HEAT_FAMILIES,
        )
        bad = tool.usage_heat_violations()
        assert any("seaweedfs_heat_wrong_prefix" in b for b in bad)

    def test_stream_lazy_lint_catches_violations(self, monkeypatch):
        from seaweedfs_tpu.maintenance import scheduler as sched_mod
        from seaweedfs_tpu.storage.erasure_coding import decoder

        tool = self._tool()
        monkeypatch.setattr(
            decoder, "STREAM_CHUNK_STATES",
            decoder.STREAM_CHUNK_STATES + ("BadState", "forwarded"),
        )
        monkeypatch.setattr(
            sched_mod, "LAZY_OUTCOMES",
            sched_mod.LAZY_OUTCOMES + ("NotSnake",),
        )
        bad = tool.stream_lazy_violations()
        assert any("not snake_case" in b for b in bad)
        assert any("duplicate" in b for b in bad)
        # a streaming failure reason dropped from the restart set is a
        # typed-fallback hole the lint must catch
        monkeypatch.setattr(
            decoder, "REPAIR_RESTART_REASONS",
            tuple(r for r in decoder.REPAIR_RESTART_REASONS
                  if r != "stream_stall"),
        )
        bad = tool.stream_lazy_violations()
        assert any("stream_stall" in b and "restart" in b for b in bad)

    def test_scrub_lint_catches_violations(self, monkeypatch):
        from seaweedfs_tpu.maintenance import scrub

        tool = self._tool()
        monkeypatch.setattr(
            scrub, "SCRUB_FINDING_KINDS",
            scrub.SCRUB_FINDING_KINDS + ("BadKind", "corrupt_needle"),
        )
        bad = tool.scrub_violations()
        assert any("not snake_case" in b for b in bad)
        assert any("duplicate" in b for b in bad)

    def test_event_type_lint_catches_violations(self, monkeypatch):
        from seaweedfs_tpu.stats import events

        tool = self._tool()
        monkeypatch.setattr(
            events, "EVENT_TYPES",
            {**events.EVENT_TYPES, "BadName": "x", "never_emitted": "x"},
        )
        bad = tool.event_type_violations()
        assert any("not snake_case" in b for b in bad)
        assert any("no seam emits it" in b
                   and "never_emitted" in b for b in bad)

    def test_slo_lint_catches_violations(self, monkeypatch):
        from seaweedfs_tpu.stats import alerts

        tool = self._tool()
        monkeypatch.setattr(
            alerts, "DEFAULT_SLOS",
            alerts.DEFAULT_SLOS + (
                alerts.Slo("BadSlo", "volume", "availability", 0.999),
                alerts.Slo("too_greedy", "volume", "availability", 1.5),
                alerts.Slo("no_thresh", "volume", "latency", 0.99),
                alerts.Slo("who", "toaster", "availability", 0.9),
            ),
        )
        bad = tool.slo_violations()
        assert any("not snake_case" in b for b in bad)
        assert any("not in (0, 1)" in b for b in bad)
        assert any("positive" in b and "threshold_s" in b for b in bad)
        assert any("unknown role" in b for b in bad)

    def test_fault_point_name_convention(self):
        tool = self._tool()
        assert tool.FAULT_POINT_RE.match("volume.read.dat")
        assert tool.FAULT_POINT_RE.match("master.assign")
        for bad in ("volume", "Volume.read", "volume..read", "volume.Read",
                    "volume.read-", ".read", "volume.5x"):
            assert not tool.FAULT_POINT_RE.match(bad), bad

    def test_task_type_lint_catches_violations(self, monkeypatch):
        from seaweedfs_tpu import maintenance

        tool = self._tool()
        spec = maintenance.TaskSpec("BadName", 1, 0, "x")
        monkeypatch.setattr(
            maintenance, "TASK_TYPES",
            {**maintenance.TASK_TYPES, "BadName": spec},
        )
        bad = tool.task_type_violations()
        assert any("not snake_case" in b for b in bad)
        assert any("concurrency" in b for b in bad)
        assert any("no matching detector" in b for b in bad)
        assert any("no matching executor" in b for b in bad)

    def test_lint_catches_violations(self):
        tool = self._tool()
        bad = tool.violations(
            {"seaweedfs_tpu_request_total": "counter",     # bad prefix
             "SeaweedFS_volume_reads": "counter",          # counter sans _total
             "SeaweedFS_volume_lat": "histogram",          # histogram sans unit
             "SeaweedFS_volume_free_total": "gauge",       # gauge with _total
             "SeaweedFS_frobnicator_x_total": "counter"},  # unknown subsystem
            [])
        assert len(bad) == 5, bad

    def test_alert_rule_name_convention(self):
        tool = self._tool()
        assert tool.ALERT_RULE_RE.match("http_error_ratio")
        for bad in ("HttpErrors", "5xx_burst", "errors-", "_x", "a__b"):
            assert not tool.ALERT_RULE_RE.match(bad), bad


class TestTTL:
    def test_parse_format(self):
        for s in ["", "3m", "4h", "5d", "6w", "7M", "8y"]:
            t = TTL.parse(s)
            assert str(t) == s
            assert TTL.from_bytes(t.to_bytes()) == t
            assert TTL.from_u32(t.to_u32()) == t

    def test_bare_number_is_minutes(self):
        assert str(TTL.parse("90")) == "90m"
