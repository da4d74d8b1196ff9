"""The bench record must be parseable: bench.py's final stdout line is what
a recorder keeps, and an oversized line loses the headline. These tests pin
the compact-summary contract and the shape of the device status."""

from __future__ import annotations

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import bench


def _representative_detail() -> dict:
    # worst-case realistic payload: every field populated, long error string
    return {
        "hash_1m_4k": {
            "native_batch_mhashes_s": 0.464,
            "native_batch_gbps": 1.901,
            "device_batch_error": "x" * 300,
        },
        "ec_rebuild": {"gbps": 3.141, "trial_seconds": [0.318, 0.322, 0.319]},
        "cdc_dedup": {"gbps": 2.105, "gbps_p75_window": 2.207},
        "small_files": {
            "write_req_s": 61712.4,
            "read_req_s": 95558.1,
            "write_assign_per_file_req_s": 12114.9,
            "python_client": {"write_req_s": 3036.5, "read_req_s": 5751.2},
        },
        "filer_small_files": {"write_req_s": 15123.4, "read_req_s": 41234.5},
        "device_kernel_gbps": 123.456,
        "device_pipeline_e2e_gbps": 0.031,
    }


def test_summary_line_is_compact_and_parseable():
    line = bench.summary_line(
        verb_gbps=4.227,
        seq_gfni=1.832,
        backend="native",
        verb_info={"trial_seconds": [0.256, 0.256, 0.254]},
        dev={"status": "up", "platform": "tpu", "device_kind": "TPU v5 lite",
             "count": 1, "h2d_mbps": 2970.0},
        detail=_representative_detail(),
    )
    assert len(line) <= 1500, f"summary line {len(line)} chars > 1500"
    parsed = json.loads(line)
    assert parsed["metric"] == "ec.encode"
    assert parsed["value"] == 4.227
    assert parsed["vs_baseline"] == 2.31
    assert parsed["extra"]["device_status"] == "up"
    assert parsed["extra"]["device_h2d_mbps"] == 2970.0
    assert parsed["extra"]["ec_rebuild_gbps"] == 3.141
    assert parsed["extra"]["filer_write_req_s"] == 15123.4
    assert parsed["extra"]["hash_device_gbps"] is None  # error went elsewhere
    assert len(parsed["extra"]["hash_device_error"]) <= 60


def test_summary_line_survives_empty_detail():
    # every sub-bench failed: the line must still parse and carry the status
    line = bench.summary_line(
        verb_gbps=0.0,
        seq_gfni=float("nan"),
        backend="python",
        verb_info={},
        dev={"status": "down", "h2d_mbps": None,
             "reason": "jax computes on the cpu"},
        detail={},
    )
    # strict RFC-8259 parse: a bare NaN token (json.dumps default for
    # float('nan')) must never reach the driver
    parsed = json.loads(line, parse_constant=lambda t: (_ for _ in ()).throw(
        AssertionError(f"non-strict JSON token {t!r} in summary line")))
    assert len(line) <= 1500
    assert parsed["extra"]["device_status"] == "down"
    assert parsed["extra"]["baseline_seq_gfni_gbps"] is None
    assert parsed["vs_baseline"] == 0.0


def test_fastlane_summary_from_metrics():
    """PR-2: native ratio + per-op p50/p99 computed from the scraped
    SeaweedFS_volume_fastlane_* series (recorded into BENCH_full.json)."""
    text = "\n".join([
        '# TYPE SeaweedFS_volume_fastlane_requests_total counter',
        'SeaweedFS_volume_fastlane_requests_total{server="h:1",op="read"} 60',
        'SeaweedFS_volume_fastlane_requests_total{server="h:1",op="write"} 40',
        'SeaweedFS_volume_fastlane_proxied_total{server="h:1"} 25',
        'SeaweedFS_volume_fastlane_request_seconds_bucket'
        '{server="h:1",op="write",le="0.001"} 20',
        'SeaweedFS_volume_fastlane_request_seconds_bucket'
        '{server="h:1",op="write",le="0.01"} 39',
        'SeaweedFS_volume_fastlane_request_seconds_bucket'
        '{server="h:1",op="write",le="+Inf"} 40',
        'SeaweedFS_volume_fastlane_request_seconds_count'
        '{server="h:1",op="write"} 40',
    ])
    out = bench.fastlane_summary_from_metrics(text)
    assert out["native_requests"] == 100 and out["proxied_requests"] == 25
    assert out["fastlane_native_ratio"] == 0.8
    w = out["ops"]["write"]
    assert w["count"] == 40
    # p50: rank 20 lands exactly on the 1ms bucket boundary
    assert w["p50_ms"] == 1.0
    # p99: rank 39.6 falls in the overflow bucket -> lower edge (10ms)
    assert w["p99_ms"] == 10.0
    # empty scrape: no division by zero, ratio None
    empty = bench.fastlane_summary_from_metrics("")
    assert empty["fastlane_native_ratio"] is None and empty["ops"] == {}


def test_summary_line_survives_minimal_status_dict():
    # a status dict with nothing but the status must still give a line
    # that carries device_status and parses strictly
    line = bench.summary_line(
        verb_gbps=1.0,
        seq_gfni=1.0,
        backend="native",
        verb_info={},
        dev={"status": "down"},  # no h2d_mbps, no reason
        detail={"ec_online": {"ec_online_encode_gbps": 2.1,
                              "write_amplification": 1.41,
                              "pathological_fallbacks": 0}},
    )
    parsed = json.loads(line)
    assert parsed["extra"]["device_status"] == "down"
    assert parsed["extra"]["device_h2d_mbps"] is None
    # the online-EC acceptance scalars ride in the compact line
    assert parsed["extra"]["ec_online_encode_gbps"] == 2.1
    assert parsed["extra"]["ec_online_wa"] == 1.41
    assert parsed["extra"]["ec_online_bad_fallbacks"] == 0


def test_device_status_shape():
    # tests run on the CPU backend, so there is no accelerator: that must
    # be a reported fact with its reason, never an exception or a guess
    st = bench.device_status()
    assert st["status"] == "down"
    assert st["h2d_mbps"] is None
    assert st["reason"] == "jax computes on the cpu"
