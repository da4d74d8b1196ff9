"""/metrics parsing and deltas, the percentile, interval unions."""

import pytest

from benchlib import promtext, stats

PAGE_A = """# HELP x_seconds a histogram
# TYPE x_seconds histogram
x_seconds_sum{stage="read",state="busy"} 1.5
x_seconds_sum{stage="read",state="wait"} 0.25
x_seconds_count{stage="read",state="busy"} 4
y_bytes_total{kernel="pipeline-pallas"} 1.07378e+09
plain 3
"""
PAGE_B = PAGE_A.replace("} 4\n", "} 9\n").replace("1.5", "4.0").replace(
    "1.07378e+09", "2.14757e+09") + 'y_bytes_total{kernel="fused"} 12\n'


def test_parse_and_delta():
    a, b = promtext.parse(PAGE_A), promtext.parse(PAGE_B)
    assert a[("plain", ())] == 3
    assert promtext.total(a, "x_seconds_sum", stage="read") == 1.75
    assert promtext.delta(a, b, "x_seconds_sum", stage="read", state="busy") == 2.5
    assert promtext.delta(a, b, "x_seconds_count") == 5
    grew = promtext.by_label(a, b, "y_bytes_total", "kernel")
    assert grew["fused"] == 12          # absent before: started at 0
    assert grew["pipeline-pallas"] == pytest.approx(1.07379e9)


def test_percentile_is_nearest_rank_over_all_values():
    values = list(range(1, 101))
    assert stats.percentile(values, 95) == 95
    assert stats.percentile(values, 50) == 50
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([1, 2, 3, 4], 95) == 4
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_quartile_spread():
    assert stats.quartile_spread([10, 10, 10, 10, 10, 10]) == 0
    assert stats.quartile_spread([9, 10, 10, 10, 10, 11]) == pytest.approx(0.05)


def test_union_counts_overlaps_once():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert stats.union_length(iv) == pytest.approx(3.0)
    assert stats.union_length([]) == 0.0


def test_interpolate():
    a, b = promtext.parse(PAGE_A), promtext.parse(PAGE_B)
    mid = promtext.interpolate(a, b, 0.5)
    assert mid[("x_seconds_count", (("stage", "read"), ("state", "busy")))] == 6.5
    assert mid[("y_bytes_total", (("kernel", "fused"),))] == 6       # from 0
    assert promtext.interpolate(a, b, 7.0) == {**a, **b}            # clamped
