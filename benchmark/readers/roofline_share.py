"""Least time the chip could take for the algorithm's work in the traced
span over the device's busy time in it, in percent.

The work is counted from the server's byte counter, not from any kernel's
name: each counted byte stands for `hbm_bytes_per_byte` bytes moved to or
from device memory and `int8_ops_per_byte` int8 operations of the bit-plane
matrix form, whatever implements it. Peaks come from benchmark/peaks.json by
the device's kind; a kind that is not there is an error. Says which bound
applies."""

import json

from benchlib import promtext


def read(ctx: dict, counter: str, labels: dict, hbm_bytes_per_byte: float,
         int8_ops_per_byte: float):
    trace, span = ctx.get("trace"), ctx.get("span")
    if not trace or not span or trace["busy_s"] <= 0:
        return None
    counted = promtext.delta(span["before"], span["after"], counter, **labels)
    if counted <= 0:
        return None
    with open(ctx["peaks_file"]) as f:
        peaks = json.load(f)
    kind = ctx["device_kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in {ctx['peaks_file']}")
    by_bound = {
        "hbm": counted * hbm_bytes_per_byte / peaks[kind]["hbm_bytes_per_s"],
        "int8": counted * int8_ops_per_byte / peaks[kind]["int8_ops_per_s"],
    }
    bound = max(by_bound, key=by_bound.get)
    return (100.0 * by_bound[bound] / trace["busy_s"],
            f"bound={bound}; counted_bytes={counted:.6g}; least_s={by_bound[bound]:.6g}")
