"""`roofline_share` where several chips share the span's work: the same
count of work (same counter, labels and costs a byte, same peaks.json) over
the sum of every chip's busy time, not over the mean of the chips used. With
the work spread evenly it reads what one chip's share reads; however the
chips are counted, it cannot pass what the busiest chip could do. None where
the trace holds no chip's plane."""

from readers import roofline_share


def read(ctx: dict, **args):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s_per_chip"):
        return None
    busy = sum(trace["busy_s_per_chip"])
    return roofline_share.read({**ctx, "trace": {**trace, "busy_s": busy}}, **args)
