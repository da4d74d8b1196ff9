"""Share of the traced span, in percent, in which no operation ran on the
device: 1 - union of the device-operation intervals over the span, from the
trace the server took of itself."""


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace or trace["busy_s"] <= 0 or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
