"""How many of the host's chips ran at least one operation in the traced
span: the entries above zero of the trace's `busy_s_per_chip` (one entry a
chip's plane, 0.0 for a chip on which nothing ran). None where the trace
holds no chip's plane (a rehearsal on the CPU)."""


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s_per_chip"):
        return None
    per_chip = trace["busy_s_per_chip"]
    return (float(sum(1 for s in per_chip if s > 0)),
            "busy_s_per_chip=" + ",".join(f"{s:.6g}" for s in per_chip))
