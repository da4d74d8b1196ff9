"""Growth of one sample family over the growth of another, times a scale,
over the window or the traced span: a mean time per operation from a
histogram's `_sum` and `_count`, for one."""

from benchlib import promtext


def read(ctx: dict, numerator: dict, denominator: dict, scale: float = 1.0,
         scope: str = "window"):
    pages = ctx.get(scope)
    if not pages:
        return None
    before = pages["before"]["metrics"] if scope == "window" else pages["before"]
    after = pages["after"]["metrics"] if scope == "window" else pages["after"]
    num = promtext.delta(before, after, numerator["name"], **numerator.get("labels", {}))
    den = promtext.delta(before, after, denominator["name"], **denominator.get("labels", {}))
    if den <= 0:
        return None
    return scale * num / den
