"""Growth of a counter over the window or the traced span: the coefficient
matrices the RS door put on a device while the window ran, for one. None
where the page has no such counter (a program without it)."""

from benchlib import promtext


def read(ctx: dict, counter: dict, scope: str = "window"):
    pages = ctx.get(scope)
    if not pages:
        return None
    before = pages["before"]["metrics"] if scope == "window" else pages["before"]
    after = pages["after"]["metrics"] if scope == "window" else pages["after"]
    if not any(name == counter["name"] for name, _ in after):
        return None
    return promtext.delta(before, after, counter["name"], **counter.get("labels", {}))
