"""Growth of a counter over the seconds of the window or of the traced span,
times a scale: CPU seconds per second as a share of one core, for one. None
where the page has no such counter (a program without it)."""

from benchlib import promtext


def read(ctx: dict, counter: dict, scale: float = 1.0, scope: str = "window"):
    pages = ctx.get(scope)
    if not pages or pages["seconds"] <= 0:
        return None
    before = pages["before"]["metrics"] if scope == "window" else pages["before"]
    after = pages["after"]["metrics"] if scope == "window" else pages["after"]
    if not any(name == counter["name"] for name, _ in after):
        return None
    grew = promtext.delta(before, after, counter["name"], **counter.get("labels", {}))
    return scale * grew / pages["seconds"]
