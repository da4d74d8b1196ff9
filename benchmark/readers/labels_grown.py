"""How many values of one label of a counter grew over the window or the
traced span: the rungs of the kernel's width ladder that the door's calls ran
at, for one. The note lists the values, with how much each grew, in the
order of their numbers where they are numbers. None where the page has no
such counter (a program without it), or where no value grew: nothing was
counted to read (a rehearsal on the CPU, whose read path does not reach the
door)."""

from benchlib import promtext


def _order(value: str):
    return (0, int(value), "") if value.isdigit() else (1, 0, value)


def read(ctx: dict, counter: str, label: str, scope: str = "window"):
    pages = ctx.get(scope)
    if not pages:
        return None
    before = pages["before"]["metrics"] if scope == "window" else pages["before"]
    after = pages["after"]["metrics"] if scope == "window" else pages["after"]
    if not any(name == counter for name, _ in after):
        return None
    grew = {v: d for v, d in promtext.by_label(before, after, counter, label).items()
            if d > 0}
    if not grew:
        return None
    note = ",".join(f"{v}:{grew[v]:.6g}" for v in sorted(grew, key=_order))
    return float(len(grew)), f"{label}={note}"
