"""Busy seconds of the busiest stage of the EC pipeline over the pipeline's
wall, in percent, over the window; says which stage it is. The wall is the
largest busy + wait of any stage (each stage's thread lives as long as the
pipeline runs)."""

from benchlib import promtext


def read(ctx: dict, family: str):
    win = ctx["window"]
    stages = promtext.grown_by_two(win["before"]["metrics"], win["after"]["metrics"],
                                   family + "_sum", "stage", "state")
    stages = {s: d for s, d in stages.items() if d.get("busy", 0.0) > 0}
    if not stages:
        return None
    wall = max(d.get("busy", 0.0) + d.get("wait", 0.0) for d in stages.values())
    busiest = max(stages, key=lambda s: stages[s]["busy"])
    ctx["busiest_stage"] = busiest
    return 100.0 * stages[busiest]["busy"] / wall, f"stage={busiest}"
