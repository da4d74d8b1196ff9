"""Seconds of a verb, as the client waits for it, that the server's EC
pipeline does not account for: shell start, locks, RPCs, index and mounts.

Client verb seconds minus the pipeline's wall, per completed verb, over the
window. The pipeline's wall is the busy + wait seconds of one stage of the
server's `<family>` histogram: a named stage, or with `stage: "longest"` the
stage whose busy + wait is largest (every stage spans the pipeline's run).
"""

from benchlib import promtext


def read(ctx: dict, family: str, stage: str = "longest"):
    win = ctx["window"]
    verbs = [v for v in win["verbs"] if v["ok"]]
    if not verbs:
        return None
    stages = promtext.grown_by_two(win["before"]["metrics"], win["after"]["metrics"],
                                   family + "_sum", "stage", "state")
    walls = {s: d.get("busy", 0.0) + d.get("wait", 0.0) for s, d in stages.items()}
    if not walls:
        return None
    wall = max(walls.values()) if stage == "longest" else walls.get(stage)
    if not wall:
        return None
    return (sum(v["seconds"] for v in verbs) - wall) / len(verbs)
