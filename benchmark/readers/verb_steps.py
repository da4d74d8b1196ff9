"""One traced verb split by what the volume server timed inside it.

Reads the pages around the traced span (`ctx["span"]`: one whole verb, the
one the loop marked `traced`) and the server's `<family>` histogram, whose
`op` label names a whole handler (`generate`) or, dotted, a step nested in
one (`generate.encode`). The window will not do: between verbs the runner's
own restore posts to the same handlers.

mode "client":  the client's seconds for the verb (the traced cycle) minus
                the growth of every whole handler: what is left is outside
                the volume server, that is shell start, imports, lock and
                unlock, topology fetches, the wire.
mode "largest": the largest among the steps and the handlers that have no
                step, outside `exclude`; says which, and the seconds of every
                op the verb called.

None where the page has no such family (a program without the counters).
"""

from benchlib import promtext


def read(ctx: dict, family: str, mode: str, exclude: tuple = ()):
    span = ctx.get("span")
    traced = [v for v in ctx["window"]["verbs"] if v.get("traced") and v["ok"]]
    if not span or len(traced) != 1:
        return None
    grew = promtext.by_label(span["before"], span["after"], family + "_sum", "op")
    if not grew:
        return None
    ops = {op: s for op, s in grew.items() if s > 0}  # those the verb called
    said = " ".join(f"{op}={ops[op]:.6g}" for op in sorted(ops))
    whole = {op for op in ops if "." not in op}
    if mode == "client":
        return traced[0]["cycle_seconds"] - sum(ops[op] for op in whole), said
    if mode != "largest":
        raise ValueError(f"verb_steps: unknown mode {mode!r}")
    stepped = {op.split(".")[0] for op in ops if "." in op}
    among = {op: s for op, s in ops.items()
             if op not in exclude and op not in stepped}
    if not among:
        return None
    largest = max(among, key=among.get)
    return among[largest], f"largest={largest}; {said}"
