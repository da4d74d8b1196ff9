"""Growth over the window of one number of the server's `GET /status`,
named by its path, as `ec.compiles.requests`."""


def dig(obj, path: str):
    for key in path.split("."):
        if not isinstance(obj, dict) or key not in obj:
            return None
        obj = obj[key]
    return obj


def read(ctx: dict, path: str):
    win = ctx["window"]
    end = dig(win["after"]["status"], path)
    if end is None:
        return None
    start = dig(win["before"]["status"], path) or 0
    return float(end - start)
