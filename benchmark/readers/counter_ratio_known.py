"""`counter_ratio`, for a numerator that an older program does not have:
None, not 0, where the page has no sample of the numerator's name at all."""

from readers import counter_ratio


def read(ctx: dict, numerator: dict, denominator: dict, scale: float = 1.0,
         scope: str = "window"):
    pages = ctx.get(scope)
    if not pages:
        return None
    after = pages["after"]["metrics"] if scope == "window" else pages["after"]
    if not any(name == numerator["name"] for name, _ in after):
        return None
    return counter_ratio.read(ctx, numerator, denominator, scale, scope)
