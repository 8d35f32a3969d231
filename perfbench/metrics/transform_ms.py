"""Host ms per answered query of projecting the dataset through the served
map before its analytics (``ServiceStats.transform_s``, the
``drop.transform`` span)."""


def read(ctx):
    s = ctx["stats"].get("transform_s")
    return 1e3 * s / len(ctx["requests"]) if ctx["requests"] and s is not None else None
