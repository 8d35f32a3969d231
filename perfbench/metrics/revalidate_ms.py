"""Host ms of cache-hit revalidation per answered query: the sampled TLB
estimate, device calls and reads included (``ServiceStats.validate_s``,
the ``drop.item.validate`` span)."""


def read(ctx):
    s = ctx["stats"].get("validate_s")
    return 1e3 * s / len(ctx["requests"]) if ctx["requests"] and s is not None else None
