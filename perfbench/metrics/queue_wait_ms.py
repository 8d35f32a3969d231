"""Ms per answered query from enqueue to the admission pass that routes it,
deferrals included (``ServiceStats.queue_wait_s``)."""


def read(ctx):
    s = ctx["stats"].get("queue_wait_s")
    return 1e3 * s / len(ctx["requests"]) if ctx["requests"] and s is not None else None
