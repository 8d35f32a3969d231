"""Ms per answered query that work items (revalidation, analytics, runner
steps) wait in their deque for a drain thread (``ServiceStats.work_wait_s``)."""


def read(ctx):
    s = ctx["stats"].get("work_wait_s")
    return 1e3 * s / len(ctx["requests"]) if ctx["requests"] and s is not None else None
