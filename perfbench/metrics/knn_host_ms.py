"""Host-clock ms per answered query of the served kNN item not covered by
the scan's device time: ``ServiceStats.downstream_s`` less the ``knn``
layer's device ms."""


def read(ctx):
    s = ctx["stats"].get("downstream_s")
    device_ms = ctx["layer_ms"].get("knn")
    if not ctx["requests"] or s is None or device_ms is None:
        return None
    return (1e3 * s - device_ms) / len(ctx["requests"])
