"""Host-clock ms per answered query of the served kNN item not covered by
the mesh fan-out's device time: ``ServiceStats.downstream_s`` less the
``knn_mesh`` layer's device ms. That is the transform, the padding, the
gather of the partials and their merge on the host."""


def read(ctx):
    s = ctx["stats"].get("downstream_s")
    device_ms = ctx["layer_ms"].get("knn_mesh")
    if not ctx["requests"] or s is None or not device_ms:
        return None
    return (1e3 * s - device_ms) / len(ctx["requests"])
