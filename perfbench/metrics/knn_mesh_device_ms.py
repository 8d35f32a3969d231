"""Device ms of the mesh kNN fan-out (the ``knn_mesh`` layer map) per
query: the average over the trace's devices, as ``trace.reduce_trace``
gives every layer, so the time one chip spends on its dataset shards."""


def read(ctx):
    ms = ctx["layer_ms"].get("knn_mesh")
    return ms / len(ctx["requests"]) if ctx["requests"] and ms else None
