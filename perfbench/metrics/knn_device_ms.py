"""Device ms of the fused kNN scan (the ``knn`` layer map) per query."""


def read(ctx):
    ms = ctx["layer_ms"].get("knn")
    return ms / len(ctx["requests"]) if ctx["requests"] and ms else None
