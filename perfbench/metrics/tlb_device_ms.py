"""Device ms of TLB estimation and search (the ``tlb`` layer map) per query."""


def read(ctx):
    ms = ctx["layer_ms"].get("tlb")
    return ms / len(ctx["requests"]) if ctx["requests"] and ms else None
