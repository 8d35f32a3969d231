"""TLB device calls (confidence-interval doublings) of revalidation per
answered query (``ServiceStats.tlb_rounds``, the ``tlb.extend`` spans)."""


def read(ctx):
    n = ctx["stats"].get("tlb_rounds")
    return n / len(ctx["requests"]) if ctx["requests"] and n is not None else None
