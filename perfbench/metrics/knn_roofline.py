"""The fused kNN scan's share of its roofline: the least time of every
query's scan at its real m and k (``bench.costs``), at the chip's peaks,
over the scans' device time."""

from bench import costs


def read(ctx):
    ms = ctx["layer_ms"].get("knn")
    if not ms or not ctx["requests"]:
        return None
    least = sum(costs.least_time_s(*costs.knn_scan(r["m"], r["k"]), ctx["peaks"])
                for r in ctx["requests"])
    return 100.0 * least / (ms * 1e-3)
