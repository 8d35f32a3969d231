"""The mesh kNN fan-out's share of its roofline: the least time of every
query's scan at its real m and k (``bench.costs``), at the peaks of the n
chips it is split over, over one chip's device time in the ``knn_mesh``
layer. n is the mesh width the window's counters give,
``ServiceStats.mesh_shards / mesh_scans``."""

from bench import costs


def read(ctx):
    ms = ctx["layer_ms"].get("knn_mesh")
    scans = ctx["stats"].get("mesh_scans")
    if not ms or not scans or not ctx["requests"]:
        return None
    n = ctx["stats"]["mesh_shards"] / scans
    least = sum(costs.least_time_s(*costs.knn_scan(r["m"], r["k"]), ctx["peaks"])
                for r in ctx["requests"])
    return 100.0 * least / n / (ms * 1e-3)
