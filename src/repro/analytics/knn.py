"""Brute-force k-NN retrieval (the paper's end-to-end downstream task, §4.4).

The paper's "2-NN retrieval" = for every point, retrieve its single nearest
OTHER point (self excluded) and check label agreement. Runtime O(m^2 k) —
exactly the shape of DROP's default cost model.

``nearest_neighbors`` is a thin adapter over the fused tiled engine
(``analytics.pairwise``): one jitted scan, one device dispatch, one
device->host transfer, distance tiles never materialized at (block, m).
The pre-engine host-loop path survives as ``nearest_neighbors_legacy`` —
it is the parity oracle and the benchmark baseline
(``benchmarks/bench_pairwise_analytics.py`` tracks the fused speedup).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("block", "use_top_k"))
def _nn_block(
    xq: jax.Array,
    x: jax.Array,
    start: jax.Array,
    block: int,
    use_top_k: bool = False,
):
    """Nearest neighbor of each row of xq among rows of x, self excluded.

    Two self-exclusion strategies, picked per backend by the caller:

    * ``use_top_k`` — one ``top_k(2)`` partial-sort pass over the negated
      distances: reads d2 once, no (b, m) index-grid compare, no rewritten
      distance matrix. If the query's own row is the closest hit the
      runner-up is the neighbor, otherwise the top hit already is. This is
      the accelerator path: on TPU/GPU the mask+argmin+take pipeline is
      three k-independent O(b·m) memory passes, while sort units make
      top_k(2) effectively one.
    * mask+argmin — the CPU path. Measured on XLA:CPU, ``lax.top_k`` is a
      20-40x PESSIMIZATION at these shapes (it lowers to a slow generic
      sort loop), while the where+argmin fuses into a single pass anyway —
      so the O(m²) distance-matrix build is the only remaining
      k-independent term there (see the e2e test's slack comment)."""
    sq_q = jnp.sum(xq * xq, axis=1, keepdims=True)
    sq_x = jnp.sum(x * x, axis=1)
    d2 = sq_q + sq_x[None, :] - 2.0 * jnp.matmul(
        xq, x.T, precision=jax.lax.Precision.HIGHEST
    )  # (b, m)
    rows = start + jnp.arange(xq.shape[0])
    if use_top_k:
        neg_vals, idx = jax.lax.top_k(-d2, 2)  # two smallest per row
        self_first = idx[:, 0] == rows
        nn = jnp.where(self_first, idx[:, 1], idx[:, 0])
        d2_nn = jnp.where(self_first, -neg_vals[:, 1], -neg_vals[:, 0])
        return nn, d2_nn
    cols = jnp.arange(x.shape[0])
    d2 = jnp.where(rows[:, None] == cols[None, :], jnp.inf, d2)
    idx = jnp.argmin(d2, axis=1)
    return idx, jnp.take_along_axis(d2, idx[:, None], axis=1)[:, 0]


def _use_top_k() -> bool:
    """top_k(2) wins on accelerators; on XLA:CPU it is measurably (20-40x)
    slower than the fused mask+argmin at kNN block shapes."""
    return jax.default_backend() != "cpu"


def nearest_neighbors_legacy(x: np.ndarray, block: int = 1024) -> np.ndarray:
    """The pre-engine host loop: one device dispatch AND one blocking
    device->host sync per (block, m) distance tile. Kept as the parity
    oracle / benchmark baseline for the fused engine."""
    x = jnp.asarray(x, dtype=jnp.float32)
    m = x.shape[0]
    # top_k(2) needs 2 candidates; the degenerate m=1 input keeps the mask
    # path (which returns the self index, as before) on every backend
    use_top_k = _use_top_k() and m >= 2
    out = []
    for a in range(0, m, block):
        b = min(a + block, m)
        xq = x[a:b]
        if xq.shape[0] < block:  # pad to keep a single compiled shape
            pad = block - xq.shape[0]
            xq = jnp.pad(xq, ((0, pad), (0, 0)))
            idx, _ = _nn_block(xq, x, jnp.int32(a), block, use_top_k)
            out.append(np.asarray(idx)[: b - a])
        else:
            idx, _ = _nn_block(xq, x, jnp.int32(a), block, use_top_k)
            out.append(np.asarray(idx))
    return np.concatenate(out)


def nearest_neighbors(
    x: np.ndarray,
    block: int = 1024,
    *,
    use_kernels: bool = False,
    split: int | None = None,
    fanout: str = "xla",
    devices=None,
) -> np.ndarray:
    """Index of the nearest other point for every row — one fused scan.

    ``split=N`` runs the dataset axis as N flash-decoding-style shards
    (``fanout="mesh"`` fans them across devices); results are bit-identical
    to the sequential scan for every shard count (``analytics.split``)."""
    if split is not None or fanout == "mesh":
        from repro.analytics.split import split_pairwise_knn

        idx, _ = split_pairwise_knn(
            x, shards=split or 1, block_q=block, block_k=block,
            use_kernels=use_kernels, fanout=fanout, devices=devices,
        )
        return idx
    from repro.analytics.pairwise import pairwise_knn

    idx, _ = pairwise_knn(x, block, block, use_kernels=use_kernels)
    return idx


def knn_retrieval_accuracy(
    x: np.ndarray,
    labels: np.ndarray,
    block: int = 1024,
    *,
    use_kernels: bool = False,
    split: int | None = None,
    fanout: str = "xla",
    devices=None,
) -> float:
    """Label agreement rate of 1-NN retrieval (paper Table 2/4 metric)."""
    nn = nearest_neighbors(
        x, block=block, use_kernels=use_kernels,
        split=split, fanout=fanout, devices=devices,
    )
    return float((labels[nn] == labels).mean())
