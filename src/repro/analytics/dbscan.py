"""DBSCAN (Ester et al. 1996) — the paper's second end-to-end task (§4.4).

The device side is one fused tiled scan (``analytics.pairwise``): eps-ball
degree counts + packed uint32 neighbor bitmasks in a single dispatch and a
single device->host transfer. The host BFS consumes the packed bits — core
checks read the precomputed degrees, and a row is only ever decoded
(``unpack_neighbors``) when the expansion actually visits it, replacing the
legacy per-row ``np.nonzero`` over m boolean matrix rows.

``dbscan_legacy`` keeps the pre-engine blocked host loop as the parity
oracle / benchmark baseline. Both paths share ``_bfs``, so fused-vs-legacy
label parity is exact (identical traversal order — DBSCAN border-point
labels are traversal-order dependent).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

NOISE = -1
UNVISITED = -2


@partial(jax.jit, static_argnames=())
def _radius_block(xq: jax.Array, x: jax.Array, eps2: jax.Array) -> jax.Array:
    sq_q = jnp.sum(xq * xq, axis=1, keepdims=True)
    sq_x = jnp.sum(x * x, axis=1)
    d2 = sq_q + sq_x[None, :] - 2.0 * jnp.matmul(
        xq, x.T, precision=jax.lax.Precision.HIGHEST
    )
    return d2 <= eps2


def _neighbor_lists(x: np.ndarray, eps: float, block: int = 1024) -> list[np.ndarray]:
    xs = jnp.asarray(x, dtype=jnp.float32)
    eps2 = jnp.float32(eps * eps)
    m = x.shape[0]
    out: list[np.ndarray] = []
    for a in range(0, m, block):
        xq = xs[a : a + block]
        n = xq.shape[0]
        if n < block:
            # pad the remainder to the full block: every tail shape used to
            # mint a fresh XLA executable (one compile per distinct m %
            # block); padded rows are sliced off before the host scan
            xq = jnp.pad(xq, ((0, block - n), (0, 0)))
        mask = np.asarray(_radius_block(xq, xs, eps2))[:n]
        for r in range(n):
            nbrs = np.nonzero(mask[r])[0]
            out.append(nbrs[nbrs != a + r])
    return out


def _bfs(
    m: int,
    min_samples: int,
    degrees: np.ndarray,
    neighbors: Callable[[int], np.ndarray],
) -> np.ndarray:
    """The (host) expansion shared by the fused and legacy paths.

    ``degrees`` INCLUDE the self point (a point is always within eps of
    itself); ``neighbors(p)`` returns p's eps-neighbors sorted ascending,
    self excluded — the exact arrays the legacy path precomputed, so the
    traversal (and with it every border-point label) is identical."""
    labels = np.full(m, UNVISITED, dtype=np.int64)
    cluster = 0
    for p in range(m):
        if labels[p] != UNVISITED:
            continue
        if degrees[p] < min_samples:
            labels[p] = NOISE
            continue
        labels[p] = cluster
        frontier = list(neighbors(p))
        while frontier:
            q = frontier.pop()
            if labels[q] == NOISE:
                labels[q] = cluster
            if labels[q] != UNVISITED:
                continue
            labels[q] = cluster
            if degrees[q] >= min_samples:
                frontier.extend(neighbors(q))
        cluster += 1
    return labels


def dbscan(
    x: np.ndarray,
    eps: float = 0.5,
    min_samples: int = 5,
    block: int = 1024,
    *,
    use_kernels: bool = False,
    split: int | None = None,
    fanout: str = "xla",
    devices=None,
) -> np.ndarray:
    """Cluster labels per point; -1 = noise. One fused device scan.

    ``split=N`` shards the device scan (``analytics.split``); counts and
    packed bitmasks merge bit-identically, so the BFS — and every
    traversal-order-dependent border label — is unchanged."""
    from repro.analytics.pairwise import NeighborDecoder, pairwise_dbscan

    m = x.shape[0]
    if split is not None or fanout == "mesh":
        from repro.analytics.split import split_pairwise_dbscan

        counts, packed = split_pairwise_dbscan(
            x, eps, shards=split or 1, block_q=block, block_k=block,
            use_kernels=use_kernels, fanout=fanout, devices=devices,
        )
    else:
        counts, packed = pairwise_dbscan(
            x, eps, block, block, use_kernels=use_kernels
        )
    return _bfs(m, min_samples, counts, NeighborDecoder(packed, m))


def dbscan_legacy(
    x: np.ndarray, eps: float = 0.5, min_samples: int = 5, block: int = 1024
) -> np.ndarray:
    """The pre-engine path: blocked radius queries with a host sync per
    block and eager per-row ``np.nonzero``. Parity oracle / benchmark
    baseline."""
    m = x.shape[0]
    nbrs = _neighbor_lists(x, eps, block=block)
    degrees = np.array([n.size + 1 for n in nbrs])
    return _bfs(m, min_samples, degrees, lambda p: nbrs[p])
