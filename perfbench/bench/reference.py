"""Plain float64 host references, independent of the program under test.

Copied in spirit from the repository's chip smoke (``reference_tlb``,
``knn_excess``) and rewritten so that they import nothing of the program:
the TLB is Eq. 1 of the DROP paper on sampled pairs, the kNN is brute force
over exact float64 distances. What they take from a run is only its answer:
the served map and the kNN.
"""

from __future__ import annotations

import numpy as np
from scipy import stats as _stats

ROW_BLOCK = 512  # query rows per float64 distance block (~70 MB at m=17k)


def sample_pairs(m: int, p: int, rng: np.random.Generator) -> np.ndarray:
    """``p`` index pairs (i, j), i != j, drawn uniformly."""
    i = rng.integers(0, m, size=p)
    j = rng.integers(0, m - 1, size=p)
    j = np.where(j >= i, j + 1, j)
    return np.stack([i, j], axis=1)


def tlb(x: np.ndarray, v: np.ndarray, pairs: np.ndarray) -> tuple[float, float]:
    """Mean TLB (Eq. 1) of the linear map ``v`` (d, k) on ``pairs``, and the
    standard error of that mean: ratio of reduced to raw pair distances,
    float64, pairs of identical rows counting 1."""
    dx = x[pairs[:, 0]].astype(np.float64) - x[pairs[:, 1]].astype(np.float64)
    dz = dx @ np.asarray(v, np.float64)
    raw = np.sqrt((dx * dx).sum(1))
    red = np.sqrt((dz * dz).sum(1))
    ratio = np.where(raw > 0, red / np.where(raw > 0, raw, 1.0), 1.0)
    return float(ratio.mean()), float(ratio.std(ddof=1) / np.sqrt(len(ratio)))


def z_two_sided(confidence: float) -> float:
    """Normal quantile of a two-sided interval at ``confidence``."""
    return float(_stats.norm.ppf(0.5 + confidence / 2.0))


def reduce_rows(x: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Rows of ``x`` through the map ``v`` in float64, centred by the data's
    own mean (centering cancels in every pair distance)."""
    x64 = x.astype(np.float64)
    return (x64 - x64.mean(0)) @ np.asarray(v, np.float64)


def nearest(z: np.ndarray, rows: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """For each of ``rows``: the float64 squared distance to its nearest
    OTHER row of ``z``, its index, and the scale |z_i|^2 + max_j |z_j|^2 of
    the distance expansion."""
    sq = np.einsum("ij,ij->i", z, z)
    best = np.empty(len(rows))
    arg = np.empty(len(rows), np.int64)
    for a in range(0, len(rows), ROW_BLOCK):
        r = rows[a:a + ROW_BLOCK]
        d2 = sq[r, None] + sq[None, :] - 2.0 * z[r] @ z.T
        d2[np.arange(len(r)), r] = np.inf
        arg[a:a + len(r)] = d2.argmin(1)
        best[a:a + len(r)] = d2[np.arange(len(r)), arg[a:a + len(r)]]
    return best, arg, sq[rows] + sq.max()


def knn_excess(z: np.ndarray, idx: np.ndarray, rows: np.ndarray) -> float:
    """Worst excess, over ``rows``, of the served neighbour's float64
    squared distance over the true nearest one, as a share of the scale of
    the distance expansion. Exact arithmetic reads 0; float32 at full
    precision reads ~1e-7; a served neighbour that is not among the nearest
    reads the gap between them."""
    idx = np.asarray(idx)
    best, _, scale = nearest(z, rows)
    served = z[rows] - z[idx[rows]]
    served_d2 = np.einsum("ij,ij->i", served, served)
    bad = (idx[rows] == rows) | (idx[rows] < 0) | (idx[rows] >= len(z))
    excess = np.where(bad, np.inf, (served_d2 - best) / scale)
    return float(excess.max())
