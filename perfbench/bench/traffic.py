"""Open-loop request schedules, in absolute requests per second.

Copied from the repository's serving-trace generators (Poisson arrivals,
Zipf popularity) with one change of unit:
their times were multiples of a warm serve measured in the same run, so a
faster program was offered more load. Here a rate is requests per second of
wall time, fixed in the traffic file.

The number of requests, the multiset of inter-arrival gaps (the
exponential distribution's quantiles) and the number of requests per
popularity rank are fixed by the traffic file; the generator given to these
functions only orders them. The order is stratified in blocks of
``block_requests`` consecutive requests: each block holds one gap from each
of that many strata of the sorted gaps, and one request from each stratum
of the requests sorted by rank, so every block of the window offers about
the same load and the same mix. A fully shuffled order lets the generator
decide where the busy periods fall, which moves the latency percentiles.
Even within blocks the order sets which requests queue behind which, and
moved the median by some 6% from one order to another on a TPU v5e, so
the query driver (``bench/serve.py``) draws the order from a fixed stream,
the same in every run: runs with different seeds then differ by the data
alone, not by how much work they offer, when, or in what order.
"""

from __future__ import annotations

import numpy as np


def zipf_weights(n: int, a: float) -> np.ndarray:
    """Normalized Zipf popularity over ranks 1..n."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** a
    return w / w.sum()


def quota(n_requests: int, weights: np.ndarray) -> np.ndarray:
    """Requests per rank: ``n_requests * weights`` by largest remainder."""
    exact = n_requests * np.asarray(weights, np.float64)
    counts = np.floor(exact).astype(np.int64)
    short = n_requests - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


def stratified(items: np.ndarray, block: int, rng: np.random.Generator) -> np.ndarray:
    """``items`` in the seed's order, in blocks of ``block``: the sorted
    multiset is cut into ``block`` strata of consecutive items, each block
    takes one item of each stratum, and the seed orders items within each
    stratum across blocks and within each block. The ``len % block`` items
    left over, evenly spaced in sorted order, close the sequence."""
    s = np.sort(np.asarray(items), kind="stable")
    n, block = len(s), max(1, int(block))
    rest = n % block
    tail = np.zeros(n, bool)
    tail[((np.arange(rest) + 0.5) * n / max(rest, 1)).astype(np.int64)] = True
    strata = s[~tail].reshape(block, -1)  # row j: stratum j, sorted
    blocks = np.stack([rng.permutation(row) for row in strata], axis=1)
    body = np.concatenate([rng.permutation(b) for b in blocks]) if len(blocks) else s[:0]
    return np.concatenate([body, rng.permutation(s[tail])])


def poisson_gaps(n: int, rate: float, block: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process at ``rate``/s: the
    exponential quantiles at (i + 0.5)/n, in a stratified seeded order."""
    q = (np.arange(n) + 0.5) / n
    return stratified(-np.log1p(-q) / rate, block, rng)


def n_requests(traffic: dict, seconds: float) -> int:
    return max(1, int(round(float(traffic["rate_per_s"]) * seconds)))


def arrivals(traffic: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times (s after the window opens) of the window's requests."""
    gaps = poisson_gaps(n_requests(traffic, seconds), float(traffic["rate_per_s"]),
                        int(traffic["block_requests"]), rng)
    # the first gap runs from the window's opening: every gap is used
    return np.cumsum(gaps)


def tenant_sequence(
    n: int, n_tenants: int, traffic: dict, rng: np.random.Generator
) -> np.ndarray:
    """Tenant (popularity rank) of each request: fixed counts per rank from
    Zipf(``zipf_a``), in a stratified seeded order."""
    counts = quota(n, zipf_weights(n_tenants, float(traffic["zipf_a"])))
    return stratified(np.repeat(np.arange(n_tenants), counts),
                      int(traffic["block_requests"]), rng)

