"""Gaussian kernel density estimation — a pairwise-distance downstream task
(mentioned in §1 alongside k-NN/k-Means as TLB-sensitive analytics).

``gaussian_kde`` is a thin adapter over the fused tiled engine
(``analytics.pairwise``): the exp-sum reduction runs inside the tile loop,
one device dispatch, one transfer. ``gaussian_kde_legacy`` keeps the
pre-engine per-block host loop as the parity oracle / benchmark baseline
(same math, so parity is tight — only the summation tree differs)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=())
def _kde_block(xq: jax.Array, x: jax.Array, inv_two_h2: jax.Array) -> jax.Array:
    sq_q = jnp.sum(xq * xq, axis=1, keepdims=True)
    sq_x = jnp.sum(x * x, axis=1)
    d2 = jnp.maximum(
        sq_q + sq_x[None, :]
        - 2.0 * jnp.matmul(xq, x.T, precision=jax.lax.Precision.HIGHEST),
        0.0,
    )
    return jnp.mean(jnp.exp(-d2 * inv_two_h2), axis=1)


def gaussian_kde_legacy(
    x: np.ndarray, queries: np.ndarray | None = None, bandwidth: float = 1.0,
    block: int = 1024,
) -> np.ndarray:
    """The pre-engine host loop (one dispatch + one sync per query block)."""
    xs = jnp.asarray(x, dtype=jnp.float32)
    qs = xs if queries is None else jnp.asarray(queries, dtype=jnp.float32)
    inv = jnp.float32(1.0 / (2.0 * bandwidth * bandwidth))
    out = []
    for a in range(0, qs.shape[0], block):
        out.append(np.asarray(_kde_block(qs[a : a + block], xs, inv)))
    return np.concatenate(out)


def gaussian_kde(
    x: np.ndarray,
    queries: np.ndarray | None = None,
    bandwidth: float = 1.0,
    block: int = 1024,
    *,
    use_kernels: bool = False,
    split: int | None = None,
    fanout: str = "xla",
    devices=None,
) -> np.ndarray:
    """Mean Gaussian kernel density at each query point (unnormalized).

    ``split=N`` shards the exp-sum (``analytics.split``); compensated
    partials folded in float64 keep densities split-point independent."""
    if split is not None or fanout == "mesh":
        from repro.analytics.split import split_pairwise_kde

        return split_pairwise_kde(
            x, queries, bandwidth, shards=split or 1,
            block_q=block, block_k=block,
            use_kernels=use_kernels, fanout=fanout, devices=devices,
        )
    from repro.analytics.pairwise import pairwise_kde

    return pairwise_kde(
        x, queries, bandwidth, block, block, use_kernels=use_kernels
    )
