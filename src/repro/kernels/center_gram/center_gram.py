"""Fused mean-center + Gram (covariance) Pallas kernel.

G = (X - mean)ᵀ (X - mean) = XᵀX - m * mean meanᵀ

Used by the covariance-path PCA (d <= m regime: eigendecompose the d x d Gram
instead of SVD on the m x d matrix). Fusing the centering into the Gram
accumulation removes a full HBM round-trip of the centered copy of X — the
paper's Algorithm 1 materializes C_X; on TPU that write+read of m*d floats is
pure memory-roofline waste.

TPU mapping: grid (d/bi, d/bj, m/bm); the row axis is 'arbitrary' (sequential)
carrying the partial Gram tile and the two partial column-sum rows in VMEM
scratch; at the last row-step the tile is corrected by -m*mu_i muⱼᵀ and
flushed. X is read twice (once per column block side) but never written.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _center_gram_kernel(xi_ref, xj_ref, o_ref, g_ref, si_ref, sj_ref, *, nm: int, m: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        si_ref[...] = jnp.zeros_like(si_ref)
        sj_ref[...] = jnp.zeros_like(sj_ref)

    xi = xi_ref[...].astype(jnp.float32)  # (bm, bi)
    xj = xj_ref[...].astype(jnp.float32)  # (bm, bj)
    g_ref[...] += jnp.dot(xi.T, xj, preferred_element_type=jnp.float32)
    si_ref[...] += jnp.sum(xi, axis=0, keepdims=True)
    sj_ref[...] += jnp.sum(xj, axis=0, keepdims=True)

    @pl.when(pl.program_id(2) == nm - 1)
    def _flush():
        mu_i = si_ref[...] / m  # (1, bi)
        mu_j = sj_ref[...] / m  # (1, bj)
        o_ref[...] = (g_ref[...] - m * mu_i.T @ mu_j).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_d", "block_m", "interpret")
)
def center_gram_pallas(
    x: jax.Array,
    block_d: int = 256,
    block_m: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """(m, d) -> (d, d) centered Gram matrix, single streaming pass over X."""
    m, d = x.shape
    bd, bm = min(block_d, d), min(block_m, m)
    pd = (-d) % bd
    pm = (-m) % bm
    if pd or pm:
        # zero row padding adds nothing to sums; zero column padding yields
        # zero rows/cols in G which we slice away
        x = jnp.pad(x, ((0, pm), (0, pd)))
    mp, dp = x.shape
    nm = mp // bm

    out = pl.pallas_call(
        functools.partial(_center_gram_kernel, nm=nm, m=m),
        grid=(dp // bd, dp // bd, nm),
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, s: (s, i)),
            pl.BlockSpec((bm, bd), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bd, bd), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((dp, dp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bd, bd), jnp.float32),
            pltpu.VMEM((1, bd), jnp.float32),
            pltpu.VMEM((1, bd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, x)
    return out[:d, :d]
