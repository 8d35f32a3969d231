"""Fused all-prefix pairwise-TLB Pallas kernel (DROP's TLB evaluation).

For P sampled pairs and a (d, K) PCA basis V, computes the (P, K) table
    tlb[p, k] = ||(x_i - x_j) @ V[:, :k+1]|| / ||x_i - x_j||
in ONE pass: diff -> project (MXU) -> square -> prefix-sum (MXU) -> normalize.
This is the TPU-native replacement for the paper's per-k TLB evaluations
(DESIGN.md §2): binary search over k collapses into reading this table.

TPU mapping: grid (P/bp, K/bk). The pair axis is 'parallel'; the K axis is
'arbitrary' (sequential) because the prefix sum carries across K tiles via an
f32 VMEM scratch column. d is kept unblocked: a (bp, d) diff tile at bp=128,
d<=4096 is ~2 MB — inside VMEM, and the (bp, d) x (d, bk) projection is
MXU-shaped. The per-pair squared-denominator is computed once at k-step 0 and
cached in scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _tlb_kernel(xi_ref, xj_ref, v_ref, o_ref, acc_ref, den_ref):
    diffs = (xi_ref[...] - xj_ref[...]).astype(jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        den_ref[...] = jnp.sum(diffs * diffs, axis=1, keepdims=True)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hi = jax.lax.Precision.HIGHEST
    z = jnp.dot(diffs, v_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32, precision=hi)  # (bp, bk)
    zsq = z * z
    # prefix sum along the tile as a matmul with an upper-triangular ones
    # matrix: Mosaic has no cumsum lowering, and the MXU does this for free
    bk = zsq.shape[1]
    upper = (
        jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 1)
    ).astype(jnp.float32)
    cum = jnp.dot(zsq, upper, preferred_element_type=jnp.float32,
                  precision=hi) + acc_ref[...]
    acc_ref[...] += jnp.sum(zsq, axis=1, keepdims=True)
    den = den_ref[...]
    tlb = jnp.sqrt(jnp.clip(cum / jnp.maximum(den, 1e-30), 0.0, 1.0))
    o_ref[...] = jnp.where(den > 1e-30, tlb, 1.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_p", "block_k", "interpret")
)
def pairwise_tlb_pallas(
    xi: jax.Array,
    xj: jax.Array,
    v: jax.Array,
    block_p: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(P, d), (P, d), (d, K) -> (P, K) all-prefix TLB table."""
    p, d = xi.shape
    d2, k = v.shape
    assert xj.shape == (p, d) and d2 == d
    bp, bk = min(block_p, p), min(block_k, k)

    pp = (-p) % bp
    pk = (-k) % bk
    if pp:
        xi = jnp.pad(xi, ((0, pp), (0, 0)))
        xj = jnp.pad(xj, ((0, pp), (0, 0)))
    if pk:
        v = jnp.pad(v, ((0, 0), (0, pk)))

    out = pl.pallas_call(
        _tlb_kernel,
        grid=((p + pp) // bp, (k + pk) // bk),
        in_specs=[
            pl.BlockSpec((bp, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bp, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bk), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bp, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((p + pp, k + pk), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bp, 1), jnp.float32),  # running sum of z^2 per pair
            pltpu.VMEM((bp, 1), jnp.float32),  # ||diff||^2 per pair
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xi, xj, v)
    return out[:p, :k]
