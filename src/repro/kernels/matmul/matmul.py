"""Tiled MXU matmul Pallas kernel — the O(mdk) hot spot of SVD-Halko.

TPU mapping: grid (M/bm, N/bn, K/bk); A and B tiles stream HBM->VMEM per
BlockSpec; partial products accumulate in an f32 VMEM scratch tile so the MXU
(128x128 systolic array) sees hardware-aligned (bm, bk) x (bk, bn) contractions;
the K grid axis is 'arbitrary' (sequential) for the accumulation carry, M/N are
'parallel'. Default 256x256x512 tiles keep the working set
(bm*bk + bk*bn + bm*bn floats ~ 1.3 MB) well inside the ~16 MB/core VMEM while
amortizing HBM reads ~256x (arithmetic intensity >> the ~240 flop/byte ridge).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # f32 operands get full f32 products (the Halko fit compares against a
    # HIGHEST-precision jnp path); Mosaic takes no precision for bf16
    f32 = a_ref.dtype == jnp.float32
    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if f32 else None,
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with zero-padding to tile multiples (stripped on return)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, f"contraction mismatch {a.shape} @ {b.shape}"
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)

    def _pad(x, mult0, mult1):
        p0 = (-x.shape[0]) % mult0
        p1 = (-x.shape[1]) % mult1
        if p0 or p1:
            x = jnp.pad(x, ((0, p0), (0, p1)))
        return x

    ap = _pad(a, bm, bk)
    bp = _pad(b, bk, bn)
    mp, kp = ap.shape
    _, np_ = bp.shape
    nk = kp // bk

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n]
