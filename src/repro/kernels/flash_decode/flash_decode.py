"""Single-token flash-decode Pallas kernel — the per-shard hot loop of the
distributed decode attention (serve/decode.py runs this math per model shard;
on TPU this kernel replaces the jnp einsum path inside the shard_map).

For one new query against a length-T cache:
    scores(t) = q . k_t * scale   (masked by cache validity)
    out       = softmax(scores) @ V        via the online recurrence

TPU mapping: grid (B, T/bt). The T axis is 'arbitrary' (sequential): each step
streams one (bt, KV, hd) cache tile HBM->VMEM, updates the running
(max, denom, acc) scratch — O(1) VMEM regardless of T, reading the cache
exactly once (the op is purely HBM-bandwidth-bound, as the roofline analysis
shows for decode cells). Batch is 'parallel'.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fd_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref, acc_ref,
               *, nt: int, scale: float):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)        # (KV, G, hd)
    k = k_ref[0].astype(jnp.float32)        # (bt, KV, hd)
    v = v_ref[0].astype(jnp.float32)        # (bt, KV, hd)
    ok = valid_ref[0]                        # (bt,)

    # scores: (KV, G, bt)
    s = jnp.einsum("kgh,tkh->kgt", q, k) * scale
    s = jnp.where(ok[None, None, :], s, NEG_INF)

    m_old = m_ref[...]                       # (KV, G)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])        # (KV, G, bt)
    corr = jnp.exp(m_old - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] + jnp.einsum(
        "kgt,tkh->kgh", p, v
    )

    @pl.when(pl.program_id(1) == nt - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_t", "interpret")
)
def flash_decode_pallas(
    q: jax.Array,        # (B, KV, G, hd)
    k_cache: jax.Array,  # (B, T, KV, hd)
    v_cache: jax.Array,  # (B, T, KV, hd)
    valid: jax.Array,    # (B, T) bool
    block_t: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, KV, G, hd) attention output for the single new token."""
    b, kv, g, hd = q.shape
    t = k_cache.shape[1]
    bt = min(block_t, t)
    pt = (-t) % bt
    if pt:  # pad the cache tail; padded slots are masked invalid
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pt), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pt), (0, 0), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pt)))
    nt = (t + pt) // bt
    scale = 1.0 / float(hd) ** 0.5

    return pl.pallas_call(
        functools.partial(_fd_kernel, nt=nt, scale=scale),
        grid=(b, nt),
        in_specs=[
            pl.BlockSpec((1, kv, g, hd), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((1, bt, kv, hd), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, bt, kv, hd), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, bt), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, kv, g, hd), lambda i, j: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((kv, g), jnp.float32),       # running max
            pltpu.VMEM((kv, g), jnp.float32),       # running denom
            pltpu.VMEM((kv, g, hd), jnp.float32),   # running numerator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k_cache, v_cache, valid)
