"""The correctness check's control: the plain reference, put in the
program's place and computed one precision step below the configuration's.

The configuration serves float32 with every distance matmul at
``Precision.HIGHEST``; the step below is ``high``, three bfloat16 passes.
The program pins ``HIGHEST`` at its call sites, so no global setting lowers
it; the control instead emulates the three passes explicitly (each operand
split into a bfloat16 head and tail, the tail-by-tail product dropped,
float32 accumulation), which reads the same on the chip and on the CPU.
"""

from __future__ import annotations

import numpy as np

BLOCK = 512  # query rows per device call


def _split(a):
    import jax
    import jax.numpy as jnp

    # reduce_precision rounds to bfloat16 wherever it runs: a round trip
    # through ``astype`` may be elided by the TPU compiler (excess
    # precision), which zeroes the tail and leaves a single pass
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _dot_high(a, b):
    """``a @ b.T`` in three bfloat16 passes, float32 accumulation."""
    import jax.numpy as jnp

    (ah, al), (bh, bl) = _split(a), _split(b)

    def dot(p, q):
        return jnp.matmul(p, q.T, preferred_element_type=jnp.float32)

    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def _block_d2(q, x):
    return (q * q).sum(1)[:, None] + (x * x).sum(1)[None, :] - 2.0 * _dot_high(q, x)


def d2_blocks(z32: np.ndarray, rows: np.ndarray):
    """Squared distances from each of ``rows`` to every row of ``z32`` at
    three bfloat16 passes, ``BLOCK`` rows at a time: yields (rows, d2)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(_block_d2)
    x = jnp.asarray(z32, jnp.float32)
    for a in range(0, len(rows), BLOCK):
        r = np.asarray(rows[a:a + BLOCK])
        pad = np.concatenate([r, np.full(BLOCK - len(r), r[0])])  # one shape
        yield r, np.array(f(x[pad], x))[:len(r)]


def knn_high(z32: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Nearest other row of each of ``rows`` among all rows of ``z32``.
    Returns a full-length index array, -1 outside ``rows``."""
    out = np.full(len(z32), -1, np.int64)
    for r, d2 in d2_blocks(z32, rows):
        d2[np.arange(len(r)), r] = np.inf
        out[r] = d2.argmin(1)
    return out


def transform32(x: np.ndarray, v: np.ndarray, mean: np.ndarray) -> np.ndarray:
    """The served map applied as the program applies it: float32, centred
    by the served mean."""
    return (np.asarray(x, np.float32) - np.asarray(mean, np.float32)) @ np.asarray(v, np.float32)
