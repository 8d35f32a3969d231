"""Fused pairwise-reduction Pallas kernels (the analytics-side hot path).

One kernel per downstream task, all the same shape: grid (m_q/bq, m/bk),
query-tile axis 'parallel', dataset-tile axis 'arbitrary' (sequential) so
the per-row online reduction carries across dataset tiles in VMEM scratch —
the (bq, d) x (d, bk) distance tile is MXU-shaped, lives only in VMEM, and
the m x m distance matrix never exists (flash-attention-style tiling,
mirroring ``kernels/pairwise_tlb``):

* ``pairwise_knn_pallas``    — running (min-d2, argmin), self excluded;
* ``pairwise_dbscan_pallas`` — eps-ball degree counts (carried) + packed
                               uint32 neighbor bitmasks (tile-local write,
                               word-major so each block is (8k, 128)-tiled);
* ``pairwise_kde_pallas``    — compensated (Neumaier) Gaussian exp-sum pair.

Each kernel also has a ``*_split_pallas`` variant with a LEADING 'parallel'
shard axis on the grid — the flash-decoding decomposition: per-shard
partials with global column indices, merged exactly on the host by
``analytics.split`` (see the split-scan contract in analytics/README.md).

The true row count ``m`` and the task scalar (eps^2 / 1/(2h^2)) are STATIC:
they bake the padding masks and threshold into the compiled kernel, keeping
the reduction bit-identical to the jnp engine's tile body at the cost of a
recompile per (m, scalar) — acceptable on the kernel path, which exists for
accelerator backends (CPU serving uses the fused jnp scan).

Like the sibling kernels this runs natively on TPU and under
``interpret=True`` everywhere else (the CPU test path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _tile_d2(xq_ref, x_ref, row0, col0, m, bq, bk):
    """(bq, bk) squared-distance tile with global row/col ids; padded
    dataset columns masked to +inf. ``row0``/``col0`` are the GLOBAL
    indices of the tile's first row/column (``i*bq``/``j*bk`` on the
    sequential grid; the split grid adds the shard offset to ``col0``)."""
    xqt = xq_ref[...].astype(jnp.float32)
    xt = x_ref[...].astype(jnp.float32)
    sq_q = jnp.sum(xqt * xqt, axis=1, keepdims=True)
    sq_t = jnp.sum(xt * xt, axis=1)
    d2 = sq_q + sq_t[None, :] - 2.0 * jnp.dot(
        xqt, xt.T, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    d2 = jnp.where(cols >= m, jnp.inf, d2)
    return d2, rows, cols


def _knn_body(xq_ref, x_ref, idx_ref, d2_ref, acc_d2, acc_idx, row0, col0, j, m, bq, bk):
    """Shared kNN tile fold: init at the first dataset tile, strict-``<``
    merge (keeps the earlier tile on ties — first-occurrence argmin,
    matching the jnp engine and the legacy global argmin exactly), write
    the carry out every step (the final tile's write is the answer)."""

    @pl.when(j == 0)
    def _init():
        acc_d2[...] = jnp.full_like(acc_d2, jnp.inf)
        acc_idx[...] = jnp.zeros_like(acc_idx)

    d2, rows, cols = _tile_d2(xq_ref, x_ref, row0, col0, m, bq, bk)
    d2 = jnp.where(rows == cols, jnp.inf, d2)  # self excluded
    t_d2 = jnp.min(d2, axis=1, keepdims=True)
    t_idx = (col0 + jnp.argmin(d2, axis=1)[:, None]).astype(jnp.int32)
    better = t_d2 < acc_d2[...]
    acc_d2[...] = jnp.where(better, t_d2, acc_d2[...])
    acc_idx[...] = jnp.where(better, t_idx, acc_idx[...])
    idx_ref[...] = acc_idx[...]
    d2_ref[...] = acc_d2[...]


def _knn_kernel(xq_ref, x_ref, idx_ref, d2_ref, acc_d2, acc_idx, *, m, bq, bk):
    i, j = pl.program_id(0), pl.program_id(1)
    _knn_body(
        xq_ref, x_ref, idx_ref, d2_ref, acc_d2, acc_idx,
        i * bq, j * bk, j, m, bq, bk,
    )


def _knn_split_kernel(
    xq_ref, x_ref, idx_ref, d2_ref, acc_d2, acc_idx, *, m, bq, bk, shard_rows
):
    """Grid-parallel split: leading shard axis, per-shard PARTIAL argmin
    with GLOBAL column indices (col0 folds in the shard offset); the host
    merges shards with ``analytics.split.merge_knn_partials``."""
    s, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    _knn_body(
        xq_ref, x_ref, idx_ref, d2_ref, acc_d2, acc_idx,
        i * bq, s * shard_rows + j * bk, j, m, bq, bk,
    )


def pack_bits_u32(mask: jax.Array) -> jax.Array:
    """(rows, cols) bool -> (rows, cols//32) uint32, little-endian bit order
    (bit j of word w flags column w*32 + j). THE bit-layout definition for
    this package: the ref oracle packs through it, the kernel body computes
    the same words on the MXU (``_pack_words_t``), and the engine's jnp tile
    body (``analytics.pairwise._pack_bits``) mirrors it — cross-path
    agreement is pinned by the parity sweeps."""
    rows, cols = mask.shape
    u = mask.astype(jnp.uint32).reshape(rows, cols // 32, 32)
    weights = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(u * weights[None, None, :], axis=-1, dtype=jnp.uint32)


# dataset tile of the DBSCAN kernels: its bk // 32 = 8 packed words fill
# the sublane side of one (8, 128) int32 output tile (word-major layout)
DBSCAN_BLOCK_K = 256


def _pack_words_t(mask: jax.Array) -> jax.Array:
    """(bq, bk) bool -> (bk // 32, bq) int32: ``pack_bits_u32(mask).T``
    as raw bits, computed on the MXU. Word w of row r is the dot of the
    row with weights 2^(c % 32) on columns c of word w, split into 16-bit
    halves: bf16 holds 0/1 and every power of two exactly, and an f32
    accumulator holds any sum below 2^24 exactly, so the packing is exact.
    The transposed product keeps the word axis off the lane dimension (a
    (bq, bk // 32) block is not (8, 128)-tiled for any bk below 4096)."""
    _, bk = mask.shape
    shape = (bk // 32, bk)
    word = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    bit = col % 32
    own = col // 32 == word
    weight = jnp.left_shift(1, bit % 16).astype(jnp.float32)
    bits = mask.astype(jnp.bfloat16)

    def half(sel):
        w = jnp.where(own & sel, weight, 0.0).astype(jnp.bfloat16)
        return jax.lax.dot_general(
            w, bits, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)

    return half(bit < 16) | jnp.left_shift(half(bit >= 16), 16)


def _dbscan_body(xq_ref, x_ref, cnt_ref, packed_ref, acc_cnt, row0, col0, j, m, bq, bk, eps2):
    @pl.when(j == 0)
    def _init():
        acc_cnt[...] = jnp.zeros_like(acc_cnt)

    d2, _rows, _cols = _tile_d2(xq_ref, x_ref, row0, col0, m, bq, bk)
    mask = d2 <= eps2  # self included (d2=0); the host BFS drops it
    acc_cnt[...] += jnp.sum(mask, axis=1, keepdims=True, dtype=jnp.int32)
    cnt_ref[...] = acc_cnt[...]
    packed_ref[...] = _pack_words_t(mask)


def _dbscan_kernel(xq_ref, x_ref, cnt_ref, packed_ref, acc_cnt, *, m, bq, bk, eps2):
    i, j = pl.program_id(0), pl.program_id(1)
    _dbscan_body(
        xq_ref, x_ref, cnt_ref, packed_ref, acc_cnt,
        i * bq, j * bk, j, m, bq, bk, eps2,
    )


def _dbscan_split_kernel(
    xq_ref, x_ref, cnt_ref, packed_ref, acc_cnt, *, m, bq, bk, eps2, shard_rows
):
    """Split variant: per-shard counts + tile-local packed segment writes;
    shard boundaries are whole bk-tiles, so the segment word layout IS the
    sequential one after shard-order concatenation."""
    s, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    _dbscan_body(
        xq_ref, x_ref, cnt_ref, packed_ref, acc_cnt,
        i * bq, s * shard_rows + j * bk, j, m, bq, bk, eps2,
    )


def _kde_body(xq_ref, x_ref, sum_ref, comp_ref, acc, comp, row0, col0, j, m, bq, bk, inv_two_h2):
    """Compensated (Neumaier) exp-sum fold — carries the rounding error of
    each tile add in a second f32 scratch, mirroring the jnp engine's carry
    (see ``analytics.pairwise._scan_core``); the caller folds sum + comp in
    float64 on the host."""

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        comp[...] = jnp.zeros_like(comp)

    d2, _rows, cols = _tile_d2(xq_ref, x_ref, row0, col0, m, bq, bk)
    e = jnp.exp(-jnp.maximum(d2, 0.0) * inv_two_h2)
    e = jnp.where(cols < m, e, 0.0)
    t = jnp.sum(e, axis=1, keepdims=True)
    a = acc[...]
    s_ = a + t
    comp[...] += jnp.where(
        jnp.abs(a) >= jnp.abs(t), (a - s_) + t, (t - s_) + a
    )
    acc[...] = s_
    sum_ref[...] = acc[...]
    comp_ref[...] = comp[...]


def _kde_kernel(xq_ref, x_ref, sum_ref, comp_ref, acc, comp, *, m, bq, bk, inv_two_h2):
    i, j = pl.program_id(0), pl.program_id(1)
    _kde_body(
        xq_ref, x_ref, sum_ref, comp_ref, acc, comp,
        i * bq, j * bk, j, m, bq, bk, inv_two_h2,
    )


def _kde_split_kernel(
    xq_ref, x_ref, sum_ref, comp_ref, acc, comp, *, m, bq, bk, inv_two_h2, shard_rows
):
    s, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    _kde_body(
        xq_ref, x_ref, sum_ref, comp_ref, acc, comp,
        i * bq, s * shard_rows + j * bk, j, m, bq, bk, inv_two_h2,
    )


def _pad_to(arr: jax.Array, rows: int) -> jax.Array:
    return jnp.pad(arr, ((0, rows - arr.shape[0]), (0, 0)))


def _grid_and_specs(xq, x, bq, bk):
    """Common ragged-shape padding + (grid, in_specs) for the three kernels."""
    mq, d = xq.shape
    pq = (-mq) % bq
    pk = (-x.shape[0]) % bk
    xq = _pad_to(xq, mq + pq)
    x = _pad_to(x, x.shape[0] + pk)
    grid = ((mq + pq) // bq, x.shape[0] // bk)
    in_specs = [
        pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
        pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
    ]
    return xq, x, grid, in_specs


@functools.partial(
    jax.jit, static_argnames=("m", "block_q", "block_k", "interpret")
)
def pairwise_knn_pallas(
    xq: jax.Array,
    x: jax.Array,
    m: int,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(mq, d), (mk, d) -> (nn index (mq,) int32, nn squared dist (mq,))."""
    mq = xq.shape[0]
    bq, bk = min(block_q, max(mq, 1)), block_k
    xq, x, grid, in_specs = _grid_and_specs(xq, x, bq, bk)
    idx, d2 = pl.pallas_call(
        functools.partial(_knn_kernel, m=m, bq=bq, bk=bk),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((xq.shape[0], 1), jnp.int32),
            jax.ShapeDtypeStruct((xq.shape[0], 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),  # running min d2
            pltpu.VMEM((bq, 1), jnp.int32),  # running argmin
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xq, x)
    return idx[:mq, 0], d2[:mq, 0]


@functools.partial(
    jax.jit,
    static_argnames=("m", "eps2", "block_q", "block_k", "interpret"),
)
def pairwise_dbscan_pallas(
    xq: jax.Array,
    x: jax.Array,
    m: int,
    eps2: float,
    block_q: int = 128,
    block_k: int = DBSCAN_BLOCK_K,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """-> (eps-ball counts (mq,) int32, packed bitmask (mq, mk_pad/32)).

    On TPU ``block_k`` must be a multiple of ``DBSCAN_BLOCK_K`` (or cover
    the whole padded dataset); interpret mode takes any multiple of 32."""
    mq = xq.shape[0]
    bq = min(block_q, max(mq, 1))
    bk = max(32, (block_k // 32) * 32)  # packed words divide the tile
    xq, x, grid, in_specs = _grid_and_specs(xq, x, bq, bk)
    w = x.shape[0] // 32
    cnt, packed = pl.pallas_call(
        functools.partial(
            _dbscan_kernel, m=m, bq=bq, bk=bk, eps2=float(eps2)
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bk // 32, bq), lambda i, j: (j, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((xq.shape[0], 1), jnp.int32),
            jax.ShapeDtypeStruct((w, xq.shape[0]), jnp.int32),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.int32),  # running degree count
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xq, x)
    packed = jax.lax.bitcast_convert_type(packed, jnp.uint32)
    return cnt[:mq, 0], packed.T[:mq]


@functools.partial(
    jax.jit,
    static_argnames=("m", "inv_two_h2", "block_q", "block_k", "interpret"),
)
def pairwise_kde_pallas(
    xq: jax.Array,
    x: jax.Array,
    m: int,
    inv_two_h2: float,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """-> compensated Gaussian exp-sum pair ((mq,) sums, (mq,) comps); the
    caller folds ``sums + comps`` in float64 and divides by m."""
    mq = xq.shape[0]
    bq, bk = min(block_q, max(mq, 1)), block_k
    xq, x, grid, in_specs = _grid_and_specs(xq, x, bq, bk)
    sums, comps = pl.pallas_call(
        functools.partial(
            _kde_kernel, m=m, bq=bq, bk=bk, inv_two_h2=float(inv_two_h2)
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((xq.shape[0], 1), jnp.float32),
            jax.ShapeDtypeStruct((xq.shape[0], 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),  # running exp-sum
            pltpu.VMEM((bq, 1), jnp.float32),  # running compensation
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xq, x)
    return sums[:mq, 0], comps[:mq, 0]


# ------------------------------------------------------------ split variants
# Same kernels with a LEADING 'parallel' shard axis on the grid: every
# (shard, query-tile) pair carries its own online reduction over the shard's
# dataset tiles, producing per-shard PARTIALS in one pallas_call — the
# flash-decoding decomposition (cf. ``kernels/flash_decode``), merged
# exactly on the host by ``analytics.split``.


def _split_grid_and_specs(xq, x, shards, bq, bk):
    """Grid/specs for the split kernels. ``x`` arrives shard-padded from
    ``analytics.split._split_prepare``: (shards * shard_rows, d) with
    shard_rows a whole number of bk-tiles."""
    mq, d = xq.shape
    pq = (-mq) % bq
    xq = _pad_to(xq, mq + pq)
    nq = (mq + pq) // bq
    shard_rows = x.shape[0] // shards
    tps = shard_rows // bk  # tiles per shard
    grid = (shards, nq, tps)
    in_specs = [
        pl.BlockSpec((bq, d), lambda s, i, j: (i, 0)),
        pl.BlockSpec((bk, d), lambda s, i, j, tps=tps: (s * tps + j, 0)),
    ]
    return xq, grid, in_specs, nq, shard_rows


@functools.partial(
    jax.jit,
    static_argnames=("m", "shards", "block_q", "block_k", "interpret"),
)
def pairwise_knn_split_pallas(
    xq: jax.Array,
    x: jax.Array,
    m: int,
    shards: int,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """-> per-shard partials ((shards, mq) int32 idx, (shards, mq) d2)."""
    mq = xq.shape[0]
    bq, bk = min(block_q, max(mq, 1)), block_k
    xq, grid, in_specs, nq, shard_rows = _split_grid_and_specs(
        xq, x, shards, bq, bk
    )
    idx, d2 = pl.pallas_call(
        functools.partial(
            _knn_split_kernel, m=m, bq=bq, bk=bk, shard_rows=shard_rows
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((bq, 1), lambda s, i, j, nq=nq: (s * nq + i, 0)),
            pl.BlockSpec((bq, 1), lambda s, i, j, nq=nq: (s * nq + i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((shards * xq.shape[0], 1), jnp.int32),
            jax.ShapeDtypeStruct((shards * xq.shape[0], 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xq, x)
    mq_pad = xq.shape[0]
    return (
        idx.reshape(shards, mq_pad)[:, :mq],
        d2.reshape(shards, mq_pad)[:, :mq],
    )


@functools.partial(
    jax.jit,
    static_argnames=("m", "eps2", "shards", "block_q", "block_k", "interpret"),
)
def pairwise_dbscan_split_pallas(
    xq: jax.Array,
    x: jax.Array,
    m: int,
    eps2: float,
    shards: int,
    block_q: int = 128,
    block_k: int = DBSCAN_BLOCK_K,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """-> ((shards, mq) int32 counts, (shards, mq, shard_words) uint32)."""
    mq = xq.shape[0]
    bq = min(block_q, max(mq, 1))
    bk = max(32, (block_k // 32) * 32)
    xq, grid, in_specs, nq, shard_rows = _split_grid_and_specs(
        xq, x, shards, bq, bk
    )
    w = shard_rows // 32
    tps = shard_rows // bk
    cnt, packed = pl.pallas_call(
        functools.partial(
            _dbscan_split_kernel,
            m=m, bq=bq, bk=bk, eps2=float(eps2), shard_rows=shard_rows,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((bq, 1), lambda s, i, j, nq=nq: (s * nq + i, 0)),
            pl.BlockSpec(
                (bk // 32, bq), lambda s, i, j, tps=tps: (s * tps + j, i)
            ),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((shards * xq.shape[0], 1), jnp.int32),
            jax.ShapeDtypeStruct((shards * w, xq.shape[0]), jnp.int32),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xq, x)
    mq_pad = xq.shape[0]
    packed = jax.lax.bitcast_convert_type(packed, jnp.uint32)
    return (
        cnt.reshape(shards, mq_pad)[:, :mq],
        packed.reshape(shards, w, mq_pad).transpose(0, 2, 1)[:, :mq],
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "m", "inv_two_h2", "shards", "block_q", "block_k", "interpret"
    ),
)
def pairwise_kde_split_pallas(
    xq: jax.Array,
    x: jax.Array,
    m: int,
    inv_two_h2: float,
    shards: int,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """-> per-shard compensated pairs ((shards, mq) sums, (shards, mq) comps)."""
    mq = xq.shape[0]
    bq, bk = min(block_q, max(mq, 1)), block_k
    xq, grid, in_specs, nq, shard_rows = _split_grid_and_specs(
        xq, x, shards, bq, bk
    )
    sums, comps = pl.pallas_call(
        functools.partial(
            _kde_split_kernel,
            m=m, bq=bq, bk=bk,
            inv_two_h2=float(inv_two_h2), shard_rows=shard_rows,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((bq, 1), lambda s, i, j, nq=nq: (s * nq + i, 0)),
            pl.BlockSpec((bq, 1), lambda s, i, j, nq=nq: (s * nq + i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((shards * xq.shape[0], 1), jnp.float32),
            jax.ShapeDtypeStruct((shards * xq.shape[0], 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xq, x)
    mq_pad = xq.shape[0]
    return (
        sums.reshape(shards, mq_pad)[:, :mq],
        comps.reshape(shards, mq_pad)[:, :mq],
    )
