"""Fused tiled pairwise-reduction engine for the downstream analytics.

Every analytics task the paper prices with the O(m^2 k) cost model — kNN
retrieval, DBSCAN radius queries, Gaussian KDE — is the same computation: a
row-reduction over the (m_q, m) pairwise squared-distance matrix. The legacy
modules each ran a Python host loop that materialized a ``(block, m)``
distance tile and synced it to host per block; at m=8000 that is a 32 MB
tile written to and re-read from RAM once per block, plus one blocking
device->host transfer per block — a k-INDEPENDENT O(m^2) memory-bound cost
that flattens the paper's §4.4 end-to-end margins on CPU.

This engine runs the ENTIRE scan as one jitted ``lax.fori_loop`` over query
tiles, with an inner ``fori_loop`` over dataset tiles and the per-task
reduction fused into the tile body (flash-attention-style online reduction,
Dao et al.: the row-reduction is carried across dataset tiles so the m x m
matrix never materializes — distance tiles live only in registers/cache):

* ``knn``    — running (min-d2, argmin) per query row, self excluded;
* ``dbscan`` — eps-ball degree counts + packed uint32 neighbor bitmasks
               (the host BFS consumes packed bits instead of re-running
               ``np.nonzero`` on boolean rows);
* ``kde``    — running sum of ``exp(-d2 / 2h^2)`` per query row.

Invariants (see ``analytics/README.md``):

* **one device dispatch** per call — the tile loops live inside a single
  jitted computation, never in Python;
* **one device->host transfer** per call — outputs come back together via a
  single ``jax.device_get`` at the end;
* **single compiled shape per bucket** — query and dataset row counts are
  padded to tile multiples through ``ShapeBucketCache.bucket_tile_rows``
  (the ``rows`` family), so remainder tiles never mint fresh executables,
  and the true row count ``m`` is a traced scalar (datasets landing in the
  same bucket share one executable).

Backend gating (measured, see ``knn._use_top_k``): the per-tile kNN
reduction uses ``lax.top_k(2)`` only off-CPU — on XLA:CPU ``top_k`` is a
20-40x pessimization at these shapes while where+argmin fuses into a single
pass.  ``use_kernels=True`` routes the scan through the
``kernels/pairwise_reduce`` Pallas kernel where a kernel backend is live
(TPU native, or interpret mode under ``REPRO_PALLAS_INTERPRET=1``); on a
plain CPU backend it falls back to this fused jnp scan, which IS the
optimized CPU path — the flag is always safe to set.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.bucketing import DEFAULT_BUCKETS, ShapeBucketCache

TASKS = ("knn", "dbscan", "kde")

# tuned on the container CPU (see benchmarks/bench_pairwise_analytics.py):
# 1024x1024 f32 distance tiles are 4 MB — L2/L3-resident, where the legacy
# (1024, m) tiles spill to RAM at serving sizes
DEFAULT_BLOCK = 1024


def _kernel_backend_live() -> bool:
    """Where ``use_kernels=True`` routes: a live kernel backend (TPU native
    or interpret mode — the shared ``repro.kernels`` gating rule), else the
    fused jnp scan here IS the optimized CPU path."""
    from repro.kernels import kernel_backend_live

    return kernel_backend_live()


def _pad_rows(x: np.ndarray, rows: int) -> np.ndarray:
    """Zero-pad ``x`` to ``rows`` rows on the host (padding happens before
    the single device transfer, so the device only ever sees bucket shapes)."""
    if x.shape[0] == rows:
        return x
    out = np.zeros((rows, x.shape[1]), dtype=x.dtype)
    out[: x.shape[0]] = x
    return out


# below this width the (bq, d) x (d, bk) gemm degenerates on XLA:CPU (the
# tiny contraction defeats the gemm micro-kernels; measured ~1.3x slower
# than unrolled elementwise at d=3, while gemm wins from d~8 up) — exactly
# the regime DROP's small-k reductions land in. CAVEAT: the unrolled
# (q-x)^2 form rounds differently from the gemm expansion the legacy/
# kernel/ref paths use, so at d <= DIRECT_D_MAX cross-path parity is
# exact-on-the-tested-seeds, not guaranteed at last-ulp ties (a pair
# straddling an eps boundary or an argmin near-tie by <1 ulp may
# legitimately resolve either way; exact duplicates still give d2 = 0 in
# every form). The parity suites run seeded data through this regime
# (DBSCAN blobs at d=3) and are deterministic.
DIRECT_D_MAX = 4


def _tile_d2(xqt, sq_q, x, sq_x, j, bk, m, col_offset):
    """One (bq, bk) squared-distance tile with padded dataset columns masked
    to +inf. Returns (d2, cols) — cols are GLOBAL dataset indices
    (``col_offset`` shifts local tile columns to global when ``x`` is one
    shard of a split dataset; the sequential scan passes 0)."""
    d = x.shape[1]
    xt = lax.dynamic_slice(x, (j * bk, 0), (bk, d))
    cols = col_offset + j * bk + jnp.arange(bk)
    if d <= DIRECT_D_MAX:
        # unrolled sum_j (q_j - x_j)^2: pure VPU, no degenerate gemm
        d2 = jnp.zeros((xqt.shape[0], bk), jnp.float32)
        for jj in range(d):
            diff = xqt[:, jj][:, None] - xt[None, :, jj]
            d2 = d2 + diff * diff
    else:
        sq_t = lax.dynamic_slice(sq_x, (j * bk,), (bk,))
        d2 = sq_q + sq_t[None, :] - 2.0 * jnp.matmul(
            xqt, xt.T, precision=lax.Precision.HIGHEST
        )
    d2 = jnp.where(cols[None, :] >= m, jnp.inf, d2)
    return d2, cols


def _knn_tile(carry, d2, cols, rows, use_top_k):
    """Fold one distance tile into the running (min-d2, argmin) carry.

    Strict ``<`` keeps the earlier tile on ties, and both per-tile
    reductions keep the first occurrence — together that reproduces the
    global-argmin first-occurrence tie-break of the legacy path exactly."""
    best_d2, best_idx = carry
    if use_top_k:
        # accelerator reduction: one top_k(2) partial-sort pass — if the
        # query's own row is the top hit the runner-up is the neighbor
        neg_vals, loc = lax.top_k(-d2, 2)
        cand = cols[loc]  # (bq, 2) global indices
        self_first = cand[:, 0] == rows
        t_d2 = jnp.where(self_first, -neg_vals[:, 1], -neg_vals[:, 0])
        t_idx = jnp.where(self_first, cand[:, 1], cand[:, 0])
    else:
        # CPU reduction: mask+argmin fuses into a single pass over the tile
        d2 = jnp.where(rows[:, None] == cols[None, :], jnp.inf, d2)
        t_d2 = jnp.min(d2, axis=1)
        t_idx = cols[jnp.argmin(d2, axis=1)]
    better = t_d2 < best_d2
    return (
        jnp.where(better, t_d2, best_d2),
        jnp.where(better, t_idx, best_idx),
    )


def _pack_bits(mask: jax.Array) -> jax.Array:
    """(bq, bk) bool -> (bq, bk//32) uint32, little-endian bit order (bit j
    of word w flags dataset column w*32 + j within the tile). Mirrors
    ``kernels.pairwise_reduce.pairwise_reduce.pack_bits_u32`` — THE layout
    definition; cross-path agreement is pinned by the parity sweeps. (Kept
    as a local copy so analytics never imports pallas at module level.)"""
    bq, bk = mask.shape
    u = mask.astype(jnp.uint32).reshape(bq, bk // 32, 32)
    weights = jnp.left_shift(
        jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32)
    )
    return jnp.sum(u * weights[None, None, :], axis=-1, dtype=jnp.uint32)


def _scan_core(
    xq: jax.Array,  # (nq*bq, d) padded queries (or one query shard)
    x: jax.Array,  # (nk*bk, d) padded dataset (or one dataset shard)
    m: jax.Array,  # true GLOBAL dataset row count (traced)
    scalar: jax.Array,  # task scalar: eps^2 (dbscan) / 1/(2h^2) (kde) / 0
    col_offset: jax.Array,  # global index of x's first row (traced int32)
    row_offset: jax.Array,  # global index of xq's first row (traced int32)
    *,
    task: str,
    bq: int,
    bk: int,
    use_top_k: bool,
):
    """The tiled pairwise scan over one (query shard, dataset shard) pair.

    This is the shared body behind the sequential ``_fused_scan`` (offsets
    0) and the split/mesh paths in ``analytics.split`` (each shard passes
    its global offsets, producing a PARTIAL carry that merges exactly —
    see the split-scan contract in ``analytics/README.md``).

    Returns per task:
      knn    -> (nn_idx  (nq*bq,) int32,  nn_d2  (nq*bq,) float32)
      dbscan -> (counts  (nq*bq,) int32,  packed (nq*bq, nk*bk/32) uint32)
      kde    -> (sums (nq*bq,) f32, comps (nq*bq,) f32)  [compensated pair;
                caller folds ``sums + comps`` in float64 and divides by m]
    """
    mq_pad, d = xq.shape
    nk = x.shape[0] // bk
    sq_x = jnp.sum(x * x, axis=1)

    def q_body(i, out):
        a = i * bq
        xqt = lax.dynamic_slice(xq, (a, 0), (bq, d))
        sq_q = jnp.sum(xqt * xqt, axis=1, keepdims=True)
        # kNN queries ARE the dataset rows, so the global query index doubles
        # as the self column to exclude (kde/dbscan never read `rows`)
        rows = row_offset + a + jnp.arange(bq)

        if task == "knn":

            def k_body(j, carry):
                d2, cols = _tile_d2(xqt, sq_q, x, sq_x, j, bk, m, col_offset)
                return _knn_tile(carry, d2, cols, rows, use_top_k)

            init = (
                jnp.full((bq,), jnp.inf, jnp.float32),
                jnp.zeros((bq,), jnp.int32),
            )
            best_d2, best_idx = lax.fori_loop(0, nk, k_body, init)
            idx_out, d2_out = out
            return (
                lax.dynamic_update_slice(idx_out, best_idx, (a,)),
                lax.dynamic_update_slice(d2_out, best_d2, (a,)),
            )

        if task == "dbscan":

            def k_body(j, carry):
                counts, packed_row = carry
                d2, _cols = _tile_d2(
                    xqt, sq_q, x, sq_x, j, bk, m, col_offset
                )
                mask = d2 <= scalar  # self included (d2=0); host drops it
                counts = counts + jnp.sum(mask, axis=1, dtype=jnp.int32)
                packed_row = lax.dynamic_update_slice(
                    packed_row, _pack_bits(mask), (0, j * (bk // 32))
                )
                return counts, packed_row

            init = (
                jnp.zeros((bq,), jnp.int32),
                jnp.zeros((bq, nk * (bk // 32)), jnp.uint32),
            )
            counts, packed_row = lax.fori_loop(0, nk, k_body, init)
            counts_out, packed_out = out
            return (
                lax.dynamic_update_slice(counts_out, counts, (a,)),
                lax.dynamic_update_slice(packed_out, packed_row, (a, 0)),
            )

        # kde: compensated (Neumaier) running exp-sum. A plain f32 running
        # sum swallows low-order tile contributions once the accumulator
        # grows (and makes per-shard partials depend on the split point);
        # carrying the rounding error in a second f32 keeps ~f64 accuracy
        # while staying in the backend's native width (jax x64 is off, so a
        # float64 carry would silently degrade back to f32 anyway). Padded
        # columns are masked, not exp(-inf), so a zero bandwidth scalar can
        # never produce inf*0 = nan.
        def k_body(j, carry):
            acc, comp = carry
            d2, cols = _tile_d2(xqt, sq_q, x, sq_x, j, bk, m, col_offset)
            e = jnp.exp(-jnp.maximum(d2, 0.0) * scalar)
            e = jnp.where(cols[None, :] < m, e, 0.0)
            t = jnp.sum(e, axis=1)
            s = acc + t
            comp = comp + jnp.where(
                jnp.abs(acc) >= jnp.abs(t),
                (acc - s) + t,  # low-order bits of t lost in the add
                (t - s) + acc,  # (tile sum larger: symmetric form)
            )
            return s, comp

        kinit = (
            jnp.zeros((bq,), jnp.float32),
            jnp.zeros((bq,), jnp.float32),
        )
        sums, comps = lax.fori_loop(0, nk, k_body, kinit)
        sums_out, comps_out = out
        return (
            lax.dynamic_update_slice(sums_out, sums, (a,)),
            lax.dynamic_update_slice(comps_out, comps, (a,)),
        )

    if task == "knn":
        init = (
            jnp.zeros((mq_pad,), jnp.int32),
            jnp.zeros((mq_pad,), jnp.float32),
        )
    elif task == "dbscan":
        init = (
            jnp.zeros((mq_pad,), jnp.int32),
            jnp.zeros((mq_pad, (x.shape[0] // bk) * (bk // 32)), jnp.uint32),
        )
    else:
        init = (
            jnp.zeros((mq_pad,), jnp.float32),
            jnp.zeros((mq_pad,), jnp.float32),
        )
    return lax.fori_loop(0, mq_pad // bq, q_body, init)


@partial(
    jax.jit,
    static_argnames=("task", "bq", "bk", "use_top_k"),
)
def _fused_scan(
    xq: jax.Array,
    x: jax.Array,
    m: jax.Array,
    scalar: jax.Array,
    task: str,
    bq: int,
    bk: int,
    use_top_k: bool,
):
    """The whole SEQUENTIAL pairwise scan as one device computation (the
    split/mesh variants live in ``analytics.split``; output contract is
    ``_scan_core``'s with both offsets zero)."""
    zero = jnp.int32(0)
    return _scan_core(
        xq, x, m, scalar, zero, zero,
        task=task, bq=bq, bk=bk, use_top_k=use_top_k,
    )


def _clamp_block(block: int, rows: int, word: int = 64) -> int:
    """Validate and shrink a tile to the data: a 300-row input under the
    default 1024 block would otherwise pad to (and scan) 1024 rows.

    EVERY accepted block is quantized to a multiple of ``word`` — including
    caller-supplied ones, which are rounded UP. The bitmask packer reshapes
    dataset tiles to ``(bq, bk // 32, 32)``, so a bk like 100 used to crash
    with an opaque reshape error deep inside jit; now it runs at 128, and a
    non-positive/non-integral block fails here with a clear message."""
    from repro.core.bucketing import round_up

    if block != int(block) or int(block) < 1:
        raise ValueError(
            f"block size must be a positive integer, got {block!r}; "
            f"pairwise tiles are quantized to multiples of {word} "
            "(the packed-bitmask word granularity)"
        )
    return max(word, min(round_up(int(block), word), round_up(rows, word)))


def _prepare(
    x: np.ndarray,
    queries: np.ndarray | None,
    bq: int,
    bk: int,
    bucket: ShapeBucketCache,
):
    """Host-side f32 conversion + tile padding through the shared buckets."""
    x = np.ascontiguousarray(x, dtype=np.float32)
    q = x if queries is None else np.ascontiguousarray(
        queries, dtype=np.float32
    )
    mq_pad = bucket.bucket_tile_rows(q.shape[0], bq)
    mk_pad = bucket.bucket_tile_rows(x.shape[0], bk)
    xk_pad = _pad_rows(x, mk_pad)
    # self-scan with matching pads: ONE padded copy serves both jit args
    # (no second host copy or device transfer of the same bytes)
    xq_pad = xk_pad if queries is None and mq_pad == mk_pad else _pad_rows(
        q, mq_pad
    )
    return x, q, xq_pad, xk_pad


def _default_top_k(m: int) -> bool:
    from repro.analytics.knn import _use_top_k

    return _use_top_k() and m >= 2


def pairwise_knn(
    x: np.ndarray,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    *,
    use_kernels: bool = False,
    use_top_k: bool | None = None,
    bucket: ShapeBucketCache | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Nearest OTHER row per row of ``x``: (indices int32, squared dists).

    ``use_top_k=None`` picks the measured per-backend reduction (top_k(2)
    off-CPU, mask+argmin on CPU); tests pass an explicit bool to exercise
    both on one backend."""
    bucket = bucket or DEFAULT_BUCKETS
    m = x.shape[0]
    if use_top_k is None:
        use_top_k = _default_top_k(m)
    block_q = _clamp_block(block_q, m)
    block_k = _clamp_block(block_k, m)
    x, _q, xq_pad, xk_pad = _prepare(x, None, block_q, block_k, bucket)
    if use_kernels and _kernel_backend_live():
        from repro.kernels.pairwise_reduce.ops import pairwise_knn_reduce

        idx, d2 = pairwise_knn_reduce(xq_pad, xk_pad, m)
    else:
        idx, d2 = _fused_scan(
            jnp.asarray(xq_pad),
            jnp.asarray(xk_pad),
            jnp.int32(m),
            jnp.float32(0.0),
            task="knn",
            bq=block_q,
            bk=block_k,
            use_top_k=use_top_k,
        )
    idx, d2 = jax.device_get((idx, d2))  # the single transfer
    return np.asarray(idx)[:m], np.asarray(d2)[:m]


def pairwise_dbscan(
    x: np.ndarray,
    eps: float,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    *,
    use_kernels: bool = False,
    bucket: ShapeBucketCache | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Eps-ball scan: (degree counts int32 (m,), packed uint32 (m, w)).

    Counts and bits INCLUDE the self column (d2=0 is always within eps);
    ``unpack_neighbors`` drops self when decoding. Bit layout is
    little-endian: dataset column c lives at word c//32, bit c%32."""
    bucket = bucket or DEFAULT_BUCKETS
    m = x.shape[0]
    # clamped tiles are 64-quantized, so packed words always divide the
    # dataset tile (bk % 32 == 0)
    block_q = _clamp_block(block_q, m)
    block_k = _clamp_block(block_k, m)
    x, _q, xq_pad, xk_pad = _prepare(x, None, block_q, block_k, bucket)
    # float32(eps * eps) — double-precision square, then ONE rounding —
    # matches the legacy path's jnp.float32(eps * eps) exactly;
    # float32(eps)**2 rounds twice and lands 1 ulp off for ~half of all
    # eps values, silently breaking eps-boundary parity
    eps2 = np.float32(float(eps) * float(eps))
    if use_kernels and _kernel_backend_live():
        from repro.kernels.pairwise_reduce.ops import pairwise_dbscan_reduce

        counts, packed = pairwise_dbscan_reduce(xq_pad, xk_pad, m, eps2)
    else:
        counts, packed = _fused_scan(
            jnp.asarray(xq_pad),
            jnp.asarray(xk_pad),
            jnp.int32(m),
            jnp.float32(eps2),
            task="dbscan",
            bq=block_q,
            bk=block_k,
            use_top_k=False,
        )
    counts, packed = jax.device_get((counts, packed))
    return np.asarray(counts)[:m], np.asarray(packed)[:m]


def pairwise_kde(
    x: np.ndarray,
    queries: np.ndarray | None = None,
    bandwidth: float = 1.0,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    *,
    use_kernels: bool = False,
    bucket: ShapeBucketCache | None = None,
) -> np.ndarray:
    """Mean Gaussian kernel density of ``x`` at each query row (unnormalized,
    matching the legacy operator: mean over the m reference points)."""
    bucket = bucket or DEFAULT_BUCKETS
    m = x.shape[0]
    mq = x.shape[0] if queries is None else queries.shape[0]
    block_q = _clamp_block(block_q, mq)
    block_k = _clamp_block(block_k, m)
    x, _q, xq_pad, xk_pad = _prepare(x, queries, block_q, block_k, bucket)
    inv = np.float32(1.0 / (2.0 * bandwidth * bandwidth))
    if use_kernels and _kernel_backend_live():
        from repro.kernels.pairwise_reduce.ops import pairwise_kde_reduce

        sums, comps = pairwise_kde_reduce(xq_pad, xk_pad, m, inv)
    else:
        sums, comps = _fused_scan(
            jnp.asarray(xq_pad),
            jnp.asarray(xk_pad),
            jnp.int32(m),
            jnp.float32(inv),
            task="kde",
            bq=block_q,
            bk=block_k,
            use_top_k=False,
        )
    sums, comps = jax.device_get((sums, comps))
    return kde_from_compensated(
        np.asarray(sums)[None, :mq], np.asarray(comps)[None, :mq], m
    )


def kde_from_compensated(
    sums: np.ndarray, comps: np.ndarray, m: int
) -> np.ndarray:
    """Fold (S, mq) per-shard compensated exp-sum pairs into densities.

    The device carries (sum, comp) in f32; the exact value of each partial
    is ``sum + comp``. Folding shards and the final mean in float64 on the
    host makes the result independent of the split point to ~f32 ulp (the
    shard combine is the associative piece; see analytics/README.md)."""
    total = (sums.astype(np.float64) + comps.astype(np.float64)).sum(axis=0)
    return (total / float(m)).astype(np.float32)


def unpack_neighbors(packed_row: np.ndarray, p: int, m: int) -> np.ndarray:
    """Decode one packed bitmask row into sorted neighbor indices, self
    excluded — the single-row primitive (``NeighborDecoder`` amortizes the
    unpack over row chunks for the BFS)."""
    bits = np.unpackbits(
        np.ascontiguousarray(packed_row).view(np.uint8), bitorder="little"
    )[:m]
    nbrs = np.flatnonzero(bits)
    return nbrs[nbrs != p]


class NeighborDecoder:
    """Lazy chunked two-level decoder for the packed eps-ball bitmasks.

    The DBSCAN BFS asks for one row at a time; decoding per row (one
    ``np.unpackbits`` + ``np.flatnonzero`` call each) pays Python/numpy
    call overhead m times, and unpacking whole chunks to a byte matrix
    re-creates the O(m^2) host scan the packing was meant to kill. Instead,
    the first touch of a row decodes its whole CHUNK sparsely:

    1. clear the chunk's self bits IN THE PACKED DOMAIN (one vectorized
       word update — the self bit is always set, d2 = 0 <= eps^2);
    2. ``np.flatnonzero`` over the packed WORDS — a 32x smaller scan than
       the unpacked matrix;
    3. ``np.unpackbits`` only the nonzero words and turn bit positions
       into global column indices with vectorized shift/mask arithmetic;
    4. one ``np.split`` at the per-row counts (``np.bincount`` over the
       word rows) hands out per-row neighbor arrays, ascending — the exact
       arrays the legacy per-row ``np.nonzero`` produced.

    Cost per chunk: O(words + set bits), not O(m * chunk) — dense
    neighborhoods decode in a few C passes, sparse ones touch almost
    nothing, and untouched chunks are never decoded at all."""

    def __init__(self, packed: np.ndarray, m: int, chunk: int = 1024) -> None:
        self.packed = packed
        self.m = m
        self.chunk = max(int(chunk), 1)
        self._chunks: dict[int, list[np.ndarray]] = {}

    def _decode_chunk(self, c: int) -> list[np.ndarray]:
        a = c * self.chunk
        b = min(a + self.chunk, self.m)
        rows = b - a
        words = np.array(self.packed[a:b])  # copy: self bits cleared below
        wpr = words.shape[1]
        g = np.arange(a, b)
        words[np.arange(rows), g // 32] &= ~np.left_shift(
            np.uint32(1), (g % 32).astype(np.uint32)
        )
        flat = words.ravel()
        wnz = np.flatnonzero(flat)  # the 32x-smaller scan
        bits = np.unpackbits(
            np.ascontiguousarray(flat[wnz]).view(np.uint8),
            bitorder="little",
        )
        pos = np.flatnonzero(bits)
        wloc = pos >> 5  # which nonzero word each set bit belongs to
        cols = (wnz[wloc] % wpr) * 32 + (pos & 31)
        counts = np.bincount(wnz[wloc] // wpr, minlength=rows)
        return np.split(cols, np.cumsum(counts)[:-1])

    def __call__(self, p: int) -> np.ndarray:
        c = p // self.chunk
        got = self._chunks.get(c)
        if got is None:
            got = self._chunks[c] = self._decode_chunk(c)
        return got[p - c * self.chunk]
