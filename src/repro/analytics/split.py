"""Split-dataset pairwise reduction: flash-decoding-style fan-out.

The fused engine (``analytics.pairwise``) scans dataset tiles SEQUENTIALLY
inside one dispatch, so single-query-batch latency is O(m) no matter how
many devices exist. This module applies the flash-decoding trick (mirror of
``kernels/flash_decode``: split the KV/dataset axis into parallel partials
with carried merge state, then a small exact combine):

* ``knn``    — per-shard online (min-d2, argmin) with GLOBAL column indices;
               the cross-shard merge is strict-``<`` in shard order, so the
               first-occurrence tie-break of the sequential scan is
               preserved bit-for-bit (``merge_knn_partials``).
* ``dbscan`` — per-shard eps-ball counts (summed: ints are associative) and
               packed uint32 bitmask SEGMENTS concatenated in shard order;
               shard boundaries are tile-aligned (multiples of bk, hence of
               32), so the concatenated words ARE the sequential layout.
* ``kde``    — per-shard compensated (sum, comp) f32 exp-sum pairs, folded
               in float64 on the host, so the result is independent of the
               split point to ~f32 ulp.

Layered twice:

1. **Single-device split** (``fanout="xla"``): shards run as one batched
   XLA computation (``vmap`` over the shard axis — still ONE dispatch and
   ONE device->host transfer, preserving the engine invariants), with a
   grid-parallel ``kernels/pairwise_reduce`` variant behind
   ``use_kernels``. On a multi-core XLA:CPU / accelerator backend the
   shard axis is embarrassingly parallel; on this container's one core it
   is a correctness/abstraction win only (see the bench ``cores=`` caveat).
2. **Mesh fan-out** (``fanout="mesh"``): ``shard_map`` over dataset shards
   x query tiles — every device computes one (query-shard, dataset-shard)
   partial, and the same host merge combines them. Single-query latency
   then scales DOWN with device count, not just throughput.

Both layers produce the SAME partial contract, merged by the same three
``merge_*_partials`` primitives — the associativity property the tests pin
(``tests/test_split_scan.py``).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.bucketing import DEFAULT_BUCKETS, ShapeBucketCache, round_up
from repro.obs import span
from repro.analytics.pairwise import (
    DEFAULT_BLOCK,
    _clamp_block,
    _default_top_k,
    _kernel_backend_live,
    _pad_rows,
    _scan_core,
    kde_from_compensated,
)

__all__ = [
    "split_pairwise_knn",
    "split_pairwise_dbscan",
    "split_pairwise_kde",
    "merge_knn_partials",
    "merge_dbscan_partials",
    "merge_kde_partials",
    "mesh_shards",
]


# --------------------------------------------------------------- merges
# Host-side, numpy, EXACT (the carries are associative): these three
# functions are the whole combine step, shared by the vmap, kernel, and
# shard_map layers — and exercised directly by the property tests.


def merge_knn_partials(
    idx_parts: np.ndarray, d2_parts: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """(S, mq) per-shard argmin partials -> global (idx, d2).

    ``np.argmin`` over the shard axis keeps the LOWEST shard on d2 ties;
    each shard's own winner is its first-occurrence (lowest-column) min, so
    the composition picks the globally lowest column index among minima —
    exactly the sequential scan's strict-``<`` tie-break, bit-for-bit."""
    sel = np.argmin(d2_parts, axis=0)
    ar = np.arange(d2_parts.shape[1])
    return (
        np.ascontiguousarray(idx_parts[sel, ar]).astype(np.int32),
        np.ascontiguousarray(d2_parts[sel, ar]),
    )


def merge_dbscan_partials(
    count_parts: np.ndarray, packed_parts: np.ndarray, words: int | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """(S, mq) counts + (S, mq, w_s) packed segments -> global (counts, packed).

    Counts are integer sums (associative, exact). Packed segments
    concatenate in shard order along the word axis; because every shard
    holds a whole number of bk-tiles (bk % 32 == 0), word w of shard s is
    global word s*w_s + w — the sequential layout, no bit shifting needed.
    ``words`` trims trailing all-zero padding words to the sequential
    width, so split and sequential outputs compare bit-identical."""
    counts = count_parts.sum(axis=0, dtype=np.int64).astype(np.int32)
    packed = np.ascontiguousarray(
        np.concatenate(list(packed_parts), axis=1)
    )
    if words is not None:
        packed = np.ascontiguousarray(packed[:, :words])
    return counts, packed


def merge_kde_partials(
    sum_parts: np.ndarray, comp_parts: np.ndarray, m: int
) -> np.ndarray:
    """(S, mq) compensated pairs -> densities; float64 fold (see
    ``pairwise.kde_from_compensated``)."""
    return kde_from_compensated(sum_parts, comp_parts, m)


# ------------------------------------------------------- single-device split


@partial(
    jax.jit, static_argnames=("task", "bq", "bk", "use_top_k", "shards")
)
def _split_scan(
    xq: jax.Array,  # (mq_pad, d) padded queries, shared by every shard
    x_sh: jax.Array,  # (shards, shard_rows, d) tile-aligned dataset shards
    m: jax.Array,  # true GLOBAL dataset row count (traced)
    scalar: jax.Array,
    task: str,
    bq: int,
    bk: int,
    use_top_k: bool,
    shards: int,
):
    """All shard partials as ONE batched device computation (vmap over the
    shard axis: the shards are data-parallel inside a single dispatch, so
    the engine's one-dispatch/one-transfer invariants survive the split)."""
    shard_rows = x_sh.shape[1]
    offsets = jnp.arange(shards, dtype=jnp.int32) * shard_rows
    zero = jnp.int32(0)

    def one(xs, off):
        return _scan_core(
            xq, xs, m, scalar, off, zero,
            task=task, bq=bq, bk=bk, use_top_k=use_top_k,
        )

    return jax.vmap(one)(x_sh, offsets)


def _split_prepare(
    x: np.ndarray,
    queries: np.ndarray | None,
    shards: int,
    bq: int,
    bk: int,
    bucket: ShapeBucketCache,
):
    """Pad queries to the sequential bucket and the dataset to ``shards``
    equal tile-aligned shards covering at least the sequential pad.

    Shard size is a whole number of bk-tiles: ties, eps masks, and packed
    words then land on exactly the same tile boundaries as the sequential
    scan, which is what makes the merges bit-exact. Fully-padded trailing
    shards (m < shards * shard_rows) contribute inert partials (+inf d2,
    zero counts/sums) that can never win a merge."""
    x32 = np.ascontiguousarray(x, dtype=np.float32)
    q32 = x32 if queries is None else np.ascontiguousarray(
        queries, dtype=np.float32
    )
    mq_pad = bucket.bucket_tile_rows(q32.shape[0], bq)
    mk_pad = bucket.bucket_tile_rows(x32.shape[0], bk)
    nk = mk_pad // bk
    tiles_per_shard = -(-nk // shards)
    shard_rows = tiles_per_shard * bk
    xq_pad = _pad_rows(q32, mq_pad)
    x_sh = _pad_rows(x32, shards * shard_rows).reshape(
        shards, shard_rows, x32.shape[1]
    )
    return xq_pad, x_sh, mk_pad


# ------------------------------------------------------------ mesh fan-out


@lru_cache(maxsize=64)
def _mesh_fn(
    devices: tuple,
    q_shards: int,
    d_shards: int,
    task: str,
    bq: int,
    bk: int,
    use_top_k: bool,
):
    """Compiled shard_map fan-out over a (q_shards, d_shards) device mesh.

    Every device runs ``_scan_core`` on its (query shard, dataset shard)
    pair with global offsets from its mesh coordinates; outputs reassemble
    so the host sees d_shards partials in shard order — the same contract
    the single-device split produces, merged by the same primitives. The
    jitted program is named per task (``jit__mesh_knn``, ``jit__mesh_dbscan``,
    ``jit__mesh_kde``), so a device trace tells the fan-outs apart."""
    mesh = Mesh(
        np.asarray(devices, dtype=object).reshape(q_shards, d_shards),
        ("q", "d"),
    )

    def call(xq_pad, x_pad, m, scalar):
        lq = xq_pad.shape[0] // q_shards
        lk = x_pad.shape[0] // d_shards

        def local(xq_l, x_l, m_l, scalar_l):
            row0 = (lax.axis_index("q") * lq).astype(jnp.int32)
            col0 = (lax.axis_index("d") * lk).astype(jnp.int32)
            outs = _scan_core(
                xq_l, x_l, m_l, scalar_l, col0, row0,
                task=task, bq=bq, bk=bk, use_top_k=use_top_k,
            )
            if task == "dbscan":
                counts, packed = outs
                # counts gain a leading shard axis; packed keeps its word
                # axis on "d" so the global array concatenates segments in
                # dataset-shard order (the sequential word layout)
                return counts[None, :], packed
            return tuple(o[None, :] for o in outs)

        out_specs = (
            (P("d", "q"), P("q", "d"))
            if task == "dbscan"
            else (P("d", "q"), P("d", "q"))
        )
        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P("q", None), P("d", None), P(), P()),
            out_specs=out_specs,
            check_vma=False,
        )(xq_pad, x_pad, m, scalar)

    call.__name__ = call.__qualname__ = f"_mesh_{task}"
    return jax.jit(call)


def _mesh_gather(fn, xq_pad, x_pad, m: int, scalar, shards: int):
    """Dispatch a mesh fan-out and gather its partials to the host."""
    with span("analytics.mesh_scan", rows=m, shards=shards):
        return jax.device_get(
            fn(xq_pad, x_pad, jnp.int32(m), jnp.float32(scalar))
        )


def _mesh_prepare(
    x: np.ndarray,
    queries: np.ndarray | None,
    q_shards: int,
    d_shards: int,
    bq: int,
    bk: int,
    bucket: ShapeBucketCache,
):
    """Pad so every mesh coordinate gets whole tiles: queries to a multiple
    of q_shards*bq, dataset to a multiple of d_shards*bk (>= the sequential
    bucket, so trims match the sequential outputs)."""
    x32 = np.ascontiguousarray(x, dtype=np.float32)
    q32 = x32 if queries is None else np.ascontiguousarray(
        queries, dtype=np.float32
    )
    mq_pad = round_up(bucket.bucket_tile_rows(q32.shape[0], bq), q_shards * bq)
    mk_pad_seq = bucket.bucket_tile_rows(x32.shape[0], bk)
    mk_pad = round_up(mk_pad_seq, d_shards * bk)
    return _pad_rows(q32, mq_pad), _pad_rows(x32, mk_pad), mk_pad_seq


def _resolve_fanout(fanout: str, devices) -> tuple[str, list]:
    """``fanout="mesh"`` needs >1 device to mean anything; degrade to the
    single-device split (same results, same merge) instead of failing."""
    if fanout not in ("xla", "mesh"):
        raise ValueError(f"fanout must be 'xla' or 'mesh', got {fanout!r}")
    if fanout == "mesh":
        devs = list(devices) if devices is not None else list(jax.devices())
        if len(devs) > 1:
            return "mesh", devs
    return "xla", []


def mesh_shards(fanout: str, devices=None) -> int:
    """Dataset shards of each scan that ``fanout`` over ``devices`` runs on
    the mesh (the default 1 x n mesh); 0 where it resolves to one device."""
    fanout, devs = _resolve_fanout(fanout, devices)
    return len(devs) if fanout == "mesh" else 0


def _mesh_shape(mesh_shape, n: int) -> tuple[int, int]:
    if mesh_shape is None:
        return 1, n  # default: every device takes a dataset shard
    q_shards, d_shards = (int(mesh_shape[0]), int(mesh_shape[1]))
    if q_shards * d_shards != n or q_shards < 1 or d_shards < 1:
        raise ValueError(
            f"mesh_shape {mesh_shape} must factor the device count {n}"
        )
    return q_shards, d_shards


# ------------------------------------------------------------- public API


def split_pairwise_knn(
    x: np.ndarray,
    shards: int = 2,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    *,
    use_kernels: bool = False,
    use_top_k: bool | None = None,
    fanout: str = "xla",
    devices=None,
    mesh_shape: tuple[int, int] | None = None,
    bucket: ShapeBucketCache | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Split-dataset nearest-OTHER-row scan; bit-identical to
    ``pairwise_knn`` (indices AND squared distances) for every shard count."""
    bucket = bucket or DEFAULT_BUCKETS
    m = x.shape[0]
    shards = max(1, int(shards))
    if use_top_k is None:
        use_top_k = _default_top_k(m)
    bq = _clamp_block(block_q, m)
    bk = _clamp_block(block_k, m)

    fanout, devs = _resolve_fanout(fanout, devices)
    if fanout == "mesh":
        q_shards, d_shards = _mesh_shape(mesh_shape, len(devs))
        xq_pad, x_pad, _ = _mesh_prepare(
            x, None, q_shards, d_shards, bq, bk, bucket
        )
        fn = _mesh_fn(
            tuple(devs), q_shards, d_shards, "knn", bq, bk, bool(use_top_k)
        )
        idx_p, d2_p = _mesh_gather(fn, xq_pad, x_pad, m, 0.0, d_shards)
    elif use_kernels and _kernel_backend_live():
        from repro.kernels.pairwise_reduce.ops import pairwise_knn_split_reduce

        xq_pad, x_sh, _ = _split_prepare(x, None, shards, bq, bk, bucket)
        idx_p, d2_p = jax.device_get(
            pairwise_knn_split_reduce(
                xq_pad, x_sh.reshape(-1, x_sh.shape[2]), m, shards,
                block_q=bq, block_k=bk,
            )
        )
    else:
        xq_pad, x_sh, _ = _split_prepare(x, None, shards, bq, bk, bucket)
        idx_p, d2_p = jax.device_get(
            _split_scan(
                jnp.asarray(xq_pad),
                jnp.asarray(x_sh),
                jnp.int32(m),
                jnp.float32(0.0),
                task="knn",
                bq=bq,
                bk=bk,
                use_top_k=use_top_k,
                shards=shards,
            )
        )
    idx_p, d2_p = np.asarray(idx_p), np.asarray(d2_p)
    with span("analytics.merge", rows=m, shards=idx_p.shape[0]):
        idx, d2 = merge_knn_partials(idx_p, d2_p)
    return idx[:m], d2[:m]


def split_pairwise_dbscan(
    x: np.ndarray,
    eps: float,
    shards: int = 2,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    *,
    use_kernels: bool = False,
    fanout: str = "xla",
    devices=None,
    mesh_shape: tuple[int, int] | None = None,
    bucket: ShapeBucketCache | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Split-dataset eps-ball scan; counts and packed bitmask rows are
    bit-identical to ``pairwise_dbscan`` (same word layout, same width)."""
    bucket = bucket or DEFAULT_BUCKETS
    m = x.shape[0]
    shards = max(1, int(shards))
    bq = _clamp_block(block_q, m)
    bk = _clamp_block(block_k, m)
    eps2 = np.float32(float(eps) * float(eps))  # ONE rounding — see pairwise

    fanout, devs = _resolve_fanout(fanout, devices)
    if fanout == "mesh":
        q_shards, d_shards = _mesh_shape(mesh_shape, len(devs))
        xq_pad, x_pad, mk_pad_seq = _mesh_prepare(
            x, None, q_shards, d_shards, bq, bk, bucket
        )
        fn = _mesh_fn(tuple(devs), q_shards, d_shards, "dbscan", bq, bk, False)
        counts_p, packed = _mesh_gather(fn, xq_pad, x_pad, m, eps2, d_shards)
        # the mesh path reassembles the packed words globally already —
        # only the counts still carry a shard axis to fold
        counts = (
            np.asarray(counts_p).sum(axis=0, dtype=np.int64).astype(np.int32)
        )
        packed = np.ascontiguousarray(
            np.asarray(packed)[:, : mk_pad_seq // 32]
        )
        return counts[:m], packed[:m]
    if use_kernels and _kernel_backend_live():
        from repro.kernels.pairwise_reduce.ops import (
            pairwise_dbscan_split_reduce,
        )
        from repro.kernels.pairwise_reduce.pairwise_reduce import (
            DBSCAN_BLOCK_K,
        )

        # the kernel's packed-word blocks tile only at whole DBSCAN_BLOCK_K
        # dataset tiles; the merge trims its wider all-zero padding words
        # back to the sequential width
        mk_pad_seq = bucket.bucket_tile_rows(m, bk)
        bk = round_up(bk, DBSCAN_BLOCK_K)
        xq_pad, x_sh, _ = _split_prepare(x, None, shards, bq, bk, bucket)
        counts_p, packed_p = jax.device_get(
            pairwise_dbscan_split_reduce(
                xq_pad, x_sh.reshape(-1, x_sh.shape[2]), m, eps2, shards,
                block_q=bq, block_k=bk,
            )
        )
    else:
        xq_pad, x_sh, mk_pad_seq = _split_prepare(
            x, None, shards, bq, bk, bucket
        )
        counts_p, packed_p = jax.device_get(
            _split_scan(
                jnp.asarray(xq_pad),
                jnp.asarray(x_sh),
                jnp.int32(m),
                jnp.float32(eps2),
                task="dbscan",
                bq=bq,
                bk=bk,
                use_top_k=False,
                shards=shards,
            )
        )
    counts, packed = merge_dbscan_partials(
        np.asarray(counts_p), np.asarray(packed_p), words=mk_pad_seq // 32
    )
    return counts[:m], packed[:m]


def split_pairwise_kde(
    x: np.ndarray,
    queries: np.ndarray | None = None,
    bandwidth: float = 1.0,
    shards: int = 2,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    *,
    use_kernels: bool = False,
    fanout: str = "xla",
    devices=None,
    mesh_shape: tuple[int, int] | None = None,
    bucket: ShapeBucketCache | None = None,
) -> np.ndarray:
    """Split-dataset Gaussian KDE; compensated shard partials folded in
    float64 make the densities split-point independent to ~f32 ulp."""
    bucket = bucket or DEFAULT_BUCKETS
    m = x.shape[0]
    mq = m if queries is None else queries.shape[0]
    shards = max(1, int(shards))
    bq = _clamp_block(block_q, mq)
    bk = _clamp_block(block_k, m)
    inv = np.float32(1.0 / (2.0 * bandwidth * bandwidth))

    fanout, devs = _resolve_fanout(fanout, devices)
    if fanout == "mesh":
        q_shards, d_shards = _mesh_shape(mesh_shape, len(devs))
        xq_pad, x_pad, _ = _mesh_prepare(
            x, queries, q_shards, d_shards, bq, bk, bucket
        )
        fn = _mesh_fn(tuple(devs), q_shards, d_shards, "kde", bq, bk, False)
        sums_p, comps_p = _mesh_gather(fn, xq_pad, x_pad, m, inv, d_shards)
    elif use_kernels and _kernel_backend_live():
        from repro.kernels.pairwise_reduce.ops import pairwise_kde_split_reduce

        xq_pad, x_sh, _ = _split_prepare(x, queries, shards, bq, bk, bucket)
        sums_p, comps_p = jax.device_get(
            pairwise_kde_split_reduce(
                xq_pad, x_sh.reshape(-1, x_sh.shape[2]), m, inv, shards,
                block_q=bq, block_k=bk,
            )
        )
    else:
        xq_pad, x_sh, _ = _split_prepare(x, queries, shards, bq, bk, bucket)
        sums_p, comps_p = jax.device_get(
            _split_scan(
                jnp.asarray(xq_pad),
                jnp.asarray(x_sh),
                jnp.int32(m),
                jnp.float32(inv),
                task="kde",
                bq=bq,
                bk=bk,
                use_top_k=False,
                shards=shards,
            )
        )
    dens = merge_kde_partials(
        np.asarray(sums_p)[:, :mq], np.asarray(comps_p)[:, :mq], m
    )
    return dens[:mq]
