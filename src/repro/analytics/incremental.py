"""Incremental downstream-analytics maintenance for append-only streams.

The delta-serving subsystem (``serve_drop.delta``) promises subscribers
O(suffix) work per append *end to end* — and the downstream analytics are
where that promise is hardest: a cold kNN/DBSCAN/KDE pass over the grown
reduced dataset is the O(m^2 k) scan DROP's cost model prices, re-paid on
every append. This module maintains the three downstream states
incrementally instead, with the new rows' pairwise contributions computed
by the SAME fused tile body the cold path runs (``pairwise._scan_core``),
just over *rectangular* shards:

* **scan A** (old rows x new rows, ``col_offset = m_old``) — how the
  appended suffix changes every existing row's reduction;
* **scan B** (new rows x all rows, ``row_offset = m_old``) — the new rows'
  own full reduction, identical tile layout to the cold scan's.

Per-append device work is O(s * m), not O(m^2). The carried states and why
each merge is exact:

* **kNN** — carried (nn_idx, nn_d2). A d2 element is a function of its two
  rows only (same d-length contraction regardless of tile position — the
  same invariant ``analytics.split``'s shard merges rely on), and the
  engine's tie-break (per-tile first-occurrence argmin + strict-``<``
  carry) composes associatively over ordered column groups, so folding
  scan A into the carry with strict ``<`` (old state, lower columns, wins
  ties) reproduces the cold scan's lowest-column-argmin bit-for-bit.
* **DBSCAN** — degrees are exact integer sums; the adjacency bitmask is
  kept as packed SEGMENTS (one row-block per append, one column-patch per
  append) so arbitrary — non-tile-aligned — append boundaries never need
  bit shifting. Labels are NOT re-grown by BFS: ``_bfs``'s output is a
  pure function of (core set, core adjacency) — cluster ids are components
  of the core subgraph ranked by minimal core index, border labels the
  minimum id over adjacent components (see ``_DbscanLabeler``) — and on an
  append-only stream degrees are monotone, so the core set only grows and
  components only merge. A union-find over core points repaired only in
  the eps-neighborhood of appended/promoted points therefore yields labels
  bit-identical to a cold ``dbscan()``.
* **KDE** — per-row compensated (sum, comp) f32 pairs from each scan are
  folded into a float64 running total (exactly the shard-merge semantics
  of ``kde_from_compensated``), so densities match a cold scan to ~f32 ulp
  — the same split-point independence the split engine guarantees.

``rebuild()`` resets everything from a cold scan — the rollback path when
the serving basis rotates and old reduced coordinates become invalid.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.analytics.dbscan import NOISE, _bfs  # noqa: F401  (parity oracle)
from repro.analytics.pairwise import (
    DEFAULT_BLOCK,
    NeighborDecoder,
    _clamp_block,
    _pad_rows,
    _scan_core,
    pairwise_dbscan,
    pairwise_kde,
    pairwise_knn,
)
from repro.core.bucketing import DEFAULT_BUCKETS, ShapeBucketCache

__all__ = ["IncrementalAnalytics", "AnalyticsSnapshot"]


@partial(jax.jit, static_argnames=("task", "bq", "bk"))
def _rect_scan(xq, x, m, scalar, col_offset, row_offset, task, bq, bk):
    """One rectangular (query shard x dataset shard) pass of the fused tile
    body — the sequential engine with nonzero global offsets."""
    return _scan_core(
        xq, x, m, scalar, col_offset, row_offset, task=task, bq=bq, bk=bk
    )


def _run_rect(
    queries: np.ndarray,
    data: np.ndarray,
    m_total: int,
    scalar: float,
    col_offset: int,
    row_offset: int,
    task: str,
    block: int,
    bucket: ShapeBucketCache,
):
    """Host wrapper: pad both shards through the shared buckets, run the
    jitted rectangular scan, slice the true rows back out."""
    nq, nk = queries.shape[0], data.shape[0]
    bq = _clamp_block(block, nq)
    bk = _clamp_block(block, nk)
    xq_pad = _pad_rows(np.ascontiguousarray(queries, np.float32),
                       bucket.bucket_tile_rows(nq, bq))
    xk_pad = _pad_rows(np.ascontiguousarray(data, np.float32),
                       bucket.bucket_tile_rows(nk, bk))
    a, b = jax.device_get(
        _rect_scan(
            jnp.asarray(xq_pad), jnp.asarray(xk_pad),
            jnp.int32(m_total), jnp.float32(scalar),
            jnp.int32(col_offset), jnp.int32(row_offset),
            task=task, bq=bq, bk=bk,
        )
    )
    return np.asarray(a)[:nq], np.asarray(b)[:nq]


# ------------------------------------------------------------ DBSCAN labels


class _SegmentedAdjacency:
    """Packed eps-ball adjacency stored as append segments.

    * ``row_blocks[t] = (row0, ncols, packed)`` — the rows appended at step
      t, with their full adjacency over columns [0, ncols) (scan B output;
      t = 0 is the bootstrap full scan).
    * ``col_patches[u] = (base, ncols, packed)`` — ALL rows that existed
      before append u (rows [0, base)) against the appended columns
      [base, base + ncols) (scan A output; local bit c maps to global
      column base + c).

    ``neighbors(r)`` decodes r's block row (self excluded) plus every later
    patch row, each ascending, concatenated ascending — the exact neighbor
    sets a cold ``NeighborDecoder`` would produce, at O(words of row r)."""

    def __init__(self) -> None:
        self.row_blocks: list[tuple[int, int, np.ndarray]] = []
        self.col_patches: list[tuple[int, int, np.ndarray]] = []

    def add_block(self, row0: int, ncols: int, packed: np.ndarray) -> None:
        self.row_blocks.append((row0, ncols, packed))

    def add_patch(self, base: int, ncols: int, packed: np.ndarray) -> None:
        self.col_patches.append((base, ncols, packed))

    @staticmethod
    def _decode(words: np.ndarray, ncols: int) -> np.ndarray:
        bits = np.unpackbits(
            np.ascontiguousarray(words).view(np.uint8), bitorder="little"
        )[:ncols]
        return np.flatnonzero(bits)

    def neighbors(self, r: int) -> np.ndarray:
        pieces = []
        for row0, ncols, packed in self.row_blocks:
            if row0 <= r < row0 + packed.shape[0]:
                own = self._decode(packed[r - row0], ncols)
                pieces.append(own[own != r])
                break
        for base, ncols, packed in self.col_patches:
            if r < base:
                pieces.append(self._decode(packed[r], ncols) + base)
        if not pieces:
            return np.empty(0, dtype=np.int64)
        return np.concatenate(pieces)


class _DbscanLabeler:
    """Union-find over core points + support sets for border points.

    ``_bfs`` labels are a pure function of the eps-graph (the BFS docstring
    semantics, restated order-free): a point is *core* iff its degree
    (self included) clears ``min_samples``; clusters are the connected
    components of the core-core adjacency, numbered by the rank of each
    component's minimal core index; a core point takes its component's id;
    a non-core point takes the MINIMUM id over components it is eps-
    adjacent to (the lowest-numbered cluster expands first and claims it),
    else NOISE. The parity suite pins this equivalence against ``_bfs``
    directly.

    Append-only monotonicity: degrees never decrease, so the core set only
    grows and components only merge — both are union-find-friendly. Per
    append only the NEWLY core points (appended or promoted) need their
    neighborhoods walked."""

    def __init__(self, min_samples: int) -> None:
        self.min_samples = int(min_samples)
        self.parent = np.empty(0, dtype=np.int64)
        self.is_core = np.empty(0, dtype=bool)
        self.min_core: dict[int, int] = {}  # root -> minimal core index
        self.support: dict[int, set[int]] = {}  # non-core -> adjacent cores

    def _find(self, a: int) -> int:
        p = self.parent
        root = a
        while p[root] != root:
            root = p[root]
        while p[a] != root:  # path compression
            p[a], a = root, int(p[a])
        return root

    def _union(self, a: int, b: int) -> None:
        ra, rb = self._find(a), self._find(b)
        if ra == rb:
            return
        if ra > rb:  # keep the lower root: min_core stays cheap to track
            ra, rb = rb, ra
        self.parent[rb] = ra
        self.min_core[ra] = min(self.min_core[ra], self.min_core.pop(rb))

    def grow(
        self,
        degrees: np.ndarray,
        prev_degrees_old: np.ndarray | None,
        adj: _SegmentedAdjacency,
        m_old: int,
    ) -> None:
        """Fold one append in: ``degrees`` are the grown exact degrees,
        ``prev_degrees_old`` the pre-append degrees of the old rows (None
        on bootstrap, when every point is 'new')."""
        m = degrees.shape[0]
        grown_parent = np.arange(m, dtype=np.int64)
        grown_parent[: self.parent.shape[0]] = self.parent
        self.parent = grown_parent
        was_core = np.zeros(m, dtype=bool)
        was_core[: self.is_core.shape[0]] = self.is_core
        self.is_core = degrees >= self.min_samples
        newly_core = np.flatnonzero(self.is_core & ~was_core)
        # mark every newly-core point before walking any neighborhood, so a
        # pair of simultaneously promoted neighbors unions from either side
        for p in newly_core:
            p = int(p)
            self.min_core[p] = p
            self.support.pop(p, None)
        for p in newly_core:
            p = int(p)
            for q in adj.neighbors(p):
                q = int(q)
                if self.is_core[q]:
                    self._union(p, q)
                else:
                    self.support.setdefault(q, set()).add(p)
        # appended non-core rows: their support is their core neighborhood
        # (promoted cores above already pushed themselves into old rows'
        # support sets — only the brand-new rows still need a walk)
        for r in range(m_old, m):
            if not self.is_core[r]:
                sup = {int(q) for q in adj.neighbors(r) if self.is_core[q]}
                if sup:
                    self.support[r] = sup

    def labels(self) -> np.ndarray:
        m = self.parent.shape[0]
        out = np.full(m, NOISE, dtype=np.int64)
        core_idx = np.flatnonzero(self.is_core)
        if core_idx.size == 0:
            return out
        roots = np.fromiter(
            (self._find(int(p)) for p in core_idx), dtype=np.int64,
            count=core_idx.size,
        )
        order = sorted(set(roots.tolist()), key=lambda r: self.min_core[r])
        cid = {r: i for i, r in enumerate(order)}
        out[core_idx] = np.fromiter(
            (cid[int(r)] for r in roots), dtype=np.int64, count=roots.size
        )
        for q, sup in self.support.items():
            if sup and not self.is_core[q]:
                out[q] = min(cid[self._find(a)] for a in sup)
        return out


# --------------------------------------------------------------- the engine


class AnalyticsSnapshot:
    """One consistent view of the three maintained downstream outputs."""

    __slots__ = ("knn_idx", "knn_d2", "labels", "densities")

    def __init__(self, knn_idx, knn_d2, labels, densities) -> None:
        self.knn_idx = knn_idx
        self.knn_d2 = knn_d2
        self.labels = labels
        self.densities = densities


class IncrementalAnalytics:
    """Per-subscription downstream state with O(s * m) appends.

    Bootstrap (and ``rebuild()``) run the COLD fused scans — the same calls
    a ``run_downstream`` leg makes — so the initial state is the cold state
    by construction; every ``append()`` then folds the suffix in via two
    rectangular ``_scan_core`` passes per task and the exact merges
    described in the module docstring."""

    def __init__(
        self,
        y: np.ndarray,
        *,
        eps: float,
        min_samples: int = 5,
        bandwidth: float = 1.0,
        block: int = DEFAULT_BLOCK,
        bucket: ShapeBucketCache | None = None,
    ) -> None:
        self.eps = float(eps)
        self.min_samples = int(min_samples)
        self.bandwidth = float(bandwidth)
        self.block = int(block)
        self.bucket = bucket or DEFAULT_BUCKETS
        self.rebuild(y)

    # float32(eps * eps): ONE rounding, matching pairwise_dbscan exactly —
    # eps-boundary parity with the cold path depends on it
    @property
    def _eps2(self) -> np.float32:
        return np.float32(self.eps * self.eps)

    @property
    def _inv2h2(self) -> np.float32:
        return np.float32(1.0 / (2.0 * self.bandwidth * self.bandwidth))

    @property
    def rows(self) -> int:
        return int(self._y.shape[0])

    # ----------------------------------------------------------- rebuild

    def rebuild(self, y: np.ndarray) -> AnalyticsSnapshot:
        """Cold bootstrap over ``y`` (reduced coordinates) — the rollback
        path: the basis rotated, every cached pairwise quantity is void."""
        y = np.ascontiguousarray(np.asarray(y), dtype=np.float32)
        if y.ndim != 2:
            raise ValueError(f"expected (m, k) reduced rows, got {y.shape}")
        self._y = y
        m = y.shape[0]
        self.nn_idx, self.nn_d2 = pairwise_knn(
            y, self.block, self.block, bucket=self.bucket
        )
        counts, packed = pairwise_dbscan(
            y, self.eps, self.block, self.block, bucket=self.bucket
        )
        self.degrees = counts.astype(np.int64)
        self._adj = _SegmentedAdjacency()
        self._adj.add_block(0, m, packed)
        self._labeler = _DbscanLabeler(self.min_samples)
        self._labeler.grow(self.degrees, None, self._adj, m_old=0)
        self.labels = self._labeler.labels()
        # KDE: keep the compensated pairs' exact float64 value per row; the
        # density divides by the CURRENT row count at snapshot time
        scan = _run_rect(
            y, y, m, self._inv2h2, 0, 0, "kde", self.block, self.bucket
        )
        self._kde64 = scan[0].astype(np.float64) + scan[1].astype(np.float64)
        return self.snapshot()

    # ------------------------------------------------------------ append

    def append(self, y_new: np.ndarray) -> dict:
        """Fold appended reduced rows in; returns the O(suffix) patch:
        ``changed`` (old rows whose nearest neighbor moved) plus the new
        rows' values. Labels and densities are returned whole from
        ``snapshot()`` — every append can renumber clusters and rescales
        every density by 1/m, so their *values* are O(m) even though the
        compute is O(s * m)."""
        y_new = np.ascontiguousarray(np.asarray(y_new), dtype=np.float32)
        s = y_new.shape[0]
        m_old = self.rows
        if s == 0:
            return {"changed": np.empty(0, np.int64)}
        if y_new.ndim != 2 or y_new.shape[1] != self._y.shape[1]:
            raise ValueError(
                f"append shape {y_new.shape} does not extend "
                f"{self._y.shape}"
            )
        grown = np.concatenate([self._y, y_new], axis=0)
        m = m_old + s

        # kNN: scan A folds new columns into the old carry (strict <: the
        # old state, holding lower column indices, keeps ties — the cold
        # scan's first-occurrence argmin); scan B is the new rows' full
        # reduction in the cold scan's own tile layout
        idx_a, d2_a = _run_rect(
            self._y, y_new, m, 0.0, m_old, 0,
            "knn", self.block, self.bucket,
        )
        idx_b, d2_b = _run_rect(
            y_new, grown, m, 0.0, 0, m_old,
            "knn", self.block, self.bucket,
        )
        better = d2_a < self.nn_d2
        changed = np.flatnonzero(better)
        self.nn_idx = np.concatenate(
            [np.where(better, idx_a, self.nn_idx).astype(np.int32), idx_b]
        )
        self.nn_d2 = np.concatenate([np.where(better, d2_a, self.nn_d2), d2_b])

        # DBSCAN: exact integer degree folds + adjacency segments, then
        # label repair confined to appended/promoted neighborhoods
        cnt_a, packed_a = _run_rect(
            self._y, y_new, m, self._eps2, m_old, 0,
            "dbscan", self.block, self.bucket,
        )
        cnt_b, packed_b = _run_rect(
            y_new, grown, m, self._eps2, 0, m_old,
            "dbscan", self.block, self.bucket,
        )
        prev_degrees = self.degrees
        self.degrees = np.concatenate(
            [prev_degrees + cnt_a, cnt_b.astype(np.int64)]
        )
        self._adj.add_patch(m_old, s, packed_a)
        self._adj.add_block(m_old, m, packed_b)
        self._labeler.grow(self.degrees, prev_degrees, self._adj, m_old)
        self.labels = self._labeler.labels()

        # KDE: compensated pairs folded in float64 (shard-merge semantics)
        sum_a, comp_a = _run_rect(
            self._y, y_new, m, self._inv2h2, m_old, 0,
            "kde", self.block, self.bucket,
        )
        sum_b, comp_b = _run_rect(
            y_new, grown, m, self._inv2h2, 0, m_old,
            "kde", self.block, self.bucket,
        )
        self._kde64 = np.concatenate([
            self._kde64 + (sum_a.astype(np.float64) + comp_a.astype(np.float64)),
            sum_b.astype(np.float64) + comp_b.astype(np.float64),
        ])

        self._y = grown
        return {
            "changed": changed,
            "idx": self.nn_idx[changed],
            "d2": self.nn_d2[changed],
            "append_idx": idx_b,
            "append_d2": d2_b,
        }

    # ---------------------------------------------------------- snapshot

    def snapshot(self) -> AnalyticsSnapshot:
        return AnalyticsSnapshot(
            knn_idx=self.nn_idx.copy(),
            knn_d2=self.nn_d2.copy(),
            labels=self.labels.copy(),
            densities=(self._kde64 / float(self.rows)).astype(np.float32),
        )
