"""Fused pairwise-analytics engine: fused-vs-legacy parity for all three
tasks (kNN indices, DBSCAN labels, KDE densities) over padded tails,
near-duplicate rows, and the m=1 degenerate input; both the CPU
(mask+argmin) and interpret-mode Pallas kernel paths; the packed-bitmask
neighbor-set property; the legacy DBSCAN remainder-recompile regression;
the cost-model extension; kNN self-exclusion on every path against a
float64 brute force; and no sort in any compiled kNN scan."""

import sys
from functools import partial

import numpy as np
import pytest

from repro.analytics import (
    dbscan,
    dbscan_legacy,
    gaussian_kde,
    gaussian_kde_legacy,
    nearest_neighbors,
    nearest_neighbors_legacy,
    pairwise_dbscan,
    pairwise_kde,
    pairwise_knn,
    unpack_neighbors,
)
from repro.analytics.dbscan import _neighbor_lists

# the package re-exports dbscan the FUNCTION; the module object is needed
# for monkeypatching the jitted radius scan
dbscan_mod = sys.modules["repro.analytics.dbscan"]


@pytest.fixture(scope="module")
def xdup():
    """Seeded data with a padded tail (131 % 32 != 0) and a near-duplicate
    pair (rows 3/7 differ by 1e-4: exercises tie-ish argmin behavior)."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(131, 8)).astype(np.float32)
    x[7] = x[3] + 1e-4
    return x


# ------------------------------------------------------------------- kNN


def test_knn_fused_matches_legacy(xdup):
    """The engine's masked min/argmin reduction reproduces the legacy host
    loop exactly, padded tail included."""
    ref = nearest_neighbors_legacy(xdup, block=64)
    idx, d2 = pairwise_knn(xdup, 32, 32)
    np.testing.assert_array_equal(idx, ref)
    true_d2 = np.sum((xdup - xdup[idx]) ** 2, axis=1)
    np.testing.assert_allclose(d2, true_d2, rtol=1e-3, atol=1e-4)


def _grid_rows(m: int, d: int) -> np.ndarray:
    """Rows on a quarter grid in [-4, 4]: every squared distance, in the
    unrolled (d <= 4) and the gemm body alike, is exact in float32, so
    ties are exact ties and a float64 brute force is an exact oracle.

    At m = 150 with 64-row tiles (pad to 192): exact duplicates straddle
    the tile boundaries (63/64, 127/128), a triple spans three tiles
    (5/70/140), a near-duplicate pair (one grid step apart) straddles a
    boundary (100/129), and the last real row, in the padded tail tile,
    duplicates row 0."""
    rng = np.random.default_rng(11)
    x = (rng.integers(-16, 17, size=(m, d)) / 4.0).astype(np.float32)
    if m == 150:
        x[64] = x[63]
        x[128] = x[127]
        x[70] = x[140] = x[5]
        x[129] = x[100]
        x[129, 0] += 0.25
        x[149] = x[0]
    return x


def _knn_float64(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Brute-force nearest OTHER row in float64, first occurrence on ties."""
    x64 = x.astype(np.float64)
    d2 = ((x64[:, None, :] - x64[None, :, :]) ** 2).sum(axis=-1)
    np.fill_diagonal(d2, np.inf)
    idx = np.argmin(d2, axis=1)
    return idx, d2[np.arange(x.shape[0]), idx]


def _knn_incremental(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    from repro.analytics.incremental import IncrementalAnalytics

    m0 = max(1, x.shape[0] // 2)
    inc = IncrementalAnalytics(x[:m0], eps=0.5, block=64)
    inc.append(x[m0:])
    snap = inc.snapshot()
    return snap.knn_idx, snap.knn_d2


def _knn_split(x: np.ndarray, shards: int) -> tuple[np.ndarray, np.ndarray]:
    from repro.analytics.split import split_pairwise_knn

    return split_pairwise_knn(x, shards=shards, block_q=64, block_k=64)


_KNN_PATHS = {
    "sequential": lambda x: pairwise_knn(x, 64, 64),
    "split2": lambda x: _knn_split(x, 2),
    "split3": lambda x: _knn_split(x, 3),
    "incremental": _knn_incremental,
}


@pytest.mark.parametrize("path", sorted(_KNN_PATHS))
@pytest.mark.parametrize("m,d", [(2, 3), (150, 3), (150, 8)])
def test_knn_self_exclusion_matches_float64(path, m, d):
    """Every kNN path excludes self by the masked min/argmin and keeps the
    first occurrence on ties: indices and squared distances equal a
    float64 brute force exactly, across tile boundaries, in the padded
    tail, at m = 2, in the unrolled (d = 3) and the gemm (d = 8) body."""
    x = _grid_rows(m, d)
    ref_idx, ref_d2 = _knn_float64(x)
    idx, d2 = _KNN_PATHS[path](x)
    np.testing.assert_array_equal(idx, ref_idx)
    np.testing.assert_array_equal(d2, ref_d2.astype(np.float32))
    np.testing.assert_array_equal(
        nearest_neighbors_legacy(x, block=64), ref_idx
    )


def _primitives(jaxpr) -> set[str]:
    """Names of every primitive in ``jaxpr`` and its nested bodies."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if hasattr(sub, "eqns"):
                    names |= _primitives(sub)
    return names


def _knn_program(name: str):
    """The jaxpr of one compiled kNN scan at a small shape."""
    import jax
    import jax.numpy as jnp

    from repro.analytics.incremental import _rect_scan
    from repro.analytics.pairwise import _fused_scan
    from repro.analytics.split import _mesh_fn, _split_scan

    xq = jnp.zeros((128, 8), jnp.float32)
    m, s, zero = jnp.int32(100), jnp.float32(0.0), jnp.int32(0)
    tiles = dict(task="knn", bq=64, bk=64)
    if name == "fused":
        return jax.make_jaxpr(partial(_fused_scan, **tiles))(xq, xq, m, s)
    if name == "split":
        x_sh = jnp.zeros((2, 64, 8), jnp.float32)
        fn = partial(_split_scan, shards=2, **tiles)
        return jax.make_jaxpr(fn)(xq, x_sh, m, s)
    if name == "rect":
        fn = partial(_rect_scan, **tiles)
        return jax.make_jaxpr(fn)(xq, xq, m, s, zero, zero)
    mesh = _mesh_fn(tuple(jax.devices()[:1]), 1, 1, "knn", 64, 64)
    return jax.make_jaxpr(mesh)(xq, xq, m, s)


@pytest.mark.parametrize("name", ["fused", "split", "rect", "mesh"])
def test_knn_scan_has_no_sort(name):
    """The kNN tile reduction is a masked min/argmin in every compiled
    scan: no ``top_k`` or ``sort`` primitive anywhere in the program (the
    walker does reach the tile body: its ``argmin`` is found)."""
    prims = _primitives(_knn_program(name))
    assert "argmin" in prims, sorted(prims)
    assert not {"top_k", "sort"} & prims, sorted(prims)


def test_knn_single_point_returns_self(xdup):
    """m=1 keeps the legacy degenerate behavior (self index) on every path."""
    one = xdup[:1]
    assert nearest_neighbors_legacy(one).tolist() == [0]
    assert nearest_neighbors(one).tolist() == [0]
    idx, _ = pairwise_knn(one, 32, 32)
    assert idx.tolist() == [0]


def test_knn_default_block_padding(xdup):
    """m far below the default 1024 block: one padded tile, exact parity."""
    np.testing.assert_array_equal(
        nearest_neighbors(xdup), nearest_neighbors_legacy(xdup)
    )


# ----------------------------------------------------------------- DBSCAN


@pytest.mark.parametrize("min_samples", [2, 4, 8])
def test_dbscan_fused_matches_legacy_blobs(min_samples):
    """Label parity on clustered data with a ragged tail. Both paths share
    the BFS, so parity is exact (border-point labels are traversal-order
    dependent — identical neighbor arrays mean identical traversal)."""
    rng = np.random.default_rng(0)
    x = np.concatenate(
        [
            rng.normal(0, 0.12, size=(61, 3)),
            rng.normal(4, 0.12, size=(49, 3)),
            rng.uniform(-8, 8, size=(20, 3)),  # sparse: noise candidates
        ]
    ).astype(np.float32)
    want = dbscan_legacy(x, eps=0.6, min_samples=min_samples, block=64)
    got = dbscan(x, eps=0.6, min_samples=min_samples, block=64)
    np.testing.assert_array_equal(got, want)


def test_dbscan_fused_matches_legacy_near_duplicates(xdup):
    want = dbscan_legacy(xdup, eps=1.5, min_samples=3, block=32)
    got = dbscan(xdup, eps=1.5, min_samples=3, block=32)
    np.testing.assert_array_equal(got, want)


def test_dbscan_single_point():
    one = np.zeros((1, 4), np.float32)
    np.testing.assert_array_equal(
        dbscan(one, eps=0.5, min_samples=2),
        dbscan_legacy(one, eps=0.5, min_samples=2),
    )


def test_packed_bitmask_equals_nonzero_sets(xdup):
    """The packed uint32 bitmask decodes to EXACTLY the neighbor sets the
    legacy per-row np.nonzero produced, and the fused degree counts are
    those set sizes + self."""
    for eps, block in ((0.8, 32), (1.5, 64), (3.0, 128)):
        counts, packed = pairwise_dbscan(xdup, eps, block, block)
        nbrs = _neighbor_lists(xdup, eps, block=block)
        for p in range(xdup.shape[0]):
            got = unpack_neighbors(packed[p], p, xdup.shape[0])
            np.testing.assert_array_equal(got, nbrs[p])
            assert counts[p] == nbrs[p].size + 1  # self included


def test_packed_bitmask_property_random_shapes():
    """Seeded sweep over ragged shapes/eps (the deterministic mirror of the
    hypothesis property below, always collected)."""
    for seed in range(4):
        rng = np.random.default_rng(seed)
        m = int(rng.integers(1, 90))
        d = int(rng.integers(1, 6))
        eps = float(rng.uniform(0.3, 3.0))
        x = rng.normal(size=(m, d)).astype(np.float32)
        counts, packed = pairwise_dbscan(x, eps, 32, 32)
        sq = np.sum(x * x, axis=1)
        d2 = sq[:, None] + sq[None, :] - 2.0 * (x @ x.T)
        want_mask = d2 <= np.float32(eps * eps)  # single rounding, as engine
        for p in range(m):
            want = np.flatnonzero(want_mask[p])
            np.testing.assert_array_equal(
                unpack_neighbors(packed[p], p, m), want[want != p]
            )
            assert counts[p] == want.size


def test_packed_bitmask_property_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        m=st.integers(1, 70),
        d=st.integers(1, 5),
        eps=st.floats(0.2, 4.0),
    )
    def check(seed, m, d, eps):
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(m, d)).astype(np.float32)
        _counts, packed = pairwise_dbscan(x, eps, 32, 32)
        sq = np.sum(x * x, axis=1)
        d2 = sq[:, None] + sq[None, :] - 2.0 * (x @ x.T)
        mask = d2 <= np.float32(eps * eps)
        for p in range(m):
            want = np.flatnonzero(mask[p])
            np.testing.assert_array_equal(
                unpack_neighbors(packed[p], p, m), want[want != p]
            )

    check()


def test_legacy_neighbor_lists_single_compiled_shape(monkeypatch):
    """Remainder-block regression: the legacy radius scan must pad the tail
    block instead of minting a fresh compile per distinct m % block."""
    shapes = []
    orig = dbscan_mod._radius_block

    def spy(xq, xs, eps2):
        shapes.append(tuple(xq.shape))
        return orig(xq, xs, eps2)

    monkeypatch.setattr(dbscan_mod, "_radius_block", spy)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(130, 5)).astype(np.float32)  # 130 = 2*64 + 2
    nbrs = _neighbor_lists(x, 1.0, block=64)
    assert len(nbrs) == 130
    assert len(shapes) == 3
    assert set(shapes) == {(64, 5)}  # ONE compiled query shape, tail padded
    # and the padded tail rows still produce correct neighbor sets
    sq = np.sum(x * x, axis=1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * (x @ x.T)
    for p in (128, 129):
        want = np.flatnonzero(d2[p] <= 1.0)
        np.testing.assert_array_equal(nbrs[p], want[want != p])


# -------------------------------------------------------------------- KDE


def test_kde_fused_matches_legacy(xdup):
    """Only the summation tree differs between the paths (tile partials vs
    one row reduce), so parity is tight but not bitwise."""
    for queries in (None, xdup[:37] + 0.25):
        want = gaussian_kde_legacy(xdup, queries, bandwidth=0.8, block=64)
        got = gaussian_kde(xdup, queries, bandwidth=0.8, block=32)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)


def test_kde_single_point_and_row():
    one = np.ones((1, 3), np.float32)
    np.testing.assert_allclose(
        gaussian_kde(one), gaussian_kde_legacy(one), rtol=1e-6
    )
    q = np.zeros((1, 3), np.float32)
    np.testing.assert_allclose(
        gaussian_kde(one, q, bandwidth=0.5),
        gaussian_kde_legacy(one, q, bandwidth=0.5),
        rtol=1e-6,
    )


# ------------------------------------------- interpret-mode kernel path


def test_engine_kernel_path_parity_interpret(monkeypatch, xdup):
    """use_kernels=True under REPRO_PALLAS_INTERPRET=1 runs the Pallas
    pairwise_reduce kernels and must agree with the fused jnp scan on all
    three tasks (kNN bit-exact, KDE within summation-tree tolerance)."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    x = xdup[:70, :6]
    idx, _ = pairwise_knn(x, 32, 32, use_kernels=True)
    np.testing.assert_array_equal(idx, nearest_neighbors_legacy(x, block=32))
    got = dbscan(x, eps=1.2, min_samples=3, block=32, use_kernels=True)
    want = dbscan_legacy(x, eps=1.2, min_samples=3, block=32)
    np.testing.assert_array_equal(got, want)
    dens = gaussian_kde(x, bandwidth=0.9, block=32, use_kernels=True)
    np.testing.assert_allclose(
        dens, gaussian_kde_legacy(x, bandwidth=0.9, block=32),
        rtol=2e-5, atol=1e-7,
    )


def test_engine_kernel_flag_safe_without_backend(monkeypatch, xdup):
    """On a plain CPU backend (no interpret env) use_kernels=True must fall
    back to the fused jnp scan — same results, no kernel machinery."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    x = xdup[:50]
    np.testing.assert_array_equal(
        pairwise_knn(x, 32, 32, use_kernels=True)[0],
        pairwise_knn(x, 32, 32)[0],
    )


# ------------------------------------------------------------ cost model


def test_downstream_cost_mem_term_and_legacy_escape():
    from repro.core.cost import (
        DEFAULT_KNN_COEFF,
        DEFAULT_KNN_MEM_COEFF,
        downstream_cost,
    )

    m = 4000
    new = downstream_cost("knn", m)
    old = downstream_cost("knn", m, legacy_cost=True)
    # legacy model is the pure O(m^2 k): zero at k=0, and exactly the
    # k-term of the new model
    assert old(0) == 0.0
    assert new(0) == pytest.approx(DEFAULT_KNN_MEM_COEFF * m * m)
    for k in (1, 5, 50):
        assert new(k) - new(0) == pytest.approx(old(k))
        assert old(k) == pytest.approx(DEFAULT_KNN_COEFF * m * m * k)
    # the mem term is method-independent: it can never flip which k wins
    assert new(5) - new(3) == pytest.approx(old(5) - old(3))


def test_optimizer_choice_invariant_to_mem_term():
    """The k-independent term shifts every method's priced objective by the
    same amount, so re-pricing ONE run's outcomes under either model picks
    the same method (two separate runs would re-measure R with wall-clock
    noise, which is not what this asserts)."""
    from repro.core import DropConfig
    from repro.core.cost import downstream_cost
    from repro.data import sinusoid_mixture
    from repro.pipeline import WorkloadOptimizer

    x = sinusoid_mixture(300, 32, rank=3, seed=7)[0]
    cfg = DropConfig(target_tlb=0.9, seed=0)
    rep = WorkloadOptimizer(methods=("fft", "paa", "dwt"), cfg=cfg).optimize(
        x, "knn"
    )
    sat = [m for m, o in rep.outcomes.items() if o.result.satisfied]
    assert sat
    new_cost = downstream_cost("knn", x.shape[0])
    old_cost = downstream_cost("knn", x.shape[0], legacy_cost=True)
    pick = {
        c: min(
            sat,
            key=lambda m: rep.outcomes[m].reduce_s
            + c(rep.outcomes[m].result.k),
        )
        for c in (new_cost, old_cost)
    }
    assert pick[new_cost] == pick[old_cost]


def test_bucket_tile_rows_quantizes_to_tile():
    from repro.core.bucketing import ShapeBucketCache

    b = ShapeBucketCache()
    assert b.bucket_tile_rows(1, 32) == 32
    assert b.bucket_tile_rows(32, 32) == 32
    assert b.bucket_tile_rows(33, 32) == 64
    assert b.bucket_tile_rows(130, 64) == 192
    # recorded under the existing rows family: {32, 64, 192}, one repeat
    assert b.stats["rows"].requests == 4
    assert b.stats["rows"].hits == 1
    assert b.stats["rows"].sizes == {32, 64, 192}
