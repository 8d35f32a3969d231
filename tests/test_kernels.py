"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle, swept
over shapes (divisible, ragged, degenerate) and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.center_gram.center_gram import center_gram_pallas
from repro.kernels.center_gram.ref import center_gram_ref
from repro.kernels.matmul.matmul import matmul_pallas
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.pairwise_tlb.pairwise_tlb import pairwise_tlb_pallas
from repro.kernels.pairwise_tlb.ref import pairwise_tlb_ref

# interpret-mode kernels run the kernel body in python; keep blocks small so
# the sweep stays fast while still exercising multi-tile grids + padding
MM_BLOCKS = dict(block_m=16, block_n=16, block_k=16)
TLB_BLOCKS = dict(block_p=16, block_k=16)
CG_BLOCKS = dict(block_d=16, block_m=32)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, dtype=jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize(
    "m,k,n",
    [
        (32, 32, 32),     # exact tiles
        (48, 16, 64),     # multi-tile
        (33, 17, 19),     # ragged -> padding path
        (5, 40, 3),       # blocks larger than dims
        (16, 1, 16),      # degenerate contraction
        (1, 16, 1),       # single row/col
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel_matches_ref(m, k, n, dtype):
    a = _rand(jax.random.PRNGKey(0), (m, k), dtype)
    b = _rand(jax.random.PRNGKey(1), (k, n), dtype)
    got = matmul_pallas(a, b, interpret=True, **MM_BLOCKS)
    want = matmul_ref(a, b)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize(
    "p,d,kdim",
    [
        (16, 32, 16),    # exact tiles
        (32, 64, 48),    # multi-tile K (prefix carry across tiles)
        (19, 33, 21),    # ragged
        (4, 8, 1),       # single component
        (1, 16, 16),     # single pair
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_tlb_kernel_matches_ref(p, d, kdim, dtype):
    kx, ky, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    xi = _rand(kx, (p, d), dtype)
    xj = _rand(ky, (p, d), dtype)
    # orthonormal-ish basis so the table is meaningful
    v = jnp.linalg.qr(_rand(kv, (d, d), jnp.float32).astype(jnp.float32))[0][:, :kdim]
    v = v.astype(dtype)
    got = pairwise_tlb_pallas(xi, xj, v, interpret=True, **TLB_BLOCKS)
    want = pairwise_tlb_ref(xi, xj, v)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_pairwise_tlb_kernel_chip_tiles_kmax_over_128():
    """The default (chip) tiles with kmax > 128: the MXU prefix sum carries
    across two K tiles and matches the cumsum oracle."""
    kx, ky, kv = jax.random.split(jax.random.PRNGKey(14), 3)
    xi = jax.random.normal(kx, (40, 192))
    xj = jax.random.normal(ky, (40, 192))
    v = jnp.linalg.qr(jax.random.normal(kv, (192, 192)))[0][:, :160]
    got = pairwise_tlb_pallas(xi, xj, v, interpret=True)
    want = pairwise_tlb_ref(xi, xj, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_pairwise_tlb_kernel_coincident_pair_is_one():
    x = jnp.ones((8, 16), jnp.float32)
    v = jnp.eye(16)[:, :8]
    got = pairwise_tlb_pallas(x, x, v, interpret=True, **TLB_BLOCKS)
    np.testing.assert_allclose(np.asarray(got), 1.0)


def test_pairwise_tlb_kernel_monotone_and_bounded():
    kx, ky, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    xi = jax.random.normal(kx, (24, 48))
    xj = jax.random.normal(ky, (24, 48))
    v = jnp.linalg.qr(jax.random.normal(kv, (48, 48)))[0]
    got = np.asarray(pairwise_tlb_pallas(xi, xj, v, interpret=True, **TLB_BLOCKS))
    assert (np.diff(got, axis=1) >= -1e-5).all()
    assert got.min() >= 0 and got.max() <= 1 + 1e-5
    np.testing.assert_allclose(got[:, -1], 1.0, atol=1e-4)  # full basis: isometry


@pytest.mark.parametrize(
    "m,d",
    [
        (64, 32),    # exact tiles
        (96, 48),    # multi-tile
        (37, 23),    # ragged
        (8, 50),     # d > m
        (200, 5),    # skinny
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_center_gram_kernel_matches_ref(m, d, dtype):
    x = _rand(jax.random.PRNGKey(4), (m, d), dtype)
    got = center_gram_pallas(x, interpret=True, **CG_BLOCKS)
    want = center_gram_ref(x)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=tol, atol=tol * m
    )


def test_center_gram_is_psd_and_symmetric():
    x = jax.random.normal(jax.random.PRNGKey(5), (60, 24))
    g = np.asarray(center_gram_pallas(x, interpret=True, **CG_BLOCKS))
    np.testing.assert_allclose(g, g.T, atol=1e-3)
    ev = np.linalg.eigvalsh(g)
    assert ev.min() > -1e-2


def test_gram_eigvecs_match_svd_right_vectors():
    """Covariance-path PCA (via the fused kernel) agrees with SVD-path PCA."""
    x = jax.random.normal(jax.random.PRNGKey(6), (128, 20))
    g = np.asarray(center_gram_pallas(x, interpret=True, **CG_BLOCKS))
    w, vecs = np.linalg.eigh(g)
    v_gram = vecs[:, ::-1][:, :5]
    c = np.asarray(x) - np.asarray(x).mean(0)
    _, _, vt = np.linalg.svd(c, full_matrices=False)
    v_svd = vt[:5].T
    overlap = np.linalg.norm(v_gram.T @ v_svd) ** 2 / 5
    assert overlap > 0.999


# --------------------------------------------------- pairwise_reduce sweeps

from repro.kernels.pairwise_reduce.pairwise_reduce import (  # noqa: E402
    pairwise_dbscan_pallas,
    pairwise_kde_pallas,
    pairwise_knn_pallas,
)
from repro.kernels.pairwise_reduce.ref import (  # noqa: E402
    pairwise_dbscan_ref,
    pairwise_kde_ref,
    pairwise_knn_ref,
)

PR_BLOCKS = dict(block_q=16, block_k=32)

PR_SHAPES = [
    (32, 32, 8),   # exact tiles
    (48, 80, 16),  # multi-tile carry across dataset tiles
    (33, 61, 7),   # ragged -> padding path on both axes
    (1, 16, 4),    # single query row
    (3, 3, 2),     # blocks larger than dims
]


@pytest.mark.parametrize("mq,mk,d", PR_SHAPES)
def test_pairwise_knn_kernel_matches_ref(mq, mk, d):
    x = _rand(jax.random.PRNGKey(7), (mk, d), jnp.float32)
    xq = x[:mq]  # kNN queries ARE dataset rows (self-exclusion contract)
    gi, gd = pairwise_knn_pallas(xq, x, mk, interpret=True, **PR_BLOCKS)
    ri, rd = pairwise_knn_ref(xq, x, mk)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
    np.testing.assert_allclose(
        np.asarray(gd), np.asarray(rd), rtol=1e-5, atol=1e-5
    )


def test_pairwise_knn_kernel_near_duplicates_tie_break():
    """First-occurrence argmin across tiles: the kernel's strict-< carry
    must match the ref's global argmin on (near-)duplicate rows."""
    x = np.array(_rand(jax.random.PRNGKey(8), (70, 6), jnp.float32))
    x[40] = x[3]          # exact duplicate across tiles
    x[41] = x[3] + 1e-4   # near duplicate
    x = jnp.asarray(x)
    gi, _ = pairwise_knn_pallas(x, x, 70, interpret=True, **PR_BLOCKS)
    ri, _ = pairwise_knn_ref(x, x, 70)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))


@pytest.mark.parametrize("mq,mk,d", PR_SHAPES)
def test_pairwise_dbscan_kernel_matches_ref(mq, mk, d):
    x = _rand(jax.random.PRNGKey(9), (mk, d), jnp.float32)
    xq = x[:mq]
    eps2 = 1.5 ** 2
    gc, gp = pairwise_dbscan_pallas(xq, x, mk, eps2, interpret=True, **PR_BLOCKS)
    rc, rp = pairwise_dbscan_ref(xq, x, mk, eps2)
    np.testing.assert_array_equal(np.asarray(gc), np.asarray(rc))
    # widths differ by padding; the extra words must be all-zero
    gp, rp = np.asarray(gp), np.asarray(rp)
    w = min(gp.shape[1], rp.shape[1])
    np.testing.assert_array_equal(gp[:, :w], rp[:, :w])
    assert not gp[:, w:].any() and not rp[:, w:].any()


@pytest.mark.parametrize("shards", [None, 2])
def test_pairwise_dbscan_kernel_chip_tiles_match_ref(shards):
    """The chip's tile layout (bq=128, bk=DBSCAN_BLOCK_K, word-major packed
    blocks) over several query and dataset tiles, bit-exact against the
    oracle; dense enough that every packed word carries set bits."""
    from repro.kernels.pairwise_reduce.pairwise_reduce import DBSCAN_BLOCK_K

    mq, mk = 200, 600
    x = _rand(jax.random.PRNGKey(15), (mk, 3), jnp.float32)
    eps2 = 1.2 ** 2
    if shards is None:
        gc, gp = pairwise_dbscan_pallas(x[:mq], x, mk, eps2, interpret=True)
    else:
        from repro.analytics.split import merge_dbscan_partials

        xp = _shard_pad(x, shards, DBSCAN_BLOCK_K)
        gc, gp = merge_dbscan_partials(*pairwise_dbscan_split_pallas(
            x[:mq], xp, mk, eps2, shards, interpret=True
        ))
    rc, rp = pairwise_dbscan_ref(x[:mq], x, mk, eps2)
    np.testing.assert_array_equal(np.asarray(gc), np.asarray(rc))
    gp, rp = np.asarray(gp), np.asarray(rp)
    assert (rp[:, : mk // 32] != 0).mean() > 0.5
    w = min(gp.shape[1], rp.shape[1])
    np.testing.assert_array_equal(gp[:, :w], rp[:, :w])
    assert not gp[:, w:].any() and not rp[:, w:].any()


@pytest.mark.parametrize("mq,mk,d", PR_SHAPES)
def test_pairwise_kde_kernel_matches_ref(mq, mk, d):
    x = _rand(jax.random.PRNGKey(10), (mk, d), jnp.float32)
    xq = x[:mq]
    sums, comps = pairwise_kde_pallas(xq, x, mk, 0.5, interpret=True, **PR_BLOCKS)
    got = np.asarray(sums, np.float64) + np.asarray(comps, np.float64)
    want = pairwise_kde_ref(xq, x, mk, 0.5)
    np.testing.assert_allclose(
        got, np.asarray(want), rtol=2e-5, atol=1e-6
    )


# ------------------------------------------------- split-variant sweeps
# The grid-parallel shard decomposition: per-shard partials from one
# pallas_call must merge to exactly the sequential kernel's answer.

from repro.kernels.pairwise_reduce.pairwise_reduce import (  # noqa: E402
    pairwise_dbscan_split_pallas,
    pairwise_kde_split_pallas,
    pairwise_knn_split_pallas,
)


def _shard_pad(x, shards, bk):
    """Tile-aligned shard padding, mirroring analytics.split._split_prepare."""
    mk = x.shape[0]
    nk = -(-mk // bk)
    tps = -(-nk // shards)
    rows = shards * tps * bk
    return jnp.pad(x, ((0, rows - mk), (0, 0)))


@pytest.mark.parametrize("shards", [1, 2, 3])
def test_pairwise_knn_split_kernel_merges_to_sequential(shards):
    from repro.analytics.split import merge_knn_partials

    x = np.array(_rand(jax.random.PRNGKey(11), (70, 6), jnp.float32))
    x[40] = x[3]  # cross-shard duplicate: tie must keep the earlier shard
    x = jnp.asarray(x)
    xp = _shard_pad(x, shards, PR_BLOCKS["block_k"])
    gi, gd = pairwise_knn_split_pallas(
        x, xp, 70, shards, interpret=True, **PR_BLOCKS
    )
    idx, d2 = merge_knn_partials(np.asarray(gi), np.asarray(gd))
    ri, rd = pairwise_knn_ref(x, x, 70)
    np.testing.assert_array_equal(idx, np.asarray(ri))
    np.testing.assert_allclose(d2, np.asarray(rd), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shards", [1, 2, 3])
def test_pairwise_dbscan_split_kernel_merges_to_sequential(shards):
    from repro.analytics.split import merge_dbscan_partials

    x = _rand(jax.random.PRNGKey(12), (61, 7), jnp.float32)
    xp = _shard_pad(x, shards, PR_BLOCKS["block_k"])
    gc, gp = pairwise_dbscan_split_pallas(
        x, xp, 61, 1.5 ** 2, shards, interpret=True, **PR_BLOCKS
    )
    counts, packed = merge_dbscan_partials(np.asarray(gc), np.asarray(gp))
    rc, rp = pairwise_dbscan_ref(x, x, 61, 1.5 ** 2)
    np.testing.assert_array_equal(counts, np.asarray(rc))
    rp = np.asarray(rp)
    w = min(packed.shape[1], rp.shape[1])
    np.testing.assert_array_equal(packed[:, :w], rp[:, :w])
    assert not packed[:, w:].any() and not rp[:, w:].any()


@pytest.mark.parametrize("shards", [1, 2, 3])
def test_pairwise_kde_split_kernel_merges_to_sequential(shards):
    from repro.analytics.split import merge_kde_partials

    x = _rand(jax.random.PRNGKey(13), (80, 5), jnp.float32)
    xp = _shard_pad(x, shards, PR_BLOCKS["block_k"])
    gs, gc = pairwise_kde_split_pallas(
        x, xp, 80, 0.5, shards, interpret=True, **PR_BLOCKS
    )
    dens = merge_kde_partials(np.asarray(gs), np.asarray(gc), 80)
    want = np.asarray(pairwise_kde_ref(x, x, 80, 0.5)) / 80.0
    np.testing.assert_allclose(dens, want, rtol=2e-5, atol=1e-6)
