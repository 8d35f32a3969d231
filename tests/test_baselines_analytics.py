"""Baseline operators (PAA/FFT/JL) + downstream analytics (kNN/DBSCAN/KDE)."""

import numpy as np
import pytest

from repro.analytics import dbscan, gaussian_kde, knn_retrieval_accuracy, nearest_neighbors
from repro.baselines import fft_min_k, fft_transform, jl_transform, paa_min_k, paa_transform
from repro.baselines.fft import fft_real_expansion
from repro.baselines.jl import jl_dimension_bound
from repro.baselines.svd_pca import pca_min_k
from repro.data import ecg_like, sinusoid_mixture


@pytest.fixture(scope="module")
def ecg():
    return ecg_like(800, 128, seed=0)


def _pair_dists(x, n=300, seed=0):
    rng = np.random.default_rng(seed)
    i = rng.integers(0, x.shape[0], n)
    j = rng.integers(0, x.shape[0], n)
    return i, j, np.linalg.norm(x[i] - x[j], axis=1)


def test_paa_contractive(ecg):
    x, _ = ecg
    t = paa_transform(x, 16)
    i, j, d_hi = _pair_dists(x)
    d_lo = np.linalg.norm(t[i] - t[j], axis=1)
    assert np.all(d_lo <= d_hi + 1e-3)


def test_paa_full_k_close_to_identity_distances(ecg):
    x, _ = ecg
    t = paa_transform(x, x.shape[1])  # one point per segment: exact
    i, j, d_hi = _pair_dists(x)
    d_lo = np.linalg.norm(t[i] - t[j], axis=1)
    np.testing.assert_allclose(d_lo, d_hi, rtol=1e-4)


def test_fft_expansion_is_isometry(ecg):
    x, _ = ecg
    e = fft_real_expansion(x)
    np.testing.assert_allclose(
        np.linalg.norm(e, axis=1), np.linalg.norm(x, axis=1), rtol=1e-4
    )


def test_fft_contractive(ecg):
    x, _ = ecg
    t = fft_transform(x, 9)
    i, j, d_hi = _pair_dists(x)
    d_lo = np.linalg.norm(t[i] - t[j], axis=1)
    assert np.all(d_lo <= d_hi + 1e-3)


def test_pca_needs_fewer_dims_than_fft_and_paa(ecg):
    """The paper's headline measurement-study result (Table 6 / Fig 1)."""
    x, _ = ecg
    k_pca = pca_min_k(x, 0.90)
    k_fft = fft_min_k(x, 0.90)
    k_paa = paa_min_k(x, 0.90)
    assert k_pca <= k_fft
    assert k_pca <= k_paa


def test_jl_shape_and_bound():
    x = np.random.default_rng(0).normal(size=(100, 64)).astype(np.float32)
    t = jl_transform(x, 8, seed=1)
    assert t.shape == (100, 8)
    # §1: JL needs ~137 dims for 5000 points at 25% distortion
    assert 120 <= jl_dimension_bound(5000, 0.25) <= 160


def test_knn_nearest_neighbor_correct_small():
    x = np.array([[0.0, 0], [0.1, 0], [5, 5], [5.1, 5]], dtype=np.float32)
    nn = nearest_neighbors(x, block=4)
    assert nn.tolist() == [1, 0, 3, 2]


def test_knn_accuracy_on_separable_classes():
    x, y = sinusoid_mixture(400, 64, rank=4, n_classes=2, noise=0.01, seed=5)
    assert knn_retrieval_accuracy(x, y) > 0.8


def test_dbscan_finds_two_blobs():
    rng = np.random.default_rng(0)
    a = rng.normal(0, 0.1, size=(50, 2))
    b = rng.normal(5, 0.1, size=(50, 2))
    labels = dbscan(np.concatenate([a, b]).astype(np.float32), eps=0.5, min_samples=4)
    assert len(set(labels[:50])) == 1 and len(set(labels[50:])) == 1
    assert labels[0] != labels[50]


def test_kde_higher_density_near_cluster():
    rng = np.random.default_rng(0)
    x = rng.normal(0, 0.5, size=(200, 4)).astype(np.float32)
    q = np.array([[0, 0, 0, 0], [10, 10, 10, 10]], dtype=np.float32)
    dens = gaussian_kde(x, q, bandwidth=1.0)
    assert dens[0] > dens[1] * 100
