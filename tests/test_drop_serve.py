"""DropService behavior: parity with sequential drop(), the basis-reuse
cache's no-refit hit path, LRU bounds, and scheduler bookkeeping."""

import numpy as np
import pytest

from repro.core import DropConfig, DropRunner, drop
from repro.core import basis_search
from repro.core.cost import zero_cost
from repro.serve_drop import BasisReuseCache, DropService, dataset_fingerprint
from repro.serve_drop.cache import BasisCacheEntry
from repro.data import sinusoid_mixture


def _datasets(n, rows=500, dim=48):
    return [sinusoid_mixture(rows, dim, rank=4 + i, seed=10 + i)[0] for i in range(n)]


CFG = DropConfig(target_tlb=0.95, seed=0)

# Eq. 2 termination consults measured wall-clock runtimes, so iteration
# counts can differ between two runs of the same query when compile noise
# lands differently. Bit-exact parity tests pin min_iterations past the
# schedule length: every run walks the full schedule, timing-independent.
PARITY_CFG = DropConfig(target_tlb=0.95, seed=0, min_iterations=99)


# ------------------------------------------------------------------ parity


def test_concurrent_queries_match_sequential_drop():
    """N distinct in-flight queries, interleaved by the scheduler, must
    produce bit-identical results to sequential drop() on the same seeds."""
    datasets = _datasets(3, rows=300, dim=32)
    svc = DropService(max_inflight=3, enable_cache=False)
    for x in datasets:
        svc.submit(x, PARITY_CFG, zero_cost())
    served = svc.run()

    assert len(served) == len(datasets)
    for x, r in zip(datasets, served):
        ref = drop(x, PARITY_CFG, cost=zero_cost())
        assert r.result.k == ref.k
        assert r.result.satisfied == ref.satisfied
        np.testing.assert_array_equal(r.result.v, ref.v)
        np.testing.assert_array_equal(r.result.mean, ref.mean)
        assert len(r.result.iterations) == len(ref.iterations)


def test_runner_steps_equal_monolithic_drop():
    """The resumable DropRunner is the same algorithm as drop()."""
    (x,) = _datasets(1, rows=300, dim=32)
    runner = DropRunner(x, PARITY_CFG, zero_cost())
    steps = 0
    while runner.step():
        steps += 1
    res = runner.result()
    ref = drop(x, PARITY_CFG, cost=zero_cost())
    assert steps + 1 == len(ref.iterations)
    assert res.k == ref.k
    np.testing.assert_array_equal(res.v, ref.v)


# ------------------------------------------------------------- cache hits


def test_resubmitted_workload_skips_fit_basis(monkeypatch):
    """A repeat submission must be served from the basis cache with zero
    fit_basis calls — the §5 reuse path."""
    (x,) = _datasets(1)
    svc = DropService()
    svc.submit(x, CFG, zero_cost())
    first = svc.run()[0]
    assert not first.cache_hit and first.result.satisfied

    calls = []
    real_fit = basis_search.fit_basis
    monkeypatch.setattr(
        basis_search, "fit_basis", lambda *a, **k: calls.append(1) or real_fit(*a, **k)
    )
    svc.submit(x, CFG, zero_cost())
    second = svc.run()[0]
    assert second.cache_hit
    assert calls == []  # no fitting anywhere on the hit path
    assert second.result.satisfied
    assert second.result.k == first.result.k
    assert second.result.tlb_estimate >= CFG.target_tlb


def test_cache_hit_result_is_valid_basis():
    """The cached basis served on a hit must actually preserve distances on
    the re-submitted data (contractive + near-target sampled TLB)."""
    (x,) = _datasets(1)
    svc = DropService()
    svc.submit(x, CFG, zero_cost())
    svc.run()
    svc.submit(x, CFG, zero_cost())
    r = svc.run()[0].result
    xt = (x - r.mean) @ r.v
    rng = np.random.default_rng(0)
    i, j = rng.integers(0, x.shape[0], 100), rng.integers(0, x.shape[0], 100)
    d_hi = np.linalg.norm(x[i] - x[j], axis=1)
    d_lo = np.linalg.norm(xt[i] - xt[j], axis=1)
    assert np.all(d_lo <= d_hi + 1e-3)


def test_raising_validation_is_counted_not_hidden(monkeypatch):
    """A revalidation that raises still falls back to a cold refit (the
    query is served), but the error lands in ``stats.validation_errors``
    instead of passing as an ordinary miss."""
    (x,) = _datasets(1)
    svc = DropService()
    svc.submit(x, CFG, zero_cost())
    svc.run()
    assert svc.stats.validation_errors == 0

    def boom(val):
        raise RuntimeError("validation path broke")

    monkeypatch.setattr(svc, "_validate", boom)
    fits = svc.stats.fit_calls
    svc.submit(x, CFG, zero_cost())
    r = svc.run()[0]
    assert r.error is None and not r.cache_hit and r.result.satisfied
    assert svc.stats.validation_errors == 1
    assert svc.stats.fit_calls > fits  # served by the cold refit
    assert svc.stats.failures == 0


def test_launcher_exits_nonzero_when_a_query_fails(monkeypatch, capsys):
    """launch/drop_serve.py prints every result, then carries a failed
    query (anything but an expired deadline) in its exit status."""
    import sys

    from repro.launch import drop_serve
    from repro.pipeline import optimizer

    def boom(*a, **k):
        raise RuntimeError("planted")

    monkeypatch.setattr(optimizer, "run_downstream", boom)
    # the persistent compile cache is process-wide; keep it off here
    monkeypatch.setattr(drop_serve, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", [
        "drop_serve", "--queries", "2", "--rows", "200",
        "--execute-downstream",
    ])
    with pytest.raises(SystemExit) as exc:
        drop_serve.main()
    assert "2 queries failed" in str(exc.value.code)
    assert "RuntimeError: planted" in str(exc.value.code)
    assert capsys.readouterr().out.count("[ERR ]") == 2


def test_concurrent_repeats_deduplicated():
    """Repeats submitted concurrently with their first instance must not all
    run cold: the scheduler defers them onto the cache."""
    (x,) = _datasets(1)
    svc = DropService(max_inflight=4)
    for _ in range(4):
        svc.submit(x, CFG, zero_cost())
    served = svc.run()
    assert sum(r.cache_hit for r in served) == 3
    assert svc.stats.cache_misses == 1


def test_tighter_target_does_not_reuse_looser_basis():
    """A cached basis fitted at 0.90 must not short-circuit a 0.99 query
    (its k is no upper bound for the tighter target)."""
    (x,) = _datasets(1)
    svc = DropService()
    svc.submit(x, DropConfig(target_tlb=0.90, seed=0), zero_cost())
    loose = svc.run()[0]
    svc.submit(x, DropConfig(target_tlb=0.99, seed=0), zero_cost())
    tight = svc.run()[0]
    assert not tight.cache_hit
    assert tight.result.k >= loose.result.k
    # and the looser direction DOES reuse: a 0.90 query after a 0.99 fit
    svc.submit(x, DropConfig(target_tlb=0.90, seed=0), zero_cost())
    assert svc.run()[0].cache_hit


def test_stale_cache_entry_does_not_cap_fallback_run():
    """Fingerprint collision on drifted data: the cached basis fails
    revalidation, and the fallback cold run must not stay capped at the
    stale (too small) k — it has to find a satisfying basis on its own."""
    x, _ = sinusoid_mixture(200, 48, rank=3, seed=0)
    x = x.astype(np.float32)
    svc = DropService()
    cfg = DropConfig(target_tlb=0.9, seed=0)
    svc.submit(x, cfg, zero_cost())
    first = svc.run()[0]
    assert first.result.satisfied and first.result.k <= 8

    # drift every row the fingerprint does NOT hash (stride = m // 64 = 3:
    # rows 0,3,6,... and the last row are sampled) into white noise: same
    # fingerprint, but the old low-rank basis no longer preserves distances
    drifted = x.copy()
    rng = np.random.default_rng(1)
    for i in range(drifted.shape[0] - 1):
        if i % 3 != 0:
            drifted[i] = rng.normal(size=drifted.shape[1]).astype(np.float32)
    from repro.serve_drop import dataset_fingerprint as fp

    assert fp(drifted) == fp(x)  # collision is the premise of this test

    svc.submit(drifted, cfg, zero_cost())
    r = svc.run()[0]
    assert not r.cache_hit  # revalidation must reject the stale basis
    assert r.result.satisfied  # and the fallback must not stay rank-capped
    assert r.result.k > first.result.k  # noise needs far more dimensions


# -------------------------------------------------------------------- LRU


def test_lru_eviction_bound_respected():
    datasets = _datasets(5, rows=200, dim=24)
    svc = DropService(cache_entries=2)
    for x in datasets:
        svc.submit(x, CFG, zero_cost())
    svc.run()
    assert len(svc.cache) <= 2
    assert svc.cache.evictions >= 3


def test_lru_evicts_least_recently_used():
    cache = BasisReuseCache(capacity=2)
    entry = lambda k: BasisCacheEntry(  # noqa: E731
        v=np.eye(4)[:, :k], mean=np.zeros(4), k=k,
        target_tlb=0.9, tlb_estimate=0.99, satisfied=True,
    )
    cache.put("a", entry(1))
    cache.put("b", entry(2))
    assert cache.get_exact("a", 0.9) is not None  # refresh a
    cache.put("c", entry(3))  # evicts b, not a
    assert cache.get_exact("b", 0.9) is None
    assert cache.get_exact("a", 0.9) is not None
    assert cache.get_exact("c", 0.9) is not None
    assert len(cache) == 2


def test_fingerprint_sensitivity():
    x = np.random.default_rng(0).normal(size=(100, 8)).astype(np.float32)
    assert dataset_fingerprint(x) == dataset_fingerprint(x.copy())
    y = x.copy()
    y[-1, -1] += 1.0
    assert dataset_fingerprint(x) != dataset_fingerprint(y)
    assert dataset_fingerprint(x) != dataset_fingerprint(x[:99])


def test_fingerprint_append_and_distinct_data():
    """Appending rows always changes the fingerprint (shape is hashed and
    the stride re-lands); independently drawn data never collides."""
    x = np.random.default_rng(1).normal(size=(200, 8)).astype(np.float32)
    grown = np.concatenate([x, x[:1]], axis=0)
    assert dataset_fingerprint(grown) != dataset_fingerprint(x)
    y = np.random.default_rng(2).normal(size=(200, 8)).astype(np.float32)
    assert dataset_fingerprint(x) != dataset_fingerprint(y)


def test_fingerprint_unsampled_permutation_aliases():
    """Documented aliasing: permuting rows the strided subsample never reads
    keeps the fingerprint (this is the premise of the TTL staleness bound),
    while permuting a sampled row changes it."""
    m = 300  # stride = m // 64 = 4: rows 0, 4, 8, ... and the last are hashed
    x = np.random.default_rng(3).normal(size=(m, 8)).astype(np.float32)
    stride = max(1, m // 64)
    aliased = x.copy()
    aliased[[1, 2]] = aliased[[2, 1]]  # neither row is sampled
    assert dataset_fingerprint(aliased) == dataset_fingerprint(x)
    visible = x.copy()
    visible[[0, 1]] = visible[[1, 0]]  # row 0 is sampled
    assert dataset_fingerprint(visible) != dataset_fingerprint(x)
    assert stride > 2  # the construction above assumes rows 1,2 unsampled


# ---------------------------------------------------------------- TTL


def test_ttl_entries_expire_and_refresh():
    entry = lambda k: BasisCacheEntry(  # noqa: E731
        v=np.eye(4)[:, :k], mean=np.zeros(4), k=k,
        target_tlb=0.9, tlb_estimate=0.99, satisfied=True,
    )
    cache = BasisReuseCache(capacity=4, ttl_ticks=2)
    cache.put("a", entry(2))
    cache.tick()
    assert cache.get_exact("a", 0.9) is not None  # age 1 <= ttl
    cache.tick()
    assert cache.get_exact("a", 0.9) is not None  # age 2 == ttl: still fresh
    cache.tick()
    assert cache.get_exact("a", 0.9) is None  # age 3 > ttl: expired
    assert cache.expired_hits == 1
    assert cache.get_warm_k("a", 0.9) == 2  # warm starts survive expiry
    cache.put("a", entry(2))  # refit re-inserts: age restarts
    assert cache.get_exact("a", 0.9) is not None

    forever = BasisReuseCache(capacity=4, ttl_ticks=None)
    forever.put("a", entry(1))
    for _ in range(100):
        forever.tick()
    assert forever.get_exact("a", 0.9) is not None  # default: never expires


def test_ttl_expired_entry_with_degraded_basis_self_heals(monkeypatch):
    """The staleness hole + its TTL fix. The exact-hit revalidation samples
    pairs with a seed pinned by the query config, so drift the sampled pairs
    never see can keep serving a degraded basis forever. Simulate exactly
    that blind spot by degrading the cached entry (rank-1 truncation) while
    forcing the validation estimate to pass:

    * without a TTL the degraded entry is served as a cache hit forever;
    * with a TTL the aged entry is refused, the query refits cold, and the
      re-inserted entry (fresh basis AND fresh age) serves future hits.
    """
    from repro.core.tlb import TLBEstimate
    from repro.serve_drop import service as service_mod

    (x,) = _datasets(1)

    def poison(svc):
        ((key, entry),) = [(k, svc.cache._entries[k]) for k in svc.cache.keys()]
        entry.v = entry.v[:, :1]
        entry.k = 1
        return entry

    class _BlindEstimator:
        """Stands in for drift the seed-pinned validation pairs miss."""

        def __init__(self, *a, **k):
            pass

        def estimate_at_k(self, k, target, **kw):
            return TLBEstimate(mean=0.999, lo=0.99, hi=1.0, pairs_used=10)

    # -- the hole: no TTL, blind validation => stale k=1 served forever
    svc = DropService()
    svc.submit(x, CFG, zero_cost())
    k_good = svc.run()[0].result.k
    assert k_good > 1
    poison(svc)
    with monkeypatch.context() as m:
        m.setattr(service_mod, "TLBEstimator", _BlindEstimator)
        svc.submit(x, CFG, zero_cost())
        stale = svc.run()[0]
    assert stale.cache_hit and stale.result.k == 1  # degraded basis served

    # -- the fix: TTL expires the entry, forcing an honest refit
    svc = DropService(cache_ttl=3)
    svc.submit(x, CFG, zero_cost())
    assert svc.run()[0].result.k == k_good
    poison(svc)
    for _ in range(4):  # age the entry past the TTL
        svc.cache.tick()
    with monkeypatch.context() as m:
        m.setattr(service_mod, "TLBEstimator", _BlindEstimator)
        svc.submit(x, CFG, zero_cost())
        healed = svc.run()[0]
    # even with validation still blind, the expired entry cannot be served:
    # the cold refit recovers a real basis and re-inserts it (the refit's
    # exact k may differ from the first run's — the stale k=1 warm hint
    # perturbs the importance-sampling trajectory — but it must be a
    # satisfying, non-degenerate fit)
    assert not healed.cache_hit
    assert healed.result.satisfied and healed.result.k > 1
    svc.submit(x, CFG, zero_cost())
    again = svc.run()[0]  # fresh entry now serves hits again (self-healed)
    assert again.cache_hit and again.result.k == healed.result.k


# ------------------------------------------------- bucketing mirrors
# deterministic counterparts of the Hypothesis properties in
# test_properties_serve.py (those skip when hypothesis is absent)


def test_bucket_quantization_idempotent():
    from repro.core.bucketing import ShapeBucketCache, round_up

    bucket = ShapeBucketCache()
    for n in (1, 31, 32, 33, 100, 127, 128, 1000):
        assert round_up(round_up(n, 32), 32) == round_up(n, 32)
        assert bucket.bucket_pairs(bucket.bucket_pairs(n)) == bucket.bucket_pairs(n)
        assert bucket.bucket_rows(bucket.bucket_rows(n)) == bucket.bucket_rows(n)
        for hard in (n, n + 5, 2 * n):
            b = bucket.bucket_rank(n, hard)
            assert bucket.bucket_rank(b, hard) == b


def test_pair_bucketing_bit_matches_unbucketed():
    """Zero-padded pair batches, sliced back, must be bit-identical to the
    unpadded evaluation (padding never reaches the estimate)."""
    import jax.numpy as jnp

    from repro.core.bucketing import ShapeBucketCache
    from repro.core.tlb import TLBEstimator

    x = np.random.default_rng(5).normal(size=(80, 12)).astype(np.float32)
    v = np.linalg.svd(x - x.mean(0), full_matrices=False)[2].T[:, :6]
    identity = ShapeBucketCache(rank_quantum=1, pair_quantum=1, row_quantum=1)
    e1 = TLBEstimator(x, jnp.asarray(v), np.random.default_rng(7),
                      bucket=ShapeBucketCache(pair_quantum=128))
    e2 = TLBEstimator(x, jnp.asarray(v), np.random.default_rng(7),
                      bucket=identity)
    np.testing.assert_array_equal(e1.table(37), e2.table(37))


# -------------------------------------------------------------- bookkeeping


def test_stats_and_result_ordering():
    datasets = _datasets(2, rows=300, dim=24)
    svc = DropService(max_inflight=2)
    ids = [svc.submit(x, CFG, zero_cost()) for x in datasets + datasets]
    served = svc.run()
    assert [r.query_id for r in served] == sorted(ids)
    assert svc.stats.queries == 4
    assert svc.stats.cache_hits == 2
    assert svc.stats.fit_calls == svc.stats.iterations
    assert svc.stats.fit_calls > 0


# ------------------------------------------------------- use_kernels plumb


def test_served_query_use_kernels_interpret_parity(monkeypatch):
    """ReduceQuery carries cfg.use_kernels end-to-end: a served query with
    the kernel path forced through the Pallas interpreter must reach the
    same rank and a satisfying TLB as the plain served run (bit-exact k —
    the kernels compute the same tables; interpret mode only swaps the
    executor). Covers the launch/drop_serve.py --use-kernels plumbing."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    x = _datasets(1, rows=160, dim=16)[0]
    plain = DropService(enable_cache=False)
    plain.submit(x, PARITY_CFG, zero_cost())
    r_plain = plain.run()[0]

    kcfg = DropConfig(
        target_tlb=0.95, seed=0, min_iterations=99, use_kernels=True
    )
    svc = DropService(enable_cache=False)
    svc.submit(x, kcfg, zero_cost())
    r_kern = svc.run()[0]
    assert r_kern.error is None
    assert r_kern.result.satisfied
    assert r_kern.result.k == r_plain.result.k
    np.testing.assert_allclose(
        r_kern.result.tlb_estimate, r_plain.result.tlb_estimate, atol=5e-4
    )


# ------------------------------------------------- served downstream exec


def test_execute_downstream_attaches_parity_output():
    """execute_downstream=True runs the declared analytics task on the
    reduced data as a scheduled work item and attaches its output to the
    ServeResult — identical to calling run_downstream on the transform
    (the split decomposition is exact, so analytics_split changes
    nothing)."""
    from repro.pipeline.optimizer import run_downstream

    x = _datasets(1, rows=260, dim=24)[0]
    svc = DropService(enable_cache=False, analytics_split=2)
    svc.submit(x, CFG, zero_cost(), downstream="knn",
               execute_downstream=True)
    r = svc.run()[0]
    assert r.error is None
    assert r.downstream is not None
    assert r.downstream_s > 0.0
    assert svc.stats.downstream_runs == 1
    xt = r.result.transform(x)
    assert np.array_equal(r.downstream, run_downstream("knn", xt))


def test_execute_downstream_on_cache_hit():
    """A cache-hit query still gets its analytics leg: the basis is
    reused, the downstream task runs on the reused transform."""
    x = _datasets(1, rows=260, dim=24)[0]
    svc = DropService()
    svc.submit(x, CFG, zero_cost(), downstream="kde",
               execute_downstream=True)
    svc.submit(x, CFG, zero_cost(), downstream="kde",
               execute_downstream=True)
    r1, r2 = svc.run()
    assert r2.cache_hit and not r1.cache_hit
    assert r1.downstream is not None and r2.downstream is not None
    assert svc.stats.downstream_runs == 2
    np.testing.assert_allclose(r1.downstream, r2.downstream, rtol=1e-5)


def test_execute_downstream_error_contained(monkeypatch):
    """A downstream failure must not lose the reduction: the result (and
    its basis) commit with the error recorded, and the scheduler keeps
    draining."""
    import repro.pipeline.optimizer as opt_mod

    def boom(*a, **k):
        raise RuntimeError("analytics exploded")

    monkeypatch.setattr(opt_mod, "run_downstream", boom)
    x = _datasets(1, rows=260, dim=24)[0]
    svc = DropService(enable_cache=False)
    svc.submit(x, CFG, zero_cost(), downstream="knn",
               execute_downstream=True)
    r = svc.run()[0]
    assert r.error is not None and "downstream" in r.error
    assert r.result is not None  # the reduction itself survived
    assert r.downstream is None
    assert svc.stats.downstream_failures == 1
    assert svc.stats.downstream_runs == 0


def test_execute_downstream_requires_task():
    svc = DropService()
    x = _datasets(1, rows=120, dim=12)[0]
    with pytest.raises(ValueError, match="downstream"):
        svc.submit(x, CFG, zero_cost(), execute_downstream=True)
