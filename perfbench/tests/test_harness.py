"""The harness's own pieces, on the CPU: files found by name, traffic that
gives every seed the same work, the float64 references, the metric readers,
and the refusal to run off a chip."""

import os
import subprocess
import sys

import numpy as np
import pytest

from bench import costs, data, reference, spec, traffic

ROOT = spec.ROOT


def test_every_cell_finds_its_files():
    bm = spec.benchmark()
    assert set(bm) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    layers = spec.layer_maps()
    for w in bm["workloads"]:
        cell = spec.cell(w["name"])
        assert cell["config"]["name"] == w["config"]
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
        for m in cell["per_layer"]:
            assert (spec.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    assert layers and all(isinstance(v, list) and v for v in layers.values())


def test_peaks_refuse_an_unknown_kind():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_every_seed_gets_the_same_work():
    t = {"rate_per_s": 7.0, "zipf_a": 1.2, "block_requests": 10}
    a = traffic.arrivals(t, 20.0, np.random.default_rng(1))
    b = traffic.arrivals(t, 20.0, np.random.default_rng(2**40))
    assert len(a) == len(b) == 140
    ga, gb = np.diff(a, prepend=0.0), np.diff(b, prepend=0.0)
    assert np.allclose(np.sort(ga), np.sort(gb)) and not np.allclose(ga, gb)
    assert 19.0 < a[-1] <= 20.0
    ta = traffic.tenant_sequence(140, 24, t, np.random.default_rng(1))
    tb = traffic.tenant_sequence(140, 24, t, np.random.default_rng(2))
    assert np.array_equal(np.bincount(ta, minlength=24), np.bincount(tb, minlength=24))
    assert not np.array_equal(ta, tb)


@pytest.mark.parametrize("n,block", [(140, 10), (143, 10), (7, 10), (50, 1)])
def test_every_block_holds_one_item_of_each_stratum(n, block):
    items = np.random.default_rng(5).permutation(n).astype(float)  # distinct
    out = traffic.stratified(items, block, np.random.default_rng(2**35 + 3))
    assert np.array_equal(np.sort(out), np.sort(items))
    body = n - n % block
    kept = np.sort(out[:body])
    stratum = np.searchsorted(kept, out[:body]) // max(body // block, 1)
    for blk in stratum.reshape(-1, block):
        assert sorted(blk.tolist()) == list(range(block))


def test_data_is_made_from_the_seed():
    spec_ = {"id": 0, "m": 50, "d": 16, "rank": 3}
    src1, x1 = data.make_collection(spec_, 2**33 + 1, 0)
    assert np.array_equal(x1, data.make_collection(spec_, 2**33 + 1, 0)[1])
    assert not np.array_equal(x1, data.make_collection(spec_, 2**33 + 1, 1)[1])
    # another seed: fresh rows from the same population
    src2, x2 = data.make_collection(spec_, 7, 0)
    assert not np.array_equal(x1, x2)
    assert np.array_equal(src2.basis, src1.basis)
    assert np.array_equal(src2.class_means, src1.class_means)
    assert x1.dtype == np.float32 and np.allclose(x1.mean(1), 0, atol=1e-5)
    # rank + 1 class means whose covariance over the classes is isotropic
    cm = src1.class_means.astype(np.float64)
    assert cm.shape == (4, 3) and np.allclose(cm.sum(0), 0, atol=1e-5)
    assert np.allclose(cm.T @ cm / len(cm), np.eye(3), atol=1e-5)


UCR = spec.load_json(spec.BENCH_DIR / "configs" / "ucr-serve.json")
HOT = spec.load_json(spec.BENCH_DIR / "traffic" / "hot.json")


@pytest.mark.parametrize("seed", [11, 2718281828])
@pytest.mark.parametrize("col", UCR["collections"], ids=lambda c: c["name"])
def test_every_tenant_is_served_at_its_rank(col, seed):
    """Exact PCA of each tenant at its real m x d, on 4000 fixed pairs:
    one component short of the rank the map misses the target by a wide
    margin, and at the rank it clears it, so DROP serves k = rank and its
    revalidation clears the target on the first round of pairs."""
    target = UCR["drop"]["target_tlb"]
    for index in range(HOT["seeds_per_collection"]):
        x = data.make_collection(col, seed, index)[1]
        x64 = x.astype(np.float64)
        v = np.linalg.svd(x64 - x64.mean(0), full_matrices=False)[2].T
        pairs = reference.sample_pairs(len(x), HOT["check_pairs"], np.random.default_rng(0))
        short = reference.tlb(x, v[:, :col["rank"] - 1], pairs)[0]
        full = reference.tlb(x, v[:, :col["rank"]], pairs)[0]
        assert short <= target - 0.015 and full >= target + 0.01, (index, short, full)


def test_references_against_brute_force():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(300, 8))
    v = np.linalg.qr(rng.normal(size=(8, 8)))[0]
    pairs = reference.sample_pairs(300, 500, rng)
    mean, se = reference.tlb(x, v, pairs)
    assert abs(mean - 1.0) < 1e-12 and se < 1e-12  # an orthonormal basis keeps every distance
    z = reference.reduce_rows(x, v[:, :3])
    d2 = ((z[:, None] - z[None]) ** 2).sum(-1)
    np.fill_diagonal(d2, np.inf)
    rows = np.arange(300)
    assert reference.knn_excess(z, d2.argmin(1), rows) < 1e-12
    wrong = np.roll(d2.argmin(1), 1)
    assert reference.knn_excess(z, wrong, rows) > 1e-3


def test_readers_on_a_known_context():
    from run import load_reader

    peaks = spec.peaks("TPU v5 lite")
    reqs = [{"hit": True, "m": 16637, "k": 12}, {"hit": False, "m": 1370, "k": 20}]
    least = sum(costs.least_time_s(*costs.knn_scan(r["m"], r["k"]), peaks) for r in reqs)
    ctx = {"busy_s": 1.0, "window_s": 4.0, "requests": reqs, "peaks": peaks,
           "stats": {"queries": 2, "cache_misses": 1, "iterations": 5},
           "layer_ms": {"tlb": 2.0, "knn": least * 2e3}}
    assert load_reader("idle_share.serve")(ctx) == 75.0
    assert load_reader("miss_share")(ctx) == 50.0
    assert load_reader("tlb_device_ms")(ctx) == 1.0
    assert load_reader("knn_device_ms")(ctx) == least * 1e3
    assert abs(load_reader("knn_roofline")(ctx) - 50.0) < 1e-9
    ctx["layer_ms"] = {}
    assert load_reader("knn_roofline")(ctx) is None  # nothing to read: no value


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "perfbench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_no_result_off_the_chip():
    name = spec.benchmark()["workloads"][0]["name"]
    p = _run(["--workload", name, "--seed", "1", "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_no_result_without_the_program(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    name = spec.benchmark()["workloads"][0]["name"]
    p = _run(["--workload", name, "--seed", "1", "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
