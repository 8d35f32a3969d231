#!/usr/bin/env python3
"""Smoke test of DROP serving on a TPU: the quickest proof that the system
starts on the chip and serves correct answers there.

    python3 chip_smoke.py             # one chip: phases (a)-(d)
    python3 chip_smoke.py --chips 4   # only the four-device mesh path

One process drives the normal entry points: a ``DropService`` behind an
``IngestFrontend``, as ``repro.launch.drop_serve`` builds it, on datasets
generated from ``--seed`` at the paper's UCR-like shapes
(``repro.data.timeseries.UCR_LIKE_SPECS``).

* (a) cold serve: one kNN query per tenant with executed analytics, plus one
  DBSCAN and one KDE execution on SynElectricDevices;
* (b) repeat serve: every tenant again; each must be a revalidated cache hit;
* (c) one delta subscription at the ``bench_delta_stream`` shape: bootstrap
  plus three 5% appends, checked against a cold recompute on the chip;
* (d) phases (a)-(b) again with ``DropConfig(use_kernels=True)``: the native
  Pallas kernels serve, and the answers must agree with (a).

Every served answer is also checked against an independent host reference
in float64 (TLB on a fixed pair sample, brute-force kNN, KDE on a row
subset), within the tolerances defined below. Wall times include
compilation and are host-clock times, not device metrics.

``--chips 4`` runs only ``DropService(devices=4)`` (the sharded scheduler,
with the mesh analytics fan-out) against a single-device ``DropService`` in
the same process, query by query, and checks that every device did work.

The last line of standard output is one JSON object, printed only when every
check passed: ``{"ok": true, "device": {"platform", "kind", "count"}}``. The
script exits non-zero, printing no such line, when JAX finds no TPU, when
``REPRO_PALLAS_INTERPRET`` is set, outside a checkout of the repository, or
when any check fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

TENANTS = ("SynStarLightCurves", "SynElectricDevices", "SynHandOutlines")
ANALYTICS_TENANT = "SynElectricDevices"
# a fourth tenant gives each of four devices a cold fit of its own
MESH_TENANTS = TENANTS + ("SynFordA",)
TARGET_TLB = 0.98  # the paper's default B

# host reference: served basis vs target on TLB_REF_PAIRS fixed pairs
TLB_REF_PAIRS = 4000
TLB_SLACK = 0.01  # the fit's own estimate is a CI over <= 800 other pairs
# kNN: the served neighbour's float64 squared distance may exceed the true
# nearest one by at most KNN_REL * (|x_i|^2 + max_j |x_j|^2), ~80 float32
# ulps of the distance expansion (a bf16-precision matmul misses by ~1e4x)
KNN_REL = 1e-5
KDE_REF_ROWS = 256
KDE_RTOL = 1e-4  # f32 distances perturb each exp term by ~1e-5 relative
KNN_AGREE = 0.999  # (d) vs (a): share of rows with the same neighbour

# (c): the bench_delta_stream shape, TLB target with margin over rank 3
SUB_ROWS, SUB_DIM, SUB_RANK, SUB_APPENDS, SUB_FRAC = 4000, 128, 3, 3, 0.05
SUB_TARGET, SUB_EPS, SUB_MIN_SAMPLES, SUB_BANDWIDTH = 0.97, 1.0, 5, 1.0
ROW_RTOL = 1e-5  # client rows vs basis.transform(grown), of max |row|
SUB_KDE_ATOL = 1e-6  # incremental vs cold KDE: compensated f32 partials

RESULT_TIMEOUT_S = 900.0


class Checks:
    """Collects failed checks, so one run reports all of them."""

    def __init__(self) -> None:
        self.failed: list[str] = []

    def require(self, ok, what: str) -> bool:
        if not ok:
            self.failed.append(what)
            print(f"  CHECK FAILED: {what}", flush=True)
        return bool(ok)


def load_repro() -> None:
    """Put the checkout's ``src`` on the path, or fail: the script proves
    this repository, so it does not run without it."""
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        sys.exit(
            f"chip_smoke.py: no repository next to the script ({src / 'repro'}"
            " is missing); run it from a checkout"
        )
    sys.path.insert(0, str(src))


def tenant_data(names, seed: int, max_rows: int | None = None) -> dict:
    """UCR-like tenants generated from ``seed`` (added to each spec's own
    seed) at their published shapes; ``max_rows`` cuts rows for tests."""
    from repro.data.timeseries import UCR_LIKE_SPECS, make_dataset

    specs = {s.name: s for s in UCR_LIKE_SPECS}
    out = {}
    for name in names:
        spec = dataclasses.replace(specs[name], seed=specs[name].seed + seed)
        if max_rows is not None:
            spec = dataclasses.replace(spec, m=min(spec.m, max_rows))
        out[name] = make_dataset(spec)[0]
    return out


# ------------------------------------------------------- host references


def reference_tlb(x: np.ndarray, res, seed: int) -> float:
    """Mean TLB of the served map at its k on fixed host-sampled pairs,
    float64 (``core.tlb.nested_prefix_tlb``; centering cancels in pair
    differences)."""
    from repro.core.tlb import nested_prefix_tlb, sample_pairs

    pairs = sample_pairs(
        x.shape[0], TLB_REF_PAIRS, np.random.default_rng(seed + 7)
    )
    z = x.astype(np.float64) @ np.asarray(res.v, np.float64)
    return float(nested_prefix_tlb(x, z, pairs)[res.k - 1])


def knn_excess(xt: np.ndarray, idx: np.ndarray) -> tuple[float, float]:
    """Brute-force float64 nearest OTHER row of every row of ``xt``. Returns
    (worst excess of the served neighbour's d2 over the true minimum, in
    units of the KNN_REL scale; share of rows whose index matches)."""
    x = xt.astype(np.float64)
    sq = np.einsum("ij,ij->i", x, x)
    scale = sq + sq.max()
    served = np.einsum("ij,ij->i", x - x[idx], x - x[idx])
    worst, same = 0.0, 0
    for a in range(0, x.shape[0], 2048):
        d2 = sq[a:a + 2048, None] + sq[None, :] - 2.0 * x[a:a + 2048] @ x.T
        rows = np.arange(a, min(a + 2048, x.shape[0]))
        d2[rows - a, rows] = np.inf
        best = d2.min(axis=1)
        excess = (served[rows] - best) / (KNN_REL * scale[rows])
        worst = max(worst, float(excess.max()))
        same += int((d2.argmin(axis=1) == idx[rows]).sum())
    return worst, same / x.shape[0]


def dbscan_bits_error(xt: np.ndarray, eps2: float, counts, packed) -> tuple[int, int]:
    """Eps-ball counts and packed neighbour bits of every row against a
    float64 host scan. Returns (wrong bits or counts, pairs skipped because
    their d2 lies within KNN_REL of eps2, where float32 may round either
    way)."""
    x = xt.astype(np.float64)
    m = x.shape[0]
    sq = np.einsum("ij,ij->i", x, x)
    wrong, skipped = 0, 0
    for a in range(0, m, 1024):
        rows = slice(a, min(a + 1024, m))
        d2 = sq[rows, None] + sq[None, :] - 2.0 * x[rows] @ x.T
        near = np.abs(d2 - eps2) <= KNN_REL * (sq[rows, None] + sq[None, :])
        got = np.unpackbits(
            np.ascontiguousarray(packed[rows]).view(np.uint8),
            axis=1, bitorder="little",
        )[:, :m].astype(bool)
        wrong += int(((got != (d2 <= eps2)) & ~near).sum())
        wrong += int((got.sum(axis=1) != counts[rows]).sum())
        skipped += int(near.sum())
    return wrong, skipped


def kde_error(xt: np.ndarray, dens: np.ndarray, seed: int) -> float:
    """Worst relative error of served KDE densities on KDE_REF_ROWS rows
    against a float64 host sum (bandwidth 1, the served default)."""
    x = xt.astype(np.float64)
    rows = np.random.default_rng(seed + 11).choice(
        x.shape[0], min(KDE_REF_ROWS, x.shape[0]), replace=False
    )
    d2 = ((x[rows, None, :] - x[None, :, :]) ** 2).sum(-1)
    ref = np.exp(-d2 / 2.0).mean(axis=1)
    return float(np.max(np.abs(dens[rows] - ref) / ref))


# ----------------------------------------------------------------- phases


def serve_phase(tag, datasets, cfg, checks, seed=0, service=None) -> dict:
    """Cold serve then repeat serve of ``datasets`` through an
    ``IngestFrontend`` (phases (a)-(b), or (d) with kernels on)."""
    from repro.serve_drop import DropService, IngestFrontend

    svc = service if service is not None else DropService()
    out = {}
    with IngestFrontend(svc) as fe:
        t0 = time.perf_counter()
        qids = {
            n: fe.submit(x, cfg, downstream="knn", execute_downstream=True)
            for n, x in datasets.items()
        }
        extra = {
            task: fe.submit(
                datasets[ANALYTICS_TENANT], cfg,
                downstream=task, execute_downstream=True,
            )
            for task in ("dbscan", "kde")
            if ANALYTICS_TENANT in datasets
        }
        out["cold"] = {n: fe.result(q, RESULT_TIMEOUT_S) for n, q in qids.items()}
        out["extra"] = {t: fe.result(q, RESULT_TIMEOUT_S) for t, q in extra.items()}
        wall_cold = time.perf_counter() - t0
        fits = svc.stats.fit_calls
        hits = svc.stats.cache_hits
        t1 = time.perf_counter()
        qids = {
            n: fe.submit(x, cfg, downstream="knn", execute_downstream=True)
            for n, x in datasets.items()
        }
        out["repeat"] = {n: fe.result(q, RESULT_TIMEOUT_S) for n, q in qids.items()}
        wall_repeat = time.perf_counter() - t1
    st = svc.stats
    print(
        f"[{tag}] cold serve: {len(qids) + len(extra)} queries in "
        f"{wall_cold:.3f} s wall (compile included); {hits} cache hits, "
        f"{fits} fit calls, {st.iterations} iterations",
        flush=True,
    )
    print(
        f"[{tag}] repeat serve: {len(qids)} queries in {wall_repeat:.3f} s "
        f"wall; {st.cache_hits - hits} cache hits, "
        f"{st.fit_calls - fits} fit calls, {st.validation_pairs} "
        f"validation pairs in all",
        flush=True,
    )
    everything = [*out["cold"].values(), *out["extra"].values(), *out["repeat"].values()]
    for r in everything:
        checks.require(r.error is None, f"{tag}: q{r.query_id} error {r.error}")
    for n, r in out["cold"].items():
        checks.require(
            not r.cache_hit and r.result.satisfied,
            f"{tag}: {n} cold serve was not a satisfied fit",
        )
    for n, r in [*out["extra"].items(), *out["repeat"].items()]:
        checks.require(
            r.cache_hit and not r.degraded and not r.suffix_update
            and not r.result.iterations,
            f"{tag}: {n} repeat was not a revalidated cache hit",
        )
    repeats = len(out["extra"]) + len(out["repeat"])
    checks.require(st.cache_hits == repeats, f"{tag}: {st.cache_hits} cache hits != {repeats} repeats")
    checks.require(st.fit_calls == fits, f"{tag}: repeat serve fitted")
    checks.require(st.downstream_runs == len(everything), f"{tag}: {st.downstream_runs} analytics runs")
    for counter in (
        "failures", "downstream_failures", "suffix_update_failures",
        "validation_errors", "drain_failures",
    ):
        checks.require(getattr(st, counter) == 0, f"{tag}: stats.{counter}={getattr(st, counter)}")
    reference_checks(tag, datasets, out, checks, seed)
    return out


def reference_checks(tag, datasets, out, checks, seed) -> None:
    """Served answers vs the host references (errored queries already
    failed their own check and carry no answer)."""
    for n, r in out["cold"].items():
        if r.error is not None:
            continue
        res = r.result
        x = datasets[n]
        tlb = reference_tlb(x, res, seed)
        worst, same = knn_excess(res.transform(x), np.asarray(r.downstream))
        print(
            f"[{tag}] {n} m={x.shape[0]} d={x.shape[1]}: k={res.k} "
            f"served tlb={res.tlb_estimate:.4f} host tlb={tlb:.4f}; kNN "
            f"worst excess={worst:.3f} (limit 1) same index={same:.5f}",
            flush=True,
        )
        checks.require(tlb >= TARGET_TLB - TLB_SLACK, f"{tag}: {n} host TLB {tlb:.4f}")
        checks.require(worst <= 1.0, f"{tag}: {n} kNN excess {worst:.3f}")
    extra = {t: r for t, r in out["extra"].items() if r.error is None}
    if "kde" in extra:
        r = extra["kde"]
        x = datasets[ANALYTICS_TENANT]
        err = kde_error(r.result.transform(x), np.asarray(r.downstream), seed)
        print(f"[{tag}] {ANALYTICS_TENANT} KDE worst rel err={err:.2e} (limit {KDE_RTOL:.0e})", flush=True)
        checks.require(err <= KDE_RTOL, f"{tag}: KDE rel err {err:.2e}")
    if "dbscan" in extra:
        labels = np.asarray(extra["dbscan"].downstream)
        checks.require(
            labels.shape == (datasets[ANALYTICS_TENANT].shape[0],),
            f"{tag}: DBSCAN labels shape {labels.shape}",
        )
        print(
            f"[{tag}] {ANALYTICS_TENANT} DBSCAN: {int(labels.max()) + 1} "
            f"clusters, {int((labels < 0).sum())} noise points",
            flush=True,
        )


def subscription_phase(checks, seed=0, rows=SUB_ROWS, dim=SUB_DIM, appends=SUB_APPENDS) -> None:
    """(c): one subscription through the ingest front-end; the client's
    state must equal a cold recompute over the rows it holds."""
    from repro.analytics import dbscan, pairwise_kde, pairwise_knn
    from repro.core import DropConfig
    from repro.data import sinusoid_mixture
    from repro.serve_drop import (
        DropService, IngestFrontend, SubscribeQuery, SubscriberState,
    )

    step = max(1, int(rows * SUB_FRAC))
    x = sinusoid_mixture(rows + appends * step, dim, rank=SUB_RANK, seed=seed)[0]
    cfg = DropConfig(target_tlb=SUB_TARGET, seed=seed, min_iterations=99)
    svc = DropService()
    client = SubscriberState()
    t0 = time.perf_counter()
    with IngestFrontend(svc) as fe:
        sid = fe.subscribe(SubscribeQuery(
            x=x[:rows], cfg=cfg, eps=SUB_EPS, min_samples=SUB_MIN_SAMPLES,
            bandwidth=SUB_BANDWIDTH,
        ))
        client.apply(fe.next_delta(sid, RESULT_TIMEOUT_S))
        boot = time.perf_counter() - t0
        walls = []
        for i in range(appends):
            ta = time.perf_counter()
            fe.append(sid, x[rows + i * step: rows + (i + 1) * step])
            client.apply(fe.next_delta(sid, RESULT_TIMEOUT_S))
            walls.append(time.perf_counter() - ta)
        fe.unsubscribe(sid)
        client.apply(fe.next_delta(sid, RESULT_TIMEOUT_S))
    grown = x[: client.rows.shape[0]]
    want = client.basis.transform(grown)
    row_err = float(np.max(np.abs(client.rows - want)) / np.max(np.abs(want)))
    idx, d2 = pairwise_knn(client.rows)
    labels = dbscan(client.rows, SUB_EPS, SUB_MIN_SAMPLES)
    dens = pairwise_kde(client.rows, None, SUB_BANDWIDTH)
    kde_err = float(np.max(np.abs(client.densities - dens)))
    print(
        f"[c] subscription m0={rows} d={dim} +{step} rows x {appends}: "
        f"k={client.basis.k}, bootstrap {boot:.3f} s wall (compile "
        f"included), appends {', '.join(f'{w:.3f}' for w in walls)} s; "
        f"{client.appends} appends, {client.rollbacks} rollbacks; DBSCAN "
        f"{int(labels.max()) + 1} clusters, {int((labels < 0).sum())} noise; "
        f"row err {row_err:.2e}, KDE vs cold {kde_err:.2e}",
        flush=True,
    )
    checks.require(client.closed and client.error is None, f"c: closed with {client.error}")
    checks.require(
        client.appends == appends and client.rollbacks == 1,
        f"c: {client.appends} appends, {client.rollbacks} rollbacks",
    )
    checks.require(row_err <= ROW_RTOL, f"c: client rows off by {row_err:.2e}")
    checks.require(np.array_equal(client.knn_idx, idx), "c: incremental kNN idx != cold")
    checks.require(np.array_equal(client.knn_d2, d2), "c: incremental kNN d2 != cold")
    checks.require(np.array_equal(client.labels, labels), "c: incremental DBSCAN != cold")
    checks.require(kde_err <= SUB_KDE_ATOL, f"c: incremental KDE off by {kde_err:.2e}")
    for counter in ("failures", "suffix_update_failures", "validation_errors", "drain_failures"):
        checks.require(getattr(svc.stats, counter) == 0, f"c: stats.{counter}")


def kernel_compiles() -> dict:
    """Executables compiled so far for each Pallas kernel the served path
    calls at top level (the Halko matmul kernel sits inside the jitted fit,
    see ``halko_program_has_kernel``)."""
    from repro.kernels.pairwise_reduce import pairwise_reduce as pr
    from repro.kernels.pairwise_tlb.pairwise_tlb import pairwise_tlb_pallas

    return {
        "tlb": pairwise_tlb_pallas._cache_size(),
        "knn": pr.pairwise_knn_pallas._cache_size(),
        "dbscan": pr.pairwise_dbscan_pallas._cache_size(),
        "kde": pr.pairwise_kde_pallas._cache_size(),
    }


def halko_program_has_kernel(x: np.ndarray, k: int) -> bool:
    """Whether the Halko fit program on the kernel path embeds a Mosaic
    kernel (the compile is the one the served full-data fit used)."""
    import jax
    import jax.numpy as jnp

    from repro.core.bucketing import DEFAULT_BUCKETS
    from repro.core.halko import svd_halko

    c = jax.ShapeDtypeStruct((DEFAULT_BUCKETS.bucket_rows(x.shape[0]), x.shape[1]), jnp.float32)
    text = svd_halko.lower(c, k, jax.random.PRNGKey(0), use_kernels=True).compile().as_text()
    return "tpu_custom_call" in text


def dbscan_kernel_check(x: np.ndarray, served, checks) -> None:
    """The served DBSCAN (eps 0.5) labels every row of these tenants noise,
    so no served answer reads the kernel's neighbour bits: scan the served
    reduced rows with the kernel at twice their median neighbour distance
    and check every bit against the host."""
    from repro.analytics.pairwise import pairwise_dbscan

    xt = served.result.transform(x)
    nn = xt - xt[np.asarray(served.downstream)]
    eps = 2.0 * float(np.sqrt(np.median(np.einsum("ij,ij->i", nn, nn))))
    counts, packed = pairwise_dbscan(xt, eps, use_kernels=True)
    eps2 = float(np.float32(eps * eps))  # the scan's own threshold
    wrong, skipped = dbscan_bits_error(xt, eps2, counts, packed)
    print(
        f"[d] DBSCAN kernel at eps={eps:.4g}: mean eps-ball {counts.mean():.1f}"
        f" rows; {wrong} bits or counts off the host scan, {skipped} pairs "
        f"within float32 rounding of eps skipped",
        flush=True,
    )
    checks.require(wrong == 0, f"d: DBSCAN kernel has {wrong} wrong bits or counts")


def kernel_phase(datasets, cfg, checks, base: dict, seed=0) -> None:
    """(d): phases (a)-(b) with the Pallas kernels serving; the answers
    must agree with the jnp path of (a)."""
    import jax

    before = kernel_compiles()
    out = serve_phase("d", datasets, dataclasses.replace(cfg, use_kernels=True), checks, seed)
    after = kernel_compiles()
    print(f"[d] kernel executables compiled before/after: {before} / {after}", flush=True)
    for name in after:
        checks.require(after[name] > before[name], f"d: the {name} kernel never compiled")
    n = ANALYTICS_TENANT if ANALYTICS_TENANT in datasets else next(iter(datasets))
    if jax.devices()[0].platform == "tpu":
        checks.require(
            halko_program_has_kernel(datasets[n], out["cold"][n].result.k),
            "d: the Halko fit program holds no Mosaic kernel",
        )
    if out["cold"][n].error is None:
        dbscan_kernel_check(datasets[n], out["cold"][n], checks)
    for n, r in out["cold"].items():
        a = base["cold"][n]
        same = float(np.mean(np.asarray(r.downstream) == np.asarray(a.downstream)))
        print(f"[d] {n}: k={r.result.k} vs (a) k={a.result.k}; kNN same index {same:.5f}", flush=True)
        checks.require(r.result.k == a.result.k, f"d: {n} k differs from (a)")
        checks.require(same >= KNN_AGREE, f"d: {n} kNN agreement {same:.5f}")


def mesh_phase(datasets, cfg, checks, devices: int = 4) -> None:
    """Four-device path only: ``DropService(devices=4)``, the sharded
    scheduler with the mesh analytics fan-out, then the same queries on a
    single-device ``DropService``."""
    import jax

    from repro.serve_drop import DropService, IngestFrontend, ShardedDropService

    def serve(svc):
        t0 = time.perf_counter()
        with IngestFrontend(svc) as fe:
            qids = {
                n: fe.submit(x, cfg, downstream="knn", execute_downstream=True)
                for n, x in datasets.items()
            }
            res = {n: fe.result(q, RESULT_TIMEOUT_S) for n, q in qids.items()}
        return res, time.perf_counter() - t0

    mesh = DropService(devices=devices)
    checks.require(
        isinstance(mesh, ShardedDropService) and mesh.analytics_fanout == "mesh",
        f"mesh: DropService(devices={devices}) built {type(mesh).__name__}",
    )
    got, wall = serve(mesh)
    occupancy = dict(mesh.stats.device_iterations)
    peaks = {
        str(d): (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in mesh.devices
    }
    ref, wall_ref = serve(DropService())
    print(
        f"[mesh] {len(got)} queries on {devices} devices in {wall:.3f} s wall "
        f"(compile included), one device {wall_ref:.3f} s; iterations per "
        f"device {occupancy}; peak bytes {peaks}",
        flush=True,
    )
    checks.require(
        len(occupancy) == devices and min(occupancy.values()) > 0,
        f"mesh: iterations per device {occupancy}",
    )
    if jax.devices()[0].platform == "tpu":  # the CPU backend keeps no stats
        checks.require(min(peaks.values()) > 0, f"mesh: peak bytes {peaks}")
    for n in datasets:
        a, b = got[n], ref[n]
        dv = float(np.max(np.abs(a.result.v - b.result.v))) if a.result.k == b.result.k else float("inf")
        same = bool(np.array_equal(np.asarray(a.downstream), np.asarray(b.downstream)))
        print(f"[mesh] {n}: k={a.result.k} vs {b.result.k}, max |dV|={dv:.2e}, kNN identical={same}", flush=True)
        checks.require(a.error is None and b.error is None, f"mesh: {n} errors {a.error} {b.error}")
        checks.require(a.result.k == b.result.k and dv <= 1e-5, f"mesh: {n} basis differs")
        checks.require(same, f"mesh: {n} kNN differs")
    for counter in ("failures", "downstream_failures", "validation_errors", "drain_failures"):
        checks.require(getattr(mesh.stats, counter) == 0, f"mesh: stats.{counter}")
    scans, shards = mesh.stats.mesh_scans, mesh.stats.mesh_shards
    checks.require(
        scans == len(datasets) and shards == devices * scans,
        f"mesh: {scans} mesh scans over {shards} shards for {len(datasets)} queries",
    )


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-device mesh path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        print("chip_smoke.py: REPRO_PALLAS_INTERPRET is set; the smoke runs "
              "native kernels only", file=sys.stderr)
        return 2
    load_repro()
    import jax

    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke.py: no TPU found; JAX sees {dev[0].platform} "
              f"devices only", file=sys.stderr)
        return 1
    if len(dev) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but {len(dev)} TPU "
              f"device(s) visible", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    from repro.core import DropConfig

    cache = enable_compile_cache()
    print(f"device: {dev[0].device_kind} x{len(dev)}; compile cache {cache}", flush=True)
    checks = Checks()
    cfg = DropConfig(target_tlb=TARGET_TLB, seed=args.seed, min_iterations=99)
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(tenant_data(MESH_TENANTS, args.seed), cfg, checks)
    else:
        datasets = tenant_data(TENANTS, args.seed)
        base = serve_phase("a", datasets, cfg, checks, args.seed)
        subscription_phase(checks, args.seed)
        kernel_phase(datasets, cfg, checks, base, args.seed)
    print(f"total wall {time.perf_counter() - t0:.3f} s; "
          f"{len(checks.failed)} failed checks", flush=True)
    if checks.failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
