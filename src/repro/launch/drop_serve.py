"""DROP serving launcher CLI: batched multi-query DR with basis reuse.

    PYTHONPATH=src python -m repro.launch.drop_serve --queries 8
    PYTHONPATH=src python -m repro.launch.drop_serve --devices 2 --async
    PYTHONPATH=src python -m repro.launch.drop_serve --fleet 2
    PYTHONPATH=src python -m repro.launch.drop_serve --method pca,fft,paa

Generates a synthetic tenant workload (a pool of distinct datasets, with a
configurable fraction of repeat submissions — the paper-§5 regime), drains it
through ``DropService`` (or the sharded multi-device scheduler with
``--devices N``, the supervised process-worker fleet with ``--fleet N`` —
the CPU scale-out mode, one XLA client per worker, with fault-tolerant
restart and measured-cost placement — and the threaded ingest front-end
with ``--async``), and
reports queries/sec, cache behavior, per-device occupancy, and the shared
shape-bucket population. ``--method`` picks the Reducer per query (a comma
list cycles across the workload — FFT/PAA queries are scheduled and cached
exactly like DROP); ``--downstream`` prices the named analytics task as the
cost model, and ``--execute-downstream`` additionally RUNS it on each
query's reduced data before the query finishes (``--analytics-split N`` /
``--analytics-fanout`` select the exact-merge shard decomposition of that
scan — see ``analytics.split``). ``--compare-sequential`` also times cold
``reduce()`` per query
for a direct speedup figure. ``--grow-steps N`` switches to the append-only
demo: one tenant's dataset grows by ``--grow-frac`` rows per step and each
snapshot climbs the escalation ladder (prefix hit -> incremental suffix
update -> cold refit as last resort; tune with ``--suffix-budget`` /
``--no-suffix-update``). ``--subscribe`` is the pub/sub variant of the same
stream: instead of re-submitting grown snapshots, it opens ONE delta
subscription through the ingest front-end and applies the server-pushed
``append``/``rollback`` deltas client-side (``SubscriberState``), so each
append costs O(suffix) end-to-end — works against the in-process scheduler,
the sharded mesh, and the process fleet alike. ``--use-kernels`` opts served queries into the
Pallas kernel path end-to-end (fit matmuls + TLB validations; native on
TPU, interpreter under ``REPRO_PALLAS_INTERPRET=1``, fused-jnp fallback on
plain CPU — always safe to set).
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def _requested_devices(argv: list[str]) -> int | None:
    """Pre-argparse peek at --devices (both '--devices N' and
    '--devices=N'); malformed values are left for argparse to report."""
    for i, arg in enumerate(argv):
        raw = None
        if arg == "--devices" and i + 1 < len(argv):
            raw = argv[i + 1]
        elif arg.startswith("--devices="):
            raw = arg.split("=", 1)[1]
        if raw is not None:
            try:
                return int(raw)
            except ValueError:
                return None
    return None


def _force_host_devices_from_argv() -> None:
    """--devices N needs the forced host platform BEFORE jax initializes
    (same trick as launch/dryrun.py); on real multi-device hardware
    XLA_FLAGS is already set and we leave it alone."""
    n = _requested_devices(sys.argv)
    if n is not None and n > 1:
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={n}"
        )


_force_host_devices_from_argv()

import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import DropConfig, reduce  # noqa: E402
from repro.core.cost import downstream_cost  # noqa: E402
from repro.core.reducer import REDUCER_METHODS  # noqa: E402
from repro.data import sinusoid_mixture  # noqa: E402
from repro.serve_drop import (  # noqa: E402
    DropService,
    FleetSupervisor,
    IngestFrontend,
    RetryLater,
    ShardedDropService,
    SubscribeQuery,
    SubscriberState,
)


def build_workload(
    n_queries: int, n_datasets: int, rows: int, dim: int, seed: int
) -> list[np.ndarray]:
    """Round-robin over a dataset pool: n_datasets distinct matrices, repeats
    after the first pass (repeat fraction = 1 - n_datasets / n_queries)."""
    pool = [
        sinusoid_mixture(rows, dim, rank=5 + i, seed=seed + i)[0]
        for i in range(n_datasets)
    ]
    return [pool[i % n_datasets] for i in range(n_queries)]


def _serve_append_stream(svc, args, method, cfg, cost) -> None:
    """--grow-steps demo: one tenant's dataset grows by --grow-frac rows per
    step; each snapshot is submitted AFTER the previous one finished (prefix
    fingerprints are hashed at submit time against the live cache), so the
    stream exercises the escalation ladder: prefix hit -> suffix update ->
    cold refit as last resort. Non-PCA methods carry no updater state, so
    their ladder tops out at revalidate-or-refit."""
    append = max(1, int(args.rows * args.grow_frac))
    m_total = args.rows + args.grow_steps * append
    x_full = sinusoid_mixture(m_total, args.dim, rank=5, seed=args.seed)[0]
    reduce(x_full[: args.rows], method, cfg, cost)  # jit warm (convention)
    print(f"append stream [{method}]: m0={args.rows} +{append} rows x "
          f"{args.grow_steps} steps (suffix budget {args.suffix_budget})")
    t0 = time.perf_counter()
    for i in range(args.grow_steps + 1):
        snap = x_full[: args.rows + i * append]
        ts = time.perf_counter()
        svc.submit(snap, cfg, cost, method=method)
        r = svc.run()[0]
        tag = ("SUFX" if r.suffix_update else "HIT " if r.cache_hit
               else "WARM" if r.warm_started else "COLD")
        print(f"  step {i:02d} [{tag}] rows={snap.shape[0]:6d} "
              f"k={r.result.k:3d} tlb={r.result.tlb_estimate:.4f} "
              f"wall={(time.perf_counter() - ts) * 1e3:7.1f} ms")
    dt = time.perf_counter() - t0
    print(f"stream served in {dt*1e3:.0f} ms; cache: "
          f"{svc.stats.prefix_hits} prefix hits, "
          f"{svc.stats.suffix_updates} suffix updates "
          f"({svc.stats.suffix_update_failures} fell through), "
          f"{svc.stats.fit_calls} basis fits")


def _delta_line(delta: dict, client: SubscriberState) -> str:
    if delta["kind"] == "closed":
        return f"  seq {delta['seq']:02d} [CLOSED  ] error={delta.get('error')}"
    tag = ("APPEND  " if delta["kind"] == "append"
           else f"ROLLBACK/{delta.get('reason', '?')}")
    return (f"  seq {delta['seq']:02d} [{tag:8s}] "
            f"rows={client.rows.shape[0]:6d} k={client.basis.k:3d} "
            f"tlb={delta['tlb']:.4f} rot={delta['rotation']:.3f} "
            f"wall={delta['wall_s'] * 1e3:7.1f} ms")


def _serve_subscribe_stream(svc, args, method, cfg) -> None:
    """--subscribe demo: ONE delta subscription on a growing tenant. The
    server pushes the difference after each append — transformed suffix
    rows plus O(suffix) downstream patches while the tracker's rotation
    stays inside --rotation-tol (TLB-gated), a full restate when the basis
    moved — and the client folds every delta into ``SubscriberState``. The
    first delta is always the bootstrap rollback; unsubscribing delivers
    the terminal ``closed``."""
    append = max(1, int(args.rows * args.grow_frac))
    steps = args.grow_steps if args.grow_steps > 0 else 5
    m_total = args.rows + steps * append
    x_full = sinusoid_mixture(m_total, args.dim, rank=5, seed=args.seed)[0]
    print(f"pub/sub delta stream [{method}]: m0={args.rows} +{append} rows "
          f"x {steps} appends (rotation tol {args.rotation_tol})")
    client = SubscriberState()
    t0 = time.perf_counter()
    with IngestFrontend(svc, queue_capacity=args.queue_capacity) as fe:
        sid = fe.subscribe(SubscribeQuery(
            x=x_full[: args.rows], cfg=cfg, method=method,
            rotation_tol=args.rotation_tol,
        ))
        delta = fe.next_delta(sid, timeout=300.0)  # bootstrap rollback
        client.apply(delta)
        print(_delta_line(delta, client))
        for _ in range(steps):
            lo = client.rows.shape[0]
            fe.append(sid, x_full[lo: lo + append])
            delta = fe.next_delta(sid, timeout=300.0)
            client.apply(delta)
            print(_delta_line(delta, client))
        fe.unsubscribe(sid)
        delta = fe.next_delta(sid, timeout=300.0)
        client.apply(delta)
        print(_delta_line(delta, client))
    dt = time.perf_counter() - t0
    grown = x_full[: client.rows.shape[0]]
    err = float(np.max(np.abs(client.rows - client.basis.transform(grown))))
    print(f"stream served in {dt*1e3:.0f} ms; client folded "
          f"{client.appends} appends + {client.rollbacks} rollbacks "
          f"-> {client.rows.shape[0]} rows @ k={client.basis.k}")
    print(f"client-state parity vs basis.transform(grown): "
          f"max |diff| = {err:.3e}"
          + (" (bit-exact)" if err == 0.0 else ""))
    stats = getattr(svc, "stats", None)
    if stats is not None:
        print(f"server: {stats.subscriptions} subscriptions, "
              f"{stats.delta_serves} delta serves, "
              f"{stats.rollbacks} rollbacks, {stats.failures} failures")


def _submit_async(
    fe: IngestFrontend, datasets, methods, cfg, cost, downstream,
    execute_downstream: bool = False, deadline_s: float | None = None,
) -> list[int]:
    """Stream submissions through the bounded ingest queue, honoring
    reject-with-retry-after backpressure."""
    qids = []
    for x, m in zip(datasets, methods):
        while True:
            try:
                qids.append(
                    fe.submit(x, cfg, cost, method=m, downstream=downstream,
                              execute_downstream=execute_downstream,
                              deadline_s=deadline_s)
                )
                break
            except RetryLater as e:
                time.sleep(e.retry_after_s)
    return qids


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--datasets", type=int, default=2,
                    help="distinct datasets in the pool (rest are repeats)")
    ap.add_argument("--rows", type=int, default=1500)
    ap.add_argument("--dim", type=int, default=96)
    ap.add_argument("--target", type=float, default=0.98)
    ap.add_argument("--method", type=str, default="pca",
                    help="reduction method per query; a comma list (e.g. "
                         "'pca,fft,paa') cycles across the workload")
    ap.add_argument("--downstream", type=str, default="knn",
                    choices=("knn", "dbscan", "kde"),
                    help="analytics task priced as the downstream cost model")
    ap.add_argument("--execute-downstream", action="store_true",
                    help="RUN the --downstream analytics on each query's "
                         "reduced data before it finishes (the served "
                         "end-to-end path; output lands on "
                         "ServeResult.downstream)")
    ap.add_argument("--analytics-split", type=int, default=None,
                    help="run executed analytics as N flash-decoding-style "
                         "dataset shards (exact merges — identical results; "
                         "see analytics.split)")
    ap.add_argument("--analytics-fanout", type=str, default=None,
                    choices=("xla", "mesh"),
                    help="shard execution: 'xla' batches shards in one "
                         "dispatch, 'mesh' shard_maps them across devices "
                         "(sharded scheduler defaults to mesh on >1 device)")
    ap.add_argument("--max-inflight", type=int, default=4)
    ap.add_argument("--cache-entries", type=int, default=16)
    ap.add_argument("--cache-ttl", type=int, default=None,
                    help="basis-cache TTL in scheduler ticks (default: none)")
    ap.add_argument("--suffix-budget", type=float, default=0.25,
                    help="append-only drift budget: a prefix-matched suffix "
                         "larger than this fraction of the fitted rows skips "
                         "revalidation and goes straight to the incremental "
                         "subspace update")
    ap.add_argument("--no-suffix-update", action="store_true",
                    help="disable incremental suffix updates (failed prefix "
                         "revalidations refit cold, the pre-tracking behavior)")
    ap.add_argument("--grow-steps", type=int, default=0,
                    help="append-stream demo: serve the base dataset, then "
                         "this many grown snapshots (each +grow-frac rows) "
                         "sequentially through the escalation ladder")
    ap.add_argument("--grow-frac", type=float, default=0.05,
                    help="per-append row growth for --grow-steps")
    ap.add_argument("--subscribe", action="store_true",
                    help="pub/sub demo: open ONE delta subscription on a "
                         "growing tenant and stream server-pushed append/"
                         "rollback deltas through the ingest front-end "
                         "(O(suffix) per append; reuses --grow-steps/"
                         "--grow-frac, default 5 appends)")
    ap.add_argument("--rotation-tol", type=float, default=0.25,
                    help="--subscribe append-vs-rollback gate on the "
                         "tracker's principal-angle rotation signal")
    ap.add_argument("--devices", type=int, default=1,
                    help="mesh devices for the sharded scheduler (>1 forces "
                         "the host-platform device count on CPU)")
    ap.add_argument("--fleet", type=int, default=0,
                    help="serve through N supervised worker PROCESSES (one "
                         "XLA client each — the CPU scale-out mode) instead "
                         "of the in-process scheduler; excludes --devices")
    ap.add_argument("--placement", type=str, default="cost",
                    choices=("cost", "rr"),
                    help="fleet placement: measured-cost (link alpha/beta + "
                         "queue depth / worker speed) or sticky round-robin")
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="stream queries through the threaded ingest "
                         "front-end instead of batch submit+run")
    ap.add_argument("--queue-capacity", type=int, default=64,
                    help="ingest backlog bound before reject-with-retry-after")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-query latency budget: past it the query "
                         "expires with error='deadline' instead of burning "
                         "compute (enforced at dequeue and between runner "
                         "steps; fleet workers and the supervisor both "
                         "enforce it)")
    ap.add_argument("--max-queue-delay-s", type=float, default=None,
                    help="adaptive admission bound for --async: reject with "
                         "a computed retry_after_s once the estimated queue "
                         "delay (backlog x recent per-query wall) exceeds "
                         "this")
    ap.add_argument("--degrade-watermark-s", type=float, default=None,
                    help="graceful degradation: once the estimated queue "
                         "delay passes this watermark, queries with ANY "
                         "cached basis (stale/prefix/coarser-method) are "
                         "served it immediately as ServeResult.degraded "
                         "instead of queueing fresh compute")
    ap.add_argument("--use-kernels", action="store_true",
                    help="route served queries' hot matmuls and TLB "
                         "validations through the Pallas kernel wrappers "
                         "(native on TPU; interpret-safe on CPU — set "
                         "REPRO_PALLAS_INTERPRET=1 to force interpreter "
                         "execution, otherwise CPU falls back to the fused "
                         "jnp paths)")
    ap.add_argument("--state-dir", type=str, default=None,
                    help="crash-consistent durability root: basis-cache "
                         "snapshots + subscription WAL (+ fleet warm-restart "
                         "profile). Relaunching with the same directory "
                         "recovers cached bases, resumes journaled "
                         "subscriptions with ROLLBACK(reason='recover'), "
                         "and skips the fleet's startup probe storm")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--compare-sequential", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    datasets = build_workload(
        args.queries, max(1, min(args.datasets, args.queries)),
        args.rows, args.dim, args.seed,
    )
    methods = [m.strip() for m in args.method.split(",") if m.strip()]
    unknown = [m for m in methods if m not in REDUCER_METHODS]
    if unknown:
        ap.error(f"unknown --method {unknown}; know {REDUCER_METHODS}")
    methods = [methods[i % len(methods)] for i in range(args.queries)]
    cfg = DropConfig(
        target_tlb=args.target, seed=args.seed,
        use_kernels=args.use_kernels,
    )
    cost = downstream_cost(args.downstream, args.rows)

    if args.fleet > 0:
        if args.devices > 1:
            ap.error("--fleet (process workers) and --devices (in-process "
                     "mesh) are alternative scale-out modes; pick one")
        if args.grow_steps > 0 and not args.subscribe:
            ap.error("--grow-steps needs the in-process prefix cache; "
                     "drop --fleet (or add --subscribe: delta "
                     "subscriptions ARE fleet-capable)")
        # cost closures do not cross the process boundary: the workers
        # re-price the named downstream task themselves
        svc = FleetSupervisor(
            workers=args.fleet,
            enable_worker_cache=not args.no_cache,
            placement=args.placement,
            state_dir=args.state_dir,
        ).start()
        print(f"fleet of {args.fleet} worker processes "
              f"({args.placement} placement): {svc.devices}")
        cost = None
    elif args.devices > 1:
        svc = ShardedDropService(
            devices=args.devices,
            max_inflight=args.max_inflight,
            cache_entries=args.cache_entries,
            enable_cache=not args.no_cache,
            cache_ttl=args.cache_ttl,
            enable_suffix_update=not args.no_suffix_update,
            suffix_budget=args.suffix_budget,
            analytics_split=args.analytics_split,
            analytics_fanout=args.analytics_fanout,
            degrade_watermark_s=args.degrade_watermark_s,
            state_dir=args.state_dir,
        )
        print(f"sharded scheduler over {len(svc.devices)} devices: "
              f"{[str(d) for d in svc.devices]} "
              f"(analytics fanout: {svc.analytics_fanout})")
    else:
        svc = DropService(
            max_inflight=args.max_inflight,
            cache_entries=args.cache_entries,
            enable_cache=not args.no_cache,
            cache_ttl=args.cache_ttl,
            enable_suffix_update=not args.no_suffix_update,
            suffix_budget=args.suffix_budget,
            analytics_split=args.analytics_split,
            analytics_fanout=args.analytics_fanout or "xla",
            degrade_watermark_s=args.degrade_watermark_s,
            state_dir=args.state_dir,
        )
        if args.state_dir and (
            svc.stats.recovery_snapshots or svc.stats.recovery_subs
        ):
            print(f"recovered from {args.state_dir}: "
                  f"{svc.stats.recovery_snapshots} basis snapshots "
                  f"({svc.stats.recovery_quarantined} quarantined), "
                  f"{svc.stats.recovery_subs} journaled subscriptions")
    if args.subscribe:
        if len(set(methods)) > 1:
            ap.error("--subscribe serves ONE growing tenant; give a "
                     "single --method")
        try:
            _serve_subscribe_stream(svc, args, methods[0], cfg)
        finally:
            if args.fleet:
                svc.shutdown()
        return

    if args.grow_steps > 0:
        if args.use_async:
            ap.error("--grow-steps is sequential by design (prefix matching "
                     "is submit-time); drop --async")
        if len(set(methods)) > 1:
            ap.error("--grow-steps serves ONE growing tenant; give a single "
                     "--method")
        _serve_append_stream(svc, args, methods[0], cfg, cost)
        return

    # warm the jit caches with one cold reduce() per distinct (dataset,
    # method) pair so the reported throughput measures serving, not XLA
    # compilation (plain reduce() shares the shape buckets but never touches
    # the service cache; the baseline single-shots compile nothing). Fleet
    # workers compile in their OWN processes, so warming here would be
    # wasted work — their first queries pay the compile instead.
    if not args.fleet:
        for i, x in enumerate(datasets[: args.datasets]):
            reduce(x, methods[i], cfg, cost)

    t0 = time.perf_counter()
    if args.use_async:
        with IngestFrontend(
            svc, queue_capacity=args.queue_capacity,
            max_queue_delay_s=args.max_queue_delay_s,
        ) as fe:
            qids = _submit_async(
                fe, datasets, methods, cfg, cost, args.downstream,
                args.execute_downstream, args.deadline_s,
            )
            results = sorted(
                (fe.result(q) for q in qids), key=lambda r: r.query_id
            )
    else:
        for x, m in zip(datasets, methods):
            svc.submit(x, cfg, cost, method=m, downstream=args.downstream,
                       execute_downstream=args.execute_downstream,
                       deadline_s=args.deadline_s)
        results = svc.run()
    dt = time.perf_counter() - t0

    qps = args.queries / dt
    hits = sum(r.cache_hit for r in results)
    mode = "async ingest" if args.use_async else "batch"
    print(f"served {args.queries} queries in {dt*1e3:.0f} ms  "
          f"({qps:.2f} queries/sec, {mode})")
    if args.fleet:
        # worker-local caches/buckets live across the process boundary; the
        # supervisor surfaces its own fleet telemetry instead
        print(f"cache: {hits}/{args.queries} worker-cache hits, "
              f"{svc.stats.warm_starts} warm starts, "
              f"{svc.stats.rejected} backpressure rejections")
        print(f"fleet: {svc.stats.worker_deaths} deaths, "
              f"{svc.stats.worker_restarts} restarts, "
              f"{svc.stats.requeued_queries} requeues, "
              f"{svc.stats.rebalances} rebalances, "
              f"{svc.stats.straggler_flags} straggler flags")
        speeds = ", ".join(
            f"{w}={s:.2f}" for w, s in sorted(svc.worker_speeds().items())
        )
        links = ", ".join(
            f"{w}: a={p.alpha_s*1e6:.0f}us b={p.beta_s_per_byte*1e9:.2f}ns/B"
            for w, p in sorted(svc.link_profiles().items())
        )
        print(f"worker speeds: {speeds}")
        print(f"link profiles: {links}")
    else:
        print(f"cache: {hits}/{args.queries} hits, "
              f"{svc.stats.warm_starts} warm starts, "
              f"{svc.stats.suffix_updates} suffix updates, "
              f"{svc.stats.fit_calls} basis fits, "
              f"{len(svc.cache)} entries resident, "
              f"{svc.stats.rejected} backpressure rejections")
    if args.deadline_s is not None or args.degrade_watermark_s is not None:
        print(f"resilience: {svc.stats.deadline_expired} deadline-expired, "
              f"{svc.stats.degraded_serves} degraded serves, "
              f"{svc.stats.rejected} admission rejections")
    if svc.stats.device_iterations:
        occ = ", ".join(
            f"{dev}={n}" for dev, n in sorted(svc.stats.device_iterations.items())
        )
        print(f"occupancy (iterations/device): {occ}; "
              f"steals={svc.stats.steals}")
    if not args.fleet:
        print(f"buckets: {svc.bucket.summary()}")
    if args.execute_downstream and not args.fleet:
        print(f"downstream [{args.downstream}]: {svc.stats.downstream_runs} "
              f"served executions "
              f"({svc.stats.downstream_failures} failed; "
              f"split={args.analytics_split or 1}, "
              f"fanout={svc.analytics_fanout})")
    for r in results:
        tag = ("DEAD" if r.error == "deadline" else "ERR " if r.error
               else "DEGR" if r.degraded
               else "SUFX" if r.suffix_update else "HIT " if r.cache_hit
               else "WARM" if r.warm_started else "COLD")
        where = f" @{r.worker}" if r.worker else ""
        ds = (
            f" ds={r.downstream_s*1e3:6.1f} ms"
            if getattr(r, "downstream", None) is not None
            else ""
        )
        print(f"  q{r.query_id:02d} [{tag}] {r.result.method:3s} "
              f"k={r.result.k:3d} tlb={r.result.tlb_estimate:.4f} "
              f"wall={r.wall_s*1e3:7.1f} ms{ds}{where}")
    if args.fleet:
        svc.shutdown()
    # an expired deadline is a served answer under the SLO; any other error
    # is a failure the exit code must carry
    failed = [r for r in results if r.error not in (None, "deadline")]
    if failed:
        sys.exit(f"{len(failed)} queries failed: "
                 + "; ".join(f"q{r.query_id} {r.error}" for r in failed[:5]))

    if args.compare_sequential:
        seq_cost = cost or downstream_cost(args.downstream, args.rows)
        t0 = time.perf_counter()
        for x, m in zip(datasets, methods):
            reduce(x, m, cfg, seq_cost)
        t_seq = time.perf_counter() - t0
        print(f"sequential cold reduce(): {t_seq*1e3:.0f} ms "
              f"({args.queries/t_seq:.2f} queries/sec) -> "
              f"service speedup {t_seq/dt:.2f}x")


if __name__ == "__main__":
    main()
