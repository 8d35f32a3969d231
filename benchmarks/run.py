"""Benchmark runner: one module per paper table/figure + framework rooflines.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig10]

Prints ``name,us_per_call,derived`` CSV per row.
"""

from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

MODULES = [
    "bench_table6_dims",      # Table 6 / Fig 1: dims for target TLB
    "bench_fig2_runtime",     # Fig 2: PAA/FFT/PCA runtime
    "bench_fig3_spectrum",    # Fig 3: spectra falloff
    "bench_fig5_sampling",    # Fig 5 / Table 5: sample proportions
    "bench_fig6_fig7_drop",   # Figs 6+7: DROP vs SVD/Halko/Oracle
    "bench_fig8_reuse",       # Fig 8: work reuse
    "bench_fig9_scalability", # Fig 9: size-independence
    "bench_fig10_knn",        # Fig 10 + Tables 2/3/4: e2e k-NN
    "bench_fig12_dbscan",     # Fig 12: e2e DBSCAN
    "bench_drop_serve",       # §5 reuse at the service layer: qps + cache
                              # (--full adds the FleetSupervisor process-
                              # worker scaling legs, 1 vs 2 workers)
    "bench_e2e_workload",     # §4.4 via WorkloadOptimizer: DR+analytics e2e
    "bench_incremental_stream",  # append-only: suffix update vs reval/refit
    "bench_delta_stream",     # pub/sub deltas vs snapshot re-serve per append
    "bench_pairwise_analytics",  # fused engine vs legacy host loops
    "bench_slo_serve",        # trace-driven SLO/load matrix: tail latency,
                              # rejections, degraded serves, deadline misses
                              # (--full adds the fleet chaos leg)
    "bench_recovery",         # durability: crash/recover bit-convergence +
                              # warm-restart vs cold-start TTFS (asserted)

    "bench_mnist_like",       # §4.5: beyond time series
    "bench_kernels",          # kernel layer
    "bench_roofline",         # framework §Roofline table (from dry-run)
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--only", default=None, help="substring filter on module")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = []
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            for row in mod.run(full=args.full):
                print(row.csv(), flush=True)
            print(f"# {mod_name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001
            failures.append(mod_name)
            print(f"# {mod_name} FAILED:", flush=True)
            traceback.print_exc()
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
