"""The correctness check's control fails it; the program passes it.

At a size a test run holds on the CPU: one StarLightCurves-shaped tenant
(9236 x 1024, rank 4) reduced by its exact PCA map at the k DROP serves
there. The program's kNN (its fused scan, float32 at full precision) reads
under the limit of ``knn_excess``; the control (``bench.control``: the same
brute force at three bfloat16 passes) reads over it. On the chip the
readings behind the limit are taken by ``perfbench/readings.py``.
"""

import numpy as np
import pytest

from bench import control, data, reference, spec

LIMITS = spec.load_json(spec.BENCH_DIR / "limits" / "ucr-serve.json")


@pytest.mark.parametrize("seed", [1, 2])
def test_control_fails_and_program_passes(seed):
    from repro.analytics.pairwise import pairwise_knn

    x = data.make_collection({"id": 0, "m": 9236, "d": 1024, "rank": 4}, seed, 0)[1]
    x64 = x.astype(np.float64)
    mean = x64.mean(0)
    v = np.linalg.svd(x64 - mean, full_matrices=False)[2][:4].T.astype(np.float32)
    z32 = control.transform32(x, v, mean)
    z64 = reference.reduce_rows(x, v)
    rows = data.collection_rng(seed, 5, 0).choice(len(x), 4096, replace=False)
    program = reference.knn_excess(z64, pairwise_knn(z32)[0], rows)
    ctrl = reference.knn_excess(z64, control.knn_high(z32, rows), rows)
    assert program <= LIMITS["knn_excess"] < ctrl, (program, ctrl)
