"""The four-chip cell on four devices: a sound run reads ``correct`` true,
and a run whose mesh fan-out leaves the first dataset shard's partials out
of the merge reads false on ``knn_excess``. (The first: at these sizes the
trailing shards of a small tenant hold only padding rows.) At the
StarLightCurves shape, the mesh scan's kNN reads under the cell's
``knn_excess`` limit and the control (``bench.control``, three bfloat16
passes, which reads the same on the CPU as on the chip) over it.

``test_faults`` runs every cell on the process's one device, where a
four-device service is the one-device service. Here a subprocess forces a
four-device host platform (``XLA_FLAGS`` must precede jax's start) and
drives ``run.run_cell`` on the cell at the small sizes ``test_faults``
uses, so the scan is split 1 x 4 and merged on the host.
"""

import json
import subprocess
import sys

import pytest

from bench import spec

ROOT = spec.ROOT
MESH_CELLS = [w["name"] for w in spec.benchmark()["workloads"] if int(w["chips"]) == 4]

PROG = r'''
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, "perfbench")
sys.path.insert(0, "src")
import numpy as np
import jax
assert len(jax.devices()) == 4, jax.devices()
import run
import repro.analytics.split as split
from test_faults import SEED, small_cell

name, fault = sys.argv[1], sys.argv[2] == "1"
if fault:
    real = split.merge_knn_partials

    def one_shard_left_out(idx_parts, d2_parts):
        d2 = np.array(d2_parts)
        d2[0] = np.inf  # the first dataset shard's partials never win
        return real(idx_parts, d2)

    split.merge_knn_partials = one_shard_left_out
res = run.run_cell(small_cell(name), SEED, 2.0, False)
print(json.dumps({"correct": res["correct"], "checks": res["checks"],
                  "count": res["device"]["count"]}))
'''


def _run(name: str, fault: bool) -> dict:
    p = subprocess.run(
        [sys.executable, "-c", PROG, name, "1" if fault else "0"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(ROOT / "perfbench" / "tests")},
    )
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", MESH_CELLS)
def test_sound_mesh_run_is_correct(name):
    res = _run(name, False)
    assert res["count"] == 4 and res["correct"], res


@pytest.mark.parametrize("name", MESH_CELLS)
def test_a_shard_left_out_of_the_merge_is_caught(name):
    res = _run(name, True)
    assert not res["correct"], res
    knn = res["checks"]["knn_excess"]
    assert knn["value"] > knn["limit"], res


CONTROL = r'''
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, "perfbench")
sys.path.insert(0, "src")
import numpy as np
import jax
assert len(jax.devices()) == 4, jax.devices()
from bench import control, data, reference
from repro.analytics.split import split_pairwise_knn

seed = int(sys.argv[1])
x = data.make_collection({"id": 0, "m": 9236, "d": 1024, "rank": 4}, seed, 0)[1]
x64 = x.astype(np.float64)
mean = x64.mean(0)
v = np.linalg.svd(x64 - mean, full_matrices=False)[2][:4].T.astype(np.float32)
z32 = control.transform32(x, v, mean)
z64 = reference.reduce_rows(x, v)
rows = data.collection_rng(seed, 5, 0).choice(len(x), 4096, replace=False)
mesh = split_pairwise_knn(z32, fanout="mesh")[0]
print(json.dumps({"program": reference.knn_excess(z64, mesh, rows),
                  "control": reference.knn_excess(z64, control.knn_high(z32, rows), rows)}))
'''


@pytest.mark.parametrize("name", MESH_CELLS)
@pytest.mark.parametrize("seed", [1, 2])
def test_control_fails_and_mesh_program_passes(name, seed):
    config = spec.cell(name)["config"]["name"]
    limit = spec.load_json(spec.BENCH_DIR / "limits" / f"{config}.json")["knn_excess"]
    p = subprocess.run(
        [sys.executable, "-c", CONTROL, str(seed)],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["program"] <= limit < res["control"], res

