"""``DropService(devices=n)``: the four-device scheduler from the normal
entry point, and the one-device service unchanged.

In-process: ``DropService()`` and ``DropService(devices=1)`` build the plain
one-device service, whose mesh counters stay 0 after a served kNN query; and
each task's mesh fan-out compiles as a program of its own name
(``jit__mesh_<task>``), the name a device trace's layer map reads.

Subprocess (``XLA_FLAGS`` must precede jax's start): on a forced four-device
host platform, ``DropService(devices=4)`` behind an ``IngestFrontend`` is
the sharded scheduler with the mesh fan-out and four drain threads. Three
tenants are served twice each (cold, then a revalidated hit); the first has
more rows than one 1024-row tile, so its scan spans two dataset shards and
the host merge picks winners from both. Every answer is held to three
things: the kNN indices equal a float64 numpy brute force over
``result.transform(x)``; the map is bit-identical to a one-device
``DropService``'s; and ``mesh_scans`` counts every answer, ``mesh_shards``
four dataset shards each. One more answer, traced, shows the spans
``analytics.mesh_scan`` and ``analytics.merge`` inside its
``drop.item.downstream``.
"""

import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import DropConfig
from repro.data import sinusoid_mixture
from repro.serve_drop import DropService, IngestFrontend, ShardedDropService

CFG = DropConfig(target_tlb=0.95, seed=0, min_iterations=99)


def test_one_device_service_is_the_plain_service():
    x = sinusoid_mixture(300, 32, rank=3, seed=1)[0]
    for svc in (DropService(), DropService(devices=1)):
        assert type(svc) is DropService
        assert IngestFrontend(svc).drain_width == 1
        svc.submit(x, CFG, downstream="knn", execute_downstream=True)
        (res,) = svc.run()
        assert res.error is None and len(res.downstream) == len(x)
        assert svc.stats.downstream_runs == 1
        assert svc.stats.mesh_scans == 0 and svc.stats.mesh_shards == 0


def test_devices_beyond_the_host_are_clamped():
    many = len(jax.devices()) + 3
    want = ShardedDropService if len(jax.devices()) > 1 else DropService
    assert type(DropService(devices=many)) is want


@pytest.mark.parametrize("task", ["knn", "dbscan", "kde"])
def test_each_mesh_fan_out_is_a_program_of_its_own_name(task):
    from repro.analytics.split import _mesh_fn

    fn = _mesh_fn(tuple(jax.devices()[:1]), 1, 1, task, 64, 64)
    x = np.zeros((128, 4), np.float32)
    text = fn.lower(x, x, np.int32(100), np.float32(0.5)).as_text()
    assert f"module @jit__mesh_{task} " in text


PROG = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import glob
import json
import sys
import numpy as np
import jax
from repro.core import DropConfig
from repro.data import sinusoid_mixture
from repro.serve_drop import DropService, IngestFrontend, ShardedDropService

assert len(jax.devices()) == 4, jax.devices()
# the whole (short) schedule: Eq. 2 stops on wall time, parity needs the
# same iterations on both services
CFG = DropConfig(target_tlb=0.95, seed=0, min_iterations=99, schedule=(0.25, 0.5))
# the scan's 1024-row tiles put the first tenant's rows on two shards, so
# its answers go through a cross-shard merge
tenants = [
    sinusoid_mixture(1100, 24, rank=4, seed=21)[0],
    sinusoid_mixture(600, 96, rank=5, seed=22)[0],
    sinusoid_mixture(300, 64, rank=3, seed=23)[0],
]


def serve(svc):
    """Every tenant cold, then every tenant again (a revalidated hit)."""
    out = []
    with IngestFrontend(svc) as fe:
        threads = len(fe._threads)
        for _ in range(2):
            qids = [fe.submit(x, CFG, downstream="knn", execute_downstream=True)
                    for x in tenants]
            out.extend(fe.result(q, 600.0) for q in qids)
    return out, threads


def brute_force(x, result):
    z = np.asarray(result.transform(x), dtype=np.float64)
    d2 = ((z[:, None, :] - z[None, :, :]) ** 2).sum(-1)
    np.fill_diagonal(d2, np.inf)
    return d2.argmin(1)


def host_spans(trace_dir):
    """(thread line, name, start, end, args) of the host plane's spans."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    (plane,) = [p for p in jax.profiler.ProfileData.from_file(path).planes
                if p.name == "/host:CPU"]
    return [(i, e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for i, ln in enumerate(plane.lines) for e in ln.events
            if e.name.startswith(("drop.item.downstream", "analytics."))]


mesh = DropService(devices=4, max_inflight=4, cache_entries=16)
got, threads = serve(mesh)
ref, _ = serve(DropService(max_inflight=4, cache_entries=16))
x_of = tenants + tenants
with IngestFrontend(mesh) as fe:  # one more hit of the first tenant, traced
    jax.profiler.start_trace(sys.argv[1])
    try:
        fe.result(fe.submit(tenants[0], CFG, downstream="knn", execute_downstream=True), 600.0)
    finally:
        jax.profiler.stop_trace()
spans = host_spans(sys.argv[1])
(down,) = [s for s in spans if s[1] == "drop.item.downstream"]
print(json.dumps({
    "sharded": type(mesh) is ShardedDropService,
    "devices": len(mesh.devices),
    "fanout": mesh.analytics_fanout,
    "threads": threads,
    "errors": [r.error for r in got + ref if r.error is not None],
    "hits": [bool(r.cache_hit) for r in got],
    "knn_exact": [bool(np.array_equal(np.asarray(r.downstream), brute_force(x, r.result)))
                  for r, x in zip(got, x_of)],
    "knn_as_one_device": [bool(np.array_equal(np.asarray(a.downstream), np.asarray(b.downstream)))
                          for a, b in zip(got, ref)],
    "maps_identical": [bool(a.result.k == b.result.k
                            and np.array_equal(a.result.v, b.result.v)
                            and np.array_equal(a.result.mean, b.result.mean))
                       for a, b in zip(got, ref)],
    "second_shard_wins": int(np.sum(np.asarray(got[0].downstream) >= 1024)),
    "answered": 1 + sum(1 for r in got if r.error is None and r.downstream is not None),
    "spans": [[name, stats.get("rows"), stats.get("shards"),
               line == down[0] and down[2] <= s and e <= down[3]]
              for line, name, s, e, stats in spans if name != "drop.item.downstream"],
    "mesh_scans": mesh.stats.mesh_scans,
    "mesh_shards": mesh.stats.mesh_shards,
    "device_iterations": mesh.stats.device_iterations,
}))
'''


def test_four_device_service_serves_exact_knn_over_the_mesh(tmp_path):
    out = subprocess.run(
        [sys.executable, "-c", PROG, str(tmp_path)],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd=__file__.rsplit("/tests/", 1)[0],
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["sharded"] and res["devices"] == 4 and res["fanout"] == "mesh", res
    assert res["threads"] == 4, res
    assert res["errors"] == [], res
    assert res["hits"] == [False] * 3 + [True] * 3, res
    assert all(res["knn_exact"]), res
    assert all(res["knn_as_one_device"]), res
    assert all(res["maps_identical"]), res
    assert res["second_shard_wins"] > 0, res
    assert res["answered"] == 7  # and the traced hit
    # the fan-out's dispatch and gather, then the merge, inside the item
    assert res["spans"] == [["analytics.mesh_scan", 1100, 4, True],
                            ["analytics.merge", 1100, 4, True]], res
    assert res["mesh_scans"] == res["answered"], res
    assert res["mesh_shards"] == 4 * res["mesh_scans"], res
