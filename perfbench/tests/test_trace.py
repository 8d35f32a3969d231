"""The trace reduction, on a trace recorded on the chip.

``data/zipf_window.xplane.pb.gz`` is the profiler trace of a 10-second
window of ``ucr-serve.zipf`` at 4 queries/s on one TPU v5e (gzip of the
``.xplane.pb`` the profiler wrote): 40 queries, 3 cold fits. It holds the
fused kNN scans, the fits, the TLB tables and the host threads that drove
them. The numbers asserted are the ones read from it when it was recorded.
"""

from pathlib import Path

import pytest

from bench import spec, trace

SMALL = Path(__file__).parent / "data" / "zipf_window.xplane.pb.gz"


def test_union_length():
    assert trace.union_length([(0, 2), (1, 3), (5, 6)]) == (4, [(0, 3), (5, 6)])
    assert trace.union_length([]) == (0, [])
    assert trace.module_name("jit__fused_scan(12345)") == "jit__fused_scan"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_trace(str(SMALL), spec.layer_maps())


def test_device_time_adds_up(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] <= reduced["span_s"]
    assert sum(reduced["module_s"].values()) >= reduced["busy_s"] * 0.9
    # every layer is the sum of its own modules, nothing else
    maps = spec.layer_maps()
    for key, ms in reduced["layer_ms"].items():
        own = sum(s for m, s in reduced["module_s"].items()
                  if any(m.startswith(p) for p in maps[key]))
        assert abs(own * 1e3 - ms) < 1e-6 * max(ms, 1.0)


def test_the_recorded_readings(reduced):
    assert reduced["busy_s"] == pytest.approx(1.132772056, rel=1e-9)
    assert reduced["layer_ms"] == pytest.approx(
        {"knn": 1130.807125, "tlb": 0.438978}, rel=1e-9)


def test_breakdown_shape(reduced):
    b = trace.breakdown(reduced)
    assert set(b) == {"device_ops", "idle_gaps"}
    for rows in b.values():
        assert len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)
    assert b["device_ops"][0][0] == "jit__fused_scan"


def test_no_device_no_reading(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no /device:TPU plane"):
        trace.reduce_trace(trace.find_xplane(str(tmp_path)), spec.layer_maps())
