"""The readers of the mesh kNN fan-out, on a known context: device ms per
answered query from the ``knn_mesh`` layer, the roofline share at the peaks
of the n chips the counters give, and the kNN item's host ms less that
layer; and no reading where the layer or the counters are absent, as on a
program without the mesh path or in a one-chip cell."""

import pytest

from bench import costs, spec
from run import load_reader

PEAKS = spec.peaks("TPU v5 lite")
REQUESTS = [{"hit": True, "m": 16637, "k": 12}, {"hit": True, "m": 9236, "k": 4},
            {"hit": True, "m": 1370, "k": 12}, {"hit": True, "m": 16637, "k": 12}]
LEAST_S = sum(costs.least_time_s(*costs.knn_scan(r["m"], r["k"]), PEAKS) for r in REQUESTS)
MESH_NAMES = ["knn_mesh_device_ms", "knn_mesh_roofline", "knn_mesh_host_ms"]


def _ctx(shards=4, layer_ms=None, requests=REQUESTS):
    # one chip's device time is twice the least time at four chips' peaks
    mesh_ms = 2.0 * LEAST_S / shards * 1e3
    return {"requests": list(requests), "peaks": PEAKS,
            "stats": {"queries": len(requests), "downstream_s": 0.2,
                      "mesh_scans": len(requests), "mesh_shards": shards * len(requests)},
            "layer_ms": {"knn_mesh": mesh_ms, "tlb": 1.0} if layer_ms is None else layer_ms}


def test_device_ms_per_answered_query():
    ctx = _ctx()
    got = load_reader("knn_mesh_device_ms")(ctx)
    assert got == pytest.approx(ctx["layer_ms"]["knn_mesh"] / 4, rel=1e-12)


@pytest.mark.parametrize("shards", [4, 2])
def test_roofline_takes_the_mesh_width_from_the_counters(shards):
    assert load_reader("knn_mesh_roofline")(_ctx(shards)) == pytest.approx(50.0, rel=1e-12)


def test_roofline_at_four_chips_is_a_quarter_of_one_chips():
    ctx = _ctx()
    one_chip = dict(ctx, stats=dict(ctx["stats"], mesh_shards=ctx["stats"]["mesh_scans"]))
    four = load_reader("knn_mesh_roofline")(ctx)
    assert load_reader("knn_mesh_roofline")(one_chip) == pytest.approx(4 * four, rel=1e-12)


def test_host_ms_is_the_item_less_the_layer():
    ctx = _ctx()
    want = (200.0 - ctx["layer_ms"]["knn_mesh"]) / 4
    assert load_reader("knn_mesh_host_ms")(ctx) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", MESH_NAMES)
def test_nothing_without_the_mesh_layer(name):
    # a one-chip cell, or a program whose fan-out has no name of its own
    assert load_reader(name)(_ctx(layer_ms={"knn": 120.0, "tlb": 1.0})) is None


@pytest.mark.parametrize("name", MESH_NAMES)
def test_nothing_without_requests(name):
    assert load_reader(name)(_ctx(requests=[])) is None


@pytest.mark.parametrize("name", ["knn_mesh_roofline", "knn_mesh_host_ms"])
def test_nothing_without_the_counters(name):
    ctx = _ctx()
    ctx["stats"] = {"queries": 4}  # a program without the mesh counters
    assert load_reader(name)(ctx) is None


def test_no_roofline_without_a_mesh_scan():
    ctx = _ctx()
    ctx["stats"].update(mesh_scans=0, mesh_shards=0)
    assert load_reader("knn_mesh_roofline")(ctx) is None


def test_the_layer_map_names_the_mesh_knn_module_alone():
    layers = spec.layer_maps()
    assert layers["knn_mesh"] == ["jit__mesh_knn"]
    others = [p for key, prefixes in layers.items() if key != "knn_mesh" for p in prefixes]
    assert not any("jit__mesh_knn".startswith(p) for p in others)
    assert not any(p.startswith("jit__mesh_knn") for p in others)
