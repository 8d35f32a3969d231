"""A run with the timed path broken underneath reads ``correct`` false.

Each test drives a whole run of a cell on the CPU at a small size (the
harness's look for a chip is skipped: ``run_cell`` is called directly), with
one fault planted in the program for the run: an answer altered where it is
produced, half of the batch left out, or a fit that returns its state
unchanged, for every tenant or for one alone. A run on four chips has no
exchange to leave out: every cell here is on one chip. The sound run beside
them reads ``correct`` true.
"""

import numpy as np
import pytest

import run
from bench import data, spec

SEED = 2**31 + 17

SMALL_SERVE = [
    {"id": 0, "name": "A", "m": 700, "d": 64, "rank": 4},
    {"id": 1, "name": "B", "m": 1100, "d": 24, "rank": 6},
    {"id": 2, "name": "C", "m": 300, "d": 128, "rank": 5},
]


def small_cell(name: str) -> dict:
    cell = spec.cell(name)
    cell["config"] = dict(cell["config"], collections=SMALL_SERVE)
    cell["traffic"] = dict(cell["traffic"], rate_per_s=4.0, seeds_per_collection=2,
                           warmup_requests=6, checked_requests=8)
    return cell


def serve_cells():
    return [w["name"] for w in spec.benchmark()["workloads"]]


def wrap_downstream(monkeypatch, fault):
    import repro.pipeline.optimizer as opt

    real = opt.run_downstream

    def broken(name, xt, **kw):
        return fault(real, name, np.asarray(xt), kw)

    monkeypatch.setattr(opt, "run_downstream", broken)


def altered(real, name, xt, kw):
    idx = np.array(real(name, xt, **kw))
    idx[::7] = (idx[::7] + 1) % len(idx)
    return idx


def half_left_out(real, name, xt, kw):
    # neighbours searched among the first half of the rows only
    m = len(xt)
    half = xt.copy()
    half[m // 2:] = 1e6
    idx = np.array(real(name, half, **kw))
    idx[m // 2:] = 0
    return idx


@pytest.mark.parametrize("name", serve_cells())
def test_sound_serve_run_is_correct(name):
    res = run.run_cell(small_cell(name), SEED, 2.0, False)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("name", serve_cells())
@pytest.mark.parametrize("fault", [altered, half_left_out], ids=["altered", "half"])
def test_broken_knn_is_caught(monkeypatch, name, fault):
    wrap_downstream(monkeypatch, fault)
    res = run.run_cell(small_cell(name), SEED, 2.0, False)
    assert not res["correct"]
    assert res["checks"]["knn_excess"]["value"] > res["checks"]["knn_excess"]["limit"]


def unfit(monkeypatch, broken):
    """The fit returns its state before any step, the first k axes, for
    the collections ``broken(x)`` picks."""
    from repro.core.drop import PcaDropReducer

    real = PcaDropReducer.result

    def unfitted(self):
        res = real(self)
        if broken(self.x):
            res.v = np.eye(res.v.shape[0], res.v.shape[1], dtype=np.float32)
        return res

    monkeypatch.setattr(PcaDropReducer, "result", unfitted)


@pytest.mark.parametrize("name", serve_cells())
def test_unfitted_map_is_caught(monkeypatch, name):
    unfit(monkeypatch, lambda x: True)
    res = run.run_cell(small_cell(name), SEED, 2.0, False)
    assert not res["correct"]
    assert res["checks"]["tlb_worst_map_se"]["value"] > res["checks"]["tlb_worst_map_se"]["limit"]


@pytest.mark.parametrize("name", serve_cells())
def test_one_unfitted_tenant_is_caught(monkeypatch, name):
    """Only the most popular tenant's fits are broken; the other five
    tenants' maps are sound."""
    one = data.make_collection(SMALL_SERVE[0], SEED, 0)[1]
    unfit(monkeypatch, lambda x: x.shape == one.shape and np.array_equal(x[0], one[0]))
    res = run.run_cell(small_cell(name), SEED, 2.0, False)
    assert not res["correct"]
    assert res["checks"]["tlb_worst_map_se"]["value"] > res["checks"]["tlb_worst_map_se"]["limit"]
