"""The readers of the program's stage counters, on a known context: each
divides its counter's window difference by the answered requests, and
reads nothing where there are no requests or the program has no such
counter."""

import pytest

from run import load_reader

STATS = {"queries": 4, "submit_s": 0.1, "queue_wait_s": 0.2, "work_wait_s": 0.04,
         "validate_s": 0.06, "tlb_rounds": 6, "transform_s": 0.03, "downstream_s": 0.5}
READS = {"submit_ms": 25.0, "queue_wait_ms": 50.0, "work_wait_ms": 10.0,
         "revalidate_ms": 15.0, "tlb_rounds": 1.5, "transform_ms": 7.5,
         "knn_host_ms": (500.0 - 120.0) / 4}


def _ctx(requests=4, stats=STATS):
    return {"requests": [{"hit": True, "m": 9236, "k": 4}] * requests,
            "stats": dict(stats), "layer_ms": {"knn": 120.0, "tlb": 1.0}}


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_divides_by_the_answered_requests(name):
    assert load_reader(name)(_ctx()) == pytest.approx(READS[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_reads_nothing_without_requests_or_counter(name):
    assert load_reader(name)(_ctx(requests=0)) is None
    # a program without the stage counters (as before they were added)
    assert load_reader(name)(_ctx(stats={"queries": 4})) is None


def test_knn_host_ms_needs_the_scans_device_time():
    ctx = _ctx()
    ctx["layer_ms"] = {}
    assert load_reader("knn_host_ms")(ctx) is None


def test_a_zero_counter_is_a_reading():
    ctx = _ctx(stats=dict(STATS, tlb_rounds=0, submit_s=0.0))
    assert load_reader("tlb_rounds")(ctx) == 0.0
    assert load_reader("submit_ms")(ctx) == 0.0
