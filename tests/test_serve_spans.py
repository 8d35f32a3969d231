"""Stage counters and profiler spans of the serving path: each counter is
booked where its stage runs, and a profiler trace holds the program's
spans on ``/host:CPU``, nested by thread and tagged with their query id."""

import glob
import time

import jax

from repro.core import DropConfig
from repro.core.cost import zero_cost
from repro.core.types import transform_blocks
from repro.core.tlb import TLBEstimator
from repro.data import sinusoid_mixture
from repro.serve_drop import DropService, IngestFrontend
from repro.serve_drop import service as service_mod

CFG = DropConfig(target_tlb=0.95, seed=0)
STAGES = ("submit_s", "queue_wait_s", "work_wait_s", "validate_s",
          "tlb_rounds", "transform_s", "downstream_s")


def _data(rows=300, dim=32):
    return sinusoid_mixture(rows, dim, rank=4, seed=10)[0]


def _drain(svc):
    while svc.poll():
        pass


def _serve(fe, x):
    qid = fe.submit(x, CFG, zero_cost(), downstream="knn", execute_downstream=True)
    _drain(fe.service)
    res = fe.result(qid, timeout=0)
    assert res.error is None
    return qid, res


def test_stage_counters_are_booked_where_the_work_happens(monkeypatch):
    device_calls = []

    class CountingEstimator(TLBEstimator):
        """Counts the TLB table's device calls, beside the estimator's own
        count."""

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            fn = self._fn
            self._fn = lambda *args: device_calls.append(1) or fn(*args)

    # the service's own name: revalidations build it, cold fits do not
    monkeypatch.setattr(service_mod, "TLBEstimator", CountingEstimator)
    svc = DropService()
    fe = IngestFrontend(svc)  # drained by hand, below
    x = _data()
    _, cold = _serve(fe, x)
    assert not cold.cache_hit
    _, hit = _serve(fe, x)
    assert hit.cache_hit
    st = svc.stats
    for name in STAGES:
        assert getattr(st, name) > 0, name
    assert st.downstream_s >= st.transform_s
    assert st.downstream_s == cold.downstream_s + hit.downstream_s
    assert st.tlb_rounds == len(device_calls) >= 1

    # a second query for the tenant while the first's work is in flight is
    # deferred; its queue wait runs until the pass that routes it
    before = st.queue_wait_s
    q1 = fe.submit(x, CFG, zero_cost(), downstream="knn", execute_downstream=True)
    q2 = fe.submit(x, CFG, zero_cost(), downstream="knn", execute_downstream=True)
    svc.poll()  # routes q1 to its revalidation and runs it; q2 is deferred
    assert [q.query_id for q in svc._queue] == [q2]
    time.sleep(0.3)
    svc.poll()  # q2 deferred again: q1's analytics item is still queued
    assert [q.query_id for q in svc._queue] == [q2]
    _drain(svc)
    assert fe.result(q1, timeout=0).cache_hit and fe.result(q2, timeout=0).cache_hit
    assert st.queue_wait_s - before >= 0.3


def _host_events(trace_dir):
    """(line, event name, start ns, end ns, stats) of every event on the
    ``/host:CPU`` plane; a line (one host thread) is its index there."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for i, ln in enumerate(plane.lines):
                for e in ln.events:
                    out.append((i, e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_spans_land_on_the_host_plane_with_their_query(tmp_path):
    svc = DropService()
    x = _data()
    with IngestFrontend(svc) as fe:
        fe.result(fe.submit(x, CFG, zero_cost(), downstream="knn",
                            execute_downstream=True), timeout=120)
        jax.profiler.start_trace(str(tmp_path))
        try:
            qid = fe.submit(x, CFG, zero_cost(), downstream="knn",
                            execute_downstream=True)
            assert fe.result(qid, timeout=120).cache_hit
        finally:
            jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    spans = {}
    for line, name, s, e, stats in events:
        if name.startswith(("drop.", "tlb.")):
            spans.setdefault(name, []).append((line, s, e, stats))
    want = ("drop.submit", "drop.item.validate", "tlb.extend",
            "drop.item.downstream", "drop.transform")
    assert set(want) <= set(spans), sorted(spans)
    for name in ("drop.submit", "drop.item.validate", "drop.item.downstream",
                 "drop.transform"):
        assert [st.get("qid") for *_, st in spans[name]] == [qid], name
    assert spans["drop.submit"][0][3]["rows"] == x.shape[0]

    def inside(child, parent):
        (cl, cs, ce, _), (pl, ps, pe, _) = child, parent
        return cl == pl and ps <= cs and ce <= pe

    (down,) = spans["drop.item.downstream"]
    (validate,) = spans["drop.item.validate"]
    assert inside(spans["drop.transform"][0], down)
    assert all(inside(t, validate) for t in spans["tlb.extend"])
    assert all(st["pairs"] > 0 for *_, st in spans["tlb.extend"])
    # the submitting thread and the drain thread are different lines
    assert spans["drop.submit"][0][0] != validate[0]


def test_blocked_transforms_are_counted_and_tagged(tmp_path):
    """A StarLightCurves-sized served query (9236 x 1024) is projected in
    row blocks: one ``transform_blocked`` and ``blocks`` > 1 on its
    ``drop.transform`` span; a 1370 x 96 one takes one call and no count."""
    big = sinusoid_mixture(9236, 1024, rank=4, seed=11)[0]
    small = sinusoid_mixture(1370, 96, rank=4, seed=12)[0]
    svc = DropService()
    st = svc.stats

    def serve(fe, x):
        qid = fe.submit(x, CFG, zero_cost(), downstream="knn",
                        execute_downstream=True)
        res = fe.result(qid, timeout=300)
        assert res.error is None and res.downstream is not None
        return qid

    with IngestFrontend(svc) as fe:
        serve(fe, small)  # cold fits and compiles, untraced
        assert st.transform_blocked == 0
        serve(fe, big)
        assert st.transform_blocked == 1
        jax.profiler.start_trace(str(tmp_path))
        try:
            q_big = serve(fe, big)
            assert st.transform_blocked == 2
            q_small = serve(fe, small)
            assert st.transform_blocked == 2
        finally:
            jax.profiler.stop_trace()
    blocks = {stats["qid"]: stats["blocks"]
              for _, name, _, _, stats in _host_events(tmp_path)
              if name == "drop.transform"}
    assert blocks == {q_big: transform_blocks(big.shape), q_small: 1}
    assert blocks[q_big] > 1
