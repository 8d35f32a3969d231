"""Process-worker serving fleet: protocol units, supervised round trips,
crash/restart fault handling, chaos injection, and measured placement.

Every blocking wait here carries a timeout — the whole point of the
supervisor is that a dead worker can never hang a client, so a hang IS
the failure mode under test."""

import io
import os
import pickle
import signal
import time

import numpy as np
import pytest

from repro.core import DropConfig
from repro.core.cost import CostModel, knn_cost
from repro.data import sinusoid_mixture
from repro.serve_drop import DropService, FleetSupervisor, IngestFrontend
from repro.serve_drop.fleet import (
    _cost_from_spec,
    _cost_spec,
    _recv_frame,
    _send_frame,
)

CFG = DropConfig(target_tlb=0.9, seed=0)


def _datasets(n, rows=96, dim=12):
    return [
        sinusoid_mixture(rows, dim, rank=3 + i, seed=10 + i)[0]
        for i in range(n)
    ]


def _wait(predicate, timeout_s=30.0, what="condition"):
    deadline = time.perf_counter() + timeout_s
    while not predicate():
        if time.perf_counter() > deadline:
            raise AssertionError(f"timed out waiting for {what}")
        time.sleep(0.02)


# ------------------------------------------------------------ pure units


def test_frame_round_trip():
    buf = io.BytesIO()
    msgs = [
        {"t": "q", "x": np.arange(6, dtype=np.float32).reshape(2, 3)},
        {"t": "hb"},
        {"t": "pong", "blob": b"\0" * 1000},
    ]
    for m in msgs:
        _send_frame(buf, m)
    buf.seek(0)
    out = [_recv_frame(buf) for _ in msgs]
    assert out[1] == {"t": "hb"}
    np.testing.assert_array_equal(out[0]["x"], msgs[0]["x"])
    assert out[2]["blob"] == msgs[2]["blob"]
    assert _recv_frame(buf) is None  # EOF, not an exception


def test_frame_truncation_is_eof():
    buf = io.BytesIO()
    _send_frame(buf, {"t": "q", "payload": b"\0" * 500})
    data = buf.getvalue()
    assert _recv_frame(io.BytesIO(data[: len(data) - 7])) is None


def test_cost_spec_named_and_rejected():
    assert _cost_spec(None) is None
    assert _cost_spec(knn_cost(64)) == "knn"
    rebuilt = _cost_from_spec("knn", 64)
    assert rebuilt.name == "knn"
    # an anonymous closure cannot cross the process boundary
    custom = CostModel(name="custom", fn=lambda k: 0.0)
    with pytest.raises(ValueError, match="downstream"):
        _cost_spec(custom)
    # but a genuinely picklable object rides along as-is
    kind, obj = _cost_spec((1, 2, 3))
    assert kind == "pickled"
    assert _cost_from_spec((kind, obj), 10) == (1, 2, 3)
    assert pickle.dumps(obj)


# ------------------------------------------------------- supervised serve


@pytest.mark.parametrize(
    "workers,chips,holds,match",
    [
        (2, 1, False, "2 worker processes but 1 TPU chip"),
        (1, 1, True, "already holds the TPU"),
    ],
)
def test_fleet_refuses_chips_it_cannot_own(monkeypatch, workers, chips, holds, match):
    """On a TPU host, start() raises before spawning anything when the
    caller holds the chip or workers outnumber chips — never a hang."""
    from repro.serve_drop import fleet as fleet_mod

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(fleet_mod, "_visible_tpu_chips", lambda: chips)
    monkeypatch.setattr(fleet_mod, "_process_holds_tpu", lambda: holds)
    sup = FleetSupervisor(workers=workers, profile=False)
    with pytest.raises(RuntimeError, match=match):
        sup.start()
    assert all(w.proc is None for w in sup._workers)


def test_fleet_round_trip_matches_inprocess():
    """Two workers serve three tenants; per-query k matches the in-process
    service (same scheduler inside every worker)."""
    datasets = _datasets(3)

    svc = DropService()
    for x in datasets:
        svc.submit(x, CFG, downstream="knn")
    expect = {r.query_id: r.result.k for r in svc.run()}

    with FleetSupervisor(workers=2, profile=False) as fleet:
        qids = [fleet.submit(x, CFG, downstream="knn") for x in datasets]
        results = fleet.run(timeout=180)
    assert [r.query_id for r in results] == sorted(qids)
    assert all(r.error is None for r in results)
    assert [r.result.k for r in results] == [expect[q] for q in sorted(expect)]
    assert {r.worker for r in results} <= {"worker-0", "worker-1"}
    assert fleet.stats.queries == 3
    assert fleet.stats.worker_deaths == 0


def test_fleet_worker_cache_serves_repeats():
    x = _datasets(1)[0]
    with FleetSupervisor(workers=1, profile=False) as fleet:
        first = fleet.result(fleet.submit(x, CFG), timeout=120)
        second = fleet.result(fleet.submit(x, CFG), timeout=120)
    assert first.error is None and second.error is None
    assert not first.cache_hit
    assert second.cache_hit  # the worker's own BasisReuseCache hit
    assert second.worker == first.worker  # sticky tenant home
    assert fleet.stats.cache_hits == 1


def test_fleet_kill9_requeues_restarts_and_completes():
    """The acceptance scenario: kill -9 a worker mid-serve. Its in-flight
    queries must finish on a survivor (retried, not errored), the slot must
    restart within the RestartPolicy bounds, and nothing may hang."""
    datasets = _datasets(4)
    with FleetSupervisor(
        workers=2,
        profile=False,
        placement="rr",
        worker_slowdowns=[2.0, 0.0],  # holds worker-0's queries in flight
    ) as fleet:
        qids = [fleet.submit(x, CFG) for x in datasets]
        w0 = fleet._workers[0]
        _wait(lambda: w0.assigned, what="worker-0 to hold in-flight work")
        time.sleep(0.3)  # let it enter its slowdown sleep
        os.kill(w0.proc.pid, signal.SIGKILL)

        results = {r.query_id: r for r in fleet.run(timeout=180)}
        assert sorted(results) == sorted(qids)
        assert all(r.error is None for r in results.values())
        assert any(r.retries > 0 for r in results.values())
        assert fleet.stats.worker_deaths == 1
        assert fleet.stats.requeued_queries >= 1

        # the slot comes back under the restart policy...
        _wait(
            lambda: fleet.stats.worker_restarts >= 1
            and fleet._workers[0].state == "ready",
            what="worker-0 restart",
        )
        # ...and serves again
        res = fleet.result(fleet.submit(_datasets(1)[0], CFG), timeout=120)
        assert res.error is None


def test_fleet_retry_exhaustion_errors_instead_of_hanging():
    """With no retry budget and no survivor to absorb the work, the killed
    worker's query must FINISH — with ServeResult.error — not hang."""
    x = _datasets(1)[0]
    with FleetSupervisor(
        workers=1,
        profile=False,
        max_query_retries=0,
        worker_slowdowns=[5.0],
    ) as fleet:
        qid = fleet.submit(x, CFG)
        w0 = fleet._workers[0]
        _wait(lambda: w0.assigned, what="query in flight")
        time.sleep(0.2)
        os.kill(w0.proc.pid, signal.SIGKILL)
        res = fleet.result(qid, timeout=60)
    assert res.error is not None
    assert "worker-0" in res.error and "retries exhausted" in res.error
    assert res.retries == 1
    assert res.result.k == 0 and not res.result.satisfied
    assert fleet.stats.failures == 1


def test_fleet_chaos_injected_failures_all_queries_complete():
    """FailureInjector-driven crashes (os._exit inside the worker) walk the
    same death->requeue->restart ladder as a real kill; every query still
    gets a result."""
    datasets = _datasets(4)
    with FleetSupervisor(
        workers=2,
        profile=False,
        placement="rr",
        failure_prob=0.6,
        failure_seed=0,
        restart_policy=None,  # default: 3 restarts, 50ms base backoff
    ) as fleet:
        qids = [fleet.submit(x, CFG) for x in datasets]
        results = {r.query_id: r for r in fleet.run(timeout=240)}
    assert sorted(results) == sorted(qids)  # nothing lost, nothing hung
    assert fleet.stats.worker_deaths >= 1  # p=0.6 x 4 queries: certain
    assert fleet.stats.worker_restarts >= 1
    # queries either survived a retry or were errored out by exhaustion —
    # both count as "finished"; a hang would have tripped the run timeout
    assert all(
        (r.error is None) or ("retries exhausted" in r.error)
        for r in results.values()
    )


# ------------------------------------------------------------- placement


def test_fleet_rebalance_moves_tenant_off_congested_slow_worker():
    """Measured-cost placement: a tenant whose home worker is slow (and
    holding a queue) moves to the faster idle worker; the supervisor's
    speed estimate for the slow worker degrades from observed serve
    times."""
    x = _datasets(1)[0]
    with FleetSupervisor(
        workers=2,
        profile=False,  # equal priors: placement starts index-tied
        placement="cost",
        worker_slowdowns=[1.0, 0.0],
    ) as fleet:
        # burst of one tenant: q1 homes on worker-0 (index tiebreak); with
        # q1 still queued there, worker-1 is decisively cheaper for q2
        q1 = fleet.submit(x, CFG)
        q2 = fleet.submit(x, CFG)
        r1 = fleet.result(q1, timeout=120)
        r2 = fleet.result(q2, timeout=120)
        assert fleet.stats.rebalances >= 1
        assert r1.worker == "worker-0"
        assert r2.worker == "worker-1"
        # home moved: later queries stay on the fast worker
        r3 = fleet.result(fleet.submit(x, CFG), timeout=120)
        assert r3.worker == "worker-1"
        speeds = fleet.worker_speeds()
        assert speeds["worker-0"] < speeds["worker-1"]
    assert r1.error is None and r2.error is None and r3.error is None


def test_fleet_reprofile_recovers_degraded_link_placement():
    """Stale link profiles: worker-0's link degrades right AFTER its
    startup probe (the echo delay kicks in once the probe's pings are
    spent), so the startup alpha stays optimistically tiny. The age-out
    reprofile re-fits the link off the hot path; with the refreshed
    alpha ~ delay/2, the measured-cost placement moves the tenant to the
    healthy worker. The serve also rides the downstream-execution
    protocol (``xds``) across the worker pipe."""
    from repro.serve_drop.cache import dataset_fingerprint

    x = _datasets(1)[0]
    with FleetSupervisor(
        workers=2,
        reprofile_interval_s=0.3,
        reprofile_after_serves=0,  # isolate the time-based age-out
        worker_link_delays=[0.25],  # worker-0 only, >> any serve cost
    ) as fleet:
        r1 = fleet.result(fleet.submit(x, CFG), timeout=120)
        assert r1.error is None
        _wait(
            lambda: fleet.link_profiles()["worker-0"].alpha_s > 0.05,
            timeout_s=60.0,
            what="reprofile to pick up worker-0's degraded RTT",
        )
        assert fleet.stats.reprofiles >= 1
        # make the cost comparison deterministic: home the tenant on the
        # degraded worker with a known serve estimate and unit speeds —
        # cost_0 ~ 0.125 + 0.05 vs cost_1 ~ 0.05 clears the 0.7 margin
        fp = dataset_fingerprint(np.ascontiguousarray(x, dtype=np.float32))
        with fleet._lock:
            fleet._tenant_home[fp] = 0
            fleet._tenant_ref_s[fp] = 0.05
            for w in fleet._workers:
                w.speed = 1.0
        r2 = fleet.result(
            fleet.submit(x, CFG, downstream="knn", execute_downstream=True),
            timeout=120,
        )
        assert r2.error is None
        assert r2.worker == "worker-1"
        assert fleet.stats.rebalances >= 1
        assert r2.downstream is not None  # xds crossed the pipe


# ---------------------------------------------------------- ingest bridge


def test_ingest_frontend_over_fleet():
    """The async front-end treats the supervisor as just another service:
    submit from the client thread, block on result, close() drains."""
    datasets = _datasets(2)
    fleet = FleetSupervisor(workers=2, profile=False)
    with fleet, IngestFrontend(fleet, queue_capacity=8) as fe:
        qids = [fe.submit(x, CFG) for x in datasets]
        results = [fe.result(q, timeout=120) for q in qids]
    assert all(r.error is None for r in results)
    assert all(r.worker in ("worker-0", "worker-1") for r in results)


def test_fleet_subscription_round_trip():
    """Pub/sub across the process boundary: subscribe homes the tenant on
    a worker, deltas stream back through the supervisor read loop with
    supervisor-stamped contiguous seq, and unsubscribe delivers the
    terminal closed — the same protocol the in-process service serves
    (test_delta_serve.py pins its parity)."""
    from repro.serve_drop import (
        SubscribeQuery,
        SubscriberState,
        SubscriptionClosed,
    )

    x = sinusoid_mixture(200, 16, rank=3, seed=4)[0]
    client = SubscriberState()
    with FleetSupervisor(workers=1, profile=False) as fleet:
        sid = fleet.subscribe(SubscribeQuery(x=x[:150], cfg=CFG, eps=1.0))

        def next_delta(timeout_s=120.0):
            out = []
            _wait(lambda: out.extend(fleet.poll_deltas(sid, max_n=1)) or out,
                  timeout_s, "delta")
            return out[0]

        boot = next_delta()
        client.apply(boot)
        assert boot["kind"] == "rollback" and boot["reason"] == "subscribe"
        assert client.rows.shape[0] == 150
        fleet.append(sid, x[150:])
        d = next_delta()
        client.apply(d)
        assert d["kind"] in ("append", "rollback")
        assert client.rows.shape[0] == 200
        np.testing.assert_allclose(
            client.rows, client.basis.transform(x), atol=1e-4
        )
        assert fleet.stats.subscriptions == 1
        fleet.unsubscribe(sid)
        d = next_delta()
        client.apply(d)
        assert d["kind"] == "closed" and client.closed
        assert sid not in fleet.live_subscriptions()
        with pytest.raises(SubscriptionClosed):
            fleet.append(sid, x[:8])


def test_fleet_worker_death_rehomes_subscriptions():
    """kill -9 of the home worker must NOT terminate its live
    subscriptions: the supervisor re-homes them on a survivor, and the
    client converges through an ordinary ``ROLLBACK(reason="rehome")``
    restate — contiguous supervisor-stamped seq, no terminal ``closed``,
    no re-subscribe. Appends keep flowing on the new home and the client
    state stays bit-converged with the grown dataset."""
    from repro.serve_drop import SubscribeQuery, SubscriberState

    x = sinusoid_mixture(200, 16, rank=3, seed=4)[0]
    client = SubscriberState()
    with FleetSupervisor(workers=2, profile=False) as fleet:
        sid = fleet.subscribe(SubscribeQuery(x=x[:150], cfg=CFG, eps=1.0))
        out = []
        _wait(lambda: out.extend(fleet.poll_deltas(sid)) or out,
              timeout_s=120.0, what="bootstrap delta")
        for d in out:
            client.apply(d)  # validates seq contiguity
        assert out[0]["kind"] == "rollback"
        assert out[0]["reason"] == "subscribe"
        home = fleet._subs[sid].worker
        os.kill(fleet._workers[home].proc.pid, signal.SIGKILL)

        rehomed = []

        def saw_rehome():
            rehomed.extend(fleet.poll_deltas(sid))
            return any(d.get("reason") == "rehome" for d in rehomed)

        _wait(saw_rehome, timeout_s=120.0,
              what="rehome rollback from a survivor")
        assert all(d["kind"] != "closed" for d in rehomed)
        for d in rehomed:
            client.apply(d)
        assert not client.closed
        assert fleet._subs[sid].worker != home
        assert fleet.stats.sub_rehomes == 1
        assert sid in fleet.live_subscriptions()

        fleet.append(sid, x[150:])

        def grown():
            for d in fleet.poll_deltas(sid):
                client.apply(d)
            return client.rows is not None and client.rows.shape[0] == 200

        _wait(grown, timeout_s=120.0, what="append after re-home")
        np.testing.assert_allclose(
            client.rows, client.basis.transform(x), atol=1e-4
        )
        # the supervisor itself stays healthy: the dead slot restarts and
        # the fleet keeps serving plain queries
        res = fleet.result(fleet.submit(_datasets(1)[0], CFG), timeout=120)
        assert res.error is None


def test_fleet_subscription_closes_only_when_fleet_lost():
    """Re-homing needs somewhere to go: with no restart budget and no
    survivor the subscription must still terminate deterministically —
    an error-carrying ``closed`` delta, never a hang."""
    from repro.fault.faults import RestartPolicy
    from repro.serve_drop import SubscribeQuery, SubscriptionClosed

    x = sinusoid_mixture(160, 16, rank=3, seed=4)[0]
    with FleetSupervisor(
        workers=1,
        profile=False,
        restart_policy=RestartPolicy(max_restarts=0),
    ) as fleet:
        sid = fleet.subscribe(SubscribeQuery(x=x, cfg=CFG, eps=1.0))
        out = []
        _wait(lambda: out.extend(fleet.poll_deltas(sid)) or out,
              timeout_s=120.0, what="bootstrap delta")
        os.kill(fleet._workers[0].proc.pid, signal.SIGKILL)
        term = []
        _wait(lambda: term.extend(fleet.poll_deltas(sid)) or term,
              timeout_s=120.0, what="terminal delta after fleet loss")
        assert term[-1]["kind"] == "closed"
        assert term[-1]["error"]  # the reason travels to the client
        assert sid not in fleet.live_subscriptions()
        with pytest.raises(SubscriptionClosed):
            fleet.append(sid, x[:8])


# ----------------------------------------------------- overload resilience


def test_fleet_circuit_breaker_opens_and_recovers():
    """Repeated query failures on a worker trip its circuit breaker:
    placement stops (new queries park in ``_pending``), the cooldown
    admits a single half-open probe, and a clean serve closes the breaker
    again."""
    x = _datasets(1)[0]
    with FleetSupervisor(
        workers=1,
        profile=False,
        breaker_threshold=2,
        breaker_cooldown_s=0.3,
    ) as fleet:
        bad = [fleet.submit(x, CFG, method="nosuch") for _ in range(2)]
        for q in bad:
            assert fleet.result(q, timeout=60).error is not None
        w0 = fleet._workers[0]
        assert w0.breaker == "open"
        assert fleet.stats.breaker_opens == 1
        # a good query submitted while open parks, rides the half-open
        # probe once the cooldown elapses, and closes the breaker
        res = fleet.result(fleet.submit(x, CFG), timeout=120)
        assert res.error is None
        assert w0.breaker == "closed"
        assert w0.consec_failures == 0


def test_fleet_slow_tenant_does_not_inflate_fast_tenant_p99():
    """Head-of-line isolation: a latency-injected worker (the slow
    tenant's home) must not drag another tenant's tail latency — under rr
    placement the tenants home on distinct workers and the fast tenant's
    worst wall stays far under the injected stall."""
    slow_x, fast_x = _datasets(2)
    with FleetSupervisor(
        workers=2,
        profile=False,
        placement="rr",
        worker_slow_probs=[1.0, 0.0],  # worker-0 stalls on EVERY query
        slow_s=0.8,
    ) as fleet:
        # warmup serves pin the sticky homes: slow -> worker-0 (first rr
        # pick), fast -> worker-1
        sw = fleet.result(fleet.submit(slow_x, CFG), timeout=120)
        fw = fleet.result(fleet.submit(fast_x, CFG), timeout=120)
        assert sw.worker == "worker-0" and fw.worker == "worker-1"
        slow_walls, fast_walls = [], []
        for _ in range(5):
            sq = fleet.submit(slow_x, CFG)
            fq = fleet.submit(fast_x, CFG)
            fast_walls.append(fleet.result(fq, timeout=120).wall_s)
            slow_walls.append(fleet.result(sq, timeout=120).wall_s)
        assert min(slow_walls) >= 0.8  # the injector really stalls
        assert max(fast_walls) < 0.5  # p99 proxy: worst of the fast walls


def test_fleet_deadline_expires_instead_of_waiting_on_stall():
    """A stalled worker (chaos latency) cannot hold a deadline query past
    its budget: the supervisor expires it with ``error="deadline"``
    promptly, and the worker's late result is dropped as stale."""
    x = _datasets(1)[0]
    with FleetSupervisor(
        workers=1,
        profile=False,
        worker_slow_probs=[1.0],
        slow_s=1.5,
    ) as fleet:
        t0 = time.perf_counter()
        qid = fleet.submit(x, CFG, deadline_s=0.3)
        res = fleet.result(qid, timeout=60)
        assert res.error == "deadline"
        assert time.perf_counter() - t0 < 1.2  # did not ride out the stall
        assert fleet.stats.deadline_expired >= 1
        # without a deadline the same query rides out the stall and serves
        ok = fleet.result(fleet.submit(x, CFG), timeout=120)
        assert ok.error is None


def test_fleet_requeue_exhaustion_wakes_frontend_waiter():
    """The ingest bridge's lost-query contract: a query that FINISHES with
    an error (here: retries exhausted after its only worker died) while
    the caller is blocked in ``IngestFrontend.result`` must wake the
    waiter with that error, never strand it until timeout."""
    x = _datasets(1)[0]
    fleet = FleetSupervisor(
        workers=1,
        profile=False,
        max_query_retries=0,
        worker_slowdowns=[5.0],
    )
    with fleet, IngestFrontend(fleet, queue_capacity=8) as fe:
        qid = fe.submit(x, CFG)
        w0 = fleet._workers[0]
        _wait(lambda: w0.assigned, what="query in flight")
        time.sleep(0.2)
        t0 = time.perf_counter()
        os.kill(w0.proc.pid, signal.SIGKILL)
        res = fe.result(qid, timeout=60)
        assert time.perf_counter() - t0 < 30  # woke on the error commit
        assert res.error is not None and "retries exhausted" in res.error
