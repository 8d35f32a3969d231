"""Async ingest front-end: accept queries while the scheduler drains.

``DropService.run()`` is batch-shaped — submit everything, then drain. A
serving deployment instead sees an open stream of tenant queries, so this
module adds the thread/condition front-end the ROADMAP asks for:

* **drain threads** — ``start()`` spawns one drain thread per mesh device
  (``service.drain_width``: 1 for the single-host service, device count for
  the sharded one); each repeatedly executes the service's lock-protected
  scheduler primitive, sleeping on a condition while idle.
* **backpressure** — admission is adaptive: the estimated queue delay
  (backlog depth x recent per-query wall EWMA, divided across the drain
  width) is compared against ``max_queue_delay_s``; a submit whose wait
  estimate exceeds the bound — or whose own ``deadline_s`` budget the
  queue would already consume — raises ``RetryLater`` carrying a COMPUTED
  ``retry_after_s`` (the time for the backlog to drain back under the
  bound). ``queue_capacity`` remains the hard memory cap
  (reject-with-retry-after, never block-and-deadlock).
* **completion** — ``result(qid)`` blocks (with optional timeout) until the
  scheduler finishes that query; the service's ``on_result`` hook wakes
  waiters, so there is no polling of the results dict.

The frontend owns no scheduler state of its own: every admission, cache,
and placement decision stays in the service, so the sync and async paths
cannot diverge.
"""

from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np

from repro.core.types import CostFn, DropConfig
from repro.obs import span
from repro.serve_drop.cache import dataset_fingerprint
from repro.serve_drop.delta import SubscribeQuery, SubscriptionClosed
from repro.serve_drop.service import DropService, ServeResult


class RetryLater(RuntimeError):
    """Backpressure rejection: the ingest queue is full. ``retry_after_s``
    estimates when capacity should free up."""

    def __init__(self, retry_after_s: float, backlog: int) -> None:
        super().__init__(
            f"ingest queue full ({backlog} queries pending); "
            f"retry after {retry_after_s:.3f}s"
        )
        self.retry_after_s = retry_after_s
        self.backlog = backlog


class IngestFrontend:
    """Thread-safe streaming front-end over a ``DropService``.

    Usage::

        with IngestFrontend(DropService(devices=4)) as fe:
            qid = fe.submit(x, cfg)          # may raise RetryLater
            res = fe.result(qid, timeout=30)
    """

    def __init__(
        self,
        service: DropService,
        *,
        queue_capacity: int = 64,
        max_queue_delay_s: float | None = None,
    ) -> None:
        self.service = service
        self.queue_capacity = max(int(queue_capacity), 1)
        # adaptive admission: reject when the estimated queue delay for a
        # new query exceeds this bound (None = capacity-only admission)
        self.max_queue_delay_s = max_queue_delay_s
        self._wake = threading.Condition()  # drain threads sleep here
        self._done = threading.Condition()  # result() waiters sleep here
        self._delta = threading.Condition()  # next_delta() waiters sleep here
        self._stop = threading.Event()  # drain threads exit on this
        self._closing = threading.Event()  # submits reject on this first
        self._threads: list[threading.Thread] = []
        self._recent_walls: deque[float] = deque(maxlen=32)
        service.on_result = self._on_result
        if hasattr(service, "on_delta"):
            service.on_delta = self._on_delta

    # ------------------------------------------------------------ lifecycle

    @property
    def drain_width(self) -> int:
        """One drain thread per device; the base service has one device."""
        return len(getattr(self.service, "devices", [None]))

    def start(self) -> "IngestFrontend":
        if self._threads:
            return self
        self._stop.clear()
        self._closing.clear()
        self._threads = [
            threading.Thread(
                target=self._drain, name=f"drop-ingest-{i}", daemon=True
            )
            for i in range(self.drain_width)
        ]
        for t in self._threads:
            t.start()
        return self

    def close(
        self, drain: bool = True, progress_deadline_s: float = 30.0
    ) -> None:
        """Stop the drain threads; ``drain=True`` finishes accepted work
        first. New submits are rejected as soon as close() begins, and any
        straggler that raced past the closing check is drained synchronously
        at the end — an accepted query is never left without a scheduler.

        The backlog wait is bounded by a PROGRESS deadline, not a total
        one: as long as the backlog keeps shrinking we keep waiting, but a
        backlog that has not moved for ``progress_deadline_s`` (a wedged
        scheduler — e.g. every drain tick raising) is abandoned so close()
        always returns. Queries stranded that way stay unresolved in the
        service; ``stats.drain_failures`` records the ticks that raised.

        Live subscriptions terminate deterministically: close() requests an
        orderly unsubscribe up front (in-flight deltas still deliver, then
        the final ``closed``), and any subscription still live once the
        drain ends — including the wedged-scheduler path — is force-closed,
        so every subscriber sees a terminal delta and no ``next_delta``
        waiter is left stranded.

        One exception: a service that journals its subscriptions
        (``persists_subscriptions``) DETACHES them instead — no terminal
        delta, no WAL ``end`` record — so a restart over the same
        ``state_dir`` resumes every stream with a ``reason="recover"``
        rollback. Pending suffixes are served during the drain first, so
        nothing journaled is left unapplied by a clean shutdown."""
        self._closing.set()  # reject new submits before waiting on backlog
        persists = getattr(self.service, "persists_subscriptions", False)
        live_subs = getattr(self.service, "live_subscriptions", None)
        unsubscribe = getattr(self.service, "unsubscribe", None)
        if live_subs is not None and unsubscribe is not None and not persists:
            for sid in live_subs():
                # orderly: queued suffixes drop, in-flight work lands first
                unsubscribe(sid)
        if drain and self._threads:
            last = self.service.backlog()
            t_last = time.perf_counter()
            while True:
                backlog = self.service.backlog()
                if not backlog:
                    break
                if backlog < last:
                    last, t_last = backlog, time.perf_counter()
                elif time.perf_counter() - t_last > progress_deadline_s:
                    break  # no progress: a drain would wait forever
                time.sleep(0.002)
        self._stop.set()
        with self._wake:
            self._wake.notify_all()
        for t in self._threads:
            t.join()
        self._threads = []
        if drain:
            while self.service.backlog():  # straggler sweep (see docstring)
                try:
                    if not self.service.poll():
                        break
                except Exception:  # same containment as the drain loop
                    with self.service._lock:
                        self.service.stats.drain_failures += 1
                    break
        if live_subs is not None and unsubscribe is not None:
            if persists:
                # journaled streams survive the frontend: close them
                # in-memory without a terminal delta or WAL end record,
                # so the next service over this state_dir resumes them
                self.service.detach_subscriptions()
            else:
                for sid in live_subs():
                    # still live after the drain (wedged scheduler, or drain
                    # was False): force the terminal delta NOW — a stranded
                    # in-flight emission is dropped by the closed state
                    unsubscribe(sid, force=True)
        with self._delta:  # belt and braces: no waiter sleeps past close
            self._delta.notify_all()

    def __enter__(self) -> "IngestFrontend":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close(drain=exc == (None, None, None))

    # -------------------------------------------------------------- intake

    def submit(
        self,
        x: np.ndarray,
        cfg: DropConfig | None = None,
        cost: CostFn | None = None,
        *,
        method: str = "pca",
        downstream: str | None = None,
        execute_downstream: bool = False,
        deadline_s: float | None = None,
    ) -> int:
        """Enqueue a query from any thread (any Reducer ``method``; the
        single-shot baselines are one-step runners to the scheduler).
        Raises ``RetryLater`` when admission rejects (backpressure) or the
        frontend is closed; ``deadline_s`` bounds the query's total
        latency (past it the result carries ``error="deadline"``).

        Admission is two-layered: the estimated queue delay (recent
        per-query wall x backlog depth / drain width) must stay under
        ``max_queue_delay_s`` AND under the query's own deadline budget —
        a query the queue would already expire is refused up front with a
        computed ``retry_after_s`` instead of being accepted into certain
        death. The deadline budget is per-tenant: the service's cost
        EWMA for this dataset fingerprint (``tenant_estimate_s``) is
        subtracted first, so a heavy tenant is refused at a shallower
        backlog than a light one whose own compute leaves the deadline
        more queue headroom. ``queue_capacity`` remains the hard cap,
        checked atomically with the enqueue (``try_submit``), so
        concurrent submitters can never jointly overshoot the bound.

        The whole call is one ``drop.submit`` span, booked in the
        service's ``stats.submit_s``."""
        t_start = time.perf_counter()
        with span("drop.submit", rows=len(x)) as sp:
            if self._closing.is_set() or self._stop.is_set():
                backlog = self.service.backlog()
                raise RetryLater(self._retry_after(backlog), backlog)
            # convert + hash on the submitter's thread: admission needs the
            # fingerprint for the per-tenant estimate, and try_submit reuses
            # it (fingerprint=) instead of hashing twice
            x = np.ascontiguousarray(np.asarray(x), dtype=np.float32)
            fp = dataset_fingerprint(x)
            bound = self.max_queue_delay_s
            if deadline_s is not None:
                # wait + own_est > deadline ⇒ refuse: the queue may only
                # consume what the deadline leaves after this tenant's compute
                budget = max(deadline_s - self._tenant_s(fp), 0.0)
                bound = budget if bound is None else min(bound, budget)
            if bound is not None:
                backlog = self.service.backlog()
                if self._queue_delay(backlog) > bound:
                    raise RetryLater(self._retry_after(backlog, bound), backlog)
            qid = self.service.try_submit(
                x, cfg, cost, method=method, downstream=downstream,
                execute_downstream=execute_downstream,
                max_backlog=self.queue_capacity,
                deadline_s=deadline_s,
                fingerprint=fp,
                t_start=t_start,
            )
            if qid is None:
                backlog = self.service.backlog()
                raise RetryLater(self._retry_after(backlog), backlog)
            sp.set_metadata(qid=qid)
            with self._wake:
                self._wake.notify_all()
            return qid

    def _per_query_s(self) -> float:
        if self._recent_walls:
            return sum(self._recent_walls) / len(self._recent_walls)
        return 0.05

    def _tenant_s(self, fp: str) -> float:
        """This tenant's expected compute seconds: the service's per-
        fingerprint cost EWMA when it has served this dataset before,
        else the global per-query wall mean (new tenants get the
        population prior, not a free pass)."""
        est = getattr(self.service, "tenant_estimate_s", None)
        if est is not None:
            known = est(fp)
            if known is not None:
                return known
        return self._per_query_s()

    def _queue_delay(self, backlog: int) -> float:
        """Expected wait for a query admitted behind ``backlog`` others:
        per-query wall EWMA x depth, divided across the drain width."""
        return self._per_query_s() * backlog / max(self.drain_width, 1)

    def _retry_after(self, backlog: int, bound: float | None = None) -> float:
        """Computed retry hint. With a queue-delay ``bound``: the time for
        the backlog to drain from its current depth back under the bound
        (excess queries x per-query rate). Without one (capacity
        rejection, closing): backlog / observed service rate, floored so
        clients never busy-spin."""
        per_query = self._per_query_s()
        width = max(self.drain_width, 1)
        if bound is not None and per_query > 0:
            admissible = bound * width / per_query  # depth the bound allows
            excess = max(backlog - admissible, 1.0)
            return max(0.005, excess * per_query / width)
        return max(0.005, per_query * max(backlog, 1) / width / 4)

    # -------------------------------------------------------------- pub/sub

    def subscribe(
        self,
        x: np.ndarray | SubscribeQuery,
        cfg: DropConfig | None = None,
        *,
        method: str = "pca",
        eps: float = 0.5,
        min_samples: int = 5,
        bandwidth: float = 1.0,
        rotation_tol: float = 0.25,
    ) -> int:
        """Open a delta subscription (``x`` may be a dataset or a prebuilt
        ``SubscribeQuery``). The first delta — a ``rollback`` with reason
        ``"subscribe"`` carrying the full bootstrap state — arrives via
        ``next_delta``/``poll_deltas`` once the scheduler serves the
        reduction. Raises ``RetryLater`` when the frontend is closing."""
        if self._closing.is_set() or self._stop.is_set():
            backlog = self.service.backlog()
            raise RetryLater(self._retry_after(backlog), backlog)
        if isinstance(x, SubscribeQuery):
            query = x
        else:
            query = SubscribeQuery(
                x=x, cfg=cfg or DropConfig(), method=method, eps=eps,
                min_samples=min_samples, bandwidth=bandwidth,
                rotation_tol=rotation_tol,
            )
        sid = self.service.subscribe(query)
        with self._wake:
            self._wake.notify_all()
        return sid

    def append(self, sub_id: int, suffix: np.ndarray) -> None:
        """Queue appended rows on a subscription from any thread; the
        resulting delta arrives asynchronously. Raises
        ``SubscriptionClosed`` once the subscription is terminal."""
        self.service.append(sub_id, suffix)
        with self._wake:
            self._wake.notify_all()

    def poll_deltas(self, sub_id: int, max_n: int | None = None) -> list:
        """Non-blocking: pop whatever deltas have been emitted (in order,
        at most once)."""
        return self.service.poll_deltas(sub_id, max_n=max_n)

    def next_delta(self, sub_id: int, timeout: float | None = None) -> dict:
        """Block until the subscription's next delta; the final ``closed``
        delta is delivered like any other, after which this raises
        ``SubscriptionClosed``. Raises TimeoutError on expiry."""
        deadline = None if timeout is None else time.perf_counter() + timeout
        with self._delta:
            while True:
                got = self.service.poll_deltas(sub_id, max_n=1)
                if got:
                    return got[0]
                if sub_id not in self.service.live_subscriptions():
                    raise SubscriptionClosed(
                        f"subscription {sub_id} is closed"
                    )
                remaining = (
                    None if deadline is None
                    else deadline - time.perf_counter()
                )
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(f"subscription {sub_id}: no delta")
                # like result(): _on_delta serializes behind _delta, so no
                # wakeup can be lost between the poll and the wait
                self._delta.wait(
                    timeout=0.05 if remaining is None else min(remaining, 0.05)
                )

    def unsubscribe(self, sub_id: int) -> None:
        self.service.unsubscribe(sub_id)

    def _on_delta(self, sub_id: int) -> None:
        with self._delta:
            self._delta.notify_all()

    # ------------------------------------------------------------- results

    def result(self, qid: int, timeout: float | None = None) -> ServeResult:
        """Block until query ``qid`` finishes; raises TimeoutError."""
        deadline = None if timeout is None else time.perf_counter() + timeout
        with self._done:
            while True:
                res = self.service.take_result(qid)
                if res is not None:
                    self._recent_walls.append(res.wall_s)
                    return res
                remaining = (
                    None if deadline is None else deadline - time.perf_counter()
                )
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(f"query {qid} still pending")
                # remaining=None waits until _on_result notifies — the hook
                # is serialized behind _done, so no wakeup can be lost
                self._done.wait(timeout=remaining)

    def _on_result(self, qid: int) -> None:
        with self._done:
            self._done.notify_all()

    # --------------------------------------------------------------- drain

    def _drain(self) -> None:
        while not self._stop.is_set():
            try:
                stepped, more = self.service._poll_once()
            except Exception:
                # An exception escaping the scheduler tick (the service
                # contains runner/validation/commit errors itself, so this
                # is an admission- or infrastructure-level failure) used to
                # kill this daemon thread silently — after which
                # close(drain=True) waited forever on a backlog nothing
                # would drain. Count it, yield, and keep the thread alive;
                # close()'s progress deadline bounds the truly wedged case.
                with self.service._lock:
                    self.service.stats.drain_failures += 1
                time.sleep(0.001)
                continue
            if stepped:
                continue
            if more:
                # placeable work exists but every runner is mid-step on
                # another drain thread — yield rather than spin
                time.sleep(0.0005)
                continue
            with self._wake:
                if not self._stop.is_set() and not self.service.backlog():
                    self._wake.wait(timeout=0.05)
