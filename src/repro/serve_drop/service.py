"""DropService: batched multi-query dimensionality reduction with reuse.

The service accepts many DR queries — each a ``ReduceQuery``: dataset +
method (any ``Reducer``: pca/fft/paa/dwt/jl) + target TLB + downstream cost
(a callable, or a named analytics task priced via ``core.cost``) — and
drives them through the shared device:

* **admission** — each query is fingerprinted and checked against the
  ``BasisReuseCache`` (keyed fingerprint × method × target). An exact hit
  is revalidated with a sampled TLB estimate on the live data (no fitting
  at all); an append-only stream whose PREFIX fingerprint matches a cached
  entry revalidates that entry on the grown data; a warm hit seeds the
  §3.4.3 rank bound of a cold PCA run; a miss runs cold.
* **suffix escalation** — a prefix-matched PCA entry that FAILS
  revalidation, or whose suffix exceeds ``suffix_budget`` (as a fraction
  of the fitted rows), is repaired by a ``_SuffixUpdate`` work item: an
  O(suffix) incremental subspace merge (``core.subspace``) TLB-gated on
  the grown data. Only when even the updated map cannot clear the target
  does the query fall to a cold refit — the service's most expensive
  operation becomes the last resort on append-only streams, not the
  default drift response.
* **scheduling** — cold runs are ``Reducer`` state machines built by
  ``make_reducer`` (DROP's multi-step Algorithm-2 loop for PCA; one-step
  runners for the deterministic baselines); the scheduler round-robins
  single steps across up to ``max_inflight`` runners, so a query that
  terminates after two cheap iterations frees its slot immediately instead
  of queueing behind a heavy tenant.
* **shape sharing** — all runners and validators quantize through one
  ``ShapeBucketCache``, so tenants with compatible shapes reuse each
  other's XLA executables (the jit cache is keyed by shape).

Per-query numerics are identical to the sequential ``reduce()``/``drop()``
APIs with the same config: every runner owns its RNG streams, and
interleaving never reorders any single query's draws.

Thread-safety: ``submit``, ``poll``, and ``take_result`` may be called from
different threads — one scheduler lock guards the queue, flight, cache, and
stats, while every unit of device compute (a runner iteration OR a cache-hit
revalidation) runs outside the lock, so ingest threads are never blocked
behind device compute. The ``on_result`` hook fires with no scheduler lock
held (waiters may re-enter ``take_result`` freely). A runner iteration that
raises is contained: the query finishes with ``ServeResult.error`` set and
the scheduler keeps draining the rest. ``ShardedDropService`` builds on
this by running one drain thread per mesh device (``DropService(devices=n)``
builds it where n resolves to more than one device), and
``serve_drop.ingest`` layers the bounded-queue async front-end on top.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Callable, ClassVar

import jax.numpy as jnp
import numpy as np

from repro.analytics.split import mesh_shards
from repro.core.bucketing import DEFAULT_BUCKETS, ShapeBucketCache
from repro.core.reducer import Reducer, make_reducer, method_cacheable
from repro.core.subspace import (
    TRACK_HEADROOM,
    SubspaceTracker,
    suffix_update as subspace_suffix_update,
)
from repro.core.tlb import TLBEstimator
from repro.core.types import CostFn, DropConfig, ReduceResult, transform_blocks
from repro.obs import span
from repro.serve_drop.cache import (
    BasisCacheEntry,
    BasisReuseCache,
    dataset_fingerprint,
)
from repro.serve_drop.delta import (
    APPEND,
    CLOSED,
    ROLLBACK,
    SubscribeQuery,
    SubscriptionClosed,
    _Subscription,
)


@dataclass
class ReduceQuery:
    """One tenant request: reduce ``x`` to the smallest TLB-preserving map
    with ``method``, priced against ``cost`` (or the named ``downstream``
    analytics task). ``DropQuery`` is the deprecated PCA-era alias."""

    query_id: int
    x: np.ndarray
    cfg: DropConfig
    cost: CostFn | None = None
    method: str = "pca"
    downstream: str | None = None  # provenance; cost resolved at submit()
    # run the named downstream analytics on the reduced data and attach the
    # output to ServeResult.downstream (the served end-to-end path); the
    # analytics execute as a scheduled work item like any device compute
    execute_downstream: bool = False
    fingerprint: str = ""  # computed once at submit()
    # rows -> fingerprint of x[:rows] for cached candidate prefix lengths,
    # hashed on the submitter's thread (append-only stream matching); best
    # effort — entries cached after submit() are not probed
    prefix_fps: dict = field(default_factory=dict)
    t0: float | None = None  # pinned at first dequeue (includes deferral time)
    # built under the lock at its append to the queue: the enqueue time
    t_enq: float = field(default_factory=time.perf_counter)
    # absolute expiry (perf_counter seconds), stamped at submit from the
    # caller's deadline_s budget; None = no deadline. Checked at dequeue
    # and between runner steps: an expired query finishes with
    # ``ServeResult.error="deadline"`` instead of burning compute.
    deadline_t: float | None = None


DropQuery = ReduceQuery  # deprecated alias (pre-Reducer-protocol name)


@dataclass
class ServeResult:
    query_id: int
    result: ReduceResult
    cache_hit: bool = False  # served straight from the basis cache
    prefix_hit: bool = False  # cache hit via append-only prefix fingerprint
    warm_started: bool = False  # cold run, but rank bound seeded from cache
    suffix_update: bool = False  # served by an incremental subspace update
    wall_s: float = 0.0
    error: str | None = None  # runner raised mid-flight, or "deadline"
    # graceful degradation: served a stale/cheaper cached basis under
    # pressure instead of queueing fresh compute; the achieved (recorded)
    # TLB rides in ``result.tlb_estimate``
    degraded: bool = False
    downstream: object = None  # executed analytics output (execute_downstream)
    downstream_s: float = 0.0  # analytics compute seconds (within wall_s)
    worker: str | None = None  # fleet mode: label of the worker that served it
    retries: int = 0  # fleet mode: re-dispatches after a worker death


@dataclass
class ServiceStats:
    queries: int = 0
    cache_hits: int = 0
    prefix_hits: int = 0  # subset of cache_hits served via prefix matching
    cache_misses: int = 0
    warm_starts: int = 0
    fit_calls: int = 0
    iterations: int = 0
    validation_pairs: int = 0
    # cache-hit revalidations that RAISED (the query then refits cold; a
    # nonzero count means a broken validation path, not data drift)
    validation_errors: int = 0
    suffix_updates: int = 0  # queries served by an incremental merge
    suffix_update_failures: int = 0  # updates that fell through (or raised)
    downstream_runs: int = 0  # served analytics executions (execute_downstream)
    downstream_failures: int = 0  # analytics executions that raised
    # delta-serving (serve_drop.delta): pub/sub subscription counters
    subscriptions: int = 0  # subscribe() calls accepted
    delta_serves: int = 0  # append deltas served (O(suffix) path)
    rollbacks: int = 0  # rollback deltas forced by appends (drift/headroom/refit)
    failures: int = 0  # queries finished with ServeResult.error set
    rejected: int = 0  # ingest backpressure rejections (reject-with-retry-after)
    # overload resilience (deadlines / degradation / circuit breaking)
    deadline_expired: int = 0  # queries expired with error="deadline"
    degraded_serves: int = 0  # stale/cheaper cached serves under pressure
    breaker_opens: int = 0  # fleet: per-worker circuit-breaker trips
    sub_rehomes: int = 0  # fleet: subscriptions re-homed after a death
    steals: int = 0  # runners migrated to an idle device between rounds
    drain_failures: int = 0  # exceptions caught at the ingest drain loop
    # fleet mode (serve_drop.fleet): process-worker supervision counters
    worker_deaths: int = 0  # workers that died or were declared hung
    worker_restarts: int = 0  # restarts performed under the RestartPolicy
    workers_lost: int = 0  # workers past the restart budget (slot retired)
    requeued_queries: int = 0  # in-flight queries re-dispatched after a death
    rebalances: int = 0  # tenants moved to a measured-cheaper worker
    straggler_flags: int = 0  # worker serve times flagged by StragglerMonitor
    reprofiles: int = 0  # periodic link re-profiles (stale-profile age-out)
    # crash-consistent durability (serve_drop.persist): recovery counters,
    # populated once at construction when state_dir recovery runs
    recovery_snapshots: int = 0  # cache entries restored from snapshot files
    recovery_quarantined: int = 0  # corrupt snapshot files quarantined
    recovery_wal_records: int = 0  # subscription-WAL records replayed
    recovery_wal_trimmed: int = 0  # torn WAL tails trimmed at replay
    recovery_wal_skipped: int = 0  # corrupt mid-WAL records skipped
    recovery_subs: int = 0  # subscriptions resumed with a recover rollback
    recovery_warm_profile: int = 0  # fleet: warm-restart profile loads
    invalid_inputs: int = 0  # NaN/Inf datasets rejected at intake
    degraded_refreshes: int = 0  # background refits scheduled by degraded serves
    effective_ttl: int | None = None  # live auto-tuned cache TTL (ticks)
    # per-device occupancy: device label -> iterations stepped there; the
    # single-host service books everything under "default"
    device_iterations: dict = field(default_factory=dict)
    # stage times, host seconds summed over queries (the ``drop.*`` spans
    # of ``repro.obs`` mark the same stages in a profiler trace)
    submit_s: float = 0.0  # submit: conversion, hashing, checks, enqueue
    queue_wait_s: float = 0.0  # enqueue to routing by _admit, deferrals in
    work_wait_s: float = 0.0  # a work item's wait in its deque for _pop_work
    validate_s: float = 0.0  # cache-hit revalidations (_validate)
    tlb_rounds: int = 0  # TLB device calls (CI doublings) of revalidations
    transform_s: float = 0.0  # host transform of served analytics
    transform_blocked: int = 0  # served transforms projected in row blocks
    downstream_s: float = 0.0  # served analytics items (ServeResult.downstream_s)
    mesh_scans: int = 0  # served analytics scans fanned out over the mesh
    mesh_shards: int = 0  # their dataset shards, summed over those scans

    def as_dict(self) -> dict:
        return dict(self.__dict__)


@dataclass(eq=False)  # identity semantics: scheduler queues remove by object
class _InFlight:
    query: ReduceQuery
    runner: Reducer
    fingerprint: str
    warm_started: bool
    t0: float  # queue-pinned at first dequeue (includes deferral time)
    device: object = None  # mesh device the runner is placed on (sharded)
    # work items are built under the lock as they enter their deque; a
    # requeued runner is stamped again
    t_ready: float = field(default_factory=time.perf_counter)
    span_name: ClassVar[str] = "drop.item.fit_step"


@dataclass(eq=False)
class _Validation:
    """A pending cache-hit revalidation: device compute, so it is scheduled
    like a runner iteration (outside the lock) instead of inside admission.
    Its fingerprint stays visible to the dedup check while it runs."""

    query: ReduceQuery
    entry: BasisCacheEntry
    fingerprint: str
    t0: float
    device: object = None  # mesh device to validate on (sharded)
    prefix: bool = False  # entry matched via prefix fingerprint (append)
    t_ready: float = field(default_factory=time.perf_counter)
    span_name: ClassVar[str] = "drop.item.validate"


@dataclass(eq=False)
class _SuffixUpdate:
    """A pending incremental subspace update for an append-only stream:
    merge the suffix into the cached updater state and TLB-gate the result
    on the grown data. Device compute, scheduled exactly like a
    ``_Validation`` (off-lock, fingerprint visible to dedup); a failed gate
    falls through to the cold-refit path, a raising update finishes the
    query with ``ServeResult.error`` instead of wedging the drain."""

    query: ReduceQuery
    entry: BasisCacheEntry
    fingerprint: str
    t0: float
    device: object = None  # mesh device to update on (sharded)
    t_ready: float = field(default_factory=time.perf_counter)
    span_name: ClassVar[str] = "drop.item.suffix_update"


@dataclass(eq=False)
class _DeltaServe:
    """A pending delta computation for one subscription: either the
    bootstrap (the subscription's reduction finished — ``base`` holds it —
    and the initial transformed rows + downstream state must build) or an
    append (fold queued suffixes into the served state and emit an append
    or rollback delta). Device compute, scheduled through the validation
    deque like every other off-lock work item; at most ONE is in flight per
    subscription, so the delta chain is serialized and sequence numbers
    never race. A raising compute closes the subscription with an error
    delta — never wedging the drain."""

    sub: _Subscription
    kind: str  # "bootstrap" | "append"
    t0: float
    suffixes: list = field(default_factory=list)  # append: queued suffix rows
    base: ServeResult | None = None  # bootstrap: the finished reduction
    device: object = None  # mesh device to compute on (sharded)
    t_ready: float = field(default_factory=time.perf_counter)
    span_name: ClassVar[str] = "drop.item.delta"

    @property
    def fingerprint(self) -> str:
        # never dedup-matches a query (real fingerprints are sha1 hex), so
        # admission's `_fingerprint_inflight` short-circuits before touching
        # the `.query` attribute this item does not have
        return ""


@dataclass(eq=False)
class _Downstream:
    """A pending served-analytics execution: the query's reduction already
    finished (``base`` holds its committed ``ServeResult``) and the named
    downstream task now runs on the reduced data. Device compute, scheduled
    exactly like a ``_Validation`` (off-lock, counted in flight); a raising
    analytics run finishes the query with ``ServeResult.error`` set while
    KEEPING the reduction result — the map is still good."""

    query: ReduceQuery
    base: ServeResult
    t0: float
    device: object = None  # mesh device to run the analytics on (sharded)
    t_ready: float = field(default_factory=time.perf_counter)
    transform_s: float = 0.0  # host transform, set by _apply_downstream
    transform_blocks: int = 0  # its row blocks (0: it did not complete)
    span_name: ClassVar[str] = "drop.item.downstream"

    @property
    def fingerprint(self) -> str:  # dedup visibility, like the other items
        return self.query.fingerprint


class DropService:
    """Multi-tenant DROP scheduler with an LRU basis-reuse cache.

    ``devices`` is how many devices the service runs on (or a list of
    them). Where it resolves to more than one visible device,
    ``DropService(devices=n)`` builds the ``ShardedDropService`` over them:
    placement, one drain thread per device, and the served analytics
    fanned across the devices by dataset shard. None, or one device, builds
    this single-device service."""

    def __new__(cls, *args, devices: int | list | None = None, **kwargs):
        if cls is DropService and devices is not None:
            from repro.serve_drop.sharded import ShardedDropService
            from repro.sharding.specs import serve_devices

            n = len(serve_devices(devices) if isinstance(devices, int) else devices)
            if n > 1:
                return super().__new__(ShardedDropService)
        return super().__new__(cls)

    def __init__(
        self,
        *,
        devices: int | list | None = None,  # one here: resolved by __new__
        max_inflight: int = 4,
        cache_entries: int = 16,
        bucket: ShapeBucketCache | None = None,
        enable_cache: bool = True,
        cache_ttl: int | None = None,
        cache_ttl_auto: bool = False,
        enable_suffix_update: bool = True,
        suffix_budget: float = 0.25,
        analytics_split: int | None = None,
        analytics_fanout: str = "xla",
        analytics_devices=None,
        degrade_watermark_s: float | None = None,
        degrade_refresh_after: int | None = 3,
        state_dir: str | None = None,
        state_fsync: bool = True,
        failure_injector=None,
    ) -> None:
        self.max_inflight = max(int(max_inflight), 1)
        # graceful-degradation watermark: when the estimated queue delay
        # (backlog x recent serve-time EWMA) exceeds this, a query whose
        # fingerprint has ANY cached basis — expired TTL, prefix-only
        # match, or a satisfying coarser method — is served that stale
        # result immediately with ``ServeResult.degraded=True`` instead of
        # queueing fresh compute. None disables the ladder.
        self.degrade_watermark_s = degrade_watermark_s
        self._serve_ewma: float | None = None  # recent wall_s per serve
        # served-analytics execution knobs (``analytics.split`` semantics):
        # split=N runs the downstream pairwise scan as N dataset shards,
        # fanout="mesh" fans them across analytics_devices — exact merges,
        # so the served output is independent of the decomposition
        self.analytics_split = analytics_split
        self.analytics_fanout = analytics_fanout
        self.analytics_devices = (
            None if analytics_devices is None else tuple(analytics_devices)
        )
        # dataset shards of each served scan where the fan-out resolves to
        # the mesh (booked as mesh_scans / mesh_shards); 0 on one device
        self._mesh_shards = mesh_shards(analytics_fanout, self.analytics_devices)
        # append-only escalation knobs: a prefix-matched suffix larger than
        # suffix_budget * fitted rows skips revalidation (a map fitted that
        # many rows ago mostly buys a failed validation) and goes straight
        # to the incremental update; 0.0 means always update, and
        # enable_suffix_update=False restores the PR 3 revalidate-or-refit
        # behavior (no tracker state is kept either)
        self.enable_suffix_update = enable_suffix_update
        self.suffix_budget = float(suffix_budget)
        # share the process-wide buckets by default: plain drop() calls (e.g.
        # the CLI's jit warmup) and the service then compile the same shapes
        self.bucket = bucket or DEFAULT_BUCKETS
        self.cache = BasisReuseCache(
            capacity=cache_entries, ttl_ticks=cache_ttl, auto_ttl=cache_ttl_auto
        )
        self.enable_cache = enable_cache
        self.stats = ServiceStats(effective_ttl=self.cache.ttl_ticks)
        self._queue: deque[ReduceQuery] = deque()
        self._inflight: deque[_InFlight] = deque()
        self._validations: deque[_Validation] = deque()
        self._results: dict[int, ServeResult] = {}
        # query ids whose results became visible but have not been notified
        # yet (drained by the next _poll_once tick, under the lock)
        self._done_now: list[int] = []
        self._next_id = 0
        # one scheduler lock guards queue/flight/cache/results/stats; device
        # compute (steps AND revalidations) runs outside it so submit()
        # never waits behind the device
        self._lock = threading.RLock()
        # work currently executing outside the lock: counts toward
        # max_inflight and keeps its fingerprint visible to admission dedup
        self._stepping_now: list = []
        # ingest hook: called with each finished query id, with NO scheduler
        # lock held (a waiter may re-enter take_result from the callback)
        self.on_result: Callable[[int], None] | None = None
        # delta-serving state: subscriptions by id, plus the map from a
        # bootstrap ReduceQuery's id to its subscription (consumed by
        # _notify, which turns the finished reduction into the first delta)
        self._subs: dict[int, _Subscription] = {}
        self._sub_boot: dict[int, _Subscription] = {}
        self._next_sub_id = 0
        # delta hook: called with each subscription id that gained deltas,
        # with NO scheduler lock held (mirror of on_result)
        self.on_delta: Callable[[int], None] | None = None
        # degraded-serve refresh hint: an entry served degraded this many
        # times schedules ONE background refit (an internal query whose
        # result is discarded — its cache re-registration is the point).
        # None disables the self-heal.
        self.degrade_refresh_after = degrade_refresh_after
        self._refresh_qids: dict[int, tuple[str, str]] = {}
        self._refresh_inflight: set[tuple[str, str]] = set()
        # per-tenant serve-cost EWMA (fingerprint -> compute seconds): the
        # ingest frontend's admission sharpener (see tenant_estimate_s)
        self._tenant_ewma: OrderedDict[str, float] = OrderedDict()
        # crash-consistent durability (serve_drop.persist): basis-cache
        # snapshots + subscription WAL rooted at state_dir. None keeps the
        # memory-only behavior. The write-through hooks run under the
        # scheduler lock — each cache insert costs one atomic file replace,
        # each subscribe/append/ack one fsync'd journal record; that is the
        # durability the crash contract sells, priced in bench_recovery.
        self._persist = None
        if state_dir is not None:
            from repro.serve_drop.persist import PersistentState

            self._persist = PersistentState(
                state_dir, fsync=state_fsync, injector=failure_injector
            )
            self.cache.on_put = self._persist.snapshots.write
            self.cache.on_evict = self._persist.snapshots.drop
            self._recover()

    # ----------------------------------------------------------- recovery

    @property
    def persists_subscriptions(self) -> bool:
        """Whether this service journals subscriptions durably (state_dir
        mode): ``IngestFrontend.close`` detaches instead of terminally
        unsubscribing, so a planned restart resumes the streams too."""
        return self._persist is not None

    def _recover(self) -> None:
        """Warm restart over ``state_dir`` (runs once, at construction,
        before any client thread exists).

        Snapshots: every decodable cache entry re-enters the LRU flagged
        ``recovered=True`` — the exact/prefix paths TLB-revalidate it on
        live data before first serve (the existing gate), and the degraded
        ladder refuses it until that first validation passes. Corrupt
        files are quarantined and counted, never fatal.

        WAL: replay folds the journal into per-sid grown datasets and
        acked seq frontiers; each live subscription is resumed with its
        ORIGINAL sid and a bootstrap reduction whose rollback is stamped
        ``reason="recover"`` at exactly the seq the client expects next —
        the same full-restate contract as the fleet's rehome, so clients
        never re-subscribe. The journal is then compacted to live state."""
        entries, quarantined = self._persist.snapshots.load()
        with self._lock:
            for fp, entry in entries:
                self.cache.put(fp, entry)
            self.stats.recovery_snapshots = len(entries)
            self.stats.recovery_quarantined = quarantined
            subs, nrec = self._persist.wal.replay()
            self.stats.recovery_wal_records = nrec
            self.stats.recovery_wal_trimmed = self._persist.wal.trimmed
            self.stats.recovery_wal_skipped = self._persist.wal.skipped
            for sid in sorted(subs):
                self._resume_subscription(subs[sid])
            self._persist.wal.compact([subs[s] for s in sorted(subs)])

    def _resume_subscription(self, rec) -> None:
        """Rebuild one journaled subscription (``persist.RecoveredSub``):
        original sid, grown dataset (subscribe-time rows + every journaled
        suffix), seq resumed at the acked client-visible frontier. The
        bootstrap reduction re-runs through the normal ladder — typically
        a prefix hit on the snapshot-recovered entry, revalidated and
        served without a refit. Caller holds the lock."""
        q = rec.query
        x = np.ascontiguousarray(rec.x, dtype=np.float32)
        q.x = x
        sub = _Subscription(
            sub_id=rec.sid, query=q, x=x, seq=rec.next_seq, recover=True
        )
        self._subs[rec.sid] = sub
        self._next_sub_id = max(self._next_sub_id, rec.sid + 1)
        self.stats.recovery_subs += 1
        qid = self.try_submit(x, q.cfg, None, method=q.method)
        sub.boot_qid = qid
        self._sub_boot[qid] = sub

    def detach_subscriptions(self) -> list[int]:
        """Planned-shutdown counterpart to ``unsubscribe`` for persistent
        services: close every live subscription in memory WITHOUT
        journaling an ``end`` or emitting a ``closed`` delta, so the WAL
        still carries them and the next process over this state_dir
        resumes each stream with a recover rollback. Returns the detached
        sids. Memory-only services have nothing to detach toward — their
        shutdown path stays ``unsubscribe``."""
        with self._lock:
            out = []
            for sid, sub in self._subs.items():
                if sub.state != "closed":
                    sub.state = "closed"
                    sub.pending_suffixes.clear()
                    out.append(sid)
            return out

    # ------------------------------------------------------------- intake

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
        """Enqueue a query; returns its id (results keyed by it).

        ``method`` selects the Reducer (pca/fft/paa/dwt/jl); ``downstream``
        names an analytics task (knn/dbscan/kde) to price as the cost model
        when ``cost`` is not given explicitly. ``execute_downstream=True``
        additionally RUNS that task on the reduced data before the query
        finishes, attaching the output as ``ServeResult.downstream`` (the
        service's analytics knobs select the shard decomposition).
        ``deadline_s`` bounds the query's total latency: past it the query
        finishes with ``error="deadline"`` instead of consuming compute.

        Thread-safe: the fingerprint is hashed outside the scheduler lock, so
        concurrent submitters only serialize on the queue append."""
        qid = self.try_submit(
            x, cfg, cost, method=method, downstream=downstream,
            execute_downstream=execute_downstream, deadline_s=deadline_s,
        )
        assert qid is not None  # unbounded submit never rejects
        return qid

    def try_submit(
        self,
        x: np.ndarray,
        cfg: DropConfig | None = None,
        cost: CostFn | None = None,
        *,
        method: str = "pca",
        downstream: str | None = None,
        execute_downstream: bool = False,
        max_backlog: int | None = None,
        deadline_s: float | None = None,
        fingerprint: str | None = None,
        t_start: float | None = None,
    ) -> int | None:
        """Enqueue unless the backlog is at ``max_backlog``; returns the
        query id or None on rejection. The bound check and the append are
        one critical section, so concurrent submitters cannot jointly
        overshoot the bound (ingest backpressure relies on this).

        The submit is one ``drop.submit`` span and is booked in
        ``stats.submit_s``. A caller that began the submit earlier (the
        ingest front end, which converts and hashes first inside its own
        span) passes its ``perf_counter`` start as ``t_start``: the service
        then books from it and opens no second span.

        The O(m*d) float32/contiguity conversion AND all fingerprint hashing
        (full + candidate prefixes) happen HERE, on the submitter's thread
        outside the scheduler lock — the runner and the validation path then
        take zero-copy views, so admission under the lock never copies or
        hashes a tenant's dataset. A caller that already hashed the
        converted dataset (the ingest frontend does, for per-tenant
        admission) passes ``fingerprint`` to skip the re-hash.

        A dataset carrying NaN/Inf rows never reaches a runner, the cache,
        or a shared tracker: it finishes immediately with
        ``ServeResult.error="invalid_input"``."""
        if t_start is None:
            with span("drop.submit", rows=len(x)) as sp:
                qid = self.try_submit(
                    x, cfg, cost, method=method, downstream=downstream,
                    execute_downstream=execute_downstream,
                    max_backlog=max_backlog, deadline_s=deadline_s,
                    fingerprint=fingerprint, t_start=time.perf_counter(),
                )
                if qid is not None:
                    sp.set_metadata(qid=qid)
            return qid
        x = np.ascontiguousarray(np.asarray(x), dtype=np.float32)
        if not np.all(np.isfinite(x)):
            return self._reject_invalid(x, method)
        cfg = cfg or DropConfig()
        if execute_downstream and downstream is None:
            raise ValueError("execute_downstream requires a downstream task")
        fp = fingerprint or dataset_fingerprint(x)
        if cost is None and downstream is not None:
            from repro.core.cost import downstream_cost

            cost = downstream_cost(downstream, x.shape[0])
        prefix_fps: dict[int, str] = {}
        if self.enable_cache and method_cacheable(method):
            with self._lock:  # metadata scan only (no hashing under lock)
                counts = self.cache.prefix_row_counts(
                    x.shape[0], x.shape[1], cfg.target_tlb, method
                )
            prefix_fps = {r: dataset_fingerprint(x[:r]) for r in counts}
        with self._lock:
            self.stats.submit_s += time.perf_counter() - t_start
            if (
                max_backlog is not None
                and len(self._queue) + self._inflight_count() >= max_backlog
            ):
                self.stats.rejected += 1
                return None
            qid = self._next_id
            self._next_id += 1
            self._queue.append(
                ReduceQuery(query_id=qid, x=x, cfg=cfg, cost=cost,
                            method=method, downstream=downstream,
                            execute_downstream=execute_downstream,
                            fingerprint=fp, prefix_fps=prefix_fps,
                            deadline_t=(
                                None if deadline_s is None
                                else time.perf_counter() + float(deadline_s)
                            ))
            )
            self.stats.queries += 1
        return qid

    def _reject_invalid(self, x: np.ndarray, method: str) -> int:
        """Input-poisoning guard: finish a NaN/Inf dataset immediately with
        ``error="invalid_input"`` — a poisoned suffix or dataset must never
        contaminate a shared cached basis. The qid still allocates and the
        result notifies through ``_done_now``, so blocked waiters and the
        ingest result path see a normal (failed) completion."""
        d = x.shape[1] if x.ndim == 2 else 0
        with self._lock:
            qid = self._next_id
            self._next_id += 1
            self.stats.queries += 1
            self.stats.invalid_inputs += 1
            self.stats.failures += 1
            self._results[qid] = ServeResult(
                query_id=qid,
                result=ReduceResult(
                    v=np.zeros((d, 0), np.float32),
                    mean=np.zeros(d, np.float32),
                    k=0, tlb_estimate=0.0, satisfied=False, runtime_s=0.0,
                    iterations=[], method=method,
                ),
                error="invalid_input",
            )
            self._done_now.append(qid)
        return qid

    def tenant_estimate_s(self, fp: str) -> float | None:
        """Expected compute seconds to serve this fingerprint again (EWMA
        over its past serves — after the first serve that is typically the
        revalidation cost, not a refit). ``None`` for unseen tenants; the
        ingest frontend falls back to its global mean then."""
        with self._lock:
            return self._tenant_ewma.get(fp)

    def backlog(self) -> int:
        """Queued + in-flight + mid-step queries (ingest backpressure gauge)."""
        with self._lock:
            return len(self._queue) + self._inflight_count()

    def take_result(self, qid: int) -> ServeResult | None:
        """Pop one finished result by query id (None while still pending)."""
        with self._lock:
            return self._results.pop(qid, None)

    # ------------------------------------------------------ delta serving

    def subscribe(self, query: SubscribeQuery) -> int:
        """Open a subscription: serve ``query.x`` once (a normal reduction
        through the scheduler) and then push deltas as appends arrive. The
        first delta is always a ``rollback`` with ``reason="subscribe"``
        carrying the bootstrap state. Returns the subscription id."""
        x = np.ascontiguousarray(np.asarray(query.x), dtype=np.float32)
        if x.ndim != 2:
            raise ValueError(f"expected (m, d) dataset, got shape {x.shape}")
        if not np.all(np.isfinite(x)):
            with self._lock:
                self.stats.invalid_inputs += 1
            raise ValueError("invalid_input: dataset contains NaN/Inf")
        query.x = x
        with self._lock:
            sid = self._next_sub_id
            self._next_sub_id += 1
            sub = _Subscription(sub_id=sid, query=query, x=x)
            self._subs[sid] = sub
            self.stats.subscriptions += 1
            # journal before the caller can observe the sid: a crash after
            # subscribe() returned always finds the stream in the WAL
            if self._persist is not None:
                self._persist.wal.journal_subscribe(sid, query)
            # submit + boot-map registration are one critical section (the
            # lock is reentrant), so a concurrent drain thread cannot finish
            # the bootstrap query before _notify knows it belongs to a sub
            qid = self.try_submit(x, query.cfg, None, method=query.method)
            sub.boot_qid = qid
            self._sub_boot[qid] = sub
        return sid

    def append(self, sub_id: int, suffix: np.ndarray) -> None:
        """Queue appended rows for a subscription. The scheduler folds them
        in as one delta (consecutive appends between ticks batch); the
        subscriber sees either an O(suffix) ``append`` delta or a
        ``rollback`` when the basis had to move."""
        suffix = np.ascontiguousarray(np.asarray(suffix), dtype=np.float32)
        if not np.all(np.isfinite(suffix)):
            with self._lock:
                self.stats.invalid_inputs += 1
            raise ValueError("invalid_input: suffix contains NaN/Inf")
        with self._lock:
            sub = self._subs.get(sub_id)
            if sub is None or sub.state == "closed":
                raise SubscriptionClosed(f"subscription {sub_id} is closed")
            if suffix.ndim != 2 or suffix.shape[1] != sub.x.shape[1]:
                raise ValueError(
                    f"suffix shape {suffix.shape} does not extend a "
                    f"{sub.x.shape[1]}-dim subscription"
                )
            if suffix.shape[0] == 0:
                return
            # journal-then-queue: a crash after the fsync'd record recovers
            # the suffix; a crash before it means the append never happened
            # — either way server and journal agree
            if self._persist is not None:
                self._persist.wal.journal_append(sub_id, suffix)
            sub.pending_suffixes.append(suffix)
            self._maybe_schedule_delta(sub)

    def poll_deltas(self, sub_id: int, max_n: int | None = None) -> list:
        """Pop emitted deltas for a subscription, in sequence order, at most
        once. Unknown ids raise KeyError (a closed-and-drained subscription
        stays known until the process ends — ids are never reused)."""
        with self._lock:
            sub = self._subs.get(sub_id)
            if sub is None:
                raise KeyError(f"unknown subscription {sub_id}")
            out: list = []
            while sub.deltas and (max_n is None or len(out) < max_n):
                out.append(sub.deltas.popleft())
            # journal the client-visible frontier BEFORE handing the deltas
            # over: recovery resumes numbering at the last acked seq, so a
            # delta emitted but never polled is lost WITH the process — and
            # the client, who never saw it, still receives a contiguous
            # stream (the recover rollback restates everything anyway)
            if out and self._persist is not None:
                self._persist.wal.journal_ack(sub_id, out[-1]["seq"] + 1)
            return out

    def unsubscribe(self, sub_id: int, *, force: bool = False) -> None:
        """Close a subscription: drops queued suffixes and emits a final
        ``closed`` delta. With work in flight the close is deferred until
        the flight lands (its delta still delivers, then the close) unless
        ``force=True``, which closes immediately and discards the in-flight
        emission — the drain path uses force so no subscription can hold
        ``close()`` hostage."""
        with self._lock:
            sub = self._subs.get(sub_id)
            if sub is None or sub.state == "closed":
                return
            sub.pending_suffixes.clear()
            if (sub.inflight or sub.state == "pending") and not force:
                sub.close_requested = True
                notify = None
            else:
                notify = self._emit(sub, {"kind": CLOSED, "error": None})
        self._fire_deltas([] if notify is None else [notify])

    def live_subscriptions(self) -> list[int]:
        with self._lock:
            return [
                sid for sid, sub in self._subs.items()
                if sub.state != "closed"
            ]

    def _maybe_schedule_delta(self, sub: _Subscription) -> None:
        """Schedule ONE append work item when the subscription is live, has
        queued suffixes, and nothing for it is in flight (the chain is
        strictly serial per subscription). Caller holds the lock."""
        if (
            sub.state != "live"
            or sub.inflight
            or sub.close_requested
            or not sub.pending_suffixes
        ):
            return
        item = _DeltaServe(
            sub=sub, kind="append", t0=time.perf_counter(),
            suffixes=list(sub.pending_suffixes),
        )
        sub.pending_suffixes.clear()
        sub.inflight = True
        self._place_validation(item)  # sharded: pick a device
        self._validations.append(item)

    def _emit(self, sub: _Subscription, delta: dict) -> int | None:
        """Sequence-stamp and queue one delta; returns the sub id to notify
        (None when the subscription already closed — emissions from a
        stranded in-flight item are dropped, preserving at-most-once with
        ``closed`` terminal). Caller holds the lock."""
        if sub.state == "closed":
            return None
        delta["seq"] = sub.seq
        sub.seq += 1
        sub.deltas.append(delta)
        if delta["kind"] == CLOSED:
            sub.state = "closed"
            sub.error = delta.get("error")
            sub.pending_suffixes.clear()
            if self._persist is not None:
                # every terminal path (unsubscribe, bootstrap failure,
                # compute error) funnels through this emit: journal the end
                # so recovery never resurrects a stream the client saw die.
                # When the last live stream closes, compact the journal to
                # empty — the WAL never carries dead history across runs.
                self._persist.wal.journal_end(sub.sub_id)
                if not any(
                    s.state != "closed" for s in self._subs.values()
                ):
                    self._persist.wal.compact([])
        return sub.sub_id

    def _fire_deltas(self, sub_ids: list[int]) -> None:
        """Fire the delta hook with no scheduler lock held (same lock-order
        contract as ``_notify``)."""
        if self.on_delta is not None:
            for sid in sub_ids:
                self.on_delta(sid)

    # ------------------------------------------------------ cache serving

    def _validation_bucket(self, val: _Validation) -> ShapeBucketCache:
        """Bucket cache for a validation's shapes (the sharded subclass
        returns the device class's cache, matching the fits on that class)."""
        return self.bucket

    def _validate(self, val: _Validation) -> tuple[bool, ReduceResult | None]:
        """Revalidate a cached basis on the live data: sampled TLB, no
        fit_basis call anywhere — this is the §5 reuse win. Device compute:
        runs OUTSIDE the scheduler lock, like a runner iteration."""
        q, entry = val.query, val.entry
        bucket = self._validation_bucket(val)
        tv = time.perf_counter()  # validation compute (excludes queue wait)
        # shared rank-bucket padding: hit shapes coincide with fit shapes
        v = bucket.pad_basis(entry.v, min(q.x.shape))
        est = TLBEstimator(
            np.ascontiguousarray(q.x, dtype=np.float32),
            jnp.asarray(v),
            np.random.default_rng(q.cfg.seed + 1),
            confidence=q.cfg.confidence,
            use_kernels=q.cfg.use_kernels,
            bucket=bucket,
        )
        e = est.estimate_at_k(
            entry.k,
            q.cfg.target_tlb,
            initial_pairs=q.cfg.initial_pairs,
            max_pairs=q.cfg.max_pairs,
        )
        with self._lock:
            self.stats.validation_pairs += e.pairs_used
            self.stats.tlb_rounds += e.rounds
        if e.mean < q.cfg.target_tlb:
            return False, None  # stale (near-repeat drifted): fall to cold
        # runtime_s stays compute-only (matching the cold path's semantics);
        # ServeResult.wall_s carries queue wait + deferral
        return True, ReduceResult(
            v=entry.v,
            mean=entry.mean,
            k=entry.k,
            tlb_estimate=e.mean,
            satisfied=True,
            runtime_s=time.perf_counter() - tv,
            iterations=[],
            method=entry.method,
        )

    # -------------------------------------------------------- scheduling

    def _admit(self) -> None:
        """Move queued queries into flight (cold runners) or into the
        validation queue (cache hits, revalidated outside the lock).

        A query whose dataset is already being fitted or validated in flight
        (same method) is deferred: when the running tenant finishes, its map
        lands in the cache and the deferred repeat is served by validation
        instead of a duplicate cold fit (the §5 reuse case under
        concurrency). Each admitted query advances the cache TTL clock by
        one tick, so a TTL counts serving decisions — independent of
        drain-thread count and of idle polling."""
        deferred: deque[ReduceQuery] = deque()
        while self._queue and self._inflight_count() < self.max_inflight:
            q = self._queue.popleft()
            now = time.perf_counter()
            if self._route(q, now):
                self.stats.queue_wait_s += now - q.t_enq
            else:
                deferred.append(q)
        self._queue.extendleft(reversed(deferred))  # keep submission order

    def _route(self, q: ReduceQuery, now: float) -> bool:
        """Route one dequeued query (``now``: its dequeue time): expire it,
        serve it degraded, queue its cache-hit work item or launch it cold.
        False defers it: its tenant is in flight. Caller holds the lock."""
        if q.t0 is None:
            q.t0 = now
        t0, fp = q.t0, q.fingerprint
        if q.deadline_t is not None and now > q.deadline_t:
            self._expire(q, t0)  # dead on dequeue: no compute for it
            return True
        use_cache = self.enable_cache and method_cacheable(q.method)
        if q.query_id in self._refresh_qids:
            # background refresh: always a fresh fit — the exact-hit
            # and degraded paths would re-serve the very staleness
            # this query exists to clear. Dedup still applies so a
            # concurrent real fit isn't duplicated.
            if use_cache and self._fingerprint_inflight(fp, q.method):
                return False
            self.cache.tick()
            self._launch_cold(q, fp, t0)
            return True
        if (
            use_cache
            and self.degrade_watermark_s is not None
            and self._queue_delay_locked() > self.degrade_watermark_s
        ):
            # under pressure: ANY cached basis for this fingerprint —
            # expired TTL, prefix-only match, or a satisfying coarser
            # method — serves immediately (stale, no revalidation)
            # rather than queueing fresh compute behind the backlog
            entry = self.cache.find_degraded(
                fp, q.prefix_fps, q.cfg.target_tlb, q.method
            )
            if entry is not None:
                self._serve_degraded(q, entry, t0)
                return True
        if use_cache and self._fingerprint_inflight(fp, q.method):
            return False
        self.cache.tick()
        if use_cache:
            entry = self.cache.get_exact(fp, q.cfg.target_tlb, q.method)
            prefix = False
            if entry is None:
                # append-only stream: a cached map fitted on a prefix of
                # this dataset (hashed at submit time) is revalidated on
                # the grown data instead of refitting cold
                entry = self.cache.find_prefix(
                    q.prefix_fps, q.cfg.target_tlb, q.method
                )
                prefix = entry is not None
            if entry is not None:
                val = self._route_hit(q, entry, fp, t0, prefix)
                self._place_validation(val)  # sharded: pick a device
                self._validations.append(val)
                return True
        self._launch_cold(q, fp, t0)
        return True

    def _route_hit(self, q, entry, fp, t0, prefix):
        """Turn a cache hit into its work item. Normally a revalidation —
        but a prefix match whose suffix exceeds the drift budget skips it
        and goes straight to the incremental subspace update (revalidating
        a map that predates that much new data mostly buys a failed
        validation before the same update runs anyway)."""
        if prefix and self._suffix_updatable(q, entry):
            if q.x.shape[0] - entry.rows > self.suffix_budget * entry.rows:
                return _SuffixUpdate(q, entry, fp, t0)
        return _Validation(q, entry, fp, t0, prefix=prefix)

    def queue_delay_estimate(self) -> float:
        """Estimated seconds a newly admitted query would queue: backlog
        depth x recent per-serve wall EWMA (0.0 until a serve lands). The
        ingest front-end's adaptive admission and the degradation ladder
        both key off this."""
        with self._lock:
            return self._queue_delay_locked()

    def _queue_delay_locked(self) -> float:
        if self._serve_ewma is None:
            return 0.0
        return (len(self._queue) + self._inflight_count()) * self._serve_ewma

    def _observe_wall(self, wall_s: float) -> None:
        """Fold one non-degraded serve's wall into the EWMA behind the
        queue-delay estimate. Caller holds the lock."""
        self._serve_ewma = (
            wall_s
            if self._serve_ewma is None
            else 0.7 * self._serve_ewma + 0.3 * wall_s
        )

    def _observe_tenant(self, fp: str, sr: ServeResult) -> None:
        """Per-tenant serve-cost EWMA keyed by fingerprint, in COMPUTE
        seconds (``result.runtime_s``) — queue wait is the frontend's own
        admission term, so folding it in here would double-count it.
        Bounded LRU over 256 tenants. Caller holds the lock."""
        if not fp:
            return
        cost = sr.result.runtime_s if sr.result is not None else sr.wall_s
        prev = self._tenant_ewma.pop(fp, None)
        self._tenant_ewma[fp] = (
            cost if prev is None else 0.7 * prev + 0.3 * cost
        )
        while len(self._tenant_ewma) > 256:
            self._tenant_ewma.popitem(last=False)

    def _schedule_refresh(
        self, q: ReduceQuery, entry: BasisCacheEntry
    ) -> None:
        """Background self-heal for a repeatedly-degraded-served entry:
        enqueue ONE internal refit (per fingerprint+method) of the
        triggering query's dataset. The result is discarded in ``_notify``
        — the fresh cache re-registration is the point. ``_admit`` routes
        refresh queries straight to a cold launch, bypassing the exact-hit
        and degraded paths that would re-serve the very staleness being
        cleared. Caller holds the lock."""
        key = (q.fingerprint, q.method)
        if key in self._refresh_inflight:
            return
        self._refresh_inflight.add(key)
        entry.degraded_serves = 0
        self.stats.degraded_refreshes += 1
        qid = self._next_id
        self._next_id += 1
        self._refresh_qids[qid] = key
        self._queue.append(
            ReduceQuery(
                query_id=qid, x=q.x, cfg=q.cfg, cost=q.cost,
                method=q.method, fingerprint=q.fingerprint,
                prefix_fps=dict(q.prefix_fps),
            )
        )

    def _serve_degraded(
        self, q: ReduceQuery, entry: BasisCacheEntry, t0: float
    ) -> None:
        """Serve a stale/cheaper cached basis immediately — no device
        compute, no revalidation: DROP's quality/cost tradeoff applied at
        serve time. ``result.tlb_estimate`` is the entry's RECORDED
        estimate (honest provenance, possibly stale), and
        ``ServeResult.degraded`` marks the serve so clients can refetch
        once pressure clears. Caller holds the lock."""
        res = ReduceResult(
            v=entry.v,
            mean=entry.mean,
            k=entry.k,
            tlb_estimate=entry.tlb_estimate,
            satisfied=entry.satisfied,
            runtime_s=0.0,
            iterations=[],
            method=entry.method,
        )
        self.stats.degraded_serves += 1
        self.stats.cache_hits += 1
        entry.degraded_serves += 1
        if (
            self.degrade_refresh_after is not None
            and entry.degraded_serves >= self.degrade_refresh_after
        ):
            self._schedule_refresh(q, entry)
        self._commit(
            ServeResult(
                query_id=q.query_id,
                result=res,
                cache_hit=True,
                degraded=True,
                wall_s=time.perf_counter() - t0,
            ),
            q,
            t0,
        )

    def _expire(self, q: ReduceQuery, t0: float | None = None) -> None:
        """Finish a query past its deadline with ``error="deadline"`` —
        expired queries never reach the device. Caller holds the lock; the
        result notifies through ``_done_now`` like any commit, so a
        blocked ``result()`` waiter always wakes."""
        d = q.x.shape[1]
        self.stats.deadline_expired += 1
        self.stats.failures += 1
        base = t0 if t0 is not None else (q.t0 or time.perf_counter())
        self._results[q.query_id] = ServeResult(
            query_id=q.query_id,
            result=ReduceResult(
                v=np.zeros((d, 0), np.float32),
                mean=np.zeros(d, np.float32),
                k=0, tlb_estimate=0.0, satisfied=False, runtime_s=0.0,
                iterations=[], method=q.method,
            ),
            wall_s=time.perf_counter() - base,
            error="deadline",
        )
        self._done_now.append(q.query_id)

    def _expire_if_due(self, work) -> bool:
        """Deadline check between scheduling and compute: a popped work
        item whose query expired is retired here — between runner steps a
        rotated runner re-pops, so mid-flight queries are re-checked every
        step. A ``_Downstream`` item keeps its finished reduction (the map
        is good) but the query still finishes with ``error="deadline"``;
        delta items carry no deadline. Returns True when expired."""
        if isinstance(work, _DeltaServe):
            return False
        q = work.query
        if q.deadline_t is None or time.perf_counter() <= q.deadline_t:
            return False
        with self._lock:
            self._stepping_now.remove(work)
            if isinstance(work, _Downstream):
                sr = work.base
                sr.error = "deadline"
                sr.wall_s = time.perf_counter() - work.t0
                self.stats.deadline_expired += 1
                self.stats.failures += 1
                self._results[q.query_id] = sr
                self._done_now.append(q.query_id)
            else:
                self._expire(q, work.t0)
        return True

    def _suffix_updatable(self, q: ReduceQuery, entry: BasisCacheEntry) -> bool:
        """Whether ``entry`` carries updater state that can absorb this
        query's suffix (tracker rows must mark exactly the entry's prefix)."""
        return (
            self.enable_suffix_update
            and entry.tracker is not None
            and entry.tracker.rows == entry.rows
            and entry.method == q.method
        )

    def _place_validation(self, val) -> None:
        """Assign a device to a pending validation or suffix update (no-op
        on one device; the sharded subclass load-balances it like a runner)."""

    def _launch_cold(
        self,
        q: ReduceQuery,
        fp: str,
        t0: float,
        fallback_warm_k: int | None = None,
    ) -> None:
        """Warm-start bookkeeping + runner launch. ``fallback_warm_k``
        carries the rank of a prefix-matched entry that failed revalidation
        (the full-fingerprint lookup cannot see it). Caller holds the lock."""
        use_cache = self.enable_cache and method_cacheable(q.method)
        warm_k = (
            self.cache.get_warm_k(fp, q.cfg.target_tlb, q.method)
            if use_cache
            else None
        )
        if warm_k is None:
            warm_k = fallback_warm_k
        # misses count failed lookups, so only when the cache could have
        # served this query; a warm start is a warm start, not also a miss
        if warm_k is not None:
            self.stats.warm_starts += 1
        elif use_cache:
            self.stats.cache_misses += 1
        self._launch(q, fp, warm_k, t0)

    def _inflight_count(self) -> int:
        return (
            len(self._inflight)
            + len(self._validations)
            + len(self._stepping_now)
        )

    def _fingerprint_inflight(self, fp: str, method: str) -> bool:
        return any(
            fl.fingerprint == fp and fl.query.method == method
            for fl in self._iter_inflight()
        )

    def _iter_inflight(self):
        """All live work: placed runners (the sharded subclass adds
        per-device queues), queued validations, and anything mid-compute
        outside the lock."""
        yield from self._inflight
        yield from self._validations
        yield from self._stepping_now

    def _launch(
        self, q: ReduceQuery, fp: str, warm_k: int | None, t0: float
    ) -> None:
        """Build the method's Reducer and place it in flight. The sharded
        subclass overrides this to pick a mesh device and its per-class
        bucket."""
        runner = make_reducer(
            q.method, q.x, q.cfg, q.cost, warm_prev_k=warm_k,
            bucket=self.bucket,
        )
        self._inflight.append(
            _InFlight(q, runner, fp, warm_started=warm_k is not None, t0=t0)
        )

    def _commit(self, sr: ServeResult, q: ReduceQuery, t0: float) -> None:
        """Retire a query's reduction result: either finish it outright, or
        — when the query asked for executed analytics and the reduction
        produced a usable map — hold the result and schedule a
        ``_Downstream`` work item (off-lock device compute, load-balanced
        by the sharded subclass like any validation). Caller holds the
        lock; finished ids queue on ``_done_now`` for the tick to notify."""
        if sr.error is None and not sr.degraded:
            # degraded serves are ~free and would mask pressure; errors
            # carry no serving cost signal — neither feeds the EWMA
            self._observe_wall(sr.wall_s)
            self._observe_tenant(q.fingerprint, sr)
        if (
            q.execute_downstream
            and q.downstream is not None
            and sr.error is None
        ):
            ds = _Downstream(q, sr, t0)
            self._place_validation(ds)  # sharded: pick a device
            self._validations.append(ds)
            return
        self._results[q.query_id] = sr
        self._done_now.append(q.query_id)

    def _finish(self, fl: _InFlight) -> None:
        res = fl.runner.result()
        self.stats.fit_calls += fl.runner.fit_calls
        self.stats.iterations += len(res.iterations)
        self._commit(
            ServeResult(
                query_id=fl.query.query_id,
                result=res,
                warm_started=fl.warm_started,
                wall_s=time.perf_counter() - fl.t0,
            ),
            fl.query,
            fl.t0,
        )
        if res.satisfied and self.enable_cache and fl.runner.cacheable:
            tracker = None
            if self.enable_suffix_update and getattr(
                fl.runner, "supports_update", False
            ):
                # memoized by the off-lock priming in _step; the guard
                # matches it — a failing bootstrap costs the entry its
                # incremental path, never the drain
                try:
                    tracker = fl.runner.tracker()
                except Exception:
                    tracker = None
            self.cache.put(
                fl.fingerprint,
                BasisCacheEntry(
                    v=res.v,
                    mean=res.mean,
                    k=res.k,
                    target_tlb=fl.query.cfg.target_tlb,
                    tlb_estimate=res.tlb_estimate,
                    satisfied=True,
                    method=fl.query.method,
                    rows=fl.query.x.shape[0],
                    tracker=tracker,
                ),
            )

    def _fail(self, fl: _InFlight, exc: BaseException) -> None:
        """A runner iteration raised: finish the query with the best basis
        found so far (or an empty one) and keep the scheduler alive. Caller
        holds the lock."""
        try:
            res = fl.runner.result()  # valid once one iteration completed
        except Exception:
            d = fl.query.x.shape[1]
            res = ReduceResult(
                v=np.zeros((d, 0), np.float32), mean=np.zeros(d, np.float32),
                k=0, tlb_estimate=0.0, satisfied=False, runtime_s=0.0,
                iterations=list(fl.runner.records), method=fl.query.method,
            )
        self.stats.failures += 1
        self.stats.fit_calls += fl.runner.fit_calls
        self.stats.iterations += len(res.iterations)  # steps it did complete
        self._results[fl.query.query_id] = ServeResult(
            query_id=fl.query.query_id,
            result=res,
            warm_started=fl.warm_started,
            wall_s=time.perf_counter() - fl.t0,
            error=f"{type(exc).__name__}: {exc}",
        )

    # ------------------------------------------------- scheduling primitives

    def _pop_runner(self) -> _InFlight | None:
        """Next runner to step (round-robin). Caller holds the lock."""
        return self._inflight.popleft() if self._inflight else None

    def _pop_work(self):
        """Next unit of device compute: pending revalidations and suffix
        updates first (they are short and serve a waiting tenant), else a
        runner iteration. Caller holds the lock."""
        work = self._validations.popleft() if self._validations else self._pop_runner()
        if work is not None:
            self.stats.work_wait_s += time.perf_counter() - work.t_ready
        return work

    def _requeue_runner(self, fl: _InFlight) -> None:
        """Rotate a still-live runner back into flight. Caller holds the lock."""
        self._inflight.append(fl)

    def _discard_runner(self, fl: _InFlight) -> None:
        """Drop a runner from flight wherever it is queued (abandon path).
        Caller holds the lock."""
        try:
            self._inflight.remove(fl)
        except ValueError:
            pass

    def _step(self, fl: _InFlight) -> bool:
        """Run one iteration of ``fl`` outside the lock; returns liveness."""
        alive = fl.runner.step()
        if (
            not alive
            and self.enable_cache
            and self.enable_suffix_update
            and getattr(fl.runner, "supports_update", False)
        ):
            # prime the updater state here, off-lock: _finish (under the
            # scheduler lock) then attaches the memoized tracker for free.
            # Only satisfied results are cached, so an unsatisfiable query
            # must not pay the O(m·d·k) bootstrap for a tracker nobody keeps
            try:
                if fl.runner.result().satisfied:
                    fl.runner.tracker()
            except Exception:
                pass  # no best basis (all steps raised): nothing to track
        label = "default" if fl.device is None else str(fl.device)
        with self._lock:
            self.stats.device_iterations[label] = (
                self.stats.device_iterations.get(label, 0) + 1
            )
        return alive

    def _work_remains(self) -> bool:
        return bool(self._queue or self._inflight_count())

    def _notify(self, qids: list[int]) -> None:
        """Fire the ingest hook with no scheduler lock held (lock order is
        always hook-side-lock -> scheduler-lock, never the reverse).

        A finished query that bootstraps a subscription is consumed here
        instead of notified: its result becomes a scheduled ``_DeltaServe``
        (the subscribe rollback), so subscribers never see the internal
        query id. A finished background refresh (degraded-serve self-heal)
        is likewise consumed: its result is discarded — the cache
        re-registration its fit performed is the whole point."""
        for qid in qids:
            with self._lock:
                sub = self._sub_boot.pop(qid, None)
                rkey = self._refresh_qids.pop(qid, None)
                if rkey is not None:
                    self._refresh_inflight.discard(rkey)
                    self._results.pop(qid, None)
            if rkey is not None:
                continue
            if sub is not None:
                self._bootstrap_subscription(sub, qid)
                continue
            if self.on_result is not None:
                self.on_result(qid)

    def _bootstrap_subscription(self, sub: _Subscription, qid: int) -> None:
        """The subscription's reduction finished: schedule the bootstrap
        delta compute, or close the subscription if the reduction errored
        (or an unsubscribe won the race)."""
        sr = self.take_result(qid)
        notify: list[int] = []
        with self._lock:
            if sub.state == "closed":
                pass  # force-unsubscribed while bootstrapping: nothing to do
            elif sr is None or sr.error is not None:
                error = "bootstrap result missing" if sr is None else sr.error
                self.stats.failures += 1
                notify_id = self._emit(sub, {"kind": CLOSED, "error": error})
                if notify_id is not None:
                    notify.append(notify_id)
            else:
                item = _DeltaServe(
                    sub=sub, kind="bootstrap", t0=time.perf_counter(), base=sr
                )
                sub.inflight = True
                self._place_validation(item)  # sharded: pick a device
                self._validations.append(item)
        self._fire_deltas(notify)

    def _run_validation(self, val: _Validation, done: list[int]) -> None:
        """Execute one revalidation outside the lock and commit the verdict:
        a pass serves the cached map (a prefix match is additionally
        re-registered under the grown dataset's fingerprint — with the
        suffix folded into its updater state — so the stream's next append
        matches again), a failed PREFIX validation escalates to an
        incremental suffix update when the entry carries updater state, and
        only otherwise falls through to a cold launch (with warm-start
        bookkeeping; a failed prefix entry still seeds the warm rank
        bound). Verdicts feed the cache's TTL auto-tuner."""
        errored = False
        tv = time.perf_counter()
        try:
            passed, result = self._validate(val)
        except Exception:
            # a broken entry must not serve — but an infrastructure error is
            # NOT a drift observation, so it stays out of the TTL tuner; it
            # is counted, so the cold refit it falls to never hides it
            passed, result, errored = False, None, True
        validate_s = time.perf_counter() - tv
        q = val.query
        new_tracker = None
        if passed and val.prefix and self._suffix_updatable(q, val.entry):
            # fold the validated suffix into the updater state (pure merge:
            # the shared entry never mutates), still outside the lock, so
            # the stream's NEXT append keeps its incremental path
            try:
                new_tracker = val.entry.tracker.merge(
                    q.x[val.entry.rows :], val.entry.tracker.width
                )
            except Exception:
                new_tracker = None  # re-register without updater state
        with self._lock:
            self._stepping_now.remove(val)
            self.stats.validate_s += validate_s
            if errored:
                self.stats.validation_errors += 1
            else:
                self.cache.note_validation(passed)
            self.stats.effective_ttl = self.cache.ttl_ticks
            if passed:
                self.stats.cache_hits += 1
                # a crash-recovered entry that cleared the live-data TLB
                # gate is re-admitted fully: the degraded ladder may serve
                # it again (this validation is the check it was waiting on)
                val.entry.recovered = False
                if val.prefix:
                    self.stats.prefix_hits += 1
                    self.cache.put(
                        val.fingerprint,
                        BasisCacheEntry(
                            v=val.entry.v,
                            mean=val.entry.mean,
                            k=val.entry.k,
                            target_tlb=q.cfg.target_tlb,
                            tlb_estimate=result.tlb_estimate,
                            satisfied=True,
                            method=val.entry.method,
                            rows=q.x.shape[0],
                            tracker=new_tracker,
                        ),
                    )
                self._commit(
                    ServeResult(
                        query_id=q.query_id,
                        result=result,
                        cache_hit=True,
                        prefix_hit=val.prefix,
                        wall_s=time.perf_counter() - val.t0,
                    ),
                    q,
                    val.t0,
                )
            elif (
                not errored
                and val.prefix
                and self._suffix_updatable(q, val.entry)
            ):
                # drift observed on an append-only stream: repair the map
                # from the suffix before giving up on reuse entirely. An
                # ERRORED validation is different — a broken entry would
                # break the merge the same way, so it keeps the guaranteed
                # cold-refit fallback
                upd = _SuffixUpdate(q, val.entry, val.fingerprint, val.t0)
                self._place_validation(upd)
                self._validations.append(upd)
            else:
                self._launch_cold(
                    q, val.fingerprint, val.t0,
                    fallback_warm_k=(
                        val.entry.k
                        if val.prefix and val.entry.satisfied
                        else None
                    ),
                )

    def _apply_suffix_update(self, upd: _SuffixUpdate):
        """Device compute for one suffix update (outside the lock): merge
        the appended rows into the cached updater state and TLB-gate the
        smallest satisfying rank on the grown data. The sharded subclass
        wraps this in the work item's device scope."""
        return subspace_suffix_update(
            upd.entry.tracker,
            upd.query.x,
            upd.query.cfg,
            bucket=self._validation_bucket(upd),
        )

    def _run_suffix_update(self, upd: _SuffixUpdate, done: list[int]) -> None:
        """Execute one incremental subspace update outside the lock and
        commit: a TLB-satisfying merge serves the query and re-registers
        the cache entry (updated map + updater state) under the grown
        fingerprint; a failed gate falls through to the cold-refit last
        resort; an update that RAISES finishes the query with
        ``ServeResult.error`` set — never wedging the drain."""
        q = upd.query
        error, tracker, result, pairs = None, None, None, 0
        try:
            tracker, result, pairs = self._apply_suffix_update(upd)
        except Exception as exc:
            error = f"{type(exc).__name__}: {exc}"
        with self._lock:
            self._stepping_now.remove(upd)
            self.stats.validation_pairs += pairs
            if error is not None:
                self.stats.failures += 1
                self.stats.suffix_update_failures += 1
                d = q.x.shape[1]
                res = ReduceResult(
                    v=np.zeros((d, 0), np.float32),
                    mean=np.zeros(d, np.float32),
                    k=0, tlb_estimate=0.0, satisfied=False, runtime_s=0.0,
                    iterations=[], method=q.method,
                )
                self._results[q.query_id] = ServeResult(
                    query_id=q.query_id,
                    result=res,
                    wall_s=time.perf_counter() - upd.t0,
                    error=error,
                )
                done.append(q.query_id)
            elif result.satisfied:
                self.stats.suffix_updates += 1
                self.cache.put(
                    upd.fingerprint,
                    BasisCacheEntry(
                        v=result.v,
                        mean=result.mean,
                        k=result.k,
                        target_tlb=q.cfg.target_tlb,
                        tlb_estimate=result.tlb_estimate,
                        satisfied=True,
                        method=q.method,
                        rows=q.x.shape[0],
                        tracker=tracker,
                    ),
                )
                self._commit(
                    ServeResult(
                        query_id=q.query_id,
                        result=result,
                        suffix_update=True,
                        wall_s=time.perf_counter() - upd.t0,
                    ),
                    q,
                    upd.t0,
                )
            else:
                # the suffix outgrew the tracked headroom: cold refit is the
                # last resort, warm-started from the entry's known-good rank
                self.stats.suffix_update_failures += 1
                self._launch_cold(
                    q, upd.fingerprint, upd.t0,
                    fallback_warm_k=(
                        upd.entry.k if upd.entry.satisfied else None
                    ),
                )

    def _apply_downstream(self, ds: _Downstream):
        """Device compute for one served-analytics run (outside the lock):
        project the dataset through the finished map and execute the named
        task via the optimizer's registry — same code path, same analytics
        knobs (``split``/``fanout``/``devices``) as ``WorkloadOptimizer``.
        The sharded subclass wraps this in the work item's device scope (or
        lets the mesh fan-out claim the whole mesh)."""
        from repro.pipeline.optimizer import run_downstream

        blocks = transform_blocks(ds.query.x.shape)
        t = time.perf_counter()
        with span("drop.transform", qid=ds.query.query_id, blocks=blocks):
            xt = ds.base.result.transform(ds.query.x)
        ds.transform_s = time.perf_counter() - t
        ds.transform_blocks = blocks
        return run_downstream(
            ds.query.downstream,
            xt,
            use_kernels=ds.query.cfg.use_kernels,
            split=self.analytics_split,
            fanout=self.analytics_fanout,
            devices=self.analytics_devices,
        )

    def _run_downstream(self, ds: _Downstream, done: list[int]) -> None:
        """Execute one served analytics task outside the lock and commit:
        the output lands on the ALREADY-FINISHED reduction result
        (``ServeResult.downstream``); a raising run sets
        ``ServeResult.error`` but keeps the map — the reduction itself
        succeeded, only the analytics leg failed."""
        t_ds = time.perf_counter()
        out, error = None, None
        try:
            out = self._apply_downstream(ds)
        except Exception as exc:
            error = f"downstream: {type(exc).__name__}: {exc}"
        downstream_s = time.perf_counter() - t_ds
        q = ds.query
        with self._lock:
            self._stepping_now.remove(ds)
            sr = ds.base
            sr.downstream = out
            sr.downstream_s = downstream_s
            sr.wall_s = time.perf_counter() - ds.t0
            self.stats.downstream_s += downstream_s
            self.stats.transform_s += ds.transform_s
            self.stats.transform_blocked += int(ds.transform_blocks > 1)
            if error is None:
                self.stats.downstream_runs += 1
                if self._mesh_shards:
                    self.stats.mesh_scans += 1
                    self.stats.mesh_shards += self._mesh_shards
            else:
                sr.error = error
                self.stats.downstream_failures += 1
                self.stats.failures += 1
            self._results[q.query_id] = sr
            done.append(q.query_id)

    def _revalidate_basis(
        self,
        grown: np.ndarray,
        served: ReduceResult,
        cfg: DropConfig,
        bucket: ShapeBucketCache,
    ) -> tuple[bool, int, float]:
        """Sampled TLB of the SERVED map on the grown data — the same gate
        ``_validate`` applies to cache hits, reused as the delta protocol's
        quality check. Returns (passed, pairs_used, tlb_mean)."""
        v = bucket.pad_basis(served.v, min(grown.shape))
        est = TLBEstimator(
            grown,
            jnp.asarray(v),
            np.random.default_rng(cfg.seed + 1),
            confidence=cfg.confidence,
            use_kernels=cfg.use_kernels,
            bucket=bucket,
        )
        e = est.estimate_at_k(
            served.k,
            cfg.target_tlb,
            initial_pairs=cfg.initial_pairs,
            max_pairs=cfg.max_pairs,
        )
        return e.mean >= cfg.target_tlb, e.pairs_used, float(e.mean)

    def _cold_refit_for(
        self,
        sub: _Subscription,
        grown: np.ndarray,
        served: ReduceResult,
        bucket: ShapeBucketCache,
    ) -> tuple[ReduceResult, object]:
        """Run a warm-started cold refit to completion for a subscription
        whose suffix outgrew every incremental path (the same last resort
        the request/response ladder ends in). Returns (result, tracker)."""
        sq = sub.query
        runner = make_reducer(
            sq.method, grown, sq.cfg, None,
            warm_prev_k=served.k if served.satisfied else None,
            bucket=bucket,
        )
        while runner.step():
            pass
        res = runner.result()
        tracker = None
        if self.enable_suffix_update and getattr(
            runner, "supports_update", False
        ):
            try:
                tracker = runner.tracker()
            except Exception:
                tracker = None
        with self._lock:
            self.stats.fit_calls += runner.fit_calls
            self.stats.iterations += len(res.iterations)
        return res, tracker

    def _apply_delta(self, item: _DeltaServe) -> tuple[dict, dict]:
        """Device compute for one delta (outside the lock): produce the
        delta dict plus the subscription-state updates the commit section
        applies under the lock. The sharded subclass wraps this in the work
        item's device scope."""
        if item.kind == "bootstrap":
            return self._delta_bootstrap(item)
        return self._delta_append(item)

    def _delta_bootstrap(self, item: _DeltaServe) -> tuple[dict, dict]:
        """Build the subscribe rollback: transform the dataset through the
        freshly served map, cold-build the incremental analytics state, and
        bootstrap the subspace tracker the append gate will merge into."""
        from repro.analytics.incremental import IncrementalAnalytics

        sub = item.sub
        sq = sub.query
        res = item.base.result
        xt = res.transform(sub.x)
        analytics = IncrementalAnalytics(
            xt,
            eps=sq.eps,
            min_samples=sq.min_samples,
            bandwidth=sq.bandwidth,
            bucket=self._validation_bucket(item),
        )
        snap = analytics.snapshot()
        tracker = None
        if (
            sq.method == "pca"
            and self.enable_suffix_update
            and res.v.shape[1] > 0
        ):
            try:
                tracker = SubspaceTracker.from_fit(sub.x, res.v)
            except Exception:
                tracker = None  # costs the sub its incremental basis path
        delta = {
            "kind": ROLLBACK,
            # a WAL-resumed subscription's first rollback says "recover":
            # same full-restate shape, stamped at the acked seq frontier
            "reason": "recover" if sub.recover else "subscribe",
            "basis": res,
            "rows": xt,
            "knn": {"idx": snap.knn_idx, "d2": snap.knn_d2},
            "labels": snap.labels,
            "densities": snap.densities,
            "tlb": res.tlb_estimate,
            "rotation": 0.0,
            "wall_s": time.perf_counter() - item.t0,
        }
        updates = {
            "x": sub.x,
            "result": res,
            "tracker": tracker,
            "analytics": analytics,
            "rollback": True,
            "pairs": 0,
            "cache_put": None,
        }
        return delta, updates

    def _delta_append(self, item: _DeltaServe) -> tuple[dict, dict]:
        """Fold queued suffixes into the served state through the delta
        escalation ladder:

        1. merge the suffix into the subspace tracker (pure, O(suffix)) and
           read the rotation signal against the SERVED basis;
        2. rotation stable -> TLB-revalidate the served map on the grown
           data (the PR 3 gate); a pass emits an ``append`` delta — suffix
           transform + incremental analytics, all O(s*m);
        3. gate failed -> O(suffix) TLB-gated suffix update
           (``core.subspace``), a satisfying merge emits a ``rollback``
           (reason drift/headroom: the basis moved, downstream rebuilt);
        4. even that unsatisfied -> warm cold refit, ``rollback`` with
           reason "refit" — the same last resort as the query ladder.

        The subscription's fields are read without the lock: only the delta
        chain mutates them and at most one item per sub is in flight."""
        sub = item.sub
        sq = sub.query
        served = sub.result
        suffix = (
            item.suffixes[0]
            if len(item.suffixes) == 1
            else np.concatenate(item.suffixes)
        )
        grown = np.concatenate([sub.x, suffix])
        bucket = self._validation_bucket(item)
        m_old = sub.x.shape[0]
        pairs = 0
        rot = 0.0
        merged = None
        stable = False
        tlb_mean = served.tlb_estimate
        if sub.tracker is not None:
            cap = max(
                1,
                min(
                    grown.shape[1], grown.shape[0],
                    sub.tracker.width + TRACK_HEADROOM,
                ),
            )
            merged = sub.tracker.merge(suffix, cap)
            rot = merged.rotation_from(served.v)
        if merged is None or rot <= sq.rotation_tol:
            stable, pairs, tlb_mean = self._revalidate_basis(
                grown, served, sq.cfg, bucket
            )
        cacheable = self.enable_cache and method_cacheable(sq.method)
        if stable:
            # O(suffix) append: old transformed rows stay valid (row-wise
            # transform => bit-identical to transforming the grown dataset),
            # downstream state folds the suffix in incrementally
            xt_suf = served.transform(suffix)
            patch = sub.analytics.append(xt_suf)
            snap = sub.analytics.snapshot()
            tracker_new = sub.tracker
            if merged is not None:
                keep = min(merged.width, served.k + TRACK_HEADROOM)
                tracker_new = SubspaceTracker(
                    v=np.ascontiguousarray(merged.v[:, :keep]),
                    s=np.ascontiguousarray(merged.s[:keep]),
                    mean=merged.mean,
                    rows=merged.rows,
                )
            delta = {
                "kind": APPEND,
                "base_rows": m_old,
                "rows": xt_suf,
                "knn": {
                    "changed": patch["changed"],
                    "idx": patch["idx"],
                    "d2": patch["d2"],
                    "append_idx": patch["append_idx"],
                    "append_d2": patch["append_d2"],
                },
                "labels": snap.labels,
                "densities": snap.densities,
                "tlb": tlb_mean,
                "rotation": rot,
                "wall_s": time.perf_counter() - item.t0,
            }
            cache_put = None
            if cacheable:
                # re-register the (still valid) map under the grown
                # fingerprint, updater state folded in: plain queries on
                # the same stream keep hitting the cache
                cache_put = (
                    dataset_fingerprint(grown),
                    BasisCacheEntry(
                        v=served.v, mean=served.mean, k=served.k,
                        target_tlb=sq.cfg.target_tlb,
                        tlb_estimate=tlb_mean, satisfied=True,
                        method=sq.method, rows=grown.shape[0],
                        tracker=tracker_new,
                    ),
                )
            updates = {
                "x": grown,
                "result": served,
                "tracker": tracker_new,
                "analytics": sub.analytics,
                "rollback": False,
                "pairs": pairs,
                "cache_put": cache_put,
            }
            return delta, updates
        # basis must move: escalate exactly like the query ladder
        res_new, tracker_new = None, None
        reason = "drift" if merged is not None and rot > sq.rotation_tol \
            else "headroom"
        if merged is not None:
            try:
                tracker_new, res2, p2 = subspace_suffix_update(
                    sub.tracker, grown, sq.cfg, bucket=bucket
                )
                pairs += p2
                if res2.satisfied:
                    res_new = res2
            except Exception:
                res_new, tracker_new = None, None
        if res_new is None:
            reason = "refit"
            res_new, tracker_new = self._cold_refit_for(
                sub, grown, served, bucket
            )
        xt = res_new.transform(grown)
        sub.analytics.rebuild(xt)
        snap = sub.analytics.snapshot()
        delta = {
            "kind": ROLLBACK,
            "reason": reason,
            "basis": res_new,
            "rows": xt,
            "knn": {"idx": snap.knn_idx, "d2": snap.knn_d2},
            "labels": snap.labels,
            "densities": snap.densities,
            "tlb": res_new.tlb_estimate,
            "rotation": rot,
            "wall_s": time.perf_counter() - item.t0,
        }
        cache_put = None
        if cacheable and res_new.satisfied:
            cache_put = (
                dataset_fingerprint(grown),
                BasisCacheEntry(
                    v=res_new.v, mean=res_new.mean, k=res_new.k,
                    target_tlb=sq.cfg.target_tlb,
                    tlb_estimate=res_new.tlb_estimate, satisfied=True,
                    method=sq.method, rows=grown.shape[0],
                    tracker=tracker_new,
                ),
            )
        updates = {
            "x": grown,
            "result": res_new,
            "tracker": tracker_new,
            "analytics": sub.analytics,
            "rollback": True,
            "pairs": pairs,
            "cache_put": cache_put,
        }
        return delta, updates

    def _run_delta(self, item: _DeltaServe, done: list[int]) -> None:
        """Execute one delta compute outside the lock and commit: apply the
        subscription-state updates, emit the delta (dropped if an
        unsubscribe closed the sub mid-flight), chain the next queued
        append, and honor a deferred close. A raising compute emits a final
        ``closed`` delta with the error — subscriptions fail loudly, never
        silently stall."""
        sub = item.sub
        delta, updates, error = None, None, None
        try:
            delta, updates = self._apply_delta(item)
        except Exception as exc:
            error = f"{type(exc).__name__}: {exc}"
        notify: list[int] = []
        with self._lock:
            self._stepping_now.remove(item)
            sub.inflight = False
            if error is not None:
                self.stats.failures += 1
                nid = self._emit(sub, {"kind": CLOSED, "error": error})
                if nid is not None:
                    notify.append(nid)
            elif sub.state != "closed":
                self.stats.validation_pairs += updates["pairs"]
                sub.x = updates["x"]
                sub.result = updates["result"]
                sub.tracker = updates["tracker"]
                sub.analytics = updates["analytics"]
                if sub.state == "pending":
                    sub.state = "live"
                if item.kind == "bootstrap":
                    sub.recover = False
                if item.kind != "bootstrap":
                    if updates["rollback"]:
                        self.stats.rollbacks += 1
                    else:
                        self.stats.delta_serves += 1
                if updates["cache_put"] is not None:
                    self.cache.put(*updates["cache_put"])
                nid = self._emit(sub, delta)
                if nid is not None:
                    notify.append(nid)
                if sub.close_requested:
                    nid = self._emit(sub, {"kind": CLOSED, "error": None})
                    if nid is not None:
                        notify.append(nid)
                else:
                    self._maybe_schedule_delta(sub)
        self._fire_deltas(notify)

    def _poll_once(self) -> tuple[bool, bool]:
        """One scheduler tick. Returns (stepped, work_remains)."""
        with self._lock:
            self._admit()
            work = self._pop_work()
            if work is not None:
                self._stepping_now.append(work)
            more = self._work_remains()
        if work is None:
            # _admit can FINISH queries without producing work (deadline
            # expiry at dequeue, degraded serves): their notifications
            # must fire even on a tick that popped nothing, or a blocked
            # result() waiter never wakes
            with self._lock:
                done = self._done_now
                self._done_now = []
            if done:
                self._notify(done)
                with self._lock:
                    more = more or self._work_remains()
            return False, more
        done: list[int] = []
        if isinstance(work, _DeltaServe):
            item_id = work.sub.sub_id
        else:
            item_id = work.query.query_id
        try:
            with span(work.span_name, qid=item_id):
                self._dispatch(work, done)
        except Exception as exc:
            # containment of last resort: _dispatch's per-path handlers catch
            # COMPUTE errors, but a commit section (cache put, tracker merge
            # bookkeeping, stats) raising would otherwise escape into the
            # drain thread with the work item half-retired — the query then
            # never finishes and close(drain=True) waits on it forever.
            # Retire the item everywhere it could still be referenced and
            # finish its query with ServeResult.error.
            self._abandon(work, exc, done)
        with self._lock:
            # results committed via _commit (this tick's, or a concurrent
            # tick's not-yet-drained ones) become notifications here
            done.extend(self._done_now)
            self._done_now.clear()
            more = self._work_remains()
        self._notify(done)
        if done:
            # _notify may have SCHEDULED work (a finished bootstrap query
            # becomes a _DeltaServe item there) after `more` was computed —
            # re-check so run()/drain loops don't exit with it pending
            with self._lock:
                more = more or self._work_remains()
        return True, more

    def _dispatch(self, work, done: list[int]) -> None:
        """Run one popped work item off the lock and commit it."""
        if self._expire_if_due(work):
            return  # expired without compute; notification drains later
        if isinstance(work, _DeltaServe):
            self._run_delta(work, done)
        elif isinstance(work, _Downstream):
            self._run_downstream(work, done)
        elif isinstance(work, _SuffixUpdate):
            self._run_suffix_update(work, done)
        elif isinstance(work, _Validation):
            self._run_validation(work, done)
        else:
            try:
                alive = self._step(work)  # device compute, off the lock
            except Exception as exc:
                with self._lock:
                    self._stepping_now.remove(work)
                    self._fail(work, exc)
                done.append(work.query.query_id)
                return
            with self._lock:
                self._stepping_now.remove(work)
                if alive:
                    work.t_ready = time.perf_counter()
                    self._requeue_runner(work)  # rotate: fair share
                else:
                    self._finish(work)

    def _abandon(self, work, exc: BaseException, done: list[int]) -> None:
        """Finish ``work``'s query with an error after a scheduler-side
        exception left it in an unknown state (see ``_poll_once``). The
        query is failed only if nothing else already produced its result."""
        if isinstance(work, _DeltaServe):
            # no query to fail: close the subscription with the error so a
            # blocked delta waiter wakes instead of waiting forever
            sub = work.sub
            notify: list[int] = []
            with self._lock:
                if work in self._stepping_now:
                    self._stepping_now.remove(work)
                self.stats.failures += 1
                sub.inflight = False
                nid = self._emit(
                    sub,
                    {
                        "kind": CLOSED,
                        "error": f"scheduler: {type(exc).__name__}: {exc}",
                    },
                )
                if nid is not None:
                    notify.append(nid)
            self._fire_deltas(notify)
            return
        q = work.query
        with self._lock:
            if work in self._stepping_now:
                self._stepping_now.remove(work)
            if isinstance(work, _InFlight):
                # a requeued runner that then raised in commit: pull it back
                # out so no thread steps a half-retired item
                self._discard_runner(work)
            if q.query_id in self._results:
                return  # the result was committed before the raise: keep it
            self.stats.failures += 1
            d = q.x.shape[1]
            self._results[q.query_id] = ServeResult(
                query_id=q.query_id,
                result=ReduceResult(
                    v=np.zeros((d, 0), np.float32),
                    mean=np.zeros(d, np.float32),
                    k=0, tlb_estimate=0.0, satisfied=False, runtime_s=0.0,
                    iterations=[], method=q.method,
                ),
                wall_s=time.perf_counter() - getattr(work, "t0", time.perf_counter()),
                error=f"scheduler: {type(exc).__name__}: {exc}",
            )
            done.append(q.query_id)

    def poll(self) -> bool:
        """One scheduler tick: admit, then run one unit of work — a pending
        cache revalidation or one iteration of the oldest in-flight runner
        (round-robin). Returns True while work remains. Thread-safe:
        concurrent pollers execute disjoint work items."""
        return self._poll_once()[1]

    def run(self) -> list[ServeResult]:
        """Drain all submitted queries; results ordered by query id."""
        while self.poll():
            pass
        return self._collect_results()

    def _collect_results(self) -> list[ServeResult]:
        with self._lock:
            out = [self._results[qid] for qid in sorted(self._results)]
            self._results = {}
        return out
