"""Process-worker serving fleet with fault-tolerant supervision.

The sharded in-process scheduler cannot scale on CPU hosts: XLA:CPU
serializes execution across forced host devices inside one client (one
execution pool per client — see the bench notes in
``benchmarks/bench_drop_serve.py``). Real scale-out on a multi-core host
therefore means one *process* (one XLA client) per device slot. This
module promotes the worker-process pattern that used to live privately in
that bench into a first-class deployment mode:

* **FleetSupervisor** — spawns one core-pinned worker process per slot,
  routes ``ReduceQuery``s to workers over pipes, and streams
  ``ServeResult``s back. It duck-types the ``DropService`` surface
  (``submit``/``try_submit``/``backlog``/``take_result``/``poll``/``run``/
  ``stats``/``on_result``), so the existing ``IngestFrontend`` async
  front-end works unchanged: sync, threaded, and process modes share one
  API.
* **protocol** — length-prefixed pickle frames over the worker's
  stdin/stdout pipes (the worker re-points its ``stdout`` at stderr first,
  so stray prints can never corrupt framing). Messages: ``ready``,
  heartbeats, queries, results, echo pings (link profiling), compute
  probes, stop. This replaces the old line-oriented READY/GO handshake.
* **fault tolerance** — worker death is detected three ways: pipe EOF
  (fastest — a ``kill -9`` lands here), exitcode polling, and heartbeat
  timeout (a hung-but-alive worker is killed and treated as dead). A dead
  worker's in-flight queries are re-dispatched to live workers (bounded by
  ``max_query_retries``, then finished with ``ServeResult.error``) — a
  client blocked in ``result()`` is NEVER hung. Its live delta
  subscriptions are RE-HOMED, not closed: the supervisor mirrors each
  sub's grown dataset and re-subscribes it on a live worker, restamping
  sequence numbers so the client sees a seamless
  ``ROLLBACK(reason="rehome")`` restate. Restarts go through
  ``fault.RestartPolicy`` (capped exponential backoff; a worker past the
  budget is retired and its slot removed). ``fault.FailureInjector`` can
  be wired into workers (``failure_prob`` for crashes, ``slow_prob`` +
  ``slow_s`` for latency chaos) so tests exercise the whole ladder
  deterministically, and a per-worker ``fault.StragglerMonitor`` watches
  serve times.
* **overload resilience** — queries carry an optional deadline: the
  remaining budget rides in the dispatch frame, the worker charges queue
  and stall time against it and its local service expires between runner
  steps; the supervisor independently expires pending/assigned queries
  past their deadline (``error="deadline"``) so a wedged worker cannot
  hold a client. Per-worker circuit breakers (closed/open/half-open) stop
  placement onto a worker with ``breaker_threshold`` consecutive
  failures or deadline timeouts until a cooldown and a successful probe
  query clear it (``ServiceStats.breaker_opens``).
* **measured placement** — beyond round-robin: at startup the supervisor
  profiles each link's transfer cost by echoing payloads of increasing
  size and fitting the classic alpha/beta model (``rtt/2 ~ alpha +
  beta * bytes``, the same latency/bandwidth decomposition colossal-ai's
  ``AlphaBetaProfiler`` fits for device links), plus each worker's compute
  speed with a fixed probe. Placement then minimizes *measured* cost:
  ``link(bytes) + (queue_depth + 1) * est_seconds / speed``, where
  ``est_seconds`` is a per-tenant EWMA and ``speed`` keeps being
  re-estimated from observed serve times. Tenants are sticky to their
  home worker (its basis cache is warm for them) and move only when
  another worker is decisively cheaper (``rebalance_margin``), surfaced
  as ``ServiceStats.rebalances``.
* **link re-profiling** — the alpha/beta fit is NOT startup-only: a link
  profile ages out after ``reprofile_interval_s`` seconds or
  ``reprofile_after_serves`` serves, whereupon the supervision loop
  re-runs a cheap echo probe on the idle worker (a background thread off
  the serving hot path) and REPLACES the fit. Compute speed needs no
  probe — the serve-time EWMA keeps it fresh — but transfer cost is only
  observable by echoing, so a link that degrades after startup (shared
  NIC, cgroup throttling, pipe contention) would otherwise keep its
  stale, optimistic profile and placement would keep routing tenants
  into the slow link. Re-profiles are surfaced as
  ``ServiceStats.reprofiles``.

Costs across the boundary: ``CostModel`` closures do not pickle, so fleet
queries carry the ``downstream`` task name (workers re-price it) or one of
the named cost families (``zero``/``knn``/``linear``, rebuilt from the
dataset's row count); arbitrary callables are rejected at submit.

The module top imports stdlib only: the worker bootstrap must pin CPU
affinity BEFORE numpy/jax initialize their thread pools, so every heavy
import here is deferred into the function that needs it.
"""

from __future__ import annotations

import os
import pickle
import queue
import struct
import subprocess
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field

_LEN = struct.Struct("<Q")
_INJECTED_EXIT = 43  # worker exit code for an injected NodeFailure "crash"
_STOP_WRITER = object()  # sentinel that retires a writer thread

# worker bootstrap for `python -c`: pin affinity from --cores before ANY
# heavy import (numpy/XLA size their pools from the mask they see first)
_WORKER_BOOT = (
    "import os, sys\n"
    "argv = sys.argv[1:]\n"
    "if '--cores' in argv:\n"
    "    cores = argv[argv.index('--cores') + 1]\n"
    "    if cores and hasattr(os, 'sched_setaffinity'):\n"
    "        os.sched_setaffinity(0, {int(c) for c in cores.split(',')})\n"
    "from repro.serve_drop.fleet import _worker_main\n"
    "_worker_main(argv)\n"
)


# --------------------------------------------------------- chip ownership


def _visible_tpu_chips() -> int:
    """TPU chips attached to this host, counted from their device files
    (``/dev/accel<N>``, or ``/dev/vfio/<N>`` under the vfio driver) so the
    count never opens a chip itself."""
    import glob

    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    return len([p for p in glob.glob("/dev/vfio/*") if p.rsplit("/", 1)[-1].isdigit()])


def _process_holds_tpu() -> bool:
    """Whether this process already opened a TPU client. Asked without
    initializing JAX: a process that never imported it, or whose backends are
    not up yet, holds nothing."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized() and "tpu" in getattr(
        xla_bridge, "_backends", {}
    )


def _check_chip_ownership(workers: int) -> None:
    """Refuse a fleet the host's chips cannot carry, before any worker
    starts. A TPU chip belongs to one process at a time, and each worker
    opens the chips of the host it starts on: a worker behind a parent that
    holds the chip, or one beyond the host's chip count, fails or hangs in
    start-up. Workers held off the chip by ``JAX_PLATFORMS`` (the CPU fleet)
    are never refused."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return
    if _process_holds_tpu():
        raise RuntimeError(
            "FleetSupervisor: this process already holds the TPU, so a "
            "worker process cannot open it; start the fleet before any JAX "
            "computation in this process, or serve in-process (DropService)"
        )
    chips = _visible_tpu_chips()
    if chips and workers > chips:
        raise RuntimeError(
            f"FleetSupervisor: {workers} worker processes but {chips} TPU "
            f"chip(s) on this host; one process owns a chip, so start at "
            f"most {chips} worker(s)"
        )


# ------------------------------------------------------------------ framing


def _send_frame(f, obj) -> None:
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    f.write(_LEN.pack(len(payload)))
    f.write(payload)
    f.flush()


def _read_exact(f, n: int) -> bytes | None:
    buf = b""
    while len(buf) < n:
        chunk = f.read(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


def _recv_frame(f):
    """One framed message, or None on EOF (peer gone)."""
    head = _read_exact(f, _LEN.size)
    if head is None:
        return None
    payload = _read_exact(f, _LEN.unpack(head)[0])
    if payload is None:
        return None
    return pickle.loads(payload)


def _cost_spec(cost):
    """Serializable form of a downstream cost model (see module docstring)."""
    if cost is None:
        return None
    name = getattr(cost, "name", None)
    if name in ("zero", "knn", "linear"):
        return name
    try:
        pickle.dumps(cost)
        return ("pickled", cost)
    except Exception:
        raise ValueError(
            "fleet queries cannot carry arbitrary cost callables across the "
            "process boundary; pass downstream='knn'/'dbscan'/'kde' or a "
            "named CostModel (zero/knn/linear) instead"
        ) from None


def _cost_from_spec(spec, rows: int):
    if spec is None:
        return None
    if isinstance(spec, tuple):
        return spec[1]
    from repro.core.cost import knn_cost, linear_cost, zero_cost

    return {"zero": zero_cost, "knn": lambda: knn_cost(rows),
            "linear": lambda: linear_cost(rows)}[spec]()


# ------------------------------------------------------------- worker side


def _compute_probe(reps: int = 3) -> float:
    """Fixed CPU-bound probe (seconds): relative worker speed under its
    core pinning. numpy-only so it never touches the XLA jit cache."""
    import numpy as np

    rng = np.random.default_rng(0)
    a = rng.standard_normal((256, 64)).astype(np.float32)
    t0 = time.perf_counter()
    for _ in range(reps):
        np.linalg.svd(a, full_matrices=False)
    return time.perf_counter() - t0


def _serve_one(svc, msg, t_rx: float | None = None):
    """Run one query through the worker's service; returns its ServeResult
    (query ids are remapped to the supervisor's). ``msg["deadline_s"]`` is
    the budget REMAINING at dispatch; time spent since receipt (injected
    latency, slowdowns) is charged against it before the local service
    enforces the rest between runner steps."""
    x = msg["x"]
    cost = _cost_from_spec(msg["cost"], x.shape[0])
    deadline_s = msg.get("deadline_s")
    if deadline_s is not None and t_rx is not None:
        deadline_s -= time.perf_counter() - t_rx
    qid = svc.submit(
        x, msg["cfg"], cost, method=msg["method"],
        downstream=msg["downstream"],
        execute_downstream=msg.get("xds", False),
        deadline_s=deadline_s,
    )
    out = None
    for r in svc.run():
        if r.query_id == qid:
            out = r
    out.query_id = msg["qid"]
    return out


def _worker_main(argv: list[str]) -> None:
    """Fleet worker entry: serve framed queries over stdin/stdout."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--fleet-worker", type=int, required=True)
    ap.add_argument("--incarnation", type=int, default=0)
    ap.add_argument("--cores", type=str, default="")
    ap.add_argument("--heartbeat-s", type=float, default=0.5)
    ap.add_argument("--failure-prob", type=float, default=0.0)
    ap.add_argument("--failure-seed", type=int, default=0)
    ap.add_argument("--slowdown-s", type=float, default=0.0)
    # chaos latency: per-query probabilistic stall (FailureInjector's
    # latency mode) — unlike --slowdown-s it is random per query, so head
    # -of-line and deadline tests see realistic jitter
    ap.add_argument("--slow-prob", type=float, default=0.0)
    ap.add_argument("--slow-s", type=float, default=0.0)
    # test knob: delay echo replies only after the first N pings, so a link
    # can "degrade" after the startup profile completes (see
    # FleetSupervisor.worker_link_delays)
    ap.add_argument("--pong-delay-s", type=float, default=0.0)
    ap.add_argument("--pong-delay-after", type=int, default=0)
    ap.add_argument("--no-cache", action="store_true")
    args = ap.parse_args(argv)

    # the `-c` bootstrap pins affinity pre-import; re-apply for direct runs
    if args.cores and hasattr(os, "sched_setaffinity"):
        os.sched_setaffinity(0, {int(c) for c in args.cores.split(",")})

    # claim the real stdout for frames, then point fd 1 (and sys.stdout) at
    # stderr: a stray print anywhere below lands in the log, not the protocol
    out = os.fdopen(os.dup(1), "wb")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    inp = os.fdopen(os.dup(0), "rb")
    wlock = threading.Lock()

    def send(msg) -> None:
        with wlock:
            _send_frame(out, msg)

    # heavy imports AFTER affinity: numpy/XLA size their pools off the mask
    import numpy as np

    from repro.core.types import ReduceResult
    from repro.fault.faults import FailureInjector, NodeFailure
    from repro.serve_drop.delta import SubscribeQuery
    from repro.serve_drop.service import DropService, ServeResult

    svc = DropService(enable_cache=not args.no_cache)
    injector = (
        FailureInjector(
            args.failure_prob, seed=args.failure_seed,
            slow_prob=args.slow_prob, slow_s=args.slow_s,
        )
        if args.failure_prob > 0 or args.slow_prob > 0
        else None
    )

    stop_hb = threading.Event()

    def heartbeat() -> None:
        while not stop_hb.wait(args.heartbeat_s):
            try:
                send({"t": "hb"})
            except OSError:
                return

    threading.Thread(target=heartbeat, daemon=True).start()
    send({"t": "ready", "pid": os.getpid(), "incarnation": args.incarnation})

    served = 0
    pings = 0
    # delta subscriptions homed on this worker: supervisor sid -> local sid.
    # Deltas are flushed after every message that can produce them and
    # forwarded as framed "delta" messages; the local service's sequence
    # numbers pass through unchanged (one worker owns a subscription for
    # its whole life — a worker death closes it at the supervisor).
    subs: dict[int, int] = {}

    def flush_subs() -> None:
        for sid, lid in list(subs.items()):
            for dlt in svc.poll_deltas(lid):
                send({"t": "delta", "sid": sid, "delta": dlt})

    def sub_error(sid: int, exc: BaseException) -> None:
        # seq=None: the supervisor stamps the next sequence number itself
        send({"t": "delta", "sid": sid, "delta": {
            "kind": "closed", "seq": None,
            "error": f"{type(exc).__name__}: {exc}",
        }})

    while True:
        msg = _recv_frame(inp)
        if msg is None or msg["t"] == "stop":
            break
        t = msg["t"]
        if t == "ping":  # link profiling: echo the payload back
            pings += 1
            if args.pong_delay_s > 0 and pings > args.pong_delay_after:
                time.sleep(args.pong_delay_s)  # simulated link degradation
            send({"t": "pong", "n": msg["n"], "blob": msg["blob"]})
        elif t == "prof":
            send({"t": "prof", "n": msg["n"], "seconds": _compute_probe()})
        elif t == "q":
            served += 1
            t_rx = time.perf_counter()  # deadline budget burns from here
            if injector is not None:
                try:
                    injector.maybe_fail(served)
                except NodeFailure:
                    os._exit(_INJECTED_EXIT)  # simulate a hard crash
                injector.maybe_delay(served)
            if args.slowdown_s > 0:
                time.sleep(args.slowdown_s)
            t0 = time.perf_counter()
            try:
                res = _serve_one(svc, msg, t_rx)
            except Exception as exc:  # the query, not the worker, fails
                d = int(msg["x"].shape[1])
                res = ServeResult(
                    query_id=msg["qid"],
                    result=ReduceResult(
                        v=np.zeros((d, 0), np.float32),
                        mean=np.zeros(d, np.float32),
                        k=0, tlb_estimate=0.0, satisfied=False,
                        runtime_s=0.0, iterations=[], method=msg["method"],
                    ),
                    error=f"{type(exc).__name__}: {exc}",
                )
            send({"t": "res", "qid": msg["qid"], "res": res,
                  "serve_s": time.perf_counter() - t0})
            flush_subs()  # a query drain may also land pending delta work
        elif t == "sub":
            try:
                lid = svc.subscribe(SubscribeQuery(
                    x=msg["x"], cfg=msg["cfg"], method=msg["method"],
                    eps=msg["eps"], min_samples=msg["min_samples"],
                    bandwidth=msg["bandwidth"],
                    rotation_tol=msg["rotation_tol"],
                ))
                subs[msg["sid"]] = lid
                while svc.poll():
                    pass
            except Exception as exc:
                sub_error(msg["sid"], exc)
            flush_subs()
        elif t == "app":
            try:
                svc.append(subs[msg["sid"]], msg["x"])
                while svc.poll():
                    pass
            except Exception as exc:
                sub_error(msg["sid"], exc)
            flush_subs()
        elif t == "unsub":
            lid = subs.get(msg["sid"])
            if lid is not None:
                try:
                    svc.unsubscribe(lid)
                    while svc.poll():
                        pass
                except Exception:
                    pass  # supervisor already fabricated the closed delta
            flush_subs()
    stop_hb.set()
    os._exit(0)


# --------------------------------------------------------- supervisor side


@dataclass
class LinkProfile:
    """Fitted alpha/beta transfer-cost model for one supervisor->worker
    link: one-way seconds ~ alpha + beta * payload_bytes."""

    alpha_s: float = 1e-4
    beta_s_per_byte: float = 1e-9

    def seconds(self, nbytes: int) -> float:
        return self.alpha_s + self.beta_s_per_byte * float(nbytes)


@dataclass(eq=False)
class _FleetSub:
    """Supervisor-side record of one delta subscription. The home worker
    can change: when it dies, the supervisor re-subscribes the sub's grown
    dataset on a live worker and the new bootstrap rollback reaches the
    client as ``ROLLBACK(reason="rehome")`` — clients converge by restating
    (the normal rollback contract) instead of seeing a terminal CLOSED.
    The supervisor therefore keeps everything a re-home needs: the query
    params and the dataset as grown by every ``append`` so far."""

    sid: int
    worker: int  # index of the current home worker (-1 while orphaned)
    fp: str
    query: object = None  # delta.SubscribeQuery (re-home re-sends params)
    x: object = None  # np.ndarray mirror of the grown dataset
    state: str = "pending"  # pending | live | closed
    next_seq: int = 0  # the supervisor restamps EVERY delta (re-home safe)
    bootstrapped: bool = False  # client saw its first (subscribe) rollback
    rehoming: bool = False  # waiting on the new home's bootstrap rollback
    # rebuilt from the subscription WAL by a relaunched supervisor: the
    # next bootstrap rollback is restamped reason="recover"
    recovering: bool = False
    pending_appends: list = field(default_factory=list)  # buffered suffixes
    deltas: deque = field(default_factory=deque)
    error: str | None = None


@dataclass(eq=False)
class _FleetQuery:
    qid: int
    x: object  # np.ndarray (float32, contiguous)
    cfg: object
    cost: object  # _cost_spec form
    method: str
    downstream: str | None
    fp: str
    t0: float  # submit time (ServeResult.wall_s baseline)
    nbytes: int
    execute_downstream: bool = False
    retries: int = 0
    dispatch_t: float = 0.0
    deadline_t: float | None = None  # absolute perf_counter expiry


class _Worker:
    """Supervisor-side handle for one worker slot (survives restarts)."""

    def __init__(self, index: int, cores: list[int] | None) -> None:
        self.index = index
        self.label = f"worker-{index}"
        self.cores = cores
        self.proc: subprocess.Popen | None = None
        self.state = "new"  # new|starting|ready|dead|restarting|lost
        self.incarnation = 0
        self.restarts = 0
        self.restart_due = 0.0
        self.last_seen = 0.0
        self.ready_evt = threading.Event()
        self.outbox: queue.Queue = queue.Queue()
        self.assigned: dict[int, _FleetQuery] = {}
        self.link = LinkProfile()
        # circuit breaker: repeated failures/timeouts stop NEW placement on
        # this worker ("open") until a cooldown elapses; the first query
        # after cooldown is a single probe ("half-open") whose verdict
        # closes or re-opens the breaker. Reset on respawn (new process).
        self.breaker = "closed"  # closed | open | half-open
        self.consec_failures = 0
        self.breaker_until = 0.0  # open -> half-open transition time
        self.probe_s: float | None = None
        self.speed = 1.0  # relative throughput (1.0 = fleet reference)
        self.served = 0
        self.straggler = None  # fault.StragglerMonitor, set by supervisor
        self.rpc: dict[int, tuple[threading.Event, dict]] = {}
        # link-profile freshness (reprofile age-out; see _maybe_reprofile)
        self.profiled_at = 0.0  # perf_counter of the last alpha/beta fit
        self.served_at_profile = 0  # w.served when that fit was taken
        self.reprofiling = False  # a background echo probe is in flight


class FleetSupervisor:
    """Process-per-slot serving fleet behind the ``DropService`` surface.

    ``workers`` processes are spawned (core-pinned on Linux), profiled, and
    supervised: crash -> requeue + restart, hang -> kill + restart, chaos
    injection via ``failure_prob``. Use it exactly like a service::

        with FleetSupervisor(workers=2) as fleet:
            qid = fleet.submit(x, cfg, downstream="knn")
            res = fleet.run()[0]            # or fleet.result(qid)

    or behind the async front-end: ``IngestFrontend(FleetSupervisor(...))``.
    """

    def __init__(
        self,
        workers: int = 2,
        *,
        restart_policy=None,
        heartbeat_s: float = 0.5,
        heartbeat_timeout_s: float | None = None,
        enable_worker_cache: bool = True,
        placement: str = "cost",  # "cost" (measured) or "rr" (sticky RR)
        rebalance_margin: float = 0.7,
        default_query_s: float = 0.05,
        max_query_retries: int = 2,
        profile: bool = True,
        reprofile_interval_s: float = 60.0,
        reprofile_after_serves: int = 256,
        pin_cores: bool = True,
        breaker_threshold: int = 3,
        breaker_cooldown_s: float = 0.5,
        failure_prob: float = 0.0,
        failure_seed: int = 0,
        worker_slowdowns: list[float] | None = None,
        worker_slow_probs: list[float] | None = None,
        slow_s: float = 0.0,
        worker_link_delays: list[float] | None = None,
        link_delay_after_pings: int = 9,
        startup_timeout_s: float = 180.0,
        state_dir: str | None = None,
        state_fsync: bool = True,
    ) -> None:
        from repro.fault.faults import RestartPolicy, StragglerMonitor
        from repro.serve_drop.service import ServiceStats

        if placement not in ("cost", "rr"):
            raise ValueError(f"unknown placement {placement!r}")
        n = max(int(workers), 1)
        self.restart_policy = restart_policy or RestartPolicy(
            max_restarts=3, backoff_s=0.05, backoff_cap_s=5.0
        )
        self.heartbeat_s = heartbeat_s
        self.heartbeat_timeout_s = heartbeat_timeout_s or max(
            10.0, 20.0 * heartbeat_s
        )
        self.enable_worker_cache = enable_worker_cache
        self.placement = placement
        self.rebalance_margin = float(rebalance_margin)
        self.default_query_s = float(default_query_s)
        self.max_query_retries = int(max_query_retries)
        self.profile = profile
        # link-profile age-out: whichever trips first re-triggers the echo
        # probe (<=0 disables that trigger; profile=False disables both)
        self.reprofile_interval_s = float(reprofile_interval_s)
        self.reprofile_after_serves = int(reprofile_after_serves)
        # consecutive failures/deadline-timeouts that open a worker's
        # breaker, and how long it stays open before the half-open probe
        self.breaker_threshold = max(int(breaker_threshold), 1)
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        self.failure_prob = float(failure_prob)
        self.failure_seed = int(failure_seed)
        self.worker_slowdowns = worker_slowdowns or []
        # chaos latency: per-worker probability of a ``slow_s`` stall per
        # query (FailureInjector.maybe_delay), for HOL/deadline tests
        self.worker_slow_probs = worker_slow_probs or []
        self.slow_s = float(slow_s)
        # test knobs: per-worker echo delay that kicks in only after the
        # first ``link_delay_after_pings`` pings — the default 9 equals the
        # startup probe's ping count (1 throwaway + 4 sizes x 2 reps), so
        # the link "degrades" right after its startup profile is taken
        self.worker_link_delays = worker_link_delays or []
        self.link_delay_after_pings = int(link_delay_after_pings)
        self.startup_timeout_s = startup_timeout_s
        self.stats = ServiceStats()
        self.on_result = None  # ingest hook, fired with no lock held
        self.on_delta = None  # delta hook, fired with no lock held
        self._subs: dict[int, _FleetSub] = {}
        self._next_sub_id = 0

        cores = self._core_partition(n) if pin_cores else [None] * n
        self._workers = [_Worker(i, cores[i]) for i in range(n)]
        for w in self._workers:
            w.straggler = StragglerMonitor()
        self._lock = threading.RLock()
        self._pending: deque[_FleetQuery] = deque()
        self._results: dict[int, object] = {}
        self._tenant_home: dict[str, int] = {}
        self._tenant_ref_s: dict[str, float] = {}
        self._next_id = 0
        self._next_nonce = 0
        self._rr = 0
        self._started = False
        self._stopping = False
        self._monitor: threading.Thread | None = None
        # crash-consistent durability (serve_drop.persist): a subscription
        # WAL under state_dir plus a warm-restart profile blob. Recovery
        # runs HERE — journaled subs park orphaned (worker=-1) and the
        # supervision tick homes them once workers come up, so their
        # clients resume on a ``ROLLBACK(reason="recover")``.
        self.state_dir = state_dir
        self.state_fsync = state_fsync
        self._wal = None
        if state_dir is not None:
            from repro.serve_drop.persist import SubscriptionWAL

            self._wal = SubscriptionWAL(state_dir, fsync=state_fsync)
            self._recover_subs()

    # ------------------------------------------------------------ lifecycle

    @property
    def devices(self) -> list[str]:
        """Worker labels (IngestFrontend sizes its drain pool off this)."""
        return [w.label for w in self._workers if w.state != "lost"]

    # ------------------------------------------------------------- recovery

    @property
    def persists_subscriptions(self) -> bool:
        return self._wal is not None

    def _recover_subs(self) -> None:
        """Rebuild journaled subscriptions from the WAL (constructor path,
        pre-threads). Each parks orphaned (worker=-1, rehoming) until the
        supervision tick places it on a live worker; that home's bootstrap
        rollback reaches the client restamped ``reason="recover"`` at the
        acked seq, so the client restates without re-subscribing."""
        from repro.serve_drop.cache import dataset_fingerprint

        recovered, nrec = self._wal.replay()
        self.stats.recovery_wal_records = nrec
        self.stats.recovery_wal_trimmed = self._wal.trimmed
        self.stats.recovery_wal_skipped = self._wal.skipped
        for sid in sorted(recovered):
            rec = recovered[sid]
            self._subs[sid] = _FleetSub(
                sid=sid, worker=-1, fp=dataset_fingerprint(rec.x),
                query=rec.query, x=rec.x, next_seq=rec.next_seq,
                bootstrapped=rec.next_seq > 0, rehoming=True,
                recovering=True,
            )
            self._next_sub_id = max(self._next_sub_id, sid + 1)
            self.stats.recovery_subs += 1
        if recovered:
            # restart-after-restart replays O(live state), not O(history)
            self._wal.compact(list(recovered.values()))

    def _journal_end(self, sid: int) -> None:
        """Terminal close reached the supervisor: journal it, and compact
        the WAL to empty once no live streams remain (caller holds the
        lock — WAL appends serialize behind it)."""
        if self._wal is None:
            return
        self._wal.journal_end(sid)
        if not any(s.state != "closed" for s in self._subs.values()):
            self._wal.compact([])

    def detach_subscriptions(self) -> list[int]:
        """Close every live subscription in-memory WITHOUT a terminal
        delta or WAL ``end`` record: the journal keeps them, so the next
        supervisor over this ``state_dir`` resumes each stream. The ingest
        frontend calls this on close when the service persists."""
        out: list[int] = []
        with self._lock:
            for sid, sub in self._subs.items():
                if sub.state == "closed":
                    continue
                out.append(sid)
                w = (
                    self._workers[sub.worker]
                    if 0 <= sub.worker < len(self._workers)
                    else None
                )
                if w is not None and w.state == "ready":
                    # worker-side cleanup only; its closed delta is dropped
                    # as stale because the sub is already closed here
                    w.outbox.put({"t": "unsub", "sid": sid})
                sub.state = "closed"
                sub.rehoming = False
                sub.recovering = False
                sub.pending_appends.clear()
        return out

    def _load_profile(self) -> bool:
        """Warm restart: adopt the previous incarnation's link fits,
        worker speeds, and per-tenant state instead of re-running the
        startup probe storm. Only when the fleet shape matches — a resized
        fleet probes cold. Staleness is self-correcting: the serve-time
        EWMA keeps re-estimating speeds and the reprofile age-out
        refreshes links."""
        if self.state_dir is None:
            return False
        from repro.serve_drop.persist import read_blob

        prof = read_blob(self.state_dir, "fleet_profile.bin")
        if (
            not isinstance(prof, dict)
            or prof.get("workers") != len(self._workers)
        ):
            return False
        now = time.perf_counter()
        with self._lock:
            for w, link, speed, probe in zip(
                self._workers, prof["links"], prof["speeds"], prof["probes"]
            ):
                w.link = LinkProfile(
                    alpha_s=float(link[0]), beta_s_per_byte=float(link[1])
                )
                w.speed = float(speed)
                w.probe_s = probe
                w.profiled_at = now
                w.served_at_profile = 0
            self._tenant_ref_s.update(prof.get("tenant_ref_s", {}))
            self._tenant_home.update({
                fp: int(i)
                for fp, i in prof.get("tenant_home", {}).items()
                if 0 <= int(i) < len(self._workers)
            })
            self.stats.recovery_warm_profile = 1
        return True

    def _save_profile(self) -> None:
        if self.state_dir is None:
            return
        from repro.serve_drop.persist import write_blob

        with self._lock:
            prof = {
                "workers": len(self._workers),
                "links": [
                    (w.link.alpha_s, w.link.beta_s_per_byte)
                    for w in self._workers
                ],
                "speeds": [w.speed for w in self._workers],
                "probes": [w.probe_s for w in self._workers],
                "tenant_ref_s": dict(self._tenant_ref_s),
                "tenant_home": dict(self._tenant_home),
            }
        try:
            write_blob(
                self.state_dir, "fleet_profile.bin", prof,
                fsync=self.state_fsync,
            )
        except OSError:
            pass  # the profile is an optimization, never worth failing on

    def tenant_estimate_s(self, fp: str) -> float | None:
        """Expected serve seconds for this fingerprint on the fleet's
        fastest live worker (the ingest frontend's per-tenant admission
        term). ``_tenant_ref_s`` is speed-normalized, so dividing by the
        best live speed yields wall seconds; None until observed."""
        with self._lock:
            ref = self._tenant_ref_s.get(fp)
            if ref is None:
                return None
            speeds = [
                w.speed for w in self._workers if w.state == "ready"
            ] or [1.0]
            return ref / max(max(speeds), 1e-3)

    @staticmethod
    def _core_partition(n: int) -> list[list[int] | None]:
        """Strided core sets per worker: each worker's XLA client otherwise
        spawns an nproc-wide pool and N workers x nproc threads thrash. A
        single worker keeps the full mask (it IS the machine's share)."""
        if n == 1 or not hasattr(os, "sched_getaffinity"):
            return [None] * n
        cores = sorted(os.sched_getaffinity(0))
        return [cores[i::n] or cores for i in range(n)]

    def start(self) -> "FleetSupervisor":
        if self._started:
            return self
        _check_chip_ownership(len(self._workers))
        self._started = True
        for w in self._workers:
            self._spawn(w)
        deadline = time.perf_counter() + self.startup_timeout_s
        for w in self._workers:
            while not w.ready_evt.wait(0.1):
                if w.proc is not None and w.proc.poll() is not None:
                    self.shutdown()
                    raise RuntimeError(
                        f"{w.label} exited during startup "
                        f"(exit {w.proc.returncode})"
                    )
                if time.perf_counter() > deadline:
                    self.shutdown()
                    raise RuntimeError(
                        f"{w.label} did not come up (see stderr)"
                    )
        if self.profile:
            if not self._load_profile():  # warm restart skips the probes
                for w in self._workers:
                    try:
                        self._profile_worker(w)
                    except (RuntimeError, TimeoutError):
                        pass  # died mid-profile: supervision restarts it;
                        # the default link/speed estimates hold until observed
                self._normalize_speeds()
                self._save_profile()
        self._monitor = threading.Thread(
            target=self._monitor_loop, name="fleet-monitor", daemon=True
        )
        self._monitor.start()
        return self

    def _spawn(self, w: _Worker) -> None:
        """Launch one worker process and its reader/writer threads. The
        bootstrap pins cores before any heavy import."""
        argv = [
            "--fleet-worker", str(w.index),
            "--incarnation", str(w.incarnation),
            "--heartbeat-s", str(self.heartbeat_s),
        ]
        if w.cores:
            argv += ["--cores", ",".join(map(str, w.cores))]
        if not self.enable_worker_cache:
            argv += ["--no-cache"]
        if self.failure_prob > 0:
            argv += [
                "--failure-prob", str(self.failure_prob),
                "--failure-seed",
                str(self.failure_seed + 1000 * w.index + 17 * w.incarnation),
            ]
        if w.index < len(self.worker_slowdowns):
            argv += ["--slowdown-s", str(self.worker_slowdowns[w.index])]
        if w.index < len(self.worker_slow_probs):
            argv += [
                "--slow-prob", str(self.worker_slow_probs[w.index]),
                "--slow-s", str(self.slow_s),
            ]
            if self.failure_prob <= 0:  # seed not already on the argv
                argv += ["--failure-seed", str(
                    self.failure_seed + 1000 * w.index + 17 * w.incarnation
                )]
        if w.index < len(self.worker_link_delays):
            argv += [
                "--pong-delay-s", str(self.worker_link_delays[w.index]),
                "--pong-delay-after", str(self.link_delay_after_pings),
            ]
        env = dict(os.environ)
        import repro

        src = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        w.proc = subprocess.Popen(
            [sys.executable, "-c", _WORKER_BOOT] + argv,
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        )
        w.state = "starting"
        w.last_seen = time.perf_counter()
        w.ready_evt = threading.Event()
        w.outbox = queue.Queue()
        threading.Thread(
            target=self._write_loop, args=(w, w.proc),
            name=f"fleet-w{w.index}-tx", daemon=True,
        ).start()
        threading.Thread(
            target=self._read_loop, args=(w, w.proc),
            name=f"fleet-w{w.index}-rx", daemon=True,
        ).start()

    def shutdown(self, timeout_s: float = 10.0) -> None:
        """Stop workers and supervision. Pending/in-flight queries are NOT
        waited for — call ``run()`` (or drain via IngestFrontend) first.
        Live journaled subscriptions are NOT ended: they stay in the WAL
        for the next supervisor over this ``state_dir`` to resume."""
        self._save_profile()  # final speeds/EWMAs beat the startup fit
        self._stopping = True
        for w in self._workers:
            if w.proc is not None and w.proc.poll() is None:
                w.outbox.put({"t": "stop"})
            w.outbox.put(_STOP_WRITER)
        deadline = time.perf_counter() + timeout_s
        for w in self._workers:
            if w.proc is None:
                continue
            try:
                w.proc.wait(timeout=max(0.1, deadline - time.perf_counter()))
            except subprocess.TimeoutExpired:
                w.proc.kill()
                w.proc.wait()
        if self._monitor is not None:
            self._monitor.join(timeout=2.0)
        if self._wal is not None:
            self._wal.close()

    def __enter__(self) -> "FleetSupervisor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # ------------------------------------------------------------ profiling

    def _rpc(self, w: _Worker, msg: dict, timeout_s: float = 30.0) -> dict:
        with self._lock:
            n = self._next_nonce
            self._next_nonce += 1
            evt, slot = threading.Event(), {}
            w.rpc[n] = (evt, slot)
        w.outbox.put({**msg, "n": n})
        if not evt.wait(timeout_s):
            with self._lock:
                w.rpc.pop(n, None)
            raise TimeoutError(f"{w.label}: no reply to {msg['t']}")
        reply = slot["msg"]
        if reply.get("t") == "dead":  # resolved by _handle_death
            raise RuntimeError(f"{w.label} died mid-{msg['t']}")
        return reply

    def _fit_link(self, w: _Worker, sizes: list[int], reps: int) -> None:
        """Fit and REPLACE the link's alpha/beta model from echo
        round-trips over growing payloads, stamping the profile fresh."""
        import numpy as np

        self._rpc(w, {"t": "ping", "blob": b""})  # throwaway: first-recv cost
        rtts = []
        for s in sizes:
            blob = b"\0" * s
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                self._rpc(w, {"t": "ping", "blob": blob})
                best = min(best, time.perf_counter() - t0)
            rtts.append(best)
        beta, alpha = np.polyfit(np.asarray(sizes, float), np.asarray(rtts), 1)
        # one-way cost; clamp: tiny-noise fits can go (meaninglessly) negative
        with self._lock:
            w.link = LinkProfile(
                alpha_s=max(float(alpha) / 2.0, 1e-6),
                beta_s_per_byte=max(float(beta) / 2.0, 1e-12),
            )
            w.profiled_at = time.perf_counter()
            w.served_at_profile = w.served

    def _profile_worker(self, w: _Worker) -> None:
        """Startup profiling: the link's alpha/beta transfer model plus the
        worker's compute speed with a fixed probe (colossal-ai
        AlphaBetaProfiler-style, over pipes)."""
        self._fit_link(w, [1 << 10, 1 << 15, 1 << 18, 1 << 20], reps=2)
        w.probe_s = float(self._rpc(w, {"t": "prof"})["seconds"])

    def _maybe_reprofile(self, now: float) -> None:
        """Age out stale link profiles (supervision tick). A ready, IDLE
        worker whose fit is older than ``reprofile_interval_s`` or has
        ``reprofile_after_serves`` serves behind it gets a cheap echo probe
        on a background thread — queries never wait behind pings, and an
        idle worker's pipe carries nothing else, so the fit is clean.
        Compute speed is NOT re-probed: the serve-time EWMA tracks it."""
        if not self.profile:
            return
        for w in self._workers:
            with self._lock:
                stale_t = (
                    self.reprofile_interval_s > 0
                    and now - w.profiled_at > self.reprofile_interval_s
                )
                stale_n = (
                    self.reprofile_after_serves > 0
                    and w.served - w.served_at_profile
                    >= self.reprofile_after_serves
                )
                due = (
                    w.state == "ready"
                    and not w.reprofiling
                    and w.profiled_at > 0.0  # startup profile completed
                    and not w.assigned  # idle: stay off the hot path
                    and (stale_t or stale_n)
                )
                if due:
                    w.reprofiling = True
            if due:
                threading.Thread(
                    target=self._reprofile, args=(w,),
                    name=f"fleet-w{w.index}-reprofile", daemon=True,
                ).start()

    def _reprofile(self, w: _Worker) -> None:
        """One background link re-profile (cheaper than startup: one rep,
        no megabyte payload). A worker death mid-probe is absorbed — the
        supervision ladder owns restarts, and the old profile stands until
        a probe completes."""
        try:
            self._fit_link(w, [1 << 10, 1 << 15, 1 << 18], reps=1)
            with self._lock:
                self.stats.reprofiles += 1
        except (RuntimeError, TimeoutError):
            pass
        finally:
            with self._lock:
                w.reprofiling = False

    def _normalize_speeds(self) -> None:
        probed = [w.probe_s for w in self._workers if w.probe_s]
        if not probed:
            return
        ref = min(probed)
        for w in self._workers:
            if w.probe_s:
                w.speed = ref / w.probe_s

    # ------------------------------------------------------------- pipe I/O

    def _write_loop(self, w: _Worker, proc: subprocess.Popen) -> None:
        outbox = w.outbox  # bound to THIS incarnation (respawn swaps it)
        while True:
            item = outbox.get()
            if item is _STOP_WRITER:
                return
            try:
                _send_frame(proc.stdin, item)
            except (OSError, ValueError):
                return  # death is handled by the reader's EOF

    def _read_loop(self, w: _Worker, proc: subprocess.Popen) -> None:
        while True:
            try:
                msg = _recv_frame(proc.stdout)
            except Exception:
                msg = None
            if msg is None:
                break
            w.last_seen = time.perf_counter()
            t = msg.get("t")
            if t == "ready":
                with self._lock:
                    if proc is w.proc:
                        w.state = "ready"
                        # a fresh process carries none of its predecessor's
                        # failure streak: the breaker restarts closed
                        w.breaker = "closed"
                        w.consec_failures = 0
                w.ready_evt.set()
            elif t == "res":
                self._commit_result(w, proc, msg)
            elif t == "delta":
                self._commit_delta(w, proc, msg)
            elif t in ("pong", "prof"):
                with self._lock:
                    pending = w.rpc.pop(msg.get("n"), None)
                if pending is not None:
                    pending[1]["msg"] = msg
                    pending[0].set()
            # "hb" needs nothing beyond the last_seen update above
        if not self._stopping:
            self._handle_death(w, proc, "pipe EOF")

    # ------------------------------------------------------------- results

    def _commit_result(self, w: _Worker, proc, msg: dict) -> None:
        qid = msg["qid"]
        with self._lock:
            fq = w.assigned.pop(qid, None) if proc is w.proc else None
            if fq is None or qid in self._results:
                return  # stale duplicate (query was requeued after a death)
            res = msg["res"]
            res.worker = w.label
            res.retries = fq.retries
            res.wall_s = time.perf_counter() - fq.t0
            self._results[qid] = res
            if res.error:
                self.stats.failures += 1
                if res.error == "deadline":
                    self.stats.deadline_expired += 1
                self._breaker_failure(w)
            else:
                self._breaker_success(w)
            if res.degraded:
                self.stats.degraded_serves += 1
            if res.cache_hit:
                self.stats.cache_hits += 1
            if res.prefix_hit:
                self.stats.prefix_hits += 1
            if res.warm_started:
                self.stats.warm_starts += 1
            if res.suffix_update:
                self.stats.suffix_updates += 1
            iters = len(res.result.iterations)
            self.stats.iterations += iters
            self.stats.device_iterations[w.label] = (
                self.stats.device_iterations.get(w.label, 0) + max(1, iters)
            )
            self._observe_speed(w, fq, float(msg.get("serve_s", 0.0)))
        self._notify(qid)

    def _observe_speed(self, w: _Worker, fq: _FleetQuery, serve_s: float) -> None:
        """Online throughput tracking: serve times, normalized by the
        worker's current speed, maintain a per-tenant reference estimate;
        deviations from it re-estimate the worker's speed. Caller holds
        the lock."""
        if serve_s <= 0:
            return
        w.served += 1
        if w.straggler is not None and w.straggler.observe(w.served, serve_s):
            self.stats.straggler_flags += 1
        ref = self._tenant_ref_s.get(fq.fp)
        if ref is not None:
            obs = max(min(ref / serve_s, 20.0), 0.05)
            w.speed = 0.7 * w.speed + 0.3 * obs
        norm = serve_s * w.speed
        self._tenant_ref_s[fq.fp] = (
            norm if ref is None else 0.5 * ref + 0.5 * norm
        )

    def _notify(self, qid: int) -> None:
        cb = self.on_result
        if cb is not None:
            cb(qid)

    # ------------------------------------------------------------ placement

    def _live(self) -> list[_Worker]:
        """Placeable workers: ready, breaker not open, and (half-open) not
        already holding the single probe query. Caller holds the lock."""
        out = []
        for w in self._workers:
            if w.state != "ready" or w.breaker == "open":
                continue
            if w.breaker == "half-open" and w.assigned:
                continue  # one probe at a time until the breaker closes
            out.append(w)
        return out

    def _breaker_failure(self, w: _Worker) -> None:
        """One failure/deadline-timeout signal (caller holds the lock)."""
        w.consec_failures += 1
        if w.breaker == "half-open" or (
            w.breaker == "closed"
            and w.consec_failures >= self.breaker_threshold
        ):
            w.breaker = "open"
            w.breaker_until = time.perf_counter() + self.breaker_cooldown_s
            self.stats.breaker_opens += 1

    def _breaker_success(self, w: _Worker) -> None:
        w.consec_failures = 0
        if w.breaker == "half-open":
            w.breaker = "closed"  # probe served clean: resume placement

    def _cost(self, w: _Worker, fq: _FleetQuery) -> float:
        est = self._tenant_ref_s.get(fq.fp, self.default_query_s)
        return w.link.seconds(fq.nbytes) + (len(w.assigned) + 1) * est / max(
            w.speed, 1e-3
        )

    def _place(self, fq: _FleetQuery) -> _Worker | None:
        """Pick a worker for ``fq`` (None when none is live — the query
        waits in ``_pending`` for a restart). Caller holds the lock."""
        live = self._live()
        if not live:
            return None
        home_i = self._tenant_home.get(fq.fp)
        home = (
            self._workers[home_i]
            if home_i is not None and self._workers[home_i].state == "ready"
            else None
        )
        if self.placement == "rr":
            if home is None:
                home = live[self._rr % len(live)]
                self._rr += 1
                self._tenant_home[fq.fp] = home.index
            return home
        best = min(live, key=lambda w: (self._cost(w, fq), w.index))
        if home is None:
            self._tenant_home[fq.fp] = best.index
            return best
        if best is not home and self._cost(best, fq) < (
            self.rebalance_margin * self._cost(home, fq)
        ):
            # decisively cheaper elsewhere: move the tenant (it forfeits
            # the old home's warm cache, which the margin prices in)
            self.stats.rebalances += 1
            self._tenant_home[fq.fp] = best.index
            return best
        return home

    def _dispatch(self, fq: _FleetQuery, w: _Worker) -> None:
        """Hand a query to a worker (caller holds the lock). The payload is
        framed by the worker's writer thread, so a full pipe never blocks
        the scheduler."""
        fq.dispatch_t = time.perf_counter()
        w.assigned[fq.qid] = fq
        msg = {
            "t": "q", "qid": fq.qid, "x": fq.x, "cfg": fq.cfg,
            "cost": fq.cost, "method": fq.method, "downstream": fq.downstream,
            "xds": fq.execute_downstream,
        }
        if fq.deadline_t is not None:
            # remaining budget at dispatch; the worker burns it from frame
            # receipt, so pipe queueing time counts against the query
            msg["deadline_s"] = fq.deadline_t - fq.dispatch_t
        w.outbox.put(msg)

    # -------------------------------------------------------------- intake

    def submit(
        self, x, cfg=None, cost=None, *, method: str = "pca",
        downstream: str | None = None, execute_downstream: bool = False,
        deadline_s: float | None = None,
    ) -> int:
        qid = self.try_submit(
            x, cfg, cost, method=method, downstream=downstream,
            execute_downstream=execute_downstream, deadline_s=deadline_s,
        )
        assert qid is not None  # unbounded submit never rejects
        return qid

    def try_submit(
        self, x, cfg=None, cost=None, *, method: str = "pca",
        downstream: str | None = None, execute_downstream: bool = False,
        max_backlog: int | None = None, deadline_s: float | None = None,
        fingerprint: str | None = None, t_start: float | None = None,
    ) -> int | None:
        """Enqueue unless the fleet backlog is at ``max_backlog`` (ingest
        backpressure). The conversion/hash work runs on the submitter's
        thread, like ``DropService.try_submit`` (and like there, a caller
        that already hashed the converted dataset passes ``fingerprint``,
        and one that began the submit earlier its start as ``t_start``,
        from which ``stats.submit_s`` is booked).
        A NaN/Inf dataset finishes immediately with
        ``error="invalid_input"`` and never crosses a worker pipe."""
        import numpy as np

        from repro.core.types import DropConfig
        from repro.serve_drop.cache import dataset_fingerprint

        if not self._started:
            self.start()
        if t_start is None:
            t_start = time.perf_counter()
        if execute_downstream and downstream is None:
            raise ValueError("execute_downstream requires a downstream task")
        x = np.ascontiguousarray(np.asarray(x), dtype=np.float32)
        if not np.all(np.isfinite(x)):
            return self._reject_invalid(x, method)
        cfg = cfg or DropConfig()
        spec = _cost_spec(cost)
        fp = fingerprint or dataset_fingerprint(x)
        with self._lock:
            self.stats.submit_s += time.perf_counter() - t_start
            if max_backlog is not None and self._backlog_locked() >= max_backlog:
                self.stats.rejected += 1
                return None
            qid = self._next_id
            self._next_id += 1
            self.stats.queries += 1
            fq = _FleetQuery(
                qid=qid, x=x, cfg=cfg, cost=spec, method=method,
                downstream=downstream, fp=fp, t0=time.perf_counter(),
                nbytes=int(x.nbytes), execute_downstream=execute_downstream,
                deadline_t=(
                    None if deadline_s is None
                    else time.perf_counter() + float(deadline_s)
                ),
            )
            w = self._place(fq)
            if w is None:
                self._pending.append(fq)
            else:
                self._dispatch(fq, w)
        return qid

    def _reject_invalid(self, x, method: str) -> int:
        """Input-poisoning guard, fleet edition (mirrors
        ``DropService._reject_invalid``): allocate the qid, finish it with
        ``error="invalid_input"``, notify waiters."""
        import numpy as np

        from repro.core.types import ReduceResult
        from repro.serve_drop.service import ServeResult

        d = int(x.shape[1]) if x.ndim == 2 else 0
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
        self._notify(qid)
        return qid

    def _backlog_locked(self) -> int:
        return len(self._pending) + sum(
            len(w.assigned) for w in self._workers
        )

    def backlog(self) -> int:
        with self._lock:
            return self._backlog_locked()

    def take_result(self, qid: int):
        with self._lock:
            return self._results.pop(qid, None)

    def result(self, qid: int, timeout: float | None = None):
        """Block until query ``qid`` finishes (fault handling guarantees it
        does while any worker survives); raises TimeoutError otherwise."""
        deadline = None if timeout is None else time.perf_counter() + timeout
        while True:
            res = self.take_result(qid)
            if res is not None:
                return res
            if deadline is not None and time.perf_counter() > deadline:
                raise TimeoutError(f"query {qid} still pending")
            time.sleep(0.002)

    # ------------------------------------------------------------- pub/sub

    def subscribe(self, query) -> int:
        """Open a delta subscription (``delta.SubscribeQuery``). The home
        worker runs the full delta subsystem locally (tracker, incremental
        analytics) and streams framed ``delta`` messages back; the
        supervisor routes, restamps sequence numbers, and mirrors the
        grown dataset. A home worker's death does NOT close the
        subscription: it is re-homed to a live worker, whose bootstrap
        rollback reaches the client as ``ROLLBACK(reason="rehome")`` —
        the client restates and converges without re-subscribing."""
        import numpy as np

        from repro.serve_drop.cache import dataset_fingerprint
        from repro.serve_drop.delta import SubscribeQuery

        if not self._started:
            self.start()
        if not isinstance(query, SubscribeQuery):
            raise TypeError("fleet.subscribe takes a SubscribeQuery")
        x = np.ascontiguousarray(np.asarray(query.x), dtype=np.float32)
        if not np.all(np.isfinite(x)):
            with self._lock:
                self.stats.invalid_inputs += 1
            raise ValueError("invalid_input: dataset contains NaN/Inf")
        query.x = x  # journal and mirror the converted dataset
        fp = dataset_fingerprint(x)
        with self._lock:
            live = self._live()
            if not live:
                raise RuntimeError("no live workers to home the subscription")
            home_i = self._tenant_home.get(fp)
            if home_i is not None and self._workers[home_i].state == "ready":
                w = self._workers[home_i]  # warm cache: same data, same home
            else:
                w = min(live, key=lambda c: (len(c.assigned), c.index))
                self._tenant_home[fp] = w.index
            sid = self._next_sub_id
            self._next_sub_id += 1
            sub = _FleetSub(sid=sid, worker=w.index, fp=fp, query=query, x=x)
            self._subs[sid] = sub
            self.stats.subscriptions += 1
            # journal before the caller can observe the sid: a crash after
            # subscribe() returned always finds the stream in the WAL
            if self._wal is not None:
                self._wal.journal_subscribe(sid, query)
            self._send_sub(sub, w)
        return sid

    def _send_sub(self, sub: _FleetSub, w: _Worker) -> None:
        """Frame a subscribe for this sub's (grown) dataset to ``w`` and
        make it the home (caller holds the lock)."""
        sub.worker = w.index
        q = sub.query
        w.outbox.put({
            "t": "sub", "sid": sub.sid, "x": sub.x, "cfg": q.cfg,
            "method": q.method, "eps": q.eps,
            "min_samples": q.min_samples, "bandwidth": q.bandwidth,
            "rotation_tol": q.rotation_tol,
        })

    def append(self, sub_id: int, suffix) -> None:
        import numpy as np

        from repro.serve_drop.delta import SubscriptionClosed

        suffix = np.ascontiguousarray(np.asarray(suffix), dtype=np.float32)
        if not np.all(np.isfinite(suffix)):
            with self._lock:
                self.stats.invalid_inputs += 1
            raise ValueError("invalid_input: suffix contains NaN/Inf")
        with self._lock:
            sub = self._subs.get(sub_id)
            if sub is None or sub.state == "closed":
                raise SubscriptionClosed(f"subscription {sub_id} is closed")
            # journal-then-queue: a crash after the fsync'd record recovers
            # the suffix; a crash before it means the append never happened
            if self._wal is not None:
                self._wal.journal_append(sub_id, suffix)
            # concatenate makes a NEW array, so a "sub" frame already
            # queued with the old reference is unaffected
            sub.x = np.concatenate([sub.x, suffix])
            w = self._workers[sub.worker] if sub.worker >= 0 else None
            if sub.rehoming or w is None or w.state != "ready":
                # the new home has not bootstrapped yet (or the sub is
                # orphaned awaiting a restart): buffer — flushed right
                # after the re-home rollback commits
                sub.pending_appends.append(suffix)
            else:
                w.outbox.put({"t": "app", "sid": sub_id, "x": suffix})

    def poll_deltas(self, sub_id: int, max_n: int | None = None) -> list:
        with self._lock:
            sub = self._subs.get(sub_id)
            if sub is None:
                raise KeyError(f"unknown subscription {sub_id}")
            out: list = []
            while sub.deltas and (max_n is None or len(out) < max_n):
                out.append(sub.deltas.popleft())
            if out and self._wal is not None:
                # ack-at-poll: journal the client-visible frontier BEFORE
                # the caller sees these deltas — deltas emitted but never
                # polled die with the process, and the recover rollback at
                # the acked seq is exactly what the client expects next
                self._wal.journal_ack(sub_id, out[-1]["seq"] + 1)
            return out

    def unsubscribe(self, sub_id: int, *, force: bool = False) -> None:
        """Ask the home worker to close the subscription (its final
        ``closed`` delta flows back framed). ``force=True`` additionally
        fabricates the terminal delta NOW — late worker emissions for a
        closed sub are dropped — so drain paths terminate deterministically
        even when the home worker is wedged."""
        notify = False
        with self._lock:
            sub = self._subs.get(sub_id)
            if sub is None or sub.state == "closed":
                return
            w = self._workers[sub.worker] if sub.worker >= 0 else None
            ready = (
                w is not None and w.state == "ready" and not sub.rehoming
            )
            if ready:
                w.outbox.put({"t": "unsub", "sid": sub_id})
            if force or not ready:
                # mid-rehome or orphaned subs close immediately: the late
                # bootstrap rollback is dropped by _commit_delta
                self._close_sub(sub, None)
                notify = True
        if notify:
            self._notify_delta(sub_id)

    def live_subscriptions(self) -> list[int]:
        with self._lock:
            return [
                sid for sid, sub in self._subs.items()
                if sub.state != "closed"
            ]

    def _close_sub(self, sub: _FleetSub, error: str | None) -> None:
        """Fabricate the terminal delta (caller holds the lock)."""
        sub.deltas.append(
            {"kind": "closed", "seq": sub.next_seq, "error": error}
        )
        sub.next_seq += 1
        sub.state = "closed"
        sub.error = error
        sub.rehoming = False
        sub.recovering = False
        sub.pending_appends.clear()
        self._journal_end(sub.sid)

    def _notify_delta(self, sub_id: int) -> None:
        cb = self.on_delta
        if cb is not None:
            cb(sub_id)

    def _commit_delta(self, w: _Worker, proc, msg: dict) -> None:
        sid = msg["sid"]
        dlt = msg["delta"]
        with self._lock:
            sub = self._subs.get(sid)
            if (
                sub is None or sub.state == "closed"
                or proc is not w.proc or sub.worker != w.index
            ):
                return  # late emission for a closed/stale/re-homed sub
            # the supervisor owns sequence numbering: a re-homed worker's
            # local stream restarts at 0, so every delta is restamped with
            # the sub's continuous position (frames from the single home
            # arrive in emission order, so this is safe)
            dlt["seq"] = sub.next_seq
            sub.next_seq += 1
            kind = dlt.get("kind")
            if kind == "rollback" and dlt.get("reason") == "subscribe":
                if sub.recovering:
                    # first bootstrap after a supervisor restart: the WAL
                    # resumed this stream, the client restates and carries
                    # on at the acked seq without re-subscribing
                    dlt["reason"] = "recover"
                    sub.recovering = False
                elif sub.bootstrapped:
                    # the new home's bootstrap after a re-home: the client
                    # is already live, so this is a re-home restate, not a
                    # fresh subscribe
                    dlt["reason"] = "rehome"
            if kind != "closed":
                sub.bootstrapped = True
            flush: list = []
            if sub.rehoming and kind == "rollback":
                # new home is live: release appends buffered mid-re-home
                sub.rehoming = False
                flush, sub.pending_appends = sub.pending_appends, []
            sub.deltas.append(dlt)
            if kind == "closed":
                sub.state = "closed"
                sub.error = dlt.get("error")
                self._journal_end(sid)
            else:
                if sub.state == "pending":
                    sub.state = "live"
                if kind == "append":
                    self.stats.delta_serves += 1
                elif kind == "rollback" and dlt.get("reason") not in (
                    "subscribe", "rehome"
                ):
                    self.stats.rollbacks += 1
            for suffix in flush:
                w.outbox.put({"t": "app", "sid": sid, "x": suffix})
        self._notify_delta(sid)

    # ---------------------------------------------------------- supervision

    def _monitor_loop(self) -> None:
        while not self._stopping:
            self._supervise_once()
            time.sleep(0.02)

    def _supervise_once(self) -> None:
        """One supervision tick: exitcode/heartbeat death checks, due
        restarts, breaker cooldowns, deadline expiry, orphaned-sub
        re-homing, and pending-query placement."""
        now = time.perf_counter()
        for w in self._workers:
            state, proc = w.state, w.proc
            if proc is None:
                continue
            if state in ("starting", "ready"):
                if proc.poll() is not None:
                    self._handle_death(w, proc, f"exit {proc.returncode}")
                elif (
                    state == "ready"
                    and now - w.last_seen > self.heartbeat_timeout_s
                ):
                    # alive but mute: kill so the pipe EOFs deterministically
                    proc.kill()
                    self._handle_death(w, proc, "heartbeat timeout")
            elif state == "restarting" and now >= w.restart_due:
                with self._lock:
                    if w.state != "restarting":
                        continue
                    w.incarnation += 1
                    self.stats.worker_restarts += 1
                    self._spawn(w)
        with self._lock:
            for w in self._workers:
                if w.breaker == "open" and now >= w.breaker_until:
                    w.breaker = "half-open"  # admit a single probe query
        self._expire_due_queries(now)
        self._rehome_orphans()
        self._maybe_reprofile(now)
        self._flush_pending()

    def _expire_due_queries(self, now: float) -> None:
        """Fail pending AND assigned queries past their deadline with
        ``error="deadline"`` — a wedged worker must not hold a client past
        its budget (the worker's own late result is dropped as a stale
        duplicate). An assigned expiry also counts as a breaker timeout
        signal for its worker."""
        expired: list[int] = []
        with self._lock:
            if self._pending:
                keep: deque = deque()
                for fq in self._pending:
                    if fq.deadline_t is not None and now > fq.deadline_t:
                        expired.append(fq.qid)
                        self._expire_query(fq)
                    else:
                        keep.append(fq)
                self._pending = keep
            for w in self._workers:
                for qid, fq in list(w.assigned.items()):
                    if fq.deadline_t is not None and now > fq.deadline_t:
                        del w.assigned[qid]
                        expired.append(qid)
                        self._expire_query(fq)
                        self._breaker_failure(w)
        for qid in expired:
            self._notify(qid)

    def _expire_query(self, fq: _FleetQuery) -> None:
        self._fail_query(fq, "deadline")
        self.stats.deadline_expired += 1

    def _rehome_orphans(self) -> None:
        """Place subscriptions parked by a death that found no live worker
        (caller does NOT hold the lock)."""
        notify: list[int] = []
        with self._lock:
            live = self._live()
            for sub in self._subs.values():
                if sub.worker >= 0 or sub.state == "closed":
                    continue
                if live:
                    self._send_sub(
                        sub,
                        min(live, key=lambda c: (len(c.assigned), c.index)),
                    )
                elif not any(
                    x.state in ("starting", "ready", "restarting", "dead")
                    for x in self._workers
                ):
                    self._close_sub(sub, "no workers left in the fleet")
                    self.stats.failures += 1
                    notify.append(sub.sid)
        for sid in notify:
            self._notify_delta(sid)

    def _flush_pending(self) -> None:
        with self._lock:
            while self._pending:
                fq = self._pending[0]
                w = self._place(fq)
                if w is None:
                    return
                self._pending.popleft()
                self._dispatch(fq, w)

    def _handle_death(self, w: _Worker, proc, why: str) -> None:
        """A worker died (or was killed as hung): requeue or fail its
        in-flight queries so no client ever hangs, then schedule the
        restart under the RestartPolicy. Subscriptions homed on the dead
        worker are re-homed: the supervisor holds everything a fresh
        bootstrap needs (query params + grown dataset mirror), so the sub
        re-subscribes on a live worker and the client sees
        ``ROLLBACK(reason="rehome")`` instead of a terminal CLOSED. Only
        a fleet with no restartable slot left closes them."""
        failed: list[int] = []
        dead_subs: list[int] = []
        with self._lock:
            if proc is not w.proc or w.state in ("dead", "restarting", "lost"):
                return
            w.state = "dead"
            self.stats.worker_deaths += 1
            w.outbox.put(_STOP_WRITER)
            for n, (evt, slot) in list(w.rpc.items()):
                slot["msg"] = {"t": "dead"}
                evt.set()
                w.rpc.pop(n, None)
            orphans = list(w.assigned.values())
            w.assigned.clear()
            exitcode = proc.poll()
            for fq in orphans:
                if fq.qid in self._results:
                    continue
                fq.retries += 1
                self._tenant_home.pop(fq.fp, None)  # home is gone
                if fq.retries > self.max_query_retries:
                    failed.append(fq.qid)
                    self._fail_query(
                        fq,
                        f"{w.label} died ({why}, exit={exitcode}); "
                        f"{fq.retries - 1} retries exhausted",
                    )
                else:
                    self.stats.requeued_queries += 1
                    tgt = self._place(fq)
                    if tgt is None:
                        self._pending.append(fq)
                    else:
                        self._dispatch(fq, tgt)
            if w.restarts >= self.restart_policy.max_restarts:
                w.state = "lost"
                self.stats.workers_lost += 1
            else:
                w.restarts += 1
                w.state = "restarting"
                w.restart_due = time.perf_counter() + self.restart_policy.delay(
                    w.restarts
                )
            fleet_alive = any(
                x.state in ("starting", "ready", "restarting", "dead")
                for x in self._workers
            )
            if not fleet_alive:
                # nobody left to restart: fail the stranded backlog
                while self._pending:
                    fq = self._pending.popleft()
                    failed.append(fq.qid)
                    self._fail_query(fq, "no workers left in the fleet")
            for sub in list(self._subs.values()):
                if sub.worker != w.index or sub.state == "closed":
                    continue
                if not fleet_alive:
                    self._close_sub(
                        sub,
                        f"{w.label} died ({why}, exit={exitcode}); "
                        "no workers left to re-home",
                    )
                    self.stats.failures += 1
                    dead_subs.append(sub.sid)
                    continue
                # re-home: buffered/folded state is rebuilt from the
                # supervisor's dataset mirror on a live worker; with no
                # worker live RIGHT NOW the sub parks (worker=-1) and the
                # supervision tick places it once a restart lands
                sub.rehoming = True
                self.stats.sub_rehomes += 1
                live = self._live()
                if live:
                    self._send_sub(
                        sub,
                        min(live, key=lambda c: (len(c.assigned), c.index)),
                    )
                else:
                    sub.worker = -1
            if not fleet_alive:
                # orphans parked by EARLIER deaths can never be placed
                for sub in self._subs.values():
                    if sub.worker < 0 and sub.state != "closed":
                        self._close_sub(sub, "no workers left in the fleet")
                        self.stats.failures += 1
                        dead_subs.append(sub.sid)
        for qid in failed:
            self._notify(qid)
        for sid in dead_subs:
            self._notify_delta(sid)

    def _fail_query(self, fq: _FleetQuery, error: str) -> None:
        """Finish a query with ServeResult.error (caller holds the lock)."""
        import numpy as np

        from repro.core.types import ReduceResult
        from repro.serve_drop.service import ServeResult

        d = int(fq.x.shape[1])
        self.stats.failures += 1
        self._results[fq.qid] = ServeResult(
            query_id=fq.qid,
            result=ReduceResult(
                v=np.zeros((d, 0), np.float32), mean=np.zeros(d, np.float32),
                k=0, tlb_estimate=0.0, satisfied=False, runtime_s=0.0,
                iterations=[], method=fq.method,
            ),
            wall_s=time.perf_counter() - fq.t0,
            error=error,
            retries=fq.retries,
        )

    # ------------------------------------------------------------ draining

    def _poll_once(self) -> tuple[bool, bool]:
        """Scheduler-primitive shim for ``IngestFrontend``: results arrive
        on reader threads, so a tick only supervises; (False, more)."""
        self._supervise_once()
        return False, self.backlog() > 0

    def poll(self) -> bool:
        """One supervision tick; True while queries are pending. Sleeps a
        moment so bare ``while poll(): pass`` loops don't busy-spin."""
        _, more = self._poll_once()
        if more:
            time.sleep(0.002)
        return more

    def run(self, timeout: float | None = None) -> list:
        """Drain everything submitted so far; results ordered by query id
        (the ``DropService.run`` contract)."""
        if not self._started:
            self.start()
        deadline = None if timeout is None else time.perf_counter() + timeout
        while self.backlog():
            if deadline is not None and time.perf_counter() > deadline:
                raise TimeoutError(f"{self.backlog()} queries still pending")
            time.sleep(0.005)
        with self._lock:
            out = [self._results[qid] for qid in sorted(self._results)]
            self._results = {}
        return out

    # ------------------------------------------------------------ telemetry

    def occupancy(self) -> dict[str, int]:
        with self._lock:
            return {
                w.label: self.stats.device_iterations.get(w.label, 0)
                for w in self._workers
            }

    def link_profiles(self) -> dict[str, LinkProfile]:
        with self._lock:
            return {w.label: w.link for w in self._workers}

    def worker_speeds(self) -> dict[str, float]:
        with self._lock:
            return {w.label: w.speed for w in self._workers}


if __name__ == "__main__":  # direct worker entry (debugging aid)
    _worker_main(sys.argv[1:])
