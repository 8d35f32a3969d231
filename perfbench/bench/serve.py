"""Driver of a query cell: multi-tenant DR + kNN serving, open loop.

Tenants are collections at the configuration's shapes; popularity rank r is
collection r mod C, seed index r div C, so ranks interleave across shapes.
Set-up serves every tenant cold on a throwaway service (compiling every
shape, stalls and all), then, on the service the window uses, every tenant
again and a warm-up prefix of the trace drawn from its own fixed stream (the
repository's two-warm-run convention: DROP stops on wall time, so only a run
without compile stalls pins the shapes the window sees). The window
then submits each request at its due time from one thread; a request's
latency runs from its due time to the moment the service signals its
result, kNN included.
"""

from __future__ import annotations

import sys
import time
from collections import Counter

import numpy as np

from bench import data, reference, traffic as traffic_mod

RESULT_WAIT_S = 60.0  # how long after the window closes answers may come
CHECK_ROWS = 4096  # kNN rows checked per sampled answer
SCHEDULE_SEED = 0  # warm-up and window schedules, the same in every run


def log(msg: str) -> None:
    print(msg, file=sys.stdout, flush=True)


class Tenants:
    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        cols = config["collections"]
        per = int(traffic["seeds_per_collection"])
        self.specs, self.x = [], []
        for r in range(per * len(cols)):
            spec = cols[r % len(cols)]
            self.specs.append(spec)
            self.x.append(data.make_collection(spec, seed, r // len(cols))[1])

    def __len__(self) -> int:
        return len(self.x)


def drop_config(config: dict, seed: int):
    from repro.core import DropConfig

    # the program's own seed must fit its PRNG: derive it from the run seed
    prog_seed = int(data.collection_rng(seed, 9).integers(0, 2**31 - 1))
    return DropConfig(**config["drop"], seed=prog_seed)


def build_service(config: dict):
    from repro.serve_drop import DropService, IngestFrontend

    svc = DropService(**config["service"])
    fe = IngestFrontend(svc)
    return svc, fe


class Stamps:
    """Completion times taken when the service signals a result (the
    frontend's ``on_result`` hook, on the drain thread), not when a
    collector gets round to it."""

    def __init__(self, svc) -> None:
        self.done: dict[int, float] = {}
        self._next = svc.on_result
        svc.on_result = self._stamp

    def _stamp(self, qid: int) -> None:
        self.done[qid] = time.perf_counter()
        self._next(qid)


def serve_all(fe, xs, cfg, downstream: str, chunk: int = 16) -> list:
    """Closed-loop: submit the datasets ``chunk`` at a time (inside the
    front end's queue capacity) and wait for each chunk's answers."""
    out = []
    for a in range(0, len(xs), chunk):
        qids = [fe.submit(x, cfg, downstream=downstream, execute_downstream=True)
                for x in xs[a:a + chunk]]
        out.extend(fe.result(q, 900.0) for q in qids)
    bad = [r.error for r in out if r.error is not None]
    if bad:
        raise RuntimeError(f"set-up query failed: {bad[0]}")
    return out


def setup(tenants: Tenants, config: dict, traffic: dict, cfg):
    """Warm every shape, then fill the window's service from a warm-up
    prefix of the trace. Returns (service, frontend, stamps)."""
    ds = config["downstream"]
    _, fe0 = build_service(dict(config, service=dict(
        config["service"], cache_entries=len(tenants))))
    with fe0:
        serve_all(fe0, tenants.x, cfg, ds)  # cold, compile stalls and all
    svc, fe = build_service(config)
    fe.start()
    rng = data.collection_rng(SCHEDULE_SEED, 2)
    n = int(traffic["warmup_requests"])
    order = traffic_mod.tenant_sequence(n, len(tenants), traffic, rng)
    # every tenant once first: each cold fit runs once without compile stalls
    order = np.concatenate([np.arange(len(tenants)), order])
    serve_all(fe, [tenants.x[t] for t in order], cfg, ds)
    return svc, fe, Stamps(svc)


def window(fe, tenants: Tenants, cfg, downstream: str, due: np.ndarray,
           who: np.ndarray, seconds: float) -> dict:
    """Submit request i at ``t0 + due[i]`` from this thread; return the
    window's per-request records once every answer is in or overdue."""
    from repro.serve_drop import RetryLater

    t0 = time.perf_counter()
    qids, late = [], np.zeros(len(due))
    for i, (t, r) in enumerate(zip(due, who)):
        wait = t0 + t - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late[i] = time.perf_counter() - (t0 + t)
        try:
            qids.append(fe.submit(tenants.x[r], cfg, downstream=downstream,
                                  execute_downstream=True))
        except RetryLater:
            qids.append(None)
    close = t0 + seconds
    time.sleep(max(0.0, close - time.perf_counter()))
    results = []
    for q in qids:
        if q is None:
            results.append(None)
            continue
        try:
            results.append(fe.result(q, max(0.0, close + RESULT_WAIT_S - time.perf_counter())))
        except TimeoutError:
            results.append(None)
    return {"t0": t0, "close": close, "end": time.perf_counter(),
            "qids": qids, "late": late, "results": results}


def latencies(win: dict, due: np.ndarray, stamps: Stamps) -> np.ndarray:
    """Due time to signalled answer, seconds; a refused, failed or missing
    request reads +inf (it misses every limit)."""
    out = np.full(len(due), np.inf)
    for i, (q, r) in enumerate(zip(win["qids"], win["results"])):
        if q is not None and r is not None and r.error is None and q in stamps.done:
            out[i] = stamps.done[q] - (win["t0"] + due[i])
    return out


def percentile_ms(lat: np.ndarray, q: float) -> float:
    """Nearest-rank percentile of all requests, failures ranked last."""
    s = np.sort(lat)
    return float(s[max(0, int(np.ceil(q / 100.0 * len(s))) - 1)] * 1e3)


def checked_sample(win: dict, lat: np.ndarray, n: int, seed: int) -> list[int]:
    """Indices of answered requests to check: ``n`` drawn from the seed,
    with the slowest answered request always among them."""
    ok = [i for i, r in enumerate(win["results"]) if r is not None and r.error is None]
    if not ok:
        return []
    rng = data.collection_rng(seed, 3)
    pick = set(rng.choice(ok, size=min(n, len(ok)), replace=False).tolist())
    pick.add(max(ok, key=lambda i: lat[i]))
    return sorted(pick)


def served_knn(r, x, rows) -> np.ndarray:
    return np.asarray(r.downstream)


def check_answers(tenants: Tenants, win: dict, picks: list[int], cfg, seed: int,
                  pairs: int, knn_answer=served_knn) -> dict:
    """The sampled answers against the float64 references.

    TLB: each distinct served map's host TLB on fixed pairs, as standard
    errors below the floor the configuration guarantees: B less the
    half-width of the c-interval on the configuration's pair budget
    (``max_pairs``), where DROP decides by the sampled mean once the
    interval clears B or the budget is spent. The worst map is the number
    compared. Its limit is the confidence c held over every map checked:
    the program's own two-sided interval at 1 - (1 - c) / maps. kNN: each
    answer on ``CHECK_ROWS`` rows drawn from the seed, the worst excess.
    ``knn_answer(result, x, rows)`` gives the kNN under check (the control
    puts its own in the program's place)."""
    knn, bases = 0.0, {}
    for i in picks:
        r, t = win["results"][i], int(win["who"][i])
        x, v = tenants.x[t], np.asarray(r.result.v)
        key = (t, v.tobytes())
        if key not in bases:
            prng = data.collection_rng(seed, 4, t)
            mean, se = reference.tlb(x, v, reference.sample_pairs(len(x), pairs, prng))
            # the budget's half-width, z_c sd / sqrt(max_pairs), in host
            # standard errors (sd / sqrt(pairs))
            slack = reference.z_two_sided(cfg.confidence) * np.sqrt(pairs / cfg.max_pairs)
            bases[key] = (cfg.target_tlb - mean) / max(se, 1e-12) - slack
        rows = data.collection_rng(seed, 5, i).choice(
            len(x), size=min(CHECK_ROWS, len(x)), replace=False)
        knn = max(knn, reference.knn_excess(reference.reduce_rows(x, v),
                                            knn_answer(r, x, rows), rows))
    zs = sorted(bases.values())
    return {"tlb_worst_map_se": zs[-1] if zs else np.inf,
            "tlb_limit_se": reference.z_two_sided(1.0 - (1.0 - cfg.confidence) / max(len(zs), 1)),
            "tlb_maps_se": [round(float(z), 3) for z in zs],
            "knn_excess": knn, "answers": len(picks), "maps": len(bases)}


class Session:
    """A query cell set up in this process: tenants, the service behind its
    front end, and completion stamps. ``measure`` runs one window."""

    def __init__(self, cell: dict, seed: int) -> None:
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.tenants = Tenants(self.config, self.traffic, seed)
        self.cfg = drop_config(self.config, seed)
        log(f"tenants: {len(self.tenants)} collections "
            f"{sorted({(s['name'], s['m'], s['d']) for s in self.tenants.specs})}")
        self.svc, self.fe, self.stamps = setup(
            self.tenants, self.config, self.traffic, self.cfg)

    def measure(self, seconds: float, traffic: dict | None = None,
                stream: int = 6, on_open=None) -> dict:
        """One window at ``traffic`` (default: the cell's); ``on_open`` runs
        just before it opens. Returns the window with its latencies and the
        service counters it moved."""
        traffic = traffic or self.traffic
        # the schedule (due times and tenants) is the same in every run: the
        # seed draws the rows and the program's seed, not the order, which
        # sets which queries queue behind which
        rng = data.collection_rng(SCHEDULE_SEED, stream)
        due = traffic_mod.arrivals(traffic, seconds, rng)
        who = traffic_mod.tenant_sequence(len(due), len(self.tenants), traffic, rng)
        before = self.svc.stats.as_dict()
        if on_open is not None:
            on_open()
        win = window(self.fe, self.tenants, self.cfg, self.config["downstream"],
                     due, who, seconds)
        after = self.svc.stats.as_dict()
        win.update(due=due, who=who, lat=latencies(win, due, self.stamps),
                   stats={k: v - before[k] for k, v in after.items()
                          if isinstance(v, (int, float)) and isinstance(before.get(k), (int, float))})
        answered = [r for r in win["results"] if r is not None and r.error is None]
        win["answered"] = len(answered)
        win["misses"] = sum(1 for r in answered if not r.cache_hit)
        log(f"window: {len(due)} requests due over {seconds:g} s "
            f"({traffic['rate_per_s']:g}/s); generator late p50 "
            f"{np.median(win['late']) * 1e3:.3f} ms, max {win['late'].max() * 1e3:.3f} ms")
        p95 = percentile_ms(win["lat"], 95) * 1e-3
        tail = [(win["lat"][i], r.cache_hit, r.wall_s) for i, r in enumerate(win["results"])
                if np.isfinite(win["lat"][i]) and win["lat"][i] >= p95]
        log(f"window tail (at or above p95): {len(tail)} answers, "
            f"{sum(1 for _, hit, _ in tail if not hit)} cold fits; median service "
            f"{np.median([w for *_, w in tail] or [0]) * 1e3:.3f} ms, median wait "
            f"{np.median([lat - w for lat, _, w in tail] or [0]) * 1e3:.3f} ms")
        log(f"window: {win['answered']} answered, {win['misses']} cold fits, "
            f"{win['answered'] - win['misses']} revalidated hits; p50 "
            f"{percentile_ms(win['lat'], 50):.3f} ms, p95 "
            f"{percentile_ms(win['lat'], 95):.3f} ms; last answer "
            f"{win['end'] - win['close']:.3f} s after the close; stats "
            f"{ {k: v for k, v in win['stats'].items() if v} }")
        return win

    def close(self) -> None:
        self.fe.close()
        del self.svc, self.fe


def run(cell: dict, seed: int, seconds: float, tracer=None) -> dict:
    """One run of a query cell. ``tracer`` (trace runs) brackets the window:
    it starts before the window opens and stops once every answer is in."""
    sess = Session(cell, seed)
    compiles0 = cell["compiles"].snapshot()
    win = sess.measure(seconds, on_open=None if tracer is None else tracer.start)
    compiles1 = cell["compiles"].snapshot()
    if tracer is not None:
        tracer.stop()
    peak = cell["memory_peak"]()
    sess.close()
    log(f"window: executables built {compiles1[0] - compiles0[0]} "
        f"(persistent-cache loads {compiles1[1] - compiles0[1]})")
    # accepted but never answered, or answered with an error
    unanswered = sum(1 for q, r in zip(win["qids"], win["results"])
                     if q is not None and (r is None or r.error is not None))
    picks = checked_sample(win, win["lat"], int(sess.traffic["checked_requests"]), seed)
    checked = check_answers(sess.tenants, win, picks, sess.cfg, seed,
                            int(sess.traffic["check_pairs"]))
    log(f"checked {checked['answers']} answers, {checked['maps']} distinct maps; "
        f"standard errors under the guaranteed floor per map {checked['tlb_maps_se']}")
    requests = [
        {"hit": bool(r.cache_hit), "tenant": int(t), "m": int(sess.tenants.x[t].shape[0]),
         "k": int(r.result.k)}
        for r, t in zip(win["results"], win["who"]) if r is not None and r.error is None
    ]
    served_k = Counter((q["tenant"], sess.tenants.specs[q["tenant"]]["rank"], q["k"])
                       for q in requests)
    log(f"window: answers by (tenant, rank, served k) {dict(sorted(served_k.items()))}")
    return {
        "setup_end": win["t0"],
        "attempted": len(win["due"]),
        "failed": len(win["due"]) - win["answered"],
        "e2e": {"query_p50_ms": percentile_ms(win["lat"], 50),
                "query_p95_ms": percentile_ms(win["lat"], 95)},
        "checks": {"tlb_worst_map_se": checked["tlb_worst_map_se"],
                   "knn_excess": checked["knn_excess"], "unanswered": unanswered},
        "limits": {"tlb_worst_map_se": checked["tlb_limit_se"]},
        "memory_peak_bytes": peak,
        "layer_ctx": {"stats": win["stats"], "requests": requests},
    }
