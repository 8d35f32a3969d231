"""Sharded multi-device DROP scheduler.

Extends the single-host ``DropService`` by *placing* each in-flight
``Reducer`` on a mesh device (``jax.device_put`` of the runner's PRNG key
plus a ``jax.default_device`` scope around its steps, for the PCA loop;
the single-shot baseline reducers are host-numpy and placement is pure
bookkeeping), so independent tenants' iterations execute on independent
devices:

* **placement** — admission assigns each cold runner to the least-loaded
  device slot; the runner's jitted stages (Halko fit, pairwise TLB) then
  dispatch to that device only.
* **per-class bucket caches** — one ``ShapeBucketCache`` per device
  *class* (platform): tenants on the same class quantize through one
  policy, so same-device tenants reuse XLA executables (the jit cache is
  keyed by shape x device) while a heterogeneous mesh (cpu + tpu) keeps
  separate telemetry per class.
* **work stealing** — between ``poll()`` rounds, an idle device steals the
  youngest queued runner from the heaviest same-class slot (migration is a
  single ``place()`` call because inter-step runner state is host numpy).
* **threaded drain** — ``run()`` on a multi-device mesh spawns one drain
  thread per device; each thread executes the same lock-protected
  ``_poll_once`` primitive, so steps of different tenants overlap across
  devices while scheduling stays serialized. Python-side overhead shares
  the GIL, but XLA compilation and execution release it — on a cold
  multi-tenant workload (the expensive case) compile+compute parallelize
  across the mesh.

Numerics: per-query results are bit-identical to the single-device
``DropService`` (and to sequential ``drop()``) — every runner owns its RNG
streams, placement never reorders a query's draws, and same-class devices
execute identical programs.

With one visible device the scheduler degenerates exactly to the base
class (single slot, no threads), so CPU test environments run unchanged.
``DropService(devices=n)`` builds this scheduler where ``n`` resolves to
more than one visible device, and the base service otherwise.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field

import jax

from repro.core.bucketing import ShapeBucketCache
from repro.core.reducer import make_reducer
from repro.serve_drop.service import DropService, ServeResult, _InFlight
from repro.sharding.specs import serve_devices


@dataclass
class _DeviceSlot:
    """One mesh device's run queue."""

    device: jax.Device
    runners: deque = field(default_factory=deque)

    @property
    def label(self) -> str:
        return str(self.device)


class ShardedDropService(DropService):
    """Multi-device DROP scheduler: per-device run queues + work stealing.

    ``devices`` may be an int (first n visible devices), an explicit device
    list, or None (every visible device). All other knobs match
    ``DropService``.
    """

    def __init__(
        self,
        *,
        devices: int | list | None = None,
        max_inflight: int = 4,
        cache_entries: int = 16,
        enable_cache: bool = True,
        cache_ttl: int | None = None,
        enable_suffix_update: bool = True,
        suffix_budget: float = 0.25,
        analytics_split: int | None = None,
        analytics_fanout: str | None = None,
        degrade_watermark_s: float | None = None,
        degrade_refresh_after: int | None = 3,
        state_dir: str | None = None,
        state_fsync: bool = True,
        failure_injector=None,
    ) -> None:
        if isinstance(devices, int) or devices is None:
            devices = serve_devices(devices)
        devices = list(devices)
        # served analytics default to the mesh fan-out when a real mesh
        # exists (every device computes one dataset-shard partial of the
        # pairwise scan; exact merges — see analytics.split), and to the
        # single-device split otherwise
        if analytics_fanout is None:
            analytics_fanout = "mesh" if len(devices) > 1 else "xla"
        # one bucket cache per device class: same-class tenants share one
        # quantization policy (=> shared executables per device), while a
        # mixed mesh keeps per-class bucket telemetry honest
        self.class_buckets: dict[str, ShapeBucketCache] = {}
        for d in devices:
            self.class_buckets.setdefault(d.platform, ShapeBucketCache())
        first_class = devices[0].platform
        super().__init__(
            max_inflight=max_inflight,
            cache_entries=cache_entries,
            bucket=self.class_buckets[first_class],
            enable_cache=enable_cache,
            cache_ttl=cache_ttl,
            enable_suffix_update=enable_suffix_update,
            suffix_budget=suffix_budget,
            analytics_split=analytics_split,
            analytics_fanout=analytics_fanout,
            analytics_devices=(
                tuple(devices) if analytics_fanout == "mesh" else None
            ),
            # deadlines and the degradation ladder live in the shared
            # _admit/_poll_once primitives, so the sharded drain threads
            # enforce them per-device with no further plumbing
            degrade_watermark_s=degrade_watermark_s,
            degrade_refresh_after=degrade_refresh_after,
            state_dir=state_dir,
            state_fsync=state_fsync,
            failure_injector=failure_injector,
        )
        self.devices = devices
        self._slots = [_DeviceSlot(d) for d in devices]
        self._rr = 0  # round-robin cursor over slots for _pop_runner

    # -------------------------------------------------------- placement

    def _stepping_by_device(self) -> dict[str, int]:
        """Work owned by each device that is not in its run queue: items
        mid-compute outside the lock AND queued validations (both carry a
        device). Caller holds the lock."""
        counts: dict[str, int] = {}
        for fl in list(self._stepping_now) + list(self._validations):
            dev = getattr(fl, "device", None)
            if dev is not None:
                counts[str(dev)] = counts.get(str(dev), 0) + 1
        return counts

    def _load(self, slot: _DeviceSlot, stepping: dict[str, int]) -> int:
        """A device's live tenants: queued runners + its mid-step work +
        its queued validations. Placement and stealing share this
        accounting, so admissions never pile onto a device that merely
        LOOKS empty because its work is all mid-step, and a burst of
        cache-hit validations spreads across the mesh instead of landing
        on one 'idle' device."""
        return len(slot.runners) + stepping.get(slot.label, 0)

    def _least_loaded(self) -> _DeviceSlot:
        stepping = self._stepping_by_device()
        return min(self._slots, key=lambda s: self._load(s, stepping))

    def _launch(self, q, fp, warm_k, t0) -> None:
        """Admit a cold runner onto the least-loaded device slot."""
        slot = self._least_loaded()
        bucket = self.class_buckets[slot.device.platform]
        runner = make_reducer(
            q.method, q.x, q.cfg, q.cost, warm_prev_k=warm_k, bucket=bucket
        )
        runner.place(slot.device)
        fl = _InFlight(
            q, runner, fp, warm_started=warm_k is not None, t0=t0,
            device=slot.device,
        )
        slot.runners.append(fl)

    def _place_validation(self, val) -> None:
        """Validations are device compute too: load-balance them so a
        repeat-heavy workload does not turn device 0 into the hit-serving
        hotspot."""
        val.device = self._least_loaded().device

    def _validation_bucket(self, val):
        device = val.device or self.devices[0]
        return self.class_buckets[device.platform]

    def _validate(self, val):
        with jax.default_device(val.device or self.devices[0]):
            return super()._validate(val)

    def _apply_suffix_update(self, upd):
        # the merge itself is host numpy, but the TLB gate's jitted table
        # must land on the work item's device like any validation
        with jax.default_device(upd.device or self.devices[0]):
            return super()._apply_suffix_update(upd)

    def _apply_delta(self, item):
        # delta computes (transform + rectangular pairwise scans + TLB
        # gates) are device compute: pin them to the item's assigned device
        # so subscription traffic load-balances like validations do
        with jax.default_device(item.device or self.devices[0]):
            return super()._apply_delta(item)

    def _apply_downstream(self, ds):
        # mesh fan-out claims the whole mesh by construction (shard_map
        # places one dataset-shard partial per device), so the work item's
        # device assignment is bookkeeping only; a single-device analytics
        # run is pinned like any validation
        if self.analytics_fanout == "mesh":
            return super()._apply_downstream(ds)
        with jax.default_device(ds.device or self.devices[0]):
            return super()._apply_downstream(ds)

    def _slot_of(self, device) -> _DeviceSlot:
        return next(s for s in self._slots if s.device == device)

    # ------------------------------------------------------- scheduling

    def _inflight_count(self) -> int:
        return (
            sum(len(s.runners) for s in self._slots)
            + len(self._validations)
            + len(self._stepping_now)
        )

    def _iter_inflight(self):
        for s in self._slots:
            yield from s.runners
        yield from self._validations
        yield from self._stepping_now

    def _rebalance(self) -> None:
        """Work stealing: an idle device takes the youngest queued runner
        from the heaviest same-class device. "Idle" counts runners mid-step
        on other drain threads, so a device busy with its only tenant never
        triggers a migration ping-pong; a donor keeps at least one runner.
        The youngest runner has the most iterations left, so the migration
        cost (re-compiling its shapes on the new device, if unseen there)
        amortizes best. Caller holds the lock."""
        stepping = self._stepping_by_device()

        def load(s: _DeviceSlot) -> int:
            return self._load(s, stepping)

        for idle in self._slots:
            if load(idle) > 0:
                continue
            donors = [
                s
                for s in self._slots
                if s is not idle
                and len(s.runners) >= 1
                and load(s) >= 2
                and s.device.platform == idle.device.platform
            ]
            if not donors:
                continue
            donor = max(donors, key=load)
            fl = donor.runners.pop()  # youngest: admitted/rotated last
            fl.runner.place(idle.device)
            fl.device = idle.device
            idle.runners.append(fl)
            self.stats.steals += 1

    def _pop_runner(self) -> _InFlight | None:
        """Round-robin across device slots so concurrent pollers pick
        runners on distinct devices. Caller holds the lock."""
        self._rebalance()
        for off in range(len(self._slots)):
            slot = self._slots[(self._rr + off) % len(self._slots)]
            if slot.runners:
                self._rr = (self._rr + off + 1) % len(self._slots)
                return slot.runners.popleft()
        return None

    def _requeue_runner(self, fl: _InFlight) -> None:
        self._slot_of(fl.device).runners.append(fl)

    def _discard_runner(self, fl: _InFlight) -> None:
        for s in self._slots:
            try:
                s.runners.remove(fl)
                return
            except ValueError:
                continue

    def _step(self, fl: _InFlight) -> bool:
        # default_device routes the step's uncommitted arrays (TLB pair
        # batches, basis upload) to the runner's device; the committed PRNG
        # key already pins the Halko fit there. Occupancy bookkeeping lives
        # in the base _step (labelled by fl.device).
        with jax.default_device(fl.device):
            return super()._step(fl)

    # ----------------------------------------------------------- drain

    def run(self) -> list[ServeResult]:
        """Drain all submitted queries. On a multi-device mesh, one drain
        thread per device executes the shared scheduler primitive; results
        are ordered by query id either way."""
        if len(self.devices) == 1:
            return super().run()
        threads = [
            threading.Thread(target=self._drain, name=f"drop-drain-{i}")
            for i in range(len(self.devices))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return self._collect_results()

    def _drain(self) -> None:
        while True:
            stepped, more = self._poll_once()
            if not more:
                return
            if not stepped:
                # every placeable runner is mid-step on another thread:
                # yield briefly instead of spinning on the lock
                time.sleep(0.0005)

    def occupancy(self) -> dict[str, int]:
        """Iterations executed per device (scheduler balance telemetry)."""
        with self._lock:
            return {
                s.label: self.stats.device_iterations.get(s.label, 0)
                for s in self._slots
            }
