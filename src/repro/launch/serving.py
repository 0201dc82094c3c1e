"""Continuous online serving: the paper's sub-20 ms loop, closed.

Everything upstream of this module is batch-shaped — pre-staged device
arrays, a fixed T, offline streaming. This is the real serving driver:

    host trace-replay source (data.replay, paced at an offered rate)
        │ fixed-shape period batch (numpy)
        ▼
    HostIngestRing — double-buffered ``jax.device_put`` staging: period
        │             t+1's events upload while period t computes (the
        │             host-boundary extension of PR 3's on-device overlap)
        ▼
    donated ``dfa_step`` per period (ingest ∘ enrich ∘ inference)
        │
        ▼
    per-period wall-clock latency vs the SLO budget; p50/p99/p999
    percentiles; exact drop accounting; graceful drain on shutdown.

Latency methodology: one sample per period, measured on the host from
step dispatch to ``jax.block_until_ready`` on that period's outputs,
including the overlapped build and upload of the next period's events.
It leaves out the period's own batch: its build, its upload and its
wait in the ingest ring for the previous step to finish, which under
load can take as long as the sample itself (the ``serve/*`` spans below
time those). Percentiles use ``np.percentile`` linear
interpolation (tested against hand-computed samples in
tests/test_serving.py).

Tracing: every phase of the loop is a ``jax.profiler.TraceAnnotation``
named ``serve/<phase>`` — ``next_batch``, ``stage``, ``dispatch``,
``wait`` (the ``block_until_ready``), ``snapshot`` and ``recover`` —
carrying ``period=k``, the index of the period it serves (its place in
``ServingReport.latency_us`` and ``per_period``), so one period's spans
join across loop iterations. They cost nothing without a profiler; under
``jax.profiler.trace`` they sit on the host plane, on the device ops'
clock.

Backpressure: the source paces arrivals in virtual time (one budget per
period — deterministic; see data.replay), so offering faster than the
batch-capacity rate ``batch_events / budget`` is exactly "ingest outruns
the budget": the host queue fills, the drop policy sheds events, and the
per-period accounting stays exact (``offered == processed + dropped``
each period when ``queue_events == 0``, cumulatively after drain
otherwise). Wall-clock overruns are tracked separately as SLO
``violations`` so CPU-container jitter never perturbs the accounting.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P
from repro.data.replay import PeriodAccounting, TraceReplaySource


def _span(phase: str, period: int):
    """The profiler span of one loop phase for period ``period``."""
    return jax.profiler.TraceAnnotation(f"serve/{phase}", period=period)


def latency_summary(samples_us) -> Dict[str, float]:
    """p50/p99/p999 of per-period wall latencies (µs), linear-interp
    percentiles (``np.percentile`` default) — the bench/gate contract.

    ``count`` rides along so a consumer can tell "no samples" (count 0,
    percentiles NaN — an EXPLICIT empty summary, not a crash or a
    silent 0.0 that would read as an impossibly fast period) from a real
    distribution, and can spot a one-sample summary where all three
    percentiles collapse to the same value by construction."""
    arr = np.asarray(list(samples_us), dtype=float)
    if arr.size == 0:
        return {"p50": float("nan"), "p99": float("nan"),
                "p999": float("nan"), "count": 0}
    p50, p99, p999 = np.percentile(arr, [50.0, 99.0, 99.9])
    return {"p50": float(p50), "p99": float(p99), "p999": float(p999),
            "count": int(arr.size)}


class HostIngestRing:
    """Double-buffered host→device staging for period batches.

    Two slots, used round-robin: staging period t+1 issues its
    ``jax.device_put`` while period t's step is still in flight, and the
    slot keeps a reference so the upload's target buffers stay alive
    until the following stage overwrites the slot (t+2's stage — by
    which point t has been consumed)."""

    def __init__(self, system, events_per_shard: int):
        _, specs = system.event_specs(events_per_shard)
        mesh = system.mesh
        self._shardings = {k: NamedSharding(mesh, s)
                           for k, s in specs.items()}
        self._now_sharding = NamedSharding(mesh, P())
        self._slots: List = [None, None]
        self.staged = 0

    def stage(self, batch: Dict[str, np.ndarray], now) -> Tuple[Dict, jax.Array]:
        dev = {k: jax.device_put(np.asarray(v), self._shardings[k])
               for k, v in batch.items()}
        dnow = jax.device_put(jnp.uint32(now), self._now_sharding)
        self._slots[self.staged & 1] = (dev, dnow)
        self.staged += 1
        return dev, dnow


@dataclasses.dataclass
class ServingReport:
    """What one :meth:`ServingLoop.run` produced."""

    periods: int                      # main-loop periods
    drained_periods: int              # extra periods run by the drain
    budget_us: int                    # the SLO
    offered: int
    processed: int
    dropped: int
    violations: int                   # periods with wall latency > SLO
    latency_us: List[float]           # one sample per period (incl drain)
    per_period: List[PeriodAccounting]
    last: object = dataclasses.field(default=None, repr=False)
    snapshots: int = 0                # async DFAState checkpoints written
    # -- live in-loop recovery (its own SLO bucket, NOT in latency_us:
    # a membership change is a planned stall, not a per-period verdict
    # latency — the gate prices it separately) --------------------------
    recoveries: int = 0               # dead pods absorbed mid-serve
    recovery_stall_us: List[float] = dataclasses.field(
        default_factory=list)         # wall stall per recovery
    duplicate_recovery_skips: int = 0  # re-trips for already-removed pods
    journal_replayed: int = 0         # journal periods re-fed on recovery

    @property
    def latency(self) -> Dict[str, float]:
        return latency_summary(self.latency_us)

    @property
    def balanced(self) -> bool:
        """The exact-accounting invariant (always true after a drain)."""
        return self.offered == self.processed + self.dropped


def build_source(system, events, nows=None,
                 batch_events: Optional[int] = None) -> TraceReplaySource:
    """A replay source wired to the system's serving knobs (the same
    fields ``DFASystem.describe()`` reports)."""
    cfg = system.cfg
    return TraceReplaySource(
        events, nows,
        batch_events=batch_events or system.n_shards * cfg.event_block,
        offered_eps=cfg.serve_offered_eps,
        budget_us=cfg.serve_budget_resolved_us(),
        queue_events=cfg.serve_queue_events,
        drop_policy=cfg.drop_policy)


class ServingLoop:
    """The continuous period loop.

    Per iteration: dispatch the donated ``dfa_step`` on the staged batch
    (async), immediately pull + stage the NEXT period's batch through the
    ingest ring so host work and upload hide behind the in-flight step,
    then block on the step's outputs and take the latency sample. On
    shutdown the source stops offering arrivals and the loop keeps
    running until the host queue is empty, so every admitted event is
    either processed or accounted as dropped — never lost in flight."""

    def __init__(self, system, source: TraceReplaySource,
                 budget_us: Optional[int] = None,
                 snapshot_dir: Optional[str] = None,
                 heartbeat=None,
                 chaos: Optional[Callable[[int], Sequence[int]]] = None,
                 recovery_devices=None):
        if source.batch_events % system.n_shards:
            raise ValueError(
                f"batch_events={source.batch_events} must divide across "
                f"{system.n_shards} shards")
        self.system = system
        self.source = source
        self.budget_us = int(budget_us
                             or system.cfg.serve_budget_resolved_us())
        self.ring = HostIngestRing(
            system, source.batch_events // system.n_shards)
        self._step = system.jit_step(donate=True)
        # elastic: snapshot the full DFAState every N completed periods
        # (cfg.snapshot_every_periods; 0 disables). The save's device_get
        # happens after block_until_ready and BEFORE the next donated
        # dispatch consumes the state, so only the file IO rides the
        # background thread — the double-buffered upload never stalls.
        self.snapshot_dir = (snapshot_dir if snapshot_dir is not None
                             else (system.cfg.snapshot_dir or None))
        self.snapshot_every = int(system.cfg.snapshot_every_periods)
        # -- live recovery (ROADMAP elastic remainder) ------------------
        # journal: the last snapshot-window's period batches, host-side.
        # Depth snapshot_every+1 covers the worst replay (recovery one
        # period before the next snapshot: snapshot_every-1 completed
        # periods to re-feed) plus the already-staged pending batch.
        # ``heartbeat`` (distributed.monitor.Heartbeat with a roster)
        # trips recovery when a whole pod goes stale; ``chaos`` is the
        # test hook — ``chaos(t) -> pods to declare dead after period
        # t`` (original pod numbering, like the heartbeat roster).
        self.heartbeat = heartbeat
        self.chaos = chaos
        self.recovery_devices = recovery_devices
        self._journal: collections.deque = collections.deque(
            maxlen=max(self.snapshot_every, 1) + 1)
        # original pod id -> live flag; recovery renumbers mesh positions
        # but heartbeat/chaos speak original ids, and a second trip for a
        # removed pod must be a counted no-op, not a second rehome
        self._live_pods: List[int] = list(range(system.mesh_pods))
        self._removed_pods: set = set()
        self._dup_skips = 0

    # -- live recovery internals ------------------------------------------

    def _dead_pods(self, t: int) -> List[int]:
        """Original pod ids newly declared dead after period ``t`` (chaos
        hook + whole-pod heartbeat trips), double-recovery filtered."""
        declared: List[int] = []
        if self.chaos is not None:
            declared.extend(int(d) for d in self.chaos(t))
        if self.heartbeat is not None:
            from repro.launch import elastic as EL
            declared.extend(EL.whole_dead_pods(self.heartbeat))
        fresh = []
        for d in dict.fromkeys(declared):       # de-dup, keep order
            if d in self._removed_pods:
                self._dup_skips += 1            # idempotence, not a crash
            else:
                fresh.append(d)
        return fresh

    def _recover(self, dead_orig: int, t: int):
        """Absorb a dead pod WITHOUT leaving the serving loop: restore the
        newest snapshot, rebuild on the survivor mesh, re-home the dead
        pod's flows, then re-feed the journal window — the loop continues
        on the smaller mesh with bitwise the state an offline
        ``recover_from_snapshot`` + trace replay would have produced,
        except no external trace access is needed. Returns the recovered
        on-device state; the wall stall is the caller's SLO bucket."""
        from repro.checkpoint import checkpoint as CKPT
        from repro.launch import elastic as EL
        pos = self._live_pods.index(dead_orig)  # current mesh position
        if self.snapshot_dir is None:
            raise RuntimeError(
                "live recovery needs snapshots: construct the loop with "
                "snapshot_dir (and cfg.snapshot_every_periods > 0) so a "
                "restore point exists inside the journal window")
        new_system, state, period = EL.recover_from_snapshot(
            self.system, self.snapshot_dir, pos,
            devices=self.recovery_devices)
        if self.source.batch_events % new_system.n_shards:
            raise ValueError(
                f"batch_events={self.source.batch_events} does not "
                f"divide across the {new_system.n_shards} survivor "
                "shards")
        new_ring = HostIngestRing(
            new_system,
            self.source.batch_events // new_system.n_shards)
        new_step = new_system.jit_step(donate=True)
        replayed = 0
        for idx, b, nw in sorted(self._journal, key=lambda e: e[0]):
            if period < idx <= t:
                out = new_step(state, *new_ring.stage(b, nw))
                state = out.state
                replayed += 1
        if period + replayed != t:
            raise RuntimeError(
                f"journal window does not reach the snapshot: restored "
                f"period {period}, journal replayed {replayed} of the "
                f"{t - period} periods since — raise "
                "snapshot_every_periods/journal depth or snapshot more "
                "often")
        jax.block_until_ready(state)
        self.system = new_system
        self.ring = new_ring
        self._step = new_step
        self._live_pods.pop(pos)
        self._removed_pods.add(dead_orig)
        if self.heartbeat is not None:
            self.heartbeat.retire_pod(dead_orig)
        return state, replayed

    def run(self, periods: int, drain: bool = True,
            state=None) -> ServingReport:
        if periods < 0:
            raise ValueError("periods must be >= 0")
        if periods == 0:
            # explicit empty run: nothing offered, nothing measured —
            # the report carries the empty latency summary (count=0,
            # NaN percentiles), so callers that size their period count
            # dynamically never divide by zero
            total = self.source.total
            return ServingReport(
                periods=0, drained_periods=0, budget_us=self.budget_us,
                offered=total.offered, processed=total.processed,
                dropped=total.dropped, violations=0, latency_us=[],
                per_period=[], last=None, snapshots=0, recoveries=0,
                recovery_stall_us=[], duplicate_recovery_skips=0,
                journal_replayed=0)
        system, source = self.system, self.source
        if state is None:
            state = system.init_sharded_state()
        latencies: List[float] = []
        accounts: List[PeriodAccounting] = []
        violations = 0
        drained = 0
        out = None
        snapshots = 0
        snap_threads: List = []
        recoveries = 0
        stalls: List[float] = []
        replayed_total = 0
        dup0 = self._dup_skips
        snap_on = self.snapshot_every > 0 and self.snapshot_dir is not None
        if snap_on:
            from repro.checkpoint import checkpoint as CKPT

        with _span("next_batch", 0):
            batch, now, acct = source.next_batch()  # period 0, staged
        with _span("stage", 0):
            staged = self.ring.stage(batch, now)    # before the loop
        self._journal.append((1, batch, now))       # consumed by period 1
        t = 0
        while True:
            accounts.append(acct)
            k = t                                   # the period served
            t0 = time.perf_counter()
            with _span("dispatch", k):
                out = self._step(state, *staged)    # async dispatch
            # pull + stage period t+1 while t computes (the overlap)
            t += 1
            if t >= periods and drain:
                source.begin_drain()                # graceful shutdown
            has_next = (t < periods
                        or (drain and source.pending > 0))
            if has_next:
                with _span("next_batch", t):
                    batch, now, acct = source.next_batch()
                with _span("stage", t):
                    staged = self.ring.stage(batch, now)
                self._journal.append((t + 1, batch, now))
                if t >= periods:
                    drained += 1
            state = out.state
            with _span("wait", k):
                jax.block_until_ready(out)          # period k done
            lat_us = (time.perf_counter() - t0) * 1e6
            latencies.append(lat_us)
            if lat_us > self.budget_us:
                violations += 1
            if snap_on and (t % self.snapshot_every == 0 or not has_next):
                # out.state is fully materialized (block_until_ready just
                # returned) and the next donated dispatch hasn't happened
                # yet: save() copies to host synchronously here, then the
                # writer thread owns the IO. The final period always
                # snapshots, so a drain never strands a partial window.
                with _span("snapshot", k):
                    th = CKPT.save(state, self.snapshot_dir, step=t,
                                   keep=system.cfg.snapshot_keep,
                                   async_=True)
                if th is not None:
                    snap_threads.append(th)
                snapshots += 1
            # live recovery: a heartbeat-declared (or chaos-injected)
            # dead pod is absorbed HERE, between periods — snapshot
            # threads must land first so the restore point exists
            for dead in self._dead_pods(t):
                for th in snap_threads:
                    th.join()
                snap_threads.clear()
                stall0 = time.perf_counter()
                with _span("recover", k):
                    state, replayed = self._recover(dead, t)
                stalls.append((time.perf_counter() - stall0) * 1e6)
                recoveries += 1
                replayed_total += replayed
                system = self.system            # the survivor system
                if has_next:
                    # the pending batch was staged on the dead mesh:
                    # re-stage on the survivor ring (it is also in the
                    # journal, but replay stops at t — the pending
                    # period t+1 runs in the normal loop path)
                    with _span("stage", t):
                        staged = self.ring.stage(batch, now)
            if not has_next:
                break

        for th in snap_threads:
            th.join()
        total = source.total
        return ServingReport(
            periods=periods, drained_periods=drained,
            budget_us=self.budget_us,
            offered=total.offered, processed=total.processed,
            dropped=total.dropped, violations=violations,
            latency_us=latencies, per_period=accounts, last=out,
            snapshots=snapshots,
            recoveries=recoveries, recovery_stall_us=stalls,
            duplicate_recovery_skips=self._dup_skips - dup0,
            journal_replayed=replayed_total)


def serve_trace(system, events, nows=None, periods: int = 100,
                drain: bool = True) -> ServingReport:
    """One-call serving run: replay ``events`` through the continuous
    loop for ``periods`` periods under the system's serving knobs."""
    source = build_source(system, events, nows)
    return ServingLoop(system, source).run(periods, drain=drain)
