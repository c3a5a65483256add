"""TwinServer: the online serving loop — ingest, refit, deploy, guard.

One `tick()` is a full serving cycle over the whole tracked fleet:

    1. FLUSH    staged telemetry into the device ring buffers (one fused
                scatter for every twin that produced samples this tick).
                With `async_ingest` the host-side merge/pad work runs on a
                background `BackgroundPump` thread (double-buffered handoff,
                data/pipeline.py); the tick only applies prepared batches,
    2. GUARD    RK4-roll deployed thetas over their newest window and
                EMA-fold the normalized rollout error into each twin's
                divergence score; emit REFIT/ALERT events on transitions.
                With `guard_budget` set, a `GuardRotation` scores a fixed-size
                rotating subset per tick (round-robin + divergence carry-over)
                so guard cost is O(budget), not O(twins),
    3. SCHEDULE admit/evict/release twins over the bounded refit-slot pool
                by staleness + divergence priority (twin/scheduler.py).
                `PackedRefitScheduler` scores the WHOLE fleet in one fused
                device call over the packed columns (twin/packed.py) and
                pops only the O(slots) winners on the host; a federation
                layer (twin/sharded.py) can cap the active pool via
                `set_active_slots`,
    4. REFIT    `steps_per_tick` fused FleetMerinda.train_step calls over all
                slots at once (the bounded compute budget),
    5. DEPLOY   recover_all on slots whose twin has trained past
                `deploy_after`, scattered into the serving theta store.

Each twin's serving state lives in one place, the server's `PackedFleet`
columns; `server.twins` is a read-only view that builds a `TwinRecord` from a
row when it is read.

Every fused call has a FIXED shape (refit_slots / max_twins / guard budget),
so steady-state serving compiles exactly once; unassigned refit slots are
parked on a scratch ring row (`max_twins`) and unused recoveries land on a
scratch theta row.  Shards of a `ShardedTwinServer` with identical configs
share the stateless module objects (`share_modules_from`), so the jit cache
is hit once per topology, not once per shard.

Per-tick wall latency is recorded against `deadline_s`, and each stage's cost
is tracked separately (`stage_summary`) — the scale benchmark's evidence that
guard cost stays flat as the tracked fleet grows.  All serving stats flow
through a bounded `repro.obs` metrics registry (scrape via
`server.metrics.expose()`; catalog in docs/OBSERVABILITY.md), and an optional
`Tracer` wraps every stage and its parts in spans exportable as a
Perfetto-loadable trace.  Every place where the serving thread waits on the
device (a read-back or the tick's final block) sits in a `sync` span whose
`site` names it, apart from the dispatch before it.
The paper's mission budget: beat the 5 s human-pilot reaction time 5x —
refresh every deployed twin in <= 1 s.

`predict(twin_id, horizon)` rolls the deployed model forward from the
twin's newest telemetry — the collision-avoidance lookahead.
"""
from __future__ import annotations

import time
from collections.abc import Mapping
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fleet import FleetConfig, FleetMerinda
from repro.core.merinda import MerindaConfig
from repro.data.pipeline import BackgroundPump
from repro.kernels.rk4.ops import rk4_poly_solve
from repro.obs import MetricRegistry, Tracer
from repro.twin.monitor import (DivergenceGuard, GuardConfig, GuardEvent,
                                GuardInstruments, GuardRotation)
from repro.twin.packed import GUARD_KINDS, PackedFleet
from repro.twin.recovery import (DegradationConfig, DegradationEvent,
                                 DegradationPolicy)
from repro.twin.scenario import (ScenarioConfig, ScenarioRefused,
                                 ScenarioResult, ScenarioRunner, effective_k)
from repro.twin.service import DeadlineConfig
from repro.twin.scheduler import (PackedRefitScheduler, SchedulerConfig,
                                  SchedulePlan, SchedulerMetrics, TwinRecord)
from repro.twin.stream import (FlushBatch, RingConfig, StagingBuffer,
                               StagingOverflow, TelemetryRing, prepare_flush)

__all__ = ["TwinServerConfig", "TickReport", "TwinServer"]

_STAGES = ("flush", "guard", "schedule", "refit")


@partial(jax.jit, static_argnames=("ring", "fleet", "window", "stride",
                                   "length"))
def _admit(ring, fleet, rstate, fstate, admit, key, *, window, stride, length):
    """One tick's admissions as one device program: gather the admitted
    twins' windows (row 1 of `admit`: ring rows) and reset their slots
    (row 0: slots in plan order, -1 past the last) with sequential key
    splits.  Built from the class-level functions, so a wrapper set on an
    instance's `windows` or `reset_slots` is not traced into it."""
    y_win, u_win = TelemetryRing.windows(ring, rstate, admit[1],
                                         window=window, stride=stride,
                                         length=length)
    return FleetMerinda.reset_slots(fleet, fstate, admit[0], key, y_win,
                                    u_win)


@partial(jax.jit, static_argnames=("ring",))
def _scenario_gather(ring, rstate, theta_hist, row):
    """A what-if query's device inputs as one program: the twin's recent
    thetas [E, n, L] and its newest observed state y0 [n].  `row` (the ring
    row) is a traced scalar, so one compile serves every twin; the stores
    come in as arguments, since ingest, deploys and promotion rebind them."""
    ys, _ = TelemetryRing.latest(ring, rstate, row[None], 0)
    return theta_hist[row], ys[0, -1]


@dataclass(frozen=True)
class TwinServerConfig(DeadlineConfig):
    """Single-server knobs; `deadline_s` (1.0 s default — 5x under the 5 s
    human-reaction budget) comes from the shared `DeadlineConfig` base
    (twin/service.py) so every server config agrees on its meaning."""
    merinda: MerindaConfig
    max_twins: int                    # tracked-object capacity
    refit_slots: int = 8              # concurrent refits (compute budget)
    capacity: int = 512               # ring samples per twin
    window: int = 24                  # refit window k
    stride: int = 8
    windows_per_twin: int = 16        # S_B per slot per train step
    steps_per_tick: int = 2           # incremental train steps per tick
    lr: float = 3e-3
    sparsify_after: int = 60          # per-slot warmup (FleetConfig)
    deploy_after: int = 24            # train steps before a slot's theta ships
    promote_margin: float = 0.7       # candidate must score < margin * incumbent
    guard: GuardConfig = GuardConfig()
    guard_budget: int | None = None   # None: score the whole store per tick;
                                      # int: rotating subset of this size
    guard_carry: int | None = None    # extra per-tick re-scores of flagged
                                      # twins (default: guard_budget // 4)
    async_ingest: bool = False        # background staging flush thread
    ingest_depth: int = 2             # prepared-batch queue depth (double buf)
    staleness_weight: float = 1.0
    divergence_weight: float = 4.0
    evict_margin: float = 0.5
    min_residency: int = 8
    max_residency: int = 64
    release_divergence: float = 0.05
    flush_pad: int = 8                # chunk-length quantum (bounds retraces)
    degradation: DegradationConfig = DegradationConfig()
                                      # deadline-aware shed ladder
                                      # (twin/recovery.py; disabled default)
    scenario: ScenarioConfig = ScenarioConfig()
                                      # what-if engine knobs
                                      # (twin/scenario.py)
    staging_capacity: int | None = None
                                      # staging-buffer sample bound (None:
                                      # unbounded — the seed behaviour)
    ingest_strict: bool = True        # overflow after retries: raise (True)
                                      # or shed oldest staged samples
    ingest_retries: int = 3           # bounded backoff attempts on overflow
    ingest_backoff_s: float = 2e-3    # first retry sleep (doubles per try)
    seed: int = 0


@dataclass
class TickReport:
    tick: int
    latency_s: float
    deadline_met: bool
    loss: float | None                # mean refit loss (None: no active slot)
    events: list[GuardEvent] = field(default_factory=list)
    admitted: list = field(default_factory=list)   # [(slot, twin_id)]
    evicted: list = field(default_factory=list)
    released: list = field(default_factory=list)
    n_active: int = 0                 # twins resident in refit slots
    n_twins: int = 0                  # twins tracked
    n_guarded: int = 0                # twins scored by the guard this tick
    degraded_level: int = 0           # shed ladder after this tick (0 = full)
    degradation_events: list = field(default_factory=list)
                                      # DegradationEvent transitions this tick


class TwinServer:
    def __init__(self, cfg: TwinServerConfig, *,
                 share_modules_from: "TwinServer | None" = None,
                 seed: int | None = None,
                 metrics: MetricRegistry | None = None,
                 tracer: Tracer | None = None,
                 shard: int | str | None = None):
        """`metrics`/`tracer` attach shared observability (a sharded server
        passes one registry + tracer to every shard with a distinct `shard`
        label); standalone servers get a private registry and a disabled
        tracer, so instrumentation is always live and always bounded."""
        m = cfg.merinda
        self.cfg = cfg
        self.metrics = MetricRegistry() if metrics is None else metrics
        self.tracer = Tracer(enabled=False) if tracer is None else tracer
        self._labels = {} if shard is None else {"shard": str(shard)}
        self.span = TelemetryRing.span(cfg.window, cfg.stride,
                                       cfg.windows_per_twin)
        self.min_samples = self.span + 1
        if cfg.capacity < max(self.min_samples, cfg.guard.window + 1):
            raise ValueError("ring capacity smaller than the refit/guard span")

        self._scratch = cfg.max_twins     # scratch ring row + theta row
        src = share_modules_from
        if src is not None:
            if src.cfg.merinda != m or src.cfg.max_twins != cfg.max_twins \
                    or src.cfg.refit_slots != cfg.refit_slots \
                    or src.cfg.capacity != cfg.capacity \
                    or src.cfg.windows_per_twin != cfg.windows_per_twin \
                    or src.cfg.lr != cfg.lr \
                    or src.cfg.sparsify_after != cfg.sparsify_after \
                    or src.cfg.guard != cfg.guard \
                    or src.cfg.scenario != cfg.scenario:
                raise ValueError("share_modules_from requires identical "
                                 "fused-call shapes and guard/scenario "
                                 "config (merinda/ring/fleet cfg)")
            # ring / fleet / guard / scenario runner are stateless (state
            # passed explicitly); sharing the instances shares their jit
            # caches across shards
            self.ring, self.fleet, self.guard = src.ring, src.fleet, src.guard
            self.scenario_runner = src.scenario_runner
        else:
            self.ring = TelemetryRing(RingConfig(
                slots=cfg.max_twins + 1, capacity=cfg.capacity, n=m.n, m=m.m))
            self.fleet = FleetMerinda(FleetConfig(
                merinda=m, fleet=cfg.refit_slots,
                windows_per_twin=cfg.windows_per_twin, lr=cfg.lr,
                sparsify_after=cfg.sparsify_after))
            self.guard = DivergenceGuard(self.fleet.model.lib, m.dt,
                                         cfg.guard, use_pallas=m.use_pallas,
                                         interpret=m.interpret)
            self.scenario_runner = ScenarioRunner(
                self.fleet.model.lib, m.dt, cfg.scenario,
                use_pallas=m.use_pallas, interpret=m.interpret,
                span=self.tracer.span)
        self._rstate = self.ring.init()
        self._key, key = jax.random.split(
            jax.random.PRNGKey(cfg.seed if seed is None else seed))
        self._fstate = self.fleet.init(key)

        sched_cfg = SchedulerConfig(
            slots=cfg.refit_slots, min_samples=self.min_samples,
            staleness_weight=cfg.staleness_weight,
            divergence_weight=cfg.divergence_weight,
            evict_margin=cfg.evict_margin, min_residency=cfg.min_residency,
            max_residency=cfg.max_residency,
            release_divergence=cfg.release_divergence)
        self.scheduler = PackedRefitScheduler(
            sched_cfg, metrics=SchedulerMetrics.create(self.metrics,
                                                       self._labels),
            span=self.tracer.span)
        # every per-twin fact, by ring row (twin/packed.py); `twins` is a
        # read-only view of it
        self.packed = PackedFleet(cfg.max_twins)
        self.twins = _TwinView(self.packed)
        self._max_active: int | None = None   # federation cap (None: all)

        self._rotation = (None if cfg.guard_budget is None else
                          GuardRotation(cfg.guard_budget,
                                        cfg.guard_budget // 4
                                        if cfg.guard_carry is None
                                        else cfg.guard_carry))

        # guard-eligible rows (deployed + enough samples): the packed
        # `guard_live` column, set at flush/deploy time; _live_rows caches
        # its sorted row array, rebuilt only when membership changes
        self._guard_min = cfg.guard.window + 1
        self._live_rows = np.empty((0,), np.int64)
        self._live_dirty = False
        self._slot_ring = np.full((cfg.refit_slots,), self._scratch,
                                  dtype=np.int32)     # refit slot -> ring row
        L = self.fleet.model.lib.size
        self._theta = jnp.zeros((cfg.max_twins + 1, m.n, L))
        # per-twin ring of recently served thetas (scenario confidence
        # ensemble); _hist_count tracks fills so unfilled slots fall back
        # to the live model inside the fused rollout
        self._theta_hist = jnp.zeros(
            (cfg.max_twins + 1, cfg.scenario.ensemble, m.n, L))
        self._hist_count = np.zeros((cfg.max_twins + 1,), np.int64)
        self._staging = StagingBuffer(capacity=cfg.staging_capacity)
        self._degradation = DegradationPolicy(cfg.degradation, cfg.deadline_s)
        self._pump = (BackgroundPump(self._prepare_timed,
                                     depth=cfg.ingest_depth)
                      if cfg.async_ingest else None)
        self.tick_count = 0
        self.inject_delay_s = 0.0     # chaos straggler (twin/recovery.py):
                                      # slept INSIDE the timed tick region so
                                      # the degradation policy sees the stall
        self.events: list[GuardEvent] = []
        self._init_instruments()

    def _init_instruments(self) -> None:
        """Resolve this server's metric children (per-shard labels)."""
        M, lab = self.metrics, self._labels
        self._m_tick = M.histogram(
            "twin_tick_latency_seconds",
            help="full serving-tick wall latency", unit="seconds",
            labels=lab)
        self._m_stage = {
            s: M.histogram("twin_stage_latency_seconds",
                           help="per-stage serving-tick wall latency",
                           unit="seconds", labels={**lab, "stage": s})
            for s in _STAGES}
        self._m_violations = M.counter(
            "twin_deadline_violations_total",
            help="ticks whose wall latency exceeded deadline_s", labels=lab)
        self._m_refreshes = M.counter(
            "twin_slot_refreshes_total",
            help="refit-slot train advances (active slots summed per tick)",
            labels=lab)
        self._m_admissions = M.counter(
            "twin_slot_admissions_total",
            help="twins admitted into refit slots (slots reset)",
            labels=lab)
        self._m_admit_calls = M.counter(
            "twin_admit_calls_total",
            help="fused admission launches (one per admitting tick)",
            labels=lab)
        self._m_dropped = M.counter(
            "twin_dropped_samples_total",
            help="telemetry samples truncated by flush backlog (ring would "
                 "have overwritten them)", labels=lab)
        self._m_overflow = M.counter(
            "twin_flush_overflows_total",
            help="flush batches that truncated a backlog", labels=lab)
        self._m_prepare = M.histogram(
            "twin_flush_prepare_seconds",
            help="host-side staging merge/pad latency (pump thread when "
                 "async)", unit="seconds", labels=lab)
        self._m_tracked = M.gauge(
            "twin_tracked_twins", help="registered tracked objects",
            labels=lab)
        self._m_deployed = M.gauge(
            "twin_deployed_twins", help="twins with a serving theta",
            labels=lab)
        self._m_active = M.gauge(
            "twin_active_slots", help="refit slots currently assigned",
            labels=lab)
        self._m_staging = M.gauge(
            "twin_staging_pending_samples",
            help="samples staged but not yet flushed", labels=lab)
        self._m_queue = M.gauge(
            "twin_pump_queue_depth",
            help="prepared flush batches awaiting the serving tick",
            labels=lab)
        self._m_degraded = M.gauge(
            "twin_degraded_level",
            help="deadline-degradation ladder level (0 = full service)",
            labels=lab)
        self._m_deg_trans = {
            d: M.counter("twin_degraded_transitions_total",
                         help="degradation ladder moves by direction",
                         labels={**lab, "direction": d})
            for d in ("up", "down")}
        self._m_shed = {
            a: M.counter("twin_degraded_shed_total",
                         help="ticks that shed a stage under degradation",
                         labels={**lab, "action": a})
            for a in ("guard", "refit", "promote")}
        self._m_ingest_retries = M.counter(
            "twin_ingest_retries_total",
            help="ingest backoff retries after a staging overflow",
            labels=lab)
        self._m_ingest_dropped = M.counter(
            "twin_ingest_dropped_total",
            help="staged samples shed (drop-oldest) by non-strict ingest "
                 "backpressure", labels=lab)
        self._guard_obs = GuardInstruments.create(M, lab)
        self._m_scn_latency = M.histogram(
            "twin_scenario_latency_seconds",
            help="what-if query wall latency (ensemble x K fused rollout)",
            unit="seconds", labels=lab)
        self._m_scn_requests = M.counter(
            "twin_scenario_requests_total",
            help="scenario queries answered", labels=lab)
        self._m_scn_rollouts = M.counter(
            "twin_scenario_rollouts_total",
            help="individual trajectories integrated for scenario queries "
                 "(effective K x ensemble)", labels=lab)
        self._m_scn_shrunk = M.counter(
            "twin_scenario_shrunk_total",
            help="scenario queries served with K shrunk by the degradation "
                 "ladder", labels=lab)
        self._m_scn_refused = M.counter(
            "twin_scenario_refused_total",
            help="scenario queries refused under deadline pressure",
            labels=lab)
        self._m_scn_confidence = M.histogram(
            "twin_scenario_confidence",
            help="per-scenario ensemble confidence (1 = recent thetas "
                 "agree)", bounds=(0.1, 0.25, 0.5, 0.75, 0.9, 0.99, 1.0),
            labels=lab)

    # ------------------------------------------------------------------ #
    def register(self, twin_id: int) -> int:
        """Start tracking an object; returns its telemetry ring row."""
        return self.packed.register(twin_id)

    def twin_snapshot(self) -> dict[int, TwinRecord]:
        """Registry copy safe to iterate while ingest threads register."""
        return dict(self.twins)

    def _guard_admit(self, rows: np.ndarray) -> None:
        """Admit the deployed `rows` with a full guard window of samples to
        the guard-eligible set (idempotent)."""
        p = self.packed
        new = rows[~p.guard_live[rows] & p.deployed[rows]
                   & (p.samples[rows] >= self._guard_min)]
        if new.size:
            p.guard_live[new] = True
            self._live_dirty = True

    # ------------------------------------------------------------------ #
    def ingest(self, twin_id: int, y, u=None, *, force: bool = False):
        """Stage telemetry for `twin_id`: y [n] or [C, n], u [m] or [C, m].

        Host-side staging only — the device scatter happens once per tick in
        the fused flush, so per-sample ingest stays cheap.  Thread-safe:
        with `async_ingest` many sensor threads may call this concurrently
        with `tick()` (the staging buffer is the synchronized handoff).

        Backpressure (bounded staging, `cfg.staging_capacity`): an overflow
        retries up to `ingest_retries` times with doubling backoff (kicking
        the pump each try so a stalled flush can clear); if still full,
        strict mode re-raises `StagingOverflow` to the producer, non-strict
        mode sheds the OLDEST staged samples (counted in
        `twin_ingest_dropped_total`) and stages the new chunk — fresh
        telemetry outranks stale backlog for a guard that scores NEWEST
        windows.  `force=True` bypasses the bound entirely (crash-recovery
        replay, twin/recovery.py).
        """
        row = self.register(twin_id)
        y = np.atleast_2d(np.asarray(y, np.float32))
        C = y.shape[0]
        m = self.cfg.merinda.m
        u = (np.zeros((C, m), np.float32) if u is None
             else np.asarray(u, np.float32).reshape(C, m))
        if C > self.cfg.capacity:
            raise ValueError("chunk larger than ring capacity")
        try:
            self._staging.append(row, y, u, force=force)
        except StagingOverflow:
            self._ingest_backpressure(row, y, u)
        if self._pump is not None:
            self._pump.kick()

    def ingest_many(self, batch, *, force: bool = False) -> int:
        """Batched `ingest`: `batch` iterates (twin_id, y) or (twin_id, y, u)
        chunks — one call per producer flush instead of one per sample, the
        shape the network front door (twin/wire.py IngestBatch) arrives in.
        Returns the number of SAMPLES staged.  Same thread-safety and
        backpressure contract as `ingest`."""
        staged = 0
        for chunk in batch:
            tid, y = chunk[0], chunk[1]
            u = chunk[2] if len(chunk) > 2 else None
            self.ingest(tid, y, u, force=force)
            staged += np.atleast_2d(np.asarray(y)).shape[0]
        return staged

    def _ingest_backpressure(self, row: int, y, u) -> None:
        """Bounded retry-with-backoff, then strict-raise or drop-oldest."""
        delay = self.cfg.ingest_backoff_s
        for _ in range(max(0, self.cfg.ingest_retries)):
            self._m_ingest_retries.inc()
            if self._pump is not None:
                self._pump.kick()      # give the flusher a chance to drain
            time.sleep(delay)
            delay *= 2
            try:
                self._staging.append(row, y, u)
                return
            except StagingOverflow:
                continue
        if self.cfg.ingest_strict:
            raise StagingOverflow(
                f"staging buffer still full after "
                f"{self.cfg.ingest_retries} retries "
                f"(capacity {self.cfg.staging_capacity} samples)")
        dropped = self._staging.drop_oldest(len(y))
        self._m_ingest_dropped.inc(dropped)
        self._staging.append(row, y, u, force=True)

    # -- staging flush: prepare (host, possibly background) + apply ----- #
    def _prepare(self) -> FlushBatch | None:
        m = self.cfg.merinda
        return prepare_flush(self._staging.swap(),
                             capacity=self.cfg.capacity,
                             pad=self.cfg.flush_pad, scratch=self._scratch,
                             n=m.n, m=m.m)

    def _prepare_timed(self) -> FlushBatch | None:
        """`_prepare` under a span + latency histogram — with async ingest
        this runs on the pump thread, so the span lands on the pump's own
        Perfetto track and the histogram shows how much host merge/pad work
        the tick was spared."""
        with self.tracer.span("pump_flush", cat="ingest", **self._labels):
            t0 = time.perf_counter()
            batch = self._prepare()
            self._m_prepare.observe(time.perf_counter() - t0)
        return batch

    @property
    def dropped_samples(self) -> int:
        """Backlog samples truncated by the flush (loud; counter-backed)."""
        return int(self._m_dropped.value)

    def _apply(self, batch: FlushBatch) -> int:
        """Host accounting, then the ring scatter, under an `apply` span."""
        with self.tracer.span("apply", cat="ingest", **self._labels):
            if batch.dropped:
                self._m_dropped.inc(batch.dropped)
                self._m_overflow.inc()
            rows = np.fromiter(batch.received, np.int64,
                               count=len(batch.received))
            raw = np.fromiter(batch.received.values(), np.int32,
                              count=len(rows))
            self.packed.samples[rows] += raw
            self._guard_admit(rows)
            self._rstate = self.ring.ingest(
                self._rstate, jnp.asarray(batch.slots), jnp.asarray(batch.ys),
                jnp.asarray(batch.us), jnp.asarray(batch.counts))
            return sum(batch.received.values())

    def _flush(self) -> int:
        if self._pump is not None:
            return sum(self._apply(b) for b in self._pump.drain())
        batch = self._prepare_timed()
        return self._apply(batch) if batch is not None else 0

    def drain(self) -> None:
        """Barrier: every sample ingested before this call reaches the ring.

        With async ingest, waits for the pump to go idle, applies every
        prepared batch, then flushes anything still staged inline.  Must be
        called from the serving (tick) thread — device state is
        single-threaded by design.

        Guarantee: on return, all samples whose `ingest()` call returned
        BEFORE `drain()` started are visible to the next fused gather.
        Samples ingested concurrently with the drain may or may not be
        included (they are never lost — at worst they wait for the next
        flush).  Busy-waits in 0.1 ms sleeps while the pump finishes its
        in-flight batch; does not block producers.
        """
        if self._pump is not None:
            while not self._pump.idle():
                for b in self._pump.drain():
                    self._apply(b)
                time.sleep(1e-4)
            for b in self._pump.drain():
                self._apply(b)
        batch = self._prepare_timed()
        if batch is not None:
            self._apply(batch)

    def close(self) -> None:
        """Stop the async flush worker (no-op for synchronous servers)."""
        if self._pump is not None:
            self._pump.close()

    # ------------------------------------------------------------------ #
    def set_active_slots(self, n: int | None) -> None:
        """Cap the refit slots the scheduler may fill (federation rebalance;
        twin/sharded.py).  None restores the full physical pool."""
        self._max_active = n

    @property
    def active_slot_cap(self) -> int:
        return (self.cfg.refit_slots if self._max_active is None
                else max(0, min(self.cfg.refit_slots, self._max_active)))

    def refit_pressure(self) -> float:
        """Aggregate staleness+divergence refit demand — the federation's
        rebalance signal, as one fused device reduction over the packed
        columns."""
        return self.scheduler.pressure(self.packed)

    # ------------------------------------------------------------------ #
    def _hist_push(self, rows: np.ndarray, thetas) -> None:
        """Append served thetas to the per-twin history rings (one scatter).

        rows [B] ring rows, thetas [B, n, L].  Every deploy/promote lands
        here so the scenario ensemble always holds the `ensemble` most
        recently SERVED models per twin — a cheap, always-fresh proxy for
        model uncertainty (thrashing refits -> wide envelope).
        """
        pos = (self._hist_count[rows] % self.cfg.scenario.ensemble)
        self._theta_hist = self._theta_hist.at[
            jnp.asarray(rows), jnp.asarray(pos.astype(np.int32))].set(thetas)
        self._hist_count[rows] += 1

    def deploy(self, twin_id: int, theta) -> None:
        """Install a theta for `twin_id` directly (warm start from an offline
        recovery — lets a fleet come up serving while online refits rotate)."""
        row = self.register(twin_id)
        theta = jnp.asarray(theta)
        self._theta = self._theta.at[row].set(theta)
        rows = np.asarray([row], np.int64)
        self._hist_push(rows, theta[None])
        self._mark_deployed(rows)

    def deploy_many(self, twin_ids, thetas) -> None:
        """Warm-start a whole fleet in one scatter: thetas [B, n, L] (or a
        single [n, L] broadcast to every twin).  The 10k-twin startup path —
        per-twin `deploy` would issue 10k device ops.

        Registers unknown twin_ids, marks every target deployed, and admits
        twins with >= guard.window+1 ring samples to the guard-eligible set.
        Serving-thread only (mutates the device theta store); not safe to
        call concurrently with `tick()`.
        """
        rows = np.asarray([self.register(t) for t in twin_ids], np.int32)
        thetas = jnp.asarray(thetas)
        if thetas.ndim == 2:
            thetas = jnp.broadcast_to(thetas, (len(rows),) + thetas.shape)
        self._theta = self._theta.at[jnp.asarray(rows)].set(thetas)
        rows = rows.astype(np.int64)
        self._hist_push(rows, thetas)
        self._mark_deployed(rows)

    def _mark_deployed(self, rows: np.ndarray) -> None:
        """`rows` now serve a theta: reset their staleness watermark, stamp
        the tick, and admit the ready ones to the guard."""
        p = self.packed
        p.deployed[rows] = True
        p.samples_at_deploy[rows] = p.samples[rows]
        p.deploy_tick[rows] = self.tick_count
        self._guard_admit(rows)

    # ------------------------------------------------------------------ #
    def _update_divergence(self, shed: bool = False
                           ) -> tuple[list[GuardEvent], int]:
        """Score guard-eligible rows, fold the scores into the divergence
        column, and report transitions — in ring-row order for the full
        scan, in pick order for the rotation."""
        gw = self.cfg.guard.window
        p = self.packed
        if self._live_dirty:
            self._live_rows = np.flatnonzero(p.guard_live)
            self._live_dirty = False
        live = self._live_rows
        if not live.size:
            return [], 0
        if self._rotation is None:
            # full scan: one fused call over the whole store (O(twins)).
            # Degraded: the scan has ONE fused shape, so shedding means
            # scoring every other tick — half the device work, freshness
            # halves instead of breaking.
            if shed and self.tick_count % 2 == 0:
                return [], 0
            rows = jnp.arange(self.cfg.max_twins)
            ys, us = self.ring.latest(self._rstate, rows, gw)
            scores = self.guard.score(self._theta[:-1], ys, us)
            with self.tracer.span("sync", site="guard.scores"):
                scores = np.asarray(scores)
            srows = live
            raw = scores[srows]
        else:
            # budgeted rotation: fixed-size fused call (O(budget)).
            # Degraded: a SMALLER fixed width (budget // guard_shrink, no
            # carry) — one extra compile the first time the ladder engages,
            # then a genuinely cheaper rollout until pressure clears.
            if shed:
                width = max(1, self._rotation.budget
                            // max(1, self.cfg.degradation.guard_shrink))
                pick = self._rotation.select(live, p.divergence,
                                             self.cfg.guard.refit_threshold,
                                             budget=width, carry=0)
            else:
                width = self._rotation.size
                pick = self._rotation.select(live, p.divergence,
                                             self.cfg.guard.refit_threshold)
            rows_np = np.full((width,), self._scratch, np.int32)
            rows_np[:len(pick)] = pick
            rows = jnp.asarray(rows_np)
            ys, us = self.ring.latest(self._rstate, rows, gw)
            scores = self.guard.score(self._theta[rows], ys, us)
            with self.tracer.span("sync", site="guard.scores"):
                scores = np.asarray(scores)
            srows = np.asarray(pick, np.int64)
            raw = scores[:len(srows)]
        # one vectorized EMA fold publishes the smoothed scores into the
        # packed divergence column the scheduler scores
        smoothed = self.guard.fold_into(p.divergence, srows, raw)
        p.div32[srows] = smoothed             # float32 shadow for the kernel
        events: list[GuardEvent] = []
        score_hist = self._guard_obs.score
        for row, tid, score, div in zip(srows.tolist(),
                                        p.twin_id[srows].tolist(), raw,
                                        smoothed.tolist()):
            score_hist.observe(float(score))
            ev = self.guard.judge(tid, div, self.tick_count)
            code = GUARD_KINDS.index(ev.kind) if ev else 0
            if code != p.guard_code[row]:
                p.guard_code[row] = code
                if ev:
                    events.append(ev)
                    self._guard_obs.events[ev.kind].inc()
        self.events.extend(events)
        self._guard_obs.scored.inc(len(srows))
        return events, len(srows)

    # ------------------------------------------------------------------ #
    def _slot_windows(self):
        rows = jnp.asarray(self._slot_ring)
        return self.ring.windows(self._rstate, rows, window=self.cfg.window,
                                 stride=self.cfg.stride, length=self.span)

    def _occupied_slots(self) -> np.ndarray:
        """Refit slots holding a twin, ascending."""
        return np.flatnonzero(self._slot_ring != self._scratch)

    def _apply_plan(self, plan: SchedulePlan) -> None:
        p = self.packed
        gone = [p.row_of[tid] for tid in plan.evict + plan.release]
        if gone:
            self._slot_ring[p.refit_slot[gone]] = self._scratch
            p.refit_slot[gone] = -1
            p.resident[gone] = False
            p.residency[gone] = p.steps_in_slot[gone] = 0
        if not plan.admit:
            return
        n = len(plan.admit)
        admit = np.full((2, self.cfg.refit_slots), -1, np.int32)
        admit[1] = self._scratch
        admit[:, :n] = [[slot for slot, _ in plan.admit],
                        [p.row_of[tid] for _, tid in plan.admit]]
        slots, rows = admit[:, :n]
        p.refit_slot[rows] = slots
        p.resident[rows] = True
        p.admitted_tick[rows] = self.tick_count
        p.residency[rows] = p.steps_in_slot[rows] = 0
        self._slot_ring[slots] = rows
        self._fstate, self._key = _admit(
            self.ring, self.fleet, self._rstate, self._fstate, admit,
            self._key, window=self.cfg.window, stride=self.cfg.stride,
            length=self.span)
        self._m_admissions.inc(len(plan.admit))
        self._m_admit_calls.inc()

    def _refit(self, defer: bool = False, skip_promote: bool = False
               ) -> float | None:
        p = self.packed
        slots = self._occupied_slots()
        if not slots.size:
            return None
        rows = self._slot_ring[slots]
        if defer:
            # degraded (level >= 2): slots hold — no train steps, residency
            # frozen.  Candidates that already converged may still ship
            # (level < 3): promotion is one shadow-eval rollout, far cheaper
            # than steps_per_tick train steps, and a finished model serving
            # beats a finished model waiting out an overload.
            if not skip_promote:
                deployable = slots[p.steps_in_slot[rows]
                                   >= self.cfg.deploy_after]
                if deployable.size:
                    y_win, u_win = self._slot_windows()
                    with self.tracer.span("promote"):
                        self._promote(deployable, y_win, u_win)
            return None
        y_win, u_win = self._slot_windows()
        with self.tracer.span("train"):
            loss_vec = None
            for _ in range(self.cfg.steps_per_tick):
                self._fstate, loss_vec, _ = self.fleet.train_step_per_slot(
                    self._fstate, y_win, u_win)
            p.steps_in_slot[rows] += self.cfg.steps_per_tick
            p.residency[rows] += 1
            deployable = slots[p.steps_in_slot[rows] >= self.cfg.deploy_after]
            promote = deployable.size > 0 and not skip_promote
            if not promote:
                with self.tracer.span("sync", site="refit.loss"):
                    loss_vec = np.asarray(loss_vec)
        if promote:
            # the loss steers nothing before promotion's own read: read both
            # at once, after promotion's programs are dispatched
            with self.tracer.span("promote"):
                loss_vec = self._promote(deployable, y_win, u_win, loss_vec)
        # report loss over ASSIGNED slots only — scratch-parked slots
        # train on zero windows and would dilute the mean toward zero
        return float(np.mean(loss_vec[slots]))

    def _promote(self, deployable, y_win, u_win, loss_vec=None):
        """Shadow-evaluate slot recoveries and deploy only improvements.

        Both the candidate theta and the incumbent are rolled over the same
        newest telemetry (one fused guard call each).  Against a HEALTHY
        incumbent (score < refit_threshold) the candidate must beat it by
        `promote_margin` — "good enough" is not enough to replace a model
        that tracks reality better.  Against a missing/diverged incumbent the
        candidate ships if it is outright good or a margin improvement.

        `loss_vec`, the train steps' per-slot loss still on the device (None
        on the degraded path, which trains nothing), is read back together
        with both scores in one `sync` and returned as numpy.
        """
        thresh = self.cfg.guard.refit_threshold
        rows = jnp.asarray(self._slot_ring)
        thetas = self.fleet.recover_all(self._fstate, y_win, u_win)
        ys_g, us_g = self.ring.latest(self._rstate, rows,
                                      self.cfg.guard.window)
        cand = self.guard.score(thetas, ys_g, us_g)
        inc = self.guard.score(self._theta[rows], ys_g, us_g)
        with self.tracer.span("sync", site="refit.result"):
            loss_vec, cand, inc = jax.device_get((loss_vec, cand, inc))
        p = self.packed
        drows = self._slot_ring[deployable]
        cand_d, inc_d = cand[deployable], inc[deployable]
        healthy_inc = p.deployed[drows] & (inc_d < thresh)
        better = cand_d < self.cfg.promote_margin * inc_d
        won = better | (~healthy_inc & (cand_d < thresh))
        # candidate lost, but the serving model is still healthy: count
        # this as a completed review so the twin's staleness resets and it
        # stops hogging a refit slot.
        reviewed = drows[~won & healthy_inc]
        p.samples_at_deploy[reviewed] = p.samples[reviewed]
        if won.any():
            slots, prows = deployable[won], drows[won].astype(np.int64)
            targets = np.full((self.cfg.refit_slots,), self._scratch,
                              dtype=np.int32)
            targets[slots] = prows
            self._theta = self._theta.at[jnp.asarray(targets)].set(thetas)
            self._hist_push(prows, thetas[jnp.asarray(slots)])
            p.set_divergence(prows, np.minimum(cand_d[won], 1e6))
            self._mark_deployed(prows)
        return loss_vec

    # ------------------------------------------------------------------ #
    def tick(self) -> TickReport:
        """One full serving cycle; see module docstring for the five stages.

        Units: `TickReport.latency_s` and `cfg.deadline_s` are SECONDS
        (`latency_summary`/`stage_summary` report milliseconds); the default
        deadline of 1.0 s is the paper's mission budget — 5x under the 5 s
        human-pilot reaction time.  `deadline_met` compares this tick's wall
        latency against `cfg.deadline_s`.

        Threading: must be called from the single serving thread (device
        state — ring, fleet, theta store — is single-threaded by design).
        `ingest()` MAY run concurrently on sensor threads; the staging
        buffer's lock and the packed fleet's registration lock are the only
        synchronization points between them.

        Fused-call costs per tick: flush is one scatter over the reporting
        twins (pow2-bucketed shapes), guard is O(guard_budget + carry)
        device work and O(budget) host work (`GuardRotation`), refit is
        `steps_per_tick` fixed-shape train steps over `refit_slots` slots.
        """
        span = self.tracer.span
        # degradation ladder: consult the level set by the PREVIOUS tick's
        # observe() — shedding decisions are made before the work they shed
        deg = self._degradation
        shed_guard, defer_refit = deg.shed_guard, deg.defer_refit
        skip_promote = deg.skip_promote
        with span("tick", tick=self.tick_count + 1, **self._labels):
            t0 = time.perf_counter()
            self.tick_count += 1
            if self.inject_delay_s > 0.0:
                time.sleep(self.inject_delay_s)
            with span("flush"):
                self._flush()
            t1 = time.perf_counter()
            with span("guard"):
                if shed_guard:
                    self._m_shed["guard"].inc()
                events, n_guarded = self._update_divergence(shed=shed_guard)
            t2 = time.perf_counter()
            # plan straight off the packed columns: a twin registered
            # mid-plan is visible only once `registered` flips, and with 0
            # samples it cannot be ready
            with span("schedule"):
                with span("plan"):
                    plan = self.scheduler.plan(self.packed, self._slot_ring,
                                               max_active=self._max_active)
                with span("admit", admitted=len(plan.admit)):
                    self._apply_plan(plan)
            t3 = time.perf_counter()
            with span("refit"):
                if defer_refit:
                    self._m_shed["refit"].inc()
                if skip_promote:
                    self._m_shed["promote"].inc()
                loss = self._refit(defer=defer_refit,
                                   skip_promote=skip_promote)
                with span("sync", site="tick.block"):
                    jax.block_until_ready(self._theta)
            t4 = time.perf_counter()
        latency = t4 - t0
        self._m_tick.observe(latency)
        for stage, dt in zip(_STAGES, (t1 - t0, t2 - t1, t3 - t2, t4 - t3)):
            self._m_stage[stage].observe(dt)
        if latency > self.cfg.deadline_s:
            self._m_violations.inc()
        deg_ev = deg.observe(self.tick_count, latency)
        self._m_degraded.set(deg.level)
        if deg_ev is not None:
            self._m_deg_trans[
                "up" if deg_ev.to_level > deg_ev.from_level else "down"].inc()
        p = self.packed
        n_active = len(self._occupied_slots())
        n_twins = len(p.row_of)
        if n_active:
            self._m_refreshes.inc(n_active)
        self._m_tracked.set(n_twins)
        self._m_deployed.set(int(np.count_nonzero(p.deployed)))
        self._m_active.set(n_active)
        self._m_staging.set(self._staging.pending_samples())
        if self._pump is not None:
            self._m_queue.set(self._pump.queue_depth())
        self._guard_obs.live.set(int(np.count_nonzero(p.guard_live)))
        return TickReport(
            tick=self.tick_count, latency_s=latency,
            deadline_met=latency <= self.cfg.deadline_s, loss=loss,
            events=events, admitted=plan.admit, evicted=plan.evict,
            released=plan.release, n_active=n_active,
            n_twins=n_twins, n_guarded=n_guarded,
            degraded_level=deg.level,
            degradation_events=[deg_ev] if deg_ev is not None else [])

    # ------------------------------------------------------------------ #
    def _serving_row(self, twin_id: int, use: str) -> int:
        """Ring row of a twin with a deployed model and telemetry (an
        all-zero ring would start a rollout from the origin instead of the
        twin's actual state)."""
        row = self.packed.row_of[twin_id]
        if not self.packed.deployed[row]:
            raise RuntimeError(f"twin {twin_id} has no deployed model")
        if self.packed.samples[row] < 1:
            raise RuntimeError(f"twin {twin_id} has no telemetry to {use}")
        return row

    def predict(self, twin_id: int, horizon: int, us=None):
        """Roll the deployed model `horizon` steps from the newest telemetry.

        Returns ys [horizon+1, n] (index 0 = the newest observed state).
        """
        row = self._serving_row(twin_id, "predict from")
        ys, _ = self.ring.latest(self._rstate, jnp.asarray([row]), 0)
        y0 = ys[:, -1, :]                                    # [1, n]
        m = self.cfg.merinda.m
        us = (jnp.zeros((1, horizon, m)) if us is None
              else jnp.asarray(us, jnp.float32).reshape(1, horizon, m))
        out = rk4_poly_solve(self._theta[row][None], y0, us,
                             dt=self.cfg.merinda.dt, library=self.fleet.model.lib,
                             use_pallas=self.cfg.merinda.use_pallas,
                             interpret=self.cfg.merinda.interpret)
        return out[0]

    def scenario(self, twin_id: int, horizon: int, us=None,
                 k: int | None = None) -> ScenarioResult:
        """Answer a batched what-if query for one twin (twin/scenario.py).

        `us` is [K, horizon, m] counterfactual input sequences (or
        [horizon, m] for K=1; None = zero inputs, K from `k`).  Returns a
        `ScenarioResult` whose center trajectories come from the LIVE theta
        and whose lo/hi/confidence come from the recent-theta ensemble.
        Under deadline pressure the degradation ladder deterministically
        shrinks K (level >= shrink_level) or raises `ScenarioRefused`
        (level >= refuse_level) before any device work is dispatched.

        Serving-thread only, like `predict` (reads device ring state).
        """
        row = self._serving_row(twin_id, "roll scenarios from")
        if horizon < 1:
            raise ValueError(f"horizon must be >= 1, got {horizon}")
        scfg = self.cfg.scenario
        m = self.cfg.merinda.m
        if us is not None:
            us = np.asarray(us, np.float32)
            if us.ndim == 2:
                us = us[None]
            if us.ndim != 3 or us.shape[1] != horizon or us.shape[2] != m:
                raise ValueError(f"us must be [K, {horizon}, {m}], "
                                 f"got {us.shape}")
            requested = us.shape[0] if k is None else int(k)
            if requested > us.shape[0]:
                raise ValueError(f"k {requested} exceeds provided "
                                 f"sequences {us.shape[0]}")
        else:
            requested = 1 if k is None else int(k)
        level = self._degradation.level
        with self.tracer.span("scenario", twin=int(twin_id), k=requested,
                              horizon=int(horizon), level=level):
            t0 = time.perf_counter()
            try:
                eff = effective_k(requested, level, scfg)
            except ScenarioRefused:
                self._m_scn_refused.inc()
                raise
            if eff < requested:
                self._m_scn_shrunk.inc()
            us_eff = (np.zeros((eff, horizon, m), np.float32)
                      if us is None else np.ascontiguousarray(us[:eff]))
            with self.tracer.span("gather"):
                theta_hist, y0 = _scenario_gather(
                    self.ring, self._rstate, self._theta_hist,
                    np.int32(row))
                count = int(self._hist_count[row])
            with self.tracer.span("rollout"):
                center, lo, hi, conf = self.scenario_runner.rollout(
                    theta_hist, count, y0, us_eff)
            self._m_scn_requests.inc()
            self._m_scn_rollouts.inc(eff * scfg.ensemble)
            for c in conf:
                self._m_scn_confidence.observe(float(c))
            self._m_scn_latency.observe(time.perf_counter() - t0)
        return ScenarioResult(twin_id=int(twin_id), horizon=int(horizon),
                              requested_k=requested, k=eff,
                              degraded_level=level, ys=center, lo=lo, hi=hi,
                              confidence=conf)

    # ------------------------------------------------------------------ #
    def reset_latency_stats(self) -> None:
        """Reset the measured-window stats (benchmarks call this after jit
        warmup).  Resets the tick/stage histograms and the violation/refresh
        counters; LEAVES the monotone accounting counters (dropped samples,
        overflows, guard events) alone — those are lifetime totals."""
        self._m_tick.reset()
        for h in self._m_stage.values():
            h.reset()
        self._m_violations.reset()
        self._m_refreshes.reset()
        self._degradation.reset()     # compile stalls are not overload
        self._m_degraded.set(0)

    def latency_summary(self) -> dict:
        """p50/p99 refresh latency vs the deadline + serving throughput.

        Registry-backed: the same bounded histograms/counters an operator
        scrapes via `metrics.expose()` produce these numbers, so benchmarks
        and production dashboards cannot disagree.  p50/p99 are log-bucket
        estimates (< 4% relative quantization); max/violations are exact.
        """
        h = self._m_tick
        ticks = h.count
        if ticks == 0:
            return {"ticks": 0}
        return {
            "ticks": ticks,
            "p50_ms": h.quantile(0.5) * 1e3,
            "p99_ms": h.quantile(0.99) * 1e3,
            "max_ms": h.max * 1e3,
            "deadline_s": self.cfg.deadline_s,
            "violations": int(self._m_violations.value),
            # actual slot-refreshes performed, not pool capacity: idle slots
            # don't count toward serving throughput
            "twin_refreshes_per_s":
                self._m_refreshes.value / max(h.sum, 1e-9),
            "dropped_samples": int(self._m_dropped.value),
            "flush_overflows": int(self._m_overflow.value),
        }

    def stage_summary(self) -> dict:
        """Mean per-tick cost of each serving stage (ms) — the guard column
        is the scale benchmark's O(budget)-flatness evidence.  Registry-
        backed (histogram sum/count), same source the exporters scrape."""
        out = {}
        for stage, hist in self._m_stage.items():
            n = hist.count
            out[f"{stage}_ms"] = (hist.sum / n * 1e3) if n else 0.0
        return out

    # -- crash-safe serving state (twin/recovery.py checkpoints) -------- #
    @property
    def degraded_level(self) -> int:
        """Current deadline-degradation ladder level (0 = full service)."""
        return self._degradation.level

    def snapshot_state(self) -> dict:
        """Full serving state as a fixed-shape host pytree — what a
        `TwinCheckpointer` writes and `restore_state` consumes.

        Every leaf's shape is a function of the CONFIG alone (max_twins,
        refit_slots, ring capacity, model dims), never of runtime
        occupancy — so a fresh server's snapshot is a valid restore `like`
        and `checkpoint.restore`'s shape checks catch config drift.  All
        host arrays are COPIES (the async checkpoint writer must not race
        the serving thread's in-place mutations); device leaves are
        device_get by the checkpointer.

        Serving-thread only (reads device state mid-mutation otherwise).
        Excludes the staging buffer/pump (in-flight samples are the
        telemetry journal's job) and the bounded debug/metric windows
        (registry children are restart-safe monotone counters).
        """
        return {
            "theta": self._theta,
            "theta_hist": self._theta_hist,
            "hist_count": self._hist_count.copy(),
            "rstate": self._rstate,
            "fstate": self._fstate,
            "key": self._key,
            "packed": self.packed.snapshot(),
            "slot_ring": self._slot_ring.copy(),
            "scalars": np.asarray(
                [self.tick_count,
                 0 if self._rotation is None else self._rotation._cursor,
                 -1 if self._max_active is None else self._max_active],
                np.int64),
        }

    def restore_state(self, state: dict) -> None:
        """Rebuild this server's serving state from a `snapshot_state`
        tree (typically `checkpoint.restore`d into a fresh server's own
        snapshot as `like`).  Serving-thread only; call before any
        post-restart ingest/tick."""
        self._theta = jnp.asarray(state["theta"])
        self._theta_hist = jnp.asarray(state["theta_hist"])
        self._hist_count[:] = np.asarray(state["hist_count"])
        self._rstate = jax.tree.map(jnp.asarray, state["rstate"])
        self._fstate = jax.tree.map(jnp.asarray, state["fstate"])
        self._key = jnp.asarray(state["key"])
        self.packed.load(state["packed"])
        self._slot_ring[:] = np.asarray(state["slot_ring"], np.int32)
        tick, cursor, max_active = np.asarray(state["scalars"]).tolist()
        self.tick_count = tick
        if self._rotation is not None:
            self._rotation._cursor = cursor
        self._max_active = None if max_active < 0 else max_active
        self._live_dirty = True


class _TwinView(Mapping):
    """Read-only twin_id -> `TwinRecord` view of a `PackedFleet`: each read
    builds a fresh record from the twin's row, so mutating one changes
    nothing the server holds.  A lookup is O(1); iteration walks a copy of
    the id map taken under the registration lock."""

    def __init__(self, packed: PackedFleet):
        self._p = packed

    def __getitem__(self, twin_id: int) -> TwinRecord:
        p = self._p
        row = p.row_of[twin_id]
        slot = int(p.refit_slot[row])
        return TwinRecord(
            twin_id=int(p.twin_id[row]), ring_slot=row,
            refit_slot=None if slot < 0 else slot,
            samples=int(p.samples[row]),
            samples_at_deploy=int(p.samples_at_deploy[row]),
            deployed=bool(p.deployed[row]),
            deploy_tick=int(p.deploy_tick[row]),
            admitted_tick=int(p.admitted_tick[row]),
            residency=int(p.residency[row]),
            steps_in_slot=int(p.steps_in_slot[row]),
            divergence=float(p.divergence[row]))

    def __iter__(self):
        return iter(self._p.ids())

    def __len__(self) -> int:
        return len(self._p.row_of)
