"""Online digital-twin serving: the paper's deployment loop as a subsystem.

The paper's contribution is *online* twinning — refitting a recovered model
from live telemetry fast enough to beat human reaction time in mid-air
collision avoidance.  The offline path (core/trainer.py, train/loop.py)
recovers one model from one recorded trace; this package is the serving-scale
loop around it: **sense -> recover -> predict -> guard**, continuously, for a
whole tracked fleet on a bounded compute budget.

The STABLE surface is the `TwinService` protocol (service.py) and the three
servers that implement it at three scales — see docs/API.md for the
contract, and the "stable vs internal" split at the bottom of this
docstring.

Modules
-------
service.py    `TwinService` — the protocol every server implements
              (ingest/ingest_many/tick/drain/predict/snapshot_state/...),
              plus the shared config bases `DeadlineConfig` and
              `FleetTopologyConfig`.  The conformance suite
              (tests/test_service_conformance.py) pins the semantics.

stream.py     `TelemetryRing` — per-twin fixed-capacity telemetry rings
              stored as device arrays.  One jitted scatter ingests a chunk
              for every twin (`ingest`); one jitted gather turns the newest
              samples into the sliding-window batches the trainer consumes
              (`windows`, parity-tested against data/pipeline.make_windows).

scheduler.py  Slot-based refit scheduling mirroring serve/engine.ServeEngine's
              admission pattern: a fixed pool of FleetMerinda slots, twins
              admitted / preempted / released by a priority score of
              staleness + divergence, so thousands of tracked objects share
              `refit_slots` concurrent recoveries.  `PackedRefitScheduler`
              (the server's planner) scores the whole fleet in one fused
              device call over the packed columns (packed.py) and pops only
              the O(slots) winners through a `PriorityBuckets` queue;
              `RefitScheduler` is the O(n log n) dict-sorting reference the
              equivalence tests hold it to.  `SlotFederation` divides a
              global active-slot budget across per-shard schedulers by
              aggregate pressure (sharded + federated serving).

packed.py     `PackedFleet` — each twin's serving state in packed, row-indexed
              columns (samples, deploy watermark, divergence, refit slot and
              residency, tick stamps, guard verdict) and the twin-id -> row
              map: the only per-twin state the server holds.  The fused
              scoring / pressure kernels reduce it on device.

sharded.py    `ShardedTwinServer` — N shards IN ONE PROCESS, each its own
              ring + slot pool + theta store + scheduler, under one
              federation: the 10k+ tracked-object architecture (async
              ingest per shard, budgeted guard rotation, slot grants
              following divergence pressure).

federation.py `FederatedTwinServer` — the same architecture across REAL
              process boundaries: a `FederationCoordinator` owning N
              `ShardWorker` subprocesses (each a `TwinServer` + its
              checkpointer), supervisor-side telemetry journals, failure
              detection + supervised restart with journal-tail replay, and
              an optional TCP ingestion front door for remote telemetry
              producers.

wire.py       The versioned wire format federation speaks: message
              dataclasses, the JSON-header + raw-array-blob codec, stream
              framing, `IngestFrontDoor`/`FrontDoorClient`.  Framing
              internals are NOT a stable API (docs/API.md).

scenario.py   `ScenarioRunner` — batched what-if rollouts: K counterfactual
              input sequences per twin evaluated in ONE fused ensemble x K
              device call against the recent-theta history, returning
              center trajectories plus lo/hi confidence bounds.
              `TwinServer.scenario()` serves it under the degradation
              ladder (shrink K, then refuse) on all three servers.

server.py     `TwinServer` — ties the loop together.  `ingest(twin_id, y, u)`
              stages telemetry; each `tick()` flushes to the rings, scores
              divergence, turns over slots, runs `steps_per_tick` fused
              incremental train steps, and deploys recovered thetas — with
              per-tick latency accounted against the 1 s refresh deadline
              (5x under the paper's 5 s human-reaction budget).
              `predict(twin_id, horizon)` is the collision-avoidance
              lookahead on the deployed model.

monitor.py    `DivergenceGuard` — RK4-rolls every deployed theta over the
              newest telemetry window and compares against what the sensors
              reported; emits REFIT (physics drifted, re-recover) and ALERT
              (model untrustworthy — the safety abort signal) events.

recovery.py   Crash-safety layer: `TwinCheckpointer` (per-shard atomic theta
              store checkpoints, async off the tick loop), `TelemetryJournal`
              (supervisor-side replay log bounded by the ring horizon),
              `ChaosConfig`/`ChaosInjector` (fault injection: shard kills,
              stragglers, torn checkpoints, ingest storms) and
              `DegradationPolicy` (deadline-aware shedding ladder:
              shrink guard -> defer refits -> skip promotion).  See
              docs/ROBUSTNESS.md.

Quick start
-----------
    from repro.core.merinda import MerindaConfig
    from repro.twin import TwinServer, TwinServerConfig

    cfg = TwinServerConfig(merinda=MerindaConfig(n=3, m=1, order=3, dt=0.01),
                           max_twins=64, refit_slots=8)
    server = TwinServer(cfg)
    for t in range(1000):
        server.ingest_many(telemetry_at(t))      # [(twin_id, y[, u]), ...]
        report = server.tick()          # fused refit of every active slot
        for ev in report.events:        # REFIT / ALERT
            handle(ev)
    ys = server.predict(twin_id, horizon=50)

Scale out by swapping the config, not the call sites (`TwinService`):
`ShardedTwinConfig.uniform(cfg, shards)` -> `ShardedTwinServer`, or
`FederatedTwinConfig.uniform(cfg, workers, front_door=True)` ->
`FederatedTwinServer`.

End-to-end scenarios: examples/online_twinning.py (64 F-8 twins, mid-stream
dynamics switch -> guard fires, scheduler re-recovers) and
examples/sharded_fleet.py (1k+ heterogeneous twins across federated shards).
Sustained latency/throughput tables: benchmarks/online_serving.py
(`--only online`), benchmarks/online_scale.py (`--only online_scale`,
64 -> 10k twins) and benchmarks/online_federated.py
(`--only online_federated`, multi-process).
"""
from repro.twin.federation import (FederatedTwinConfig, FederatedTwinServer,
                                   FederationCoordinator, ShardWorker)
from repro.twin.monitor import (DivergenceGuard, GuardConfig, GuardEvent,
                                GuardInstruments, GuardRotation)
from repro.twin.packed import PackedFleet, fleet_pressure, fleet_scores
from repro.twin.recovery import (ChaosConfig, ChaosInjector,
                                 DegradationConfig, DegradationEvent,
                                 DegradationPolicy, RecoveryConfig,
                                 ShardFailure, TelemetryJournal,
                                 TwinCheckpointer)
from repro.twin.scenario import (ScenarioConfig, ScenarioRefused,
                                 ScenarioResult, ScenarioRunner, effective_k)
from repro.twin.scheduler import (FederationConfig, PackedRefitScheduler,
                                  PriorityBuckets, RefitScheduler,
                                  SchedulerConfig, SchedulePlan,
                                  SchedulerMetrics, SlotFederation,
                                  TwinRecord)
from repro.twin.server import TickReport, TwinServer, TwinServerConfig
from repro.twin.service import (DeadlineConfig, FleetTopologyConfig,
                                TwinService, conforms)
from repro.twin.sharded import (ShardedTickReport, ShardedTwinConfig,
                                ShardedTwinServer)
from repro.twin.stream import (RingConfig, StagingBuffer, StagingOverflow,
                               TelemetryRing, prepare_flush)
from repro.twin.wire import FrontDoorClient, IngestFrontDoor

# --------------------------------------------------------------------------- #
# STABLE serving surface (docs/API.md): the protocol, the three servers that
# implement it, their configs, and the report/event types callers consume.
# Everything callers need to serve a fleet at any scale.
# --------------------------------------------------------------------------- #
_STABLE = [
    "TwinService", "conforms",
    "DeadlineConfig", "FleetTopologyConfig",
    "TwinServer", "TwinServerConfig", "TickReport",
    "ShardedTwinServer", "ShardedTwinConfig", "ShardedTickReport",
    "FederatedTwinServer", "FederatedTwinConfig",
    "FrontDoorClient", "IngestFrontDoor",
    "GuardConfig", "GuardEvent",
    "ScenarioConfig", "ScenarioResult", "ScenarioRefused",
    "RecoveryConfig", "ChaosConfig",
    "DegradationConfig", "DegradationEvent",
]

# --------------------------------------------------------------------------- #
# INTERNAL building blocks, exported for tests/benchmarks/extension authors.
# Subject to change without deprecation (packed layouts, wire framing,
# scheduler internals) — depend on the stable surface instead where possible.
# --------------------------------------------------------------------------- #
_INTERNAL = [
    "FederationCoordinator", "ShardWorker",
    "DivergenceGuard", "GuardInstruments", "GuardRotation",
    "ScenarioRunner", "effective_k",
    "FederationConfig", "PackedFleet", "PackedRefitScheduler",
    "PriorityBuckets", "RefitScheduler", "SchedulerConfig", "SchedulePlan",
    "SchedulerMetrics", "SlotFederation", "TwinRecord",
    "fleet_pressure", "fleet_scores",
    "ChaosInjector", "DegradationPolicy", "ShardFailure",
    "TelemetryJournal", "TwinCheckpointer",
    "RingConfig", "StagingBuffer", "StagingOverflow", "TelemetryRing",
    "prepare_flush",
]

__all__ = _STABLE + _INTERNAL
