"""The stable serving API: the `TwinService` protocol + shared config bases.

Three servers implement the same serving surface at three scales:

    TwinServer            one process, one ring/fleet/theta store
    ShardedTwinServer     one process, N in-process shards + slot federation
    FederatedTwinServer   one coordinator process, N shard-worker SUBPROCESSES
                          (twin/federation.py) behind a versioned wire format
                          (twin/wire.py)

The process split is what forces the protocol: a coordinator cannot reach
into a worker's packed twin state or theta store, so everything a caller may
depend on has to be a method on this surface — and once it is, telemetry
producers, front doors (`twin.wire.IngestFrontDoor`), benchmarks, and the
conformance suite (tests/test_service_conformance.py) run unchanged against
all three implementations.  `docs/API.md` documents the stable surface;
modules not named there (`packed`, `wire` framing internals) are
implementation detail and may change without deprecation.

Config consolidation (the other half of the redesign): the deadline lives in
ONE base (`DeadlineConfig`) instead of being re-declared per server config,
and the fleet-topology knobs a sharded and a federated deployment share —
global slot budget, per-shard grant floor, rebalance cadence, pressure
smoothing, recovery + chaos schedules — live in `FleetTopologyConfig`, which
both `ShardedTwinConfig` and `FederatedTwinConfig` extend.  The topology
base also owns the mapping onto the scheduler-level `FederationConfig`
(`make_federation`), so the two deployment shapes cannot drift.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Iterable, Protocol, runtime_checkable

from repro.twin.recovery import ChaosConfig, RecoveryConfig
from repro.twin.scheduler import FederationConfig

__all__ = ["TwinService", "DeadlineConfig", "FleetTopologyConfig",
           "IngestChunkLike", "conforms"]

# batch element accepted by `ingest_many`: (twin_id, y) or (twin_id, y, u)
IngestChunkLike = tuple


@runtime_checkable
class TwinService(Protocol):
    """What every twin server exposes, single-process or federated.

    Semantics every implementation must honor (the conformance suite pins
    them):

      * `ingest` stages telemetry host-side and never blocks on device work;
        `force=True` bypasses staging backpressure (crash-recovery replay).
      * `ingest_many` is the batched form — one call per producer flush, so
        a network front door is not forced into per-sample calls.  Returns
        the number of SAMPLES staged.
      * `tick` runs one full serving cycle and returns a report object with
        at least `.events` (guard transitions), `.latency_s`,
        `.deadline_met`, `.n_twins`, `.n_active`.
      * `drain` is the ingest barrier: every sample whose `ingest` returned
        before the call is visible to the next fused gather.
      * `predict` rolls the deployed model forward from the newest
        telemetry — the collision-avoidance lookahead.
      * `scenario` answers a batched what-if query: K counterfactual input
        sequences rolled forward from the newest telemetry with ensemble
        confidence bounds (`twin/scenario.py`).  Under deadline pressure
        the degradation ladder may deterministically shrink K or refuse
        with `ScenarioRefused`.
      * `snapshot_state` returns a host pytree sufficient to rebuild the
        serving state (per-shard sub-trees for multi-shard services).
      * `close` releases background threads/processes; idempotent.
    """

    def register(self, twin_id: int) -> Any: ...

    def ingest(self, twin_id: int, y, u=None, *,
               force: bool = False) -> None: ...

    def ingest_many(self, batch: Iterable[IngestChunkLike], *,
                    force: bool = False) -> int: ...

    def deploy(self, twin_id: int, theta) -> None: ...

    def deploy_many(self, twin_ids, thetas) -> None: ...

    def tick(self) -> Any: ...

    def drain(self) -> None: ...

    def predict(self, twin_id: int, horizon: int, us=None): ...

    def scenario(self, twin_id: int, horizon: int, us=None,
                 k: int | None = None): ...

    def snapshot_state(self) -> dict: ...

    def latency_summary(self) -> dict: ...

    def stage_summary(self) -> dict: ...

    def reset_latency_stats(self) -> None: ...

    def close(self) -> None: ...


_PROTOCOL_METHODS = tuple(
    name for name in vars(TwinService)
    if not name.startswith("_") and callable(getattr(TwinService, name)))


def conforms(obj) -> list[str]:
    """Names from the `TwinService` surface that `obj` is missing (empty
    list = structurally conformant).  Runtime `isinstance` checks only see
    attribute presence; tests use this for a readable diff."""
    return [name for name in _PROTOCOL_METHODS
            if not callable(getattr(obj, name, None))]


# --------------------------------------------------------------------------- #
# shared config bases
# --------------------------------------------------------------------------- #
@dataclass(frozen=True, kw_only=True)
class DeadlineConfig:
    """The mission refresh budget, declared once.

    `deadline_s` is SECONDS; the 1.0 s default is the paper's margin — 5x
    under the 5 s human-pilot reaction time.  `TwinServerConfig` inherits it
    directly; fleet configs (`ShardedTwinConfig`, `FederatedTwinConfig`)
    override the default to None, meaning "derive the tightest per-shard
    deadline" — set it explicitly to gate the WHOLE fleet tick instead.
    """
    deadline_s: float = 1.0


@dataclass(frozen=True, kw_only=True)
class FleetTopologyConfig(DeadlineConfig):
    """Fleet-shape knobs shared by in-process sharding and multi-process
    federation.  One definition, two deployment shapes — `ShardedTwinConfig`
    and `FederatedTwinConfig` both extend this, so the slot-budget /
    rebalance / recovery surface cannot drift between them."""
    deadline_s: float | None = field(default=None, kw_only=True)
    total_slots: int | None = None    # global active-refit budget
                                      # (None: sum of physical pools —
                                      # federation never constrains)
    min_shard_slots: int = 1          # per-shard grant floor
    rebalance_every: int = 4          # federation period (ticks)
    pressure_smooth: float = 0.5      # EMA on the pressure signal
    recovery: RecoveryConfig | None = None
                                      # per-shard checkpointing + journal +
                                      # supervised restart (twin/recovery.py)
    chaos: ChaosConfig | None = None  # injected failure schedule (tests/
                                      # benchmarks; None in production)

    def make_federation(self, pools: list[int]) -> "FederationConfig":
        """The scheduler-level federation for this topology's physical slot
        pools — the one place the config names map onto
        `FederationConfig`'s."""
        total = sum(pools) if self.total_slots is None else self.total_slots
        return FederationConfig(total_slots=total,
                                min_shard_slots=self.min_shard_slots,
                                pressure_smooth=self.pressure_smooth)
