"""Divergence guard: does the deployed twin still match reality?

The paper's safety case (mid-air collision avoidance) rests on the deployed
model staying faithful to the physical system it shadows.  The guard closes
that loop: every serving tick it RK4-rolls each deployed theta forward over
the NEWEST telemetry window (same integrator the twin was recovered with —
kernels/rk4) and scores the normalized rollout error against what the sensors
actually reported.

    score = mean((SOLVE(y_0, theta, U) - Y)^2) / (var(Y) + eps)

Variance normalization makes one threshold meaningful across systems with
wildly different state magnitudes (F-8 angle-of-attack radians vs Lorenz
tens).  A diverged model frequently goes unstable under rollout; non-finite
errors are clamped to a large finite score so the guard fires instead of
propagating NaNs.

Host-side hysteresis (`judge`) turns scores into events:
  * score > refit_threshold  -> REFIT  (scheduler priority boost: the twin's
    physics drifted — re-recover it)
  * score > alert_threshold  -> ALERT  (the model is too wrong to trust for
    prediction — the collision-avoidance abort signal)

Scores are EMA-smoothed so a single noisy window does not flap the scheduler.

At 10k+ tracked objects, rolling EVERY deployed theta per tick makes the
guard the serving bottleneck — `GuardRotation` bounds it: each tick scores a
fixed-size subset (budgeted round-robin over the store, plus a carry-over
quota that re-scores the currently most-diverged twins every tick), so guard
cost is O(budget) instead of O(twins) while every twin is still guaranteed a
score within ceil(twins / budget) ticks.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import shard
from repro.kernels.rk4.ops import rk4_poly_solve
from repro.obs.registry import DEFAULT_SCORE_BUCKETS

__all__ = ["GuardConfig", "GuardEvent", "GuardInstruments", "DivergenceGuard",
           "GuardRotation", "score_confidence"]

_BLOWUP_SCORE = 1e6     # score assigned to non-finite (unstable) rollouts


@dataclass(frozen=True)
class GuardConfig:
    window: int = 32                 # telemetry steps rolled per check
    refit_threshold: float = 0.1
    alert_threshold: float = 1.0
    ema: float = 0.5                 # new-score weight in the EMA


def score_confidence(score: float) -> float:
    """Map a normalized divergence score to a confidence in (0, 1].

    The guard's score is already a scale-free ratio (rollout error over
    telemetry variance), so `1 / (1 + score)` gives a dimensionless trust
    weight: ~1 while the model tracks, ~0 for a blown-up rollout.  The
    same squash the scenario engine applies to its ensemble spread
    (twin/scenario.py), so ALERT confidence and what-if confidence are
    directly comparable on one dashboard axis.
    """
    return 1.0 / (1.0 + max(float(score), 0.0))


@dataclass(frozen=True)
class GuardEvent:
    twin_id: int
    kind: str        # "REFIT" | "ALERT"
    score: float
    tick: int
    confidence: float = 1.0    # score_confidence(score); 1.0 = full trust


@dataclass
class GuardInstruments:
    """Guard/rotation instruments (obs registry children, one set per shard).

    Owned by the SERVER, not by `DivergenceGuard`: sharded serving shares
    one stateless guard instance across shards (`share_modules_from`), so
    per-shard attribution has to live with the per-shard caller.  The
    definitions live here so the guard's metric surface is catalogued next
    to the signals it measures.

    `events` counts REFIT/ALERT state TRANSITIONS (what an operator pages
    on), not the per-tick re-judgement of an already-flagged twin; `score`
    is the raw (pre-EMA) divergence-score distribution; `scored` counts
    fused guard evaluations (rotation throughput); `live` gauges the
    guard-eligible set the rotation cycles over.
    """
    events: dict            # kind -> Counter
    score: object           # Histogram of raw divergence scores
    scored: object          # Counter: twins scored by the fused guard call
    live: object            # Gauge: guard-eligible (deployed + sampled) twins

    @staticmethod
    def create(registry, labels: dict | None = None) -> "GuardInstruments":
        labels = labels or {}
        return GuardInstruments(
            events={kind: registry.counter(
                        "twin_guard_events_total",
                        help="guard state transitions by kind",
                        labels={**labels, "kind": kind})
                    for kind in ("REFIT", "ALERT")},
            score=registry.histogram(
                "twin_divergence_score",
                help="raw guard divergence scores (normalized rollout "
                     "error; 1e6 = non-finite blowup)",
                bounds=DEFAULT_SCORE_BUCKETS, labels=labels),
            scored=registry.counter(
                "twin_guard_scored_total",
                help="twin scorings performed by the fused guard rollout",
                labels=labels),
            live=registry.gauge(
                "twin_guard_live",
                help="guard-eligible twins (deployed with enough samples)",
                labels=labels))


class DivergenceGuard:
    """Scores deployed thetas against reality; see module docstring.

    Backend: `use_pallas`/`interpret` mirror `MerindaConfig` and flow into
    the fused `rk4_poly_solve` rollout — `TwinServer` always passes its
    MerindaConfig's values, so the guard rolls with the SAME backend the twin
    was trained/recovered with.  ``interpret=None`` is the auto default
    resolved in kernels/backend (compiled on TPU, interpreter elsewhere);
    the old local ``interpret=True`` default silently pinned interpreter
    mode regardless of the config.
    """

    def __init__(self, library, dt: float, cfg: GuardConfig = GuardConfig(),
                 *, use_pallas: bool = False, interpret: bool | None = None):
        self.lib = library
        self.dt = dt
        self.cfg = cfg
        self.use_pallas = use_pallas
        self.interpret = interpret

    # ------------------------------------------------------------------ #
    @partial(jax.jit, static_argnames=("self",))
    def score(self, theta, ys, us):
        """Normalized rollout error per twin (fused over the whole store).

        theta: [B, n, L]; ys: [B, k+1, n] newest telemetry; us: [B, k, m].
        Returns [B] float32 — finite even when the rollout diverges.
        """
        y_est = rk4_poly_solve(shard(theta, "twin_theta"), ys[:, 0, :], us,
                               dt=self.dt, library=self.lib,
                               use_pallas=self.use_pallas,
                               interpret=self.interpret)
        num = jnp.mean(jnp.square(y_est - ys), axis=(1, 2))
        den = jnp.mean(jnp.square(ys - jnp.mean(ys, axis=1, keepdims=True)),
                       axis=(1, 2)) + 1e-6
        return jnp.nan_to_num(num / den, nan=_BLOWUP_SCORE,
                              posinf=_BLOWUP_SCORE)

    # ------------------------------------------------------------------ #
    def smooth(self, prev: float, score: float) -> float:
        """EMA update used by the server when folding scores into records."""
        a = self.cfg.ema
        return a * min(float(score), _BLOWUP_SCORE) + (1.0 - a) * prev

    def fold_into(self, div_by_row: np.ndarray, rows: np.ndarray,
                  scores) -> np.ndarray:
        """Vectorized `smooth`: EMA-fold raw `scores` into the by-row
        divergence array IN PLACE at `rows`, returning the updated values.

        `div_by_row` is the packed fleet's divergence column
        (twin/packed.py), so this single numpy statement is how the guard
        publishes its view to the scheduler's fused scoring call.  Same
        float64 arithmetic order as the scalar `smooth`.
        """
        a = self.cfg.ema
        rows = np.asarray(rows)
        clipped = np.minimum(np.asarray(scores, np.float64), _BLOWUP_SCORE)
        div_by_row[rows] = a * clipped + (1.0 - a) * div_by_row[rows]
        return div_by_row[rows]

    def judge(self, twin_id: int, score: float, tick: int) -> GuardEvent | None:
        """Threshold an (already smoothed) score into an event, or None."""
        if score > self.cfg.alert_threshold:
            return GuardEvent(twin_id, "ALERT", float(score), tick,
                              score_confidence(score))
        if score > self.cfg.refit_threshold:
            return GuardEvent(twin_id, "REFIT", float(score), tick,
                              score_confidence(score))
        return None


class GuardRotation:
    """Budgeted round-robin guard scheduling with divergence carry-over.

    Each tick `select()` picks which ring rows the guard scores:

      * `budget` rows advance a cyclic cursor over the eligible set, so every
        eligible twin is re-scored within ceil(eligible / budget) ticks — the
        freshness floor (host-tested in tests/test_twin_sharded.py);
      * up to `carry` EXTRA rows re-score the currently most-diverged twins
        (EMA score above the refit threshold) every tick, so a flagged twin's
        escalation to ALERT is never delayed by its place in the rotation.

    The carry quota rides ON TOP of the round-robin budget (fused guard call
    shape = budget + carry, scratch-padded), so priority twins never starve
    the rotation and the freshness bound survives any divergence pattern.

    Selection is pure numpy over a pre-sorted eligible-row array and a
    by-row divergence array (both maintained incrementally by the server):
    at 10k twins a per-tick python rescan of the store would reintroduce the
    O(twins) host cost this class exists to remove.

    Complexity contract: per tick, device work is one fused rollout of
    exactly `budget + carry` rows and host work is O(budget + carry + F)
    where F is the count of currently-flagged twins (vectorized numpy) —
    BOTH independent of the tracked-twin count.  The empirical gate: the
    scale benchmark (`benchmarks/run.py --only online_scale`) requires mean
    guard stage cost per tick to grow < 2x from 1k to 10k twins at a fixed
    budget (last recorded: 21 -> 39 ms, 1.84x — bench_out/online_scale.csv),
    and the freshness floor (every eligible twin re-scored within
    ceil(eligible / budget) ticks) is host-tested in
    tests/test_twin_sharded.py.
    """

    def __init__(self, budget: int, carry: int = 0):
        if budget < 1:
            raise ValueError("guard rotation budget must be >= 1")
        self.budget = budget
        self.carry = max(0, carry)
        self._cursor = 0       # next ring row served by the rotation (cyclic)

    @property
    def size(self) -> int:
        """Fixed fused-call width (rows beyond the pick are scratch-padded)."""
        return self.budget + self.carry

    def select(self, rows: np.ndarray, div_by_row: np.ndarray,
               threshold: float, *, budget: int | None = None,
               carry: int | None = None) -> np.ndarray:
        """Pick this tick's ring rows.

        rows: SORTED int array of eligible ring rows; div_by_row: full
        by-row EMA score array (indexed by ring row, not position).
        Returns at most `budget + carry` distinct rows.

        `budget`/`carry` override the configured quotas for ONE call — the
        deadline-degradation path (twin/recovery.py) shrinks the fused guard
        width under overload without touching the rotation's steady-state
        shape.  The cursor still advances by what was actually scored, so
        the freshness bound degrades proportionally instead of breaking.
        """
        eff_budget = self.budget if budget is None else max(1, budget)
        eff_carry = self.carry if carry is None else max(0, carry)
        rows = np.asarray(rows)
        if rows.size == 0:
            return rows
        i = int(np.searchsorted(rows, self._cursor))
        take = min(eff_budget, rows.size)
        pick = rows[(i + np.arange(take)) % rows.size]
        self._cursor = int(pick[-1]) + 1
        if eff_carry:
            flagged = rows[div_by_row[rows] > threshold]
            flagged = flagged[~np.isin(flagged, pick)]
            if flagged.size > eff_carry:
                part = np.argpartition(-div_by_row[flagged],
                                       eff_carry - 1)[:eff_carry]
                flagged = flagged[part]
            # deterministic order: most diverged first, row id breaks ties
            flagged = flagged[np.lexsort((flagged, -div_by_row[flagged]))]
            pick = np.concatenate([pick, flagged])
        return pick
