"""Packed fleet-state arrays: each twin's serving state, one row per twin.

A `PackedFleet` is the only holder of per-twin serving state: sample
counters, deploy watermark, divergence, refit-slot residency, tick stamps and
the guard's last verdict all live in row-indexed numpy columns, and the
twin-id -> row map assigns the rows.  Every mutation point in the server
(flush accounting, deploy, guard fold, plan application, refit) writes the
columns; the scheduler scores the WHOLE fleet in one fused, jit-compiled
device call (`fleet_scores`) that returns only O(slots) winners, the
waiting-queue depth, and the federation pressure reduction — so per-tick host
work is O(budget), not O(twins).

Rows are TelemetryRing rows, so the guard's by-row divergence array, the
rotation's live set, and the scheduler's score arrays all share one indexing
scheme.

Precision contract: the device kernel scores in float32 (it only has to
RANK candidates — `jax.lax.top_k` ties break toward the lower row index);
the host re-scores the returned O(slots) candidates in float64 with exactly
the reference planner's arithmetic, so every admission/eviction COMPARISON
in `PackedRefitScheduler.plan` is bit-identical to `RefitScheduler.plan`.
The only divergence window is a float32 ranking swap across the top-k
cutoff between candidates whose float64 priorities differ by less than
float32 resolution — semantically a coin-flip tie.
"""
from __future__ import annotations

import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import null_span

__all__ = ["GUARD_KINDS", "PackedFleet", "fleet_scores", "fleet_pressure"]

GUARD_KINDS = ("OK", "REFIT", "ALERT")    # `guard_code` values, in order


def _pad_capacity(n: int, floor: int = 64) -> int:
    """Round a row capacity up to a pow2 bucket (bounds jit recompiles when
    tests/tools build many small fleets; servers pass their exact, fixed
    `max_twins` and compile once per topology)."""
    cap = floor
    while cap < n:
        cap *= 2
    return cap


class PackedFleet:
    """Row-indexed serving state for one shard's tracked fleet.

    All arrays have length `capacity` (= the server's `max_twins`); a row is
    live once `registered[row]` is True, and `row_of` maps a twin id to its
    row.  Sample counters are int32 — the fused call's native dtype, exact in
    float64 host re-scoring, and good for 8 years of serving at 8 samples/s
    — so the per-tick device call reads the columns without a conversion
    pass.  Two columns are float32/bool shadows the device kernel reads,
    written at the same mutation points as their host truth: `div32` of
    `divergence` (float64, the guard's exact truth for host re-scoring) and
    `resident` of `refit_slot >= 0`; `check_mirrors` asserts they never
    drift.  `guard_code` indexes `GUARD_KINDS` (the guard's last verdict)
    and `guard_live` marks rows the guard scores (deployed, with a full
    guard window of samples).

    Thread-safety: `register` may be called from ingest threads.  It holds
    the fleet's lock and sets `registered` LAST, so a concurrently-planning
    tick sees either a fully initialized row or an unready one; `ids()`
    copies the id map under the same lock.  Every other field is written
    only by the serving thread.
    """

    _COLUMNS = ("twin_id", "registered", "samples", "samples_at_deploy",
                "deployed", "divergence", "div32", "resident", "residency",
                "refit_slot", "deploy_tick", "admitted_tick", "steps_in_slot",
                "guard_code", "guard_live")
    __slots__ = ("capacity", "row_of", "_lock") + _COLUMNS

    def __init__(self, capacity: int):
        self.capacity = capacity
        self.row_of: dict[int, int] = {}
        self._lock = threading.Lock()
        self.twin_id = np.full((capacity,), -1, np.int64)
        self.registered = np.zeros((capacity,), bool)
        self.samples = np.zeros((capacity,), np.int32)
        self.samples_at_deploy = np.zeros((capacity,), np.int32)
        self.deployed = np.zeros((capacity,), bool)
        self.divergence = np.zeros((capacity,), np.float64)
        self.div32 = np.zeros((capacity,), np.float32)
        self.resident = np.zeros((capacity,), bool)
        self.residency = np.zeros((capacity,), np.int64)
        self.refit_slot = np.full((capacity,), -1, np.int32)
        self.deploy_tick = np.full((capacity,), -1, np.int64)
        self.admitted_tick = np.full((capacity,), -1, np.int64)
        self.steps_in_slot = np.zeros((capacity,), np.int64)
        self.guard_code = np.zeros((capacity,), np.int8)
        self.guard_live = np.zeros((capacity,), bool)

    def set_divergence(self, rows, values) -> None:
        """Write divergence truth + its float32 device shadow together —
        the only sanctioned way to move the divergence column."""
        self.divergence[rows] = values
        self.div32[rows] = self.divergence[rows]

    def check_mirrors(self) -> None:
        """Assert the device shadows match their host truth (tests)."""
        if not np.array_equal(self.div32,
                              self.divergence.astype(np.float32)):
            raise AssertionError("div32 shadow drifted from divergence")
        if not np.array_equal(self.resident, self.refit_slot >= 0):
            raise AssertionError("resident shadow drifted from refit_slot")

    # ------------------------------------------------------------------ #
    def snapshot(self) -> dict:
        """Copy every column into a plain dict of numpy arrays — the
        checkpointable packed-fleet state (twin/recovery.py).  COPIES, not
        views: the async checkpoint writer must not race the serving
        thread's in-place column mutations."""
        return {c: getattr(self, c).copy() for c in self._COLUMNS}

    def load(self, state: dict) -> None:
        """Restore columns IN PLACE (`[:]`) from a `snapshot()` dict and
        rebuild the id map from them."""
        for c in self._COLUMNS:
            col = getattr(self, c)
            src = np.asarray(state[c])
            if src.shape != col.shape:
                raise ValueError(f"packed column {c!r}: snapshot shape "
                                 f"{src.shape} != live shape {col.shape}")
            col[:] = src
        rows = np.flatnonzero(self.registered)
        with self._lock:
            self.row_of = dict(zip(self.twin_id[rows].tolist(),
                                   rows.tolist()))

    # ------------------------------------------------------------------ #
    def register(self, twin_id: int) -> int:
        """The twin's row, assigning the next free one on first sight.
        `registered` is set last — see class docstring for the
        concurrent-plan visibility argument."""
        row = self.row_of.get(twin_id)
        if row is not None:
            return row
        with self._lock:
            row = self.row_of.get(twin_id)
            if row is None:
                row = len(self.row_of)
                if row >= self.capacity:
                    raise RuntimeError(
                        f"server full ({self.capacity} twins)")
                self.twin_id[row] = twin_id
                self.registered[row] = True
                self.row_of[twin_id] = row
        return row

    def ids(self) -> list[int]:
        """Registered twin ids, copied under the registration lock (safe to
        iterate while ingest threads register)."""
        with self._lock:
            return list(self.row_of)

    # ------------------------------------------------------------------ #
    @classmethod
    def from_records(cls, twins: dict, *, capacity: int | None = None
                     ) -> "PackedFleet":
        """Build packed arrays from a `TwinRecord` dict (rows =
        `ring_slot`).  The reference-planner interop path: equivalence
        tests feed the same record dict to both planners."""
        max_row = max((r.ring_slot for r in twins.values()), default=-1)
        cap = (_pad_capacity(max_row + 1) if capacity is None else capacity)
        if max_row >= cap:
            raise ValueError(f"ring_slot {max_row} exceeds capacity {cap}")
        fleet = cls(cap)
        for rec in twins.values():
            row = rec.ring_slot
            if fleet.registered[row]:
                raise ValueError(f"duplicate ring_slot {row}")
            fleet.twin_id[row] = rec.twin_id
            fleet.samples[row] = rec.samples
            fleet.samples_at_deploy[row] = rec.samples_at_deploy
            fleet.deployed[row] = rec.deployed
            fleet.set_divergence(row, rec.divergence)
            fleet.refit_slot[row] = (-1 if rec.refit_slot is None
                                     else rec.refit_slot)
            fleet.resident[row] = rec.refit_slot is not None
            fleet.residency[row] = rec.residency
            fleet.registered[row] = True
            fleet.row_of[rec.twin_id] = row
        return fleet

    def slot_rows_from_records(self, twins: dict, slots: int) -> np.ndarray:
        """[slots] array of resident ring rows (`capacity` marks an empty
        slot — the same scratch-row convention as the server's slot ring)."""
        slot_rows = np.full((slots,), self.capacity, np.int64)
        for rec in twins.values():
            if rec.refit_slot is None:
                continue
            if not 0 <= rec.refit_slot < slots:
                raise ValueError(f"refit_slot {rec.refit_slot} out of range")
            if slot_rows[rec.refit_slot] != self.capacity:
                raise ValueError(f"slot {rec.refit_slot} doubly occupied")
            slot_rows[rec.refit_slot] = rec.ring_slot
        return slot_rows


# --------------------------------------------------------------------------- #
# the fused scoring kernel: one jit-compiled call over the whole fleet
# --------------------------------------------------------------------------- #
def _score_terms(samples, at_deploy, deployed, divergence, resident,
                 registered, min_samples, sw, dw, k: int):
    """Score every row and reduce to what the host actually needs.

        priority = sw * (staleness + never_deployed) + dw * divergence
        staleness = (samples - samples_at_deploy) / max(min_samples, 1)

    Returns (cand_rows [k], cand_prio [k], n_waiting [], pressure []):
    the top-k READY, UNSLOTTED rows by priority (ties toward the lower row
    index — `lax.top_k` is stable), the waiting-queue depth, and the summed
    priority over all ready rows (the federation pressure signal).  k =
    the slot-pool size is sufficient for exact planning: one tick can
    admit at most `slots` twins (fill + evict combined), so every waiting
    twin the reference planner could touch is inside the top-k.
    """
    stale = (samples - at_deploy).astype(jnp.float32) / jnp.maximum(
        min_samples, 1).astype(jnp.float32)
    stale = stale + jnp.where(deployed, 0.0, 1.0)
    prio = sw * stale + dw * divergence
    ready = registered & (samples >= min_samples)
    pressure = jnp.sum(jnp.where(ready, prio, 0.0))
    waiting = ready & ~resident
    n_waiting = jnp.sum(waiting)
    cand_prio, cand_rows = jax.lax.top_k(
        jnp.where(waiting, prio, -jnp.inf), k)
    return cand_rows, cand_prio, n_waiting, pressure


@partial(jax.jit, static_argnames=("k",))
def _fleet_scores(*operands, k: int):
    """`_score_terms` packed into one int32 [2k + 2] array, so the host reads
    it back once: cand_rows, then cand_prio and pressure bit-cast from
    float32 (bit-exact, the -inf padding included), then n_waiting."""
    cand_rows, cand_prio, n_waiting, pressure = _score_terms(*operands, k=k)
    bits = jax.lax.bitcast_convert_type
    return jnp.concatenate([
        cand_rows.astype(jnp.int32), bits(cand_prio, jnp.int32),
        bits(pressure, jnp.int32)[None], n_waiting.astype(jnp.int32)[None]])


def _device_operands(fleet: PackedFleet):
    # zero-copy: every column is already in the kernel's dtype (int32
    # counters, float32 divergence shadow) — no O(n) conversion pass on the
    # serving tick's hot path
    return (fleet.samples, fleet.samples_at_deploy, fleet.deployed,
            fleet.div32, fleet.resident, fleet.registered)


def fleet_scores(fleet: PackedFleet, *, min_samples: int, sw: float,
                 dw: float, k: int, span=null_span):
    """Host wrapper: returns (cand_rows, cand_prio, n_waiting, pressure)
    as numpy/python values.  Rows whose cand_prio is -inf are padding
    (fewer than k twins waiting) — callers must drop them.  The one read
    back of the packed result is a `sync` span of `span` (a
    `Tracer.span`)."""
    k = max(1, min(k, fleet.capacity))
    packed = _fleet_scores(
        *_device_operands(fleet), np.int32(min_samples), np.float32(sw),
        np.float32(dw), k=k)
    with span("sync", site="plan.scores"):
        packed = np.asarray(packed)
    floats = packed[k:2 * k + 1].view(np.float32)
    return (packed[:k], floats[:k], int(packed[-1]), float(floats[k]))


@jax.jit
def _fleet_pressure(samples, at_deploy, deployed, divergence, resident,
                    registered, min_samples, sw, dw):
    stale = (samples - at_deploy).astype(jnp.float32) / jnp.maximum(
        min_samples, 1).astype(jnp.float32)
    stale = stale + jnp.where(deployed, 0.0, 1.0)
    prio = sw * stale + dw * divergence
    ready = registered & (samples >= min_samples)
    return jnp.sum(jnp.where(ready, prio, 0.0))


def fleet_pressure(fleet: PackedFleet, *, min_samples: int, sw: float,
                   dw: float, span=null_span) -> float:
    """Aggregate refit demand as one fused device reduction — the number
    `SlotFederation.rebalance` consumes, without an O(twins) host scan."""
    pressure = _fleet_pressure(
        *_device_operands(fleet), np.int32(min_samples), np.float32(sw),
        np.float32(dw))
    with span("sync", site="rebalance.pressure"):
        return float(pressure)
