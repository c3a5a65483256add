"""Scheduler scale benchmark: planning cost from 10k to 100k tracked twins.

The serving loop's SCHEDULE stage must not become the host bottleneck the
guard rotation already removed — the planner's contract is per-tick host
cost O(budget + log n), with the O(n) scoring fused on device
(twin/packed.py).  This benchmark isolates the planner from the rest of the
loop (no rings, no refits) and drives BOTH planners over the same synthetic
fleet dynamics:

  * `bucketed` — `PackedRefitScheduler`, the server's planner: one fused
    device scoring call + PriorityBuckets winner pops;
  * `reference` — `RefitScheduler`, the O(n log n) dict-sorting oracle
    (fewer ticks; its per-plan cost is the point being retired).

Fleet dynamics per tick: every twin ingests a fixed telemetry chunk
(staleness drifts fleet-wide — the property that makes incremental host
structures useless and the fused device pass necessary), a random subset's
divergence jitters (guard folds), residents accrue residency and "deploy"
after a few ticks (watermark reset), and each planner's own plans are
applied — so slot turnover, eviction pressure, and voluntary release all
stay live across the sweep.

The acceptance gate, printed at the end: bucketed plan p50 grows <= 2x from
10k -> 100k twins.  `pressure_ms` times the federation's rebalance signal
(`pressure()`), which must also stay flat for the bucketed planner (fused
device reduction) and is O(n) host work for the reference.

Emitted to bench_out/sched_scale.csv by benchmarks/run.py
(`--only sched_scale`); `--smoke` runs tiny fleets for CI.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import print_rows, write_csv
from repro.twin.packed import PackedFleet
from repro.twin.scheduler import (PackedRefitScheduler, RefitScheduler,
                                  SchedulerConfig, TwinRecord)

SLOTS = 64
MIN_SAMPLES = 32
CHUNK = 8             # samples ingested per twin per tick
DEPLOY_AFTER = 3      # resident ticks before the synthetic "deploy"
JITTER = 256          # twins whose divergence moves per tick
WARMUP = 2            # ticks excluded from stats (jit compile lands here)


def _make_fleet(n_twins: int, seed: int) -> PackedFleet:
    """A mid-mission fleet: everyone registered and sampled past readiness,
    most deployed, divergence long-tailed so eviction pressure exists."""
    rng = np.random.default_rng(seed)
    fleet = PackedFleet(n_twins)
    fleet.twin_id[:] = np.arange(n_twins)
    fleet.registered[:] = True
    fleet.samples[:] = MIN_SAMPLES + rng.integers(0, 4 * MIN_SAMPLES,
                                                  n_twins)
    fleet.deployed[:] = rng.random(n_twins) < 0.7
    fleet.samples_at_deploy[:] = np.where(
        fleet.deployed, (fleet.samples * rng.random(n_twins)).astype(
            np.int64), 0)
    fleet.set_divergence(slice(None), rng.exponential(0.05, n_twins))
    return fleet


def _advance(fleet: PackedFleet, rng) -> None:
    """One tick of fleet dynamics, vectorized (untimed — only plan cost is
    under measurement)."""
    fleet.samples += CHUNK
    jitter = rng.integers(0, fleet.capacity, min(JITTER, fleet.capacity))
    fleet.set_divergence(jitter, np.abs(
        fleet.divergence[jitter] + rng.normal(0.0, 0.05, jitter.size)))
    res = np.nonzero(fleet.resident)[0]
    fleet.residency[res] += 1
    done = res[fleet.residency[res] >= DEPLOY_AFTER]
    fleet.samples_at_deploy[done] = fleet.samples[done]
    fleet.deployed[done] = True
    fleet.set_divergence(done, fleet.divergence[done] * 0.25)


def _apply(fleet: PackedFleet, slot_rows: np.ndarray, row_slot: dict,
           plan) -> None:
    """Apply a plan to the packed state (twin_id == row in this driver)."""
    for tid in plan.evict + plan.release:
        slot_rows[row_slot.pop(tid)] = fleet.capacity
        fleet.resident[tid] = False
        fleet.residency[tid] = 0
    for slot, tid in plan.admit:
        slot_rows[slot] = tid
        row_slot[tid] = slot
        fleet.resident[tid] = True
        fleet.residency[tid] = 0


def _fleet_to_records(fleet: PackedFleet,
                      row_slot: dict) -> dict[int, TwinRecord]:
    """Rebuild the reference planner's dict view (untimed — the O(n) dict
    materialization is the data layout the packed refactor retired, not the
    planning cost under measurement)."""
    return {row: TwinRecord(
        twin_id=row, ring_slot=row, refit_slot=row_slot.get(row),
        samples=int(fleet.samples[row]),
        samples_at_deploy=int(fleet.samples_at_deploy[row]),
        deployed=bool(fleet.deployed[row]),
        residency=int(fleet.residency[row]),
        divergence=float(fleet.divergence[row]))
        for row in range(fleet.capacity)}


def _drive(n_twins: int, planner: str, ticks: int, seed: int = 0) -> dict:
    cfg = SchedulerConfig(slots=SLOTS, min_samples=MIN_SAMPLES,
                          min_residency=2, max_residency=8)
    rng = np.random.default_rng(seed)
    fleet = _make_fleet(n_twins, seed)
    slot_rows = np.full((SLOTS,), fleet.capacity, np.int64)
    row_slot: dict[int, int] = {}
    sched = (PackedRefitScheduler(cfg) if planner == "bucketed"
             else RefitScheduler(cfg))

    plan_s: list[float] = []
    turnover = 0
    for t in range(WARMUP + ticks):
        if planner == "bucketed":
            t0 = time.perf_counter()
            plan = sched.plan(fleet, slot_rows)
            dt = time.perf_counter() - t0
        else:
            twins = _fleet_to_records(fleet, row_slot)
            t0 = time.perf_counter()
            plan = sched.plan(twins)
            dt = time.perf_counter() - t0
        if t >= WARMUP:
            plan_s.append(dt)
            turnover += len(plan.admit) + len(plan.release)
        _apply(fleet, slot_rows, row_slot, plan)
        _advance(fleet, rng)

    if planner == "bucketed":
        sched.pressure(fleet)            # warm the fused-reduction compile
        t0 = time.perf_counter()
        pressure = sched.pressure(fleet)
    else:
        twins = _fleet_to_records(fleet, row_slot)
        t0 = time.perf_counter()
        pressure = sched.pressure(twins)
    pressure_ms = (time.perf_counter() - t0) * 1e3

    q = np.quantile(np.asarray(plan_s), [0.5, 0.99]) * 1e3
    return {
        "twins": n_twins, "planner": planner, "slots": SLOTS,
        "ticks": ticks,
        "plan_p50_ms": round(float(q[0]), 3),
        "plan_p99_ms": round(float(q[1]), 3),
        "pressure_ms": round(pressure_ms, 3),
        "turnover": turnover,                 # sanity: slots actually churn
        "pressure": round(pressure, 1),
    }


def _check_flat(rows: list[dict]) -> None:
    """The acceptance gate: bucketed plan p50 within 2x across the sweep."""
    group = sorted((r for r in rows if r["planner"] == "bucketed"),
                   key=lambda r: r["twins"])
    if len(group) < 2:
        return
    lo, hi = group[0], group[-1]
    ratio = hi["plan_p50_ms"] / max(lo["plan_p50_ms"], 1e-9)
    flat = ("FLAT (O(budget + log n) holds)" if ratio <= 2.0
            else "NOT FLAT — planner scaling regression")
    print(f"[sched_scale] bucketed plan p50 {lo['twins']} -> {hi['twins']} "
          f"twins: {lo['plan_p50_ms']:.3f} -> {hi['plan_p50_ms']:.3f} "
          f"ms ({ratio:.2f}x) — {flat}")
    ref = {r["twins"]: r for r in rows if r["planner"] == "reference"}
    for r in group:
        other = ref.get(r["twins"])
        if other:
            speedup = other["plan_p50_ms"] / max(r["plan_p50_ms"], 1e-9)
            print(f"[sched_scale] {r['twins']} twins: bucketed "
                  f"{r['plan_p50_ms']:.3f} ms vs reference "
                  f"{other['plan_p50_ms']:.3f} ms ({speedup:.1f}x faster)")


def run(quick: bool = True, smoke: bool = False) -> None:
    if smoke:
        sizes, ticks, ref_ticks = [2000, 8000], 8, 4
    elif quick:
        sizes, ticks, ref_ticks = [10_000, 30_000, 100_000], 20, 4
    else:
        sizes, ticks, ref_ticks = [10_000, 30_000, 100_000, 300_000], 40, 6
    rows = [_drive(n, "bucketed", ticks) for n in sizes]
    rows += [_drive(n, "reference", ref_ticks) for n in sizes]
    print_rows("schedule planning at scale: fused device scoring vs "
               "dict sorting", rows)
    _check_flat(rows)
    path = write_csv("sched_scale.csv", rows)
    print(f"[sched_scale] wrote {path}")


if __name__ == "__main__":
    run()
