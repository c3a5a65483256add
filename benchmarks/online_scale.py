"""Scale benchmark: sharded serving from 64 to 10k tracked objects.

Sweeps the `ShardedTwinServer` over fleet size x shard count with a FIXED
per-shard guard budget — async (`BackgroundPump`) ingestion by default, plus
sync-ingest twin rows (the `ingest` CSV column) that isolate the 1-core
pump-contention artifact from real stage-cost regressions — and reports
per-tick
latency (p50/p99/max vs the 1 s refresh deadline), twin refreshes/s, and the
per-stage cost breakdown.  The claims under test:

  * the sharded architecture keeps the serving tick inside the mission
    deadline as the tracked fleet grows 64 -> 10k (shards absorb the load);
  * guard cost per tick is O(budget), not O(twins): at fixed shards and
    budget, guard_ms must stay flat (within 2x) from 1k -> 10k twins — the
    `GuardRotation` contract, checked and printed at the end;
  * observability is affordable at full scale: the LARGEST sweep re-runs
    with span tracing enabled (every tick sampled) and reports the p50
    overhead in the `trace_overhead_pct` column — the obs-layer contract
    is < 5%.  The traced run also emits the operator artifacts:
    bench_out/trace_online_scale.json (Perfetto-loadable Chrome trace with
    per-shard tick/stage spans), bench_out/metrics_online_scale.prom
    (Prometheus text exposition incl. per-shard stage histograms), and
    bench_out/metrics_online_scale.json (registry snapshot).

All latency/stage columns come from the servers' obs metrics registry
(`latency_summary`/`stage_summary` are registry-backed) — benchmarks and
production dashboards read the same numbers.  Emitted to
bench_out/online_scale.csv by benchmarks/run.py (`--only online_scale`);
`--smoke` runs a tiny sweep for CI.

`--chaos` switches to the CRASH-SAFETY table (bench_out/online_chaos.csv):
a checkpoint-off/on pair proving the on-tick snapshot cost is <= 5% of tick
p50 (same contract tracing carries), a kill-one-shard run reporting recovery
ticks + journal-replay accounting, and an injected-straggler run showing the
degradation ladder shedding with zero deadline violations.
"""
from __future__ import annotations

import shutil
import tempfile

import jax
import numpy as np

from benchmarks.common import OUT_DIR, print_rows, write_csv
from repro.core.merinda import MerindaConfig
from repro.obs import SnapshotWriter, Tracer
from repro.systems.f8_crusader import F8Crusader
from repro.systems.simulate import simulate_batch
from repro.twin.monitor import GuardConfig
from repro.twin.recovery import (ChaosConfig, DegradationConfig,
                                 RecoveryConfig)
from repro.twin.server import TwinServerConfig
from repro.twin.sharded import ShardedTwinConfig, ShardedTwinServer

CHUNK = 8          # telemetry samples per twin per tick
GUARD_BUDGET = 128 # per-shard rotating guard subset (fixed across the sweep)
WARMUP = 18        # ticks excluded from stats: jit compile, slot fill, and
                   # the first deploy/promote compilations all land in warmup


def scale_config(system, n_twins: int, shards: int, *,
                 guard_budget: int = GUARD_BUDGET, seed: int = 0,
                 use_pallas: bool = False, **kw) -> TwinServerConfig:
    """One shard of the `online_scale` deployment (hidden 16, window 16,
    guard window 24, budgeted guard); `kw` overrides serving fields.  Also
    what chip_smoke.py phase (b) serves."""
    per_shard = -(-n_twins // shards)
    return TwinServerConfig(
        merinda=MerindaConfig(n=system.spec.n, m=system.spec.m, order=3,
                              dt=system.spec.dt, hidden=16, head_hidden=16,
                              n_active=24, use_pallas=use_pallas),
        max_twins=per_shard, refit_slots=8,
        capacity=64, window=16, stride=8, windows_per_twin=4,
        steps_per_tick=1, deploy_after=8, min_residency=4, max_residency=16,
        guard=GuardConfig(window=24),
        guard_budget=min(guard_budget, per_shard), seed=seed, **kw)


def _serve_scale(n_twins: int, shards: int, ticks: int, *,
                 guard_budget: int = GUARD_BUDGET, seed: int = 0,
                 trace: bool = False, sync: bool = False) -> dict:
    system = F8Crusader()
    horizon = CHUNK * (WARMUP + ticks) + 1
    sim = simulate_batch(system, jax.random.PRNGKey(seed), batch=n_twins,
                         horizon=horizon, noise_std=0.002)
    ys, us = np.asarray(sim.ys_noisy), np.asarray(sim.us)

    scfg = scale_config(system, n_twins, shards, guard_budget=guard_budget,
                        seed=seed, async_ingest=not sync)
    tracer = Tracer(sample_every=1) if trace else None
    srv = ShardedTwinServer(ShardedTwinConfig.uniform(
        scfg, shards, rebalance_every=4), tracer=tracer)
    try:
        # warm start: every twin serves the offline-recovered model from tick
        # 1 (broadcast deploy), so the guard is active across the whole store
        theta0 = system.true_theta(srv.shards[0].fleet.model.lib)
        srv.deploy_many(list(range(n_twins)), theta0)

        for t in range(WARMUP + ticks):
            lo = t * CHUNK
            for i in range(n_twins):
                srv.ingest(i, ys[i, lo:lo + CHUNK], us[i, lo:lo + CHUNK])
            if t < WARMUP:
                # bootstrap is paced faster than any real sensor stream:
                # barrier the async flush so readiness, admissions, and every
                # jit compile land before the stats reset; measured ticks run
                # free (ingest prep overlapped on the pump thread)
                srv.drain()
            srv.tick()
            if t == WARMUP - 1:
                srv.reset_latency_stats()
        srv.drain()
        s = srv.latency_summary()
        st = srv.stage_summary()
        deployed = sum(r.deployed for shard in srv.shards
                       for r in shard.twins.values())
        if trace:
            # the operator artifact set: Perfetto trace + Prometheus
            # exposition + JSON snapshot, from the live run's registry
            OUT_DIR.mkdir(parents=True, exist_ok=True)
            tracer.write(OUT_DIR / "trace_online_scale.json")
            (OUT_DIR / "metrics_online_scale.prom").write_text(
                srv.metrics.expose())
            SnapshotWriter(srv.metrics,
                           OUT_DIR / "metrics_online_scale.json",
                           tracer=tracer).write()
            print(f"[online_scale] traced run: {len(tracer)} span events "
                  f"({tracer.dropped_events} dropped) -> "
                  f"{OUT_DIR / 'trace_online_scale.json'}")
        return {
            "twins": n_twins, "shards": shards,
            "slots": sum(x.cfg.refit_slots for x in srv.shards),
            "guard_budget": scfg.guard_budget,
            # ingest mode is part of the row identity: on hosts with fewer
            # cores than pump threads, "pump" rows carry background flush
            # work time-sliced into the stage columns — "sync" rows are the
            # contention-free reference (see _check_guard_flat)
            "ingest": "sync" if sync else "pump",
            "tracing": "on" if trace else "off", "ticks": s["ticks"],
            "p50_ms": round(s["p50_ms"], 2), "p99_ms": round(s["p99_ms"], 2),
            "max_ms": round(s["max_ms"], 2),
            "deadline_s": s["deadline_s"], "violations": s["violations"],
            "twin_refreshes_per_s": round(s["twin_refreshes_per_s"], 1),
            "flush_ms": round(st["flush_ms"], 2),
            "guard_ms": round(st["guard_ms"], 2),
            "schedule_ms": round(st["schedule_ms"], 2),
            "refit_ms": round(st["refit_ms"], 2),
            "dropped_samples": s["dropped_samples"],
            "flush_overflows": s["flush_overflows"],
            "trace_overhead_pct": "n/a",
            "deployed": deployed,
        }
    finally:
        srv.close()


def _check_guard_flat(rows: list[dict]) -> None:
    """The O(budget) contract: guard_ms within 2x from 1k -> 10k twins at
    fixed shard count and budget, checked PER INGEST MODE.

    Stage columns are WALL time between tick timestamps.  On hosts with
    fewer cores than pump threads, async ("pump") flush preparation
    time-slices into the guard/refit windows and inflates their attribution
    with work that scales with twins — a known 1-core contention artifact
    (PR 6's NOT-FLAT verdict).  The "sync" rows exist precisely to separate
    that artifact from a real guard regression: the contract verdict that
    matters is the sync one."""
    by_group: dict[tuple, list[dict]] = {}
    for r in rows:
        by_group.setdefault((r["shards"], r["ingest"]), []).append(r)
    for (shards, ingest), group in sorted(by_group.items()):
        group = [r for r in group if r["twins"] >= 1000]
        if len(group) < 2:
            continue
        lo = min(group, key=lambda r: r["twins"])
        hi = max(group, key=lambda r: r["twins"])
        ratio = hi["guard_ms"] / max(lo["guard_ms"], 1e-9)
        flat = "FLAT (O(budget) holds)" if ratio < 2.0 else (
            "NOT FLAT (pump contention artifact on starved hosts — "
            "trust the sync row)" if ingest == "pump" else "NOT FLAT")
        print(f"[online_scale] guard cost {lo['twins']} -> {hi['twins']} "
              f"twins @ {shards} shards [{ingest}]: {lo['guard_ms']:.2f} -> "
              f"{hi['guard_ms']:.2f} ms/tick ({ratio:.2f}x) — {flat}")


def _tracing_overhead(rows: list[dict], off: dict, on: dict) -> None:
    """Fill `trace_overhead_pct` on the traced row and report against the
    obs-layer contract (p50 within 5% of the tracing-off run)."""
    pct = (on["p50_ms"] - off["p50_ms"]) / max(off["p50_ms"], 1e-9) * 100.0
    on["trace_overhead_pct"] = round(pct, 2)
    verdict = "within the 5% budget" if pct <= 5.0 else "OVER the 5% budget"
    print(f"[online_scale] tracing overhead @ {on['twins']} twins / "
          f"{on['shards']} shards: p50 {off['p50_ms']:.2f} -> "
          f"{on['p50_ms']:.2f} ms ({pct:+.2f}%) — {verdict}")


# ------------------------------------------------------------------------- #
# --chaos mode: crash-safety + degradation cost, bench_out/online_chaos.csv
# ------------------------------------------------------------------------- #
def _serve_chaos(scenario: str, n_twins: int, shards: int, ticks: int, *,
                 ckpt_every: int | None = None,
                 chaos: ChaosConfig | None = None,
                 degradation: bool = False,
                 deadline_s: float = 1.0, seed: int = 0) -> dict:
    """One fault-injected serving run; returns an online_chaos.csv row.

    Sync ingest (the contention-free reference mode) so the recovery
    columns are deterministic; measured ticks start after warmup, with the
    kill/slow schedules placed INSIDE the measured region."""
    system = F8Crusader()
    horizon = CHUNK * (WARMUP + ticks) + 1
    sim = simulate_batch(system, jax.random.PRNGKey(seed), batch=n_twins,
                         horizon=horizon, noise_std=0.002)
    ys, us = np.asarray(sim.ys_noisy), np.asarray(sim.us)

    scfg = scale_config(
        system, n_twins, shards, seed=seed, deadline_s=deadline_s,
        degradation=DegradationConfig(enabled=degradation, hold_ticks=1,
                                      alpha=0.9),
        async_ingest=False)
    ckpt_dir = tempfile.mkdtemp(prefix="twin_chaos_ckpt_")
    recovery = (RecoveryConfig(ckpt_dir=ckpt_dir, ckpt_every=ckpt_every)
                if ckpt_every is not None else None)
    srv = ShardedTwinServer(ShardedTwinConfig.uniform(
        scfg, shards, rebalance_every=4, recovery=recovery, chaos=chaos))
    try:
        theta0 = system.true_theta(srv.shards[0].fleet.model.lib)
        srv.deploy_many(list(range(n_twins)), theta0)
        reports = []
        for t in range(WARMUP + ticks):
            lo = t * CHUNK
            for i in range(n_twins):
                srv.ingest(i, ys[i, lo:lo + CHUNK], us[i, lo:lo + CHUNK])
            rep = srv.tick()
            if t >= WARMUP:
                reports.append(rep)
            if t == WARMUP - 1:
                srv.reset_latency_stats()
        srv.drain()
        s = srv.latency_summary()
        restarted = [r for rep in reports for r in rep.restarted]
        return {
            "scenario": scenario, "twins": n_twins, "shards": shards,
            "ticks": s["ticks"],
            "ckpt_every": "off" if ckpt_every is None else ckpt_every,
            "deadline_s": deadline_s,
            "p50_ms": round(s["p50_ms"], 2), "p99_ms": round(s["p99_ms"], 2),
            "max_ms": round(s["max_ms"], 2), "violations": s["violations"],
            "degraded_ticks": sum(r.degraded_level > 0 for r in reports),
            "recovery_ticks": sum(r["down_ticks"] for r in restarted),
            "replayed_samples": sum(r["replayed"] for r in restarted),
            "lost_samples": sum(r["lost"] for r in restarted),
            "shard_deaths": len(restarted),
            "ckpt_overhead_pct": "n/a",
        }
    finally:
        srv.close()
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def _ckpt_overhead(off: dict, on: dict) -> None:
    """Fill `ckpt_overhead_pct` on the checkpointing row and report against
    the crash-safety contract: p50 within 5% of the checkpoint-off run
    (same budget tracing gets — the snapshot is on-tick, the write is not).
    """
    pct = (on["p50_ms"] - off["p50_ms"]) / max(off["p50_ms"], 1e-9) * 100.0
    on["ckpt_overhead_pct"] = round(pct, 2)
    if on["twins"] >= 1000:
        verdict = ("within the 5% budget" if pct <= 5.0
                   else "OVER the 5% budget")
    else:
        # tiny smoke fleets have ~20 ms p50: the few-ms background-writer
        # contention on a starved host dominates the ratio.  The contract
        # is evaluated at fleet scale (>= 1k twins, quick/full runs).
        verdict = "informational at smoke size (contract is >= 1k twins)"
    print(f"[online_chaos] checkpoint overhead @ {on['twins']} twins / "
          f"{on['shards']} shards (every {on['ckpt_every']} ticks): p50 "
          f"{off['p50_ms']:.2f} -> {on['p50_ms']:.2f} ms ({pct:+.2f}%) — "
          f"{verdict}")


def run_chaos(quick: bool = True, smoke: bool = False) -> None:
    """`--chaos`: the crash-safety cost/recovery table.

    Rows: a checkpoint-off/-on pair at the largest fleet (the <= 5%
    on-tick overhead contract), a kill-one-shard run (recovery +
    replay accounting; deadline 5.0 s so the restore tick itself is
    not a flaky violation), and an injected-straggler run with the
    degradation ladder enabled (sheds before the deadline breaks:
    violations must stay 0 while degraded_ticks > 0)."""
    if smoke:
        size, kill_size, ticks = (128, 2), (128, 2), 8
    elif quick:
        size, kill_size, ticks = (10000, 4), (1000, 4), 12
    else:
        size, kill_size, ticks = (10000, 4), (10000, 4), 24
    kill_tick = WARMUP + ticks // 3 + 1
    slow_lo, slow_hi = WARMUP + 2, WARMUP + 2 + max(3, ticks // 4)
    rows = [
        _serve_chaos("baseline", *size, ticks),
        _serve_chaos("checkpoint", *size, ticks, ckpt_every=8),
        _serve_chaos("kill_shard", *kill_size, ticks, ckpt_every=4,
                     deadline_s=5.0,
                     chaos=ChaosConfig(kill_shard=kill_size[1] - 1,
                                       kill_at_tick=kill_tick)),
        # deadline 2 s, stall 1.7 s: pressure 0.85 > high_water drives the
        # ladder, while organic tick cost (< 300 ms at every sweep size)
        # keeps the stalled ticks under the deadline — the scenario proves
        # shedding engages BEFORE violations happen, so violations stays 0
        _serve_chaos("degrade", *kill_size, ticks, degradation=True,
                     deadline_s=2.0,
                     chaos=ChaosConfig(slow_shard=0, slow_s=1.7,
                                       slow_from_tick=slow_lo,
                                       slow_until_tick=slow_hi)),
    ]
    _ckpt_overhead(rows[0], rows[1])
    kill = rows[2]
    print(f"[online_chaos] kill_shard: {kill['shard_deaths']} death(s), "
          f"{kill['recovery_ticks']} recovery tick(s), "
          f"{kill['replayed_samples']} samples replayed, "
          f"{kill['lost_samples']} lost")
    deg = rows[3]
    shed = ("shed under pressure, 0 violations" if deg["violations"] == 0
            else f"{deg['violations']} VIOLATIONS despite shedding")
    print(f"[online_chaos] degrade: {deg['degraded_ticks']} degraded "
          f"tick(s) — {shed}")
    print_rows("crash-safe serving: checkpoint overhead, failover, "
               "degradation", rows)
    path = write_csv("online_chaos.csv", rows)
    print(f"[online_chaos] wrote {path}")


def run(quick: bool = True, smoke: bool = False,
        chaos: bool = False) -> None:
    if chaos:
        run_chaos(quick=quick, smoke=smoke)
        return
    # sweep entries: (twins, shards, ticks, sync_ingest).  Each pump sweep
    # point >= 1k twins gets a sync twin row so the guard-flatness verdict
    # can separate pump contention from a real regression (see
    # _check_guard_flat).
    if smoke:
        sweeps = [(64, 1, 6, False), (128, 2, 6, False), (128, 2, 6, True)]
    elif quick:
        sweeps = [(64, 1, 12, False), (1000, 1, 12, False),
                  (1000, 2, 12, False), (1000, 4, 12, False),
                  (10000, 4, 12, False),
                  (1000, 4, 12, True), (10000, 4, 12, True)]
    else:
        sweeps = [(64, 1, 24, False), (1000, 1, 24, False),
                  (1000, 2, 24, False), (1000, 4, 24, False),
                  (10000, 4, 24, False), (10000, 2, 24, False),
                  (1000, 4, 24, True), (10000, 4, 24, True)]
    rows = [_serve_scale(n, s, t, sync=sy) for n, s, t, sy in sweeps]
    # re-run the LARGEST pump config with full-sampling tracing on: the
    # overhead column is the proof tracing is affordable at scale, and the
    # traced run writes the Perfetto/Prometheus artifacts next to the CSV
    big = max((i for i in range(len(sweeps)) if not sweeps[i][3]),
              key=lambda i: (sweeps[i][0], sweeps[i][1]))
    n, s, t, _ = sweeps[big]
    traced = _serve_scale(n, s, t, trace=True)
    _tracing_overhead(rows, rows[big], traced)
    rows.append(traced)
    print_rows("online serving at scale: sharded fleets, async ingest, "
               "budgeted guard", rows)
    _check_guard_flat([r for r in rows if r["tracing"] == "off"])
    path = write_csv("online_scale.csv", rows)
    print(f"[online_scale] wrote {path}")


if __name__ == "__main__":
    run()
