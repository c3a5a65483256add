"""Federated serving benchmark: multi-process workers vs one process.

Sweeps `FederatedTwinServer` (coordinator + N worker subprocesses, wire
messages per tick) against the in-process `ShardedTwinServer` at the SAME
twin count and shard count — both driven through the `TwinService` protocol
with identical call sites, so the delta is the process split itself.  Rows
land in bench_out/online_federated.csv (`--only online_federated`).  The
claims under test:

  * refresh throughput scales with worker processes: send-all-then-collect
    ticks run workers CONCURRENTLY, so at 10k twins / 4 workers the
    federated fleet must reach >= 3x the single-process refresh rate —
    ON A HOST WITH THE CORES TO SHOW IT (>= workers + 1).  The verdict
    printed at the end is honest about this: on fewer cores the workers
    time-slice one core and the measured "speedup" is IPC overhead, not
    the architecture, and is reported as HOST-LIMITED rather than FAIL.
  * the ingestion front door is affordable: one sweep point ingests over
    the length-prefixed TCP door (`ingest=tcp`) instead of in-process
    calls (`ingest=direct`) — same protocol batch, one socket hop added.
  * federation survives a worker kill: the `kill_restart` scenario
    SIGKILLs a worker mid-measurement and reports recovery ticks,
    journal-replay accounting (lost_samples must be 0 — every routed
    sample is journaled supervisor-side BEFORE the worker sees it), and
    whether slot grants migrated to the survivors while the worker was
    down.  tests/test_federation.py gates the same semantics.

Workers serve with sync in-worker ingest (the pipe already decouples
producers from the serving loop), and the in-process baseline runs sync
ingest too — the comparison is contention-free by construction on any
host.  Checkpoint/journal machinery is OFF in the throughput rows and ON
in the kill row (its cost is benchmarked separately in online_chaos.csv).

The process running this module is the coordinator, and a chip belongs to
one process: it keeps its own JAX on the CPU (telemetry is generated there)
and runs the in-process baseline in a spawned process of its own, so the
workers and the baseline each get the chips.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import shutil
import tempfile
from concurrent.futures import ProcessPoolExecutor

import jax
import numpy as np

from benchmarks.common import print_rows, write_csv
from repro.core.merinda import MerindaConfig
from repro.systems.f8_crusader import F8Crusader
from repro.systems.simulate import simulate_batch
from repro.compile_cache import enable_compile_cache
from repro.twin import (ChaosConfig, FederatedTwinConfig, FederatedTwinServer,
                        FrontDoorClient, GuardConfig, RecoveryConfig,
                        ShardedTwinConfig, ShardedTwinServer, TwinServerConfig)
from repro.twin.federation import pin_host_jax_to_cpu

CHUNK = 8           # telemetry samples per twin per tick
GUARD_BUDGET = 128  # per-worker rotating guard subset
WARMUP = 18         # jit compile + slot fill + first deploys, per worker
SPEEDUP_TARGET = 3.0


def _shard_cfg(system, n_twins: int, workers: int, *, seed: int,
               deadline_s: float = 1.0) -> TwinServerConfig:
    per_shard = -(-n_twins // workers)
    return TwinServerConfig(
        merinda=MerindaConfig(n=system.spec.n, m=system.spec.m, order=3,
                              dt=system.spec.dt, hidden=16, head_hidden=16,
                              n_active=24),
        max_twins=per_shard, refit_slots=8,
        capacity=64, window=16, stride=8, windows_per_twin=4,
        steps_per_tick=1, deploy_after=8, min_residency=4, max_residency=16,
        guard=GuardConfig(window=24),
        guard_budget=min(GUARD_BUDGET, per_shard),
        deadline_s=deadline_s, async_ingest=False, seed=seed)


def _row(scenario, mode, n_twins, workers, ingest, s, deadline_s) -> dict:
    return {
        "scenario": scenario, "mode": mode, "twins": n_twins,
        "workers": workers, "ingest": ingest, "ticks": s["ticks"],
        "deadline_s": deadline_s,
        "p50_ms": round(s["p50_ms"], 2), "p99_ms": round(s["p99_ms"], 2),
        "max_ms": round(s["max_ms"], 2), "violations": s["violations"],
        "twin_refreshes_per_s": round(s["twin_refreshes_per_s"], 1),
        "speedup": "n/a",
        "shard_deaths": 0, "recovery_ticks": 0,
        "replayed_samples": 0, "lost_samples": 0, "grants_migrated": "n/a",
    }


def _telemetry(n_twins: int, ticks: int, seed: int):
    """(ys, us) host arrays for WARMUP + ticks serving ticks."""
    sim = simulate_batch(F8Crusader(), jax.random.PRNGKey(seed),
                         batch=n_twins, horizon=CHUNK * (WARMUP + ticks) + 1,
                         noise_std=0.002)
    return np.asarray(sim.ys_noisy), np.asarray(sim.us)


def _serve(mode: str, n_twins: int, workers: int, ticks: int, *,
           tcp: bool = False, seed: int = 0) -> dict:
    """One throughput run: identical protocol call sites for both modes.
    The in-process mode runs in a process of its own (see module doc)."""
    ys, us = _telemetry(n_twins, ticks, seed)
    if mode == "inproc":
        with ProcessPoolExecutor(
                max_workers=1, mp_context=mp.get_context("spawn")) as ex:
            return ex.submit(_serve_telemetry, mode, ys, us, n_twins,
                             workers, ticks, tcp=tcp, seed=seed).result()
    return _serve_telemetry(mode, ys, us, n_twins, workers, ticks, tcp=tcp,
                            seed=seed)


def _serve_telemetry(mode: str, ys, us, n_twins: int, workers: int,
                     ticks: int, *, tcp: bool = False, seed: int = 0) -> dict:
    enable_compile_cache()     # a spawned baseline starts without it
    system = F8Crusader()
    scfg = _shard_cfg(system, n_twins, workers, seed=seed)
    if mode == "federated":
        srv = FederatedTwinServer(FederatedTwinConfig.uniform(
            scfg, workers, rebalance_every=4, front_door=tcp))
    else:
        srv = ShardedTwinServer(ShardedTwinConfig.uniform(
            scfg, workers, rebalance_every=4))
    door = FrontDoorClient(srv.front_address) if tcp else None
    sink = door if door is not None else srv
    try:
        theta0 = np.asarray(system.true_theta(scfg.merinda.library))
        srv.deploy_many(list(range(n_twins)), theta0)
        for t in range(WARMUP + ticks):
            lo = t * CHUNK
            sink.ingest_many([(i, ys[i, lo:lo + CHUNK], us[i, lo:lo + CHUNK])
                              for i in range(n_twins)])
            if t < WARMUP:
                srv.drain()
            srv.tick()
            if t == WARMUP - 1:
                srv.reset_latency_stats()
        srv.drain()
        return _row("serve", mode, n_twins, workers,
                    "tcp" if tcp else "direct", srv.latency_summary(),
                    scfg.deadline_s)
    finally:
        if door is not None:
            door.close()
        srv.close()


def _serve_kill(n_twins: int, workers: int, ticks: int, *,
                seed: int = 0) -> dict:
    """kill_restart: SIGKILL one worker a third into the measured region,
    supervised restart after 1 tick, journal-tail replay.  Deadline 5 s so
    the restore tick (process boot + compile) is reported, not flaky."""
    system = F8Crusader()
    ys, us = _telemetry(n_twins, ticks, seed)
    scfg = _shard_cfg(system, n_twins, workers, seed=seed, deadline_s=5.0)
    victim = workers - 1
    kill_tick = WARMUP + max(2, ticks // 3)
    ckpt_dir = tempfile.mkdtemp(prefix="twin_fed_ckpt_")
    # grant migration is only OBSERVABLE under scarcity: at the default
    # budget (sum of pools) every worker sits at its pool cap, so a death
    # just revokes the victim's grant.  Serve half the aggregate capacity
    # and the victim's share visibly flows to the survivors while it is
    # down, then back on restart.
    total_slots = max(workers, (workers * scfg.refit_slots) // 2)
    cfg = FederatedTwinConfig.uniform(
        scfg, workers, rebalance_every=4, total_slots=total_slots,
        recovery=RecoveryConfig(ckpt_dir=ckpt_dir, ckpt_every=4,
                                restart_delay_ticks=1),
        chaos=ChaosConfig(kill_shard=victim, kill_at_tick=kill_tick))
    srv = FederatedTwinServer(cfg)
    try:
        theta0 = np.asarray(system.true_theta(scfg.merinda.library))
        srv.deploy_many(list(range(n_twins)), theta0)
        reports = []
        for t in range(WARMUP + ticks):
            lo = t * CHUNK
            srv.ingest_many([(i, ys[i, lo:lo + CHUNK], us[i, lo:lo + CHUNK])
                             for i in range(n_twins)])
            if t < WARMUP:
                srv.drain()
            rep = srv.tick()
            if t >= WARMUP:
                reports.append(rep)
            if t == WARMUP - 1:
                srv.reset_latency_stats()
        srv.drain()
        pre = next((r.grants for r in reports if r.dead_shards == 0),
                   [0] * workers)
        migrated = any(
            r.dead_shards > 0 and r.grants[victim] == 0
            and sum(r.grants) == total_slots
            and any(g > p for i, (g, p) in enumerate(zip(r.grants, pre))
                    if i != victim)
            for r in reports)
        restarted = [x for r in reports for x in r.restarted]
        row = _row("kill_restart", "federated", n_twins, workers, "direct",
                   srv.latency_summary(), scfg.deadline_s)
        row.update({
            "shard_deaths": len(restarted),
            "recovery_ticks": sum(x["down_ticks"] for x in restarted),
            "replayed_samples": sum(x["replayed"] for x in restarted),
            "lost_samples": sum(x["lost"] for x in restarted),
            "grants_migrated": "yes" if migrated else "NO",
        })
        return row
    finally:
        srv.close()
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def _speedup_verdicts(rows: list[dict]) -> None:
    """Fill `speedup` on federated serve rows against the in-process row at
    the same (twins, workers) and print the throughput verdict — honest
    about host cores: the >= 3x contract needs >= workers + 1 cores."""
    inproc = {(r["twins"], r["workers"]): r for r in rows
              if r["mode"] == "inproc"}
    cores = os.cpu_count() or 1
    for r in rows:
        if r["mode"] != "federated" or r["scenario"] != "serve":
            continue
        base = inproc.get((r["twins"], r["workers"]))
        if base is None:
            continue
        ratio = (r["twin_refreshes_per_s"]
                 / max(base["twin_refreshes_per_s"], 1e-9))
        r["speedup"] = round(ratio, 2)
        need = r["workers"] + 1
        if cores < need:
            verdict = (f"HOST-LIMITED ({cores} core(s) < {need} needed: "
                       f"workers time-slice one core, so this measures IPC "
                       f"overhead, not concurrency — rerun on >= {need} "
                       f"cores for the >= {SPEEDUP_TARGET:.0f}x contract)")
        elif ratio >= SPEEDUP_TARGET:
            verdict = f">= {SPEEDUP_TARGET:.0f}x contract holds"
        else:
            verdict = f"BELOW the {SPEEDUP_TARGET:.0f}x contract"
        print(f"[online_federated] {r['twins']} twins / {r['workers']} "
              f"workers [{r['ingest']}]: {base['twin_refreshes_per_s']:.1f} "
              f"-> {r['twin_refreshes_per_s']:.1f} refreshes/s "
              f"({ratio:.2f}x single-process) — {verdict}")


def _kill_verdict(row: dict) -> None:
    ok = (row["lost_samples"] == 0 and row["shard_deaths"] >= 1
          and row["grants_migrated"] == "yes")
    print(f"[online_federated] kill_restart @ {row['twins']} twins / "
          f"{row['workers']} workers: {row['shard_deaths']} death(s), "
          f"{row['recovery_ticks']} recovery tick(s), "
          f"{row['replayed_samples']} samples replayed, "
          f"{row['lost_samples']} lost, grants migrated: "
          f"{row['grants_migrated']} — "
          f"{'crash-safe' if ok else 'RECOVERY CONTRACT BROKEN'}")


def run(quick: bool = True, smoke: bool = False) -> None:
    pin_host_jax_to_cpu()
    if smoke:
        sweeps = [("inproc", 256, 2, 6, False), ("federated", 256, 2, 6,
                                                 False)]
        kill = (256, 2, 8)
    elif quick:
        sweeps = [("inproc", 10000, 4, 10, False),
                  ("federated", 10000, 4, 10, False),
                  ("federated", 1000, 2, 10, True)]
        kill = (1000, 4, 12)
    else:
        sweeps = [("inproc", 10000, 4, 16, False),
                  ("federated", 10000, 4, 16, False),
                  ("inproc", 100000, 8, 10, False),
                  ("federated", 100000, 8, 10, False),
                  ("federated", 10000, 4, 16, True)]
        kill = (10000, 4, 16)
    rows = [_serve(m, n, w, t, tcp=tcp) for m, n, w, t, tcp in sweeps]
    rows.append(_serve_kill(*kill))
    _speedup_verdicts(rows)
    _kill_verdict(rows[-1])
    print_rows("federated serving: worker processes vs in-process shards, "
               "TCP front door, kill+restart", rows)
    path = write_csv("online_federated.csv", rows)
    print(f"[online_federated] wrote {path}")


if __name__ == "__main__":
    run()
