"""Online serving benchmark: sustained refresh latency + twin throughput.

Streams simulated F-8 telemetry through `TwinServer` and measures the
steady-state serving tick against the paper's mission budget (refresh every
deployed twin in <= 1 s — 5x under the 5 s human-pilot reaction time).

Reported per fleet size:
  p50/p99/max per-tick refresh latency (ms), deadline violations, and
  twin-refreshes-per-second (refit slots advanced per wall second) — the
  number every scaling PR (sharded fleets, async ingestion, multi-backend)
  must move.  Emitted to bench_out/online.csv by benchmarks/run.py
  (`--only online`).
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import print_rows, write_csv
from repro.core.merinda import MerindaConfig
from repro.systems.f8_crusader import F8Crusader
from repro.systems.simulate import simulate_batch
from repro.twin.monitor import GuardConfig
from repro.twin.server import TwinServer, TwinServerConfig

CHUNK = 8          # telemetry samples per twin per tick
WARMUP = 18        # ticks excluded from stats: jit compile, slot fill, and
                   # the first deploy/guard activations all land in warmup


def online_config(system, n_twins: int, refit_slots: int, *, seed: int = 0,
                  use_pallas: bool = False) -> TwinServerConfig:
    """The `online` deployment: one TwinServer, hidden 32, window 24,
    guard window 32 (also what chip_smoke.py phase (a) serves)."""
    return TwinServerConfig(
        merinda=MerindaConfig(n=system.spec.n, m=system.spec.m, order=3,
                              dt=system.spec.dt, hidden=32, head_hidden=32,
                              n_active=24, use_pallas=use_pallas),
        max_twins=n_twins, refit_slots=refit_slots,
        capacity=256, window=24, stride=8, windows_per_twin=8,
        steps_per_tick=2, deploy_after=8, min_residency=4, max_residency=16,
        guard=GuardConfig(window=32), seed=seed)


def _serve(n_twins: int, refit_slots: int, ticks: int, seed: int = 0,
           use_pallas: bool = False) -> dict:
    system = F8Crusader()
    horizon = CHUNK * (WARMUP + ticks) + 1
    trace = simulate_batch(system, jax.random.PRNGKey(seed), batch=n_twins,
                           horizon=horizon, noise_std=0.002)
    ys, us = np.asarray(trace.ys_noisy), np.asarray(trace.us)

    srv = TwinServer(online_config(system, n_twins, refit_slots, seed=seed,
                                   use_pallas=use_pallas))

    for t in range(WARMUP + ticks):
        lo = t * CHUNK
        for i in range(n_twins):
            srv.ingest(i, ys[i, lo:lo + CHUNK], us[i, lo:lo + CHUNK])
        srv.tick()
        if t == WARMUP - 1:
            srv.reset_latency_stats()
    # latency_summary/stage_summary read the server's obs metrics registry —
    # the SAME histograms/counters `srv.metrics.expose()` scrapes in
    # production, so the CSV and an operator dashboard cannot disagree
    s = srv.latency_summary()
    st = srv.stage_summary()
    deployed = sum(r.deployed for r in srv.twins.values())
    return {
        "twins": n_twins, "refit_slots": refit_slots,
        "backend": "pallas" if use_pallas else "reference",
        "ticks": s["ticks"],
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
        "deployed": deployed,
    }


def run(quick: bool = True, smoke: bool = False,
        use_pallas: bool = False) -> None:
    """`use_pallas=True` serves the same sweep on the Pallas hot path
    (compiled on TPU, interpreter mode elsewhere — `--pallas` in run.py);
    tick-level output parity with the reference backend is CI-gated in
    tests/test_hotpath_parity.py."""
    if smoke:
        sweeps = [(16, 4, 8)]          # CI smoke: exercise the loop, not perf
    else:
        sweeps = ([(64, 8, 30)] if quick
                  else [(64, 8, 60), (128, 8, 60), (256, 16, 60)])
    rows = [_serve(n, s, t, use_pallas=use_pallas) for n, s, t in sweeps]
    print_rows("online serving: sustained refresh latency (1 s deadline)",
               rows)
    path = write_csv("online_pallas.csv" if use_pallas else "online.csv",
                     rows)
    print(f"[online] wrote {path}")


if __name__ == "__main__":
    run()
