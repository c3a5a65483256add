"""Sharded serving of a heterogeneous 1k+ twin fleet, end to end.

    PYTHONPATH=src python examples/sharded_fleet.py [--per-family 384]

Three FAMILIES of tracked objects live in one `ShardedTwinServer`, one shard
per family — each shard owns its own telemetry rings, refit-slot pool, theta
store, and scheduler, with its own model configuration (state dims differ!):

  shard 0: F-8 Crusader airframes   (n=3, m=1, order 3, dt 10 ms)
  shard 1: Van der Pol oscillators  (n=2, m=1, order 3, dt 20 ms)
  shard 2: Lotka-Volterra systems   (n=2, m=0, order 2, dt 20 ms)

Every twin warm-starts from its family's offline-recovered model.  A subset
of F-8s flies with DAMAGED elevators (their true dynamics differ from the
deployed model): the budgeted guard rotation flags them, the F-8 shard's
aggregate pressure rises, and the slot FEDERATION migrates refit grants from
the quiet families toward the emergency — watch the `grants` column move.

Ingestion runs async (background staging flush per shard) and the guard
scores a rotating budget per tick, so the tick cost is bounded regardless of
fleet size — the same architecture benchmarks/online_scale.py pushes to 10k.
"""
import argparse

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.merinda import MerindaConfig
from repro.systems.f8_crusader import (DamagedF8, F8Crusader,
                                       trim_neighborhood)
from repro.systems.lotka_volterra import LotkaVolterra
from repro.systems.simulate import simulate_batch
from repro.systems.van_der_pol import VanDerPol
from repro.twin.monitor import GuardConfig
from repro.twin.server import TwinServerConfig
from repro.twin.sharded import ShardedTwinConfig, ShardedTwinServer

CHUNK = 8   # telemetry samples per twin per serving tick


def family_cfg(system, n_active: int, seed: int) -> TwinServerConfig:
    return TwinServerConfig(
        merinda=MerindaConfig(n=system.spec.n, m=system.spec.m,
                              order=system.spec.order, dt=system.spec.dt,
                              hidden=16, head_hidden=16, n_active=n_active),
        max_twins=4096, refit_slots=8,
        capacity=64, window=16, stride=8, windows_per_twin=4,
        steps_per_tick=1, sparsify_after=30, deploy_after=8,
        min_residency=4, max_residency=16,
        guard=GuardConfig(window=24), guard_budget=96,
        async_ingest=True, seed=seed)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--per-family", type=int, default=384)
    ap.add_argument("--damaged", type=int, default=16,
                    help="F-8s flying with degraded elevator authority")
    ap.add_argument("--ticks", type=int, default=40)
    ap.add_argument("--warmup", type=int, default=20,
                    help="ticks excluded from the latency report (jit "
                         "compile, slot fill, first promote compilation)")
    args = ap.parse_args()
    enable_compile_cache()

    nf = args.per_family
    families = [("f8", trim_neighborhood(F8Crusader()), 24),
                ("vdp", VanDerPol(), 12),
                ("lv", LotkaVolterra(), 6)]
    horizon = CHUNK * args.ticks + 1

    # ---- telemetry: one simulated batch per family; the damaged F-8s fly
    # DamagedF8 dynamics while serving the nominal model -------------------
    print(f"simulating {3 * nf} twins "
          f"({args.damaged} F-8s have damaged elevators)...")
    telemetry = []
    for i, (name, system, _) in enumerate(families):
        tr = simulate_batch(system, jax.random.PRNGKey(i), batch=nf,
                            horizon=horizon, noise_std=0.002)
        telemetry.append([np.array(tr.ys_noisy), np.array(tr.us)])
    dmg = trim_neighborhood(DamagedF8())
    tr = simulate_batch(dmg, jax.random.PRNGKey(0), batch=args.damaged,
                        horizon=horizon, noise_std=0.002)
    telemetry[0][0][:args.damaged] = np.asarray(tr.ys_noisy)
    telemetry[0][1][:args.damaged] = np.asarray(tr.us)

    # ---- the sharded server: one shard per family, global slot budget ----
    cfg = ShardedTwinConfig(
        servers=tuple(family_cfg(system, n_active, seed=i)
                      for i, (_, system, n_active) in enumerate(families)),
        total_slots=12, min_shard_slots=1, rebalance_every=4,
        pressure_smooth=0.5)
    server = ShardedTwinServer(cfg)

    # family routing: twin id i*nf + k -> shard i; warm-start every family
    # from its offline-recovered model in one fused scatter per shard
    for i, (name, system, _) in enumerate(families):
        ids = [i * nf + k for k in range(nf)]
        for tid in ids:
            server.register(tid, shard=i)
        theta0 = system.true_theta(server.shards[i].fleet.model.lib)
        server.deploy_many(ids, theta0)

    print(f"serving {3 * nf} twins on {server.n_shards} shards "
          f"(global budget {cfg.total_slots} refit slots, guard budget "
          f"{cfg.servers[0].guard_budget}/shard/tick)...")
    flagged: set[int] = set()
    for t in range(args.ticks):
        lo = t * CHUNK
        for i in range(3):
            ys, us = telemetry[i]
            for k in range(nf):
                server.ingest(i * nf + k, ys[k, lo:lo + CHUNK],
                              us[k, lo:lo + CHUNK])
        rep = server.tick()
        flagged |= {e.twin_id for e in rep.events}
        if rep.tick == args.warmup:
            server.reset_latency_stats()
        if t % 8 == 7 or rep.tick == 1:
            print(f"  tick {rep.tick:3d}  lat={rep.latency_s * 1e3:6.1f} ms"
                  f"  grants={rep.grants}  active={rep.n_active}"
                  f"  guarded={rep.n_guarded}  events={len(rep.events)}")
    server.drain()

    # ---- report ---------------------------------------------------------- #
    s = server.latency_summary()
    st = server.stage_summary()
    dmg_ids = set(range(args.damaged))
    f8 = server.shards[0]
    div_d = np.mean([f8.twins[i].divergence for i in dmg_ids])
    div_h = np.mean([f8.twins[i].divergence for i in range(nf)
                     if i not in dmg_ids])
    print(f"\n== per-refresh latency vs the {s['deadline_s']:.0f} s deadline ==")
    print(f"  p50 {s['p50_ms']:.1f} ms | p99 {s['p99_ms']:.1f} ms | "
          f"max {s['max_ms']:.1f} ms | violations {s['violations']}/"
          f"{s['ticks']} | {s['twin_refreshes_per_s']:.0f} twin refreshes/s")
    print(f"  stage cost/tick: flush {st['flush_ms']:.1f} | guard "
          f"{st['guard_ms']:.1f} | schedule {st['schedule_ms']:.1f} | "
          f"refit {st['refit_ms']:.1f} ms")
    print("== federation ==")
    print(f"  final grants {server.grants} (f8/vdp/lv), pressures "
          f"{[round(p, 1) for p in server.federation.pressures]}")
    print("== divergence guard (F-8 shard) ==")
    print(f"  mean divergence: damaged {div_d:.3f} vs healthy {div_h:.4f}")
    caught = sorted(i for i in flagged if i in dmg_ids)
    print(f"  flagged {len(flagged)} twins, {len(caught)}/{args.damaged} "
          f"true damaged among them")
    probe = 0
    pred = server.predict(probe, 50)
    print(f"== prediction ==\n  twin {probe} lookahead "
          f"{50 * families[0][1].spec.dt:.1f} s: "
          f"y(T)={np.asarray(pred[-1]).round(4).tolist()}")
    server.close()


if __name__ == "__main__":
    main()
