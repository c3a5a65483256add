"""What-if scenario serving over a mixed-fidelity heterogeneous fleet.

    PYTHONPATH=src python examples/scenario_fleet.py [--per-family 64]

Five FAMILIES of tracked objects live in one `ShardedTwinServer`, one shard
per family — the full serving zoo, mixing flight dynamics with process
models of very different stiffness and fidelity:

  shard 0: F-8 Crusader airframes      (n=3, m=1, order 3, dt 10 ms)
  shard 1: quadrotors (near hover)     (n=3, m=1, order 3, dt 10 ms)
  shard 2: pathogen outbreaks          (n=2, m=1, order 2, dt 20 ms)
  shard 3: battery thermal models      (n=2, m=1, order 2, dt 50 ms)
  shard 4: grid-frequency areas        (n=2, m=1, order 2, dt 20 ms)

After a short serving warmup the example asks each family its natural
WHAT-IF question through `server.scenario()` — K counterfactual input
sequences rolled forward in one fused ensemble call, answered with
confidence bounds from the recent-theta history:

  F-8:      "elevator authority fades 30% over the next 2 s"
  quad:     "differential thrust saturates high for 1 s"
  pathogen: "treatment stops vs doubles"
  battery:  "cell pulls 0 / 1x / 2x current for a minute"
  grid:     "a feeder trips: load steps 0.1 / 0.2 / 0.3 pu"

The point of the demo: one service call shape answers operator questions
across every physics family, and the confidence column tells you which
answers to trust (families whose online refits thrash report wider bands).
"""
import argparse

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.merinda import MerindaConfig
from repro.systems.f8_crusader import F8Crusader
from repro.systems.grid_frequency import GridFrequency
from repro.systems.pathogen import PathogenicAttack
from repro.systems.quadrotor import Quadrotor
from repro.systems.simulate import simulate_batch
from repro.systems.thermal_battery import ThermalBattery
from repro.twin.monitor import GuardConfig
from repro.twin.scenario import ScenarioConfig
from repro.twin.server import TwinServerConfig
from repro.twin.sharded import ShardedTwinConfig, ShardedTwinServer

CHUNK = 8   # telemetry samples per twin per serving tick


def trim_f8(system, y0_frac: float = 0.5, input_scale: float = 0.03):
    """Confine the F-8 to its trim neighborhood (see sharded_fleet.py)."""
    import dataclasses
    system.spec = dataclasses.replace(
        system.spec,
        y0_low=tuple(v * y0_frac for v in system.spec.y0_low),
        y0_high=tuple(v * y0_frac for v in system.spec.y0_high),
        input_scale=input_scale)
    return system


def family_cfg(system, n_active: int, seed: int) -> TwinServerConfig:
    return TwinServerConfig(
        merinda=MerindaConfig(n=system.spec.n, m=system.spec.m,
                              order=system.spec.order, dt=system.spec.dt,
                              hidden=16, head_hidden=16, n_active=n_active),
        max_twins=1024, refit_slots=4,
        capacity=64, window=16, stride=8, windows_per_twin=4,
        steps_per_tick=1, sparsify_after=30, deploy_after=8,
        min_residency=4, max_residency=16,
        guard=GuardConfig(window=24), guard_budget=32,
        scenario=ScenarioConfig(max_k=8, ensemble=4),
        async_ingest=True, seed=seed)


def ramp(scale, horizon, m, frac):
    """One input channel ramping linearly to `frac`*scale over the horizon."""
    us = np.zeros((horizon, m), np.float32)
    us[:, 0] = scale * frac * np.linspace(0.0, 1.0, horizon)
    return us


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--per-family", type=int, default=64)
    ap.add_argument("--ticks", type=int, default=24)
    ap.add_argument("--horizon", type=int, default=40,
                    help="what-if lookahead steps")
    args = ap.parse_args()
    enable_compile_cache()

    nf = args.per_family
    families = [
        ("f8", trim_f8(F8Crusader()), 24,
         "elevator fades 30% over the lookahead"),
        ("quadrotor", Quadrotor(), 8,
         "differential thrust ramps to saturation"),
        ("pathogen", PathogenicAttack(), 8,
         "treatment stops vs doubles"),
        ("battery", ThermalBattery(), 8,
         "cell current 0x / 1x / 2x for the lookahead"),
        ("grid", GridFrequency(), 8,
         "feeder trip: load steps 0.1 / 0.2 / 0.3 pu"),
    ]
    horizon = CHUNK * args.ticks + 1

    print(f"simulating {len(families) * nf} twins in {len(families)} "
          "families...")
    telemetry = []
    for i, (name, system, _, _) in enumerate(families):
        tr = simulate_batch(system, jax.random.PRNGKey(i), batch=nf,
                            horizon=horizon, noise_std=0.002)
        telemetry.append((np.asarray(tr.ys_noisy), np.asarray(tr.us)))

    cfg = ShardedTwinConfig(
        servers=tuple(family_cfg(system, n_active, seed=i)
                      for i, (_, system, n_active, _) in enumerate(families)),
        total_slots=12, min_shard_slots=1, rebalance_every=4,
        pressure_smooth=0.5)
    server = ShardedTwinServer(cfg)

    for i, (name, system, _, _) in enumerate(families):
        ids = [i * nf + k for k in range(nf)]
        for tid in ids:
            server.register(tid, shard=i)
        theta0 = system.true_theta(server.shards[i].fleet.model.lib)
        server.deploy_many(ids, theta0)

    print(f"serving {len(families) * nf} twins on {server.n_shards} "
          "shards...")
    for t in range(args.ticks):
        lo = t * CHUNK
        for i in range(len(families)):
            ys, us = telemetry[i]
            server.ingest_many(
                [(i * nf + k, ys[k, lo:lo + CHUNK], us[k, lo:lo + CHUNK])
                 for k in range(nf)])
        rep = server.tick()
        if t % 8 == 7 or rep.tick == 1:
            print(f"  tick {rep.tick:3d}  lat={rep.latency_s * 1e3:6.1f} ms"
                  f"  active={rep.n_active}  events={len(rep.events)}")
    server.drain()

    # ---- one what-if per family ----------------------------------------- #
    H = args.horizon
    print(f"\n== what-if scenarios (horizon {H} steps, K counterfactuals, "
          "ensemble confidence) ==")
    for i, (name, system, _, question) in enumerate(families):
        m, scale = system.spec.m, system.spec.input_scale
        if name == "battery":
            us = np.stack([np.full((H, m), f * scale, np.float32)
                           for f in (0.0, 1.0, 2.0)])
        elif name == "grid":
            us = np.stack([np.full((H, m), f, np.float32)
                           for f in (0.1, 0.2, 0.3)])
        elif name == "pathogen":
            us = np.stack([np.zeros((H, m), np.float32),
                           np.full((H, m), 2.0 * scale, np.float32)])
        else:
            us = np.stack([ramp(scale, H, m, f) for f in (0.3, 0.6, 1.0)])
        res = server.scenario(i * nf, H, us)
        width = np.mean(res.hi - res.lo, axis=(1, 2))
        yT = res.ys[:, -1, :]
        print(f"  {name:10s} {question}")
        for j in range(res.k):
            print(f"     K={j}: y(T)={np.round(yT[j], 3).tolist()}  "
                  f"band={width[j]:.4f}  conf={res.confidence[j]:.3f}")

    s = server.latency_summary()
    print(f"\n== serving health ==\n  p50 {s['p50_ms']:.1f} ms | "
          f"p99 {s['p99_ms']:.1f} ms | violations {s['violations']}/"
          f"{s['ticks']}")
    server.close()


if __name__ == "__main__":
    main()
