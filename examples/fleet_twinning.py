"""Fleet digital twinning: many concurrent MERINDA twins on one device mesh.

    PYTHONPATH=src python examples/fleet_twinning.py [--fleet 16]

The paper's deployment scenario scaled out: every tracked aircraft gets a
continuously-refit digital twin.  One fused train step advances EVERY twin
(vmapped over the fleet axis; on the production mesh the fleet axis shards
over ('pod','data') — see launch/dryrun.py's merinda fleet cell).  Prints
per-refresh latency against the paper's 5-second human-pilot baseline.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core.fleet import FleetConfig, FleetMerinda
from repro.core.merinda import MerindaConfig
from repro.data.pipeline import make_windows
from repro.systems.f8_crusader import F8Crusader
from repro.systems.simulate import simulate_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fleet", type=int, default=16)
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()
    enable_compile_cache()

    key = jax.random.PRNGKey(0)
    system = F8Crusader()
    print(f"simulating {args.fleet} aircraft...")
    trace = simulate_batch(system, key, batch=args.fleet, noise_std=0.005)
    y_win, u_win = make_windows(trace.ys_noisy, trace.us, window=24, stride=8)
    # regroup windows per twin: [F, S_B, k+1, n]
    S_B = y_win.shape[0] // args.fleet
    y_win = y_win.reshape(args.fleet, S_B, *y_win.shape[1:])[:, :32]
    u_win = u_win.reshape(args.fleet, S_B, *u_win.shape[1:])[:, :32]

    mcfg = MerindaConfig(n=system.spec.n, m=system.spec.m, order=3,
                         dt=system.spec.dt, hidden=64, n_active=24)
    fleet = FleetMerinda(FleetConfig(merinda=mcfg, fleet=args.fleet))
    state = fleet.init(key)

    print(f"refitting {args.fleet} twins concurrently "
          f"({args.steps} fused steps)...")
    state, loss = fleet.train_step(state, y_win, u_win)  # compile
    t0 = time.perf_counter()
    for _ in range(args.steps - 1):
        state, loss = fleet.train_step(state, y_win, u_win)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / (args.steps - 1)
    print(f"  mean fused step: {dt * 1e3:.1f} ms for {args.fleet} twins "
          f"({dt * 1e3 / args.fleet:.2f} ms/twin on 1 CPU core)")
    print(f"  vs 5 s human-pilot reaction baseline: "
          f"{5.0 / dt:.0f}x headroom per refresh")

    thetas = fleet.recover_all(state, y_win, u_win)
    print(f"  recovered fleet models: theta {tuple(thetas.shape)}, "
          f"mean |theta| {float(jnp.mean(jnp.abs(thetas))):.3f}")


if __name__ == "__main__":
    main()
