"""Online digital twinning, end to end: 64 F-8 twins served live.

    PYTHONPATH=src python examples/online_twinning.py [--twins 64]

The paper's mission scenario as a running system.  A fleet of F-8 Crusaders
streams telemetry into `TwinServer`; every twin starts from an
offline-recovered model (the warm-start deployment path).  Mid-stream, a
subset of airframes suffers elevator damage — their true dynamics change
while the deployed models do not.  The divergence guard catches the mismatch
(REFIT, escalating to ALERT), the scheduler readmits the damaged twins into
refit slots, and the fleet re-recovers online — all while per-refresh latency
is accounted against the 1 s deadline (5x under the 5 s human-pilot
reaction time).
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.merinda import MerindaConfig
from repro.systems.f8_crusader import (DamagedF8, F8Crusader,
                                       trim_neighborhood)
from repro.systems.simulate import continue_batch, simulate_batch
from repro.twin.monitor import GuardConfig
from repro.twin.server import TwinServer, TwinServerConfig

CHUNK = 8   # telemetry samples per twin per serving tick


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--twins", type=int, default=64)
    ap.add_argument("--damaged", type=int, default=12,
                    help="airframes that lose elevator authority mid-stream")
    ap.add_argument("--pre-ticks", type=int, default=25)
    ap.add_argument("--post-ticks", type=int, default=45)
    args = ap.parse_args()
    enable_compile_cache()

    key = jax.random.PRNGKey(0)
    nominal = trim_neighborhood(F8Crusader())
    damaged = trim_neighborhood(DamagedF8())
    n_tw = args.twins
    dmg_ids = list(range(args.damaged))

    # ---- telemetry: nominal phase, then a mid-stream dynamics switch ---- #
    t1 = CHUNK * args.pre_ticks
    t2 = CHUNK * args.post_ticks
    print(f"simulating {n_tw} airframes "
          f"({args.damaged} lose elevator authority at t={t1 * 0.01:.1f}s)...")
    tr = simulate_batch(nominal, key, batch=n_tw, horizon=t1, noise_std=0.002)
    k_u, k_n1, k_n2 = jax.random.split(jax.random.PRNGKey(1), 3)
    us2 = jnp.transpose(
        nominal.sample_inputs(k_u, t2, batch=(n_tw,)), (1, 0, 2))
    y_end = tr.ys[:, -1, :]
    ys2 = np.array(continue_batch(nominal, y_end, us2, k_n1, noise_std=0.002))
    if dmg_ids:
        idx = jnp.asarray(dmg_ids, jnp.int32)
        ys2[dmg_ids] = np.asarray(continue_batch(
            damaged, y_end[idx], us2[idx], k_n2, noise_std=0.002))
    ys = np.concatenate([np.asarray(tr.ys_noisy[:, :-1]), ys2[:, :-1]], 1)
    us = np.concatenate([np.asarray(tr.us), np.asarray(us2)], 1)

    # ---- the serving loop ---------------------------------------------- #
    cfg = TwinServerConfig(
        merinda=MerindaConfig(n=3, m=1, order=3, dt=nominal.spec.dt,
                              hidden=32, head_hidden=32, n_active=24),
        max_twins=n_tw, refit_slots=8, capacity=256,
        window=24, stride=8, windows_per_twin=8, steps_per_tick=2,
        sparsify_after=40, deploy_after=16, min_residency=4, max_residency=24,
        guard=GuardConfig(window=32), deadline_s=1.0)
    server = TwinServer(cfg)

    # warm start: every twin begins with its offline-recovered model
    theta0 = nominal.true_theta(server.fleet.model.lib)
    for i in range(n_tw):
        server.register(i)
        server.deploy(i, theta0)

    print(f"serving {n_tw} twins ({cfg.refit_slots} refit slots, "
          f"{CHUNK} samples/twin/tick, deadline {cfg.deadline_s:.0f} s)...")
    first_refit_tick = None
    for t in range(args.pre_ticks + args.post_ticks):
        lo = t * CHUNK
        for i in range(n_tw):
            server.ingest(i, ys[i, lo:lo + CHUNK], us[i, lo:lo + CHUNK])
        rep = server.tick()
        for ev in rep.events:
            tag = "<-- dynamics switch detected" \
                if first_refit_tick is None else ""
            if first_refit_tick is None:
                first_refit_tick = rep.tick
            print(f"  tick {rep.tick:3d}  [{ev.kind}] twin {ev.twin_id} "
                  f"score={ev.score:.3f} {tag}")
        if rep.admitted and first_refit_tick is not None:
            print(f"  tick {rep.tick:3d}  scheduler admitted "
                  f"{[tid for _, tid in rep.admitted]} into slots "
                  f"{[s for s, _ in rep.admitted]}")
        if t % 10 == 9:
            print(f"  tick {rep.tick:3d}  lat={rep.latency_s * 1e3:6.1f} ms "
                  f"deadline_met={rep.deadline_met} active={rep.n_active} "
                  f"loss={'-' if rep.loss is None else f'{rep.loss:.3f}'}")

    # ---- report --------------------------------------------------------- #
    s = server.latency_summary()
    div_d = (np.mean([server.twins[i].divergence for i in dmg_ids])
             if dmg_ids else float("nan"))
    div_h = np.mean([server.twins[i].divergence for i in range(n_tw)
                     if i not in dmg_ids])
    kinds = [e.kind for e in server.events]
    print(f"\n== per-refresh latency vs the {s['deadline_s']:.0f} s deadline ==")
    print(f"  p50 {s['p50_ms']:.1f} ms | p99 {s['p99_ms']:.1f} ms | "
          f"max {s['max_ms']:.1f} ms | violations {s['violations']}/{s['ticks']}"
          f" | {s['twin_refreshes_per_s']:.0f} twin refreshes/s")
    print(f"== divergence guard ==")
    print(f"  events: {kinds.count('REFIT')} REFIT, "
          f"{kinds.count('ALERT')} ALERT "
          f"(first at tick {first_refit_tick}; switch at tick "
          f"{args.pre_ticks + 1})")
    print(f"  mean divergence: damaged {div_d:.3f} vs healthy {div_h:.4f}")
    refit_set = {e.twin_id for e in server.events}
    print(f"  flagged twins: {sorted(refit_set)}")
    print(f"  (true damaged: {dmg_ids})")
    horizon = 50
    probe = dmg_ids[0] if dmg_ids else 0
    pred = server.predict(probe, horizon)
    print(f"== prediction ==\n  twin {probe} lookahead "
          f"{horizon * cfg.merinda.dt:.1f} s: y(T)="
          f"{np.asarray(pred[-1]).round(4).tolist()}")


if __name__ == "__main__":
    main()
