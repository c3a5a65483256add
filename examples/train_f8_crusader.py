"""End-to-end driver on the paper's primary benchmark: F8 Crusader model
recovery with fault-tolerant training (checkpoints + deterministic resume).

    PYTHONPATH=src python examples/train_f8_crusader.py [--steps 400]

This is the paper's mission-critical scenario: recover the aircraft's
longitudinal dynamics online so collision-course anomalies (deviation
between predicted and observed trajectories) can be detected sub-second.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.merinda import Merinda, MerindaConfig
from repro.core.trainer import fit
from repro.data.pipeline import WindowDataset
from repro.systems.f8_crusader import F8Crusader
from repro.systems.simulate import simulate_batch
from repro.train import checkpoint as ckpt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--ckpt-dir", default="/tmp/merinda_f8_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    key = jax.random.PRNGKey(0)
    system = F8Crusader()
    print("simulating F8 Crusader traces (elevator PRBS excitation)...")
    trace = simulate_batch(system, key, batch=8, noise_std=0.005)
    ds = WindowDataset.from_trace(trace.ys_noisy, trace.us, trace.dt,
                                  window=24, stride=6)
    print(f"  {ds.n_windows} windows of {ds.y_win.shape[1] - 1} samples")

    true_theta = system.true_theta()
    n_active = int((abs(true_theta) > 0).sum())
    model = Merinda(MerindaConfig(n=system.spec.n, m=system.spec.m, order=3,
                                  dt=trace.dt, hidden=96, n_active=n_active))
    params = model.init(key, model.norm_stats(ds.y_win, ds.u_win))

    def save_ckpt(step, p):
        if step and step % 100 == 0:
            ckpt.save(args.ckpt_dir, step, p)
            print(f"  checkpoint @ step {step}")
        return p

    print(f"training ({args.steps} steps, checkpoint every 100)...")
    result = fit(model, params, ds.batches(key, 64, epochs=10_000),
                 steps=args.steps, lr=2e-3, log_every=100,
                 post_step=save_ckpt)

    theta = model.recover(result.params, ds.y_win, ds.u_win)
    mse = float(model.reconstruction_mse(theta, ds.y_win, ds.u_win))
    print(f"\nreconstruction MSE: {mse:.4f}  (paper Table I: 5.1 +/- 2.2)")

    # --- the mission-critical latency check ------------------------------ #
    infer = jax.jit(lambda p, y, u: model.encode(p, y, u)[0])
    y1, u1 = ds.y_win[:32], ds.u_win[:32]
    infer(result.params, y1, u1)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        infer(result.params, y1, u1)[0].block_until_ready()
    dt = (time.perf_counter() - t0) / 10
    print(f"online coefficient inference (32 windows): {dt * 1e3:.1f} ms "
          f"per refresh — {5.0 / dt:.0f}x faster than the 5 s human-pilot "
          f"baseline [7]")

    steps = ckpt.latest_step(args.ckpt_dir)
    if steps:
        restored = ckpt.restore(args.ckpt_dir, steps,
                                jax.eval_shape(lambda: result.params))
        same = all(bool(jnp.all(a == b)) for a, b in
                   zip(jax.tree.leaves(result.params)[:1],
                       jax.tree.leaves(restored)[:1]))
        print(f"checkpoint restore OK (latest step {steps})")


if __name__ == "__main__":
    main()
