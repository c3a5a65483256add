"""Quickstart: recover the Lotka-Volterra equations with MERINDA in ~2 min.

    PYTHONPATH=src python examples/quickstart.py

Simulates predator-prey traces, trains the GRU-flow model-recovery network
(the paper's architecture: GRU -> pruned dense head -> RK4 ODE loss), and
prints the recovered governing equations next to the ground truth.
"""
import jax

from repro.compile_cache import enable_compile_cache
from repro.core.merinda import Merinda, MerindaConfig
from repro.core.trainer import fit
from repro.data.pipeline import WindowDataset
from repro.systems.lotka_volterra import LotkaVolterra
from repro.systems.simulate import simulate_batch


def main():
    enable_compile_cache()
    key = jax.random.PRNGKey(0)
    system = LotkaVolterra()
    print("simulating traces...")
    trace = simulate_batch(system, key, batch=4, horizon=250, noise_std=0.01)
    ds = WindowDataset.from_trace(trace.ys_noisy, trace.us, trace.dt,
                                  window=40, stride=12)

    true_theta = system.true_theta()
    n_active = int((abs(true_theta) > 0).sum())
    model = Merinda(MerindaConfig(n=2, m=0, order=2, dt=trace.dt,
                                  hidden=64, n_active=n_active))
    params = model.init(key, model.norm_stats(ds.y_win, ds.u_win))

    print("training MERINDA (400 steps)...")
    result = fit(model, params, ds.batches(key, 64, epochs=10_000),
                 steps=400, lr=3e-3, log_every=100)

    theta = model.recover(result.params, ds.y_win, ds.u_win)
    mse = float(model.reconstruction_mse(theta, ds.y_win, ds.u_win))
    print(f"\nreconstruction MSE: {mse:.4f}")
    print("\nrecovered model:")
    for eq, terms in model.lib.coeff_dict(theta).items():
        rhs = " + ".join(f"{c:+.3f}*{t}" for t, c in terms.items())
        print(f"  {eq} = {rhs}")
    print("\nground truth:")
    for eq, terms in model.lib.coeff_dict(true_theta).items():
        rhs = " + ".join(f"{c:+.3f}*{t}" for t, c in terms.items())
        print(f"  {eq} = {rhs}")


if __name__ == "__main__":
    main()
