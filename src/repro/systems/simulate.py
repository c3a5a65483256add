"""Ground-truth trace generation for the MR benchmarks.

Traces are integrated at `substeps` RK4 sub-intervals per sample so the sampled
trajectory is accurate well past the Nyquist requirement, then optionally
corrupted with measurement noise (the "human-induced noise" regime the paper
mentions for MR).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.odeint import integrate
from repro.systems.base import DynamicalSystem

__all__ = ["Trace", "simulate", "simulate_batch", "continue_batch"]


@dataclass
class Trace:
    """A sampled trajectory. ys: [T+1, n] clean, ys_noisy likewise, us: [T, m]."""
    ys: jnp.ndarray
    ys_noisy: jnp.ndarray
    us: jnp.ndarray
    dt: float


@partial(jax.jit, static_argnames=("system", "horizon", "substeps"))
def _simulate(system: DynamicalSystem, key, horizon: int, substeps: int,
              noise_std: float):
    k0, k1, k2 = jax.random.split(key, 3)
    y0 = system.sample_y0(k0)
    us = system.sample_inputs(k1, horizon)
    ys = integrate(system.rhs, y0, us, system.spec.dt, substeps=substeps)
    noise = noise_std * jax.random.normal(k2, ys.shape) * jnp.std(ys, 0, keepdims=True)
    return ys, ys + noise, us


def simulate(system: DynamicalSystem, key, horizon: int | None = None,
             substeps: int = 10, noise_std: float = 0.0) -> Trace:
    horizon = horizon or system.spec.horizon
    ys, ys_noisy, us = _simulate(system, key, horizon, substeps, noise_std)
    return Trace(ys=ys, ys_noisy=ys_noisy, us=us, dt=system.spec.dt)


def simulate_batch(system: DynamicalSystem, key, batch: int,
                   horizon: int | None = None, substeps: int = 10,
                   noise_std: float = 0.0) -> Trace:
    """Batch of independent traces: ys [B, T+1, n], us [B, T, m]."""
    horizon = horizon or system.spec.horizon
    keys = jax.random.split(key, batch)
    sim = jax.vmap(lambda k: _simulate(system, k, horizon, substeps, noise_std))
    ys, ys_noisy, us = sim(keys)
    return Trace(ys=ys, ys_noisy=ys_noisy, us=us, dt=system.spec.dt)


def continue_batch(system: DynamicalSystem, y0s, us, key,
                   noise_std: float = 0.0, substeps: int = 10):
    """Continue each trajectory under `system` from its own state: y0s
    [B, n], us [B, T, m] -> noisy ys [B, T+1, n] (noise scaled per channel
    like `simulate`).  A mid-stream dynamics change is the nominal trace up
    to the switch, then this under the changed system."""
    ys = jax.vmap(lambda y0, u: integrate(system.rhs, y0, u, system.spec.dt,
                                          substeps=substeps))(y0s, us)
    noise = noise_std * jax.random.normal(key, ys.shape) \
        * jnp.std(ys, axis=1, keepdims=True)
    return ys + noise


REGISTRY = {}


def register_systems():
    """Populate the name -> constructor registry (import-cycle-free)."""
    from repro.systems.f8_crusader import F8Crusader
    from repro.systems.grid_frequency import GridFrequency
    from repro.systems.lorenz import Lorenz
    from repro.systems.lotka_volterra import LotkaVolterra
    from repro.systems.pathogen import PathogenicAttack
    from repro.systems.quadrotor import Quadrotor
    from repro.systems.thermal_battery import ThermalBattery
    from repro.systems.van_der_pol import VanDerPol

    REGISTRY.update({
        "lotka_volterra": LotkaVolterra,
        "lorenz": Lorenz,
        "f8_crusader": F8Crusader,
        "pathogenic_attack": PathogenicAttack,
        "van_der_pol": VanDerPol,
        "quadrotor": Quadrotor,
        "thermal_battery": ThermalBattery,
        "grid_frequency": GridFrequency,
    })
    return REGISTRY
