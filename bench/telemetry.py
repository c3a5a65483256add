"""The benchmark's own telemetry generator, for any physics family.

The family's model comes from its physics file (`bench/family.py`), a copy
of the published model kept with the benchmark so that a change to the
program's `systems/` cannot move the yardstick.

A stream is made on the host, by JAX's CPU backend, before the window: it
takes no device memory, and its numbers do not depend on the accelerator
(`bench/tests/test_telemetry.py` checks it on the same backend).  It is
integrated with classic RK4 at `substeps` sub-intervals per sample (1: one
`dt`, far below the modelled time constants) with zero-order-hold input, in
one jitted scan over all twins, twins on the lane axis.  An open-loop
airframe such as the F-8 departs controlled flight within minutes, so each
twin may fly a stability augmentation: the inputs are the sum-of-sines
command plus `gains @ y`, computed at each sample and held.  The recorded
input is the one actually applied, so the telemetry obeys the model's
equations exactly as an open-loop stream would.  Initial states, input
tones and noise come from the seed alone.  Measurement noise per channel is
`noise_std` times that channel's standard deviation over the twin's whole
stream.
"""
from __future__ import annotations

from functools import cache, partial, reduce

import jax
import jax.numpy as jnp
import numpy as np

from bench.family import Physics, monomials, theta


@cache
def _term_rows(n_vars: int, order: int) -> np.ndarray:
    """[order, L] rows of [1, y, u] whose product is each monomial (row 0,
    the constant, pads the terms of degree under `order`)."""
    terms = monomials(n_vars, order)
    rows = np.zeros((order, len(terms)), np.int32)
    for j, t in enumerate(terms):
        rows[:len(t), j] = [v + 1 for v in t]
    return rows


def _phi(y, u, order: int):
    """Library Phi [L, N] of y [n, N], u [m, N]."""
    xa = jnp.concatenate([jnp.ones_like(y[:1]), y, u], axis=0)
    rows = _term_rows(y.shape[0] + u.shape[0], order)
    return reduce(lambda acc, r: acc * xa[r], rows[1:], xa[rows[0]])


def _rhs(theta, y, u, order: int):
    """dy/dt [n, N] for y [n, N], u [m, N], theta [n, L]: twins on the
    lane axis, theta @ Phi in full f32."""
    return jnp.dot(theta, _phi(y, u, order),
                   precision=jax.lax.Precision.HIGHEST)


@partial(jax.jit, static_argnames=("dt", "order", "substeps"))
def _integrate(theta_nom, theta_dmg, onset, gains, y0, us, dt: float,
               order: int, substeps: int):
    """(ys [N, T+1, n], applied inputs [N, T, m]): twin i follows theta_dmg
    from sample onset[i] on; the inputs are the command plus the stability
    augmentation `gains` [m, n] @ y, sampled and held like the command."""
    h = dt / substeps
    hi = jax.lax.Precision.HIGHEST

    def body(y, tu):
        t, cmd = tu
        damaged = (t >= onset)[None, :]
        u = cmd + gains @ y

        def rhs(y):
            phi = _phi(y, u, order)
            return jnp.where(damaged, jnp.dot(theta_dmg, phi, precision=hi),
                             jnp.dot(theta_nom, phi, precision=hi))

        def sub(y, _):
            k1 = rhs(y)
            k2 = rhs(y + 0.5 * h * k1)
            k3 = rhs(y + 0.5 * h * k2)
            k4 = rhs(y + h * k3)
            return y + (h / 6.0) * (k1 + 2.0 * k2 + 2.0 * k3 + k4), None

        y2, _ = jax.lax.scan(sub, y, None, length=substeps, unroll=True)
        return y2, (y2, u)

    T = us.shape[1]
    _, (ys, applied) = jax.lax.scan(
        body, y0.T, (jnp.arange(T), jnp.transpose(us, (1, 2, 0))), unroll=8)
    # ys [T, n, N], applied [T, m, N]
    return (jnp.concatenate([y0[:, None], jnp.transpose(ys, (2, 0, 1))],
                            axis=1),
            jnp.transpose(applied, (2, 0, 1)))


@jax.jit
def _add_noise(ys, noise, noise_std):
    return ys + noise_std * noise * jnp.std(ys, axis=1, keepdims=True)


def sum_of_sines(freqs, phases, amps, T: int, scale: float,
                 dt: float) -> np.ndarray:
    """Inputs [N, T, m] from per-twin tones [N, m, tones]."""
    t = np.arange(T)[None, :, None, None] * dt
    wave = np.sin(2 * np.pi * freqs[:, None] * t + phases[:, None])
    return scale * (amps[:, None] * wave).sum(-1)


def draw(rng: np.random.Generator, physics: Physics, n_twins: int, T: int,
         *, y0_frac: float, input_scale: float):
    """Initial states [N, n] and inputs [N, T, m] for a stream of T samples."""
    p = physics
    shape = (n_twins, p.m, p.tones)
    y0 = rng.uniform(p.y0_low * y0_frac, p.y0_high * y0_frac, (n_twins, p.n))
    freqs = rng.uniform(*p.freq_hz, shape)
    phases = rng.uniform(0.0, 2 * np.pi, shape)
    amps = rng.uniform(*p.amp, shape)
    return y0, sum_of_sines(freqs, phases, amps, T, input_scale, p.dt)


def gain_matrix(physics: Physics, gains) -> np.ndarray:
    """The stability augmentation [m, n]: a flat list of n gains is one
    input's row."""
    g = np.asarray(gains, np.float64)
    g = g[None] if g.ndim == 1 else g
    if g.shape != (physics.m, physics.n):
        raise ValueError(f"sas_gains of shape {np.shape(gains)} do not fit "
                         f"{physics.name}'s {physics.m} inputs x "
                         f"{physics.n} states")
    return g


def host():
    """JAX's CPU device, on which the stream is made."""
    return jax.devices("cpu")[0]


def integrate(physics: Physics, y0, us, onset, *, effectiveness: float,
              gains, substeps: int = 1):
    """Clean trajectories ys [N, T+1, n] and the applied inputs [N, T, m]
    (f32, on the device JAX is set to), from commands `us`."""
    p = physics
    nom = jnp.asarray(theta(p, p.order), jnp.float32)
    dmg = jnp.asarray(theta(p, p.order, effectiveness), jnp.float32)
    return _integrate(nom, dmg, jnp.asarray(onset, jnp.int32),
                      jnp.asarray(gain_matrix(p, gains), jnp.float32),
                      jnp.asarray(y0, jnp.float32),
                      jnp.asarray(us, jnp.float32), p.dt, p.order, substeps)


def stream(physics: Physics, seed: int, n_twins: int, T: int, *,
           y0_frac: float, input_scale: float, noise_std: float, onset,
           effectiveness: float, gains, substeps: int = 1):
    """Noisy telemetry for `n_twins` twins over T samples, from `seed`.

    Returns (ys [N, T, n], us [N, T, m]) as host float32 arrays, aligned as
    the server stores them: us[:, t] is held from sample t to t+1.  `onset`
    [N] is the sample at which each twin is damaged (T or more: never).
    """
    rng = np.random.default_rng(seed)
    y0, cmd = draw(rng, physics, n_twins, T, y0_frac=y0_frac,
                   input_scale=input_scale)
    with jax.default_device(host()):
        ys, us = integrate(physics, y0, cmd, onset,
                           effectiveness=effectiveness, gains=gains,
                           substeps=substeps)
        key = jax.random.PRNGKey(int(rng.integers(0, 2 ** 31)))
        noisy = _add_noise(ys[:, :T],
                           jax.random.normal(key, (n_twins, T, physics.n)),
                           noise_std)
    return np.asarray(noisy, np.float32), np.asarray(us, np.float32)
