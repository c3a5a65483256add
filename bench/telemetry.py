"""The benchmark's own F-8 Crusader telemetry generator.

A copy of the physics the twin server is fed, kept with the benchmark so that
a change to the program's `systems/` cannot move the yardstick.  It follows
the Garrard & Jordan (1977) polynomial model of the F-8 longitudinal
dynamics (states: angle of attack, pitch angle, pitch rate; input: elevator)
and the damaged-elevator variant, in which every input-dependent coefficient
is scaled by the elevator's effectiveness.

A stream is made on the host, by JAX's CPU backend, before the window: it
takes no device memory, and its numbers do not depend on the accelerator
(`bench/tests/test_telemetry.py` checks it on the same backend).  It is
integrated with classic RK4 at `substeps` sub-intervals per sample (1: 10
ms, far below the F-8's time constants) with zero-order-hold input, in one
jitted scan over all twins, twins on the lane axis.  The open-loop F-8
departs controlled flight within minutes, so each airframe flies with a
pitch stability augmentation: the elevator is the pilot's sum-of-sines
command plus `gains . y`, computed at each sample and held.  The recorded
input is the elevator actually applied, so the telemetry obeys the F-8
equations exactly as an open-loop stream would.  Initial states, input
tones and noise come from the seed alone.  Measurement noise per channel is
`noise_std` times that channel's standard deviation over the twin's whole
stream.

`theta_f8` places the coefficients into the library the server learns:
all monomials of total degree <= 3 over [y0, y1, y2, u0], ordered by degree
and then lexicographically (35 terms).
"""
from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

N_STATE, N_INPUT, ORDER = 3, 1, 3
DT = 0.01
# full F-8 initial-state box; the stream flies its trim neighbourhood
Y0_LOW = np.array([-0.15, -0.05, -0.05])
Y0_HIGH = np.array([0.30, 0.05, 0.05])

# per-state {monomial: coefficient}; a monomial is a sorted tuple of the
# variables 0..3 = (y0, y1, y2, u0)
_A, _B, _Q, _U = 0, 1, 2, 3
F8_ROWS = (
    {(_A,): -0.877, (_Q,): 1.0, (_A, _Q): -0.088, (_A, _A): 0.47,
     (_B, _B): -0.019, (_A, _A, _Q): -1.0, (_A, _A, _A): 3.846,
     (_U,): -0.215, (_A, _A, _U): 0.28, (_A, _U, _U): 0.47,
     (_U, _U, _U): 0.63},
    {(_Q,): 1.0},
    {(_A,): -4.208, (_Q,): -0.396, (_A, _A): -0.47, (_A, _A, _A): -3.564,
     (_U,): -20.967, (_A, _A, _U): 6.265, (_A, _U, _U): 46.0,
     (_U, _U, _U): 61.4},
)


def monomials(n_vars: int = N_STATE + N_INPUT, order: int = ORDER):
    """Library terms as sorted variable tuples, by degree then lexicographic."""
    return [combo for d in range(order + 1)
            for combo in itertools.combinations_with_replacement(
                range(n_vars), d)]


def theta_f8(effectiveness: float = 1.0) -> np.ndarray:
    """F-8 coefficients [3, 35]; `effectiveness` scales the input terms."""
    index = {t: j for j, t in enumerate(monomials())}
    theta = np.zeros((N_STATE, len(index)))
    for i, row in enumerate(F8_ROWS):
        for term, c in row.items():
            theta[i, index[term]] = c * (effectiveness if _U in term else 1.0)
    return theta


def _term_rows() -> np.ndarray:
    """[3, 35] rows of [1, y0, y1, y2, u0] whose product is each monomial
    (row 0, the constant, pads the terms of degree under 3)."""
    rows = np.zeros((ORDER, len(monomials())), np.int32)
    for j, t in enumerate(monomials()):
        rows[:len(t), j] = [v + 1 for v in t]
    return rows


_TERMS = _term_rows()


def _phi(y, u):
    """Library Phi [35, N] of y [3, N], u [1, N]."""
    xa = jnp.concatenate([jnp.ones_like(u), y, u], axis=0)
    return xa[_TERMS[0]] * xa[_TERMS[1]] * xa[_TERMS[2]]


def _rhs(theta, y, u):
    """dy/dt [3, N] for y [3, N], u [1, N], theta [3, 35]: twins on the
    lane axis, theta @ Phi in full f32."""
    return jnp.dot(theta, _phi(y, u), precision=jax.lax.Precision.HIGHEST)


@partial(jax.jit, static_argnames=("substeps",))
def _integrate(theta_nom, theta_dmg, onset, gains, y0, us, substeps: int):
    """(ys [N, T+1, 3], applied inputs [N, T, 1]): twin i follows theta_dmg
    from sample onset[i] on; the elevator is the command plus the stability
    augmentation `gains` . y, sampled and held like the command."""
    h = DT / substeps
    hi = jax.lax.Precision.HIGHEST

    def body(y, tu):
        t, cmd = tu
        damaged = (t >= onset)[None, :]
        u = cmd[None, :] + (gains @ y)[None, :]

        def rhs(y):
            phi = _phi(y, u)
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
    _, (ys, applied) = jax.lax.scan(body, y0.T, (jnp.arange(T), us[..., 0].T),
                                    unroll=8)
    # ys [T, 3, N], applied [T, 1, N]
    return (jnp.concatenate([y0[:, None], jnp.transpose(ys, (2, 0, 1))],
                            axis=1),
            jnp.transpose(applied, (2, 0, 1)))


@jax.jit
def _add_noise(ys, noise, noise_std):
    return ys + noise_std * noise * jnp.std(ys, axis=1, keepdims=True)


def sum_of_sines(freqs, phases, amps, T: int, scale: float) -> np.ndarray:
    """Elevator input [N, T, 1] from per-twin tones [N, tones]."""
    t = np.arange(T)[None, :, None] * DT
    wave = np.sin(2 * np.pi * freqs[:, None, :] * t + phases[:, None, :])
    return (scale * (amps[:, None, :] * wave).sum(-1))[..., None]


def draw(rng: np.random.Generator, n_twins: int, T: int, *,
         y0_frac: float, input_scale: float, tones: int = 4):
    """Initial states [N, 3] and inputs [N, T, 1] for a stream of T samples."""
    y0 = rng.uniform(Y0_LOW * y0_frac, Y0_HIGH * y0_frac, (n_twins, N_STATE))
    freqs = rng.uniform(0.1, 1.5, (n_twins, tones))
    phases = rng.uniform(0.0, 2 * np.pi, (n_twins, tones))
    amps = rng.uniform(0.2, 1.0, (n_twins, tones))
    return y0, sum_of_sines(freqs, phases, amps, T, input_scale)


def host():
    """JAX's CPU device, on which the stream is made."""
    return jax.devices("cpu")[0]


def integrate(y0, us, onset, *, effectiveness: float, gains=(0.0, 0.0, 0.0),
              substeps: int = 1):
    """Clean trajectories ys [N, T+1, 3] and the applied elevator
    [N, T, 1] (f32, on the device JAX is set to), from commands `us`."""
    nom = jnp.asarray(theta_f8(), jnp.float32)
    dmg = jnp.asarray(theta_f8(effectiveness), jnp.float32)
    return _integrate(nom, dmg, jnp.asarray(onset, jnp.int32),
                      jnp.asarray(gains, jnp.float32),
                      jnp.asarray(y0, jnp.float32),
                      jnp.asarray(us, jnp.float32), substeps)


def stream(seed: int, n_twins: int, T: int, *, y0_frac: float,
           input_scale: float, noise_std: float, onset,
           effectiveness: float, gains, substeps: int = 1):
    """Noisy telemetry for `n_twins` twins over T samples, from `seed`.

    Returns (ys [N, T, 3], us [N, T, 1]) as host float32 arrays, aligned as
    the server stores them: us[:, t] is held from sample t to t+1.  `onset`
    [N] is the sample at which each twin's elevator is damaged (T or more:
    never).
    """
    rng = np.random.default_rng(seed)
    y0, cmd = draw(rng, n_twins, T, y0_frac=y0_frac, input_scale=input_scale)
    with jax.default_device(host()):
        ys, us = integrate(y0, cmd, onset, effectiveness=effectiveness,
                           gains=gains, substeps=substeps)
        key = jax.random.PRNGKey(int(rng.integers(0, 2 ** 31)))
        noisy = _add_noise(ys[:, :T],
                           jax.random.normal(key, (n_twins, T, N_STATE)),
                           noise_std)
    return np.asarray(noisy, np.float32), np.asarray(us, np.float32)
