"""bench/telemetry.py is a faithful copy of the program's F-8 physics.

Checked against `repro.systems.f8_crusader` as it stands: the coefficients
in the served library, the right-hand side on random states, and a whole
integrated, noisy stream for one seed, nominal and with elevator damage.
"""
import jax
import jax.numpy as jnp
import numpy as np

from bench import telemetry
from repro.core.library import make_library
from repro.core.odeint import integrate
from repro.systems.f8_crusader import DamagedF8, F8Crusader


def test_coefficients_match_the_program_library():
    lib = make_library(3, 1, 3)
    assert [tuple(sorted(int(i) - 1 for i in t if i)) for t in
            lib.term_indices] == [tuple(t) for t in telemetry.monomials()]
    np.testing.assert_array_equal(telemetry.theta_f8(),
                                  F8Crusader().true_theta(lib))
    np.testing.assert_array_equal(telemetry.theta_f8(0.25),
                                  DamagedF8(0.25).true_theta(lib))


def test_rhs_matches_on_random_states():
    rng = np.random.default_rng(3)
    y = rng.uniform(-0.3, 0.3, (64, 3)).astype(np.float32)
    u = rng.uniform(-0.1, 0.1, (64, 1)).astype(np.float32)
    for eff, system in ((1.0, F8Crusader()), (0.25, DamagedF8(0.25))):
        theta = jnp.asarray(telemetry.theta_f8(eff), jnp.float32)
        ours = telemetry._rhs(theta, jnp.asarray(y.T), jnp.asarray(u.T)).T
        theirs = system.rhs(jnp.asarray(y), jnp.asarray(u))
        np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-6)


def test_stream_matches_the_program_integrator_for_one_seed():
    """Same initial states, inputs and noise draws: the program's RK4
    (`core.odeint.integrate`, 10 substeps) under F8Crusader, switching to
    DamagedF8 at the onset, and the program's noise rule, give the
    benchmark's stream."""
    n, T, onset = 6, 300, 120
    ys, us = telemetry.stream(11, n, T, y0_frac=0.5, input_scale=0.03,
                              noise_std=0.002, onset=np.full((n,), onset),
                              effectiveness=0.25, gains=(0.0, 0.0, 0.0))
    rng = np.random.default_rng(11)
    y0, cmd = telemetry.draw(rng, n, T, y0_frac=0.5, input_scale=0.03)
    np.testing.assert_array_equal(us, cmd.astype(np.float32))
    run = jax.vmap(lambda y, u, f: integrate(f, y, u, 0.01, substeps=10),
                   in_axes=(0, 0, None))
    u32 = jnp.asarray(cmd, jnp.float32)
    nom = run(jnp.asarray(y0, jnp.float32), u32[:, :onset], F8Crusader().rhs)
    dmg = run(nom[:, -1], u32[:, onset:], DamagedF8(0.25).rhs)
    clean = jnp.concatenate([nom, dmg[:, 1:]], axis=1)[:, :T]
    key = jax.random.PRNGKey(int(rng.integers(0, 2 ** 31)))
    noise = jax.random.normal(key, (n, T, 3))
    want = clean + 0.002 * noise * jnp.std(clean, axis=1, keepdims=True)
    np.testing.assert_allclose(ys, want, rtol=2e-4, atol=2e-6)


def test_stability_augmentation_records_the_applied_elevator():
    n, T = 4, 200
    gains = (0.0, 0.1, 0.1)
    ys, us = telemetry.integrate(np.full((n, 3), 0.02), np.zeros((n, T, 1)),
                                 np.full((n,), T + 1), effectiveness=0.25,
                                 gains=gains)
    np.testing.assert_allclose(np.asarray(us)[..., 0],
                               np.asarray(ys)[:, :T] @ np.asarray(gains),
                               rtol=1e-6, atol=1e-9)
