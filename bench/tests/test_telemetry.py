"""bench/telemetry.py and the physics files are faithful copies of the
program's physics.

Every physics file whose `name` is a system of the program's registry
(`repro.systems.simulate`) is checked against it: the library, the
coefficients, the sampling interval, the initial-state box and the
right-hand side on random states.  The F-8 (`repro.systems.f8_crusader`)
is also checked with elevator damage and through a whole integrated, noisy
stream for one seed, and its stream and coefficients are pinned to the
bytes they had before the generator took the family from a file.
"""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import family, telemetry
from bench.harness import BENCH
from repro.core.library import make_library
from repro.core.odeint import integrate
from repro.systems.f8_crusader import DamagedF8, F8Crusader
from repro.systems.simulate import register_systems

F8 = family.load_physics(BENCH / "physics" / "f8_crusader.json")
REGISTRY = register_systems()
PHYSICS_FILES = sorted(
    p for p in [*(BENCH / "physics").glob("*.json"),
                *Path(__file__).parent.glob("*.json")]
    if json.loads(p.read_text()).get("name") in REGISTRY)


def _sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def test_coefficients_match_the_program_library():
    lib = make_library(3, 1, 3)
    assert [tuple(sorted(int(i) - 1 for i in t if i)) for t in
            lib.term_indices] == [tuple(t) for t in family.monomials(4, 3)]
    np.testing.assert_array_equal(family.theta(F8, 3),
                                  F8Crusader().true_theta(lib))
    np.testing.assert_array_equal(family.theta(F8, 3, 0.25),
                                  DamagedF8(0.25).true_theta(lib))


def test_rhs_matches_on_random_states():
    rng = np.random.default_rng(3)
    y = rng.uniform(-0.3, 0.3, (64, 3)).astype(np.float32)
    u = rng.uniform(-0.1, 0.1, (64, 1)).astype(np.float32)
    for eff, system in ((1.0, F8Crusader()), (0.25, DamagedF8(0.25))):
        theta = jnp.asarray(family.theta(F8, 3, eff), jnp.float32)
        ours = telemetry._rhs(theta, jnp.asarray(y.T), jnp.asarray(u.T),
                              3).T
        theirs = system.rhs(jnp.asarray(y), jnp.asarray(u))
        np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("path", PHYSICS_FILES, ids=lambda p: p.stem)
def test_physics_file_matches_its_registry_system(path):
    phys = family.load_physics(path)
    system = REGISTRY[phys.name]()
    spec = system.spec
    assert (phys.n, phys.m, phys.order, phys.dt) == (spec.n, spec.m,
                                                     spec.order, spec.dt)
    np.testing.assert_array_equal(phys.y0_low, spec.y0_low)
    np.testing.assert_array_equal(phys.y0_high, spec.y0_high)
    lib = make_library(phys.n, phys.m, phys.order)
    assert [tuple(sorted(int(i) - 1 for i in t if i)) for t in
            lib.term_indices] == family.monomials(phys.n + phys.m,
                                                  phys.order)
    np.testing.assert_array_equal(family.theta(phys, phys.order),
                                  system.true_theta(lib))
    rng = np.random.default_rng(5)
    y = rng.uniform(phys.y0_low, phys.y0_high, (32, phys.n))
    u = rng.uniform(-0.5, 0.5, (32, phys.m))
    theta = jnp.asarray(family.theta(phys, phys.order), jnp.float32)
    ours = telemetry._rhs(theta, jnp.asarray(y.T, jnp.float32),
                          jnp.asarray(u.T, jnp.float32), phys.order).T
    theirs = system.rhs(jnp.asarray(y, jnp.float32),
                        jnp.asarray(u, jnp.float32))
    np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-5)


def test_f8_stream_and_theta_bytes_unchanged():
    """The `steady_damage` mix's stream at seed 123 (16 twins, 256
    samples, damage at samples 40 and 120) and the nominal and damaged
    thetas, byte for byte as the generator with the F-8 written into its
    code made them."""
    mix = json.loads((BENCH / "traffic" / "steady_damage.json").read_text())
    ys, us = telemetry.stream(
        F8, 123, 16, 256, y0_frac=mix["y0_frac"],
        input_scale=mix["input_scale"], noise_std=mix["noise_std"],
        onset=np.where(np.arange(16) < 8, 40, 120),
        effectiveness=mix["damage_effectiveness"], gains=mix["sas_gains"])
    assert _sha(ys) == ("6c0cd439b4880e49fd00444fba2e4193"
                        "bf48fdf12d825919969a0268c2c7401c")
    assert _sha(us) == ("d8ba5723f9de9fa176aeeeaf17c6b82b"
                        "41b60464d389f5ce5ae02c0263ed80ea")
    assert _sha(family.theta(F8, 3)) == (
        "e2586e3b5d11197248eae91be8bd14dc5b9039d7a123c51a5194524b7489cd95")
    assert _sha(family.theta(F8, 3, 0.25)) == (
        "9702633df29f38d7c8eff01265b1bb9e2f7ccd7a13f4c4e8666515010c2734aa")


def test_stream_matches_the_program_integrator_for_one_seed():
    """Same initial states, inputs and noise draws: the program's RK4
    (`core.odeint.integrate`, 10 substeps) under F8Crusader, switching to
    DamagedF8 at the onset, and the program's noise rule, give the
    benchmark's stream."""
    n, T, onset = 6, 300, 120
    ys, us = telemetry.stream(F8, 11, n, T, y0_frac=0.5, input_scale=0.03,
                              noise_std=0.002, onset=np.full((n,), onset),
                              effectiveness=0.25, gains=(0.0, 0.0, 0.0))
    rng = np.random.default_rng(11)
    y0, cmd = telemetry.draw(rng, F8, n, T, y0_frac=0.5, input_scale=0.03)
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
    ys, us = telemetry.integrate(F8, np.full((n, 3), 0.02),
                                 np.zeros((n, T, 1)), np.full((n,), T + 1),
                                 effectiveness=0.25, gains=gains)
    np.testing.assert_allclose(np.asarray(us)[..., 0],
                               np.asarray(ys)[:, :T] @ np.asarray(gains),
                               rtol=1e-6, atol=1e-9)
