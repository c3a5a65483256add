"""Fused slot admission: one device program per admitting tick.

`TwinServer._apply_plan` resets every slot a tick admits in one call of
`server._admit` (ring gather + `FleetMerinda.reset_slots`).  The reference is
the per-slot loop it replaced: `ring.windows` of one row, then `reset_slot`
with a fresh `key, sub = split(key)`, in plan order.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.merinda import MerindaConfig
from repro.systems.lotka_volterra import LotkaVolterra
from repro.systems.simulate import simulate_batch
from repro.twin import server as server_mod
from repro.twin.monitor import GuardConfig
from repro.twin.scheduler import SchedulePlan
from repro.twin.server import TwinServer, TwinServerConfig

jax.config.update("jax_platform_name", "cpu")

SLOTS = 8
TWINS = 10


def _server():
    sys_ = LotkaVolterra()
    return TwinServer(TwinServerConfig(
        merinda=MerindaConfig(n=2, m=0, order=2, hidden=8, head_hidden=8,
                              n_active=4, dt=sys_.spec.dt),
        max_twins=TWINS, refit_slots=SLOTS, capacity=128, window=16,
        stride=8, windows_per_twin=4, guard=GuardConfig(window=16)))


@pytest.fixture(scope="module")
def world():
    """A server whose ring holds 100 samples of each twin, and a fleet
    state with nonzero Adam moments and step counters, so that the reset
    of each visibly changes the admitted slots."""
    sys_ = LotkaVolterra()
    tr = simulate_batch(sys_, jax.random.PRNGKey(0), batch=TWINS,
                        horizon=100, noise_std=0.002)
    srv = _server()
    ys, us = np.asarray(tr.ys_noisy), np.asarray(tr.us)
    srv._rstate = srv.ring.ingest(
        srv._rstate, jnp.arange(TWINS, dtype=jnp.int32), ys[:, :100],
        us[:, :100], jnp.full((TWINS,), 100, jnp.int32))
    fs = srv._fstate
    rnd = jax.random.split(jax.random.PRNGKey(7), 2)
    opt = fs["opt"]._replace(
        mu=jax.tree.map(lambda a: jax.random.normal(rnd[0], a.shape),
                        fs["opt"].mu),
        nu=jax.tree.map(lambda a: jax.random.uniform(rnd[1], a.shape),
                        fs["opt"].nu))
    fstate = {"params": fs["params"], "opt": opt, "step": fs["step"] + 3,
              "steps": jnp.arange(SLOTS, dtype=jnp.int32) + 5}
    return srv, fstate


def _loop(srv, fstate, key, ticks):
    """The per-slot admission loop, tick after tick."""
    for tick in ticks:
        for slot, row in tick:
            y_w, u_w = srv.ring.windows(
                srv._rstate, jnp.asarray([row]), window=srv.cfg.window,
                stride=srv.cfg.stride, length=srv.span)
            key, sub = jax.random.split(key)
            fstate = srv.fleet.reset_slot(fstate, jnp.int32(slot), sub,
                                          y_w[0], u_w[0])
    return fstate, key


def _fused(srv, fstate, key, ticks):
    for tick in ticks:
        admit = np.full((2, SLOTS), -1, np.int32)
        admit[1] = srv._scratch
        for i, (slot, row) in enumerate(tick):
            admit[:, i] = slot, row
        fstate, key = server_mod._admit(
            srv.ring, srv.fleet, srv._rstate, fstate, admit, key,
            window=srv.cfg.window, stride=srv.cfg.stride, length=srv.span)
    return fstate, key


CASES = {
    "none": [[]],
    "one": [[(4, 3)]],
    "three_unsorted": [[(5, 0), (1, 7), (3, 2)]],
    "all_slots": [[(6, 1), (0, 9), (7, 4), (2, 2), (5, 8), (1, 0), (3, 6),
                   (4, 5)]],
    "same_slot_twice": [[(2, 1), (6, 4)], [(2, 9)]],
}


@pytest.mark.parametrize("ticks", list(CASES.values()), ids=list(CASES))
def test_fused_admission_matches_per_slot_loop(world, ticks):
    srv, fstate = world
    key = jax.random.PRNGKey(11)
    want, want_key = _loop(srv, fstate, key, ticks)
    got, got_key = _fused(srv, fstate, key, ticks)
    np.testing.assert_array_equal(np.asarray(got_key), np.asarray(want_key))
    # bit-identical everywhere: the admitted slots equal the loop's, and
    # every other slot keeps its leaves (the loop leaves them untouched)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    admitted = {slot for tick in ticks for slot, _ in tick}
    untouched = [s for s in range(SLOTS) if s not in admitted]
    for before, g in zip(jax.tree.leaves(fstate["params"]),
                         jax.tree.leaves(got["params"])):
        np.testing.assert_array_equal(np.asarray(g)[untouched],
                                      np.asarray(before)[untouched])
    steps = np.asarray(got["steps"])
    assert all(steps[s] == 0 for s in admitted)
    for leaf in jax.tree.leaves(got["opt"].mu) + jax.tree.leaves(
            got["opt"].nu):
        assert not np.asarray(leaf)[sorted(admitted)].any()
    if not admitted:
        np.testing.assert_array_equal(np.asarray(got_key), np.asarray(key))


def test_admission_compiles_once_and_launches_only_when_admitting(world):
    srv = _server()
    srv._rstate = world[0]._rstate
    for tid in reversed(range(TWINS)):           # ring rows differ from slots
        srv.register(tid)
    admissions = srv.metrics.counter("twin_slot_admissions_total")
    calls = srv.metrics.counter("twin_admit_calls_total")
    compiled = server_mod._admit._cache_size()
    fstate0, key0 = srv._fstate, srv._key
    tid = iter(range(TWINS))
    free = iter(range(SLOTS))
    ticks = []
    for n in (1, 8, 3):
        residents = [int(srv.packed.twin_id[row]) for row in srv._slot_ring
                     if row != srv._scratch]
        if n > SLOTS - len(residents):            # release the pool
            srv._apply_plan(SchedulePlan(release=residents))
            free = iter(range(SLOTS))
            tid = iter(range(TWINS))
        admit = [(next(free), next(tid)) for _ in range(n)]
        srv._apply_plan(SchedulePlan(admit=admit))
        ticks.append([(slot, srv.twins[t].ring_slot) for slot, t in admit])
    # the server admitted the plan's twins' ring rows into the plan's slots
    want, want_key = _loop(srv, fstate0, key0, ticks)
    np.testing.assert_array_equal(np.asarray(srv._key), np.asarray(want_key))
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(srv._fstate)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert server_mod._admit._cache_size() == compiled + 1
    assert admissions.value == 12
    assert calls.value == 3
    srv._apply_plan(SchedulePlan())                  # admits nothing
    assert calls.value == 3 and admissions.value == 12
    assert server_mod._admit._cache_size() == compiled + 1
