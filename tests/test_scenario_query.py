"""A what-if query's device path (twin/server.py `scenario`).

A query is one gather program (`_scenario_gather`: the twin's recent
thetas and newest observed state), one rollout program and one read back
of the rollout's four results packed into one array.  These tests pin that
path bit for bit against the path it replaced — `ring.latest` on a device
row, the history row and the newest column indexed eagerly, the unpacked
`_roll_impl` and four reads — for every output and for the `theta_hist`
and `y0` that reach `ScenarioRunner.rollout`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.merinda import MerindaConfig
from repro.obs import Tracer
from repro.systems.simulate import simulate_batch
from repro.systems.van_der_pol import VanDerPol
from repro.twin import server as server_mod
from repro.twin.monitor import GuardConfig
from repro.twin.scenario import ScenarioConfig
from repro.twin.server import TwinServer, TwinServerConfig

jax.config.update("jax_platform_name", "cpu")

pytestmark = pytest.mark.scenario

_CAPACITY = 64          # ring samples per twin: twin 2 below laps its ring
_TWINS = 3
_ENSEMBLE = 4


@pytest.fixture(scope="module")
def world():
    sys_ = VanDerPol()
    tr = simulate_batch(sys_, jax.random.PRNGKey(3), batch=_TWINS,
                        horizon=120, noise_std=0.002)
    return sys_, np.asarray(tr.ys_noisy), np.asarray(tr.us)


def _server(sys_, tracer=None, use_pallas=False):
    cfg = TwinServerConfig(
        merinda=MerindaConfig(n=2, m=1, order=sys_.spec.order, hidden=8,
                              head_hidden=8, n_active=8, dt=sys_.spec.dt,
                              use_pallas=use_pallas),
        max_twins=6, refit_slots=2, capacity=_CAPACITY, window=16, stride=8,
        windows_per_twin=4, steps_per_tick=1, deploy_after=2,
        min_residency=2, max_residency=6, guard=GuardConfig(window=16),
        scenario=ScenarioConfig(max_k=8, ensemble=_ENSEMBLE))
    return TwinServer(cfg, tracer=tracer)


@pytest.fixture(scope="module", params=["jnp", "pallas"])
def served(world, request):
    """Three twins that differ in what a query gathers: twin 0 has one
    deploy (history count 1, below the ensemble) and 30 samples; twin 1
    exactly `ensemble` deploys and 50 samples; twin 2 six deploys (its
    history has wrapped) and 110 samples (its ring has wrapped).  The
    rollout runs the jnp reference or the Pallas kernel (interpreted)."""
    sys_, ys, us = world
    srv = _server(sys_, use_pallas=request.param == "pallas")
    theta = np.asarray(sys_.true_theta(srv.fleet.model.lib), np.float32)
    for twin, (deploys, samples) in enumerate([(1, 30), (_ENSEMBLE, 50),
                                               (6, 110)]):
        srv.register(twin)
        for j in range(deploys):
            srv.deploy(twin, theta * (1.0 + 0.02 * j))
        for t in range(0, samples, 10):
            srv.ingest(twin, ys[twin, t:t + 10], us[twin, t:t + 10])
            srv.drain()         # one flush can write at most `capacity`
    rows = [srv.twins[t].ring_slot for t in range(_TWINS)]
    counts = np.asarray(srv._rstate["count"])
    assert [int(srv._hist_count[r]) for r in rows] == [1, _ENSEMBLE, 6]
    assert int(counts[rows[2]]) > _CAPACITY
    return srv


def _eager_answer(srv, twin_id, us):
    """The query as the eager path answered it: (theta_hist, y0, [center,
    lo, hi, confidence])."""
    row = srv.twins[twin_id].ring_slot
    ys, _ = srv.ring.latest(srv._rstate, jnp.asarray([row]), 0)
    theta_hist = srv._theta_hist[row]
    count = int(srv._hist_count[row])
    y0 = ys[0, -1, :]
    out = jax.jit(srv.scenario_runner._roll_impl)(
        jnp.asarray(theta_hist), jnp.int32(count),
        jnp.asarray(y0, jnp.float32), jnp.asarray(us, jnp.float32))
    return np.asarray(theta_hist), np.asarray(y0), [np.asarray(x)
                                                    for x in out]


def _query(srv, twin_id, horizon, us, monkeypatch):
    """`srv.scenario`, with the inputs that reach the runner's `rollout`."""
    seen = []
    orig = srv.scenario_runner.rollout

    def rollout(theta_hist, count, y0, us):
        seen.append((np.asarray(theta_hist), count, np.asarray(y0)))
        return orig(theta_hist, count, y0, us)
    monkeypatch.setattr(srv.scenario_runner, "rollout", rollout)
    res = srv.scenario(twin_id, horizon, us)
    monkeypatch.undo()
    assert len(seen) == 1
    return res, seen[0]


@pytest.mark.parametrize("horizon", [7, 20])
@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("twin", range(_TWINS))
def test_query_matches_eager_path(served, monkeypatch, twin, k, horizon):
    us = np.random.default_rng(100 * twin + 10 * k + horizon).normal(
        0.0, 0.05, (k, horizon, 1)).astype(np.float32)
    res, (theta_hist, count, y0) = _query(served, twin, horizon, us,
                                          monkeypatch)
    ref_hist, ref_y0, ref = _eager_answer(served, twin, us)
    assert count == int(served._hist_count[served.twins[twin].ring_slot])
    np.testing.assert_array_equal(theta_hist, ref_hist)
    np.testing.assert_array_equal(y0, ref_y0)
    assert res.k == k
    for got, want in zip((res.ys, res.lo, res.hi, res.confidence), ref):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)


def test_y0_is_the_newest_sample(world, served):
    """y0 is the last sample each twin sent, wrapped ring or not."""
    _, ys, _ = world
    for twin, samples in enumerate([30, 50, 110]):
        row = served.twins[twin].ring_slot
        _, y0 = server_mod._scenario_gather(
            served.ring, served._rstate, served._theta_hist, np.int32(row))
        np.testing.assert_array_equal(np.asarray(y0),
                                      ys[twin, samples - 1].astype(np.float32))


def test_other_twin_compiles_nothing(served):
    us = np.zeros((3, 9, 1), np.float32)
    served.scenario(0, 9, us)
    compiles = []

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for twin in (1, 2):
            assert served.scenario(twin, 9, us).k == 3
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []


def test_one_gather_one_read_per_query(world):
    sys_, ys, us = world
    tracer = Tracer(sample_every=1)
    srv = _server(sys_, tracer=tracer)
    theta = sys_.true_theta(srv.fleet.model.lib)
    for twin in range(2):
        srv.register(twin)
        srv.deploy(twin, theta)
        srv.ingest(twin, ys[twin, :20], us[twin, :20])
    srv.tick()

    def spans(name, site=None):
        return [e for e in tracer.to_chrome_trace()["traceEvents"]
                if e["ph"] == "X" and e["name"] == name
                and (site is None or e["args"].get("site") == site)]
    for i, (twin, k) in enumerate([(0, 1), (1, 8), (0, 4)]):
        srv.scenario(twin, 10, k=k)
        assert len(spans("scenario")) == len(spans("gather")) == i + 1
        assert len(spans("sync", "scenario.result")) == i + 1


@pytest.mark.parametrize("missing, message", [
    ("deploy", "no deployed model"), ("telemetry", "no telemetry")])
def test_unservable_twin_raises_before_device_work(world, monkeypatch,
                                                   missing, message):
    sys_, ys, us = world
    srv = _server(sys_)
    srv.register(0)
    if missing == "telemetry":
        srv.deploy(0, sys_.true_theta(srv.fleet.model.lib))
    calls = []
    monkeypatch.setattr(server_mod, "_scenario_gather",
                        lambda *a: calls.append("gather"))
    monkeypatch.setattr(srv.scenario_runner, "rollout",
                        lambda *a: calls.append("rollout"))
    with pytest.raises(RuntimeError, match=message):
        srv.scenario(0, 10)
    assert calls == []
