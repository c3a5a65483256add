"""Online twin server: scheduling order, admit/evict, guard, predict."""
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.merinda import MerindaConfig
from repro.systems.lotka_volterra import LotkaVolterra
from repro.systems.simulate import simulate_batch
from repro.twin.monitor import DivergenceGuard, GuardConfig
from repro.twin.packed import PackedFleet
from repro.twin.scheduler import (PackedRefitScheduler, RefitScheduler,
                                  SchedulerConfig, TwinRecord)
from repro.twin.server import TwinServer, TwinServerConfig

jax.config.update("jax_platform_name", "cpu")


# --------------------------------------------------------------------- #
# scheduler policy — every test runs against BOTH planners (the reference
# dict-sorting oracle and the packed device-scored default), since they
# promise identical admission semantics
# --------------------------------------------------------------------- #
class _PackedPlanAdapter:
    """Give `PackedRefitScheduler` the reference's dict-based plan() shape."""

    def __init__(self, cfg):
        self._s = PackedRefitScheduler(cfg)

    def plan(self, twins, max_active=None):
        return self._s.plan_records(twins, max_active=max_active)


@pytest.fixture(params=["reference", "bucketed"])
def _sched(request):
    def build(**kw):
        d = dict(slots=2, min_samples=10, min_residency=2, max_residency=8,
                 evict_margin=0.5)
        d.update(kw)
        cfg = SchedulerConfig(**d)
        return (RefitScheduler(cfg) if request.param == "reference"
                else _PackedPlanAdapter(cfg))
    return build


def test_scheduler_fills_free_slots_by_priority(_sched):
    s = _sched()
    twins = {i: TwinRecord(twin_id=i, ring_slot=i, samples=10 + i)
             for i in range(4)}
    twins[1].divergence = 5.0            # highest priority
    plan = s.plan(twins)
    assert plan.admit[0] == (0, 1)       # diverged twin wins slot 0
    assert len(plan.admit) == 2 and not plan.evict


def test_scheduler_respects_readiness(_sched):
    s = _sched()
    twins = {0: TwinRecord(twin_id=0, ring_slot=0, samples=3)}   # < min
    assert s.plan(twins).admit == []


def test_scheduler_preempts_only_after_min_residency(_sched):
    s = _sched()
    resident = TwinRecord(twin_id=0, ring_slot=0, refit_slot=0, samples=50,
                          deployed=True, samples_at_deploy=50, residency=1)
    challenger = TwinRecord(twin_id=1, ring_slot=1, samples=50,
                            divergence=9.0, deployed=True)
    other = TwinRecord(twin_id=2, ring_slot=2, refit_slot=1, samples=50,
                       deployed=True, samples_at_deploy=50, residency=1)
    twins = {0: resident, 1: challenger, 2: other}
    assert s.plan(twins).evict == []             # too fresh to preempt
    resident.residency = other.residency = 5
    plan = s.plan(twins)
    assert plan.evict == [0]                     # weakest resident goes
    assert (0, 1) in plan.admit


def _resident(tid, slot, **kw):
    d = dict(twin_id=tid, ring_slot=tid, refit_slot=slot, samples=50,
             deployed=True, samples_at_deploy=50, residency=4)
    d.update(kw)
    return TwinRecord(**d)


def test_scheduler_releases_converged_resident(_sched):
    s = _sched()
    resident = _resident(0, 0, residency=9, divergence=0.01)
    other = _resident(2, 1)                    # keeps the pool full
    waiting = TwinRecord(twin_id=1, ring_slot=1, samples=50)
    plan = s.plan({0: resident, 1: waiting, 2: other})
    assert plan.release == [0]
    assert (0, 1) in plan.admit


def test_scheduler_releases_stuck_resident(_sched):
    """A non-converging resident cannot hold its slot forever."""
    s = _sched()
    resident = _resident(0, 0, residency=16, divergence=50.0)  # 2*max_res
    other = _resident(2, 1)
    waiting = TwinRecord(twin_id=1, ring_slot=1, samples=50)
    plan = s.plan({0: resident, 1: waiting, 2: other})
    assert plan.release == [0]


def test_scheduler_free_slots_absorb_waiting_without_release(_sched):
    """When idle slots can take every waiting twin, converged residents
    keep their slots (and their training state)."""
    s = _sched()
    resident = _resident(0, 0, residency=9, divergence=0.01)
    waiting = TwinRecord(twin_id=1, ring_slot=1, samples=50)
    plan = s.plan({0: resident, 1: waiting})   # slot 1 is free
    assert plan.release == [] and plan.evict == []
    assert plan.admit == [(1, 1)]


# --------------------------------------------------------------------- #
# server end-to-end (tiny model so CI stays fast)
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def lv_world():
    sys_ = LotkaVolterra()
    tr = simulate_batch(sys_, jax.random.PRNGKey(0), batch=4, horizon=400,
                        noise_std=0.002)
    return sys_, np.asarray(tr.ys_noisy), np.asarray(tr.us)


def _server(sys_, **kw):
    d = dict(
        merinda=MerindaConfig(n=2, m=0, order=2, hidden=8, head_hidden=8,
                              n_active=4, dt=sys_.spec.dt),
        max_twins=6, refit_slots=2, capacity=128, window=16, stride=8,
        windows_per_twin=4, steps_per_tick=1, deploy_after=2,
        min_residency=2, max_residency=6,
        guard=GuardConfig(window=16))
    d.update(kw)
    return TwinServer(TwinServerConfig(**d))


def test_server_admits_and_refits(lv_world):
    sys_, ys, us = lv_world
    srv = _server(sys_)
    chunk = 10
    reports = []
    for t in range(12):
        for i in range(4):
            srv.ingest(i, ys[i, t * chunk:(t + 1) * chunk],
                       us[i, t * chunk:(t + 1) * chunk])
        reports.append(srv.tick())
    # both slots busy once twins are ready; min_samples = 8*3+16+1 = 41
    assert reports[-1].n_active == 2
    assert reports[-1].n_twins == 4
    admitted = [a for r in reports for a in r.admitted]
    assert len(admitted) >= 2
    # refit losses are finite once slots are active
    assert all(np.isfinite(r.loss) for r in reports if r.loss is not None)
    # every tick's latency was recorded
    assert srv.latency_summary()["ticks"] == 12
    # per-slot step counters advanced (incremental stepping)
    assert int(srv._fstate["steps"].max()) > 0


def test_tick_loss_is_mean_of_last_train_loss_over_assigned_slots(lv_world):
    """`TickReport.loss` is the mean, over the slots holding a twin, of the
    last train step's per-slot loss — whether the tick promotes (the loss
    is then read back with the promotion scores) or not — and None on the
    degraded paths that train nothing (level 2 defers the steps but may
    still promote, level 3 skips promotion too)."""
    sys_, ys, us = lv_world
    srv = _server(sys_)
    fleet = srv.fleet
    step, recover = fleet.train_step_per_slot, fleet.recover_all
    last = {}

    def train_step_per_slot(state, y_win, u_win):
        out = step(state, y_win, u_win)
        last["loss"] = out[1]
        return out

    def recover_all(*args):
        last["promoted"] = True
        return recover(*args)

    fleet.train_step_per_slot = train_step_per_slot
    fleet.recover_all = recover_all
    levels = [0] * 8 + [2, 2, 3, 3, 0, 0, 2, 0, 0, 3, 0, 0]
    seen = set()
    chunk = 10
    for t, level in enumerate(levels):
        for i in range(4):
            srv.ingest(i, ys[i, t * chunk:(t + 1) * chunk],
                       us[i, t * chunk:(t + 1) * chunk])
        srv._degradation.level = level
        last.clear()
        report = srv.tick()
        promoted = last.get("promoted", False)
        if level >= 2 or "loss" not in last:
            assert "loss" not in last and report.loss is None
            if level == 3:
                assert not promoted
            seen.add(("deferred", promoted))
            continue
        slots = srv._occupied_slots()
        assert slots.size
        want = float(np.mean(np.asarray(last["loss"])[slots]))
        assert report.loss == want
        seen.add(("trained", promoted))
    # every path ran: train with and without promotion, a deferred
    # promotion (no loss to read) and a deferred tick that promotes nothing
    assert seen == {("trained", True), ("trained", False),
                    ("deferred", True), ("deferred", False)}


def test_server_slot_turnover_rotates_fleet(lv_world):
    """With 4 ready twins and 2 slots, releases/evictions must rotate the
    pool: every twin gets slot time eventually."""
    sys_, ys, us = lv_world
    srv = _server(sys_, max_residency=3, min_residency=1)
    chunk = 10
    slotted = set()
    for t in range(30):
        for i in range(4):
            lo = (t * chunk) % 300
            srv.ingest(i, ys[i, lo:lo + chunk], us[i, lo:lo + chunk])
        rep = srv.tick()
        slotted |= {tid for _, tid in rep.admitted}
    assert slotted == {0, 1, 2, 3}


def test_packed_mirrors_track_records_through_serving(lv_world):
    """The packed columns are the server's only per-twin state; through
    serving the device shadows (float32 divergence, `resident`) must stay
    in lockstep with their host truth — a stale shadow silently mis-ranks
    candidates, which the from_records equivalence tests can never see —
    the `twins` view must read each row's columns, and the slot ring and
    `refit_slot` must name the same residents."""
    sys_, ys, us = lv_world
    srv = _server(sys_, max_residency=3, min_residency=1)
    chunk = 10
    for t in range(30):
        for i in range(4):
            lo = (t * chunk) % 300
            srv.ingest(i, ys[i, lo:lo + chunk], us[i, lo:lo + chunk])
        srv.tick()
        p = srv.packed
        p.check_mirrors()
        assert sorted(srv.twins) == [0, 1, 2, 3]
        for tid, rec in srv.twins.items():
            row = rec.ring_slot
            assert p.row_of[tid] == row and p.registered[row]
            assert rec.twin_id == p.twin_id[row] == tid
            assert rec.samples == p.samples[row]
            assert rec.samples_at_deploy == p.samples_at_deploy[row]
            assert rec.deployed == p.deployed[row]
            assert rec.divergence == p.divergence[row]
            assert rec.residency == p.residency[row]
            assert rec.steps_in_slot == p.steps_in_slot[row]
            assert rec.deploy_tick == p.deploy_tick[row]
            assert rec.admitted_tick == p.admitted_tick[row]
            slot = -1 if rec.refit_slot is None else rec.refit_slot
            assert slot == p.refit_slot[row]
            assert (slot >= 0) == (row in srv._slot_ring)
        for slot, row in enumerate(srv._slot_ring):
            if row != srv._scratch:
                assert p.refit_slot[row] == slot


def test_packed_register_is_thread_safe():
    """Ingest threads register twins while the serving side iterates the
    ids: every id gets exactly one row, the rows are dense, and the id map
    agrees with the `twin_id` column."""
    fleet = PackedFleet(1024)
    errors = []

    def guarded(fn, *args):
        try:
            fn(*args)
        except Exception as e:      # reported below, not lost in a thread
            errors.append(e)

    def register(k):              # every id, in an order of its own
        for tid in np.random.default_rng(k).permutation(1000).tolist():
            fleet.register(tid)

    def iterate():
        for _ in range(300):
            for tid in fleet.ids():
                fleet.row_of[tid]

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = ([threading.Thread(target=guarded, args=(register, k))
                    for k in range(16)]
                   + [threading.Thread(target=guarded, args=(iterate,))
                      for _ in range(2)])
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert not errors
    assert sorted(fleet.row_of) == list(range(1000))
    assert sorted(fleet.row_of.values()) == list(range(1000))
    for tid, row in fleet.row_of.items():
        assert fleet.twin_id[row] == tid and fleet.registered[row]
    assert int(fleet.registered.sum()) == 1000


def test_twins_view_is_a_copy(lv_world):
    """A record read from `srv.twins` is a copy: mutating it changes
    neither the server's snapshot nor its next plan."""
    sys_, ys, us = lv_world
    ctl = _server(sys_)
    srv = TwinServer(ctl.cfg, share_modules_from=ctl)

    def feed(t):
        for s in (ctl, srv):
            for i in range(4):
                s.ingest(i, ys[i, t * 10:(t + 1) * 10],
                         us[i, t * 10:(t + 1) * 10])

    for t in range(4):              # 40 samples each: one short of ready
        feed(t)
        ctl.tick()
        srv.tick()
    before = srv.snapshot_state()
    for tid in (2, 3):
        rec = srv.twins[tid]
        rec.samples += 1000         # would outrank twins 0 and 1
        rec.divergence = 50.0
        rec.refit_slot = 0
        rec.steps_in_slot = rec.deploy_tick = 99
    jax.tree.map(np.testing.assert_array_equal, srv.snapshot_state(), before)
    assert srv.twins[2].samples == 40 and srv.twins[3].refit_slot is None
    feed(4)
    want, got = ctl.tick(), srv.tick()
    assert want.admitted == [(0, 0), (1, 1)]
    assert got.admitted == want.admitted


def test_guard_fires_on_perturbed_dynamics(lv_world):
    """Deploy the TRUE model, then the truth with flipped signs: the guard
    must stay quiet on the former and fire REFIT/ALERT on the latter."""
    sys_, ys, us = lv_world
    srv = _server(sys_, refit_slots=2, deploy_after=10 ** 6)  # no auto-deploy
    lib = srv.fleet.model.lib
    true = sys_.true_theta(lib)
    chunk = 10
    for t in range(6):    # enough samples for the guard window
        for i in range(2):
            srv.ingest(i, ys[i, t * chunk:(t + 1) * chunk],
                       us[i, t * chunk:(t + 1) * chunk])
        srv.tick()
    srv.deploy(0, true)
    srv.deploy(1, -true)           # wrong physics
    events = []
    for t in range(6, 10):
        for i in range(2):
            srv.ingest(i, ys[i, t * chunk:(t + 1) * chunk],
                       us[i, t * chunk:(t + 1) * chunk])
        events += srv.tick().events
    assert srv.twins[0].divergence < 0.05          # true model tracks
    assert srv.twins[1].divergence > 0.1           # wrong model diverges
    kinds = {(e.twin_id, e.kind) for e in events}
    assert any(tid == 1 for tid, _ in kinds)       # guard fired for twin 1
    assert all(tid != 0 for tid, _ in kinds)       # ...and only for twin 1


def test_flush_handles_backlog_beyond_capacity(lv_world):
    """Telemetry staged faster than ticks must not crash the fused flush;
    only the newest capacity-worth of samples survives."""
    sys_, ys, us = lv_world
    srv = _server(sys_, capacity=128)
    srv.ingest(0, ys[0, :100], us[0, :100])
    srv.ingest(0, ys[0, 100:200], us[0, 100:200])   # backlog: 200 > 128
    srv.tick()
    assert srv.twins[0].samples == 200              # telemetry accounting
    assert int(srv._rstate["count"][0]) == 128      # ring kept the newest
    yl, _ = srv.ring.latest(srv._rstate, jnp.asarray([0]), 10)
    np.testing.assert_allclose(np.asarray(yl[0]), ys[0, 189:200], rtol=1e-6)


def test_flush_capacity_not_multiple_of_pad(lv_world):
    """flush_pad rounding of the chunk axis must not lap a ring whose
    capacity is not a multiple of the pad quantum."""
    sys_, ys, us = lv_world
    srv = _server(sys_, capacity=100)           # 100 % 8 != 0
    srv.ingest(0, ys[0, :97], us[0, :97])       # rounds to 104 without cap
    srv.tick()
    assert int(srv._rstate["count"][0]) == 97
    yl, _ = srv.ring.latest(srv._rstate, jnp.asarray([0]), 5)
    np.testing.assert_allclose(np.asarray(yl[0]), ys[0, 91:97], rtol=1e-6)


def test_predict_shapes_and_rollout(lv_world):
    sys_, ys, us = lv_world
    srv = _server(sys_)
    lib = srv.fleet.model.lib
    srv.register(0)
    for t in range(5):
        srv.ingest(0, ys[0, t * 10:(t + 1) * 10], us[0, t * 10:(t + 1) * 10])
    srv.tick()
    with pytest.raises(RuntimeError):
        srv.predict(0, 10)                         # nothing deployed yet
    srv.register(5)
    srv.deploy(5, sys_.true_theta(lib))
    with pytest.raises(RuntimeError):
        srv.predict(5, 10)                         # deployed, no telemetry
    srv.deploy(0, sys_.true_theta(lib))
    out = srv.predict(0, 12)
    assert out.shape == (13, 2)
    assert bool(jnp.all(jnp.isfinite(out)))
    # rollout starts from the newest observed state
    np.testing.assert_allclose(np.asarray(out[0]), ys[0, 49], rtol=1e-5)


def test_latency_summary_tracks_deadline(lv_world):
    sys_, ys, us = lv_world
    srv = _server(sys_)
    for t in range(3):
        srv.ingest(0, ys[0, t * 10:(t + 1) * 10], us[0, t * 10:(t + 1) * 10])
        srv.tick()
    s = srv.latency_summary()
    assert s["ticks"] == 3 and s["p50_ms"] > 0 and s["deadline_s"] == 1.0
    srv.reset_latency_stats()
    assert srv.latency_summary() == {"ticks": 0}
