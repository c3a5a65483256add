"""Sharded serving: federation rebalance, async ingest, guard rotation."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.merinda import MerindaConfig
from repro.systems.lotka_volterra import LotkaVolterra
from repro.systems.simulate import simulate_batch
from repro.twin.monitor import GuardConfig, GuardRotation
from repro.twin.scheduler import (FederationConfig, RefitScheduler,
                                  SchedulerConfig, SlotFederation, TwinRecord)
from repro.twin.server import TwinServer, TwinServerConfig
from repro.twin.sharded import ShardedTwinConfig, ShardedTwinServer
from repro.twin.stream import StagingBuffer, prepare_flush

jax.config.update("jax_platform_name", "cpu")


# --------------------------------------------------------------------- #
# guard rotation (pure host logic)
# --------------------------------------------------------------------- #
def test_rotation_covers_every_twin_within_bound():
    """Round-robin freshness floor: every eligible twin is scored within
    ceil(twins / budget) ticks, regardless of the divergence pattern."""
    n, budget = 23, 5
    rot = GuardRotation(budget=budget, carry=2)
    rows = np.arange(n)
    div = np.zeros(n)
    div[[4, 17]] = 3.0                                  # permanently flagged
    bound = -(-n // budget)                              # ceil(23/5) = 5
    last_scored = {row: 0 for row in range(n)}
    for tick in range(1, 4 * bound + 1):
        for row in rot.select(rows, div, threshold=0.1):
            last_scored[int(row)] = tick
        gaps = [tick - t for t in last_scored.values()]
        assert max(gaps) <= bound, f"tick {tick}: twin starved {max(gaps)}"


def test_rotation_carry_rescores_flagged_every_tick():
    rot = GuardRotation(budget=2, carry=2)
    rows = np.arange(10)
    div = np.zeros(10)
    div[7] = 5.0                                        # flagged
    hits = sum(7 in rot.select(rows, div, threshold=0.1) for _ in range(5))
    assert hits == 5                                    # carry-over every tick


def test_rotation_fixed_fused_width():
    rot = GuardRotation(budget=3, carry=1)
    assert rot.size == 4
    pick = rot.select(np.arange(3), np.asarray([0.0, 9.0, 9.0]),
                      threshold=0.1)
    assert len(pick) <= 4 and len(set(pick.tolist())) == len(pick)


# --------------------------------------------------------------------- #
# staging buffer + flush preparation (thread-safety, overflow assert)
# --------------------------------------------------------------------- #
def test_staging_swap_is_atomic_handoff():
    buf = StagingBuffer()
    buf.append(0, np.ones((4, 2), np.float32), np.zeros((4, 1), np.float32))
    taken = buf.swap()
    assert list(taken) == [0] and buf.empty()
    assert buf.staged_samples == 4 and buf.swapped_samples == 4
    assert buf.swap() == {}


def test_staging_concurrent_appends_lose_nothing():
    buf = StagingBuffer()
    per_thread, n_threads = 200, 8

    def pump(row):
        for _ in range(per_thread):
            buf.append(row, np.ones((1, 2), np.float32),
                       np.zeros((1, 1), np.float32))

    threads = [threading.Thread(target=pump, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    taken = buf.swap()
    total = sum(len(c[0]) for chunks in taken.values() for c in chunks)
    assert total == per_thread * n_threads


def test_prepare_flush_overflow_raises_not_wraps():
    """A chunk the padded buffer cannot hold must raise, not silently lap."""
    staged = {0: [(np.ones((12, 2), np.float32),
                   np.zeros((12, 1), np.float32))]}
    with pytest.raises(RuntimeError, match="lap"):
        prepare_flush(staged, capacity=8, pad=4, scratch=3, n=2, m=1)


def test_prepare_flush_accounts_raw_received():
    staged = {1: [(np.ones((30, 2), np.float32),
                   np.zeros((30, 1), np.float32)),
                  (2 * np.ones((10, 2), np.float32),
                   np.zeros((10, 1), np.float32))]}
    batch = prepare_flush(staged, capacity=32, pad=8, scratch=5, n=2, m=1)
    assert batch.received == {1: 40}            # raw, pre-truncation
    assert int(batch.counts[0]) == 32           # newest capacity-worth kept
    np.testing.assert_allclose(batch.ys[0, -10:], 2.0)


# --------------------------------------------------------------------- #
# scheduler: federation grant cap
# --------------------------------------------------------------------- #
def _sched(**kw):
    d = dict(slots=4, min_samples=10, min_residency=2, max_residency=8,
             evict_margin=0.5)
    d.update(kw)
    return RefitScheduler(SchedulerConfig(**d))


def _resident(tid, slot, **kw):
    d = dict(twin_id=tid, ring_slot=tid, refit_slot=slot, samples=50,
             deployed=True, samples_at_deploy=50, residency=4)
    d.update(kw)
    return TwinRecord(**d)


def test_plan_respects_grant_cap_on_admission():
    s = _sched()
    twins = {i: TwinRecord(twin_id=i, ring_slot=i, samples=20)
             for i in range(6)}
    plan = s.plan(twins, max_active=2)
    assert len(plan.admit) == 2                 # 4 physical, grant only 2


def test_plan_sheds_lowest_priority_when_grant_shrinks():
    s = _sched()
    twins = {i: _resident(i, i) for i in range(4)}
    twins[2].divergence = 9.0                   # highest priority: keep
    plan = s.plan(twins, max_active=1)
    assert len(plan.release) == 3 and 2 not in plan.release


def test_federation_moves_slots_toward_pressure():
    fed = SlotFederation(FederationConfig(total_slots=6, min_shard_slots=1,
                                          pressure_smooth=1.0), [4, 4])
    assert fed.rebalance([1.0, 1.0]) == [3, 3]          # symmetric demand
    grants = fed.rebalance([0.1, 10.0])
    assert grants[1] > grants[0] and sum(grants) == 6
    assert grants == [2, 4]                             # clamped at physical


def test_federation_floor_keeps_idle_shard_alive():
    fed = SlotFederation(FederationConfig(total_slots=4, min_shard_slots=1,
                                          pressure_smooth=1.0), [4, 4])
    assert fed.rebalance([0.0, 50.0]) == [1, 3]


# --------------------------------------------------------------------- #
# sharded server end-to-end (tiny model so CI stays fast)
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def lv_world():
    sys_ = LotkaVolterra()
    tr = simulate_batch(sys_, jax.random.PRNGKey(0), batch=8, horizon=400,
                        noise_std=0.002)
    return sys_, np.asarray(tr.ys_noisy), np.asarray(tr.us)


def _server_cfg(sys_, **kw):
    d = dict(
        merinda=MerindaConfig(n=2, m=0, order=2, hidden=8, head_hidden=8,
                              n_active=4, dt=sys_.spec.dt),
        max_twins=6, refit_slots=2, capacity=128, window=16, stride=8,
        windows_per_twin=4, steps_per_tick=1, deploy_after=2,
        min_residency=1, max_residency=4,
        guard=GuardConfig(window=16))
    d.update(kw)
    return TwinServerConfig(**d)


def test_sharded_routes_and_serves(lv_world):
    sys_, ys, us = lv_world
    srv = ShardedTwinServer(
        ShardedTwinConfig.uniform(_server_cfg(sys_), 2, total_slots=3))
    try:
        for t in range(8):
            for i in range(6):
                srv.ingest(i, ys[i, t * 10:(t + 1) * 10],
                           us[i, t * 10:(t + 1) * 10])
            rep = srv.tick()
        assert rep.n_twins == 6
        assert rep.n_active <= 3                 # global grant respected
        assert sum(srv.grants) == 3
        # placement is modulo and sticky
        assert srv.shard_of(4) == 0 and srv.shard_of(5) == 1
        assert sorted(srv.shards[0].twins) == [0, 2, 4]
        assert srv.latency_summary()["ticks"] == 8
    finally:
        srv.close()


def test_sharded_grants_follow_divergence_pressure(lv_world):
    """Slots migrate toward the shard whose twins diverged: deploy WRONG
    physics on shard 1's twins, right physics on shard 0's."""
    sys_, ys, us = lv_world
    srv = ShardedTwinServer(ShardedTwinConfig.uniform(
        _server_cfg(sys_, deploy_after=10 ** 6), 2,
        total_slots=3, rebalance_every=2, pressure_smooth=1.0))
    try:
        lib = srv.shards[0].fleet.model.lib
        true = sys_.true_theta(lib)
        srv.deploy_many([0, 2, 4], true)         # shard 0: healthy models
        srv.deploy_many([1, 3, 5], -true)        # shard 1: wrong physics
        for t in range(8):
            for i in range(6):
                srv.ingest(i, ys[i, t * 10:(t + 1) * 10],
                           us[i, t * 10:(t + 1) * 10])
            srv.tick()
        assert srv.grants[1] > srv.grants[0]     # slots followed the pressure
        assert any(e.twin_id % 2 == 1 for e in
                   [e for s in srv.shards for e in s.events])
    finally:
        srv.close()


def test_async_ingest_no_drops_no_duplicates(lv_world):
    """Concurrent ingest threads + serving ticks: after drain, per-twin
    sample accounting and ring write heads both match exactly what was sent
    (no drops, no duplicates)."""
    sys_, ys, us = lv_world
    srv = TwinServer(_server_cfg(sys_, max_twins=4, capacity=128,
                                 async_ingest=True))
    try:
        n_tw, chunks, chunk = 4, 24, 5
        sent = {i: 0 for i in range(n_tw)}

        def pump(i):
            for c in range(chunks):
                lo = (c * chunk) % 300
                srv.ingest(i, ys[i, lo:lo + chunk], us[i, lo:lo + chunk])
                sent[i] += chunk

        threads = [threading.Thread(target=pump, args=(i,))
                   for i in range(n_tw)]
        for t in threads:
            t.start()
        for _ in range(6):
            srv.tick()
        for t in threads:
            t.join()
        srv.drain()
        for i in range(n_tw):
            rec = srv.twins[i]
            assert rec.samples == sent[i] == chunks * chunk
            # ring write head counts every sample exactly once
            assert int(srv._rstate["count"][rec.ring_slot]) == sent[i]
    finally:
        srv.close()


def test_async_ingest_preserves_chronology(lv_world):
    """Samples must land in the ring in ingest order even when flushes are
    prepared on the background thread across several ticks."""
    sys_, ys, us = lv_world
    srv = TwinServer(_server_cfg(sys_, max_twins=2, async_ingest=True))
    try:
        for c in range(10):
            srv.ingest(0, ys[0, c * 10:(c + 1) * 10],
                       us[0, c * 10:(c + 1) * 10])
            if c % 3 == 0:
                srv.tick()
        srv.drain()
        yl, _ = srv.ring.latest(srv._rstate, jnp.asarray([0]), 20)
        np.testing.assert_allclose(np.asarray(yl[0]), ys[0, 79:100],
                                   rtol=1e-6)
    finally:
        srv.close()


def test_guard_rotation_budget_bounds_fused_width(lv_world):
    """With guard_budget set, every tick scores at most budget+carry twins,
    and all deployed twins are still scored within the rotation bound."""
    sys_, ys, us = lv_world
    budget = 2
    srv = TwinServer(_server_cfg(sys_, deploy_after=10 ** 6,
                                 guard_budget=budget, guard_carry=1))
    lib = srv.fleet.model.lib
    true = sys_.true_theta(lib)
    n_tw = 6
    for t in range(5):                  # enough samples for the guard window
        for i in range(n_tw):
            srv.ingest(i, ys[i, t * 10:(t + 1) * 10],
                       us[i, t * 10:(t + 1) * 10])
        srv.tick()
    for i in range(n_tw):
        srv.deploy(i, true)
    bound = -(-n_tw // budget)          # ceil(6/2) = 3 ticks
    scored_ticks = {i: None for i in range(n_tw)}
    for t in range(5, 5 + bound):
        for i in range(n_tw):
            srv.ingest(i, ys[i, t * 10:(t + 1) * 10],
                       us[i, t * 10:(t + 1) * 10])
        rep = srv.tick()
        assert rep.n_guarded <= budget + 1
        for i in range(n_tw):
            prev = srv.twins[i].divergence
            if scored_ticks[i] is None and prev != 0.0:
                scored_ticks[i] = rep.tick
    # every deployed twin was folded into the EMA within the bound — the
    # true model tracks, so scores are tiny but nonzero
    assert all(v is not None for v in scored_ticks.values())


def test_shared_modules_require_identical_shapes(lv_world):
    sys_, _, _ = lv_world
    a = TwinServer(_server_cfg(sys_))
    with pytest.raises(ValueError, match="identical"):
        TwinServer(_server_cfg(sys_, refit_slots=4), share_modules_from=a)
    b = TwinServer(_server_cfg(sys_), share_modules_from=a)
    assert b.ring is a.ring and b.fleet is a.fleet and b.guard is a.guard
