"""Crash-safe serving: checkpoints, failover + journal replay, degradation.

The contract under test (twin/recovery.py + server/sharded wiring): a shard
crash loses NO telemetry inside the ring horizon — the supervisor restores
the last committed checkpoint, replays the journal suffix, and the guard
re-derives the same ALERT set an uninterrupted run produces.  Overload never
breaks the deadline silently: the degradation ladder sheds work in a fixed
order BEFORE the deadline is violated and restores when pressure clears.
"""
import threading

import jax
import numpy as np
import pytest

from repro.core.merinda import MerindaConfig
from repro.systems.lotka_volterra import LotkaVolterra
from repro.systems.simulate import simulate_batch
from repro.twin.monitor import GuardConfig
from repro.twin.packed import GUARD_KINDS
from repro.twin.recovery import (ChaosConfig, ChaosInjector,
                                 DegradationConfig, DegradationPolicy,
                                 RecoveryConfig, TelemetryJournal,
                                 TwinCheckpointer)
from repro.twin.scheduler import FederationConfig, SlotFederation
from repro.twin.server import TwinServer, TwinServerConfig
from repro.twin.sharded import ShardedTwinConfig, ShardedTwinServer
from repro.twin.stream import StagingBuffer, StagingOverflow


# --------------------------------------------------------------------- #
# telemetry journal: the replay source
# --------------------------------------------------------------------- #
def _chunk(rng, c, n=2, m=1):
    return (rng.normal(size=(c, n)).astype(np.float32),
            rng.normal(size=(c, m)).astype(np.float32))


def test_journal_replays_exact_suffix():
    rng = np.random.default_rng(0)
    j = TelemetryJournal(horizon=100)
    sent_y, sent_u = [], []
    for c in (3, 5, 4):
        y, u = _chunk(rng, c)
        j.append(7, y, u)
        sent_y.append(y)
        sent_u.append(u)
    all_y = np.concatenate(sent_y)
    all_u = np.concatenate(sent_u)
    # seen=4 falls INSIDE the second chunk: the first replayed chunk must be
    # trimmed, and the concatenation must equal the true suffix exactly
    chunks, lost = j.replay_since(7, seen=4)
    assert lost == 0
    got_y = np.concatenate([y for y, _ in chunks])
    got_u = np.concatenate([u for _, u in chunks])
    np.testing.assert_array_equal(got_y, all_y[4:])
    np.testing.assert_array_equal(got_u, all_u[4:])
    # fully caught up -> nothing to replay
    assert j.replay_since(7, seen=12) == ([], 0)
    assert j.total(7) == 12 and j.twin_ids() == [7]


def test_journal_horizon_eviction_counts_lost():
    rng = np.random.default_rng(1)
    j = TelemetryJournal(horizon=6)
    for _ in range(5):                      # 20 samples, horizon keeps <= ~8
        j.append(1, *_chunk(rng, 4))
    chunks, lost = j.replay_since(1, seen=0)
    got = sum(len(y) for y, _ in chunks)
    assert lost > 0 and lost + got == 20    # every sample accounted for
    assert got >= 6                         # horizon worth is recoverable
    # the tail inside the horizon is never lost
    _, lost_tail = j.replay_since(1, seen=20 - 6)
    assert lost_tail == 0


def test_journal_concurrent_appends_keep_per_twin_order():
    j = TelemetryJournal(horizon=10_000)

    def pump(tid):
        for i in range(50):
            j.append(tid, np.full((2, 2), i, np.float32))

    threads = [threading.Thread(target=pump, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for tid in range(4):
        chunks, lost = j.replay_since(tid, seen=0)
        assert lost == 0
        vals = np.concatenate([y for y, _ in chunks])[:, 0]
        assert list(vals) == sorted(vals)   # chronological per twin


# --------------------------------------------------------------------- #
# checkpointer: atomic commits, GC, torn-write fallback
# --------------------------------------------------------------------- #
def _snap(v):
    return lambda: {"w": np.full((4, 3), v, np.float32),
                    "step": np.asarray([v], np.int64)}


def test_checkpointer_roundtrip_and_gc(tmp_path):
    ck = TwinCheckpointer(RecoveryConfig(ckpt_dir=str(tmp_path),
                                         ckpt_every=4, keep=2))
    assert not ck.maybe_save(0, 3, _snap(3))        # off cadence
    for tick in (4, 8, 12):
        assert ck.maybe_save(0, tick, _snap(tick))
    ck.wait()
    assert ck.latest(0) == 12
    tick, state = ck.restore_latest(0, _snap(0)())
    assert tick == 12
    np.testing.assert_array_equal(np.asarray(state["w"]),
                                  np.full((4, 3), 12, np.float32))
    kept = sorted(p.name for p in ck.shard_dir(0).glob("step_*"))
    assert len(kept) <= 2                           # GC keeps the last `keep`


def test_checkpointer_torn_commit_falls_back(tmp_path):
    ck = TwinCheckpointer(RecoveryConfig(ckpt_dir=str(tmp_path),
                                         ckpt_every=1, keep=2))
    ck.maybe_save(0, 1, _snap(1))
    ck.maybe_save(0, 2, _snap(2))
    assert ck.tear_latest(0) == 2                   # crash mid-write of #2
    tick, state = ck.restore_latest(0, _snap(0)())
    assert tick == 1                                # fell back, didn't corrupt
    np.testing.assert_array_equal(np.asarray(state["step"]), [1])


def test_checkpointer_keep_must_cover_torn_fallback(tmp_path):
    with pytest.raises(ValueError, match="keep"):
        RecoveryConfig(ckpt_dir=str(tmp_path), keep=1)


def test_checkpointer_restore_nothing_committed(tmp_path):
    ck = TwinCheckpointer(RecoveryConfig(ckpt_dir=str(tmp_path)))
    assert ck.restore_latest(3, _snap(0)()) == (None, None)


# --------------------------------------------------------------------- #
# chaos injector: deterministic one-shot schedule
# --------------------------------------------------------------------- #
def test_chaos_kill_fires_once_even_past_the_tick():
    inj = ChaosInjector(ChaosConfig(kill_shard=1, kill_at_tick=5))
    assert not inj.should_kill(0, 5)                # wrong shard
    assert not inj.should_kill(1, 4)
    assert inj.should_kill(1, 7)                    # >= semantics, skipped 5/6
    assert not inj.should_kill(1, 8)                # one-shot


def test_chaos_windows():
    inj = ChaosInjector(ChaosConfig(slow_shard=0, slow_s=0.5,
                                    slow_from_tick=3, slow_until_tick=5,
                                    storm_shard=1, storm_factor=3,
                                    storm_from_tick=2, storm_until_tick=4))
    assert inj.slow_delay(0, 2) == 0.0
    assert inj.slow_delay(0, 4) == 0.5
    assert inj.slow_delay(1, 4) == 0.0
    assert inj.storm_extra(1, 3) == 2
    assert inj.storm_extra(1, 4) == 0
    assert not inj.should_tear()                    # not scheduled


# --------------------------------------------------------------------- #
# degradation ladder (policy unit; server wiring below)
# --------------------------------------------------------------------- #
def test_degradation_ladder_up_then_down_with_hysteresis():
    pol = DegradationPolicy(DegradationConfig(enabled=True, hold_ticks=2,
                                              alpha=0.9), deadline_s=1.0)
    levels = []
    for t in range(1, 9):
        pol.observe(t, 0.95)                        # sustained overload
        levels.append(pol.level)
    # one level per hold_ticks, capped at max_level
    assert levels == [1, 1, 2, 2, 3, 3, 3, 3]
    assert pol.shed_guard and pol.defer_refit and pol.skip_promote
    for t in range(9, 30):
        pol.observe(t, 0.01)                        # pressure clears
        if pol.level == 0:
            break
    assert pol.level == 0
    assert not (pol.shed_guard or pol.defer_refit or pol.skip_promote)


def test_degradation_disabled_observes_but_never_sheds():
    pol = DegradationPolicy(DegradationConfig(enabled=False), deadline_s=1.0)
    for t in range(1, 10):
        assert pol.observe(t, 5.0) is None
    assert pol.level == 0 and pol.pressure > 1.0    # pressure still visible


# --------------------------------------------------------------------- #
# federation: dead shards give their slots to the survivors
# --------------------------------------------------------------------- #
def test_federation_dead_shard_grant_flows_to_survivors():
    fed = SlotFederation(FederationConfig(total_slots=8, min_shard_slots=1,
                                          pressure_smooth=1.0), [4, 4, 4])
    base = fed.rebalance([1.0, 1.0, 1.0])
    assert sum(base) == 8 and all(g >= 1 for g in base)
    dead = fed.rebalance([1.0, 0.0, 1.0], alive=[True, False, True])
    assert dead[1] == 0                             # no floor for the dead
    assert sum(dead) <= 8 and dead[0] + dead[2] == sum(dead)
    assert dead[0] >= base[0] and dead[2] >= base[2]
    back = fed.rebalance([1.0, 1.0, 1.0], alive=[True, True, True])
    assert back[1] >= 1                             # restart rejoins the floor


def test_federation_all_dead_parks_the_budget():
    fed = SlotFederation(FederationConfig(total_slots=6, min_shard_slots=1,
                                          pressure_smooth=1.0), [3, 3])
    assert fed.rebalance([1.0, 1.0], alive=[False, False]) == [0, 0]


# --------------------------------------------------------------------- #
# bounded staging: retry/backoff then strict-raise or drop-oldest
# --------------------------------------------------------------------- #
def test_staging_overflow_strict_and_force():
    buf = StagingBuffer(capacity=8)
    y = np.zeros((4, 2), np.float32)
    u = np.zeros((4, 1), np.float32)
    buf.append(0, y, u)
    buf.append(1, y, u)
    with pytest.raises(StagingOverflow):
        buf.append(2, y, u)
    buf.append(2, y, u, force=True)                 # replay bypass
    assert buf.pending_samples() == 12


def test_staging_drop_oldest_preserves_chronology():
    buf = StagingBuffer(capacity=100)
    for i in range(4):
        buf.append(0, np.full((2, 1), i, np.float32),
                   np.zeros((2, 1), np.float32))
    dropped = buf.drop_oldest(3)
    assert dropped >= 3
    staged = buf.swap()
    ys = np.concatenate([y for y, _ in staged[0]])[:, 0]
    # the OLDEST chunks went first; what survives is still in order
    assert list(ys) == sorted(ys) and ys[0] >= 2


def _world():
    sys_ = LotkaVolterra()
    tr = simulate_batch(sys_, jax.random.PRNGKey(0), batch=8, horizon=400,
                       noise_std=0.002)
    return sys_, np.asarray(tr.ys_noisy), np.asarray(tr.us)


@pytest.fixture(scope="module")
def lv_world():
    return _world()


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


def test_server_ingest_backpressure_sheds_oldest(lv_world):
    """Non-strict bounded staging: overload drops the OLDEST staged samples
    (counted) and keeps serving; strict mode raises to the producer."""
    sys_, ys, us = lv_world
    srv = TwinServer(_server_cfg(sys_, staging_capacity=16,
                                 ingest_strict=False, ingest_retries=1,
                                 ingest_backoff_s=1e-4))
    try:
        for k in range(5):                          # 40 > 16 staged samples
            srv.ingest(k % 2, ys[0, k * 8:(k + 1) * 8])
        assert int(srv._m_ingest_dropped.value) > 0
        assert int(srv._m_ingest_retries.value) > 0
        srv.tick()                                  # still serves
        assert srv.twins[0].samples + srv.twins[1].samples <= 16
    finally:
        srv.close()
    strict = TwinServer(_server_cfg(sys_, staging_capacity=8,
                                    ingest_retries=0))
    try:
        strict.ingest(0, ys[0, :8])
        with pytest.raises(StagingOverflow):
            strict.ingest(1, ys[1, :8])
        strict.ingest(1, ys[1, :8], force=True)     # replay path bypasses
    finally:
        strict.close()


# --------------------------------------------------------------------- #
# serving-state snapshot/restore round trip
# --------------------------------------------------------------------- #
def test_server_snapshot_restore_roundtrip(lv_world):
    """A fresh server restored from a snapshot serves indistinguishably:
    same registry, same thetas/predictions, same guard + scheduler state."""
    sys_, ys, us = lv_world
    cfg = _server_cfg(sys_)
    srv = TwinServer(cfg)
    try:
        lib = srv.fleet.model.lib
        true = sys_.true_theta(lib)
        for t in range(6):
            for i in range(4):
                srv.ingest(i, ys[i, t * 20:(t + 1) * 20])
            if t == 1:
                srv.deploy(0, true)
                srv.deploy(1, -true)
            srv.tick()
        snap = jax.tree.map(np.asarray, jax.device_get(srv.snapshot_state()))

        twin = TwinServer(cfg, share_modules_from=srv)
        twin.restore_state(snap)
        assert twin.tick_count == srv.tick_count
        assert sorted(twin.twins) == sorted(srv.twins)
        for tid, rec in srv.twins.items():
            r2 = twin.twins[tid]
            assert (r2.samples, r2.deployed, r2.refit_slot, r2.residency) \
                == (rec.samples, rec.deployed, rec.refit_slot, rec.residency)
            assert r2.divergence == pytest.approx(rec.divergence)
        for col, want in srv.packed.snapshot().items():
            np.testing.assert_array_equal(getattr(twin.packed, col), want,
                                          err_msg=col)
        assert twin.packed.row_of == srv.packed.row_of
        np.testing.assert_array_equal(twin._slot_ring, srv._slot_ring)
        np.testing.assert_array_equal(np.asarray(twin._theta),
                                      np.asarray(srv._theta))
        np.testing.assert_array_equal(
            np.asarray(twin.predict(0, 10)), np.asarray(srv.predict(0, 10)))
        # both continue ticking identically on identical telemetry
        for i in range(4):
            chunk = ys[i, 120:140]
            srv.ingest(i, chunk)
            twin.ingest(i, chunk)
        r1, r2 = srv.tick(), twin.tick()
        assert r1.n_guarded == r2.n_guarded
        assert [e.kind for e in r1.events] == [e.kind for e in r2.events]
    finally:
        srv.close()


def test_restored_server_emits_events_in_live_order(lv_world):
    """A restored server reports a tick's guard events in the order the
    live server does, even when twins became guard-eligible out of ring-row
    order."""
    sys_, ys, _ = lv_world
    cfg = _server_cfg(sys_, deploy_after=10 ** 6)    # guard only
    srv = TwinServer(cfg)
    twin = TwinServer(cfg, share_modules_from=srv)
    try:
        true = sys_.true_theta(srv.fleet.model.lib)
        for t in range(2):
            for i in range(2):
                srv.ingest(i, ys[i, t * 20:(t + 1) * 20])
            srv.tick()
        srv.deploy(1, -true)                # twin 1 is guarded first
        srv.deploy(0, -true)
        twin.restore_state(
            jax.tree.map(np.asarray, jax.device_get(srv.snapshot_state())))
        both = False
        for t in range(2, 6):
            for i in range(2):
                srv.ingest(i, ys[i, t * 20:(t + 1) * 20])
                twin.ingest(i, ys[i, t * 20:(t + 1) * 20])
            live, restored = srv.tick().events, twin.tick().events
            assert ([(e.twin_id, e.kind) for e in restored]
                    == [(e.twin_id, e.kind) for e in live])
            both |= len({e.twin_id for e in live}) == 2
        assert both                         # one tick flagged both twins
    finally:
        srv.close()
        twin.close()


def test_restore_rejects_mismatched_shapes(lv_world):
    sys_, _, _ = lv_world
    srv = TwinServer(_server_cfg(sys_))
    other = TwinServer(_server_cfg(sys_, max_twins=8))
    try:
        snap = jax.tree.map(np.asarray, jax.device_get(srv.snapshot_state()))
        with pytest.raises((ValueError, KeyError)):
            other.packed.load(snap["packed"])
    finally:
        srv.close()
        other.close()


# --------------------------------------------------------------------- #
# chaos lane: fault-injected sharded serving
# --------------------------------------------------------------------- #
def _fleet_cfg(sys_, shards, twins_per_shard, **kw):
    scfg = TwinServerConfig(
        merinda=MerindaConfig(n=2, m=0, order=2, hidden=8, head_hidden=8,
                              n_active=4, dt=sys_.spec.dt),
        max_twins=twins_per_shard, refit_slots=4, capacity=64,
        window=16, stride=8, windows_per_twin=4, steps_per_tick=1,
        deploy_after=10 ** 6,                  # guard-only serving: samples
        min_residency=1,                       # stay under the refit span so
        guard=GuardConfig(window=16))          # no slot ever trains
    return ShardedTwinConfig.uniform(scfg, shards, **kw)


def _alert_sets(fleet):
    alert = GUARD_KINDS.index("ALERT")
    state = {int(s.packed.twin_id[row]) for s in fleet.shards if s is not None
             for row in np.flatnonzero(s.packed.guard_code == alert)}
    events = {e.twin_id for s in fleet.shards if s is not None
              for e in s.events if e.kind == "ALERT"}
    return state, events


def _run_fleet(fleet, sys_, ys, us, n_twins, damaged, ticks, per_tick=2):
    lib = fleet.shards[0].fleet.model.lib
    true = np.asarray(sys_.true_theta(lib))
    rng = np.random.default_rng(7)
    for tid in range(n_twins):
        fleet.register(tid)
    fleet.deploy_many(list(range(n_twins)),
                      np.stack([-true if tid in damaged else true
                                for tid in range(n_twins)]))
    reports = []
    for t in range(ticks):
        for tid in range(n_twins):
            s = t * per_tick
            fleet.ingest(tid, ys[tid % ys.shape[0], s:s + per_tick])
        reports.append(fleet.tick())
    fleet.drain()
    return reports


@pytest.mark.chaos
def test_kill_shard_at_1k_twins_recovers_all_alerts(lv_world, tmp_path):
    """THE crash contract: kill 1 of 4 shards mid-serving at 1024 twins;
    the supervisor restores the last committed checkpoint + replays the
    journal, and the re-derived guard ALERT set EQUALS an uninterrupted
    run's — zero lost alerts inside the ring horizon — within a bounded
    number of recovery ticks."""
    sys_, ys, us = lv_world
    n_twins, shards, ticks = 1024, 4, 16
    damaged = {tid for tid in range(n_twins) if tid % 7 == 3}

    control = ShardedTwinServer(_fleet_cfg(sys_, shards, n_twins // shards))
    try:
        _run_fleet(control, sys_, ys, us, n_twins, damaged, ticks)
        control_state, control_events = _alert_sets(control)
        control_samples = {tid: s.twins[tid].samples
                          for s in control.shards for tid in s.twins}
    finally:
        control.close()
    assert control_state == damaged                 # the guard works at all

    chaos = ShardedTwinServer(_fleet_cfg(
        sys_, shards, n_twins // shards,
        recovery=RecoveryConfig(ckpt_dir=str(tmp_path), ckpt_every=4,
                                restart_delay_ticks=1),
        chaos=ChaosConfig(kill_shard=2, kill_at_tick=12)))
    try:
        reports = _run_fleet(chaos, sys_, ys, us, n_twins, damaged, ticks)
        died = [r for r in reports if r.dead_shards > 0]
        restarts = [rec for r in reports for rec in r.restarted]
        assert died and restarts, "chaos schedule never fired"
        rec = restarts[0]
        assert rec["shard"] == 2
        assert rec["ckpt_tick"] is not None         # restored, not rebuilt
        assert rec["lost"] == 0                     # inside the ring horizon
        assert rec["replayed"] > 0
        # bounded recovery: down for restart_delay (+ the kill tick itself)
        assert rec["down_ticks"] <= 2
        assert int(chaos._m_replay_lost.value) == 0

        chaos_state, chaos_events = _alert_sets(chaos)
        assert chaos_state == control_state         # same final ALERT set
        assert chaos_events == control_events       # same twins ever alerted
        # replay restored every sample the crash interrupted
        chaos_samples = {tid: s.twins[tid].samples
                         for s in chaos.shards for tid in s.twins}
        assert chaos_samples == control_samples
        assert reports[-1].dead_shards == 0
    finally:
        chaos.close()


@pytest.mark.chaos
def test_torn_checkpoint_falls_back_to_previous_commit(lv_world, tmp_path):
    """A crash mid-checkpoint-write (COMMIT torn off) must not poison
    recovery: restore falls back to the previous committed tick and the
    journal covers the longer gap."""
    sys_, ys, us = lv_world
    n_twins = 32
    damaged = {3, 10, 17}
    fleet = ShardedTwinServer(_fleet_cfg(
        sys_, 2, n_twins // 2,
        recovery=RecoveryConfig(ckpt_dir=str(tmp_path), ckpt_every=3,
                                restart_delay_ticks=1),
        chaos=ChaosConfig(kill_shard=1, kill_at_tick=8,
                          torn_checkpoint=True)))
    try:
        reports = _run_fleet(fleet, sys_, ys, us, n_twins, damaged, 14)
        rec = [r for rep in reports for r in rep.restarted][0]
        # newest commit before the kill was tick 6; chaos tore it -> tick 3
        assert rec["ckpt_tick"] == 3
        assert rec["lost"] == 0 and rec["replayed"] > 0
        assert int(fleet.checkpointer._m_torn.value) == 1
        state, _ = _alert_sets(fleet)
        assert state == damaged                     # served through it all
    finally:
        fleet.close()


@pytest.mark.chaos
def test_degradation_sheds_before_deadline_breaks(lv_world, tmp_path):
    """Injected straggler drives pressure ABOVE high_water while staying
    UNDER the deadline: the ladder climbs through guard->refit->promote
    shedding with ZERO deadline violations, then returns to level 0 once
    the stall clears."""
    sys_, ys, us = lv_world
    srv = TwinServer(_server_cfg(
        sys_, deadline_s=0.5,
        degradation=DegradationConfig(enabled=True, high_water=0.8,
                                      low_water=0.5, alpha=0.9,
                                      hold_ticks=1)))
    try:
        for t in range(4):                          # warm up + compile
            for i in range(4):
                srv.ingest(i, ys[i, t * 20:(t + 1) * 20])
            srv.tick()
        srv.reset_latency_stats()                   # compile != overload
        assert srv.degraded_level == 0
        ups0 = int(srv._m_deg_trans["up"].value)
        downs0 = int(srv._m_deg_trans["down"].value)
        srv.inject_delay_s = 0.45                   # 90% of deadline
        seen_levels = []
        for t in range(5):
            rep = srv.tick()
            seen_levels.append(rep.degraded_level)
        assert max(seen_levels) == 3                # full ladder engaged
        assert seen_levels == sorted(seen_levels)   # one level at a time
        assert int(srv._m_shed["guard"].value) > 0
        assert int(srv._m_shed["refit"].value) > 0
        assert int(srv._m_shed["promote"].value) > 0
        assert int(srv._m_violations.value) == 0    # shed BEFORE breaking
        srv.inject_delay_s = 0.0                    # pressure clears
        for t in range(30):
            rep = srv.tick()
            if rep.degraded_level == 0:
                break
        assert rep.degraded_level == 0              # restored, full service
        assert srv._degradation.pressure < 0.5
        assert int(srv._m_violations.value) == 0
        ups = int(srv._m_deg_trans["up"].value) - ups0
        downs = int(srv._m_deg_trans["down"].value) - downs0
        assert ups == downs == 3                    # clean round trip
    finally:
        srv.close()


@pytest.mark.chaos
def test_chaos_slow_shard_degrades_only_that_shard(lv_world):
    """The sharded slow-shard knob lands INSIDE the victim's timed tick:
    its own ladder climbs (visible in the sharded report) while the
    healthy shard keeps full service."""
    sys_, ys, us = lv_world
    base = _server_cfg(
        sys_, deadline_s=0.5,
        degradation=DegradationConfig(enabled=True, high_water=0.8,
                                      low_water=0.5, alpha=0.9,
                                      hold_ticks=1))
    fleet = ShardedTwinServer(ShardedTwinConfig(
        servers=(base, base),
        chaos=ChaosConfig(slow_shard=1, slow_s=0.45,
                          slow_from_tick=3, slow_until_tick=7)))
    try:
        levels = []
        for t in range(8):
            for i in range(6):
                fleet.ingest(i, ys[i, t * 10:(t + 1) * 10])
            rep = fleet.tick()
            levels.append(rep.degraded_level)
        assert max(levels) >= 1                     # victim shed
        assert fleet.shards[0].degraded_level == 0  # healthy shard untouched
        assert int(fleet._m_slow_inj.value) == 4    # ticks 3..6
    finally:
        fleet.close()


@pytest.mark.chaos
def test_storm_duplicates_journal_and_shard_alike(lv_world, tmp_path):
    """An ingest storm (x3 duplication) must hit the journal and the shard
    identically, or replay after a later crash would diverge from what the
    shard actually saw."""
    sys_, ys, us = lv_world
    fleet = ShardedTwinServer(_fleet_cfg(
        sys_, 2, 8,
        recovery=RecoveryConfig(ckpt_dir=str(tmp_path), ckpt_every=2),
        chaos=ChaosConfig(storm_shard=0, storm_factor=3,
                          storm_from_tick=2, storm_until_tick=4)))
    try:
        for t in range(5):
            for tid in (0, 1):                      # shard 0 and shard 1
                fleet.ingest(tid, ys[tid, t * 4:(t + 1) * 4])
            fleet.tick()
        fleet.drain()
        # shard 0's twin saw the duplicated samples; shard 1's did not
        assert fleet.journals[0].total(0) == fleet.shards[0].twins[0].samples
        assert fleet.journals[1].total(1) == fleet.shards[1].twins[1].samples
        assert fleet.journals[0].total(0) > fleet.journals[1].total(1)
    finally:
        fleet.close()
