"""Federation layer: wire codec, coordinator serving, kill + restart.

Three tiers, cheapest first:

  * wire codec unit tests — pure host-side roundtrips of the versioned
    JSON-header + array-blob format, the version gate, and the front-door
    trust boundary (no subprocesses);
  * one live `FederatedTwinServer` (2 workers) exercised through the
    `TwinService` surface: routed batched ingest, tick fan-out, predict
    across the pipe (including the worker-survives-refusal contract), the
    TCP front door, fleet snapshots;
  * the crash contract (`chaos` marker): SIGKILL a worker mid-serve and
    assert 0 lost samples after journal-tail replay, slot grants migrating
    to the survivor while the worker is down, and the grant shape restored
    after the supervised restart — the ISSUE 9 acceptance semantics.

Cross-implementation guard-event equality lives in
tests/test_service_conformance.py; this file owns the federation-only
behavior.
"""
import socket
import struct

import jax
import numpy as np
import pytest

from repro.core.merinda import MerindaConfig
from repro.systems.lotka_volterra import LotkaVolterra
from repro.systems.simulate import simulate_batch
from repro.twin import (FederatedTwinConfig, FederatedTwinServer,
                        FrontDoorClient, GuardConfig, RecoveryConfig,
                        TwinServerConfig, conforms)
from repro.twin import wire as W

N_TWINS = 8
WORKERS = 2
PER_TICK = 8


# --------------------------------------------------------------------------- #
# wire codec (no subprocesses)
# --------------------------------------------------------------------------- #
def _chunks(with_u: bool = True):
    rng = np.random.default_rng(0)
    return [(tid,
             rng.standard_normal((3, 2)).astype(np.float32),
             rng.standard_normal((3, 1)).astype(np.float32) if with_u
             else None)
            for tid in (4, 9, 4)]


@pytest.mark.parametrize("with_u", [True, False])
def test_ingest_batch_roundtrip(with_u):
    batch = _chunks(with_u)
    msg = W.decode(W.encode(W.IngestBatch.from_chunks(batch, force=True)))
    assert isinstance(msg, W.IngestBatch) and msg.force
    assert msg.n_samples == 9
    out = list(msg.chunks())
    assert [c[0] for c in out] == [c[0] for c in batch]
    for (_, y, u), (_, y0, u0) in zip(out, batch):
        np.testing.assert_array_equal(y, y0)
        if with_u:
            np.testing.assert_array_equal(u, u0)
        else:
            assert u is None


def test_tick_done_roundtrip():
    done = W.TickDone(tick=7, latency_s=0.25, deadline_met=True, n_active=3,
                      n_twins=5, n_guarded=2, degraded_level=1, pressure=0.5,
                      loss=0.125, ckpt_tick=4,
                      events=[[3, "ALERT", 2.5, 7]])
    out = W.decode(W.encode(done))
    assert out.tick == 7 and out.ckpt_tick == 4 and out.loss == 0.125
    assert out.events == [[3, "ALERT", 2.5, 7]]


def test_hello_sample_keys_stringify_over_json():
    """JSON stringifies int dict keys — the coordinator converts back when
    computing the replay suffix; the codec itself must not hide it."""
    out = W.decode(W.encode(W.Hello(shard=1, tick=3, ckpt_tick=2,
                                    samples={5: 10})))
    assert out.samples == {"5": 10}
    assert {int(k): int(v) for k, v in out.samples.items()} == {5: 10}


def test_hello_device_roundtrip_and_default():
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "id": 0,
           "coords": [1, 0, 0], "chip": 1}
    assert W.decode(W.encode(W.Hello(shard=1, device=dev))).device == dev
    assert W.decode(W.encode(W.Hello(shard=1))).device == {}


# --------------------------------------------------------------------------- #
# one process per chip (no subprocesses: refused before any spawn)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("workers,chips", [(2, 1), (5, 4)])
def test_coordinator_refuses_more_workers_than_chips(monkeypatch, workers,
                                                     chips):
    from repro.twin import federation
    monkeypatch.setattr(federation, "tpu_chips_for_workers", lambda: chips)
    spawned = []
    monkeypatch.setattr(federation, "ShardWorker",
                        lambda *a, **k: spawned.append(a))
    cfg = FederatedTwinConfig.uniform(_worker_cfg(LotkaVolterra()), workers)
    with pytest.raises(ValueError, match="one TPU chip each"):
        FederatedTwinServer(cfg)
    assert spawned == []


def test_chip_count_follows_worker_platform(monkeypatch):
    """Off-TPU platforms never limit workers; on TPU the chips this host
    can open do, and counting never initializes a backend."""
    from repro.twin import federation

    def host(on_bus, nodes):
        monkeypatch.setattr(federation.hardware_utils,
                            "num_available_tpu_chips_and_device_id",
                            lambda: (on_bus, None))
        monkeypatch.setattr(federation, "_tpu_device_nodes", lambda: nodes)

    host(4, 4)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert federation.tpu_chips_for_workers() is None
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert federation.tpu_chips_for_workers() == 4
    monkeypatch.delenv("JAX_PLATFORMS")
    assert federation.tpu_chips_for_workers() == 4
    host(4, 1)          # a container given one chip of a four-chip host
    assert federation.tpu_chips_for_workers() == 1
    host(0, 0)
    assert federation.tpu_chips_for_workers() is None


def test_one_chip_env_confines_worker_to_its_chip():
    from repro.twin.federation import _one_chip_env
    envs = [_one_chip_env(i) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4


def test_decode_rejects_foreign_version():
    payload = bytearray(W.encode(W.Ack(n=1)))
    payload[:2] = struct.pack(">H", W.WIRE_VERSION + 1)
    with pytest.raises(W.WireError, match="version"):
        W.decode(bytes(payload))


def test_untrusted_decode_admits_only_ingest():
    """The front-door trust boundary: nothing that deserializes beyond
    JSON + raw arrays crosses it."""
    blob = W.encode(W.SnapshotBlob.pack({"theta": np.zeros(3)}))
    with pytest.raises(W.WireError):
        W.decode(blob, trusted=False)
    ok = W.decode(W.encode(W.IngestBatch.from_chunks(_chunks())),
                  trusted=False)
    assert isinstance(ok, W.IngestBatch)


def test_stream_framing_eof():
    a, b = socket.socketpair()
    try:
        payload = W.encode(W.DrainCmd())
        W.write_frame(a, payload)
        a.close()
        assert W.read_frame(b) == payload
        assert W.read_frame(b) is None     # clean EOF, not an exception
    finally:
        b.close()


# --------------------------------------------------------------------------- #
# live federation
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def lv_world():
    sys_ = LotkaVolterra()
    tr = simulate_batch(sys_, jax.random.PRNGKey(0), batch=N_TWINS,
                        horizon=300, noise_std=0.002)
    return sys_, np.asarray(tr.ys_noisy)


def _worker_cfg(sys_, **kw):
    kw.setdefault("refit_slots", 4)
    return TwinServerConfig(
        merinda=MerindaConfig(n=2, m=0, order=2, hidden=8, head_hidden=8,
                              n_active=4, dt=sys_.spec.dt),
        max_twins=N_TWINS // WORKERS + 1, capacity=128, window=16, stride=8,
        windows_per_twin=4, steps_per_tick=1, deploy_after=2,
        min_residency=1, max_residency=4, guard=GuardConfig(window=16), **kw)


def _feed(srv, ys, tick, per_tick=PER_TICK):
    lo = tick * per_tick
    return srv.ingest_many([(tid, ys[tid, lo:lo + per_tick])
                            for tid in range(N_TWINS)])


@pytest.fixture(scope="module")
def fed_srv(lv_world):
    sys_, _ = lv_world
    srv = FederatedTwinServer(FederatedTwinConfig.uniform(
        _worker_cfg(sys_), WORKERS, rebalance_every=2, front_door=True))
    yield srv
    srv.close()
    srv.close()                            # idempotent


def test_federated_serves_through_the_protocol(fed_srv, lv_world):
    sys_, ys = lv_world
    assert conforms(fed_srv) == []
    assert fed_srv.register(3) == 3 % WORKERS
    assert _feed(fed_srv, ys, 0) == N_TWINS * PER_TICK
    fed_srv.drain()
    for t in range(4):
        rep = fed_srv.tick()
    assert rep.n_twins == N_TWINS
    assert len(rep.grants) == WORKERS and sum(rep.grants) > 0
    assert rep.dead_shards == 0
    s = fed_srv.latency_summary()
    assert s["ticks"] >= 4 and s["dropped_samples"] == 0
    assert set(fed_srv.snapshot_state()) == {"shard0", "shard1"}
    devices = [w.hello.device for w in fed_srv.workers]
    assert [d["platform"] for d in devices] == ["cpu"] * WORKERS
    assert [d["chip"] for d in devices] == [None] * WORKERS


def test_predict_refusal_leaves_worker_alive(fed_srv, lv_world):
    _, ys = lv_world
    with pytest.raises(RuntimeError):
        fed_srv.predict(999, horizon=4)    # unknown twin: logical refusal
    _feed(fed_srv, ys, 5)
    rep = fed_srv.tick()                   # ...but the worker still serves
    assert rep.dead_shards == 0


def test_predict_roundtrip_after_deploy(fed_srv, lv_world):
    sys_, ys = lv_world
    theta = np.asarray(sys_.true_theta(_worker_cfg(sys_).merinda.library))
    fed_srv.deploy_many(list(range(N_TWINS)), theta)
    _feed(fed_srv, ys, 0)                  # predict rolls from newest samples
    fed_srv.drain()
    ys_hat = fed_srv.predict(1, horizon=5)
    assert np.asarray(ys_hat).shape[0] == 6    # horizon+1, row 0 = observed
    assert np.all(np.isfinite(ys_hat))


def test_front_door_feeds_the_fleet(fed_srv, lv_world):
    _, ys = lv_world
    client = FrontDoorClient(fed_srv.front_address)
    try:
        staged = client.ingest_many(
            [(tid, ys[tid, 48:56]) for tid in range(N_TWINS)])
        assert staged == N_TWINS * 8
        assert client.ingest(0, ys[0, 56:60]) == 4
    finally:
        client.close()
    fed_srv.drain()
    assert fed_srv.tick().n_twins == N_TWINS


def test_register_rejects_conflicting_pin(fed_srv):
    with pytest.raises(ValueError):
        fed_srv.register(3, shard=(3 % WORKERS) + 1)


@pytest.mark.chaos
def test_kill_restart_replays_journal_and_migrates_grants(lv_world,
                                                          tmp_path):
    """ISSUE 9 acceptance: SIGKILL a worker mid-serve -> the survivor
    inherits its slot grant under scarcity, the supervised restart replays
    the journal tail with 0 lost samples, and the grant shape recovers."""
    sys_, ys = lv_world
    victim, total_slots = 1, 4             # scarcity: half the pool sum
    srv = FederatedTwinServer(FederatedTwinConfig.uniform(
        _worker_cfg(sys_, refit_slots=4), WORKERS,
        rebalance_every=1, total_slots=total_slots,
        recovery=RecoveryConfig(ckpt_dir=str(tmp_path), ckpt_every=2,
                                restart_delay_ticks=2)))
    try:
        for tid in range(N_TWINS):
            srv.register(tid)
        for t in range(4):                 # build state + checkpoints
            _feed(srv, ys, t)
            srv.drain()
            pre = srv.tick()
        assert pre.grants[victim] > 0

        srv.kill_worker(victim)
        _feed(srv, ys, 4)                  # journal-only for the dead half
        down = srv.tick()
        assert down.dead_shards == 1
        assert down.grants[victim] == 0
        assert sum(down.grants) == total_slots          # migrated, not lost
        assert down.grants[1 - victim] > pre.grants[1 - victim]

        _feed(srv, ys, 5)
        back = srv.tick()                  # restart_delay_ticks=2 elapsed
        assert len(back.restarted) == 1
        rec = back.restarted[0]
        assert rec["shard"] == victim
        assert rec["lost"] == 0
        assert rec["replayed"] > 0
        assert back.dead_shards == 0
        assert back.grants[victim] > 0     # share flowed back

        _feed(srv, ys, 6)                  # the fleet keeps serving
        assert srv.tick().n_twins == N_TWINS
    finally:
        srv.close()
