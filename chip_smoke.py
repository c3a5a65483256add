#!/usr/bin/env python3
"""Chip smoke test: the twin-serving path, end to end, on a TPU.

    python chip_smoke.py                # one chip: phases (a) and (b)
    python chip_smoke.py --four-chips   # four chips: the federated path only

(a) `online` deployment on one `TwinServer`: 64 F-8 twins, 8 refit slots,
    hidden 32, guard window 32, Pallas kernels compiled.  Warm-start
    thetas, then elevator damage on a known subset of airframes; tick until
    the guard has flagged them, then answer one `predict` and one
    `scenario(k=8)`.  The same telemetry also runs through the jnp
    reference (`use_pallas=False`) in this process.  Passes when both flag
    the same twins (every damaged one among them), `predict` and the
    scenario's centre trajectories agree within PRED_RTOL/PRED_ATOL, and
    every refit loss is finite.
(b) Scale path: 10 000 twins on a 4-shard `ShardedTwinServer` (the
    `online_scale` deployment, Pallas kernels).  Passes when every tick's
    outputs are finite, no shard dies, and no tick after warm-up misses
    the 1 s deadline.
--four-chips: `FederatedTwinServer`, 4 worker processes, one chip each,
    serving the phase (a) fleet with damage, against 1 worker on one chip
    with the same telemetry.  Passes when the guard events are identical,
    `predict` agrees, each worker reports its own chip, and no worker dies.
    This process is the coordinator and keeps its own JAX on the CPU.

The times printed are from one run of a smoke test, not a benchmark.  The
last line of output is the JSON result; it is printed only when every
phase passed.  Without a TPU the script exits nonzero and prints no result:
there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.online_scale import scale_config  # noqa: E402
from benchmarks.online_serving import online_config  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.library import make_library  # noqa: E402
from repro.kernels.backend import resolve_interpret  # noqa: E402
from repro.systems.f8_crusader import (DamagedF8, F8Crusader,  # noqa: E402
                                       trim_neighborhood)
from repro.systems.simulate import continue_batch, simulate_batch  # noqa: E402
from repro.twin import (FederatedTwinConfig, FederatedTwinServer,  # noqa: E402
                        ShardedTwinConfig, ShardedTwinServer, TwinServer)
from repro.twin.federation import (pin_host_jax_to_cpu,  # noqa: E402
                                   tpu_chips_for_workers)

CHUNK = 8                 # telemetry samples per twin per tick
PRE_TICKS = 12            # nominal flight before the damage
MAX_POST_TICKS = 30       # ticks allowed for the guard to flag the damage
HORIZON = 50              # predict / scenario lookahead (0.5 s)
# predict/scenario parity, kernel vs reference, from the same theta and
# state: f32 RK4 over 50 steps, differing only in operation order
PRED_RTOL, PRED_ATOL = 1e-4, 1e-5
SCORE_RTOL = 1e-5         # guard scores, 4 workers vs 1 (same kernels)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Phase:
    """Named pass/fail checks of one phase, printed as they are made."""

    def __init__(self, name: str):
        self.name, self.failed = name, []

    def check(self, ok: bool, what: str) -> None:
        print(f"[{self.name}] {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def damaged_fleet(n_twins: int, n_damaged: int, seed: int = 0):
    """Telemetry (ys, us) for PRE_TICKS nominal ticks, then MAX_POST_TICKS
    ticks in which twins [0, n_damaged) fly with a damaged elevator."""
    nominal = trim_neighborhood(F8Crusader())
    damaged = trim_neighborhood(DamagedF8())
    t1, t2 = CHUNK * PRE_TICKS, CHUNK * MAX_POST_TICKS
    tr = simulate_batch(nominal, jax.random.PRNGKey(seed), batch=n_twins,
                        horizon=t1, noise_std=0.002)
    k_u, k_n1, k_n2 = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    us2 = jnp.swapaxes(nominal.sample_inputs(k_u, t2, batch=(n_twins,)), 0, 1)
    y_end = tr.ys[:, -1, :]
    ys2 = np.array(continue_batch(nominal, y_end, us2, k_n1, noise_std=0.002))
    idx = jnp.arange(n_damaged)
    ys2[:n_damaged] = np.asarray(continue_batch(
        damaged, y_end[idx], us2[idx], k_n2, noise_std=0.002))
    ys = np.concatenate([np.asarray(tr.ys_noisy[:, :-1]), ys2[:, :-1]], 1)
    us = np.concatenate([np.asarray(tr.us), np.asarray(us2)], 1)
    theta0 = np.asarray(nominal.true_theta(make_library(3, 1, 3)))
    return ys, us, theta0


def serve_damage(servers: dict, ys, us, theta0, n_damaged: int) -> dict:
    """Drive every server in `servers` with the same telemetry until each
    has flagged all damaged twins; returns per-server flags, losses and
    first-flag tick."""
    n_twins = ys.shape[0]
    out = {k: {"flagged": set(), "losses": [], "events": []}
           for k in servers}
    for srv in servers.values():
        srv.deploy_many(list(range(n_twins)), np.stack([theta0] * n_twins))
    damaged = set(range(n_damaged))
    for t in range(PRE_TICKS + MAX_POST_TICKS):
        lo = t * CHUNK
        batch = [(i, ys[i, lo:lo + CHUNK], us[i, lo:lo + CHUNK])
                 for i in range(n_twins)]
        for k, srv in servers.items():
            srv.ingest_many(batch)
            rep = srv.tick()
            sub = getattr(rep, "reports", [rep])
            out[k]["losses"] += [r.loss for r in sub
                                 if r is not None and r.loss is not None]
            out[k]["events"] += rep.events
            out[k]["flagged"] |= {e.twin_id for e in rep.events}
            out[k]["dead"] = out[k].get("dead", 0) + getattr(
                rep, "dead_shards", 0)
            if t == 3:                      # compile-heavy ticks behind us
                srv.reset_latency_stats()
        if t >= PRE_TICKS and all(damaged <= o["flagged"]
                                  for o in out.values()):
            break
    for k, srv in servers.items():
        out[k]["ticks"] = t + 1
        out[k]["p50_ms"] = srv.latency_summary()["p50_ms"]
    return out


def probe_answers(srv, probe: int, theta0, us_k):
    """predict + scenario(k=8) for `probe` from a freshly deployed theta0,
    so that both backends answer from the same model and state."""
    srv.deploy(probe, theta0)
    pred = np.asarray(srv.predict(probe, HORIZON, us_k[0]))
    scen = srv.scenario(probe, HORIZON, us_k, k=us_k.shape[0])
    return pred, np.asarray(scen.ys)


def _close(a, b) -> bool:
    return bool(np.all(np.isfinite(a)) and np.all(np.isfinite(b))
                and np.allclose(a, b, rtol=PRED_RTOL, atol=PRED_ATOL))


def phase_online(n_twins: int = 64, n_damaged: int = 12) -> Phase:
    ph = Phase("a:online")
    system = F8Crusader()
    ys, us, theta0 = damaged_fleet(n_twins, n_damaged)
    servers = {name: TwinServer(online_config(system, n_twins, 8,
                                              use_pallas=pallas))
               for name, pallas in (("pallas", True), ("reference", False))}
    try:
        t0 = time.perf_counter()
        out = serve_damage(servers, ys, us, theta0, n_damaged)
        print(f"[a:online] served {out['pallas']['ticks']} ticks in "
              f"{time.perf_counter() - t0:.1f} s; steady tick p50 "
              f"pallas {out['pallas']['p50_ms']:.2f} ms, reference "
              f"{out['reference']['p50_ms']:.2f} ms (one smoke run)")
        fp, fr = out["pallas"]["flagged"], out["reference"]["flagged"]
        print(f"[a:online] flagged pallas {sorted(fp)} reference "
              f"{sorted(fr)}; damaged {list(range(n_damaged))}")
        ph.check(fp == fr, "pallas and reference flag the same twins")
        ph.check(set(range(n_damaged)) <= fp, "every damaged twin flagged")
        losses = out["pallas"]["losses"] + out["reference"]["losses"]
        ph.check(bool(losses) and bool(np.all(np.isfinite(losses))),
                 f"all {len(losses)} refit losses finite")
        probe = n_damaged                       # a healthy twin
        us_k = 0.03 * np.random.default_rng(0).standard_normal(
            (8, HORIZON, system.spec.m)).astype(np.float32)
        (pp, sp), (pr, sr) = (probe_answers(servers[k], probe, theta0, us_k)
                              for k in ("pallas", "reference"))
        print(f"[a:online] predict max |pallas - reference| "
              f"{np.max(np.abs(pp - pr)):.3e}, scenario "
              f"{np.max(np.abs(sp - sr)):.3e} (rtol {PRED_RTOL}, "
              f"atol {PRED_ATOL})")
        ph.check(_close(pp, pr), "predict agrees")
        ph.check(sp.shape == (8, HORIZON + 1, system.spec.n)
                 and _close(sp, sr), "scenario(k=8) agrees")
    finally:
        for srv in servers.values():
            srv.close()
    return ph


def phase_scale(n_twins: int = 10_000, shards: int = 4, warmup: int = 18,
                ticks: int = 6) -> Phase:
    ph = Phase("b:scale")
    system = F8Crusader()
    sim = simulate_batch(system, jax.random.PRNGKey(1), batch=n_twins,
                         horizon=CHUNK * (warmup + ticks) + 1,
                         noise_std=0.002)
    ys, us = np.asarray(sim.ys_noisy), np.asarray(sim.us)
    scfg = scale_config(system, n_twins, shards, use_pallas=True)
    srv = ShardedTwinServer(ShardedTwinConfig.uniform(scfg, shards,
                                                      rebalance_every=4))
    finite, deaths = True, 0
    try:
        theta0 = np.asarray(system.true_theta(scfg.merinda.library))
        srv.deploy_many(list(range(n_twins)), theta0)
        t0 = time.perf_counter()
        for t in range(warmup + ticks):
            lo = t * CHUNK
            srv.ingest_many([(i, ys[i, lo:lo + CHUNK], us[i, lo:lo + CHUNK])
                             for i in range(n_twins)])
            if t < warmup:
                srv.drain()
            rep = srv.tick()
            deaths += len(rep.restarted) + rep.dead_shards
            finite &= bool(np.isfinite(rep.latency_s)) and all(
                np.isfinite(r.loss) for r in rep.reports
                if r is not None and r.loss is not None) and all(
                np.isfinite(e.score) for e in rep.events)
            if t == warmup - 1:
                srv.reset_latency_stats()
        srv.drain()
        preds = [np.asarray(srv.predict(i, HORIZON))
                 for i in range(0, n_twins, n_twins // shards)]
        s = srv.latency_summary()
        print(f"[b:scale] {n_twins} twins / {shards} shards: "
              f"{warmup + ticks} ticks in {time.perf_counter() - t0:.1f} s; "
              f"steady tick p50 {s['p50_ms']:.2f} ms p99 {s['p99_ms']:.2f} "
              f"ms over {s['ticks']} ticks (one smoke run)")
        ph.check(finite and all(np.all(np.isfinite(p)) for p in preds),
                 "every tick's outputs and the predictions are finite")
        ph.check(deaths == 0, f"{deaths} shard deaths")
        ph.check(s["violations"] == 0,
                 f"{s['violations']} deadline violations after warm-up")
    finally:
        srv.close()
    return ph


def phase_federated(workers: int = 4, n_twins: int = 64,
                    n_damaged: int = 12) -> tuple[Phase, list[dict]]:
    ph = Phase("four-chips")
    system = F8Crusader()
    ys, us, theta0 = damaged_fleet(n_twins, n_damaged)
    us_k = 0.03 * np.random.default_rng(0).standard_normal(
        (8, HORIZON, system.spec.m)).astype(np.float32)
    runs = {}
    for n in (workers, 1):
        # guard-only serving (refits train, never deploy): guard events are
        # then a function of telemetry and thetas alone, whatever the split
        scfg = dataclasses.replace(
            online_config(system, -(-n_twins // n), 8, use_pallas=True),
            deploy_after=10 ** 6)
        srv = FederatedTwinServer(FederatedTwinConfig.uniform(scfg, n))
        try:
            out = serve_damage({n: srv}, ys, us, theta0, n_damaged)[n]
            out["devices"] = [w.hello.device for w in srv.workers]
            out["pred"] = probe_answers(srv, n_damaged, theta0, us_k)
            runs[n] = out
        finally:
            srv.close()
        print(f"[four-chips] {n} worker(s): {out['ticks']} ticks, tick p50 "
              f"{out['p50_ms']:.2f} ms (one smoke run); devices "
              f"{out['devices']}")
    many, one = runs[workers], runs[1]
    key = lambda e: (e.tick, e.twin_id, e.kind)  # noqa: E731
    ev_m, ev_1 = sorted(many["events"], key=key), sorted(one["events"],
                                                         key=key)
    ph.check([key(e) for e in ev_m] == [key(e) for e in ev_1]
             and np.allclose([e.score for e in ev_m],
                             [e.score for e in ev_1], rtol=SCORE_RTOL),
             f"guard events identical ({len(ev_m)} events; scores within "
             f"rtol {SCORE_RTOL})")
    ph.check(set(range(n_damaged)) <= many["flagged"],
             "every damaged twin flagged")
    ph.check(_close(many["pred"][0], one["pred"][0])
             and _close(many["pred"][1], one["pred"][1]),
             "predict and scenario agree")
    devs = many["devices"]
    ph.check(all(d["platform"] == "tpu" for d in devs)
             and sorted(d["chip"] for d in devs) == list(range(workers)),
             f"each of {workers} workers on its own TPU chip")
    ph.check(many["dead"] == 0 and one["dead"] == 0, "0 worker deaths")
    return ph, devs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the federated path: 4 workers, one chip "
                         "each, against 1 worker")
    args = ap.parse_args()
    enable_compile_cache()
    compile_s = [0.0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.__setitem__(
            0, compile_s[0] + secs) if event == COMPILE_EVENT else None)
    print(f"jax {jax.__version__}", flush=True)

    if args.four_chips:
        chips = tpu_chips_for_workers()
        if chips is None or chips < 4:
            print(f"no four TPU chips found (found {chips or 0})",
                  file=sys.stderr)
            return 2
        pin_host_jax_to_cpu()            # the workers own the chips
        ph, devs = phase_federated()
        phases = [ph]
        device = {"platform": devs[0]["platform"], "kind": devs[0]["kind"],
                  "count": len({d["chip"] for d in devs})}
    else:
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            print(f"no TPU found: JAX runs on {dev.platform}",
                  file=sys.stderr)
            return 2
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
        print(f"device {device}", flush=True)
        interp = resolve_interpret(None)
        print(f"[setup] resolve_interpret(None) = {interp}")
        if interp:
            print("Pallas kernels would run interpreted", file=sys.stderr)
            return 1
        phases = [phase_online(), phase_scale()]
        print(f"[setup] backend compile {compile_s[0]:.1f} s in this process")

    for ph in phases:
        print(f"[{ph.name}] {'PASS' if not ph.failed else 'FAIL'}")
    if any(ph.failed for ph in phases):
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
