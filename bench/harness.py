"""One run of one benchmark cell: set-up, the measured window, the check.

Everything a cell needs is found by name: `BENCHMARK.json` names the cell's
configuration and traffic mix, `bench/configs/<config>.json` and
`bench/traffic/<mix>.json` hold their parameters, the configuration's
`system` names its physics family in `bench/physics/<system>.json`,
`bench/limits/<cell>.json` the limits of the correctness comparison,
`bench/e2e/<metric>.py` and `bench/metrics/<metric>.py` the readers of each
metric.  Adding a cell, a configuration, a family, a mix or a metric adds
files and entries and edits none.

The loop is closed: every twin reports one chunk of telemetry through one
`ingest_many`, then the server ticks once, then the mix's what-if queries
run.  Telemetry is made from the seed before the window; damage onsets are
fixed to data indices, so the stream does not depend on the server's speed.

The harness takes from the program only the system under test, its spans and
its kernel names.  To check what the timed path produced, it wraps the
module calls the window drives (guard scoring, the ring gathers, the refit
step, the scenario rollout) and keeps the inputs and outputs
of a sample of them, drawn from the seed.  After the window has closed and
the peak memory has been read, `bench/reference.py` recomputes them from the
benchmark's own telemetry.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench.family import Physics, load_physics, theta

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a reader file by path (its name may hold a dot)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def physics_for(cfg: dict, path: Path | None = None) -> Physics:
    """The physics family a configuration names by its `system` key (from
    `path` where given, else `bench/physics/<system>.json`), checked
    against the library and the sampling interval the configuration
    learns."""
    path = path or BENCH / "physics" / f"{cfg['system']}.json"
    phys = load_physics(path)
    mc = cfg["merinda"]
    if phys.name != cfg["system"]:
        raise ValueError(f"{path} holds {phys.name!r}, the configuration "
                         f"names {cfg['system']!r}")
    if mc["dt"] != phys.dt:
        raise ValueError(f"the configuration samples at merinda.dt "
                         f"{mc['dt']}, {phys.name} at dt {phys.dt}")
    if mc["order"] < phys.order:
        raise ValueError(f"the configuration learns a library of order "
                         f"{mc['order']}, below {phys.name}'s {phys.order}")
    return phys


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    physics: Physics             # the family config["system"] names

    @property
    def dims(self) -> dict:
        """The learned library's order and the state and input counts, as
        `bench/flops.py` takes them."""
        return {"order": self.config["merinda"]["order"],
                "n": self.physics.n, "m": self.physics.m}

    @staticmethod
    def load(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> "Cell":
        spec = load_json(bench_file)
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        w = cells[name]
        cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]

        def serves(metric):
            return name in metric.get("workloads", [name])
        config = load_json(ROOT / cfg_entry["file"])
        return Cell(
            name=name, chips=int(w["chips"]), config=config,
            physics=physics_for(config),
            traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
            limits=load_json(BENCH / "limits" / f"{name}.json"),
            end_to_end=[m for m in spec["end_to_end"] if serves(m)],
            per_layer=[m for m in spec["per_layer"] if serves(m)])


# --------------------------------------------------------------------------- #
def device_info(jax, chips: int, require_chip: bool) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if require_chip:
        if dev.platform != "tpu":
            raise NoChip(f"no TPU found: JAX runs on {dev.platform}")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": min(len(devs), chips)}


def peak_of(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


# --------------------------------------------------------------------------- #
def build_server(cfg: dict, physics: Physics, tracer=None):
    """The program's server for a configuration file and its physics."""
    from repro.core.merinda import MerindaConfig
    from repro.twin.monitor import GuardConfig
    from repro.twin.server import TwinServer, TwinServerConfig
    from repro.twin.sharded import ShardedTwinConfig, ShardedTwinServer

    merinda = MerindaConfig(n=physics.n, m=physics.m, **cfg["merinda"])
    fields = dict(cfg["server_config"])
    fields.setdefault("max_twins", cfg["twins"])
    scfg = TwinServerConfig(merinda=merinda, guard=GuardConfig(**cfg["guard"]),
                            **fields)
    if cfg["server"] == "TwinServer":
        return TwinServer(scfg, tracer=tracer)
    if cfg["server"] == "ShardedTwinServer":
        return ShardedTwinServer(
            ShardedTwinConfig.uniform(scfg, cfg["shards"],
                                      rebalance_every=cfg["rebalance_every"]),
            tracer=tracer)
    raise ValueError(f"unknown server {cfg['server']!r}")


def shard_servers(srv) -> list:
    return list(getattr(srv, "shards", [srv]))


# --------------------------------------------------------------------------- #
@dataclass
class Traffic:
    """The cell's telemetry and queries, made from the seed before the window."""
    ys: np.ndarray               # [N, T, n] noisy states, float32
    us: np.ndarray               # [N, T, m] inputs, float32
    chunk: int
    q_twins: np.ndarray          # [max_loops, Q] queried twin per slot
    q_seeds: np.ndarray          # [max_loops] per-loop query input seeds
    record_loops: set = field(default_factory=set)

    def batch(self, loop: int):
        lo = loop * self.chunk
        hi = lo + self.chunk
        if hi > self.ys.shape[1]:
            raise RuntimeError(
                f"telemetry stream exhausted at loop {loop}: the config's "
                "max_loop_rate_per_s is below the server's loop rate")
        ys, us = self.ys[:, lo:hi], self.us[:, lo:hi]
        return [(i, ys[i], us[i]) for i in range(ys.shape[0])]

    def query_inputs(self, loop: int, k: int, horizon: int, std: float):
        rng = np.random.default_rng(int(self.q_seeds[loop]))
        q = self.q_twins.shape[1]
        return (std * rng.standard_normal((q, k, horizon,
                                           self.us.shape[-1]))
                ).astype(np.float32)


def make_traffic(cfg: dict, physics: Physics, mix: dict, seed: int,
                 seconds: float) -> Traffic:
    from bench import telemetry

    n, chunk = cfg["twins"], cfg["chunk"]
    warmup = cfg["warmup_loops"] + 60           # room for a longer warm-up
    max_loops = warmup + int(math.ceil(seconds * cfg["max_loop_rate_per_s"]))
    T = max_loops * chunk
    rng = np.random.default_rng([seed % 2 ** 63, 1])
    n_dmg = int(round(mix["damaged_share"] * n))
    onset = np.full((n,), T + 1, np.int64)
    # the same onset loops in every run, evenly spread over the mix's range,
    # so that the seed picks which twins fail and not how much work follows
    lo, hi = mix["damage_onset_loops"]
    dmg = rng.choice(n, size=n_dmg, replace=False)
    onset[dmg] = (cfg["warmup_loops"]
                  + np.linspace(lo, hi, n_dmg).round().astype(np.int64)) \
        * chunk
    ys, us = telemetry.stream(
        physics, int(rng.integers(0, 2 ** 63)), n, T, y0_frac=mix["y0_frac"],
        input_scale=mix["input_scale"], noise_std=mix["noise_std"],
        onset=onset, effectiveness=mix["damage_effectiveness"],
        gains=mix["sas_gains"])
    if not np.all(np.isfinite(ys)):
        raise RuntimeError(f"the {physics.name} telemetry is not finite: "
                           "the mix leaves the model's stable region")
    q = int(mix.get("queries_per_loop", 0))
    if q:
        ranks = np.arange(1, n + 1, dtype=np.float64) ** -mix["query_zipf_s"]
        order = rng.permutation(n)
        q_twins = order[rng.choice(n, size=(max_loops, q),
                                   p=ranks / ranks.sum())]
    else:
        q_twins = np.zeros((max_loops, 0), np.int64)
    q_seeds = rng.integers(0, 2 ** 63, max_loops)
    return Traffic(ys=ys, us=us, chunk=chunk, q_twins=q_twins,
                   q_seeds=q_seeds)


# --------------------------------------------------------------------------- #
class Recorder:
    """Wraps the module calls the window drives.

    Always (cheap): the shapes of every call, for the operation counts.
    On the loops drawn for the check, and for the window's first call of
    each kind (promotion is rare, and the check must see one): the inputs
    and outputs themselves, as device arrays, read only after the window.
    """

    def __init__(self, srv):
        self.shard = 0
        self.loop = -1
        self.phase = "setup"         # setup | window
        self.keep = False
        self.traced = False
        self.shapes: list[tuple] = []    # (phase, traced, kind, shape)
        self.kept: list[dict] = []
        self.kinds_kept: set[str] = set()
        self._gathers: dict[int, tuple] = {}    # id(out) -> (out, rows)
        shards = shard_servers(srv)
        seen = set()
        for i, s in enumerate(shards):
            self._wrap_tick(s, i)
            for obj, names in ((s.ring, ("latest", "windows")),
                               (s.guard, ("score",)),
                               (s.fleet, ("train_step_per_slot",
                                          "recover_all")),
                               (s.scenario_runner, ("rollout",))):
                if id(obj) in seen:
                    continue
                seen.add(id(obj))
                for name in names:
                    setattr(obj, name, getattr(self, "_wrap_" + name)(
                        getattr(obj, name)))

    def seen(self, kind: str) -> bool:
        return any(k == kind for _, _, k, _ in self.shapes)

    def _wrap_tick(self, s, i):
        orig = s.tick

        def tick():
            self.shard = i
            self._gathers.clear()
            return orig()
        s.tick = tick

    def _note(self, kind, shape) -> bool:
        """Count the call; whether to keep its inputs and outputs."""
        self.shapes.append((self.phase, self.traced, kind, shape))
        keep = self.keep or (self.phase == "window"
                             and kind not in self.kinds_kept)
        if keep:
            self.kinds_kept.add(kind)
        return keep

    def _rows(self, arr):
        """The ring rows a gathered array came from, copied now (the program
        may hand JAX a host buffer that it later mutates in place) but on
        the device, so that nothing waits for the device inside the window;
        the check reads them after it."""
        import jax.numpy as jnp
        hit = self._gathers.get(id(arr))
        return None if hit is None else jnp.array(hit[1], copy=True)

    def _wrap_latest(self, orig):
        def latest(state, slots, length):
            out = orig(state, slots, length)
            self._gathers[id(out[0])] = (out[0], slots)
            return out
        return latest

    def _wrap_windows(self, orig):
        def windows(state, slots, **kw):
            out = orig(state, slots, **kw)
            self._gathers[id(out[0])] = (out[0], slots)
            return out
        return windows

    def _wrap_score(self, orig):
        def score(theta, ys, us):
            out = orig(theta, ys, us)
            if self._note("rk4_guard", (theta.shape[0], us.shape[1])):
                self.kept.append(dict(kind="guard", loop=self.loop,
                                      shard=self.shard, rows=self._rows(ys),
                                      theta=theta, ys=ys, us=us, out=out))
            return out
        return score

    def _wrap_train_step_per_slot(self, orig):
        def train_step_per_slot(state, y_win, u_win):
            out = orig(state, y_win, u_win)
            if self._note("refit_step", tuple(u_win.shape[:3])):
                self.kept.append(dict(kind="step", loop=self.loop,
                                      shard=self.shard,
                                      rows=self._rows(y_win), state=state,
                                      y_win=y_win, u_win=u_win, out=out))
            return out
        return train_step_per_slot

    def _wrap_recover_all(self, orig):
        def recover_all(state, y_win, u_win):
            self.shapes.append((self.phase, self.traced, "recover",
                                tuple(u_win.shape[:3])))
            return orig(state, y_win, u_win)
        return recover_all

    def _wrap_rollout(self, orig):
        def rollout(theta_hist, count, y0, us):
            out = orig(theta_hist, count, y0, us)
            E = np.shape(theta_hist)[0]
            self._note("rk4_scenario", (E * us.shape[0], us.shape[1]))
            if self.keep_query:
                self.kept.append(dict(kind="scenario", loop=self.loop,
                                      twin=self.query_twin,
                                      theta_hist=theta_hist, count=count,
                                      y0=np.asarray(y0), us=np.asarray(us),
                                      out=out))
            return out
        return rollout

    keep_query = False
    query_twin = -1


def warm_counts(srv, theta0: np.ndarray, slots: int) -> None:
    """Compile, before the window, the operations whose shapes follow the
    number of models deployed at once (1 to `slots`, the refit slots that
    may promote in one tick): the scatters into the served and the recent
    thetas, through the program's own `deploy_many` of the nominal model
    that every twin already serves, and the gather of the promoted slots'
    thetas out of the slot batch, on zeros of the same shapes."""
    import jax
    import jax.numpy as jnp
    batch = jnp.zeros((slots,) + theta0.shape, jnp.float32)
    out = []
    for k in range(1, slots + 1):
        srv.deploy_many(list(range(k)),
                        np.broadcast_to(theta0, (k,) + theta0.shape))
        out.append(batch[jnp.asarray(list(range(k)))])
    jax.block_until_ready(out)


# --------------------------------------------------------------------------- #
@dataclass
class Window:
    """What the window measured, by the harness's own clock."""
    seconds: float = 0.0
    loops: int = 0
    samples: int = 0
    tick_s: list = field(default_factory=list)
    ingest_s: list = field(default_factory=list)
    scenario_s: list = field(default_factory=list)
    query_failed: int = 0
    compiles: int = 0
    deadline_s: float = 1.0

    @property
    def attempted(self) -> int:
        return len(self.tick_s) + len(self.scenario_s) + self.query_failed

    @property
    def failed(self) -> int:
        late = sum(t > self.deadline_s for t in self.tick_s)
        slow = sum(t > self.deadline_s for t in self.scenario_s)
        return late + slow + self.query_failed


def run_loop(srv, traffic: Traffic, mix: dict, rec: Recorder, loop: int,
             win: Window | None, annotate):
    """One closed-loop iteration: ingest, tick, queries."""
    batch = traffic.batch(loop)
    rec.loop = loop
    # warm-up keeps every call too, so that the recording's own device
    # copies are compiled before the window; `run_cell` drops what it kept
    rec.keep = win is None or loop in traffic.record_loops
    t0 = time.perf_counter()
    with annotate("ingest"):
        staged = srv.ingest_many(batch)
    t1 = time.perf_counter()
    with annotate("tick"):
        srv.tick()
    t2 = time.perf_counter()
    if win is not None:
        win.ingest_s.append(t1 - t0)
        win.tick_s.append(t2 - t1)
        win.samples += staged
    q = traffic.q_twins.shape[1]
    if q:
        k, h = mix["query_k"], mix["query_horizon"]
        us = traffic.query_inputs(loop, k, h, mix["query_input_std"])
        for j in range(q):
            twin = int(traffic.q_twins[loop, j])
            rec.query_twin = twin
            rec.keep_query = rec.keep and j < mix.get("record_queries", q)
            t3 = time.perf_counter()
            try:
                with annotate("scenario"):
                    srv.scenario(twin, h, us[j], k=k)
            except Exception as e:  # noqa: BLE001 - a refused query counts
                if win is None:
                    raise
                win.query_failed += 1
                print(f"query twin {twin} failed: {e!r}", file=sys.stderr)
                continue
            if win is not None:
                win.scenario_s.append(time.perf_counter() - t3)
    rec.keep = rec.keep_query = False


# --------------------------------------------------------------------------- #
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, t_start: float | None = None,
             stand_in: str | None = None, log=print) -> dict:
    """One run; returns the result line's object (plus `compared`).
    `stand_in` is passed to `reference.check` (the control, or a fault)."""
    t_start = time.perf_counter() if t_start is None else t_start
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.obs import Tracer

    enable_compile_cache()
    # JAX options the configuration states, such as the precision of every
    # matmul that names none
    for key, value in cell.config.get("jax_config", {}).items():
        jax.config.update(key, value)
    compiles = [0]      # programs compiled or loaded from the disk cache

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    device = device_info(jax, cell.chips, require_chip)
    # off the chip (the harness's own tests) the table's first device
    # stands in, so the readers run; such numbers are never reported
    peaks = (peak_of(device["kind"]) if require_chip else
             next(iter(load_json(BENCH / "peaks.json")["devices"].values())))
    cfg, mix = cell.config, cell.traffic
    phases = {"start": time.perf_counter() - t_start}
    t0 = time.perf_counter()
    traffic = make_traffic(cfg, cell.physics, mix, seed, seconds)
    phases["telemetry"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    tracer = Tracer(capacity=1 << 20) if trace else None
    srv = build_server(cfg, cell.physics, tracer=tracer)
    rec = Recorder(srv)
    annotate = jax.profiler.TraceAnnotation
    try:
        # ---- set-up: warm start every twin with the family's nominal model
        theta0 = theta(cell.physics, cfg["merinda"]["order"]).astype(
            np.float32)
        srv.deploy_many(list(range(cfg["twins"])),
                        np.broadcast_to(theta0, (cfg["twins"],)
                                        + theta0.shape))
        warm_counts(srv, theta0, cfg["server_config"]["refit_slots"])
        phases["server"] = time.perf_counter() - t0
        t_warm = time.perf_counter()
        # warm up until the configured loops have run, a promotion has run,
        # and two loops in a row compiled nothing
        loop = clean = 0
        loop_s = 1.0
        while (loop < cfg["warmup_loops"] or clean < 2
               or not rec.seen("recover")):
            if loop >= cfg["warmup_loops"] + 50:
                raise RuntimeError("warm-up found no steady state: "
                                   f"{loop} loops, promotion seen "
                                   f"{rec.seen('recover')}")
            before = compiles[0]
            t0 = time.perf_counter()
            run_loop(srv, traffic, mix, rec, loop, None, annotate)
            loop_s = time.perf_counter() - t0
            clean = clean + 1 if compiles[0] == before else 0
            loop += 1
        warm = loop
        rec.kept.clear()
        rec.kinds_kept.clear()
        # the loops whose calls the check reads: the window's first, and a
        # draw from the seed over the loops the window is expected to hold
        rng = np.random.default_rng([seed % 2 ** 63, 2])
        est = max(2, int(seconds / max(loop_s, 1e-3)))
        picks = rng.choice(est - 1, size=min(est - 1, cfg["record_ticks"] - 1),
                           replace=False)
        traffic.record_loops = {warm} | {warm + 1 + int(p) for p in picks}
        if tracer is not None:
            tracer.clear()
        setup_s = time.perf_counter() - t_start
        phases["warm-up"] = time.perf_counter() - t_warm
        log(f"set-up {setup_s:.3f} s ({warm} warm-up loops, "
            f"{compiles[0]} programs compiled or loaded; "
            + ", ".join(f"{k} {v:.2f} s" for k, v in phases.items()) + ")")

        # ---- the window ----
        win = Window(deadline_s=cfg["server_config"].get("deadline_s", 1.0))
        rec.phase = "window"
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        trace_s = min(seconds, cfg["trace_seconds"])
        tr = {}
        c0 = compiles[0]
        t_win = time.perf_counter()
        end = t_win + seconds
        if trace:
            # no Python call tracer: it would trace every call of the loop,
            # and writing it out would stall the run for tens of seconds
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            rec.traced = True
            tr["t0"] = time.perf_counter()
        loop_starts = []
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            if trace and rec.traced and now - t_win >= trace_s:
                tr["t1"] = now
                tr["loops"] = win.loops
                tr["ticks"] = len(win.tick_s)
                tr["tick_s"] = list(win.tick_s)
                tr["scenario_s"] = list(win.scenario_s)
                tr["ingest_s"] = list(win.ingest_s)
                tracer.enabled = False
                rec.traced = False
                jax.profiler.stop_trace()
            loop_starts.append(now)
            run_loop(srv, traffic, mix, rec, warm + win.loops, win, annotate)
            win.loops += 1
        win.seconds = time.perf_counter() - t_win
        win.compiles = compiles[0] - c0
        if trace and rec.traced:
            tr["t1"] = time.perf_counter()
            tr.update(loops=win.loops, ticks=len(win.tick_s),
                      tick_s=list(win.tick_s), scenario_s=list(win.scenario_s),
                      ingest_s=list(win.ingest_s))
            tracer.enabled = False
            rec.traced = False
            jax.profiler.stop_trace()
        log(f"window {win.seconds:.3f} s: {win.loops} loops, "
            f"{len(win.tick_s)} ticks, {len(win.scenario_s)} queries, "
            f"{win.compiles} programs compiled or loaded inside the window")

        # ---- after the window: memory, then the check ----
        stats = jax.devices()[0].memory_stats() or {}
        device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
        from bench import reference
        compared = reference.check(cell, traffic, srv, rec,
                                   stand_in=stand_in)
    finally:
        close = getattr(srv, "close", None)
        if close is not None:
            close()

    result = {"correct": all(c["value"] <= c["limit"]
                             for c in compared.values()),
              "attempted": win.attempted, "failed": win.failed}
    if trace:
        from bench import trace as tracemod
        reduced = tracemod.reduce(trace_dir, tracer, cell.chips)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        ctx = tracemod.Context(cell=cell, trace=reduced, window=win,
                               traced=tr, calls=rec.shapes, peaks=peaks,
                               spans=tracer.to_chrome_trace()["traceEvents"])
        metrics = {}
        for m in cell.per_layer:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = reduced.breakdown()
        tracemod.cleanup(trace_dir)
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = load_module(BENCH / "e2e" / f"{m['name']}.py").read(win)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["compared"] = compared
    return result
