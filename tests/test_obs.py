"""Observability layer: registry accuracy, thread-safety, trace format,
exporters, the bench-regression gate, and serving-loop non-interference."""
import glob
import importlib.util
import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.merinda import MerindaConfig
from repro.obs import (DEFAULT_LATENCY_BUCKETS, MetricRegistry, NULL_SPAN,
                       SnapshotWriter, Tracer, log_buckets)
from repro.systems.lotka_volterra import LotkaVolterra
from repro.systems.simulate import simulate_batch
from repro.twin.monitor import GuardConfig
from repro.twin.server import TwinServer, TwinServerConfig

jax.config.update("jax_platform_name", "cpu")


# --------------------------------------------------------------------- #
# histogram: bucket layout + quantile accuracy vs exact
# --------------------------------------------------------------------- #
def test_log_buckets_geometric():
    b = log_buckets(1e-3, 1.0, 10)
    assert b[0] == pytest.approx(1e-3) and b[-1] >= 1.0
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert all(r == pytest.approx(10 ** 0.1) for r in ratios)
    with pytest.raises(ValueError):
        log_buckets(1.0, 0.5)


@pytest.mark.parametrize("dist", ["lognormal", "uniform", "bimodal"])
def test_histogram_quantiles_match_exact(dist):
    """The bounded-memory histogram must track exact quantiles within one
    bucket ratio (the documented error bound) on realistic latency shapes."""
    rng = np.random.default_rng(0)
    if dist == "lognormal":
        xs = rng.lognormal(mean=-6.0, sigma=1.0, size=20000)   # ~ms scale
    elif dist == "uniform":
        xs = rng.uniform(1e-4, 1e-2, size=20000)
    else:
        # unequal modes so no tested quantile lands in the empty gap
        # between them (there, ANY in-gap value is a valid quantile and
        # the relative-error bound is meaningless)
        xs = np.concatenate([rng.normal(2e-3, 1e-4, 9000),
                             rng.normal(5e-2, 2e-3, 11000)]).clip(1e-5)
    reg = MetricRegistry()
    h = reg.histogram("t_seconds", bounds=DEFAULT_LATENCY_BUCKETS)
    for x in xs:
        h.observe(float(x))
    bucket_ratio = 10 ** (1 / 60) - 1            # per_decade=60 -> ~3.9%
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(xs, q))
        approx = h.quantile(q)
        assert abs(approx - exact) / exact < bucket_ratio + 0.01, \
            f"{dist} q={q}: {approx} vs exact {exact}"
    assert h.max == pytest.approx(float(xs.max()))
    assert h.sum == pytest.approx(float(xs.sum()), rel=1e-6)
    assert h.count == len(xs)


def test_histogram_overflow_bucket_uses_exact_max():
    reg = MetricRegistry()
    h = reg.histogram("t", bounds=(1.0, 2.0))
    for v in (0.5, 3.0, 500.0):
        h.observe(v)
    assert h.quantile(1.0) == pytest.approx(500.0)   # +inf bucket -> max
    assert h.quantile(0.0) > 0.0
    h.reset()
    assert h.count == 0 and h.quantile(0.5) == 0.0


# --------------------------------------------------------------------- #
# thread-safety: concurrent updates must not lose increments
# --------------------------------------------------------------------- #
def test_counter_and_histogram_concurrent_updates():
    reg = MetricRegistry()
    c = reg.counter("hits_total")
    h = reg.histogram("lat_seconds")
    n_threads, per = 8, 5000

    def work(k):
        for i in range(per):
            c.inc()
            h.observe(1e-4 * (1 + (i + k) % 7))

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per            # no lost increments
    assert h.count == n_threads * per


def test_counter_rejects_negative():
    c = MetricRegistry().counter("x_total")
    with pytest.raises(ValueError):
        c.inc(-1)


# --------------------------------------------------------------------- #
# registry semantics: families, labels, exposition, snapshot
# --------------------------------------------------------------------- #
def test_registry_get_or_create_and_type_conflict():
    reg = MetricRegistry()
    a = reg.counter("ticks_total", labels={"shard": "0"})
    b = reg.counter("ticks_total", labels={"shard": "0"})
    c = reg.counter("ticks_total", labels={"shard": "1"})
    assert a is b and a is not c                 # same child per label set
    with pytest.raises(ValueError):
        reg.gauge("ticks_total")                 # one name, one type


def test_expose_prometheus_text_format():
    reg = MetricRegistry()
    reg.counter("req_total", help="requests").inc(3)
    reg.gauge("depth", labels={"shard": "1"}).set(7)
    h = reg.histogram("lat_seconds", bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    text = reg.expose()
    assert "# TYPE req_total counter" in text
    assert "req_total 3" in text
    assert 'depth{shard="1"} 7' in text
    # cumulative buckets: 1 <= 0.1, 2 <= 1.0, 3 <= +Inf == _count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1.0"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text


def test_snapshot_is_json_able():
    reg = MetricRegistry()
    reg.counter("c_total", labels={"shard": "0"}).inc()
    reg.histogram("h_seconds").observe(0.01)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["c_total"]["kind"] == "counter"
    series = snap["h_seconds"]["series"][0]
    assert series["count"] == 1 and "p99" in series


# --------------------------------------------------------------------- #
# tracer: Chrome trace-event validity, sampling, ring bound, off-switch
# --------------------------------------------------------------------- #
def test_trace_json_is_valid_chrome_trace(tmp_path):
    tr = Tracer()
    with tr.span("tick", tick=1):
        with tr.span("flush"):
            pass
        with tr.span("guard", shard="0"):
            pass
    path = tmp_path / "trace.json"
    tr.write(path)
    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"tick", "flush", "guard"}
    for e in xs:                                  # required complete-event keys
        for k in ("name", "cat", "ph", "ts", "dur", "pid", "tid"):
            assert k in e
        assert isinstance(e["ts"], float) and e["dur"] >= 0
    assert any(m["name"] == "thread_name" for m in metas)
    # children nest inside the root span's window
    tick = next(e for e in xs if e["name"] == "tick")
    for e in xs:
        assert e["ts"] >= tick["ts"] - 1e-6
        assert e["ts"] + e["dur"] <= tick["ts"] + tick["dur"] + 1e-6
    assert tick["args"]["tick"] == 1
    assert next(e for e in xs if e["name"] == "guard")["args"]["shard"] == "0"


def test_tracer_sampling_keeps_subtrees_whole():
    tr = Tracer(sample_every=3)
    for i in range(9):
        with tr.span("root", i=i):
            with tr.span("child"):
                pass
    names = [e["name"] for e in tr.to_chrome_trace()["traceEvents"]
             if e["ph"] == "X"]
    # roots 0, 3, 6 sampled — each with its child (whole subtree or nothing)
    assert names.count("root") == 3 and names.count("child") == 3


def test_tracer_ring_bound_and_drop_count():
    tr = Tracer(capacity=4)
    for i in range(10):
        with tr.span("s", i=i):
            pass
    assert len(tr) == 4
    assert tr.dropped_events == 6
    kept = [e["args"]["i"] for e in tr.to_chrome_trace()["traceEvents"]
            if e["ph"] == "X"]
    assert kept == [6, 7, 8, 9]                   # newest survive


def test_tracer_disabled_is_noop():
    tr = Tracer(enabled=False)
    assert tr.span("x") is NULL_SPAN              # shared object, no alloc
    with tr.span("x"):
        pass
    assert len(tr) == 0


def _profiler_host_events(trace_dir) -> list[tuple]:
    """(name, start_ns, end_ns, stats) of every host-plane event."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    assert paths, "the profiler wrote no trace"
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: str(v) for k, v in ev.stats}))
    return out


def test_spans_mirrored_into_profiler_trace(tmp_path):
    """Recorded spans land on the profiler's host plane as `<cat>/<name>`
    with their args, nested as recorded; disabled and unsampled spans write
    nothing there."""
    on, off = Tracer(), Tracer(enabled=False)
    sampled = Tracer(sample_every=2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with on.span("tick", tick=7, shard="3"):
            with on.span("guard"):
                with on.span("sync", site="guard.scores"):
                    pass
            with on.span("pump_flush", cat="ingest"):
                pass
        with off.span("disabled_root"):
            pass
        for i in range(2):
            with sampled.span(f"sampled_{i}"):
                with sampled.span(f"child_{i}"):
                    pass
    finally:
        jax.profiler.stop_trace()
    events = {name: (s, e, stats)
              for name, s, e, stats in _profiler_host_events(tmp_path)}
    tick, guard, sync, pump = (events[n] for n in (
        "twin/tick", "twin/guard", "twin/sync", "ingest/pump_flush"))
    assert tick[2]["tick"] == "7" and tick[2]["shard"] == "3"
    assert sync[2]["site"] == "guard.scores"
    # nested as recorded
    assert tick[0] <= guard[0] <= sync[0] <= sync[1] <= guard[1] <= tick[1]
    assert guard[1] <= pump[0] <= pump[1] <= tick[1]
    assert "twin/sampled_0" in events and "twin/child_0" in events
    names = " ".join(events)
    for absent in ("disabled_root", "sampled_1", "child_1"):
        assert absent not in names
    # the Chrome export is unchanged: plain names, args as recorded
    xs = {e["name"]: e for e in on.to_chrome_trace()["traceEvents"]
          if e["ph"] == "X"}
    assert set(xs) == {"tick", "guard", "sync", "pump_flush"}
    assert xs["sync"]["args"] == {"site": "guard.scores"}


def test_obs_imports_and_records_without_jax():
    """`repro.obs` stays importable, and its tracer usable, where JAX
    cannot be imported."""
    code = "\n".join([
        "import sys",
        "sys.modules['jax'] = None            # `import jax` now fails",
        "import repro.obs as obs",
        "tr = obs.Tracer()",
        "with tr.span('tick', tick=1):",
        "    with tr.span('sync', site='x'):",
        "        pass",
        "assert len(tr) == 2",
        "assert obs.Tracer(enabled=False).span('x') is obs.NULL_SPAN",
    ])
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = {**os.environ, "PYTHONPATH": src}
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)


# --------------------------------------------------------------------- #
# exporters
# --------------------------------------------------------------------- #
def test_snapshot_writer_period_gate_and_atomic_write(tmp_path):
    reg = MetricRegistry()
    reg.counter("c_total").inc(5)
    tr = Tracer()
    path = tmp_path / "snap.json"
    w = SnapshotWriter(reg, path, every_s=3600.0, tracer=tr)
    assert w.maybe_write() is True
    assert w.maybe_write() is False               # inside the period
    assert w.writes == 1
    doc = json.loads(path.read_text())
    assert doc["metrics"]["c_total"]["series"][0]["value"] == 5
    assert doc["trace"]["enabled"] is True
    assert not path.with_suffix(".json.tmp").exists()


# --------------------------------------------------------------------- #
# bench-regression gate (tools/check_bench.py)
# --------------------------------------------------------------------- #
def _load_check_bench():
    root = Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "check_bench", root / "tools" / "check_bench.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("check_bench", mod)
    spec.loader.exec_module(mod)
    return mod


def test_check_bench_flags_latency_and_violations():
    cb = _load_check_bench()
    base = [{"twins": "64", "shards": "1", "p50_ms": "10.0",
             "p99_ms": "20.0", "violations": "0"}]
    fresh = [{"twins": "64", "shards": "1", "p50_ms": "14.0",
              "p99_ms": "20.5", "violations": "1"}]
    reg, checked, skipped = cb.compare_rows(fresh, base, tolerance=0.25)
    assert checked == 1 and not skipped
    assert len(reg) == 2                          # p50 +40%, violations +1
    assert any("p50_ms" in r for r in reg)
    assert any("violations" in r for r in reg)


def test_check_bench_skips_new_configs_and_non_numeric():
    cb = _load_check_bench()
    base = [{"twins": "64", "p50_ms": "10.0", "violations": "0",
             "trace_overhead_pct": "n/a"}]
    fresh = [{"twins": "64", "p50_ms": "10.2", "violations": "0",
              "trace_overhead_pct": "n/a"},            # within tolerance
             {"twins": "128", "p50_ms": "99.0", "violations": "9",
              "trace_overhead_pct": "n/a"}]            # no baseline -> skip
    reg, checked, skipped = cb.compare_rows(fresh, base, tolerance=0.25)
    assert checked == 1 and len(skipped) == 1 and reg == []


# --------------------------------------------------------------------- #
# non-interference: tracing must not change serving behaviour
# --------------------------------------------------------------------- #
def _run_server(ys, us, dt, tracer, query=False):
    cfg = TwinServerConfig(
        merinda=MerindaConfig(n=2, m=0, order=2, hidden=8, head_hidden=8,
                              n_active=4, dt=dt),
        max_twins=64, refit_slots=2, capacity=128, window=16, stride=8,
        windows_per_twin=4, steps_per_tick=1, deploy_after=2,
        min_residency=2, max_residency=6, guard=GuardConfig(window=16),
        seed=0)
    srv = TwinServer(cfg, tracer=tracer)
    # a zero model serves every twin, so the guard scores from the start
    srv.deploy_many(range(64), np.zeros((2, srv.fleet.model.lib.size)))
    chunk = 10
    reports = []
    for t in range(8):
        for i in range(64):
            srv.ingest(i, ys[i, t * chunk:(t + 1) * chunk],
                       us[i, t * chunk:(t + 1) * chunk])
        reports.append(srv.tick())
    answer = srv.scenario(0, 6, np.zeros((2, 6, 0)), k=2) if query else None
    return reports, answer


def _span_paths(events) -> list[tuple[str, ...]]:
    """Each recorded span as its chain of names from the root, in start
    order (`sync` spans as `sync:<site>`); nesting is containment on one
    thread."""
    xs = sorted((e for e in events if e["ph"] == "X"),
                key=lambda e: (e["tid"], e["ts"], -e["dur"]))
    out, stack = [], []
    for e in xs:
        name = (f"sync:{e['args']['site']}" if e["name"] == "sync"
                else e["name"])
        while stack and not (stack[-1][0]["tid"] == e["tid"]
                             and e["ts"] + e["dur"]
                             <= stack[-1][0]["ts"] + stack[-1][0]["dur"]
                             + 1e-3):
            stack.pop()
        path = (stack[-1][1] if stack else ()) + (name,)
        out.append(path)
        stack.append((e, path))
    return out


# every place where a promoting tick waits on the device, in order, with the
# stage span that holds it: the plan's scores in one read, the train loss and
# both promotion scores in another
_TICK_SYNCS = [
    ("tick", "guard", "sync:guard.scores"),
    ("tick", "schedule", "plan", "sync:plan.scores"),
    ("tick", "refit", "promote", "sync:refit.result"),
    ("tick", "refit", "sync:tick.block"),
]
# a tick that trains and promotes nothing reads its loss in the train span
_NO_PROMOTE_LOSS = ("tick", "refit", "train", "sync:refit.loss")


def test_tracing_on_off_identical_tick_reports():
    """64-twin serving run twice — tracing off vs every-tick spans — must
    produce IDENTICAL TickReports (scheduling, losses, guard events); the
    tracer only measures, never steers."""
    sys_ = LotkaVolterra()
    tr = simulate_batch(sys_, jax.random.PRNGKey(1), batch=64, horizon=90,
                        noise_std=0.002)
    ys, us = np.asarray(tr.ys_noisy), np.asarray(tr.us)

    off, off_answer = _run_server(ys, us, sys_.spec.dt,
                                  Tracer(enabled=False), query=True)
    tracer = Tracer(sample_every=1)
    on, on_answer = _run_server(ys, us, sys_.spec.dt, tracer, query=True)

    assert len(tracer) > 0                        # spans actually recorded
    for a, b in zip(off, on):
        assert a.tick == b.tick
        assert a.admitted == b.admitted
        assert a.evicted == b.evicted
        assert a.released == b.released
        assert a.n_active == b.n_active
        assert a.n_twins == b.n_twins
        assert a.n_guarded == b.n_guarded
        assert [(e.kind, e.twin_id) for e in a.events] == \
               [(e.kind, e.twin_id) for e in b.events]
        if a.loss is None:
            assert b.loss is None
        else:
            assert a.loss == pytest.approx(b.loss, rel=1e-6)
    np.testing.assert_array_equal(off_answer.ys, on_answer.ys)
    np.testing.assert_array_equal(off_answer.confidence, on_answer.confidence)
    names = {e["name"] for e in tracer.to_chrome_trace()["traceEvents"]
             if e["ph"] == "X"}
    assert {"tick", "flush", "guard", "schedule", "refit"} <= names

    # each traced tick's waits on the device, in order, each in its stage
    paths = _span_paths(tracer.to_chrome_trace()["traceEvents"])
    ticks, sites = [], None
    for path in paths:
        if path == ("tick",):
            sites = []
            ticks.append(sites)
        elif path[0] == "tick" and path[-1].startswith("sync:"):
            sites.append(path)
    assert len(ticks) == len(on)
    assert _TICK_SYNCS in ticks                  # a promoting tick
    # a training tick without a promotion reads its loss on its own
    no_promote = [t for t in ticks if _NO_PROMOTE_LOSS in t]
    assert no_promote
    assert all(("tick", "refit", "promote", "sync:refit.result") not in t
               for t in no_promote)
    assert all(t[-1] == ("tick", "refit", "sync:tick.block") for t in ticks)
    assert ("tick", "flush", "apply") in paths
    assert ("tick", "schedule", "admit") in paths
    # the query's one read back sits in its rollout, after the gather
    query = [p for p in paths if p[0] == "scenario"]
    assert query == [("scenario",), ("scenario", "gather"),
                     ("scenario", "rollout"),
                     ("scenario", "rollout", "sync:scenario.result")]
